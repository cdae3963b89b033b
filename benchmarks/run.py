"""Benchmark driver: one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [names...]
Environment: BENCH_ROUNDS / BENCH_CLIENTS / BENCH_COHORT / BENCH_BATCH.

``BENCHES`` is the module-level registry (name -> module, each exposing
``run()``); ``ARTIFACTS`` maps every committed ``experiments/bench/*.json``
to the bench that regenerates it.  ``tests/test_benchmarks_registry.py``
audits both against the scripts on disk and the committed artifacts, so a
new benchmark (or a new committed artifact) that skips the registry fails
tier-1 instead of silently falling out of ``python -m benchmarks.run``.
"""

import importlib
import sys
import time

#: name -> module path (lazy: importing a bench may touch jax device state).
BENCHES = {
    "table1_iid": "benchmarks.table1_iid",
    "table2_adaptation": "benchmarks.table2_adaptation",
    "table3_noniid": "benchmarks.table3_noniid",
    "table4_ablation": "benchmarks.table4_ablation",
    "fig3_pvt_stability": "benchmarks.fig3_pvt_stability",
    "fig4_ppq_vs_apq": "benchmarks.fig4_ppq_vs_apq",
    "memory_measured": "benchmarks.memory_measured",
    "kernels_micro": "benchmarks.kernels_micro",
    "roofline_report": "benchmarks.roofline_report",
    "api_wire": "benchmarks.api_wire",
    "compress_pareto": "benchmarks.compress_pareto",
    "cohort_scale": "benchmarks.cohort_scale",
    "async_scale": "benchmarks.async_scale",
    "population_scale": "benchmarks.population_scale",
}

#: committed experiments/bench artifact -> the bench that regenerates it.
ARTIFACTS = {
    "async_scale.json": "async_scale",
    "compress_strategies.json": "compress_pareto",
    "kernels_micro.json": "kernels_micro",
    "population_scale.json": "population_scale",
}


def run_bench(name: str) -> None:
    importlib.import_module(BENCHES[name]).run()


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    names = sys.argv[1:] or list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        raise SystemExit(f"unknown bench(es) {unknown}; known: "
                         f"{sorted(BENCHES)}")
    for name in names:
        t0 = time.time()
        print(f"\n######## {name} ########")
        run_bench(name)
        print(f"[{name}: {time.time() - t0:.0f}s]")


if __name__ == "__main__":
    main()
