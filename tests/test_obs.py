"""Telemetry subsystem gates (DESIGN.md §15).

The contract under test:

  * **off-by-default**: ``obs=None`` is a strict no-op — same program
    signatures, no spans, no records (every other tier-1 gate runs with
    obs off, so this is implicitly re-proven suite-wide);
  * **enabling metrics changes nothing**: with a live ``Obs`` at cohort 8
    the trained trees and byte ledgers are bit/byte-identical to
    ``obs=None`` on the loop, engine, and async paths — metric bundles
    are assembled eagerly on the host AFTER each compiled step, never
    inside it;
  * tracer span ordering on both clocks (wall + virtual under a
    ``FixedTrace``), JSONL/Perfetto export schema roundtrip, and the
    ``python -m repro.obs.report`` CLI rendering a run without error.
"""

import io
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.omc import OMCConfig
from repro.data.synthetic import make_frame_task
from repro.federated import async_engine, engine, simulate, traces
from repro.federated.cohort import CohortPlan
from repro.models import conformer as cf
from repro.obs import Obs, null_span
from repro.obs import metrics as obs_metrics
from repro.obs import report as obs_report
from repro.obs.export import (
    JSONL_KINDS,
    read_jsonl,
    span_record,
    to_perfetto,
)
from repro.obs.log import Logger
from repro.obs.trace import VIRTUAL, WALL, Span, Tracer
from repro.scale import ShardLayout, run_training_sharded

CFG = cf.ConformerConfig(
    n_layers=1, d_model=16, n_heads=2, d_ff=32, n_classes=8, d_in=4
)
OMC = OMCConfig.parse("S1E3M7")
PLAN = CohortPlan(num_clients=16, cohort_size=8, failure_rate=0.25)
TASK = make_frame_task(d_in=CFG.d_in, n_classes=CFG.n_classes, seq_len=12,
                       num_clients=PLAN.num_clients)
DATA_FN = lambda c, r, s: TASK.batch(c, r, s, 4)
SIM = simulate.SimConfig(local_steps=2, client_lr=0.1)
KEY = jax.random.PRNGKey(0)


def _assert_bit_identical(a_storage, b_storage):
    la = jax.tree_util.tree_leaves(a_storage)
    lb = jax.tree_util.tree_leaves(b_storage)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _assert_ledgers_equal(h0, h1):
    assert len(h0) == len(h1)
    for r0, r1 in zip(h0, h1):
        for k in ("down_bytes", "up_bytes", "loss", "cohort", "dropped"):
            if k in r0 or k in r1:
                assert r0.get(k) == r1.get(k), (k, r0, r1)


# ---------------------------------------------------------------------------
# The §15 acceptance gate: metrics-enabled == metrics-disabled, bitwise
# ---------------------------------------------------------------------------


def _run_loop(obs):
    return simulate.run_training(cf, CFG, OMC, SIM, PLAN, DATA_FN, KEY,
                                 num_rounds=2, eval_every=100, wire=True,
                                 obs=obs)


def _run_engine(obs):
    return engine.run_training_vectorized(
        cf, CFG, OMC, SIM, engine.CohortSpec(PLAN), DATA_FN, KEY,
        num_rounds=2, eval_every=100, obs=obs,
    )


def _run_async(obs):
    st, hist, _ = async_engine.run_async_training(
        cf, CFG, OMC, SIM, async_engine.AsyncConfig(buffer_goal=8),
        traces.ParetoTrace(seed=1), DATA_FN, KEY, num_clients=16,
        flushes=2, wire=True, obs=obs,
    )
    return st, hist


@pytest.mark.tier1
@pytest.mark.parametrize("path,run", [
    ("loop", _run_loop),
    ("engine", _run_engine),
    ("async", _run_async),
], ids=["loop", "engine", "async"])
def test_metrics_on_is_bit_identical(tmp_path, path, run):
    """Cohort 8, two rounds/flushes: enabling obs must not move one bit of
    trained state nor one byte of the wire ledgers (DESIGN.md §15)."""
    s0, h0 = run(None)
    obs = Obs(run_name=path, out_dir=str(tmp_path))
    s1, h1 = run(obs)
    _assert_bit_identical(s0, s1)
    _assert_ledgers_equal(h0, h1)
    # and the run actually produced telemetry, not a silent no-op
    kind = "flush" if path == "async" else "round"
    recs = obs.sink.records(kind)
    assert len(recs) == 2
    assert all(np.isfinite(r["update_norm"]) for r in recs)


@pytest.mark.tier1
def test_metrics_on_is_bit_identical_sharded(tmp_path):
    """The streamed path: chunk metric partials ride the fixed-capacity
    program as extra outputs; main outputs must stay bit-identical."""
    def run(obs):
        return run_training_sharded(
            cf, CFG, OMC, SIM, PLAN, ShardLayout(16, 2), DATA_FN, KEY, 2,
            capacity=3, obs=obs,
        )

    s0, h0, _ = run(None)
    obs = Obs(run_name="sharded", out_dir=str(tmp_path))
    s1, h1, _ = run(obs)
    _assert_bit_identical(s0, s1)
    _assert_ledgers_equal(h0, h1)
    recs = obs.sink.records("round")
    assert len(recs) == 2
    assert all("update_sq_wsum" in r for r in recs)  # folded chunk partials


def test_round_record_schema(tmp_path):
    """Engine round records carry the §15 bundle: loss, alive, update and
    per-leaf quantization-error norms, plus the byte ledger fields."""
    obs = Obs(run_name="schema", out_dir=str(tmp_path))
    _run_engine(obs)
    rec = obs.sink.records("round")[0]
    assert rec["kind"] == "round"
    for k in ("round", "loss", "alive", "update_norm", "qerr_norm",
              "down_bytes", "up_bytes"):
        assert k in rec, rec.keys()
    assert any(k.startswith("qerr/") for k in rec)  # per-leaf series
    # wall span per round, including the compile-bearing round 0
    assert len(obs.tracer.spans(WALL, "round")) == 2


# ---------------------------------------------------------------------------
# Tracer: two clocks
# ---------------------------------------------------------------------------


def test_tracer_wall_spans_nest_and_order():
    tr = Tracer()
    before = time.time_ns() / 1e9
    with tr.span("outer", idx=0) as args:
        with tr.span("inner"):
            pass
        args["bytes"] = 123
    with tr.span("next"):
        pass
    inner, outer, nxt = tr.spans()
    assert (inner.name, outer.name) == ("inner", "outer")
    assert outer.args == {"idx": 0, "bytes": 123}
    assert outer.ts <= inner.ts and inner.end <= outer.end + 1e-9
    assert all(s.cat == WALL for s in tr.spans())
    # the caused-by parent, and the profiler's absolute clock
    assert (inner.parent, outer.parent, nxt.parent) == ("outer", None, None)
    assert before <= outer.ts <= nxt.end <= time.time_ns() / 1e9
    assert span_record(inner)["parent"] == "outer"


def test_tracer_virtual_vs_wall_under_fixed_trace(tmp_path):
    """FixedTrace(latency=2): every async client round is a virtual span of
    exactly that duration, stacked deterministically on the virtual clock;
    wall flush spans live on the wall clock, independent of it."""
    obs = Obs(run_name="fixed", out_dir=str(tmp_path))
    async_engine.run_async_training(
        cf, CFG, OMC, SIM, async_engine.AsyncConfig(buffer_goal=4),
        traces.FixedTrace(latency=2.0), DATA_FN, KEY, num_clients=4,
        flushes=2, wire=False, obs=obs,
    )
    v = obs.tracer.spans(VIRTUAL, "client_round")
    assert len(v) >= 8  # 4 clients x >= 2 completed rounds
    assert all(s.dur == pytest.approx(2.0) for s in v)
    # virtual timestamps advance with the simulated clock, in event order
    ts = [s.ts for s in v]
    assert ts == sorted(ts)
    w = obs.tracer.spans(WALL, "flush")
    assert len(w) == 2
    # the two clocks never mix categories
    assert not obs.tracer.spans(WALL, "client_round")
    summary = obs_report.span_summary(
        [span_record(s) for s in obs.tracer.spans()])
    assert summary["virtual:client_round"]["count"] == len(v)
    assert summary["virtual:client_round"]["mean_s"] == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# Export: JSONL + Perfetto schema
# ---------------------------------------------------------------------------


def test_export_roundtrip_schema(tmp_path):
    obs = Obs(run_name="export", out_dir=str(tmp_path))
    obs.record("round", {"loss": jnp.float32(1.5)}, round=0, up_bytes=10)
    with obs.span("encode_payload", bytes=42):
        pass
    obs.vspan("client_round", 1.0, 2.0, client=3)
    paths = obs.flush()

    records = read_jsonl(paths["jsonl"])
    assert all(r["kind"] in JSONL_KINDS for r in records)
    kinds = [r["kind"] for r in records]
    assert "meta" in kinds and "round" in kinds and "span" in kinds
    meta = records[kinds.index("meta")]
    assert "dispatch_counts" in meta  # kernels.ops counters ride the meta
    rnd = records[kinds.index("round")]
    assert rnd["loss"] == 1.5 and rnd["up_bytes"] == 10  # jax scalar -> float

    with open(paths["perfetto"]) as f:
        doc = json.load(f)
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    names = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert names == {"wall clock", "virtual clock"}
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"encode_payload", "client_round"}
    virt = next(e for e in xs if e["name"] == "client_round")
    assert virt["pid"] == 2 and virt["ts"] == 1.0 * 1e6
    assert virt["dur"] == 2.0 * 1e6
    # span_record <-> Span: seconds preserved through the JSONL form
    sp = Span("x", ts=0.5, dur=0.25, args={"n": 1})
    rec = span_record(sp)
    assert rec == {"kind": "span", "name": "x", "cat": WALL, "ts": 0.5,
                   "dur": 0.25, "args": {"n": 1.0}}
    assert to_perfetto([sp])["traceEvents"][-1]["dur"] == 0.25 * 1e6


def test_null_span_and_maybe_span_are_noops():
    """``null_span`` is the one helper (``maybe_span`` merged into it): no
    ``Tracer`` record without an ``Obs`` or with tracing off, one with."""
    with null_span(None, "anything", a=1) as args:
        args["b"] = 2  # must accept writes like the live version
    assert args == {"a": 1, "b": 2}
    with null_span(Obs(trace=False), "anything", step=3) as args:
        pass
    obs = Obs()
    with null_span(obs, "live", step=0):
        with obs.span("child"):
            pass
    child, live = obs.tracer.spans()
    assert (live.name, child.parent) == ("live", "live")


def test_logger_quiet_and_structured(tmp_path):
    obs = Obs(run_name="log", out_dir=str(tmp_path), trace=False)
    err = io.StringIO()
    log = Logger(quiet=False, obs=obs, stream=err)
    log.info("hello", n=3)
    log.warn("careful")
    assert "[info] hello n=3" in err.getvalue()
    assert "[warn] careful" in err.getvalue()
    quiet_err = io.StringIO()
    Logger(quiet=True, obs=obs, stream=quiet_err).info("silent", n=4)
    assert quiet_err.getvalue() == ""  # text suppressed...
    logs = obs.sink.records("log")
    assert [r["msg"] for r in logs] == ["hello", "careful", "silent"]
    assert logs[-1]["n"] == 4  # ...but the structured record still lands


# ---------------------------------------------------------------------------
# Metric math
# ---------------------------------------------------------------------------


def test_server_round_bundle_matches_manual_norms():
    specs = cf.param_specs(CFG)
    params = cf.init(KEY, CFG)
    storage = engine.compress_params(params, specs, OMC)
    old_f32 = jax.tree_util.tree_map(jnp.asarray, params)
    # a synthetic "mean" one small step away from the server
    mean = jax.tree_util.tree_map(lambda x: x + 0.01, old_f32)
    new_storage = engine.apply_server_step(old_f32, mean, specs, OMC, 1.0)
    bundle = obs_metrics.server_round_bundle(specs, old_f32, new_storage,
                                             mean, 1.0)
    assert float(bundle["update_norm"]) > 0
    assert float(bundle["qerr_norm"]) >= 0
    per_leaf = [v for k, v in bundle.items() if k.startswith("qerr/")]
    assert per_leaf
    total = float(jnp.sqrt(sum(jnp.asarray(v) ** 2 for v in per_leaf)))
    assert total == pytest.approx(float(bundle["qerr_norm"]), rel=1e-5)
    # degraded form (fused paths): mean unavailable -> no qerr series
    degraded = obs_metrics.server_round_bundle(specs, old_f32, new_storage,
                                               None, 1.0)
    assert "qerr_norm" not in degraded and "update_norm" in degraded


def test_fold_partial_bundles():
    a = {"update_sq_wsum": jnp.float32(1.0)}
    b = {"update_sq_wsum": jnp.float32(2.5)}
    acc = obs_metrics.fold_partial_bundles(None, a)
    acc = obs_metrics.fold_partial_bundles(acc, b)
    assert float(acc["update_sq_wsum"]) == pytest.approx(3.5)


# ---------------------------------------------------------------------------
# Report CLI
# ---------------------------------------------------------------------------


def test_report_cli_smoke(tmp_path, capsys):
    obs = Obs(run_name="cli", out_dir=str(tmp_path))
    _run_engine(obs)
    obs.record("serve", queries=16, query_ms_p50=1.0, query_ms_p95=2.0,
               swap_ms_mean=3.0, swaps=2)
    # guarantee at least one kernel dispatch count in the meta record
    from repro.kernels import ops as kernel_ops
    kernel_ops.pack_bits(jnp.arange(521, dtype=jnp.uint32) & np.uint32(0x7), 3)
    paths = obs.flush()
    assert obs_report.main([paths["jsonl"]]) == 0
    out = capsys.readouterr().out
    for section in ("rounds", "serve", "spans", "dispatch"):
        assert section in out, out
    assert "qerr_norm" in out and "wire_mb" in out


def test_report_cli_missing_file():
    assert obs_report.main(["/nonexistent/run.obs.jsonl"]) != 0
