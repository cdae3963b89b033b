"""The program marks its own layers for the profiler (DESIGN.md §15).

  * the round program's four ``jax.named_scope``s cover every operation of
    the round and never nest in one another (fused and unfused paths);
  * a serving step materializes its weights under ``omc.materialize``;
  * a codec round trip under the profiler writes its ``omc.codec.*`` host
    spans, one ``omc.codec.d2h`` for every device read the code makes, and a
    ``Tracer`` span recorded alongside lines up with its profiler event.
"""

import contextlib
import re
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import codecs
from repro.core.omc import OMCConfig
from repro.core.policy import QuantizePolicy
from repro.core.store import compress_tree, is_compressed
from repro.data.synthetic import make_frame_task
from repro.federated import engine, materialize, simulate
from repro.federated.cohort import CohortPlan
from repro.federated.round import make_serve_fns
from repro.federated.state import compress_params
from repro.models import conformer as cf
from repro.models import transformer as tr
from repro.obs import Obs, null_span

ROUND_SCOPES = (engine.DECOMPRESS, engine.CLIENT, engine.TRANSPORT_ENCODE,
                engine.SERVER_STEP)
OMC = OMCConfig.parse("S1E3M7")


def _op_names(lowered) -> list:
    return re.findall(r'op_name="([^"]*)"',
                      lowered.as_text(dialect="hlo", debug_info=True))


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "unfused"])
def test_round_program_scopes_cover_it_side_by_side(fused):
    """Every operation of the round's own computation (``jit(round_fn)/...``;
    called computations inherit their caller's scope) lies under exactly one
    of the four scopes, and each scope the path runs appears."""
    cfg = cf.ConformerConfig(n_layers=1, d_model=16, n_heads=2, d_ff=32,
                             n_classes=8, d_in=4)
    task = make_frame_task(d_in=cfg.d_in, n_classes=cfg.n_classes, seq_len=12,
                           num_clients=8)
    specs = cf.param_specs(cfg)
    spec = engine.CohortSpec(CohortPlan(num_clients=8, cohort_size=4))
    fn = engine.make_round_fn(cf, cfg, specs, OMC,
                              simulate.SimConfig(local_steps=1, client_lr=0.1),
                              spec, lambda c, r, s: task.batch(c, r, s, 2),
                              fused_agg=fused)
    storage = jax.eval_shape(
        lambda k: compress_params(cf.init(k, cfg), specs, OMC),
        jax.random.PRNGKey(0))
    sds = jax.ShapeDtypeStruct
    names = [n for n in _op_names(fn.lower(
        storage, [sds((4,), jnp.int32)], sds((4,), jnp.bool_), sds((), jnp.int32)))
        if n.startswith("jit(round_fn)/")]
    assert names
    held = [[s for s in ROUND_SCOPES if s + "/" in n] for n in names]
    assert all(len(h) == 1 for h in held), \
        [n for n, h in zip(names, held) if len(h) != 1][:5]
    want = set(ROUND_SCOPES) - ({engine.TRANSPORT_ENCODE} if not fused else set())
    assert {h[0] for h in held} == want


def test_serve_step_materializes_under_its_scope():
    cfg = tr.TransformerConfig(n_layers=2, d_model=32, n_heads=2, n_kv_heads=1,
                               d_ff=64, vocab=128)
    storage = jax.eval_shape(
        lambda k: compress_params(tr.init(k, cfg), tr.param_specs(cfg), OMC),
        jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: tr.init_decode_state(cfg, 2, 8))
    _, decode = make_serve_fns(tr, cfg)
    names = _op_names(jax.jit(decode).lower(
        storage, cache, jax.ShapeDtypeStruct((2, 1), jnp.int32)))
    scoped = [n for n in names if materialize.MATERIALIZE in n]
    assert scoped and any("convert" in n or "mul" in n for n in scoped)


@contextlib.contextmanager
def _time_limit(seconds: int):
    def fail(*_):
        raise TimeoutError(f"over its {seconds} s limit")

    old = signal.signal(signal.SIGALRM, fail)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _host_events(xplane: str):
    """``[(name, absolute start ns)]`` of the host plane's ``omc.*`` events:
    the trace's times count from its ``profile_start_time``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane)
    stats = [dict(p.stats) for p in data.planes]
    start = next(int(s["profile_start_time"]) for s in stats
                 if "profile_start_time" in s)
    return [(e.name, start + int(e.start_ns)) for p in data.planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events if e.name.startswith("omc.")]


def test_codec_round_trip_spans_under_the_profiler(tmp_path, monkeypatch):
    """Chunks of 256 fields, so leaves split, and still each leaf crosses
    once each way: a compressed leaf is read three times to encode (its
    packed words, scale, bias) and uploaded three times to decode; a raw
    leaf once each way; each chunk is one pack and one unpack call."""
    with _time_limit(60):
        monkeypatch.setattr(codecs, "_CHUNK_FIELDS", 256)
        key = jax.random.PRNGKey(0)
        tree = dict(w=jax.random.normal(key, (40, 32)),
                    v=jax.random.normal(jax.random.fold_in(key, 1), (16, 8)),
                    scale=jnp.ones((32,)))
        storage = compress_tree(tree, OMC.fmt, QuantizePolicy(min_size=64))
        leaves = jax.tree_util.tree_leaves(storage, is_leaf=is_compressed)
        chunks = sum(-(-int(np.prod(l.codes.shape)) // 256)
                     for l in leaves if is_compressed(l))
        n_omc = sum(map(is_compressed, leaves))
        n_raw = len(leaves) - n_omc
        assert n_omc == 2 and n_raw == 1 and chunks == 6
        codecs.decode_payload(codecs.encode_payload(storage))  # compile first

        obs = Obs("codec", out_dir=str(tmp_path))
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            with null_span(obs, "probe"):
                decoded, _ = codecs.decode_payload(codecs.encode_payload(storage))
                jax.block_until_ready(decoded)
        finally:
            jax.profiler.stop_trace()
        events = _host_events(str(sorted((tmp_path / "trace").rglob("*.xplane.pb"))[-1]))
        count = {}
        for name, _ in events:
            count[name] = count.get(name, 0) + 1
        assert count["omc.codec.d2h"] == 3 * n_omc + n_raw
        assert count["omc.codec.h2d"] == 3 * n_omc + n_raw
        assert count["omc.codec.pack"] == count["omc.codec.unpack"] == chunks
        assert count["omc.codec.encode"] == count["omc.codec.decode"] == 1
        (probe,) = obs.tracer.spans(name="probe")
        (start,) = [t for n, t in events if n == "omc.probe"]
        assert abs(probe.ts * 1e9 - start) < 1e6
