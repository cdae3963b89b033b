"""DeepSeek-V2 (``models/deepseek_v2``) against a plain float32 reference at
a small size on seeded random weights: serving through ``ServeSession``
(prefill, then decode through the latent cache) against the reference's
full forward pass; absorbed against expanded latent attention; the no-drop
expert dispatch against a per-expert dense loop; the held-expert shares of
a layer against the uncut layer; YaRN against its closed form; the marks in
the lowered decode program; and the serving session's donated caches.

On the CPU both sides compute in float32 at full precision, so what
separates program and reference is the order of summation and the OMC
affine's solve: about 1e-6 to 1e-5 on logits of order 1 (widths 64-128).
The tolerances below sit an order of magnitude above that and far below
what a wrong rotary pair, scale, gate or missing expert moves (1e-2 and
more)."""

import importlib
import importlib.util
import math
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.session import ServeSession
from repro.core.omc import OMCConfig
from repro.federated.round import make_serve_fns
from repro.federated.state import compress_params
from repro.models import deepseek_v2 as ds
from repro.models import moe
from repro.models import transformer as tr
from repro.models.common import yarn_inv_freq
from repro.models.registry import get_family, is_servable

BENCH_REFERENCE = Path(__file__).resolve().parents[1] / "bench" / "reference"
YARN = dict(type="yarn", factor=40, original_max_position_embeddings=4096, beta_fast=32,
            beta_slow=1, mscale=0.707, mscale_all_dim=0.707)
SMALL = dict(hidden_size=64, num_hidden_layers=3, num_attention_heads=4, vocab_size=256,
             intermediate_size=96, moe_intermediate_size=32, router_experts=8,
             num_experts_per_tok=2, n_shared_experts=2, held_experts=(0, 1, 2, 3),
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             first_k_dense_replace=1, norm_topk_prob=False, routed_scaling_factor=1.0,
             rope_theta=10000.0, rope_scaling=YARN, rms_norm_eps=1e-6)
CFG = ds.DeepseekV2Config(**SMALL)
OMC = OMCConfig.parse("S1E3M7", pvt=True)


def _reference():
    """``bench/reference/deepseek_v2.py``, loaded by path as part of its
    package (it imports its siblings)."""
    name = "deepseek_bench_reference"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, BENCH_REFERENCE / "__init__.py",
            submodule_search_locations=[str(BENCH_REFERENCE)])
        pkg = importlib.util.module_from_spec(spec)
        sys.modules[name] = pkg
        spec.loader.exec_module(pkg)
    return (importlib.import_module(f"{name}.deepseek_v2"),
            importlib.import_module(f"{name}.weights"),
            importlib.import_module(f"{name}.omc"))


def test_family_is_registered_and_servable():
    assert get_family("deepseek_v2") is ds
    assert is_servable("deepseek_v2")


def test_serving_matches_the_reference_forward():
    """Prefill, then decode through the latent cache, from S1E3M7 codes:
    logits at every served position within 1e-4 of the reference's full
    forward pass (float32, highest precision, each layer's weights drawn
    from the seed and rounded as the program stores them).  Measured 6e-6
    on logits up to 3.6; rotating halves in place of adjacent pairs moves
    them by 2."""
    ref, weights, ref_omc = _reference()
    config = dict(SMALL, held_experts=list(SMALL["held_experts"]), rope_scaling=dict(YARN))
    key = jax.random.PRNGKey(7)
    storage = compress_params(weights.init(key, ref.layout(config)), ds.param_specs(CFG), OMC)
    b, p, new = 2, 11, 6
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(8), (b, p + new), 0,
                                           CFG.vocab_size))
    sess = ServeSession(ds, CFG, storage)
    cache, logits = sess.prefill(dict(tokens=jnp.asarray(tokens[:, :p])),
                                 sess.init_cache(b, p + new, dtype=jnp.float32))
    got = [logits[:, -1]]
    for j in range(new - 1):
        cache, logits = sess.decode_step(cache, jnp.asarray(tokens[:, p + j:p + j + 1]))
        got.append(logits[:, -1])
    got = np.stack(got, 1)
    want = np.asarray(ref.Forward(config, key, ref_omc.Fmt("S1E3M7"))(tokens, p - 1, new))
    assert np.abs(want).max() > 0.1  # logits that say something
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)


def _attention_weights(key):
    return ds._moe_init(key, CFG)


def test_absorbed_decode_equals_expanded_attention():
    """The last position through the cache (``W_UK``/``W_UV`` absorbed)
    against the same position of the expanded causal attention; float32
    on both paths, so within 1e-5."""
    w = _attention_weights(jax.random.PRNGKey(1))
    b, s = 2, 13
    h = jax.random.normal(jax.random.PRNGKey(2), (b, s, CFG.hidden_size))
    positions = jnp.arange(s, dtype=jnp.int32)[None]
    q_nope, q_pe, c, k_pe = ds.mla_inputs(CFG, w, h, positions)
    full = ds.mla_expanded(CFG, w, q_nope, q_pe, c, k_pe, positions)
    buf = 20  # slots past the cached positions hold garbage and must be masked
    c_cache = jax.random.normal(jax.random.PRNGKey(3), (b, CFG.kv_lora_rank, buf))
    pe_cache = jax.random.normal(jax.random.PRNGKey(4), (b, CFG.qk_rope_head_dim, buf))
    c_cache = c_cache.at[:, :, :s - 1].set(jnp.swapaxes(c[:, :s - 1], 1, 2))
    pe_cache = pe_cache.at[:, :, :s - 1].set(jnp.swapaxes(k_pe[:, :s - 1], 1, 2))
    last = slice(s - 1, s)
    got = ds.mla_absorbed(CFG, w, q_nope[:, last], q_pe[:, last], c[:, last], k_pe[:, last],
                          c_cache, pe_cache, jnp.int32(s - 1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(full[:, last]), atol=1e-5, rtol=1e-5)


def _dense_loop(x, gate, ids, w1, w3, w2, held):
    """Every held expert over every token, weighted by the token's gate for
    it (zero where it was not chosen)."""
    y = jnp.zeros_like(x)
    for j, e in enumerate(held):
        g = jnp.where(ids == e, gate, 0.0).sum(-1)
        y = y + g[:, None] * ((jax.nn.silu(x @ w1[j]) * (x @ w3[j])) @ w2[j])
    return y


@pytest.mark.parametrize("skew", [0.0, 50.0], ids=["spread", "all_to_one_expert"])
def test_no_drop_dispatch_matches_a_dense_loop(skew):
    """The grouped-matmul dispatch keeps every (token, expert) pair, also
    when every token picks the same held expert (a capacity of 1.25 × the
    mean would drop most of them): equal to the dense loop within 1e-5."""
    t, d, f, n_experts, k = 40, 16, 24, 8, 3
    held = (5, 0, 2, 7)
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    common = jnp.ones((d,)) / d ** 0.5  # every token leans along it; expert 5 reads it
    x = jax.random.normal(keys[0], (t, d)) + 4 * common
    router = jax.random.normal(keys[1], (d, n_experts)).at[:, 5].add(skew * common)
    w1 = jax.random.normal(keys[2], (len(held), d, f)) / 4
    w3 = jax.random.normal(keys[3], (len(held), d, f)) / 4
    w2 = jax.random.normal(keys[4], (len(held), f, d)) / 5
    gate, ids = moe.route_topk(x, router, k, normalize=False)
    if skew:
        assert bool((ids == 5).any(-1).all())  # every token routed to expert 5
    got = moe.held_expert_ffn(x, gate, ids, w1, w3, w2, held, n_experts)
    want = _dense_loop(x, gate, ids, w1, w3, w2, held)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_router_keeps_top_k_weights_as_they_are():
    """No renormalisation unless asked: the weights are the softmax over all
    experts, times the scaling factor."""
    x = jax.random.normal(jax.random.PRNGKey(6), (5, 16))
    router = jax.random.normal(jax.random.PRNGKey(7), (16, 8))
    probs = jax.nn.softmax(x @ router, -1)
    gate, ids = moe.route_topk(x, router, 3, normalize=False, scaling=2.5)
    np.testing.assert_allclose(np.asarray(gate),
                               2.5 * np.asarray(jnp.take_along_axis(probs, ids, -1)), rtol=1e-6)
    assert float(gate.sum(-1).max()) < 2.5
    gate_n, _ = moe.route_topk(x, router, 3, normalize=True)
    np.testing.assert_allclose(np.asarray(gate_n.sum(-1)), 1.0, rtol=1e-6)


def test_held_expert_shares_add_up_to_the_uncut_layer():
    """A layer of 16 routed experts cut into 8 shares of 2 (guide: the
    chip's share of an expert-parallel deployment): the shares' outputs
    summed, the shared experts counted once, equal the layer that holds all
    16, within 1e-5."""
    full = ds.DeepseekV2Config(**dict(SMALL, router_experts=16, num_experts_per_tok=6,
                                      held_experts=tuple(range(16))))
    w = ds._moe_init(jax.random.PRNGKey(9), full)
    h = jax.random.normal(jax.random.PRNGKey(10), (2, 7, full.hidden_size))
    uncut = ds.moe_layer(full, w, h)
    shared = ds.DeepseekV2Config(**dict(SMALL, router_experts=16, num_experts_per_tok=6,
                                        held_experts=(0,)))
    total = -7 * ds.moe_layer(  # each share adds the shared experts: keep one of 8
        shared, dict(w, w1=w["w1"][:1] * 0, w3=w["w3"][:1], w2=w["w2"][:1]), h)
    for share in range(8):
        held = (2 * share, 2 * share + 1)
        cut = ds.DeepseekV2Config(**dict(SMALL, router_experts=16, num_experts_per_tok=6,
                                         held_experts=held))
        part = {n: w[n][jnp.array(held)] for n in ("w1", "w3", "w2")}
        total = total + ds.moe_layer(cut, dict(w, **part), h)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=1e-5, rtol=1e-5)


def test_yarn_frequencies_and_score_scale_in_closed_form():
    """DeepSeek-V2-Lite's YaRN: correction range [10, 23] of 32 pairs,
    extrapolated frequencies below it, interpolated (÷40) above, a linear
    ramp between; score scale 192^-0.5 · (0.1·0.707·ln 40 + 1)²."""
    lite = ds.DeepseekV2Config(**dict(SMALL, qk_nope_head_dim=128, qk_rope_head_dim=64))
    got = lite.inv_freq
    for i in range(32):
        extra = 10000.0 ** (-2 * i / 64)
        ramp = min(max((i - 10) / 13, 0.0), 1.0)
        assert got[i] == pytest.approx(extra / 40 * ramp + extra * (1 - ramp), rel=1e-6)
    assert got[9] == pytest.approx(10000.0 ** (-18 / 64), rel=1e-6)
    assert got[23] == pytest.approx(10000.0 ** (-46 / 64) / 40, rel=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert lite.softmax_scale == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    assert lite.softmax_scale == pytest.approx(0.1147213868, rel=1e-9)
    plain = yarn_inv_freq(64, 10000.0, 1.0, 4096, 32, 1)  # factor 1: plain rotary
    np.testing.assert_allclose(plain, 10000.0 ** (-np.arange(0, 64, 2) / 64), rtol=1e-6)


def test_marks_in_the_lowered_decode_program():
    """``omc.mla`` over latent attention and ``omc.moe`` with its route,
    experts and shared parts in the decode program's op metadata, as the
    profiler's ``tf_op`` stat carries them."""
    storage = jax.eval_shape(lambda k: compress_params(ds.init(k, CFG), ds.param_specs(CFG), OMC),
                             jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: ds.init_decode_state(CFG, 2, 8, jnp.float32))
    _, decode = make_serve_fns(ds, CFG)
    text = jax.jit(decode).lower(storage, cache, jax.ShapeDtypeStruct((2, 1), jnp.int32)) \
        .as_text(dialect="hlo", debug_info=True)
    names = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("omc.mla/", "omc.moe/omc.moe.route/", "omc.moe/omc.moe.experts/",
                  "omc.moe/omc.moe.shared/", "omc.materialize/"):
        assert any(scope in n for n in names), scope


@pytest.mark.parametrize("family", ["deepseek_v2", "transformer"])
def test_serve_session_consumes_the_cache_and_still_matches(family):
    """Prefill and decode donate their cache: the one passed in is deleted,
    and the tokens match a session that does not donate."""
    mod = get_family(family)
    cfg = CFG if family == "deepseek_v2" else tr.TransformerConfig(
        n_layers=2, d_model=32, n_heads=2, n_kv_heads=1, d_ff=64, vocab=128)
    params = mod.init(jax.random.PRNGKey(11), cfg)
    storage = compress_params(params, mod.param_specs(cfg), OMC)
    sess = ServeSession(mod, cfg, storage)
    prompt = dict(tokens=jax.random.randint(jax.random.PRNGKey(12), (2, 6), 0, 128))
    cache = sess.init_cache(2, 12)
    new, logits = sess.prefill(prompt, cache)
    assert all(a.is_deleted() for a in jax.tree_util.tree_leaves(cache))
    tok = jnp.argmax(logits[:, -1], -1)[:, None]
    after, logits2 = sess.decode_step(new, tok)
    assert all(a.is_deleted() for a in jax.tree_util.tree_leaves(new))
    prefill_fn, decode_fn = make_serve_fns(mod, cfg)
    c0, l0 = jax.jit(prefill_fn)(storage, prompt, sess.init_cache(2, 12))
    _, l1 = jax.jit(decode_fn)(storage, c0, tok)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(l0))
    np.testing.assert_array_equal(np.asarray(logits2), np.asarray(l1))
    assert not any(a.is_deleted() for a in jax.tree_util.tree_leaves(after))
