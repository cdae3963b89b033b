"""Chunked online-softmax attention vs a naive reference; cache semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as attn


def _naive(q, k, v, causal=True, window=None):
    b, sq, h, hd = q.shape
    _, sk, kvh, _ = k.shape
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, hd) / np.sqrt(hd)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, k)
    qi = jnp.arange(sq)[:, None]
    ki = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    s = jnp.where(mask[None, :, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqhgk,bkhd->bqhgd", p, v)
    return o.reshape(b, sq, h, hd)


@pytest.mark.parametrize("causal,window", [(True, None), (True, 8), (False, None)])
@pytest.mark.parametrize("hkv", [(4, 4), (4, 2), (6, 1)])
def test_attend_matches_naive(causal, window, hkv):
    h, kvh = hkv
    b, s, hd = 2, 32, 16
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, s, h, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, kvh, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, kvh, hd))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    got = attn.attend(q, k, v, pos, pos, causal=causal, window=window,
                      q_block=8, kv_block=8)
    want = _naive(q, k, v, causal, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_attend_matches_last_row_of_train_attention():
    b, s, h, kvh, hd = 2, 16, 4, 2, 8
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (b, s, h, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, kvh, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, kvh, hd))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    full = _naive(q, k, v, causal=True)
    got = attn.decode_attend(q[:, -1:], k, v, pos, jnp.int32(s - 1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(full[:, -1:]),
                               rtol=2e-5, atol=2e-5)


def test_ring_cache_insert_overwrites_oldest():
    b, buf, kvh, hd = 1, 4, 1, 2
    k = jnp.zeros((b, buf, kvh, hd))
    v = jnp.zeros((b, buf, kvh, hd))
    pos = jnp.full((b, buf), -1, jnp.int32)
    for p in range(6):
        newk = jnp.full((b, 1, kvh, hd), float(p))
        k, v, pos = attn.cache_insert(k, v, pos, newk, newk, jnp.int32(p),
                                      ring=True)
    # positions 2..5 should be resident; slot = pos % buf
    assert sorted(np.asarray(pos[0]).tolist()) == [2, 3, 4, 5]
    for slot in range(buf):
        p = int(pos[0, slot])
        assert p % buf == slot
        assert float(k[0, slot, 0, 0]) == float(p)


def test_pick_q_block_divisibility():
    from repro.models.attention import _pick_q_block
    # nq must be a multiple of the mesh axis when divisible
    assert 4096 % _pick_q_block(4096, 512, 16) == 0
    assert (4096 // _pick_q_block(4096, 512, 16)) % 16 == 0
    assert (32768 // _pick_q_block(32768, 512, 16)) % 16 == 0
    # no mesh: plain target
    assert _pick_q_block(4096, 512, 1) == 512
    # awkward sizes fall back to any divisor
    assert 24 % _pick_q_block(24, 512, 16) == 0


def test_attend_value_head_dim_differs_from_query_key():
    """Latent attention's shapes: queries and keys of 12, values of 8, a
    score scale of its own; against the naive softmax."""
    b, s, h, hd, hd_v, scale = 2, 24, 4, 12, 8, 0.3
    key = jax.random.PRNGKey(5)
    q = jax.random.normal(key, (b, s, h, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, hd_v))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    got = attn.attend(q, k, v, pos, pos, q_block=8, kv_block=8, scale=scale)
    assert got.shape == (b, s, h, hd_v)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_attend_equal_dims_path_is_unchanged():
    """The grouped-query path (values as wide as keys, the default
    ``1/sqrt(hd)`` scale) traces to the same program as before the value
    size and scale became options: the same jaxpr as an explicit scale."""
    b, s, h, kvh, hd = 2, 16, 4, 2, 8
    q = jnp.zeros((b, s, h, hd))
    k = v = jnp.zeros((b, s, kvh, hd))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    default = jax.make_jaxpr(lambda q, k, v: attn.attend(q, k, v, pos, pos))(q, k, v)
    explicit = jax.make_jaxpr(lambda q, k, v: attn.attend(q, k, v, pos, pos,
                                                         scale=1.0 / np.sqrt(hd)))(q, k, v)
    assert str(default) == str(explicit)
    assert attn.attend(q, k, v, pos, pos).shape == (b, s, h, hd)


@pytest.mark.parametrize("window", [None, 4])
def test_decode_steps_write_the_cache_after_the_layers(window):
    """Decode steps whose layers read the cache and attend their own token
    beside it, the tokens written after the last layer: over several steps,
    past a sliding window's ring wrap, each step's logits equal a prefill of
    the whole sequence so far."""
    from repro.models import transformer as tr
    from repro.models.common import IDENTITY_MAT

    cfg = tr.TransformerConfig(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                               vocab=64, window=window)
    params = tr.init(jax.random.PRNGKey(4), cfg)
    b, p, steps = 2, 6, 5
    toks = jax.random.randint(jax.random.PRNGKey(5), (b, p + steps), 0, cfg.vocab)
    prefill = jax.jit(lambda t, c: tr.prefill(cfg, params, dict(tokens=t), IDENTITY_MAT, c))
    decode = jax.jit(lambda c, t: tr.decode_step(cfg, params, c, t, IDENTITY_MAT))
    cache, _ = prefill(toks[:, :p], tr.init_decode_state(cfg, b, p + steps, jnp.float32))
    for j in range(steps):
        cache, got = decode(cache, toks[:, p + j:p + j + 1])
        _, want = prefill(toks[:, :p + j + 1],
                          tr.init_decode_state(cfg, b, p + steps, jnp.float32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-4, atol=5e-4)
    assert int(cache.length) == p + steps
