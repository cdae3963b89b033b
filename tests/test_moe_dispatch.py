"""MoE dispatch: shard_map path == single-device reference; capacity rules."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_host_mesh
from repro.models import moe
from repro.models.common import activate_mesh

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")

CFG = moe.MoEConfig(n_layers=1, d_model=32, n_heads=4, n_kv_heads=2,
                    d_ff=64, vocab=64, n_experts=4, top_k=2)


def _ffn_weights(key):
    blk = moe._block_init(key, CFG)
    return {k: blk[k] for k in ("router", "w1", "w3", "w2")}


def test_shard_map_matches_reference_1x1():
    w = _ffn_weights(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    y_ref, aux_ref = moe.moe_ffn(x, w, CFG)
    mesh = make_host_mesh(1, 1)
    with activate_mesh(mesh):
        y_sm, aux_sm = jax.jit(lambda x, w: moe.moe_ffn(x, w, CFG))(x, w)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_sm),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(aux_ref), float(aux_sm), rtol=1e-5)


_MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
import jax.numpy as jnp
from repro.launch.mesh import make_host_mesh
from repro.models import moe
from repro.models.common import activate_mesh

cfg = moe.MoEConfig(n_layers=1, d_model=32, n_heads=4, n_kv_heads=2,
                    d_ff=64, vocab=64, n_experts=4, top_k=2)
blk = moe._block_init(jax.random.PRNGKey(0), cfg)
w = {k: blk[k] for k in ("router", "w1", "w3", "w2")}
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
y_ref, aux_ref = moe.moe_ffn(x, w, cfg)
mesh = make_host_mesh(2, 4)
with activate_mesh(mesh):
    y_sm, aux_sm = jax.jit(lambda x, w: moe.moe_ffn(x, w, cfg))(x, w)
# capacity differs per-shard (T_local < T), so token drops may differ around
# the capacity boundary; with cf=1.25 at these sizes none should drop.
np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_sm),
                           rtol=1e-4, atol=1e-4)
print("MULTIDEV-OK")
"""


def test_shard_map_matches_reference_8dev():
    """Real expert-parallel dispatch over a (2, 4) host mesh (subprocess:
    device count must be set before jax init)."""
    r = subprocess.run(
        [sys.executable, "-c", _MULTIDEV_SCRIPT],
        capture_output=True, text=True,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},
        timeout=600,
    )
    assert "MULTIDEV-OK" in r.stdout, r.stdout + r.stderr


def test_capacity_bounds():
    assert moe._capacity(1, CFG) == CFG.top_k  # can't exceed pairs
    c = moe._capacity(1000, CFG)
    assert c % 8 == 0
    assert c >= 1000 * CFG.top_k / CFG.n_experts


def test_expert_weights_shapes_with_partitions():
    cfg2 = moe.MoEConfig(n_layers=1, d_model=32, n_heads=4, n_kv_heads=2,
                         d_ff=64, vocab=64, n_experts=4, top_k=2,
                         ep_partitions=2)
    blk = moe._block_init(jax.random.PRNGKey(0), cfg2)
    assert blk["w1"].shape == (8, 32, 32)  # [E*parts, D, F/parts]
    assert blk["w2"].shape == (8, 32, 32)


def test_tied_embeddings_serve_through_the_embedding_transpose():
    """With ``tie_embeddings`` the serving head is the embedding's transpose
    (no ``lm_head`` leaf): prefill's and decode's logits equal the forward
    pass's.  A capacity factor that drops no token keeps the two paths'
    routing alike."""
    from repro.models.common import IDENTITY_MAT

    cfg = moe.MoEConfig(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                        n_experts=4, top_k=2, tie_embeddings=True, capacity_factor=8.0)
    params = moe.init(jax.random.PRNGKey(2), cfg)
    assert "lm_head" not in params
    b, p = 2, 6
    toks = jax.random.randint(jax.random.PRNGKey(3), (b, p + 1), 0, cfg.vocab)
    hidden, _ = moe.forward(cfg, params, {"tokens": toks}, IDENTITY_MAT)
    full = hidden @ params["embed"].T
    cache = moe.init_decode_state(cfg, b, p + 1, jnp.float32)
    cache, logits = moe.prefill(cfg, params, {"tokens": toks[:, :p]}, IDENTITY_MAT, cache)
    np.testing.assert_allclose(np.asarray(logits[:, 0]), np.asarray(full[:, p - 1]),
                               rtol=1e-4, atol=1e-4)
    _, logits = moe.decode_step(cfg, params, cache, toks[:, p:], IDENTITY_MAT)
    np.testing.assert_allclose(np.asarray(logits[:, 0]), np.asarray(full[:, p]),
                               rtol=1e-4, atol=1e-4)
