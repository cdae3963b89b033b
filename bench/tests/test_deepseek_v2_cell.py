"""The DeepSeek-V2-Lite serving cell at smoke size, and its yardsticks at the
cell's own shapes: the reference's layout and parameter count against the
program's tree, a sound run correct and a run with a served token altered
not, the float8 control failing the limit, and the counters behind
``serve_mfu_active`` and ``serve_decode_roofline`` against values worked out
by hand."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from harness import latent_moe, spec, trace
from reference import deepseek_v2 as ref
from reference import weights

CELL = "deepseek_v2_lite.serve_2k8k"
SEED = 3_150_000_017
SMOKE = dict(hidden_size=64, num_hidden_layers=3, num_attention_heads=4, vocab_size=2048,
             vocab=2048, intermediate_size=96, moe_intermediate_size=32, router_experts=8,
             num_experts_per_tok=2, held_experts=[0, 1, 2, 3], kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)


def smoke_cell(size=None, traffic=None):
    c = spec.resolve(CELL)
    return dataclasses.replace(
        c, config=dict(c.config, **dict(SMOKE, **(size or {}))),
        traffic=dict(c.traffic, **dict(dict(batch=2, lengths=[24, 8, 8, 8], new_tokens=6),
                                       **(traffic or {}))))


def program_shapes(config):
    from drivers import common

    family, cfg = common.program_model(config)
    return jax.eval_shape(lambda k: family.init(k, cfg), jax.random.PRNGKey(0))


def test_layout_is_the_program_tree():
    config = spec.resolve(CELL).config
    drawn = jax.eval_shape(lambda k: weights.init(k, ref.layout(config)), jax.random.PRNGKey(0))
    prog = program_shapes(config)
    assert jax.tree_util.tree_structure(drawn) == jax.tree_util.tree_structure(prog)
    assert jax.tree_util.tree_map(lambda a: a.shape, drawn) == \
        jax.tree_util.tree_map(lambda a: a.shape, prog)


def test_param_count_is_the_program_tree():
    config = spec.resolve(CELL).config
    prog = program_shapes(config)
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(prog))
    assert ref.param_count(config) == n == 3_110_989_312


def run_smoke(cell, seed=SEED):
    return run.run_cell(cell, seed, 0.5, False, jax.devices(), {}, time.perf_counter(),
                        say=lambda *a, **k: None)


def test_sound_run_is_correct():
    line = run_smoke(smoke_cell())
    assert line["correct"], line["checks"]


def test_served_token_altered(monkeypatch):
    from repro.models import deepseek_v2

    real = deepseek_v2.decode_step

    def shifted(cfg, params, cache, tokens, mat):
        cache, logits = real(cfg, params, cache, tokens, mat)
        return cache, jnp.roll(logits, 1, axis=-1)

    monkeypatch.setattr(deepseek_v2, "decode_step", shifted)
    line = run_smoke(smoke_cell())
    assert not line["correct"]
    assert line["checks"]["served_gap"]["value"] > line["checks"]["served_gap"]["limit"]


# Wide enough that float8 operands move the logits as at full size; the
# smoke program reads far below it.
CONTROL_SIZE = dict(hidden_size=1024, num_attention_heads=8, intermediate_size=2048,
                    moe_intermediate_size=512, kv_lora_rank=256, qk_nope_head_dim=64,
                    qk_rope_head_dim=32, v_head_dim=64, router_experts=16,
                    num_experts_per_tok=4, held_experts=[0, 1, 2, 3, 4, 5, 6, 7])


@pytest.mark.parametrize("seed", [3_150_000_101, 3_150_000_102])
def test_control_fails_the_limit(seed):
    cell = smoke_cell(CONTROL_SIZE, dict(batch=4, new_tokens=12))
    drv = spec.driver(cell).Driver(cell, seed)
    drv.setup()
    drv.window(3.0)
    drv.release()
    program, control = drv.check(control=True)
    limit = cell.limits["limits"]["served_gap"]
    assert program["served_gap"] <= limit, program
    assert control["served_gap"] > limit, control


# -- the counters at the cell's shapes, worked out by hand --------------------

ATTENTION = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048  # 13,762,560
EXPERT = 3 * 2048 * 1408  # 8,650,752
ACTIVE = (27 * ATTENTION + 3 * 2048 * 10944  # attention everywhere, layer 0's SwiGLU
          + 26 * (2048 * 64 + 2 * EXPERT + 6 * 8 / 64 * EXPERT)  # router, shared, routed
          + 2048 * 102400)  # head
NORMS = 27 * (2048 + 512 + 2048) + 2048
MATRICES = 3_110_989_312 - NORMS - 102400 * 2048  # every selected leaf but the embedding
AFFINES = 7 + 26 * (5 + 3) + 26 * 3 * 8 + 2  # layer 0, expert layers, experts, head+embed
WEIGHT_BYTES = 2 * MATRICES + 8 * AFFINES + 4 * NORMS + 8 * 2048 * 2  # + 8 embedding rows


def test_active_params_and_weight_bytes_by_hand():
    config = spec.resolve(CELL).config
    assert ACTIVE == 1_270_480_896
    assert latent_moe.active_params(config) == ACTIVE
    assert latent_moe.stored_leaf_bytes(config, 8) == WEIGHT_BYTES == 5_802_840_648


def fake_run(counts, decode_s=0.0):
    cell = spec.resolve(CELL)
    ops = {"/device:TPU:0": [("%fusion.1 = f32[8] fusion()", 0, 10 ** 9)]}
    host = [(trace.WINDOW, 0, 10 ** 10)]
    modules = {"/device:TPU:0": [("jit_decode_fn(3)", 10 ** 9, 10 ** 9 + int(decode_s * 1e9)),
                                 ("jit_prefill_fn(2)", 0, 10 ** 9)]}
    peaks = {"bf16_flops": 1.97e14, "hbm_bytes_per_s": 8.19e11}
    return run.Run(cell, peaks, 1, counts, 0, trace.from_events(ops, host, modules))


def test_serve_mfu_active_by_hand():
    counts = dict(tokens=8 * 128 + 8 * 10, seconds=20.0, batches=2)
    got = spec.load_reader("serve_mfu_active")(fake_run(counts))
    assert got == pytest.approx(100 * 2 * ACTIVE * 1104 / 20.0 / 1.97e14, rel=1e-12)


def test_serve_decode_roofline_by_hand():
    # a full 8192 batch (127 decode steps at 8192..8318 cached positions),
    # then 10 tokens of a 2048 batch (9 steps at 2048..2056)
    counts = dict(tokens=8 * 128 + 8 * 10, seconds=20.0, batches=2)
    lengths = list(range(8192, 8319)) + list(range(2048, 2057))
    assert latent_moe.decode_lengths(spec.resolve(CELL).traffic, 2, 1104) == lengths
    cache = sum(27 * 8 * (512 + 64) * 4 * (n + 1) for n in lengths)
    moved = 136 * WEIGHT_BYTES + cache
    got = spec.load_reader("serve_decode_roofline")(fake_run(counts, decode_s=4.0))
    assert got == pytest.approx(100 * moved / 8.19e11 / 4.0, rel=1e-12)
    assert spec.load_reader("serve_decode_roofline")(fake_run(counts)) is None
