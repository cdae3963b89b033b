"""The benchmark's FLOP and byte counters equal the program's own roofline
arithmetic (``repro.roofline.analysis``) at the cells' shapes."""

import functools
import types

import jax
import pytest

from drivers import common
from harness import counters, spec
from reference import conformer, transformer, weights
from repro.roofline import analysis


def _program_tree_size(config):
    family, cfg = common.program_model(config)
    tree = jax.eval_shape(lambda k: family.init(k, cfg), jax.random.PRNGKey(0))
    return sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("name,ref", [("conformer_s", conformer), ("qwen2_5_3b", transformer)])
def test_param_count_is_the_program_tree(name, ref):
    config = spec.resolve({"conformer_s": "conformer_s.train_local5",
                           "qwen2_5_3b": "qwen2_5_3b.serve_stream"}[name]).config
    assert ref.param_count(config) == _program_tree_size(config)


def test_bench_layout_matches_program_tree():
    for workload, ref in (("conformer_s.train_local5", conformer),
                          ("qwen2_5_3b.serve_stream", transformer)):
        config = spec.resolve(workload).config
        family, cfg = common.program_model(config)
        prog = jax.eval_shape(lambda k: family.init(k, cfg), jax.random.PRNGKey(0))
        mine = jax.eval_shape(functools.partial(weights.init, layout=ref.layout(config)),
                              jax.random.PRNGKey(0))
        assert jax.tree_util.tree_structure(prog) == jax.tree_util.tree_structure(mine)
        assert [a.shape for a in jax.tree_util.tree_leaves(prog)] == \
            [a.shape for a in jax.tree_util.tree_leaves(mine)]


@pytest.mark.parametrize("n", [1, 31, 2 ** 18, 17 * 512 * 2048, 151936 * 2048])
@pytest.mark.parametrize("width", [2, 11, 16, 19])
def test_packbits_bound_bytes(n, width):
    assert counters.packbits_bound_bytes(n, width) == analysis.packbits_bound_bytes(n, width)


@pytest.mark.parametrize("cohort", [4, 8])
def test_fused_aggregate_bound_bytes(cohort):
    config = spec.resolve("conformer_s.train_local5").config
    for _, shape, stack in conformer.layout(config).values():
        n = max(stack, 1)
        for s in shape:
            n *= s
        for cb in (1, 2, 4):
            assert (counters.fused_aggregate_bound_bytes(cohort, n, cb)
                    == analysis.fused_aggregate_bound_bytes(cohort, n, cb))


def _shape(kind, batch, seq):
    return types.SimpleNamespace(kind=kind, global_batch=batch, seq_len=seq)


def test_train_flops_are_six_n_frames():
    for workload in ("conformer_s.train_local5",):
        cell = spec.resolve(workload)
        n = conformer.param_count(cell.config)
        stub = types.SimpleNamespace(param_count=lambda: n)
        frames = cell.traffic["frames"]
        assert counters.train_flops_per_sample(n, frames) == \
            analysis.model_flops(None, stub, _shape("train", 1, frames))


def test_decode_flops_are_two_n():
    cell = spec.resolve("qwen2_5_3b.serve_stream")
    _, cfg = common.program_model(cell.config)
    n = transformer.param_count(cell.config)
    assert n == cfg.param_count()
    assert counters.decode_flops_per_token(n) == \
        analysis.model_flops(None, cfg, _shape("decode", 1, 1))


def test_container_bytes():
    assert [counters.container_bytes(b) for b in (2, 8, 9, 11, 16, 17, 32)] == \
        [1, 1, 2, 2, 2, 4, 4]
