"""Runs at smoke size with the harness's look for a chip skipped: a sound run
is correct, and a run with the timed path broken underneath is not: a round
that returns its state unchanged, a client loss over half of each batch, a
served token altered where it is produced, a decoded value altered."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
import tiny

SEED = 3_000_000_017


def run_tiny(workload, seed=SEED):
    return run.run_cell(tiny.cell(workload), seed, 0.5, False, jax.devices(), {},
                        time.perf_counter(), say=lambda *a, **k: None)


@pytest.mark.parametrize("workload", ["conformer_s.train_local5",
                                      "qwen2_5_3b.serve_stream", "conformer_s.wire_roundtrip"])
def test_sound_run_is_correct(workload):
    line = run_tiny(workload)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"


def test_round_that_returns_its_state_unchanged(monkeypatch):
    from repro.federated import engine

    real = engine.make_round_fn

    def frozen(*a, **k):
        fn = real(*a, **k)
        return lambda storage, *rest: (storage,) + tuple(fn(storage, *rest)[1:])

    monkeypatch.setattr(engine, "make_round_fn", frozen)
    line = run_tiny("conformer_s.train_local5")
    assert not line["correct"]
    assert line["checks"]["first_update_gap"]["value"] > 0.9


def test_half_of_each_batch_left_out(monkeypatch):
    from repro.models import conformer

    real = conformer.loss

    def half(cfg, params, batch, mat):
        keep = batch["labels"].shape[0] // 2
        return real(cfg, params, {k: v[:keep] for k, v in batch.items()}, mat)

    monkeypatch.setattr(conformer, "loss", half)
    assert not run_tiny("conformer_s.train_local5")["correct"]


def test_served_token_altered(monkeypatch):
    from repro.models import transformer

    real = transformer.decode_step

    def shifted(cfg, params, cache, tokens, mat):
        cache, logits = real(cfg, params, cache, tokens, mat)
        return cache, jnp.roll(logits, 1, axis=-1)

    monkeypatch.setattr(transformer, "decode_step", shifted)
    assert not run_tiny("qwen2_5_3b.serve_stream")["correct"]


def test_decoded_value_altered(monkeypatch):
    from repro.api import codecs

    real = codecs._unpack_np

    def flipped(words, bits, n):
        out = np.array(real(words, bits, n))
        out[0] ^= 1
        return out

    monkeypatch.setattr(codecs, "_unpack_np", flipped)
    line = run_tiny("conformer_s.wire_roundtrip")
    assert not line["correct"]
    assert line["checks"]["mismatched_values"]["value"] > 0
