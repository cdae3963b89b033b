"""Closed-loop streaming generation through ``ServeSession``.

One caller holds a batch of ``batch`` sequences at a time: it sends the
prompts, ``prefill`` returns the first token, then ``decode_step`` one token
at a time, each read to the host as a streaming server reads it, until
``new_tokens``; then the next batch.  Prompt lengths follow the list
``lengths`` in its order, over and over, the same for every seed; the seed
draws the prompt tokens, uniform over the vocabulary, and the weights, on
the device, which the program compresses.

The window ends at the first token read once its seconds are spent, in the
middle of a batch as a rule.  The rate is the tokens delivered to the host
over the window; a token's gap is the time since the previous token of its
sequence, the first counted from when its batch was sent.  Set-up serves one
batch of each prompt length.
"""

from __future__ import annotations

import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from reference import omc as ref_omc, weights

from . import common


class Driver:
    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, seed
        self.config, self.traffic = cell.config, cell.traffic
        self.reference = importlib.import_module(f"reference.{self.config['reference']}")
        self.layout = self.reference.layout(self.config)
        self.fmt = ref_omc.Fmt(self.config["omc"]["format"])
        key = weights.seed_key(seed)
        self.k_weights = jax.random.fold_in(key, 0)
        self.rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 1])
        t = self.traffic
        self.lengths = [int(p) for p in t["lengths"]]
        self.batch, self.new_tokens = t["batch"], t["new_tokens"]

    def _prompts(self, length: int):
        return self.rng.integers(0, self.config["vocab"], (self.batch, length), np.int32)

    # -- the program -----------------------------------------------------

    def setup(self, prior=None):
        from repro.api.session import ServeSession
        from repro.core.omc import OMCConfig
        from repro.federated.state import compress_params

        o = self.config["omc"]
        t0 = time.perf_counter()
        family, cfg = common.program_model(self.config)
        omc = OMCConfig.parse(o["format"], pvt=o["pvt"])
        specs = family.param_specs(cfg)
        build = jax.jit(lambda k: compress_params(weights.init(k, self.layout), specs, omc))
        storage = jax.block_until_ready(build(self.k_weights))
        if prior is not None:
            self.session, self.pick = prior.session, prior.pick
            self.session.storage = storage
        else:
            self.session = ServeSession(family, cfg, storage)
            self.pick = jax.jit(lambda logits: jnp.argmax(logits[:, -1], axis=-1)
                                .astype(jnp.int32))
        t1 = time.perf_counter()
        warm = np.random.default_rng(0)
        for p in sorted(set(self.lengths)):
            self._serve(warm.integers(0, self.config["vocab"], (self.batch, p), np.int32))
        self.phases = dict(weights=t1 - t0, warm_up=time.perf_counter() - t1)

    def _serve(self, prompts, deadline: float = float("inf")):
        """Serve one batch, stopping at the first token read at or after
        ``deadline``; returns ``(tokens [B, served], gaps in s, time of the
        last token)``."""
        b, p = prompts.shape
        sent = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.prefill"):
            cache = self.session.init_cache(b, p + self.new_tokens)
            cache, logits = self.session.prefill(dict(tokens=jnp.asarray(prompts)), cache)
            tok = self.pick(logits)
        with jax.profiler.TraceAnnotation("bench.read_token"):
            out = [np.asarray(tok)]
        last = time.perf_counter()
        gaps = [last - sent]
        for _ in range(self.new_tokens - 1):
            if last >= deadline:
                break
            with jax.profiler.TraceAnnotation("bench.decode_step"):
                cache, logits = self.session.decode_step(cache, tok[:, None])
                tok = self.pick(logits)
            with jax.profiler.TraceAnnotation("bench.read_token"):
                out.append(np.asarray(tok))
            now = time.perf_counter()
            gaps.append(now - last)
            last = now
        return np.stack(out, 1), gaps, last

    def window(self, seconds: float):
        """Batches until the first token read once ``seconds`` are spent;
        the batches served in full are kept for the comparison."""
        self.served, gaps, tokens, batches = [], [], 0, 0
        start = time.perf_counter()
        deadline = start + seconds
        while True:
            prompts = self._prompts(self.lengths[batches % len(self.lengths)])
            with jax.profiler.TraceAnnotation("bench.request_batch"):
                toks, g, last = self._serve(prompts, deadline)
            batches += 1
            tokens += toks.size
            gaps.extend(g)
            if toks.shape[1] == self.new_tokens:
                self.served.append((prompts, toks))
            if last >= deadline:
                break
        elapsed = last - start
        return dict(metrics=dict(serve_tokens_per_s=tokens / elapsed,
                                 serve_token_gap_p95_ms=1e3 * float(np.quantile(gaps, 0.95))),
                    attempted=batches * self.batch, failed=0,
                    counts=dict(tokens=tokens, seconds=elapsed, batches=batches,
                                longest_gap_s=max(gaps),
                                params=self.reference.param_count(self.config)))

    def release(self):
        self.session.storage = None

    # -- the reference ---------------------------------------------------

    def sample(self):
        """The batches compared: the first of the longest prompts, and one
        other drawn from the seed."""
        longest = max(p.shape[1] for p, _ in self.served)
        first = next(i for i, (p, _) in enumerate(self.served) if p.shape[1] == longest)
        rest = [i for i in range(len(self.served)) if i != first]
        picked = [first]
        if rest:
            picked.append(int(np.random.default_rng(self.seed & 0xFFFFFFFF).choice(rest)))
        return [self.served[i] for i in picked]

    def check(self, control: bool = False):
        """Widest gap by which a served token's logit lies below the
        reference's best at its position; with ``control``, the same for the
        tokens that the reference computed in the configuration's control
        precision puts first."""
        if not self.served:  # no request finished: nothing shows the tokens right
            out = dict(served_gap=float("inf"))
            return (out, out) if control else out
        ref = self.reference.Forward(self.config, self.k_weights, self.fmt)
        low = (self.reference.Forward(self.config, self.k_weights, self.fmt,
                                      self.config["control"])
               if control else None)
        served_gap, control_gap = 0.0, 0.0
        for prompts, toks in self.sample():
            p = prompts.shape[1]
            seq = np.concatenate([prompts, toks[:, :-1]], 1)
            logits = ref(seq, p - 1, toks.shape[1])
            best = logits.max(-1)
            chosen = jnp.take_along_axis(logits, jnp.asarray(toks)[..., None], -1)[..., 0]
            served_gap = max(served_gap, float((best - chosen).max()))
            if low is not None:
                first = low(seq, p - 1, toks.shape[1]).argmax(-1)
                picked = jnp.take_along_axis(logits, first[..., None], -1)[..., 0]
                control_gap = max(control_gap, float((best - picked).max()))
            del logits
        out = dict(served_gap=served_gap)
        return (out, dict(served_gap=control_gap)) if control else out
