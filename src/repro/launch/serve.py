"""Serving driver: batched generation over OMC-compressed weights.

Weights stay compressed in memory (the paper's storage model); each layer
decompresses on the fly inside the jitted decode step.  The driver runs on
a :class:`repro.api.session.ServeSession`, the same abstraction the wire
demo hot-swaps payloads into — so what this benchmarks is exactly the
serve path a federated deployment would run between rounds (DESIGN.md §7).
Reports prefill and per-token decode latency/throughput.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
        --batch 4 --prompt-len 32 --gen 16 --fmt S1E3M7

``--wire-roundtrip`` additionally pushes the weights through the wire codec
(encode -> decode -> hot_swap) before serving, proving the payload path is
bit-transparent to generation.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.api.codecs import encode_payload
from repro.api.session import ServeSession
from repro.configs.registry import get_arch
from repro.core.omc import OMCConfig
from repro.federated.state import compress_params
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import get_family, is_servable
from repro.obs.log import Logger


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--fmt", default="S1E3M7")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--wire-roundtrip", action="store_true",
                    help="serialize weights through the wire codec first")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress stderr text")
    args = ap.parse_args()
    enable_compile_cache()
    log = Logger(quiet=args.quiet)

    arch = get_arch(args.arch)
    if not is_servable(arch.FAMILY):
        raise SystemExit(f"{args.arch} ({arch.FAMILY}) has no decode step")
    cfg = arch.smoke_config() if args.smoke else arch.config()
    family = get_family(arch.FAMILY)
    omc = OMCConfig.parse(args.fmt)

    key = jax.random.PRNGKey(args.seed)
    params = family.init(key, cfg)
    storage = compress_params(params, family.param_specs(cfg), omc)
    sess = ServeSession(family, cfg, storage)
    if args.wire_roundtrip:
        t0 = time.time()
        payload = encode_payload(storage)
        sess.hot_swap(payload)
        log.info(f"wire roundtrip: {len(payload)} B payload in "
                 f"{(time.time() - t0) * 1e3:.1f} ms",
                 payload_bytes=len(payload),
                 roundtrip_ms=(time.time() - t0) * 1e3)

    b, s = args.batch, args.prompt_len
    toks = jax.random.randint(jax.random.fold_in(key, 1), (b, s), 0, cfg.vocab)
    batch = dict(tokens=toks)
    if arch.FAMILY == "vlm":
        batch["patches"] = jax.random.normal(
            jax.random.fold_in(key, 2), (b, cfg.prefix_embeds, cfg.d_model))
    if arch.FAMILY == "encdec":
        batch["frames"] = jax.random.normal(
            jax.random.fold_in(key, 2), (b, 4 * (s + args.gen), cfg.d_model))

    cache = sess.init_cache(b, 4 * (s + args.gen), dtype=jnp.float32)
    t0 = time.time()
    cache, logits = jax.block_until_ready(sess.prefill(batch, cache))
    t_prefill = time.time() - t0
    log.info(f"prefill [{b}x{s}] in {t_prefill * 1e3:.1f} ms",
             batch=b, prompt_len=s, prefill_ms=t_prefill * 1e3)

    out_tokens = []
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    t0 = time.time()
    for i in range(args.gen):
        cache, logits = sess.decode_step(cache, tok)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    log.result(
        f"decoded {args.gen} tokens x {b} seqs in {dt * 1e3:.1f} ms "
        f"({args.gen * b / dt:.1f} tok/s, {dt / args.gen * 1e3:.2f} ms/tok)",
        gen_tokens=args.gen, batch=b, decode_ms=dt * 1e3,
        tok_per_s=args.gen * b / dt,
    )
    gen = jnp.concatenate(out_tokens, axis=1)
    log.info(f"sample token ids: {gen[0, :12].tolist()}")


if __name__ == "__main__":
    main()
