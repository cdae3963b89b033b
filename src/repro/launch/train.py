"""End-to-end federated training driver with checkpoint/restart.

Runs the jit-able federated round (compressed-state OMC by default) on a
synthetic LM/frame task, checkpointing atomically every ``--ckpt-every``
rounds and resuming from the latest checkpoint if one exists (fault
tolerance: kill the process at any point and rerun the same command).

Examples:
    # CPU-scale smoke run
    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --smoke \
        --rounds 30 --batch 8 --seq 64

    # ~100M-parameter end-to-end run (real hardware scale)
    PYTHONPATH=src python -m repro.launch.train --arch conformer_s \
        --rounds 300 --batch 16

    # paper FP32 control
    ... --fmt S1E8M23
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from repro import checkpoint as ck
from repro.configs.registry import get_arch
from repro.core.omc import OMCConfig
from repro.data.synthetic import make_frame_task, make_lm_task
from repro.federated.round import make_round_fn
from repro.federated.state import init_state, state_bytes_report
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import get_family
from repro.optim import fedavg


def make_task(arch, cfg, seq: int, num_clients: int, iid: bool, seed: int):
    fam = arch.FAMILY
    if fam == "conformer":
        task = make_frame_task(d_in=cfg.d_in, n_classes=cfg.n_classes,
                               seq_len=seq, num_clients=num_clients, iid=iid,
                               seed=seed)
        return lambda c, r, s, b: task.batch(c, r, s, b)
    if fam in ("transformer", "moe", "xlstm", "griffin"):
        task = make_lm_task(vocab=min(cfg.vocab, 4096), seq_len=seq,
                            num_clients=num_clients, iid=iid, seed=seed)
        return lambda c, r, s, b: task.batch(c, r, s, b)
    raise SystemExit(f"train driver supports LM/conformer tasks, not {fam}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="conformer_s")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU scale)")
    ap.add_argument("--fmt", default="S1E4M14")
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=48)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--non-iid", action="store_true")
    ap.add_argument("--client-lr", type=float, default=0.05)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    arch = get_arch(args.arch)
    cfg = arch.smoke_config() if args.smoke else arch.config()
    family = get_family(arch.FAMILY)
    omc = OMCConfig.parse(args.fmt)
    opt = fedavg(1.0)

    state = init_state(jax.random.PRNGKey(args.seed), family, cfg, omc, opt)
    rep = state_bytes_report(state.params)
    print(f"arch={args.arch} fmt={args.fmt} params={rep['num_params'] / 1e6:.1f}M "
          f"container={rep['container_ratio']:.0%} packed={rep['packed_ratio']:.0%} of FP32")

    start_round = 0
    if args.ckpt_dir:
        found = ck.latest_checkpoint(args.ckpt_dir)
        if found:
            state, manifest = ck.restore_state(found[0], state)
            start_round = manifest["step"]
            print(f"resumed from {found[0]} at round {start_round}")

    data_fn = make_task(arch, cfg, args.seq, args.clients, not args.non_iid,
                        args.seed)
    round_fn = jax.jit(make_round_fn(family, cfg, omc, opt,
                                     client_lr=args.client_lr))

    t0 = time.time()
    for r in range(start_round, args.rounds):
        batch = data_fn(r % args.clients, r, 0, args.batch)
        state, metrics = round_fn(state, batch)
        if (r + 1) % args.log_every == 0 or r == start_round:
            dt = time.time() - t0
            print(f"round {r + 1}/{args.rounds} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"({(r + 1 - start_round) / max(dt, 1e-9):.2f} rounds/s)")
        if args.ckpt_dir and (r + 1) % args.ckpt_every == 0:
            path = ck.save_state(args.ckpt_dir, r + 1, state)
            print(f"checkpointed -> {path}")
    if args.ckpt_dir:
        ck.save_state(args.ckpt_dir, args.rounds, state)
    print("done")


if __name__ == "__main__":
    main()
