"""Smoke-size cells: each real cell's files, with the model cut to a size a
CPU test run holds and the traffic cut to match.

Training keeps the full-size ratio of a round's update to the weights (about
1e-4) with a smaller client learning rate: at smoke width the weights are
larger, and at the cell's rate an update would be far coarser than float32
and bfloat16 resolve alike.  Serving keeps a head width at which logits
spread as they do at full size."""

from __future__ import annotations

import dataclasses

from harness import spec

SIZES = {
    "conformer_s": dict(n_layers=2, d_model=48, n_heads=4, d_ff=96, n_classes=32,
                        d_in=16, conv_kernel=4, window=16),
    "qwen2_5_3b": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=160,
                       vocab=2048, head_dim=16),
}
TRAFFIC = {
    "fed_rounds": dict(population=8, cohort=4, frames=32, batch=2, client_lr=1e-3),
    "serve_stream": dict(batch=2, lengths=[8, 8, 24], new_tokens=6),
    "wire_roundtrip": {},
}


def cell(workload: str) -> spec.Cell:
    c = spec.resolve(workload)
    config = dict(c.config, **SIZES[c.config["name"]])
    traffic = dict(c.traffic, **TRAFFIC[c.traffic["driver"]])
    if c.traffic["driver"] == "fed_rounds":
        traffic["local_steps"] = min(c.traffic["local_steps"], 2)
    return dataclasses.replace(c, config=config, traffic=traffic)
