"""Uniform model API over the zoo.

Each family module exposes::

    init(key, cfg) -> params
    param_specs(cfg) -> ParamSpec pytree (same structure as params)
    loss(cfg, params, batch, mat) -> scalar          # train objective
    prefill(cfg, params, batch, mat, state) -> (state, logits)   (if servable)
    decode_step(cfg, params, state, tokens, mat) -> (state, logits)
    init_decode_state(cfg, batch, max_len, dtype) -> state

``get_family(name)`` returns the module; ``"vlm"`` resolves to the
transformer (the ViT frontend is a stub — DESIGN.md §6) and ``"conformer"``
has no decode step (encoder-only; paper benchmarks only).
"""

from __future__ import annotations

from types import ModuleType
from typing import Dict

from . import conformer, deepseek_v2, encdec, griffin, moe, transformer, xlstm

_FAMILIES: Dict[str, ModuleType] = {
    "transformer": transformer,
    "vlm": transformer,  # prefix_embeds > 0 in the config
    "moe": moe,
    "deepseek_v2": deepseek_v2,
    "xlstm": xlstm,
    "griffin": griffin,
    "encdec": encdec,
    "conformer": conformer,
}

SERVABLE = {"transformer", "vlm", "moe", "deepseek_v2", "xlstm", "griffin", "encdec"}


def get_family(name: str) -> ModuleType:
    if name not in _FAMILIES:
        raise KeyError(f"unknown model family {name!r}; have {sorted(_FAMILIES)}")
    return _FAMILIES[name]


def is_servable(name: str) -> bool:
    return name in SERVABLE
