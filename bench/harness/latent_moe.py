"""Operations and bytes of serving a latent-attention expert model
(DeepSeek-V2: ``reference/deepseek_v2.py``'s layout) from OMC codes,
computed from the configuration's shapes and the traffic's fixed order.

The yardstick of ``serve_mfu_active`` and ``serve_decode_roofline``: the
parameters one delivered token uses on this chip, and the least HBM bytes
of a decode step (every stored leaf read once at its container's bytes, the
embedding only at the rows the step looks up, the latent cache read to its
current length and written at one position in every layer).
"""

from __future__ import annotations

import numpy as np

from reference import deepseek_v2, omc

FLOAT32 = 4


def attention_params(cfg) -> int:
    """Matrices of one layer's latent attention."""
    d, h, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d


def expert_params(cfg) -> int:
    """One routed expert's SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def active_params(cfg) -> float:
    """Parameters one token uses on this chip: attention in every layer, the
    dense layers' SwiGLU, and in every expert layer the router, the shared
    experts and, on average, ``top_k · held / router_experts`` routed
    experts; then the head.  The embedding is a lookup."""
    d, n_dense = cfg["hidden_size"], cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    routed = cfg["num_experts_per_tok"] * len(cfg["held_experts"]) / cfg["router_experts"]
    per_moe = (d * cfg["router_experts"] + cfg["n_shared_experts"] * expert_params(cfg)
               + routed * expert_params(cfg))
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + n_dense * 3 * d * cfg["intermediate_size"]
            + n_moe * per_moe + d * cfg["vocab_size"])


def decode_flops_per_token(cfg) -> float:
    """Model FLOPs of one delivered token on this chip: 2·N_active."""
    return 2.0 * active_params(cfg)


def stored_leaf_bytes(cfg, batch: int) -> int:
    """Least bytes a decode step reads of the stored weights: each
    OMC-selected leaf's codes at the container's width plus its affine (two
    float32 a layer, a layer and expert for the experts' matrices), every
    other leaf in float32, and of the embedding only ``batch`` rows."""
    bits = omc.Fmt(cfg["omc"]["format"]).bits
    container = 1 if bits <= 8 else 2 if bits <= 16 else 4
    total = 0
    for path, (_, shape, stack) in deepseek_v2.layout(cfg).items():
        group, name = path.rsplit("/", 1) if "/" in path else ("", path)
        n = int(np.prod(shape)) * max(stack, 1)
        if path == "embed":
            n = batch * shape[1]
        if omc.selected(name, shape, 0):
            per_expert = group == "blocks" and name in deepseek_v2.EXPERT_LEAVES
            affines = max(stack, 1) * (shape[0] if per_expert else 1)
            total += n * container + 2 * FLOAT32 * affines
        else:
            total += n * FLOAT32
    return total


def latent_cache_bytes(cfg, batch: int, length: int) -> int:
    """Latent cache traffic of one decode step at ``length`` cached
    positions: read to its length and write one position, in every layer."""
    per_position = (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * FLOAT32
    return cfg["num_hidden_layers"] * batch * per_position * (length + 1)


def decode_lengths(traffic, batches: int, tokens: int):
    """Cached positions at each decode step of a window that served
    ``batches`` batches and delivered ``tokens`` tokens, the batches' prompt
    lengths following the traffic's order from its start: a batch's first
    token comes from its prefill, each later one from a decode step."""
    b, new = traffic["batch"], traffic["new_tokens"]
    out = []
    for i in range(batches):
        served = new if i < batches - 1 else tokens // b - new * (batches - 1)
        prompt = traffic["lengths"][i % len(traffic["lengths"])]
        out += [prompt + j for j in range(served - 1)]
    return out


def decode_window_bytes(cfg, traffic, batches: int, tokens: int) -> int:
    """Least HBM bytes of every decode step of the window."""
    lengths = decode_lengths(traffic, batches, tokens)
    b = traffic["batch"]
    return sum(stored_leaf_bytes(cfg, b) + latent_cache_bytes(cfg, b, n) for n in lengths)
