"""Operations and bytes of a DeepSeek-V2 decoder served one token per slot
and step, from a configuration file's shapes (config.json keys) and the
program's expert counters.

A decode step multiplies each active row through the latent attention's
projections (absorbed: W_uk folded into the query, W_uv applied to the
latent context, so the row's products are those of the layer's weights),
through the dense MLP or through 6 routed and the 2 shared experts, and
through the output head.  Attention over the cache runs in the latent
space: per head and cached position, q.c_kv (kv_lora_rank), q.k_rope
(qk_rope_head_dim) and p.c_kv (kv_lora_rank), each a multiply-add.

Bytes: the weights outside the routed experts once a step, the routed
experts that the step's active rows chose (the program counts them: the
grouped matmul reads no other), and the latent cache rows that the active
slots attend over and write.  Embedding rows are a gather and left out.
"""
from __future__ import annotations

from typing import Dict, Iterable

from counts import dtype_bytes


def shapes(cfg: dict) -> Dict[str, int]:
    """Parameters per part of the stack."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, nd = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rd, vd = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    f, e = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    layers = cfg["num_hidden_layers"]
    dense = min(cfg["first_k_dense_replace"], layers)
    return {
        "attention": d * h * (nd + rd) + d * (r + rd) + r * h * (nd + vd)
        + h * vd * d,
        "dense_mlp": 3 * d * cfg["intermediate_size"],
        "expert": 3 * d * f,
        "shared": 3 * d * f * cfg["n_shared_experts"],
        "router": d * e,
        "head": d * cfg["vocab_size"],
        "embed": cfg["vocab_size"] * d,
        "layers": layers, "dense_layers": dense, "moe_layers": layers - dense,
        "experts": e, "per_token": cfg["num_experts_per_tok"],
    }


def norm_params(cfg: dict) -> int:
    """RMSNorm scales: two per layer, the latent's, and the final one."""
    d = cfg["hidden_size"]
    return cfg["num_hidden_layers"] * (2 * d + cfg["kv_lora_rank"]) + d


def weight_bytes(cfg: dict, dtype: str) -> int:
    """Every weight the configuration holds (the router in float32)."""
    s = shapes(cfg)
    item = dtype_bytes(dtype)
    moe = s["experts"] * s["expert"] + s["shared"]
    return (item * (s["embed"] + s["head"] + s["layers"] * s["attention"]
                    + s["dense_layers"] * s["dense_mlp"]
                    + s["moe_layers"] * moe + norm_params(cfg))
            + 4 * s["moe_layers"] * s["router"])


def step_fixed_bytes(cfg: dict, dtype: str) -> int:
    """Bytes a decode step reads whatever the routing: every weight but the
    routed experts and the embedding."""
    s = shapes(cfg)
    item = dtype_bytes(dtype)
    return (item * (s["head"] + s["layers"] * s["attention"]
                    + s["dense_layers"] * s["dense_mlp"]
                    + s["moe_layers"] * s["shared"] + norm_params(cfg))
            + 4 * s["moe_layers"] * s["router"])


def active_params(cfg: dict) -> int:
    """Weights one decode row multiplies: attention, the dense MLP or the
    chosen and shared experts with the router, and the head."""
    s = shapes(cfg)
    return (s["layers"] * s["attention"] + s["dense_layers"] * s["dense_mlp"]
            + s["moe_layers"] * (s["per_token"] * s["expert"] + s["shared"]
                                 + s["router"])
            + s["head"])


def latent_width(cfg: dict) -> int:
    """Values cached per position and layer: c_kv and k_rope."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def attention_flops(cfg: dict) -> int:
    """Per cached position, layer and row."""
    return 2 * cfg["num_attention_heads"] * (2 * cfg["kv_lora_rank"]
                                             + cfg["qk_rope_head_dim"])


def serve_totals(cfg: dict, dtype: str, requests: Iterable[tuple],
                 steps: int, experts_hit: int) -> Dict[str, float]:
    """Operations and bytes of serving `requests` ((prompt_len, new_tokens)
    pairs) over `steps` decode steps, in which the active rows chose
    `experts_hit` experts (summed over MoE layers and steps)."""
    s = shapes(cfg)
    item = dtype_bytes(dtype)
    slot_steps = 0
    positions = 0                         # sum over slot-steps of pos + 1
    for prompt_len, new_tokens in requests:
        t = prompt_len + new_tokens - 1   # steps the request holds a slot
        slot_steps += t
        positions += t * (t + 1) // 2
    flops = (2 * active_params(cfg) * slot_steps
             + s["layers"] * attention_flops(cfg) * positions)
    cache = s["layers"] * item * latent_width(cfg) * (positions + slot_steps)
    return {"slot_steps": slot_steps, "flops": float(flops),
            "bytes": float(steps * step_fixed_bytes(cfg, dtype)
                           + experts_hit * item * s["expert"] + cache)}


def gmm_totals(cfg: dict, dtype: str, experts_hit: int, rows: int
               ) -> Dict[str, float]:
    """The routed experts' grouped matmuls (gate, up, down) over `rows`
    (row, choice) pairs that chose `experts_hit` experts: the experts'
    weights once each, each pair's row in and out of each product."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    item = dtype_bytes(dtype)
    return {"flops": float(2 * 3 * d * f * rows),
            "bytes": float(item * (experts_hit * 3 * d * f
                                   + rows * 3 * (d + f)))}
