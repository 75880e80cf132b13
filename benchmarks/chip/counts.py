"""Operations and bytes from shapes, and the table of chip peaks.

Everything here is computed from the shapes in a configuration file, never
from the program under test, so a change to the program cannot move the
yardstick.  Counts are of the work the algorithm needs: a batch-1
projection is 2 * K * N operations however far the kernel pads its rows.
"""
from __future__ import annotations

from typing import Dict, Iterable, List

#: Published peaks per chip, keyed by JAX's `device_kind`.  Source: Google
#: Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB
#: HBM at 819 GB/s).  A kind that is not here is an error, not a default.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak is known for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def config_dtype(cfg: dict) -> str:
    """The type a configuration file serves in (`dtype`, or a decoder
    file's `torch_dtype`)."""
    return cfg.get("dtype") or cfg["torch_dtype"]


def dtype_bytes(dtype: str) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}[dtype]


# ------------------------------------------------------------ single ops

def conv_flops(h_out: int, w_out: int, c_in: int, c_out: int, k: int) -> int:
    """Multiply-adds of a K x K convolution, counted as two operations."""
    return 2 * h_out * w_out * c_out * k * k * c_in


def conv_bytes(h_in: int, w_in: int, c_in: int, h_out: int, w_out: int,
               c_out: int, k: int, itemsize: int) -> int:
    """Input and output activations once, weights once."""
    return itemsize * (h_in * w_in * c_in + h_out * w_out * c_out
                       + k * k * c_in * c_out)


def matmul_flops(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def matmul_bytes(m: int, k: int, n: int, itemsize: int) -> int:
    return itemsize * (m * k + k * n + m * n)


def decode_attention_flops(heads: int, head_dim: int, positions: int) -> int:
    """One query token against `positions` cached keys and values:
    q.k and p.v, each a multiply-add per head, position and lane."""
    return 4 * heads * head_dim * positions


def decode_attention_bytes(heads: int, kv_heads: int, head_dim: int,
                           positions: int, itemsize: int) -> int:
    """The K and V cache rows read once, query in and output out."""
    return itemsize * (2 * positions * kv_heads * head_dim
                       + 2 * heads * head_dim)


# ------------------------------------------------- whole configurations

def chain_ops(layers: Iterable[dict], dtype: str) -> List[dict]:
    """Per-layer operations and bytes of an op chain (a network file's
    `layers`); pooling layers count no operations."""
    item = dtype_bytes(dtype)
    out = []
    for layer in layers:
        kind = layer["kind"]
        if kind == "conv":
            h, w, c, co, k, s = (layer[x] for x in
                                 ("h", "w", "c_in", "c_out", "k", "s"))
            ho, wo = max(1, h // s), max(1, w // s)
            out.append({"kind": "conv",
                        "flops": conv_flops(ho, wo, c, co, k),
                        "bytes": conv_bytes(h, w, c, ho, wo, co, k, item)})
        elif kind == "linear":
            m, k, n = layer["rows"], layer["c_in"], layer["c_out"]
            out.append({"kind": "linear", "flops": matmul_flops(m, k, n),
                        "bytes": matmul_bytes(m, k, n, item)})
        else:
            out.append({"kind": kind, "flops": 0, "bytes": 0})
    return out


def decoder_block_graph_ops(cfg: dict, cache_len: int, dtype: str
                            ) -> List[dict]:
    """Per-node operations and bytes of the planner's decoder-block graph
    at batch 1: an embedding-row projection, then per block the q and o
    projections, decode attention over `cache_len` positions, and the two
    MLP projections (the residual adds count no operations)."""
    item = dtype_bytes(dtype)
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    ops = [{"kind": "linear", "flops": matmul_flops(1, d, d),
            "bytes": matmul_bytes(1, d, d, item)}]
    for _ in range(cfg["num_hidden_layers"]):
        ops += [
            {"kind": "linear", "flops": matmul_flops(1, d, h * hd),
             "bytes": matmul_bytes(1, d, h * hd, item)},
            {"kind": "attention",
             "flops": decode_attention_flops(h, hd, cache_len),
             "bytes": decode_attention_bytes(h, kv, hd, cache_len, item)},
            {"kind": "linear", "flops": matmul_flops(1, h * hd, d),
             "bytes": matmul_bytes(1, h * hd, d, item)},
            {"kind": "linear", "flops": matmul_flops(1, d, f),
             "bytes": matmul_bytes(1, d, f, item)},
            {"kind": "linear", "flops": matmul_flops(1, f, d),
             "bytes": matmul_bytes(1, f, d, item)},
        ]
    return ops


def decoder_matmul_params(cfg: dict) -> int:
    """Weights that every decode row multiplies: all layers' projections
    and the output head (the embedding is a row gather, not a matmul)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def decoder_weight_bytes(cfg: dict, dtype: str) -> int:
    """Bytes a decode step reads from its weights: the matmul weights,
    biases and norm scales (embedding rows are negligible and left out)."""
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    small = cfg["num_hidden_layers"] * (2 * d + h * hd + 2 * kv * hd) + d
    return dtype_bytes(dtype) * (decoder_matmul_params(cfg) + small)


def decoder_serve_totals(cfg: dict, dtype: str, requests: Iterable[tuple],
                         steps: int) -> Dict[str, float]:
    """Operations and bytes of serving `requests` ((prompt_len, new_tokens)
    pairs) one token per slot and step, over `steps` decode steps.

    Every slot-step multiplies one row through the matmul weights and
    attends over the positions written so far; each step reads the weights
    once and the cached K/V rows that its active slots attend over."""
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    layers = cfg["num_hidden_layers"]
    item = dtype_bytes(dtype)
    slot_steps = 0
    positions = 0                         # sum over slot-steps of pos + 1
    for prompt_len, new_tokens in requests:
        t = prompt_len + new_tokens - 1   # steps the request holds a slot
        slot_steps += t
        positions += t * (t + 1) // 2
    flops = (2 * decoder_matmul_params(cfg) * slot_steps
             + layers * decode_attention_flops(h, hd, 1) * positions)
    kv_bytes = layers * item * 2 * kv * hd * (positions + slot_steps)
    return {"slot_steps": slot_steps, "flops": float(flops),
            "bytes": float(steps * decoder_weight_bytes(cfg, dtype)
                           + kv_bytes)}


def roofline_s(op: dict, peak_row: Dict[str, float]) -> float:
    """The least time one op can take on the chip: operations over peak or
    bytes over bandwidth, whichever is larger."""
    return max(op["flops"] / peak_row["bf16_flops"],
               op["bytes"] / peak_row["hbm_bytes_per_s"])
