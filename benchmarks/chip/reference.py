"""Plain references, in float32 `jax.numpy`, written from the configuration
files alone.  Nothing here imports the program under test.

* `graph_forward`: an op chain as a network file lists it (convolutions
  with SAME padding, pooling, a projection), with the shape adaptation the
  file states for its projection shortcuts.
* `block_graph_layers`: the planner's decoder-block graph at batch 1
  (embedding-row projection; per block q projection, decode attention over
  a cache, o projection, residual add, two MLP projections, residual add),
  as layers that `graph_forward` runs.
* `Qwen2`: a Qwen2 decoder (RMSNorm, rotary q/k with bias, causal
  multi-head attention, SiLU-gated MLP, untied output head) over whole
  sequences.

Each takes a `mode` that sets the arithmetic of its matrix products:
`"fp32"` (float32 operands, full precision: the reference), and two
controls a step below a configuration's precision: `"bf16x3"` (float32
operands split into a bfloat16 high and low part and multiplied in three
products, as a TPU's three-pass float32 does) and `"fp8"` (operands
rounded to float8 e4m3).
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("fp32", "bf16x3", "fp8")


# ----------------------------------------------------------- arithmetic

def _split_bf16(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def _fp8(a):
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def product(fn, a, b, mode: str):
    """`fn(a, b)` (a bilinear product) in float32 under `mode`."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode == "fp32":
        return fn(a, b)
    if mode == "bf16x3":
        ah, al = _split_bf16(a)
        bh, bl = _split_bf16(b)
        return fn(ah, bh) + (fn(ah, bl) + fn(al, bh))
    if mode == "fp8":
        return fn(_fp8(a), _fp8(b))
    raise ValueError(f"unknown mode {mode!r}; choices: {MODES}")


def mm(a, b, mode: str = "fp32"):
    return product(lambda x, y: jnp.matmul(x, y, precision=HIGHEST),
                   a, b, mode)


def einsum(spec: str, a, b, mode: str = "fp32"):
    return product(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                   a, b, mode)


def conv_same(x, w, stride: int, mode: str = "fp32"):
    """NHWC x HWIO convolution with SAME padding."""
    return product(lambda a, b: jax.lax.conv_general_dilated(
        a, b, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST),
        x, w, mode)


def rel_err(y, ref) -> float:
    """max |y - ref| / max |ref|, in float64 on the host; inf when `y` is
    not finite or has another shape."""
    y = np.asarray(y, np.float64)
    ref = np.asarray(ref, np.float64)
    if y.shape != ref.shape or not np.isfinite(y).all():
        return math.inf
    return float(np.abs(y - ref).max() / max(np.abs(ref).max(), 1e-30))


# ----------------------------------------------------- seeded weights

def fan_in(layer: dict) -> int:
    kind = layer["kind"]
    if kind == "conv":
        return layer["k"] * layer["k"] * layer["c_in"]
    if kind == "linear":
        return layer["c_in"]
    if kind == "attention":
        return layer["head_dim"]
    raise ValueError(f"layer kind {kind!r} has no weights")


def weight_shape(layer: dict) -> tuple:
    kind = layer["kind"]
    if kind == "conv":
        return (layer["k"], layer["k"], layer["c_in"], layer["c_out"])
    if kind == "linear":
        return (layer["c_in"], layer["c_out"])
    if kind == "attention":                      # stacked K/V cache
        return (2, layer["positions"], layer["kv_heads"], layer["head_dim"])
    raise ValueError(f"layer kind {kind!r} has no weights")


def seeded_weights(layers: Iterable[dict], seed: int):
    """The weights of an op chain or graph, one per weighted layer in
    order, drawn as the configuration states: standard normal values from
    numpy's generator seeded with `seed`, divided by the square root of
    the layer's fan-in, in float32.  A generator: one layer at a time."""
    rng = np.random.default_rng(seed)
    for layer in layers:
        if layer["kind"] in ("conv", "linear", "attention"):
            w = rng.standard_normal(weight_shape(layer))
            yield (w / np.sqrt(max(1, fan_in(layer)))).astype(np.float32)


# ------------------------------------------------------------ op chain

def fit_axis(x, axis: int, size: int):
    """Tile then crop one axis to `size` (the files' shape adaptation)."""
    cur = x.shape[axis]
    if cur < size:
        reps = [1] * x.ndim
        reps[axis] = -(-size // cur)
        x = jnp.tile(x, reps)
    return jax.lax.slice_in_dim(x, 0, size, axis=axis)


def adapt(x, layer: dict):
    if layer["kind"] == "conv":
        if x.ndim == 2:
            x = x.reshape(1, 1, *x.shape)
        for axis, size in ((1, layer["h"]), (2, layer["w"]),
                           (3, layer["c_in"])):
            x = fit_axis(x, axis, size)
        return x
    if layer["kind"] == "linear":
        shape = (layer["rows"], layer["c_in"])
    else:                                        # attention: one query row
        shape = (1, layer["heads"] * layer["head_dim"])
    flat = fit_axis(x.reshape(-1), 0, int(np.prod(shape)))
    return flat.reshape(shape)


def pool(x, out_bytes: int):
    """Global average pooling when the file records one value per
    channel, else max pooling down to the recorded edge."""
    c = x.shape[-1]
    edge = max(1, math.isqrt(max(1, out_bytes // (4 * c))))
    if edge <= 1:
        return jnp.mean(x, axis=(1, 2), keepdims=True)
    r = max(1, x.shape[1] // edge)
    x = x[:, :edge * r, :edge * r, :]
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, r, r, 1),
                                 (1, r, r, 1), "VALID")


def decode_attention(q_row, kv, layer: dict, mode: str = "fp32"):
    """One query row (1, H * hd) against a stacked (2, S, KV, hd) cache,
    attending to every cached position; heads grouped KV-major."""
    h, kvh, hd = layer["heads"], layer["kv_heads"], layer["head_dim"]
    q = q_row.reshape(kvh, h // kvh, hd)
    k = jnp.swapaxes(kv[0], 0, 1)                # (KV, S, hd)
    v = jnp.swapaxes(kv[1], 0, 1)
    scores = einsum("hgd,hsd->hgs", q, k, mode) / np.sqrt(hd)
    window = layer.get("window", 0)
    if window:
        s = k.shape[1]
        pos = np.arange(s)
        scores = jnp.where((pos > s - 1 - window)[None, None], scores,
                           -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return einsum("hgs,hsd->hgd", probs, v, mode).reshape(1, h * hd)


def _apply(layer: dict, x, w, mode: str):
    kind = layer["kind"]
    if kind == "conv":
        s = layer["s"]
        y = conv_same(adapt(x, layer), w, s, mode)
        return y[:, :max(1, layer["h"] // s), :max(1, layer["w"] // s), :]
    if kind == "linear":
        return mm(adapt(x, layer), w, mode)
    if kind == "attention":
        return decode_attention(adapt(x, layer), w, layer, mode)
    if kind == "pool":
        return pool(x, layer["out_bytes"])
    raise ValueError(f"unknown layer kind {kind!r}")


def graph_forward(layers: List[dict], weights, x, mode: str = "fp32",
                  dtype: Optional[str] = None):
    """Run layers in order.  A layer's input is the previous layer's
    output, or the layers its `inputs` names (a `"add"` layer sums them).
    `weights` is an iterable of per-layer weights (`seeded_weights`);
    with `dtype`, each weight is first rounded to that type, as the
    configuration stores it."""
    weights = iter(weights)
    acts: Dict[str, jax.Array] = {}
    prev = jnp.asarray(x, jnp.float32)
    for i, layer in enumerate(layers):
        name = layer.get("id", str(i))
        srcs = [acts[s] for s in layer.get("inputs", ())] or [prev]
        if layer["kind"] == "add":
            y = srcs[0]
            for s in srcs[1:]:
                y = y + s
        else:
            w = None
            if layer["kind"] != "pool":
                w = jnp.asarray(next(weights))
                if dtype is not None:
                    w = w.astype(dtype).astype(jnp.float32)
            y = _apply(layer, srcs[0], w, mode)
        acts[name] = y
        prev = y
    return prev


def block_graph_layers(cfg: dict, cache_len: int) -> List[dict]:
    """The decoder-block graph of a decoder file, as layers."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    layers = [{"id": "embed", "kind": "linear", "rows": 1, "c_in": d,
               "c_out": d}]
    prev = "embed"
    for i in range(cfg["num_hidden_layers"]):
        b = f"b{i}"
        layers += [
            {"id": f"{b}.q", "kind": "linear", "rows": 1, "c_in": d,
             "c_out": h * hd, "inputs": [prev]},
            {"id": f"{b}.attn", "kind": "attention", "heads": h,
             "kv_heads": kv, "head_dim": hd, "positions": cache_len,
             "inputs": [f"{b}.q"]},
            {"id": f"{b}.o", "kind": "linear", "rows": 1, "c_in": h * hd,
             "c_out": d, "inputs": [f"{b}.attn"]},
            {"id": f"{b}.attn_res", "kind": "add",
             "inputs": [prev, f"{b}.o"]},
            {"id": f"{b}.up", "kind": "linear", "rows": 1, "c_in": d,
             "c_out": f, "inputs": [f"{b}.attn_res"]},
            {"id": f"{b}.down", "kind": "linear", "rows": 1, "c_in": f,
             "c_out": d, "inputs": [f"{b}.up"]},
            {"id": f"{b}.mlp_res", "kind": "add",
             "inputs": [f"{b}.attn_res", f"{b}.down"]},
        ]
        prev = f"{b}.mlp_res"
    return layers


# ---------------------------------------------------------------- Qwen2

def rms_norm(x, scale, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta: float):
    """Rotary embedding on (B, T, H, hd), halves rotated (GPT-NeoX form,
    as Qwen2 applies it), positions 0 .. T - 1."""
    t, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def qwen2_layer(x, p: dict, cfg: dict, mode: str):
    """One decoder layer on (B, T, d); `p` holds float32 weights."""
    b, t, d = x.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    eps = cfg["rms_norm_eps"]
    a = rms_norm(x, p["ln1"], eps)
    q = (mm(a, p["wq"], mode) + p["bq"]).reshape(b, t, h, hd)
    k = (mm(a, p["wk"], mode) + p["bk"]).reshape(b, t, kv, hd)
    v = (mm(a, p["wv"], mode) + p["bv"]).reshape(b, t, kv, hd)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    q = q.reshape(b, t, kv, h // kv, hd)
    s = einsum("btkgd,bskd->bkgts", q, k, mode) / np.sqrt(hd)
    causal = np.tril(np.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = einsum("bkgts,bskd->btkgd", jax.nn.softmax(s, axis=-1), v, mode)
    x = x + mm(o.reshape(b, t, h * hd), p["wo"], mode)
    a = rms_norm(x, p["ln2"], eps)
    g = jax.nn.silu(mm(a, p["w_gate"], mode)) * mm(a, p["w_up"], mode)
    return x + mm(g, p["w_down"], mode)


def qwen2_head(x, ln_f, unembed, cfg: dict, mode: str):
    return mm(rms_norm(x, ln_f, cfg["rms_norm_eps"]), unembed, mode)


class Qwen2:
    """A Qwen2 decoder's logits over whole sequences, one layer at a time.

    `weights` maps "embed" (V, d), "ln_f" (d,), "unembed" (d, V) and
    "layers", a list of per-layer dicts (ln1, ln2, wq, wk, wv, wo, bq, bk,
    bv, w_gate, w_up, w_down); each is cast to float32 as it is used."""

    def __init__(self, cfg: dict, mode: str = "fp32"):
        self.cfg = cfg
        self._layer = jax.jit(lambda x, p: qwen2_layer(x, p, cfg, mode))
        self._head = jax.jit(lambda x, a, b: qwen2_head(x, a, b, cfg, mode))

    def hidden(self, tokens, weights):
        x = _f32(weights["embed"])[jnp.asarray(tokens)]
        for p in weights["layers"]:
            x = self._layer(x, {k: _f32(v) for k, v in p.items()})
        return x

    def logits(self, tokens, weights):
        """(B, T, V) float32 logits of `tokens` (B, T)."""
        return self._head(self.hidden(tokens, weights),
                          _f32(weights["ln_f"]), _f32(weights["unembed"]))


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


@jax.jit
def served_gaps(ref_logits, served):
    """For each position: how far the reference's logit of the `served`
    token lies below the reference's best (B, T)."""
    best = ref_logits.max(-1)
    got = jnp.take_along_axis(ref_logits, served[..., None], -1)[..., 0]
    return best - got
