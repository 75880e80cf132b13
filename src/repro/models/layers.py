"""Shared neural-net layers (pure JAX, pytree params, scan-friendly).

Conventions:
  * params are plain dicts of jnp arrays; layer stacks hold them with a
    leading (n_layers, ...) axis so the decoder can lax.scan over layers;
  * every attention variant supports three modes: full-sequence causal
    (train/prefill) and single-token decode against a KV cache;
  * shapes: x (B, T, D); caches (B, S, n_kv, hd).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding.ctx import batch_axes, constrain

Params = Dict[str, jax.Array]

_NEG_INF = -1e30


# ---------------------------------------------------------------- norms
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


# ----------------------------------------------------------------- rope
def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's magnitude correction for a context stretched `factor` times
    (DeepSeek-V2's `yarn_get_mscale`)."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_softmax_factor(scaling) -> float:
    """What YaRN multiplies the attention softmax scale by: mscale(factor,
    mscale_all_dim) squared, or 1 without scaling."""
    if scaling is None or not scaling.mscale_all_dim:
        return 1.0
    return yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2


def _yarn_inv_freq(head_dim: int, theta: float, scaling) -> np.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies (float64): pairs below the
    correction range keep the base frequency, pairs above it are divided
    by `factor`, and a linear ramp blends the pairs between.  The range is
    found in units of `head_dim` and the ramp runs over its pairs, as the
    published modelling code has it."""
    def correction_dim(rotations: float) -> float:
        return (head_dim * math.log(scaling.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    base = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                           / head_dim)
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0, 1)
    return base / scaling.factor * ramp + base * (1 - ramp)


def rope_frequencies(head_dim: int, theta: float,
                     scaling=None) -> jax.Array:
    if scaling is not None:
        return jnp.asarray(_yarn_inv_freq(head_dim, theta, scaling),
                           jnp.float32)
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 1e4, scaling=None) -> jax.Array:
    """x: (..., T, H, hd); positions: (..., T).  Rotates halves (the
    dimension i pairs with i + hd/2).  `scaling` (a `RopeScaling`) gives
    YaRN's frequencies and scales cos and sin by mscale / mscale_all_dim."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta, scaling)             # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., T, hd/2)
    cos = jnp.cos(angles)[..., None, :]                      # (..., T, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    if scaling is not None:
        attn = (yarn_mscale(scaling.factor, scaling.mscale)
                / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if attn != 1.0:
            cos, sin = cos * attn, sin * attn
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ------------------------------------------------------------- attention
def _causal_mask(q_len: int, k_len: int, q_offset: int = 0,
                 window: int = 0) -> jax.Array:
    """(q_len, k_len) boolean mask; window > 0 adds a sliding window."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    k_pos = jnp.arange(k_len)[None, :]
    mask = k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    return mask


def attention_scores(q: jax.Array, k: jax.Array, v: jax.Array,
                     mask: jax.Array) -> jax.Array:
    """q: (B,T,H,hd) k/v: (B,S,Hkv,hd) grouped-query attention core.

    `mask` is (T, S) shared across the batch, or (B, T, S) when rows mask
    different key ranges (mixed-length left-padded batches / per-slot
    continuous-batching timelines)."""
    b, t, h, hd = q.shape
    hkv = k.shape[2]
    group = h // hkv
    qg = q.reshape(b, t, hkv, group, hd)
    scores = jnp.einsum("bthgd,bshd->bhgts", qg, k) / np.sqrt(hd)
    m = mask[:, None, None] if mask.ndim == 3 else mask[None, None, None]
    scores = jnp.where(m, scores.astype(jnp.float32), _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgts,bshd->bthgd", probs, v)
    return out.reshape(b, t, h, hd)


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 1e4
    sliding_window: int = 0          # 0 = full


def init_attention(rng, d_model: int, spec: AttnSpec,
                   dtype=jnp.bfloat16) -> Params:
    keys = jax.random.split(rng, 4)
    h, kv, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    s = float(1.0 / np.sqrt(d_model))
    p = {
        "wq": jax.random.normal(keys[0], (d_model, h * hd), dtype) * s,
        "wk": jax.random.normal(keys[1], (d_model, kv * hd), dtype) * s,
        "wv": jax.random.normal(keys[2], (d_model, kv * hd), dtype) * s,
        "wo": jax.random.normal(keys[3], (h * hd, d_model), dtype) *
        (float(1.0 / np.sqrt(h * hd))),
    }
    if spec.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    if spec.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _project_qkv(p: Params, x: jax.Array, spec: AttnSpec, positions):
    b, t, _ = x.shape
    h, kv, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    q = constrain(x @ p["wq"], batch_axes(), None, "model")
    k = constrain(x @ p["wk"], batch_axes(), None, "model")
    v = constrain(x @ p["wv"], batch_axes(), None, "model")
    if spec.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, t, h, hd)
    k = k.reshape(b, t, kv, hd)
    v = v.reshape(b, t, kv, hd)
    if spec.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    q = apply_rope(q, positions, spec.rope_theta)
    k = apply_rope(k, positions, spec.rope_theta)
    return q, k, v


# sequences at/above this length take the memory-bounded flash path
FLASH_THRESHOLD = 2048
DECODE_FLASH_THRESHOLD = 8192


def _attend(q, k, v, spec: AttnSpec) -> jax.Array:
    t = q.shape[1]
    if t >= FLASH_THRESHOLD:
        from repro.models.flash import flash_full
        return flash_full(q, k, v, window=spec.sliding_window)
    mask = _causal_mask(t, t, window=spec.sliding_window)
    return attention_scores(q, k, v, mask)


def attention_full(p: Params, x: jax.Array, spec: AttnSpec) -> jax.Array:
    """Causal self-attention over the whole sequence (train / prefill)."""
    b, t, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    q, k, v = _project_qkv(p, x, spec, positions)
    out = _attend(q, k, v, spec)
    return out.reshape(b, t, -1) @ p["wo"]


def attention_decode(p: Params, x: jax.Array, spec: AttnSpec,
                     cache_k: jax.Array, cache_v: jax.Array,
                     pos: jax.Array, start=None) -> Tuple[jax.Array,
                                                          jax.Array,
                                                          jax.Array]:
    """One-token decode. x: (B,1,D); cache: (B,S,kv,hd).

    `pos` is a shared scalar, or a (B,) vector when rows sit at different
    timeline positions (continuous batching: each slot has its own clock).
    `start` is an optional (B,) vector of first-valid cache positions; keys
    below it are masked out (left-padded batches).  The flash-decode path
    only handles the shared-scalar unpadded case, so per-row timelines fall
    back to the masked dense path regardless of cache length.
    """
    b, _, _ = x.shape
    s = cache_k.shape[1]
    per_row = jnp.ndim(pos) == 1
    pos_b = pos if per_row else jnp.broadcast_to(pos[None], (b,))
    q, k, v = _project_qkv(p, x, spec, pos_b[:, None])
    if per_row:
        rows = jnp.arange(b)
        cache_k = cache_k.at[rows, pos_b].set(k[:, 0].astype(cache_k.dtype))
        cache_v = cache_v.at[rows, pos_b].set(v[:, 0].astype(cache_v.dtype))
    else:
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k.astype(cache_k.dtype), (0, pos, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v.astype(cache_v.dtype), (0, pos, 0, 0))
    if s >= DECODE_FLASH_THRESHOLD and not per_row and start is None:
        from repro.models.flash import flash_decode
        out = flash_decode(q, cache_k.astype(q.dtype),
                           cache_v.astype(q.dtype), pos,
                           window=spec.sliding_window)
    else:
        k_pos = jnp.arange(s)
        mask = k_pos[None, :] <= pos_b[:, None]                  # (B, S)
        if spec.sliding_window > 0:
            mask &= k_pos[None, :] > pos_b[:, None] - spec.sliding_window
        if start is not None:
            mask &= k_pos[None, :] >= start[:, None]
        out = attention_scores(q, cache_k.astype(q.dtype),
                               cache_v.astype(q.dtype), mask[:, None, :])
    return out.reshape(b, 1, -1) @ p["wo"], cache_k, cache_v


# ------------------------------------------------------------------- mlp
def init_mlp(rng, d_model: int, d_ff: int, dtype=jnp.bfloat16) -> Params:
    k1, k2, k3 = jax.random.split(rng, 3)
    s_in = float(1.0 / np.sqrt(d_model))
    s_out = float(1.0 / np.sqrt(d_ff))
    return {
        "w_gate": jax.random.normal(k1, (d_model, d_ff), dtype) * s_in,
        "w_up": jax.random.normal(k2, (d_model, d_ff), dtype) * s_in,
        "w_down": jax.random.normal(k3, (d_ff, d_model), dtype) * s_out,
    }


def mlp(p: Params, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(constrain(x @ p["w_gate"], batch_axes(), None, "model"))
    h = h * constrain(x @ p["w_up"], batch_axes(), None, "model")
    return constrain(h @ p["w_down"], batch_axes(), None, None)


def attention_prefill(p: Params, x: jax.Array, spec: AttnSpec, start=None
                      ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Causal self-attention returning (out, (k, v)) for cache filling.

    `start` is an optional (B,) vector of first real token positions for
    left-padded batches; keys before a row's start never enter its softmax,
    so a padded prompt attends exactly as it would alone (RoPE phases are
    relative, so the constant position shift cancels in the scores)."""
    b, t, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    q, k, v = _project_qkv(p, x, spec, positions)
    if start is None:
        out = _attend(q, k, v, spec)
    else:
        mask = _causal_mask(t, t, window=spec.sliding_window)    # (t, t)
        mask = mask[None] & (jnp.arange(t)[None, None, :] >=
                             start[:, None, None])               # (B, t, t)
        out = attention_scores(q, k, v, mask)
    return out.reshape(b, t, -1) @ p["wo"], (k, v)
