"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

K/V are projected through a low-rank latent `c_kv` (kv_lora_rank); the KV
cache stores only (c_kv, k_rope) — a ~4-8x cache compression.  Decode uses
the *absorbed* formulation: W_uk is folded into the query so attention runs
directly in the latent space, and W_uv is applied to the latent context.

Rope rotates halves of the 64-dim rope head (`layers.apply_rope`); the
published weights store those dims interleaved, which is a fixed
permutation of the rope columns of `wq` and `w_krope`.  With the config's
`rope_scaling` (YaRN) the frequencies are YaRN's and the softmax scale is
mscale(factor, mscale_all_dim)^2 / sqrt(qk_nope + qk_rope).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.layers import apply_rope, rms_norm, yarn_softmax_factor
from repro.sharding.ctx import batch_axes, constrain

Params = Dict[str, jax.Array]
_NEG_INF = -1e30


def init_mla(rng, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(rng, 6)
    s = float(1.0 / np.sqrt(d))
    sr = float(1.0 / np.sqrt(r))
    return {
        "wq": jax.random.normal(ks[0], (d, h * (nd + rd)), dtype) * s,
        "w_dkv": jax.random.normal(ks[1], (d, r), dtype) * s,
        "w_krope": jax.random.normal(ks[2], (d, rd), dtype) * s,
        "w_uk": jax.random.normal(ks[3], (r, h, nd), dtype) * sr,
        "w_uv": jax.random.normal(ks[4], (r, h, vd), dtype) * sr,
        "wo": jax.random.normal(ks[5], (h * vd, d), dtype)
        * (float(1.0 / np.sqrt(h * vd))),
        "kv_norm": jnp.ones((r,), dtype),
    }


def _queries(p: Params, x: jax.Array, cfg: ModelConfig, positions):
    b, t, _ = x.shape
    h = cfg.n_heads
    nd, rd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = constrain(x @ p["wq"], batch_axes(), None, "model")
    q = q.reshape(b, t, h, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    return q_nope, q_rope


def _latents(p: Params, x: jax.Array, cfg: ModelConfig, positions):
    c_kv = rms_norm(x @ p["w_dkv"], p["kv_norm"], cfg.norm_eps)
    k_rope = x @ p["w_krope"]                       # single shared rope head
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                        cfg.rope_scaling)[:, :, 0, :]
    return c_kv, k_rope


def softmax_scale(cfg: ModelConfig) -> float:
    return float(yarn_softmax_factor(cfg.rope_scaling)
                 / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))


def _attend_latent(p: Params, q_nope, q_rope, c_kv, k_rope, mask,
                   cfg: ModelConfig) -> jax.Array:
    """Absorbed attention in latent space.
    q_nope: (B,T,H,nd)  q_rope: (B,T,H,rd)
    c_kv:   (B,S,r)     k_rope: (B,S,rd)
    mask:   (T,S) shared by the batch, or (B,T,S) per row
    """
    scale = softmax_scale(cfg)
    q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, p["w_uk"])   # absorb W_uk
    scores = (jnp.einsum("bthr,bsr->bhts", q_lat, c_kv)
              + jnp.einsum("bthd,bsd->bhts", q_rope, k_rope)) * scale
    m = mask[:, None] if mask.ndim == 3 else mask[None, None]
    scores = jnp.where(m, scores.astype(jnp.float32), _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q_nope.dtype)
    ctx = jnp.einsum("bhts,bsr->bthr", probs, c_kv)           # latent context
    out = jnp.einsum("bthr,rhv->bthv", ctx, p["w_uv"])
    b, t = out.shape[:2]
    return out.reshape(b, t, -1) @ p["wo"]


_FLASH_THRESHOLD = 2048
_DECODE_FLASH_THRESHOLD = 8192


def _attend_auto(p: Params, q_nope, q_rope, c_kv, k_rope,
                 cfg: ModelConfig) -> jax.Array:
    """Causal latent attention; memory-bounded flash path for long seqs."""
    b, t = q_nope.shape[:2]
    if t >= _FLASH_THRESHOLD:
        from repro.models.flash import flash_latent_full
        scale = softmax_scale(cfg)
        q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, p["w_uk"])
        ctx = flash_latent_full(q_lat, q_rope, c_kv, k_rope, scale)
        out = jnp.einsum("bthr,rhv->bthv", ctx, p["w_uv"])
        return out.reshape(b, t, -1) @ p["wo"]
    mask = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    return _attend_latent(p, q_nope, q_rope, c_kv, k_rope, mask, cfg)


def mla_full(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    b, t, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    q_nope, q_rope = _queries(p, x, cfg, positions)
    c_kv, k_rope = _latents(p, x, cfg, positions)
    return _attend_auto(p, q_nope, q_rope, c_kv, k_rope, cfg)


def mla_decode(p: Params, x: jax.Array, cfg: ModelConfig,
               cache_ckv: jax.Array, cache_krope: jax.Array,
               pos: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B,1,D); cache_ckv: (B,S,r); cache_krope: (B,S,rd).

    `pos` is a shared scalar, or a (B,) vector when each row runs its own
    timeline (continuous batching): each row's latents are written at its
    own position, its query is roped there, and it attends to the cached
    positions up to it.  Per-row timelines take the masked dense path."""
    b = x.shape[0]
    s = cache_ckv.shape[1]
    per_row = jnp.ndim(pos) == 1
    pos_b = pos if per_row else jnp.broadcast_to(pos[None], (b,))
    q_nope, q_rope = _queries(p, x, cfg, pos_b[:, None])
    c_kv, k_rope = _latents(p, x, cfg, pos_b[:, None])
    if per_row:
        rows = jnp.arange(b)
        cache_ckv = cache_ckv.at[rows, pos_b].set(
            c_kv[:, 0].astype(cache_ckv.dtype))
        cache_krope = cache_krope.at[rows, pos_b].set(
            k_rope[:, 0].astype(cache_krope.dtype))
    else:
        cache_ckv = jax.lax.dynamic_update_slice(
            cache_ckv, c_kv.astype(cache_ckv.dtype), (0, pos, 0))
        cache_krope = jax.lax.dynamic_update_slice(
            cache_krope, k_rope.astype(cache_krope.dtype), (0, pos, 0))
    if s >= _DECODE_FLASH_THRESHOLD and not per_row:
        from repro.models.flash import flash_latent_decode
        q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, p["w_uk"])
        ctx = flash_latent_decode(q_lat, q_rope, cache_ckv.astype(x.dtype),
                                  cache_krope.astype(x.dtype), pos,
                                  softmax_scale(cfg))
        out = jnp.einsum("bthr,rhv->bthv", ctx, p["w_uv"])
        out = out.reshape(b, 1, -1) @ p["wo"]
    else:
        mask = jnp.arange(s)[None, :] <= pos_b[:, None]          # (B, S)
        out = _attend_latent(p, q_nope, q_rope, cache_ckv.astype(x.dtype),
                             cache_krope.astype(x.dtype), mask[:, None, :],
                             cfg)
    return out, cache_ckv, cache_krope


def mla_prefill(p: Params, x: jax.Array, cfg: ModelConfig
                ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Causal MLA returning (out, (c_kv, k_rope)) for the compressed cache."""
    b, t, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    q_nope, q_rope = _queries(p, x, cfg, positions)
    c_kv, k_rope = _latents(p, x, cfg, positions)
    out = _attend_auto(p, q_nope, q_rope, c_kv, k_rope, cfg)
    return out, (c_kv, k_rope)
