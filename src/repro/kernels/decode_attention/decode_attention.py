"""Pallas TPU kernel: flash-style single-token decode attention.

The long-context decode workhorse (decode_32k / long_500k input shapes):
one query token attends to a KV cache of S positions without ever
materializing the (H, S) score matrix in HBM.  Online-softmax running
(max, sum, acc) state lives in VMEM scratch; the cache is streamed through
VMEM in (bs, head_dim) blocks.

Grid: (n_kv_heads, S/bs) — S innermost/sequential.  GQA is handled by
processing all `group = n_heads // n_kv_heads` query heads of one KV head
together as the row dimension of the MXU ops.

Causality/window masking is positional: positions > pos (and, for sliding
windows, <= pos - window) are masked.  `pos` arrives as a (1,) SMEM
scalar; the window is static.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiles import check_tile as _check_tile

_NEG_INF = -1e30


def _decode_attn_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                        acc_ref, m_ref, l_ref, *,
                        bs: int, n_s: int, window: int):
    s_idx = pl.program_id(1)

    @pl.when(s_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)              # (g, hd)
    k = k_ref[0].astype(jnp.float32)              # (bs, hd)
    v = v_ref[0].astype(jnp.float32)              # (bs, hd)
    hd = q.shape[-1]

    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) \
        * (hd ** -0.5)                             # (g, bs)

    pos = pos_ref[0]
    k_pos = s_idx * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    mask = k_pos <= pos
    if window > 0:
        mask &= k_pos > pos - window
    scores = jnp.where(mask, scores, _NEG_INF)

    m_prev = m_ref[:, :1]                          # (g, 1)
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)                    # (g, bs)
    l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha \
        + jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(s_idx == n_s - 1)
    def _store():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     pos: jax.Array, *, window: int = 0, bs: int = None,
                     interpret: bool = False) -> jax.Array:
    """q: (n_heads, hd); k/v: (S, n_kv, hd); pos: scalar int32.

    Returns (n_heads, hd).  Single-sequence; vmap over batch in ops.py.
    ``bs=None`` takes the default cache block clamped to the lane-padded
    cache length; an explicit ``bs`` past that cap raises (see
    kernels.tiles.check_tile).
    """
    h, hd = q.shape
    s, kv, _ = k.shape
    g = h // kv
    g_pad = max(8, -(-g // 8) * 8)
    # bs is the second-minor dim of the (1, bs, hd) cache block: the TPU
    # lowering needs it a multiple of 8
    bs = _check_tile("bs", bs, 512, s, 8, lim_align=128)

    # (kv, g_pad, hd) query layout; (kv, S_pad, hd) cache layout
    qg = q.reshape(kv, g, hd)
    if g_pad != g:
        qg = jnp.pad(qg, ((0, 0), (0, g_pad - g), (0, 0)))
    kt = jnp.swapaxes(k, 0, 1)
    vt = jnp.swapaxes(v, 0, 1)
    s_pad = (-s) % bs
    if s_pad:
        # padded positions carry k_pos > pos and are masked out
        kt = jnp.pad(kt, ((0, 0), (0, s_pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, s_pad), (0, 0)))
    sp = kt.shape[1]
    grid = (kv, sp // bs)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    out = pl.pallas_call(
        functools.partial(_decode_attn_kernel, bs=bs, n_s=grid[1],
                          window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, g_pad, hd), lambda hh, ss: (hh, 0, 0)),
            pl.BlockSpec((1, bs, hd), lambda hh, ss: (hh, ss, 0)),
            pl.BlockSpec((1, bs, hd), lambda hh, ss: (hh, ss, 0)),
        ],
        out_specs=pl.BlockSpec((1, g_pad, hd), lambda hh, ss: (hh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((kv, g_pad, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g_pad, hd), jnp.float32),
            pltpu.VMEM((g_pad, 1), jnp.float32),
            pltpu.VMEM((g_pad, 1), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention",
    )(pos_arr, qg, kt, vt)
    return out[:, :g, :].reshape(h, hd)
