"""Pallas TPU kernel: chunked Mamba2 SSD scan.

EXPERIMENTS.md §Perf iteration A replaced the per-timestep SSD scan with a
chunked matmul formulation (598x on the dominant memory term); this kernel
is the follow-on lever identified there: the per-chunk (L, L) decay-score
tile and the running (hd, N) state live in VMEM scratch for the whole
sequence, so HBM sees only the streaming x/B/C/dt inputs and the y output.

Grid: (B, H, T/L) — the chunk dimension is innermost and sequential; the
state carries across chunk steps in scratch (same pattern as the K loop of
split_matmul).  Per-(batch, head) working set at L=256, hd=64, N=64 is
~0.6 MB — comfortably inside VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiles import check_chunk as _check_chunk


def _ssd_chunk_kernel(x_ref, b_ref, c_ref, dt_ref, l_ref, lrow_ref, s0_ref,
                      y_ref, sf_ref, state_ref, *, n_chunks: int, L: int):
    nc = pl.program_id(2)

    @pl.when(nc == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0, 0].astype(jnp.float32)            # (L, hd)
    b = b_ref[0, 0, 0].astype(jnp.float32)            # (L, N)
    c = c_ref[0, 0, 0].astype(jnp.float32)            # (L, N)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)          # (L, 1)
    l = l_ref[0, 0, 0]                                # (L, 1) cum. log-decay
    l_row = lrow_ref[0, 0, 0]                         # (1, L) same, as a row
    # l at the chunk's last step, as a masked sum (exact: one term is
    # nonzero) since Mosaic cannot broadcast an offset (1, 1) slice
    last = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0) == L - 1
    l_end = jnp.sum(jnp.where(last, l, 0.0), axis=0, keepdims=True)  # (1, 1)

    h0 = state_ref[...]                               # (hd, N)
    # inter-chunk: y_t += exp(l_t) * C_t . h0
    y_inter = jnp.exp(l) * jnp.dot(c, h0.T,
                                   preferred_element_type=jnp.float32)
    # intra-chunk: W_{tj} = (C_t.B_j) exp(l_t - l_j), j <= t
    s_cb = jnp.dot(c, b.T, preferred_element_type=jnp.float32)  # (L, L)
    ldiff = l - l_row                                 # l_t - l_j
    causal = (jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1))
    w = jnp.where(causal, jnp.exp(ldiff) * s_cb, 0.0)
    xdt = x * dt                                      # (L, hd)
    y_ref[0, 0, 0] = (y_inter + jnp.dot(
        w, xdt, preferred_element_type=jnp.float32)).astype(y_ref.dtype)

    # state update: h' = exp(l_L) h0 + sum_j exp(l_L - l_j) dt_j x_j B_j^T
    decay_end = jnp.exp(l_end - l)                    # (L, 1)
    state_ref[...] = jnp.exp(l_end) * h0 + jnp.dot(
        (xdt * decay_end).T, b, preferred_element_type=jnp.float32)

    @pl.when(nc == n_chunks - 1)
    def _store():
        sf_ref[0, 0] = state_ref[...].astype(sf_ref.dtype)


def ssd_chunk_scan(x: jax.Array, b: jax.Array, c: jax.Array,
                   dt: jax.Array, a: jax.Array, state0: jax.Array, *,
                   chunk: int = None, interpret: bool = False):
    """Chunked SSD scan.

    x: (B,T,H,hd) f32; b/c: (B,T,N); dt: (B,T,H); a: (H,) negative;
    state0: (B,H,hd,N).  Returns (final_state (B,H,hd,N), y (B,T,H,hd)).
    ``chunk=None`` takes the default chunk clamped to T; an explicit chunk
    must divide T exactly and not exceed it, else ValueError (see
    kernels.tiles.check_chunk).
    """
    bsz, t, h, hd = x.shape
    n = b.shape[-1]
    L = _check_chunk("chunk", chunk, 256, t)
    nch = t // L

    # layouts: leading (B, H) program dims, chunked time
    xc = x.transpose(0, 2, 1, 3).reshape(bsz, h, nch, L, hd)
    bc = jnp.broadcast_to(b[:, None], (bsz, h, t, n)) \
        .reshape(bsz, h, nch, L, n)
    cc = jnp.broadcast_to(c[:, None], (bsz, h, t, n)) \
        .reshape(bsz, h, nch, L, n)
    dtc = dt.transpose(0, 2, 1).reshape(bsz, h, nch, L, 1)
    # the within-chunk cumulative log-decay l_t = sum_{j<=t} dt_j a is
    # taken here: the TPU kernel lowering has no cumsum, and the kernel
    # needs l both as a column and as a row
    logd = dtc.astype(jnp.float32) * a.astype(jnp.float32)[None, :, None,
                                                            None, None]
    lc = jnp.cumsum(logd, axis=3)                     # (B, H, nch, L, 1)
    lr = lc.reshape(bsz, h, nch, 1, L)

    grid = (bsz, h, nch)
    y, sf = pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, n_chunks=nch, L=L),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, L, hd),
                         lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, n),
                         lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, n),
                         lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, 1),
                         lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, 1),
                         lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, L),
                         lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, hd, n), lambda i, j, k: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, L, hd),
                         lambda i, j, k: (i, j, k, 0, 0)),
            pl.BlockSpec((1, 1, hd, n), lambda i, j, k: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, nch, L, hd), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, hd, n), state0.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((hd, n), jnp.float32)],
        interpret=interpret,
    )(xc, bc, cc, dtc, lc, lr, state0)
    y = y.reshape(bsz, h, t, hd).transpose(0, 2, 1, 3)
    return sf, y
