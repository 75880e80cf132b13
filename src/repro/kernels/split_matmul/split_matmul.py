"""Pallas TPU kernel: channel-partitioned matmul (the co-execution primitive).

Computes Y = X @ W[:, c0 : c0 + width] — one compute group's share of a
channel-split linear layer (paper Section 2, Fig. 4) — as a blocked MXU
matmul with explicit VMEM tiling.

TPU adaptation of the paper's workgroup story: the BlockSpec (bm, bn, bk)
plays the role of the OpenCL workgroup shape; N-padding of the channel
slice to bn is the tile-quantization analogue of the delegate's float4
slicing, and is exactly the discontinuity the white-box predictor features
expose (DESIGN.md §2B).

Grid: (M/bm, W/bn, K/bk) with a VMEM fp32 accumulator; the K grid dimension
is innermost and accumulating.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiles import check_tile as _check_tile


def _split_matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    """One (bm, bn) output tile; accumulates over the K grid dimension."""
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def split_matmul(x: jax.Array, w: jax.Array, c0: int, width: int, *,
                 bm: int = None, bn: int = None, bk: int = None,
                 interpret: bool = False) -> jax.Array:
    """Y = X @ W[:, c0:c0+width] via a blocked Pallas kernel.

    x: (M, K); w: (K, N).  c0/width are static Python ints (the
    partitioner's decision is made offline).  Returns (M, width).

    Tile params left as None take the default blocking clamped to the
    problem extents; explicitly requested tiles must already be legal
    (aligned and within the padded extents) or ValueError is raised —
    clamping lives in registry.TileSpec.clamp_tile, not here.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2 and 0 <= c0 and c0 + width <= n
    assert width > 0

    bm = _check_tile("bm", bm, 128, m, 8)
    bn = _check_tile("bn", bn, 128, width, 128)
    bk = _check_tile("bk", bk, 512, k, 128)

    # slice this group's channels; pad all dims to block multiples
    w_slice = jax.lax.slice(w, (0, c0), (k, c0 + width))
    m_pad, k_pad, n_pad = (-m) % bm, (-k) % bk, (-width) % bn
    if m_pad or k_pad:
        x = jnp.pad(x, ((0, m_pad), (0, k_pad)))
    if k_pad or n_pad:
        w_slice = jnp.pad(w_slice, ((0, k_pad), (0, n_pad)))
    mp, kp = x.shape
    np_ = w_slice.shape[1]
    grid = (mp // bm, np_ // bn, kp // bk)

    out = pl.pallas_call(
        functools.partial(_split_matmul_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="split_matmul",
    )(x, w_slice)
    return out[:m, :width]


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult
