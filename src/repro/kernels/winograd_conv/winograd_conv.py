"""Pallas TPU kernel: Winograd F(2x2, 3x3) convolution.

This is the paper's kernel-selection case study (Fig. 6b): TFLite switches
3x3 convolutions to a Winograd kernel above C_out >= 128, producing the
latency discontinuity the white-box predictor captures.  Here the same
algorithm is adapted to TPU: input/output tile transforms are cheap
elementwise/small-matrix work done in jnp, and the hot spot — 16
independent (P, C_in) x (C_in, C_out) matmuls in the Hadamard domain — runs
as one Pallas kernel with the Hadamard point as the leading grid dimension.

Layout: U (16, P, C_in) transformed input tiles, V (16, C_in, C_out)
transformed filters; the kernel computes M[g] = U[g] @ V[g] with MXU-aligned
(bm, bn, bk) VMEM blocks, then jnp applies the inverse transform A^T M A.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiles import check_tile as _check_tile

# F(2x2, 3x3) transform matrices (Lavin & Gray 2016)
_BT = np.array([[1, 0, -1, 0],
                [0, 1, 1, 0],
                [0, -1, 1, 0],
                [0, 1, 0, -1]], np.float32)
_G = np.array([[1, 0, 0],
               [0.5, 0.5, 0.5],
               [0.5, -0.5, 0.5],
               [0, 0, 1]], np.float32)
_AT = np.array([[1, 1, 1, 0],
                [0, 1, -1, -1]], np.float32)


def _hadamard_matmul_kernel(u_ref, v_ref, o_ref, acc_ref, *, n_k: int):
    k_idx = pl.program_id(3)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(u_ref[0], v_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k - 1)
    def _store():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def hadamard_matmul(u: jax.Array, v: jax.Array, *, bm: int = None,
                    bn: int = None, bk: int = None,
                    interpret: bool = False) -> jax.Array:
    """M[g] = U[g] @ V[g] for g in [0, 16).  u: (16,P,K); v: (16,K,N).

    None tile params resolve to the default blocking clamped to the
    problem extents; explicit values must already be legal (see
    kernels.tiles.check_tile) or ValueError is raised.
    """
    g, p, k = u.shape
    _, _, n = v.shape
    bm = _check_tile("bm", bm, 128, p, 8)
    bn = _check_tile("bn", bn, 128, n, 128)
    bk = _check_tile("bk", bk, 256, k, 128)
    pp, kp, np_ = (-p) % bm, (-k) % bk, (-n) % bn
    if pp or kp:
        u = jnp.pad(u, ((0, 0), (0, pp), (0, kp)))
    if kp or np_:
        v = jnp.pad(v, ((0, 0), (0, kp), (0, np_)))
    grid = (g, u.shape[1] // bm, v.shape[2] // bn, u.shape[2] // bk)

    out = pl.pallas_call(
        functools.partial(_hadamard_matmul_kernel, n_k=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda gg, i, j, kk: (gg, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda gg, i, j, kk: (gg, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda gg, i, j, kk: (gg, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, u.shape[1], v.shape[2]), u.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="winograd_conv",
    )(u, v)
    return out[:, :p, :n]


def winograd_conv2d(x: jax.Array, w: jax.Array, *, interpret: bool = False,
                    bm: int = None, bn: int = None, bk: int = None
                    ) -> jax.Array:
    """3x3 stride-1 SAME conv via F(2x2,3x3).

    x: (B, H, W, C_in); w: (3, 3, C_in, C_out) -> (B, H, W, C_out).
    """
    b, h, wdt, cin = x.shape
    kh, kw, _, cout = w.shape
    assert (kh, kw) == (3, 3)
    ho, wo = h, wdt                       # SAME, stride 1
    th, tw = -(-ho // 2), -(-wo // 2)     # 2x2 output tiles

    # pad input: 1 halo + tile remainder
    xp = jnp.pad(x, ((0, 0), (1, 2 * th - ho + 1), (1, 2 * tw - wo + 1),
                     (0, 0)))
    # gather 4x4 input tiles at stride 2: (B, th, tw, 4, 4, C)
    tiles = jnp.stack(
        [jnp.stack([xp[:, i:i + 2 * th:2, j:j + 2 * tw:2, :]
                    for j in range(4)], axis=3) for i in range(4)], axis=3)
    # input transform: U = B^T d B  over the 4x4 dims
    bt = jnp.asarray(_BT, x.dtype)
    u = jnp.einsum("ij,bhwjkc,lk->bhwilc", bt, tiles, bt)
    p = b * th * tw
    u = u.reshape(p, 16, cin).transpose(1, 0, 2)          # (16, P, Cin)

    # filter transform: V = G g G^T
    gm = jnp.asarray(_G, w.dtype)
    v = jnp.einsum("ij,jkcn,lk->ilcn", gm, w, gm)          # (4,4,Cin,Cout)
    v = v.reshape(16, cin, cout)

    m = hadamard_matmul(u, v, bm=bm, bn=bn, bk=bk, interpret=interpret)

    # inverse transform: y = A^T M A
    m = m.transpose(1, 0, 2).reshape(b, th, tw, 4, 4, cout)
    at = jnp.asarray(_AT, x.dtype)
    y = jnp.einsum("ij,bhwjkc,lk->bhwilc", at, m, at)      # (B,th,tw,2,2,C)
    y = y.transpose(0, 1, 3, 2, 4, 5).reshape(b, 2 * th, 2 * tw, cout)
    return y[:, :ho, :wo, :]
