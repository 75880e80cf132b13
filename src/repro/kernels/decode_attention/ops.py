"""Jitted batched wrapper for the decode-attention kernel, plus the
registry lowerings that let graph-IR "attention" nodes execute through the
shared `(x, w, op)` unit contract (see kernels/registry.py) — exclusive,
head-split, and kv-block-split co-execution."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.coexec import (COEXEC_AXIS, LANE_AXIS, _merge_stacked,
                               _stacked_spec,
                               cached_coexec_program, gather_stacked,
                               mesh_fingerprint, split_for_mesh)
from repro.kernels import registry
from repro.kernels.decode_attention.decode_attention import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref


@functools.partial(jax.jit, static_argnames=("window", "bs", "interpret",
                                             "use_kernel"))
def decode_attention_op(q, k, v, pos, *, window: int = 0, bs: int = None,
                        interpret: bool = False, use_kernel: bool = True):
    """Batched decode attention.

    q: (B, H, hd); k/v: (B, S, kv, hd); pos scalar (shared write position).
    """
    if not use_kernel:
        fn = functools.partial(decode_attention_ref, window=window)
        return jax.vmap(lambda qq, kk, vv: fn(qq, kk, vv, pos))(q, k, v)
    fn = functools.partial(decode_attention, window=window, bs=bs,
                           interpret=interpret)
    return jax.vmap(lambda qq, kk, vv: fn(qq, kk, vv, pos))(q, k, v)


# ------------------------------------------------- registry unit lowering

def _unit_attention(x, w, op, *, use_kernel: bool, interpret: bool = False,
                    tile=None):
    """`(x, w, op)` unit contract of an AttnOp node: `x` is the flattened
    (1, H*hd) query block, `w` the stacked (2, S, KV, hd) KV cache."""
    q = x.reshape(op.H, op.hd)
    k, v = w[0], w[1]
    pos = op.S - 1                   # attend to the whole recorded cache
    if use_kernel:
        # the tile-less default keeps the historical min(512, S) block so
        # untuned plans stay bit-identical with pre-tile builds
        bs = (min(512, op.S) if tile is None
              else registry.resolve_tile(op, tile).get("bs"))
        out = decode_attention_op(q[None], k[None], v[None], pos,
                                  window=op.window, bs=bs,
                                  interpret=interpret)[0]
    else:
        out = decode_attention_ref(q, k, v, pos, window=op.window)
    return out.reshape(1, op.H * op.hd)


def attention_unit_pallas(x, w, op, *, interpret: bool = False, tile=None):
    return _unit_attention(x, w, op, use_kernel=True, interpret=interpret,
                           tile=tile)


def attention_unit_oracle(x, w, op):
    return _unit_attention(x, w, op, use_kernel=False)


registry.register_lowering("attention", pallas=attention_unit_pallas,
                           oracle=attention_unit_oracle)


# ------------------------------------------------ head-split co-execution
#
# Heads are KV-major (ref.py reshapes q to (kv, g, hd)), so a split at a
# GQA-group boundary owns a *contiguous* output-channel range — exactly the
# channel-split layout coexec.py's gather/chaining machinery expects.  Each
# side attends its own KV heads over the full cache; per-head softmax is
# independent, so the split is bit-identical to the unsplit oracle.

def _head_split_sides(op, n_fast):
    g = op.H // op.KV
    kv_fast = n_fast // g
    kv_pad = max(kv_fast, op.KV - kv_fast)
    return g, kv_fast, kv_pad


def pack_head_split(w, op, n_fast, mesh):
    """(2, S, KV, hd) stacked KV cache -> (split, (2, 2, S, kv_pad, hd)):
    per-side KV-head slices, zero-padded to the wider side (SPMD uniform
    shapes) and stacked on the co-execution group axis."""
    registry.validate_axis_split(op, "head", n_fast)
    _, kv_fast, kv_pad = _head_split_sides(op, n_fast)

    def side(lo, n):
        buf = jnp.zeros((2, op.S, kv_pad, op.hd), w.dtype)
        return buf.at[:, :, :n].set(w[:, :, lo:lo + n])

    packed = jnp.stack([side(0, kv_fast), side(kv_fast, op.KV - kv_fast)])
    packed = jax.device_put(                     # consumption sharding:
        packed, NamedSharding(mesh, P(COEXEC_AXIS, None, None, None, None)))
    split = split_for_mesh(op.H * op.hd, n_fast * op.hd, mesh)
    return split, packed


def run_head_split(x, packed, split, mesh, op, n_fast, *, gather=True,
                   x_plan=None, use_pallas=False, interpret=False,
                   tile=None):
    """Head-split decode attention over the two-group mesh.

    x: (1, H*hd) replicated query block — or, with `x_plan`, a producer's
    group-local (2, 1, c_pad) stack (chained input, gather elided).
    Returns (1, H*hd) if gather else the group-local (2, 1, c_pad) stack.
    Numerics are mode-independent (`op.mode` picks a latency profile, not
    a different math), so the oracle math serves both modes.
    """
    g, _, kv_pad = _head_split_sides(op, n_fast)
    h_pad = kv_pad * g
    pos = op.S - 1
    c_loc = split.c_pad // int(mesh.shape[LANE_AXIS])

    def build():
        def local(x_l, w_l):
            x_full = (_merge_stacked(x_l, x_plan) if x_plan is not None
                      else x_l)
            q = x_full.reshape(op.H, op.hd)

            def pad_q(qs):
                return jnp.zeros((h_pad, op.hd),
                                 q.dtype).at[:qs.shape[0]].set(qs)

            first = jax.lax.axis_index(COEXEC_AXIS) == 0
            # padded q heads hit zero-padded KV heads -> zero outputs,
            # which sit past each side's valid channel range and are
            # sliced off
            q_side = jnp.where(first, pad_q(q[:n_fast]), pad_q(q[n_fast:]))
            k_l, v_l = w_l[0][0], w_l[0][1]      # (S, kv_pad, hd) each
            out = decode_attention_ref(q_side, k_l, v_l, pos,
                                       window=op.window)
            y = out.reshape(1, h_pad * op.hd)
            y = jnp.zeros((1, split.c_pad),
                          y.dtype).at[:, :h_pad * op.hd].set(y)
            # each device computed the whole side; emit this lane's
            # channel shard so the global stack is the canonical
            # (2, 1, c_pad) layout
            lane = jax.lax.axis_index(LANE_AXIS)
            y = jax.lax.dynamic_slice_in_dim(y, lane * c_loc, c_loc,
                                             axis=-1)
            return y[None]                       # (1, 1, c_pad / lanes)

        x_spec = _stacked_spec(3) if x_plan is not None else P()
        kwargs = dict(mesh=mesh,
                      in_specs=(x_spec,
                                P(COEXEC_AXIS, None, None, None, None)),
                      out_specs=_stacked_spec(3))
        return jax.shard_map(local, **kwargs)

    key = ("attn-head", op, n_fast, x_plan, mesh_fingerprint(mesh),
           tuple(x.shape), str(x.dtype), str(packed.dtype), tile)
    y = cached_coexec_program(key, build)(x, packed)
    if not gather:
        return y
    return gather_stacked(y, split, mesh)


registry.register_split_lowering("attention", "head",
                                 pack=pack_head_split, run=run_head_split)


# -------------------------------------------- kv-block-split co-execution
#
# For long caches each side computes *all* H heads over its slice of cache
# positions, producing flash-style softmax partials (running max m, weight
# sum l, unnormalized output o) that merge inside the program via an
# all-gather over the group axis.  The merged output is always
# materialized (replicated) — this axis never chains group-local — and is
# tolerance-exact, not bit-exact (the log-sum-exp merge reassociates the
# softmax reduction), which is why the registry gates it to S >=
# KV_BLOCK_MIN_S and window == 0.

def pack_kv_block_split(w, op, n_fast, mesh):
    """(2, S, KV, hd) stacked KV cache -> (split, (2, 2, s_pad, KV, hd)):
    per-side cache-position slices, fast side owning rows [0, n_fast)."""
    registry.validate_axis_split(op, "kv-block", n_fast)
    s_pad = max(n_fast, op.S - n_fast)

    def side(lo, n):
        buf = jnp.zeros((2, s_pad, op.KV, op.hd), w.dtype)
        return buf.at[:, :n].set(w[:, lo:lo + n])

    packed = jnp.stack([side(0, n_fast), side(n_fast, op.S - n_fast)])
    packed = jax.device_put(
        packed, NamedSharding(mesh, P(COEXEC_AXIS, None, None, None, None)))
    # degenerate channel plan: both sides contribute every output channel;
    # the executor keys on the materialized (1, H*hd) result, not on it
    split = split_for_mesh(op.H * op.hd, op.H * op.hd, mesh)
    return split, packed


def run_kv_block_split(x, packed, split, mesh, op, n_fast, *, gather=True,
                       x_plan=None, use_pallas=False, interpret=False,
                       tile=None):
    """kv-block-split decode attention: returns the materialized (1, H*hd)
    output regardless of `gather` (the merge happens inside the program)."""
    s_pad = max(n_fast, op.S - n_fast)
    g = op.H // op.KV

    def build():
        return _build_kv_block_program(x_plan, mesh, op, n_fast, s_pad, g)

    key = ("attn-kv-block", op, n_fast, x_plan, mesh_fingerprint(mesh),
           tuple(x.shape), str(x.dtype), str(packed.dtype), tile)
    return cached_coexec_program(key, build)(x, packed)


def _build_kv_block_program(x_plan, mesh, op, n_fast, s_pad, g):
    def local(x_l, w_l):
        x_full = _merge_stacked(x_l, x_plan) if x_plan is not None else x_l
        q = x_full.reshape(op.KV, g, op.hd).astype(jnp.float32)
        k_l = jnp.swapaxes(w_l[0][0], 0, 1).astype(jnp.float32)
        v_l = jnp.swapaxes(w_l[0][1], 0, 1).astype(jnp.float32)
        first = jax.lax.axis_index(COEXEC_AXIS) == 0
        valid = jnp.where(first, n_fast, op.S - n_fast)
        # registry gates this axis to window == 0 and decode reads the
        # whole cache (pos == S-1), so the only mask is the side boundary
        mask = jnp.arange(s_pad) < valid
        scores = jnp.einsum("hgd,hsd->hgs", q, k_l) / jnp.sqrt(
            jnp.float32(op.hd))
        scores = jnp.where(mask[None, None, :], scores, -1e30)
        m = jnp.max(scores, axis=-1)                        # (kv, g)
        e = jnp.exp(scores - m[..., None]) * mask[None, None, :]
        l = jnp.sum(e, axis=-1)                             # (kv, g)
        o = jnp.einsum("hgs,hsd->hgd", e, v_l)              # unnormalized
        ms = jax.lax.all_gather(m, COEXEC_AXIS, axis=0)     # (2, kv, g)
        ls = jax.lax.all_gather(l, COEXEC_AXIS, axis=0)
        os_ = jax.lax.all_gather(o, COEXEC_AXIS, axis=0)
        mg = jnp.max(ms, axis=0)
        scale = jnp.exp(ms - mg[None])                      # (2, kv, g)
        den = jnp.sum(ls * scale, axis=0)
        num = jnp.sum(os_ * scale[..., None], axis=0)
        out = num / den[..., None]
        return out.reshape(1, op.H * op.hd).astype(x_full.dtype)

    x_spec = _stacked_spec(3) if x_plan is not None else P()
    # the merge reads all-gathered partials, so every device holds the same
    # output; shard_map's varying-axis inference cannot see that
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, P(COEXEC_AXIS, None, None, None, None)),
        out_specs=P(), check_vma=False)


registry.register_split_lowering("attention", "kv-block",
                                 pack=pack_kv_block_split,
                                 run=run_kv_block_split)
