"""Jitted wrapper for the chunked-SSD Pallas kernel, plus the registry
lowerings that let graph-IR "ssm" nodes execute through the shared
`(x, w, op)` unit contract (see kernels/registry.py) — exclusive and
state-split co-execution."""
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
from repro.kernels.ssd_chunk.ref import ssd_scan_ref
from repro.kernels.ssd_chunk.ssd_chunk import ssd_chunk_scan


@functools.partial(jax.jit, static_argnames=("chunk", "interpret",
                                             "use_kernel"))
def ssd_chunk_op(x, b, c, dt, a, state0, *, chunk: int = None,
                 interpret: bool = False, use_kernel: bool = True):
    if not use_kernel:
        return ssd_scan_ref(x, b, c, dt, a, state0)
    return ssd_chunk_scan(x, b, c, dt, a, state0, chunk=chunk,
                          interpret=interpret)


# ------------------------------------------------- registry unit lowering

def _unpack_params(w, op):
    """Slice the flat parameter vector of an SSMOp into the scan operands,
    applying the stabilizing transforms (dt bounded positive, a strictly
    negative) so a generically-initialized node never overflows the decay
    exp(dt * a).  Shared by the Pallas path and the oracle, so the two
    stay elementwise comparable."""
    t, h, hd, n = op.T, op.H, op.hd, op.N
    sizes = [t * n, t * n, t * h, h, h * hd * n]
    parts, lo = [], 0
    for s in sizes:
        parts.append(w[lo:lo + s])
        lo += s
    b = parts[0].reshape(1, t, n)
    c = parts[1].reshape(1, t, n)
    dt = 0.05 + 0.2 * jax.nn.sigmoid(parts[2].reshape(1, t, h))
    a = -(0.1 + jnp.abs(parts[3]))
    state0 = parts[4].reshape(1, h, hd, n)
    return b, c, dt, a, state0


def _unit_ssm(x, w, op, *, use_kernel: bool, interpret: bool = False,
              tile=None):
    """`(x, w, op)` unit contract of an SSMOp node: `x` is the (T, H*hd)
    inner-projected token block, `w` the flat B/C/dt/a/state0 vector."""
    xb = x.reshape(1, op.T, op.H, op.hd)
    b, c, dt, a, state0 = _unpack_params(w, op)
    # the tile-less default keeps the historical min(256, T) chunk so
    # untuned plans stay bit-identical with pre-tile builds
    chunk = (min(256, op.T) if tile is None
             else registry.resolve_tile(op, tile).get("chunk"))
    _, y = ssd_chunk_op(xb, b, c, dt, a, state0,
                        chunk=chunk, interpret=interpret,
                        use_kernel=use_kernel)
    return y.reshape(op.T, op.H * op.hd)


def ssm_unit_pallas(x, w, op, *, interpret: bool = False, tile=None):
    return _unit_ssm(x, w, op, use_kernel=True, interpret=interpret,
                     tile=tile)


def ssm_unit_oracle(x, w, op):
    return _unit_ssm(x, w, op, use_kernel=False)


registry.register_lowering("ssm", pallas=ssm_unit_pallas,
                           oracle=ssm_unit_oracle)


# ----------------------------------------------- state-split co-execution
#
# The SSD scan is independent per state head: B/C projections are shared,
# but dt, a, and the state tensor slice head-wise, and head h owns output
# channels [h*hd, (h+1)*hd) — a contiguous range, so the channel-split
# gather/chaining machinery applies unchanged and the split is
# bit-identical to the unsplit oracle.

def pack_state_split(w, op, n_fast, mesh):
    """Flat B/C/dt/a/state0 vector -> (split, (2, L_pad)): per-side flat
    parameter vectors with H replaced by the padded per-side head count.
    B and C are shared, so they replicate into both sides.

    Every nonlinearity is applied HERE, eagerly: the stabilizing
    transforms (`_unpack_params`, like the unsplit oracle path) AND the
    decay `exp(dt * a)` the scan consumes.  Inside the jitted SPMD
    program the GSPMD partitioner's fusion choices can round composite
    nonlinear chains differently than the oracle's program, and the
    recurrence amplifies a 1-ulp decay difference — so the traced side
    carries only mul/add/einsum over pre-transformed values.  Padded head
    slots hold zeros (decay 0, dt 0, state0 0 -> zero outputs past the
    valid channel range)."""
    registry.validate_axis_split(op, "ssm-state", n_fast)
    t, h, hd, n = op.T, op.H, op.hd, op.N
    h_pad = max(n_fast, h - n_fast)
    b, c, dt, a, state0 = _unpack_params(w, op)
    decay = jnp.exp(dt * a)                      # (1, t, h), eager

    def side(lo, m):
        dt_s = jnp.zeros((t, h_pad), dt.dtype).at[:, :m].set(
            dt[0, :, lo:lo + m])
        dec_s = jnp.zeros((t, h_pad), decay.dtype).at[:, :m].set(
            decay[0, :, lo:lo + m])
        s0_s = jnp.zeros((h_pad, hd, n), state0.dtype).at[:m].set(
            state0[0, lo:lo + m])
        return jnp.concatenate([b.reshape(-1), c.reshape(-1),
                                dt_s.reshape(-1), dec_s.reshape(-1),
                                s0_s.reshape(-1)])

    packed = jnp.stack([side(0, n_fast), side(n_fast, h - n_fast)])
    packed = jax.device_put(                     # consumption sharding
        packed, NamedSharding(mesh, P(COEXEC_AXIS, None)))
    split = split_for_mesh(h * hd, n_fast * hd, mesh)
    return split, packed


def _unpack_packed_side(w_side, op, h_pad):
    """Positional unpack of one side of `pack_state_split`'s layout —
    values are already transformed, so no nonlinearities here."""
    t, hd, n = op.T, op.hd, op.N
    sizes = [t * n, t * n, t * h_pad, t * h_pad, h_pad * hd * n]
    parts, lo = [], 0
    for s in sizes:
        parts.append(w_side[lo:lo + s])
        lo += s
    return (parts[0].reshape(1, t, n), parts[1].reshape(1, t, n),
            parts[2].reshape(1, t, h_pad), parts[3].reshape(1, t, h_pad),
            parts[4].reshape(1, h_pad, hd, n))


def _ssd_scan_decay(x, b, c, dt, decay, state0):
    """`ssd_scan_ref` with the decay factor passed in precomputed —
    the scan body `ssd_scan_ref` runs, minus its leading `exp`."""

    def step(s, inp):
        x_t, b_t, c_t, dec_t, dt_t = inp
        upd = dt_t[..., None, None] * (x_t[..., :, None]
                                       * b_t[:, None, None, :])
        s = dec_t[..., None, None] * s + upd
        return s, jnp.einsum("bhdn,bn->bhd", s, c_t)

    seq = (x.swapaxes(0, 1), b.swapaxes(0, 1), c.swapaxes(0, 1),
           decay.swapaxes(0, 1), dt.swapaxes(0, 1))
    sf, ys = jax.lax.scan(step, state0, seq)
    return sf, ys.swapaxes(0, 1)


def run_state_split(x, packed, split, mesh, op, n_fast, *, gather=True,
                    x_plan=None, use_pallas=False, interpret=False,
                    tile=None):
    """State-split SSD scan over the two-group mesh.

    x: (T, H*hd) replicated token block — or, with `x_plan`, a producer's
    group-local (2, T, c_pad) stack.  Returns (T, H*hd) if gather else the
    group-local (2, T, c_pad) stack.  Numerics are mode-independent
    (`op.mode` picks chunked vs recurrent latency, not different math).
    """
    t, h, hd = op.T, op.H, op.hd
    h_pad = max(n_fast, h - n_fast)
    c_loc = split.c_pad // int(mesh.shape[LANE_AXIS])

    def build():
        def local(x_l, w_l):
            x_full = (_merge_stacked(x_l, x_plan) if x_plan is not None
                      else x_l)
            xb = x_full.reshape(t, h, hd)

            def pad_x(sl):
                return jnp.zeros((t, h_pad, hd), x_full.dtype).at[
                    :, :sl.shape[1]].set(sl)

            first = jax.lax.axis_index(COEXEC_AXIS) == 0
            # padded heads see zero inputs and zero initial state -> zero
            # outputs past each side's valid channel range, sliced below
            x_side = jnp.where(first, pad_x(xb[:, :n_fast]),
                               pad_x(xb[:, n_fast:]))
            b, c, dt, decay, state0 = _unpack_packed_side(w_l[0], op, h_pad)
            _, y = _ssd_scan_decay(x_side[None], b, c, dt, decay, state0)
            y2 = y[0].reshape(t, h_pad * hd)
            out = jnp.zeros((t, split.c_pad), y2.dtype).at[
                :, :h_pad * hd].set(y2)
            # each device computed the whole side; emit this lane's
            # channel shard so the global stack is the canonical
            # (2, T, c_pad) layout
            lane = jax.lax.axis_index(LANE_AXIS)
            out = jax.lax.dynamic_slice_in_dim(out, lane * c_loc, c_loc,
                                               axis=-1)
            return out[None]                     # (1, T, c_pad / lanes)

        x_spec = _stacked_spec(3) if x_plan is not None else P()
        # check_vma=False: a chained input varies over both mesh axes while
        # the scan's initial state varies over the group axis only, and
        # scan needs its carry's varying axes fixed
        return jax.shard_map(local, mesh=mesh,
                             in_specs=(x_spec, P(COEXEC_AXIS, None)),
                             out_specs=_stacked_spec(3), check_vma=False)

    key = ("ssm-state", op, n_fast, x_plan, mesh_fingerprint(mesh),
           tuple(x.shape), str(x.dtype), str(packed.dtype), tile)
    y = cached_coexec_program(key, build)(x, packed)
    if not gather:
        return y
    return gather_stacked(y, split, mesh)


registry.register_split_lowering("ssm", "ssm-state",
                                 pack=pack_state_split, run=run_state_split)
