"""TPU-native transfer of the paper's co-execution mechanism.

The paper splits one operation's output channels across two heterogeneous
compute devices that share memory.  On a TPU mesh the analogous structure is
an **uneven channel split across two device groups of one mesh axis**:

  * group 0 ("fast", the GPU analogue) owns `c_fast` output channels,
  * group 1 ("slow", the CPU analogue) owns `C_out - c_fast`,

with the split chosen by the same predictor-driven partitioner, where the
per-group throughputs play the role of the CPU/GPU latency models and the
all-gather that materializes the full output plays the role of
`T_overhead` (see core/sync.collective_overhead_us).

SPMD requires uniform per-device shapes, so both groups are padded to the
same local width `c_pad` and masked — the exact analogue of the paper's
channel-alignment granularity (grid step 8 / float4 slices).  When the
*consumer* is also channel-split (the paper's "subsequent CPU and GPU
operations read the shared output directly"), `gather=False` keeps the
result group-local as a `(2, ..., c_pad)` stack, and the consumer op takes
that stack directly via `x_plan=`: the reconstruction happens *inside* the
consumer's shard_map program (a fused all-gather), eliding the explicit
reshard-to-replicated synchronization point between the two ops — the SVM
analogue of skipping the map/unmap pair.  Both linear (`coexec_matmul`) and
convolution (`coexec_conv2d`) support split execution and chaining.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

COEXEC_AXIS = "coexec"
LANE_AXIS = "lane"


@dataclasses.dataclass(frozen=True)
class SplitPlan:
    """Uneven output-channel split across two device groups."""

    c_out: int
    c_fast: int                  # channels owned by group 0
    align: int = 8               # channel alignment granularity

    @property
    def c_slow(self) -> int:
        return self.c_out - self.c_fast

    @property
    def c_pad(self) -> int:
        """Uniform local width (SPMD): max of the two shares, aligned."""
        a = self.align
        return -(-max(self.c_fast, self.c_slow) // a) * a


def throughput_split(c_out: int, fast_share: float, align: int = 8) -> SplitPlan:
    """Balance channels proportionally to group throughputs (the closed-form
    optimum of the paper's objective for linear cost models)."""
    c_fast = int(round(c_out * fast_share / align)) * align
    c_fast = min(max(c_fast, 0), c_out)
    return SplitPlan(c_out=c_out, c_fast=c_fast, align=align)


def split_for_mesh(c_out: int, c_fast: int, mesh: Mesh,
                   align: int = 8) -> SplitPlan:
    """Alignment-aware re-split: a partitioner decision (c_gpu channels on
    the fast group) lowered onto a concrete mesh.  The padded local width
    must shard evenly over the mesh's lane axis, so the alignment is lifted
    to lcm(align, lane_count)."""
    lanes = int(mesh.shape[LANE_AXIS])
    return SplitPlan(c_out=c_out, c_fast=c_fast,
                     align=int(np.lcm(align, lanes)))


def pack_weights(w: jax.Array, plan: SplitPlan,
                 mesh: Mesh | None = None) -> jax.Array:
    """(..., C_out) -> (2, ..., c_pad): per-group padded weight slices.

    Works for linear (C_in, C_out) and conv (K, K, C_in, C_out) weights —
    the split is always over the trailing output-channel dim.  With
    `mesh`, the packed stack is placed in its consumption sharding
    (group- and lane-wise) up front, so repeated co-execution calls on
    the same packed weights do not re-shard per call.
    """
    lead = w.shape[:-1]
    wf = jnp.zeros(lead + (plan.c_pad,), w.dtype).at[..., :plan.c_fast].set(
        w[..., :plan.c_fast])
    ws = jnp.zeros(lead + (plan.c_pad,), w.dtype).at[..., :plan.c_slow].set(
        w[..., plan.c_fast:])
    packed = jnp.stack([wf, ws])
    if mesh is not None:
        packed = jax.device_put(
            packed, NamedSharding(mesh, _stacked_spec(packed.ndim)))
    return packed


def coexec_mesh(devices=None) -> Mesh:
    """A two-group mesh along the co-execution axis.

    Degrades gracefully: with fewer than 2 devices there is nothing to
    co-execute against, so the mesh collapses to a **single group** holding
    every device — callers detect this via `mesh_groups(mesh) == 1` and run
    ops exclusively (the executor does exactly that).  Odd device counts
    >= 3 drop the last device to keep the two groups even.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    if not devices:
        raise ValueError("coexec_mesh needs at least one device")
    n = len(devices) - len(devices) % 2
    if n < 2:
        arr = np.array(devices).reshape(1, len(devices))
    else:
        arr = np.array(devices[:n]).reshape(2, n // 2)
    return Mesh(arr, (COEXEC_AXIS, LANE_AXIS))


def mesh_groups(mesh: Mesh) -> int:
    """Number of co-execution groups (2 = split-capable, 1 = degraded)."""
    return int(mesh.shape[COEXEC_AXIS])


_PROGRAM_CACHE: dict = {}


def mesh_fingerprint(mesh: Mesh) -> tuple:
    """Hashable identity of a mesh (device ids × axis names)."""
    return (tuple(int(d.id) for d in mesh.devices.flat),
            tuple(mesh.axis_names))


def cached_coexec_program(key: tuple, build):
    """One jitted program per eager co-execution call-site configuration.

    Eager shard_map closures are rebuilt on every call, which defeats
    jax's trace and compile caches (fresh function identity each time)
    and turns every co-executed node into a retrace + recompile.  Call
    sites pass a hashable key covering everything that shapes the traced
    program (op, split geometry, input shapes/dtypes, mesh) plus a
    zero-argument `build` returning the shard_map-wrapped local; the
    jitted program is built once per distinct key and reused for the
    life of the process.  (Eager shard_map dispatch executes the body
    primitive-by-primitive across the mesh — orders of magnitude slower
    than one compiled program, and re-traced per call besides.)

    Jitting routes the program through the GSPMD partitioner, whose
    fusion choices can perturb the fp32 rounding of *composite*
    nonlinearities (sigmoid-style rewrites); lowerings that need
    bit-identity against an eager oracle keep such transforms out of the
    traced body (they pre-apply them at weight-pack time) so the traced
    program is fusion-stable."""
    fn = _PROGRAM_CACHE.get(key)
    if fn is None:
        fn = _PROGRAM_CACHE[key] = jax.jit(build())
    return fn


def _merge_stacked(x_local: jax.Array, x_plan: SplitPlan) -> jax.Array:
    """Reconstruct the full (..., C) activation from this device's shard of
    a (2, ..., c_pad) group-local stack — *inside* a shard_map program.

    This is the elided boundary: instead of an explicit reshard to
    replicated between producer and consumer, the consumer all-gathers the
    stack over (lane, coexec) as part of its own program and strips the
    alignment padding with static slices.
    """
    xg = jax.lax.all_gather(x_local[0], LANE_AXIS,
                            axis=x_local.ndim - 2, tiled=True)
    xs = jax.lax.all_gather(xg, COEXEC_AXIS, axis=0)
    return jnp.concatenate([xs[0][..., :x_plan.c_fast],
                            xs[1][..., :x_plan.c_slow]], axis=-1)


def _stacked_spec(ndim: int) -> P:
    """(2, ..., c_pad) stacks shard group-wise + lane-wise on channels."""
    return P(COEXEC_AXIS, *([None] * (ndim - 2)), LANE_AXIS)


def gather_stacked(y: jax.Array, plan: SplitPlan, mesh: Mesh) -> jax.Array:
    """Materialize the combined output of a group-local (2, ..., c_pad)
    stack — the paper's synchronization point.

    Reshard each group's slice to replicated first: concatenating slices
    that are still lane-sharded miscompiles on some jax releases (values
    double through the partitioner), and the gather IS the sync point, so
    an explicit reshard is the honest lowering.
    """
    rep = NamedSharding(mesh, P())
    y_fast = jax.device_put(y[0][..., :plan.c_fast], rep)
    y_slow = jax.device_put(y[1][..., :plan.c_slow], rep)
    return jnp.concatenate([y_fast, y_slow], axis=-1)


def gather_stacked_traced(y: jax.Array, plan: SplitPlan,
                          mesh: Mesh) -> jax.Array:
    """`gather_stacked` spelled as a shard_map program, safe under jit.

    `gather_stacked` reshards with `jax.device_put`, which cannot appear
    inside a traced (jitted) computation.  Fused segment programs instead
    reconstruct the full activation with the same `_merge_stacked`
    collective the chained consumers use (all-gather over lane + coexec,
    static padding slices) and emit it replicated.  Both spellings are
    pure data movement over identical values, so they agree bit-for-bit.
    """

    def merge(y_local: jax.Array) -> jax.Array:
        return _merge_stacked(y_local, plan)

    # the all-gathers leave every device the same value, but shard_map's
    # varying-axis inference cannot see that: the replication is asserted
    return jax.shard_map(merge, mesh=mesh,
                         in_specs=(_stacked_spec(y.ndim),),
                         out_specs=P(), check_vma=False)(y)


def coexec_matmul(x: jax.Array, packed_w: jax.Array, plan: SplitPlan,
                  mesh: Mesh, *, gather: bool = True,
                  x_plan: SplitPlan | None = None) -> jax.Array:
    """Channel-split matmul: each group computes its slice of X @ W.

    x: (L, C_in) replicated — or, with `x_plan`, the producer's group-local
    (2, L, x_plan.c_pad) stack (chained input, no reshard in between).
    packed_w: (2, C_in, c_pad) sharded on group.
    Returns (L, C_out) if gather else the group-local (2, L, c_pad) stack.
    """

    def build():
        def local(x_l, w_l):
            # w_l: (1, C_in, c_pad) — this group's slice
            x_full = (_merge_stacked(x_l, x_plan) if x_plan is not None
                      else x_l)
            return (x_full @ w_l[0])[None]    # (1, L, c_pad)

        x_spec = _stacked_spec(3) if x_plan is not None else P()
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(x_spec, _stacked_spec(3)),
            out_specs=_stacked_spec(3))

    key = ("matmul", x_plan, mesh_fingerprint(mesh),
           tuple(x.shape), str(x.dtype),
           tuple(packed_w.shape), str(packed_w.dtype))
    y = cached_coexec_program(key, build)(x, packed_w)  # (2, L, c_pad)

    if not gather:
        return y
    return gather_stacked(y, plan, mesh)


def coexec_conv2d(x: jax.Array, packed_w: jax.Array, plan: SplitPlan,
                  mesh: Mesh, *, stride: int = 1, gather: bool = True,
                  x_plan: SplitPlan | None = None) -> jax.Array:
    """Channel-split SAME convolution across the two co-execution groups.

    x: (B, H, W, C_in) replicated — or, with `x_plan`, the producer's
    group-local (2, B, H, W, x_plan.c_pad) stack.
    packed_w: (2, K, K, C_in, c_pad) sharded on group.
    Returns (B, H', W', C_out) if gather else the (2, B, H', W', c_pad)
    stack.  Output channels are the split dim; spatial dims follow SAME
    semantics (callers crop to the declared ConvOp shape).
    """

    def build():
        def local(x_l, w_l):
            x_full = (_merge_stacked(x_l, x_plan) if x_plan is not None
                      else x_l)
            y = jax.lax.conv_general_dilated(
                x_full.astype(jnp.float32), w_l[0].astype(jnp.float32),
                window_strides=(stride, stride), padding="SAME",
                dimension_numbers=("NHWC", "HWIO",
                                   "NHWC")).astype(x_full.dtype)
            return y[None]                    # (1, B, H', W', c_pad)

        x_spec = _stacked_spec(5) if x_plan is not None else P()
        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(x_spec, _stacked_spec(5)),
            out_specs=_stacked_spec(5))

    key = ("conv2d", x_plan, stride, mesh_fingerprint(mesh),
           tuple(x.shape), str(x.dtype),
           tuple(packed_w.shape), str(packed_w.dtype))
    y = cached_coexec_program(key, build)(x, packed_w)  # (2,B,H',W',c_pad)

    if not gather:
        return y
    return gather_stacked(y, plan, mesh)


def coexec_linear_ref(x: jax.Array, w: jax.Array) -> jax.Array:
    """Oracle for tests: plain X @ W."""
    return x @ w
