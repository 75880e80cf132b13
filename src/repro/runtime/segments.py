"""The segment compiler: lower a plan's DAG schedule into a handful of
jitted programs.

`PlanExecutor._execute` walks the graph node-by-node in Python — one
shard_map dispatch plus one device sync per op.  That per-boundary cost is
exactly what the paper's SVM synchronization (and our gather-elision) is
meant to kill, but elision alone still pays Python dispatch between every
pair of chained ops.  This module closes the gap: the plan's
`segment_partition()` (see `repro.graph.ir.Graph.segments`) groups the
schedule into maximal same-mesh runs — co-executed ops whose outputs chain
group-locally, plus the residual `add` joins between them — and
`compile_segments` lowers each fused run into ONE `jax.jit` program:

  * chained edges consume the producer's group-local `(2, ..., c_pad)`
    stack via `x_plan=` exactly as the eager walk does (the reconstruction
    is fused into the consumer's shard_map program);
  * a stack consumed by an `add` (or by a non-chaining consumer) is
    reconstructed *inside* the program with `gather_stacked_traced` — the
    jit-safe spelling of the same all-gather;
  * the segment's single published output is materialized at the boundary,
    so one fused segment issues exactly one device sync no matter how many
    ops it contains.

Pool and exclusive (unsplit-kind or exclusively-placed) nodes stay on the
eager per-node path as singleton segments: they are true reshard points
and gain nothing from fusion.

The static layout pass mirrors `PlanExecutor._execute`'s decisions over
shapes only (same chaining predicate, same adaptation, same crops), so the
emitted program computes bit-identical values to the unfused walk; weights
are passed as traced arguments — never baked in as constants — so jit
cannot constant-fold them differently from eager execution.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from repro.core.coexec import (coexec_conv2d, coexec_matmul,
                               gather_stacked_traced)
from repro.graph.ir import SEGMENT_FUSED, SEGMENT_POOL
from repro.kernels import registry


@dataclasses.dataclass
class SegmentProgram:
    """One executable segment of the fused walk.

    Fused segments carry the jitted `fn(ext_vals, weights)` program plus
    statically-known gather/elision counts; pool/exclusive singletons have
    `fn=None` and run through the executor's eager per-node helpers.
    `ext_inputs` names the producers the program reads (in order; `None`
    is the graph input), and the per-node flag maps feed the measurement
    records of the member nodes.
    """

    index: int                           # position in the partition
    kind: str                            # fused | pool | exclusive
    node_ids: Tuple[str, ...]
    ext_inputs: Tuple[Optional[str], ...]
    gathers: int                         # reshards issued by this segment
    elided: int                          # chained (group-local) edges inside
    chained: Dict[str, bool]             # node id -> consumed chained input
    gathered: Dict[str, bool]            # node id -> output materialized
    modes: Dict[str, str]                # node id -> measurement mode
    fn: Optional[Callable] = None        # jitted program (fused only)
    weights: Optional[List[jax.Array]] = None


def _eval_shape(fn, in_shape: Tuple[int, ...], dtype) -> Tuple[int, ...]:
    """Output shape of a single-array function without running it."""
    out = jax.eval_shape(fn, jax.ShapeDtypeStruct(tuple(in_shape), dtype))
    return tuple(out.shape)


def compile_segments(exe, x_shape: Tuple[int, ...]) -> List[SegmentProgram]:
    """Lower the executor's plan into segment programs for input `x_shape`.

    The layout pass walks the partition in order, tracking each value's
    state (materialized shape vs group-local stack) exactly as the eager
    walk would, and records one instruction per fused-segment member; the
    emission pass replays those instructions over traced values inside
    `jax.jit`.  Programs depend on the input shape (chaining is
    shape-exact), hence the per-shape memoization in `PlanExecutor`.
    """
    graph, dtype = exe.graph, exe.dtype
    partition = exe.plan.segment_partition()
    pos = {n.id: i for i, n in enumerate(graph)}

    # materialized shape of every published (cross-segment) value
    plain_shape: Dict[Optional[str], Tuple[int, ...]] = {None: tuple(x_shape)}
    programs: List[SegmentProgram] = []
    for k, seg in enumerate(partition):
        if seg.kind != SEGMENT_FUSED:
            programs.append(_layout_singleton(exe, k, seg, plain_shape))
            continue

        seg_ids = set(seg.node_ids)
        stacked: Dict[str, Tuple[Any, Tuple[int, ...]]] = {}
        local_shape: Dict[str, Tuple[int, ...]] = {}
        instrs: List[Dict[str, Any]] = []
        ext: List[Optional[str]] = []
        weights: List[jax.Array] = []
        gathers = elided = 0
        chained_f: Dict[str, bool] = {}
        modes: Dict[str, str] = {}

        def plain_in(src: Optional[str]) -> Tuple[int, ...]:
            """Shape of `src` consumed as a materialized value (counts the
            interior gather when it is a still-stacked segment member)."""
            nonlocal gathers
            if src in stacked:
                _, lsh = stacked.pop(src)
                gathers += 1
                local_shape[src] = lsh
                return lsh
            if src in local_shape:
                return local_shape[src]
            if src not in ext:
                ext.append(src)
            return plain_shape[src]

        for nid in seg.node_ids:
            node = graph.node(nid)
            i = pos[nid]
            spec = exe.specs[i]
            if spec.unit == "add":
                shapes = {tuple(plain_in(s)) for s in node.inputs}
                if len(shapes) != 1:
                    raise ValueError(
                        f"add node {nid!r} joins mismatched shapes "
                        f"{sorted(shapes)}")
                local_shape[nid] = shapes.pop()
                instrs.append({"id": nid, "kind": "add",
                               "srcs": tuple(node.inputs)})
                modes[nid] = "add"
                chained_f[nid] = False
                continue
            src = node.inputs[0] if node.inputs else None
            do_split = exe.split_capable and spec.coexec
            op = spec.op
            # the eager walk's chaining predicate, over static shapes
            ch = False
            if do_split and src in stacked:
                lsh = stacked[src][1]
                if spec.unit == "conv":
                    ch = tuple(lsh) == (1, op.H_in, op.W_in, op.C_in)
                else:    # 2D contracts: linear, attention, ssm
                    ch = tuple(lsh) == tuple(
                        registry.get(spec.unit).input_shape(op))
                ch = ch and len(graph.consumers(src)) == 1
            if ch:
                _, lsh = stacked.pop(src)
                elided += 1
                in_shape = lsh
            else:
                in_shape = plain_in(src)
            chained_f[nid] = ch
            if do_split:
                split, packed = exe._splits[i]
                slot = len(weights)
                weights.append(packed)
                if spec.unit == "linear":
                    out_l: Tuple[int, ...] = (op.L, op.C_out)
                elif spec.unit == "conv":
                    b = (in_shape[0] if ch else
                         _eval_shape(lambda v: exe._adapt(v, spec),
                                     in_shape, dtype)[0])
                    out_l = (b, op.H_out, op.W_out, op.C_out)
                else:    # head-/state-split attention, ssm
                    out_l = tuple(registry.get(spec.unit).output_shape(op))
                stacked[nid] = (split, out_l)
                modes[nid] = "coexec"
                instrs.append({"id": nid, "kind": "op", "mode": "coexec",
                               "src": src, "chained": ch, "split": split,
                               "slot": slot, "spec": spec, "shape": out_l})
            else:
                w = exe.params[i]
                slot = len(weights)
                weights.append(w)
                local_shape[nid] = _eval_shape(
                    lambda v: exe._dense(exe._adapt(v, spec), w, spec),
                    in_shape, dtype)
                modes[nid] = "exclusive"
                instrs.append({"id": nid, "kind": "op", "mode": "exclusive",
                               "src": src, "chained": False, "slot": slot,
                               "spec": spec})

        last = seg.node_ids[-1]
        if last in stacked:                   # boundary gather
            gathers += 1
            local_shape[last] = stacked.pop(last)[1]
        if stacked:
            raise AssertionError(             # convexity guarantees this
                f"segment {seg.node_ids} leaks stacked values {set(stacked)}")
        plain_shape[last] = tuple(local_shape[last])
        gathered_f = {nid: True for nid in seg.node_ids}
        for ins in instrs:
            if ins.get("chained"):
                gathered_f[ins["src"]] = False
        programs.append(SegmentProgram(
            index=k, kind=SEGMENT_FUSED, node_ids=seg.node_ids,
            ext_inputs=tuple(ext), gathers=gathers, elided=elided,
            chained=chained_f, gathered=gathered_f, modes=modes,
            fn=_emit(exe, k, instrs, tuple(ext)), weights=weights))
    return programs


def _layout_singleton(exe, index: int, seg, plain_shape) -> SegmentProgram:
    """Pool/exclusive singleton: stays eager, only its shape is tracked."""
    nid = seg.node_ids[0]
    graph = exe.graph
    node = graph.node(nid)
    i = [j for j, n in enumerate(graph) if n.id == nid][0]
    spec = exe.specs[i]
    src = node.inputs[0] if node.inputs else None
    if seg.kind == SEGMENT_POOL:
        mode = "pool"
        out_shape = _eval_shape(lambda v: exe._pool(v, spec.pool_bytes),
                                plain_shape[src], exe.dtype)
    elif exe.split_capable and spec.coexec:
        # typed-axis split (head / kv-block / ssm-state): co-executes, but
        # outside fused segments — each lowering stays its own compilation
        # unit so XLA fusion context cannot perturb fp32 rounding
        mode = "coexec"
        out_shape = tuple(registry.get(spec.unit).output_shape(spec.op))
    else:
        mode = "exclusive"
        w = exe.params[i]
        out_shape = _eval_shape(
            lambda v: exe._dense(exe._adapt(v, spec), w, spec),
            plain_shape[src], exe.dtype)
    plain_shape[nid] = out_shape
    return SegmentProgram(
        index=index, kind=seg.kind, node_ids=seg.node_ids,
        ext_inputs=(src,), gathers=0, elided=0, chained={nid: False},
        gathered={nid: True}, modes={nid: mode})


def _emit(exe, index: int, instrs: List[Dict[str, Any]],
          ext_keys: Tuple[Optional[str], ...]) -> Callable:
    """Close the instruction list into one jitted program.

    Signature: `fn(ext_vals, weights) -> materialized segment output`,
    where `ext_vals` follows `ext_keys` and `weights` the instruction
    slots — both traced arguments, so no activation or parameter is ever
    baked into the compiled computation as a constant.

    The program is named `segment_<index>` (its device module is
    `jit_segment_<index>`), and each member's ops sit under a
    `jax.named_scope` of its node id, so a profile names both.
    """
    from repro.runtime.executor import _Stacked
    mesh = exe.mesh

    def program(ext_vals, weights):
        env: Dict[Optional[str], Any] = {}
        ext = dict(zip(ext_keys, ext_vals))

        def plain(src):
            v = env[src] if src in env else ext[src]
            if isinstance(v, _Stacked):     # interior reshard, fused in
                v = gather_stacked_traced(v.data, v.split, mesh)
                env[src] = v
            return v

        for ins in instrs:
            with jax.named_scope(ins["id"]):
                env[ins["id"]] = _member(exe, ins, env, plain, weights)
        return plain(instrs[-1]["id"])

    program.__name__ = program.__qualname__ = f"segment_{index}"
    return jax.jit(program)


def _member(exe, ins: Dict[str, Any], env, plain, weights):
    """One segment member's ops over traced values (see `_emit`)."""
    from repro.runtime.executor import _Stacked
    mesh = exe.mesh
    if ins["kind"] == "add":
        parts = [plain(s) for s in ins["srcs"]]
        out = parts[0]
        for p in parts[1:]:
            out = out + p
        return out
    spec = ins["spec"]
    op = spec.op
    if ins["mode"] != "coexec":
        return exe._dense(exe._adapt(plain(ins["src"]), spec),
                          weights[ins["slot"]], spec)
    if ins["chained"]:
        prod = env[ins["src"]]
        x_in, x_plan = prod.data, prod.split
    else:
        x_in = exe._adapt(plain(ins["src"]), spec)
        x_plan = None
    split = ins["split"]
    packed = weights[ins["slot"]]
    if spec.unit == "linear":
        y = coexec_matmul(x_in, packed, split, mesh, gather=False,
                          x_plan=x_plan)
    elif spec.unit == "conv":
        y = coexec_conv2d(x_in, packed, split, mesh, stride=op.S,
                          gather=False, x_plan=x_plan)
        # SAME conv rounds up; crop to the declared shape
        y = y[:, :, :op.H_out, :op.W_out, :]
    else:    # head-/state-split attention, ssm
        low = registry.get_split_lowering(spec.unit, spec.axis)
        y = low.run(x_in, packed, split, mesh, op, spec.c_fast,
                    gather=False, x_plan=x_plan, use_pallas=exe.use_pallas,
                    interpret=exe.interpret, tile=spec.tile)
    return _Stacked(y, split, ins["shape"])
