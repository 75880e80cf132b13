"""Plan execution runtime: lower a CoexecPlan into a real split-execution
graph.

PR 1 made partitioning a compile-once artifact; this module closes the
plan->execution gap.  `PlanExecutor` walks the plan's op graph
(`repro.graph`) in topological order and lowers every node to actual
computation on the co-execution mesh:

  * **co-executed** conv/linear nodes run channel-split across the two
    device groups (`core/coexec.coexec_matmul` / `coexec_conv2d`), with the
    split taken verbatim from the plan's `PartitionDecision` (GPU share ->
    fast group) and re-aligned to the mesh (`split_for_mesh`);
  * gather-elision is a *graph property*: a split node's output stays
    **group-local** (`gather=False`) iff its **sole consumer** is a
    compatible split node — the consumer reconstructs its input inside its
    own shard_map program, eliding the explicit reshard.  This is the TPU
    analogue of the paper's fine-grained SVM: "subsequent CPU and GPU
    operations read the shared output directly".  An explicit reshard
    (`gather_stacked`) happens only at true boundaries: pool/add nodes,
    exclusive nodes, shape-adapting transitions, fan-out, and the final
    output — and a **fanned-out** split output is gathered exactly once
    (the materialized activation is written back for the remaining
    consumers);
  * **exclusive** nodes (all channels on one side), attention/ssm nodes
    (never split), and every node on a degraded single-group mesh run
    unsplit through the shared kernel registry — jnp oracle by default,
    Pallas kernels with `use_pallas=True`;
  * **pool** nodes lower to max/global-average pooling on the materialized
    activation (pooling always runs GPU-side in the paper: no sync point);
  * **add** nodes materialize their producers and sum them — the residual
    joins of decoder-block graphs.

Where an op node's declared input shape disagrees with the producing
activation (ResNet projection shortcuts in the legacy unit chains), the
executor re-materializes the declared shape deterministically (tile +
crop), and the unsplit oracle (`run_oracle`) applies the identical
adaptation — so executed plans are testable against the oracle end to end.

Two walks execute a plan.  The per-node walk (`run(fused=False)`, the
default) dispatches node by node and syncs after each: it is the
reference that times each op.  The fused walk (`run(fused=True)`) runs the
whole plan as ONE jitted program per input shape (`repro.runtime.segments`)
with ONE `block_until_ready`, bit-identical to the per-node walk on the
CPU (on the chip XLA may fuse across segments: see `segments._fence`).

Every node execution is recorded as a `repro.measure.MeasurementRecord` —
the one schema shared with the simulator and the predictor training sets —
and the resulting `ExecutionReport` pairs executed wall time with the
plan's predicted latency per op (what `MeasurementStore`/`Calibrator`
consume for online replanning).  The per-node walk measures each op; the
fused walk measures the walk and spreads its wall over the ops pro rata
by predicted latency.  Note the predictions model a *phone*, the
execution runs on *this host* — the report tracks the ratio's stability
across ops, not its absolute value.

The timings are read from the walk's spans (`repro.measure.trace`), which
a profiler session also records on its clock: `repro.exec.run` bounds one
walk; `repro.exec.segment` the fused walk's one program (`index` 0, mode
`"fused"`) or one node in the per-node walk, from the call that enqueues
it to the end of its sync; and `repro.exec.sync` each
`block_until_ready` — inside its segment, and for the per-node walk's
terminal gather, directly inside the run.  A segment's time less its sync
is the host's dispatch time.
"""
from __future__ import annotations

import dataclasses
import math
import platform
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.core.coexec import (SplitPlan, cached_coexec_program,
                               coexec_conv2d, coexec_matmul, coexec_mesh,
                               gather_stacked, mesh_fingerprint, mesh_groups,
                               pack_weights, split_for_mesh)
from repro.core.networks import Unit, pool_out_edge
from repro.graph.ir import Graph
from repro.kernels import registry
from repro.measure import trace
from repro.measure.record import (SOURCE_EXECUTOR, SOURCE_FUSED,
                                  MeasurementRecord, usable_for_fidelity)
from repro.runtime.plan import (CoexecPlan, ExecSpec, network_fingerprint,
                                spec_label)

# -------------------------------------------------------------- reporting

#: deprecated alias — the executor's one-off timing format was unified
#: into the shared measurement schema (see docs/MIGRATION.md)
OpTiming = MeasurementRecord


@dataclasses.dataclass
class ExecutionReport:
    """Per-op measurement records + reshard accounting for one plan run."""

    device: str                  # the plan's (simulated) target device
    network_fingerprint: str
    chain: bool
    split_capable: bool
    timings: List[MeasurementRecord]
    reshard_points: int
    elided: int
    fused: bool = False          # fused walk (True) vs per-node walk
    sync_points: int = 0         # device syncs issued by the walk
    #: fused runs: the walk's wall, one entry (the per-node wall_us of the
    #: records is this attributed pro-rata by pred_us); it duplicates
    #: `wall_us` and stays only while the benchmark's span test sums it
    segment_wall_us: List[float] = dataclasses.field(default_factory=list)
    segments: int = 0            # fused runs: the plan's segment count

    @property
    def wall_us(self) -> float:
        return sum(t.wall_us for t in self.timings)

    @property
    def predicted_us(self) -> float:
        return sum(t.pred_us for t in self.timings)

    def count(self, mode: str) -> int:
        return sum(1 for t in self.timings if t.mode == mode)

    def fidelity_error(self) -> float:
        """Σ |log(wall/pred)| over usable units — delegates to the one
        metric implementation (`repro.measure.fidelity_error`), so the
        executor's number can never drift from what the CLI, benchmarks,
        and Calibrator report."""
        from repro.measure.calibrate import fidelity_error
        return fidelity_error(self.timings)

    def mean_log_ratio(self) -> Optional[float]:
        """Mean signed log(wall/pred) — the drift signal `ServingEngine`
        tracks across runs (None when nothing is comparable)."""
        ratios = [math.log(t.wall_us / t.pred_us) for t in self.timings
                  if usable_for_fidelity(t)]
        if not ratios:
            return None
        return sum(ratios) / len(ratios)

    def fidelity_summary(self) -> str:
        n = len(self.timings)
        if n == 0:
            return (f"fidelity: 0 units (empty schedule), "
                    f"{self.reshard_points} reshard points "
                    f"({self.elided} elided)")
        # guard the ratio: schedules with no predicted latency at all
        # (e.g. pool-only) must not divide by ~zero into a garbage figure
        if self.predicted_us > 0.0:
            ratio = f"(x{self.wall_us / self.predicted_us:.2f})"
        else:
            ratio = "(ratio n/a: no predicted latency)"
        seg = (f"{self.segments} segments "
               f"({self.sync_points} syncs), " if self.fused else "")
        return (f"fidelity: {n} units ({self.count('coexec')} co-executed, "
                f"{self.count('exclusive')} exclusive, "
                f"{self.count('pool')} pool), {seg}"
                f"{self.reshard_points} reshard points "
                f"({self.elided} elided), "
                f"executed {self.wall_us / 1e3:.1f} ms vs predicted "
                f"{self.predicted_us / 1e3:.1f} ms {ratio}")

    def to_json(self) -> Dict[str, Any]:
        return {"device": self.device,
                "network_fingerprint": self.network_fingerprint,
                "chain": self.chain,
                "split_capable": self.split_capable,
                "reshard_points": self.reshard_points,
                "elided": self.elided,
                "fused": self.fused,
                "sync_points": self.sync_points,
                "segment_wall_us": list(self.segment_wall_us),
                "segments": self.segments,
                "wall_us": self.wall_us,
                "predicted_us": self.predicted_us,
                "timings": [t.to_json() for t in self.timings]}

    @staticmethod
    def from_json(d: Dict[str, Any]) -> "ExecutionReport":
        return ExecutionReport(
            device=d["device"],
            network_fingerprint=d["network_fingerprint"],
            chain=d["chain"], split_capable=d["split_capable"],
            timings=[MeasurementRecord.from_json(t) for t in d["timings"]],
            reshard_points=d["reshard_points"], elided=d["elided"],
            fused=d.get("fused", False),
            sync_points=d.get("sync_points", 0),
            segment_wall_us=list(d.get("segment_wall_us", [])),
            segments=d.get("segments", 0))


# ------------------------------------------------------------- activations

@dataclasses.dataclass
class _Stacked:
    """A group-local (2, ..., c_pad) activation that has NOT been gathered.

    `shape` is the logical materialized shape the stack reconstructs to —
    what shape-chaining compatibility is checked against.
    """

    data: jax.Array
    split: SplitPlan
    shape: Tuple[int, ...]


_Act = Union[jax.Array, _Stacked]


def _fit_axis(x: jax.Array, axis: int, size: int, *, align: int = 8,
              adapt: bool = False) -> jax.Array:
    """Re-materialize one axis to `size`.

    By default this is strict: the only tolerated mismatch is cropping
    away alignment padding — `size <= cur <= size` rounded up to `align`
    (callers on a split mesh pass the lcm-of-8-and-lanes granularity the
    channel split pads to).  Anything else raises: it means the caller
    wired incompatible shapes together, and silently tiling values to
    paper over that corrupts results without failing any test.

    `adapt=True` opts in to the deterministic tile + crop the executor
    uses for *declared* shape adaptation (`_adapt`: ResNet projection
    shortcuts in the legacy unit chains), where re-materializing is the
    documented semantics rather than an accident.
    """
    cur = x.shape[axis]
    if cur == size:
        return x
    if not adapt:
        padded = -(-size // align) * align
        if not (size < cur <= padded):
            raise ValueError(
                f"axis {axis} has size {cur}, expected {size} (or its "
                f"alignment padding up to {padded}); shapes do not chain "
                "and this call site does not adapt")
    if cur < size:
        reps = [1] * x.ndim
        reps[axis] = -(-size // cur)
        x = jnp.tile(x, reps)
    return jax.lax.slice_in_dim(x, 0, size, axis=axis)


# --------------------------------------------------------------- executor

class PlanExecutor:
    """Executes a compiled `CoexecPlan` on the co-execution mesh.

    Parameters are materialized once at construction from a seeded rng
    (fan-in-scaled, via the kernel registry) and shared by the split run
    and the unsplit oracle, so the two are comparable elementwise.
    """

    def __init__(self, plan: CoexecPlan, units: Optional[Sequence[Unit]] = None,
                 *, mesh=None, dtype=jnp.float32, seed: int = 0,
                 use_pallas: bool = False, interpret: bool = False):
        self.plan = plan
        self.specs = plan.exec_specs()
        if units is not None:
            fp = network_fingerprint(list(units))
            if fp != plan.provenance.network_fingerprint:
                raise ValueError(
                    "units do not match the plan's network fingerprint "
                    f"({fp} != {plan.provenance.network_fingerprint}); "
                    "the plan was compiled for a different graph")
        self.graph: Graph = plan.graph_ir()
        fp = self.graph.fingerprint()
        if fp != plan.provenance.network_fingerprint:
            raise ValueError(
                "graph does not match the plan's network fingerprint "
                f"({fp} != {plan.provenance.network_fingerprint}); "
                "the plan was compiled for a different graph")
        if [n.kind for n in self.graph] != [s.unit for s in self.specs]:
            raise ValueError("plan schedule and graph disagree on node "
                             "kinds — corrupt plan")
        self.mesh = coexec_mesh() if mesh is None else mesh
        self.split_capable = mesh_groups(self.mesh) == 2
        self.dtype = dtype
        self.use_pallas = use_pallas
        self.interpret = interpret
        self.last_report: Optional[ExecutionReport] = None
        self._warmed: set = set()      # (chain, fused) keys executed once
        # the fused walk and its records' fields, memoized per input shape
        # (chaining is shape-exact, so the layout depends on the shape)
        self._programs: Dict[Tuple[int, ...], Any] = {}

        rng = np.random.default_rng(seed)
        self.params: List[Optional[jax.Array]] = []
        for spec in self.specs:
            if spec.op is None:
                self.params.append(None)
            else:
                w = registry.get(spec.unit).init_weight(spec.op, rng)
                self.params.append(jnp.asarray(w, dtype))
        # pre-split the co-executed weights once: (split, packed) per spec —
        # they depend only on (spec, mesh, params), and packing host-side
        # inside the per-op stopwatch would contaminate the timings.
        # Channel splits pack the trailing weight dim; typed axes (head /
        # kv-block / ssm-state) pack through their registered split
        # lowering (per-side KV-head slices, cache-block slices, per-head
        # parameter vectors)
        self._splits: List[Optional[Tuple[SplitPlan, jax.Array]]] = []
        for spec, w in zip(self.specs, self.params):
            if self.split_capable and spec.coexec:
                if spec.axis == "channel":
                    split = split_for_mesh(spec.op.C_out, spec.c_fast,
                                           self.mesh)
                    self._splits.append(
                        (split, pack_weights(w, split, self.mesh)))
                else:
                    low = registry.get_split_lowering(spec.unit, spec.axis)
                    self._splits.append(
                        low.pack(w, spec.op, spec.c_fast, self.mesh))
            else:
                self._splits.append(None)
        self._input_seed = seed + 1

    @property
    def units(self) -> List[Unit]:
        """Legacy unit-list view (chain plans only; see plan.units)."""
        return self.plan.units

    # ------------------------------------------------------------- inputs
    def input_template(self) -> jax.Array:
        """A seeded input matching the first source node's declared shape
        (deterministic: every call returns the same values, so `run` and
        `run_oracle` with x=None see identical inputs)."""
        src = self.graph.sources[0]
        shape = tuple(registry.get(src.kind).input_shape(src.op))
        if src.kind == "conv":
            shape = (1,) + shape
        rng = np.random.default_rng(self._input_seed)
        x = rng.standard_normal(shape).astype(np.float32)
        return jnp.asarray(x, self.dtype)

    # -------------------------------------------------------- elementaries
    def _materialize(self, act: _Act) -> Tuple[jax.Array, int]:
        """Explicit reshard of a group-local stack (1 sync point), no-op on
        plain activations."""
        if isinstance(act, _Stacked):
            return gather_stacked(act.data, act.split, self.mesh), 1
        return act, 0

    def _adapt(self, x: jax.Array, spec: ExecSpec) -> jax.Array:
        """Re-materialize a plain activation to the node's declared input
        shape (identity when shapes already chain)."""
        op = spec.op
        if spec.unit == "conv":
            if x.ndim == 2:                   # linear -> conv (not in the
                x = x.reshape(1, 1, *x.shape)  # paper's nets, but total)
            x = _fit_axis(x, 1, op.H_in, adapt=True)
            x = _fit_axis(x, 2, op.W_in, adapt=True)
            return _fit_axis(x, 3, op.C_in, adapt=True)
        # 2D (rows, channels) contracts: linear, attention, ssm
        shape = tuple(registry.get(spec.unit).input_shape(op))
        flat = x.reshape(-1)
        flat = _fit_axis(flat, 0, int(np.prod(shape)), adapt=True)
        return flat.reshape(shape)

    def _pool(self, x: jax.Array, pool_bytes: int) -> jax.Array:
        """Lower a pool unit: global average pool when the recorded output
        is one value per channel, else max-pool down to the recorded edge."""
        c = x.shape[-1]
        edge = pool_out_edge(pool_bytes, c)
        if edge <= 1:
            return jnp.mean(x, axis=(1, 2), keepdims=True)
        r = max(1, x.shape[1] // edge)
        x = x[:, :edge * r, :edge * r, :]
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max,
            window_dimensions=(1, r, r, 1), window_strides=(1, r, r, 1),
            padding="VALID")

    def _dense(self, x: jax.Array, w: jax.Array, spec: ExecSpec
               ) -> jax.Array:
        """Unsplit execution through the registry lowering."""
        low = registry.get_lowering(spec.unit)
        if not self.use_pallas:
            return low.oracle(x, w, spec.op)

        # bound here so a cached program does not hold the executor and its
        # weights
        interpret = self.interpret

        def kernel(x, w):
            return low.pallas(x, w, spec.op, interpret=interpret,
                              tile=spec.tile)

        if self.mesh.size == 1:
            return kernel(x, w)
        # a Pallas kernel is never partitioned automatically: on a
        # multi-device mesh every device runs the whole op on replicated
        # operands, as XLA runs the oracle (check_vma=False: a kernel's
        # output type carries no varying-axis annotation)
        key = ("dense", spec.unit, spec.op, spec.tile, interpret,
               mesh_fingerprint(self.mesh), tuple(x.shape), str(x.dtype),
               tuple(w.shape), str(w.dtype))
        return cached_coexec_program(key, lambda: jax.shard_map(
            kernel, mesh=self.mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=False))(x, w)

    def _chains(self, act: _Stacked, spec: ExecSpec) -> bool:
        """Can this unit consume the producer's stack directly?  Only when
        the declared input shape equals the stack's logical shape exactly —
        any adaptation is a true boundary."""
        op = spec.op
        if spec.unit == "conv":
            return act.shape == (1, op.H_in, op.W_in, op.C_in)
        # 2D (rows, channels) contracts: linear, attention, ssm
        return act.shape == tuple(registry.get(spec.unit).input_shape(op))

    # ------------------------------------------------------------ segments
    def walk_program(self, x_shape: Optional[Tuple[int, ...]] = None):
        """The fused walk (`WalkProgram`: one jitted program over every
        segment) for input shape `x_shape` (default: the input template's
        shape).  Memoized per shape."""
        return self._walk(x_shape)[0]

    def _walk(self, x_shape: Optional[Tuple[int, ...]] = None):
        """(`WalkProgram`, per-node record fields, per-node wall shares)
        for `x_shape`, built once per shape: a run only scales the shares
        by its wall."""
        if x_shape is None:
            x_shape = tuple(self.input_template().shape)
        x_shape = tuple(x_shape)
        if x_shape not in self._programs:
            from repro.runtime.segments import compile_walk
            walk = compile_walk(self, x_shape)
            pos = {n.id: i for i, n in enumerate(self.graph)}
            prov = self.plan.provenance
            fields, preds = [], []
            for sp in walk.segments:
                for nid in sp.node_ids:
                    spec = self.specs[pos[nid]]
                    preds.append(spec.pred_total_us)
                    fields.append(dict(
                        index=pos[nid], unit=spec.unit,
                        label=spec_label(spec), mode=sp.modes[nid],
                        c_fast=spec.c_fast, c_slow=spec.c_slow,
                        chained_input=sp.chained[nid],
                        gathered_output=sp.gathered[nid],
                        pred_us=spec.pred_total_us, op=spec.op,
                        source=SOURCE_FUSED, device=prov.device,
                        host=platform.node(), plan_key=self.plan.key,
                        network_fingerprint=prov.network_fingerprint,
                        node_id=nid, segment=sp.index))
            total = sum(preds)
            shares = [p / total if total > 0.0 else 1.0 / len(preds)
                      for p in preds]
            self._programs[x_shape] = (walk, fields, shares)
        return self._programs[x_shape]

    # ----------------------------------------------------------------- run
    def run(self, x: Optional[jax.Array] = None, *, chain: bool = True,
            warmup: bool = False, fused: bool = False
            ) -> Tuple[jax.Array, ExecutionReport]:
        """Execute the plan; returns (output, ExecutionReport).

        `warmup=True` runs the whole schedule once untimed first, so the
        reported per-op wall times measure steady-state execution rather
        than shard_map tracing + XLA compilation (first-touch compile can
        dominate the microsecond-scale predictions by orders of
        magnitude).  The executor tracks what it has already executed
        (per chain flag), so `warmup=True` is a no-op after the first
        run — callers can pass it unconditionally without paying 2N
        schedule passes for N recorded runs.  The warmup pass never
        publishes its report: only the timed run lands on
        `self.last_report` (a warmup report leaking there would poison
        the measurement store and any calibration fit from it).  The
        CLIs and `tab3 --execute` warm up by default; equivalence tests
        skip it for speed.

        `fused=True` takes the fused walk instead of the per-node walk:
        the plan's whole partition lowered into one jitted program per
        input shape (see `repro.runtime.segments`), one dispatch and one
        device sync per inference, outputs bit-identical on the CPU.  The
        per-node walk stays as the `fused=False` reference that times
        each op.
        """
        if fused and not chain:
            raise ValueError(
                "fused=True implies chaining — chain=False is the "
                "gather-every-op reference walk and has no fused form")
        def step():
            with trace.span("repro.exec.run"):
                if fused:
                    return self._execute_fused(x)
                return self._execute(x, chain=chain)

        key = (chain, fused)
        if warmup and key not in self._warmed:
            step()                               # untimed: not published
            self._warmed.add(key)
        y, report = step()
        self._warmed.add(key)
        self.last_report = report
        return y, report

    __call__ = run

    def _execute(self, x: Optional[jax.Array] = None, *, chain: bool = True
                 ) -> Tuple[jax.Array, ExecutionReport]:
        x0: jax.Array = (self.input_template() if x is None
                         else jnp.asarray(x, self.dtype))
        acts: Dict[str, _Act] = {}
        remaining = {n.id: len(self.graph.consumers(n.id))
                     for n in self.graph}
        timings: List[MeasurementRecord] = []
        reshard = elided = 0
        host = platform.node()
        prov = self.plan.provenance

        def materialized(src: Optional[str]) -> jax.Array:
            """The plain (gathered) activation of a producer.  A stacked
            output is gathered ONCE and written back, so fan-out costs a
            single reshard no matter how many consumers follow."""
            nonlocal reshard
            if src is None:
                return x0
            act = acts[src]
            if isinstance(act, _Stacked):
                act, r = self._materialize(act)
                reshard += r
                acts[src] = act
            return act

        for i, (node, spec) in enumerate(zip(self.graph, self.specs)):
            w = self.params[i]
            src = node.inputs[0] if node.inputs else None
            do_split = self.split_capable and spec.coexec
            if spec.unit in ("pool", "add"):
                mode = spec.unit
            else:
                mode = "coexec" if do_split else "exclusive"
            chained = False
            with trace.span("repro.exec.segment", index=i, mode=mode) as seg:
                if mode == "pool":
                    out = self._pool(materialized(src), spec.pool_bytes)
                elif mode == "add":
                    parts = [materialized(s) for s in node.inputs]
                    shapes = {tuple(p.shape) for p in parts}
                    if len(shapes) != 1:
                        raise ValueError(
                            f"add node {node.id!r} joins mismatched shapes "
                            f"{sorted(shapes)}")
                    out = parts[0]
                    for p in parts[1:]:
                        out = out + p
                else:
                    x_plan = None
                    prod_act = x0 if src is None else acts[src]
                    # gather-elision as a graph property: consume the
                    # producer's group-local stack iff we are its SOLE
                    # consumer, we split too, and the shapes chain exactly
                    if (isinstance(prod_act, _Stacked) and chain and do_split
                            and self._chains(prod_act, spec)
                            and len(self.graph.consumers(src)) == 1):
                        x_in, x_plan = prod_act.data, prod_act.split
                        chained = True
                        elided += 1
                    else:
                        x_in = self._adapt(materialized(src), spec)
                    if do_split:
                        op = spec.op
                        split, packed = self._splits[i]
                        if spec.unit == "linear":
                            y = coexec_matmul(x_in, packed, split, self.mesh,
                                              gather=False, x_plan=x_plan)
                            out = _Stacked(y, split, (op.L, op.C_out))
                        elif spec.unit == "conv":
                            y = coexec_conv2d(x_in, packed, split, self.mesh,
                                              stride=op.S, gather=False,
                                              x_plan=x_plan)
                            # SAME conv rounds up; crop the stack to the
                            # declared (floor) shape so chaining stays exact
                            y = y[:, :, :op.H_out, :op.W_out, :]
                            b = x_in.shape[1] if chained else x_in.shape[0]
                            out = _Stacked(y, split,
                                           (b, op.H_out, op.W_out, op.C_out))
                        else:       # typed axis: registered split lowering
                            low = registry.get_split_lowering(spec.unit,
                                                              spec.axis)
                            y = low.run(x_in, packed, split, self.mesh, op,
                                        spec.c_fast, gather=False,
                                        x_plan=x_plan,
                                        use_pallas=self.use_pallas,
                                        interpret=self.interpret,
                                        tile=spec.tile)
                            if spec.axis == "kv-block":
                                # non-stackable: the lowering merged its
                                # softmax partials and materialized internally
                                out = y
                            else:
                                shape = tuple(registry.get(
                                    spec.unit).output_shape(op))
                                out = _Stacked(y, split, shape)
                        if isinstance(out, _Stacked) and not chain:
                            out, r = self._materialize(out)  # sync every op
                            reshard += r
                    else:
                        out = self._dense(x_in, w, spec)
                acts[node.id] = out
                with trace.span("repro.exec.sync"):
                    jax.block_until_ready(out.data if isinstance(out, _Stacked)
                                          else out)
            timings.append(MeasurementRecord(
                index=i, unit=spec.unit, label=spec_label(spec), mode=mode,
                c_fast=spec.c_fast, c_slow=spec.c_slow,
                chained_input=chained,
                gathered_output=not isinstance(out, _Stacked),
                wall_us=seg.elapsed_s * 1e6,
                pred_us=spec.pred_total_us,
                op=spec.op, source=SOURCE_EXECUTOR, device=prov.device,
                host=host, plan_key=self.plan.key,
                network_fingerprint=prov.network_fingerprint,
                node_id=node.id))
            # free consumed producers (keep the graph output alive)
            for s in node.inputs:
                remaining[s] -= 1
                if remaining[s] == 0:
                    acts.pop(s, None)

        # the terminal sync point: with chaining, the last co-executed op's
        # gather is deferred to here — time it and charge it to that op so
        # chained and gather-every-op wall totals stay comparable
        with trace.span("repro.exec.sync") as sync:
            y, r = self._materialize(acts[self.graph.output.id])
            jax.block_until_ready(y)
        reshard += r
        if timings and r:
            timings[-1].gathered_output = True
            timings[-1].wall_us += sync.elapsed_s * 1e6
        report = ExecutionReport(
            device=prov.device,
            network_fingerprint=prov.network_fingerprint,
            chain=chain, split_capable=self.split_capable, timings=timings,
            reshard_points=reshard, elided=elided,
            # one block_until_ready per node plus the terminal one
            sync_points=len(timings) + 1)
        return y, report

    def _execute_fused(self, x: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, ExecutionReport]:
        """The fused walk: the whole plan as one jitted program, one
        dispatch and one device sync.

        The walk's wall time cannot be split per node by measurement — the
        whole point is that the nodes no longer sync — so each node's
        record carries the walk's wall attributed **pro-rata by predicted
        latency** (equal shares when nothing is predicted), flagged
        `source="fused"` and tagged with its segment index.  Summing the
        records recovers the walk's wall exactly, so report totals stay
        comparable with the per-node walk, and `Calibrator.fit` consumes
        the records unchanged.
        """
        if x is None:
            x0: jax.Array = self.input_template()
        elif isinstance(x, jax.Array) and x.dtype == self.dtype:
            x0 = x                        # as `jnp.asarray` would return it
        else:
            x0 = jnp.asarray(x, self.dtype)
        walk, fields, shares = self._walk(tuple(x0.shape))
        with trace.span("repro.exec.segment", index=0, mode="fused") as seg:
            y = walk.fn(x0, walk.weights)
            with trace.span("repro.exec.sync"):
                jax.block_until_ready(y)
        wall = seg.elapsed_s * 1e6
        prov = self.plan.provenance
        report = ExecutionReport(
            device=prov.device,
            network_fingerprint=prov.network_fingerprint,
            chain=True, split_capable=self.split_capable,
            timings=[MeasurementRecord(wall_us=wall * share, **kw)
                     for kw, share in zip(fields, shares)],
            reshard_points=sum(sp.gathers for sp in walk.segments),
            elided=sum(sp.elided for sp in walk.segments), fused=True,
            sync_points=1, segment_wall_us=[wall],
            segments=len(walk.segments))
        return y, report

    def run_oracle(self, x: Optional[jax.Array] = None) -> jax.Array:
        """The unsplit reference: every node dense, identical params and
        shape adaptation — what split execution must match elementwise."""
        x0 = (self.input_template() if x is None
              else jnp.asarray(x, self.dtype))
        acts: Dict[str, jax.Array] = {}
        for node, spec, w in zip(self.graph, self.specs, self.params):
            src = acts[node.inputs[0]] if node.inputs else x0
            if spec.unit == "pool":
                acts[node.id] = self._pool(src, spec.pool_bytes)
            elif spec.unit == "add":
                out = acts[node.inputs[0]]
                for s in node.inputs[1:]:
                    out = out + acts[s]
                acts[node.id] = out
            else:
                acts[node.id] = self._dense(self._adapt(src, spec), w, spec)
        return acts[self.graph.output.id]


# --------------------------------------------------------------------- CLI

def main(argv: Optional[Sequence[str]] = None) -> int:
    """Deprecated CLI shim: forwards to `python -m repro execute`.

    Flags are a strict subset of the unified CLI's, and the provenance it
    builds is identical — it warm-hits the same plan-cache entries.
    """
    import sys

    from repro.api import _warn_once
    from repro.cli import main as _cli_main

    _warn_once("python -m repro.runtime.executor", "python -m repro execute")
    rest = list(sys.argv[1:] if argv is None else argv)
    return _cli_main(["execute", *rest])


if __name__ == "__main__":
    raise SystemExit(main())
