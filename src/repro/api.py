"""The compile→run facade: one stable API over planning, caching,
execution, and serving.

The paper's workflow is one pipeline — profile a device, predict per-op
CPU/GPU latency, pick a split, execute with cheap synchronization — but the
pieces live in four subsystems (core/partitioner, core/planner, runtime,
serving).  This module is the single front door:

    import repro
    target = repro.Target(device="moto2022", threads=3)
    compiled = repro.compile("resnet18", target)        # cached planning
    y = compiled.run()                                  # split execution
    report = compiled.profile()                         # fidelity report
    compiled.save("resnet18.coexec.json")               # ship the artifact

`Target` captures everything a plan's validity depends on at the request
level (device, threads, sync mechanism, candidate-grid step, measurement
seed, mesh policy) and validates itself eagerly.  `compile` resolves the
network (a `repro.graph.Graph`, a registered network or model name, a
unit list, or a bare op list), trains-or-loads the mux predictors when
the mode needs them, runs the *cached* planners (`plan_graph_cached` /
`partition_ops_plan_cached` / `grid_plan_graph_cached` —
provenance-identical to calling them directly, so facade and pre-facade
callers share on-disk cache entries bit-for-bit), and returns a
`CompiledNetwork`: the `CoexecPlan` plus a lazily-built `PlanExecutor`
and save/load/explain on top.

Importing this module never imports jax; execution machinery loads on the
first `run`/`profile`/`executor` call.

The unified CLI (`python -m repro` — see cli.py) and `ServingEngine
(compiled=...)` are thin clients of this module.  The legacy single-op
entry points are re-exported at the bottom as deprecation shims.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import warnings
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

from repro.core.networks import NETWORKS, Unit
from repro.core.simulator.devices import DEVICES
from repro.core.sync import SyncMechanism
from repro.core.types import ConvOp, LinearOp, Op
from repro.graph.ir import Graph, from_units
from repro.runtime.cache import (PlanCache, grid_plan_graph_cached,
                                 partition_ops_plan_cached,
                                 plan_graph_cached)
from repro.runtime.plan import CoexecPlan, PlanProvenance, spec_label

#: compile() planning modes
MODE_PREDICTED = "predicted"     # GBDT predictors (the deployable path)
MODE_GRID = "grid"               # measurement-driven oracle (upper bound)

#: Target.mesh policies
MESH_AUTO = "auto"               # split when >= 2 devices, degrade otherwise
MESH_SINGLE = "single"           # force the degraded exclusive-only mesh
MESH_SPLIT = "split"             # require a 2-group mesh, error otherwise

ARTIFACT_FORMAT = "repro.compiled_network"
ARTIFACT_VERSION = 1

DEFAULT_CACHE_DIR = "reports/plans"
DEFAULT_MEASUREMENTS_DIR = "reports/measurements"


# ------------------------------------------------------------------ target

@dataclasses.dataclass(frozen=True)
class Target:
    """Where and how a network will run — the request half of provenance.

    Validates eagerly: an invalid device/mechanism/step/mesh fails at
    construction, not deep inside planning.  `mechanism` accepts either a
    `SyncMechanism` or its string value and normalizes to the string, so
    targets compare/serialize structurally.
    """

    device: str
    threads: int = 3
    mechanism: str = SyncMechanism.SVM_POLL.value
    step: int = 8
    seed: int = 1
    mesh: str = MESH_AUTO

    def __post_init__(self):
        if self.device not in DEVICES:
            raise ValueError(f"unknown device {self.device!r}; "
                             f"choices: {sorted(DEVICES)}")
        if isinstance(self.mechanism, SyncMechanism):
            object.__setattr__(self, "mechanism", self.mechanism.value)
        try:
            SyncMechanism(self.mechanism)
        except ValueError:
            raise ValueError(
                f"unknown sync mechanism {self.mechanism!r}; "
                f"choices: {[m.value for m in SyncMechanism]}") from None
        # exact int checks: bool is an int subclass, but threads=True would
        # serialize as JSON `true` and split the cache key from threads=1
        if type(self.threads) is not int or self.threads < 1:
            raise ValueError(f"threads must be a positive int, "
                             f"got {self.threads!r}")
        if type(self.step) is not int or self.step < 1:
            raise ValueError(f"step must be a positive int, "
                             f"got {self.step!r}")
        if self.mesh not in (MESH_AUTO, MESH_SINGLE, MESH_SPLIT):
            raise ValueError(f"unknown mesh policy {self.mesh!r}; "
                             f"choices: ['auto', 'single', 'split']")

    @property
    def sync_mechanism(self) -> SyncMechanism:
        return SyncMechanism(self.mechanism)

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @staticmethod
    def from_json(d: Dict[str, Any]) -> "Target":
        return Target(**d)


# -------------------------------------------------------------- predictors

def _trained_mux_predictors(device: str, threads: int, *, samples: int,
                            estimators: int,
                            cache_dir: Optional[Union[str, Path]] = None,
                            kinds: Sequence[str] = ("linear", "conv")):
    """Train (or load from `cache_dir`) the (cpu, gpu) MuxPredictor pair.

    The on-disk layout is one pickle per underlying LatencyPredictor, keyed
    by every training knob — a load is checksum-identical to a retrain, so
    predictor caching never changes which plan-cache entry a compile hits.
    `kinds` beyond linear/conv (attention, ssm) add decode members as extra
    role files; the linear/conv files are shared with conv-only compiles.
    """
    from repro.runtime.plan import train_mux_predictors

    if cache_dir is None:
        return train_mux_predictors(device, threads, samples=samples,
                                    estimators=estimators, kinds=kinds)

    from repro.core.predictor.train import LatencyPredictor, MuxPredictor
    root = Path(cache_dir)
    stem = f"mux_{device}_cpu{threads}_{samples}x{estimators}"
    paths = {f"{side}_{kind}": root / f"{stem}_{side}_{kind}.pkl"
             for side in ("cpu", "gpu") for kind in kinds}
    if all(p.exists() for p in paths.values()):
        try:
            def member(side, kind):
                if kind not in kinds:
                    return None
                return LatencyPredictor.load(paths[f"{side}_{kind}"])

            cp = MuxPredictor(member("cpu", "linear"),
                              member("cpu", "conv"),
                              attention=member("cpu", "attention"),
                              ssm=member("cpu", "ssm"))
            gp = MuxPredictor(member("gpu", "linear"),
                              member("gpu", "conv"),
                              attention=member("gpu", "attention"),
                              ssm=member("gpu", "ssm"))
            return cp, gp
        except Exception:           # noqa: BLE001 — corrupt cache: retrain
            pass
    cp, gp = train_mux_predictors(device, threads, samples=samples,
                                  estimators=estimators, kinds=kinds)
    root.mkdir(parents=True, exist_ok=True)
    for side, p in (("cpu", cp), ("gpu", gp)):
        for kind in kinds:
            m = p.member(kind)
            if m is not None:
                m.save(paths[f"{side}_{kind}"])
    return cp, gp


# ------------------------------------------------------- network resolution

def available_networks() -> Dict[str, List[str]]:
    """Every name `compile` resolves, from the two registries: legacy
    unit-chain networks (`core.networks.NETWORKS`) and decoder-block model
    graphs (`graph.frontends`: tiny configs + `models.registry`)."""
    from repro.graph.frontends import model_names
    return {"networks": sorted(NETWORKS), "models": model_names()}


def _unknown_name_error(name: str) -> ValueError:
    names = available_networks()
    return ValueError(
        f"unknown network {name!r}; registered unit networks: "
        f"{names['networks']}; model graphs (via graph.from_model): "
        f"{names['models']}")


def _resolve_graph(network) -> Tuple[Union[Graph, List[Op]], bool]:
    """Normalize `compile`'s first argument to (graph_or_ops, is_graph).

    Accepts a `repro.graph.Graph`, a registered network or model name, a
    unit list (("conv"/"linear"/"pool", payload) tuples), or a bare op
    list.  Everything except bare op lists lowers to a Graph; bare op
    lists are planned per-op (no end-to-end report, threads/seed-free
    provenance — the Table 2 contract), hence the flag.
    """
    if isinstance(network, Graph):
        return network, True
    if isinstance(network, str):
        if network in NETWORKS:
            return from_units(NETWORKS[network]()), True
        from repro.graph.frontends import from_model, model_names
        if network in model_names():
            return from_model(network), True
        raise _unknown_name_error(network)
    seq = list(network)
    if not seq:
        raise ValueError("cannot compile an empty network")
    if all(isinstance(e, (LinearOp, ConvOp)) for e in seq):
        return seq, False
    if all(isinstance(e, tuple) and len(e) == 2 and isinstance(e[0], str)
           for e in seq):
        return from_units(seq), True
    raise TypeError(
        "network must be a repro.graph.Graph, a registered name, a unit "
        "list [(kind, payload), ...], or a bare op list "
        f"[LinearOp/ConvOp, ...]; got {type(seq[0]).__name__} elements")


# ------------------------------------------------------------------ compile

def compile(network, target: Target, *,               # noqa: A001 — facade
            mode: str = MODE_PREDICTED,
            cache: Union[PlanCache, str, Path] = DEFAULT_CACHE_DIR,
            predictors=None,
            samples: int = 400, estimators: int = 60,
            predictor_cache: Optional[Union[str, Path]] = None,
            bucket: str = "",
            tune: bool = False,
            tune_cache=None) -> "CompiledNetwork":
    """Compile a network into a `CompiledNetwork` (cached planning).

    * `network` — a `repro.graph.Graph`, a registered name ("resnet18",
      "tiny_decoder", "gemma3-12b", ...), a unit list, or a bare op list.
    * `target` — the validated `Target` (device/threads/mechanism/step/
      seed/mesh).
    * `mode` — "predicted" plans with trained GBDT predictors (the paper's
      deployable path); "grid" uses the measurement-driven oracle and
      needs no predictors.
    * `cache` — a `PlanCache` or a directory path; planning is skipped
      entirely on a warm hit (the plan file is just read back).
    * `predictors` — optional pre-trained (cpu, gpu) pair; when omitted in
      "predicted" mode a deterministic pair is trained (or loaded from
      `predictor_cache`) with `samples`/`estimators`.

    Provenance is identical to the underlying cached planners, so plans
    compiled here warm-hit entries written by pre-facade callers and vice
    versa.  `bucket` tags the plan with a serving (batch, seq) bucket —
    folded into the provenance digest so portfolio entries get their own
    cache files (see `compile_portfolio`); only graph plans in
    "predicted" mode accept it.

    `tune=True` runs the kernel tile autotuner (`runtime.autotune`) over
    the plan's ops on a cache miss and attaches winning non-default
    `TileConfig`s to the decisions; the tune-cache version folds into
    provenance, so tuned and untuned plans occupy distinct cache entries
    and each warm-hits independently.  `tune_cache` is a `TuneCache` or a
    directory path (default `reports/tune`); a warm tune cache makes the
    annotation pass measurement-free.
    """
    if not isinstance(target, Target):
        raise TypeError(f"target must be a repro.Target, "
                        f"got {type(target).__name__}")
    if mode not in (MODE_PREDICTED, MODE_GRID):
        raise ValueError(f"unknown mode {mode!r}; "
                         f"choices: ['predicted', 'grid']")
    graph_or_ops, is_graph = _resolve_graph(network)
    if bucket and (mode != MODE_PREDICTED or not is_graph):
        raise ValueError("bucket= requires a graph network in "
                         "mode='predicted' (portfolio entries must be "
                         "replannable)")
    if not isinstance(cache, PlanCache):
        cache = PlanCache(Path(cache))
    mech = target.sync_mechanism
    hits_before = cache.hits

    tune_tag = ""
    annotate = None
    if tune:
        from repro.runtime.autotune import (DEFAULT_TUNE_DIR, TuneCache,
                                            annotate_plan_tiles,
                                            tune_cache_version)
        tc = tune_cache
        if not isinstance(tc, TuneCache):
            tc = TuneCache(Path(tc) if tc is not None
                           else Path(DEFAULT_TUNE_DIR))
        tune_tag = tune_cache_version()

        def annotate(plan, _tc=tc):
            return annotate_plan_tiles(plan, cache=_tc)

    if mode == MODE_GRID:
        if predictors is not None:
            raise ValueError("mode='grid' is measurement-driven and takes "
                             "no predictors; drop predictors= or use "
                             "mode='predicted'")
        if not is_graph:
            from repro.kernels.registry import op_kind
            graph_or_ops = from_units(
                [(op_kind(op), op) for op in graph_or_ops])
        plan = grid_plan_graph_cached(
            graph_or_ops, target.device, target.threads, mechanism=mech,
            step=target.step, seed=target.seed, tune=tune_tag,
            annotate=annotate, cache=cache)
    else:
        if predictors is None:
            kinds: Tuple[str, ...] = ("linear", "conv")
            if is_graph:
                # decode kinds present in the graph get predictor members
                # so the planner can price (axis, split, mode) candidates;
                # conv/linear-only graphs keep the pre-decode predictor
                # bundle (and its checksum, hence their cached plans)
                kinds += tuple(sorted(
                    {n.kind for n in graph_or_ops
                     if n.op is not None and n.kind in ("attention", "ssm")}))
            predictors = _trained_mux_predictors(
                target.device, target.threads, samples=samples,
                estimators=estimators, cache_dir=predictor_cache,
                kinds=kinds)
        cpu_pred, gpu_pred = predictors
        if gpu_pred.device != target.device:
            raise ValueError(
                f"predictors were trained for {gpu_pred.device!r} but the "
                f"target device is {target.device!r}")
        if is_graph:
            plan = plan_graph_cached(
                graph_or_ops, cpu_pred, gpu_pred, threads=target.threads,
                mechanism=mech, step=target.step, seed=target.seed,
                bucket=bucket, tune=tune_tag, annotate=annotate,
                cache=cache)
        else:
            plan = partition_ops_plan_cached(
                graph_or_ops, cpu_pred, gpu_pred,
                mechanism=mech, step=target.step, tune=tune_tag,
                annotate=annotate, cache=cache)

    return CompiledNetwork(plan=plan, target=target, mode=mode,
                           from_cache=cache.hits > hits_before,
                           predictors=predictors)


# --------------------------------------------------------- compiled network

def _artifact_checksum(doc: Dict[str, Any]) -> str:
    # .get, not [] — a truncated artifact must surface as the checksum
    # ValueError in from_json, not a KeyError from in here
    body = {k: doc.get(k) for k in ("format", "version", "mode", "target",
                                    "plan")}
    blob = json.dumps(body, sort_keys=True, separators=(",", ":"))
    return hashlib.blake2b(blob.encode(), digest_size=16).hexdigest()


class CompiledNetwork:
    """The compile-once / run-many artifact: plan + lazily-built executor.

    Owns the `CoexecPlan` (schedule + provenance), the `Target` it was
    compiled for, and provenance extras (`mode`, `from_cache`).  Execution
    state (`PlanExecutor`, jax, the mesh) is built on first use and memoized
    per (dtype, chain-independent) configuration, so a compiled network is
    cheap to construct, serialize, and ship.
    """

    def __init__(self, plan: CoexecPlan, target: Target, *,
                 mode: str = MODE_PREDICTED, from_cache: bool = False,
                 predictors=None):
        self.plan = plan
        self.target = target
        self.mode = mode
        self.from_cache = from_cache
        self.predictors = predictors      # (cpu, gpu) when mode needed them
        self.last_report = None           # ExecutionReport of the last run
        self.calibration = None           # Calibrator from recalibrate()
        self._executors: Dict[Tuple, Any] = {}

    # --------------------------------------------------------- accessors
    @property
    def provenance(self) -> PlanProvenance:
        return self.plan.provenance

    @property
    def key(self) -> str:
        return self.plan.key

    @property
    def units(self) -> List[Unit]:
        """Legacy unit-list view (chain plans only; raises for DAG plans
        — use `.graph` instead)."""
        return self.plan.units

    @property
    def graph(self):
        """The compiled network's op graph (`repro.graph.Graph`)."""
        return self.plan.graph_ir()

    @property
    def decisions(self):
        return self.plan.decisions

    @property
    def decisions_by_node(self):
        return self.plan.decisions_by_node

    def report(self):
        """The planning-time `PlanReport` (None for bare-op plans)."""
        return self.plan.report()

    def __repr__(self) -> str:
        return (f"CompiledNetwork(mode={self.mode!r}, "
                f"device={self.target.device!r}, key={self.key!r}, "
                f"units={len(self.plan.schedule)})")

    # --------------------------------------------------------- execution
    def _mesh(self):
        from repro.core.coexec import coexec_mesh, mesh_groups
        mesh = coexec_mesh()
        if self.target.mesh == MESH_SINGLE and mesh_groups(mesh) != 1:
            import jax
            mesh = coexec_mesh(jax.devices()[:1])
        elif self.target.mesh == MESH_SPLIT and mesh_groups(mesh) != 2:
            raise RuntimeError(
                "target requires a 2-group split mesh but only a degraded "
                "single-group mesh is available (need >= 2 devices)")
        return mesh

    def executor(self, *, dtype="float32", seed: int = 0,
                 use_pallas: bool = False):
        """The (memoized) `PlanExecutor` lowering of this plan."""
        import jax.numpy as jnp

        from repro.runtime.executor import PlanExecutor
        dt = jnp.dtype(dtype)
        key = (dt.name, seed, use_pallas, self.target.mesh)
        if key not in self._executors:
            self._executors[key] = PlanExecutor(
                self.plan, mesh=self._mesh(), dtype=dt, seed=seed,
                use_pallas=use_pallas)
        return self._executors[key]

    def run(self, x=None, *, dtype="float32", chain: bool = True,
            warmup: bool = False, fused: bool = False, seed: int = 0,
            use_pallas: bool = False):
        """Execute the plan once; returns the output activation.

        `fused=True` takes the fused walk: the whole plan as one jitted
        program, one dispatch and one device sync per inference, outputs
        bit-identical to the per-node walk on the CPU.  Its per-node
        records carry the walk's wall spread pro rata by predicted
        latency; the per-node walk (`fused=False`) is the reference that
        times each op.  The
        per-op `ExecutionReport` of this run is kept on `self.last_report`
        (and `profile()` is the report-first spelling).
        """
        exe = self.executor(dtype=dtype, seed=seed, use_pallas=use_pallas)
        y, report = exe.run(x, chain=chain, warmup=warmup, fused=fused)
        self.last_report = report
        return y

    def profile(self, x=None, *, dtype="float32", chain: bool = True,
                warmup: bool = True, fused: bool = False, seed: int = 0,
                use_pallas: bool = False):
        """Execute the plan and return the executed-vs-predicted
        `ExecutionReport` (warmed up by default so timings are
        steady-state, not tracing + compilation)."""
        exe = self.executor(dtype=dtype, seed=seed, use_pallas=use_pallas)
        _, report = exe.run(x, chain=chain, warmup=warmup, fused=fused)
        self.last_report = report
        return report

    # ------------------------------------- measurement & adaptive replan
    def _store(self, store):
        from repro.measure import MeasurementStore
        if isinstance(store, MeasurementStore):
            return store
        return MeasurementStore(Path(store))

    def record(self, x=None, *, store=DEFAULT_MEASUREMENTS_DIR,
               dtype="float32", chain: bool = True, warmup: bool = True,
               fused: bool = False, seed: int = 0, use_pallas: bool = False):
        """Execute the plan and append its per-op `MeasurementRecord`s to
        the measurement store (keyed by this plan's provenance digest).

        Returns the `ExecutionReport`; the accumulated records are what
        `recalibrate()` fits on.  Fused runs record with
        `source="fused"` (the walk's wall attributed pro-rata) and feed the
        same calibration fit.
        """
        report = self.profile(x, dtype=dtype, chain=chain, warmup=warmup,
                              fused=fused, seed=seed, use_pallas=use_pallas)
        self._store(store).append(report)
        return report

    def recalibrate(self, store=DEFAULT_MEASUREMENTS_DIR):
        """Fit a `Calibrator` from every execution recorded for this plan
        and keep it on `self.calibration` (replan() uses it).

        Raises ValueError when nothing was recorded yet — call
        `record()` (ideally ≥2 runs) first.
        """
        from repro.measure import Calibrator
        records = self._store(store).load(self.key)
        if not records:
            raise ValueError(
                f"no recorded executions for plan {self.key}; call "
                f"record() first (>= 2 runs give a stable fit)")
        self.calibration = Calibrator.fit(records)
        return self.calibration

    def replan(self, calibrator=None, *, store=DEFAULT_MEASUREMENTS_DIR,
               cache: Union[PlanCache, str, Path] = DEFAULT_CACHE_DIR):
        """Re-plan with calibrated predictors; returns
        (new CompiledNetwork, PlanDiff).

        Uses `calibrator`, falling back to `self.calibration`, falling
        back to `recalibrate(store)`.  The new plan lands in the plan
        cache under a new provenance digest (calibration version folded
        in); the old entry is untouched.
        """
        if self.mode != MODE_PREDICTED or self.predictors is None:
            raise ValueError(
                "replan() needs the (cpu, gpu) predictors of a "
                "mode='predicted' compile; grid plans are "
                "measurement-driven and artifacts carry no predictors")
        cal = calibrator or self.calibration or self.recalibrate(store)
        if not isinstance(cache, PlanCache):
            cache = PlanCache(Path(cache))
        from repro.measure.replan import replan as _replan
        cpu_pred, gpu_pred = self.predictors
        hits_before = cache.hits
        new_plan, diff = _replan(self.plan, cpu_pred, gpu_pred, cal,
                                 cache=cache)
        compiled = CompiledNetwork(plan=new_plan, target=self.target,
                                   mode=self.mode,
                                   from_cache=cache.hits > hits_before,
                                   predictors=self.predictors)
        compiled.calibration = cal
        return compiled, diff

    # ------------------------------------------------------------ explain
    def explain(self) -> str:
        """Per-op decision table: what the planner chose and why it costs
        what it costs (pure plan introspection, no execution)."""
        prov = self.provenance
        tune_tag = (f" tune={prov.tune}"
                    if getattr(prov, "tune", "") else "")
        lines = [
            f"CompiledNetwork [{self.mode}] device={prov.device} "
            f"cpu{prov.threads} mechanism={prov.mechanism} "
            f"step={prov.step} planner={prov.planner}{tune_tag}",
            f"  key={self.key}  fingerprint={prov.network_fingerprint}",
            f"  {'node':>12}  {'seg':>3}  {'label':<42} "
            f"{'cpu':>5}/{'gpu':<5} {'pred_us':>9}  placement",
        ]
        n_co = 0
        for spec in self.plan.exec_specs():
            label = spec_label(spec)     # same renderer as execute --per-op
            tag = spec.node_id
            seg = f"{spec.segment}" if spec.segment >= 0 else "-"
            if spec.unit in ("pool", "add"):
                lines.append(f"  {tag:>12}  {seg:>3}  {label:<42} "
                             f"{'-':>5}/{'-':<5} {'-':>9}  gpu (no sync)")
                continue
            c_cpu, c_gpu = spec.c_slow, spec.c_fast
            mode_tag = ""
            if spec.unit in ("attention", "ssm") and spec.op is not None \
                    and getattr(spec.op, "mode", ""):
                mode_tag = f", mode={spec.op.mode}"
            if spec.coexec:
                n_co += 1
                if spec.axis != "channel":
                    from repro.kernels.registry import axis_spec
                    size = axis_spec(spec.unit, spec.axis).size(spec.op)
                    placement = (f"coexec {spec.axis}-split "
                                 f"{c_gpu}/{size}{mode_tag}")
                else:
                    placement = "co-executed"
            elif spec.unit in ("attention", "ssm"):
                if c_gpu == 0 and c_cpu == 0:
                    placement = "gpu-only (unsplit kind)"   # legacy plan
                elif c_gpu:
                    placement = f"gpu-only{mode_tag}"
                else:
                    placement = f"cpu-only{mode_tag}"
            elif c_gpu:
                placement = "gpu-only"
            else:
                placement = "cpu-only"
            lines.append(f"  {tag:>12}  {seg:>3}  {label:<42} {c_cpu:>5}/"
                         f"{c_gpu:<5} {spec.pred_total_us:>9.1f}  "
                         f"{placement}")
        n_ops = sum(1 for e in self.plan.schedule
                    if e["unit"] not in ("pool", "add"))
        parts = self.plan.segment_partition()
        n_fused = sum(1 for s in parts if s.kind == "fused")
        tail = (f"  {n_co}/{n_ops} ops co-executed | "
                f"{len(parts)} segments ({n_fused} fused)")
        if self.plan.end_to_end_us is not None:
            speedup = self.plan.baseline_us / self.plan.end_to_end_us
            tail += (f" | baseline {self.plan.baseline_us / 1e3:.1f} ms -> "
                     f"end-to-end {self.plan.end_to_end_us / 1e3:.1f} ms "
                     f"({speedup:.2f}x)")
        lines.append(tail)
        from repro.analysis import errors as diag_errors, verify_plan
        diags = verify_plan(self.plan, stats=False)
        errs = diag_errors(diags)
        if errs:
            lines.append(f"  verify: {len(errs)} error(s) — {errs[0]}")
        else:
            warns = sum(1 for d in diags if d.severity == "warning")
            lines.append("  verify: clean"
                         + (f" ({warns} warnings)" if warns else ""))
        return "\n".join(lines)

    # ------------------------------------------------------------- codecs
    def to_json(self) -> Dict[str, Any]:
        doc = {"format": ARTIFACT_FORMAT, "version": ARTIFACT_VERSION,
               "mode": self.mode, "target": self.target.to_json(),
               "plan": self.plan.to_json()}
        doc["checksum"] = _artifact_checksum(doc)
        return doc

    @staticmethod
    def from_json(doc: Dict[str, Any], *,
                  verify: bool = True) -> "CompiledNetwork":
        if doc.get("format") != ARTIFACT_FORMAT:
            raise ValueError(f"not a {ARTIFACT_FORMAT} artifact "
                             f"(format={doc.get('format')!r})")
        if doc.get("version") != ARTIFACT_VERSION:
            raise ValueError(f"unsupported artifact version "
                             f"{doc.get('version')!r}")
        if doc.get("checksum") != _artifact_checksum(doc):
            raise ValueError("artifact checksum mismatch: the file was "
                             "modified after it was saved")
        return CompiledNetwork(plan=CoexecPlan.from_json(doc["plan"],
                                                         verify=verify),
                               target=Target.from_json(doc["target"]),
                               mode=doc["mode"])

    def save(self, path: Union[str, Path]) -> Path:
        """Write the shippable artifact (target + plan + checksum) as
        JSON; `CompiledNetwork.load` round-trips it exactly."""
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.to_json(), indent=1))
        return path

    @staticmethod
    def load(path: Union[str, Path], *,
             verify: bool = True) -> "CompiledNetwork":
        """Load a saved artifact.  ``verify=True`` (default) statically
        verifies the embedded plan (`repro.analysis`) and raises
        `VerificationError` on error diagnostics; pass ``verify=False``
        to inspect a quarantined artifact anyway."""
        return CompiledNetwork.from_json(json.loads(Path(path).read_text()),
                                         verify=verify)


# ---------------------------------------------------------- plan portfolio

PORTFOLIO_FORMAT = "repro.plan_portfolio"
PORTFOLIO_VERSION = 1

#: default (batch, seq) buckets for `compile_portfolio`
DEFAULT_BUCKETS = ((1, 64), (4, 64), (4, 256))


@dataclasses.dataclass(frozen=True, order=True)
class Bucket:
    """One (batch, seq) serving shape a portfolio holds a plan for.

    Ordering is lexicographic (batch, then seq) — `select` relies on it
    to pick the *smallest* bucket that covers a step."""

    batch: int
    seq: int

    @property
    def tag(self) -> str:
        """The provenance tag folded into the plan digest."""
        return f"b{self.batch}s{self.seq}"

    def covers(self, batch: int, seq: int) -> bool:
        return self.batch >= batch and self.seq >= seq


class PlanPortfolio:
    """One compiled plan per (batch, seq) bucket — the serving scheduler's
    plan source.

    `select(batch, seq)` returns the smallest bucket that covers the
    step's live shape (falling back to the largest bucket when nothing
    covers it) together with its `CompiledNetwork`; the compiled
    network's memoized executor makes repeated selections free.
    `replace()` swaps one bucket's entry in place — the drift-triggered
    replan path.  Serializes like `CompiledNetwork` (one checksummed
    JSON document embedding every entry); loaded portfolios carry no
    predictors, so they can serve but not replan.
    """

    def __init__(self, model: str, target: Target,
                 entries: Dict[Bucket, "CompiledNetwork"], *,
                 mode: str = MODE_PREDICTED):
        if not entries:
            raise ValueError("a portfolio needs at least one bucket")
        self.model = model
        self.target = target
        self.mode = mode
        self.entries = dict(sorted(entries.items()))

    @property
    def buckets(self) -> List[Bucket]:
        return list(self.entries)

    def __len__(self) -> int:
        return len(self.entries)

    def __repr__(self) -> str:
        tags = ",".join(b.tag for b in self.buckets)
        return (f"PlanPortfolio(model={self.model!r}, "
                f"device={self.target.device!r}, buckets=[{tags}])")

    def select(self, batch: int, seq: int
               ) -> Tuple[Bucket, "CompiledNetwork"]:
        """Smallest bucket covering (batch, seq); the largest bucket when
        none covers (an oversized step is served by the biggest plan
        rather than refused)."""
        for b in self.buckets:                   # sorted ascending
            if b.covers(batch, seq):
                return b, self.entries[b]
        b = self.buckets[-1]
        return b, self.entries[b]

    def replace(self, bucket: Bucket,
                compiled: "CompiledNetwork") -> None:
        """Swap one bucket's compiled plan in place (post-replan)."""
        if bucket not in self.entries:
            raise KeyError(f"unknown bucket {bucket.tag}")
        self.entries[bucket] = compiled

    def can_replan(self) -> bool:
        """Whether entries carry predictors (in-process compiles do;
        artifacts loaded from disk do not)."""
        return all(c.predictors is not None for c in self.entries.values())

    # ------------------------------------------------------------- codecs
    def to_json(self) -> Dict[str, Any]:
        doc = {"format": PORTFOLIO_FORMAT, "version": PORTFOLIO_VERSION,
               "model": self.model, "mode": self.mode,
               "target": self.target.to_json(),
               "entries": [{"batch": b.batch, "seq": b.seq,
                            "artifact": c.to_json()}
                           for b, c in self.entries.items()]}
        doc["checksum"] = _portfolio_checksum(doc)
        return doc

    @staticmethod
    def from_json(doc: Dict[str, Any], *,
                  verify: bool = True) -> "PlanPortfolio":
        if doc.get("format") != PORTFOLIO_FORMAT:
            raise ValueError(f"not a {PORTFOLIO_FORMAT} artifact "
                             f"(format={doc.get('format')!r})")
        if doc.get("version") != PORTFOLIO_VERSION:
            raise ValueError(f"unsupported portfolio version "
                             f"{doc.get('version')!r}")
        if doc.get("checksum") != _portfolio_checksum(doc):
            raise ValueError("portfolio checksum mismatch: the file was "
                             "modified after it was saved")
        entries = {
            Bucket(e["batch"], e["seq"]):
                CompiledNetwork.from_json(e["artifact"], verify=verify)
            for e in doc["entries"]}
        return PlanPortfolio(model=doc["model"],
                             target=Target.from_json(doc["target"]),
                             entries=entries, mode=doc["mode"])

    def save(self, path: Union[str, Path]) -> Path:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.to_json(), indent=1))
        return path

    @staticmethod
    def load(path: Union[str, Path], *,
             verify: bool = True) -> "PlanPortfolio":
        """Load a saved portfolio; ``verify=False`` skips the static
        verification of every embedded plan."""
        return PlanPortfolio.from_json(json.loads(Path(path).read_text()),
                                       verify=verify)


def _portfolio_checksum(doc: Dict[str, Any]) -> str:
    body = {k: doc.get(k) for k in ("format", "version", "model", "mode",
                                    "target", "entries")}
    blob = json.dumps(body, sort_keys=True, separators=(",", ":"))
    return hashlib.blake2b(blob.encode(), digest_size=16).hexdigest()


def compile_portfolio(model, target: Target, *,
                      buckets: Sequence[Tuple[int, int]] = DEFAULT_BUCKETS,
                      blocks: int = 1,
                      cache: Union[PlanCache, str, Path] = DEFAULT_CACHE_DIR,
                      predictors=None,
                      samples: int = 400, estimators: int = 60,
                      predictor_cache: Optional[Union[str, Path]] = None
                      ) -> PlanPortfolio:
    """Compile one `CoexecPlan` per (batch, seq) bucket of a model graph.

    `model` is a model-graph name or `ModelConfig` (`tiny_decoder`,
    "gemma3-12b", ... — legacy unit networks have no batch/seq knobs).
    Each bucket lowers through `graph.from_model(model, blocks=blocks,
    cache_len=seq, batch=batch)` and compiles through the ordinary cached
    path with the bucket tag folded into provenance — recompiling the
    same portfolio in another process is all warm cache hits.  The
    predictor pair is trained (or loaded) once and shared across buckets.
    """
    from repro.graph.frontends import from_model, resolve_config
    cfg = resolve_config(model)
    seen = set()
    parsed: List[Bucket] = []
    for batch, seq in buckets:
        b = Bucket(int(batch), int(seq))
        if b.batch < 1 or b.seq < 1:
            raise ValueError(f"bucket {b.tag}: batch and seq must be >= 1")
        if b in seen:
            raise ValueError(f"duplicate bucket {b.tag}")
        seen.add(b)
        parsed.append(b)
    if predictors is None:
        kinds: Tuple[str, ...] = ("linear", "conv")
        probe = from_model(cfg, blocks=blocks, cache_len=parsed[0].seq,
                           batch=parsed[0].batch)
        kinds += tuple(sorted({n.kind for n in probe if n.op is not None
                               and n.kind in ("attention", "ssm")}))
        predictors = _trained_mux_predictors(
            target.device, target.threads, samples=samples,
            estimators=estimators, cache_dir=predictor_cache, kinds=kinds)
    entries = {}
    for b in parsed:
        graph = from_model(cfg, blocks=blocks, cache_len=b.seq,
                           batch=b.batch)
        entries[b] = compile(graph, target, mode=MODE_PREDICTED,
                             cache=cache, predictors=predictors,
                             bucket=b.tag)
    return PlanPortfolio(model=cfg.name, target=target, entries=entries)


# ------------------------------------------------------------- deprecation

#: entry points that already warned this process (one warning per spelling)
_DEPRECATED_SEEN: set = set()


def _warn_once(old: str, new: str) -> None:
    """Emit a DeprecationWarning for `old` exactly once per process."""
    if old in _DEPRECATED_SEEN:
        return
    _DEPRECATED_SEEN.add(old)
    warnings.warn(f"{old} is deprecated; use {new} instead",
                  DeprecationWarning, stacklevel=3)


def optimal_partition(op: Op, cpu_pred, gpu_pred, *,
                      mechanism: SyncMechanism = SyncMechanism.SVM_POLL,
                      step: int = 8):
    """Deprecated single-op wrapper; use `repro.compile([op], target)`."""
    _warn_once("repro.api.optimal_partition",
               "repro.compile([op], Target(...), mode='predicted')")
    from repro.core.partitioner import optimal_partition as _impl
    return _impl(op, cpu_pred, gpu_pred, mechanism=mechanism, step=step)


def grid_search_partition(op: Op, device: str, threads: int, *,
                          mechanism: SyncMechanism = SyncMechanism.SVM_POLL,
                          step: int = 8, seed: int = 0):
    """Deprecated single-op wrapper; use `repro.compile([op], target,
    mode='grid')`."""
    _warn_once("repro.api.grid_search_partition",
               "repro.compile([op], Target(...), mode='grid')")
    from repro.core.partitioner import grid_search_partition as _impl
    return _impl(op, device, threads, mechanism=mechanism, step=step,
                 seed=seed)
