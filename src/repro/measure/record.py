"""The unified measurement schema: one record type for every timing.

Before this module, the repo had three disconnected timing formats: the
executor's per-op `OpTiming` (printed and dropped), the simulator's bare
floats (`measure_latency_us`), and the benchmark CSV rows.  None of them
could feed the others: an executed run could not become a predictor
training sample, and calibration had nothing stable to fit on.

`MeasurementRecord` is the one JSON-serializable schema they all share:

  * **what ran** — op kind + shape via the kernel registry codec
    (`op_to_json`/`op_from_json`), the split decision (`c_fast`/`c_slow`),
    the execution mode, and the chain/gather flags;
  * **the measurement** — `wall_us` (observed) vs `pred_us` (what the
    plan/oracle expected);
  * **provenance** — the measuring `source` ("executor" | "simulator"),
    the plan's simulated target `device`, the `backend` (simulator
    records), the measuring `host`, and the plan-cache digests
    (`plan_key`, `network_fingerprint`) that key the on-disk store.

Records round-trip bit-stably through JSON (`to_json` → `from_json` →
`to_json` is the identity; floats survive via repr-shortest encoding), so
an append-only JSONL store is a faithful log.  `features()` exposes the
registry's per-kind base features — the exact featurization the latency
predictors train on — which is what lets executed runs become training
samples with zero glue code (`core/predictor/dataset.training_from_records`).

This module is deliberately a leaf: it imports only the kernel registry
(itself jax-free), so the simulator, the predictors, the runtime, and the
benchmarks can all produce/consume records without import cycles.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

from repro.core.types import Op
from repro.kernels.registry import op_from_json, op_kind, op_label, op_to_json

MEASUREMENT_SCHEMA_VERSION = 1

#: record sources
SOURCE_EXECUTOR = "executor"      # wall-clock timed plan execution
SOURCE_SIMULATOR = "simulator"    # analytic device-model measurement
SOURCE_FUSED = "fused"            # fused-walk execution: per-node wall is
                                  # the walk's wall attributed pro-rata by
                                  # predicted latency

#: execution modes (executor) + the simulator's pseudo-mode
MODE_COEXEC = "coexec"
MODE_EXCLUSIVE = "exclusive"
MODE_POOL = "pool"
MODE_ADD = "add"                  # residual join of a graph plan
MODE_SIMULATED = "simulated"


@dataclasses.dataclass
class MeasurementRecord:
    """Executed(or simulated)-vs-predicted record for one measured unit.

    The first ten fields are the former executor `OpTiming` (same names,
    same order, so pre-refactor constructor calls keep working); the
    provenance tail is defaulted and filled in by whoever measures.
    """

    index: int                   # schedule position (or batch index)
    unit: str                    # registry op kind ("conv"|"linear"|
                                 # "attention"|"ssm") or "pool"|"add"
    label: str
    mode: str                    # coexec | exclusive | pool | add | simulated
    c_fast: int                  # GPU-analogue channel share (0 = unsplit)
    c_slow: int                  # CPU-analogue channel share
    chained_input: bool          # consumed the producer's group-local stack
    gathered_output: bool        # output materialized (reshard point)
    wall_us: float               # observed latency
    pred_us: float               # predicted/oracle latency (0 = none)
    op: Optional[Op] = None      # the measured op (None for pool units)
    source: str = SOURCE_EXECUTOR
    device: str = ""             # simulated target device of the plan
    backend: str = ""            # simulator records: "gpu" | "cpuN"
    host: str = ""               # platform.node() of the measuring host
    plan_key: str = ""           # PlanProvenance digest (the store key)
    network_fingerprint: str = ""
    node_id: str = ""            # graph node id ("" for bare-op records)
    segment: int = -1            # fused segment index (-1 = per-node walk)
    schema_version: int = MEASUREMENT_SCHEMA_VERSION

    def features(self) -> Optional[List[float]]:
        """The kernel registry's base features of the measured op — the
        predictors' training featurization (None for pool units)."""
        if self.op is None:
            return None
        from repro.kernels import registry
        return registry.entry_for(self.op).base_features(self.op)

    def to_json(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["op"] = None if self.op is None else op_to_json(self.op)
        return d

    @staticmethod
    def from_json(d: Dict[str, Any]) -> "MeasurementRecord":
        d = dict(d)
        if d.get("op") is not None:
            d["op"] = op_from_json(d["op"])
        return MeasurementRecord(**d)


def usable_for_fidelity(record: MeasurementRecord) -> bool:
    """The one fidelity filter: a record contributes to Σ |log(wall/pred)|
    iff both sides are positive and it is not a pool unit (pools carry no
    prediction to compare against).  Shared by `ExecutionReport`
    (`fidelity_error`/`mean_log_ratio`) and `repro.measure.calibrate`
    (fitting + `fidelity_error`), so the acceptance metric cannot drift
    between the two."""
    return (record.wall_us > 0.0 and record.pred_us > 0.0
            and record.unit != "pool")


def record_for_op(op: Op, *, index: int = 0, wall_us: float, pred_us: float,
                  mode: str = MODE_SIMULATED, source: str = SOURCE_SIMULATOR,
                  device: str = "", backend: str = "", host: str = "",
                  plan_key: str = "", network_fingerprint: str = ""
                  ) -> MeasurementRecord:
    """Build a record for a bare op (kind/label via the registry)."""
    return MeasurementRecord(
        index=index, unit=op_kind(op), label=op_label(op), mode=mode,
        c_fast=0, c_slow=0, chained_input=False, gathered_output=True,
        wall_us=float(wall_us), pred_us=float(pred_us), op=op,
        source=source, device=device, backend=backend, host=host,
        plan_key=plan_key, network_fingerprint=network_fingerprint)
