"""Shared benchmark infrastructure: predictor training with disk cache,
plus machine-readable result reports.

All paper benchmarks share one pool of trained GBDT predictors per
(device, backend, op kind, whitebox) tuple, cached under reports/predictors
so repeated benchmark runs are fast.  Scale knobs (--full) switch between
a CI-sized run and the paper-scale dataset (12,500 configs per op kind).

Every suite also writes a JSON report under reports/bench/<suite>.json
(suite name, host device, git sha, parsed metric rows) so the perf
trajectory is trackable across PRs: `bench_main` is the standalone-script
entry point, and `benchmarks.run` calls `write_bench_report` per suite.
"""
from __future__ import annotations

import datetime
import json
import os
import platform
import subprocess
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.compile_cache import place_compile_cache                   # noqa: E402

place_compile_cache()

from repro.core.predictor import (LatencyPredictor, sample_conv_ops,   # noqa: E402
                                  sample_linear_ops, train_predictor)
from repro.core.predictor.gbdt import GBDTParams                      # noqa: E402
from repro.measure import MeasurementRecord, MeasurementStore         # noqa: E402
from repro.runtime import PlanCache                                   # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
REPORTS = ROOT / "reports"
PRED_CACHE = REPORTS / "predictors"
PLAN_CACHE_DIR = REPORTS / "plans"
BENCH_REPORTS = REPORTS / "bench"
MEASUREMENTS_DIR = REPORTS / "measurements"


def plan_cache() -> PlanCache:
    """Fresh handle on the shared on-disk plan cache (counters start at 0)."""
    return PlanCache(PLAN_CACHE_DIR)


def measurement_store() -> MeasurementStore:
    """Handle on the shared on-disk measurement store (JSONL per plan)."""
    return MeasurementStore(MEASUREMENTS_DIR)

FULL = os.environ.get("BENCH_FULL", "0") == "1"
N_TRAIN = 10_000 if FULL else 2_500
N_ESTIMATORS = 300 if FULL else 120

DEVICES = ("pixel4", "pixel5", "moto2022", "oneplus11")

_memo: Dict[Tuple, LatencyPredictor] = {}


def train_ops(kind: str, seed: int = 1):
    if kind == "linear":
        return sample_linear_ops(N_TRAIN, seed=seed)
    return sample_conv_ops(N_TRAIN, seed=seed)


def get_predictor(device: str, backend: str, kind: str,
                  whitebox: bool = True) -> LatencyPredictor:
    key = (device, backend, kind, whitebox, N_TRAIN, N_ESTIMATORS)
    if key in _memo:
        return _memo[key]
    tag = f"{device}_{backend}_{kind}_{'wb' if whitebox else 'bb'}" \
          f"_{N_TRAIN}_{N_ESTIMATORS}.pkl"
    path = PRED_CACHE / tag
    if path.exists():
        p = LatencyPredictor.load(path)
    else:
        t0 = time.time()
        p = train_predictor(train_ops(kind), device, backend,
                            whitebox=whitebox,
                            params=GBDTParams(n_estimators=N_ESTIMATORS))
        print(f"  [train] {tag} ({time.time()-t0:.0f}s)")
        p.save(path)
    _memo[key] = p
    return p


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.2f},{derived}"


# ------------------------------------------------------- JSON reporting

def _git_sha() -> Optional[str]:
    try:
        out = subprocess.run(["git", "rev-parse", "HEAD"], cwd=ROOT,
                             capture_output=True, text=True, timeout=10)
        return out.stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        return None


def parse_rows(rows: List[str]) -> List[Dict[str, object]]:
    """`name,us_per_call,derived` CSV rows -> metric dicts (the derived
    field may itself contain commas, hence maxsplit)."""
    out = []
    for row in rows:
        name, us, derived = str(row).split(",", 2)
        out.append({"name": name, "us_per_call": float(us),
                    "derived": derived})
    return out


def write_bench_report(suite: str, rows: List[str], *,
                       extra: Optional[Dict[str, object]] = None,
                       measurements: Optional[List[MeasurementRecord]] = None
                       ) -> Path:
    """Persist one suite's results as reports/bench/<suite>.json.

    `measurements` embeds unified-schema records in the report (the
    executor/calibration suites carry their raw per-op measurements
    alongside the derived CSV rows); `load_bench_measurements` reads them
    back as `MeasurementRecord`s.
    """
    doc = {
        "suite": suite,
        "device": platform.processor() or platform.machine(),
        "host": platform.node(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "git_sha": _git_sha(),
        "generated_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "full": FULL,
        "metrics": parse_rows(rows),
    }
    if measurements:
        doc["measurements"] = [r.to_json() for r in measurements]
    if extra:
        doc.update(extra)
    BENCH_REPORTS.mkdir(parents=True, exist_ok=True)
    path = BENCH_REPORTS / f"{suite}.json"
    path.write_text(json.dumps(doc, indent=1))
    return path


def load_bench_measurements(suite: str) -> List[MeasurementRecord]:
    """The unified-schema records a suite's JSON report embedded (empty
    for suites that only wrote CSV rows)."""
    path = BENCH_REPORTS / f"{suite}.json"
    if not path.exists():
        return []
    doc = json.loads(path.read_text())
    return [MeasurementRecord.from_json(d)
            for d in doc.get("measurements", [])]


def bench_main(suite: str, run_fn, *,
               extra: Optional[Dict[str, object]] = None,
               measurements_fn=None) -> List[str]:
    """Standalone-script entry point: print CSV rows AND write the JSON
    report (used by every tab*/fig* script's __main__).  A suite that
    collects unified-schema measurements passes `measurements_fn` (called
    after `run_fn`, returns the records to embed)."""
    rows = [str(r) for r in run_fn()]
    print("\n".join(rows))
    measurements = measurements_fn() if measurements_fn else None
    path = write_bench_report(suite, rows, extra=extra,
                              measurements=measurements)
    print(f"# wrote {path.relative_to(ROOT)}")
    return rows
