"""Find a cell's files by name, run it, and build its result line.

A cell (an entry of `workloads` in `BENCHMARK.json`) names a configuration
and a traffic mix.  Each is a file of its own:

* `configs/<config>.json`: the configuration as it is run;
* `traffic/<traffic>.json`: the mix's parameters, including the `runner`
  that runs it (`runners/<runner>.py`);
* `metrics/<metric>.py`: one reader per metric, `read(ctx)`, returning a
  number or None where it finds nothing to read;
* `limits/<workload>.json`: the limit of each number that `correct`
  compares, with the readings it was set from.

A runner module has four functions:

* `context(cell)`: a context manager that holds for the whole run (the
  configuration's matrix precision, for example);
* `setup(cell, seed, seconds, devices)`: build, load and warm up; returns
  a state;
* `window(state, seconds)`: the measured window; returns what the metric
  readers need (`ctx["raw"]`);
* `check(state, raw)`: free the program's state, run the plain reference
  and return `{name: number}` for every number that `correct` compares.

A metric reader sees `ctx` with keys `cell`, `config`, `traffic`,
`raw`, `trace` (a `trace.Trace`, or None in an untraced run), `window`
(the traced window's bounds), `peak` (the chip's row of `counts.PEAKS`),
`chips` and `setup_s`.
"""
from __future__ import annotations

import importlib.util
import json
import math
import re
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent


class CellError(ValueError):
    """The benchmark's files do not describe the requested cell."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise CellError(f"missing file {path}")
    name = "_bench_" + re.sub(r"\W", "_", str(path.with_suffix("")))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec: dict, workload: str, group: str) -> List[dict]:
    """The metrics of `group` ("end_to_end" or "per_layer") that this cell
    reports: those that list it, and those that list no cells and move
    (or are) an end-to-end metric the cell reports."""
    e2e = {m["name"] for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])}
    out = []
    for m in spec[group]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


def resolve(spec: dict, workload: str, root: Path = HERE) -> dict:
    """Everything one cell needs, found by the names in `spec`."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise CellError(f"unknown workload {workload!r}; "
                        f"known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise CellError(f"{workload}: unknown config {w['config']!r}")
    entry = configs[w["config"]]
    base = root.parents[1]
    config = load_json(base / entry["file"])
    traffic = load_json(root / "traffic" / f"{w['traffic']}.json")
    if "runner" not in traffic:
        raise CellError(f"traffic {w['traffic']!r} names no runner")
    runner = root / "runners" / f"{traffic['runner']}.py"
    metrics = {g: cell_metrics(spec, workload, g)
               for g in ("end_to_end", "per_layer")}
    for group in metrics.values():
        for m in group:
            if not (root / "metrics" / f"{m['name']}.py").is_file():
                raise CellError(f"no reader metrics/{m['name']}.py")
    if not runner.is_file():
        raise CellError(f"no runner {runner}")
    return {"workload": w, "config": config, "traffic": traffic, "runner": runner, "metrics": metrics,
            "limits_file": root / "limits" / f"{workload}.json"}


def read_metrics(metrics: List[dict], ctx: dict, root: Path = HERE
                 ) -> Dict[str, dict]:
    out = {}
    for m in metrics:
        reader = load_module(root / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is None:
            continue
        value = float(value)
        if not math.isfinite(value):
            raise ValueError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def judge(numbers: Dict[str, float], limits_file: Path
          ) -> Dict[str, dict]:
    """Each compared number beside its limit.  A number with no limit, or
    one that is not finite, fails."""
    limits = load_json(limits_file) if limits_file.is_file() else {}
    out = {}
    for name, value in numbers.items():
        limit = limits.get(name, {}).get("limit")
        ok = (limit is not None and value is not None
              and math.isfinite(value) and value <= limit)
        out[name] = {"value": value, "limit": limit, "ok": bool(ok)}
    return out


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, dict], device: dict,
                checks: Dict[str, dict],
                breakdown: Optional[dict] = None) -> str:
    doc = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        doc["breakdown"] = breakdown
    doc["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                     for k, v in checks.items()}
    return json.dumps(doc)
