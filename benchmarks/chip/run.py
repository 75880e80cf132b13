#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python benchmarks/chip/run.py --workload resnet18.b1 --seed 7 \\
        --seconds 10 --trace 0

Each run is a new process.  It places JAX's compilation cache inside the
checkout, refuses to run unless JAX finds a TPU with as many chips as the
cell asks for, builds the cell from the seed and warms up its shapes
(`setup_s`), measures for `--seconds`, and then checks what the timed path
produced against the plain reference.  With `--trace 0` it reports the
cell's end-to-end metrics; with `--trace 1` the window runs under JAX's
profiler and it reports the per-layer metrics, the device's busy and
window seconds, and a breakdown of device time and idle gaps.

The last line of standard output is one JSON object (`correct`,
`attempted`, `failed`, `metrics`, `device`, optionally `breakdown`, and
last `checks`: each compared number beside its limit); the compared
numbers are also the last lines of standard error.  Exit codes: 0 after a
run (correct or not), 1 without a TPU or enough chips, 2 when the
benchmark's files or the program are missing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import gc                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import harness           # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(devices, chips: int) -> dict:
    used = devices[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in used]
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": int(max(peaks))}


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devices,
             *, t_start: float = T_START) -> dict:
    """Set up, measure and check one cell; returns the result's parts."""
    import jax

    import counts
    from tracing import Trace, find_xplane, summarize

    runner = harness.load_module(cell["runner"])
    chips = cell["workload"]["chips"]
    with runner.context(cell):
        t_setup = time.perf_counter()
        state = runner.setup(cell, seed, seconds, devices[:chips])
        setup_s = time.perf_counter() - t_start
        phases = {"before_setup": t_setup - t_start,
                  **state.get("setup_phases", {})}
        print("setup " + " ".join(f"{k} {v:.3f}" for k, v in
                                  phases.items()), file=sys.stderr)
        tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        if trace:
            jax.profiler.start_trace(tdir)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                raw = runner.window(state, seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        device = device_info(devices, chips)
        t_check = time.perf_counter()
        numbers = runner.check(state, raw)
        print(f"check_s {time.perf_counter() - t_check:.3f}", file=sys.stderr)
    del state
    gc.collect()

    ctx = {"cell": cell, "config": cell["config"],
           "traffic": cell["traffic"], "raw": raw, "trace": None,
           "window": None, "peak": counts.peak(device["kind"]),
           "chips": chips, "setup_s": setup_s}
    breakdown = None
    if trace:
        tr = Trace.from_file(find_xplane(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        ctx["trace"], ctx["window"] = tr, tr.window()
        summary = summarize(tr)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        breakdown = summary["breakdown"]
    group = "per_layer" if trace else "end_to_end"
    metrics = harness.read_metrics(cell["metrics"][group], ctx)
    checks = harness.judge(numbers, cell["limits_file"])
    failed = int(raw.get("failed", 0))
    correct = failed == 0 and all(c["ok"] for c in checks.values())
    return {"correct": correct, "attempted": int(raw["attempted"]),
            "failed": failed, "metrics": metrics, "device": device,
            "checks": checks, "breakdown": breakdown}


def main(argv=None) -> int:
    args = parse(argv)
    try:
        spec = harness.load_json(ROOT / "BENCHMARK.json")
        cell = harness.resolve(spec, args.workload)
    except (OSError, harness.CellError, KeyError, ValueError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"run.py: {ROOT} holds no program (src/repro)",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import place_compile_cache
    place_compile_cache()                    # before jax is imported

    import jax
    # every program, however quick to compile, is kept in the checkout's
    # cache, so that only a cell's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    chips = cell["workload"]["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"run.py: {args.workload} needs {chips} TPU chip(s); JAX "
              f"finds {len(devices)} {devices[0].platform} device(s). "
              f"Nothing was run.", file=sys.stderr)
        return 1
    res = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    for name, c in res["checks"].items():
        verdict = "ok" if c["ok"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    print(harness.result_line(**res))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
