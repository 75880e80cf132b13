#!/usr/bin/env python3
"""Attribute one cell's device idle time to the program's spans.

    python benchmarks/chip/span_report.py --workload resnet18.b1 \\
        --seed 7 --seconds 10

One process: the cell's set-up, one window with the profiler off, then
one with it on, over the same inputs; the reference check is not run.
The last line of standard output is one JSON object:

* `untraced`, `traced`: the cell's end-to-end metrics of each window
  (traced over untraced is what the profiler costs, spans included);
* `per_layer`: the cell's per-layer metrics of the traced window;
* `counts`: how many of each program span the traced window holds;
* `coverage`: the share of the traced window inside the program's
  top-level spans (`repro.exec.run`, `repro.sched.step`), None where the
  program records none;
* `idle_by_span`: the device's idle seconds in the traced window by the
  innermost program span the host was in (`spans.idle_by_span`);
* `busy_s`, `window_s` and `device`.

Like `run.py` it runs only on a TPU with the cell's chips (exit code 1
otherwise, 2 when the benchmark's files are missing).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import json              # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import harness           # noqa: E402


def span_report(cell: dict, seed: int, seconds: float, devices) -> dict:
    import jax

    import counts
    import spans
    from tracing import Trace, find_xplane

    runner = harness.load_module(cell["runner"])
    chips = cell["workload"]["chips"]
    out = {}
    with runner.context(cell):
        state = runner.setup(cell, seed, seconds, devices[:chips])
        ctx = {"cell": cell, "config": cell["config"],
               "traffic": cell["traffic"], "trace": None, "window": None,
               "peak": counts.peak(devices[0].device_kind),
               "chips": chips, "setup_s": time.perf_counter() - T_START}
        e2e = [m for m in cell["metrics"]["end_to_end"]
               if m["name"] != "setup_s"]
        with jax.profiler.TraceAnnotation("bench.window"):
            ctx["raw"] = runner.window(state, seconds)
        out["untraced"] = harness.read_metrics(e2e, ctx)
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            jax.profiler.start_trace(tdir)
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    ctx["raw"] = runner.window(state, seconds)
            finally:
                jax.profiler.stop_trace()
            tr = Trace.from_file(find_xplane(tdir))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    del state
    win = tr.window()
    out["traced"] = harness.read_metrics(e2e, ctx)
    ctx["trace"], ctx["window"] = tr, win
    out["per_layer"] = harness.read_metrics(cell["metrics"]["per_layer"],
                                            ctx)
    out["counts"] = {n: len(spans.intervals(ctx, n)) for n in sorted(
        {n for n, _, _ in tr.host if n.startswith(spans.PREFIX)})}
    out["coverage"] = spans.coverage(tr, win)
    out["idle_by_span"] = spans.idle_by_span(tr, win)
    out["busy_s"] = tr.busy_ns(win) / 1e9
    out["window_s"] = (win[1] - win[0]) / 1e9
    return out


def main(argv=None) -> int:
    import run

    args = run.parse(argv)
    try:
        spec = harness.load_json(ROOT / "BENCHMARK.json")
        cell = harness.resolve(spec, args.workload)
    except (OSError, harness.CellError, KeyError, ValueError) as e:
        print(f"span_report.py: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import place_compile_cache
    place_compile_cache()                    # before jax is imported

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    chips = cell["workload"]["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"span_report.py: {args.workload} needs {chips} TPU chip(s); "
              f"JAX finds {len(devices)} {devices[0].platform} device(s).",
              file=sys.stderr)
        return 1
    out = span_report(cell, args.seed, args.seconds, devices)
    out["device"] = run.device_info(devices, chips)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
