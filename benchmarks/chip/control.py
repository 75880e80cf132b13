#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from.

    python benchmarks/chip/control.py --workload resnet18.b1 \\
        --seeds 11,12,13 --seconds 2 --out out/readings.jsonl

For each seed, in one process, this runs the cell as `run.py` does (set-up,
a window of `--seconds`, then the check against the plain reference) and
also computes the control: the reference itself, put in the program's
place and computed a step below the configuration's precision.  It writes
one JSON line per seed: the program's numbers and the control's
(`<name>.control`).  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def readings(cell: dict, seed: int, seconds: float, devices) -> dict:
    runner = harness.load_module(cell["runner"])
    chips = cell["workload"]["chips"]
    t0 = time.perf_counter()
    with runner.context(cell):
        state = runner.setup(cell, seed, seconds, devices[:chips])
        raw = runner.window(state, seconds)
        numbers = runner.check(state, raw, control=True)
    del state
    gc.collect()
    return {"seed": seed, "attempted": raw["attempted"],
            "failed": raw["failed"], "seconds": time.perf_counter() - t0,
            **numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.resolve(spec, args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import place_compile_cache
    place_compile_cache()

    import jax
    # every program, however quick to compile, is kept in the checkout's
    # cache, so that only a cell's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control.py: no TPU; nothing was run", file=sys.stderr)
        return 1
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            row = {"workload": args.workload, **readings(
                cell, seed, args.seconds, devices)}
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
