#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell, to find the highest rate
the system sustains (the knee).

    python benchmarks/chip/sweep.py --workload codeqwen15_7b.chat \\
        --rates 6,8,10,12 --seconds 10 --seed 1 --out out/sweep.jsonl

One process sets the cell up once and then serves one window of
`--seconds` of arrivals at each rate in turn.  For each rate it writes
the end-to-end metrics and two signs of a growing backlog: the median time
to first token of the last third of the arrivals over that of the first
third, and how long the run went on after the last arrival.  The cell
then offers a fixed rate, set from this sweep; the benchmark's own runs
never sweep.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.load_json(ROOT / "BENCHMARK.json"),
                           args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import place_compile_cache
    place_compile_cache()

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("sweep.py: no TPU; nothing was run", file=sys.stderr)
        return 1
    runner = harness.load_module(cell["runner"])
    mix, vocab = cell["traffic"], cell["config"]["vocab_size"]
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with runner.context(cell), open(args.out, "a") as f:
        state = runner.setup(cell, args.seed, args.seconds, devices[:1])
        for rate in (float(r) for r in args.rates.split(",")):
            reqs = traffic.open_loop(dict(mix, rate_per_s=rate),
                                     args.seconds, args.seed, vocab)
            state["reqs"] = reqs
            raw = runner.window(state, args.seconds)
            rep = state.pop("report")
            by_rid = {s.rid: s for s in rep.stats}
            order = sorted(reqs, key=lambda r: r.arrival_s)
            third = max(1, len(order) // 3)
            early = [by_rid[r.rid].ttft_s for r in order[:third]]
            late = [by_rid[r.rid].ttft_s for r in order[-third:]]
            row = {
                "rate_per_s": rate, "requests": len(reqs),
                "failed": raw["failed"], "wall_s": raw["wall_s"],
                "steps": raw["steps"],
                "step_ms": raw["wall_s"] / raw["steps"] * 1e3,
                "ttft_p90_ms": float(np.percentile(raw["ttft_s"], 90)) * 1e3,
                "itl_p95_ms": float(np.percentile(raw["itl_s"], 95)) * 1e3,
                "tokens_per_s": raw["window_tokens"] / raw["window_s"],
                "run_tokens_per_s": raw["tokens"] / raw["wall_s"],
                "late_over_early_ttft": float(np.median(late)
                                              / np.median(early)),
                "after_last_arrival_s": rep.duration_s - order[-1].arrival_s,
                "occupancy": raw["totals"]["slot_steps"]
                / (raw["steps"] * mix["max_batch"]),
            }
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
