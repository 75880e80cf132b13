"""Table 3 — end-to-end speedups (VGG16, ResNet-18/34, Inception-v3) with
GPU + 3 CPU threads co-execution.

Paper headline: up to 1.67x / 1.79x / 1.27x / 1.27x average e2e speedups on
Pixel 4 / Pixel 5 / Moto 2022 / OnePlus 11.

`--execute` additionally lowers one compiled network through the
`repro.compile` facade and reports executed-vs-predicted latency per op
(predictions model the phone, execution runs on this host — the per-op
ratio's spread is the fidelity signal), then runs EVERY network both ways
through the executor — per-node walk vs fused walk (one program, one
sync) — and reports the wall-time comparison (fused should never lose:
same computation, strictly fewer dispatches and device syncs).
"""
from __future__ import annotations

import repro
from benchmarks.common import DEVICES, csv_row, get_predictor, plan_cache
from repro.core.networks import NETWORKS
from repro.core.predictor.train import MuxPredictor

_PAPER_E2E = {
    ("pixel4", "vgg16"): 1.14, ("pixel4", "resnet18"): 1.54,
    ("pixel4", "resnet34"): 1.67, ("pixel4", "inception_v3"): 1.62,
    ("pixel5", "vgg16"): 1.56, ("pixel5", "resnet18"): 1.78,
    ("pixel5", "resnet34"): 1.76, ("pixel5", "inception_v3"): 1.79,
    ("moto2022", "vgg16"): 1.08, ("moto2022", "resnet18"): 1.11,
    ("moto2022", "resnet34"): 1.14, ("moto2022", "inception_v3"): 1.27,
    ("oneplus11", "vgg16"): 1.05, ("oneplus11", "resnet18"): 1.25,
    ("oneplus11", "resnet34"): 1.27, ("oneplus11", "inception_v3"): 1.17,
}


def run(execute: bool = False, exec_device: str = "moto2022",
        exec_network: str = "resnet18", chain: bool = True) -> list:
    rows = []
    threads = 3
    cache = plan_cache()
    compiled_networks = {}
    for dev in DEVICES:
        gp = MuxPredictor(get_predictor(dev, "gpu", "linear", whitebox=True),
                          get_predictor(dev, "gpu", "conv", whitebox=True))
        cp = MuxPredictor(
            get_predictor(dev, f"cpu{threads}", "linear", whitebox=False),
            get_predictor(dev, f"cpu{threads}", "conv", whitebox=False))
        target = repro.Target(device=dev, threads=threads)
        for name in NETWORKS:
            compiled = repro.compile(name, target, predictors=(cp, gp),
                                     cache=cache)
            compiled_networks[(dev, name)] = compiled
            r = compiled.report()
            rows.append(csv_row(
                f"tab3_{dev}_{name}", r.end_to_end_us,
                f"base_ms={r.baseline_us/1e3:.1f},"
                f"ind={r.individual_speedup:.2f}x,"
                f"e2e={r.end_to_end_speedup:.2f}x,"
                f"paper_e2e={_PAPER_E2E[(dev, name)]}"))
    print(f"# plan cache: {cache.hits} hits / {cache.misses} misses "
          f"({cache.root})")
    if execute:
        rows += _execute_rows(compiled_networks[(exec_device, exec_network)],
                              exec_device, exec_network, chain)
        for name in NETWORKS:
            rows += _fused_rows(compiled_networks[(exec_device, name)],
                                exec_device, name)
    return rows


def _execute_rows(compiled, dev: str, name: str, chain: bool) -> list:
    """Lower one compiled network into actual split execution; one row per
    op (executed wall us vs the plan's predicted us) plus a summary row."""
    rep = compiled.profile(chain=chain, warmup=True)
    rows = []
    for t in rep.timings:
        ratio = (f"{t.wall_us / t.pred_us:.1f}" if t.pred_us > 0
                 else "na")                    # pool units carry no pred
        rows.append(csv_row(
            f"tab3_exec_{dev}_{name}_{t.index:02d}_{t.unit}", t.wall_us,
            f"pred_us={t.pred_us:.1f},ratio={ratio},mode={t.mode},"
            f"split={t.c_fast}/{t.c_slow},"
            f"chained={int(t.chained_input)}"))
    rows.append(csv_row(
        f"tab3_exec_{dev}_{name}_total", rep.wall_us,
        f"pred_us={rep.predicted_us:.1f},"
        f"reshard={rep.reshard_points},elided={rep.elided},"
        f"split_capable={int(rep.split_capable)}"))
    print("# " + rep.fidelity_summary())
    return rows


def _fused_rows(compiled, dev: str, name: str) -> list:
    """Fused (one-program walk) vs unfused (per-node walk) wall time for one
    network — best of 2 timed runs each, after the shared warmup."""
    best = {}
    for fused in (False, True):
        reps = [compiled.profile(fused=fused, warmup=True)
                for _ in range(2)]
        best[fused] = min(reps, key=lambda r: r.wall_us)
    ru, rf = best[False], best[True]
    speedup = ru.wall_us / rf.wall_us if rf.wall_us > 0 else float("inf")
    print(f"# {name}: fused {rf.wall_us / 1e3:.1f} ms "
          f"({rf.segments} segments, {rf.sync_points} syncs) "
          f"vs unfused {ru.wall_us / 1e3:.1f} ms ({ru.sync_points} syncs) "
          f"-> {speedup:.2f}x")
    return [
        csv_row(f"tab3_exec_{dev}_{name}_unfused", ru.wall_us,
                f"sync={ru.sync_points},reshard={ru.reshard_points},"
                f"elided={ru.elided}"),
        csv_row(f"tab3_exec_{dev}_{name}_fused", rf.wall_us,
                f"segments={rf.segments},"
                f"sync={rf.sync_points},reshard={rf.reshard_points},"
                f"elided={rf.elided},speedup={speedup:.2f}x"),
    ]


if __name__ == "__main__":
    import argparse

    from benchmarks.common import bench_main

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--execute", action="store_true",
                    help="execute one cached plan and report per-op "
                         "executed-vs-predicted latency")
    ap.add_argument("--exec-device", default="moto2022", choices=DEVICES)
    ap.add_argument("--exec-network", default="resnet18",
                    choices=sorted(NETWORKS))
    ap.add_argument("--no-chain", action="store_true",
                    help="gather after every co-executed op")
    args = ap.parse_args()
    # --execute writes to a separate suite so plain tab3.json stays a
    # stable row set for cross-PR tracking
    suite = "tab3_e2e" if args.execute else "tab3"
    extra = ({"execute": True, "exec_device": args.exec_device,
              "exec_network": args.exec_network,
              "chain": not args.no_chain} if args.execute else None)
    bench_main(suite, lambda: run(execute=args.execute,
                                  exec_device=args.exec_device,
                                  exec_network=args.exec_network,
                                  chain=not args.no_chain), extra=extra)
