"""`python -m repro` — one CLI over the compile→run facade.

Subcommands:

  * `plan`      — compile (or fetch from cache) a co-execution plan; can
                  also write the plan JSON (`--out`) and the shippable
                  `CompiledNetwork` artifact (`--save`).
  * `execute`   — compile (or load an artifact) and run the plan end to
                  end, reporting executed-vs-predicted fidelity per op.
  * `calibrate` — close the loop: execute + record measurements, fit a
                  `Calibrator`, replan with corrected predictors, and
                  print the plan diff.
  * `tune`      — measured Pallas tile-config search for a network's ops,
                  cached in the on-disk `TuneCache`; `plan/execute
                  --tune` attach the winners to compiled plans.
  * `verify`    — statically verify plan/portfolio/bench/tune artifacts
                  (`repro.analysis`): schema discipline, axis/tile
                  legality, segment invariants, provenance digests —
                  without importing jax or executing anything.
  * `lint`      — run the repo-contract linter over `src/repro`
                  (import-light modules, registry completeness,
                  no-silent-clamp).
  * `bench`     — forward to the paper benchmark driver (`benchmarks.run`).
  * `serve`     — forward to the serving launcher (`repro.launch.serve`):
                  the fixed-batch engine, or — with `--arrivals poisson
                  --portfolio ...` — the continuous scheduler over a
                  bucketed plan portfolio with drift-triggered replanning.

`plan` and `execute` are thin clients of `repro.compile`; their provenance
(and therefore their on-disk cache entries) is bit-identical to the
retired `python -m repro.runtime.plan` / `python -m repro.runtime.executor`
CLIs, which now forward here with a DeprecationWarning.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path
from typing import Optional, Sequence


def _add_compile_args(ap: argparse.ArgumentParser) -> None:
    from repro.core.simulator.devices import DEVICES
    from repro.core.sync import SyncMechanism

    # no choices= here: unknown names surface repro.api's ValueError, which
    # lists both registries (unit networks + model graphs) in one message
    ap.add_argument("--network", default="resnet18",
                    help="unit network (vgg16, resnet18, ...) or any name "
                         "--model accepts")
    ap.add_argument("--model", default=None,
                    help="decoder-block model graph via graph.from_model "
                         "(tiny_decoder, tiny_ssm, gemma3-12b, ...); "
                         "overrides --network")
    ap.add_argument("--cache-len", type=int, default=128,
                    help="KV-cache length of --model attention nodes")
    ap.add_argument("--tokens", type=int, default=1,
                    help="tokens per decode step of --model graphs "
                         "(chunked prefill; pure-SSM configs only)")
    ap.add_argument("--blocks", type=int, default=1,
                    help="decoder blocks to chain for --model graphs")
    ap.add_argument("--device", default="moto2022", choices=sorted(DEVICES))
    ap.add_argument("--threads", type=int, default=3)
    ap.add_argument("--mechanism", default="svm_poll",
                    choices=[m.value for m in SyncMechanism])
    ap.add_argument("--step", type=int, default=8,
                    help="candidate-grid step (channels)")
    ap.add_argument("--mode", default="predicted",
                    choices=["predicted", "grid"],
                    help="predicted = GBDT planning (deployable); "
                         "grid = measurement-driven oracle")
    ap.add_argument("--cache-dir", default="reports/plans",
                    help="on-disk PlanCache directory")
    ap.add_argument("--samples", type=int, default=400,
                    help="training ops per predictor (simulator-measured)")
    ap.add_argument("--estimators", type=int, default=60,
                    help="GBDT trees per predictor")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--predictor-cache", default=None,
                    help="optional directory to cache trained predictors "
                         "(a load is checksum-identical to a retrain)")
    ap.add_argument("--tune", action="store_true",
                    help="autotune kernel tile configs on a plan-cache "
                         "miss and attach the winners to the plan "
                         "(tuned plans get their own cache entries)")
    ap.add_argument("--tune-cache-dir", default="reports/tune",
                    help="on-disk TuneCache directory (measured tile "
                         "choices, content-addressed)")


class _UserInputError(Exception):
    """A bad CLI input (unknown name, invalid target, ...) — printed as a
    clean one-line error; internal failures keep their tracebacks."""


def _network_arg(args):
    """The compile() input: model names — via --model or --network — build
    a decoder-block graph honoring the CLI's blocks/cache-len knobs;
    everything else resolves by name inside `repro.compile`."""
    name = args.model or args.network
    if args.model or _is_model_name(name):
        from repro.graph import from_model
        return from_model(name, blocks=args.blocks,
                          cache_len=args.cache_len,
                          tokens=getattr(args, "tokens", 1))
    return name


def _is_model_name(name: str) -> bool:
    from repro.core.networks import NETWORKS
    if name in NETWORKS:
        return False
    from repro.graph.frontends import model_names
    return name in model_names()


def _compile(args):
    from repro.api import Target, compile as _api_compile
    t0 = time.time()
    # ValueErrors up to and including compile() are user-input problems
    # (unknown name/device/mechanism, bad mode, predictor/target mismatch)
    # and print as one-line errors; later failures keep their tracebacks
    try:
        target = Target(device=args.device, threads=args.threads,
                        mechanism=args.mechanism, step=args.step,
                        seed=args.seed)
        compiled = _api_compile(_network_arg(args), target, mode=args.mode,
                                cache=args.cache_dir, samples=args.samples,
                                estimators=args.estimators,
                                predictor_cache=args.predictor_cache,
                                tune=getattr(args, "tune", False),
                                tune_cache=getattr(args, "tune_cache_dir",
                                                   None))
    except ValueError as e:
        raise _UserInputError(str(e)) from e
    return compiled, time.time() - t0


def _cache_status(compiled) -> str:
    return "HIT" if compiled.from_cache else "MISS (compiled)"


def _cmd_plan(args) -> int:
    from repro.runtime.cache import PlanCache
    compiled, dt = _compile(args)
    plan = compiled.plan
    n_co = sum(1 for d in plan.decisions if not d.exclusive)
    name = args.model or args.network
    print(f"plan {name} on {args.device} (cpu{args.threads}, "
          f"{args.mechanism}, {args.mode}): cache {_cache_status(compiled)}")
    print(f"  compiled in {dt:.1f}s (predictors + planning; a warm hit is "
          f"a pure JSON read)")
    print(f"  key {plan.key} -> "
          f"{PlanCache(Path(args.cache_dir)).path_for(plan.provenance)}")
    if plan.end_to_end_us is not None:
        print(f"  baseline (GPU only): {plan.baseline_us / 1e3:.1f} ms | "
              f"end-to-end co-exec: {plan.end_to_end_us / 1e3:.1f} ms "
              f"({plan.baseline_us / plan.end_to_end_us:.2f}x)")
    print(f"  {n_co}/{len(plan.decisions)} ops co-executed")
    # write artifacts before the explain dump: a consumer closing the pipe
    # early (`... | head`) must not be able to skip the requested writes
    if args.out:
        plan.save(Path(args.out))
        print(f"  wrote plan {args.out}")
    if args.save:
        compiled.save(args.save)
        print(f"  wrote artifact {args.save}")
    if args.explain:
        print(compiled.explain())
    if args.verbose:
        from repro.analysis import rejections
        print(f"  {rejections.summary()}")
        for digest, rule, detail in rejections.entries():
            why = f": {detail}" if detail else ""
            print(f"    {digest} rejected by {rule}{why}")
    return 0


def _cmd_execute(args) -> int:
    if args.artifact:
        from repro.api import CompiledNetwork
        compiled = CompiledNetwork.load(args.artifact)
        print(f"execute artifact {args.artifact} "
              f"(device {compiled.target.device}, key {compiled.key})")
    else:
        compiled, _ = _compile(args)
        print(f"execute {args.model or args.network} on {args.device} plan "
              f"{compiled.key} (cache {_cache_status(compiled)})")
    exe = compiled.executor()
    groups = ("2-group split mesh" if exe.split_capable
              else "degraded single-group mesh (exclusive execution)")
    print(f"  {groups}")
    report = compiled.profile(chain=not args.no_chain,
                              warmup=not args.no_warmup)
    if args.fused:
        import numpy as np
        # differential spelling: run both walks on the same input and
        # compare outputs byte-for-byte (the harness CI greps this line)
        x = exe.input_template()
        y_unfused = compiled.run(x, warmup=True)
        rep_unfused = compiled.last_report
        y_fused = compiled.run(x, warmup=True, fused=True)
        rep_fused = compiled.last_report
        identical = (np.asarray(y_fused).tobytes()
                     == np.asarray(y_unfused).tobytes())
        n_seg = rep_fused.segments
        print(f"  fused: {n_seg} segments, {rep_fused.sync_points} syncs "
              f"(vs {rep_unfused.sync_points} unfused), outputs "
              f"{'bit-identical' if identical else 'DIVERGED'}")
        print(f"  fused wall {rep_fused.wall_us / 1e3:.1f} ms vs unfused "
              f"{rep_unfused.wall_us / 1e3:.1f} ms")
        report = rep_fused
        if not identical:
            return 1
    if args.per_op:
        for t in report.timings:
            extra = " chained" if t.chained_input else ""
            if t.segment >= 0:
                extra += f" seg={t.segment}"
            print(f"  [{t.index:02d}] {t.label:42s} {t.mode:9s} "
                  f"{t.c_fast}/{t.c_slow} wall {t.wall_us:9.0f}us "
                  f"pred {t.pred_us:8.1f}us{extra}")
    print(report.fidelity_summary())
    return 0


def _cmd_calibrate(args) -> int:
    from repro.measure import MeasurementStore, fidelity_error

    if args.mode != "predicted":
        print("error: calibrate needs mode='predicted' (grid plans are "
              "measurement-driven; there are no predictors to calibrate)",
              file=sys.stderr)
        return 2
    compiled, dt = _compile(args)
    print(f"calibrate {args.model or args.network} on {args.device} "
          f"(cpu{args.threads}, {args.mechanism}): plan {compiled.key} "
          f"(cache {_cache_status(compiled)}, {dt:.1f}s)")
    store = MeasurementStore(Path(args.store_dir))
    for i in range(args.runs):
        # the executor warms up once; later runs are already steady-state
        rep = compiled.record(store=store, warmup=not args.no_warmup)
        print(f"  run {i + 1}/{args.runs}: {rep.fidelity_summary()}")
    records = store.load(compiled.key)
    cal = compiled.recalibrate(store)
    pre = fidelity_error(records)
    post = cal.fidelity_error(records)
    print(f"  {cal.summary()}" if args.verbose else
          f"  calibrator {cal.version}: {len(cal.corrections)} corrections "
          f"from {cal.n_records} records")
    shrink = f" ({pre / post:.1f}x smaller)" if post > 0 else ""
    print(f"  fidelity error {pre:.2f} -> {post:.2f} "
          f"(sum |log wall/pred| over {cal.n_records} usable records)"
          f"{shrink}")
    if args.save_calibration:
        path = cal.save(Path(args.save_calibration))
        print(f"  wrote calibrator {path}")
    recompiled, diff = compiled.replan(cal, store=store,
                                       cache=args.cache_dir)
    print(diff.summary())
    from repro.runtime.cache import PlanCache
    print(f"  new plan cached at "
          f"{PlanCache(Path(args.cache_dir)).path_for(recompiled.provenance)}")
    print(f"  measurements {store.path_for(compiled.key)} "
          f"({len(records)} records)")
    return 0


def _cmd_tune(args) -> int:
    """Measured tile search for every unique op of a network, through the
    on-disk TuneCache (warm entries are returned without measuring)."""
    from repro.api import _resolve_graph
    from repro.kernels import registry
    from repro.runtime.autotune import (TuneCache, autotune, measure_device,
                                        tune_cache_version)
    try:
        graph_or_ops, is_graph = _resolve_graph(_network_arg(args))
    except ValueError as e:
        raise _UserInputError(str(e)) from e
    ops = ([n.op for n in graph_or_ops if n.op is not None] if is_graph
           else list(graph_or_ops))
    unique = list(dict.fromkeys(ops))
    cache = TuneCache(Path(args.tune_cache_dir))
    device, backend = measure_device()
    print(f"tune {args.model or args.network}: {len(unique)} unique ops on "
          f"{device}/{backend} ({tune_cache_version()}) -> {cache.root}")
    tuned = 0
    for op in unique:
        spec = registry.tile_spec(registry.op_kind(op))
        n_cand = len(spec.configs(op))
        t0 = time.time()
        hits = cache.hits
        best = autotune(op, cache=cache, device=device, backend=backend,
                        reps=args.reps)
        warm = cache.hits > hits
        default = spec.default_config(op)
        if best == default:
            verdict = f"default {best.label()}"
        else:
            tuned += 1
            verdict = f"{default.label()} -> {best.label()}"
        src = "cache" if warm else f"measured {n_cand} candidates"
        print(f"  {registry.op_label(op):42s} {verdict:28s} "
              f"({src}, {time.time() - t0:.1f}s)")
    print(f"  {tuned}/{len(unique)} ops tuned away from the default "
          f"blocking ({cache.hits} cache hits)")
    return 0


def _cmd_verify(args) -> int:
    """Statically verify artifacts on disk; exit 1 on error-severity
    diagnostics (warnings and info never fail the run)."""
    from repro.analysis import SEV_ERROR, SEV_INFO, SEV_WARNING, verify_path
    paths = [Path(p) for p in args.paths]
    if args.all_artifacts:
        for d in ("reports/plans", "reports/tune", "reports/bench"):
            paths.extend(sorted(Path(d).glob("*.json")))
    if not paths:
        print("error: nothing to verify (pass artifact paths or "
              "--all-artifacts)", file=sys.stderr)
        return 2
    n_err = n_warn = 0
    for p in paths:
        kind, diags = verify_path(p, stats=args.verbose)
        errs = [d for d in diags if d.severity == SEV_ERROR]
        warns = [d for d in diags if d.severity == SEV_WARNING]
        n_err += len(errs)
        n_warn += len(warns)
        print(f"{'FAIL' if errs else 'ok':4s} {kind:9s} {p}")
        shown = errs + warns
        if args.verbose:
            shown += [d for d in diags if d.severity == SEV_INFO]
        for d in shown:
            print(f"       {d}")
    print(f"verified {len(paths)} artifact(s): {n_err} error(s), "
          f"{n_warn} warning(s)")
    return 1 if n_err else 0


def _cmd_lint(args) -> int:
    """Run the repo-contract linter; exit 1 on any finding."""
    from repro.analysis.lint import LINT_RULES, lint_repo, package_root
    pkg = Path(args.src) if args.src else package_root()
    diags = lint_repo(pkg)
    for d in diags:
        print(d)
    rules = ", ".join(sorted(LINT_RULES))
    print(f"lint {pkg}: {len(diags)} finding(s) across [{rules}]")
    return 1 if diags else 0


def _cmd_bench(rest: Sequence[str]) -> int:
    # benchmarks/ lives at the repo root (it is not an installed package);
    # running from the checkout works directly, an installed interpreter
    # needs the cwd fallback
    try:
        from benchmarks.run import main as bench_main
    except ImportError:
        sys.path.insert(0, str(Path.cwd()))
        try:
            from benchmarks.run import main as bench_main
        except ImportError:
            print("error: cannot import benchmarks.run — run `python -m "
                  "repro bench` from the repository root", file=sys.stderr)
            return 2
    return bench_main(list(rest)) or 0


def _cmd_serve(rest: Sequence[str]) -> int:
    from repro.launch.serve import serve_main
    return serve_main(list(rest)) or 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    # bench/serve forward their whole tail verbatim; dispatch before
    # argparse so leading options (`serve --arch ...`) survive (argparse
    # REMAINDER refuses option-looking tokens in first position)
    from repro.compile_cache import place_compile_cache
    if argv[:1] == ["bench"]:
        return _cmd_bench(argv[1:])
    if argv[:1] == ["serve"]:
        place_compile_cache()
        return _cmd_serve(argv[1:])
    ap = argparse.ArgumentParser(
        prog="python -m repro",
        description="Fine-grained CPU-GPU co-execution: compile, run, "
                    "benchmark, serve.")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p_plan = sub.add_parser(
        "plan", help="compile (or fetch from cache) a co-execution plan")
    _add_compile_args(p_plan)
    p_plan.add_argument("--out", default=None,
                        help="also write the plan JSON to this path")
    p_plan.add_argument("--save", default=None,
                        help="write the shippable CompiledNetwork artifact "
                             "(plan + target + checksum) to this path")
    p_plan.add_argument("--explain", action="store_true",
                        help="print the per-op decision table")
    p_plan.add_argument("-v", "--verbose", action="store_true",
                        help="also print cache-rejection counts (which "
                             "verifier rule each stale entry failed)")

    p_exec = sub.add_parser(
        "execute", help="execute a compiled plan end to end and report "
                        "executed-vs-predicted fidelity")
    _add_compile_args(p_exec)
    p_exec.add_argument("--artifact", default=None,
                        help="execute a saved CompiledNetwork artifact "
                             "instead of compiling")
    p_exec.add_argument("--no-chain", action="store_true",
                        help="gather after every co-executed op "
                             "(no elision)")
    p_exec.add_argument("--no-warmup", action="store_true",
                        help="skip the untimed warmup pass (timings then "
                             "include tracing + compilation)")
    p_exec.add_argument("--per-op", action="store_true",
                        help="print one line per executed unit")
    p_exec.add_argument("--fused", action="store_true",
                        help="also run the fused walk (one program) and "
                             "compare it byte-for-byte against the per-node "
                             "walk")

    p_cal = sub.add_parser(
        "calibrate", help="record executions, fit a latency calibrator, "
                          "replan with corrected predictors, and show the "
                          "plan diff")
    _add_compile_args(p_cal)
    p_cal.add_argument("--runs", type=int, default=2,
                       help="timed executions to record before fitting")
    p_cal.add_argument("--store-dir", default="reports/measurements",
                       help="measurement store directory (append-only "
                            "JSONL per plan)")
    p_cal.add_argument("--save-calibration", default=None,
                       help="also write the fitted calibrator JSON here")
    p_cal.add_argument("--no-warmup", action="store_true",
                       help="skip the untimed warmup before the first "
                            "recorded run")
    p_cal.add_argument("--verbose", action="store_true",
                       help="print per-(kind, mode) correction lines")

    p_tune = sub.add_parser(
        "tune", help="autotune kernel tile configs for a network's ops and "
                     "store the winners in the on-disk TuneCache")
    _add_compile_args(p_tune)
    p_tune.add_argument("--reps", type=int, default=2,
                        help="timed repetitions per candidate (median)")

    p_verify = sub.add_parser(
        "verify", help="statically verify plan/portfolio/bench/tune "
                       "artifacts without importing jax or executing "
                       "anything")
    p_verify.add_argument("paths", nargs="*",
                          help="artifact JSON files (plan, CompiledNetwork "
                               "artifact, portfolio, bench report, tune "
                               "entry — dispatched by document shape)")
    p_verify.add_argument("--all-artifacts", action="store_true",
                          help="scan reports/plans, reports/tune and "
                               "reports/bench")
    p_verify.add_argument("-v", "--verbose", action="store_true",
                          help="also print info diagnostics (static "
                               "resource accounting)")

    p_lint = sub.add_parser(
        "lint", help="run the repo-contract linter (import-light, "
                     "registry completeness, no-silent-clamp)")
    p_lint.add_argument("--src", default=None,
                        help="package directory to lint (default: the "
                             "installed repro package)")

    # bench/serve exist here only so `python -m repro --help` lists them;
    # their real dispatch is the verbatim-forward intercept above
    sub.add_parser("bench",
                   help="run paper benchmark suites (forwards to "
                        "benchmarks.run; e.g. --only tab3)")
    sub.add_parser("serve",
                   help="serve requests: fixed-batch engine, or continuous "
                        "scheduler with a plan portfolio (--arrivals "
                        "poisson --rate ... --portfolio ...); forwards to "
                        "repro.launch.serve")

    args = ap.parse_args(argv)
    if args.cmd in ("execute", "calibrate", "tune"):
        place_compile_cache()               # before these import jax
    try:
        if args.cmd == "plan":
            return _cmd_plan(args)
        if args.cmd == "calibrate":
            return _cmd_calibrate(args)
        if args.cmd == "tune":
            return _cmd_tune(args)
        if args.cmd == "verify":
            return _cmd_verify(args)
        if args.cmd == "lint":
            return _cmd_lint(args)
        return _cmd_execute(args)
    except _UserInputError as e:
        # e.g. an unknown --network/--model: surface the registry listing
        # from repro.api instead of a traceback
        print(f"error: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    raise SystemExit(main())
