"""Serving launcher: batched requests through the ServingEngine, or
Poisson traffic through the continuous scheduler.

Fixed-batch mode (the original engine):

    PYTHONPATH=src python -m repro serve --arch gemma3_12b --reduced \
        --requests 8 --max-new 12

Continuous-batching mode (`--arrivals poisson` selects the
`repro.serving.ContinuousScheduler`): synthetic Poisson traffic is
admitted per-step against a bucketed plan portfolio —

    PYTHONPATH=src python -m repro serve --arch codeqwen15_7b --reduced \
        --arrivals poisson --rate 200 --requests 50 \
        --portfolio reports/portfolio.json

`--portfolio <path>` loads the portfolio artifact if it exists and
otherwise compiles one there (`repro.compile_portfolio`; a loaded
artifact serves but cannot replan — it carries no predictors).
`--throttle-at`/`--throttle-scale` simulate a mid-run thermal throttle,
exercising the drift-triggered in-place replanning path.

`--compiled <artifact>` additionally ships a `repro.CompiledNetwork`
artifact (saved by `python -m repro plan --save ...`) with the engine and
executes it once after serving, printing the per-op fidelity summary.

`python -m repro.launch.serve` still works but is deprecated in favor of
the unified `python -m repro serve`.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path
from typing import Optional, Sequence

import jax
import numpy as np

from repro.models import ARCH_IDS, build_model, get_config
from repro.serving import Request, ServingEngine


def _parse_buckets(text: str):
    """"1x64,4x64,4x256" -> ((1, 64), (4, 64), (4, 256))."""
    out = []
    for part in text.split(","):
        b, _, s = part.strip().partition("x")
        out.append((int(b), int(s)))
    return tuple(out)


def _load_or_compile_portfolio(args, cfg):
    import repro

    path = Path(args.portfolio)
    if path.exists():
        pf = repro.PlanPortfolio.load(path)
        note = "" if pf.can_replan() else \
            " (loaded artifact: serves, cannot replan)"
        print(f"portfolio {path}: {pf}{note}")
        return pf
    buckets = _parse_buckets(args.buckets)
    print(f"compiling portfolio for {cfg.name} on {args.device} "
          f"(buckets {args.buckets}) ...")
    pf = repro.compile_portfolio(cfg, repro.Target(device=args.device),
                                 buckets=buckets, cache=args.cache_dir,
                                 samples=args.samples,
                                 estimators=args.estimators)
    pf.save(path)
    print(f"  wrote {path}: {pf}")
    return pf


def _serve_scheduler(args, cfg, model, params) -> int:
    from repro.serving import (ContinuousScheduler, SchedulerConfig,
                               ThrottleSim, poisson_requests)

    portfolio = None
    if args.portfolio:
        portfolio = _load_or_compile_portfolio(args, cfg)
    throttle = None
    if args.throttle_at is not None:
        throttle = ThrottleSim(at_s=args.throttle_at,
                               scale=args.throttle_scale)
        print(f"simulating throttle: x{args.throttle_scale} wall time "
              f"from t={args.throttle_at}s")
    store = args.store_dir if portfolio is not None else None
    sched = ContinuousScheduler(
        cfg, model, params, portfolio=portfolio, measurement_store=store,
        throttle=throttle, plan_cache=args.cache_dir,
        config=SchedulerConfig(max_batch=args.max_batch,
                               max_len=args.max_len,
                               fidelity_every=args.fidelity_every))
    reqs = poisson_requests(args.requests, rate=args.rate,
                            vocab_size=cfg.vocab_size,
                            max_new=(args.max_new // 2 or 1, args.max_new),
                            seed=args.seed)
    t0 = time.time()
    report = sched.run(reqs)
    dt = time.time() - t0
    for c in report.completions[:4]:
        print(f"req {c.rid}: {c.tokens}")
    print(report.summary())
    print(f"(wall {dt:.1f}s, {report.total_tokens / dt:.1f} tok/s on "
          f"{_device_label()})")
    return 0


def _device_label() -> str:
    dev = jax.devices()[0]
    return f"{len(jax.devices())} x {dev.platform} {dev.device_kind}"


def serve_main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro serve")
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family widths for CPU smoke runs")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut depth only, to this many layers (published "
                         "widths kept)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--compiled", default=None,
                    help="CompiledNetwork artifact to ship with the engine "
                         "(executed once after serving; see `python -m "
                         "repro plan --save`)")
    ap.add_argument("--arrivals", default="batch",
                    choices=["batch", "poisson"],
                    help="batch = fixed-batch ServingEngine; poisson = "
                         "continuous scheduler over Poisson traffic")
    ap.add_argument("--rate", type=float, default=100.0,
                    help="Poisson arrival rate, requests/s (scheduler "
                         "virtual clock)")
    ap.add_argument("--portfolio", default=None,
                    help="plan-portfolio artifact path: loaded if present, "
                         "else compiled there (scheduler mode)")
    ap.add_argument("--buckets", default="1x64,4x64",
                    help="portfolio (batch x seq) buckets, e.g. "
                         "'1x64,4x64,4x256'")
    ap.add_argument("--device", default="moto2022",
                    help="simulated target device for portfolio compilation")
    ap.add_argument("--cache-dir", default="reports/plans",
                    help="plan cache directory (portfolio compilation and "
                         "in-place replans)")
    ap.add_argument("--store-dir", default="reports/measurements",
                    help="measurement store for per-bucket fidelity records")
    ap.add_argument("--max-len", type=int, default=128,
                    help="per-slot cache length (scheduler mode)")
    ap.add_argument("--fidelity-every", type=int, default=16,
                    help="plan-execution cadence in scheduler steps")
    ap.add_argument("--throttle-at", type=float, default=None,
                    help="simulate a thermal throttle from this time (s) on "
                         "the scheduler clock")
    ap.add_argument("--throttle-scale", type=float, default=1.8,
                    help="wall-time multiplier of the simulated throttle")
    ap.add_argument("--samples", type=int, default=400,
                    help="predictor training ops (portfolio compilation)")
    ap.add_argument("--estimators", type=int, default=60,
                    help="GBDT trees per predictor (portfolio compilation)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))

    if args.arrivals == "poisson":
        return _serve_scheduler(args, cfg, model, params)

    compiled = None
    if args.compiled:
        from repro.api import CompiledNetwork
        compiled = CompiledNetwork.load(args.compiled)
        print(f"shipping compiled plan {compiled.key} "
              f"(device {compiled.target.device})")

    rng = np.random.default_rng(0)
    reqs = []
    for rid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size,
                              size=rng.integers(4, 17)).astype(np.int32)
        frames = None
        if cfg.is_encoder_decoder:
            frames = rng.normal(size=(cfg.encoder_seq, cfg.d_model)
                                ).astype(np.float32) * 0.02
        reqs.append(Request(rid=rid, prompt=prompt,
                            max_new_tokens=args.max_new,
                            temperature=args.temperature, frames=frames))

    engine = ServingEngine(cfg, model, params, max_batch=args.max_batch,
                           max_len=64 + args.max_new, compiled=compiled)
    t0 = time.time()
    completions = engine.run(reqs)
    dt = time.time() - t0
    total_tokens = sum(len(c.tokens) for c in completions)
    for c in completions[:4]:
        print(f"req {c.rid}: {c.tokens}")
    print(f"{len(completions)} completions, {total_tokens} tokens "
          f"in {dt:.1f}s ({total_tokens/dt:.1f} tok/s on {_device_label()})")

    if compiled is not None:
        _, report = engine.execute_plan()
        print(report.fidelity_summary())
    return 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    """Deprecated CLI shim: forwards to `python -m repro serve`."""
    from repro.api import _warn_once
    _warn_once("python -m repro.launch.serve", "python -m repro serve")
    return serve_main(argv)


if __name__ == "__main__":
    raise SystemExit(main())
