#!/usr/bin/env python3
"""Smoke run of the system's main path on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # co-execution across two chip groups

One process drives everything, so it holds the chip for the whole run.
Phases:

* plan -> execute: `repro.compile` plans resnet18 and a two-block
  codeqwen1.5-7b decoder graph (published widths, 4096-entry KV cache) for
  the phone target, and the plan executor runs each with the Pallas kernels
  (`use_pallas=True`) on the unfused and the fused walk.  Both outputs are
  compared with the jnp oracle walk in fp32, and the script prints how many
  `tpu_custom_call`s the programs of the executed nodes hold.
* serve: the continuous scheduler that `repro serve --arrivals poisson`
  builds, on codeqwen1.5-7b at its published widths cut to 8 layers
  (bf16), serves a dozen Poisson requests on the wall clock.  One decode
  step is then checked against an fp32 run of the same step.

With `--four-chips` only plan -> execute runs, on a two-group mesh
(`Target(mesh="split")`): at least one node must execute split across the
groups, and both walks must match the unsplit oracle.

The last line of standard output is, on success, exactly
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
The script exits non-zero and prints no such line when JAX finds no TPU,
when it runs outside a checkout of the repository, or when any phase fails.

`--rehearse` runs every phase at tiny sizes with the Pallas kernels in
TPU interpret mode, for a host without a chip (`JAX_PLATFORMS=cpu`, and
`XLA_FLAGS=--xla_force_host_platform_device_count=4` for `--four-chips`),
and then exits non-zero: a rehearsal never reports a result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: plan -> execute: max |walk - oracle| / max |oracle|, both run in fp32
#: (matmul precision "highest", inside the Pallas kernels too)
EXEC_TOL = 1e-4
#: serve: max |bf16 step logit - fp32 step logit| / max |fp32 step logit|
LOGIT_TOL = 0.1

SEED = 0                    # random weights, traffic and tokens
SERVE_LAYERS = 8
SERVE_BATCH = 8
SERVE_LEN = 512
SERVE_REQUESTS = 12
SERVE_RATE = 100.0          # requests/s, Poisson
LOGIT_PREFIX = 16           # decode steps that fill the cache before the check


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


def _rel_err(y, ref) -> float:
    import numpy as np
    y = np.asarray(y, np.float32)
    ref = np.asarray(ref, np.float32)
    _check(y.shape == ref.shape, f"shape {y.shape} != oracle {ref.shape}")
    _check(bool(np.isfinite(y).all()), "non-finite output")
    return float(np.abs(y - ref).max() / max(np.abs(ref).max(), 1e-30))


def _kernel_count(exe, report) -> int:
    """`tpu_custom_call`s in the programs of the nodes the walk ran
    exclusively: each runs its registry Pallas lowering, on its own
    (unfused walk) or inside the walk's one program (fused walk)."""
    import jax

    from repro.kernels import registry
    modes = {t.node_id: t.mode for t in report.timings}
    n = 0
    for node, spec, w in zip(exe.graph, exe.specs, exe.params):
        if modes.get(node.id) != "exclusive":
            continue
        shape = tuple(registry.get(spec.unit).input_shape(spec.op))
        if spec.unit == "conv":
            shape = (1,) + shape
        low = registry.get_lowering(spec.unit)
        fn = jax.jit(lambda v, w, low=low, spec=spec:
                     low.pallas(v, w, spec.op, tile=spec.tile))
        text = fn.lower(jax.ShapeDtypeStruct(shape, exe.dtype), w).as_text()
        n += text.count("tpu_custom_call")
    return n


def plan_execute(args) -> None:
    import jax

    import repro
    from repro.graph import from_model

    target = repro.Target(device="moto2022", threads=3,
                          mesh="split" if args.four_chips else "auto")
    decoder = from_model("tiny_decoder" if args.rehearse else "codeqwen15_7b",
                         blocks=2, cache_len=512 if args.rehearse else 4096)
    n_split = 0
    for name, network in (("resnet18", "resnet18"),
                          ("codeqwen15_7b x2 blocks", decoder)):
        t0 = time.perf_counter()
        compiled = repro.compile(network, target, samples=120, estimators=25)
        t_plan = time.perf_counter() - t0
        exe = compiled.executor(use_pallas=True)
        x = exe.input_template()
        with jax.default_matmul_precision("highest"):
            ref = compiled.executor(use_pallas=False).run_oracle(x)
            t0 = time.perf_counter()
            y_u, rep_u = exe.run(x)
            t_first = time.perf_counter() - t0
            y_f, rep_f = exe.run(x, fused=True)
        err_u, err_f = _rel_err(y_u, ref), _rel_err(y_f, ref)
        split = sum(t.mode == "coexec" for t in rep_u.timings)
        kernels = _kernel_count(exe, rep_u)
        print(f"[plan->execute] {name}: {len(rep_u.timings)} nodes, "
              f"{split} split across groups, mesh groups "
              f"{2 if exe.split_capable else 1}; plan {t_plan:.1f}s, first "
              f"unfused run (compiles) {t_first:.1f}s")
        print(f"[plan->execute] {name}: max rel err vs oracle: unfused "
              f"{err_u:.3e}, fused {err_f:.3e} (bound {EXEC_TOL:g}); "
              f"{kernels} tpu_custom_call in the executed exclusive nodes' "
              f"programs; {rep_f.segments} segments in the fused walk")
        _check(err_u <= EXEC_TOL and err_f <= EXEC_TOL,
               f"{name}: walk differs from the oracle beyond {EXEC_TOL}")
        if args.four_chips:
            _check(exe.split_capable, f"{name}: mesh is not two groups")
        if not args.rehearse:
            _check(exe.split_capable or kernels >= 1,
                   f"{name}: no Pallas kernel in the executed programs")
        n_split += split
    if args.four_chips:
        # the phone-target planner splits resnet18's convolutions; it runs
        # the decode-width projections on one side
        _check(n_split >= 1, "no node executed split across the groups")


def serve(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import build_model, get_config
    from repro.serving import (ContinuousScheduler, SchedulerConfig,
                               poisson_requests)

    cfg = get_config("codeqwen15_7b")
    cfg = (cfg.reduced() if args.rehearse
           else dataclasses.replace(cfg, n_layers=SERVE_LAYERS))
    model = build_model(cfg)
    print(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model},"
          f" heads {cfg.n_heads}/{cfg.n_kv_heads} kv, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}, {cfg.param_count() / 1e9:.2f} B params {cfg.dtype}")
    t0 = time.perf_counter()
    params = jax.jit(model.init)(jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    t_init = time.perf_counter() - t0

    sched = ContinuousScheduler(cfg, model, params, config=SchedulerConfig(
        max_batch=SERVE_BATCH, max_len=SERVE_LEN, clock="wall",
        seed=SEED))
    # one short request compiles the decode step before the timed traffic
    t0 = time.perf_counter()
    sched.run(poisson_requests(1, rate=SERVE_RATE, vocab_size=cfg.vocab_size,
                               prompt_lens=(2,), max_new=(2,),
                               seed=SEED))
    t_compile = time.perf_counter() - t0
    reqs = poisson_requests(SERVE_REQUESTS, rate=SERVE_RATE,
                            vocab_size=cfg.vocab_size, seed=SEED)
    report = sched.run(reqs)
    want = sum(r.max_new_tokens for r in reqs)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"[serve] completions {len(report.completions)}/{len(reqs)}, "
          f"tokens {report.total_tokens}/{want}, {report.steps} steps")
    print(f"[serve] compile s: init {t_init:.2f}, first decode step "
          f"{t_compile:.2f}")
    print(f"[serve] steady wall tokens/s (information, not a metric): "
          f"{report.tokens_per_s:.1f} over {report.duration_s:.3f}s")
    print(f"[serve] peak_bytes_in_use: "
          f"{'not reported' if peak is None else peak}")
    _check(len(report.completions) == len(reqs), "not every request completed")
    _check(report.total_tokens == want, "token count differs from requested")
    del sched

    # one decode step, bf16 as served, against fp32 under "highest"
    rng = np.random.default_rng(SEED)
    toks = rng.integers(1, cfg.vocab_size, (LOGIT_PREFIX + 1, SERVE_BATCH, 1))
    step = jax.jit(model.decode_step)
    cache = model.init_cache(SERVE_BATCH, SERVE_LEN)
    for t in range(LOGIT_PREFIX):
        _, cache = step(params, jnp.asarray(toks[t], jnp.int32), cache,
                        jnp.full((SERVE_BATCH,), t, jnp.int32))
    tok = jnp.asarray(toks[LOGIT_PREFIX], jnp.int32)
    pos = jnp.full((SERVE_BATCH,), LOGIT_PREFIX, jnp.int32)
    lo16 = np.asarray(step(params, tok, cache, pos)[0], np.float32)

    def upcast(tree):
        # one leaf at a time, freeing each bf16 source, so the fp32 copy
        # never sits beside the whole bf16 tree
        leaves, treedef = jax.tree.flatten(tree)
        for i, a in enumerate(leaves):
            leaves[i] = a.astype(jnp.float32)
            a.delete()
        return jax.tree.unflatten(treedef, leaves)

    params32, cache32 = upcast(params), upcast(cache)
    del params, cache
    model32 = build_model(dataclasses.replace(cfg, dtype="float32"))
    with jax.default_matmul_precision("highest"):
        lo32 = np.asarray(jax.jit(model32.decode_step, donate_argnums=(2,))(
            params32, tok, cache32, pos)[0])
    diff = float(np.abs(lo16 - lo32).max())
    scale = float(np.abs(lo32).max())
    print(f"[serve] one decode step at position {LOGIT_PREFIX}: max |logit "
          f"bf16 - fp32| {diff:.4f}, max |logit fp32| {scale:.4f}, ratio "
          f"{diff / scale:.4f} (bound {LOGIT_TOL:g})")
    _check(bool(np.isfinite(lo16).all()), "non-finite bf16 logits")
    _check(diff <= LOGIT_TOL * scale, "bf16 step differs from fp32 beyond "
           "the bound")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only plan -> execute, split across two chip "
                         "groups (needs 4 chips)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, Pallas in TPU interpret mode, no chip "
                         "needed; always exits non-zero")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: {ROOT} is not a checkout of the repository "
              f"(no src/repro)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import place_compile_cache
    cache_dir = place_compile_cache()           # before jax is imported

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU found (JAX's devices are "
              f"{dev.platform}); nothing was run", file=sys.stderr)
        return 1
    print(f"[setup] jax {jax.__version__}, {len(devices)} x {dev.platform} "
          f"{dev.device_kind}; compile cache {cache_dir}")

    phases = [("plan->execute", plan_execute)]
    if not args.four_chips:
        phases.append(("serve", serve))
    failed = []
    t_all = time.perf_counter()
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            if args.rehearse:
                from jax.experimental.pallas import tpu as pltpu
                with pltpu.force_tpu_interpret_mode():
                    fn(args)
            else:
                fn(args)
        except Exception:                   # reported, and the run fails
            traceback.print_exc()
            failed.append(name)
            print(f"[{name}] FAILED after {time.perf_counter() - t0:.1f}s")
        else:
            print(f"[{name}] ok in {time.perf_counter() - t0:.1f}s")
    print(f"[done] wall {time.perf_counter() - t_all:.1f}s")

    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    if args.rehearse:
        print(f"chip_smoke: rehearsal on {dev.platform} finished; no TPU, "
              f"no result", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
