"""End-to-end network co-execution planning (paper Table 3) + the
TPU-native channel-split demo.

Part 1: compile ResNet-18 for GPU + 3 CPU threads on the Moto 2022 model
        through the `repro.compile` facade, then EXECUTE the compiled
        network (on this single-device host the mesh degrades to one
        group and ops run unsplit; the fidelity summary still pairs
        executed wall time with the plan's predictions per op).
Part 2: run an actual uneven channel-split matmul across two device groups
        via shard_map, in this process.  It needs at least two devices (a
        multi-chip host, or virtual CPU devices as in the second command)
        and says why it skips when there is one.

    PYTHONPATH=src python examples/coexec_e2e.py
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/coexec_e2e.py

Any failure exits non-zero.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

import repro                                               # noqa: E402
from repro.core.predictor import (sample_conv_ops,         # noqa: E402
                                  sample_linear_ops, train_predictor)
from repro.core.predictor.train import MuxPredictor        # noqa: E402


def part1():
    dev, threads = "moto2022", 3
    print("== Part 1: ResNet-18 end-to-end partition plan ==")
    lt = sample_linear_ops(1500, seed=1)
    ct = sample_conv_ops(2000, seed=1)
    gp = MuxPredictor(train_predictor(lt, dev, "gpu", whitebox=True),
                      train_predictor(ct, dev, "gpu", whitebox=True))
    cp = MuxPredictor(
        train_predictor(lt, dev, f"cpu{threads}", whitebox=False),
        train_predictor(ct, dev, f"cpu{threads}", whitebox=False))
    compiled = repro.compile("resnet18",
                             repro.Target(device=dev, threads=threads),
                             predictors=(cp, gp),
                             cache=ROOT / "reports" / "plans")
    r = compiled.report()
    print(f"plan cache {'HIT' if compiled.from_cache else 'MISS (compiled)'}"
          f" (key {compiled.key})")
    print(f"baseline (GPU only): {r.baseline_us/1e3:.1f} ms")
    print(f"co-exec individual:  {r.individual_us/1e3:.1f} ms "
          f"({r.individual_speedup:.2f}x)")
    print(f"co-exec end-to-end:  {r.end_to_end_us/1e3:.1f} ms "
          f"({r.end_to_end_speedup:.2f}x; paper: 1.11x on Moto 2022)")
    co = sum(1 for d in r.decisions if not d.exclusive)
    print(f"{co}/{len(r.decisions)} ops co-executed")

    y = compiled.run()
    print(f"executed plan -> output {tuple(y.shape)}")
    print(compiled.last_report.fidelity_summary())


def part2():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.coexec import (coexec_matmul, coexec_mesh, mesh_groups,
                                   pack_weights, throughput_split)
    n = len(jax.devices())
    print(f"\n== Part 2: shard_map channel-split matmul ({n} devices) ==")
    mesh = coexec_mesh()
    if mesh_groups(mesh) < 2:
        print("skipped: one device gives a one-group mesh, with nothing to "
              "split against (see the module docstring for a multi-device "
              "run)")
        return
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(50, 768)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(768, 3072)), jnp.float32)
    # group 0 is 4x faster than group 1 -> it takes ~80% of the channels
    plan = throughput_split(3072, fast_share=0.8)
    y = coexec_matmul(x, pack_weights(w, plan), plan, mesh)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                               rtol=2e-4, atol=2e-4)
    print(f"channel split: {plan.c_fast} fast-group / {plan.c_slow} "
          f"slow-group channels (padded to {plan.c_pad}) -- results match")


if __name__ == "__main__":
    part1()
    part2()
