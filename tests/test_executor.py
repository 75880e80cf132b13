"""Tests for the plan execution runtime (repro.runtime.executor).

In-process tests run on the real single-device CPU platform, where
`coexec_mesh` degrades to one group and the executor runs every unit
unsplit (exclusive) — equivalence with the oracle then validates the
registry lowering, pool lowering and shape adaptation.  True split
execution (2 groups), gather-elided chaining and mesh-degradation sweeps
need >1 device, so they run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (kept out of this
process on purpose — see conftest.py).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.networks import (NETWORKS, pool_out_edge, unit_input_shape,
                                 unit_output_shape)
from repro.core.predictor import sample_conv_ops, sample_linear_ops, \
    train_predictor
from repro.core.predictor.gbdt import GBDTParams
from repro.core.predictor.train import MuxPredictor
from repro.core.types import ConvOp, LinearOp
from repro.kernels import registry
from repro.runtime import (PlanCache, PlanExecutor, decision_to_spec,
                           plan_network_cached)

_FAST = GBDTParams(n_estimators=40, max_depth=6, learning_rate=0.2)


@pytest.fixture(scope="module")
def mux_predictors():
    lt = sample_linear_ops(250, seed=1)
    ct = sample_conv_ops(250, seed=1)
    dev = "moto2022"
    gp = MuxPredictor(
        train_predictor(lt, dev, "gpu", whitebox=True, params=_FAST),
        train_predictor(ct, dev, "gpu", whitebox=True, params=_FAST))
    cp = MuxPredictor(
        train_predictor(lt, dev, "cpu3", whitebox=False, params=_FAST),
        train_predictor(ct, dev, "cpu3", whitebox=False, params=_FAST))
    return cp, gp


def _small_units():
    return [("conv", ConvOp(28, 28, 32, 64, 3, 1)),
            ("conv", ConvOp(28, 28, 64, 64, 3, 2)),
            ("pool", 4 * 7 * 7 * 64),
            ("conv", ConvOp(7, 7, 64, 96, 3, 1)),
            ("pool", 4 * 96),
            ("linear", LinearOp(1, 96, 128))]


def _plan(units, mux_predictors, tmp_path):
    cp, gp = mux_predictors
    return plan_network_cached(units, cp, gp, threads=3,
                               cache=PlanCache(tmp_path))


# ------------------------------------------------------------ registry

def test_registry_is_the_shared_dispatch_table():
    lin = LinearOp(4, 32, 64)
    conv = ConvOp(8, 8, 16, 24, 3, 2)
    assert registry.op_kind(lin) == "linear"
    assert registry.op_kind(conv) == "conv"
    assert registry.get("linear").input_shape(lin) == (4, 32)
    assert registry.get("linear").weight_shape(lin) == (32, 64)
    assert registry.get("conv").output_shape(conv) == (4, 4, 24)
    # the predictors featurize through the same table
    from repro.core.predictor.features import blackbox_features
    feats = blackbox_features([lin])
    assert feats.shape == (1, len(registry.get("linear").base_features(lin)))
    np.testing.assert_allclose(feats[0],
                               registry.get("linear").base_features(lin))
    # lowerings resolve lazily and compute
    low = registry.get_lowering("linear")
    x = jnp.ones((4, 32)); w = jnp.ones((32, 64))
    np.testing.assert_allclose(np.asarray(low.oracle(x, w, lin)),
                               np.asarray(x @ w))
    # decoder-block kinds are first-class registry entries (graph IR era),
    # but never splittable; unknown kinds still raise
    assert not registry.get("attention").splittable
    assert not registry.get("ssm").splittable
    with pytest.raises(KeyError):
        registry.get("softmax")


def test_conv_lowering_crops_to_declared_shape():
    # SAME stride-2 conv at odd H gives ceil(H/S); ConvOp declares floor
    op = ConvOp(35, 35, 8, 16, 3, 2)
    low = registry.get_lowering("conv")
    x = jnp.ones((1, 35, 35, 8)); w = jnp.ones((3, 3, 8, 16))
    assert low.oracle(x, w, op).shape == (1,) + registry.get(
        "conv").output_shape(op)


def test_networks_expose_shapes():
    assert unit_input_shape(("conv", ConvOp(28, 28, 32, 64, 3, 2))) == \
        (28, 28, 32)
    assert unit_input_shape(("pool", 4 * 7 * 7 * 64)) is None
    assert unit_output_shape(("conv", ConvOp(28, 28, 32, 64, 3, 2))) == \
        (14, 14, 64)
    assert unit_output_shape(("linear", LinearOp(2, 8, 10))) == (2, 10)
    assert unit_output_shape(("pool", 4 * 14 * 14 * 64), c_prev=64) == \
        (14, 14, 64)
    assert pool_out_edge(4 * 512, 512) == 1          # global pooling
    assert pool_out_edge(4 * 56 * 56 * 64, 64) == 56


# ----------------------------------------------------------- exec specs

def test_exec_specs_mirror_schedule(mux_predictors, tmp_path):
    plan = _plan(_small_units(), mux_predictors, tmp_path)
    specs = plan.exec_specs()
    assert [s.unit for s in specs] == [k for k, _ in _small_units()]
    for spec, dec in zip([s for s in specs if s.unit != "pool"],
                         plan.decisions):
        assert spec == decision_to_spec(dec)
        assert (spec.c_fast, spec.c_slow) == (dec.c_gpu, dec.c_cpu)
        assert spec.exclusive == dec.exclusive
    pool = [s for s in specs if s.unit == "pool"]
    assert [p.pool_bytes for p in pool] == [4 * 7 * 7 * 64, 4 * 96]
    assert all(not p.coexec for p in pool)


def test_executor_rejects_mismatched_units(mux_predictors, tmp_path):
    plan = _plan(_small_units(), mux_predictors, tmp_path)
    with pytest.raises(ValueError, match="fingerprint"):
        PlanExecutor(plan, units=_small_units()[:-1])


# ----------------------------------- oracle equivalence (degraded mesh)

def test_degraded_mesh_runs_exclusively(mux_predictors, tmp_path):
    """Satellite: on this single-device platform the mesh degrades to one
    group and every planned co-execution runs as exclusive execution."""
    plan = _plan(_small_units(), mux_predictors, tmp_path)
    exe = PlanExecutor(plan)
    assert not exe.split_capable           # 1 CPU device -> 1 group
    y, rep = exe.run()
    assert rep.count("coexec") == 0
    assert rep.count("exclusive") == 4 and rep.count("pool") == 2
    assert rep.reshard_points == 0 and rep.elided == 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(exe.run_oracle()),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("network,n_units", [("resnet18", 5), ("vgg16", 4)])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, 5e-2)])
def test_executed_slice_matches_oracle_across_dtypes(
        mux_predictors, tmp_path, network, n_units, dtype, tol):
    units = NETWORKS[network]()[:n_units]
    plan = _plan(units, mux_predictors, tmp_path)
    exe = PlanExecutor(plan, dtype=dtype)
    y, rep = exe.run()
    assert len(rep.timings) == n_units
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(exe.run_oracle(), np.float32),
                               rtol=tol, atol=tol)


def test_executed_resnet18_plan_end_to_end(mux_predictors, tmp_path):
    """Acceptance: a cached resnet18 CoexecPlan executes end to end and
    matches the unsplit oracle."""
    units = NETWORKS["resnet18"]()
    plan = _plan(units, mux_predictors, tmp_path)
    # warm cache: the executor consumes the stored artifact
    plan = _plan(units, mux_predictors, tmp_path)
    exe = PlanExecutor(plan)
    y, rep = exe.run()
    assert y.shape == (1, 1000)
    assert len(rep.timings) == len(units)
    np.testing.assert_allclose(np.asarray(y), np.asarray(exe.run_oracle()),
                               rtol=2e-4, atol=2e-4)
    summary = rep.fidelity_summary()
    assert summary.startswith("fidelity:") and "reshard" in summary


def test_execution_report_serializes(mux_predictors, tmp_path):
    plan = _plan(_small_units(), mux_predictors, tmp_path)
    exe = PlanExecutor(plan)
    _, rep = exe.run()
    doc = json.loads(json.dumps(rep.to_json()))
    assert doc["network_fingerprint"] == plan.provenance.network_fingerprint
    assert len(doc["timings"]) == len(_small_units())
    assert {"index", "unit", "mode", "wall_us", "pred_us"} <= \
        set(doc["timings"][0])


def test_serving_engine_executes_plan(mux_predictors, tmp_path):
    from repro.serving.engine import ServingEngine

    plan = _plan(_small_units(), mux_predictors, tmp_path)

    class _Model:                      # never traced: jit is lazy
        @staticmethod
        def prefill(params, toks, cache):
            raise NotImplementedError

        @staticmethod
        def decode_step(params, tok, cache, pos):
            raise NotImplementedError

    eng = ServingEngine(cfg=None, model=_Model, params={}, coexec_plan=plan)
    y, rep = eng.execute_plan()
    assert eng.last_execution_report is rep
    assert rep.fidelity_summary().startswith("fidelity:")
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(eng.plan_executor.run_oracle()),
        rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError):
        ServingEngine(cfg=None, model=_Model, params={}).execute_plan()


# ------------------------------------ true split execution (subprocess)

_SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.core.coexec import coexec_mesh, mesh_groups
    from repro.core.networks import NETWORKS
    from repro.core.partitioner import PartitionDecision
    from repro.core.types import ConvOp, LinearOp
    from repro.runtime.executor import PlanExecutor
    from repro.runtime.plan import (CoexecPlan, PlanProvenance,
                                    build_schedule, network_fingerprint)

    devs = jax.devices()
    assert len(devs) == 8
    # satellite: coexec_mesh degrades on <2 and odd device counts
    for k, want in [(1, 1), (2, 2), (3, 2), (5, 2), (8, 2)]:
        assert mesh_groups(coexec_mesh(devs[:k])) == want, k

    def forced_plan(units, splits):
        decs = []
        i = 0
        for kind, payload in units:
            if kind == "pool":
                continue
            c_fast, c_slow = splits[i]
            decs.append(PartitionDecision(
                op=payload, c_cpu=c_slow, c_gpu=c_fast,
                pred_cpu_us=1.0, pred_gpu_us=1.0, pred_total_us=2.0))
            i += 1
        prov = PlanProvenance(
            device="moto2022", threads=3, mechanism="svm_poll", step=8,
            seed=1, network_fingerprint=network_fingerprint(units),
            predictor_checksum="")
        return CoexecPlan(provenance=prov,
                          schedule=build_schedule(units, decs))

    mesh = coexec_mesh(devs)

    def check(units, splits, tag):
        exe = PlanExecutor(forced_plan(units, splits), mesh=mesh)
        assert exe.split_capable
        y_chain, rep_chain = exe.run(chain=True)
        y_gather, rep_gather = exe.run(chain=False)
        y_oracle = exe.run_oracle()
        np.testing.assert_allclose(np.asarray(y_chain),
                                   np.asarray(y_oracle),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(y_gather),
                                   np.asarray(y_oracle),
                                   rtol=2e-5, atol=2e-5)
        # elision must not change values, only the number of sync points
        np.testing.assert_allclose(np.asarray(y_chain),
                                   np.asarray(y_gather),
                                   rtol=1e-6, atol=1e-6)
        assert rep_chain.reshard_points < rep_gather.reshard_points, tag
        assert rep_chain.elided > 0 and rep_gather.elided == 0, tag
        assert rep_chain.count("coexec") == rep_gather.count("coexec") > 0
        print(tag, "reshard", rep_chain.reshard_points, "vs",
              rep_gather.reshard_points, "elided", rep_chain.elided)

    units = [("conv", ConvOp(16, 16, 8, 32, 3, 1)),
             ("conv", ConvOp(16, 16, 32, 48, 3, 1)),
             ("conv", ConvOp(16, 16, 48, 48, 3, 2)),
             ("pool", 4 * 4 * 4 * 48),
             ("conv", ConvOp(4, 4, 48, 64, 3, 1)),
             ("linear", LinearOp(1, 4 * 4 * 64, 100)),
             ("linear", LinearOp(1, 100, 40))]
    check(units, [(24, 8), (32, 16), (16, 32), (40, 24), (60, 40),
                  (30, 10)], "synthetic")

    # a real resnet18 tail slice (stage-4 convs + global pool + classifier),
    # mixed with exclusive ops
    tail = NETWORKS["resnet18"]()[-6:]
    ops = [p for k, p in tail if k != "pool"]
    splits = []
    for j, op in enumerate(ops):
        if j == 1:
            splits.append((op.C_out, 0))         # exclusive boundary
        else:
            splits.append((op.C_out - op.C_out // 4, op.C_out // 4))
    check(tail, splits, "resnet18-tail")
    print("SPLIT_EXEC_OK")

    # Pallas kernels of exclusive nodes on the split mesh run replicated
    # under shard_map (the TPU partitions no kernel automatically)
    splits[-1] = (ops[-1].C_out, 0)              # the classifier, exclusive
    plan = forced_plan(tail, splits)
    ref = PlanExecutor(plan, mesh=mesh).run_oracle()
    exe = PlanExecutor(plan, mesh=mesh, use_pallas=True, interpret=True)
    y_u, rep_u = exe.run()
    y_f, rep_f = exe.run(fused=True)
    assert rep_u.count("exclusive") > 0 and rep_u.count("coexec") > 0
    assert rep_f.sync_points == 1 < rep_u.sync_points
    for y in (y_u, y_f):
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    print("PALLAS_ON_SPLIT_MESH_OK")
""")


def test_split_execution_and_gather_elision_on_8_virtual_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_PROG], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SPLIT_EXEC_OK" in out.stdout
    assert "PALLAS_ON_SPLIT_MESH_OK" in out.stdout
