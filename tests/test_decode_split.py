"""Differential tests for typed-axis co-execution (head / kv-block /
ssm-state splits) and the registry's split validation.

Kernel- and executor-level split lowerings need >1 device, so they run in
a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
(same idiom as test_executor.py); validation, codec round-trip, and
explain() labels run in-process.
"""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.core.partitioner import PartitionDecision
from repro.core.types import AttnOp, SSMOp
from repro.graph.frontends import from_model
from repro.kernels import registry
from repro.runtime.plan import (CoexecPlan, PlanProvenance,
                                build_graph_schedule, segments_json)

ATTN = AttnOp(H=8, S=512, KV=4, hd=16)
SSM = SSMOp(T=64, H=8, hd=8, N=16)


# ------------------------------------------------ registry-level rejection

def test_head_split_must_respect_gqa_grouping():
    # H=8 / KV=4 -> GQA groups of 2 query heads; odd splits are illegal
    for bad in (1, 3, 5, 7):
        with pytest.raises(ValueError, match="granularity"):
            registry.validate_axis_split(ATTN, "head", bad)
    for ok in (0, 2, 4, 6, 8):
        registry.validate_axis_split(ATTN, "head", ok)


def test_head_split_needs_multiple_gqa_groups():
    mha = AttnOp(H=4, S=512, KV=1, hd=16)     # one KV head = one group
    with pytest.raises(ValueError, match="unavailable"):
        registry.validate_axis_split(mha, "head", 2)


def test_ssm_state_split_lane_alignment():
    misaligned = SSMOp(T=64, H=8, hd=12, N=16)      # 12 % 8 != 0
    with pytest.raises(ValueError, match="unavailable|hd"):
        registry.validate_axis_split(misaligned, "ssm-state", 4)
    registry.validate_axis_split(SSM, "ssm-state", 4)


def test_kv_block_split_gates_short_and_windowed_caches():
    short = AttnOp(H=8, S=128, KV=4, hd=16)         # S < KV_BLOCK_MIN_S
    with pytest.raises(ValueError, match="unavailable"):
        registry.validate_axis_split(short, "kv-block", 64)
    windowed = AttnOp(H=8, S=512, KV=4, hd=16, window=256)
    with pytest.raises(ValueError, match="unavailable"):
        registry.validate_axis_split(windowed, "kv-block", 256)
    registry.validate_axis_split(ATTN, "kv-block", 256)


def test_axis_split_out_of_range_rejected():
    with pytest.raises(ValueError, match="out of range"):
        registry.validate_axis_split(ATTN, "head", 9)
    with pytest.raises(ValueError, match="out of range"):
        registry.validate_axis_split(SSM, "ssm-state", -1)


def test_illegal_split_cannot_enter_a_schedule():
    g = from_model("tiny_decoder", cache_len=512)
    decisions, opaque = _typed_decisions(g)
    attn = next(n for n in g if n.kind == "attention")
    decisions[attn.id] = PartitionDecision(
        op=attn.op, c_cpu=attn.op.H - 1, c_gpu=1,   # breaks GQA grouping
        pred_cpu_us=1.0, pred_gpu_us=1.0, pred_total_us=2.0, axis="head")
    with pytest.raises(ValueError, match="granularity"):
        build_graph_schedule(g, decisions, opaque)


# --------------------------------------------- codec round-trip + explain

def _forced_plan(g, decisions, opaque=None):
    prov = PlanProvenance(
        device="moto2022", threads=3, mechanism="svm_poll", step=8, seed=1,
        network_fingerprint=g.fingerprint(), predictor_checksum="")
    return CoexecPlan(
        provenance=prov,
        schedule=build_graph_schedule(g, decisions, opaque or {}),
        graph_json=None if g.is_unit_chain() else g.to_json(),
        segments=segments_json(g, decisions))


def _typed_decisions(g):
    decisions, opaque = {}, {}
    for n in g:
        if n.kind in ("linear", "conv"):
            c = n.op.C_out
            decisions[n.id] = PartitionDecision(
                op=n.op, c_cpu=c // 4, c_gpu=c - c // 4,
                pred_cpu_us=1.0, pred_gpu_us=1.0, pred_total_us=2.0)
        elif n.kind == "attention":
            decisions[n.id] = PartitionDecision(
                op=n.op.with_mode("streaming"), c_cpu=n.op.H // 2,
                c_gpu=n.op.H // 2, pred_cpu_us=1.0, pred_gpu_us=1.0,
                pred_total_us=2.0, axis="head")
        elif n.kind == "ssm":
            decisions[n.id] = PartitionDecision(
                op=n.op.with_mode("recurrent"), c_cpu=n.op.H // 2,
                c_gpu=n.op.H // 2, pred_cpu_us=1.0, pred_gpu_us=1.0,
                pred_total_us=2.0, axis="ssm-state")
    return decisions, opaque


def test_axis_and_mode_roundtrip_through_plan_json():
    g = from_model("tiny_hybrid", blocks=2, cache_len=512)
    decisions, opaque = _typed_decisions(g)
    plan = _forced_plan(g, decisions, opaque)
    blob = plan.dumps()
    back = CoexecPlan.loads(blob)
    assert back.dumps() == blob                      # codec is bit-stable
    for nid, dec in decisions.items():
        got = back.decisions_by_node[nid]
        assert got.axis == dec.axis, nid
        assert getattr(got.op, "mode", None) == getattr(dec.op, "mode",
                                                        None), nid
        assert (got.c_cpu, got.c_gpu) == (dec.c_cpu, dec.c_gpu), nid


def test_channel_only_plans_serialize_without_axis_or_mode_keys():
    """Pre-axis byte compatibility: a pure conv/linear plan must not leak
    the new keys into its JSON (cached plans stay byte-identical)."""
    from repro.core.networks import NETWORKS
    from repro.graph.ir import from_units
    g = from_units(NETWORKS["resnet18"]())
    decisions, _ = _typed_decisions(g)
    blob = _forced_plan(g, decisions).dumps()
    assert '"axis"' not in blob
    assert '"mode"' not in blob


def test_explain_prints_axis_split_and_mode():
    import repro
    g = from_model("tiny_hybrid", blocks=2, cache_len=512)
    decisions, opaque = _typed_decisions(g)
    compiled = repro.CompiledNetwork(
        plan=_forced_plan(g, decisions, opaque),
        target=repro.Target(device="moto2022", threads=3))
    text = compiled.explain()
    assert "coexec head-split 2/4, mode=streaming" in text
    assert "coexec ssm-state-split 2/4, mode=recurrent" in text
    assert "unsplit kind" not in text


# --------------------------------- split vs oracle (8-device subprocess)

_SPLIT_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    import jax.numpy as jnp
    from repro.core.coexec import coexec_mesh, gather_stacked
    from repro.core.partitioner import PartitionDecision
    from repro.core.types import AttnOp, SSMOp
    from repro.graph.frontends import from_model
    from repro.kernels import registry
    from repro.runtime.executor import PlanExecutor
    from repro.runtime.plan import (CoexecPlan, PlanProvenance,
                                    build_graph_schedule, segments_json)

    mesh = coexec_mesh(jax.devices())
    rng = np.random.default_rng(7)

    def unit_io(op, dtype):
        ent = registry.entry_for(op)
        x = jnp.asarray(rng.standard_normal(ent.input_shape(op)), dtype)
        w = jnp.asarray(ent.init_weight(op, rng), dtype)
        return ent, x, w

    # ---- head-split decode attention: bit-identical fp32, close bf16
    attn = AttnOp(H=8, S=512, KV=4, hd=16)
    for dtype, check in ((jnp.float32, "exact"), (jnp.bfloat16, "close")):
        ent, x, w = unit_io(attn, dtype)
        ref = np.asarray(ent.lowering.oracle(x, w, attn))
        for n_fast in (2, 4, 6):
            low = registry.get_split_lowering("attention", "head")
            split, packed = low.pack(w, attn, n_fast, mesh)
            y = np.asarray(low.run(x, packed, split, mesh, attn, n_fast))
            if check == "exact":
                assert y.tobytes() == ref.tobytes(), ("head", n_fast)
            else:
                np.testing.assert_allclose(
                    y.astype(np.float32), ref.astype(np.float32),
                    rtol=3e-2, atol=3e-2)
    print("HEAD_SPLIT_OK")

    # ---- ssm-state split: bit-identical fp32, close bf16
    ssm = SSMOp(T=64, H=8, hd=8, N=16)
    for dtype, check in ((jnp.float32, "exact"), (jnp.bfloat16, "close")):
        ent, x, w = unit_io(ssm, dtype)
        ref = np.asarray(ent.lowering.oracle(x, w, ssm))
        for n_fast in (2, 4, 6):
            low = registry.get_split_lowering("ssm", "ssm-state")
            split, packed = low.pack(w, ssm, n_fast, mesh)
            y = np.asarray(low.run(x, packed, split, mesh, ssm, n_fast))
            if check == "exact":
                assert y.tobytes() == ref.tobytes(), ("ssm-state", n_fast)
            else:
                np.testing.assert_allclose(
                    y.astype(np.float32), ref.astype(np.float32),
                    rtol=3e-2, atol=3e-2)
    print("SSM_SPLIT_OK")

    # ---- kv-block split: tolerance-exact (log-sum-exp merge reassociates)
    ent, x, w = unit_io(attn, jnp.float32)
    ref = np.asarray(ent.lowering.oracle(x, w, attn))
    for n_fast in (128, 256, 384):
        low = registry.get_split_lowering("attention", "kv-block")
        split, packed = low.pack(w, attn, n_fast, mesh)
        y = np.asarray(low.run(x, packed, split, mesh, attn, n_fast))
        np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-6)
    print("KV_BLOCK_OK")

    # ---- executor level: planned typed-axis schedule, fused AND unfused.
    # Both walks run the same split programs, so they agree bit-for-bit.
    # Against the unsplit per-node oracle they agree to fp32 rounding: the
    # channel-split linear nodes are 1-row matmuls, which XLA:CPU reduces
    # in an order that depends on the output width, so a column slice of
    # x @ w is not bit-identical to those columns of the full product
    def forced(g):
        decisions, opaque = {}, {}
        for n in g:
            if n.kind in ("linear", "conv"):
                c = n.op.C_out
                decisions[n.id] = PartitionDecision(
                    op=n.op, c_cpu=c // 4, c_gpu=c - c // 4,
                    pred_cpu_us=1.0, pred_gpu_us=1.0, pred_total_us=2.0)
            elif n.kind == "attention":
                decisions[n.id] = PartitionDecision(
                    op=n.op, c_cpu=n.op.H // 2, c_gpu=n.op.H // 2,
                    pred_cpu_us=1.0, pred_gpu_us=1.0, pred_total_us=2.0,
                    axis="head")
            elif n.kind == "ssm":
                decisions[n.id] = PartitionDecision(
                    op=n.op, c_cpu=n.op.H // 2, c_gpu=n.op.H // 2,
                    pred_cpu_us=1.0, pred_gpu_us=1.0, pred_total_us=2.0,
                    axis="ssm-state")
        prov = PlanProvenance(
            device="moto2022", threads=3, mechanism="svm_poll", step=8,
            seed=1, network_fingerprint=g.fingerprint(),
            predictor_checksum="")
        return CoexecPlan(
            provenance=prov,
            schedule=build_graph_schedule(g, decisions, opaque),
            graph_json=None if g.is_unit_chain() else g.to_json(),
            segments=segments_json(g, decisions))

    for name, g in [("tiny_decoder", from_model("tiny_decoder",
                                                cache_len=512)),
                    ("tiny_ssm", from_model("tiny_ssm", tokens=64)),
                    ("tiny_hybrid", from_model("tiny_hybrid", blocks=2,
                                               cache_len=512))]:
        plan = forced(g)
        # typed-axis nodes are never inside fused segments: each is a
        # singleton segment of the walk, its shard_map program reading
        # and publishing materialized values only
        typed = {nid for nid, d in plan.decisions_by_node.items()
                 if d.axis not in ("channel", "none")}
        assert typed, name
        fused_nodes = {nid for seg in plan.segment_partition()
                       if seg.kind == "fused" for nid in seg.node_ids}
        assert not (typed & fused_nodes), (name, typed & fused_nodes)
        exe = PlanExecutor(plan, mesh=mesh)
        y_u, rep_u = exe.run(chain=True)
        y_f, rep_f = exe.run(fused=True)
        y_o = exe.run_oracle()
        assert np.asarray(y_f).tobytes() == np.asarray(y_u).tobytes(), name
        np.testing.assert_allclose(np.asarray(y_u), np.asarray(y_o),
                                   rtol=1e-5, atol=1e-5, err_msg=name)
        assert rep_u.count("coexec") > 0, name
        # the whole walk, typed-axis singletons included, syncs once
        assert rep_f.sync_points == 1 < rep_u.sync_points, name
        print(name, "exec ok:", len(typed), "typed-axis node(s)")
    print("DECODE_EXEC_OK")
""")


def test_typed_axis_splits_match_oracle_on_8_virtual_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SPLIT_PROG], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for marker in ("HEAD_SPLIT_OK", "SSM_SPLIT_OK", "KV_BLOCK_OK",
                   "DECODE_EXEC_OK"):
        assert marker in out.stdout, out.stdout[-2000:]
