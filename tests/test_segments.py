"""Differential test harness for the segment compiler.

The fused walk (`PlanExecutor.run(fused=True)` lowering the plan's whole
segment partition into one jitted program, see `repro.runtime.segments`)
is locked against two references: the per-node walk (`fused=False`) and
the unsplit oracle (`run_oracle`) — outputs must agree bit-for-bit, and
the partition (`Graph.segments`) must cut exactly where the unfused walk
materializes.

Layers:
  * pure graph properties of `Graph.segments` / `elided` /
    `materialization_points` (no jax execution);
  * a property-based random-DAG differential (hypothesis, falling back to
    the deterministic `hypothesis_fallback` shim): random residual-block
    graphs with exclusive boundaries, fused == unfused == oracle across
    fp32/bf16;
  * a true-split 8-virtual-device subprocess (the PR-5 pattern) asserting
    one gather per fused segment and strictly fewer device syncs;
  * the `_fit_axis` strictness regression;
  * a fidelity round-trip: fused `source="fused"` records through
    `MeasurementStore` -> `Calibrator.fit` -> `replan()`.
"""
import os
import subprocess
import sys
import textwrap
from collections import defaultdict

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:
    from hypothesis_fallback import given, settings, st

import jax.numpy as jnp

from repro.core.networks import NETWORKS
from repro.core.partitioner import PartitionDecision
from repro.core.predictor import (sample_conv_ops, sample_linear_ops,
                                  train_predictor)
from repro.core.predictor.gbdt import GBDTParams
from repro.core.predictor.train import MuxPredictor
from repro.core.types import ConvOp, LinearOp
from repro.graph.frontends import from_model
from repro.graph.ir import (SEGMENT_EXCLUSIVE, SEGMENT_FUSED, SEGMENT_POOL,
                            Graph, Node, Segment, from_units)
from repro.measure import MeasurementStore
from repro.runtime import PlanCache
from repro.runtime.executor import PlanExecutor, _fit_axis
from repro.runtime.plan import (CoexecPlan, PlanProvenance,
                                build_graph_schedule, segments_json)


def _forced_plan(g: Graph, decisions, opaque=None) -> CoexecPlan:
    """A hand-built plan over `g` with explicit split decisions — segment
    structure must be deterministic for these tests, so no predictors."""
    prov = PlanProvenance(
        device="moto2022", threads=3, mechanism="svm_poll", step=8, seed=1,
        network_fingerprint=g.fingerprint(), predictor_checksum="")
    return CoexecPlan(
        provenance=prov,
        schedule=build_graph_schedule(g, decisions, opaque or {}),
        graph_json=None if g.is_unit_chain() else g.to_json(),
        segments=segments_json(g, decisions))


def _all_coexec(g: Graph):
    """Every splittable node co-executed (uneven ~3/4-1/4 split), opaque
    kinds priced at a token latency."""
    decisions, opaque = {}, {}
    for n in g:
        if n.kind in ("linear", "conv"):
            c = n.op.C_out
            c_cpu = max(1, c // 4)
            decisions[n.id] = PartitionDecision(
                op=n.op, c_cpu=c_cpu, c_gpu=c - c_cpu,
                pred_cpu_us=1.0, pred_gpu_us=1.0, pred_total_us=2.0)
        elif n.kind in ("attention", "ssm"):
            opaque[n.id] = 1.0
    return decisions, opaque


# ------------------------------------------------- pure graph properties

def test_segment_dataclass_validates():
    s = Segment(kind=SEGMENT_FUSED, node_ids=["a", "b"])
    assert s.node_ids == ("a", "b") and len(s) == 2
    with pytest.raises(ValueError):
        Segment(kind="bogus", node_ids=("a",))
    with pytest.raises(ValueError):
        Segment(kind=SEGMENT_POOL, node_ids=())


def test_tiny_decoder_partition_structure():
    """The decoder block partitions exactly as designed: the attention
    node is an exclusive singleton; the o_proj+residual and the whole MLP
    (up, down, residual join) fuse."""
    g = from_model("tiny_decoder")
    decisions, _ = _all_coexec(g)
    coexec = set(decisions)
    segs = g.segments(coexec)
    got = [(s.kind, s.node_ids) for s in segs]
    assert got == [
        (SEGMENT_FUSED, ("embed",)),
        (SEGMENT_FUSED, ("b0.q_proj",)),
        (SEGMENT_EXCLUSIVE, ("b0.attn",)),
        (SEGMENT_FUSED, ("b0.o_proj", "b0.attn_res")),
        (SEGMENT_FUSED, ("b0.mlp_up", "b0.mlp_down", "b0.mlp_res")),
    ]


@pytest.mark.parametrize("network", ["resnet18", "vgg16"])
def test_conv_network_partitions_to_single_digit_segments(network):
    g = from_units(NETWORKS[network]())
    decisions, _ = _all_coexec(g)
    coexec = set(decisions)
    segs = g.segments(coexec)
    # covering partition, in topological order
    assert [nid for s in segs for nid in s.node_ids] == [n.id for n in g]
    n_fused = sum(1 for s in segs if s.kind == SEGMENT_FUSED)
    # a handful of jitted programs instead of ~20 Python-dispatched ops
    assert 0 < n_fused < 10, [s.node_ids for s in segs]
    assert len(segs) < len(g.nodes)
    # boundary kinds: pools are pool singletons, fused members are
    # coexec ops or adds
    for s in segs:
        if s.kind == SEGMENT_POOL:
            assert len(s) == 1 and g.node(s.node_ids[0]).kind == "pool"
        elif s.kind == SEGMENT_FUSED:
            for nid in s.node_ids:
                assert nid in coexec or g.node(nid).kind == "add"
        # convexity: only the last node of a fused run is consumed outside
        if s.kind == SEGMENT_FUSED:
            ids = set(s.node_ids)
            for nid in s.node_ids[:-1]:
                assert set(g.consumers(nid)) <= ids, (s.node_ids, nid)


def test_unsplit_kinds_and_exclusive_ops_are_boundaries():
    g = from_model("tiny_decoder")
    decisions, _ = _all_coexec(g)
    # demote one mid-block linear to exclusive: it must become a singleton
    decisions["b0.mlp_up"] = PartitionDecision(
        op=g.node("b0.mlp_up").op, c_cpu=0,
        c_gpu=g.node("b0.mlp_up").op.C_out,
        pred_cpu_us=0.0, pred_gpu_us=1.0, pred_total_us=1.0)
    coexec = {nid for nid, d in decisions.items()
              if d.c_cpu > 0 and d.c_gpu > 0}
    segs = {s.node_ids: s.kind for s in g.segments(coexec)}
    assert segs[("b0.attn",)] == SEGMENT_EXCLUSIVE      # unsplit kind
    assert segs[("b0.mlp_up",)] == SEGMENT_EXCLUSIVE    # demoted op
    assert segs[("b0.mlp_down", "b0.mlp_res")] == SEGMENT_FUSED


def test_materialization_points_are_coexec_minus_elided():
    for build in (lambda: from_model("tiny_decoder"),
                  lambda: from_units(NETWORKS["resnet18"]())):
        g = build()
        decisions, _ = _all_coexec(g)
        coexec = frozenset(decisions)
        el = g.elided(coexec)
        assert el <= coexec
        assert g.materialization_points(coexec) == coexec - el
        # an elided producer and its sole consumer share a fused segment
        seg_of = {}
        for k, s in enumerate(g.segments(coexec)):
            for nid in s.node_ids:
                seg_of[nid] = (k, s.kind)
        for nid in el:
            (k, kind) = seg_of[nid]
            cons = g.consumers(nid)[0]
            assert kind == SEGMENT_FUSED
            assert seg_of[cons] == (k, SEGMENT_FUSED), (nid, cons)


def test_plan_embeds_and_reloads_segment_partition():
    g = from_model("tiny_decoder")
    decisions, opaque = _all_coexec(g)
    plan = _forced_plan(g, decisions, opaque)
    doc = plan.to_json()
    assert doc["segments"] == segments_json(g, decisions)
    back = CoexecPlan.from_json(doc)
    assert back.segment_partition() == plan.segment_partition()
    # omitted-when-absent: a plan without the field re-derives identically
    bare = CoexecPlan(provenance=plan.provenance, schedule=plan.schedule,
                      graph_json=plan.graph_json)
    assert "segments" not in bare.to_json()
    assert bare.segment_partition() == plan.segment_partition()
    # the ExecSpec view carries the partition index
    seg_of = plan.segment_of()
    for spec in plan.exec_specs():
        assert spec.segment == seg_of[spec.node_id]


# ------------------------------------- random-DAG differential (property)

def _residual_graph(rng, n_blocks: int, exclusive_mid: bool
                    ) -> Graph:
    """embed -> n_blocks x (u = linear, v = linear, r = add(prev, v))."""
    c = int(rng.choice([16, 24, 32]))
    L = int(rng.integers(2, 5))
    nodes = [Node(id="embed", kind="linear", op=LinearOp(L, c, c))]
    prev = "embed"
    for b in range(n_blocks):
        nodes.append(Node(id=f"b{b}.u", kind="linear",
                          op=LinearOp(L, c, c), inputs=(prev,)))
        nodes.append(Node(id=f"b{b}.v", kind="linear",
                          op=LinearOp(L, c, c), inputs=(f"b{b}.u",)))
        nodes.append(Node(id=f"b{b}.r", kind="add",
                          inputs=(prev, f"b{b}.v")))
        prev = f"b{b}.r"
    return Graph(nodes)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10 ** 6), n_blocks=st.integers(1, 3),
       dtype=st.sampled_from(["float32", "bfloat16"]),
       exclusive_mid=st.sampled_from([False, True]))
def test_random_residual_dag_fused_equals_unfused_and_oracle(
        seed, n_blocks, dtype, exclusive_mid):
    rng = np.random.default_rng(seed)
    g = _residual_graph(rng, n_blocks, exclusive_mid)
    decisions, _ = _all_coexec(g)
    if exclusive_mid:
        op = g.node("b0.v").op
        decisions["b0.v"] = PartitionDecision(
            op=op, c_cpu=0, c_gpu=op.C_out, pred_cpu_us=0.0,
            pred_gpu_us=1.0, pred_total_us=1.0)
    exe = PlanExecutor(_forced_plan(g, decisions), dtype=jnp.dtype(dtype))
    x = exe.input_template()
    y_u, rep_u = exe.run(x, chain=True)
    y_f, rep_f = exe.run(x, fused=True)
    y_o = exe.run_oracle(x)
    assert np.asarray(y_f).tobytes() == np.asarray(y_u).tobytes()
    assert np.asarray(y_f).tobytes() == np.asarray(y_o).tobytes()
    assert rep_f.fused and not rep_u.fused
    # one program, one sync, one wall for the whole walk
    assert rep_f.sync_points == len(rep_f.segment_wall_us) == 1
    assert rep_f.segments == len(exe.walk_program().segments)
    assert rep_f.sync_points <= rep_u.sync_points
    assert len(rep_f.timings) == len(rep_u.timings) == len(g.nodes)
    # the partition indices on the records cover the partition in order
    segs = exe.plan.segment_partition()
    assert [t.segment for t in rep_f.timings] == \
        [k for k, s in enumerate(segs) for _ in s.node_ids]


def test_pool_boundaries_differential():
    """Conv graph with pools: pools are singleton boundaries; fused ==
    unfused == oracle bit-for-bit."""
    units = [("conv", ConvOp(8, 8, 8, 16, 3, 1)),
             ("conv", ConvOp(8, 8, 16, 16, 3, 1)),
             ("pool", 4 * 4 * 4 * 16),
             ("conv", ConvOp(4, 4, 16, 24, 3, 1)),
             ("linear", LinearOp(1, 4 * 4 * 24, 32))]
    g = from_units(units)
    decisions, _ = _all_coexec(g)
    exe = PlanExecutor(_forced_plan(g, decisions))
    y_u, rep_u = exe.run(chain=True)
    y_f, rep_f = exe.run(fused=True)
    y_o = exe.run_oracle()
    assert np.asarray(y_f).tobytes() == np.asarray(y_u).tobytes()
    assert np.asarray(y_f).tobytes() == np.asarray(y_o).tobytes()
    kinds = [p.kind for p in exe.walk_program().segments]
    assert SEGMENT_POOL in kinds
    assert rep_f.count("pool") == rep_u.count("pool") == 1


def test_fused_requires_chaining():
    g = from_units([("linear", LinearOp(1, 8, 8))])
    decisions, _ = _all_coexec(g)
    exe = PlanExecutor(_forced_plan(g, decisions))
    with pytest.raises(ValueError, match="fused"):
        exe.run(chain=False, fused=True)


# ---------------------------------------------- _fit_axis strictness fix

def test_fit_axis_strict_raises_on_non_alignment_mismatch():
    x = jnp.ones((4, 10))
    # growing an axis is never alignment padding
    with pytest.raises(ValueError, match="axis 1"):
        _fit_axis(x, 1, 32)
    # shrinking past the alignment envelope is a real mismatch too:
    # 10 > roundup(4, 8) = 8
    with pytest.raises(ValueError, match="axis 1"):
        _fit_axis(x, 1, 4)
    # exact size is the identity
    assert _fit_axis(x, 1, 10) is x
    # cropping alignment padding is the legitimate case: 37 -> padded 40
    y = _fit_axis(jnp.ones((4, 40)), 1, 37)
    assert y.shape == (4, 37)
    # lcm-of-8-and-lanes granularity via align=
    assert _fit_axis(jnp.ones((4, 48)), 1, 33, align=24).shape == (4, 33)
    with pytest.raises(ValueError):           # 48 > roundup(33, 8) = 40
        _fit_axis(jnp.ones((4, 48)), 1, 33, align=8)


def test_fit_axis_adapt_keeps_tile_and_crop():
    x = jnp.arange(6, dtype=jnp.float32).reshape(1, 6)
    y = _fit_axis(x, 1, 15, adapt=True)       # tile x3 (18) then crop
    assert y.shape == (1, 15)
    np.testing.assert_array_equal(
        np.asarray(y)[0], np.tile(np.arange(6), 3)[:15])
    assert _fit_axis(x, 1, 4, adapt=True).shape == (1, 4)


# --------------------------------- true split execution (8-device subproc)

_SPLIT_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.core.coexec import coexec_mesh
    from repro.core.networks import NETWORKS
    from repro.core.partitioner import PartitionDecision
    from repro.graph.frontends import from_model
    from repro.graph.ir import from_units
    from repro.runtime.executor import PlanExecutor
    from repro.runtime.plan import (CoexecPlan, PlanProvenance,
                                    build_graph_schedule, segments_json)

    def forced(g):
        decisions, opaque = {}, {}
        for n in g:
            if n.kind in ("linear", "conv"):
                c = n.op.C_out
                c_cpu = max(1, c // 4)
                decisions[n.id] = PartitionDecision(
                    op=n.op, c_cpu=c_cpu, c_gpu=c - c_cpu,
                    pred_cpu_us=1.0, pred_gpu_us=1.0, pred_total_us=2.0)
            elif n.kind in ("attention", "ssm"):
                opaque[n.id] = 1.0
        prov = PlanProvenance(
            device="moto2022", threads=3, mechanism="svm_poll", step=8,
            seed=1, network_fingerprint=g.fingerprint(),
            predictor_checksum="")
        return CoexecPlan(
            provenance=prov,
            schedule=build_graph_schedule(g, decisions, opaque),
            graph_json=None if g.is_unit_chain() else g.to_json(),
            segments=segments_json(g, decisions)), decisions

    mesh = coexec_mesh(jax.devices())
    for name, g in [("resnet18", from_units(NETWORKS["resnet18"]())),
                    ("tiny_decoder", from_model("tiny_decoder"))]:
        plan, decisions = forced(g)
        exe = PlanExecutor(plan, mesh=mesh)
        assert exe.split_capable
        progs = exe.walk_program().segments
        fused = [p for p in progs if p.kind == "fused"]
        assert 0 < len(progs) < 10 and fused, name
        # acceptance: a fused segment issues EXACTLY ONE gather — at its
        # boundary; every interior edge stays group-local or is merged
        # inside the program
        for p in fused:
            assert p.gathers == 1, (name, p.node_ids, p.gathers)
        y_u, rep_u = exe.run(chain=True)
        y_f, rep_f = exe.run(fused=True)
        y_o = exe.run_oracle()
        assert np.asarray(y_f).tobytes() == np.asarray(y_u).tobytes(), name
        np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_o),
                                   rtol=2e-5, atol=2e-5)
        # strictly fewer device syncs than the per-node walk: one for
        # the whole walk, whatever the number of segments
        assert rep_f.sync_points < rep_u.sync_points, name
        assert rep_f.sync_points == 1 and rep_f.segments == len(progs)
        # both walks reshard at the same points and elide the same edges
        assert rep_f.reshard_points == rep_u.reshard_points, name
        assert rep_f.elided == rep_u.elided, name
        # the partition's boundaries ARE the unfused materialization
        # points: producers of chained records == graph.elided
        coexec = frozenset(exe.plan.coexec_node_ids())
        want = g.elided(coexec)
        from_unfused = {g.node(t.node_id).inputs[0]
                        for t in rep_u.timings if t.chained_input}
        from_fused = {nid for p in progs
                      for nid, gf in p.gathered.items() if not gf}
        assert from_unfused == want, name
        assert from_fused == want, name
        print(name, "segments", len(progs), "fused", len(fused),
              "sync", rep_f.sync_points, "vs", rep_u.sync_points)
    print("FUSED_SPLIT_OK")
""")


def test_fused_split_execution_on_8_virtual_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SPLIT_PROG], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FUSED_SPLIT_OK" in out.stdout


# ------------------------------------------------- fidelity round-trip

_FAST = GBDTParams(n_estimators=30, max_depth=5, learning_rate=0.25)


@pytest.fixture(scope="module")
def mux_predictors():
    lt = sample_linear_ops(150, seed=1)
    ct = sample_conv_ops(150, seed=1)
    dev = "moto2022"
    gp = MuxPredictor(
        train_predictor(lt, dev, "gpu", whitebox=True, params=_FAST),
        train_predictor(ct, dev, "gpu", whitebox=True, params=_FAST))
    cp = MuxPredictor(
        train_predictor(lt, dev, "cpu3", whitebox=False, params=_FAST),
        train_predictor(ct, dev, "cpu3", whitebox=False, params=_FAST))
    return cp, gp


def test_fused_records_roundtrip_store_calibrate_replan(mux_predictors,
                                                        tmp_path):
    """Fused plan -> MeasurementStore -> Calibrator.fit -> replan(), end
    to end on source="fused" records; the pro-rata attribution sums back
    to the walk's wall, and the records keep their segment indices."""
    import repro

    cache = PlanCache(tmp_path / "plans")
    target = repro.Target(device="moto2022", threads=3)
    units = [("conv", ConvOp(14, 14, 16, 32, 3, 1)),
             ("conv", ConvOp(14, 14, 32, 32, 3, 2)),
             ("pool", 4 * 7 * 7 * 32),
             ("linear", LinearOp(1, 7 * 7 * 32, 64)),
             ("linear", LinearOp(1, 64, 32))]
    compiled = repro.compile(units, target, predictors=mux_predictors,
                             cache=cache)
    store = MeasurementStore(tmp_path / "meas")
    for _ in range(2):
        rep = compiled.record(store=store, warmup=False, fused=True)
        assert rep.fused
        by_seg = defaultdict(float)
        for t in rep.timings:
            assert t.source == "fused" and t.segment >= 0
            by_seg[t.segment] += t.wall_us
        assert sorted(by_seg) == list(range(rep.segments))
        (wall,) = rep.segment_wall_us
        assert sum(by_seg.values()) == pytest.approx(wall, rel=1e-9,
                                                     abs=1e-6)

    records = store.load(compiled.key)
    assert len(records) == 2 * len(compiled.plan.schedule)
    assert all(r.source == "fused" for r in records)
    cal = compiled.recalibrate(store)
    assert cal.n_records > 0

    recompiled, diff = compiled.replan(cal, store=store, cache=cache)
    assert recompiled.key != compiled.key
    assert recompiled.provenance.calibration == cal.version
    # the replanned network executes fused too, and its records keep the
    # new provenance key
    rep2 = recompiled.profile(warmup=False, fused=True)
    assert rep2.fused
    assert all(t.plan_key == recompiled.key for t in rep2.timings)
