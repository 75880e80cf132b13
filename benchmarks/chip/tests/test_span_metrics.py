"""CPU checks of the readers of the program's spans (`spans.py` and the
metrics that use it).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

Every reader on hand-built traces, where each number is worked out by
hand; on a trace of a program that records no span (it must read None);
on a trace of the executor recorded on the CPU; and through whole traced
runs of tiny cells.  A CPU trace holds no device plane, so the readers
of device idle time read None there.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness     # noqa: E402
import spans       # noqa: E402
import spec_checks  # noqa: E402
import tracing     # noqa: E402

SPEC = harness.load_json(ROOT / "BENCHMARK.json")
EXEC_METRICS = ["sync_wait_ms", "dispatch_ms", "idle_in_sync_ms"]
SERVE_METRICS = {"inputs_ms.serve": "repro.sched.inputs",
                 "decode_call_ms.serve": "repro.sched.decode",
                 "sample_ms.serve": "repro.sched.sample",
                 "emit_ms.serve": "repro.sched.emit"}
NEW = [f"{m}.{u}" for m in EXEC_METRICS for u in ("infer", "decode")] + \
    list(SERVE_METRICS)
SEED = 2**33 + 12345


def reader(name: str):
    return harness.load_module(HERE / "metrics" / f"{name}.py")


def ctx_of(tr) -> dict:
    return {"trace": tr, "window": tr.window() if tr else None, "raw": {}}


def exec_trace() -> tracing.Trace:
    """Two runs of two segments each, on two chips; times in ns.

    run 1 [10, 50]: segment [10, 30] with sync [20, 30]; segment
    [30, 48] with sync [40, 48].  run 2 [60, 100]: segment [60, 80] with
    sync [70, 80]; segment [80, 95] with sync [85, 95].  A run outside
    the window [0, 120] does not count.
    """
    host = [[("bench.window", 0, 120),
             ("repro.exec.run", 10, 50),
             ("repro.exec.segment", 10, 30), ("repro.exec.sync", 20, 30),
             ("_api.py:3108 try_to_block", 21, 29),
             ("repro.exec.segment", 30, 48), ("repro.exec.sync", 40, 48),
             ("repro.exec.run", 60, 100),
             ("repro.exec.segment", 60, 80), ("repro.exec.sync", 70, 80),
             ("repro.exec.segment", 80, 95), ("repro.exec.sync", 85, 95),
             ("repro.exec.run", 130, 140)]]
    # chip 0 busy [22, 28] and [72, 90].  Idle in the syncs: [20, 30]
    # 4, [40, 48] 8, [70, 80] 2, [85, 95] 5: 19.  Chip 1 is busy all
    # through: 0.  Mean over the chips: 9.5.
    ops = {"/device:TPU:0": [("%fusion.1", 22, 28), ("%fusion.2", 72, 90)],
           "/device:TPU:1": [("%fusion.1", 0, 120)]}
    return tracing.Trace(ops, {}, host)


def step_trace() -> tracing.Trace:
    host = [[("bench.window", 0, 100)]]
    for k, t in enumerate((10, 50)):
        host[0] += [("repro.sched.step", t, t + 30),
                    ("repro.sched.inputs", t + 2, t + 4),
                    ("repro.sched.decode", t + 4, t + 9),
                    ("repro.sched.sample", t + 9, t + 15),
                    ("repro.sched.emit", t + 15, t + 15 + 10 * (k + 1))]
    return tracing.Trace({"/device:TPU:0": [("%while.1", 5, 95)]}, {}, host)


# ------------------------------------------------------ by hand

def test_executor_readers_by_hand():
    ctx = ctx_of(exec_trace())
    for unit in ("infer", "decode"):
        # two runs: syncs 10 + 8 + 10 + 10 = 38 ns
        assert reader(f"sync_wait_ms.{unit}").read(ctx) == \
            pytest.approx(19e-6)
        # segments 20 + 18 + 20 + 15 = 73, less their syncs 38
        assert reader(f"dispatch_ms.{unit}").read(ctx) == \
            pytest.approx(17.5e-6)
        assert reader(f"idle_in_sync_ms.{unit}").read(ctx) == \
            pytest.approx(9.5 / 2 * 1e-6)


def test_scheduler_readers_by_hand():
    ctx = ctx_of(step_trace())
    want = {"inputs_ms.serve": 2, "decode_call_ms.serve": 5,
            "sample_ms.serve": 6, "emit_ms.serve": (10 + 20) / 2}
    for name, ns in want.items():
        assert reader(name).read(ctx) == pytest.approx(ns * 1e-6)


def test_readers_read_nothing_without_program_spans():
    tr = tracing.Trace({"/device:TPU:0": [("%fusion", 5, 10)]}, {},
                       [[("bench.window", 0, 100),
                         ("_api.py:3108 try_to_block", 10, 20)]])
    for name in NEW:
        assert reader(name).read(ctx_of(tr)) is None
        assert reader(name).read(ctx_of(None)) is None
    assert spans.coverage(tr, tr.window()) is None


def test_interval_overlap_and_innermost():
    a = tracing.merge([(0, 10), (20, 30)])
    assert spans.overlap(a, [(5, 25)]) == 10
    assert spans.overlap(a, []) == 0
    nested = [("run", 0, 100), ("seg", 10, 40), ("sync", 30, 40),
              ("seg", 50, 90), ("sync", 80, 90), ("other", 120, 130)]
    assert spans.innermost(nested) == [
        ("run", 0, 10), ("seg", 10, 30), ("sync", 30, 40), ("run", 40, 50),
        ("seg", 50, 80), ("sync", 80, 90), ("run", 90, 100),
        ("other", 120, 130)]


def test_idle_by_span_and_coverage_by_hand():
    tr = exec_trace()
    win = tr.window()
    idle = spans.idle_by_span(tr, win)
    # chip 0 idle [0,22] [28,72] [90,120]; chip 1 none.  Syncs: 19 (as
    # above); segment self [10,20] [30,40] [60,70] [80,85]: 10 + 10 + 10
    # + 0 = 30; run self [48,50] [95,100]: 7; outside: [0,10] [50,60]
    # [100,120]: 40.  Averaged over two chips, in seconds.
    assert idle == pytest.approx({"repro.exec.sync": 9.5e-9,
                                  "repro.exec.segment": 15e-9,
                                  "repro.exec.run": 3.5e-9,
                                  spans.OUTSIDE: 20e-9})
    assert spans.coverage(tr, win) == pytest.approx(80 / 120)


def test_every_new_metric_is_declared_for_its_cells():
    declared = {m["name"]: m for m in SPEC["per_layer"]}
    for name in NEW:
        m = declared[name]
        assert m["unit"] == "ms" and m["better"] == "lower"
        assert (HERE / "metrics" / f"{name}.py").is_file()
    # each cell carries them by the end-to-end metrics it reports
    carried = {n for names in spec_checks.CARRIES.values() for n in names}
    assert set(NEW) <= carried
    spec_checks.check_spec(SPEC, HERE)


# ---------------------------------------------- recorded on the CPU

def test_readers_on_an_executor_trace_recorded_on_cpu(tmp_path):
    import jax

    from repro.core.networks import NETWORKS
    from repro.core.partitioner import PartitionDecision
    from repro.graph.ir import from_units
    from repro.runtime.executor import PlanExecutor
    from repro.runtime.plan import (CoexecPlan, PlanProvenance,
                                    build_graph_schedule, segments_json)

    g = from_units(NETWORKS["resnet18"]()[:5])
    decisions = {n.id: PartitionDecision(
        op=n.op, c_cpu=0, c_gpu=n.op.C_out, pred_cpu_us=0.0,
        pred_gpu_us=1.0, pred_total_us=1.0)
        for n in g if n.kind in ("conv", "linear")}
    prov = PlanProvenance(
        device="moto2022", threads=3, mechanism="svm_poll", step=8, seed=1,
        network_fingerprint=g.fingerprint(), predictor_checksum="")
    exe = PlanExecutor(CoexecPlan(
        provenance=prov, schedule=build_graph_schedule(g, decisions, {}),
        graph_json=g.to_json(), segments=segments_json(g, decisions)))
    exe.run(fused=True, warmup=True)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        reports = [exe.run(fused=True)[1] for _ in range(3)]
    jax.profiler.stop_trace()
    tr = tracing.Trace.from_file(tracing.find_xplane(str(tmp_path)))
    ctx = ctx_of(tr)
    assert len(spans.intervals(ctx, spans.RUN)) == 3
    assert len(spans.intervals(ctx, spans.SYNC)) == \
        sum(r.sync_points for r in reports)
    sync = reader("sync_wait_ms.infer").read(ctx)
    dispatch = reader("dispatch_ms.infer").read(ctx)
    assert sync > 0.0 and dispatch > 0.0
    # the segment spans split into dispatch and sync, and hold the
    # executor's own segment timings
    seg_ms = spans.span_ms(ctx, spans.SEGMENT, spans.RUN)
    assert sync + dispatch == pytest.approx(seg_ms)
    wall_ms = sum(sum(r.segment_wall_us) for r in reports) / 3 / 1e3
    assert seg_ms == pytest.approx(wall_ms, rel=0.05)
    assert reader("idle_in_sync_ms.infer").read(ctx) is None
    assert 0.5 < spans.coverage(tr, tr.window()) <= 1.0


@pytest.fixture
def cpu_peak(monkeypatch):
    import jax

    import counts
    kind = jax.devices()[0].device_kind
    monkeypatch.setitem(counts.PEAKS, kind, counts.PEAKS["TPU v5 lite"])


@pytest.mark.parametrize("workload,config,mix,names", [
    ("resnet18.b1", {}, {"use_pallas": False},
     ["sync_wait_ms.infer", "dispatch_ms.infer"]),
    ("codeqwen15_7b.chat",
     {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
      "intermediate_size": 128, "num_hidden_layers": 2, "vocab_size": 256},
     {"max_batch": 8, "max_len": 128, "rate_per_s": 20.0,
      "prompt_len": {"median": 8, "sigma": 0.8, "min": 4, "max": 32},
      "output_len": {"median": 8, "sigma": 0.8, "min": 4, "max": 32}},
     list(SERVE_METRICS)),
])
def test_traced_run_reports_the_span_metrics(cpu_peak, workload, config,
                                             mix, names):
    """A whole traced run of a tiny cell, as `run.py --trace 1` makes it:
    the span readers read finite numbers (device idle needs the chip)."""
    import jax

    import run
    cell = harness.resolve(SPEC, workload)
    cell["config"].update(config)
    cell["traffic"].update(mix)
    res = run.run_cell(cell, SEED, 0.3, True, jax.devices())
    for name in names:
        assert res["metrics"][name]["value"] >= 0.0
        assert res["metrics"][name]["unit"] == "ms"
    assert res["correct"]


def test_span_report_on_a_tiny_cell(cpu_peak):
    """`span_report.py`'s reading of one tiny cell on the CPU: both
    windows' end-to-end numbers, the span counts (one sync per segment,
    `sync_points` per run) and the window's coverage."""
    import jax

    import span_report
    cell = harness.resolve(SPEC, "resnet18.b1")
    cell["traffic"]["use_pallas"] = False
    out = span_report.span_report(cell, SEED, 0.3, jax.devices())
    assert out["untraced"]["infer_ms"]["value"] > 0.0
    assert out["traced"]["infer_ms"]["value"] > 0.0
    n = out["counts"]
    assert n[spans.SEGMENT] == n[spans.SYNC] == \
        n[spans.RUN] * out["per_layer"]["sync_points.infer"]["value"]
    assert 0.5 < out["coverage"] <= 1.0
    assert out["idle_by_span"] == {}         # no device plane on the CPU
