"""Program spans (`repro.measure.trace`) and the scheduler's per-token
counters.

Under `jax.profiler.trace` on the CPU: the executor's walks record
`repro.exec.run` ⊃ `repro.exec.segment` ⊃ `repro.exec.sync`, with one sync
span per counted sync point, and its timings are the spans' own readings;
the scheduler records one `repro.sched.step` per step with its phases
inside.  Outputs are bit-identical with the profiler on and off.  Under
the virtual clock the scheduler's token stamps and admission times are
checked exactly.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.partitioner import PartitionDecision
from repro.core.types import ConvOp, LinearOp
from repro.graph.ir import from_units
from repro.measure import trace
from repro.models import build_model, get_config
from repro.runtime.executor import PlanExecutor
from repro.runtime.plan import (CoexecPlan, PlanProvenance,
                                build_graph_schedule, segments_json)
from repro.serving import (ContinuousScheduler, FixedBatchReference, Request,
                           SchedulerConfig)
from repro.serving.scheduler import DEFAULT_STEP_COST_S, RequestStats

UNITS = [("conv", ConvOp(8, 8, 8, 16, 3, 1)),
         ("conv", ConvOp(8, 8, 16, 16, 3, 1)),
         ("pool", 4 * 4 * 4 * 16),
         ("conv", ConvOp(4, 4, 16, 24, 3, 1)),
         ("linear", LinearOp(1, 4 * 4 * 24, 32))]
#: the spans inside a scheduler step, one of each per step;
#: `repro.sched.read` sits inside `repro.sched.sample`
STEP_CHILDREN = {"repro.sched.inputs", "repro.sched.decode",
                 "repro.sched.sample", "repro.sched.read",
                 "repro.sched.emit"}


def _executor() -> PlanExecutor:
    """A hand-built plan (no predictors) over a small conv chain: fused
    segments on both sides of a pool singleton."""
    g = from_units(UNITS)
    decisions = {}
    for n in g:
        if n.kind in ("linear", "conv"):
            c = n.op.C_out
            decisions[n.id] = PartitionDecision(
                op=n.op, c_cpu=c // 4, c_gpu=c - c // 4, pred_cpu_us=1.0,
                pred_gpu_us=1.0, pred_total_us=2.0)
    prov = PlanProvenance(
        device="moto2022", threads=3, mechanism="svm_poll", step=8, seed=1,
        network_fingerprint=g.fingerprint(), predictor_checksum="")
    plan = CoexecPlan(provenance=prov,
                      schedule=build_graph_schedule(g, decisions, {}),
                      graph_json=g.to_json(),
                      segments=segments_json(g, decisions))
    return PlanExecutor(plan)


def _recorded(tmp_path, fn):
    """Run `fn` under the profiler; returns its result and the program's
    spans as (name, start_ns, end_ns, stats), in start order."""
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
              {k: v for k, v in e.stats})
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("repro.")]
    return out, sorted(spans, key=lambda s: (s[1], -s[2]))


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _named(spans, name):
    return [s for s in spans if s[0] == name]


@pytest.fixture(scope="module")
def gqa_model():
    cfg = get_config("codeqwen15_7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _reqs(prompts, max_new, arrivals, temps=None):
    rng = np.random.default_rng(7)
    return [Request(rid=i, prompt=rng.integers(1, 256, t).astype(np.int32),
                    max_new_tokens=max_new[i],
                    temperature=0.0 if temps is None else temps[i],
                    arrival_s=arrivals[i])
            for i, t in enumerate(prompts)]


# ------------------------------------------------------------ executor

def test_fused_walk_spans_nest_and_count_the_syncs(tmp_path):
    exe = _executor()
    x = exe.input_template()
    y_off, rep = exe.run(x, fused=True, warmup=True)
    (y_on, rep_on), spans = _recorded(
        tmp_path, lambda: [exe.run(x, fused=True) for _ in range(2)][-1])
    assert np.asarray(y_on).tobytes() == np.asarray(y_off).tobytes()
    runs = _named(spans, "repro.exec.run")
    segs = _named(spans, "repro.exec.segment")
    syncs = _named(spans, "repro.exec.sync")
    assert len(runs) == 2
    # the whole walk is one program: one segment span and one sync a run
    assert len(segs) == len(syncs) == 2 * rep_on.sync_points == 2
    assert rep_on.segments == len(exe.walk_program().segments) > 1
    for r in runs:
        assert sum(_inside(s, r) for s in syncs) == rep_on.sync_points
        assert sum(_inside(g, r) for g in segs) == 1
    for s in syncs:
        assert any(_inside(s, g) for g in segs)
    for g in segs:
        assert any(_inside(g, r) for r in runs)
    assert [(g[3]["index"], g[3]["mode"]) for g in segs] == [(0, "fused")] * 2


def test_node_walk_spans_nest_and_count_the_syncs(tmp_path):
    exe = _executor()
    x = exe.input_template()
    y_off, _ = exe.run(x, warmup=True)
    (y_on, rep), spans = _recorded(tmp_path, lambda: exe.run(x))
    assert np.asarray(y_on).tobytes() == np.asarray(y_off).tobytes()
    (run,) = _named(spans, "repro.exec.run")
    segs = _named(spans, "repro.exec.segment")
    syncs = _named(spans, "repro.exec.sync")
    assert len(segs) == len(rep.timings) == len(UNITS)
    assert len(syncs) == rep.sync_points == len(UNITS) + 1
    assert [g[3]["index"] for g in segs] == list(range(len(UNITS)))
    assert [g[3]["mode"] for g in segs] == [t.mode for t in rep.timings]
    # one sync per segment; the terminal one sits directly in the run
    loose = [s for s in syncs if not any(_inside(s, g) for g in segs)]
    assert len(loose) == 1 and _inside(loose[0], run)
    assert loose[0][1] >= max(g[2] for g in segs)


class _Kept(trace.span):
    """A span that keeps itself in `_Kept.made`, to read its elapsed time
    after the walk."""

    __slots__ = ("name",)
    made: list = []

    def __init__(self, name, **stats):
        super().__init__(name, **stats)
        self.name = name
        _Kept.made.append(self)


@pytest.mark.parametrize("fused", [True, False])
def test_walk_timings_are_the_span_readings(monkeypatch, fused):
    exe = _executor()
    exe.run(fused=fused, warmup=True)
    monkeypatch.setattr(trace, "span", _Kept)
    _Kept.made = []
    _, rep = exe.run(fused=fused)
    segs = [s.elapsed_s * 1e6 for s in _Kept.made
            if s.name == "repro.exec.segment"]
    assert all(w > 0.0 for w in segs)
    if fused:
        assert len(segs) == 1 and rep.segment_wall_us == segs
        assert sum(t.wall_us for t in rep.timings) == pytest.approx(
            sum(segs), rel=1e-12)
    else:
        # one device: nothing is gathered at the end, so each record is
        # its segment span's reading exactly
        assert [t.wall_us for t in rep.timings] == segs


def test_segment_programs_carry_their_index_and_node_scopes():
    exe = _executor()
    x = exe.input_template()
    walk = exe.walk_program(tuple(x.shape))
    text = walk.fn.lower(x, walk.weights).as_text(debug_info=True)
    assert "jit_walk" in text
    kinds = []
    for sp in walk.segments:
        kinds.append(sp.kind)
        for nid in sp.node_ids:
            assert f"jit(walk)/segment_{sp.index}/{nid}/" in text
    assert kinds.count("fused") == 3 and kinds.count("pool") == 1


def test_span_without_a_profiler_session_only_times():
    with trace.span("repro.test", index=1) as s:
        s.set(active=2)
        jnp.ones(4).block_until_ready()
    assert s.elapsed_s > 0.0


# ----------------------------------------------------------- scheduler

def test_scheduler_records_one_step_span_per_step(gqa_model, tmp_path):
    cfg, model, params = gqa_model
    reqs = _reqs([3, 5, 2, 4], [3, 2, 4, 2], [0.0, 0.0, 0.002, 0.003],
                 temps=[0.0, 0.7, 0.0, 0.7])
    conf = SchedulerConfig(max_batch=2, max_len=32)
    off = ContinuousScheduler(cfg, model, params, config=conf).run(reqs)
    sched = ContinuousScheduler(cfg, model, params, config=conf)
    rep, spans = _recorded(tmp_path, lambda: sched.run(reqs))
    assert [(c.rid, c.tokens) for c in rep.completions] == \
        [(c.rid, c.tokens) for c in off.completions]
    steps = _named(spans, "repro.sched.step")
    assert len(steps) == rep.steps
    assert [s[3]["step"] for s in steps] == list(range(rep.steps))
    assert all(1 <= s[3]["active"] <= conf.max_batch for s in steps)
    children = [s for s in spans if s[0].startswith("repro.sched.")
                and s[0] != "repro.sched.step"]
    assert {c[0] for c in children} == STEP_CHILDREN
    for name in STEP_CHILDREN:
        assert len(_named(children, name)) == rep.steps
    for c in children:
        assert sum(_inside(c, s) for s in steps) == 1
    samples = _named(children, "repro.sched.sample")
    for r in _named(children, "repro.sched.read"):
        assert sum(_inside(r, s) for s in samples) == 1


def test_token_stamps_and_queue_wait_on_the_virtual_clock(gqa_model):
    cfg, model, params = gqa_model
    # one slot: the second request queues behind the first
    reqs = _reqs([3, 2], [4, 3], [0.0, 0.0])
    rep = ContinuousScheduler(
        cfg, model, params,
        config=SchedulerConfig(max_batch=1, max_len=32)).run(reqs)
    cost = DEFAULT_STEP_COST_S
    by_rid = {s.rid: s for s in rep.stats}
    for s in rep.stats:
        assert len(s.token_s) == s.n_tokens
        assert s.token_s[0] == s.first_token_s
        assert s.token_s[-1] == s.done_s
        assert s.itl_s == pytest.approx([cost] * (s.n_tokens - 1),
                                        abs=1e-12)
        assert s.queue_wait_s == s.admitted_s - s.arrival_s
    # the first holds the slot for 3 + 4 - 1 steps
    assert by_rid[0].queue_wait_s == 0.0
    assert by_rid[1].queue_wait_s == pytest.approx(6 * cost, abs=1e-12)
    assert rep.itl_p(50) == pytest.approx(cost, abs=1e-12)
    assert rep.queue_wait_p(100) == pytest.approx(6 * cost, abs=1e-12)
    doc = rep.to_json()
    assert doc["itl_p99_s"] == pytest.approx(cost, abs=1e-12)
    assert doc["queue_wait_p50_s"] == pytest.approx(3 * cost, abs=1e-12)
    assert by_rid[1].to_json()["queue_wait_s"] == by_rid[1].queue_wait_s
    assert "queue wait p50" in rep.summary() and "itl p50" in rep.summary()


def test_fixed_batch_reference_stamps_tokens_and_admission(gqa_model):
    class _Plan:
        end_to_end_us = 2000.0

    compiled = type("C", (), {"plan": _Plan()})()
    reqs = _reqs([3, 5, 2], [2, 4, 3], [0.0, 0.001, 0.010])
    rep = FixedBatchReference(compiled, max_batch=2).run(reqs)
    for s in rep.stats:
        assert len(s.token_s) == s.n_tokens
        assert s.token_s[0] == s.first_token_s
        assert s.token_s[-1] == pytest.approx(s.done_s, abs=1e-12)
        assert s.itl_s == pytest.approx([2e-3] * (s.n_tokens - 1),
                                        abs=1e-12)
    # a batch is admitted when its last member has arrived
    assert [s.admitted_s for s in rep.stats[:2]] == [0.001, 0.001]
    assert rep.stats[2].queue_wait_s > 0.0


def test_request_stats_without_counters_serialize():
    s = RequestStats(rid=0, arrival_s=0.0, first_token_s=1.0, done_s=2.0,
                     n_tokens=1)
    assert s.queue_wait_s is None and s.itl_s == []
    assert s.to_json()["queue_wait_s"] is None
