"""Tests for the continuous-batching scheduler, plan portfolios, and the
drift-triggered replan loop (PR 8).

Covers: scheduler-vs-solo token equality (continuous batching must not
change greedy completions), `sample_tokens` against the eager sampling
formula and its one program per path, the scheduler's contract with the
chip benchmark's watch of `sample_tokens` (one call per step, a host
array, one index per emitting row), mixed-length left-padded batches
through the fixed-batch engine, the decode early-break accounting, windowed drift +
the latest-vs-first alias, portfolio select/save/load/tamper, bucketed
plan provenance byte-compat, Poisson traffic determinism, calibrator
composition, and the two serving acceptance criteria: the portfolio
scheduler beating the fixed-batch reference on p99 latency AND tokens/s,
and a simulated mid-run throttle triggering an in-place replan whose
post-replan fidelity error is lower than pre-replan.
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

import repro
from repro.core.predictor import sample_conv_ops, sample_linear_ops, \
    train_predictor
from repro.core.predictor.gbdt import GBDTParams
from repro.core.predictor.train import MuxPredictor
from repro.measure.calibrate import (MIN_AFFINE_SPREAD, AffineCorrection,
                                     Calibrator, _fit_group)
from repro.models import build_model, get_config
from repro.runtime.plan import PlanProvenance
from repro.serving import (ContinuousScheduler, FixedBatchReference, Request,
                           SchedulerConfig, ServingEngine, ThrottleSim,
                           poisson_requests)

_FAST = GBDTParams(n_estimators=40, max_depth=6, learning_rate=0.2)


@pytest.fixture(scope="module")
def gqa_model():
    cfg = get_config("codeqwen15_7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


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


@pytest.fixture(scope="module")
def plan_cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("plans")


def _portfolio(gqa_model, mux_predictors, cache, buckets):
    cfg, _, _ = gqa_model
    return repro.compile_portfolio(
        cfg, repro.Target(device="moto2022"), buckets=buckets,
        cache=cache, predictors=mux_predictors)


def _reqs(prompts, max_new, arrivals=None, temps=None):
    rng = np.random.default_rng(7)
    vocab = 256
    out = []
    for i, t in enumerate(prompts):
        out.append(Request(
            rid=i,
            prompt=rng.integers(1, vocab, t).astype(np.int32),
            max_new_tokens=max_new[i] if isinstance(max_new, (list, tuple))
            else max_new,
            temperature=0.0 if temps is None else temps[i],
            arrival_s=0.0 if arrivals is None else arrivals[i]))
    return out


# ------------------------------------------------------- scheduler basics

def test_scheduler_matches_solo_greedy(gqa_model):
    """Continuous batching with staggered arrivals and mixed prompt
    lengths must produce exactly the completions each request gets when
    served alone — slot join/evict cannot leak across timelines."""
    cfg, model, params = gqa_model
    reqs = _reqs(prompts=[3, 7, 2, 9, 5], max_new=[4, 2, 5, 3, 4],
                 arrivals=[0.0, 0.0, 0.002, 0.004, 0.01])
    sched = ContinuousScheduler(
        cfg, model, params,
        config=SchedulerConfig(max_batch=2, max_len=32))
    rep = sched.run(reqs)
    got = {c.rid: c.tokens for c in rep.completions}
    assert sorted(got) == [0, 1, 2, 3, 4]
    for r in reqs:
        solo = ServingEngine(cfg, model, params, max_batch=1, max_len=32)
        want = solo.run([dataclasses.replace(r, arrival_s=0.0)])[0].tokens
        assert got[r.rid] == want, f"request {r.rid} diverged"
    assert rep.total_tokens == sum(len(t) for t in got.values())
    for s in rep.stats:
        assert s.ttft_s > 0.0
        assert s.latency_s >= s.ttft_s


def test_scheduler_rejects_non_slotted_models():
    cfg = get_config("rwkv6_1b6").reduced()
    model = build_model(cfg)
    with pytest.raises(ValueError, match="per-slot position"):
        ContinuousScheduler(cfg, model, params=None)


def test_scheduler_validates_request_length(gqa_model):
    cfg, model, params = gqa_model
    sched = ContinuousScheduler(
        cfg, model, params, config=SchedulerConfig(max_len=16))
    with pytest.raises(ValueError, match="exceeds max_len"):
        sched.run(_reqs(prompts=[14], max_new=8))
    with pytest.raises(ValueError, match="unknown clock"):
        SchedulerConfig(clock="sundial")


# ------------------------------------------------------------- sampling

def _eager_sample(rng, logits, temperatures):
    """The step's sampling as it was before it ran as one program: eager
    ops, the greedy-or-sampled choice read back from the device."""
    import jax.numpy as jnp
    temps = jnp.asarray(temperatures, jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if not bool(jnp.any(temps > 0.0)):
        return greedy, rng
    rng, sub = jax.random.split(rng)
    safe = jnp.where(temps > 0.0, temps, 1.0)
    sampled = jax.random.categorical(
        sub, logits / safe[:, None], axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy), rng


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("temps", [
    (0.0,) * 8, (0.0, 0.7, 0.0, 1.3, 0.0, 0.0, 0.7, 0.2), (0.7,) * 8])
def test_sample_tokens_matches_the_eager_formula(dtype, temps):
    """One program per path gives the eager formula's tokens, token for
    token, and the same next key; an all-greedy step leaves the key as
    it was.  The tokens come back as a host int32 vector."""
    from repro.serving import engine
    logits = jax.numpy.asarray(
        3.0 * np.random.default_rng(5).normal(size=(8, 512)), dtype)
    temps = np.asarray(temps, np.float32)
    rng = jax.random.PRNGKey(11)
    for _ in range(3):
        got, got_rng = engine.sample_tokens(rng, logits, temps)
        want, want_rng = _eager_sample(rng, logits, temps)
        assert isinstance(got, np.ndarray)
        assert got.dtype == np.int32 and got.shape == (8,)
        np.testing.assert_array_equal(got, np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got_rng),
                                      np.asarray(want_rng))
        if not (temps > 0).any():
            assert got_rng is rng
        rng = got_rng


def test_sample_tokens_scalar_temperature_and_one_program_per_path():
    """A scalar temperature applies to every row; over 20 steps at one
    shape each path compiles once."""
    from repro.serving import engine
    greedy, sampled = engine._programs()
    greedy.clear_cache()
    sampled.clear_cache()
    logits = jax.numpy.asarray(
        np.random.default_rng(6).normal(size=(4, 64)), jax.numpy.float32)
    rng = jax.random.PRNGKey(3)
    for _ in range(20):
        tok, same = engine.sample_tokens(rng, logits, 0.0)
        assert same is rng
        np.testing.assert_array_equal(tok, np.argmax(np.asarray(logits), -1))
        got, rng2 = engine.sample_tokens(rng, logits, 0.9)
        want, want_rng = _eager_sample(rng, logits, np.full(4, 0.9))
        np.testing.assert_array_equal(got, np.asarray(want))
        np.testing.assert_array_equal(np.asarray(rng2), np.asarray(want_rng))
        rng = rng2
    assert greedy._cache_size() == 1
    assert sampled._cache_size() == 1


def _watched_run(monkeypatch, sched, reqs):
    """Run `sched` with `sample_tokens` wrapped as the chip benchmark's
    chat runner wraps it: each call's result is handed back behind an
    object that notes every row the scheduler indexes."""
    from repro.serving import engine
    real = engine.sample_tokens
    calls = []

    class Read:
        def __init__(self, tokens):
            self.tokens, self.rows = tokens, []

        def __getitem__(self, i):
            self.rows.append(i)
            return self.tokens[i]

    def watched(rng, logits, temps):
        tokens, rng = real(rng, logits, temps)
        read = Read(tokens)
        calls.append(read)
        return read, rng

    monkeypatch.setattr(engine, "sample_tokens", watched)
    rep = sched.run(reqs)
    monkeypatch.setattr(engine, "sample_tokens", real)
    return rep, calls


def test_scheduler_keeps_the_benchmark_watch_contract(gqa_model,
                                                      monkeypatch):
    """The scheduler calls `engine.sample_tokens` through the module once
    per step, gets a host array back, and indexes each emitting row once:
    the rows read are the tokens served.  Same seed, same completions."""
    cfg, model, params = gqa_model
    reqs = _reqs(prompts=[3, 6, 2, 5, 4, 7], max_new=[5, 3, 6, 4, 2, 5],
                 arrivals=[0.0, 0.0, 0.001, 0.002, 0.004, 0.006],
                 temps=[0.0, 0.7, 0.7, 0.0, 0.7, 0.0])
    runs = []
    for _ in range(2):
        sched = ContinuousScheduler(
            cfg, model, params,
            config=SchedulerConfig(max_batch=3, max_len=32, seed=9))
        rep, calls = _watched_run(monkeypatch, sched, reqs)
        assert len(calls) == rep.steps
        for c in calls:
            assert isinstance(c.tokens, np.ndarray)
            assert c.tokens.dtype == np.int32
            assert c.tokens.shape == (3,)
            assert len(set(c.rows)) == len(c.rows)
        assert sum(len(c.rows) for c in calls) == rep.total_tokens
        served = sorted(t for comp in rep.completions for t in comp.tokens)
        read = sorted(int(c.tokens[i]) for c in calls for i in c.rows)
        assert served == read
        runs.append({c.rid: c.tokens for c in rep.completions})
    assert sorted(runs[0]) == [r.rid for r in reqs]
    assert runs[0] == runs[1]


# --------------------------------------------- fixed-batch engine repairs

def test_mixed_length_padded_batch_matches_alone(gqa_model):
    """A short prompt left-padded behind a long one must decode exactly
    as it would alone (the pad-aware start mask + relative RoPE phase)."""
    cfg, model, params = gqa_model
    reqs = _reqs(prompts=[3, 10], max_new=5)
    batched = ServingEngine(cfg, model, params, max_batch=2,
                            max_len=32).run(reqs)
    for r, c in zip(reqs, batched):
        solo = ServingEngine(cfg, model, params, max_batch=1,
                             max_len=32).run([r])[0]
        assert c.tokens == solo.tokens, f"request {r.rid} diverged"


def test_engine_decode_step_accounting(gqa_model):
    """The decode loop pays exactly max(max_new) - 1 steps — a batch of
    short requests must not pay for the engine-level budget, and an
    all-single-token batch pays zero decode steps."""
    cfg, model, params = gqa_model
    engine = ServingEngine(cfg, model, params, max_batch=4, max_len=32)
    engine.run(_reqs(prompts=[4, 3, 2, 5], max_new=[1, 4, 1, 1]))
    assert engine.last_batch_decode_steps == 3
    engine.run(_reqs(prompts=[4, 3], max_new=[1, 1]))
    assert engine.last_batch_decode_steps == 0


def test_engine_windowed_drift_and_alias(gqa_model):
    cfg, model, params = gqa_model
    engine = ServingEngine(cfg, model, params)
    assert engine.drift is None
    assert engine.drift_latest_vs_first is None
    # a single noisy FIRST run must not poison the windowed trigger...
    engine._fidelity_log = [5.0] + [0.1] * 8
    assert abs(engine.drift) < 0.05
    # ...but the legacy alias keeps the raw two-point comparison
    assert engine.drift_latest_vs_first == pytest.approx(-4.9)
    # genuine sustained drift is visible on the window
    engine._fidelity_log = [0.1] * 6 + [0.8] * 4
    assert engine.drift == pytest.approx(0.7)


# ------------------------------------------------------------ traffic gen

def test_poisson_requests_deterministic():
    a = poisson_requests(40, rate=100.0, vocab_size=64, seed=3)
    b = poisson_requests(40, rate=100.0, vocab_size=64, seed=3)
    assert len(a) == 40
    for x, y in zip(a, b):
        assert x.arrival_s == y.arrival_s
        np.testing.assert_array_equal(x.prompt, y.prompt)
        assert x.max_new_tokens == y.max_new_tokens
    arrivals = [r.arrival_s for r in a]
    assert arrivals == sorted(arrivals)
    mean_gap = arrivals[-1] / len(arrivals)
    assert 0.25 / 100.0 < mean_gap < 4.0 / 100.0
    c = poisson_requests(40, rate=100.0, vocab_size=64, seed=4)
    assert [r.arrival_s for r in c] != arrivals


# -------------------------------------------------------------- portfolio

def test_portfolio_select_save_load_tamper(gqa_model, mux_predictors,
                                           plan_cache_dir, tmp_path):
    pf = _portfolio(gqa_model, mux_predictors, plan_cache_dir,
                    buckets=((1, 32), (2, 32)))
    b, compiled = pf.select(1, 16)
    assert (b.batch, b.seq) == (1, 32)         # smallest covering bucket
    assert compiled.plan.provenance.bucket == "b1s32"
    b2, _ = pf.select(2, 32)
    assert (b2.batch, b2.seq) == (2, 32)
    b3, _ = pf.select(4, 64)                    # nothing covers: largest
    assert (b3.batch, b3.seq) == (2, 32)
    keys = {c.key for c in pf.entries.values()}
    assert len(keys) == 2                       # bucket tag splits digests

    path = pf.save(tmp_path / "portfolio.json")
    loaded = repro.PlanPortfolio.load(path)
    assert [bk.tag for bk in loaded.buckets] == [bk.tag for bk in pf.buckets]
    assert {c.key for c in loaded.entries.values()} == keys
    assert pf.can_replan() and not loaded.can_replan()

    doc = json.loads(path.read_text())
    doc["model"] = "tampered"
    path.write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="checksum mismatch"):
        repro.PlanPortfolio.load(path)


def test_bucket_provenance_is_byte_compatible():
    """An unbucketed provenance must keep its pre-PR-8 digest and JSON
    shape — existing on-disk plan caches stay warm."""
    base = PlanProvenance(device="moto2022", threads=3, mechanism="spin",
                          step=8, seed=0, network_fingerprint="f" * 8,
                          predictor_checksum="p" * 8)
    assert "bucket" not in base.to_json()
    assert dataclasses.replace(base, bucket="").key == base.key
    tagged = dataclasses.replace(base, bucket="b2s32")
    assert tagged.key != base.key
    assert tagged.to_json()["bucket"] == "b2s32"
    assert PlanProvenance.from_json(tagged.to_json()) == tagged


# ------------------------------------------------------------- calibrator

def test_calibrator_compose_matches_sequential_application():
    inner = Calibrator({("linear", "*"): AffineCorrection(1.1, 0.2, 4)})
    outer = Calibrator({("linear", "*"): AffineCorrection(0.9, -0.1, 3),
                        ("conv", "*"): AffineCorrection(1.0, 0.5, 2)})
    composed = outer.compose(inner)
    for pred in (3.0, 120.0, 9e4):
        twice = outer.correct_us("linear", "*",
                                 inner.correct_us("linear", "*", pred))
        once = composed.correct_us("linear", "*", pred)
        np.testing.assert_allclose(once, twice, rtol=1e-12)
    # keys present on only one side compose against the identity
    np.testing.assert_allclose(
        composed.correct_us("conv", "*", 10.0),
        outer.correct_us("conv", "*", 10.0), rtol=1e-12)
    assert outer.compose(None) is outer


def test_affine_fit_gated_on_prediction_spread():
    """Clustered log-predictions make the affine slope unidentifiable —
    the fit must fall back to a pure shift instead of extrapolating."""
    logp = np.log(np.array([100.0, 101.0, 102.0, 103.0]))
    logw = np.log(np.array([180.0, 250.0, 140.0, 210.0]))
    assert float(np.ptp(logp)) < MIN_AFFINE_SPREAD
    corr = _fit_group(logp, logw)
    assert corr.a == 1.0
    spread = np.log(np.array([10.0, 100.0, 1000.0, 10000.0]))
    wall = 2.0 * spread + 0.3
    assert _fit_group(spread, wall).a == pytest.approx(2.0, abs=1e-6)


# --------------------------------------------------- serving acceptance

def test_scheduler_beats_fixed_batch_reference(gqa_model, mux_predictors,
                                               plan_cache_dir):
    """Acceptance: at the same arrival rate the portfolio scheduler wins
    BOTH p99 latency and tokens/s against the fixed-batch reference
    served by the single largest plan."""
    cfg, model, params = gqa_model
    pf = _portfolio(gqa_model, mux_predictors, plan_cache_dir,
                    buckets=((1, 32), (2, 32), (4, 32)))
    _, largest = pf.select(4, 32)
    cost = largest.plan.end_to_end_us * 1e-6
    # rate chosen from the plan's own step cost: past the fixed-batch
    # engine's capacity (padded prefill + head-of-line blocking) but
    # under the scheduler's
    rate = 0.33 / cost
    reqs = poisson_requests(200, rate=rate, vocab_size=cfg.vocab_size,
                            prompt_lens=(2, 4, 12), max_new=(2, 4),
                            temperatures=(0.0,), seed=11)
    sched = ContinuousScheduler(
        cfg, model, params, portfolio=pf,
        config=SchedulerConfig(max_batch=4, max_len=32,
                               fidelity_every=10**9))
    srep = sched.run(reqs)
    frep = FixedBatchReference(largest, max_batch=4).run(reqs)
    assert srep.bucket_switches > 0
    assert len(srep.bucket_steps) >= 2
    assert srep.latency_p(99) < frep.latency_p(99)
    assert srep.tokens_per_s > frep.tokens_per_s


def test_throttle_triggers_validated_replan(gqa_model, mux_predictors,
                                            plan_cache_dir, tmp_path):
    """Acceptance: a mid-run simulated throttle drives the bucket's
    windowed drift over threshold, the scheduler replans in place, and
    the committed plan's fidelity error is lower than the trailing
    pre-replan window."""
    cfg, model, params = gqa_model
    pf = _portfolio(gqa_model, mux_predictors, plan_cache_dir,
                    buckets=((2, 32),))
    bucket = pf.buckets[0]
    old_key = pf.entries[bucket].key
    cost = pf.entries[bucket].plan.end_to_end_us * 1e-6
    rate = 0.1 / cost
    reqs = poisson_requests(48, rate=rate, vocab_size=cfg.vocab_size,
                            prompt_lens=(2, 4, 12), max_new=(2, 4),
                            temperatures=(0.0,), seed=23)
    sched = ContinuousScheduler(
        cfg, model, params, portfolio=pf,
        measurement_store=tmp_path / "measurements",
        plan_cache=plan_cache_dir,
        config=SchedulerConfig(max_batch=2, max_len=32, fidelity_every=4,
                               fidelity_window=4, drift_cooldown=2),
        throttle=ThrottleSim(at_s=100 * cost, scale=2.5))
    rep = sched.run(reqs)
    assert rep.replan_events, "throttle never triggered a replan"
    ev = rep.replan_events[0]
    assert ev.post_fidelity is not None
    assert ev.post_fidelity < ev.pre_fidelity
    assert ev.new_key != ev.old_key
    # the portfolio now serves the repaired, calibrated plan
    new = pf.entries[bucket]
    assert new.key != old_key
    assert new.plan.provenance.calibration != ""
    assert rep.to_json()["replan_events"][0]["bucket"] == bucket.tag
