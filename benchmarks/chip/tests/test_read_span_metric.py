"""CPU checks of `read_ms.serve`, the reader of the scheduler's
`repro.sched.read` spans (the step's one read of its tokens to the host).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

On a hand-built trace, where the number is worked out by hand; on traces
of a program that records no such span (it must read None); against its
declaration in `BENCHMARK.json`; and through a whole traced run of a
tiny chat cell.
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
import spec_checks  # noqa: E402
import tracing     # noqa: E402

SPEC = harness.load_json(ROOT / "BENCHMARK.json")
NAME = "read_ms.serve"
SEED = 2**33 + 54321


def reader():
    return harness.load_module(HERE / "metrics" / f"{NAME}.py")


def ctx_of(tr) -> dict:
    return {"trace": tr, "window": tr.window() if tr else None, "raw": {}}


def step_trace(reads: bool = True) -> tracing.Trace:
    """Two steps in the window [0, 100] and one after it; times in ns.

    step 1 [10, 40]: sample [19, 25] holding read [21, 25]; step 2
    [50, 80]: sample [59, 75] holding read [60, 75].  The step at
    [110, 120] lies outside the window and does not count.
    """
    host = [("bench.window", 0, 100)]
    for t, read in ((10, (21, 25)), (50, (60, 75)), (110, (112, 115))):
        host += [("repro.sched.step", t, t + 10 if t > 100 else t + 30),
                 ("repro.sched.inputs", t + 2, t + 4),
                 ("repro.sched.decode", t + 4, t + 9),
                 ("repro.sched.sample", t + 9, read[1])]
        if reads:
            host.append(("repro.sched.read", *read))
        host.append(("repro.sched.emit", read[1], read[1] + 1))
    return tracing.Trace({"/device:TPU:0": [("%while.1", 5, 95)]}, {},
                         [host])


def test_read_ms_by_hand():
    # reads 4 + 15 = 19 ns over two steps
    assert reader().read(ctx_of(step_trace())) == pytest.approx(9.5e-6)


def test_read_ms_reads_nothing_without_read_spans():
    """A program older than the read span (steps, no reads) reads None,
    not zero; so does a run without a trace."""
    assert reader().read(ctx_of(step_trace(reads=False))) is None
    assert reader().read(ctx_of(None)) is None


def test_read_ms_is_declared_for_the_chat_cell():
    """Declared as it was (unit, direction, source, layer, what it moves),
    and carried by exactly the cells that report `itl_p95_ms`."""
    spec_checks.check_spec(SPEC, HERE)
    assert spec_checks.DECLARED[NAME] == \
        ("ms", "lower", "program_span", "scheduler", "itl_p95_ms")
    assert NAME in spec_checks.CARRIES["itl_p95_ms"]


@pytest.fixture
def cpu_peak(monkeypatch):
    import jax

    import counts
    kind = jax.devices()[0].device_kind
    monkeypatch.setitem(counts.PEAKS, kind, counts.PEAKS["TPU v5 lite"])


def test_traced_tiny_chat_run_reports_read_ms(cpu_peak):
    """A whole traced run of a tiny chat cell, as `run.py --trace 1`
    makes it: the read is inside the sample span, so it reads no more
    than `sample_ms.serve`."""
    import jax

    import run
    cell = harness.resolve(SPEC, "codeqwen15_7b.chat")
    cell["config"].update({
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 128,
        "num_hidden_layers": 2, "vocab_size": 256})
    cell["traffic"].update({
        "max_batch": 8, "max_len": 128, "rate_per_s": 20.0,
        "prompt_len": {"median": 8, "sigma": 0.8, "min": 4, "max": 32},
        "output_len": {"median": 8, "sigma": 0.8, "min": 4, "max": 32}})
    res = run.run_cell(cell, SEED, 0.3, True, jax.devices())
    got = res["metrics"]
    assert got[NAME]["unit"] == "ms"
    assert 0.0 < got[NAME]["value"] <= got["sample_ms.serve"]["value"]
    assert res["correct"]
