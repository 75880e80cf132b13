"""CPU checks of the on-chip benchmark's own parts.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

The trace reduction, the peak table and the counts from shapes; the plain
references against the program at small sizes; the controls against each
cell's limit; how cells are found by name; the traffic generator; and
whole runs with the timed path broken underneath, which must come out as
not correct.  Nothing here needs a chip: runs are driven through
`run.run_cell` with the harness's look for a TPU skipped.
"""
from __future__ import annotations

import json
import math
import os
import shutil
import statistics
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import counts      # noqa: E402
import harness     # noqa: E402
import reference   # noqa: E402
import spec_checks  # noqa: E402
import traffic     # noqa: E402
import tracing     # noqa: E402

SPEC = harness.load_json(ROOT / "BENCHMARK.json")
TINY_DECODER = {"hidden_size": 64, "num_attention_heads": 4,
                "num_key_value_heads": 2, "intermediate_size": 128,
                "num_hidden_layers": 2, "vocab_size": 256}
TINY_CHAT = {"max_batch": 8, "max_len": 128, "rate_per_s": 20.0,
             "prompt_len": {"median": 8, "sigma": 0.8, "min": 4, "max": 32},
             "output_len": {"median": 8, "sigma": 0.8, "min": 4, "max": 32}}
SEED = 2**33 + 12345          # larger than 32 bits, as a run may be given


def tiny_cell(workload: str, config=None, mix=None) -> dict:
    """A cell resolved from the files, cut to a size the CPU holds, with
    the kernels as their XLA oracles (Pallas needs the chip)."""
    cell = harness.resolve(SPEC, workload)
    cell["config"].update(config or {})
    cell["traffic"].update(mix or {})
    if cell["traffic"]["runner"] == "network":
        cell["traffic"]["use_pallas"] = False
    return cell


@pytest.fixture
def cpu_peak(monkeypatch):
    import jax
    kind = jax.devices()[0].device_kind
    monkeypatch.setitem(counts.PEAKS, kind, counts.PEAKS["TPU v5 lite"])


def run_tiny(cell, seconds=1.0, trace=False):
    import jax

    import run
    return run.run_cell(cell, SEED, seconds, trace, jax.devices())


# ------------------------------------------------- trace, peaks, counts

def test_interval_arithmetic():
    busy = tracing.merge([(5, 8), (0, 2), (1, 3), (7, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert tracing.covered(busy) == 7
    assert tracing.gaps(busy, 0, 12) == [(3, 5), (9, 12)]
    assert tracing.clip(busy, 2, 6) == [(2, 3), (5, 6)]


def synthetic_trace():
    ops = {"/device:TPU:0": [
        ("%fusion.1 = f32[8]", 10, 20),
        ("%split_matmul_op.1 = custom-call() tpu_custom_call", 30, 50),
        ("%all-gather.2 = f32[8]", 60, 64),
        ("%fusion.2 = f32[8]", 80, 90)]}
    modules = {"/device:TPU:0": [("jit_program(1)", 10, 20),
                                 ("jit_split_matmul_op(2)", 28, 52),
                                 ("jit_program(3)", 60, 90)]}
    host = [[("bench.window", 0, 100), ("run", 5, 95),
             ("dispatch", 21, 29), ("wait", 65, 79)]]
    return tracing.Trace(ops, modules, host)


def test_trace_reduction_by_hand():
    tr = synthetic_trace()
    win = tr.window()
    assert win == (0, 100)
    assert tr.busy_ns(win) == 10 + 20 + 4 + 10
    assert tr.kernel_ns("split_matmul_op", "tpu_custom_call", win) == 20
    assert tr.kernel_ns("program", "tpu_custom_call", win) == 0
    assert tr.collective_ns(win) == 4
    assert tr.module_time_ns("program", win) == (40, 2)
    top = dict(tr.top_ops(win))
    assert top["%split_matmul_op.1 = custom-call() tpu_custom_call"] == 20e-9
    idle = dict(tr.idle_by_host(win))
    # gaps 0-10, 20-30, 50-60, 64-80, 90-100: midpoints 5 (run), 25
    # (dispatch), 55 (run), 72 (wait), 95 (run, which ends there)
    assert idle == pytest.approx({"run": 30e-9, "dispatch": 10e-9,
                                  "wait": 16e-9})
    s = tracing.summarize(tr)
    assert s["busy_s"] == pytest.approx(44e-9)
    assert s["window_s"] == pytest.approx(100e-9)


def test_trace_recorded_on_cpu(tmp_path):
    """A real profiler file: the window span is found on the host thread
    that opened it, and a CPU trace holds no device plane."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = tracing.Trace.from_file(tracing.find_xplane(str(tmp_path)))
    lo, hi = tr.window()
    assert hi > lo
    assert any(n == "bench.step" and lo <= s and e <= hi
               for n, s, e in tr.host)
    assert tr.devices == []
    assert tr.busy_ns((lo, hi)) == 0.0


def test_peak_table():
    assert counts.peak("TPU v5 lite")["bf16_flops"] == 197e12
    assert counts.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peak("TPU v9 imaginary")


def test_counts_by_hand():
    # 3x3 conv, 56x56x64 -> 64, stride 1: 2 * 56*56*64 * 3*3*64 operations
    (conv,) = counts.chain_ops([{"kind": "conv", "h": 56, "w": 56,
                                 "c_in": 64, "c_out": 64, "k": 3, "s": 1}],
                               "float32")
    assert conv["flops"] == 2 * 56 * 56 * 64 * 9 * 64 == 231211008
    assert conv["bytes"] == 4 * (56 * 56 * 64 * 2 + 9 * 64 * 64)
    # batch-1 projection 4096 -> 13440 in bfloat16
    assert counts.matmul_flops(1, 4096, 13440) == 110100480
    assert counts.matmul_bytes(1, 4096, 13440, 2) == 2 * (
        4096 + 4096 * 13440 + 13440)
    # decode attention, 32 heads of 128 over 4096 positions, bfloat16
    assert counts.decode_attention_flops(32, 128, 4096) == 67108864
    assert counts.decode_attention_bytes(32, 32, 128, 4096, 2) == 2 * (
        2 * 4096 * 32 * 128 + 2 * 32 * 128)
    # the roofline time of that attention is bound by its bytes
    op = {"flops": 67108864, "bytes": counts.decode_attention_bytes(
        32, 32, 128, 4096, 2)}
    assert counts.roofline_s(op, counts.peak("TPU v5 lite")) == \
        pytest.approx(op["bytes"] / 819e9)


def test_serve_totals_by_hand():
    cfg = dict(json.load(open(HERE / "configs" / "codeqwen15_7b.json")))
    tot = counts.decoder_serve_totals(cfg, "bfloat16", [(3, 2)], steps=4)
    # one request holds its slot for 3 + 2 - 1 = 4 steps, at positions
    # 0..3, so it attends over 1 + 2 + 3 + 4 = 10 positions in all
    assert tot["slot_steps"] == 4
    layers, d = 8, 4096
    assert tot["flops"] == 2 * counts.decoder_matmul_params(cfg) * 4 + \
        layers * 4 * 32 * 128 * 10
    # q and o at 32 heads of 128, k and v at 4 KV heads of 128
    assert counts.decoder_matmul_params(cfg) == layers * (
        2 * d * d + 2 * d * 4 * 128 + 3 * d * 13440) + d * 92416


# ------------------------------------------------------------ references

def test_resnet18_reference_matches_program(cpu_peak):
    """The reference, from the file's layers and the stated weight
    recipe, against the program's executor on resnet18 itself."""
    res = run_tiny(tiny_cell("resnet18.b1"), seconds=0.2)
    assert res["checks"]["rel_err"]["value"] < 1e-5


def test_block_graph_reference_matches_program(cpu_peak):
    cfg = dict(TINY_DECODER, torch_dtype="float32")
    res = run_tiny(tiny_cell("codeqwen15_7b.blocks.b1", cfg,
                             {"cache_len": 64}), seconds=0.2)
    assert res["checks"]["rel_err"]["value"] < 1e-5


def test_qwen2_reference_matches_program_forward():
    """The reference against the program's own full-sequence forward, in
    float32, on the weights this benchmark makes."""
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, str(HERE / "runners"))
    import serve
    from repro.models import build_model
    cfg = dict(json.load(open(HERE / "configs" / "codeqwen15_7b.json")))
    cfg.update(TINY_DECODER, torch_dtype="float32")
    params = serve.make_weights(cfg, SEED)
    model = build_model(serve.model_config(cfg))
    toks = np.random.default_rng(0).integers(1, 256, (2, 12))
    with jax.default_matmul_precision("highest"):
        want, _ = model.forward(params, jnp.asarray(toks))
    got = reference.Qwen2(cfg).logits(toks, serve.as_reference(params, cfg))
    assert reference.rel_err(want, got) < 1e-5


def test_chat_reference_matches_served_tokens(cpu_peak):
    """Greedy tokens served through the program's KV cache, in float32,
    lie on the reference's best logit."""
    cfg = dict(TINY_DECODER, torch_dtype="float32")
    res = run_tiny(tiny_cell("codeqwen15_7b.chat", cfg, TINY_CHAT))
    assert res["failed"] == 0
    assert res["checks"]["logit_gap"]["value"] < 1e-4


@pytest.mark.parametrize("workload,config,mix", [
    ("resnet18.b1", {}, {}),
    ("codeqwen15_7b.blocks.b1", TINY_DECODER, {"cache_len": 64}),
    ("codeqwen15_7b.chat", TINY_DECODER, TINY_CHAT),
    ("vgg16.b1", {}, {}),
])
def test_control_fails_the_limit(workload, config, mix):
    """The reference a step below the stated precision, put in the
    program's place, reads above the cell's limit."""
    import jax
    import control
    cell = tiny_cell(workload, config, mix)
    row = control.readings(cell, SEED, 0.5, jax.devices())
    limits = harness.load_json(cell["limits_file"])
    for name, lim in limits.items():
        assert row[name] <= lim["limit"]
        assert row[f"{name}.control"] > lim["limit"]


def test_lower_precision_output_fails(cpu_peak):
    """resnet18 run in bfloat16 where its file states float32 fails."""
    cell = tiny_cell("resnet18.b1")
    cell["config"]["dtype"] = "bfloat16"
    res = run_tiny(cell, seconds=0.2)
    assert res["checks"]["rel_err"]["value"] > \
        harness.load_json(cell["limits_file"])["rel_err"]["limit"]
    assert not res["correct"]


# ------------------------------------------------- discovery, generators

def test_every_cell_resolves():
    """Every declared cell resolves to its files, reports `setup_s` and
    another end-to-end metric, and carries the per-layer metrics that its
    end-to-end metrics call for; every accepted cell is still there."""
    spec_checks.check_spec(SPEC, HERE)


def test_a_cell_added_as_files_is_found(tmp_path):
    root = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, root, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    (root / "traffic" / "b1_dummy.json").write_text(json.dumps(
        dict(harness.load_json(HERE / "traffic" / "b1.json"), mesh="single")))
    (root / "metrics" / "dummy_count.infer.py").write_text(
        "def read(ctx):\n    return 1\n")
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "resnet18.dummy", "config": "resnet18",
                              "traffic": "b1_dummy", "chips": 1, "why": "x"})
    spec["per_layer"].append({
        "name": "dummy_count.infer", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "executor",
        "moves": "infer_ms", "workloads": ["resnet18.dummy"]})
    spec["end_to_end"][1]["workloads"].append("resnet18.dummy")
    cell = harness.resolve(spec, "resnet18.dummy", root)
    assert cell["traffic"]["mesh"] == "single"
    assert [m["name"] for m in cell["metrics"]["per_layer"]] == \
        ["dummy_count.infer"]
    assert harness.read_metrics(cell["metrics"]["per_layer"], {}, root) == \
        {"dummy_count.infer": {"value": 1.0, "unit": "count"}}
    with pytest.raises(harness.CellError):
        harness.resolve(spec, "resnet18.nowhere", root)


def test_a_serve_cell_added_as_files_passes_the_checks(tmp_path):
    """What adding a decoder served on the chat path takes: a
    configuration, a traffic mix, a limits file and a metric reader as new
    files, and entries appended to the spec.  No file that is there
    changes, and the spec's checks pass; they fail where the new cell is
    left out of a metric that its end-to-end metrics call for."""
    root = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, root, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    new = {
        "configs/tiny_decoder.json": dict(harness.load_json(
            HERE / "configs" / "codeqwen15_7b.json"),
            name="tiny_decoder", **TINY_DECODER),
        "traffic/chat_slow.json": dict(harness.load_json(
            HERE / "traffic" / "chat.json"), rate_per_s=4.0),
        "limits/tiny_decoder.chat_slow.json": {"logit_gap": {"limit": 0.1}},
    }
    for rel, doc in new.items():
        (root / rel).write_text(json.dumps(doc))
    (root / "metrics" / "route_ms.moe.py").write_text(
        "def read(ctx):\n    return None\n")

    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({
        "name": "tiny_decoder", "source": "https://example.org/tiny",
        "file": "benchmarks/chip/configs/tiny_decoder.json",
        "reduced": [], "why": "x"})
    cell = "tiny_decoder.chat_slow"
    spec["workloads"].append({"name": cell, "config": "tiny_decoder",
                              "traffic": "chat_slow", "chips": 1,
                              "why": "x"})
    # the new cell reports what a served cell reports
    itl = {m["name"]: m for m in spec["end_to_end"]}["itl_p95_ms"]
    served = itl["workloads"][0]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if served in m.get("workloads", ()):
            m["workloads"].append(cell)
    spec["per_layer"].append({
        "name": "route_ms.moe", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "model step",
        "moves": "itl_p95_ms", "workloads": [cell]})

    spec_checks.check_spec(spec, root)
    after = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    assert {p: after[p] for p in before} == before
    assert sorted(str(p.relative_to(root)) for p in set(after) - set(before)) \
        == sorted([*new, "metrics/route_ms.moe.py"])
    got = harness.resolve(spec, cell, root)
    assert "route_ms.moe" in {m["name"] for m in got["metrics"]["per_layer"]}

    read_ms = {m["name"]: m for m in spec["per_layer"]}["read_ms.serve"]
    read_ms["workloads"].remove(cell)
    with pytest.raises(AssertionError, match="read_ms.serve"):
        spec_checks.check_spec(spec, root)


def test_open_loop_generator():
    mix = harness.load_json(HERE / "traffic" / "chat.json")
    a = traffic.open_loop(mix, 30.0, SEED, 92416)
    b = traffic.open_loop(mix, 30.0, SEED, 92416)
    c = traffic.open_loop(mix, 30.0, SEED + 1, 92416)
    key = lambda rs: [(r.arrival_s, r.prompt.tolist(), r.max_new_tokens,  # noqa: E731
                       r.temperature) for r in rs]
    assert key(a) == key(b)
    assert key(a) != key(c)
    assert len(a) == round(mix["rate_per_s"] * 30)
    # the stated medians, and the same work for every seed
    assert statistics.median(len(r.prompt) for r in a) == \
        mix["prompt_len"]["median"]
    assert statistics.median(r.max_new_tokens for r in a) == \
        mix["output_len"]["median"]
    assert sorted(len(r.prompt) for r in a) == \
        sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in c)
    # each seed orders the sets anew, and pairs prompts with outputs anew
    assert sorted((len(r.prompt), r.max_new_tokens) for r in a) != \
        sorted((len(r.prompt), r.max_new_tokens) for r in c)
    assert a[-1].arrival_s == pytest.approx(c[-1].arrival_s)
    assert a[-1].arrival_s == pytest.approx(30.0, rel=0.1)
    assert all(mix["prompt_len"]["min"] <= len(r.prompt)
               <= mix["prompt_len"]["max"] for r in a)


def test_token_times_by_hand():
    """Rows 0 and 1 give tokens in steps 1-3; row 1 ends and row 2 starts
    in step 3; step 4 lies past the window's 2.5 s."""
    serve = harness.load_module(HERE / "runners" / "serve.py")
    steps = [(1.0, [0, 1]), (1.5, [0, 1]), (2.5, [0, 2]), (3.0, [0, 2])]
    got = serve.token_times(steps, 0.0, 2.5)
    assert got["window_tokens"] == 6
    assert got["itl_s"] == [0.5, 0.5, 1.0, 0.5, 0.5]


# --------------------------------------------- the timed path, broken

@pytest.mark.parametrize("workload,config,mix", [
    ("resnet18.b1", {}, {}),
    ("codeqwen15_7b.blocks.b1", TINY_DECODER, {"cache_len": 64}),
    ("vgg16.b1", {}, {}),
])
def test_altered_answer_is_not_correct(cpu_peak, monkeypatch, workload,
                                       config, mix):
    """An inference's answer altered where it is produced."""
    from repro.api import CompiledNetwork
    real = CompiledNetwork.run

    def altered(self, *a, **kw):
        y = real(self, *a, **kw)
        return y.at[0, 0].add(0.1 * (abs(y).max() + 1.0))

    monkeypatch.setattr(CompiledNetwork, "run", altered)
    res = run_tiny(tiny_cell(workload, config, mix), seconds=0.2)
    assert not res["correct"]


def test_altered_token_is_not_correct(cpu_peak, monkeypatch):
    """One served token altered where it is sampled."""
    import jax.numpy as jnp

    from repro.serving import engine
    real = engine.sample_tokens
    calls = {"n": 0}

    def altered(rng, logits, temps):
        tok, rng = real(rng, logits, temps)
        calls["n"] += 1
        if calls["n"] % 5 == 0:
            tok = (tok + 1) % logits.shape[-1]
        return tok, rng

    monkeypatch.setattr(engine, "sample_tokens", altered)
    cfg = dict(TINY_DECODER, torch_dtype="float32")
    res = run_tiny(tiny_cell("codeqwen15_7b.chat", cfg, TINY_CHAT))
    assert not res["correct"]


SPLIT_FAULT = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax, jax.numpy as jnp
import counts, harness, run
from repro.runtime import segments
counts.PEAKS[jax.devices()[0].device_kind] = counts.PEAKS["TPU v5 lite"]
cell = harness.resolve(harness.load_json(sys.argv[3]), "resnet18.b1.split")
cell["traffic"]["use_pallas"] = False
if sys.argv[4] == "broken":
    # the exchange between the chip groups left out: each gather keeps
    # the fast group's channels and zeros where the slow group's go
    def local_only(y, plan, mesh):
        fast = y[0][..., :plan.c_fast]
        return jnp.concatenate([fast, jnp.zeros_like(y[1][..., :plan.c_slow])], -1)
    segments.gather_stacked_traced = local_only
res = run.run_cell(cell, 2**33 + 5, 0.2, False, jax.devices())
print(json.dumps({"correct": res["correct"], "split": res["attempted"],
                  "rel_err": res["checks"]["rel_err"]["value"]}))
"""


@pytest.mark.parametrize("mode", ["sound", "broken"])
def test_split_exchange_left_out_is_not_correct(mode):
    """resnet18 on two groups of two virtual devices: sound, it is
    correct; with the gathers between the groups left out, it is not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", SPLIT_FAULT, str(HERE), str(ROOT / "src"),
         str(ROOT / "BENCHMARK.json"), mode],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is (mode == "sound")
    assert math.isfinite(res["rel_err"])
