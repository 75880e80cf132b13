"""CPU checks of the `deepseek_v2_lite.chat` cell's own parts.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

The plain reference (`reference_mla_moe`) against the program's forward on
the weights the cell's runner makes; a whole tiny run of the cell, whose
served tokens lie on the reference's best logit, and which fails when a
served token is altered; the float8 control against the cell's limit;
the counts by hand at the configuration's sizes; and the spec's checks
with the cell in it.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE / "runners"))
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import counts            # noqa: E402
import counts_mla_moe    # noqa: E402
import harness           # noqa: E402
import reference         # noqa: E402
import reference_mla_moe  # noqa: E402
import serve_mla_moe     # noqa: E402
import spec_checks       # noqa: E402

CELL = "deepseek_v2_lite.chat"
SPEC = harness.load_json(ROOT / "BENCHMARK.json")
TINY_MLA_MOE = {"hidden_size": 64, "num_attention_heads": 4,
                "num_key_value_heads": 4, "kv_lora_rank": 32,
                "qk_rope_head_dim": 16, "qk_nope_head_dim": 16,
                "v_head_dim": 16, "intermediate_size": 128,
                "moe_intermediate_size": 32, "n_routed_experts": 8,
                "num_hidden_layers": 3, "vocab_size": 256}
TINY_CHAT = {"max_batch": 8, "max_len": 128, "rate_per_s": 20.0,
             "prompt_len": {"median": 8, "sigma": 0.8, "min": 4, "max": 32},
             "output_len": {"median": 8, "sigma": 0.8, "min": 4, "max": 32}}
SEED = 2**33 + 4242           # larger than 32 bits, as a run may be given


def tiny_cell(dtype: str = "float32") -> dict:
    cell = harness.resolve(SPEC, CELL)
    cell["config"].update(TINY_MLA_MOE, torch_dtype=dtype)
    cell["traffic"].update(TINY_CHAT)
    return cell


@pytest.fixture
def cpu_peak(monkeypatch):
    import jax
    kind = jax.devices()[0].device_kind
    monkeypatch.setitem(counts.PEAKS, kind, counts.PEAKS["TPU v5 lite"])


def run_tiny(cell, seconds=1.0):
    import jax

    import run
    return run.run_cell(cell, SEED, seconds, False, jax.devices())


def test_reference_matches_program_forward():
    """The reference against the program's own full-sequence forward, in
    float32, on the weights the runner makes."""
    import jax
    import jax.numpy as jnp

    from repro.models import build_model
    cfg = tiny_cell()["config"]
    params = serve_mla_moe.make_weights(cfg, SEED)
    model = build_model(serve_mla_moe.model_config(cfg))
    toks = np.random.default_rng(0).integers(1, 256, (2, 12))
    with jax.default_matmul_precision("highest"):
        want, _ = model.forward(params, jnp.asarray(toks))
    got = reference_mla_moe.DeepseekV2(cfg).logits(
        toks, serve_mla_moe.as_reference(params))
    assert reference.rel_err(want, got) < 1e-5


def test_served_tokens_match_the_reference(cpu_peak):
    """Greedy tokens served through the scheduler, the latent cache and the
    dropless experts, in float32, lie on the reference's best logit; the
    window's counts follow the program's expert counters."""
    res = run_tiny(tiny_cell())
    assert res["failed"] == 0
    assert res["checks"]["logit_gap"]["value"] < 1e-4
    assert res["correct"]
    assert {"ttft_p90_ms", "itl_p95_ms", "tokens_per_s"} <= set(res["metrics"])


def test_altered_token_is_not_correct(cpu_peak, monkeypatch):
    """One served token altered where it is sampled."""
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
    assert not run_tiny(tiny_cell())["correct"]


def test_control_fails_the_limit():
    """The reference with float8 operands, put in the program's place,
    reads above the cell's limit; the bfloat16 program reads below it."""
    import jax

    import control
    cell = tiny_cell("bfloat16")
    row = control.readings(cell, SEED, 0.5, jax.devices())
    lim = harness.load_json(cell["limits_file"])["logit_gap"]["limit"]
    assert row["logit_gap"] <= lim < row["logit_gap.control"]


def test_counts_by_hand():
    """At the configuration's sizes: 10.36 GB of bfloat16 weights, and the
    routed experts 89% of the matmul weights a step reads when every
    expert is hit."""
    cfg = harness.load_json(HERE / "configs" / "deepseek_v2_lite.json")
    d, v, f, e = 2048, 102400, 1408, 64
    attn = (d * 16 * 192 + d * 576 + 512 * 16 * 256 + 16 * 128 * d)
    assert attn == 13_762_560
    dense = attn + 3 * d * 10944
    moe = attn + (e + 2) * 3 * d * f + d * e
    assert (dense, moe) == (81_002_496, 584_843_264)
    norms = 9 * (2 * d + 512) + d
    bf16 = 2 * (2 * v * d + dense + 8 * moe + norms) + 2 * 8 * d * e
    assert counts_mla_moe.weight_bytes(cfg, "bfloat16") == bf16
    assert abs(bf16 / 1e9 - 10.36) < 0.005
    routed = 2 * 8 * e * 3 * d * f
    assert abs(routed / 1e9 - 8.86) < 0.005
    every = counts_mla_moe.step_fixed_bytes(cfg, "bfloat16") + routed
    assert abs(every / 1e9 - 9.94) < 0.005
    assert round(100 * routed / every) == 89
    # one slot-step of a two-token request, 43 experts hit
    tot = counts_mla_moe.serve_totals(cfg, "bfloat16", [(1, 2)], 2, 43)
    active = 9 * attn + 3 * d * 10944 + 8 * (8 * 3 * d * f + d * e) + d * v
    assert tot["flops"] == 2 * 2 * active + 9 * 2 * 16 * 1088 * 3
    assert tot["bytes"] == (2 * counts_mla_moe.step_fixed_bytes(
        cfg, "bfloat16") + 43 * 2 * 3 * d * f + 9 * 2 * 576 * (3 + 2))
    g = counts_mla_moe.gmm_totals(cfg, "bfloat16", 43, 72)
    assert g == {"flops": 2 * 3 * d * f * 72.0,
                 "bytes": 2 * (43 * 3 * d * f + 72 * 3 * (d + f)) * 1.0}


def _gmm_trace(calls_per_run, kept_in_second):
    """Two decode-program runs of `calls_per_run` grouped-matmul calls of
    0.5 ms each: the trace keeps every call of the first run and the
    first `kept_in_second` of the second."""
    import tracing
    ops, mods = [("%fusion.1", 0.0, 6e7)], []
    for r, kept in enumerate((calls_per_run, kept_in_second)):
        t0 = 3e7 * r
        mods.append(("jit_decode_step(1)", t0, t0 + 1e6 * calls_per_run))
        ops += [(f"%gmm.{10 + i % 3}", t0 + 1e6 * i, t0 + 1e6 * i + 5e5)
                for i in range(kept)]
    return tracing.Trace({"/device:TPU:0": ops},
                         {"/device:TPU:0": mods}, [])


def test_gmm_readers_by_hand():
    """Two steps of 24 grouped-matmul calls of 0.5 ms, of which the trace
    kept 30: the readers scale the kept events' mean to the calls made,
    and take the calls a step makes from the trace."""
    import tracing
    cfg = harness.load_json(HERE / "configs" / "deepseek_v2_lite.json")
    raw = {"steps": 2, "gmm_totals": {"flops": 0.0, "bytes": 0.0123 * 819e9}}
    ctx = {"trace": _gmm_trace(24, 6), "window": (0.0, 6e7), "raw": raw,
           "config": cfg, "peak": counts.PEAKS["TPU v5 lite"]}
    read = {name: harness.load_module(HERE / "metrics" / f"{name}.py").read
            for name in ("moe_gmm_ms.serve", "moe_gmm_roofline")}
    assert read["moe_gmm_ms.serve"](ctx) == pytest.approx(12.0)
    assert read["moe_gmm_roofline"](ctx) == pytest.approx(
        100 * 12.3 / 24.0)
    # a program that fuses gate and up makes 16 calls a step, not 24
    ctx["trace"] = _gmm_trace(16, 16)
    assert read["moe_gmm_ms.serve"](ctx) == pytest.approx(8.0)
    assert read["moe_gmm_roofline"](ctx) == pytest.approx(
        100 * 12.3 / 16.0)
    # no kernel events, or no program runs to hold them
    full = _gmm_trace(24, 24)
    for tr in (tracing.Trace({"/device:TPU:0": full.ops["/device:TPU:0"][:1]},
                             full.modules, []),
               tracing.Trace(full.ops, {}, [])):
        ctx["trace"] = tr
        assert all(r(ctx) is None for r in read.values())


def test_spec_checks_pass_with_the_cell():
    spec_checks.check_spec(SPEC, HERE)
    cell = harness.resolve(SPEC, CELL)
    names = {m["name"] for m in cell["metrics"]["per_layer"]}
    assert {"moe_gmm_roofline", "moe_gmm_ms.serve", "mfu.serve",
            "decode_step_roofline", "occupancy.serve"} <= names
    assert cell["config"]["num_hidden_layers"] == 9
    assert json.dumps(cell["config"]["rope_scaling"], sort_keys=True) == \
        json.dumps({"beta_fast": 32, "beta_slow": 1, "factor": 40,
                    "mscale": 0.707, "mscale_all_dim": 0.707,
                    "original_max_position_embeddings": 4096,
                    "type": "yarn"}, sort_keys=True)


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "linear", "factor": 40.0}),
    ("topk_method", "group_limited_greedy"),
    ("q_lora_rank", 1536)])
def test_settings_the_program_does_not_model_are_refused(key, value):
    """A configuration stating a setting the program does not model (a
    rope scaling other than YaRN, another routing, a query low-rank
    projection) describes another model, and the runner refuses it."""
    cfg = dict(harness.resolve(SPEC, CELL)["config"])
    serve_mla_moe.model_config(cfg)
    cfg[key] = dict(cfg[key], **value) if isinstance(value, dict) else value
    with pytest.raises(ValueError, match=key.split("_")[0]):
        serve_mla_moe.model_config(cfg)
