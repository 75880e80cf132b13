"""Open loop into the program's continuous scheduler, for a DeepSeek-V2
decoder (latent attention, routed and shared experts).

The traffic, the scheduler's run on the wall clock and the watch of each
step's tokens are those of `runners/serve.py`, whose `token_times`,
`_Read`, `sample` and `program_requests` this runner uses.  What differs
is the model: the configuration file's config.json keys become the
program's `ModelConfig` (MLA, YaRN rope, dropless top-k routing without
renormalised gates), the weights are made in that model's layout, the
window's operations and bytes come from `counts_mla_moe` with the experts
that the program counted as hit, and the check runs the plain reference
`reference_mla_moe.DeepseekV2`.

The weights are made on the device in one jitted call from the seed, in
the layout the program's `model.init` gives and in the type the
configuration serves (the router in float32, as the program holds it).
After the window, a sample of the finished greedy requests, drawn from
the seed and holding the longest, is run through the float32 reference
over prompt and served tokens, one layer at a time; the number compared
is the widest gap by which a served token's reference logit lies below
the reference's best at its position.
"""
from __future__ import annotations

import contextlib
import gc
import sys
import time
from pathlib import Path

import numpy as np

import counts_mla_moe
import reference
import traffic
from reference_mla_moe import DeepseekV2
from traffic import rng_for

sys.path.insert(0, str(Path(__file__).resolve().parent))
from serve import _Read, program_requests, sample, token_times  # noqa: E402

#: settings of the published config.json that the program does not model
#: as options; a file that states others describes another model
FIXED = {"q_lora_rank": None, "scoring_func": "softmax",
         "topk_method": "greedy", "routed_scaling_factor": 1,
         "moe_layer_freq": 1, "n_group": 1, "topk_group": 1,
         "hidden_act": "silu", "attention_bias": False,
         "tie_word_embeddings": False}


def context(cell):
    return contextlib.nullcontext()


def model_config(cfg: dict):
    """The program's `ModelConfig` for a DeepSeek-V2 configuration file."""
    from repro.models.config import ModelConfig, RopeScaling
    for key, want in FIXED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{cfg['name']}: {key} = {cfg[key]!r}; the "
                             f"program serves {want!r} only")
    rs = cfg["rope_scaling"]
    if rs.get("type") != "yarn":
        raise ValueError(f"{cfg['name']}: rope_scaling type = "
                         f"{rs.get('type')!r}; the program serves 'yarn' only")
    return ModelConfig(
        name=cfg["name"], family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        attn_kind="mla", kv_lora_rank=cfg["kv_lora_rank"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        n_experts=cfg["n_routed_experts"],
        n_shared_experts=cfg["n_shared_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        first_dense_layers=cfg["first_k_dense_replace"],
        norm_topk_prob=cfg["norm_topk_prob"],
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=RopeScaling(
            factor=float(rs["factor"]),
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        norm_eps=cfg["rms_norm_eps"], dtype=cfg["torch_dtype"])


def _block(cfg: dict, moe: bool):
    """(path, shape, law) of one layer's weights."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, nd = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rd, vd = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    out = [(("ln1",), (d,), ("scale", 0.1)),
           (("ln2",), (d,), ("scale", 0.1)),
           (("attn", "wq"), (d, h * (nd + rd)), ("normal", d ** -0.5)),
           (("attn", "w_dkv"), (d, r), ("normal", d ** -0.5)),
           (("attn", "w_krope"), (d, rd), ("normal", d ** -0.5)),
           (("attn", "w_uk"), (r, h, nd), ("normal", r ** -0.5)),
           (("attn", "w_uv"), (r, h, vd), ("normal", r ** -0.5)),
           (("attn", "wo"), (h * vd, d), ("normal", (h * vd) ** -0.5)),
           (("attn", "kv_norm"), (r,), ("scale", 0.1))]
    if not moe:
        f = cfg["intermediate_size"]
        return out + [(("ffn", "w_gate"), (d, f), ("normal", d ** -0.5)),
                      (("ffn", "w_up"), (d, f), ("normal", d ** -0.5)),
                      (("ffn", "w_down"), (f, d), ("normal", f ** -0.5))]
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    return out + [
        (("ffn", "router"), (d, e), ("router", d ** -0.5)),
        (("ffn", "w_gate"), (e, d, f), ("normal", d ** -0.5)),
        (("ffn", "w_up"), (e, d, f), ("normal", d ** -0.5)),
        (("ffn", "w_down"), (e, f, d), ("normal", f ** -0.5)),
        (("ffn", "shared", "w_gate"), (d, fs), ("normal", d ** -0.5)),
        (("ffn", "shared", "w_up"), (d, fs), ("normal", d ** -0.5)),
        (("ffn", "shared", "w_down"), (fs, d), ("normal", fs ** -0.5))]


def make_weights(cfg: dict, seed: int):
    """All weights on the device, in one jitted call, in the served type
    (the router in float32).  Layers stacked in the program's pattern are
    drawn one layer at a time, so no float32 copy of a stack exists."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(cfg["torch_dtype"])
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    dense = cfg["first_k_dense_replace"]
    stacked = cfg["num_hidden_layers"] - dense

    def draw(key, shape, law):
        kind, scale = law
        z = jax.random.normal(key, shape, jnp.float32)
        if kind == "scale":
            return (1.0 + scale * z).astype(dtype)
        return (scale * z).astype(jnp.float32 if kind == "router" else dtype)

    def tree(items, key, layers=None):
        out = {}
        for i, (path, shape, law) in enumerate(items):
            k = jax.random.fold_in(key, i)
            if layers is None:
                val = draw(k, shape, law)
            else:
                val = jax.lax.map(
                    lambda j, k=k, shape=shape, law=law: draw(
                        jax.random.fold_in(k, j), shape, law),
                    jnp.arange(layers))
            node = out
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = val
        return out

    def build(key):
        top = tree([(("embed",), (v, d), ("normal", 1.0)),
                    (("unembed",), (d, v), ("normal", d ** -0.5)),
                    (("ln_f",), (d,), ("scale", 0.1))],
                   jax.random.fold_in(key, 0))
        top["prologue"] = [tree(_block(cfg, False),
                                jax.random.fold_in(key, 1 + i))
                           for i in range(dense)]
        top["pattern"] = [tree(_block(cfg, True),
                               jax.random.fold_in(key, 1 + dense), stacked)]
        return top

    key = jax.random.PRNGKey(int(rng_for(seed, "weights").integers(2**31)))
    return jax.block_until_ready(jax.jit(build)(key))


class Layers:
    """The program's parameters as the reference's layers, each sliced out
    of its stack when the reference comes to it."""

    def __init__(self, params):
        self.params = params

    @staticmethod
    def _layer(blk: dict) -> dict:
        return {"ln1": blk["ln1"], "ln2": blk["ln2"], "attn": blk["attn"],
                "ffn": blk["ffn"]}

    def __iter__(self):
        import jax
        yield from (self._layer(b) for b in self.params["prologue"])
        stack = self.params["pattern"][0]
        for i in range(stack["ln1"].shape[0]):
            yield self._layer(jax.tree.map(lambda a, i=i: a[i], stack))


def as_reference(params) -> dict:
    return {"embed": params["embed"], "unembed": params["unembed"],
            "ln_f": params["ln_f"], "layers": Layers(params)}


def setup(cell, seed: int, seconds: float, devices):
    import jax

    from repro.models import build_model
    from repro.serving import ContinuousScheduler, SchedulerConfig

    cfg, mix = cell["config"], cell["traffic"]
    t0 = time.perf_counter()
    mcfg = model_config(cfg)
    model = build_model(mcfg)
    params = make_weights(cfg, seed)
    t_weights = time.perf_counter()
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the program's weight layout differs from the "
                           "one this benchmark makes")
    sched = ContinuousScheduler(mcfg, model, params, config=SchedulerConfig(
        max_batch=mix["max_batch"], max_len=mix["max_len"], clock="wall",
        seed=int(rng_for(seed, "sampling").integers(2**31))))
    # warm-up: the decode step and both sampling paths (all greedy, and
    # some rows sampled) at the window's one shape
    warm = traffic.open_loop(dict(mix, rate_per_s=1000.0), 0.004, seed + 1,
                             cfg["vocab_size"])
    for i, r in enumerate(warm):
        r.prompt, r.max_new_tokens = r.prompt[:4], 4
        r.temperature = 0.7 if i % 2 else 0.0
    sched.run(program_requests(warm))
    reqs = traffic.open_loop(mix, seconds, seed, cfg["vocab_size"])
    return {"cell": cell, "cfg": cfg, "params": params, "sched": sched,
            "reqs": reqs, "seed": seed,
            "setup_phases": {"weights": t_weights - t0,
                             "warm_up": time.perf_counter() - t_weights}}


def window(state, seconds: float) -> dict:
    from repro.serving import engine

    reqs = state["reqs"]
    real = engine.sample_tokens
    steps = []

    def watched(rng, logits, temps):
        tokens, rng = real(rng, logits, temps)
        read = _Read(tokens)
        steps.append((time.perf_counter(), read.rows))
        return read, rng

    engine.sample_tokens = watched
    try:
        t0 = time.perf_counter()
        report = state["sched"].run(program_requests(reqs))
        wall = time.perf_counter() - t0
    finally:
        engine.sample_tokens = real
    state["report"] = report
    done = {c.rid: c.tokens for c in report.completions}
    failed = sum(1 for r in reqs
                 if len(done.get(r.rid, ())) != r.max_new_tokens)
    cfg = state["cfg"]
    hit, rows = sum(report.moe_experts_hit), sum(report.moe_rows)
    return {"attempted": len(reqs), "failed": failed, "wall_s": wall,
            "steps": report.steps, "tokens": report.total_tokens,
            "ttft_s": [s.ttft_s for s in report.stats],
            **token_times(steps, t0, seconds), "window_s": seconds,
            "max_batch": state["cell"]["traffic"]["max_batch"],
            "moe_experts_hit": hit, "moe_rows": rows,
            "totals": counts_mla_moe.serve_totals(
                cfg, cfg["torch_dtype"],
                [(len(r.prompt), r.max_new_tokens) for r in reqs],
                report.steps, hit),
            "gmm_totals": counts_mla_moe.gmm_totals(
                cfg, cfg["torch_dtype"], hit, rows)}


def gaps(state, chosen, done: dict, control: bool = False,
         rows: int = 4):
    """Reference logit gaps of every served token of `chosen`; with
    `control`, also the gap of the token that the reference computed with
    float8 operands puts first at each of those positions."""
    import jax.numpy as jnp

    cfg = state["cfg"]
    weights = as_reference(state["params"])
    models = [DeepseekV2(cfg, "fp32")]
    if control:
        models.append(DeepseekV2(cfg, "fp8"))
    seqs = [(np.concatenate([r.prompt, np.asarray(done[r.rid][:-1],
                                                  np.int32)]),
             len(r.prompt), np.asarray(done[r.rid], np.int32))
            for r in chosen]
    # one length for every seed (the mix's longest request fits in it), so
    # the reference compiles once and its program is found in the cache
    t = state["cell"]["traffic"]["max_len"]
    served_out, control_out = [], []
    for i in range(0, len(seqs), rows):
        part = seqs[i:i + rows]
        toks = np.zeros((rows, t), np.int32)
        served = np.zeros((rows, t), np.int32)
        mask = np.zeros((rows, t), bool)
        for j, (s, p, o) in enumerate(part):
            toks[j, :len(s)] = s
            served[j, p - 1:p - 1 + len(o)] = o
            mask[j, p - 1:p - 1 + len(o)] = True
        logits = models[0].logits(jnp.asarray(toks), weights)
        g = reference.served_gaps(logits, jnp.asarray(served))
        served_out.append(np.asarray(g)[mask])
        if control:
            low = models[1].logits(jnp.asarray(toks), weights)
            g = reference.served_gaps(logits, jnp.argmax(low, -1))
            control_out.append(np.asarray(g)[mask])
            del low
        del logits
    if control:
        return np.concatenate(served_out), np.concatenate(control_out)
    return np.concatenate(served_out)


def check(state, raw, control: bool = False) -> dict:
    report = state.pop("report")
    state.pop("sched")
    gc.collect()
    done = {c.rid: c.tokens for c in report.completions}
    chosen = sample(state["reqs"], done, state["cell"]["traffic"]
                    ["check_requests"], state["seed"])
    if not chosen:
        return {"logit_gap": float("inf")}
    g = gaps(state, chosen, done, control)
    if control:
        g, low = g
    out = {"logit_gap": float(g.max())}
    if control:
        out["logit_gap.control"] = float(low.max())
    return out
