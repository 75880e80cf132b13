"""Open loop into the program's continuous scheduler.

The mix's requests (`traffic.open_loop`: Poisson arrivals over the window,
lognormal prompt and output lengths) are handed to
`ContinuousScheduler.run` on the wall clock.  The scheduler never sleeps:
it admits each request at its due time on its own clock, which advances by
the wall time of each step and skips ahead only when every slot is free,
so time to first token is measured from the request's due time.
Requests still in flight when the arrivals end are drained and counted.

The scheduler stamps only a request's first and last token, so the window
watches each step from outside: it hands the scheduler `sample_tokens`
wrapped to note the host time after each step's sampling (the time the
scheduler itself stamps tokens with) and which rows the scheduler reads a
token from.  A row that gives a token in two steps in a row gives both to
one request (a request admitted to a freed row gives no token in its
first step unless its prompt is a single token, and the mixes' prompts
are four or more), so every gap between two tokens of a request is one
step's time.  Tokens given within `seconds` of the run's start are the
window's; the drain after it serves the rest.

The weights are made here, on the device in one jitted call from the
seed, in the layout the program's `model.init` gives and in the type the
configuration serves.  After the window, a sample of the finished greedy
requests, drawn from the seed and holding the longest, is run through the
plain float32 reference (`reference.Qwen2`) over prompt and served tokens;
the number compared is the widest gap by which a served token's reference
logit lies below the reference's best at its position.
"""
from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

import counts
import reference
from programs import model_config
import traffic
from traffic import rng_for


def context(cell):
    return contextlib.nullcontext()


def _leaves(cfg: dict):
    """(path, shape, law) of every weight, in the program's layout: one
    stacked block per layer position, leading axis the layer."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, n = d // h, cfg["num_hidden_layers"]
    out = [(("embed",), (v, d), ("normal", 1.0)),
           (("unembed",), (d, v), ("normal", d ** -0.5)),
           (("ln_f",), (d,), ("scale", 0.1))]
    blk = [(("ln1",), (n, d), ("scale", 0.1)),
           (("ln2",), (n, d), ("scale", 0.1)),
           (("attn", "wq"), (n, d, h * hd), ("normal", d ** -0.5)),
           (("attn", "wk"), (n, d, kv * hd), ("normal", d ** -0.5)),
           (("attn", "wv"), (n, d, kv * hd), ("normal", d ** -0.5)),
           (("attn", "wo"), (n, h * hd, d), ("normal", (h * hd) ** -0.5)),
           (("attn", "bq"), (n, h * hd), ("normal", 0.1)),
           (("attn", "bk"), (n, kv * hd), ("normal", 0.1)),
           (("attn", "bv"), (n, kv * hd), ("normal", 0.1)),
           (("ffn", "w_gate"), (n, d, f), ("normal", d ** -0.5)),
           (("ffn", "w_up"), (n, d, f), ("normal", d ** -0.5)),
           (("ffn", "w_down"), (n, f, d), ("normal", f ** -0.5))]
    out += [(("pattern", 0) + p, s, law) for p, s, law in blk]
    return out


def make_weights(cfg: dict, seed: int):
    """All weights on the device, in one jitted call, in the served type."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(cfg["torch_dtype"])
    leaves = _leaves(cfg)

    def build(key):
        tree = {"prologue": [], "pattern": [{}]}
        for i, (path, shape, (law, scale)) in enumerate(leaves):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            val = (1.0 + scale * z) if law == "scale" else scale * z
            node = tree
            for p in path[:-1]:
                node = node[p] if isinstance(p, int) else \
                    node.setdefault(p, {})
            node[path[-1]] = val.astype(dtype)
        return tree

    key = jax.random.PRNGKey(int(rng_for(seed, "weights").integers(2**31)))
    return jax.block_until_ready(jax.jit(build)(key))


def reference_weights(params, layer: int) -> dict:
    blk = params["pattern"][0]
    return {"ln1": blk["ln1"][layer], "ln2": blk["ln2"][layer],
            **{k: blk["attn"][k][layer] for k in
               ("wq", "wk", "wv", "wo", "bq", "bk", "bv")},
            **{k: blk["ffn"][k][layer] for k in
               ("w_gate", "w_up", "w_down")}}


def as_reference(params, cfg: dict) -> dict:
    return {"embed": params["embed"], "unembed": params["unembed"],
            "ln_f": params["ln_f"],
            "layers": [reference_weights(params, i)
                       for i in range(cfg["num_hidden_layers"])]}


class _Read:
    """One step's sampled tokens; notes each row the scheduler reads."""

    __slots__ = ("tokens", "rows")

    def __init__(self, tokens):
        self.tokens, self.rows = tokens, []

    def __getitem__(self, i):
        self.rows.append(i)
        return self.tokens[i]


def token_times(steps, t0: float, seconds: float) -> dict:
    """From (host time, rows read) per step: every gap between consecutive
    tokens of a request, and the tokens given within `seconds` of `t0`."""
    itl, in_window, prev_t, prev_rows = [], 0, None, set()
    for t, rows in steps:
        rows = set(rows)
        if prev_t is not None:
            itl += [t - prev_t] * len(rows & prev_rows)
        if t - t0 <= seconds:
            in_window += len(rows)
        prev_t, prev_rows = t, rows
    return {"itl_s": itl, "window_tokens": in_window}


def program_requests(reqs):
    from repro.serving.engine import Request
    return [Request(rid=r.rid, prompt=r.prompt,
                    max_new_tokens=r.max_new_tokens,
                    temperature=r.temperature, arrival_s=r.arrival_s)
            for r in reqs]


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
    totals = counts.decoder_serve_totals(
        cfg, cfg["torch_dtype"],
        [(len(r.prompt), r.max_new_tokens) for r in reqs], report.steps)
    return {"attempted": len(reqs), "failed": failed, "wall_s": wall,
            "steps": report.steps, "tokens": report.total_tokens,
            "ttft_s": [s.ttft_s for s in report.stats],
            **token_times(steps, t0, seconds), "window_s": seconds,
            "max_batch": state["cell"]["traffic"]["max_batch"],
            "totals": totals}


def sample(reqs, done: dict, k: int, seed: int) -> list:
    """Up to `k` finished greedy requests drawn from the seed, the longest
    (prompt and output) among them."""
    greedy = [r for r in reqs if r.temperature == 0.0 and r.rid in done]
    if not greedy:
        return []
    longest = max(greedy, key=lambda r: (len(r.prompt) + r.max_new_tokens,
                                         -r.rid))
    rest = [r for r in greedy if r is not longest]
    rng = rng_for(seed, "check")
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def gaps(state, chosen, done: dict, control: bool = False,
         rows: int = 4):
    """Reference logit gaps of every served token of `chosen`; with
    `control`, also the gap of the token that the reference computed with
    float8 operands puts first at each of those positions."""
    import jax.numpy as jnp

    cfg = state["cfg"]
    weights = as_reference(state["params"], cfg)
    models = [reference.Qwen2(cfg, "fp32")]
    if control:
        models.append(reference.Qwen2(cfg, "fp8"))
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
