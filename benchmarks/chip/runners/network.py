"""Closed loop over one planned network: batch 1, back to back.

The program plans the network (`repro.compile`) for the configuration's
phone target and the mix's mesh, and the window calls
`CompiledNetwork.run(x, fused=True, use_pallas=True, dtype=...)` on one
input made from the seed, one inference after the other, each ended by the
executor's own `block_until_ready`.  The executor draws its weights from
the seed; the reference draws the same weights from the configuration's
stated recipe (`reference.seeded_weights`) and compares the last output of
the window with its float32 result.
"""
from __future__ import annotations

import contextlib
import gc
import time
from pathlib import Path

import numpy as np

import counts
import reference
from programs import model_config
from traffic import rng_for

#: plans and the predictors they were planned with, kept inside the
#: checkout at a fixed path, so that only a cell's first run there plans
PLAN_CACHE = Path(__file__).resolve().parents[3] / ".bench_cache"


def context(cell):
    """The configuration's matrix precision holds for the whole run."""
    precision = cell["config"].get("matmul_precision")
    if not precision:
        return contextlib.nullcontext()
    import jax
    return jax.default_matmul_precision(precision)


def layers_of(cell) -> list:
    """The network's layers as the configuration file states them."""
    cfg = cell["config"]
    if cfg["kind"] == "network":
        return cfg["layers"]
    return reference.block_graph_layers(cfg, cell["traffic"]["cache_len"])


def ops_of(cell) -> list:
    cfg = cell["config"]
    if cfg["kind"] == "network":
        return counts.chain_ops(cfg["layers"], counts.config_dtype(cfg))
    return counts.decoder_block_graph_ops(cfg, cell["traffic"]["cache_len"],
                                          counts.config_dtype(cfg))


def program_layers(compiled) -> list:
    """The planned graph's weighted layers, in the terms of the files."""
    out = []
    for node in compiled.graph:
        op = node.op
        if node.kind == "conv":
            out.append({"kind": "conv", "h": op.H_in, "w": op.W_in,
                        "c_in": op.C_in, "c_out": op.C_out, "k": op.K,
                        "s": op.S})
        elif node.kind == "linear":
            out.append({"kind": "linear", "rows": op.L, "c_in": op.C_in,
                        "c_out": op.C_out})
        elif node.kind == "attention":
            out.append({"kind": "attention", "heads": op.H,
                        "kv_heads": op.KV, "head_dim": op.hd,
                        "positions": op.S})
        else:
            out.append({"kind": node.kind})
    return out


def _same_network(mine: list, theirs: list) -> bool:
    keys = ("kind", "h", "w", "c_in", "c_out", "k", "s", "rows", "heads",
            "kv_heads", "head_dim", "positions")
    strip = [[{k: v for k, v in d.items() if k in keys and k != "inputs"}
              for d in ls if d["kind"] not in ("pool", "add")]
             for ls in (mine, theirs)]
    return strip[0] == strip[1]


def input_shape(cell) -> tuple:
    cfg = cell["config"]
    if cfg["kind"] == "network":
        first = cfg["layers"][0]
        return (1, first["h"], first["w"], first["c_in"])
    return (1, cfg["hidden_size"])


def setup(cell, seed: int, seconds: float, devices):
    import jax
    import jax.numpy as jnp

    import repro
    from repro.graph import from_model
    from repro.runtime.cache import PlanCache

    cfg, mix = cell["config"], cell["traffic"]
    dtype = counts.config_dtype(cfg)
    t0 = time.perf_counter()
    if cfg["kind"] == "network":
        network = cfg["network"]
    else:
        network = from_model(model_config(cfg),
                             blocks=cfg["num_hidden_layers"],
                             cache_len=mix["cache_len"])
    target = repro.Target(device=cfg["target"]["device"],
                          threads=cfg["target"]["threads"],
                          mesh=mix["mesh"])
    # planning is deterministic from the seedless target, so a plan is
    # made once and read back by every later run
    compiled = repro.compile(network, target,
                             cache=PlanCache(PLAN_CACHE / "plans"),
                             predictor_cache=PLAN_CACHE / "predictors",
                             samples=cfg["planner"]["samples"],
                             estimators=cfg["planner"]["estimators"])
    t_plan = time.perf_counter()
    if not _same_network(layers_of(cell), program_layers(compiled)):
        raise RuntimeError("the program's network differs from the "
                           "configuration file's layers")
    key = int(rng_for(seed, "input").integers(0, 2**31 - 1))
    x = jax.jit(lambda k: jax.random.normal(k, input_shape(cell),
                                            jnp.float32).astype(dtype))(
        jax.random.PRNGKey(key))
    kw = {"fused": mix["fused"], "use_pallas": mix["use_pallas"],
          "dtype": dtype, "seed": seed}
    jax.block_until_ready(compiled.run(x, **kw))   # weights, compiles
    t_first = time.perf_counter()
    jax.block_until_ready(compiled.run(x, **kw))   # one steady run
    return {"cell": cell, "compiled": compiled, "x": x, "kw": kw,
            "seed": seed, "dtype": dtype,
            "setup_phases": {"plan": t_plan - t0,
                             "first_run": t_first - t_plan,
                             "second_run": time.perf_counter() - t_first},
            "mesh_groups": 2 if compiled.executor(
                dtype=dtype, seed=seed,
                use_pallas=mix["use_pallas"]).split_capable else 1}


def window(state, seconds: float) -> dict:
    compiled, x, kw = state["compiled"], state["x"], state["kw"]
    n = 0
    t0 = time.perf_counter()
    while True:
        y = compiled.run(x, **kw)
        n += 1
        if time.perf_counter() - t0 >= seconds:
            break
    wall = time.perf_counter() - t0
    report = compiled.last_report
    state["y"] = y
    return {"attempted": n, "failed": 0, "n": n, "wall_s": wall,
            "sync_points": report.sync_points,
            "mesh_groups": state["mesh_groups"],
            "ops": ops_of(state["cell"])}


def control_mode(cfg: dict) -> str:
    """The step below the configuration's precision: three-pass products
    for float32 at "highest", float8 operands for bfloat16."""
    if counts.config_dtype(cfg) == "float32":
        return "bf16x3"
    return "fp8"


def check(state, raw, control: bool = False) -> dict:
    """Free the program's state, then compare with the reference; with
    `control`, also compare the reference computed a step below the
    configuration's precision (`<name>.control`)."""
    import jax
    import jax.numpy as jnp

    y = np.asarray(jax.device_get(state.pop("y")).astype(jnp.float32))
    x = np.asarray(jax.device_get(state.pop("x")).astype(jnp.float32))
    state.pop("compiled")
    gc.collect()
    layers = layers_of(state["cell"])
    weights = reference.seeded_weights(layers, state["seed"])
    if control:
        weights = list(weights)
    ref = reference.graph_forward(layers, weights, x, "fp32", state["dtype"])
    out = {"rel_err": reference.rel_err(y, ref)}
    if control:
        low = reference.graph_forward(layers, weights, x,
                                      control_mode(state["cell"]["config"]),
                                      state["dtype"])
        out["rel_err.control"] = reference.rel_err(low, ref)
    return out
