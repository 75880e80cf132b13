"""Mixture-of-Experts layer: dropless grouped-matmul routing, and a
capacity-based scatter dispatch for shard-local sharded runs.

The layer is dropless: the (row, choice) pairs are sorted by expert and
each expert's rows are multiplied by its weights in one grouped matmul
(`grouped_matmul`: the Pallas `megablox.gmm` kernel when lowered for a
TPU, which skips experts no row chose; `jax.lax.ragged_dot` elsewhere and
under an activation mesh, where the partitioner places it).  No token is
ever dropped, whatever the routing.

With cfg.moe_local_dispatch under a mesh, expert-parallel execution is
the MoE analogue of the paper's co-execution: output "channels" (here:
experts) are partitioned across compute groups, and the dispatch uses a
per-expert capacity buffer written by scatter and read back by gather
(GShard/Switch), kept local to each data shard; pairs beyond an expert's
capacity are dropped there.

Gates are the router's softmax over all experts, top-k; they are
renormalised to sum 1 only when the config's `norm_topk_prob` says so.
Aux losses: switch load-balance loss + router z-loss (returned to the
training loop).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.layers import init_mlp, mlp
from repro.sharding.ctx import batch_axes, constrain, current_mesh

Params = Dict[str, jax.Array]

#: the routed experts' weights, stacked (E, ...) in a layer's params
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def init_moe(rng, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    d, e, ff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(rng, 5)
    s_in, s_out = float(1.0 / np.sqrt(d)), float(1.0 / np.sqrt(ff))
    p: Params = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * s_in,
        "w_gate": jax.random.normal(ks[1], (e, d, ff), dtype) * s_in,
        "w_up": jax.random.normal(ks[2], (e, d, ff), dtype) * s_in,
        "w_down": jax.random.normal(ks[3], (e, ff, d), dtype) * s_out,
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], d,
                               cfg.moe_d_ff * cfg.n_shared_experts, dtype)
    return p


def route(p: Params, xt: jax.Array, cfg: ModelConfig):
    """Router in float32: (logits (N,E), probs (N,E), gates (N,k),
    experts (N,k))."""
    logits = jnp.matmul(xt.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return logits, probs, gates, experts


def _aux_loss(logits, probs, experts, e: int) -> jax.Array:
    """Switch load-balance loss + router z-loss."""
    me = probs.mean(0)                                        # (E,)
    ce = jax.nn.one_hot(experts, e, dtype=jnp.float32).sum(1).mean(0)
    return e * jnp.sum(me * ce) + 1e-3 * jnp.mean(
        jnp.square(jax.nn.logsumexp(logits, axis=-1)))


def _groups(experts, valid, e: int):
    """The expert of each (row, choice) pair, flat (N*k,), with `e` (no
    expert) for the pairs of rows that are not `valid`; and how many
    pairs each expert receives (E,)."""
    flat = experts.reshape(-1)                       # pair j is row j // k
    if valid is not None:
        flat = jnp.where(jnp.repeat(valid, experts.shape[-1]), flat, e)
    sizes = jnp.zeros((e,), jnp.int32).at[flat].add(1, mode="drop")
    return flat, sizes


def _counts(sizes: jax.Array) -> jax.Array:
    """int32 (2,): experts that received a pair, and the pairs routed."""
    return jnp.stack([(sizes > 0).sum(), sizes.sum()]).astype(jnp.int32)


def moe_layer(p: Params, x: jax.Array, cfg: ModelConfig,
              capacity_factor: float = 1.25, valid=None, layer=None
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, T, D) -> (y, aux_loss, counts).

    With `layer`, the routed experts' weights (`EXPERT_WEIGHTS`) are
    stacks over layers, of which this layer's are `[layer]`.

    `valid` (B,) marks the rows that carry a request (None: all); the
    others are routed to no expert and give zeros.  `counts` (int32, 2)
    holds the experts that the valid rows chose and the (row, choice)
    pairs they routed.

    With cfg.moe_local_dispatch under a mesh, the whole dispatch+expert+
    combine runs under a partial-manual shard_map over the batch axes, so
    the scatter into the capacity buffer stays shard-local with a
    per-shard capacity slice (EXPERIMENTS.md §Perf B2); expert weights
    remain on the auto model axis.  The scatter form moves O(N*k*D) bytes,
    where the earlier GShard-style dense (N, E, C) one-hot einsum made the
    llama4-scout prefill_32k dry-run collective-bound (§Perf B).
    """
    b, t, d = x.shape
    n = b * t
    xt = x.reshape(n, d)
    valid_t = None if valid is None else jnp.repeat(valid, t)
    mesh = current_mesh()
    if mesh is not None and cfg.moe_local_dispatch:
        if layer is not None:
            p = dict(p, **{w: p[w][layer] for w in EXPERT_WEIGHTS})
        y, aux, counts = _moe_local(p, xt, cfg, mesh, capacity_factor,
                                    valid_t)
    else:
        y, aux, counts = _moe_dropless(
            p, xt, cfg, valid_t, impl=None if mesh is None else "ragged_dot",
            layer=layer)
    return y.reshape(b, t, d), aux, counts


def _moe_local(p: Params, xt: jax.Array, cfg: ModelConfig, mesh,
               capacity_factor: float, valid
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The capacity dispatch, shard-local over the batch axes where the
    rows divide among the shards, else over all rows."""
    n = xt.shape[0]
    e, k = cfg.n_experts, cfg.experts_per_token
    valid = jnp.ones((n,), bool) if valid is None else valid
    from jax.sharding import PartitionSpec as P
    axes = tuple(a for a in batch_axes() if a in mesh.shape)
    shards = 1
    for a in axes:
        shards *= mesh.shape[a]
    if shards > 1 and n % shards == 0 and (n // shards) * k >= e:
        cap_local = max(1, int(capacity_factor * (n // shards) * k / e))

        def local(xt_l, valid_l):
            y_l, aux_l, experts_l = _moe_core(p, xt_l, cfg, cap_local)
            return y_l, aux_l[None], _groups(experts_l, valid_l, e)[1][None]

        y, aux, sizes = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(axes), P(axes)),
            out_specs=(P(axes), P(axes), P(axes)),
            axis_names=set(axes), check_vma=False)(xt, valid)
        return y, aux.mean(), _counts(sizes.sum(0))

    capacity = max(1, int(capacity_factor * n * k / e))
    y, aux, experts = _moe_core(p, xt, cfg, capacity)
    return y, aux, _counts(_groups(experts, valid, e)[1])


# ------------------------------------------------------- grouped matmul
#: elements of one weight tile of the grouped matmul (bf16: 3 MiB, twice
#: over for double buffering), an untuned budget
_GMM_TILE_ELEMS = 1536 * 1024


def _tile_choices(dim: int):
    """Tile sizes the TPU accepts for a dimension: the whole dimension, or
    a multiple of 128 that divides it."""
    return sorted({dim} | {t for t in range(128, dim, 128) if dim % t == 0})


def gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """(tm, tk, tn) for `megablox.gmm`: 128 rows (fewer when m is small),
    and the largest weight tile within `_GMM_TILE_ELEMS`."""
    tm = 128 if m >= 128 else -(-m // 16) * 16
    best = (_tile_choices(k)[0], _tile_choices(n)[0])
    for tk in _tile_choices(k):
        for tn in _tile_choices(n):
            if tk * tn <= _GMM_TILE_ELEMS and \
                    (tk * tn, tk) > (best[0] * best[1], best[0]):
                best = (tk, tn)
    return tm, best[0], best[1]


def grouped_matmul(x: jax.Array, w: jax.Array, sizes: jax.Array,
                   impl: Optional[str] = None, layer=None) -> jax.Array:
    """Rows of `x` (M, K), sorted by group, times their group's `w`
    (G, K, N): group g owns the `sizes[g]` rows after those of groups
    before it.  Rows past `sizes.sum()` give zeros.

    With `layer`, `w` is a stack (L, G, K, N) and the groups are those of
    `w[layer]`.  The kernel then reads the stack in place, picking the
    layer's groups by their offset in it: a Pallas call reads its operands
    whole, so a slice handed to it would first be copied out, every
    group's weights and not only the chosen ones.

    `impl`: "gmm" (the Pallas kernel), "gmm_interpret" (the same kernel
    in Pallas' interpreter), "ragged_dot" (`jax.lax.ragged_dot`), or None:
    the kernel where the program is lowered for a TPU (an ahead-of-time
    compile for a described chip too), `ragged_dot` elsewhere."""
    m = x.shape[0]
    held = jnp.arange(m) < sizes.sum()

    def ragged(x, w, sizes):
        return jax.lax.ragged_dot(x, w if layer is None else w[layer], sizes)

    def kernel(x, w, sizes, interpret=False):
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
        if layer is not None:
            stack, g = w.shape[:2]
            w = w.reshape(stack * g, *w.shape[2:])
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((stack * g,), sizes.dtype), sizes, (layer * g,))
        tiling = gmm_tiling(m, w.shape[1], w.shape[2])
        pad = -m % tiling[0]
        xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
        return megablox.gmm(xp, w, sizes, x.dtype, tiling,
                            interpret=interpret)[:m]

    if impl is None:
        y = jax.lax.platform_dependent(x, w, sizes, tpu=kernel,
                                       default=ragged)
    elif impl == "ragged_dot":
        y = ragged(x, w, sizes)
    elif impl in ("gmm", "gmm_interpret"):
        y = kernel(x, w, sizes, interpret=impl == "gmm_interpret")
    else:
        raise ValueError(f"unknown grouped matmul {impl!r}; choices: "
                         f"['gmm', 'gmm_interpret', 'ragged_dot']")
    return jnp.where(held[:, None], y, jnp.zeros((), y.dtype))


def _moe_dropless(p: Params, xt: jax.Array, cfg: ModelConfig, valid=None,
                  impl: Optional[str] = None, layer=None
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sort the (row, choice) pairs by expert -> grouped matmuls over the
    experts that rows chose -> unsort and sum weighted by the gates ->
    shared experts.  Pairs of rows that are not `valid` sort last, into no
    group.  With `layer`, the expert weights are stacks over layers (see
    `grouped_matmul`)."""
    n, d = xt.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    logits, probs, gates, experts = route(p, xt, cfg)
    flat, sizes = _groups(experts, valid, e)
    order = jnp.argsort(flat, stable=True)
    xs = xt[order // k]                                        # (N*k, D)
    h = jax.nn.silu(grouped_matmul(xs, p["w_gate"], sizes, impl, layer)) \
        * grouped_matmul(xs, p["w_up"], sizes, impl, layer)
    ys = grouped_matmul(h, p["w_down"], sizes, impl, layer)    # (N*k, D)
    pairs = jnp.zeros_like(ys).at[order].set(ys).reshape(n, k, d)
    y = jnp.einsum("nkd,nk->nd", pairs.astype(jnp.float32), gates)
    y = y.astype(xt.dtype)
    if "shared" in p:
        y = y + mlp(p["shared"], xt)
    return y, _aux_loss(logits, probs, experts, e), _counts(sizes)


def _moe_core(p: Params, xt: jax.Array, cfg: ModelConfig,
              capacity: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Scatter dispatch -> expert FFNs -> gather combine, over flat tokens.
    Returns (y, aux, experts (N, k))."""
    n, d = xt.shape
    e, k = cfg.n_experts, cfg.experts_per_token

    logits, probs, gate_vals, expert_idx = route(p, xt, cfg)

    # position of each (token, slot) in its expert's queue, via cumsum over
    # the (N*k, E) one-hot — O(N*E) ints, no capacity dimension
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)   # (N,k,E)
    flat_oh = onehot.reshape(n * k, e)
    pos_flat = jnp.cumsum(flat_oh, axis=0) - flat_oh
    pos = jnp.einsum("me,me->m", pos_flat, flat_oh)             # (N*k,)
    pos = pos.reshape(n, k).astype(jnp.int32)
    keep = (pos < capacity)                                     # (N,k) bool

    # scatter tokens into the (E*C, D) buffer; dropped tokens target a
    # sink row that is sliced away
    slot = jnp.where(keep, expert_idx * capacity + pos, e * capacity)
    buf = jnp.zeros((e * capacity + 1, d), xt.dtype)
    buf = buf.at[slot.reshape(-1)].add(
        jnp.repeat(xt, k, axis=0) if k > 1 else xt)
    xin = buf[:-1].reshape(e, capacity, d)

    xin = constrain(xin, "model", None, None)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, p["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", xin, p["w_up"])
    h = constrain(h, "model", None, None)
    yout = constrain(jnp.einsum("ecf,efd->ecd", h, p["w_down"]),
                     "model", None, None)

    # gather back and combine with the gates
    out_flat = yout.reshape(e * capacity, d)
    gathered = out_flat[jnp.minimum(slot, e * capacity - 1)]    # (N,k,D)
    w_comb = (gate_vals * keep).astype(gathered.dtype)
    y = jnp.einsum("nkd,nk->nd", gathered, w_comb)
    y = y.astype(xt.dtype)

    if "shared" in p:
        y = y + mlp(p["shared"], xt)
    return y, _aux_loss(logits, probs, expert_idx, e), expert_idx
