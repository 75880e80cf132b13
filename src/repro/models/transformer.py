"""Generic decoder-only transformer stack.

Layer heterogeneity (dense vs MoE, local vs global attention, MLA) is
expressed as a repeating *pattern* of block kinds; parameters for each
pattern position are stacked over the repeat axis so the whole stack runs
as one `lax.scan` per pattern position — this keeps HLO size and compile
time bounded even for 126-layer models lowered on 512 host devices.

Examples:
  llama3-405b:   prologue=[]            pattern=[gqa+mlp] x126
  deepseek-v2:   prologue=[mla+mlp]     pattern=[mla+moe] x26
  llama4-scout:  prologue=[]            pattern=[gqa+mlp, gqa+moe] x24
  gemma3-12b:    prologue=[]            pattern=[5 x local(gqa+mlp),
                                                 1 x global(gqa+mlp)] x8
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models.layers import (AttnSpec, attention_decode, attention_full,
                                 init_attention, init_mlp, mlp, rms_norm)
from repro.sharding.ctx import batch_axes, constrain

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class BlockKind:
    attn: str                    # 'gqa' | 'mla'
    ffn: str                     # 'mlp' | 'moe'
    window: int = 0              # sliding window (0 = full)


def layer_program(cfg: ModelConfig) -> Tuple[List[BlockKind],
                                             List[BlockKind], int]:
    """Returns (prologue_blocks, pattern_blocks, n_repeats)."""
    window = cfg.sliding_window
    attn = cfg.attn_kind
    if cfg.is_moe:
        if cfg.first_dense_layers:
            pro = [BlockKind(attn, "mlp")] * cfg.first_dense_layers
            n = cfg.n_layers - cfg.first_dense_layers
            return pro, [BlockKind(attn, "moe")], n
        if cfg.moe_interleave > 1:
            pat = [BlockKind(attn, "mlp")] * (cfg.moe_interleave - 1) \
                + [BlockKind(attn, "moe")]
            assert cfg.n_layers % cfg.moe_interleave == 0
            return [], pat, cfg.n_layers // cfg.moe_interleave
        return [], [BlockKind(attn, "moe")], cfg.n_layers
    if cfg.local_global_ratio:
        r = cfg.local_global_ratio
        pat = [BlockKind(attn, "mlp", window=window)] * r \
            + [BlockKind(attn, "mlp", window=0)]
        assert cfg.n_layers % (r + 1) == 0
        return [], pat, cfg.n_layers // (r + 1)
    return [], [BlockKind(attn, "mlp", window=window)], cfg.n_layers


def _attn_spec(cfg: ModelConfig, window: int) -> AttnSpec:
    return AttnSpec(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                    head_dim=cfg.head_dim, qkv_bias=cfg.qkv_bias,
                    qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
                    sliding_window=window)


# ------------------------------------------------------------------ blocks
def init_block(rng, cfg: ModelConfig, kind: BlockKind, dtype) -> Params:
    k1, k2 = jax.random.split(rng)
    p: Params = {"ln1": jnp.ones((cfg.d_model,), dtype),
                 "ln2": jnp.ones((cfg.d_model,), dtype)}
    if kind.attn == "mla":
        p["attn"] = mla_mod.init_mla(k1, cfg, dtype)
    else:
        p["attn"] = init_attention(k1, cfg.d_model,
                                   _attn_spec(cfg, kind.window), dtype)
    if kind.ffn == "moe":
        p["ffn"] = moe_mod.init_moe(k2, cfg, dtype)
    else:
        p["ffn"] = init_mlp(k2, cfg.d_model, cfg.d_ff, dtype)
    return p


def block_forward(p: Params, x: jax.Array, cfg: ModelConfig,
                  kind: BlockKind) -> Tuple[jax.Array, jax.Array]:
    x = constrain(x, batch_axes(), None, None)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind.attn == "mla":
        h = mla_mod.mla_full(p["attn"], h, cfg)
    else:
        h = attention_full(p["attn"], h, _attn_spec(cfg, kind.window))
    x = x + h
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if kind.ffn == "moe":
        h, aux, _ = moe_mod.moe_layer(p["ffn"], h, cfg)
    else:
        h = mlp(p["ffn"], h)
    return x + h, aux


def block_prefill(p: Params, x: jax.Array, cfg: ModelConfig, kind: BlockKind,
                  start=None) -> Tuple[jax.Array, jax.Array,
                                       Tuple[jax.Array, jax.Array]]:
    """Like block_forward but also returns the (k, v)-like pair to cache."""
    from repro.models.layers import attention_prefill
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind.attn == "mla":
        h, kv = mla_mod.mla_prefill(p["attn"], h, cfg)
    else:
        h, kv = attention_prefill(p["attn"], h, _attn_spec(cfg, kind.window),
                                  start=start)
    x = x + h
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if kind.ffn == "moe":
        h, aux, _ = moe_mod.moe_layer(p["ffn"], h, cfg)
    else:
        h = mlp(p["ffn"], h)
    return x + h, aux, kv


def block_decode(p: Params, x: jax.Array, cfg: ModelConfig, kind: BlockKind,
                 cache: Tuple[jax.Array, jax.Array], pos: jax.Array,
                 start=None, active=None, layer=None):
    """-> (x, cache, counts): `counts` is the MoE layer's (experts hit,
    pairs routed) of the `active` rows, None for a dense block.  With
    `layer`, the routed experts' weights are stacks (see `moe_layer`)."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind.attn == "mla":
        h, ck, cv = mla_mod.mla_decode(p["attn"], h, cfg, cache[0], cache[1],
                                       pos)
    else:
        h, ck, cv = attention_decode(p["attn"], h, _attn_spec(cfg,
                                                              kind.window),
                                     cache[0], cache[1], pos, start=start)
    x = x + h
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    counts = None
    if kind.ffn == "moe":
        h, _, counts = moe_mod.moe_layer(p["ffn"], h, cfg, valid=active,
                                         layer=layer)
    else:
        h = mlp(p["ffn"], h)
    return x + h, (ck, cv), counts


# ------------------------------------------------------------------- model
class TransformerModel:
    """Decoder-only LM with the uniform Model API (see registry.py)."""

    def __init__(self, cfg: ModelConfig):
        if cfg.rope_scaling is not None and cfg.attn_kind != "mla":
            raise ValueError("rope_scaling is applied by mla attention only")
        self.cfg = cfg
        self.prologue, self.pattern, self.n_repeats = layer_program(cfg)
        dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]
        self.dtype = dt

    # ------------------------------------------------------------- params
    def init(self, rng) -> Params:
        cfg = self.cfg
        k_emb, k_out, k_pro, k_pat = jax.random.split(rng, 4)
        params: Params = {
            "embed": jax.random.normal(
                k_emb, (cfg.vocab_size, cfg.d_model), self.dtype) * 0.02,
            "unembed": jax.random.normal(
                k_out, (cfg.d_model, cfg.vocab_size), self.dtype)
            * (float(1.0 / np.sqrt(cfg.d_model))),
            "ln_f": jnp.ones((cfg.d_model,), self.dtype),
        }
        params["prologue"] = [
            init_block(k, cfg, kind, self.dtype)
            for k, kind in zip(jax.random.split(k_pro,
                                                max(1, len(self.prologue))),
                               self.prologue)]
        # pattern params: one stacked pytree per pattern position, built
        # stacked (vmap over the layer keys) so no per-layer copy exists
        # beside the stack
        pat = []
        for i, kind in enumerate(self.pattern):
            keys = jax.random.split(jax.random.fold_in(k_pat, i),
                                    self.n_repeats)
            pat.append(jax.vmap(
                lambda k, kind=kind: init_block(k, cfg, kind, self.dtype))(
                    keys))
        params["pattern"] = pat
        return params

    # ------------------------------------------------------------ forward
    def forward(self, params: Params, tokens: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
        """tokens (B, T) -> (logits (B,T,V), aux_loss)."""
        cfg = self.cfg
        x = constrain(params["embed"][tokens], batch_axes(), None, None)
        aux_total = jnp.zeros((), jnp.float32)
        for p, kind in zip(params["prologue"], self.prologue):
            x, aux = block_forward(p, x, cfg, kind)
            aux_total += aux

        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots" else None)

        @functools.partial(jax.checkpoint, prevent_cse=False, policy=policy)
        def scan_body(carry, layer_params):
            # rematerialized: backward saves only the per-layer carry, not
            # the block-internal activations (critical at 4k x 256 batch)
            x, aux_total = carry
            for p, kind in zip(layer_params, self.pattern):
                x, aux = block_forward(p, x, cfg, kind)
                aux_total += aux
            return (x, aux_total), None

        (x, aux_total), _ = jax.lax.scan(
            scan_body, (x, aux_total), tuple(params["pattern"]))
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = constrain(x @ params["unembed"], batch_axes(), None,
                           "model")
        return logits, aux_total

    def loss(self, params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        tokens = batch["tokens"]
        labels = batch["labels"]
        logits, aux = self.forward(params, tokens)
        logits = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        return nll.mean() + 0.01 * aux

    # ------------------------------------------------------------ serving
    @property
    def moe_layers(self) -> int:
        """How many of the stack's layers route to experts."""
        return (sum(k.ffn == "moe" for k in self.prologue)
                + self.n_repeats * sum(k.ffn == "moe" for k in self.pattern))

    def cache_spec(self, batch: int, max_len: int):
        """Shapes/dtypes of the KV cache pytree.  A stack with experts also
        carries `moe`, int32 (moe_layers, 2): per MoE layer in layer
        order, the experts hit and the (row, choice) pairs routed, summed
        over the decode steps the cache has seen."""
        cfg = self.cfg
        if cfg.attn_kind == "mla":
            k_shape = (batch, max_len, cfg.kv_lora_rank)
            v_shape = (batch, max_len, cfg.qk_rope_head_dim)
        else:
            k_shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
            v_shape = k_shape
        per_block = lambda: (jnp.zeros(k_shape, self.dtype),   # noqa: E731
                             jnp.zeros(v_shape, self.dtype))
        pro = [per_block() for _ in self.prologue]
        pat = [jax.tree.map(
            lambda x: jnp.zeros((self.n_repeats,) + x.shape, x.dtype),
            per_block()) for _ in self.pattern]
        cache = {"prologue": pro, "pattern": pat}
        if self.moe_layers:
            cache["moe"] = jnp.zeros((self.moe_layers, 2), jnp.int32)
        return cache

    def init_cache(self, batch: int, max_len: int):
        return self.cache_spec(batch, max_len)

    @property
    def pad_aware(self) -> bool:
        """True when prefill/decode accept a per-row `start` pad boundary
        (the GQA attention path; MLA caches latents and cannot mask pads
        without re-deriving per-row keys)."""
        kinds = self.prologue + self.pattern
        return all(k.attn != "mla" for k in kinds)

    #: decode_step accepts a (B,) pos vector (one timeline per batch slot)
    #: on both attention paths, GQA and MLA
    per_slot_pos = True

    def _check_padded(self, start) -> None:
        if start is not None and not self.pad_aware:
            raise ValueError("per-row start masking requires pad_aware "
                             "attention (gqa); this stack contains mla")

    def prefill(self, params: Params, tokens: jax.Array, cache,
                start=None) -> Tuple[jax.Array, Any]:
        """Full-sequence causal pass that also fills the KV cache for the
        first T positions.  Returns (last-position logits, filled cache).
        `start` (B,) marks each row's first real token in a left-padded
        batch; positions before it are masked out of every softmax."""
        cfg = self.cfg
        self._check_padded(start)
        x = params["embed"][tokens]

        def fill(c, kv):
            return jax.lax.dynamic_update_slice(
                c, kv.astype(c.dtype), (0,) * c.ndim)

        new_pro = []
        for p, kind, c in zip(params["prologue"], self.prologue,
                              cache["prologue"]):
            x, _, kv = block_prefill(p, x, cfg, kind, start=start)
            new_pro.append((fill(c[0], kv[0]), fill(c[1], kv[1])))

        def scan_body(x, scanned):
            layer_params, layer_cache = scanned
            new_cache = []
            for p, kind, c in zip(layer_params, self.pattern, layer_cache):
                x, _, kv = block_prefill(p, x, cfg, kind, start=start)
                new_cache.append((fill(c[0], kv[0]), fill(c[1], kv[1])))
            return x, tuple(new_cache)

        x, new_pat = jax.lax.scan(
            scan_body, x, (tuple(params["pattern"]),
                           tuple(cache["pattern"])))
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = x[:, -1, :] @ params["unembed"]
        out = {"prologue": new_pro, "pattern": list(new_pat)}
        if "moe" in cache:
            out["moe"] = cache["moe"]
        return logits, out

    def decode_step(self, params: Params, tokens: jax.Array, cache,
                    pos: jax.Array, start=None, active=None
                    ) -> Tuple[jax.Array, Any]:
        """tokens (B,1); pos: scalar int32 — position being written — or a
        (B,) vector when each batch slot runs its own timeline (continuous
        batching).  `start` (B,) masks cache entries before each row's
        first real token (left-padded batches).  `active` (B,) bool marks
        the rows that carry a request (None: all): the MoE layers route
        only those, and the cache's `moe` counters add what they routed."""
        cfg = self.cfg
        self._check_padded(start)
        x = params["embed"][tokens]
        new_pro, counts = [], []
        for p, kind, c in zip(params["prologue"], self.prologue,
                              cache["prologue"]):
            x, c2, n = block_decode(p, x, cfg, kind, c, pos, start=start,
                                    active=active)
            new_pro.append(c2)
            if n is not None:
                counts.append(n[None])

        # the routed experts' stacks go into the layer loop whole, each
        # layer picking its own by offset: a slice of them handed to the
        # grouped matmul's Pallas call would be copied out every step
        scanned, stacks = [], []
        for p, kind in zip(params["pattern"], self.pattern):
            ffn = dict(p["ffn"])
            stacks.append({k: ffn.pop(k) for k in moe_mod.EXPERT_WEIGHTS}
                          if kind.ffn == "moe" else {})
            scanned.append(dict(p, ffn=ffn))

        def scan_body(x, scanned):
            layer_params, layer_cache, i = scanned
            new_cache, ns = [], []
            for p, kind, c, held in zip(layer_params, self.pattern,
                                        layer_cache, stacks):
                p = dict(p, ffn={**p["ffn"], **held})
                x, c2, n = block_decode(p, x, cfg, kind, c, pos, start=start,
                                        active=active,
                                        layer=i if held else None)
                new_cache.append(c2)
                if n is not None:
                    ns.append(n)
            return x, (tuple(new_cache), jnp.stack(ns) if ns else None)

        layers = jnp.arange(self.n_repeats) if any(stacks) else None
        x, (new_pat, pat_counts) = jax.lax.scan(
            scan_body, x, (tuple(scanned), tuple(cache["pattern"]), layers))
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = x @ params["unembed"]
        out = {"prologue": new_pro, "pattern": list(new_pat)}
        if "moe" in cache:
            if pat_counts is not None:                # (repeats, moe, 2)
                counts.append(pat_counts.reshape(-1, 2))
            out["moe"] = cache["moe"] + jnp.concatenate(counts)
        return logits[:, 0, :], out
