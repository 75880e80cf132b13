"""Measured Pallas tile-config search, cached on disk.

The planner prices *which side runs how much* of an op; this module picks
*how the kernel blocks* what it runs.  `autotune(op)` measures every legal
candidate in the kind's registry `TileSpec` grid (see
`registry.TileSpec.configs`) against the op's actual kernel lowering and
returns the fastest — by default searching only the numerics-preserving
grid, whose candidates vary how the output space is tiled but keep every
reduction-axis block at its default, so the reduction blocks are summed in
the default order.  The winner agrees with the default to fp32 rounding,
not always bit for bit: a re-tiled matmul tile is computation, and the
backend may order its sum by tile shape.  `preserve_numerics=False`
additionally searches reduction-axis blocks (bk / bs / chunk), which
reassociate the reduction itself; those are never selected unless asked.

Measurements run the compiled kernels; ``interpret=True`` must be asked for
(the Pallas interpreter's grid-step overhead, not kernel time, would pick
the winner).

Results persist in a content-addressed `TuneCache` with the same digest
discipline as `runtime.cache.PlanCache`: the key digests the op codec, the
measuring device and backend, the kernel blocking version
(`registry.KERNEL_TILE_VERSION`), and the search mode, so a kernel rewrite
or a different host invalidates stale choices.  Corrupt or mismatched
entries are treated as misses and overwritten, never trusted.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from repro.core.types import Op
from repro.kernels import registry

TUNE_SCHEMA_VERSION = 1

#: default on-disk location, next to the plan cache's reports layout
DEFAULT_TUNE_DIR = Path("reports/tune")

#: a candidate must beat the default by this fraction to dethrone it —
#: keeps measurement noise from churning the cached choice run to run
TUNE_HYSTERESIS = 0.02


def tune_cache_version() -> str:
    """The tune-cache format/kernels version folded into
    `PlanProvenance.tune` when a plan is compiled with tuning enabled —
    bumping either constant invalidates every tuned plan."""
    return f"tune-v{TUNE_SCHEMA_VERSION}.k{registry.KERNEL_TILE_VERSION}"


def measure_device() -> Tuple[str, str]:
    """(device_kind, backend) identity of the host actually measured."""
    import jax
    dev = jax.devices()[0]
    return (getattr(dev, "device_kind", dev.platform), jax.default_backend())


@dataclasses.dataclass(frozen=True)
class TuneKey:
    """Everything a cached tile choice's validity depends on."""

    op_json: Tuple[Tuple[str, Any], ...]
    device: str
    backend: str
    kernel_version: int = registry.KERNEL_TILE_VERSION
    schema_version: int = TUNE_SCHEMA_VERSION
    preserve_numerics: bool = True

    @staticmethod
    def for_op(op: Op, device: str, backend: str, *,
               preserve_numerics: bool = True) -> "TuneKey":
        return TuneKey(op_json=tuple(sorted(registry.op_to_json(op).items())),
                       device=device, backend=backend,
                       preserve_numerics=preserve_numerics)

    def _canonical(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["op_json"] = dict(self.op_json)
        return d

    @property
    def key(self) -> str:
        blob = json.dumps(self._canonical(), sort_keys=True,
                          separators=(",", ":"))
        return hashlib.blake2b(blob.encode(), digest_size=16).hexdigest()


class TuneCache:
    """On-disk cache of measured tile choices — one JSON file per TuneKey
    digest.  `hits`/`misses` count lookups since construction (tests
    assert on them, mirroring PlanCache)."""

    def __init__(self, root: Path = DEFAULT_TUNE_DIR):
        self.root = Path(root)
        self.hits = 0
        self.misses = 0

    def path_for(self, key: TuneKey) -> Path:
        return self.root / f"{key.key}.json"

    def get(self, key: TuneKey) -> Optional[registry.TileConfig]:
        from repro.analysis import rejections
        path = self.path_for(key)
        if path.exists():
            try:
                doc = json.loads(path.read_text())
                want = key._canonical()
                got = doc.get("key")
                if got == want:
                    kind = dict(key.op_json)["kind"]
                    tile = registry.tile_from_json(kind, doc["tile"])
                    self.hits += 1
                    return tile
                fields = sorted(set(want) | set(got or {})) \
                    if isinstance(got, dict) else []
                stale = [f for f in fields
                         if (got or {}).get(f) != want.get(f)]
                rejections.record(path.stem, "provenance.mismatch",
                                  f"stale tune key fields: {stale}")
            except (ValueError, KeyError, TypeError) as e:
                # corrupt entry: treat as a miss, but say which field/rule
                rejections.record(path.stem, "tile.legality"
                                  if "tile" in str(e).lower()
                                  else "schema.malformed", str(e))
        self.misses += 1
        return None

    def put(self, key: TuneKey, tile: registry.TileConfig,
            measured: List[Tuple[str, float]]) -> Path:
        path = self.path_for(key)
        path.parent.mkdir(parents=True, exist_ok=True)
        doc = {"schema_version": TUNE_SCHEMA_VERSION,
               "key": key._canonical(),
               "tile": registry.tile_to_json(tile),
               "measured_us": [[label, round(us, 3)]
                               for label, us in measured]}
        path.write_text(json.dumps(doc, indent=1))
        return path

    def keys(self) -> List[str]:
        if not self.root.is_dir():
            return []
        return sorted(p.stem for p in self.root.glob("*.json"))


def _op_arrays(op: Op, seed: int = 0):
    """Representative (x, w) inputs for measuring one op's kernel."""
    import jax.numpy as jnp
    import numpy as np
    entry = registry.entry_for(op)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(
        entry.input_shape(op)).astype(np.float32))
    if registry.op_kind(op) == "conv":
        x = x[None]                        # lowering expects a batch dim
    w = jnp.asarray(entry.init_weight(op, rng))
    return x, w


def measure_tile_us(op: Op, tile: Optional[registry.TileConfig], *,
                    reps: int = 2, interpret: bool = False,
                    seed: int = 0) -> float:
    """Median wall (us) of the op's Pallas lowering under one config.

    ``tile=None`` measures the default blocking.  The first call warms the
    jit cache (tile params are static), so the timed reps measure steady-
    state execution only.
    """
    x, w = _op_arrays(op, seed=seed)
    low = registry.get_lowering(registry.op_kind(op))

    def run():
        y = low.pallas(x, w, op, interpret=interpret, tile=tile)
        try:
            return y.block_until_ready()
        except AttributeError:              # tuple outputs
            import jax
            return jax.block_until_ready(y)

    run()                                   # compile + warm
    walls = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        run()
        walls.append((time.perf_counter() - t0) * 1e6)
    walls.sort()
    return walls[len(walls) // 2]


def autotune(op: Op, candidates: Optional[List[registry.TileConfig]] = None,
             *, cache: Optional[TuneCache] = None,
             device: str = "", backend: str = "",
             preserve_numerics: bool = True, reps: int = 2,
             interpret: bool = False, seed: int = 0
             ) -> registry.TileConfig:
    """Measured search over an op's legal tile-config grid.

    Returns the winning `TileConfig` (the clamped default when nothing
    beats it by `TUNE_HYSTERESIS`).  With a `cache`, a prior choice for
    the same (op, device, backend, kernel version, search mode) is
    returned without measuring anything; a cold search stores its result
    plus the per-candidate timings.
    """
    kind = registry.op_kind(op)
    spec = registry.tile_spec(kind)
    if not device or not backend:
        mdev, mback = measure_device()
        device = device or mdev
        backend = backend or mback
    key = TuneKey.for_op(op, device, backend,
                         preserve_numerics=preserve_numerics)
    if cache is not None and candidates is None:
        hit = cache.get(key)
        if hit is not None:
            return hit
    if candidates is None:
        candidates = spec.configs(op, preserve_numerics=preserve_numerics)
    default = spec.default_config(op)

    measured: List[Tuple[str, float]] = []
    best, best_us, default_us = default, None, None
    for cfg in candidates:
        us = measure_tile_us(op, cfg, reps=reps, interpret=interpret,
                             seed=seed)
        measured.append((cfg.label(), us))
        if cfg == default:
            default_us = us
        if best_us is None or us < best_us:
            best, best_us = cfg, us
    if default_us is None:                  # default outside the grid
        default_us = measure_tile_us(op, default, reps=reps,
                                     interpret=interpret, seed=seed)
        measured.append((default.label(), default_us))
    # hysteresis: stay on the default unless the winner clearly beats it
    if best != default and best_us > default_us * (1.0 - TUNE_HYSTERESIS):
        best = default
    if cache is not None:
        cache.put(key, best, measured)
    return best


def annotate_plan_tiles(plan, *, cache: Optional[TuneCache] = None,
                        device: str = "", backend: str = "",
                        preserve_numerics: bool = True, reps: int = 2,
                        interpret: bool = False):
    """Attach autotuned tile configs to a plan's decisions, in place.

    The tune pass `compile(..., tune=True)` runs on a plan-cache miss
    (see `runtime.cache.plan_graph_cached`'s `annotate` hook): every
    unique op is tuned once, and a decision gains a `tile` only when the
    winner differs from the default blocking — a plan whose ops all tune
    to their defaults serializes byte-identically to an untuned one
    (modulo the provenance `tune` tag).
    """
    from repro.runtime.plan import decision_from_json, decision_to_json
    if not device or not backend:
        mdev, mback = measure_device()
        device = device or mdev
        backend = backend or mback
    chosen: Dict[Any, Optional[registry.TileConfig]] = {}
    for entry in plan.schedule:
        dec_json = entry.get("decision")
        if not dec_json:
            continue
        dec = decision_from_json(dec_json)
        op = dec.op
        if op not in chosen:
            spec = registry.tile_spec(registry.op_kind(op))
            best = autotune(op, cache=cache, device=device, backend=backend,
                            preserve_numerics=preserve_numerics, reps=reps,
                            interpret=interpret)
            chosen[op] = None if best == spec.default_config(op) else best
        if chosen[op] is not None:
            entry["decision"] = decision_to_json(
                dataclasses.replace(dec, tile=chosen[op]))
    return plan
