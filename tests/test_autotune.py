"""Tile-config autotuner: grid legality, numerics, cache, plan threading.

Covers the tile-tuning contract end to end: every numerics-preserving
candidate keeps the default's reduction blocking and agrees with it (to
fp32 rounding where it re-tiles a matmul),
reduction-axis variation is tolerance-exact, illegal explicit tiles raise
at validation (no silent clamping), the TuneCache digest discipline
(cold/warm/corrupt/cross-instance), the byte-compatibility guarantees for
pre-tile plan JSON and provenance digests, tile-aware predictor
featurization, and the `compile(..., tune=True)` annotation pass.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.partitioner import PartitionDecision  # noqa: E402
from repro.core.types import AttnOp, ConvOp, LinearOp, SSMOp  # noqa: E402
from repro.kernels import registry  # noqa: E402
from repro.runtime.autotune import (TuneCache, TuneKey,  # noqa: E402
                                    annotate_plan_tiles, autotune,
                                    tune_cache_version)
from repro.runtime.plan import (PlanProvenance, decision_from_json,  # noqa: E402
                                decision_to_json, decision_to_spec,
                                predictor_checksum, spec_label)

#: one small op per kind (conv is winograd-eligible: C_out >= 128)
OPS = {
    "linear": LinearOp(L=16, C_in=256, C_out=256),
    "conv": ConvOp(H_in=8, W_in=8, C_in=32, C_out=128),
    "attention": AttnOp(H=4, S=256, KV=2, hd=16),
    "ssm": SSMOp(T=64, H=2, hd=8, N=16),
}


def _io(op):
    from repro.runtime.autotune import _op_arrays
    return _op_arrays(op, seed=3)


def _pallas(op, tile):
    x, w = _io(op)
    low = registry.get_lowering(registry.op_kind(op))
    return np.asarray(jax.block_until_ready(
        low.pallas(x, w, op, interpret=True, tile=tile)))


# ------------------------------------------------ differential: numerics

#: fp32 bound for the matmul kinds' preserving candidates (K = C_in = 256
#: here): the K blocks are summed in the default order, but each
#: (bm, bk) x (bk, bn) tile dot is itself computation, whose internal order
#: the backend may pick by tile shape
TILE_RTOL, TILE_ATOL = 1e-6, 1e-5


@pytest.mark.parametrize("kind", sorted(OPS))
def test_preserving_grid_is_bit_identical_to_default(kind):
    """Every candidate in the numerics-preserving grid keeps the default's
    reduction blocking.  Where the grid re-tiles matmuls (linear, conv) a
    candidate agrees with the default to fp32 rounding; where it re-blocks
    no computation (attention, ssm: the default alone) the bytes match."""
    op = OPS[kind]
    spec = registry.tile_spec(kind)
    grid = spec.configs(op)
    default = spec.default_config(op)
    assert default in grid
    # reduction params stay pinned to the default-resolved value
    for cfg in grid:
        for p in spec.params:
            if p.reduction:
                assert cfg.get(p.name) == default.get(p.name), cfg.label()
    ref = _pallas(op, None)
    x, w = _io(op)
    oracle = np.asarray(
        registry.get_lowering(kind).oracle(x, w, op))
    np.testing.assert_allclose(ref, oracle, rtol=2e-3, atol=2e-3)
    for cfg in grid:
        y = _pallas(op, cfg)
        if kind in ("linear", "conv"):
            np.testing.assert_allclose(y, ref, rtol=TILE_RTOL, atol=TILE_ATOL,
                                       err_msg=cfg.label())
        else:
            assert y.tobytes() == ref.tobytes(), cfg.label()


@pytest.mark.parametrize("kind,tile_kw", [
    ("linear", {"bk": 128}),          # split reduction: reassociates
    ("attention", {"bs": 128}),       # smaller cache block than default
    ("ssm", {"chunk": 32}),           # finer chunking than default
])
def test_reduction_axis_variation_is_tolerance_exact(kind, tile_kw):
    op = OPS[kind]
    spec = registry.tile_spec(kind)
    default = spec.default_config(op)
    cfg = spec.config(**{**default.as_dict(), **tile_kw})
    assert cfg != default
    y = _pallas(op, cfg)
    np.testing.assert_allclose(y, _pallas(op, None), rtol=1e-5, atol=1e-5)


def test_extended_linear_grid_searches_reduction_axis():
    op = OPS["linear"]
    spec = registry.tile_spec("linear")
    bks = {cfg.get("bk") for cfg in spec.configs(op,
                                                 preserve_numerics=False)}
    assert len(bks) > 1                       # bk actually varies
    assert all(len({c.get("bk")
                    for c in spec.configs(op)}) == 1 for _ in [0])


def test_attention_preserving_grid_collapses_to_default():
    op = OPS["attention"]
    spec = registry.tile_spec("attention")
    assert spec.configs(op) == [spec.default_config(op)]


# ------------------------------------------- strict validation, no clamp

def test_illegal_explicit_tiles_raise_at_kernel_entry():
    lin = OPS["linear"]
    spec = registry.tile_spec("linear")
    x, w = _io(lin)
    low = registry.get_lowering("linear")
    base = spec.default_config(lin).as_dict()
    for bad in ({"bm": 12},              # not a multiple of the min tile
                {"bn": 1024},            # exceeds the padded C_out extent
                {"bm": -8}):             # not positive
        cfg = spec.config(**{**base, **bad})
        with pytest.raises(ValueError, match="tile"):
            low.pallas(x, w, lin, interpret=True, tile=cfg)
    ssm = OPS["ssm"]
    with pytest.raises(ValueError, match="divide"):
        registry.get_lowering("ssm").pallas(
            *_io(ssm), ssm, interpret=True,
            tile=registry.tile_spec("ssm").config(chunk=48))


def test_clamp_lives_in_registry_not_kernels():
    """The old silent kernel clamp is now an explicit registry rewrite."""
    op = OPS["linear"]
    spec = registry.tile_spec("linear")
    oversize = spec.config(bm=256, bn=512, bk=256)
    extents = registry.tile_extents(op)
    with pytest.raises(ValueError, match="exceeds the padded"):
        spec.validate_tile(oversize, extents)
    clamped = spec.clamp_tile(oversize, extents)
    assert clamped.get("bm") == 16 and clamped.get("bn") == 256
    assert registry.resolve_tile(op, clamped) == clamped


def test_vmem_budget_rejects_oversized_working_sets():
    big = LinearOp(L=4096, C_in=4096, C_out=4096)
    spec = registry.tile_spec("linear")
    with pytest.raises(ValueError, match="VMEM budget"):
        spec.validate_tile(spec.config(bm=4096, bn=4096, bk=4096),
                           registry.tile_extents(big))


def test_winograd_min_cout_hoisted_into_registry():
    assert registry.WINOGRAD_MIN_COUT == 128
    assert OPS["conv"].C_out >= registry.WINOGRAD_MIN_COUT


# --------------------------------------------------------- tile codecs

def test_tile_json_roundtrip_and_mismatch():
    spec = registry.tile_spec("linear")
    cfg = spec.config(bm=8, bn=256, bk=256)
    assert registry.tile_from_json(
        "linear", registry.tile_to_json(cfg)) == cfg
    with pytest.raises(ValueError, match="spec params"):
        registry.tile_from_json("linear", {"bm": 8})
    with pytest.raises(ValueError, match="unknown tile param"):
        spec.config(bz=4)


# ------------------------------------------------------------ TuneCache

def test_tune_cache_cold_warm_corrupt_and_cross_instance(tmp_path):
    op = OPS["linear"]
    key = TuneKey.for_op(op, "host", "cpu")
    cache = TuneCache(tmp_path)
    assert cache.get(key) is None and cache.misses == 1
    tile = registry.tile_spec("linear").config(bm=8, bn=256, bk=256)
    path = cache.put(key, tile, [("bm8/bn256/bk256", 12.0)])
    assert cache.get(key) == tile and cache.hits == 1
    # a fresh instance (≈ another process) hits the same file
    other = TuneCache(tmp_path)
    assert other.get(key) == tile and other.hits == 1
    assert other.keys() == [key.key]
    # corrupt JSON and mismatched keys are misses, never trusted
    path.write_text("{not json")
    assert TuneCache(tmp_path).get(key) is None
    doc = {"schema_version": 1, "key": {"device": "elsewhere"},
           "tile": registry.tile_to_json(tile), "measured_us": []}
    path.write_text(json.dumps(doc))
    assert TuneCache(tmp_path).get(key) is None
    # a different search mode never aliases
    relaxed = TuneKey.for_op(op, "host", "cpu", preserve_numerics=False)
    assert relaxed.key != key.key


def test_tune_key_digests_kernel_version():
    op = OPS["linear"]
    key = TuneKey.for_op(op, "host", "cpu")
    bumped = dataclasses.replace(key, kernel_version=key.kernel_version + 1)
    assert bumped.key != key.key
    assert tune_cache_version() == \
        f"tune-v1.k{registry.KERNEL_TILE_VERSION}"


def test_autotune_hysteresis_and_cache(tmp_path, monkeypatch):
    op = OPS["linear"]
    spec = registry.tile_spec("linear")
    default = spec.default_config(op)
    winner = spec.config(bm=8, bn=256, bk=256)
    assert winner in spec.configs(op)

    timings = {winner: 50.0, default: 100.0}

    def fake_measure(op_, tile, **kw):
        cfg = registry.resolve_tile(op_, tile)
        return timings.get(cfg, 100.0)

    import repro.runtime.autotune as at
    monkeypatch.setattr(at, "measure_tile_us", fake_measure)
    cache = TuneCache(tmp_path)
    best = autotune(op, cache=cache, device="host", backend="cpu")
    assert best == winner and cache.misses == 1
    # warm: returned from disk without re-measuring
    monkeypatch.setattr(at, "measure_tile_us",
                        lambda *a, **k: pytest.fail("measured on warm hit"))
    assert autotune(op, cache=TuneCache(tmp_path), device="host",
                    backend="cpu") == winner
    # hysteresis: a 1% win does not dethrone the default
    monkeypatch.setattr(at, "measure_tile_us", fake_measure)
    timings[winner] = 99.5
    best = autotune(op, device="host", backend="cpu")
    assert best == default


# ------------------------------------------- plan byte-compat regression

def _decision(op, tile=None):
    return PartitionDecision(op=op, c_cpu=0, c_gpu=op.C_out,
                             pred_cpu_us=0.0, pred_gpu_us=1.0,
                             pred_total_us=1.0, tile=tile)


def test_untuned_decision_json_has_no_tile_key():
    """Pre-PR-9 byte compatibility: tile is omit-when-default, so every
    existing plan file and cache entry keeps its exact bytes."""
    d = decision_to_json(_decision(OPS["linear"]))
    assert "tile" not in d
    back = decision_from_json(d)
    assert back.tile is None
    assert decision_to_json(back) == d


def test_tiled_decision_roundtrips_and_validates():
    spec = registry.tile_spec("linear")
    tile = spec.config(bm=8, bn=256, bk=256)
    d = decision_to_json(_decision(OPS["linear"], tile))
    assert d["tile"] == {"bm": 8, "bn": 256, "bk": 256}
    assert decision_from_json(d).tile == tile
    with pytest.raises(ValueError, match="exceeds the padded"):
        decision_to_json(_decision(
            OPS["linear"], spec.config(bm=256, bn=512, bk=256)))


def test_tune_provenance_is_byte_compatible():
    base = PlanProvenance(device="moto2022", threads=3, mechanism="spin",
                          step=8, seed=0, network_fingerprint="f" * 8,
                          predictor_checksum="p" * 8)
    assert "tune" not in base.to_json()
    assert dataclasses.replace(base, tune="").key == base.key
    tagged = dataclasses.replace(base, tune=tune_cache_version())
    assert tagged.key != base.key
    assert tagged.to_json()["tune"] == tune_cache_version()
    assert PlanProvenance.from_json(tagged.to_json()) == tagged


def test_exec_spec_equality_and_label_carry_tile():
    tile = registry.tile_spec("linear").config(bm=8, bn=256, bk=256)
    plain = decision_to_spec(_decision(OPS["linear"]), "n0")
    tiled = decision_to_spec(_decision(OPS["linear"], tile), "n0")
    assert plain != tiled                 # a retuned tile is a new program
    assert "tile[" not in spec_label(plain)
    assert f"tile[{tile.label()}]" in spec_label(tiled)


def test_predictor_checksum_tile_tag():
    class Fake:
        device, backend, whitebox = "d", "cpu", True
        models = {}
    blind = Fake()
    aware = Fake()
    aware.tiles = True
    legacy = predictor_checksum(blind)
    blind.tiles = False                   # explicit False == pre-field
    assert predictor_checksum(blind) == legacy
    assert predictor_checksum(aware) != legacy


# ------------------------------------------- tile-aware predictor feats

def test_tile_features_and_tile_aware_training():
    from repro.core.predictor.features import (feature_names,
                                               tile_feature_names,
                                               tile_features)
    from repro.core.predictor.train import train_predictor
    assert tile_feature_names("linear") == ["tile_bm", "tile_bn", "tile_bk"]
    ops = [OPS["linear"], LinearOp(L=64, C_in=128, C_out=128)]
    feats = tile_features(ops)            # None -> clamped defaults
    d0 = registry.default_tile(ops[0])
    assert list(feats[0]) == [float(v) for _, v in d0.values]
    assert feature_names("linear", True, tiles=True)[-3:] == \
        tile_feature_names("linear")
    train = [LinearOp(L=8 * i, C_in=128, C_out=128)
             for i in range(1, 13)]
    p = train_predictor(train, "moto2022", "cpu", tiles=True)
    assert p.tile_aware
    tiles = [registry.default_tile(op) for op in ops]
    got = p.predict(ops, tiles)
    assert got.shape == (2,) and np.all(np.isfinite(got))
    blind = train_predictor(train, "moto2022", "cpu")
    assert not blind.tile_aware           # and pre-field unpickles too
    assert predictor_checksum(p) != predictor_checksum(blind)


# -------------------------------------------------- annotate + compile

def _patch_fixed_winner(monkeypatch, op, winner):
    def fake_measure(op_, tile, **kw):
        return 10.0 if registry.resolve_tile(op_, tile) == winner else 90.0
    import repro.runtime.autotune as at
    monkeypatch.setattr(at, "measure_tile_us", fake_measure)


def test_compile_tune_true_threads_tiles_and_new_cache_key(
        tmp_path, monkeypatch):
    import repro
    op = OPS["linear"]
    spec = registry.tile_spec("linear")
    winner = spec.config(bm=8, bn=256, bk=256)
    _patch_fixed_winner(monkeypatch, op, winner)
    target = repro.Target(device="moto2022", threads=3)
    kw = dict(cache=tmp_path / "plans", samples=60, estimators=8,
              predictor_cache=tmp_path / "pred")
    base = repro.compile([op] * 2, target, **kw)
    tuned = repro.compile([op] * 2, target, tune=True,
                          tune_cache=tmp_path / "tune", **kw)
    assert base.key != tuned.key
    assert base.provenance.tune == ""
    assert tuned.provenance.tune == tune_cache_version()
    tiles = [d.tile for d in tuned.decisions]
    assert tiles and all(t == winner for t in tiles)
    assert all(d.tile is None for d in base.decisions)
    assert f"tile[{winner.label()}]" in tuned.explain()
    assert f"tune={tune_cache_version()}" in tuned.explain()
    # warm recompile: plan-cache hit, tiles survive the JSON roundtrip
    monkeypatch.setattr("repro.runtime.autotune.measure_tile_us",
                        lambda *a, **k: pytest.fail("tuned on warm hit"))
    warm = repro.compile([op] * 2, target, tune=True,
                         tune_cache=tmp_path / "tune", **kw)
    assert warm.from_cache and warm.key == tuned.key
    assert [d.tile for d in warm.decisions] == tiles


def test_all_default_tune_keeps_plan_json_identical(tmp_path, monkeypatch):
    """When every op tunes to its default, the tuned plan differs from the
    untuned one only by the provenance tune tag — no tile keys leak."""
    import repro
    op = OPS["linear"]
    _patch_fixed_winner(monkeypatch, op,
                        registry.tile_spec("linear").default_config(op))
    target = repro.Target(device="moto2022", threads=3)
    kw = dict(cache=tmp_path / "plans", samples=60, estimators=8,
              predictor_cache=tmp_path / "pred")
    base = repro.compile([op], target, **kw)
    tuned = repro.compile([op], target, tune=True,
                          tune_cache=tmp_path / "tune", **kw)
    a = json.loads(base.plan.dumps())
    b = json.loads(tuned.plan.dumps())
    assert b["provenance"].pop("tune") == tune_cache_version()
    a["provenance"].pop("key", None), b["provenance"].pop("key", None)
    assert a == b
    assert '"tile"' not in tuned.plan.dumps()


def test_annotate_plan_tiles_dedups_ops(monkeypatch):
    calls = []
    op = OPS["linear"]
    spec = registry.tile_spec("linear")
    winner = spec.config(bm=8, bn=256, bk=256)

    def fake_autotune(op_, **kw):
        calls.append(op_)
        return winner

    import repro.runtime.autotune as at
    monkeypatch.setattr(at, "autotune", fake_autotune)
    schedule = [{"decision": decision_to_json(_decision(op))}
                for _ in range(3)]
    plan = type("P", (), {"schedule": schedule})()
    annotate_plan_tiles(plan, device="host", backend="cpu")
    assert len(calls) == 1                # tuned once, applied thrice
    for entry in schedule:
        assert entry["decision"]["tile"] == registry.tile_to_json(winner)


# -------------------------------- split lowerings accept tuned tiles

_SPLIT_TILE_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    import jax.numpy as jnp
    from repro.core.coexec import coexec_mesh
    from repro.core.types import AttnOp, SSMOp
    from repro.kernels import registry

    mesh = coexec_mesh(jax.devices())
    rng = np.random.default_rng(11)

    def unit_io(op):
        ent = registry.entry_for(op)
        x = jnp.asarray(rng.standard_normal(ent.input_shape(op)),
                        jnp.float32)
        w = jnp.asarray(ent.init_weight(op, rng), jnp.float32)
        return ent, x, w

    # Split lowerings accept the tuned tile and stay bit-identical to the
    # unsplit oracle (their shard_map math is tile-independent); a
    # different tile must compile a DISTINCT cached program — a retuned
    # plan can never silently alias a stale jitted program.
    from repro.core import coexec

    attn = AttnOp(H=8, S=256, KV=4, hd=16)
    tile = registry.tile_spec("attention").config(bs=128)
    ent, x, w = unit_io(attn)
    ref = np.asarray(ent.lowering.oracle(x, w, attn))
    low = registry.get_split_lowering("attention", "head")
    split, packed = low.pack(w, attn, 4, mesh)
    y0 = np.asarray(low.run(x, packed, split, mesh, attn, 4))
    n_after_default = len(coexec._PROGRAM_CACHE)
    y1 = np.asarray(low.run(x, packed, split, mesh, attn, 4, tile=tile))
    assert len(coexec._PROGRAM_CACHE) == n_after_default + 1
    assert y0.tobytes() == ref.tobytes()
    assert y1.tobytes() == ref.tobytes()
    print("HEAD_TILE_OK")

    ssm = SSMOp(T=64, H=8, hd=8, N=16)
    tile = registry.tile_spec("ssm").config(chunk=32)
    ent, x, w = unit_io(ssm)
    ref = np.asarray(ent.lowering.oracle(x, w, ssm))
    low = registry.get_split_lowering("ssm", "ssm-state")
    split, packed = low.pack(w, ssm, 4, mesh)
    y0 = np.asarray(low.run(x, packed, split, mesh, ssm, 4))
    n_after_default = len(coexec._PROGRAM_CACHE)
    y1 = np.asarray(low.run(x, packed, split, mesh, ssm, 4, tile=tile))
    assert len(coexec._PROGRAM_CACHE) == n_after_default + 1
    assert y0.tobytes() == ref.tobytes()
    assert y1.tobytes() == ref.tobytes()
    print("SSM_TILE_OK")
""")


def test_split_lowerings_accept_tuned_tiles_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SPLIT_TILE_PROG], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "HEAD_TILE_OK" in out.stdout, out.stdout[-2000:]
    assert "SSM_TILE_OK" in out.stdout, out.stdout[-2000:]
