"""What every `BENCHMARK.json` has to satisfy, derived from the spec itself.

`check_spec(spec, root)` asserts it for a spec and the benchmark's files
under `root` (the directory that holds `harness.py`), so that a cell and a
per-layer metric can be added as files plus appended entries, and the
checks cover them without naming them.  Which per-layer metrics a cell
carries follows from the end-to-end metrics it reports, never from the
name of the cell or of its traffic.
"""
from __future__ import annotations

import re
from pathlib import Path

import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

#: cells the benchmark has had: none of them may go
ACCEPTED = ("resnet18.b1", "codeqwen15_7b.chat", "codeqwen15_7b.blocks.b1",
            "resnet18.b1.split", "vgg16.b1")

#: the program's spans that every cell reporting an end-to-end metric
#: carries: the scheduler's step for a served token's gap, the executor's
#: walk for a decode step or an inference
_EXEC = ("sync_wait_ms", "dispatch_ms", "idle_in_sync_ms")
CARRIES = {
    "itl_p95_ms": ("inputs_ms.serve", "decode_call_ms.serve",
                   "sample_ms.serve", "emit_ms.serve", "read_ms.serve"),
    "decode_ms": tuple(f"{m}.decode" for m in _EXEC),
    "infer_ms": tuple(f"{m}.infer" for m in _EXEC),
}

#: (unit, better, source, layer, moves) of metrics whose declaration is
#: fixed in full
DECLARED = {"read_ms.serve": ("ms", "lower", "program_span", "scheduler",
                              "itl_p95_ms")}


def _names_are_sound(spec: dict) -> None:
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in spec[group]]
        for name in names:
            assert NAME.match(name), f"{group}: bad name {name!r}"
        assert len(set(names)) == len(names), f"{group}: a name repeats"
    metrics = [m["name"] for g in ("end_to_end", "per_layer")
               for m in spec[g]]
    assert len(set(metrics)) == len(metrics), "a metric name repeats"


def _chips_are_sound(spec: dict) -> None:
    chips = [w["chips"] for w in spec["workloads"]]
    assert set(chips) <= {1, 4}, f"chips must be 1 or 4: {chips}"
    four = chips.count(4)
    assert four <= max(1, len(chips) // 2), \
        f"{four} four-chip cells of {len(chips)}"


def _declarations_are_sound(spec: dict, root: Path) -> None:
    declared = {m["name"]: m for g in ("end_to_end", "per_layer")
                for m in spec[g]}
    e2e = {m["name"] for m in spec["end_to_end"]}
    cells = {w["name"] for w in spec["workloads"]}
    for name, m in declared.items():
        assert (root / "metrics" / f"{name}.py").is_file(), \
            f"no reader metrics/{name}.py"
        assert set(m.get("workloads", ())) <= cells, \
            f"{name} lists an undeclared cell"
    for m in spec["per_layer"]:
        assert m["moves"] in e2e, f"{m['name']} moves no end-to-end metric"
    for moves, names in CARRIES.items():
        for name in names:
            m = declared[name]
            assert (m["unit"], m["better"], m["moves"]) == \
                ("ms", "lower", moves), f"{name} is declared otherwise"
    for name, want in DECLARED.items():
        m = declared[name]
        got = (m["unit"], m["better"], m["source"], m["layer"], m["moves"])
        assert got == want, f"{name} is declared as {got}, not {want}"
    used = {w["config"] for w in spec["workloads"]}
    for c in spec["configs"]:
        assert (root.parents[1] / c["file"]).is_file(), \
            f"no configuration file {c['file']}"
        assert c["name"] in used, f"configuration {c['name']} is unused"


def _cell_is_sound(spec: dict, name: str, root: Path) -> None:
    cell = harness.resolve(spec, name, root)
    assert cell["runner"].is_file(), f"{name}: no runner"
    assert cell["limits_file"].is_file(), f"{name}: no limits file"
    e2e = {m["name"] for m in cell["metrics"]["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2, f"{name} reports {e2e}"
    per_layer = {m["name"] for m in cell["metrics"]["per_layer"]}
    assert per_layer, f"{name} has no per-layer metric"
    for m in cell["metrics"]["per_layer"]:
        assert m["moves"] in e2e, \
            f"{name}: {m['name']} moves {m['moves']}, which it does not report"
    for moves, names in CARRIES.items():
        if moves in e2e:
            missing = set(names) - per_layer
            assert not missing, f"{name} reports {moves} but not {missing}"


def check_spec(spec: dict, root: Path) -> None:
    """Assert that `spec` and the files under `root` describe a sound
    benchmark that keeps every accepted cell."""
    _names_are_sound(spec)
    _chips_are_sound(spec)
    _declarations_are_sound(spec, root)
    names = [w["name"] for w in spec["workloads"]]
    missing = set(ACCEPTED) - set(names)
    assert not missing, f"accepted cells gone: {sorted(missing)}"
    for name in names:
        _cell_is_sound(spec, name, root)
