"""Repo-contract linter: machine-check the conventions the repo relies on.

Three contracts, accumulated over the PR history and until now enforced
only by subprocess tests or review:

  * ``lint.import-light``    — the planning/graph/measure/serving layers
    must not import jax at module top level.  Planning runs on the
    serving control plane and in CI containers without an accelerator;
    one stray top-level ``import jax`` there drags ~2s of backend init
    into every `repro plan` invocation and breaks the jax-free
    subprocess tests.  Function-local imports and ``if TYPE_CHECKING:``
    blocks are fine.
  * ``lint.registry-complete`` — every registered op kind must carry the
    full contract surface: shape/feature callables, a codec entry, a
    tile spec, a registered lowering module, and either channel
    splittability or declared typed axes.  A half-registered kind
    compiles plans the executor cannot lower.
  * ``lint.no-silent-clamp`` — kernel entry points must not
    ``min()``-clamp user-provided tile parameters.  An illegal tile is a
    caller bug; silently shrinking it makes autotune measurements lie
    about the config they claim to measure (the PR 9 rule — validation
    lives in `kernels.tiles.check_tile`, which raises).

Pure stdlib + the jax-free registry; `python -m repro lint` never
imports jax (subprocess-tested alongside the verifier).
"""
from __future__ import annotations

import ast
import fnmatch
from pathlib import Path
from typing import List, Optional, Set

from repro.analysis.verify import SEV_ERROR, Diagnostic

LINT_RULES = {
    "lint.import-light": "no top-level jax imports in planning/graph/"
                         "measure/serving modules",
    "lint.registry-complete": "every op kind has codec + features + "
                              "tiles + lowering + axes-or-splittable",
    "lint.no-silent-clamp": "kernel entry points never min()-clamp "
                            "user tile params",
}

#: modules (relative to the repro package) bound by the import-light
#: contract.  Execution layers (runtime/executor, runtime/segments,
#: core/coexec, kernels/*/ops, launch/, models/, serving is control-plane
#: so it IS bound) are exempt by omission.
IMPORT_LIGHT_GLOBS = (
    "__init__.py", "__main__.py", "api.py", "cli.py", "compile_cache.py",
    "graph/*.py", "measure/*.py", "serving/*.py", "analysis/*.py",
    "core/*.py", "core/predictor/*.py", "core/simulator/*.py",
    "runtime/__init__.py", "runtime/plan.py", "runtime/cache.py",
    "runtime/autotune.py",
    "kernels/__init__.py", "kernels/registry.py", "kernels/tiles.py",
)

#: core/coexec.py is the execution sync layer — it owns the device
#: streams the paper's co-execution mechanisms synchronize, so it is
#: jax-bound by design even though it lives under core/.
IMPORT_LIGHT_EXEMPT = {"core/coexec.py"}

#: parameter names that carry user tile choices into kernel entry points
_TILE_PARAM_NAMES = {"tile", "tiles", "bm", "bn", "bk", "bs", "chunk"}


def _err(rule: str, node: str, message: str, hint: str = "") -> Diagnostic:
    return Diagnostic(SEV_ERROR, rule, node, message, hint)


def package_root() -> Path:
    """The repro package directory the default lint run scans."""
    return Path(__file__).resolve().parents[1]


# --------------------------------------------------------- import-light

def _is_type_checking(test: ast.expr) -> bool:
    return (isinstance(test, ast.Name) and test.id == "TYPE_CHECKING") or \
        (isinstance(test, ast.Attribute) and test.attr == "TYPE_CHECKING")


def _jax_imports(tree: ast.Module) -> List[int]:
    """Line numbers of module-scope jax imports (TYPE_CHECKING-guarded
    blocks excluded; function bodies are not module scope)."""
    lines: List[int] = []

    def visit(stmts, guarded: bool) -> None:
        for s in stmts:
            if isinstance(s, ast.Import):
                if not guarded and any(
                        a.name == "jax" or a.name.startswith("jax.")
                        for a in s.names):
                    lines.append(s.lineno)
            elif isinstance(s, ast.ImportFrom):
                mod = s.module or ""
                if not guarded and (mod == "jax" or
                                    mod.startswith("jax.")):
                    lines.append(s.lineno)
            elif isinstance(s, ast.If):
                visit(s.body, guarded or _is_type_checking(s.test))
                visit(s.orelse, guarded)
            elif isinstance(s, ast.Try):
                for blk in [s.body, s.orelse, s.finalbody,
                            *[h.body for h in s.handlers]]:
                    visit(blk, guarded)
            elif isinstance(s, (ast.With, ast.ClassDef)):
                visit(s.body, guarded)

    visit(tree.body, False)
    return lines


def lint_import_light(pkg: Path) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    for path in sorted(pkg.rglob("*.py")):
        rel = path.relative_to(pkg).as_posix()
        if rel in IMPORT_LIGHT_EXEMPT:
            continue
        if not any(fnmatch.fnmatch(rel, g) for g in IMPORT_LIGHT_GLOBS):
            continue
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError as e:
            diags.append(_err("lint.import-light", f"{rel}:{e.lineno}",
                              f"does not parse: {e.msg}"))
            continue
        for lineno in _jax_imports(tree):
            diags.append(_err(
                "lint.import-light", f"{rel}:{lineno}",
                "top-level jax import in an import-light module",
                "move the import inside the functions that use it (or "
                "under `if TYPE_CHECKING:` for annotations)"))
    return diags


# -------------------------------------------------- registry completeness

def lint_registry(pkg: Path) -> List[Diagnostic]:
    from repro.kernels import registry
    diags: List[Diagnostic] = []
    kinds = registry.kinds()
    codec_kinds = set(registry._KIND_BY_TYPE.values())
    if codec_kinds != set(kinds):
        diags.append(_err(
            "lint.registry-complete", "registry",
            f"op codec covers {sorted(codec_kinds)} but the registry "
            f"declares {kinds}"))
    for kind in kinds:
        entry = registry.get(kind)
        loc = f"registry:{kind}"
        for field in ("input_shape", "weight_shape", "output_shape",
                      "base_features"):
            if not callable(getattr(entry, field, None)):
                diags.append(_err("lint.registry-complete", loc,
                                  f"kind lacks a callable {field!r}"))
        if not entry.splittable and not entry.axes:
            diags.append(_err(
                "lint.registry-complete", loc,
                "kind is neither channel-splittable nor declares typed "
                "axes — the planner can never co-execute or even place "
                "it deliberately",
                "declare AxisSpecs or set splittable=True"))
        try:
            registry.tile_spec(kind)
        except KeyError:
            diags.append(_err("lint.registry-complete", loc,
                              "kind has no TileSpec",
                              "register it in _TILE_SPECS"))
        if entry.modes and registry.default_mode(kind) != entry.modes[0]:
            diags.append(_err("lint.registry-complete", loc,
                              "default_mode disagrees with the entry's "
                              "declared mode order"))
        mod = registry._LOWERING_MODULES.get(kind)
        if mod is None:
            diags.append(_err("lint.registry-complete", loc,
                              "kind has no lowering module mapping",
                              "add it to _LOWERING_MODULES"))
            continue
        # the ops module imports jax, so check the registration call
        # textually instead of importing it
        ops_path = pkg / Path(*mod.split(".")[1:]).with_suffix(".py")
        if not ops_path.is_file():
            diags.append(_err("lint.registry-complete", loc,
                              f"lowering module {mod} has no source file"))
        elif f'register_lowering("{kind}"' not in ops_path.read_text():
            diags.append(_err(
                "lint.registry-complete", loc,
                f"lowering module {mod} never calls "
                f"register_lowering({kind!r})"))
    return diags


# --------------------------------------------------------- no-silent-clamp

def lint_silent_clamp(pkg: Path) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    for path in sorted((pkg / "kernels").rglob("*.py")):
        if path.name in ("registry.py", "tiles.py", "__init__.py"):
            continue
        rel = path.relative_to(pkg).as_posix()
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError:
            continue                       # import-light pass reports these
        for fn in [n for n in ast.walk(tree)
                   if isinstance(n, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))]:
            args = fn.args
            params: Set[str] = {a.arg for a in
                                [*args.posonlyargs, *args.args,
                                 *args.kwonlyargs]} & _TILE_PARAM_NAMES
            if not params:
                continue
            for call in ast.walk(fn):
                if not (isinstance(call, ast.Call)
                        and isinstance(call.func, ast.Name)
                        and call.func.id == "min"):
                    continue
                touched = {n.id for a in call.args
                           for n in ast.walk(a)
                           if isinstance(n, ast.Name)} & params
                if touched:
                    diags.append(_err(
                        "lint.no-silent-clamp",
                        f"{rel}:{call.lineno}",
                        f"{fn.name}() min()-clamps tile param(s) "
                        f"{sorted(touched)}",
                        "validate via kernels.tiles.check_tile (raise on "
                        "illegal) instead of silently shrinking"))
    return diags


# ----------------------------------------------------------------- driver

def lint_repo(pkg: Optional[Path] = None) -> List[Diagnostic]:
    """Run every repo-contract lint over the repro package tree."""
    pkg = package_root() if pkg is None else Path(pkg)
    diags: List[Diagnostic] = []
    diags.extend(lint_import_light(pkg))
    diags.extend(lint_registry(pkg))
    diags.extend(lint_silent_clamp(pkg))
    return diags
