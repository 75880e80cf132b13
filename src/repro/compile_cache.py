"""Where JAX keeps its persistent compilation cache.

A program that compiles for the chip pays seconds to minutes per program
on a cold start.  JAX's persistent compilation cache keeps compiled
programs on disk, keyed in part by the cache directory's path, so the
directory must stay put from run to run.

`place_compile_cache()` is called by every entry point that runs JAX (the
CLI's execute/calibrate/tune/serve/bench commands, `benchmarks/common.py`
and `chip_smoke.py`) before JAX is imported.  A `JAX_COMPILATION_CACHE_DIR`
set from outside is left as it is, and nothing else is configured;
otherwise the cache goes to the fixed `<checkout>/.jax_cache`.  This module
imports no JAX.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path
from typing import Optional

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout that holds this package (src/repro/compile_cache.py)
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def place_compile_cache() -> Optional[str]:
    """Point JAX's compilation cache at a fixed directory; return it.

    JAX reads the variable when it is imported, so in a process that has
    already imported JAX nothing is changed, and the result is the
    variable's value (None when it is unset).
    """
    path = os.environ.get(ENV)
    if path or "jax" in sys.modules:
        return path
    os.environ[ENV] = str(CHECKOUT_CACHE)
    return os.environ[ENV]
