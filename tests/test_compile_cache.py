"""The persistent compilation cache is placed from outside, or at a fixed
path in the checkout — never a temp, pid or time path.

JAX reads the location when it is imported, so these run in fresh
interpreters (this test process imported JAX at collection).
"""
import os
import subprocess
import sys
from pathlib import Path

from repro.compile_cache import CHECKOUT_CACHE, ENV, place_compile_cache

SRC = str(Path(__file__).resolve().parents[1] / "src")

_PROG = """
import sys
from repro.compile_cache import place_compile_cache
placed = place_compile_cache()
assert "jax" not in sys.modules, "the helper imported jax"
import jax
if sys.argv[1:] == ["compile"]:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(3.0)).block_until_ready()
print(placed)
print(jax.config.jax_compilation_cache_dir)
"""


def _run(env_dir=None, *args):
    env = {k: v for k, v in os.environ.items() if k != ENV}
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env[ENV] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _PROG, *args], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()[-2:]


def test_outside_setting_is_kept_and_the_cache_lands_there(tmp_path):
    where = tmp_path / "cache"
    placed, seen = _run(where, "compile")
    assert placed == seen == str(where)
    assert any(where.iterdir()), "nothing was written to the cache"


def test_default_is_the_fixed_checkout_path():
    placed, seen = _run()
    assert placed == seen == str(CHECKOUT_CACHE)
    assert CHECKOUT_CACHE.name == ".jax_cache"
    assert (CHECKOUT_CACHE.parent / "src" / "repro").is_dir()


def test_no_change_once_jax_is_imported(monkeypatch):
    import jax  # noqa: F401  (already imported by the suite)
    monkeypatch.delenv(ENV, raising=False)
    assert place_compile_cache() is None
    assert ENV not in os.environ
