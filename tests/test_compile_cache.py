"""The persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to one fixed place in the checkout (``repro.compile_cache``).

Each case compiles in a fresh subprocess: the cache directory is process
state that JAX fixes on first use.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_default_dir_is_in_the_checkout(tmp_path, monkeypatch):
    """Derived from the package's location, not the working directory."""
    monkeypatch.chdir(tmp_path)
    assert compile_cache.DEFAULT_DIR == ROOT / ".cache" / "jax-compile"


def _compile_in_child(tmp_path, env_dir):
    """Enable the cache in a child with its default moved under
    ``tmp_path``, compile one small program, and return the default dir."""
    default = tmp_path / "default"
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
               # cache every program, however quick or small
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    body = f"""
        from pathlib import Path
        import jax, jax.numpy as jnp
        from repro import compile_cache
        compile_cache.DEFAULT_DIR = Path({str(default)!r})
        print("DIR", compile_cache.enable())
        jax.jit(lambda x: jnp.sin(x) * 2 + 1)(jnp.arange(8.0)).block_until_ready()
    """
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, env=env, cwd=tmp_path,
                       timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr[-3000:]
    return default, r.stdout


def _entries(d: Path) -> list[Path]:
    return [p for p in d.rglob("*") if p.is_file()] if d.exists() else []


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "default"])
def test_compiled_entries_land_in_one_place(tmp_path, env_set):
    env_dir = tmp_path / "from_env" if env_set else None
    default, out = _compile_in_child(tmp_path, env_dir)
    where = env_dir if env_set else default
    assert f"DIR {where}" in out
    assert _entries(where), f"no compiled entry under {where}"
    if env_set:
        assert not _entries(default), "the default dir was written too"
