"""Where the persistent XLA compilation cache lives.

The sweep compiles one program per bucket *shape* (DESIGN.md §11) and the
sharded search one per (mesh, population) layout (§13) — all of them
re-traced identically run after run. A persistent cache lets the second run
of the same campaign skip straight to execution — only if the next run
looks in the same directory, so it is a fixed path (never built from a temp
name, a pid or the time), chosen by `configure` in this order:

1. ``JAX_COMPILATION_CACHE_DIR``, where the environment sets it: jax reads
   the variable itself, and no other directory is set in code;
2. the directory given on the command line (``--compilation-cache DIR``);
3. `DEFAULT_DIR`, the fixed ``.jax_cache`` directory at the root of the
   checkout (listed in ``.gitignore``).

Every CLI entry point (``python -m repro.search``, ``sweep``, ``serve``,
``faults``) and ``chip_smoke.py`` call it before their first jit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def configure(cache_dir: str | None = None) -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``cache_dir`` (the CLI flag) is used only when ``JAX_COMPILATION_CACHE_DIR``
    is unset; with neither, the cache goes to `DEFAULT_DIR`. The size and
    compile-time thresholds drop to zero so the search programs — small, but
    re-traced per bucket shape — are cached too.
    """
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir
    path = os.path.abspath(os.path.expanduser(cache_dir or DEFAULT_DIR))
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
