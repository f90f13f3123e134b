"""Where JAX's persistent compilation cache lives.

Layers are a Python loop, so every serving program re-traces and compiles
the block once per layer; at real widths a cold start is mostly compiling.
The persistent cache makes that a one-time cost per (program, machine) —
but only if every process of a deployment agrees on ONE directory, and the
directory does not move: its path is part of how an entry is found, so a
temp name, a pid or a timestamp never hits.

``JAX_COMPILATION_CACHE_DIR`` places the cache from outside. JAX honours it
by itself; when it is set nothing is set in code. When it is not, the cache
goes to one fixed, git-ignored directory inside the checkout. Import-light
on purpose: entry points call :func:`ensure_compile_cache` before anything
imports JAX.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

__all__ = ["ENV_VAR", "DEFAULT_DIR", "ensure_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_compile_cache"


def ensure_compile_cache() -> str:
    """Call first thing in every entry point that compiles (``cli`` main,
    the replica worker entries, the smoke). Returns the directory in
    effect. Child processes inherit the environment, so a whole process
    tree — router, workers, a smoke's server child — shares one cache."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    path = str(DEFAULT_DIR)
    os.environ[ENV_VAR] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        # already imported: the environment was read at import time
        jax.config.update("jax_compilation_cache_dir", path)
    return path
