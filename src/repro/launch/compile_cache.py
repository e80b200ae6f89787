"""Persistent XLA compilation cache for the repo's entry points.

Chip compiles of the engine's programs take tens of seconds each, so the
entry-point scripts (``chip_smoke.py``, :mod:`repro.launch.serve_agg`,
:mod:`repro.launch.shard_agg`, ``benchmarks/_harness.py``) keep compiled
programs on disk.  Library code and tests never call this.
"""
from __future__ import annotations

import os
import pathlib

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: it is
    used as it is and nothing is set here.  Otherwise the cache lives at
    the fixed, git-ignored ``<repo>/.jax_cache`` — a fixed path, because
    the path is part of each entry's key."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
