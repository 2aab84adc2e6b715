"""Persistent XLA compilation cache for the command-line entry points.

A cold run on a chip can spend much of its time compiling; JAX's persistent
cache lets later processes reuse those programs.  The cache key includes the
directory, so it must not move between runs: it is the directory that
``JAX_COMPILATION_CACHE_DIR`` names when that is set (JAX reads the variable
itself), and otherwise the fixed ``<repo>/.jax_cache``.

Entry points call :func:`enable_compile_cache` at the start of ``main``;
importing ``repro`` changes no JAX setting.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
