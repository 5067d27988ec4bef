"""JAX's persistent compilation cache for the repository's entry points.

The serving kernels take tens of seconds to compile for a TPU (the fused
insert+query wave kernel alone takes about half a minute at deployment
widths), and each pow2 wave bucket is its own program.  Every entry point
(``chip_smoke.py``, ``benchmarks/run.py``, ``benchmarks/serve_bench.py``)
calls ``enable()`` before its first compile so a second run in the same
environment reads its executables back instead of recompiling them.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory.  Otherwise the cache lives at the fixed
path ``<repo root>/.jax_cache`` (listed in ``.gitignore``): a fixed path,
never one built from a temp name, pid or time, so later runs find it.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["ENV", "REPO_CACHE_DIR", "enable"]

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    # keep every program, not only those over a second to compile: a
    # serving run compiles hundreds of small ones, which add up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
