"""Process-wide jit-wrapper memoization, and where compiled executables
persist between processes.

``jax.jit(fn)`` built fresh at a call site carries its own (empty)
compile cache — per-call construction recompiles identical executables,
the regression class dfanalyze's jaxhygiene pass fails on. ``jit_once``
is the shared fix: one wrapper per function object, every caller
(trainer eval paths, serving scorers) sharing one executable cache per
argument shape. Lazy jax import — callers like trainer/serving must
stay importable where jax isn't.
"""

# dfanalyze: device-hot — this module exists to construct jit wrappers

from __future__ import annotations

import os
from pathlib import Path

_jit_cache: dict = {}

# one fixed directory inside the checkout (git-ignored): the path is part
# of the persistent cache's key, so a directory that moves never hits
COMPILE_CACHE_DIR = Path(__file__).resolve().parent.parent.parent / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; entry points call this
    before their first JAX use. ``JAX_COMPILATION_CACHE_DIR`` places the
    cache from outside (JAX reads it itself, nothing is set here);
    unset, it goes to ``COMPILE_CACHE_DIR``. Returns the directory in
    use.

    The installed 1.0 s ``min_compile_time`` floor would skip exactly the
    executables this system has most of — the sub-second serving
    bucket-rung forwards and topology kernels, recompiled by every
    process start — so it is dropped to 0: every compile is stored."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(COMPILE_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def jit_once(fn):
    """The memoized ``jax.jit(fn)``: same function object → same
    wrapper, process-wide."""
    cached = _jit_cache.get(fn)
    if cached is None:
        import jax

        cached = _jit_cache[fn] = jax.jit(fn)
    return cached
