"""Persistent XLA compile cache for the launch drivers and `chip_smoke.py`.

`enable_persistent_cache()` points jax's compilation cache at
`$JAX_COMPILATION_CACHE_DIR` when that is set, and otherwise at the
fixed repo-local `.jax_cache/` (the path is part of what makes a later
process hit: a directory that moves never does). It also drops the
size/compile-time admission thresholds so even the smoke-scale programs
are cached. The effect is cross-PROCESS: the first run pays the full
XLA wall and seeds the cache; every later run of the same program (same
arch/shape/mesh/donation/sharding signature) deserializes the executable
instead of recompiling — `--aot-warmup` then reports a near-zero compile
wall (scripts/ci.sh gates the second run at <20% of the first).

Why a module and not three lines in each driver: the cache only helps
if every entry point configures it IDENTICALLY (the cache key includes
compile options, not the config source), and `jax.config.update` after
a backend is initialized is where subtle breakage lives — keeping the
calls in one place keeps the drivers honest.
"""
from __future__ import annotations

import os

import jax
from jax.experimental.compilation_cache import compilation_cache

REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def cache_dir() -> str:
    """$JAX_COMPILATION_CACHE_DIR if set, else the repo's `.jax_cache/`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR


def enable_persistent_cache() -> str:
    """Enable jax's persistent compilation cache at `cache_dir()`;
    returns the directory used. Idempotent — safe to call from every
    driver entry point, before or after backend init."""
    d = cache_dir()
    os.makedirs(d, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", d)
    # admit EVERYTHING: the smoke programs compile in <1s and would be
    # rejected by the default 1s/small-entry thresholds, but they are
    # exactly what ci.sh re-runs
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # the cache module latches its state at the process's FIRST
    # compile; without a reset, enabling after any jit ran is a no-op
    compilation_cache.reset_cache()
    return d


def warmup(scheme) -> float:
    """AOT-compile `scheme`'s round program (schemes exposing
    `warmup_compile`) and return the compile wall seconds; 0.0 when the
    scheme has no AOT path (the tiny parity schemes compile lazily)."""
    fn = getattr(scheme, "warmup_compile", None)
    return float(fn()) if fn is not None else 0.0
