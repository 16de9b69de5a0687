"""Persistent XLA compilation cache, placed from outside.

One helper for every entry point that compiles for the chip (the train and
serve CLIs, ``chip_smoke.py``). The cache directory is part of the cache
key, so it must not move between runs: where ``JAX_COMPILATION_CACHE_DIR``
is set JAX already uses it and this module sets no directory at all;
otherwise the cache lives at one fixed path inside the checkout
(:data:`CHECKOUT_CACHE_DIR`, git-ignored).
"""

from __future__ import annotations

import os
from typing import Optional

# <checkout>/.jax_compile_cache — a pure function of where the package
# lives (no pid, time, hostname or CPU identity in the path)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_compile_cache")


def enable_compilation_cache() -> Optional[str]:
    """Turn the persistent compilation cache on for a TPU backend and
    return the directory in use (None when the cache stays off). Opt-out
    with ``DLION_COMPILE_CACHE=0``.

    TPU backend only: XLA:CPU AOT cache entries compiled on one host
    fatally abort the process when loaded on a host with different CPU
    features, and CPU compiles are fast enough that caching them buys
    little (ROADMAP tier-1 note). Initializes the backend — call it after
    ``jax.distributed.initialize()`` on multi-host launches."""
    import jax

    if os.environ.get("DLION_COMPILE_CACHE", "1") == "0":
        return None
    if jax.default_backend() != "tpu":
        return None
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache_dir
