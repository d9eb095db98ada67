"""Compilation helpers shared by the entry points and benchmarks.

``Compiled.cost_analysis()`` returns a plain dict on recent JAX but a
one-element list of dicts on older releases (e.g. 0.4.x); every consumer of
the dry-run lowering path and the cost-model benchmarks goes through
:func:`cost_analysis_dict` so the difference is absorbed in one place.

:func:`enable_compile_cache` turns on JAX's persistent compilation cache for
an entry point (``launch/serve.py``, ``chip_smoke.py``); it is never called
at import time.
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: a fixed path, because a cache directory that moves
# between runs never hits
_CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cost_analysis_dict(compiled) -> dict:
    """HLO cost analysis of a compiled executable as a flat dict."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE_DIR))
    return str(_CHECKOUT_CACHE_DIR)
