"""Persistent XLA compile cache for process entry points.

Called by the programs that run on the chip (chip_smoke.py,
chipbench/run.py, examples/*) — never at import time of a library
module.  The directory is part of the cache key, so it must not move:
``JAX_COMPILATION_CACHE_DIR`` decides when it is set (then nothing is
set in code — jax reads the variable itself), otherwise the cache lives
at ``<checkout>/.jax_cache``, derived from this file's own location.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ENV = "JAX_COMPILATION_CACHE_DIR"
_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
_counts = {"hits": 0, "misses": 0}
_listening = False


def _on_event(event: str, **_kw) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        _counts[key] += 1


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory."""
    import jax
    path = os.environ.get(_ENV)
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # A Mosaic kernel is serialized into its custom call WITH its MLIR
    # locations, and those carry the Python call stack by default: the
    # same train step traced from another caller would then hash to
    # another key.  Innermost-frame locations keep the key a function
    # of the program alone.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    # keep the engine's small programs (sub-second compiles) too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    global _listening
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return path


def compile_cache_stats() -> dict:
    """Persistent-cache hits/misses of this process since
    ``enable_compile_cache()`` (jax's own monitoring events)."""
    return dict(_counts)
