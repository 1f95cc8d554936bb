"""JAX's persistent compilation cache for the entry point that runs on
the chip (chip_smoke.py).

Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets no other directory.  Otherwise the cache lives at one fixed path in
the checkout (``.jax_cache/``, git-ignored): the directory is part of
what a later run must find again, so it never takes a temp name, a pid
or a time.
"""

from __future__ import annotations

import os
from typing import Dict

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO, ".jax_cache")

_counts: Dict[str, int] = {"hits": 0, "misses": 0}
_listening = False


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _counts["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _counts["misses"] += 1


def enable_compile_cache() -> str:
    """Turn the persistent cache on (every compile is cached, however
    short) and count its hits and misses; returns the directory."""
    global _listening
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return path


def cache_counts() -> Dict[str, int]:
    """Persistent-cache hits and misses (writes) since enable."""
    return dict(_counts)
