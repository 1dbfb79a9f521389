"""The process's jit compiles and persistent-cache hits, counted once.

``jax.monitoring`` listeners cannot be taken off again, so they are put on
once per process and add to this module's totals, under one lock. Two
readers, neither of which the other knows: the engine reads :func:`count`
around every dispatch (it moves exactly when some jitted function was
traced and lowered anew, which is what ``retraced`` on a dispatch record
means), and the server's runtime telemetry copies :func:`totals` into its
``llm_jit_*`` counters at every scrape.
"""

from __future__ import annotations

import threading

_lock = threading.Lock()
_installed = False
_compiles = 0
_compile_seconds = 0.0
_cache_hits = 0


def install() -> None:
    """Put the listeners on; ``serve`` does so before its warmup compiles,
    so a warm restart's cache hits are on the first scrape."""
    global _installed
    from jax import monitoring

    with _lock:
        if _installed:
            return
        _installed = True
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


def _on_event(event: str, **kw) -> None:
    global _cache_hits
    if "cache_hit" in event:
        with _lock:
            _cache_hits += 1


def _on_duration(event: str, duration: float, **kw) -> None:
    global _compiles, _compile_seconds
    if "backend_compile" in event:
        with _lock:
            _compiles += 1
            _compile_seconds += max(0.0, duration)


def totals() -> tuple[int, float, int]:
    """(backend compiles, seconds in them, persistent-cache hits) since
    the listeners went on."""
    with _lock:
        return _compiles, _compile_seconds, _cache_hits


def count() -> int:
    """Backend compiles plus persistent-cache hits."""
    with _lock:
        return _compiles + _cache_hits
