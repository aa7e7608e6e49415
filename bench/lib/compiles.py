"""Counts of executables built (compiled, or loaded from the persistent
cache) and of persistent-cache hits, from JAX's monitoring events, so a
run can say what was built inside its window."""
from __future__ import annotations

import collections
import contextlib

_BUILT = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_counts: collections.Counter = collections.Counter()
_installed = False


def _on_duration(event, duration_secs, **kwargs):
    if event == _BUILT:
        _counts["built"] += 1
        _counts["built_s"] += duration_secs


def _on_event(event, **kwargs):
    if event == _HIT:
        _counts["cache_hits"] += 1


def install() -> None:
    global _installed
    if not _installed:
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(_on_duration)
        mon.register_event_listener(_on_event)
        _installed = True


def snapshot() -> dict:
    return {k: _counts[k] for k in ("built", "built_s", "cache_hits")}


def since(before: dict) -> dict:
    now = snapshot()
    return {k: now[k] - before[k] for k in now}


@contextlib.contextmanager
def window():
    """Counts what is built inside a measured window.  Yields the counts,
    filled at exit."""
    before = snapshot()
    counts: dict = {}
    try:
        yield counts
    finally:
        counts.update(since(before))
