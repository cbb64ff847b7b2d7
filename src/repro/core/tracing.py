"""Spans on the served path, written into the JAX profiler's own trace.

``span(name)`` marks one stretch of host work.  While tracing is
inactive (the default) it returns one shared no-op context after a
single flag check, and neither imports nor calls JAX: the HTTP frontend
runs without it.  While tracing is active it returns
``jax.profiler.TraceAnnotation(name)``, so a span that runs while a
profiler session is open lands in that session's trace, on its thread's
line and on the clock of the device planes; spans nested on one thread
nest in the trace.  The trace is the only store: it is written when the
profiler session stops.

``enable()`` / ``disable()`` flip the flag; whoever opens a profiler
session calls them around it.  The span names are read by name by the
benchmark's trace reduction (``bench/spans.py``):

``http.ask`` ``http.report`` ``http.tell`` ``http.request``
    one request on a dispatch lane, dequeue to encoded response
``http.read``
    one readable event on the IO thread
``study.lock_wait``
    acquiring a study's lock (not holding it)
``obs_cache.sync``
    pulling new completions and the pending view into a study's cache
``tpe.propose`` / ``tpe.readback``
    one TPE proposal call / its blocking read-back of the result
``pruner.should_prune``
    one pruning verdict
``wal.append`` / ``wal.fsync`` / ``wal.compact``
    one WAL record / one group-commit fsync / one compaction
"""
from __future__ import annotations

import contextlib
from typing import Any

_NOOP = contextlib.nullcontext()
_active = False
_annotation: Any = None


def enable() -> None:
    """Make ``span`` write profiler annotations from now on."""
    global _active, _annotation
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation
    _active = True


def disable() -> None:
    """Make ``span`` a no-op again."""
    global _active
    _active = False


def span(name: str) -> contextlib.AbstractContextManager:
    """Context manager marking one span named ``name``."""
    if _active:
        return _annotation(name)
    return _NOOP
