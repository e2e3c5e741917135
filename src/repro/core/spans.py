"""Named host spans on the profiler's clock, with optional time counters.

``span(name, stats, key, **meta)`` marks a stretch of host work.  It
opens a ``jax.profiler.TraceAnnotation``, so while a profiler session
runs the span lands on the host plane of the same trace as the device's
ops, on the same clock, with ``meta`` (e.g. ``rid=7``) as its stats.
When no session runs the annotation records nothing.  Given a ``stats``
dict and a ``key``, the span also adds its ``perf_counter_ns`` duration
to ``stats[key]``, traced or not.

The RPC layer does not import JAX, and a process that has not imported
it runs no profiler: there a span only counts.
"""
from __future__ import annotations

import contextlib
import sys
import time
from typing import Dict, Iterator, Optional


@contextlib.contextmanager
def span(name: str, stats: Optional[Dict[str, int]] = None,
         key: Optional[str] = None, **meta) -> Iterator[None]:
    jax = sys.modules.get("jax")
    ann = (jax.profiler.TraceAnnotation(name, **meta) if jax is not None
           else contextlib.nullcontext())
    t0 = time.perf_counter_ns()
    try:
        with ann:
            yield
    finally:
        if stats is not None:
            stats[key] += time.perf_counter_ns() - t0
