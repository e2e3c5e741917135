"""Device idle time inside the program's own host spans.

The program marks its host work with spans on the profiler's clock
(``repro.core.spans``): the worker's ``sched.assemble``,
``sched.dispatch``, ``sched.fetch``, ``sched.select`` and ``sched.admit``
among them.  ``idle_inside`` gives the part of the device's idle time
that lies inside such spans, so a gap the step loop leaves is told apart
by what the host was doing in it.  ``share`` is that part as a percent
of a traced run's window, or None where the trace holds no such span, as
in a program without them.
"""
from __future__ import annotations

import functools
import glob
import os
import re
from typing import List, Optional, Tuple

from . import cell, trace

Intervals = List[Tuple[float, float]]


def _clip(ivs, lo: float, hi: float) -> Intervals:
    return trace.union([(max(s, lo), min(e, hi)) for s, e in ivs
                        if min(e, hi) > max(s, lo)])


def _overlap(a: Intervals, b: Intervals) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_inside(tr: trace.Trace, window: Tuple[float, float],
                pattern: str) -> float:
    """Device-idle ns inside ``window`` (ns from the start of the trace)
    that lie inside the union of the host events whose name matches
    ``pattern``, averaged over the device planes.  A gap that a span
    covers in part counts only the part the span covers."""
    lo, hi = window
    pat = re.compile(pattern)
    spans = _clip([(h.start, h.end) for h in tr.host if pat.search(h.name)],
                  lo, hi)
    total = 0.0
    for evs in tr.devices.values():
        busy = _clip([(e.start, e.end) for e in evs], lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(s, t) for s, t in zip(edges[::2], edges[1::2]) if t > s]
        total += _overlap(idle, spans)
    return total / max(len(tr.devices), 1)


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int) -> trace.Trace:
    return trace.load(path)


def loaded() -> Optional[trace.Trace]:
    """The trace a traced run wrote (``cell.TRACE_DIR``), loaded once for
    all readers; ``cell.Context`` does not carry it."""
    found = glob.glob(os.path.join(str(cell.TRACE_DIR), "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        return None
    return _load(found[0], os.stat(found[0]).st_mtime_ns)


def share(ctx, pattern: str) -> Optional[float]:
    """Percent of the traced window in which the device was idle inside
    the host spans matching ``pattern``."""
    tr = loaded()
    if tr is None or ctx.window_s <= 0 or not tr.devices \
            or not any(re.search(pattern, h.name) for h in tr.host):
        return None
    window_ns = ctx.window_s * 1e9
    return 100.0 * idle_inside(tr, (0.0, window_ns), pattern) / window_ns
