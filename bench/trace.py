"""From a JAX profiler trace to device busy time, op times and idle gaps.

``capture`` records one window of the process's own device (only the
process that holds the chip can trace it), with the Python tracer off so
that tracing costs the host little.  ``load`` reads the ``.xplane.pb``
with nothing but JAX: each device plane's ``XLA Ops`` line holds one event
per operation run on that core, on the same clock as the host threads'
events (JAX's own, such as ``PjitFunction(...)``, and the benchmark's
``TraceAnnotation`` spans).  ``reduce`` makes the numbers the metrics and
the ``breakdown`` read.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, List, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
# host events that say nothing about what the host was doing
_NOISE = re.compile(r"^(ThreadpoolListener|\$)")


def options():
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 2
    return o


def start(directory: str) -> None:
    import jax
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory, exist_ok=True)
    jax.profiler.start_trace(directory, profiler_options=options())


def stop(directory: str) -> str:
    """Stop tracing; the path of the trace file written."""
    import jax
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one trace under {directory}, found "
                           f"{found}")
    return found[0]


@dataclasses.dataclass
class Event:
    name: str
    start: float      # ns from the start of the trace
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def op(self) -> str:
        """The op's HLO name without its instance number: the trace
        names a device op by its HLO text, ``%copy.75 = bf16[...] ...``."""
        return _OP.match(self.name).group(1)


_OP = re.compile(r"%?(.*?)(?:\.\d+)?(?: = .*)?$", re.S)


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]     # device plane -> its ops
    host: List[Event]                   # every host thread's events


def _event(e) -> Event:
    return Event(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = sorted(
                        (_event(e) for e in line.events),
                        key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_event(e) for e in line.events
                            if e.duration_ns > 0
                            and not _NOISE.match(e.name))
    return Trace(devices, host)


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping [start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def leaves(evs: List[Event]) -> List[Event]:
    """The ops that contain no other op: a loop (``while``) or a call
    holds its body's ops, which are counted in their own right."""
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt.start >= e.end]


class _Host:
    """Host events as arrays, to find what ran at an instant."""

    def __init__(self, host: List[Event]):
        self.names = [h.name for h in host]
        self.start = np.array([h.start for h in host])
        self.end = np.array([h.end for h in host])

    def label(self, t: float) -> str:
        """The innermost (shortest) host event running at time ``t``."""
        on = np.flatnonzero((self.start <= t) & (t < self.end))
        if not on.size:
            return "(no host event)"
        return self.names[on[np.argmin(self.end[on] - self.start[on])]]


@dataclasses.dataclass
class Reduced:
    window_ns: float
    busy_ns: float                      # mean over the device planes
    ops: List[Tuple[str, float]]        # leaf op -> device ns, largest first
    idle: List[Tuple[str, float]]       # host label -> idle ns, largest first
    kernel_ns: Dict[str, float]         # pattern name -> device ns of the
    #                                     leaf ops whose name it matches

    def breakdown(self, top: int = 10) -> dict:
        return {"device_ops": [[n, t / 1e9] for n, t in self.ops[:top]],
                "idle_gaps": [[n, t / 1e9] for n, t in self.idle[:top]]}


def reduce(tr: Trace, window: Tuple[float, float],
           kernels: Dict[str, "re.Pattern"]) -> Reduced:
    """Busy time, op totals, kernel times and labelled idle time of the
    ops inside ``window`` (ns from the start of the trace)."""
    lo, hi = window
    host = _Host(tr.host)
    busy, ops, kern, idle = [], {}, {k: 0.0 for k in kernels}, {}
    for evs in tr.devices.values():
        ivs = [(max(e.start, lo), min(e.end, hi)) for e in evs]
        for e in leaves(evs):
            s, t = max(e.start, lo), min(e.end, hi)
            if t <= s:
                continue
            op = e.op
            ops[op] = ops.get(op, 0.0) + (t - s)
            for k, pat in kernels.items():
                if pat.search(op):
                    kern[k] += t - s
        merged = union([(s, t) for s, t in ivs if t > s])
        busy.append(sum(t - s for s, t in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, t in zip(edges[::2], edges[1::2]):
            if t > s:
                name = host.label((s + t) / 2)
                idle[name] = idle.get(name, 0.0) + t - s
    n = max(len(tr.devices), 1)
    return Reduced(
        window_ns=hi - lo, busy_ns=float(np.sum(busy)) / n,
        ops=sorted(ops.items(), key=lambda kv: -kv[1]),
        idle=sorted(((k, v / n) for k, v in idle.items()),
                    key=lambda kv: -kv[1]),
        kernel_ns={k: v / n for k, v in kern.items()})
