"""Wire->device ingest: RPC page payloads become model-ready tensors.

This is the serving half of the paper's "GPU-side deserialization for
direct device memory placement" future-work item (§8).  An inference
request arrives as a Bebop *page* (core/pages.py): a checksummed
``[N, stride]`` u8 matrix of fixed-layout records.  Admission does exactly
three things, none of which parses a value on the host:

  1. header validation (magic / version / CRC) — bounds the blast radius
     of a corrupt client before anything touches the device;
  2. raw device placement — the payload bytes are DMA'd to the accelerator
     unmodified;
  3. kernel decode — the ``bebop_decode`` Pallas kernel materializes every
     column in one pass over the page block, driven by a *decode plan*
     computed once per schema.

Plans are cached by the page header's ``schema_hash`` (murmur3+lowbias32 of
the schema name, the same 32-bit id the RPC router uses for methods), so
steady-state admission is a dict hit plus a device call.  The cache is the
serving analogue of bebopc compiling a schema ahead of time: layout
planning happens once, request handling never walks the type tree.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Dict, Iterator, Optional, Tuple

import numpy as np

from ..core import pages
from ..core import types as T
from ..core.device import (DeviceLayout, default_out_dtype,
                           plan_device_layout)
from ..core.hashing import schema_hash
from ..kernels import ops


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


_ALIGN = 64  # jax's CPU client takes a zero-copy path for 64B-aligned hosts


def _aligned_rows(payload: np.ndarray, rows: int) -> np.ndarray:
    """Stage ``payload`` as a 64B-aligned [rows, width / 4] u32 buffer.

    The decode kernel reads whole 32-bit words (kernels/bebop_decode.py),
    so each row is zero-padded to ``width``, the stride rounded up to 4
    bytes, and the buffer is handed over as little-endian words: a view,
    not a second copy.  Device placement of an aligned buffer avoids a
    copy inside the runtime (zero-copy / fast-path transfer), so the one
    memcpy here is the only time the payload bytes move on the host.
    Padding rows and bytes are zeroed — they decode to zeros that the
    caller slices off, and nothing uninitialized ever reaches the device.
    """
    n, stride = payload.shape
    width = -(-stride // 4) * 4
    if rows == n and width == stride and payload.flags["C_CONTIGUOUS"] \
            and payload.ctypes.data % _ALIGN == 0:
        return payload.view("<u4")
    buf = np.empty(rows * width + _ALIGN, np.uint8)
    off = (-buf.ctypes.data) % _ALIGN
    out = buf[off:off + rows * width].reshape(rows, width)
    out[:n, :stride] = payload
    out[:n, stride:] = 0
    out[n:] = 0
    return out.view("<u4")


@dataclasses.dataclass(frozen=True)
class DecodePlan:
    """Everything the decode kernel needs, precomputed per schema."""

    struct: T.Struct
    layout: DeviceLayout
    fields: Tuple[Tuple[int, int, str, str], ...]

    @property
    def stride(self) -> int:
        return self.layout.stride


class PlanCache:
    """schema_hash -> DecodePlan.  Thread-safe; hit/miss counters."""

    def __init__(self):
        self._plans: Dict[int, DecodePlan] = {}
        self._lock = threading.Lock()
        self.hits = 0    # guarded by _lock
        self.misses = 0  # guarded by _lock

    def register(self, s: T.Struct,
                 out_dtypes: Optional[Dict[str, str]] = None) -> DecodePlan:
        """Plan a struct's device layout and index it by schema hash."""
        layout = plan_device_layout(s)
        out_dtypes = out_dtypes or {}
        fields = tuple(
            c.as_field(out_dtypes.get(c.name, default_out_dtype(c.wire_dtype)))
            for c in layout.columns)
        plan = DecodePlan(s, layout, fields)
        with self._lock:
            self._plans[schema_hash(s.name)] = plan
        return plan

    def lookup(self, shash: int) -> DecodePlan:
        with self._lock:
            plan = self._plans.get(shash)
            if plan is None:
                self.misses += 1
            else:
                self.hits += 1
        if plan is None:
            raise pages.PageError(
                f"no decode plan registered for schema hash {shash:#010x}")
        return plan

    def __contains__(self, shash: int) -> bool:
        with self._lock:
            return shash in self._plans

    def __len__(self) -> int:
        with self._lock:
            return len(self._plans)


@dataclasses.dataclass
class IngestResult:
    """One admitted page: header + device-resident decoded columns."""

    header: pages.PageHeader
    plan: DecodePlan
    columns: Dict[str, Any]          # name -> [N, count] device array

    @property
    def record_count(self) -> int:
        return self.header.record_count


class PageIngest:
    """Admission path: raw page bytes -> device-decoded column tensors.

    ``block_n`` bounds the Pallas block height; short pages are zero-padded
    to a power-of-two row count before the kernel runs (padding rows decode
    to zeros and are sliced off — they are never read by the model), so
    the jit cache sees a small set of shapes instead of one per batch size.
    """

    def __init__(self, cache: Optional[PlanCache] = None, *,
                 block_n: int = 256, verify: bool = True,
                 impl: Optional[str] = None, device=None):
        self.cache = cache or PlanCache()
        self.block_n = block_n
        self.verify = verify
        self.impl = impl
        self.device = device
        self.stats = {"pages": 0, "records": 0, "payload_bytes": 0,
                      "rejected": 0}
        self._compiled: Dict[Tuple, Any] = {}

    def register(self, s: T.Struct,
                 out_dtypes: Optional[Dict[str, str]] = None) -> DecodePlan:
        return self.cache.register(s, out_dtypes)

    # -- admission -----------------------------------------------------------
    def admit(self, buf, offset: int = 0, *,
              expect_schema: Optional[str] = None,
              deadline=None) -> IngestResult:
        """Validate one page, place it on device, decode every column."""
        try:
            header = pages.read_header(buf, offset)
            if deadline is not None and deadline.expired():
                raise pages.PageError("deadline expired before placement")
            plan = self.cache.lookup(header.schema_hash)
            if header.record_stride != plan.stride:
                raise pages.PageError(
                    f"stride mismatch: page {header.record_stride}, "
                    f"plan {plan.stride}")
            payload = pages.read_payload(buf, offset, verify=self.verify,
                                         expect_schema=expect_schema)
        except pages.PageError:
            self.stats["rejected"] += 1
            raise
        columns = self._decode(payload, plan)
        self.stats["pages"] += 1
        self.stats["records"] += header.record_count
        self.stats["payload_bytes"] += header.record_count \
            * header.record_stride
        return IngestResult(header, plan, columns)

    def admit_stream(self, buf, *, cursor: int = 0,
                     deadline=None) -> Iterator[IngestResult]:
        """Admit consecutive pages, skipping whole pages below ``cursor``."""
        start = pages.seek_cursor(buf, cursor)
        if start is None:
            return
        for off in pages.iter_pages(buf):
            if off < start:
                continue
            yield self.admit(buf, off, deadline=deadline)

    # -- device decode -------------------------------------------------------
    def _decode_fn(self, fields: Tuple, block_n: int):
        """One jitted decode callable per (plan, block); shapes retrace."""
        import jax
        key = (fields, block_n)
        fn = self._compiled.get(key)
        if fn is None:
            fn = jax.jit(lambda p: ops.decode_columns(
                p, fields, block_n=block_n, impl=self.impl))
            self._compiled[key] = fn
        return fn

    def _decode(self, payload: np.ndarray, plan: DecodePlan
                ) -> Dict[str, Any]:
        import jax
        n = payload.shape[0]
        padded = min(self.block_n, _next_pow2(n))
        rows = (n + padded - 1) // padded * padded
        # raw bytes -> device as words, no parsing (aligned for
        # zero-copy placement)
        dev = jax.device_put(_aligned_rows(payload, rows), self.device)
        outs = self._decode_fn(plan.fields, padded)(dev)
        cols = {c.name: o[:n] if rows != n else o
                for c, o in zip(plan.layout.columns, outs)}
        return cols
