"""Async-aware span tracer — the hot-path measurement layer.

The north-star BLS gap was unmeasurable: bench rounds died in opaque
backend-init probes and tier-1 stalls in cold Mosaic compiles with
nothing naming where the time went.  This tracer makes the hot paths
(gossip -> verify -> import, kernel compile) emit SPANS —
named, timestamped, parent-linked intervals — into a bounded
ring buffer that two sinks consume:

  - Chrome ``trace_event`` JSON (sinks.dump_chrome_trace /
    GET /trace on utils/metrics_server.py) for offline flamegraphs,
  - derived per-span-name histograms in the process-global
    utils/metrics.py Registry, so every span family also lands on
    /metrics without separate instrumentation.

Design constraints, in order:

  1. **Near-zero cost when disabled.**  ``trace_span`` is one object
     allocation and one flag check per call when tracing is off
     (asserted in tests/test_observability.py); call sites that want
     even that gone guard on ``enabled()``.
  2. **Async-aware parenting.**  The current span rides a
     ``contextvars.ContextVar``, so ``asyncio`` tasks inherit their
     creator's span as parent (task creation copies the context) and
     concurrent tasks cannot corrupt each other's lineage.  Threads do
     NOT inherit context; cross-thread links pass an explicit
     ``parent_id`` (bls/service.py's dispatcher does).
  3. **Bounded memory.**  The ring keeps the most recent N finished
     spans (``LODESTAR_TPU_TRACE=N`` sets N; ``=1`` uses the default
     capacity); recording is O(1) under a small lock.

Enable with ``LODESTAR_TPU_TRACE=1`` (or ``=N`` for a capacity) or at
runtime with ``configure(enabled=True)``.
"""

from __future__ import annotations

import contextvars
import functools
import itertools
import os
import sys
import threading
import time
from collections import deque
from typing import Callable, List, Optional, Tuple

DEFAULT_CAPACITY = 65536

# the current span's id, propagated into asyncio tasks automatically
_CURRENT: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "lodestar_tpu_trace_span", default=None
)

# monotonic origin so span timestamps are comparable process-wide
_T0_NS = time.perf_counter_ns()


def _parse_env(raw: Optional[str]):
    """LODESTAR_TPU_TRACE: unset/0/false -> disabled; 1/true -> default
    capacity; an integer N > 1 -> enabled with ring capacity N."""
    if raw is None:
        return False, DEFAULT_CAPACITY
    val = raw.strip().lower()
    if val in ("", "0", "false", "no", "off"):
        return False, DEFAULT_CAPACITY
    try:
        n = int(val)
    except ValueError:
        return True, DEFAULT_CAPACITY
    if n <= 0:
        return False, DEFAULT_CAPACITY
    return True, (DEFAULT_CAPACITY if n == 1 else n)


class SpanRecord:
    """One finished span.  Times are µs from the process trace origin
    (monotonic), matching Chrome trace_event's ``ts``/``dur`` fields."""

    __slots__ = ("name", "span_id", "parent_id", "tid", "ts_us", "dur_us", "attrs")

    def __init__(self, name, span_id, parent_id, tid, ts_us, dur_us, attrs):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.tid = tid
        self.ts_us = ts_us
        self.dur_us = dur_us
        self.attrs = attrs

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "tid": self.tid,
            "ts_us": self.ts_us,
            "dur_us": self.dur_us,
            "attrs": dict(self.attrs),
        }


class Tracer:
    """Bounded, thread-safe store of finished spans + sink fan-out."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = capacity
        self._ring: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        # sink callbacks fn(record) run at span finish (must be cheap
        # and non-blocking: the registry-histogram sink qualifies)
        self._sinks: List[Callable[[SpanRecord], None]] = []

    def next_id(self) -> int:
        return next(self._ids)

    def record(self, rec: SpanRecord) -> None:
        with self._lock:
            self._ring.append(rec)
        for sink in self._sinks:
            try:
                sink(rec)
            except Exception:  # noqa: BLE001 — a broken sink must never
                pass  # take down the traced hot path

    def add_sink(self, sink: Callable[[SpanRecord], None]) -> None:
        if sink not in self._sinks:
            self._sinks.append(sink)

    def snapshot(self) -> List[SpanRecord]:
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def __len__(self) -> int:
        return len(self._ring)


class _State:
    __slots__ = ("enabled", "tracer")

    def __init__(self, enabled: bool, tracer: Tracer):
        self.enabled = enabled
        self.tracer = tracer


_env_enabled, _env_capacity = _parse_env(os.environ.get("LODESTAR_TPU_TRACE"))
_STATE = _State(_env_enabled, Tracer(_env_capacity))


def enabled() -> bool:
    """Ultra-hot call sites guard attr computation on this."""
    return _STATE.enabled


def get_tracer() -> Tracer:
    return _STATE.tracer


def current_id() -> Optional[int]:
    """The active span's id in THIS context (None when disabled or no
    span is open) — capture it to parent spans across threads."""
    if not _STATE.enabled:
        return None
    return _CURRENT.get()


def configure(
    enabled: Optional[bool] = None, capacity: Optional[int] = None
) -> Tracer:
    """Runtime (re)configuration — tests and the node CLI use this
    instead of re-importing with a different env.  Changing capacity
    swaps in a fresh ring (old spans are dropped); sinks carry over."""
    if capacity is not None and capacity != _STATE.tracer.capacity:
        fresh = Tracer(capacity)
        fresh._sinks = list(_STATE.tracer._sinks)
        _STATE.tracer = fresh
    if enabled is not None:
        _STATE.enabled = enabled
    return _STATE.tracer


class trace_span:
    """``with trace_span("bls.verify", batch=n): ...`` — or as a
    decorator, ``@trace_span("chain.import")``.

    When tracing is disabled ``__enter__`` is a flag check; the
    decorator form re-checks per call, so enabling at runtime
    activates already-decorated functions.  ``parent_id`` overrides
    contextvar parenting for cross-thread links.

    When tracing is enabled and JAX is already imported, the span also
    holds a ``jax.profiler.TraceAnnotation`` of the same name, so a
    profiler capture shows every program span on the thread that ran
    it, on the device trace's clock."""

    __slots__ = (
        "name", "attrs", "parent_id", "_span_id", "_t0", "_token", "_ann"
    )

    def __init__(self, name: str, parent_id: Optional[int] = None, **attrs):
        self.name = name
        self.attrs = attrs
        self.parent_id = parent_id
        self._span_id = None
        self._t0 = 0
        self._token = None
        self._ann = None

    def set(self, **attrs) -> "trace_span":
        """Attach attributes mid-span (no-op when disabled)."""
        if self._span_id is not None:
            self.attrs.update(attrs)
        return self

    def __enter__(self) -> "trace_span":
        if not _STATE.enabled:
            return self
        tracer = _STATE.tracer
        self._span_id = tracer.next_id()
        if self.parent_id is None:
            self.parent_id = _CURRENT.get()
        self._token = _CURRENT.set(self._span_id)
        # only a process that already has JAX: importing it here would
        # pull JAX into processes that never use it
        profiler = sys.modules.get("jax.profiler")
        if profiler is not None:
            self._ann = profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._span_id is None:
            return False
        return self._close(time.perf_counter_ns(), exc_type)

    def _close(self, t1: int, exc_type) -> bool:
        span_id = self._span_id
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._token is not None:
            _CURRENT.reset(self._token)
            self._token = None
        self._span_id = None
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        _STATE.tracer.record(
            SpanRecord(
                self.name,
                span_id,
                self.parent_id,
                threading.get_ident(),
                (self._t0 - _T0_NS) // 1000,
                (t1 - self._t0) // 1000,
                self.attrs,
            )
        )
        return False

    def __call__(self, fn: Callable) -> Callable:
        name, attrs = self.name, self.attrs

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not _STATE.enabled:
                return fn(*args, **kwargs)
            with trace_span(name, **attrs):
                return fn(*args, **kwargs)

        return wrapper


class timed_span(trace_span):
    """``with timed_span("bls.job.encode", (hist, "encode")): ...`` — a
    trace_span whose one ``perf_counter`` pair also feeds
    ``hist.observe(label, seconds)``.  The observation is always made,
    tracing on or off; the span only when tracing is on.  A context
    manager, not a wrapper: code that traces a kernel keeps its stack."""

    __slots__ = ("_hist", "_label")

    def __init__(self, name: str, observe: Tuple[object, str], **attrs):
        super().__init__(name, **attrs)
        self._hist, self._label = observe

    def __enter__(self) -> "timed_span":
        super().__enter__()
        if self._span_id is None:
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter_ns()
        self._hist.observe(self._label, (t1 - self._t0) / 1e9)
        if self._span_id is None:
            return False
        return self._close(t1, exc_type)
