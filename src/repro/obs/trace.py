"""Tracing core: spans, named counters, dispatch log, Chrome-trace export.

The software analogue of the paper's hardware performance-counter setup
(Sec. V): everything the runtime wants to measure funnels through this
module into one in-process ring buffer, and one exported artifact makes
a run auditable after the fact.

Design points:

* **Zero overhead when disabled.** `span()`/`counter()` return shared
  no-op singletons and `dispatch_event()` returns immediately; the only
  cost on the hot path is one module-global predicate. Enablement comes
  from the ``REPRO_OBS`` env at import (via `repro.obs.env`) or
  programmatically via `enable()`/`disable()`.
* **Thread-safe ring buffers.** Spans/instants land in a bounded
  `collections.deque` guarded by one lock; old events fall off the
  front instead of growing without bound under serving load.
* **Chrome trace-event export.** `chrome_trace()` renders the buffer as
  the trace-event JSON object form (openable in Perfetto /
  chrome://tracing); repo-specific payloads (generic counters, the
  per-(op, bits, backend, pipeline) op counters, the dispatch log) ride
  under a top-level ``"repro"`` key, which the format explicitly allows.
* **jax-aware, jax-free.** jax is imported lazily inside `time_call` /
  `Span.sync` only, so this module (and `repro.obs.env`) can load
  before jax initialises. A span around code under `jit` tracing would
  time the trace, once per compilation: the kernel entry points open
  none there (`kernels/api.py::_run_counted`).
* **One clock with the profiler.** With ``xla_annotations`` on, every
  span is also a `jax.profiler.TraceAnnotation`, so a profiled run shows
  the spans on the host plane of the same ``.xplane.pb`` as the device
  ops. `to_perf_counter` maps a span's ``ts`` onto
  `time.perf_counter()` seconds, the clock callers time windows with.
* **Loss is counted.** `dropped()` counts the events the ring buffer
  pushed out, so a reader can refuse a window that lost spans.

Timestamps are microseconds relative to a module-load epoch
(`perf_counter_ns`), matching the trace-event format's ``ts``/``dur``
unit.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

from repro.obs import env as obsenv

TRACE_SCHEMA_VERSION = 1
DEFAULT_CAPACITY = 100_000

_T0_NS = time.perf_counter_ns()
_LOCK = threading.RLock()
_EVENTS: deque = deque(maxlen=DEFAULT_CAPACITY)
_DISPATCH: deque = deque(maxlen=DEFAULT_CAPACITY)
_COUNTERS: Dict[str, "Counter"] = {}
_TIDS: Dict[int, int] = {}
_ENABLED = obsenv.get_bool("REPRO_OBS")
_XLA_ANNOTATIONS = False
_DROPPED = 0


def now_us() -> float:
    """The current time on the spans' clock (µs since the epoch)."""
    return (time.perf_counter_ns() - _T0_NS) / 1e3


def to_perf_counter(ts_us: float) -> float:
    """A span's ``ts`` (or ``ts + dur``) as `time.perf_counter()`
    seconds."""
    return (_T0_NS + ts_us * 1e3) * 1e-9


def _append(event: Dict[str, Any]) -> None:
    """Add one event to the ring buffer (caller holds ``_LOCK``),
    counting the oldest one it pushes out."""
    global _DROPPED
    if len(_EVENTS) == _EVENTS.maxlen:
        _DROPPED += 1
    _EVENTS.append(event)


def _tid() -> int:
    """Small stable per-thread id (trace viewers want dense tids)."""
    ident = threading.get_ident()
    with _LOCK:
        tid = _TIDS.get(ident)
        if tid is None:
            tid = _TIDS[ident] = len(_TIDS)
        return tid


# ------------------------------------------------------------- lifecycle ---

def enabled() -> bool:
    return _ENABLED


def enable(capacity: Optional[int] = None,
           xla_annotations: Optional[bool] = None) -> None:
    """Turn observability on; optionally resize the ring buffers and/or
    mirror spans into XLA profiles via `jax.profiler.TraceAnnotation`."""
    global _ENABLED, _EVENTS, _DISPATCH, _XLA_ANNOTATIONS, _DROPPED
    with _LOCK:
        if capacity is not None and capacity != _EVENTS.maxlen:
            _DROPPED += max(0, len(_EVENTS) - capacity)
            _EVENTS = deque(_EVENTS, maxlen=capacity)
            _DISPATCH = deque(_DISPATCH, maxlen=capacity)
        if xla_annotations is not None:
            _XLA_ANNOTATIONS = xla_annotations
        _ENABLED = True


def disable() -> None:
    global _ENABLED
    _ENABLED = False


def reset() -> None:
    """Drop all recorded events, dispatch entries, and generic counters
    (op counters live in `repro.obs.counters` — `repro.obs.reset()`
    clears both), and zero the drop count."""
    global _DROPPED
    with _LOCK:
        _DROPPED = 0
        _EVENTS.clear()
        _DISPATCH.clear()
        _COUNTERS.clear()


@contextmanager
def enabled_scope(xla_annotations: Optional[bool] = None):
    """Force-enable observability inside the block, restoring the prior
    state on exit — how benchmarks take counter readings without
    requiring ``REPRO_OBS`` in the environment."""
    global _ENABLED
    prev = _ENABLED
    enable(xla_annotations=xla_annotations)
    try:
        yield
    finally:
        _ENABLED = prev


# ------------------------------------------------------------------ spans ---

class Span:
    """One timed region. ``with span("qdot", cat="kernel", w_bits=4):``
    records an "X" (complete) trace event on exit carrying the attrs as
    ``args``. `set()` adds attrs mid-span; `sync(value)` blocks on a jax
    value so device time lands inside the span, and returns it."""

    __slots__ = ("name", "cat", "attrs", "_t0", "_ann")

    def __init__(self, name: str, cat: str, attrs: Dict[str, Any]):
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self._t0 = 0.0
        self._ann = None

    def __enter__(self) -> "Span":
        # the span's interval includes its profiler annotation: in a
        # profiled run that cost is host time spent in the region
        self._t0 = now_us()
        if _XLA_ANNOTATIONS:
            try:
                import jax
                self._ann = jax.profiler.TraceAnnotation(self.name)
                self._ann.__enter__()
            except Exception:
                self._ann = None
        return self

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def sync(self, value):
        try:
            import jax
            jax.block_until_ready(value)
        except Exception:
            pass
        return value

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        dur = now_us() - self._t0
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        if _ENABLED:
            with _LOCK:
                _append({
                    "name": self.name, "cat": self.cat, "ph": "X",
                    "ts": round(self._t0, 3), "dur": round(dur, 3),
                    "pid": 0, "tid": _tid(),
                    "args": dict(self.attrs)})
        return False


class _NullSpan:
    """Shared do-nothing span returned while observability is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self

    def sync(self, value):
        return value


_NULL_SPAN = _NullSpan()


def span(name: str, cat: str = "span", **attrs):
    """A context manager timing the enclosed block (no-op singleton when
    disabled). Extra keyword attrs land in the event's ``args``."""
    if not _ENABLED:
        return _NULL_SPAN
    return Span(name, cat, attrs)


def complete(name: str, start_us: float, cat: str = "span",
             **attrs) -> None:
    """Record a span that began at ``start_us`` (a `now_us()` reading)
    and ends now: for an interval no one block encloses, such as a
    request's wait in a queue. A no-op when disabled."""
    if not _ENABLED:
        return
    end = now_us()
    with _LOCK:
        _append({"name": name, "cat": cat, "ph": "X",
                 "ts": round(start_us, 3), "dur": round(end - start_us, 3),
                 "pid": 0, "tid": _tid(), "args": dict(attrs)})


def dropped() -> int:
    """Events the ring buffer pushed out since the last `reset()`."""
    return _DROPPED


# --------------------------------------------------------------- counters ---

class Counter:
    """A named monotonically-accumulating value; `add` is a no-op while
    observability is off so handles can be cached across enable state."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0

    def add(self, v=1) -> "Counter":
        if _ENABLED:
            with _LOCK:
                self.value += v
        return self


class _NullCounter(Counter):
    __slots__ = ()

    def add(self, v=1):
        return self


_NULL_COUNTER = _NullCounter("<disabled>")


def counter(name: str) -> Counter:
    """The named counter (created on first use); a shared no-op when
    observability is off, so the registry holds no disabled-mode state."""
    if not _ENABLED:
        return _NULL_COUNTER
    with _LOCK:
        c = _COUNTERS.get(name)
        if c is None:
            c = _COUNTERS[name] = Counter(name)
        return c


def counter_values() -> Dict[str, float]:
    with _LOCK:
        return {name: c.value for name, c in _COUNTERS.items()}


# ----------------------------------------------------------- dispatch log ---

def dispatch_event(**fields) -> None:
    """Record one structured backend/pipeline dispatch decision
    (`kernels/api.py` calls this once per resolution). Also mirrored
    into the span stream as an instant event so trace viewers show the
    decision inline with the kernel spans."""
    if not _ENABLED:
        return
    ts = now_us()
    with _LOCK:
        _DISPATCH.append(dict(fields, ts=round(ts, 3)))
        _append({
            "name": f"dispatch:{fields.get('op', '?')}",
            "cat": "dispatch", "ph": "i", "s": "t",
            "ts": round(ts, 3), "pid": 0, "tid": _tid(),
            "args": dict(fields)})


def dispatch_log() -> List[Dict[str, Any]]:
    with _LOCK:
        return list(_DISPATCH)


# -------------------------------------------------------------- rendering ---

def events() -> List[Dict[str, Any]]:
    with _LOCK:
        return list(_EVENTS)


def spans(name: Optional[str] = None,
          cat: Optional[str] = None) -> List[Dict[str, Any]]:
    return [e for e in events()
            if e["ph"] == "X"
            and (name is None or e["name"] == name)
            and (cat is None or e["cat"] == cat)]


def chrome_trace() -> Dict[str, Any]:
    """The full buffer as a Chrome trace-event JSON object. Repo payloads
    (counters, op counters, dispatch log) ride under ``"repro"`` — extra
    top-level keys are explicitly allowed by the object form."""
    from repro.obs import counters as _opcounters
    return {
        "traceEvents": events(),
        "displayTimeUnit": "ms",
        "repro": {
            "version": TRACE_SCHEMA_VERSION,
            "counters": counter_values(),
            "op_counters": _opcounters.snapshot(),
            "dispatch": dispatch_log(),
        },
    }


def export_chrome_trace(path: str) -> str:
    with open(path, "w") as fh:
        json.dump(chrome_trace(), fh, indent=1, default=str)
    return path


def export_if_configured(default_path: Optional[str] = None) -> Optional[str]:
    """Export the trace when observability is on: to ``REPRO_OBS_TRACE``
    if set, else to ``default_path`` (no-op when neither). CLIs call
    this on exit so `REPRO_OBS=1 REPRO_OBS_TRACE=t.json <cli>` is the
    whole recipe."""
    if not _ENABLED:
        return None
    path = obsenv.get("REPRO_OBS_TRACE") or default_path
    if not path:
        return None
    return export_chrome_trace(path)


def summary() -> Dict[str, Any]:
    """Aggregate view: per-span-name {count, total_us, mean_us, max_us},
    generic counters, dispatch-event count."""
    agg: Dict[str, Dict[str, float]] = {}
    for e in spans():
        s = agg.setdefault(e["name"], {"count": 0, "total_us": 0.0,
                                       "max_us": 0.0})
        s["count"] += 1
        s["total_us"] += e["dur"]
        s["max_us"] = max(s["max_us"], e["dur"])
    for s in agg.values():
        s["mean_us"] = s["total_us"] / s["count"]
    return {"spans": agg, "counters": counter_values(),
            "dispatch_events": len(dispatch_log())}


# ------------------------------------------------------------ shared timer ---

def time_call(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Mean wall-clock µs per call of ``fn(*args)``.

    The one timing implementation behind `kernels.tune._time` and
    `benchmarks.common.time_call` (previously two divergent copies):
    ``warmup`` synced calls to amortise compilation, then ``iters``
    back-to-back calls with one `block_until_ready` on the last result —
    async dispatch overlaps inside the loop, the sync charges all device
    work to the measured window.
    """
    import jax

    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6
