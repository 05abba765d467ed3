"""Runtime telemetry (DESIGN.md §9): per-process counters, spans, report.

Every degradation the runtime executor performs is recorded here — which
rung fell to which, for which problem key, classified how, and whether the
underlying failure was injected — so benchmarks and CI can assert on the
aggregate: a faulted run's report must record *exactly* the injected
fallbacks, and a clean steady-state run must report **zero**.

Two counters sit on the network engine's cold path and are always on:
``network.builds`` (``execute_network`` memo misses, each one plan + jit +
first call) and ``network.build_ns`` (the nanoseconds those misses took).
A steady-state window reads ``network.builds`` unchanged across it.

Spans time the host layers of a call.  ``span(name)`` is off by default:
it costs one flag test and returns a shared null context.  Inside
``with tracing():`` it records ``perf_counter_ns`` start/end pairs (the
newest ``MAX_SPANS`` per name); while a profiler session runs, it also
opens a ``jax.profiler.TraceAnnotation(name)``, so the span lands in the
trace on the device's clock.  The program's spans:

* ``network.memo``: the ``execute_network`` memo key and lookup;
* ``network.call``: a memo hit's jitted call, up to its return (no sync);
* ``network.build``: a memo miss's plan + jit + first call.

In-memory and per-process on purpose (the persistent artifact is the
quarantine store): ``runtime_report()`` snapshots to a JSON-serializable
dict (``"spans"``: per name its count, median and p95 in µs),
``reset_runtime_telemetry()`` clears counters, events and spans.
Stdlib-only (``jax.profiler`` is imported when tracing is switched on).
"""
from __future__ import annotations

import collections
import contextlib
import statistics
import threading
import time
from typing import Optional

#: Bounded event log — counters never saturate, the event detail does.
MAX_EVENTS = 256
#: Spans kept per name (the newest).
MAX_SPANS = 1 << 16

_LOCK = threading.Lock()
_COUNTERS: collections.Counter = collections.Counter()
_EVENTS: list = []
#: name -> deque of (start_ns, end_ns)
_SPANS: dict = {}
_TRACING = False
_NULL_SPAN = contextlib.nullcontext()
_annotation = None   # jax.profiler.TraceAnnotation, bound by tracing()


def _append_event(event: dict) -> None:
    _EVENTS.append(event)
    if len(_EVENTS) > MAX_EVENTS:
        del _EVENTS[: len(_EVENTS) - MAX_EVENTS]


def record_fallback(*, scope: str, key: str, from_rung: str, to_rung: str,
                    failure_kind: str, segment_kind: Optional[str],
                    injected: bool, error: str) -> None:
    """One rung-down retry (or network-jit -> per-block recovery)."""
    with _LOCK:
        _COUNTERS["fallbacks"] += 1
        _COUNTERS[f"fallbacks.{failure_kind}"] += 1
        _COUNTERS[f"fallbacks.{scope}"] += 1
        if injected:
            _COUNTERS["injected_fallbacks"] += 1
        _append_event({
            "event": "fallback", "scope": scope, "key": key,
            "from_rung": from_rung, "to_rung": to_rung,
            "failure_kind": failure_kind, "segment_kind": segment_kind,
            "injected": bool(injected), "error": str(error)[:300],
        })


def record_recovery(*, scope: str, key: str, rung: str) -> None:
    """A degraded attempt succeeded — the ladder landed somewhere."""
    with _LOCK:
        _COUNTERS["recoveries"] += 1
        _append_event({"event": "recovery", "scope": scope, "key": key,
                       "rung": rung})


def record_quarantine_hit(*, scope: str, key: str, banned) -> None:
    """A plan consult honored a persisted quarantine entry (skipped the
    banned rungs with ZERO retry attempts — the steady state after a
    failure)."""
    with _LOCK:
        _COUNTERS["quarantine_hits"] += 1
        _append_event({"event": "quarantine_hit", "scope": scope,
                       "key": key, "banned": sorted(banned)})


def count(name: str) -> None:
    """One more of the always-on counter ``name``."""
    with _LOCK:
        _COUNTERS[name] += 1


def record_build(ns: int) -> None:
    """One ``execute_network`` memo miss that took ``ns`` nanoseconds."""
    with _LOCK:
        _COUNTERS["network.builds"] += 1
        _COUNTERS["network.build_ns"] += int(ns)


class _Span:
    __slots__ = ("name", "t0", "note")

    def __init__(self, name: str):
        self.name = name
        self.note = None

    def __enter__(self):
        if _annotation.is_enabled():   # a profiler session is running
            self.note = _annotation(self.name)
            self.note.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self.note is not None:
            self.note.__exit__(*exc)
        times = _SPANS.get(self.name)
        if times is None:
            with _LOCK:
                times = _SPANS.setdefault(
                    self.name, collections.deque(maxlen=MAX_SPANS))
        times.append((self.t0, t1))
        return False


def span(name: str):
    """A context that times ``name`` while :func:`tracing` is on; the
    shared null context otherwise."""
    if not _TRACING:
        return _NULL_SPAN
    return _Span(name)


@contextlib.contextmanager
def tracing():
    """Record spans inside the block; while a profiler session runs, each
    span also opens a profiler annotation."""
    global _TRACING, _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    was = _TRACING
    _TRACING = True
    try:
        yield
    finally:
        _TRACING = was


def _span_summary(times) -> dict:
    us = sorted((e - s) * 1e-3 for s, e in list(times))
    p95 = (statistics.quantiles(us, n=100, method="inclusive")[94]
           if len(us) > 1 else us[0])
    return {"count": len(us), "median_us": statistics.median(us),
            "p95_us": p95}


def fallback_count() -> int:
    with _LOCK:
        return int(_COUNTERS.get("fallbacks", 0))


def runtime_report() -> dict:
    """JSON-serializable snapshot; steady state = ``fallbacks == 0`` and
    ``counters["network.builds"]`` unchanged."""
    with _LOCK:
        return {
            "fallbacks": int(_COUNTERS.get("fallbacks", 0)),
            "injected_fallbacks": int(_COUNTERS.get("injected_fallbacks", 0)),
            "numeric_trips": int(_COUNTERS.get("fallbacks.numeric", 0)),
            "recoveries": int(_COUNTERS.get("recoveries", 0)),
            "quarantine_hits": int(_COUNTERS.get("quarantine_hits", 0)),
            "counters": {k: int(v) for k, v in sorted(_COUNTERS.items())},
            "events": [dict(e) for e in _EVENTS],
            "spans": {k: _span_summary(v) for k, v in sorted(_SPANS.items())
                      if v},
        }


def reset_runtime_telemetry() -> None:
    with _LOCK:
        _COUNTERS.clear()
        _EVENTS.clear()
        _SPANS.clear()
