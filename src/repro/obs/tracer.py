"""Nestable span tracer: monotonic wall-clock phases with counter deltas.

The orchestrator's perf contracts ("one host transfer per run", "pretrain
never retraces", "most of an online row is per-segment dispatch") were prose
until now.  A :func:`span` turns each hot-path phase into a recorded event:

    with span("re-discover", segment=3):
        graph = ql.discover_graph(...)

or, as a decorator around a whole stage::

    @span.wrap("cluster")
    def cluster_clients(...): ...

Spans nest (each records its depth; readers rebuild parents from the close
order), carry arbitrary scalar attributes, and snapshot the JAX counters
(``obs.counters``) at both boundaries so every event knows how many jit
compilations and ``jax.device_get`` transfers happened inside it —
including everything its children did; readers that want exclusive time
subtract child durations (``tools/trace_report.py`` does).

Profiler sink: every span, whether or not the tracer is enabled, also
enters a ``jax.profiler.TraceAnnotation`` under its bare name (attrs left
out, so one phase's annotations group).  Any ``jax.profiler`` trace then
holds the program's phases on its host plane, on the device's clock: a
device idle gap is attributed to the innermost phase covering it.  The
profiler's own TraceMe check decides whether the annotation is recorded.

Cost model: when tracing is disabled (the default) ``span(...)`` allocates
one small handle whose ``__enter__``/``__exit__`` enter and leave the
annotation and check one flag — no clock is read, no event is stored.
Spans sit at phase granularity (a handful per orchestrator segment, never
inside a ``lax.scan``), so the disabled cost per run is microseconds.

Timing semantics under JAX's async dispatch: a span measures *host*
wall-clock between its boundaries.  Phases that only enqueue device work
record their dispatch cost; the device time they enqueued lands in whichever
later span first blocks (for the orchestrator that is ``fl-segment``'s eval
chain and the single ``metrics-materialize`` transfer).  That is exactly the
attribution the scan-fusion ROADMAP item needs — dispatch overhead vs
blocked-on-device time — without inserting ``block_until_ready`` calls that
would change the measured program.

Not thread-safe by design: the tracer mirrors the repo's single-threaded
driver loops.  (A threaded driver would need one tracer per thread.)
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from repro.obs import counters as _counters

__all__ = ["SpanEvent", "span", "enabled", "start", "stop", "events",
           "drain", "phase_totals"]


class SpanEvent:
    """One closed span: name, wall-clock window, nesting, counter deltas."""

    __slots__ = ("name", "t0", "dur", "depth", "attrs",
                 "compiles", "transfers", "bytes_fetched",
                 "live_arrays", "live_bytes")

    def __init__(self, name, t0, dur, depth, attrs,
                 compiles, transfers, bytes_fetched,
                 live_arrays=None, live_bytes=None):
        self.name = name
        self.t0 = t0                    # seconds since tracer start
        self.dur = dur                  # seconds
        self.depth = depth              # 0 = top level
        self.attrs = attrs              # scalar labels ({} when none)
        self.compiles = compiles        # jit compilations inside the span
        self.transfers = transfers      # jax.device_get calls inside
        self.bytes_fetched = bytes_fetched
        self.live_arrays = live_arrays  # optional device-memory snapshot
        self.live_bytes = live_bytes    # (at span exit; REPRO_OBS_MEM=1)

    def to_dict(self) -> Dict[str, Any]:
        d = {"type": "span", "name": self.name, "t0": self.t0,
             "dur": self.dur, "depth": self.depth,
             "compiles": self.compiles, "transfers": self.transfers,
             "bytes_fetched": self.bytes_fetched}
        if self.attrs:
            d["attrs"] = self.attrs
        if self.live_arrays is not None:
            d["live_arrays"] = self.live_arrays
            d["live_bytes"] = self.live_bytes
        return d

    def __repr__(self):
        return (f"SpanEvent({self.name!r}, dur={self.dur:.6f}, "
                f"depth={self.depth}, compiles={self.compiles}, "
                f"transfers={self.transfers})")


# Module-level tracer state.  `_enabled` is the one flag the disabled fast
# path reads; everything else is only touched while tracing.
_enabled = False
_t_start = 0.0
_events: List[SpanEvent] = []
_stack: List[list] = []       # open frames: [name, attrs, t0, counters, idx]
_snapshot_memory = False
_on_close = None              # manifest hook: called with each closed event


def enabled() -> bool:
    return _enabled


def start(snapshot_memory: bool = False, on_close=None) -> None:
    """Begin tracing: reset the event list and the counter epoch."""
    global _enabled, _t_start, _snapshot_memory, _on_close
    _events.clear()
    _stack.clear()
    _counters.install()
    _counters.set_active(True)
    _snapshot_memory = snapshot_memory
    _on_close = on_close
    _t_start = time.perf_counter()
    _enabled = True


def stop() -> List[SpanEvent]:
    """Stop tracing and return the recorded events (open spans discarded)."""
    global _enabled, _on_close
    _enabled = False
    _on_close = None
    _counters.set_active(False)
    _stack.clear()
    return list(_events)


def events() -> List[SpanEvent]:
    """The completed spans recorded so far (tracing keeps running)."""
    return list(_events)


def drain() -> List[SpanEvent]:
    """Return completed spans and clear the list — per-row bench attribution
    pulls one run's spans without stopping the tracer."""
    out = list(_events)
    _events.clear()
    return out


class _SpanHandle:
    """Context manager for one span; ``span.wrap`` builds the decorator."""

    __slots__ = ("name", "attrs", "_live", "_annotation")

    def __init__(self, name: str, attrs: Optional[dict]):
        self.name = name
        self.attrs = attrs
        self._live = False
        self._annotation = None

    def __enter__(self):
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        if not _enabled:
            return self
        self._live = True
        _stack.append([self.name, self.attrs, time.perf_counter(),
                       _counters.snapshot()])
        return self

    def __exit__(self, exc_type, exc, tb):
        self._annotation.__exit__(exc_type, exc, tb)
        self._annotation = None
        if not self._live:
            return False
        self._live = False
        t1 = time.perf_counter()
        name, attrs, t0, c0 = _stack.pop()
        c1 = _counters.snapshot()
        depth = len(_stack)
        # Children close before their parent, so a parent's event index is
        # unknown here; events carry (close order, depth) instead and readers
        # rebuild the tree from that — a span's parent is the nearest *later*
        # event with a smaller depth (see tools/trace_report.py).
        ev = SpanEvent(
            name=name, t0=t0 - _t_start, dur=t1 - t0, depth=depth,
            attrs=attrs or {},
            compiles=c1[0] - c0[0], transfers=c1[1] - c0[1],
            bytes_fetched=c1[2] - c0[2])
        if _snapshot_memory:
            ev.live_arrays, ev.live_bytes = _counters.live_memory()
        _events.append(ev)
        if _on_close is not None:
            _on_close(ev)
        return False


def span(name: str, **attrs) -> _SpanHandle:
    """A context manager timing one phase while tracing is active, and
    annotating any running ``jax.profiler`` trace with ``name`` always.

    Keyword arguments become the event's ``attrs`` (keep them scalar — they
    are written verbatim into the JSONL manifest)."""
    return _SpanHandle(name, attrs or None)


def _wrap(name: str, **attrs):
    """Decorator form: time every call of ``fn`` as a ``name`` span."""
    def deco(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name, **attrs):
                return fn(*args, **kwargs)
        return inner
    return deco


span.wrap = _wrap


def phase_totals(evs: Optional[List[SpanEvent]] = None) -> Dict[str, dict]:
    """Aggregate events by span name: total/count/mean seconds + counter
    sums.  The bench harness turns one run's drained events into per-phase
    row fields with this."""
    evs = events() if evs is None else evs
    out: Dict[str, dict] = {}
    for e in evs:
        d = out.setdefault(e.name, {"total": 0.0, "count": 0,
                                    "compiles": 0, "transfers": 0})
        d["total"] += e.dur
        d["count"] += 1
        d["compiles"] += e.compiles
        d["transfers"] += e.transfers
    for d in out.values():
        d["mean"] = d["total"] / d["count"]
    return out
