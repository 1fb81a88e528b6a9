"""Distributed span/trace plane — request- and step-scoped timelines.

The flight recorder (recorder.py) answers "what events happened on this
process, recently".  This module answers the fleet-level questions the
recorder cannot: *where did this request's latency go* (queue-wait vs
prefill chunks vs decode ticks vs COW copies vs a failover replay), and
*what did each pipeline stage actually do* relative to what
``schedule.simulate()`` predicted.

Design constraints, in order:

- **Zero new retraces.**  A trace context is two host-side ints
  ``(trace_id, span_id)`` riding existing request/action objects
  (``RouterRequest``, ``Sequence``, the pipeline dispatch closure).
  Nothing here is ever passed into a jitted function or mixed into an
  executable cache key — pinned by tests/test_tracing.py.
- **One choke point stays one choke point.**  Finished spans flow
  through the ordinary ``emit("trace.span", ...)`` path (metrics +
  ring); the hot-path budget gated by ci_op_benchmark is unchanged
  because span starts/ends happen at request/tick/action frequency,
  never per dispatched eager op.
- **One timeline with the device in it.**  Ring spans are stamped with
  ``time.perf_counter_ns()`` (monotonic, process-local).  :func:`phase`
  names the host's work as a ``jax.profiler.TraceAnnotation`` (prefix
  ``ptpu.``), so a ``jax.profiler`` trace holds the host phases and the
  device's operations on one axis. Every ``ptpu.serve.step`` carries
  the field ``perf_ns``, the ``perf_counter_ns()`` reading taken as it
  opens: the span's start on the profile's axis less ``perf_ns`` is the
  offset that lays any ``perf_counter_ns`` stamp of the process (the
  ring's spans, a request's ``submit_ns`` / ``admit_ns``) over that axis.

Spans form a tree per trace: the serving root span ("request") parents
queue.wait / prefill.chunk / decode.tick / cow.copy / failover.replay;
a pipeline batch root parents per-stage pp.stage and pp.p2p spans, each
stamped with the elastic epoch that dispatched it. A serving engine
records one ``serve.tick`` span a harvested tick besides, under a trace
id of its own (:func:`new_id`): the tick's device interval as the host
sees it, with the step span's fields, for the whole run and not only
inside a profiler session (``PagedServingEngine._harvest``).
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import jax

from ..core import flags

__all__ = [
    "Span", "trace_enabled", "new_id", "new_trace", "start_span", "end_span",
    "record_span", "span", "active_spans", "active_tree", "finished_spans",
    "to_chrome_trace", "phase", "PHASE_PREFIX",
    "measured_schedule_stats", "reset",
]

flags.define_flag("trace_spans", True,
                  "Enable the request/step span plane (tracing.py): span "
                  "context rides request and pipeline action objects and "
                  "finished spans feed paddle_trace_* metrics + the ring")
flags.define_flag("trace_buffer_size", 32768,
                  "Finished-span ring capacity per process; oldest spans "
                  "are dropped first (chrome-trace export reads this ring). "
                  "The default keeps a serving engine's `serve.tick` spans "
                  "for some minutes of 10 ms ticks")

# cached enable knob, same idiom as observability._sampling
_on = [1 if flags.flag_value("trace_spans") else 0]

_lock = threading.Lock()
_ids = itertools.count(1)
_active: Dict[int, "Span"] = {}
_finished: deque = deque(maxlen=max(1, int(flags.flag_value("trace_buffer_size"))))


def _on_flag_change(name, value):
    if name == "trace_spans":
        _on[0] = 1 if value else 0
    elif name == "trace_buffer_size":
        global _finished
        with _lock:
            _finished = deque(_finished, maxlen=max(1, int(value)))


flags.on_change(_on_flag_change)


def trace_enabled() -> bool:
    return bool(_on[0])


class Span:
    """One timed node of a trace tree. Mutable only via end_span()."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "start_ns",
                 "end_ns", "fields")

    def __init__(self, name: str, trace_id: int, span_id: int,
                 parent_id: int, start_ns: int, fields: dict):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start_ns = start_ns
        self.end_ns = 0
        self.fields = fields

    @property
    def dur_s(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9 if self.end_ns else 0.0

    def to_dict(self) -> dict:
        return {"name": self.name, "trace_id": self.trace_id,
                "span_id": self.span_id, "parent_id": self.parent_id,
                "start_ns": self.start_ns, "end_ns": self.end_ns,
                "dur_s": round(self.dur_s, 9), "fields": dict(self.fields)}

    def __repr__(self):
        state = "open" if not self.end_ns else f"{self.dur_s * 1e3:.3f}ms"
        return (f"Span({self.name} trace={self.trace_id} "
                f"span={self.span_id}<-{self.parent_id} {state})")


def new_id() -> int:
    """A fresh trace id with no root span: for a producer whose spans
    belong together (a chrome export puts them on one row) and whose life
    is no request's, so that nothing of it stands in `active_spans()`
    (a serving engine's ticks). Given whether tracing is on or off."""
    return next(_ids)


def new_trace(name: str, **fields) -> Optional[Span]:
    """Allocate a fresh trace: returns its root span (trace_id == the
    root's span_id), or None when tracing is off."""
    if not _on[0]:
        return None
    sid = next(_ids)
    sp = Span(name, sid, sid, 0, time.perf_counter_ns(), fields)
    with _lock:
        _active[sid] = sp
    return sp


def start_span(name: str, trace_id: int, parent_id: int = 0,
               **fields) -> Optional[Span]:
    if not _on[0] or not trace_id:
        return None
    sid = next(_ids)
    sp = Span(name, trace_id, sid, parent_id, time.perf_counter_ns(), fields)
    with _lock:
        _active[sid] = sp
    return sp


def end_span(sp: Optional[Span], end_ns: Optional[int] = None,
             **fields) -> Optional[Span]:
    """Close an open span (idempotent; None-tolerant so call sites can
    thread maybe-None contexts without guards). ``end_ns``: a
    ``perf_counter_ns()`` reading the caller has taken already, for a
    span that ends where another stamp is set."""
    if sp is None or sp.end_ns:
        return sp
    sp.end_ns = time.perf_counter_ns() if end_ns is None else end_ns
    if fields:
        sp.fields.update(fields)
    with _lock:
        _active.pop(sp.span_id, None)
        _finished.append(sp)
        n_active = len(_active)
    from . import emit as _emit
    _emit("trace.span", dur_s=sp.dur_s, name=sp.name, trace=sp.trace_id,
          span=sp.span_id, parent=sp.parent_id, active=n_active)
    return sp


def record_span(name: str, trace_id: int, parent_id: int,
                start_ns: int, dur_s: float, event: bool = True,
                **fields) -> Optional[Span]:
    """Record an already-measured interval as a finished span (the engine
    tick attributions time with perf_counter and report after the fact).
    ``dur_s`` is the difference of two ``perf_counter_ns()`` readings
    times 1e-9, and ``end_ns`` comes out as the later reading to the
    nanosecond. ``event=False``: the caller emits an event of its own with
    these fields (the engine's ``serve.tick``: ``serving.step``), so the
    span feeds no ``trace.span`` beside it."""
    if not _on[0] or not trace_id:
        return None
    sid = next(_ids)
    sp = Span(name, trace_id, sid, parent_id, start_ns, fields)
    sp.end_ns = start_ns + round(dur_s * 1e9)
    with _lock:
        _finished.append(sp)
        n_active = len(_active)
    if event:
        from . import emit as _emit
        _emit("trace.span", dur_s=dur_s, name=name, trace=trace_id,
              span=sid, parent=parent_id, active=n_active)
    return sp


class span:
    """``with tracing.span("cow.copy", tid, parent): ...`` convenience."""

    def __init__(self, name: str, trace_id: int, parent_id: int = 0,
                 **fields):
        self._args = (name, trace_id, parent_id, fields)
        self.span: Optional[Span] = None

    def __enter__(self) -> Optional[Span]:
        name, tid, pid, fields = self._args
        self.span = start_span(name, tid, pid, **fields)
        return self.span

    def __exit__(self, exc_type, exc, tb):
        end_span(self.span, error=repr(exc)) if exc else end_span(self.span)
        return False


# ---------------------------------------------------------------------------
# Views: active tree (distress dumps), finished spans, chrome export
# ---------------------------------------------------------------------------

def active_spans() -> List[dict]:
    with _lock:
        return [sp.to_dict() for sp in _active.values()]


def active_tree() -> dict:
    """In-flight traces as nested trees — the distress-dump 'traces'
    section, so a post-mortem shows exactly which requests/steps were
    mid-flight and in which phase when the process died."""
    with _lock:
        live = [sp for sp in _active.values()]
    now = time.perf_counter_ns()
    nodes = {}
    for sp in live:
        d = sp.to_dict()
        d["open_for_s"] = round((now - sp.start_ns) / 1e9, 6)
        d["children"] = []
        nodes[sp.span_id] = d
    roots: Dict[int, list] = {}
    for d in nodes.values():
        parent = nodes.get(d["parent_id"])
        if parent is not None:
            parent["children"].append(d)
        else:
            roots.setdefault(d["trace_id"], []).append(d)
    return {"in_flight_spans": len(nodes),
            "traces": {str(tid): spans for tid, spans in roots.items()}}


def finished_spans(trace_id: Optional[int] = None,
                   name: Optional[str] = None) -> List[dict]:
    with _lock:
        out = list(_finished)
    return [sp.to_dict() for sp in out
            if (trace_id is None or sp.trace_id == trace_id)
            and (name is None or sp.name == name)]


def to_chrome_trace(pid="paddle_tpu", offset_ns: int = 0,
                    include_active: bool = False) -> dict:
    """Finished spans as a chrome://tracing document (distress dumps
    read it). ``offset_ns`` shifts the ring's ``perf_counter_ns`` stamps,
    e.g. by the offset a ``ptpu.serve.step``'s ``perf_ns`` gives onto a
    profile's axis; tid groups spans by trace. A serving engine's
    ``serve.tick`` spans share the engine's trace id, so with the offset
    of ANY profiled step its whole run of ticks, the ones before and
    behind the profiled stretch too, lies on one row over the profile."""
    with _lock:
        spans = list(_finished)
        if include_active:
            spans += list(_active.values())
    events = []
    for sp in spans:
        ev = {"name": sp.name, "ph": "X", "pid": pid,
              "tid": f"trace-{sp.trace_id}",
              "ts": (sp.start_ns + offset_ns) / 1e3,
              "dur": max(0.0, ((sp.end_ns or time.perf_counter_ns())
                               - sp.start_ns) / 1e3),
              "args": {"trace_id": sp.trace_id, "span_id": sp.span_id,
                       "parent_id": sp.parent_id, **sp.fields}}
        events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------------
# Phases on the profiler's clock
# ---------------------------------------------------------------------------

PHASE_PREFIX = "ptpu."


def phase(name: str, **fields):
    """``with tracing.phase("serve.schedule", tick=n): ...`` names a
    stretch of host work as the ``jax.profiler.TraceAnnotation``
    ``ptpu.<name>``, ``fields`` as its metadata, and nothing else: no
    lock, no ``emit``, no ring write. Outside a profiler session a
    TraceAnnotation is a flag test, so "off" is the default state."""
    return jax.profiler.TraceAnnotation(PHASE_PREFIX + name, **fields)


# ---------------------------------------------------------------------------
# Schedule conformance: measured timeline -> bubble/straggler accounting
# ---------------------------------------------------------------------------

def measured_schedule_stats(timeline, stages: int, groups: int = 0) -> dict:
    """Aggregate a measured pipeline action timeline the same way
    ``schedule.simulate()`` aggregates its unit-cost one.

    ``timeline``: [(stage, phase, microbatch, start_s, dur_s)] with
    start offsets on one clock (the runtime stamps them relative to the
    batch's t0).  Global stage s occupies device group ``s % groups``.
    Returns measured makespan / per-group busy seconds / bubble fraction
    ``1 - busy/(G*makespan)`` plus per-group straggler attribution —
    directly comparable to the simulate() prediction, which is the whole
    point (arXiv 2301.13062: measure what overlapped, don't trust the
    schedule)."""
    G = groups or stages
    busy = [0.0] * G
    t_lo, t_hi = float("inf"), 0.0
    for s, _phase, _m, start_s, dur_s in timeline:
        busy[s % G] += dur_s
        t_lo = min(t_lo, start_s)
        t_hi = max(t_hi, start_s + dur_s)
    makespan = (t_hi - t_lo) if timeline else 0.0
    total = sum(busy)
    bubble = 1.0 - total / (G * makespan) if makespan > 0 else 0.0
    mean = total / G if G else 0.0
    straggler = max(range(G), key=lambda g: busy[g]) if G else 0
    excess = ((busy[straggler] - mean) / mean) if mean > 0 else 0.0
    return {"makespan_s": round(makespan, 6),
            "busy_s": [round(b, 6) for b in busy],
            "bubble_fraction": round(bubble, 6),
            "straggler_group": straggler,
            "straggler_excess": round(excess, 4),
            "groups": G, "actions": len(timeline)}


def reset():
    """Drop all span state (test isolation)."""
    global _ids
    with _lock:
        _active.clear()
        _finished.clear()
    _ids = itertools.count(1)


def install() -> None:
    """Expose the in-flight span tree as a distress-dump section, next
    to the membership/pipeline sections (each guarded per-section)."""
    from . import distress
    distress.register_section("traces", active_tree)
