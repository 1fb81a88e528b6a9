"""From a profiler trace to the numbers the benchmark reports.

`load` turns an `.xplane.pb` file (jax.profiler) into plain lists:

    {"device": {"/device:TPU:0": [[name, start_ns, dur_ns], ...], ...},
     "host":   [[name, start_ns, dur_ns], ...]}        # bench.* spans

`reduce` takes that and gives busy and idle time, the operations that took
the most device time, the idle gaps by what the host was doing, and the
time spent in collectives. It reads nothing else, so it is checked against
the small recorded trace in benchmark/lib/testdata (same layout, as JSON).

Device events come from each TPU plane's "XLA Ops" line. That line nests
(a `while` holds its body's operations), so busy time is the UNION of the
intervals and an operation's time is its SELF time: its duration less the
part its children on the same line cover.
"""
from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)",
    re.IGNORECASE)

# the op line names an event by its whole HLO text:
#   %fusion.208 = bf16[512,4096]{1,0:T(8,128)(2,1)S(1)} fusion(...)
_HLO = re.compile(r"^%?(?P<op>[^\s=]+) = \(?(?P<dtype>[a-z]+\d*)"
                  r"\[(?P<dims>[\d,]*)\]")

Event = Tuple[str, float, float]


def short_name(text: str) -> str:
    """`fusion.208_bf16_512_4096_` for the HLO text above: the operation's
    name, the type and the shape of its (first) result."""
    m = _HLO.match(text)
    if m is None:
        return text.lstrip("%")[:64]
    return f"{m['op']}_{m['dtype']}_{m['dims'].replace(',', '_')}_"


def load(path: str) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device: Dict[str, List[list]] = {}
    host: List[list] = []
    seen = []
    for plane in data.planes:
        seen.append(plane.name)
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OP_LINE:
                    device[plane.name] = [
                        [short_name(e.name), float(e.start_ns),
                         float(e.duration_ns)] for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    if not device:
        raise ValueError(f"no {DEVICE_PLANE}* plane with an {OP_LINE!r} line "
                         f"in {path}; planes: {seen}")
    return {"device": device, "host": sorted(host, key=lambda e: e[1])}


def newest_trace(trace_dir: str) -> str:
    """The newest .xplane.pb the profiler wrote under `trace_dir`."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _clip(events, lo: float, hi: float) -> List[Event]:
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def union(events) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals of the events, in order."""
    merged: List[List[float]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return [(s, e) for s, e in merged]


def self_times(events) -> Dict[str, float]:
    """Seconds of self time by operation name: an event's duration less
    what the events nested inside it cover."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    self_ns = [dur for _, _, dur in order]
    stack: List[int] = []
    for i, (_, start, dur) in enumerate(order):
        while stack and (order[stack[-1]][1] + order[stack[-1]][2]) <= start:
            stack.pop()
        if stack:
            parent = stack[-1]
            parent_end = order[parent][1] + order[parent][2]
            self_ns[parent] -= min(start + dur, parent_end) - start
        stack.append(i)
    out: Dict[str, float] = {}
    for (name, _, _), ns in zip(order, self_ns):
        out[name] = out.get(name, 0.0) + max(ns, 0.0) * 1e-9
    return out


def _covering_span(host, lo: float, hi: float) -> str:
    """Name of the bench.* span that covers most of [lo, hi)."""
    best, best_ns = "no_span", 0.0
    for name, start, dur in host:
        ns = min(start + dur, hi) - max(start, lo)
        if ns > best_ns:
            best, best_ns = name, ns
    return best


def reduce(trace: dict, window: Optional[Tuple[float, float]] = None) -> dict:
    """The traced window is the span from the first bench.* span's start to
    the last one's end (or `window`, in ns; or, with no span at all, the
    extent of the device events)."""
    host = [tuple(e) for e in trace["host"]]
    planes = {k: [tuple(e) for e in v] for k, v in trace["device"].items()}
    if window is None and host:
        window = (min(s for _, s, _ in host), max(s + d for _, s, d in host))
    if window is None:
        every = [e for ev in planes.values() for e in ev]
        window = (min(s for _, s, _ in every), max(s + d for _, s, d in every))
    lo, hi = window
    window_s = (hi - lo) * 1e-9

    busy, ops, collective = [], {}, []
    first_gaps: List[Tuple[float, float]] = []
    for n, name in enumerate(sorted(planes)):
        events = _clip(planes[name], lo, hi)
        merged = union(events)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for op, secs in self_times(events).items():
            ops[op] = ops.get(op, 0.0) + secs
        collective.append(sum(e - s for s, e in union(
            [ev for ev in events if COLLECTIVE.match(ev[0])])) * 1e-9)
        if n == 0:
            edges = [lo] + [t for iv in merged for t in iv] + [hi]
            first_gaps = [(edges[i], edges[i + 1])
                          for i in range(0, len(edges), 2)
                          if edges[i + 1] > edges[i]]
    chips = len(planes)
    device_ops = sorted(((op, secs / chips) for op, secs in ops.items()),
                        key=lambda kv: -kv[1])[:10]

    by_span: Dict[str, float] = {}
    longest = []
    for g_lo, g_hi in first_gaps:
        span = _covering_span(host, g_lo, g_hi)
        by_span[span] = by_span.get(span, 0.0) + (g_hi - g_lo) * 1e-9
        longest.append((span, (g_hi - g_lo) * 1e-9))
    totals = sorted(by_span.items(), key=lambda kv: -kv[1])[:5]
    longest.sort(key=lambda kv: -kv[1])
    idle_gaps = ([[f"all:{k}", v] for k, v in totals]
                 + [[f"longest:{k}", v] for k, v in longest[:10 - len(totals)]])

    return {
        "window_s": window_s,
        "chips": chips,
        "busy_s": sum(busy) / chips,                 # mean over the chips
        "busy_s_by_chip": busy,
        "collective_s_chip0": collective[0],
        "device_ops": [[k, v] for k, v in device_ops],
        "idle_gaps": idle_gaps,
    }


def idle_share_percent(reduced: dict) -> float:
    """Share of the traced window in which no operation ran on a device,
    the mean over the devices."""
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
