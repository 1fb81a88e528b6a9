"""The program's own names in a profiler trace: `ptpu.*` host phases and the
named scope of every device operation.

`xplane.load` keeps only the benchmark's `bench.*` spans and shortened HLO
names. This module opens the same `.xplane.pb` file and gives what the
program wrote into it (paddle_tpu/observability/tracing.py `phase()`,
`jax.named_scope` in the serve tick and the train step):

    {"device": {"/device:TPU:0": [[name, start_ns, dur_ns], ...], ...},
     "host":   [[name, start_ns, dur_ns], ...],             # bench.* spans
     "program_spans": [[name, start_ns, dur_ns, {field: value}], ...],
     "device_scopes": {"/device:TPU:0": [scope or "", ...], ...}}

"device" and "host" are `xplane.load`'s layout, so `xplane.reduce` reads
the same dict (and the same recorded fixture); "device_scopes" runs
parallel to "device": entry i is the innermost scope of SCOPES in event
i's JAX `op_name`, "" when it has none.

Where the scope comes from (read off a chip trace, PR 25): the event
METADATA of a TPU plane's "XLA Ops" line carries JAX's `op_name` in the
stat `tf_op` (`jit(step_fn)/layers/while/body/closed_call/qkv/gather:`).
`jax.profiler.ProfileData` shows an event's own stats only, so the file is
decoded here from its protobuf wire format (XSpace > XPlane > XLine >
XEvent, XEventMetadata, XStat: tsl/profiler/protobuf/xplane.proto) with
nothing but the standard library. Operations the compiler made itself
(copy insertion, the zero-fill of a scan's stacked output, the `while`)
carry no `op_name` at all; in `serve_decode` they are 13 % of the busy
time. The trace also holds each executable's HLO (plane `/host:metadata`,
stat `Hlo Proto`, xla/service/hlo.proto), and from it such an operation
gets the scope of the values it moves: a `while` that of its body, any
other that of its users, else of its operands (`hlo_scopes`).

Idle attribution SPLITS each idle interval of device 0 over the phases it
intersects (`xplane.reduce` gives a gap whole to the span that covers most
of it): a 5 ms gap runs through harvest, the client's loop, schedule,
prepare and dispatch.

    JAX_PLATFORMS=cpu python3 benchmark/lib/program_trace.py \\
        .bench_cache/trace/<workload> [out.json --count 2]

prints the planes, lines and stat names of a trace and, with `out.json`,
cuts the first `--count` ticks out as a fixture in the layout above.
"""
from __future__ import annotations

import functools
import json
import os
import re
import struct
import sys
from typing import Dict, Iterator, List, Optional, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark.lib import xplane  # noqa: E402

PROGRAM_PREFIX = "ptpu."
STEP = "ptpu.serve.step"
PHASES = ("schedule", "prepare", "dispatch", "wait", "harvest", "submit")
# every jax.named_scope the two hot programs set (engine._build_step,
# serving_attention.block_multihead_attention_, engine._copy_blocks,
# distributed/hybrid.py)
SCOPES = frozenset((
    "embed", "layers", "qkv", "cache_write", "paged_attention", "attn_out",
    "ffn", "head", "sample", "cow_copy",
    "attention", "head_loss", "pp_send", "pipeline", "grad_sync",
    "grad_norm", "adamw"))
# the stat of an operation's event metadata that holds JAX's op_name
OP_NAME_STAT = "tf_op"


# --------------------------------------------------------------------------
# protobuf wire format, as far as xplane.proto needs it
# --------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message; a length-delimited
    value is its bytes, a varint or fixed value its unsigned integer."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wt == 1:
            val = struct.unpack_from("<Q", buf, i)[0]
            i += 8
        elif wt == 5:
            val = struct.unpack_from("<I", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield num, wt, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= (1 << 63) else v


def _stat(buf: bytes, stat_names: Dict[int, str]) -> Tuple[str, object]:
    """One XStat: (name, value); a ref_value is looked up among the stat
    metadata names, as the profiler's own tools do."""
    name, val = "", None
    for num, wt, v in _fields(buf):
        if num == 1:
            name = stat_names.get(v, str(v))
        elif num == 2:
            val = struct.unpack("<d", struct.pack("<Q", v))[0]
        elif num == 3:
            val = v
        elif num == 4:
            val = _signed(v)
        elif num in (5, 6):
            val = v.decode("utf-8", "replace") if num == 5 else v
        elif num == 7:
            val = stat_names.get(v, "")
    return name, val


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, val = 0, b""
    for num, _, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _plane(buf: bytes) -> dict:
    """One XPlane as plain Python: its name and its lines, each event with
    name, start and duration in ns, its own stats and its metadata's."""
    name = ""
    lines, event_meta, stat_meta = [], {}, {}
    for num, _, v in _fields(buf):
        if num == 2:
            name = v.decode()
        elif num == 3:
            lines.append(v)
        elif num == 4:
            k, m = _map_entry(v)
            event_meta[k] = m
        elif num == 5:
            k, m = _map_entry(v)
            stat_meta[k] = m
    stat_names = {}
    for k, m in stat_meta.items():
        for num, _, v in _fields(m):
            if num == 2:
                stat_names[k] = v.decode()
    metas: Dict[int, Tuple[str, dict]] = {}
    for k, m in event_meta.items():
        mname, stats = "", {}
        for num, _, v in _fields(m):
            if num == 2:
                mname = v.decode("utf-8", "replace")
            elif num == 5:
                sname, sval = _stat(v, stat_names)
                stats[sname] = sval
        metas[k] = (mname, stats)
    out_lines = []
    for raw in lines:
        lname, t0_ns, events = "", 0, []
        raw_events = []
        for num, _, v in _fields(raw):
            if num == 2:
                lname = v.decode()
            elif num == 3:
                t0_ns = _signed(v)
            elif num == 4:
                raw_events.append(v)
        for ev in raw_events:
            mid, off_ps, dur_ps, stats = 0, 0, 0, {}
            for num, _, v in _fields(ev):
                if num == 1:
                    mid = v
                elif num == 2:
                    off_ps = _signed(v)
                elif num == 3:
                    dur_ps = _signed(v)
                elif num == 4:
                    sname, sval = _stat(v, stat_names)
                    stats[sname] = sval
            mname, mstats = metas.get(mid, ("", {}))
            events.append((mname, t0_ns + off_ps / 1000.0, dur_ps / 1000.0,
                           stats, mstats))
        out_lines.append((lname, events))
    return {"name": name, "lines": out_lines, "metadata": metas}


def planes(path: str) -> List[dict]:
    """Every plane of an .xplane.pb file (XSpace.planes = field 1)."""
    with open(path, "rb") as f:
        buf = f.read()
    return [_plane(v) for num, _, v in _fields(buf) if num == 1]


# --------------------------------------------------------------------------
# scopes
# --------------------------------------------------------------------------

_WRAPPED = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def scope_of(op_name: Optional[str]) -> str:
    """The innermost scope of SCOPES in a JAX op_name such as
    `jit(step_fn)/jit(main)/layers/while/body/ffn/dot_general` or
    `jit(step)/transpose(jvp(attention))/mul`: path components are read
    from the right, and a component counts through JAX's wrappers
    (`jvp(...)`, `transpose(...)`); "" when there is none."""
    if not op_name:
        return ""
    for comp in reversed(op_name.split("/")):
        if comp in SCOPES:
            return comp
        if "(" in comp:
            # transpose(jvp(ffn)): the innermost word is the scope's name
            words = _WRAPPED.findall(comp)
            if words and words[-1] in SCOPES:
                return words[-1]
    return ""


def _packed(val) -> List[int]:
    """A repeated int64 field: one varint, or a packed run of them."""
    if isinstance(val, int):
        return [val]
    out, i = [], 0
    while i < len(val):
        v, i = _varint(val, i)
        out.append(v)
    return out


def _hlo_computations(hlo_proto: bytes) -> List[Tuple[int, List[dict]]]:
    """(id, instructions) of every computation of a serialized HloProto;
    an instruction is {"id", "name", "opcode", "op_name", "operands",
    "calls"}."""
    out = []
    for num, _, module in _fields(hlo_proto):
        if num != 1:                       # HloProto.hlo_module
            continue
        for num2, _, comp in _fields(module):
            if num2 != 3:                  # HloModuleProto.computations
                continue
            comp_id, instructions = 0, []
            for num3, _, raw in _fields(comp):
                if num3 == 5:              # HloComputationProto.id
                    comp_id = raw
                if num3 != 2:              # HloComputationProto.instructions
                    continue
                ins = {"id": 0, "name": "", "opcode": "", "op_name": "",
                       "operands": [], "calls": []}
                for num4, _, v in _fields(raw):
                    if num4 == 1:
                        ins["name"] = v.decode()
                    elif num4 == 2:
                        ins["opcode"] = v.decode()
                    elif num4 == 7:        # OpMetadata.op_name = 2
                        for num5, _, m in _fields(v):
                            if num5 == 2:
                                ins["op_name"] = m.decode("utf-8", "replace")
                    elif num4 == 35:
                        ins["id"] = v
                    elif num4 == 36:
                        ins["operands"] += _packed(v)
                    elif num4 == 38:
                        ins["calls"] += _packed(v)
                instructions.append(ins)
            out.append((comp_id, instructions))
    return out


def _common_prefix(names: List[str]) -> str:
    """The path components the op_names share from the left."""
    return "/".join(os.path.commonprefix(
        [n.split("/") for n in names if n]))


def hlo_scopes(hlo_proto: bytes) -> Dict[str, str]:
    """{instruction name: scope} for one executable. An instruction whose
    own op_name holds a scope of SCOPES has that scope. One without (the
    compiler made it, or JAX named it outside every scope) gets the scope
    of the values it moves: a `while` the scope common to its body's
    op_names, any other the scope its users agree on, else the scope its
    operands agree on, followed through further unnamed instructions.
    What stays without is "" and is counted as unscoped."""
    comps = _hlo_computations(hlo_proto)
    body_names = {cid: [i["op_name"] for i in ins] for cid, ins in comps}
    scopes: Dict[str, str] = {}
    for _, instructions in comps:
        by_id = {i["id"]: i for i in instructions}
        users: Dict[int, List[int]] = {}
        for i in instructions:
            for op in i["operands"]:
                users.setdefault(op, []).append(i["id"])
        own: Dict[int, str] = {}
        for i in instructions:
            scope = scope_of(i["op_name"])
            if not scope and i["opcode"] == "while":
                scope = scope_of(_common_prefix(
                    [n for c in i["calls"] for n in body_names.get(c, [])]))
            own[i["id"]] = scope

        def through(start: int, edges) -> str:
            """The one scope reached from `start` along `edges` through
            instructions that have none of their own; "" if none or
            several."""
            found, seen, todo = set(), {start}, list(edges(start))
            while todo:
                j = todo.pop()
                if j in seen or j not in by_id:
                    continue
                seen.add(j)
                if own[j]:
                    found.add(own[j])
                else:
                    todo.extend(edges(j))
            return found.pop() if len(found) == 1 else ""

        for i in instructions:
            scope = own[i["id"]]
            if not scope:
                scope = (through(i["id"], lambda j: users.get(j, []))
                         or through(i["id"],
                                    lambda j: by_id[j]["operands"]))
            scopes[i["name"]] = scope
    return scopes


def _program_scopes(all_planes: List[dict]) -> Dict[str, Dict[str, str]]:
    """{program id: hlo_scopes} of every executable the trace holds."""
    out = {}
    for plane in all_planes:
        if plane["name"] != "/host:metadata":
            continue
        for name, stats in plane["metadata"].values():
            proto = stats.get("Hlo Proto")
            m = re.search(r"\((\d+)\)$", name)
            if m and isinstance(proto, bytes):
                out[m.group(1)] = hlo_scopes(proto)
    return out


_INSTRUCTION = re.compile(r"^%?([^\s=]+)")


@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    device: Dict[str, List[list]] = {}
    scopes: Dict[str, List[str]] = {}
    host: List[list] = []
    spans: List[list] = []
    every = planes(path)
    programs = _program_scopes(every)
    for plane in every:
        if plane["name"].startswith(xplane.DEVICE_PLANE):
            for lname, events in plane["lines"]:
                if lname != xplane.OP_LINE:
                    continue
                device[plane["name"]] = [
                    [xplane.short_name(n), s, d] for n, s, d, _, _ in events]
                scopes[plane["name"]] = [
                    scope_of(mst.get(OP_NAME_STAT))
                    or programs.get(str(mst.get("program_id")), {}).get(
                        _INSTRUCTION.match(n).group(1), "")
                    for n, _, _, _, mst in events]
        elif plane["name"] == xplane.HOST_PLANE:
            for _, events in plane["lines"]:
                for n, s, d, st, _ in events:
                    if n.startswith(xplane.SPAN_PREFIX):
                        host.append([n, s, d])
                    elif n.startswith(PROGRAM_PREFIX):
                        spans.append([n, s, d, st])
    return {"device": device, "device_scopes": scopes,
            "host": sorted(host, key=lambda e: e[1]),
            "program_spans": sorted(spans, key=lambda e: e[1])}


def load_json(path: str) -> dict:
    """A recorded fixture; one cut by `xplane`'s recorder (PR 24) holds
    none of the program's names."""
    with open(path) as f:
        trace = json.load(f)
    trace.setdefault("program_spans", [])
    trace.setdefault("device_scopes", {
        plane: [""] * len(events) for plane, events in trace["device"].items()})
    return trace


def of_record(record) -> Optional[dict]:
    """The traced window of a run (`notes["trace_file"]`: the profiler's
    file, or a recorded fixture), or None when the run traced none."""
    path = (record.notes or {}).get("trace_file")
    if record.trace is None or not path or not os.path.exists(path):
        return None
    return load_json(path) if path.endswith(".json") else load(path)


# --------------------------------------------------------------------------
# reductions
# --------------------------------------------------------------------------

def window_of(trace: dict) -> Tuple[float, float]:
    """The traced window as `xplane.reduce` takes it: the first bench.*
    span's start to the last one's end."""
    host = trace["host"]
    return (min(e[1] for e in host), max(e[1] + e[2] for e in host))


def idle_intervals(trace: dict) -> List[Tuple[float, float]]:
    """Intervals of the window in which device 0 ran no operation."""
    lo, hi = window_of(trace)
    first = sorted(trace["device"])[0]
    merged = xplane.union(xplane._clip(
        [tuple(e) for e in trace["device"][first]], lo, hi))
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def split_idle(gaps, spans) -> Dict[str, float]:
    """Nanoseconds of the idle intervals `gaps` by the phase span each
    part of them lies under: {span name: ns, "outside": ns}. `spans` are
    (name, start_ns, dur_ns) of non-overlapping spans; what no span
    covers is "outside". Every nanosecond of a gap is counted once."""
    out: Dict[str, float] = {"outside": 0.0}
    spans = sorted(spans, key=lambda e: e[1])
    for g_lo, g_hi in gaps:
        covered = 0.0
        for name, start, dur in spans:
            if start >= g_hi:
                break
            ns = min(start + dur, g_hi) - max(start, g_lo)
            if ns > 0:
                out[name] = out.get(name, 0.0) + ns
                covered += ns
        out["outside"] += (g_hi - g_lo) - covered
    return out


def phase_spans(trace: dict) -> List[Tuple[str, float, float]]:
    names = {PROGRAM_PREFIX + "serve." + p for p in PHASES}
    return [(e[0], e[1], e[2]) for e in trace["program_spans"]
            if e[0] in names]


def idle_shares(trace: dict) -> Optional[Dict[str, float]]:
    """Percent of the traced window in which device 0 ran nothing and the
    host was in each phase: {"schedule": .., ..., "submit": ..,
    "outside": ..}. "outside" is what none of the six phases covers: the
    client's loop, and the microseconds of `ptpu.serve.step` between its
    phases. The seven add up to the idle share of the window. None when
    the trace holds no ptpu.* span."""
    if not trace["program_spans"]:
        return None
    lo, hi = window_of(trace)
    split = split_idle(idle_intervals(trace), phase_spans(trace))
    out = {p: 100.0 * split.get(PROGRAM_PREFIX + "serve." + p, 0.0)
           / (hi - lo) for p in PHASES}
    out["outside"] = 100.0 * split["outside"] / (hi - lo)
    return out


def scope_shares(trace: dict) -> Optional[Dict[str, float]]:
    """Percent of device busy time (the union of the operations' intervals,
    summed over the chips) that is SELF time of each scope's operations;
    "" is what carries no scope of SCOPES. None when no operation in the
    window carries one: a program without named scopes."""
    lo, hi = window_of(trace)
    busy, by_scope = 0.0, {}
    for plane in sorted(trace["device"]):
        named = [(scope, e[1], e[2]) for e, scope in
                 zip(trace["device"][plane], trace["device_scopes"][plane])]
        events = xplane._clip(named, lo, hi)
        busy += sum(e - s for s, e in xplane.union(events)) * 1e-9
        for scope, secs in xplane.self_times(events).items():
            by_scope[scope] = by_scope.get(scope, 0.0) + secs
    if not any(by_scope.get(s) for s in SCOPES) or busy <= 0:
        return None
    return {scope: 100.0 * secs / busy for scope, secs in by_scope.items()}


def step_durations_ms(trace: dict) -> List[float]:
    """Durations of the `ptpu.serve.step` spans that ran a batch."""
    return [e[2] * 1e-6 for e in trace["program_spans"]
            if e[0] == STEP and "batch" in e[3]]


# what the readers in benchmark/layer_metrics call

def serve_idle_share(record, phase: str) -> Optional[float]:
    trace = of_record(record)
    shares = idle_shares(trace) if trace else None
    return None if shares is None else shares[phase]


def scope_share(record, *scopes: str) -> Optional[float]:
    trace = of_record(record)
    shares = scope_shares(trace) if trace else None
    if shares is None:
        return None
    return sum(shares.get(s, 0.0) for s in scopes)


# --------------------------------------------------------------------------
# looking at a trace by hand, and cutting a fixture out of it
# --------------------------------------------------------------------------

def describe(path: str) -> None:
    for plane in planes(path):
        print("plane", plane["name"])
        for lname, events in plane["lines"]:
            own, meta = set(), set()
            for _, _, _, st, mst in events:
                own.update(st)
                meta.update(mst)
            print("   line", repr(lname), len(events), "events; stats",
                  sorted(own), "; metadata stats", sorted(meta))
            for n, s, d, st, mst in events[:2]:
                print("      ", n[:60], int(s), int(d),
                      {k: str(v)[:90] for k, v in {**mst, **st}.items()})


def cut(trace: dict, count: int, span: str = "bench.tick") -> dict:
    """The first `count` ticks: everything from the first `span`'s start
    to the start of the one after the last, times relative to it."""
    marks = [e for e in trace["host"] if e[0] == span]
    lo, hi = marks[0][1], marks[count][1]
    keep = lambda e: e[1] >= lo and e[1] + e[2] <= hi
    shift = lambda e: [e[0], e[1] - lo] + list(e[2:])
    device, scopes = {}, {}
    for plane, events in trace["device"].items():
        pairs = [(shift(e), s) for e, s in
                 zip(events, trace["device_scopes"][plane]) if keep(e)]
        device[plane] = [e for e, _ in pairs]
        scopes[plane] = [s for _, s in pairs]
    return {"device": device, "device_scopes": scopes,
            "host": [shift(e) for e in trace["host"] if keep(e)],
            "program_spans": [shift(e) for e in trace["program_spans"]
                              if keep(e)]}


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="an .xplane.pb file, or a directory "
                                  "that holds one")
    ap.add_argument("out", nargs="?")
    ap.add_argument("--count", type=int, default=2)
    args = ap.parse_args()
    path = (args.trace if os.path.isfile(args.trace)
            else xplane.newest_trace(args.trace))
    describe(path)
    if args.out:
        small = cut(load(path), args.count)
        with open(args.out, "w") as f:
            json.dump(small, f, separators=(",", ":"))
        print("wrote", args.out, os.path.getsize(args.out), "bytes;",
              {k: len(v) for k, v in small["device"].items()},
              "device events,", len(small["program_spans"]), "ptpu spans")
    return 0


if __name__ == "__main__":
    sys.exit(main())
