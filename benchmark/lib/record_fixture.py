"""Cut a small recorded trace out of a traced run, for the reduction's
test: the device events and bench.* spans of the first few ticks, as JSON
in the layout `xplane.load` returns.

    JAX_PLATFORMS=cpu python3 benchmark/lib/record_fixture.py \
        .bench_cache/trace/<workload> out.json --span bench.tick --count 2

Also prints the trace's planes and lines, which is how the layout the
reduction relies on was read off a chip trace in the first place.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    import jax

    from benchmark.lib import xplane

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("out")
    ap.add_argument("--span", default="bench.tick")
    ap.add_argument("--count", type=int, default=2)
    args = ap.parse_args()
    path = xplane.newest_trace(args.trace_dir)
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("plane", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("   line", repr(line.name), len(events),
                  [e.name for e in events[:3]])
    trace = xplane.load(path)
    marks = [e for e in trace["host"] if e[0] == args.span]
    lo = marks[0][1]
    hi = marks[args.count][1]          # start of the tick after the last
    inside = lambda e: e[1] >= lo and e[1] + e[2] <= hi
    cut = lambda events: [[e[0], e[1] - lo, e[2]] for e in events
                          if inside(e)]
    small = {"device": {k: cut(v) for k, v in trace["device"].items()},
             "host": cut(trace["host"])}
    with open(args.out, "w") as f:
        json.dump(small, f, separators=(",", ":"))
    print("wrote", args.out, os.path.getsize(args.out), "bytes;",
          {k: len(v) for k, v in small["device"].items()}, "device events,",
          len(small["host"]), "spans")
    return 0


if __name__ == "__main__":
    sys.exit(main())
