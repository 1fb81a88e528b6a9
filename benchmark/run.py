"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One new process per run: load, warm up, measure, print one JSON object as
the last line of standard output, exit. Without the TPU devices the cell
asks for, or on a device kind the peaks table lacks, it exits non-zero and
prints no result. `--trace 0` reports the cell's end-to-end metrics;
`--trace 1` reports its per-layer metrics and, after the measured window,
profiles a short further window for the device's busy time and the
breakdown.

Everything that belongs to one cell is found by the names in
BENCHMARK.json: configs/<file>, traffic/<traffic>.json, the traffic's
`kind` -> drivers/<kind>.py, end_to_end/<metric>.py and
layer_metrics/<metric>.py. Adding a cell, a configuration, a traffic mix,
a kind of driver or a metric is adding files and BENCHMARK.json entries.
"""
from __future__ import annotations

import time

PROCESS_START_S = time.perf_counter()

import argparse       # noqa: E402
import importlib      # noqa: E402
import json           # noqa: E402
import math           # noqa: E402
import os             # noqa: E402
import sys            # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)     # the program (paddle_tpu) and this package


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json "
                     f"(have {[e['name'] for e in entries]})")


def metrics_of(bench: dict, kind: str, workload: str):
    """The cell's metrics of one kind: those with no `workloads` key and
    those that list the cell."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def read_metrics(bench, kind, subdir, record, required: bool):
    out = {}
    for m in metrics_of(bench, kind, record.context.workload["name"]):
        reader = importlib.import_module(f"benchmark.{subdir}.{m['name']}")
        value = reader.read(record)
        if value is None:
            if required:
                raise SystemExit(f"benchmark: end-to-end metric {m['name']} "
                                 f"found nothing to read")
            continue    # a reader with nothing to read: leave it out
        value = float(value)
        if not math.isfinite(value):
            raise SystemExit(f"benchmark: metric {m['name']} is {value}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find(bench["workloads"], args.workload, "workload")
    config_entry = find(bench["configs"], cell["config"], "config")
    config = load_json(ROOT, config_entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")

    from benchmark.lib import compile_log, harness, peaks   # imports jax

    try:
        device = harness.require_tpu(cell["chips"])
    except harness.BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    cache_dir = harness.configure_compile_cache()
    ctx = harness.Context(
        workload=cell, config=config, traffic=traffic, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), device=device,
        peaks=peaks.peaks_for(device["kind"]),
        process_start_s=PROCESS_START_S,
        compile_log=compile_log.CompileLog())
    driver = importlib.import_module(f"benchmark.drivers.{traffic['kind']}")
    record = driver.run(ctx)

    if args.trace:
        metrics = read_metrics(bench, "per_layer", "layer_metrics", record,
                               required=False)
    else:
        metrics = read_metrics(bench, "end_to_end", "end_to_end", record,
                               required=True)
    device["memory_peak_bytes"] = record.memory_peak_bytes
    result = {"correct": bool(record.correct),
              "attempted": int(record.attempted),
              "failed": int(record.failed), "metrics": metrics,
              "device": device}
    if record.trace is not None:
        device["busy_s"] = record.trace["busy_s"]
        device["window_s"] = record.trace["window_s"]
        result["breakdown"] = {"device_ops": record.trace["device_ops"],
                               "idle_gaps": record.trace["idle_gaps"]}
    # for a reader of the log, not for the driver: counts and notes
    result["counters"] = record.counters
    result["notes"] = dict(record.notes, setup_s=record.setup_s,
                           compile_cache=cache_dir,
                           executables_made=ctx.compile_log.made,
                           cache_hits=ctx.compile_log.hits)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
