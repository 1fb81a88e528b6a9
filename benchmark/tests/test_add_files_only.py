"""A later PR adds a configuration, a traffic mix, a kind of driver, a
per-layer metric and cells by adding files and BENCHMARK.json entries,
editing no file that is there. Shown on a temporary copy, driven on the
CPU by a wrapper that stands in for the TPU check (run.py itself refuses
to run without one)."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

from benchmark.tests.helpers import FIXTURES, ROOT_DIR

WRAPPER = """
import runpy, sys
sys.path.insert(0, {copy!r})
import benchmark.lib.harness as harness
import benchmark.drivers.closed_loop_serve as serve
harness.require_tpu = lambda chips: {{"platform": "cpu",
                                     "kind": "TPU v5 lite", "count": chips}}
serve.memory_peak_bytes = lambda: 0
sys.argv = ["run.py"] + sys.argv[1:]
runpy.run_path({copy!r} + "/benchmark/run.py", run_name="__main__")
"""


def digests(root):
    out = {}
    for base, _, files in os.walk(root):
        if "__pycache__" in base:
            continue
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def run_cell(tmp, copy, workload, trace):
    wrapper = os.path.join(tmp, "drive.py")
    with open(wrapper, "w") as f:
        f.write(WRAPPER.format(copy=copy))
    out = subprocess.run(
        [sys.executable, wrapper, "--workload", workload, "--seed",
         str(2**31 + 99), "--seconds", "1", "--trace", str(trace)],
        cwd=copy, capture_output=True, text=True, timeout=600,
        env={"JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT_DIR,
             "PATH": "/usr/bin:/bin", "HOME": tmp})
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_new_cells_are_files_and_entries_only(tmp_path):
    tmp = str(tmp_path)
    copy = os.path.join(tmp, "checkout")
    shutil.copytree(os.path.join(ROOT_DIR, "benchmark"),
                    os.path.join(copy, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digests(os.path.join(copy, "benchmark"))

    # files: a configuration, two traffic mixes, a kind of driver, readers
    bench_dir = os.path.join(copy, "benchmark")
    for kind, names in (("configs", ["tiny-serve.json"]),
                        ("traffic", ["tiny_closed.json",
                                     "fixture_count.json"]),
                        ("drivers", ["fixture_counter.py"]),
                        ("layer_metrics", ["fixture_spans.py",
                                           "fixture_absent.py"])):
        for name in names:
            target = os.path.join(bench_dir, kind, name)
            assert not os.path.exists(target)
            shutil.copy(os.path.join(FIXTURES, kind, name), target)

    # entries: BENCHMARK.json gains entries, and the new cells' names in
    # the `workloads` of the metrics they report
    with open(os.path.join(ROOT_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    old = json.loads(json.dumps(bench))
    bench["configs"].append({
        "name": "tiny-serve", "source": "fixture",
        "file": "benchmark/configs/tiny-serve.json", "reduced": [],
        "why": "fixture"})
    cells = ["fixture_serve", "fixture_count"]
    bench["workloads"] += [
        {"name": "fixture_serve", "config": "tiny-serve",
         "traffic": "tiny_closed", "chips": 1, "why": "fixture"},
        {"name": "fixture_count", "config": "tiny-serve",
         "traffic": "fixture_count", "chips": 1, "why": "fixture"}]
    for m in bench["end_to_end"]:
        if m["name"] in ("gap_p90_ms", "ttft_mean_ms"):
            m["workloads"].append("fixture_serve")
    for name in ("fixture_spans", "fixture_absent"):
        bench["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_span", "layer": "entry", "moves": "setup_s",
            "workloads": cells})
    with open(os.path.join(copy, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    for key in ("configs", "workloads"):
        assert bench[key][:len(old[key])] == old[key]

    serve = run_cell(tmp, copy, "fixture_serve", trace=0)
    assert serve["correct"] and serve["failed"] == 0 < serve["attempted"]
    assert set(serve["metrics"]) == {"gap_p90_ms", "ttft_mean_ms", "setup_s"}
    assert serve["metrics"]["gap_p90_ms"]["unit"] == "ms"
    assert serve["device"]["count"] == 1

    count = run_cell(tmp, copy, "fixture_count", trace=1)
    assert count["correct"] and count["attempted"] == 7
    # the new reader is there, the one with nothing to read is left out,
    # and so are the readers of other cells
    assert set(count["metrics"]) == {"compiles_in_window", "fixture_spans"}
    assert count["metrics"]["fixture_spans"]["value"] == 7

    after = digests(os.path.join(copy, "benchmark"))
    assert {k: after[k] for k in before} == before
    assert len(after) == len(before) + 6
