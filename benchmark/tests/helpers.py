"""Shared by the benchmark's tests: fixture loading and a context."""
import json
import os
import time

from benchmark.lib.compile_log import CompileLog
from benchmark.lib.harness import Context
from benchmark.lib.peaks import PEAKS

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
ROOT_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def fixture(kind: str, name: str) -> dict:
    with open(os.path.join(FIXTURES, kind, name + ".json")) as f:
        return json.load(f)


_LOG = []


def context(config: str, traffic: str, seed: int = 1, seconds: float = 0.3,
            trace: bool = False) -> Context:
    if not _LOG:            # listeners cannot be unregistered: make one
        _LOG.append(CompileLog())
    return Context(
        workload={"name": "fixture", "chips": 1}, config=fixture("configs", config),
        traffic=fixture("traffic", traffic), seed=seed, seconds=seconds,
        trace=trace, device={"platform": "cpu", "kind": "cpu", "count": 1},
        peaks=PEAKS["TPU v5 lite"], process_start_s=time.perf_counter(),
        compile_log=_LOG[0])
