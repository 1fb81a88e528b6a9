"""`index_walk_run_share` (PR 53) by hand on a window in program_trace's
own layout, and on the programs that give it nothing to read."""
import json
import types

import pytest

from benchmark.layer_metrics import index_walk_run_share

MS = 1_000_000


def record(tmp_path, steps):
    path = tmp_path / "window.json"
    path.write_text(json.dumps({
        "device": {"/device:TPU:0": [["fusion.1", 0, MS]]},
        "device_scopes": {"/device:TPU:0": ["index_scores"]},
        "host": [["bench.tick", 0, MS * len(steps)]],
        "program_spans": [["ptpu.serve.step", i * MS, MS, f]
                          for i, f in enumerate(steps)]}))
    return types.SimpleNamespace(trace={"busy_s": 0.001},
                                 notes={"trace_file": str(path)})


def test_the_share_is_the_runs_over_the_blocks_of_the_windows_ticks(tmp_path):
    rec = record(tmp_path, [
        {"batch": 16, "index_blocks": 3072, "index_blocks_run": 3000},
        {"batch": 16, "index_blocks": 1024, "index_blocks_run": 0},
        {"batch": 16}])       # a tick of a program part that wrote neither
    assert index_walk_run_share.read(rec) == pytest.approx(
        100 * 3000 / 4096)


@pytest.mark.parametrize("steps", [
    [{"batch": 16, "index_keys": 5}],                       # the parent
    [{"batch": 16, "index_blocks": 0, "index_blocks_run": 0}],  # none selected
    []], ids=["parent", "no_selection", "no_tick"])
def test_nothing_to_read_is_none_and_does_not_raise(tmp_path, steps):
    assert index_walk_run_share.read(record(tmp_path, steps)) is None
    untraced = types.SimpleNamespace(trace=None, notes={})
    assert index_walk_run_share.read(untraced) is None
