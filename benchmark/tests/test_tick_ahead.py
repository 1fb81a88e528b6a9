"""`tick_ahead_share` on traces in program_trace's own layout: the share of
the step spans that ran a batch whose `ahead` field is 1, and nothing to
read on a program that writes no such field (the parent of PR 33)."""
import json
import types

import pytest

from benchmark.layer_metrics import tick_ahead_share

MS = 1_000_000


def record(tmp_path, fields):
    trace = {
        "device": {"/device:TPU:0": [["fusion.1", 0, MS]]},
        "device_scopes": {"/device:TPU:0": ["ffn"]},
        "host": [["bench.tick", 0, len(fields) * 10 * MS]],
        "program_spans": [["ptpu.serve.step", i * 10 * MS, 9 * MS, f]
                          for i, f in enumerate(fields)],
    }
    path = tmp_path / "ticks.json"
    path.write_text(json.dumps(trace))
    return types.SimpleNamespace(trace={"busy_s": 0.001},
                                 notes={"trace_file": str(path)})


@pytest.mark.parametrize("fields, want", [
    # 15 decode ticks launched ahead and the mixed tick that admits a
    # prompt, planned with every id known: serve_decode's cycle
    ([{"batch": 16, "ahead": 1}] * 15 + [{"batch": 16, "ahead": 0}], 93.75),
    ([{"batch": 4, "ahead": 0}] * 3, 0.0),
    # a call that ran no batch (a settled tick's events, an idle engine)
    # is no tick
    ([{"batch": 2, "ahead": 1}, {"tick": 7}, {"batch": 2, "ahead": 0}], 50.0),
    # the parent's spans carry no `ahead`: nothing to read, no error
    ([{"batch": 16, "kind": "decode"}] * 4, None),
    ([], None),
])
def test_share_of_step_spans_launched_ahead(tmp_path, fields, want):
    assert tick_ahead_share.read(record(tmp_path, fields)) == want


def test_an_untraced_run_reads_nothing(tmp_path):
    rec = record(tmp_path, [{"batch": 1, "ahead": 1}])
    rec.trace = None
    assert tick_ahead_share.read(rec) is None
    rec.trace, rec.notes = {"busy_s": 0.0}, {}
    assert tick_ahead_share.read(rec) is None
