"""What PR 56 adds to the benchmark, on tiny fixtures on the CPU (counts
and comparisons only, no chip number): the closed loop of a model whose
layers are mostly state-space mixers with a per-sequence state pool, its
five readers, its scopes, its arithmetic, the limits of its comparison."""
import json
import math
import types

import pytest

import jax.numpy as jnp
import numpy as np

from benchmark.drivers import closed_loop_serve_ssm as D
from benchmark.end_to_end import decode_tokens_per_s, setup_s
from benchmark.layer_metrics import (batch_occupancy,
                                     ssm_chunk_scan_roofline,
                                     ssm_state_update_roofline,
                                     ssm_tick_attention_share,
                                     ssm_tick_ffn_share,
                                     ssm_tick_head_sample_share,
                                     state_slots_live_share,
                                     tick_ssm_share, tick_ssm_state_share)
from benchmark.lib import (agreement_ssm, program_trace, reference_granite4,
                           serve_window, ssm_math, ssm_scopes, traffic as T)
from benchmark.lib.peaks import PEAKS
from benchmark.tests.helpers import ROOT_DIR, context

NEW = (tick_ssm_share, tick_ssm_state_share, ssm_state_update_roofline,
       ssm_chunk_scan_roofline, state_slots_live_share)
# the accepted readers of the rest of the tick, under entries that move
# this cell's judged metric (PR 56, second session)
REST = (ssm_tick_attention_share, ssm_tick_ffn_share,
        ssm_tick_head_sample_share)
LISTED = ("batch_occupancy", "window_prefill_tick_share",
          "plain_tick_p50_ms", "prefill_tick_p50_ms",
          "window_host_gap_share", "window_ticks_ahead_share")
CELL = "serve_ssm_chat_decode64"
CONFIG = "granite-4.0-h-micro-serve"


@pytest.fixture(autouse=True)
def no_memory_stats(monkeypatch):
    monkeypatch.setattr(serve_window, "memory_peak_bytes", lambda: 0)


def cell_config():
    with open(f"{ROOT_DIR}/benchmark/configs/{CONFIG}.json") as f:
        return json.load(f)


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        rows = [json.loads(line) for line in open(path)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    return next(r for r in rows if r["name"] == "granite-4.0-h-micro")


def test_the_cells_configuration_keeps_the_catalogs_numbers():
    """Every key of the catalog's `config` is in the file with the
    catalog's value but `max_position_embeddings`; the entries are the
    issue's, looked up by name."""
    cfg, row = cell_config(), catalog_row()
    published = dict(row["config"])
    assert published.pop("max_position_embeddings") == 131072
    assert {k: cfg[k] for k in published} == published
    assert cfg["source"] == row["source_url"]
    assert cfg["published"] == {"max_position_embeddings": 131072}
    assert list(cfg["reduced"]) == ["max_position_embeddings"]
    assert cfg["max_position_embeddings"] == cfg["engine"]["max_len"] == 1664
    assert (cfg["num_hidden_layers"], len(cfg["layer_types"]),
            cfg["layer_types"].count("mamba")) == (40, 40, 36)
    for key in ("assumed", "deployment", "engine_why"):
        assert cfg[key]
    assert cfg["correctness"]["why"] and "float32" in " ".join(cfg["assumed"])
    with open(f"{ROOT_DIR}/BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == list(cfg["reduced"])
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "closed_ssm_chat64_1k512", 1)
    metrics = {m["name"]: m for kind in ("end_to_end", "per_layer")
               for m in bench[kind]}
    for name in ("decode_tokens_per_s", *LISTED):
        assert CELL in metrics[name]["workloads"]
    for name in ("gap_p90_ms", "ttft_mean_ms", "tick_ffn_share"):
        assert CELL not in metrics[name]["workloads"]
    for reader in NEW + REST:
        m = metrics[reader.__name__.rsplit(".", 1)[1]]
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["moves"] == "decode_tokens_per_s"
        assert (m["layer"], m["source"]) == (
            ("server", "program_counter")
            if m["name"] == "state_slots_live_share"
            else ("kernels", "device_trace"))
    e = cfg["engine"]
    with open(f"{ROOT_DIR}/benchmark/traffic/"
              "closed_ssm_chat64_1k512.json") as f:
        tr = json.load(f)
    grid = T.prompt_grid(tr)
    assert (len(grid), grid[0], grid[-1], grid[1] - grid[0]) == (
        64, 128, 1136, 16)
    assert sum(grid) / len(grid) == 632
    assert tr["clients"] == e["max_batch"] == e["state_slots"] == 64
    assert (tr["kind"], tr["max_new_tokens"], tr["trace_ticks"],
            tr["order_seed"], tr["stagger"]) == (
        "closed_loop_serve_ssm", 512, 48, 0, True)
    assert grid[-1] + tr["max_new_tokens"] <= e["max_len"]
    assert e["num_blocks"] == 64 * e["max_len"] // e["block_size"] + 1024
    assert (e["token_budget"], e["state_dtype"]) == (512, "float32")
    c = cfg["correctness"]
    assert c["prompt_lens"] == [100, 700, 1136] and c["new_tokens"] == 64
    assert (c["batch_first_prompt"] + e["max_batch"] - 1
            + c["batch_new_tokens"]) <= c["batch_reference_len"]
    assert e["max_batch"] % c["batch_reference_group"] == 0
    assert e["max_batch"] * c["batch_new_tokens"] == 1024
    assert c["reference_len"] >= max(c["prompt_lens"]) + c["new_tokens"]
    assert c["state_prompt"] + c["state_new_tokens"] <= e["max_len"]


def test_the_arithmetic_at_the_published_widths():
    cfg = cell_config()
    kw = reference_granite4.model_kw(cfg)
    assert kw["ssm"] == (64, 64, 128, 1, 4) and kw["scale"] == 1 / 64
    assert (kw["embed_scale"], kw["residual_scale"], kw["logit_divisor"],
            sum(kw["plan"])) == (12.0, 0.22, 8.0, 36)
    lcfg = D.granite_config(cfg, jnp.bfloat16)
    assert lcfg.num_params() == 3_191_396_096 and lcfg.head_dim == 64
    assert [i for i, s in enumerate(lcfg.layers) if s.attn == "full"] == [
        5, 15, 25, 35]
    assert all(s.rope is None for s in lcfg.layers)
    assert ssm_math.shapes(cfg) == (64, 64, 128, 1, 4, 4352)
    assert ssm_math.slot_bytes(cfg) == 2_123_264
    assert ssm_math.step_bytes(cfg) == 4_246_528
    assert 65 * 36 * ssm_math.slot_bytes(cfg) == 4_968_437_760
    assert ssm_math.scan_row_flops(cfg) == 4 * 128 * 4096
    peaks = PEAKS["TPU v5 lite"]
    # a decode tick's 64 x 36 updates: 9.78 GB, 11.9 ms
    assert ssm_math.step_least_seconds(cfg, 64 * 36, peaks) == (
        pytest.approx(0.011946, rel=1e-3))
    # a row's u, B, C, delta and y are 17,152 B (20.9 ns) against 2.1
    # MFLOP (10.6 ns): a chunk is bound by its bytes at these widths
    assert ssm_math.scan_row_bytes(cfg) == 17_152
    secs, by = ssm_math.scan_least_seconds(cfg, 448 * 36, 36, peaks)
    assert by == "memory" and secs == pytest.approx(
        36 * (4_246_528 + 448 * 17_152) / 819e9)


def test_a_program_without_state_space_layers_fails_at_once(monkeypatch):
    """The parent of PR 56 has no `llama.SsmSpec`: the driver raises in
    `granite_config`, before any weight is made."""
    from paddle_tpu.models import llama as L
    monkeypatch.delattr(L, "SsmSpec")
    ctx = context("tiny-granite", "tiny_ssm_closed", seed=1)
    with pytest.raises(NotImplementedError, match="state-space layers"):
        D.run(ctx)


def test_ssm_driver_rehearsal():
    ctx = context("tiny-granite", "tiny_ssm_closed", seed=2**31 + 5,
                  seconds=1.0)
    rec = D.run(ctx)
    assert rec.correct, rec.notes
    n = rec.notes
    assert n["positions_judged"] == 36 and n["agreement"] >= 0.98
    assert n["batch_positions_judged"] == 16 and n["batch_agreement"] >= 0.98
    assert n["batch_most_slots_live"] == 4
    for launch in ("decode", "mixed"):
        assert n[f"heads64_{launch}_largest_error_over_tolerance"] < 1.0
        assert n[f"heads64_{launch}_pages_hold_the_rows"]
    for name in ("decode", "chunk", "three_chunks", "idle"):
        part = n["ssm_" + name]
        assert part["carried_rows_exact"] and part["those_untouched"]
        assert part["state_largest_error_over_tolerance"] < 0.05
        assert part[
            "y_behind_the_references_convolution_over_tolerance"] < 0.05
    assert n["carried_state_error_over_tolerance"] < 1e-3
    assert n["prefix_cache"].startswith("off")
    assert rec.failed == 0 and rec.attempted > 0
    c = rec.counters
    assert c["compiles_in_window"] == 0
    assert c["ssm_step_rows"] + c["ssm_scan_rows"] == (
        9 * c["engine_tokens_computed"]) > 0
    assert 0 < c["state_slots_live"] <= 4 * c["engine_steps"]
    assert batch_occupancy.read(rec) >= 4
    for reader in (decode_tokens_per_s, setup_s):
        assert math.isfinite(reader.read(rec)) and reader.read(rec) >= 0
    assert {"gap_p90_ms", "ttft_mean_ms", "tick_p50_ms"} <= set(
        n["not_judged"])
    # the judged rate is `serve_window`'s (pauses of the machine left
    # out); the raw window is a note
    assert c["tokens_out"] <= c["tokens_out_raw"]
    assert n["raw_window"]["tokens_out"] == c["tokens_out_raw"]
    assert decode_tokens_per_s.read(rec) == pytest.approx(
        c["tokens_out"] / c["elapsed_s"])
    for reader in NEW + REST:   # untraced: nothing to read, no raise
        assert reader.read(rec) is None


def test_the_limits():
    ref = np.random.default_rng(0).normal(size=(5, 256)).astype(np.float32)
    rounded = np.asarray(jnp.asarray(ref).astype(jnp.bfloat16)
                         .astype(jnp.float32))
    good, worst = agreement_ssm.judge_rows(rounded, ref)
    assert good and 0.15 < worst < 0.5
    # a state kept in bfloat16 is ~1.7e-3 off, 17 times STATE_TOL
    assert 5 < agreement_ssm.state_error(rounded, ref) / (
        agreement_ssm.STATE_TOL) < 40
    assert agreement_ssm.judge_states(ref * (1 + 5e-5), ref,
                                      agreement_ssm.STATE_TOL)[0]
    assert not agreement_ssm.judge_states(ref[::-1], ref,
                                          agreement_ssm.CARRIED_TOL)[0]


def test_readers_find_nothing_in_a_program_without_the_names(tmp_path):
    """A program that writes neither the scopes nor the step fields (the
    parent): every new reader returns None and none raises, traced or
    not."""
    rec = types.SimpleNamespace(
        counters={"engine_steps": 3, "engine_tokens_computed": 48},
        trace=None, trace_counters=None, notes={},
        context=types.SimpleNamespace(config=cell_config(),
                                      peaks=PEAKS["TPU v5 lite"]))
    for reader in NEW:
        assert reader.read(rec) is None
    ms = 1_000_000
    path = tmp_path / "tick.json"
    path.write_text(json.dumps({
        "device": {"/device:TPU:0": [["fusion.1", 0, ms],
                                     ["fusion.3", ms, ms]]},
        "device_scopes": {"/device:TPU:0": ["attn_out", "ffn"]},
        "host": [["bench.tick", 0, 2 * ms]],
        "program_spans": [["ptpu.serve.step", 0, 2 * ms, {"batch": 64}]]}))
    rec.trace, rec.notes = {"busy_s": 0.002}, {"trace_file": str(path)}
    for reader in NEW:
        assert reader.read(rec) is None


def test_the_scopes_reach_scope_of_only_once_registered(monkeypatch):
    step = "jit(step_fn)/layers/while/body/ssm/ssm_step/mul"
    out = "jit(step_fn)/layers/while/body/while/body/ssm/ssm_out/add"
    monkeypatch.setattr(program_trace, "SCOPES", frozenset(
        s for s in program_trace.SCOPES if s not in ssm_scopes.SSM))
    assert program_trace.scope_of(step) == "layers"
    ssm_scopes.register()
    assert program_trace.scope_of(step) == ssm_scopes.STEP
    assert program_trace.scope_of(out) == ssm_scopes.OUT
    assert program_trace.scope_of(
        "jit(step_fn)/layers/while/body/ssm/ssm_scan/while/body/while/"
        "body/dot_general") == ssm_scopes.SCAN
    assert program_trace.scope_of(
        "jit(step_fn)/layers/while/body/ssm/reshape") == ssm_scopes.ALL


def test_trace_readers_on_a_recorded_tick(tmp_path):
    """The five readers by hand, on a trace in program_trace's own layout
    of two ticks (a decode tick of 64 rows; a tick of 63 rows beside a
    449-row chunk), with the engine's fields on their step spans."""
    ms = 1_000_000
    decode = {"batch": 64, "ssm_step_rows": 36 * 64, "ssm_scan_rows": 0,
              "ssm_segments": 0, "state_slots_live": 64}
    chunk = {"batch": 64, "ssm_step_rows": 36 * 63,
             "ssm_scan_rows": 36 * 449, "ssm_segments": 36,
             "state_slots_live": 62}
    S = ssm_scopes
    trace = {
        "device": {"/device:TPU:0": [
            ["fusion.1", 0, 3 * ms], ["fusion.2", 3 * ms, 1 * ms],
            ["fusion.3", 4 * ms, 18 * ms], ["fusion.4", 22 * ms, 1 * ms],
            ["fusion.5", 23 * ms, 2 * ms], ["fusion.6", 25 * ms, 5 * ms],
            ["fusion.7", 30 * ms, 2 * ms], ["fusion.8", 32 * ms, 18 * ms],
            ["while.9", 50 * ms, 8 * ms], ["fusion.10", 58 * ms, 2 * ms]]},
        "device_scopes": {"/device:TPU:0": [
            S.IN, S.CONV, S.STEP, S.GATE, S.OUT, "ffn",
            S.CONV, S.STEP, S.SCAN, S.ALL]},
        "host": [["bench.tick", 0, 30 * ms], ["bench.tick", 30 * ms, 30 * ms]],
        "program_spans": [["ptpu.serve.step", 0, 30 * ms, decode],
                          ["ptpu.serve.step", 30 * ms, 30 * ms, chunk]],
    }
    path = tmp_path / "ticks.json"
    path.write_text(json.dumps(trace))
    cfg, peaks = cell_config(), PEAKS["TPU v5 lite"]
    rec = types.SimpleNamespace(
        trace={"busy_s": 0.060}, notes={"trace_file": str(path)},
        trace_counters={}, counters={},
        context=types.SimpleNamespace(config=cfg, peaks=peaks))
    assert tick_ssm_share.read(rec) == pytest.approx(100 * 55 / 60)
    assert tick_ssm_state_share.read(rec) == pytest.approx(100 * 47 / 60)
    rows, other = 36 * 127, 36 * 449
    took = (36 + 3 * rows / (rows + other)) * 1e-3
    assert ssm_state_update_roofline.read(rec) == pytest.approx(
        100 * rows * 4_246_528 / 819e9 / took)
    floor = max(36 * 449 * 2_097_152 / 197e12,
                (36 * 4_246_528 + 36 * 449 * ssm_math.scan_row_bytes(cfg))
                / 819e9)
    assert ssm_chunk_scan_roofline.read(rec) == pytest.approx(
        100 * floor / 8e-3)
    assert ssm_chunk_scan_roofline.read(rec) < 100
    assert state_slots_live_share.read(rec) == pytest.approx(
        100 * 126 / 128)
    assert ssm_tick_ffn_share.read(rec) == pytest.approx(100 * 5 / 60)
    assert ssm_tick_attention_share.read(rec) == 0
    assert ssm_tick_head_sample_share.read(rec) == 0
