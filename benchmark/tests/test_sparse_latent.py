"""The cell `serve_sparse_latent_longctx` (PR 43): its configuration
against the catalog's numbers, its driver rehearsed on the CPU at the tiny
fixture, and its per-layer readers by hand on a recorded tick and on a
program that has none of the names."""
import importlib
import json
import math
import sys
import types

import pytest

from benchmark.drivers import closed_loop_serve_sparse_latent as D
from benchmark.end_to_end import decode_tokens_per_s, setup_s
from benchmark.layer_metrics import (
    batch_occupancy, held_experts_hit_share, held_pairs_share,
    index_scores_roofline, sparse_attention_roofline, sparse_selected_share,
    tick_index_share, tick_sparse_attention_share, tick_window_latent_share,
    window_latent_roofline)
from benchmark.lib import (program_trace, serve_window, sparse_latent_math,
                           sparse_latent_scopes as scopes, traffic as T)
from benchmark.lib.peaks import PEAKS
from benchmark.tests.helpers import ROOT_DIR, context

NEW = (tick_index_share, tick_sparse_attention_share,
       tick_window_latent_share, index_scores_roofline,
       sparse_attention_roofline, window_latent_roofline,
       sparse_selected_share)
CELL = "serve_sparse_latent_longctx"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(autouse=True)
def no_memory_stats(monkeypatch):
    monkeypatch.setattr(serve_window, "memory_peak_bytes", lambda: 0)


def cell_config():
    with open(f"{ROOT_DIR}/benchmark/configs/dots3-note-prev-serve.json") as f:
        return json.load(f)


def test_the_cells_configuration_keeps_the_catalogs_numbers():
    """Every key of the catalog's `config` under the same name but the five
    `reduced` ones; the share and the deployment are written out; the
    guide's floors hold."""
    cfg = cell_config()
    try:
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "dots3-note-prev")
    except OSError:
        pytest.skip("the catalog is not beside this checkout")
    pub = row["config"]
    assert {k: cfg[k] for k in pub if k not in cfg["reduced"]} == {
        k: v for k, v in pub.items() if k not in cfg["reduced"]}
    assert cfg["published"] == {k: pub[k] for k in cfg["reduced"]}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["max_position_embeddings"]) == (
        5, 32, 19008, 33280)
    assert cfg["layer_types"] == pub["layer_types"][:5]
    assert cfg["router_width"] == 256 and cfg["held_experts_first"] == 64
    # a whole period behind the leading dense layer, 8 experts, an eighth
    assert cfg["layer_types"][1:] == pub["layer_types"][1:5]
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8 and cfg["vocab_size"] * 8 >= 152064
    assert cfg["assumed"] and "8 chips" in cfg["deployment"]
    with open(f"{ROOT_DIR}/BENCHMARK.json") as f:
        bench = json.load(f)
    # by name, not by place: a later PR appends behind these
    entry = next(c for c in bench["configs"]
                 if c["name"] == "dots3-note-prev-serve")
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == list(cfg["reduced"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dots3-note-prev-serve", "closed_sparse32_24k1k", 1)
    metrics = {m["name"]: m for kind in ("end_to_end", "per_layer")
               for m in bench[kind]}
    for name in ("decode_tokens_per_s", "batch_occupancy",
                 "held_experts_roofline", "held_experts_hit_share",
                 "held_pairs_share"):
        assert CELL in metrics[name]["workloads"]
    for reader in NEW:
        m = metrics[reader.__name__.rsplit(".", 1)[1]]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "decode_tokens_per_s"
    e, tr = cfg["engine"], json.load(open(
        f"{ROOT_DIR}/benchmark/traffic/closed_sparse32_24k1k.json"))
    grid = T.prompt_grid(tr)
    assert (len(grid), grid[0], grid[-1]) == (32, 16384, 32256)
    assert tr["clients"] == e["max_batch"] == 32
    assert (tr["max_new_tokens"], tr["trace_ticks"], tr["order_seed"]) == (
        1024, 48, 0)
    assert grid[-1] + tr["max_new_tokens"] <= e["max_len"] == cfg[
        "max_position_embeddings"]
    assert e["num_blocks"] >= 32 * e["max_len"] // e["block_size"]
    span = -(-(cfg["sliding_window_size"] + e["token_budget"])
             // e["block_size"]) + 2
    assert e["window_blocks"] >= 32 * span
    c = cfg["correctness"]
    assert c["reference_len"] >= max(c["prompt_lens"]) + c["new_tokens"]
    topk = cfg["index_topk"]
    assert min(c["prompt_lens"]) + c["new_tokens"] <= topk
    assert any(topk < p < topk + e["token_budget"] for p in c["prompt_lens"])


def test_the_arithmetic_at_the_published_widths():
    cfg, peaks = cell_config(), PEAKS["TPU v5 lite"]
    # an index pair 2 x 64 x 128 FLOPs, an index key 256 B
    assert sparse_latent_math.index_least_seconds(cfg, 0, 10, peaks)[0] == \
        pytest.approx(10 * 2 * 64 * 128 / peaks["bf16_flops_per_s"])
    assert sparse_latent_math.index_least_seconds(cfg, 10, 0, peaks)[0] == \
        pytest.approx(10 * 256 / peaks["hbm_bytes_per_s"])
    # a selected pair 2 x 128 x 320 FLOPs; the rows read once: at most the
    # distinct keys, at most the pairs, 1,152 B each
    assert sparse_latent_math.sparse_least_seconds(cfg, 10**9, 7, peaks) == \
        sparse_latent_math.sparse_least_seconds(cfg, 7, 7, peaks)
    assert sparse_latent_math.sparse_least_seconds(cfg, 5, 10**6, peaks)[0] \
        == pytest.approx(max(10**6 * 2 * 128 * 320 / peaks["bf16_flops_per_s"],
                             5 * 1152 / peaks["hbm_bytes_per_s"]))
    # a window pair 2 x 64 x 384 FLOPs, a window key 2,176 B
    assert sparse_latent_math.window_least_seconds(cfg, 3, 0, peaks)[0] == \
        pytest.approx(3 * 2176 / peaks["hbm_bytes_per_s"])
    assert sparse_latent_math.window_least_seconds(cfg, 0, 3, peaks)[0] == \
        pytest.approx(3 * 2 * 64 * 384 / peaks["bf16_flops_per_s"])
    assert sparse_latent_math.selected_share(2048, 32768) == 6.25


def test_a_program_without_the_sparse_index_fails_at_once(monkeypatch):
    """The parent of PR 43 has no `ops/kernels/sparse_index.py`: the driver
    raises when run.py imports it, before any weight is made."""
    import paddle_tpu.ops.kernels as package
    name = "benchmark.drivers.closed_loop_serve_sparse_latent"
    monkeypatch.setitem(sys.modules, "paddle_tpu.ops.kernels.sparse_index",
                        None)
    monkeypatch.delattr(package, "sparse_index", raising=False)
    monkeypatch.delitem(sys.modules, name)
    with pytest.raises(ImportError):
        importlib.import_module(name)
    sys.modules[name] = D


def test_sparse_latent_driver_rehearsal():
    ctx = context("tiny-dots3", "tiny_sparse_latent_closed", seed=2**31 + 5,
                  seconds=1.0)
    rec = D.run(ctx)
    assert rec.correct, rec.notes
    n = rec.notes
    assert n["positions_judged"] == 36 and n["agreement"] >= 0.96
    assert n["prefix_cache"].startswith("off")
    for launch in ("decode", "mixed", "swa_decode", "swa_mixed"):
        assert n[f"{launch}_largest_error_over_tolerance"] < 1.0
        assert n[f"{launch}_pages_hold_the_rows"]
    assert n["decode_index_pages_hold_the_keys"]
    assert n["selection_equal_share"] == 1.0 and n["selection_rows_judged"]
    for rows in (4, 32):
        part = n[f"sparse_rows_{rows}"]
        assert part["padding_rows_zero"]
        assert part["those_equal_the_shared_expert"]
        assert part["largest_error_over_tolerance"] < 1.0
    assert rec.failed == 0 and rec.attempted > 0
    c = rec.counters
    assert c["compiles_in_window"] == 0
    assert c["moe_pairs"] == 2 * c["engine_tokens_computed"]
    assert 0 < c["moe_pairs_held"] < 4 * c["moe_pairs"]
    assert 0 < c["sparse_pairs_selected"] < c["index_pairs"]
    assert c["index_keys"] > 0 and c["attn_pairs_latent_window"] > 0
    assert c["window_pages_released"] > 0
    assert sparse_selected_share.read(rec) == pytest.approx(
        100 * c["sparse_pairs_selected"] / c["index_pairs"])
    assert 0 < held_experts_hit_share.read(rec) <= 100
    assert held_pairs_share.read(rec) == pytest.approx(
        100 * c["moe_pairs_held"] / (4 * c["moe_pairs"]))
    assert batch_occupancy.read(rec) >= 4
    for reader in (decode_tokens_per_s, setup_s):
        assert math.isfinite(reader.read(rec)) and reader.read(rec) >= 0
    # the whole window is judged, by its raw books; the notes say what
    # kinds of tick it held
    assert (c["tokens_out"], c["elapsed_s"]) == (c["tokens_out_raw"],
                                                 c["elapsed_raw_s"])
    assert n["pauses_left_out"]["tokens_out"] <= c["tokens_out"]
    held = n["window_ticks"]
    assert held["decode"] + held["chunk"] == c["ticks"]
    assert held["decode"] > 0 and held["chunk"] > 0 and held["last_ms"]
    assert "judged_ticks" not in n and "judged_ticks" not in ctx.traffic
    for reader in NEW[:6]:      # untraced: nothing to read, no raise
        assert reader.read(rec) is None


def test_readers_find_nothing_in_a_program_without_the_names(tmp_path):
    """A program that writes neither the counters nor the scopes nor the
    step fields (the parent): every reader returns None and none raises,
    traced or not."""
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
        "device": {"/device:TPU:0": [["paged_attention_decode.1", 0, ms],
                                     ["gmm.3", ms, ms]]},
        "device_scopes": {"/device:TPU:0": ["paged_attention", "experts"]},
        "host": [["bench.tick", 0, 2 * ms]],
        "program_spans": [["ptpu.serve.step", 0, 2 * ms, {"batch": 32}]]}))
    rec.trace, rec.notes = {"busy_s": 0.002}, {"trace_file": str(path)}
    for reader in NEW:
        assert reader.read(rec) is None


def test_the_scopes_reach_scope_of_once_registered():
    scopes.register()
    at = "jit(step_fn)/layers/while/body/"
    assert program_trace.scope_of(
        at + "qkv/index_q/dot_general") == scopes.INDEX_Q
    assert program_trace.scope_of(at + "qkv/index_k/mul") == scopes.INDEX_K
    assert program_trace.scope_of(
        at + "cond/branch_1_fun/index_scores/pallas_call") == scopes.SCORES
    assert program_trace.scope_of(
        at + "cond/branch_1_fun/index_select/while/body/reduce_sum"
    ) == scopes.SELECT
    assert program_trace.scope_of(
        at + "paged_attention/paged_attention_sparse/cond/gather"
    ) == scopes.SPARSE
    assert program_trace.scope_of(
        at + "paged_attention/paged_attention_latent_window/pallas_call"
    ) == scopes.WINDOW
    assert program_trace.scope_of(
        at + "paged_attention/paged_attention_latent/cond/pallas_call"
    ) == "paged_attention_latent"


def test_trace_readers_on_a_recorded_tick(tmp_path):
    """The readers by hand, on a trace in program_trace's own layout of
    two ticks (a decode tick of 32 rows at 24,000 positions; a chunk tick
    of 2,016 + 31 rows), with the engine's fields on their step spans."""
    ms = 1_000_000
    rows, ctx, k = 32, 24000, 2048
    decode = {"batch": 32, "kind": "decode",
              "index_keys": 2 * rows * ctx, "index_pairs": 2 * rows * ctx,
              "sparse_pairs_selected": 2 * rows * k,
              "attn_keys_latent_window": 3 * rows * 513,
              "attn_pairs_latent_window": 3 * rows * 513}
    chunk = {"batch": 32, "kind": "mixed",
             "index_keys": 2 * (31 * ctx + 26000),
             "index_pairs": 2 * (31 * ctx + 2016 * 25000),
             "sparse_pairs_selected": 2 * 2047 * k,
             "attn_keys_latent_window": 3 * (31 * 513 + 2528),
             "attn_pairs_latent_window": 3 * 2047 * 513}
    trace = {
        "device": {"/device:TPU:0": [
            ["fusion.1", 0, 2 * ms], ["fusion.2", 2 * ms, 3 * ms],
            ["fusion.3", 5 * ms, 5 * ms],
            ["paged_attention_latent_decode.4", 10 * ms, 1 * ms],
            ["fusion.5", 11 * ms, 9 * ms],
            ["paged_index_scores_chunk.6", 20 * ms, 30 * ms],
            ["fusion.7", 50 * ms, 40 * ms], ["fusion.8", 90 * ms, 150 * ms],
            ["paged_attention_latent_mixed.9", 240 * ms, 20 * ms],
            ["fusion.10", 260 * ms, 40 * ms]]},
        "device_scopes": {"/device:TPU:0": [
            scopes.SCORES, scopes.SELECT, scopes.SPARSE, scopes.WINDOW,
            "experts", scopes.SCORES, scopes.SELECT, scopes.SPARSE,
            scopes.WINDOW, "head"]},
        "host": [["bench.tick", 0, 20 * ms],
                 ["bench.tick", 20 * ms, 280 * ms]],
        "program_spans": [["ptpu.serve.step", 0, 20 * ms, decode],
                          ["ptpu.serve.step", 20 * ms, 280 * ms, chunk]],
    }
    path = tmp_path / "ticks.json"
    path.write_text(json.dumps(trace))
    cfg, peaks = cell_config(), PEAKS["TPU v5 lite"]
    counters = {"index_pairs": decode["index_pairs"] + chunk["index_pairs"],
                "sparse_pairs_selected": 2 * (rows + 2047) * k,
                "engine_steps": 2}
    rec = types.SimpleNamespace(
        trace={"busy_s": 0.300}, notes={"trace_file": str(path)},
        trace_counters=counters, counters=counters,
        context=types.SimpleNamespace(config=cfg, peaks=peaks))
    assert tick_index_share.read(rec) == pytest.approx(
        100 * (2 + 3 + 30 + 40) / 300)
    assert tick_sparse_attention_share.read(rec) == pytest.approx(
        100 * (5 + 150) / 300)
    assert tick_window_latent_share.read(rec) == pytest.approx(
        100 * (1 + 20) / 300)
    # each tick's own floor: bytes for the decode tick, FLOPs for the chunk
    flops, hbm = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    floor = (decode["index_keys"] * 256 / hbm
             + chunk["index_pairs"] * 2 * 64 * 128 / flops)
    assert index_scores_roofline.read(rec) == pytest.approx(
        100 * floor / 0.032)
    floor = (max(2 * rows * k * 1152 / hbm,
                 2 * rows * k * 2 * 128 * 320 / flops)
             + max(chunk["index_keys"] * 1152 / hbm,
                   chunk["sparse_pairs_selected"] * 2 * 128 * 320 / flops))
    assert sparse_attention_roofline.read(rec) == pytest.approx(
        100 * floor / 0.155)
    floor = (decode["attn_keys_latent_window"] * 2176 / hbm
             + max(chunk["attn_keys_latent_window"] * 2176 / hbm,
                   chunk["attn_pairs_latent_window"] * 2 * 64 * 384 / flops))
    assert window_latent_roofline.read(rec) == pytest.approx(
        100 * floor / 0.021)
    for reader in (index_scores_roofline, sparse_attention_roofline,
                   window_latent_roofline):
        assert reader.read(rec) < 100
    assert sparse_selected_share.read(rec) == pytest.approx(
        100 * counters["sparse_pairs_selected"] / counters["index_pairs"])
