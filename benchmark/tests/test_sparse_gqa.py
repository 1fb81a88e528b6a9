"""The cell `serve_sparse_gqa_sessions_longctx` (PR 50): its configuration
against the catalog's numbers, its traffic letter for letter, its driver
rehearsed on the CPU at the tiny fixture, and its per-layer readers by hand
on a recorded tick and on a program that has none of the names."""
import json
import math
import types

import numpy as np
import pytest

from benchmark.drivers import closed_loop_sparse_sessions as D
from benchmark.end_to_end import decode_tokens_per_s, setup_s
from benchmark.layer_metrics import (
    batch_occupancy, gqa_index_scores_roofline, gqa_sparse_selected_share,
    gqa_tick_index_share, gqa_tick_sparse_attention_share,
    held_experts_hit_share, held_experts_roofline, held_pairs_share,
    session_prefix_hit_share, sparse_gqa_attention_roofline,
    tick_index_select_share, turn_first_token_ms)
from benchmark.lib import (serve_window, sparse_gqa_math,
                           sparse_gqa_scopes as scopes, traffic as T)
from benchmark.lib.peaks import PEAKS
from benchmark.tests.helpers import ROOT_DIR, context, fixture

NEW = (gqa_index_scores_roofline, sparse_gqa_attention_roofline,
       tick_index_select_share, session_prefix_hit_share,
       turn_first_token_ms, gqa_tick_index_share,
       gqa_tick_sparse_attention_share, gqa_sparse_selected_share)
LISTED = (batch_occupancy, held_experts_hit_share, held_pairs_share,
          held_experts_roofline)
CELL = "serve_sparse_gqa_sessions_longctx"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
name_of = lambda reader: reader.__name__.rsplit(".", 1)[1]


@pytest.fixture(autouse=True)
def no_memory_stats(monkeypatch):
    monkeypatch.setattr(serve_window, "memory_peak_bytes", lambda: 0)


def cell_config():
    with open(f"{ROOT_DIR}/benchmark/configs/"
              "keye-vl2-30b-a3b-serve.json") as f:
        return json.load(f)


def cell_traffic():
    with open(f"{ROOT_DIR}/benchmark/traffic/"
              "closed_sparse_sessions16_32k64k.json") as f:
        return json.load(f)


def test_the_cells_configuration_keeps_the_catalogs_numbers():
    """Every key of the catalog's `config` under the same name but the four
    `reduced` ones; the share and the deployment are written out; the
    guide's floors hold."""
    cfg = cell_config()
    try:
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Keye-VL-2.0-30B-A3B")
    except OSError:
        pytest.skip("the catalog is not beside this checkout")
    pub = row["config"]
    assert {k: cfg[k] for k in pub if k not in cfg["reduced"]} == {
        k: v for k, v in pub.items() if k not in cfg["reduced"]}
    assert cfg["published"] == {k: pub[k] for k in cfg["reduced"]}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (4, 16, 18992, 65536)
    assert (cfg["router_width"], cfg["held_experts_first"]) == (128, 32)
    assert (cfg["n_routed_experts"], cfg["first_k_dense_replace"]) == (16, 0)
    # four layers of a period of one, 8 experts, an eighth of the rows
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= 151936
    assert len(cfg["assumed"]) >= 8 and "8 chips" in cfg["deployment"]
    with open(f"{ROOT_DIR}/BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "keye-vl2-30b-a3b-serve")
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == list(cfg["reduced"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "keye-vl2-30b-a3b-serve", "closed_sparse_sessions16_32k64k", 1)
    metrics = {m["name"]: m for kind in ("end_to_end", "per_layer")
               for m in bench[kind]}
    for name in ("decode_tokens_per_s", *map(name_of, LISTED)):
        assert metrics[name]["workloads"][-1] == CELL
    for reader in NEW:
        m = metrics[name_of(reader)]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "decode_tokens_per_s"


def test_the_traffic_is_the_issues_letter_for_letter():
    cfg, tr = cell_config(), cell_traffic()
    e = cfg["engine"]
    assert tr["kind"] == "closed_loop_sparse_sessions"
    assert tr["clients"] == e["max_batch"] == 16 and tr["turns"] == 8
    lengths = sorted(D.context_length(tr, c) for c in range(16))
    assert lengths == list(range(32768, 63488 + 1, 2048))   # one a client
    assert T.prompt_grid(tr) == [32, 64, 96, 128]
    assert (tr["max_new_tokens"], tr["stagger"], tr["order_seed"],
            tr["trace_ticks"]) == (128, True, 0, 48)
    assert [T.new_tokens(tr, c, 0) for c in (0, 7, 15)] == [8, 64, 128]
    # the longest request fills max_len; the pool holds every slot's
    assert lengths[-1] + 8 * (128 + 128) == e["max_len"] == 65536
    assert e["num_blocks"] == 16 * 65536 // 16 + 2048
    # no two clients share a page, a seed makes the ids
    a, b = (D.context_tokens(tr, 2**31 + 7, c, 18992) for c in (0, 1))
    assert a[:16].tolist() != b[:16].tolist() and a.min() >= 1
    assert np.array_equal(a, D.context_tokens(tr, 2**31 + 7, 0, 18992))
    c = cfg["correctness"]
    topk = cfg["sa_config"]["topk"]
    assert c["prompt_lens"] == [1500, 4090, 12000] and c["new_tokens"] == 64
    assert c["prompt_lens"][0] + 64 <= topk < c["prompt_lens"][1]
    assert (c["prompt_lens"][2] + 2 * 64 + c["turn_more"]
            <= c["reference_len"] == 12288)
    assert c["copy_keep"] % e["block_size"] == 8         # half a page
    # one request's decode rows lie past the crossing of the two sparse
    # reads at these widths in bf16: they gather, as most of the window's
    from paddle_tpu.ops.kernels.serving_attention import (
        sparse_walk_keys_heads)
    crossing = sparse_walk_keys_heads(cfg["num_key_value_heads"],
                                      cfg["head_dim"], 2, topk)
    assert 32768 < crossing < c["long_prompt_len"] == 41000
    assert (c["long_prompt_len"] + 64 <= c["long_reference_len"] == 41088
            and c["long_reference_len"] % 128 == 0)


def test_the_arithmetic_at_the_published_widths():
    cfg, peaks = cell_config(), PEAKS["TPU v5 lite"]
    flops, hbm = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    # an index pair 2 x 16 x 64 FLOPs, an index key 128 B
    assert sparse_gqa_math.index_pair_flops(cfg) == 2048
    assert sparse_gqa_math.index_key_bytes(cfg) == 128
    assert sparse_gqa_math.index_least_seconds(cfg, 0, 10, peaks)[0] == \
        pytest.approx(10 * 2048 / flops)
    assert sparse_gqa_math.index_least_seconds(cfg, 10, 0, peaks)[0] == \
        pytest.approx(10 * 128 / hbm)
    # a selected pair 2 x 32 x 256 FLOPs; a position 2,048 B, read once:
    # at most the distinct keys, at most the pairs
    assert sparse_gqa_math.pair_flops(cfg) == 16384
    assert sparse_gqa_math.position_bytes(cfg) == 2048
    assert sparse_gqa_math.sparse_least_seconds(cfg, 10**9, 7, peaks) == \
        sparse_gqa_math.sparse_least_seconds(cfg, 7, 7, peaks)
    assert sparse_gqa_math.sparse_least_seconds(cfg, 5, 10**6, peaks)[0] \
        == pytest.approx(max(10**6 * 16384 / flops, 5 * 2048 / hbm))


def test_sparse_sessions_driver_rehearsal(monkeypatch):
    # (a 64-wide toy in bf16 ties far more often than the published widths:
    # the cell's limit is the chip's, this rehearsal's the toy's)
    monkeypatch.setattr(D.agreement_sparse_gqa, "MIN_AGREEMENT", 0.85)
    ctx = context("tiny-keye", "tiny_sparse_sessions", seed=2**31 + 5,
                  seconds=1.0)
    rec = D.run(ctx)
    assert rec.correct, rec.notes
    n = rec.notes
    assert n["positions_judged"] == 6 * 12 and n["agreement"] >= 0.85
    assert len(n["agreement_by_request"]) == 6      # the long one last
    assert n["rows_gathered"] >= n["rows_gathered_wanted"] == 2 * 11
    assert n["prefix_cache"] == "on"
    assert n["cached_hit_tokens"] >= n["cached_hit_tokens_wanted"] == 140
    assert n["cached_page_copies"] >= 1
    for tick in D.TICKS:
        assert n[f"{tick}_largest_error_over_tolerance"] < 1.0
        assert all(n[f"{tick}_pages_hold_the_rows"].values())
    assert n["selection_equal_share"] == 1.0 and n["selection_rows_judged"]
    for rows in (4, 32):
        part = n[f"sparse_rows_{rows}"]
        assert part["padding_rows_zero"] and part["those_are_zero"]
        assert part["largest_error_over_tolerance"] < 1.0
    assert rec.failed == 0 and rec.attempted > 0
    c = rec.counters
    assert c["compiles_in_window"] == 0
    assert 0 < c["moe_pairs_held"] < 2 * c["moe_pairs"]
    assert 0 < c["sparse_pairs_selected"] < c["index_pairs"]
    assert c["index_keys"] > 0 and c["index_pages_live"] > 0
    # the window holds no context's prefill: sessions come back to cached
    # pages, whatever the program's speed
    assert 80 < session_prefix_hit_share.read(rec) <= 100
    assert session_prefix_hit_share.read(rec) == pytest.approx(
        100 * c["prefix_hit_tokens"] / c["prompt_tokens_submitted"])
    assert turn_first_token_ms.read(rec) > 0
    assert gqa_sparse_selected_share.read(rec) == pytest.approx(
        100 * c["sparse_pairs_selected"] / c["index_pairs"])
    assert 0 < held_experts_hit_share.read(rec) <= 100
    assert held_pairs_share.read(rec) == pytest.approx(
        100 * c["moe_pairs_held"] / (2 * c["moe_pairs"]))
    assert batch_occupancy.read(rec) >= 2
    for reader in (decode_tokens_per_s, setup_s):
        assert math.isfinite(reader.read(rec)) and reader.read(rec) >= 0
    # judged on the books with the machine's pauses left out; the raw
    # window stays in the notes
    assert c["tokens_out"] <= c["tokens_out_raw"] == n["raw_window"][
        "tokens_out"]
    held = n["window_ticks"]
    assert held["decode"] + held["with_new_rows"] == c["ticks"]
    assert held["turns_finished"] > 0
    for reader in NEW[:3]:      # untraced: nothing to read, no raise
        assert reader.read(rec) is None


def test_a_clients_turns_grow_one_session_and_start_the_next(monkeypatch):
    """A turn's prompt is the context, the session's earlier parts with the
    engine's answers and a new part; after `turns` turns a new session over
    the same context."""
    ctx = context("tiny-keye", "tiny_sparse_sessions", seed=3)
    sent, admitted = [], set()
    eng = types.SimpleNamespace(
        stats={"tokens_computed": 0, "steps": 0, **dict.fromkeys(D.STATS, 0)},
        blocks=types.SimpleNamespace(stats={"prefix_hit_tokens": 0},
                                     has_sequence=admitted.__contains__),
        stream=lambda rid: [7, 8, 9], run=lambda: None,
        submit=lambda tokens, **kw: sent.append(tokens) or len(sent))
    loop = D.SparseSessionsLoop(eng, ctx, None)
    # every context prefilled once, alone, before the first turn
    assert [len(t) for t in sent] == [len(c) for c in loop.contexts]
    assert loop.prompt_tokens_submitted == 0
    del sent[:]
    client = loop.clients[1]
    for _ in range(3):
        loop.submit(client)
    context_ids = loop.contexts[1]
    assert len(context_ids) == D.context_length(ctx.traffic, 1)
    first, second, third = sent
    assert np.array_equal(first[:len(context_ids)], context_ids)
    assert np.array_equal(second[:len(first)], first)
    assert second[len(first):len(first) + 3].tolist() == [7, 8, 9]
    assert np.array_equal(third[:len(context_ids)], context_ids)
    assert len(third) < len(second)       # a new session, the same context
    # a prompt's tokens enter the books with its hits: once admitted
    loop.count_admitted()
    assert loop.prompt_tokens_submitted == 0
    admitted.update(rid for rid, _ in loop.pending[:2])
    loop.count_admitted()
    assert loop.prompt_tokens_submitted == len(first) + len(second)
    assert [n for _, n in loop.pending] == [len(third)]


def parent_like(tmp_path=None):
    return types.SimpleNamespace(
        counters={"engine_steps": 3, "engine_tokens_computed": 48},
        samples={}, trace=None, trace_counters=None, notes={},
        context=types.SimpleNamespace(config=cell_config(),
                                      peaks=PEAKS["TPU v5 lite"]))


def test_readers_find_nothing_in_a_program_without_the_names(tmp_path):
    """A program that writes neither the counters nor the scopes nor the
    step fields (the parent): every new reader returns None and none
    raises, traced or not."""
    rec = parent_like()
    for reader in NEW:
        assert reader.read(rec) is None
    ms = 1_000_000
    path = tmp_path / "tick.json"
    path.write_text(json.dumps({
        "device": {"/device:TPU:0": [["paged_attention_decode.1", 0, ms],
                                     ["gmm.3", ms, ms]]},
        "device_scopes": {"/device:TPU:0": ["paged_attention", "experts"]},
        "host": [["bench.tick", 0, 2 * ms]],
        "program_spans": [["ptpu.serve.step", 0, 2 * ms, {"batch": 16}]]}))
    rec.trace, rec.notes = {"busy_s": 0.002}, {"trace_file": str(path)}
    for reader in NEW:
        assert reader.read(rec) is None


def test_trace_readers_on_a_recorded_tick(tmp_path):
    """The readers by hand, on a trace in program_trace's own layout of two
    ticks (a decode tick of 16 rows at 48,000 positions; a tick with a
    turn's 128 new rows), with the engine's fields on their step spans."""
    scopes.register()
    ms = 1_000_000
    rows, ctx, k, layers = 16, 48000, 2048, 4
    decode = {"batch": 16, "kind": "decode",
              "index_keys": layers * rows * ctx,
              "index_pairs": layers * rows * ctx,
              "sparse_pairs_selected": layers * rows * k}
    turn = {"batch": 16, "kind": "mixed",
            "index_keys": layers * (15 * ctx + 50000),
            "index_pairs": layers * (15 * ctx + 128 * 50000),
            "sparse_pairs_selected": layers * 143 * k}
    trace = {
        "device": {"/device:TPU:0": [
            ["fusion.1", 0, 2 * ms], ["fusion.2", 2 * ms, 1 * ms],
            ["paged_attention_decode_masked.3", 3 * ms, 12 * ms],
            ["fusion.4", 15 * ms, 5 * ms],
            ["paged_index_scores_chunk.5", 20 * ms, 4 * ms],
            ["fusion.6", 24 * ms, 6 * ms],
            ["paged_attention_mixed_masked.7", 30 * ms, 20 * ms]]},
        "device_scopes": {"/device:TPU:0": [
            scopes.SCORES, scopes.SELECT, scopes.SPARSE, "experts",
            scopes.SCORES, scopes.SELECT, scopes.SPARSE]},
        "host": [["bench.tick", 0, 20 * ms], ["bench.tick", 20 * ms, 30 * ms]],
        "program_spans": [["ptpu.serve.step", 0, 20 * ms, decode],
                          ["ptpu.serve.step", 20 * ms, 30 * ms, turn]],
    }
    path = tmp_path / "ticks.json"
    path.write_text(json.dumps(trace))
    cfg, peaks = cell_config(), PEAKS["TPU v5 lite"]
    rec = types.SimpleNamespace(
        trace={"busy_s": 0.050}, notes={"trace_file": str(path)},
        trace_counters={}, counters={}, samples={"ttft_ms": [30.0, 50.0]},
        context=types.SimpleNamespace(config=cfg, peaks=peaks))
    assert tick_index_select_share.read(rec) == pytest.approx(100 * 7 / 50)
    assert gqa_tick_index_share.read(rec) == pytest.approx(100 * 13 / 50)
    assert gqa_tick_sparse_attention_share.read(rec) == pytest.approx(
        100 * 32 / 50)
    flops, hbm = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    # each tick's own floor: bytes for the decode tick, FLOPs for the turn
    floor = (decode["index_keys"] * 128 / hbm
             + max(turn["index_pairs"] * 2048 / flops,
                   turn["index_keys"] * 128 / hbm))
    assert gqa_index_scores_roofline.read(rec) == pytest.approx(
        100 * floor / 0.006)
    floor = sum(max(f["sparse_pairs_selected"] * 16384 / flops,
                    min(f["index_keys"], f["sparse_pairs_selected"])
                    * 2048 / hbm) for f in (decode, turn))
    assert sparse_gqa_attention_roofline.read(rec) == pytest.approx(
        100 * floor / 0.032)
    for reader in (gqa_index_scores_roofline, sparse_gqa_attention_roofline):
        assert 0 < reader.read(rec) < 100
    assert turn_first_token_ms.read(rec) == 40.0


def test_the_fixture_traffic_deals_its_contexts():
    tr = fixture("traffic", "tiny_sparse_sessions")
    assert sorted(D.context_length(tr, c) for c in range(2)) == [40, 48]
