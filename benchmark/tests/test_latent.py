"""What PR 41 adds to the benchmark, on tiny fixtures on the CPU (counts
and comparisons only, no chip number): the long-context closed loop of a
model with latent attention and a held share of its experts, its six
readers, its scopes, and the arithmetic they are measured against."""
import importlib
import json
import math
import sys
import types

import pytest

import jax.numpy as jnp

from benchmark.drivers import closed_loop_serve_latent as D
from benchmark.end_to_end import decode_tokens_per_s, setup_s
from benchmark.layer_metrics import (batch_occupancy, held_experts_hit_share,
                                     held_experts_roofline, held_pairs_share,
                                     latent_attention_roofline,
                                     latent_row_fill_share,
                                     tick_latent_attention_share)
from benchmark.lib import (latent_math, latent_scopes, program_trace,
                           reference_kimi, serve_window, traffic as T)
from benchmark.lib.peaks import PEAKS
from benchmark.tests.helpers import ROOT_DIR, context

NEW = (tick_latent_attention_share, latent_attention_roofline,
       held_experts_roofline, held_experts_hit_share, held_pairs_share,
       latent_row_fill_share)
CELL = "serve_latent_longctx_decode"


@pytest.fixture(autouse=True)
def no_memory_stats(monkeypatch):
    monkeypatch.setattr(serve_window, "memory_peak_bytes", lambda: 0)


def cell_config():
    with open(f"{ROOT_DIR}/benchmark/configs/kimi-k2.6-serve.json") as f:
        return json.load(f)


def test_the_cells_configuration_keeps_the_catalogs_numbers():
    """Every number of the catalog's `config` under the same key but the
    four `reduced` names; the share and the deployment are written out."""
    cfg = cell_config()
    published = {
        "first_k_dense_replace": 1, "hidden_size": 7168,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 1,
        "n_shared_experts": 1, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_key_value_heads": 64,
        "num_nextn_predict_layers": 0, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_theta": 50000,
        "routed_scaling_factor": 2.827, "topk_group": 1, "v_head_dim": 128,
        "ep_size": 1}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert (cfg["scoring_func"], cfg["topk_method"], cfg["model_type"],
            cfg["norm_topk_prob"], cfg["tie_word_embeddings"]) == (
        "sigmoid", "noaux_tc", "kimi_k2", True, False)
    assert cfg["published"] == {
        "num_hidden_layers": 61, "n_routed_experts": 384,
        "vocab_size": 163840, "max_position_embeddings": 262144}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["max_position_embeddings"]) == (
        6, 12, 20480, 9216)
    assert cfg["router_width"] == 384
    assert 0 <= cfg["held_experts_first"] <= 384 - 12
    # the guide's floors: four sparse layers, 8 experts, an eighth
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8 and cfg["vocab_size"] * 8 >= 163840
    assert set(cfg["reduced"]) == set(cfg["published"])
    assert cfg["assumed"] and "32 chips" in cfg["deployment"]
    with open(f"{ROOT_DIR}/BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "kimi-k2.6-serve")
    assert set(entry["reduced"]) == set(cfg["reduced"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-k2.6-serve", "closed_latent64_8k1k", 1)
    for name in ("decode_tokens_per_s", "batch_occupancy"):
        metric = next(m for kind in ("end_to_end", "per_layer")
                      for m in bench[kind] if m["name"] == name)
        assert metric["workloads"][-1] == CELL
    assert [m["name"] for m in bench["per_layer"][-6:]] == [
        r.__name__.rsplit(".", 1)[1] for r in NEW]
    assert all(m["workloads"] == [CELL] and m["moves"] ==
               "decode_tokens_per_s" for m in bench["per_layer"][-6:])
    e, tr = cfg["engine"], json.load(open(
        f"{ROOT_DIR}/benchmark/traffic/closed_latent64_8k1k.json"))
    grid = T.prompt_grid(tr)
    assert (len(grid), grid[0], grid[-1]) == (64, 4096, 8128)
    assert tr["clients"] == e["max_batch"] == 64
    assert (tr["max_new_tokens"], tr["trace_ticks"], tr["order_seed"]) == (
        1024, 48, 0)
    assert grid[-1] + tr["max_new_tokens"] <= e["max_len"] == cfg[
        "max_position_embeddings"]
    assert e["num_blocks"] >= 64 * e["max_len"] // e["block_size"]
    c = cfg["correctness"]
    assert c["reference_len"] >= max(c["prompt_lens"]) + c["new_tokens"]
    assert any(p < 4096 < p + c["new_tokens"] for p in c["prompt_lens"])


def test_the_arithmetic_at_the_published_widths():
    cfg, peaks = cell_config(), PEAKS["TPU v5 lite"]
    assert latent_math.key_bytes(cfg) == 1152
    assert latent_math.pair_flops(cfg) == 64 * 2 * 320
    assert latent_math.expert_params(cfg) == 3 * 7168 * 2048
    assert latent_math.sparse_layers(cfg) == 5
    assert latent_math.row_fill_share(cfg, 640 * 2) == pytest.approx(90.0)
    assert latent_math.row_fill_share(cfg, 576 * 2) == pytest.approx(100.0)
    # a decode tick's rows are memory-bound, a chunk's compute-bound
    rows, ctx = 64, 6600
    sec, bound = latent_math.attention_least_seconds(
        cfg, 6 * rows * ctx, 6 * rows * ctx, peaks)
    assert bound == "memory" and sec == pytest.approx(
        6 * rows * ctx * 1152 / 819e9)
    sec, bound = latent_math.attention_least_seconds(
        cfg, 6 * 7000, 6 * 960 * 6500, peaks)
    assert bound == "compute" and sec == pytest.approx(
        6 * 960 * 6500 * 40960 / 197e12)
    # 16 pairs on an expert's 88 MB: memory-bound either way
    sec, bound = latent_math.experts_least_seconds(cfg, 45, 80, peaks)
    assert bound == "memory" and sec == pytest.approx(
        45 * 3 * 7168 * 2048 * 2 / 819e9)
    assert latent_math.hit_share(cfg, 45, 1) == pytest.approx(75.0)
    assert latent_math.held_pairs_share(cfg, 80, 512) == pytest.approx(3.125)
    # the reference reads the same file on its own
    kw = reference_kimi.model_kw(cfg)
    assert kw["held"] == (cfg["held_experts_first"], 12)
    assert kw["scale"] == pytest.approx(192 ** -0.5 * 1.4158883 ** 2)
    lcfg = D.kimi_config(cfg, jnp.bfloat16)
    assert lcfg.score_scale == pytest.approx(kw["scale"])
    assert lcfg.held == kw["held"] and lcfg.num_experts == 384


def test_a_program_without_latent_attention_fails_at_once(monkeypatch):
    """The parent of PR 41 has no `paged_attention_latent`: the driver
    raises when run.py imports it, before any weight is made."""
    import paddle_tpu.ops.pallas as package
    name = "benchmark.drivers.closed_loop_serve_latent"
    monkeypatch.setitem(sys.modules,
                        "paddle_tpu.ops.pallas.paged_attention_latent", None)
    monkeypatch.delattr(package, "paged_attention_latent")
    monkeypatch.delitem(sys.modules, name)
    with pytest.raises(ImportError):
        importlib.import_module(name)
    sys.modules[name] = D


def test_latent_driver_rehearsal():
    ctx = context("tiny-kimi", "tiny_latent_closed", seed=2**31 + 5,
                  seconds=1.0)
    rec = D.run(ctx)
    assert rec.correct, rec.notes
    n = rec.notes
    assert n["positions_judged"] == 36 and n["agreement"] >= 0.98
    assert n["prefix_cache"] == "on"
    for launch in ("decode", "mixed"):
        assert n[f"latent_{launch}_largest_error_over_tolerance"] < 1.0
        assert n[f"latent_{launch}_pages_hold_the_rows"]
    for rows in (4, 32):
        part = n[f"sparse_rows_{rows}"]
        assert part["padding_rows_zero"]
        assert part["those_equal_the_shared_expert"]
        assert part["largest_error_over_tolerance"] < 1.0
    assert rec.failed == 0 and rec.attempted > 0
    c = rec.counters
    assert c["compiles_in_window"] == 0
    assert c["moe_pairs"] == 2 * c["engine_tokens_computed"]
    assert 0 < c["moe_pairs_held"] < 2 * c["moe_pairs"]
    assert c["attn_pairs_latent"] >= c["attn_keys_latent"] > 0
    assert c["latent_row_bytes"] == 128 * 2      # 48 values in 128 lanes
    assert latent_row_fill_share.read(rec) == pytest.approx(100 * 48 / 128)
    assert 0 < held_experts_hit_share.read(rec) <= 100
    assert held_pairs_share.read(rec) == pytest.approx(
        100 * c["moe_pairs_held"] / (2 * c["moe_pairs"]))
    assert batch_occupancy.read(rec) >= 4
    for reader in (decode_tokens_per_s, setup_s):
        assert math.isfinite(reader.read(rec)) and reader.read(rec) >= 0
    assert {"gap_p90_ms", "ttft_mean_ms", "tick_p50_ms"} <= set(
        n["not_judged"])
    # the judged rate is the raw window's; the filtered books are a note
    assert (c["tokens_out"], c["elapsed_s"]) == (c["tokens_out_raw"],
                                                 c["elapsed_raw_s"])
    assert n["pauses_left_out"]["decode_tokens_per_s"] > 0
    for reader in NEW[:3]:      # untraced: nothing to read, no raise
        assert reader.read(rec) is None


def test_the_balanced_bias_evens_the_held_experts_share():
    """A seeded router's experts differ in popularity; balanced as
    `noaux_tc` balances them, the four held of sixteen carry about their
    quarter of the pairs on other traffic than the batch they were
    balanced on, whatever the seed."""
    import jax
    import numpy as np

    from benchmark.drivers.closed_loop_serve import build_engine
    from benchmark.tests.helpers import fixture
    from paddle_tpu.models import llama as L
    cfg = fixture("configs", "tiny-kimi")
    lcfg = D.kimi_config(cfg, jnp.float32)

    def share(params, seed):
        eng = build_engine(cfg, params, lcfg)
        rng = np.random.default_rng(seed + 100)
        for n in (90, 70, 110):
            eng.submit(rng.integers(1, 512, n).tolist(), max_new_tokens=30)
        eng.run()
        return eng.stats["moe_pairs_held"] / (eng.stats["moe_pairs"] * 2)

    seeded, balanced = [], []
    for seed in (2, 3, 4):
        params = L.init_params(lcfg, jax.random.PRNGKey(seed))
        seeded.append(share(params, seed))
        dense, sparse = params["blocks"]
        bias = D.balanced_bias(params, lcfg, seed, tokens=512, steps=400,
                               rate=2e-3)
        assert bias.shape == sparse["router_bias"].shape
        balanced.append(share({**params, "blocks": (
            dense, {**sparse, "router_bias": bias})}, seed))
    assert max(seeded) - min(seeded) > 0.15            # 0.07 .. 0.31
    assert all(abs(b - 0.25) < 0.06 for b in balanced), balanced


def test_readers_find_nothing_in_a_program_without_the_names(tmp_path):
    """A program that writes neither the counters nor the scopes nor the
    step fields (the parent): every reader returns None and none raises,
    traced or not."""
    rec = types.SimpleNamespace(
        counters={"engine_steps": 3, "engine_tokens_computed": 48,
                  "moe_pairs": 384, "moe_experts_hit": 100},
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
        "program_spans": [["ptpu.serve.step", 0, 2 * ms,
                           {"batch": 64, "moe_experts_hit": 45}]]}))
    rec.trace, rec.notes = {"busy_s": 0.002}, {"trace_file": str(path)}
    for reader in NEW:
        assert reader.read(rec) is None


def test_the_scopes_reach_scope_of_only_once_registered(monkeypatch):
    read = "jit(step_fn)/layers/while/body/paged_attention/" \
        "paged_attention_latent/pallas_call"
    out = "jit(step_fn)/layers/while/body/attn_out/latent_out/dot_general"
    monkeypatch.setattr(program_trace, "SCOPES", frozenset(
        s for s in program_trace.SCOPES if s not in latent_scopes.LATENT))
    assert program_trace.scope_of(read) == "paged_attention"
    assert program_trace.scope_of(out) == "attn_out"
    latent_scopes.register()
    assert program_trace.scope_of(read) == latent_scopes.READ
    assert program_trace.scope_of(out) == latent_scopes.OUT
    assert program_trace.scope_of(
        "jit(step_fn)/layers/while/body/qkv/latent_q/dot_general"
    ) == latent_scopes.Q
    assert program_trace.scope_of(
        "jit(step_fn)/layers/while/body/qkv/latent_kv/mul"
    ) == latent_scopes.KV


def test_trace_readers_on_a_recorded_tick(tmp_path):
    """The readers by hand, on a trace in program_trace's own layout of
    two ticks as the chip ran them (a decode tick of 64 rows at ~6,600
    positions; a chunk tick of 960 + 63 rows), with the engine's fields on
    their step spans."""
    ms = 1_000_000
    rows, ctx = 64, 6600
    decode = {"batch": 64, "attn_keys_latent": 6 * rows * ctx,
              "attn_pairs_latent": 6 * rows * ctx, "moe_experts_hit": 45,
              "moe_pairs_held": 80, "moe_pairs": 512}
    chunk = {"batch": 64, "attn_keys_latent": 6 * (63 * ctx + 7000),
             "attn_pairs_latent": 6 * (63 * ctx + 960 * 6500),
             "moe_experts_hit": 60, "moe_pairs_held": 1536,
             "moe_pairs": 8184}
    trace = {
        "device": {"/device:TPU:0": [
            ["paged_attention_latent_decode.1", 0, 8 * ms],
            ["fusion.2", 8 * ms, 2 * ms], ["fusion.3", 10 * ms, 1 * ms],
            ["fusion.4", 11 * ms, 2 * ms], ["gmm.5", 13 * ms, 4 * ms],
            ["fusion.6", 17 * ms, 3 * ms],
            ["paged_attention_latent_decode.7", 20 * ms, 10 * ms],
            ["paged_attention_latent_mixed.10", 30 * ms, 30 * ms],
            ["gmm.8", 60 * ms, 10 * ms], ["fusion.9", 70 * ms, 30 * ms]]},
        "device_scopes": {"/device:TPU:0": [
            latent_scopes.READ, latent_scopes.Q, latent_scopes.KV,
            latent_scopes.OUT, "experts", "ffn", latent_scopes.READ,
            latent_scopes.READ, "experts", "head"]},
        "host": [["bench.tick", 0, 20 * ms], ["bench.tick", 20 * ms, 80 * ms]],
        "program_spans": [["ptpu.serve.step", 0, 20 * ms, decode],
                          ["ptpu.serve.step", 20 * ms, 80 * ms, chunk]],
    }
    path = tmp_path / "ticks.json"
    path.write_text(json.dumps(trace))
    cfg, peaks = cell_config(), PEAKS["TPU v5 lite"]
    counters = {"moe_experts_hit": 105, "moe_pairs_held": 1616,
                "moe_pairs": 8696, "engine_steps": 2,
                "latent_row_bytes": 1280.0}
    rec = types.SimpleNamespace(
        trace={"busy_s": 0.100}, notes={"trace_file": str(path)},
        trace_counters=counters, counters=counters,
        context=types.SimpleNamespace(config=cfg, peaks=peaks))
    assert tick_latent_attention_share.read(rec) == pytest.approx(
        100 * (8 + 2 + 1 + 2 + 10 + 30) / 100)
    # the floor is each tick's own: bytes for the decode tick, FLOPs for
    # the chunk tick, over the 48 ms under the launches' scope
    floor = (6 * rows * ctx * 1152 / 819e9
             + 6 * (63 * ctx + 960 * 6500) * 40960 / 197e12)
    assert latent_attention_roofline.read(rec) == pytest.approx(
        100 * floor / 0.048)
    assert latent_attention_roofline.read(rec) < 100
    floor = (45 + 60) * 3 * 7168 * 2048 * 2 / 819e9
    assert held_experts_roofline.read(rec) == pytest.approx(
        100 * floor / 0.014)
    assert held_experts_hit_share.read(rec) == pytest.approx(
        100 * 105 / (2 * 5 * 12))
    assert held_pairs_share.read(rec) == pytest.approx(
        100 * 1616 / (8696 * 5))
    assert latent_row_fill_share.read(rec) == pytest.approx(90.0)
