"""What PR 34 adds to the benchmark, on tiny fixtures on the CPU (counts
and comparisons only, no chip number): the long-context closed loop of a
model with a layer plan, its nine readers, its scopes, and the faults
nearest to each tolerance of its `correct`."""
import json
import math
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.drivers import closed_loop_serve_longctx as lc
from benchmark.end_to_end import decode_tokens_per_s, setup_s
from benchmark.layer_metrics import (batch_occupancy,
                                     full_attention_roofline,
                                     moe_experts_hit_share,
                                     sparse_experts_hit_share,
                                     sparse_experts_roofline,
                                     tick_full_attention_share,
                                     tick_shared_expert_share,
                                     tick_window_attention_share,
                                     window_attention_roofline,
                                     window_keys_share,
                                     window_pool_pages_share)
from benchmark.lib import (agreement_blockdiff, agreement_moe, laguna_scopes,
                           program_trace, reference_laguna, serve_window,
                           traffic as T, window_math)
from benchmark.lib.peaks import PEAKS
from benchmark.tests.helpers import ROOT_DIR, context, fixture

NEW = (tick_full_attention_share, tick_window_attention_share,
       tick_shared_expert_share, full_attention_roofline,
       window_attention_roofline, sparse_experts_roofline,
       sparse_experts_hit_share, window_keys_share, window_pool_pages_share)


@pytest.fixture(autouse=True)
def no_memory_stats(monkeypatch):
    monkeypatch.setattr(serve_window, "memory_peak_bytes", lambda: 0)


def cell_config():
    with open(f"{ROOT_DIR}/benchmark/configs/laguna-xs2-serve.json") as f:
        return json.load(f)


def test_the_cells_configuration_keeps_the_catalogs_numbers():
    """Every number of the catalog's `config` under the same key but the
    two `reduced` names; the three per-layer lists are cut to the depth,
    and the depth is layer 0 and one whole period."""
    cfg = cell_config()
    published = {
        "vocab_size": 100352, "hidden_size": 2048, "intermediate_size": 8192,
        "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "sliding_window": 512, "partial_rotary_factor": 0.5,
        "moe_routed_scaling_factor": 2.5}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_parameters"]["full_attention"] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
    assert cfg["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1}
    assert (cfg["gating"], cfg["tie_word_embeddings"], cfg["attention_bias"],
            cfg["moe_apply_router_weight_on_input"]) == (True, False, False,
                                                         False)
    assert cfg["num_hidden_layers"] == 5 == len(cfg["layer_types"])
    assert cfg["layer_types"] == ["full_attention"] + [
        "sliding_attention"] * 3 + ["full_attention"]
    assert cfg["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert set(cfg["reduced"]) == set(cfg["published"])
    with open(f"{ROOT_DIR}/BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "laguna-xs2-serve")
    assert set(entry["reduced"]) == set(cfg["reduced"])
    e, tr = cfg["engine"], json.load(open(
        f"{ROOT_DIR}/benchmark/traffic/closed_longctx32_8k1k.json"))
    grid = T.prompt_grid(tr)
    assert (len(grid), grid[0], grid[-1]) == (32, 4096, 8064)
    assert tr["clients"] == e["max_batch"] == 32
    assert grid[-1] + tr["max_new_tokens"] <= e["max_len"] == cfg[
        "max_position_embeddings"]
    # the pools: every sequence's pages in the full one, its window, a
    # chunk and two part pages in the other
    assert e["num_blocks"] >= 32 * e["max_len"] // 16
    assert e["window_blocks"] >= 32 * ((512 + 512) // 16 + 2)
    c = cfg["correctness"]
    assert c["reference_len"] >= 6144 >= max(c["prompt_lens"]) + c[
        "new_tokens"]
    assert min(c["prompt_lens"]) < 512 < min(c["prompt_lens"]) + c[
        "new_tokens"]
    assert any(p < 4096 < p + c["new_tokens"] for p in c["prompt_lens"])


def test_laguna_config_carries_the_plan():
    cfg = fixture("configs", "tiny-laguna")
    lcfg = lc.laguna_config(cfg, jnp.bfloat16)
    assert [(s.attn, s.heads, s.ffn) for s in lcfg.layer_plan] == [
        ("full", 6, "dense"), ("window", 8, "sparse"),
        ("window", 8, "sparse"), ("window", 8, "sparse"),
        ("full", 6, "sparse")]
    full, window = lcfg.layer_plan[0].rope, lcfg.layer_plan[1].rope
    assert (full.partial, full.yarn_factor, full.yarn_original) == (0.5, 8.0,
                                                                    32)
    assert (window.partial, window.yarn_factor, window.theta) == (1.0, 0.0,
                                                                  1e4)
    assert (lcfg.intermediate_size, lcfg.dense_intermediate_size,
            lcfg.shared_expert_width, lcfg.router_score, lcfg.router_scale,
            lcfg.attn_gate, lcfg.sliding_window) == (32, 128, 32, "sigmoid",
                                                     2.5, True, 24)
    # the reference reads the same file on its own
    assert [l[2:] for l in reference_laguna.layers_of(cfg)] == [
        (s.attn, s.heads, s.ffn) for s in lcfg.layer_plan]
    assert [l[:2] for l in reference_laguna.layers_of(cfg)] == [
        (0, 0), (1, 0), (1, 1), (1, 2), (2, 0)]
    with pytest.raises(NotImplementedError, match="input"):
        lc.laguna_config({**cfg, "moe_apply_router_weight_on_input": True},
                         jnp.bfloat16)


def test_a_program_without_a_layer_plan_fails_at_once(monkeypatch):
    """The parent of PR 34 has no `LayerSpec`: the driver raises before any
    weight is made, and run.py exits non-zero."""
    from paddle_tpu.models import llama as L
    monkeypatch.delattr(L, "LayerSpec")
    with pytest.raises(AttributeError, match="LayerSpec"):
        lc.run(context("tiny-laguna", "tiny_longctx_closed", seed=1))


def test_longctx_driver_rehearsal():
    ctx = context("tiny-laguna", "tiny_longctx_closed", seed=2**31 + 5,
                  seconds=1.0)
    rec = lc.run(ctx)
    assert rec.correct, rec.notes
    n = rec.notes
    assert n["positions_judged"] == 36 and n["agreement"] >= 0.98
    assert n["window_pages_released_in_check"] > 0
    assert n["prefix_cache"].startswith("off")
    for launch in ("decode", "mixed"):
        assert n[f"window_walk_{launch}_largest_error_over_tolerance"] < 1.0
    for kind in ("full_dense", "window_sparse", "full_sparse"):
        for rows in (4, 32):
            assert n[f"{kind}_rows_{rows}"]["padding_rows_zero"]
            assert n[f"{kind}_rows_{rows}"][
                "largest_error_over_tolerance"] < 1.0
    assert rec.failed == 0 and rec.attempted > 0
    c = rec.counters
    assert c["compiles_in_window"] == 0
    assert c["moe_pairs"] == 4 * c["engine_tokens_computed"]
    assert 0 < c["attn_keys_window"] < c["attn_keys_full"]
    assert c["attn_pairs_window"] >= c["attn_keys_window"]
    # 4 sparse layers of 5: the accepted reader would divide by 5
    assert sparse_experts_hit_share.read(rec) == pytest.approx(
        moe_experts_hit_share.read(rec) * 5 / 4)
    assert 0 < sparse_experts_hit_share.read(rec) <= 100
    share = window_keys_share.read(rec)
    assert share == pytest.approx(100 * (c["attn_keys_full"]
                                         + c["attn_keys_window"])
                                  / c["attn_keys_causal"])
    assert 40 < share < 100
    assert 0 < window_pool_pages_share.read(rec) < 100
    assert batch_occupancy.read(rec) >= 4
    for reader in (decode_tokens_per_s, setup_s):
        assert math.isfinite(reader.read(rec)) and reader.read(rec) >= 0
    # what the cell computes and does not list is in the notes; untraced,
    # the trace readers find nothing and do not raise
    assert {"gap_p90_ms", "ttft_mean_ms", "tick_p50_ms"} <= set(
        n["not_judged"])
    for reader in NEW[:6]:
        assert reader.read(rec) is None


def test_readers_find_nothing_in_a_program_without_the_counters():
    rec = types.SimpleNamespace(
        counters={"engine_steps": 3, "engine_tokens_computed": 48},
        trace=None, trace_counters=None, notes={},
        context=types.SimpleNamespace(config={}, peaks={}))
    for reader in NEW:
        assert reader.read(rec) is None


def test_the_scopes_reach_scope_of_only_once_registered(monkeypatch):
    name = "jit(step_fn)/layers/while/body/paged_attention/" \
        "paged_attention_window/pallas_call"
    shared = "jit(step_fn)/layers/while/body/moe/shared_expert/dot_general"
    monkeypatch.setattr(program_trace, "SCOPES", frozenset(
        s for s in program_trace.SCOPES
        if s not in (laguna_scopes.FULL, laguna_scopes.WINDOW,
                     laguna_scopes.GATE, laguna_scopes.SHARED, "moe")))
    assert program_trace.scope_of(name) == "paged_attention"
    assert program_trace.scope_of(shared) == "layers"
    laguna_scopes.register()
    assert program_trace.scope_of(name) == laguna_scopes.WINDOW
    assert program_trace.scope_of(shared) == laguna_scopes.SHARED
    assert program_trace.scope_of(
        "jit(step_fn)/layers/while/body/attn_gate/mul") == laguna_scopes.GATE


def test_trace_readers_on_a_recorded_tick(tmp_path):
    """The nine readers by hand, on a five-operation trace in
    program_trace's own layout and the engine's counters of one decode
    tick: 32 rows at 6,000 positions."""
    ms = 1_000_000
    trace = {
        "device": {"/device:TPU:0": [
            ["paged_attention_decode.1", 0, 4 * ms],
            ["paged_attention_decode.2", 4 * ms, 1 * ms],
            ["gmm.3", 5 * ms, 4 * ms], ["fusion.4", 9 * ms, 500_000],
            ["fusion.5", 9_500_000, 500_000]]},
        "device_scopes": {"/device:TPU:0": [
            laguna_scopes.FULL, laguna_scopes.WINDOW, "experts",
            laguna_scopes.SHARED, laguna_scopes.GATE]},
        "host": [["bench.tick", 0, 10 * ms]],
        "program_spans": [["ptpu.serve.step", 0, 10 * ms, {"batch": 32}]],
    }
    path = tmp_path / "tick.json"
    path.write_text(json.dumps(trace))
    cfg = cell_config()
    rows, ctx = 32, 6000
    counters = {"attn_keys_full": 2 * rows * ctx,
                "attn_pairs_full": 2 * rows * ctx,
                "attn_keys_window": 3 * rows * 512,
                "attn_pairs_window": 3 * rows * 512,
                "attn_keys_causal": 5 * rows * ctx,
                "moe_experts_hit": 4 * 160, "moe_pairs": rows * 8,
                "full_pages_live": rows * 376, "window_pages_live": rows * 33,
                "engine_steps": 1, "engine_tokens_computed": rows}
    rec = types.SimpleNamespace(
        trace={"busy_s": 0.010}, notes={"trace_file": str(path)},
        trace_counters=counters, counters=counters,
        context=types.SimpleNamespace(config=cfg,
                                      peaks=PEAKS["TPU v5 lite"]))
    assert tick_full_attention_share.read(rec) == pytest.approx(40.0)
    assert tick_window_attention_share.read(rec) == pytest.approx(10.0)
    assert tick_shared_expert_share.read(rec) == pytest.approx(5.0)
    # a key is 4,096 bytes a layer; a decode row is memory-bound
    assert window_math.key_bytes(cfg) == 4096
    least = 2 * rows * ctx * 4096 / 819e9                 # 1.92 ms
    assert full_attention_roofline.read(rec) == pytest.approx(
        100 * least / 0.004)
    least = 3 * rows * 512 * 4096 / 819e9
    assert window_attention_roofline.read(rec) == pytest.approx(
        100 * least / 0.001)
    assert (window_math.heads_of(cfg, "full"),
            window_math.heads_of(cfg, "window")) == (48, 64)
    # a chunk's pairs are compute: 4 x 128 x 64 FLOPs a pair
    sec, bound = window_math.attention_least_seconds(
        cfg, "window", 992, 481 * 512, PEAKS["TPU v5 lite"])
    assert bound == "compute" and sec == pytest.approx(
        4 * 128 * 64 * 481 * 512 / 197e12)
    least = 640 * 3 * 2048 * 512 * 2 / 819e9
    assert sparse_experts_roofline.read(rec) == pytest.approx(
        100 * least / 0.004)
    assert sparse_experts_hit_share.read(rec) == pytest.approx(
        100 * 640 / (4 * 256))
    assert window_keys_share.read(rec) == pytest.approx(
        100 * (2 * ctx + 3 * 512) / (5 * ctx))
    assert window_pool_pages_share.read(rec) == pytest.approx(100 * 33 / 376)


# ---- the tolerances, and the faults nearest to them -------------------------

@pytest.fixture(scope="module")
def tiny_model():
    from paddle_tpu.models import llama as L
    cfg = fixture("configs", "tiny-laguna")
    lcfg = lc.laguna_config(cfg, jnp.float32)
    return cfg, lcfg, L.init_params(lcfg, jax.random.PRNGKey(0))


@pytest.mark.parametrize("kind", [0, 1, 2])
def test_one_layer_check_fails_what_it_must(tiny_model, kind):
    """The one-layer check of each kind (float32 on the CPU): the sound
    program reads far inside `agreement_moe`'s tolerance; weights rounded
    to 8 bits, a dropped shared expert and a softmax router read over
    it."""
    cfg, lcfg, params = tiny_model
    h = jax.random.normal(jax.random.PRNGKey(kind), (32, cfg["hidden_size"]),
                          jnp.float32)
    valid = jnp.arange(32) < 29
    def one(params, **fault):
        outs, refs = lc.ffn_outputs(lcfg, params, h, valid, kinds=(kind,),
                                    **fault)
        return outs[kind], refs[kind]

    out, ref = one(params)
    assert agreement_moe.judge(out[:29], ref)[1] < 0.05
    assert not np.any(out[29:])

    def to_8_bits(w):
        scale = jnp.max(jnp.abs(w)) / 127.0
        return jnp.round(w / scale) * scale

    names = ("w1", "w3", "w2", "ws1", "ws3", "ws2")
    rounded = {**params, "blocks": tuple(
        {n: to_8_bits(w) if n in names else w for n, w in b.items()}
        for b in params["blocks"])}
    out8, _ = one(rounded)
    assert agreement_moe.judge(out8[:29], ref)[1] > 1.0
    if lcfg.kinds[kind].ffn == "sparse":
        for fault in (dict(shared=False), dict(score="softmax")):
            _, bad = one(params, **fault)
            assert agreement_moe.judge(out[:29], bad)[1] > 2.5


@pytest.mark.parametrize("decode", [True, False])
def test_window_walk_check_fails_a_window_a_page_off(decode):
    """The window walk's check at the tiny fixture's shapes (float32,
    interpret mode): the sound launch reads far inside the tolerance; a
    window one page wider or narrower, or none, reads over it."""
    cfg = fixture("configs", "tiny-laguna")
    case = lc.attention_case(cfg, 7, jnp.float32, decode)
    W, bs = cfg["sliding_window"], cfg["engine"]["block_size"]
    out, ref = lc.attention_outputs(cfg, case, W, decode)
    assert agreement_blockdiff.judge_attention(out, ref)[1] < 0.01
    assert (np.asarray(case[3]) < 0).any()          # pages behind are gone
    for off in (W - bs, W + bs, 0):
        # a wider view reads table entries that a window pool gave back:
        # hand it whole tables, the fault is the mask's alone
        tables = jnp.where(case[3] < 0, 0, case[3])
        bad, _ = lc.attention_outputs(
            cfg, case[:3] + (tables,) + case[4:], off, decode)
        good, worst = agreement_blockdiff.judge_attention(bad, ref)
        assert not good and worst > 2.0
