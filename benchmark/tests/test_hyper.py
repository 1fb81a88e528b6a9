"""What PR 54 adds to the benchmark, on tiny fixtures on the CPU (counts
and comparisons only, no chip number): the closed loop of a model whose
residual stream is four lanes mixed by hyper-connections around latent
attention and experts held whole, its two readers, its scopes, the
limits of its comparison."""
import dataclasses
import json
import math
import types

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.drivers import closed_loop_serve_hyper as D
from benchmark.end_to_end import decode_tokens_per_s, setup_s
from benchmark.layer_metrics import (batch_occupancy,
                                     latent_attention_roofline,
                                     tick_hyper_coeff_share,
                                     tick_hyper_share,
                                     tick_latent_attention_share)
from benchmark.lib import (agreement_hyper, hyper_scopes,
                           program_trace, reference_xing4, serve_window,
                           traffic as T)
from benchmark.lib.peaks import PEAKS
from benchmark.tests.helpers import ROOT_DIR, context, fixture

NEW = (tick_hyper_share, tick_hyper_coeff_share)
LISTED = ("batch_occupancy", "tick_latent_attention_share",
          "latent_attention_roofline", "window_prefill_tick_share",
          "plain_tick_p50_ms", "prefill_tick_p50_ms",
          "window_host_gap_share", "window_ticks_ahead_share")
CELL = "serve_hyper_latent_mixed_4k"


@pytest.fixture(autouse=True)
def no_memory_stats(monkeypatch):
    monkeypatch.setattr(serve_window, "memory_peak_bytes", lambda: 0)


def cell_config():
    with open(f"{ROOT_DIR}/benchmark/configs/xing4.0-29b-a4b-serve.json") as f:
        return json.load(f)


def test_the_cells_configuration_keeps_the_catalogs_numbers():
    """Every key of the catalog's `config` is in the file with the
    catalog's value, but the four `reduced` names; the entries are the
    issue's."""
    cfg = cell_config()
    published = {
        "attention_bias": False, "ep_size": 1, "hidden_act": "silu",
        "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512,
        "model_type": "xing4_0", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 32, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30, "q_lora_rank": 768,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert cfg["published"] == {
        "num_hidden_layers": 40, "first_k_dense_replace": 2,
        "max_position_embeddings": 262144, "num_nextn_predict_layers": 1}
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["max_position_embeddings"],
            cfg["num_nextn_predict_layers"]) == (6, 1, 6400, 0)
    assert set(cfg["reduced"]) == set(cfg["published"])
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    for key in ("source", "assumed", "deployment", "engine_why"):
        assert cfg[key]
    assert cfg["correctness"]["why"] and "ep_size 1" in cfg["deployment"]
    with open(f"{ROOT_DIR}/BENCHMARK.json") as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "xing4.0-29b-a4b-serve")
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/"
        "config.json")
    assert entry["reduced"] == list(cfg["reduced"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "xing4.0-29b-a4b-serve", "closed_hyper64_4k256", 1)
    metrics = {m["name"]: m for kind in ("end_to_end", "per_layer")
               for m in bench[kind]}
    for name in ("decode_tokens_per_s", *LISTED):
        assert CELL in metrics[name]["workloads"]
    for reader in NEW:
        m = metrics[reader.__name__.rsplit(".", 1)[1]]
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert (m["moves"], m["layer"], m["source"]) == (
            "decode_tokens_per_s", "kernels", "device_trace")
    e, tr = cfg["engine"], json.load(open(
        f"{ROOT_DIR}/benchmark/traffic/closed_hyper64_4k256.json"))
    grid = T.prompt_grid(tr)
    assert (len(grid), grid[0], grid[-1]) == (64, 2048, 6080)
    assert sum(grid) / len(grid) == 4064
    assert tr["clients"] == e["max_batch"] == 64
    assert (tr["max_new_tokens"], tr["trace_ticks"], tr["order_seed"]) == (
        256, 48, 0)
    assert grid[-1] + tr["max_new_tokens"] <= e["max_len"] == cfg[
        "max_position_embeddings"]
    assert e["num_blocks"] == 64 * e["max_len"] // e["block_size"] + 2048
    c = cfg["correctness"]
    assert c["reference_len"] >= max(c["prompt_lens"]) + c["new_tokens"]
    assert any(p < 4096 < p + c["new_tokens"] for p in c["prompt_lens"])


def test_the_arithmetic_at_the_published_widths():
    cfg = cell_config()
    # the reference reads the same file on its own
    kw = reference_xing4.model_kw(cfg)
    assert kw["hyper"] == (4, 20, 1e-6, -30, 30)
    assert kw["scale"] == pytest.approx(192 ** -0.5 * 1.4158883 ** 2)
    assert (kw["heads"], kw["top_k"], kw["router_scale"],
            kw["dense_layers"]) == (32, 4, 2.0, 1)
    lcfg = D.xing_config(cfg, jnp.bfloat16)
    assert lcfg.score_scale == pytest.approx(kw["scale"])
    assert (lcfg.hyper_lanes, lcfg.experts_held, lcfg.num_experts,
            lcfg.num_layers) == (4, (), 64, 6)


def test_a_program_without_hyper_connections_fails_at_once(monkeypatch):
    """The parent of PR 54 has no `LlamaConfig.hyper_lanes`: the driver
    raises in `xing_config`, before any weight is made."""
    from paddle_tpu.models import llama as L

    fields = [(f.name, f.type, f) for f in dataclasses.fields(L.LlamaConfig)
              if not f.name.startswith("hyper_")]
    Old = dataclasses.make_dataclass("LlamaConfig", fields, frozen=True)
    monkeypatch.setattr(L, "LlamaConfig", Old)
    ctx = context("tiny-xing", "tiny_hyper_closed", seed=1)
    with pytest.raises(NotImplementedError, match="hyper-connections"):
        D.run(ctx)


def test_hyper_driver_rehearsal():
    ctx = context("tiny-xing", "tiny_hyper_closed", seed=2**31 + 5,
                  seconds=1.0)
    rec = D.run(ctx)
    assert rec.correct, rec.notes
    n = rec.notes
    assert n["positions_judged"] == 36 and n["agreement"] >= 0.98
    for launch in ("decode", "mixed"):
        assert n[f"latent_{launch}_largest_error_over_tolerance"] < 1.0
        assert n[f"latent_{launch}_pages_hold_the_rows"]
    for rows in (4, 32):
        for which in ("attn", "mlp"):
            part = n[f"mix_{which}_rows_{rows}"]
            assert all(0 <= v < 1.0 for v in part.values()), part
        part = n[f"sparse_rows_{rows}"]
        assert part["padding_rows_zero"]
        assert part["rows_with_no_held_expert"] == 0
        assert part["largest_error_over_tolerance"] < 1.0
    assert rec.failed == 0 and rec.attempted > 0
    c = rec.counters
    assert c["compiles_in_window"] == 0
    assert c["moe_pairs"] == 2 * c["engine_tokens_computed"]
    assert c["hyper_rows"] == 2 * 3 * c["engine_tokens_computed"] > 0
    assert c["attn_pairs_latent"] >= c["attn_keys_latent"] > 0
    assert batch_occupancy.read(rec) >= 4
    for reader in (decode_tokens_per_s, setup_s):
        assert math.isfinite(reader.read(rec)) and reader.read(rec) >= 0
    assert {"gap_p90_ms", "ttft_mean_ms", "tick_p50_ms"} <= set(
        n["not_judged"])
    # the judged rate is the raw window's; the books without the paused
    # ticks are a note
    assert (c["tokens_out"], c["elapsed_s"]) == (c["tokens_out_raw"],
                                                 c["elapsed_raw_s"])
    assert decode_tokens_per_s.read(rec) == pytest.approx(
        c["tokens_out_raw"] / c["elapsed_raw_s"])
    left = n["pauses_left_out"]
    assert left["tokens_out"] <= c["tokens_out"]
    assert left["elapsed_s"] <= c["elapsed_s"] + 1e-9
    for reader in NEW:          # untraced: nothing to read, no raise
        assert reader.read(rec) is None


@pytest.mark.parametrize("fault", ["bf16_coefficients", "alpha_zero",
                                   "one_iteration_less", "rows_first"])
def test_the_mixing_check_catches_a_mix_made_otherwise(fault):
    """Part 3 of `correct` against a reference with one piece of the mix
    made otherwise: sound, every reading is under its limit; coefficients
    in bfloat16, without the dynamic term, with rows before columns or
    (on a mix whose 20 rounds do not reach the fixed point) with a round
    less fail the coefficients' limit."""
    from paddle_tpu.models import llama as L

    cfg = fixture("configs", "tiny-xing")
    lcfg = dataclasses.replace(D.xing_config(cfg, jnp.float32),
                               dtype=jnp.float32)
    params = L.init_params(lcfg, jax.random.PRNGKey(4))
    if fault == "one_iteration_less":
        params = {**params, "blocks": tuple(
            {**b, **{f"hc_{w}_b": b[f"hc_{w}_b"].at[:, 8:].multiply(3.0)
                     for w in ("attn", "mlp")}} for b in params["blocks"])}
    ok, notes = D.check_mixing(cfg, params, lcfg, 4)
    assert ok, notes
    ok, notes = D.check_mixing(cfg, params, lcfg, 4, fault=fault)
    assert not ok
    assert max(v["coefficients_largest_error_over_tolerance"]
               for v in notes.values()) > 1.0


def test_the_limits_of_the_rows_and_the_coefficients():
    ref = np.ones((3, 24), np.float32) * 0.5
    assert agreement_hyper.judge_coefficients(ref + 2.5e-5, ref) == (
        True, pytest.approx(0.5, rel=5e-3))
    assert not agreement_hyper.judge_coefficients(ref + 2.0 ** -9, ref)[0]
    rows = np.random.default_rng(0).normal(size=(5, 256)).astype(np.float32)
    rounded = np.asarray(jnp.asarray(rows).astype(jnp.bfloat16)
                         .astype(jnp.float32))
    good, worst = agreement_hyper.judge_rows(rounded, rows)
    assert good and 0.15 < worst < 0.5
    swapped = np.concatenate([rows[:, 64:128], rows[:, :64], rows[:, 128:]],
                             axis=-1)
    assert not agreement_hyper.judge_rows(swapped, rows)[0]


def test_readers_find_nothing_in_a_program_without_the_names(tmp_path):
    """A program that writes neither the scopes nor the step field (the
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
                                     ["gmm.3", ms, ms]]},
        "device_scopes": {"/device:TPU:0": ["attn_out", "experts"]},
        "host": [["bench.tick", 0, 2 * ms]],
        "program_spans": [["ptpu.serve.step", 0, 2 * ms, {"batch": 64}]]}))
    rec.trace, rec.notes = {"busy_s": 0.002}, {"trace_file": str(path)}
    for reader in NEW:
        assert reader.read(rec) is None


def test_the_scopes_reach_scope_of_only_once_registered(monkeypatch):
    post = "jit(step_fn)/layers/while/body/attn_out/latent_out/hyper/" \
        "hyper_post/concatenate"
    coeff = "jit(step_fn)/layers/while/body/hyper/hyper_coeff/div"
    monkeypatch.setattr(program_trace, "SCOPES", frozenset(
        s for s in program_trace.SCOPES if s not in hyper_scopes.HYPER))
    assert program_trace.scope_of(post) == "latent_out"
    assert program_trace.scope_of(coeff) == "layers"
    hyper_scopes.register()
    assert program_trace.scope_of(post) == hyper_scopes.POST
    assert program_trace.scope_of(coeff) == hyper_scopes.COEFF
    assert program_trace.scope_of(
        "jit(step_fn)/layers/while/body/hyper/hyper_pre/mul"
    ) == hyper_scopes.PRE
    assert program_trace.scope_of(
        "jit(step_fn)/layers/while/body/moe/hyper/hyper_post/mul"
    ) == hyper_scopes.POST


def test_trace_readers_on_a_recorded_tick(tmp_path):
    """The two readers by hand, on a trace in program_trace's own layout
    of two ticks (a decode tick of 64 rows; a chunk tick of 1,984 + 63
    rows), with the engine's field on their step spans."""
    ms = 1_000_000
    decode = {"batch": 64, "hyper_rows": 12 * 64}
    chunk = {"batch": 64, "hyper_rows": 12 * 2047}
    trace = {
        "device": {"/device:TPU:0": [
            ["fusion.1", 0, 1 * ms], ["fusion.2", 1 * ms, 1 * ms],
            ["gmm.3", 2 * ms, 8 * ms], ["fusion.4", 10 * ms, 2 * ms],
            ["paged_attention_latent_mixed.5", 12 * ms, 20 * ms],
            ["fusion.6", 32 * ms, 3 * ms], ["fusion.7", 35 * ms, 1 * ms],
            ["fusion.8", 36 * ms, 4 * ms], ["gmm.9", 40 * ms, 10 * ms]]},
        "device_scopes": {"/device:TPU:0": [
            hyper_scopes.COEFF, hyper_scopes.POST, "experts",
            hyper_scopes.COEFF, "paged_attention_latent", hyper_scopes.POST,
            hyper_scopes.PRE, hyper_scopes.ALL, "experts"]},
        "host": [["bench.tick", 0, 10 * ms], ["bench.tick", 10 * ms, 40 * ms]],
        "program_spans": [["ptpu.serve.step", 0, 10 * ms, decode],
                          ["ptpu.serve.step", 10 * ms, 40 * ms, chunk]],
    }
    path = tmp_path / "ticks.json"
    path.write_text(json.dumps(trace))
    cfg, peaks = cell_config(), PEAKS["TPU v5 lite"]
    rec = types.SimpleNamespace(
        trace={"busy_s": 0.050}, notes={"trace_file": str(path)},
        trace_counters={}, counters={},
        context=types.SimpleNamespace(config=cfg, peaks=peaks))
    assert tick_hyper_share.read(rec) == pytest.approx(
        100 * (1 + 1 + 2 + 3 + 1 + 4) / 50)
    assert tick_hyper_coeff_share.read(rec) == pytest.approx(100 * 3 / 50)
    assert tick_latent_attention_share.read(rec) == pytest.approx(40.0)
    assert latent_attention_roofline.read(rec) is None   # no key counts
