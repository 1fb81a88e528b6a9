"""What PR 47 adds to the benchmark, on tiny fixtures on the CPU (counts and
comparisons only, no chip number): the training cell of a model with a
layer plan and a held share of its experts, its configuration against the
catalog, its arithmetic by hand, its eleven readers on a recorded trace,
and its driver rehearsed end to end."""
import json
import math
import os
import types

import pytest

from benchmark.drivers import train_steps_plan as D
from benchmark.end_to_end import setup_s, train_tokens_per_s_per_chip
from benchmark.layer_metrics import (
    train_experts_roofline, train_full_attention_share,
    train_held_pairs_share, train_moe_load_max_over_mean, train_moe_mfu,
    train_moe_overhead_share, train_moe_share, train_moe_step_roofline,
    train_moe_whole_form_share, train_step_p50_ms,
    train_window_attention_roofline, train_window_attention_share)
from benchmark.lib import program_trace, train_plan_math as M
from benchmark.lib import train_plan_scopes
from benchmark.lib.peaks import PEAKS
from benchmark.tests.helpers import ROOT_DIR, context

CELL = "train_moe_window_8k"
CONFIG = "mellum2-12b-a2.5b-train"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("train_moe_mfu", "train_moe_step_roofline",
       "train_window_attention_share", "train_full_attention_share",
       "train_moe_share", "train_moe_overhead_share",
       "train_window_attention_roofline", "train_experts_roofline",
       "train_held_pairs_share", "train_moe_load_max_over_mean",
       "train_moe_whole_form_share")


def bench():
    with open(f"{ROOT_DIR}/BENCHMARK.json") as f:
        return json.load(f)


def cell_config():
    entry = next(c for c in bench()["configs"] if c["name"] == CONFIG)
    with open(os.path.join(ROOT_DIR, entry["file"])) as f:
        return entry, json.load(f)


# ---- the configuration against the catalog ----------------------------------

def test_the_configuration_keeps_the_catalogs_numbers():
    """Every key of the catalog row's `config` (looked up by the model's
    name) is in the file under the same key, and equal unless `reduced`
    names it; the cut is written out beside the published values."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    entry, cfg = cell_config()
    assert entry["source"] == row["source_url"]
    reduced = set(entry["reduced"])
    assert reduced == {"num_hidden_layers", "layer_types", "mlp_layer_types",
                       "num_experts", "vocab_size",
                       "max_position_embeddings"} == set(cfg["reduced"])
    for key, published in row["config"].items():
        if key in reduced:
            assert cfg["published"][key] == published, key
        else:
            assert cfg[key] == published, key
    assert cfg["num_hidden_layers"] == 4 and cfg["vocab_size"] == 24576
    assert cfg["layer_types"] == row["config"]["layer_types"][:4]
    assert cfg["mlp_layer_types"] == ["sparse"] * 4
    assert cfg["num_experts"] == 16 and cfg["experts_held"] == [0, 16]
    assert cfg["router_width"] == row["config"]["num_experts"] == 64
    assert "9.52 GB" in cfg["deployment"] and "four chips" in cfg["deployment"]
    # the floors of a model_config PR: a whole period, 8 experts, an eighth
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= row["config"]["vocab_size"]


def test_the_cell_and_its_metrics_are_declared():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pretrain_8k_moe", 1)
    with open(f"{ROOT_DIR}/benchmark/traffic/pretrain_8k_moe.json") as f:
        tr = json.load(f)
    assert (tr["kind"], tr["seq_len"], tr["global_batch"]) == (
        "train_steps_plan", 8192, 2)
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_tokens_per_s_per_chip"
    listed = {m["name"] for m in b["per_layer"] + b["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert listed == set(NEW) | {
        "train_tokens_per_s_per_chip", "train_step_p50_ms",
        "train_device_idle_share", "train_head_loss_share",
        "train_optimizer_share", "train_unscoped_share"}
    # what would read wrongly here stays unlisted
    for name in ("train_mfu", "train_step_roofline", "train_attention_share",
                 "train_ffn_share", "train_collective_share"):
        assert CELL not in by_name[name]["workloads"]


def test_the_program_config_is_the_files():
    import jax.numpy as jnp

    _, cfg = cell_config()
    lcfg = D.mellum_config(cfg, jnp.float32)
    assert [s.attn for s in lcfg.layer_plan] == ["window"] * 3 + ["full"]
    assert lcfg.num_experts == 64 and lcfg.experts_held == (0, 16)
    assert lcfg.top_k == 8 and lcfg.sliding_window == 1024
    window, full = lcfg.kinds
    assert window.rope.yarn_factor == 0 and window.rope.theta == 500000
    assert full.rope.yarn_factor == 16 and full.rope.yarn_original == 8192
    assert full.rope.attention_factor == pytest.approx(0.1 * math.log(16) + 1)
    assert lcfg.head_dim == 128 and lcfg.vocab_size == 24576


# ---- the arithmetic, by hand -------------------------------------------------

def test_train_plan_math_by_hand():
    _, cfg = cell_config()
    # visible keys of one sequence of 8192: a window of 1024 and causal
    assert M.visible_keys(8192, 1024) == 1024 * 1025 // 2 + 7168 * 1024 \
        == 7_864_832
    assert M.visible_keys(8192) == 8192 * 8193 // 2 == 33_558_528
    assert M.visible_keys(8, 3) == 1 + 2 + 3 * 6
    assert M.visible_keys(8, 8) == M.visible_keys(8, 100) == 36
    assert M.layer_windows(cfg) == [1024, 1024, 1024, 0]
    # this chip's 595.2 M parameters, as the ISSUE reckons them
    assert M.attention_params(cfg) == 2304 * 4096 * 2 + 2 * 2304 * 512 \
        == 21_233_664
    assert M.router_params(cfg) == 2304 * 64
    assert M.expert_params(cfg) == 3 * 2304 * 896 == 6_193_152
    layer = 21_233_664 + 147_456 + 2 * 2304 + 16 * 6_193_152
    assert M.n_params(cfg) == 4 * layer + 2 * 24576 * 2304 + 2304 \
        == 595_153_152
    assert M.n_params(cfg) * 16 == pytest.approx(9.52e9, rel=1e-3)
    # a step of 2 x 8192 tokens with an even router's 131,072 held pairs
    held = 2 * 8192 * 8 * 16 // 64 * 4
    attention = 12 * 32 * 128 * 2 * (3 * 7_864_832 + 33_558_528)
    assert M.attention_flops(cfg, 8192, 2, M.layer_windows(cfg)) == attention
    assert M.experts_flops(cfg, held) == 6 * 6_193_152 * 131_072
    dense = 6 * 16384 * (4 * (21_233_664 + 147_456) + 2304 * 24576)
    assert M.step_flops(cfg, 8192, 2, held) == pytest.approx(
        dense + attention + 6 * 6_193_152 * 131_072)
    assert M.step_flops(cfg, 8192, 2, held) == pytest.approx(24.46e12,
                                                             rel=1e-3)
    assert M.step_bytes(cfg) == 24 * 595_153_152


# ---- the readers on a recorded step ------------------------------------------

def recorded(tmp_path):
    """One traced step in program_trace's own layout: device self times by
    scope in ms, with the step's counters."""
    train_plan_scopes.register()
    ms = 1_000_000
    ops = [("attention", 60), ("attention_window", 90), ("attention_full", 80),
           ("ffn", 10), ("router", 20), ("dispatch", 70), ("experts", 150),
           ("combine", 110), ("moe", 5), ("head_loss", 60), ("adamw", 35),
           ("layers", 5), ("", 5)]
    events, scopes, t = [], [], 0
    for n, (scope, dur) in enumerate(ops):
        events.append([f"fusion.{n}", t * ms, dur * ms])
        scopes.append(scope)
        t += dur
    assert t == 700
    trace = {"device": {"/device:TPU:0": events},
             "device_scopes": {"/device:TPU:0": scopes},
             "host": [["bench.train_step", 0, 700 * ms]],
             "program_spans": []}
    path = tmp_path / "step.json"
    path.write_text(json.dumps(trace))
    _, cfg = cell_config()
    counters = {"steps": 1, "tokens": 16384, "sequences": 2, "seq_len": 8192,
                "chips": 1, "elapsed_s": 0.7, "moe_launches": 4,
                "moe_pairs": 524288, "moe_pairs_held": 131072,
                "moe_load_max": 4 * 2150, "moe_whole_form": 4}
    return types.SimpleNamespace(
        trace={"busy_s": 0.7, "window_s": 0.7},
        notes={"trace_file": str(path)}, counters=counters,
        trace_counters=counters, samples={"step_ms": [700.0]},
        context=types.SimpleNamespace(config=cfg,
                                      peaks=PEAKS["TPU v5 lite"]))


def test_the_new_readers_on_a_recorded_step(tmp_path):
    rec = recorded(tmp_path)
    cfg, peak = rec.context.config, 197e12
    flops = M.step_flops(cfg, 8192, 2, 131072)
    assert train_moe_mfu.read(rec) == pytest.approx(100 * flops / 0.7 / peak)
    assert train_moe_step_roofline.read(rec) == pytest.approx(
        100 * (flops / peak) / 0.7)
    assert train_window_attention_share.read(rec) == pytest.approx(90 / 7)
    assert train_full_attention_share.read(rec) == pytest.approx(80 / 7)
    assert train_moe_share.read(rec) == pytest.approx(355 / 7)
    assert train_moe_overhead_share.read(rec) == pytest.approx(200 / 7)
    window_flops = 12 * 32 * 128 * 2 * 3 * 7_864_832
    assert train_window_attention_roofline.read(rec) == pytest.approx(
        100 * (window_flops / peak) / 0.090)
    assert train_experts_roofline.read(rec) == pytest.approx(
        100 * (6 * 6_193_152 * 131072 / peak) / 0.150)
    assert train_held_pairs_share.read(rec) == pytest.approx(25.0)
    assert train_moe_load_max_over_mean.read(rec) == pytest.approx(
        2150 / 2048)
    assert train_moe_whole_form_share.read(rec) == pytest.approx(100.0)
    for reader in (train_moe_mfu, train_moe_step_roofline,
                   train_window_attention_roofline, train_experts_roofline):
        assert 0 < reader.read(rec) < 100


def test_the_new_readers_find_nothing_on_a_program_without_the_counters(
        tmp_path):
    """A dense train cell's record (and the parent's): no routed-expert
    counter and none of the new scopes, so every new reader leaves its
    metric out and none raises."""
    import importlib

    rec = recorded(tmp_path)
    dense = {"steps": 1, "tokens": 16384, "seq_len": 8192, "chips": 1,
             "elapsed_s": 0.7}
    rec.counters = rec.trace_counters = dense
    trace = json.loads(open(rec.notes["trace_file"]).read())
    trace["device_scopes"]["/device:TPU:0"] = [
        s if s in ("attention", "ffn", "head_loss", "adamw", "layers") else
        "ffn" for s in trace["device_scopes"]["/device:TPU:0"]]
    open(rec.notes["trace_file"], "w").write(json.dumps(trace))
    program_trace.load.cache_clear()
    for name in NEW:
        reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
        assert reader.read(rec) is None, name
    rec.trace = None
    for name in NEW:
        reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
        assert reader.read(rec) is None, name


def test_the_scopes_are_the_innermost_of_their_operations():
    train_plan_scopes.register()
    step = "jit(per_shard_step)/transpose(jvp(pipeline))/while/body/"
    assert program_trace.scope_of(
        step + "layers/while/body/checkpoint/attention/attention_window/"
        "flash_attention_dq/pallas_call") == "attention_window"
    assert program_trace.scope_of(
        step + "layers/while/body/checkpoint/attention/dot_general"
    ) == "attention"
    assert program_trace.scope_of(
        step + "layers/while/body/checkpoint/ffn/moe/experts/"
        "transpose(jvp(jit(tgmm)))/pallas_call") == "experts"
    assert program_trace.scope_of(
        step + "layers/while/body/checkpoint/rematted_computation/ffn/moe/"
        "combine/take") == "combine"


# ---- the driver, rehearsed ---------------------------------------------------

def test_the_driver_rehearsal(monkeypatch):
    """`train_steps_plan.run` at the tiny fixture through the real train
    step: both comparisons run, with the reference on the program's own
    choice of experts every bf16 gradient leaf is within a few per cent of
    the float32 reference's, the first step returns the judged loss and
    moves every leaf (an unchanged state would read 1), and the step's
    counters are in the books. (The limits and the first-loss band are the
    timed size's: 64 tokens a step say nothing about them.)"""
    monkeypatch.setattr(D, "memory_peak_bytes", lambda: 0)
    ctx = context("tiny-mellum2", "tiny_pretrain_moe", seed=2**31 + 11,
                  seconds=0.5)
    rec = D.run(ctx)
    a = rec.notes["agreement"]
    assert rec.notes["first_step_returns_the_judged_loss"]
    assert a["leaves_compared"] == 43
    assert a["worst"]["loss"] < 2e-3
    assert max(v for g, v in a["worst"].items() if g != "loss") < 0.05
    assert a["moe_stats"]["moe_launches"] == 4
    assert set(a["update"]["moment_worst"]) == set(a["worst"]) - {"loss"}
    assert max(a["update"]["moment_worst"].values()) < 0.05
    assert 0 < a["update"]["change_worst"] < 0.9
    c = rec.counters
    assert c["steps"] == rec.attempted == len(rec.samples["step_ms"]) > 0
    assert c["tokens"] == c["steps"] * 64 and c["sequences"] == c["steps"] * 2
    assert c["moe_launches"] == 4 * c["steps"]
    assert c["moe_pairs"] == c["moe_launches"] * 64 * 4
    assert 0 < c["moe_pairs_held"] < c["moe_pairs"]
    assert c["compiles_in_window"] == 0 and rec.failed == 0
    assert train_tokens_per_s_per_chip.read(rec) == (
        c["tokens"] / c["elapsed_s"])
    assert setup_s.read(rec) > 0 and train_step_p50_ms.read(rec) > 0
    assert 0 < train_held_pairs_share.read(rec) < 100
    assert train_moe_load_max_over_mean.read(rec) >= 1.0
    assert train_moe_mfu.read(rec) > 0
    assert train_moe_step_roofline.read(rec) is None      # no trace
