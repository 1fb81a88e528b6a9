"""What PR 32 adds to the benchmark, on tiny fixtures on the CPU (counts
and comparisons only, no chip number): the block-diffusion closed loop,
its four readers, its scope, and the faults nearest to each tolerance of
its `correct`."""
import dataclasses
import json
import math
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.drivers import closed_loop_serve_blockdiff as bd
from benchmark.end_to_end import decode_tokens_per_s, gap_p90_ms
from benchmark.layer_metrics import (batch_occupancy,
                                     blockdiff_experts_roofline,
                                     diff_commit_forward_share,
                                     diff_tokens_per_forward,
                                     moe_experts_hit_share,
                                     moe_experts_roofline, tick_p50_ms,
                                     tick_unmask_share)
from benchmark.lib import (agreement, agreement_blockdiff, blockdiff_scopes,
                           program_trace, reference_sdar, serve_window)
from benchmark.tests.helpers import context, fixture


@pytest.fixture(autouse=True)
def no_memory_stats(monkeypatch):
    # the CPU backend reports no memory statistics
    monkeypatch.setattr(serve_window, "memory_peak_bytes", lambda: 0)


def test_sdar_config_carries_the_keys_program_llama_config_refuses():
    cfg = fixture("configs", "tiny-sdar")
    lcfg = bd.sdar_config(cfg, jnp.bfloat16)
    assert lcfg.head_dim * lcfg.num_heads != lcfg.hidden_size
    assert (lcfg.intermediate_size, lcfg.num_experts, lcfg.top_k,
            lcfg.qk_norm_per_head, lcfg.norm_topk_prob, lcfg.block_length,
            lcfg.mask_token_id) == (32, 8, 2, True, True, 4, 511)
    with pytest.raises(NotImplementedError, match="low_confidence_static"):
        bd.sdar_config({**cfg, "remasking": "low_confidence_dynamic"},
                       jnp.bfloat16)


def test_the_cells_configuration_keeps_the_catalogs_numbers():
    """Every number of the catalog's `config` under the same key, but the
    two keys `reduced` names; the expert width is `moe_intermediate_size`."""
    from benchmark.tests.helpers import ROOT_DIR
    with open(f"{ROOT_DIR}/benchmark/configs/sdar30b-a3b-serve.json") as f:
        cfg = json.load(f)
    published = {
        "head_dim": 128, "hidden_size": 2048, "intermediate_size": 6144,
        "max_window_layers": 48, "moe_intermediate_size": 768,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_theta": 1000000, "vocab_size": 151936,
        "decoder_sparse_step": 1}
    assert {k: cfg[k] for k in published} == published
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "max_position_embeddings": 32768}
    assert set(cfg["reduced"]) == set(cfg["published"])
    e = cfg["engine"]
    assert e["block_size"] % cfg["block_length"] == 0
    longest = 229 + 256
    assert -(-longest // 4) * 4 <= e["max_len"] == cfg[
        "max_position_embeddings"]


def test_a_program_without_head_dim_as_a_field_fails_at_once(monkeypatch):
    """The parent of PR 32 derives `head_dim`: the driver raises before any
    weight is made, and run.py exits non-zero."""
    from paddle_tpu.models import llama as L

    new = ("head_dim", "qk_norm_per_head", "block_length", "mask_token_id")
    fields = [(f.name, f.type, f) for f in dataclasses.fields(L.LlamaConfig)
              if f.name not in new]
    Old = dataclasses.make_dataclass("LlamaConfig", fields, frozen=True)
    monkeypatch.setattr(L, "LlamaConfig", Old)
    with pytest.raises(TypeError, match="head_dim"):
        bd.run(context("tiny-sdar", "tiny_blockdiff_closed", seed=1))


def test_blockdiff_driver_rehearsal():
    ctx = context("tiny-sdar", "tiny_blockdiff_closed", seed=2**31 + 5,
                  seconds=1.0)
    rec = bd.run(ctx)
    assert rec.correct, rec.notes
    n = rec.notes
    assert n["rows_judged"] > 30 and n["agreement"] >= 0.98
    assert n["largest_log_conf_error_over_tolerance"] < 1.0
    assert n["transfers_exact"] and n["forwards_counted"]
    assert n["attention_largest_error_over_tolerance"] < 1.0
    for rows in (32, 16):
        assert n[f"layer_rows_{rows}"]["padding_rows_zero"]
        assert n[f"layer_rows_{rows}"]["largest_error_over_tolerance"] < 1.0
    assert rec.failed == 0 and rec.attempted > 0
    c = rec.counters
    assert c["compiles_in_window"] == 0
    # every forward of a block is Bd rows; a block costs 2 denoise forwards
    # and a commit, but the open first block of a prompt with a tail
    assert c["diff_rows"] == 4 * (c["diff_denoise_forwards"]
                                  + c["diff_commit_forwards"])
    assert c["diff_commit_forwards"] == c["diff_blocks_committed"]
    assert c["diff_denoise_forwards"] <= 2 * c["diff_commit_forwards"]
    assert c["moe_pairs"] == 2 * c["engine_tokens_computed"]
    per_forward = diff_tokens_per_forward.read(rec)
    assert 1.0 < per_forward <= 4 / 3 + 0.1
    assert 100 / 3 <= diff_commit_forward_share.read(rec) < 50
    assert batch_occupancy.read(rec) >= 4       # rows computed, not tokens
    assert moe_experts_hit_share.read(rec) > 0
    for reader in (decode_tokens_per_s, gap_p90_ms):
        assert math.isfinite(reader.read(rec)) and reader.read(rec) >= 0
    # what the cell computes and does not list is in the notes; untraced,
    # the trace readers among them find nothing
    assert n["not_judged"] == {"gap_p90_ms": gap_p90_ms.read(rec),
                               "tick_p50_ms": tick_p50_ms.read(rec)}
    # a block's tokens come in one tick: most gaps are zero
    gaps = np.asarray(rec.samples["gap_ms"])
    assert (gaps < 0.01).mean() > 0.6
    # no trace: the trace readers find nothing and do not raise
    for reader in (blockdiff_experts_roofline, tick_unmask_share):
        assert reader.read(rec) is None


def test_readers_find_nothing_in_a_program_without_the_counters():
    rec = types.SimpleNamespace(
        counters={"engine_steps": 3, "engine_tokens_computed": 48},
        trace=None, trace_counters=None, notes={},
        context=types.SimpleNamespace(config={}, peaks={}))
    for reader in (diff_tokens_per_forward, diff_commit_forward_share,
                   blockdiff_experts_roofline, tick_unmask_share):
        assert reader.read(rec) is None


def test_the_unmask_scope_reaches_scope_of_only_once_registered(monkeypatch):
    name = "jit(step_fn)/jit(main)/sample/unmask/reduce_max"
    monkeypatch.setattr(program_trace, "SCOPES", frozenset(
        program_trace.SCOPES - {blockdiff_scopes.UNMASK}))
    assert program_trace.scope_of(name) == "sample"
    blockdiff_scopes.register()
    assert program_trace.scope_of(name) == "unmask"
    assert program_trace.scope_of(name.replace("/unmask", "")) == "sample"


def test_trace_readers_on_a_recorded_tick(tmp_path):
    """The unmask share and the experts' roofline, by hand, on a
    three-operation trace in program_trace's own layout: the expert width
    is `moe_intermediate_size`, an eighth of what `moe_experts_roofline`
    would read from this configuration."""
    ms = 1_000_000
    trace = {
        "device": {"/device:TPU:0": [["gmm.1", 0, 6 * ms],
                                     ["fusion.2", 6 * ms, 1 * ms],
                                     ["paged_attention.3", 7 * ms, 1 * ms]]},
        "device_scopes": {"/device:TPU:0": ["experts", "unmask",
                                            "paged_attention"]},
        "host": [["bench.tick", 0, 10 * ms]],
        "program_spans": [["ptpu.serve.step", 0, 10 * ms, {"batch": 16}]],
    }
    path = tmp_path / "tick.json"
    path.write_text(json.dumps(trace))
    cfg = {"hidden_size": 2048, "intermediate_size": 6144,
           "moe_intermediate_size": 768, "num_experts": 128,
           "num_experts_per_tok": 8, "num_hidden_layers": 7}
    from benchmark.lib.peaks import PEAKS
    rec = types.SimpleNamespace(
        trace={"busy_s": 0.008}, notes={"trace_file": str(path)},
        trace_counters={"moe_experts_hit": 126 * 7, "moe_pairs": 512},
        counters={}, context=types.SimpleNamespace(
            config=cfg, peaks=PEAKS["TPU v5 lite"]))
    assert tick_unmask_share.read(rec) == pytest.approx(12.5)
    least = 126 * 7 * 3 * 2048 * 768 * 2 / 819e9          # 10.16 ms
    assert blockdiff_experts_roofline.read(rec) == pytest.approx(
        100 * least / 0.006)
    assert moe_experts_roofline.read(rec) == pytest.approx(
        8 * 100 * least / 0.006)        # why the cell does not list it


# ---- the tolerances, and the faults nearest to them -------------------------

def test_transfer_rule_by_hand():
    conf = np.asarray([0.9, 0.2, 0.2, 0.7], np.float32)
    masked = [False, True, True, True]
    # two a forward of T = 2: the most confident masked rows, row 0 is known
    assert reference_sdar.transfer(masked, conf, 2).tolist() == [
        False, True, False, True]       # 0.7, then the tie to the lower row
    assert agreement_blockdiff.judge_transfer(
        masked, conf, [False, True, False, True], 2)
    assert not agreement_blockdiff.judge_transfer(
        masked, conf, [False, False, True, True], 2)    # the tie's other row
    assert not agreement_blockdiff.judge_transfer(
        masked, conf, [True, False, False, True], 2)    # a known row taken
    # T = 4: one a forward; T = 1: all that is left; T = 3: ceil(4 / 3) = 2
    assert reference_sdar.transfer(masked, conf, 4).sum() == 1
    assert reference_sdar.transfer(masked, conf, 1).sum() == 3
    assert reference_sdar.transfer(masked, conf, 3).sum() == 2


def test_confidence_judge_passes_rounding_and_fails_another_tokens_conf():
    rng = np.random.default_rng(0)
    logits = rng.normal(0, 1, (32, 512)).astype(np.float32)
    x0, conf = reference_sdar.propose(logits)
    ok, worst = agreement_blockdiff.judge_confidence(logits, x0, conf)
    assert ok == 32 and worst < 1e-3
    # bf16's rounding of the logits (2^-9 of each) stays inside
    rounded = np.asarray(jnp.asarray(logits).astype(jnp.bfloat16)
                         .astype(jnp.float32))
    ok, worst = agreement_blockdiff.judge_confidence(
        logits, x0, reference_sdar.propose(rounded)[1])
    assert ok == 32 and worst < 0.5
    # the confidence of the second-best token, or a softmax at twice the
    # temperature, is off by whole logit units
    second = np.sort(logits, axis=-1)[:, -2]
    wrong = conf * np.exp(second - logits.max(axis=-1))
    assert agreement_blockdiff.judge_confidence(logits, x0, wrong)[0] < 16
    assert agreement_blockdiff.judge_confidence(
        logits, x0, reference_sdar.propose(logits / 2)[1])[0] == 0


def test_attention_judge_fails_the_causal_and_the_short_mask():
    """Float32 on the CPU at the CELL's shapes (16 slots x 4 rows, 4 KV
    heads, group 8, heads of 128, contexts 64-484): the launch under the
    block-causal mask sits at rounding; under the causal mask inside a
    block, or with its view one block short, it fails."""
    from benchmark.tests.helpers import ROOT_DIR
    with open(f"{ROOT_DIR}/benchmark/configs/sdar30b-a3b-serve.json") as f:
        cfg = json.load(f)
    case = bd.attention_case(cfg, 2**31 + 7, jnp.float32)
    past = np.asarray(case[4])
    assert past.min() == 64 and past.max() + 4 == 488 and not (past % 4).any()
    good, worst = agreement_blockdiff.judge_attention(
        *bd.attention_outputs(cfg, case, 4))
    assert good and worst < 1e-3
    causal, c_worst = agreement_blockdiff.judge_attention(
        *bd.attention_outputs(cfg, case, 0))
    short, s_worst = agreement_blockdiff.judge_attention(
        *bd.attention_outputs(cfg, case, 4, short=4))
    assert not causal and not short
    assert c_worst > 2.0 and s_worst > 2.0
    print("attention faults over tolerance: causal", c_worst, "short",
          s_worst)


def test_token_judge_fails_a_causal_engine(monkeypatch):
    """Parts 1 and 2 on the tiny fixture with the fault nearest to them:
    the served path under the causal mask inside a block (the block's rows
    do not see one another's later rows) moves the logits by whole units."""
    from paddle_tpu.inference.serving import engine as E

    real = E.paged_layer_attention
    monkeypatch.setattr(E, "paged_layer_attention",
                        lambda *a, **kw: real(*a, **{**kw, "block_length": 0}))
    ctx = context("tiny-sdar", "tiny_blockdiff_closed", seed=2**31 + 5)
    cfg = ctx.config
    lcfg = bd.sdar_config(cfg, jnp.bfloat16)
    from paddle_tpu.models import llama as L
    from benchmark.drivers.closed_loop_serve import build_engine
    from benchmark.lib.harness import seed_key
    params = L.init_params(lcfg, seed_key(ctx.seed))
    ok, notes = bd.check_generation(build_engine(cfg, params, lcfg), cfg,
                                    params, ctx.seed)
    assert not ok
    print("a causal engine on the tiny fixture:", notes)
    assert (notes["agreement"] < agreement.MIN_AGREEMENT
            or notes["confidence_agreement"] < agreement.MIN_AGREEMENT)
    assert notes["transfers_exact"] and notes["forwards_counted"]


def test_the_cell_runs_through_run_py_as_files_and_entries_only(
        tmp_path, monkeypatch):
    """The tiny configuration and traffic as a cell of a temporary copy,
    through run.py itself (a wrapper stands in for the TPU check), untraced
    and traced: the result line holds the judged metrics, and the traced
    one the counter readers (no device trace on the CPU, so the two scope
    readers are left out, as on a program without the scope)."""
    import os
    import shutil

    from benchmark.tests.helpers import FIXTURES, ROOT_DIR
    from benchmark.tests import test_add_files_only
    from benchmark.tests.test_add_files_only import digests, run_cell

    # this driver's window is serve_window's, whose memory reading the CPU
    # backend cannot give either
    monkeypatch.setattr(
        test_add_files_only, "WRAPPER", test_add_files_only.WRAPPER.replace(
            "serve.memory_peak_bytes = lambda: 0",
            "serve.memory_peak_bytes = lambda: 0\n"
            "import benchmark.lib.serve_window as window\n"
            "window.memory_peak_bytes = lambda: 0"))
    tmp = str(tmp_path)
    copy = os.path.join(tmp, "checkout")
    shutil.copytree(os.path.join(ROOT_DIR, "benchmark"),
                    os.path.join(copy, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digests(os.path.join(copy, "benchmark"))
    for kind, name in (("configs", "tiny-sdar.json"),
                       ("traffic", "tiny_blockdiff_closed.json")):
        shutil.copy(os.path.join(FIXTURES, kind, name),
                    os.path.join(copy, "benchmark", kind, name))
    with open(os.path.join(ROOT_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-sdar", "source": "fixture",
        "file": "benchmark/configs/tiny-sdar.json", "reduced": [],
        "why": "fixture"})
    bench["workloads"].append({
        "name": "fixture_blockdiff", "config": "tiny-sdar",
        "traffic": "tiny_blockdiff_closed", "chips": 1, "why": "fixture"})
    real = "serve_blockdiff_decode"
    for m in bench["end_to_end"] + bench["per_layer"]:
        if real in m.get("workloads", ()):
            m["workloads"].append("fixture_blockdiff")
    with open(os.path.join(copy, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    plain = run_cell(tmp, copy, "fixture_blockdiff", trace=0)
    assert plain["correct"] and plain["failed"] == 0 < plain["attempted"]
    # gap_p90_ms is not judged in this cell: it is among the notes
    assert set(plain["metrics"]) == {"decode_tokens_per_s", "setup_s"}
    assert set(plain["notes"]["not_judged"]) == {"gap_p90_ms", "tick_p50_ms"}
    assert plain["counters"]["compiles_in_window"] == 0
    after = digests(os.path.join(copy, "benchmark"))
    assert {k: after[k] for k in before} == before


@pytest.mark.parametrize("weights", ["served", "8_bit"])
def test_one_layer_check_at_the_cells_widths_fails_8_bit_experts(
        weights, monkeypatch):
    """Part 5 through the driver's own `check_one_layer` at SDAR's widths
    (hidden 2048, 128 experts of 768, 8 a row renormalised; 512 rows with
    259 valid, and 64), layer 0's weights seeded as the program seeds
    them: the program as it is passes, and one that computes in the
    precision below the configuration's, expert weights rounded to 8 bits
    (the reference keeps the bf16 weights the configuration states), comes
    out not correct."""
    from benchmark.tests.helpers import ROOT_DIR
    from paddle_tpu.models import llama as L
    with open(f"{ROOT_DIR}/benchmark/configs/sdar30b-a3b-serve.json") as f:
        cfg = json.load(f)
    lcfg = bd.sdar_config(cfg, jnp.bfloat16)
    d, f_, E = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["num_experts"]
    keys = jax.random.split(jax.random.PRNGKey(32), 4)
    shapes = {"router": (d, E), "w1": (E, d, f_), "w3": (E, d, f_),
              "w2": (E, f_, d)}
    params = {"blocks": {
        n: (0.02 * jax.random.normal(k, shapes[n], jnp.float32)
            ).astype(jnp.bfloat16)[None] for n, k in zip(shapes, keys)}}
    if weights == "8_bit":
        real = L.routed_ffn

        def eight_bit(w):
            w = w.astype(jnp.float32)
            scale = jnp.abs(w).max(axis=-2, keepdims=True) / 127.0
            return (jnp.round(w / scale) * scale).astype(jnp.bfloat16)

        monkeypatch.setattr(L, "routed_ffn", lambda h, lp, *a, **kw: real(
            h, {**lp, **{n: eight_bit(lp[n]) for n in ("w1", "w3", "w2")}},
            *a, **kw))
    ok, notes = bd.check_one_layer(cfg, params, lcfg, 2**31 + 9)
    print("one layer at SDAR's widths,", weights, notes)
    assert all(v["padding_rows_zero"] for v in notes.values())
    assert ok == (weights == "served")
