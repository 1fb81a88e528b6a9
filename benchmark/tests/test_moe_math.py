"""`moe_math` against hand-counted bytes and FLOPs, and `agreement_moe`
against the faults it exists to catch (float32 on the CPU, tiny sizes)."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.layer_metrics import (cow_copies_per_tick,
                                     moe_experts_hit_share,
                                     moe_experts_roofline, prefix_hit_share)
from benchmark.lib import agreement_moe, moe_math, reference_olmoe
from benchmark.lib.peaks import PEAKS
from paddle_tpu.models import llama as L

OLMOE = {"hidden_size": 2048, "intermediate_size": 1024, "num_experts": 64,
         "num_experts_per_tok": 8, "num_hidden_layers": 16}
V5E = PEAKS["TPU v5 lite"]


def test_bytes_and_flops_by_hand():
    # one expert: three matrices of 2048 x 1024
    assert moe_math.expert_params(OLMOE) == 3 * 2048 * 1024 == 6291456
    # every expert of one layer, bf16: the issue's 0.805 GB
    assert moe_math.experts_bytes(OLMOE, 64) == 64 * 6291456 * 2 == 805306368
    # a decode tick: 16 rows x 8 experts, in each of 16 layers
    assert moe_math.experts_flops(OLMOE, 128) == 2 * 128 * 16 * 6291456
    # a mixed tick's layer at 512 rows: 51.5 GFLOP (the issue's number)
    assert moe_math.experts_flops(OLMOE, 4096) / 16 == pytest.approx(
        51.5e9, rel=1e-3)


def test_least_seconds_is_memory_bound_at_decode_and_compute_bound_late():
    # 16 rows, 56 of 64 experts hit in each of 16 layers
    secs, bound = moe_math.experts_least_seconds(OLMOE, 56 * 16, 128, V5E)
    assert bound == "memory"
    assert secs == pytest.approx(56 * 16 * 6291456 * 2 / 819e9)
    # at 16,384 rows a layer the FLOPs take over
    secs, bound = moe_math.experts_least_seconds(OLMOE, 64 * 16,
                                                 16384 * 8, V5E)
    assert bound == "compute"
    assert secs == pytest.approx(2 * 16384 * 8 * 16 * 6291456 / 197e12)


def test_hit_share_and_load_ratio_by_hand():
    # 10 ticks x 16 layers x 64 experts = 10240 groups, 8960 with a row
    assert moe_math.hit_share(OLMOE, 8960, 10) == 87.5
    # 128 pairs on 64 experts: mean 2; the fullest expert got 7
    assert moe_math.load_max_over_mean(OLMOE, 7, 128) == 3.5


def _record(counters=None, trace_counters=None, trace=None):
    ctx = types.SimpleNamespace(config=OLMOE, peaks=V5E)
    return types.SimpleNamespace(counters=counters or {}, notes={},
                                 trace_counters=trace_counters, trace=trace,
                                 context=ctx)


def test_counter_readers_by_hand_and_none_without_their_counters():
    rec = _record({"moe_experts_hit": 8960, "engine_steps": 10,
                   "prompt_tokens_submitted": 2000, "prefix_hit_tokens": 1780,
                   "cow_block_copies": 3})
    assert moe_experts_hit_share.read(rec) == 87.5
    assert prefix_hit_share.read(rec) == 89.0
    assert cow_copies_per_tick.read(rec) == 0.3
    # a program (the parent's) or a cell without them: nothing, no raise
    bare = _record({"engine_steps": 10})
    for reader in (moe_experts_hit_share, prefix_hit_share,
                   cow_copies_per_tick, moe_experts_roofline):
        assert reader.read(bare) is None


# ---- the one-layer comparison ---------------------------------------------

def _layer(dtype, top_k=8, seed=0):
    cfg = L.LlamaConfig(vocab_size=64, hidden_size=128, intermediate_size=64,
                        num_layers=1, num_heads=2, num_kv_heads=2,
                        num_experts=16, top_k=top_k, qk_norm=True,
                        norm_topk_prob=False, dtype=dtype, param_dtype=dtype)
    params = L.init_params(cfg, jax.random.PRNGKey(seed))
    lp = {k: params["blocks"][k][0] for k in ("router", "w1", "w3", "w2")}
    lp["router"] = lp["router"] * 8.0
    h = jax.random.normal(jax.random.PRNGKey(seed + 1), (48, 128),
                          jnp.float32).astype(dtype)
    return cfg, lp, h


def _ref(cfg, lp, h, **over):
    kw = dict(top_k=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob)
    kw.update(over)
    return np.asarray(reference_olmoe.expert_block(
        h.astype(jnp.float32), lp, **kw))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_program_agrees(dtype):
    cfg, lp, h = _layer(dtype)
    out = L.routed_ffn(h, lp, cfg)
    ok, worst = agreement_moe.judge(np.asarray(out.astype(jnp.float32)),
                                    _ref(cfg, lp, h))
    assert ok and worst < (0.01 if dtype == jnp.float32 else 1.0)


@pytest.mark.parametrize("fault", ["one_expert_dropped", "renormalised",
                                   "padding_row_routed", "weights_8_bit"])
def test_each_fault_fails_it(fault):
    cfg, lp, h = _layer(jnp.float32)
    good = _ref(cfg, lp, h)
    if fault == "one_expert_dropped":
        out = _ref(cfg, lp, h, top_k=cfg.top_k - 1)
    elif fault == "renormalised":
        out = _ref(cfg, lp, h, norm_topk_prob=True)
    elif fault == "padding_row_routed":
        # the last 8 rows are padding and should be zeros: a program that
        # routes them returns their experts' output instead
        valid = jnp.arange(48) < 40
        out = np.asarray(L.routed_ffn(h, lp, cfg))
        good = np.asarray(L.routed_ffn(h, lp, cfg, valid))
        assert not np.any(good[40:])
    else:
        def eight_bit(w):
            scale = jnp.abs(w).max(axis=-2, keepdims=True) / 127.0
            return jnp.round(w / scale) * scale
        q = {**lp, **{n: eight_bit(lp[n]) for n in ("w1", "w3", "w2")}}
        out = np.asarray(L.routed_ffn(h, q, cfg))
    ok, worst = agreement_moe.judge(out, good)
    # 8-bit weights are the nearest fault: 1.27 of the tolerance here,
    # twice what bf16 reads on the chip; the others are far outside
    assert not ok and worst > (1.2 if fault == "weights_8_bit" else 5.0), (
        fault, worst)
