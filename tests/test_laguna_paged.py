"""Laguna-shaped models (a layer plan: a leading dense layer, window and
full attention with their own head counts and ropes, a per-head attention
gate, routed experts under a sigmoid router beside a shared one) through
`llama.forward` and `PagedServingEngine` with its two page pools, against
the plain float32 reference `benchmark/lib/reference_laguna.py`.

Everything here is float32 at a tiny size (the benchmark's fixture
`tiny-laguna.json`: 5 layers, d 64, 2 key-value heads of 16, 6 query heads
in full and 8 in window layers, window 24, YaRN x8 on half of each head in
full layers, 16 experts of 32, four a row, vocabulary 512). `forward`'s
logits equal the reference's to 1e-4; the engine's tokens EQUAL the
reference's greedy loop, through chunked prefill, both pools, page
releases, a preemption and a resume; every fault nearest to the model
(no gate, no shared expert, a softmax router, the other kind's rope, a
window a page off) moves the logits by whole percents.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import random
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import closed_loop_serve_longctx as D
from benchmark.lib import reference_laguna as R
from paddle_tpu.inference.serving import PagedServingEngine
from paddle_tpu.inference.serving.block_manager import (BlockManager,
                                                        NoFreeBlocksError)
from paddle_tpu.inference.serving.scheduler import Scheduler, Sequence
from paddle_tpu.models import llama as L

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "benchmark", "tests", "fixtures",
                       "configs", "tiny-laguna.json")) as f:
    TINY = json.load(f)
WIDTH = 128             # the reference's padded length (one compile)


def make(seed=0):
    cfg = dataclasses.replace(D.laguna_config(TINY, jnp.float32),
                              dtype=jnp.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(seed))
    # a router and a head sharp enough that top-k sets and argmaxes differ
    blocks = tuple({**b, **({"router": b["router"] * 20.0}
                            if "router" in b else {})}
                   for b in params["blocks"])
    return cfg, {**params, "blocks": blocks,
                 "lm_head": params["lm_head"] * 8.0}


@pytest.fixture(scope="module")
def tiny():
    return make()


def prompt_of(n, seed=1):
    return np.random.default_rng(seed).integers(1, 500, n).tolist()


def reference_tokens(params, prompt, new):
    with jax.default_matmul_precision("highest"):
        return R.generate(params, prompt, new, WIDTH, **R.model_kw(TINY))[0]


def engine(cfg, params, **kw):
    e = TINY["engine"]
    kw = {**dict(num_blocks=e["num_blocks"], block_size=e["block_size"],
                 max_batch=e["max_batch"], token_budget=e["token_budget"],
                 max_len=e["max_len"], window_blocks=e["window_blocks"],
                 pallas=False), **kw}
    return PagedServingEngine(cfg, params, **kw)


FAULTS = {
    "no_gate": lambda c: dataclasses.replace(c, attn_gate=False),
    "no_shared_expert": lambda c: dataclasses.replace(
        c, shared_expert_width=0),
    "softmax_router": lambda c: dataclasses.replace(
        c, router_score="softmax"),
    "swapped_ropes": lambda c: dataclasses.replace(c, layer_plan=tuple(
        dataclasses.replace(s, rope=next(
            o.rope for o in c.kinds if o.attn != s.attn))
        for s in c.layer_plan)),
    "window_plus_a_page": lambda c: dataclasses.replace(
        c, sliding_window=c.sliding_window + 8),
    "window_minus_a_page": lambda c: dataclasses.replace(
        c, sliding_window=c.sliding_window - 8),
}


# ---- the model ---------------------------------------------------------------

def test_forward_equals_the_reference_on_logits(tiny):
    cfg, params = tiny
    tokens = jnp.asarray(prompt_of(100, seed=11), jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(R.logits_at(params, tokens, jnp.arange(100),
                                     **R.model_kw(TINY)))
        got = np.asarray(L.forward(params, tokens[None], cfg)[0])
    assert np.abs(got - ref).max() < 1e-4 * np.abs(ref).max()


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_moves_forward_and_the_engine_off_the_reference(tiny, fault):
    """The negative controls: each computes another model, and both
    `forward`'s logits and the engine's tokens show it."""
    cfg, params = tiny
    bad = FAULTS[fault](cfg)
    tokens = jnp.asarray(prompt_of(100, seed=11), jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(R.logits_at(params, tokens, jnp.arange(100),
                                     **R.model_kw(TINY)))
        got = np.asarray(L.forward(params, tokens[None], bad)[0])
        assert np.abs(got - ref).max() > 1e-2 * np.abs(ref).max()
        prompts = [prompt_of(n, seed=n) for n in (70, 55, 41)]
        eng = engine(bad, params)
        rids = [eng.submit(p, max_new_tokens=40) for p in prompts]
        done = {d.rid: d.output_tokens for d in eng.run()}
    assert any(done[rid] != reference_tokens(params, p, 40)
               for rid, p in zip(rids, prompts))


def test_a_plan_with_a_period_runs_as_the_unrolled_stack():
    """12 layers = the dense layer, two periods of (3 window, 1 full), 3
    window: `scan_plan`'s scan over periods, its sliced stacks and its
    remainder equal the layers applied one by one."""
    cfg0 = D.laguna_config(TINY, jnp.float32)
    plan = tuple(cfg0.layer_plan[0 if i == 0 else 1 + (i - 1) % 4]
                 for i in range(12))
    cfg = dataclasses.replace(cfg0, num_layers=12, layer_plan=plan,
                              dtype=jnp.float32)
    assert L.plan_segments(cfg) == [(1, ((0, 1),)), (2, ((1, 3), (2, 1))),
                                    (1, ((1, 3),))]
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(prompt_of(40), jnp.int32)[None]
    x = jnp.take(params["embed"], tokens, axis=0)
    at = [0] * 3
    for spec, k in zip(plan, cfg.kind_of_layer):
        lp = jax.tree.map(lambda a: a[at[k]], params["blocks"][k])
        at[k] += 1
        x = L.block(x, lp, cfg, *L.rope_table(jnp.arange(40), cfg.head_dim,
                                              spec.rope), spec=spec)
    want = L.rms_norm(x, params["final_norm"], cfg.rms_eps) @ params[
        "lm_head"]
    got = jax.jit(lambda p, t: L.forward(p, t, cfg))(params, tokens)
    assert np.abs(np.asarray(got - want)).max() < 1e-5


def test_counts_at_the_published_plan():
    """`num_params()` 33.4 B and 3 B active at the published 40 layers;
    the loop is the leading layer, nine periods and a remainder."""
    with open(os.path.join(HERE, "..", "benchmark", "configs",
                           "laguna-xs2-serve.json")) as f:
        cut = json.load(f)
    full = {**cut, "num_hidden_layers": 40,
            "layer_types": (cut["layer_types"][:4] * 10),
            "num_attention_heads_per_layer":
                cut["num_attention_heads_per_layer"][:4] * 10,
            "mlp_layer_types": ["dense"] + ["sparse"] * 39}
    cfg = D.laguna_config(full, jnp.bfloat16)
    assert round(cfg.num_params() / 1e9, 2) == 33.44
    active = cfg.num_active_params() + cfg.vocab_size * cfg.hidden_size
    assert 2.9e9 < active < 3.1e9                 # "33.4B-A3B"
    assert cfg.flops_per_token() == 6 * (cfg.num_active_params()
                                         + cfg.hidden_size * cfg.vocab_size)
    assert [n for n, _ in L.plan_segments(cfg)] == [1, 9, 1]
    cut5 = D.laguna_config(cut, jnp.bfloat16)
    assert L.plan_segments(cut5) == [(1, ((0, 1), (1, 3), (2, 1)))]
    assert [(k.attn, k.heads, k.ffn) for k in cut5.kinds] == [
        ("full", 48, "dense"), ("window", 64, "sparse"),
        ("full", 48, "sparse")]


def test_a_replaced_config_cannot_carry_a_stale_head_width():
    cfg = L.CONFIGS["llama-test"]
    assert cfg.head_dim == 16
    assert dataclasses.replace(cfg, hidden_size=128).head_dim == 32
    assert dataclasses.replace(cfg, num_heads=8).head_dim == 8
    given = dataclasses.replace(cfg, head_dim=8)
    assert given.head_dim == 8
    assert dataclasses.replace(given, hidden_size=128).head_dim == 8
    sdar = L.LlamaConfig(hidden_size=2048, num_heads=32, head_dim=128)
    assert dataclasses.replace(sdar, hidden_size=1024).head_dim == 128
    assert dataclasses.replace(cfg, hidden_size=128, head_dim=0
                               ).head_dim == 32      # what PR 32 asked for


# ---- the engine --------------------------------------------------------------

@pytest.mark.parametrize("pallas", [False, True])
def test_engine_equals_the_reference_through_both_pools(tiny, pallas):
    """Chunked prefill (chunks of 32 over prompts of 70 and 33), then
    decode, two sequences in different phases; contexts cross the window
    of 24 several times, so window pages go back while full pages stay."""
    cfg, params = tiny
    eng = engine(cfg, params, pallas=pallas)
    prompts = [prompt_of(70, seed=3), prompt_of(33, seed=4)]
    news = [20, 30]
    rids = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    done = {d.rid: d.output_tokens for d in eng.run()}
    for rid, p, n in zip(rids, prompts, news):
        assert done[rid] == reference_tokens(params, p, n)
    st = eng.engine_stats
    assert st["window_pages_released"] >= 10
    assert (st["blocks_window_allocs"] > st["blocks_window_released"]
            == st["window_pages_released"])
    assert eng.blocks.num_allocated() == eng.blocks.window_allocated() == 0
    assert 0 < st["attn_keys_window"] < st["attn_keys_full"]
    assert st["attn_keys_full"] + st["attn_keys_window"] < st[
        "attn_keys_causal"]
    assert st["window_pages_live"] < st["full_pages_live"]
    assert st["prefix_cache"].startswith("off")
    assert st["blocks_prefix_hit_tokens"] == 0


@pytest.mark.parametrize("short", [dict(num_blocks=16),
                                   dict(window_blocks=9)])
def test_a_preempted_sequence_resumes_through_both_pools(tiny, short):
    """Either pool too small for the sequences' growth: one is preempted
    (both its tables freed), re-prefills from its ids and ends on the
    reference's tokens; nothing leaks."""
    cfg, params = tiny
    eng = engine(cfg, params, max_batch=3, **short)
    prompts = [prompt_of(n, seed=n) for n in (60, 50, 44)]
    rids = [eng.submit(p, max_new_tokens=16) for p in prompts]
    done = {d.rid: d.output_tokens for d in eng.run()}
    assert eng.engine_stats["preemptions"] >= 1
    for rid, p in zip(rids, prompts):
        assert done[rid] == reference_tokens(params, p, 16)
    assert eng.blocks.num_allocated() == eng.blocks.window_allocated() == 0


def test_a_repeated_prompt_is_not_served_from_the_prefix_cache(tiny):
    cfg, params = tiny
    eng = engine(cfg, params)
    prompt = prompt_of(40, seed=9)
    outs = []
    for _ in range(2):
        eng.submit(prompt, max_new_tokens=6)
        outs.append(eng.run()[0].output_tokens)
    assert outs[0] == outs[1] == reference_tokens(params, prompt, 6)
    assert eng.engine_stats["blocks_prefix_hit_tokens"] == 0


@pytest.mark.parametrize("what, build", [
    ("quant_kv", lambda c, p: engine(c, p, quant_kv=True)),
    ("quant_mode", lambda c, p: engine(c, p, quant_mode="w8")),
    ("adapter_slots", lambda c, p: engine(c, p, adapter_slots=2)),
    ("draft", lambda c, p: engine(c, p, draft=(L.CONFIGS["llama-test"], {}))),
    ("pallas_ffn", lambda c, p: engine(c, p, pallas_ffn=True)),
    ("LLMPredictor", lambda c, p: __import__(
        "paddle_tpu.inference.llm", fromlist=["x"]).LLMPredictor(c, p)),
    ("hybrid", lambda c, p: __import__(
        "paddle_tpu.distributed.hybrid", fromlist=["x"]).param_specs(c)),
    ("DraftModel", lambda c, p: __import__(
        "paddle_tpu.inference.serving.speculative",
        fromlist=["x"]).DraftModel(dataclasses.replace(c, num_experts=0,
                                                       layer_plan=tuple(
            dataclasses.replace(s, ffn="dense") for s in c.layer_plan)), p)),
])
def test_what_a_layer_plan_refuses_raises_at_construction(tiny, what, build):
    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="layer plan"):
        build(cfg, params)


def test_what_a_layer_plan_refuses_raises_in_use(tiny):
    cfg, params = tiny
    eng = engine(cfg, params)
    with pytest.raises(NotImplementedError, match="layer plan"):
        eng.submit([1, 2, 3], adapter="a")
    with pytest.raises(NotImplementedError, match="layer plan"):
        eng.extract_pages([1, 2, 3])
    with pytest.raises(NotImplementedError, match="layer plan"):
        eng.ingest_pages({})
    with pytest.raises(ValueError, match="window_blocks"):
        engine(cfg, params, window_blocks=4)
    # the flash kernel has no block-causal mask; since PR 47 it has the
    # window, and gives what the XLA path gives
    with pytest.raises(ValueError, match="flash"):
        L.attention(jnp.zeros((1, 8, 2, 16)), jnp.zeros((1, 8, 2, 16)),
                    jnp.zeros((1, 8, 2, 16)), impl="flash", block_length=4)
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 8, 2, 16))
               for i in range(3))
    np.testing.assert_allclose(
        np.asarray(L.attention(q, k, v, impl="flash", window=4)),
        np.asarray(L.attention(q, k, v, impl="xla", window=4)),
        rtol=2e-5, atol=2e-5)


# ---- a uniform config is what it was -----------------------------------------

def _sha(text):
    return hashlib.sha256(re.sub(r"0x[0-9a-f]+", "0x", text).encode()
                          ).hexdigest()[:16]


def _tick_jaxpr(eng, tok_pad, decode):
    B, Bd = eng.max_batch, eng.cfg.block_length
    fn = eng._build_step(tok_pad, B, decode)
    args = (eng.params, eng._key_cache, eng._value_cache, None,
            np.zeros((tok_pad,), np.int32),
            np.full((B, eng.max_blocks_per_seq), -1, np.int32),
            np.zeros((B + 1,), np.int32), np.zeros((B,), np.int32),
            np.zeros((B,), np.int32), eng._rope_emb,
            np.ones((B,), np.float32), np.ones((B,), np.float32),
            np.zeros((B, 2), np.uint32), np.ones((B,), bool), (),
            *((np.zeros((B,), np.int32), np.zeros((B, Bd), bool)) if Bd
              else (None, None)),
            eng._last_out, np.full((tok_pad,), -1, np.int32))
    return str(jax.make_jaxpr(fn)(*args))


UNIFORM = {
    "dense": {},
    "moe": dict(num_experts=4, top_k=2, qk_norm=True, norm_topk_prob=False),
    "blockdiff": dict(intermediate_size=32, head_dim=32, num_experts=4,
                      top_k=2, qk_norm=True, qk_norm_per_head=True,
                      block_length=4, mask_token_id=255),
}


@pytest.mark.parametrize("name", sorted(UNIFORM))
def test_a_uniform_config_builds_the_parents_pytree_and_programs(name):
    """The parameter pytree (paths, shapes, every value), `forward`'s
    jaxpr and the ticks' jaxprs (stock path; kernel path in interpret
    mode, mixed and decode) of a dense, a routed-expert and a
    block-diffusion config, against digests taken with this function's
    code: letter for letter the programs they were.
    tests/data/uniform_digests.json was taken on PR 46's tree: its
    `.params` and `.forward` entries are the ones PR 33 took (compared
    when the file was written), its `.tick.` entries are that PR's, which
    put the uniform stack through the one layer loop (`_layer_loop`: the
    scan's leaves repacked, the expert counters in the carry; the same
    equations, one add and one max more a routed-expert tick). A PR that
    changes a program on purpose takes its entries anew and says which."""
    with open(os.path.join(HERE, "data", "uniform_digests.json")) as f:
        want = {k: v for k, v in json.load(f).items()
                if k.startswith(name + ".")}
    cfg = L.LlamaConfig(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, max_seq_len=64), **UNIFORM[name]})
    params = L.init_params(cfg, jax.random.PRNGKey(3))
    got = {f"{name}.params": _sha(";".join(
        f"{jax.tree_util.keystr(p)}{a.shape}{a.dtype}"
        f"{hashlib.sha256(np.asarray(a).tobytes()).hexdigest()}"
        for p, a in jax.tree_util.tree_leaves_with_path(params)))}
    toks = jnp.zeros((1, 16), jnp.int32)
    got[f"{name}.forward"] = _sha(str(jax.make_jaxpr(
        lambda p, t: L.forward(p, t, cfg, attn_impl="xla"))(params, toks)))
    for pallas in (False, True):
        eng = PagedServingEngine(cfg, params, num_blocks=16, block_size=8,
                                 max_batch=2, token_budget=16, max_len=64,
                                 pallas=pallas)
        got[f"{name}.tick.mixed.pallas{int(pallas)}"] = _sha(
            _tick_jaxpr(eng, 16, False))
        if pallas and not cfg.block_length:
            got[f"{name}.tick.decode.pallas1"] = _sha(
                _tick_jaxpr(eng, 2, True))
    assert got == want


# ---- two pools, two lifetimes (host books alone) ------------------------------

def pools(**kw):
    return BlockManager(**{**dict(num_blocks=32, block_size=4,
                                  window_blocks=12, window=10,
                                  page_bytes=100, window_page_bytes=30),
                           **kw})


def test_window_pages_go_back_behind_the_window():
    bm = pools()
    assert bm.allocate_sequence(1, list(range(30))) == 0      # no prefix hit
    assert bm.num_blocks_of(1) == 8 and bm.window_table(1) == []
    bm.ensure_capacity(1, 16)
    assert len(bm.window_table(1)) == 4
    # the next query sits at 16 and sees from 16 - 9 = 7 on: page 0
    # (positions 0..3) goes, page 1 (4..7) holds position 7 and stays
    assert bm.release_behind(1, 16) == 1
    assert bm.window_table(1)[:2] == [-1, bm.window_table(1)[1]]
    assert bm.release_behind(1, 16) == 0
    bm.ensure_capacity(1, 30)
    assert bm.release_behind(1, 30) == 4                      # pages 1..4
    assert bm.window_table(1)[:5] == [-1] * 5
    assert bm.window_allocated() == 3 and bm.num_allocated() == 8
    assert bm.bytes_in_use() == 8 * 100 + 3 * 30
    assert bm.bytes_total() == 32 * 100 + 12 * 30
    assert bm.utilization() == 11 / 44
    bm.free_sequence(1)
    assert bm.window_allocated() == bm.num_allocated() == 0
    assert bm.stats["window_allocs"] == 8 and bm.stats["window_released"] == 5
    # a repeated prompt hits nothing: the prefix cache is off
    bm.allocate_sequence(2, list(range(30)))
    bm.register_computed(2, list(range(30)), 30)
    bm.free_sequence(2)
    assert bm.allocate_sequence(3, list(range(30))) == 0


def test_growth_needs_both_pools_and_leaves_no_half_state():
    bm = pools(window_blocks=3)
    bm.allocate_sequence(1, list(range(8)))
    bm.ensure_capacity(1, 8)
    assert bm.growth(1, 20) == (3, 3) and not bm.can_allocate(3, 3)
    with pytest.raises(NoFreeBlocksError, match="window"):
        bm.ensure_capacity(1, 20)
    assert (bm.num_blocks_of(1), len(bm.window_table(1))) == (2, 2)
    short = pools(num_blocks=2)
    with pytest.raises(NoFreeBlocksError):
        short.allocate_sequence(1, list(range(30)))
    assert short.num_allocated() == 0 and not short.has_sequence(1)
    with pytest.raises(ValueError, match="come together"):
        BlockManager(8, 4, window_blocks=4)


def tick(sched, harvest=True):
    """One tick on the host's books alone, as the engine advances them."""
    batch, _ = sched.schedule()
    for seq, n in batch.items:
        if sched.on_dispatched(seq, n):
            seq.tokens[-1] = 7                                # the id read
            seq.generated.append(7)
        if harvest:
            sched.on_harvested(seq, seq.num_computed)
        if len(seq.generated) >= seq.max_new_tokens:
            sched.finish(seq, "length")
    return batch


def test_admission_waits_when_the_window_pool_is_short():
    bm = pools(window_blocks=6)
    sched = Scheduler(bm, token_budget=16, max_batch=4)
    for rid in range(2):
        sched.add_request(Sequence(rid, list(range(1, 17)), 4))
    first = tick(sched)
    # 16 rows of budget go to the first; the second is not admitted on 0
    assert [s.rid for s, _ in first.items] == [0]
    assert bm.has_sequence(0) and not bm.has_sequence(1)
    assert bm.window_allocated() == 3       # 4 pages, one behind the window
    # 15 rows of the second's prompt would need 4 window pages; 0's decode
    # row took one more and 2 are free: not admitted, and the pages it had
    # been given in the other pool are back
    second = tick(sched)
    assert [s.rid for s, _ in second.items] == [0]
    assert not bm.has_sequence(1) and sched.queue_depth() == 1
    assert bm.num_allocated() == bm.num_blocks_of(0)
    while sched.get(0).status != "finished":
        tick(sched)
    assert bm.window_allocated() == bm.num_allocated() == 0
    assert [s.rid for s, _ in tick(sched).items] == [1]


def test_preemption_frees_both_pools_and_nothing_leaks_over_random_ticks():
    rnd = random.Random(0)
    bm = pools(num_blocks=40, window_blocks=14)
    sched = Scheduler(bm, token_budget=12, max_batch=4, max_queue=1000)
    rid = 0
    for step in range(1000):
        if rnd.random() < 0.3 and sched.queue_depth() < 6:
            sched.add_request(Sequence(rid, [1] * rnd.randint(1, 40),
                                       rnd.randint(1, 24)))
            rid += 1
        if rnd.random() < 0.05 and sched.running:
            sched.cancel(rnd.choice(sched.running).rid)
        tick(sched)
        held = sum(bm.num_blocks_of(s.rid) for s in sched.running)
        wheld = sum(sum(p >= 0 for p in bm.window_table(s.rid))
                    for s in sched.running)
        assert (bm.num_allocated(), bm.window_allocated()) == (held, wheld)
        for s in sched.running:
            table = bm.window_table(s.rid)
            first = max(0, s.num_computed - 9) // 4
            assert all(p == -1 for p in table[:first])
            assert all(p >= 0 for p in table[first:])
            assert len(table) == -(-s.num_computed // 4)
    assert sched.stats["preemptions"] > 0 and rid > 100
    while sched.has_work():
        tick(sched)
    assert bm.num_allocated() == bm.window_allocated() == 0
    assert bm.stats["window_allocs"] >= bm.stats["window_released"] > 0
    assert sorted(bm._wfree) == list(range(14))
