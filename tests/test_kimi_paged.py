"""Kimi-shaped models (multi-head latent attention over a latent page pool,
a leading dense layer, a sigmoid router with a selection bias, a chip's
share of the routed experts beside a shared one) through `llama.forward`
and `PagedServingEngine`, against the plain float32 reference
`benchmark/lib/reference_kimi.py`.

Everything here is float32 at a tiny size whose geometry stays odd (the
benchmark's fixture `tiny-kimi.json`: 3 layers, d 64, 8 heads of 16 + 8,
latent 40 + rope 8 = a cache row of 48 values in a pool of 128 lanes, YaRN
x8 on the rope slice, 16 experts of 32 of which 4 are held, two a row,
vocabulary 512). `forward` (expanded form) equals the reference on logits;
the engine (absorbed form, stock path and Pallas interpreter) EQUALS the
reference's greedy loop through chunked prefill, paged decode, a
preemption and a prefix hit; a fault seeded in each new piece of the
reference moves both off it; the shares of a layer add up to the uncut
layer.
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import closed_loop_serve_latent as D
from benchmark.lib import agreement, reference_kimi as R
from paddle_tpu.inference.serving import PagedServingEngine
from paddle_tpu.models import llama as L
from paddle_tpu.ops.pallas import paged_attention_latent as PL
from tests.test_mellum2_train import _eqns
from tests.test_tracing import _profiled

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "benchmark", "tests", "fixtures",
                       "configs", "tiny-kimi.json")) as f:
    TINY = json.load(f)
WIDTH = 128             # the reference's padded length (one compile)
FAULTS = ("no_inner_norm", "rope_on_nope", "bias_as_weight", "v_from_k",
          "scale_without_m2", "no_bias")


def sharpened(params):
    """A router and a head sharp enough that top-k sets and argmaxes
    differ, a selection bias that changes choices, queries and rope keys
    large enough that the scores are not flat, and routed experts that
    weigh as much as the shared one."""
    blocks = tuple({**b, "wqb": b["wqb"] * 30.0, "wkva": b["wkva"] * 5.0,
                    **({"router": b["router"] * 20.0,
                        "router_bias": b["router_bias"] * 10.0,
                        "w2": b["w2"] * 8.0} if "router" in b else {})}
                   for b in params["blocks"])
    return {**params, "blocks": blocks, "lm_head": params["lm_head"] * 8.0}


def make(file=TINY, seed=0):
    cfg = dataclasses.replace(D.kimi_config(file, jnp.float32),
                              dtype=jnp.float32)
    return cfg, sharpened(L.init_params(cfg, jax.random.PRNGKey(seed)))


@pytest.fixture(scope="module")
def tiny():
    return make()


def prompt_of(n, seed=1):
    return np.random.default_rng(seed).integers(1, 500, n).tolist()


def reference_tokens(params, prompt, new, **fault):
    with jax.default_matmul_precision("highest"):
        return R.generate(params, prompt, new, WIDTH, **R.model_kw(TINY),
                          **fault)[0]


def reference_logits(params, tokens, file=TINY, **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(R.logits_at(
            params, jnp.asarray(tokens, jnp.int32), jnp.arange(len(tokens)),
            **{**R.model_kw(file), **kw}))


def engine(cfg, params, **kw):
    e = TINY["engine"]
    kw = {**dict(num_blocks=e["num_blocks"], block_size=e["block_size"],
                 max_batch=e["max_batch"], token_budget=e["token_budget"],
                 max_len=e["max_len"], pallas=False), **kw}
    return PagedServingEngine(cfg, params, **kw)


# ---- the model ---------------------------------------------------------------

def test_kimi_config_carries_the_latent_plan_and_the_share():
    cfg = D.kimi_config(TINY, jnp.bfloat16)
    assert [(s.attn, s.heads, s.ffn) for s in cfg.layer_plan] == [
        ("latent", 8, "dense"), ("latent", 8, "sparse"),
        ("latent", 8, "sparse")]
    ls = cfg.one_latent()
    assert ls.width == 48 and cfg.rope_dim == ls.qk_rope_head_dim == 8
    assert {s.latent for s in cfg.layer_plan} == {ls}
    assert (cfg.num_experts, cfg.held, cfg.top_k, cfg.router_bias) == (
        16, (4, 4), 2, True)
    m = 0.1 * np.log(8) + 1
    assert cfg.score_scale == pytest.approx(24 ** -0.5 * m * m)
    rope = cfg.layer_plan[0].rope
    assert (rope.yarn_factor, rope.yarn_original, rope.attention_factor) == (
        8.0, 32, 1.0)
    params = jax.eval_shape(lambda k: L.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    sparse = params["blocks"][1]
    assert sparse["router"].shape == (2, 64, 16)        # over all experts
    assert sparse["router_bias"].shape == (2, 16)
    assert sparse["w1"].shape == (2, 4, 64, 32)         # the held ones
    assert sparse["wkva"].shape == (2, 64, 48)
    assert "wq" not in sparse and "wk" not in sparse


def test_counts_at_the_published_config():
    """1.026 T parameters, 32.9 B of them active a token with the
    embedding's row counted as the issue's arithmetic counts it
    (`num_active_params` itself leaves a lookup out: 31.7 B)."""
    with open(os.path.join(HERE, "..", "benchmark", "configs",
                           "kimi-k2.6-serve.json")) as f:
        file = json.load(f)
    whole = {**file, **file["published"],
             "n_routed_experts": file["router_width"]}
    cfg = D.kimi_config(whole, jnp.bfloat16)
    assert cfg.experts_held == () and cfg.num_layers == 61
    assert cfg.num_params() == 1_026_408_232_448
    assert cfg.num_active_params() == 31_686_066_176
    assert round((cfg.num_active_params()
                  + cfg.vocab_size * cfg.hidden_size) / 1e9, 1) == 32.9
    # the chip's share, as the configuration's file cuts it
    cut = D.kimi_config(file, jnp.bfloat16)
    held = jax.eval_shape(lambda k: L.init_params(cut, k),
                          jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(held))
    assert round(n * 2 / 1e9, 2) == 8.35


@pytest.mark.parametrize("held", ["share", "every_expert"])
def test_forward_equals_the_reference_on_logits(tiny, held):
    if held == "share":
        cfg, params, file = *tiny, TINY
    else:
        file = {**TINY, "n_routed_experts": TINY["router_width"]}
        cfg, params = make(file)
        assert cfg.experts_held == ()
    tokens = prompt_of(100, seed=11)
    ref = reference_logits(params, tokens, file)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(L.forward(params, jnp.asarray(tokens)[None], cfg)[0])
    assert np.abs(got - ref).max() < 1e-4 * np.abs(ref).max()


@pytest.mark.parametrize("fault", FAULTS)
def test_a_seeded_fault_moves_forward_and_the_engine_off_the_reference(
        tiny, fault):
    """The negative controls: the reference with one piece computed
    wrongly is another model, and both `forward`'s logits and the engine's
    tokens (judged as the cell's check judges them) show it."""
    cfg, params = tiny
    tokens = prompt_of(100, seed=11)
    bad = reference_logits(params, tokens, fault=fault)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(L.forward(params, jnp.asarray(tokens)[None], cfg)[0])
    assert np.abs(got - bad).max() > 1e-2 * np.abs(bad).max()
    eng = engine(cfg, params)
    prompts = [prompt_of(n, seed=n) for n in (70, 55, 41)]
    rids = [eng.submit(p, max_new_tokens=24) for p in prompts]
    done = {d.rid: d.output_tokens for d in eng.run()}
    shares = []
    for rid, p in zip(rids, prompts):
        seq = p + done[rid]
        at = np.arange(len(p) - 1, len(seq) - 1)
        sound = reference_logits(params, seq + [0] * (WIDTH - len(seq)))[at]
        wrong = reference_logits(params, seq + [0] * (WIDTH - len(seq)),
                                 fault=fault)[at]
        assert agreement.judge(sound, done[rid])[0] == 1.0
        shares.append(agreement.judge(wrong, done[rid])[0])
    assert min(shares) < 1.0


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The share test: the routed parts that the four shares (4 of 16
    experts each) give, with the shared expert counted once, equal the
    uncut reference layer."""
    whole_file = {**TINY, "n_routed_experts": TINY["router_width"]}
    cfg, params = make(whole_file)
    lp = {n: w[0] for n, w in params["blocks"][1].items()}
    h = jax.random.normal(jax.random.PRNGKey(5), (40, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(R.sparse_ffn(
            h, lp, top_k=2, router_scale=cfg.router_scale, held=None))
        shared = np.asarray(L.ffn(h, {"w1": lp["ws1"], "w3": lp["ws3"],
                                      "w2": lp["ws2"]}))
        total, pairs = shared.copy(), 0
        for first in range(0, 16, 4):
            part = dataclasses.replace(cfg, experts_held=(first, 4))
            mine = {**lp, **{n: lp[n][first:first + 4]
                             for n in ("w1", "w3", "w2")}}
            y, load = L.routed_ffn_load(h, mine, part)
            total += np.asarray(y) - shared
            pairs += int(load.sum())
            ref = np.asarray(R.sparse_ffn(
                h, mine, top_k=2, router_scale=cfg.router_scale,
                held=(first, 4)))
            assert np.abs(np.asarray(y) - ref).max() < 1e-5
    assert pairs == 40 * 2              # every pair is someone's
    assert np.abs(total - want).max() < 1e-5 * max(1, np.abs(want).max())


@pytest.mark.parametrize("form", ["dense_einsum", "sorted_gmm"])
def test_a_share_computes_its_own_pairs_in_both_expert_forms(
        tiny, form, monkeypatch):
    """Both forms of `routed_ffn_load` under a share: the load counts the
    reference's pairs on held experts, padding rows are zero, and a row
    none of whose experts is held is the shared expert alone."""
    cfg, params = tiny
    monkeypatch.setattr(L, "expert_form", lambda cfg: form)
    lp = {n: w[0] for n, w in params["blocks"][1].items()}
    h = jax.random.normal(jax.random.PRNGKey(6), (48, 64), jnp.float32)
    valid = jnp.arange(48) < 41
    y, load = L.routed_ffn_load(h, lp, cfg, valid)
    chosen = np.asarray(R.chosen_experts(h, lp, 2))[:41]
    mine = (chosen >= 4) & (chosen < 8)
    assert np.array_equal(np.asarray(load),
                          [(chosen == e).sum() for e in range(4, 8)])
    assert int(load.sum()) == mine.sum() and 0 < mine.sum() < 82
    assert not np.any(np.asarray(y)[41:])
    shared = np.asarray(L.ffn(h, {"w1": lp["ws1"], "w3": lp["ws3"],
                                  "w2": lp["ws2"]}))
    alone = ~mine.any(axis=-1)
    assert alone.any()
    assert np.array_equal(np.asarray(y)[:41][alone], shared[:41][alone])
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(R.sparse_ffn(h[:41], lp, top_k=2,
                                      router_scale=cfg.router_scale,
                                      held=(4, 4)))
    assert np.abs(np.asarray(y)[:41] - ref).max() < 1e-4


# ---- the compact form of a held share ----------------------------------------

# 4 of 64 experts: a sixteenth, small enough that the held pairs get fewer
# places than there are pairs (at 4 of 16, and at these row counts, they get
# them all)
SMALL = {**TINY, "router_width": 64, "held_experts_first": 20}


@pytest.fixture(scope="module")
def small_share():
    """No selection bias: `sharpened`'s is the same for every row and
    larger than the spread of the scores, so that all rows would choose the
    same few experts and none of the held four."""
    cfg, params = make(SMALL, seed=2)
    return cfg, to_the_held(params, by=0.0)


def to_the_held(params, by=100.0):
    """`params` with a selection bias that sends every row's two experts to
    the four held ones (experts 20-23), so that a launch's held pairs are
    two a valid row."""
    dense, sparse = params["blocks"]
    bias = jnp.zeros_like(sparse["router_bias"]).at[:, 20:24].set(by)
    return {**params, "blocks": (dense, {**sparse, "router_bias": bias})}


def sorted_ffn(cfg, lp, h, valid):
    """The sorted form of one sparse layer on rows h, in the kernel's
    interpreter: (y, load, its program's text)."""
    fn = lambda h: L.routed_ffn_load(h, lp, cfg, valid)
    y, load = fn(h)
    return np.asarray(y), np.asarray(load), str(jax.make_jaxpr(fn)(h))


def conditionals(cfg, lp, h, valid):
    """How many `cond`s of the layer's program choose between launches of
    the grouped-matmul kernel (the kernel's own body, in the interpreter,
    holds `cond`s of its own: not descended into)."""
    from jax._src import core

    opaque = ("pallas_call",)
    closed = jax.make_jaxpr(lambda h: L.routed_ffn_load(h, lp, cfg, valid))(h)
    return sum(
        1 for eqn in _eqns(closed.jaxpr, opaque)
        if eqn.primitive.name == "cond" and any(
            e.primitive.name == "pallas_call"
            for sub in core.jaxprs_in_params(eqn.params)
            for e in _eqns(sub, opaque)))


@pytest.mark.parametrize("rows, live, slots", [(200, 190, 128), (72, 72, 128),
                                               (300, 263, 256)])
def test_the_compact_form_equals_the_whole_form_and_the_reference(
        small_share, rows, live, slots, monkeypatch):
    """Under a share small enough (4 of 64) the held pairs of `rows` rows,
    no multiple of `GMM_ROWS`, get `slots` places of rows x 2: the compact
    form equals the whole form (forced by places for every pair: no factor
    reaches them, the rule stops at half) and the reference, padding rows
    are exactly zero and a row with no held pair is the shared expert
    alone, bit for bit."""
    cfg, params = small_share
    monkeypatch.setattr(L, "expert_form", lambda cfg: "sorted_gmm")
    lp = {n: w[0] for n, w in params["blocks"][1].items()}
    h = jax.random.normal(jax.random.PRNGKey(rows), (rows, 64), jnp.float32)
    valid = jnp.arange(rows) < live
    assert L.held_pair_slots(rows, cfg) == slots < rows * 2
    y, load, text = sorted_ffn(cfg, lp, h, valid)
    assert 0 < load.sum() <= slots
    assert conditionals(cfg, lp, h, valid) == 1
    monkeypatch.setattr(L, "held_pair_slots",
                        lambda rows, cfg: rows * cfg.top_k)
    whole, load_whole, text_whole = sorted_ffn(cfg, lp, h, valid)
    assert conditionals(cfg, lp, h, valid) == 0     # one form: no choice
    # the [T, C] weights that add C rows into T: in the one, not the other
    combine = f"f32[{rows},{slots}]"
    assert combine in text and combine not in text_whole
    assert np.array_equal(load, load_whole)
    assert np.abs(y - whole).max() < 1e-5
    assert not np.any(y[live:])
    picked = np.asarray(R.chosen_experts(h, lp, 2))[:live]
    mine = (picked >= 20) & (picked < 24)
    assert int(load.sum()) == mine.sum()
    shared = np.asarray(L.ffn(h, {"w1": lp["ws1"], "w3": lp["ws3"],
                                  "w2": lp["ws2"]}))
    alone = ~mine.any(axis=-1)
    assert 0 < alone.sum() < live
    assert np.array_equal(y[:live][alone], shared[:live][alone])
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(R.sparse_ffn(h[:live], lp, top_k=2,
                                      router_scale=cfg.router_scale,
                                      held=(20, 4)))
    assert np.abs(y[:live] - ref).max() < 1e-4


@pytest.mark.parametrize("live, over", [(200, True), (65, True), (64, False)])
def test_held_pairs_past_their_places_take_the_whole_form(
        small_share, live, over, monkeypatch):
    """A selection bias that sends every row to held experts: 2 x `live`
    pairs against 128 places of 400. Past them the launch takes the whole
    form and drops nothing; either way the output is the reference's."""
    cfg, params = small_share
    monkeypatch.setattr(L, "expert_form", lambda cfg: "sorted_gmm")
    lp = {n: w[0] for n, w in to_the_held(params)["blocks"][1].items()}
    h = jax.random.normal(jax.random.PRNGKey(live), (200, 64), jnp.float32)
    y, load, _ = sorted_ffn(cfg, lp, h, jnp.arange(200) < live)
    assert load.sum() == 2 * live
    assert (load.sum() > L.held_pair_slots(200, cfg)) == over
    assert not np.any(y[live:])
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(R.sparse_ffn(h[:live], lp, top_k=2,
                                      router_scale=cfg.router_scale,
                                      held=(20, 4)))
    assert np.abs(y[:live] - ref).max() < 1e-4 * max(1, np.abs(ref).max())


@pytest.mark.parametrize("rows, share, top_k, slots", [
    (1024, (60, 12, 384), 8, 1024),     # the cell's chunk tick: 256 even
    (64, (60, 12, 384), 8, 128),        # its decode tick: 16 even, one tile
    (1024, (), 8, 8192),                # every expert held: every pair
    (48, (4, 4, 16), 2, 96),            # the tiny fixture: never above all
    (200, (20, 4, 64), 2, 128),
    (1000, (0, 1, 384), 8, 128),        # 21 even: 84 in one tile
    (16384, (0, 16, 64), 8, 65536),     # Mellum2's launch: half, not all
    (2048, (64, 32, 256), 8, 8192),     # dots3's chunk: 4 x 2,048 is half
    (64, (64, 32, 256), 8, 256),        # a tick of 64 rows on that share
    (100, (0, 16, 64), 2, 128),         # half is 100: up to a whole tile
    (300, (0, 16, 64), 2, 384)])        # half is 300: three tiles of 600
def test_the_places_follow_from_rows_top_k_and_the_share(rows, share, top_k,
                                                         slots):
    cfg = L.LlamaConfig(num_experts=share[2] if share else 64, top_k=top_k,
                        experts_held=share[:2])
    assert L.held_pair_slots(rows, cfg) == slots
    if not share:
        assert slots == rows * top_k


@pytest.mark.parametrize("biased", [True, False])
def test_the_engine_counts_the_launches_that_took_the_whole_form(
        small_share, biased, monkeypatch, tmp_path):
    """`moe_compact_overflow` against a hand count, in `engine.stats` and
    on the step spans: chunks of 256 rows have 128 places a sparse layer
    (of two), a decode tick of 4 rows holds all 8 pairs. With every row
    sent to held experts the prompt of 150 has 300 held pairs and both its
    sparse layers take the whole form, the prompt of 40 has 80 and none
    does; with the seeded router none ever does. The tokens are the
    reference's either way."""
    cfg, params = small_share
    if biased:
        params = to_the_held(params)
    monkeypatch.setattr(L, "expert_form", lambda cfg: "sorted_gmm")
    eng = engine(cfg, params, token_budget=256)
    eng._next_is_determined = lambda cur: False     # a tick a step
    prompts = [prompt_of(150, seed=5), prompt_of(40, seed=6)]
    done = []

    def drive():
        for p in prompts:
            eng.submit(p, max_new_tokens=3)
            done.extend(eng.run())

    spans, _ = _profiled(str(tmp_path), drive)
    steps = [s[3] for s in spans
             if s[0] == "ptpu.serve.step" and "batch" in s[3]]
    assert L.held_pair_slots(256, cfg) == 128 and L.held_pair_slots(4, cfg) == 8
    want = [2 * biased, 0, 0, 0, 0, 0]      # a chunk tick, two decode ticks
    assert [f["moe_compact_overflow"] for f in steps] == want
    assert eng.stats["moe_compact_overflow"] == sum(want)
    if biased:
        assert [f["moe_pairs_held"] for f in steps] == [
            2 * 2 * n for n in (150, 1, 1, 40, 1, 1)]
    with jax.default_matmul_precision("highest"):
        for d, p in zip(done, prompts):
            assert d.output_tokens == R.generate(
                params, p, 3, 192, **R.model_kw(SMALL))[0]


# ---- the engine --------------------------------------------------------------

@pytest.mark.parametrize("pallas", [False, True])
def test_engine_equals_the_reference_through_the_latent_pool(tiny, pallas):
    """Chunked prefill (chunks of 32 over prompts of 70 and 33), then
    decode, two sequences in different phases, over one pool and no value
    pool; the counters equal hand counts."""
    cfg, params = tiny
    eng = engine(cfg, params, pallas=pallas)
    assert eng._value_cache is None
    assert eng._key_cache.shape == (3, 96, 1, 8, PL.padded_width(48))
    prompts = [prompt_of(70, seed=3), prompt_of(33, seed=4)]
    news = [20, 30]
    rids = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    done = {d.rid: d.output_tokens for d in eng.run()}
    for rid, p, n in zip(rids, prompts, news):
        assert done[rid] == reference_tokens(params, p, n)
    st = eng.engine_stats
    assert eng.blocks.num_allocated() == 0 and st["latent_pages_live"] > 0
    assert st["prefix_cache"] if "prefix_cache" in st else True
    # a row at position p sees p + 1 keys in each of 3 layers; the rows
    # computed are positions 0 .. prompt + new - 2 of each request (a tick
    # launched ahead behind a sequence's last token computes a void row,
    # counted apart)
    rows = sum(p + n - 1 for p, n in zip((70, 33), news))
    void = st["ahead_void_rows"]
    assert st["tokens_computed"] - void == rows
    pairs = sum((p + n - 1) * (p + n) // 2 for p, n in zip((70, 33), news))
    assert st["attn_pairs_latent"] >= 3 * pairs
    assert st["moe_pairs"] == st["tokens_computed"] * 2
    assert 0 < st["moe_pairs_held"] < 2 * st["moe_pairs"]


def test_counters_equal_hand_counts_for_one_request(tiny):
    """One request alone, so that every tick is known: a prompt of 37 in
    chunks of 32 and 5, then 7 decode rows. `attn_keys_latent` is the sum
    over ticks of the keys the tick's rows see (past + this), times the
    layers; `moe_pairs_held` with every expert held is every pair of both
    sparse layers."""
    file = {**TINY, "held_experts_first": 0, "n_routed_experts": 15}
    cfg = dataclasses.replace(D.kimi_config(file, jnp.float32),
                              dtype=jnp.float32, experts_held=(0, 16))
    params = sharpened(L.init_params(cfg, jax.random.PRNGKey(0)))
    eng = engine(cfg, params)
    eng._next_is_determined = lambda cur: False     # no void row
    eng.submit(prompt_of(37, seed=2), max_new_tokens=8)
    eng.run()
    st = eng.stats
    keys = 32 + 37 + sum(range(38, 45))
    pairs = 37 * 38 // 2 + sum(range(38, 45))
    assert (st["steps"], st["tokens_computed"]) == (9, 44)
    assert st["attn_keys_latent"] == 3 * keys
    assert st["attn_pairs_latent"] == 3 * pairs
    assert st["moe_pairs"] == 44 * 2
    assert st["moe_pairs_held"] == 2 * 44 * 2        # two sparse layers
    assert st["moe_experts_hit"] <= 9 * 2 * 16


@pytest.mark.parametrize("pallas", [False, True])
def test_a_preempted_sequence_resumes_through_the_latent_pool(tiny, pallas):
    cfg, params = tiny
    eng = engine(cfg, params, max_batch=3, num_blocks=16, pallas=pallas)
    prompts = [prompt_of(n, seed=n) for n in (60, 50, 44)]
    rids = [eng.submit(p, max_new_tokens=16) for p in prompts]
    done = {d.rid: d.output_tokens for d in eng.run()}
    assert eng.engine_stats["preemptions"] >= 1
    for rid, p in zip(rids, prompts):
        assert done[rid] == reference_tokens(params, p, 16)
    assert eng.blocks.num_allocated() == 0


@pytest.mark.parametrize("pallas", [False, True])
def test_a_repeated_prefix_is_served_from_the_latent_pages(tiny, pallas):
    """The pages are of one kind and one lifetime, so the prefix cache
    works as for a uniform model: a second prompt over the first's 40
    leading ids maps its whole pages, and a third that diverges inside a
    shared page gets a copy (copy-on-write over one pool)."""
    cfg, params = tiny
    eng = engine(cfg, params, pallas=pallas)
    head = prompt_of(40, seed=9)
    prompts = [head + prompt_of(9, seed=s) for s in (1, 2)] + [head[:36]
                                                               + [7, 8, 9]]
    for p in prompts:
        eng.submit(p, max_new_tokens=6)
        assert eng.run()[0].output_tokens == reference_tokens(params, p, 6)
    st = eng.engine_stats
    assert st["blocks_prefix_hit_tokens"] >= 40 + 32


def test_page_hand_off_is_refused_for_a_latent_plan(tiny):
    """Explicit, as for every plan: pages are handed off by prefix hash
    over one uniform stack's pool."""
    cfg, params = tiny
    eng = engine(cfg, params)
    with pytest.raises(NotImplementedError, match="layer plan"):
        eng.extract_pages(prompt_of(40))
    with pytest.raises(NotImplementedError, match="layer plan"):
        eng.ingest_pages({})


@pytest.mark.parametrize("what, kw", [
    ("mixed with full attention", dict(layer_plan=(
        L.LayerSpec("latent", 8), L.LayerSpec("full", 8)), num_layers=2)),
    ("more than one key row", dict(num_kv_heads=2)),
    # neither on its spec (`LayerSpec.latent`) nor among the config's own
    ("a latent layer without its widths", dict(
        kv_lora_rank=0, num_layers=1,
        layer_plan=(L.LayerSpec("latent", 8),))),
    ("a share outside the experts", dict(experts_held=(14, 4))),
])
def test_what_a_latent_config_refuses_raises_at_construction(tiny, what, kw):
    cfg, _ = tiny
    with pytest.raises((ValueError, NotImplementedError),
                       match="LayerSpec.latent" if "widths" in what
                       else None):
        dataclasses.replace(cfg, **kw)


def test_a_uniform_block_body_refuses_a_share_of_the_experts():
    cfg = L.LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=16,
                        num_layers=1, num_heads=2, num_kv_heads=2,
                        num_experts=8, experts_held=(2, 4))
    with pytest.raises(NotImplementedError, match="share"):
        L.require_uniform(cfg, "a trainer")


# ---- the launches ------------------------------------------------------------

@pytest.mark.parametrize("decode", [True, False])
def test_the_attention_op_equals_dense_attention_in_the_latent_space(decode):
    """The cell's direct check of the layer's attention op (the page
    write, then the decode launch, or the decode launch on the one-row
    sequences beside the mixed walk on the chunk), at the fixture's shapes
    in float32 (interpreter): every row within a hundredth of the check's
    tolerance and the pool holding the new rows bit for bit; a scale
    without m^2 fails it."""
    from benchmark.lib import agreement_blockdiff
    case = D.attention_case(TINY, 7, jnp.float32, decode)
    out, ref, written = D.attention_outputs(TINY, case, decode)
    assert out.shape == ref.shape == (case["rows"].shape[0], 8 * 16)
    good, worst = agreement_blockdiff.judge_attention(out, ref)
    assert written and good and worst < 0.01
    bad, _, _ = D.attention_outputs(TINY, case, decode, scale=24 ** -0.5)
    assert not agreement_blockdiff.judge_attention(bad, ref)[0]


def test_pages_kept_in_fewer_bits_fail_the_direct_check(monkeypatch):
    """What a token cannot see: cache rows rounded to 8 bits on their way
    into the pool leave the attention within the tolerance's reach, and the
    pool no longer holds the rows bit for bit."""
    from paddle_tpu.ops.kernels import serving_attention as SA
    write = PL.write_latent_pages
    monkeypatch.setattr(PL, "write_latent_pages", lambda pool, layer, pages,
                        lo, hi, new, **kw: write(
                            pool, layer, pages, lo, hi,
                            new.astype(jnp.float8_e4m3fn).astype(new.dtype),
                            **kw))
    assert SA is not None
    case = D.attention_case(TINY, 7, jnp.bfloat16, True)
    _, _, written = D.attention_outputs(TINY, case, True)
    assert not written
