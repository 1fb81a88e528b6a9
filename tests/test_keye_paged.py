"""Keye-VL-2.0-shaped models (a uniform Qwen3-MoE stack whose every layer
attends over the heads' OWN keys and values under a learned sparse index:
index keys in a third page array under the one block table, the index
query from the layer's normed input, a chip's share of the routed experts)
through `llama.forward` and `PagedServingEngine` with the prefix cache on,
against the plain float32 reference `benchmark/lib/reference_keye.py`.

Everything here is float32 at a tiny size whose ratios stay the model's
(the benchmark's fixture `tiny-keye.json`: 2 layers; d 64; 4 query heads
over 2 key-value heads of 16; 2 index heads of 8 keeping 8 keys; 16 experts
of 32 of which 4 are held, four a row; vocabulary 512), with contexts of 40
and more, so that a row keeps a fifth of its keys.
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import closed_loop_sparse_sessions as D
from benchmark.lib import agreement_blockdiff
from benchmark.lib import reference_keye as R
from paddle_tpu.inference.serving import PagedServingEngine
from paddle_tpu.models import llama as L
from paddle_tpu.ops.kernels import serving_attention as SA
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.ops.pallas import paged_attention_latent as PL

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "benchmark", "tests", "fixtures",
                       "configs", "tiny-keye.json")) as f:
    TINY = json.load(f)
WIDTH = 128             # the reference's padded length (one compile)


def sharpened(params):
    """A router and a head sharp enough that top-k sets and argmaxes
    differ, queries and an index large enough that neither the scores nor
    the selection are flat, norms and an index-key bias that are not one
    and zero."""
    b = params["blocks"]
    key = jax.random.PRNGKey(9)
    off = lambda i, n: b[n] + 0.3 * jax.random.normal(
        jax.random.fold_in(key, i), b[n].shape)
    blocks = {**b, "wq": b["wq"] * 20.0, "router": b["router"] * 20.0,
              "w2": b["w2"] * 8.0, "wiq": b["wiq"] * 30.0,
              "wik": b["wik"] * 30.0, "wiw": b["wiw"] * 30.0,
              **{n: off(i, n) for i, n in enumerate(
                  ("q_norm", "k_norm", "ik_norm", "ik_bias"))}}
    return {**params, "blocks": blocks, "lm_head": params["lm_head"] * 8.0}


def make(file=TINY, seed=0):
    cfg = dataclasses.replace(D.keye_config(file, jnp.float32),
                              dtype=jnp.float32)
    init = jax.jit(lambda key: L.init_params(cfg, key))
    return cfg, sharpened(init(jax.random.PRNGKey(seed)))


@pytest.fixture(scope="module")
def tiny():
    return make()


@pytest.fixture(autouse=True)
def small_key_blocks(monkeypatch):
    """Key blocks of 32 keys in the index's launches and the masked decode
    walk, so that a table of 128 keys is four blocks (the walks cross
    block edges in every engine test here)."""
    for name in ("_INDEX_KEYS", "_INDEX_ROW_KEYS"):
        monkeypatch.setattr(PL, name, 32)
    monkeypatch.setattr(PA, "_MASKED_DECODE_KEYS", 32)


def engine(cfg, params, **kw):
    e = {k: TINY["engine"][k] for k in ("num_blocks", "block_size",
                                        "max_batch", "token_budget",
                                        "max_len")}
    return PagedServingEngine(cfg, params, **{**e, "pallas": False, **kw})


# the stock read; the kernels' path as it is (the crossing of these widths
# lies past every context here: a selecting decode row walks); the kernels'
# path with every selecting decode row GATHERING, as most of the cell's do
FORMS = [False, True, "gather"]


def kernels(monkeypatch, form) -> bool:
    """`engine`'s `pallas` of one of `FORMS`."""
    if form == "gather":
        monkeypatch.setattr(SA, "_HEADS_GATHER_POS_S", 0.0)
    return bool(form)


def rows_gathered(eng) -> int:
    """The rows that selected and did not walk, a row a layer."""
    st = eng.stats
    return (eng.cfg.num_layers * st["tokens_computed"]
            - st["sparse_rows_dense"] - st["sparse_rows_walked"])


def prompt_of(n, seed=1):
    return np.random.default_rng(seed).integers(1, 500, n).tolist()


def reference_tokens(params, prompt, new, **fault):
    with jax.default_matmul_precision("highest"):
        return R.generate(params, prompt, new, WIDTH, **R.model_kw(TINY),
                          **fault)[0]


def reference_logits(params, tokens, file=TINY, **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(R.logits_at(
            params, jnp.asarray(tokens), jnp.arange(len(tokens)),
            **R.model_kw(file), **kw))


def test_keye_config_carries_the_index_on_a_uniform_stack():
    cfg, params = make()
    assert cfg.layer_plan == () and isinstance(params["blocks"], dict)
    ix = L.IndexSpec(heads=2, head_dim=8, topk=8)
    assert cfg.index == ix and cfg.kinds == (L.LayerSpec(
        "full", 4, L.RopeSpec(theta=10000.0), "sparse", index=ix),)
    assert cfg.kinds[0].sparse_index == ix
    assert cfg.index_rope_width(cfg.kinds[0]) == 8
    assert (cfg.experts_held, cfg.num_experts, cfg.top_k) == ((4, 4), 16, 4)
    assert params["blocks"]["wiq"].shape == (2, 64, 2 * 8)     # from h
    assert params["blocks"]["w1"].shape == (2, 4, 64, 32)
    # the published file: every width as published, the index over heads'
    # own keys, a whole layer's parameters
    with open(os.path.join(HERE, "..", "benchmark", "configs",
                           "keye-vl2-30b-a3b-serve.json")) as f:
        file = json.load(f)
    big = D.keye_config(file, jnp.bfloat16)
    assert big.index == L.IndexSpec(16, 64, 2048)
    assert (big.hidden_size, big.num_heads, big.num_kv_heads, big.head_dim,
            big.intermediate_size, big.num_experts, big.top_k,
            big.experts_held) == (2048, 32, 4, 128, 768, 128, 8, (32, 16))
    per_layer, _ = big._layer_params(big.kinds[0])
    # attention 18,874,368 + index 2,260,992 + router 262,144 + 128
    # experts of 4,718,592, and the norms
    assert per_layer == 625_377_280 + 2 * 2048 + 2 * 128 + 2 * 64
    with pytest.raises(NotImplementedError, match="causal full-attention"):
        dataclasses.replace(cfg, block_length=4)
    with pytest.raises(ValueError, match="states its layers' indexes"):
        dataclasses.replace(cfg, layer_plan=cfg.layers,
                            dense_intermediate_size=32)


@pytest.mark.parametrize("held", ["share", "every_expert"])
def test_forward_equals_the_reference_on_logits(tiny, held):
    """Rows below `topk` see every key, rows above select: one prompt of
    100 holds both."""
    if held == "share":
        cfg, params, file = *tiny, TINY
    else:
        file = {**TINY, "num_experts": TINY["router_width"]}
        cfg, params = make(file)
        assert cfg.experts_held == ()
    tokens = prompt_of(100, seed=11)
    ref = reference_logits(params, tokens, file)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(L.forward(params, jnp.asarray(tokens)[None], cfg)[0])
    assert np.abs(got - ref).max() < 1e-4 * np.abs(ref).max()


def test_tied_scores_go_to_the_lower_position(tiny):
    """No head weighs anything: every score is 0 and a row keeps its first
    8 keys, in the program's threshold search as in the reference's stable
    sort."""
    cfg, params = tiny
    flat = {**params, "blocks": {**params["blocks"],
                                 "wiw": params["blocks"]["wiw"] * 0.0}}
    tokens = prompt_of(60, seed=3)
    ref = reference_logits(flat, tokens)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(L.forward(flat, jnp.asarray(tokens)[None], cfg)[0])
        seen = R.forward(flat, jnp.asarray(tokens), jnp.arange(60),
                         **R.model_kw(TINY))[2][0]
    assert np.abs(got - ref).max() < 1e-4 * np.abs(ref).max()
    assert np.array_equal(np.nonzero(np.asarray(seen)[50])[0], np.arange(8))


@pytest.mark.parametrize("fault", R.FAULTS)
def test_a_seeded_mistake_moves_the_logits(tiny, fault):
    """Leaving out the index, a selected key, the key's bias, the query's
    rope or the scores' float32 operands is another model."""
    cfg, params = tiny
    tokens = prompt_of(100, seed=11)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(L.forward(params, jnp.asarray(tokens)[None], cfg)[0])
    wrong = reference_logits(params, tokens, fault=fault)
    assert np.abs(got - wrong).max() > 1e-2 * np.abs(wrong).max()


@pytest.mark.parametrize("form", FORMS)
def test_chunked_prefill_and_decode_equal_the_reference(tiny, monkeypatch,
                                                        form):
    cfg, params = tiny
    pallas = kernels(monkeypatch, form)
    eng = engine(cfg, params, pallas=pallas)
    assert eng._index_cache.shape == (2, 64, 1, 8, 128)     # whole lanes
    assert eng._value_cache.shape == eng._key_cache.shape == (2, 64, 2, 8, 16)
    assert "prefix_cache" not in eng.engine_stats            # it is on
    prompts = [prompt_of(n, seed=n) for n in (70, 55, 6)]
    rids = [eng.submit(p, max_new_tokens=16) for p in prompts]
    done = {d.rid: d.output_tokens for d in eng.run()}
    for rid, p in zip(rids, prompts):
        assert done[rid] == reference_tokens(params, p, 16)
    st = eng.stats
    assert st["index_pairs"] > st["sparse_pairs_selected"] > 0
    assert st["sparse_rows_dense"] > 0 and st["index_pages_live"] > 0
    assert (st["sparse_rows_walked"] > 0) == pallas
    if pallas:
        # every decode row that holds more than 8 keys, a row a layer: 15
        # of each longer request, 13 of the shortest
        assert rows_gathered(eng) == (2 * 43 if form == "gather" else 0)
    assert st["moe_pairs_held"] > 0


@pytest.mark.parametrize("form", FORMS)
def test_a_prefix_hit_turn_and_a_copied_page_equal_the_reference(
        tiny, monkeypatch, form):
    """A session's next turn selects over cached pages' index keys, which
    another request wrote; a prompt that leaves a cached page half way
    copies it, with its index keys."""
    cfg, params = tiny
    eng = engine(cfg, params, pallas=kernels(monkeypatch, form))
    first = prompt_of(70, seed=5)
    rid = eng.submit(first, max_new_tokens=12)
    out = {d.rid: d.output_tokens for d in eng.run()}[rid]
    turn = first + out + prompt_of(10, seed=6)
    half = first[:60] + prompt_of(9, seed=7)
    for prompt, hit in ((turn, 80), (half, 60)):
        before = dict(eng.blocks.stats), eng.stats["cow_block_copies"]
        rid = eng.submit(prompt, max_new_tokens=12)
        got = {d.rid: d.output_tokens for d in eng.run()}[rid]
        assert got == reference_tokens(params, prompt, 12)
        assert (eng.blocks.stats["prefix_hit_tokens"]
                - before[0]["prefix_hit_tokens"]) == hit
    assert eng.stats["cow_block_copies"] - before[1] == 1


def test_a_copied_page_takes_its_index_keys_with_it(tiny):
    cfg, params = tiny
    eng = engine(cfg, params)
    rid = eng.submit(prompt_of(40, seed=2), max_new_tokens=2)
    eng.run()
    pools = (eng._key_cache, eng._value_cache, eng._index_cache)
    src = [np.asarray(p[:, 1]) for p in pools]
    assert all(np.any(s) for s in src)
    eng._copy_blocks([(1, 50)])
    for got, want in zip((eng._key_cache, eng._value_cache,
                          eng._index_cache), src):
        assert np.array_equal(np.asarray(got[:, 50]), want)
        assert np.array_equal(np.asarray(got[:, 1]), want)


@pytest.mark.parametrize("pallas", [False, True])
def test_a_preempted_sequence_resumes_through_the_three_pools(tiny, pallas):
    cfg, params = tiny
    eng = engine(cfg, params, max_batch=3, num_blocks=22, pallas=pallas)
    prompts = [prompt_of(n, seed=n) for n in (60, 50, 44)]
    rids = [eng.submit(p, max_new_tokens=16) for p in prompts]
    done = {d.rid: d.output_tokens for d in eng.run()}
    assert eng.engine_stats["preemptions"] >= 1
    for rid, p in zip(rids, prompts):
        assert done[rid] == reference_tokens(params, p, 16)
    assert eng.blocks.num_allocated() == 0


# -- the ops at a 64-wide index key (half a lane tile) -------------------

OPS = {**TINY, "head_dim": 16,
       "sa_config": {**TINY["sa_config"], "indexer_head_dim": 64}}


@pytest.mark.parametrize("form", ["walk", "gather"])
@pytest.mark.parametrize("tick", D.TICKS)
def test_the_sparse_reads_equal_dense_attention_over_the_selection(
        monkeypatch, tick, form):
    """`paged_index_select` at a 64-wide key (its pool's rows 128 lanes)
    selects the reference's sets, and the read under the selection, in
    either form a one-row sequence's can take (a chunk's rows walk),
    equals dense float32 attention over the same selected keys of the
    SECOND of two layers; the three pools hold the new rows bit for bit."""
    monkeypatch.setattr(SA, "_HEADS_GATHER_POS_S",
                        1e9 if form == "walk" else 0.0)
    case = D.op_case(OPS, 7, jnp.float32, tick)
    for name in ("key_pool", "value_pool", "index_pool"):   # a layer before
        case[name] = jnp.concatenate([case[name][:, ::-1], case[name]])
    res = D.op_outputs(OPS, case)
    assert all(res["pools_ok"].values()), res["pools_ok"]
    assert len(res["selection"]) and res["selection"].all()
    good, worst = agreement_blockdiff.judge_attention(res["out"], res["ref"])
    assert good and worst < 0.05, worst


def test_walk_and_gather_are_chosen_by_the_crossing(monkeypatch):
    assert SA.sparse_walk_keys_heads(4, 128, 2, 2048) == int(
        2048 * SA._HEADS_GATHER_POS_S * SA._HEADS_WALK_BYTES_S / 2048)
    # the host's count follows the same rule as the device's: a chunk's
    # rows always walk, a one-row sequence's up to the crossing
    cfg, params = make()
    eng = engine(cfg, params, pallas=True)
    past, this = np.array([40, 3, 50]), np.array([1, 5, 20])
    for const, rows in ((1e9, 21), (0.0, 20)):
        monkeypatch.setattr(SA, "_HEADS_GATHER_POS_S", const)
        keys = eng._plan_keys(past, this)
        assert keys["sparse_rows_walked"] == 2 * rows
        assert keys["sparse_rows_dense"] == 2 * 5
        assert keys["index_keys"] == 2 * (41 + 70)
        assert keys["index_pairs"] == 2 * (41 + 20 * 50 + 210)
        assert keys["sparse_pairs_selected"] == 2 * (8 + 20 * 8)


@pytest.mark.parametrize("pallas", [False, True, "decode"])
def test_a_selection_of_every_key_is_the_dense_read(pallas):
    case = D.op_case(OPS, 3, jnp.float32, "decode" if pallas == "decode"
                     else "turn")
    past, this, tables = case["past"], case["this"], case["tables"]
    cu = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(this)])
    tok = int(this.sum())
    every = jnp.full((tok, tables.shape[1] * 8 // 128, 4), 0xFFFFFFFF,
                     jnp.uint32)
    none = jnp.full((tok, 8), -1, jnp.int32)
    read = lambda select: SA.paged_layer_attention(
        case["qkv"], case["key_pool"], case["value_pool"], 0, past, this,
        cu, tables, use_pallas=pallas, select=select)[0]
    dense = read(None)
    # (the masked walk is what reads a selection on the kernels' path; the
    # positions go unread)
    sparse = read((none, none, jnp.ones((4,), bool), every))
    err = float(jnp.abs(dense - sparse).max())
    assert err == 0.0 if not pallas else err < 1e-5


# -- what is refused, what is counted, what is named ---------------------

@pytest.mark.parametrize("what, kw", [
    ("int8 pages", dict(quant_kv=True)),
    ("LoRA", dict(adapter_slots=2)),
    ("a draft model", dict(draft=(None, None))),
])
def test_what_was_never_judged_under_the_index_is_refused_once(tiny, what,
                                                               kw):
    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="sparse index"):
        engine(cfg, params, **kw)


def test_page_hand_off_and_adapters_are_refused(tiny):
    cfg, params = tiny
    eng = engine(cfg, params)
    for call in (lambda: eng.extract_pages(prompt_of(40)),
                 lambda: eng.ingest_pages({}),
                 lambda: eng.submit(prompt_of(9), adapter="a")):
        with pytest.raises(NotImplementedError, match="sparse index"):
            call()
    with pytest.raises(NotImplementedError, match="never judged"):
        SA.paged_layer_attention(
            jnp.zeros((4, 8 * 16)), jnp.zeros((1, 4, 2, 8, 16)),
            jnp.zeros((1, 4, 2, 8, 16)), 0, jnp.zeros((1,), jnp.int32),
            jnp.ones((1,), jnp.int32), jnp.array([0, 1]),
            jnp.zeros((1, 2), jnp.int32), window=4, select=(None,) * 4)


def test_the_page_accounting_counts_the_index_keys(tiny):
    cfg, params = tiny
    eng = engine(cfg, params)
    # keys and values 2 x 2 heads x 8 x 16, an index key's row 8 x 128
    # lanes, 4 B each, 2 layers
    assert eng.kv_page_bytes == 2 * 4 * (2 * 2 * 8 * 16 + 8 * 128)
    assert eng.blocks.bytes_total() >= 64 * eng.kv_page_bytes
    assert eng.engine_stats["kv_page_bytes"] == eng.kv_page_bytes


def test_the_engine_counts_the_index_blocks_that_come_in_one_copy(
        tiny, monkeypatch):
    """`index_blocks` / `index_blocks_run` on the kernels' path, the
    ticks' program stood in for (the counts are the host's, from the
    lengths and the block tables it plans with): key blocks of 32 keys =
    4 pages of 8, row tiles of 4 tokens. A prompt of 37 alone in a fresh
    pool gets pages 0..4 in one go: its first chunk's 8 work items fetch
    block 0 (pages 0-3, a run) each; the second chunk's two items blocks 0
    and 1, the 7 decode rows at positions 37..43 the same two, and block 1
    (page 4, then 5 as the row at 40 needs it, the rest unassigned) is no
    run; times the 2 index layers. The stock path counts nothing."""
    cfg, params = tiny
    monkeypatch.setattr(PL, "_INDEX_TOKENS", 4)
    eng = engine(cfg, params, pallas=True)
    eng._next_is_determined = lambda cur: False     # no void row
    monkeypatch.setattr(eng, "_build_step", lambda tok_pad, B, *rest: (
        lambda *args, index_cache: (
            jnp.zeros((B + len(eng._moe_fields),), jnp.int32), args[1],
            args[2], index_cache)))
    eng.submit(prompt_of(37, seed=2), max_new_tokens=8)
    eng.run()
    st = eng.stats
    assert st["index_keys_fetched"] == 2 * 32 * (8 + 2 * 2 + 7 * 2)
    assert st["index_blocks"] == 2 * (8 + 2 * 2 + 7 * 2)
    assert st["index_blocks_run"] == 2 * (8 + 2 + 7)
    keys = engine(cfg, params)._plan_keys(
        np.array([40]), np.array([1]), tables=np.arange(16)[None])
    assert (keys["index_blocks"], keys["index_blocks_run"]) == (0, 0)


def test_a_tick_with_few_rows_takes_the_eighth_of_the_budget(tiny):
    """Under the index a padded row costs `max_len` keys in the selection:
    a turn's few new rows run the small executable."""
    cfg, params = tiny
    assert engine(cfg, params)._row_pads == (32,)       # 4 < 2 x 4 slots
    eng = engine(cfg, params, token_budget=64, max_batch=4)
    assert eng._row_pads == (8, 64)
    eng.submit(prompt_of(70, seed=4), max_new_tokens=3)  # chunks 64 and 6
    done = eng.run()
    assert done[0].output_tokens == reference_tokens(
        params, prompt_of(70, seed=4), 3)
    assert {k[0] for k in eng._step_fns} == {8, 64}


def test_the_tick_runs_under_the_indexs_scopes(tiny):
    """The named scopes the per-layer metrics read, in the tick's program:
    the same names dots3-note's ops write."""
    cfg, params = tiny
    eng = engine(cfg, params)
    fn = eng._build_step(32, 4)
    B, mb = 4, eng.max_blocks_per_seq
    z = lambda *s: np.zeros(s, np.int32)
    text = fn.lower(
        eng.params, eng._key_cache, eng._value_cache, None, z(32), z(B, mb),
        z(B + 1), z(B), z(B), eng._rope_emb, np.ones((B,), np.float32),
        np.ones((B,), np.float32), np.zeros((B, 2), np.uint32),
        np.ones((B,), bool), (), None, None, eng._last_out,
        np.full((32,), -1, np.int32), index_cache=eng._index_cache
    ).as_text(debug_info=True)
    for scope in ("index_q", "index_k", "index_scores", "index_select",
                  "paged_attention_sparse", "cache_write", "router"):
        assert scope in text, scope


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The share test: the routed parts that the four shares (4 of 16
    experts each) give add up to the uncut reference layer (the model has
    no shared expert to count once)."""
    whole_file = {**TINY, "num_experts": TINY["router_width"]}
    cfg, params = make(whole_file)
    lp = {n: w[0] for n, w in params["blocks"].items()}
    h = jax.random.normal(jax.random.PRNGKey(5), (40, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(R.sparse_ffn(h, lp, top_k=4, held=None))
        total, pairs = np.zeros_like(want), 0
        for first in range(0, 16, 4):
            part = dataclasses.replace(cfg, experts_held=(first, 4))
            mine = {**lp, **{n: lp[n][first:first + 4]
                             for n in ("w1", "w3", "w2")}}
            y, load = L.routed_ffn_load(h, mine, part)
            total += np.asarray(y)
            pairs += int(load.sum())
            ref = np.asarray(R.sparse_ffn(h, mine, top_k=4,
                                          held=(first, 4)))
            assert np.abs(np.asarray(y) - ref).max() < 1e-5
    assert pairs == 40 * 4              # every pair is someone's
    assert np.abs(total - want).max() < 1e-5 * max(1, np.abs(want).max())
