"""dots3-note-shaped models (latent attention of TWO kinds in one model: one
under a learned sparse index over latent pages that carry an index key
beside each cache row, one under a window in a latent pool of its own
width; a head-wise gate on both; a leading dense layer; a chip's share of
the routed experts beside a shared one) through `llama.forward` and
`PagedServingEngine`, against the plain float32 reference
`benchmark/lib/reference_dots3.py`.

Everything here is float32 at a tiny size whose ratios stay the model's
(the benchmark's fixture `tiny-dots3.json`: 5 layers full, full, sliding x
3; d 64; full layers 8 heads of 16 + 8, ranks 48 and 40, a cache row of 48
values, 4 index heads of 16 keeping 8 keys; sliding layers 4 heads of 24 +
8, ranks 32 and 56, a cache row of 64 values, a window of 5; 16 experts of
32 of which 4 are held, two a row; vocabulary 512), with contexts of 40 and
more, so that a row keeps a fifth of its keys and the window has long
released its first pages.
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import closed_loop_serve_sparse_latent as D
from benchmark.lib import agreement, agreement_blockdiff
from benchmark.lib import reference_dots3 as R
from paddle_tpu.inference.serving import PagedServingEngine
from paddle_tpu.models import llama as L
from paddle_tpu.ops.kernels import serving_attention as SA
from paddle_tpu.ops.kernels import sparse_index as SI
from paddle_tpu.ops.pallas import paged_attention_latent as PL

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "benchmark", "tests", "fixtures",
                       "configs", "tiny-dots3.json")) as f:
    TINY = json.load(f)
WIDTH = 128             # the reference's padded length (one compile)


def sharpened(params):
    """A router and a head sharp enough that top-k sets and argmaxes
    differ, a selection bias that changes choices, queries, rope keys and
    an index large enough that neither the scores nor the selection are
    flat, an index-key bias that is not zero, and routed experts that weigh
    as much as the shared one."""
    def one(b):
        out = {**b, "wqb": b["wqb"] * 30.0, "wkva": b["wkva"] * 5.0,
               "wg": b["wg"] * 20.0}
        if "router" in b:
            out.update(router=b["router"] * 20.0,
                       router_bias=b["router_bias"] * 10.0, w2=b["w2"] * 8.0)
        if "wiq" in b:
            out.update(wiq=b["wiq"] * 30.0, wik=b["wik"] * 30.0,
                       wiw=b["wiw"] * 30.0, ik_bias=b["ik_bias"] + 0.5)
        return out
    return {**params, "blocks": tuple(map(one, params["blocks"])),
            "lm_head": params["lm_head"] * 8.0}


def make(file=TINY, seed=0):
    cfg = dataclasses.replace(D.dots3_config(file, jnp.float32),
                              dtype=jnp.float32)
    # (one compiled program, not an eager dispatch a leaf: half the time of
    # a fixture that every worker of the suite's run builds for itself)
    init = jax.jit(lambda key: L.init_params(cfg, key))
    return cfg, sharpened(init(jax.random.PRNGKey(seed)))


@pytest.fixture(scope="module")
def tiny():
    return make()


@pytest.fixture(autouse=True)
def index_key_tiles_of_four_pages(monkeypatch):
    """The index's two launches on key tiles of 32 keys, so that a table
    of 128 keys is four tiles (the walks cross tile edges in every engine
    test here) and a tile's page copies, which the kernels write out, are
    4 and not 16 in every interpreted program this module compiles."""
    for name in ("_INDEX_KEYS", "_INDEX_ROW_KEYS", "_DECODE_KEYS"):
        monkeypatch.setattr(PL, name, 32)


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
                 max_len=e["max_len"], pallas=False,
                 window_blocks=e["window_blocks"]), **kw}
    return PagedServingEngine(cfg, params, **kw)


# ---- the model ---------------------------------------------------------------

def test_a_plan_may_hold_two_latent_kinds_a_window_an_index_and_a_gate():
    """What `LlamaConfig` refused before this model: latent layers under
    an attention gate, of two kinds with their own widths, head counts and
    ropes, a window on one and an index on the other."""
    cfg = D.dots3_config(TINY, jnp.bfloat16)
    assert [(s.attn, s.heads, s.ffn) for s in cfg.layer_plan] == [
        ("latent", 8, "dense"), ("latent", 8, "sparse"),
        *[("latent", 4, "sparse")] * 3]
    assert cfg.attn_gate and len(cfg.kinds) == 3
    full, swa = cfg.layer_plan[1].latent, cfg.layer_plan[2].latent
    assert (full.width, full.window, full.index) == (
        48, 0, L.IndexSpec(heads=4, head_dim=16, topk=8))
    assert (swa.width, swa.window, swa.index) == (64, 5, None)
    assert full.q_scale == pytest.approx((64 / 48) ** 0.5)
    assert swa.kv_scale == pytest.approx((64 / 56) ** 0.5)
    assert (full.score_scale, swa.score_scale) == (24 ** -0.5, 32 ** -0.5)
    assert cfg.layer_plan[1].rope.theta != cfg.layer_plan[2].rope.theta
    with pytest.raises(ValueError, match="2 kinds"):
        cfg.one_latent()        # no widths "of the config": read the spec
    params = jax.eval_shape(lambda k: L.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    dense, sparse, window = params["blocks"]
    assert sparse["wiq"].shape == (1, 48, 4 * 16) and "wiq" not in window
    assert sparse["wkva"].shape == (1, 64, 48)
    assert window["wkva"].shape == (3, 64, 64)
    assert sparse["wg"].shape == (1, 64, 8) and window["wg"].shape == (3, 64, 4)
    assert sparse["w1"].shape == (1, 4, 64, 32)         # the held experts
    assert dense["w1"].shape == (1, 64, 96)


def test_counts_at_the_published_config():
    """279.55 B parameters, 15.48 B of them active a token (the head
    counted, the embedding's row lookup not), and the chip's share
    as the configuration's file cuts it: 4.087 B = 8.17 GB in bf16, of
    which full attention 134.68 M + index 9.37 M, sliding attention
    90.83 M, an expert 23.59 M, the dense FFN 212.34 M."""
    with open(os.path.join(HERE, "..", "benchmark", "configs",
                           "dots3-note-prev-serve.json")) as f:
        file = json.load(f)
    whole = {**file, **file["published"],
             "n_routed_experts": file["router_width"]}
    cfg = D.dots3_config(whole, jnp.bfloat16)
    assert cfg.experts_held == () and cfg.num_layers == 46
    assert sum(s.latent.index is not None for s in cfg.layer_plan) == 13
    assert round(cfg.num_params() / 1e9, 2) == 279.55
    assert round(cfg.num_active_params() / 1e9, 2) == 15.48
    cut = D.dots3_config(file, jnp.bfloat16)
    held = jax.eval_shape(lambda k: L.init_params(cut, k),
                          jax.random.PRNGKey(0))
    size = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    assert round(size(held) * 2 / 1e9, 2) == 8.17
    dense, sparse, window = held["blocks"]
    attn = ("wqa", "wqb", "wkva", "wkvb", "wo", "wg")
    index = ("wiq", "wik", "wiw")
    m = lambda stack, names: round(sum(
        int(np.prod(stack[n].shape[1:])) for n in names) / 1e6, 2)
    assert (m(sparse, attn), m(sparse, index), m(window, attn)) == (
        134.68, 9.37, 90.83)
    assert m(dense, ("w1", "w3", "w2")) == 212.34
    assert round(3 * 5120 * 1536 / 1e6, 2) == 23.59
    assert sparse["w1"].shape == (1, 32, 5120, 1536)
    assert sparse["router"].shape == (1, 5120, 256)


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


@pytest.fixture(scope="module")
def sound(tiny):
    """What the fault cases below share, computed once: the sound
    program's `forward` logits on a prompt, and the engine's tokens for
    three requests, each judged against the sound reference (all tie)."""
    cfg, params = tiny
    tokens = prompt_of(100, seed=11)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(L.forward(params, jnp.asarray(tokens)[None], cfg)[0])
    eng = engine(cfg, params)
    prompts = [prompt_of(n, seed=n) for n in (70, 55, 41)]
    rids = [eng.submit(p, max_new_tokens=24) for p in prompts]
    done = {d.rid: d.output_tokens for d in eng.run()}
    judged = []
    for rid, p in zip(rids, prompts):
        seq = p + done[rid]
        at = np.arange(len(p) - 1, len(seq) - 1)
        seq = seq + [0] * (WIDTH - len(seq))
        assert agreement.judge(reference_logits(params, seq)[at],
                               done[rid])[0] == 1.0
        judged.append((seq, at, done[rid]))
    return tokens, got, judged


@pytest.mark.parametrize("fault", R.FAULTS)
def test_a_seeded_fault_moves_forward_and_the_engine_off_the_reference(
        tiny, sound, fault):
    """The negative controls, one in each new part (the rescale, the
    window's bound, the index, its key's bias, the selection's size, the
    gate): the reference with that part computed wrongly is another model,
    and both `forward`'s logits and the engine's tokens (judged as the
    cell's check judges them) show it."""
    _, params = tiny
    tokens, got, judged = sound
    bad = reference_logits(params, tokens, fault=fault)
    assert np.abs(got - bad).max() > 1e-2 * np.abs(bad).max()
    shares = [agreement.judge(reference_logits(params, seq, fault=fault)[at],
                              out)[0] for seq, at, out in judged]
    assert min(shares) < 1.0


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The share test: the routed parts that the four shares (4 of 16
    experts each; 8 shares of 32 of 256 at the published widths) give,
    with the shared expert counted once, equal the uncut reference
    layer."""
    from benchmark.lib import reference_kimi
    whole_file = {**TINY, "n_routed_experts": TINY["router_width"]}
    cfg, params = make(whole_file)
    lp = {n: w[0] for n, w in params["blocks"][1].items()}
    h = jax.random.normal(jax.random.PRNGKey(5), (40, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference_kimi.sparse_ffn(
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
    assert pairs == 40 * 2              # every pair is someone's
    assert np.abs(total - want).max() < 1e-5 * max(1, np.abs(want).max())


# ---- the exact selection -----------------------------------------------------

def test_the_selection_is_the_stable_sort_s_with_ties_to_the_lower_position():
    """`select_topk` against a stable full sort on scores with many exact
    ties (values from a set of nine, -0.0 among them), rows that see fewer
    than k keys, rows that see none; `selected_positions` lists the set
    ascending with -1 behind it, across its blocks of 128 keys."""
    rng = np.random.default_rng(0)
    T, S, k = 37, 300, 24
    scores = rng.choice([-2.0, -0.0, 0.0, 0.5, 0.5, 1.0, 3.0, 7.25, -1e30],
                        (T, S)).astype(np.float32)
    seen = rng.integers(0, S + 1, T)
    seen[:3] = (0, 5, k)
    visible = (np.arange(S)[None, :] < seen[:, None]) & (
        rng.random((T, S)) < 0.9)
    mask = np.asarray(SI.select_topk(jnp.asarray(scores),
                                     jnp.asarray(visible), k))
    want = np.asarray(R.selected(jnp.asarray(scores), jnp.asarray(visible),
                                 k))
    assert np.array_equal(mask, want)
    assert np.array_equal(mask.sum(1), np.minimum(visible.sum(1), k))
    pos = np.asarray(SI.selected_positions(
        SI.pack_mask(jnp.asarray(mask)), k))
    for t in range(T):
        mine = np.flatnonzero(mask[t])
        assert np.array_equal(pos[t, :len(mine)], mine)
        assert np.all(pos[t, len(mine):] == -1)


def _order_keys(x):
    """`sparse_index._order_key` by hand (numpy)."""
    b = np.where(x == 0.0, np.float32(0.0), x).astype(np.float32).view(
        np.int32)
    return (b ^ ((b >> 31) & 0x7FFFFFFF)).view(np.uint32) ^ np.uint32(
        0x80000000)


@pytest.mark.parametrize("scores", ["ties", "zeros", "signed"])
@pytest.mark.parametrize("T, S, k", [(1, 256, 4), (16, 4096, 64),
                                     (31, 33280, 2048)])
def test_the_selection_launch_finds_select_topk_s_set_bit_for_bit(T, S, k,
                                                                  scores):
    """`pallas.index_select.select_bits` (interpreter) against
    `select_topk`: its bits are `pack_mask` of `select_topk`'s mask, word
    for word; its `kth` is the k-th largest order key a row sees (0 where
    it sees fewer than k); and the mask its two words a row stand for (the
    keys above `kth`, and of those equal to it the ones at positions <=
    `cut`) is that mask too. Scores from a set of nine with many exact
    ties across the k-th key, so that the position passes decide ("ties");
    mostly -0.0 and +0.0, one key ("zeros"); distinct negative and positive
    floats, where no block of rows needs a position pass ("signed"). Rows
    that see nothing, fewer than k, exactly k and every key; T of 1, 16 and
    31 (a padded row block behind them); S a power of two and dots3's
    33,280 (260 lane tiles: chunks of 13, and 4 of the 32 tiles of the
    last tile of words)."""
    from paddle_tpu.ops.pallas import index_select as PS
    rng = np.random.default_rng(T + S)
    if scores == "ties":
        x = rng.choice([-2.0, -0.0, 0.0, 0.5, 0.5, 1.0, 3.0, 7.25, -1e30],
                       (T, S))
    elif scores == "zeros":
        x = np.where(rng.random((T, S)) < 0.9, rng.choice([0.0, -0.0], (T, S)),
                     rng.normal(size=(T, S)))
    else:
        x = rng.normal(size=(T, S)) * 1e3
    x = x.astype(np.float32)
    seen = rng.integers(k + 1, S + 1, T)
    seen[[0, T // 4, T // 2, T - 1]] = (S, k, k - 1, 0)[:4] if T > 1 else S
    visible = np.arange(S)[None, :] < seen[:, None]
    want = SI.select_topk(jnp.asarray(x), jnp.asarray(visible), k)
    bits, kth, cut = PS.select_bits(jnp.asarray(x), jnp.asarray(seen), k,
                                    interpret=True)
    assert bits.dtype == kth.dtype == jnp.uint32
    assert bits.shape == (T, S // 128, 4) and kth.shape == cut.shape == (T,)
    assert np.array_equal(np.asarray(bits), np.asarray(SI.pack_mask(want)))
    want, kth, cut = np.asarray(want), np.asarray(kth), np.asarray(cut)
    u = np.where(visible, _order_keys(x), 0)
    by_hand = np.where(seen >= k, np.sort(u, axis=1)[:, S - k], 0)
    assert np.array_equal(kth, by_hand)
    pos = np.arange(S)[None, :]
    assert np.array_equal(want, visible & (
        (u > kth[:, None]) | ((u == kth[:, None]) & (pos <= cut[:, None]))))
    assert np.array_equal(want.sum(1), np.minimum(seen, k))
    # the cut is a position only where a row's ties do not all fit
    over = (u == by_hand[:, None]).sum(1) * (seen >= k) > k - (
        u > by_hand[:, None]).sum(1)
    assert (scores == "signed") <= (not over.any())
    assert (scores == "ties") <= bool(over.any())
    assert np.all(cut[~over] >= S - 1) and np.all(cut[over] < seen[over])


@pytest.mark.parametrize("use_pallas", [True, "decode"])
def test_the_index_select_of_the_kernels_path_hands_on_the_stock_path_s_sets(
        use_pallas):
    """`paged_index_select` end to end on a small table (two layers, the
    second one's pages; tables out of order; sequences that select, one
    that holds exactly `topk` keys and one without a row): through the
    index launches and the selection launch (interpreter) the selected
    sets, as bits and as positions with their pages, are the stock path's
    (`select_topk`), which the form's name says too."""
    rng = np.random.default_rng(11)
    bs, ID, IH, nb, mb, topk = 8, 16, 4, 64, 16, 8
    ends = np.array([40, 64, 121, topk, 0])
    this = (np.array([1, 1, 1, 1, 0]) if use_pallas == "decode"
            else np.array([9, 1, 5, 4, 0]))
    past = ends - this
    tok = 8 if use_pallas == "decode" else 24
    pool = jnp.asarray(rng.normal(size=(2, nb, 1, bs, ID)), jnp.float32)
    tables = np.full((5, mb), -1, np.int32)
    free = rng.permutation(nb)
    for b, n in enumerate(-(-ends // bs)):
        tables[b, :n], free = free[:n], free[n:]
    # scores in eighths: exact ties across a row's k-th key
    qi = jnp.asarray(rng.integers(-2, 3, (tok, IH, ID)), jnp.float32)
    w = jnp.asarray(rng.integers(1, 3, (tok, IH)) / 8, jnp.float32)
    ki = jnp.asarray(rng.integers(-2, 3, (tok, ID)), jnp.float32)
    pool = jnp.round(pool)
    cu = np.concatenate([[0], np.cumsum(this)]).astype(np.int32)
    args = (qi, w, ki, pool, jnp.int32(1), jnp.asarray(past),
            jnp.asarray(this), jnp.asarray(cu), jnp.asarray(tables), topk)
    assert SA.index_select_form(mb * bs, use_pallas) == "launch"
    assert SA.index_select_form(mb * bs, False) == "passes"
    stock = SA.paged_index_select(*args, use_pallas=False)
    mine = SA.paged_index_select(*args, use_pallas=use_pallas)
    for name, a, b in zip(("positions", "pages", "sparse", "bits", "pool"),
                          stock, mine):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    pos = np.asarray(mine[0])
    assert np.array_equal(np.asarray(mine[2]), ends * (this > 0) > topk)
    assert ((pos >= 0).sum(1)[:cu[3]] == topk).all() and (pos[cu[3]:] < 0
                                                            ).all()


@pytest.mark.parametrize("decode", [True, False])
def test_the_layer_ops_select_the_reference_s_sets_and_read_them(decode):
    """The cell's direct check of a full layer's ops at the fixture's
    shapes in float32 (Pallas interpreter): `paged_index_select` selects
    exactly the reference's sets, the index pool and the latent pool hold
    the new rows bit for bit, and the read over the selection is dense
    float32 attention over those keys, within a hundredth of the check's
    tolerance."""
    case = D.op_case(TINY, 7, jnp.float32, "full_attention", decode)
    res = D.op_outputs(TINY, case, decode)
    assert res["pool_ok"] and res["index_pool_ok"]
    assert res["selection"].all() and len(res["selection"]) >= 3
    good, worst = agreement_blockdiff.judge_attention(res["out"], res["ref"])
    assert good and worst < 0.01


@pytest.mark.parametrize("decode", [True, False])
def test_the_windowed_latent_walks_equal_dense_attention_in_the_window(
        decode):
    """The same for a sliding layer: the decode launch and the mixed walk
    under their window bound (interpreter), over tables that hold -1
    behind the windows."""
    case = D.op_case(TINY, 7, jnp.float32, "sliding_attention", decode)
    assert (np.asarray(case["tables"]) < 0).any()
    res = D.op_outputs(TINY, case, decode)
    good, worst = agreement_blockdiff.judge_attention(res["out"], res["ref"])
    assert res["pool_ok"] and good and worst < 0.01
    # one key more or fewer is another answer
    wider = D.op_outputs(TINY, {**case, "window": case["window"] + 1},
                         decode)
    assert not agreement_blockdiff.judge_attention(wider["out"],
                                                   res["ref"])[0]


def eight_bits(*names):
    def corrupt(case):
        for n in names:
            case[n] = case[n].astype(jnp.float8_e4m3fn).astype(case[n].dtype)
        return case
    return corrupt


@pytest.mark.parametrize("what", ["index keys in 8 bits", "pages in 8 bits",
                                  "an approximate selection"])
def test_lower_precision_fails_the_direct_check(what, monkeypatch):
    """What a token cannot see, each caught by a limit of part 2: index
    keys rounded to 8 bits on their way into their pages (the pool no
    longer holds them and the selected sets move), cache rows rounded
    likewise, and a selection that is not exact (a row's least key swapped
    for the best one left out)."""
    from benchmark.lib import agreement_sparse_latent as A
    corrupt = None
    if what == "index keys in 8 bits":
        corrupt = eight_bits("ki", "index_pool")
    elif what == "pages in 8 bits":
        corrupt = eight_bits("rows")
    else:
        from paddle_tpu.ops.pallas import index_select as PS
        passes, launch = SI.select_topk, PS.select_bits

        def nearly(mask, scores, visible):
            lost = jnp.argmin(jnp.where(mask, scores, jnp.inf), axis=1)
            got = jnp.argmax(jnp.where(visible & ~mask, scores, -jnp.inf),
                             axis=1)
            rows = jnp.arange(mask.shape[0])
            full = jnp.sum(visible, axis=1) > jnp.sum(mask, axis=1)
            return (mask.at[rows, lost].set(~full & mask[rows, lost])
                    .at[rows, got].set(full | mask[rows, got]))

        def nearly_bits(scores, seen, k, **kw):
            bits, kth, cut = launch(scores, seen, k, **kw)
            S = scores.shape[1]
            return SI.pack_mask(nearly(
                SI.unpack_mask(bits)[:, :S] > 0, scores,
                jnp.arange(S)[None] < seen[:, None])), kth, cut
        # both forms of the selection (the Pallas read takes the launch)
        monkeypatch.setattr(SI, "select_topk", lambda scores, visible, k:
                            nearly(passes(scores, visible, k), scores,
                                   visible))
        monkeypatch.setattr(PS, "select_bits", nearly_bits)
    # a tick with a chunk: thirty rows that select
    case = D.op_case(TINY, 7, jnp.bfloat16, "full_attention", False)
    res = D.op_outputs(TINY, case, False, corrupt)
    share = res["selection"].mean()
    if what == "pages in 8 bits":
        assert not res["pool_ok"] and share == 1.0
    elif what == "index keys in 8 bits":
        assert not res["index_pool_ok"] and share < A.MIN_SELECTION
    else:
        assert res["pool_ok"] and share < A.MIN_SELECTION


def test_the_sparse_read_with_every_key_selected_is_the_dense_walk():
    """Bit for bit, on the stock path: a selection of every key a row sees
    (`topk` = the table's width) through `paged_attention_sparse` against
    the same rows through the dense read."""
    case = D.op_case(TINY, 3, jnp.float32, "full_attention", False)
    c = case
    cu = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                          jnp.cumsum(c["this"]).astype(jnp.int32)])
    S = c["tables"].shape[1] * TINY["engine"]["block_size"]

    def read(select):
        return SA.paged_latent_attention(
            c["q_nope"], c["q_rope"], c["rows"], c["wk"], c["wv"], c["pool"],
            jnp.int32(0), c["past"], c["this"], cu, c["tables"], c["scale"],
            use_pallas=False, select=select)[0]

    tok = c["rows"].shape[0]
    tok_b = np.repeat(np.arange(len(c["this"])), np.asarray(c["this"]))
    pos = np.asarray(c["past"])[tok_b] + np.arange(tok) - np.asarray(cu)[tok_b]
    every = np.where(np.arange(S)[None, :] <= pos[:, None],
                     np.arange(S)[None, :], -1).astype(np.int32)
    pages = np.repeat(np.asarray(c["tables"])[tok_b],
                      TINY["engine"]["block_size"], axis=1)
    sparse = read((jnp.asarray(every), jnp.asarray(pages),
                   jnp.ones((len(c["this"]),), bool)))
    assert np.array_equal(np.asarray(sparse), np.asarray(read(None)))


# ---- the masked walk: the third way to read a selecting row -------------------

def tick_case(past, this, seed=5):
    """`D.op_case`'s seeded arrays of a full layer under another tick: slot
    b holds `past[b]` keys and brings `this[b]` rows, over tables drawn
    anew from the case's pages (every page of its pools holds seeded
    rows)."""
    case = D.op_case(TINY, seed, jnp.float32, "full_attention", False)
    past, this = np.asarray(past, np.int32), np.asarray(this, np.int32)
    assert int(this.sum()) == case["rows"].shape[0]
    need = -(-(past + this) // TINY["engine"]["block_size"])
    pages = np.random.default_rng(seed).permutation(case["pool"].shape[1])
    tables = np.full(case["tables"].shape, -1, np.int32)
    for b, n in enumerate(need):
        tables[b, :n] = pages[need[:b].sum():need[:b].sum() + n]
    return {**case, "tables": jnp.asarray(tables), "past": jnp.asarray(past),
            "this": jnp.asarray(this)}


def layer_out(case):
    """Every row of the tick through the layer's ops as a tick with a chunk
    calls them on the kernels (interpreter): [tok, H * v]."""
    c = case
    cu = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                          jnp.cumsum(c["this"]).astype(jnp.int32)])
    return np.asarray(D._layer_ops(
        {n: c[n] for n in ("pool", "index_pool")},
        {n: c[n] for n in ("q_nope", "q_rope", "rows", "wk", "wv", "qi", "iw",
                           "ki")},
        dict(past=c["past"], this=c["this"], cu=cu, tables=c["tables"]),
        decode=False, topk=c["topk"], window=0, scale=c["scale"])[0])


def crossing_at(monkeypatch, keys):
    """The two rates patched so that a selecting chunk of the fixture's
    full layers walks up to `keys` keys and gathers beyond."""
    cfg = D.dots3_config(TINY, jnp.float32)
    spec = cfg.layer_plan[0]
    per_key = spec.heads * 2 * (PL.padded_width(spec.latent.width)
                                + spec.latent.kv_lora_rank)
    monkeypatch.setattr(SA, "_GATHER_ROW_S", 1.0)
    monkeypatch.setattr(SA, "_WALK_FLOPS",
                        (keys + 0.5) * per_key / spec.latent.index.topk)
    assert SA.sparse_walk_keys(
        spec.heads, PL.padded_width(spec.latent.width),
        spec.latent.kv_lora_rank, spec.latent.index.topk) == keys


@pytest.mark.parametrize("past, what", [
    (20, "a chunk that starts mid-page"),
    (3, "a chunk whose first rows see at most topk keys")])
def test_the_masked_walk_reads_what_the_gather_reads(past, what,
                                                     monkeypatch):
    """One seeded selection, two reads of the same chunk of 28 rows beside
    three decode rows: through the masked walk (the crossing beyond every
    length) and through the gather by row blocks (the crossing at 0)."""
    case = tick_case([50, 6, 30, past], [1, 1, 1, 28])
    assert past % TINY["engine"]["block_size"] and past + 28 > case["topk"]
    crossing_at(monkeypatch, 1 << 20)
    walked = layer_out(case)
    crossing_at(monkeypatch, 0)
    gathered = layer_out(case)
    good, worst = agreement_blockdiff.judge_attention(walked, gathered)
    assert good and worst < 0.01
    # two programs: the chunk's rows differ in their last bits, the one-row
    # sequences' rows gather either way
    assert np.array_equal(walked[:3], gathered[:3])
    assert not np.array_equal(walked[3:], gathered[3:])


@pytest.mark.parametrize("window", [0, 5])
def test_the_masked_walk_with_every_visible_key_selected_is_the_walk(window):
    """Bit for bit (interpreter), a chunk beside one-row sequences: a mask
    of every key a row sees, and one of every key of the table (as bits,
    the form `paged_index_select` hands on), against the walk without a
    mask."""
    c = tick_case([50, 6, 30, 20], [1, 1, 1, 28])
    tok, H = c["q_nope"].shape[:2]
    W, C = c["pool"].shape[-1], c["wk"].shape[0]
    cu = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                          jnp.cumsum(c["this"]).astype(jnp.int32)])
    q = jax.random.normal(jax.random.PRNGKey(1), (tok, H, W), jnp.float32)
    S = c["tables"].shape[1] * TINY["engine"]["block_size"]
    _, pos, _ = SA._packed_rows(c["past"], c["this"], cu, tok, 4)

    def walk(mask):
        return np.asarray(PL.latent_attention_packed(
            q, c["pool"], c["tables"], c["past"], c["this"], cu, c["scale"],
            jnp.int32(0), C, window=window, mask=mask))

    plain = walk(None)
    assert np.abs(plain).max() > 0
    every = jnp.ones((tok, S), bool)
    assert np.array_equal(walk(SI.pack_mask(
        jnp.arange(S)[None, :] <= pos[:, None])), plain)
    assert np.array_equal(walk(SI.pack_mask(every)), plain)
    # and a row that selected nothing comes back 0
    none = walk(SI.pack_mask(every.at[5].set(False)))
    assert not none[5].any() and np.array_equal(np.delete(none, 5, 0),
                                                np.delete(plain, 5, 0))


def test_a_tick_sends_each_selecting_sequence_through_its_own_launch(
        monkeypatch):
    """A tick of four slots: a one-row sequence that selects (50 keys), a
    chunk of 12 rows under the crossing (32 keys after the tick), a chunk
    of 17 over it (77), and a row that sees 5 keys (the dense walk); the
    two rates patched so that the crossing lies at 40 keys. Every judged
    row equals the reference over the op's own selection, the selection is
    the reference's, and each chunk's rows are bit for bit those of the
    launch the rule names: the walk's for the one under the crossing, the
    gather's for the one over it, the gather's for the one-row sequence."""
    case = tick_case([50, 20, 60, 4], [1, 12, 17, 1])
    crossing_at(monkeypatch, 1 << 20)
    walked = layer_out(case)
    crossing_at(monkeypatch, 0)
    gathered = layer_out(case)
    crossing_at(monkeypatch, 40)
    mixed = layer_out(case)
    assert np.array_equal(mixed[:1], gathered[:1])
    assert np.array_equal(mixed[1:13], walked[1:13])
    assert not np.array_equal(mixed[1:13], gathered[1:13])
    assert np.array_equal(mixed[13:30], gathered[13:30])
    assert not np.array_equal(mixed[13:30], walked[13:30])
    assert np.array_equal(mixed[30:], walked[30:])          # the dense walk
    res = D.op_outputs(TINY, case, False)
    good, worst = agreement_blockdiff.judge_attention(res["out"], res["ref"])
    assert res["pool_ok"] and res["index_pool_ok"] and res["pages_ok"]
    assert res["selection"].all() and good and worst < 0.01


def test_the_walked_rows_and_pairs_equal_hand_counts(tiny, monkeypatch):
    """`_plan_keys` for that tick, by the formula the device uses: on the
    kernels the chunk under the crossing walks (12 rows, their causal
    pairs, in each of the 2 index layers), the chunk over it and the
    one-row sequence gather, and the stock read walks nothing; a model
    without an index has no such counter."""
    from benchmark.drivers import closed_loop_serve_latent as DK
    cfg, params = tiny
    crossing_at(monkeypatch, 40)
    past, this = np.array([50, 20, 60, 4]), np.array([1, 12, 17, 1])
    keys = engine(cfg, params, pallas=True)._plan_keys(past, this)
    assert keys["sparse_rows_walked"] == 2 * 12
    assert keys["sparse_pairs_walked"] == 2 * (12 * 20 + 12 * 13 // 2)
    assert keys["sparse_pairs_selected"] == 2 * (8 + 12 * 8 + 17 * 8)
    assert keys["sparse_rows_dense"] == 2 * 1
    decode = engine(cfg, params, pallas=True)._plan_keys(
        np.array([50, 20, 60, 4]), np.array([1, 1, 1, 1]))
    stock = engine(cfg, params)._plan_keys(past, this)
    for k in ("sparse_rows_walked", "sparse_pairs_walked"):
        assert decode[k] == stock[k] == 0
    with open(os.path.join(HERE, "..", "benchmark", "tests", "fixtures",
                           "configs", "tiny-kimi.json")) as f:
        kimi = json.load(f)
    kcfg = dataclasses.replace(DK.kimi_config(kimi, jnp.float32),
                               dtype=jnp.float32)
    e = kimi["engine"]
    eng = PagedServingEngine(
        kcfg, L.init_params(kcfg, jax.random.PRNGKey(0)),
        num_blocks=e["num_blocks"], block_size=e["block_size"],
        max_batch=e["max_batch"], token_budget=e["token_budget"],
        max_len=e["max_len"], pallas=True)
    assert not any(k.startswith("sparse_") for k in eng.stats)
    assert not any(k.startswith("sparse_")
                   for k in eng._plan_keys(past, this))


# ---- the index's two launches: a sequence's keys out of its pages -----------

@pytest.mark.parametrize("launch", ["paged_index_scores_chunk",
                                    "paged_index_scores_decode"])
def test_an_index_launch_scores_a_sequence_s_keys_out_of_its_pages(
        launch, monkeypatch):
    """Each launch (interpreter) against `sparse_index.index_scores` over
    keys gathered by hand, on the stacked pool, `layer` 1 of 2, key tiles
    of 32 keys (4 pages of 8), row tiles of 4 tokens. The block tables'
    pages are out of order and not contiguous, with -1 behind a sequence's
    last page; the pool's other layer and every page no table lists hold
    NaN, so a copy of a wrong page shows. The slots: a sequence that ends
    on a page's edge (40 keys), one that ends on a key tile's edge (64),
    one mid-page whose table is full, one that does not select (at most
    `topk` keys: the caller hands the launch no row of it), one with no
    row."""
    monkeypatch.setattr(PL, "_INDEX_TOKENS", 4)
    rng = np.random.default_rng(3)
    bs, ID, IH, nb, mb, topk, tok = 8, 16, 4, 64, 12, 8, 32
    chunk = launch.endswith("chunk")
    ends = np.array([40, 64, 93, 7, 0])
    this = np.array([9, 6, 5, 4, 0]) if chunk else np.array([1, 1, 1, 1, 0])
    past = ends - this
    pool = np.full((2, nb, 1, bs, ID), np.nan, np.float32)
    tables = np.full((5, mb), -1, np.int32)
    free = rng.permutation(nb)[::2]                 # every other page
    for b, n in enumerate(-(-ends // bs)):
        tables[b, :n], free = free[:n], free[n:]
        pool[1, tables[b, :n]] = rng.normal(size=(n, 1, bs, ID))
    qi = rng.normal(size=(tok, IH, ID)).astype(np.float32)
    w = rng.normal(size=(tok, IH)).astype(np.float32)
    cu = np.concatenate([[0], np.cumsum(this)]).astype(np.int32)
    rows = np.where(ends > topk, this, 0)           # `paged_index_select`'s
    args = (jnp.asarray(pool), jnp.asarray(tables), jnp.asarray(past),
            jnp.asarray(rows))
    if chunk:
        got = PL.index_scores_packed(jnp.asarray(qi), jnp.asarray(w), *args,
                                     jnp.asarray(cu), jnp.int32(1))
    else:
        got = PL.index_scores_rows(jnp.asarray(qi[cu[:5]]),
                                   jnp.asarray(w[cu[:5]]), *args,
                                   jnp.int32(1))
    got = np.asarray(got)
    assert got.shape == ((tok if chunk else 5), mb * bs)
    for b in range(5):
        by_hand = pool[1, np.maximum(tables[b], 0), 0].reshape(mb * bs, ID)
        want = np.asarray(SI.index_scores(
            jnp.asarray(qi), jnp.asarray(by_hand), jnp.asarray(w)))
        for t in range(rows[b]):
            r, p = cu[b] + t, past[b] + t
            mine = got[r if chunk else b]
            assert np.isfinite(want[r, :p + 1]).all()
            assert np.allclose(mine[:p + 1], want[r, :p + 1], rtol=1e-5,
                               atol=1e-5), (b, t)
            # behind the last key tile of the row's work item (of the row
            # itself in the one-row form) nothing was copied or multiplied
            last = past[b] + min(t // 4 * 4 + 4, rows[b]) - 1
            assert not mine[(last // 32 + 1) * 32:].any()
        if not chunk and not rows[b]:
            assert not got[b].any()
    # the host's count of the copies, by hand: a one-row sequence its keys
    # in whole key blocks; a chunk's every work item (4 rows) the keys up
    # to its last row in whole tiles
    by_hand = ((64 + 64 + 64, 64 + 64, 96 + 96) if chunk else (64, 64, 96))
    assert PL.index_keys_fetched(past[:3], this[:3], tok, bs, mb) == sum(
        by_hand)


# ---- the engine --------------------------------------------------------------

@pytest.mark.parametrize("pallas", [False, True])
def test_engine_equals_the_reference_through_both_pools(tiny, pallas):
    """Chunked prefill (chunks of 32 over prompts of 70 and 6), then decode
    across page edges, two sequences in different phases: the short one
    starts under `index_topk` (the dense walk), crosses it while decoding
    (the selection starts) and crosses the window; the long one selects
    from its first chunk on. Full layers' pages carry two rows a position,
    the window layers' pool has its own width and gives pages back."""
    cfg, params = tiny
    eng = engine(cfg, params, pallas=pallas)
    (full, window), (index, none) = eng._key_cache, eng._value_cache
    assert full.shape == (2, 64, 1, 8, PL.padded_width(48))
    assert window.shape == (3, 32, 1, 8, PL.padded_width(64))
    # (an index key's row in whole lanes, as a latent row's: PR 50)
    assert index.shape == (2, 64, 1, 8, PL.padded_width(16))
    assert none is None
    prompts = [prompt_of(70, seed=3), prompt_of(6, seed=4)]
    news = [20, 30]
    rids = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    done = {d.rid: d.output_tokens for d in eng.run()}
    for rid, p, n in zip(rids, prompts, news):
        assert done[rid] == reference_tokens(params, p, n)
    st = eng.engine_stats
    assert eng.blocks.num_allocated() == 0
    assert eng.blocks.window_allocated() == 0
    assert st["window_pages_released"] > 0 and st["sparse_rows_dense"] > 0
    assert 0 < st["sparse_pairs_selected"] < st["index_pairs"]
    # the kernels read a selecting chunk through the masked walk
    assert (st["sparse_rows_walked"] > 0) == pallas
    assert (st["sparse_pairs_walked"] > st["sparse_rows_walked"]) == pallas
    assert st["prefix_cache"].startswith("off")
    assert 0 < st["moe_pairs_held"] < 4 * st["moe_pairs"]


def test_counters_equal_hand_counts_for_two_requests_alone(tiny):
    """Each request alone, so that every tick is known. A prompt of 37 in
    chunks of 32 and 5, then 7 decode rows: every tick's sequence holds
    more than 8 keys, so its rows select (a row at p keeps min(p + 1, 8))
    and none walks densely. A prompt of 5 and one decode row stay under 8:
    the dense walk. The window layers count the keys and pairs inside a
    window of 5; every count is times the layers of its kind (2 full, 3
    sliding)."""
    cfg, params = tiny
    eng = engine(cfg, params)
    eng._next_is_determined = lambda cur: False     # no void row
    eng.submit(prompt_of(37, seed=2), max_new_tokens=8)
    eng.run()
    st = dict(eng.stats)
    keys = 32 + 37 + sum(range(38, 45))
    pairs = 37 * 38 // 2 + sum(range(38, 45))
    assert (st["steps"], st["tokens_computed"]) == (9, 44)
    assert (st["index_keys"], st["index_pairs"]) == (2 * keys, 2 * pairs)
    assert st["sparse_pairs_selected"] == 2 * (36 + 24 * 8 + 5 * 8 + 7 * 8)
    assert st["sparse_rows_dense"] == st["attn_keys_latent"] == 0
    assert st["attn_keys_latent_window"] == 3 * (32 + 9 + 7 * 5)
    assert st["attn_pairs_latent_window"] == 3 * (
        (1 + 2 + 3 + 4) + 28 * 5 + 5 * 5 + 7 * 5)
    assert st["index_pages_live"] == st["latent_pages_live"] > 0
    # while it ran: the pages wholly behind position 43's window
    assert st["window_pages_released"] == (43 - 4) // 8
    eng.submit(prompt_of(5, seed=2), max_new_tokens=2)
    eng.run()
    new = {k: eng.stats[k] - st[k] for k in st}
    assert (new["index_keys"], new["sparse_pairs_selected"]) == (0, 0)
    assert new["sparse_rows_dense"] == 2 * (5 + 1)
    assert new["attn_keys_latent"] == 2 * (5 + 6)
    assert new["attn_pairs_latent"] == 2 * (15 + 6)


def test_the_index_keys_fetched_equal_hand_counts_for_two_requests_alone(
        tiny, monkeypatch):
    """`index_keys_fetched` on the kernels' path, each request alone and
    the ticks' program stood in for (the count is the host's, from the
    lengths it plans with): key tiles of 32 keys, row tiles of 4 tokens. A
    prompt of 37 in chunks of 32 and 5, then 7 decode rows, selects in
    every tick: the first chunk's 8 work items fetch the keys up to rows
    3, 7, ... 31 in whole tiles (32 each), the second chunk's two up to
    rows 35 and 36 (64 each), a decode row at position p its p // 32 + 1
    key blocks; times the 2 index layers. A prompt of 5 never selects and
    fetches nothing; the stock path gathers and counts nothing."""
    cfg, params = tiny
    monkeypatch.setattr(PL, "_INDEX_TOKENS", 4)
    eng = engine(cfg, params, pallas=True)
    eng._next_is_determined = lambda cur: False     # no void row
    monkeypatch.setattr(eng, "_build_step", lambda tok_pad, B, *rest: (
        lambda *args: (jnp.zeros((B + len(eng._moe_fields),), jnp.int32),
                       args[1], args[2])))
    eng.submit(prompt_of(37, seed=2), max_new_tokens=8)
    eng.run()
    st = dict(eng.stats)
    assert (st["steps"], st["tokens_computed"]) == (9, 44)
    assert st["index_keys"] == 2 * (32 + 37 + sum(range(38, 45)))
    assert st["index_keys_fetched"] == 2 * (8 * 32 + 2 * 64 + 7 * 64)
    eng.submit(prompt_of(5, seed=2), max_new_tokens=2)
    eng.run()
    assert eng.stats["index_keys_fetched"] == st["index_keys_fetched"]
    assert engine(cfg, params)._plan_keys(
        np.array([32]), np.array([5]))["index_keys_fetched"] == 0


@pytest.mark.parametrize("pallas", [False, True])
def test_a_preempted_sequence_resumes_through_both_pools(tiny, pallas):
    cfg, params = tiny
    eng = engine(cfg, params, max_batch=3, num_blocks=16, pallas=pallas)
    prompts = [prompt_of(n, seed=n) for n in (60, 50, 44)]
    rids = [eng.submit(p, max_new_tokens=16) for p in prompts]
    done = {d.rid: d.output_tokens for d in eng.run()}
    assert eng.engine_stats["preemptions"] >= 1
    for rid, p in zip(rids, prompts):
        assert done[rid] == reference_tokens(params, p, 16)
    assert eng.blocks.num_allocated() == 0
    assert eng.blocks.window_allocated() == 0


@pytest.mark.parametrize("what, kw", [
    ("int8 pages", dict(quant_kv=True)),
    ("LoRA", dict(adapter_slots=2)),
    ("a draft model", dict(draft=(None, None))),
])
def test_what_the_engine_refuses_under_this_plan_raises_at_construction(
        tiny, what, kw):
    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="layer plan"):
        engine(cfg, params, **kw)


def test_page_hand_off_is_refused_and_two_widths_in_one_pool_too(tiny):
    cfg, params = tiny
    eng = engine(cfg, params)
    with pytest.raises(NotImplementedError, match="layer plan"):
        eng.extract_pages(prompt_of(40))
    # a pool is one array: two full kinds of different cache rows have none
    wide = dataclasses.replace(cfg.layer_plan[1].latent, kv_lora_rank=200)
    plan = (cfg.layer_plan[0],
            dataclasses.replace(cfg.layer_plan[1], latent=wide),
            *cfg.layer_plan[2:])
    with pytest.raises(NotImplementedError, match="row widths"):
        engine(dataclasses.replace(cfg, layer_plan=plan), params)


def test_the_tick_runs_under_the_new_scopes(tiny):
    """The named scopes the per-layer metrics read, in the tick's program."""
    cfg, params = tiny
    eng = engine(cfg, params)
    fn = eng._build_step(32, 4)
    B, mb = 4, eng.max_blocks_per_seq
    z = lambda *s: np.zeros(s, np.int32)
    text = fn.lower(
        eng.params, eng._key_cache, eng._value_cache, None, z(32),
        (z(B, mb), z(B, mb)), z(B + 1), z(B), z(B), eng._rope_emb,
        np.ones((B,), np.float32), np.ones((B,), np.float32),
        np.zeros((B, 2), np.uint32), np.ones((B,), bool), (), None, None,
        eng._last_out, np.full((32,), -1, np.int32)).as_text(debug_info=True)
    for scope in ("index_q", "index_k", "index_scores", "index_select",
                  "paged_attention_sparse", "paged_attention_latent_window",
                  "paged_attention_latent/", "latent_q", "latent_kv",
                  "latent_out", "attn_gate", "shared_expert"):
        assert scope in text, scope
