"""SDAR-shaped models (generation by diffusion over blocks: a block-causal
mask, four rows a sequence a tick, denoise and commit forwards; per-head
QK-norm; heads wider than hidden / heads; routed experts renormalised)
through `llama.forward` and `PagedServingEngine`, against the plain
float32 reference `benchmark/lib/reference_sdar.py`.

Everything here is float32 at a tiny size (2 layers, d 64, 4 query / 2
key-value heads of 32, so heads x head_dim = 128 is not the hidden size, 8
experts of width 32, two a row, blocks of 4, vocabulary 512). The engine's
tokens must EQUAL the reference loop's, and so must every denoise forward:
the block going in, the proposals at its masked rows, the rows taken; the
confidences agree to 1e-4 of themselves (float32 sums in another order).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_sdar as R
from benchmark.lib.agreement_blockdiff import record_forwards
from paddle_tpu.inference.serving import PagedServingEngine
from paddle_tpu.inference.serving.scheduler import UNKNOWN
from paddle_tpu.models import llama as L
from paddle_tpu.ops.kernels import serving_attention as SA
from paddle_tpu.ops.pallas import paged_attention as PA

BD, MASK = 4, 511


def make(seed=0, **kw):
    cfg = L.LlamaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=32, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=32, max_seq_len=128,
        rope_theta=1e6, rms_eps=1e-6, num_experts=8, top_k=2, qk_norm=True,
        qk_norm_per_head=True, norm_topk_prob=True, block_length=BD,
        mask_token_id=MASK, dtype=jnp.float32,
        param_dtype=jnp.float32, **kw)
    params = L.init_params(cfg, jax.random.PRNGKey(seed))
    # gains that are not all one, so that a missing norm shows; a router
    # and a head sharp enough that top-k weights and confidences differ
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), 2)
    blocks = dict(params["blocks"])
    for name, key in zip(("q_norm", "k_norm"), keys):
        blocks[name] = 1.0 + 0.3 * jax.random.normal(key, blocks[name].shape)
    blocks["router"] = blocks["router"] * 20.0
    return cfg, {**params, "blocks": blocks,
                 "lm_head": params["lm_head"] * 8.0}


def ref_kw(cfg):
    return dict(heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim, theta=cfg.rope_theta, eps=cfg.rms_eps,
                top_k=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob)


def reference(cfg, params, prompt, new, steps):
    with jax.default_matmul_precision("highest"):
        return R.generate(params, prompt, new, block_length=BD, steps=steps,
                          mask_id=MASK, **ref_kw(cfg))


def engine(cfg, params, **kw):
    kw = {**dict(num_blocks=48, block_size=8, max_batch=4, token_budget=32,
                 max_len=128, pallas=False), **kw}
    eng = PagedServingEngine(cfg, params, **kw)
    eng.forwards = record_forwards(eng)
    return eng


def prompt_of(n, seed=1):
    return np.random.default_rng(seed).integers(1, 500, n).tolist()


def same_forwards(eng, rid, want):
    """The engine's denoise forwards of one request against the reference
    loop's: block, mask flags, proposals at the masked rows and the rows
    taken equal, confidences to float32 rounding."""
    got = [f for f in eng.forwards if f["rid"] == rid]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        m = np.asarray(w["masked"])
        assert (g["start"], g["ids"], g["masked"], g["taken"]) == (
            w["start"], w["ids"], w["masked"], w["taken"])
        assert np.array_equal(np.asarray(g["proposed"])[m],
                              np.asarray(w["proposed"])[m])
        np.testing.assert_allclose(np.asarray(g["conf"])[m],
                                   np.asarray(w["conf"])[m], rtol=1e-4)


@pytest.fixture(scope="module")
def tiny():
    return make()


@pytest.mark.parametrize("pallas", [False, True])
@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("prompt_len", [12, 9, 18, 7])   # remainders 0 1 2 3
def test_engine_equals_the_reference_loop(tiny, prompt_len, steps, pallas):
    cfg, params = tiny
    prompt = prompt_of(prompt_len, seed=prompt_len)
    eng = engine(cfg, params, pallas=pallas)
    rid = eng.submit(prompt, max_new_tokens=10, denoising_steps=steps)
    events = []
    while eng.has_work():
        events.append([e.token for e in eng.step() if e.token >= 0])
    want, forwards = reference(cfg, params, prompt, 10, steps)
    assert [t for ev in events for t in ev] == want
    same_forwards(eng, rid, forwards)
    # tokens come a block at a time: the open first block's new rows, whole
    # blocks, and what the last block holds up to max_new_tokens
    first = BD - prompt_len % BD
    sizes = [len(ev) for ev in events if ev]
    assert sizes[0] == first and set(sizes[1:-1]) <= {BD}
    assert sum(sizes) == 10
    blocks = len(sizes)
    s = eng.stats
    assert s["diff_commit_forwards"] == s["diff_blocks_committed"] == blocks
    assert s["diff_denoise_forwards"] == len(forwards)
    assert s["diff_rows"] == BD * (blocks + len(forwards))
    assert s["diff_tokens_unmasked"] == first + BD * (blocks - 1)
    assert s["decode_fast_steps"] == 0      # no one-row launch, ever
    # ticks are launched ahead (which forward a block has next and its
    # quota are counts), and every row of them was wanted
    assert s["ticks_ahead"] > 0 == s["ahead_void_rows"]


def test_chunked_prefill_cut_mid_prompt_and_two_sequences_at_different_steps(
        tiny):
    """A prompt of 43 in chunks of 8 (whole blocks; the tail of 3 opens the
    first block), a second request admitted while the first is mid-block, a
    third with other denoising steps: ticks mix prefill chunks, denoise and
    commit forwards, and every request equals its own reference."""
    cfg, params = tiny
    eng = engine(cfg, params, prefill_chunk=8, token_budget=16)
    specs = [(43, 2, 9), (10, 4, 6), (21, 1, 8)]
    prompts = [prompt_of(n, seed=n) for n, _, _ in specs]
    rids = [eng.submit(prompts[0], max_new_tokens=specs[0][2],
                       denoising_steps=specs[0][1])]
    for _ in range(6):          # 40 positions in 5 chunks, then the block
        eng.step()
    seq = eng.scheduler.get(rids[0])
    assert seq.num_computed == 40 and seq.block_forwards > 0
    for p, (_, steps, new) in zip(prompts[1:], specs[1:]):
        rids.append(eng.submit(p, max_new_tokens=new, denoising_steps=steps))
    kinds = set()
    while eng.has_work():
        eng.step()
        kinds.add(tuple(sorted(
            (s.block_ids is not None, bool(s.block_masked
                                           and any(s.block_masked)))
            for s in eng.scheduler.running)))
    assert any(len(set(k)) > 1 for k in kinds)   # different states in a tick
    done = {c.rid: c.output_tokens for c in eng._completions}
    for rid, p, (_, steps, new) in zip(rids, prompts, specs):
        want, forwards = reference(cfg, params, p, new, steps)
        assert done[rid] == want
        same_forwards(eng, rid, forwards)
    # a tick of blocks alone packs min(B x Bd, token_budget) = 16 rows, what
    # a tick with a chunk packs here: one executable
    assert eng.stats["step_builds"] == 1


def test_preemption_recomputes_whole_blocks_and_keeps_the_open_block(tiny):
    cfg, params = tiny
    # 10 pages of 8: two requests of 26 + 16 tokens outgrow them
    eng = engine(cfg, params, num_blocks=10, max_batch=2)
    prompts = [prompt_of(26, seed=3), prompt_of(25, seed=4)]
    rids = [eng.submit(p, max_new_tokens=16, denoising_steps=2)
            for p in prompts]
    done = {c.rid: c.output_tokens for c in eng.run()}
    assert eng.scheduler.stats["preemptions"] > 0
    for rid, p in zip(rids, prompts):
        want, forwards = reference(cfg, params, p, 16, 2)
        assert done[rid] == want
        # a preempted block is not denoised twice
        same_forwards(eng, rid, forwards)


def test_prefix_hit_is_cut_to_a_block_multiple(tiny):
    """A second prompt shares 22 tokens with the first: two full pages of
    8 hit, the third matches 6 leading tokens, and of those only the whole
    block (4) counts: the keys of positions 20, 21 depend on 22, 23."""
    cfg, params = tiny
    eng = engine(cfg, params)
    a = prompt_of(30, seed=5)
    b = a[:22] + prompt_of(9, seed=6)
    ra = eng.submit(a, max_new_tokens=6, denoising_steps=2)
    eng.run()
    rb = eng.submit(b, max_new_tokens=6, denoising_steps=2)
    seq = eng.scheduler.get(rb)
    eng.step()
    assert eng.blocks.stats["prefix_hit_tokens"] == 20
    assert eng.blocks.stats["cow_copies"] == 1
    done = {c.rid: c.output_tokens for c in eng.run()}
    want, forwards = reference(cfg, params, b, 6, 2)
    assert done[rb] == want and seq.status == "finished"
    same_forwards(eng, rb, forwards)
    assert ra != rb


def test_a_prompt_holding_the_mask_token_is_served_unchanged(tiny):
    """Whether a row is masked is the sequence's own state: a known row
    that carries `mask_token_id` (here in the prefilled part and in the
    open first block's tail) is never unmasked."""
    cfg, params = tiny
    prompt = prompt_of(14, seed=7)
    prompt[3] = prompt[12] = prompt[13] = MASK
    eng = engine(cfg, params)
    rid = eng.submit(prompt, max_new_tokens=7, denoising_steps=2)
    (done,) = eng.run()
    want, forwards = reference(cfg, params, prompt, 7, 2)
    assert done.output_tokens == want and done.prompt_tokens == prompt
    first = [f for f in eng.forwards if f["rid"] == rid][0]
    assert first["ids"][:2] == [MASK, MASK]
    assert first["masked"] == [False, False, True, True]
    same_forwards(eng, rid, forwards)


def test_stream_yields_a_block_at_a_time_and_eos_stops_inside_one(tiny):
    cfg, params = tiny
    prompt = prompt_of(9, seed=9)
    want, _ = reference(cfg, params, prompt, 10, 2)
    eng = engine(cfg, params)
    assert list(eng.stream(eng.submit(
        prompt, max_new_tokens=10, denoising_steps=2))) == want
    # the fifth token as end-of-sequence: four come out, the block's rest
    # is dropped
    stop = want[4]
    cut = want.index(stop)
    eng = engine(cfg, params)
    rid = eng.submit(prompt, max_new_tokens=10, eos_token_id=stop,
                     denoising_steps=2)
    (done,) = eng.run()
    assert done.output_tokens == want[:cut] and done.finish_reason == "stop"
    assert rid == done.rid


# ---- the block tick launched ahead ------------------------------------------
#
# `PagedServingEngine.step` launches a block-diffusion tick before the last
# one is read wherever counts determine it: which forward a block has next,
# its quota, where the block stands. The rows the last forward took, and
# their ids, reach the next one on the device. Contract, as for plain rows
# (tests/test_serving_ahead.py): the events of every `step()` call, every
# completion, every `diff_*` counter and every recorded forward are those of
# the synchronous order (`_next_is_determined` patched to False).

DIFF = ("diff_denoise_forwards", "diff_commit_forwards",
        "diff_blocks_committed", "diff_tokens_unmasked", "diff_rows")
# more requests than slots, every remainder of the prompt, every step count,
# a prompt in chunks: (prompt_len, denoising_steps, max_new_tokens)
MIXED = [(43, 2, 9), (10, 4, 14), (21, 1, 8), (7, 2, 17), (12, 4, 5),
         (18, 1, 11), (5, 2, 12)]


def drive(eng):
    """Step until idle: the event list of every call, as plain tuples."""
    calls = []
    while eng.has_work():
        calls.append([(e.rid, e.token, e.finished, e.reason)
                      for e in eng.step()])
    return calls


def synchronous(eng, monkeypatch):
    monkeypatch.setattr(eng, "_next_is_determined", lambda cur: False)


def mixed_run(tiny, pallas, ahead, monkeypatch):
    cfg, params = tiny
    eng = engine(cfg, params, pallas=pallas, prefill_chunk=16)
    if not ahead:
        synchronous(eng, monkeypatch)
    seqs = [eng.scheduler.get(eng.submit(
        prompt_of(n, seed=n), max_new_tokens=new, denoising_steps=steps))
        for n, steps, new in MIXED]
    calls = drive(eng)
    done = {c.rid: (c.output_tokens, c.finish_reason) for c in eng.run()}
    return eng, seqs, calls, done


@pytest.mark.parametrize("pallas", [False, True], ids=["stock", "kernel"])
def test_ahead_events_counters_and_forwards_are_the_synchronous_order(
        tiny, pallas, monkeypatch):
    eng, seqs, calls, done = mixed_run(tiny, pallas, True, monkeypatch)
    sync, _, calls0, done0 = mixed_run(tiny, pallas, False, monkeypatch)
    assert calls == calls0            # one tick's events a call, in order
    assert done == done0 and all(r == "length" for _, r in done.values())
    assert sync.stats["ticks_ahead"] == 0 == sync.stats["ahead_void_rows"]
    assert eng.stats["ticks_ahead"] > eng.stats["steps"] // 2
    assert eng.stats["steps"] == sync.stats["steps"] == len(calls)
    for name in DIFF:
        assert eng.stats[name] == sync.stats[name] > 0, name
    assert eng.stats["tokens_computed"] == (
        sync.stats["tokens_computed"] + eng.stats["ahead_void_rows"])
    # the recorder reads a sequence's state on entry to `_harvest_blocks`:
    # it is what the harvested tick was launched with, forward for forward
    assert eng.forwards == sync.forwards
    # no new executable, and nothing unknown or masked left behind
    assert eng.stats["step_builds"] == sync.stats["step_builds"]
    assert len(eng._step_fns) == len(sync._step_fns) == 2
    for seq in seqs:
        assert seq.tokens == seq.prompt + seq.generated
        assert UNKNOWN not in seq.tokens and MASK not in seq.generated
        assert seq.in_flight == 0
    assert eng.blocks.num_allocated() == 0
    if not pallas:      # and the tokens are the reference loop's
        cfg, params = tiny
        for seq, (n, steps, new) in zip(seqs, MIXED):
            want, forwards = reference(cfg, params, prompt_of(n, seed=n),
                                       new, steps)
            assert done[seq.rid][0] == want
            same_forwards(eng, seq.rid, forwards)


@pytest.mark.parametrize("pallas", [False, True], ids=["stock", "kernel"])
def test_two_tick_shapes_are_two_executables_either_way(tiny, pallas,
                                                        monkeypatch):
    """A budget above max_batch x Bd: a tick of blocks alone and a tick
    with a prefill chunk are the engine's two executables, launched ahead
    or not."""
    cfg, params = tiny
    builds = []
    for ahead in (True, False):
        eng = engine(cfg, params, pallas=pallas, max_batch=2,
                     token_budget=16)
        if not ahead:
            synchronous(eng, monkeypatch)
        for n in (19, 6, 9):
            eng.submit(prompt_of(n, seed=n), max_new_tokens=9,
                       denoising_steps=2)
        eng.run()
        assert sorted(k[0] for k in eng._step_fns) == [8, 16]
        builds.append((eng.stats["step_builds"], len(eng._step_fns)))
    assert builds == [(2, 2), (2, 2)]


def step_until_block_in_flight(eng, rid, blocks_out=1):
    """Step until `rid` has streamed `blocks_out` blocks and a tick that
    runs its open block is in flight; returns the tokens streamed."""
    got, blocks = 0, 0
    for _ in range(64):
        n = sum(e.rid == rid and e.token >= 0 for e in eng.step())
        got, blocks = got + n, blocks + bool(n)
        cur = eng._in_flight
        if blocks >= blocks_out and cur is not None and rid in cur.slots:
            return got
    raise AssertionError("no tick with a block of the request in flight")


def books_balance(eng, prefill_rows):
    """Every row computed is a prefill row, a row of a counted forward or
    a void row, and no counter holds a void one."""
    s = eng.stats
    assert s["diff_rows"] == BD * (s["diff_denoise_forwards"]
                                   + s["diff_commit_forwards"])
    assert s["diff_commit_forwards"] == s["diff_blocks_committed"]
    assert s["tokens_computed"] == (prefill_rows + s["diff_rows"]
                                    + s["ahead_void_rows"])


@pytest.mark.parametrize("pallas", [False, True], ids=["stock", "kernel"])
def test_eos_inside_a_block_with_the_next_block_in_flight(tiny, pallas,
                                                          monkeypatch):
    """The end-of-sequence id is found when the commit is harvested, with
    the next block's first denoise forward in flight: those Bd rows are
    computed and dropped, counted in `ahead_void_rows` alone."""
    cfg, params = tiny
    prompt = prompt_of(9, seed=9)
    want, _ = reference(cfg, params, prompt, 14, 2)
    stop = want[4]                       # inside the second block
    cut = want.index(stop)
    runs = []
    for ahead in (True, False):
        eng = engine(cfg, params, pallas=pallas)
        if not ahead:
            synchronous(eng, monkeypatch)
        rid = eng.submit(prompt, max_new_tokens=14, eos_token_id=stop,
                         denoising_steps=2)
        calls = drive(eng)
        (done,) = eng.run()
        if not pallas:
            assert done.output_tokens == want[:cut]
        assert done.finish_reason == "stop" and done.rid == rid
        assert eng.blocks.num_allocated() == 0
        books_balance(eng, prefill_rows=8)
        runs.append((eng, calls, done.output_tokens))
    (eng, calls, out), (sync, calls0, out0) = runs
    assert out == out0 and [c for c in calls if c] == [c for c in calls0 if c]
    assert eng.stats["ahead_void_rows"] == BD
    assert sync.stats["ahead_void_rows"] == 0
    for name in DIFF:
        assert eng.stats[name] == sync.stats[name], name
    assert eng.forwards == sync.forwards
    # whoever gets the pages next is served as the reference says
    other = prompt_of(13, seed=14)
    r2 = eng.submit(other, max_new_tokens=6, denoising_steps=2)
    (done,) = eng.run()
    assert done.rid == r2
    if not pallas:
        assert done.output_tokens == reference(cfg, params, other, 6, 2)[0]


def test_cancel_with_a_block_tick_in_flight(tiny):
    """`cancel` settles the tick in flight: what it committed is not lost,
    nothing is void, and the other request is served as the reference."""
    cfg, params = tiny
    prompt, other = prompt_of(9, seed=21), prompt_of(14, seed=22)
    eng = engine(cfg, params)
    rid = eng.submit(prompt, max_new_tokens=40, denoising_steps=2)
    keep = eng.submit(other, max_new_tokens=9, denoising_steps=4)
    seen = step_until_block_in_flight(eng, rid, blocks_out=2)
    assert eng.cancel(rid) and eng._in_flight is None
    assert not eng.cancel(rid)
    seq = eng.scheduler.get(rid)
    assert len(seq.generated) >= seen and not any(seq.block_masked)
    held = [e.token for e in eng.step() if e.rid == rid and e.token >= 0]
    assert seq.generated == reference(cfg, params, prompt, 40, 2)[0][
        :seen + len(held)]
    done = {c.rid: c for c in eng.run()}
    assert done[rid].finish_reason == "cancelled"
    assert done[rid].output_tokens == seq.generated
    assert done[keep].output_tokens == reference(cfg, params, other, 9, 4)[0]
    assert eng.stats["ahead_void_rows"] == 0
    assert eng.blocks.num_allocated() == 0
    books_balance(eng, prefill_rows=8 + 12)


def test_deadline_with_a_block_tick_in_flight(tiny):
    """A deadline that falls while the sequence's block is in a tick in
    flight: the forward is computed and dropped, in no `diff_*` counter and
    in no record, and the stream ends `deadline` behind the blocks
    committed before it."""
    cfg, params = tiny
    prompt, other = prompt_of(9, seed=23), prompt_of(14, seed=24)
    eng = engine(cfg, params)
    rid = eng.submit(prompt, max_new_tokens=40, denoising_steps=2,
                     deadline_s=3600.0)
    keep = eng.submit(other, max_new_tokens=9, denoising_steps=4)
    seen = step_until_block_in_flight(eng, rid, blocks_out=2)
    before = {k: eng.stats[k] for k in DIFF}
    eng.scheduler.get(rid).deadline = time.monotonic() - 1.0
    eng.step()      # launches a tick without it, harvests the one with it
    assert eng.stats["ahead_void_rows"] == BD
    assert eng.stats["diff_rows"] - before["diff_rows"] == BD   # `keep`'s
    done = {c.rid: c for c in eng.run()}
    assert done[rid].finish_reason == "deadline"
    assert done[rid].output_tokens == reference(
        cfg, params, prompt, 40, 2)[0][:seen]
    assert done[keep].output_tokens == reference(cfg, params, other, 9, 4)[0]
    assert eng.scheduler.stats["deadline_expired"] == 1
    assert eng.stats["diff_denoise_forwards"] == len(eng.forwards)
    assert eng.blocks.num_allocated() == 0
    books_balance(eng, prefill_rows=8 + 12)


def test_tick_behind_the_last_commit_is_planned_with_everything_known(tiny):
    """The commit forward that reaches `max_new_tokens` frees a slot: the
    tick behind it is not launched ahead, and admits both the request that
    waited and one submitted on seeing the last token."""
    cfg, params = tiny
    eng = engine(cfg, params, max_batch=2)
    short = eng.submit(prompt_of(6, seed=31), max_new_tokens=6,
                       denoising_steps=2)
    eng.submit(prompt_of(9, seed=32), max_new_tokens=60, denoising_steps=2)
    waiting = eng.submit(prompt_of(5, seed=33), max_new_tokens=30,
                         denoising_steps=2)
    late = None
    for _ in range(40):
        events = eng.step()
        if late is not None:
            # the very next tick runs its first block (a prompt of 3 has no
            # whole block to prefill)
            assert eng.scheduler.get(late).block_forwards == 1
            break
        if any(e.rid == short and e.finished for e in events):
            assert eng._in_flight is None     # nothing was planned blind
            eng.cancel(waiting)
            late = eng.submit(prompt_of(3, seed=34), max_new_tokens=30,
                              denoising_steps=2)
    else:
        raise AssertionError("the short request never finished")
    assert eng.stats["ticks_ahead"] > 0 == eng.stats["ahead_void_rows"]
    eng.run()


def test_sixteen_staggered_requests_are_mostly_launched_ahead(tiny):
    """The cell's traffic at a tiny size: 16 slots, requests of 40 blocks
    (120 ticks at 2 denoising steps) staggered three ticks apart. Only the
    tick behind a request's last commit is planned synchronously."""
    cfg, params = dataclasses.replace(tiny[0], max_seq_len=256), tiny[1]
    eng = engine(cfg, params, max_batch=16, token_budget=64, num_blocks=352,
                 max_len=256)
    left = [prompt_of(5 + i, seed=40 + i) for i in range(16)]
    while eng.has_work() or left:
        if left and eng.stats["steps"] % 3 == 0:
            eng.submit(left.pop(), max_new_tokens=160, denoising_steps=2)
        eng.step()
    s = eng.stats
    assert len(eng.run()) == 16 and s["ahead_void_rows"] == 0
    assert s["diff_blocks_committed"] == sum(
        -(-((5 + i) % BD + 160) // BD) for i in range(16))
    assert s["ticks_ahead"] / s["steps"] >= 0.85
    assert s["step_builds"] == 1


# ---- the block-causal read, both paths -------------------------------------

def paged_case(dtype=jnp.float32, seed=0):
    """Four slots of one block each at contexts 8..40 in a pool of 8-slot
    pages: (q [16, KV, G, hd], pools [1, nb, KV, 8, hd], tables, past)."""
    B, KV, G, hd, bs, width = 4, 2, 2, 32, 8, 6
    past = np.asarray([8, 20, 32, 40], np.int32)
    rng = np.random.default_rng(seed)
    tables = rng.permutation(B * width).reshape(B, width).astype(np.int32)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (B * BD, KV, G, hd), dtype)
    k = jax.random.normal(keys[1], (1, B * width, KV, bs, hd), dtype)
    v = jax.random.normal(keys[2], (1, B * width, KV, bs, hd), dtype)
    return q, k, v, jnp.asarray(tables), jnp.asarray(past)


def dense_reference(case, block_length):
    q, k, v, tables, past = case
    B, (_, _, KV, bs, hd) = tables.shape[0], k.shape
    rows = lambda pool: pool[0][tables].transpose(0, 1, 3, 2, 4).reshape(
        B, -1, KV, hd)
    with jax.default_matmul_precision("highest"):
        ref = jax.vmap(lambda qb, kb, vb, pb: R.block_causal_attention(
            qb, kb, vb, pb, block_length))(
            q.reshape(B, BD, -1, hd), rows(k), rows(v), past)
    return np.asarray(ref).reshape(B * BD, -1, hd)


def read_through(path, case, block_len, short=0):
    """The paged read of `case` through one path: "mixed" and "blockspec"
    are the kernel's two walks (interpret mode), "stock" the XLA gather of
    `paged_layer_attention` (the write it does first is of zeros' worth:
    k and v rows that the pool already holds)."""
    q, k, v, tables, past = case
    B, (_, _, KV, bs, hd) = tables.shape[0], k.shape
    this = jnp.full((B,), BD, jnp.int32)
    cu = jnp.arange(B + 1, dtype=jnp.int32) * BD
    if path == "stock":
        G = q.shape[2]
        pos = (past[:, None] + jnp.arange(BD)[None]).reshape(-1)
        slot = jnp.repeat(jnp.arange(B), BD)
        page = tables[slot, pos // bs]
        held = lambda pool: pool[0][page, :, pos % bs]       # [tok, KV, hd]
        qkv = jnp.concatenate([q.reshape(B * BD, KV * G, hd), held(k),
                               held(v)], axis=1).reshape(B * BD, -1)
        o = SA.paged_layer_attention(
            qkv, k, v, jnp.int32(0), past - short, this, cu, tables,
            use_pallas=False, block_length=block_len)[0]
        return np.asarray(o).reshape(B * BD, -1, hd)
    whole = PA.whole_pages
    try:
        if path == "blockspec":
            PA.whole_pages = lambda hd, interpret=None: False
        o = PA.paged_attention_packed(
            q, k, v, tables, past - short, this, cu, hd ** -0.5,
            layer=jnp.int32(0), block_len=block_len)
    finally:
        PA.whole_pages = whole
    return np.asarray(o).reshape(B * BD, -1, hd)


def worst(out, ref):
    return float(np.abs(out - ref).max() / np.abs(ref).max())


@pytest.mark.parametrize("path", ["stock", "mixed", "blockspec"])
def test_block_causal_read_against_dense_attention(path):
    """Each read path under the block-causal mask equals dense float32
    attention under M to 1e-5; under the causal mask, or told the block
    starts one block earlier (a mask one block short), it is off by whole
    percents, and under the causal mask it equals the causal reference."""
    case = paged_case()
    ref = dense_reference(case, BD)
    assert worst(read_through(path, case, BD), ref) < 1e-5
    causal = read_through(path, case, 0)
    assert worst(causal, ref) > 1e-2
    assert worst(causal, dense_reference(case, 0)) < 1e-5
    if path != "stock":     # the stock path writes where `past` says
        assert worst(read_through(path, case, BD, short=BD), ref) > 1e-2


def test_decode_launch_refuses_a_block():
    case = paged_case()
    q, k, v, tables, past = case
    with pytest.raises(ValueError, match="decode"):
        SA.paged_layer_attention(
            jnp.zeros((16, (4 + 2 + 2) * 32)), k, v, jnp.int32(0), past,
            jnp.ones((4,), jnp.int32), jnp.arange(5, dtype=jnp.int32) * 4,
            tables, use_pallas="decode", block_length=BD)


def test_mixed_work_counts_to_the_end_of_the_block():
    """The host's mirror of the mixed launch's walk: under the block mask
    a tile's keys end with its last row's block, never past the sequence."""
    args = ([0, 14], [6, 2], 16, 4, 2, 2, 8, 4, 8)    # pages of 4 positions
    causal = PA.mixed_work(*args)
    block = PA.mixed_work(*args, block_len=4)
    assert block["attn_rows_packed"] == causal["attn_rows_packed"]
    assert block["attn_pages_fetched"] >= causal["attn_pages_fetched"]


# ---- the model's own keys ---------------------------------------------------

def test_per_head_qk_norm_against_the_formula(tiny):
    cfg, params = tiny
    lp = jax.tree.map(lambda a: a[0], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(3), (5, 4 * 32 + 2 * 32))
    q, k = L.qk_normed(x[:, :128], x[:, 128:], lp, cfg)

    def formula(v, w):
        v = np.asarray(v, np.float64).reshape(5, -1, 32)
        return (v / np.sqrt((v * v).mean(-1, keepdims=True) + cfg.rms_eps)
                * np.asarray(w, np.float64)).reshape(5, -1)

    np.testing.assert_allclose(q, formula(x[:, :128], lp["q_norm"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(k, formula(x[:, 128:], lp["k_norm"]),
                               rtol=1e-5, atol=1e-6)
    # OLMoE's form on the same vectors is another function
    whole = dataclasses.replace(cfg, qk_norm_per_head=False)
    lw = {"q_norm": jnp.tile(lp["q_norm"], 4), "k_norm": jnp.tile(
        lp["k_norm"], 2)}
    assert not np.allclose(L.qk_normed(x[:, :128], x[:, 128:], lw, whole)[0],
                           q, atol=1e-3)
    assert lp["q_norm"].shape == (32,) and lp["wq"].shape == (64, 128)


def test_forward_takes_the_block_causal_mask(tiny):
    cfg, params = tiny
    tokens = jnp.asarray(prompt_of(19, seed=11), jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(R.forward_full(params, tokens, block_length=BD,
                                        **ref_kw(cfg)))
        got = np.asarray(L.forward(params, tokens[None], cfg)[0])
        causal = np.asarray(L.forward(
            params, tokens[None], dataclasses.replace(cfg, block_length=0))[0])
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() < 1e-4 * scale
    assert np.abs(causal - ref).max() > 1e-2 * scale
    with pytest.raises(ValueError, match="block-causal"):
        L.forward(params, tokens[None], cfg, attn_impl="flash")


def test_block_logits_from_kept_keys_equal_the_recomputed_prefix(tiny):
    """`block_logits_kv` (the prefix's keys and values from one
    `forward_full` over the final tokens) against `block_logits` (the
    prefix recomputed) to float32 rounding, for blocks at several starts,
    with tokens behind the block that the prefix must not see."""
    cfg, params = tiny
    kw = ref_kw(cfg)
    seq = jnp.asarray(prompt_of(32, seed=12), jnp.int32)
    with jax.default_matmul_precision("highest"):
        _, kv = R.forward_full(params, seq, jnp.zeros((1,), jnp.int32),
                               block_length=BD, with_kv=True, **kw)
        for start in (0, 8, 20):
            x_t = jnp.asarray([7, MASK, 9, MASK], jnp.int32)
            a = np.asarray(R.block_logits_kv(params, kv, x_t,
                                             jnp.int32(start),
                                             block_length=BD, **kw))
            b = np.asarray(R.block_logits(params, seq[:start], x_t,
                                          block_length=BD, **kw))
            assert np.abs(a - b).max() < 1e-5 * np.abs(b).max()


def test_defaults_leave_every_other_model_as_it_was():
    cfg = L.CONFIGS["llama-test"]
    assert (cfg.head_dim, cfg.block_length, cfg.qk_norm_per_head) == (16, 0,
                                                                      False)
    assert dataclasses.replace(cfg, hidden_size=128,
                               head_dim=0).head_dim == 32
    # an autoregressive engine knows no denoising steps
    eng = PagedServingEngine(cfg, L.init_params(cfg, jax.random.PRNGKey(0)),
                             block_size=8, max_batch=2, token_budget=16)
    with pytest.raises(ValueError, match="autoregressive"):
        eng.submit([1, 2, 3], denoising_steps=2)
    assert "diff_rows" not in eng.stats


# ---- what a block-diffusion config refuses ----------------------------------

@pytest.mark.parametrize("kw, what", [
    (dict(temperature=0.7), "greedy"),
    (dict(top_p=0.9), "greedy"),
    (dict(adapter="a"), "LoRA"),
    (dict(top_k=5), "greedy"),
    (dict(denoising_steps=5), "denoising_steps"),
    (dict(denoising_steps=0), "denoising_steps"),
])
def test_submit_refuses_with_a_message(tiny, kw, what):
    cfg, params = tiny
    eng = engine(cfg, params)
    with pytest.raises((NotImplementedError, ValueError), match=what):
        eng.submit([1, 2, 3, 4, 5], max_new_tokens=4, **kw)
    assert not eng.has_work()


def test_construction_refuses_with_a_message(tiny):
    from paddle_tpu.inference.llm import LLMPredictor
    cfg, params = tiny
    dense = dataclasses.replace(cfg, num_experts=0)
    dparams = L.init_params(dense, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="draft"):
        engine(dense, dparams, draft=(dense, dparams))
    with pytest.raises(NotImplementedError, match="top_k"):
        engine(cfg, params, top_k=5)
    with pytest.raises(ValueError, match="block_length"):
        engine(cfg, params, block_size=6)
    with pytest.raises(NotImplementedError, match="int8 pages"):
        engine(cfg, params, quant_kv=True)
    with pytest.raises(NotImplementedError, match="block-diffusion"):
        LLMPredictor(cfg, params)
    with pytest.raises(ValueError, match="max_len"):
        # 126 positions fit max_len, the last block's 128 do not
        engine(cfg, params, max_len=126).submit(prompt_of(100),
                                                max_new_tokens=26)
