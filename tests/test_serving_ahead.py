"""The serve tick that is launched ahead (`PagedServingEngine.step`): the
next tick is called before the last one's ids are read wherever it is
determined by counts, its decode rows fed from the last tick's output on
the device.

Contract: the events of every `step()` call, and so every token stream,
finish reason and `Completion`, are those of the synchronous order (the
predicate `_next_is_determined` patched to False); what the host learns a
tick late (an end-of-sequence id, a deadline) costs one row computed and
dropped, never a token; whatever reads sequences or pools from outside a
tick sees the tick in flight settled.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.llm import LLMPredictor
from paddle_tpu.inference.serving import BlockManager, PagedServingEngine
from paddle_tpu.inference.serving.block_manager import _chain_hash
from paddle_tpu.inference.serving.scheduler import UNKNOWN
from paddle_tpu.models import llama as L


@pytest.fixture(scope="module")
def tiny():
    cfg = L.LlamaConfig(vocab_size=97, hidden_size=32,
                        intermediate_size=64, num_layers=2, num_heads=4,
                        num_kv_heads=2, max_seq_len=96, dtype=jnp.float32)
    return cfg, L.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def reference(tiny):
    """Greedy tokens of the per-token host loop, cut before `eos`."""
    cfg, params = tiny
    pred = LLMPredictor(cfg, params, max_len=96, attn_impl="xla")
    memo = {}

    def ref(tokens, max_new, eos=None):
        key = (tuple(tokens), max_new)
        if key not in memo:
            seq, _ = pred.generate(jnp.asarray(tokens, jnp.int32)[None, :],
                                   max_new_tokens=max_new,
                                   return_scores=True)
            memo[key] = [int(t) for t in np.asarray(seq)[0, len(tokens):]]
        gen = memo[key]
        return gen[:gen.index(eos)] if eos in gen else gen

    return ref


def _prompt(cfg, n, seed):
    return np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (n,)).tolist()


def _engine(tiny, **kw):
    kw = {**dict(num_blocks=48, block_size=4, max_batch=4, token_budget=8),
          **kw}
    return PagedServingEngine(*tiny, **kw)


def _drive(eng):
    """Step until idle: the event list of every call, as plain tuples."""
    calls = []
    while eng.has_work():
        calls.append([(e.rid, e.token, e.finished, e.reason)
                      for e in eng.step()])
    return calls


def _step_until_in_flight(eng, rid, tokens_out=2):
    """Step until `rid` has streamed `tokens_out` tokens and a tick that
    holds a row of it is in flight."""
    got = 0
    for _ in range(64):
        got += sum(e.rid == rid and e.token >= 0 for e in eng.step())
        cur = eng._in_flight
        if got >= tokens_out and cur is not None and any(
                s.rid == rid for s, _ in cur.batch.items):
            return got
    raise AssertionError("no tick with a row of the request in flight")


# ---------------------------------------------------------------------------
# (a) the synchronous order's events, call by call
# ---------------------------------------------------------------------------

# prompts longer than the budget (chunked), staggered lengths and budgets,
# more requests than slots (a queue), greedy and sampled rows, one that
# stops at an end-of-sequence id: (prompt_len, max_new, temperature, seed)
MIXED = [(19, 7, 0.0, 0), (3, 12, 0.0, 0), (11, 5, 0.8, 7), (6, 9, 0.0, 0),
         (23, 4, 1.1, 3), (2, 10, 0.0, 0), (9, 6, 0.0, 0)]


def _mixed_run(tiny, reference, pallas, ahead, monkeypatch):
    cfg, _ = tiny
    eng = _engine(tiny, pallas=pallas)
    if not ahead:
        monkeypatch.setattr(eng, "_next_is_determined", lambda cur: False)
    stop = _prompt(cfg, 5, seed=140)
    rids = [eng.submit(_prompt(cfg, n, seed=100 + i), max_new_tokens=new,
                       temperature=temp or None, top_p=0.9 if temp else None,
                       seed=seed)
            for i, (n, new, temp, seed) in enumerate(MIXED)]
    rids.append(eng.submit(stop, max_new_tokens=12,
                           eos_token_id=reference(stop, 12)[5]))
    calls = _drive(eng)
    done = {c.rid: (c.output_tokens, c.finish_reason) for c in eng.run()}
    streams = {r: list(eng.stream(r)) for r in rids}
    return eng, calls, done, streams


@pytest.mark.parametrize("pallas", [False, True], ids=["stock", "kernel"])
def test_events_are_those_of_the_synchronous_order(tiny, reference, pallas,
                                                   monkeypatch):
    eng, calls, done, streams = _mixed_run(tiny, reference, pallas, True,
                                           monkeypatch)
    sync, calls0, done0, streams0 = _mixed_run(tiny, reference, pallas,
                                               False, monkeypatch)
    assert calls == calls0            # one tick's events a call, in order
    assert done == done0 and streams == streams0
    assert sync.stats["ticks_ahead"] == 0 == sync.stats["ahead_void_rows"]
    assert eng.stats["ticks_ahead"] > eng.stats["steps"] // 2
    assert eng.stats["steps"] == sync.stats["steps"] == len(calls)
    assert eng.stats["tokens_computed"] == (
        sync.stats["tokens_computed"] + eng.stats["ahead_void_rows"])
    # the greedy rows are the host loop's tokens either way
    cfg, _ = tiny
    for i, (n, new, temp, _) in enumerate(MIXED):
        if not temp:
            assert done[i][0] == reference(_prompt(cfg, n, 100 + i), new)
    assert all(done[i][1] == "length" for i in range(len(MIXED)))
    assert done[len(MIXED)][1] == "stop"
    assert eng.blocks.num_allocated() == 0


def test_no_new_executable_and_no_unknown_id_left(tiny):
    """A tick launched ahead runs the executable every tick of its shape
    runs: one mixed and one decode build, whatever the order."""
    cfg, _ = tiny
    eng = _engine(tiny, pallas=True)
    seqs = []
    for i, n in enumerate((13, 4, 7)):
        rid = eng.submit(_prompt(cfg, n, seed=30 + i), max_new_tokens=6)
        seqs.append(eng.scheduler.get(rid))
    eng.run()
    assert eng.stats["ticks_ahead"] > 0
    assert eng.stats["step_builds"] == 2 == len(eng._step_fns)
    for seq in seqs:
        assert UNKNOWN not in seq.tokens
        assert seq.tokens == seq.prompt + seq.generated


# ---------------------------------------------------------------------------
# (b), (c) what the host learns a tick late
# ---------------------------------------------------------------------------

def test_eos_with_a_row_in_flight_is_one_void_row(tiny, reference):
    cfg, _ = tiny
    prompt = _prompt(cfg, 6, seed=41)
    full = reference(prompt, 12)
    k = next(i for i in range(3, 12) if full[i] not in full[:i])
    eng = _engine(tiny, num_blocks=6)
    rid = eng.submit(prompt, max_new_tokens=12, eos_token_id=full[k])
    table = []
    while eng.has_work():
        eng.step()
        table = eng.blocks._tables.get(rid, table)
    (done,) = eng.run()
    assert done.output_tokens == full[:k] and done.finish_reason == "stop"
    assert list(eng.stream(rid)) == full[:k]
    assert eng.stats["ahead_void_rows"] == 1
    assert eng.stats["ticks_ahead"] >= k
    # the row in flight embedded the end-of-sequence id at position
    # len(prompt) + k: its page is free and was never hashed, nor any other
    # page beyond what the harvested ticks filled
    assert eng.blocks.num_allocated() == 0
    at = len(prompt) + k
    hashed = [b for b in table if b in eng.blocks._block_hash]
    assert hashed == table[:at // 4]
    assert table[at // 4] in eng.blocks._free
    # whoever gets those pages next decodes as the reference does
    for seed in (42, 43):
        other = _prompt(cfg, 9, seed=seed)
        r2 = eng.submit(other, max_new_tokens=8)
        assert {c.rid: c.output_tokens for c in eng.run()}[r2] == \
            reference(other, 8)
    assert set(table) <= set(eng.blocks._free) | set(
        eng.blocks._cached_free)


def test_cancel_with_a_row_in_flight(tiny, reference):
    """`cancel` settles the tick in flight: its token is not lost, the
    stream ends `cancelled` behind it, the pool is whole."""
    cfg, _ = tiny
    prompt, other = _prompt(cfg, 5, seed=51), _prompt(cfg, 7, seed=52)
    eng = _engine(tiny)
    rid = eng.submit(prompt, max_new_tokens=20)
    keep = eng.submit(other, max_new_tokens=9)
    seen = _step_until_in_flight(eng, rid)
    assert eng.cancel(rid) and eng._in_flight is None
    assert not eng.cancel(rid)
    seq = eng.scheduler.get(rid)
    assert UNKNOWN not in seq.tokens and len(seq.generated) == seen + 1
    events = eng.step()           # the settled tick's events, and no more
    assert [(e.rid, e.token) for e in events if e.rid == rid] == [
        (rid, seq.generated[-1])]
    assert list(eng.stream(rid)) == seq.generated
    done = {c.rid: c for c in eng.run()}
    assert done[rid].finish_reason == "cancelled"
    assert done[rid].output_tokens == reference(prompt, 20)[:seen + 1]
    assert done[keep].output_tokens == reference(other, 9)
    assert eng.stats["ahead_void_rows"] == 0
    assert eng.blocks.num_allocated() == 0


def test_deadline_with_a_row_in_flight(tiny, reference):
    """A deadline that falls while the sequence has a row in flight: the
    row is computed and dropped, the stream ends `deadline` with the
    tokens harvested before it."""
    cfg, _ = tiny
    prompt, other = _prompt(cfg, 5, seed=61), _prompt(cfg, 7, seed=62)
    eng = _engine(tiny)
    rid = eng.submit(prompt, max_new_tokens=20, deadline_s=3600.0)
    keep = eng.submit(other, max_new_tokens=9)
    seen = _step_until_in_flight(eng, rid)
    eng.scheduler.get(rid).deadline = time.monotonic() - 1.0
    done = {c.rid: c for c in eng.run()}
    assert done[rid].finish_reason == "deadline"
    assert done[rid].output_tokens == reference(prompt, 20)[:seen]
    assert done[keep].output_tokens == reference(other, 9)
    assert eng.stats["ahead_void_rows"] == 1
    assert eng.scheduler.stats["deadline_expired"] == 1
    assert eng.blocks.num_allocated() == 0


# ---------------------------------------------------------------------------
# (d) pages filled by launched-ahead ticks serve prefix hits
# ---------------------------------------------------------------------------

def test_a_turn_that_resends_the_answer_hits_the_pages(tiny, reference):
    cfg, _ = tiny
    prompt = _prompt(cfg, 9, seed=71)
    eng = _engine(tiny)
    eng.submit(prompt, max_new_tokens=14)
    (first,) = eng.run()
    assert eng.stats["ticks_ahead"] >= 12
    turn = prompt + first.output_tokens + _prompt(cfg, 5, seed=72)
    hits0 = eng.blocks.stats["prefix_hit_tokens"]
    rid = eng.submit(turn, max_new_tokens=6)
    (second,) = eng.run()
    # 22 of the 23 positions behind the last token were computed (the last
    # id is never fed back), so 5 whole pages are addressed: 9 prompt
    # tokens and 11 that decode rows launched ahead wrote
    assert eng.blocks.stats["prefix_hit_tokens"] - hits0 == 20
    assert second.rid == rid
    assert second.output_tokens == reference(turn, 6)


# ---------------------------------------------------------------------------
# (e) a tick that frees a slot is followed by one planned with everything
# known
# ---------------------------------------------------------------------------

def test_tick_after_a_freed_slot_admits_what_was_just_submitted(tiny):
    cfg, _ = tiny
    eng = _engine(tiny, max_batch=2)
    short = eng.submit(_prompt(cfg, 4, seed=81), max_new_tokens=3)
    eng.submit(_prompt(cfg, 6, seed=82), max_new_tokens=30)
    waiting = eng.submit(_prompt(cfg, 5, seed=83), max_new_tokens=30)
    late = None
    for _ in range(40):
        events = eng.step()
        if late is not None:
            # the very next tick holds both: the queued request, and the
            # one submitted in the iteration that saw the last token
            assert {e.rid for e in events if e.token >= 0} >= {late}
            assert eng.scheduler.get(waiting).num_computed > 0
            break
        if any(e.rid == short and e.finished for e in events):
            assert eng._in_flight is None     # nothing was planned blind
            eng.cancel(waiting)
            waiting = eng.submit(_prompt(cfg, 5, seed=84),
                                 max_new_tokens=30)
            late = waiting
    else:
        raise AssertionError("the short request never finished")
    assert eng.stats["ticks_ahead"] > 0


# ---------------------------------------------------------------------------
# (g) readers from outside a tick
# ---------------------------------------------------------------------------

def test_extract_pages_settles_the_tick_in_flight(tiny, reference):
    cfg, _ = tiny
    prompt = _prompt(cfg, 13, seed=91)
    eng = _engine(tiny, token_budget=16)
    rid = eng.submit(prompt, max_new_tokens=10)
    seen = _step_until_in_flight(eng, rid, tokens_out=3)
    payload = eng.extract_pages(prompt)
    assert eng._in_flight is None and eng.has_work()
    assert [d for d, _ in payload["chain"]] == [4, 8, 12]
    assert payload["k"].shape[1] == 3
    stats = eng.engine_stats              # settles too; nothing in flight
    assert stats["steps"] == eng.stats["steps"]
    rest = [tok for call in _drive(eng) for _, tok, _, _ in call if tok >= 0]
    assert len(rest) == 10 - seen
    (done,) = eng.run()
    assert done.output_tokens == reference(prompt, 10)
    # a second engine adopts the pages while ITS tick is in flight
    eng2 = _engine(tiny, token_budget=16)
    r2 = eng2.submit(_prompt(cfg, 6, seed=92), max_new_tokens=8)
    _step_until_in_flight(eng2, r2)
    assert eng2.ingest_pages(payload) == 3 and eng2._in_flight is None
    r3 = eng2.submit(prompt, max_new_tokens=10)
    out = {c.rid: c.output_tokens for c in eng2.run()}
    assert out[r3] == reference(prompt, 10)
    assert out[r2] == reference(_prompt(cfg, 6, seed=92), 8)
    assert eng2.blocks.stats["prefix_hit_tokens"] == 12


# ---------------------------------------------------------------------------
# the two halves of a tick's progress, and the pool's hashing
# ---------------------------------------------------------------------------

def test_progress_by_count_at_dispatch_ids_at_harvest(tiny):
    cfg, _ = tiny
    eng = _engine(tiny)
    prompt = _prompt(cfg, 6, seed=95)
    rid = eng.submit(prompt, max_new_tokens=8)
    seq = eng.scheduler.get(rid)
    eng.step()                    # the prompt's tick, and a decode row ahead
    cur = eng._in_flight
    assert cur is not None and cur.ahead and cur.slots == {rid: 0}
    # harvested: one token; dispatched: one more position, id unknown
    assert len(seq.generated) == 1 and seq.tokens[-1] == UNKNOWN
    assert seq.tokens[:-1] == prompt + seq.generated
    assert seq.num_computed == len(prompt) + 1 == cur.ends[0]
    # only pages whose ids are all read are hashed
    assert sorted(eng.blocks._block_hash) == eng.blocks.block_table(rid)[:1]
    with pytest.raises(RuntimeError, match="row in flight"):
        eng.scheduler._preempt(seq)
    eng.run()
    assert UNKNOWN not in seq.tokens and len(seq.generated) == 8


def test_not_launched_ahead_where_planning_would_preempt(tiny, reference):
    """A pool too small for the running sequences' next rows: those ticks
    are planned with every id known, and preemption recomputes exactly."""
    cfg, _ = tiny
    eng = _engine(tiny, num_blocks=9, max_batch=3)
    prompts = [_prompt(cfg, 7, seed=s) for s in (96, 97, 98)]
    rids = [eng.submit(p, max_new_tokens=14) for p in prompts]
    done = {c.rid: c.output_tokens for c in eng.run()}
    assert eng.scheduler.stats["preemptions"] > 0
    assert 0 < eng.stats["ticks_ahead"] < eng.stats["steps"]
    for rid, p in zip(rids, prompts):
        assert done[rid] == reference(p, 14)


@pytest.mark.parametrize("cuts", [(8,), (3, 4, 8), (4, 9, 12)])
def test_register_computed_goes_on_from_the_last_page(cuts):
    """Hashing in steps addresses what one call addresses, and each call
    reads only the pages that filled since the last."""
    toks = list(range(20, 33))
    bm = BlockManager(num_blocks=8, block_size=4)
    bm.allocate_sequence(1, toks)
    seen = []

    class Spy(list):
        def __getitem__(self, s):
            seen.append((s.start, s.stop))
            return list.__getitem__(self, s)

    for n in cuts:
        bm.register_computed(1, Spy(toks), n)
    pages = max(cuts) // 4
    assert seen == [(4 * i, 4 * i + 4) for i in range(pages)]
    h, want = 0, {}
    for i in range(pages):
        h = _chain_hash(h, tuple(toks[4 * i:4 * i + 4]))
        want[h] = bm.block_table(1)[i]
    assert bm._hash_to_block == want
    # a sequence admitted over those pages goes on behind its hits
    seen.clear()
    assert bm.allocate_sequence(2, toks) == pages * 4
    bm.register_computed(2, Spy(toks), 12)
    assert seen == [(4 * i, 4 * i + 4) for i in range(pages, 3)]
