"""Paged-KV continuous-batching serving subsystem tests.

Parity contract: every request scheduled through the paged engine must
produce EXACTLY the tokens the single-request `LLMPredictor` host loop
(`return_scores=True` → `_generate_hostloop`) produces — paged blocks,
chunked prefill, continuous batching and even forced preemption/resume
are scheduling/memory optimizations, not numerics changes.

Also covers: block-manager alloc/free/refcount/prefix-cache/COW/LRU
semantics, load shedding (`RejectedError`), deadlines, cancellation,
streaming delivery, sampling determinism, zero-retrace steady state, the
`observability.summary()["serving"]` SLO surface, and the chaos harness's
`serving:stall` → deadline path.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.distributed.fault_tolerance import chaos
from paddle_tpu.inference.llm import LLMPredictor
from paddle_tpu.inference.serving import (BlockManager,
                                          DeadlineExceededError,
                                          NoFreeBlocksError,
                                          PagedServingEngine, RejectedError)
from paddle_tpu.models import llama as L


@pytest.fixture(scope="module")
def tiny():
    cfg = L.LlamaConfig(vocab_size=97, hidden_size=32,
                        intermediate_size=64, num_layers=2, num_heads=4,
                        num_kv_heads=2, max_seq_len=96, dtype=jnp.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def hostloop_ref(tiny):
    """Greedy reference via the per-token host loop (the ISSUE's parity
    target); memoized because every step dispatches separately."""
    cfg, params = tiny
    pred = LLMPredictor(cfg, params, max_len=96, attn_impl="xla")
    memo = {}

    def ref(tokens, max_new, eos=None):
        key = (tuple(tokens), max_new, eos)
        if key not in memo:
            seq, _ = pred.generate(jnp.asarray(tokens, jnp.int32)[None, :],
                                   max_new_tokens=max_new, eos_token_id=eos,
                                   return_scores=True)
            gen = [int(t) for t in np.asarray(seq)[0, len(tokens):]]
            if eos is not None and eos in gen:
                gen = gen[:gen.index(eos)]
            memo[key] = gen
        return memo[key]

    return ref


def _prompts(cfg, n, lens, seed=1):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, cfg.vocab_size, (ln,)).tolist()
            for ln, _ in zip((lens * n)[:n], range(n))]


# ---------------------------------------------------------------------------
# BlockManager unit tests (pure host-side, no model)
# ---------------------------------------------------------------------------

class TestBlockManager:
    def test_alloc_grow_free_roundtrip(self):
        bm = BlockManager(num_blocks=8, block_size=4)
        cached = bm.allocate_sequence(1, [1, 2, 3, 4, 5])    # 2 blocks
        assert cached == 0 and len(bm.block_table(1)) == 2
        assert bm.num_allocated() == 2
        assert bm.ensure_capacity(1, 9) == 1                 # 3rd block
        assert bm.utilization() == pytest.approx(3 / 8)
        bm.free_sequence(1)
        assert bm.num_free() == 8 and not bm.has_sequence(1)

    def test_prefix_sharing_by_refcount(self):
        bm = BlockManager(num_blocks=8, block_size=4)
        toks = list(range(8))
        bm.allocate_sequence(1, toks + [99])
        bm.register_computed(1, toks + [99], 8)
        cached = bm.allocate_sequence(2, toks + [55])
        assert cached == 8
        t1, t2 = bm.block_table(1), bm.block_table(2)
        assert t1[:2] == t2[:2]                  # physically shared pages
        assert bm.ref_count(t1[0]) == 2
        assert bm.stats["prefix_hit_blocks"] == 2
        bm.free_sequence(2)
        assert bm.ref_count(t1[0]) == 1          # seq 1 still holds them

    def test_whole_prompt_hit_demotes_final_block_to_cow(self):
        """A prompt fully covered by cached blocks must NOT write its
        recomputed last token into a shared page."""
        bm = BlockManager(num_blocks=8, block_size=4)
        toks = list(range(8))
        bm.allocate_sequence(1, toks)
        bm.register_computed(1, toks, 8)
        cached = bm.allocate_sequence(2, toks)   # identical prompt
        assert cached == 7                       # always recompute the last
        t1, t2 = bm.block_table(1), bm.block_table(2)
        assert t1[0] == t2[0] and t1[1] != t2[1]  # final block is private
        assert bm.take_copies() == [(t1[1], t2[1])]
        assert bm.stats["cow_copies"] == 1

    def test_partial_block_hit_is_copy_on_write(self):
        bm = BlockManager(num_blocks=8, block_size=4)
        toks = [1, 2, 3, 4, 5, 6, 7, 8]
        bm.allocate_sequence(1, toks)
        bm.register_computed(1, toks, 8)
        # same first block, second block shares only 3 of 4 tokens
        cached = bm.allocate_sequence(2, [1, 2, 3, 4, 5, 6, 7, 77])
        assert cached == 4 + 3
        t1, t2 = bm.block_table(1), bm.block_table(2)
        assert t1[0] == t2[0] and t1[1] != t2[1]
        assert bm.take_copies() == [(t1[1], t2[1])]

    def test_freed_cached_blocks_serve_hits_until_reclaimed(self):
        bm = BlockManager(num_blocks=3, block_size=4)
        toks = list(range(4))
        bm.allocate_sequence(1, toks + [9])
        bm.register_computed(1, toks + [9], 4)
        bm.free_sequence(1)                      # parked, still addressable
        assert bm.num_free() == 3
        assert bm.allocate_sequence(2, toks + [7]) == 4   # revived
        bm.free_sequence(2)
        # pressure reclaims the LRU cached page and drops its hash
        bm.allocate_sequence(3, list(range(50, 62)))      # needs all 3
        assert bm.stats["cache_evictions"] >= 1
        bm.free_sequence(3)
        assert bm.allocate_sequence(4, toks + [7]) == 0   # hash gone

    def test_cancel_with_pending_cow_purges_copies(self):
        """A sequence freed while its COW copies are still pending must
        take those pairs with it: a stale (src, dst) surviving the free
        would clobber dst after the page is reallocated."""
        bm = BlockManager(num_blocks=8, block_size=4)
        toks = list(range(8))
        bm.allocate_sequence(1, toks)
        bm.register_computed(1, toks, 8)
        bm.allocate_sequence(2, toks)            # whole-hit → pending COW
        assert bm.stats["cow_copies"] == 1
        bm.free_sequence(2)                      # cancelled pre-step
        assert bm.stats["cow_purged"] == 1
        assert bm.take_copies() == []            # nothing stale survives
        bm.free_sequence(1)
        assert bm.num_free() == 8                # every pin released

    def test_pending_cow_pins_shared_source(self):
        """The src of a pending copy holds an extra ref until the copy
        executes, so neither a free nor LRU reclaim can retire the page
        out from under the device copy."""
        bm = BlockManager(num_blocks=8, block_size=4)
        toks = [1, 2, 3, 4, 5, 6, 7, 8]
        bm.allocate_sequence(1, toks)
        bm.register_computed(1, toks, 8)
        bm.allocate_sequence(2, [1, 2, 3, 4, 5, 6, 7, 77])
        t1 = bm.block_table(1)
        assert bm.ref_count(t1[1]) == 2          # seq 1's table + the pin
        assert bm.take_copies() == [(t1[1], bm.block_table(2)[1])]
        assert bm.ref_count(t1[1]) == 1          # pin released on drain

    def test_pending_cow_src_not_reclaimed_from_cache(self):
        """Partial-hit src living only in the parked LRU cache must be
        revived by the pin — under pool pressure the fresh-page loop in
        the SAME allocate call would otherwise reclaim it before the
        copy ran."""
        bm = BlockManager(num_blocks=3, block_size=4)
        toks = [1, 2, 3, 4, 5, 6, 7]
        bm.allocate_sequence(1, toks)
        bm.register_computed(1, toks, 7)
        bm.free_sequence(1)                      # both pages parked
        cached = bm.allocate_sequence(2, [1, 2, 3, 99, 100, 101, 102, 103])
        assert cached == 3                       # partial hit on block 0
        (src, dst), = bm.take_copies()
        assert src not in bm.block_table(2)      # src survived as src,
        assert dst == bm.block_table(2)[0]       # not recycled into the
        #                                          new table

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_partial_match_looks_behind_one_hash_and_finds_the_same(
            self, seed):
        """A partial match reads the pages registered behind the chain's
        last hash (`_children`), not every page the cache holds: through
        a churn of admissions, frees and evictions it names the page a
        scan of every cached page names, and the index holds exactly the
        hashes `_hash_info` holds, each behind its own parent."""
        def scan(bm, prev_h, rest):
            best_blk, best_n = None, 1
            for h, (ph, chunk) in bm._hash_info.items():
                blk = bm._hash_to_block.get(h)
                if ph != prev_h or blk is None or (
                        blk not in bm._refs and blk not in bm._cached_free):
                    continue
                n = next((i for i, (a, b) in enumerate(zip(chunk, rest))
                          if a != b), len(chunk))
                if n > best_n:
                    best_blk, best_n = blk, n
            return (best_blk, best_n) if best_blk is not None else None

        rs = np.random.RandomState(seed)
        bm = BlockManager(num_blocks=24, block_size=4)
        live, asked = [], 0
        for rid in range(200):
            # few ids and short prompts: prefixes repeat, pages share
            # parents, and 24 pages are reclaimed over and over
            toks = rs.randint(0, 3, rs.randint(5, 17)).tolist()
            try:
                bm.allocate_sequence(rid, toks)
            except NoFreeBlocksError:
                bm.free_sequence(live.pop(0))
                continue
            bm.take_copies()
            bm.register_computed(rid, toks, len(toks))
            live.append(rid)
            if len(live) > 3:
                bm.free_sequence(live.pop(rs.randint(len(live))))
            for prev_h in list(bm._children)[:6]:
                rest = rs.randint(0, 3, 4).tolist()
                assert bm._partial_match(prev_h, rest) == scan(
                    bm, prev_h, rest)
                asked += 1
            behind = {h: p for p, kids in bm._children.items()
                      for h in kids}
            assert behind == {h: info[0]
                              for h, info in bm._hash_info.items()}
            assert all(bm._children.values())    # no empty set is kept
        assert asked > 500 and bm.stats["cache_evictions"] > 20
        assert bm.stats["cow_copies"] > 20

    def test_a_list_is_read_as_it_stands_and_an_array_as_its_ints(self):
        """`allocate_sequence` converts what is not a list and reads a
        list as it stands; numpy's integers in one hash and compare as
        the ints they stand for, so all three find the same pages."""
        toks = list(range(10, 22))
        bm = BlockManager(num_blocks=16, block_size=4)
        bm.allocate_sequence(1, toks + [5])
        bm.register_computed(1, toks + [5], 12)
        for rid, form in enumerate((toks + [7], np.asarray(toks + [7]),
                                    [np.int32(t) for t in toks + [7]]), 2):
            assert bm.allocate_sequence(rid, form) == 12
            assert bm.block_table(rid)[:3] == bm.block_table(1)[:3]

    def test_exhaustion_raises_and_leaves_no_state(self):
        bm = BlockManager(num_blocks=2, block_size=4)
        bm.allocate_sequence(1, list(range(8)))
        with pytest.raises(NoFreeBlocksError):
            bm.allocate_sequence(2, [1, 2])
        assert not bm.has_sequence(2)
        with pytest.raises(NoFreeBlocksError):
            bm.ensure_capacity(1, 12)
        assert len(bm.block_table(1)) == 2       # unchanged
        bm.free_sequence(1)
        assert bm.num_free() == 2


# ---------------------------------------------------------------------------
# Engine parity + scheduling behavior
# ---------------------------------------------------------------------------

class TestPagedEngineParity:
    def test_mixed_length_batch_matches_hostloop(self, tiny, hostloop_ref):
        cfg, params = tiny
        eng = PagedServingEngine(cfg, params, num_blocks=48, block_size=4,
                                 max_batch=4, token_budget=16)
        prompts = _prompts(cfg, 5, [7, 2, 13, 5, 9], seed=2)
        budgets = [8, 11, 4, 9, 6]
        rids = [eng.submit(p, max_new_tokens=b)
                for p, b in zip(prompts, budgets)]
        done = {c.rid: c for c in eng.run()}
        assert len(done) == 5
        for rid, p, b in zip(rids, prompts, budgets):
            assert done[rid].output_tokens == hostloop_ref(p, b), \
                f"rid {rid} diverged"
            assert done[rid].finish_reason == "length"

    def test_preemption_resume_is_exact(self, tiny, hostloop_ref):
        """A pool too small for all three sequences forces eviction; the
        recompute-on-resume path must still be bit-exact."""
        cfg, params = tiny
        eng = PagedServingEngine(cfg, params, num_blocks=6, block_size=4,
                                 max_batch=3, token_budget=16)
        prompts = _prompts(cfg, 3, [6, 4, 3], seed=5)
        rids = [eng.submit(p, max_new_tokens=10, priority=i)
                for i, p in enumerate(prompts)]
        done = {c.rid: c for c in eng.run()}
        assert eng.scheduler.stats["preemptions"] >= 1
        for rid, p in zip(rids, prompts):
            assert done[rid].output_tokens == hostloop_ref(p, 10), \
                f"rid {rid} diverged after preemption"
        # the evicted sequences record their preemption count
        assert sum(s.preemptions for s in eng.scheduler._by_rid.values()) \
            == eng.scheduler.stats["preemptions"]

    def test_eos_stops_early(self, tiny, hostloop_ref):
        cfg, params = tiny
        prompt = _prompts(cfg, 1, [6], seed=4)[0]
        eos = hostloop_ref(prompt, 3)[2]
        eng = PagedServingEngine(cfg, params, num_blocks=32, block_size=4,
                                 max_batch=2, token_budget=16)
        rid = eng.submit(prompt, max_new_tokens=40, eos_token_id=eos)
        (done,) = eng.run()
        assert done.finish_reason == "stop"
        assert eos not in done.output_tokens
        assert done.output_tokens == hostloop_ref(prompt, 40, eos)

    def test_admitted_mid_flight_yields_what_it_yields_alone(
            self, tiny, hostloop_ref):
        cfg, params = tiny
        eng = PagedServingEngine(cfg, params, num_blocks=48, block_size=4,
                                 max_batch=3, token_budget=16)
        p1, p2 = _prompts(cfg, 2, [9, 6], seed=27)
        r1 = eng.submit(p1, max_new_tokens=12)
        for _ in range(4):
            eng.step()                            # r1 is decoding by now
        r2 = eng.submit(p2, max_new_tokens=7)
        done = {c.rid: c.output_tokens for c in eng.run()}
        assert done[r2] == hostloop_ref(p2, 7)
        assert done[r1] == hostloop_ref(p1, 12)

    @pytest.mark.parametrize("form", [
        list, tuple, lambda p: np.asarray(p, np.int32),
        lambda p: np.asarray(p, np.int64)[None, :], jnp.asarray,
        lambda p: [np.int32(t) for t in p]],
        ids=["list", "tuple", "int32", "int64_2d", "jax", "numpy_ints"])
    def test_submit_reads_any_form_of_ids_as_python_ints(self, tiny, form):
        """`submit` turns a prompt into a list of Python ints in one pass
        (the scheduler, the hashes and the tick's planning read that
        list), whatever it was handed."""
        cfg, params = tiny
        prompt = _prompts(cfg, 1, [9], seed=31)[0]
        eng = PagedServingEngine(cfg, params, num_blocks=32, block_size=4,
                                 max_batch=2, token_budget=16)
        eng.submit(form(prompt), max_new_tokens=2)
        (seq,) = eng.scheduler.waiting
        assert seq.tokens == prompt and type(seq.tokens) is list
        assert all(type(t) is int for t in seq.tokens)

    def test_eos_returns_the_requests_pages(self, tiny, hostloop_ref):
        cfg, params = tiny
        prompt = _prompts(cfg, 1, [6], seed=28)[0]
        ref = hostloop_ref(prompt, 5)
        eos = ref[4]
        assert eos not in ref[:4]                 # it ends the fifth tick
        eng = PagedServingEngine(cfg, params, num_blocks=32, block_size=4,
                                 max_batch=2, token_budget=16)
        eng.submit(prompt, max_new_tokens=40, eos_token_id=eos)
        eng.step()
        assert eng.blocks.num_allocated() > 0
        (done,) = eng.run()
        assert done.finish_reason == "stop" and done.output_tokens == ref[:4]
        assert eng.blocks.num_allocated() == 0
        assert eng.blocks.num_free() == 32

    def test_prefix_cache_reuses_blocks_across_requests(self, tiny):
        cfg, params = tiny
        eng = PagedServingEngine(cfg, params, num_blocks=32, block_size=4,
                                 max_batch=4, token_budget=32)
        shared = _prompts(cfg, 1, [9], seed=6)[0]     # 2 full blocks + 1
        r1 = eng.submit(shared, max_new_tokens=4)
        out1 = {c.rid: c for c in eng.run()}[r1]
        assert eng.blocks.stats["prefix_hit_blocks"] == 0
        r2 = eng.submit(shared, max_new_tokens=4)
        out2 = {c.rid: c for c in eng.run()}[r2]
        assert eng.blocks.stats["prefix_hit_blocks"] >= 2
        assert eng.blocks.stats["prefix_hit_tokens"] >= 8
        assert out1.output_tokens == out2.output_tokens

    def test_chunked_prefill_long_prompt(self, tiny, hostloop_ref):
        """A prompt longer than the token budget prefills across several
        steps, interleaved with a decoding request — both stay exact."""
        cfg, params = tiny
        eng = PagedServingEngine(cfg, params, num_blocks=48, block_size=4,
                                 max_batch=2, token_budget=8)
        short, long = _prompts(cfg, 2, [3, 30], seed=7)
        r1 = eng.submit(short, max_new_tokens=12)
        eng.step()                                    # r1 decoding
        r2 = eng.submit(long, max_new_tokens=5)       # 30 > budget 8
        done = {c.rid: c for c in eng.run()}
        assert done[r1].output_tokens == hostloop_ref(short, 12)
        assert done[r2].output_tokens == hostloop_ref(long, 5)

    def test_sampling_is_seed_deterministic(self, tiny):
        cfg, params = tiny

        def run():
            eng = PagedServingEngine(cfg, params, num_blocks=32,
                                     block_size=4, max_batch=2,
                                     token_budget=16)
            rid = eng.submit(_prompts(cfg, 1, [5], seed=8)[0],
                             max_new_tokens=8, temperature=0.9, top_p=0.95,
                             seed=123)
            return {c.rid: c for c in eng.run()}[rid].output_tokens

        a, b = run(), run()
        assert a == b and len(a) == 8

    def test_zero_budget_and_overlong(self, tiny):
        cfg, params = tiny
        eng = PagedServingEngine(cfg, params, num_blocks=32, block_size=4,
                                 max_batch=2, token_budget=16)
        rid = eng.submit([1, 2, 3], max_new_tokens=0)
        (done,) = eng.run()
        assert done.rid == rid and done.output_tokens == []
        with pytest.raises(ValueError):
            eng.submit(list(range(90)), max_new_tokens=10)
        with pytest.raises(ValueError):
            # fits max_len but can never fit the block pool
            small = PagedServingEngine(cfg, params, num_blocks=2,
                                       block_size=4, max_batch=1,
                                       token_budget=8)
            small.submit(list(range(10)), max_new_tokens=2)


class TestSchedulingPolicies:
    def test_load_shed_raises_rejected(self, tiny):
        cfg, params = tiny
        obs.reset()
        eng = PagedServingEngine(cfg, params, num_blocks=32, block_size=4,
                                 max_batch=1, token_budget=8, max_queue=2)
        for _ in range(2):
            eng.submit([1, 2], max_new_tokens=2)
        with pytest.raises(RejectedError):
            eng.submit([3, 4], max_new_tokens=2)
        assert eng.scheduler.stats["shed"] == 1
        assert obs.summary()["serving"]["shed"] == 1
        eng.run()                                 # queue still drains

    def test_deadline_expires_without_compute(self, tiny, hostloop_ref):
        cfg, params = tiny
        obs.reset()
        eng = PagedServingEngine(cfg, params, num_blocks=32, block_size=4,
                                 max_batch=2, token_budget=16)
        p1, p2 = _prompts(cfg, 2, [4, 3], seed=9)
        r1 = eng.submit(p1, max_new_tokens=6)
        r2 = eng.submit(p2, max_new_tokens=6, deadline_s=-1.0)  # born dead
        done = {c.rid: c for c in eng.run()}
        assert done[r2].finish_reason == "deadline"
        assert done[r2].output_tokens == []
        assert done[r1].output_tokens == hostloop_ref(p1, 6)
        assert eng.scheduler.stats["deadline_expired"] == 1
        assert obs.summary()["serving"]["deadline_expired"] == 1

    def test_cancel_frees_blocks(self, tiny):
        cfg, params = tiny
        eng = PagedServingEngine(cfg, params, num_blocks=32, block_size=4,
                                 max_batch=2, token_budget=16)
        r1 = eng.submit(_prompts(cfg, 1, [5], seed=10)[0], max_new_tokens=30)
        eng.step()
        assert eng.blocks.num_allocated() > 0
        assert eng.cancel(r1)
        assert not eng.cancel(r1)                 # idempotent
        assert eng.blocks.num_allocated() == 0
        done = {c.rid: c for c in eng.run()}
        assert done[r1].finish_reason == "cancelled"

    def test_stream_raises_typed_deadline(self, tiny):
        """An expiry mid-stream surfaces as DeadlineExceededError from the
        iterator, not a silent empty stream (the router relies on this to
        propagate typed failures through its own stream())."""
        cfg, params = tiny
        eng = PagedServingEngine(cfg, params, num_blocks=32, block_size=4,
                                 max_batch=2, token_budget=16)
        rid = eng.submit(_prompts(cfg, 1, [4], seed=17)[0],
                         max_new_tokens=6, deadline_s=-1.0)   # born dead
        with pytest.raises(DeadlineExceededError):
            list(eng.stream(rid))

    def test_cancel_storm_releases_pool_exactly(self, tiny, hostloop_ref):
        """Cancelling a pile of prefix-sharing in-flight requests (COW
        pages, shared blocks, chunked prefills) must return the pool to
        utilization 0 with no stale pending copies, and the engine must
        still serve a fresh request exactly."""
        cfg, params = tiny
        eng = PagedServingEngine(cfg, params, num_blocks=48, block_size=4,
                                 max_batch=4, token_budget=8)
        base = _prompts(cfg, 1, [8], seed=18)[0]
        eng.submit(base, max_new_tokens=2)
        eng.run()                                 # seeds the prefix cache
        rids = [eng.submit(base + extra, max_new_tokens=20)
                for extra in ([7], [11, 12], list(range(20)))]
        eng.step()                                # mid-flight: COW + chunks
        for r in rids:
            assert eng.cancel(r)
        assert eng.blocks.num_allocated() == 0
        assert eng.blocks.take_copies() == []
        done = {c.rid: c for c in eng.run()}
        assert all(done[r].finish_reason == "cancelled" for r in rids)
        fresh = _prompts(cfg, 1, [5], seed=19)[0]
        r2 = eng.submit(fresh, max_new_tokens=6)
        out = {c.rid: c for c in eng.run()}[r2]
        assert out.output_tokens == hostloop_ref(fresh, 6)

    def test_streaming_iterator_delivers_incrementally(self, tiny,
                                                       hostloop_ref):
        cfg, params = tiny
        eng = PagedServingEngine(cfg, params, num_blocks=32, block_size=4,
                                 max_batch=2, token_budget=16)
        p1, p2 = _prompts(cfg, 2, [5, 3], seed=11)
        r1 = eng.submit(p1, max_new_tokens=7)
        r2 = eng.submit(p2, max_new_tokens=4)
        streamed = list(eng.stream(r1))
        assert streamed == hostloop_ref(p1, 7)
        # the other request progressed while r1 streamed
        done = {c.rid: c for c in eng.run()}
        assert done[r2].output_tokens == hostloop_ref(p2, 4)


# ---------------------------------------------------------------------------
# SLO metrics / zero-retrace / chaos
# ---------------------------------------------------------------------------

class TestServingObservability:
    def test_zero_retrace_steady_state(self, tiny):
        """After the first step compiles the fused executable, the serving
        loop must never rebuild it — asserted from the engine counter AND
        the metrics registry."""
        cfg, params = tiny
        obs.reset()
        eng = PagedServingEngine(cfg, params, num_blocks=48, block_size=4,
                                 max_batch=3, token_budget=16)
        for p, b in zip(_prompts(cfg, 6, [5, 9, 2, 7, 12, 4], seed=12),
                        [6, 3, 9, 5, 4, 7]):
            eng.submit(p, max_new_tokens=b)
        eng.step()                                # warmup: one build
        builds_after_warmup = eng.stats["step_builds"]
        assert builds_after_warmup == 1
        eng.run()
        assert eng.stats["step_builds"] == builds_after_warmup
        reg = obs.registry()
        assert reg.value("paddle_serving_step_builds_total") == 1
        assert reg.value("paddle_serving_steps_total") == eng.stats["steps"]

    def test_summary_exposes_slo_surface(self, tiny):
        cfg, params = tiny
        obs.reset()
        eng = PagedServingEngine(cfg, params, num_blocks=32, block_size=4,
                                 max_batch=2, token_budget=16)
        for p in _prompts(cfg, 3, [4, 6], seed=13):
            eng.submit(p, max_new_tokens=5)
        eng.step()
        mid = obs.summary()["serving"]
        assert mid["running"] >= 1                # gauges live mid-run
        eng.run()
        s = obs.summary()["serving"]
        assert s["admitted"] == 3 and s["completed"] == 3
        assert s["ttft_p50_s"] > 0 and s["ttft_p99_s"] >= s["ttft_p50_s"]
        assert s["tpot_p50_s"] > 0
        assert s["queue_depth"] == 0 and s["running"] == 0
        assert 0.0 <= s["kv_block_utilization"] <= 1.0
        assert s["steps_total"] == eng.stats["steps"]

    def test_chaos_stall_trips_deadline_path(self, tiny):
        """A chaos-injected decode stall pushes an in-flight request past
        its deadline; the expiry shows up in metrics and the completion."""
        cfg, params = tiny
        obs.reset()
        chaos.reconfigure("serving:stall@delay=0.3;count=1")
        try:
            eng = PagedServingEngine(cfg, params, num_blocks=32,
                                     block_size=4, max_batch=2,
                                     token_budget=16)
            rid = eng.submit(_prompts(cfg, 1, [4], seed=15)[0],
                             max_new_tokens=20, deadline_s=0.15)
            done = {c.rid: c for c in eng.run()}
            assert done[rid].finish_reason == "deadline"
            assert eng.scheduler.stats["deadline_expired"] == 1
            reg = obs.registry()
            assert reg.value("paddle_chaos_injections_total",
                             {"site": "serving", "kind": "stall"}) == 1
            assert obs.summary()["serving"]["deadline_expired"] == 1
        finally:
            chaos.reconfigure("")

    def test_chaos_reject_surfaces_as_rejected(self, tiny):
        cfg, params = tiny
        chaos.reconfigure("serving:reject@count=1")
        try:
            eng = PagedServingEngine(cfg, params, num_blocks=32,
                                     block_size=4, max_batch=2,
                                     token_budget=16)
            eng.submit(_prompts(cfg, 1, [3], seed=16)[0], max_new_tokens=2)
            with pytest.raises(RejectedError):
                eng.run()
            eng.run()                             # next tick recovers
        finally:
            chaos.reconfigure("")


# ---------------------------------------------------------------------------
# Pallas paged-attention kernel (ops/pallas/paged_attention.py)
# ---------------------------------------------------------------------------

def _mha_args(past, this, KV=2, G=2, hd=8, bs=8, mb=4, nb=24, quant=False,
              seed=0, shared_first_page=False, dtype=np.float32):
    """Build block_multihead_attention_ inputs for a ragged batch. With
    shared_first_page, every sequence's table entry 0 points at the SAME
    physical page (the COW/prefix-cache layout after a shared-prefix
    admission)."""
    rs = np.random.RandomState(seed)
    H = KV * G
    B = len(this)
    tok = sum(this)
    cu = np.zeros(B + 1, np.int32)
    cu[1:] = np.cumsum(this)
    tables = np.full((B, mb), -1, np.int32)
    used = 1 if shared_first_page else 0
    for b in range(B):
        need = -(-max(past[b] + this[b], 0) // bs)
        for p in range(need):
            if shared_first_page and p == 0:
                tables[b, 0] = 0
                continue
            tables[b, p] = used
            used += 1
    assert used <= nb
    qkv = rs.randn(max(tok, 1), (H + 2 * KV) * hd).astype(np.float32)
    if quant:
        kc = rs.randint(-127, 128, (nb, KV, bs, hd)).astype(np.int8)
        vc = rs.randint(-127, 128, (nb, KV, bs, hd)).astype(np.int8)
        kq = rs.uniform(20, 60, (KV,)).astype(np.float32)
        vq = rs.uniform(20, 60, (KV,)).astype(np.float32)
        scales = dict(
            cache_k_quant_scales=jnp.asarray(kq),
            cache_v_quant_scales=jnp.asarray(vq),
            cache_k_dequant_scales=jnp.asarray(
                np.broadcast_to(1.0 / kq, (nb, KV)).copy()),
            cache_v_dequant_scales=jnp.asarray(
                np.broadcast_to(1.0 / vq, (nb, KV)).copy()))
    else:
        kc = rs.randn(nb, KV, bs, hd).astype(np.float32)
        vc = rs.randn(nb, KV, bs, hd).astype(np.float32)
        scales = {}
    if not quant:
        kc, vc = jnp.asarray(kc, dtype), jnp.asarray(vc, dtype)
    return dict(qkv=jnp.asarray(qkv, dtype), key_cache=jnp.asarray(kc),
                value_cache=jnp.asarray(vc),
                seq_lens_encoder=jnp.zeros(B, jnp.int32),
                seq_lens_decoder=jnp.asarray(past, np.int32),
                seq_lens_this_time=jnp.asarray(this, np.int32),
                cu_seqlens_q=jnp.asarray(cu),
                block_tables=jnp.asarray(tables), block_size=bs, **scales)


def _mha_both(args, pallas_mode=True):
    from paddle_tpu.ops.kernels.serving_attention import (
        block_multihead_attention_)
    stock = block_multihead_attention_.__wrapped__(use_pallas=False, **args)
    pal = block_multihead_attention_.__wrapped__(use_pallas=pallas_mode,
                                                 **args)
    _assert_stacked_pool_matches(args, pallas_mode, pal)
    return stock, pal


def _assert_stacked_pool_matches(args, pallas_mode, pal):
    """The engine's call: the same caches as layer 1 of a three-layer
    pool, read and written where they lie through the layer index. Same
    bits as the one-layer op, and the other layers are not touched."""
    from paddle_tpu.ops.kernels.serving_attention import (
        paged_layer_attention)
    rs = np.random.RandomState(99)

    def pool(cache):
        c = np.asarray(cache)
        other = rs.randint(-100, 100, (2,) + c.shape).astype(c.dtype)
        return jnp.asarray(np.stack([other[0], c, other[1]]))
    kp, vp = pool(args["key_cache"]), pool(args["value_cache"])
    scales = tuple(args[k] for k in (
        "cache_k_quant_scales", "cache_v_quant_scales",
        "cache_k_dequant_scales", "cache_v_dequant_scales") if k in args)
    out, _, kp2, vp2 = jax.jit(
        lambda layer: paged_layer_attention(
            args["qkv"], kp, vp, layer, args["seq_lens_decoder"],
            args["seq_lens_this_time"], args["cu_seqlens_q"],
            args["block_tables"], quant_scales=scales or None,
            use_pallas=pallas_mode))(jnp.int32(1))
    assert np.array_equal(np.asarray(out), np.asarray(pal[0]))
    for got, before, want in ((kp2, kp, pal[2]), (vp2, vp, pal[3])):
        got = np.asarray(got)
        assert np.array_equal(got[1], np.asarray(want))
        assert np.array_equal(got[[0, 2]], np.asarray(before)[[0, 2]])


# the decode walk's cases: G, page size, pages a key block (P), table width
# (a multiple of P or not); lengths default to [idle, 1, P*bs - 1, P*bs,
# P*bs + 1, the full table]
_DECODE_WALK = [
    dict(id="g1-bs4-p2-even", G=1, bs=4, P=2, mb=6),
    dict(id="g1-bs4-p2-odd", G=1, bs=4, P=2, mb=7),
    dict(id="g4-bs4-p4-even", G=4, bs=4, P=4, mb=8),
    dict(id="g4-bs4-p4-odd", G=4, bs=4, P=4, mb=9),
    dict(id="g1-bs16-p2-even", G=1, bs=16, P=2, mb=4),
    dict(id="g1-bs16-p2-odd", G=1, bs=16, P=2, mb=5),
    dict(id="g4-bs16-p2-even", G=4, bs=16, P=2, mb=4),
    dict(id="g4-bs16-p2-odd", G=4, bs=16, P=2, mb=5),
    dict(id="g4-bs16-p1-single-pages", G=4, bs=16, P=1, mb=3),
    # the block the shapes give: 128 keys, or the whole table if narrower
    dict(id="g4-bs16-reckoned-p8", G=4, bs=16, mb=10,
         lengths=[0, 1, 127, 128, 129, 160]),
    dict(id="g1-bs4-reckoned-table", G=1, bs=4, mb=5,
         lengths=[0, 1, 19, 20, 7, 12]),
    dict(id="g4-bs4-cow-first-page", G=4, bs=4, P=2, mb=5, cow=True,
         lengths=[9, 8, 7, 17, 20]),
    dict(id="g4-bs16-int8-partial-last-page", G=4, bs=16, P=2, mb=5,
         quant=True, lengths=[0, 1, 31, 33, 40, 71]),
    dict(id="g1-bs16-int8-partial-last-page", G=1, bs=16, P=2, mb=4,
         quant=True, lengths=[11, 64, 0, 45]),
]


# the mixed walk's cases: G, page size, the row tile TQ and the small tile
# TS in tokens, pages a key block (P); `this` walks 0 / 1 / TQ - 1 / TQ /
# TQ + 1 and a chunk of several tiles, `past` 0, mid-page and page boundaries
_MIXED_WALK = [
    dict(id="g4-tq8-ts4-p2-odd-table", G=4, bs=4, TQ=8, TS=4, P=2, mb=7,
         past=[3, 5, 8, 0, 16, 7], this=[0, 1, 7, 8, 9, 19]),
    dict(id="g4-tq8-whole-tiles-only", G=4, bs=4, TQ=8, TS=8, P=2, mb=8,
         past=[0, 11, 4, 9, 0], this=[1, 1, 3, 0, 17]),
    dict(id="g1-tq16-p2", G=1, bs=4, TQ=16, TS=16, P=2, mb=13,
         past=[0, 1, 16, 5, 9, 0], this=[0, 1, 15, 16, 17, 33]),
    dict(id="g1-tq32-ts16-p4", G=1, bs=4, TQ=32, TS=16, P=4, mb=17,
         past=[7, 0, 16, 0, 32, 2], this=[1, 16, 17, 31, 33, 0]),
    dict(id="g4-bs16-tq8-ts4-p1", G=4, bs=16, TQ=8, TS=4, P=1, mb=3,
         past=[0, 16, 30, 5], this=[9, 1, 12, 0]),
    dict(id="g4-reckoned-from-shapes", G=4, bs=4, mb=9,
         past=[2, 0, 8, 13], this=[1, 20, 0, 5]),
    dict(id="g1-reckoned-from-shapes", G=1, bs=16, mb=4,
         past=[0, 16, 40], this=[23, 1, 9]),
    dict(id="g4-tq8-cow-first-page", G=4, bs=4, TQ=8, TS=4, P=2, mb=5,
         cow=True, past=[4, 8, 4], this=[9, 1, 4]),
    dict(id="g4-tq8-int8", G=4, bs=16, TQ=8, TS=4, P=2, mb=5, quant=True,
         past=[0, 16, 30, 5, 41], this=[9, 1, 12, 0, 17]),
    dict(id="g1-tq16-int8", G=1, bs=16, TQ=16, TS=16, P=2, mb=4, quant=True,
         past=[11, 0, 32], this=[1, 33, 17]),
    dict(id="g4-tq8-bf16", G=4, bs=16, TQ=8, TS=4, P=2, mb=5,
         dtype=jnp.bfloat16, past=[0, 16, 30, 5, 41],
         this=[9, 1, 12, 0, 17]),
    dict(id="g1-tq16-bf16", G=1, bs=16, TQ=16, TS=16, P=2, mb=4,
         dtype=jnp.bfloat16, past=[11, 0, 32], this=[1, 33, 17]),
]


def _mixed_calls(monkeypatch):
    """A list that grows by one for every launch of the mixed walk."""
    from paddle_tpu.ops.pallas import paged_attention as PA
    calls, real = [], PA._mixed_call
    monkeypatch.setattr(
        PA, "_mixed_call", lambda *a, **k: calls.append(1) or real(*a, **k))
    return calls


def _steer_mixed_walk(monkeypatch, case):
    """Steer the tiles the shapes would give; the program has no knob."""
    from paddle_tpu.ops.pallas import paged_attention as PA
    if "TQ" in case:
        monkeypatch.setattr(PA, "_MIXED_TOKENS", case["TQ"])
        monkeypatch.setattr(PA, "_MIXED_SMALL_TOKENS", case["TS"])
        monkeypatch.setattr(PA, "_MIXED_KEYS", case["P"] * case["bs"])


class TestPallasPagedAttention:
    def test_supported_gates(self):
        from paddle_tpu.ops.pallas import paged_attention as PA
        assert PA.supported(4, 2, 64, 16)
        assert PA.supported(4, 4, 8, 1)          # MHA, minimum geometry
        assert not PA.supported(4, 3, 64, 16)    # H % KV != 0
        assert not PA.supported(4, 0, 64, 16)    # no kv heads
        assert not PA.supported(4, 2, 4, 16)     # head_dim floor
        assert not PA.supported(4, 2, 64, 0)     # degenerate page

    @pytest.mark.parametrize("available", [False, True])
    @pytest.mark.parametrize("supported", [False, True])
    def test_rule_is_available_and_supported(self, monkeypatch, available,
                                             supported):
        """`selected`: the kernel where it runs and takes the geometry.
        No launch here (`paged_attention` reads `available()` for its
        interpret mode, so a test that runs the kernel patches the rule,
        never `available`)."""
        from paddle_tpu.ops.pallas import paged_attention as PA
        seen = []
        monkeypatch.setattr(PA, "available", lambda: available)
        monkeypatch.setattr(
            PA, "supported",
            lambda *geometry: seen.append(geometry) or supported)
        assert PA.selected(32, 8, 128, 16) is (available and supported)
        assert seen in ([], [(32, 8, 128, 16)])

    @pytest.mark.parametrize("rule", [False, True])
    def test_op_told_nothing_follows_the_rule(self, monkeypatch, rule):
        """`use_pallas=None` is the forced call the rule names, with the
        geometry the op reads off its arguments."""
        from paddle_tpu.ops.kernels.serving_attention import (
            block_multihead_attention_)
        from paddle_tpu.ops.pallas import flash_attention as FA
        from paddle_tpu.ops.pallas import paged_attention as PA
        args = _mha_args(past=[8, 0, 15], this=[5, 9, 1], seed=9)
        asked = []
        monkeypatch.setattr(PA, "selected",
                            lambda *geometry: asked.append(geometry) or rule)
        launches0 = FA.trace_launches()
        got = block_multihead_attention_.__wrapped__(**args)
        assert asked == [(4, 2, 8, 8)]
        assert (FA.trace_launches() > launches0) is rule
        want = block_multihead_attention_.__wrapped__(use_pallas=rule,
                                                      **args)
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g), np.asarray(w))

    @pytest.mark.parametrize("bs", [8, 16])
    def test_parity_across_page_sizes(self, bs):
        """Interpret-mode kernel vs stock XLA on a ragged mixed batch:
        chunked prefill resume (past>0), fresh prefill, decode rows."""
        args = _mha_args(past=[8, 0, 15], this=[5, 9, 1], bs=bs, mb=4,
                         nb=24, seed=1)
        stock, pal = _mha_both(args)
        np.testing.assert_allclose(np.asarray(pal[0]), np.asarray(stock[0]),
                                   atol=5e-5, rtol=1e-5)
        # cache writes are SHARED code, identical bit-for-bit
        assert np.array_equal(np.asarray(pal[2]), np.asarray(stock[2]))
        assert np.array_equal(np.asarray(pal[3]), np.asarray(stock[3]))

    def test_parity_ragged_with_idle_slot(self):
        args = _mha_args(past=[3, 0, 7, 0], this=[2, 0, 1, 4], seed=2)
        stock, pal = _mha_both(args)
        np.testing.assert_allclose(np.asarray(pal[0]), np.asarray(stock[0]),
                                   atol=5e-5, rtol=1e-5)

    def test_decode_mode_parity(self):
        """The max_q=1 specialized launch on a pure-decode batch."""
        args = _mha_args(past=[7, 0, 30, 12], this=[1, 1, 1, 1], seed=3)
        stock, pal = _mha_both(args, pallas_mode="decode")
        np.testing.assert_allclose(np.asarray(pal[0]), np.asarray(stock[0]),
                                   atol=5e-5, rtol=1e-5)

    def test_cow_shared_pages_parity(self):
        """Two sequences reading the SAME physical first page (prefix-cache
        sharing): the in-kernel table walk must dereference the shared
        block for both without cross-talk."""
        args = _mha_args(past=[8, 8, 8], this=[1, 3, 1],
                         shared_first_page=True, seed=4)
        stock, pal = _mha_both(args)
        np.testing.assert_allclose(np.asarray(pal[0]), np.asarray(stock[0]),
                                   atol=5e-5, rtol=1e-5)

    def test_int8_pages_partial_last_page(self):
        """In-register dequant with ragged lengths mid-page (partial last
        pages on every sequence)."""
        args = _mha_args(past=[10, 0, 33], this=[1, 13, 1], KV=2, G=3,
                         hd=16, bs=16, quant=True, seed=5)
        stock, pal = _mha_both(args)
        np.testing.assert_allclose(np.asarray(pal[0]), np.asarray(stock[0]),
                                   atol=5e-5, rtol=1e-5)
        assert np.asarray(pal[2]).dtype == np.int8

    @pytest.mark.parametrize("case", _MIXED_WALK, ids=lambda c: c["id"])
    def test_mixed_walk_parity(self, case, monkeypatch):
        """The mixed launch's walk (work items by `cu_seqlens_q`, live key
        blocks of P whole pages up to a tile's causal limit) against the
        stock path: chunks that cross tile and key-block boundaries beside
        one-token sequences and idle slots, -1 entries behind every live
        length, the stacked pool with a traced layer (`_mha_both`)."""
        from paddle_tpu.ops.pallas import paged_attention as PA
        G, bs, mb, KV, hd = case["G"], case["bs"], case["mb"], 2, 8
        past, this = case["past"], case["this"]
        quant, dtype = case.get("quant", False), case.get("dtype", np.float32)
        _steer_mixed_walk(monkeypatch, case)
        tq, ts = PA.mixed_tiles(sum(this), G, KV, hd)
        if "TQ" in case:
            assert (tq, ts) == (case["TQ"], case["TS"])
            assert PA.mixed_pages_per_block(bs, KV, hd, 4, mb) == case["P"]
            assert max(this) > tq                 # a chunk of several tiles
        nb = sum(-(-(a + b) // bs) for a, b in zip(past, this)) + 2
        args = _mha_args(past, this, KV=KV, G=G, hd=hd, bs=bs, mb=mb, nb=nb,
                         quant=quant, seed=31, dtype=dtype,
                         shared_first_page=case.get("cow", False))
        tables = np.asarray(args["block_tables"])
        for row, a, b in zip(tables, past, this):  # -1 behind the live
            assert (row[-(-(a + b) // bs):] == -1).all()
        assert (tables == -1).any()
        if quant:       # a scale of its own for every page and head
            rs = np.random.RandomState(32)
            for name in ("cache_k_dequant_scales", "cache_v_dequant_scales"):
                args[name] = jnp.asarray(
                    rs.uniform(0.01, 0.05, (nb, KV)).astype(np.float32))
        calls = _mixed_calls(monkeypatch)
        stock, pal = _mha_both(args)
        assert len(calls) == 2            # the op, and the stacked pool
        # bf16: the stock path rounds its f32 answer once, the kernel too
        tol = (dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16
               else dict(atol=5e-5, rtol=1e-5))
        np.testing.assert_allclose(
            np.asarray(pal[0], np.float32), np.asarray(stock[0], np.float32),
            **tol)
        assert np.array_equal(np.asarray(pal[2]), np.asarray(stock[2]))
        assert np.array_equal(np.asarray(pal[3]), np.asarray(stock[3]))

    @pytest.mark.parametrize("past, this, quant", [
        ([8, 0, 15], [5, 9, 1], False), ([3, 0, 7, 0], [2, 0, 1, 4], False),
        ([10, 0, 33], [1, 13, 1], True)], ids=["ragged", "idle", "int8"])
    def test_blockspec_walk_through_the_layer(self, past, this, quant,
                                              monkeypatch):
        """Where whole pages cannot be copied (head dims off whole lanes
        on the chip) a mixed launch packs its rows per sequence for the
        BlockSpec walk (`_kernel`): the same answers."""
        from paddle_tpu.ops.pallas import paged_attention as PA
        monkeypatch.setattr(PA, "whole_pages", lambda *a, **k: False)
        args = _mha_args(past, this, KV=2, G=3 if quant else 2,
                         hd=16 if quant else 8, bs=16 if quant else 8,
                         quant=quant, seed=33)
        calls = _mixed_calls(monkeypatch)
        stock, pal = _mha_both(args)
        assert not calls
        np.testing.assert_allclose(np.asarray(pal[0]), np.asarray(stock[0]),
                                   atol=5e-5, rtol=1e-5)

    def test_mixed_walk_reads_no_page_behind_a_tiles_limit(self, monkeypatch):
        """NaN in every page the tables do not name and in the named
        pages' slots past the live length: the answer stays what it was,
        and rows that are no sequence's token come back 0."""
        from paddle_tpu.ops.pallas import paged_attention as PA
        _steer_mixed_walk(monkeypatch, dict(TQ=8, TS=4, P=2, bs=4))
        rs = np.random.RandomState(34)
        KV, G, hd, bs, nb = 2, 4, 8, 4, 16
        past = np.array([5, 0, 8], np.int32)
        this = np.array([1, 11, 0], np.int32)        # lengths 6, 11, idle
        cu = np.array([0, 1, 12, 12], np.int32)
        q = jnp.asarray(rs.randn(14, KV, G, hd).astype(np.float32))
        kc = rs.randn(nb, KV, bs, hd).astype(np.float32)
        vc = rs.randn(nb, KV, bs, hd).astype(np.float32)
        bt = np.array([[3, 7, -1, -1, -1], [5, 1, 9, -1, -1],
                       [11, 12, -1, -1, -1]], np.int32)

        def run(k):
            return np.asarray(PA.paged_attention_packed(
                q, jnp.asarray(k), jnp.asarray(vc), jnp.asarray(bt),
                jnp.asarray(past), jnp.asarray(this), jnp.asarray(cu), 0.3,
                interpret=True))
        clean = run(kc)
        dirty = kc.copy()
        named = {3: 4, 7: 2, 5: 4, 1: 4, 9: 3}       # page: live slots
        for page in range(nb):
            dirty[page, :, named.get(page, 0):] = np.nan
        out = run(dirty)
        assert np.array_equal(out, clean)
        assert np.abs(clean[:12]).min(axis=(1, 2, 3)).all()
        assert not clean[12:].any()                  # no sequence's rows

    def test_mixed_work_hand_counted(self, monkeypatch):
        """`mixed_work` mirrors the kernel's trip counts: the work items'
        live and packed rows, live and fetched pages."""
        from paddle_tpu.ops.pallas import paged_attention as PA
        _steer_mixed_walk(monkeypatch, dict(TQ=8, TS=4, P=2, bs=4))
        # group 4, pages of 4, TQ 8, TS 4, key blocks of 2 pages = 8 keys
        work = PA.mixed_work([3, 5, 0, 16], [0, 1, 8, 19], 32, 4, 2, 4, 8,
                             4, 9)
        # items: (1: 1 token), (2: 8), (3: 8, 8, 3): five, the idle slot
        # none; each packed on a tile of 4 or 8 rows
        assert work["attn_rows_live"] == 1 + 8 + 19
        assert work["attn_rows_packed"] == 4 + 8 + 8 + 8 + 4
        # pages: 6 keys -> 2, 8 -> 2, 35 -> 9
        assert work["attn_pages_live"] == 2 + 2 + 9
        # key blocks a tile: ceil(6/8), ceil(8/8), ceil(24/8), ceil(32/8),
        # ceil(35/8) capped at the padded table's 5 blocks
        assert work["attn_pages_fetched"] == (1 + 1 + 3 + 4 + 5) * 2
        # the static count of items holds every schedule of the shape
        tq, _ = PA.mixed_tiles(32, 4, 2, 8)
        assert PA.mixed_items(32, 4, tq) == 32 // 8 + 4
        assert PA.mixed_items(4, 4, tq) == 4     # never more than tokens
        assert PA.mixed_work([], [], 32, 4, 2, 4, 8, 4, 9) == dict.fromkeys(
            work, 0)

    @pytest.mark.parametrize("case", _DECODE_WALK, ids=lambda c: c["id"])
    def test_decode_walk_parity(self, case, monkeypatch):
        """The decode launch's own walk (whole pages, P a key block, live
        blocks only) against the stock path: an idle slot, lengths 1,
        P*bs - 1, P*bs, P*bs + 1 and the full table in one batch, -1
        entries behind every live length, the stacked pool with a traced
        layer (inside `_mha_both`)."""
        from paddle_tpu.ops.pallas import paged_attention as PA
        G, bs, mb, KV, hd = case["G"], case["bs"], case["mb"], 2, 8
        quant = case.get("quant", False)
        if "P" in case:       # steer the key block; the program has no knob
            monkeypatch.setattr(PA, "_DECODE_KEYS", case["P"] * bs)
        P = PA.decode_pages_per_block(bs, KV, hd, 1 if quant else 4, mb)
        assert P == case.get("P", min(128 // bs, mb))
        span = P * bs
        lengths = case.get("lengths",
                           [0, 1, span - 1, span, span + 1, mb * bs])
        past = [5 if n == 0 else n - 1 for n in lengths]
        this = [0 if n == 0 else 1 for n in lengths]
        nb = sum(-(-(a + b) // bs) for a, b in zip(past, this)) + 2
        args = _mha_args(past, this, KV=KV, G=G, hd=hd, bs=bs, mb=mb, nb=nb,
                         quant=quant, seed=case.get("seed", 11),
                         shared_first_page=case.get("cow", False))
        tables = np.asarray(args["block_tables"])
        for row, a, b in zip(tables, past, this):  # -1 behind the live
            assert (row[-(-(a + b) // bs):] == -1).all()
        assert (tables == -1).any()
        if quant:
            # a scale of its own for every page and head: the walk must
            # pair each page's keys with that page's row
            rs = np.random.RandomState(12)
            for name in ("cache_k_dequant_scales", "cache_v_dequant_scales"):
                args[name] = jnp.asarray(
                    rs.uniform(0.01, 0.05, (nb, KV)).astype(np.float32))
        stock, pal = _mha_both(args, pallas_mode="decode")
        np.testing.assert_allclose(np.asarray(pal[0]), np.asarray(stock[0]),
                                   atol=5e-5, rtol=1e-5)
        assert np.array_equal(np.asarray(pal[2]), np.asarray(stock[2]))
        assert np.array_equal(np.asarray(pal[3]), np.asarray(stock[3]))

    def test_decode_walk_reads_no_page_behind_the_live_length(
            self, monkeypatch):
        """NaN in every page the tables do not name, and in the named
        pages' slots past the live length: the walk's answer stays what
        it was (a fetched page behind the length is masked, never used)."""
        from paddle_tpu.ops.pallas import paged_attention as PA
        monkeypatch.setattr(PA, "_DECODE_KEYS", 8)   # P = 2 pages of 4
        rs = np.random.RandomState(13)
        KV, G, hd, bs, nb = 2, 4, 8, 4, 12
        q = jnp.asarray(rs.randn(2, KV, G, hd).astype(np.float32))
        kc = rs.randn(nb, KV, bs, hd).astype(np.float32)
        vc = rs.randn(nb, KV, bs, hd).astype(np.float32)
        bt = np.array([[3, 7, -1, -1, -1], [5, 1, 9, -1, -1]], np.int32)
        past = np.array([5, 8], np.int32)            # lengths 6 and 9
        this = jnp.ones((2,), jnp.int32)
        clean = PA.paged_attention(q, jnp.asarray(kc), jnp.asarray(vc),
                                   jnp.asarray(bt), jnp.asarray(past), this,
                                   G, 0.3, interpret=True)
        # the zero behind a masked key is 0 * v: keep v finite, poison k
        dirty = kc.copy()
        named = {3: 4, 7: 2, 5: 4, 1: 4, 9: 1}       # page: live slots
        for page in range(nb):
            dirty[page, :, named.get(page, 0):] = np.nan
        out = PA.paged_attention(q, jnp.asarray(dirty), jnp.asarray(vc),
                                 jnp.asarray(bt), jnp.asarray(past), this,
                                 G, 0.3, interpret=True)
        assert np.array_equal(np.asarray(out), np.asarray(clean))

    def test_forced_bad_geometry_raises(self):
        args = _mha_args(past=[0], this=[2], KV=1, G=2, hd=4, seed=6)
        with pytest.raises(ValueError, match="not supported"):
            _mha_both(args)

    def test_kernel_rejects_one_sided_dequant(self):
        from paddle_tpu.ops.pallas import paged_attention as PA
        q = jnp.zeros((1, 1, 2, 8), jnp.float32)
        kc = jnp.zeros((2, 1, 8, 8), jnp.float32)
        bt = jnp.zeros((1, 2), jnp.int32)
        z = jnp.zeros((1,), jnp.int32)
        with pytest.raises(ValueError, match="both"):
            PA.paged_attention(q, kc, kc, bt, z, z, 2, 1.0,
                               k_dequant=jnp.ones((2, 1)))

    def test_pad_rows_come_back_zero(self):
        from paddle_tpu.ops.pallas import paged_attention as PA
        rs = np.random.RandomState(8)
        q = jnp.asarray(rs.randn(2, 1, 8, 8).astype(np.float32))
        kc = jnp.asarray(rs.randn(4, 1, 8, 8).astype(np.float32))
        vc = jnp.asarray(rs.randn(4, 1, 8, 8).astype(np.float32))
        bt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
        past = jnp.asarray([3, 0], jnp.int32)
        this = jnp.asarray([1, 2], jnp.int32)   # rows 2..7 of seq 0 dead
        o = np.asarray(PA.paged_attention(q, kc, vc, bt, past, this,
                                          2, 0.35, interpret=True))
        assert np.all(o[0, :, 2:] == 0.0)       # t >= this[0]
        assert np.all(o[1, :, 4:] == 0.0)       # t >= this[1]
        assert np.all(o[0, :, :2] != 0.0)

    def test_stacked_pool_needs_its_layer(self):
        from paddle_tpu.ops.pallas import paged_attention as PA
        q = jnp.zeros((1, 1, 2, 8), jnp.float32)
        pool = jnp.zeros((3, 2, 1, 8, 8), jnp.float32)
        bt = jnp.zeros((1, 2), jnp.int32)
        z = jnp.zeros((1,), jnp.int32)
        with pytest.raises(ValueError, match="layer"):
            PA.paged_attention(q, pool, pool, bt, z, z, 2, 1.0)
        with pytest.raises(ValueError, match="layer"):
            PA.paged_attention(q, pool[0], pool[0], bt, z, z, 2, 1.0, layer=1)


class TestNoFlagNoDimension:
    """What went with the rule's second and third homes: a flag nobody
    needs to set cannot be set, searched or pinned."""

    def test_flag_is_unknown(self):
        from paddle_tpu.core import flags
        with pytest.raises(KeyError):
            flags.flag_value("serving_pallas_attention")
        with pytest.raises(KeyError):
            flags.flag_value("no_such_flag_at_all")

    def test_tuner_has_no_attention_dimension(self):
        import dataclasses

        from paddle_tpu.tuner.search import Candidate
        names = {f.name for f in dataclasses.fields(Candidate)}
        assert "pallas_attention" not in names and "pallas_ffn" in names
        assert "serving_pallas_attention" not in Candidate().to_flags()
        with pytest.raises(TypeError):
            Candidate(pallas_attention=True)

    def test_pinned_profile_applies_strictly(self, monkeypatch):
        import os

        from paddle_tpu import tuner
        from paddle_tpu.core import flags
        from paddle_tpu.tuner import profile as P
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tuned_profiles",
            "serving_llama_tiny_cpu.json")
        prof = tuner.load_profile(path)              # the CRC holds
        # pinned on one CPU device; the suite runs on eight virtual ones
        one_cpu = tuner.topology_signature(n_devices=1)
        monkeypatch.setattr(P, "topology_signature", lambda: one_cpu)
        keep = {k: flags.flag_value(k) for k in prof.flags}
        try:
            tuner.apply_profile(prof, strict=True)   # every key is a flag
            assert flags.flag_value("serving_max_batch") == 16
        finally:
            flags.set_flags(keep)


class TestEnginePallas:
    def _engine(self, tiny, pallas, **kw):
        cfg, params = tiny
        defaults = dict(num_blocks=48, block_size=4, max_batch=4,
                        token_budget=16)
        defaults.update(kw)
        return PagedServingEngine(cfg, params, pallas=pallas, **defaults)

    def test_token_parity_flag_on_vs_off(self, tiny):
        prompts = _prompts(tiny[0], 4, [7, 2, 13, 5], seed=21)

        def run(pallas):
            eng = self._engine(tiny, pallas)
            rids = [eng.submit(p, max_new_tokens=9) for p in prompts]
            done = {c.rid: c.output_tokens for c in eng.run()}
            return [done[r] for r in rids], eng.stats

        off, s_off = run(False)
        on, s_on = run(True)
        assert on == off
        assert s_on["pallas_steps"] == s_on["steps"] > 0
        assert s_off["pallas_steps"] == 0

    def test_chunked_prefill_token_parity_through_the_mixed_walk(
            self, tiny, monkeypatch):
        """Prompts longer than the token budget are prefilled in chunks
        beside decoding sequences: the same tokens through the mixed walk
        (tiles of 8 tokens, key blocks of 2 pages, so chunks cross both)
        and through the stock path."""
        _steer_mixed_walk(monkeypatch, dict(TQ=8, TS=8, P=2, bs=4))
        prompts = _prompts(tiny[0], 3, [37, 5, 21], seed=25)

        def run(pallas):
            eng = self._engine(tiny, pallas, num_blocks=64)
            rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
            done = {c.rid: c.output_tokens for c in eng.run()}
            return [done[r] for r in rids], eng.stats

        off, _ = run(False)
        on, stats = run(True)
        assert on == off
        mixed = stats["steps"] - stats["decode_fast_steps"]
        assert mixed >= 4                         # 63 tokens, 16 a tick
        # ticks of several items, each on a tile of 8 rows at least
        assert stats["attn_rows_packed"] > 8 * mixed
        assert stats["attn_rows_live"] >= 37 + 5 + 21   # + decode rows
        assert stats["attn_rows_packed"] >= stats["attn_rows_live"]

    def test_preemption_recompute_bit_exact_flag_on(self, tiny):
        """Starved pool forces eviction; the pallas path's recompute on
        resume must reproduce the ample-pool pallas outputs exactly."""
        prompts = _prompts(tiny[0], 3, [6, 4, 3], seed=22)

        def run(num_blocks, max_batch):
            eng = self._engine(tiny, True, num_blocks=num_blocks,
                               max_batch=max_batch)
            rids = [eng.submit(p, max_new_tokens=10, priority=i)
                    for i, p in enumerate(prompts)]
            done = {c.rid: c.output_tokens for c in eng.run()}
            return [done[r] for r in rids], eng

        ample, _ = run(48, 3)
        starved, eng = run(6, 3)
        assert eng.scheduler.stats["preemptions"] >= 1
        assert starved == ample

    def test_zero_steady_state_retraces_and_decode_fast_path(self, tiny):
        eng = self._engine(tiny, True)
        prompts = _prompts(tiny[0], 3, [5, 3, 8], seed=23)
        for p in prompts:
            eng.submit(p, max_new_tokens=6)
        eng.run()                                 # warm: builds happen here
        builds = eng.stats["step_builds"]
        assert builds <= 2                        # mixed + decode launches
        for p in prompts:
            eng.submit(p, max_new_tokens=6)
        eng.run()
        assert eng.stats["step_builds"] == builds  # steady state: zero
        assert eng.stats["decode_fast_steps"] > 0
        assert eng.stats["pallas_steps"] == eng.stats["steps"]

    def test_attn_page_counters_hand_counted(self, tiny, monkeypatch):
        """`attn_pages_live` / `attn_pages_fetched`: what a launch's walk
        must read and what it fetches, from the host's own lengths; a
        mixed tick adds its work items and their rows too."""
        from paddle_tpu.ops.pallas import paged_attention as PA
        monkeypatch.setattr(PA, "_DECODE_KEYS", 8)   # P = 2 pages of 4
        monkeypatch.setattr(PA, "_MIXED_KEYS", 8)
        eng = self._engine(tiny, True)
        assert PA.decode_pages_per_block(4, 2, 8, 4,
                                         eng.max_blocks_per_seq) == 2
        # group 2: a row tile of 16 tokens (the budget), a small one of 8
        assert PA.mixed_tiles(16, 2, 2, tiny[0].head_dim) == (16, 8)
        for p in _prompts(tiny[0], 2, [7, 3], seed=24):
            eng.submit(p, max_new_tokens=4)
        eng.step()                      # the mixed tick: both prompts whole
        assert eng.stats["decode_fast_steps"] == 0
        # two items, 7 + 3 tokens on two small tiles of 8; keys 0..6 and
        # 0..2: 2 + 1 pages, one block of 2 pages each
        mixed = {"attn_rows_live": 10,
                 "attn_rows_packed": 16, "attn_pages_live": 3,
                 "attn_pages_fetched": 4}
        assert {k: eng.stats[k] for k in mixed} == mixed
        eng.run()
        assert eng.stats["decode_fast_steps"] == 3 == eng.stats["steps"] - 1
        # decode tick k reads positions 0 .. prompt + k - 1:
        #   prompt 7: 8, 9, 10 keys -> 2 + 3 + 3 pages, 1 + 2 + 2 blocks
        #   prompt 3: 4, 5, 6 keys  -> 1 + 2 + 2 pages, 1 + 1 + 1 blocks
        assert eng.stats["attn_pages_live"] == 3 + 8 + 5
        # (the two idle slots of the four walk and count nothing)
        assert eng.stats["attn_pages_fetched"] == 4 + (5 + 3) * 2
        assert eng.stats["attn_rows_packed"] == 16  # decode ticks add none
        # the helper alone: a full table of 5 pages is 3 blocks of 2 (the
        # wrapper pads the table to 6), and no sequence is no page
        assert PA.decode_pages_walked([20, 1], 4, 2, 8, 4, 5) == (6, 8)
        assert PA.decode_pages_walked([], 4, 2, 8, 4, 5) == (0, 0)

    @pytest.mark.parametrize("rule", [False, True], ids=["off_tpu", "on"])
    def test_engine_told_nothing_follows_the_rule(self, tiny, monkeypatch,
                                                  rule):
        """`pallas=None` asks `paged_attention.selected` once, when the
        engine is built. Where the rule says no (this host, unpatched) the
        engine serves the stock path, which is a choice and no fallback;
        where it says yes (the rule patched, never `available()`: the
        kernel then runs in interpret mode) the engine is the `pallas=True`
        engine."""
        from paddle_tpu.ops.pallas import paged_attention as PA
        if rule:
            monkeypatch.setattr(PA, "selected", lambda *geometry: True)
        elif PA.available():
            pytest.skip("a TPU: the unpatched rule takes the kernel")
        obs.reset()
        prompts = _prompts(tiny[0], 2, [5, 9], seed=24)

        def run(pallas):
            eng = self._engine(tiny, pallas)
            rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
            done = {c.rid: c.output_tokens for c in eng.run()}
            return eng, [done[r] for r in rids]

        eng, tokens = run(None)
        assert eng.pallas is rule
        events = [(kind, f) for _, _, kind, _, f in obs.recorder().events()]
        assert "serving.pallas_fallback" not in [kind for kind, _ in events]
        assert "pallas_fallbacks" not in obs.summary()["serving"]
        writes = {f["cache_write"] for kind, f in events
                  if kind == "serving.step_build"}
        if rule:
            assert writes == {"pallas_pages"}
            assert eng.stats["pallas_steps"] == eng.stats["steps"] > 0
            assert eng.stats["decode_fast_steps"] > 0
        else:
            assert writes == {"scatter_rows"}
            assert eng.stats["pallas_steps"] == 0
            assert eng.stats["step_builds"] == 1
        assert tokens == run(rule)[1]

    def test_step_key_holds_what_differs_between_ticks(self, tiny):
        """The attention read is the engine's constant and no part of the
        executable key: a run with a mixed tick and decode ticks builds two
        executables, told apart by the tick's shape alone."""
        eng = self._engine(tiny, True)
        for p in _prompts(tiny[0], 3, [5, 3, 8], seed=26):
            eng.submit(p, max_new_tokens=6)
        eng.run()
        assert eng.stats["decode_fast_steps"] > 0
        assert eng.stats["step_builds"] == 2 == len(eng._step_fns)
        B, budget = eng.max_batch, eng.token_budget
        assert set(eng._step_fns) == {(budget, B, False, False, (), False),
                                      (B, B, True, False, (), False)}

    def test_tick_hands_its_host_arrays_to_the_executable_as_they_are(
            self, tiny, monkeypatch):
        """A tick's ten small host arrays go into the jitted call as numpy
        arrays, where the call's own argument handling moves them; wrapped
        one by one in `jnp.asarray` they were half of the dispatch phase on
        the chip (PERF.md, PR 30). The tokens are those of the stock engine
        either way."""
        from paddle_tpu.inference.serving import engine as E
        made = []
        real = E.jnp.asarray
        monkeypatch.setattr(E.jnp, "asarray",
                            lambda *a, **k: made.append(1) or real(*a, **k))
        outs = []
        for pallas in (True, False):
            eng = self._engine(tiny, pallas)
            for seed in (26, 27):     # the first pass traces the executables
                rids = [eng.submit(p, max_new_tokens=5)
                        for p in _prompts(tiny[0], 3, [5, 3, 8], seed=seed)]
                made.clear()
                done = {d.rid: d.output_tokens for d in eng.run()}
            assert eng.stats["steps"] > 8 and not made
            outs.append([done[r] for r in rids])
        assert outs[0] == outs[1]

    def test_forced_bad_geometry_fails_at_init(self):
        # head_dim 16/4 = 4 is under the kernel's floor: forced pallas
        # must fail loudly at construction, not mid-serve
        cfg = L.LlamaConfig(vocab_size=31, hidden_size=16,
                            intermediate_size=32, num_layers=1, num_heads=4,
                            num_kv_heads=2, max_seq_len=32,
                            dtype=jnp.float32)
        params = L.init_params(cfg, jax.random.PRNGKey(1))
        with pytest.raises(ValueError, match="not supported"):
            PagedServingEngine(cfg, params, num_blocks=8, block_size=4,
                               max_batch=2, token_budget=8, pallas=True)

    def test_pallas_steps_flow_to_summary(self, tiny):
        obs.reset()
        eng = self._engine(tiny, True)
        eng.submit(_prompts(tiny[0], 1, [6], seed=25)[0], max_new_tokens=4)
        eng.run()
        s = obs.summary()["serving"]
        assert s["pallas_steps"] == eng.stats["pallas_steps"] > 0


# ---------------------------------------------------------------------------
# The page pool is updated in place: structure of the tick, and its contents
# ---------------------------------------------------------------------------

ENGINE_KW = dict(num_blocks=48, block_size=4, max_batch=4, token_budget=16)


def _step_args(eng, tok_pad, tokens=None, tables=None, cu=None, past=None,
               this=None):
    """The fifteen arguments `_step` hands the tick's executable (greedy,
    no adapters), zero-filled where the caller gives nothing."""
    B = eng.max_batch

    def arr(v, shape, dtype, fill=0):
        return jnp.asarray(np.full(shape, fill, dtype) if v is None
                           else np.asarray(v, dtype))
    return (eng.params, eng._key_cache, eng._value_cache, eng._kv_scales,
            arr(tokens, (tok_pad,), np.int32),
            arr(tables, (B, eng.max_blocks_per_seq), np.int32, -1),
            arr(cu, (B + 1,), np.int32), arr(past, (B,), np.int32),
            arr(this, (B,), np.int32), eng._rope_emb,
            jnp.ones((B,), jnp.float32), jnp.ones((B,), jnp.float32),
            jnp.zeros((B, 2), jnp.uint32), jnp.ones((B,), bool), ())


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


class TestPoolUpdatedInPlace:
    """Counts, not times: the guard that keeps a later refactor from
    bringing the whole-pool passes back into the tick."""

    @pytest.mark.parametrize("pallas", [True, False])
    @pytest.mark.parametrize("tick", ["decode", "mixed"])
    def test_tick_carries_the_pool_and_writes_rows(self, tiny, pallas, tick):
        cfg, params = tiny
        eng = PagedServingEngine(cfg, params, pallas=pallas, **ENGINE_KW)
        # a decode tick packs max_batch rows (and with the kernel takes the
        # max_q=1 launch), a mixed tick token_budget rows
        tok_pad = eng.max_batch if tick == "decode" else eng.token_budget
        fn = eng._build_step(tok_pad, eng.max_batch,
                             pallas and tick == "decode")
        args = _step_args(eng, tok_pad)
        pool = eng._key_cache.shape
        layer = pool[1:]
        slots = eng.num_blocks * eng.block_size

        eqns = list(_eqns(jax.make_jaxpr(fn)(*args).jaxpr))
        scans = [e for e in eqns if e.primitive.name == "scan"
                 and e.params["length"] == cfg.num_layers]
        assert len(scans) == 1
        scan = scans[0]
        nc, ncar = scan.params["num_consts"], scan.params["num_carry"]
        carry = [v.aval.shape for v in scan.invars[nc:nc + ncar]]
        xs = [v.aval.shape for v in scan.invars[nc + ncar:]]
        ys = [v.aval.shape for v in scan.outvars[ncar:]]
        # both caches are loop state; nothing of the pool's shape, whole or
        # one layer of it, is scanned over or stacked up
        assert carry.count(pool) == 2
        assert pool not in xs + ys and layer not in xs + ys
        # the write is a row write: no one-hot matmul and no select over
        # every slot of a layer
        for e in eqns:
            if e.primitive.name in ("dot_general", "select_n"):
                shapes = [v.aval.shape for v in e.invars]
                assert not any(slots in sh for sh in shapes), (e, shapes)
        if pallas:
            # ...and the kernel reads the stack through the layer index: no
            # equation of the loop body yields one layer of the pool
            body = list(_eqns(scan.params["jaxpr"].jaxpr))
            made = [v.aval.shape for e in body for v in e.outvars]
            assert layer not in made and (1,) + layer not in made

        # input, loop state and output are one buffer: the donated caches
        # alias the step's outputs
        text = fn.lower(*args).as_text()
        aliased = re.findall(r"tensor<%s[^>]*> \{[^}]*tf\.aliasing_output"
                             % "x".join(map(str, pool)), text)
        assert len(aliased) == 2, text[:2000]

    def test_step_build_names_the_write_form(self, tiny):
        cfg, params = tiny
        for pallas, want in ((True, "pallas_pages"), (False, "scatter_rows")):
            eng = PagedServingEngine(cfg, params, pallas=pallas, **ENGINE_KW)
            eng._get_step_fn(16, 4)
            builds = [f for _, _, kind, _, f in obs.recorder().events()
                      if kind == "serving.step_build"]
            assert builds[-1]["cache_write"] == want


def _onehot_layer(qkv, key_pool, value_pool, layer, seq_lens_decoder,
                  seq_lens_this_time, cu_seqlens_q, block_tables, **kw):
    """The reference for the pool's contents: the write this repo had
    before the pool became loop state. One layer's pages are sliced out
    of the stack, every slot of the layer is rewritten through a one-hot
    product over the tick's rows, and the layer is put back. The rows
    (split, rope, int8 rounding) and the attention output are the
    program's own: only the write differs."""
    from paddle_tpu.ops.kernels import serving_attention as SA
    out, qkv_out, _, _ = SA.paged_layer_attention(
        qkv, key_pool, value_pool, layer, seq_lens_decoder,
        seq_lens_this_time, cu_seqlens_q, block_tables, **kw)
    _, nb, KV, bs, hd = key_pool.shape
    tok, B = qkv.shape[0], block_tables.shape[0]
    H = qkv.shape[1] // hd - 2 * KV
    qkv3 = qkv.reshape(tok, H + 2 * KV, hd)
    k_tok, v_tok = qkv3[:, H:H + KV], qkv3[:, H + KV:]
    cu = cu_seqlens_q.astype(jnp.int32)
    idx = jnp.arange(tok, dtype=jnp.int32)
    tok_b = jnp.clip(jnp.searchsorted(cu, idx, side="right") - 1, 0, B - 1)
    local = idx - cu[tok_b]
    pos = seq_lens_decoder[tok_b] + local
    valid = local < seq_lens_this_time[tok_b]
    if kw.get("rope_emb") is not None:
        cos_t, sin_t = SA._rotary_table(kw["rope_emb"], hd)
        k_tok = SA._rope_pairwise(k_tok, cos_t[0, pos][:, None],
                                  sin_t[0, pos][:, None],
                                  kw["use_neox_style"])
    if kw.get("quant_scales") is not None:
        kq, vq = kw["quant_scales"][:2]
        k_tok = jnp.clip(jnp.round(k_tok * kq.reshape(1, KV, 1)), -127, 127)
        v_tok = jnp.clip(jnp.round(v_tok * vq.reshape(1, KV, 1)), -127, 127)
    page = jnp.take_along_axis(block_tables[tok_b], (pos // bs)[:, None],
                               axis=1)[:, 0]
    flat = jnp.where(valid, page * bs + pos % bs, -1)
    onehot = flat[None, :] == jnp.arange(nb * bs)[:, None]     # [slots, tok]
    written = onehot.any(axis=1)[:, None, None]

    def write(pool, rows):
        pages = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
        slots = pages.transpose(0, 2, 1, 3).reshape(nb * bs, KV, hd)
        new = jnp.einsum("st,tkd->skd", onehot.astype(jnp.float32),
                         rows.astype(jnp.float32),
                         precision="highest").astype(pool.dtype)
        slots = jnp.where(written, new, slots)
        pages = slots.reshape(nb, bs, KV, hd).transpose(0, 2, 1, 3)
        return jax.lax.dynamic_update_index_in_dim(pool, pages, layer, 0)
    return out, qkv_out, write(key_pool, k_tok), write(value_pool, v_tok)


def _sentinel_pools(eng, seed=5):
    """Fill the engine's pools with a pattern, so that a page nobody
    writes can be told from one that was rewritten with what it held."""
    rs = np.random.RandomState(seed)
    shape, dtype = eng._key_cache.shape, eng._key_cache.dtype
    mk = ((lambda: rs.randint(-127, 128, shape).astype(np.int8))
          if dtype == jnp.int8 else
          (lambda: rs.randn(*shape).astype(np.float32)))
    k, v = mk(), mk()
    eng._key_cache, eng._value_cache = jnp.asarray(k), jnp.asarray(v)
    return k, v


@pytest.fixture(scope="module")
def kv_manifest(tiny):
    from paddle_tpu.inference import quant as Q
    cfg, params = tiny
    rs = np.random.RandomState(7)
    return Q.calibrate(cfg, params,
                       [rs.randint(1, cfg.vocab_size, (2, 12))
                        for _ in range(2)])


class TestPoolContentParity:
    """After N ticks the carried pool holds, bit for bit, what the
    one-hot write puts there from the same inputs, and pages that no
    sequence owns keep what they held."""

    # name -> (prompt lengths, new tokens, engine options)
    CASES = {
        "decode_ticks": ([3, 5, 2, 6], 7, {}),
        "chunk_ends_mid_page": ([37, 3], 3, {}),    # 16 + 16 + 5 rows, 4 a page
        "idle_slots": ([6], 5, {}),
        "int8_pages": ([9, 4, 18], 5, {"quant_kv": True}),
        "spec_mode": ([5, 7], 8, {"spec": True}),
        "lora_class": ([6, 11, 4], 5, {"lora": True}),
    }

    def _run(self, tiny, kv_manifest, pallas, lens, new, opts):
        from paddle_tpu.inference.serving import DraftModel, make_adapter
        cfg, params = tiny
        kw = dict(ENGINE_KW)
        if opts.get("quant_kv"):
            kw.update(quant_kv=True, quant_manifest=kv_manifest)
        if opts.get("spec"):
            dcfg = L.LlamaConfig(vocab_size=97, hidden_size=32,
                                 intermediate_size=64, num_layers=1,
                                 num_heads=4, num_kv_heads=2, max_seq_len=96,
                                 dtype=jnp.float32)
            dparams = dict(params, blocks=jax.tree.map(lambda a: a[:1],
                                                       params["blocks"]))
            kw.update(draft=DraftModel(dcfg, dparams), spec_k=3)
        if opts.get("lora"):
            kw.update(adapter_slots=2)
        eng = PagedServingEngine(cfg, params, pallas=pallas, max_len=96, **kw)
        if opts.get("lora"):
            eng.adapters.register(make_adapter(cfg, "a", rank=4, seed=3))
        before = _sentinel_pools(eng)
        owned, table_of = set(), eng.blocks.block_table

        def spy(rid):
            row = table_of(rid)
            owned.update(int(b) for b in row)
            return row
        eng.blocks.block_table = spy
        rids = [eng.submit(p, max_new_tokens=new,
                           **({"adapter": "a"} if opts.get("lora") and i == 0
                              else {}))
                for i, p in enumerate(_prompts(cfg, len(lens), lens, seed=31))]
        done = {c.rid: c.output_tokens for c in eng.run()}
        return ([done[r] for r in rids], np.asarray(eng._key_cache),
                np.asarray(eng._value_cache), before, owned, eng.stats)

    @pytest.mark.parametrize("pallas", [True, False])
    @pytest.mark.parametrize("case", sorted(CASES))
    def test_engine_pool_equals_onehot_write(self, tiny, kv_manifest,
                                             monkeypatch, case, pallas):
        from paddle_tpu.inference.serving import engine as E
        lens, new, opts = self.CASES[case]
        toks, k, v, (k0, v0), owned, stats = self._run(
            tiny, kv_manifest, pallas, lens, new, opts)
        monkeypatch.setattr(E, "paged_layer_attention", _onehot_layer)
        ref_toks, rk, rv, _, ref_owned, _ = self._run(
            tiny, kv_manifest, pallas, lens, new, opts)
        assert toks == ref_toks and owned == ref_owned
        assert np.array_equal(k, rk) and np.array_equal(v, rv)
        free = sorted(set(range(ENGINE_KW["num_blocks"])) - owned)
        assert free and len(owned) >= len(lens)
        assert np.array_equal(k[:, free], k0[:, free])
        assert np.array_equal(v[:, free], v0[:, free])
        assert not np.array_equal(k, k0)
        if case == "decode_ticks" and pallas:
            assert stats["decode_fast_steps"] > 0
        if case == "spec_mode":
            assert stats["spec_ticks"] > 0

    @pytest.mark.parametrize("pallas", [True, "decode", False])
    def test_unassigned_table_entry_writes_nothing(self, tiny, monkeypatch,
                                                   pallas):
        """A row whose table entry is −1 is dropped, the rows beside it
        land, pad rows and idle slots write nothing."""
        from paddle_tpu.inference.serving import engine as E
        cfg, params = tiny
        B, bs = ENGINE_KW["max_batch"], ENGINE_KW["block_size"]
        decode = pallas == "decode"
        tok_pad = B if decode else ENGINE_KW["token_budget"]
        if decode:      # slot 1's only page is unassigned, slot 3 is idle
            past, this = [5, 2, 9, 0], [1, 1, 1, 0]
            tables = {0: [7, 3], 1: [-1], 2: [11, 12, 13]}
        else:           # slot 0's chunk crosses an unassigned page
            past, this = [2, 0, 9, 0], [9, 0, 1, 0]
            tables = {0: [7, -1, 21], 2: [11, 12, 13]}
        cu = np.concatenate([[0], np.cumsum(this)])
        tab = np.full((B, 96 // bs), -1, np.int32)
        for b, row in tables.items():
            tab[b, :len(row)] = row
        tokens = np.arange(1, tok_pad + 1) % cfg.vocab_size

        def tick():
            eng = PagedServingEngine(cfg, params, pallas=bool(pallas),
                                     max_len=96, **ENGINE_KW)
            before = _sentinel_pools(eng)
            fn = eng._get_step_fn(tok_pad, B, decode)
            _, kc, vc = fn(*_step_args(eng, tok_pad, tokens, tab, cu, past,
                                       this))
            return np.asarray(kc), np.asarray(vc), before
        k, v, (k0, v0) = tick()
        monkeypatch.setattr(E, "paged_layer_attention", _onehot_layer)
        rk, rv, _ = tick()
        assert np.array_equal(k, rk) and np.array_equal(v, rv)
        changed = {int(p) for p in
                   np.nonzero((k != k0).any(axis=(0, 2, 3, 4)))[0]}
        assert changed == ({3, 13} if decode else {7, 21, 13})


class TestPagePlan:
    """`page_plan` against a walk over the tokens: every valid token has
    its page, slot and source row in the plan exactly once, nothing else
    is in it, and an entry is repeated only right after itself."""

    @pytest.mark.parametrize("B, bs, tokens, max_blocks, num_blocks", [
        (16, 16, 512, 128, 2304),      # the benchmark's mixed tick
        (16, 16, 16, 128, 2304),       # ... and its decode tick
        (4, 4, 16, 24, 96), (3, 8, 7, 6, 40)])
    def test_plan_is_the_tokens_pages(self, B, bs, tokens, max_blocks,
                                      num_blocks):
        from paddle_tpu.ops.kernels.serving_attention import page_plan
        rs = np.random.RandomState(B * tokens)
        plan = jax.jit(lambda *a: page_plan(*a, num_blocks, bs, tokens))
        for _ in range(40):
            this, left = np.zeros(B, np.int64), tokens
            for b in rs.permutation(B):   # idle, decode row or a chunk
                kind = rs.randint(4)
                n = (0, 1)[kind] if kind < 2 else rs.randint(1, left + 1) \
                    if left else 0
                this[b] = min(n, left)
                left -= this[b]
            past = np.array([rs.randint(0, max_blocks * bs - n + 1)
                             for n in this])
            cu = np.concatenate([[0], np.cumsum(this)])
            tab = np.full((B, max_blocks), -1, np.int64)
            free = iter(rs.permutation(num_blocks))
            for b in range(B):
                for p in range(-(-(past[b] + this[b]) // bs)):
                    tab[b, p] = next(free)
            if rs.rand() < 0.3:           # an unassigned entry somewhere
                tab[rs.randint(B), rs.randint(max_blocks)] = -1
            pages, lo, hi, src = (np.asarray(a) for a in plan(
                *(jnp.asarray(a, jnp.int32) for a in (past, this, cu, tab))))
            want = {(tab[b, (past[b] + i) // bs], (past[b] + i) % bs):
                    cu[b] + i for b in range(B) for i in range(this[b])
                    if tab[b, (past[b] + i) // bs] >= 0}
            got = {}
            for j in range(len(pages)):
                if j and pages[j] in pages[:j] and hi[j] > lo[j]:
                    assert (pages[j], lo[j], hi[j]) == (
                        pages[j - 1], lo[j - 1], hi[j - 1])
                    assert np.array_equal(src[j], src[j - 1])
                for s in range(lo[j], hi[j]):
                    got[(pages[j], s)] = src[j, s]
            assert got == want
