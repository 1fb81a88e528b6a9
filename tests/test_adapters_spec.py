"""Multi-tenant adapter serving + speculative decoding tests.

Two contracts pin the whole subsystem:

- **bit-exactness** — LoRA adapters change ONLY the rows that asked for
  them (base rows in a mixed batch match the adapter-off engine
  token-for-token; each adapter row matches a solo run of that
  adapter), and speculative decoding changes NOTHING (greedy spec
  output is identical to plain greedy decode, through preemption
  recompute, prefix/COW sharing, chaos eviction and replica failover —
  a wrong draft costs acceptance rate, never correctness);
- **zero steady-state retraces** — which adapter a request uses is
  data (slot selectors into the stacked rank-class pack), so hot-swaps
  and chaos evictions never build a new step executable; the draft
  holds at exactly two cached executables of its own.

Also covers: the CRC'd versioned adapter manifest, the raw/q8 wire
codec, pin/unpin refcount pairing, LRU slot eviction +
NoAdapterSlotsError, the transport publish/fetch plane under chaos
``adapter:corrupt``/``adapter:delay``, adapter-aware router placement
with transport prefetch, the per-adapter fleet digest, and the
``summary()["adapters"]``/``["spec"]`` observability sections.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.distributed.fault_tolerance import chaos
from paddle_tpu.inference.serving import (AdapterCorruptError,
                                          AdapterManager,
                                          AdapterMissingError,
                                          AdapterTransport, DraftModel,
                                          LoraAdapter, NoAdapterSlotsError,
                                          PagedServingEngine, ServingRouter,
                                          load_adapter, make_adapter,
                                          pack_adapter, save_adapter,
                                          unpack_adapter)
from paddle_tpu.inference.serving.adapters import rank_class, target_dims
from paddle_tpu.models import llama as L

ENGINE_KW = dict(num_blocks=96, block_size=8, max_batch=8, token_budget=32)


@pytest.fixture(scope="module")
def tiny():
    cfg = L.LlamaConfig(vocab_size=97, hidden_size=32,
                        intermediate_size=64, num_layers=2, num_heads=4,
                        num_kv_heads=2, max_seq_len=96, dtype=jnp.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def draft(tiny):
    """Half-depth draft reusing the target's own layer-prefix weights —
    cheap, and correlated enough that acceptance is well above zero."""
    cfg, params = tiny
    dcfg = L.LlamaConfig(vocab_size=97, hidden_size=32,
                         intermediate_size=64, num_layers=1, num_heads=4,
                         num_kv_heads=2, max_seq_len=96, dtype=jnp.float32)
    dparams = {"embed": params["embed"],
               "final_norm": params["final_norm"],
               "lm_head": params["lm_head"],
               "blocks": jax.tree.map(lambda a: a[:1], params["blocks"])}
    return dcfg, dparams


def _prompts(cfg, n, ln=8, seed=1):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, cfg.vocab_size, (ln,)).tolist() for _ in range(n)]


def _run(eng, prompts, adapters=None, max_new=8, **kw):
    rids = []
    for i, p in enumerate(prompts):
        extra = dict(kw)
        if adapters is not None and adapters[i] is not None:
            extra["adapter"] = adapters[i]
        rids.append(eng.submit(p, max_new_tokens=max_new, **extra))
    done = {c.rid: c.output_tokens for c in eng.run()}
    return [done.get(r) for r in rids]


def _engine(tiny, **over):
    cfg, params = tiny
    kw = dict(ENGINE_KW, **over)
    return PagedServingEngine(cfg, params, max_len=cfg.max_seq_len, **kw)


def _spec_engine(tiny, draft, **over):
    dcfg, dparams = draft
    return _engine(tiny, draft=DraftModel(dcfg, dparams), spec_k=3, **over)


# ---------------------------------------------------------------------------
# manifest: CRC'd versioned persistence
# ---------------------------------------------------------------------------

class TestManifest:
    def test_round_trip_bit_exact(self, tiny, tmp_path):
        cfg, _ = tiny
        ad = make_adapter(cfg, "billing", rank=3, alpha=6.0, seed=7)
        p = str(tmp_path / "billing.json")
        save_adapter(ad, cfg, p)
        got = load_adapter(p, cfg)
        assert got.name == "billing" and got.rank == 3
        assert got.alpha == 6.0 and got.scaling == 2.0
        for t in ad.weights:
            np.testing.assert_array_equal(got.weights[t][0],
                                          ad.weights[t][0])
            np.testing.assert_array_equal(got.weights[t][1],
                                          ad.weights[t][1])

    def test_hand_edit_fails_crc(self, tiny, tmp_path):
        cfg, _ = tiny
        p = str(tmp_path / "a.json")
        save_adapter(make_adapter(cfg, "a"), cfg, p)
        with open(p) as f:
            doc = json.load(f)
        doc["payload"]["alpha"] = 99.0
        with open(p, "w") as f:
            json.dump(doc, f)
        with pytest.raises(ValueError, match="CRC"):
            load_adapter(p)

    def test_bad_format_and_version_fail_loud(self, tiny, tmp_path):
        cfg, _ = tiny
        p = str(tmp_path / "a.json")
        save_adapter(make_adapter(cfg, "a"), cfg, p)
        with open(p) as f:
            doc = json.load(f)
        for key, val, pat in (("format", "nope", "format"),
                              ("version", 99, "version")):
            bad = dict(doc)
            bad[key] = val
            with open(p, "w") as f:
                json.dump(bad, f)
            with pytest.raises(ValueError, match=pat):
                load_adapter(p)
        with open(p, "w") as f:
            f.write("{not json")
        with pytest.raises(ValueError, match="unreadable"):
            load_adapter(p)

    def test_model_signature_mismatch(self, tiny, tmp_path):
        cfg, _ = tiny
        p = str(tmp_path / "a.json")
        save_adapter(make_adapter(cfg, "a"), cfg, p)
        other = L.LlamaConfig(vocab_size=97, hidden_size=32,
                              intermediate_size=64, num_layers=3,
                              num_heads=4, num_kv_heads=2, max_seq_len=96,
                              dtype=jnp.float32)
        with pytest.raises(ValueError, match="different model"):
            load_adapter(p, other)


# ---------------------------------------------------------------------------
# wire codec: raw + q8
# ---------------------------------------------------------------------------

class TestWireCodec:
    def test_raw_round_trip_bit_exact(self, tiny):
        cfg, _ = tiny
        ad = make_adapter(cfg, "w", rank=4, seed=2)
        got = unpack_adapter(pack_adapter(ad, wire="raw"))
        assert got.name == ad.name and got.rank == ad.rank
        for t in ad.weights:
            np.testing.assert_array_equal(got.weights[t][0],
                                          ad.weights[t][0])

    def test_int8_wire_smaller_and_close(self, tiny):
        cfg, _ = tiny
        ad = make_adapter(cfg, "w", rank=4, seed=2)
        raw, q8 = pack_adapter(ad, wire="raw"), pack_adapter(ad,
                                                             wire="int8")
        assert len(q8) < 0.5 * len(raw)
        got = unpack_adapter(q8)
        for t in ad.weights:
            a, b = ad.weights[t]
            np.testing.assert_allclose(got.weights[t][0], a, atol=2e-3)
            np.testing.assert_allclose(got.weights[t][1], b, atol=2e-3)

    def test_corrupt_blob_rejected(self, tiny):
        cfg, _ = tiny
        blob = pack_adapter(make_adapter(cfg, "w"), wire="raw")
        bad = blob[:-1] + bytes([blob[-1] ^ 0xFF])
        with pytest.raises(AdapterCorruptError, match="CRC"):
            unpack_adapter(bad)
        with pytest.raises(AdapterCorruptError):
            unpack_adapter(b"garbage with no header newline?" * 3)

    def test_rank_class_padding(self):
        assert [rank_class(r) for r in (1, 2, 3, 4, 5, 8, 9)] == \
            [1, 2, 4, 4, 8, 8, 16]


# ---------------------------------------------------------------------------
# AdapterManager: slots, refcounts, LRU
# ---------------------------------------------------------------------------

class TestAdapterManager:
    def test_register_get_missing(self, tiny):
        cfg, _ = tiny
        mgr = AdapterManager(cfg, slots=2)
        mgr.register(make_adapter(cfg, "a"))
        assert mgr.registered("a") and mgr.names() == ["a"]
        assert not mgr.has("a")          # registered != device-resident
        with pytest.raises(AdapterMissingError):
            mgr.get("nope")
        with pytest.raises(AdapterMissingError):
            mgr.slot_of("a")             # not loaded yet

    def test_pin_unpin_refcount_pairing(self, tiny):
        cfg, _ = tiny
        mgr = AdapterManager(cfg, slots=2)
        mgr.register(make_adapter(cfg, "a"))
        with pytest.raises(AdapterMissingError):
            mgr.pin("ghost")             # raises BEFORE any count moves
        assert mgr.ref_count("ghost") == 0
        mgr.pin("a")
        mgr.pin("a")
        assert mgr.ref_count("a") == 2
        mgr.unpin("a")
        mgr.unpin("a")
        with pytest.raises(ValueError, match="unpin"):
            mgr.unpin("a")
        assert mgr.stats["pins"] == mgr.stats["unpins"] == 2

    def test_lru_eviction_counts_swap(self, tiny):
        cfg, _ = tiny
        mgr = AdapterManager(cfg, slots=1)
        for n in ("a", "b"):
            mgr.register(make_adapter(cfg, n, rank=4))
        mgr.ensure_loaded("a")
        assert mgr.has("a") and mgr.stats["swaps"] == 0
        mgr.ensure_loaded("b")           # evicts a (LRU, refcount 0)
        assert mgr.has("b") and not mgr.has("a")
        assert mgr.stats["evictions"] == 1
        mgr.ensure_loaded("a")           # re-load after eviction = swap
        assert mgr.stats["swaps"] == 1

    def test_all_slots_pinned_raises(self, tiny):
        cfg, _ = tiny
        mgr = AdapterManager(cfg, slots=1)
        for n in ("a", "b"):
            mgr.register(make_adapter(cfg, n, rank=4))
        mgr.pin("a")
        mgr.ensure_loaded("a")
        with pytest.raises(NoAdapterSlotsError, match="pinned"):
            mgr.ensure_loaded("b")
        mgr.unpin("a")                   # refcount 0 -> evictable again
        assert mgr.ensure_loaded("b")[0] == 4

    def test_evict_keeps_host_copy(self, tiny):
        cfg, _ = tiny
        mgr = AdapterManager(cfg, slots=2)
        mgr.register(make_adapter(cfg, "a"))
        cls, slot = mgr.ensure_loaded("a")
        before = np.asarray(mgr.device_packs(cls)["wq"][0][:, slot])
        assert mgr.evict_device("a", why="chaos")
        assert not mgr.has("a") and mgr.registered("a")
        assert not mgr.evict_device("a")        # idempotent
        cls2, slot2 = mgr.ensure_loaded("a")    # bit-identical re-pin
        after = np.asarray(mgr.device_packs(cls2)["wq"][0][:, slot2])
        np.testing.assert_array_equal(before, after)

    def test_replace_pinned_refused(self, tiny):
        cfg, _ = tiny
        mgr = AdapterManager(cfg, slots=2)
        mgr.register(make_adapter(cfg, "a", seed=1))
        mgr.pin("a")
        with pytest.raises(ValueError, match="pinned"):
            mgr.register(make_adapter(cfg, "a", seed=2))
        mgr.unpin("a")
        mgr.register(make_adapter(cfg, "a", seed=2))   # drain -> ok

    def test_bytes_accounting_and_snapshot(self, tiny):
        cfg, _ = tiny
        mgr = AdapterManager(cfg, slots=2)
        assert mgr.bytes_total() == mgr.bytes_in_use() == 0
        mgr.register(make_adapter(cfg, "a", rank=4))
        mgr.ensure_loaded("a")
        # slots are pre-allocated per class: total covers BOTH slots,
        # in_use only the occupied one
        assert mgr.bytes_total() == 2 * mgr.bytes_in_use() > 0
        dims = target_dims(cfg)
        want = sum(4 * cfg.num_layers * (din * 4 + 4 * dout)
                   for din, dout in dims.values())
        assert mgr.bytes_in_use() == want
        snap = mgr.snapshot()
        assert snap["registered"] == ["a"] and "a" in snap["resident"]
        assert snap["resident"]["a"]["rank_class"] == 4
        assert snap["slots_per_class"] == 2

    def test_mixed_rank_classes_separate_packs(self, tiny):
        cfg, _ = tiny
        mgr = AdapterManager(cfg, slots=1)
        mgr.register(make_adapter(cfg, "small", rank=2))
        mgr.register(make_adapter(cfg, "big", rank=8))
        c1, _ = mgr.ensure_loaded("small")
        c2, _ = mgr.ensure_loaded("big")
        assert (c1, c2) == (2, 8)
        # one slot per CLASS: different classes never evict each other
        assert mgr.has("small") and mgr.has("big")
        assert mgr.num_resident() == 2


# ---------------------------------------------------------------------------
# transport: publish/fetch, prefetch, chaos corrupt + delay drills
# ---------------------------------------------------------------------------

class TestTransport:
    def test_publish_fetch_prefetch(self, tiny):
        cfg, _ = tiny
        tr = AdapterTransport()
        ad = make_adapter(cfg, "pub", rank=4, seed=5)
        nbytes = tr.publish(ad)
        assert nbytes > 0 and tr.stats["publishes"] == 1
        got = tr.fetch("pub")
        assert got is not None and got.name == "pub"
        assert tr.fetch("ghost") is None
        mgr = AdapterManager(cfg, slots=2)
        assert mgr.prefetch("pub", tr) == "ok"
        assert mgr.registered("pub")
        assert mgr.prefetch("pub", tr) == "registered"
        assert mgr.prefetch("ghost", tr) == "miss"

    def test_chaos_corrupt_drill(self, tiny):
        """adapter:corrupt on the fetch path flips a payload byte; the
        CRC rejects it and prefetch degrades to result='corrupt' instead
        of registering damaged weights."""
        cfg, _ = tiny
        tr = AdapterTransport()
        tr.publish(make_adapter(cfg, "pub", seed=5))
        mgr = AdapterManager(cfg, slots=2)
        chaos.reconfigure("adapter:corrupt@op=fetch")
        try:
            assert mgr.prefetch("pub", tr) == "corrupt"
        finally:
            chaos.reconfigure("")
        assert not mgr.registered("pub")
        assert mgr.prefetch("pub", tr) == "ok"   # clean retry succeeds

    def test_chaos_corrupt_on_publish(self, tiny):
        cfg, _ = tiny
        tr = AdapterTransport()
        chaos.reconfigure("adapter:corrupt@op=publish")
        try:
            tr.publish(make_adapter(cfg, "pub", seed=5))
        finally:
            chaos.reconfigure("")
        with pytest.raises(AdapterCorruptError):
            tr.fetch("pub")

    def test_chaos_delay_drill(self, tiny):
        """adapter:delay sleeps at the choke point — slow prefetch, not
        broken prefetch: the fetch still succeeds afterwards."""
        cfg, _ = tiny
        tr = AdapterTransport()
        tr.publish(make_adapter(cfg, "pub", seed=5))
        chaos.reconfigure("adapter:delay@op=fetch;delay=0.05")
        try:
            t0 = time.perf_counter()
            got = tr.fetch("pub")
            dt = time.perf_counter() - t0
        finally:
            chaos.reconfigure("")
        assert got is not None and got.name == "pub"
        assert dt >= 0.05


# ---------------------------------------------------------------------------
# engine: mixed-adapter batches, hot-swap, zero retraces, chaos evict
# ---------------------------------------------------------------------------

class TestEngineAdapters:
    def test_mixed_batch_base_rows_bit_exact(self, tiny):
        cfg, _ = tiny
        prompts = _prompts(cfg, 4)
        base_out = _run(_engine(tiny), prompts)
        eng = _engine(tiny, adapter_slots=2)
        eng.adapters.register(make_adapter(cfg, "t-a", rank=4, alpha=8.0,
                                           seed=3, scale=0.3))
        mixed = _run(eng, prompts, adapters=["t-a", None, "t-a", None])
        assert mixed[1] == base_out[1] and mixed[3] == base_out[3]
        assert mixed[0] != base_out[0] and mixed[2] != base_out[2]
        # adapter rows are counts plus selectors the host holds: their
        # ticks are launched ahead like any other
        assert eng.stats["ticks_ahead"] > 0

    def test_mixed_batch_matches_solo_runs(self, tiny):
        """Segmented application: each adapter row in a 2-adapter mixed
        batch is bit-identical to a solo run of that adapter."""
        cfg, _ = tiny
        prompts = _prompts(cfg, 4)
        ads = {n: make_adapter(cfg, n, rank=4, alpha=8.0, seed=s,
                               scale=0.3)
               for n, s in (("t-a", 3), ("t-b", 4))}

        def fresh():
            eng = _engine(tiny, adapter_slots=2)
            for a in ads.values():
                eng.adapters.register(a)
            return eng

        solo_a = _run(fresh(), prompts, adapters=["t-a"] * 4)
        solo_b = _run(fresh(), prompts, adapters=["t-b"] * 4)
        mixed = _run(fresh(), prompts,
                     adapters=["t-a", "t-b", "t-a", "t-b"])
        assert mixed == [solo_a[0], solo_b[1], solo_a[2], solo_b[3]]

    def test_hot_swap_beyond_slots_zero_retrace(self, tiny):
        """Three tenants over ONE device slot: every request forces an
        LRU swap, and none of it builds a new executable — adapter
        routing is data, not a trace key."""
        cfg, _ = tiny
        eng = _engine(tiny, adapter_slots=1)
        names = ["t-a", "t-b", "t-c"]
        for i, n in enumerate(names):
            eng.adapters.register(make_adapter(cfg, n, rank=4, seed=i))
        prompts = _prompts(cfg, 3)
        for n in names:                       # warm: serial, 1 slot
            _run(eng, prompts[:1], adapters=[n])
        builds = eng.stats["step_builds"]
        swaps0 = eng.adapters.stats["swaps"]
        for n in reversed(names):
            _run(eng, prompts[:1], adapters=[n])
        assert eng.stats["step_builds"] == builds
        assert eng.adapters.stats["swaps"] > swaps0

    def test_submit_unknown_adapter_fails_clean(self, tiny):
        eng = _engine(tiny)
        with pytest.raises(AdapterMissingError):
            eng.submit([1, 2, 3], max_new_tokens=4, adapter="ghost")
        assert eng.scheduler.queue_depth() == 0
        assert eng.adapters.stats["pins"] == eng.adapters.stats["unpins"]

    def test_completion_unpins_adapter(self, tiny):
        cfg, _ = tiny
        eng = _engine(tiny, adapter_slots=2)
        eng.adapters.register(make_adapter(cfg, "t-a"))
        _run(eng, _prompts(cfg, 2), adapters=["t-a", "t-a"])
        assert eng.adapters.ref_count("t-a") == 0
        assert eng.adapters.stats["pins"] == eng.adapters.stats["unpins"] \
            == 2

    def test_chaos_evict_mid_stream_bit_exact(self, tiny):
        """adapter:evict fires at the per-tick residency check: the slot
        is force-dropped mid-stream, the next tick reloads it (a swap),
        and the output stream never notices."""
        cfg, _ = tiny
        prompts = _prompts(cfg, 2)

        def fresh():
            eng = _engine(tiny, adapter_slots=2)
            eng.adapters.register(make_adapter(cfg, "t-a", rank=4,
                                               seed=3, scale=0.3))
            return eng

        ref = _run(fresh(), prompts, adapters=["t-a", "t-a"])
        eng = fresh()
        chaos.reconfigure("adapter:evict@op=use;call=3")
        try:
            got = _run(eng, prompts, adapters=["t-a", "t-a"])
        finally:
            chaos.reconfigure("")
        assert got == ref
        assert eng.adapters.stats["evictions"] >= 1
        assert eng.adapters.stats["swaps"] >= 1

    def test_adapter_bytes_ride_block_manager_gauges(self, tiny):
        cfg, _ = tiny
        eng = _engine(tiny, adapter_slots=2)
        kv_only = eng.blocks.bytes_total()
        eng.adapters.register(make_adapter(cfg, "t-a"))
        _run(eng, _prompts(cfg, 1), adapters=["t-a"])
        assert eng.blocks.bytes_total() == \
            kv_only + eng.adapters.bytes_total()
        assert eng.blocks.bytes_in_use() >= eng.adapters.bytes_in_use() > 0
        st = eng.engine_stats
        assert st["adapters_resident"] == 1
        assert st["adapter_bytes_in_use"] == eng.adapters.bytes_in_use()


# ---------------------------------------------------------------------------
# speculative decoding: bit-exact greedy parity in every regime
# ---------------------------------------------------------------------------

class TestSpeculative:
    def test_greedy_parity_weak_draft(self, tiny, draft):
        """A half-depth draft is WRONG often — and the output stream
        must not show it: bit-exact vs plain greedy, acceptance in
        (0, 1)."""
        cfg, _ = tiny
        prompts = _prompts(cfg, 4)
        base_out = _run(_engine(tiny), prompts, max_new=10)
        eng = _spec_engine(tiny, draft)
        assert _run(eng, prompts, max_new=10) == base_out
        assert eng.stats["spec_ticks"] > 0
        assert 0.0 < eng.spec.acceptance_rate <= 1.0
        # a speculative tick advances by the accepted length, which is no
        # count the host holds at dispatch: none is launched ahead
        assert eng.stats["ticks_ahead"] == 0

    def test_perfect_draft_full_acceptance(self, tiny):
        """Draft == target: every proposal is accepted, every tick emits
        k+1 tokens, and parity is trivially bit-exact."""
        cfg, params = tiny
        prompts = _prompts(cfg, 2)
        base_out = _run(_engine(tiny), prompts, max_new=9)
        eng = _engine(tiny, draft=DraftModel(cfg, params), spec_k=3)
        assert _run(eng, prompts, max_new=9) == base_out
        assert eng.spec.acceptance_rate == 1.0

    def test_parity_with_eos(self, tiny, draft):
        cfg, _ = tiny
        prompts = _prompts(cfg, 2)
        probe = _run(_engine(tiny), prompts, max_new=8)
        eos = probe[0][3]        # a token the stream actually produces
        base = _run(_engine(tiny), prompts, max_new=8, eos_token_id=eos)
        spec = _spec_engine(tiny, draft)
        assert _run(spec, prompts, max_new=8, eos_token_id=eos) == base

    def test_parity_through_preemption_recompute(self, tiny, draft):
        """A starved block pool forces preemption mid-decode; the
        epoch-guarded draft catch-up keeps the stream bit-exact."""
        cfg, _ = tiny
        kw = dict(num_blocks=10, block_size=8, max_batch=8,
                  token_budget=32)
        prompts = _prompts(cfg, 6)
        base = _run(_engine(tiny, **kw), prompts, max_new=10)
        eng = _spec_engine(tiny, draft, **kw)
        assert _run(eng, prompts, max_new=10) == base
        assert eng.scheduler.stats["preemptions"] >= 1

    def test_parity_with_prefix_sharing(self, tiny, draft):
        """Shared-prefix prompts ride the prefix cache + COW; the draft
        mirrors page copies eagerly and parity holds."""
        cfg, _ = tiny
        rs = np.random.RandomState(3)
        shared = rs.randint(1, cfg.vocab_size, (16,)).tolist()
        prompts = [shared + rs.randint(1, cfg.vocab_size, (3,)).tolist()
                   for _ in range(4)]
        base = _run(_engine(tiny), prompts, max_new=8)
        eng = _spec_engine(tiny, draft)
        assert _run(eng, prompts, max_new=8) == base
        assert eng.blocks.stats["prefix_hit_tokens"] > 0

    def test_sampled_requests_not_speculated(self, tiny, draft):
        """Greedy verification needs temperature==0 — sampled requests
        decode the normal path, spec stays off for them."""
        cfg, _ = tiny
        eng = _spec_engine(tiny, draft)
        out = _run(eng, _prompts(cfg, 2), max_new=6, temperature=0.8,
                   seed=11)
        assert all(len(o) == 6 for o in out)
        assert eng.stats["spec_ticks"] == 0

    def test_zero_retrace_and_two_draft_fns(self, tiny, draft):
        cfg, _ = tiny
        prompts = _prompts(cfg, 3)
        eng = _spec_engine(tiny, draft)
        first = _run(eng, prompts, max_new=8)
        builds = eng.stats["step_builds"]
        assert _run(eng, prompts, max_new=8) == first
        assert eng.stats["step_builds"] == builds
        # catch-up chunk + 1-token proposal: exactly two executables
        assert len(eng.spec._fns) <= 2
        assert eng.spec.stats["draft_builds"] <= 2

    def test_acceptance_accounting(self, tiny, draft):
        cfg, _ = tiny
        eng = _spec_engine(tiny, draft)
        _run(eng, _prompts(cfg, 3), max_new=8)
        s = eng.spec.stats
        assert s["proposed"] >= s["accepted"] >= 0
        assert s["ticks"] == eng.stats["spec_ticks"] > 0
        assert s["bonus"] == s["ticks"]
        assert eng.spec.acceptance_rate == round(
            s["accepted"] / s["proposed"], 4)
        snap = eng.spec.snapshot()
        assert snap["acceptance_rate"] == eng.spec.acceptance_rate
        assert "tracked_sequences" in snap
        st = eng.engine_stats
        assert st["spec_acceptance_rate"] == eng.spec.acceptance_rate

    def test_draft_validation_fails_loud(self, tiny):
        cfg, params = tiny
        bad_vocab = L.LlamaConfig(vocab_size=101, hidden_size=32,
                                  intermediate_size=64, num_layers=1,
                                  num_heads=4, num_kv_heads=2,
                                  max_seq_len=96, dtype=jnp.float32)
        with pytest.raises(ValueError, match="vocab"):
            _engine(tiny, draft=DraftModel(
                bad_vocab, L.init_params(bad_vocab, jax.random.PRNGKey(1))))
        short = L.LlamaConfig(vocab_size=97, hidden_size=32,
                              intermediate_size=64, num_layers=1,
                              num_heads=4, num_kv_heads=2, max_seq_len=32,
                              dtype=jnp.float32)
        with pytest.raises(ValueError, match="max_seq_len"):
            _engine(tiny, draft=DraftModel(
                short, L.init_params(short, jax.random.PRNGKey(1))))

    def test_spec_composes_with_adapters(self, tiny, draft):
        """Adapters + speculation together: the adapter-routed stream
        under spec equals the same adapter stream without spec."""
        cfg, _ = tiny
        prompts = _prompts(cfg, 2)
        ad = make_adapter(cfg, "t-a", rank=4, seed=3, scale=0.3)

        def fresh(spec):
            eng = (_spec_engine(tiny, draft, adapter_slots=2) if spec
                   else _engine(tiny, adapter_slots=2))
            eng.adapters.register(ad)
            return eng

        ref = _run(fresh(False), prompts, adapters=["t-a", None])
        eng = fresh(True)
        assert _run(eng, prompts, adapters=["t-a", None]) == ref
        assert eng.stats["spec_ticks"] > 0


# ---------------------------------------------------------------------------
# router + fleet: adapter-aware placement, failover mid-spec, digests
# ---------------------------------------------------------------------------

class TestRouterFleet:
    def test_adapter_affinity_routes_to_resident_replica(self, tiny):
        cfg, _ = tiny
        prompts = _prompts(cfg, 4)
        router = ServingRouter(lambda: _engine(tiny, adapter_slots=2),
                               num_replicas=2, probation_s=1e9)
        ad = make_adapter(cfg, "t-a", rank=4, seed=3)
        # registered + loaded ONLY on replica 1 -> placement must prefer
        # it for adapter traffic even though replica 0 is less loaded
        router.replicas[1].engine.adapters.register(ad)
        router.replicas[1].engine.adapters.ensure_loaded("t-a")
        for p in prompts:
            router.submit(p, max_new_tokens=6, adapter="t-a")
        done = router.run()
        assert len(done) == 4
        assert router.stats["adapter_routed"] == 4
        assert router.replicas[1].engine.adapters.stats["hits"] > 0
        assert router.replicas[0].engine.adapters.stats["hits"] == 0

    def test_prefetch_over_transport(self, tiny):
        """No replica knows the adapter, the transport does: placement
        prefetches it onto the chosen replica instead of failing."""
        cfg, _ = tiny
        tr = AdapterTransport()
        tr.publish(make_adapter(cfg, "t-a", rank=4, seed=3))
        router = ServingRouter(lambda: _engine(tiny, adapter_slots=2),
                               num_replicas=2, probation_s=1e9,
                               adapter_transport=tr)
        for p in _prompts(cfg, 2):
            router.submit(p, max_new_tokens=6, adapter="t-a")
        done = router.run()
        assert len(done) == 2
        assert router.stats["adapter_prefetches"] >= 1

    def test_publish_adapter_reaches_all_replicas(self, tiny):
        cfg, _ = tiny
        tr = AdapterTransport()
        router = ServingRouter(lambda: _engine(tiny, adapter_slots=2),
                               num_replicas=2, probation_s=1e9,
                               adapter_transport=tr)
        router.publish_adapter(make_adapter(cfg, "t-a", rank=4, seed=3))
        for h in router.replicas:
            assert h.engine.adapters.registered("t-a")
        assert tr.fetch("t-a") is not None

    def test_unknown_adapter_request_sheds_not_livelocks(self, tiny):
        """An adapter registered nowhere (and absent from the transport)
        can never place: the request must shed terminally, not spin in
        the pending queue forever."""
        cfg, _ = tiny
        router = ServingRouter(lambda: _engine(tiny), num_replicas=1,
                               probation_s=1e9)
        router.submit(_prompts(cfg, 1)[0], max_new_tokens=4,
                      adapter="ghost")
        done = router.run()
        assert [c.finish_reason for c in done] == ["adapter_missing"]
        assert done[0].output_tokens == []
        assert router.stats["shed"] == 1

    def test_replica_kill_mid_spec_bit_exact_failover(self, tiny, draft):
        """The ISSUE's chaos drill: kill a replica mid-speculative-
        decode — exactly one failover wave, zero replay mismatches,
        output bit-equal to a single-engine run."""
        cfg, _ = tiny
        prompts = _prompts(cfg, 4)
        base = _run(_spec_engine(tiny, draft), prompts, max_new=12)
        # spec ticks emit up to k+1 tokens, so streams finish in few
        # guarded steps — the kill must land early to hit them mid-decode
        chaos.reconfigure("replica:kill@victim=0;call=2")
        try:
            router = ServingRouter(lambda: _spec_engine(tiny, draft),
                                   num_replicas=2, probation_s=1e9,
                                   tenant_weights={"default": 4})
            rids = [router.submit(p, max_new_tokens=12) for p in prompts]
            done = {c.rid: c.output_tokens for c in router.run()}
        finally:
            chaos.reconfigure("")
        assert [done.get(r) for r in rids] == base
        # both streams the dead replica held fail over, each counted
        assert router.stats["failovers"] == 2
        assert router.stats["mismatches"] == 0
        assert router.stats["shed"] == 0

    def test_summary_sections(self, tiny, draft):
        cfg, _ = tiny
        eng = _spec_engine(tiny, draft, adapter_slots=2)
        eng.adapters.register(make_adapter(cfg, "t-a", rank=4, seed=3))
        _run(eng, _prompts(cfg, 2), adapters=["t-a", None])
        s = obs.summary()
        ad = s["adapters"]
        for k in ("registered", "loads", "swaps", "evictions", "hits",
                  "resident", "bytes_in_use", "bytes_total"):
            assert k in ad
        assert ad["loads"] >= 1
        sp = s["spec"]
        for k in ("ticks", "proposed", "accepted", "bonus",
                  "draft_steps", "acceptance_rate"):
            assert k in sp
        assert sp["ticks"] >= 1

    def test_fleet_summary_per_adapter_digest(self, tiny):
        from paddle_tpu.observability.fleet import fleet_summary

        cfg, _ = tiny
        eng = _engine(tiny, adapter_slots=2)
        eng.adapters.register(make_adapter(cfg, "digest-t", rank=4,
                                           seed=3))
        _run(eng, _prompts(cfg, 2), adapters=["digest-t", "digest-t"])
        fs = fleet_summary()
        d = fs["adapters"]["digest-t"]
        assert d["loads"] >= 1 and d["hits"] >= 1
        assert d["resident_ranks"] >= 1
        assert "spec_acceptance_rate" in fs

    def test_replica_snapshot_has_adapter_fields(self, tiny, draft):
        cfg, _ = tiny
        router = ServingRouter(lambda: _spec_engine(tiny, draft,
                                                    adapter_slots=2),
                               num_replicas=1, probation_s=1e9)
        router.replicas[0].engine.adapters.register(
            make_adapter(cfg, "t-a", rank=4, seed=3))
        for p in _prompts(cfg, 2):
            router.submit(p, max_new_tokens=6, adapter="t-a")
        router.run()
        snap = router.replicas[0].snapshot()
        assert snap["adapters_resident"] == ["t-a"]
        assert snap["adapter_bytes_in_use"] > 0
        assert snap["adapter_hits"] >= 1
        assert "spec_acceptance_rate" in snap
