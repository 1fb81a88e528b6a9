"""Autotuner (paddle_tpu.tuner): the cost model's simulate-exact bubble
claim, the pruning-never-drops-the-winner guarantee on a seeded toy
space, the tuned-profile manifest's fail-loud discipline, and the
zero-retrace property of FLAGS_tuned_profile application.

The distributed/auto_tuner package is the reference-parity PLAN search
(dp/tp/pp degrees against an analytical cluster); paddle_tpu.tuner is
the measurement-driven FLAG tuner — these tests pin the latter.
"""
import json
import os
import zlib

import numpy as np
import pytest

import jax

from paddle_tpu import tuner
from paddle_tpu.core import flags
from paddle_tpu.distributed.pipeline import schedule as psched
from paddle_tpu.tuner import (Candidate, CostModel, OpCosts, Ranked,
                              TunedProfile, Workload)


def _toy_costs(key="test/toy", **times):
    """OpCosts detached from the pinned baseline file."""
    oc = OpCosts.__new__(OpCosts)
    oc.path, oc.key, oc.pinned = "<toy>", key, True
    oc.times = dict(times)
    oc.noises = {k: 0.0 for k in times}
    return oc


SERVING_TIMES = dict(
    decode_tick_stock=3e-3, decode_tick_fused=2.6e-3,
    block_mha_decode_stock=1.3e-4, block_mha_decode_pallas=6.9e-4,
    ffn_fwd_stock=6.6e-6, ffn_fwd_pallas=6.6e-6,
    dp_flat_pack_cached=1.6e-5, dp_flat_pack_bf16_cached=2.6e-5,
    dp_q8_pack_cached=7.3e-5, dp_q8_decode_cached=1.7e-5)


def _model(link=1e9):
    return CostModel(costs=_toy_costs(**SERVING_TIMES),
                     link_bytes_per_s=link)


# ---------------------------------------------------------------------------
# cost model: simulate-exact bubbles, monotonicity, term structure
# ---------------------------------------------------------------------------

class TestCostModel:
    def test_bubble_matches_simulate_exactly(self):
        """The model's bubble term IS schedule.simulate() — bit-equal,
        never a closed-form approximation."""
        m = _model()
        for sched in ("1f1b", "fthenb", "zbh1"):
            for pp, mb in [(2, 2), (4, 4), (4, 8)]:
                got = m.bubble(sched, pp, mb)
                acts = psched.build_schedule(psched.normalize(sched),
                                             pp, mb)
                sim = psched.simulate(acts, pp, groups=pp)
                assert got["bubble_fraction"] == sim["bubble_fraction"]
                assert got["makespan"] == sim["makespan"]

    def test_more_microbatches_lower_bubble(self):
        """Monotonicity: growing M at fixed pp strictly shrinks the
        predicted bubble (the reason pp_accumulate_steps is a tuning
        axis at all)."""
        m = _model()
        for sched in ("1f1b", "fthenb"):
            fracs = [m.bubble(sched, 4, mb)["bubble_fraction"]
                     for mb in (2, 4, 8, 16)]
            assert fracs == sorted(fracs, reverse=True)
            assert fracs[0] > fracs[-1]

    def test_more_microbatches_lower_train_step_per_microbatch(self):
        """Predicted step time per microbatch drops as M grows — the
        normalized form of the bubble claim, through _train_terms."""
        m = _model()
        w = Workload("t", kind="train", pp=4)
        per_mb = []
        for mb in (2, 4, 8, 16):
            r = m.predict(w, Candidate(pp_microbatches=mb))
            per_mb.append(r["cost"] / mb)
        assert per_mb == sorted(per_mb, reverse=True)

    def test_interleave_virtual_degree_prices_groups(self):
        """virtual_degree>1 routes through the grouped simulate path
        (P=pp*v stages contending for pp executors) and still beats the
        same M at v=1 on bubble fraction."""
        m = _model()
        v1 = m.bubble("interleave", 4, 8, virtual=1)
        v2 = m.bubble("interleave", 4, 8, virtual=2)
        assert v2["bubble_fraction"] < v1["bubble_fraction"]

    def test_comm_term_scales_with_wire_ratio(self):
        """bf16 grad comm halves the wire seconds; the int8 codec cuts
        them ~4x but pays the q8 pack/decode executables per bucket."""
        m = _model()
        w = Workload("t", kind="train", pp=1, dp=4,
                     grad_bytes=100 << 20, stage_phase_s=0.0)
        full = m.predict(w, Candidate())
        bf16 = m.predict(w, Candidate(dp_comm_dtype="bf16"))
        q8 = m.predict(w, Candidate(dp_comm_dtype="int8"))
        assert bf16["terms"]["comm_s"] == pytest.approx(
            0.5 * full["terms"]["comm_s"])
        assert q8["terms"]["comm_s"] < 0.3 * full["terms"]["comm_s"]
        assert q8["terms"]["pack_s"] > full["terms"]["pack_s"]

    def test_zero1_adds_gather_term(self):
        m = _model()
        w = Workload("t", kind="train", pp=1, dp=4,
                     grad_bytes=100 << 20, param_bytes=100 << 20,
                     stage_phase_s=0.0)
        plain = m.predict(w, Candidate())
        zero1 = m.predict(w, Candidate(dp_shard_update=True))
        assert plain["terms"]["gather_s"] == 0.0
        assert zero1["terms"]["gather_s"] > 0.0

    def test_serving_cost_is_seconds_per_token(self):
        """Bigger max_batch amortizes the fixed host slice of the tick:
        sec/token must fall. The attention read is no candidate's and no
        entry of the kernel's prices it: whatever the machine class, the
        anchor is the stock tick and the one kernel lever (the fused FFN)
        moves it by its per-op delta."""
        m = _model()
        w = Workload("s", kind="serving")
        small = m.predict(w, Candidate(max_batch=4))
        big = m.predict(w, Candidate(max_batch=16))
        assert big["cost"] < small["cost"]
        times = dict(SERVING_TIMES, ffn_fwd_pallas=3.3e-6,
                     block_mha_decode_pallas=1.0, decode_tick_fused=1.0)
        for key in ("test/toy", "tpu/13cpu"):
            m = CostModel(costs=_toy_costs(key=key, **times),
                          link_bytes_per_s=1e9)
            stock = m.predict(w, Candidate())
            fused = m.predict(w, Candidate(pallas_ffn=True))
            assert stock["anchor"] == fused["anchor"] == "decode_tick_stock"
            assert stock["terms"]["attn_s"] == fused["terms"]["attn_s"]
            assert fused["tick_s"] == pytest.approx(
                stock["tick_s"] - w.tick_layers * 3.3e-6)

    def test_spec_k_term_rides_acceptance_and_draft_cost(self):
        """Speculation pays k draft steps (draft_cost_ratio of a tick
        each) to emit 1+acceptance*k tokens per verify tick: a cheap,
        accurate draft makes spec_k>0 win; an expensive or wild draft
        makes it lose. Without a priced draft the term vanishes —
        spec_k is cost-neutral on a draftless workload."""
        m = _model()
        good = Workload("s", kind="serving",
                        extra={"draft_cost_ratio": 0.05,
                               "spec_acceptance": 0.8})
        bad = Workload("s", kind="serving",
                       extra={"draft_cost_ratio": 0.9,
                              "spec_acceptance": 0.05})
        off, on = Candidate(spec_k=0), Candidate(spec_k=4)
        assert m.predict(good, on)["cost"] < m.predict(good, off)["cost"]
        assert m.predict(bad, on)["cost"] > m.predict(bad, off)["cost"]
        draftless = Workload("s", kind="serving")
        assert (m.predict(draftless, on)["cost"]
                == m.predict(draftless, off)["cost"])
        assert m.predict(good, on)["terms"]["spec_s"] > 0.0
        assert m.predict(good, off)["terms"]["spec_s"] == 0.0

    def test_adapter_slots_trade_gather_compute_for_swap_misses(self):
        """The S-slot gathered einsum prices compute linearly in slots;
        the LRU miss term falls as slots approach the tenant count.
        With swaps free, fewer slots win; with swaps expensive, more
        slots win — the trade the axis exists to explore."""
        m = _model()
        cheap_swaps = Workload("s", kind="serving",
                               extra={"adapter_flop_ratio": 0.1,
                                      "adapter_tenants": 8,
                                      "adapter_swap_s": 0.0})
        dear_swaps = Workload("s", kind="serving",
                              extra={"adapter_flop_ratio": 0.1,
                                     "adapter_tenants": 8,
                                     "adapter_swap_s": 1.0})
        one, eight = Candidate(adapter_slots=1), Candidate(adapter_slots=8)
        assert (m.predict(cheap_swaps, one)["cost"]
                < m.predict(cheap_swaps, eight)["cost"])
        assert (m.predict(dear_swaps, eight)["cost"]
                < m.predict(dear_swaps, one)["cost"])
        # adapter-free workload: every slot count prices identically
        plain = Workload("s", kind="serving")
        assert (m.predict(plain, one)["cost"]
                == m.predict(plain, eight)["cost"])

    def test_spec_adapter_knobs_round_trip_flags(self):
        """spec_k/adapter_slots ride to_flags()/from_flags() like every
        other axis, under the exact FLAGS_* names the engine reads."""
        c = Candidate(spec_k=2, adapter_slots=8, max_batch=16)
        fl = c.to_flags()
        assert fl["spec_k"] == 2 and fl["adapter_slots"] == 8
        assert Candidate.from_flags(fl) == c
        assert flags.flag_value("spec_k") is not None
        assert flags.flag_value("adapter_slots") is not None

    def test_missing_tick_anchor_fails_loud(self):
        m = CostModel(costs=_toy_costs(ffn_fwd_stock=1e-6),
                      link_bytes_per_s=1e9)
        with pytest.raises(ValueError, match="decode_tick_stock"):
            m.predict(Workload("s", kind="serving"), Candidate())

    def test_baseline_entry_formats(self):
        """entry_time/entry_noise read both the legacy bare-float pin
        and the dispersion dict the noise-aware gate now writes."""
        assert tuner.entry_time(3.5e-4) == 3.5e-4
        assert tuner.entry_noise(3.5e-4) == 0.0
        assert tuner.entry_time({"t": 2e-3, "noise": 0.2}) == 2e-3
        assert tuner.entry_noise({"t": 2e-3, "noise": 0.2}) == 0.2
        assert tuner.entry_time({"error": "boom"}) is None

    def test_opcosts_reads_pinned_baseline(self):
        """The shipped cpu pin parses under the current machine key
        schema (dict entries carry dispersion)."""
        oc = OpCosts(key="cpu/1cpu")
        assert oc.time("decode_tick_stock") is not None
        assert oc.noise("decode_tick_stock") >= 0.0

    def test_opcosts_fails_on_a_machine_without_pins(self):
        """No pins for the machine class: a lookup raises instead of
        handing the model a default; a pinned machine keeps defaults for
        entries it lacks."""
        oc = OpCosts(key="tpu/13cpu")
        with pytest.raises(KeyError, match="tpu/13cpu"):
            oc.time("decode_tick_stock", 0.0)
        assert OpCosts(key="cpu/1cpu").time("no_such_op", 0.0) == 0.0


# ---------------------------------------------------------------------------
# search: enumeration, pruning guarantee on a seeded toy space
# ---------------------------------------------------------------------------

class TestSearch:
    def test_enumerate_always_includes_incumbent(self):
        cands = tuner.enumerate_space({"max_batch": [4, 16],
                                       "pallas_ffn": [True]})
        assert Candidate() in cands
        assert len(cands) == 3  # incumbent + 2x1 combos (no dup default)

    def test_candidate_flag_round_trip(self):
        c = Candidate(dp_comm_dtype="int8", pp_microbatches=8,
                      pallas_ffn=True, max_batch=16)
        assert Candidate.from_flags(c.to_flags()) == c
        assert Candidate.from_flags(Candidate().to_flags()) == Candidate()

    def test_pruning_never_discards_measured_winner(self):
        """Seeded toy space: candidate analytic costs within 1.3x of the
        incumbent survive; measurement (a perturbed version of the
        analytic cost, up to 20% off — less than the 30% margin) picks
        the true winner from the survivors. Run across seeds so this is
        a guarantee, not luck."""
        m = _model()
        w = Workload("s", kind="serving")
        axes = {"max_batch": [4, 8, 16], "token_budget": [64, 128],
                "pallas_ffn": [False, True]}
        cands = tuner.enumerate_space(axes)
        for seed in range(8):
            rs = np.random.RandomState(seed)
            noise = {c: rs.uniform(0.85, 1.15) for c in cands}
            survivors = tuner.search(m, w, cands, topk=len(cands),
                                     prune_ratio=1.3)
            # the measured winner over the FULL space, with measurement
            # = analytic x bounded perturbation
            all_ranked = tuner.search(m, w, cands, topk=len(cands),
                                      prune_ratio=1e9)
            measured = {r.candidate: r.cost * noise[r.candidate]
                        for r in all_ranked}
            winner = min(measured, key=measured.get)
            assert any(r.candidate == winner for r in survivors), (
                f"seed {seed}: pruning discarded measured winner "
                f"{winner.describe()}")

    def test_infeasible_candidates_dropped_not_fatal(self):
        m = _model()
        w = Workload("s", kind="serving")
        bad = Candidate(pp_schedule="no_such_schedule",
                        pp_microbatches=2)
        # serving path ignores pp fields, so force the train path
        wt = Workload("t", kind="train", pp=4)
        out = tuner.search(m, wt, [Candidate(pp_microbatches=4), bad],
                           topk=4, prune_ratio=1e9)
        assert len(out) == 1
        with pytest.raises(ValueError, match="no feasible"):
            tuner.search(m, wt, [bad], topk=1)
        del w

    def test_topk_orders_cheapest_first(self):
        m = _model()
        w = Workload("s", kind="serving")
        out = tuner.search(m, w, tuner.enumerate_space(
            {"max_batch": [4, 8, 16]}), topk=2, prune_ratio=1e9)
        assert len(out) == 2
        assert out[0].cost <= out[1].cost


# ---------------------------------------------------------------------------
# manifest: round-trip, CRC/version/topology fail-loud
# ---------------------------------------------------------------------------

class TestProfileManifest:
    def _prof(self):
        return TunedProfile(
            workload="w", topology=tuner.topology_signature(),
            flags=Candidate(max_batch=16).to_flags(),
            predicted_cost=1e-4, measured_s=1.1e-4,
            baseline_measured_s=2e-4, source_key="cpu/1cpu",
            candidates_considered=12)

    def test_round_trip(self, tmp_path):
        p = str(tmp_path / "prof.json")
        tuner.save_profile(self._prof(), p)
        got = tuner.load_profile(p)
        assert got.flags == self._prof().flags
        assert got.candidate() == Candidate(max_batch=16)
        assert got.measured_s == pytest.approx(1.1e-4)
        assert got.baseline_measured_s == pytest.approx(2e-4)
        got.validate_for()  # same process topology: must not raise

    def test_hand_edit_fails_crc(self, tmp_path):
        p = str(tmp_path / "prof.json")
        tuner.save_profile(self._prof(), p)
        doc = json.load(open(p))
        doc["payload"]["flags"]["serving_max_batch"] = 999
        json.dump(doc, open(p, "w"))
        with pytest.raises(ValueError, match="CRC"):
            tuner.load_profile(p)

    def test_wrong_version_fails(self, tmp_path):
        p = str(tmp_path / "prof.json")
        tuner.save_profile(self._prof(), p)
        doc = json.load(open(p))
        doc["version"] = 99
        json.dump(doc, open(p, "w"))
        with pytest.raises(ValueError, match="version"):
            tuner.load_profile(p)

    def test_wrong_format_and_garbage_fail(self, tmp_path):
        p = str(tmp_path / "notprof.json")
        json.dump({"format": "something-else"}, open(p, "w"))
        with pytest.raises(ValueError, match="not a"):
            tuner.load_profile(p)
        open(p, "w").write("{torn")
        with pytest.raises(ValueError, match="unreadable"):
            tuner.load_profile(p)
        with pytest.raises(ValueError, match="unreadable"):
            tuner.load_profile(str(tmp_path / "missing.json"))

    def test_topology_mismatch_fails_loud(self, tmp_path):
        prof = self._prof()
        prof.topology = {"platform": "tpu", "n_devices": 256,
                         "device_kind": "TPU v5e"}
        p = str(tmp_path / "prof.json")
        tuner.save_profile(prof, p)
        loaded = tuner.load_profile(p)  # load is fine...
        with pytest.raises(ValueError, match="topology"):
            loaded.validate_for()       # ...applying here is not
        with pytest.raises(ValueError, match="topology"):
            tuner.apply_profile(p)

    def test_crc_covers_canonical_payload(self, tmp_path):
        """The CRC is over sorted-keys-compact JSON, so key order in the
        file is cosmetic but value changes are not."""
        p = str(tmp_path / "prof.json")
        tuner.save_profile(self._prof(), p)
        doc = json.load(open(p))
        canon = json.dumps(doc["payload"], sort_keys=True,
                           separators=(",", ":")).encode()
        assert doc["crc32"] == zlib.crc32(canon)


# ---------------------------------------------------------------------------
# application: FLAGS_tuned_profile -> zero retrace after warmup
# ---------------------------------------------------------------------------

@pytest.fixture
def tiny_llama():
    from paddle_tpu.models import llama as L

    cfg = L.LlamaConfig(vocab_size=97, hidden_size=32,
                        intermediate_size=64, num_layers=2, num_heads=4,
                        num_kv_heads=2, max_seq_len=96, dtype=np.float32)
    return cfg, L.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture
def reset_tuner_flags():
    keep = {k: flags.flag_value(k) for k in
            ("tuned_profile", "serving_max_batch", "serving_token_budget",
             "pp_accumulate_steps", "pallas_ffn", "dp_grad_comm_dtype",
             "dp_comm_block_size", "dp_shard_update", "pp_schedule",
             "pp_virtual_degree")}
    yield
    flags.set_flags(keep)
    from paddle_tpu.tuner import profile as _p
    _p._applied.update(path=None, profile=None)


class TestProfileApplication:
    def test_apply_sets_flags_and_is_idempotent(self, tmp_path,
                                                reset_tuner_flags):
        prof = TunedProfile(
            workload="w", topology=tuner.topology_signature(),
            flags=Candidate(max_batch=16, pp_microbatches=8).to_flags())
        p = str(tmp_path / "prof.json")
        tuner.save_profile(prof, p)
        flags.set_flags({"tuned_profile": p})
        got = tuner.maybe_apply_flagged()
        assert got is not None
        assert flags.flag_value("serving_max_batch") == 16
        assert flags.flag_value("pp_accumulate_steps") == 8
        # the flag that selected the profile survives application
        assert flags.flag_value("tuned_profile") == p
        assert tuner.maybe_apply_flagged() is got  # cached, not re-read

    def test_unset_flag_is_noop(self, reset_tuner_flags):
        flags.set_flags({"tuned_profile": ""})
        assert tuner.maybe_apply_flagged() is None

    def test_engine_zero_retrace_under_profile(self, tmp_path, tiny_llama,
                                               reset_tuner_flags):
        """An engine built with geometry UNSET under FLAGS_tuned_profile
        adopts the profile's step geometry and serves a full trace with
        zero executable rebuilds after its two warmup steps — profile
        application happens before tracing, so the steady state never
        retraces."""
        from paddle_tpu.inference.serving import PagedServingEngine

        cfg, params = tiny_llama
        prof = TunedProfile(
            workload="w", topology=tuner.topology_signature(),
            flags=Candidate(max_batch=4, token_budget=32).to_flags())
        p = str(tmp_path / "prof.json")
        tuner.save_profile(prof, p)
        flags.set_flags({"tuned_profile": p})
        eng = PagedServingEngine(cfg, params, block_size=8,
                                 max_len=cfg.max_seq_len)
        assert eng.max_batch == 4 and eng.token_budget == 32
        rs = np.random.RandomState(3)
        for _ in range(4):
            eng.submit(rs.randint(1, cfg.vocab_size, 8).tolist(),
                       max_new_tokens=6)
        eng.step()   # prefill executable
        eng.step()   # decode executable
        warm = eng.stats["step_builds"]
        done = eng.run()
        assert len(done) == 4
        assert eng.stats["step_builds"] == warm

    def test_train_step_reads_accumulate_flag(self, reset_tuner_flags):
        """make_train_step(num_microbatches=None) resolves the tuned
        pp_accumulate_steps at build time."""
        from jax.sharding import Mesh

        from paddle_tpu.distributed import hybrid
        from paddle_tpu.models import llama as L

        flags.set_flags({"pp_accumulate_steps": 2})
        cfg = L.LlamaConfig(vocab_size=64, hidden_size=32,
                            intermediate_size=64, num_layers=2,
                            num_heads=4, num_kv_heads=4, max_seq_len=32)
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1),
                    ("dp", "pp", "cp", "tp"))
        step = hybrid.make_train_step(cfg, mesh)
        assert step is not None

    def test_tune_end_to_end_pins_winner(self, tmp_path):
        """tune(): analytic search + fake runner -> saved manifest whose
        winner is the measured-best candidate, with the incumbent's
        measurement recorded as baseline_measured_s."""
        m = _model()
        w = Workload("s", kind="serving")
        # fake measurement: max_batch=16 is the true winner
        truth = {4: 4.4e-4, 8: 4.0e-4, 16: 2.4e-4}

        def runner(c):
            return truth[c.max_batch]

        p = str(tmp_path / "tuned.json")
        prof = tuner.tune(m, w, {"max_batch": [4, 8, 16]}, runner,
                          topk=3, prune_ratio=2.0, steps=1, out_path=p)
        assert prof.candidate() == Candidate(max_batch=16)
        assert prof.measured_s == pytest.approx(2.4e-4)
        assert prof.baseline_measured_s == pytest.approx(4.0e-4)
        assert os.path.exists(p)
        assert tuner.load_profile(p).flags == prof.flags


# ---------------------------------------------------------------------------
# observability: tuner metrics land in the summary
# ---------------------------------------------------------------------------

class TestTunerMetrics:
    def test_summary_has_tuner_section(self):
        from paddle_tpu import observability as obs

        obs.reset()
        m = _model()
        w = Workload("s", kind="serving")
        ranked = tuner.search(m, w, tuner.enumerate_space(
            {"max_batch": [4, 16]}), topk=2, prune_ratio=1e9)
        tuner.validate_candidates(ranked, lambda c: 1e-4, steps=1)
        s = obs.summary()["tuner"]
        assert s["candidates_enumerated"] >= 3
        assert s["candidates_measured"] == 2
        assert s["measured_step_s"] == pytest.approx(1e-4)
        assert s["gap_ratio"] > 0
