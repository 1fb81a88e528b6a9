"""Distributed tracing + fleet metrics plane (observability/tracing.py,
observability/fleet.py).

The contracts under test, in dependency order:

- span plane basics: trace trees, the active-tree view, chrome-trace
  export, and the flag kill switch;
- phases on the profiler's clock: inside a jax.profiler session every
  tick is one ptpu.serve.step with its five phases as contiguous
  children, a step's perf_ns lays the ring's spans over the profile's
  axis, and outside a session phase() records and builds nothing;
- stable device names: the lowered serve and train steps carry every
  named scope, and every pallas_call has a name;
- serving propagation: one router submission = one trace whose child
  spans (queue.wait / prefill.chunk / decode.tick) decompose TTFT/TPOT,
  riding the request objects as plain host ints;
- failover parenting: a chaos-killed replica's replayed stream KEEPS its
  original trace_id and gains exactly one failover.replay span that
  closes on the survivor — the acceptance drill of the tracing plane;
- pipeline conformance: the runtime's measured action timeline is
  dependency-valid against the schedule it claims to have run, and the
  measured-vs-predicted bubble diff lands in summary()["pipeline"];
- fleet merge: percentiles over store-published per-rank histogram
  snapshots are bit-for-bit what a single process holding all the
  samples would compute;
- the zero-retrace pin: tracing on vs off changes no executable counts.
"""
from __future__ import annotations

import json
import socket
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.core import flags
from paddle_tpu.distributed.fault_tolerance import chaos
from paddle_tpu.distributed.store import TCPStore
from paddle_tpu.observability import fleet, tracing
from paddle_tpu.observability.metrics import Histogram, Registry


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture()
def store():
    st = TCPStore("127.0.0.1", _free_port(), is_master=True, world_size=1)
    yield st
    st.stop()


@pytest.fixture(autouse=True)
def _clean():
    obs.reset()
    yield
    obs.reset()


# ---------------------------------------------------------------------------
# Span plane basics
# ---------------------------------------------------------------------------

class TestSpanPlane:
    def test_trace_tree_and_finished_view(self):
        root = tracing.new_trace("request", rid=7)
        assert root.trace_id == root.span_id and root.parent_id == 0
        child = tracing.start_span("queue.wait", root.trace_id,
                                   root.span_id)
        tree = tracing.active_tree()
        assert tree["in_flight_spans"] == 2
        (roots,) = tree["traces"].values()
        assert roots[0]["name"] == "request"
        assert roots[0]["children"][0]["name"] == "queue.wait"
        tracing.end_span(child)
        tracing.end_span(root, reason="stop")
        done = tracing.finished_spans(trace_id=root.trace_id)
        assert [d["name"] for d in done] == ["queue.wait", "request"]
        assert all(d["dur_s"] >= 0 for d in done)
        assert tracing.active_tree()["in_flight_spans"] == 0
        # finished spans flow through the choke point into metrics
        assert obs.registry().value("paddle_trace_spans_total",
                                    {"name": "request"}) == 1

    def test_end_span_idempotent_and_none_tolerant(self):
        assert tracing.end_span(None) is None
        sp = tracing.new_trace("x")
        tracing.end_span(sp)
        end1 = sp.end_ns
        tracing.end_span(sp)
        assert sp.end_ns == end1
        assert len(tracing.finished_spans()) == 1

    def test_flag_kill_switch(self):
        flags.set_flags({"trace_spans": False})
        try:
            assert tracing.new_trace("request") is None
            assert tracing.start_span("queue.wait", 123) is None
            assert tracing.record_span("decode.tick", 123, 1, 0, 1e-3) \
                is None
        finally:
            flags.set_flags({"trace_spans": True})
        assert tracing.new_trace("request") is not None

    def test_chrome_trace_export(self):
        root = tracing.new_trace("pipeline.batch", epoch=0)
        tracing.record_span("pp.F", root.trace_id, root.span_id,
                            root.start_ns, 1e-3, stage=0)
        tracing.end_span(root)
        doc = tracing.to_chrome_trace()
        # the document must survive a JSON round trip (the file format)
        doc = json.loads(json.dumps(doc))
        assert {e["name"] for e in doc["traceEvents"]} == \
            {"pipeline.batch", "pp.F"}
        assert all(e["ph"] == "X" and e["dur"] >= 0
                   for e in doc["traceEvents"])
        # an offset (e.g. the one a step's perf_ns gives) shifts every
        # stamp onto the other axis
        base = {e["name"]: e["ts"] for e in doc["traceEvents"]}
        moved = tracing.to_chrome_trace(pid="rank1", offset_ns=int(1e9))
        assert all(e["pid"] == "rank1"
                   and abs(e["ts"] - base[e["name"]] - 1e6) < 1e-6
                   for e in moved["traceEvents"])

    def test_distress_dump_carries_active_span_tree(self, tmp_path):
        root = tracing.new_trace("request", rid=42)
        tracing.start_span("decode.tick", root.trace_id, root.span_id)
        path = obs.dump_distress("test_traces", directory=str(tmp_path))
        doc = json.loads(open(path).read())
        assert doc["traces"]["in_flight_spans"] == 2
        (spans,) = doc["traces"]["traces"].values()
        assert spans[0]["name"] == "request"
        assert spans[0]["fields"]["rid"] == 42
        assert spans[0]["children"][0]["name"] == "decode.tick"


# ---------------------------------------------------------------------------
# Serving propagation (tiny model, CPU)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    from paddle_tpu.models import llama as L

    cfg = L.LlamaConfig(vocab_size=97, hidden_size=32,
                        intermediate_size=64, num_layers=2, num_heads=4,
                        num_kv_heads=2, max_seq_len=96, dtype=jnp.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def tiny_moe():
    from paddle_tpu.models import llama as L

    cfg = L.LlamaConfig(vocab_size=97, hidden_size=32,
                        intermediate_size=16, num_layers=2, num_heads=4,
                        num_kv_heads=4, max_seq_len=96, num_experts=8,
                        top_k=2, qk_norm=True, norm_topk_prob=False,
                        dtype=jnp.float32)
    return cfg, L.init_params(cfg, jax.random.PRNGKey(0))


def _factory(tiny, **kw):
    from paddle_tpu.inference.serving import PagedServingEngine

    cfg, params = tiny
    kw.setdefault("num_blocks", 32)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_batch", 2)
    kw.setdefault("token_budget", 16)

    def build():
        return PagedServingEngine(cfg, params, **kw)

    return build


def _prompt(cfg, n, seed=3):
    return np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (n,)).tolist()


class TestServingPropagation:
    def test_request_trace_decomposes_ttft(self, tiny):
        from paddle_tpu.inference.serving import ServingRouter

        router = ServingRouter(_factory(tiny), num_replicas=1)
        rid = router.submit(_prompt(tiny[0], 6), max_new_tokens=4)
        tid = router._reqs[rid].trace_id
        assert tid > 0
        list(router.stream(rid))
        spans = tracing.finished_spans(trace_id=tid)
        by_name = {}
        for d in spans:
            by_name.setdefault(d["name"], []).append(d)
        # the TTFT decomposition: queue wait, then prefill chunks (the
        # final chunk yields the first token), then per-token decode
        assert set(by_name) >= {"request", "queue.wait", "prefill.chunk",
                                "decode.tick"}
        root = by_name["request"][0]
        assert root["span_id"] == tid and root["fields"]["rid"] == rid
        for name in ("queue.wait", "prefill.chunk", "decode.tick"):
            assert all(d["parent_id"] == tid for d in by_name[name]), name
        # 4 new tokens: the final prefill chunk produced the first, each
        # decode tick one more
        assert len(by_name["decode.tick"]) == 3
        assert by_name["decode.tick"][0]["fields"]["replica"] == 0
        assert root["fields"]["reason"] == "length"

    def test_failover_replay_keeps_trace_id(self, tiny):
        """THE acceptance drill: replica 0 chaos-killed mid-decode; the
        replayed stream keeps its original trace_id, gains exactly one
        failover.replay span parented to the request root, and that span
        closes on the survivor once the streamed prefix re-confirms."""
        from paddle_tpu.inference.serving import ServingRouter

        chaos.reconfigure("replica:kill@victim=0;call=3")
        try:
            router = ServingRouter(_factory(tiny), num_replicas=2,
                                   probation_s=60.0)
            rid = router.submit(_prompt(tiny[0], 6, seed=31),
                                max_new_tokens=12)
            tid = router._reqs[rid].trace_id
            tokens = list(router.stream(rid))
        finally:
            chaos.reconfigure("")
        assert len(tokens) == 12
        assert router._reqs[rid].failovers == 1
        assert router._reqs[rid].trace_id == tid   # identity preserved
        spans = tracing.finished_spans(trace_id=tid)
        replays = [d for d in spans if d["name"] == "failover.replay"]
        assert len(replays) == 1
        assert replays[0]["parent_id"] == tid
        assert replays[0]["fields"]["from_replica"] == 0
        assert replays[0]["fields"]["why"] == "chaos_kill"
        # the replay closed on the survivor after full re-confirmation
        assert replays[0]["fields"]["replica"] == 1
        assert replays[0]["fields"]["confirmed"] == \
            replays[0]["fields"]["replay"]
        # post-failover serving spans name the survivor
        post = [d for d in spans if d["name"] == "decode.tick"
                and d["fields"].get("replica") == 1]
        assert post, spans
        # one merged chrome trace holds the whole story
        doc = tracing.to_chrome_trace()
        names = {e["name"] for e in doc["traceEvents"]
                 if e["args"]["trace_id"] == tid}
        assert "failover.replay" in names and "request" in names

    def test_zero_retrace_pin_tracing_on_vs_off(self, tiny):
        """Trace context must never reach a jitted signature: the same
        workload compiles the same number of step executables with the
        span plane on and off."""

        def run():
            eng = _factory(tiny)()
            for i in range(3):
                root = tracing.new_trace("request", rid=i)
                eng.submit(_prompt(tiny[0], 4 + i, seed=50 + i),
                           max_new_tokens=6,
                           trace=((root.trace_id, root.span_id)
                                  if root else None))
            while eng.has_work():
                eng.step()
            return eng.stats["step_builds"]

        builds_on = run()
        assert tracing.finished_spans(name="queue.wait")  # plane was live
        obs.reset()
        flags.set_flags({"trace_spans": False})
        try:
            builds_off = run()
        finally:
            flags.set_flags({"trace_spans": True})
        assert builds_on == builds_off
        assert tracing.finished_spans() == []   # off = zero spans

    def test_zero_retrace_pin_profiler_on_vs_off(self, tiny, tmp_path):
        """phase() is metadata for the profiler, never a cache key: the
        same workload builds the same executables inside a jax.profiler
        session and outside one, and outside one it records nothing."""

        def run():
            eng = _factory(tiny)()
            for i in range(3):
                eng.submit(_prompt(tiny[0], 4 + i, seed=50 + i),
                           max_new_tokens=6)
            while eng.has_work():
                eng.step()
            return eng.stats["step_builds"]

        assert not jax.profiler.TraceAnnotation.is_enabled()
        builds_outside = run()
        outside_ns = time.perf_counter_ns()
        with tracing.phase("serve.step", tick=0, perf_ns=outside_ns) as ph:
            ph.set_metadata(batch=1)      # a no-op outside a session
        spans, builds_inside = _profiled(str(tmp_path), run)
        assert builds_inside == builds_outside
        # only what ran inside the session is in the profile
        steps = [s for s in spans if s[0] == "ptpu.serve.step"]
        assert steps and all(s[3]["tick"] >= 0 and "batch" in s[3]
                             for s in steps if "kind" in s[3])
        assert all(s[3]["perf_ns"] > outside_ns for s in steps)


# ---------------------------------------------------------------------------
# Phases on the profiler's clock
# ---------------------------------------------------------------------------

def _profiled(out_dir, fn):
    """Run `fn` inside a jax.profiler session (python tracer off); return
    the session's ptpu.* events [(name, start_ns, dur_ns, stats)], in
    order, and fn's result."""
    import glob

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=options)
    try:
        result = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(out_dir + "/**/*.xplane.pb", recursive=True))[-1]
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans += [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                      for e in line.events
                      if e.name.startswith(tracing.PHASE_PREFIX)]
    return sorted(spans, key=lambda e: (e[1], -e[2])), result


PHASES = ["ptpu.serve.schedule", "ptpu.serve.prepare",
          "ptpu.serve.dispatch", "ptpu.serve.wait", "ptpu.serve.harvest"]


@pytest.fixture(scope="module")
def profiled_engine(tiny, tmp_path_factory):
    """A tiny engine stepped inside a profiler session: two traced
    requests (a prompt of two chunks, then decode ticks). Returns the
    profile's ptpu.* events, the ring's spans and the tick count."""
    obs.reset()
    eng = _factory(tiny, prefill_chunk=8)()
    eng.submit(_prompt(tiny[0], 12, seed=9), max_new_tokens=3)
    eng.run()                              # both executables built

    def drive():
        anchor = time.perf_counter_ns()
        roots = [tracing.new_trace("request", rid=i) for i in range(2)]
        for i, root in enumerate(roots):
            eng.submit(_prompt(tiny[0], 12 + i, seed=70 + i),
                       max_new_tokens=4,
                       trace=(root.trace_id, root.span_id))
        steps0 = eng.stats["steps"]
        while eng.has_work():
            eng.step()
        return anchor, steps0, eng.stats["steps"] - steps0

    # every reading of the span clock the drive takes, as it was returned
    readings, clock = [], time.perf_counter_ns

    def reading():
        readings.append(clock())
        return readings[-1]

    out = str(tmp_path_factory.mktemp("profile"))
    time.perf_counter_ns = reading
    try:
        spans, (anchor, steps0, ticks) = _profiled(out, drive)
    finally:
        time.perf_counter_ns = clock
    ring = tracing.finished_spans()
    obs.reset()
    return {"spans": spans, "ring": ring, "anchor": anchor,
            "steps0": steps0, "ticks": ticks, "readings": readings}


def _children(spans, step):
    _, start, dur, _ = step
    # (a request's first token is a mark inside harvest, not a phase)
    return [s for s in spans
            if s[0] not in ("ptpu.serve.step", "ptpu.serve.first_token")
            and start <= s[1] and s[1] + s[2] <= start + dur]


class TestPhasesOnTheProfilersClock:
    def test_one_step_per_tick_with_five_contiguous_phases(
            self, profiled_engine):
        """A call launches the next tick (schedule, prepare, dispatch)
        and harvests the one in flight (wait, harvest); with none in
        flight it launches that one first, and where the next is not
        determined (a sequence's last token) it launches none."""
        spans = profiled_engine["spans"]
        steps = [s for s in spans if s[0] == "ptpu.serve.step"]
        assert len(steps) == profiled_engine["ticks"] > 3
        launches = []
        for step in steps:
            kids = _children(spans, step)
            names = [k[0] for k in kids]
            n = (len(names) - 2) // 3
            assert names == PHASES[:3] * n + PHASES[3:] and n in (0, 1, 2)
            launches.append(n)
            # the step's fields are those of the tick it harvested: one
            # launched by an earlier call had been launched ahead
            if n != 1:
                assert step[3]["ahead"] == (n == 0)
            # contiguous: inside the step, each phase starts where the
            # one before ended (no overlap), and nothing else of the
            # program's lies between two of them (`names` above are ALL
            # the step's children). The step's self time is what no phase
            # covers, the predicate and the annotations' own cost: a
            # share of time is the chip's to measure, not this test's
            assert step[1] <= kids[0][1]
            assert kids[-1][1] + kids[-1][2] <= step[1] + step[2]
            for a, b in zip(kids, kids[1:]):
                assert a[1] + a[2] <= b[1]
        # steps do not overlap each other either
        for a, b in zip(steps, steps[1:]):
            assert a[1] + a[2] <= b[1]
        # as many launches as ticks, the first call's two among them
        assert launches[0] == 2 and launches[-1] == 0
        assert sum(launches) == len(steps)

    def test_step_fields_name_the_tick(self, profiled_engine):
        steps = [s[3] for s in profiled_engine["spans"]
                 if s[0] == "ptpu.serve.step"]
        first = profiled_engine["steps0"]
        assert [f["tick"] for f in steps] == list(
            range(first, first + len(steps)))
        for f in steps:
            assert set(f) == {"tick", "perf_ns", "batch", "tokens",
                              "prefill_tokens", "kind", "ahead", "void_rows",
                              "sampled_rows"}
            assert f["ahead"] in (0, 1) and f["void_rows"] == 0
            assert f["sampled_rows"] == 0           # greedy requests
            assert 1 <= f["batch"] <= 2 and f["tokens"] >= f["batch"]
            assert f["kind"] in ("decode", "mixed")
        # two prompts of 12 and 13 tokens in chunks of 8: the first
        # chunks are prefill tokens, the last decode ticks carry none
        assert steps[0]["prefill_tokens"] > 0
        assert steps[-1]["prefill_tokens"] == 0
        assert steps[-1]["tokens"] == steps[-1]["batch"]

    def test_moe_tick_adds_its_three_counters_to_the_step(
            self, tiny_moe, tmp_path):
        """A routed-expert tick's step span carries `moe_pairs`,
        `moe_experts_hit` and `moe_max_load` beside the fields of a
        dense tick (which the test above pins)."""
        eng = _factory(tiny_moe)()
        eng.submit(_prompt(tiny_moe[0], 6), max_new_tokens=2)
        eng.run()                          # both executables built

        def drive():
            eng.submit(_prompt(tiny_moe[0], 9, seed=5), max_new_tokens=3)
            steps0 = eng.stats["steps"]
            while eng.has_work():
                eng.step()
            return eng.stats["steps"] - steps0

        stats0 = dict(eng.stats)
        spans, ticks = _profiled(str(tmp_path), drive)
        steps = [s[3] for s in spans if s[0] == "ptpu.serve.step"]
        assert len(steps) == ticks == 3
        cfg = tiny_moe[0]
        for f in steps:
            assert set(f) == {"tick", "perf_ns", "batch", "tokens",
                              "prefill_tokens", "kind", "ahead", "void_rows",
                              "sampled_rows", "moe_pairs", "moe_experts_hit",
                              "moe_max_load"}
            assert f["moe_pairs"] == f["tokens"] * cfg.top_k
            assert 1 <= f["moe_max_load"] <= f["tokens"] * cfg.num_layers
            assert (cfg.top_k * cfg.num_layers <= f["moe_experts_hit"]
                    <= min(f["moe_pairs"], cfg.num_experts)
                    * cfg.num_layers)
        # and the engine's stats are the sums (max_load: the maximum)
        assert (eng.stats["moe_pairs"] - stats0["moe_pairs"]
                == sum(f["moe_pairs"] for f in steps))
        assert (eng.stats["moe_experts_hit"] - stats0["moe_experts_hit"]
                == sum(f["moe_experts_hit"] for f in steps))
        assert eng.stats["moe_max_load"] >= max(
            f["moe_max_load"] for f in steps)

    def test_attention_walk_counts_ride_on_the_step(self, tiny, tmp_path):
        """A tick through the decode launch of paged attention carries
        `attn_pages_live` and `attn_pages_fetched`; a tick through the
        mixed launch those and its work items' live and packed rows. All
        from the host's lengths, summed into `engine.stats`."""
        from paddle_tpu.ops.pallas import paged_attention as PA
        eng = _factory(tiny, pallas=True)()
        eng.submit(_prompt(tiny[0], 6), max_new_tokens=2)
        eng.run()                          # both executables built

        def drive():
            eng.submit(_prompt(tiny[0], 9, seed=5), max_new_tokens=3)
            while eng.has_work():
                eng.step()

        stats0 = dict(eng.stats)
        spans, _ = _profiled(str(tmp_path), drive)
        steps = [s[3] for s in spans if s[0] == "ptpu.serve.step"]
        assert [f["kind"] for f in steps] == ["mixed", "decode", "decode"]
        base = {"tick", "perf_ns", "batch", "tokens", "prefill_tokens",
                "kind", "ahead", "void_rows", "sampled_rows"}
        pair = {"attn_pages_live", "attn_pages_fetched"}
        rows = {"attn_rows_live", "attn_rows_packed"}
        geometry = (4, tiny[0].num_kv_heads, tiny[0].head_dim, 4,
                    eng.max_blocks_per_seq)
        # the mixed tick: 9 tokens at past 0 are one work item on the
        # whole tile of 16 tokens (the budget; the small one holds 8),
        # 3 live pages, one key block fetched
        assert PA.mixed_tiles(16, 2, *geometry[1:3]) == (16, 8)
        assert set(steps[0]) == base | pair | rows
        assert {k: steps[0][k] for k in pair | rows} == {
            "attn_rows_live": 9, "attn_rows_packed": 16,
            "attn_pages_live": 3,
            "attn_pages_fetched": PA.mixed_pages_per_block(*geometry)}
        P = PA.decode_pages_per_block(*geometry)
        for k, f in enumerate(steps[1:], start=1):
            assert set(f) == base | pair
            assert f["attn_pages_live"] == -(-(9 + k) // 4)
            assert f["attn_pages_fetched"] == -(-(9 + k) // (4 * P)) * P
        for name in pair | rows:
            assert (eng.stats[name] - stats0[name]
                    == sum(f.get(name, 0) for f in steps))

    def test_submit_is_a_span_outside_every_step(self, profiled_engine):
        spans = profiled_engine["spans"]
        submits = [s for s in spans if s[0] == "ptpu.serve.submit"]
        steps = [s for s in spans if s[0] == "ptpu.serve.step"]
        assert len(submits) == 2
        for sub in submits:
            assert not any(st[1] <= sub[1] < st[1] + st[2] for st in steps)

    def test_step_perf_ns_lays_ring_spans_over_the_profile(
            self, profiled_engine):
        spans, ring = profiled_engine["spans"], profiled_engine["ring"]
        steps = [s for s in spans if s[0] == "ptpu.serve.step"]
        # a step's perf_ns is a reading of the ring's clock as it was
        # returned, one a step, in the steps' order
        stamps = [s[3]["perf_ns"] for s in steps]
        assert stamps == sorted(set(stamps)) and stamps[0] > \
            profiled_engine["anchor"]
        assert set(stamps) <= set(profiled_engine["readings"])
        # so any step gives the offset from that clock (perf_counter_ns)
        # to the profile's axis, and all give the same one
        offset = steps[0][1] - stamps[0]
        assert all(abs(s[1] - s[3]["perf_ns"] - offset) < 2_000_000
                   for s in steps)
        ticks = [d for d in ring
                 if d["name"] in ("decode.tick", "prefill.chunk")]
        assert any(d["name"] == "decode.tick" for d in ticks)
        slack = 2_000_000    # ns between a clock read and the annotation

        def phase_of(step, name):
            return [k for k in _children(spans, step) if k[0] == name]

        def wait_end(step):
            (wait,) = phase_of(step, "ptpu.serve.wait")
            return wait[1] + wait[2]

        for d in ticks:
            lo, hi = d["start_ns"] + offset, d["end_ns"] + offset
            # the span lies over the tick's device interval. It ends with
            # the wait of the step that harvested the tick; it starts with
            # that tick's dispatch or, for a tick launched ahead, where
            # the tick before it ended (the same clock readings bound both)
            i = min(range(len(steps)),
                    key=lambda j: abs(wait_end(steps[j]) - hi))
            assert abs(wait_end(steps[i]) - hi) < slack, d
            if steps[i][3]["ahead"]:
                assert abs(lo - wait_end(steps[i - 1])) < slack
            else:
                disp = phase_of(steps[i], "ptpu.serve.dispatch")[0]
                assert abs(lo - disp[1]) < slack

    def test_outside_a_session_nothing_is_recorded(self, tmp_path):
        with tracing.phase("serve.step", tick=1):
            with tracing.phase("serve.wait"):
                pass
        with tracing.phase("serve.first_token", rid=1, submit_ns=1):
            pass
        spans, _ = _profiled(str(tmp_path), lambda: None)
        assert spans == []


# ---------------------------------------------------------------------------
# Stable names on the device side
# ---------------------------------------------------------------------------

SERVE_SCOPES = ["embed", "layers", "qkv", "cache_write", "paged_attention",
                "attn_out", "ffn", "head", "sample"]
TRAIN_SCOPES = ["embed", "attention", "ffn", "head_loss", "pp_send",
                "grad_sync", "grad_norm", "adamw", "layers", "pipeline"]


def _scopes_in(text):
    """Scope words found in the name stacks of a lowered module's
    locations (`loc("jit(f)/layers/while/body/ffn/dot_general")`)."""
    import re

    words = set()
    for loc in re.findall(r'loc\("([^"]+)"', text):
        for comp in loc.split("/"):
            words.update(re.findall(r"[A-Za-z_][A-Za-z0-9_]*", comp))
    return words


class TestStableDeviceNames:
    @pytest.mark.parametrize("pallas", [False, True],
                             ids=["stock", "pallas"])
    def test_serve_step_carries_every_scope(self, tiny, pallas):
        eng = _factory(tiny, pallas=pallas)()
        lowered = []
        build = eng._build_step

        def spy(*a, **k):
            fn = build(*a, **k)

            def call(*args):
                shapes = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
                lowered.append(fn.lower(*shapes).as_text(debug_info=True))
                return fn(*args)
            return call

        eng._build_step = spy
        eng.submit(_prompt(tiny[0], 6), max_new_tokens=2)
        eng.run()
        assert lowered
        for text in lowered:
            assert set(SERVE_SCOPES) <= _scopes_in(text)

    @pytest.mark.parametrize("form", ["dense_einsum", "sorted_gmm"])
    def test_moe_serve_step_carries_the_moe_scopes(self, tiny_moe, form,
                                                   monkeypatch):
        """A routed-expert tick names `moe` and inside it `router`,
        `dispatch`, `experts`, `combine` in place of `ffn`, in either
        expert form."""
        from paddle_tpu.models import llama as L

        monkeypatch.setattr(L, "expert_form", lambda cfg: form)
        eng = _factory(tiny_moe, pallas=True)()
        lowered = []
        build = eng._build_step

        def spy(*a, **k):
            fn = build(*a, **k)

            def call(*args):
                shapes = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
                lowered.append(fn.lower(*shapes).as_text(debug_info=True))
                return fn(*args)
            return call

        eng._build_step = spy
        eng.submit(_prompt(tiny_moe[0], 6), max_new_tokens=2)
        eng.run()
        assert len(lowered) == 2               # mixed, then decode
        want = (set(SERVE_SCOPES) - {"ffn"}) | {
            "moe", "router", "dispatch", "experts", "combine"}
        for text in lowered:
            words = _scopes_in(text)
            assert want <= words and "ffn" not in words

    def test_cow_copy_carries_its_scope(self, tiny):
        eng = _factory(tiny)()
        eng._copy_blocks([(0, 1)])
        kc = jax.ShapeDtypeStruct(eng._key_cache.shape,
                                  eng._key_cache.dtype)
        idx = jax.ShapeDtypeStruct((8,), jnp.int32)
        text = eng._copy_fn.lower(kc, kc, None, None, idx, idx).as_text(
            debug_info=True)
        assert "cow_copy" in _scopes_in(text)

    def test_train_step_carries_every_scope(self):
        from paddle_tpu.distributed import hybrid as H
        from paddle_tpu.models import llama as L

        cfg = L.LlamaConfig(vocab_size=64, hidden_size=32,
                            intermediate_size=64, num_layers=2, num_heads=4,
                            num_kv_heads=2, max_seq_len=16,
                            dtype=jnp.float32)
        mesh = H.build_mesh(1, 1, 1)
        params = H.shard_params(L.init_params(cfg, jax.random.PRNGKey(0)),
                                mesh, cfg)
        opt = H.init_opt_state(params)
        step = H.make_train_step(cfg, mesh, num_microbatches=2)
        tokens = jnp.zeros((2, 16), jnp.int32)
        lowered = step.lower(params, opt, tokens, tokens)
        found = _scopes_in(lowered.as_text(debug_info=True))
        assert set(TRAIN_SCOPES) <= found, set(TRAIN_SCOPES) - found
        # in the executable, forward, jvp and transpose operations carry
        # the scope's name, the outermost inside JAX's wrappers
        import re

        names = set(re.findall(r'op_name="([^"]+)"',
                               lowered.compile().as_text()))
        # the head and the loss stand after the schedule, not in its slots
        assert any(re.search(r"/jvp\(pipeline\)/head_loss/", n)
                   for n in names)
        assert not any(re.search(r"/while/.*head_loss", n) for n in names)
        assert any(re.search(r"/transpose\(jvp\(pipeline\)\)/.*/ffn/", n)
                   for n in names)
        assert any(n.endswith(("/adamw/sqrt", "/adamw/sqrt:"))
                   for n in names)

    @pytest.mark.parametrize("module, count", [
        ("flash_attention", 3), ("fused_ffn", 6), ("fused_sample", 1),
        ("paged_attention", 6), ("paged_attention_latent", 6),
        ("index_select", 1), ("ssm_step", 1)])
    def test_every_pallas_call_has_a_name(self, module, count):
        import ast
        import os

        import paddle_tpu.ops.pallas as pkg

        path = os.path.join(os.path.dirname(pkg.__file__), module + ".py")
        with open(path) as f:
            tree = ast.parse(f.read())
        calls = [n for n in ast.walk(tree) if isinstance(n, ast.Call)
                 and isinstance(n.func, ast.Attribute)
                 and n.func.attr == "pallas_call"]
        assert len(calls) == count
        names = []
        for call in calls:
            kw = {k.arg: k.value for k in call.keywords}
            assert isinstance(kw.get("name"), ast.Constant), (
                f"{module}.py:{call.lineno}: pallas_call without name=")
            names.append(kw["name"].value)
        assert len(set(names)) == count
        assert all(n.startswith(module.replace("fused_sample",
                                               "fused_sample_prep")[:5])
                   for n in names)

    def test_pallas_call_sites_are_all_in_ops_pallas(self):
        """The 24 named sites above are all there are in the package."""
        import os
        import re

        import paddle_tpu

        root = os.path.dirname(paddle_tpu.__file__)
        sites = {}
        for base, _, files in os.walk(root):
            for f in files:
                if f.endswith(".py"):
                    with open(os.path.join(base, f)) as fh:
                        n = len(re.findall(r"\bpl\.pallas_call\(", fh.read()))
                    if n:
                        sites[os.path.relpath(os.path.join(base, f),
                                              root)] = n
        assert sites == {"ops/pallas/flash_attention.py": 3,
                         "ops/pallas/fused_ffn.py": 6,
                         "ops/pallas/index_select.py": 1,
                         "ops/pallas/fused_sample.py": 1,
                         "ops/pallas/paged_attention.py": 6,
                         "ops/pallas/paged_attention_latent.py": 6,
                         "ops/pallas/ssm_step.py": 1}


# ---------------------------------------------------------------------------
# Pipeline conformance
# ---------------------------------------------------------------------------

class TestPipelineConformance:
    def test_measured_timeline_matches_schedule(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        from paddle_tpu.distributed.fleet.meta_parallel.parallel_layers \
            .pp_layers import LayerDesc, PipelineLayer
        from paddle_tpu.distributed.pipeline import (PipelineEngine,
                                                     build_schedule)

        model = PipelineLayer(
            layers=[LayerDesc(nn.Linear, 16, 32), LayerDesc(nn.ReLU),
                    LayerDesc(nn.Linear, 32, 4)],
            loss_fn=lambda o, y: ((o - y) ** 2).mean(), num_stages=2)
        engine = PipelineEngine(model, accumulate_steps=4, schedule="1F1B")
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.normal(size=(8, 16)).astype(np.float32))
        y = paddle.to_tensor(rs.normal(size=(8, 4)).astype(np.float32))
        engine.run(x, y, train=True)

        conf = engine.last_conformance
        assert conf["schedule"] == "1f1b"
        # the dispatcher executed exactly the actions the schedule holds,
        # in an order that respects every dependency edge
        acts = build_schedule("1F1B", 2, 4)
        assert conf["actions"] == sum(len(v) for v in acts.values())
        assert conf["actions"] == len(engine.last_timeline)
        assert conf["order_dependency_valid"] is True
        assert 0.0 <= conf["measured_bubble_fraction"] <= 1.0
        assert conf["bubble_gap"] == pytest.approx(
            conf["measured_bubble_fraction"]
            - conf["predicted_bubble_fraction"], abs=1e-6)
        assert len(conf["per_group_busy_s"]) == 2
        assert conf["straggler_group"] in (0, 1)
        # the batch trace: one pipeline.batch root + a span per action
        batch = tracing.finished_spans(name="pipeline.batch")
        assert len(batch) == 1 and batch[0]["fields"]["epoch"] == 0
        tid = batch[0]["trace_id"]
        stage_spans = [d for d in tracing.finished_spans(trace_id=tid)
                       if d["name"].startswith("pp.")
                       and d["name"] != "pp.p2p"]
        assert len(stage_spans) == conf["actions"]
        # measured-vs-predicted lands in the summary gauges
        pipe = obs.summary()["pipeline"]
        assert pipe["measured_bubble_fraction"] == \
            conf["measured_bubble_fraction"]
        assert pipe["bubble_gap"] == conf["bubble_gap"]
        assert pipe["straggler_group"] == conf["straggler_group"]

    def test_measured_schedule_stats_on_known_timeline(self):
        # two stages, perfectly packed: zero bubble, no straggler excess
        tl = [(0, "F", 0, 0.0, 1.0), (1, "F", 0, 1.0, 1.0),
              (0, "B", 0, 1.0, 1.0), (1, "B", 0, 2.0, 1.0)]
        st = tracing.measured_schedule_stats(tl, 2)
        assert st["makespan_s"] == 3.0
        assert st["busy_s"] == [2.0, 2.0]
        assert st["bubble_fraction"] == pytest.approx(1 - 4.0 / 6.0,
                                                      abs=1e-6)
        assert st["straggler_excess"] == 0.0
        # a slow stage 1 shows up as the straggler
        tl[1] = (1, "F", 0, 1.0, 2.0)
        st = tracing.measured_schedule_stats(tl, 2)
        assert st["straggler_group"] == 1
        assert st["straggler_excess"] > 0


# ---------------------------------------------------------------------------
# Fleet merge
# ---------------------------------------------------------------------------

def _rank_registry(values, extra=()):
    reg = Registry()
    h = reg.histogram("paddle_serving_ttft_seconds")
    for v in values:
        h.observe(v)
    c = reg.counter("paddle_serving_requests_total")
    c.inc(len(values), {"event": "admitted"})
    for name, labels, v in extra:
        reg.counter(name).inc(v, labels)
    return reg


class TestFleetMerge:
    def test_histogram_merge_bitexact_vs_single_process(self, store):
        rs = np.random.RandomState(7)
        vals0 = rs.exponential(0.05, 300).tolist()
        vals1 = rs.exponential(0.08, 200).tolist()
        fleet.publish(store, 0, reg=_rank_registry(vals0))
        fleet.publish(store, 1, reg=_rank_registry(vals1))
        payloads = fleet.collect(store, range(4))   # absent ranks skipped
        assert [p["rank"] for p in payloads] == [0, 1]
        out = fleet.fleet_summary(
            states=[(p["rank"], p["state"]) for p in payloads])
        # reference: ONE process observed every sample in rank order
        ref = Histogram("ref")
        for v in vals0 + vals1:
            ref.observe(v)
        assert out["ttft_p50_s"] == round(ref.percentile(50), 9)
        assert out["ttft_p99_s"] == round(ref.percentile(99), 9)
        assert out["ttft_count"] == 500
        assert out["admitted"] == 500
        assert out["world"] == 2 and out["ranks"] == ["0", "1"]
        # bucket counts merged element-wise, not re-binned
        merged = fleet.merged_histogram(
            [p["state"]["histograms"]["paddle_serving_ttft_seconds"]
             for p in payloads])
        assert merged._counts == [a + b for a, b in zip(
            _rank_registry(vals0).get(
                "paddle_serving_ttft_seconds")._counts,
            _rank_registry(vals1).get(
                "paddle_serving_ttft_seconds")._counts)]
        # the digest republishes as paddle_fleet_* gauges
        reg = obs.registry()
        assert reg.value("paddle_fleet_ttft_p50_seconds") == \
            out["ttft_p50_s"]
        assert reg.value("paddle_fleet_merges_total") == 1

    def test_counters_sum_and_gauges_keep_rank_labels(self):
        st0 = fleet.export_state(_rank_registry(
            [0.1], extra=[("paddle_router_shed_total", None, 3)]))
        st1 = fleet.export_state(_rank_registry(
            [0.2], extra=[("paddle_router_shed_total", None, 2)]))
        merged = fleet.merge_states([(0, st0), (1, st1)])
        assert merged["counters"]["paddle_router_shed_total"].value() == 5
        out = fleet.fleet_summary(states=[(0, st0), (1, st1)])
        assert out["shed"] == 5
        assert out["shed_rate"] == pytest.approx(5 / 7)

    def test_local_fallback_is_a_fleet_of_one(self):
        out = fleet.fleet_summary()
        assert out["world"] == 1 and out["ranks"] == ["local"]

    def test_publisher_cadence(self, store):
        pub = fleet.FleetPublisher(store, 3, interval_s=100.0)
        assert pub.maybe_publish(now=1000.0)
        assert not pub.maybe_publish(now=1050.0)   # inside the interval
        assert pub.maybe_publish(now=1100.0)
        assert pub.publishes == 2
        assert store.check("paddle_fleet/snap/3")
