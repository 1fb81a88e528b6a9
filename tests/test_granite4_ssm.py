"""State-space layers (Mamba-2) beside attention layers without a rope,
granite-4.0-h-shaped, against the plain float32 reference
`benchmark/lib/reference_granite4.py` (the recurrence token by token).

Float32 at a tiny size (the benchmark's fixture `tiny-granite.json`: one
period of ten layers, d 64, 8 heads of 16, state 16, blocks of 8). The
out-projections are scaled up so that the layers, not the tied embedding,
decide the next token (at the published widths they do by themselves).
`llama.forward` equals the reference; the three operations of
`ops/kernels/ssm.py` equal the recurrence on ragged streams; the engine
(chunked prefill, decode, launch-ahead, preemption, slots given back)
gives the reference's tokens; what must fail a wrong program does.
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import closed_loop_serve_ssm as D
from benchmark.lib import agreement, agreement_ssm, reference_granite4 as R
from paddle_tpu.inference.serving import PagedServingEngine
from paddle_tpu.inference.serving.block_manager import (BlockManager,
                                                        NoFreeBlocksError)
from paddle_tpu.models import llama as L
from paddle_tpu.observability import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "tests", "fixtures", "configs",
                       "tiny-granite.json")) as _f:
    TINY = json.load(_f)
KW = R.model_kw(TINY)
# the tick's jaxpr of two uniform configs at the parent of PR 56 (1df3aed):
# equations, inner jaxprs counted in
PARENT_EQNS = {"dense": 398, "moe": 474}


def make(file=TINY, seed=0):
    cfg = D.granite_config(file, jnp.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(seed))
    up = lambda st, names: {n: (w * 8.0 if n in names else w)
                            for n, w in st.items()}
    ssm, attn = params["blocks"]
    return cfg, {**params, "blocks": (up(ssm, ("w_out", "w2")),
                                      up(attn, ("wo", "w2")))}


def prompt_of(n, seed):
    return np.random.default_rng(seed).integers(
        1, TINY["vocab_size"], n).tolist()


def reference_tokens(params, prompt, new, **fault):
    with jax.default_matmul_precision("highest"):
        return R.generate(params, prompt, new, **KW, **fault)[0]


def engine(cfg, params, **kw):
    e = TINY["engine"]
    kw = {**dict(num_blocks=e["num_blocks"], block_size=e["block_size"],
                 max_batch=e["max_batch"], token_budget=e["token_budget"],
                 max_len=e["max_len"], pallas=False), **kw}
    return PagedServingEngine(cfg, params, **kw)


@pytest.fixture(scope="module")
def tiny():
    return make()


def test_forward_equals_the_reference_on_a_period(tiny):
    """`llama.forward` (whole sequences through the packed-stream mixer
    from a zero state, three blocks and a ragged last one) against the
    token-by-token reference, and the parameter count is the model's."""
    cfg, params = tiny
    assert [s.attn for s in cfg.layers].count("ssm") == 9
    assert cfg.num_params() == sum(x.size for x in jax.tree.leaves(params))
    toks = jnp.asarray([prompt_of(27, 1), prompt_of(27, 2)], jnp.int32)
    out = jax.jit(lambda p, t: L.forward(p, t, cfg))(params, toks)
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            ref = np.asarray(R.forward(params, toks[b], **KW))
            assert np.abs(np.asarray(out[b]) - ref).max() < 2e-5 * np.abs(
                ref).max()
    # the tokens the tests below compare are not the tied embedding's echo
    out = reference_tokens(params, prompt_of(20, 3), 8)
    assert len(set(out)) > 2


RAGGED = {
    "one_rows": [1, 1, 1, 1, 1, 1],
    "a_long_segment": [1, 37, 1, 0, 1, 1],
    "two_short_and_padding": [5, 1, 0, 6, 1, 0],
    "whole_blocks": [16, 8, 1, 1, 0, 24],
}


@pytest.mark.parametrize("name", RAGGED)
@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32_pool", "bf16_pool"])
def test_the_three_operations_equal_the_recurrence(tiny, name, pool_dtype):
    """`ssm_conv`, `ssm_step` and `ssm_scan` as `llama.ssm_recurrence`
    calls them, on a ragged stream with padding behind it, seeded non-zero
    carried states, shuffled slots, some segments over a stale slot: y, the
    new states, the new carried rows and the untouched slots against the
    recurrence. A bfloat16 pool must FAIL the state bound (and only it)."""
    cfg, params = tiny
    this = np.asarray(RAGGED[name], np.int32)
    file = {**TINY, "engine": {**TINY["engine"], "max_batch": len(this),
                               "state_slots": len(this) + 2}}
    case = D.mixer_case(file, cfg, 11, int(this.sum()) + 5, this)
    case["state"] = case["state"].astype(pool_dtype)
    lp = {n: w[0] for n, w in params["blocks"][0].items()}
    got = D.mixer_outputs(file, lp, case, one_row=False)
    assert got["conv_largest_row_error_over_tolerance"] < 0.01
    assert got["y_largest_row_error_over_tolerance"] < (
        0.01 if pool_dtype == jnp.float32 else 1.0)
    assert got["carried_rows_exact"] and got["those_untouched"]
    assert got["slots_not_in_the_tick"] >= 2
    if pool_dtype == jnp.float32:
        assert got["state_largest_error_over_tolerance"] < 0.05
    else:
        assert got["state_largest_error_over_tolerance"] > 5.0


def test_a_decode_tick_traces_no_scan(tiny):
    cfg, params = tiny
    this = np.ones((4,), np.int32)
    case = D.mixer_case(TINY, cfg, 3, 4, this)
    lp = {n: w[0] for n, w in params["blocks"][0].items()}
    sm = case["sm"]

    def run(one_row):
        return jax.make_jaxpr(lambda x, dt, s, c: L.ssm_recurrence(
            x, dt, lp, sm, s, c, jnp.int32(0),
            *(jnp.asarray(case[n]) for n in ("slots", "past", "this", "cu")),
            one_row))(case["xbc"], case["dt"], case["state"], case["conv"])

    assert "while" not in str(run(True)) and "while" in str(run(False))
    assert D.mixer_outputs(TINY, lp, case, True)[
        "state_largest_error_over_tolerance"] < 0.05


@pytest.mark.parametrize("chunk", [16, 13], ids=["divides", "does_not"])
@pytest.mark.parametrize("ahead", [True, False])
def test_the_engine_equals_the_reference_through_chunks_and_slots(
        tiny, chunk, ahead):
    """Prompts of 70 and 41 on chunks of 16 or 13 rows (the scan's block is
    8) and pages of 8: prefill in chunks beside the other's rows, decode
    across page edges, ticks launched ahead or not, and every token is the
    reference's; the counters are the work's."""
    cfg, params = tiny
    tracing.reset()
    eng = engine(cfg, params, prefill_chunk=chunk)
    if not ahead:
        eng._next_is_determined = lambda cur: False
    prompts = [prompt_of(70, seed=70), prompt_of(41, seed=41)]
    rids = [eng.submit(p, max_new_tokens=14) for p in prompts]
    done = {d.rid: d.output_tokens for d in eng.run()}
    for rid, p in zip(rids, prompts):
        assert done[rid] == reference_tokens(params, p, 14)
    assert (eng.stats["ticks_ahead"] > 0) == ahead
    assert eng.stats["ssm_step_rows"] + eng.stats["ssm_scan_rows"] == (
        9 * eng.stats["tokens_computed"])
    ticks = [s["fields"] for s in tracing.finished_spans(name="serve.tick")
             if s["trace_id"] == eng._trace_id]
    assert sum(t["ssm_scan_rows"] for t in ticks) == eng.stats[
        "ssm_scan_rows"] > 0
    assert all(t["state_slots_live"] in (1, 2) for t in ticks)
    assert eng._key_cache.shape[0] == 1          # the attention layer alone
    assert eng._state[0].shape == (9, 5, 8, 16, 16) and eng._rope_emb == ()
    stats = eng.engine_stats
    assert stats["prefix_cache"].startswith("off")
    assert (stats["state_slots"], stats["state_bytes_in_use"]) == (4, 0)
    assert stats["state_bytes_total"] == 4 * 9 * (8 * 16 * 16 * 4
                                                  + 3 * 160 * 4)
    assert stats["blocks_prefix_hit_tokens"] == stats["cow_block_copies"] == 0


def test_the_kernel_path_gives_the_same_tokens(tiny):
    """pallas=True: the page write and the BlockSpec walk in the
    interpreter at the model's own softmax scale and no rope, the decode
    executable without the scan."""
    cfg, params = tiny
    eng = engine(cfg, params, pallas=True)
    prompt = prompt_of(37, seed=3)
    rid = eng.submit(prompt, max_new_tokens=5)
    out = {d.rid: d.output_tokens for d in eng.run()}[rid]
    assert out == reference_tokens(params, prompt, 5)
    assert eng.stats["decode_fast_steps"] > 0


def test_a_preempted_sequence_resumes_to_the_same_tokens(tiny):
    """Too few pages for three sequences: one is preempted, gives its slot
    back, and is recomputed from its ids from a zero state."""
    cfg, params = tiny
    eng = engine(cfg, params, num_blocks=14)
    prompts = [prompt_of(n, seed=n) for n in (30, 33, 29)]
    rids = [eng.submit(p, max_new_tokens=16) for p in prompts]
    done = {d.rid: d.output_tokens for d in eng.run()}
    assert eng.engine_stats["preemptions"] > 0
    for rid, p in zip(rids, prompts):
        assert done[rid] == reference_tokens(params, p, 16)
    assert eng.blocks.slots_live() == 0


def test_a_freed_slots_stale_state_is_never_read(tiny):
    """A second request takes the slot the first left full of its state:
    its tokens are those of a fresh engine (`past == 0` starts from zeros,
    no clear ever runs)."""
    cfg, params = tiny
    eng = engine(cfg, params, max_batch=1)
    first, second = prompt_of(45, seed=1), prompt_of(9, seed=2)
    eng.submit(first, max_new_tokens=6)
    eng.run()
    assert float(jnp.abs(eng._state[0][:, 0]).max()) > 0    # stale, not zero
    rid = eng.submit(second, max_new_tokens=6)
    out = {d.rid: d.output_tokens for d in eng.run()}[rid]
    assert out == reference_tokens(params, second, 6)


def test_a_zeroed_carried_state_fails_parity(tiny):
    """The carried state is READ: the same continuation from a zeroed
    state leaves the reference, judged as the cell's check judges."""
    cfg, params = tiny
    prompt = prompt_of(40, seed=8)
    shares = {}
    for zeroed in (False, True):
        eng = engine(cfg, params)
        rid = eng.submit(prompt, max_new_tokens=12)
        eng._next_is_determined = lambda cur: False
        for _ in range(4):
            eng.step()
        if zeroed:
            eng._state = jax.tree.map(jnp.zeros_like, eng._state)
        out = {d.rid: d.output_tokens for d in eng.run()}[rid]
        seq = prompt + out
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(R.logits_at(
                params, jnp.asarray(seq, jnp.int32),
                jnp.arange(len(prompt) - 1, len(seq) - 1), **KW))
        shares[zeroed] = agreement.judge(logits, out)[0]
    assert shares[False] == 1.0 and shares[True] < 1.0


def test_the_cells_check_passes_here_and_fails_a_bf16_pool(tiny):
    """The benchmark's parts 2 and 3 at the fixture's shapes: sound on the
    program, and part 2 fails a state pool kept in bfloat16 by
    `STATE_TOL`."""
    cfg, params = tiny
    ok, notes = D.check_mixer(TINY, params, cfg, seed=5)
    assert ok, notes
    assert notes["ssm_idle"]["slots_not_in_the_tick"] > 0
    ok, notes = D.check_mixer(TINY, params, cfg, seed=5,
                              state_dtype=jnp.bfloat16)
    assert not ok
    assert all(n["state_largest_error_over_tolerance"] > 1.0
               and n["y_largest_row_error_over_tolerance"] <= 1.0
               for n in notes.values())
    ok, notes = D.check_carried(engine(cfg, params), TINY, params, seed=5)
    assert ok and notes["carried_state_largest_error"] < 1e-5, notes
    assert notes["carried_state_positions"] == 70 + 20 - 1
    ok, bad = D.check_carried(engine(cfg, params), TINY, params, seed=5,
                              fault="bf16_state")
    assert bad["carried_state_largest_error"] > 1e-4


def test_the_file_states_the_pool_the_engine_makes(tiny):
    """A slot a batch entry and the program's one state dtype: the engine
    takes neither as an argument, and the benchmark's driver refuses a
    configuration file that states another pool."""
    cfg, params = tiny
    eng = engine(cfg, params, max_batch=3)
    assert eng.state_slots == 3 and eng._state[0].shape[1] == 4
    assert eng._state[0].dtype == L.SSM_STATE_DTYPE == jnp.float32
    D.stated_pool(engine(cfg, params), TINY)
    for key, other in (("state_slots", 5), ("state_dtype", "bfloat16")):
        file = {**TINY, "engine": {**TINY["engine"], key: other}}
        with pytest.raises(ValueError, match="states a state pool"):
            D.stated_pool(engine(cfg, params), file)


def test_the_token_check_fills_every_slot(tiny):
    """Part 1 of the benchmark's check: the long requests, then
    `max_batch` short ones live together (every slot taken), both against
    the reference, sequences side by side."""
    cfg, params = tiny
    ok, notes = D.check_tokens(engine(cfg, params), TINY, params, seed=9)
    assert ok, notes
    assert (notes["positions_judged"], notes["batch_positions_judged"]) == (
        36, 16)
    assert notes["agreement"] == notes["batch_agreement"] == 1.0
    assert notes["batch_most_slots_live"] == TINY["engine"]["max_batch"]
    # side by side or one at a time: the reference's rows are the same
    toks = jnp.asarray([prompt_of(16, 1), prompt_of(16, 2)], jnp.int32)
    at = jnp.asarray([[3, 15], [0, 9]])
    with jax.default_matmul_precision("highest"):
        both = R.logits_at(params, toks, at, **KW)
        for b in range(2):
            alone = R.logits_at(params, toks[b], at[b], **KW)
            assert np.allclose(both[b], alone, rtol=0, atol=1e-5 * float(
                jnp.abs(alone).max()))


def test_each_refusal_names_the_layers(tiny):
    cfg, params = tiny
    for kw in (dict(draft=(cfg, params)), dict(pallas_ffn=True),
               dict(quant_mode="w8"), dict(quant_kv=True),
               dict(adapter_slots=2)):
        with pytest.raises(NotImplementedError, match="state-space layers"):
            engine(cfg, params, **kw)
    eng = engine(cfg, params)
    for call in (lambda: eng.extract_pages([1, 2, 3]),
                 lambda: eng.ingest_pages({}),
                 lambda: eng.submit([1, 2], adapter="a")):
        with pytest.raises(NotImplementedError, match="state-space layers"):
            call()
    with pytest.raises(NotImplementedError, match="state-space layers"):
        L.require_uniform(cfg, "a test")
    from paddle_tpu.distributed import hybrid
    with pytest.raises(NotImplementedError, match="state-space layers"):
        hybrid.require_trainable(cfg)
    with pytest.raises(ValueError, match="bad layer"):
        dataclasses.replace(cfg, layer_plan=tuple(
            dataclasses.replace(s, ssm=None) for s in cfg.layer_plan))


def test_block_manager_slot_accounting():
    bm = BlockManager(num_blocks=16, block_size=4, state_slots=2,
                      page_bytes=10, state_slot_bytes=100)
    assert bm.bytes_total() == 360 and bm.slots_live() == 0
    assert bm.allocate_sequence(1, list(range(9))) == 0
    assert bm.allocate_sequence(2, list(range(9))) == 0     # no prefix hit
    assert {bm.slot_of(1), bm.slot_of(2)} == {0, 1}
    assert bm.bytes_in_use() == 6 * 10 + 200
    assert not bm.can_allocate(1, n_slots=1) and bm.can_allocate(1)
    with pytest.raises(NoFreeBlocksError, match="state slot"):
        bm.allocate_sequence(3, [1, 2, 3])
    assert not bm.has_sequence(3) and bm.num_allocated() == 6
    bm.register_computed(1, list(range(9)), 8)
    assert bm.lookup_prefix(list(range(9))) == 0
    bm.free_sequence(1)
    assert bm.slots_live() == 1 and bm.allocate_sequence(3, [1, 2, 3]) == 0
    assert bm.slot_of(3) not in (bm.slot_of(2),)
    # without slots nothing differs: the prefix cache serves a second map
    plain = BlockManager(num_blocks=16, block_size=4)
    plain.allocate_sequence(1, list(range(9)))
    plain.register_computed(1, list(range(9)), 8)
    assert plain.allocate_sequence(2, list(range(9))) == 8
    assert plain.bytes_total() == 0 and plain.state_slots == 0


@pytest.mark.parametrize("name", ["dense", "moe"])
def test_a_uniform_configs_tick_traces_as_it_did(name):
    """The new fields are static defaults: the tick's jaxpr of a uniform
    config has the equations it had at the parent."""
    cfg = {"dense": L.CONFIGS["llama-test"],
           "moe": L.LlamaConfig(vocab_size=256, hidden_size=64,
                                intermediate_size=64, num_layers=2,
                                num_heads=4, num_kv_heads=2, max_seq_len=128,
                                num_experts=4, top_k=2)}[name]

    def count(jaxpr):
        n = 0
        for e in jaxpr.eqns:
            n += 1
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        n += count(inner)
        return n

    params = L.init_params(cfg, jax.random.PRNGKey(0))
    eng = PagedServingEngine(cfg, params, num_blocks=16, block_size=8,
                             max_batch=4, token_budget=32, max_len=128,
                             pallas=False)
    build, got = eng._build_step, {}

    def counted(tok_pad, B, *rest):
        fn = build(tok_pad, B, *rest)

        def tick(*a, **k):
            got[tok_pad] = count(jax.make_jaxpr(fn)(*a, **k).jaxpr)
            return fn(*a, **k)
        return tick

    eng._build_step = counted
    eng.submit([5, 6, 7, 8, 9], max_new_tokens=3)
    eng.run()
    assert got == {32: PARENT_EQNS[name]}
    assert eng._state is None and eng.state_slots == 0


@pytest.mark.parametrize("one_row", [True, False])
def test_the_step_launch_equals_the_stock_form(one_row):
    """`ops/pallas/ssm_step.py` in the interpreter, as `ssm_recurrence`
    calls it beside the kernels' read path (32 heads, state width 128, one
    group):
    the new states and the untouched slots are the stock form's bit for
    bit but for the order of two float32 sums (1e-6), y within the
    rounding of the state to bfloat16 in its read, and the cell's check
    passes on it."""
    file = {**TINY, "mamba_d_state": 128, "mamba_n_heads": 32,
            "mamba_d_head": 8, "hidden_size": 128,
            "engine": {**TINY["engine"], "pallas": True}}
    cfg, params = make(file)
    cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)   # the stream's
    this = np.asarray([1, 1, 0, 1] if one_row else [1, 9, 0, 1], np.int32)
    case = D.mixer_case(file, cfg, 21, int(this.sum()) + 3, this)
    lp = {n: w[0] for n, w in params["blocks"][0].items()}
    sm = case["sm"]

    def run(kernel):
        return jax.jit(lambda x, dt, s, c: L.ssm_recurrence(
            x, dt, lp, sm, s, c, jnp.int32(0),
            *(jnp.asarray(case[n]) for n in ("slots", "past", "this", "cu")),
            one_row, kernel))(case["xbc"], case["dt"], case["state"],
                              case["conv"])

    (y0, _, s0, c0), (y1, _, s1, c1) = run(False), run(True)
    assert "ssm_state_step" in str(jax.make_jaxpr(lambda *a: L.ssm_recurrence(
        *a, lp, sm, case["state"], case["conv"], jnp.int32(0),
        *(jnp.asarray(case[n]) for n in ("slots", "past", "this", "cu")),
        one_row, True))(case["xbc"], case["dt"]))
    live = case["slots"][this > 0]
    np.testing.assert_allclose(np.asarray(s1[0, live]),
                               np.asarray(s0[0, live]), rtol=0, atol=2e-6)
    rest = np.setdiff1d(np.arange(4), live)
    assert np.array_equal(np.asarray(s1[0, rest]), np.asarray(s0[0, rest]))
    assert np.array_equal(np.asarray(c1), np.asarray(c0))
    assert agreement_ssm.judge_rows(np.asarray(y1), np.asarray(y0))[1] < 1.0
    got = D.mixer_outputs(file, lp, case, one_row, kernel=True)
    assert got["state_largest_error_over_tolerance"] < 0.05
    assert got["y_largest_row_error_over_tolerance"] < 1.0
    assert got["those_untouched"] and got["carried_rows_exact"]
