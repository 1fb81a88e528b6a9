"""The sampler a greedy tick skips (`PagedServingEngine._build_step`, scope
`sample`): the divide by the temperatures, the vocabulary sort, softmax,
cumsum, cutoff and the categorical draw sit in the sampled branch of one
`lax.cond` on "does any row of this tick sample", and a tick with no such
row computes `argmax` alone.

Contract: one executable a tick shape, whichever branch runs; an all-greedy
run returns the tokens of `argmax` and of the parent's tick; a sampled row's
stream is the parent's on the same seed, bit for bit, alone or among greedy
rows, admitted up front or mid-stream, behind a tick in flight or in the
synchronous order; `stats["ticks_sampled"]` / `["sampled_rows"]` and the
step span's `sampled_rows` count exactly the ticks and rows that sampled.

The pinned streams were taken with `_scenarios` on the parent commit
(PR 34, bceedd9), where every tick sorted.
"""
from __future__ import annotations

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.llm import LLMPredictor
from paddle_tpu.inference.serving import PagedServingEngine
from paddle_tpu.models import llama as L
from paddle_tpu.observability import tracing
from tests.test_serving_paged import _eqns, _step_args

SAMPLED = dict(temperature=0.9, top_p=0.95, seed=123)

# _scenarios(tiny, pallas) on the parent commit: the streams of the three
# requests in the order of `_prompts` (float32: the stock path and the
# kernel path in interpret mode returned the same)
PARENT = {
    "all_greedy": [[77, 78, 78, 78, 78, 78, 78, 78],
                   [93, 78, 78, 78, 78, 78, 78, 78],
                   [63, 25, 82, 77, 78, 1, 70, 41]],
    "one_sampled": [[77, 78, 78, 78, 78, 78, 78, 78],
                    [65, 5, 36, 51, 77, 86, 24, 41],
                    [63, 25, 82, 77, 78, 1, 70, 41]],
    "mid_stream": [[77, 78, 78, 78, 78, 78, 78, 78, 78, 78],
                   [65, 5, 36, 51, 77, 86],
                   [63, 25, 82, 77, 78, 1, 70, 41, 36, 43]],
    "top_k": [[77, 78, 78, 78, 78, 78, 78, 78],
              [93, 49, 9, 51, 89, 86, 94, 34],
              [63, 25, 82, 77, 78, 1, 70, 41]],
}


@pytest.fixture(scope="module")
def tiny():
    cfg = L.LlamaConfig(vocab_size=97, hidden_size=32,
                        intermediate_size=64, num_layers=2, num_heads=4,
                        num_kv_heads=2, max_seq_len=96, dtype=jnp.float32)
    return cfg, L.init_params(cfg, jax.random.PRNGKey(0))


def _prompts(cfg):
    return [np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (n,)).tolist()
        for n, seed in ((5, 11), (7, 12), (3, 13))]


def _engine(tiny, **kw):
    kw = {**dict(num_blocks=48, block_size=4, max_batch=4, token_budget=8),
          **kw}
    return PagedServingEngine(*tiny, **kw)


def _run(eng):
    return [c.output_tokens for c in sorted(eng.run(), key=lambda c: c.rid)]


def _all_greedy(eng, prompts):
    for p in prompts:
        eng.submit(p, max_new_tokens=8)
    return _run(eng)


def _one_sampled(eng, prompts):
    """The second request samples; the prompts (15 tokens on a budget of
    8) come in chunks, so ticks mix prefill and decode rows of both. The
    others say temperature 0: an engine with a top-k samples by default."""
    for i, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=8,
                   **(SAMPLED if i == 1 else dict(temperature=0.0)))
    return _run(eng)


def _sampled_mid_stream(eng, prompts):
    """Two greedy requests decode, a tick of theirs in flight, when the
    sampled request is admitted. Returns the streams in the order of
    `prompts`."""
    a = eng.submit(prompts[0], max_new_tokens=10)
    b = eng.submit(prompts[2], max_new_tokens=10)
    for _ in range(4):
        eng.step()
    s = eng.submit(prompts[1], max_new_tokens=6, **SAMPLED)
    out = {c.rid: c.output_tokens for c in eng.run()}
    return [out[a], out[s], out[b]]


def _scenarios(tiny, pallas):
    prompts = _prompts(tiny[0])
    return {
        "all_greedy": _all_greedy(_engine(tiny, pallas=pallas), prompts),
        "one_sampled": _one_sampled(_engine(tiny, pallas=pallas), prompts),
        "mid_stream": _sampled_mid_stream(_engine(tiny, pallas=pallas),
                                          prompts),
        # the static top-k mask ahead of the sort
        "top_k": _one_sampled(_engine(tiny, pallas=pallas, top_k=5),
                              prompts),
    }


# ---- the program: one sort, and it is inside the cond --------------------------

@pytest.mark.parametrize("pallas,tok_pad,decode", [
    (False, 8, False), (True, 8, False), (True, 4, True)])
def test_the_ticks_one_sort_is_in_the_sampled_branch(tiny, pallas, tok_pad,
                                                     decode):
    eng = _engine(tiny, pallas=pallas)
    fn = eng._build_step(tok_pad, eng.max_batch, decode)
    jaxpr = jax.make_jaxpr(fn)(*_step_args(eng, tok_pad)).jaxpr

    def sorts(j):
        return [e for e in _eqns(j) if e.primitive.name == "sort"]

    one, = sorts(jaxpr)
    assert one.invars[0].aval.shape == (eng.max_batch, tiny[0].vocab_size)
    # `lax.cond(pred, true_fn, false_fn)` lowers to branches (false, true)
    # (the kernels' `pl.when` are conds too, with no sort)
    by_branch = [[sorts(branch.jaxpr) for branch in e.params["branches"]]
                 for e in _eqns(jaxpr) if e.primitive.name == "cond"]
    assert [c for c in by_branch if any(c)] == [[[], [one]]]


# ---- the streams ---------------------------------------------------------------

@pytest.fixture(scope="module", params=[False, True],
                ids=["stock", "pallas"])
def streams(request, tiny):
    return _scenarios(tiny, request.param)


def test_an_all_greedy_run_returns_argmax_and_the_parents_tokens(tiny,
                                                                 streams):
    got = streams
    assert got["all_greedy"] == PARENT["all_greedy"]
    pred = LLMPredictor(*tiny, max_len=96, attn_impl="xla")
    for p, stream in zip(_prompts(tiny[0]), got["all_greedy"]):
        seq, _ = pred.generate(jnp.asarray(p, jnp.int32)[None, :],
                               max_new_tokens=8, return_scores=True)
        assert stream == [int(t) for t in np.asarray(seq)[0, len(p):]]


@pytest.mark.parametrize("scenario", ["one_sampled", "mid_stream", "top_k"])
def test_a_sampled_row_among_greedy_ones_keeps_the_parents_stream(
        streams, scenario):
    got = streams
    assert got[scenario] == PARENT[scenario]
    # the sampled request did sample, and the greedy ones beside it read
    # the all-greedy stream (mid-stream they run two tokens longer)
    greedy = got["all_greedy"]
    assert got[scenario][1] != greedy[1][:len(got[scenario][1])]
    for i in (0, 2):
        assert got[scenario][i][:8] == greedy[i]


@pytest.mark.parametrize("pallas", [False, True], ids=["stock", "pallas"])
def test_the_synchronous_order_samples_the_same_stream(tiny, pallas):
    """Launch-ahead on and off: the sampled tick behind a tick in flight
    draws what the tick planned with every id known draws."""
    eng = _engine(tiny, pallas=pallas)
    eng._next_is_determined = lambda cur: False
    assert (_sampled_mid_stream(eng, _prompts(tiny[0]))
            == PARENT["mid_stream"])
    assert eng.stats["ticks_ahead"] == 0


# ---- one executable, the branch flipped by the tick's input ---------------------

@pytest.mark.parametrize("pallas", [False, True], ids=["stock", "pallas"])
def test_a_sampled_request_mid_stream_builds_no_executable(tiny, pallas):
    eng = _engine(tiny, pallas=pallas)
    prompts = _prompts(tiny[0])
    # every shape of the run, greedy; other prompts, so that no prefix hit
    # moves the sampled prompt's chunks (a chunk splits the row's key)
    _all_greedy(eng, [p[::-1] for p in prompts])
    built = eng.stats["step_builds"], len(eng._step_fns)
    sizes = {k: fn._cache_size() for k, fn in eng._step_fns.items()}
    ahead0 = eng.stats["ticks_ahead"]
    assert eng.stats["ticks_sampled"] == 0 == eng.stats["sampled_rows"]

    got = _sampled_mid_stream(eng, prompts)
    assert got == PARENT["mid_stream"]
    assert eng.stats["ticks_sampled"] > 0
    assert eng.stats["ticks_ahead"] > ahead0     # and under launch-ahead
    assert (eng.stats["step_builds"], len(eng._step_fns)) == built
    assert {k: fn._cache_size()
            for k, fn in eng._step_fns.items()} == sizes


# ---- the counters ---------------------------------------------------------------

def _spy_on_greedy(eng):
    """Record the `greedy` argument of every tick the engine launches:
    what the device's predicate reads."""
    seen = []
    get = eng._get_step_fn

    def spying(*key):
        fn = get(*key)

        def call(*args):
            seen.append(np.array(args[13]))
            return fn(*args)
        return call

    eng._get_step_fn = spying
    return seen


def test_an_all_greedy_run_counts_no_sampled_tick(tiny):
    eng = _engine(tiny)
    seen = _spy_on_greedy(eng)
    _all_greedy(eng, _prompts(tiny[0]))
    assert seen and all(g.all() for g in seen)
    stats = eng.engine_stats
    assert stats["ticks_sampled"] == 0 == stats["sampled_rows"]


@pytest.mark.parametrize("drive,greedy_ticks", [
    (_one_sampled, 0), (_sampled_mid_stream, 5)])
def test_the_counters_count_the_ticks_and_rows_that_sampled(tiny, drive,
                                                            greedy_ticks):
    eng = _engine(tiny)
    seen = _spy_on_greedy(eng)
    drive(eng, _prompts(tiny[0]))
    rows = [int((~g).sum()) for g in seen]
    assert rows.count(0) == greedy_ticks < len(rows)
    stats = eng.engine_stats
    assert stats["steps"] == len(rows)
    assert stats["ticks_sampled"] == sum(r > 0 for r in rows)
    assert stats["sampled_rows"] == sum(rows)


def test_two_sampled_rows_a_tick_count_twice(tiny):
    eng = _engine(tiny)
    seen = _spy_on_greedy(eng)
    prompts = _prompts(tiny[0])
    for i, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=6, **(SAMPLED if i else {}))
    eng.run()
    rows = [int((~g).sum()) for g in seen]
    assert max(rows) == 2
    assert eng.stats["sampled_rows"] == sum(rows)
    assert eng.stats["ticks_sampled"] == sum(r > 0 for r in rows)


def test_the_step_span_carries_the_ticks_sampled_rows(tiny, tmp_path):
    eng = _engine(tiny)
    prompts = _prompts(tiny[0])
    _all_greedy(eng, [p[::-1] for p in prompts])     # executables built
    seen = _spy_on_greedy(eng)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _sampled_mid_stream(eng, prompts)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path) + "/**/*.xplane.pb", recursive=True)
    steps = sorted(
        (e.start_ns, dict(e.stats))
        for plane in jax.profiler.ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name == tracing.PHASE_PREFIX + "serve.step")
    fields = [f for _, f in steps if "batch" in f]   # the calls that harvest
    # the span describes the tick harvested: in the order launched
    assert ([f["sampled_rows"] for f in fields]
            == [int((~g).sum()) for g in seen])
    assert {f["sampled_rows"] for f in fields} == {0, 1}
