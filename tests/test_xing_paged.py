"""Xing-shaped models (a residual stream of four lanes mixed by
manifold-constrained hyper-connections around latent attention, a leading
dense layer and routed experts all held) through `PagedServingEngine`,
against the plain float32 reference `benchmark/lib/reference_xing4.py`.

Float32 at a tiny size (the benchmark's fixture `tiny-xing.json`). The
engine (stock path, absorbed form; the lanes live inside a tick, the cache
holds what it held) EQUALS the reference's greedy loop through prefill in
chunks and paged decode, across a page edge and a chunk edge, with ticks
launched ahead and without; the latent launches run at 32 heads in the
Pallas interpreter; the engine counts the rows it mixed; a uniform stack
of heads' own keys takes the lanes through the same seam.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_xing4 as R
from paddle_tpu.inference.serving import PagedServingEngine
from paddle_tpu.models import llama as L
from paddle_tpu.observability import tracing
from tests.test_hyper_connections import TINY, WIDTH, make, prompt_of


def reference_tokens(params, prompt, new, file=TINY, **fault):
    with jax.default_matmul_precision("highest"):
        return R.generate(params, prompt, new, WIDTH, **R.model_kw(file),
                          **fault)[0]


def engine(cfg, params, **kw):
    e = TINY["engine"]
    kw = {**dict(num_blocks=e["num_blocks"], block_size=e["block_size"],
                 max_batch=e["max_batch"], token_budget=e["token_budget"],
                 max_len=e["max_len"], pallas=False), **kw}
    return PagedServingEngine(cfg, params, **kw)


@pytest.fixture(scope="module")
def tiny():
    return make()


@pytest.mark.parametrize("ahead", [True, False])
def test_the_engine_equals_the_reference_through_chunks_and_pages(
        tiny, ahead):
    """Prompts of 70 and 41 on a budget of 32 rows and pages of 8: the
    first is prefilled in three chunks (one beside the other's rows), both
    decode across page edges, and every token is the reference's; the
    engine's count of mixed rows is rows x 2 x layers, in its stats and on
    the `serve.tick` spans."""
    cfg, params = tiny
    tracing.reset()
    eng = engine(cfg, params)
    if not ahead:
        eng._next_is_determined = lambda cur: False
    prompts = [prompt_of(70, seed=70), prompt_of(41, seed=41)]
    rids = [eng.submit(p, max_new_tokens=14) for p in prompts]
    done = {d.rid: d.output_tokens for d in eng.run()}
    for rid, p in zip(rids, prompts):
        assert done[rid] == reference_tokens(params, p, 14)
    assert (eng.stats["ticks_ahead"] > 0) == ahead
    assert eng.stats["hyper_rows"] == (
        2 * cfg.num_layers * eng.stats["tokens_computed"]) > 0
    ticks = [s["fields"] for s in tracing.finished_spans(name="serve.tick")
             if s["trace_id"] == eng._trace_id]
    assert sum(t["hyper_rows"] for t in ticks) == eng.stats["hyper_rows"]
    assert all(t["hyper_rows"] == 6 * t["tokens"] for t in ticks)
    assert eng._value_cache is None      # the lanes add nothing to a page
    assert eng._key_cache.shape[-1] == 128


def test_a_fault_in_the_mix_moves_the_engine_off_the_reference(tiny):
    """The engine's tokens, judged as the cell's check judges them, tie
    with the sound reference everywhere and not with a reference whose mix
    lost its dynamic term."""
    from benchmark.lib import agreement
    cfg, params = tiny
    eng = engine(cfg, params)
    prompt = prompt_of(55, seed=5)
    rid = eng.submit(prompt, max_new_tokens=24)
    out = {d.rid: d.output_tokens for d in eng.run()}[rid]
    seq = prompt + out
    at = np.arange(len(prompt) - 1, len(seq) - 1)
    shares = {}
    for fault in ("", "alpha_zero"):
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(R.logits_at(
                params, jnp.asarray(seq + [0] * (WIDTH - len(seq)),
                                    jnp.int32), jnp.asarray(at),
                **R.model_kw(TINY), fault=fault))
        shares[fault] = agreement.judge(logits, out)[0]
    assert shares[""] == 1.0 and shares["alpha_zero"] < 1.0


def test_the_latent_launches_run_at_32_heads_in_the_interpreter():
    """Xing4.0's head count (a decode item is 32 rows of the MXU, not
    Kimi's 64): the page write, the mixed walk and the decode walk in the
    Pallas interpreter give the reference's tokens."""
    file = {**TINY, "num_attention_heads": 32, "num_key_value_heads": 32,
            "num_hidden_layers": 2}
    cfg, params = make(file)
    assert [s.heads for s in cfg.layer_plan] == [32, 32]
    eng = engine(cfg, params, pallas=True)
    prompt = prompt_of(37, seed=3)
    rid = eng.submit(prompt, max_new_tokens=4)
    out = {d.rid: d.output_tokens for d in eng.run()}[rid]
    assert out == reference_tokens(params, prompt, 4, file)
    assert eng.stats["pallas_steps"] == eng.stats["steps"] > 0


def test_a_uniform_stack_takes_the_lanes_through_the_same_seam():
    """Heads' own keys and values, no plan: the engine's greedy tokens are
    `llama.forward`'s, which the reference has been compared with on the
    latent plan."""
    cfg = L.LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                        num_layers=2, num_heads=4, num_kv_heads=2,
                        max_seq_len=64, hyper_lanes=4, dtype=jnp.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(2))
    params = {**params, "lm_head": params["lm_head"] * 20.0}
    eng = PagedServingEngine(cfg, params, num_blocks=16, block_size=8,
                             max_batch=2, token_budget=16, max_len=64,
                             pallas=False)
    prompt = prompt_of(21, seed=9)
    prompt = [t % 128 for t in prompt]
    rid = eng.submit(prompt, max_new_tokens=6)
    out = {d.rid: d.output_tokens for d in eng.run()}[rid]
    seq = list(prompt)
    forward = jax.jit(lambda ids: L.forward(params, ids[None], cfg)[0])
    for _ in range(6):      # one shape: a causal row reads nothing behind it
        with jax.default_matmul_precision("highest"):
            logits = forward(jnp.asarray(seq + [0] * (32 - len(seq))))
        seq.append(int(jnp.argmax(logits[len(seq) - 1])))
    assert out == seq[len(prompt):]
    assert eng.stats["hyper_rows"] == 4 * eng.stats["tokens_computed"]
