"""Manifold-constrained hyper-connections (`LlamaConfig.hyper_lanes`: a
residual stream of lanes mixed through `llama.residual`) in `llama.forward`,
against the plain float32 reference `benchmark/lib/reference_xing4.py`.

Everything here is float32 at a tiny size (the benchmark's fixture
`tiny-xing.json`: Kimi's tiny latent plan with 8 routed experts all held,
four lanes, 20 Sinkhorn rounds). `forward` equals the reference on logits;
the reference with its dynamic term dropped, one Sinkhorn round less, the
rounds' order swapped or bf16 coefficients is another model and `forward`
shows it; the mix is doubly stochastic and finite at the clamp; a config
without lanes traces the tick it always traced; every path that cannot
run the lanes says so by name.
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import closed_loop_serve_hyper as D
from benchmark.drivers import closed_loop_serve_latent as KD
from benchmark.lib import reference_xing4 as R
from paddle_tpu.inference.serving import PagedServingEngine
from paddle_tpu.models import llama as L
from tests.test_mellum2_train import _eqns

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "..", "benchmark", "tests", "fixtures",
                        "configs")
with open(os.path.join(FIXTURES, "tiny-xing.json")) as f:
    TINY = json.load(f)
WIDTH = 128
FAULTS = ("alpha_zero", "one_iteration_less", "rows_first",
          "bf16_coefficients")


def sharpened(params):
    """`test_kimi_paged.sharpened`'s router, head and queries, and a mix
    whose static logits are three times the seeded ones (6 on the diagonal,
    a spread of 1.5): 20 Sinkhorn rounds then do NOT reach the fixed point,
    so a round less is another model."""
    def one(b):
        b = {**b, "wqb": b["wqb"] * 30.0, "wkva": b["wkva"] * 5.0}
        if "router" in b:
            b.update(router=b["router"] * 20.0,
                     router_bias=b["router_bias"] * 10.0, w2=b["w2"] * 8.0)
        for which in ("attn", "mlp"):
            name = f"hc_{which}_b"
            b[name] = b[name].at[:, 8:].multiply(3.0)
        return b
    return {**params, "blocks": tuple(map(one, params["blocks"])),
            "lm_head": params["lm_head"] * 8.0}


def make(file=TINY, seed=0, sharp=True):
    cfg = dataclasses.replace(D.xing_config(file, jnp.float32),
                              dtype=jnp.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(seed))
    return cfg, sharpened(params) if sharp else params


@pytest.fixture(scope="module")
def tiny():
    return make()


def prompt_of(n, seed=1):
    return np.random.default_rng(seed).integers(1, 500, n).tolist()


def reference_logits(params, tokens, file=TINY, **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(R.logits_at(
            params, jnp.asarray(tokens, jnp.int32), jnp.arange(len(tokens)),
            **{**R.model_kw(file), **kw}))


def forward_logits(params, tokens, cfg):
    with jax.default_matmul_precision("highest"):
        return np.asarray(L.forward(params, jnp.asarray(tokens)[None],
                                    cfg)[0])


# ---- the model ---------------------------------------------------------------

def test_xing_config_carries_the_lanes_and_every_expert():
    cfg = D.xing_config(TINY, jnp.bfloat16)
    assert (cfg.hyper_lanes, cfg.hyper_sinkhorn_iters, cfg.hyper_eps,
            cfg.hyper_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert [(s.attn, s.ffn) for s in cfg.layer_plan] == [
        ("latent", "dense"), ("latent", "sparse"), ("latent", "sparse")]
    assert (cfg.num_experts, cfg.experts_held, cfg.top_k) == (8, (), 2)
    params = jax.eval_shape(lambda k: L.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    dense, sparse = params["blocks"]
    for stack, n in ((dense, 1), (sparse, 2)):
        for which in ("attn", "mlp"):
            assert stack[f"hc_{which}_phi"].shape == (n, 24, 4 * 64)
            assert stack[f"hc_{which}_phi"].dtype == jnp.bfloat16
            assert (stack[f"hc_{which}_b"].shape,
                    stack[f"hc_{which}_b"].dtype) == ((n, 24), jnp.float32)
            assert stack[f"hc_{which}_alpha"].shape == (n, 3)
    assert sparse["w1"].shape == (2, 8, 64, 32)


def test_counts_at_the_published_config():
    """29.5 B parameters, 3.9 B of them active a token (the embedding's
    row, a lookup, left out): the name's 29B-A4B."""
    with open(os.path.join(HERE, "..", "benchmark", "configs",
                           "xing4.0-29b-a4b-serve.json")) as f:
        file = json.load(f)
    cfg = D.xing_config({**file, **file["published"],
                         "num_nextn_predict_layers": 0}, jnp.bfloat16)
    assert (cfg.num_layers, cfg.hyper_lanes) == (40, 4)
    assert [s.ffn for s in cfg.layer_plan[:3]] == ["dense", "dense",
                                                   "sparse"]
    mixing = 40 * 2 * (4 * 3584 * 24 + 24 + 3)
    assert cfg.num_params() == 29_505_505_264
    assert cfg.num_params() - dataclasses.replace(
        cfg, hyper_lanes=0).num_params() == mixing == 27_527_280
    assert cfg.num_active_params() == 3_932_487_680
    # the chip's cut, as the configuration's file makes it
    held = jax.eval_shape(lambda k: L.init_params(
        D.xing_config(file, jnp.bfloat16), k), jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(held))
    assert round(n * 2 / 1e9, 2) == 9.59


@pytest.mark.parametrize("sharp", [False, True])
def test_forward_equals_the_reference_on_logits(sharp):
    cfg, params = make(sharp=sharp)
    tokens = prompt_of(100, seed=11)
    ref = reference_logits(params, tokens)
    got = forward_logits(params, tokens, cfg)
    assert np.abs(got - ref).max() < 2e-5 * np.abs(ref).max()


@pytest.mark.parametrize("fault", FAULTS)
def test_a_seeded_fault_moves_forward_off_the_reference(tiny, fault):
    """The negative controls, at a tolerance five times the sound
    program's: a mix without its dynamic term (`alpha` = 0), with 19
    Sinkhorn rounds, with rows before columns, or made in bfloat16 is
    another model."""
    cfg, params = tiny
    tokens = prompt_of(100, seed=11)
    bad = reference_logits(params, tokens, fault=fault)
    got = forward_logits(params, tokens, cfg)
    assert np.abs(got - bad).max() > 1e-4 * np.abs(bad).max()


def test_the_mix_is_doubly_stochastic_and_finite_at_the_clamp():
    """After 20 rounds on the seeded draw every row of a batch row's mix
    (normalised last) sums to 1 within 1e-5, and so do the columns of most
    batch rows; where a mix has an entry near 0.005 the iteration has not
    reached its fixed point in 20 rounds and a column is off by up to
    6e-4, which is the model's own (the reference stops there too). With
    every logit beyond the clamp (+-40, clipped to +-30: entries from
    e^-30 to e^30) the coefficients are finite, the rows sum to 1 and the
    input and output gates stay inside their ranges."""
    cfg, params = make(sharp=False)
    lp = {n: w[0] for n, w in params["blocks"][1].items()}
    x = L.hyper_spread(jnp.take(params["embed"],
                                jnp.asarray(prompt_of(64)), axis=0), cfg)
    x = x + 0.3 * jax.random.normal(jax.random.PRNGKey(3), x.shape)
    c = np.asarray(L.hyper_coeff(x, lp, cfg, "mlp"))
    M = c[:, 8:].reshape(-1, 4, 4)
    assert np.abs(M.sum(2) - 1).max() < 1e-5       # rows
    columns = np.abs(M.sum(1) - 1).max(axis=1)
    assert np.median(columns) < 1e-5 and columns.max() < 5e-3
    assert (c[:, :4] > 0).all() and (c[:, :4] < 1).all()
    assert (c[:, 4:8] > 0).all() and (c[:, 4:8] < 2).all()
    signs = jnp.asarray(np.where(np.arange(16) % 3 == 0, 40.0, -40.0),
                        jnp.float32)
    hard = {**lp, "hc_mlp_b": lp["hc_mlp_b"].at[8:].set(signs),
            "hc_mlp_alpha": jnp.zeros((3,))}
    c = np.asarray(L.hyper_coeff(x, hard, cfg, "mlp"))
    assert np.isfinite(c).all()
    assert np.abs(c[:, 8:].reshape(-1, 4, 4).sum(2) - 1).max() < 1e-5


def test_the_dynamic_term_is_as_large_as_the_static_one():
    """`alpha u` has a standard deviation of 0.5 to 1 on real rows: a
    program that dropped the projection would not pass for the model."""
    cfg, params = make(sharp=False)
    lp = {n: w[0] for n, w in params["blocks"][1].items()}
    x = L.hyper_spread(jnp.take(params["embed"],
                                jnp.asarray(prompt_of(200)), axis=0), cfg)
    v = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                          + cfg.rms_eps)
    u = np.asarray(v @ lp["hc_mlp_phi"].T)
    alpha = np.asarray(lp["hc_mlp_alpha"])
    for a, cols in zip(alpha, (u[:, :4], u[:, 4:8], u[:, 8:])):
        assert 0.5 < np.std(a * cols) < 1.0


def test_a_stream_of_lanes_is_flat_and_the_seam_is_the_plain_sum_without():
    cfg, params = make(sharp=False)
    plain = dataclasses.replace(cfg, hyper_lanes=0)
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 64))
    h, out = L.residual(x, {}, plain, "attn")
    assert h is x and np.array_equal(out(2 * x), 3 * x)
    assert L.hyper_spread(x, plain) is x and L.hyper_collapse(x, plain) is x
    wide = L.hyper_spread(x, cfg)
    assert wide.shape == (5, 256)
    assert np.array_equal(wide[:, 64:128], x)
    assert np.allclose(L.hyper_collapse(wide, cfg), 4 * x)
    lp = {n: w[0] for n, w in params["blocks"][0].items()}
    h, out = L.residual(wide, lp, cfg, "attn")
    assert h.shape == (5, 64) and out(x).shape == (5, 256)


# ---- a config without lanes is what it was ------------------------------------

def _tick_equations(eng, tok_pad, decode):
    B = eng.max_batch
    fn = eng._build_step(tok_pad, B, decode)
    args = (eng.params, eng._key_cache, eng._value_cache, None,
            np.zeros((tok_pad,), np.int32),
            np.zeros((B, eng.max_blocks_per_seq), np.int32),
            np.zeros((B + 1,), np.int32), np.zeros((B,), np.int32),
            np.zeros((B,), np.int32), eng._rope_emb,
            np.zeros((B,), np.float32), np.ones((B,), np.float32),
            np.zeros((B, 2), np.uint32), np.ones((B,), bool), ())
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return len(list(_eqns(jaxpr, ())))


def test_a_config_without_lanes_traces_the_tick_it_always_traced():
    """The Kimi-shaped toy tick (the fixture `tiny-kimi.json`, stock path)
    has the 841 equations it had before the seam, in both executables (its
    text was the parent's letter for letter when the seam was made: PERF.md
    section 6, PR 54); with four lanes it has more."""
    with open(os.path.join(FIXTURES, "tiny-kimi.json")) as f:
        kimi = json.load(f)
    cfg = dataclasses.replace(KD.kimi_config(kimi, jnp.float32),
                              dtype=jnp.float32)
    e = kimi["engine"]
    kw = dict(num_blocks=e["num_blocks"], block_size=e["block_size"],
              max_batch=e["max_batch"], token_budget=e["token_budget"],
              max_len=e["max_len"], pallas=False)
    eng = PagedServingEngine(
        cfg, L.init_params(cfg, jax.random.PRNGKey(0)), **kw)
    assert _tick_equations(eng, e["max_batch"], True) == 841
    assert _tick_equations(eng, e["token_budget"], False) == 841
    lanes = dataclasses.replace(cfg, hyper_lanes=4)
    eng = PagedServingEngine(
        lanes, L.init_params(lanes, jax.random.PRNGKey(0)), **kw)
    assert _tick_equations(eng, e["token_budget"], False) > 841 + 6 * 400


# ---- refusals, by name --------------------------------------------------------

def test_every_path_that_cannot_run_the_lanes_says_so_by_name():
    from paddle_tpu.distributed import hybrid as H
    from paddle_tpu.inference import llm
    from paddle_tpu.inference.quant import transform as Q
    from paddle_tpu.inference.serving import speculative as SP

    uniform = L.LlamaConfig(vocab_size=64, hidden_size=32,
                            intermediate_size=64, num_layers=2, num_heads=4,
                            num_kv_heads=2, max_seq_len=64, hyper_lanes=4,
                            dtype=jnp.float32)
    params = L.init_params(uniform, jax.random.PRNGKey(0))
    assert params["blocks"]["hc_attn_phi"].shape == (2, 24, 128)
    named = pytest.raises(NotImplementedError, match="hyper-connections")
    with named:
        L.require_uniform(uniform, "a block body")
    with named:
        llm.LLMPredictor(uniform, params)
    with named:
        SP.DraftModel(uniform, params)
    with named:
        H.require_trainable(uniform)
    with named:
        Q.quantize_llama_params(params, "w8")
    kw = dict(num_blocks=16, block_size=8, max_batch=2, token_budget=16,
              max_len=64, pallas=False)
    plain = dataclasses.replace(uniform, hyper_lanes=0)
    for asked in (dict(pallas_ffn=True), dict(quant_mode="w8"),
                  dict(adapter_slots=2), dict(quant_kv=True),
                  dict(draft=(plain, L.init_params(
                      plain, jax.random.PRNGKey(1))))):
        with named:
            PagedServingEngine(uniform, params, **kw, **asked)
    eng = PagedServingEngine(uniform, params, **kw)
    assert eng._resolve_ffn() == (False, None)
    with named:
        eng.submit([1, 2, 3], max_new_tokens=2, adapter="a")
    with named:
        eng.extract_pages([1, 2, 3])
    with named:
        eng.ingest_pages({})
    with pytest.raises(NotImplementedError, match="two lanes or more"):
        L.LlamaConfig(hyper_lanes=1)
    with pytest.raises(NotImplementedError, match="block_length"):
        L.LlamaConfig(hyper_lanes=4, block_length=4)
