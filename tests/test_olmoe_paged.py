"""OLMoE-shaped models (routed experts, QK-norm, un-renormalised top-k
weights) through `llama.forward` and through `PagedServingEngine`, against
the plain float32 reference `benchmark/lib/reference_olmoe.py`.

Everything here is float32, parameters and activations, at a tiny size
(2 layers, d 64, 4/4 heads of 16, 8 experts of width 32), so that no
expert flips between program and reference but on an exact tie. The
tolerance is 1e-4 of the row's largest logit: float32 sums in another
order differ by a few 1e-6 of it (measured here: under 3e-6), while any
fault of the mathematics (a dropped expert, renormalised weights, a
missing QK-norm, a routed padding row) moves logits by 1e-2 and more,
which the tests below show by making each fault on purpose.

The engine returns tokens, not logits, so it is judged teacher-forced as
`benchmark/lib/agreement.judge` does it, with a tie tolerance of 1e-4
(not bf16's four ulps), at every position, and its tokens must equal
greedy decoding through `llama.forward`.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_olmoe
from paddle_tpu.inference.serving import PagedServingEngine
from paddle_tpu.models import llama as L

TOL = 1e-4
FORMS = ["dense_einsum", "sorted_gmm"]


def make(top_k=2, qk_norm=True, norm_topk_prob=False, seed=0, **kw):
    cfg = L.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=32, num_layers=2,
        num_heads=4, num_kv_heads=4, max_seq_len=128, num_experts=8,
        top_k=top_k, qk_norm=qk_norm, norm_topk_prob=norm_topk_prob,
        dtype=jnp.float32, param_dtype=jnp.float32, **kw)
    params = L.init_params(cfg, jax.random.PRNGKey(seed))
    # gains that are not all one, so that a missing norm shows; a router
    # sharp enough that the top-k weights differ
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), 3)
    blocks = dict(params["blocks"])
    for name, key in zip(("q_norm", "k_norm"), keys):
        if name in blocks:
            blocks[name] = 1.0 + 0.3 * jax.random.normal(
                key, blocks[name].shape)
    blocks["router"] = blocks["router"] * 20.0
    return cfg, {**params, "blocks": blocks}


def ref_logits(cfg, params, tokens, at=None, **over):
    kw = dict(heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
              theta=cfg.rope_theta, eps=cfg.rms_eps, top_k=cfg.top_k,
              norm_topk_prob=cfg.norm_topk_prob, qk_norm=cfg.qk_norm)
    kw.update(over)
    tokens = jnp.asarray(tokens, jnp.int32)
    at = jnp.arange(len(tokens)) if at is None else jnp.asarray(at)
    return np.asarray(reference_olmoe.logits_at(params, tokens, at, **kw))


def rel_err(a, b):
    """Largest difference of two [n, V] logit arrays, per row, as a share
    of the row's largest |reference logit|."""
    return float((np.abs(a - b).max(-1) / np.abs(b).max(-1)).max())


def tokens_of(n, seed=1):
    return np.random.default_rng(seed).integers(1, 128, n).astype(np.int32)


@pytest.fixture(params=FORMS)
def form(request, monkeypatch):
    """Both expert forms on the CPU: the dense einsum is what the CPU
    takes; the sorted form (the Pallas grouped matmul the TPU takes) runs
    in interpret mode when steered."""
    monkeypatch.setattr(L, "expert_form", lambda cfg: request.param)
    return request.param


# --------------------------------------------------------------------------
# llama.forward (the same route, routed_ffn, qk_normed) against the reference
# --------------------------------------------------------------------------

@pytest.mark.parametrize("top_k", [2, 8], ids=["top2", "top8of8"])
@pytest.mark.parametrize("qk_norm", [True, False], ids=["qknorm", "noqknorm"])
@pytest.mark.parametrize("norm_topk_prob", [False, True],
                         ids=["raw", "renorm"])
def test_forward_agrees_with_the_reference(form, top_k, qk_norm,
                                           norm_topk_prob):
    cfg, params = make(top_k=top_k, qk_norm=qk_norm,
                       norm_topk_prob=norm_topk_prob)
    toks = tokens_of(40)
    got = np.asarray(L.forward(params, jnp.asarray(toks)[None], cfg,
                               attn_impl="xla"))[0]
    assert rel_err(got, ref_logits(cfg, params, toks)) < TOL


@pytest.mark.parametrize("fault", ["renormalised", "raw_weights",
                                   "no_qk_norm", "one_expert_fewer"])
def test_the_tolerance_sees_each_part_of_the_mathematics(fault):
    """The reference computed with one part of OLMoE's equations changed
    differs from the program by far more than TOL: the comparison above
    would fail if the program made that change."""
    cfg, params = make()
    over = {"renormalised": dict(norm_topk_prob=True),
            "no_qk_norm": dict(qk_norm=False),
            "one_expert_fewer": dict(top_k=1)}.get(fault, {})
    if fault == "raw_weights":
        cfg, params = make(norm_topk_prob=True)
        over = dict(norm_topk_prob=False)
    toks = tokens_of(40)
    got = np.asarray(L.forward(params, jnp.asarray(toks)[None], cfg,
                               attn_impl="xla"))[0]
    assert rel_err(got, ref_logits(cfg, params, toks, **over)) > 100 * TOL


def test_mistral_shaped_config_is_untouched_by_the_new_keys():
    """The defaults are today's behaviour: no QK-norm gains in the tree,
    and `block` takes the dense FFN."""
    cfg = L.LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                        num_layers=2, num_heads=4, num_kv_heads=2)
    assert (cfg.qk_norm, cfg.norm_topk_prob) == (False, True)
    blocks = L.init_params(cfg, jax.random.PRNGKey(0))["blocks"]
    assert "q_norm" not in blocks and "router" not in blocks
    assert L.expert_form(cfg) is None


def test_num_params_and_flops_count_qk_norm_and_active_experts():
    cfg, params = make(top_k=2)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert n == cfg.num_params()
    plain = dataclasses.replace(cfg, qk_norm=False)
    assert cfg.num_params() - plain.num_params() == 2 * 2 * 64
    d, f = cfg.hidden_size, cfg.intermediate_size
    active = 4 * d * d + 2 * 3 * d * f + d * 8     # attention, 2 experts, router
    assert cfg.flops_per_token() == 6 * (2 * active + 2 * d * 128)


# --------------------------------------------------------------------------
# the expert forms against each other, padding rows, the load counter
# --------------------------------------------------------------------------

def layer0(params):
    return jax.tree.map(lambda a: a[0], params["blocks"])


@pytest.mark.parametrize("rows, n_valid, top_k", [
    (16, 16, 8), (32, 19, 2), (72, 72, 2), (64, 1, 8)])
def test_sorted_form_equals_dense_form(monkeypatch, rows, n_valid, top_k):
    cfg, params = make(top_k=top_k)
    lp = layer0(params)
    h = jax.random.normal(jax.random.PRNGKey(rows), (rows, 64), jnp.float32)
    valid = jnp.arange(rows) < n_valid
    out = {}
    for f in FORMS:
        monkeypatch.setattr(L, "expert_form", lambda cfg, f=f: f)
        out[f] = L.routed_ffn_load(h, lp, cfg, valid)
    (ya, la), (yb, lb) = out["dense_einsum"], out["sorted_gmm"]
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    scale = float(jnp.abs(ya).max())
    assert float(jnp.abs(ya - yb).max()) < 1e-5 * scale
    # and both equal the reference's expert block on the valid rows
    ref = reference_olmoe.expert_block(
        h[:n_valid], lp, top_k=top_k, norm_topk_prob=cfg.norm_topk_prob)
    assert float(jnp.abs(yb[:n_valid] - ref).max()) < 1e-5 * scale


def test_stacked_weights_with_a_layer_index_equal_the_layers_slice(form):
    """The serving tick hands `routed_ffn_load` the stacked expert leaves
    and the layer's index, so that no layer's experts are sliced out."""
    cfg, params = make()
    h = jax.random.normal(jax.random.PRNGKey(3), (24, 64), jnp.float32)
    for li in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[li], params["blocks"])
        stacked = {**lp, **{n: params["blocks"][n]
                            for n in ("w1", "w3", "w2")}}
        ya, la = L.routed_ffn_load(h, lp, cfg)
        yb, lb = jax.jit(lambda h, lp, li: L.routed_ffn_load(
            h, lp, cfg, layer=li))(h, stacked, jnp.int32(li))
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        np.testing.assert_allclose(np.asarray(ya), np.asarray(yb),
                                   rtol=0, atol=1e-6)


def test_a_padding_row_changes_no_output_and_no_counter(form):
    cfg, params = make(top_k=2)
    lp = layer0(params)
    h = jax.random.normal(jax.random.PRNGKey(5), (32, 64), jnp.float32)
    valid = jnp.arange(32) < 20
    y, load = L.routed_ffn_load(h, lp, cfg, valid)
    # other garbage in the padding rows: same outputs, same load
    h2 = h.at[20:].set(1e3 * jax.random.normal(jax.random.PRNGKey(6),
                                               (12, 64)))
    y2, load2 = L.routed_ffn_load(h2, lp, cfg, valid)
    np.testing.assert_array_equal(np.asarray(y[:20]), np.asarray(y2[:20]))
    np.testing.assert_array_equal(np.asarray(load), np.asarray(load2))
    assert not np.any(np.asarray(y[20:])) and not np.any(np.asarray(y2[20:]))
    # the load is a numpy count of the valid rows' experts
    _, e = L.route(h[:20], lp, cfg)
    np.testing.assert_array_equal(
        np.asarray(load), np.bincount(np.asarray(e).reshape(-1), minlength=8))
    assert int(load.sum()) == 20 * cfg.top_k


def test_sorted_form_has_the_dense_forms_gradient(monkeypatch):
    cfg, params = make()
    lp = layer0(params)
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 16, 64), jnp.float32)
    grads = {}
    for f in FORMS:
        monkeypatch.setattr(L, "expert_form", lambda cfg, f=f: f)
        grads[f] = jax.grad(lambda lp, h: jnp.sum(
            L.routed_ffn(h, lp, cfg) ** 2), argnums=(0, 1))(lp, h)
    for a, b in zip(jax.tree.leaves(grads["dense_einsum"]),
                    jax.tree.leaves(grads["sorted_gmm"])):
        assert float(jnp.abs(a - b).max()) <= 1e-4 * float(
            jnp.abs(a).max() + 1e-9)


# --------------------------------------------------------------------------
# the paged engine: chunked prefill, then paged decode
# --------------------------------------------------------------------------

def engine(cfg, params, **kw):
    kw = {"num_blocks": 40, "block_size": 16, "max_batch": 4,
          "token_budget": 32, "max_len": 128, "pallas": True, **kw}
    return PagedServingEngine(cfg, params, **kw)


def greedy_through_forward(cfg, params, prompt, n, width=96):
    """Greedy decoding by full forward passes, at one padded width so that
    it compiles once (causal attention: the padding behind a position
    cannot reach it)."""
    fwd = jax.jit(lambda params, toks: L.forward(params, toks, cfg,
                                                 attn_impl="xla"))
    toks = np.zeros((1, width), np.int32)
    toks[0, :len(prompt)] = prompt
    for i in range(len(prompt), len(prompt) + n):
        toks[0, i] = int(jnp.argmax(fwd(params, jnp.asarray(toks))[0, i - 1]))
    return toks[0, len(prompt):len(prompt) + n].tolist()


def judge(ref, chosen, tol=TOL):
    """agreement.judge's arithmetic at a float32 tie tolerance: the share
    of positions whose chosen token is the reference's best or ties with
    it within `tol` of the row's largest |logit|."""
    gaps = ref.max(-1) - ref[np.arange(len(chosen)), chosen]
    return float((gaps <= tol * np.abs(ref).max(-1)).mean())


@pytest.mark.parametrize("top_k, qk_norm, norm_topk_prob", [
    (2, True, False), (8, True, False), (2, False, False), (2, True, True)],
    ids=["olmoe_top2", "olmoe_top8of8", "no_qk_norm", "renormalised"])
def test_engine_agrees_with_the_reference_and_with_forward(
        form, top_k, qk_norm, norm_topk_prob):
    cfg, params = make(top_k=top_k, qk_norm=qk_norm,
                       norm_topk_prob=norm_topk_prob)
    eng = engine(cfg, params)
    prompts = [tokens_of(n, seed=n) for n in (5, 40, 70)]   # 40, 70: chunked
    if form == "sorted_gmm" and (top_k, qk_norm, norm_topk_prob) != (
            2, True, False):
        prompts = prompts[:2]     # the interpreted kernel is slow: two do
    rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
    done = {d.rid: d.output_tokens for d in eng.run()}
    assert eng.stats["decode_fast_steps"] > 0 < eng.stats["step_builds"] <= 2
    for rid, prompt in zip(rids, prompts):
        out = np.asarray(done[rid])
        assert len(out) == 8
        seq = np.concatenate([prompt, out])
        at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
        assert judge(ref_logits(cfg, params, seq, at), out) == 1.0
        assert list(out) == greedy_through_forward(cfg, params, prompt, 8)


def test_engine_counters_are_a_numpy_count(form):
    """`moe_pairs` is valid rows x top_k: the mixed tick's padding rows
    are routed nowhere. `moe_experts_hit` and `moe_max_load` are bounded by
    what the pairs allow (the load itself is checked against a numpy count
    in test_a_padding_row_changes_no_output_and_no_counter)."""
    cfg, params = make(top_k=2)
    eng = engine(cfg, params)
    eng.submit(tokens_of(21), max_new_tokens=4)
    eng.run()
    s = eng.stats
    assert s["tokens_computed"] == 21 + 3
    assert s["moe_pairs"] == s["tokens_computed"] * cfg.top_k
    assert (cfg.top_k * cfg.num_layers * s["steps"] <= s["moe_experts_hit"]
            <= s["moe_pairs"] * cfg.num_layers)
    assert 1 <= s["moe_max_load"] <= 21
    # a mixed tick of 21 rows in 32: the 11 padding rows make no pair
    eng2 = engine(cfg, params)
    eng2.submit(tokens_of(21), max_new_tokens=1)
    eng2.step()
    assert eng2.stats["moe_pairs"] == 42
    assert eng2.stats["moe_experts_hit"] <= 8 * cfg.num_layers


def test_engine_serves_moe_with_preemption_and_the_prefix_cache():
    """As for a dense model: a pool too small for both requests preempts
    and recomputes, a repeated prompt is served from cached pages, and the
    tokens stay those of greedy decoding."""
    cfg, params = make()
    prompt = tokens_of(40, seed=9)
    want = greedy_through_forward(cfg, params, prompt, 6)
    eng = engine(cfg, params)
    a = eng.submit(prompt, max_new_tokens=6)
    first = {d.rid: d.output_tokens for d in eng.run()}[a]
    b = eng.submit(prompt, max_new_tokens=6)      # 2 full pages cached
    again = {d.rid: d.output_tokens for d in eng.run()}[b]
    assert list(first) == list(again) == want
    assert eng.blocks.stats["prefix_hit_tokens"] >= 32
    small = engine(cfg, params, num_blocks=6, max_len=64)
    rids = [small.submit(tokens_of(30, seed=s), max_new_tokens=20)
            for s in (1, 2)]
    done = {d.rid: d.output_tokens for d in small.run()}
    assert small.scheduler.stats["preemptions"] > 0
    for rid, s in zip(rids, (1, 2)):
        assert list(done[rid]) == greedy_through_forward(
            cfg, params, tokens_of(30, seed=s), 20)


@pytest.mark.parametrize("kw, what", [
    (dict(pallas_ffn=True), "fused"), (dict(quant_mode="w8"), "quant"),
    (dict(draft="x"), "draft")])
def test_modes_that_cannot_take_experts_refuse_at_construction(kw, what):
    cfg, params = make()
    with pytest.raises(NotImplementedError, match="experts"):
        engine(cfg, params, **kw)
