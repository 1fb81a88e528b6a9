"""One view of a config's layers (`LlamaConfig.layers`) and the one layer
loop behind it (`PagedServingEngine._layer_loop`): a uniform config is a
plan of one kind. What guards the seam: the same model written both ways,
as a uniform config and as an explicit `layer_plan` of `num_layers` equal
specs over the same weights, serves the same tokens and leaves the same
pages, and traces to the same tick programs; the view's one rope is the
table a uniform config always had, bit for bit. Everything here is float32
at a tiny size, the weights made once a module."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.serving import PagedServingEngine
from paddle_tpu.models import llama as L
from tests.test_laguna_paged import _tick_jaxpr

BASE = dict(vocab_size=128, hidden_size=32, intermediate_size=64,
            num_layers=3, num_heads=4, num_kv_heads=2, max_seq_len=64,
            dtype=jnp.float32)
# (without QK-norm: `LlamaConfig` refuses it of a written plan)
MODELS = {"dense": {}, "moe": dict(num_experts=4, top_k=2)}
PROMPTS = ([5, 9, 2, 7, 1, 8, 3, 6, 4, 11, 10], [12, 13, 14, 15, 16])


def written_out(cfg):
    """The uniform `cfg` as an explicit plan of `num_layers` equal specs."""
    return dataclasses.replace(cfg, layer_plan=cfg.layers,
                               dense_intermediate_size=cfg.intermediate_size)


def engine(cfg, params, pallas):
    return PagedServingEngine(cfg, params, num_blocks=16, block_size=8,
                              max_batch=2, token_budget=16, max_len=64,
                              pallas=pallas)


@pytest.fixture(scope="module")
def writings():
    """{model: ((uniform config, params), (the written-out plan, the same
    weights as `(blocks,)`))}."""
    out = {}
    for name, kw in MODELS.items():
        cfg = L.LlamaConfig(**{**BASE, **kw})
        params = jax.jit(lambda k, cfg=cfg: L.init_params(cfg, k))(
            jax.random.PRNGKey(7))
        params = {**params, "lm_head": params["lm_head"] * 8.0}
        out[name] = ((cfg, params),
                     (written_out(cfg),
                      {**params, "blocks": (params["blocks"],)}))
    return out


@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_uniform_config_and_its_written_out_plan_serve_the_same(
        writings, name):
    """Two requests through a mixed tick (both prompts in one chunk mix)
    and decode ticks of each writing's engine: the same tokens, the pages
    bit for bit, the same counts."""
    runs = []
    for cfg, params in writings[name]:
        eng = engine(cfg, params, pallas=False)
        for prompt in PROMPTS:
            eng.submit(prompt, max_new_tokens=3)
        done = sorted(eng.run(), key=lambda d: d.rid)
        runs.append(([d.output_tokens for d in done],
                     np.asarray(eng._key_cache),
                     np.asarray(eng._value_cache), dict(eng.stats)))
    (tokens, keys, values, stats), (tokens_p, keys_p, values_p, stats_p) = runs
    assert tokens == tokens_p and all(len(t) == 3 for t in tokens)
    assert keys.tobytes() == keys_p.tobytes() and keys.any()
    assert values.tobytes() == values_p.tobytes()
    assert stats["steps"] == stats_p["steps"] >= 3
    # the written plan has the plan's counters beside; what both count
    # is equal (the experts' among it)
    shared = set(stats) & set(stats_p)
    assert {k for k in stats if k.startswith("moe_")} <= shared
    assert {k: stats[k] for k in shared} == {k: stats_p[k] for k in shared}
    assert set(stats_p) - set(stats) == {
        "attn_keys_full", "attn_keys_window", "attn_keys_causal",
        "attn_pairs_full", "attn_pairs_window"}


@pytest.mark.parametrize("pallas", [False, True], ids=["stock", "kernel"])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_uniform_config_and_its_written_out_plan_are_one_program(
        writings, name, pallas):
    """The mixed tick's and the decode tick's jaxpr of the two writings,
    on the stock path and on the kernel path (traced, not run: an
    interpreted kernel takes seconds to lower): letter for letter one
    program over the same leaves, so what the run above shows of the stock
    path holds on the kernel path."""
    (cfg, params), (plan, params_p) = writings[name]
    assert (jax.tree.leaves(params_p) == jax.tree.leaves(params)
            and jax.tree.structure(params_p) != jax.tree.structure(params))
    eng, eng_p = engine(cfg, params, pallas), engine(plan, params_p, pallas)
    for tok_pad, decode in ((16, False), (2, True)):
        text = _tick_jaxpr(eng, tok_pad, decode)
        assert text == _tick_jaxpr(eng_p, tok_pad, decode)
        assert ("scan" in text) and ("pallas_call" in text) is pallas


@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_view_of_a_uniform_config_is_one_kind_and_one_run(name):
    cfg = L.LlamaConfig(**{**BASE, **MODELS[name], "rope_theta": 5e5})
    assert cfg.layer_plan == () and len(cfg.layers) == cfg.num_layers
    assert cfg.kinds == (L.LayerSpec(
        "full", 4, L.RopeSpec(theta=5e5),
        "sparse" if cfg.num_experts else "dense"),)
    assert cfg.kind_of_layer == (0,) * cfg.num_layers
    assert L.plan_segments(cfg) == [(1, ((0, cfg.num_layers),))]
    plan = written_out(cfg)
    assert plan.layers == plan.layer_plan == cfg.layers
    assert (plan.num_params(), plan.num_active_params()) == (
        cfg.num_params(), cfg.num_active_params())
    blocks = {"wq": 0}      # the two formats of `params["blocks"]`
    assert L.kind_stacks(blocks) == (blocks,) == L.kind_stacks([blocks])
    # a derived view is not a written plan: QK-norm and block diffusion,
    # which a written plan is refused, stay a uniform config's
    assert len(L.LlamaConfig(**{**BASE, "qk_norm": True,
                                "block_length": 4}).kinds) == 1
    with pytest.raises(NotImplementedError, match="layer plan"):
        dataclasses.replace(plan, qk_norm=True)


@pytest.mark.parametrize("theta, head_dim", [(10000.0, 16), (5e5, 128)])
def test_the_views_rope_is_the_uniform_table_bit_for_bit(theta, head_dim):
    cfg = L.LlamaConfig(**{**BASE, "rope_theta": theta,
                           "head_dim": head_dim})
    pos = jnp.arange(512)
    (spec,) = cfg.kinds
    for got, want in zip(
            L.rope_table(pos, cfg.rope_width(spec), spec.rope),
            L.rope_cos_sin(pos, cfg.head_dim, cfg.rope_theta)):
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
