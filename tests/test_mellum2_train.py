"""Mellum2-12B-A2.5B through the trainer, at tiny widths on the CPU: a layer
plan (window, window, window, full; YaRN on the full layers), one chip's
share of the routed experts (4 of 16 here, 16 of 64 on the chip) and a
sliced vocabulary through `distributed.hybrid.make_train_step` on mesh
1·1·1, held to the plain float32 reference
`benchmark/lib/reference_mellum2.py`: loss, every gradient leaf, the AdamW
update; the four shares add up to the uncut layer; a forced imbalance
drops nothing; what stays refused raises by name.

Everything here computes in float32 (`LlamaConfig.dtype`), so that the
tolerances are float32's: what bf16 products do to the gradients is judged
on the chip at the timed sizes (benchmark/lib/agreement_train.py).
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import train_steps_plan as D
from benchmark.lib import agreement_train, reference_mellum2 as R
from paddle_tpu.distributed import hybrid as H
from paddle_tpu.models import llama as L

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests", "fixtures", "configs",
    "tiny-mellum2.json")
FORMS = ("dense_einsum", "sorted_gmm")
B, T = 2, 32


def tiny_file(**over):
    with open(FIXTURE) as f:
        return dict(json.load(f), **over)


def make(seed=0, **over):
    cfg = tiny_file(**over)
    lcfg = dataclasses.replace(D.mellum_config(cfg, jnp.float32),
                               dtype=jnp.float32)
    return cfg, lcfg, L.init_params(lcfg, jax.random.PRNGKey(seed))


def data(cfg, seed=1):
    d = jax.random.randint(jax.random.PRNGKey(seed), (B, T + 1), 0,
                           cfg["vocab_size"], jnp.int32)
    return d[:, :-1], d[:, 1:]


def reference(cfg, params, tokens, targets):
    return R.loss_and_grads(params, tokens, targets,
                            q_block=cfg["correctness"]["q_block"],
                            **R.model_kw(cfg))


def assert_trees_close(got, want, rtol, what):
    flat = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    for path, w in jax.tree_util.tree_flatten_with_path(want)[0]:
        g, w = np.asarray(flat[path]), np.asarray(w)
        assert np.abs(g - w).max() <= rtol * (np.abs(w).max() + 1e-12), (
            what, jax.tree_util.keystr(path), np.abs(g - w).max(),
            np.abs(w).max())


@pytest.fixture(scope="module")
def tiny():
    cfg, lcfg, params = make()
    tokens, targets = data(cfg)
    return cfg, lcfg, params, tokens, targets, reference(cfg, params, tokens,
                                                         targets)


def test_the_tiny_config_is_the_cells_shape(tiny):
    cfg, lcfg = tiny[:2]
    assert [s.attn for s in lcfg.layer_plan] == ["window"] * 3 + ["full"]
    assert lcfg.experts_held == (0, 4) and lcfg.num_experts == 16
    assert len(lcfg.kinds) == 2
    full = lcfg.kinds[1].rope
    assert full.yarn_factor == 4 and full.attention_factor > 1
    assert lcfg.kinds[0].rope.yarn_factor == 0
    assert R.layers_of(cfg) == [(0, 0, "window"), (0, 1, "window"),
                                (0, 2, "window"), (1, 0, "full")]


# ---- the reference against llama.loss_fn ----------------------------------

@pytest.mark.parametrize("form", FORMS)
def test_llama_loss_fn_has_the_references_loss_and_gradients(tiny, form,
                                                             monkeypatch):
    cfg, lcfg, params, tokens, targets, (ref_loss, ref_grads) = tiny
    monkeypatch.setattr(L, "expert_form", lambda c: form)
    loss, grads = jax.value_and_grad(lambda p: L.loss_fn(
        p, tokens, targets, lcfg, attn_impl="xla"))(params)
    assert abs(float(loss) - float(ref_loss)) <= 2e-6 * float(ref_loss)
    assert_trees_close(grads, ref_grads, 2e-4, form)


# ---- the hybrid step on mesh 1.1.1 ----------------------------------------

@pytest.mark.parametrize("attn_impl", ["xla", "flash"])
@pytest.mark.parametrize("form", FORMS)
def test_the_trainers_loss_and_gradients_are_the_references(
        tiny, form, attn_impl, monkeypatch):
    """`make_loss_and_grads` is the half of `make_train_step` before the
    optimizer: under the plan with YaRN and the held share, in both expert
    forms (the sorted one through the grouped-matmul kernel and its
    `custom_vjp` in interpret mode) and through the windowed flash kernel
    forward and backward."""
    cfg, lcfg, params, tokens, targets, (ref_loss, ref_grads) = tiny
    monkeypatch.setattr(L, "expert_form", lambda c: form)
    mesh = H.build_mesh(1, 1, 1)
    sp = H.shard_params(params, mesh, lcfg)
    loss, grads, stats = H.make_loss_and_grads(
        lcfg, mesh, attn_impl=attn_impl)(sp, tokens, targets)
    assert abs(float(loss) - float(ref_loss)) <= 2e-6 * float(ref_loss)
    assert_trees_close(H.unstack_pipeline(grads), ref_grads, 3e-4,
                       (form, attn_impl))
    assert int(stats["moe_launches"]) == 4
    assert int(stats["moe_pairs"]) == 4 * B * T * lcfg.top_k
    assert 0 < int(stats["moe_pairs_held"]) < int(stats["moe_pairs"])
    # 4 of 16 held: 128 places of the 256 pairs of 64 rows x 4 (half of
    # them, where HELD_ROOM x the even count would be all), about 64 pairs
    # held: every launch takes the compact form, differentiated too
    assert L.held_pair_slots(B * T, lcfg) == 128
    assert int(stats["moe_whole_form"]) == 0


@pytest.mark.parametrize("microbatches, warmup", [(1, 0), (2, 0), (1, 4)])
def test_the_train_step_makes_the_references_adamw_update(tiny, microbatches,
                                                          warmup):
    """As `test_hybrid_parallel._ref_step`, with the reference's gradients
    through the reference's own AdamW step: loss, the updated weights (at
    a quarter of the rate in the first of four warm-up steps), and the
    counters a step made `with_stats` returns."""
    cfg, lcfg, params, tokens, targets, (ref_loss, ref_grads) = tiny
    hp = H.AdamWConfig(lr=1e-2, weight_decay=0.1, grad_clip=1.0,
                       warmup_steps=warmup)
    want, want_m, _ = R.adamw_step(params, ref_grads, None, None, 1,
                                   **dataclasses.asdict(hp))
    mesh = H.build_mesh(1, 1, 1)
    # a copy: the step donates its weights, and on one device
    # `shard_params` hands back the fixture's own buffers
    sp = H.shard_params(jax.tree.map(jnp.array, params), mesh, lcfg)
    step = H.make_train_step(lcfg, mesh, num_microbatches=microbatches,
                             hp=hp, attn_impl="xla", with_stats=True)
    new_sp, new_opt, loss, stats = step(sp, H.init_opt_state(sp), tokens,
                                        targets)
    assert abs(float(loss) - float(ref_loss)) <= 2e-6 * float(ref_loss)
    assert int(new_opt["step"]) == 1
    # Adam divides a gradient by its own size: where one is nearly zero its
    # float32 rounding decides the sign of an lr-sized move, so the update
    # is held to `test_hybrid_parallel`'s absolute 5e-5 at lr 1e-2
    got = dict(jax.tree_util.tree_flatten_with_path(
        H.unstack_pipeline(jax.device_get(new_sp)))[0])
    for path, w in jax.tree_util.tree_flatten_with_path(want)[0]:
        np.testing.assert_allclose(np.asarray(got[path]), np.asarray(w),
                                   atol=5e-5, err_msg=str(path))
    assert_trees_close(H.unstack_pipeline(new_opt["m"]), want_m, 3e-4, "m")
    if warmup:
        moved = float(jnp.abs(got[(jax.tree_util.DictKey("lm_head"),)]
                              - params["lm_head"]).max())
        assert 0.9 * 1e-2 / warmup <= moved <= 1.1 * 1e-2 / warmup
    assert int(stats["moe_launches"]) == 4 * microbatches
    assert int(stats["moe_pairs"]) == 4 * B * T * lcfg.top_k


def _small(**over):
    return L.LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                         num_layers=2, num_heads=4, num_kv_heads=2,
                         max_seq_len=16, dtype=jnp.float32, **over)


@pytest.mark.parametrize("over", [{}, {"num_experts": 4, "top_k": 2}],
                         ids=["dense", "moe"])
def test_a_step_returns_three_outputs_whatever_the_config(over):
    """The counters are a fourth output only of a step made `with_stats`,
    for a dense config too (all zero there); a step made without returns
    what it returned, experts or none (`__graft_entry__`'s dryrun and the
    benchmark's `train_steps` unpack three)."""
    cfg = _small(**over)
    mesh = H.build_mesh(1, 1, 1)
    tokens = jnp.zeros((2, 16), jnp.int32)

    def fresh():
        sp = H.shard_params(L.init_params(cfg, jax.random.PRNGKey(0)), mesh,
                            cfg)
        return sp, H.init_opt_state(sp), tokens, tokens

    assert len(H.make_train_step(cfg, mesh, num_microbatches=1)(
        *fresh())) == 3
    stats = H.make_train_step(cfg, mesh, num_microbatches=1,
                              with_stats=True)(*fresh())[3]
    assert set(stats) == set(H.MOE_STATS)
    assert int(stats["moe_launches"]) == (2 if over else 0)
    stats = H.make_loss_and_grads(cfg, mesh)(fresh()[0], tokens, tokens)[2]
    assert set(stats) == set(H.MOE_STATS)


def test_the_counters_cross_stages_and_microbatches():
    """A uniform config's experts on dp = 1 under pp 2 x tp 2 and two
    microbatches (what `__graft_entry__.dryrun_multichip` builds on four
    devices): three outputs as ever, and `with_stats` counts every
    stage's layers once a microbatch, a pipeline bubble's launches for
    nothing."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    cfg = L.LlamaConfig(vocab_size=64, hidden_size=64, intermediate_size=64,
                        num_layers=4, num_heads=8, num_kv_heads=8,
                        max_seq_len=16, num_experts=2, top_k=2,
                        dtype=jnp.float32)
    mesh = H.build_mesh(dp=1, pp=2, tp=2)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64,
                                jnp.int32)

    def fresh():
        sp = H.shard_params(L.init_params(cfg, jax.random.PRNGKey(0)), mesh,
                            cfg)
        return sp, H.init_opt_state(sp), tokens, tokens

    out = H.make_train_step(cfg, mesh, num_microbatches=2)(*fresh())
    assert len(out) == 3
    *_, loss, stats = H.make_train_step(cfg, mesh, num_microbatches=2,
                                        with_stats=True)(*fresh())
    assert float(loss) == float(out[2])
    assert int(stats["moe_launches"]) == 2 * 4
    assert int(stats["moe_pairs"]) == 4 * 2 * 16 * 2
    assert int(stats["moe_pairs_held"]) == int(stats["moe_pairs"])


def test_the_loss_hands_over_the_experts_its_routers_chose(tiny, monkeypatch):
    """`make_loss_and_grads(chosen=True)`: every launch's choice in the
    order of the launches, which is `llama.route`'s on the layer's input;
    a reference sent there has the program's loss and gradients; and on
    two microbatches the launches are a microbatch's layers one after
    another."""
    cfg, lcfg, params, tokens, targets, (ref_loss, ref_grads) = tiny
    mesh = H.build_mesh(1, 1, 1)
    sp = H.shard_params(params, mesh, lcfg)
    one = H.make_loss_and_grads(lcfg, mesh, attn_impl="xla", chosen=True)(
        sp, tokens, targets)[2]["chosen"]
    assert one.shape == (4, B * T, lcfg.top_k) and one.dtype == jnp.int32
    # float32 on both sides: the reference's own top-k is the program's
    want = R.loss_and_grads(
        params, tokens, targets, chosen=one.reshape(4, B, T, lcfg.top_k),
        q_block=cfg["correctness"]["q_block"], **R.model_kw(cfg))
    assert abs(float(want[0]) - float(ref_loss)) <= 1e-6 * float(ref_loss)
    assert_trees_close(want[1], ref_grads, 1e-5, "chosen")
    two = H.make_loss_and_grads(lcfg, mesh, 2, attn_impl="xla", chosen=True)(
        sp, tokens, targets)[2]["chosen"]
    assert two.shape == (8, B * T // 2, lcfg.top_k)
    np.testing.assert_array_equal(
        np.asarray(two).reshape(2, 4, T, lcfg.top_k).swapaxes(0, 1).reshape(
            4, B * T, lcfg.top_k), np.asarray(one))
    # a row's choice is handed on whether its experts are held here or not
    assert int(one.max()) >= lcfg.experts_held[1]


def test_a_uniform_moe_on_dp1_trains_through_routed_ffn_load():
    """Every expert held, no plan: dp = 1 exchanges nothing, so the step
    is `routed_ffn_load`'s and equals `llama.loss_fn`'s gradients."""
    cfg = L.LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                        num_layers=2, num_heads=4, num_kv_heads=2,
                        max_seq_len=16, num_experts=4, top_k=2,
                        dtype=jnp.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64,
                                jnp.int32)
    want = jax.value_and_grad(lambda p: L.loss_fn(
        p, tokens, tokens, cfg, attn_impl="xla"))(params)
    mesh = H.build_mesh(1, 1, 1)
    loss, grads, stats = H.make_loss_and_grads(cfg, mesh, attn_impl="xla")(
        H.shard_params(params, mesh, cfg), tokens, tokens)
    assert abs(float(loss) - float(want[0])) <= 2e-6 * float(want[0])
    assert_trees_close(H.unstack_pipeline(grads), want[1], 2e-4, "uniform")
    assert int(stats["moe_pairs_held"]) == int(stats["moe_pairs"]) == 2 * 32 * 2


# ---- the shares add up ------------------------------------------------------

@pytest.mark.parametrize("form", FORMS)
def test_the_four_shares_add_up_to_the_uncut_layer(form, monkeypatch):
    """Experts 0-3, 4-7, 8-11 and 12-15 on four chips: their outputs, and
    their gradients with respect to the layer's input, sum to what the
    uncut reference gives for the whole layer. The program's layer under
    each share equals the reference's under that share."""
    monkeypatch.setattr(L, "expert_form", lambda c: form)
    cfg, lcfg, _ = make()
    whole_cfg = dataclasses.replace(lcfg, experts_held=())
    lp = jax.tree.map(lambda a: a[0], L.init_params(
        whole_cfg, jax.random.PRNGKey(3))["blocks"][0])
    h = jax.random.normal(jax.random.PRNGKey(4), (B * T, 64), jnp.float32)
    pull = jax.random.normal(jax.random.PRNGKey(5), (B * T, 64), jnp.float32)

    def uncut(h):
        return R.sparse_ffn(h, lp, top_k=lcfg.top_k)

    want, want_dh = jax.value_and_grad(
        lambda h: jnp.sum(uncut(h) * pull))(h)
    want_y = uncut(h)
    total_y, total_dh = 0.0, 0.0
    for first in range(0, 16, 4):
        share = dataclasses.replace(lcfg, experts_held=(first, 4))
        lp_share = dict(lp, **{n: lp[n][first:first + 4]
                               for n in ("w1", "w3", "w2")})
        f = lambda h: L.routed_ffn_load(h, lp_share, share)[0]
        y = f(h)
        ref_y = R.sparse_ffn(h, lp_share, top_k=lcfg.top_k, held=(first, 4))
        assert float(jnp.abs(y - ref_y).max()) <= 1e-5 * float(
            jnp.abs(want_y).max())
        total_y = total_y + y
        total_dh = total_dh + jax.grad(lambda h: jnp.sum(f(h) * pull))(h)
    assert float(jnp.abs(total_y - want_y).max()) <= 1e-5 * float(
        jnp.abs(want_y).max())
    assert float(jnp.abs(total_dh - want_dh).max()) <= 1e-4 * float(
        jnp.abs(want_dh).max())


# ---- no pair dropped --------------------------------------------------------

def _forced(params):
    """The router of every layer forced onto the held experts: a router of
    zeros scores every expert alike, and `lax.top_k` (the program's and the
    reference's) breaks the tie by the lowest index, so every row's four
    experts are 0, 1, 2, 3, the four held here."""
    def force(stack):
        return dict(stack, router=jnp.zeros_like(stack["router"]))
    return dict(params, blocks=tuple(force(s) for s in params["blocks"]))


@pytest.mark.parametrize("form", FORMS)
def test_a_forced_imbalance_drops_nothing(form, monkeypatch):
    """Every one of the 64 rows on each of the four held experts, where
    `_moe_ffn`'s capacity 2.0 is 2 * 64 * 4 / 16 = 32 rows an expert: the
    trainer's loss and gradients are still the reference's. The 256 held
    pairs of a launch pass its 128 places, so the sorted form takes the
    whole form under `jax.grad`, in every launch, and counts it."""
    monkeypatch.setattr(L, "expert_form", lambda c: form)
    cfg, lcfg, params = make(seed=7)
    params = _forced(params)
    tokens, targets = data(cfg, seed=8)
    ref_loss, ref_grads = reference(cfg, params, tokens, targets)
    mesh = H.build_mesh(1, 1, 1)
    loss, grads, stats = H.make_loss_and_grads(lcfg, mesh, attn_impl="xla")(
        H.shard_params(params, mesh, lcfg), tokens, targets)
    assert abs(float(loss) - float(ref_loss)) <= 2e-6 * float(ref_loss)
    assert_trees_close(H.unstack_pipeline(grads), ref_grads, 3e-4, form)
    # the fullest expert of each of the 4 launches holds every row, and
    # every pair the router made is on an expert held here
    assert int(stats["moe_load_max"]) == 4 * B * T
    assert int(stats["moe_pairs_held"]) == int(stats["moe_pairs"])
    assert int(stats["moe_pairs"]) == 4 * 2 * L.held_pair_slots(B * T, lcfg)
    assert int(stats["moe_whole_form"]) == (4 if form == "sorted_gmm" else 0)


# ---- the compact form under the derivative ---------------------------------

def _one_layer(seed=3):
    """One sparse layer of the tiny share (4 of 16 held, 64 rows x 4) with
    a router sharp enough to choose: (lcfg, lp, h, pull)."""
    cfg, lcfg, _ = make()
    lp = jax.tree.map(lambda a: a[0], L.init_params(
        lcfg, jax.random.PRNGKey(seed))["blocks"][0])
    lp = dict(lp, router=lp["router"] * 30.0)
    h = jax.random.normal(jax.random.PRNGKey(4), (B * T, 64), jnp.float32)
    pull = jax.random.normal(jax.random.PRNGKey(5), (B * T, 64), jnp.float32)
    return lcfg, lp, h, pull


def _value_and_grads(lcfg, lp, h, pull):
    """(the layer's output, the gradients of its pull-weighted sum by the
    layer's input and by the router and the three expert matrices)."""
    names = ("router", "w1", "w3", "w2")

    def f(h, ws):
        y = L.routed_ffn_load(h, dict(lp, **ws), lcfg)[0]
        return jnp.sum(y * pull), y

    (_, y), grads = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(
        h, {n: lp[n] for n in names})
    return y, grads


@pytest.mark.parametrize("product", [True, False],
                         ids=["product", "gather"])
def test_the_compact_forms_gradients_are_the_whole_forms(product,
                                                         monkeypatch):
    """Value and gradients (the layer's input, the router, the three expert
    matrices) of the sorted form on 128 places equal those on all 256, in
    float32, whichever way the places go back into their rows (both forms
    are held to the reference by the tests above)."""
    monkeypatch.setattr(L, "expert_form", lambda c: "sorted_gmm")
    monkeypatch.setattr(L, "combine_is_a_product",
                        lambda places, rows, k: product and places < rows * k)
    lcfg, lp, h, pull = _one_layer()
    load = L.routed_ffn_load(h, lp, lcfg)[1]
    assert 0 < int(load.sum()) <= L.held_pair_slots(B * T, lcfg) == 128
    text = str(jax.make_jaxpr(lambda h: _value_and_grads(lcfg, lp, h, pull))(h))
    # the [T, C] product is the one product made at the highest precision
    assert "cond[" in text and ("Precision.HIGHEST" in text) == product
    compact = _value_and_grads(lcfg, lp, h, pull)
    monkeypatch.setattr(L, "held_pair_slots",
                        lambda rows, cfg: rows * cfg.top_k)
    whole = _value_and_grads(lcfg, lp, h, pull)
    assert_trees_close(compact, whole, 2e-5, "whole")


def _an_order(T, k, held_of, seed):
    """A launch's sorted order as `_experts_sorted` makes it, with about
    one pair in `held_of` held: (pair [T*k], place [T, k], n)."""
    held = jax.random.uniform(jax.random.PRNGKey(seed), (T * k,)) < 1 / held_of
    order = jnp.argsort(~held, stable=True).astype(jnp.int32)
    place = jnp.argsort(order).astype(jnp.int32).reshape(T, k)
    return order, place, jnp.sum(held).astype(jnp.int32)


@pytest.mark.parametrize("product", [True, False],
                         ids=["product", "gather"])
@pytest.mark.parametrize("T, k, C", [(24, 4, 48), (24, 4, 96), (16, 2, 24)])
def test_the_two_gathers_are_each_others_transposes(T, k, C, product,
                                                    monkeypatch):
    """<to_places(x), g> = <x, to_rows(g)> on random data, the derivative
    of `_dispatch` IS the way back with unit weights and that of `_combine`
    by the outputs the way to the places times the pairs' weights, and
    both hold against finite differences. Rows behind the last held pair
    are garbage on purpose: nothing may read them."""
    monkeypatch.setattr(L, "combine_is_a_product", lambda *shape: product)
    d = 8
    order, place, n = _an_order(T, k, 4, seed=T + C)
    assert 0 < int(n) <= C
    pair = order[:C]
    keys = jax.random.split(jax.random.PRNGKey(C), 4)
    x = jax.random.normal(keys[0], (T, d), jnp.float32)
    g = jax.random.normal(keys[1], (C, d), jnp.float32)
    g = jnp.where((jnp.arange(C) < n)[:, None], g, jnp.nan)
    w = jax.random.uniform(keys[2], (T, k), jnp.float32, 0.5, 1.5)
    dy = jax.random.normal(keys[3], (T, d), jnp.float32)
    there = L._to_places(x, pair, n, k)
    back = L._to_rows(g, None, pair, place, n)
    assert not np.any(np.isnan(np.asarray(back)))
    np.testing.assert_allclose(float(jnp.sum(there * jnp.nan_to_num(g))),
                               float(jnp.sum(x * back)), atol=1e-4)
    # a row with no held pair gets an exact zero, a place behind n too
    alone = ~np.asarray((place < n).any(axis=1))
    assert alone.any() and not np.any(np.asarray(back)[alone])
    assert not np.any(np.asarray(there)[int(n):])
    # the derivatives are the other direction
    dx, = jax.vjp(lambda x: L._dispatch(x, pair, place, n), x)[1](g)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(back), rtol=1e-6)
    dys, dw = jax.vjp(lambda ys, w: L._combine(ys, w, pair, place, n),
                      g, w)[1](dy)
    want = L._to_places(dy, pair, n, k) * jnp.take(w.reshape(-1), pair)[:, None]
    np.testing.assert_allclose(np.asarray(dys), np.asarray(want), rtol=1e-6)
    got = jnp.take(jnp.nan_to_num(g), jnp.minimum(place, C - 1), axis=0)
    want_dw = jnp.where(place < n, jnp.einsum("tkd,td->tk", got, dy), 0.0)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(want_dw),
                               rtol=1e-5, atol=1e-6)
    # and both against finite differences (garbage rows replaced: a
    # difference quotient through a NaN is a NaN)
    from jax.test_util import check_grads
    ys = jnp.nan_to_num(g)
    with jax.default_matmul_precision("highest"):
        check_grads(lambda x: L._dispatch(x, pair, place, n), (x,), order=1,
                    modes=["rev"], atol=2e-2, rtol=2e-2)
        check_grads(lambda ys, w: L._combine(ys, w, pair, place, n), (ys, w),
                    order=1, modes=["rev"], atol=2e-2, rtol=2e-2)


def _eqns(jaxpr, opaque=()):
    """Every equation of a jaxpr, the nested ones too, but for what lies
    inside the primitives named `opaque`."""
    from jax._src import core
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name not in opaque:
            for sub in core.jaxprs_in_params(eqn.params):
                yield from _eqns(sub, opaque)


def _wide_scatter_adds(jaxpr, d):
    """The scatter-adds of a jaxpr, every nested one too, whose updates
    are rows of d values."""
    return [e for e in _eqns(jaxpr) if e.primitive.name == "scatter-add"
            and e.invars[2].aval.shape[-1:] == (d,)]


@pytest.mark.parametrize("whole", [False, True], ids=["share", "all_held"])
def test_the_differentiated_routed_ffn_scatters_no_rows(whole, monkeypatch):
    """Neither direction of the derivative adds d-wide rows into place one
    at a time: the way to the places and the way back are gathers forward
    and backward, under a share (both branches of its `cond`) and with
    every expert held. JAX's own transpose of a row gather is such a
    scatter-add, which is what the test would find."""
    monkeypatch.setattr(L, "expert_form", lambda c: "sorted_gmm")
    lcfg, lp, h, pull = _one_layer()
    if whole:
        lcfg = dataclasses.replace(lcfg, experts_held=(), num_experts=4)
        lp = dict(lp, router=lp["router"][:, :4])
    jaxpr = jax.make_jaxpr(lambda h: _value_and_grads(lcfg, lp, h, pull))(h)
    assert "gather" in {e.primitive.name for e in _eqns(jaxpr.jaxpr)}
    assert not _wide_scatter_adds(jaxpr.jaxpr, 64)
    plain = jax.make_jaxpr(jax.grad(lambda h: jnp.sum(
        jnp.take(h, jnp.arange(8) // 2, axis=0))))(h)
    assert len(_wide_scatter_adds(plain.jaxpr, 64)) == 1


def test_capacity_dispatch_would_have_dropped_those_rows():
    """The test above has teeth: under the same forced router `_moe_ffn`
    (every expert held, its capacity factor 2.0) leaves rows out that the
    reference and `routed_ffn_load` compute."""
    cfg, lcfg, params = make(seed=7)
    whole = dataclasses.replace(lcfg, experts_held=())
    lp = jax.tree.map(lambda a: a[0], _forced(L.init_params(
        whole, jax.random.PRNGKey(7)))["blocks"][0])
    h = jax.random.normal(jax.random.PRNGKey(9), (1, B * T, 64), jnp.float32)
    want = R.sparse_ffn(h[0], lp, top_k=lcfg.top_k)
    kept = L.routed_ffn_load(h, lp, whole)[0][0]
    mesh = H.build_mesh(1, 1, 1)
    dropped = jax.shard_map(
        lambda h: H._moe_ffn(h, lp, whole, 1), mesh=mesh,
        in_specs=jax.sharding.PartitionSpec(),
        out_specs=jax.sharding.PartitionSpec(), check_vma=False)(h)[0]
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(kept - want).max()) <= 1e-5 * scale
    assert float(jnp.abs(dropped - want).max()) >= 1e-2 * scale


# ---- what stays refused -----------------------------------------------------

def _two_chips():
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")


@pytest.mark.parametrize("mesh_kw, name", [
    ({"pp": 2}, "pipeline stages"), ({"dp": 2}, "experts_held"),
    ({"cp": 2}, "context parallelism")])
def test_what_the_trainer_refuses_raises_by_name(mesh_kw, name):
    _two_chips()
    cfg, lcfg, params = make()
    mesh = H.build_mesh(**mesh_kw)
    with pytest.raises(NotImplementedError, match=name):
        H.make_train_step(lcfg, mesh, num_microbatches=1)
    with pytest.raises(NotImplementedError, match=name):
        H.shard_params(params, mesh, lcfg)


@pytest.mark.parametrize("over, name", [
    ({"attn_gate": True}, "attn_gate"),
    ({"shared_expert_width": 32}, "shared_expert_width"),
    ({"router_bias": True}, "router_bias")])
def test_what_the_block_body_lacks_is_refused_by_name(over, name):
    lcfg = dataclasses.replace(make()[1], **over)
    with pytest.raises(NotImplementedError, match=name):
        H.param_specs(lcfg)


def test_the_chosen_experts_are_refused_off_one_chip():
    _two_chips()
    lcfg = dataclasses.replace(make()[1], experts_held=())
    with pytest.raises(NotImplementedError, match="chosen experts"):
        H.make_loss_and_grads(lcfg, H.build_mesh(dp=2), chosen=True)


# ---- the judge that decides `correct` on the chip --------------------------

def test_the_judge_passes_the_reference_and_fails_a_wrong_leaf(tiny):
    cfg, lcfg, params, tokens, targets, (ref_loss, ref_grads) = tiny
    host = jax.tree.map(np.array, jax.device_get(ref_grads))
    ok, notes = agreement_train.judge(ref_loss, host, ref_loss, host)
    assert ok and notes["leaves_compared"] == 3 + 4 * 10
    assert set(notes["worst"]) == set(agreement_train.LIMITS)
    # one layer's w2 twice what it is: the experts group fails
    wrong = jax.tree.map(np.copy, host)
    wrong["blocks"][0]["w2"][1] *= 2.0
    ok, notes = agreement_train.judge(ref_loss, wrong, ref_loss, host)
    assert not ok and notes["failed_groups"] == ["experts"]
    ok, notes = agreement_train.judge(float(ref_loss) + 0.01, host, ref_loss,
                                      host)
    assert not ok and notes["failed_groups"] == ["loss"]


def test_the_update_judge_reads_one_for_a_state_left_unchanged(tiny):
    """Comparison 2 as the chip makes it, at float32 on the tiny shape: the
    step's first moment is the reference's and its change of every leaf
    close to the reference's (Adam's first move is a sign: an element
    whose gradient is nearly zero may go the other way); a step that
    leaves the weights where they were reads exactly 1 and fails, and so
    does one that moved them at another rate."""
    cfg, lcfg, params, tokens, targets, (ref_loss, ref_grads) = tiny
    hp = dict(dataclasses.asdict(H.AdamWConfig()), lr=1e-3, warmup_steps=4)
    mesh = H.build_mesh(1, 1, 1)
    old = H.shard_params(params, mesh, lcfg)
    new, opt, _ = H.make_train_step(
        lcfg, mesh, num_microbatches=1, hp=H.AdamWConfig(**hp),
        attn_impl="xla")(jax.tree.map(jnp.array, old),
                         H.init_opt_state(old), tokens, targets)
    ref = H.stack_pipeline(ref_grads, 1)
    ok, notes = agreement_train.judge_update(old, new, opt["m"], ref, hp,
                                             view=D.unstacked)
    assert ok and max(notes["moment_worst"].values()) < 1e-3
    assert notes["change_worst"] < 0.2
    ok, notes = agreement_train.judge_update(old, old, opt["m"], ref, hp,
                                             view=D.unstacked)
    assert not ok and notes["change_worst"] == pytest.approx(1.0, abs=1e-6)
    ok, notes = agreement_train.judge_update(
        old, new, opt["m"], ref, dict(hp, warmup_steps=0), view=D.unstacked)
    assert not ok and notes["change_worst"] > 0.7
    ok, notes = agreement_train.judge_update(
        old, new, jax.tree.map(jnp.zeros_like, opt["m"]), ref, hp,
        view=D.unstacked)
    assert not ok and len(notes["moment_failed_groups"]) == 6


def test_a_window_one_key_short_is_seen_at_float32(tiny):
    """The comparison the chip makes, at float32 on the tiny shape: the
    program under a window of 7 against the reference's 8 fails the
    attention group by orders of magnitude more than float32 leaves."""
    cfg, lcfg, params, tokens, targets, (ref_loss, ref_grads) = tiny
    mesh = H.build_mesh(1, 1, 1)
    sp = H.shard_params(params, mesh, lcfg)
    errs = {}
    for window in (8, 7):
        c = dataclasses.replace(lcfg, sliding_window=window)
        loss, grads, _ = H.make_loss_and_grads(c, mesh, attn_impl="xla")(
            sp, tokens, targets)
        errs[window] = agreement_train.judge(
            loss, H.unstack_pipeline(grads), ref_loss, ref_grads)[1]["worst"]
    assert errs[8]["attention"] < 1e-4
    assert errs[7]["attention"] > 100 * errs[8]["attention"]
