"""Hybrid-parallel engine parity tests on an 8-virtual-device CPU mesh.

The arbiter for all the collective/transpose reasoning in
paddle_tpu/distributed/hybrid.py: a dp=2 × pp=2 × tp=2 sharded train step must
reproduce the single-device loss AND the single-device AdamW update bit-for-
close. This mirrors the reference's distributed test strategy (SURVEY.md §4:
multi-process localhost runs compared against single-process losses,
test_dist_base.py:957) — compiled single-process SPMD replaces the
subprocesses.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import llama as L
from paddle_tpu.distributed import hybrid as H

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")


def _cfg(**kw):
    base = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
                num_layers=4, num_heads=4, num_kv_heads=2, max_seq_len=16,
                dtype=jnp.float32)
    base.update(kw)
    return L.LlamaConfig(**base)


def _data(cfg, B=4, T=16, seed=1):
    k = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(k, (B, T), 0, cfg.vocab_size, jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    return tokens, targets


def _ref_step(cfg, params, tokens, targets, hp):
    """Single-device reference: global-mean loss, AdamW with the same math,
    and which elements' clipped gradient lies within 3 eps of zero without
    being zero."""
    loss, grads = jax.value_and_grad(
        lambda p: L.loss_fn(p, tokens, targets, cfg, attn_impl="xla"))(params)
    sq = sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree.leaves(grads))
    opt = H.init_opt_state(params)
    new_p, _ = H._adamw_update(params, grads, opt, hp, sq)
    clip = jnp.minimum(1.0, hp.grad_clip / (jnp.sqrt(sq) + 1e-6))
    near_eps = jax.tree.map(
        lambda g: (g != 0) & (jnp.abs(g * clip) <= 3 * hp.eps), grads)
    return loss, new_p, near_eps


@pytest.mark.parametrize("moe", [False, True], ids=["dense", "moe"])
def test_dp2_pp2_tp2_parity(moe):
    cfg = _cfg(num_experts=4 if moe else 0, top_k=2)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    tokens, targets = _data(cfg)
    hp = H.AdamWConfig(lr=1e-2, weight_decay=0.0, grad_clip=1.0)

    ref_loss, ref_p, near_eps = _ref_step(cfg, params, tokens, targets, hp)

    mesh = H.build_mesh(dp=2, pp=2, tp=2)
    sp = H.shard_params(params, mesh, cfg)
    opt = H.init_opt_state(sp)
    step = H.make_train_step(cfg, mesh, num_microbatches=2, hp=hp)
    new_sp, _, loss = step(sp, opt, tokens, targets)

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-5)
    got = H.unstack_pipeline(jax.device_get(new_sp))
    want = jax.device_get(ref_p)
    flat_got = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    flat_near = dict(jax.tree_util.tree_flatten_with_path(
        jax.device_get(near_eps))[0])
    exempt = 0
    for path, w in jax.tree_util.tree_flatten_with_path(want)[0]:
        g, w, near = np.asarray(flat_got[path]), np.asarray(w), flat_near[path]
        # AdamW's first move is lr * g / (|g| + eps): where |g| is near eps
        # (1e-8; w3 has such an element, 7.9e-9) float32's rounding of the
        # gradient, 4e-10 by the order of its sums, moves the update by
        # 1.1 % of lr. Those elements alone are held to 2 % of lr (a wrong
        # sign reads 2 lr); every other element keeps 5e-5
        np.testing.assert_allclose(np.where(near, w, g), w, atol=5e-5,
                                   err_msg=f"param mismatch at {path}")
        np.testing.assert_allclose(g, w, atol=2e-4,
                                   err_msg=f"param mismatch at {path}")
        exempt += int(near.sum())
    # a few elements in ten thousand (6 dense, 21 moe), never a leaf
    assert exempt * 2000 <= sum(
        w.size for w in jax.tree.leaves(want)), exempt


def test_eval_loss_matches_reference():
    cfg = _cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    tokens, targets = _data(cfg)
    ref = L.loss_fn(params, tokens, targets, cfg, attn_impl="xla")
    mesh = H.build_mesh(dp=2, pp=2, tp=2)
    sp = H.shard_params(params, mesh, cfg)
    ev = H.make_eval_step(cfg, mesh, num_microbatches=2)
    loss = ev(sp, tokens, targets)
    np.testing.assert_allclose(float(loss), float(ref), rtol=2e-5)


def test_loss_decreases_over_steps():
    cfg = _cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    tokens, targets = _data(cfg)
    mesh = H.build_mesh(dp=2, pp=2, tp=2)
    sp = H.shard_params(params, mesh, cfg)
    opt = H.init_opt_state(sp)
    step = H.make_train_step(cfg, mesh, num_microbatches=2,
                             hp=H.AdamWConfig(lr=5e-3, weight_decay=0.0))
    losses = []
    for _ in range(6):
        sp, opt, loss = step(sp, opt, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_other_mesh_shapes():
    """pp=4 (tall pipeline) and tp=4/8 layouts also compile and match.
    Wide-head config so heads/kv-heads stay divisible by tp."""
    cfg = _cfg(num_heads=8, num_kv_heads=8)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    tokens, targets = _data(cfg)
    ref = L.loss_fn(params, tokens, targets, cfg, attn_impl="xla")
    for dp, pp, tp in [(1, 4, 2), (2, 1, 4), (1, 1, 8)]:
        mesh = H.build_mesh(dp=dp, pp=pp, tp=tp)
        sp = H.shard_params(params, mesh, cfg)
        ev = H.make_eval_step(cfg, mesh, num_microbatches=2)
        loss = ev(sp, tokens, targets)
        np.testing.assert_allclose(float(loss), float(ref), rtol=3e-5,
                                   err_msg=f"mesh {(dp, pp, tp)}")


def test_cp_context_parallel_parity():
    """cp (ring-attention context parallelism — a capability the reference
    LACKS, SURVEY.md §2.5) must reproduce the single-device loss exactly:
    sequence sharded over cp, ring attention rotating k/v over the axis."""
    cfg = _cfg(num_heads=8, num_kv_heads=8)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    tokens, targets = _data(cfg)
    ref = L.loss_fn(params, tokens, targets, cfg, attn_impl="xla")
    for dp, pp, cp, tp in [(1, 1, 2, 1), (1, 1, 2, 2), (2, 1, 2, 2),
                           (1, 2, 2, 2)]:
        mesh = H.build_mesh(dp=dp, pp=pp, tp=tp, cp=cp)
        sp = H.shard_params(params, mesh, cfg)
        ev = H.make_eval_step(cfg, mesh, num_microbatches=1)
        loss = ev(sp, tokens, targets)
        np.testing.assert_allclose(float(loss), float(ref), rtol=3e-5,
                                   err_msg=f"mesh {(dp, pp, cp, tp)}")


def test_cp_training_step_runs():
    """dp x pp x cp x tp train step: gradients flow through the ring."""
    cfg = _cfg(num_heads=8, num_kv_heads=8)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    tokens, targets = _data(cfg)
    mesh = H.build_mesh(dp=1, pp=2, tp=2, cp=2)
    sp = H.shard_params(params, mesh, cfg)
    opt = H.init_opt_state(sp)
    step = H.make_train_step(cfg, mesh, num_microbatches=2,
                             hp=H.AdamWConfig(lr=3e-3))
    losses = []
    for _ in range(5):
        sp, opt, loss = step(sp, opt, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_cp_gqa_parity():
    """GQA (kv heads < heads) through the ring path must match too."""
    cfg = _cfg(num_heads=8, num_kv_heads=2)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    tokens, targets = _data(cfg)
    ref = L.loss_fn(params, tokens, targets, cfg, attn_impl="xla")
    mesh = H.build_mesh(dp=1, pp=1, tp=2, cp=2)
    sp = H.shard_params(params, mesh, cfg)
    loss = H.make_eval_step(cfg, mesh, num_microbatches=1)(sp, tokens, targets)
    np.testing.assert_allclose(float(loss), float(ref), rtol=3e-5)


# ---- a schedule of one slot is a call of the slot, not a scan --------------

def _plan_cfg():
    """The tiny Mellum2 fixture: window and full layers (two kinds, two
    ropes), every layer sparse with 4 of 16 experts held; float32."""
    import dataclasses
    import json
    import os

    from benchmark.drivers import train_steps_plan as D
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "tests", "fixtures",
        "configs", "tiny-mellum2.json")
    with open(path) as f:
        return dataclasses.replace(D.mellum_config(json.load(f), jnp.float32),
                                   dtype=jnp.float32)


# name -> (config, (dp, pp, tp)): M = 1 on any of these is the one slot
ONE_SLOT = {
    "uniform": (lambda: _cfg(), (1, 1, 1)),
    "uniform_dp2_tp2": (lambda: _cfg(), (2, 1, 2)),
    "plan_sparse": (_plan_cfg, (1, 1, 1)),
}


def _loss_and_grads(cfg, mesh, microbatches, with_stats, chosen):
    """`make_loss_and_grads` with the counters on or off, as
    `make_train_step` builds it."""
    specs = H.param_specs(cfg)
    f = H._per_shard_loss_and_grads(cfg, mesh, microbatches, True, "xla",
                                    "stock", with_stats, chosen)
    P = jax.sharding.PartitionSpec
    return jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(specs, P("dp", "cp"), P("dp", "cp")),
        out_specs=(P(), specs, P()), check_vma=False))


def _assert_leaves_close(got, want, scale=1):
    """Every leaf of `got`, divided by `scale`, within 3e-4 of the largest
    element of `want`'s."""
    flat = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    for path, w in jax.tree_util.tree_flatten_with_path(want)[0]:
        g, w = np.asarray(flat[path]) / scale, np.asarray(w)
        assert np.abs(g - w).max() <= 3e-4 * (np.abs(w).max() + 1e-12), (
            jax.tree_util.keystr(path), np.abs(g - w).max(), np.abs(w).max())


def _global_norm(tree):
    return float(np.sqrt(sum(
        np.sum(np.asarray(g, np.float64) ** 2) for g in jax.tree.leaves(tree))))


# the chosen experts are kept where a layer routes, on one chip alone
@pytest.mark.parametrize("name, with_stats, chosen", [
    ("uniform", False, False), ("uniform", True, False),
    ("uniform_dp2_tp2", False, False), ("plan_sparse", False, False),
    ("plan_sparse", True, False), ("plan_sparse", True, True)],
    ids=lambda v: v if isinstance(v, str) else str(int(v)))
def test_one_slot_has_the_two_slot_loss_and_gradients(name, with_stats,
                                                      chosen):
    """M = 1 on pp = 1 runs `pipe_step` once, without a scan; the same
    batch in two microbatches runs the scan. The loss is the global mean
    on both, so loss and every gradient leaf agree, with the counters
    riding the carry or not."""
    make_cfg, (dp, pp, tp) = ONE_SLOT[name]
    cfg = make_cfg()
    mesh = H.build_mesh(dp=dp, pp=pp, tp=tp)
    sp = H.shard_params(L.init_params(cfg, jax.random.PRNGKey(0)), mesh, cfg)
    tokens, targets = _data(cfg)
    one = _loss_and_grads(cfg, mesh, 1, with_stats, chosen)(
        sp, tokens, targets)
    two = _loss_and_grads(cfg, mesh, 2, with_stats, chosen)(
        sp, tokens, targets)
    np.testing.assert_allclose(float(one[0]), float(two[0]), rtol=2e-5)
    _assert_leaves_close(one[1], two[1])
    want = set(H.MOE_STATS) | ({"chosen"} if chosen else set())
    assert set(one[2]) == set(two[2]) == (want if with_stats else set())
    # a sparse layer counts where dp = 1 runs `routed_ffn_load`
    sparse = sum(s.ffn == "sparse" for s in cfg.layers) * (dp == 1)
    if with_stats:
        assert int(one[2]["moe_launches"]) == sparse
        assert int(two[2]["moe_launches"]) == 2 * sparse
        assert int(one[2]["moe_pairs"]) == int(two[2]["moe_pairs"])
        assert int(one[2]["moe_pairs_held"]) == int(two[2]["moe_pairs_held"])
    if chosen:
        B, T = tokens.shape
        launches = one[2]["chosen"].shape[0]
        assert launches == sparse > 0
        # two microbatches launch a microbatch's layers one after another
        np.testing.assert_array_equal(
            np.asarray(two[2]["chosen"]).reshape(
                2, launches, B * T // 2, cfg.top_k).swapaxes(0, 1).reshape(
                    launches, B * T, cfg.top_k), np.asarray(one[2]["chosen"]))


def _equations(jaxpr, scope=(), in_scan=False):
    """(equation, scope, inside a scan that is not the layers') of every
    equation of a jaxpr, the nested ones too; scope is the named scopes
    around it, an enclosing equation's first."""
    from jax._src import core
    for eqn in jaxpr.eqns:
        here = scope + tuple(
            part for part in str(eqn.source_info.name_stack).split("/")
            if part)
        yield eqn, here, in_scan
        inside = in_scan or (eqn.primitive.name == "scan"
                             and "layers" not in here)
        for sub in core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, here, inside)


def _scans(jaxpr):
    """(scope, length) of every `scan` of a jaxpr."""
    return ((here, eqn.params["length"])
            for eqn, here, _ in _equations(jaxpr)
            if eqn.primitive.name == "scan")


@pytest.mark.parametrize("differentiated", [False, True],
                         ids=["loss", "loss_and_grads"])
@pytest.mark.parametrize("name", ["uniform", "uniform_dp2_tp2",
                                  "plan_sparse"])
def test_one_slot_traces_no_scan_around_its_layers(name, differentiated):
    """The jaxpr of the one-slot loss has the scans over the layers and
    no other; two slots put one scan of length 2 around them (and its
    transpose, differentiated). A loop of one trip hid its trip count from
    the chip's compiler, which ran the layers' forward pass once inside it
    and once more lifted out of it (PERF.md, PR 49)."""
    make_cfg, (dp, pp, tp) = ONE_SLOT[name]
    cfg = make_cfg()
    mesh = H.build_mesh(dp=dp, pp=pp, tp=tp)
    sp = H.shard_params(L.init_params(cfg, jax.random.PRNGKey(0)), mesh, cfg)
    tokens, targets = _data(cfg)

    def scans(microbatches):
        f = (H.make_loss_and_grads(cfg, mesh, microbatches, attn_impl="xla")
             if differentiated else
             H.make_eval_step(cfg, mesh, num_microbatches=microbatches))
        found = list(_scans(jax.make_jaxpr(f)(sp, tokens, targets).jaxpr))
        around = [(s, n) for s, n in found if "layers" not in s]
        return found, around

    found, around = scans(1)
    assert found and not around, around
    layer_scans = len(found)
    found, around = scans(2)
    assert [n for _, n in around] == [2] * (2 if differentiated else 1), around
    assert all(any("pipeline" in part for part in s) for s, _ in around)
    if not differentiated:      # the same scans over the layers, inside it
        assert len(found) - len(around) == layer_scans, found


# ---- the head and the loss run after the schedule, shared over pp ----------

@pytest.mark.parametrize("pp", [1, 2, 3, 4, 8])
@pytest.mark.parametrize("M", [1, 2, 3, 4, 8, 9])
def test_head_rounds_covers_every_microbatch_once(M, pp):
    share = H.head_rounds(M, pp)
    rounds = -(-M // pp)
    assert len(share) == pp and {len(row) for row in share} == {rounds}
    placed = [m for row in share for m in row if m >= 0]
    assert sorted(placed) == list(range(M))
    # what is not a microbatch is padding, and a stage's rounds go up by pp
    assert sum(m == -1 for row in share for m in row) == rounds * pp - M
    for stage, row in enumerate(share):
        assert [m for m in row if m >= 0] == list(range(stage, M, pp))


# name -> ((dp, pp, tp, cp), microbatches, global batch)
SHARED_HEAD = {
    "pp2_m4": ((1, 2, 2, 1), 4, 4),
    "pp4_m8": ((1, 4, 2, 1), 8, 8),
    "pp2_m3_not_divisible": ((1, 2, 2, 1), 3, 6),
    "pp4_m2_fewer_than_stages": ((1, 4, 2, 1), 2, 4),
    "pp2_m2_cp2": ((1, 2, 2, 2), 2, 4),
    "dp2_pp2_m3": ((2, 2, 2, 1), 3, 12),
}


@pytest.fixture(scope="module")
def one_stage():
    """(cfg, params, batch -> (tokens, targets, loss, grads) of that batch
    on mesh 1·1·1·1 in one microbatch: one slot, one round, no collective
    over pp; made once a batch size)."""
    cfg = _cfg(num_heads=8, num_kv_heads=8)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    made = {}

    def reference(batch):
        if batch not in made:
            tokens, targets = _data(cfg, B=batch)
            mesh = H.build_mesh()
            loss, grads, _ = H.make_loss_and_grads(
                cfg, mesh, 1, attn_impl="xla")(
                    H.shard_params(params, mesh, cfg), tokens, targets)
            made[batch] = (tokens, targets, float(loss),
                           H.unstack_pipeline(jax.device_get(grads)))
        return made[batch]

    return cfg, params, reference


@pytest.mark.parametrize("name", list(SHARED_HEAD))
def test_shared_head_has_the_one_stage_loss_and_gradients(name, one_stage):
    """Every stage runs the head on its `head_rounds` of the last stage's
    outputs: loss, every gradient leaf and the loss-only step agree with
    one stage's, whether pp divides M, M < pp, or the sequence is cut
    over cp beside it."""
    (dp, pp, tp, cp), M, batch = SHARED_HEAD[name]
    cfg, params, reference = one_stage
    tokens, targets, ref_loss, ref_grads = reference(batch)
    mesh = H.build_mesh(dp=dp, pp=pp, tp=tp, cp=cp)
    sp = H.shard_params(params, mesh, cfg)
    loss, grads, _ = H.make_loss_and_grads(cfg, mesh, M, attn_impl="xla")(
        sp, tokens, targets)
    np.testing.assert_allclose(float(loss), ref_loss, rtol=2e-5)
    # by direction: one factor common to all leaves is divided out, which
    # AdamW's step does not see (its size has a test of its own, below)
    got = H.unstack_pipeline(jax.device_get(grads))
    _assert_leaves_close(got, ref_grads,
                         scale=_global_norm(got) / _global_norm(ref_grads))
    ev = H.make_eval_step(cfg, mesh, num_microbatches=M)
    np.testing.assert_allclose(float(ev(sp, tokens, targets)), ref_loss,
                               rtol=2e-5)


def test_gradients_come_out_times_pp_tp_cp(one_stage):
    """A defect this test pins in one place (PERF.md section 7(a)): the
    loss is summed over pp and cp and its terms over tp by psums, which
    transpose to psums under check_vma=False, so every gradient leaf comes
    out times pp * tp * cp, and `grad_clip` sees that norm. The PR that
    divides the loss before `jax.grad` makes `factor` 1 here."""
    (dp, pp, tp, cp), M, batch = SHARED_HEAD["pp2_m2_cp2"]
    factor = pp * tp * cp
    cfg, params, reference = one_stage
    tokens, targets, _, ref_grads = reference(batch)
    mesh = H.build_mesh(dp=dp, pp=pp, tp=tp, cp=cp)
    _, grads, _ = H.make_loss_and_grads(cfg, mesh, M, attn_impl="xla")(
        H.shard_params(params, mesh, cfg), tokens, targets)
    got = _global_norm(H.unstack_pipeline(jax.device_get(grads)))
    assert got / _global_norm(ref_grads) == pytest.approx(factor, rel=1e-4)


def _head_products(jaxpr, shape):
    """(scope, inside a scan that is not the layers') of every
    `dot_general` of a jaxpr with an operand of `shape`: the logits'
    product and, differentiated, the product that takes their cotangent
    back to the rows."""
    return ((here, in_scan) for eqn, here, in_scan in _equations(jaxpr)
            if eqn.primitive.name == "dot_general"
            and any(v.aval.shape == shape for v in eqn.invars))


# (dp, pp, tp), M -> passes of head and loss a stage a step
@pytest.mark.parametrize("differentiated", [False, True],
                         ids=["loss", "loss_and_grads"])
@pytest.mark.parametrize("mesh_shape, M", [
    ((1, 1, 1), 1), ((1, 1, 2), 4), ((1, 2, 2), 4), ((1, 2, 2), 3),
    ((1, 4, 2), 2)], ids=str)
def test_head_products_stand_outside_the_slots(mesh_shape, M, differentiated):
    """The traced step holds no product against `lm_head`'s [D, V/tp]
    inside the pipeline scan's body and `head_rounds`' rounds of them
    after it (twice that differentiated: the logits, and their cotangent
    back to the rows); one microbatch on one stage has the one product it
    had inside its one slot (2 differentiated), all under `head_loss`."""
    dp, pp, tp = mesh_shape
    # a vocabulary whose shard no layer's weight has the shape of
    cfg = _cfg(vocab_size=96, num_heads=8, num_kv_heads=8)
    mesh = H.build_mesh(dp=dp, pp=pp, tp=tp)
    sp = H.shard_params(L.init_params(cfg, jax.random.PRNGKey(0)), mesh, cfg)
    tokens, targets = _data(cfg, B=12)
    f = (H.make_loss_and_grads(cfg, mesh, M, attn_impl="xla")
         if differentiated else H.make_eval_step(cfg, mesh, M))
    found = list(_head_products(
        jax.make_jaxpr(f)(sp, tokens, targets).jaxpr,
        (cfg.hidden_size, cfg.vocab_size // tp)))
    rounds = len(H.head_rounds(M, pp)[0])
    assert rounds == -(-M // pp)
    assert len(found) == rounds * (2 if differentiated else 1), found
    assert not any(in_scan for _, in_scan in found), found
    assert all(any("head_loss" in part for part in s) for s, _ in found)
