"""Pallas flash-attention kernel vs the XLA reference path.

Runs the kernel in Pallas interpreter mode on CPU (the fake-backend strategy
of SURVEY.md §4). Interpret mode skips Mosaic's block-mapping validation
(which is what let the round-2 lse BlockSpec bug reach the chip), so the
kernel mirrors that rule statically (`fa._assert_mosaic_tileable`, exercised
at every trace) and `test_mosaic_tiling_rule*` below pins the regression.
The kernel was verified end-to-end (lower+compile+run, fwd+bwd, GQA) on a
real TPU v5e chip on 2026-07-29; tests/test_chip_compile.py re-checks the
lowering for a described v5e.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa


def ref_attention(q, k, v, causal=True):
    """Plain einsum attention (the model's XLA path), f32."""
    B, T, H, hd = q.shape
    KV = k.shape[2]
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    scale = 1.0 / (hd ** 0.5)
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((T, k.shape[1]), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32)).astype(q.dtype)


def _rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    B, T, H, hd = 2, 128, 4, 64
    q = _rand((B, T, H, hd), 0)
    k = _rand((B, T, H, hd), 1)
    v = _rand((B, T, H, hd), 2)
    out = fa.flash_attention(q, k, v, causal=causal, interpret=True)
    ref = ref_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_forward_gqa():
    B, T, H, KV, hd = 2, 64, 8, 2, 32
    q = _rand((B, T, H, hd), 0)
    k = _rand((B, T, KV, hd), 1)
    v = _rand((B, T, KV, hd), 2)
    out = fa.flash_attention(q, k, v, causal=True, interpret=True)
    ref = ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_multi_block_seq():
    """T spans several kv blocks so the online-softmax rescaling is exercised."""
    B, T, H, hd = 1, 512, 2, 64
    q = _rand((B, T, H, hd), 3)
    k = _rand((B, T, H, hd), 4)
    v = _rand((B, T, H, hd), 5)
    out = fa.flash_attention(q, k, v, causal=True, interpret=True)
    ref = ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_reference(causal):
    B, T, H, hd = 1, 128, 2, 32
    q = _rand((B, T, H, hd), 6)
    k = _rand((B, T, H, hd), 7)
    v = _rand((B, T, H, hd), 8)

    def loss_flash(q, k, v):
        o = fa.flash_attention(q, k, v, causal=causal, interpret=True)
        return jnp.sum(jnp.sin(o))  # non-trivial cotangent

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(ref_attention(q, k, v, causal=causal)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=5e-4, atol=5e-5, err_msg=name)


def test_gradients_gqa():
    B, T, H, KV, hd = 1, 64, 4, 2, 32
    q = _rand((B, T, H, hd), 9)
    k = _rand((B, T, KV, hd), 10)
    v = _rand((B, T, KV, hd), 11)

    def loss_flash(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(ref_attention(q, k, v) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=5e-4, atol=5e-5, err_msg=name)


def test_bf16_inputs():
    B, T, H, hd = 1, 128, 2, 64
    q = _rand((B, T, H, hd), 12, jnp.bfloat16)
    k = _rand((B, T, H, hd), 13, jnp.bfloat16)
    v = _rand((B, T, H, hd), 14, jnp.bfloat16)
    out = fa.flash_attention(q, k, v, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = ref_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


def test_unsupported_shapes_raise():
    q = jnp.zeros((1, 100, 3, 16))  # T=100 not tileable; H=3 not mult of KV=2
    k = jnp.zeros((1, 100, 2, 16))
    with pytest.raises(ValueError):
        fa.flash_attention(q, k, jnp.zeros_like(k), interpret=True)


def test_inside_jit_and_scan():
    """Kernel must be traceable inside jit + scan (the model's usage)."""
    B, T, H, hd = 1, 64, 2, 32
    q = _rand((B, T, H, hd), 15)
    k = _rand((B, T, H, hd), 16)
    v = _rand((B, T, H, hd), 17)

    @jax.jit
    def f(q, k, v):
        def body(carry, _):
            o = fa.flash_attention(carry, k, v, interpret=True)
            return o, None
        out, _ = jax.lax.scan(body, q, None, length=2)
        return out

    out = f(q, k, v)
    ref = ref_attention(ref_attention(q, k, v), k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_mosaic_tiling_rule_rejects_rank3_lse():
    # The exact round-2 failure: lse [B, H, T] with block (1, 1, bq) puts a
    # size-1 second-minor dim against H != 1. Must be rejected statically.
    with pytest.raises(ValueError, match="8, 128"):
        fa._assert_mosaic_tileable((1, 1, 256), (4, 12, 2048), "lse")


def test_mosaic_tiling_rule_accepts_current_layouts():
    # o block: last dim == array dim; second-minor divisible by 8
    fa._assert_mosaic_tileable((1, 1, 256, 128), (4, 12, 2048, 128), "o")
    # lse lane-broadcast block: last dim == array dim (LANES)
    fa._assert_mosaic_tileable((1, 1, 256, fa.LANES), (4, 12, 2048, fa.LANES),
                               "lse")


def test_kernel_constants_are_f32():
    # Under jax_enable_x64 a bare python float is weak f64 and the resulting
    # f64->f32 convert fails Mosaic legalization (tpu.truncf). Pin the dtype.
    assert np.asarray(fa.NEG_INF).dtype == np.float32


# --------------------------------------------------------------------------
# the sliding window (position i sees j iff i - W < j <= i)
# --------------------------------------------------------------------------

def ref_window_attention(q, k, v, window):
    """`ref_attention` under a causal window of `window` keys."""
    B, T, H, hd = q.shape
    KV = k.shape[2]
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) / (hd ** 0.5)
    pos = jnp.arange(T)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] > pos[:, None] - window)
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -1e30), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v)


# T 768 = 3 blocks of 256: a window smaller than, equal to and larger than
# a block, and one that is no multiple of anything; T 96 = 3 blocks of 32
WINDOW_CASES = [(768, 100), (768, 256), (768, 350), (768, 512),
                (96, 1), (96, 32), (96, 33), (96, 95)]


@pytest.mark.parametrize("T,window", WINDOW_CASES)
def test_window_forward_matches_reference(T, window):
    B, H, KV, hd = 1, 2, 1, 32
    q, k, v = (_rand((B, T, n, hd), s) for n, s in ((H, 20), (KV, 21), (KV, 22)))
    out = fa.flash_attention(q, k, v, window=window, interpret=True)
    ref = ref_window_attention(q, k, v, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("which", ["dq", "dk", "dv"])
@pytest.mark.parametrize("T,window", [(768, 100), (768, 256), (768, 350),
                                      (96, 33)])
def test_window_gradients_match_reference(T, window, which):
    B, H, KV, hd = 1, 2, 1, 32
    q, k, v = (_rand((B, T, n, hd), s) for n, s in ((H, 23), (KV, 24), (KV, 25)))
    arg = "dq dk dv".split().index(which)

    def loss(f):
        return lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))

    got = jax.grad(loss(lambda q, k, v: fa.flash_attention(
        q, k, v, window=window, interpret=True)), argnums=arg)(q, k, v)
    want = jax.grad(loss(lambda q, k, v: ref_window_attention(
        q, k, v, window)), argnums=arg)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-4, atol=5e-5)


def test_window_walks_only_the_blocks_it_sees():
    """The grids shrink to the window's span: at T 8192 in blocks of 512 a
    window of 1024 visits 3 kv blocks a q block and 3 q blocks a kv block
    where the causal kernels step through 16."""
    assert fa._kv_steps(16, 512, 512, 1024) == 3
    assert fa._q_steps(16, 512, 512, 1024, 16) == 3
    assert fa._kv_steps(16, 512, 512, 512) == 2
    assert fa._kv_steps(16, 512, 512, 1) == 1
    for i in range(16):      # the traced spans are the counted ones
        first, last = fa._kv_span(np.int32(i), 512, 512, 1024)
        assert (int(first), int(last)) == (max(i - 2, 0), i)
        first, last = fa._q_span(np.int32(i), 512, 512, 1024, 16)
        assert (int(first), int(last)) == (i, min(i + 2, 15))


@pytest.mark.parametrize("window", [0, 128, 4096])
def test_no_window_is_todays_program(window):
    """window=0, and a window no shorter than the sequence, trace the very
    kernels the causal path traced before there was a window: the same
    jaxpr, forward and backward, and so bit-equal outputs."""
    B, T, H, hd = 1, 128, 2, 32
    q, k, v = (_rand((B, T, H, hd), s) for s in (26, 27, 28))

    def run(**kw):
        f = lambda q, k, v: jnp.sum(fa.flash_attention(
            q, k, v, interpret=True, **kw) ** 2)
        return jax.make_jaxpr(jax.value_and_grad(f, argnums=(0, 1, 2)))(q, k, v)

    plain = run()
    if window == 0 or window >= T:
        assert str(run(window=window)) == str(plain)
        a = fa.flash_attention(q, k, v, interpret=True, window=window)
        b = fa.flash_attention(q, k, v, interpret=True)
        assert (np.asarray(a) == np.asarray(b)).all()
    else:
        assert str(run(window=window)) != str(plain)


def test_window_needs_the_causal_mask():
    q = _rand((1, 64, 2, 32), 29)
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(q, q, q, causal=False, window=8, interpret=True)
