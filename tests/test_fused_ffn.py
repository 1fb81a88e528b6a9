"""Fused Pallas SwiGLU FFN + mega-kernelized decode tick.

Runs every kernel in Pallas interpreter mode on CPU (the fake-backend
strategy of SURVEY.md §4). With one d_ff block the forward kernel
performs the stock ops in the stock order in f32, so fp32 parity is
gated BIT-EXACTLY (np.array_equal, not allclose) — the same property
that makes the serving engine's fused decode tick token-parity exact.
The backward kernels recompute activations, so grad parity is gated at
float32-ulp tolerances. Trace-time launch accounting and the
executable-cache keying (ffn mode retraces exactly once, zero
steady-state retraces) are pinned on both the training step and the
serving tick.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import llama as L
from paddle_tpu.ops.pallas import flash_attention as FA
from paddle_tpu.ops.pallas import fused_ffn as FF
from paddle_tpu.ops.pallas import fused_sample as FS


def _rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


def _stock_ffn(x, w1, w3, w2):
    # llama.ffn's stock branch, verbatim op order
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _w8(w):
    # the stock weight-only int8 layout: per-out-channel absmax scales
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    return jnp.round(w / s * 127.0).astype(jnp.int8), s


# ---------------------------------------------------------------------------
# forward / backward parity
# ---------------------------------------------------------------------------

def test_forward_bit_exact_fp32():
    x = _rand((64, 32), 0)
    w1, w3, w2 = _rand((32, 64), 1), _rand((32, 64), 2), _rand((64, 32), 3)
    out = FF.fused_ffn(x, w1, w3, w2, interpret=True)
    ref = _stock_ffn(x, w1, w3, w2)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_forward_bf16():
    x = _rand((32, 32), 0, jnp.bfloat16)
    w1 = _rand((32, 64), 1, jnp.bfloat16)
    w3 = _rand((32, 64), 2, jnp.bfloat16)
    w2 = _rand((64, 32), 3, jnp.bfloat16)
    out = FF.fused_ffn(x, w1, w3, w2, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = _stock_ffn(x.astype(jnp.float32), w1.astype(jnp.float32),
                     w3.astype(jnp.float32), w2.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=0.06, atol=0.3)


def test_forward_leading_dims_flattened():
    x = _rand((2, 8, 32), 0)
    w1, w3, w2 = _rand((32, 64), 1), _rand((32, 64), 2), _rand((64, 32), 3)
    out = FF.fused_ffn(x, w1, w3, w2, interpret=True)
    assert out.shape == (2, 8, 32)
    ref = _stock_ffn(x.reshape(16, 32), w1, w3, w2).reshape(2, 8, 32)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_forward_multiblock_dff():
    # d_ff > one block: the accumulator loop runs; parity stays f32-ulp
    x = _rand((128, 128), 0)
    w1, w3 = _rand((128, 1024), 1), _rand((128, 1024), 2)
    w2 = _rand((1024, 128), 3)
    out = FF.fused_ffn(x, w1, w3, w2, interpret=True)
    ref = _stock_ffn(x, w1, w3, w2)
    # blocked d_ff accumulation reorders the K=1024 reduction vs the
    # stock single matmul: f32 ordering noise, not a math difference
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=5e-3)


def test_grad_parity_through_custom_vjp():
    x = _rand((32, 32), 0)
    w1, w3, w2 = _rand((32, 64), 1), _rand((32, 64), 2), _rand((64, 32), 3)

    def f_fused(args):
        return jnp.sum(FF.fused_ffn(*args, interpret=True) ** 2)

    def f_stock(args):
        return jnp.sum(_stock_ffn(*args) ** 2)

    g_fused = jax.grad(f_fused)((x, w1, w3, w2))
    g_stock = jax.grad(f_stock)((x, w1, w3, w2))
    for a, b in zip(g_fused, g_stock):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-2)


def test_int8_dequant_bit_exact():
    x = _rand((64, 32), 0)
    w1, w3, w2 = _rand((32, 64), 1), _rand((32, 64), 2), _rand((64, 32), 3)
    w1_q, w1_s = _w8(w1)
    w3_q, w3_s = _w8(w3)
    w2_q, w2_s = _w8(w2)
    out = FF.fused_ffn_w8(x, w1_q, w1_s, w3_q, w3_s, w2_q, w2_s,
                          interpret=True)
    # stock w8 math: int8 matmul in f32, per-channel scale post-matmul
    u = (x @ w1_q.astype(jnp.float32)) * (w1_s / 127.0)
    v = (x @ w3_q.astype(jnp.float32)) * (w3_s / 127.0)
    ref = ((jax.nn.silu(u) * v) @ w2_q.astype(jnp.float32)) * (w2_s / 127.0)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_apply_ffn_dispatch_and_params_kind():
    w1, w3, w2 = _rand((32, 64), 1), _rand((32, 64), 2), _rand((64, 32), 3)
    fp = {"w1": w1, "w3": w3, "w2": w2}
    w1_q, w1_s = _w8(w1)
    w3_q, w3_s = _w8(w3)
    w2_q, w2_s = _w8(w2)
    w8 = {"w1_q": w1_q, "w1_s": w1_s, "w3_q": w3_q, "w3_s": w3_s,
          "w2_q": w2_q, "w2_s": w2_s}
    assert FF.params_kind(fp) == "fp"
    assert FF.params_kind(w8) == "w8"
    # w8a8 leaves (activation scales) must stay on the stock path
    assert FF.params_kind({**w8, "w1_a": w1_s}) is None
    assert FF.params_kind({"w1": w1}) is None
    x = _rand((16, 32), 0)
    assert np.array_equal(
        np.asarray(FF.apply_ffn(x, fp, interpret=True)),
        np.asarray(FF.fused_ffn(x, w1, w3, w2, interpret=True)))
    with pytest.raises(ValueError):
        FF.apply_ffn(x, {"w1": w1}, interpret=True)


def test_supported_gates_geometry():
    assert FF.supported(64, 32, 64)
    assert not FF.supported(0, 32, 64)
    assert not FF.supported(64, 4, 64)      # d below lane minimum
    assert not FF.supported(64, 32, 4)
    # huge d_ff with no legal block divisor
    assert not FF.supported(64, 32, 1021 * 7)


def test_fused_ffn_raises_on_bad_shapes():
    x = _rand((16, 32), 0)
    w1, w3 = _rand((32, 64), 1), _rand((32, 64), 2)
    with pytest.raises(ValueError):
        FF.fused_ffn(x, w1, w3, _rand((32, 64), 3), interpret=True)


# ---------------------------------------------------------------------------
# gemm epilogue / GLU (the incubate fused-op surface)
# ---------------------------------------------------------------------------

def test_gemm_epilogue_parity():
    x = _rand((32, 64), 0)
    y = _rand((64, 32), 1)
    bias = _rand((32,), 2)
    out = FF.fused_gemm_epilogue(x, y, bias, activation="gelu",
                                 interpret=True)
    # the gelu tail compiles differently under the interpreter's jit than
    # eager XLA (tanh fusion), so this gate is tight-allclose, not
    # bit-exact — bit-exactness is the FFN/GLU/sampler kernels' property
    ref = jax.nn.gelu(x @ y + bias[None, :])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_fused_glu_parity():
    u = _rand((32, 64), 0)
    v = _rand((32, 64), 1)
    out = FF.fused_glu(u, v, act="silu", interpret=True)
    assert np.array_equal(np.asarray(out),
                          np.asarray(jax.nn.silu(u) * v))


# ---------------------------------------------------------------------------
# fused sampler prep
# ---------------------------------------------------------------------------

def test_sampler_prep_matches_sample_rows_bit_exact():
    from paddle_tpu.inference.serving.engine import _sample_rows

    B, V = 8, 97
    logits = _rand((B, V), 0) * 3.0
    temps = jnp.asarray(np.linspace(0.5, 1.4, B), jnp.float32)
    top_ps = jnp.asarray(np.linspace(0.6, 1.0, B), jnp.float32)
    keys = jax.vmap(jax.random.key_data)(
        jax.random.split(jax.random.PRNGKey(7), B))
    masked, amax = FS.fused_sample_prep(logits, temps, top_ps, top_k=0,
                                        interpret=True)
    assert np.array_equal(np.asarray(amax),
                          np.asarray(jnp.argmax(logits, axis=-1)))
    stock = _sample_rows(logits, keys, temps, top_ps, 0)
    draw = jax.vmap(lambda k, row: jax.random.categorical(
        jax.random.wrap_key_data(k), row))(keys, masked).astype(jnp.int32)
    assert np.array_equal(np.asarray(draw), np.asarray(stock))


def test_sampler_prep_top_k():
    from paddle_tpu.inference.serving.engine import _sample_rows

    B, V = 4, 64
    logits = _rand((B, V), 1) * 2.0
    temps = jnp.full((B,), 0.8, jnp.float32)
    top_ps = jnp.full((B,), 0.9, jnp.float32)
    keys = jax.vmap(jax.random.key_data)(
        jax.random.split(jax.random.PRNGKey(3), B))
    masked, _ = FS.fused_sample_prep(logits, temps, top_ps, top_k=8,
                                     interpret=True)
    stock = _sample_rows(logits, keys, temps, top_ps, 8)
    draw = jax.vmap(lambda k, row: jax.random.categorical(
        jax.random.wrap_key_data(k), row))(keys, masked).astype(jnp.int32)
    assert np.array_equal(np.asarray(draw), np.asarray(stock))


# ---------------------------------------------------------------------------
# model / predictor / training wiring
# ---------------------------------------------------------------------------

def _tiny_cfg():
    return L.LlamaConfig(vocab_size=97, hidden_size=32,
                         intermediate_size=64, num_layers=2, num_heads=4,
                         num_kv_heads=2, max_seq_len=96, dtype=np.float32)


def test_llama_ffn_impl_bit_exact():
    cfg = _tiny_cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size, jnp.int32)
    stock = L.forward(params, toks, cfg)
    pallas = L.forward(params, toks, cfg, ffn_impl="pallas")
    assert np.array_equal(np.asarray(stock), np.asarray(pallas))


def test_llm_predictor_forced_pallas_ffn_parity():
    from paddle_tpu.inference.llm import LLMPredictor

    cfg = _tiny_cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    toks = np.asarray([[5, 9, 17, 3, 88, 41, 2, 60]], np.int32)
    stock = LLMPredictor(cfg, params, max_len=cfg.max_seq_len,
                         pallas_ffn=False)
    fused = LLMPredictor(cfg, params, max_len=cfg.max_seq_len,
                         pallas_ffn=True)
    out_s = stock.generate(toks, max_new_tokens=6)
    out_f = fused.generate(toks, max_new_tokens=6)
    assert np.array_equal(np.asarray(out_s), np.asarray(out_f))


def test_train_step_pallas_ffn_parity_and_zero_retrace():
    from paddle_tpu.distributed import hybrid as H

    cfg = _tiny_cfg()
    mesh = H.build_mesh(dp=1, pp=1, tp=1)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0,
                              cfg.vocab_size, jnp.int32)
    tgts = jnp.roll(toks, -1, axis=1)

    def run(ffn_impl):
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        sp = H.shard_params(params, mesh, cfg)
        opt = H.init_opt_state(sp)
        step = H.make_train_step(cfg, mesh, num_microbatches=1,
                                 hp=H.AdamWConfig(lr=1e-3),
                                 attn_impl="xla", ffn_impl=ffn_impl)
        sp, opt, loss = step(sp, opt, toks, tgts)
        tl = FA.trace_launches()
        sp, opt, loss = step(sp, opt, toks, tgts)   # steady state
        # zero steady-state retraces: a retrace would re-run the traced
        # Pallas launches and bump the trace-time counter
        assert FA.trace_launches() == tl
        return float(loss)

    loss_stock = run("stock")
    loss_pallas = run("pallas")
    np.testing.assert_allclose(loss_pallas, loss_stock, rtol=1e-6)


# ---------------------------------------------------------------------------
# serving engine: fused decode tick
# ---------------------------------------------------------------------------

def _engine(cfg, params, **kw):
    from paddle_tpu.inference.serving import PagedServingEngine

    return PagedServingEngine(cfg, params, num_blocks=96, block_size=8,
                              max_batch=6, token_budget=32,
                              max_len=cfg.max_seq_len, **kw)


def _run_trace(eng, prompts, sampled=False):
    rids = []
    for i, p in enumerate(prompts):
        kw = {"max_new_tokens": 6}
        if sampled and i % 2:
            kw.update(temperature=0.7 + 0.05 * i, top_p=0.85,
                      seed=100 + i)
        rids.append(eng.submit(p, **kw))
    by_rid = {c.rid: c.output_tokens for c in eng.run()}
    return [by_rid[r] for r in rids]


def _prompts(cfg, n=6):
    rs = np.random.RandomState(0)
    return [rs.randint(1, cfg.vocab_size, 12).tolist() for _ in range(n)]


@pytest.mark.parametrize("sampled", [False, True])
def test_fused_tick_token_parity(sampled):
    cfg = _tiny_cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg)
    stock = _run_trace(_engine(cfg, params), prompts, sampled)
    fused_eng = _engine(cfg, params, pallas=True, pallas_ffn=True)
    fused = _run_trace(fused_eng, prompts, sampled)
    assert fused == stock
    assert fused_eng.stats["fused_ticks"] > 0
    assert fused_eng.stats["ffn_steps"] > 0


def test_fused_tick_zero_retrace_and_launch_budget():
    cfg = _tiny_cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg)
    eng = _engine(cfg, params, pallas=True, pallas_ffn=True)
    _run_trace(eng, prompts)                      # warm: compiles the tick
    builds = eng.stats["step_builds"]
    _run_trace(eng, prompts)                      # steady state
    assert eng.stats["step_builds"] == builds
    # per-tick launch accounting: DISTINCT Pallas launches traced into the
    # fused tick executable (scan traces its body once) stays within the
    # mega-kernel budget of 3·layers + 1
    launches = eng.stats["tick_pallas_launches"]
    assert 0 < launches <= 3 * cfg.num_layers + 1


def test_ffn_mode_is_in_executable_cache_key():
    # flipping the ffn mode retraces exactly once per (shape, mode) and
    # repeated flips are cache hits — zero steady-state retraces
    cfg = _tiny_cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    eng = _engine(cfg, params)
    eng._get_step_fn(32, 4, ffn_mode=False)
    b0 = eng.stats["step_builds"]
    eng._get_step_fn(32, 4, ffn_mode=True)
    assert eng.stats["step_builds"] == b0 + 1
    eng._get_step_fn(32, 4, ffn_mode=False)
    eng._get_step_fn(32, 4, ffn_mode=True)
    assert eng.stats["step_builds"] == b0 + 1


def test_forced_pallas_ffn_validates_eagerly():
    cfg = L.LlamaConfig(vocab_size=97, hidden_size=4,
                        intermediate_size=4, num_layers=1, num_heads=2,
                        num_kv_heads=2, max_seq_len=64, dtype=np.float32)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="not supported"):
        _engine(cfg, params, pallas_ffn=True)


def test_ffn_fallback_reason_counted():
    from paddle_tpu import observability as obs
    from paddle_tpu.core import flags

    cfg = _tiny_cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    eng = _engine(cfg, params)          # flag-driven (pallas_ffn=None)
    obs.reset()
    flags.set_flags({"pallas_ffn": True})
    try:
        _run_trace(eng, _prompts(cfg, n=2))
    finally:
        flags.set_flags({"pallas_ffn": False})
    s = obs.summary().get("serving", {})
    if FA.available():                  # real TPU: the fused path engages
        assert s.get("ffn_steps", 0) > 0
    else:                               # CPU: flag falls back, counted
        assert s.get("ffn_fallbacks", 0) > 0
