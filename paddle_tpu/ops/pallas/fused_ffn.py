"""Fused SwiGLU FFN for TPU (Pallas).

Reference parity target: `fused_feedforward` / the gated half of
`fused_bias_act` (paddle/phi/kernels/fusion/; SURVEY.md §2.1) — but
following the Operator-Fusion-in-XLA methodology (PAPERS.md arXiv
2301.13062): XLA already fuses the bias/activation epilogues into its
GEMMs, what it does NOT do is keep the `[rows, d_ff]` gate intermediate
out of HBM across THREE matmuls. This kernel owns exactly that seam:

    out = (silu(x @ w1) * (x @ w3)) @ w2          -- one launch

tiled over (rows, d_ff) blocks with the running `[rows, d]` output sum
in VMEM accumulator scratch, so `u = x @ w1[:, j]`, `v = x @ w3[:, j]`
and `g = silu(u) * v` live and die in registers/VMEM per d_ff block and
the intermediate never round-trips HBM.

Structure mirrors flash_attention.py:

- grid `(rows/bR, d_ff/bF)` with the d_ff axis innermost (sequential on
  TPU), accumulator zeroed at `j == 0` and the output written at
  `j == nF - 1` (`pl.when` predication);
- `jax.custom_vjp` with Pallas backward kernels: dx recomputes (u, v)
  per block and fuses the transposed down-matmul with the
  silu-gradient epilogue into one accumulated launch; dw1/dw3/dw2 are
  accumulated outer-product kernels over the row blocks (one 3-output
  launch), so bwd = 2 launches total;
- an int8 weight-only variant (`fused_ffn_w8`) dequantizing IN-REGISTER
  from the per-out-channel scale rows `quantize_llama_params` produces
  ([1, d_ff] for w1/w3, [1, d] for w2) — the gate/up scales land on the
  accumulators BEFORE the nonlinearity (they cannot commute past silu),
  the down scale is constant across d_ff blocks and folds once into the
  final output, the same factoring idiom as paged attention's per-page
  scales;
- small shapes use whole-dimension blocks (block == array dim is always
  Mosaic-legal), so the serving engine's tiny decode batches run the
  same kernel CI exercises in interpret mode. With a single d_ff block
  the kernel performs the stock ops in the stock order in f32, which is
  what makes the engine's fused-tick token parity bit-exact on the
  smoke configs.

Callers gate with `available()` (real TPU; interpret mode ignores it
and is how CPU CI runs these kernels) + `supported(rows, d, d_ff)` and
fall back to the stock XLA path; `FLAGS_pallas_ffn` is the user switch,
resolved OUTSIDE traced code (trace-time flag reads are a TPL001
finding) and carried in the callers' executable cache keys so a flip
retraces exactly once.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import flags
from .flash_attention import (_BLOCK_CANDIDATES, _assert_mosaic_tileable,
                              _i32, available, count_launch)

__all__ = ["fused_ffn", "fused_ffn_w8", "apply_ffn", "params_kind",
           "available", "supported", "fused_gemm_epilogue", "fused_glu",
           "epilogue_supported"]

flags.define_flag(
    "pallas_ffn", False,
    help="Run SwiGLU FFN blocks through the fused Pallas kernel (one "
         "launch: gate matmul + silu + up matmul + mul + down matmul, "
         "d_ff intermediate kept in VMEM) instead of the stock XLA "
         "matmul chain. Takes effect when the kernel is available() and "
         "the (rows, hidden, d_ff) geometry is supported(); otherwise "
         "the stock path serves the call "
         "(paddle_pallas_ffn_fallback_total counts why). Resolved at "
         "build/tick time outside traced code — the training step, "
         "LLMPredictor and PagedServingEngine key their executables on "
         "the resolved value, so flips retrace exactly once. Also "
         "routes incubate fused_bias_act (swiglu/geglu) and "
         "gemm_epilogue through the Pallas epilogue kernels on TPU.")

# scalar constants entering kernel bodies stay concrete np.float32 (the
# jax_enable_x64 weak-float hazard, see flash_attention.py)
_ONE = np.float32(1.0)
_QMAX = np.float32(127.0)   # transform.py QMAX; s/127 dequant must match

# d_ff tiles: the block is the last dim of the w1/w3 blocks, so Mosaic
# needs it 128-divisible (or the whole dim, always legal)
_F_TILES = (512, 256, 128)
# Mosaic's default scoped-VMEM limit (16 MiB) is below what the backward
# needs at any tiling once d reaches 4096, so every FFN launch states its
# own limit (a v5e core has 128 MiB of VMEM) and `_plan` budgets the
# heaviest launch's working set under it
_VMEM_LIMIT = 100 * 1024 * 1024
_VMEM_BUDGET = 96 * 1024 * 1024
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)
# whole-k GEMM epilogue launches stay under Mosaic's default limit
_EPILOGUE_BUDGET = 14 * 1024 * 1024


def _working_set(br: int, bf: int, d: int) -> int:
    """Upper bound, in bytes, on the VMEM of the heaviest FFN launch (the
    backward's dw kernel; the forward and dx launches hold a subset):
    double-buffered blocks counted at 4 bytes — in x/do [br, d] and
    w1/w3/w2, out dw1/dw3/dw2 [d, bf] — the three f32 [d, bf]
    accumulators, and the f32 temporaries (casts of every input block,
    the three outer products, and u/v/sg/dg/du/dv/g [br, bf])."""
    blocks = 2 * 4 * (2 * br * d + 6 * d * bf)
    scratch = 4 * 3 * d * bf
    temps = 4 * (2 * br * d + 6 * d * bf + 8 * br * bf)
    return blocks + scratch + temps


def _plan(rows: int, d: int, d_ff: int) -> Optional[Tuple[int, int]]:
    """(row_block, f_block) shared by the forward and both backward
    launches, or None when no Mosaic-legal tiling fits the VMEM budget:
    the largest tile area that fits, ties to the taller row block (the
    weights are re-streamed once per row block)."""
    if rows < 1 or d < 8 or d_ff < 8:
        return None
    f_opts = [d_ff] if d_ff <= 512 else [b for b in _F_TILES
                                         if d_ff % b == 0]
    r_opts = [rows] if rows <= 512 else [b for b in _BLOCK_CANDIDATES
                                         if rows % b == 0]
    fits = [(br * bf, br, bf) for bf in f_opts for br in r_opts
            if _working_set(br, bf, d) <= _VMEM_BUDGET]
    if not fits:
        return None
    _, br, bf = max(fits)
    return br, bf


def supported(rows: int, d: int, d_ff: int) -> bool:
    """Static gate: can this FFN geometry run through the kernel?
    (availability — is there TPU hardware — is `available()`; interpret
    mode ignores it and is how CPU CI exercises the kernel bit-for-bit)."""
    return _plan(int(rows), int(d), int(d_ff)) is not None


def params_kind(lp) -> Optional[str]:
    """Which fused variant serves this (possibly quantized) block's FFN
    leaves: "fp" (plain weights), "w8" (weight-only int8 + per-channel
    scales), or None (w8a8/fp8 stay on the stock path)."""
    names = ("w1", "w3", "w2")
    if all(n in lp for n in names):
        return "fp"
    if (all(f"{n}_q" in lp and f"{n}_s" in lp for n in names)
            and not any(f"{n}_a" in lp for n in names)):
        return "w8"
    return None


# ---------------------------------------------------------------------------
# Forward kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, w1_ref, w3_ref, w2_ref, o_ref, acc):
    """One (row block i, d_ff block j) grid step; j innermost so `acc`
    carries the partial down-projection across the d_ff walk."""
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)

    x = x_ref[...].astype(jnp.float32)                 # [bR, d]
    u = jax.lax.dot_general(                           # gate: x @ w1[:, j]
        x, w1_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    v = jax.lax.dot_general(                           # up: x @ w3[:, j]
        x, w3_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    g = jax.nn.silu(u) * v                             # [bR, bF], VMEM-only
    acc[:] += jax.lax.dot_general(                     # down: g @ w2[j, :]
        g, w2_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _():
        o_ref[...] = acc[:].astype(o_ref.dtype)


def _fwd_w8_kernel(x_ref, w1_ref, s1_ref, w3_ref, s3_ref, w2_ref, s2_ref,
                   o_ref, acc):
    """int8 weight-only forward: per-out-channel dequant in-register.
    s1/s3 [1, bF] scale the gate/up accumulators BEFORE silu (the scale
    cannot commute past the nonlinearity); s2 [1, d] is constant across
    d_ff blocks, so it factors out of the accumulation and folds once
    into the final write — same placement as the stock matmul_param
    math, hence bit-identical tokens in interpret mode."""
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)

    x = x_ref[...].astype(jnp.float32)
    u = jax.lax.dot_general(
        x, w1_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * s1_ref[...]
    v = jax.lax.dot_general(
        x, w3_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * s3_ref[...]
    g = jax.nn.silu(u) * v
    acc[:] += jax.lax.dot_general(
        g, w2_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _():
        o_ref[...] = (acc[:] * s2_ref[...]).astype(o_ref.dtype)


def _fwd(x, w1, w3, w2, interpret: bool):
    R, d = x.shape
    f = w1.shape[1]
    br, bf = _plan(R, d, f)
    mem = {"memory_space": pltpu.VMEM}
    in_specs = [
        pl.BlockSpec((br, d), lambda i, j: (i, _i32(0)), **mem),
        pl.BlockSpec((d, bf), lambda i, j: (_i32(0), j), **mem),
        pl.BlockSpec((d, bf), lambda i, j: (_i32(0), j), **mem),
        pl.BlockSpec((bf, d), lambda i, j: (j, _i32(0)), **mem),
    ]
    out_spec = pl.BlockSpec((br, d), lambda i, j: (i, _i32(0)), **mem)
    inputs = [x, w1, w3, w2]
    for spec, arr in zip(in_specs, inputs):
        _assert_mosaic_tileable(spec.block_shape, arr.shape, "ffn input")
    _assert_mosaic_tileable(out_spec.block_shape, (R, d), "ffn output")
    count_launch()
    return pl.pallas_call(
        _fwd_kernel,
        name="fused_ffn_fwd",
        grid=(R // br, f // bf),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((R, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((br, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*inputs)


# ---------------------------------------------------------------------------
# Backward kernels (recompute u/v per block; the intermediate is never
# stored, mirroring the forward's no-HBM-round-trip contract)
# ---------------------------------------------------------------------------

def _act_grads(x, w1_ref, w3_ref, w2_ref, do):
    """Shared bwd epilogue math for one (row, d_ff) block pair:
    recompute u/v, then du/dv from dg = do @ w2^T with the silu
    gradient silu'(u) = sig(u) * (1 + u * (1 - sig(u)))."""
    u = jax.lax.dot_general(
        x, w1_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    v = jax.lax.dot_general(
        x, w3_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    sg = jax.nn.sigmoid(u)
    dg = jax.lax.dot_general(                          # do @ w2[j, :]^T
        do, w2_ref[...].astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    du = dg * v * (sg * (_ONE + u * (_ONE - sg)))
    dv = dg * (u * sg)                                 # dg * silu(u)
    return u, v, sg, du, dv


def _dx_kernel(x_ref, w1_ref, w3_ref, w2_ref, do_ref, dx_ref, acc):
    """dx = du @ w1^T + dv @ w3^T, accumulated across the d_ff walk with
    the activation-gradient epilogue fused into the transposed down
    matmul (dg never leaves VMEM)."""
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)

    x = x_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    _, _, _, du, dv = _act_grads(x, w1_ref, w3_ref, w2_ref, do)
    acc[:] += (jax.lax.dot_general(
        du, w1_ref[...].astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        + jax.lax.dot_general(
            dv, w3_ref[...].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32))

    @pl.when(j == nj - 1)
    def _():
        dx_ref[...] = acc[:].astype(dx_ref.dtype)


def _dw_kernel(x_ref, w1_ref, w3_ref, w2_ref, do_ref,
               dw1_ref, dw3_ref, dw2_ref, a1, a3, a2):
    """Accumulated outer products over the row walk (grid (nF, nR), row
    axis innermost): dw1 = x^T du, dw3 = x^T dv, dw2 = g^T do — three
    outputs from one launch, one u/v recompute shared by all."""
    i = pl.program_id(1)
    ni = pl.num_programs(1)

    @pl.when(i == 0)
    def _():
        a1[:] = jnp.zeros_like(a1)
        a3[:] = jnp.zeros_like(a3)
        a2[:] = jnp.zeros_like(a2)

    x = x_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    u, v, sg, du, dv = _act_grads(x, w1_ref, w3_ref, w2_ref, do)
    g = (u * sg) * v                                   # silu(u) * v
    a1[:] += jax.lax.dot_general(                      # [d, bF]
        x, du, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    a3[:] += jax.lax.dot_general(
        x, dv, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    a2[:] += jax.lax.dot_general(                      # [bF, d]
        g, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(i == ni - 1)
    def _():
        dw1_ref[...] = a1[:].astype(dw1_ref.dtype)
        dw3_ref[...] = a3[:].astype(dw3_ref.dtype)
        dw2_ref[...] = a2[:].astype(dw2_ref.dtype)


def _bwd(interpret, res, do):
    x, w1, w3, w2 = res
    R, d = x.shape
    f = w1.shape[1]
    br, bf = _plan(R, d, f)
    mem = {"memory_space": pltpu.VMEM}
    in_specs = [
        pl.BlockSpec((br, d), lambda i, j: (i, _i32(0)), **mem),
        pl.BlockSpec((d, bf), lambda i, j: (_i32(0), j), **mem),
        pl.BlockSpec((d, bf), lambda i, j: (_i32(0), j), **mem),
        pl.BlockSpec((bf, d), lambda i, j: (j, _i32(0)), **mem),
        pl.BlockSpec((br, d), lambda i, j: (i, _i32(0)), **mem),
    ]
    inputs = [x, w1, w3, w2, do]
    for spec, arr in zip(in_specs, inputs):
        _assert_mosaic_tileable(spec.block_shape, arr.shape, "ffn dx input")
    count_launch()
    dx = pl.pallas_call(
        _dx_kernel,
        name="fused_ffn_dx",
        grid=(R // br, f // bf),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, d), lambda i, j: (i, _i32(0)), **mem),
        out_shape=jax.ShapeDtypeStruct((R, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((br, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*inputs)

    # dw grid transposes the walk: d_ff block j outermost (each owns its
    # dw1/dw3 column block and dw2 row block), row blocks accumulated
    # innermost through the scratch
    dw_in_specs = [
        pl.BlockSpec((br, d), lambda j, i: (i, _i32(0)), **mem),
        pl.BlockSpec((d, bf), lambda j, i: (_i32(0), j), **mem),
        pl.BlockSpec((d, bf), lambda j, i: (_i32(0), j), **mem),
        pl.BlockSpec((bf, d), lambda j, i: (j, _i32(0)), **mem),
        pl.BlockSpec((br, d), lambda j, i: (i, _i32(0)), **mem),
    ]
    dw_out_specs = [
        pl.BlockSpec((d, bf), lambda j, i: (_i32(0), j), **mem),
        pl.BlockSpec((d, bf), lambda j, i: (_i32(0), j), **mem),
        pl.BlockSpec((bf, d), lambda j, i: (j, _i32(0)), **mem),
    ]
    dw_out_shape = [
        jax.ShapeDtypeStruct((d, f), w1.dtype),
        jax.ShapeDtypeStruct((d, f), w3.dtype),
        jax.ShapeDtypeStruct((f, d), w2.dtype),
    ]
    for spec, arr in zip(dw_in_specs, inputs):
        _assert_mosaic_tileable(spec.block_shape, arr.shape, "ffn dw input")
    for spec, sds in zip(dw_out_specs, dw_out_shape):
        _assert_mosaic_tileable(spec.block_shape, sds.shape, "ffn dw output")
    count_launch()
    dw1, dw3, dw2 = pl.pallas_call(
        _dw_kernel,
        name="fused_ffn_dw",
        grid=(f // bf, R // br),
        in_specs=dw_in_specs,
        out_specs=dw_out_specs,
        out_shape=dw_out_shape,
        scratch_shapes=[
            pltpu.VMEM((d, bf), jnp.float32),
            pltpu.VMEM((d, bf), jnp.float32),
            pltpu.VMEM((bf, d), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*inputs)
    return dx, dw1, dw3, dw2


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _ffn(x, w1, w3, w2, interpret):
    return _fwd(x, w1, w3, w2, interpret)


def _ffn_fwd(x, w1, w3, w2, interpret):
    o = _fwd(x, w1, w3, w2, interpret)
    return o, (x, w1, w3, w2)


_ffn.defvjp(_ffn_fwd, _bwd)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def _flatten_rows(x):
    lead, d = x.shape[:-1], x.shape[-1]
    return x.reshape(math.prod(lead) if lead else 1, d), lead, d


def fused_ffn(x, w1, w3, w2, interpret: Optional[bool] = None):
    """One-launch SwiGLU FFN: `silu(x @ w1) * (x @ w3) @ w2`.

    x [..., d]; w1/w3 [d, d_ff]; w2 [d_ff, d] → [..., d] in x.dtype.
    Differentiable (custom_vjp; bwd = 2 Pallas launches). `interpret`
    forces the Pallas interpreter (CPU testing); default: interpret on
    non-TPU backends.
    """
    x2, lead, d = _flatten_rows(x)
    f = w1.shape[1]
    if w1.shape != (d, f) or w3.shape != (d, f) or w2.shape != (f, d):
        raise ValueError(f"FFN weight shapes w1={w1.shape} w3={w3.shape} "
                         f"w2={w2.shape} do not match hidden d={d}")
    if not supported(x2.shape[0], d, f):
        raise ValueError(f"unsupported FFN geometry rows={x2.shape[0]} "
                         f"d={d} d_ff={f}; use the stock XLA path")
    if interpret is None:
        interpret = not available()
    o = _ffn(x2, w1, w3, w2, bool(interpret))
    return o.reshape(*lead, d)


def fused_ffn_w8(x, w1_q, w1_s, w3_q, w3_s, w2_q, w2_s,
                 interpret: Optional[bool] = None):
    """Weight-only int8 SwiGLU FFN, dequantized in-register (fwd only —
    the serving path; quantized weights are never trained).

    w*_q int8 from `quantize_llama_params`; w1_s/w3_s [1, d_ff] and
    w2_s [1, d] per-out-channel absmax scales (divided by 127 here, the
    stock `matmul_param` dequant, so interpret-mode outputs are
    bit-identical to the stock w8 path).
    """
    x2, lead, d = _flatten_rows(x)
    f = w1_q.shape[1]
    R = x2.shape[0]
    if not supported(R, d, f):
        raise ValueError(f"unsupported FFN geometry rows={R} d={d} "
                         f"d_ff={f}; use the stock XLA path")
    if interpret is None:
        interpret = not available()
    br, bf = _plan(R, d, f)
    s1 = (w1_s.reshape(1, f) / _QMAX).astype(jnp.float32)
    s3 = (w3_s.reshape(1, f) / _QMAX).astype(jnp.float32)
    s2 = (w2_s.reshape(1, d) / _QMAX).astype(jnp.float32)
    mem = {"memory_space": pltpu.VMEM}
    in_specs = [
        pl.BlockSpec((br, d), lambda i, j: (i, _i32(0)), **mem),
        pl.BlockSpec((d, bf), lambda i, j: (_i32(0), j), **mem),
        pl.BlockSpec((1, bf), lambda i, j: (_i32(0), j), **mem),
        pl.BlockSpec((d, bf), lambda i, j: (_i32(0), j), **mem),
        pl.BlockSpec((1, bf), lambda i, j: (_i32(0), j), **mem),
        pl.BlockSpec((bf, d), lambda i, j: (j, _i32(0)), **mem),
        pl.BlockSpec((1, d), lambda i, j: (_i32(0), _i32(0)), **mem),
    ]
    inputs = [x2, w1_q, s1, w3_q, s3, w2_q, s2]
    for spec, arr in zip(in_specs, inputs):
        _assert_mosaic_tileable(spec.block_shape, arr.shape, "ffn w8 input")
    count_launch()
    o = pl.pallas_call(
        _fwd_w8_kernel,
        name="fused_ffn_w8_fwd",
        grid=(R // br, f // bf),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, d), lambda i, j: (i, _i32(0)), **mem),
        out_shape=jax.ShapeDtypeStruct((R, d), x2.dtype),
        scratch_shapes=[pltpu.VMEM((br, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*inputs)
    return o.reshape(*lead, d)


def apply_ffn(h, lp, interpret: Optional[bool] = None):
    """Dispatch a (possibly quantized) llama block's FFN leaves through
    the matching fused variant. Callers gate with `params_kind(lp)` +
    `supported(...)` first; unsupported quant layouts raise."""
    kind = params_kind(lp)
    if kind == "fp":
        return fused_ffn(h, lp["w1"], lp["w3"], lp["w2"],
                         interpret=interpret)
    if kind == "w8":
        return fused_ffn_w8(h, lp["w1_q"], lp["w1_s"], lp["w3_q"],
                            lp["w3_s"], lp["w2_q"], lp["w2_s"],
                            interpret=interpret)
    raise ValueError("fused FFN serves fp or weight-only int8 leaves; "
                     "gate with params_kind(lp) before calling")


# ---------------------------------------------------------------------------
# GEMM/GLU epilogue kernels — the incubate fused-op surface
# (fused_bias_act gated variants, gemm_epilogue) routes here when
# FLAGS_pallas_ffn is on, so the reference's fused ops actually fuse on TPU
# ---------------------------------------------------------------------------

_EPI_ACTS = {
    "none": lambda t: t,
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "sigmoid": jax.nn.sigmoid,
}


def epilogue_supported(m: int, k: int, n: int, activation: str) -> bool:
    """Static gate for `fused_gemm_epilogue`: activation in the fused
    set and an (m, n) tiling that keeps the whole k dim in VMEM."""
    if activation not in _EPI_ACTS:
        return False
    if m < 1 or k < 8 or n < 8:
        return False
    bm = m if m <= 512 else next(
        (b for b in _BLOCK_CANDIDATES if m % b == 0), None)
    bn = n if n <= 512 else next(
        (b for b in _F_TILES if n % b == 0), None)
    if bm is None or bn is None:
        return False
    return 4 * (bm * k + k * bn + 2 * bm * bn) <= _EPILOGUE_BUDGET


def _epilogue_kernel(x_ref, y_ref, b_ref, o_ref, *, act: str,
                     has_bias: bool):
    out = jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), y_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    if has_bias:
        out = out + b_ref[...].astype(jnp.float32)
    o_ref[...] = _EPI_ACTS[act](out).astype(o_ref.dtype)


def fused_gemm_epilogue(x, y, bias=None, activation: str = "none",
                        interpret: Optional[bool] = None):
    """`act(x @ y + bias)` in one launch — the cublasLt-epilogue analog.
    x [m, k], y [k, n], bias [n] or None."""
    m, k = x.shape
    n = y.shape[1]
    if not epilogue_supported(m, k, n, activation):
        raise ValueError(f"unsupported epilogue geometry m={m} k={k} "
                         f"n={n} act={activation!r}")
    if interpret is None:
        interpret = not available()
    bm = m if m <= 512 else next(b for b in _BLOCK_CANDIDATES if m % b == 0)
    bn = n if n <= 512 else next(b for b in _F_TILES if n % b == 0)
    has_bias = bias is not None
    mem = {"memory_space": pltpu.VMEM}
    in_specs = [
        pl.BlockSpec((bm, k), lambda i, j: (i, _i32(0)), **mem),
        pl.BlockSpec((k, bn), lambda i, j: (_i32(0), j), **mem),
    ]
    inputs = [x, y]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (_i32(0), j),
                                     **mem))
        inputs.append(jnp.reshape(bias, (1, n)))
    else:
        # dummy operand keeps the kernel signature static
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (_i32(0), j),
                                     **mem))
        inputs.append(jnp.zeros((1, n), x.dtype))
    for spec, arr in zip(in_specs, inputs):
        _assert_mosaic_tileable(spec.block_shape, arr.shape, "epilogue input")
    count_launch()
    return pl.pallas_call(
        functools.partial(_epilogue_kernel, act=activation,
                          has_bias=has_bias),
        name="fused_gemm_epilogue",
        grid=(m // bm, n // bn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j), **mem),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(*inputs)


def _glu_kernel(u_ref, v_ref, o_ref, *, act: str):
    u = u_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    o_ref[...] = (_EPI_ACTS[act](u) * v).astype(o_ref.dtype)


def fused_glu(u, v, act: str = "silu",
              interpret: Optional[bool] = None):
    """Gated-activation epilogue `act(u) * v` in one launch (the
    swiglu/geglu half of fused_bias_act). u, v [rows, f]."""
    u2, lead, f = _flatten_rows(u)
    v2 = v.reshape(u2.shape)
    R = u2.shape[0]
    br = R if R <= 512 else next(
        (b for b in _BLOCK_CANDIDATES if R % b == 0), None)
    if br is None or act not in _EPI_ACTS or f < 8:
        raise ValueError(f"unsupported glu geometry rows={R} f={f} "
                         f"act={act!r}")
    if interpret is None:
        interpret = not available()
    mem = {"memory_space": pltpu.VMEM}
    spec = pl.BlockSpec((br, f), lambda i: (i, _i32(0)), **mem)
    _assert_mosaic_tileable(spec.block_shape, u2.shape, "glu input")
    count_launch()
    o = pl.pallas_call(
        functools.partial(_glu_kernel, act=act),
        name="fused_glu",
        grid=(R // br,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((R, f), u2.dtype),
        interpret=interpret,
    )(u2, v2)
    return o.reshape(*lead, f)


def glu_supported(rows: int, f: int, act: str) -> bool:
    if act not in _EPI_ACTS or f < 8 or rows < 1:
        return False
    return rows <= 512 or any(rows % b == 0 for b in _BLOCK_CANDIDATES)
