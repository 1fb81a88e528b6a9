"""Flash attention for TPU (Pallas).

Reference parity target: the fused flash-attention path
(`paddle/phi/kernels/gpu/flash_attn_kernel.h:1` wrapping third_party/flashattn;
SURVEY.md §5 long-context, §7 M8). This is NOT a port of the CUDA kernel — it
is the standard online-softmax tiling written for the TPU memory hierarchy:

- grid (batch, q_head, q_block, kv_block) with the kv dimension innermost, so
  the (m, l, acc) running statistics live in VMEM scratch across kv steps;
- blocks sized so q/k/v tiles + the p = exp(s) intermediate stay well inside
  VMEM, with the MXU doing the two matmuls per tile in f32 accumulation;
- causal skipping via predicated iterations (`pl.when`): blocks strictly above
  the diagonal are never computed;
- a static sliding `window` W (position i sees j iff i - W < j <= i): the
  kv dimension of the grid shrinks to the few blocks a q block can see, and
  the index maps start it at the first of them, so blocks wholly behind the
  window are neither fetched nor multiplied, forward and backward (the
  dk/dv kernel walks the q blocks that can see a kv block the same way);
- GQA handled with BlockSpec index maps (q head h reads kv head h // group) —
  no materialized jnp.repeat of K/V;
- backward = recomputation kernels (dq; dk/dv) from the saved logsumexp, the
  flash-attention-2 formulation: ds = p * (dp - delta), delta = rowsum(dO*O).

Layout contract: q [B, T, H, hd], k/v [B, S, KV, hd] (the model's natural
layout); kernels run in [B, H, T, hd] — the transposes at the boundary are
fused by XLA into the surrounding projections.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# All scalar constants entering kernel bodies must be concrete np.float32:
# under jax_enable_x64 a bare python float is a weak f64, and the resulting
# f64->f32 convert inside the kernel fails Mosaic legalization (tpu.truncf).
NEG_INF = np.float32(-1e30)


def _i32(x):
    """Index-map constants must match the int32 grid indices (a python int
    promotes to int64 under jax_enable_x64, and jnp floor-divide's signed
    decomposition does not lower through Mosaic — use lax.div on int32)."""
    return np.int32(x)

# lse/delta are carried as [B, H, T, LANES] with the value broadcast across a
# small trailing lane dim. Mosaic requires the last two dims of every block to
# be divisible by the (8, 128) native tile or EQUAL the array dims; a rank-3
# [B, H, T] block (1, 1, bq) puts a size-1 second-minor dim against H and
# fails lowering on real TPU (round 2's failure). With the trailing dim,
# the block's last dim equals the array dim (legal for any LANES) and the
# second-minor bq is 8-divisible. LANES=8 keeps the residual small (vs the
# 128-lane variant of jax's reference kernel, 16x the HBM for the same math).
LANES = 8

# Segment-id carrier layouts (varlen/unpadded attention): q ids are
# lane-broadcast [B, T, SEG_LANES] so a [bq, SEG_LANES] tile can be jnp.tiled
# across the kv lane dim; kv ids are sublane-broadcast [B, SEG_SUBLANES, S] so
# a [1, bk] row slices out legally. Same layouts as jax's reference TPU flash
# kernel (pallas/ops/tpu/flash_attention.py NUM_LANES/NUM_SUBLANES).
SEG_LANES = 128
SEG_SUBLANES = 8


def _assert_mosaic_tileable(block_shape, array_shape, what: str) -> None:
    """Static mirror of Mosaic's block-mapping rule so CPU CI catches illegal
    BlockSpecs without TPU hardware (interpret=True skips the real check)."""
    if len(block_shape) < 2:
        return
    b2, b1 = block_shape[-2], block_shape[-1]
    a2, a1 = array_shape[-2], array_shape[-1]
    if not (b1 % 128 == 0 or b1 == a1) or not (b2 % 8 == 0 or b2 == a2):
        raise ValueError(
            f"flash attention {what}: block {tuple(block_shape)} vs array "
            f"{tuple(array_shape)} violates Mosaic's (8, 128) tiling rule — "
            "the last two block dims must be divisible by (8, 128) or equal "
            "the array dims")


def available() -> bool:
    """True when the Pallas TPU kernel path can run on the default backend."""
    return jax.default_backend() == "tpu"


# Trace-time launch accounting, shared by every kernel wrapper in this
# package: each wrapper bumps the counter once per pl.pallas_call it emits.
# The wrappers only run while an executable is being TRACED, so the delta
# across a fresh jit trace equals the number of Pallas launches that
# executable performs per call — which is how the serving engine pins its
# per-tick launch budget (chip_smoke.py asserts the fused decode tick's 4).
_TRACE_LAUNCHES = [0]


def count_launch(n: int = 1) -> None:
    _TRACE_LAUNCHES[0] += n


def trace_launches() -> int:
    """Monotonic count of Pallas launches traced so far in this process."""
    return _TRACE_LAUNCHES[0]


# Tunable caps, measured on a v5e-class chip (B=16 T=2048 H=12 hd=128,
# fwd+bwd, interleaved steady-state): 512 -> 22.6ms, 1024 -> 24.7ms,
# 256 -> 30.5ms. 512 amortizes the MXU well while p = exp(s) (512x512 f32,
# 1MB) and the kv tiles stay comfortably inside VMEM.
_BLOCK_CANDIDATES = (512, 256, 128, 64, 32, 16, 8)


def _pick_block(n: int) -> Optional[int]:
    for b in _BLOCK_CANDIDATES:
        if n % b == 0 and b <= n:
            return b
    return None


def supported(q_shape, k_shape) -> bool:
    """Static-shape gate: fall back to the XLA path when tiling doesn't fit."""
    B, T, H, hd = q_shape
    S, KV = k_shape[1], k_shape[2]
    if H % KV != 0:
        return False
    if _pick_block(T) is None or _pick_block(S) is None:
        return False
    return hd >= 8


# ---------------------------------------------------------------------------
# The window's walk: which blocks of the other axis a block can see
# ---------------------------------------------------------------------------

def _kv_span(i, block_q: int, block_k: int, window: int):
    """(first, last) kv block that q block `i` sees under a causal window:
    its rows are i*bq .. i*bq + bq - 1 and row r sees r - W + 1 .. r. On
    int32 values: a grid index inside a kernel or an index map, an np.int32
    in a test (`_kv_steps` is the same on python ints)."""
    first = jax.lax.div(jnp.maximum(i * _i32(block_q) - _i32(window - 1),
                                    _i32(0)), _i32(block_k))
    last = jax.lax.div(i * _i32(block_q) + _i32(block_q - 1), _i32(block_k))
    return first, last


def _q_span(jk, block_q: int, block_k: int, window: int, nq: int):
    """(first, last) q block that sees kv block `jk` under a causal window:
    column c is seen by rows c .. c + W - 1."""
    first = jax.lax.div(jk * _i32(block_k), _i32(block_q))
    last = jnp.minimum(
        jax.lax.div(jk * _i32(block_k) + _i32(block_k + window - 2),
                    _i32(block_q)), _i32(nq - 1))
    return first, last


def _kv_steps(nq: int, block_q: int, block_k: int, window: int) -> int:
    """The most kv blocks a q block sees (`_kv_span` on python ints): the
    kv dimension of the window's grid."""
    return max((i * block_q + block_q - 1) // block_k
               - max(i * block_q - (window - 1), 0) // block_k + 1
               for i in range(nq))


def _q_steps(nk: int, block_q: int, block_k: int, window: int,
             nq: int) -> int:
    """The most q blocks that see a kv block (`_q_span` on python ints)."""
    return max(min((jk * block_k + block_k + window - 2) // block_q, nq - 1)
               - (jk * block_k) // block_q + 1 for jk in range(nk))


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _seg_mask(qs_ref, ks_ref, block_k: int):
    """[Bq, Bk] same-segment mask from the lane-/sublane-broadcast carriers.
    Explicit jnp.tile of both operands (not a two-sided broadcast) is the
    form Mosaic legalizes; requires block_k % SEG_LANES == 0."""
    qs = jnp.tile(qs_ref[0], (1, block_k // SEG_LANES))   # [Bq, Bk]
    ks = ks_ref[0, :1]                                    # [1, Bk]
    return qs == ks


def _fwd_kernel(*refs, sm_scale: float, causal: bool, block_q: int,
                block_k: int, has_seg: bool, window: int = 0):
    if has_seg:
        q_ref, k_ref, v_ref, qs_ref, ks_ref, o_ref, lse_ref, acc, m_sc, l_sc = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_sc, l_sc = refs
        qs_ref = ks_ref = None
    i, step = pl.program_id(2), pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(step == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc[:] = jnp.zeros_like(acc)

    # window: the grid's steps count from the first kv block q block i sees
    j = step + _kv_span(i, block_q, block_k, window)[0] if window else step
    # causal: kv block j is needed iff its first col <= last row of q block i
    needed = (not causal) or (j * block_k <= i * block_q + block_q - 1)

    @pl.when(needed)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)          # [Bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)          # [Bk, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [Bq, Bk]
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + i * block_q
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block_k
            seen = rows >= cols
            if window:
                seen = seen & (cols > rows - window)
            s = jnp.where(seen, s, NEG_INF)
        if has_seg:
            # With causal=True every row keeps its diagonal entry (a token is
            # always in its own segment), so no all-NEG_INF row can poison
            # the running max (exp(NEG_INF - NEG_INF) = 1 bug class).
            s = jnp.where(_seg_mask(qs_ref, ks_ref, block_k), s, NEG_INF)
        m_prev = m_sc[:, :1]                          # [Bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                        # [Bq, Bk]
        if window:
            # a row may see no key of an early block of its span (its own
            # comes last): exp(NEG_INF - NEG_INF) = 1 must not count
            p = jnp.where(seen, p, np.float32(0))
        alpha = jnp.exp(m_prev - m_new)               # [Bq, 1]
        l_sc[:] = l_sc[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)           # [Bk, hd]
        acc[:] = acc[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)

    @pl.when(step == nj - 1)
    def _():
        l = l_sc[:, :1]
        o_ref[0, 0] = (acc[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(m_sc[:, :1] + jnp.log(l),
                                         (block_q, LANES))


def _seg_carriers(q_seg, kv_seg):
    """[B, T] / [B, S] int32 → lane-broadcast [B, T, SEG_LANES] and
    sublane-broadcast [B, SEG_SUBLANES, S]."""
    qs = jnp.broadcast_to(q_seg.astype(jnp.int32)[:, :, None],
                          (*q_seg.shape, SEG_LANES))
    ks = jnp.broadcast_to(kv_seg.astype(jnp.int32)[:, None, :],
                          (kv_seg.shape[0], SEG_SUBLANES, kv_seg.shape[1]))
    return qs, ks


def _kv_index(block_q: int, block_k: int, window: int):
    """The kv block of grid step j for q block i: j itself, or under a
    window the j-th block of q block i's span, held at its last (a block
    index that does not change is not fetched again)."""
    if not window:
        return lambda i, j: j

    def at(i, j):
        first, last = _kv_span(i, block_q, block_k, window)
        return jnp.minimum(first + j, last)
    return at


def _fwd(q, k, v, sm_scale: float, causal: bool, interpret: bool,
         q_seg=None, kv_seg=None, window: int = 0):
    """q [B, H, T, hd]; k/v [B, KV, S, hd] →
    (o [B, H, T, hd], lse [B, H, T, LANES] lane-broadcast).
    q_seg/kv_seg: optional [B, T] / [B, S] int32 segment ids (varlen).
    window W > 0 (causal): row i sees columns i - W + 1 .. i."""
    B, H, T, hd = q.shape
    KV, S = k.shape[1], k.shape[2]
    G = H // KV
    bq, bk = _pick_block(T), _pick_block(S)
    has_seg = q_seg is not None
    if has_seg and bk % SEG_LANES != 0:
        raise ValueError(f"segment ids need block_k % {SEG_LANES} == 0; "
                         f"got block_k={bk} (S={S})")
    kv_at = _kv_index(bq, bk, window)
    grid = (B, H, T // bq,
            _kv_steps(T // bq, bq, bk, window) if window else S // bk)
    kernel = functools.partial(_fwd_kernel, sm_scale=np.float32(sm_scale), causal=causal,
                               block_q=bq, block_k=bk, has_seg=has_seg,
                               window=window)
    mem = {"memory_space": pltpu.VMEM}
    scratch = [
        pltpu.VMEM((bq, hd), jnp.float32),
        pltpu.VMEM((bq, 128), jnp.float32),
        pltpu.VMEM((bq, 128), jnp.float32),
    ]
    in_specs = [
        pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, _i32(0)), **mem),
        pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, jax.lax.div(h, _i32(G)), kv_at(i, j), _i32(0)), **mem),
        pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, jax.lax.div(h, _i32(G)), kv_at(i, j), _i32(0)), **mem),
    ]
    inputs = [q, k, v]
    if has_seg:
        qs, ks = _seg_carriers(q_seg, kv_seg)
        in_specs += [
            pl.BlockSpec((1, bq, SEG_LANES), lambda b, h, i, j: (b, i, _i32(0)), **mem),
            pl.BlockSpec((1, SEG_SUBLANES, bk), lambda b, h, i, j: (b, _i32(0), kv_at(i, j)), **mem),
        ]
        inputs += [qs, ks]
    out_specs = [
        pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, _i32(0)), **mem),
        pl.BlockSpec((1, 1, bq, LANES), lambda b, h, i, j: (b, h, i, _i32(0)), **mem),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, H, T, hd), q.dtype),
        jax.ShapeDtypeStruct((B, H, T, LANES), jnp.float32),
    ]
    for spec, arr in zip(in_specs, inputs):
        _assert_mosaic_tileable(spec.block_shape, arr.shape, "fwd input")
    for spec, sds in zip(out_specs, out_shape):
        _assert_mosaic_tileable(spec.block_shape, sds.shape, "fwd output")
    count_launch()
    o, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*inputs)
    return o, lse


# ---------------------------------------------------------------------------
# Backward kernels (flash-attention-2 recomputation form)
# ---------------------------------------------------------------------------

def _dq_kernel(*refs, sm_scale: float, causal: bool, block_q: int,
               block_k: int, has_seg: bool, window: int = 0):
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dq_ref, dq_acc) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc = refs
        qs_ref = ks_ref = None
    i, step = pl.program_id(2), pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(step == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    j = step + _kv_span(i, block_q, block_k, window)[0] if window else step
    needed = (not causal) or (j * block_k <= i * block_q + block_q - 1)

    @pl.when(needed)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]                    # [Bq, 1] (lanes equal)
        delta = delta_ref[0, 0][:, :1]                # [Bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + i * block_q
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block_k
            seen = rows >= cols
            if window:
                seen = seen & (cols > rows - window)
            s = jnp.where(seen, s, NEG_INF)
        if has_seg:
            s = jnp.where(_seg_mask(qs_ref, ks_ref, block_k), s, NEG_INF)
        p = jnp.exp(s - lse)                          # [Bq, Bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(step == nj - 1)
    def _():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(*refs, sm_scale: float, causal: bool, block_q: int,
                block_k: int, group: int, has_seg: bool, window: int = 0,
                num_q_blocks: int = 0):
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        qs_ref = ks_ref = None
    # grid: (B, KV, kv_block, g, q_block)
    jk = pl.program_id(2)
    g = pl.program_id(3)
    step = pl.program_id(4)
    nq = pl.num_programs(4)

    @pl.when((g == 0) & (step == 0))
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if window:
        # the grid's steps count from the first q block that sees kv block
        # jk, and stop counting at the last one that does
        first, last = _q_span(jk, block_q, block_k, window, num_q_blocks)
        iq = step + first
        needed = iq <= last
    else:
        iq = step
        # causal: q block iq contributes iff its last row >= kv block's first col
        needed = (not causal) or (iq * block_q + block_q - 1 >= jk * block_k)

    @pl.when(needed)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)           # [Bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)           # [Bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [Bq, Bk]
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + iq * block_q
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + jk * block_k
            seen = rows >= cols
            if window:
                seen = seen & (cols > rows - window)
            s = jnp.where(seen, s, NEG_INF)
        if has_seg:
            s = jnp.where(_seg_mask(qs_ref, ks_ref, block_k), s, NEG_INF)
        p = jnp.exp(s - lse)                          # [Bq, Bk]
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale              # [Bq, Bk]
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when((g == group - 1) & (step == nq - 1))
    def _():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(sm_scale, causal, interpret, window, res, do):
    q, k, v, o, lse, q_seg, kv_seg = res              # lse [B, H, T, LANES]
    B, H, T, hd = q.shape
    KV, S = k.shape[1], k.shape[2]
    G = H // KV
    bq, bk = _pick_block(T), _pick_block(S)
    has_seg = q_seg is not None
    kv_at = _kv_index(bq, bk, window)
    if window:
        def q_at(jk, iq):
            first, last = _q_span(jk, bq, bk, window, T // bq)
            return jnp.minimum(first + iq, last)
    else:
        q_at = lambda jk, iq: iq
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (B, H, T, LANES))
    mem = {"memory_space": pltpu.VMEM}

    dq_in_specs = [
        pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, _i32(0)), **mem),
        pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, jax.lax.div(h, _i32(G)), kv_at(i, j), _i32(0)), **mem),
        pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, jax.lax.div(h, _i32(G)), kv_at(i, j), _i32(0)), **mem),
        pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, _i32(0)), **mem),
        pl.BlockSpec((1, 1, bq, LANES), lambda b, h, i, j: (b, h, i, _i32(0)), **mem),
        pl.BlockSpec((1, 1, bq, LANES), lambda b, h, i, j: (b, h, i, _i32(0)), **mem),
    ]
    dq_inputs = [q, k, v, do, lse, delta]
    if has_seg:
        qs, ks = _seg_carriers(q_seg, kv_seg)
        dq_in_specs += [
            pl.BlockSpec((1, bq, SEG_LANES), lambda b, h, i, j: (b, i, _i32(0)), **mem),
            pl.BlockSpec((1, SEG_SUBLANES, bk), lambda b, h, i, j: (b, _i32(0), kv_at(i, j)), **mem),
        ]
        dq_inputs += [qs, ks]
    dq_out_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, _i32(0)),
                               **mem)
    for spec, arr in zip(dq_in_specs, dq_inputs):
        _assert_mosaic_tileable(spec.block_shape, arr.shape, "dq input")
    _assert_mosaic_tileable(dq_out_spec.block_shape, q.shape, "dq output")
    count_launch()
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=np.float32(sm_scale), causal=causal,
                          block_q=bq, block_k=bk, has_seg=has_seg,
                          window=window),
        name="flash_attention_dq",
        grid=(B, H, T // bq,
              _kv_steps(T // bq, bq, bk, window) if window else S // bk),
        in_specs=dq_in_specs,
        out_specs=dq_out_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret,
    )(*dq_inputs)

    dkv_in_specs = [
        pl.BlockSpec((1, 1, bq, hd),
                     lambda b, kv, jk, g, iq: (b, kv * G + g, q_at(jk, iq), _i32(0)), **mem),
        pl.BlockSpec((1, 1, bk, hd),
                     lambda b, kv, jk, g, iq: (b, kv, jk, _i32(0)), **mem),
        pl.BlockSpec((1, 1, bk, hd),
                     lambda b, kv, jk, g, iq: (b, kv, jk, _i32(0)), **mem),
        pl.BlockSpec((1, 1, bq, hd),
                     lambda b, kv, jk, g, iq: (b, kv * G + g, q_at(jk, iq), _i32(0)), **mem),
        pl.BlockSpec((1, 1, bq, LANES),
                     lambda b, kv, jk, g, iq: (b, kv * G + g, q_at(jk, iq), _i32(0)), **mem),
        pl.BlockSpec((1, 1, bq, LANES),
                     lambda b, kv, jk, g, iq: (b, kv * G + g, q_at(jk, iq), _i32(0)), **mem),
    ]
    dkv_inputs = [q, k, v, do, lse, delta]
    if has_seg:
        dkv_in_specs += [
            pl.BlockSpec((1, bq, SEG_LANES),
                         lambda b, kv, jk, g, iq: (b, q_at(jk, iq), _i32(0)), **mem),
            pl.BlockSpec((1, SEG_SUBLANES, bk),
                         lambda b, kv, jk, g, iq: (b, _i32(0), jk), **mem),
        ]
        dkv_inputs += [qs, ks]
    dkv_out_specs = [
        pl.BlockSpec((1, 1, bk, hd),
                     lambda b, kv, jk, g, iq: (b, kv, jk, _i32(0)), **mem),
        pl.BlockSpec((1, 1, bk, hd),
                     lambda b, kv, jk, g, iq: (b, kv, jk, _i32(0)), **mem),
    ]
    for spec, arr in zip(dkv_in_specs, dkv_inputs):
        _assert_mosaic_tileable(spec.block_shape, arr.shape, "dkv input")
    for spec in dkv_out_specs:
        _assert_mosaic_tileable(spec.block_shape, k.shape, "dkv output")
    count_launch()
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=np.float32(sm_scale), causal=causal,
                          block_q=bq, block_k=bk, group=G, has_seg=has_seg,
                          window=window, num_q_blocks=T // bq),
        name="flash_attention_dkv",
        grid=(B, KV, S // bk, G,
              _q_steps(S // bk, bq, bk, window, T // bq) if window
              else T // bq),
        in_specs=dkv_in_specs,
        out_specs=dkv_out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, S, hd), k.dtype),
            jax.ShapeDtypeStruct((B, KV, S, hd), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, hd), jnp.float32),
            pltpu.VMEM((bk, hd), jnp.float32),
        ],
        interpret=interpret,
    )(*dkv_inputs)
    # segment-id inputs are int: no cotangents
    return dq, dk, dv, None, None


# ---------------------------------------------------------------------------
# Public API (custom_vjp over the BHTD kernels, BTHD at the boundary)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_bhtd_seg(q, k, v, q_seg, kv_seg, sm_scale, causal, interpret,
                    window=0):
    o, _ = _fwd(q, k, v, sm_scale, causal, interpret, q_seg, kv_seg, window)
    return o


def _flash_bhtd_seg_fwd(q, k, v, q_seg, kv_seg, sm_scale, causal, interpret,
                        window=0):
    o, lse = _fwd(q, k, v, sm_scale, causal, interpret, q_seg, kv_seg, window)
    return o, (q, k, v, o, lse, q_seg, kv_seg)


_flash_bhtd_seg.defvjp(_flash_bhtd_seg_fwd, _bwd)


def _flash_bhtd(q, k, v, sm_scale, causal, interpret):
    """Segment-free entry (kept: the train step and AOT smoke target it)."""
    return _flash_bhtd_seg(q, k, v, None, None, sm_scale, causal, interpret,
                           0)


def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    interpret: Optional[bool] = None,
                    q_segment_ids=None, kv_segment_ids=None,
                    window: int = 0):
    """Fused attention. q [B, T, H, hd], k/v [B, S, KV, hd] → [B, T, H, hd].

    GQA when H > KV (H % KV == 0). `interpret` forces the Pallas interpreter
    (CPU testing); default: interpret on non-TPU backends.

    q_segment_ids/kv_segment_ids [B, T] / [B, S] int32 restrict attention to
    same-segment pairs (varlen/unpadded packing; the flash_attn_unpadded op).
    Rows must be self-aligned (token t's kv t shares its segment) so every
    row keeps >= 1 valid key — guaranteed for packed self-attention.

    window W > 0 (static, causal only): position i sees j iff
    i - W < j <= i, W keys with the query's own among them. Key blocks
    wholly behind the window are neither fetched nor multiplied, forward and
    backward; a window of T or more is the causal mask and runs as one.
    """
    B, T, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    if causal and T != S:
        raise ValueError(f"causal flash attention needs T == S, got {T} vs {S}")
    if not supported(q.shape, k.shape):
        raise ValueError(f"unsupported shapes q={q.shape} k={k.shape}; "
                         "use the XLA attention path")
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("pass both q_segment_ids and kv_segment_ids or neither")
    if window and not causal:
        raise ValueError("a window is a causal window: window > 0 needs "
                         "causal=True")
    if window < 0:
        raise ValueError(f"window={window}")
    if window >= T:
        window = 0                   # every earlier key is inside it
    if sm_scale is None:
        sm_scale = 1.0 / (hd ** 0.5)
    if interpret is None:
        interpret = not available()
    qt = jnp.swapaxes(q, 1, 2)       # [B, H, T, hd]
    kt = jnp.swapaxes(k, 1, 2)       # [B, KV, S, hd]
    vt = jnp.swapaxes(v, 1, 2)
    o = _flash_bhtd_seg(qt, kt, vt, q_segment_ids, kv_segment_ids,
                        float(sm_scale), bool(causal), bool(interpret),
                        int(window))
    return jnp.swapaxes(o, 1, 2)


def supports_segments(k_shape) -> bool:
    """Varlen needs block_k % SEG_LANES == 0 (the q-seg lane tile)."""
    bk = _pick_block(k_shape[1])
    return bk is not None and bk % SEG_LANES == 0
