"""Paged-KV attention for TPU (Pallas).

Reference parity target: the paged attention read inside
`block_multihead_attention_kernel.cu` (SURVEY.md §5 serving). The stock
XLA path in ops/kernels/serving_attention.py materializes every
sequence's pages into a dense `[B, max_kv, KV, hd]` gather before the
score dot — on a paged pool that is the single biggest avoidable HBM
round-trip in the decode loop. This kernel never materializes the
gather: the per-sequence block table is *scalar-prefetched* into SMEM
(`pltpu.PrefetchScalarGridSpec`) and the K/V page BlockSpec index maps
read it directly, so each grid step DMAs exactly one `[block_size, hd]`
page from wherever it lives in the pool.

Design:

- grid `(B, KV, P)` with the page axis innermost; online-softmax
  running statistics (m, l, acc) live in VMEM scratch across the page
  walk (the flash_attention.py formulation over pages instead of dense
  kv blocks);
- ragged mixed prefill+decode in ONE launch: the packed q tokens are
  regrouped per sequence into `[B, KV, max_q * G, hd]` rows (GQA group
  g and chunk offset t fold into one MXU axis, row r = t*G + g) and the
  chunked-prefill metadata the scheduler already produces
  (`seq_lens_decoder` past + `seq_lens_this_time`) is prefetched so the
  kernel masks `kv_pos <= past + t` per row — in-chunk causality holds
  because the pages already contain this step's tokens (the append
  happens before the read, same as the stock path);
- pages past a sequence's live length are *skipped* (`pl.when` on the
  prefetched lengths), so a 4-page sequence in a 64-page table costs 4
  iterations, not 64;
- int8 pages dequantize IN-REGISTER: the per-page scale planes
  `[num_blocks, KV]` ride the same prefetched table through (8, KV)
  SMEM blocks; the k scale is constant over hd so it factors out of the
  q·k dot and lands on the scores, the v scale lands on the probabilities —
  bit-identical placement to the stock path's folding, and no fp copy
  of the cache ever exists;
- `max_q=1` is the decode-specialized launch: rows collapse to the GQA
  group (`[B, KV, G, hd]`), zero padding waste on the steady-state hot
  path.

Layout contract: q rows are packed/unpacked by the caller
(block_multihead_attention_); caches stay in their pool layout
`[num_blocks, KV, block_size, hd]` — no transpose, no reshape, no copy.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (NEG_INF, _assert_mosaic_tileable, _i32,
                              available, count_launch)

__all__ = ["paged_attention", "available", "supported"]

# m/l carriers use the same [rows, LANES] lane-broadcast trick as
# flash_attention.py (a [rows, 1] scratch column is not a legal vreg shape
# on all Mosaic versions; 128 lanes is the native tile)
_STAT_LANES = 128
# rows of the per-page scale planes brought into SMEM with each page
_SCALE_ROWS = 8


def supported(num_heads: int, num_kv_heads: int, head_dim: int,
              block_size: int) -> bool:
    """Static gate: can this head/page geometry run through the kernel?
    (availability — is there TPU hardware — is `available()`; interpret
    mode ignores it and is how CPU CI exercises the kernel bit-for-bit)."""
    if num_kv_heads <= 0 or num_heads % num_kv_heads != 0:
        return False
    # blocks equal the array dims on the last two axes, so any
    # (block_size, head_dim) is Mosaic-legal; keep the same floor as the
    # flash kernel so degenerate head dims fall back loudly instead of
    # wasting the MXU
    return head_dim >= 8 and block_size >= 1


def _kernel(tables_ref, past_ref, this_ref, *refs, sm_scale: float,
            block_size: int, group: int, has_quant: bool):
    """One (sequence b, kv head, page p) grid step.

    refs: q, k_page, v_page, [k_scale, v_scale,] o, acc, m, l.
    q rows pack chunk offset t and GQA head g as r = t*G + g; absolute
    position of row r is past[b] + t. The page walk keeps flash-style
    (m, l, acc) online-softmax state in scratch across the innermost
    grid axis."""
    if has_quant:
        q_ref, k_ref, v_ref, kdq_ref, vdq_ref, o_ref, acc, m_sc, l_sc = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc, m_sc, l_sc = refs
        kdq_ref = vdq_ref = None
    b = pl.program_id(0)
    p = pl.program_id(2)
    n_pages = pl.num_programs(2)
    if has_quant:
        # this page's row inside the [_SCALE_ROWS, KV] SMEM scale block
        scale_row = jax.lax.rem(tables_ref[b, p], _i32(_SCALE_ROWS))
        kv_head = pl.program_id(1)

    @pl.when(p == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc[:] = jnp.zeros_like(acc)

    past = past_ref[b]
    this = this_ref[b]
    # pages hold positions [p*bs, (p+1)*bs); only those below the live
    # length past+this can ever be unmasked — skip the rest entirely
    needed = p * block_size < past + this

    @pl.when(needed)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)           # [rows, hd]
        k = k_ref[0, 0].astype(jnp.float32)           # [bs, hd] (int8 pages
        s = jax.lax.dot_general(                      # dequant in-register)
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [rows, bs]
        if has_quant:
            # per-page k scale is constant over hd: it factors out of the
            # dot, so one scalar multiply dequantizes the whole score tile
            s = s * (sm_scale * kdq_ref[scale_row, kv_head])
        else:
            s = s * sm_scale
        rows_i = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        t = jax.lax.div(rows_i, _i32(group))          # chunk offset of row
        kv_abs = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                  + p * _i32(block_size))
        ok = (kv_abs <= past + t) & (t < this)        # causal + live rows
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_sc[:, :1]                          # [rows, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        prob = jnp.exp(s - m_new)                     # [rows, bs]
        prob = jnp.where(ok, prob, 0.0)               # dead rows stay 0
        alpha = jnp.exp(m_prev - m_new)               # [rows, 1]
        l_sc[:] = l_sc[:] * alpha + jnp.sum(prob, axis=-1, keepdims=True)
        if has_quant:
            # v scale likewise factors out: fold into the probabilities
            prob = prob * vdq_ref[scale_row, kv_head]
        v = v_ref[0, 0].astype(jnp.float32)           # [bs, hd]
        acc[:] = acc[:] * alpha + jax.lax.dot_general(
            prob, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)

    @pl.when(p == n_pages - 1)
    def _():
        # rows whose every position is masked (pad rows, idle slots) have
        # l == 0; divide by 1 so they emit 0, not NaN — the caller zeroes
        # invalid token rows anyway
        l = l_sc[:, :1]
        o_ref[0, 0] = (acc[:] / jnp.where(l == 0.0, 1.0, l)
                       ).astype(o_ref.dtype)


def paged_attention(q_rows, key_cache, value_cache, block_tables,
                    seq_lens_decoder, seq_lens_this_time, group: int,
                    sm_scale: float, k_dequant=None, v_dequant=None,
                    interpret: Optional[bool] = None):
    """Attention over paged caches, block table walked in-kernel.

    q_rows [B, KV, max_q * G, hd] — per-sequence packed rows (row
    r = t * G + g: chunk offset t, GQA head g; the caller packs/unpacks
    against cu_seqlens); `group` is G = H // KV (static); key_cache /
    value_cache [num_blocks, KV, block_size, hd] ALREADY containing this
    step's appended tokens; block_tables [B, max_blocks] int32 (−1 =
    unassigned; never dereferenced thanks to the length skip, but
    clamped defensively); seq_lens_decoder / seq_lens_this_time [B]
    int32 past/this lengths (the scheduler's chunked-prefill metadata).

    k_dequant / v_dequant [num_blocks, KV] f32 enable the int8-page
    mode (pass both or neither). Returns [B, KV, max_q * G, hd] in
    q_rows.dtype; pad rows come back 0.
    """
    if (k_dequant is None) != (v_dequant is None):
        raise ValueError("pass both k_dequant and v_dequant or neither")
    has_quant = k_dequant is not None
    B, KV, rows, hd = q_rows.shape
    if rows <= 0 or group <= 0 or rows % group != 0:
        raise ValueError(f"q_rows rows={rows} must be a positive multiple "
                         f"of group={group}")
    num_blocks, KVc, bs, hdc = key_cache.shape
    if (KVc, hdc) != (KV, hd):
        raise ValueError(f"cache [nb, KV, bs, hd]={key_cache.shape} does "
                         f"not match q rows [B, KV, rows, hd]={q_rows.shape}")
    max_blocks = block_tables.shape[1]
    if interpret is None:
        interpret = not available()

    tables = jnp.maximum(block_tables.astype(jnp.int32), 0)   # [B, mb]
    past = seq_lens_decoder.reshape(-1).astype(jnp.int32)     # [B]
    this = seq_lens_this_time.reshape(-1).astype(jnp.int32)   # [B]

    mem = {"memory_space": pltpu.VMEM}
    in_specs = [
        pl.BlockSpec((1, 1, rows, hd),
                     lambda b, kv, p, tr, pr, th: (b, kv, _i32(0), _i32(0)),
                     **mem),
        pl.BlockSpec((1, 1, bs, hd),
                     lambda b, kv, p, tr, pr, th: (tr[b, p], kv, _i32(0),
                                                   _i32(0)), **mem),
        pl.BlockSpec((1, 1, bs, hd),
                     lambda b, kv, p, tr, pr, th: (tr[b, p], kv, _i32(0),
                                                   _i32(0)), **mem),
    ]
    inputs = [q_rows, key_cache, value_cache]
    if has_quant:
        # a (1, 1) block of the [num_blocks, KV] plane breaks Mosaic's
        # (8, 128) rule; (_SCALE_ROWS, KV) is legal (8 rows, whole last
        # dim) and the kernel picks its scalar out of the block
        scale_spec = pl.BlockSpec(
            (_SCALE_ROWS, KV),
            lambda b, kv, p, tr, pr, th: (
                jax.lax.div(tr[b, p], _i32(_SCALE_ROWS)), _i32(0)),
            memory_space=pltpu.SMEM)
        in_specs += [scale_spec, scale_spec]
        # whole SMEM blocks only: pad a pool that is no multiple of 8
        pad = (0, -num_blocks % _SCALE_ROWS), (0, 0)
        inputs += [jnp.pad(k_dequant.astype(jnp.float32), pad),
                   jnp.pad(v_dequant.astype(jnp.float32), pad)]
    out_spec = pl.BlockSpec(
        (1, 1, rows, hd),
        lambda b, kv, p, tr, pr, th: (b, kv, _i32(0), _i32(0)), **mem)
    for spec, arr in zip(in_specs, inputs):
        _assert_mosaic_tileable(spec.block_shape, arr.shape, "paged input")
    _assert_mosaic_tileable(out_spec.block_shape, q_rows.shape,
                            "paged output")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, max_blocks),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((rows, _STAT_LANES), jnp.float32),
            pltpu.VMEM((rows, _STAT_LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel, sm_scale=np.float32(sm_scale), block_size=int(bs),
        group=int(group), has_quant=has_quant)
    count_launch()
    return pl.pallas_call(
        kernel,
        name="paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, rows, hd), q_rows.dtype),
        interpret=interpret,
    )(tables, past, this, *inputs)
