"""Paged-KV attention for TPU (Pallas).

Reference parity target: the paged attention read inside
`block_multihead_attention_kernel.cu` (SURVEY.md §5 serving). The stock
XLA path in ops/kernels/serving_attention.py materializes every
sequence's pages into a dense `[B, max_kv, KV, hd]` gather before the
score dot — on a paged pool that is the single biggest avoidable HBM
round-trip in the decode loop. These kernels never materialize the
gather: the per-sequence block table is *scalar-prefetched* into SMEM
(`pltpu.PrefetchScalarGridSpec`) and the pages are read through it from
wherever they lie in the pool.

Design — two walks over one pool, chosen by the static shapes of the
launch (one query token a sequence, `rows == group`, at a head_dim whose
whole pages Mosaic copies: `decode_walk`), because their needs conflict:
a decode launch has 1–4 query rows a head and is bound by the count and
size of its fetches; a mixed launch has up to token_budget rows a head
and is bound by its products.

The mixed walk (`_kernel`, ragged prefill + decode in ONE launch):

- grid `(B, KV, P)` with the page axis innermost, one `[block_size, hd]`
  page of one KV head a step through a BlockSpec index map; online-softmax
  running statistics (m, l, acc) live in VMEM scratch across the page
  walk (the flash_attention.py formulation over pages instead of dense
  kv blocks);
- the packed q tokens are regrouped per sequence into
  `[B, KV, max_q * G, hd]` rows (GQA group g and chunk offset t fold into
  one MXU axis, row r = t*G + g) and the chunked-prefill metadata the
  scheduler already produces (`seq_lens_decoder` past +
  `seq_lens_this_time`) is prefetched so the kernel masks
  `kv_pos <= past + t` per row — in-chunk causality holds because the
  pages already contain this step's tokens (the append happens before the
  read, same as the stock path);
- pages past a sequence's live length are *skipped* (`pl.when` on the
  prefetched lengths): no fetch, no product, but still a grid step each;
- int8 pages dequantize IN-REGISTER: the per-page scale planes
  `[num_blocks, KV]` ride the same prefetched table through (8, KV)
  SMEM blocks; the k scale is constant over hd so it factors out of the
  q·k dot and lands on the scores, the v scale lands on the probabilities —
  bit-identical placement to the stock path's folding, and no fp copy
  of the cache ever exists.

The decode walk (`_decode_kernel`, `max_q = 1`, rows `[B, KV, G, hd]`):

- grid `(B,)`, the pools left in HBM (`memory_space=pl.ANY`); a sequence
  walks only its own live key blocks in a `fori_loop` whose trip count is
  ceil((past + 1) / (P * block_size)), an idle slot none: a 17-page
  sequence in a 128-page table costs 3 iterations, not 128 grid steps a
  head;
- a fetch is a WHOLE page, `pool[layer, tables[b, p]]` = `[KV, block_size,
  hd]`, contiguous in the page-major pool, so one copy serves every KV
  head; a key block is P pages (`decode_pages_per_block`: about 128 key
  positions, from shapes and a VMEM budget alone) gathered by
  `pltpu.make_async_copy` into a double-buffered scratch, the next
  block's copies started before this block's products;
- per head the same online softmax, mask (`kv_pos <= past`), zero for an
  idle slot and in-register int8 dequantisation as the mixed walk, over a
  block of P * block_size keys; the dots keep their operand types (blocks
  cast to f32). An int8 page's `[KV]` scale row rides with the page: one
  more copy beside it, out of the plane padded to whole lanes (Mosaic
  takes no copy of an 8-wide row out of `[num_blocks, KV]`) into SMEM,
  so what a launch holds on chip does not grow with the table;
- a table no multiple of P wide is padded by the wrapper; entries of −1
  are clamped, lie behind every live length and are masked if fetched.

The append that comes before the read is `write_pages`, a third small
kernel over the pages a batch touches, with the pools aliased input to
output: beside these kernels an XLA scatter of rows makes the compiler
hold the pool in another layout and convert all of it for every launch.

Layout contract: q rows are packed/unpacked by the caller
(block_multihead_attention_); caches stay in their pool layout — one
layer's `[num_blocks, KV, block_size, hd]`, or the serving engine's whole
stacked pool `[L, num_blocks, KV, block_size, hd]` with the layer as one
more prefetched scalar, which the mixed walk's index maps and the decode
walk's copies put in front of the page: `(layer, tables[b, p], …)`. No
transpose, no reshape, no copy, and no slice of a layer out of the stack.
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

__all__ = ["paged_attention", "write_pages", "available", "supported",
           "selected", "decode_walk"]

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
    # the mixed walk's blocks equal the array dims on the last two axes,
    # so any (block_size, head_dim) is Mosaic-legal (the decode walk asks
    # more: `decode_walk`); keep the same floor as the flash kernel so
    # degenerate head dims fall back loudly instead of wasting the MXU
    return head_dim >= 8 and block_size >= 1


def selected(num_heads: int, num_kv_heads: int, head_dim: int,
             block_size: int) -> bool:
    """The rule a caller that was told nothing follows: the kernel where
    it runs (`available()`: a TPU) and the geometry is `supported()`, the
    stock XLA path elsewhere. `block_multihead_attention_(use_pallas=None)`
    and `PagedServingEngine(pallas=None)` both ask here, once, when they
    trace or are built."""
    return available() and supported(num_heads, num_kv_heads, head_dim,
                                     block_size)


def decode_walk(head_dim: int, interpret: Optional[bool] = None) -> bool:
    """Can a launch of one query token a sequence (`rows == group`) take
    the decode walk? It copies whole pages `[KV, block_size, hd]` out of
    the pool left in HBM, and Mosaic slices HBM in whole lanes: compiled
    for a v5e, every head_dim of 8 to 64 (page sizes 4 to 32, bf16 and
    int8 pages) is refused with "Slice shape along dimension 4 must be
    aligned to tiling (128)", 128 and 256 are taken, and the mixed walk's
    BlockSpec pages and `write_pages` are taken at all of them
    (tests/test_chip_compile.py). Such a launch then takes the mixed walk
    with max_q = 1. The interpreter takes any geometry, which is how CPU
    CI runs the decode walk at small widths."""
    if interpret is None:
        interpret = not available()
    return interpret or head_dim % _STAT_LANES == 0


def _kernel(tables_ref, past_ref, this_ref, layer_ref, *refs,
            sm_scale: float, block_size: int, group: int, has_quant: bool):
    """One (sequence b, kv head, page p) grid step. `layer_ref` is read
    by the K/V index maps only.

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


# the decode walk's key block: about one MXU tile of key positions a step,
# inside a VMEM budget for the double-buffered K and V page scratch
_DECODE_KEYS = 128
_DECODE_VMEM_BYTES = 4 << 20


def decode_pages_per_block(block_size: int, num_kv_heads: int, head_dim: int,
                           itemsize: int, max_blocks: int) -> int:
    """Pages the decode walk fetches and works on at a time, from shapes
    alone: as many whole pages `[KV, block_size, hd]` as make a key block
    of about `_DECODE_KEYS` positions, no more than the scratch budget
    holds twice over for K and for V, and no more than a table is wide."""
    page_bytes = num_kv_heads * block_size * head_dim * itemsize
    return max(1, min(_DECODE_KEYS // block_size,
                      _DECODE_VMEM_BYTES // (4 * page_bytes), max_blocks))


def decode_pages_walked(ends, block_size: int, num_kv_heads: int,
                        head_dim: int, itemsize: int, max_blocks: int):
    """(live, fetched) pages of one decode launch, reckoned on the host:
    `ends` [n] are the live lengths `past + 1` of the sequences that take
    part (idle slots left out). Live pages hold a key the query may see;
    fetched pages are the walk's trip count (`n_blocks` of
    `_decode_kernel`) times its P whole pages a key block."""
    pages = decode_pages_per_block(block_size, num_kv_heads, head_dim,
                                   itemsize, max_blocks)
    ends = np.asarray(ends, np.int64)
    blocks = np.minimum(-(-ends // (pages * block_size)),
                        -(-max_blocks // pages))
    return int((-(-ends // block_size)).sum()), int(blocks.sum()) * pages


def _decode_kernel(tables_ref, past_ref, this_ref, layer_ref, *refs,
                   sm_scale: float, block_size: int, pages: int,
                   has_quant: bool):
    """One sequence b of a decode launch (one query token, rows = the GQA
    group): walk its live key blocks of `pages` whole pages each.

    refs: q [1, KV, G, hd], k_pool, v_pool, [k_scale, v_scale
    [num_blocks, LANES],] (all left in HBM), o, then scratch: kbuf, vbuf
    [2, pages, KV, bs, hd], [kdq, vdq [2, pages, LANES] in SMEM,] sems
    [2, 2], acc [KV, G, hd], m, l [KV, G, LANES]. A page comes in one copy
    that serves every KV head, an int8 page's scale row in one more beside
    it; block i+1's copies start before block i's products. The online
    softmax runs per head over the block's pages * bs keys."""
    if has_quant:
        (q_ref, k_hbm, v_hbm, kdq_hbm, vdq_hbm, o_ref, kbuf, vbuf, kdq_ref,
         vdq_ref, sems, acc, m_sc, l_sc) = refs
    else:
        q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, acc, m_sc, l_sc = refs
    b = pl.program_id(0)
    layer = layer_ref[0]
    KV, G, hd = acc.shape
    span = pages * block_size
    width = tables_ref.shape[1]
    past = past_ref[b]
    # the one query sits at position `past`: keys 0..past are live, in
    # ceil((past + 1) / span) blocks (never past the table's end); an idle
    # slot walks none
    n_blocks = jnp.where(
        this_ref[b] > 0,
        jnp.minimum(jax.lax.div(past + _i32(span), _i32(span)),
                    _i32(width // pages)), _i32(0))

    def copies(i, slot):
        out = []
        for j in range(pages):
            page = tables_ref[b, i * _i32(pages) + _i32(j)]
            out.append(pltpu.make_async_copy(
                k_hbm.at[layer, page], kbuf.at[slot, _i32(j)],
                sems.at[_i32(0), slot]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[layer, page], vbuf.at[slot, _i32(j)],
                sems.at[_i32(1), slot]))
            if has_quant:
                out.append(pltpu.make_async_copy(
                    kdq_hbm.at[page], kdq_ref.at[slot, _i32(j)],
                    sems.at[_i32(0), slot]))
                out.append(pltpu.make_async_copy(
                    vdq_hbm.at[page], vdq_ref.at[slot, _i32(j)],
                    sems.at[_i32(1), slot]))
        return out

    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc[...] = jnp.zeros_like(acc)

    @pl.when(n_blocks > 0)
    def _():
        for c in copies(_i32(0), _i32(0)):
            c.start()

    def block(i, _):
        slot = jax.lax.rem(i, _i32(2))

        @pl.when(i + _i32(1) < n_blocks)
        def _():
            for c in copies(i + _i32(1), _i32(1) - slot):
                c.start()

        for c in copies(i, slot):
            c.wait()
        kv_abs = (jax.lax.broadcasted_iota(jnp.int32, (G, span), 1)
                  + i * _i32(span))
        ok = kv_abs <= past                           # causal = live keys
        if has_quant:
            key_page = jax.lax.div(
                jax.lax.broadcasted_iota(jnp.int32, (1, span), 1),
                _i32(block_size))

            def page_scales(scale_ref, kv):           # [1, span], per key
                vec = jnp.zeros((1, span), jnp.float32)
                for j in range(pages):
                    vec = jnp.where(key_page == j,
                                    scale_ref[slot, _i32(j), _i32(kv)], vec)
                return vec
        for kv in range(KV):
            q = q_ref[0, kv].astype(jnp.float32)      # [G, hd]
            k = kbuf[slot, :, kv].astype(jnp.float32).reshape(span, hd)
            s = jax.lax.dot_general(                  # int8 pages dequant
                q, k, (((1,), (1,)), ((), ())),       # in-register
                preferred_element_type=jnp.float32)   # [G, span]
            if has_quant:
                # a page's k scale is constant over hd: it factors out of
                # the dot and lands on that page's scores
                s = s * (sm_scale * page_scales(kdq_ref, kv))
            else:
                s = s * sm_scale
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_sc[kv][:, :1]                  # [G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            prob = jnp.exp(s - m_new)                 # [G, span]
            prob = jnp.where(ok, prob, 0.0)
            alpha = jnp.exp(m_prev - m_new)           # [G, 1]
            l_sc[kv] = l_sc[kv] * alpha + jnp.sum(prob, axis=-1,
                                                  keepdims=True)
            if has_quant:
                # the v scale likewise: fold into the probabilities
                prob = prob * page_scales(vdq_ref, kv)
            v = vbuf[slot, :, kv].astype(jnp.float32).reshape(span, hd)
            acc[kv] = acc[kv] * alpha + jax.lax.dot_general(
                prob, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_sc[kv] = jnp.broadcast_to(m_new, m_sc.shape[1:])

    jax.lax.fori_loop(_i32(0), n_blocks, block, None)
    # an idle slot walked nothing and has l == 0: divide by 1, emit 0
    l = l_sc[...][:, :, :1]
    o_ref[0] = (acc[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _decode_call(q_rows, key_cache, value_cache, tables, past, this, layer,
                 sm_scale, k_dequant, v_dequant, interpret):
    """The decode launch (`rows == group`): grid over sequences, pools
    left in HBM, whole pages gathered by the kernel."""
    B, KV, G, hd = q_rows.shape
    _, _, _, bs, _ = key_cache.shape
    has_quant = k_dequant is not None
    max_blocks = tables.shape[1]
    pages = decode_pages_per_block(bs, KV, hd, key_cache.dtype.itemsize,
                                   max_blocks)
    # whole key blocks only: a table that is no multiple wide is padded
    # (page 0, behind every live length, fetched at most and masked)
    tables = jnp.pad(tables, ((0, 0), (0, -max_blocks % pages)))
    inputs = [q_rows, key_cache, value_cache]
    scale_scratch = []
    if has_quant:
        # a page's scale row rides with the page, copied into SMEM: Mosaic
        # takes no copy of an 8- or 16-wide row out of the [num_blocks, KV]
        # plane, so the planes are padded to whole lanes
        pad = (0, 0), (0, -KV % _STAT_LANES)
        inputs += [jnp.pad(k_dequant.astype(jnp.float32), pad),
                   jnp.pad(v_dequant.astype(jnp.float32), pad)]
        scale_scratch = [pltpu.SMEM((2, pages, inputs[-1].shape[1]),
                                    jnp.float32)] * 2

    row_spec = pl.BlockSpec((1, KV, G, hd),
                            lambda b, *_: (b, _i32(0), _i32(0), _i32(0)),
                            memory_space=pltpu.VMEM)
    _assert_mosaic_tileable(row_spec.block_shape, q_rows.shape, "decode rows")
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[row_spec] + [hbm] * (len(inputs) - 1),
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((2, pages, KV, bs, hd), key_cache.dtype),
            pltpu.VMEM((2, pages, KV, bs, hd), value_cache.dtype),
            *scale_scratch,
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((KV, G, hd), jnp.float32),
            pltpu.VMEM((KV, G, _STAT_LANES), jnp.float32),
            pltpu.VMEM((KV, G, _STAT_LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, sm_scale=np.float32(sm_scale), block_size=int(bs),
        pages=int(pages), has_quant=has_quant)
    count_launch()
    return pl.pallas_call(
        kernel,
        name="paged_attention_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_rows.shape, q_rows.dtype),
        interpret=interpret,
    )(tables, past, this, layer, *inputs)


def paged_attention(q_rows, key_cache, value_cache, block_tables,
                    seq_lens_decoder, seq_lens_this_time, group: int,
                    sm_scale: float, k_dequant=None, v_dequant=None,
                    interpret: Optional[bool] = None, layer=None):
    """Attention over paged caches, block table walked in-kernel.

    q_rows [B, KV, max_q * G, hd] — per-sequence packed rows (row
    r = t * G + g: chunk offset t, GQA head g; the caller packs/unpacks
    against cu_seqlens); `group` is G = H // KV (static); key_cache /
    value_cache [num_blocks, KV, block_size, hd] ALREADY containing this
    step's appended tokens — or, with `layer` (an int32 scalar, traced or
    not), the stacked pool [L, num_blocks, KV, block_size, hd], of which
    the kernel reads that layer's pages where they lie; block_tables
    [B, max_blocks] int32 (−1 = unassigned; never dereferenced thanks to
    the length skip, but clamped defensively); seq_lens_decoder / seq_lens_this_time [B]
    int32 past/this lengths (the scheduler's chunked-prefill metadata).

    k_dequant / v_dequant [num_blocks, KV] f32 enable the int8-page
    mode (pass both or neither). Returns [B, KV, max_q * G, hd] in
    q_rows.dtype; pad rows come back 0.

    Rows equal to `group` (max_q = 1: the CALLER guarantees every
    seq_lens_this_time <= 1) take the decode walk where `decode_walk`
    says Mosaic lowers it, any other launch the mixed walk.
    """
    if (k_dequant is None) != (v_dequant is None):
        raise ValueError("pass both k_dequant and v_dequant or neither")
    has_quant = k_dequant is not None
    B, KV, rows, hd = q_rows.shape
    if rows <= 0 or group <= 0 or rows % group != 0:
        raise ValueError(f"q_rows rows={rows} must be a positive multiple "
                         f"of group={group}")
    if key_cache.ndim != (4 if layer is None else 5):
        raise ValueError(
            f"cache {key_cache.shape}: pass one layer's [nb, KV, bs, hd], "
            f"or the stacked [L, nb, KV, bs, hd] together with `layer`")
    if layer is None:
        # one layer is a stack of one: a leading axis of 1 is a bitcast
        key_cache, value_cache, layer = key_cache[None], value_cache[None], 0
    _, num_blocks, KVc, bs, hdc = key_cache.shape
    if (KVc, hdc) != (KV, hd):
        raise ValueError(f"cache [nb, KV, bs, hd]={key_cache.shape[1:]} does "
                         f"not match q rows [B, KV, rows, hd]={q_rows.shape}")
    max_blocks = block_tables.shape[1]
    if interpret is None:
        interpret = not available()

    tables = jnp.maximum(block_tables.astype(jnp.int32), 0)   # [B, mb]
    past = seq_lens_decoder.reshape(-1).astype(jnp.int32)     # [B]
    this = seq_lens_this_time.reshape(-1).astype(jnp.int32)   # [B]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    if rows == group and decode_walk(hd, interpret):
        return _decode_call(q_rows, key_cache, value_cache, tables, past,
                            this, layer, sm_scale, k_dequant, v_dequant,
                            interpret)

    mem = {"memory_space": pltpu.VMEM}
    # the layer axis is squeezed: the body sees [1, 1, bs, hd] pages
    page_spec = pl.BlockSpec(
        (None, 1, 1, bs, hd),
        lambda b, kv, p, tr, pr, th, ly: (ly[0], tr[b, p], kv, _i32(0),
                                          _i32(0)), **mem)
    in_specs = [
        pl.BlockSpec((1, 1, rows, hd),
                     lambda b, kv, p, tr, pr, th, ly: (b, kv, _i32(0),
                                                       _i32(0)), **mem),
        page_spec, page_spec,
    ]
    inputs = [q_rows, key_cache, value_cache]
    if has_quant:
        # a (1, 1) block of the [num_blocks, KV] plane breaks Mosaic's
        # (8, 128) rule; (_SCALE_ROWS, KV) is legal (8 rows, whole last
        # dim) and the kernel picks its scalar out of the block
        scale_spec = pl.BlockSpec(
            (_SCALE_ROWS, KV),
            lambda b, kv, p, tr, pr, th, ly: (
                jax.lax.div(tr[b, p], _i32(_SCALE_ROWS)), _i32(0)),
            memory_space=pltpu.SMEM)
        in_specs += [scale_spec, scale_spec]
        # whole SMEM blocks only: pad a pool that is no multiple of 8
        pad = (0, -num_blocks % _SCALE_ROWS), (0, 0)
        inputs += [jnp.pad(k_dequant.astype(jnp.float32), pad),
                   jnp.pad(v_dequant.astype(jnp.float32), pad)]
    out_spec = pl.BlockSpec(
        (1, 1, rows, hd),
        lambda b, kv, p, tr, pr, th, ly: (b, kv, _i32(0), _i32(0)), **mem)
    for spec, arr in zip(in_specs, inputs):
        _assert_mosaic_tileable(spec.block_shape, arr.shape, "paged input")
    _assert_mosaic_tileable(out_spec.block_shape, q_rows.shape,
                            "paged output")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
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
    )(tables, past, this, layer, *inputs)


def _write_kernel(layer_ref, page_ref, lo_ref, hi_ref, k_new_ref, v_new_ref,
                  k_in_ref, v_in_ref, k_out_ref, v_out_ref):
    """One touched page: slots [lo, hi) take the staged rows, the others
    keep what the page held. The select runs on 32-bit lanes (exact for
    bf16 and int8 pages alike); `layer_ref` and `page_ref` are read by
    the index maps only."""
    del layer_ref, page_ref
    j = pl.program_id(0)
    slot = jax.lax.broadcasted_iota(jnp.int32, k_in_ref.shape, 2)
    fresh = (slot >= lo_ref[j]) & (slot < hi_ref[j])
    for new_ref, in_ref, out_ref in ((k_new_ref, k_in_ref, k_out_ref),
                                     (v_new_ref, v_in_ref, v_out_ref)):
        wide = jnp.int32 if out_ref.dtype == jnp.int8 else jnp.float32
        out_ref[...] = jnp.where(fresh, new_ref[...].astype(wide),
                                 in_ref[...].astype(wide)
                                 ).astype(out_ref.dtype)


def write_pages(key_pool, value_pool, layer, pages, lo, hi, k_new, v_new,
                interpret: Optional[bool] = None):
    """Write the new tokens' rows into the stacked pools in place, a page
    at a time: for each plan entry j, slots [lo[j], hi[j]) of
    `pool[layer, pages[j]]` take `new[j]`'s rows and the other slots keep
    theirs. The pools [L, num_blocks, KV, block_size, hd] are aliased
    input to output (`input_output_aliases`), so pages outside the plan
    are never read or written.

    pages / lo / hi [n] int32, k_new / v_new [n, KV, block_size, hd] in the
    pools' dtype. An entry may repeat the one before it (same page, same
    slots, same rows: the block stays put and the result is the same),
    which is how the caller pads a plan; apart from that the plan's pages
    are distinct, as the pages that a batch's sequences write are.
    Returns (key_pool, value_pool)."""
    _, _, KV, bs, hd = key_pool.shape
    n = pages.shape[0]
    if interpret is None:
        interpret = not available()
    mem = {"memory_space": pltpu.VMEM}
    new_spec = pl.BlockSpec(
        (1, KV, bs, hd),
        lambda j, ly, pg, lo_, hi_: (j, _i32(0), _i32(0), _i32(0)), **mem)
    page_spec = pl.BlockSpec(
        (None, 1, KV, bs, hd),
        lambda j, ly, pg, lo_, hi_: (ly[0], pg[j], _i32(0), _i32(0),
                                     _i32(0)), **mem)
    _assert_mosaic_tileable(new_spec.block_shape, k_new.shape, "page write")
    _assert_mosaic_tileable(page_spec.block_shape, key_pool.shape,
                            "page write")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(n,),
        in_specs=[new_spec, new_spec, page_spec, page_spec],
        out_specs=[page_spec, page_spec])
    count_launch()
    return pl.pallas_call(
        _write_kernel,
        name="paged_cache_write",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(key_pool.shape, key_pool.dtype),
                   jax.ShapeDtypeStruct(value_pool.shape, value_pool.dtype)],
        # operands count the four prefetched scalars: pools are 6 and 7
        input_output_aliases={6: 0, 7: 1},
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), pages.astype(jnp.int32),
      lo.astype(jnp.int32), hi.astype(jnp.int32), k_new, v_new,
      key_pool, value_pool)
