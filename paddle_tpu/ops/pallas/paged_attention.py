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

Design — three walks over one pool, chosen by the static shapes of the
launch. Two copy whole pages out of the pool left in HBM and run wherever
Mosaic takes such a copy (`whole_pages`: head dims that are whole lanes,
every benchmark cell): the decode walk for a launch of one query token a
sequence (`rows == group`), bound by the count and size of its fetches,
and the mixed walk for a ragged launch, bound by its products. The third,
the BlockSpec walk, takes both kinds of launch at every other head dim.

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
  block's copies started before this block's products (`_page_copies`,
  shared with the mixed walk);
- per head an online softmax over the block's P * block_size keys with
  running statistics (m, l, acc) in VMEM scratch, the mask `kv_pos <=
  past`, zero for an idle slot; the dots run on blocks cast to f32;
- int8 pages dequantize IN-REGISTER: the k scale is constant over hd so
  it factors out of the q·k dot and lands on the scores, the v scale on
  the probabilities — bit-identical placement to the stock path's
  folding, and no fp copy of the cache ever exists. A page's `[KV]` scale
  row rides with the page: one more copy beside it, out of the plane
  padded to whole lanes (Mosaic takes no copy of an 8-wide row out of
  `[num_blocks, KV]`) into SMEM, so what a launch holds on chip does not
  grow with the table;
- a table no multiple of P wide is padded by the wrapper; entries of −1
  are clamped, lie behind every live length and are masked if fetched.

The mixed walk (`_mixed_kernel` under `paged_attention_packed`: ragged
prefill chunks, decode rows and idle slots in ONE launch, on the packed
token stream):

- the launch runs over *work items* (sequence b, row tile starting at
  chunk offset t0), reckoned inside the jitted step from `cu_seqlens_q` /
  `seq_lens_this_time` (`_work_items`): a sequence with `this` tokens has
  ceil(this / TQ) tiles, an idle slot none. Their count is static
  (`mixed_items`: token_num // TQ + B at most), the table rides as two
  more prefetched scalars, an unused item walks nothing. TQ
  (`mixed_tiles`) comes from shapes and a VMEM budget alone;
- a tile's rows `[KV, TQ * G, hd]` (GQA group g and chunk offset t fold
  into one MXU axis, row r = t*G + g) are gathered from the stream into
  `[items, KV, TQ * G, hd]` and the output is scattered back by the same
  table: what is packed is the tick's tokens in tiles, not token_num rows
  for every slot;
- per item the decode walk's `fori_loop` over key blocks of P whole pages
  (`mixed_pages_per_block`), up to the tile's OWN causal limit
  ceil((past + t0 + live) / (P * block_size)): the first tile of a chunk
  does not visit the keys of the last. The chunked-prefill metadata the
  scheduler already produces masks `kv_pos <= past + t0 + t` per row —
  in-chunk causality holds because the pages already contain this step's
  tokens (the append happens before the read, same as the stock path);
- q·k runs on the operands' own type (bf16 x bf16 products are exact in
  the f32 accumulator; int8 pages convert exactly), p·v in f32; the same
  online softmax, int8 scales and zero for rows without a query as the
  decode walk;
- an item with few live tokens (`_MIXED_SMALL_TOKENS`: a decode row or a
  speculative verify run beside a chunk) computes on the tile's first
  rows only — static slices of the same blocks under `pl.when` — so a
  one-token sequence in a mixed tick costs about what it costs in a
  decode tick.

Both ragged walks take a static `block_len` (0 = causal): the block-causal
mask of generation by diffusion over blocks, under which the query at
position p sees key j iff j < (p // block_len + 1) * block_len and
j < past + this (`_see_limit`); a tile's key blocks then end with its last
row's block. With 0 the kernels are what they were.

Every walk takes a static `window` W (0 = none): the query at position p
sees key j iff p - W < j <= p, W keys with its own. It is a lower limit
beside `_see_limit`'s upper one (`_see_from`): a whole-page walk begins at
the key block that holds position max(0, p_first - W + 1) of its first
query row, masks below each row's limit inside the blocks it visits, and
never reads a table entry behind that block, so a caller may have given
the pages behind the window back (entries of -1). With 0 the kernels
are what they were.

Both whole-page walks take a static `mask` (None = none): a SELECTION over
the table's key positions a query token, handed in as bits
(`sparse_index.pack_mask`: the selection a learned sparse index made,
`serving_attention.paged_index_select`), widened to a byte a key for the
launch's rows alone and cut into the walk's key blocks (the decode walk:
`[B, key blocks, 1, span]`; the mixed walk: `[items, key blocks, TQ, span]`,
an item's whole mask one VMEM block). Inside a key block the selection's
bytes are ANDed into what a row sees, a token's row broadcast over its GQA
group's sublanes (`_spread_over_heads`); a row may then see no key of a
block, so the probabilities are zeroed as under a window. This is the MASKED
WALK of a layer of heads' own keys and values under a sparse index: every
page of the context is read once for a tile of rows and the softmax runs
over exactly the selected keys. The masked DECODE walk also takes a key
block in ONE copy a pool where the block's pages lie side by side in the
pool (`block_runs`, computed from the table and prefetched as one more
scalar plane; `_run_copies`): the contexts it walks are long prompts whose
pages were taken in one go. Which rows take it and which gather their
selected keys instead is `serving_attention.paged_layer_attention`'s rule.
Without a mask nothing of it is traced (no operand, no scratch): the
kernels are what they were. The BlockSpec walk has no mask.

The BlockSpec walk (`_kernel`; rows `[B, KV, max_q * G, hd]` packed per
sequence, max_q = 1 for a decode launch): grid `(B, KV, table width)`
with the page axis innermost, one `[block_size, hd]` page of one KV head
a step through an index map over the prefetched table, pages past a
sequence's live length skipped (`pl.when`: no fetch, no product, but
still a grid step each), the scale planes through (8, KV) SMEM blocks.
Its blocks equal the array dims on the last two axes, so any geometry
lowers; it costs a grid step a page and head, and token_num rows a slot.

The append that comes before the read is `write_pages`, a third small
kernel over the pages a batch touches, with the pools aliased input to
output: beside these kernels an XLA scatter of rows makes the compiler
hold the pool in another layout and convert all of it for every launch.

Layout contract: `paged_attention` takes q rows packed per sequence by
the caller (block_multihead_attention_), `paged_attention_packed` the
token stream as it is; caches stay in their pool layout — one layer's
`[num_blocks, KV, block_size, hd]`, or the serving engine's whole stacked
pool `[L, num_blocks, KV, block_size, hd]` with the layer as one more
prefetched scalar, which the BlockSpec walk's index maps and the other
walks' copies put in front of the page: `(layer, tables[b, p], …)`. No
transpose, no reshape, no copy, and no slice of a layer out of the stack.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..kernels.sparse_index import unpack_mask
from .flash_attention import (NEG_INF, _assert_mosaic_tileable, _i32,
                              available, count_launch)

__all__ = ["paged_attention", "write_pages", "available", "supported",
           "selected", "whole_pages", "paged_attention_packed",
           "mixed_work", "decode_pages_walked", "block_runs"]

# m/l carriers use the same [rows, LANES] lane-broadcast trick as
# flash_attention.py (a [rows, 1] scratch column is not a legal vreg shape
# on all Mosaic versions; 128 lanes is the native tile)
_STAT_LANES = 128
# rows of the per-page scale planes brought into SMEM with each page
_SCALE_ROWS = 8


def _see_limit(pos, end, block_len: int):
    """The last key position a query at absolute position `pos` may see
    (int32 arrays or scalars, in a kernel or outside one): `pos` itself
    under the causal mask (block_len 0); under the block-causal mask of
    generation by diffusion over blocks the end of the query's own block
    of `block_len` positions, (pos // Bd + 1) * Bd - 1, and never past
    `end - 1`, the sequence's last position that holds a key (`end` is
    not looked at under the causal mask)."""
    if not block_len:
        return pos
    bd = _i32(block_len)
    return jnp.minimum((jax.lax.div(pos, bd) + _i32(1)) * bd, end) - _i32(1)


def _see_from(pos, window: int):
    """The first key position a query at absolute position `pos` may see
    under a window of `window` keys (the query's own among them): pos -
    window + 1, where that is negative every key from 0 on."""
    return pos - _i32(window - 1)


def supported(num_heads: int, num_kv_heads: int, head_dim: int,
              block_size: int) -> bool:
    """Static gate: can this head/page geometry run through the kernel?
    (availability — is there TPU hardware — is `available()`; interpret
    mode ignores it and is how CPU CI exercises the kernel bit-for-bit)."""
    if num_kv_heads <= 0 or num_heads % num_kv_heads != 0:
        return False
    # the BlockSpec walk's blocks equal the array dims on the last two axes,
    # so any (block_size, head_dim) is Mosaic-legal (the decode walk asks
    # more: `whole_pages`); keep the same floor as the flash kernel so
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


def whole_pages(head_dim: int, interpret: Optional[bool] = None) -> bool:
    """Can a launch take the walks that copy whole pages (the decode walk
    at one query token a sequence, `rows == group`; the mixed walk of
    `paged_attention_packed`)? They copy `[KV, block_size, hd]` out of
    the pool left in HBM, and Mosaic slices HBM in whole lanes: compiled
    for a v5e, every head_dim of 8 to 64 (page sizes 4 to 32, bf16 and
    int8 pages) is refused with "Slice shape along dimension 4 must be
    aligned to tiling (128)", 128 and 256 are taken, and the BlockSpec
    walk's pages and `write_pages` are taken at all of them
    (tests/test_chip_compile.py). Elsewhere every launch takes the
    BlockSpec walk (`_kernel`; a decode launch with max_q = 1). The
    interpreter takes any geometry, which is how CPU CI runs the
    whole-page walks at small widths."""
    if interpret is None:
        interpret = not available()
    return interpret or head_dim % _STAT_LANES == 0


def _kernel(tables_ref, past_ref, this_ref, layer_ref, *refs,
            sm_scale: float, block_size: int, group: int, has_quant: bool,
            block_len: int = 0, window: int = 0):
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
    if window:      # nor a page wholly behind the first row's window
        needed &= (p + _i32(1)) * block_size > _see_from(past, window)

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
        # causal (or block-causal: `_see_limit`) + live rows
        ok = (kv_abs <= _see_limit(past + t, past + this if block_len
                                   else None, block_len)) & (t < this)
        if window:
            ok &= kv_abs >= _see_from(past + t, window)
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


# the decode walk's key block: about one MXU tile of key positions a step;
# either whole-page walk's, inside a VMEM budget for the double-buffered K
# and V page scratch
_DECODE_KEYS = 128
_PAGE_SCRATCH_BYTES = 4 << 20


def decode_pages_per_block(block_size: int, num_kv_heads: int, head_dim: int,
                           itemsize: int, max_blocks: int) -> int:
    """Pages the decode walk fetches and works on at a time, from shapes
    alone: as many whole pages `[KV, block_size, hd]` as make a key block
    of about `_DECODE_KEYS` positions, no more than the scratch budget
    holds twice over for K and for V, and no more than a table is wide."""
    return _pages_per_block(_DECODE_KEYS, block_size, num_kv_heads, head_dim,
                            itemsize, max_blocks)


def _pages_per_block(keys: int, block_size: int, num_kv_heads: int,
                     head_dim: int, itemsize: int, max_blocks: int) -> int:
    page_bytes = num_kv_heads * block_size * head_dim * itemsize
    return max(1, min(keys // block_size,
                      _PAGE_SCRATCH_BYTES // (4 * page_bytes), max_blocks))


# the masked decode walk's key block: a selecting sequence holds thousands
# of keys, so its blocks are as large as the page scratch allows (64 pages
# of Keye-VL-2.0's 16 KB), not one MXU tile: on the v5e 16 sequences of
# 34k-65k keys took 6.17 ms at 128 keys a block, 6.07 at 256, 4.50 at 512
# and 3.94 at 1,024 (PERF.md section 6, PR 50)
_MASKED_DECODE_KEYS = 1024


def masked_decode_pages_per_block(block_size: int, num_kv_heads: int,
                                  head_dim: int, itemsize: int,
                                  max_blocks: int) -> int:
    """Pages of a key block of the masked decode walk (`_decode_call` with
    a mask): the decode walk's rule at `_MASKED_DECODE_KEYS` positions."""
    return _pages_per_block(_MASKED_DECODE_KEYS, block_size, num_kv_heads,
                            head_dim, itemsize, max_blocks)


def decode_pages_walked(ends, block_size: int, num_kv_heads: int,
                        head_dim: int, itemsize: int, max_blocks: int,
                        window: int = 0):
    """(live, fetched) pages of one decode launch, reckoned on the host:
    `ends` [n] are the live lengths `past + 1` of the sequences that take
    part (idle slots left out). Live pages hold a key the query may see
    (under a `window`: one of its last `window` positions); fetched pages
    are the walk's trip count (`n_blocks` of `_decode_kernel`, less the
    blocks behind the window) times its P whole pages a key block."""
    pages = decode_pages_per_block(block_size, num_kv_heads, head_dim,
                                   itemsize, max_blocks)
    ends = np.asarray(ends, np.int64)
    span = pages * block_size
    first = np.maximum(ends - window, 0) if window else np.zeros_like(ends)
    blocks = (np.minimum(-(-ends // span), -(-max_blocks // pages))
              - first // span)
    return (int((-(-ends // block_size) - first // block_size).sum()),
            int(blocks.sum()) * pages)


def _stacked(key_cache, value_cache, layer):
    """(key pool, value pool, layer [1] int32): the stacked pool
    [L, nb, KV, bs, hd] with its layer, or one layer's caches as a stack of
    one (a leading axis of 1 is a bitcast)."""
    if key_cache.ndim != (4 if layer is None else 5):
        raise ValueError(
            f"cache {key_cache.shape}: pass one layer's [nb, KV, bs, hd], "
            f"or the stacked [L, nb, KV, bs, hd] together with `layer`")
    if layer is None:
        key_cache, value_cache, layer = key_cache[None], value_cache[None], 0
    return key_cache, value_cache, jnp.asarray(layer, jnp.int32).reshape(1)


def _walk_refs(refs, has_quant: bool):
    """Split a whole-page walk's refs: q, the pools left in HBM (k, v[,
    k_scale, v_scale]), o, their scratch buffers in the same order, then
    sems, acc, m, l."""
    n = 4 if has_quant else 2
    return (refs[0], refs[1:1 + n], refs[1 + n], refs[2 + n:2 + 2 * n],
            *refs[2 + 2 * n:])


def _page_copies(tables_ref, b, i, j, slot, pages: int, layer, pools, bufs,
                 sems, first: int = 0):
    """The copies that bring page j (static or traced) of sequence b's key
    block i into buffer `slot`, shared by the decode walk and the mixed
    walk: one whole page `pool[layer, page]` = `[KV, block_size, hd]` of K
    and one of V (a copy serves every KV head), and with int8 pages that
    page's scale rows beside them. `pools` are the refs left in HBM (k,
    v[, k_scale, v_scale [num_blocks, LANES]]), `bufs` their
    double-buffered scratch ([2, pages, KV, bs, hd], scale rows [2, pages,
    LANES] in SMEM), `sems` [2, 2]: K and its scales signal `sems[0,
    slot]`, V and its scales `sems[1, slot]`. `first` (static) leaves out
    the pools before it: 2 gives the scale rows alone."""
    page = tables_ref[b, i * _i32(pages) + j]
    return [pltpu.make_async_copy(
        pool.at[layer, page] if n < 2 else pool.at[page], buf.at[slot, j],
        sems.at[_i32(n % 2), slot])
        for n, (pool, buf) in enumerate(zip(pools, bufs)) if n >= first]


def _block_copies(tables_ref, b, i, slot, pages: int, layer, pools, bufs,
                 sems):
    """Every copy of key block i, page by page (`_page_copies`): the form
    of a block whose pages lie anywhere in the pool. A block that is a RUN
    (`block_runs`) comes in `_run_copies` instead, where the launch was
    handed the plane that says so (the masked decode walk alone)."""
    return [c for j in range(pages)
            for c in _page_copies(tables_ref, b, i, _i32(j), slot, pages,
                                  layer, pools, bufs, sems)]


def block_runs(tables, pages: int, num_blocks: int, xp=jnp):
    """Which key blocks of a block table are RUNS: `tables` [B, width]
    int32 as the scheduler made it (-1 = no page), cut into key blocks of
    `pages` entries (the last one padded with -1) -> [B, blocks] int32, 1
    where a block's entries are p, p + 1, ..., p + pages - 1 with p >= 0
    and p + pages <= `num_blocks`: its pages lie side by side in the pool,
    so `pool[layer, p : p + pages]` is ONE contiguous region and one copy
    brings it where `pages` copies bring any other block. A block with a
    -1 or padded entry, a descending or broken sequence of pages, or one
    that would end past the pool is no run. Read from the table alone, by
    nobody's setting; `xp` is `jnp` for the plane a launch prefetches and
    `numpy` for the host's count of the same blocks
    (`paged_attention_latent.index_blocks_walked`), so the two cannot
    drift."""
    tables = xp.asarray(tables, dtype=xp.int32)
    if tables.shape[1] % pages:
        tables = xp.pad(tables, ((0, 0), (0, -tables.shape[1] % pages)),
                        constant_values=-1)
    blocks = tables.reshape(tables.shape[0], -1, pages)
    first = blocks[:, :, 0]
    run = ((blocks == first[:, :, None] + xp.arange(pages, dtype=xp.int32)
            ).all(axis=-1) & (first >= 0) & (first + pages <= num_blocks))
    return run.astype(xp.int32)


def _run_copies(tables_ref, b, i, slot, pages: int, layer, pools, bufs, sems):
    """Key block i of sequence b where it is a run (`block_runs`): ONE copy
    of `pool[layer, p : p + pages]` = [pages, KV, block_size, hd] for K and
    one for V, the same bytes into the same buffer under the same
    semaphore as `_block_copies`' 2 x pages; an int8 pool's scale rows
    still ride a page at a time."""
    page = tables_ref[b, i * _i32(pages)]
    whole = [pltpu.make_async_copy(
        pool.at[layer, pl.ds(page, pages)], buf.at[slot],
        sems.at[_i32(n), slot])
        for n, (pool, buf) in enumerate(zip(pools[:2], bufs[:2]))]
    return whole + [c for j in range(pages) for c in _page_copies(
        tables_ref, b, i, _i32(j), slot, pages, layer, pools, bufs, sems,
        first=2)]


def _page_scales(scale_ref, slot, kv, pages: int, block_size: int):
    """[1, pages * block_size] f32: for each key of the block in buffer
    `slot`, its page's scale for head kv (static or traced; the rows
    `_block_copies` brought into SMEM)."""
    if isinstance(kv, int):
        kv = _i32(kv)
    span = pages * block_size
    key_page = jax.lax.div(
        jax.lax.broadcasted_iota(jnp.int32, (1, span), 1), _i32(block_size))
    vec = jnp.zeros((1, span), jnp.float32)
    for j in range(pages):
        vec = jnp.where(key_page == j, scale_ref[slot, _i32(j), kv], vec)
    return vec


def _spread_over_heads(mask, tokens: int, heads: int):
    """A tile's selection `mask` [tq, span] int8 (a row a token) as the
    keys each query row may see, [tokens * heads, span] bool, row r =
    t * heads + h: token t's row broadcast over its heads' sublanes (a
    latent walk's H heads, a GQA walk's group)."""
    mask = mask.astype(jnp.int32)
    rows = [jnp.broadcast_to(mask[t:t + 1], (heads, mask.shape[1]))
            for t in range(tokens)]
    return (rows[0] if tokens == 1 else jnp.concatenate(rows, axis=0)) != 0


def _mask_blocks(rows_mask, blocks: int, span: int):
    """A selection's bits for some token rows [..., nb, 4] uint32
    (`sparse_index.pack_mask`) as a byte a key cut into a walk's key
    blocks: [..., blocks, span] int8."""
    m = unpack_mask(rows_mask)                                  # [..., S]
    pad = [(0, 0)] * (m.ndim - 1) + [(0, max(blocks * span - m.shape[-1], 0))]
    return jnp.pad(m, pad)[..., :blocks * span].reshape(
        *m.shape[:-1], blocks, span)


def _decode_kernel(tables_ref, past_ref, this_ref, layer_ref, *refs,
                   sm_scale: float, block_size: int, pages: int,
                   has_quant: bool, window: int = 0, masked: bool = False):
    """One sequence b of a decode launch (one query token, rows = the GQA
    group): walk its live key blocks of `pages` whole pages each.

    refs: q [1, KV, G, hd], k_pool, v_pool, [k_scale, v_scale
    [num_blocks, LANES],] (all left in HBM), o, then scratch: kbuf, vbuf
    [2, pages, KV, bs, hd], [kdq, vdq [2, pages, LANES] in SMEM,] sems
    [2, 2], acc [KV, G, hd], m, l [KV, G, LANES]. A page comes in one copy
    that serves every KV head, an int8 page's scale row in one more beside
    it; block i+1's copies start before block i's products. The online
    softmax runs per head over the block's pages * bs keys. `masked`
    (static): the MASKED WALK; behind q comes the sequence's selection
    [1, key blocks, 1, span] int8, and the row sees of a key block only the
    keys it selected; in front of q then comes one more prefetched scalar,
    `runs_ref` [B, key blocks] (`block_runs`), and a block it marks comes
    in one copy a pool (`_run_copies`), any other page by page as in the
    unmasked walk, which is handed no such plane and traces as it did."""
    runs_ref = None
    if masked:
        runs_ref, mask_ref, refs = refs[0], refs[2], refs[1:2] + refs[3:]
    q_ref, pools, o_ref, bufs, sems, acc, m_sc, l_sc = _walk_refs(
        refs, has_quant)
    kbuf, vbuf = bufs[:2]
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
    # under a window the walk begins at the block that holds the first key
    # the query sees; the table's entries behind it are never read
    # (with window 0 nothing below traces an operation it did not trace
    # before the window was there)
    first = (jax.lax.div(jnp.maximum(_see_from(past, window), _i32(0)),
                         _i32(span)) if window else _i32(0))

    def copies(i, slot):
        return _block_copies(tables_ref, b, i, slot, pages, layer, pools,
                             bufs, sems)

    def start(i, slot):
        if runs_ref is None:
            for c in copies(i, slot):
                c.start()
            return
        run = runs_ref[b, i] != 0

        @pl.when(run)
        def _():
            for c in _run_copies(tables_ref, b, i, slot, pages, layer, pools,
                                 bufs, sems):
                c.start()

        @pl.when(jnp.logical_not(run))
        def _():
            for c in copies(i, slot):
                c.start()

    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc[...] = jnp.zeros_like(acc)

    @pl.when(n_blocks > (first if window else 0))
    def _():
        start(first, jax.lax.rem(first, _i32(2)) if window else _i32(0))

    def block(i, _):
        slot = jax.lax.rem(i, _i32(2))

        @pl.when(i + _i32(1) < n_blocks)
        def _():
            start(i + _i32(1), _i32(1) - slot)

        # (a DMA semaphore counts bytes: a run's one copy is waited for by
        # the pages' descriptors as their own copies are)
        for c in copies(i, slot):
            c.wait()
        kv_abs = (jax.lax.broadcasted_iota(jnp.int32, (G, span), 1)
                  + i * _i32(span))
        ok = kv_abs <= past                           # causal = live keys
        if window:
            ok &= kv_abs >= _see_from(past, window)
        if masked:
            ok &= mask_ref[0, i].astype(jnp.int32) != 0
        for kv in range(KV):
            q = q_ref[0, kv].astype(jnp.float32)      # [G, hd]
            k = kbuf[slot, :, kv].astype(jnp.float32).reshape(span, hd)
            s = jax.lax.dot_general(                  # int8 pages dequant
                q, k, (((1,), (1,)), ((), ())),       # in-register
                preferred_element_type=jnp.float32)   # [G, span]
            if has_quant:
                # a page's k scale is constant over hd: it factors out of
                # the dot and lands on that page's scores
                s = s * (sm_scale * _page_scales(bufs[2], slot, kv, pages,
                                                   block_size))
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
                prob = prob * _page_scales(bufs[3], slot, kv, pages,
                                          block_size)
            v = vbuf[slot, :, kv].astype(jnp.float32).reshape(span, hd)
            acc[kv] = acc[kv] * alpha + jax.lax.dot_general(
                prob, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_sc[kv] = jnp.broadcast_to(m_new, m_sc.shape[1:])

    jax.lax.fori_loop(first, n_blocks, block, None)
    # an idle slot walked nothing and has l == 0: divide by 1, emit 0
    l = l_sc[...][:, :, :1]
    o_ref[0] = (acc[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _walk_operands(key_cache, value_cache, tables, k_dequant, v_dequant,
                   pages: int):
    """What a whole-page walk is launched with beside its q rows: the
    table padded to whole key blocks of `pages` (page 0, behind every live
    length, fetched at most and masked), the pools it leaves in HBM, and
    the scratch `_block_copies` fills (the double-buffered pages, an int8
    pool's scale rows, the semaphores)."""
    _, _, KV, bs, hd = key_cache.shape
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % pages)))
    pools = [key_cache, value_cache]
    scratch = [pltpu.VMEM((2, pages, KV, bs, hd), key_cache.dtype),
               pltpu.VMEM((2, pages, KV, bs, hd), value_cache.dtype)]
    if k_dequant is not None:
        # a page's scale row rides with the page, copied into SMEM: Mosaic
        # takes no copy of an 8- or 16-wide row out of the [num_blocks, KV]
        # plane, so the planes are padded to whole lanes
        pad = (0, 0), (0, -KV % _STAT_LANES)
        pools += [jnp.pad(k_dequant.astype(jnp.float32), pad),
                  jnp.pad(v_dequant.astype(jnp.float32), pad)]
        scratch += [pltpu.SMEM((2, pages, pools[-1].shape[1]),
                               jnp.float32)] * 2
    return tables, pools, scratch + [pltpu.SemaphoreType.DMA((2, 2))]


def _decode_call(q_rows, key_cache, value_cache, tables, past, this, layer,
                 sm_scale, k_dequant, v_dequant, interpret, window: int = 0,
                 mask=None, raw_tables=None):
    """The decode launch (`rows == group`): grid over sequences, pools
    left in HBM, whole pages gathered by the kernel. `mask` [B, nb, 4]
    uint32 (or None: static): the masked walk, which also prefetches
    which of its key blocks are runs (`block_runs` of `raw_tables`, the
    table before its -1 entries were clamped) and fetches those in one
    copy a pool."""
    B, KV, G, hd = q_rows.shape
    _, _, _, bs, _ = key_cache.shape
    has_quant = k_dequant is not None
    max_blocks = tables.shape[1]
    pages = (decode_pages_per_block if mask is None
             else masked_decode_pages_per_block)(
        bs, KV, hd, key_cache.dtype.itemsize, max_blocks)
    tables, pools, page_scratch = _walk_operands(
        key_cache, value_cache, tables, k_dequant, v_dequant, pages)
    operands, mask_specs, runs = [], [], []
    if mask is not None:
        span, blocks = pages * bs, tables.shape[1] // pages
        runs = [block_runs(raw_tables, pages, key_cache.shape[1])]
        operands = [_mask_blocks(mask, blocks, span)[:, :, None]]
        mask_specs = [pl.BlockSpec(
            (1, blocks, 1, span),
            lambda b, *_: (b, _i32(0), _i32(0), _i32(0)),
            memory_space=pltpu.VMEM)]

    row_spec = pl.BlockSpec((1, KV, G, hd),
                            lambda b, *_: (b, _i32(0), _i32(0), _i32(0)),
                            memory_space=pltpu.VMEM)
    _assert_mosaic_tileable(row_spec.block_shape, q_rows.shape, "decode rows")
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 + len(runs),
        grid=(B,),
        in_specs=[row_spec] + mask_specs + [hbm] * len(pools),
        out_specs=row_spec,
        scratch_shapes=[
            *page_scratch,
            pltpu.VMEM((KV, G, hd), jnp.float32),
            pltpu.VMEM((KV, G, _STAT_LANES), jnp.float32),
            pltpu.VMEM((KV, G, _STAT_LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, sm_scale=np.float32(sm_scale), block_size=int(bs),
        pages=int(pages), has_quant=has_quant, window=int(window),
        masked=mask is not None)
    count_launch()
    call = dict(grid_spec=grid_spec, interpret=interpret,
                out_shape=jax.ShapeDtypeStruct(q_rows.shape, q_rows.dtype))
    # (a site a name, each a literal: what a trace calls the launch)
    if mask is None:
        launch = pl.pallas_call(kernel, name="paged_attention_decode", **call)
    else:
        launch = pl.pallas_call(kernel, name="paged_attention_decode_masked",
                                **call)
    return launch(tables, past, this, layer, *runs, q_rows, *operands, *pools)


# the mixed walk's work item: the row tile of this many tokens of one
# sequence (x group rows a KV head) against key blocks of about this many
# positions; a sequence with at most this many tokens in a tick (a decode
# row, a speculative verify) computes on a small row tile. Measured on a
# v5e over the benchmark's mixes (PERF.md, PR 30): tiles of 64 tokens beat
# 32 and 128 everywhere, blocks of 512 keys beat 256 by 10-17 % on chunks
# and lose 4 % on a tick of one-row items
_MIXED_TOKENS = 64
_MIXED_KEYS = 512
_MIXED_SMALL_TOKENS = 8
# acc, m, l and the double-buffered q and o blocks of an item stay inside
# this; the launch states its own scoped-VMEM limit (a v5e core has 128 MiB)
_MIXED_VMEM_BYTES = 40 << 20
_MIXED_VMEM_LIMIT = 96 << 20


def mixed_pages_per_block(block_size: int, num_kv_heads: int, head_dim: int,
                          itemsize: int, max_blocks: int) -> int:
    """Pages the mixed walk fetches and works on at a time: the decode
    walk's rule at the mixed walk's key block."""
    return _pages_per_block(_MIXED_KEYS, block_size, num_kv_heads, head_dim,
                            itemsize, max_blocks)


def mixed_tiles(token_num: int, group: int, num_kv_heads: int,
                head_dim: int):
    """(TQ, TS) of a mixed launch, from shapes alone. TQ: the tokens of a
    work item's row tile, `_MIXED_TOKENS`, no more than the launch packs
    and than `_MIXED_VMEM_BYTES` holds (per token and head: acc, m, l in
    f32 and four q / o block rows), in whole sublane tiles of rows (16, the
    bf16 tile). TS: the tokens of the small row tile an item with few live
    tokens computes on (TS == TQ: there is none)."""
    step = 16 // math.gcd(group, 16)
    per_token = group * num_kv_heads * 4 * (5 * head_dim + 2 * _STAT_LANES)
    cap = min(_MIXED_TOKENS, _MIXED_VMEM_BYTES // per_token,
              -(-token_num // step) * step)
    tq = max(step, cap // step * step)
    ts = min(tq, -(-_MIXED_SMALL_TOKENS // step) * step)
    return tq, ts


def mixed_items(token_num: int, batch: int, tq: int) -> int:
    """The static count of work items of a mixed launch: a sequence with
    `this` tokens has ceil(this / TQ), so no tick has more than
    token_num // TQ + B, nor more than it has tokens."""
    return max(1, min(token_num, token_num // tq + batch))


def mixed_work(past, this, token_num: int, block_size: int,
               num_kv_heads: int, group: int, head_dim: int, itemsize: int,
               max_blocks: int, block_len: int = 0, window: int = 0):
    """What one mixed launch walks, reckoned on the host from the
    scheduler's own lengths (`past`, `this` [B], idle slots 0): the trip
    counts of `_mixed_kernel`, as `decode_pages_walked` mirrors
    `_decode_kernel`. Returns a dict: `attn_rows_live` query tokens and
    `attn_rows_packed` the token rows of the tiles the work items with a
    query row are computed on (TS or TQ an item: their ratio is the tile
    occupancy); `attn_pages_live` pages that hold a key
    some query may see; `attn_pages_fetched` key blocks walked x P, every
    item's own (a chunk's later tiles walk its earlier keys again). Under
    a `window` a page is live if it holds one of the last `window`
    positions of some query of the chunk, and an item's walk begins at
    the block of its first row's first visible key."""
    tq, ts = mixed_tiles(token_num, group, num_kv_heads, head_dim)
    pages = mixed_pages_per_block(block_size, num_kv_heads, head_dim,
                                  itemsize, max_blocks)
    past = np.asarray(past, np.int64)
    this = np.asarray(this, np.int64)
    past, this = past[this > 0], this[this > 0]
    tiles = -(-this // tq)
    seq = np.repeat(np.arange(len(this)), tiles)
    t0 = (np.arange(int(tiles.sum())) - np.repeat(np.cumsum(tiles) - tiles,
                                                  tiles)) * tq
    live = np.minimum(this[seq] - t0, tq)
    seen = past[seq] + t0 + live
    if block_len:       # to the end of the tile's last row's block
        seen = np.minimum(-(-seen // block_len) * block_len,
                          past[seq] + this[seq])
    blocks = np.minimum(-(-seen // (pages * block_size)),
                        -(-max_blocks // pages))
    live_pages = -(-(past + this) // block_size)
    if window:
        blocks = blocks - (np.maximum(past[seq] + t0 - (window - 1), 0)
                           // (pages * block_size))
        live_pages = live_pages - (np.maximum(past - (window - 1), 0)
                                   // block_size)
    return {"attn_rows_live": int(this.sum()),
            "attn_rows_packed": int(np.where(live <= ts, ts, tq).sum()),
            "attn_pages_live": int(live_pages.sum()),
            "attn_pages_fetched": int(blocks.sum()) * pages}


def _work_items(cu, this, tq: int, items: int, token_num: int):
    """The mixed launch's work items, inside the jitted step: (seq [items],
    t0 [items], first [B]). Item j is the row tile of sequence seq[j] that
    starts at chunk offset t0[j]; a sequence's ceil(this / tq) tiles are
    consecutive from first[b]; an item past the last one sits behind every
    chunk (t0 = token_num), so it has no rows and walks nothing."""
    B = this.shape[0]
    tiles = -(-this // tq)                                      # [B]
    ends = jnp.cumsum(tiles)
    first = ends - tiles
    j = jnp.arange(items, dtype=jnp.int32)
    # (compare_all: a few dozen comparisons that fuse, where the default
    # binary search is a loop of small gathers in every layer)
    seq = jnp.clip(jnp.searchsorted(ends, j, side="right",
                                    method="compare_all"), 0,
                   B - 1).astype(jnp.int32)
    t0 = jnp.where(j < ends[-1], (j - first[seq]) * tq, token_num)
    return seq, t0.astype(jnp.int32), first.astype(jnp.int32)


def _loop_i32(n: int, body) -> None:
    """`body(j)` for j = 0 .. n - 1 on an int32 counter. (`fori_loop` with
    static bounds becomes a scan whose counter is int64 under
    jax_enable_x64, and Mosaic lowers no arithmetic on one.)"""
    jax.lax.while_loop(lambda j: j < _i32(n),
                       lambda j: (body(j), j + _i32(1))[1], _i32(0))


def _mixed_kernel(tables_ref, past_ref, this_ref, layer_ref, seq_ref, t0_ref,
                  *refs, sm_scale: float, block_size: int, pages: int,
                  group: int, small: int, has_quant: bool,
                  block_len: int = 0, window: int = 0, masked: bool = False):
    """One work item j of a mixed launch: the query rows of sequence
    seq[j] from chunk offset t0[j] on (row r = t * G + g of the tile, its
    query at position past + t0 + t), against that sequence's key blocks
    of `pages` whole pages up to the tile's own causal limit.

    refs: q [1, KV, R, hd], the pools (left in HBM), o, then scratch as
    the decode walk's: kbuf, vbuf [2, pages, KV, bs, hd], [scale rows,]
    sems, acc [KV, R, hd], m, l [KV, R, LANES]. The pages come as the
    decode walk's do (`_page_copies`); q.k runs on the operands' own
    type with f32 accumulation, p.v in f32. An item with at most `small`
    live tokens (a decode row or a verify run beside a chunk) computes on
    the tile's first small * G rows only. `masked` (static): the MASKED
    WALK; behind q comes the item's selection [1, key blocks, TQ, span] int8,
    and a row sees of a key block only the keys its token selected."""
    if masked:
        mask_ref, refs = refs[1], refs[:1] + refs[2:]
    q_ref, pools, o_ref, bufs, sems, acc, m_sc, l_sc = _walk_refs(
        refs, has_quant)
    kbuf, vbuf = bufs[:2]
    j = pl.program_id(0)
    b = seq_ref[j]
    t0 = t0_ref[j]
    layer = layer_ref[0]
    KV, R, hd = acc.shape
    bs = block_size
    span = pages * bs
    width = tables_ref.shape[1]
    past = past_ref[b]
    # tokens of this tile that hold a query (none: an unused item)
    live = jnp.clip(this_ref[b] - t0, _i32(0), _i32(R // group))
    # keys 0 .. past + t0 + live - 1 can be seen from the tile (under the
    # block-causal mask: up to the end of its last row's block), the first
    # tile of a chunk does not visit the keys of the last
    # (with block_len 0 nothing below traces an operation it did not
    # trace before the mask was there: the causal executables are the same)
    end = past + this_ref[b] if block_len else None

    def seen():
        s = past + t0 + live
        if block_len:
            s = _see_limit(s - _i32(1), end, block_len) + _i32(1)
        return s

    n_blocks = jnp.where(
        live > 0,
        jnp.minimum(jax.lax.div(seen() + _i32(span - 1), _i32(span)),
                    _i32(width // pages)), _i32(0))
    # under a window the tile's walk begins at the block that holds the
    # first key its first row sees; table entries behind it are never read
    first = (jax.lax.div(jnp.maximum(_see_from(past + t0, window), _i32(0)),
                         _i32(span)) if window else _i32(0))
    # the products' operand type: q's and the pages' own (int8 pages
    # convert exactly), never wider than what either holds
    ct = (q_ref.dtype if kbuf.dtype == jnp.int8
          else jnp.promote_types(q_ref.dtype, kbuf.dtype))
    # 16-bit products are exact in the f32 accumulator at any precision, and
    # Mosaic takes no other for them: a caller's default_matmul_precision
    # ("highest" around a reference check) is left to the f32 dots
    qk_precision = (jax.lax.Precision.DEFAULT if jnp.dtype(ct).itemsize < 4
                    else None)

    def fetch(i, slot, wait=False):
        # a loop over the block's pages, not P copies of the descriptors:
        # the kernel is traced anew in every process that builds a tick,
        # before it can ask the compile cache
        def page(j):
            for c in _page_copies(tables_ref, b, i, j, slot, pages, layer,
                                  pools, bufs, sems):
                if wait:
                    c.wait()
                else:
                    c.start()
        _loop_i32(pages, page)

    def head_block(buf, slot, kv, dtype):             # [span, hd] of a head
        x = buf[slot, :, kv]                          # [pages, bs, hd]
        if x.dtype != dtype or bs % (32 // x.dtype.itemsize) != 0:
            # whole f32 sublane tiles merge without a relayout
            x = x.astype(jnp.float32)
        return x.reshape(span, hd).astype(dtype)

    def walk(rows):
        """The item's walk on its first `rows` rows (static)."""
        t = jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0),
                        _i32(group))
        # the last key a row's query sees (its own position; the end of
        # its block under the block-causal mask); rows without a query
        # (pad, t >= this) sit before every key
        pos = jnp.where(t < live,
                        _see_limit(past + t0 + t, end, block_len),
                        _i32(-1))                             # [rows, 1]
        m_sc[:, :rows] = jnp.full((KV, rows, _STAT_LANES), NEG_INF,
                                  jnp.float32)
        l_sc[:, :rows] = jnp.zeros((KV, rows, _STAT_LANES), jnp.float32)
        acc[:, :rows] = jnp.zeros((KV, rows, hd), jnp.float32)

        pl.when(n_blocks > (first if window else 0))(
            lambda: fetch(first, jax.lax.rem(first, _i32(2)) if window
                          else _i32(0)))

        def block(i, _):
            slot = jax.lax.rem(i, _i32(2))
            pl.when(i + _i32(1) < n_blocks)(
                lambda: fetch(i + _i32(1), _i32(1) - slot))
            fetch(i, slot, wait=True)
            kv_abs = (jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
                      + i * _i32(span))
            ok = kv_abs <= pos                        # [rows, span]
            if window:
                ok &= kv_abs >= _see_from(pos, window)
            if masked:
                ok &= _spread_over_heads(mask_ref[0, i], rows // group,
                                         group)

            def head(kv):
                s = jax.lax.dot_general(
                    q_ref[0, kv, :rows].astype(ct),
                    head_block(kbuf, slot, kv, ct),
                    (((1,), (1,)), ((), ())), precision=qk_precision,
                    preferred_element_type=jnp.float32)       # [rows, span]
                if has_quant:
                    # a page's k scale is constant over hd: it factors out
                    # of the dot and lands on that page's scores
                    s = s * (sm_scale * _page_scales(bufs[2], slot, kv,
                                                     pages, bs))
                else:
                    s = s * sm_scale
                s = jnp.where(ok, s, NEG_INF)
                m_prev = m_sc[kv, :rows, :1]                  # [rows, 1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                # a masked key of a row with a live one gives exp(-1e30 -
                # m) = 0 exactly; a row with none yet (only rows without
                # a query: key 0 is in every first block) is zeroed below
                prob = jnp.exp(s - m_new)                     # [rows, span]
                if window or masked:
                    # a row may see no key of the tile's first blocks (they
                    # hold its earlier rows' windows, or no key it
                    # selected): there m_new is still -1e30 and exp(0) = 1
                    # would count every masked key
                    prob = jnp.where(ok, prob, 0.0)
                alpha = jnp.exp(m_prev - m_new)               # [rows, 1]
                l_sc[kv, :rows] = (l_sc[kv, :rows] * alpha
                                   + jnp.sum(prob, axis=-1, keepdims=True))
                if has_quant:
                    # the v scale likewise: fold into the probabilities
                    prob = prob * _page_scales(bufs[3], slot, kv, pages, bs)
                acc[kv, :rows] = acc[kv, :rows] * alpha + jax.lax.dot_general(
                    prob, head_block(vbuf, slot, kv, jnp.float32),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_sc[kv, :rows] = jnp.broadcast_to(m_new,
                                                   (rows, _STAT_LANES))

            # a loop over the heads, not KV copies of the body: unrolled,
            # the launch is 4 % faster at 8 heads and 20 % at 16 heads of
            # one-row items (0.1-0.2 % of a cell's rate), and every process
            # that builds a tick spends 0.3 s more tracing it (PERF.md,
            # PR 30)
            _loop_i32(KV, head)

        jax.lax.fori_loop(first, n_blocks, block, None)
        l = l_sc[:, :rows, :1]
        out = acc[:, :rows] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, :, :rows] = jnp.where(pos >= 0, out, 0.0).astype(o_ref.dtype)
        if rows < R:
            o_ref[0, :, rows:] = jnp.zeros((KV, R - rows, hd), o_ref.dtype)

    if 0 < small * group < R:
        pl.when(live <= small)(lambda: walk(small * group))
        pl.when(live > small)(lambda: walk(R))
    else:
        walk(R)


def _mixed_call(q_items, key_cache, value_cache, tables, past, this, layer,
                seq, t0, group, small, sm_scale, k_dequant, v_dequant,
                interpret, block_len: int = 0, window: int = 0,
                mask=None):
    """The mixed launch: grid over work items, pools left in HBM, whole
    pages gathered by the kernel. `mask` [items, TQ, nb, 4] uint32 (or
    None: static): the masked walk."""
    items, KV, R, hd = q_items.shape
    _, _, _, bs, _ = key_cache.shape
    has_quant = k_dequant is not None
    pages = mixed_pages_per_block(bs, KV, hd, key_cache.dtype.itemsize,
                                  tables.shape[1])
    tables, pools, page_scratch = _walk_operands(
        key_cache, value_cache, tables, k_dequant, v_dequant, pages)
    row_spec = pl.BlockSpec((1, KV, R, hd),
                            lambda j, *_: (j, _i32(0), _i32(0), _i32(0)),
                            memory_space=pltpu.VMEM)
    _assert_mosaic_tileable(row_spec.block_shape, q_items.shape, "mixed rows")
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands, mask_specs = [], []
    if mask is not None:
        # an item's tokens' selections cut into the walk's key blocks, an
        # item's whole in VMEM (4 MB at 64 tokens x 65,536 keys), block i
        # of it read by number
        span, blocks = pages * bs, tables.shape[1] // pages
        operands = [_mask_blocks(mask, blocks, span).transpose(0, 2, 1, 3)]
        mask_specs = [pl.BlockSpec(
            (1, blocks, mask.shape[1], span),
            lambda j, *_: (j, _i32(0), _i32(0), _i32(0)),
            memory_space=pltpu.VMEM)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(items,),
        in_specs=[row_spec] + mask_specs + [hbm] * len(pools),
        out_specs=row_spec,
        scratch_shapes=[
            *page_scratch,
            pltpu.VMEM((KV, R, hd), jnp.float32),
            pltpu.VMEM((KV, R, _STAT_LANES), jnp.float32),
            pltpu.VMEM((KV, R, _STAT_LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _mixed_kernel, sm_scale=np.float32(sm_scale), block_size=int(bs),
        pages=int(pages), group=int(group), small=int(small),
        has_quant=has_quant, block_len=int(block_len), window=int(window),
        masked=mask is not None)
    count_launch()
    call = dict(
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_items.shape, q_items.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_MIXED_VMEM_LIMIT),
        interpret=interpret)
    if mask is None:
        launch = pl.pallas_call(kernel, name="paged_attention_mixed", **call)
    else:
        launch = pl.pallas_call(kernel, name="paged_attention_mixed_masked",
                                **call)
    return launch(tables, past, this, layer, seq, t0, q_items, *operands,
                  *pools)


def paged_attention_packed(q_tok, key_cache, value_cache, block_tables,
                           seq_lens_decoder, seq_lens_this_time, cu_seqlens_q,
                           sm_scale: float, k_dequant=None, v_dequant=None,
                           interpret: Optional[bool] = None, layer=None,
                           block_len: int = 0, window: int = 0, mask=None):
    """Attention of a ragged mixed batch (prefill chunks, decode rows and
    idle slots in one launch) over paged caches, on the packed token
    stream itself.

    q_tok [token_num, KV, G, hd]: sequence b's `seq_lens_this_time[b]`
    tokens lie at rows cu_seqlens_q[b] … of the stream, its token t at
    position `seq_lens_decoder[b] + t`; the caches, tables, scales and
    `layer` are `paged_attention`'s. Returns [token_num, KV, G, hd] in
    q_tok.dtype, rows that are no sequence's token 0.

    `block_len` (static; 0 = causal) is the block length Bd of generation
    by diffusion over blocks: the query at position p sees key j iff
    j < (p // Bd + 1) * Bd and j < past + this (`_see_limit`), so the rows
    of one block see one another whichever tile they fall in. `window`
    (static; 0 = none) keeps of those the last `window` keys, the query's
    own among them; the pages behind every window need not be in the table.
    `mask` (a selection over the table's key positions as bits,
    [token_num, blocks of 128 keys, 4] uint32 as `sparse_index.pack_mask`
    lays them; or None: static) makes it the MASKED WALK: row t sees of the
    keys above only those whose bit is set, and one with none set comes
    back 0; the whole-page walk alone has it.

    Where whole pages can be copied (`whole_pages`) this is the mixed
    walk: the launch runs over work items reckoned here from the lengths
    (`_work_items`), gathers their rows into [items, KV, TQ * G, hd] and
    scatters the output back by the same table. Elsewhere the rows are
    packed per sequence, [B, KV, token_num * G, hd], for the BlockSpec
    walk of `paged_attention`."""
    if (k_dequant is None) != (v_dequant is None):
        raise ValueError("pass both k_dequant and v_dequant or neither")
    token_num, KV, G, hd = q_tok.shape
    B = block_tables.shape[0]
    if interpret is None:
        interpret = not available()
    cu = cu_seqlens_q.astype(jnp.int32).reshape(-1)
    past = seq_lens_decoder.reshape(-1).astype(jnp.int32)
    this = seq_lens_this_time.reshape(-1).astype(jnp.int32)
    tok_idx = jnp.arange(token_num, dtype=jnp.int32)
    tok_b = jnp.clip(jnp.searchsorted(cu, tok_idx, side="right",
                                      method="compare_all") - 1, 0, B - 1)
    tok_local = tok_idx - cu[tok_b]
    tok_valid = (tok_local < this[tok_b])[:, None, None, None]

    if not whole_pages(hd, interpret):
        _refuse_mask(mask, hd)
        row_tok = jnp.clip(cu[:B, None] + tok_idx[None, :], 0, token_num - 1)
        q_pack = q_tok[row_tok].transpose(0, 2, 1, 3, 4)  # [B, KV, tok, G, hd]
        o_pack = paged_attention(
            q_pack.reshape(B, KV, token_num * G, hd), key_cache, value_cache,
            block_tables, past, this, G, sm_scale, k_dequant=k_dequant,
            v_dequant=v_dequant, interpret=interpret, layer=layer,
            block_len=block_len, window=window)
        o_pack = o_pack.reshape(B, KV, token_num, G, hd)
        return jnp.where(tok_valid, o_pack[tok_b, :, tok_local], 0
                         ).astype(q_tok.dtype)

    key_cache, value_cache, layer = _stacked(key_cache, value_cache, layer)
    tq, ts = mixed_tiles(token_num, G, KV, hd)
    items = mixed_items(token_num, B, tq)
    seq, t0, first = _work_items(cu, this, tq, items, token_num)
    row_tok = jnp.clip((cu[seq] + t0)[:, None]
                       + jnp.arange(tq, dtype=jnp.int32)[None, :],
                       0, token_num - 1)                  # [items, tq]
    q_items = q_tok[row_tok].transpose(0, 2, 1, 3, 4)     # [items, KV, tq, G, hd]
    o_items = _mixed_call(
        q_items.reshape(items, KV, tq * G, hd), key_cache, value_cache,
        jnp.maximum(block_tables.astype(jnp.int32), 0), past, this, layer,
        seq, t0, G, ts, sm_scale, k_dequant, v_dequant, interpret,
        block_len, window, None if mask is None else mask[row_tok])
    o_items = o_items.reshape(items, KV, tq, G, hd)
    item = jnp.clip(first[tok_b] + tok_local // tq, 0, items - 1)
    return jnp.where(tok_valid, o_items[item, :, tok_local % tq], 0
                     ).astype(q_tok.dtype)


def paged_attention(q_rows, key_cache, value_cache, block_tables,
                    seq_lens_decoder, seq_lens_this_time, group: int,
                    sm_scale: float, k_dequant=None, v_dequant=None,
                    interpret: Optional[bool] = None, layer=None,
                    block_len: int = 0, window: int = 0, mask=None):
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
    seq_lens_this_time <= 1) take the decode walk where `whole_pages`
    says Mosaic lowers it, any other launch the BlockSpec walk (`_kernel`).
    `block_len` > 0 (static) asks for the block-causal mask
    (`paged_attention_packed`), which the BlockSpec walk has and the
    decode walk has not: one row a sequence is no block. `window` > 0
    (static) keeps the last `window` keys of what a row sees, in every
    walk. `mask` [B, blocks of 128 keys, 4] uint32 (or None: static): the
    decode walk as the MASKED WALK, sequence b's one row seeing only the
    keys whose bit is set (`paged_attention_packed` says the rest).
    """
    if (k_dequant is None) != (v_dequant is None):
        raise ValueError("pass both k_dequant and v_dequant or neither")
    has_quant = k_dequant is not None
    B, KV, rows, hd = q_rows.shape
    if rows <= 0 or group <= 0 or rows % group != 0:
        raise ValueError(f"q_rows rows={rows} must be a positive multiple "
                         f"of group={group}")
    key_cache, value_cache, layer = _stacked(key_cache, value_cache, layer)
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

    if rows == group and whole_pages(hd, interpret):
        if block_len > 1:
            raise ValueError(
                "the decode walk (one query row a sequence) has no "
                f"block-causal mask: block_len={block_len} rows of a block "
                "go through paged_attention_packed")
        return _decode_call(q_rows, key_cache, value_cache, tables, past,
                            this, layer, sm_scale, k_dequant, v_dequant,
                            interpret, window, mask, block_tables)

    _refuse_mask(mask, hd)
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
        group=int(group), has_quant=has_quant, block_len=int(block_len),
        window=int(window))
    count_launch()
    return pl.pallas_call(
        kernel,
        name="paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, rows, hd), q_rows.dtype),
        interpret=interpret,
    )(tables, past, this, layer, *inputs)


def _refuse_mask(mask, head_dim: int) -> None:
    if mask is not None:
        raise NotImplementedError(
            f"a selection mask at head_dim={head_dim}: the whole-page walks "
            "alone have one, and Mosaic copies whole pages at head dims "
            "that are whole lanes (`whole_pages`)")


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
