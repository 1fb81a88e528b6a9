"""Paged attention over LATENT page pools (Pallas): the read and the write
of multi-head latent attention's cache (`models.llama.latent_kv`: one row
(c | k_rope) a position for every head) in the absorbed form, where a
head's query row (q_nope Wkvb,K^T | q_rope) meets the cache row itself and
its output is the probabilities' sum over the rows' first `value_dim`
values (the latents), before Wkvb,V. It is multi-query attention with H
query heads over ONE key row whose leading slice is also the value row.

What differs from `paged_attention.py`, whose two whole-page walks these
follow step for step (`_work_items`, `mixed_items` and the double-buffered
page copies are its own):

- one pool `[L, num_blocks, 1, block_size, W]`, no value pool: a page is
  copied once and read twice, as keys `[span, W]` and as values
  `[span, :value_dim]`;
- the group is all H heads (64 at Kimi-K2's widths), so a decode row is
  already an MXU tile of rows: the small row tile of a mixed launch is one
  token (`_SMALL_TOKENS`), a work item's 32 tokens (2,048 rows), a decode
  walk's key block 512 positions; a work item's blocks of rows are 4.6 MB
  in and out, so the caller sends a mixed tick's one-row sequences through
  the decode launch (`serving_attention.paged_latent_attention`) and the
  items behind the last one in use touch no block;
- both products run on the operands' own 16-bit type with float32
  accumulation (the probabilities are rounded to the pages' type for p.v,
  as flash kernels do): at 64 rows a key the read is near the chip's
  ridge (121 FLOP/B against 240), and float32 products, which the MXU
  makes in several bf16 passes, would put it far on the compute side;
- a static `window` W in both walks (0 = none): the query at position p
  sees keys p - W + 1 .. p, its own among them, and a walk begins at the
  key block that holds the first key its first row sees; the pages behind
  every window need not be in the table (a latent layer with a window,
  dots3-note's sliding kind: 64 heads over rows of 1,088 values in 1,152
  lanes, 1,024 of them the value row);
- at more than 64 heads a work item's row tile holds fewer tokens
  (`mixed_tokens`: 16 at 128 heads), so that a tile stays 2,048 rows;
- no int8 pages and no block-causal mask: no latent model served has them;
- the MASKED WALK (`latent_attention_packed(mask=...)`, static): the mixed
  walk with a selection mask, a row a token over the table's key positions
  (handed in as bits, `sparse_index.pack_mask`, and widened to 8 bits for
  the items' rows alone), cut into the walk's key blocks ([items, key
  blocks, tq, span]: an item's whole mask is one VMEM block, 0.5 MB at 16
  tokens x 33,280 keys). Inside a key block the tile's [tq, span] bytes are
  widened and each token's row is broadcast over its H heads' sublanes (row
  r = t * H + h), then ANDed into what the row sees; a row may see no key
  of a block, so the probabilities are zeroed as under a window. It is the
  third way to read a sparse layer's selected keys (the other two gather):
  which one a row takes is `serving_attention.paged_latent_attention`'s
  rule, whose cost formula, keys x H x 2 x (W + C) / `_WALK_FLOPS` against
  topk x `_GATHER_ROW_S`, reads this walk's measured rate. Without a mask
  the kernel traces to what it was before the mask existed: Kimi's chunk
  walk, dots3's window walks and the dense first chunk are the same
  instructions.

Beside the walks, for a latent layer with a learned sparse index
(`models.llama.IndexSpec`): the page write serves the index keys' pool as
it serves the latent pool (`write_latent_pages`: a row of 128 lanes), and
`index_scores_packed` is the INDEX WALK of a chunk: a work item's tokens'
index heads against its sequence's index keys, ReLU, the heads' weighted
sum, [rows, keys] in float32, a key tile at a time, so that [rows, heads,
keys] never exists; `index_scores_rows` is its ONE-ROW FORM (grid
(sequence, key block): a decode row's index heads against its sequence's
key blocks), the split the latent read makes between its decode launch and
its mixed walk. Both take the stacked index pool [L, num_blocks, 1,
block_size, ID], the layer and the block table as the walks above do, and
copy a key tile's whole pages themselves (`_copies`: a page of 16 rows of
128 lanes is an aligned 4 KB copy; a tile whose pages lie side by side in
the pool, `paged_attention.block_runs` of the table, in ONE copy,
`_run_copy`), double-buffered from one grid step to the next, up to the
last key a row of the item sees: no copy of a sequence's keys by position
exists, and a slot without a row copies nothing. `index_keys_fetched` is
the host's count of those keys, `index_blocks_walked` of the tiles they
come in and of those that are one copy. The
exact selection and the gathered read over the
selected rows are plain XLA (`ops/kernels/sparse_index.py`,
`serving_attention.paged_latent_attention`, which also states the one rule
of which read a row takes: dense, sparse (gathered or the masked walk) or
window).

W is the pool's row width, whole lanes: the engine pads a row of 576
values (512 + 64) to 640 with zeros, which add nothing to a score and are
never read as values. Mosaic takes no whole-page copy out of a pool whose
rows are 4.5 lane tiles wide ("Slice shape along dimension 4 must be
aligned to tiling (128)", tests/test_chip_compile.py), so the choice was
between these 64 idle lanes (a ninth more bytes a key) and the BlockSpec
walk that PRs 28 and 30 measured 18-83 x slower.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..kernels.sparse_index import unpack_mask
from .flash_attention import NEG_INF, _i32, available, count_launch
from .paged_attention import (_STAT_LANES, _loop_i32, _spread_over_heads,
                              _work_items, block_runs, mixed_items)

__all__ = ["latent_attention", "latent_attention_packed",
           "index_scores_packed", "index_scores_rows", "index_keys_fetched",
           "index_blocks_walked",
           "write_latent_pages", "padded_width", "LANES"]

LANES = 128
# key positions of a key block (whole pages), tokens of a work item's row
# tile and of its small row tile (x H rows each)
_DECODE_KEYS = 512
_MIXED_KEYS = 512
_MIXED_TOKENS = 32
_SMALL_TOKENS = 1
_VMEM_LIMIT = 96 << 20


def padded_width(width: int) -> int:
    """A cache row's width in the pool: `width` values in whole lanes."""
    return -(-width // LANES) * LANES


def _pages(keys: int, block_size: int, max_blocks: int) -> int:
    return max(1, min(keys // block_size, max_blocks))


def _copies(tables_ref, b, i, slot, pages: int, layer, pool, buf, sems):
    """The copies that bring key block i of sequence b into buffer `slot`
    page by page: one whole page `pool[layer, page, 0]` = [block_size, W]
    each, wherever the block's pages lie in the pool. (A block whose pages
    lie side by side comes as one copy where a launch knows it:
    `_run_copy`, the index walks.)"""
    return [pltpu.make_async_copy(
        pool.at[layer, tables_ref[b, i * _i32(pages) + _i32(j)], _i32(0)],
        buf.at[slot, _i32(j)], sems.at[slot]) for j in range(pages)]


def _run_copy(tables_ref, b, i, slot, pages: int, layer, pool, buf, sems):
    """Key block i of sequence b where it is a run
    (`paged_attention.block_runs`: its table entries are p, p + 1, ..., p +
    pages - 1): the ONE copy of `pool[layer, p : p + pages, 0]` = [pages,
    block_size, W], the same bytes into the same buffer under the same
    semaphore as `_copies`' `pages` copies."""
    return pltpu.make_async_copy(
        pool.at[layer, pl.ds(tables_ref[b, i * _i32(pages)], pages), _i32(0)],
        buf.at[slot], sems.at[slot])


def _block_products(q, kbuf, slot, ok, sm_scale, value_dim, m_prev, l_prev,
                    acc_prev, windowed: bool = False):
    """One key block's online-softmax step for query rows q [R, W] against
    the block in `kbuf[slot]` [pages, bs, W]; `ok` [R | 1, span] marks the
    keys a row sees. `windowed`: a row may see no key of the walk's first
    blocks (they hold its tile's earlier rows' windows). Returns (m, l,
    acc)."""
    pages, bs, W = kbuf.shape[1:]
    k = kbuf[slot].reshape(pages * bs, W)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.DEFAULT,
                            preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(ok, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # a masked key of a row with a live one gives exp(-1e30 - m) = 0; a
    # row with none yet is a row without a query (key 0 is in every first
    # block), zeroed by the caller
    prob = jnp.exp(s - m_new)
    if windowed:
        # there m_new is still -1e30 and exp(0) = 1 would count every
        # masked key
        prob = jnp.where(ok, prob, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(prob, axis=-1, keepdims=True)
    acc = acc_prev * alpha + jax.lax.dot_general(
        prob.astype(k.dtype), k[:, :value_dim], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc


def _decode_kernel(tables_ref, past_ref, this_ref, layer_ref, q_ref, pool,
                   o_ref, kbuf, sems, acc, m_sc, l_sc, *, sm_scale: float,
                   block_size: int, pages: int, value_dim: int,
                   window: int = 0):
    """One sequence b of a decode launch: its one token's H query rows
    against its live key blocks of `pages` whole pages; under a `window`
    from the block that holds the first key it sees."""
    b = pl.program_id(0)
    layer = layer_ref[0]
    H = acc.shape[0]
    span = pages * block_size
    width = tables_ref.shape[1]
    past = past_ref[b]
    n_blocks = jnp.where(
        this_ref[b] > 0,
        jnp.minimum(jax.lax.div(past + _i32(span), _i32(span)),
                    _i32(width // pages)), _i32(0))
    first = _first_block(past, window, span)

    def copies(i, slot):
        return _copies(tables_ref, b, i, slot, pages, layer, pool, kbuf, sems)

    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc[...] = jnp.zeros_like(acc)

    @pl.when(n_blocks > first)
    def _():
        for c in copies(first, jax.lax.rem(first, _i32(2))):
            c.start()

    def block(i, _):
        slot = jax.lax.rem(i, _i32(2))

        @pl.when(i + _i32(1) < n_blocks)
        def _():
            for c in copies(i + _i32(1), _i32(1) - slot):
                c.start()

        for c in copies(i, slot):
            c.wait()
        kv_abs = (jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
                  + i * _i32(span))
        m, l, a = _block_products(
            q_ref[0], kbuf, slot, _sees(kv_abs, past, window), sm_scale,
            value_dim, m_sc[:, :1], l_sc[:, :1], acc[...])
        acc[...] = a
        m_sc[...] = jnp.broadcast_to(m, (H, _STAT_LANES))
        l_sc[...] = jnp.broadcast_to(l, (H, _STAT_LANES))

    jax.lax.fori_loop(first, n_blocks, block, None)
    l = l_sc[:, :1]
    o_ref[0] = (acc[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _first_block(pos, window: int, span: int):
    """The key block a walk begins at: 0, or under a window the block that
    holds the first key the query at `pos` sees."""
    if not window:
        return _i32(0)
    return jax.lax.div(jnp.maximum(pos - _i32(window - 1), _i32(0)),
                       _i32(span))


def _sees(kv_abs, pos, window: int):
    """Causal, and under a window W the last W keys, the query's own among
    them."""
    ok = kv_abs <= pos
    if window:
        ok &= kv_abs >= pos - _i32(window - 1)
    return ok


def _check(q, pool, value_dim):
    W = pool.shape[-1]
    if pool.ndim != 5 or pool.shape[2] != 1 or q.shape[-1] != W:
        raise ValueError(
            f"latent pool {pool.shape}: pass the stacked [L, nb, 1, bs, W] "
            f"and query rows [..., H, W]={q.shape}")
    if not 0 < value_dim <= W:
        raise ValueError(f"value_dim={value_dim} outside the row of {W}")


def latent_attention(q_rows, pool, block_tables, seq_lens_decoder,
                     seq_lens_this_time, sm_scale: float, layer,
                     value_dim: int, interpret: Optional[bool] = None,
                     window: int = 0):
    """The decode launch: q_rows [B, H, W], one token a sequence at
    position `seq_lens_decoder[b]` (an idle slot: `seq_lens_this_time[b]`
    0), against the pages `block_tables[b]` of `pool[layer]`, which already
    hold the token's own row. `window` W > 0 (static): the last W keys, the
    row's own among them; table entries behind it may be anything inside
    the pool. Returns [B, H, value_dim], idle slots 0."""
    _check(q_rows, pool, value_dim)
    B, H, W = q_rows.shape
    bs = pool.shape[3]
    if interpret is None:
        interpret = not available()
    pages = _pages(_DECODE_KEYS, bs, block_tables.shape[1])
    tables = jnp.maximum(block_tables.astype(jnp.int32), 0)
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % pages)))
    row = lambda w: pl.BlockSpec((1, H, w), lambda b, *_: (b, _i32(0), _i32(0)),
                                 memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(B,),
        in_specs=[row(W), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row(value_dim),
        scratch_shapes=[
            pltpu.VMEM((2, pages, bs, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((H, value_dim), jnp.float32),
            pltpu.VMEM((H, _STAT_LANES), jnp.float32),
            pltpu.VMEM((H, _STAT_LANES), jnp.float32)])
    kernel = functools.partial(
        _decode_kernel, sm_scale=np.float32(sm_scale), block_size=int(bs),
        pages=int(pages), value_dim=int(value_dim), window=int(window))
    count_launch()
    return pl.pallas_call(
        kernel, name="paged_attention_latent_decode", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, value_dim), q_rows.dtype),
        interpret=interpret,
    )(tables, seq_lens_decoder.reshape(-1).astype(jnp.int32),
      seq_lens_this_time.reshape(-1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q_rows, pool)


def _mixed_kernel(tables_ref, past_ref, this_ref, layer_ref, seq_ref, t0_ref,
                  used_ref, q_ref, *refs,
                  sm_scale: float, block_size: int, pages: int, heads: int,
                  small: int, value_dim: int, window: int = 0,
                  masked: bool = False):
    """One work item j of a mixed launch: the query rows of sequence
    seq[j] from chunk offset t0[j] on (row r = t * H + h), against that
    sequence's key blocks up to the tile's own causal limit. An item with
    at most `small` live tokens computes on its first small * H rows. An
    item behind the last one in use (`used_ref`) does nothing: its blocks
    are the last used item's (the index maps say so), which it must leave
    as they are. `masked` (static): the MASKED WALK; the first of `refs`
    is then the item's selection mask [1, key blocks, tq, span] int8, and
    a row sees of a key block only the keys its token selected."""
    del used_ref
    mask_ref, refs = (refs[0], refs[1:]) if masked else (None, refs)
    pool, o_ref, kbuf, sems, acc, m_sc, l_sc = refs
    j = pl.program_id(0)
    b = seq_ref[j]
    t0 = t0_ref[j]
    layer = layer_ref[0]
    R = acc.shape[0]
    span = pages * block_size
    width = tables_ref.shape[1]
    past = past_ref[b]
    live = jnp.clip(this_ref[b] - t0, _i32(0), _i32(R // heads))
    n_blocks = jnp.where(
        live > 0,
        jnp.minimum(jax.lax.div(past + t0 + live + _i32(span - 1),
                                _i32(span)), _i32(width // pages)), _i32(0))
    # under a window the tile's walk begins at the block that holds the
    # first key its first row sees
    first = _first_block(past + t0, window, span)

    def fetch(i, slot, wait=False):
        def page(p):
            c = pltpu.make_async_copy(
                pool.at[layer, tables_ref[b, i * _i32(pages) + p], _i32(0)],
                kbuf.at[slot, p], sems.at[slot])
            c.wait() if wait else c.start()
        _loop_i32(pages, page)

    def walk(rows):
        t = jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0),
                        _i32(heads))
        # a row's own position; rows without a query sit before every key
        pos = jnp.where(t < live, past + t0 + t, _i32(-1))       # [rows, 1]
        m_sc[:rows] = jnp.full((rows, _STAT_LANES), NEG_INF, jnp.float32)
        l_sc[:rows] = jnp.zeros((rows, _STAT_LANES), jnp.float32)
        acc[:rows] = jnp.zeros((rows, value_dim), jnp.float32)
        pl.when(n_blocks > first)(
            lambda: fetch(first, jax.lax.rem(first, _i32(2))))

        def block(i, _):
            slot = jax.lax.rem(i, _i32(2))
            pl.when(i + _i32(1) < n_blocks)(
                lambda: fetch(i + _i32(1), _i32(1) - slot))
            fetch(i, slot, wait=True)
            kv_abs = (jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
                      + i * _i32(span))
            ok = _sees(kv_abs, pos, window)
            if masked:
                ok &= _spread_over_heads(mask_ref[0, i], rows // heads, heads)
            # (under a mask, as under a window, a row may see no key of
            # the walk's first blocks)
            m, l, a = _block_products(
                q_ref[0, :rows], kbuf, slot, ok, sm_scale, value_dim,
                m_sc[:rows, :1], l_sc[:rows, :1], acc[:rows],
                windowed=window > 0 or masked)
            acc[:rows] = a
            m_sc[:rows] = jnp.broadcast_to(m, (rows, _STAT_LANES))
            l_sc[:rows] = jnp.broadcast_to(l, (rows, _STAT_LANES))

        jax.lax.fori_loop(first, n_blocks, block, None)
        l = l_sc[:rows, :1]
        out = acc[:rows] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, :rows] = jnp.where(pos >= 0, out, 0.0).astype(o_ref.dtype)
        if rows < R:
            o_ref[0, rows:] = jnp.zeros((R - rows, value_dim), o_ref.dtype)

    if 0 < small * heads < R:
        pl.when((live > 0) & (live <= small))(lambda: walk(small * heads))
        pl.when(live > small)(lambda: walk(R))
    else:
        pl.when(live > 0)(lambda: walk(R))


def mixed_tokens(token_num: int, heads: int = 64) -> int:
    """Tokens of a work item's row tile: `_MIXED_TOKENS` at up to 64
    heads, fewer above, so that a tile keeps its `_MIXED_TOKENS` x 64 rows
    (at 128 heads twice the rows would be 9 MB of queries in and 8 MB of
    accumulator beside a 8 MB score block)."""
    tokens = max(1, _MIXED_TOKENS * 64 // max(heads, 64))
    return max(1, min(tokens, token_num))


def latent_attention_packed(q_tok, pool, block_tables, seq_lens_decoder,
                            seq_lens_this_time, cu_seqlens_q,
                            sm_scale: float, layer, value_dim: int,
                            interpret: Optional[bool] = None,
                            window: int = 0, mask=None):
    """The mixed launch, on the packed token stream: q_tok [token_num, H,
    W], sequence b's `seq_lens_this_time[b]` tokens at rows cu_seqlens_q[b]
    on, its token t at position `seq_lens_decoder[b] + t`, causal, under
    `window` W > 0 (static) over the last W keys. `mask` (a selection over
    the table's key positions as bits, [token_num, blocks of 128 keys, 4]
    uint32 as `sparse_index.pack_mask` lays them; or None: static) makes
    it the MASKED WALK: row t sees of the keys above only those whose bit
    is set, and one with none set comes back 0. Without a mask nothing of
    it is traced: no operand, no scratch, the same kernel as before it
    existed. Returns [token_num, H, value_dim], rows that are no
    sequence's token 0."""
    _check(q_tok, pool, value_dim)
    token_num, H, W = q_tok.shape
    B = block_tables.shape[0]
    bs = pool.shape[3]
    if interpret is None:
        interpret = not available()
    cu = cu_seqlens_q.astype(jnp.int32).reshape(-1)
    past = seq_lens_decoder.reshape(-1).astype(jnp.int32)
    this = seq_lens_this_time.reshape(-1).astype(jnp.int32)
    tok_idx = jnp.arange(token_num, dtype=jnp.int32)
    tok_b = jnp.clip(jnp.searchsorted(cu, tok_idx, side="right",
                                      method="compare_all") - 1, 0, B - 1)
    tok_local = tok_idx - cu[tok_b]
    tok_valid = (tok_local < this[tok_b])[:, None, None]

    tq = mixed_tokens(token_num, H)
    items = mixed_items(token_num, B, tq)
    seq, t0, first = _work_items(cu, this, tq, items, token_num)
    row_tok = jnp.clip((cu[seq] + t0)[:, None]
                       + jnp.arange(tq, dtype=jnp.int32)[None, :],
                       0, token_num - 1)                          # [items, tq]
    q_items = q_tok[row_tok].reshape(items, tq * H, W)
    pages = _pages(_MIXED_KEYS, bs, block_tables.shape[1])
    tables = jnp.maximum(block_tables.astype(jnp.int32), 0)
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % pages)))
    # the items in use come first; one behind them names the last one's
    # blocks, so that its 4.6 MB of rows are neither fetched nor written
    used = jnp.maximum(jnp.sum(-(-this // tq)), 1).astype(jnp.int32)
    row = lambda w: pl.BlockSpec(
        (1, tq * H, w),
        lambda j, tb, pa, th, ly, sq, t0_, used_: (
            jnp.minimum(j, used_[0] - _i32(1)), _i32(0), _i32(0)),
        memory_space=pltpu.VMEM)
    operands, in_specs = [q_items], [row(W)]
    if mask is not None:
        # an item's tokens' mask rows cut into the walk's key blocks:
        # [items, key blocks, tq, span], an item's whole in VMEM (0.5 MB at
        # 16 tokens x 33,280 keys), block i of it read by number
        span, blocks = pages * bs, tables.shape[1] // pages
        m = unpack_mask(mask[row_tok])                        # [items, tq, S]
        m = jnp.pad(m, ((0, 0), (0, 0), (0, max(
            blocks * span - m.shape[-1], 0))))[..., :blocks * span]
        operands.append(m.reshape(items, tq, blocks, span
                                  ).transpose(0, 2, 1, 3))
        in_specs.append(pl.BlockSpec(
            (1, blocks, tq, span),
            lambda j, tb, pa, th, ly, sq, t0_, used_: (
                jnp.minimum(j, used_[0] - _i32(1)), _i32(0), _i32(0),
                _i32(0)), memory_space=pltpu.VMEM))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7, grid=(items,),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row(value_dim),
        scratch_shapes=[
            pltpu.VMEM((2, pages, bs, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((tq * H, value_dim), jnp.float32),
            pltpu.VMEM((tq * H, _STAT_LANES), jnp.float32),
            pltpu.VMEM((tq * H, _STAT_LANES), jnp.float32)])
    kernel = functools.partial(
        _mixed_kernel, sm_scale=np.float32(sm_scale), block_size=int(bs),
        pages=int(pages), heads=int(H), small=min(_SMALL_TOKENS, tq),
        value_dim=int(value_dim), window=int(window),
        masked=mask is not None)
    count_launch()
    call = dict(
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((items, tq * H, value_dim),
                                       q_tok.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)
    # (a site a name, each a literal: what a trace calls the launch)
    if mask is None:
        launch = pl.pallas_call(kernel, name="paged_attention_latent_mixed",
                                **call)
    else:
        launch = pl.pallas_call(kernel, name="paged_attention_latent_masked",
                                **call)
    o_items = launch(tables, past, this,
                     jnp.asarray(layer, jnp.int32).reshape(1), seq, t0,
                     used.reshape(1), *operands, pool)
    o_items = o_items.reshape(items, tq, H, value_dim)
    item = jnp.clip(first[tok_b] + tok_local // tq, 0, items - 1)
    return jnp.where(tok_valid, o_items[item, tok_local % tq], 0
                     ).astype(q_tok.dtype)


def _write_kernel(layer_ref, page_ref, lo_ref, hi_ref, new_ref, in_ref,
                  out_ref):
    """One touched page: slots [lo, hi) take the staged rows, the others
    keep what the page held (`paged_attention._write_kernel`, one pool)."""
    del layer_ref, page_ref
    j = pl.program_id(0)
    slot = jax.lax.broadcasted_iota(jnp.int32, in_ref.shape, 2)
    fresh = (slot >= lo_ref[j]) & (slot < hi_ref[j])
    out_ref[...] = jnp.where(fresh, new_ref[...].astype(jnp.float32),
                             in_ref[...].astype(jnp.float32)
                             ).astype(out_ref.dtype)


def write_latent_pages(pool, layer, pages, lo, hi, new,
                       interpret: Optional[bool] = None):
    """`paged_attention.write_pages` for the one latent pool [L, nb, 1,
    bs, W], aliased input to output: for each plan entry j, slots [lo[j],
    hi[j]) of `pool[layer, pages[j]]` take `new[j]`'s rows ([n, 1, bs, W])
    and the other slots keep theirs. Returns the pool."""
    _, _, _, bs, W = pool.shape
    n = pages.shape[0]
    if interpret is None:
        interpret = not available()
    mem = {"memory_space": pltpu.VMEM}
    new_spec = pl.BlockSpec(
        (1, 1, bs, W),
        lambda j, ly, pg, lo_, hi_: (j, _i32(0), _i32(0), _i32(0)), **mem)
    page_spec = pl.BlockSpec(
        (None, 1, 1, bs, W),
        lambda j, ly, pg, lo_, hi_: (ly[0], pg[j], _i32(0), _i32(0),
                                     _i32(0)), **mem)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(n,),
        in_specs=[new_spec, page_spec], out_specs=page_spec)
    count_launch()
    return pl.pallas_call(
        _write_kernel, name="paged_cache_write_latent", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operands count the four prefetched scalars: the pool is 5
        input_output_aliases={5: 0}, interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), pages.astype(jnp.int32),
      lo.astype(jnp.int32), hi.astype(jnp.int32), new.astype(pool.dtype),
      pool)


# ---------------------------------------------------------------------------
# the index's two launches (a latent layer's sparse index)
# ---------------------------------------------------------------------------
_INDEX_KEYS = 512       # index keys of a key tile of a chunk's walk
_INDEX_TOKENS = 32      # tokens of a work item's row tile (x IH rows)
# index keys of a key block of the one-row form: once a block in a run is
# one copy, its grid steps are what is left of the launch. On the v5e, one
# layer, 16 sequences of 33.8k-64.5k keys whose last 130 pages are dealt
# page by page: 0.52 ms at 1,024 keys (64 pages), 0.43 at 2,048, 0.44 at
# 4,096 (whose blocks are runs less often); tables with no run at all 1.15,
# 1.08, 1.07 (PERF.md section 6, PR 53)
_INDEX_ROW_KEYS = 2048


def _key_tile(tables_ref, runs_ref, b, i, more, layer, pool, kbuf, sems):
    """Key tile i of sequence b, [pages * bs, ID], out of its pages of
    `pool[layer]`: the tiles of one sequence come to consecutive grid steps
    from tile 0 on, so a tile's page copies were started by the step before
    it (tile 0's start here) and tile i + 1's start now where `more` says a
    step will wait for them; buffer i % 2. A tile that `runs_ref` [B,
    tiles] marks (`block_runs`: its pages lie side by side in the pool, as
    a prompt's fresh pages do) comes in ONE copy, `_run_copy`: a page of
    4 KB a copy leaves the launch bound by how fast descriptors are issued
    (21 ns a copy, PERF.md section 6, PR 53). The page-by-page copies of
    any other tile are written out once (a loop over them costs the
    one-row form half its speed, PERF.md section 6, PR 45) at ONE site, a
    loop over the zero to two tiles that start in this step, and either
    form is waited for as one, by a descriptor of the whole buffer (a DMA
    semaphore counts bytes): a launch holds pages + 2 descriptors, not
    three times the pages, which is what its lowering costs every
    start-up."""
    _, pages, bs, ID = kbuf.shape

    def start(t):
        at = (tables_ref, b, t, jax.lax.rem(t, _i32(2)), pages, layer, pool,
              kbuf, sems)
        run = runs_ref[b, t] != 0
        pl.when(run)(lambda: _run_copy(*at).start())

        @pl.when(jnp.logical_not(run))
        def _():
            for c in _copies(*at):
                c.start()
        return t + _i32(1)

    jax.lax.while_loop(
        lambda t: t < jnp.where(more, i + _i32(2), i + _i32(1)), start,
        jnp.where(i == 0, _i32(0), i + _i32(1)))
    slot = jax.lax.rem(i, _i32(2))
    pltpu.make_async_copy(kbuf.at[slot], kbuf.at[slot], sems.at[slot]).wait()
    return kbuf[slot].reshape(pages * bs, ID)


def _score_tile(tables_ref, runs_ref, b, i, rows, last, layer_ref, q_ref,
                w_ref, pool, o_ref, kbuf, sems):
    """Key tile i of sequence b for the query rows q_ref [1, tq * IH, ID]
    (row r = t * IH + h; w_ref their float32 head weights), the last of
    which sits at position `last`: I(t, s) = sum_h w[t, h] ReLU(q[t, h] .
    k[s]) into o_ref [1, tq, keys]. A tile wholly behind `last`, and every
    tile where `rows` is false, is written as zeros: nothing is copied for
    it and nothing multiplied."""
    tq, keys = o_ref.shape[1:]
    needed = rows & (i * _i32(keys) <= last)

    @pl.when(needed)
    def _():
        k = _key_tile(tables_ref, runs_ref, b, i,
                      (i + _i32(1)) * _i32(keys) <= last, layer_ref[0], pool,
                      kbuf, sems)
        s = jax.lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.DEFAULT,
                                preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 0.0) * w_ref[0]                 # [tq * IH, keys]
        o_ref[0] = jnp.sum(s.reshape(tq, -1, keys), axis=1)

    @pl.when(jnp.logical_not(needed))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _index_kernel(tables_ref, runs_ref, seq_ref, t0_ref, past_ref, this_ref,
                  layer_ref, q_ref, w_ref, pool, o_ref, kbuf, sems):
    """Key tile i of work item j: the item's tokens' index heads against
    one tile of its sequence's index keys, up to the item's last row."""
    j, i = pl.program_id(0), pl.program_id(1)
    b = seq_ref[j]
    live = jnp.clip(this_ref[b] - t0_ref[j], _i32(0), _i32(o_ref.shape[1]))
    _score_tile(tables_ref, runs_ref, b, i, live > 0,
                past_ref[b] + t0_ref[j] + live - _i32(1), layer_ref, q_ref,
                w_ref, pool, o_ref, kbuf, sems)


def _index_row_kernel(tables_ref, runs_ref, past_ref, this_ref, layer_ref,
                      q_ref, w_ref, pool, o_ref, kbuf, sems):
    """Key block i of sequence b of the one-row form: its one row's index
    heads (a row tile of one token) against one block of its index keys,
    up to its own position."""
    b, i = pl.program_id(0), pl.program_id(1)
    _score_tile(tables_ref, runs_ref, b, i, this_ref[b] > 0, past_ref[b],
                layer_ref, q_ref, w_ref, pool, o_ref, kbuf, sems)


def index_tokens(token_num: int) -> int:
    return max(1, min(_INDEX_TOKENS, token_num))


def _index_tables(pool, block_tables, keys: int):
    """The index launches' view of a block table: (tables with -1 made 0
    and padded to whole key tiles, which of the tiles are runs
    (`block_runs`, [B, tiles] int32), pages of a tile, tiles): a tile is
    `keys` index keys in whole pages, or the whole table where that is
    shorter."""
    if pool.ndim != 5 or pool.shape[2] != 1:
        raise ValueError(f"index pool {pool.shape}: pass the stacked "
                         "[L, nb, 1, bs, ID]")
    pages = _pages(keys, pool.shape[3], block_tables.shape[1])
    runs = block_runs(block_tables, pages, pool.shape[1])
    tables = jnp.maximum(block_tables.astype(jnp.int32), 0)
    tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % pages)))
    return tables, runs, pages, tables.shape[1] // pages


def _index_trips(past, this, token_num: int, block_size: int,
                 max_blocks: int):
    """The trip counts of `_index_row_kernel` and `_index_kernel`, from
    their own constants, for the sequences that SELECT (`past`, `this`
    numpy [n]), as a list of (places in `past` [m], the pages of a key
    block of the launch that takes them, their trips [m]): the one-row
    sequences together, each the key blocks of the one-row form up to its
    own position; then every chunk, a place a work item of `index_tokens`
    rows, each the key tiles up to its last row."""
    past, this = np.asarray(past, np.int64), np.asarray(this, np.int64)
    pr = _pages(_INDEX_ROW_KEYS, block_size, max_blocks)
    pk = _pages(_INDEX_KEYS, block_size, max_blocks)
    tq = index_tokens(token_num)
    one = np.flatnonzero(this == 1)
    out = [(one, pr, past[one] // (pr * block_size) + 1)]
    for at in np.flatnonzero(this > 1).tolist():
        n = int(this[at])
        last = past[at] + np.minimum(np.arange(0, n, tq) + tq, n) - 1
        out.append((np.full(len(last), at), pk, last // (pk * block_size) + 1))
    return out


def index_keys_fetched(past, this, token_num: int, block_size: int,
                       max_blocks: int) -> int:
    """The index keys the two launches copy out of their pages for one
    layer, reckoned on the host from the lengths of the sequences that
    SELECT (`past`, `this` numpy [n]; the others copy nothing): a one-row
    sequence its past + 1 keys in whole key blocks of the one-row form; a
    chunk's every work item of `index_tokens` rows the keys up to its last
    row in whole key tiles (a chunk's later items read its earlier keys
    again: over `index_keys` this is how many times a key is read). The
    trip counts of `_index_row_kernel` and `_index_kernel`, from the same
    constants (`_index_trips`)."""
    return sum(int(trips.sum()) * pages * block_size
               for _, pages, trips in _index_trips(
                   past, this, token_num, block_size, max_blocks))


def index_blocks_walked(past, this, tables, token_num: int, block_size: int,
                        num_blocks: int):
    """(key blocks the two index launches fetch for one layer, those of
    them that come in ONE copy), reckoned on the host from the lengths and
    the block tables (numpy [n, max_blocks], -1 = no page) of the
    sequences that select: `index_keys_fetched`'s trips, each block judged
    by `block_runs`, the rule the launches' prefetched plane is made by."""
    tables = np.asarray(tables)
    seen = {}       # pages of a key block -> runs up to each block of a row
    blocks = run = 0
    for at, pages, trips in _index_trips(past, this, token_num, block_size,
                                         tables.shape[1]):
        if not len(at):
            continue
        if pages not in seen:
            seen[pages] = np.cumsum(block_runs(tables, pages, num_blocks,
                                               xp=np), axis=1)
        blocks += int(trips.sum())
        run += int(seen[pages][at, trips - 1].sum())    # (a trip is >= 1)
    return blocks, run


def index_scores_packed(qi_tok, w_tok, pool, block_tables, seq_lens_decoder,
                        seq_lens_this_time, cu_seqlens_q, layer,
                        interpret: Optional[bool] = None):
    """The index walk of the packed token stream's chunks: qi_tok
    [token_num, IH, ID] index queries, w_tok [token_num, IH] float32 head
    weights, against the index keys in the pages `block_tables[b]` of the
    stacked index pool `pool[layer]` ([L, nb, 1, bs, ID]: a key tile is
    `_INDEX_KEYS` keys, whole pages copied inside the launch as the latent
    walks copy theirs, double-buffered; no copy of a sequence's keys by
    position exists). Returns I [token_num, max_blocks * bs] float32; what
    lies behind a row's own position is not a score (zeros or a later
    row's) and the caller masks it."""
    token_num, IH, ID = qi_tok.shape
    B, bs = block_tables.shape[0], pool.shape[3]
    S = block_tables.shape[1] * bs
    if interpret is None:
        interpret = not available()
    cu = cu_seqlens_q.astype(jnp.int32).reshape(-1)
    past = seq_lens_decoder.reshape(-1).astype(jnp.int32)
    this = seq_lens_this_time.reshape(-1).astype(jnp.int32)
    tok_idx = jnp.arange(token_num, dtype=jnp.int32)
    tok_b = jnp.clip(jnp.searchsorted(cu, tok_idx, side="right",
                                      method="compare_all") - 1, 0, B - 1)
    tok_local = tok_idx - cu[tok_b]
    tq = index_tokens(token_num)
    tables, runs, pages, tiles = _index_tables(pool, block_tables,
                                               _INDEX_KEYS)
    tk = pages * bs
    items = mixed_items(token_num, B, tq)
    seq, t0, first = _work_items(cu, this, tq, items, token_num)
    row_tok = jnp.clip((cu[seq] + t0)[:, None]
                       + jnp.arange(tq, dtype=jnp.int32)[None, :],
                       0, token_num - 1)                          # [items, tq]
    q_items = qi_tok[row_tok].reshape(items, tq * IH, ID)
    w_items = w_tok.astype(jnp.float32)[row_tok].reshape(items, tq * IH, 1)
    rows = lambda w: pl.BlockSpec(
        (1, tq * IH, w), lambda j, i, *_: (j, _i32(0), _i32(0)),
        memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7, grid=(items, tiles),
        in_specs=[rows(ID), rows(1), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, tq, tk), lambda j, i, *_: (j, _i32(0), i),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((2, pages, bs, ID), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    count_launch()
    o_items = pl.pallas_call(
        _index_kernel, name="paged_index_scores_chunk", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((items, tq, tiles * tk), jnp.float32),
        interpret=interpret,
    )(tables, runs, seq, t0, past, this,
      jnp.asarray(layer, jnp.int32).reshape(1), q_items, w_items, pool)
    item = jnp.clip(first[tok_b] + tok_local // tq, 0, items - 1)
    return o_items[item, tok_local % tq, :S]


def index_scores_rows(qi_rows, w_rows, pool, block_tables, seq_lens_decoder,
                      seq_lens_this_time, layer,
                      interpret: Optional[bool] = None):
    """The one-row form of the index walk, as the decode launch is the
    latent walk's: qi_rows [B, IH, ID] the index queries of each sequence's
    one row at position `seq_lens_decoder[b]` (a slot without one:
    `seq_lens_this_time[b]` 0), w_rows [B, IH] float32 head weights,
    against the index keys in the pages `block_tables[b]` of `pool[layer]`,
    a key block of `_INDEX_ROW_KEYS` keys a grid step, whole pages copied
    inside the launch. Returns I [B, max_blocks * bs] float32: the row's
    scores up to its key block's end (behind its own position they are not
    scores and the caller masks them), zeros behind it and in a slot
    without a row."""
    B, IH, ID = qi_rows.shape
    bs = pool.shape[3]
    S = block_tables.shape[1] * bs
    if interpret is None:
        interpret = not available()
    tables, runs, pages, blocks = _index_tables(pool, block_tables,
                                                _INDEX_ROW_KEYS)
    keys = pages * bs
    row = lambda w: pl.BlockSpec(
        (1, IH, w), lambda b, i, *_: (b, _i32(0), _i32(0)),
        memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(B, blocks),
        in_specs=[row(ID), row(1), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, keys), lambda b, i, *_: (b, _i32(0), i),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((2, pages, bs, ID), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    count_launch()
    return pl.pallas_call(
        _index_row_kernel, name="paged_index_scores_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, blocks * keys), jnp.float32),
        interpret=interpret,
    )(tables, runs, seq_lens_decoder.reshape(-1).astype(jnp.int32),
      seq_lens_this_time.reshape(-1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qi_rows,
      w_rows.astype(jnp.float32)[..., None], pool)[:, 0, :S]
