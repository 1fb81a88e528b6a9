"""The learned sparse index of a layer (DeepSeek-V3.2's lightning indexer,
`models.llama.IndexSpec`: over a latent cache, dots3-note's, or over the
heads' own keys and values, Keye-VL-2.0's), the parts that are plain XLA:
the scores of a row's index heads against index keys, the EXACT selection
of a row's k best visible keys, and that selection as a list of positions.

    I(t, s) = sum_j w[t, j] * ReLU(qI[t, j] . kI[s])          (float32)
    S_t     = the k visible s of largest I(t, s), ties to the lower s

The selection sorts nothing. `lax.top_k` and `lax.sort` at k = 2,048 of
33,280 keys for 2,047 rows are the slowest operations a tick could hold
(PERF.md section 6, PR 43, has the chip's readings of both beside this
form's), and `lax.approx_max_k` is another model. Instead the k-th largest
score's order key is found bit by bit, in 32 counting passes over the
scores (`select_topk`), and the mask, packed a bit a key (`pack_mask`: the
form in which the engine hands a selection on), becomes positions by
popcounts over blocks of 128 keys and one exact product that hands each
slot its block (`selected_positions`): compares, adds, selects and a
matmul.

WHO RUNS `select_topk` (since PR 55). The stock path of
`serving_attention.paged_index_select` (`use_pallas` False: every CPU
test, the reference-side form) and the model's own forward
(`models.llama`). Each of its 32 passes reads all of [rows, keys] from HBM
and its ties take a `cumsum` over the keys, so on the Pallas read path the
same rule runs as one launch that holds a block of rows in VMEM
(`ops/pallas/index_select.select_bits`: the k-th key, the ties' cut and the
selection already in `pack_mask`'s bits), `select_topk`'s set bit for bit
(`tests/test_dots3_paged.py`). `pack_mask` stays the stock path's and the
format's definition; `selected_positions` and `index_scores` serve both
paths.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["index_scores", "select_topk", "pack_mask", "unpack_mask",
           "selected_positions"]

BLOCK = 128         # keys of a block of `selected_positions`


def index_scores(qi: jax.Array, ki: jax.Array, w: jax.Array) -> jax.Array:
    """qi [T, IH, ID], ki [S, ID] (or [T, S, ID]: a key set a row), w
    [T, IH] float32 -> I [T, S] float32; the products on the operands' own
    type with float32 accumulation."""
    eq = "thd,sd->ths" if ki.ndim == 2 else "thd,tsd->ths"
    s = jnp.einsum(eq, qi, ki, preferred_element_type=jnp.float32)
    return jnp.sum(jnp.maximum(s, 0.0) * w[..., None], axis=1)


def _order_key(x: jax.Array) -> jax.Array:
    """float32 -> uint32 whose unsigned order is the floats' order (-0.0
    first made +0.0, so that equal floats have equal keys). No float but
    a NaN has the key 0, which `select_topk` gives the keys a row does
    not see."""
    x = jnp.where(x == 0.0, 0.0, x.astype(jnp.float32))
    b = lax.bitcast_convert_type(x, jnp.int32)
    b = b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))
    return lax.bitcast_convert_type(b, jnp.uint32) ^ jnp.uint32(0x80000000)


def select_topk(scores: jax.Array, visible: jax.Array, k: int) -> jax.Array:
    """Of row t's visible keys (`visible` [T, S] bool) the k of largest
    `scores` [T, S] float32, ties to the lower position; every visible key
    of a row that sees at most k. Returns the selected set as a mask
    [T, S]. The k-th largest order key is the largest u with at least k
    keys >= u, built from its top bit down; everything above it is taken,
    and of the keys equal to it the first by position that still fit. The
    rule of the selection wherever it runs, and the form the stock path and
    the model's forward run; a tick on the Pallas read path runs
    `pallas.index_select.select_bits`, which is tested against this."""
    u = jnp.where(visible, _order_key(scores), jnp.uint32(0))

    def bit(i, best):
        cand = best | (jnp.uint32(1) << (jnp.uint32(31)
                                         - i.astype(jnp.uint32)))
        n = jnp.sum(u >= cand[:, None], axis=1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, best)

    kth = lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[:1], jnp.uint32))
    above = u > kth[:, None]
    tied = (u == kth[:, None]) & visible
    room = k - jnp.sum(above, axis=1, dtype=jnp.int32)
    return above | (tied & (jnp.cumsum(tied, axis=1, dtype=jnp.int32)
                            <= room[:, None]))


def pack_mask(mask: jax.Array) -> jax.Array:
    """A selection mask [T, S] bool as bits: [T, nb, 4] uint32, nb blocks of
    `BLOCK` keys (S padded up with keys not selected), bit j of word w of
    block b the key b * 128 + w * 32 + j. What `selected_positions` counts
    in, and the form in which a selection is handed on (33,280 keys a row
    in 4 KB)."""
    T, S = mask.shape
    mask = jnp.pad(mask, ((0, 0), (0, -S % BLOCK)))
    bits = mask.reshape(T, -1, 4, 32).astype(jnp.uint32)
    return jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def unpack_mask(words: jax.Array) -> jax.Array:
    """`pack_mask`'s words [..., nb, 4] uint32 as a byte a key, [..., nb *
    128] int8 (1: selected)."""
    u8 = jnp.uint8      # a word's four bytes first: nothing wider than a
    #                     byte a key is ever laid out
    octets = ((words[..., None] >> jnp.arange(0, 32, 8, dtype=jnp.uint32))
              & 0xFF).astype(u8)
    bits = (octets[..., None] >> jnp.arange(8, dtype=u8)) & u8(1)
    return bits.astype(jnp.int8).reshape(*words.shape[:-2], -1)


_SPREAD_ROWS = 256      # rows of a block of `selected_positions`' product


def selected_positions(words: jax.Array, k: int,
                       carry: Optional[jax.Array] = None):
    """A selection's bits (`pack_mask`'s `words` [T, nb, 4] uint32) with at
    most k keys a row as positions [T, k] int32, ascending, -1 behind a
    row's last; with `carry` [T, nb, C] int32 (values under 2 ** 24 that
    belong to a row's blocks of 128 keys: a page table cut into eights),
    also each slot's block's values [C, T, k].

    No sort, no gather and no scatter: on this chip a gather of one word a
    slot and a scatter of one row a block are both the slowest way to move
    4 million words (PERF.md section 6, PR 43 has the readings). The keys
    are counted in blocks of 128 (four 32-bit words of mask bits and their
    popcounts). What a slot needs of its block (the four words as eight
    halves, the count of keys before it, the block's number, `carry`) comes
    to it through a PRODUCT: the slot's one-hot row over the blocks (block
    b's keys fill the slots from the count before it up to its running
    count) times the blocks' values, in float32 at the highest precision,
    which is exact for one term of under 2 ** 24; `_SPREAD_ROWS` rows at a
    time, so that the one-hot rows of a tick are never all there. Inside
    the block the slot's key is the (slot - count before)-th set bit: the
    word by the words' running popcounts, the bit by five halvings of the
    word, each a popcount of its lower half; selects, no indexing."""
    u32, i32, f32 = jnp.uint32, jnp.int32, jnp.float32
    T, nb, _ = words.shape
    count = jnp.sum(lax.population_count(words).astype(i32), axis=-1,
                    dtype=i32)
    upto = jnp.cumsum(count, axis=-1, dtype=i32)                # [T, nb]
    before = upto - count
    per_block = [(words & u32(0xFFFF)).astype(f32),
                 (words >> u32(16)).astype(f32), before[..., None].astype(f32),
                 jnp.broadcast_to(jnp.arange(nb, dtype=f32)[None, :, None],
                                  (T, nb, 1))]
    if carry is not None:
        per_block.append(carry.astype(f32))
    per_block = jnp.concatenate(per_block, axis=-1)             # [T, nb, C']
    slot = jnp.arange(k, dtype=i32)

    def spread(args):
        lo, hi, values = args                       # [R, nb], [R, nb, C']
        mine = ((lo[:, None, :] <= slot[None, :, None])
                & (slot[None, :, None] < hi[:, None, :])).astype(f32)
        return jnp.einsum("rkb,rbc->crk", mine, values,
                          precision=lax.Precision.HIGHEST,
                          preferred_element_type=f32)

    if T <= _SPREAD_ROWS:
        mine = spread((before, upto, per_block))
    else:
        more = -T % _SPREAD_ROWS
        cut = lambda a: jnp.pad(
            a, ((0, more),) + ((0, 0),) * (a.ndim - 1)).reshape(
            -1, _SPREAD_ROWS, *a.shape[1:])
        mine = lax.map(spread, (cut(before), cut(upto), cut(per_block)))
        mine = jnp.moveaxis(mine, 0, 1).reshape(mine.shape[1], -1, k)[:, :T]
    mine = mine.astype(i32)                                     # [C', T, k]
    w = [(mine[i].astype(u32) | (mine[4 + i].astype(u32) << u32(16)))
         for i in range(4)]
    slot = slot[None]
    rank = slot - mine[8]
    block = mine[9]
    ones = [lax.population_count(x).astype(i32) for x in w]
    word = jnp.zeros_like(rank)
    x = w[0]
    for i in range(1, 4):
        past = (rank >= ones[i - 1]) & (word == i - 1)
        rank = jnp.where(past, rank - ones[i - 1], rank)
        word = jnp.where(past, i, word)
        x = jnp.where(past, w[i], x)
    bit = jnp.zeros_like(rank)
    for width in (16, 8, 4, 2, 1):
        low = lax.population_count(
            (x >> bit.astype(u32)) & u32((1 << width) - 1)).astype(i32)
        up = rank >= low
        bit = jnp.where(up, bit + width, bit)
        rank = jnp.where(up, rank - low, rank)
    live = slot < upto[:, -1:]
    pos = jnp.where(live, block * BLOCK + word * 32 + bit, -1)
    return pos if carry is None else (pos, mine[10:])
