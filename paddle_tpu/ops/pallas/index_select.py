"""The sparse index's exact selection as ONE launch (Pallas): the search
for a row's k-th largest order key, the cut among the keys tied with it and
the selection's bits, over a block of rows whose scores are fetched from
HBM once.

`sparse_index.select_topk` is the rule and the stock form: 32 counting
passes, each a reduce over [rows, keys] in HBM, then a `cumsum` over the
keys for the ties and `pack_mask`'s reduce over a minor dimension of 32.
Here a work item holds `_ROWS` rows of scores in VMEM (8 sublanes x S
lanes: 2 MB at 65,536 keys), makes their order keys once
(`sparse_index._order_key`'s rule as a SIGNED word, so that every compare
is a signed one: the unsigned key is the word with its top bit flipped)
and runs every pass there:

- `kth`, the largest u with at least k keys >= u, from its top bit down,
  32 passes (0 for a row that sees fewer than k keys);
- `cut`, the largest position p with count(above) + count(tied & pos <= p)
  <= k: the keys equal to `kth` are taken lowest position first while they
  fit, so the ties' cut is a position, found by the same counting pass
  over the tied keys' positions, a bit of the position a pass. A block none
  of whose rows holds more ties than fit makes no such pass (scores that
  are sums of products seldom tie): the loop's trip count, not another
  form;
- the bits: a key above `kth` or tied at a position <= `cut` sets bit
  (position % 32) of its lane, and 32 lane tiles of keys fold into one
  tile of words (`_pack_group`: five steps of two lane rotations and a
  select), so the launch writes `pack_mask`'s words, 1/32 of what it read.

A pass walks the block's lanes a chunk at a time up to the block's largest
count of visible keys (a prefetched scalar), not to S: a row of a padded
block sees nothing and costs the fetch of its scores and a tile of zeros.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention
from .flash_attention import _i32, count_launch
from .paged_attention import _loop_i32

__all__ = ["select_bits", "fits"]

LANES = 128
_ROWS = 8               # rows of a work item: a float32 tile's sublanes
_CHUNK_TILES = 16       # most lane tiles of a chunk of a pass (16 vregs)
_GROUP = 32             # lane tiles (blocks of 128 keys) of a tile of words
_VMEM_LIMIT = 96 << 20  # what a launch may ask for
_UNSEEN = np.int32(-2 ** 31)        # the signed word of the order key 0
_NEVER = np.int32(2 ** 31 - 1)      # the position of a key that is not tied


def _vmem_bytes(keys: int) -> int:
    """What a launch holds in VMEM: the scores' block twice (the pipeline's
    two buffers) and the order keys once, 96 B a key position, and 4 MiB
    for the bits' tiles and the compiler's own."""
    return 3 * _ROWS * 4 * (-(-keys // LANES) * LANES) + (4 << 20)


def fits(keys: int) -> bool:
    """Whether a row block of `keys` key positions fits the launch's VMEM
    (static by shape: up to a million positions)."""
    return _vmem_bytes(keys) <= _VMEM_LIMIT


def _chunk(keys: int) -> int:
    """Lanes of a chunk of a pass: the most whole lane tiles, at most
    `_CHUNK_TILES`, that divide `keys` (a multiple of 128): 2,048 lanes at
    65,536 keys, 1,664 at 33,280."""
    tiles = keys // LANES
    return LANES * max(d for d in range(1, _CHUNK_TILES + 1)
                       if tiles % d == 0)


def _pack_group(tiles, lane):
    """32 lane tiles [8, 128] int32, each lane its key's bit in place (bit
    lane % 32) or 0, as ONE tile of words: lane 32 q + v the word q (keys
    32 q .. 32 q + 31) of tile v. Five folds: a fold ORs each lane with the
    one h beyond it and keeps of two tiles the halves that are whole, the
    first tile's in the lower h lanes of every 2 h and the second's in the
    upper, so the tiles halve as the lanes a word is spread over do."""
    for h in (16, 8, 4, 2, 1):
        n = len(tiles) // 2
        low = (lane & _i32(2 * h - 1)) < _i32(h)
        tiles = [jnp.where(low,
                           a | pltpu.roll(a, _i32(LANES - h), 1),
                           b | pltpu.roll(b, _i32(h), 1))
                 for a, b in zip(tiles[:n], tiles[n:])]
    return tiles[0]


def _select_kernel(trips_ref, x_ref, seen_ref, bits_ref, o_ref, key_ref, *,
                   k: int, chunk: int, pos_bits: int):
    """Rows j * 8 .. j * 8 + 7: x_ref [8, S] float32 scores, seen_ref
    [8, 1] the keys each row sees (positions 0 .. seen - 1), key_ref [8, S]
    int32 scratch (the order keys, then where each key stands against the
    cut), bits_ref [8, S / 32] int32 the selection's words (a tile of 128:
    lane 32 q + v the word q of the tile's block v), o_ref [8, 128] int32:
    lane 0 `kth` (the unsigned key's bits), the others `cut`."""
    i32 = jnp.int32
    trips = trips_ref[pl.program_id(0)]
    seen = seen_ref[...]
    lane = jax.lax.broadcasted_iota(i32, (_ROWS, chunk), 1)
    zeros = jnp.zeros((_ROWS, chunk), i32)
    ones = lambda m: jnp.where(m, _i32(1), _i32(0))   # (no bool -> int here)

    def at(c):
        return pl.ds(pl.multiple_of(c * _i32(chunk), chunk), chunk)

    def count(test):
        """How many of a row's keys pass `test` (a chunk of key_ref ->
        bool), [8, 1]."""
        acc = jax.lax.fori_loop(
            _i32(0), trips,
            lambda c, acc: acc + ones(test(key_ref[:, at(c)])), zeros)
        return jnp.sum(acc, axis=1, keepdims=True, dtype=i32)

    def order_keys(c, carry):
        x = x_ref[:, at(c)]
        b = pltpu.bitcast(jnp.where(x == 0.0, 0.0, x), i32)
        s = b ^ ((b >> 31) & _i32(0x7FFFFFFF))
        key_ref[:, at(c)] = jnp.where(lane + c * _i32(chunk) < seen, s,
                                      _UNSEEN)
        return carry

    jax.lax.fori_loop(_i32(0), trips, order_keys, _i32(0))

    def key_bit(i, best):
        cand = best | (_i32(1) << (_i32(31) - i))
        n = count(lambda s: s >= (cand ^ _UNSEEN))
        return jnp.where(n >= k, cand, best)

    # (a `fori_loop` with static bounds is a scan whose counter is int64
    # under jax_enable_x64, and Mosaic lowers no arithmetic on one)
    _, kth = jax.lax.while_loop(
        lambda c: c[0] < _i32(32),
        lambda c: (c[0] + _i32(1), key_bit(c[0], c[1])),
        (_i32(0), jnp.zeros((_ROWS, 1), i32)))
    kth_s = kth ^ _UNSEEN

    def ties(c, carry):
        above, tied = carry
        s = key_ref[:, at(c)]
        pos = lane + c * _i32(chunk)
        a, t = s > kth_s, (s == kth_s) & (pos < seen)
        # a key above the k-th stands before every position, a tied one at
        # its own, any other nowhere: the selection is `<= cut`
        key_ref[:, at(c)] = jnp.where(a, _i32(-1), jnp.where(t, pos, _NEVER))
        return above + ones(a), tied + ones(t)

    above, tied = jax.lax.fori_loop(_i32(0), trips, ties, (zeros, zeros))
    room = _i32(k) - jnp.sum(above, axis=1, keepdims=True, dtype=i32)
    over = jnp.sum(tied, axis=1, keepdims=True, dtype=i32) > room

    def pos_bit(i, best):
        cand = best | (_i32(1) << (_i32(pos_bits - 1) - i))
        n = count(lambda p: p <= cand)          # (the keys above among them)
        return jnp.where(n <= _i32(k), cand, best)

    cut = jax.lax.fori_loop(
        _i32(0), jnp.where(jnp.max(ones(over)) > 0, _i32(pos_bits),
                           _i32(0)),
        pos_bit, jnp.zeros((_ROWS, 1), i32))
    cut = jnp.where(over, cut, _NEVER - _i32(1))
    o_ref[...] = jnp.where(
        jax.lax.broadcasted_iota(i32, o_ref.shape, 1) == 0, kth, cut)

    # the selection as bits, 32 lane tiles of keys a tile of words
    lane1 = jax.lax.broadcasted_iota(i32, (_ROWS, LANES), 1)
    bit = _i32(1) << (lane1 & _i32(31))
    tiles = key_ref.shape[1] // LANES
    live = trips * _i32(chunk)

    def group(g, count_):
        """Lane tiles g * 32 .. g * 32 + count_ - 1 (static count)."""
        def words():
            first = g * _i32(_GROUP * LANES)
            xs = []
            for v in range(_GROUP):
                if v >= count_:
                    xs.append(jnp.zeros((_ROWS, LANES), i32))
                    continue
                lo = pl.multiple_of(first + _i32(v * LANES), LANES)
                p = key_ref[:, pl.ds(lo, LANES)]
                xs.append(jnp.where((p <= cut) & (lane1 + lo < seen), bit,
                                    _i32(0)))
            return _pack_group(xs, lane1)

        out = pl.ds(pl.multiple_of(g * _i32(LANES), LANES), LANES)
        started = g * _i32(_GROUP * LANES) < live

        @pl.when(started)
        def _():
            bits_ref[:, out] = words()

        @pl.when(jnp.logical_not(started))
        def _():
            bits_ref[:, out] = jnp.zeros((_ROWS, LANES), i32)

    whole, rest = divmod(tiles, _GROUP)
    _loop_i32(whole, lambda g: group(g, _GROUP))
    if rest:
        group(_i32(whole), rest)


def select_bits(scores: jax.Array, seen: jax.Array, k: int,
                interpret: Optional[bool] = None):
    """`scores` [T, S] float32, `seen` [T] int32 (row t sees the keys 0 ..
    seen[t] - 1; 0: a row that selects nothing) -> (bits [T, blocks of 128
    keys, 4] uint32, kth [T] uint32, cut [T] int32): the bits are
    `sparse_index.pack_mask` of `sparse_index.select_topk`'s selection,
    which is, of the keys row t sees, those whose order key is above
    kth[t] and of those equal to it the ones at positions <= cut[t]."""
    T, S = scores.shape
    if interpret is None:
        # (asked of the module, so that a rehearsal that steers
        # `flash_attention.available` steers this launch too)
        interpret = not flash_attention.available()
    pad_t, pad_s = -T % _ROWS, -S % LANES
    if pad_t or pad_s:      # (no serving shape: tables are whole blocks)
        scores = jnp.pad(scores, ((0, pad_t), (0, pad_s)))
    seen = jnp.pad(seen.astype(jnp.int32), (0, pad_t))
    rows, keys = scores.shape
    chunk = _chunk(keys)
    blocks, groups = rows // _ROWS, -(-keys // (_GROUP * LANES))
    trips = -(-jnp.max(seen.reshape(blocks, _ROWS), axis=1) // _i32(chunk))
    kernel = functools.partial(_select_kernel, k=k, chunk=chunk,
                               pos_bits=max(1, (S - 1).bit_length()))
    row_block = lambda w: pl.BlockSpec(
        (_ROWS, w), lambda j, *_: (j, _i32(0)), memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(blocks,),
        in_specs=[row_block(keys), row_block(1)],
        out_specs=[row_block(groups * LANES), row_block(LANES)],
        scratch_shapes=[pltpu.VMEM((_ROWS, keys), jnp.int32)])
    count_launch()
    words, out = pl.pallas_call(
        kernel, name="index_select_bits", grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, groups * LANES), jnp.int32),
                   jax.ShapeDtypeStruct((rows, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(16 << 20, _vmem_bytes(keys))),
        interpret=interpret,
    )(trips.astype(jnp.int32), scores.astype(jnp.float32), seen[:, None])
    # a tile of words is [word of a block, block of 32]: blocks first
    bits = jnp.swapaxes(words[:T].reshape(T, groups, 4, _GROUP), 2, 3
                        ).reshape(T, groups * _GROUP, 4)[:, :-(-S // LANES)]
    unsigned = lambda a: jax.lax.bitcast_convert_type(a, jnp.uint32)
    return unsigned(bits), unsigned(out[:T, 0]), out[:T, 1]
