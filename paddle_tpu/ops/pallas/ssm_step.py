"""The state-space layers' one-row state update as ONE launch (Pallas):
every live one-row segment's slot of the state pool is read once and
written once, in place.

`ops/kernels/ssm.ssm_step` is the rule and the stock form. The chip's
compiler makes it two fusions a layer: the reduce that gives y and the
in-place update each read the layer's whole pool, three passes over 136 MB
where two are needed, and idle slots cost what live ones do (PERF.md
section 6, PR 56). Here a work item is (batch entry, a group of `_HEADS`
heads): the entry's slot comes from a prefetched scalar (an entry that is
not a one-row segment is sent to the void slot, which nobody reads), its
block [heads, P, N] of the pool is the launch's in- AND output
(`input_output_aliases` on the donated pool), and for each head

    S <- a S + (delta u) (outer) B          y = S C + D u

with the outer product and the read as products on the matrix unit, so
that nothing is moved between sublanes and lanes: (delta u) comes as three
bfloat16 terms that sum to its float32 value (rows 0-2 of an [8, P] tile,
B in the same rows of an [8, N] tile: the products are exact, the sum is
float32), and y as C [8, N] times S^T, S rounded to bfloat16 as the stock
scan's read rounds it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention
from .flash_attention import _i32, count_launch

__all__ = ["step", "fits"]

_HEADS = 32     # heads of a work item: 32 x 64 x 128 x 4 B = 1 MB a block
_ROWS = 8       # rows of the tiles that carry a head's vectors


def fits(heads: int, head_dim: int, d_state: int) -> bool:
    """Whether the launch takes these widths: whole groups of heads, a
    state tile of whole float32 tiles."""
    return heads % _HEADS == 0 and head_dim % 8 == 0 and d_state % 128 == 0


def _kernel(layer_ref, slot_ref, fresh_ref, a_ref, du_ref, b_ref, c_ref,
            state_ref, y_ref, out_ref):
    del layer_ref, slot_ref
    e = pl.program_id(0)
    keep = jnp.where(fresh_ref[e] > _i32(0), jnp.float32(0), jnp.float32(1))
    bk, ck = b_ref[0], c_ref[0]                               # [8, N] bf16
    for h in range(_HEADS):
        s = state_ref[0, 0, h].astype(jnp.float32) * keep     # [P, N]
        term = lax.dot_general(du_ref[0, 0, h], bk, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
        new = a_ref[0, 0, h:h + 1, :] * s + term            # [1, N] x [P, N]
        out_ref[0, 0, h] = new.astype(out_ref.dtype)
        y_ref[0, 0, h] = lax.dot_general(
            ck, new.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [8, P]


def step(a, du, Bm, Cm, state_pool, layer, slots, fresh,
         interpret: Optional[bool] = None):
    """a [B, H] float32 (the decays), du [B, H, P] float32 (delta u), Bm
    and Cm [B, N] (one group), `slots` [B] int32 each entry's slot (the
    void one for an entry that is not a one-row segment), `fresh` [B]
    whether it starts from zeros. Returns (y [B, H, P] float32 = S_new C,
    state_pool)."""
    B, H, P = du.shape
    N = Bm.shape[-1]
    if interpret is None:
        interpret = not flash_attention.available()
    count_launch()
    bf = jnp.bfloat16

    def head_bits(x):
        # x cut to its leading 8 bits of mantissa: a bfloat16 value, made
        # by a mask and not by a convert there and back, which the compiler
        # may drop as excess precision
        word = lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(
            0xFFFF0000)
        return lax.bitcast_convert_type(word, jnp.float32)

    hi = head_bits(du)
    mid = head_bits(du - hi)
    lo = head_bits(du - hi - mid)
    hi, mid, lo = hi.astype(bf), mid.astype(bf), lo.astype(bf)
    pad = lambda rows: jnp.concatenate(
        [rows, jnp.zeros(rows.shape[:-2] + (_ROWS - rows.shape[-2],)
                         + rows.shape[-1:], bf)], axis=-2)
    du8 = pad(jnp.stack([hi, mid, lo], axis=-2)).reshape(
        B, H // _HEADS, _HEADS, _ROWS, P)
    b8 = pad(jnp.repeat(Bm.astype(bf)[:, None], 3, axis=1))   # [B, 8, N]
    c8 = pad(Cm.astype(bf)[:, None])
    grid = (B, H // _HEADS)
    block = (1, 1, _HEADS) + state_pool.shape[3:]
    zero = _i32(0)
    at = lambda e, g, layer, slot, fresh: (layer[0], slot[e], g, zero, zero)
    tile = lambda e, g, *_: (e, g, zero, zero, zero)
    row = lambda e, g, *_: (e, zero, zero)
    y8, state_pool = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, _HEADS, N),
                             lambda e, g, *_: (e, g, zero, zero)),
                pl.BlockSpec((1, 1, _HEADS, _ROWS, P), tile),
                pl.BlockSpec((1, _ROWS, N), row),
                pl.BlockSpec((1, _ROWS, N), row),
                pl.BlockSpec(block, at)],
            out_specs=[
                pl.BlockSpec((1, 1, _HEADS, _ROWS, P), tile),
                pl.BlockSpec(block, at)]),
        out_shape=[jax.ShapeDtypeStruct(
            (B, H // _HEADS, _HEADS, _ROWS, P), jnp.float32),
            jax.ShapeDtypeStruct(state_pool.shape, state_pool.dtype)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="ssm_state_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
      fresh.astype(jnp.int32),
      jnp.broadcast_to(a.astype(jnp.float32).reshape(
          B, H // _HEADS, _HEADS, 1), (B, H // _HEADS, _HEADS, N)),
      du8, b8, c8,
      state_pool)
    return y8[:, :, :, 0].reshape(B, H, P), state_pool
