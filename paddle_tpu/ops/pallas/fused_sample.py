"""Fused sampling-prep kernel for the serving decode tick (Pallas).

The stock tail of the engine's step executable runs temperature scaling,
top-k thresholding, the top-p sort/softmax/cumsum cascade and the greedy
argmax as ~8 separate XLA ops over the `[B, vocab]` logits block. This
kernel performs ALL of that masking math in ONE launch — the MPK-style
fused decode tick's "+1 sampler" launch — emitting the masked logits and
the greedy argmax together.

The masking math is the engine's `_sample_rows` (same temperature
scaling, same f32 constants, same cutoffs), but the two cutoffs are found
by bisection instead of sort/top_k/cumsum, which Mosaic does not lower.
The masked logits equal the stock path's except where the kept mass is
within f32 rounding of top_p (in practice top_p == 1.0 rows, over tail
elements whose summed probability is below one ulp of the total), since
the mass is summed in vocabulary order here and in sorted order there.
The final `jax.random.categorical` draw stays OUTSIDE the kernel: it is a
[B]-sized op on those masked logits, which keeps per-row PRNG key
handling on the one code path.

`supported()` gates the geometry and `available()` gates hardware as
usual; CPU CI runs interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (LANES, _assert_mosaic_tileable, available,
                              count_launch)

__all__ = ["fused_sample_prep", "available", "supported"]

# kernel scalar constants stay concrete np.float32 (x64 weak-float hazard)
_EPS = np.float32(1e-6)
_NEG_INF = np.float32(-np.inf)
_ONE = np.float32(1.0)
_ZERO = np.float32(0.0)
_MAG_MASK = np.int32(0x7FFFFFFF)
_KEY_MIN = np.int32(-2 ** 31)
_KEY_MAX = np.int32(2 ** 31 - 1)


def supported(batch: int, vocab: int) -> bool:
    """Static gate: one whole-array block must fit the VMEM working set
    (logits, keys, probabilities and the bisection's masked operand are
    live [B, V] 4-byte arrays)."""
    return (batch >= 1 and vocab >= 8
            and 4 * batch * vocab * 6 <= 12 * 1024 * 1024)


def _order_key(x):
    """f32 → int32 whose signed order is the floats' order (the sign-
    magnitude bit pattern with the magnitude of negatives flipped)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ _MAG_MASK, bits)


def _key_value(key):
    """Inverse of `_order_key` (the map is an involution on the bits)."""
    return jax.lax.bitcast_convert_type(
        jnp.where(key < 0, key ^ _MAG_MASK, key), jnp.float32)


def _bisect_key(holds, lo, hi):
    """32 halvings of the whole int32 key range [lo, hi]: `holds(t)`
    ([B, 1] bool) is true at `lo` and false at `hi` and flips once
    between them; returns the last key where it holds and the first where
    it does not. The midpoint is the overflow-free floor average."""
    def body(_, lo_hi):
        lo, hi = lo_hi
        mid = (lo & hi) + ((lo ^ hi) >> 1)
        ok = holds(mid)
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    return jax.lax.fori_loop(0, 32, body, (lo, hi))


def _sample_kernel(l_ref, t_ref, p_ref, masked_ref, amax_ref, *,
                   top_k: int):
    """Mosaic lowers neither sort, top_k nor cumsum, so both cutoffs of
    `_sample_rows` are found by bisection over the order-preserving int32
    keys of the logits — a compare-and-reduce pass over [B, V] per step —
    and land on the same values: the k-th largest logit, and the smallest
    logit whose strictly-greater probability mass is below top_p (the
    exclusive prefix mass of its first sorted occurrence)."""
    l = l_ref[...].astype(jnp.float32)                 # [B, V]
    # greedy argmax on the RAW logits (pre-temperature), as the stock
    # step computes it (jnp.argmax takes an int64 index under
    # jax_enable_x64; Mosaic lowers int32 only)
    amax = jax.lax.argmax(l, 1, jnp.int32)[:, None]
    l = l / jnp.maximum(t_ref[...][:, :1], _EPS)
    lo = jnp.full((l.shape[0], 1), _KEY_MIN, jnp.int32)
    hi = jnp.full((l.shape[0], 1), _KEY_MAX, jnp.int32)
    if top_k:
        key = _order_key(l)
        k_f = np.float32(top_k)
        kth, _ = _bisect_key(
            lambda t: jnp.sum(jnp.where(key >= t, _ONE, _ZERO), axis=-1,
                              keepdims=True) >= k_f, lo, hi)
        l = jnp.where(l < _key_value(kth), _NEG_INF, l)
    key = _order_key(l)
    e = jnp.exp(l - jnp.max(l, axis=-1, keepdims=True))
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    top_p = p_ref[...][:, :1]
    _, cut = _bisect_key(
        lambda t: jnp.sum(jnp.where(key > t, probs, _ZERO), axis=-1,
                          keepdims=True) >= top_p, lo, hi)
    masked_ref[...] = jnp.where(l < _key_value(cut), _NEG_INF, l)
    amax_ref[...] = jnp.broadcast_to(amax, amax_ref.shape)


def fused_sample_prep(logits, temps, top_ps, top_k: int = 0,
                      interpret: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """One-launch sampling prep over f32 logits [B, V].

    temps/top_ps [B] f32; top_k static (0 = off). Returns
    (masked_logits [B, V] f32 — feed `jax.random.categorical` per row —
    and greedy argmax [B] int32): the stock `_sample_rows` mask (see the
    module docstring for the top_p rounding boundary) and the stock argmax.
    """
    B, V = logits.shape
    if not supported(B, V):
        raise ValueError(f"unsupported sampler geometry B={B} V={V}; "
                         "use the stock sampling path")
    if interpret is None:
        interpret = not available()
    t = jnp.broadcast_to(temps.astype(jnp.float32)[:, None], (B, LANES))
    p = jnp.broadcast_to(top_ps.astype(jnp.float32)[:, None], (B, LANES))
    mem = {"memory_space": pltpu.VMEM}
    in_specs = [
        pl.BlockSpec((B, V), lambda: (0, 0), **mem),
        pl.BlockSpec((B, LANES), lambda: (0, 0), **mem),
        pl.BlockSpec((B, LANES), lambda: (0, 0), **mem),
    ]
    out_specs = [
        pl.BlockSpec((B, V), lambda: (0, 0), **mem),
        pl.BlockSpec((B, LANES), lambda: (0, 0), **mem),
    ]
    inputs = [logits.astype(jnp.float32), t, p]
    for spec, arr in zip(in_specs, inputs):
        _assert_mosaic_tileable(spec.block_shape, arr.shape, "sampler input")
    count_launch()
    masked, amax = pl.pallas_call(
        functools.partial(_sample_kernel, top_k=int(top_k)),
        name="fused_sample_prep",
        grid=(),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((B, V), jnp.float32),
            jax.ShapeDtypeStruct((B, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(*inputs)
    return masked, amax[:, 0]
