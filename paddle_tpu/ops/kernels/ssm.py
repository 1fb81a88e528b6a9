"""The state-space mixer's three operations over a serving tick's packed
stream (Mamba-2, arXiv:2405.21060: the recurrence S_t = a_t S_{t-1} +
delta_t u_t (outer) B_t, y_t = S_t C_t + D u_t, a_t = exp(-A delta_t), behind a
causal depthwise convolution of width K), in stock `jax.numpy`.

A tick's stream is `T` rows packed from 0: batch entry b's segment is rows
cu[b] .. cu[b] + this[b] - 1, it continues a sequence that has `past[b]`
positions behind it, and the sequence's recurrent state lives in slot
`slots[b]` of two pools stacked over the model's state-space layers:

    state_pool [L, slots + 1, H, P, N]        float32 (the pool's dtype)
    conv_pool  [L, slots + 1, (K - 1) * C]    the last K - 1 rows of xBC
                                              before the activation, oldest
                                              first, C = conv_dim values each

The last slot is the void one: entries without rows (an idle batch entry)
are sent there, and nobody reads it. A segment starts from its slot's state,
or from ZEROS where `past == 0`, whatever the slot holds: a slot that a
finished sequence gave back is never cleared, so admission costs no device
operation. Both pools come back updated in place (a donated or loop-carried
pool: one dynamic-update-slice, or one scatter of `B` rows); a slot that no
entry of the tick names is bit for bit what it was.

- `ssm_conv`: the convolution and its carried rows.
- `ssm_step`: every ONE-ROW segment (a decode row, a prompt's last odd row).
  The stock form, the rule: one pass over the layer's whole pool in slot
  order (no state is gathered out of the pool or scattered back: the tick's
  rows are gathered to the slots instead, which is 64 rows). Beside the
  kernels' read path (`kernel=True`, bfloat16 activations, one group, a
  state of whole lanes) it is the launch of `ops/pallas/ssm_step.py`, which
  reads each live slot once and writes it once in place, by batch entry.
- `ssm_scan`: every segment of MORE than one row, a segment at a time in
  blocks of `chunk` rows that start at the segment's own first row (SSD
  form): inside a block Y = (L o (C B^T)) (delta U) with L the lower-
  triangular products of a, between blocks the carried S. Rows of the
  block's window that lie behind the segment's end have delta = 0, which is
  the identity, and are not written back, so two segments of one tick
  never meet. The cumulative decays are float32 sums of logs. The result
  equals the recurrence whatever the engine's chunk size or `chunk`.

`y` comes back float32 [T, H, P], zero on rows the function does not own
(the two are summed by the caller).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ssm_conv", "ssm_step", "ssm_scan", "stream_rows"]

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def stream_rows(cu, this, rows: int):
    """(batch entry [rows], offset inside its segment [rows], whether the
    row exists [rows]) of a packed stream's rows."""
    B = this.shape[0]
    tok = jnp.arange(rows, dtype=jnp.int32)
    tok_b = jnp.clip(jnp.searchsorted(cu, tok, side="right") - 1, 0, B - 1)
    local = tok - cu[tok_b]
    return tok_b, local, (local >= 0) & (local < this[tok_b])


def ssm_conv(xbc, conv_pool, layer, slots, past, this, cu, w, bias):
    """xbc [T, C] -> (silu(conv(xbc) + bias) [T, C] in xbc's dtype,
    conv_pool). w [C, K] (tap k multiplies the row K - 1 - k behind), bias
    [C]. A segment's first K - 1 rows read its slot's carried rows (zeros
    where `past == 0`); the slot takes the new last K - 1 rows."""
    T, C = xbc.shape
    K = w.shape[1]
    B = slots.shape[0]
    void = conv_pool.shape[1] - 1
    tok_b, local, valid = stream_rows(cu, this, T)
    carried = lax.dynamic_index_in_dim(conv_pool, layer, 0, keepdims=False)[
        slots].reshape(B, K - 1, C)
    carried = jnp.where((past > 0)[:, None, None], carried, 0)
    x32, w32, c32 = xbc.astype(_F32), w.astype(_F32), carried.astype(_F32)
    acc = x32 * w32[:, K - 1]
    for m in range(1, K):       # the row m behind, where the segment has it
        behind = jnp.concatenate([jnp.zeros((m, C), _F32), x32[:T - m]])
        acc = acc + jnp.where((local >= m)[:, None], behind, 0.0
                              ) * w32[:, K - 1 - m]
    # what the carried rows add to a segment's row j < K - 1: the rows
    # m = j + 1 .. K - 1 behind it, carried[K - 1 + j - m]
    head = jnp.stack([
        sum(c32[:, K - 1 + j - m] * w32[:, K - 1 - m]
            for m in range(j + 1, K)) for j in range(K - 1)], axis=1)
    at = tok_b * (K - 1) + jnp.clip(local, 0, K - 2)
    acc = acc + jnp.where(((local < K - 1) & valid)[:, None],
                          head.reshape(B * (K - 1), C)[at], 0.0)
    out = jax.nn.silu(acc + bias.astype(_F32)).astype(xbc.dtype)
    # the new carried rows: positions this - (K - 1) .. this - 1 of the
    # segment, from the stream or, before its first row, from the old ones
    o = this[:, None] - (K - 1) + jnp.arange(K - 1, dtype=jnp.int32)[None]
    new = jnp.where(
        (o >= 0)[..., None],
        xbc[jnp.clip(cu[:B, None] + o, 0, T - 1)].astype(carried.dtype),
        jnp.take_along_axis(carried,
                            jnp.clip(o + K - 1, 0, K - 2)[..., None], axis=1))
    conv_pool = conv_pool.at[layer, jnp.where(this > 0, slots, void)].set(
        new.reshape(B, (K - 1) * C).astype(conv_pool.dtype))
    return out, conv_pool


def _per_head(x, heads: int):
    """[n, G, N] of the groups -> [n, H, N] of the heads (a view for one
    group)."""
    G = x.shape[1]
    return x if G == heads else jnp.repeat(x, heads // G, axis=1)


def ssm_step(u, Bm, Cm, delta, A, D, state_pool, layer, slots, past, this,
             cu, kernel=False):
    """The one-row segments' update. u [T, H, P], Bm and Cm [T, G, N], delta
    [T, H] float32 (after the softplus), A [H] (= exp(A_log)), D [H].
    Returns (y [T, H, P] float32, state_pool). `kernel`: beside the
    kernels' read path, the one launch of `ops/pallas/ssm_step.py` where it
    takes the widths (each live slot read once and written once in place,
    by batch entry; the stock form below is the rule, and what the chip's
    compiler makes of it reads the layer's pool twice)."""
    from ..pallas import ssm_step as _kernel
    T, H, P = u.shape
    S1 = state_pool.shape[1]
    B = slots.shape[0]
    one = this == 1
    # (B and C go to the matrix unit as they are: bfloat16 activations)
    if (kernel and Bm.shape[1] == 1 and Bm.dtype == jnp.bfloat16
            and _kernel.fits(H, P, Bm.shape[2])):
        r = jnp.clip(cu[:B], 0, T - 1)
        us, ds = u[r].astype(_F32), delta[r]
        y, state_pool = _kernel.step(
            jnp.exp(-A.astype(_F32) * ds), ds[:, :, None] * us, Bm[r][:, 0],
            Cm[r][:, 0], state_pool, layer,
            jnp.where(one, slots, S1 - 1), past == 0)
        y = y + D.astype(_F32)[None, :, None] * us
        tok_b, _, valid = stream_rows(cu, this, T)
        return (jnp.where((one[tok_b] & valid)[:, None, None], y[tok_b],
                          0.0), state_pool)
    mine = jnp.where(one, slots, S1)            # the others: dropped
    row_of = jnp.full((S1,), -1, jnp.int32).at[mine].set(cu[:B], mode="drop")
    fresh = jnp.zeros((S1,), bool).at[mine].set(past == 0, mode="drop")
    active = row_of >= 0
    r = jnp.maximum(row_of, 0)
    us = u[r].astype(_F32)                                       # [S1, H, P]
    Bs = _per_head(Bm[r].astype(_F32), H)                        # [S1, H, N]
    Cs = _per_head(Cm[r].astype(_F32), H)
    ds = delta[r]                                                # [S1, H]
    a = jnp.exp(-A.astype(_F32) * ds)
    old = lax.dynamic_index_in_dim(state_pool, layer, 0, keepdims=False)
    s_in = jnp.where(fresh[:, None, None, None], 0.0, old.astype(_F32))
    new = (a[:, :, None, None] * s_in
           + (ds[:, :, None] * us)[..., None] * Bs[:, :, None, :])
    y = (jnp.sum(new * Cs[:, :, None, :], axis=-1)
         + D.astype(_F32)[None, :, None] * us)                   # [S1, H, P]
    state_pool = lax.dynamic_update_index_in_dim(
        state_pool, jnp.where(active[:, None, None, None],
                              new.astype(state_pool.dtype), old), layer, 0)
    tok_b, _, valid = stream_rows(cu, this, T)
    y_tok = jnp.where((one[tok_b] & valid)[:, None, None],
                      y[jnp.minimum(slots[tok_b], S1 - 1)], 0.0)
    return y_tok, state_pool


def ssm_scan(u, Bm, Cm, delta, A, D, state_pool, layer, slots, past, this,
             cu, chunk: int):
    """The longer segments' scan, arguments as `ssm_step`'s. A segment at a
    time (a loop over the tick's segments of more than one row), in blocks
    of `chunk` rows from the segment's first."""
    T, H, P = u.shape
    G, N = Bm.shape[1:]
    Q = int(chunk)
    long = this > 1
    order = jnp.argsort(~long, stable=True).astype(jnp.int32)   # long first
    A32, D32 = A.astype(_F32), D.astype(_F32)

    def padded(x):      # a block's window never runs off the stream
        return jnp.concatenate(
            [x, jnp.zeros((Q,) + x.shape[1:], x.dtype)])

    u_p, B_p, C_p, d_p = padded(u), padded(Bm), padded(Cm), padded(delta)
    i = jnp.arange(Q, dtype=jnp.int32)
    z = jnp.int32(0)
    layer = jnp.asarray(layer, jnp.int32)
    causal = i[:, None] >= i[None, :]                            # [i, j]
    # Sums of the logs of a over the rows up to and behind a row, as
    # products with 0/1 matrices at full precision: on the chip a cumsum
    # comes out of the matrix unit at its default precision, the logs
    # rounded to 8 bits, and the decay over a block is then off by 1e-4 to
    # 5e-4 (my chip runs, PR 56) where a tick's new state is held to 1e-4
    upto = causal.astype(_F32)                    # [i, j]: j <= i
    behind = (i[:, None] < i[None, :]).astype(_F32)   # [j, k]: k > j

    def segment(n, carry):
        y, pool = carry
        b = order[n]
        start, rows, slot = cu[b], this[b], slots[b]
        S = lax.dynamic_slice(pool, (layer, slot, z, z, z),
                              (1, 1) + pool.shape[2:])[0, 0].astype(_F32)
        S = jnp.where(past[b] > 0, S, 0.0)

        def block(k, carry):
            y, S = carry
            at = start + k * Q
            live = k * Q + i < rows
            uq = lax.dynamic_slice_in_dim(u_p, at, Q).astype(_F32)
            Bq = lax.dynamic_slice_in_dim(B_p, at, Q)
            Cq = lax.dynamic_slice_in_dim(C_p, at, Q)
            dq = jnp.where(live[:, None],
                           lax.dynamic_slice_in_dim(d_p, at, Q), 0.0)
            logs = -A32 * dq                                     # [Q, H]
            c = jnp.matmul(upto, logs, precision=_HIGHEST)
            whole = jnp.sum(logs, axis=0)                        # [H]
            du = dq[:, :, None] * uq                             # [Q, H, P]
            Bg, Cg = Bq.astype(_F32), Cq.astype(_F32)            # [Q, G, N]
            # inside the block: row i reads row j <= i through the product
            # of a over j + 1 .. i
            scores = jnp.repeat(jnp.einsum("ign,jgn->gij", Cg, Bg), H // G,
                                axis=0)                          # [H, i, j]
            decay = jnp.exp(jnp.where(
                causal[None], c.T[:, :, None] - c.T[:, None, :], -jnp.inf))
            y_in = jnp.einsum("hij,jhp->ihp", scores * decay, du)
            # the state the block starts from, through the product of a up
            # to row i
            y_st = jnp.exp(c)[:, :, None] * jnp.einsum(
                "ghpn,ign->ighp", S.reshape(G, H // G, P, N), Cg
            ).reshape(Q, H, P)
            yq = y_in + y_st + D32[None, :, None] * uq
            # the state behind the block: float32 at full precision (the
            # sum is carried over the whole sequence)
            # (the product of a over the rows BEHIND row j as the exp of
            # their own sum, not of a difference of two prefix sums: a
            # head that forgets within a few rows has prefix sums in the
            # hundreds, whose difference is exact to 1e-4 of the few that
            # matter)
            tail = jnp.exp(jnp.matmul(behind, logs, precision=_HIGHEST))
            S = (jnp.exp(whole)[:, None, None] * S
                 + jnp.einsum(
                     "jghp,jgn->ghpn",
                     (tail[:, :, None] * du).reshape(Q, G, H // G, P), Bg,
                     precision=_HIGHEST).reshape(H, P, N))
            was = lax.dynamic_slice_in_dim(y, at, Q)
            y = lax.dynamic_update_slice_in_dim(
                y, jnp.where(live[:, None, None], yq, was), at, 0)
            return y, S

        y, S = lax.fori_loop(0, (rows + Q - 1) // Q, block, (y, S))
        pool = lax.dynamic_update_slice(
            pool, S.astype(pool.dtype)[None, None], (layer, slot, z, z, z))
        return y, pool

    y, state_pool = lax.fori_loop(
        0, jnp.sum(long, dtype=jnp.int32), segment,
        (jnp.zeros((T + Q, H, P), _F32), state_pool))
    return y[:T], state_pool
