"""The window of the paged walks (`paged_attention.py`: the query at
position p sees key j iff p - W < j <= p): the decode walk, the mixed walk
and the BlockSpec walk in interpret mode, and the stock XLA read of
`paged_layer_attention`, against dense float32 attention under the mask, at
6 and 8 query rows a key-value head (48 / 8 and 64 / 8: Laguna's full and
window layers). The key blocks are made small (`_DECODE_KEYS`,
`_MIXED_KEYS`) so that contexts of a few dozen positions begin their walk
several blocks in, and the pages behind every window are taken out of the
table (-1), as the engine's window pool gives them back."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.kernels import serving_attention as SA
from paddle_tpu.ops.pallas import paged_attention as PA

W, BS, WIDTH, KV, HD = 20, 4, 24, 2, 16


@pytest.fixture
def small_blocks(monkeypatch):
    monkeypatch.setattr(PA, "_DECODE_KEYS", 8)      # 2 pages a key block
    monkeypatch.setattr(PA, "_MIXED_KEYS", 16)      # 4 pages a key block


def case(G, this, past, seed=0):
    """Slots with `this[b]` new rows behind `past[b]` positions: seeded q
    [tok, KV, G, hd], a one-layer pool whose pages hold seeded keys and
    values, shuffled tables with every page wholly behind the slot's first
    row's window taken out, and cu."""
    B = len(this)
    rng = np.random.default_rng(seed)
    tables = rng.permutation(B * WIDTH).reshape(B, WIDTH).astype(np.int32)
    for b in range(B):
        tables[b, :max(0, past[b] - (W - 1)) // BS] = -1
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    tok = int(np.sum(this))
    q = jax.random.normal(keys[0], (tok, KV, G, HD), jnp.float32)
    k = jax.random.normal(keys[1], (1, B * WIDTH, KV, BS, HD), jnp.float32)
    v = jax.random.normal(keys[2], (1, B * WIDTH, KV, BS, HD), jnp.float32)
    cu = np.concatenate([[0], np.cumsum(this)]).astype(np.int32)
    return (q, k, v, jnp.asarray(tables), jnp.asarray(past, jnp.int32),
            jnp.asarray(this, jnp.int32), jnp.asarray(cu))


def dense(c, window):
    """Dense float32 attention of every row over its sequence's keys, the
    pool read with the ORIGINAL page of every position (the released
    entries are behind every window, so page 0's garbage must not show)."""
    q, k, v, tables, past, this, cu = c
    out = np.zeros(q.shape, np.float32)
    kk, vv = np.asarray(k[0]), np.asarray(v[0])
    for b in range(len(this)):
        for t in range(int(this[b])):
            p = int(past[b]) + t
            lo = max(0, p - window + 1) if window else 0
            pos = np.arange(lo, p + 1)
            pages = np.asarray(tables)[b, pos // BS]
            assert (pages >= 0).all()
            keys = kk[pages, :, pos % BS]                  # [n, KV, hd]
            vals = vv[pages, :, pos % BS]
            qr = np.asarray(q[int(cu[b]) + t])             # [KV, G, hd]
            s = np.einsum("kgd,nkd->kgn", qr, keys) / np.sqrt(HD)
            s = np.exp(s - s.max(-1, keepdims=True))
            out[int(cu[b]) + t] = np.einsum(
                "kgn,nkd->kgd", s / s.sum(-1, keepdims=True), vals)
    return out


def read(path, c, window):
    q, k, v, tables, past, this, cu = c
    if path == "decode":
        # one row a slot (an idle slot's is masked by its length)
        rows = jnp.clip(cu[:-1], 0, q.shape[0] - 1)
        o = np.asarray(PA.paged_attention(
            q[rows], k, v, tables, past, this, q.shape[2], HD ** -0.5,
            layer=jnp.int32(0), window=window))
        return o[np.asarray(this) > 0]
    if path == "stock":
        tok, G = q.shape[0], q.shape[2]
        b_of = np.repeat(np.arange(len(this)), np.asarray(this))
        pos = np.asarray(past)[b_of] + (np.arange(tok) - np.asarray(cu)[b_of])
        page = np.asarray(tables)[b_of, pos // BS]
        held = lambda pool: pool[0][page, :, pos % BS]
        qkv = jnp.concatenate([q.reshape(tok, KV * G, HD), held(k), held(v)],
                              axis=1).reshape(tok, -1)
        o = SA.paged_layer_attention(qkv, k, v, jnp.int32(0), past, this, cu,
                                     tables, use_pallas=False, window=window)
        return np.asarray(o[0]).reshape(q.shape)
    whole = PA.whole_pages
    try:
        if path == "blockspec":
            PA.whole_pages = lambda hd, interpret=None: False
        return np.asarray(PA.paged_attention_packed(
            q, k, v, tables, past, this, cu, HD ** -0.5, layer=jnp.int32(0),
            window=window))
    finally:
        PA.whole_pages = whole


def worst(out, ref):
    return float(np.abs(out - ref).max() / np.abs(ref).max())


@pytest.mark.parametrize("G", [6, 8])
@pytest.mark.parametrize("path", ["decode", "mixed", "blockspec", "stock"])
def test_window_walks_against_dense_attention(small_blocks, path, G):
    """Each walk under the window equals dense attention over the last W
    keys to 1e-5 with the pages behind the window gone from the table; a
    window one page wider or narrower is off by whole percents, and W = 0
    on whole tables is the causal read it was."""
    if path == "decode":
        this, past = [1, 1, 0, 1, 1], [3, 19, 0, 20, 77]
    else:
        # a chunk over several row tiles that crosses the window, a decode
        # row deep in its context, an idle slot, a chunk from position 0
        this, past = [37, 1, 0, 23], [30, 81, 0, 0]
    c = case(G, this, past)
    ref = dense(c, W)
    assert worst(read(path, c, W), ref) < 1e-5
    for off in (W + BS, W - BS):
        full = case(G, this, [0 * p for p in past])[3]      # whole tables
        c_off = c[:3] + (jnp.where(c[3] < 0, full, c[3]),) + c[4:]
        assert worst(read(path, c_off, off), ref) > 1e-2
    whole = case(G, this, [0] * len(past))
    whole = whole[:4] + c[4:]
    assert worst(read(path, whole, 0), dense(whole, 0)) < 1e-5


def test_host_mirrors_count_the_walk_behind_the_window(small_blocks):
    """`decode_pages_walked` and `mixed_work` under a window: live pages
    are those that hold a visible key, fetched ones begin at the block of
    the first visible key."""
    geom = (BS, KV, HD, 4, WIDTH)
    live, fetched = PA.decode_pages_walked([78], *geom, window=W)
    assert live == 78 // BS + 1 - (78 - W) // BS          # pages 14..19
    assert fetched == 2 * (10 - (78 - W) // 8)            # blocks of 2 pages
    causal = PA.decode_pages_walked([78], *geom)
    assert causal == (20, 20)
    args = ([62], [30], 64, BS, KV, 6, HD, 4, WIDTH)
    mixed, plain = PA.mixed_work(*args, window=W), PA.mixed_work(*args)
    assert (mixed["attn_pages_fetched"], plain["attn_pages_fetched"]) == (
        4 * (6 - 2), 4 * 6)                       # blocks of 4 pages
    assert mixed["attn_pages_live"] == 23 - (62 - W + 1) // BS
    assert {k: mixed[k] for k in ("attn_rows_packed", "attn_rows_live")} == {
        k: plain[k] for k in ("attn_rows_packed", "attn_rows_live")}
