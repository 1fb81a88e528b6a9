"""The RUN COPY (PR 53): a key block whose table entries are p, p + 1, ...,
p + pages - 1 is one contiguous region of `pool[layer]`, and the index's two
launches and the masked decode walk bring it into their buffer in ONE copy
where they bring any other block page by page. Which blocks those are is
`paged_attention.block_runs`, read from the block table alone.

Every launch here runs the Pallas interpreter ONCE over six sequences, a
table of each kind, with the plane the launch makes for itself and once
with a plane of zeros (the page-by-page form, the launches as they were):
the outputs must be equal bit for bit, row by row, and the plane must say
of each table what its case says.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.kernels import sparse_index as SI
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.ops.pallas import paged_attention_latent as PL

BS, PAGES, NB, WIDTH = 16, 4, 64, 12         # 3 key blocks of 4 pages of 16
KEYS = BS * PAGES
FULL = 3 * KEYS - 5                          # a row that walks all three

# name -> (block table, the row's position, which of its key blocks are runs)
CASES = {
    "all_in_runs": (list(range(8, 20)), FULL, [1, 1, 1]),
    "a_permutation": ([37, 9, 50, 22, 3, 41, 17, 30, 12, 45, 26, 6], FULL,
                      [0, 0, 0]),
    "mixed": ([20, 21, 22, 23, 30, 28, 29, 31, 40, 41, 42, 43], FULL,
              [1, 0, 1]),
    "ends_on_the_pools_last_page": (list(range(NB - 12, NB)), FULL,
                                    [1, 1, 1]),
    "descending": (list(range(19, 7, -1)), FULL, [0, 0, 0]),
    "cut_by_unassigned_entries": ([24, 25, 26, 27, 32, 33] + [-1] * 6,
                                  KEYS + 20, [1, 0, 0]),
}
NAMES = list(CASES)
TABLES = np.array([CASES[n][0] for n in NAMES], np.int32)
PAST = np.array([CASES[n][1] for n in NAMES], np.int32)
RUNS = np.array([CASES[n][2] for n in NAMES], np.int32)
B = len(NAMES)


def _both_forms(monkeypatch_ctx, module, launch):
    """(the launch with the plane it makes, the launch with no block a
    run), each traced anew."""
    new = np.asarray(jax.jit(launch)())
    with monkeypatch_ctx() as m:
        m.setattr(module, "block_runs",
                  lambda t, pages, nb, xp=jnp: jnp.zeros(
                      (t.shape[0], -(-t.shape[1] // pages)), jnp.int32))
        old = np.asarray(jax.jit(launch)())
    return new, old


@pytest.fixture(scope="module")
def forms():
    """name of a launch -> (with runs, page by page) over the six tables."""
    k = jax.random.split(jax.random.PRNGKey(53), 10)
    index_pool = jax.random.normal(k[0], (2, NB, 1, BS, 128), jnp.float32)
    tables, past = jnp.asarray(TABLES), jnp.asarray(PAST)
    one, layer = jnp.ones((B,), jnp.int32), jnp.int32(1)
    mp = pytest.MonkeyPatch
    out = {}
    with mp.context() as m:
        m.setattr(PL, "_INDEX_ROW_KEYS", KEYS)
        m.setattr(PL, "_INDEX_KEYS", KEYS)
        m.setattr(PA, "_MASKED_DECODE_KEYS", KEYS)
        qi = jax.random.normal(k[1], (B, 2, 128), jnp.float32)
        w = jax.random.normal(k[2], (B, 2), jnp.float32)
        out["index_scores_rows"] = _both_forms(
            mp.context, PL, lambda: PL.index_scores_rows(
                qi, w, index_pool, tables, past, one, layer, interpret=True))
        # five rows a sequence, the last of them at the case's position
        rows = 5
        this = jnp.full((B,), rows, jnp.int32)
        cu = jnp.arange(B + 1, dtype=jnp.int32) * rows
        qt = jax.random.normal(k[3], (32, 2, 128), jnp.float32)
        wt = jax.random.normal(k[4], (32, 2), jnp.float32)
        out["index_scores_packed"] = _both_forms(
            mp.context, PL, lambda: PL.index_scores_packed(
                qt, wt, index_pool, tables, past - (rows - 1), this, cu,
                layer, interpret=True).reshape(32, -1)[:B * rows].reshape(
                    B, rows, -1))
        kp = jax.random.normal(k[5], (2, NB, 2, BS, 32), jnp.float32)
        vp = jax.random.normal(k[6], (2, NB, 2, BS, 32), jnp.float32)
        q = jax.random.normal(k[7], (B, 2, 2, 32), jnp.float32)
        sel = np.array(jax.random.bernoulli(k[8], 0.3, (B, WIDTH * BS)))
        sel[:, 0] = True
        mask = SI.pack_mask(jnp.asarray(sel))
        out["paged_attention_masked"] = _both_forms(
            mp.context, PA, lambda: PA.paged_attention(
                q, kp, vp, tables, past, one, 2, 0.25, interpret=True,
                layer=layer, mask=mask))
    return out


@pytest.mark.parametrize("xp", [np, jnp], ids=["host", "device"])
@pytest.mark.parametrize("name", NAMES)
def test_block_runs_reads_a_table_as_its_case_says(name, xp):
    at = NAMES.index(name)
    got = PA.block_runs(xp.asarray(TABLES[at:at + 1]), PAGES, NB, xp=xp)
    assert np.asarray(got).tolist() == [CASES[name][2]]


def test_block_runs_refuses_what_no_copy_can_take():
    runs = lambda t, nb=NB: np.asarray(PA.block_runs(
        np.array([t], np.int32), PAGES, nb, xp=np)).tolist()[0]
    assert runs([60, 61, 62, 63]) == [1]
    assert runs([60, 61, 62, 63], nb=63) == [0]         # ends past the pool
    assert runs([-1, 0, 1, 2]) == [0]                   # begins unassigned
    assert runs([-1, -1, -1, -1]) == [0]
    assert runs([4, 5, 6, 8]) == [0] and runs([4, 5, 5, 6]) == [0]
    # a table no multiple of a block wide: the last block is padded, no run
    assert runs([4, 5, 6, 7, 8, 9]) == [1, 0]
    # one page a block: every assigned page is its own run
    assert np.asarray(PA.block_runs(np.array([[3, -1, 9]]), 1, NB, xp=np)
                      ).tolist() == [[1, 0, 1]]


@pytest.mark.parametrize("name", NAMES)
def test_index_scores_rows_with_the_run_copy_is_the_page_by_page_form(
        forms, name):
    new, old = forms["index_scores_rows"]
    at = NAMES.index(name)
    assert np.abs(old[at]).max() > 0
    assert np.array_equal(new[at], old[at])


@pytest.mark.parametrize("name", NAMES)
def test_index_scores_packed_with_the_run_copy_is_the_page_by_page_form(
        forms, name):
    new, old = forms["index_scores_packed"]
    at = NAMES.index(name)
    assert np.abs(old[at]).max() > 0
    assert np.array_equal(new[at], old[at])


@pytest.mark.parametrize("name", NAMES)
def test_masked_decode_walk_with_the_run_copy_is_the_page_by_page_form(
        forms, name):
    new, old = forms["paged_attention_masked"]
    at = NAMES.index(name)
    assert np.abs(old[at]).max() > 0
    assert np.array_equal(new[at], old[at])


@pytest.mark.parametrize("rows", [1, 5], ids=["one_row", "chunk"])
def test_the_hosts_count_of_runs_is_the_planes_sum(monkeypatch, rows):
    """`index_blocks_walked` (the engine's `index_blocks` /
    `index_blocks_run`) against the plane the launch prefetches, summed
    over the key blocks each sequence's walk fetches."""
    monkeypatch.setattr(PL, "_INDEX_ROW_KEYS", KEYS)
    monkeypatch.setattr(PL, "_INDEX_KEYS", KEYS)
    this = np.full((B,), rows)
    past = PAST - (rows - 1)
    plane = np.asarray(PL._index_tables(
        jnp.zeros((1, NB, 1, BS, 128)), jnp.asarray(TABLES), KEYS)[1])
    assert plane.tolist() == RUNS.tolist()
    trips = PAST // KEYS + 1                # up to the last row's block
    blocks, run = PL.index_blocks_walked(past, this, TABLES, 32, BS, NB)
    assert blocks == int(trips.sum()) == 17
    assert run == sum(int(plane[b, :trips[b]].sum()) for b in range(B)) == 9
    assert PL.index_keys_fetched(past, this, 32, BS, WIDTH) == blocks * KEYS
    # a sequence that does not take part fetches nothing
    this[0] = 0
    assert PL.index_blocks_walked(past, this, TABLES, 32, BS, NB) == (14, 6)
