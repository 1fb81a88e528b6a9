"""Stratified traffic: every seed offers the same work, in another order."""
from collections import Counter

import pytest

from benchmark.lib import traffic as T
from benchmark.tests.helpers import fixture

import json
import os

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")
CLOSED = [f[:-5] for f in sorted(os.listdir(TRAFFIC_DIR))
          if json.load(open(os.path.join(TRAFFIC_DIR, f)))["kind"]
          == "closed_loop_serve"]


def mix(name):
    with open(os.path.join(TRAFFIC_DIR, name + ".json")) as f:
        return json.load(f)


def lengths_of_pass(tr, p):
    grid, k = T.prompt_grid(tr), tr["clients"]
    out = []
    for i in range(p * len(grid), (p + 1) * len(grid)):
        out.append(T.request_length(tr, i % k, i // k))
    return out


@pytest.mark.parametrize("name", CLOSED)
def test_every_pass_offers_the_whole_grid_in_its_own_order(name):
    tr = mix(name)
    grid = T.prompt_grid(tr)
    passes = [lengths_of_pass(tr, p) for p in range(4)]
    for lengths in passes:
        assert Counter(lengths) == Counter(grid)
    assert len({tuple(x) for x in passes}) == 4


@pytest.mark.parametrize("name", CLOSED)
@pytest.mark.parametrize("seed", [0, 1, 7, 2**31 + 12345, 2**32 + 3])
def test_the_seed_makes_the_token_ids_and_nothing_of_the_sizes(name, seed):
    tr = mix(name)
    for client, j in ((0, 0), (tr["clients"] - 1, 3)):
        ids = T.request_tokens(tr, seed, client, j, 32768)
        assert len(ids) == T.request_length(tr, client, j)
        assert not (ids[:8] == T.request_tokens(tr, seed + 1, client, j,
                                                32768)[:8]).all()


def test_the_cells_grids_are_the_issues():
    assert T.prompt_grid(mix("closed_decode16")) == list(range(64, 245, 12))
    long = T.prompt_grid(mix("closed_longprompt4"))
    assert long == list(range(512, 1713, 80))
    assert len(long) == 16 and sum(long) / 16 == 1112


def test_tokens_are_seeded_and_never_zero():
    tr = fixture("traffic", "tiny_closed")
    a = T.request_tokens(tr, 5, 1, 2, 256)
    assert (a == T.request_tokens(tr, 5, 1, 2, 256)).all()
    assert len(a) == T.request_length(tr, 1, 2) and a.min() >= 1
    assert not (a[:8] == T.request_tokens(tr, 6, 1, 2, 256)[:8]).all()


def test_stagger_cuts_only_first_requests():
    tr = mix("closed_decode16")
    firsts = [T.new_tokens(tr, c, 0) for c in range(16)]
    assert firsts == [16 * (c + 1) for c in range(16)]
    assert {T.new_tokens(tr, c, j) for c in range(16) for j in (1, 2)} == {256}
    assert T.new_tokens(mix("closed_longprompt4"), 0, 0) == 16


def test_a_grid_that_no_pass_covers_is_refused():
    with pytest.raises(ValueError):
        T.prompt_grid({"clients": 3,
                       "prompt_grid": {"first": 8, "last": 44, "step": 12}})
