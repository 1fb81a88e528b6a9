"""The one general traffic generator. A traffic mix is a data file of
parameters under benchmark/traffic/; nothing here knows a mix by name.

Closed loops (`kind: closed_loop_serve`): K clients, each submitting its
next request in the host iteration that harvests its last token. Lengths
come from the file's fixed grid. The i-th request overall (client c's
j-th request is i = j*K + c) takes the length grid[perm_p[i mod G]], where
perm_p is the permutation for pass p = i // G drawn from the file's
`order_seed`: every pass of G requests offers the whole grid once.

`--seed` makes the token ids (and the weights) and nothing else. It does
not touch the order: a simulation of this loop through the real scheduler
with every tick costing exactly its mode's time (PR 24, PERF.md) gave the
mean time to first token a spread of 6.4 % over seeds that permuted the
order and 4.3 % over seeds that only rotated one fixed order, because
which prompts queue behind which is work, not noise. With the order in
the file every run walks the same sequence of scheduler states.
"""
from __future__ import annotations

import numpy as np

# numpy seeds are 32-bit; the driver's seeds run a little past 2**31
_MASK = 0xFFFFFFFF


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & _MASK, int(seed) >> 32,
                                  *[int(s) for s in stream]])


def prompt_grid(traffic: dict) -> list:
    g = traffic["prompt_grid"]
    grid = list(range(g["first"], g["last"] + 1, g["step"]))
    if len(grid) % traffic["clients"] and traffic["clients"] % len(grid):
        raise ValueError("the grid's size and the client count must divide "
                         "one another, so that a pass covers the grid")
    return grid


def request_length(traffic: dict, client: int, j: int) -> int:
    """Prompt length of client `client`'s j-th request."""
    grid = prompt_grid(traffic)
    i = j * traffic["clients"] + client
    perm = _rng(traffic["order_seed"], 1, i // len(grid)).permutation(
        len(grid))
    return grid[perm[i % len(grid)]]


def request_tokens(traffic: dict, seed: int, client: int, j: int,
                   vocab_size: int) -> np.ndarray:
    """Token ids of that request's prompt: seeded, distinct per request,
    never 0 (no prompt shares a prefix page with another by construction
    of 16 or more equal leading ids: the ids are uniform draws)."""
    n = request_length(traffic, client, j)
    return _rng(seed, 2, client, j).integers(1, vocab_size, n,
                                             dtype=np.int32)


def new_tokens(traffic: dict, client: int, j: int) -> int:
    """Output length of client `client`'s j-th request. With `stagger`
    set, each client's first request is cut to (client+1)/K of the full
    length, so that from then on one client finishes every
    max_new_tokens/K ticks and they never finish in waves."""
    full = traffic["max_new_tokens"]
    if j == 0 and traffic.get("stagger"):
        return max(1, full * (client + 1) // traffic["clients"])
    return full
