"""Closed-loop chat sessions through `PagedServingEngine`: K clients, each a
run of sessions of `turns` turns. Every session starts with the same
system prefix of `prefix_tokens` ids made from `--seed`; turn t's prompt
is the prefix, the earlier turns' user parts and the engine's own answers
to them, and a new user part whose length comes from the traffic file's
grid in the order its `order_seed` fixes. The next turn is submitted in
the host iteration that harvests the last token of the one before, after
the last turn a new session: a closed loop on tick boundaries, as
`closed_loop_serve`, whose `Loop`, `Client` and books this driver reuses.

The work is the block manager's: a turn's prompt is cached pages up to the
last full page of what the engine has already seen, and a new part that
fits one tick.

`correct`: the configuration's own check (`check_against_reference`,
unchanged), then a session's next turn and a prompt that forces a page
copy judged the same way, so that tokens that come from cached and from
copied pages are held to the reference too (`check_cached_turn`); the
share that has to agree is taken over all of these positions together.
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from ..lib import agreement, program_trace, reference, serve_window
from ..lib import traffic as T
from ..lib.harness import Context, Record
from ..lib.program import llama_config
from .closed_loop_serve import Loop, check_against_reference


def system_prefix(traffic: dict, seed: int, vocab_size: int) -> np.ndarray:
    """The ids every session of a run starts with."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 4])
    return rng.integers(1, vocab_size, traffic["prefix_tokens"],
                        dtype=np.int32)


def check_cached_turn(eng, cfg: dict, params, seed: int):
    """Three requests through the engine, each judged against the
    reference's teacher-forced logits: 300 ids; those, the answer and 100
    more (a session's next turn: served from cached pages); and the first
    296 of them followed by 60 others (a prompt that leaves a cached page
    half way: that page is copied, copy-on-write, which also compiles the
    page-copy executable before the window). Returns the positions that
    agreed, the positions judged, whether the hits and the copy were
    found, and the notes."""
    c = cfg["correctness"]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 5])
    first, more, other = (rng.integers(1, cfg["vocab_size"], n,
                                       dtype=np.int32) for n in (300, 100, 60))
    hits0 = eng.blocks.stats["prefix_hit_tokens"]
    copies0 = eng.stats["cow_block_copies"]
    agreed, judged, prompt = 0.0, 0, first
    with jax.default_matmul_precision("highest"):
        for turn in range(3):
            rid = eng.submit(prompt, max_new_tokens=16)
            out = np.asarray({d.rid: d.output_tokens
                              for d in eng.run()}[rid], np.int32)
            seq = np.zeros((c["reference_len"],), np.int32)
            seq[:len(prompt)] = prompt
            seq[len(prompt):len(prompt) + len(out)] = out
            at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
            logits = reference.logits_at(
                params, jnp.asarray(seq), jnp.asarray(at),
                heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"])
            share, _ = agreement.judge(np.asarray(logits), out)
            agreed += share * len(out)
            judged += len(out)
            prompt = (np.concatenate([first, out, more]) if turn == 0
                      else np.concatenate([first[:296], other]))
    hits = eng.blocks.stats["prefix_hit_tokens"] - hits0
    copies = eng.stats["cow_block_copies"] - copies0
    # the next turn finds the first prompt's 18 full pages, the third
    # request those and 8 ids of the page it copies
    return agreed, judged, hits >= 2 * 288 + 8 and copies >= 1, {
        "cached_turn_agreement": agreed / judged,
        "cached_turn_hit_tokens": hits, "cached_turn_copies": copies}


def check(eng, cfg: dict, params, lcfg, seed: int):
    """The share that has to agree is taken over every position the run
    judges, the six requests' 288 and the cached turns' 48 together: 0.98
    of 48 alone would allow no near tie at all, and bfloat16 flips one
    position in about a thousand (one run in 13 read 47 of 48 on parent
    and change alike, PR 27). Tokens from a wrong page are wrong by the
    dozen, which 0.98 of 336 does not bear either."""
    ok, notes = check_against_reference(eng, cfg, params, seed)
    if "agreement" not in notes:
        return False, notes
    agreed, judged, found, turn_notes = check_cached_turn(eng, cfg, params,
                                                          seed)
    share = ((notes["agreement"] * notes["positions_judged"] + agreed)
             / (notes["positions_judged"] + judged))
    notes.update(turn_notes, agreement_with_cached_turns=share)
    return ok and found and share >= agreement.MIN_AGREEMENT, notes


class SessionLoop(Loop):
    """The closed loop whose requests are the turns of sessions."""

    def __init__(self, eng, ctx: Context, spans):
        self.prefix = system_prefix(ctx.traffic, ctx.seed,
                                    ctx.config["vocab_size"])
        self.prompt_tokens_submitted = 0
        super().__init__(eng, ctx, spans)

    def reset_books(self):
        super().reset_books()
        self.prompt_tokens_submitted = 0
        self.blocks0 = dict(self.eng.blocks.stats)

    def submit(self, client):
        tr = self.ctx.traffic
        # the answer to the turn before: what the engine streamed for it
        answer = ([] if client.rid is None
                  else list(self.eng.stream(client.rid)))
        client.j += 1
        user = T.request_tokens(tr, self.ctx.seed, client.index, client.j,
                                self.ctx.config["vocab_size"])
        if client.j % tr["turns"] == 0:
            client.history = [self.prefix]
        else:
            client.history.append(np.asarray(answer, np.int32))
        client.history.append(user)
        tokens = np.concatenate(client.history)
        client.prompt_len, client.got = len(tokens), 0
        client.want = T.new_tokens(tr, client.index, client.j)
        self.prompt_tokens_submitted += len(tokens)
        client.submitted_s = time.perf_counter()
        client.rid = self.eng.submit(tokens, max_new_tokens=client.want,
                                     eos_token_id=None)
        self.by_rid[client.rid] = client

    def counters(self) -> dict:
        out = super().counters()
        blocks = self.eng.blocks.stats
        out["prompt_tokens_submitted"] = self.prompt_tokens_submitted
        out["prefix_hit_tokens"] = (blocks["prefix_hit_tokens"]
                                    - self.blocks0["prefix_hit_tokens"])
        out["cow_block_copies"] = (self.eng.stats["cow_block_copies"]
                                   - self.stats0["cow_block_copies"])
        return out


def run(ctx: Context) -> Record:
    record = serve_window.run(ctx, llama_config, check, SessionLoop)
    trace = program_trace.of_record(record)
    if trace is not None:
        # for PERF.md's breakdown: this cell reports no gap_p90_ms, so the
        # tick_*_share metrics (which move it) may not list it
        record.notes["scope_shares"] = program_trace.scope_shares(trace)
        record.notes["idle_shares"] = program_trace.idle_shares(trace)
    return record
