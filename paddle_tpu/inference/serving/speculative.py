"""Speculative decoding over the paged engine: draft-propose, verify
in the ONE jitted step, greedy-accept — bit-exact vs plain decode.

A small draft model proposes ``k`` tokens per tick for each greedy
decode-ready sequence; the engine widens that sequence's chunk from 1
to ``k+1`` tokens so the EXISTING fused mixed prefill+decode executable
verifies every proposal in a single launch (spec-mode executables
additionally return the all-position argmax — the verify read). Greedy
verification accepts the longest proposal prefix that matches the
target model's own argmax and always emits one bonus token, so the
emitted stream is IDENTICAL to non-speculative greedy decode: a wrong
draft costs acceptance rate, never correctness. Preemption recompute,
prefix/COW sharing and router replay-and-confirm failover therefore
stay bit-exact with speculation on.

The draft shares the paged-KV *machinery* — same block tables, same
block ids, its own (small) cache arrays indexed by them — so paging,
COW mirroring and preemption need no second allocator:

- per-sequence draft progress (``draft_c``) is epoch-guarded by
  ``seq.preemptions``: a preempted sequence's draft KV is recomputed by
  the catch-up pass exactly like the target's recompute;
- engine COW page copies are mirrored eagerly into the draft caches;
- catch-up and proposal run through exactly TWO cached draft
  executables (a fixed-width catch-up chunk and the 1-token proposal
  step) — zero steady-state retraces on the draft side too.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...core import flags
from ...models import llama as L
from ...observability import emit as _emit
from ...ops.kernels.serving_attention import block_multihead_attention_
from .. import quant as Q

flags.define_flag("spec_k", 4,
                  "Draft tokens proposed per speculative decode tick "
                  "(the verify chunk is k+1 tokens wide). 0 disables "
                  "speculation even when a draft model is attached.")

__all__ = ["DraftModel"]


class DraftModel:
    """The proposer half of speculative decoding. Construct with the
    draft config+params, attach via
    ``PagedServingEngine(..., draft=DraftModel(dcfg, dparams))`` (the
    engine calls :meth:`bind`). The draft must share the target's
    vocabulary; everything else (layers, width, heads) may be smaller —
    that is the point."""

    def __init__(self, cfg: L.LlamaConfig, params: Dict[str, Any]):
        if cfg.num_experts:
            raise NotImplementedError(
                "draft models are dense LLaMA (MoE drafts defeat the "
                "latency purpose)")
        L.require_uniform(cfg, "DraftModel")
        self.cfg = cfg
        self.params = params
        self.engine = None
        self._kc = None
        self._vc = None
        self._rope = None
        self._fns: Dict[int, Any] = {}
        self._chunk = 0
        # rid -> (draft tokens computed, seq.preemptions epoch)
        self._state: Dict[int, Tuple[int, int]] = {}
        # rid -> (num_computed at propose, k) awaiting commit
        self._pending: Dict[int, Tuple[int, int]] = {}
        self.stats = {"draft_steps": 0, "draft_builds": 0, "ticks": 0,
                      "proposed": 0, "accepted": 0, "bonus": 0,
                      "catchup_tokens": 0}

    # -- engine attachment -------------------------------------------------
    def bind(self, engine) -> "DraftModel":
        """Adopt the engine's paged geometry: draft caches are
        [L_d, num_blocks, KV_d, block_size, hd_d], indexed by the SAME
        block ids the engine's BlockManager hands out."""
        if self.cfg.vocab_size != engine.cfg.vocab_size:
            raise ValueError(
                f"draft vocab {self.cfg.vocab_size} != target vocab "
                f"{engine.cfg.vocab_size}: greedy verification compares "
                f"token ids, the vocabularies must match")
        if self.cfg.max_seq_len < engine.max_len:
            raise ValueError(
                f"draft max_seq_len {self.cfg.max_seq_len} < engine "
                f"max_len {engine.max_len}: the draft must cover every "
                f"position the target serves")
        self.engine = engine
        cfg = self.cfg
        shape = (cfg.num_layers, engine.num_blocks, cfg.num_kv_heads,
                 engine.block_size, cfg.head_dim)
        self._kc = jnp.zeros(shape, cfg.dtype)
        self._vc = jnp.zeros(shape, cfg.dtype)
        cos, sin = L.rope_cos_sin(jnp.arange(engine.max_len),
                                  cfg.head_dim, cfg.rope_theta)
        self._rope = jnp.stack([
            jnp.concatenate([cos, cos], -1)[None],
            jnp.concatenate([sin, sin], -1)[None]])
        # fixed catch-up width: with the 1-token proposal step this keeps
        # the draft at exactly two steady-state executables
        self._chunk = max(1, int(engine.token_budget))
        return self

    # -- the draft step ----------------------------------------------------
    def _build_fn(self, n_pad: int):
        cfg = self.cfg
        bs = self.engine.block_size

        @functools.partial(jax.jit, donate_argnums=(1, 2))
        def draft_fn(params, kc, vc, tokens, table, dec, this, cu, rope):
            x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
            zeros_b = jnp.zeros((1,), jnp.int32)

            def body(carry, layer):
                x = carry
                lp, k_cache, v_cache = layer
                h = L.rms_norm(x, lp["attn_norm"], cfg.rms_eps)
                q = Q.matmul_param(h, lp, "wq")
                k = Q.matmul_param(h, lp, "wk")
                v = Q.matmul_param(h, lp, "wv")
                qkv = jnp.concatenate([q, k, v], axis=-1)
                o, _, k_cache, v_cache = \
                    block_multihead_attention_.__wrapped__(
                        qkv, k_cache, v_cache, zeros_b, dec, this,
                        cu_seqlens_q=cu, block_tables=table,
                        rope_emb=rope, use_neox_style=True,
                        block_size=bs, rope_theta=cfg.rope_theta,
                        use_pallas=False)
                x = x + Q.matmul_param(o, lp, "wo")
                h = L.rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
                gate = (jax.nn.silu(Q.matmul_param(h, lp, "w1"))
                        * Q.matmul_param(h, lp, "w3"))
                x = x + Q.matmul_param(gate, lp, "w2")
                return x, (k_cache, v_cache)

            x, (kcs, vcs) = lax.scan(
                body, x, (params["blocks"], kc, vc))
            h = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
            logits = Q.matmul_param(h, params, "lm_head"
                                    ).astype(jnp.float32)
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                    kcs, vcs)

        return draft_fn

    def _run(self, n_pad: int, toks: np.ndarray, table: np.ndarray,
             start: int, n: int) -> np.ndarray:
        fn = self._fns.get(n_pad)
        if fn is None:
            fn = self._build_fn(n_pad)
            self._fns[n_pad] = fn
            self.stats["draft_builds"] += 1
        cu = np.zeros((2,), np.int32)
        cu[1] = n
        out, self._kc, self._vc = fn(
            self.params, self._kc, self._vc, jnp.asarray(toks),
            jnp.asarray(table), jnp.asarray([start], np.int32),
            jnp.asarray([n], np.int32), jnp.asarray(cu), self._rope)
        self.stats["draft_steps"] += 1
        _emit("spec.draft_step", tokens=n)
        return np.asarray(out)

    # -- propose / commit --------------------------------------------------
    def propose(self, seq, k: int) -> List[int]:
        """Draft k tokens for a decode-ready sequence. The caller has
        already grown the block table to cover ``len(tokens)+k``
        positions. Catch-up recomputes any draft-KV gap (dc..c) — after
        preemption that is the whole sequence, mirroring the target's
        recompute; writes into prefix-shared pages are benign because
        draft KV is a pure function of the token chain (identical for
        every sharer of a hash-matched page)."""
        eng = self.engine
        rid = seq.rid
        c = seq.num_computed
        st = self._state.get(rid)
        dc = 0
        if st is not None and st[1] == seq.preemptions and st[0] <= c:
            dc = st[0]
        row = eng.blocks.block_table(rid)
        table = np.full((1, eng.max_blocks_per_seq), -1, np.int32)
        table[0, :len(row)] = row
        pos = dc
        while pos < c:
            m = min(self._chunk, c - pos)
            toks = np.zeros((self._chunk,), np.int32)
            toks[:m] = seq.tokens[pos:pos + m]
            self._run(self._chunk, toks, table, pos, m)
            self.stats["catchup_tokens"] += m
            pos += m
        props: List[int] = []
        tok = int(seq.tokens[c])
        for _ in range(int(k)):
            g = self._run(1, np.asarray([tok], np.int32), table, pos, 1)
            tok = int(g[0])
            props.append(tok)
            pos += 1
        self._pending[rid] = (c, int(k))
        return props

    def commit(self, seq, accepted: int) -> None:
        """Record verified progress: draft KV is valid through the last
        position whose input token the target confirmed."""
        pend = self._pending.pop(seq.rid, None)
        if pend is None:
            return
        c, k = pend
        self._state[seq.rid] = (c + 1 + min(int(accepted), k - 1),
                                seq.preemptions)

    def forget(self, rid: int) -> None:
        self._state.pop(rid, None)
        self._pending.pop(rid, None)

    # -- paged-KV mirroring ------------------------------------------------
    def copy_blocks(self, pairs) -> None:
        """Mirror the engine's COW page copies into the draft caches
        (eager per-pair writes — no new executable shapes)."""
        for s, d in pairs:
            self._kc = self._kc.at[:, d].set(self._kc[:, s])
            self._vc = self._vc.at[:, d].set(self._vc[:, s])

    # -- accounting --------------------------------------------------------
    def record_tick(self, proposed: int, accepted: int) -> None:
        self.stats["ticks"] += 1
        self.stats["proposed"] += int(proposed)
        self.stats["accepted"] += int(accepted)
        self.stats["bonus"] += 1

    @property
    def acceptance_rate(self) -> float:
        p = self.stats["proposed"]
        return round(self.stats["accepted"] / p, 4) if p else 0.0

    def snapshot(self) -> dict:
        return {"acceptance_rate": self.acceptance_rate,
                "tracked_sequences": len(self._state), **self.stats}
