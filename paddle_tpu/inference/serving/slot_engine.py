"""Continuous-batching LLM serving engine (in-flight batching).

The reference ecosystem serves LLMs with slot-based in-flight batching
(PaddleNLP's llm predictor over `block_multihead_attention_`: requests
join and leave a fixed pool of batch slots between decode steps, so the
chip never idles while any request is live). This module is the
TPU-native version of that scheduler over `inference/llm.py`'s cached
decode:

- a fixed number of SLOTS shares one resident KV cache [L, slots, S, ...];
- each slot has its own write position: the decode step takes a per-row
  `pos` VECTOR (the uniform-`pos` fast path in llm.py serves the
  single-request case), with cache writes as per-row masked selects —
  the scatter-free form XLA turns into in-place predicated updates;
- admission happens between decode chunks: a new request is prefilled
  alone (batch 1, reusing the flash prefill) and its cache rows are
  inserted into its slot with one dynamic_update_slice on the slot axis;
- completion (eos or per-request token budget) frees the slot on the
  host side after each chunk; freed slots are refilled from the queue.

Greedy decoding only (parity with `LLMPredictor.generate()` per request
is exact and tested); sampling policies live in LLMPredictor.

This dense-slot engine is the serving BASELINE: every slot pre-reserves
`max_len` KV memory and there is no prefix sharing, preemption or
admission control. The paged subsystem (:mod:`.engine`'s
:class:`PagedServingEngine` over :mod:`.block_manager` /
:mod:`.scheduler`) supersedes it for production serving;
``tools/serving_smoke.py`` gates paged throughput against this engine.
"""
from __future__ import annotations

import functools
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...models import llama as L
from ...observability import emit as _emit
from ..llm import init_cache

__all__ = ["Request", "Completion", "ServingEngine"]


@dataclass
class Request:
    rid: int
    tokens: List[int]
    max_new_tokens: int
    eos_token_id: Optional[int] = None


@dataclass
class Completion:
    rid: int
    prompt_tokens: List[int]
    output_tokens: List[int]
    finish_reason: str  # "stop" (eos) | "length"


@dataclass
class _Slot:
    rid: int = -1
    prompt: List[int] = field(default_factory=list)
    generated: List[int] = field(default_factory=list)
    budget: int = 0
    eos: int = -1
    active: bool = False


def _apply_rope_rows(x, cos, sin):
    """x [B, 1, H, hd]; cos/sin [B, hd/2] — per-row positions (each slot is
    at a different sequence offset)."""
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    c = cos[:, None, None, :]
    s = sin[:, None, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def _block_decode_rows(x, lp, cfg: L.LlamaConfig, ck, cv, pos):
    """One decode block with per-row positions. x [B, 1, d]; ck/cv
    [B, S, KV, hd]; pos [B] int32 (write index per row)."""
    B, T, d = x.shape
    hd, nh, nkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    S = ck.shape[1]
    h = L.rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    q, k = L.qk_normed(h @ lp["wq"].astype(h.dtype),
                       h @ lp["wk"].astype(h.dtype), lp, cfg)
    v = (h @ lp["wv"].astype(h.dtype)).reshape(B, 1, nkv, hd)
    cos, sin = L.rope_cos_sin(pos, hd, cfg.rope_theta)   # [B, hd/2]
    q = _apply_rope_rows(q.reshape(B, 1, nh, hd), cos, sin)
    k = _apply_rope_rows(k.reshape(B, 1, nkv, hd), cos, sin)
    # per-row masked-select write at column pos[b] (scatter-free)
    write = (jnp.arange(S)[None, :] == pos[:, None])[:, :, None, None]
    ck = jnp.where(write, k.astype(ck.dtype), ck)
    cv = jnp.where(write, v.astype(cv.dtype), cv)
    # attention over each row's own prefix: cols <= pos[b]
    qk, ckk, cvv = q, ck, cv
    if nkv != nh:
        ckk = jnp.repeat(ck, nh // nkv, axis=2)
        cvv = jnp.repeat(cv, nh // nkv, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", qk.astype(jnp.float32),
                   ckk.astype(jnp.float32)) / (hd ** 0.5)
    cols = jnp.arange(S)[None, None, None, :]
    s = jnp.where(cols <= pos[:, None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhts,bshd->bthd", p, cvv)
    x = x + o.reshape(B, 1, nh * hd) @ lp["wo"].astype(o.dtype)
    h = L.rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    if cfg.num_experts:
        x = x + L.routed_ffn(h, lp, cfg)
    else:
        gate = jax.nn.silu(h @ lp["w1"].astype(h.dtype)) * (h @ lp["w3"].astype(h.dtype))
        x = x + gate @ lp["w2"].astype(h.dtype)
    return x, ck, cv


def _decode_rows(params, tokens, cache, pos, cfg: L.LlamaConfig):
    """tokens [B] → (last_logits [B, V] f32, cache); per-row positions."""
    x = jnp.take(params["embed"], tokens[:, None], axis=0).astype(cfg.dtype)

    def body(carry, layer):
        x = carry
        lp, ck, cv = layer
        x, ck, cv = _block_decode_rows(x, lp, cfg, ck, cv, pos)
        return x, (ck, cv)

    x, (ks, vs) = lax.scan(body, x, (params["blocks"], cache["k"],
                                     cache["v"]))
    x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = (x @ params["lm_head"].astype(x.dtype)).astype(jnp.float32)
    return logits[:, 0], {"k": ks, "v": vs}


class ServingEngine:
    """Slot-scheduler + per-row decode. Typical use:

        eng = ServingEngine(cfg, params, num_slots=8)
        rid = eng.submit([1, 2, 3], max_new_tokens=32, eos_token_id=2)
        done = eng.run()          # drains queue+slots, list of Completion
    """

    def __init__(self, cfg: L.LlamaConfig, params: Dict[str, Any],
                 num_slots: int = 8, max_len: Optional[int] = None,
                 chunk: int = 8, attn_impl: str = "auto",
                 cache_dtype=None, weight_dtype=None):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.cfg = cfg
        if weight_dtype is not None:
            params = jax.tree.map(
                lambda a: a.astype(weight_dtype)
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating) else a,
                params)
        self.params = params
        self.num_slots = int(num_slots)
        self.max_len = int(max_len or cfg.max_seq_len)
        self.chunk = int(chunk)
        self.cache_dtype = cache_dtype or cfg.dtype
        self._queue: deque[Request] = deque()
        self._slots = [_Slot() for _ in range(self.num_slots)]
        self._next_rid = 0
        self._completions: List[Completion] = []
        self.stats = {"admitted": 0, "completed": 0, "decode_chunks": 0,
                      "decode_steps": 0}

        # device state
        self._cache = init_cache(cfg, self.num_slots, self.max_len,
                                 self.cache_dtype)
        V = cfg.vocab_size
        self._last_logits = jnp.zeros((self.num_slots, V), jnp.float32)
        self._pos = jnp.zeros((self.num_slots,), jnp.int32)
        self._eos = jnp.full((self.num_slots,), -1, jnp.int32)

        cfg_, impl = cfg, attn_impl
        from ..llm import _forward_cached

        @jax.jit
        def prefill_one(params, tokens, cache, length):
            """tokens [1, T_padded] (right-padded to a bucket so prefill
            compiles once per bucket, not once per prompt length); `length`
            is the real prompt length — the next-token logits live at row
            length-1, and the padded-garbage cache columns are never
            attended (decode masks cols <= pos and overwrites col pos
            before reading it)."""
            logits, cache = _forward_cached(params, tokens, cache,
                                            jnp.int32(0), cfg_, impl)
            last = lax.dynamic_slice_in_dim(logits, length - 1, 1, axis=1)
            return last[:, 0], cache

        @functools.partial(jax.jit, donate_argnums=(0, 3, 4))
        def insert_slot(cache, small, logits_row, last_logits, pos, b,
                        prompt_len):
            cache = {
                key: lax.dynamic_update_slice(
                    cache[key], small[key],
                    (jnp.int32(0), b, jnp.int32(0), jnp.int32(0),
                     jnp.int32(0)))
                for key in ("k", "v")
            }
            last_logits = lax.dynamic_update_slice(
                last_logits, logits_row, (b, jnp.int32(0)))
            pos = lax.dynamic_update_slice(pos, prompt_len[None], (b,))
            return cache, last_logits, pos

        C = self.chunk

        @functools.partial(jax.jit, donate_argnums=(1,))
        def decode_chunk(params, cache, last_logits, pos, eos):
            """C greedy steps with per-row positions. finished rows keep
            emitting their eos; pos clamps at S-1 so parked slots never
            write out of range."""
            finished = jnp.zeros((last_logits.shape[0],), bool)

            def body(carry, _):
                logits, cache, pos, finished = carry
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                nxt = jnp.where(finished & (eos >= 0), eos, nxt)
                finished = finished | ((nxt == eos) & (eos >= 0))
                logits, cache = _decode_rows(params, nxt, cache, pos, cfg_)
                pos = jnp.minimum(pos + 1, self.max_len - 1)
                return (logits, cache, pos, finished), nxt

            (logits, cache, pos, finished), toks = lax.scan(
                body, (last_logits, cache, pos, finished), None, length=C)
            return logits, cache, pos, toks.T   # [B, C]

        self._prefill_one = prefill_one
        self._insert_slot = insert_slot
        self._decode_chunk = decode_chunk

    # -- client API ------------------------------------------------------
    def submit(self, tokens, max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None) -> int:
        tokens = [int(t) for t in np.asarray(tokens).reshape(-1)]
        if len(tokens) + max(max_new_tokens, 0) > self.max_len:
            raise ValueError(f"prompt {len(tokens)} + new {max_new_tokens} "
                             f"exceeds max_len {self.max_len}")
        rid = self._next_rid
        self._next_rid += 1
        if max_new_tokens <= 0:   # parity with generate(max_new_tokens=0)
            self._completions.append(Completion(rid, tokens, [], "length"))
            self.stats["completed"] += 1
            return rid
        self._queue.append(Request(rid, tokens, int(max_new_tokens),
                                   eos_token_id))
        return rid

    def has_work(self) -> bool:
        return bool(self._queue) or any(s.active for s in self._slots)

    def run(self) -> List[Completion]:
        """Drive until queue and slots drain; returns completions in
        finish order."""
        while self.has_work():
            self.step()
        out, self._completions = self._completions, []
        return out

    # -- scheduler internals ---------------------------------------------
    def _admit(self):
        for b, slot in enumerate(self._slots):
            if slot.active or not self._queue:
                continue
            req = self._queue.popleft()
            T = len(req.tokens)
            bucket = min(self.max_len, -(-T // 16) * 16)  # next mult of 16
            padded = req.tokens + [0] * (bucket - T)
            tokens = jnp.asarray(padded, jnp.int32)[None, :]
            small = init_cache(self.cfg, 1, self.max_len, self.cache_dtype)
            logits_row, small = self._prefill_one(self.params, tokens, small,
                                                  jnp.int32(T))
            self._cache, self._last_logits, self._pos = self._insert_slot(
                self._cache, small, logits_row, self._last_logits,
                self._pos, jnp.int32(b), jnp.int32(T))
            eos = -1 if req.eos_token_id is None else int(req.eos_token_id)
            self._eos = self._eos.at[b].set(eos)
            self._slots[b] = _Slot(rid=req.rid, prompt=req.tokens,
                                   generated=[], budget=req.max_new_tokens,
                                   eos=eos, active=True)
            self.stats["admitted"] += 1
            _emit("serving.admit", rid=req.rid, prompt_len=T,
                  queue_depth=len(self._queue), engine="slot")

    def _harvest(self, toks: np.ndarray):
        for b, slot in enumerate(self._slots):
            if not slot.active:
                continue
            for t in toks[b]:
                t = int(t)
                if slot.eos >= 0 and t == slot.eos:
                    self._finish(b, "stop")
                    break
                slot.generated.append(t)
                if len(slot.generated) >= slot.budget:
                    self._finish(b, "length")
                    break

    def _finish(self, b: int, reason: str):
        slot = self._slots[b]
        self._completions.append(Completion(slot.rid, slot.prompt,
                                            slot.generated, reason))
        self._slots[b] = _Slot()
        self.stats["completed"] += 1
        _emit("serving.complete", rid=slot.rid, reason=reason,
              generated=len(slot.generated), engine="slot")

    def step(self):
        """One scheduler tick: admit into free slots, decode one chunk,
        harvest finished requests."""
        self._admit()
        if not any(s.active for s in self._slots):
            return
        import time as _time
        t0 = _time.perf_counter()
        self._last_logits, self._cache, self._pos, toks = self._decode_chunk(
            self.params, self._cache, self._last_logits, self._pos,
            self._eos)
        toks = np.asarray(toks)   # sync before timing
        self.stats["decode_chunks"] += 1
        self.stats["decode_steps"] += self.chunk
        _emit("serving.step", dur_s=_time.perf_counter() - t0,
              tokens=self.chunk * sum(s.active for s in self._slots),
              batch=sum(s.active for s in self._slots), engine="slot")
        self._harvest(toks)
