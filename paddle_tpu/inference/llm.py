"""LLM serving: KV-cached prefill + decode over the flagship LLaMA.

Reference parity: the serving pipeline the reference builds from
`block_multihead_attention_` / `masked_multihead_attention_` +
AnalysisPredictor (SURVEY §2.6; fusion/gpu/*_attention kernels). TPU-native
shape: the whole decode step is ONE jitted program — embed → L cached
attention blocks (lax.scan over stacked layer params) → logits → greedy
argmax — with the KV cache as a donated carry, so XLA keeps it resident in
HBM and the per-token cost is the bandwidth of reading the cache once.
Cache writes are `lax.dynamic_update_slice_in_dim` (uniform position), not
scatter — the form XLA turns into an in-place DUS.

The prefill step reuses the model's flash-attention path and fills the
cache for all prompt tokens in one pass.
"""
from __future__ import annotations

import functools
import math
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core import flags
from ..models import llama as L
from ..observability import emit as _obs_emit
from ..ops.pallas import fused_ffn as FF
from . import quant as Q

__all__ = ["LLMPredictor", "init_cache"]


def _ffn_fusable(h, lp) -> bool:
    """Static (trace-time) gate: can this block's FFN run through the fused
    Pallas kernel? Checks the param leaf structure (fp or weight-only int8;
    w8a8/fp8 fall back) and the kernel's shape support."""
    kind = FF.params_kind(lp)
    if kind is None:
        return False
    w1 = lp["w1"] if kind == "fp" else lp["w1_q"]
    d, f = w1.shape[-2], w1.shape[-1]
    return FF.supported(math.prod(h.shape[:-1]), d, f)


def init_cache(cfg: L.LlamaConfig, batch: int, max_len: int,
               dtype=None) -> Dict[str, jax.Array]:
    """KV cache pytree [L, B, S, KV, hd] (layer axis scanned)."""
    dtype = dtype or cfg.dtype
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _cached_attention(q, ck, cv, pos_limit):
    """q [B, T, H, hd]; ck/cv [B, S, KV, hd]; attend to cache positions
    < pos_limit + row offset (causal within the new tokens)."""
    B, T, H, hd = q.shape
    S, KV = ck.shape[1], ck.shape[2]
    if KV != H:
        ck = jnp.repeat(ck, H // KV, axis=2)
        cv = jnp.repeat(cv, H // KV, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                   ck.astype(jnp.float32)) / (hd ** 0.5)
    # row t may see cache cols <= pos_limit + t
    cols = jnp.arange(S)[None, None, None, :]
    rows = pos_limit + jnp.arange(T)[None, None, :, None]
    s = jnp.where(cols <= rows, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", p, cv)


def _block_cached(x, lp, cfg: L.LlamaConfig, cache_k, cache_v, pos,
                  attn_impl: str, ffn_impl: str = "stock"):
    """One transformer block writing its k/v into the cache at `pos`.
    x [B, T, d]; cache_k/v [B, S, KV, hd]; pos: scalar start index.
    Returns (x_out, cache_k, cache_v)."""
    B, T, d = x.shape
    hd, nh, nkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    h = L.rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    q, k = L.qk_normed(Q.matmul_param(h, lp, "wq"),
                       Q.matmul_param(h, lp, "wk"), lp, cfg)
    v = Q.matmul_param(h, lp, "wv").reshape(B, T, nkv, hd)
    cos, sin = L.rope_cos_sin(pos + jnp.arange(T), hd, cfg.rope_theta)
    q = L.apply_rope(q.reshape(B, T, nh, hd), cos, sin)
    k = L.apply_rope(k.reshape(B, T, nkv, hd), cos, sin)
    cache_k = lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype),
                                              pos, axis=1)
    cache_v = lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype),
                                              pos, axis=1)
    if T > 1 and attn_impl != "xla" and pos is not None:
        # prefill: the fresh tokens only see themselves — use the fused
        # flash kernel on the new span (cache ahead of pos is empty)
        o = L.attention(q, k, v, impl=attn_impl)
    else:
        o = _cached_attention(q, cache_k, cache_v, pos)
    x = x + Q.matmul_param(o.reshape(B, T, nh * hd), lp, "wo")
    h = L.rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    if cfg.num_experts:
        x = x + L.routed_ffn(h, lp, cfg)
    elif ffn_impl == "pallas" and _ffn_fusable(h, lp):
        x = x + FF.apply_ffn(h, lp)
    else:
        gate = (jax.nn.silu(Q.matmul_param(h, lp, "w1"))
                * Q.matmul_param(h, lp, "w3"))
        x = x + Q.matmul_param(gate, lp, "w2")
    return x, cache_k, cache_v


def _forward_cached(params, tokens, cache, pos, cfg: L.LlamaConfig,
                    attn_impl: str, ffn_impl: str = "stock"):
    """tokens [B, T] starting at absolute position `pos` (scalar int32).
    Returns (logits [B, T, V] f32, new cache)."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)

    def body(carry, layer):
        x = carry
        lp, ck, cv = layer
        x, ck, cv = _block_cached(x, lp, cfg, ck, cv, pos, attn_impl,
                                  ffn_impl)
        return x, (ck, cv)

    x, (ks, vs) = lax.scan(body, x, (params["blocks"], cache["k"], cache["v"]))
    x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = Q.matmul_param(x, params, "lm_head").astype(jnp.float32)
    return logits, {"k": ks, "v": vs}


def _sample_next(logits, key, temperature, top_p, top_k):
    """Temperature/top-k/top-p token selection on f32 logits [B, V]
    (the serving analog of the reference's top_p_sampling fused op,
    `ops/kernels/tail_nn.py:616`). top_k is static (0 = off); top_p is a
    traced scalar or None (static off); temperature a traced scalar."""
    l = logits / temperature
    if top_k:
        # top_k is a static python int (see docstring) — int() is trace-free
        vals = jax.lax.top_k(l, int(top_k))[0]  # tpu-lint: disable=TPL001
        l = jnp.where(l < vals[..., -1:], -jnp.inf, l)
    if top_p is not None:
        sl = jnp.sort(l, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sl, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < top_p           # exclusive prefix mass
        cutoff = jnp.min(jnp.where(keep, sl, jnp.inf), axis=-1,
                         keepdims=True)
        l = jnp.where(l < cutoff, -jnp.inf, l)
    return jax.random.categorical(key, l, axis=-1).astype(jnp.int32)


_DECODE_CHUNKS = (32, 8, 1)


def _chunk_plan(n: int):
    """Exact greedy decomposition of n into chunk sizes from _DECODE_CHUNKS
    (32a + 8b + c) so any request reuses at most 3 compiled loop programs."""
    plan = []
    for c in _DECODE_CHUNKS:
        k, n = divmod(n, c)
        plan.extend([c] * k)
    return plan


class LLMPredictor:
    """Greedy/temperature decode over a functional LLaMA with a resident
    KV cache. API shape follows the reference Predictor's create→run flow;
    `generate` is the serving entry (reference: the fused-MT decode loop in
    PaddleNLP's llm predictor built on block_multihead_attention_).

    The decode loop itself runs ON DEVICE: a `lax.scan` of whole decode
    steps (argmax → embed → L cached blocks → logits) inside one jitted
    program per chunk size, with the cache as a donated carry. One host
    dispatch covers up to 32 tokens, so per-token cost is cache+weight
    bandwidth, not host round-trip latency. `weight_dtype=bfloat16`
    casts the served weights once at construction (the reference serving
    stack deploys fp16 weights the same way), halving the per-step HBM read.
    """

    def __init__(self, cfg: L.LlamaConfig, params: Dict[str, Any],
                 max_len: Optional[int] = None, attn_impl: str = "auto",
                 cache_dtype=None, weight_dtype=None,
                 quant_mode: Optional[str] = None, quant_manifest=None,
                 pallas_ffn: Optional[bool] = None):
        if cfg.block_length:
            raise NotImplementedError(
                f"LLMPredictor decodes one token a step under the causal "
                f"mask; a block-diffusion config (block_length="
                f"{cfg.block_length}) is served by "
                f"inference.serving.PagedServingEngine")
        L.require_uniform(cfg, "LLMPredictor")
        self.cfg = cfg
        if weight_dtype is not None:
            params = jax.tree.map(
                lambda a: a.astype(weight_dtype)
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating) else a,
                params)
        # quantized weight path (inference.quant): the transform swaps the
        # matmul leaves, matmul_param dispatches on pytree structure, so
        # quant vs fp compile to distinct executables with no traced branch
        self.quant_mode = Q.resolve_quant_mode(quant_mode)
        if self.quant_mode and cfg.num_experts:
            raise NotImplementedError(
                "quantized LLMPredictor covers dense LLaMA; MoE expert "
                "matmuls stay fp (drop quant_mode for MoE configs)")
        if self.quant_mode:
            manifest = Q.resolve_manifest(quant_manifest)
            if manifest is not None:
                manifest.validate_for(cfg)
            params = Q.quantize_llama_params(params, self.quant_mode,
                                             manifest)
        self.params = params
        self.max_len = int(max_len or cfg.max_seq_len)
        self.attn_impl = attn_impl
        self.cache_dtype = cache_dtype or cfg.dtype
        # fused-FFN routing resolves HERE (host side, construction time):
        # None = FLAGS_pallas_ffn on real TPU hardware; True forces the
        # kernel (interpret mode off-TPU — the parity-test hook); False = off.
        # The resolved string is a static closure constant, so the flag never
        # reaches traced code and flipping it means a new predictor, not a
        # retrace of this one.
        if pallas_ffn is None:
            pallas_ffn = bool(flags.flag_value("pallas_ffn")
                              and FF.available())
        self.ffn_impl = "pallas" if pallas_ffn else "stock"

        cfg_ = cfg
        impl = attn_impl
        fimpl = self.ffn_impl

        @jax.jit
        def prefill(params, tokens, cache):
            logits, cache = _forward_cached(params, tokens, cache,
                                            jnp.int32(0), cfg_, impl, fimpl)
            return logits[:, -1], cache

        @functools.partial(jax.jit, donate_argnums=(2,))
        def decode_step(params, token, cache, pos):
            logits, cache = _forward_cached(params, token[:, None], cache,
                                            pos, cfg_, "xla", fimpl)
            return logits[:, -1], cache

        self._prefill = prefill
        self._decode = decode_step
        # keyed by (chunk_len, sample, top_k, use_top_p)
        self._chunk_fns: Dict[Tuple[int, bool, int, bool], Any] = {}

    def _decode_chunk_fn(self, C: int, top_k: int = 0, use_top_p: bool = False,
                         sample: bool = False):
        """Jitted on-device loop of C decode steps. Carry: (last_logits,
        cache, pos, finished[, key]); emits the C chosen tokens. `eos` is a
        traced int32 scalar, -1 = no eos (finished then never sets).
        Greedy by default; `sample` adds temperature/top-k/top-p selection
        with the PRNG key threaded through the carry."""
        cache_key = (C, sample, int(top_k), bool(use_top_p))
        fn = self._chunk_fns.get(cache_key)
        if fn is not None:
            return fn
        cfg_ = self.cfg
        fimpl = self.ffn_impl

        if sample:
            @functools.partial(jax.jit, donate_argnums=(2,))
            def decode_chunk(params, last_logits, cache, pos, finished, eos,
                             key, temperature, top_p):
                tp = top_p if use_top_p else None

                def body(carry, _):
                    logits, cache, pos, finished, key = carry
                    key, sub = jax.random.split(key)
                    nxt = _sample_next(logits, sub, temperature, tp, top_k)
                    nxt = jnp.where(finished, eos, nxt)
                    finished = finished | (nxt == eos)
                    logits, cache = _forward_cached(params, nxt[:, None],
                                                    cache, pos, cfg_, "xla",
                                                    fimpl)
                    return (logits[:, -1], cache, pos + 1, finished, key), nxt

                (logits, cache, pos, finished, key), toks = lax.scan(
                    body, (last_logits, cache, pos, finished, key), None,
                    length=C)
                return logits, cache, finished, key, toks.T  # [B, C]
        else:
            @functools.partial(jax.jit, donate_argnums=(2,))
            def decode_chunk(params, last_logits, cache, pos, finished, eos):
                def body(carry, _):
                    logits, cache, pos, finished = carry
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    nxt = jnp.where(finished, eos, nxt)
                    finished = finished | (nxt == eos)
                    logits, cache = _forward_cached(params, nxt[:, None],
                                                    cache, pos, cfg_, "xla",
                                                    fimpl)
                    return (logits[:, -1], cache, pos + 1, finished), nxt

                (logits, cache, pos, finished), toks = lax.scan(
                    body, (last_logits, cache, pos, finished), None, length=C)
                return logits, cache, finished, toks.T  # [B, C]

        self._chunk_fns[cache_key] = decode_chunk
        return decode_chunk

    def generate(self, tokens, max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None,
                 return_scores: bool = False,
                 temperature: Optional[float] = None,
                 top_k: int = 0, top_p: Optional[float] = None,
                 seed: int = 0):
        """tokens [B, T] int32 prompt → [B, T + max_new] completion.
        Greedy by default; `temperature` (with optional `top_k`/`top_p`)
        switches to on-device sampling — the serving analog of the
        reference's top_p_sampling decode. Default path: on-device chunked
        scan (one dispatch per ≤32 tokens). `return_scores=True` keeps the
        host-driven per-token loop since it must surface every step's
        logits."""
        tokens = jnp.asarray(tokens, jnp.int32)
        B, T = tokens.shape
        if T + max_new_tokens > self.max_len:
            raise ValueError(f"prompt {T} + new {max_new_tokens} exceeds "
                             f"max_len {self.max_len}")
        if temperature is None and (top_k or top_p is not None):
            temperature = 1.0        # top-k/top-p imply sampling
        sample = temperature is not None and temperature > 0.0
        if temperature is not None and temperature <= 0.0:
            top_k, top_p = 0, None   # temperature<=0 = greedy by convention
        cache = init_cache(self.cfg, B, self.max_len, self.cache_dtype)
        t0 = time.perf_counter()
        last_logits, cache = self._prefill(self.params, tokens, cache)
        _obs_emit("serving.prefill", dur_s=time.perf_counter() - t0,
                  tokens=B * T, batch=B, prompt_len=T)
        if return_scores:
            if sample:
                raise NotImplementedError(
                    "return_scores=True uses the greedy host loop; "
                    "sampling + per-step scores is not supported")
            return self._generate_hostloop(tokens, last_logits, cache,
                                           max_new_tokens, eos_token_id)
        eos = jnp.int32(-1 if eos_token_id is None else eos_token_id)
        finished = jnp.zeros((B,), bool)
        key = jax.random.PRNGKey(int(seed))
        temp = jnp.float32(temperature if sample else 1.0)
        tp = jnp.float32(top_p if top_p is not None else 1.0)
        out = [tokens]
        done = 0
        for C in _chunk_plan(max_new_tokens):
            t0 = time.perf_counter()
            if sample:
                fn = self._decode_chunk_fn(C, top_k=int(top_k),
                                           use_top_p=top_p is not None,
                                           sample=True)
                last_logits, cache, finished, key, toks = fn(
                    self.params, last_logits, cache, jnp.int32(T + done),
                    finished, eos, key, temp, tp)
            else:
                fn = self._decode_chunk_fn(C)
                last_logits, cache, finished, toks = fn(
                    self.params, last_logits, cache, jnp.int32(T + done),
                    finished, eos)
            _obs_emit("serving.decode_chunk",
                      dur_s=time.perf_counter() - t0, tokens=B * C,
                      chunk=C, pos=T + done)
            out.append(toks)
            done += C
            if eos_token_id is not None and bool(finished.all()):
                rem = max_new_tokens - done
                if rem:
                    out.append(jnp.full((B, rem), eos_token_id, jnp.int32))
                break
        return jnp.concatenate(out, axis=1)

    def _generate_hostloop(self, tokens, last_logits, cache, max_new_tokens,
                           eos_token_id):
        """Per-token host loop; surfaces each step's logits (scores).
        The sequence is eos-padded to [B, T + max_new] so both generate
        paths return the same shape; `scores` covers only the steps that
        actually ran (early eos stop ends the loop)."""
        B, T = tokens.shape
        out = [tokens]
        scores = []
        finished = jnp.zeros((B,), bool)
        done = 0
        for i in range(max_new_tokens):
            nxt = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
            if eos_token_id is not None:
                nxt = jnp.where(finished, eos_token_id, nxt)
                finished = finished | (nxt == eos_token_id)
            out.append(nxt[:, None])
            scores.append(last_logits)
            done = i + 1
            if i == max_new_tokens - 1:   # last token decided: the next
                break                     # forward's logits would be unused
            if eos_token_id is not None and bool(finished.all()):
                break
            last_logits, cache = self._decode(self.params, nxt, cache,
                                              jnp.int32(T + i))
        if eos_token_id is not None and done < max_new_tokens:
            out.append(jnp.full((B, max_new_tokens - done), eos_token_id,
                                jnp.int32))
        return jnp.concatenate(out, axis=1), jnp.stack(scores, axis=1)
