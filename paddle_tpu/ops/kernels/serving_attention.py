"""Serving / decode attention family — the LLM-inference op tier.

Reference parity targets (VERDICT r3 Missing #3):
- `masked_multihead_attention_` — one-step decode attention over a dense
  KV cache (`paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu`,
  python/paddle/incubate/nn/functional/masked_multihead_attention.py)
- `block_multihead_attention_` — paged-KV-cache attention for mixed
  prefill/decode batches (`block_multihead_attention_kernel.cu`)
- `flash_attn_unpadded` / `flash_attn_varlen_qkvpacked` — varlen flash
  (`paddle/phi/kernels/gpu/flash_attn_kernel.cc` FlashAttnUnpaddedKernel)
- `variable_length_memory_efficient_attention`
  (`fusion/cutlass/variable_length_memory_efficient_attention.cu`)
- `fused_multi_transformer_` — whole-stack serving transformer
  (`fusion/gpu/fused_multi_transformer_op.cu`,
  incubate/nn/functional/fused_transformer.py:976)

TPU-native design, not a port: the CUDA kernels exist to hand-schedule
gather+dot over ragged caches; on TPU the same ops are expressed as
static-shape XLA programs — full-cache reads with position masks (the
decode step is HBM-bandwidth-bound either way; a masked read of the padded
cache costs the same bytes as the CUDA kernel's bounded read when the
cache is sized to the batch's max length) — while the varlen prefill path
routes to the Pallas flash kernel's segment-id mode
(ops/pallas/flash_attention.py) so the MXU sees one fused kernel.

Cache quantization: `block_multihead_attention_` serves int8 paged caches
— per-head quant multipliers on the append path, per-page dequant scales
folded into the score/probability products on the read path (the scale is
constant over head_dim, so it factors out of the dot; no fp copy of the
cache is ever materialized). Output-side quant args (qkv_out_scale /
out_shift / out_smooth) still raise explicitly.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..dispatch import register_op

__all__ = [
    "masked_multihead_attention_", "block_multihead_attention_",
    "flash_attn_unpadded", "flash_attn_varlen_qkvpacked",
    "variable_length_memory_efficient_attention", "fused_multi_transformer_",
]


def _require_no_quant(**kwargs):
    set_args = [k for k, v in kwargs.items() if v is not None]
    if set_args:
        raise NotImplementedError(
            f"quantized-cache serving args not implemented: {set_args}; "
            "use the bf16 cache path (PTQ int8 covers weight quant)")


def _rope_pairwise(x, cos, sin, neox: bool):
    """Apply rotary embedding to x [..., hd] given cos/sin [..., hd//2].
    neox=False: adjacent-pair (GPT-J / paddle default) rotation;
    neox=True: rotate-half convention."""
    x32 = x.astype(jnp.float32)
    hd = x.shape[-1]
    if neox:
        x1, x2 = x32[..., : hd // 2], x32[..., hd // 2:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
    else:
        x1, x2 = x32[..., 0::2], x32[..., 1::2]
        r1 = x1 * cos - x2 * sin
        r2 = x2 * cos + x1 * sin
        out = jnp.stack([r1, r2], axis=-1).reshape(x32.shape)
    return out.astype(x.dtype)


def _rotary_table(rotary_t, hd):
    """Normalize a rotary tensor into (cos, sin) tables [Br, S, hd//2] f32,
    Br in {1, B}.

    Accepts both reference layouts: a leading stack dim of 2 (cos over sin,
    the fused_multi_transformer `rotary_embs` [2, B, 1, S, hd] form) or a
    single tensor with cos in even / sin in odd lanes (the MMHA
    `rotary_tensor` [B, 1, 1, S, hd] form)."""
    rt = jnp.asarray(rotary_t, jnp.float32)
    if rt.ndim >= 4 and rt.shape[0] == 2:      # [2, B?, ..., S, hd] stack
        cos_t = rt[0].reshape((-1,) + rt.shape[-2:])   # [Br, S, hd]
        sin_t = rt[1].reshape((-1,) + rt.shape[-2:])
        return cos_t[..., : hd // 2], sin_t[..., : hd // 2]
    rt = rt.reshape((-1,) + rt.shape[-2:]) if rt.ndim > 2 else rt[None]
    # interleaved lanes: [B,1,1,S,hd] / [1,S,hd] / [S,hd]
    return rt[..., 0::2], rt[..., 1::2]


def _split_rotary(rotary_t, pos, hd):
    """(cos, sin) [B, hd//2] at integer positions `pos` [B] — one position
    per batch row (the decode-step gather)."""
    cos_t, sin_t = _rotary_table(rotary_t, hd)
    if cos_t.shape[0] == 1:
        return cos_t[0][pos], sin_t[0][pos]
    b = jnp.arange(pos.shape[0])
    return cos_t[b, pos], sin_t[b, pos]


# ---------------------------------------------------------------------------
# masked_multihead_attention_ (dense cache, one decode step)
# ---------------------------------------------------------------------------

@register_op
def masked_multihead_attention_(x, cache_kv=None, bias=None, src_mask=None,
                                cum_offsets=None, sequence_lengths=None,
                                rotary_tensor=None, beam_cache_offset=None,
                                qkv_out_scale=None, out_shift=None,
                                out_smooth=None, seq_len=1, rotary_emb_dims=0,
                                use_neox_rotary_style=False,
                                compute_dtype="default", out_scale=-1.0,
                                quant_round_type=1, quant_max_bound=127.0,
                                quant_min_bound=-127.0):
    """One-step decode attention. x [B, 3*H*hd] fused qkv for the new token;
    cache_kv [2, B, H, max_seq, hd]; sequence_lengths [B(,1)] = number of
    tokens ALREADY in the cache (the new token lands at that index).

    Returns (out [B, H*hd], cache_kv_out) — cache semantically in-place
    (trailing `_` op), functionally returned (XLA donation makes it真 in
    place under jit).
    """
    _require_no_quant(qkv_out_scale=qkv_out_scale, out_shift=out_shift,
                      out_smooth=out_smooth)
    if beam_cache_offset is not None:
        raise NotImplementedError("beam search cache offsets: use the "
                                  "beam_search op family for decode-time beams")
    if cache_kv is None:
        raise ValueError("masked_multihead_attention_ requires cache_kv")
    two, B, H, S, hd = cache_kv.shape
    qkv = x.reshape(B, 3, H, hd)
    if bias is not None:
        qkv = qkv + bias.reshape(1, 3, H, hd).astype(qkv.dtype)
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]          # [B, H, hd]

    if sequence_lengths is not None:
        pos = sequence_lengths.reshape(-1).astype(jnp.int32)  # [B]
    else:
        pos = jnp.zeros((B,), jnp.int32)

    if rotary_emb_dims and rotary_tensor is not None:
        cos, sin = _split_rotary(rotary_tensor, pos, hd)  # [B, hd//2]
        q = _rope_pairwise(q, cos[:, None], sin[:, None], use_neox_rotary_style)
        k = _rope_pairwise(k, cos[:, None], sin[:, None], use_neox_rotary_style)

    # write the new k/v at per-row positions as a one-hot select over S
    # (no scatter: a reduce the compiler vectorizes well at S ~ thousands)
    onehot = jax.nn.one_hot(pos, S, dtype=cache_kv.dtype)     # [B, S]
    sel = onehot[:, None, :, None]                            # [B, 1, S, 1]
    new_k = cache_kv[0] * (1 - sel) + k[:, :, None, :].astype(cache_kv.dtype) * sel
    new_v = cache_kv[1] * (1 - sel) + v[:, :, None, :].astype(cache_kv.dtype) * sel

    scale = 1.0 / np.sqrt(hd)
    s = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32),
                   new_k.astype(jnp.float32)) * scale          # [B, H, S]
    valid = jnp.arange(S)[None, :] <= pos[:, None]             # [B, S]
    s = jnp.where(valid[:, None, :], s, -1e30)
    if src_mask is not None:
        sm = src_mask.reshape(B, 1, -1)[..., :S].astype(jnp.float32)
        s = s + sm
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhs,bhsd->bhd", p, new_v.astype(jnp.float32))
    out = out.astype(x.dtype).reshape(B, H * hd)
    return out, jnp.stack([new_k, new_v])


# ---------------------------------------------------------------------------
# flash_attn_unpadded (varlen packed flash)
# ---------------------------------------------------------------------------

def _unpack_cu(cu_seqlens, total):
    """cu_seqlens [B+1] → (seg id, local pos, seg length) per packed
    position [total]. Tail positions beyond cu[-1] share a fresh id so they
    only see each other (and are discarded on unpack)."""
    cu = cu_seqlens.astype(jnp.int32)
    nb = cu.shape[0] - 1
    idx = jnp.arange(total, dtype=jnp.int32)
    seg = jnp.searchsorted(cu, idx, side="right").astype(jnp.int32)  # 1..B
    start = cu[jnp.clip(seg - 1, 0, nb)]
    end = cu[jnp.clip(seg, 0, nb)]
    return seg, idx - start, jnp.maximum(end - start, 0)


def _xla_varlen_sdpa(q, k, v, qcu, kcu, scale, causal):
    """Masked SDPA over packed [total, H, hd] arrays (fallback path).
    Causal uses the flash-attention varlen convention: bottom-RIGHT
    alignment — q local position i sees k local positions
    <= i + (len_k - len_q), which reduces to plain causal when the
    packings match and to full attention for a 1-token q over a longer
    cached k (the decode case)."""
    q_seg, q_loc, q_len = _unpack_cu(qcu, q.shape[0])
    k_seg, k_loc, k_len = _unpack_cu(kcu, k.shape[0])
    s = jnp.einsum("thd,shd->hts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = q_seg[:, None] == k_seg[None, :]
    if causal:
        mask = mask & (k_loc[None, :]
                       <= q_loc[:, None] + (k_len[None, :] - q_len[:, None]))
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # a q row whose whole k side is masked (possible for degenerate cu
    # tables) yields a uniform softmax; zero it instead
    p = jnp.where(mask.any(axis=1)[None, :, None], p, 0.0)
    return jnp.einsum("hts,shd->thd", p, v.astype(jnp.float32)).astype(q.dtype)


@register_op
def flash_attn_unpadded(q, k, v, cu_seqlens_q, cu_seqlens_k,
                        fixed_seed_offset=None, attn_mask=None,
                        max_seqlen_q=None, max_seqlen_k=None, scale=None,
                        dropout=0.0, causal=False, return_softmax=False,
                        is_test=False, rng_name=""):
    """Varlen flash attention over packed sequences.

    q [total_q, H, hd], k/v [total_k, KV, hd], cu_seqlens_* [B+1] int32.
    Routes to the Pallas flash kernel's segment-id mode when the packing is
    self-aligned (total_q == total_k, the training/prefill case) and tiling
    fits; otherwise the masked XLA path. Returns (out, softmax, lse, seed)
    per the phi signature (softmax None unless return_softmax).

    Unsupported arguments are rejected HERE, before any compute or cache
    write, so a bad call fails loudly at entry on every path (the
    attn_mask rejection used to fire only after the fallback SDPA had
    already run).
    """
    if return_softmax:
        raise NotImplementedError("flash_attn_unpadded return_softmax=True: "
                                  "the softmax matrix is never materialized")
    if dropout > 0.0 and not is_test:
        raise NotImplementedError("flash_attn_unpadded dropout: pallas "
                                  "kernel has no in-kernel RNG; apply "
                                  "dropout outside or use is_test=True")
    if attn_mask is not None:
        raise NotImplementedError(
            "flash_attn_unpadded attn_mask: neither the segment-id pallas "
            "path nor the masked XLA fallback takes an additive mask over "
            "packed sequences; use dense flash_attn")
    total_q, H, hd = q.shape
    total_k = k.shape[0]
    if scale is None:
        scale = 1.0 / np.sqrt(hd)
    q_seg, _, _ = _unpack_cu(cu_seqlens_q, total_q)
    k_seg, _, _ = _unpack_cu(cu_seqlens_k, total_k)

    from ..pallas import flash_attention as FA

    # The fused segment path assumes q position t and k position t belong to
    # the same sequence offset — true only when the two packings are
    # IDENTICAL, not merely equal-total. Verify when the cu tensors are
    # concrete; under tracing require them to be the same object.
    same_pack = total_q == total_k
    if same_pack and cu_seqlens_q is not cu_seqlens_k:
        try:
            same_pack = bool(jnp.all(jnp.asarray(cu_seqlens_q)
                                     == jnp.asarray(cu_seqlens_k)))
        except jax.errors.TracerBoolConversionError:
            same_pack = False
    if (same_pack
            and FA.supported((1, total_q, H, hd), (1, total_k, k.shape[1], hd))
            and FA.supports_segments((None, total_k))):
        o = FA.flash_attention(q[None], k[None], v[None], causal=causal,
                               sm_scale=float(scale),
                               q_segment_ids=q_seg[None],
                               kv_segment_ids=k_seg[None])[0]
    else:
        kv_rep = k.shape[1]
        if kv_rep != H:  # GQA on the fallback path
            k = jnp.repeat(k, H // kv_rep, axis=1)
            v = jnp.repeat(v, H // kv_rep, axis=1)
        o = _xla_varlen_sdpa(q, k, v, cu_seqlens_q, cu_seqlens_k,
                             float(scale), causal)
    return o, None, None, jnp.zeros((2,), jnp.int64)


@register_op
def flash_attn_varlen_qkvpacked(qkv, cu_seqlens_q, cu_seqlens_k,
                                fixed_seed_offset=None, attn_mask=None,
                                max_seqlen_q=None, max_seqlen_k=None,
                                scale=None, dropout=0.0, causal=False,
                                return_softmax=False, is_test=False,
                                rng_name=""):
    """qkv [total, 2 + H/KV, KV, hd] paddle packed-GQA layout: first
    (H/KV)·KV rows are q heads, then k, then v."""
    total, g2, KV, hd = qkv.shape
    G = g2 - 2
    q = qkv[:, :G].reshape(total, G * KV, hd)
    k, v = qkv[:, G], qkv[:, G + 1]
    return flash_attn_unpadded.__wrapped__(
        q, k, v, cu_seqlens_q, cu_seqlens_k, fixed_seed_offset, attn_mask,
        max_seqlen_q, max_seqlen_k, scale, dropout, causal, return_softmax,
        is_test, rng_name)


# ---------------------------------------------------------------------------
# variable_length_memory_efficient_attention
# ---------------------------------------------------------------------------

@register_op
def variable_length_memory_efficient_attention(query, key, value, seq_lens,
                                               kv_seq_lens, mask=None,
                                               scale=None, causal=False,
                                               pre_cache_length=0):
    """Batched varlen SDPA. query [B, H, T, hd], key/value [B, KV, S, hd],
    seq_lens/kv_seq_lens [B(,1)] valid lengths. Reference:
    fusion/cutlass/variable_length_memory_efficient_attention.cu.

    Argument validation happens at entry (same loud-rejection contract as
    flash_attn_unpadded): a GQA layout that doesn't divide, or a
    pre_cache_length that would be silently ignored, fails before any
    compute."""
    B, H, T, hd = query.shape
    KV, S = key.shape[1], key.shape[2]
    if KV <= 0 or H % KV != 0:
        raise ValueError(
            f"variable_length_memory_efficient_attention: {H} query heads "
            f"do not divide over {KV} kv heads; GQA needs H % KV == 0")
    pre_cache_length = int(pre_cache_length)
    if pre_cache_length < 0:
        raise ValueError(
            f"pre_cache_length must be >= 0, got {pre_cache_length}")
    if pre_cache_length and not causal:
        raise NotImplementedError(
            "variable_length_memory_efficient_attention pre_cache_length "
            "shifts the causal diagonal; without causal=True it would be "
            "silently ignored — pass causal=True or drop it")
    if KV != H:
        key = jnp.repeat(key, H // KV, axis=1)
        value = jnp.repeat(value, H // KV, axis=1)
    if scale is None:
        scale = 1.0 / np.sqrt(hd)
    s = jnp.einsum("bhtd,bhsd->bhts", query.astype(jnp.float32),
                   key.astype(jnp.float32)) * scale
    ql = seq_lens.reshape(B, 1, 1, 1).astype(jnp.int32)
    kl = kv_seq_lens.reshape(B, 1, 1, 1).astype(jnp.int32)
    rows = jnp.arange(T).reshape(1, 1, T, 1)
    cols = jnp.arange(S).reshape(1, 1, 1, S)
    valid = (rows < ql) & (cols < kl)
    if causal:
        valid = valid & (cols - pre_cache_length <= rows)
    s = jnp.where(valid, s, -1e30)
    if mask is not None:
        s = s + mask.astype(jnp.float32)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows (t >= seq_len) produce uniform p; zero them so pads
    # stay numerically inert downstream
    p = jnp.where(rows < ql, p, 0.0)
    return jnp.einsum("bhts,bhsd->bhtd", p,
                      value.astype(jnp.float32)).astype(query.dtype)


# ---------------------------------------------------------------------------
# block_multihead_attention_ (paged KV cache)
# ---------------------------------------------------------------------------

@register_op
def block_multihead_attention_(qkv, key_cache, value_cache, seq_lens_encoder,
                               seq_lens_decoder, seq_lens_this_time,
                               padding_offsets=None, cum_offsets=None,
                               cu_seqlens_q=None, cu_seqlens_k=None,
                               block_tables=None, pre_key_cache=None,
                               pre_value_cache=None, rope_emb=None, mask=None,
                               tgt_mask=None, cache_k_quant_scales=None,
                               cache_v_quant_scales=None,
                               cache_k_dequant_scales=None,
                               cache_v_dequant_scales=None,
                               qkv_out_scale=None, qkv_bias=None,
                               out_shift=None, out_smooth=None,
                               max_enc_len_this_time=None,
                               max_dec_len_this_time=None, max_seq_len=-1,
                               block_size=64, use_neox_style=False,
                               dynamic_cachekv_quant=False,
                               quant_round_type=1, quant_max_bound=127.0,
                               quant_min_bound=-127.0, out_scale=-1.0,
                               compute_dtype="default", rope_theta=10000.0,
                               use_pallas=None):
    """Paged-KV-cache attention for a mixed prefill/decode batch.

    qkv [token_num, (H + 2·KV)·hd] packed by cu_seqlens_q; key_cache /
    value_cache [num_blocks, KV, block_size, hd]; block_tables
    [B, max_blocks] int32 (−1 = unassigned); per-row pos = seq_lens_decoder
    (past length, 0 for prefill rows) + local offset.

    Returns (fmha_out [token_num, H·hd], qkv_out, key_cache_out,
    value_cache_out). Only the new tokens' rows are written, at
    [page, :, slot, :] of a cache (`write_rows`); pad rows and rows whose
    table entry is −1 write nothing. The serving engine runs the same
    code on its whole stacked pool (`paged_layer_attention`).

    Int8 cache path: pass int8 key/value caches plus all four scale
    tensors — `cache_{k,v}_quant_scales` [KV] per-head quant multipliers
    (`quant_max_bound / absmax`) applied on append, and
    `cache_{k,v}_dequant_scales` [num_blocks, KV] per-page dequant
    multipliers (`absmax / quant_max_bound`) gathered alongside each
    row's pages and applied to scores/probabilities (never to a
    materialized fp cache copy). Scales must be STATIC (calibrated):
    `dynamic_cachekv_quant=True` raises, because per-step scales would
    make page contents depend on prefill chunking and break the
    preemption recompute-on-resume bit-parity guarantee.
    """
    quant_args = {"cache_k_quant_scales": cache_k_quant_scales,
                  "cache_v_quant_scales": cache_v_quant_scales,
                  "cache_k_dequant_scales": cache_k_dequant_scales,
                  "cache_v_dequant_scales": cache_v_dequant_scales}
    kv_quant = any(v is not None for v in quant_args.values())
    if kv_quant:
        missing = [k for k, v in quant_args.items() if v is None]
        if missing:
            raise ValueError(
                f"int8 KV cache needs all four cache scale tensors; "
                f"missing {missing}")
        if key_cache.dtype != jnp.int8 or value_cache.dtype != jnp.int8:
            raise ValueError(
                f"cache quant scales passed but caches are "
                f"{key_cache.dtype}/{value_cache.dtype}; allocate the "
                f"paged caches as int8 (PagedServingEngine does this "
                f"when quant_kv is enabled)")
        if dynamic_cachekv_quant:
            raise NotImplementedError(
                "dynamic_cachekv_quant: per-step cache scales would make "
                "page contents depend on write chunking and break "
                "preemption recompute bit-parity; use static calibrated "
                "scales (inference.quant.calibrate)")
    _require_no_quant(qkv_out_scale=qkv_out_scale, out_shift=out_shift,
                      out_smooth=out_smooth)
    if pre_key_cache is not None or pre_value_cache is not None:
        raise NotImplementedError(
            "block_multihead_attention_: pre_key_cache/pre_value_cache "
            "(system-prompt pre-cache) is not wired. Shared prompt prefixes "
            "are served by the paged prefix cache instead: submit through "
            "paddle_tpu.inference.PagedServingEngine and its BlockManager "
            "deduplicates the shared blocks (copy-on-write); for a dense "
            "cache use fused_multi_transformer_ without pre_caches")
    if mask is not None or tgt_mask is not None:
        raise NotImplementedError(
            "block_multihead_attention_ mask/tgt_mask: only right-padded "
            "causal batches are supported; custom masks not wired yet")
    if block_tables is None or cu_seqlens_q is None:
        missing = [n for n, v in (("block_tables", block_tables),
                                  ("cu_seqlens_q", cu_seqlens_q))
                   if v is None]
        raise ValueError(
            f"block_multihead_attention_ needs {' and '.join(missing)}: "
            "this is the paged-KV kernel and both come from the serving "
            "subsystem (paddle_tpu.inference.PagedServingEngine packs them "
            "from its BlockManager block tables each step). For a dense "
            "per-slot cache without block tables use the dense fallbacks: "
            "masked_multihead_attention_ (one decode step) or "
            "fused_multi_transformer_ (whole stack)")
    _, KV, bs, hd = key_cache.shape
    H = qkv.shape[1] // hd - 2 * KV

    # ---- pallas dispatch (static, resolved at trace time):
    #   None     -> paged_attention.selected(): the kernel on a TPU at a
    #               supported() geometry, the stock path elsewhere
    #   True     -> force the kernel (interpret mode off-TPU; how CPU CI
    #               exercises it bit-for-bit)
    #   "decode" -> force, with the decode-specialized max_q=1 launch; the
    #               CALLER guarantees every seq_lens_this_time <= 1
    #   False    -> stock XLA path
    from ..pallas import paged_attention as PA
    if use_pallas is None:
        use_pallas = PA.selected(H, KV, hd, bs)
    # one layer's caches are a pool of one layer (a leading axis of 1 is a
    # bitcast): the op and the serving engine's tick share one write and
    # one read
    fmha_out, qkv_out, kcs, vcs = paged_layer_attention(
        qkv, key_cache[None], value_cache[None], 0, seq_lens_decoder,
        seq_lens_this_time, cu_seqlens_q, block_tables, rope_emb=rope_emb,
        quant_scales=(cache_k_quant_scales, cache_v_quant_scales,
                      cache_k_dequant_scales, cache_v_dequant_scales)
        if kv_quant else None,
        qkv_bias=qkv_bias, use_neox_style=use_neox_style,
        quant_max_bound=quant_max_bound, quant_min_bound=quant_min_bound,
        use_pallas=use_pallas)
    return fmha_out, qkv_out, kcs[0], vcs[0]


def write_rows(pool, layer, page, slot, rows):
    """Put `rows[t]` ([tok, KV, hd]) at `pool[layer, page[t], :, slot[t], :]`
    of the stacked pool [L, num_blocks, KV, block_size, hd] and touch
    nothing else: one XLA scatter with a (KV, hd) update window — the
    stock path's write. A row whose page lies outside [0, num_blocks)
    writes nothing (the caller sends a row there to drop it); the rows
    that land name distinct slots."""
    tok = rows.shape[0]
    idx = jnp.stack([jnp.full((tok,), layer, jnp.int32),
                     page.astype(jnp.int32), slot.astype(jnp.int32)], axis=1)
    dnums = lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2), inserted_window_dims=(0, 1, 3),
        scatter_dims_to_operand_dims=(0, 1, 3))
    return lax.scatter(pool, idx, rows.astype(pool.dtype), dnums,
                       unique_indices=True,
                       mode=lax.GatherScatterMode.FILL_OR_DROP)


def page_plan(past, this, cu, block_tables, num_blocks, bs, token_num):
    """The pages a batch writes, for `paged_attention.write_pages`: a
    sequence's new positions past[b] … past[b]+this[b]−1 are contiguous, so
    it writes a run of its table's pages, the first and last of them in
    part. Returns (pages [n], lo [n], hi [n], src [n, bs]): entry j writes
    slots [lo, hi) of physical page `pages[j]`, and slot s takes packed
    token row `src[j, s]` (clamped; outside [lo, hi) it is not read). n is
    static, min(tokens, ceil(tokens / bs) + 2·B), which no batch exceeds.
    An entry with nothing to write (past the batch's last page, or on a
    table entry of −1) repeats the last one before it that has (the first
    that has, when none came before; an empty write when there is none)."""
    B, max_blocks = block_tables.shape
    n = min(token_num, -(-token_num // bs) + 2 * B)
    first = past // bs
    count = jnp.where(this > 0, (past + this - 1) // bs - first + 1, 0)
    ends = jnp.cumsum(count)                                    # [B]
    j = jnp.arange(n, dtype=jnp.int32)
    b = jnp.clip(jnp.searchsorted(ends, j, side="right"), 0, B - 1)
    logical = first[b] + j - (ends[b] - count[b])
    page = block_tables[b, jnp.clip(logical, 0, max_blocks - 1)]
    live = ((j < ends[-1]) & (logical < max_blocks)
            & (page >= 0) & (page < num_blocks))
    start = logical * bs - past[b]          # page's slot 0, in the chunk
    lo = jnp.where(live, jnp.clip(-start, 0, bs), 0)
    hi = jnp.where(live, jnp.clip(this[b] - start, 0, bs), 0)
    src = jnp.clip((cu[b] + start)[:, None]
                   + jnp.arange(bs, dtype=jnp.int32)[None, :],
                   0, token_num - 1)
    last = lax.cummax(jnp.where(live, j, -1))
    rep = jnp.where(last >= 0, last, jnp.argmax(live)).astype(jnp.int32)
    return (jnp.clip(page, 0, num_blocks - 1)[rep], lo[rep], hi[rep],
            src[rep])


def paged_layer_attention(qkv, key_pool, value_pool, layer, seq_lens_decoder,
                          seq_lens_this_time, cu_seqlens_q, block_tables,
                          rope_emb=None, quant_scales=None, qkv_bias=None,
                          use_neox_style=False, quant_max_bound=127.0,
                          quant_min_bound=-127.0, use_pallas=False,
                          block_length=0, window=0, rotary_dim=0,
                          kind=None):
    """One layer of `block_multihead_attention_` on the stacked page pool
    [L, num_blocks, KV, block_size, hd]: split and rotate `qkv`, write the
    new tokens' rows into `layer`'s pages where they lie, then attend over
    them. The pools come back updated in place (a donated or loop-carried
    pool is aliased through the write: the page-write kernel beside the
    Pallas read, an XLA row scatter on the stock path); no other layer
    and no page the batch does not own is touched. `quant_scales` is None or
    (k_quant [KV], v_quant [KV], k_dequant [num_blocks, KV], v_dequant)
    of this layer; `use_pallas` is False, True or "decode", already
    resolved. `block_length` Bd > 0 (static) takes the block-causal mask
    of generation by diffusion over blocks in place of the causal one: the
    row at absolute position p sees key j iff j < (p // Bd + 1) * Bd and
    j < past + this, on either read path (not the decode launch: one row
    a sequence is no block). `window` W > 0 (static) keeps of those keys
    the last W, the row's own among them (i - W < j <= i), on either read
    path and in the decode launch; table entries behind every window may
    be -1. `rotary_dim` > 0: `rope_emb` is [2, 1, S, rotary_dim] and only
    the leading `rotary_dim` values of each head are rotated (rotate-half
    inside them). `kind` names the layer's kind in a layer plan: the read
    then runs under scope `paged_attention_<kind>` inside
    `paged_attention`. Returns (fmha_out, qkv_out, key_pool,
    value_pool)."""
    from ..pallas import paged_attention as PA
    _, num_blocks, KV, bs, hd = key_pool.shape
    B, max_blocks = block_tables.shape
    token_num = qkv.shape[0]
    H = qkv.shape[1] // hd - 2 * KV
    max_kv = max_blocks * bs
    kv_quant = quant_scales is not None
    if use_pallas and not PA.supported(H, KV, hd, bs):
        raise ValueError(
            f"use_pallas={use_pallas!r} forced but geometry H={H} KV={KV} "
            f"hd={hd} block_size={bs} is not supported() by the pallas "
            f"paged-attention kernel")
    if block_length > 1 and use_pallas == "decode":
        raise ValueError(
            f"block_length={block_length}: the rows of a block go through "
            "the mixed launch (use_pallas=True), not the decode launch")

    # named scopes (jax.named_scope): the device operations of this op
    # belong to `qkv` (split, bias, rope, token indices), `cache_write`
    # (the row write only) or `paged_attention` (the read: the Pallas
    # launch or the stock gather path)
    with jax.named_scope("qkv"):
        qkv3 = qkv.reshape(token_num, H + 2 * KV, hd)
        if qkv_bias is not None:
            qkv3 = qkv3 + qkv_bias.reshape(1, H + 2 * KV, hd).astype(qkv3.dtype)
        q_tok, k_tok, v_tok = (qkv3[:, :H], qkv3[:, H:H + KV],
                               qkv3[:, H + KV:])          # [tok, H/KV, hd]

        cu = cu_seqlens_q.astype(jnp.int32).reshape(-1)
        tok_idx = jnp.arange(token_num, dtype=jnp.int32)
        tok_b = jnp.clip(jnp.searchsorted(cu, tok_idx, side="right") - 1, 0, B - 1)
        tok_local = tok_idx - cu[tok_b]
        past = seq_lens_decoder.reshape(-1).astype(jnp.int32)    # [B]
        this = seq_lens_this_time.reshape(-1).astype(jnp.int32)  # [B]
        tok_pos = past[tok_b] + tok_local                        # absolute pos
        tok_valid = tok_local < this[tok_b]

        if rope_emb is not None:
            rot = rotary_dim or hd
            cos_t, sin_t = _rotary_table(rope_emb, rot)          # [Br, S, rot//2]
            tb = jnp.zeros_like(tok_b) if cos_t.shape[0] == 1 else tok_b
            cos = cos_t[tb, tok_pos]                             # [tok, rot//2]
            sin = sin_t[tb, tok_pos]

            def rotated(x):
                if rot == hd:
                    return _rope_pairwise(x, cos[:, None], sin[:, None],
                                          use_neox_style)
                return jnp.concatenate(
                    [_rope_pairwise(x[..., :rot], cos[:, None], sin[:, None],
                                    use_neox_style), x[..., rot:]], axis=-1)
            q_tok, k_tok = rotated(q_tok), rotated(k_tok)

        # ---- quantize-on-append: per-head static multipliers, round+clip to
        # the int8 page dtype. Quantization is per-token VALUE-based (no
        # dependence on which chunk wrote the token), so a preemption resume
        # that re-prefills with different chunk boundaries reproduces the
        # int8 pages bit-for-bit.
        if kv_quant:
            k_quant, v_quant, k_dequant, v_dequant = quant_scales
            kqs = k_quant.astype(jnp.float32).reshape(1, KV, 1)
            vqs = v_quant.astype(jnp.float32).reshape(1, KV, 1)
            k_store = jnp.clip(jnp.round(k_tok.astype(jnp.float32) * kqs),
                               quant_min_bound, quant_max_bound).astype(jnp.int8)
            v_store = jnp.clip(jnp.round(v_tok.astype(jnp.float32) * vqs),
                               quant_min_bound, quant_max_bound).astype(jnp.int8)
        else:
            k_store, v_store = k_tok, v_tok
            k_dequant = v_dequant = None

    with jax.named_scope("cache_write"):
        # ---- paged cache write: token t -> page block_tables[b, pos//bs],
        # slot pos%bs; pad rows and rows whose table entry is unassigned
        # (−1) write nothing. Only the new rows move, in either form.
        if use_pallas:
            # a page at a time through the kernel, which keeps the pool in
            # the layout the read wants (an XLA scatter beside the Pallas
            # read makes the compiler hold the pool slot-major and re-lay
            # it out whole for every launch)
            pages, lo, hi, src = page_plan(past, this, cu, block_tables,
                                           num_blocks, bs, token_num)

            def staged(rows):                                    # [n, KV, bs, hd]
                return rows[src].transpose(0, 2, 1, 3).astype(key_pool.dtype)
            key_pool, value_pool = PA.write_pages(
                key_pool, value_pool, layer, pages, lo, hi,
                staged(k_store), staged(v_store))
        else:
            # dropped rows go to pages past the pool, one each
            tok_page = jnp.take_along_axis(
                block_tables[tok_b], (tok_pos // bs)[:, None], axis=1)[:, 0]
            tok_page = jnp.where(tok_valid & (tok_page >= 0), tok_page,
                                 num_blocks + tok_idx)
            tok_slot = tok_pos % bs
            key_pool = write_rows(key_pool, layer, tok_page, tok_slot, k_store)
            value_pool = write_rows(value_pool, layer, tok_page, tok_slot,
                                    v_store)

    with jax.named_scope("paged_attention"), (
            jax.named_scope(f"paged_attention_{kind}") if kind
            else contextlib.nullcontext()):
        G = H // KV
        q_g = q_tok.reshape(token_num, KV, G, hd)                # head h = kv*G+g
        if use_pallas:
            # ---- pallas read: the kernel walks the block table — no dense
            # gather ever exists, and no slice of the layer either: the
            # freshly written pool goes in whole, with the layer index; int8
            # pages ride with their scale planes.
            sm_scale = float(1.0 / np.sqrt(hd))
            if use_pallas == "decode":
                # one token a sequence: rows [B, KV, G, hd], row b = token
                # cu[b] (an idle slot's is masked by its length)
                row_tok = jnp.clip(cu[:B], 0, token_num - 1)
                o = PA.paged_attention(
                    q_g[row_tok], key_pool, value_pool, block_tables, past,
                    this, G, sm_scale, k_dequant=k_dequant,
                    v_dequant=v_dequant, layer=layer, window=window)[tok_b]
                o = jnp.where(tok_valid[:, None, None, None], o, 0)
            else:
                # ragged chunks: the packed stream goes in as it is
                o = PA.paged_attention_packed(
                    q_g, key_pool, value_pool, block_tables, past, this, cu,
                    sm_scale, k_dequant=k_dequant, v_dequant=v_dequant,
                    layer=layer, block_len=block_length, window=window)
            fmha_out = o.astype(qkv.dtype).reshape(token_num, H * hd)
            return fmha_out, qkv3.reshape(token_num, -1), key_pool, value_pool

        # ---- stock read (CPU tests; it runs in no benchmark cell): slice the
        # layer out and gather each row's pages into a dense [B, max_kv] view
        def _dense_rows(pool):
            pages = lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
            rows = pages[block_tables]                           # [B, mb, KV, bs, hd]
            return rows.transpose(0, 1, 3, 2, 4).reshape(B, max_kv, KV, hd)
        rows_k, rows_v = _dense_rows(key_pool), _dense_rows(value_pool)
        page_valid = (block_tables >= 0)[:, :, None]             # [B, mb, 1]
        page_valid = jnp.broadcast_to(page_valid, (B, max_blocks, bs)
                                      ).reshape(B, max_kv)

        # grouped-head attention WITHOUT materializing the GQA-expanded cache
        # (q head h reads kv head h // G — the same mapping the Pallas kernel
        # uses via index maps); rows stay [tok, max_kv, KV, hd]
        k_tok_rows = rows_k[tok_b]                               # [tok, max_kv, KV, hd]
        v_tok_rows = rows_v[tok_b]
        s = jnp.einsum("tkgd,tskd->tkgs", q_g.astype(jnp.float32),
                       k_tok_rows.astype(jnp.float32)) / np.sqrt(hd)
        if kv_quant:
            # per-page dequant: gather each row's page scales like the pages
            # themselves, expand to slots, apply on the SCORES — the scale is
            # constant over hd so it factors out of the q·k dot, and the int8
            # rows are consumed directly by the einsum (convert fused into
            # the dot read; no dequantized cache copy exists)
            def _page_scales(dq):                                # [nb, KV]
                rows = dq.astype(jnp.float32)[block_tables]      # [B, mb, KV]
                rows = jnp.broadcast_to(rows[:, :, None, :],
                                        (B, max_blocks, bs, KV))
                return rows.reshape(B, max_kv, KV)[tok_b]        # [tok, max_kv, KV]
            kdq = jnp.swapaxes(_page_scales(k_dequant), 1, 2)
            vdq = jnp.swapaxes(_page_scales(v_dequant), 1, 2)
            s = s * kdq[:, :, None, :]                           # [tok, KV, 1, mkv]
        kv_pos = jnp.arange(max_kv)[None, :]
        if block_length:
            # block-causal: to the end of the row's own block, and no key
            # behind the sequence's last written position
            see = jnp.minimum((tok_pos // block_length + 1) * block_length,
                              (past + this)[tok_b]) - 1
        else:
            see = tok_pos
        ok = (kv_pos <= see[:, None]) & page_valid[tok_b]        # [tok, max_kv]
        if window:
            ok &= kv_pos > (tok_pos - window)[:, None]
        s = jnp.where(ok[:, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        if kv_quant:
            # value dequant likewise factors out: fold into the probabilities
            p = p * vdq[:, :, None, :]
        o = jnp.einsum("tkgs,tskd->tkgd", p, v_tok_rows.astype(jnp.float32))
        o = jnp.where(tok_valid[:, None, None, None], o, 0.0)
        fmha_out = o.astype(qkv.dtype).reshape(token_num, H * hd)
        return fmha_out, qkv3.reshape(token_num, -1), key_pool, value_pool


def paged_latent_attention(q_nope, q_rope, row_tok, wk, wv, pool, layer,
                           seq_lens_decoder, seq_lens_this_time, cu_seqlens_q,
                           block_tables, sm_scale, use_pallas=False):
    """One layer of multi-head latent attention on the stacked LATENT page
    pool [L, num_blocks, 1, block_size, W]: write the new tokens' cache rows
    `row_tok` [tok, w] (latent | rope key, `models.llama.latent_kv`; w <= W,
    the pool's rows are whole lanes and the rest of a row is zeros) into
    `layer`'s pages where they lie, then attend, causal, scores times
    `sm_scale`. There is no value pool. q_nope [tok, H, nope] and q_rope
    [tok, H, rope] are a token's queries (`models.llama.latent_q`), wk
    [C, H, nope] and wv [C, H, v] the halves of Wkvb
    (`models.llama.latent_wkvb`).

    WHICH FORM A LAUNCH TAKES (the one rule, here and nowhere else): every
    row attends in the ABSORBED form, (q_nope wk_h^T | q_rope) against the
    cache rows themselves, its head's output the probabilities' sum over
    the rows' latents, then through wv_h: 2 x (W + C) FLOPs a (row, key,
    head). `use_pallas` "decode" is the decode launch (one token a
    sequence); True is a tick with a chunk, whose one-row sequences go
    through that same decode launch and whose chunks go through the mixed
    walk (`paged_attention_latent`): a work item of the walk moves 4.6 MB
    of rows for one live token, and 63 of them cost a layer 2.6 ms where
    the decode launch takes under 1 (PERF.md section 6, PR 41). The
    EXPANDED form for a chunk (the sequence's cache rows rebuilt through
    Wkvb to a head's keys and values, 2 x (nope + rope + v) FLOPs a (row,
    key, head): 3.6 x fewer) was built and measured in that PR and is not
    taken: its kernel ran the chunk at 37 TFLOP/s where the walk runs it
    at 131, and the rebuild cost 0.9 ms a layer beside it. False is the
    stock dense gather (CPU tests). Scopes: `latent_q` (the absorption),
    `cache_write`, `paged_attention` > `paged_attention_latent` (the
    launches), `latent_out` (wv). Returns (o [tok, H * v], pool)."""
    from ..pallas import paged_attention_latent as PL
    _, num_blocks, _, bs, W = pool.shape
    B, max_blocks = block_tables.shape
    token_num, H, nope = q_nope.shape
    C, w = wk.shape[0], row_tok.shape[-1]
    max_kv = max_blocks * bs
    cu = cu_seqlens_q.astype(jnp.int32).reshape(-1)
    tok_idx = jnp.arange(token_num, dtype=jnp.int32)
    tok_b = jnp.clip(jnp.searchsorted(cu, tok_idx, side="right") - 1, 0, B - 1)
    tok_local = tok_idx - cu[tok_b]
    past = seq_lens_decoder.reshape(-1).astype(jnp.int32)
    this = seq_lens_this_time.reshape(-1).astype(jnp.int32)
    tok_pos = past[tok_b] + tok_local
    tok_valid = tok_local < this[tok_b]
    with jax.named_scope("latent_q"):
        q_tok = jnp.concatenate(
            [jnp.einsum("thn,chn->thc", q_nope, wk.astype(q_nope.dtype)),
             q_rope, jnp.zeros((token_num, H, W - w), q_nope.dtype)], axis=-1)
    rows = jnp.pad(row_tok, ((0, 0), (0, W - w)))[:, None]     # [tok, 1, W]

    with jax.named_scope("cache_write"):
        if use_pallas:
            pages, lo, hi, src = page_plan(past, this, cu, block_tables,
                                           num_blocks, bs, token_num)
            pool = PL.write_latent_pages(
                pool, layer, pages, lo, hi,
                rows[src].transpose(0, 2, 1, 3))               # [n, 1, bs, W]
        else:
            tok_page = jnp.take_along_axis(
                block_tables[tok_b], (tok_pos // bs)[:, None], axis=1)[:, 0]
            tok_page = jnp.where(tok_valid & (tok_page >= 0), tok_page,
                                 num_blocks + tok_idx)
            pool = write_rows(pool, layer, tok_page, tok_pos % bs, rows)

    def way_out(o_latent):                          # [tok, H, C] -> [tok, H*v]
        with jax.named_scope("latent_out"):
            return jnp.einsum("thc,chv->thv", o_latent,
                              wv.astype(o_latent.dtype)
                              ).reshape(token_num, -1)

    with jax.named_scope("paged_attention"):
        if use_pallas == "decode":
            with jax.named_scope("paged_attention_latent"):
                first = jnp.clip(cu[:B], 0, token_num - 1)
                o = PL.latent_attention(q_tok[first], pool, block_tables,
                                        past, this, sm_scale, layer, C)[tok_b]
                o = jnp.where(tok_valid[:, None, None], o, 0)
            return way_out(o), pool
        if use_pallas:
            with jax.named_scope("paged_attention_latent"):
                single = this == 1
                first = jnp.clip(cu[:B], 0, token_num - 1)
                rows_1 = PL.latent_attention(
                    q_tok[first], pool, block_tables, past,
                    single.astype(jnp.int32), sm_scale, layer, C)[tok_b]
                chunks = PL.latent_attention_packed(
                    q_tok, pool, block_tables, past,
                    jnp.where(single, 0, this), cu, sm_scale, layer, C)
                o = jnp.where((single[tok_b] & tok_valid)[:, None, None],
                              rows_1, chunks)
            return way_out(o), pool
        # ---- stock read (CPU tests): a dense gather of every row's pages
        with jax.named_scope("paged_attention_latent"):
            pages = lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
            keys = pages[block_tables][:, :, 0].reshape(B, max_kv, W)
            keys = keys[tok_b].astype(jnp.float32)             # [tok, S, W]
            s = jnp.einsum("thw,tsw->ths", q_tok.astype(jnp.float32),
                           keys) * sm_scale
            live = jnp.broadcast_to((block_tables >= 0)[:, :, None],
                                    (B, max_blocks, bs)).reshape(B, max_kv)
            ok = ((jnp.arange(max_kv)[None, :] <= tok_pos[:, None])
                  & live[tok_b])
            p = jax.nn.softmax(jnp.where(ok[:, None, :], s, -1e30), axis=-1)
            o = jnp.einsum("ths,tsc->thc", p, keys[..., :C])
            o = jnp.where(tok_valid[:, None, None], o, 0.0
                          ).astype(q_tok.dtype)
        return way_out(o), pool


# ---------------------------------------------------------------------------
# fused_multi_transformer_ (whole serving stack)
# ---------------------------------------------------------------------------

@register_op
def fused_multi_transformer_(x, ln_scales, ln_biases, qkv_weights, qkv_biases,
                             linear_weights, linear_biases, ffn_ln_scales,
                             ffn_ln_biases, ffn1_weights, ffn1_biases,
                             ffn2_weights, ffn2_biases, pre_layer_norm=True,
                             epsilon=1e-5, residual_alpha=1.0, cache_kvs=None,
                             beam_offset=None, pre_caches=None, seq_lens=None,
                             rotary_embs=None, time_step=None, attn_mask=None,
                             dropout_rate=0.0, rotary_emb_dims=0,
                             activation="gelu", training=False, mode="upscale_in_train",
                             trans_qkvw=True, ring_id=-1, norm_type="layernorm",
                             use_neox_rotary_style=False, gqa_group_size=-1):
    """Serving transformer stack: per layer [pre-LN → qkv → cached attention
    → out-proj → residual → FFN]. Two stages like the reference kernel:
    time_step None = context/prefill (writes cache positions 0..T-1);
    time_step set = one-token decode via masked_multihead_attention_.

    x [B, T, D]; qkv_weights[i] [3·H·hd, D] when trans_qkvw (paddle layout);
    cache_kvs[i] [2, B, H, max_seq, hd]. Returns (out, cache_kvs).
    """
    if training or dropout_rate:
        raise NotImplementedError("fused_multi_transformer_ is the serving "
                                  "path; train with the regular layers")
    if beam_offset is not None or pre_caches is not None:
        raise NotImplementedError("beam/pre-cache serving not wired")
    if gqa_group_size and gqa_group_size > 0:
        raise NotImplementedError(
            "fused_multi_transformer_ gqa_group_size: the packed GQA weight "
            "layout is not wired; use the LLMPredictor path for GQA decode")
    B, T, D = x.shape
    L = len(qkv_weights)
    act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
           "swiglu": None}[activation] if activation != "swiglu" else None

    def norm(y, scale, bias):
        y32 = y.astype(jnp.float32)
        if norm_type == "rmsnorm":
            out = y32 * lax.rsqrt(jnp.mean(y32 * y32, -1, keepdims=True)
                                  + epsilon)
        else:
            mu = jnp.mean(y32, -1, keepdims=True)
            var = jnp.var(y32, -1, keepdims=True)
            out = (y32 - mu) * lax.rsqrt(var + epsilon)
        if scale is not None:
            out = out * scale.astype(jnp.float32)
        if bias is not None:
            out = out + bias.astype(jnp.float32)
        return out.astype(y.dtype)

    decode = time_step is not None
    new_caches = []
    h = x
    for i in range(L):
        w = qkv_weights[i]
        cache = cache_kvs[i] if cache_kvs is not None else None
        if w.ndim == 4:                      # paddle layout [3, H, hd, D]
            _, H, hd, _ = w.shape
            qkvw = w.reshape(3 * H * hd, D)
        else:
            qkvw = w if trans_qkvw else w.T  # [3·H·hd, D]
            if cache is None:
                raise ValueError("2-D qkv_weights need cache_kvs to carry "
                                 "the head layout; pass [3, H, hd, D] weights")
            H = cache.shape[2]
            hd = qkvw.shape[0] // 3 // H
        resid = h
        y = norm(h, ln_scales[i], ln_biases[i]) if pre_layer_norm else h
        qkv = y @ qkvw.T.astype(y.dtype)     # [B, T, 3·H·hd]
        if decode:
            if cache is None:
                raise ValueError("decode stage needs cache_kvs")
            step_pos = jnp.full((B,), jnp.asarray(time_step).reshape(()),
                                jnp.int32)
            o, cache = masked_multihead_attention_.__wrapped__(
                qkv.reshape(B, -1), cache, qkv_biases[i] if qkv_biases else None,
                attn_mask, None, step_pos, rotary_embs, None,
                seq_len=1, rotary_emb_dims=rotary_emb_dims,
                use_neox_rotary_style=use_neox_rotary_style)
            attn_out = o.reshape(B, 1, H * hd)
        else:
            qkv5 = qkv.reshape(B, T, 3, H, hd)
            if qkv_biases:
                qkv5 = qkv5 + qkv_biases[i].reshape(1, 1, 3, H, hd).astype(qkv5.dtype)
            q, k, v = qkv5[:, :, 0], qkv5[:, :, 1], qkv5[:, :, 2]
            if rotary_emb_dims and rotary_embs is not None:
                # prefill: per-batch tables sliced over positions 0..T-1
                # ([Br, S, hd//2] -> [Br, T, 1, hd//2], broadcast over heads)
                cos_t, sin_t = _rotary_table(rotary_embs, hd)
                cos = cos_t[:, :T, None]
                sin = sin_t[:, :T, None]
                q = _rope_pairwise(q, cos, sin, use_neox_rotary_style)
                k = _rope_pairwise(k, cos, sin, use_neox_rotary_style)
            s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                           k.astype(jnp.float32)) / np.sqrt(hd)
            causal = jnp.tril(jnp.ones((T, T), bool))
            s = jnp.where(causal[None, None], s, -1e30)
            if seq_lens is not None:
                sl = seq_lens.reshape(B, 1, 1, 1).astype(jnp.int32)
                s = jnp.where(jnp.arange(T).reshape(1, 1, 1, T) < sl, s, -1e30)
            if attn_mask is not None:
                s = s + attn_mask.astype(jnp.float32)
            p = jax.nn.softmax(s, -1)
            attn_out = jnp.einsum("bhts,bshd->bthd", p,
                                  v.astype(jnp.float32)).astype(h.dtype)
            attn_out = attn_out.reshape(B, T, H * hd)
            if cache is not None:
                S = cache.shape[3]
                pad = S - T
                kp = jnp.pad(k.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, pad), (0, 0)))
                vp = jnp.pad(v.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, pad), (0, 0)))
                cache = jnp.stack([kp, vp]).astype(cache.dtype)
        new_caches.append(cache)
        attn_out = attn_out @ linear_weights[i].astype(attn_out.dtype)
        if linear_biases and linear_biases[i] is not None:
            attn_out = attn_out + linear_biases[i].astype(attn_out.dtype)
        h = resid * residual_alpha + attn_out
        if not pre_layer_norm:          # post-LN: norm AFTER the attn residual
            h = norm(h, ln_scales[i], ln_biases[i])
        resid = h
        y = norm(h, ffn_ln_scales[i], ffn_ln_biases[i]) if pre_layer_norm else h
        f = y @ ffn1_weights[i].astype(y.dtype)
        if ffn1_biases and ffn1_biases[i] is not None:
            f = f + ffn1_biases[i].astype(f.dtype)
        if activation == "swiglu":
            g, u = jnp.split(f, 2, axis=-1)
            f = jax.nn.silu(g) * u
        else:
            f = act(f)
        f = f @ ffn2_weights[i].astype(f.dtype)
        if ffn2_biases and ffn2_biases[i] is not None:
            f = f + ffn2_biases[i].astype(f.dtype)
        h = resid * residual_alpha + f
        if not pre_layer_norm:          # post-LN: ffn_ln after the FFN residual
            h = norm(h, ffn_ln_scales[i], ffn_ln_biases[i])
    return h, new_caches
