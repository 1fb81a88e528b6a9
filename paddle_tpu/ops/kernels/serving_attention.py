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
                          kind=None, select=None, softmax_scale=0.0,
                          head_dim=0):
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
    `paged_attention`. `softmax_scale`: the factor on the scores (0:
    hd ** -0.5), on either read path. `head_dim` > 0 and narrower than the
    pool's rows: the heads of `qkv` are that wide and lie in the leading
    lanes of a pool row (a head of 64 in 128 lanes, zeros behind it, so
    that a page is whole lanes: the engine's pool beside the kernel); q, k
    and v are padded here and the output is cut back, the scores (at
    head_dim ** -0.5 unless told) and the values are what they were.

    WHICH READ A ROW TAKES UNDER A SPARSE INDEX (`select` = (positions
    [tok, k], their pages [tok, k], sparse [B], the selection's bits [tok,
    max_kv / 128, 4]) from `paged_index_select`; the one rule, here and
    nowhere else, as `paged_latent_attention` states its own). The rows of
    a sequence that holds at most k keys after this tick have no selection
    to make and take the DENSE walks as without an index (scope
    `paged_attention`). The rows of a sequence that holds more attend over
    their k selected keys alone (a row that sees at most k has them all
    selected), softmax over exactly those, under scope
    `paged_attention_sparse`, each form launched under its own `lax.cond`
    on the tick's own lengths. A selecting CHUNK's rows (more than one row
    of a sequence in a tick) always take the MASKED WALK:
    `paged_attention`'s mixed walk with the selection's bits ANDed into
    what a row sees: every page of the context read once for a tile of
    rows, nothing moved twice, at context / k times the selected pairs'
    FLOPs. A selecting sequence's ONE row of a tick (a decode row, in a
    decode tick or beside a chunk) reads in the decode launch, over the
    sequences' first rows, in one of two forms:
    * the masked decode walk, bound by the bytes of the pages it copies:
      a key block whose pages lie side by side in the pool
      (`paged_attention.block_runs`: its table entries are p, p + 1, ...,
      as a prompt's fresh pages are) comes in ONE copy a pool, any other
      block page by page;
    * the GATHER: each selected position's `KV x hd` keys and values
      fetched out of both pools by page and slot, and a softmax over
      exactly them.
    Which of the two is a formula of shapes and measured chip constants,
    nobody's setting (`sparse_walk_keys_heads`, where the readings stand):
    a one-row sequence walks while it holds at most that many keys. The stock
    read (`use_pallas` False, CPU tests) is the dense float32 read with the
    selection ANDed into its mask, so that a selection of every key IS the
    dense read. Int8 pages, a window and a block-causal mask were never
    judged under a selection and raise. Returns (fmha_out, qkv_out,
    key_pool, value_pool)."""
    from ..pallas import paged_attention as PA
    if head_dim and head_dim < key_pool.shape[-1]:
        tok, wide = qkv.shape[0], key_pool.shape[-1]
        padded = jnp.pad(qkv.reshape(tok, -1, head_dim),
                         ((0, 0), (0, 0), (0, wide - head_dim)))
        if qkv_bias is not None:
            qkv_bias = jnp.pad(qkv_bias.reshape(-1, head_dim),
                               ((0, 0), (0, wide - head_dim))).reshape(-1)
        out, qkv_out, key_pool, value_pool = paged_layer_attention(
            padded.reshape(tok, -1), key_pool, value_pool, layer,
            seq_lens_decoder, seq_lens_this_time, cu_seqlens_q, block_tables,
            rope_emb, quant_scales, qkv_bias, use_neox_style,
            quant_max_bound, quant_min_bound, use_pallas, block_length,
            window, (rotary_dim or head_dim) if rope_emb is not None else 0,
            kind, select, softmax_scale or float(head_dim) ** -0.5)
        return (out.reshape(tok, -1, wide)[..., :head_dim].reshape(tok, -1),
                qkv_out, key_pool, value_pool)
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
    if select is not None and (kv_quant or window or block_length):
        raise NotImplementedError(
            "a sparse index's selection over int8 pages, under a window or "
            "a block-causal mask was never judged against a reference")

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
            sm_scale = float(softmax_scale or 1.0 / np.sqrt(hd))

            def walk_rows(this_w, mask=None):
                """The decode walk over the sequences whose `this_w` is not
                0: one token a sequence, rows [B, KV, G, hd], row b = token
                cu[b] (an idle slot's is masked by its length); under the
                selection `mask` where there is one."""
                row_tok = jnp.clip(cu[:B], 0, token_num - 1)
                o = PA.paged_attention(
                    q_g[row_tok], key_pool, value_pool, block_tables, past,
                    this_w, G, sm_scale, k_dequant=k_dequant,
                    v_dequant=v_dequant, layer=layer, window=window,
                    mask=None if mask is None else mask[row_tok])[tok_b]
                return jnp.where(tok_valid[:, None, None, None], o, 0)

            def walk_packed(this_w, mask=None):
                """The mixed walk: ragged chunks, the packed stream goes in
                as it is."""
                return PA.paged_attention_packed(
                    q_g, key_pool, value_pool, block_tables, past, this_w,
                    cu, sm_scale, k_dequant=k_dequant, v_dequant=v_dequant,
                    layer=layer, block_len=block_length, window=window,
                    mask=mask)

            walk = walk_rows if use_pallas == "decode" else walk_packed
            if select is None:
                o = walk(this)
            else:
                idx, page, sparse, bits = select
                o = lax.cond(
                    jnp.any((this > 0) & ~sparse),
                    lambda: walk(jnp.where(sparse, 0, this)),
                    lambda: jnp.zeros_like(q_g))

                def read(o, seqs, launch):
                    """`o` with the rows of the sequences `seqs` [B] taken
                    from `launch()`, which runs only in a tick with one."""
                    mine = (seqs[tok_b] & tok_valid)[:, None, None, None]
                    return lax.cond(jnp.any(seqs),
                                    lambda: jnp.where(mine, launch(), o),
                                    lambda: o)

                def gathered_rows(q, idx, page):
                    """Rows q [n, KV, G, hd] over the keys and values at
                    positions idx [n, k] (-1: none) of pages `page`."""
                    # a head's row of a position, hd values, by its place
                    # among the pool's rows (a gather of [KV, 1, hd] slabs
                    # makes XLA lay the whole pool out slot-major first)
                    at = ((((layer * num_blocks + jnp.maximum(page, 0)) * KV
                            )[..., None] + jnp.arange(KV, dtype=jnp.int32))
                          * bs + (jnp.maximum(idx, 0) % bs)[..., None])
                    k_sel, v_sel = (
                        jnp.take(pool.reshape(-1, hd), at, axis=0,
                                 mode="clip")
                        for pool in (key_pool, value_pool))   # [n, k, KV, hd]
                    s = jnp.einsum("nvgd,nkvd->nvgk", q, k_sel,
                                   preferred_element_type=jnp.float32
                                   ) * sm_scale
                    s = jnp.where(((idx >= 0) & (page >= 0))[:, None, None],
                                  s, -1e30)
                    p = jax.nn.softmax(s, axis=-1).astype(v_sel.dtype)
                    return jnp.einsum("nvgk,nkvd->nvgd", p, v_sel,
                                      preferred_element_type=jnp.float32
                                      ).astype(q.dtype)

                # a selecting chunk's rows walk under the mask in the mixed
                # launch; a selecting sequence's ONE row of a tick (a decode
                # row, in a decode tick or beside a chunk) reads in the
                # decode launch, over the sequences' first rows: the masked
                # walk up to the crossing, the gather beyond it
                one = this == 1
                first = jnp.clip(cu[:B], 0, token_num - 1)
                walks = sparse & one & (
                    past + this <= sparse_walk_keys_heads(
                        KV, hd, key_pool.dtype.itemsize, idx.shape[1]))
                with jax.named_scope("paged_attention_sparse"):
                    o = read(o, walks, lambda: walk_rows(
                        walks.astype(this.dtype), bits))
                    o = read(o, sparse & one & ~walks, lambda: gathered_rows(
                        q_g[first], idx[first], page[first])[tok_b])
                    if use_pallas != "decode":
                        o = read(o, sparse & ~one, lambda: walk_packed(
                            jnp.where(sparse & ~one, this, 0), bits))
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
                       k_tok_rows.astype(jnp.float32))
        s = s * softmax_scale if softmax_scale else s / np.sqrt(hd)
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
        if select is not None:
            # the rows of a selecting sequence: their selected keys alone
            from . import sparse_index
            with jax.named_scope("paged_attention_sparse"):
                chosen = sparse_index.unpack_mask(select[3])[:, :max_kv] != 0
                ok &= chosen | ~select[2][tok_b][:, None]
        s = jnp.where(ok[:, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        if kv_quant:
            # value dequant likewise factors out: fold into the probabilities
            p = p * vdq[:, :, None, :]
        o = jnp.einsum("tkgs,tskd->tkgd", p, v_tok_rows.astype(jnp.float32))
        o = jnp.where(tok_valid[:, None, None, None], o, 0.0)
        fmha_out = o.astype(qkv.dtype).reshape(token_num, H * hd)
        return fmha_out, qkv3.reshape(token_num, -1), key_pool, value_pool


def _packed_rows(past, this, cu, token_num: int, B: int):
    """A packed stream's rows: (their sequence [tok], their absolute
    position [tok], whether they exist [tok])."""
    tok_idx = jnp.arange(token_num, dtype=jnp.int32)
    tok_b = jnp.clip(jnp.searchsorted(cu, tok_idx, side="right") - 1, 0, B - 1)
    tok_local = tok_idx - cu[tok_b]
    return tok_b, past[tok_b] + tok_local, tok_local < this[tok_b]


def _write_latent_rows(pool, layer, rows, past, this, cu, block_tables,
                       tok_b, tok_pos, tok_valid, use_pallas):
    """The new tokens' rows [tok, 1, W] into `layer`'s pages of a one-side
    pool [L, nb, 1, bs, W] where they lie: a page at a time through the
    kernel beside the Pallas read, an XLA row scatter on the stock path."""
    from ..pallas import paged_attention_latent as PL
    _, num_blocks, _, bs, _ = pool.shape
    token_num = rows.shape[0]
    if use_pallas:
        pages, lo, hi, src = page_plan(past, this, cu, block_tables,
                                       num_blocks, bs, token_num)
        return PL.write_latent_pages(
            pool, layer, pages, lo, hi,
            rows[src].transpose(0, 2, 1, 3))                   # [n, 1, bs, W]
    tok_page = jnp.take_along_axis(
        block_tables[tok_b], (tok_pos // bs)[:, None], axis=1)[:, 0]
    tok_page = jnp.where(tok_valid & (tok_page >= 0), tok_page,
                         num_blocks + jnp.arange(token_num, dtype=jnp.int32))
    return write_rows(pool, layer, tok_page, tok_pos % bs, rows)


# rows of a block of the sparse read's GATHER: a block gathers RB x topk
# cache rows (671 MB at 256 x 2,048 x 640 lanes). The rows that still
# gather: a selecting sequence's ONE row of a tick (a decode row: one block
# over the sequences' first rows), and the rows of a selecting chunk whose
# sequence holds more keys than `sparse_walk_keys` (by row blocks); the
# other chunks' rows take the masked walk and gather nothing.
_SPARSE_ROWS = 256
# The two sparse reads of a chunk as chip constants (TPU v5e; PR 44's chip
# readings at the cell's widths: one chunk of 2,016 rows, 128 heads, W 640,
# C 512, topk 2,048, beside 31 one-row sequences; PERF.md section 6).
# The masked walk, every row charged its sequence's keys after the tick as
# `sparse_walk_keys` charges it: 84.6 ms at 22,016 keys and 121.6 ms at
# 32,016 (3.5 ms + 3.69 ms a thousand keys; 25.7 ms at 6,016) = 155 and
# 157 TFLOP/s by that count (148 and 152 over the causal pairs alone; the
# walk without a mask: 79.8 and 115.1 ms).
_WALK_FLOPS = 1.55e14
# The gather by row blocks with the attention over the gathered rows: 96.0
# ms for the chunk at every context = 23.2 ns a (row, selected key).
_GATHER_ROW_S = 2.32e-8


def sparse_walk_keys(heads: int, width: int, value_dim: int,
                     topk: int) -> int:
    """The CROSSING: the most keys a selecting sequence may hold after a
    tick for the masked walk to be the cheaper read of its chunk's rows.
    For one query row the walk multiplies every key the sequence holds,
    keys x heads x 2 x (width + value_dim) FLOPs at `_WALK_FLOPS`; the
    gather moves `topk` cache rows at `_GATHER_ROW_S` each, the attention
    behind the gather counted in. From shapes and the two constants alone:
    the device's rule (`paged_latent_attention`) and the host's count of it
    (`PagedServingEngine._plan_keys`) both ask here."""
    return min(int(topk * _GATHER_ROW_S * _WALK_FLOPS
                   / (heads * 2 * (width + value_dim))), 2 ** 31 - 1)


# The two sparse reads of a layer of heads' own keys and values as chip
# constants (TPU v5e, PR 50's chip readings at Keye-VL-2.0's widths: 32
# query heads over 4 key-value heads of 128, bf16 pages of 16, topk 2,048;
# PERF.md section 6 has the readings).
# The masked decode walk of one-row sequences is bound by the bytes of the
# pages it copies, keys x 2 x KV x hd x itemsize: 16 sequences of 33.8k-
# 64.5k keys (786,064 in all, 1.61 GB) in 3.94 ms at key blocks of 1,024
# (4.50 at 512, 6.07 at 256, 6.17 at 128; the unmasked walk at its 128:
# 6.14):
_HEADS_WALK_BYTES_S = 4.08e11
# The gather with the attention over what it fetched, a selected position
# (its KV heads' keys and values: 2 KV rows of hd, 12 ns a row of 256 B):
# 3.23 ms for the same 16 rows x 2,048 positions whatever their contexts.
# (A CHUNK's rows always walk: a turn's 143 rows took 49.3 ms through the
# gather against 4 for the masked mixed walk at 50k keys, and a chunk of
# 2,032 rows ending at 32,032 keys walks in 19.9 ms, by its products at
# 53 TFLOP/s: its crossing would lie near 650k keys, past every context
# the model's 262,144 positions allow.)
_HEADS_GATHER_POS_S = 9.85e-8


def sparse_walk_keys_heads(kv_heads: int, head_dim: int, itemsize: int,
                           topk: int) -> int:
    """The CROSSING of a layer of heads' own keys and values: the most
    keys a selecting sequence may hold after a tick for the masked decode
    walk to be the cheaper read of its ONE row. The row walks alone, so
    its walk is the bytes of its pages at `_HEADS_WALK_BYTES_S`; the
    gather costs it `topk` positions at `_HEADS_GATHER_POS_S`. From shapes
    and the two constants alone: the device's rule
    (`paged_layer_attention`) and the host's count of it
    (`PagedServingEngine._plan_keys`) both ask here."""
    per_key_s = 2 * kv_heads * head_dim * itemsize / _HEADS_WALK_BYTES_S
    return min(int(topk * _HEADS_GATHER_POS_S / per_key_s), 2 ** 31 - 1)


def _by_row_blocks(fn, args, rows: int):
    """`fn` over blocks of `_SPARSE_ROWS` leading rows of `args` (a tuple
    of arrays with `rows` leading rows), concatenated: what a block holds
    at once is bounded whatever the tick's rows."""
    if rows <= _SPARSE_ROWS:
        return fn(*args)
    pad = -rows % _SPARSE_ROWS
    blocks = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)
                               ).reshape(-1, _SPARSE_ROWS, *a.shape[1:])
    out = lax.map(lambda xs: fn(*xs), tuple(blocks(a) for a in args))
    return out.reshape(-1, *out.shape[2:])[:rows]


def index_select_form(max_kv: int, use_pallas) -> str:
    """The form `paged_index_select`'s exact selection takes over tables of
    `max_kv` key positions, static by shape and read path: "launch" (the
    Pallas read: `pallas.index_select.select_bits`, a block of rows'
    scores fetched once and every counting pass in VMEM) wherever a row
    block fits there, else "passes" (`sparse_index.select_topk`: the stock
    path, and a table past a million positions). The engine names it in
    `serving.step_build`."""
    from ..pallas import index_select
    return ("launch" if use_pallas and index_select.fits(max_kv)
            else "passes")


def paged_index_select(qi, w, ki_tok, pool, layer, seq_lens_decoder,
                       seq_lens_this_time, cu_seqlens_q, block_tables,
                       topk: int, use_pallas=False):
    """A layer's sparse index (over a latent cache or over heads' own
    keys and values) on the stacked INDEX-KEY pool [L, num_blocks, 1,
    block_size, IW] (one more row a position of the full layers' pages,
    under their block table; IW is the index head's width ID in whole
    lanes, the engine pads a 64-wide key to 128 with zeros, which add
    nothing to a score): write the new tokens'
    index keys `ki_tok` [tok, ID] into `layer`'s pages, then, for the rows
    of every sequence that holds more than `topk` keys after this tick,
    score every key the row sees (qi [tok, IH, ID] index queries, w [tok,
    IH] float32 head weights; `sparse_index.index_scores`) and select the
    `topk` best EXACTLY (`sparse_index.select_topk`: no approximate top-k,
    no scores in fewer bits). A sequence with at most `topk` keys has no
    selection to make and its rows take the dense walk
    (`paged_latent_attention` says the whole rule).

    `use_pallas` as `paged_latent_attention`'s, and the same split by the
    rows a sequence has in the tick: "decode" and the one-row sequences of
    a tick with a chunk go through the one-row form of the index walk
    (`paged_attention_latent.index_scores_rows`: a sequence's row against
    its key blocks), a chunk's rows through the index walk
    (`index_scores_packed`), which never holds [rows, heads, keys]. Both
    launches take the stacked pool, the layer and the block table and copy
    a key tile's whole pages themselves, up to the last key a row sees:
    nothing lays a sequence's keys out by position and no layer of the
    pool is sliced or copied. False gathers every sequence's keys by
    position and scores each row against its own (the stock path: CPU
    tests, the reference-side form). Scopes: `cache_write`, `index_scores`
    (the gather and the products, or the two launches), `index_select`:
    the exact selection in the form `index_select_form` names (on the
    Pallas read path the launch `index_select_bits`: a block of 8 rows'
    scores fetched once, every counting pass in VMEM up to the keys the
    block's rows see, the bits packed there; on the stock path
    `select_topk`'s 32 passes and `cumsum`, then `pack_mask`), and on
    both paths `selected_positions` over the packed bits
    with each position's page. Returns (positions [tok, topk] int32
    ascending, -1 behind a row's last and everywhere in a row that takes
    the dense walk; the page of each selected key by its row's block table
    [tok, topk]; sparse [B] bool: the sequences whose rows were selected
    for; the selection itself as `select_topk` makes it, a bit a key of the
    table (`sparse_index.pack_mask`: [tok, blocks of 128 keys, 4] uint32,
    the words the positions are counted from), which the masked walk reads
    where the gather reads the positions; pool). `block_size` divides
    128."""
    from ..pallas import paged_attention_latent as PL
    from ..pallas.index_select import select_bits
    from . import sparse_index
    L_, num_blocks, _, bs, ID = pool.shape
    B, max_blocks = block_tables.shape
    token_num = qi.shape[0]
    lanes = ((0, 0),) * (qi.ndim - 1) + ((0, ID - qi.shape[-1]),)
    qi, ki_tok = jnp.pad(qi, lanes), jnp.pad(ki_tok, lanes[1:])
    max_kv = max_blocks * bs
    cu = cu_seqlens_q.astype(jnp.int32).reshape(-1)
    past = seq_lens_decoder.reshape(-1).astype(jnp.int32)
    this = seq_lens_this_time.reshape(-1).astype(jnp.int32)
    tok_b, tok_pos, tok_valid = _packed_rows(past, this, cu, token_num, B)
    with jax.named_scope("cache_write"):
        pool = _write_latent_rows(pool, layer, ki_tok[:, None], past, this,
                                  cu, block_tables, tok_b, tok_pos,
                                  tok_valid, use_pallas)
    sparse = (this > 0) & (past + this > topk)

    def select():
        with jax.named_scope("index_scores"):
            if not use_pallas:
                # every sequence's index keys by position, one gather of
                # its pages, and each row against its own sequence's
                flat = pool.reshape(L_ * num_blocks, bs * ID)
                keys = jnp.take(flat, layer * num_blocks
                                + jnp.maximum(block_tables, 0), axis=0
                                ).reshape(B, max_kv, ID)
                scores = sparse_index.index_scores(qi, keys[tok_b], w)
            else:
                # the two launches copy a sequence's pages themselves;
                # a sequence that does not select copies nothing
                first = jnp.clip(cu[:B], 0, token_num - 1)
                one = sparse & (this == 1)
                rows = PL.index_scores_rows(
                    qi[first], w[first], pool, block_tables, past,
                    one.astype(jnp.int32), layer)              # [B, max_kv]
                if use_pallas == "decode":
                    scores = rows[tok_b]
                else:
                    # the one-row sequences' rows into the walk's output,
                    # in place (a `where` over [tok, max_kv] is three
                    # passes over it)
                    scores = PL.index_scores_packed(
                        qi, w, pool, block_tables, past,
                        jnp.where(sparse & ~one, this, 0), cu, layer)
                    scores = scores.at[jnp.where(
                        one, first, token_num + jnp.arange(B))].set(
                        rows, mode="drop", unique_indices=True)
        with jax.named_scope("index_select"):
            # the keys a row sees: those up to its own, of a valid row of a
            # sequence that selects
            seen = jnp.where(tok_valid & sparse[tok_b], tok_pos + 1, 0)
            if index_select_form(max_kv, use_pallas) == "launch":
                bits = select_bits(scores, seen, topk)[0]
            else:
                bits = sparse_index.pack_mask(sparse_index.select_topk(
                    scores, jnp.arange(max_kv)[None, :] < seen[:, None],
                    topk))
            # a row's table in eights (the pages of a block of 128 keys)
            # rides along, so that each selected key comes with its page
            # and the read looks nothing up
            per = sparse_index.BLOCK // bs
            mine = jnp.pad(block_tables, ((0, 0), (0, -max_blocks % per))
                           )[tok_b].reshape(token_num, -1, per)
            pos, pages = sparse_index.selected_positions(bits, topk,
                                                         carry=mine)
            sub = jnp.maximum(pos, 0) % sparse_index.BLOCK // bs
            page = pages[0]
            for i in range(1, per):
                page = jnp.where(sub == i, pages[i], page)
            return pos, page, bits

    none = jnp.full((token_num, topk), -1, jnp.int32)
    idx, page, bits = lax.cond(
        jnp.any(sparse), select,
        lambda: (none, none, jnp.zeros(
            (token_num, -(-max_kv // sparse_index.BLOCK), 4), jnp.uint32)))
    return idx, page, sparse, bits, pool


def paged_latent_attention(q_nope, q_rope, row_tok, wk, wv, pool, layer,
                           seq_lens_decoder, seq_lens_this_time, cu_seqlens_q,
                           block_tables, sm_scale, use_pallas=False,
                           window: int = 0, select=None):
    """One layer of multi-head latent attention on the stacked LATENT page
    pool [L, num_blocks, 1, block_size, W]: write the new tokens' cache rows
    `row_tok` [tok, w] (latent | rope key, `models.llama.latent_kv`; w <= W,
    the pool's rows are whole lanes and the rest of a row is zeros) into
    `layer`'s pages where they lie, then attend, causal, scores times
    `sm_scale`. There is no value pool. q_nope [tok, H, nope] and q_rope
    [tok, H, rope] are a token's queries (`models.llama.latent_q`), wk
    [C, H, nope] and wv [C, H, v] the halves of Wkvb
    (`models.llama.latent_wkvb`).

    WHICH READ A ROW TAKES, AND IN WHICH FORM (the one rule, here and
    nowhere else). Every row attends in the ABSORBED form, (q_nope wk_h^T |
    q_rope) against the cache rows themselves, its head's output the
    probabilities' sum over the rows' latents, then through wv_h: 2 x (W +
    C) FLOPs a (row, key, head). Which keys:

    - WINDOW (`window` W > 0, static: a layer whose spec has one): the last
      W keys, the row's own among them, by the dense walks below with
      their window bound; the table's entries behind every window may be
      -1 (`paged_attention_latent_window` inside `paged_attention`);
    - SPARSE (`select` = (positions [tok, k], their pages [tok, k], sparse
      [B], the selection's bits [tok, max_kv / 128, 4]) from
      `paged_index_select`: a layer whose spec has an index): the rows of a
      sequence that holds
      more than k keys after this tick attend over their k selected cache
      rows alone (`paged_attention_sparse`); a row that sees at most k keys
      has them all selected. The rows of the other sequences take the DENSE
      walk. The selected rows are GATHERED or WALKED, in three launches,
      each under its own `lax.cond` on the tick's own lengths (a tick
      without such a sequence launches nothing for it; the stock read
      gathers every selecting row and needs no mask):
      * a selecting sequence with ONE row this tick (a decode row): the
        GATHER of its k cache rows by position, launched over the
        sequences' first rows (at most `max_batch` of them);
      * a selecting CHUNK that holds at most `sparse_walk_keys` keys after
        this tick: the MASKED WALK (`paged_attention_latent`'s mixed walk
        with the selection mask ANDed into what a row sees): every page of
        the context read once for a tile of rows, no cache row moved twice,
        at context / k times the gather's FLOPs;
      * a selecting chunk beyond that crossing: the gather again, by blocks
        of `_SPARSE_ROWS` rows.
      The crossing is a formula of shapes and two chip constants, nobody's
      setting: for one query row the walk costs keys x H x 2 x (W + C) /
      `_WALK_FLOPS` seconds and the gather k x `_GATHER_ROW_S`
      (`sparse_walk_keys`, where both readings stand). Softmax over exactly
      the selected keys either way, bf16 products, float32 sums,
      probabilities rounded to the pages' type for p.v;
    - DENSE (everything else): every key up to the row's own
      (`paged_attention_latent`).

    The dense walks: `use_pallas` "decode" is the decode launch (one token
    a sequence); True is a tick with a chunk, whose one-row sequences go
    through that same decode launch and whose chunks go through the mixed
    walk (`paged_attention_latent`): a work item of the walk moves 4.6 MB
    of rows for one live token, and 63 of them cost a layer 2.6 ms where
    the decode launch takes under 1 (PERF.md section 6, PR 41). The
    EXPANDED form for a chunk (the sequence's cache rows rebuilt through
    Wkvb to a head's keys and values, 2 x (nope + rope + v) FLOPs a (row,
    key, head): 3.6 x fewer) was built and measured in that PR and is not
    taken: its kernel ran the chunk at 37 TFLOP/s where the walk runs it
    at 131, and the rebuild cost 0.9 ms a layer beside it. False is the
    stock read (CPU tests): the sparse read's own gather over every key a
    row sees, in float32, so that a selection of every key IS the dense
    read. Scopes: `latent_q` (the absorption), `cache_write`,
    `paged_attention` > `paged_attention_latent` | `_latent_window` |
    `_sparse` (the launches), `latent_out` (wv). Returns (o [tok, H * v],
    pool)."""
    from ..pallas import paged_attention_latent as PL
    L_, num_blocks, _, bs, W = pool.shape
    B, max_blocks = block_tables.shape
    token_num, H, nope = q_nope.shape
    C, w = wk.shape[0], row_tok.shape[-1]
    max_kv = max_blocks * bs
    cu = cu_seqlens_q.astype(jnp.int32).reshape(-1)
    past = seq_lens_decoder.reshape(-1).astype(jnp.int32)
    this = seq_lens_this_time.reshape(-1).astype(jnp.int32)
    tok_b, tok_pos, tok_valid = _packed_rows(past, this, cu, token_num, B)
    with jax.named_scope("latent_q"):
        q_tok = jnp.concatenate(
            [jnp.einsum("thn,chn->thc", q_nope, wk.astype(q_nope.dtype)),
             q_rope, jnp.zeros((token_num, H, W - w), q_nope.dtype)], axis=-1)
    rows = jnp.pad(row_tok, ((0, 0), (0, W - w)))[:, None]     # [tok, 1, W]

    with jax.named_scope("cache_write"):
        pool = _write_latent_rows(pool, layer, rows, past, this, cu,
                                  block_tables, tok_b, tok_pos, tok_valid,
                                  use_pallas)

    def way_out(o_latent):                          # [tok, H, C] -> [tok, H*v]
        with jax.named_scope("latent_out"):
            return jnp.einsum("thc,chv->thv", o_latent,
                              wv.astype(o_latent.dtype)
                              ).reshape(token_num, -1)

    def read_rows(q, idx, page):
        """Rows q [n, H, W] over the cache rows at positions idx [n, k]
        (-1: none) of pages `page` [n, k] alone."""
        at = ((layer * num_blocks + jnp.maximum(page, 0)) * bs
              + jnp.maximum(idx, 0) % bs)
        # (inside the pool by construction: no fill, no select over the
        # gathered rows)
        keys = jnp.take(pool.reshape(L_ * num_blocks * bs, W), at, axis=0,
                        mode="clip")
        if not use_pallas:
            q, keys = q.astype(jnp.float32), keys.astype(jnp.float32)
        s = jnp.einsum("nhw,nkw->nhk", q, keys,
                       preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(((idx >= 0) & (page >= 0))[:, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(keys.dtype)
        return jnp.einsum("nhk,nkc->nhc", p, keys[..., :C],
                          preferred_element_type=jnp.float32
                          ).astype(q_tok.dtype)

    def dense(this_d):
        """The dense walks over the sequences whose `this_d` is not 0."""
        kw = {"window": window} if window else {}
        first = jnp.clip(cu[:B], 0, token_num - 1)
        valid = tok_valid[:, None, None]
        if use_pallas == "decode":
            o = PL.latent_attention(q_tok[first], pool, block_tables, past,
                                    this_d, sm_scale, layer, C, **kw)[tok_b]
            return jnp.where(valid, o, 0)
        if use_pallas:
            single = this_d == 1
            rows_1 = PL.latent_attention(
                q_tok[first], pool, block_tables, past,
                single.astype(jnp.int32), sm_scale, layer, C, **kw)[tok_b]
            chunks = PL.latent_attention_packed(
                q_tok, pool, block_tables, past,
                jnp.where(single, 0, this_d), cu, sm_scale, layer, C, **kw)
            # (the walk leaves the rows that are no sequence's zero)
            return jnp.where(single[tok_b][:, None, None] & valid, rows_1,
                             chunks)
        # ---- stock read (CPU tests): every key a row sees, selected
        pos = jnp.arange(max_kv, dtype=jnp.int32)[None, :]
        seen = pos <= tok_pos[:, None]
        if window:
            seen &= pos > (tok_pos - window)[:, None]
        return jnp.where(valid, read_rows(
            q_tok, jnp.where(seen, pos, -1),
            jnp.repeat(block_tables[tok_b], bs, axis=1)), 0)

    with jax.named_scope("paged_attention"):
        if select is None:
            name = "paged_attention_latent" + ("_window" if window else "")
            with jax.named_scope(name):
                o = dense(this)
        else:
            idx, page, sparse, *mask = select
            with jax.named_scope("paged_attention_latent"):
                o = lax.cond(
                    jnp.any((this > 0) & ~sparse),
                    lambda: dense(jnp.where(sparse, 0, this)),
                    lambda: jnp.zeros((token_num, H, C), q_tok.dtype))

            def read(o, seqs, launch):
                """`o` with the rows of the sequences `seqs` [B] taken
                from `launch()`, which runs only in a tick that has one."""
                mine = (seqs[tok_b] & tok_valid)[:, None, None]
                return lax.cond(jnp.any(seqs),
                                lambda: jnp.where(mine, launch(), o),
                                lambda: o)

            def gathered():
                return _by_row_blocks(read_rows, (q_tok, idx, page),
                                      token_num)

            with jax.named_scope("paged_attention_sparse"):
                if not use_pallas:
                    o = read(o, sparse, gathered)
                else:
                    first = jnp.clip(cu[:B], 0, token_num - 1)
                    o = read(o, sparse & (this == 1), lambda: _by_row_blocks(
                        read_rows, (q_tok[first], idx[first], page[first]),
                        B)[tok_b])
                    if use_pallas != "decode":
                        chunk = sparse & (this > 1)
                        walk = chunk & (past + this <= sparse_walk_keys(
                            H, W, C, idx.shape[1]))
                        o = read(o, walk, lambda: PL.latent_attention_packed(
                            q_tok, pool, block_tables, past,
                            jnp.where(walk, this, 0), cu, sm_scale, layer, C,
                            mask=mask[0]))
                        o = read(o, chunk & ~walk, gathered)
    return way_out(o.astype(q_tok.dtype)), pool


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
