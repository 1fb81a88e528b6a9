"""Quantized model transform: swap transformer matmuls for int8/fp8.

``quantize_llama_params`` rewrites the stacked-params pytree the serving
engines scan over: every block matmul weight (wq/wk/wv/wo/w1/w3/w2) and
the lm_head are replaced by three leaves —

- ``<name>_q``  int8 (or float8_e4m3fn) weights, same [L, in, out] layout;
- ``<name>_s``  f32 per-output-channel absmax scales [L, 1, out]
  (keepdims so a ``lax.scan`` layer slice broadcasts directly);
- ``<name>_a``  f32 per-layer activation absmax [L] — w8a8 mode only.

``matmul_param(h, tree, name)`` is the ONE matmul entry both
``LLMPredictor`` and ``PagedServingEngine`` call: it dispatches
statically on which leaves exist in the pytree (pytree structure is part
of every jit signature, so the quantized and fp paths compile to
different executables and the steady state performs zero retraces —
quant mode is never a traced branch).

Arithmetic (the EQuARX block-scale recipe on the MXU):

- w8 (weight-only int8): ``(h @ w_q) * (s / 127)`` — the per-column
  scale commutes out of the dot product, so the int8 weights feed the
  matmul directly (XLA fuses the int8→fp convert into the dot's operand
  read; no dequantized weight copy is materialized);
- w8a8: ``round(clip(h / a * 127))`` int8 activations, int8×int8→int32
  ``dot_general`` (``preferred_element_type=int32`` — the MXU's native
  double-rate path), one fused rescale ``(a * s) / 127²``;
- fp8: weight-only float8_e4m3fn storage where ``jax.dtypes`` has it
  (``(h @ w_q) * (s / 448)``), same per-channel absmax scaling.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp

from ...observability import emit as _emit

__all__ = ["quantize_llama_params", "matmul_param", "fp8_dtype",
           "QUANT_MODES", "WEIGHT_NAMES", "QMAX", "FP8_MAX"]

QMAX = 127.0
FP8_MAX = 448.0            # float8_e4m3fn finite max
QUANT_MODES = ("", "w8", "w8a8", "fp8")
WEIGHT_NAMES = ("wq", "wk", "wv", "wo", "w1", "w3", "w2")


def fp8_dtype():
    """The platform's fp8 storage dtype, or None when this jax build has
    no float8_e4m3fn (callers gate, never crash mid-trace)."""
    return getattr(jnp, "float8_e4m3fn", None)


def _quantize_stack(w, in_axis: int, mode: str):
    """(w_q, scales) for a weight stack; scales are absmax with keepdims
    along `in_axis` so layer slices broadcast against [..., out]."""
    w = jnp.asarray(w, jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=in_axis, keepdims=True), 1e-8)
    if mode == "fp8":
        ft = fp8_dtype()
        wq = (w / s * FP8_MAX).astype(ft)
    else:
        wq = jnp.clip(jnp.round(w / s * QMAX), -QMAX, QMAX).astype(jnp.int8)
    return wq, s.astype(jnp.float32)


def quantize_llama_params(params: Dict, mode: str,
                          manifest=None) -> Dict:
    """Return a new params pytree with quantized matmul weights. ``mode``
    in {"w8", "w8a8", "fp8"}; w8a8 needs a calibration manifest for the
    activation scales. Embedding and norm weights stay fp."""
    if mode not in QUANT_MODES:
        raise ValueError(f"quant mode {mode!r} not in {QUANT_MODES}")
    if not mode:
        return params
    if mode == "fp8" and fp8_dtype() is None:
        raise RuntimeError(
            "quant_mode='fp8' but this jax build has no float8_e4m3fn; "
            "use 'w8' (weight-only int8) instead")
    if mode == "w8a8" and manifest is None:
        raise ValueError(
            "quant_mode='w8a8' quantizes activations with STATIC "
            "calibrated scales; run inference.quant.calibrate over a "
            "sample workload and pass the manifest")
    if "blocks" not in params or "lm_head" not in params:
        raise ValueError("quantize_llama_params expects the stacked LLaMA "
                         "params pytree (init_params output)")
    from ...models.llama import kind_stacks
    if any(n.startswith("hc_") for stack in kind_stacks(params["blocks"])
           for n in stack):
        raise NotImplementedError(
            "quantized transform covers blocks whose residual path is the "
            "plain sum; hyper-connections (LlamaConfig.hyper_lanes: the "
            "`hc_*` mixing weights) were never judged in fewer bits")
    blocks = dict(params["blocks"])
    missing = [n for n in WEIGHT_NAMES if n not in blocks]
    if missing:
        raise NotImplementedError(
            f"quantized transform covers dense LLaMA blocks; params are "
            f"missing {missing} (MoE experts stay fp)")
    count = 0
    for name in WEIGHT_NAMES:
        w = blocks.pop(name)
        wq, s = _quantize_stack(w, in_axis=1, mode=mode)   # [L, in, out]
        blocks[name + "_q"] = wq
        blocks[name + "_s"] = s                            # [L, 1, out]
        if mode == "w8a8":
            blocks[name + "_a"] = jnp.asarray(
                manifest.act_scales[name], jnp.float32)    # [L]
        count += int(w.shape[0])
    out = dict(params)
    out["blocks"] = blocks
    lm_q, lm_s = _quantize_stack(params["lm_head"], in_axis=0, mode=mode)
    out.pop("lm_head")
    out["lm_head_q"] = lm_q                                # [in, out]
    out["lm_head_s"] = lm_s                                # [1, out]
    if mode == "w8a8":
        out["lm_head_a"] = jnp.float32(manifest.act_scales["lm_head"][0])
    count += 1
    _emit("quant.convert", mode=mode, matmuls=count)
    return out


def matmul_param(h, tree, name: str):
    """``h @ tree[name]`` with static dispatch on quantization: fp when
    the plain leaf exists, otherwise the quantized executables described
    in the module docstring. ``tree`` is either a scan-sliced block dict
    (leaves [in, out] / [1, out] / scalar) or the root params dict
    (lm_head leaves have the same trailing shapes)."""
    wq = tree.get(name + "_q")
    if wq is None:
        return h @ tree[name].astype(h.dtype)
    s = tree[name + "_s"]
    a = tree.get(name + "_a")
    if wq.dtype == jnp.int8 and a is not None:             # w8a8
        xq = jnp.clip(jnp.round(h.astype(jnp.float32) / a * QMAX),
                      -QMAX, QMAX).astype(jnp.int8)
        acc = jnp.matmul(xq, wq, preferred_element_type=jnp.int32)
        y = acc.astype(jnp.float32) * ((a * s) / (QMAX * QMAX))
        return y.astype(h.dtype)
    qmax = QMAX if wq.dtype == jnp.int8 else FP8_MAX       # weight-only
    acc = h @ wq.astype(h.dtype)
    return acc * (s / qmax).astype(h.dtype)
