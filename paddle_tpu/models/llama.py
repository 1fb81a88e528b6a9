"""Functional LLaMA-family decoder — the flagship model of the framework.

Role in the framework (SURVEY.md §6/§7): the reference's headline benchmark is
LLaMA-13B trained through fleet hybrid parallel (BASELINE.json config 4, built
in model code on top of fleet primitives: mp_layers.py ColumnParallelLinear /
RowParallelLinear / VocabParallelEmbedding, pipeline_parallel.py schedules).
Here the flagship is a pure-functional JAX model: a params pytree + jittable
forward/loss, designed so the hybrid-parallel engine
(paddle_tpu.distributed.hybrid) can shard the SAME pytree over a
('dp','pp','tp') mesh with shard_map — layers are stacked on a leading axis
(lax.scan-able, pp-splittable), and every projection is written so tp sharding
of its output/input dim is valid.

TPU-first choices: bf16 compute / f32 master params, static shapes, scan over
stacked layer params (one compiled block body, not L unrolled layers), GQA,
RoPE computed in f32, optional MoE (top-k routing through `route` and
`routed_ffn`; the hybrid engine dispatches tokens with all_to_all over the
ep axis) and optional QK-norm (`qk_normed`), as OLMoE has them or per head
as Qwen3-MoE and SDAR have it; `block_length` > 0 puts the full-sequence
forward under SDAR's block-causal mask (generation by diffusion over
blocks itself is the serving engine's, inference/serving/engine.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    # MoE: 0 = dense MLP. When >0, every layer's MLP is a top-k gated MoE.
    num_experts: int = 0
    top_k: int = 2
    # two shape keys of a model's own config.json (defaults: what every
    # model before OLMoE had): RMSNorm over the whole projected q and k
    # vectors before the split into heads, and whether the top-k router
    # weights are renormalised to sum to one
    qk_norm: bool = False
    norm_topk_prob: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # the width of one head; 0 = hidden_size // num_heads, what every model
    # before SDAR had (its 32 heads of 128 are wider than its hidden 2048).
    # `dataclasses.replace(cfg, hidden_size=..)` keeps the resolved value:
    # pass head_dim=0 with it to derive it anew
    head_dim: int = 0
    # with `qk_norm`: the second form of QK-norm (Qwen3-MoE's, which SDAR
    # keeps), RMSNorm over each head's vector (weight [head_dim]) after the
    # split into heads, instead of OLMoE's over the whole projected vector
    qk_norm_per_head: bool = False
    # generation by diffusion over blocks (SDAR): 0 = autoregressive. With
    # block_length Bd > 0 attention is block-causal (position i sees j iff
    # j // Bd <= i // Bd), a block of Bd positions is generated together
    # from rows that carry `mask_token_id`, in the request's denoise
    # forwards and one commit forward (inference/serving/engine.py)
    block_length: int = 0
    mask_token_id: int = 0

    def __post_init__(self):
        if not self.head_dim:
            object.__setattr__(self, "head_dim",
                               self.hidden_size // self.num_heads)

    def num_params(self) -> int:
        d, f, v = self.hidden_size, self.intermediate_size, self.vocab_size
        hd = self.head_dim
        attn = d * self.num_heads * hd + 2 * d * self.num_kv_heads * hd + self.num_heads * hd * d
        if self.qk_norm:
            attn += (2 * hd if self.qk_norm_per_head
                     else (self.num_heads + self.num_kv_heads) * hd)
        if self.num_experts:
            mlp = self.num_experts * 3 * d * f + d * self.num_experts
        else:
            mlp = 3 * d * f
        per_layer = attn + mlp + 2 * d
        return v * d + self.num_layers * per_layer + d + d * v

    def flops_per_token(self) -> int:
        """Approximate training FLOPs/token (fwd+bwd ≈ 6·N_active): a
        token passes through `top_k` of the experts and the router."""
        d, f = self.hidden_size, self.intermediate_size
        hd = self.head_dim
        attn = d * self.num_heads * hd + 2 * d * self.num_kv_heads * hd + self.num_heads * hd * d
        if self.num_experts:
            mlp = (3 * d * f * min(self.top_k, self.num_experts)
                   + d * self.num_experts)
        else:
            mlp = 3 * d * f
        dense = self.num_layers * (attn + mlp) + 2 * self.hidden_size * self.vocab_size
        return 6 * dense


# Predefined sizes (the reference's headline configs; LLaMA-7B/13B per
# BASELINE.json config 4).
CONFIGS = {
    "llama-test": LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                              num_layers=4, num_heads=4, num_kv_heads=2, max_seq_len=128),
    "llama-7b": LlamaConfig(hidden_size=4096, intermediate_size=11008, num_layers=32,
                            num_heads=32, num_kv_heads=32),
    "llama-13b": LlamaConfig(hidden_size=5120, intermediate_size=13824, num_layers=40,
                             num_heads=40, num_kv_heads=40),
}


def init_params(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """Build the parameter pytree. Block params are stacked on a leading
    num_layers axis so the forward is a lax.scan and the pipeline engine can
    reshape to [pp, layers_per_stage, ...]."""
    d, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    hd, nh, nkv, L = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads, cfg.num_layers
    pt = cfg.param_dtype
    keys = jax.random.split(key, 10)

    def normal(k, shape, scale=0.02):
        return (scale * jax.random.normal(k, shape, jnp.float32)).astype(pt)

    blocks = {
        "wq": normal(keys[0], (L, d, nh * hd)),
        "wk": normal(keys[1], (L, d, nkv * hd)),
        "wv": normal(keys[2], (L, d, nkv * hd)),
        "wo": normal(keys[3], (L, nh * hd, d)),
        "attn_norm": jnp.ones((L, d), pt),
        "mlp_norm": jnp.ones((L, d), pt),
    }
    if cfg.qk_norm and cfg.qk_norm_per_head:
        blocks["q_norm"] = jnp.ones((L, hd), pt)
        blocks["k_norm"] = jnp.ones((L, hd), pt)
    elif cfg.qk_norm:
        blocks["q_norm"] = jnp.ones((L, nh * hd), pt)
        blocks["k_norm"] = jnp.ones((L, nkv * hd), pt)
    if cfg.num_experts:
        e = cfg.num_experts
        blocks["router"] = normal(keys[4], (L, d, e))
        blocks["w1"] = normal(keys[5], (L, e, d, f))
        blocks["w3"] = normal(keys[6], (L, e, d, f))
        blocks["w2"] = normal(keys[7], (L, e, f, d))
    else:
        blocks["w1"] = normal(keys[5], (L, d, f))
        blocks["w3"] = normal(keys[6], (L, d, f))
        blocks["w2"] = normal(keys[7], (L, f, d))
    return {
        "embed": normal(keys[8], (v, d)),
        "blocks": blocks,
        "final_norm": jnp.ones((d,), pt),
        "lm_head": normal(keys[9], (d, v)),
    }


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    x32 = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * w.astype(jnp.float32)).astype(x.dtype)


def rope_cos_sin(positions: jax.Array, head_dim: int, theta: float):
    """positions [T] int → (cos, sin) [T, head_dim/2] in f32."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [B, T, H, hd]; rotate-half convention, f32 math."""
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, impl: str = "auto",
              block_length: int = 0) -> jax.Array:
    """Causal MHA/GQA. q [B,T,H,hd], k/v [B,T,KV,hd] → [B,T,H,hd].

    impl: 'auto' uses the Pallas flash kernel on TPU when available, else the
    XLA einsum path (which XLA fuses well on its own).

    block_length Bd > 0: the block-causal mask of generation by diffusion
    over blocks, position i sees j iff j // Bd <= i // Bd (full inside a
    block, causal across blocks), on the XLA path alone: the flash kernel
    knows the causal mask only, and impl='flash' with it raises.
    """
    if block_length and impl == "flash":
        raise ValueError("the flash kernel has no block-causal mask: "
                         "block_length > 0 takes impl='auto' or 'xla'")
    if block_length:
        impl = "xla"
    if impl == "flash":
        # explicit request: no silent fallback — unsupported shapes raise
        from ..ops.pallas import flash_attention as _fa

        return _fa.flash_attention(q, k, v, causal=True)
    if impl == "auto":
        from ..ops.pallas import flash_attention as _fa

        if (_fa.available() and q.shape[1] == k.shape[1]
                and _fa.supported(q.shape, k.shape)):
            return _fa.flash_attention(q, k, v, causal=True)
    B, T, H, hd = q.shape
    KV = k.shape[2]
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    scale = 1.0 / (hd ** 0.5)
    scores = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * scale
    if block_length:
        blk = jnp.arange(T) // block_length
        mask = blk[None, :] <= blk[:, None]
    else:
        mask = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def qk_normed(q: jax.Array, k: jax.Array, lp: Dict[str, jax.Array],
              cfg: LlamaConfig):
    """QK-norm on the projected q [..., H*hd] and k [..., KV*hd], before
    rope, in the model's form. OLMoE's: RMSNorm over the WHOLE projected
    vector, before the split into heads. Qwen3-MoE's and SDAR's
    (`qk_norm_per_head`): RMSNorm over each head's hd values with one
    weight [hd] for all heads, after the split. Identity for a model
    without it."""
    if not cfg.qk_norm:
        return q, k
    if cfg.qk_norm_per_head:
        def per_head(x, w):
            heads = x.reshape(*x.shape[:-1], -1, cfg.head_dim)
            return rms_norm(heads, w, cfg.rms_eps).reshape(x.shape)
        return per_head(q, lp["q_norm"]), per_head(k, lp["k_norm"])
    return (rms_norm(q, lp["q_norm"], cfg.rms_eps),
            rms_norm(k, lp["k_norm"], cfg.rms_eps))


def route(h: jax.Array, lp: Dict[str, jax.Array], cfg: LlamaConfig):
    """Top-k routing of rows h [T, d]: (w [T, k] f32, e [T, k] i32). The
    softmax runs in float32 over ALL experts; the k weights are renormalised
    to sum to one only where the model's config says so (`norm_topk_prob`)."""
    gate = jax.nn.softmax(
        h.astype(jnp.float32) @ lp["router"].astype(jnp.float32), axis=-1)
    w, e = lax.top_k(gate, cfg.top_k)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, e.astype(jnp.int32)


def expert_form(cfg: LlamaConfig) -> Optional[str]:
    """The form `routed_ffn` computes the experts in (None for a dense
    model): rows sorted by expert through the grouped-matmul Pallas kernel
    where that kernel can run (the TPU), else every expert over every row
    in XLA, as `attention`'s 'auto' chooses its kernel. On the TPU v5e at
    OLMoE's widths the sorted form won at a decode tick's 16 rows (1.05
    against 1.20 ms a layer: it reads only the experts that are hit) and
    at a mixed tick's 512 (1.54-1.76 against 2.46 ms: an eighth of the
    FLOPs); `jax.lax.ragged_dot` lost to both (PERF.md section 6, PR 27)."""
    if not cfg.num_experts:
        return None
    from ..ops.pallas import flash_attention as _fa

    return "sorted_gmm" if _fa.available() else "dense_einsum"


def _layer_of(w: jax.Array, layer) -> jax.Array:
    return w if layer is None else w[layer]


def _experts_dense(h, w, e, valid, lp, cfg: LlamaConfig, layer):
    """Every expert over every row; a row's output keeps its k experts by a
    [T, E] combine weight that is zero elsewhere (and on a padding row)."""
    with jax.named_scope("dispatch"):
        comb = jnp.sum(jax.nn.one_hot(e, cfg.num_experts, dtype=w.dtype)
                       * w[..., None], axis=-2)                   # [T, E]
        comb = jnp.where(valid[:, None], comb, 0.0)
    with jax.named_scope("experts"):
        w1, w3, w2 = (_layer_of(lp[n], layer).astype(h.dtype)
                      for n in ("w1", "w3", "w2"))
        g = jnp.einsum("td,edf->tef", h, w1)
        u = jnp.einsum("td,edf->tef", h, w3)
        a = jax.nn.silu(g) * u
        out = jnp.einsum("tef,efd->ted", a, w2)
    with jax.named_scope("combine"):
        return jnp.einsum("ted,te->td", out, comb.astype(h.dtype))


GMM_ROWS = 128      # the grouped-matmul kernel's row tile


def _gmm(xs, w, group_sizes, group_offset):
    from jax.experimental.pallas.ops.tpu import megablox
    from ..ops.pallas import flash_attention as _fa

    tile = lambda n, t: t if n % t == 0 else n
    return megablox.gmm(
        xs, w, group_sizes, preferred_element_type=xs.dtype,
        tiling=(GMM_ROWS, tile(xs.shape[1], 1024), tile(w.shape[-1], 1024)),
        group_offset=group_offset, interpret=not _fa.available())


@jax.custom_vjp
def _grouped_matmul(xs: jax.Array, w: jax.Array, group_sizes: jax.Array,
                    group_offset: jax.Array):
    """xs [M, K] rows sorted by group (M a multiple of GMM_ROWS), w
    [G, K, N], group_sizes [E] i32: row r of group g times
    w[g - group_offset]; rows behind the last group are undefined. The
    grouped-matmul Pallas kernel JAX ships (megablox `gmm`, tiles of
    128 x 1024 x 1024: the fastest of five tilings on the v5e). It is
    traced with x64 off, forward and backward: under this package's
    jax_enable_x64 it hands the kernel a 64-bit scalar, which the TPU
    compiler refuses."""
    with jax.enable_x64(False):
        return _gmm(xs, w, group_sizes, group_offset)


def _grouped_matmul_fwd(xs, w, group_sizes, group_offset):
    with jax.enable_x64(False):
        return jax.vjp(lambda a, b: _gmm(a, b, group_sizes, group_offset),
                       xs, w)


def _grouped_matmul_bwd(pull, g):
    with jax.enable_x64(False):
        return (*pull(g), None, None)


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def _experts_sorted(h, w, e, valid, load, lp, cfg: LlamaConfig, layer):
    """The T*k (row, expert) pairs sorted by expert, three grouped matmuls
    over `load` rows a group, then un-sorted and summed over a row's k. A
    padding row's pairs sort behind every group and belong to none.

    With `layer`, the weights are the stacked [L, E, ...] leaves and the
    kernel finds the layer's experts by its index map (group g reads
    w[layer*E + g], i.e. a group offset of -layer*E): a slice of the
    stack would be copied whole, 268 MB a matrix at OLMoE's widths, before
    each launch."""
    T, k = e.shape
    pad = -(T * k) % GMM_ROWS
    offset = jnp.asarray(0 if layer is None else -layer * cfg.num_experts,
                         jnp.int32)

    def dot(x, name):
        wn = lp[name].astype(h.dtype)
        return _grouped_matmul(x, wn.reshape(-1, *wn.shape[-2:]), load,
                               offset)

    # rows behind the last group are whatever the kernel left there, in
    # either direction: select them away, do not multiply by zero
    keep = (jnp.arange(T * k + pad) < jnp.sum(load))[:, None]
    with jax.named_scope("dispatch"):
        flat_e = jnp.where(valid[:, None], e, cfg.num_experts).reshape(-1)
        order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)  # [T*k]
        xs = jnp.where(keep, jnp.take(h, jnp.pad(order // k, (0, pad)),
                                      axis=0), 0)
    with jax.named_scope("experts"):
        a = jnp.where(keep, jax.nn.silu(dot(xs, "w1")) * dot(xs, "w3"), 0)
        ys = dot(a, "w2")
    with jax.named_scope("combine"):
        ys = jnp.where(keep, ys, 0)[:T * k].astype(jnp.float32)
        y = jnp.take(ys, jnp.argsort(order), axis=0).reshape(T, k, -1)
        return jnp.sum(y * w[..., None], axis=1).astype(h.dtype)


def routed_ffn_load(h: jax.Array, lp: Dict[str, jax.Array], cfg: LlamaConfig,
                    valid: Optional[jax.Array] = None, layer=None):
    """The routed SwiGLU experts over normed activations h [..., d]:
    sum_j w_j * (silu(h W1[e_j]) * (h W3[e_j])) W2[e_j] over a row's top-k
    experts. `valid` [...] bool marks the rows that exist (a serving tick
    pads its rows): a padding row joins no expert's group, yields zeros and
    counts in no load. With `layer` (an int32 scalar) `lp`'s w1, w3 and w2
    are the stacked [L, E, ...] leaves, as a layer loop that must not
    slice them hands them over; the router is the layer's own. Returns
    (y [..., d], load [E] i32: valid rows on each expert). Scopes: router,
    dispatch, experts, combine."""
    shape = h.shape
    h = h.reshape(-1, shape[-1])
    T = h.shape[0]
    valid = (jnp.ones((T,), bool) if valid is None
             else valid.reshape(-1))
    with jax.named_scope("router"):
        w, e = route(h, lp, cfg)
    with jax.named_scope("dispatch"):
        load = jnp.sum(jax.nn.one_hot(e, cfg.num_experts, dtype=jnp.int32)
                       * valid[:, None, None], axis=(0, 1),
                       dtype=jnp.int32)                           # [E]
    if expert_form(cfg) == "sorted_gmm":
        y = _experts_sorted(h, w, e, valid, load, lp, cfg, layer)
    else:
        y = _experts_dense(h, w, e, valid, lp, cfg, layer)
    return y.reshape(shape), load


def routed_ffn(h: jax.Array, lp: Dict[str, jax.Array], cfg: LlamaConfig,
               valid: Optional[jax.Array] = None) -> jax.Array:
    """`routed_ffn_load` without the load (the one routed FFN of the tree:
    `block`, `inference/llm.py` and the paged engine's tick call it; the
    hybrid trainer dispatches over the ep axis with its own all_to_all)."""
    return routed_ffn_load(h, lp, cfg, valid)[0]


def ffn(h: jax.Array, lp: Dict[str, jax.Array], impl: str = "stock") -> jax.Array:
    """SwiGLU FFN body over normed activations h [..., d].

    impl: 'stock' is the three-matmul XLA path; 'pallas' routes supported
    shapes through the one-launch fused kernel (ops/pallas/fused_ffn.py)
    and falls back to stock otherwise, mirroring attention's 'auto'.
    """
    if impl == "pallas":
        from ..ops.pallas import fused_ffn as _ff

        rows = math.prod(h.shape[:-1])
        d, f = lp["w1"].shape
        if _ff.supported(rows, d, f):
            return _ff.fused_ffn(h, lp["w1"].astype(h.dtype),
                                 lp["w3"].astype(h.dtype),
                                 lp["w2"].astype(h.dtype))
    gate = jax.nn.silu(h @ lp["w1"].astype(h.dtype)) * (h @ lp["w3"].astype(h.dtype))
    return gate @ lp["w2"].astype(h.dtype)


def block(x: jax.Array, lp: Dict[str, jax.Array], cfg: LlamaConfig,
          cos: jax.Array, sin: jax.Array, attn_impl: str = "auto",
          ffn_impl: str = "stock") -> jax.Array:
    """One transformer block; lp leaves have the layer axis already indexed."""
    B, T, d = x.shape
    hd, nh, nkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    q, k = qk_normed(h @ lp["wq"].astype(h.dtype),
                     h @ lp["wk"].astype(h.dtype), lp, cfg)
    v = (h @ lp["wv"].astype(h.dtype)).reshape(B, T, nkv, hd)
    q = apply_rope(q.reshape(B, T, nh, hd), cos, sin)
    k = apply_rope(k.reshape(B, T, nkv, hd), cos, sin)
    o = attention(q, k, v, impl=attn_impl,
                  block_length=cfg.block_length).reshape(B, T, nh * hd)
    x = x + o @ lp["wo"].astype(o.dtype)
    h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    if cfg.num_experts:
        x = x + routed_ffn(h, lp, cfg)
    else:
        x = x + ffn(h, lp, impl=ffn_impl)
    return x


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: LlamaConfig,
            attn_impl: str = "auto", ffn_impl: str = "stock") -> jax.Array:
    """tokens [B, T] int32 → logits [B, T, vocab] (f32)."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    T = tokens.shape[1]
    cos, sin = rope_cos_sin(jnp.arange(T), cfg.head_dim, cfg.rope_theta)

    def body(carry, lp):
        return block(carry, lp, cfg, cos, sin, attn_impl, ffn_impl), None

    x, _ = lax.scan(body, x, params["blocks"])
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    return (x @ params["lm_head"].astype(x.dtype)).astype(jnp.float32)


def loss_fn(params: Dict[str, Any], tokens: jax.Array, targets: jax.Array,
            cfg: LlamaConfig, attn_impl: str = "auto",
            ffn_impl: str = "stock") -> jax.Array:
    """Next-token cross entropy, mean over tokens."""
    logits = forward(params, tokens, cfg, attn_impl, ffn_impl)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - true)
