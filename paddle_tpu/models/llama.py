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
`routed_ffn`; the hybrid engine runs the same `routed_ffn_load` on dp = 1
and dispatches tokens with all_to_all over the ep axis where dp > 1) and
optional QK-norm (`qk_normed`), as OLMoE has them or per head
as Qwen3-MoE and SDAR have it; `block_length` > 0 puts the full-sequence
forward under SDAR's block-causal mask (generation by diffusion over
blocks itself is the serving engine's, inference/serving/engine.py).

A stack that is not uniform is a *layer plan* (`LlamaConfig.layer_plan`,
one `LayerSpec` a layer: full or sliding-window attention, its count of
query heads, its rope, a dense or a routed FFN), as Laguna-XS.2 has one: a
leading dense layer, then window and full attention 3:1 with 64 and 48
query heads and two ropes, a per-head attention gate, 256 small routed
experts beside a shared one. Layers that share a `LayerSpec` are one
*kind*: their parameters are one stack (`params["blocks"]` is then a tuple
of stacks, one a kind, in order of first occurrence) and they share one
traced body; `scan_plan` runs the plan as scans over runs of one kind
inside a scan over the plan's periods. Without a plan every layer is the
config's own one kind (with `num_experts` > 0 every layer's FFN is
routed) and `params["blocks"]` is one dict. `LlamaConfig.layers` is the one
answer to "what are this config's layers", for both: the written plan, or
`num_layers` times that one kind, so `kinds`, `plan_segments` and
`scan_plan` serve a uniform config as a plan of one kind (one run, one
`lax.scan` over the unsliced stack, `kind_stacks` its one dict as a tuple
of one); the serving tick has no other layer loop.

A third kind of attention is *latent* (`LayerSpec.attn = "latent"`:
DeepSeek-V3's block, which Kimi-K2 keeps): queries through a low-rank
bottleneck with its own norm, keys and values through one latent a
position with its own norm beside one rope key that all heads share
(`latent_q`, `latent_kv`, `latent_wkvb`; `latent_self_attention` is the
expanded form that `forward` runs, the paged engine attends over the
cached latents themselves). A latent layer's widths are its spec's
(`LayerSpec.latent`, a `LatentSpec`), so one model may have latent layers
of two kinds, as dots3-note has: a kind with a causal WINDOW over its
latent cache, and a kind with a learned sparse INDEX (`IndexSpec`,
DeepSeek-V3.2's lightning indexer: `index_qkw` scores every visible key
with a few narrow heads, `select_topk` keeps the `topk` best exactly, and
the layer attends over those alone). The same index may stand over the
heads' OWN keys and values (`LlamaConfig.index` on a uniform config, or
`LayerSpec.index`: Keye-VL-2.0, whose index query comes from the layer's
normed input, there being no query latent; `index_select` is the
selection of either kind in the model's own forward). And a config may
hold ONE CHIP'S SHARE of its
routed experts (`experts_held` = (first, count)): `route` runs over all
`num_experts`, with a selection bias where the model has one
(`router_bias`), and `routed_ffn_load` computes the pairs of the held
experts and leaves out what the absent ones would add; nothing here
stands in for the chips that hold them. In the sorted form only those
pairs are gathered, multiplied and added into their rows (the COMPACT form
of `_experts_sorted`): they get `held_pair_slots` places, `HELD_ROOM`
times what an even router sends to a share of that size but never more
than half of all pairs, in whole tiles of the kernel's rows, and a launch
with more held pairs than places takes the form that moves all T*k pairs,
which is also the one form of a config that holds every expert; no pair is
ever dropped. The way from rows to places and the way back are one pair of
transposed gathers (`_dispatch`, `_combine`: each is the other's
derivative, so a trained launch adds no d-wide row into place one at a
time), and the way back is the [T, C] product where the places are few
(`combine_is_a_product`, from the launch's shapes and two measured rates).

The residual path itself may be other than the plain sum `x + F(norm(x))`:
with `hyper_lanes` n > 0 (manifold-constrained hyper-connections, mHC,
arXiv:2512.24880; Xing4.0's `hc_mult` 4) a row's state is n lanes of
`hidden_size`, kept FLAT as [..., n d] (lane i the slice [i d, (i + 1) d)),
and every sub-block reads a learned mix of the lanes and writes back
through a doubly stochastic mix of them (`residual`, the one seam every
`x + ...` of a block goes through; `hyper_coeff`, `hyper_pre`,
`hyper_post`). The embedded row is copied to the lanes (`hyper_spread`)
and the head reads their sum (`hyper_collapse`); with `hyper_lanes` 0 all
of it is the identity and a block is traced as it always was.

A fourth kind of mixer keeps no keys at all: a STATE-SPACE layer
(`LayerSpec.attn = "ssm"` with an `SsmSpec`: Mamba-2's heads, head width,
state width, groups, convolution width and block length; granite-4.0-h's
36 of 40 layers). Its parameters are `w_in` (z | xBC) and `w_dt`, the depthwise
convolution `conv_w` / `conv_b`, `dt_bias`, `A_log`, `D` (float32 whatever
the weights' dtype: they make decays), the gated norm `ssm_norm` and
`w_out`; `ssm_mixer` is the one body, over a packed stream whose segments
carry their recurrent state in a pool of slots (`ops/kernels/ssm.py`), for
the serving tick and, on whole sequences from a zero state, for `block`.
Beside it a layer of heads may state its own softmax scale
(`LayerSpec.softmax_scale`) and have no rope at all (`rope=None`: no table
is made, nothing is rotated), and a config three scalars and a tied head
(`embed_scale` on the embedded row, `residual_scale` on every sub-block's
output through `residual`, `logit_divisor` under the logits,
`tie_embeddings`: `head_logits` multiplies with the embedding's transpose
and there is no `lm_head`); at their defaults every config traces as it
did.
"""
from __future__ import annotations

import dataclasses
import math
import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.kernels.sparse_index import index_scores, select_topk


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """One rotary embedding: plain rope of base `theta` over the leading
    `partial` share of each head (rotate-half inside that slice, the rest
    of the head passes through), or, with `yarn_factor` > 0, YaRN's
    frequencies (`rope_inv_freq`) with cos and sin times
    `attention_factor`, computed once, whatever the length."""
    theta: float = 10000.0
    partial: float = 1.0
    yarn_factor: float = 0.0
    yarn_original: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class IndexSpec:
    """A layer's learned sparse index (DeepSeek-V3.2's lightning indexer),
    over a latent cache or over the heads' own keys and values: `heads`
    index heads of `head_dim` (`wiq`), projected from the layer's query
    latent where it has one (a latent layer, dots3-note) and from its
    normed input where it has none (Keye-VL-2.0); one index key of `head_dim` a position from the layer's
    normed input through a LayerNorm with a bias (`wik`, `ik_norm`,
    `ik_bias`); a weight a head (`wiw`). Rope: a latent layer's on the
    leading `qk_rope_head_dim` values of each index head and of the key; a
    layer of heads' own keys turns the WHOLE index head, by a table of the
    layer's theta made for `head_dim` (`LlamaConfig.index_rope_width`). A
    query attends over the `topk` visible keys of largest score alone
    (`select_topk`)."""
    heads: int
    head_dim: int
    topk: int


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """The widths of one kind of latent layer (DeepSeek-V3's block): the
    queries through a `q_lora_rank` bottleneck with its own norm to heads
    of `qk_nope_head_dim` + `qk_rope_head_dim`, keys and values through a
    `kv_lora_rank` latent with its own norm beside ONE rope key of
    `qk_rope_head_dim` that all heads share; the latent expands to a
    head's `qk_nope_head_dim` key and `v_head_dim` value. A cache holds
    (latent | rope key) a position, `width` values. `softmax_scale` is the
    factor on the scores (0: head_dim ** -0.5; a YaRN model folds its
    mscale ** 2 into it). `q_scale` and `kv_scale` are fixed factors on
    the two normed latents (LongCat-Flash's `mla_scale_q_lora` /
    `mla_scale_kv_lora`; 1: none). `window` W > 0: a query sees the last W
    keys, its own among them. `index`: the layer's sparse index."""
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    softmax_scale: float = 0.0
    q_scale: float = 1.0
    kv_scale: float = 1.0
    window: int = 0
    index: Optional[IndexSpec] = None

    @property
    def width(self) -> int:
        """Values a position's cache row holds: latent | rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def score_scale(self) -> float:
        return self.softmax_scale or float(
            self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5


# a recurrent state in a serving pool: a sum carried over the whole sequence
SSM_STATE_DTYPE = jnp.float32


@dataclasses.dataclass(frozen=True)
class SsmSpec:
    """The widths of one kind of state-space layer (Mamba-2): `heads` heads
    of `head_dim` (the inner width is their product), a state of `d_state`
    values a head value, `groups` groups of heads that share B and C, a
    causal depthwise convolution of `d_conv` taps over xBC (inner width + 2
    groups x d_state values), and the block length `chunk` of the chunked
    scan."""
    heads: int
    head_dim: int
    d_state: int
    groups: int = 1
    d_conv: int = 4
    chunk: int = 256

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.groups * self.d_state

    @property
    def in_width(self) -> int:
        """Columns of `w_in`: z | xBC (in_proj's dt columns are `w_dt`)."""
        return self.inner + self.conv_dim


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """What one layer of a layer plan is: `attn` "full" (causal),
    "window" (causal over the last `LlamaConfig.sliding_window` keys, the
    query's own among them), "latent" (causal multi-head latent
    attention at the widths of `latent`, which also says whether the layer
    has a window or a sparse index; a latent spec without one takes the
    config's `q_lora_rank` and its sibling fields, `__post_init__` writes
    them here) or "ssm" (a state-space mixer at the widths of `ssm`: no
    heads of attention, no rope, no keys), its query heads, its rope (None:
    the layer rotates nothing, and no table is made for it), the factor on
    a layer of heads' scores `softmax_scale` (0: head_dim ** -0.5), and
    `ffn` "dense" (SwiGLU of
    `dense_intermediate_size`) or "sparse" (the routed experts of
    `intermediate_size`, with the shared expert where the config has
    one). `index`: the sparse index of a layer of heads' own keys and
    values (a latent layer's is its `LatentSpec.index`; `sparse_index` is
    either). Layers with equal specs are one kind."""
    attn: str = "full"
    heads: int = 0
    rope: Optional[RopeSpec] = RopeSpec()
    ffn: str = "dense"
    latent: Optional[LatentSpec] = None
    index: Optional[IndexSpec] = None
    ssm: Optional[SsmSpec] = None
    softmax_scale: float = 0.0

    @property
    def sparse_index(self) -> Optional[IndexSpec]:
        """The layer's sparse index, whichever kind of cache it stands
        over; None: the layer attends over every key it sees."""
        return self.latent.index if self.latent is not None else self.index


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
    # MoE: 0 = dense MLP. When >0, every layer's MLP is a top-k gated MoE
    # (with a `layer_plan`: every layer whose spec says "sparse").
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
    # A derived width remembers what it was derived from
    # (`head_dim_derived_from`), so `dataclasses.replace(cfg,
    # hidden_size=..)` derives it anew instead of keeping a stale one; a
    # width that was given stays what was given
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
    # (hidden_size, num_heads) a derived `head_dim` was derived from; ()
    # for one that was given. Set by `__post_init__`, never by a caller
    head_dim_derived_from: Tuple[int, ...] = ()
    # a stack that is not uniform: one LayerSpec a layer (len ==
    # num_layers), () = every layer is the one kind the fields above
    # describe. With a plan `num_heads` is not read (a spec has its own
    # heads), `rope_theta` neither; `sliding_window` is the span of a
    # "window" layer, `dense_intermediate_size` the width of a "dense"
    # layer's FFN beside experts of `intermediate_size`
    layer_plan: Tuple[LayerSpec, ...] = ()
    sliding_window: int = 0
    dense_intermediate_size: int = 0
    # one more SwiGLU expert of this width that every row passes through,
    # beside the routed ones and ungated (0 = none)
    shared_expert_width: int = 0
    # the router's scores, "softmax" or "sigmoid" over all experts in
    # float32, and a factor on the k weights after their renormalisation
    router_score: str = "softmax"
    router_scale: float = 1.0
    # a sigmoid gate on each head's attention output, from the layer's
    # normed input through `wg` [d, heads]
    attn_gate: bool = False
    # the widths of a plan's latent layers whose spec states none
    # (`LayerSpec.latent`): a model with one kind of latent layer may give
    # them here, and `__post_init__` writes them into its plan as a
    # `LatentSpec`, which is what every function reads
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    softmax_scale: float = 0.0
    # a learned bias [num_experts] added to the router's scores for the
    # CHOICE of the k experts alone; their weights are the scores without
    # it (`topk_method: noaux_tc`)
    router_bias: bool = False
    # one chip's share of the routed experts: (first, count) = this
    # config HOLDS experts first .. first + count - 1 of `num_experts`. The
    # router runs over all of them; a (row, expert) pair whose expert is
    # not held is computed by no one here and adds nothing (its chip's
    # part of the sum). () = every expert is held
    experts_held: Tuple[int, ...] = ()
    # a uniform config's learned sparse index over its heads' own keys and
    # values (every layer has it); a plan's layers
    # carry their own (`LayerSpec.index`, `LatentSpec.index`)
    index: Optional[IndexSpec] = None
    # the residual stream as n lanes mixed by manifold-constrained
    # hyper-connections (`residual`): 0 = the plain sum x + F(norm(x)).
    # `hyper_sinkhorn_iters` rounds of column-then-row normalisation with
    # `hyper_eps` in every denominator make the lanes' mix doubly
    # stochastic; its logits are clipped to `hyper_clamp` before the exp
    hyper_lanes: int = 0
    hyper_sinkhorn_iters: int = 20
    hyper_eps: float = 1e-6
    hyper_clamp: Tuple[float, float] = (-30.0, 30.0)
    # three scalars and a tied head (Granite's `embedding_multiplier`,
    # `residual_multiplier`, `logits_scaling`, `tie_word_embeddings`): the
    # embedded row times `embed_scale`, every sub-block's output times
    # `residual_scale` before it joins the stream (`residual`), the logits
    # over `logit_divisor`, and the head the embedding's transpose (no
    # `lm_head` among the parameters). At 1, 1, 1, False nothing is traced
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_divisor: float = 1.0
    tie_embeddings: bool = False

    def __post_init__(self):
        here = (self.hidden_size, self.num_heads)
        was = self.head_dim_derived_from
        if not self.head_dim or (was and self.head_dim == was[0] // was[1]):
            object.__setattr__(self, "head_dim", here[0] // here[1])
            object.__setattr__(self, "head_dim_derived_from", here)
        else:   # given, here or over a derived one by `replace`
            object.__setattr__(self, "head_dim_derived_from", ())
        if self.layer_plan:
            if self.index is not None:
                raise ValueError(
                    "a layer plan states its layers' indexes itself "
                    "(LayerSpec.index, LatentSpec.index)")
            if len(self.layer_plan) != self.num_layers:
                raise ValueError(
                    f"layer_plan has {len(self.layer_plan)} layers, "
                    f"num_layers is {self.num_layers}")
            object.__setattr__(self, "layer_plan", tuple(
                self._with_widths(s) for s in self.layer_plan))
            for spec in self.layer_plan:
                if (spec.attn not in ("full", "window", "latent", "ssm")
                        or spec.ffn not in ("dense", "sparse")
                        or spec.heads % self.num_kv_heads
                        or (spec.attn == "ssm") != (spec.ssm is not None)):
                    raise ValueError(f"layer_plan: bad layer {spec}")
                if spec.ssm is not None and (
                        spec.ssm.heads % spec.ssm.groups
                        or spec.ssm.d_conv < 2 or spec.index is not None):
                    raise ValueError(f"layer_plan: bad state-space layer "
                                     f"{spec}")
                if spec.attn != "latent" and spec.latent is not None:
                    raise ValueError(f"layer_plan: {spec.attn} layer with "
                                     "latent widths")
                if spec.attn == "window" and self.sliding_window < 1:
                    raise ValueError("a window layer needs sliding_window")
                if spec.ffn == "sparse" and not self.num_experts:
                    raise ValueError("a sparse layer needs num_experts")
            if self.block_length or self.qk_norm:
                raise NotImplementedError(
                    "a layer plan with block_length or qk_norm: no model "
                    "served has both, and neither was judged under a plan")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"router_score={self.router_score!r}")
        if self.experts_held:
            first, count = self.experts_held
            if not (0 <= first and 0 < count
                    and first + count <= self.num_experts):
                raise ValueError(
                    f"experts_held={self.experts_held}: (first, count) "
                    f"inside num_experts={self.num_experts}")
        for spec in self.kinds:
            ix = spec.sparse_index
            if ix is not None and spec.latent is None and (
                    spec.attn != "full" or self.block_length):
                raise NotImplementedError(
                    "a sparse index stands over a latent layer or, over "
                    "heads' own keys and values, over a causal "
                    f"full-attention layer; no model served has {spec}")
        if self.hyper_lanes and any(s.attn == "ssm" for s in self.layer_plan):
            raise NotImplementedError(
                "state-space layers under hyper-connections: no model "
                "served has both")
        if self.hyper_lanes and (self.hyper_lanes < 2 or self.block_length
                                 or self.hyper_sinkhorn_iters < 1):
            raise NotImplementedError(
                f"hyper-connections (hyper_lanes={self.hyper_lanes}) take "
                "two lanes or more and a Sinkhorn iteration or more, and "
                "were never judged under block diffusion (block_length)")
        latent = {s.attn == "latent" for s in self.layer_plan}
        if True in latent and (False in latent or self.num_kv_heads != 1):
            raise NotImplementedError(
                "latent layers beside heads' own keys and values, or with "
                "num_kv_heads != 1 (the latent is one key row for every "
                "head): no model served has them")

    def _with_widths(self, spec: LayerSpec) -> LayerSpec:
        """A latent spec that states no widths takes the config's."""
        if spec.attn != "latent" or spec.latent is not None:
            return spec
        if not (self.kv_lora_rank and self.q_lora_rank
                and self.qk_nope_head_dim and self.qk_rope_head_dim
                and self.v_head_dim):
            raise ValueError(
                "a latent layer needs its widths: LayerSpec.latent (a "
                "LatentSpec), or the config's q_lora_rank, kv_lora_rank, "
                "qk_nope_head_dim, qk_rope_head_dim and v_head_dim for a "
                "plan with one kind of them")
        return dataclasses.replace(spec, latent=LatentSpec(
            self.q_lora_rank, self.kv_lora_rank, self.qk_nope_head_dim,
            self.qk_rope_head_dim, self.v_head_dim, self.softmax_scale))

    # -- the held share of the routed experts ------------------------------
    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the routed experts this config holds."""
        return tuple(self.experts_held) or (0, self.num_experts)

    def rope_width(self, spec: Optional[LayerSpec] = None) -> int:
        """The width a layer's rope table is made for (of which a
        `RopeSpec.partial` share is rotated): a head, or a latent layer's
        rope slice."""
        if spec is not None and spec.latent is not None:
            return spec.latent.qk_rope_head_dim
        return self.head_dim

    def index_rope_width(self, spec: LayerSpec) -> int:
        """The width the rope table of a layer's sparse index is made for:
        a latent layer's rope slice (the layer's own table turns the
        leading values of each index head), the whole index head on a
        layer of heads' own keys (a table of its own, the layer's theta)."""
        if spec.latent is not None:
            return spec.latent.qk_rope_head_dim
        return spec.index.head_dim

    def one_latent(self) -> LatentSpec:
        """The widths of a plan with ONE kind of latent layer; a plan with
        two has no answer, and a caller reads a layer's own spec."""
        kinds = {s.latent for s in self.layer_plan if s.latent is not None}
        if len(kinds) != 1:
            raise ValueError(
                f"the plan has {len(kinds)} kinds of latent layer: read "
                "LayerSpec.latent")
        return next(iter(kinds))

    # what the accepted benchmark's driver of a one-kind latent model
    # still asks of the whole config (benchmark/drivers/
    # closed_loop_serve_latent.py, benchmark/tests/test_latent.py); the
    # program itself reads a layer's spec
    @property
    def rope_dim(self) -> int:
        return self.rope_width(self.layers[0])

    @property
    def score_scale(self) -> float:
        return self.one_latent().score_scale

    # -- the layers -------------------------------------------------------
    @property
    def layers(self) -> Tuple[LayerSpec, ...]:
        """One LayerSpec a layer, of every config: the plan as written, or
        `num_layers` times the one kind the config's own fields describe
        (its heads, causal attention over the heads' own keys, its one
        rope, a routed FFN where it has experts). What asks "what are this
        config's layers" reads this; `layer_plan` stays what a user wrote
        (() = uniform: `params["blocks"]` one dict), and what
        `__post_init__` refuses of a written plan is not refused of this."""
        return self.layer_plan or (LayerSpec(
            "full", self.num_heads, RopeSpec(theta=self.rope_theta),
            "sparse" if self.num_experts else "dense",
            index=self.index),) * self.num_layers

    @property
    def kinds(self) -> Tuple[LayerSpec, ...]:
        """The distinct specs of the layers, in order of first occurrence
        (a uniform config: its one)."""
        return tuple(dict.fromkeys(self.layers))

    @property
    def kind_of_layer(self) -> Tuple[int, ...]:
        kinds = self.kinds
        return tuple(kinds.index(s) for s in self.layers)

    def _layer_params(self, spec: LayerSpec) -> Tuple[int, int]:
        """(all, active a token) matmul and norm parameters of one layer.
        The routed experts count whole (`num_experts`), whatever share of
        them a config holds: this is the model's size, not a chip's."""
        heads, ffn, ls = spec.heads, spec.ffn, spec.latent
        d, hd = self.hidden_size, self.head_dim
        norms = 2 * d
        if spec.ssm is not None:
            sm = spec.ssm
            attn = d * (sm.in_width + sm.heads) + sm.inner * d
            # the convolution and its bias, dt_bias, A_log, D, the gated norm
            norms += (sm.d_conv + 1) * sm.conv_dim + 3 * sm.heads + sm.inner
        elif ls is not None:
            r, c = ls.q_lora_rank, ls.kv_lora_rank
            nope, rope, v = (ls.qk_nope_head_dim, ls.qk_rope_head_dim,
                             ls.v_head_dim)
            attn = (d * r + r * heads * (nope + rope) + d * (c + rope)
                    + c * heads * (nope + v) + heads * v * d)
            norms += r + c
        else:
            attn = 2 * d * heads * hd + 2 * d * self.num_kv_heads * hd
        ix = spec.sparse_index
        if ix is not None:
            attn += ((r if spec.latent else d) * ix.heads
                     * ix.head_dim + d * ix.head_dim + d * ix.heads)
            norms += 2 * ix.head_dim
        if self.attn_gate:
            attn += d * heads
        if self.qk_norm:
            norms += (2 * hd if self.qk_norm_per_head
                      else (heads + self.num_kv_heads) * hd)
        if ffn == "sparse":
            one = 3 * d * self.intermediate_size
            fixed = d * self.num_experts + 3 * d * self.shared_expert_width
            if self.router_bias:
                norms += self.num_experts
            mlp = self.num_experts * one + fixed
            active = min(self.top_k, self.num_experts) * one + fixed
        else:
            mlp = active = 3 * d * (self.dense_intermediate_size
                                    if self.layer_plan
                                    else self.intermediate_size)
        n = self.hyper_lanes
        if n:   # a sub-block's phi [n d, n^2 + 2n]; its b and 3 alphas
            attn += 2 * n * d * (n * n + 2 * n)
            norms += 2 * (n * n + 2 * n + 3)
        return attn + mlp + norms, attn + active

    def num_params(self) -> int:
        d, v = self.hidden_size, self.vocab_size
        return (v * d + sum(self._layer_params(s)[0] for s in self.layers)
                + d + (0 if self.tie_embeddings else d * v))

    def num_active_params(self) -> int:
        """Matmul parameters one token is multiplied with: a token passes
        through `top_k` of the experts, the router and the shared expert;
        the head counts, the embedding (a row lookup) does not."""
        return (sum(self._layer_params(s)[1] for s in self.layers)
                + self.hidden_size * self.vocab_size)

    def flops_per_token(self) -> int:
        """Approximate training FLOPs/token (fwd+bwd ≈ 6·N_active): a
        token passes through `top_k` of the experts and the router."""
        return 6 * (self.num_active_params()
                    + self.hidden_size * self.vocab_size)


def require_uniform(cfg: LlamaConfig, what: str) -> None:
    """Raise for a config with a layer plan, a held share of its experts
    or hyper-connections, in the name of a block body that runs one
    uniform stack of whole layers (`params["blocks"]` one dict, every
    expert) around one residual stream and would compute another model
    under the config's name. The paths that take the first two:
    `llama.forward`, `PagedServingEngine`, and the trainer
    (`distributed.hybrid`, which names what it refuses itself,
    `hybrid.require_trainable`); the lanes: the first two of those."""
    if cfg.layer_plan:
        raise NotImplementedError(
            f"{what} runs one uniform stack of layers and does not take a "
            "layer plan (LlamaConfig.layer_plan"
            + (", here with state-space layers"
               if any(s.attn == "ssm" for s in cfg.layer_plan) else "")
            + "); `llama.forward`, `PagedServingEngine` and "
            "`distributed.hybrid` (state-space layers aside) do")
    if (cfg.embed_scale != 1 or cfg.residual_scale != 1
            or cfg.logit_divisor != 1 or cfg.tie_embeddings):
        raise NotImplementedError(
            f"{what} computes no embed_scale, residual_scale, "
            "logit_divisor or tied head; `llama.forward` and "
            "`PagedServingEngine` do")
    if cfg.experts_held:
        raise NotImplementedError(
            f"{what} holds every routed expert and does not take a chip's "
            "share of them (LlamaConfig.experts_held); `llama.forward`, "
            "`PagedServingEngine` and `distributed.hybrid` (on dp = 1) do")
    if cfg.hyper_lanes:
        raise NotImplementedError(
            f"{what} adds a sub-block's output to one residual stream and "
            "does not take hyper-connections (LlamaConfig.hyper_lanes); "
            "`llama.forward` and `PagedServingEngine` do")


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


def _normal(key: jax.Array, shape, dtype, scale: float = 0.02):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _init_blocks(cfg: LlamaConfig, key: jax.Array, L: int, nh: int,
                 ffn_kind: str, ls: Optional[LatentSpec] = None,
                 index: Optional[IndexSpec] = None,
                 sm: Optional[SsmSpec] = None) -> Dict[str, jax.Array]:
    """One stack of `L` layers with `nh` query heads, latent attention at
    the widths `ls` (None: heads' own keys and values, under the sparse
    index `index` where the layer has one) and an FFN of
    `ffn_kind` ("dense" | "sparse"); the keys are split as they always
    were, so a uniform config draws the weights it drew. A config that
    holds a share of its experts (`experts_held`) draws the whole router
    and the held experts' matrices alone. `sm`: a state-space mixer at
    those widths in place of attention."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    hd, nkv = cfg.head_dim, cfg.num_kv_heads
    pt = cfg.param_dtype
    keys = jax.random.split(key, 10)
    normal = functools.partial(_normal, dtype=pt)

    if sm is not None:
        # Drawn so that the state MATTERS (Mamba-2's own initialisation):
        # A in U(1, 16) and delta's bias the inverse softplus of
        # exp(U(log 0.001, log 0.1)), so a head forgets over tens to
        # thousands of tokens; in_proj's dt columns (`w_dt`, a matrix of
        # its own: z | xBC is 66 whole lanes of 128 wide at Granite's
        # widths, with dt's 64 behind them 66.5, and the chip's compiler
        # then re-lays the whole stack out, 1.25 GB a tick) at 1 / 32 of
        # the weights' 0.02, or a projection of std 0.02 sqrt(d) would
        # swamp that bias and every head would forget within two tokens;
        # D = 1; the
        # convolution's taps U(-1/2, 1/2). dt_bias, A_log and D are float32
        # whatever the weights' dtype: they make decays
        ks = jax.random.split(keys[0], 5)
        uniform = lambda k, shape, lo, hi: jax.random.uniform(
            k, shape, jnp.float32, lo, hi)
        dt = jnp.exp(uniform(ks[2], (L, sm.heads), math.log(1e-3),
                             math.log(1e-1)))
        blocks = {
            "w_in": normal(ks[0], (L, d, sm.in_width)),
            "w_dt": normal(ks[1], (L, d, sm.heads), scale=0.02 / 32),
            "conv_w": uniform(keys[1], (L, sm.conv_dim, sm.d_conv),
                              -0.5, 0.5).astype(pt),
            "conv_b": normal(ks[4], (L, sm.conv_dim)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(uniform(ks[3], (L, sm.heads), 1.0, 16.0)),
            "D": jnp.ones((L, sm.heads), jnp.float32),
            "ssm_norm": jnp.ones((L, sm.inner), pt),
            "w_out": normal(keys[3], (L, sm.inner, d)),
            "attn_norm": jnp.ones((L, d), pt),
            "mlp_norm": jnp.ones((L, d), pt),
        }
        ix = None
    elif ls is not None:
        r, c = ls.q_lora_rank, ls.kv_lora_rank
        nope, rope, vd = (ls.qk_nope_head_dim, ls.qk_rope_head_dim,
                          ls.v_head_dim)
        ka = jax.random.split(keys[0], 2)
        kb = jax.random.split(keys[1], 2)
        blocks = {
            "wqa": normal(ka[0], (L, d, r)),
            "wqb": normal(ka[1], (L, r, nh * (nope + rope))),
            "wkva": normal(kb[0], (L, d, c + rope)),
            "wkvb": normal(kb[1], (L, c, nh * (nope + vd))),
            "wo": normal(keys[3], (L, nh * vd, d)),
            "qa_norm": jnp.ones((L, r), pt),
            "kva_norm": jnp.ones((L, c), pt),
            "attn_norm": jnp.ones((L, d), pt),
            "mlp_norm": jnp.ones((L, d), pt),
        }
        ix, ki = ls.index, jax.random.split(keys[2], 3)
    else:
        blocks = {
            "wq": normal(keys[0], (L, d, nh * hd)),
            "wk": normal(keys[1], (L, d, nkv * hd)),
            "wv": normal(keys[2], (L, d, nkv * hd)),
            "wo": normal(keys[3], (L, nh * hd, d)),
            "attn_norm": jnp.ones((L, d), pt),
            "mlp_norm": jnp.ones((L, d), pt),
        }
        ix, ki = index, jax.random.split(jax.random.fold_in(keys[3], 1), 3)
    if ix is not None:
        blocks.update(
            wiq=normal(ki[0], (L, d if ls is None else r,
                               ix.heads * ix.head_dim)),
            wik=normal(ki[1], (L, d, ix.head_dim)),
            wiw=normal(ki[2], (L, d, ix.heads)),
            ik_norm=jnp.ones((L, ix.head_dim), pt),
            ik_bias=jnp.zeros((L, ix.head_dim), pt))
    if cfg.attn_gate:
        blocks["wg"] = normal(keys[8], (L, d, nh))
    if cfg.qk_norm and cfg.qk_norm_per_head:
        blocks["q_norm"] = jnp.ones((L, hd), pt)
        blocks["k_norm"] = jnp.ones((L, hd), pt)
    elif cfg.qk_norm:
        blocks["q_norm"] = jnp.ones((L, nh * hd), pt)
        blocks["k_norm"] = jnp.ones((L, nkv * hd), pt)
    if ffn_kind == "sparse":
        e = cfg.held[1]
        blocks["router"] = normal(keys[4], (L, d, cfg.num_experts))
        if cfg.router_bias:
            # float32 whatever the weights' dtype: it decides a choice
            blocks["router_bias"] = _normal(
                jax.random.fold_in(keys[4], 1), (L, cfg.num_experts),
                jnp.float32)
        blocks["w1"] = normal(keys[5], (L, e, d, f))
        blocks["w3"] = normal(keys[6], (L, e, d, f))
        blocks["w2"] = normal(keys[7], (L, e, f, d))
        if cfg.shared_expert_width:
            fs = cfg.shared_expert_width
            ks = jax.random.split(keys[9], 3)
            blocks["ws1"] = normal(ks[0], (L, d, fs))
            blocks["ws3"] = normal(ks[1], (L, d, fs))
            blocks["ws2"] = normal(ks[2], (L, fs, d))
    else:
        if cfg.layer_plan:
            f = cfg.dense_intermediate_size
        blocks["w1"] = normal(keys[5], (L, d, f))
        blocks["w3"] = normal(keys[6], (L, d, f))
        blocks["w2"] = normal(keys[7], (L, f, d))
    n = cfg.hyper_lanes
    if n:
        # A sub-block's mixing weights (`hyper_coeff`). phi lies
        # TRANSPOSED, [n^2 + 2n, n d]: 24 rows pad to 32 in bf16 tiles
        # where 24 columns would pad to 128 lanes. b and alpha are float32
        # whatever the weights' dtype (they make coefficients, which are
        # float32), and drawn so that both terms matter: phi at 2.4 /
        # sqrt(n d) (0.02 at Xing4.0's 14,336) gives u = v phi, over n d
        # values of unit mean square, a standard deviation of 2.4 at any
        # width, alpha near 0.25 then puts alpha u near 0.6, and the lanes'
        # mix leans to the identity (2 on the diagonal of b_res, as
        # hyper-connections start from it) without being it
        kh = jax.random.split(jax.random.fold_in(key, 11), 6)
        k = n * n + 2 * n
        lean = jnp.concatenate([
            jnp.zeros((2 * n,), jnp.float32),
            2.0 * jnp.eye(n, dtype=jnp.float32).reshape(-1)])
        for which, (kp, kb, ka) in (("attn", kh[:3]), ("mlp", kh[3:])):
            blocks[f"hc_{which}_phi"] = normal(kp, (L, k, n * d),
                                               scale=2.4 * (n * d) ** -0.5)
            blocks[f"hc_{which}_b"] = lean + _normal(
                kb, (L, k), jnp.float32, 0.5)
            blocks[f"hc_{which}_alpha"] = 0.25 + _normal(
                ka, (L, 3), jnp.float32, 0.05)
    return blocks


def init_params(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """Build the parameter pytree. Block params are stacked on a leading
    num_layers axis so the forward is a lax.scan and the pipeline engine can
    reshape to [pp, layers_per_stage, ...]. With a layer plan
    `params["blocks"]` is a tuple of such stacks, one a kind
    (`cfg.kinds`), each as deep as the plan has layers of that kind."""
    d, v = cfg.hidden_size, cfg.vocab_size
    pt = cfg.param_dtype
    keys = jax.random.split(key, 10)
    normal = functools.partial(_normal, dtype=pt)

    if cfg.layer_plan:
        kind_of = cfg.kind_of_layer
        blocks = tuple(
            _init_blocks(cfg, jax.random.fold_in(key, 1 + k),
                         kind_of.count(k), spec.heads, spec.ffn, spec.latent,
                         spec.index, spec.ssm)
            for k, spec in enumerate(cfg.kinds))
    else:
        blocks = _init_blocks(cfg, key, cfg.num_layers, cfg.num_heads,
                              "sparse" if cfg.num_experts else "dense",
                              index=cfg.index)
    params = {
        # (under `embed_scale` the row enters the stream at the weights'
        # 0.02 as every other config's does; drawn at 0.02 a tied head
        # would find the input token's own embedding, `embed_scale`-fold in
        # the stream, five sigma over every other logit, and echo it)
        "embed": normal(keys[8], (v, d), scale=0.02 / cfg.embed_scale),
        "blocks": blocks,
        "final_norm": jnp.ones((d,), pt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(keys[9], (d, v))
    return params


def kind_stacks(blocks) -> Tuple[Dict[str, jax.Array], ...]:
    """`params["blocks"]` as one stack a kind (`cfg.kinds`): a written
    plan's tuple, a uniform config's one dict as a tuple of one."""
    return (blocks,) if isinstance(blocks, dict) else tuple(blocks)


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    x32 = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * w.astype(jnp.float32)).astype(x.dtype)


def rope_cos_sin(positions: jax.Array, head_dim: int, theta: float):
    """positions [T] int → (cos, sin) [T, head_dim/2] in f32."""
    inv_freq = rope_inv_freq(head_dim, RopeSpec(theta=theta))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def rope_inv_freq(head_dim: int, spec: RopeSpec) -> jax.Array:
    """The inverse frequencies [rot / 2] of `spec` over the rot =
    `spec.partial` * head_dim leading values of a head. YaRN
    (`yarn_factor` s > 0, the `yarn` rule of the `rope_parameters`
    convention): with d(b) = rot * ln(original / (2 pi b)) / (2 ln theta),
    low = max(floor(d(beta_fast)), 0), high = min(ceil(d(beta_slow)),
    rot - 1) and ramp_i = clip((i - low) / (high - low), 0, 1), frequency
    i is f_i / s where the ramp is 1 (long wavelengths: interpolated), f_i
    where it is 0 (short ones: kept), and a blend between."""
    rot = int(head_dim * spec.partial)
    f = 1.0 / (spec.theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    if not spec.yarn_factor:
        return f

    def dim_of(beta):
        return (rot * math.log(spec.yarn_original / (2 * math.pi * beta))
                / (2 * math.log(spec.theta)))

    low = max(math.floor(dim_of(spec.yarn_beta_fast)), 0)
    high = min(math.ceil(dim_of(spec.yarn_beta_slow)), rot - 1)
    ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return f / spec.yarn_factor * ramp + f * (1.0 - ramp)


def rope_table(positions: jax.Array, head_dim: int, spec: RopeSpec):
    """positions [T] int → (cos, sin) [T, rot / 2] in f32 for `spec`
    (`rope_inv_freq`), times its `attention_factor`."""
    angles = (positions.astype(jnp.float32)[:, None]
              * rope_inv_freq(head_dim, spec)[None, :])
    return (jnp.cos(angles) * spec.attention_factor,
            jnp.sin(angles) * spec.attention_factor)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [B, T, H, hd]; rotate-half convention, f32 math. Tables narrower
    than hd / 2 rotate the leading 2 * width values of each head (rotate-half
    inside them) and pass the rest through."""
    rot = 2 * cos.shape[-1]
    if rot < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, impl: str = "auto",
              block_length: int = 0, window: int = 0,
              select: Optional[jax.Array] = None) -> jax.Array:
    """Causal MHA/GQA. q [B,T,H,hd], k/v [B,T,KV,hd] → [B,T,H,hd].

    impl: 'auto' uses the Pallas flash kernel on TPU when available, else the
    XLA einsum path (which XLA fuses well on its own).

    block_length Bd > 0: the block-causal mask of generation by diffusion
    over blocks, position i sees j iff j // Bd <= i // Bd (full inside a
    block, causal across blocks), on the XLA path alone: the flash kernel
    has no such mask, and impl='flash' with it raises.

    window W > 0: position i sees j iff i - W < j <= i (W keys, the
    query's own among them). The flash kernel has the window, forward and
    backward (key blocks wholly behind it are neither fetched nor
    multiplied), so 'auto' and 'flash' take it as they take the causal
    mask; the XLA path, with its [T, T] scores, remains the CPU's.
    """
    if block_length and impl == "flash":
        raise ValueError("the flash kernel has no block-causal mask: "
                         "block_length > 0 takes impl='auto' or 'xla'")
    if block_length:
        impl = "xla"
    if impl == "flash":
        # explicit request: no silent fallback — unsupported shapes raise
        from ..ops.pallas import flash_attention as _fa

        return _fa.flash_attention(q, k, v, causal=True, window=window)
    if impl == "auto":
        from ..ops.pallas import flash_attention as _fa

        if (_fa.available() and q.shape[1] == k.shape[1]
                and _fa.supported(q.shape, k.shape)):
            return _fa.flash_attention(q, k, v, causal=True, window=window)
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
    if window:
        pos = jnp.arange(T)
        mask = mask & (pos[None, :] > pos[:, None] - window)
    mask = (mask[None, None] if select is None
            else (mask[None] & select)[:, None])
    scores = jnp.where(mask, scores, -1e30)
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
    scores (`router_score`: a softmax, or a sigmoid of each logit) run in
    float32 over ALL experts; the k weights are renormalised to sum to one
    only where the model's config says so (`norm_topk_prob`), and then
    take the config's `router_scale`. With `router_bias` the k experts are
    those of the largest score + `lp["router_bias"]`, and their weights
    the scores themselves."""
    logits = h.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
    if cfg.router_score == "sigmoid":
        gate = jax.nn.sigmoid(logits)
    else:
        gate = jax.nn.softmax(logits, axis=-1)
    if cfg.router_bias:
        _, e = lax.top_k(gate + lp["router_bias"].astype(jnp.float32),
                         cfg.top_k)
        w = jnp.take_along_axis(gate, e, axis=-1)
    else:
        w, e = lax.top_k(gate, cfg.top_k)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    if cfg.router_scale != 1.0:
        w = w * cfg.router_scale
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
        comb = jnp.sum(jax.nn.one_hot(e, cfg.held[1], dtype=w.dtype)
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
# A config that holds a share of its experts gives the pairs on them this
# many times the places an even router would fill (`held_pair_slots`). The
# count on 12 of 384 experts is a sum of T*k rare draws, so it scatters
# like a Poisson count about its mean and four times the mean is out of
# its reach (256 expected in a chunk tick of 1,024 rows: 1,024 places are
# 48 standard deviations off); what the factor leaves room for is a router
# that prefers the held experts, which PR 41 saw carry 2.5-3.9 % of the
# pairs by seed against the even 3.125 %, and a trained one's hot experts.
# Under a share the places never pass HALF of all pairs either
# (`held_pair_slots`): the compact form exists to move well under all of
# them, and at a share of a quarter (Mellum2's 16 of 64) four times the
# even count would be every place, the whole form under another name. Past
# the places nothing is dropped: the launch takes the whole form, which
# then costs at most twice what its held pairs need.
HELD_ROOM = 4


def held_pair_slots(rows: int, cfg: LlamaConfig) -> int:
    """The places `_experts_sorted` gives the (row, expert) pairs of a
    launch of `rows` rows: all rows * top_k of them where every expert is
    held; under a held share `HELD_ROOM` times what an even router sends
    to the held experts (rows * top_k * count / num_experts, rounded up)
    or half of all pairs, whichever is fewer, in whole tiles of `GMM_ROWS`,
    and never more than all. A function of the launch's rows, `top_k` and
    the config's share alone."""
    pairs = rows * cfg.top_k
    if not cfg.experts_held:
        return pairs
    even = -(-pairs * cfg.held[1] // cfg.num_experts)
    slots = min(HELD_ROOM * even, pairs // 2)
    return min(slots + -slots % GMM_ROWS, pairs)


def _fit(n: int, cap: int) -> int:
    """The largest whole-lane (multiple of 128) divisor of n that is no
    larger than cap; n itself where it is within the cap or has none."""
    if n <= cap:
        return n
    return max((t for t in range(128, cap + 1, 128) if n % t == 0),
               default=n)


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
    compiler refuses.

    The backward pass is the two products megablox's own vjp makes (the
    rows' gradient g w^T through `gmm` with the weights transposed in the
    kernel, the weights' gradient xs^T g a group through `tgmm`), each
    under a tiling of its own: megablox hands both the forward's, and at
    an expert of 2304 x 896, whose sides are no multiple of 1024 and so
    whole tiles, `tgmm`'s float32 accumulator and its double-buffered
    output are 17.3 MB of the chip's 16 MB of scoped VMEM (the v5e's
    compiler refuses it). `tgmm` gets output tiles of at most 1024 x 1024
    values in whole-lane divisors of K and N (`_fit`)."""
    with jax.enable_x64(False):
        return _gmm(xs, w, group_sizes, group_offset)


def _grouped_matmul_fwd(xs, w, group_sizes, group_offset):
    with jax.enable_x64(False):
        return (_gmm(xs, w, group_sizes, group_offset),
                (xs, w, group_sizes, group_offset))


def _grouped_matmul_bwd(res, g):
    # the kernels themselves (the package's own name `gmm` is its custom_vjp)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    from ..ops.pallas import flash_attention as _fa

    xs, w, group_sizes, group_offset = res
    K, N = w.shape[-2:]
    interpret = not _fa.available()
    with jax.enable_x64(False):
        dxs = gmm(
            g, w, group_sizes, xs.dtype,
            (GMM_ROWS, _fit(N, 1024), _fit(K, 1024)), group_offset,
            transpose_rhs=True, interpret=interpret)
        tk = _fit(K, 1024)
        dw = tgmm(
            xs.swapaxes(0, 1), g, group_sizes, w.dtype,
            (GMM_ROWS, tk, _fit(N, 1024 * 1024 // tk)), group_offset,
            w.shape[0], interpret=interpret)
    return dxs, dw, None, None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


# The sorted order is a partial permutation: place p < sum(load) holds
# exactly one (row, choice) pair. So the way from rows to places (DISPATCH:
# xs[p] = h[row of p], a gather of C rows) and the way back (COMBINE: y[t]
# = sum_j w[t, j] * ys[place of (t, j)], a gather of a row's k places, or
# the [T, C] product where C is few) are each other's transposes, and the
# derivative of either is the other: no scatter-add of d-wide rows in
# either direction (JAX's transpose of a `jnp.take` is one; on the v5e
# PR 42 read 0.8 us a row of 7,168 for it, and PR 47's step spent 231 ms
# of 672 under the two scopes).
#
# The two rates of the way back, on the TPU v5e (PR 48's chip runs, call 1,
# bf16 rows, host clock around a jitted call less 0.5 ms of launch; PERF.md
# section 6). The product: 83.2 ms at T 16,384, C 65,536, d 2,304
# (Mellum2's launch) and 3.68 ms at T 2,048, C 8,192, d 5,120 (dots3's
# chunk) = 3.3e-14 and 3.7e-14 s a (row, place) and lane of d. The gather:
# 7.91 ms at Mellum2's launch (a quarter of its T * k pairs held) and 1.22
# ms at dots3's chunk (an eighth) = 2.5e-11 and 0.9e-11 s a (row, choice)
# and lane; the slower one stands here, the one read at the share the
# places are made for. Both grow with T * d, so the crossing is a count of
# places a choice: 700, i.e. 5,600 places at k 8 (`combine_is_a_product`).
_PRODUCT_PLACE_S = 3.5e-14
_GATHER_PAIR_S = 2.45e-11


def combine_is_a_product(places: int, rows: int, top_k: int) -> bool:
    """Whether C = `places` places go back into the launch's `rows` rows as
    the [T, C] float32 product (C few: a row meets every place once, on the
    MXU) or as the gather of a row's k places: the product's T * C * d
    against the gather's T * k * d at the two measured rates, so a count of
    places a choice, from the launch's shapes alone. Where every pair has
    its place (C = T * k: every expert held, or the fallback of a share) it
    is the gather whatever the count: the un-sort then moves exactly the
    rows that are summed, and a serving tick of a few rows is bound by its
    launches either way (the rates were read at 2,048 and 16,384 rows)."""
    return (places < rows * top_k
            and places * _PRODUCT_PLACE_S < top_k * _GATHER_PAIR_S)


def _to_places(x, pair, n, k: int):
    """Rows to places: out[p] = x[pair[p] // k] for the places p < n, zero
    behind them (x [T, d]; pair [C] i32, the pair t * k + j a place holds)."""
    keep = (jnp.arange(pair.shape[0]) < n)[:, None]
    return jnp.where(keep, jnp.take(x, pair // k, axis=0), 0)


def _to_rows(ys, w, pair, place, n):
    """Places to rows, in float32: out[t] = sum_j w[t, j] * ys[place[t, j]]
    over the pairs (t, j) whose place is under n (w None: unit weights); a
    row with none gets an exact zero. ys [C, d], whose rows from n on are
    whatever the kernel left there (selected away, never multiplied by
    zero); pair [C] i32 and place [T, k] i32 are the two directions of the
    one order: the product reads the one, the gather the other."""
    C = pair.shape[0]
    T, k = place.shape
    if combine_is_a_product(C, T, k):
        live = jnp.arange(C) < n
        # [T, C]: a pair's weight at (its row, its place), zero elsewhere
        # and behind the last group. As a float32 product over the C rows
        # it adds each into its row; on the v5e at Kimi's widths (C 1,024
        # of 8,192) that took 0.15 ms a layer where a scatter-add of the
        # same rows took 0.84 and the un-sort of all pairs and their sum
        # 2.05 (PERF.md section 6, PR 42)
        ys = jnp.where(live[:, None], ys, 0).astype(jnp.float32)
        comb = jnp.where(
            (pair // k == jnp.arange(T)[:, None]) & live,
            1.0 if w is None else jnp.take(w.reshape(-1), pair), 0.0)
        return jnp.dot(comb, ys, precision=lax.Precision.HIGHEST)
    # un-sorted in the products' own dtype and float32 from there on: a
    # gather moves values and rounds none, so the sum is what it would be
    # un-sorted in float32, and no pass holds a float32 copy of the places
    # in the sorted order as well (1.2 GB at a trainer's launch of 16,384
    # rows on every place)
    got = jnp.take(ys, jnp.minimum(place, C - 1), axis=0)      # [T, k, d]
    got = jnp.where((place < n)[..., None], got, 0).astype(jnp.float32)
    return jnp.sum(got if w is None else got * w[..., None], axis=1)


@jax.custom_vjp
def _dispatch(h, pair, place, n):
    """The rows of the first C places of the sorted order, xs [C, d] (C =
    len(pair); places from n on are zero). Its derivative is the way back
    with unit weights."""
    return _to_places(h, pair, n, place.shape[1])


def _dispatch_fwd(h, pair, place, n):
    return _dispatch(h, pair, place, n), (pair, place, n)


def _dispatch_bwd(res, g):
    pair, place, n = res
    return _to_rows(g, None, pair, place, n).astype(g.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, w, pair, place, n):
    """Each place's output times its pair's router weight, summed into its
    row in float32: y [T, d] in ys' dtype. Its derivative by ys is the way
    to the places (a gather of C rows of the rows' gradient, each times its
    pair's weight: a place receives one contribution, nothing accumulates),
    by w a row-wise dot at the C places, handed to their pairs as scalars."""
    return _to_rows(ys, w, pair, place, n).astype(ys.dtype)


def _combine_fwd(ys, w, pair, place, n):
    return _combine(ys, w, pair, place, n), (ys, w, pair, place, n)


def _combine_bwd(res, g):
    ys, w, pair, place, n = res
    C = pair.shape[0]
    gp = _to_places(g, pair, n, w.shape[1]).astype(jnp.float32)    # [C, d]
    dys = (gp * jnp.take(w.reshape(-1), pair)[:, None]).astype(ys.dtype)
    at = jnp.where(jnp.arange(C) < n,
                   jnp.sum(gp * ys.astype(jnp.float32), axis=-1), 0.0)
    dw = jnp.where(place < n, jnp.take(at, jnp.minimum(place, C - 1)), 0.0)
    return dys, dw.astype(w.dtype), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _experts_sorted(h, w, e, valid, load, lp, cfg: LlamaConfig, layer):
    """The (row, expert) pairs sorted by expert, three grouped matmuls
    over `load` rows a group, and each pair's output times its router
    weight summed into its row. A padding row's pairs, and under a held
    share (`experts_held`) the pairs of experts held elsewhere, sort behind
    every group and belong to none.

    Only the first `held_pair_slots(T, cfg)` places of that order are
    gathered (`_dispatch`), multiplied and summed into their rows
    (`_combine`); a row with no held pair gets an exact zero. Where every
    expert is held that is all T*k (the WHOLE form). Under a share it is
    at most half of them (the COMPACT form), chosen on the device by
    sum(load) <= C; a launch whose held pairs do not fit takes the whole
    form, so no pair is ever dropped. One code path at either count: the
    way back is the [T, C] product where C is few and the gather of a
    row's k places where it is many (`combine_is_a_product`).

    With `layer`, the weights are the stacked [L, E, ...] leaves and the
    kernel finds the layer's experts by its index map (group g reads
    w[layer*E + g], i.e. a group offset of -layer*E): a slice of the
    stack would be copied whole, 268 MB a matrix at OLMoE's widths, before
    each launch."""
    T, k = e.shape
    held = cfg.held[1]
    offset = jnp.asarray(0 if layer is None else -layer * held, jnp.int32)

    def dot(x, name):
        wn = lp[name].astype(h.dtype)
        return _grouped_matmul(x, wn.reshape(-1, *wn.shape[-2:]), load,
                               offset)

    def form(slots):
        pad = -slots % GMM_ROWS
        # summed here and again for the `cond`, not handed in: as one more
        # operand of the branches it keeps XLA from sinking the router's
        # tail into them (PERF.md section 6, PR 48)
        n = jnp.sum(load)
        with jax.named_scope("dispatch"):
            mine = valid[:, None]
            if cfg.experts_held:
                # a pair of an expert held elsewhere: no one's
                mine = mine & (e >= 0) & (e < held)
            flat_e = jnp.where(mine, e, held).reshape(-1)
            order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
            # in whole tiles of the kernel's rows: a place behind `slots`
            # is behind n too, so it holds no pair
            pair = jnp.pad(order[:slots], (0, pad))
            # the order's other direction; only the gather of the way back
            # reads it, so a launch that never takes one never sorts twice
            place = jnp.argsort(order).astype(jnp.int32).reshape(T, k)
            xs = _dispatch(h, pair, place, n)
        with jax.named_scope("experts"):
            # rows behind the last group are whatever the kernel left there,
            # in either direction: select them away, do not multiply by zero
            keep = (jnp.arange(slots + pad) < n)[:, None]
            a = jnp.where(keep, jax.nn.silu(dot(xs, "w1")) * dot(xs, "w3"), 0)
            ys = dot(a, "w2")
        with jax.named_scope("combine"):
            return _combine(ys, w, pair, place, n)

    slots = held_pair_slots(T, cfg)
    if slots == T * k:
        return form(slots)
    # Under differentiation a `cond` hands the residuals of BOTH branches
    # out of the conditional, each one materialised (a trainer's step of
    # 16,384 rows then asks 17.2 GB of the chip's 15.75); under
    # `jax.checkpoint` a branch's residuals are its arguments alone and its
    # backward runs its forward again (the sorts, the C-row gather and the
    # three products; not the way back, which nothing reads). Forward only,
    # `jax.checkpoint` changes nothing.
    return lax.cond(jnp.sum(load) <= slots,
                    jax.checkpoint(lambda: form(slots)),
                    jax.checkpoint(lambda: form(T * k)))


def routed_ffn_load(h: jax.Array, lp: Dict[str, jax.Array], cfg: LlamaConfig,
                    valid: Optional[jax.Array] = None, layer=None,
                    chosen: bool = False):
    """The routed SwiGLU experts over normed activations h [..., d]:
    sum_j w_j * (silu(h W1[e_j]) * (h W3[e_j])) W2[e_j] over a row's top-k
    experts. `valid` [...] bool marks the rows that exist (a serving tick
    pads its rows): a padding row joins no expert's group, yields zeros and
    counts in no load. With `layer` (an int32 scalar) `lp`'s w1, w3 and w2
    are the stacked [L, E, ...] leaves, as a layer loop that must not
    slice them hands them over; the router is the layer's own. Returns
    (y [..., d], load [E] i32: valid rows on each expert), and with
    `chosen` a third member, the experts `route` gave each row ([T, k]
    i32 over all experts, held here or not: what a comparison hands a
    reference so that it sends no row elsewhere). Scopes: router,
    dispatch, experts, combine. With `cfg.shared_expert_width` the layer's
    shared expert (`ws1`, `ws3`, `ws2` of `lp`, ungated) is added on the
    valid rows under scope `shared_expert`."""
    shape = h.shape
    h = h.reshape(-1, shape[-1])
    T = h.shape[0]
    valid = (jnp.ones((T,), bool) if valid is None
             else valid.reshape(-1))
    with jax.named_scope("router"):
        w, routed = route(h, lp, cfg)
        # from here on an expert is its place among the held ones; one held
        # elsewhere falls outside [0, count) and into no group
        e = routed - cfg.held[0] if cfg.experts_held else routed
    with jax.named_scope("dispatch"):
        load = jnp.sum(jax.nn.one_hot(e, cfg.held[1], dtype=jnp.int32)
                       * valid[:, None, None], axis=(0, 1),
                       dtype=jnp.int32)                           # [E]
    if expert_form(cfg) == "sorted_gmm":
        y = _experts_sorted(h, w, e, valid, load, lp, cfg, layer)
    else:
        y = _experts_dense(h, w, e, valid, lp, cfg, layer)
    if cfg.shared_expert_width:
        with jax.named_scope("shared_expert"):
            shared = ffn(h, {"w1": lp["ws1"], "w3": lp["ws3"],
                             "w2": lp["ws2"]})
            y = y + jnp.where(valid[:, None], shared, 0)
    out = (y.reshape(shape), load)
    return out + (routed,) if chosen else out


def routed_ffn(h: jax.Array, lp: Dict[str, jax.Array], cfg: LlamaConfig,
               valid: Optional[jax.Array] = None) -> jax.Array:
    """`routed_ffn_load` without the load (the one routed FFN of the tree:
    `block`, `inference/llm.py`, the paged engine's tick and the hybrid
    trainer on dp = 1 call it or `routed_ffn_load`; only where dp > 1
    exchanges the experts does the trainer dispatch over the ep axis with
    its own all_to_all, `hybrid._moe_ffn`)."""
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
          ffn_impl: str = "stock",
          spec: Optional[LayerSpec] = None, index_rope=None) -> jax.Array:
    """One transformer block; lp leaves have the layer axis already indexed.
    `spec` is the layer's entry of a layer plan (its heads, window or full
    attention, dense or sparse FFN; cos and sin are its rope's, narrower
    than a head for a partial one); None: the config's one kind.
    `index_rope`: (cos, sin) of the sparse index of a layer of heads' own
    keys and values (`LlamaConfig.index_rope_width`), which then attends
    over its selection alone."""
    B, T = x.shape[:2]
    hd, nkv = cfg.head_dim, cfg.num_kv_heads
    nh = spec.heads if spec else cfg.num_heads
    ix = spec.index if spec else cfg.index
    h, out = residual(x, lp, cfg, "attn")
    h = rms_norm(h, lp["attn_norm"], cfg.rms_eps)
    if spec and spec.attn == "ssm":
        x = out(ssm_sequences(h, lp, cfg, spec.ssm))
    elif spec and spec.attn == "latent":
        x = out(latent_self_attention(h, lp, cfg, nh, cos, sin, spec.latent))
    else:
        q, k = qk_normed(h @ lp["wq"].astype(h.dtype),
                         h @ lp["wk"].astype(h.dtype), lp, cfg)
        v = (h @ lp["wv"].astype(h.dtype)).reshape(B, T, nkv, hd)
        # (a layer with no rope rotates nothing)
        rope = (lambda t: t) if cos is None else (
            lambda t: apply_rope(t, cos, sin))
        q = rope(q.reshape(B, T, nh, hd))
        k = rope(k.reshape(B, T, nkv, hd))
        window = cfg.sliding_window if spec and spec.attn == "window" else 0
        select = None
        if ix is not None:
            pos = jnp.arange(T)
            select = index_select(
                h, None, lp, cfg, ix, *index_rope,
                jnp.broadcast_to(pos[None, :] <= pos[:, None], (B, T, T)))
        scale = spec.softmax_scale if spec else 0.0
        if scale:       # the kernels and the einsum divide by sqrt(hd)
            q = (q.astype(jnp.float32) * (scale * hd ** 0.5)).astype(q.dtype)
        o = attention(q, k, v, impl=attn_impl, block_length=cfg.block_length,
                      window=window, select=select)
        if cfg.attn_gate:
            o = attn_gated(o, h, lp)
        o = o.reshape(B, T, nh * hd)
        x = out(o @ lp["wo"].astype(o.dtype))
    h, out = residual(x, lp, cfg, "mlp")
    h = rms_norm(h, lp["mlp_norm"], cfg.rms_eps)
    if (spec.ffn == "sparse") if spec else cfg.num_experts:
        return out(routed_ffn(h, lp, cfg))
    return out(ffn(h, lp, impl=ffn_impl))


def hyper_spread(x: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """The embedded rows x [..., d] as the stream's `hyper_lanes` lanes
    [..., n d], every lane a copy (0 lanes: x itself)."""
    n = cfg.hyper_lanes
    return jnp.tile(x, (1,) * (x.ndim - 1) + (n,)) if n else x


def _lanes(x: jax.Array, cfg: LlamaConfig) -> List[jax.Array]:
    """The lanes of a flat stream x [..., n d], each [..., d] in float32."""
    d = cfg.hidden_size
    return [x[..., i * d:(i + 1) * d].astype(jnp.float32)
            for i in range(cfg.hyper_lanes)]


def hyper_collapse(x: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """What the final norm and the head read of the stream x [..., n d]:
    the lanes' sum [..., d], added in float32 (0 lanes: x itself)."""
    if not cfg.hyper_lanes:
        return x
    return functools.reduce(jnp.add, _lanes(x, cfg)).astype(x.dtype)


def hyper_coeff(x: jax.Array, lp: Dict[str, jax.Array], cfg: LlamaConfig,
                which: str) -> jax.Array:
    """The mixing coefficients of sub-block `which` ("attn" | "mlp") for
    the rows x [..., n d], float32 [rows, n^2 + 2n]: n of `hyper_pre`
    (sigmoid), n of the sub-block's output (2 sigmoid), and the lanes' mix
    [out lane j, in lane i] at column 2n + j n + i, doubly stochastic:
        v = x / sqrt(mean(x^2) + rms_eps) over all n d values (the norm's
            weight is folded into phi);  u = v phi
        logits = alpha_pre u[:n] + b[:n] | alpha_post u[n:2n] + b[n:2n] |
            alpha_res u[2n:] + b[2n:]
        M = exp(clip(the last, *hyper_clamp)), then `hyper_sinkhorn_iters`
            times: every column over (its sum + hyper_eps), every row over
            (its sum + hyper_eps).
    The row's scale is applied to u, not to x (one pass over the stream:
    its squares and its product with phi, float32 accumulation). The
    iteration runs on [n^2 + 2n, rows], a row of the batch a lane, with
    the 4 x 4 unrolled and its sums written as adds of rows: elementwise
    work on dense vectors that XLA fuses, where a [rows, n, n] array would
    pad 32 times."""
    n, f32 = cfg.hyper_lanes, jnp.float32
    rows = x.reshape(-1, x.shape[-1])
    phi = lp[f"hc_{which}_phi"].astype(x.dtype)
    alpha, b = (lp[f"hc_{which}_{name}"].astype(f32)
                for name in ("alpha", "b"))
    r32 = rows.astype(f32)
    scale = lax.rsqrt(jnp.mean(r32 * r32, axis=-1) + cfg.rms_eps)
    u = lax.dot_general(rows, phi, (((1,), (1,)), ((), ())),
                        preferred_element_type=f32)
    u = (u * scale[:, None]).T                             # [n^2 + 2n, rows]
    pre = jax.nn.sigmoid(alpha[0] * u[:n] + b[:n, None])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * u[n:2 * n] + b[n:2 * n, None])
    m = jnp.exp(jnp.clip(alpha[2] * u[2 * n:] + b[2 * n:, None],
                         *cfg.hyper_clamp))
    m = [m[j * n:(j + 1) * n] for j in range(n)]        # out lane j: [n, rows]
    eps = cfg.hyper_eps
    for _ in range(cfg.hyper_sinkhorn_iters):
        col = functools.reduce(jnp.add, m) + eps
        m = [mj / col for mj in m]
        m = [mj / (functools.reduce(
            jnp.add, [mj[i:i + 1] for i in range(n)]) + eps) for mj in m]
    return jnp.concatenate([pre, post, *m]).T


def hyper_pre(x: jax.Array, c: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """What a sub-block sees of the stream x [..., n d]: sum_i c[:, i] x_i
    [..., d], float32 multiply-adds over the lanes' slices."""
    lanes = _lanes(x.reshape(-1, x.shape[-1]), cfg)
    h = functools.reduce(jnp.add, [c[:, i:i + 1] * lane
                                   for i, lane in enumerate(lanes)])
    return h.astype(x.dtype).reshape(*x.shape[:-1], cfg.hidden_size)


def hyper_post(x: jax.Array, y: jax.Array, c: jax.Array,
               cfg: LlamaConfig) -> jax.Array:
    """The stream behind a sub-block whose output is y [..., d]: lane j is
    sum_i M[j, i] x_i + post_j y (coefficients c of `hyper_coeff`)."""
    n, d = cfg.hyper_lanes, cfg.hidden_size
    lanes = _lanes(x.reshape(-1, n * d), cfg)
    y32 = y.reshape(-1, d).astype(jnp.float32)
    col = lambda k: c[:, k:k + 1]
    out = [functools.reduce(jnp.add, [
        col(2 * n + j * n + i) * lanes[i] for i in range(n)])
        + col(n + j) * y32 for j in range(n)]
    return jnp.concatenate(out, axis=-1).astype(x.dtype).reshape(x.shape)


def residual(x: jax.Array, lp: Dict[str, jax.Array], cfg: LlamaConfig,
             which: str):
    """The two ends of the residual seam around sub-block `which` ("attn" |
    "mlp") of one layer: (h, out) with h what the sub-block's norm reads
    of the stream x and out(y) the stream behind the sub-block whose output
    is y. The plain sum: h is x and out(y) is x + y, traced where the
    caller writes it, as every block always did. With `hyper_lanes`: h =
    `hyper_pre` of the lanes under the row's coefficients (scopes `hyper` >
    `hyper_coeff`, `hyper_pre`, here) and out(y) = `hyper_post` (scope
    `hyper` > `hyper_post`, inside whatever scope the caller adds y in).
    Every `x = x + ...` of `block` and of the serving tick's layer loop
    goes through here; `residual_scale` multiplies y where it is not 1
    (never under hyper-connections: no model served has both)."""
    if not cfg.hyper_lanes:
        if cfg.residual_scale != 1:
            return x, lambda y: x + cfg.residual_scale * y
        return x, lambda y: x + y
    with jax.named_scope("hyper"):
        with jax.named_scope("hyper_coeff"):
            c = hyper_coeff(x, lp, cfg, which)
        with jax.named_scope("hyper_pre"):
            h = hyper_pre(x, c, cfg)

    def out(y):
        with jax.named_scope("hyper"), jax.named_scope("hyper_post"):
            return hyper_post(x, y, c, cfg)

    return h, out


def attn_gated(o: jax.Array, h: jax.Array, lp: Dict[str, jax.Array]):
    """The per-head attention gate: o [..., H, hd] times sigmoid(h wg)
    [..., H], from the layer's normed input h [..., d] (scope
    `attn_gate`)."""
    with jax.named_scope("attn_gate"):
        g = jax.nn.sigmoid((h @ lp["wg"].astype(h.dtype)
                            ).astype(jnp.float32))
        return (o.astype(jnp.float32) * g[..., None]).astype(o.dtype)


def latent_cq(h: jax.Array, lp: Dict[str, jax.Array], cfg: LlamaConfig,
              ls: Optional[LatentSpec] = None) -> jax.Array:
    """A latent layer's query latent from its normed input h [B, T, d]:
    c_q = q_scale * RMSNorm(h Wqa) [B, T, q_lora_rank]: what the queries
    and, where the layer has one, the index queries are made of."""
    ls = ls or cfg.one_latent()
    cq = rms_norm(h @ lp["wqa"].astype(h.dtype), lp["qa_norm"], cfg.rms_eps)
    return cq if ls.q_scale == 1.0 else (
        cq.astype(jnp.float32) * ls.q_scale).astype(cq.dtype)


def latent_q(h: jax.Array, lp: Dict[str, jax.Array], cfg: LlamaConfig,
             heads: int, cos: jax.Array, sin: jax.Array,
             ls: Optional[LatentSpec] = None, cq: Optional[jax.Array] = None):
    """The queries of a latent layer from its normed input h [B, T, d]:
    q = c_q Wqb (`latent_cq`, or `cq` where the caller has it), a head's
    (q_nope | q_rope) with rope on the `qk_rope_head_dim` slice (cos, sin
    [T, rope / 2]). Returns (q_nope [B, T, H, nope], q_rope [B, T, H,
    rope]). `ls`: the layer's widths (None: the plan's one kind)."""
    ls = ls or cfg.one_latent()
    nope = ls.qk_nope_head_dim
    cq = latent_cq(h, lp, cfg, ls) if cq is None else cq
    q = (cq @ lp["wqb"].astype(h.dtype)).reshape(*h.shape[:2], heads, -1)
    return q[..., :nope], apply_rope(q[..., nope:], cos, sin)


def latent_kv(h: jax.Array, lp: Dict[str, jax.Array], cfg: LlamaConfig,
              cos: jax.Array, sin: jax.Array,
              ls: Optional[LatentSpec] = None) -> jax.Array:
    """What a latent layer's cache holds of each position, from the
    layer's normed input h [B, T, d]: (c | k_rope) [B, T, kv_lora_rank +
    rope], c = kv_scale * RMSNorm(the latent part of h Wkva), k_rope the
    rest under rope: one key vector for every head."""
    ls = ls or cfg.one_latent()
    C = ls.kv_lora_rank
    ckr = h @ lp["wkva"].astype(h.dtype)
    c = rms_norm(ckr[..., :C], lp["kva_norm"], cfg.rms_eps)
    if ls.kv_scale != 1.0:
        c = (c.astype(jnp.float32) * ls.kv_scale).astype(c.dtype)
    k_r = apply_rope(ckr[..., None, C:], cos, sin)[..., 0, :]
    return jnp.concatenate([c, k_r], axis=-1)


def latent_wkvb(lp: Dict[str, jax.Array], cfg: LlamaConfig, heads: int,
                dtype, ls: Optional[LatentSpec] = None):
    """Wkvb as (keys [C, H, nope], values [C, H, v]): what rebuilds a
    head's key and value from a latent (the expanded form), and what the
    absorbed form folds into its queries and applies to its outputs
    (`ops.kernels.serving_attention.paged_latent_attention`)."""
    ls = ls or cfg.one_latent()
    w = lp["wkvb"].astype(dtype).reshape(ls.kv_lora_rank, heads, -1)
    return w[..., :ls.qk_nope_head_dim], w[..., ls.qk_nope_head_dim:]


def index_qkw(h: jax.Array, cq: Optional[jax.Array],
              lp: Dict[str, jax.Array], cfg: LlamaConfig, ix: IndexSpec,
              cos: jax.Array, sin: jax.Array):
    """The sparse index's three parts from a layer's normed input h
    [B, T, d] and, of a latent layer, its query latent cq (`latent_cq`;
    None where the layer has none): index queries qI [B, T, IH, ID] = (cq | h)
    Wiq, ONE index key a position kI [B, T, ID] = LayerNorm(h Wik) (weight
    and bias), both under rope by the table handed in (cos, sin [T, n]:
    the leading 2n values of each are turned, `LlamaConfig
    .index_rope_width`), and the heads' weights w [B, T, IH] float32 =
    (h Wiw) * IH ** -0.5 * ID ** -0.5."""
    src = h if cq is None else cq
    with jax.named_scope("index_q"):
        qi = apply_rope((src @ lp["wiq"].astype(h.dtype)).reshape(
            *h.shape[:2], ix.heads, ix.head_dim), cos, sin)
        w = ((h @ lp["wiw"].astype(h.dtype)).astype(jnp.float32)
             * (ix.heads ** -0.5 * ix.head_dim ** -0.5))
    with jax.named_scope("index_k"):
        k = (h @ lp["wik"].astype(h.dtype)).astype(jnp.float32)
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = k * lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True)
                          + cfg.rms_eps)
        k = (k * lp["ik_norm"].astype(jnp.float32)
             + lp["ik_bias"].astype(jnp.float32)).astype(h.dtype)
        k = apply_rope(k[..., None, :], cos, sin)[..., 0, :]
    return qi, k, w


def index_select(h: jax.Array, cq: Optional[jax.Array],
                 lp: Dict[str, jax.Array], cfg: LlamaConfig, ix: IndexSpec,
                 cos: jax.Array, sin: jax.Array,
                 visible: jax.Array) -> jax.Array:
    """Whole sequences' selections [B, T, T] bool: of the keys `visible`
    shows each row, the `ix.topk` of largest index score (`index_qkw`,
    `index_scores`, `select_topk`): what the model's own forward attends
    over, under either kind of cache."""
    qi, ki, w = index_qkw(h, cq, lp, cfg, ix, cos, sin)
    return jax.vmap(lambda a, b, c, m: select_topk(
        index_scores(a, b, c), m, ix.topk))(qi, ki, w, visible)


def latent_self_attention(h: jax.Array, lp: Dict[str, jax.Array],
                          cfg: LlamaConfig, heads: int, cos: jax.Array,
                          sin: jax.Array,
                          ls: Optional[LatentSpec] = None) -> jax.Array:
    """Causal latent attention of whole sequences h [B, T, d] in the
    EXPANDED form (no cache): every position's latent through Wkvb to a
    head's (k_nope | v), k_h = (k_nope_h | k_rope), scores times the
    spec's `score_scale`, softmax in float32; under `ls.window` over the
    last W keys, under `ls.index` over the selected keys alone
    (`index_select`); with `cfg.attn_gate` a
    head's output times its gate; the heads' outputs through Wo. The paged
    engine computes the same numbers over its latent pages, in the
    absorbed form. `ls`: the layer's widths (None: the plan's one kind)."""
    ls = ls or cfg.one_latent()
    B, T, _ = h.shape
    C = ls.kv_lora_rank
    cq = latent_cq(h, lp, cfg, ls)
    q = jnp.concatenate(latent_q(h, lp, cfg, heads, cos, sin, ls, cq),
                        axis=-1)
    row = latent_kv(h, lp, cfg, cos, sin, ls)
    wk, wv = latent_wkvb(lp, cfg, heads, h.dtype, ls)
    k = jnp.concatenate(
        [jnp.einsum("btc,chn->bthn", row[..., :C], wk),
         jnp.broadcast_to(row[:, :, None, C:],
                          (B, T, heads, ls.qk_rope_head_dim))], axis=-1)
    v = jnp.einsum("btc,chv->bthv", row[..., :C], wv)
    s = (jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32)
         * ls.score_scale)
    pos = jnp.arange(T)
    mask = jnp.broadcast_to(pos[None, :] <= pos[:, None], (B, T, T))
    if ls.window:
        mask = mask & (pos[None, :] > pos[:, None] - ls.window)
    if ls.index is not None:
        mask = index_select(h, cq, lp, cfg, ls.index, cos, sin, mask)
    s = jnp.where(mask[:, None], s, -1e30)
    o = jnp.einsum("bhts,bshv->bthv", jax.nn.softmax(s, axis=-1
                                                     ).astype(h.dtype), v)
    if cfg.attn_gate:
        o = attn_gated(o, h, lp)
    return o.reshape(B, T, -1) @ lp["wo"].astype(o.dtype)


def ssm_recurrence(xbc: jax.Array, dt: jax.Array, lp: Dict[str, jax.Array],
                   sm: SsmSpec, state_pool, conv_pool, layer, slots, past,
                   this, cu, one_row: bool = False, kernel=False):
    """What of a state-space mixer is no matrix product with its weights,
    on a packed stream's projected rows xbc [T, conv_dim] and dt [T, H]
    (`ops/kernels/ssm.py` says what a stream, a slot and the two pools
    are): the convolution with its carried rows (scope `ssm_conv`, with
    the split into u, B, C and delta = softplus(dt + dt_bias)), the
    one-row segments' state update (`ssm_step`) and the longer segments'
    chunked scan (`ssm_scan`; not traced where the caller promises
    `one_row`: every segment of the tick is one row; `kernel`: the one-row
    update as the launch of `ops/pallas/ssm_step.py`). Returns (y [T, H, P]
    float32, the convolution's output [T, conv_dim], state_pool,
    conv_pool)."""
    from ..ops.kernels import ssm as S
    T = xbc.shape[0]
    H, P, N, G = sm.heads, sm.head_dim, sm.d_state, sm.groups
    with jax.named_scope("ssm_conv"):
        xbc, conv_pool = S.ssm_conv(xbc, conv_pool, layer, slots, past, this,
                                    cu, lp["conv_w"], lp["conv_b"])
        u = xbc[:, :sm.inner].reshape(T, H, P)
        Bm = xbc[:, sm.inner:sm.inner + G * N].reshape(T, G, N)
        Cm = xbc[:, sm.inner + G * N:].reshape(T, G, N)
        delta = jax.nn.softplus(dt.astype(jnp.float32)
                                + lp["dt_bias"].astype(jnp.float32))
        args = (u, Bm, Cm, delta, jnp.exp(lp["A_log"].astype(jnp.float32)),
                lp["D"])
    with jax.named_scope("ssm_step"):
        y, state_pool = S.ssm_step(*args, state_pool, layer, slots, past,
                                   this, cu, kernel)
    if not one_row:
        with jax.named_scope("ssm_scan"):
            y_long, state_pool = S.ssm_scan(
                *args, state_pool, layer, slots, past, this, cu, sm.chunk)
            y = y + y_long
    return y, xbc, state_pool, conv_pool


def ssm_mixer(h: jax.Array, lp: Dict[str, jax.Array], sm: SsmSpec,
              eps: float, state_pool, conv_pool, layer, slots, past, this, cu,
              one_row: bool = False, kernel=False):
    """The state-space mixer of one layer on a packed stream's normed rows
    h [T, d] (`layer` is the layer's place among the state-space layers):
    (its output [T, d] before the residual, state_pool, conv_pool). Scopes
    `ssm` > `ssm_in` (w_in: z | xBC; w_dt), `ssm_conv`, `ssm_step`,
    `ssm_scan` (`ssm_recurrence`), `ssm_gate` (times silu(z), then the norm
    over a group's values), `ssm_out` (w_out)."""
    T = h.shape[0]
    with jax.named_scope("ssm"):
        with jax.named_scope("ssm_in"):
            zx = h @ lp["w_in"].astype(h.dtype)
            z, xbc = zx[:, :sm.inner], zx[:, sm.inner:]
            dt = h @ lp["w_dt"].astype(h.dtype)
        y, _, state_pool, conv_pool = ssm_recurrence(
            xbc, dt, lp, sm, state_pool, conv_pool, layer, slots, past, this,
            cu, one_row, kernel)
        with jax.named_scope("ssm_gate"):
            g = (y.reshape(T, sm.inner) * jax.nn.silu(z.astype(jnp.float32))
                 ).reshape(T, sm.groups, -1)
            g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
            o = (g.reshape(T, sm.inner)
                 * lp["ssm_norm"].astype(jnp.float32)).astype(h.dtype)
        with jax.named_scope("ssm_out"):
            return o @ lp["w_out"].astype(o.dtype), state_pool, conv_pool


def ssm_state_pools(sm: SsmSpec, layers: int, slots: int, conv_dtype):
    """Zeroed (state_pool, conv_pool) for `layers` state-space layers and
    `slots` sequences, the void slot behind them."""
    return (jnp.zeros((layers, slots + 1, sm.heads, sm.head_dim, sm.d_state),
                      SSM_STATE_DTYPE),
            jnp.zeros((layers, slots + 1, (sm.d_conv - 1) * sm.conv_dim),
                      conv_dtype))


def ssm_sequences(h: jax.Array, lp: Dict[str, jax.Array], cfg: LlamaConfig,
                  sm: SsmSpec) -> jax.Array:
    """`ssm_mixer` on whole sequences h [B, T, d], each from a zero state:
    the sequences are the segments of one packed stream, and the pools are
    made and dropped here."""
    B, T, d = h.shape
    counts = jnp.full((B,), T, jnp.int32)
    out, _, _ = ssm_mixer(
        h.reshape(B * T, d), lp, sm, cfg.rms_eps,
        *ssm_state_pools(sm, 1, B, h.dtype), jnp.int32(0),
        jnp.arange(B, dtype=jnp.int32), jnp.zeros((B,), jnp.int32), counts,
        jnp.arange(B + 1, dtype=jnp.int32) * T)
    return out.reshape(B, T, d)


def plan_segments(cfg: LlamaConfig):
    """The plan as the loops that run it: [(repeats, ((kind, count),
    ...)), ...]. Consecutive layers of one kind are a run; the longest
    stretch that is one sequence of runs repeated is a period (Laguna's 40
    layers: its leading dense layer once, then (3 window, 1 full) nine
    times, then 3 window once)."""
    runs: List[Tuple[int, int]] = []
    for k in cfg.kind_of_layer:
        if runs and runs[-1][0] == k:
            runs[-1] = (k, runs[-1][1] + 1)
        else:
            runs.append((k, 1))
    best = None
    for p in range(len(runs)):
        for q in range(1, (len(runs) - p) // 2 + 1):
            n = 1
            while runs[p + n * q:p + (n + 1) * q] == runs[p:p + q]:
                n += 1
            if n >= 2 and (best is None or n * q > best[1] * best[2]):
                best = (p, q, n)
    if best is None:
        return [(1, tuple(runs))]
    p, q, n = best
    parts = [(1, tuple(runs[:p])), (n, tuple(runs[p:p + q])),
             (1, tuple(runs[p + n * q:]))]
    return [part for part in parts if part[1]]


def scan_plan(cfg: LlamaConfig, body: Callable, carry, stacks,
              by_index: bool = False):
    """Run a layer plan: `body(kind, carry, leaves) -> carry` once a layer,
    in the plan's order, with `leaves` the layer's slice of `stacks[kind]`
    (a pytree whose leaves are stacked over that kind's layers; what a
    layer must find by index instead, the page pools and the expert
    matrices, the caller keeps out of it and hands the layer's place in
    its stack as one more leaf). A run of layers of one kind is one
    `lax.scan`, and a period of runs that repeats is a scan over the
    periods with the runs' scans inside, so a kind's body is traced once a
    run of the period, not once a layer. A stack is sliced only where a
    run does not cover it whole. `by_index`: no stack is sliced at all;
    the scans run over the layers' places and a layer's leaves are read
    out of the whole stack where the body runs (a slice of a stack is a
    copy: of 27 of granite-4.0-h's 36 state-space layers, 4.1 GB that the
    chip has not got; the serving tick of a config with such layers asks
    for this, nothing that is differentiated does)."""
    done = [0] * len(cfg.kinds)

    def part(stack, start, count):
        if by_index:
            return start + jnp.arange(count, dtype=jnp.int32)
        return jax.tree.map(
            lambda a: a if (start, count) == (0, a.shape[0])
            else a[start:start + count], stack)

    def run_of(kind):
        if by_index:
            return lambda c, i: (body(kind, c, jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                stacks[kind])), None)
        return lambda c, leaves: (body(kind, c, leaves), None)

    for repeats, runs in plan_segments(cfg):
        if repeats == 1:
            for kind, count in runs:
                carry, _ = lax.scan(run_of(kind), carry,
                                    part(stacks[kind], done[kind], count))
                done[kind] += count
            continue
        per = {}
        for kind, count in runs:
            per[kind] = per.get(kind, 0) + count
        xs = {kind: jax.tree.map(
            lambda a, n=n: a.reshape(repeats, n, *a.shape[1:]),
            part(stacks[kind], done[kind], repeats * n))
            for kind, n in per.items()}

        def period(c, xs_p):
            at = dict.fromkeys(per, 0)
            for kind, count in runs:
                c, _ = lax.scan(
                    run_of(kind), c,
                    xs_p[kind][at[kind]:at[kind] + count] if by_index
                    else part(xs_p[kind], at[kind], count))
                at[kind] += count
            return c, None

        carry, _ = lax.scan(period, carry, xs)
        for kind, n in per.items():
            done[kind] += repeats * n
    return carry


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: LlamaConfig,
            attn_impl: str = "auto", ffn_impl: str = "stock") -> jax.Array:
    """tokens [B, T] int32 → logits [B, T, vocab] (f32)."""
    x = hyper_spread(embedded(params, tokens, cfg), cfg)
    T = tokens.shape[1]
    if cfg.layer_plan:
        kinds = cfg.kinds
        ropes = {spec.rope: rope_table(jnp.arange(T), cfg.rope_width(spec),
                                       spec.rope) for spec in kinds
                 if spec.rope is not None}
        ropes[None] = (None, None)

        iropes = {spec: rope_table(jnp.arange(T), cfg.index_rope_width(spec),
                                   spec.rope)
                  for spec in kinds if spec.index is not None}

        def plan_body(kind, carry, lp):
            return block(carry, lp, cfg, *ropes[kinds[kind].rope],
                         attn_impl, ffn_impl, spec=kinds[kind],
                         index_rope=iropes.get(kinds[kind]))

        x = scan_plan(cfg, plan_body, x, params["blocks"])
    else:
        cos, sin = rope_cos_sin(jnp.arange(T), cfg.head_dim, cfg.rope_theta)
        irope = cfg.index and rope_cos_sin(jnp.arange(T), cfg.index.head_dim,
                                           cfg.rope_theta)

        def body(carry, lp):
            return block(carry, lp, cfg, cos, sin, attn_impl, ffn_impl,
                         index_rope=irope), None

        x, _ = lax.scan(body, x, params["blocks"])
    x = rms_norm(hyper_collapse(x, cfg), params["final_norm"], cfg.rms_eps)
    return head_logits(params, x, cfg)


def embedded(params: Dict[str, Any], tokens: jax.Array,
             cfg: LlamaConfig) -> jax.Array:
    """The tokens' rows of the embedding in the compute dtype, times
    `embed_scale` where the config has one."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    return x if cfg.embed_scale == 1 else x * cfg.embed_scale


def head_logits(params: Dict[str, Any], h: jax.Array,
                cfg: LlamaConfig) -> jax.Array:
    """Float32 logits of normed rows h [..., d]: through `lm_head`, or with
    `tie_embeddings` through the embedding's transpose; over
    `logit_divisor` where the config has one."""
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", h,
                            params["embed"].astype(h.dtype))
    else:
        logits = h @ params["lm_head"].astype(h.dtype)
    logits = logits.astype(jnp.float32)
    return logits if cfg.logit_divisor == 1 else logits / cfg.logit_divisor


def loss_fn(params: Dict[str, Any], tokens: jax.Array, targets: jax.Array,
            cfg: LlamaConfig, attn_impl: str = "auto",
            ffn_impl: str = "stock") -> jax.Array:
    """Next-token cross entropy, mean over tokens."""
    logits = forward(params, tokens, cfg, attn_impl, ffn_impl)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - true)
