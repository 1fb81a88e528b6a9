"""TPU-native hybrid-parallel training engine (the fleet analog).

Reference design (SURVEY.md §2.5/CS5): fleet composes DP / TP (Megatron
mp_layers) / PP (1F1B over NCCL p2p) / sequence-parallel / expert-parallel as
Python wrappers firing NCCL collectives per bucket/microbatch
(python/paddle/distributed/fleet/meta_parallel/*, pipeline_parallel.py:575,
mpu/mp_layers.py:49,336,543, moe/moe_layer.py:263).

TPU-native redesign: ONE compiled XLA program per train step. A
`jax.sharding.Mesh` with axes ('dp','pp','tp') replaces the
HybridCommunicateGroup topology; the whole step (all microbatches, forward,
backward, grad sync, optimizer) runs inside a single `jax.shard_map`ped,
jitted function where:

- **TP + SP (Megatron sequence parallel)**: activations stay sequence-sharded
  over 'tp' between layers; `all_gather(seq)` before column-parallel matmuls,
  `psum_scatter(seq)` after row-parallel matmuls — the exact
  ScatterOp/AllGatherOp/ReduceScatterOp pattern of
  fleet/utils/sequence_parallel_utils.py, but compiled to ICI collectives.
- **PP**: GPipe microbatch rotation via `lax.ppermute` inside a `lax.scan` —
  the schedule is differentiated through (ppermute transposes to the inverse
  permutation), so one `jax.grad` covers the whole pipeline instead of the
  reference's hand-built forward_backward_pipeline (pipeline_parallel.py:575).
  A schedule of one slot (one microbatch on pp = 1) is one call of the
  slot and no scan. The slots run the layers alone: once the schedule has
  run, the last stage's M outputs are shared over 'pp' (`head_rounds`) and
  every stage runs the head and the loss on ceil(M / pp) of them
  (`lm_head` and `final_norm` are held by every stage, and their gradients
  summed over 'pp', as it is).
- **EP (MoE)**: where dp > 1 exchanges the experts, GShard-style capacity
  dispatch + `all_to_all` over the 'dp' axis (expert parallelism rides the
  data-parallel axis, as in the reference's global_scatter/global_gather
  design, moe_layer.py:263; `_moe_ffn`, which drops the pairs over its
  capacity). Where nothing is exchanged (dp = 1: every expert here, or one
  chip's share of them, `LlamaConfig.experts_held`) a sparse layer is
  `llama.routed_ffn_load`: the router over all experts in float32, the
  pairs of the experts held here sorted and multiplied by the grouped-matmul
  kernel forward and backward, no pair dropped whatever the load;
  `make_train_step(with_stats=True)` returns the routed layers' counters
  as a fourth output.
- **A layer plan** (`LlamaConfig.layer_plan`: full and sliding-window
  layers, a rope a kind, dense or routed FFNs): `params["blocks"]` is a
  tuple of stacks by kind, a stage's layers run by `llama.scan_plan` under
  the same remat policy as a uniform stack (which is a plan of one kind),
  a window layer through the flash kernel's window. Refused by name: a plan
  with pp > 1 (stages would be cut by whole periods), a held share with
  dp > 1 (the exchange), latent layers, a window under cp > 1.
- **DP**: gradient psum over 'dp' — the EagerReducer (reducer.h:88) collapses
  to one fused collective XLA schedules during the backward.
- **ZeRO-ish**: optimizer states live sharded exactly like the params (tp/pp
  sharded states come for free; the 'sharding'-axis stage-1/2 variants are the
  fleet API layer's job).

Gradient-sync rule (spec-driven): a param leaf's gradient is psum-ed over every
mesh axis NOT appearing in its PartitionSpec (replicated axes), while sharded
axes need nothing — collective transposes already routed cross-shard
contributions. Loss is pre-scaled by 1/dp so the psum yields the global-batch
mean.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import flags
from ..models import llama as L

MESH_AXES = ("dp", "pp", "cp", "tp")


# --------------------------------------------------------------------------
# Mesh + sharding layout
# --------------------------------------------------------------------------

def build_mesh(dp: int = 1, pp: int = 1, tp: int = 1, cp: int = 1,
               devices=None) -> Mesh:
    """dp x pp x cp x tp device mesh. cp = context parallelism (sequence
    sharding with ring attention) — a capability the reference LACKS
    (SURVEY.md §2.5 CP row: 'not present in core repo'); here it is a
    first-class mesh axis alongside the reference's dims."""
    devices = devices if devices is not None else jax.devices()
    n = dp * pp * cp * tp
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)}")
    arr = np.asarray(devices[:n]).reshape(dp, pp, cp, tp)
    return Mesh(arr, MESH_AXES)


def stack_pipeline(params: Dict[str, Any], pp: int) -> Dict[str, Any]:
    """Reshape block leaves [L, ...] → [pp, L//pp, ...] (stage-major); a
    layer plan's stacks by kind each get the stage axis (pp = 1 there)."""
    def f(x):
        Lg = x.shape[0]
        assert Lg % pp == 0, f"num_layers {Lg} not divisible by pp {pp}"
        return x.reshape(pp, Lg // pp, *x.shape[1:])
    out = dict(params)
    out["blocks"] = jax.tree.map(f, params["blocks"])
    return out


def unstack_pipeline(params: Dict[str, Any]) -> Dict[str, Any]:
    def f(x):
        return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])
    out = dict(params)
    out["blocks"] = jax.tree.map(f, params["blocks"])
    return out


def head_rounds(num_microbatches: int, pp: int) -> Tuple[Tuple[int, ...], ...]:
    """Which microbatch's head and loss a stage runs in which round, once
    the schedule has run: `[stage][round]` -> the microbatch, or -1 for a
    place that is padding (pp does not divide M, or M < pp). Stage j takes
    microbatches j, j + pp, ... in ceil(M / pp) rounds, so every microbatch
    stands exactly once. The rounds are the head's passes a stage a step
    (M + pp - 1 while the slots ran them)."""
    rounds = -(-num_microbatches // pp)
    return tuple(
        tuple(m if m < num_microbatches else -1
              for m in range(stage, rounds * pp, pp))
        for stage in range(pp))


# what `_moe_stats` counts of one step's launches of `llama.routed_ffn_load`
# (int32 scalars; all zero where no layer runs it: a dense config, or
# experts exchanged over dp > 1)
MOE_STATS = ("moe_launches", "moe_pairs", "moe_pairs_held", "moe_load_max",
             "moe_whole_form")


def require_trainable(cfg: L.LlamaConfig, dp: int = 1, pp: int = 1,
                      cp: int = 1) -> None:
    """Raise, by name, for what this engine would compute wrongly: the
    block body is `_block_sp` (full or window attention over the heads' own
    keys and values, one rope a kind, a dense or a routed FFN), a layer
    plan is not cut into stages, and a held share is not exchanged."""
    what = "distributed.hybrid trains a layer plan of full and window "\
           "layers with dense or routed FFNs, and"
    for name, has in (
            ("latent attention (LayerSpec.attn = 'latent')",
             any(s.attn == "latent" for s in cfg.layers)),
            ("a per-head attention gate (attn_gate)", cfg.attn_gate),
            ("a shared expert (shared_expert_width)",
             cfg.shared_expert_width),
            ("a router bias (router_bias)", cfg.router_bias),
            ("QK-norm (qk_norm)", cfg.qk_norm),
            ("block diffusion (block_length)", cfg.block_length),
            ("hyper-connections (hyper_lanes)", cfg.hyper_lanes),
            ("state-space layers (LayerSpec.attn = 'ssm': the chunked "
             "scan has no backward pass here)",
             any(s.attn == "ssm" for s in cfg.layers)),
            ("a layer without a rope, a layer's own softmax scale, "
             "embed_scale, residual_scale, logit_divisor or a tied head",
             any(s.rope is None or s.softmax_scale for s in cfg.layers)
             or cfg.embed_scale != 1 or cfg.residual_scale != 1
             or cfg.logit_divisor != 1 or cfg.tie_embeddings)):
        if has:
            raise NotImplementedError(f"{what} does not take {name}")
    if cfg.layer_plan and pp > 1:
        raise NotImplementedError(
            f"{what} does not cut a layer plan into pipeline stages "
            f"(pp = {pp}): stages would have to be cut by whole periods")
    if cfg.experts_held and dp > 1:
        raise NotImplementedError(
            "distributed.hybrid does not exchange a held share of the "
            f"experts (LlamaConfig.experts_held with dp = {dp}): the "
            "all_to_all over the chips that hold the other shares is not "
            "built; dp = 1 trains this chip's share")
    if cp > 1 and any(s.attn == "window" for s in cfg.layers):
        raise NotImplementedError(
            f"{what} does not take a window layer under context "
            f"parallelism (cp = {cp}): ring attention has no window")


def _kind_specs(spec: L.LayerSpec) -> Dict[str, P]:
    """PartitionSpecs of one kind's stack (leaves [pp, L_kind, ...])."""
    blocks = {
        "wq": P("pp", None, None, "tp"),
        "wk": P("pp", None, None, "tp"),
        "wv": P("pp", None, None, "tp"),
        "wo": P("pp", None, "tp", None),
        "attn_norm": P("pp", None, None),
        "mlp_norm": P("pp", None, None),
    }
    if spec.ffn == "sparse":
        blocks["router"] = P("pp", None, None, None)
        blocks["w1"] = P("pp", None, "dp", None, "tp")
        blocks["w3"] = P("pp", None, "dp", None, "tp")
        blocks["w2"] = P("pp", None, "dp", "tp", None)
    else:
        blocks["w1"] = P("pp", None, None, "tp")
        blocks["w3"] = P("pp", None, None, "tp")
        blocks["w2"] = P("pp", None, "tp", None)
    return blocks


def param_specs(cfg: L.LlamaConfig) -> Dict[str, Any]:
    """PartitionSpecs for the stage-stacked param pytree.

    Layout: blocks leaves carry a leading 'pp' stage axis; projections are
    tp-sharded Megatron-style (wq/wk/wv/w1/w3 on the output dim, wo/w2 on the
    input dim); embed/lm_head are vocab-parallel; MoE experts are sharded over
    'dp' (= the ep axis; a held share, `experts_held`, lives on dp = 1). With
    a layer plan `blocks` is a tuple of such dicts, one a kind, as
    `llama.init_params` makes it.
    """
    require_trainable(cfg)
    kinds = tuple(_kind_specs(spec) for spec in cfg.kinds)
    return {
        "embed": P("tp", None),
        "blocks": kinds if cfg.layer_plan else kinds[0],
        "final_norm": P(),
        "lm_head": P(None, "tp"),
    }


def shard_params(params: Dict[str, Any], mesh: Mesh, cfg):
    """Stage-stack + device_put with NamedShardings (host → HBM, laid out).
    cfg: LlamaConfig. (Generic Layers shard their params inside
    hybrid_generic.GenericHybridEngine — no call needed.)"""
    pp = mesh.shape["pp"]
    require_trainable(cfg, mesh.shape["dp"], pp, mesh.shape["cp"])
    stacked = stack_pipeline(params, pp)
    specs = param_specs(cfg)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), stacked, specs)


# --------------------------------------------------------------------------
# Optimizer (sharded AdamW — states shaped/sharded exactly like params)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: Optional[float] = 1.0
    # > 0: step t runs at lr * min(1, t / warmup_steps), a linear warm-up
    # by the optimizer state's own counter (0: lr from the first step)
    warmup_steps: int = 0


def init_opt_state(params):
    zeros = lambda p: jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), p)
    step = jnp.zeros((), jnp.int32)
    sharding = getattr(jax.tree.leaves(params)[0], "sharding", None)
    if isinstance(sharding, NamedSharding):
        # the train step returns the counter replicated over the params'
        # mesh; an uncommitted counter on the first call is a different
        # input type, and the second call would compile the step again
        step = jax.device_put(step, NamedSharding(sharding.mesh, P()))
    return {"m": zeros(params), "v": zeros(params), "step": step}


def _adamw_update(params, grads, opt, hp: AdamWConfig, global_sq_sum,
                  lr=None):
    """lr: optional traced scalar overriding hp.lr (lets an LR schedule
    feed the compiled step without recompilation)."""
    lr = hp.lr if lr is None else lr
    step = opt["step"] + 1
    if hp.warmup_steps:
        lr = lr * jnp.minimum(1.0, step.astype(jnp.float32)
                              / hp.warmup_steps)
    if hp.grad_clip is not None:
        gnorm = jnp.sqrt(global_sq_sum)
        scale = jnp.minimum(1.0, hp.grad_clip / (gnorm + 1e-6))
        grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = hp.beta1, hp.beta2
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        u = (m / bc1) / (jnp.sqrt(v / bc2) + hp.eps)
        u = u + hp.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * u).astype(p.dtype), m, v

    flat_p, tree = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(opt["m"])
    flat_v = jax.tree.leaves(opt["v"])
    new_p, new_m, new_v = [], [], []
    for p_, g_, m_, v_ in zip(flat_p, flat_g, flat_m, flat_v):
        a, b, c = upd(p_, g_, m_, v_)
        new_p.append(a); new_m.append(b); new_v.append(c)
    return (jax.tree.unflatten(tree, new_p),
            {"m": jax.tree.unflatten(tree, new_m),
             "v": jax.tree.unflatten(tree, new_v), "step": step})


# --------------------------------------------------------------------------
# Per-shard building blocks (run inside shard_map)
# --------------------------------------------------------------------------

def _vp_embed_lookup(embed_local, tok, cfg: L.LlamaConfig):
    """Vocab-parallel embedding with sequence-parallel output
    (VocabParallelEmbedding, mp_layers.py:49, composed with the SP scatter of
    sequence_parallel_utils.py): every tp rank looks up the FULL sequence
    against its vocab shard (partial rows), and the vocab-psum is fused with
    the SP seq-scatter into one reduce_scatter — which also transposes to the
    correct all_gather in backward, so each embed shard's gradient collects
    contributions from all sequence chunks.

    tok [B, T] → [B, T/tp, D].
    """
    vloc = embed_local.shape[0]
    start = lax.axis_index("tp") * vloc
    local_ids = tok - start
    in_range = (local_ids >= 0) & (local_ids < vloc)
    safe = jnp.clip(local_ids, 0, vloc - 1)
    emb = jnp.take(embed_local, safe, axis=0)
    emb = jnp.where(in_range[..., None], emb, 0)
    return lax.psum_scatter(emb, "tp", scatter_dimension=1, tiled=True)


def _vp_cross_entropy(logits_local, targets, vloc):
    """Vocab-parallel softmax CE (ParallelCrossEntropy, mp_layers.py:744):
    logits_local [..., V/tp] over the FULL sequence; per-token loss via
    psum-max / psum-sum over the tp (vocab) axis. The result is replicated
    over tp."""
    start = lax.axis_index("tp") * vloc
    # cross-shard max via all_gather (lax.pmax has no differentiation rule);
    # the shift is mathematically grad-free anyway (logsumexp invariance).
    gmax = lax.all_gather(jnp.max(logits_local, axis=-1), "tp")
    lmax = lax.stop_gradient(jnp.max(gmax, axis=0))
    shifted = logits_local - lmax[..., None]
    sumexp = lax.psum(jnp.sum(jnp.exp(shifted), axis=-1), "tp")
    local_t = targets - start
    in_range = (local_t >= 0) & (local_t < vloc)
    safe = jnp.clip(local_t, 0, vloc - 1)
    true_shift = jnp.take_along_axis(shifted, safe[..., None], axis=-1)[..., 0]
    true_shift = lax.psum(jnp.where(in_range, true_shift, 0.0), "tp")
    return jnp.log(sumexp) - true_shift


def _moe_ffn(h_full, lp, cfg: L.LlamaConfig, ep_size: int):
    """GShard top-k MoE with all_to_all expert dispatch over the 'dp' (=ep)
    axis (reference: global_scatter/global_gather collectives feeding expert
    FFNs, moe_layer.py:263). Expert FFN weights are additionally tp-sharded.

    h_full: [B, T, D] (full sequence, after the SP all_gather).
    lp['w1'] local: [E/ep, D, F/tp].
    """
    B, T, D = h_full.shape
    N = B * T
    E = cfg.num_experts
    assert E % ep_size == 0, f"num_experts {E} not divisible by ep (dp) {ep_size}"
    k = cfg.top_k
    x = h_full.reshape(N, D)
    gates = jax.nn.softmax(
        x.astype(jnp.float32) @ lp["router"].astype(jnp.float32), axis=-1)
    C = max(1, (N * k) // E) * 2  # capacity factor 2.0, static
    C = min(C, N)
    topw, topi = lax.top_k(gates, k)
    topw = topw / (jnp.sum(topw, axis=-1, keepdims=True) + 1e-9)
    disp = jnp.zeros((N, E, C), jnp.float32)
    comb = jnp.zeros((N, E, C), jnp.float32)
    counts = jnp.zeros((E,), jnp.int32)
    for c in range(k):
        e_idx = topi[:, c]
        maski = jax.nn.one_hot(e_idx, E, dtype=jnp.int32)
        pos = jnp.cumsum(maski, axis=0) - 1 + counts[None, :]
        counts = counts + jnp.sum(maski, axis=0)
        p = jnp.take_along_axis(pos, e_idx[:, None], axis=1)[:, 0]
        ok = (p < C)
        oh = (jax.nn.one_hot(e_idx, E, dtype=jnp.float32)[:, :, None]
              * jax.nn.one_hot(jnp.clip(p, 0, C - 1), C, dtype=jnp.float32)[:, None, :])
        oh = oh * ok[:, None, None]
        disp = disp + oh
        comb = comb + oh * topw[:, c][:, None, None]
    xe = jnp.einsum("nd,nec->ecd", x.astype(jnp.float32), disp).astype(x.dtype)  # [E, C, D]
    # all_to_all: experts → owner dp rank; tokens from every dp rank concat on C
    xe = lax.all_to_all(xe, "dp", split_axis=0, concat_axis=1, tiled=True)  # [E/ep, C*ep, D]
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, lp["w1"].astype(xe.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", xe, lp["w3"].astype(xe.dtype))
    ye = jnp.einsum("ecf,efd->ecd", h, lp["w2"].astype(h.dtype))
    # NOTE: ye stays PARTIAL over tp (row-parallel w2 shards); the tp reduction
    # happens at the caller's psum_scatter back into sequence shards, so the
    # backward transposes to an all_gather and every tp rank's w2 shard sees
    # gradient contributions from the whole sequence.
    ye = lax.all_to_all(ye, "dp", split_axis=1, concat_axis=0, tiled=True)  # [E, C, D]
    y = jnp.einsum("ecd,nec->nd", ye.astype(jnp.float32), comb)
    return y.reshape(B, T, D).astype(h_full.dtype)


def _moe_stats(load, rows: int, cfg: L.LlamaConfig):
    """What one launch of `routed_ffn_load` adds to the step's counters
    (`MOE_STATS`), from its `load` [held] (rows on each held expert): the
    pairs the router made over all experts, those on the experts held here,
    the fullest held expert's rows, and whether the sorted form moved all
    rows * top_k pairs (its WHOLE form: every expert held, or a share whose
    places, `llama.held_pair_slots`, are all of them or fewer than the
    launch's held pairs)."""
    held = jnp.sum(load)
    slots = L.held_pair_slots(rows, cfg)
    whole = (L.expert_form(cfg) == "sorted_gmm") & (
        (slots == rows * cfg.top_k) | (held > slots))
    stats = {"moe_launches": 1, "moe_pairs": rows * cfg.top_k,
             "moe_pairs_held": held, "moe_load_max": jnp.max(load),
             "moe_whole_form": whole}
    # int32 whatever jax_enable_x64 makes of a sum: a scan's carry
    return {k: jnp.asarray(v, jnp.int32) for k, v in stats.items()}


def _block_sp(x, lp, cfg: L.LlamaConfig, cos, sin, ep_size: int,
              attn_impl: str = "auto", cp: int = 1, ffn_impl: str = "stock",
              spec: Optional[L.LayerSpec] = None, chosen: bool = False):
    """One transformer block with Megatron TP + sequence parallelism.

    x: [B, T/tp, D] sequence-sharded. lp: this layer's local weight shards.
    spec: the layer's kind (`cfg.kinds`; None: a uniform config's one).
    Returns (x, the layer's `MOE_STATS` where its experts ran through
    `llama.routed_ffn_load`, else {}; with `chosen` also the experts its
    router gave each row, under "chosen").
    """
    spec = spec or cfg.kinds[0]
    Bm, Tloc, D = x.shape
    hd = cfg.head_dim
    window = cfg.sliding_window if spec.attn == "window" else 0
    stats = {}
    # named scopes: forward, jvp and transpose operations of a region carry
    # its name inside JAX's wrappers, so a trace reader counts them to it
    with jax.named_scope("attention"):
        h = L.rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        h_full = lax.all_gather(h, "tp", axis=1, tiled=True)          # SP gather [B, T, D]
        T = h_full.shape[1]
        nh_loc = lp["wq"].shape[-1] // hd
        nkv_loc = lp["wk"].shape[-1] // hd
        q = (h_full @ lp["wq"].astype(h_full.dtype)).reshape(Bm, T, nh_loc, hd)
        kk = (h_full @ lp["wk"].astype(h_full.dtype)).reshape(Bm, T, nkv_loc, hd)
        vv = (h_full @ lp["wv"].astype(h_full.dtype)).reshape(Bm, T, nkv_loc, hd)
        q = L.apply_rope(q, cos, sin)
        kk = L.apply_rope(kk, cos, sin)
        if cp > 1:
            # context parallelism: T here is the cp-LOCAL sequence; blockwise
            # ring attention rotates k/v shards over the 'cp' axis (ICI ring)
            from ..ops.ring_attention import ring_attention_shard

            if attn_impl == "flash":
                raise ValueError(
                    "attn_impl='flash' cannot be forced on a cp>1 mesh: context "
                    "parallelism uses ring attention over the cp axis (fusing "
                    "Pallas flash inside the ring blocks is a future "
                    "optimization); use attn_impl='auto'")
            o = ring_attention_shard(q, kk, vv, "cp", causal=True)
            o = o.astype(h_full.dtype).reshape(Bm, T, nh_loc * hd)
        else:
            # a plan's kernel calls carry their kind's name inside `attention`
            with (jax.named_scope(f"attention_{spec.attn}") if cfg.layer_plan
                  else contextlib.nullcontext()):
                o = L.attention(q, kk, vv, impl=attn_impl, window=window)
            o = o.reshape(Bm, T, nh_loc * hd)
        partial = o @ lp["wo"].astype(o.dtype)                         # row-parallel partial
        x = x + lax.psum_scatter(partial, "tp", scatter_dimension=1, tiled=True)
    with jax.named_scope("ffn"):
        h = L.rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        h_full = lax.all_gather(h, "tp", axis=1, tiled=True)
        if spec.ffn == "sparse" and ep_size > 1:
            y_partial = _moe_ffn(h_full, lp, cfg, ep_size)  # partial over tp
            x = x + lax.psum_scatter(y_partial, "tp", scatter_dimension=1, tiled=True)
        elif spec.ffn == "sparse":
            # nothing to exchange: the one routed FFN of the tree, over the
            # experts held here (their f/tp columns: SwiGLU is elementwise
            # in f, so a shard's output is partial over tp like a dense one)
            with jax.named_scope("moe"):
                y_partial, load, *routed = L.routed_ffn_load(
                    h_full, lp, cfg, chosen=chosen)
                stats = _moe_stats(load, Bm * T, cfg)
                if chosen:
                    stats["chosen"] = routed[0]
            x = x + lax.psum_scatter(y_partial, "tp", scatter_dimension=1, tiled=True)
        else:
            # column-parallel w1/w3 + row-parallel w2 → the shard's FFN body is
            # exactly the dense SwiGLU over local f/tp, so the fused Pallas
            # kernel drops in per-shard, before the tp reduce-scatter
            partial = L.ffn(h_full, lp, impl=ffn_impl)
            x = x + lax.psum_scatter(partial, "tp", scatter_dimension=1, tiled=True)
    return x, stats


def _add_stats(acc: dict, stats: dict) -> dict:
    """The step's counters so far plus one layer's; the layer's chosen
    experts, where `acc` keeps them, at the place of its launch."""
    if not (acc and stats):
        return acc
    out = {k: acc[k] + stats[k] for k in MOE_STATS}
    if "chosen" in acc:
        out["chosen"] = lax.dynamic_update_index_in_dim(
            acc["chosen"], stats["chosen"], acc["moe_launches"], 0)
    return out


def _make_shard_loss(cfg: L.LlamaConfig, num_microbatches: int,
                     dp: int, pp: int, tp: int, cp: int = 1,
                     remat: Union[bool, str] = True,
                     attn_impl: str = "auto", ffn_impl: str = "stock",
                     with_stats: bool = False, chosen: bool = False):
    """Build the per-shard loss(params, tokens, targets) -> (scalar, stats)
    function; stats are the step's `MOE_STATS` with `with_stats`, else {}
    (and nothing is counted). `chosen` adds the experts every launch of
    `llama.routed_ffn_load` chose, "chosen" [launches, rows, top_k] i32 in
    the order of the launches (a microbatch's layers one after another).

    Inside: GPipe pipeline over `num_microbatches`, TP/SP per block; then
    the last stage's outputs shared over pp (`head_rounds`) and the final
    norm, the head and the vocab-parallel CE on every stage's share of the
    microbatches; loss pre-scaled by 1/dp.
    """
    require_trainable(cfg, dp, pp, cp)
    M = num_microbatches
    kinds = cfg.kinds
    zero_stats = ({k: jnp.zeros((), jnp.int32) for k in MOE_STATS}
                  if with_stats or chosen else {})
    if chosen and (dp, pp, cp) != (1, 1, 1):
        raise NotImplementedError(
            "the chosen experts are kept on a mesh of dp = pp = cp = 1: "
            "another stage's or shard's launches are not gathered")

    def stage_fn(x, stats, blocks_local, ropes):
        if remat not in (True, False, "dots"):
            raise ValueError(f"remat must be True, False or 'dots', got {remat!r}")

        def body_of(kind):
            def body(carry, lp):
                y, stats = _block_sp(
                    carry[0], lp, cfg, *ropes[kinds[kind].rope], dp,
                    attn_impl, cp, ffn_impl, kinds[kind], chosen)
                return y, _add_stats(carry[1], stats)
            if remat == "dots":
                # save matmul outputs, recompute elementwise/norms: trades a
                # little HBM for skipping most of the backward's forward replay
                return jax.checkpoint(
                    body, prevent_cse=False,
                    policy=jax.checkpoint_policies.dots_saveable)
            return jax.checkpoint(body, prevent_cse=False) if remat else body

        bodies = [body_of(kind) for kind in range(len(kinds))]
        # a uniform stack is a plan of one kind: one scan over its stack
        with jax.named_scope("layers"):
            return L.scan_plan(
                cfg, lambda kind, carry, lp: bodies[kind](carry, lp),
                (x, stats), L.kind_stacks(blocks_local))

    def shard_loss(params, tokens, targets):
        # local shapes: tokens [B/dp, T]; blocks leaves [1, L/pp, ...]
        blocks_local = jax.tree.map(lambda x: x[0], params["blocks"])
        Bloc, T = tokens.shape
        assert Bloc % M == 0, f"local batch {Bloc} not divisible by microbatches {M}"
        Bm = Bloc // M
        Tloc = T // tp
        D = cfg.hidden_size
        tok_mb = tokens.reshape(M, Bm, T)
        tgt_mb = targets.reshape(M, Bm, T)
        stage = lax.axis_index("pp")
        # T is the cp-local sequence; rope positions offset by the cp shard
        pos0 = lax.axis_index("cp") * T if cp > 1 else 0
        # one table a rope: a plan's kinds have their own (YaRN with its
        # attention factor on one, the default on another)
        ropes = {spec.rope: L.rope_table(pos0 + jnp.arange(T),
                                         cfg.rope_width(spec), spec.rope)
                 for spec in kinds}
        vloc = params["lm_head"].shape[1]
        stats0 = dict(zero_stats)
        if chosen:
            launches = M * sum(s.ffn == "sparse" for s in cfg.layers)
            stats0["chosen"] = jnp.zeros((launches, Bm * T, cfg.top_k),
                                         jnp.int32)

        def embed_mb(m):
            with jax.named_scope("embed"):
                x = _vp_embed_lookup(params["embed"], tok_mb[m], cfg)
                return x.astype(cfg.dtype)            # [Bm, T/tp, D]

        def mb_loss(y, m):
            # y [Bm, T/tp, D]: exit the SP region (all_gather seq), then
            # vocab-parallel head + CE over the full sequence. per_tok is
            # replicated over tp; SUM over the microbatch's tokens.
            with jax.named_scope("head_loss"):
                h = L.rms_norm(y, params["final_norm"], cfg.rms_eps)
                h_full = lax.all_gather(h, "tp", axis=1, tiled=True)  # [Bm, T, D]
                logits = (h_full @ params["lm_head"].astype(h_full.dtype)
                          ).astype(jnp.float32)
                per_tok = _vp_cross_entropy(logits, tgt_mb[m], vloc)
                return jnp.sum(per_tok)

        def pipe_step(carry, t):
            x_in, stats_acc = carry
            m = jnp.clip(t - stage, 0, M - 1)
            active = (t - stage >= 0) & (t - stage < M)
            x0 = embed_mb(m)
            x = jnp.where(stage == 0, x0, x_in)
            # the counters ride the stage's own carry: started from zero
            # here and added outside, the compiled step ran the layers'
            # forward pass a third time for them alone (PERF.md, PR 47)
            y, stats = stage_fn(x, stats_acc, blocks_local, ropes)
            # a bubble's launches count for nothing
            stats_acc = jax.tree.map(
                lambda new, old: jnp.where(active, new, old), stats, stats_acc)
            with jax.named_scope("pp_send"):
                y_send = lax.ppermute(
                    y, "pp", [(i, (i + 1) % pp) for i in range(pp)])
            return (y_send, stats_acc), y

        def share_outputs(outs):
            # outs [M, Bm, T/tp, D]: on the last stage the microbatches'
            # outputs. Stage j gets its `head_rounds` of them from the last
            # stage, which keeps its own: pp - 1 sends of [rounds, ...],
            # each from the one source (a stage not named receives zeros)
            def of(j):
                return jnp.stack([outs[max(m, 0)] for m in share[j]])
            mine = of(pp - 1)
            with jax.named_scope("pp_share"):
                for j in range(pp - 1):
                    got = lax.ppermute(of(j), "pp", [(pp - 1, j)])
                    mine = jnp.where(stage == j, got, mine)
            return mine

        carry0 = (jnp.zeros((Bm, Tloc, D), cfg.dtype), stats0)
        slots = M + pp - 1
        share = head_rounds(M, pp)
        with jax.named_scope("pipeline"):
            if slots == 1:
                # a schedule of one slot is a call: as a scan of length one
                # its body is loop-invariant, the compiler cannot see the
                # trip count, and it lifted a second copy of the layers'
                # forward pass out of the loop (PERF.md, PR 49)
                (_, stats), y = pipe_step(carry0, 0)
                outs = y[None]
            else:
                (_, stats), ys = lax.scan(
                    pipe_step, carry0, jnp.arange(slots))
                # the last stage's slot pp - 1 + m ran microbatch m
                outs = ys[pp - 1:]
            # the head and the loss, once a microbatch: every stage runs
            # its rounds of them (a masked pass is a pass, forward and
            # backward, and inside the slots there were M + pp - 1 a stage)
            mine = share_outputs(outs)
            mb_of = jnp.asarray(share)[stage]
            loss_sum = jnp.zeros((), jnp.float32)
            for r in range(len(share[0])):
                lmb = mb_loss(mine[r], jnp.maximum(mb_of[r], 0))
                loss_sum = loss_sum + jnp.where(mb_of[r] >= 0, lmb, 0.0)
        # every stage summed its own microbatches; already replicated over
        # tp. Normalize to the GLOBAL batch mean: local token count is
        # M*Bm*T, and the extra 1/dp makes the implicit sum over dp ranks a
        # global mean.
        loss_sum = lax.psum(loss_sum, ("pp", "cp") if cp > 1 else "pp")
        # every stage counted its own layers, every cp shard its own rows
        stats = jax.tree.map(
            lambda c: lax.psum(c, ("pp", "cp") if cp > 1 else "pp"), stats)
        return loss_sum / (M * Bm * T * cp * dp), stats

    return shard_loss


def _sync_axes(spec: P) -> Tuple[str, ...]:
    used = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            used.update(entry)
        else:
            used.add(entry)
    return tuple(a for a in MESH_AXES if a not in used)


def sync_grads(grads, specs):
    """psum each grad leaf over the mesh axes its param is replicated on."""
    def f(g, s):
        axes = _sync_axes(s)
        return lax.psum(g, axes) if axes else g
    return jax.tree.map(f, grads, specs, is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------------------------------
# Public train step factory
# --------------------------------------------------------------------------

def make_train_step(cfg, mesh: Mesh, num_microbatches: Optional[int] = None,
                    hp: Optional[AdamWConfig] = None,
                    remat: Union[bool, str] = True,
                    attn_impl: str = "auto", loss_fn=None,
                    ffn_impl: Optional[str] = None,
                    with_stats: bool = False):
    """Model-agnostic entry (VERDICT r3 task #2).

    cfg: a LlamaConfig (the hand-optimized flagship path below) OR any
    `nn.Layer` — Layers route to the generic compiled engine
    (hybrid_generic.GenericHybridEngine: manual dp/pp GPipe + GSPMD tp)
    and the returned step closes over engine state:
    `step(x, labels) -> loss`, with the engine on `step.engine`.
    `loss_fn` is required for the Layer path.

    LlamaConfig path: returns jitted step(params, opt_state, tokens,
    targets) → (params, opt_state, loss). params must be stage-stacked +
    sharded (see shard_params); tokens/targets are [B_global, T] int32
    sharded P('dp',None). With `with_stats` the step returns a fourth
    output whatever the config, its own `MOE_STATS` as a dict of int32
    scalars: launches of `llama.routed_ffn_load` (a sparse layer on dp =
    1), the pairs they routed, those on the experts held here, the sum over
    launches of the fullest held expert's rows, and the launches that took
    the sorted form's whole form; all zero where no layer runs it.

    remat: True = full per-block rematerialization (lowest memory);
    "dots" = jax.checkpoint_policies.dots_saveable — saves matmul outputs and
    recomputes only elementwise/norm work in backward (≈20% faster on the
    v5e-class chip, measured 0.353 vs 0.291 MFU on the bench config);
    False = save everything (usually OOMs beyond toy sizes).
    attn_impl: "auto" (Pallas flash on TPU when supported), "flash" (force),
    anything else = plain XLA attention.
    ffn_impl: None resolves FLAGS_pallas_ffn HERE, at build time (the flag
    never reaches traced code — trace purity); "pallas" forces the fused
    SwiGLU kernel on supported shapes; anything else = stock XLA FFN.
    num_microbatches: None resolves FLAGS_pp_accumulate_steps at build
    time (same discipline), so a tuned profile's microbatch pin applies
    without threading a ctor arg through every training entry.
    """
    # apply any FLAGS_tuned_profile before the flag-backed knobs
    # (microbatches, pallas_ffn) are resolved into the executable
    from .. import tuner as _tuner
    from .pipeline import runtime as _pprt  # noqa: F401 (defines pp_* flags)
    _tuner.maybe_apply_flagged()
    if num_microbatches is None:
        num_microbatches = max(
            1, int(flags.flag_value("pp_accumulate_steps")))
    if not isinstance(cfg, L.LlamaConfig):
        from .hybrid_generic import GenericHybridEngine

        if loss_fn is None and getattr(cfg, "_loss_fn", None) is not None:
            loss_fn = cfg._loss_fn
        if loss_fn is None:
            raise ValueError("make_train_step(Layer, ...) needs loss_fn=")
        eng = GenericHybridEngine(cfg, mesh, loss_fn, hp=hp,
                                  num_microbatches=num_microbatches)

        def step(x, labels):
            return eng.train_batch(x, labels)

        step.engine = eng
        return step
    hp = hp or AdamWConfig()
    if ffn_impl is None:
        from ..ops.pallas import fused_ffn as _ff

        ffn_impl = "pallas" if (flags.flag_value("pallas_ffn")
                                and _ff.available()) else "stock"
    specs = param_specs(cfg)
    loss_and_grads = _per_shard_loss_and_grads(
        cfg, mesh, num_microbatches, remat, attn_impl, ffn_impl, with_stats)
    opt_specs = {"m": specs, "v": specs, "step": P()}

    def per_shard_step(params, opt, tokens, targets):
        loss, grads, stats = loss_and_grads(params, tokens, targets)
        # global grad-norm² for clipping: local shards' sq-sums + psum over the
        # axes each leaf is sharded on (replicated leaves are already synced).
        with jax.named_scope("grad_norm"):
            sq = 0.0
            for g, s in zip(jax.tree.leaves(grads),
                            jax.tree.leaves(
                                specs, is_leaf=lambda x: isinstance(x, P))):
                loc = jnp.sum(g.astype(jnp.float32) ** 2)
                shard_axes = tuple(a for a in MESH_AXES
                                   if a not in _sync_axes(s))
                sq = sq + (lax.psum(loc, shard_axes) if shard_axes else loc)
        with jax.named_scope("adamw"):
            new_params, new_opt = _adamw_update(params, grads, opt, hp, sq)
        return (new_params, new_opt, loss) + (stats,) * with_stats

    step = jax.shard_map(
        per_shard_step, mesh=mesh,
        in_specs=(specs, opt_specs, P("dp", "cp"), P("dp", "cp")),
        out_specs=(specs, opt_specs, P()) + (P(),) * with_stats,
        check_vma=False)
    return jax.jit(step, donate_argnums=(0, 1))


def _per_shard_loss_and_grads(cfg, mesh: Mesh, num_microbatches: int,
                              remat, attn_impl: str, ffn_impl: str,
                              with_stats: bool, chosen: bool = False):
    """The half of the train step before the optimizer, per shard:
    (params, tokens, targets) -> (loss, synced grads, stats), the loss the
    global-batch mean on every device, stats as `_make_shard_loss` has
    them."""
    dp, pp, cp, tp = (mesh.shape[a] for a in MESH_AXES)
    specs = param_specs(cfg)
    shard_loss = _make_shard_loss(cfg, num_microbatches, dp, pp, tp, cp,
                                  remat, attn_impl, ffn_impl, with_stats,
                                  chosen)

    def loss_and_grads(params, tokens, targets):
        (loss, stats), grads = jax.value_and_grad(shard_loss, has_aux=True)(
            params, tokens, targets)
        with jax.named_scope("grad_sync"):
            grads = sync_grads(grads, specs)
            loss = lax.psum(loss, "dp")  # replicate the global mean for reporting
        return loss, grads, stats

    return loss_and_grads


def make_loss_and_grads(cfg: L.LlamaConfig, mesh: Mesh,
                        num_microbatches: int = 1,
                        remat: Union[bool, str] = True,
                        attn_impl: str = "auto", ffn_impl: str = "stock",
                        chosen: bool = False):
    """The very loss `make_train_step` differentiates, without the
    optimizer: jitted f(params, tokens, targets) -> (loss, grads, the
    step's `MOE_STATS`) on the step's sharding layout, grads laid out like
    the params. What a comparison with a reference reads before any
    optimizer state exists; with `chosen` (a mesh of dp = pp = cp = 1) the
    stats also hold "chosen" [launches, rows, top_k] i32, the experts each
    launch of the routed FFN gave its rows, for a reference to send every
    row where the program sent it."""
    specs = param_specs(cfg)
    f = _per_shard_loss_and_grads(cfg, mesh, num_microbatches, remat,
                                  attn_impl, ffn_impl, True, chosen)
    return jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(specs, P("dp", "cp"), P("dp", "cp")),
        out_specs=(P(), specs, P()), check_vma=False))


def make_eval_step(cfg, mesh: Mesh, num_microbatches: int = 1, loss_fn=None,
                   train_step=None):
    """Jitted loss-only step (no grads) with the same sharding layout.
    cfg: LlamaConfig (flagship path) or any nn.Layer (routes to the
    generic engine, mirroring make_train_step).

    Layer path: pass `train_step` (the callable make_train_step returned)
    to evaluate that step's LIVE engine state; without it, the eval step
    re-reads the Layer's current Tensors before every call so updates made
    elsewhere (another engine after sync_to_layer, eager code) are seen."""
    if not isinstance(cfg, L.LlamaConfig):
        from .hybrid_generic import GenericHybridEngine

        if loss_fn is None and getattr(cfg, "_loss_fn", None) is not None:
            loss_fn = cfg._loss_fn
        if loss_fn is None:
            raise ValueError("make_eval_step(Layer, ...) needs loss_fn=")
        shared = getattr(train_step, "engine", None)
        eng = shared or GenericHybridEngine(
            cfg, mesh, loss_fn, num_microbatches=num_microbatches)

        def step(x, labels):
            if shared is None:
                eng.refresh_from_layer()
            return eng.eval_batch(x, labels)

        step.engine = eng
        return step
    dp, pp, cp, tp = (mesh.shape[a] for a in MESH_AXES)
    specs = param_specs(cfg)
    shard_loss = _make_shard_loss(cfg, num_microbatches, dp, pp, tp, cp,
                                  remat=False)

    def per_shard(params, tokens, targets):
        return lax.psum(shard_loss(params, tokens, targets)[0], "dp")

    f = jax.shard_map(per_shard, mesh=mesh,
                      in_specs=(specs, P("dp", "cp"), P("dp", "cp")),
                      out_specs=P(), check_vma=False)
    return jax.jit(f)
