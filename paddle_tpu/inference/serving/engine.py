"""Paged-KV continuous-batching serving engine.

The integration layer the block manager (memory), scheduler (policy) and
`block_multihead_attention_` (compute) were built toward: ONE jitted
fixed-shape program serves every step of a mixed prefill+decode batch.

TPU-native shape (the MPK argument, PAPERS.md arxiv 2512.22219): instead
of per-request kernel launches over ragged inputs, every scheduler tick
packs its chunk mix into a `[token_budget]` token vector + `[max_batch]`
length/table rows and runs the SAME compiled executable — prefill chunks,
decode steps and any blend of the two share one signature, so the steady
state performs **zero retraces** (executables are cached keyed by the
(token-budget, batch-slots) signature, counted by
``paddle_serving_step_builds_total``). The KV cache is a donated carry
([L, num_blocks, KV, block_size, hd] per side), so XLA updates pages in
place; prefix-cache sharing and preemption are pure block-table edits.

Client surface:

- ``submit(...) -> rid`` with admission control (:class:`RejectedError`
  on queue overflow), per-request priority/deadline/sampling knobs;
- ``step()`` — one tick's :class:`TokenEvent` records (the streaming
  unit): one token a decoding sequence for an autoregressive model, none
  or a whole block's for a block-diffusion one (below). A tick is one
  scheduler pass + one fused device step; the next one is launched before
  this one's ids are read wherever counts determine it, its decode rows
  fed from this one's output on the device, so the chip does not wait for
  the host between two ticks (``step`` says when);
- ``stream(rid)`` — iterator of tokens as they are produced;
- ``run()`` — drain everything, return :class:`Completion` list
  (greedy/sampling parity with ``LLMPredictor``).

Generation by diffusion over blocks (``cfg.block_length`` Bd > 0, SDAR):
a step does not yield one token a sequence. Attention is block-causal
(position i sees j iff j // Bd <= i // Bd). A prompt is prefilled up to its
last whole block; its tail opens the first generated block, whose other
rows are masked (input id ``cfg.mask_token_id``). A running sequence brings
its open block's Bd rows to every tick. While a row is masked the tick is a
*denoise forward* of the block: all Bd rows go through the head, and on the
device (scope ``unmask``) each masked row proposes x0 = argmax(logits) with
confidence softmax(logits)[x0], and the k_s = min(masks left, ceil(Bd / T))
most confident masked rows (ties to the lower position) become tokens —
remasking ``low_confidence_static``, T the request's ``denoising_steps``.
The host fetches [max_batch, Bd] ids, confidences and flags, never logits.
Once no row is masked the next tick is the block's *commit forward*: the
same rows, whose keys and values now stand in the pages (every forward
writes them; the commit's overwrite is the one that is read later), after
which the block is committed: ``num_computed += Bd`` and its new tokens
come out as TokenEvents of one tick. What the last block holds beyond
``max_new_tokens`` is computed and dropped. A tick that holds only block
rows takes the ``tok_pad = max_batch * Bd`` executable, one with a prefill
chunk the ``token_budget`` one; both run the mixed attention launch.
These ticks are launched ahead like any other. A forward takes exactly k_s
rows, so the masks a block has left, hence whether its next forward is a
denoise or the commit, that forward's quota, the block behind a commit
(all masked) and the tick in which ``max_new_tokens`` is reached are counts
the host holds before it reads a tick. Which rows were taken and their ids
it does not hold: the tick behind gets the block as the tick in flight got
it, and on the device takes x0 and drops the mask flag where that tick's
output says a row was taken. The sequence itself (``block_ids``,
``block_masked``, ``num_computed``, tokens, events, counters) is written at
the harvest alone, a tick late.
Sampling (temperature > 0), a draft model, LoRA adapters and int8 pages
are refused with a block-diffusion config.

The tick has ONE loop over layers, for every config (``_layer_loop``):
``llama.scan_plan`` over ``cfg.kinds``, one traced body a kind. What a
config's layers are is ``LlamaConfig.layers``' to say and nobody else's: a
uniform config is a plan of one kind (one ``lax.scan`` over its whole
stack, ``params["blocks"]`` read as a tuple of one), so its page pools,
attention launches and rope tables are derived as a plan's are. A written
layer plan (``cfg.layer_plan``: Laguna-XS.2's leading dense layer, window
and full attention with their own head counts and ropes, sparse layers
beside a shared expert) is the same loop with more kinds. With
window layers the pages live in two pools with two lifetimes: the full
layers' ``[L_full, num_blocks, ...]``, which keeps every page of a sequence,
and the window layers' ``[L_window, window_blocks, ...]``, whose pages go back
once every position in them lies more than ``sliding_window`` - 1 behind the
sequence's computed length (``BlockManager.release_behind``, after a tick is
harvested). A sequence has a block table over each; the prefix cache is off
(``engine_stats["prefix_cache"]`` says so). With a WRITTEN plan, int8 pages,
weight quantisation, LoRA adapters, a draft model, the fused FFN and
``extract_pages`` / ``ingest_pages`` are refused: each was never judged
against a reference under a plan, so each refusal is a policy stated once
(``__init__``, ``submit``, ``_refuse_page_handoff``, ``_resolve_ffn``), not
a branch of the loop, which carries LoRA, the int8 pages' scales, QK-norm,
``block_length`` and the fused FFN for whatever kind of layer takes them.

Latent attention (a plan whose layers are ``attn="latent"``: DeepSeek-V3's
block, Kimi-K2's, dots3-note's) is served over LATENT page pools: an array
``[L, num_blocks, 1, block_size, W]`` and no value pool. A position's row is
``llama.latent_kv``'s (latent | rope key) at the widths of the layer's own
spec (``LayerSpec.latent``), in whole lanes: 576 values in W = 640 at Kimi's
widths (``paged_attention_latent`` says why). A plan's latent layers may be
of two kinds (``_pool_plan``): those WITHOUT a window share the pool, one
row width; those WITH one (``LatentSpec.window``) live in the window pool at
their own row width (dots3-note: 1,088 values in 1,152 lanes) under the
window pool's block table and lifetime, released behind the window as
Laguna's are. Where the full kind has a learned sparse INDEX
(``LatentSpec.index``), its pages carry a second row a position, the index
key (``[L_full, num_blocks, 1, block_size, IW]``, the index head's width in
whole lanes, riding where a value pool would, which a latent pool has not:
same page numbers, same block table, same lifetime), and
the layer runs ``paged_index_select`` (index keys written, every visible
key scored, the ``topk`` best selected exactly) before its read. Every
launch, the decode tick's and the tick's with a prefill chunk, goes through
``paged_latent_attention``, which states the one rule of which read a row
takes (the dense walk; the sparse read over the selected rows for a
sequence that holds more than ``topk`` keys, gathered or, for a chunk under
the crossing, through the masked walk; the windowed walk) and in
which form (absorbed; the expanded form for chunks was measured and is not
taken). Counters (``_plan_keys``): ``attn_keys_latent`` / ``attn_pairs_latent``
of the dense walk, ``attn_*_latent_window`` of the windowed one,
``index_keys`` / ``index_pairs`` scored (``index_keys_fetched``: the keys
the index's two launches copied out of their pages to score them, whole key
tiles and a chunk's every work item its own; ``index_blocks`` the key blocks
those came in and ``index_blocks_run`` the ones that were ONE copy because
their pages lie side by side in the pool), ``sparse_pairs_selected`` read,
``sparse_rows_dense`` rows that had no selection to make,
``sparse_rows_walked`` / ``sparse_pairs_walked`` the selecting chunk rows
that read their keys through the masked walk and the (row, key) pairs it
multiplied for them, ``index_pages_live``. Without a window layer the pages
are of one kind and one lifetime, so the prefix cache, copy-on-write and
preemption work as for a uniform model; with one the prefix cache is off,
as for every window pool. Page hand-off is refused as for every plan. A config that holds a share of its
routed experts (``cfg.experts_held``) routes over all of them and computes
its own (``llama.routed_ffn_load``); ``moe_pairs_held`` counts those pairs,
and ``moe_compact_overflow`` the launches that had more of them than
``llama.held_pair_slots`` gives places and so took the whole form.

A learned sparse index may also stand over the heads' OWN keys and values
(``LlamaConfig.index`` on a uniform config: Keye-VL-2.0). Beside a value
pool the index keys cannot ride in its place, so they live in a THIRD
stacked array, ``_index_cache`` ``[L, num_blocks, 1, block_size, IW]`` (IW
the index head's width in whole lanes), under the same page numbers, block
table, lifetime and prefix hashes: the tick donates and carries it with the
two pools, ``_copy_blocks`` copies a page's index keys with the page,
preemption and resume rewrite them with the rows, ``kv_page_bytes`` (hence
``BlockManager.bytes_total`` and ``engine_stats``) counts them, and the
prefix cache serves pages whose index keys another request wrote. The layer
runs ``paged_index_select`` and hands its selection to
``paged_layer_attention``, which states the one rule of which read a row
takes (the dense walks; for a sequence that holds more than ``topk`` keys
the masked walk of a chunk's rows, and of a decode row the masked walk or,
past a measured crossing, the gather). The index's counters are those above, and a
step span also carries ``prefix_hit_tokens``. A tick with a chunk is padded
to an eighth of the token budget where its rows fit (``_row_pads``): under
the index a padded row costs ``max_len`` keys in the selection. What was
never judged against a reference under such an index is refused once, where
a written plan's refusals stand: int8 pages, weight quantisation, LoRA
adapters, a draft model, the fused FFN (``__init__``, ``submit``) and
``extract_pages`` / ``ingest_pages`` (``_refuse_page_handoff``: the payload
carries no index keys).

The residual stream may be LANES (``cfg.hyper_lanes``: manifold-constrained
hyper-connections, Xing4.0's ``hc_mult`` 4): the embedded row is copied to n
lanes kept flat, ``[tok, n d]`` (``llama.hyper_spread``), every sub-block of
the layer loop goes through ``llama.residual`` (the one seam: the plain sum
``x + F(norm(x))`` of every other config, traced as it always was; here a
learned mix of the lanes in, a doubly stochastic mix of them and the
sub-block's output back), and the head reads the lanes' sum
(``llama.hyper_collapse``, in ``_build_step``'s one ``head``). The lanes live
inside a tick: pages, block tables, the scheduler and the prefix cache hold
what they held. Scopes ``hyper`` > ``hyper_coeff`` (a row's norm, its
product with phi, the Sinkhorn rounds), ``hyper_pre`` (the sub-block's
input), ``hyper_post`` (the lanes' update, inside the scope in which the
sub-block's output is added: ``attn_out``, ``moe`` or ``ffn``); the step
span's ``hyper_rows`` = live rows x 2 x layers, summed in
``stats["hyper_rows"]``. Refused with such a stream, where a written plan's
refusals stand and by name: int8 pages, weight quantisation, LoRA adapters,
a draft model, the fused FFN (``__init__``, ``submit``, ``_resolve_ffn``)
and ``extract_pages`` / ``ingest_pages`` (``_refuse_page_handoff``).

A layer may keep NO keys at all: a state-space layer (``LayerSpec.attn =
"ssm"``: Mamba-2, granite-4.0-h's 36 of 40 layers) carries a recurrent state
a sequence, not a row a token. It lives in a STATE POOL beside the page pools
(``_state``: ``[L_ssm, slots + 1, H, P, N]`` in ``llama.SSM_STATE_DTYPE``,
float32, and ``[L_ssm, slots + 1, (d_conv - 1) conv_dim]`` of the convolution's
carried rows; the last slot is the void one that idle batch entries write),
donated and carried through the tick with them. A running sequence owns one
of ``max_batch`` slots (``BlockManager(state_slots=...)``: taken at
admission, given back at finish, cancel and preemption); ``_launch`` sends each batch entry's slot,
and the layer loop's body of that kind is ``llama.ssm_mixer`` (scopes ``ssm``
> ``ssm_in``, ``ssm_conv``, ``ssm_step``, ``ssm_scan``, ``ssm_gate``,
``ssm_out``; beside the kernel the one-row update is the launch of
``ops/pallas/ssm_step.py``): a segment starts from its slot's state, or from zeros where it
has nothing behind it, whatever the slot holds, so admission and preemption
cost no device operation, and a preempted sequence is recomputed from its
ids. The page pools hold the attention layers alone. A page hit cannot
restore a state, so the prefix cache is off (``engine_stats["prefix_cache"]``
says so) and copy-on-write never runs. Launch-ahead stays on: the next tick
is determined by counts, and the state is on the device in tick order. The
step span carries ``ssm_step_rows`` (one-row segments x state-space layers),
``ssm_scan_rows`` and ``ssm_segments`` (the longer segments' rows and count,
x layers) and ``state_slots_live``; ``stats`` sums them, ``engine_stats``
has ``state_slots``, ``state_bytes_total`` and ``state_bytes_in_use``. Such a
config may also state a layer of heads' softmax scale, have no rope, scale
the embedded row, every sub-block's output and the logits, and tie its head
(``llama.embedded``, ``llama.residual``, ``llama.head_logits``). Refused with
state-space layers, where a written plan's refusals stand and by name: int8
pages, weight quantisation, LoRA adapters, a draft model (a rejected draft
would need the state rolled back), the fused FFN and ``extract_pages`` /
``ingest_pages`` (the payload carries no state).

SLO metrics (TTFT/TPOT histograms, queue-depth and KV-block-utilization
gauges, admit/preempt/shed counters + flight-recorder events) flow
through ``observability.emit`` — ``observability.summary()["serving"]``
is the operator digest.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...core import flags
from ...models import llama as L
from ...observability import emit as _emit
from ...observability import tracing as _tracing
from ...ops.kernels.serving_attention import (index_select_form,
                                              paged_index_select,
                                              paged_latent_attention,
                                              paged_layer_attention,
                                              sparse_walk_keys,
                                              sparse_walk_keys_heads)
from ...ops.pallas import flash_attention as FA
from ...ops.pallas import fused_ffn as FF
from ...ops.pallas import fused_sample as FS
from ...ops.pallas import paged_attention as PA
from ...ops.pallas import paged_attention_latent as PL
from .. import quant as Q
from . import adapters as AD
from . import speculative as SP
from .block_manager import BlockManager, NoFreeBlocksError
from .scheduler import (FINISHED, UNKNOWN, Completion,
                        DeadlineExceededError, RejectedError, ScheduledBatch,
                        Scheduler, Sequence)

# step-geometry flags: the executable signature is keyed on
# (token_budget, batch_slots), so these are exactly the knobs a tuned
# profile (tuner/profile.py) pins per (model, topology). Ctor args left
# at None read them, so applying a profile BEFORE engine construction
# takes effect with zero steady-state retraces.
flags.define_flag("serving_token_budget", 64,
                  "Default token budget per scheduler tick (the padded "
                  "token-vector length of the fused step executable) "
                  "when the PagedServingEngine ctor leaves it unset.")
flags.define_flag("serving_max_batch", 8,
                  "Default concurrent sequence slots per step when the "
                  "PagedServingEngine ctor leaves max_batch unset.")

flags.define_flag("serving_window_blocks", 0,
                  "Pages of the window layers' pool (a model with "
                  "sliding-window layers) when the PagedServingEngine ctor "
                  "leaves window_blocks unset; 0 = max_batch sequences' "
                  "windows and chunks.")

__all__ = ["PagedServingEngine", "TokenEvent", "RejectedError",
           "DeadlineExceededError"]

# chaos harness hook (site "serving"): installed by
# distributed/fault_tolerance/chaos.py while a spec is active
_CHAOS_HOOK = [None]


def set_chaos_hook(fn):
    _CHAOS_HOOK[0] = fn


@dataclass
class TokenEvent:
    """One streamed token (or a terminal event with token < 0)."""
    rid: int
    token: int                 # -1 for compute-free terminal events
    finished: bool
    reason: Optional[str] = None   # stop | length | deadline | cancelled


@dataclass(eq=False)
class _Tick:
    """One tick between its launch and its harvest: what `_launch` planned
    and called, for `_harvest` to read a call of `step()` later."""
    events: List[TokenEvent]        # deadlines that fell while scheduling
    ahead: bool                     # launched behind a tick not yet read
    batch: Optional[ScheduledBatch] = None     # None: nothing was scheduled
    out: Any = None                 # the executable's `nxt`, on the device
    all_arg: Any = None             # a speculative tick's verify read
    t0: int = 0                     # perf_counter_ns at the call
    # item index by rid of the sequences this tick yields a token (under
    # block diffusion: whose open block it runs): the entry of `out` that a
    # row launched behind it feeds from
    slots: Dict[int, int] = field(default_factory=dict)
    # per item, `num_computed` once this tick's rows are in (None: a
    # speculative chunk or a block-diffusion tick, counted at harvest)
    ends: List[Optional[int]] = field(default_factory=list)
    # block diffusion: the `quota` the tick was called with, per item (0
    # beside a block: its commit forward)
    quota: Any = None
    n_prefill: int = 0
    # rows of sequences with no token out when the tick was planned, an
    # open block's aside: a prompt's chunks, the last one too, and a
    # turn's new part over cached pages
    prompt_rows: int = 0
    spec_plan: Dict[int, List[int]] = field(default_factory=dict)
    in_block: List[bool] = field(default_factory=list)
    was_decode: List[bool] = field(default_factory=list)
    tok_pad: int = 0
    decode: bool = False            # the one-row launch
    ffn_mode: bool = False
    lens: Tuple[Any, ...] = ()      # cu, dec_lens, this_lens
    pages: int = 0                  # table entries assigned (int8 pages)
    # pages allocated in (the pool, the window pool) when it was launched
    pool_pages: Tuple[int, int] = (0, 0)
    sampled_rows: int = 0           # rows with a temperature: 0 skips the sort
    tables: Any = None              # under a sparse index: the full pool's
    slots_live: int = 0             # state slots that held a sequence


def _in_window_pool(spec: "L.LayerSpec") -> bool:
    """Whether a layer's pages live in the window pool: a window layer of
    heads' own keys and values, or a latent layer whose spec has a
    window."""
    return spec.attn == "window" or bool(spec.latent and spec.latent.window)


def _pool_plan(cfg: "L.LlamaConfig"):
    """((layers of the pool, of the window pool), (a row's width in each
    pool), the window, the full layers' IndexSpec or None) of a config. A
    row is a head (`head_dim`), or a latent layer's (latent | rope key) in
    whole lanes. The index stands over a latent pool or over the heads' own
    keys and values (`LayerSpec.sparse_index`): its keys are one more row a
    position of the full pool's pages either way. Each pool is one array,
    so its layers share one row width,
    one window and one index geometry; a plan that asks for two of a kind
    in one pool is refused."""
    window = [s for s in cfg.layers if _in_window_pool(s)]
    full = [s for s in cfg.layers
            if not _in_window_pool(s) and s.attn != "ssm"]

    def one(values, what):
        values = set(values)
        if len(values) > 1:
            raise NotImplementedError(
                f"layers of one page pool with {what} {sorted(values)}: a "
                "pool is one array, and no model served has them")
        return next(iter(values), 0)

    def width(specs):
        return one([PL.padded_width(s.latent.width) if s.latent
                    else cfg.head_dim for s in specs] or [cfg.head_dim],
                   "row widths")

    index = {s.sparse_index for s in full} - {None}
    if len({(i.head_dim, i.topk) for i in index}) > 1:
        raise NotImplementedError(
            f"index layers of two geometries {sorted(index)}: their keys "
            "share the full layers' pages, one width a position")
    return ((len(full), len(window)),
            (width(full), width(window)),
            one([s.latent.window if s.latent else cfg.sliding_window
                 for s in window], "windows"),
            next(iter(index), None))


def _sample_rows(logits, keys, temps, top_ps, top_k: int):
    """Per-row temperature/top-k/top-p sampling on f32 logits [B, V] —
    the batched form of llm.py's `_sample_next` (same masking math, so
    the paged engine's sampling distribution matches LLMPredictor's).
    temps/top_ps [B]; keys [B, 2] uint32; top_k static (0 = off)."""
    l = logits / jnp.maximum(temps, 1e-6)[:, None]
    if top_k:
        # top_k is a static python int (see docstring) — int() is trace-free
        vals = jax.lax.top_k(l, int(top_k))[0]  # tpu-lint: disable=TPL001
        l = jnp.where(l < vals[..., -1:], -jnp.inf, l)
    sl = jnp.sort(l, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sl, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = cum - probs < top_ps[:, None]          # exclusive prefix mass
    cutoff = jnp.min(jnp.where(keep, sl, jnp.inf), axis=-1, keepdims=True)
    l = jnp.where(l < cutoff, -jnp.inf, l)
    return jax.vmap(lambda k, row: jax.random.categorical(
        jax.random.wrap_key_data(k), row))(keys, l).astype(jnp.int32)


def _unmask_rows(logits, masked, quota):
    """One denoise forward's transfer, on the device (low_confidence_static
    at temperature 0). logits [B, Bd, V] f32 of every slot's block; masked
    [B, Bd] bool, the rows still masked; quota [B] i32, how many of them
    become tokens now (0 on a commit forward, a prefill chunk or an idle
    slot). Each row proposes x0 = argmax(logits) with confidence
    softmax_f32(logits)[x0] = 1 / sum(exp(logits - max)); the `quota`
    masked rows of highest confidence are taken, ties to the lower
    position. Returns int32 [B * Bd * 3]: per row x0, the confidence's
    float32 bits, and whether it was taken — what the host fetches instead
    of logits."""
    Bd = logits.shape[1]
    top = jnp.max(logits, axis=-1)
    x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)           # [B, Bd]
    conf = 1.0 / jnp.sum(jnp.exp(logits - top[..., None]), axis=-1)
    c = jnp.where(masked, conf, -1.0)
    lower = jnp.arange(Bd)[None, :] < jnp.arange(Bd)[:, None]    # [i, j]: j<i
    beats = masked[:, None, :] & (
        (c[:, None, :] > c[:, :, None])
        | ((c[:, None, :] == c[:, :, None]) & lower[None]))      # [B, i, j]
    rank = jnp.sum(beats, axis=-1, dtype=jnp.int32)
    take = masked & (rank < quota[:, None])
    return jnp.stack([x0, lax.bitcast_convert_type(conf, jnp.int32),
                      take.astype(jnp.int32)], axis=-1).reshape(-1)


def _key_bits(key) -> np.ndarray:
    """Raw uint32[2] view of a PRNG key (typed or legacy)."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return np.asarray(key)


class PagedServingEngine:
    """Continuous batching over a paged KV cache. Typical use::

        eng = PagedServingEngine(cfg, params, num_blocks=64, block_size=16,
                                 max_batch=8, token_budget=64)
        rid = eng.submit([1, 2, 3], max_new_tokens=32, eos_token_id=2)
        for tok in eng.stream(rid):   # streaming
            ...
        done = eng.run()              # or drain everything
    """

    def __init__(self, cfg: L.LlamaConfig, params: Dict[str, Any],
                 num_blocks: Optional[int] = None, block_size: int = 16,
                 max_batch: Optional[int] = None,
                 token_budget: Optional[int] = None,
                 max_len: Optional[int] = None,
                 prefill_chunk: Optional[int] = None, top_k: int = 0,
                 max_queue: Optional[int] = None, cache_dtype=None,
                 weight_dtype=None, quant_mode: Optional[str] = None,
                 quant_kv: Optional[bool] = None, quant_manifest=None,
                 pallas: Optional[bool] = None,
                 pallas_ffn: Optional[bool] = None,
                 adapter_slots: Optional[int] = None,
                 draft: Optional[Any] = None,
                 spec_k: Optional[int] = None,
                 window_blocks: Optional[int] = None):
        plan = bool(cfg.layer_plan)
        # a sparse index over the heads' own keys and values (a uniform
        # config's, `cfg.index`): refused what a written plan is refused
        indexed = any(s.index is not None for s in cfg.layers)
        # and hyper-connections (`cfg.hyper_lanes`), under a plan or not
        hyper = bool(cfg.hyper_lanes)
        # and state-space layers (always under a plan)
        ssm = {s.ssm for s in cfg.layers} - {None}
        if plan or indexed or hyper:
            asked = {"a draft model": draft is not None,
                     "pallas_ffn": bool(pallas_ffn),
                     "quant_mode": bool(Q.resolve_quant_mode(quant_mode)),
                     "quant_kv (int8 pages)": bool(
                         flags.flag_value("quant_kv_cache")
                         if quant_kv is None else quant_kv),
                     "adapter_slots (LoRA)": adapter_slots is not None}
            if any(asked.values()):
                what = ("state-space layers" if ssm else "hyper-connections"
                        if hyper else "a layer plan" if plan else
                        "a sparse index over the heads' own keys and values")
                raise NotImplementedError(
                    f"a config with {what} is served in fp weights and "
                    "fp pages, one token a row: "
                    f"{[k for k, v in asked.items() if v]} were never judged "
                    f"against a reference under {what}; drop them")
        if cfg.num_experts and (draft is not None or pallas_ffn
                                or Q.resolve_quant_mode(quant_mode)):
            raise NotImplementedError(
                "PagedServingEngine serves routed experts in fp weights "
                "through models.llama.routed_ffn; a draft model, the fused "
                "Pallas FFN (pallas_ffn=True) and weight quantisation "
                "(quant_mode) cover dense FFNs only: drop them for a "
                "config with num_experts (int8 pages, quant_kv, are fine)")
        Bd = int(cfg.block_length)
        if Bd and (draft is not None or top_k):
            raise NotImplementedError(
                f"a block-diffusion config (block_length={Bd}) is served "
                "greedily, a block at a time: a draft model (speculative "
                "decoding proposes one token a row) and top_k sampling do "
                "not apply; drop them")
        if Bd and block_size % Bd:
            raise ValueError(
                f"block_size={block_size} is no multiple of the model's "
                f"block_length={Bd}: a block's keys and values must lie in "
                "one page, and a prefix hit ends on a block boundary")
        # apply any FLAGS_tuned_profile before geometry is resolved and
        # executables are keyed, so a pinned profile is zero-retrace
        from ... import tuner as _tuner
        _tuner.maybe_apply_flagged()
        if max_batch is None:
            max_batch = int(flags.flag_value("serving_max_batch"))
        if token_budget is None:
            token_budget = int(flags.flag_value("serving_token_budget"))
        self.cfg = cfg
        if weight_dtype is not None:
            params = jax.tree.map(
                lambda a: a.astype(weight_dtype)
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating) else a,
                params)
        # quantized serving (inference.quant): weight transform + int8
        # paged KV. None = read the FLAGS_quant_* surface.
        self.quant_mode = Q.resolve_quant_mode(quant_mode)
        if quant_kv is None:
            quant_kv = bool(flags.flag_value("quant_kv_cache"))
        self.quant_kv = bool(quant_kv)
        if Bd and self.quant_kv:
            raise NotImplementedError(
                f"int8 pages (quant_kv) with block_length={Bd}: a denoise "
                "forward's keys are rewritten by the commit forward, and "
                "their rounding was never judged against the reference; "
                "serve a block-diffusion config with fp pages")
        manifest = Q.resolve_manifest(quant_manifest)
        if self.quant_kv and manifest is None:
            raise ValueError(
                "quant_kv needs calibrated KV scales: run "
                "inference.quant.calibrate over a sample workload, "
                "save_manifest it, and pass quant_manifest (or set "
                "FLAGS_quant_manifest)")
        if manifest is not None:
            manifest.validate_for(cfg)
        self.params = Q.quantize_llama_params(params, self.quant_mode,
                                              manifest)
        self.max_len = int(max_len or cfg.max_seq_len)
        self.block_size = int(block_size)
        self.max_batch = int(max_batch)
        self.token_budget = int(token_budget)
        self.top_k = int(top_k)
        if self.quant_kv:
            if (cache_dtype is not None
                    and np.dtype(cache_dtype) != np.dtype(np.int8)):
                raise ValueError(
                    f"quant_kv serves int8 pages; cache_dtype="
                    f"{np.dtype(cache_dtype)} conflicts (drop it or "
                    f"disable quant_kv)")
            self.cache_dtype = jnp.int8
        else:
            self.cache_dtype = cache_dtype or cfg.dtype
        self.max_blocks_per_seq = -(-self.max_len // self.block_size)
        if num_blocks is None:
            num_blocks = self.max_batch * self.max_blocks_per_seq
        self.num_blocks = int(num_blocks)

        # dtype-aware page footprint (both cache sides, all layers, plus
        # the per-page f32 scale rows when quantized) — keeps the byte
        # gauges and the router's least-loaded placement truthful
        kvh = cfg.num_kv_heads
        # a latent pool: one side, a row of (latent | rope key) in whole
        # lanes for every head. A plan's latent layers may be of two
        # kinds, the one with a window in the window pool at its own
        # width; the full kind's pages carry its index keys as a second
        # row a position (`_pool_plan`)
        # the attention read, decided here and for good: None = the kernel
        # where it runs and takes this geometry (PA.selected), the stock
        # path elsewhere; True = force (interpret mode off-TPU — how CPU CI
        # drives it; a bad geometry fails here); False = the stock
        # reference
        paged = [s for s in cfg.layers if s.attn != "ssm"]
        geometry = (max(s.heads for s in paged), cfg.num_kv_heads,
                    cfg.head_dim, self.block_size)
        if pallas and not PA.supported(*geometry):
            raise ValueError(
                f"pallas=True forced but geometry H={cfg.num_heads} "
                f"KV={cfg.num_kv_heads} hd={cfg.head_dim} "
                f"block_size={self.block_size} is not supported() by the "
                f"paged-attention kernel")
        self.pallas = bool(PA.selected(*geometry) if pallas is None
                           else pallas)
        self.latent = any(s.attn == "latent" for s in cfg.layers)
        (self._pool_layers, self._row_widths, self.window,
         self._index) = _pool_plan(cfg)
        # Beside the kernel a head's row of a page is in WHOLE LANES where
        # that at most doubles it (a head of 64 in 128, as a latent row is
        # padded: `paged_attention_latent.padded_width`): the device tiles
        # a row of 64 into 128 lanes anyway or, left to lay the pool out
        # itself, puts the pages innermost and re-lays both pools out on
        # the way into and out of every tick; and only whole lanes take the
        # walks that copy whole pages (`paged_attention.whole_pages`; the
        # BlockSpec walk took 72 % of granite-4.0-h-micro's tick at 64:
        # PERF.md section 6, PR 56). The lanes behind a head hold zeros:
        # `paged_layer_attention(head_dim=...)` pads q, k and v and cuts the
        # output back, the scores and the values are what they were
        self._head_width, lanes = cfg.head_dim, PL.padded_width(cfg.head_dim)
        if (self.pallas and not self.latent
                and cfg.head_dim < lanes <= 2 * cfg.head_dim):
            self._head_width = lanes
            self._row_widths = (lanes, lanes)
        # layers whose pages live in the pool and in the window pool (a
        # config without window layers: all of them, and none)
        n_window = self._pool_layers[1]
        item = np.dtype(self.cache_dtype).itemsize
        sides = 1 if self.latent else 2
        full_bytes, window_bytes = (
            sides * kvh * self.block_size * w * item
            for w in self._row_widths)
        if self._index is not None:
            # an index key's row in whole lanes, as a latent row's
            # (`paged_attention_latent`: Mosaic copies no page whose rows
            # are half a lane tile; 64 values in 128 at Keye-VL-2.0's)
            self._index_width = PL.padded_width(self._index.head_dim)
            full_bytes += self.block_size * self._index_width * item
        hd = self._row_widths[0]
        self.kv_page_bytes = (self._pool_layers[0] * full_bytes
                              + n_window * window_bytes)
        # recurrent state: one slot a running sequence in a pool stacked
        # over the state-space layers (the module's docstring)
        if len(ssm) > 1:
            raise NotImplementedError(
                f"state-space layers of two geometries {sorted(map(str, ssm))}"
                ": their states share one pool, and no model served has "
                "them")
        self._ssm = next(iter(ssm), None)
        self._ssm_layers = sum(s.ssm is not None for s in cfg.layers)
        self.state_slots = self.max_batch if self._ssm else 0
        self.state_slot_bytes = 0
        if self._ssm:
            sm = self._ssm
            self.state_slot_bytes = self._ssm_layers * (
                sm.inner * sm.d_state * np.dtype(L.SSM_STATE_DTYPE).itemsize
                + (sm.d_conv - 1) * sm.conv_dim
                * np.dtype(cfg.dtype).itemsize)
        if self.quant_kv:
            self.kv_page_bytes += 2 * cfg.num_layers * kvh * 4
        if n_window:
            # a sequence's window pages: its window, the chunk in flight,
            # and the partly filled pages at both ends
            span = -(-(self.window + self.token_budget)
                     // self.block_size) + 2
            if window_blocks is None:
                window_blocks = (
                    int(flags.flag_value("serving_window_blocks"))
                    or self.max_batch * span)
            if window_blocks < span:
                raise ValueError(
                    f"window_blocks={window_blocks} cannot hold one "
                    f"sequence's window and chunk ({span} pages)")
        elif window_blocks:
            raise ValueError("window_blocks without a window layer")
        self.window_blocks = int(window_blocks or 0)
        self.blocks = BlockManager(
            self.num_blocks, self.block_size,
            page_bytes=self._pool_layers[0] * full_bytes
            if n_window else self.kv_page_bytes,
            hit_multiple=max(Bd, 1), window_blocks=self.window_blocks,
            window=self.window, window_page_bytes=n_window * window_bytes,
            state_slots=self.state_slots,
            state_slot_bytes=self.state_slot_bytes)
        self.scheduler = Scheduler(self.blocks, self.token_budget,
                                   self.max_batch,
                                   prefill_chunk=prefill_chunk,
                                   max_queue=max_queue, block_length=Bd)
        self._next_rid = 0
        self._completions: List[Completion] = []
        self._events_by_rid: Dict[int, List[TokenEvent]] = {}
        self.stats = {"steps": 0, "step_builds": 0, "tokens_computed": 0,
                      "cow_block_copies": 0, "pallas_steps": 0,
                      "decode_fast_steps": 0, "ffn_steps": 0,
                      "fused_ticks": 0, "tick_pallas_launches": 0,
                      "spec_ticks": 0, "attn_pages_live": 0,
                      "attn_pages_fetched": 0,
                      "attn_rows_live": 0, "attn_rows_packed": 0,
                      "ticks_ahead": 0, "ahead_void_rows": 0,
                      "ticks_sampled": 0, "sampled_rows": 0}
        # the expert counters a tick sends behind its tokens
        self._moe_fields = ()
        if cfg.num_experts:
            # routed-expert work, summed over ticks (max_load: the largest
            # seen): (row, expert) pairs a sparse layer, (layer, expert)
            # groups with at least one row, most rows on one expert in one
            # layer; with a held share of the experts the groups and the
            # load are over the held ones, `moe_pairs_held` the pairs on
            # them, summed over the sparse layers, and
            # `moe_compact_overflow` the (tick, sparse layer) launches whose
            # held pairs passed `llama.held_pair_slots` and took the
            # whole form
            self._moe_fields = ("moe_pairs", "moe_experts_hit",
                                "moe_max_load") + (
                ("moe_pairs_held", "moe_compact_overflow")
                if cfg.experts_held else ())
            self.stats.update(dict.fromkeys(self._moe_fields, 0))
        if cfg.hyper_lanes:
            # rows x sub-blocks whose lanes were mixed, summed over ticks
            self.stats["hyper_rows"] = 0
        if self._ssm:
            # summed over ticks and state-space layers: the one-row
            # segments, the longer segments' rows and their count; and the
            # slots that held a sequence when a tick was launched
            self.stats.update(ssm_step_rows=0, ssm_scan_rows=0,
                              ssm_segments=0, state_slots_live=0)
        if self.latent:
            # keys and (row, key) pairs inside the causal mask, summed over
            # ticks and layers (`_plan_keys`), and pages allocated when a
            # tick is launched, summed over ticks
            self.stats.update(attn_keys_latent=0, attn_pairs_latent=0,
                              latent_pages_live=0)
            if n_window:
                # the same of the window layers, inside their windows
                self.stats.update(attn_keys_latent_window=0,
                                  attn_pairs_latent_window=0)
        elif plan:
            # keys and (row, key) pairs inside the masks, summed over ticks
            # and over the layers of the kind, and the keys a causal mask
            # would show in all layers (`_plan_keys`)
            self.stats.update(attn_keys_full=0, attn_keys_window=0,
                              attn_keys_causal=0, attn_pairs_full=0,
                              attn_pairs_window=0)
        if self._index is not None:
            # the sparse index, summed over ticks and index layers: index
            # keys read and (row, key) pairs scored (the causal keys and
            # pairs of the sequences that select), the keys the kernels'
            # two launches copied to score them, the pairs selected, the
            # rows that had no selection to make and took the dense walk,
            # the selecting rows that took the masked walk and the pairs it
            # multiplied for them, and the pages that carry index keys
            self.stats.update(index_keys=0, index_keys_fetched=0,
                              index_blocks=0, index_blocks_run=0,
                              index_pairs=0, sparse_pairs_selected=0,
                              sparse_rows_dense=0, sparse_rows_walked=0,
                              sparse_pairs_walked=0, index_pages_live=0)
        if n_window:
            # pages allocated in each pool, summed over ticks (their ratio
            # is what the window pool saves), and pages given back so far
            self.stats.update(full_pages_live=0, window_pages_live=0,
                              window_pages_released=0)
        if Bd:
            # block diffusion, summed over ticks: sequence-forwards of each
            # kind (one sequence's block through one tick), blocks
            # committed, masked rows that became tokens, block rows computed
            self.stats.update(diff_denoise_forwards=0, diff_commit_forwards=0,
                              diff_blocks_committed=0, diff_tokens_unmasked=0,
                              diff_rows=0)
        # multi-tenant LoRA adapters: paged ref-counted device slots.
        # Always constructed (device packs allocate lazily on the first
        # registered adapter), so submit(adapter=...) works out of the box
        self.adapters = AD.AdapterManager(cfg, slots=adapter_slots)
        # adapter residency shares the KV pool's byte gauges so the
        # router's least-loaded byte tiebreak sees the real footprint
        self.blocks.extra_bytes = lambda: (self.adapters.bytes_in_use(),
                                           self.adapters.bytes_total())
        # speculative decoding: a DraftModel (or a (cfg, params) pair)
        # sharing this engine's paged-KV geometry; spec_k=0 disables
        self.spec: Optional[SP.DraftModel] = None
        self.spec_k = int(spec_k) if spec_k is not None \
            else int(flags.flag_value("spec_k"))
        if draft is not None:
            self.spec = (draft if isinstance(draft, SP.DraftModel)
                         else SP.DraftModel(*draft))
            self.spec.bind(self)
        # post-mortem sections (router precedent: last engine wins the
        # name — fleets snapshot through the router section instead)
        from ...observability import register_distress_section
        register_distress_section("adapters", self.adapters.snapshot)
        if self.spec is not None:
            register_distress_section("spec", self.spec.snapshot)
        if indexed and self.pallas and not PA.whole_pages(cfg.head_dim):
            raise NotImplementedError(
                f"a sparse index over heads of {cfg.head_dim}: the masked "
                "walk is the whole-page walks', which Mosaic takes at head "
                "dims that are whole lanes; serve it with pallas=False")
        # whether a tick's read is one of the whole-page walks (their
        # counters are reckoned only then) or the BlockSpec walk
        self._whole_pages = (self.pallas and not self.latent
                             and PA.whole_pages(self._head_width))
        # the (query heads, window) of each attention launch a tick makes
        self._launches = tuple(dict.fromkeys(
            (s.heads, self.window if _in_window_pool(s) else 0)
            for s in paged))
        # fused-FFN routing mirrors the attention tri-state: None =
        # FLAGS_pallas_ffn per tick; True = force (interpret off-TPU);
        # False = off. Forced mode validates params + geometry eagerly.
        self.pallas_ffn = pallas_ffn
        if pallas_ffn:
            blocks0 = self.params["blocks"]
            kind = FF.params_kind(blocks0)
            if kind is None:
                raise ValueError(
                    "pallas_ffn=True forced but the (quantized) param "
                    "leaves are not fusable: the fused FFN kernel covers "
                    "fp and weight-only int8 (w8); w8a8/fp8 fall back")
            w1 = blocks0["w1"] if kind == "fp" else blocks0["w1_q"]
            d, f = int(w1.shape[-2]), int(w1.shape[-1])
            rows = max(self.token_budget, self.max_batch)
            if not FF.supported(rows, d, f):
                raise ValueError(
                    f"pallas_ffn=True forced but FFN geometry d={d} f={f} "
                    f"rows<={rows} is not supported() by the fused kernel")

        # device state: the page pool, stacked over layers. The tick
        # donates it, carries it through its layer loop and returns it:
        # one buffer, updated in place
        shape = (self._pool_layers[0], self.num_blocks, kvh, self.block_size,
                 hd)
        self._key_cache = jnp.zeros(shape, self.cache_dtype)
        # The index keys of the full layers, where they have an index: one
        # more row a position of the same pages (same page numbers, same
        # block table, same lifetime, same prefix hashes). A latent pool
        # has no value side (the values are the rows' latents) and the
        # index keys ride in its place; beside heads' own keys AND values
        # they are a third array, `_index_cache`
        index_keys = self._index and jnp.zeros(
            shape[:2] + (1, self.block_size, self._index_width),
            self.cache_dtype)
        self._value_cache = (index_keys if self.latent
                             else jnp.zeros(shape, self.cache_dtype))
        self._index_cache = None if self.latent else index_keys
        if n_window:
            # two pools ride the tick's carry: (full layers', window layers')
            wshape = ((n_window, self.window_blocks) + shape[2:-1]
                      + (self._row_widths[1],))
            self._key_cache = (self._key_cache,
                               jnp.zeros(wshape, self.cache_dtype))
            self._value_cache = (self._value_cache,
                                 None if self.latent
                                 else jnp.zeros(wshape, self.cache_dtype))
        # the state pools of the state-space layers, donated and carried
        # like the page pools: (recurrent state, convolution rows)
        self._state = self._ssm and L.ssm_state_pools(
            self._ssm, self._ssm_layers, self.state_slots, cfg.dtype)
        if self.quant_kv:
            # static calibrated absmax per (layer, kv head) -> per-head
            # quant multipliers [L, KV] for the append path and GENUINELY
            # per-page dequant arrays [L, num_blocks, KV] for the read
            # path (COW copies move scale rows with their pages; today
            # every page of a layer shares the calibrated value, but the
            # layout is the per-page contract the kernel consumes)
            kab = jnp.asarray(np.asarray(manifest.kv_scales.get("k"),
                                         np.float32))
            vab = jnp.asarray(np.asarray(manifest.kv_scales.get("v"),
                                         np.float32))
            want = (cfg.num_layers, kvh)
            if kab.shape != want or vab.shape != want:
                raise ValueError(
                    f"manifest kv_scales must be [num_layers, num_kv_heads]"
                    f"={want}; got k={kab.shape} v={vab.shape} — re-run "
                    f"calibration against this model")
            self._kv_scales = (
                Q.QMAX / kab, Q.QMAX / vab,
                jnp.tile((kab / Q.QMAX)[:, None, :], (1, self.num_blocks, 1)),
                jnp.tile((vab / Q.QMAX)[:, None, :], (1, self.num_blocks, 1)))
        else:
            self._kv_scales = None
        # rope tables in the kernel's stacked [2, 1, S, hd] layout (only the
        # first hd//2 lanes of each are read): one a rope of the layers, as
        # wide as what it rotates (a uniform config: its one, whose bits
        # are `rope_cos_sin`'s)
        def rope_emb(cos, sin):
            return jnp.stack([jnp.concatenate([cos, cos], -1)[None],
                              jnp.concatenate([sin, sin], -1)[None]])
        widths = {s.rope: cfg.rope_width(s) for s in cfg.kinds[::-1]}
        # (a layer without a rope has no table)
        self._ropes = tuple(dict.fromkeys(
            s.rope for s in cfg.kinds if s.rope is not None))
        self._rope_emb = tuple(
            rope_emb(*L.rope_table(jnp.arange(self.max_len), widths[r], r))
            for r in self._ropes)
        if self._index_cache is not None:
            # behind the layers' ropes: the table that turns the whole
            # index head of a layer of heads' own keys (the layer's theta
            # at the index head's width)
            spec = next(s for s in cfg.kinds if s.index is not None)
            self._rope_emb += (rope_emb(*L.rope_table(
                jnp.arange(self.max_len), cfg.index_rope_width(spec),
                spec.rope)),)
        # The padded row counts of a tick with a chunk: the token budget,
        # and under an index over heads' own keys an eighth of it for a
        # tick whose rows fit there. Under a sparse index a padded row is
        # not free: the selection runs over the table's whole width for
        # every row of the executable (`select_topk`: 32 passes over
        # [rows, max_len] scores), so a turn's hundred-odd new rows in the
        # budget's executable would pay for all its rows
        self._row_pads = (self.token_budget,)
        if self._index_cache is not None and (
                self.token_budget // 8 >= 2 * self.max_batch):
            self._row_pads = (self.token_budget // 8, self.token_budget)
        # executables keyed by what differs between two ticks of this
        # engine: (token-budget, batch-slots, decode, ffn-mode, adapter
        # rank classes, spec-mode); `decode` = every chunk is one token
        self._step_fns: Dict[Tuple[Any, ...], Any] = {}
        self._copy_fn = None
        # the tick launched and not yet harvested (`step`), the events of
        # one harvested outside `step()` (`_settle`), the newest tick's
        # output (the `prev` argument of the next; zeros of its shape
        # before the first) and when the device last came free
        self._in_flight: Optional[_Tick] = None
        self._held: List[TokenEvent] = []
        self._last_out: Any = np.zeros(
            (self.max_batch * (3 * Bd if Bd else 1)
             + len(self._moe_fields),), np.int32)
        self._device_free_ns = 0
        # when a `step()` last found nothing to schedule: what a tick
        # behind an empty engine counts its `gap_ns` from
        self._empty_ns = 0
        # the device's time at work so far, by the ticks' own intervals
        # (`_harvest`): what a request's `device_s` is a difference of
        self._device_busy_ns = 0
        self._hits_seen = 0     # `prefix_hit_tokens` at the last harvest
        # set by ReplicaHandle so this engine's tick spans say which
        # replica served them (the merged-trace failover story)
        self._trace_replica: Optional[int] = None
        # the trace its `serve.tick` spans share: an id with no root span,
        # so that `active_spans()` and the distress dump's `traces` hold
        # requests alone
        self._trace_id = _tracing.new_id()

    # -- client API -------------------------------------------------------
    def submit(self, tokens, max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None, priority: int = 0,
               deadline_s: Optional[float] = None,
               temperature: Optional[float] = None,
               top_k: Optional[int] = None, top_p: Optional[float] = None,
               seed: int = 0, trace: Optional[Tuple[int, int]] = None,
               adapter: Optional[str] = None,
               denoising_steps: Optional[int] = None) -> int:
        """Enqueue a request. Raises ValueError when it cannot ever fit,
        RejectedError (load shed) when the wait queue is full,
        :class:`~.adapters.AdapterMissingError` when ``adapter`` names an
        unregistered LoRA adapter (pinned while the request is live).

        ``trace``: optional ``(trace_id, parent_span_id)`` context (the
        router's per-request trace) — rides the Sequence as two host
        ints so every queue-wait/prefill/decode span of this request
        lands in the same trace tree; never touches the jitted step.

        ``denoising_steps``: for a block-diffusion config alone, the
        denoise forwards a block gets at most (1..block_length, the
        default); the rows to unmask are picked by the one rule served,
        ``low_confidence_static``."""
        with _tracing.phase("serve.submit"):
            submit_ns = time.perf_counter_ns()
            tokens = np.asarray(tokens).reshape(-1).astype(
                np.int64, copy=False).tolist()     # Python ints, one pass
            total = len(tokens) + max(int(max_new_tokens), 0)
            Bd = self.cfg.block_length
            steps = 0
            if Bd:
                steps = self._check_block_request(
                    temperature, top_k, top_p, adapter, denoising_steps)
                total = -(-total // Bd) * Bd    # the last block is whole
            elif denoising_steps is not None:
                raise ValueError(
                    "denoising_steps belongs to a block-diffusion config "
                    "(block_length > 0); this engine's model is "
                    "autoregressive")
            if adapter is not None and (self.cfg.layer_plan
                                        or self._index is not None
                                        or self.cfg.hyper_lanes):
                raise NotImplementedError(
                    "LoRA adapters with a layer plan, a sparse index, "
                    "hyper-connections or state-space layers were never "
                    "judged against a reference; submit without one")
            if total > self.max_len:
                raise ValueError(
                    f"prompt {len(tokens)} + new {max_new_tokens} "
                    f"exceeds max_len {self.max_len}")
            if self.blocks.blocks_needed(total) > self.num_blocks:
                raise ValueError(
                    f"request needs {self.blocks.blocks_needed(total)} KV "
                    f"blocks but the pool has {self.num_blocks}; raise "
                    f"num_blocks or lower max_new_tokens")
            if top_k is not None and int(top_k) != self.top_k:
                raise ValueError(
                    f"per-request top_k={top_k} != engine top_k="
                    f"{self.top_k}: top_k is static in the fused step (one "
                    "executable); build the engine with the top_k you serve")
            rid = self._next_rid
            self._next_rid += 1
            self._events_by_rid[rid] = []
            if max_new_tokens <= 0:   # parity with generate(max_new_tokens=0)
                self._finish_event(Sequence(rid, tokens, 0), "length")
                return rid
            if temperature is None and (self.top_k or top_p is not None):
                temperature = 1.0      # top-k/top-p imply sampling
            sample = temperature is not None and float(temperature) > 0.0
            seq = Sequence(
                rid, tokens, int(max_new_tokens),
                eos=-1 if eos_token_id is None else int(eos_token_id),
                priority=int(priority),
                deadline=(time.monotonic() + float(deadline_s)
                          if deadline_s is not None else None),
                temperature=float(temperature) if sample else 0.0,
                top_p=float(top_p) if top_p is not None else 1.0,
                seed=int(seed), denoising_steps=steps, submit_ns=submit_ns)
            if trace is not None:
                seq.trace_id, seq.parent_span = int(trace[0]), int(trace[1])
            seq._key = jax.random.PRNGKey(int(seed)) if sample else None
            if adapter is not None:
                # pin BEFORE enqueue (AdapterMissingError moves no counts);
                # unpinned on every completion path via _record_completion
                self.adapters.pin(adapter)
                seq.adapter = adapter
                seq._adapter_pinned = True
            try:
                self.scheduler.add_request(seq)   # RejectedError on overflow
            except BaseException:
                if adapter is not None:
                    seq._adapter_pinned = False
                    self.adapters.unpin(adapter)
                raise
            self._update_gauges()
            return rid

    def _check_block_request(self, temperature, top_k, top_p, adapter,
                             denoising_steps) -> int:
        """What a block-diffusion config refuses at submit, with a message
        (no silent autoregressive decoding under this model's name);
        returns the request's denoising steps T."""
        Bd = self.cfg.block_length
        if ((temperature is not None and float(temperature) > 0.0)
                or top_k or top_p is not None):
            raise NotImplementedError(
                f"block_length={Bd}: only greedy unmasking (temperature 0) "
                "is served; sampling of x0 is not implemented")
        if adapter is not None:
            raise NotImplementedError(
                f"block_length={Bd}: LoRA adapters were never judged "
                "against the block-diffusion reference; submit without one")
        steps = Bd if denoising_steps is None else int(denoising_steps)
        if not 1 <= steps <= Bd:
            raise ValueError(
                f"denoising_steps={steps} must lie in 1..block_length={Bd}")
        return steps

    def cancel(self, rid: int) -> bool:
        self._settle()
        seq = self.scheduler.get(rid)
        if seq is None or seq.status == "finished":
            return False
        self.scheduler.cancel(rid)
        self._finish_event(seq, "cancelled", already_finished=True)
        return True

    def has_work(self) -> bool:
        """True while a request waits or runs, a tick is in flight, or a
        settled tick's events wait for the next `step()`."""
        return (self.scheduler.has_work() or self._in_flight is not None
                or bool(self._held))

    # -- cross-replica page migration (serving/disagg.py) ------------------
    def extract_pages(self, tokens) -> Optional[Dict[str, Any]]:
        """Host-side export of the full-block prefix pages covering
        `tokens`: the KV handoff payload a prefill replica ships to a
        decode replica (disagg.py packs it onto the wire). Returns None
        when this pool cannot serve the complete chain (never a partial
        payload — the receiver recomputes instead). Quantized engines
        export int8 pages plus their per-page dequant scale rows."""
        self._refuse_page_handoff("extract_pages")
        self._settle()
        chain = self.blocks.prefix_chain(tokens)
        if not chain:
            return None
        blks = self.blocks.chain_blocks(chain)
        if blks is None:
            return None
        ids = jnp.asarray(np.asarray(blks, np.int32))
        out: Dict[str, Any] = {
            "chain": [(int(d), int(h)) for d, h in chain],
            "tokens": [int(t) for t in tokens][:chain[-1][0]],
            "dtype": np.dtype(self.cache_dtype).name,
            "k": np.asarray(jnp.take(self._key_cache, ids, axis=1)),
            "v": np.asarray(jnp.take(self._value_cache, ids, axis=1)),
        }
        if self.quant_kv:
            out["kdq"] = np.asarray(
                jnp.take(self._kv_scales[2], ids, axis=1))
            out["vdq"] = np.asarray(
                jnp.take(self._kv_scales[3], ids, axis=1))
        return out

    def _refuse_page_handoff(self, what: str):
        if self._ssm:
            raise NotImplementedError(
                f"{what} with state-space layers: a sequence's recurrent "
                "state lives in a slot of the state pool, which the "
                "hand-off's payload (k, v) does not carry, and pages "
                "adopted without it would continue from a zero state")
        if self.cfg.hyper_lanes:
            raise NotImplementedError(
                f"{what} with hyper-connections (LlamaConfig.hyper_lanes): "
                "a hand-off of pages was never judged against a reference "
                "under a residual stream of lanes")
        if self._index_cache is not None:
            raise NotImplementedError(
                f"{what} under a sparse index over the heads' own keys and "
                "values: a page's index keys live in a third array that the "
                "hand-off's payload (k, v) does not carry, and a page "
                "adopted without them would be selected from by zeros")
        if self.cfg.layer_plan:
            raise NotImplementedError(
                f"{what} with a layer plan: pages are handed off by prefix "
                "hash over one pool [L, ...]; a plan's layers lie in stacks "
                "by kind and, with window layers, in two pools of which one "
                "keeps no prefix")

    def ingest_pages(self, payload: Dict[str, Any]) -> int:
        """Adopt migrated KV pages into this engine's pool and device
        caches. The pages park in the prefix cache exactly like locally
        computed freed-but-cached blocks, so the next
        ``allocate_sequence`` over the same prompt hits them — no new
        executable shapes, only eager page writes (the zero-retrace pin
        holds). Returns pages adopted (0 = all already present). Raises
        ValueError on cache-geometry/dtype mismatch (heterogeneous
        pools must recompute, not adopt)."""
        self._refuse_page_handoff("ingest_pages")
        self._settle()
        if payload["dtype"] != np.dtype(self.cache_dtype).name:
            raise ValueError(
                f"migrated pages are {payload['dtype']} but this engine "
                f"caches {np.dtype(self.cache_dtype).name}: recompute "
                f"instead of adopting across cache dtypes")
        k, v = payload["k"], payload["v"]
        L, _, kvh, bs, hd = self._key_cache.shape
        want = (L, kvh, bs, hd)
        got = (k.shape[0],) + tuple(k.shape[2:])
        if got != want or k.shape != v.shape:
            raise ValueError(
                f"migrated page geometry {got} != engine cache {want}: "
                f"pools must share [L, KV, block_size, hd] to adopt pages")
        chain = payload["chain"]
        toks = payload["tokens"]
        adopted: List[Tuple[int, int]] = []   # (payload row, block id)
        for idx, (depth, h) in enumerate(chain):
            prev_h = 0 if idx == 0 else int(chain[idx - 1][1])
            chunk = toks[depth - self.block_size:depth]
            try:
                blk = self.blocks.adopt_page(int(h), prev_h, chunk)
            except Exception:
                break   # pool fully referenced: keep what landed so far
            if blk is not None:
                adopted.append((idx, blk))
        if not adopted:
            return 0
        rows = np.asarray([r for r, _ in adopted], np.int32)
        ids = np.asarray([b for _, b in adopted], np.int32)
        kp = jnp.asarray(np.ascontiguousarray(k[:, rows]),
                         self.cache_dtype)
        vp = jnp.asarray(np.ascontiguousarray(v[:, rows]),
                         self.cache_dtype)
        self._key_cache = self._key_cache.at[:, ids].set(kp)
        self._value_cache = self._value_cache.at[:, ids].set(vp)
        if self.quant_kv and "kdq" in payload:
            kq, vq, kdq, vdq = self._kv_scales
            kdq = kdq.at[:, ids].set(
                jnp.asarray(np.ascontiguousarray(
                    payload["kdq"][:, rows]), jnp.float32))
            vdq = vdq.at[:, ids].set(
                jnp.asarray(np.ascontiguousarray(
                    payload["vdq"][:, rows]), jnp.float32))
            self._kv_scales = (kq, vq, kdq, vdq)
        return len(adopted)

    def run(self) -> List[Completion]:
        """Drive until queue and batch drain; completions in finish order."""
        while self.has_work():
            self.step()
        out, self._completions = self._completions, []
        return out

    def stream(self, rid: int) -> Iterator[int]:
        """Yield rid's tokens as they are produced, driving the engine
        while the request is live (other requests progress too).

        Mid-flight failures are TYPED, never a silently truncated stream:
        a deadline expiry raises :class:`DeadlineExceededError`, a shed
        raises :class:`RejectedError` (including chaos ``serving:reject``
        injections surfacing through ``step()``). Normal termination
        (stop / length / client cancel) ends the iterator."""
        events = self._events_by_rid.get(rid)
        if events is None:
            raise KeyError(f"unknown rid {rid}")
        i = 0
        while True:
            while i < len(events):
                ev = events[i]
                i += 1
                if ev.token >= 0:
                    yield ev.token
                if ev.finished:
                    if ev.reason == "deadline":
                        raise DeadlineExceededError(
                            f"request {rid} expired mid-stream after "
                            f"{i - 1} tokens (reason=deadline)")
                    if ev.reason == "shed":
                        raise RejectedError(
                            f"request {rid} shed mid-stream after "
                            f"{i - 1} tokens")
                    return
            if not self.has_work():
                return
            self.step()

    # -- the fused step ---------------------------------------------------
    def _resolve_ffn(self) -> Tuple[bool, Optional[str]]:
        """Host-side fused-FFN dispatch for this tick: (on, fallback
        reason). None re-reads FLAGS_pallas_ffn every tick; the result
        rides the executable cache key so flag flips retrace exactly once."""
        if (self.pallas_ffn is False or self.cfg.layer_plan
                or self._index is not None or self.cfg.hyper_lanes):
            return False, None
        if self.pallas_ffn:      # forced (params+geometry validated at init)
            return True, None
        if not flags.flag_value("pallas_ffn"):
            return False, None
        if self.cfg.num_experts:
            return False, "moe"
        blocks0 = self.params["blocks"]
        kind = FF.params_kind(blocks0)
        if kind is None:
            return False, "quant"
        if not FF.available():
            return False, "unavailable"
        w1 = blocks0["w1"] if kind == "fp" else blocks0["w1_q"]
        if not FF.supported(max(self.token_budget, self.max_batch),
                            int(w1.shape[-2]), int(w1.shape[-1])):
            return False, "unsupported"
        return True, None

    def _build_step(self, tok_pad: int, B: int, decode: bool = False,
                    ffn_mode=False, ad_sig: Tuple[int, ...] = (),
                    spec_mode: bool = False):
        """Trace+compile the fixed-shape mixed prefill+decode executable
        for the (token-budget, batch-slots, decode, ffn-mode,
        adapter-signature, spec-mode) signature. `decode` is the caller's
        promise that every scheduled chunk is one token: beside the
        kernel (`self.pallas`) the read then takes its max_q=1 launch.
        The executable is embed, the layers (`_layer_loop`, the one loop
        over layers of every config), head and sample; no layer is written
        here. `ffn_mode` swaps the per-layer SwiGLU for the fused Pallas
        kernel; combined with the decode launch it also swaps the
        sampling tail for the one-launch sampler prep — the fused decode
        tick (~2 launches/layer + 1 sampler).

        `ad_sig` is the sorted tuple of active LoRA rank classes
        (() = adapter-off): per class the step takes the WHOLE stacked
        slot pack plus a [tok_pad, slots] selector, so which adapter a
        token routes through is pure data — mixed-adapter batches run
        segmented/gathered in one executable, and only the SET of rank
        classes keys a retrace. `spec_mode` additionally returns the
        all-position argmax — the speculative-decoding verify read."""
        cfg = self.cfg
        top_k = self.top_k
        Bd = cfg.block_length      # static: 0 = one token a slot comes back
        # the op's vocabulary for the engine's constant and the tick's shape
        use_pallas = self.pallas and ("decode" if decode else True)
        fused_tick = bool(ffn_mode) and use_pallas == "decode"

        def block_rows(cu):
            # the packed row of every slot's last token (cu[1:] - 1; an idle
            # slot's is garbage the host never reads); under block
            # diffusion of its last Bd rows, its whole block: [B (* Bd)]
            last = jnp.clip(cu[1:] - 1, 0, tok_pad - 1)
            if Bd:
                last = jnp.clip(
                    last[:, None] - (Bd - 1 - jnp.arange(Bd))[None],
                    0, tok_pad - 1).reshape(-1)
            return last

        def head(params, x, cu=None):
            # float32 logits [n, V] of the slots' last rows (`block_rows`
            # of `cu`) or, without `cu`, of every row of the stream x
            # [tok, d] (under hyper-connections [tok, lanes d], read as the
            # lanes' sum): the one place the stream becomes logits
            with jax.named_scope("head"):
                rows = x if cu is None else x[block_rows(cu)]
                h = L.rms_norm(L.hyper_collapse(rows, cfg),
                               params["final_norm"], cfg.rms_eps)
                if cfg.tie_embeddings or cfg.logit_divisor != 1:
                    return L.head_logits(params, h, cfg)
                return Q.matmul_param(h, params, "lm_head"
                                      ).astype(jnp.float32)

        @functools.partial(jax.jit, donate_argnums=(1, 2),
                           donate_argnames=("index_cache", "state"))
        def step_fn(params, key_cache, value_cache, kv_scales, tokens,
                    block_tables, cu_seqlens_q, seq_lens_decoder,
                    seq_lens_this_time, rope_emb, temps, top_ps, keys,
                    greedy, ad_args, quota=None, masked=None, prev=None,
                    feed=None, index_cache=None, state=None, slots=None):
            # `state`: the state-space layers' two pools (`_state`), given
            # up and handed back behind the page arrays; `slots` [B] each
            # batch entry's slot of them (an idle entry: the void one)
            # `index_cache`: the third page array of a config with a sparse
            # index over heads' own keys and values (`_index_cache`), given
            # up and handed back behind the two pools as they are
            # a tick launched ahead of the last one's harvest takes the ids
            # the host does not hold yet from the last tick's output, on
            # the device: `prev` is that tick's `nxt` (any earlier one when
            # no row needs it) and `feed` [tok_pad] the entry of it a row
            # embeds, -1 for a row whose id the host wrote into `tokens`.
            # Under block diffusion `prev` is the last forward's transfer
            # and `feed` the block row of it [B * Bd, 3] that a row follows:
            # the host sent the block as that forward got it, and a row it
            # took carries its x0 from here on and is masked no longer
            if feed is not None and Bd:
                with jax.named_scope("sample"), jax.named_scope("unmask"):
                    was = prev[:B * Bd * 3].reshape(B * Bd, 3)[
                        jnp.maximum(feed, 0)]
                    took = (feed >= 0) & (was[:, 2] > 0)
                    tokens = jnp.where(took, was[:, 0], tokens)
                    masked = masked & ~took[block_rows(cu_seqlens_q)
                                            ].reshape(B, Bd)
            elif feed is not None:
                tokens = jnp.where(feed >= 0, prev[jnp.maximum(feed, 0)],
                                   tokens)
            # named scopes: every device operation of the tick belongs to
            # a region named here (embed; layers > qkv, cache_write,
            # paged_attention, attn_out, ffn or moe > router, dispatch,
            # experts, combine, and with lanes hyper > hyper_coeff,
            # hyper_pre, hyper_post; head; sample > unmask), whatever number
            # the compiler gives it. Metadata only. `quota` [B] and
            # `masked` [B, Bd] come with a block-diffusion tick alone
            # (`_unmask_rows` says what they are).
            with jax.named_scope("embed"):
                x = L.hyper_spread(L.embedded(params, tokens, cfg), cfg)
            # rows are packed from 0: what lies behind the last chunk is
            # padding, which no expert may see
            valid = jnp.arange(tok_pad) < cu_seqlens_q[B]
            with jax.named_scope("layers"):
                x, kcs, vcs, ics, state, loads = self._layer_loop(
                    params, x, key_cache, value_cache, index_cache,
                    kv_scales, ad_args, block_tables, cu_seqlens_q,
                    seq_lens_decoder, seq_lens_this_time, rope_emb, valid,
                    use_pallas, ffn_mode, state, slots, decode)
            pools = (kcs, vcs) if index_cache is None else (kcs, vcs, ics)
            if state is not None:
                pools += (state,)
            # last-token hidden state per slot, or its whole block's
            logits = head(params, x, cu_seqlens_q)         # [B (* Bd), V]
            with jax.named_scope("sample"):
                if Bd:
                    with jax.named_scope("unmask"):
                        nxt = _unmask_rows(logits.reshape(B, Bd, -1),
                                           masked, quota)
                else:
                    nxt_greedy = jnp.argmax(logits,
                                            axis=-1).astype(jnp.int32)

                    def sampled():
                        if fused_tick and FS.supported(B, logits.shape[-1]):
                            # fused decode tick "+1": the temperature/
                            # top-k/top-p masking in ONE launch; the
                            # categorical draw stays outside on
                            # bit-identical masked logits (token parity vs
                            # stock)
                            kept, _ = FS.fused_sample_prep(
                                logits, temps, top_ps, top_k)
                            draw = jax.vmap(
                                lambda k_, row: jax.random.categorical(
                                    jax.random.wrap_key_data(k_), row)
                            )(keys, kept).astype(jnp.int32)
                        else:
                            draw = _sample_rows(logits, keys, temps, top_ps,
                                                top_k)
                        return jnp.where(greedy, nxt_greedy, draw)

                    # what only a sampled row needs (the divide, the sort
                    # of the vocabulary, softmax, cumsum, the draw) runs in
                    # a tick that has one; a tick of greedy rows, which is
                    # every tick of a request that passes no temperature,
                    # ends at the argmax. One executable either way: the
                    # tick's own input picks the branch on the device
                    nxt = lax.cond(jnp.any(~greedy), sampled,
                                   lambda: nxt_greedy)
            if cfg.num_experts:
                # the tick's expert counters ride behind the B tokens, so
                # the host's one fetch brings both
                with jax.named_scope("moe"):
                    hit, max_load, *held = loads
                    nxt = jnp.concatenate([nxt, jnp.stack([
                        cu_seqlens_q[B] * cfg.top_k, jnp.sum(hit),
                        jnp.max(max_load), *map(jnp.sum, held)]
                    ).astype(jnp.int32)])
            if spec_mode:
                # the verify read: greedy argmax at EVERY packed row, so
                # a k+1-wide speculative chunk's per-position targets
                # come out of this same single launch
                all_logits = head(params, x)
                with jax.named_scope("sample"):
                    all_arg = jnp.argmax(all_logits,
                                         axis=-1).astype(jnp.int32)
                return (nxt, all_arg, *pools)
            return (nxt, *pools)

        return step_fn

    def _layer_loop(self, params, x, key_cache, value_cache, index_cache,
                    kv_scales, ad_args, block_tables, cu, past, this,
                    rope_emb, valid, use_pallas, ffn_mode, state=None,
                    slots=None, one_row=False):
        """The tick's layers, of every config (`llama.scan_plan` over
        `cfg.kinds`; a uniform config is one kind, one `lax.scan` over its
        whole stack): one body a kind, which finds its pages in its
        attention's pool (`key_cache` / `value_cache` / `block_tables` are
        (full, window) pairs with window layers, else the one pool) by the
        layer's place in that pool, its rope's table among `rope_emb`, and
        its experts in its kind's whole stack by its place there. What a
        layer only reads rides its kind's stack: the parameters and, where
        the tick has them, the int8 pages' four scale arrays `kv_scales`
        and the LoRA packs of `ad_args` (both stacked over ALL layers:
        `__init__` refuses them with a written plan, which alone can have
        more than one kind; the selectors carry no layer axis and are
        closed over). Scopes: qkv, cache_write,
        paged_attention (`paged_attention_full` / `_window` inside it
        where the tick makes more than one kind of launch), `attn_gate`,
        attn_out, ffn or moe (`shared_expert` inside). Every sub-block
        reads the stream and adds to it through `llama.residual` (the
        plain sum, or with `cfg.hyper_lanes` the lanes' mixes under
        `hyper`; x is then [tok, lanes d]). Latent layers
        (`latent_attention` below) have one pool and no value side; a
        layer of heads' own keys and values under a sparse index
        (`LayerSpec.index`) writes and scores its index keys in
        `index_cache` (`index_q`, `index_k`, `paged_index_select`'s own)
        and hands its selection to `paged_layer_attention`, which states
        the rule of which read a row then takes. A state-space layer
        (`LayerSpec.attn = "ssm"`) has no pages: its body is
        `llama.ssm_mixer` over the two pools of `state`, found by the
        layer's place among the state-space layers and the batch entries'
        `slots`; `one_row` is the decode tick's promise that every segment
        is one row.
        Returns (x, key_cache, value_cache, index_cache, state, (experts
        hit, largest load[, pairs on held experts, launches past their
        places]) or ())."""
        cfg = self.cfg
        kinds, kind_of = cfg.kinds, cfg.kind_of_layer
        two = isinstance(key_cache, tuple)
        pools_k = key_cache if two else (key_cache,)
        pools_v = value_cache if two else (value_cache,)
        tables = block_tables if two else (block_tables,)
        expert_names = ("w1", "w3", "w2")
        ad_sels = tuple(a["sel"] for a in ad_args)
        stacks, experts = [], []
        for k, (spec, leaves) in enumerate(zip(
                kinds, L.kind_stacks(params["blocks"]))):
            sparse = spec.ffn == "sparse"
            experts.append({n: leaves[n] for n in expert_names} if sparse
                           else {})
            layers = [i for i, kk in enumerate(kind_of) if kk == k]
            # the layer's place among the layers of its pool
            # (a state-space layer's: among the state-space layers)
            same = [i for i, s in enumerate(cfg.layers)
                    if (s.attn == "ssm") == (spec.attn == "ssm") and (
                        not two
                        or _in_window_pool(s) == _in_window_pool(spec))]
            stacks.append({
                "lp": {n: v for n, v in leaves.items()
                       if not (sparse and n in expert_names)},
                "place": jnp.arange(len(layers), dtype=jnp.int32),
                "page_layer": jnp.asarray([same.index(i) for i in layers],
                                          jnp.int32),
                # kq, vq [L, KV]; kdq, vdq [L, nb, KV]
                "kv": kv_scales and tuple(kv_scales),
                "ad": tuple(a["packs"] for a in ad_args)})

        if self.latent or index_cache is not None:
            # every packed row's position, for its rope: the rows are one
            # "sequence" of the model's latent and index functions
            # ([1, tok, ...])
            tok = jnp.arange(x.shape[0], dtype=jnp.int32)
            tok_b = jnp.clip(jnp.searchsorted(cu, tok, side="right") - 1, 0,
                             cu.shape[0] - 2)
            tok_pos = jnp.clip(past[tok_b] + tok - cu[tok_b], 0,
                               self.max_len - 1)

        def latent_attention(spec, x, lp, pool, index_pool, table,
                             page_layer):
            """x + the latent attention sub-block at the spec's widths
            (scopes `latent_q` and `latent_kv`, inside `qkv` here and
            around the absorption in the op; `index_q`, `index_k` and the
            index's own in `paged_index_select` for a layer with one;
            `paged_attention_latent` | `_latent_window` | `_sparse` inside
            `paged_attention`; `attn_gate`; `latent_out` in the op and
            around Wo); the pools come back with the rows' own. Which
            read a row takes and in which form is
            `paged_latent_attention`'s to say."""
            ls = spec.latent
            half = ls.qk_rope_head_dim // 2
            select = None
            h, out = L.residual(x, lp, cfg, "attn")
            with jax.named_scope("qkv"):
                h = L.rms_norm(h, lp["attn_norm"], cfg.rms_eps)[None]
                table_r = rope_emb[self._ropes.index(spec.rope)]
                cos, sin = (table_r[i, 0, tok_pos, :half] for i in (0, 1))
                with jax.named_scope("latent_q"):
                    cq = L.latent_cq(h, lp, cfg, ls)
                    q_nope, q_rope = L.latent_q(h, lp, cfg, spec.heads, cos,
                                                sin, ls, cq)
                with jax.named_scope("latent_kv"):
                    row = L.latent_kv(h, lp, cfg, cos, sin, ls)[0]
                if ls.index is not None:
                    qi, ki, w = L.index_qkw(h, cq, lp, cfg, ls.index, cos,
                                            sin)
            if ls.index is not None:
                *select, index_pool = paged_index_select(
                    qi[0], w[0], ki[0], index_pool, page_layer, past, this,
                    cu, table, ls.index.topk, use_pallas)
            o, pool = paged_latent_attention(
                q_nope[0], q_rope[0], row,
                *L.latent_wkvb(lp, cfg, spec.heads, x.dtype, ls), pool,
                page_layer, past, this, cu, table, ls.score_scale,
                use_pallas, window=ls.window,
                select=tuple(select) if select else None)
            if cfg.attn_gate:
                o = L.attn_gated(o.reshape(o.shape[0], spec.heads, -1),
                                 h[0], lp).reshape(o.shape)
            with jax.named_scope("attn_out"), jax.named_scope("latent_out"):
                return out(Q.matmul_param(o, lp, "wo")), pool, index_pool

        def put(pools, at, new):
            return pools[:at] + (new,) + pools[at + 1:]

        def body(kind, carry, leaves):
            spec = kinds[kind]
            x, pk, pv, pi, ps, *counts = carry
            lp = leaves["lp"]

            def lora(h, t, y):
                # segmented/gathered LoRA: every slot of every active rank
                # class applies at once; sel[row, slot] carries alpha/rank
                # for the row's adapter and 0 elsewhere, so a zero row
                # contributes an EXACT 0.0 delta (base rows bit-match the
                # adapter-free math) and the slot-reduction has one nonzero
                # term (mixed batches bit-match solo runs)
                for sel, packs in zip(ad_sels, leaves["ad"]):
                    A, Bm = packs[t]            # [S,din,c] / [S,c,dout]
                    u = jnp.einsum("td,sdr->tsr", h.astype(jnp.float32), A)
                    w = jnp.einsum("tsr,sro->tso", u, Bm)
                    y = y + jnp.einsum("tso,ts->to", w, sel).astype(y.dtype)
                return y

            if spec.attn == "ssm":
                h, out = L.residual(x, lp, cfg, "attn")
                h = L.rms_norm(h, lp["attn_norm"], cfg.rms_eps)
                y, *ps = L.ssm_mixer(h, lp, spec.ssm, cfg.rms_eps, *ps,
                                     leaves["page_layer"], slots, past, this,
                                     cu, one_row, use_pallas)
                with jax.named_scope("ssm"), jax.named_scope("ssm_out"):
                    x = out(y)
                ps = tuple(ps)
            elif spec.attn == "latent":
                pool = int(two and _in_window_pool(spec))
                x, kc, ic = latent_attention(
                    spec, x, lp, pk[pool], pv[pool], tables[pool],
                    leaves["page_layer"])
                pk, pv = put(pk, pool, kc), put(pv, pool, ic)
            else:
                pool = int(two and spec.attn == "window")
                rot = int(cfg.head_dim * (spec.rope.partial if spec.rope
                                          else 1.0))
                h, out = L.residual(x, lp, cfg, "attn")
                with jax.named_scope("qkv"):
                    h = L.rms_norm(h, lp["attn_norm"], cfg.rms_eps)
                    q, k, v = (lora(h, n, Q.matmul_param(h, lp, n))
                               for n in ("wq", "wk", "wv"))
                    q, k = L.qk_normed(q, k, lp, cfg)
                    qkv = jnp.concatenate([q, k, v], axis=-1)
                    if spec.index is not None:
                        half = cfg.index_rope_width(spec) // 2
                        cos, sin = (rope_emb[-1][i, 0, tok_pos, :half]
                                    for i in (0, 1))
                        qi, ki, w = L.index_qkw(h[None], None, lp, cfg,
                                                spec.index, cos, sin)
                select = None
                if spec.index is not None:
                    *select, pi = paged_index_select(
                        qi[0], w[0], ki[0], pi, leaves["page_layer"], past,
                        this, cu, tables[pool], spec.index.topk, use_pallas)
                # scopes itself: qkv (split, rope), cache_write,
                # paged_attention
                o, _, kc, vc = paged_layer_attention(
                    qkv, pk[pool], pv[pool], leaves["page_layer"], past,
                    this, cu, tables[pool],
                    rope_emb=spec.rope and rope_emb[
                        self._ropes.index(spec.rope)],
                    softmax_scale=spec.softmax_scale,
                    head_dim=cfg.head_dim,
                    quant_scales=leaves["kv"], use_neox_style=True,
                    use_pallas=use_pallas, block_length=cfg.block_length,
                    window=cfg.sliding_window if spec.attn == "window"
                    else 0,
                    rotary_dim=rot if rot < cfg.head_dim else 0,
                    kind=spec.attn if len(self._launches) > 1 else None,
                    select=select and tuple(select))
                pk, pv = put(pk, pool, kc), put(pv, pool, vc)
                if cfg.attn_gate:
                    o = L.attn_gated(o.reshape(o.shape[0], spec.heads, -1),
                                     h, lp).reshape(o.shape)
                with jax.named_scope("attn_out"):
                    x = out(lora(o, "wo", Q.matmul_param(o, lp, "wo")))
            h, out = L.residual(x, lp, cfg, "mlp")
            if spec.ffn == "sparse":
                with jax.named_scope("moe"):
                    h = L.rms_norm(h, lp["mlp_norm"], cfg.rms_eps)
                    y, load = L.routed_ffn_load(
                        h, {**lp, **experts[kind]}, cfg, valid,
                        layer=leaves["place"])
                    x = out(y)
                    hit, top, *held = counts
                    counts = (hit + jnp.sum(load > 0, dtype=jnp.int32),
                              jnp.maximum(top, jnp.max(load)),
                              *(c + n for c, n in zip(
                                  held, self._held_counts(load, h.shape[0]))))
            else:
                with jax.named_scope("ffn"):
                    h = L.rms_norm(h, lp["mlp_norm"], cfg.rms_eps)
                    if ffn_mode:
                        # one launch: gate+up matmuls, silu·mul, down
                        # matmul — the d_ff intermediate never leaves VMEM
                        x = out(FF.apply_ffn(h, lp))
                    else:
                        gate = (jax.nn.silu(Q.matmul_param(h, lp, "w1"))
                                * Q.matmul_param(h, lp, "w3"))
                        x = out(Q.matmul_param(gate, lp, "w2"))
            return (x, pk, pv, pi, ps, *counts)

        # the expert counters ride the carry: one a field the tick sends
        # behind its tokens but `moe_pairs`, which the lengths give
        zero = jnp.zeros((), jnp.int32)
        x, pk, pv, pi, ps, *counts = L.scan_plan(
            cfg, body,
            (x, pools_k, pools_v, index_cache, state)
            + (zero,) * len(self._moe_fields[1:]), stacks,
            by_index=bool(self._ssm))
        return (x, pk if two else pk[0], pv if two else pv[0], pi, ps,
                tuple(counts))

    def _held_counts(self, load, rows: int):
        """One sparse layer's two counters under a held share of the
        experts, () without one: the pairs on held experts, and 1 where
        they pass `llama.held_pair_slots`, the places the sorted form gives
        them, past which its launch takes the whole form."""
        if not self.cfg.experts_held:
            return ()
        pairs = jnp.sum(load, dtype=jnp.int32)
        return (pairs, (pairs > L.held_pair_slots(rows, self.cfg)
                        ).astype(jnp.int32))

    def _get_step_fn(self, tok_pad: int, B: int, decode: bool = False,
                     ffn_mode=False, ad_sig: Tuple[int, ...] = (),
                     spec_mode: bool = False):
        key = (tok_pad, B, decode, ffn_mode, ad_sig, spec_mode)
        fn = self._step_fns.get(key)
        if fn is None:
            fn = self._build_step(tok_pad, B, decode, ffn_mode,
                                  ad_sig, spec_mode)
            self._step_fns[key] = fn
            self.stats["step_builds"] += 1
            # cache_write: how this executable's layers put their new rows
            # into the carried pool (paged_layer_attention chooses by the
            # read path: the page-write kernel beside the Pallas read, an
            # XLA row scatter on the stock path)
            # experts: the form `routed_ffn` computes the experts in
            # index_select: the form of a sparse index's exact selection
            # (`serving_attention.index_select_form`), "" without an index
            _emit("serving.step_build", tok_pad=tok_pad, batch=B,
                  ad_sig=list(ad_sig), spec=bool(spec_mode),
                  cache_write="pallas_pages" if self.pallas
                  else "scatter_rows",
                  experts=L.expert_form(self.cfg),
                  index_select=index_select_form(
                      self.max_blocks_per_seq * self.block_size, self.pallas)
                  if any(s.sparse_index is not None for s in self.cfg.kinds)
                  else "",
                  block_length=self.cfg.block_length,
                  latent=self.latent,
                  experts_held=list(self.cfg.experts_held))
        return fn

    def _copy_blocks(self, pairs: List[Tuple[int, int]]):
        """Execute COW page copies on the device caches (padded to a fixed
        pair count so the copy executable compiles once)."""
        PAD = 8
        if self._copy_fn is None:
            nb = self.num_blocks
            quant_kv = self.quant_kv

            @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 6))
            def copy_fn(kc, vc, kdq, vdq, src, dst, ic=None):
                # one-hot selects, statically unrolled over the pad width:
                # a scatter-free page copy that rewrites the whole pool.
                # When quantized, a page's dequant-scale rows move WITH the
                # page (per-page layout contract; numerically a no-op while
                # scales are calibration-static).
                with jax.named_scope("cow_copy"):
                    for i in range(PAD):
                        s = jnp.maximum(src[i], 0)
                        sel = (jnp.arange(nb) == dst[i])[None, :, None,
                                                         None, None]
                        blk_k = lax.dynamic_slice_in_dim(kc, s, 1, axis=1)
                        kc = jnp.where(sel, blk_k, kc)
                        # (a latent pool has no values, or its index
                        # keys there; `ic`: the third array of an index
                        # over heads' own keys: a copied page takes its
                        # index keys with it)
                        vc, ic = (a if a is None else jnp.where(
                            sel, lax.dynamic_slice_in_dim(a, s, 1, axis=1), a)
                            for a in (vc, ic))
                        if quant_kv:
                            sel3 = (jnp.arange(nb) == dst[i])[None, :, None]
                            kdq = jnp.where(sel3, lax.dynamic_slice_in_dim(
                                kdq, s, 1, axis=1), kdq)
                            vdq = jnp.where(sel3, lax.dynamic_slice_in_dim(
                                vdq, s, 1, axis=1), vdq)
                return kc, vc, kdq, vdq, ic

            self._copy_fn = copy_fn
        for i in range(0, len(pairs), PAD):
            chunk = pairs[i:i + PAD]
            src = np.full((PAD,), -1, np.int32)
            dst = np.full((PAD,), -1, np.int32)   # -1 never matches arange
            for j, (s, d) in enumerate(chunk):
                src[j], dst[j] = s, d
            kdq = vdq = None
            if self.quant_kv:
                kq, vq, kdq, vdq = self._kv_scales
            (self._key_cache, self._value_cache, kdq, vdq,
             self._index_cache) = self._copy_fn(
                self._key_cache, self._value_cache, kdq, vdq,
                jnp.asarray(src), jnp.asarray(dst), self._index_cache)
            if self.quant_kv:
                self._kv_scales = (kq, vq, kdq, vdq)
            self.stats["cow_block_copies"] += len(chunk)
            _emit("serving.cow", copies=len(chunk))
        if self.spec is not None:
            # mirror COW into the draft caches so draft KV at a copied
            # page stays valid for the copy's owner
            self.spec.copy_blocks(pairs)

    # -- scheduler tick ---------------------------------------------------
    def step(self) -> List[TokenEvent]:
        """One tick's events: the streamed tokens of exactly one fused
        device step, in the order the device runs them.

        A tick has two halves. Its *launch* (schedule, prepare, dispatch)
        plans a mixed batch from counts the host holds, calls the
        executable and advances every scheduled sequence by count:
        ``num_computed`` by its rows, and one position of unknown id where
        the tick yields it a token (a block-diffusion sequence: the rows
        ``num_computed`` will take at the harvest, in ``in_flight``). Its
        *harvest* (wait, then harvest)
        reads the tick's ids — the one sync —, writes them into the
        sequences, runs `_emit_token` / `TokenEvent` / completions, hashes
        the pages that filled into the prefix cache, and keeps the books.

        The next tick is launched before this one is harvested wherever it
        is determined without this one's ids (`_next_is_determined`): its
        decode rows take their ids, and the rows of an open block their ids
        and mask flags, from this tick's output on the device, so the chip
        runs it while the host reads, harvests and plans. `step()` then
        returns this tick's events with the next one in flight. Planned
        with every id read are only a speculative tick, the tick behind one
        that frees a slot by count, and one that would preempt. What the
        host learns a tick late — an end-of-sequence id, a deadline —
        leaves the sequence a row (a block's Bd rows) in the tick in
        flight: computed and dropped (``stats["ahead_void_rows"]``).

        In a ``jax.profiler`` trace the call is ``ptpu.serve.step`` and
        its phases (schedule, prepare, dispatch of the tick launched here;
        wait, harvest of the tick whose events it returns; a call with no
        tick in flight launches that one first, so it has schedule,
        prepare, dispatch twice) are contiguous children, so a step's
        self time is what no phase covers. The span's fields describe the
        tick harvested, with ``ahead`` = 1 if it had been launched ahead,
        ``void_rows`` and ``sampled_rows`` (the rows with a temperature; 0
        means the tick took no sort, ``stats["ticks_sampled"]`` counts the
        others). The clock readings at the phase boundaries are
        the ones the ring's cow.copy / prefill.chunk / decode.tick spans
        get, and ``perf_ns`` is the ``perf_counter_ns()`` reading taken as
        the span opens: its start on the profile's axis less ``perf_ns``
        lays every such reading of the process over the device's
        operations. A request's first token is the mark
        ``ptpu.serve.first_token`` inside ``harvest``
        (`_emit_first_token`)."""
        with _tracing.phase("serve.step", tick=self.stats["steps"],
                            perf_ns=time.perf_counter_ns()) as span:
            if self._held:      # a tick settled outside step(): its events
                events, self._held = self._held, []
                return events
            cur = self._in_flight
            if cur is None:
                cur = self._launch(None)
                if cur.batch is None:
                    return self._harvest(cur, span)
                self._in_flight = cur
            nxt = None
            if self._next_is_determined(cur):
                nxt = self._launch(cur)
                if nxt.batch is None and not nxt.events:
                    nxt = None
            self._in_flight = nxt
            return self._harvest(cur, span)

    def _settle(self):
        """Harvest the tick in flight, if any, and hold its events for the
        next `step()`: what reads or edits sequences and pools from outside
        a tick (`cancel`, `extract_pages`, `ingest_pages`, `engine_stats`)
        sees every dispatched row accounted for."""
        cur, self._in_flight = self._in_flight, None
        if cur is not None:
            self._held.extend(self._harvest(cur, None))

    def _next_is_determined(self, cur: "_Tick") -> bool:
        """Whether the tick after `cur` can be planned before `cur`'s ids
        are read: everything the scheduler decides for it must be a count
        the host holds now. A block-diffusion tick is such: a forward takes
        exactly min(quota, masks left) rows (`_unmask_rows`), so which
        forward of its block a sequence has next, that forward's quota and
        where the block stands are counts; which rows were taken, and
        their ids, stay on the device (`_launch`). Not with a draft model
        (a speculative tick advances by the accepted length, and the next
        proposal starts from this tick's id): that engine alone keeps the
        synchronous order, in this same loop. Not when a sequence of `cur`
        reaches `max_new_tokens` in it (under block diffusion: by the
        commit forward it has in `cur`): a tick that frees a slot is
        followed by one planned with full knowledge, so whoever waits for
        the slot, or submits when the last token is seen, is admitted as in
        the synchronous order. And not when planning would have to
        preempt: a preempted sequence re-prefills from its ids."""
        if cur.batch is None or (self.spec is not None and self.spec_k > 0):
            return False
        Bd = self.cfg.block_length
        for i in cur.slots.values():
            seq = cur.batch.items[i][0]
            new = 1
            if Bd:
                # a denoise forward yields no token, a commit forward the
                # block's rows behind the sequence's tokens
                new = 0 if cur.quota[i] else Bd - (len(seq.tokens)
                                                   - seq.num_computed)
            if new and len(seq.generated) + new >= seq.max_new_tokens:
                return False
        return self.scheduler.next_fits()

    def _launch(self, prev: Optional["_Tick"]) -> "_Tick":
        """Schedule, prepare and dispatch one tick. `prev` is the tick in
        flight this one is launched behind (None: every id is known)."""
        with _tracing.phase("serve.schedule"):
            hook = _CHAOS_HOOK[0]
            if hook is not None:
                hook("step")
            batch, expired = self.scheduler.schedule()
            tick = _Tick(events=[self._finish_event(seq, "deadline",
                                                    already_finished=True)
                                 for seq in expired], ahead=prev is not None)
            if not batch:
                return tick
            pairs = self.blocks.take_copies()

        with _tracing.phase("serve.prepare"):
            if pairs:
                t0c = time.perf_counter_ns()
                self._copy_blocks(pairs)
                # attribute the COW interval to the first traced request
                # in the batch (its page appends forced the copies)
                tseq = next((s for s, _ in batch.items if s.trace_id), None)
                if tseq is not None:
                    _tracing.record_span(
                        "cow.copy", tseq.trace_id, tseq.parent_span, t0c,
                        (time.perf_counter_ns() - t0c) * 1e-9,
                        copies=len(pairs), replica=self._trace_replica)

            ffn_mode, ffn_fb = self._resolve_ffn()
            if ffn_fb is not None:
                _emit("pallas_ffn.fallback", reason=ffn_fb)

            # adapter residency for this tick: every adapter referenced by the
            # batch gets a device slot (loading/LRU-swapping as needed). The
            # chaos "adapter" site drills mid-stream eviction here — a forced
            # evict simply reloads below, counted as a swap.
            ad_hook = AD._CHAOS_HOOK[0]
            active: Dict[str, Tuple[int, int]] = {}
            for seq, _n in batch.items:
                name = seq.adapter
                if name is None or name in active:
                    continue
                if (ad_hook is not None
                        and ad_hook("use", name=name) == "evict"):
                    self.adapters.evict_device(name, why="chaos")
                active[name] = self.adapters.ensure_loaded(name)
            ad_sig = tuple(sorted({cls for cls, _ in active.values()}))

            # speculative plan: widen each greedy decode-ready chunk by k
            # draft tokens (inside the token budget and the block pool), so
            # the ONE fused step below verifies the whole proposal
            spec_plan: Dict[int, List[int]] = {}
            if self.spec is not None and self.spec_k > 0:
                budget_left = self.token_budget - batch.total_tokens
                for i, (seq, n) in enumerate(batch.items):
                    if budget_left < 1:
                        break
                    if (n != 1 or seq.temperature > 0.0
                            or seq.num_computed + 1 != len(seq.tokens)):
                        continue
                    k_eff = min(self.spec_k, budget_left,
                                seq.max_new_tokens - len(seq.generated) - 1)
                    if k_eff < 1:
                        continue
                    try:
                        self.blocks.ensure_capacity(
                            seq.rid, len(seq.tokens) + k_eff)
                    except NoFreeBlocksError:
                        continue   # pool exhausted: this tick unspeculated
                    spec_plan[i] = self.spec.propose(seq, k_eff)
                    budget_left -= k_eff
            spec_mode = bool(spec_plan)

            B, Bd = self.max_batch, self.cfg.block_length
            rows = batch.total_tokens + sum(map(len, spec_plan.values()))
            tok_pad = next((p for p in self._row_pads if p >= rows),
                           self.token_budget)
            # block diffusion: which items bring their open block (the
            # others are prefill chunks)
            in_block = [bool(Bd) and self.scheduler.prefill_left(seq) <= 0
                        for seq, _ in batch.items]
            for (seq, _n), blk in zip(batch.items, in_block):
                # a sequence's first block; the next opens as one commits
                if blk and seq.block_ids is None:
                    self._open_block(seq)
            if Bd and all(in_block):
                # every chunk is one block: the steady-state executable
                tok_pad = min(B * Bd, tok_pad)
            decode = (self.pallas and not spec_plan and not Bd
                      and all(n == 1 for _, n in batch.items))
            if decode:
                # decode fast path: every scheduled chunk is one token, so the
                # step packs [max_batch] tokens instead of [token_budget] and
                # the kernel runs its max_q=1 specialized launch — the
                # steady-state executable (built once; the MPK-style single
                # launch per decode step)
                tok_pad = B
            tokens = np.zeros((tok_pad,), np.int32)
            # the entry of `prev`'s output a row embeds, where the host
            # does not hold its id yet (-1: `tokens` has it); for a row of
            # a block with a denoise forward in flight, its row there
            feed = np.full((tok_pad,), -1, np.int32)
            cu = np.zeros((B + 1,), np.int32)
            dec_lens = np.zeros((B,), np.int32)
            this_lens = np.zeros((B,), np.int32)
            tables = np.full((B, self.max_blocks_per_seq), -1, np.int32)
            wtables = (np.full_like(tables, -1) if self.window_blocks
                       else None)
            temps = np.ones((B,), np.float32)
            top_ps = np.ones((B,), np.float32)
            keys = np.zeros((B, 2), np.uint32)
            greedy = np.ones((B,), bool)
            # block diffusion: the rows to unmask now and the rows still
            # masked; zero on a commit forward and on a prefill chunk
            quota = np.zeros((B,), np.int32)
            masked = np.zeros((B, Bd), bool)
            # each entry's slot of the state pools; an idle entry: the void
            slots = np.full((B,), self.state_slots, np.int32)
            pos = 0
            for i, (seq, n) in enumerate(batch.items):
                if self._ssm:
                    slots[i] = self.blocks.slot_of(seq.rid)
                start = seq.planned()
                chunk = seq.tokens[start:start + n]
                if in_block[i]:
                    chunk, flags = seq.block_ids, seq.block_masked
                    left = sum(flags)
                    j = None if prev is None else prev.slots.get(seq.rid)
                    if j is not None and left:
                        # a denoise forward of this block is in flight: it
                        # takes `prev.quota[j]` of the rows, which ones the
                        # device says (`feed`); the block goes as it got it
                        left -= prev.quota[j]
                        feed[pos:pos + Bd] = np.arange(j * Bd, (j + 1) * Bd)
                    elif j is not None:
                        # its commit forward is: this is the next block,
                        # every row masked, which the sequence will hold
                        # once the commit is harvested
                        chunk = [self.cfg.mask_token_id] * Bd
                        flags, left = [True] * Bd, Bd
                    quota[i] = min(left, -(-Bd // seq.denoising_steps))
                    masked[i] = flags
                props = spec_plan.get(i)
                if props is not None:
                    chunk = list(chunk) + props   # [t_c, d1..dk]: verify rows
                    n = len(chunk)
                tokens[pos:pos + n] = chunk
                if chunk[-1] == UNKNOWN:
                    # the token `prev` yields this sequence: a row's id is
                    # unknown only behind everything the host holds
                    tokens[pos + n - 1] = 0
                    feed[pos + n - 1] = prev.slots[seq.rid]
                pos += n
                cu[i + 1] = pos
                dec_lens[i] = start
                this_lens[i] = n
                row = self.blocks.block_table(seq.rid)
                tables[i, :len(row)] = row
                if wtables is not None:
                    row = self.blocks.window_table(seq.rid)
                    wtables[i, :len(row)] = row
                if seq.temperature > 0.0:
                    greedy[i] = False
                    temps[i] = seq.temperature
                    top_ps[i] = seq.top_p
                    seq._key, sub = jax.random.split(seq._key)
                    keys[i] = _key_bits(sub)
            cu[len(batch.items) + 1:] = pos
            tick.sampled_rows = int(np.count_nonzero(~greedy))

            # per-class [tok_pad, slots] selectors: each adapter-bound chunk's
            # rows carry its slot's alpha/rank scaling; everything else is 0.0
            ad_args: Tuple[Any, ...] = ()
            if ad_sig:
                sels = {cls: np.zeros((tok_pad, self.adapters.slots),
                                      np.float32) for cls in ad_sig}
                for i, (seq, _n) in enumerate(batch.items):
                    name = seq.adapter
                    if name is None:
                        continue
                    cls, slot = active[name]
                    sels[cls][cu[i]:cu[i + 1], slot] = \
                        self.adapters.get(name).scaling
                ad_args = tuple({"sel": jnp.asarray(sels[cls]),
                                 "packs": self.adapters.device_packs(cls)}
                                for cls in ad_sig)

            # tick classification per request, snapshotted BEFORE the tick
            # extends the sequence: a request mid-prompt is in a prefill
            # chunk; one with tokens out (or one on its way) is in a decode
            # tick
            was_decode = [len(s.tokens) > len(s.prompt)
                          for s, _ in batch.items]

        with _tracing.phase("serve.dispatch"):
            tick.t0 = time.perf_counter_ns()
            builds0 = self.stats["step_builds"]
            fn = self._get_step_fn(tok_pad, B, decode, ffn_mode,
                                   ad_sig, spec_mode)
            fused_tick = bool(ffn_mode) and decode
            launches0 = FA.trace_launches()
            # the host arrays go in as they are: the call moves them with
            # its own argument handling, which costs the tick half of what
            # ten `jnp.asarray` did (1.3 against 2.7 ms of dispatch on the
            # chip, PERF.md PR 30), and nothing writes them afterwards.
            # `prev`/`feed` are always there, so a tick launched ahead runs
            # the executable every other tick of its shape runs
            if self._index is not None:
                tick.tables = tables
            if wtables is not None:
                tables = (tables, wtables)
            out = fn(self.params, self._key_cache, self._value_cache,
                     self._kv_scales, tokens, tables, cu, dec_lens,
                     this_lens, self._rope_emb, temps, top_ps, keys,
                     greedy, ad_args, *((quota, masked) if Bd
                                        else (None, None)),
                     self._last_out, feed,
                     **({} if self._index_cache is None
                        else {"index_cache": self._index_cache}),
                     **({"state": self._state, "slots": slots}
                        if self._ssm else {}))
            if spec_mode:
                tick.out, tick.all_arg = out[:2]
            else:
                tick.out = out[0]
            self._last_out = tick.out
            if self._ssm:
                *out, self._state = out
                tick.slots_live = self.blocks.slots_live()
            if self._index_cache is not None:
                *out, self._index_cache = out
            self._key_cache, self._value_cache = out[-2:]
            if fused_tick and self.stats["step_builds"] > builds0:
                # fresh trace: the launch-counter delta counts the DISTINCT
                # Pallas launches traced into this tick's executable (the
                # layer scan body is traced once, so per-layer kernels count
                # once — paged attention + fused FFN + the sampler prep).
                # Steady-state ticks re-run the same executable, so the count
                # holds for every subsequent tick.
                self.stats["tick_pallas_launches"] = (FA.trace_launches()
                                                      - launches0)
            # progress by count: every plain row advances its sequence now,
            # so the next tick can be planned before this one is read. A
            # speculative chunk advances by what is accepted, at its
            # harvest, and so no tick is planned behind it. A
            # block-diffusion tick advances `num_computed` at its harvest
            # too (`ends[i] is None` for both), because the sequence keeps
            # the state its tick in flight was called with until then
            # (`_harvest_blocks`); the rows that will count, a prefill
            # chunk's and a commit forward's, stand in `seq.in_flight` for
            # the plan of the tick behind
            tick.ends = [None] * len(batch.items)
            # the device's time at work up to this call: the ticks read so
            # far and, of the one in flight, what lies behind. A request's
            # device time counts from its first dispatch
            busy0 = self._device_busy_ns
            if prev is not None:
                busy0 += tick.t0 - max(prev.t0, self._device_free_ns)
            for i, (seq, n) in enumerate(batch.items):
                if seq.busy0_ns is None:
                    seq.busy0_ns = busy0
                if not (was_decode[i] or in_block[i]):
                    tick.prompt_rows += n
                if Bd:
                    if not in_block[i]:
                        tick.n_prefill += n
                        seq.in_flight += n
                    else:
                        tick.slots[seq.rid] = i
                        if not quota[i]:
                            seq.in_flight += Bd
                elif i not in spec_plan:
                    if self.scheduler.on_dispatched(seq, n):
                        tick.slots[seq.rid] = i
                    else:
                        tick.n_prefill += n
                    tick.ends[i] = seq.num_computed
            tick.batch, tick.spec_plan, tick.in_block = (batch, spec_plan,
                                                         in_block)
            tick.tok_pad, tick.decode, tick.ffn_mode = (tok_pad, decode,
                                                        bool(ffn_mode))
            tick.lens = (cu, dec_lens, this_lens)
            tick.quota = quota
            tick.was_decode = was_decode
            if self.quant_kv:
                tick.pages = int((tables >= 0).sum())
            if self.window_blocks:
                tick.pool_pages = (self.blocks.num_allocated(),
                                   self.blocks.window_allocated())
            elif self._index is not None or self.latent:
                tick.pool_pages = (self.blocks.num_allocated(), 0)
        return tick

    def _harvest(self, cur: "_Tick", span) -> List[TokenEvent]:
        """Wait for a dispatched tick and harvest it: the ids into the
        sequences, events, prefix-cache hashes, books. `span` is the step
        span that takes the tick's fields (None outside `step()`)."""
        events = cur.events
        if cur.batch is None:
            # nothing to schedule (launched ahead: only deadlines fell)
            self._empty_ns = time.perf_counter_ns()
            self._update_gauges()
            return events
        batch, in_block = cur.batch, cur.in_block
        decode, ffn_mode = cur.decode, cur.ffn_mode
        cu, dec_lens, this_lens = cur.lens
        B, Bd = self.max_batch, self.cfg.block_length
        with _tracing.phase("serve.wait"):
            all_arg = None if cur.all_arg is None else np.asarray(cur.all_arg)
            nxt = np.asarray(cur.out)     # the step's one sync point
            now = time.perf_counter_ns()
            # the tick's device interval: a tick launched ahead starts
            # when the one before it ends, not when it was called
            free = self._device_free_ns       # the end of the tick before
            t0 = max(cur.t0, free)
            self._device_free_ns = now
            self._device_busy_ns += now - t0
            dur = (now - t0) * 1e-9
            moe = None
            if self._moe_fields:
                n = len(self._moe_fields)
                nxt, moe = nxt[:-n], [int(c) for c in nxt[-n:]]

        with _tracing.phase("serve.harvest"):
            spec_extra = sum(len(p) for p in cur.spec_plan.values())
            fields = {}
            if moe is not None:
                fields = dict(zip(self._moe_fields, moe))
                for name, n in fields.items():
                    self.stats[name] = (max(self.stats[name], n)
                                        if name == "moe_max_load"
                                        else self.stats[name] + n)
            if self.cfg.hyper_lanes:
                # the rows the lanes were mixed for: two sub-blocks a layer
                fields["hyper_rows"] = (2 * self.cfg.num_layers
                                        * (batch.total_tokens + spec_extra))
                self.stats["hyper_rows"] += fields["hyper_rows"]
            if self._ssm:
                # the state-space layers' work, from the host's own lengths
                long = this_lens > 1
                fields.update(
                    ssm_step_rows=self._ssm_layers * int(
                        np.count_nonzero(this_lens == 1)),
                    ssm_scan_rows=self._ssm_layers * int(
                        this_lens[long].sum()),
                    ssm_segments=self._ssm_layers * int(
                        np.count_nonzero(long)),
                    state_slots_live=cur.slots_live)
                for name in ("ssm_step_rows", "ssm_scan_rows",
                             "ssm_segments", "state_slots_live"):
                    self.stats[name] += fields[name]
            if self._whole_pages:
                # how well the launch's walk fits the traffic, from the
                # host's own lengths: pages that hold a live key against
                # pages fetched (whole key blocks), and on a mixed tick the
                # work items with their live and packed query rows
                cfg = self.cfg
                pool = (self._head_width,
                        np.dtype(self.cache_dtype).itemsize,
                        self.max_blocks_per_seq)
                # one launch a kind of attention, summed
                walked: Dict[str, int] = {}
                for heads, window in self._launches:
                    if decode:
                        one = dict(zip(
                            ("attn_pages_live", "attn_pages_fetched"),
                            PA.decode_pages_walked(
                                (dec_lens + this_lens)[this_lens > 0],
                                self.block_size, cfg.num_kv_heads, *pool,
                                window=window)))
                    else:
                        one = PA.mixed_work(
                            dec_lens, this_lens, cur.tok_pad,
                            self.block_size, cfg.num_kv_heads,
                            heads // cfg.num_kv_heads, *pool, block_len=Bd,
                            window=window)
                    for name, n in one.items():
                        walked[name] = walked.get(name, 0) + n
                fields.update(walked)
                for name, n in walked.items():
                    self.stats[name] += n
            if self.cfg.layer_plan or self._index is not None:
                keys = self._plan_keys(dec_lens, this_lens, cur.tok_pad,
                                       cur.tables)
                if self.latent:
                    keys["latent_pages_live"] = cur.pool_pages[0]
                if self._index is not None:
                    keys["index_pages_live"] = cur.pool_pages[0]
                if self._index_cache is not None:
                    # prompt tokens the block manager served from cached
                    # pages since the tick before was read: pages whose
                    # index keys another request wrote
                    hits = self.blocks.stats["prefix_hit_tokens"]
                    fields["prefix_hit_tokens"] = hits - self._hits_seen
                    self._hits_seen = hits
                if self.window_blocks:
                    keys.update(
                        full_pages_live=cur.pool_pages[0],
                        window_pages_live=cur.pool_pages[1])
                fields.update(keys)
                for name, n in keys.items():
                    self.stats[name] += n
                if self.window_blocks:
                    self.stats["window_pages_released"] = \
                        self.blocks.stats["window_released"]
            if _tracing.trace_enabled():
                # per-request tick attribution: each traced request in the
                # batch gets a span over this tick's device interval: of a
                # request's TTFT, queue.wait and the prefill chunks are the
                # queue and device parts (the host's part lies in no ring
                # span: `_emit_first_token`), and TPOT is its decode ticks
                for (seq, n), dec in zip(batch.items, cur.was_decode):
                    if seq.trace_id:
                        _tracing.record_span(
                            "decode.tick" if dec else "prefill.chunk",
                            seq.trace_id, seq.parent_span, t0, dur,
                            rid=seq.rid, tokens=n,
                            replica=self._trace_replica)
            if self.pallas:
                self.stats["pallas_steps"] += 1
                if decode:
                    self.stats["decode_fast_steps"] += 1
            if ffn_mode:
                self.stats["ffn_steps"] += 1
                if decode:
                    self.stats["fused_ticks"] += 1
            if self.quant_kv:
                _emit("quant.kv_step",
                      tokens=batch.total_tokens * self.cfg.num_layers,
                      pages=cur.pages * self.cfg.num_layers)
            tick = self.stats["steps"]
            self.stats["steps"] += 1
            self.stats["ticks_ahead"] += cur.ahead
            self.stats["ticks_sampled"] += cur.sampled_rows > 0
            self.stats["sampled_rows"] += cur.sampled_rows
            self.stats["tokens_computed"] += batch.total_tokens + spec_extra
            void0 = self.stats["ahead_void_rows"]
            if Bd:
                events.extend(self._harvest_blocks(
                    batch, in_block, nxt.reshape(B, Bd, 3)))
            else:
                self.stats["ahead_void_rows"] += self._harvest_rows(
                    cur, nxt, all_arg, events)
            void = self.stats["ahead_void_rows"] - void0
            # the tick's fields, built once: the step span's in a profile,
            # and with the launch's clock readings the `serve.tick` span
            # of the ring and the tick's one event
            kind = ("decode" if decode else
                    "block" if Bd and all(in_block) else "mixed")
            fields.update(
                batch=len(batch.items),
                tokens=batch.total_tokens + spec_extra,
                prefill_tokens=cur.n_prefill,
                ahead=int(cur.ahead), void_rows=void,
                sampled_rows=cur.sampled_rows)
            if span is not None:
                span.set_metadata(kind=kind, **fields)
            # `gap_ns`: what the device had nothing queued before this
            # tick's call, as far as the host sees it: from the end of the
            # tick before, or of a `step()` that found nothing to schedule
            # since (an engine with no request charges nothing), to the
            # entry of `serve.dispatch`; an engine's first tick has none
            free = max(free, self._empty_ns) or cur.t0
            fields.update(prompt_rows=cur.prompt_rows, tick=tick,
                          launch_ns=cur.t0, gap_ns=max(0, cur.t0 - free),
                          replica=self._trace_replica)
            _tracing.record_span("serve.tick", self._trace_id, 0, t0, dur,
                                 event=False, kind=kind, **fields)
            # an event's own `kind` is its name: the tick's is `tick_kind`
            _emit("serving.step", dur_s=dur, pallas=bool(self.pallas),
                  ffn=ffn_mode, tick_kind=kind, **fields)
            self._update_gauges()
            return events

    def _plan_keys(self, past: np.ndarray, this: np.ndarray,
                   tok_pad: int = 0, tables=None) -> dict:
        """One tick's keys inside the masks of a layer plan, from the
        host's own lengths (and, for what the index walk's work items
        fetch, the tick's padded row count `tok_pad`; 0: the token
        budget's; for how many of the key blocks they fetch come in one
        copy, the tick's block `tables` [B, max_blocks]), summed over the
        layers of the kind. `keys`:
        the distinct keys a sequence's rows see (a chunk's rows share
        theirs): past + this in a full layer, of those the ones from
        position past - (sliding_window - 1) on in a window layer;
        `attn_keys_causal` is what a causal mask would show in every
        layer. `pairs`: the (query row, key) pairs, a row at position p
        sees p + 1 keys in a full layer and min(p + 1, sliding_window) in
        a window layer."""
        cfg = self.cfg
        n_full, n_window = self._pool_layers
        out: Dict[str, int] = {}
        live = this > 0
        past, this = past[live].astype(np.int64), this[live].astype(np.int64)
        if tables is not None:
            tables = tables[live]
        each_keys = past + this
        each_pairs = this * past + this * (this + 1) // 2
        keys, pairs = int(each_keys.sum()), int(each_pairs.sum())
        if self._index is not None:
            # the full layers' rows divide by the rule of
            # `paged_latent_attention` and `paged_layer_attention`: a
            # sequence that holds more than `topk` keys after this tick
            # selects (its rows score every key they see and attend over
            # min(p + 1, topk) of them), the others walk densely
            k = self._index.topk
            sel = each_keys > k
            under = np.clip(k - past, 0, this)   # rows that see <= k keys
            chosen = (under * past + under * (under + 1) // 2
                      + (this - under) * k)
            # of the selecting sequences those under the crossing read
            # through the masked walk, which multiplies their rows' causal
            # pairs (the kernels' rule, the ops': a latent layer's chunks;
            # of a layer of heads' own keys every chunk, and its one-row
            # sequences under theirs; the stock read has no such form)
            rows_walked = pairs_walked = 0
            item = np.dtype(self.cache_dtype).itemsize
            for spec in cfg.layers if self.pallas else ():
                if spec.sparse_index is None:
                    continue
                if spec.latent is not None:
                    walk = (this > 1) & (each_keys <= sparse_walk_keys(
                        spec.heads, self._row_widths[0],
                        spec.latent.kv_lora_rank, k))
                else:
                    walk = (this > 1) | (each_keys <= sparse_walk_keys_heads(
                        cfg.num_kv_heads, cfg.head_dim, item, k))
                rows_walked += int(this[sel & walk].sum())
                pairs_walked += int(each_pairs[sel & walk].sum())
            # what the index's two launches copy out of their pages to
            # score `index_keys` (the kernels' read: the stock path gathers
            # every table whole and has no such count)
            fetched = n_full * PL.index_keys_fetched(
                past[sel], this[sel], tok_pad or self.token_budget,
                self.block_size, self.max_blocks_per_seq) if self.pallas else 0
            # the key blocks those copies come in, and how many of them are
            # ONE copy because their pages lie side by side in the pool
            # (`paged_attention.block_runs`, the launches' own rule)
            blocks = (0, 0)
            if self.pallas and tables is not None:
                blocks = PL.index_blocks_walked(
                    past[sel], this[sel], tables[sel],
                    tok_pad or self.token_budget, self.block_size,
                    self.num_blocks)
            if self.latent:
                out = {"attn_keys_latent":
                       n_full * int(each_keys[~sel].sum()),
                       "attn_pairs_latent":
                       n_full * int(each_pairs[~sel].sum())}
            out.update({
                   "index_keys": n_full * int(each_keys[sel].sum()),
                   "index_keys_fetched": fetched,
                   "index_blocks": n_full * blocks[0],
                   "index_blocks_run": n_full * blocks[1],
                   "index_pairs": n_full * int(each_pairs[sel].sum()),
                   "sparse_pairs_selected": n_full * int(chosen[sel].sum()),
                   "sparse_rows_dense": n_full * int(this[~sel].sum()),
                   "sparse_rows_walked": rows_walked,
                   "sparse_pairs_walked": pairs_walked})
        elif self.latent:
            out = {"attn_keys_latent": n_full * keys,
                   "attn_pairs_latent": n_full * pairs}
        wkeys = wpairs = 0
        if n_window:
            W = self.window
            wkeys = keys - int(np.maximum(past - (W - 1), 0).sum())
            # the first `under` rows of a chunk see fewer than W keys
            under = np.clip(W - 1 - past, 0, this)
            wpairs = int((under * (past + 1) + under * (under - 1) // 2
                          + (this - under) * W).sum())
        if self.latent:
            if n_window:
                out.update(attn_keys_latent_window=n_window * wkeys,
                           attn_pairs_latent_window=n_window * wpairs)
        if self.latent or not cfg.layer_plan:
            return out
        return {**out, "attn_keys_full": n_full * keys,
                "attn_keys_window": n_window * wkeys,
                "attn_keys_causal": (n_full + n_window) * keys,
                "attn_pairs_full": n_full * pairs,
                "attn_pairs_window": n_window * wpairs}

    def _harvest_rows(self, cur: _Tick, nxt: np.ndarray, all_arg,
                      events: List[TokenEvent]) -> int:
        """An autoregressive tick's harvest: a slot yields a token iff its
        chunk reached the end of the sequence's tokens (final prefill
        chunk or decode row; `cur.slots`). Appends the events; returns the
        rows computed and dropped."""
        void = 0
        for i, (seq, _n) in enumerate(cur.batch.items):
            props = cur.spec_plan.get(i)
            if props is not None:
                events.extend(self._harvest_spec(
                    seq, props, int(cur.lens[0][i]), all_arg))
            elif seq.status == FINISHED:
                # finished while this row was in flight (an end-of-sequence
                # id or a deadline the host learnt a tick late): computed
                # and dropped. Its pages are free already, which is safe in
                # device order (whoever gets them runs after this tick),
                # and are never hashed
                void += 1
            else:
                self.scheduler.on_harvested(seq, cur.ends[i])
                if seq.rid in cur.slots:
                    events.append(self._emit_token(seq, int(nxt[i]),
                                                   at=cur.ends[i]))
        return void

    def _open_block(self, seq: Sequence):
        """Open the next block of a block-diffusion sequence: what its
        tokens hold behind the last whole block (the prompt's tail, for the
        first block alone) as known rows, the rest masked."""
        Bd = self.cfg.block_length
        tail = seq.tokens[seq.planned():]
        seq.block_ids = tail + [self.cfg.mask_token_id] * (Bd - len(tail))
        seq.block_masked = [False] * len(tail) + [True] * (Bd - len(tail))
        seq.block_forwards = 0

    def _harvest_blocks(self, batch: ScheduledBatch, in_block: List[bool],
                        out: np.ndarray) -> List[TokenEvent]:
        """A block-diffusion tick's harvest. `out` [B, Bd, 3] int32 is what
        `_unmask_rows` returned: per row the proposed token, its
        confidence's bits, whether it was taken. A prefill chunk advances
        `num_computed`. A block with masked rows had a denoise forward:
        the rows taken become tokens, nothing is committed. A block with
        none had its commit forward: its keys and values stand, it is
        committed, its new tokens come out together, and the sequence's
        next block opens.

        On entry a sequence holds what this tick was called with: its
        `num_computed`, `block_ids` and `block_masked` are written here and
        nowhere else, a tick late where the next was launched ahead (which
        planned from counts and took the rest from this tick's `out` on
        the device). The rows of a sequence that finished meanwhile (an
        end-of-sequence id inside its last block, a deadline) were computed
        and are dropped, as `_harvest_rows` drops a plain row: counted in
        `ahead_void_rows` and in nothing else."""
        Bd = self.cfg.block_length
        events: List[TokenEvent] = []
        for i, (seq, n) in enumerate(batch.items):
            if seq.status == FINISHED:
                self.stats["ahead_void_rows"] += n
                continue
            if not in_block[i]:
                self.scheduler.on_computed(seq, n)
                seq.in_flight -= n
                continue
            self.stats["diff_rows"] += Bd
            if any(seq.block_masked):
                for r in np.flatnonzero(out[i, :, 2]):
                    seq.block_ids[r] = int(out[i, r, 0])
                    seq.block_masked[r] = False
                    self.stats["diff_tokens_unmasked"] += 1
                seq.block_forwards += 1
                self.stats["diff_denoise_forwards"] += 1
                continue
            self.stats["diff_commit_forwards"] += 1
            self.stats["diff_blocks_committed"] += 1
            seq.in_flight -= Bd
            new = seq.block_ids[len(seq.tokens) - seq.num_computed:]
            _emit("serving.block_commit", rid=seq.rid, start=seq.num_computed,
                  tokens=len(new), denoise_forwards=seq.block_forwards)
            for tok in new:
                # what the last block holds beyond max_new_tokens (or
                # behind an end-of-sequence token) is computed and dropped
                ev = self._emit_token(seq, tok)
                events.append(ev)
                if ev.finished:
                    break
            else:
                self.scheduler.on_computed(seq, Bd)
                self._open_block(seq)
        return events

    def _emit_token(self, seq: Sequence, tok: int,
                    at: Optional[int] = None) -> TokenEvent:
        """Give a sequence one harvested token (at position `at`, which
        its tick's dispatch left unknown; None: appended) and make its
        event: the end-of-sequence token finishes it unsurfaced ("stop"),
        the max_new_tokens-th finishes it ("length"). Token stamps and the
        time between tokens stay on the scheduler's clock (arrival and
        deadlines are time.monotonic()); the first token's `ttft_s` is read
        on the span clock (`_emit_first_token`)."""
        now = time.monotonic()
        first = seq.first_token_at is None
        if seq.eos >= 0 and tok == seq.eos:
            self.scheduler.append_token(seq, tok, at)  # timestamps
            seq.generated.pop()                    # eos not surfaced,
            # nor the position a row in flight has opened behind it
            del seq.tokens[len(seq.prompt) + len(seq.generated):]
            return self._finish_event(seq, "stop")
        self.scheduler.append_token(seq, tok, at)
        if first:
            self._emit_first_token(seq)
        else:
            _emit("serving.token", rid=seq.rid, first=False, ttft_s=None,
                  tpot_s=now - seq._prev_token_at)
        seq._prev_token_at = now
        if len(seq.generated) >= seq.max_new_tokens:
            ev = TokenEvent(seq.rid, tok, True, "length")
            self._record_completion(seq, "length")
            self.scheduler.finish(seq, "length")
        else:
            ev = TokenEvent(seq.rid, tok, False)
        self._events_by_rid[seq.rid].append(ev)
        return ev

    def _emit_first_token(self, seq: Sequence):
        """A request's first surfaced token: where its time went, on the
        span clock. One `ptpu.serve.first_token` mark in the profile (a
        child of `serve.harvest`; after `_settle` it has no step around it)
        with the request's two stamps as they are, and on the
        `serving.token` event `ttft_s` from the entry of `submit` to here,
        split into `queue_s` (submitted, not yet planned), `device_s` (of
        the time since its first dispatch, what lay inside a tick's
        interval, its own ticks' or another's: a tick's interval runs from
        its call, or the end of the tick before it, to the end of its
        read-back) and `host_s`, the rest: no tick was in flight or being
        read. The executable's call and the read-back's tail, the host's
        too, are inside a tick's interval, so `host_s` is a lower bound
        (by 0.3 to 1.5 ms a synchronous tick on the chip, PERF.md PR 39)."""
        now_ns = time.perf_counter_ns()
        with _tracing.phase("serve.first_token", rid=seq.rid,
                            submit_ns=seq.submit_ns, admit_ns=seq.admit_ns):
            pass
        device_ns = self._device_busy_ns - seq.busy0_ns
        if self._in_flight is not None and self._in_flight.batch is not None:
            # the tick launched ahead runs from where this one ended
            device_ns += now_ns - self._device_free_ns
        _emit("serving.token", rid=seq.rid, first=True,
              ttft_s=(now_ns - seq.submit_ns) * 1e-9, tpot_s=None,
              queue_s=(seq.admit_ns - seq.submit_ns) * 1e-9,
              device_s=device_ns * 1e-9,
              host_s=(now_ns - seq.admit_ns - device_ns) * 1e-9)

    def _harvest_spec(self, seq: Sequence, props: List[int], base: int,
                      all_arg: np.ndarray) -> List[TokenEvent]:
        """Greedy-verify one widened decode chunk. Row ``base`` held the
        scheduled token, rows ``base+1..base+k`` the draft proposals;
        ``all_arg[base+j]`` is the target's own argmax given the chunk
        through row ``j``. Accept the longest proposal prefix that
        matches, then emit it plus one bonus token — byte-for-byte the
        stream plain greedy decode would have produced, just more of it
        per tick. ``num_computed`` advances only over verified rows, so
        the ``num_computed == len(tokens)-1`` decode invariant (and with
        it preemption recompute and prefix caching) is preserved."""
        k = len(props)
        g = [int(all_arg[base + j]) for j in range(k + 1)]
        a = 0
        while a < k and props[a] == g[a]:
            a += 1
        emitted = props[:a] + [g[a]]
        self.spec.commit(seq, a)
        self.spec.record_tick(k, a)
        self.stats["spec_ticks"] += 1
        _emit("spec.tick", rid=seq.rid, proposed=k, accepted=a,
              emitted=len(emitted))
        events: List[TokenEvent] = []
        for tok in emitted:
            self.scheduler.on_computed(seq, 1)
            events.append(self._emit_token(seq, tok))
            if events[-1].finished:
                break
        return events

    # -- bookkeeping ------------------------------------------------------
    def _finish_event(self, seq: Sequence, reason: str,
                      already_finished: bool = False) -> TokenEvent:
        if not already_finished:
            self.scheduler.finish(seq, reason)
        self._record_completion(seq, reason)
        ev = TokenEvent(seq.rid, -1, True, reason)
        self._events_by_rid.setdefault(seq.rid, []).append(ev)
        return ev

    def _record_completion(self, seq: Sequence, reason: str):
        if getattr(seq, "_adapter_pinned", False):
            seq._adapter_pinned = False   # before unpin: re-entrancy safe
            self.adapters.unpin(seq.adapter)
        if self.spec is not None:
            self.spec.forget(seq.rid)
        if seq.block_masked:
            # a finished sequence denoises nothing: what it has in flight
            # is dropped, and whoever reads its state sees no masked row
            seq.block_masked = [False] * len(seq.block_masked)
        self._completions.append(Completion(seq.rid, list(seq.prompt),
                                            list(seq.generated), reason))
        _emit("serving.complete", rid=seq.rid, reason=reason,
              generated=len(seq.generated),
              preemptions=seq.preemptions)

    def _update_gauges(self):
        _emit("serving.gauges", queue_depth=self.scheduler.queue_depth(),
              running=self.scheduler.num_running(),
              kv_utilization=self.blocks.utilization(),
              kv_bytes_in_use=self.blocks.bytes_in_use(),
              kv_bytes_total=self.blocks.bytes_total())

    @property
    def engine_stats(self) -> dict:
        """One merged host-side view (engine + scheduler + block pool), with
        the tick in flight settled so that every dispatched row is in it."""
        self._settle()
        out = {**self.stats, **self.scheduler.stats,
               "kv_utilization": round(self.blocks.utilization(), 4),
               "kv_page_bytes": self.kv_page_bytes,
               "kv_bytes_in_use": self.blocks.bytes_in_use(),
               **{f"blocks_{k}": v for k, v in self.blocks.stats.items()},
               "adapters_resident": self.adapters.num_resident(),
               "adapter_bytes_in_use": self.adapters.bytes_in_use(),
               "adapter_swaps": self.adapters.stats["swaps"],
               "adapter_evictions": self.adapters.stats["evictions"]}
        if self.spec is not None:
            out["spec_acceptance_rate"] = self.spec.acceptance_rate
        if self.window_blocks:
            out["prefix_cache"] = ("off: the window pool keeps no page "
                                   "behind a window for a prefix hit to map")
        if self._ssm:
            out.update(
                state_slots=self.state_slots,
                state_bytes_total=self.state_slots * self.state_slot_bytes,
                state_bytes_in_use=(self.blocks.slots_live()
                                    * self.state_slot_bytes),
                prefix_cache="off: a page hit cannot restore a sequence's "
                             "recurrent state")
        return out
