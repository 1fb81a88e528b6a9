"""Training steps of a model with a LAYER PLAN and a held share of its
routed experts (Mellum2-12B-A2.5B: sliding-window and full layers 3:1 with
two ropes, 16 of 64 experts held, a quarter of the vocabulary) through
`distributed.hybrid.make_train_step` on the mesh the configuration names:
`train_steps`'s loop, set-up, window and `Record` fields, with the
program's config object built from the published per-layer lists
(`lib/program.llama_config` is uniform-only), the step's own routed-expert
counters in the books, AdamW as the configuration's `trainer.adamw` has it
(`hybrid.AdamWConfig`'s defaults under a linear warm-up), and `correct`
decided ON THE CHIP AT THE TIMED SIZES by what the step itself computes
(all outside the window, in `setup_s`):

1. before any optimizer state exists, loss and gradients of the very
   per-shard loss that the timed step differentiates
   (`hybrid.make_loss_and_grads`) on batch 0 of the cell's shape against
   `reference_mellum2` in float32, the reference computed a sequence and a
   query block at a time so that it fits and sending every row to the
   experts the program's own routers chose (`agreement_train.judge`: the
   loss, and per parameter leaf the gradient's relative L2 error, at
   bf16's limits);
2. the first step that runs (batch 0, the seeded weights) returns that
   loss, leaves a first moment that is the reference's gradient's, and
   changes every parameter leaf as the reference's AdamW step from the
   reference's own gradients does (`agreement_train.judge_update`: a state
   left unchanged reads 1);
3. `train_steps`'s first-loss band and every loss finite.

A program whose trainer takes no layer plan (the parent of PR 47) fails
here with NotImplementedError from `hybrid.param_specs`, before any weight
is made.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import math
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.distributed import hybrid as H
from paddle_tpu.models import llama as L

from ..lib import (agreement_train, program_trace, reference_mellum2 as R,
                   train_plan_scopes)
from ..lib.harness import (Context, Record, Spans, memory_peak_bytes,
                           seed_key, traced_window)
from ..lib.program import DTYPES
from .train_steps import first_loss_band

train_plan_scopes.register()     # before any reader loads a trace

# the same executable family on the same weights and batch: what differs
# is how XLA fused the forward pass beside an optimizer, so float32 sums
# of bf16 products may round differently in the last places
SAME_LOSS_ABS = 1e-4


def rope_spec(r: dict) -> "L.RopeSpec":
    yarn = r["rope_type"] == "yarn"
    return L.RopeSpec(
        theta=float(r["rope_theta"]),
        yarn_factor=float(r["factor"]) if yarn else 0.0,
        yarn_original=int(r["original_max_position_embeddings"])
        if yarn else 0,
        yarn_beta_fast=float(r["beta_fast"]) if yarn else 32.0,
        yarn_beta_slow=float(r["beta_slow"]) if yarn else 1.0,
        attention_factor=float(r["attention_factor"]) if yarn else 1.0)


def mellum_config(cfg: dict, param_dtype) -> "L.LlamaConfig":
    """The program's config object from the published keys: one
    `LayerSpec` a layer from `layer_types` / `mlp_layer_types` and
    `rope_parameters`, the router `router_width` wide (the published
    `num_experts` where the file holds every expert), `experts_held` the
    share held here."""
    if cfg["attention_bias"] or cfg["tie_word_embeddings"]:
        raise NotImplementedError(
            "attention biases or a tied head: the program computes neither")
    kind = {"sliding_attention": "window", "full_attention": "full"}
    plan = tuple(
        L.LayerSpec(attn=kind[t], heads=cfg["num_attention_heads"],
                    rope=rope_spec(cfg["rope_parameters"][t]), ffn=f)
        for t, f in zip(cfg["layer_types"], cfg["mlp_layer_types"]))
    return L.LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["moe_intermediate_size"],
        dense_intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        max_seq_len=cfg["max_position_embeddings"],
        rms_eps=cfg["rms_norm_eps"],
        num_experts=cfg.get("router_width", cfg["num_experts"]),
        experts_held=tuple(cfg.get("experts_held", ())),
        top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]), router_score="softmax",
        layer_plan=plan, sliding_window=cfg["sliding_window"],
        dtype=jnp.bfloat16, param_dtype=param_dtype)


def build(cfg: dict, tr: dict, seed: int):
    """(lcfg, mesh, shardings, make_params, make_batch): `make_params()`
    puts the seeded weights on the device in the trainer's layout (the
    same weights every call: the step donates them, and the update's
    comparison wants them again), and the jitted batch maker gives
    (tokens, targets) = make_batch(step index)."""
    t = cfg["trainer"]
    lcfg = mellum_config(cfg, DTYPES[t["param_dtype"]])
    dp, pp, tp = (t["mesh"][a] for a in ("dp", "pp", "tp"))
    mesh = H.build_mesh(dp, pp, tp)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                             H.param_specs(lcfg),
                             is_leaf=lambda x: isinstance(x, P))
    key = seed_key(seed)
    embed_scale = t["embed_std"] / 0.02

    def init(k):
        # `init_params`' N(0, 0.02^2) for every matrix, the embedding's
        # rows at the configuration's width (the file says why)
        params = L.init_params(lcfg, k)
        return H.stack_pipeline(
            dict(params, embed=params["embed"] * embed_scale), pp)

    make_params = functools.partial(jax.jit(init, out_shardings=shardings),
                                    key)
    batch, seq = tr["global_batch"], tr["seq_len"]
    rows = NamedSharding(mesh, P("dp", None))
    data_key = jax.random.fold_in(key, 7)

    def draw(i):
        data = jax.random.randint(jax.random.fold_in(data_key, i),
                                  (batch, seq + 1), 0, cfg["vocab_size"],
                                  jnp.int32)
        return data[:, :-1], data[:, 1:]

    return lcfg, mesh, shardings, make_params, jax.jit(
        draw, out_shardings=(rows, rows))


def unstacked(tree):
    """The trainer's tree without the stage axis of its stacks (pp = 1)."""
    return dict(tree, blocks=jax.tree.map(lambda a: a[0], tree["blocks"]))


def optimizer(cfg: dict) -> dict:
    """AdamW's settings from the file, as `hybrid.AdamWConfig` and
    `reference_mellum2.adamw_step` both take them (`hybrid`'s defaults
    where the file names none)."""
    return dict(dataclasses.asdict(H.AdamWConfig()),
                **cfg["trainer"]["adamw"])


def check(cfg: dict, tr: dict, lcfg, mesh, params, tokens, targets):
    """`agreement_train`'s comparison 1: (correct, notes, the program's
    loss on this batch, the reference's gradients). `lcfg` is the config
    the PROGRAM computes under; the reference reads the file's keys (a
    probe of the comparison hands a degraded `lcfg`)."""
    batch, seq = tokens.shape
    M = tr["microbatches"]
    t0 = time.perf_counter()
    loss, grads, stats = H.make_loss_and_grads(lcfg, mesh, M, chosen=True)(
        params, tokens, targets)
    loss = float(loss)
    # the program's launches (a microbatch's layers one after another, its
    # rows sequence-major) in the reference's order [layers, B, T, top_k]
    chosen = stats.pop("chosen").reshape(
        M, -1, batch // M, seq, lcfg.top_k).swapaxes(0, 1).reshape(
        -1, batch, seq, lcfg.top_k)
    notes = {"moe_stats": {k: int(v) for k, v in stats.items()},
             "program_s": time.perf_counter() - t0}
    t1 = time.perf_counter()
    ref_loss, ref_grads = R.loss_and_grads(
        params, tokens, targets, view=unstacked, chosen=chosen,
        q_block=cfg["correctness"]["q_block"], **R.model_kw(cfg))
    ok, whole = agreement_train.judge(loss, grads, ref_loss, ref_grads,
                                      view=unstacked)
    notes.update(whole, reference_s=time.perf_counter() - t1)
    return ok, notes, loss, ref_grads


def run(ctx: Context) -> Record:
    cfg, tr = ctx.config, ctx.traffic
    spans = Spans()
    phases = {"imports_s": time.perf_counter() - ctx.process_start_s}
    t_build = time.perf_counter()
    lcfg, mesh, shardings, make_params, make_batch = build(cfg, tr,
                                                            ctx.seed)
    params = make_params()
    batch, seq = tr["global_batch"], tr["seq_len"]
    chips = math.prod(mesh.shape.values())
    jax.block_until_ready(params)
    phases["weights_s"] = time.perf_counter() - t_build

    t_check = time.perf_counter()
    agrees, agreement, judged_loss, ref_grads = check(
        cfg, tr, lcfg, mesh, params, *make_batch(0))
    # off the chip while the first step runs: the step's activations want
    # the room
    ref_grads = jax.device_get(ref_grads)
    gc.collect()
    phases["agreement_s"] = time.perf_counter() - t_check

    t_step = time.perf_counter()
    opt = jax.jit(H.init_opt_state, out_shardings={
        "m": shardings, "v": shardings,
        "step": NamedSharding(mesh, P())})(params)
    hp = optimizer(cfg)
    step = H.make_train_step(
        lcfg, mesh, num_microbatches=tr["microbatches"],
        hp=H.AdamWConfig(**hp), with_stats=True)
    losses, stats, step_ms = [], [], []
    n_steps = 0

    def one_step():
        nonlocal params, opt, n_steps
        t0 = time.perf_counter()
        with spans.span("bench.make_batch"):
            tokens, targets = make_batch(n_steps)
        with spans.span("bench.train_step"):
            params, opt, loss, moe = step(params, opt, tokens, targets)
        with spans.span("bench.fetch_loss"):
            jax.block_until_ready(loss)
        t1 = time.perf_counter()
        n_steps += 1
        losses.append(loss)
        stats.append(moe)
        step_ms.append((t1 - t0) * 1e3)
        return t0, t1

    def moe_sums(lo, hi):
        """The step's own counters summed over steps lo .. hi - 1."""
        return {k: sum(int(s[k]) for s in stats[lo:hi])
                for k in H.MOE_STATS}

    jax.block_until_ready(opt)
    phases["optimizer_and_step_s"] = time.perf_counter() - t_step
    t_warm = time.perf_counter()
    one_step()
    phases["first_step_s"] = time.perf_counter() - t_warm
    t_update = time.perf_counter()
    updates, agreement["update"] = agreement_train.judge_update(
        make_params(), params, opt["m"],
        jax.device_put(ref_grads, shardings), hp, view=unstacked)
    updates = updates and int(opt["step"]) == 1
    del ref_grads
    gc.collect()
    phases["update_s"] = time.perf_counter() - t_update
    t_warm = time.perf_counter()
    for _ in range(tr["warm_steps"] - 1):
        one_step()
    warm = len(losses)
    phases["warm_steps_s"] = time.perf_counter() - t_warm

    gc.collect()
    gc.freeze()
    made0 = ctx.compile_log.made
    setup_s = time.perf_counter() - ctx.process_start_s
    deadline = time.perf_counter() + ctx.seconds
    first_start_s = last_end_s = None
    while time.perf_counter() < deadline:
        t0, last_end_s = one_step()
        if first_start_s is None:
            first_start_s = t0
    measured = len(losses) - warm
    counters = {
        "elapsed_s": last_end_s - first_start_s,
        "steps": measured, "tokens": measured * batch * seq, "seq_len": seq,
        "sequences": measured * batch, "chips": chips,
        "compiles_in_window": ctx.compile_log.made - made0,
        **moe_sums(warm, warm + measured),
    }

    trace = trace_counters = None
    notes = {"setup_phases": phases, "agreement": agreement}
    if ctx.trace:
        with traced_window(ctx.workload["name"]) as traced:
            for _ in range(tr["trace_steps"]):
                one_step()
        trace = traced["reduced"]
        trace_counters = {"steps": tr["trace_steps"],
                          "tokens": tr["trace_steps"] * batch * seq,
                          "sequences": tr["trace_steps"] * batch,
                          **moe_sums(warm + measured, len(stats))}
        notes["trace_file"] = traced["path"]
        # for a reader of the log: every scope's share, the ones no metric
        # of this cell names (`attention`, `ffn`: what lies outside the
        # kernels' scopes) among them
        notes["scope_shares"] = program_trace.scope_shares(
            program_trace.load(traced["path"]))

    values = [float(x) for x in losses]
    lo, hi = first_loss_band(cfg)
    finite = [math.isfinite(v) for v in values]
    same_loss = abs(values[0] - judged_loss) <= SAME_LOSS_ABS
    correct = (agrees and updates and same_loss and all(finite)
               and lo <= values[0] <= hi)
    # for a reader of the log: how the trained router's load on the held
    # experts moves over the run (an even router: a quarter of the pairs)
    notes["moe_pairs_held_by_step"] = [int(s["moe_pairs_held"])
                                       for s in stats]
    notes.update(first_loss=values[0], last_loss=values[-1],
                 first_loss_band=[lo, hi], judged_loss=judged_loss,
                 first_step_returns_the_judged_loss=same_loss,
                 first_step_makes_the_references_update=updates)
    return Record(
        correct=correct, attempted=measured,
        failed=sum(not f for f in finite[warm:warm + measured]),
        setup_s=setup_s, samples={"step_ms": step_ms[warm:warm + measured]},
        counters=counters, spans=spans, trace=trace,
        trace_counters=trace_counters, notes=notes, context=ctx,
        memory_peak_bytes=memory_peak_bytes())
