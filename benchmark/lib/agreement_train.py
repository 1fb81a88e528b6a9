"""What decides `correct` in a training cell with a reference of its own
(`reference_mellum2`), on the chip at the timed sizes, in two comparisons
with the plain float32 reference, both of the whole step:

1. LOSS AND GRADIENTS (`judge`): the very per-shard loss the timed step
   differentiates (`hybrid.make_loss_and_grads`) on batch 0 of the timed
   shape, leaf by leaf, before any optimizer state exists. A gradient leaf
   is compared by its relative L2 error |g - r| / |r| (a stack's layers
   one by one): a gradient is a sum over 16,384 positions, so a norm says
   whether the sum is the reference's sum. The program's stream is bf16
   and the reference's float32, and a router is discrete: where a row's
   eighth and ninth score lie within bf16's rounding the two sides would
   send the row to different experts and its stream would differ from
   there on (PR 47's first delivery read 6-8 % on the router's and the
   experts' leaves for that reason alone, which hid an 8-bit product). So
   the program hands over THE EXPERTS EACH LAYER'S ROUTER CHOSE
   (`make_loss_and_grads(chosen=True)`: what `routed_ffn_load` itself
   routed by) and the reference sends every row there
   (`reference_mellum2.sparse_ffn(chosen=)`; the weights stay the
   reference's own softmax). What is left is rounding (`LIMITS` says
   of what), and the limits lie under what products in 8 bits in the
   experts, or a window one key short, read: each fails them.
2. THE FIRST STEP'S UPDATE (`judge_update`): after the timed executable's
   first step (batch 0, the seeded weights) its first moment, leaf by
   leaf, against the reference's AdamW step from the reference's OWN
   gradients (`reference_mellum2.adamw_step`; after one step m is the
   clipped gradient times 1 - beta1, so this holds the timed executable's
   gradients themselves to the reference at the limits of 1), and the
   CHANGE of every parameter leaf against the reference's change, by the
   worst leaf's relative L2 error. A state left unchanged reads exactly 1
   there. AdamW's first move is lr * g / (|g| + eps), a sign: an element
   whose gradient is smaller than its rounding error moves the other way
   and counts twice, so a sound step reads sqrt(4 x share of such
   elements), two tenths on the worst leaf and not the gradient's few per
   cent; the limit lies between that and 1.

Every limit lies between two readings taken on the chip at the timed sizes
(PERF.md section 6, PR 47): what the program reads over seeds, and what it
reads with 8-bit products in the experts or a window one key short
(`python3 benchmark/lib/agreement_train.py --probe ...` on the chip), each
of which must fail comparison 1.
"""
from __future__ import annotations

import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import reference_mellum2 as R

# comparison 1: group -> (limit, why). The loss is an absolute difference
# (it is near ln 24576 + 0.46 = 10.57); the others
# relative L2 errors of a group's worst leaf. Sound readings: eleven runs on
# eleven seeds; the controls: `--probe` on one (my chip runs, PR 47). What
# is left with no row sent elsewhere is the rounding of a whole step in
# bf16, stream, intermediates and cotangents, not of its products alone:
# 0.6 to 3.8 % by group here, where one layer's routed FFN alone read 0.5 %
# (PR 47's first delivery); a 256-wide copy on the CPU reads 1 % in every
# group, and 0.3 % with a float32 stream whose normed inputs alone are
# rounded.
LIMITS = {
    "loss": (1e-3, "float32 loss of bf16 products against float32 "
                   "products: the rounding of 16,384 positions' logits "
                   "averages out (read at most 6e-5; neither control moves "
                   "it)"),
    "attention": (0.021, "wq, wk, wv, wo of every layer (sound 0.01558 to "
                         "0.01570, the worst leaves wq and wk; a window "
                         "one key short 0.0284)"),
    "router": (0.053, "the difference of nearly equal expert outputs, the "
                      "least conditioned leaf (sound 0.0331 to 0.0393; "
                      "8-bit expert products 0.0743)"),
    "experts": (0.046, "a layer's w1, w3, w2 over its held experts (sound "
                       "0.0276 to 0.0342; 8-bit products 0.0695, the worst "
                       "leaf w2)"),
    "norms": (0.044, "gains of the norms: sums over every position (sound "
                     "0.0288 to 0.0351, the worst leaf mlp_norm; 8-bit "
                     "products 0.0530)"),
    "embed": (0.009, "the rows that occur: the whole backward pass in bf16 "
                     "(sound 0.00706 to 0.00728; 8-bit products 0.0113)"),
    "lm_head": (0.0074, "one bf16 product from float32 logits (sound "
                        "0.00568 to 0.00571; 8-bit products 0.0097)"),
}
GROUP = {"wq": "attention", "wk": "attention", "wv": "attention",
         "wo": "attention", "router": "router", "w1": "experts",
         "w3": "experts", "w2": "experts", "attn_norm": "norms",
         "mlp_norm": "norms", "final_norm": "norms", "embed": "embed",
         "lm_head": "lm_head"}
# comparison 2: the worst leaf's |change - reference's change| / |reference's
# change| after the first step. 1 is a state left unchanged; a sound step
# reads 0.212 to 0.232 over ten seeds (the router, whose gradient is off
# by 3.8 %: sqrt(4 x 0.038 / pi) = 0.22), the embedding 0.04. The limit
# leaves the more room above the reading: fresh seeds read higher, and
# nothing reads between.
UPDATE_LIMIT = 0.5


def _rel(g, r):
    g, r = g.astype(jnp.float32), r.astype(jnp.float32)
    return jnp.sqrt(jnp.sum((g - r) ** 2)
                    / jnp.maximum(jnp.sum(r ** 2), 1e-30))


@jax.jit
def leaf_errors(got, want) -> dict:
    """{leaf name: relative L2 error} of two gradient trees in the layout
    `embed`, `final_norm`, `lm_head`, `blocks` a tuple of stacks [n, ...]:
    a stack's leaf a layer (`blocks.<stack>.<layer>.<name>`). On the
    device the trees live on; only the numbers come to the host."""
    out = {name: _rel(got[name], want[name])
           for name in ("embed", "final_norm", "lm_head")}
    for k, (gs, ws) in enumerate(zip(got["blocks"], want["blocks"])):
        for name in sorted(ws):
            for layer in range(ws[name].shape[0]):
                out[f"blocks.{k}.{layer}.{name}"] = _rel(gs[name][layer],
                                                         ws[name][layer])
    return out


def worst_by_group(errors: dict) -> dict:
    worst = {}
    for name, err in errors.items():
        group = GROUP[name.rsplit(".", 1)[-1]]
        worst[group] = max(worst.get(group, 0.0), float(err))
    return worst


def judge(loss, grads, ref_loss, ref_grads, view=lambda tree: tree):
    """Comparison 1: (correct, notes). Every group's worst leaf under its
    limit, and the losses within theirs. `view` reads the reference's
    layout out of the trees (inside the jitted comparison: the trainer's
    stage axis comes off without a copy); they live on one device or on
    the host."""
    errors = jax.jit(lambda g, r: leaf_errors(view(g), view(r)))(
        grads, ref_grads)
    errors = {k: float(v) for k, v in errors.items()}
    worst = dict(worst_by_group(errors),
                 loss=abs(float(loss) - float(ref_loss)))
    failed = sorted(g for g, v in worst.items()
                    if not (np.isfinite(v) and v <= LIMITS[g][0]))
    notes = {"loss": float(loss), "reference_loss": float(ref_loss),
             "worst": worst, "limits": {g: LIMITS[g][0] for g in worst},
             "failed_groups": failed, "leaves_compared": len(errors),
             "leaf_errors": errors}
    return not failed, notes


def judge_update(old, new, moment, ref_grads, hp: dict,
                 view=lambda tree: tree):
    """Comparison 2: (correct, notes) after the timed executable's FIRST
    step. `old` and `new` are the weights before and after it, `moment`
    its first moment, `ref_grads` the reference's gradients on that batch,
    `hp` the optimizer's settings as `reference_mellum2.adamw_step` takes
    them. The moment is held to the reference's by `LIMITS`' groups, the
    change of the weights by `UPDATE_LIMIT` on the worst leaf."""
    @jax.jit
    def errors(old, new, moment, ref_grads):
        old, new, moment, ref_grads = (view(t) for t in (old, new, moment,
                                                         ref_grads))
        ref_new, ref_m, _ = R.adamw_step(old, ref_grads, None, None, 1, **hp)
        change = lambda after: jax.tree.map(jnp.subtract, after, old)
        return (leaf_errors(moment, ref_m),
                leaf_errors(change(new), change(ref_new)))

    moment_errors, change_errors = (
        {k: float(v) for k, v in e.items()}
        for e in errors(old, new, moment, ref_grads))
    worst = worst_by_group(moment_errors)
    failed = sorted(g for g, v in worst.items()
                    if not (np.isfinite(v) and v <= LIMITS[g][0]))
    leaf, change = max(change_errors.items(), key=lambda kv: (
        kv[1] if np.isfinite(kv[1]) else np.inf))
    moved = bool(np.isfinite(change) and change <= UPDATE_LIMIT)
    notes = {"moment_worst": worst, "moment_failed_groups": failed,
             "change_worst": change, "change_worst_leaf": leaf,
             "change_limit": UPDATE_LIMIT,
             "change_by_group": worst_by_group(change_errors)}
    return moved and not failed, notes


# --------------------------------------------------------------------------
# the two readings that the limits lie between, on the chip
# --------------------------------------------------------------------------

def main() -> int:
    """The cell's comparison 1 with the program degraded, to show that it
    fails: `--probe int8_experts` rounds the operands of every grouped
    product to 8 bits (symmetric, a scale a tensor, in float32
    arithmetic: a cast to float8 and back is folded away by the v5e's
    compiler, which has no such type, and read as no change at all),
    `--probe short_window` runs the program under a window one key short;
    `--probe none` is the program as it is. Prints one JSON object of
    readings."""
    import argparse
    import dataclasses
    import json

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--probe", choices=("none", "int8_experts",
                                        "short_window"), default="none")
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()

    from benchmark.drivers import train_steps_plan as D
    from benchmark.lib import harness
    from paddle_tpu.models import llama as L

    root = harness.ROOT
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"]
                if w["name"] == "train_moe_window_8k")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        tr = json.load(f)
    harness.require_tpu(1)
    harness.configure_compile_cache()
    lcfg, mesh, _, make_params, make_batch = D.build(cfg, tr, args.seed)
    program_cfg = lcfg
    if args.probe == "short_window":
        program_cfg = dataclasses.replace(
            lcfg, sliding_window=lcfg.sliding_window - 1)
    if args.probe == "int8_experts":
        exact = L._grouped_matmul

        def int8(x):
            # straight through: the gradient passes as if nothing was
            # rounded (`round` alone has none, and every gradient reads 1.0)
            scale = jnp.max(jnp.abs(x)).astype(jnp.float32) / 127.0
            q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
            return x + jax.lax.stop_gradient((q * scale).astype(x.dtype) - x)

        L._grouped_matmul = lambda xs, w, sizes, offset: exact(
            int8(xs), int8(w), sizes, offset)
    ok, notes, _, _ = D.check(cfg, tr, program_cfg, mesh, make_params(),
                              *make_batch(0))
    # a leaf name's worst layer: what a limit by group is chosen from
    by_name = {}
    for leaf, err in notes.pop("leaf_errors").items():
        name = leaf.rsplit(".", 1)[-1]
        by_name[name] = max(by_name.get(name, 0.0), err)
    notes["worst_by_name"] = by_name
    print(json.dumps({"probe": args.probe, "seed": args.seed,
                      "correct": ok, "readings": notes}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
