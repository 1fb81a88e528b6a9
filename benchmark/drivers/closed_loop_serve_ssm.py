"""Closed-loop serving of a model whose layers are mostly state-space
mixers (granite-4.0-h-micro: 36 Mamba-2 layers whose recurrent state lives
in a per-sequence state pool beside the pages of 4 NoPE attention layers of
64-wide heads, a dense SwiGLU behind every layer, three scalars and a tied
head) through `PagedServingEngine`: `closed_loop_serve_latent`'s loop,
clients and window (`lib/serve_window.run`; the judged rate is that
function's own, pauses of the whole machine left out: `run` says why), with
the program's config object built from
the published keys, the engine's state counters in the books, and `correct`
judged against `reference_granite4` in five parts, of what the served path
produced at the published widths (all outside the window, in `setup_s`):

1. every generated token of the correctness requests (prefill in chunks,
   then decode, through the state pool and the pages), teacher-forced
   against the reference's full forward of `reference_len` positions (the
   recurrence token by token): its logit there ties with the reference's
   best (`agreement.judge`) at `agreement_ssm.MIN_AGREEMENT` of the
   positions; then the same of `max_batch` short requests submitted
   together, so that EVERY slot of the pool is live in one tick (several
   prompts in one chunk's tick, then 64 one-row updates), at the same
   share of their positions;
2. one state-space layer's convolution, one-row update and chunked scan
   directly (`llama.ssm_recurrence`, as the tick calls it) at the timed
   shapes (`mixer_shapes`: 64 one-row segments; 63 beside a 449-row
   segment; 61 beside three of 150 rows; and a tick with idle entries) on
   seeded bf16 streams, seeded non-zero carried states and shuffled slots,
   some segments with nothing behind them over a stale slot: the
   convolution against the reference's in float32 and y against the
   recurrence in float32 on the rows the program's convolution gave,
   within `ROWS_TOL_ULPS` a row, and y against the recurrence behind the
   REFERENCE's own convolution (nothing the program prepared on that side)
   within `OWN_CONV_TOL_ULPS`; the pool's new states within a relative
   `STATE_TOL` (a bfloat16 pool fails it); the pool's new carried rows,
   and every slot that no entry of the tick names, bit for bit;
3. the state a sequence holds in its slot after a chunked prefill and
   `state_new_tokens` - 1 decode rows, in every layer, against the
   reference's state behind the same tokens, within `CARRIED_TOL`;
4. the attention op at 64-wide heads and the model's softmax scale
   (`paged_layer_attention`: the page write, then the decode launch or the
   mixed walk, no rope; a head's row in 128 lanes of its page, as the
   engine's pool has it beside the kernel) at both ticks' rows against
   dense float32
   attention, the pools holding the new rows bit for bit and nothing else
   changed;
5. every request returns exactly its `max_new_tokens` (here, and in the
   window by the loop's `failed`).

A program without state-space layers (the parent of PR 56) fails in
`granite_config`, before any weight is made.
"""
from __future__ import annotations

import importlib
import time

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.models import llama as L

from ..lib import (agreement, agreement_ssm, program_trace,
                   reference_granite4 as R, serve_window, ssm_scopes)
from ..lib.harness import Context, Record
from .closed_loop_serve import Loop
from .closed_loop_serve_latent import NOT_JUDGED

ssm_scopes.register()        # before any reader loads a trace

# summed over ticks
STATS = ("ssm_step_rows", "ssm_scan_rows", "ssm_segments",
         "state_slots_live")


def granite_config(cfg: dict, param_dtype) -> "L.LlamaConfig":
    """The program's config object from the published keys."""
    if not hasattr(L, "SsmSpec"):
        raise NotImplementedError(
            "this program has no state-space layers (llama.SsmSpec): it "
            f"cannot run layer_types with {cfg['layer_types'].count('mamba')}"
            " mamba layers")
    kw = R.model_kw(cfg)        # refuses what neither side computes
    sm = L.SsmSpec(
        heads=cfg["mamba_n_heads"], head_dim=cfg["mamba_d_head"],
        d_state=cfg["mamba_d_state"], groups=cfg["mamba_n_groups"],
        d_conv=cfg["mamba_d_conv"], chunk=cfg["mamba_chunk_size"])
    mixer = {"mamba": L.LayerSpec(attn="ssm", rope=None, ssm=sm),
             "attention": L.LayerSpec(attn="full", rope=None,
                                      heads=cfg["num_attention_heads"],
                                      softmax_scale=kw["scale"])}
    return L.LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["shared_intermediate_size"],
        dense_intermediate_size=cfg["shared_intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        max_seq_len=cfg["max_position_embeddings"],
        rms_eps=cfg["rms_norm_eps"],
        layer_plan=tuple(mixer[t] for t in cfg["layer_types"]),
        embed_scale=kw["embed_scale"], residual_scale=kw["residual_scale"],
        logit_divisor=kw["logit_divisor"], tie_embeddings=True,
        dtype=jnp.bfloat16 if param_dtype == jnp.bfloat16 else jnp.float32,
        param_dtype=param_dtype)


def stated_pool(eng, cfg: dict) -> None:
    """The file's `state_slots` and `state_dtype` STATE what the program
    makes (a slot a batch entry, `llama.SSM_STATE_DTYPE`; the engine takes
    neither as an argument) and the readers count slots and bytes by them:
    a file that says otherwise is refused."""
    e = cfg["engine"]
    stated = (e["state_slots"], e["state_dtype"])
    made = (eng.state_slots, eng._state[0].dtype.name)
    if stated != made:
        raise ValueError(f"the configuration states a state pool of {stated}"
                         f" (slots, dtype), the engine made {made}")


def judged_against_reference(done, rids, prompts, new: int, width: int,
                             params, kw: dict, group: int):
    """(agreeing positions, judged positions, the largest gap over the
    tolerance) of the requests' tokens, teacher-forced against the
    reference's full forward of `width` positions, `group` requests a
    forward; a string where a request returned another count than `new`."""
    agreed, worst = 0.0, 0.0
    for g in range(0, len(rids), group):
        seqs = np.zeros((len(rids[g:g + group]), width), np.int32)
        outs = []
        for row, rid, prompt in zip(seqs, rids[g:], prompts[g:]):
            out = np.asarray(done[rid], np.int32)
            if len(out) != new:
                return f"request {rid} returned {len(out)} tokens, not {new}"
            row[:len(prompt)] = prompt
            row[len(prompt):len(prompt) + new] = out
            outs.append(out)
        first = np.asarray([len(p) - 1 for p in prompts[g:g + group]])
        at = first[:, None] + np.arange(new)[None]
        logits = R.logits_at(params, jnp.asarray(seqs), jnp.asarray(at), **kw)
        for rows, out in zip(np.asarray(logits), outs):
            share, gap = agreement.judge(rows, out)
            agreed += share * new
            worst = max(worst, gap)
    return agreed, len(rids) * new, worst


def check_tokens(eng, cfg: dict, params, seed: int, **fault):
    """Part 1 (and 5): the long requests, then `max_batch` short ones live
    together, every slot of the pool taken. `fault` goes to the reference:
    the tests run it under the mistakes the check must catch."""
    c, B = cfg["correctness"], cfg["engine"]["max_batch"]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 3])
    kw = {**R.model_kw(cfg), **fault}
    notes, ok = {}, True
    waves = (("", c["prompt_lens"], c["new_tokens"], c["reference_len"], 1),
             ("batch_", range(c["batch_first_prompt"],
                              c["batch_first_prompt"] + B),
              c["batch_new_tokens"], c["batch_reference_len"],
              c["batch_reference_group"]))
    for name, lens, new, width, group in waves:
        prompts = [rng.integers(1, cfg["vocab_size"], n, dtype=np.int32)
                   for n in lens]
        rids = [eng.submit(p, max_new_tokens=new) for p in prompts]
        live = 0
        while eng.has_work():
            eng.step()
            live = max(live, eng.blocks.slots_live())
        done = {d.rid: d.output_tokens for d in eng.run()}
        with jax.default_matmul_precision("highest"):     # the reference
            got = judged_against_reference(done, rids, prompts, new, width,
                                           params, kw, group)
        if isinstance(got, str):
            return False, {"why": got}
        agreed, judged, worst = got
        ok = ok and agreed / judged >= agreement_ssm.MIN_AGREEMENT
        notes.update({name + "positions_judged": judged,
                      name + "agreement": agreed / judged,
                      name + "largest_gap_over_tolerance": worst,
                      name + "most_slots_live": live})
    return ok and notes["batch_most_slots_live"] == B, notes


def mixer_shapes(cfg: dict):
    """Part 2's ticks as (name, rows of the executable, the entries'
    `this` [B], one_row): a decode tick; the decode rows beside one chunk
    that fills the budget; beside three chunks, not at the end of the
    batch; and fewer rows with idle entries between them."""
    e = cfg["engine"]
    B, T = e["max_batch"], e["token_budget"]
    one = np.ones((B,), np.int32)
    chunk = one.copy()
    chunk[-1] = T - (B - 1)
    three = one.copy()
    three[[B // 8, B // 3, B - 2]] = (T - (B - 3)) // 3
    idle = one.copy()
    idle[B // 2] = (T - B) // 4
    idle[1::4] = 0
    return (("decode", B, one, True), ("chunk", T, chunk, False),
            ("three_chunks", T, three, False), ("idle", T, idle, False))


def mixer_case(cfg: dict, lcfg, seed: int, rows: int, this: np.ndarray):
    """Part 2's inputs for one tick: a seeded stream xbc [rows, conv_dim]
    and dt [rows, H] at a served layer's magnitudes, one-layer pools of
    seeded non-zero states and carried rows, the entries' slots shuffled,
    every fourth live entry with nothing behind it (over a stale slot)."""
    sm = next(s.ssm for s in lcfg.kinds if s.ssm is not None)
    e = cfg["engine"]
    B, S = e["max_batch"], e["state_slots"]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 6, rows,
                                 int(this.sum())])
    live = this > 0
    slots = np.where(live, rng.permutation(S)[:B], S).astype(np.int32)
    past = np.where(live & (np.arange(B) % 4 != 2),
                    rng.integers(1, e["max_len"] - int(this.max()), B), 0
                    ).astype(np.int32)
    cu = np.concatenate([[0], np.cumsum(this)]).astype(np.int32)
    k = jax.random.split(jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), rows + int(this.sum())), 4)
    normal = lambda key, shape, scale, dtype: (
        scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    state, conv = L.ssm_state_pools(sm, 1, S, lcfg.dtype)
    return dict(
        xbc=normal(k[0], (rows, sm.conv_dim), 0.9, lcfg.dtype),
        dt=normal(k[1], (rows, sm.heads), 0.03, lcfg.dtype),
        state=normal(k[2], state.shape, 1.0, state.dtype),
        conv=normal(k[3], conv.shape, 0.9, conv.dtype),
        slots=slots, past=past, this=this.astype(np.int32), cu=cu, sm=sm)


def mixer_outputs(cfg: dict, lp, case: dict, one_row: bool,
                  kernel: bool = False):
    """One `mixer_case` through `llama.ssm_recurrence` (`kernel`: as the
    tick calls it beside the kernels' read path) and through the
    reference, a segment at a time. Returns a dict of the comparisons'
    readings."""
    c, sm = case, case["sm"]
    ssm = R.model_kw(cfg)["ssm"]
    K, C = sm.d_conv, sm.conv_dim
    y, xbc2, state, conv = jax.jit(
        lambda xbc, dt, state, conv: L.ssm_recurrence(
            xbc, dt, lp, sm, state, conv, jnp.int32(0),
            *(jnp.asarray(c[n]) for n in ("slots", "past", "this", "cu")),
            one_row, kernel))(
        c["xbc"], c["dt"], c["state"], c["conv"])
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    y, xbc2, xbc = np.asarray(y), f32(xbc2), f32(c["xbc"])
    new_state, new_conv = f32(state)[0], np.asarray(conv)[0]
    old_state, old_conv = f32(c["state"])[0], np.asarray(c["conv"])[0]
    delta = np.asarray(jax.nn.softplus(
        c["dt"].astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32)))

    # The reference runs on the HOST's CPU: token by token it multiplies a
    # state by exp(-A delta) once a row, and the chip's float32 exp is off
    # by about 1e-6 one way, which over a 449-row segment is 4.5e-4 of the
    # state, four times the bound, where the chunked form takes one exp of
    # a sum (my chip runs, PR 56: the reading grew with the rows, 1.3, 1.7
    # and 4.5 to 5.7 times the bound at 112, 150 and 449 rows, and read
    # 0.0 on one-row segments, where both sides take the same one exp)
    host = jax.devices("cpu")[0]
    lp_host = {n: np.asarray(w.astype(jnp.float32)) for n, w in lp.items()
               if n in ("conv_w", "conv_b", "A_log", "D")}

    @jax.jit
    def reference(x, x2, d, before, s_in):     # one length, many segments
        def one(x, x2, d, before, s_in):
            own = R.conv(x, lp_host, ssm, before=before)
            y, s = R.recurrence(x2, d, lp_host, ssm, state=s_in)
            # and all of it the reference's own: y behind ITS convolution
            return own, y, s, R.recurrence(own, d, lp_host, ssm,
                                           state=s_in)[0]
        return jax.vmap(one)(x, x2, d, before, s_in)

    conv_worst = y_worst = own_worst = state_worst = 0.0
    rows_exact = True
    live = np.flatnonzero(c["this"] > 0)
    for n in np.unique(c["this"][live]):
        who = live[c["this"][live] == n]
        at = c["cu"][who][:, None] + np.arange(n)[None]          # [m, n]
        behind = (c["past"][who] > 0)[:, None, None]
        before = np.where(behind, old_conv[c["slots"][who]].reshape(
            len(who), K - 1, C).astype(np.float32), 0.0)
        s_in = np.where(behind[..., None], old_state[c["slots"][who]], 0.0)
        with jax.default_device(host):
            ref_conv, ref_y, ref_s, ref_own = reference(
                xbc[at], xbc2[at], delta[at], before, s_in)
        flat = lambda a: np.asarray(a).reshape(len(who) * n, -1)
        conv_worst = max(conv_worst, agreement_ssm.judge_rows(
            flat(xbc2[at]), flat(ref_conv))[1])
        y_worst = max(y_worst, agreement_ssm.judge_rows(
            flat(y[at]), flat(ref_y))[1])
        own_worst = max(own_worst, agreement_ssm.judge_rows(
            flat(y[at]), flat(ref_own), agreement_ssm.OWN_CONV_TOL_ULPS)[1])
        state_worst = max(state_worst, agreement_ssm.state_error(
            new_state[c["slots"][who]], ref_s))
        # the slot's new carried rows: the last K - 1 of (old | stream)
        want = np.concatenate([before, xbc[at]], axis=1)[:, -(K - 1):]
        rows_exact &= bool(np.array_equal(
            new_conv[c["slots"][who]].astype(np.float32),
            want.reshape(len(who), -1)))
    others = np.setdiff1d(np.arange(new_state.shape[0] - 1),
                          c["slots"][live])
    untouched = bool(
        np.array_equal(new_state[others], old_state[others])
        and np.array_equal(new_conv[others], old_conv[others]))
    return {"conv_largest_row_error_over_tolerance": conv_worst,
            "y_largest_row_error_over_tolerance": y_worst,
            "y_behind_the_references_convolution_over_tolerance": own_worst,
            "state_largest_error_over_tolerance":
                state_worst / agreement_ssm.STATE_TOL,
            "carried_rows_exact": rows_exact,
            "slots_not_in_the_tick": int(len(others)),
            "those_untouched": untouched}


def check_mixer(cfg: dict, params, lcfg, seed: int, state_dtype=None):
    """Part 2. `state_dtype`: the tests' fault, a pool in another dtype."""
    lp = {n: w[0] for n, w in params["blocks"][0].items()}
    ok, notes = True, {}
    for name, rows, this, one_row in mixer_shapes(cfg):
        case = mixer_case(cfg, lcfg, seed, rows, this)
        if state_dtype is not None:
            case["state"] = case["state"].astype(state_dtype)
        got = mixer_outputs(cfg, lp, case, one_row,
                            bool(cfg["engine"]["pallas"]))
        ok = ok and (
            got["conv_largest_row_error_over_tolerance"] <= 1.0
            and got["y_largest_row_error_over_tolerance"] <= 1.0
            and got["y_behind_the_references_convolution_over_tolerance"]
            <= 1.0
            and got["state_largest_error_over_tolerance"] <= 1.0
            and got["carried_rows_exact"] and got["those_untouched"])
        notes["ssm_" + name] = got
    return ok, notes


def check_carried(eng, cfg: dict, params, seed: int, **fault):
    """Part 3: one request alone, its slot read out of the pool once it
    has finished (a freed slot keeps what it held)."""
    c = cfg["correctness"]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    prompt = rng.integers(1, cfg["vocab_size"], c["state_prompt"],
                          dtype=np.int32)
    rid = eng.submit(prompt, max_new_tokens=c["state_new_tokens"])
    eng.step()
    slot = eng.blocks.slot_of(rid)
    out = {d.rid: d.output_tokens for d in eng.run()}[rid]
    if len(out) != c["state_new_tokens"]:
        return False, {"why": f"the state request returned {len(out)} "
                              f"tokens, not {c['state_new_tokens']}"}
    # the rows the engine computed: the prompt and all but the last token
    seq = jnp.asarray(np.concatenate([prompt, out[:-1]]).astype(np.int32))
    carried = []
    with jax.default_matmul_precision("highest"):
        R.stream(params, seq, **R.model_kw(cfg), **fault, carried=carried)
    state, conv = eng._state
    worst = agreement_ssm.state_error(
        np.asarray(state[:, slot].astype(jnp.float32)),
        np.stack([np.asarray(s) for _, s in carried]))
    rows = agreement_ssm.judge_rows(
        np.asarray(conv[:, slot].astype(jnp.float32)),
        np.stack([np.asarray(t).reshape(-1) for t, _ in carried]))[1]
    good = worst <= agreement_ssm.CARRIED_TOL
    return good, {"carried_state_positions": int(seq.shape[0]),
                  "carried_state_largest_error": worst,
                  "carried_state_error_over_tolerance":
                      worst / agreement_ssm.CARRIED_TOL,
                  "carried_conv_rows_error_in_ulps": rows}


def attention_case(cfg: dict, seed: int, dtype, decode: bool):
    """Part 4's inputs at a timed tick's shapes: `max_batch` entries at
    contexts spread from 64 to max_len, each one decode row, or (not
    `decode`) the last a chunk that fills the token budget; seeded qkv
    [tok, (H + 2 KV) hd] and a one-layer pool pair of seeded keys and
    values, every sequence on its own shuffled pages."""
    e = cfg["engine"]
    B, bs = e["max_batch"], e["block_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // H
    this = np.ones((B,), np.int32)
    if not decode:
        this[-1] = e["token_budget"] - (B - 1)
    hi = e["max_len"] - int(this[-1])
    past = (64 + (hi - 64) * np.arange(B) // (B - 1)).astype(np.int32)
    held = -(-(past + this) // bs)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 4])
    pages = rng.permutation(int(held.sum())).astype(np.int32)
    tables = np.full((B, e["max_len"] // bs), -1, np.int32)
    at = 0
    for b in range(B):
        tables[b, :held[b]] = pages[at:at + held[b]]
        at += held[b]
    k = jax.random.split(jax.random.PRNGKey(seed & 0x7FFFFFFF), 3)
    normal = lambda key, shape: jax.random.normal(
        key, shape, jnp.float32).astype(dtype)
    # a head's row of a page in whole lanes, zeros behind the head: the
    # engine's pool beside the kernel
    from paddle_tpu.ops.pallas.paged_attention_latent import padded_width
    pool = (1, int(held.sum()), KV, bs, hd)
    wide = lambda a: jnp.pad(a, ((0, 0),) * 4 + ((0, padded_width(hd) - hd),))
    return dict(qkv=normal(k[0], (int(this.sum()), (H + 2 * KV) * hd)),
                k=wide(normal(k[1], pool)), v=wide(normal(k[2], pool)),
                tables=jnp.asarray(tables), past=jnp.asarray(past),
                this=jnp.asarray(this))


def attention_outputs(cfg: dict, case: dict, decode: bool, scale=None):
    """(the layer op's output [tok, H hd], the dense reference's, whether
    both pools came back holding the new rows bit for bit and nothing else
    changed) for one `attention_case`: `paged_layer_attention` as the tick
    calls it (no rope; write, then the launch) under `scale` (None: the
    model's), against `reference_granite4.attention` over each sequence's
    own keys."""
    from paddle_tpu.ops.kernels.serving_attention import (
        paged_layer_attention)
    c = case
    bs = cfg["engine"]["block_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // H
    scale = R.model_kw(cfg)["scale"] if scale is None else scale
    tables, past, this = c["tables"], c["past"], c["this"]
    cu = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                          jnp.cumsum(this).astype(jnp.int32)])
    out, _, kp, vp = jax.jit(
        lambda qkv, k, v: paged_layer_attention(
            qkv, k, v, jnp.int32(0), past, this, cu, tables,
            use_neox_style=True, use_pallas="decode" if decode else True,
            softmax_scale=scale, head_dim=hd))(c["qkv"], c["k"], c["v"])
    tok_b = np.repeat(np.arange(len(this)), np.asarray(this))
    pos = np.asarray(past)[tok_b] + (np.arange(len(tok_b))
                                     - np.asarray(cu)[tok_b])
    page = np.asarray(tables)[tok_b, pos // bs]
    qkv3 = c["qkv"].reshape(len(tok_b), H + 2 * KV, hd)
    want_k = c["k"].at[0, page, :, pos % bs, :hd].set(qkv3[:, H:H + KV])
    want_v = c["v"].at[0, page, :, pos % bs, :hd].set(qkv3[:, H + KV:])
    written = bool(jax.jit(jnp.array_equal)(kp, want_k)
                   and jax.jit(jnp.array_equal)(vp, want_v))

    @jax.jit
    def one(q, table, n_past, want_k, want_v):
        # the sequence's keys in order; a query row at n_past + i sees
        # those up to its own: the causal mask of a square of the last
        # rows, which `R.attention` gives when q is padded in front
        f32 = lambda a: a.astype(jnp.float32)
        keys = want_k[0][jnp.clip(table, 0)][..., :hd].transpose(
            0, 2, 1, 3).reshape(-1, KV, hd)
        vals = want_v[0][jnp.clip(table, 0)][..., :hd].transpose(
            0, 2, 1, 3).reshape(-1, KV, hd)
        S = keys.shape[0]
        qg = f32(q).reshape(q.shape[0], KV, H // KV, hd)
        s = jnp.einsum("tkgd,skd->kgts", qg, f32(keys)) * scale
        see = (jnp.arange(S)[None, :]
               <= n_past + jnp.arange(q.shape[0])[:, None])
        s = jnp.where(see[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgts,skd->tkgd", p, f32(vals)).reshape(
            q.shape[0], H * hd)

    ref = np.zeros((len(tok_b), H * hd), np.float32)
    q = qkv3[:, :H]
    with jax.default_matmul_precision("highest"):
        for b in range(len(this)):
            rows_b = slice(int(cu[b]), int(cu[b + 1]))
            ref[rows_b] = np.asarray(one(q[rows_b], tables[b], past[b],
                                         want_k, want_v))
    return np.asarray(out.astype(jnp.float32)), ref, written


def check_attention(cfg: dict, seed: int, **op):
    """Part 4."""
    ok, notes = True, {}
    for decode in (True, False):
        out, ref, written = attention_outputs(
            cfg, attention_case(cfg, seed, jnp.bfloat16, decode), decode,
            **op)
        # a row against its OWN size: the rows' contexts run from 64 to
        # max_len under a softmax scale of 1/64, so their outputs differ
        # fourfold in size (`agreement_ssm.ATTN_TOL_ULPS`)
        good, worst = agreement_ssm.judge_rows(
            out, ref, agreement_ssm.ATTN_TOL_ULPS)
        ok = ok and good and written
        name = "heads64_" + ("decode" if decode else "mixed")
        notes[name + "_largest_error_over_tolerance"] = worst
        notes[name + "_pages_hold_the_rows"] = written
    return ok, notes


def check(eng, cfg: dict, params, lcfg, seed: int):
    t = [time.perf_counter()]

    def lap():
        t.append(time.perf_counter())
        return t[-1] - t[-2]

    stated_pool(eng, cfg)
    ok_tokens, notes = check_tokens(eng, cfg, params, seed)
    phases = {"tokens_s": lap()}
    ok_mixer, mixer_notes = check_mixer(cfg, params, lcfg, seed)
    phases["mixer_s"] = lap()
    ok_state, state_notes = check_carried(eng, cfg, params, seed)
    phases["carried_state_s"] = lap()
    ok_attn, attn_notes = check_attention(cfg, seed)
    phases["attention_s"] = lap()
    stats = eng.engine_stats
    notes.update(mixer_notes, **state_notes, **attn_notes,
                 prefix_cache=stats.get("prefix_cache", "on"),
                 state_bytes_total=stats.get("state_bytes_total"),
                 check_phases=phases)
    return ok_tokens and ok_mixer and ok_state and ok_attn, notes


class SsmLoop(Loop):
    """The closed loop, with the engine's state counters in its books."""

    def counters(self) -> dict:
        out = super().counters()
        for name in STATS:
            out[name] = self.eng.stats[name] - self.stats0[name]
        return out


def run(ctx: Context) -> Record:
    record = serve_window.run(ctx, granite_config, check, SsmLoop)
    # The judged rate is `serve_window`'s own: the window's books WITHOUT
    # the ticks a pause of the whole machine fell into. The latent and the
    # hyper cells judge the raw window, because there a pause hides behind
    # device work and taking it out over-corrects; here it does not hide (a
    # tick is 34 ms and one tick is launched ahead, so a pause of 200 ms
    # idles the chip for most of its length): of ten runs (my chip runs, PR
    # 56, calls 4 and 5) the four without a pause and the six with 0.11 to
    # 1.62 s of pauses read within 0.2 % of one another with the pauses
    # left out (1,553.9 to 1,556.8 in call 5) and 2.9 % apart raw (1,510.5
    # with 1.62 s paused, 1,555.5 with none): the raw rate measures the
    # machine's neighbours. The raw books stay in the notes.
    c = record.counters
    record.notes["raw_window"] = {
        "tokens_out": c["tokens_out_raw"], "elapsed_s": c["elapsed_raw_s"],
        "decode_tokens_per_s": c["tokens_out_raw"] / c["elapsed_raw_s"]}
    slow = [t for t in record.samples["tick_ms"] if t > 250.0]
    record.notes["ticks_over_250_ms"] = {"count": len(slow),
                                         "total_ms": sum(slow)}
    # `gap_p90_ms` and `ttft_mean_ms` are not judged in this cell (one
    # tick in six carries a prefill chunk), and a per-layer metric may
    # list only a cell that reports the end-to-end metric it moves: they
    # and the metrics that move them are read by their own readers into
    # the notes, as `closed_loop_serve_latent` leaves its
    read = {name: importlib.import_module(
        f"benchmark.end_to_end.{name}").read(record)
        for name in ("gap_p90_ms", "ttft_mean_ms")}
    for name in NOT_JUDGED:
        read[name] = importlib.import_module(
            f"benchmark.layer_metrics.{name}").read(record)
    read["tick_attention_share"] = program_trace.scope_share(
        record, *ssm_scopes.ATTENTION)
    for scope in ssm_scopes.SSM:
        read["tick_" + scope + "_share"] = program_trace.scope_share(
            record, scope)
    record.notes["not_judged"] = {k: float(v) for k, v in read.items()
                                  if v is not None}
    return record
