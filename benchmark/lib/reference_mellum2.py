"""The plain reference of Mellum2-12B-A2.5B (JetBrains, `model_type:
mellum`) for TRAINING: forward pass, mean next-token cross entropy and its
gradients. x is the residual stream, h = RMSNorm(x) with eps 1e-6, no
biases anywhere (`attention_bias` false).

Layer l has an attention of kind t = `layer_types[l]` in {sliding_attention,
full_attention} (three sliding, one full, seven times over) with 32 query
heads over 4 key-value heads of 128, and a sparse FFN (`mlp_layer_types`
all "sparse").

Attention, on one sequence's rows x [T, d]:
    h = RMSNorm(x; attn_norm)
    q = h Wq [T, 32, 128]   k = h Wk, v = h Wv [T, 4, 128]
    rope on the whole head of q and k, rotate-half (x1, x2 the two halves:
    (x1 c - x2 s, x2 c + x1 s)), by `rope_parameters[t]`:
      sliding: default rope, theta 5e5: f_i = theta^(-2i/128), i = 0..63
      full:    YaRN, theta 5e5, factor 16, original 8192, beta_fast 32,
               beta_slow 1: d(b) = 128 ln(8192 / (2 pi b)) / (2 ln theta);
               low = max(floor(d(32)), 0), high = min(ceil(d(1)), 127);
               ramp_i = clip((i - low) / (high - low), 0, 1);
               f'_i = (f_i / 16) ramp_i + f_i (1 - ramp_i);
               cos and sin times attention_factor 1.2772588722239782
               (= 0.1 ln 16 + 1), whatever the length
    scores q.k / sqrt(128), query head a on key-value head a // 8, softmax
        in float32 over keys j <= i (full) or i - 1023 <= j <= i (sliding:
        `sliding_window` 1024 keys, the query's own among them; *assumed*:
        that convention, the one the `sliding_window` key has in the
        families that share this config layout)
    x = x + concat_heads(sum_j p_j v_j) Wo
    (*assumed*: no QK-norm; the config names none)
FFN:
    h = RMSNorm(x; mlp_norm)
    p = softmax(h Wr) in float32 over all 64 experts (*assumed*: a softmax;
        the config has no scoring key and `norm_topk_prob` is the softmax
        families' key); the 8 largest; w = p_top / sum(p_top)
        (`norm_topk_prob` true);
    x = x + sum_j w_j E_{e_j}(h), E_e(h) = (silu(h W1_e) * (h W3_e)) W2_e of
        width 896 (`moe_intermediate_size`); no shared expert
        (*assumed*: `intermediate_size` 7168 is unused, no layer is dense).
    Given a held share (`held` = (first, count)) only the experts first ..
    first + count - 1 are here: the router still runs over all 64 and picks
    its 8, a pair on an absent expert adds nothing (its chip's part of the
    sum), and that partial result goes on to the next layer.
logits = RMSNorm(x; final_norm) W_head   (untied)
loss = mean over positions of logsumexp(logits) - logits[target]
(*assumed*: no auxiliary load-balancing loss, the config names no
coefficient; no MTP module, `config` has no key for one.)

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, no kernel, no sort, no scan over
stacked layers (a python loop over the layers, each reading its place in
its kind's stack), gradients by `jax.grad` of this very forward pass. It
is independent of `paddle_tpu.models.llama` and shares only the layout of
the weight tree: `embed` [V, d], `final_norm` [d], `lm_head` [d, V] and
`blocks`, a tuple of stacks, one a kind of layer in order of first
occurrence, with `wq wk wv wo attn_norm mlp_norm router` [n, d, 64] and
`w1 w3` [n, held, d, f], `w2` [n, held, f, d]. What a layer is comes in as
`layers`, one (stack, place in it, attention kind) a layer, which
`layers_of` reads off a configuration file's own keys.

Departures from a textbook forward and backward pass, all for memory alone
(at the timed sizes, 2 x 8192 tokens at published widths, it runs on the
chip beside the program's 2.4 GB of float32 weights):
- the batch is computed a sequence at a time, the sequences' loss sums and
  gradients added (`loss_and_grads`);
- attention is computed a block of `q_block` queries at a time against all
  the sequence's keys under the mask (32 heads x 8192^2 float32 scores are
  8.6 GB a sequence), and a block's scores are recomputed in the backward
  pass (`jax.checkpoint`) rather than kept;
- a layer's activations are recomputed in the backward pass
  (`jax.checkpoint` a layer): 16 experts x three [8192, 896] float32
  intermediates are 1.4 GB a layer;
- the held experts are computed one at a time over every row (a
  `lax.scan` over the experts held, none over layers), a row's output
  times its combine weight, which is zero where the router did not pick
  the expert: the same sum, without a gather of rows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

KINDS = {"sliding_attention": "window", "full_attention": "full"}


def layers_of(cfg: dict):
    """[(stack, place, attention kind)] a layer, stacks numbered in order
    of first occurrence of a layer type, from the published
    `layer_types`."""
    order, count, out = [], {}, []
    for published in cfg["layer_types"][:cfg["num_hidden_layers"]]:
        if published not in order:
            order.append(published)
        n = count.get(published, 0)
        out.append((order.index(published), n, KINDS[published]))
        count[published] = n + 1
    return out


def rope_kw(cfg: dict) -> dict:
    """{attention kind: (theta, yarn or None)} from `rope_parameters`."""
    out = {}
    for published, r in cfg["rope_parameters"].items():
        yarn = None
        if r["rope_type"] == "yarn":
            yarn = {k: float(r[k]) for k in (
                "factor", "original_max_position_embeddings", "beta_fast",
                "beta_slow", "attention_factor")}
        out[KINDS[published]] = (float(r["rope_theta"]), yarn)
    return out


def model_kw(cfg: dict) -> dict:
    """What `loss_and_grads` needs beside the weights, from a configuration
    file's own keys (`experts_held` where the file holds a share)."""
    return {
        "layers": layers_of(cfg), "ropes": rope_kw(cfg),
        "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "window": cfg["sliding_window"], "top_k": cfg["num_experts_per_tok"],
        "eps": cfg["rms_norm_eps"],
        "held": tuple(cfg.get("experts_held") or ()) or None,
    }


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps
                             ) * gain


def rope_tables(T: int, head_dim: int, theta: float, yarn):
    """(cos, sin) [T, head_dim / 2] of positions 0 .. T - 1."""
    i = jnp.arange(0, head_dim, 2, dtype=jnp.float32)
    f = theta ** (-i / head_dim)
    factor = 1.0
    if yarn is not None:
        def dim_of(beta):
            return (head_dim * math.log(
                yarn["original_max_position_embeddings"]
                / (2 * math.pi * beta)) / (2 * math.log(theta)))
        low = max(math.floor(dim_of(yarn["beta_fast"])), 0)
        high = min(math.ceil(dim_of(yarn["beta_slow"])), head_dim - 1)
        ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        f = f / yarn["factor"] * ramp + f * (1.0 - ramp)
        factor = yarn["attention_factor"]
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * f[None, :]
    return jnp.cos(angles) * factor, jnp.sin(angles) * factor


def rotate(x, cos, sin):
    """x [T, H, hd], rotate-half over the whole head."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def masked_attention(q, k, v, window: int, q_block: int):
    """q [T, H, hd], k and v [T, KV, hd] -> [T, H, hd]: softmax attention
    under the causal mask (window 0) or the causal window of `window` keys,
    a block of `q_block` queries at a time against every key."""
    T, H, hd = q.shape
    KV = k.shape[1]
    q_block = min(q_block, T)
    assert T % q_block == 0, (T, q_block)
    key_pos = jnp.arange(T)

    @jax.checkpoint
    def block(args):
        qb, first = args                              # [q_block, KV, G, hd]
        pos = first + jnp.arange(q_block)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) / math.sqrt(hd)
        seen = key_pos[None, :] <= pos[:, None]
        if window:
            seen = seen & (key_pos[None, :] > pos[:, None] - window)
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v)

    blocks = q.reshape(T // q_block, q_block, KV, H // KV, hd)
    out = jax.lax.map(block, (blocks, jnp.arange(0, T, q_block)))
    return out.reshape(T, H, hd)


def attention_block(x, lp, cos, sin, *, kv_heads: int, head_dim: int,
                    window: int, eps: float, q_block: int):
    T = x.shape[0]
    h = _rms_norm(x, lp["attn_norm"], eps)
    q = rotate((h @ lp["wq"]).reshape(T, -1, head_dim), cos, sin)
    k = rotate((h @ lp["wk"]).reshape(T, kv_heads, head_dim), cos, sin)
    v = (h @ lp["wv"]).reshape(T, kv_heads, head_dim)
    o = masked_attention(q, k, v, window, q_block)
    return x + o.reshape(T, -1) @ lp["wo"]


def expert_sum(h, lp, weight):
    """sum_e weight[:, e] * E_e(h) over the experts of `lp` (w1, w3 [n, d,
    f], w2 [n, f, d]), E_e(h) = (silu(h W1_e) * (h W3_e)) W2_e over EVERY
    row h [T, d]; weight [T, n] is zero where the router did not pick the
    expert. One expert at a time, its intermediates recomputed in the
    backward pass (memory alone: three [T, f] float32 arrays an expert)."""
    @jax.checkpoint
    def add(out, ew):
        w1, w3, w2, col = ew
        return out + col[:, None] * (
            (jax.nn.silu(h @ w1) * (h @ w3)) @ w2), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(h),
                          (lp["w1"], lp["w3"], lp["w2"], weight.T))
    return out


def sparse_ffn(h, lp, *, top_k: int, held=None, chosen=None):
    """The routed experts' sum over rows h [T, d]; under `held` = (first,
    count) the part that the experts held here give. `chosen` [T, top_k]
    int32, where given, are the experts a comparison wants each row on
    (the program's own choice, so that a router logit that rounds the
    other way does not send a row elsewhere); their weights are still
    this function's softmax, renormalised over them."""
    experts = lp["router"].shape[-1]
    p = jax.nn.softmax(h @ lp["router"], axis=-1)
    if chosen is None:
        top, chosen = jax.lax.top_k(p, top_k)
    else:
        top = jnp.take_along_axis(p, chosen, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    # [T, experts]: a row's weight on each expert, zero where not chosen
    weight = jnp.sum(jax.nn.one_hot(chosen, experts, dtype=h.dtype)
                     * top[..., None], axis=1)
    first, count = held or (0, experts)
    return expert_sum(h, lp, weight[:, first:first + count])


def layer(x, lp, cos, sin, chosen=None, *, window: int, kv_heads: int,
          head_dim: int, top_k: int, eps: float, held, q_block: int):
    x = attention_block(x, lp, cos, sin, kv_heads=kv_heads,
                        head_dim=head_dim, window=window, eps=eps,
                        q_block=q_block)
    return x + sparse_ffn(_rms_norm(x, lp["mlp_norm"], eps), lp,
                          top_k=top_k, held=held, chosen=chosen)


def sequence_loss_sum(params, tokens, targets, *, layers, ropes, kv_heads,
                      head_dim, window, top_k, eps, held=None,
                      q_block: int = 512, chosen=None):
    """Sum over one sequence's positions of the next-token cross entropy:
    tokens and targets [T] int32; `chosen` [layers, T, top_k] int32, where
    given, the experts each layer's rows are sent to (`sparse_ffn`)."""
    T = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0)
    tables = {kind: rope_tables(T, head_dim, theta, yarn)
              for kind, (theta, yarn) in ropes.items()}
    for n, (stack, place, kind) in enumerate(layers):
        lp = {k: v[place] for k, v in params["blocks"][stack].items()}
        run = jax.checkpoint(functools.partial(
            layer, window=window if kind == "window" else 0,
            kv_heads=kv_heads, head_dim=head_dim, top_k=top_k, eps=eps,
            held=held, q_block=q_block))
        x = run(x, lp, *tables[kind],
                None if chosen is None else chosen[n])
    logits = _rms_norm(x, params["final_norm"], eps) @ params["lm_head"]
    true = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.scipy.special.logsumexp(logits, axis=-1) - true)


def loss_and_grads(params, tokens, targets, view=lambda p: p, chosen=None,
                   **kw):
    """(mean loss, gradients like `params`) of a batch tokens, targets
    [B, T], in float32 at the highest matmul precision, a sequence at a
    time. `params` are float32. `view` reads the weight tree out of what
    is handed in (the trainer's stacks carry a leading stage axis of 1;
    taking it off outside would copy 2.4 GB), and the gradients come in
    the layout of what was handed in. `chosen` [layers, B, T, top_k]
    int32, where given: the experts a comparison wants every layer's rows
    on (`sparse_ffn`)."""
    B, T = tokens.shape

    @functools.partial(jax.jit, donate_argnums=(4, 5))
    def add(params, tok, tgt, chosen, loss, grads):
        l, g = jax.value_and_grad(lambda p: sequence_loss_sum(
            view(p), tok, tgt, chosen=chosen, **kw))(params)
        return loss + l, jax.tree.map(jnp.add, grads, g)

    with jax.default_matmul_precision("highest"):
        loss = jnp.zeros((), jnp.float32)
        grads = jax.tree.map(jnp.zeros_like, params)
        for b in range(B):
            loss, grads = add(params, tokens[b], targets[b],
                              None if chosen is None else chosen[:, b],
                              loss, grads)
        scale = jnp.float32(1.0 / (B * T))
        return loss * scale, jax.jit(
            lambda g: jax.tree.map(lambda a: a * scale, g),
            donate_argnums=0)(grads)


def adamw_step(params, grads, m, v, t: int, *, lr: float, beta1: float,
               beta2: float, eps: float, weight_decay: float,
               grad_clip=None, warmup_steps: int = 0):
    """Step t (from 1) of AdamW with decoupled weight decay (Loshchilov &
    Hutter) on float32 trees, the gradients first scaled so that their
    global L2 norm is at most `grad_clip`, the rate lr * min(1, t /
    warmup_steps) under a linear warm-up. `m` and `v` None: zeros (t = 1).
    Returns (params, m, v). In float32 as the trainer's master weights are,
    so that a move of an lr beside a weight of 1 rounds as theirs does."""
    leaves = jax.tree.leaves(grads)
    if grad_clip is not None:
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
        grads = jax.tree.map(
            lambda g: g * jnp.minimum(1.0, grad_clip / (norm + 1e-6)), grads)
    if warmup_steps:
        lr = lr * min(1.0, t / warmup_steps)
    zeros = jax.tree.map(jnp.zeros_like, grads)
    m = jax.tree.map(lambda m, g: beta1 * m + (1 - beta1) * g,
                     zeros if m is None else m, grads)
    v = jax.tree.map(lambda v, g: beta2 * v + (1 - beta2) * g * g,
                     zeros if v is None else v, grads)

    def move(p, m, v):
        u = (m / (1 - beta1 ** t)) / (jnp.sqrt(v / (1 - beta2 ** t)) + eps)
        return p - jnp.float32(lr) * (u + weight_decay * p)

    return jax.tree.map(move, params, m, v), m, v
