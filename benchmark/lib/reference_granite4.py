"""The plain reference of granite-4.0-h-micro's forward pass (model_type
`granitemoehybrid`: Mamba-2 layers and NoPE grouped-query attention layers,
each followed by a dense SwiGLU, three scalars and a tied head), in
straightforward `jax.numpy` and float32: no cache, no pool, no chunking,
the recurrence TOKEN BY TOKEN. Callers set
`jax.default_matmul_precision("highest")`. It reads the program's
parameter pytree (`models/llama.py` `init_params` of a plan of two kinds:
`params["blocks"]` = (the state-space stack, the attention stack)) and
nothing else of the program.

With x_t the layer's input row, d 2048, H heads of P (d_in = H P), N the
state's width, G groups, K the convolution's width, conv_dim = d_in + 2 G N:

    h_t            = RMSNorm(x_t; attn_norm, eps)
    [z | xBC | dt]_t = h_t W_in                       # d_in | conv_dim | H
                                                      # (the program keeps the
                                                      # dt columns as `w_dt`)
    xBC'_t         = silu(sum_k w_conv[:, k] xBC_{t-(K-1)+k} + b_conv)
                                                      # zeros before the sequence
    [u | B | C]_t  = xBC'_t                           # d_in (H x P) | G N | G N
    delta_t        = softplus(dt_t + dt_bias)         # [H]
    a_t            = exp(-exp(A_log) delta_t)
    S_t[h]         = a_t[h] S_{t-1}[h] + delta_t[h] u_t[h] (outer) B_t   # [P, N]
    y_t[h]         = S_t[h] C_t + D[h] u_t[h]
    g_t            = y_t silu(z_t); o_t = g_t / sqrt(mean(g_t^2) + eps) w_norm
                                                      # the mean over a group's d_in / G
    x_t           <- x_t + residual_multiplier (o_t W_out)

    attention:  q, k, v = h_t Wq, h_t Wk, h_t Wv (no rope, no bias)
                p = softmax(attention_multiplier q k^T, causal)
                x_t <- x_t + residual_multiplier ((p v) Wo)
    every layer then:  m_t = RMSNorm(x_t; mlp_norm)
                x_t <- x_t + residual_multiplier ((silu(m_t W1) * (m_t W3)) W2)
    model:      x_t = embedding_multiplier embed[token]
                logits = RMSNorm(x_T; final_norm) embed^T / logits_scaling

Every departure from the published config is in the configuration file's
`assumed` (benchmark/configs/granite-4.0-h-micro-serve.json). `fault` names
a mistake the correctness checks must catch: "bf16_state" rounds the carried
state to bfloat16 after every token (the nearest precision below the
configuration's float32 state).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_F32 = jnp.float32


def _f32(a):
    return jnp.asarray(a).astype(_F32)


def model_kw(cfg: dict) -> dict:
    """The constants the functions below take, from a configuration file's
    published keys."""
    if (cfg["model_type"] != "granitemoehybrid" or cfg["num_local_experts"]
            or cfg["position_embedding_type"] != "nope"
            or cfg["attention_bias"] or cfg["mamba_proj_bias"]
            or not cfg["mamba_conv_bias"] or not cfg["tie_word_embeddings"]
            or cfg["normalization_function"] != "rmsnorm"
            or cfg["hidden_act"] != "silu"):
        raise NotImplementedError(
            "routed experts, a rope, projection biases, a convolution "
            "without a bias, an untied head, another norm or activation: "
            "this reference computes none of them")
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    if H * P != cfg["mamba_expand"] * cfg["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand "
                         "x hidden_size")
    return dict(
        plan=tuple(t == "mamba" for t in cfg["layer_types"]),
        heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        scale=float(cfg["attention_multiplier"]),
        eps=float(cfg["rms_norm_eps"]),
        ssm=(H, P, cfg["mamba_d_state"], cfg["mamba_n_groups"],
             cfg["mamba_d_conv"]),
        embed_scale=float(cfg["embedding_multiplier"]),
        residual_scale=float(cfg["residual_multiplier"]),
        logit_divisor=float(cfg["logits_scaling"]))


def rms_norm(x, w, eps):
    x = _f32(x)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps
                         ) * _f32(w)


def ssm_inputs(h, lp, ssm):
    """The normed rows h [T, d] -> (z [T, d_in], xBC [T, conv_dim] before
    the convolution, delta [T, H])."""
    H, P = ssm[:2]
    zx = h @ _f32(lp["w_in"])
    dt = h @ _f32(lp["w_dt"])
    return (zx[:, :H * P], zx[:, H * P:],
            jax.nn.softplus(dt + _f32(lp["dt_bias"])))


def conv(xbc, lp, ssm, before=None):
    """xBC [T, conv_dim] -> xBC' [T, conv_dim], row by row: K taps back,
    `before` [K - 1, conv_dim] the rows in front of the first (None:
    zeros)."""
    K = ssm[4]
    w, b = _f32(lp["conv_w"]), _f32(lp["conv_b"])              # [C, K], [C]
    if before is None:
        before = jnp.zeros((K - 1, xbc.shape[1]), _F32)
    rows = jnp.concatenate([_f32(before), xbc])
    T = xbc.shape[0]
    acc = sum(rows[k:k + T] * w[:, k] for k in range(K))
    return jax.nn.silu(acc + b)


def recurrence(xbc, delta, lp, ssm, state=None, fault=None):
    """The recurrence, one token at a time. xBC' [T, conv_dim], delta [T,
    H], `state` [H, P, N] (None: zeros) -> (y [T, H, P], the state behind
    the last row)."""
    H, P, N, G, _ = ssm
    T = xbc.shape[0]
    u = xbc[:, :H * P].reshape(T, H, P)
    Bm = jnp.repeat(xbc[:, H * P:H * P + G * N].reshape(T, G, N), H // G,
                    axis=1)
    Cm = jnp.repeat(xbc[:, H * P + G * N:].reshape(T, G, N), H // G, axis=1)
    A, D = jnp.exp(_f32(lp["A_log"])), _f32(lp["D"])
    if state is None:
        state = jnp.zeros((H, P, N), _F32)

    def token(S, row):
        u_t, B_t, C_t, d_t = row
        a = jnp.exp(-A * d_t)
        S = (a[:, None, None] * S
             + (d_t[:, None] * u_t)[:, :, None] * B_t[:, None, :])
        if fault == "bf16_state":
            S = S.astype(jnp.bfloat16).astype(_F32)
        return S, jnp.sum(S * C_t[:, None, :], axis=-1) + D[:, None] * u_t

    state, y = lax.scan(token, _f32(state), (u, Bm, Cm, delta))
    return y, state


def gate(y, z, lp, ssm, eps):
    """y [T, H, P], z [T, d_in] -> o [T, d_in]: times silu(z) BEFORE the
    norm, which is over each group's d_in / G values."""
    H, P, _, G, _ = ssm
    T = y.shape[0]
    g = (y.reshape(T, H * P) * jax.nn.silu(z)).reshape(T, G, H * P // G)
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(T, H * P) * _f32(lp["ssm_norm"])


def ssm_mixer(h, lp, ssm, eps, fault=None):
    """The mixer of one state-space layer on normed rows h [T, d] of ONE
    sequence from its first token -> ([T, d] before the residual, (the
    last K - 1 rows of xBC, the state behind the last row))."""
    K = ssm[4]
    z, xbc, delta = ssm_inputs(h, lp, ssm)
    y, state = recurrence(conv(xbc, lp, ssm), delta, lp, ssm, fault=fault)
    o = gate(y, z, lp, ssm, eps) @ _f32(lp["w_out"])
    tail = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), _F32),
                            xbc])[-(K - 1):]
    return o, (tail, state)


def attention(q, k, v, scale: float):
    """q [T, H, hd], k and v [T, KV, hd] of one sequence -> [T, H, hd]:
    causal softmax of scale q k^T, float32."""
    T, H, hd = q.shape
    KV = k.shape[1]
    qg = q.reshape(T, KV, H // KV, hd)
    s = jnp.einsum("tkgd,skd->kgts", qg, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("kgts,skd->tkgd", p, v).reshape(T, H, hd)


def attention_mixer(h, lp, heads, kv_heads, scale):
    T = h.shape[0]
    q, k, v = (h @ _f32(lp[n]) for n in ("wq", "wk", "wv"))
    hd = q.shape[1] // heads
    o = attention(q.reshape(T, heads, hd), k.reshape(T, kv_heads, hd),
                  v.reshape(T, kv_heads, hd), scale)
    return o.reshape(T, heads * hd) @ _f32(lp["wo"])


@functools.partial(jax.jit, static_argnames=(
    "is_ssm", "heads", "kv_heads", "scale", "eps", "ssm", "residual_scale",
    "fault"))
def layer(x, lp, *, is_ssm, heads, kv_heads, scale, eps, ssm,
          residual_scale, fault=None):
    """One layer on one sequence's stream x [T, d] float32; lp the layer's
    own leaves -> (x, what the mixer carries behind the last row; None for
    an attention layer)."""
    h = rms_norm(x, lp["attn_norm"], eps)
    carried = None
    if is_ssm:
        o, carried = ssm_mixer(h, lp, ssm, eps, fault)
    else:
        o = attention_mixer(h, lp, heads, kv_heads, scale)
    x = x + residual_scale * o
    m = rms_norm(x, lp["mlp_norm"], eps)
    y = (jax.nn.silu(m @ _f32(lp["w1"])) * (m @ _f32(lp["w3"]))
         ) @ _f32(lp["w2"])
    return x + residual_scale * y, carried


def stream(params, tokens, *, plan, heads, kv_heads, scale, eps, ssm,
           embed_scale, residual_scale, logit_divisor, fault=None,
           carried=None):
    """tokens [T] of one sequence, or [B, T] of B sequences side by side
    (`layer` under `vmap`: no row of one reads another) -> the stream
    behind the last layer [T, d] or [B, T, d], a layer at a time (a
    layer's float32 weights are made as it runs). `carried`, a list, takes
    each state-space layer's (conv rows, state)."""
    del logit_divisor
    stacks = params["blocks"]
    x = embed_scale * _f32(jnp.take(params["embed"], tokens, axis=0))
    at = [0, 0]
    for is_ssm in plan:
        kind = 0 if is_ssm else 1
        lp = jax.tree.map(lambda w: w[at[kind]], stacks[kind])
        at[kind] += 1
        one = functools.partial(
            layer, is_ssm=is_ssm, heads=heads, kv_heads=kv_heads,
            scale=scale, eps=eps, ssm=ssm, residual_scale=residual_scale,
            fault=fault)
        x, c = (one if tokens.ndim == 1
                else jax.vmap(one, in_axes=(0, None)))(x, lp)
        if carried is not None and is_ssm:
            carried.append(c)
    return x


@functools.partial(jax.jit, static_argnames=("eps", "logit_divisor",
                                             "blocks"))
def head(x, final_norm, embed, *, eps, logit_divisor, blocks=8):
    """Rows x [..., d] -> logits [..., V], the tied head in `blocks` blocks
    of the vocabulary."""
    h = rms_norm(x, final_norm, eps)
    V = embed.shape[0]
    step = -(-V // blocks)
    return jnp.concatenate(
        [h @ _f32(embed[i:i + step]).T for i in range(0, V, step)],
        axis=-1) / logit_divisor


def logits_at(params, tokens, at, **kw):
    """Float32 logits [len(at), V] of the full forward of `tokens` [T] at
    positions `at`; [B, n, V] of tokens [B, T] at `at` [B, n]."""
    x = stream(params, tokens, **kw)
    rows = x[at] if tokens.ndim == 1 else jnp.take_along_axis(
        x, at[..., None], axis=1)
    return head(rows, params["final_norm"], params["embed"], eps=kw["eps"],
                logit_divisor=kw["logit_divisor"])


def forward(params, tokens, **kw):
    """tokens [T] -> logits [T, V]."""
    return logits_at(params, tokens, jnp.arange(tokens.shape[0]), **kw)


def generate(params, prompt, new_tokens: int, **kw):
    """Greedy decoding by full forwards (tests at tiny sizes): the tokens
    and the logits each was chosen from."""
    tokens, rows = list(map(int, prompt)), []
    for _ in range(new_tokens):
        row = logits_at(params, jnp.asarray(tokens, jnp.int32),
                        jnp.asarray([len(tokens) - 1]), **kw)[0]
        rows.append(row)
        tokens.append(int(jnp.argmax(row)))
    return tokens[len(prompt):], jnp.stack(rows)
