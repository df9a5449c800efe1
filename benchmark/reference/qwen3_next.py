"""Plain float32 reference of Qwen3-Next-80B-A3B-Instruct's language model
(three Gated DeltaNet layers to one gated-attention layer, every layer 512
softmax-routed experts and a sigmoid-gated shared expert), one chip's share of
a 4-way expert-parallel stage.

Straightforward ``jax.numpy``: no kernels, no cache, no chunks, no batching,
no code of the system under test but its random initialiser (``init_params``,
imported there and nowhere else: the parameter tree's layout is all this file
shares with ``models/qwen3_next.py``; ``ops/kda.py`` is not imported).  The
equations are those the keys of the model's ``config.json`` select, as the
configuration file lists them under ``assumed``; ``d`` ``hidden_size``:

- ``N(x) = x * rsqrt(mean(x^2) + rms_norm_eps) * (1 + w)``, the scale
  zero-centred; ``x = E[ids]``; blocks ``x = x + Mixer_l(N(x))``, ``x = x +
  MoE(N(x))``; layer ``l`` is attention where ``(l + 1) %
  full_attention_interval == 0``; untied head, ``logits = N(x) W_head``; no
  bias anywhere;
- a **Gated DeltaNet** layer, **token by token**: ``[q | k | v] = silu(conv(h
  W_qkv))``, ONE causal depthwise convolution of ``linear_conv_kernel_dim``
  taps over all ``2 Hk dk + Hv dv`` channels from zeros; ``z = h W_z``, ``[b |
  a] = h W_ba``; ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a +
  dt_bias)`` one scalar a value head, no lower bound; ``q`` and ``k``
  L2-normalised a head (``x / sqrt(sum x^2 + 1e-6)``), ``q`` scaled by ``dk **
  -0.5``; value head ``h`` reads q/k head ``h // (Hv / Hk)``; from ``S = 0`` a
  ``lax.scan`` over tokens of ``S' = exp(g_t) S``, ``S = S' + beta_t k_t (v_t -
  S'^T k_t)^T``, ``o_t = S^T q_t``; output ``(RMSNorm_dv(o) * w_o_norm *
  silu(z)) W_out``, that norm a value head's with a plain scale;
- a **gated attention** layer: ``[q_i | gate_i] = (h W_q)_i`` a head of ``2
  head_dim``, ``k, v = h W_kv``; ``q = N(q)``, ``k = N(k)`` a head; rotary
  (rotate-half, ``rope_theta``) on the first ``head_dim *
  partial_rotary_factor`` channels of q and k; causal softmax at ``head_dim **
  -0.5``, ``H / Hkv`` query heads a K/V head; output ``(attn * sigmoid(gate))
  W_o``;
- the **experts** of every layer: ``p = softmax(h W_r)`` over all
  ``num_experts_published`` experts; the top ``num_experts_per_tok`` of ``p``;
  ``w = p[top] / sum p[top]`` (``norm_topk_prob``); ``y = sigmoid(h w_sg) *
  Shared(h) + sum_j w_j Expert_top_j(h)`` over the choices among the
  ``num_experts`` experts held here, from ``expert_first``: the other chips'
  terms are theirs.

Weights are the server's own (bfloat16 values), the arithmetic float32 under
``jax.default_matmul_precision("highest")``.  Queries of an attention layer
are processed ``QUERY_BLOCK`` positions at a time (the same sums).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: queries whose scores are held at one time (a block of the same sum)
QUERY_BLOCK = 512


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)


def _norm(x, w, eps):
    """The family's zero-centred RMSNorm: the scale is ``1 + w``."""
    return _rms(x, eps) * (1.0 + _f32(w))


def _swiglu(p, h):
    return (jax.nn.silu(h @ _f32(p["w_gate"])) * (h @ _f32(p["w_up"]))) \
        @ _f32(p["w_down"])


def _l2_norm(x):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def _rope_half(x, theta):
    """x: (S, heads, R) at positions 0..S-1, rotate-half over all ``R``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def gdn_recurrence(q, k, v, g, beta, state):
    """The scalar-gated delta rule a token at a time: ``q, k`` (S, Hk, dk),
    ``v`` (S, Hv, dv), ``g``, ``beta`` (S, Hv), ``state`` (Hv, dk, dv)
    float32 -> ``(o (S, Hv, dv), state after the last token)``; value head
    ``h`` reads q/k head ``h // (Hv / Hk)``.  ``_gdn`` runs it from zeros;
    ``checks/gdn_state.py`` runs it over the q, k, v, g, beta the served
    programs hand their own scan and step, from where it last stopped."""
    per = v.shape[1] // q.shape[1]

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        q_t, k_t = jnp.repeat(q_t, per, axis=0), jnp.repeat(k_t, per, axis=0)
        state = jnp.exp(g_t)[:, None, None] * state
        delta = v_t - jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + b_t[:, None, None] * k_t[:, :, None] \
            * delta[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    state, o = jax.lax.scan(token, state, (q, k, v, g, beta))
    return o, state


def _gdn(p, h, config: dict):
    """h: (S, d) -> (S, d): the whole sequence from a zero state, a token at
    a time."""
    s = h.shape[0]
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    taps = config["linear_conv_kernel_dim"]
    # tap j weighs the input taps - 1 - j tokens back
    padded = jnp.pad(h @ _f32(p["w_qkv"]), ((taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(_f32(p["conv_w"])[j] * padded[j:j + s]
                          for j in range(taps)))
    q = qkv[:, :hk * dk].reshape(s, hk, dk)
    k = qkv[:, hk * dk:2 * hk * dk].reshape(s, hk, dk)
    v = qkv[:, 2 * hk * dk:].reshape(s, hv, dv)
    q, k = _l2_norm(q) * dk ** -0.5, _l2_norm(k)
    ba = h @ _f32(p["w_ba"])
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(_f32(p["a_log"])) * jax.nn.softplus(
        ba[:, hv:] + _f32(p["dt_bias"]))
    o, _ = gdn_recurrence(q, k, v, g, beta,
                          jnp.zeros((hv, dk, dv), jnp.float32))
    o = _rms(o, config["rms_norm_eps"]) * _f32(p["o_norm"])
    return (o.reshape(s, hv * dv) * jax.nn.silu(h @ _f32(p["w_z"]))) \
        @ _f32(p["w_out"])


def _attention(p, h, config: dict):
    """h: (S, d) -> (S, d): dense causal attention, gated a channel."""
    s = h.shape[0]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    dim, eps = config["head_dim"], config["rms_norm_eps"]
    rot = int(dim * config["partial_rotary_factor"])
    theta = config["rope_theta"]
    qg = (h @ _f32(p["w_q"])).reshape(s, heads, 2 * dim)
    q, gate = qg[..., :dim], qg[..., dim:]
    kv = h @ _f32(p["w_kv"])
    k = kv[:, :kv_heads * dim].reshape(s, kv_heads, dim)
    v = kv[:, kv_heads * dim:].reshape(s, kv_heads, dim)
    q, k = _norm(q, p["q_norm"], eps), _norm(k, p["k_norm"], eps)

    def rotated(x):
        return jnp.concatenate([_rope_half(x[..., :rot], theta),
                                x[..., rot:]], -1)

    q, k = rotated(q), rotated(k)
    j = jnp.arange(s)[None, :]

    def block(args):
        qb, i = args                    # (QUERY_BLOCK, heads, dim), positions
        ok = j <= i[:, None]
        out = []
        for hd in range(heads):
            at = hd // (heads // kv_heads)
            scores = jnp.where(ok, qb[:, hd] @ k[:, at].T * dim ** -0.5,
                               -jnp.inf)
            out.append(jax.nn.softmax(scores, -1) @ v[:, at])
        return jnp.stack(out, axis=1)

    n_blocks = -(-s // QUERY_BLOCK)
    pad = n_blocks * QUERY_BLOCK - s    # padded queries attend as the last
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        n_blocks, QUERY_BLOCK, heads, dim)
    pos = jnp.minimum(jnp.arange(n_blocks * QUERY_BLOCK), s - 1).reshape(
        n_blocks, QUERY_BLOCK)
    att = jax.lax.map(block, (qb, pos)).reshape(-1, heads, dim)[:s]
    return (att * jax.nn.sigmoid(gate)).reshape(s, heads * dim) \
        @ _f32(p["w_o"])


def route(p, h, config: dict):
    """``(S, num_experts_published)`` weights of the routed sum, 0 where an
    expert was not chosen."""
    n, k = config["num_experts_published"], config["num_experts_per_tok"]
    prob = jax.nn.softmax(h @ _f32(p["router"]), -1)
    w, top = jax.lax.top_k(prob, k)
    if config["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return (w[..., None] * (top[..., None] == jnp.arange(n))).sum(-2)


def routed(p, h, config: dict):
    """The held experts' terms of the routed sum for ``h`` (S, d): one
    expert after the other, each under its own column of the (token, expert)
    weight matrix."""
    first, held = config.get("expert_first", 0), config["num_experts"]
    weights = route(p, h, config)[:, first:first + held]
    # the loop slices the stacked experts as integers of their width: the
    # CPU backend has no bfloat16 slice and would convert each stacked
    # tensor to float32 whole, ahead of the loop
    stored = p["experts"]["w_up"].dtype
    as_bits = jnp.dtype(f"uint{8 * stored.itemsize}")
    few = max(1, h.shape[0] // 8)

    def one(total, xs):
        bits, weight = xs               # weight: (S,)
        expert = jax.tree.map(
            lambda a: jax.lax.bitcast_convert_type(a, stored), bits)

        def routed_only(total):
            # a routed weight is positive and an unrouted one 0: where no
            # more than `few` tokens chose the expert the selection holds
            # them all and the rest add exact zeros
            top_w, rows = jax.lax.top_k(weight, few)
            return total.at[rows].add(
                top_w[:, None] * _swiglu(expert, h[rows]))

        def every_token(total):
            return total + weight[:, None] * _swiglu(expert, h)

        return jax.lax.cond((weight != 0).sum() <= few, routed_only,
                            every_token, total), None

    bits = jax.tree.map(
        lambda a: jax.lax.bitcast_convert_type(a, as_bits), p["experts"])
    total, _ = jax.lax.scan(one, jnp.zeros_like(h), (bits, weights.T))
    return total


def shared(p, h):
    """The shared expert under its own gate: every chip computes it alike."""
    return jax.nn.sigmoid(h @ _f32(p["w_shared_gate"])) \
        * _swiglu(p["shared"], h)


def forward(params, input_ids, config: dict):
    """Logits (B, S, V) in float32 for token ids (B, S), one sequence
    after the other."""
    return jax.lax.map(lambda ids: _forward_one(params, ids, config),
                       input_ids)


def _forward_one(params, input_ids, config: dict):
    with jax.default_matmul_precision("highest"):
        eps, every = config["rms_norm_eps"], config["full_attention_interval"]
        x = _f32(params["wte"])[input_ids]
        for i in range(config["num_hidden_layers"]):
            p = params[f"h{i}"]
            h = _norm(x, p["ln_mix"], eps)
            if (i + 1) % every:
                x = x + _gdn(p["gdn"], h, config)
            else:
                x = x + _attention(p["attn"], h, config)
            h = _norm(x, p["ln_mlp"], eps)
            x = x + shared(p["moe"], h) + routed(p["moe"], h, config)
        return _norm(x, params["ln_f"], eps) @ _f32(params["head"])


def init_params(config: dict, seed: int):
    """The weights the server makes from ``seed``: the system's own random
    init of its ``system_config`` preset (bfloat16 values).  The only place
    this file touches the system under test."""
    from distributedtensorflow_tpu import models
    from distributedtensorflow_tpu.serve.model import family_of

    cfg = getattr(models, config["system_config"])()
    return family_of(cfg).init_params(cfg, jax.random.PRNGKey(seed))


def logits(params, input_ids, config: dict):
    """Next-token logits (B, S, V) in float32 for token ids (B, S)."""
    return forward(params, input_ids, config)


def token_nll(params, batch: dict, config: dict):
    """Next-token negative log-likelihood (B, S-1) of
    ``batch["input_ids"]`` at positions 0..S-2."""
    input_ids = batch["input_ids"]
    logp = jax.nn.log_softmax(logits(params, input_ids, config)[:, :-1], -1)
    return -jnp.take_along_axis(logp, input_ids[:, 1:, None], -1)[..., 0]


def loss(params, batch: dict, config: dict):
    """Mean next-token cross-entropy over positions 0..S-2."""
    return token_nll(params, batch, config).mean()
