"""Plain float32 reference of Ling-3.0-flash-VL's language model (Kimi Delta
Attention layers, a latent-attention layer every sixth, group-limited sigmoid
experts), one chip's share of an 8-way expert-parallel stage.

Straightforward ``jax.numpy``: no kernels, no cache, no chunks, no batching,
no absorbed attention, no code of the system under test but its random
initialiser (``init_params``, imported there and nowhere else: the parameter
tree's layout is all this file shares with ``models/ling.py``; ``ops/kda.py``
is not imported).  The equations are those the keys of the model's
``config.json`` select, as the configuration file lists them under
``assumed``; ``d`` ``hidden_size``, ``H`` ``num_attention_heads``, ``D``
``head_dim``:

- ``x = E[ids]``; untied head, ``logits = RMSNorm(x) W_head``; RMSNorm with
  ``rms_norm_eps`` and a learned scale everywhere; pre-norm blocks ``x = x +
  Mix_l(N1(x))``, ``x = x + FFN_l(N2(x))``;
- a ``"kda"`` layer (``layer_types``), **token by token**: ``q, k, v =
  silu(conv(h Wq)), silu(conv(h Wk)), silu(conv(h Wv))`` (``[Wq | Wk | Wv]``
  is the stored ``w_qkv``), the causal depthwise convolution over
  ``short_conv_kernel_size`` tokens from zeros, no bias; ``q`` and ``k``
  L2-normalised a head (``x / sqrt(sum x^2 + 1e-6)``), ``q`` scaled by ``D **
  -0.5``; ``g_t = kda_lower_bound * sigmoid(exp(A_h) * (h W_f + b_f))`` a
  channel, ``beta_t = sigmoid(h W_beta)`` a head (``[W_f | W_g | W_beta]`` is
  the stored ``w_gates``); from ``S = 0`` a ``lax.scan`` over tokens of ``S' =
  Diag(exp(g_t)) S``, ``S = S' + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S^T
  q_t``; output ``(RMSNorm_D(o) * sigmoid(h W_g + b_g)) Wo``;
- an ``"mla"`` layer, **non-absorbed**: ``q = h Wq`` -> ``H x [nope | rope]``,
  each head's values RMS-normed (``q_head_norm``); ``[c | k_r] = h W_kva``,
  ``c_kv = RMSNorm(c)``; every head's ``k_nope_i = c_kv W_UK_i`` and ``v_i =
  c_kv W_UV_i`` computed for every position; rotary with interleaved pairs and
  ``rope_theta`` on ``q_rope`` and the one shared ``k_r``; scores at ``(nope +
  rope) ** -0.5``, causal softmax; output ``(o_i * sigmoid(h W_gate)_i) Wo``;
- FFN of the first ``first_k_dense_replace`` layers: SwiGLU of
  ``intermediate_size``; of the others ``s = sigmoid(h W_r)`` over all
  ``num_experts_published`` experts, ``s' = s + b``; ``n_group`` groups of consecutive
  experts, a group's score the sum of its two largest ``s'``; the
  ``topk_group`` best groups kept; the top ``num_experts_per_tok`` of ``s'``
  inside them; ``w = s[top] / (sum s[top] + 1e-20) * routed_scaling_factor``;
  ``y = Shared(h) + sum_j w_j Expert_top_j(h)`` over the choices among the
  ``num_experts`` experts held here, from ``expert_first``: the other chips'
  terms are theirs.

Weights are the server's own (bfloat16 values), the arithmetic float32 under
``jax.default_matmul_precision("highest")``.  Queries of an MLA layer are
processed ``QUERY_BLOCK`` positions at a time (the same sums).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: queries whose scores are held at one time (a block of the same sum)
QUERY_BLOCK = 512


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * _f32(scale)


def _swiglu(p, h):
    return (jax.nn.silu(h @ _f32(p["w_gate"])) * (h @ _f32(p["w_up"]))) \
        @ _f32(p["w_down"])


def _rope_interleaved(x, theta):
    """x: (S, ..., D) at positions 0..S-1, D stored as interleaved pairs."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    pos = jnp.arange(x.shape[0], dtype=jnp.float32).reshape(
        (-1,) + (1,) * (x.ndim - 1))
    ang = pos * freqs
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _l2_norm(x):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def kda_recurrence(q, k, v, g, beta, state):
    """The gated delta rule a token at a time: ``q, k, v, g`` (S, heads, D),
    ``beta`` (S, heads), ``state`` (heads, key, value) float32 -> ``(o (S,
    heads, D), state after the last token)``.  ``_kda`` runs it from zeros;
    ``checks/kda_state.py`` runs it over the q, k, v, g, beta the served
    programs hand their own scan and step, from where it last stopped."""
    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[:, :, None] * state
        delta = v_t - jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + b_t[:, None, None] * k_t[:, :, None] \
            * delta[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    state, o = jax.lax.scan(token, state, (q, k, v, g, beta))
    return o, state


def _kda(p, h, config: dict):
    """h: (S, d) -> (S, d): the whole sequence from a zero state, a token at
    a time."""
    s = h.shape[0]
    heads, dim = config["num_attention_heads"], config["head_dim"]
    taps, c = config["short_conv_kernel_size"], heads * dim
    qkv = h @ _f32(p["w_qkv"])
    gates = h @ _f32(p["w_gates"])

    def conv(u, w):         # tap j weighs the input taps - 1 - j tokens back
        padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
        return jax.nn.silu(sum(_f32(w)[j] * padded[j:j + s]
                               for j in range(taps)))

    q, k, v = (conv(qkv[:, i * c:(i + 1) * c], p["conv_w"][i]).reshape(
        s, heads, dim) for i in range(3))
    q, k = _l2_norm(q) * dim ** -0.5, _l2_norm(k)
    decay = (gates[:, :c] + _f32(p["b_decay"])).reshape(s, heads, dim)
    g = config["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(_f32(p["a_log"]))[:, None] * decay)
    beta = jax.nn.sigmoid(gates[:, 2 * c:])

    o, _ = kda_recurrence(q, k, v, g, beta,
                          jnp.zeros((heads, dim, dim), jnp.float32))
    o = _rms_norm(o, p["o_norm"], config["rms_norm_eps"]).reshape(s, c)
    return (o * jax.nn.sigmoid(gates[:, c:2 * c] + _f32(p["b_gate"]))) \
        @ _f32(p["w_o"])


def _attention(p, h, config: dict):
    """h: (S, d) -> (S, d), every key and value decompressed."""
    s = h.shape[0]
    heads, eps = config["num_attention_heads"], config["rms_norm_eps"]
    rank, nope = config["kv_lora_rank"], config["qk_nope_head_dim"]
    rope_dim, vd = config["qk_rope_head_dim"], config["v_head_dim"]
    theta = config["rope_theta"]
    q = _rms_norm((h @ _f32(p["w_q"])).reshape(s, heads, nope + rope_dim),
                  p["q_head_norm"], eps)
    q_nope, q_rope = q[..., :nope], _rope_interleaved(q[..., nope:], theta)
    ckr = h @ _f32(p["w_kva"])
    c_kv = _rms_norm(ckr[:, :rank], p["kv_norm"], eps)
    k_rope = _rope_interleaved(ckr[:, rank:], theta)            # (S, rope)
    k_nope = (c_kv @ _f32(p["w_uk"]).reshape(rank, -1)).reshape(
        s, heads, nope)
    v = (c_kv @ _f32(p["w_uv"]).reshape(rank, -1)).reshape(s, heads, vd)
    scale = (nope + rope_dim) ** -0.5
    j = jnp.arange(s)[None, :]

    def block(args):
        qn, qr, i = args                # (QUERY_BLOCK, heads, .), positions
        ok = j <= i[:, None]
        out = []
        for hd in range(heads):
            scores = (qn[:, hd] @ k_nope[:, hd].T + qr[:, hd] @ k_rope.T) \
                * scale
            scores = jnp.where(ok, scores, -jnp.inf)
            out.append(jax.nn.softmax(scores, -1) @ v[:, hd])
        return jnp.stack(out, axis=1)   # (QUERY_BLOCK, heads, v)

    n_blocks = -(-s // QUERY_BLOCK)
    pad = n_blocks * QUERY_BLOCK - s    # padded queries attend as the last

    def blocks(a):
        return jnp.pad(a, ((0, pad), (0, 0), (0, 0))).reshape(
            n_blocks, QUERY_BLOCK, *a.shape[1:])

    pos = jnp.minimum(jnp.arange(n_blocks * QUERY_BLOCK), s - 1).reshape(
        n_blocks, QUERY_BLOCK)
    att = jax.lax.map(block, (blocks(q_nope), blocks(q_rope), pos)).reshape(
        n_blocks * QUERY_BLOCK, heads, vd)[:s]
    att = att * jax.nn.sigmoid(h @ _f32(p["w_gate"]))[:, :, None]
    return att.reshape(s, heads * vd) @ _f32(p["w_o"])


def route(p, h, config: dict):
    """``(S, num_experts_published)`` weights of the routed sum, 0 where an expert
    was not chosen: the group-limited selection by brute force."""
    n, k = config["num_experts_published"], config["num_experts_per_tok"]
    groups, keep = config["n_group"], config["topk_group"]
    s = jax.nn.sigmoid(h @ _f32(p["router"]))
    choice = s + _f32(p["bias"])
    per = n // groups
    group_score = jnp.sort(choice.reshape(-1, groups, per), -1)[..., -2:] \
        .sum(-1)                                            # (S, groups)
    # a group's rank: the groups that score more and, of equal scores, the
    # earlier ones (as top_k takes them)
    mine, other = group_score[:, :, None], group_score[:, None, :]
    earlier = jnp.arange(groups)[None, :] < jnp.arange(groups)[:, None]
    rank = ((other > mine) | ((other == mine) & earlier)).sum(-1)
    kept = jnp.repeat(rank < keep, per, axis=1)
    _, top = jax.lax.top_k(jnp.where(kept, choice, -jnp.inf), k)
    w = jnp.take_along_axis(s, top, -1)
    if config["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * config["routed_scaling_factor"]
    return (w[..., None] * (top[..., None] == jnp.arange(n))).sum(-2)


def _experts(p, h, config: dict):
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


def forward(params, input_ids, config: dict):
    """Logits (B, S, V) in float32 for token ids (B, S), one sequence
    after the other."""
    return jax.lax.map(lambda ids: _forward_one(params, ids, config),
                       input_ids)


def _forward_one(params, input_ids, config: dict):
    if any(config.get("expert_swiglu_limit_list", ())) \
            or any(config.get("share_expert_swiglu_limit_list", ())):
        raise NotImplementedError("a non-zero SwiGLU limit")
    with jax.default_matmul_precision("highest"):
        eps = config["rms_norm_eps"]
        x = _f32(params["wte"])[input_ids]
        for i, kind in enumerate(config["layer_types"]):
            p = params[f"h{i}"]
            h = _rms_norm(x, p["ln_mix"], eps)
            if kind == "kda":
                x = x + _kda(p["kda"], h, config)
            else:
                x = x + _attention(p["attn"], h, config)
            h = _rms_norm(x, p["ln_mlp"], eps)
            if i < config["first_k_dense_replace"]:
                x = x + _swiglu(p["mlp"], h)
            else:
                x = x + _swiglu(p["moe"]["shared"], h) \
                    + _experts(p["moe"], h, config)
        return _rms_norm(x, params["ln_f"], eps) @ _f32(params["head"])


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
