"""Plain float32 reference of NVIDIA-Nemotron-3-Super-120B-A12B's language
model (``model_type`` ``nemotron_h``: Mamba-2 layers, latent expert layers and
attention layers without rotary, one part a layer), one chip's share of a
4-way expert-parallel stage.

Straightforward ``jax.numpy``: no kernels, no cache, no chunks, no batching, no
code of the system under test but its random initialiser (``init_params``,
imported there and nowhere else: the parameter tree's layout is all this file
shares with ``models/nemotron_h.py``; ``ops/ssd.py`` is not imported).  The
equations are those the keys of the model's ``config.json`` select, as the
configuration file lists them under ``assumed``; ``d`` ``hidden_size``:

- ``x = E[ids]``; untied head, ``logits = RMSNorm(x) W_head``; RMSNorm with
  ``layer_norm_epsilon`` and a learned scale; layer ``l`` is one pre-norm part
  of the kind ``hybrid_override_pattern[l]``: ``x = x + Part_l(RMSNorm(x))``;
- ``M``, **token by token**: ``[z | xBC | dt] = h W_in`` (``mamba_num_heads x
  mamba_head_dim | that + 2 n_groups ssm_state_size | mamba_num_heads``);
  ``xBC = silu(conv(xBC) + b)``, the causal depthwise convolution over
  ``conv_kernel`` tokens from zeros; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)`` a head; from ``S = 0`` a ``lax.scan`` over tokens of ``S =
  exp(dt A) S + dt x (outer) B``, ``y = S C + D x``, head ``h`` reading the
  ``B`` and ``C`` of group ``h // (heads / n_groups)``; ``y = RMSNorm(y *
  silu(z))`` over each of the ``n_groups`` groups of channels; output ``y
  W_out``;
- ``E``: ``s = sigmoid(h W_r)`` over all ``n_routed_experts_published``
  experts; the top ``num_experts_per_tok`` of ``s + b``; ``w = s[top] / (sum +
  1e-20) * routed_scaling_factor``; ``u = h W_down``; ``y = (sum_j w_j W2_j
  relu(W1_j u)^2) W_up + W2_s relu(W1_s h)^2`` over the choices among the
  ``n_routed_experts`` experts held here, from ``expert_first``: the other
  chips' terms are theirs;
- ``*``: ``num_attention_heads`` query heads on ``num_key_value_heads`` K/V
  heads of ``head_dim``, **no rotary**, scores at ``head_dim ** -0.5``, causal
  softmax, every key and value of the sequence held.

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


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * _f32(scale)


def _relu2(p, h):
    return jnp.square(jax.nn.relu(h @ _f32(p["w_up"]))) @ _f32(p["w_down"])


def ssd_recurrence(x, dt, a, b, c, d, state):
    """Mamba-2's recurrence a token at a time: ``x`` (S, heads, P), ``dt`` (S,
    heads), ``a``, ``d`` (heads,), ``b``, ``c`` (S, groups, N), ``state``
    (heads, P, N) float32 -> ``(y (S, heads, P), state after the last
    token)``.  ``_mamba2`` runs it from zeros; ``checks/ssd_state.py`` runs it
    over the inputs the served programs hand their own scan and step, from
    where it last stopped."""
    per = x.shape[1] // b.shape[1]

    def token(state, xs):
        x_t, dt_t, b_t, c_t = xs
        b_h, c_h = jnp.repeat(b_t, per, axis=0), jnp.repeat(c_t, per, axis=0)
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_h) + d[:, None] * x_t

    state, y = jax.lax.scan(token, state, (x, dt, b, c))
    return y, state


def _mamba2(p, h, config: dict):
    """h: (S, d) -> (S, d): the whole sequence from a zero state, a token at
    a time."""
    s = h.shape[0]
    heads, dim = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, n = config["n_groups"], config["ssm_state_size"]
    taps, inner = config["conv_kernel"], heads * dim
    # in_proj is stored as its columns [z] and [x | B | C | dt]
    z, xbcdt = h @ _f32(p["w_z"]), h @ _f32(p["w_xbcdt"])
    xbc = xbcdt[:, :inner + 2 * groups * n]
    dt = jax.nn.softplus(xbcdt[:, inner + 2 * groups * n:]
                         + _f32(p["dt_bias"]))
    # tap j weighs the input taps - 1 - j tokens back
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(_f32(p["conv_b"]) + sum(
        _f32(p["conv_w"])[j] * padded[j:j + s] for j in range(taps)))
    y, _ = ssd_recurrence(
        xbc[:, :inner].reshape(s, heads, dim), dt, -jnp.exp(_f32(p["a_log"])),
        xbc[:, inner:inner + groups * n].reshape(s, groups, n),
        xbc[:, inner + groups * n:].reshape(s, groups, n), _f32(p["d"]),
        jnp.zeros((heads, dim, n), jnp.float32))
    y = (y.reshape(s, inner) * jax.nn.silu(z)).reshape(s, groups, -1)
    y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True)
                          + config["layer_norm_epsilon"])
    return (y.reshape(s, inner) * _f32(p["norm"])) @ _f32(p["w_out"])


def _attention(p, h, config: dict):
    """h: (S, d) -> (S, d), no position signal."""
    s = h.shape[0]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    dim = config["head_dim"]
    qkv = h @ _f32(p["wqkv"])
    q = qkv[:, :heads * dim].reshape(s, heads, dim)
    k = qkv[:, heads * dim:(heads + kv_heads) * dim].reshape(s, kv_heads, dim)
    v = qkv[:, (heads + kv_heads) * dim:].reshape(s, kv_heads, dim)
    j = jnp.arange(s)[None, :]

    def block(args):
        qb, i = args                    # (QUERY_BLOCK, heads, dim), positions
        ok = j <= i[:, None]
        out = []
        for hd in range(heads):
            kv = hd // (heads // kv_heads)
            scores = jnp.where(ok, qb[:, hd] @ k[:, kv].T * dim ** -0.5,
                               -jnp.inf)
            out.append(jax.nn.softmax(scores, -1) @ v[:, kv])
        return jnp.stack(out, axis=1)

    n_blocks = -(-s // QUERY_BLOCK)
    pad = n_blocks * QUERY_BLOCK - s    # padded queries attend as the last
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        n_blocks, QUERY_BLOCK, heads, dim)
    pos = jnp.minimum(jnp.arange(n_blocks * QUERY_BLOCK), s - 1).reshape(
        n_blocks, QUERY_BLOCK)
    att = jax.lax.map(block, (qb, pos)).reshape(-1, heads * dim)[:s]
    return att @ _f32(p["wo"])


def route(p, h, config: dict):
    """``(S, n_routed_experts_published)`` weights of the routed sum, 0 where
    an expert was not chosen."""
    n, k = config["n_routed_experts_published"], config["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ _f32(p["router"]))
    _, top = jax.lax.top_k(s + _f32(p["bias"]), k)
    w = jnp.take_along_axis(s, top, -1)
    if config["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * config["routed_scaling_factor"]
    return (w[..., None] * (top[..., None] == jnp.arange(n))).sum(-2)


def routed(p, h, config: dict):
    """The held experts' terms of the routed sum for ``h`` (S, d), through
    ``W_up``: one expert after the other on the latent ``u = h W_down``, each
    under its own column of the (token, expert) weight matrix."""
    first, held = config.get("expert_first", 0), config["n_routed_experts"]
    weights = route(p, h, config)[:, first:first + held]
    u = h @ _f32(p["w_latent_down"])
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
            return total.at[rows].add(top_w[:, None] * _relu2(expert, u[rows]))

        def every_token(total):
            return total + weight[:, None] * _relu2(expert, u)

        return jax.lax.cond((weight != 0).sum() <= few, routed_only,
                            every_token, total), None

    bits = jax.tree.map(
        lambda a: jax.lax.bitcast_convert_type(a, as_bits), p["experts"])
    total, _ = jax.lax.scan(one, jnp.zeros_like(u), (bits, weights.T))
    return total @ _f32(p["w_latent_up"])


def forward(params, input_ids, config: dict):
    """Logits (B, S, V) in float32 for token ids (B, S), one sequence
    after the other."""
    return jax.lax.map(lambda ids: _forward_one(params, ids, config),
                       input_ids)


def _forward_one(params, input_ids, config: dict):
    with jax.default_matmul_precision("highest"):
        eps = config["layer_norm_epsilon"]
        x = _f32(params["wte"])[input_ids]
        for i, kind in enumerate(config["hybrid_override_pattern"]):
            p = params[f"h{i}"]
            h = _rms_norm(x, p["ln"], eps)
            if kind == "M":
                x = x + _mamba2(p["mamba"], h, config)
            elif kind == "*":
                x = x + _attention(p["attn"], h, config)
            else:
                x = x + routed(p["moe"], h, config) \
                    + _relu2(p["moe"]["shared"], h)
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
