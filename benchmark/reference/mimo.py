"""Plain float32 reference of the MiMo-V2 decoder (Xiaomi MiMo-V2.5's language
model), cut to a chip's share of its experts.

Straightforward ``jax.numpy``: no kernels, no cache, no grouping of tokens,
no code of the system under test but its random initialiser.  The equations
are the configuration file's (``assumed``), read off the keys of the
published ``config.json``:

- ``x = E[ids]``; untied head, ``logits = RMSNorm(x) Wu``; RMSNorm with
  ``layernorm_epsilon`` everywhere, no biases;
- block, pre-norm: ``x = x + Attn(N1(x))``, then ``x = x + FFN(N2(x))``;
- attention of layer ``l``, of kind ``hybrid_layer_pattern[l]`` (0 full, 1
  window): q (``num_attention_heads`` x ``head_dim``), k (Hkv x
  ``head_dim``), v (Hkv x ``v_head_dim``), Hkv ``num_key_value_heads`` on a
  full layer and ``swa_num_key_value_heads`` on a window one; rotary
  (rotate-half) on the first ``int(head_dim * partial_rotary_factor)`` values
  of every head of q and k, base ``rope_theta`` on a full layer and
  ``swa_rope_theta`` on a window one; ``v`` times ``attention_value_scale``;
  scores at ``head_dim ** -0.5`` under a dense (T, T) mask of the kind,
  causal, on a window layer over keys ``j`` with ``i - sliding_window < j <=
  i``; where the kind has a sink (``add_swa_attention_sink_bias`` /
  ``add_full_attention_sink_bias``) the head's learned scalar is one more
  column of the scores, dropped after the softmax; output ``concat(o) Wo``;
- FFN of a layer with ``moe_layer_freq[l] == 0``: ``(silu(h Wgate) * h Wup)
  Wdown``;
- FFN of the others: ``s = sigmoid(h Wr)`` over ``n_routed_experts_published``
  experts, the top ``num_experts_per_tok`` of ``s + b``, weights ``s[top] /
  (sum s[top] + 1e-20)`` (``norm_topk_prob``) times ``routed_scaling_factor``
  (null: 1), ``y = sum_j w_j Expert_top_j(h)``, no shared expert — of which
  this share holds experts ``expert_first .. expert_first +
  n_routed_experts``: the others' terms are left out, here as in the program.

Weights are the server's own (bfloat16 values), the arithmetic float32 under
``jax.default_matmul_precision("highest")``.  The system stores q, k and v as
one matrix ``wqkv`` (the published ``fused_qkv`` layout); it is split here.
Queries are taken ``QUERY_BLOCK`` at a time and the experts one after the
other, so that the check's sequences fit the host at the published widths.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: queries whose scores are held at one time (a block of the same sum)
QUERY_BLOCK = 256
#: an expert is applied to 1 / EXPERT_SHARE of the tokens where no more
#: chose it (uniform routing sends it 8 / 256), to all of them otherwise
EXPERT_SHARE = 8


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * _f32(scale)


def _partial_rope(x, n_rot, theta):
    """x: (S, H, D); rotate-half rotary embedding at positions 0..S-1 on the
    first ``n_rot`` values of every head, the others as they are."""
    half = n_rot // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    x1, x2, rest = x[..., :half], x[..., half:n_rot], x[..., n_rot:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang), rest], -1)


def _swiglu(p, h):
    return (jax.nn.silu(h @ _f32(p["w_gate"])) * (h @ _f32(p["w_up"]))) \
        @ _f32(p["w_down"])


def _attention(p, h, config: dict, window_kind: bool):
    """``h`` (S, d) of one sequence."""
    s = h.shape[0]
    n_q, hd, vd = (config["num_attention_heads"], config["head_dim"],
                   config["v_head_dim"])
    n_kv = config["swa_num_key_value_heads" if window_kind
                  else "num_key_value_heads"]
    theta = config["swa_rope_theta" if window_kind else "rope_theta"]
    has_sink = config["add_swa_attention_sink_bias" if window_kind
                      else "add_full_attention_sink_bias"]
    n_rot = int(hd * config["partial_rotary_factor"])
    q, k, v = jnp.split(h @ _f32(p["wqkv"]),
                        [n_q * hd, (n_q + n_kv) * hd], axis=-1)
    q = _partial_rope(q.reshape(s, n_q, hd), n_rot, theta)
    k = _partial_rope(k.reshape(s, n_kv, hd), n_rot, theta)
    v = v.reshape(s, n_kv, vd) * config["attention_value_scale"]
    group = n_q // n_kv
    j = jnp.arange(s)[None, :]

    def block(args):
        qb, i = args                    # (QUERY_BLOCK, n_q, hd), positions
        ok = j <= i[:, None]
        if window_kind:
            ok &= j > i[:, None] - config["sliding_window"]
        # one K/V head at a time, its group's query heads folded into the
        # rows of one plain matrix product (row = query * group + head)
        ok = jnp.repeat(ok, group, axis=0)
        heads = []
        for kv in range(n_kv):
            mine = slice(kv * group, (kv + 1) * group)
            scores = (qb[:, mine].reshape(-1, hd) @ k[:, kv].T) * hd ** -0.5
            scores = jnp.where(ok, scores, -jnp.inf)
            if has_sink:
                # the key without a value: a column that is dropped again
                column = jnp.tile(_f32(p["sink"])[mine], qb.shape[0])
                weights = jax.nn.softmax(
                    jnp.concatenate([scores, column[:, None]], -1),
                    -1)[:, :-1]
            else:
                weights = jax.nn.softmax(scores, -1)
            heads.append((weights @ v[:, kv]).reshape(-1, group, vd))
        return jnp.concatenate(heads, axis=1)   # (QUERY_BLOCK, n_q, vd)

    n_blocks = -(-s // QUERY_BLOCK)
    pad = n_blocks * QUERY_BLOCK - s    # padded queries attend as the last
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        n_blocks, QUERY_BLOCK, n_q, hd)
    pos = jnp.minimum(jnp.arange(n_blocks * QUERY_BLOCK), s - 1).reshape(
        n_blocks, QUERY_BLOCK)
    att = jax.lax.map(block, (qp, pos)).reshape(-1, n_q * vd)[:s]
    return att @ _f32(p["wo"])


def _experts(p, h, config: dict):
    """The held experts' terms of the routed sum, for ``h`` (S, d).

    An expert is applied to the tokens routed to it, found as the ``tokens
    // EXPERT_SHARE`` largest entries of its column of the (token, expert)
    weight matrix — a routed weight is positive, an unrouted one 0, so where
    no more than that many tokens chose the expert the selection holds them
    all and the rest add exact zeros — and to *every* token under its column
    where more did.  Either way the sum is the dense one."""
    first, held = config["expert_first"], config["n_routed_experts"]
    k = config["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ _f32(p["router"]))
    _, top = jax.lax.top_k(s + _f32(p["bias"]), k)
    w = jnp.take_along_axis(s, top, -1)
    if config["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * (config["routed_scaling_factor"] or 1.0)
    # (S, held): the weight with which each held expert enters a token
    per_expert = (w[..., None] * (
        top[..., None] == first + jnp.arange(held))).sum(-2)
    few = max(1, h.shape[0] // EXPERT_SHARE)
    # the loop slices the stacked experts as integers of their width: the
    # CPU backend has no bfloat16 slice and would convert each stacked
    # tensor to float32 whole, ahead of the loop
    stored = p["experts"]["w_up"].dtype
    as_bits = jnp.dtype(f"uint{8 * stored.itemsize}")

    def one(total, xs):
        bits, weight = xs               # weight: (S,)
        expert = jax.tree.map(
            lambda a: jax.lax.bitcast_convert_type(a, stored), bits)

        def routed_only(total):
            top_w, rows = jax.lax.top_k(weight, few)
            return total.at[rows].add(
                top_w[:, None] * _swiglu(expert, h[rows]))

        def every_token(total):
            return total + weight[:, None] * _swiglu(expert, h)

        return jax.lax.cond((weight != 0).sum() <= few, routed_only,
                            every_token, total), None

    bits = jax.tree.map(
        lambda a: jax.lax.bitcast_convert_type(a, as_bits), p["experts"])
    total, _ = jax.lax.scan(one, jnp.zeros_like(h), (bits, per_expert.T))
    return total


def _forward_one(params, ids, config: dict):
    with jax.default_matmul_precision("highest"):
        eps = config["layernorm_epsilon"]
        x = _f32(params["wte"])[ids]
        for i in range(config["num_hidden_layers"]):
            p = params[f"h{i}"]
            x = x + _attention(p["attn"], _rms_norm(x, p["ln_attn"], eps),
                               config, config["hybrid_layer_pattern"][i] == 1)
            h = _rms_norm(x, p["ln_mlp"], eps)
            if config["moe_layer_freq"][i]:
                x = x + _experts(p["moe"], h, config)
            else:
                x = x + _swiglu(p["mlp"], h)
        return _rms_norm(x, params["ln_f"], eps) @ _f32(params["head"])


def forward(params, input_ids, config: dict):
    """Logits (B, S, V) in float32 for token ids (B, S), one sequence
    after the other."""
    return jax.lax.map(lambda ids: _forward_one(params, ids, config),
                       input_ids)


def init_params(config: dict, seed: int):
    """The weights the server makes from ``seed``: the system's own random
    init of its ``system_config`` preset (bfloat16 values).  The only place
    this file touches the system under test."""
    from distributedtensorflow_tpu import models
    from distributedtensorflow_tpu.models import mimo

    cfg = getattr(models, config["system_config"])()
    return mimo.init_params(cfg, jax.random.PRNGKey(seed))


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
