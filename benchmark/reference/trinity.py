"""Plain float32 reference of the afmoe decoder (Arcee Trinity), cut to a
chip's share of its experts.

Straightforward ``jax.numpy``: no kernels, no cache, no grouping of tokens,
no code of the system under test but its random initialiser.  The
equations are those of ``transformers``' ``modeling_afmoe.py``, as the
configuration file lists them under ``assumed``:

- ``x = E[ids] * sqrt(d)`` (``mup_enabled``); untied head, ``logits =
  RMSNorm(x) Wu``; RMSNorm with ``rms_norm_eps`` everywhere, no biases;
- block: ``x = x + N_post_attn(Attn(N_in(x)))``, then ``x = x +
  N_post_mlp(FFN(N_pre_mlp(x)))``;
- attention: q (``num_attention_heads`` x ``head_dim``), k, v
  (``num_key_value_heads`` x ``head_dim``) and a gate as wide as q from one
  projection each; RMSNorm over every head of q and of k; rotary
  (rotate-half, ``rope_theta``) on q and k of ``sliding_attention`` layers
  only; softmax at scale ``head_dim ** -0.5``, causal, on sliding layers
  over keys ``j`` with ``i - sliding_window < j <= i``; output ``(a *
  sigmoid(g)) Wo``;
- FFN of the first ``num_dense_layers`` layers: ``(silu(h Wgate) * h Wup)
  Wdown``;
- FFN of the others: ``s = sigmoid(h Wr)`` over ``num_experts_published``
  experts, the top ``num_experts_per_tok`` of ``s + b``, weights ``s[top] /
  (sum s[top] + 1e-20) * route_scale``, ``y = Shared(h) + sum_j w_j
  Expert_top_j(h)`` — of which this share holds experts ``expert_first ..
  expert_first + num_experts``: the others' terms are left out, here as in
  the program.  The held experts are applied one after the other, each
  under its own column of the (token, expert) weight matrix (``_experts``
  says how the zero entries of a column are skipped without changing the
  sum).

Weights are the server's own (bfloat16 values), the arithmetic float32
under ``jax.default_matmul_precision("highest")``.  The system stores q, k,
v and the gate as one matrix ``wqkvg``; it is split here.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


#: queries whose scores are held at one time (a block of the same sum)
QUERY_BLOCK = 512
#: an expert is applied to 1 / EXPERT_SHARE of the tokens where no more
#: chose it (uniform routing sends it 4 / 256), to all of them otherwise
EXPERT_SHARE = 8


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * _f32(scale)


def _rope(x, theta):
    """x: (B, S, H, D); rotate-half rotary embedding at positions 0..S-1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[None, :, None, None] \
        * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _swiglu(p, h):
    return (jax.nn.silu(h @ _f32(p["w_gate"])) * (h @ _f32(p["w_up"]))) \
        @ _f32(p["w_down"])


def _attention(p, h, config: dict, sliding: bool):
    b, s, _ = h.shape
    n_q, n_kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = config["head_dim"], config["rms_norm_eps"]
    proj = h @ _f32(p["wqkvg"])
    q, k, v, gate = jnp.split(
        proj, [n_q * hd, (n_q + n_kv) * hd, (n_q + 2 * n_kv) * hd], axis=-1)
    q = _rms_norm(q.reshape(b, s, n_q, hd), p["q_norm"], eps)
    k = _rms_norm(k.reshape(b, s, n_kv, hd), p["k_norm"], eps)
    v = v.reshape(b, s, n_kv, hd)
    if sliding:
        q, k = _rope(q, config["rope_theta"]), _rope(k, config["rope_theta"])
    q = q.reshape(b, s, n_kv, n_q // n_kv, hd)
    j = jnp.arange(s)[None, :]

    def block(args):
        # the same softmax attention for QUERY_BLOCK queries: the (heads,
        # S, S) scores of a 4,192-token check are 3.4 GB at once, and a
        # loop makes the blocks share their memory.  One K/V head at a
        # time, its group's query heads folded into the rows of one plain
        # matrix product (the CPU's batched products are ten times slower)
        qb, i = args                    # (b, QUERY_BLOCK, ...), positions
        ok = j <= i[:, None]
        if sliding:
            ok &= j > i[:, None] - config["sliding_window"]
        heads = []
        for kv in range(n_kv):
            rows = qb[:, :, kv].reshape(b, -1, hd)      # (b, Q * g, hd)
            scores = jnp.einsum("bmd,bkd->bmk", rows, k[:, :, kv]) \
                / math.sqrt(hd)
            scores = jnp.where(jnp.repeat(ok, n_q // n_kv, axis=0), scores,
                               -jnp.inf)
            heads.append(jnp.einsum(
                "bmk,bkd->bmd", jax.nn.softmax(scores, -1), v[:, :, kv]
            ).reshape(b, QUERY_BLOCK, n_q // n_kv, hd))
        return jnp.stack(heads, axis=2)     # (b, Q, n_kv, g, hd)

    n_blocks = -(-s // QUERY_BLOCK)
    pad = n_blocks * QUERY_BLOCK - s    # padded queries attend as the last
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    qp = jnp.moveaxis(qp.reshape(b, n_blocks, QUERY_BLOCK, *q.shape[2:]),
                      1, 0)
    pos = jnp.minimum(jnp.arange(n_blocks * QUERY_BLOCK), s - 1).reshape(
        n_blocks, QUERY_BLOCK)
    att = jnp.moveaxis(jax.lax.map(block, (qp, pos)), 0, 1).reshape(
        b, n_blocks * QUERY_BLOCK, n_q * hd)[:, :s]
    return (att * jax.nn.sigmoid(gate)) @ _f32(p["wo"])


def _experts(p, h, config: dict):
    """The held experts' terms of the routed sum, for ``h`` (B, S, d).

    An expert is applied to the tokens routed to it, found as the
    ``tokens // EXPERT_SHARE`` largest entries of its column of the (token,
    expert) weight matrix — a routed weight is positive, an unrouted one 0,
    so where no more than that many tokens chose the expert the selection
    holds them all and the rest add exact zeros — and to *every* token
    under its column where more did (a router may crowd one expert).  Either
    way the sum is the dense one; the selection only saves the 8,384-token
    check seven eighths of 61 TFLOP on the CPU."""
    first, held = config["expert_first"], config["num_experts"]
    k = config["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ _f32(p["router"]))
    _, top = jax.lax.top_k(s + _f32(p["bias"]), k)
    w = jnp.take_along_axis(s, top, -1)
    if config["route_norm"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * config["route_scale"]
    # (B * S, held): the weight with which each held expert enters a token
    per_expert = (w[..., None] * (
        top[..., None] == first + jnp.arange(held))).sum(-2).reshape(
            -1, held)
    tokens = h.reshape(-1, h.shape[-1])
    few = max(1, tokens.shape[0] // EXPERT_SHARE)
    # the loop slices the stacked experts as integers of their width: the
    # CPU backend has no bfloat16 slice and would convert each stacked
    # tensor to float32 whole, ahead of the loop (1.2 GB each, 14.5 GB)
    stored = p["experts"]["w_up"].dtype
    as_bits = jnp.dtype(f"uint{8 * stored.itemsize}")

    def one(total, xs):
        bits, weight = xs               # weight: (B * S,)
        expert = jax.tree.map(
            lambda a: jax.lax.bitcast_convert_type(a, stored), bits)

        def routed_only(total):
            top_w, rows = jax.lax.top_k(weight, few)
            return total.at[rows].add(
                top_w[:, None] * _swiglu(expert, tokens[rows]))

        def every_token(total):
            return total + weight[:, None] * _swiglu(expert, tokens)

        return jax.lax.cond((weight != 0).sum() <= few, routed_only,
                            every_token, total), None

    bits = jax.tree.map(
        lambda a: jax.lax.bitcast_convert_type(a, as_bits), p["experts"])
    total, _ = jax.lax.scan(one, jnp.zeros_like(tokens),
                            (bits, per_expert.T))
    return total.reshape(h.shape)


def forward(params, input_ids, config: dict):
    """Logits (B, S, V) in float32 for token ids (B, S), one sequence
    after the other."""
    return jax.lax.map(
        lambda ids: _forward_one(params, ids[None], config)[0], input_ids)


def _forward_one(params, input_ids, config: dict):
    with jax.default_matmul_precision("highest"):
        eps = config["rms_norm_eps"]
        x = _f32(params["wte"])[input_ids]
        if config["mup_enabled"]:
            x = x * math.sqrt(config["hidden_size"])
        for i in range(config["num_hidden_layers"]):
            p = params[f"h{i}"]
            sliding = config["layer_types"][i] == "sliding_attention"
            a = _attention(p["attn"], _rms_norm(x, p["ln_in"], eps), config,
                           sliding)
            x = x + _rms_norm(a, p["ln_post_attn"], eps)
            h = _rms_norm(x, p["ln_pre_mlp"], eps)
            if i < config["num_dense_layers"]:
                f = _swiglu(p["mlp"], h)
            else:
                f = _swiglu(p["moe"]["shared"], h) \
                    + _experts(p["moe"], h, config)
            x = x + _rms_norm(f, p["ln_post_mlp"], eps)
        return _rms_norm(x, params["ln_f"], eps) @ _f32(params["head"])


def init_params(config: dict, seed: int):
    """The weights the server makes from ``seed``: the system's own random
    init of its ``system_config`` preset (bfloat16 values).  The only place
    this file touches the system under test."""
    from distributedtensorflow_tpu import models
    from distributedtensorflow_tpu.models import afmoe

    cfg = getattr(models, config["system_config"])()
    return afmoe.init_params(cfg, jax.random.PRNGKey(seed))


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
