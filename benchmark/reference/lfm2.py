"""Plain float32 reference of the LiquidAI LFM2-MoE decoder (gated
short-convolution layers with a grouped-query attention layer every fourth,
a sigmoid router over SwiGLU experts past the leading dense layers).

Straightforward ``jax.numpy``: no kernels, no cache, no tail carried between
calls, no chunks, no grouping of tokens, no code of the system under test but
its random initialiser (``init_params``, imported there and nowhere else:
``ops/ssm.py``, ``parallel/moe.py`` and ``models/lfm2.py``'s layer functions
are not imported).  The equations are those of ``transformers``'
``modeling_lfm2_moe.py`` as remembered (there is no network here), as the
configuration file lists them under ``assumed``:

- ``x = E[ids]`` (no scale); tied head, ``logits = RMSNorm(x) E^T``; RMSNorm
  with a learned scale and ``norm_eps`` everywhere, no bias anywhere; pre-norm
  blocks ``x = x + Op_i(N_op(x))``, ``x = x + FFN_i(N_ffn(x))``;
- a ``conv`` layer: ``[B | C | u] = h W_in`` (``hidden_size`` each, in that
  order); ``g = B * u``; ``c_t = sum_k w_k * g_{t - (K - 1) + k}`` a channel,
  ``K = conv_L_cache``, ``g`` before the sequence's start zero: the sum of
  ``K`` shifted products over the whole sequence; output ``(C * c) W_out``;
- a ``full_attention`` layer: ``q, k, v = h Wq, h Wk, h Wv``,
  ``num_attention_heads`` query heads on ``num_key_value_heads`` K/V heads of
  ``head_dim``; RMSNorm over each head of q and of k (a learned scale of
  ``head_dim``), then rotary (rotate-half, ``rope_theta``, the whole head) on
  both; scores ``q . k * head_dim ** -0.5``, dense causal softmax over the
  whole sequence, output ``concat(o) Wo``;
- the FFN of the first ``num_dense_layers`` layers: ``(silu(h Wgate) * h Wup)
  Wdown`` of ``intermediate_size``;
- the FFN of the others: ``s = sigmoid(h Wr)`` over ``num_experts``, the top
  ``num_experts_per_tok`` of ``s + b`` (``expert_bias``: selects only),
  weights ``s[top] / (sum s[top] + 1e-6) * routed_scaling_factor``
  (``norm_topk_prob``), ``y = sum_j w_j Expert_top_j(h)``, every expert a
  SwiGLU of ``moe_intermediate_size``, no shared expert.  The experts are
  applied one after the other, each under its own column of the (token,
  expert) weight matrix (``_experts`` says how the zero entries of a column
  are skipped without changing the sum).

Weights are the server's own (bfloat16 values), the arithmetic float32 under
``jax.default_matmul_precision("highest")``.  Departures of the stored form
from the published one, used as they are: the convolution's weight is stored
``(K, hidden_size)`` (published ``(hidden_size, 1, K)``), tap ``k`` weighing
the input ``K - 1 - k`` tokens back; ``q``, ``k``, ``v`` projections are one
matrix ``wqkv``; an expert's ``w1 / w3 / w2`` are stacked over the experts as
``w_gate / w_up / w_down``.  Queries are processed ``QUERY_BLOCK`` positions
at a time (the same sums).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: queries whose scores are held at one time (a block of the same sum)
QUERY_BLOCK = 512
#: an expert is applied to 1 / EXPERT_SHARE of the tokens where no more
#: chose it (uniform routing sends it 4 / 64), to all of them otherwise
EXPERT_SHARE = 8
#: what the published router adds to a token's summed top scores
ROUTE_NORM_EPS = 1e-6


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * _f32(scale)


def _swiglu(p, h):
    return (jax.nn.silu(h @ _f32(p["w_gate"])) * (h @ _f32(p["w_up"]))) \
        @ _f32(p["w_down"])


def _rope(x, theta):
    """x: (S, H, D); rotate-half rotary embedding at positions 0..S-1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _head_dim(config: dict) -> int:
    return config.get("head_dim") or (
        config["hidden_size"] // config["num_attention_heads"])


def _attention(p, h, config: dict):
    """h: (S, d) -> (S, d): dense causal softmax over normed, rotated q, k."""
    s = h.shape[0]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    dim, eps = _head_dim(config), config["norm_eps"]
    theta = config["rope_parameters"]["rope_theta"]
    qkv = h @ _f32(p["wqkv"])
    q = qkv[:, :heads * dim].reshape(s, heads, dim)
    k = qkv[:, heads * dim:(heads + kv_heads) * dim].reshape(s, kv_heads, dim)
    v = qkv[:, (heads + kv_heads) * dim:].reshape(s, kv_heads, dim)
    q = _rope(_rms_norm(q, p["q_norm"], eps), theta)
    k = _rope(_rms_norm(k, p["k_norm"], eps), theta)
    group = heads // kv_heads
    j = jnp.arange(s)[None, :]

    def block(args):
        qb, i = args                    # (QUERY_BLOCK, heads, dim), positions
        ok = j <= i[:, None]
        out = []
        for hd in range(heads):
            scores = qb[:, hd] @ k[:, hd // group].T * dim ** -0.5
            scores = jnp.where(ok, scores, -jnp.inf)
            out.append(jax.nn.softmax(scores, -1) @ v[:, hd // group])
        return jnp.stack(out, axis=1)

    n_blocks = -(-s // QUERY_BLOCK)
    pad = n_blocks * QUERY_BLOCK - s    # padded queries attend as the last
    qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        n_blocks, QUERY_BLOCK, heads, dim)
    pos = jnp.minimum(jnp.arange(n_blocks * QUERY_BLOCK), s - 1).reshape(
        n_blocks, QUERY_BLOCK)
    att = jax.lax.map(block, (qs, pos)).reshape(
        n_blocks * QUERY_BLOCK, heads * dim)[:s]
    return att @ _f32(p["wo"])


def _conv(p, h, config: dict):
    """h: (S, d) -> (S, d): the whole sequence from a zero tail."""
    s, d = h.shape
    taps = config["conv_L_cache"]
    bcu = h @ _f32(p["w_in"])
    b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    padded = jnp.pad(b * u, ((taps - 1, 0), (0, 0)))
    conv = sum(_f32(p["conv_w"])[k] * padded[k:k + s] for k in range(taps))
    return (c * conv) @ _f32(p["w_out"])


def _experts(p, h, config: dict):
    """The routed sum over the experts, for ``h`` (S, d).

    An expert is applied to the tokens routed to it, found as the
    ``tokens // EXPERT_SHARE`` largest entries of its column of the (token,
    expert) weight matrix — a routed weight is positive, an unrouted one 0,
    so where no more than that many tokens chose the expert the selection
    holds them all and the rest add exact zeros — and to *every* token
    under its column where more did (a router may crowd one expert).  Either
    way the sum is the dense loop's over the 64; the selection only saves the
    4,353-token check seven eighths of 41 TFLOP a request on the CPU."""
    experts, k = config["num_experts"], config["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ _f32(p["router"]))
    _, top = jax.lax.top_k(s + _f32(p["bias"]), k)
    w = jnp.take_along_axis(s, top, -1)
    if config["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + ROUTE_NORM_EPS)
    w = w * config["routed_scaling_factor"]
    # (S, experts): the weight with which each expert enters a token
    per_expert = (w[..., None] * (
        top[..., None] == jnp.arange(experts))).sum(-2)
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


def forward(params, input_ids, config: dict):
    """Logits (B, S, V) in float32 for token ids (B, S), one sequence
    after the other."""
    return jax.lax.map(lambda ids: _forward_one(params, ids, config),
                       input_ids)


def _forward_one(params, input_ids, config: dict):
    with jax.default_matmul_precision("highest"):
        eps = config["norm_eps"]
        wte = _f32(params["wte"])
        x = wte[input_ids]
        for i in range(config["num_hidden_layers"]):
            p = params[f"h{i}"]
            h = _rms_norm(x, p["ln_op"], eps)
            if config["layer_types"][i] == "conv":
                x = x + _conv(p["conv"], h, config)
            else:
                x = x + _attention(p["attn"], h, config)
            h = _rms_norm(x, p["ln_ffn"], eps)
            if i < config["num_dense_layers"]:
                x = x + _swiglu(p["mlp"], h)
            else:
                x = x + _experts(p["moe"], h, config)
        return _rms_norm(x, params["ln_f"], eps) @ wte.T


def init_params(config: dict, seed: int):
    """The weights the server makes from ``seed``: the system's own random
    init of its ``system_config`` preset (bfloat16 values).  The only place
    this file touches the system under test."""
    from distributedtensorflow_tpu import models
    from distributedtensorflow_tpu.models import lfm2

    cfg = getattr(models, config["system_config"])()
    return lfm2.init_params(cfg, jax.random.PRNGKey(seed))


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
