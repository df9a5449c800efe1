"""Plain float32 reference of the GLM-5 decoder (``model_type glm_moe_dsa``:
latent attention whose rows a learned indexer selects, sigmoid-routed
experts), as one chip's share of an expert-parallel deployment holds it.

Straightforward ``jax.numpy``: no kernels, no cache, no grouping of tokens,
no absorbed attention, no gathered rows, no code of the system under test but
its random initialiser.  The equations, as the configuration file lists them
under ``assumed``:

- ``x = E[ids]``; untied head, ``logits = RMSNorm(x) Wu``; RMSNorm with
  ``rms_norm_eps`` everywhere, no biases; pre-norm blocks ``x = x +
  Attn(N1(x))``, ``x = x + FFN(N2(x))``;
- attention, **non-absorbed** (``h = N1(x)``): ``c_q = RMSNorm(h W_qa)``, ``q
  = c_q W_qb`` -> ``num_attention_heads x [q_nope | q_rope]``; ``[c | k_r] = h
  W_kva``, ``c_kv = RMSNorm(c)``; every head's ``k_nope_i = c_kv W_UK_i`` and
  ``v_i = c_kv W_UV_i`` for every position; rotary on ``q_rope`` and the one
  shared ``k_r``, the values interleaved pairs ``(x[2i], x[2i+1])``,
  ``rope_theta`` of ``rope_parameters``; scores ``(q_nope_i . k_nope_j +
  q_rope_i . k_rope_j) * qk_head_dim ** -0.5``;
- the **indexer**: ``qI = c_q W_qI`` -> ``index_n_heads x index_head_dim``,
  ``kI = LayerNorm(h W_kI)`` (weight and bias, eps 1e-6), one key a token
  shared by the index heads; the same rotary on the first
  ``qk_rope_head_dim`` values of both; head weights ``w = (h W_w) *
  index_n_heads ** -0.5 * index_head_dim ** -0.5``; ``I(t, s) = sum_j w_tj
  relu(qI_tj . kI_s)`` as a dense (queries, S) array; ``S_t`` = the
  ``min(index_topk, t + 1)`` positions ``s <= t`` of largest ``I(t, .)``
  (``lax.top_k``: ties to the lowest position), as a mask; the softmax runs
  over ``S_t`` alone, output ``concat_i(sum_j p_ij v_j) W_o``;
- FFN of the first ``first_k_dense_replace`` layers: ``(silu(h Wgate) * h
  Wup) Wdown`` of ``intermediate_size``;
- FFN of the others: ``s = sigmoid(h W_r)`` over ``n_routed_experts_published``,
  the top ``num_experts_per_tok`` of ``s + b`` (one group), weights ``s[top] /
  (sum s[top] + 1e-20) * routed_scaling_factor``, ``y = Shared(h) + sum_j w_j
  Expert_top_j(h)`` — of which this share holds experts ``expert_first ..
  expert_first + n_routed_experts``: the others' terms are left out, here as
  in the program.

Weights are the server's own (bfloat16 values), the arithmetic float32 under
``jax.default_matmul_precision("highest")``.  Departures of the stored form
from the published one: ``kv_b_proj`` is stored as its two halves ``w_uk``,
``w_uv``; the published inference code's Hadamard rotation of ``qI`` and
``kI`` (orthonormal: every product as it is) and its fp8 keys are left out.
Queries are processed ``QUERY_BLOCK`` positions at a time (the same sums).
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


def _layer_norm(x, scale, bias, eps=1e-6):
    x = x - x.mean(-1, keepdims=True)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * _f32(scale) + _f32(bias)


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


def _rope_leading(x, rope_dim, theta):
    return jnp.concatenate([_rope_interleaved(x[..., :rope_dim], theta),
                            x[..., rope_dim:]], -1)


def _swiglu(p, h):
    return (jax.nn.silu(h @ _f32(p["w_gate"])) * (h @ _f32(p["w_up"]))) \
        @ _f32(p["w_down"])


def _theta(config: dict) -> float:
    return float(config["rope_parameters"]["rope_theta"])


def index_scores(p, h, c_q, config: dict):
    """``I`` (S, S) in float32: row ``t`` the scores of query ``t`` against
    every position's index key (the future's too: the caller masks)."""
    s = h.shape[0]
    heads, dim = config["index_n_heads"], config["index_head_dim"]
    rope_dim = config["qk_rope_head_dim"]
    q = _rope_leading((c_q @ _f32(p["w_q"])).reshape(s, heads, dim),
                      rope_dim, _theta(config))
    k = _rope_leading(
        _layer_norm(h @ _f32(p["w_k"]), p["k_norm"], p["k_bias"]),
        rope_dim, _theta(config))
    w = (h @ _f32(p["w_w"])) * (heads ** -0.5 * dim ** -0.5)
    total = jnp.zeros((s, s), jnp.float32)
    for j in range(heads):
        total = total + w[:, j:j + 1] * jax.nn.relu(q[:, j] @ k.T)
    return total


def selected(scores, topk: int):
    """The mask (S, S): entry ``(t, s)`` whether ``s <= t`` is one of the
    ``min(topk, t + 1)`` positions of largest ``scores[t]``."""
    s = scores.shape[0]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    if s <= topk:
        return causal
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    picked = jnp.zeros((s, s), bool).at[
        jnp.arange(s)[:, None], idx].set(True)
    return picked & causal


def _attention(p, h, config: dict):
    """h: (S, d) -> (S, d), every key and value decompressed, the softmax of
    each query over its selected positions."""
    s = h.shape[0]
    heads, eps = config["num_attention_heads"], config["rms_norm_eps"]
    rank, nope = config["kv_lora_rank"], config["qk_nope_head_dim"]
    rope_dim, vd = config["qk_rope_head_dim"], config["v_head_dim"]
    theta = _theta(config)
    c_q = _rms_norm(h @ _f32(p["w_qa"]), p["q_norm"], eps)
    q = (c_q @ _f32(p["w_qb"])).reshape(s, heads, nope + rope_dim)
    q_nope, q_rope = q[..., :nope], _rope_interleaved(q[..., nope:], theta)
    ckr = h @ _f32(p["w_kva"])
    c_kv = _rms_norm(ckr[:, :rank], p["kv_norm"], eps)
    k_rope = _rope_interleaved(ckr[:, rank:], theta)            # (S, rope)
    k_nope = (c_kv @ _f32(p["w_uk"]).reshape(rank, -1)).reshape(
        s, heads, nope)
    v = (c_kv @ _f32(p["w_uv"]).reshape(rank, -1)).reshape(s, heads, vd)
    scale = (nope + rope_dim) ** -0.5
    mask = selected(index_scores(p["indexer"], h, c_q, config),
                    config["index_topk"])

    def block(args):
        # one head at a time, plain matrix products (the CPU's batched
        # products are ten times slower)
        qn, qr, ok = args               # (QUERY_BLOCK, heads, .), (., S)
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
        a = jnp.concatenate([a, jnp.repeat(a[-1:], pad, 0)], 0)
        return a.reshape(n_blocks, QUERY_BLOCK, *a.shape[1:])

    att = jax.lax.map(block, (blocks(q_nope), blocks(q_rope), blocks(mask))
                      ).reshape(n_blocks * QUERY_BLOCK, heads * vd)[:s]
    return att @ _f32(p["w_o"])


def _experts(p, h, config: dict):
    """The held experts' terms of the routed sum for ``h`` (S, d): a loop
    over the held experts, each applied to the tokens routed to it — found as
    the ``tokens // EXPERT_SHARE`` largest entries of its column of the
    (token, expert) weight matrix: a routed weight is positive, an unrouted
    one 0, so where no more than that many tokens chose the expert the
    selection holds them all and the rest add exact zeros — and to *every*
    token under its column where more did.  Either way the sum is the dense
    one over the held experts."""
    first, held = config.get("expert_first", 0), config["n_routed_experts"]
    k = config["num_experts_per_tok"]
    if config.get("n_group", 1) != 1:
        raise NotImplementedError("group-limited routing: n_group > 1")
    s = jax.nn.sigmoid(h @ _f32(p["router"]))     # the published width
    _, top = jax.lax.top_k(s + _f32(p["bias"]), k)
    w = jnp.take_along_axis(s, top, -1)
    if config["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * config["routed_scaling_factor"]
    per_expert = (w[..., None] * (
        top[..., None] == first + jnp.arange(held))).sum(-2)    # (S, held)
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
        eps = config["rms_norm_eps"]
        x = _f32(params["wte"])[input_ids]
        for i in range(config["num_hidden_layers"]):
            p = params[f"h{i}"]
            x = x + _attention(p["attn"], _rms_norm(x, p["ln_attn"], eps),
                               config)
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
    from distributedtensorflow_tpu.models import joyai

    cfg = getattr(models, config["system_config"])()
    return joyai.init_params(cfg, jax.random.PRNGKey(seed))


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
