"""Plain float32 reference of the Ouro looped decoder (ByteDance/Ouro-2.6B,
``model_type`` ``ouro``: the whole stack of layers run ``total_ut_steps``
times over the same weights, sandwich norms, an exit gate after every pass).

Straightforward ``jax.numpy``: no kernels, no cache, no device loop, a Python
loop over the passes and the layers, no code of the system under test but its
random initialiser (``models/ouro.py``'s layer functions and ``serve/`` are
not imported).  The equations are those of the ``ouro`` modelling code the
keys of the model's ``config.json`` belong to, as the configuration file
lists them under ``assumed``:

- ``x = E[ids]``; RMSNorm with ``rms_norm_eps`` and a plain learned scale
  everywhere; no bias but the gate's;
- for ``u`` in ``0 .. total_ut_steps - 1``, for layer ``l`` in ``0 ..
  num_hidden_layers - 1`` (the same weights every pass): ``a = Attn_l(N1_l(x))``,
  ``x = x + N2_l(a)``, ``m = W_down(silu(W_gate N3_l(x)) * (W_up N3_l(x)))``,
  ``x = x + N4_l(m)`` — the sandwich: the output of the mixer and of the MLP is
  normed before it joins the residual;
- ``Attn_l``: ``q, k, v = h Wq, h Wk, h Wv`` (``num_attention_heads`` query
  heads on ``num_key_value_heads`` K/V heads of ``head_dim``), rotary
  (rotate-half: channel ``i`` pairs with ``i + head_dim / 2``, frequencies
  ``rope_theta ** (-2 i / head_dim)``) on all channels of q and k, scores ``q .
  k * head_dim ** -0.5``, dense causal softmax over the whole sequence, output
  ``concat(o) Wo``.  Pass ``u`` attends the keys and values pass ``u`` made:
  with no cache that is simply this pass's own ``k`` and ``v``;
- after EVERY pass ``x = N_f(x)`` (its output feeds the next pass) and the exit
  gate ``g_u = x w_g + b_g``; ``lam_u = sigmoid(g_u)``, ``p_u = lam_u *
  prod_{j<u}(1 - lam_j)``, the last pass taking what is left;
- ``logits = x W_head`` of ``x`` after the last pass (``early_exit_threshold``
  1: the cumulative exit probability reaches 1 only there), the head untied.

Weights are the server's own (bfloat16 values), the arithmetic float32 under
``jax.default_matmul_precision("highest")``.  Departures of the stored form
from the published one, used as they are: ``q``, ``k``, ``v`` projections are
one matrix ``wqkv``, every matrix is stored ``(in, out)``, the gate's weight
is a vector ``(hidden_size,)`` and its bias ``(1,)``.  Queries are processed
``QUERY_BLOCK`` positions at a time (the same sums), so that the 641 positions
of the check's request hold one block of scores a head at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: queries whose scores are held at one time (a block of the same sum)
QUERY_BLOCK = 256


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * _f32(scale)


def _rotary(x, theta: float):
    """x: (S, heads, dim) at positions 0..S-1, rotate-half on all channels."""
    s, _, dim = x.shape
    half = dim // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs    # (S, half)
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, h, config: dict):
    """h: (S, d) -> (S, d): dense causal softmax over this pass's K/V."""
    s = h.shape[0]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    dim, theta = config["head_dim"], float(config["rope_theta"])
    qkv = h @ _f32(p["wqkv"])
    q = _rotary(qkv[:, :heads * dim].reshape(s, heads, dim), theta)
    k = _rotary(qkv[:, heads * dim:(heads + kv_heads) * dim].reshape(
        s, kv_heads, dim), theta)
    v = qkv[:, (heads + kv_heads) * dim:].reshape(s, kv_heads, dim)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    j = jnp.arange(s)[None, None, :]

    def block(args):
        qb, i = args                    # (QUERY_BLOCK, heads, dim), positions
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * dim ** -0.5
        scores = jnp.where(j <= i[None, :, None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    n_blocks = -(-s // QUERY_BLOCK)
    pad = n_blocks * QUERY_BLOCK - s    # padded queries attend as the last
    qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        n_blocks, QUERY_BLOCK, heads, dim)
    pos = jnp.minimum(jnp.arange(n_blocks * QUERY_BLOCK), s - 1).reshape(
        n_blocks, QUERY_BLOCK)
    att = jax.lax.map(block, (qs, pos)).reshape(
        n_blocks * QUERY_BLOCK, heads * dim)[:s]
    return att @ _f32(p["wo"])


def _swiglu(p, h):
    return (jax.nn.silu(h @ _f32(p["w_gate"])) * (h @ _f32(p["w_up"]))) \
        @ _f32(p["w_down"])


def _layer(p, x, config: dict):
    eps = config["rms_norm_eps"]
    a = _attention(p["attn"], _rms_norm(x, p["ln_in"], eps), config)
    x = x + _rms_norm(a, p["ln_attn_out"], eps)
    m = _swiglu(p["mlp"], _rms_norm(x, p["ln_mlp_in"], eps))
    return x + _rms_norm(m, p["ln_mlp_out"], eps)


def _forward_one(params, input_ids, config: dict):
    """``(logits (S, V), exit distribution (passes, S))`` of one sequence."""
    with jax.default_matmul_precision("highest"):
        eps = config["rms_norm_eps"]
        x = _f32(params["wte"])[input_ids]
        stay = jnp.ones(x.shape[:1], jnp.float32)
        exits = []
        passes = config["total_ut_steps"]
        for u in range(passes):
            for i in range(config["num_hidden_layers"]):
                x = _layer(params[f"h{i}"], x, config)
            x = _rms_norm(x, params["ln_f"], eps)
            gate = params["exit_gate"]
            lam = jax.nn.sigmoid(x @ _f32(gate["w"]) + _f32(gate["b"])[0])
            exits.append(stay if u == passes - 1 else lam * stay)
            stay = stay * (1.0 - lam)
        return x @ _f32(params["head"]), jnp.stack(exits)


def forward(params, input_ids, config: dict):
    """Logits (B, S, V) in float32 for token ids (B, S), one sequence
    after the other."""
    return jax.lax.map(
        lambda ids: _forward_one(params, ids, config)[0], input_ids)


def exit_distribution(params, input_ids, config: dict):
    """``p`` (B, passes, S): the probability of leaving after each pass, a
    position; sums to 1 over the passes."""
    return jax.lax.map(
        lambda ids: _forward_one(params, ids, config)[1], input_ids)


def init_params(config: dict, seed: int):
    """The weights the server makes from ``seed``: the system's own random
    init of its ``system_config`` preset (bfloat16 values).  The only place
    this file touches the system under test."""
    from distributedtensorflow_tpu import models
    from distributedtensorflow_tpu.models import ouro

    cfg = getattr(models, config["system_config"])()
    return ouro.init_params(cfg, jax.random.PRNGKey(seed))


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
