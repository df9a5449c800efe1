"""Plain float32 reference of the AI21 Jamba decoder (Mamba-1 layers with an
attention layer every ``attn_layer_period``, a dense SwiGLU in every layer).

Straightforward ``jax.numpy``: no kernels, no cache, no state carried between
calls, no chunks, no code of the system under test but its random
initialiser (``ops/ssm.py`` and ``models/jamba.py``'s layer functions are not
imported).  The equations are those of the ``jamba`` modelling code the keys
of the model's ``config.json`` belong to, as the configuration file lists
them under ``assumed``:

- ``x = E[ids]``; tied head, ``logits = RMSNorm(x) E^T``; RMSNorm with
  ``rms_norm_eps`` everywhere; pre-norm blocks ``x = x + Mixer_i(N_in(x))``,
  ``x = x + FFN(N_ff(x))``, ``FFN`` the SwiGLU of ``intermediate_size`` in
  every layer (``num_experts`` 1; more is refused);
- layer ``i`` attends where ``i % attn_layer_period == attn_layer_offset``:
  ``q, k, v = h Wq, h Wk, h Wv``, ``num_attention_heads`` query heads on
  ``num_key_value_heads`` K/V heads of ``hidden_size / num_attention_heads``,
  no rotary and no other position signal, scores ``q . k * D ** -0.5``, dense
  causal softmax over the whole sequence, output ``concat(o) Wo``;
- every other layer is Mamba: ``[u | z] = h W_in`` (``mamba_expand *
  hidden_size`` channels each); ``u' = silu(conv(u) + b_conv)``, the causal
  depthwise convolution over ``mamba_d_conv`` tokens from zeros; ``[r | B | C]
  = u' W_x`` (``mamba_dt_rank | mamba_d_state | mamba_d_state``), each under an
  RMSNorm with a learned scale (``dt_layernorm``, ``b_layernorm``,
  ``c_layernorm``); ``delta = softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``;
  a ``lax.scan`` over tokens from a zero state: ``s_t = exp(delta_t (x) A) *
  s_{t-1} + (delta_t * u'_t) (x) B_t``, ``y_t = s_t C_t + D * u'_t``; output
  ``(y * silu(z)) W_out``.

Weights are the server's own (bfloat16 values), the arithmetic float32 under
``jax.default_matmul_precision("highest")``.  Departures of the stored form
from the published one, used as they are: ``A_log`` is stored ``(d_state,
channels)`` (the published one transposed) and the scan state is laid out so
too; the convolution's weight is stored ``(d_conv, channels)`` (published
``(channels, 1, d_conv)``), tap ``k`` weighing the input ``d_conv - 1 - k``
tokens back; ``q``, ``k``, ``v`` projections are one matrix ``wqkv``.
Queries are processed ``QUERY_BLOCK`` positions at a time (the same sums).
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


def _attends(config: dict, layer: int) -> bool:
    return layer % config["attn_layer_period"] == config["attn_layer_offset"]


def _attention(p, h, config: dict):
    """h: (S, d) -> (S, d): dense causal softmax, no position signal."""
    s = h.shape[0]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    dim = config["hidden_size"] // heads
    qkv = h @ _f32(p["wqkv"])
    q = qkv[:, :heads * dim].reshape(s, heads, dim)
    k = qkv[:, heads * dim:(heads + kv_heads) * dim].reshape(s, kv_heads, dim)
    v = qkv[:, (heads + kv_heads) * dim:].reshape(s, kv_heads, dim)
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


def _mamba(p, h, config: dict):
    """h: (S, d) -> (S, d): the whole sequence from a zero state."""
    s = h.shape[0]
    n, r = config["mamba_d_state"], config["mamba_dt_rank"]
    taps, eps = config["mamba_d_conv"], config["rms_norm_eps"]
    channels = config["mamba_expand"] * config["hidden_size"]
    uz = h @ _f32(p["w_in"])
    u, z = uz[:, :channels], uz[:, channels:]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    conv = _f32(p["conv_b"]) + sum(
        _f32(p["conv_w"])[k] * padded[k:k + s] for k in range(taps))
    u = jax.nn.silu(conv)
    rbc = u @ _f32(p["w_x"])
    rank = _rms_norm(rbc[:, :r], p["dt_norm"], eps)
    b = _rms_norm(rbc[:, r:r + n], p["b_norm"], eps)
    c = _rms_norm(rbc[:, r + n:], p["c_norm"], eps)
    delta = jax.nn.softplus(rank @ _f32(p["w_dt"]) + _f32(p["dt_bias"]))
    a = -jnp.exp(_f32(p["a_log"]))                          # (n, channels)

    def step(state, xs):
        u_t, d_t, b_t, c_t = xs
        state = jnp.exp(d_t[None, :] * a) * state \
            + (d_t * u_t)[None, :] * b_t[:, None]
        return state, c_t @ state

    _, y = jax.lax.scan(step, jnp.zeros((n, channels), jnp.float32),
                        (u, delta, b, c))
    y = y + _f32(p["d"]) * u
    return (y * jax.nn.silu(z)) @ _f32(p["w_out"])


def forward(params, input_ids, config: dict):
    """Logits (B, S, V) in float32 for token ids (B, S), one sequence
    after the other."""
    return jax.lax.map(lambda ids: _forward_one(params, ids, config),
                       input_ids)


def _forward_one(params, input_ids, config: dict):
    if config.get("num_experts", 1) != 1:
        raise NotImplementedError("routed experts: num_experts > 1")
    with jax.default_matmul_precision("highest"):
        eps = config["rms_norm_eps"]
        wte = _f32(params["wte"])
        x = wte[input_ids]
        for i in range(config["num_hidden_layers"]):
            p = params[f"h{i}"]
            h = _rms_norm(x, p["ln_in"], eps)
            if _attends(config, i):
                x = x + _attention(p["attn"], h, config)
            else:
                x = x + _mamba(p["mamba"], h, config)
            x = x + _swiglu(p["mlp"], _rms_norm(x, p["ln_ff"], eps))
        return _rms_norm(x, params["ln_f"], eps) @ wte.T


def init_params(config: dict, seed: int):
    """The weights the server makes from ``seed``: the system's own random
    init of its ``system_config`` preset (bfloat16 values).  The only place
    this file touches the system under test."""
    from distributedtensorflow_tpu import models
    from distributedtensorflow_tpu.models import jamba

    cfg = getattr(models, config["system_config"])()
    return jamba.init_params(cfg, jax.random.PRNGKey(seed))


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
