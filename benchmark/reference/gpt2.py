"""Plain float32 reference of the GPT-2 decoder the system runs.

Straightforward ``jax.numpy``: no kernels, no cache, no batching tricks,
no code of the system under test.  It follows GPT-2 (Radford et al. 2019;
``openai-community/gpt2-medium`` ``config.json`` for the widths): pre-LN
blocks, fused qkv, causal softmax attention scaled by 1/sqrt(head size),
tanh-approximated GELU MLP of 4x width, final LayerNorm, output head tied
to the token embedding.  Departures, which are the system's own and are
listed in the configuration files under ``departures``: rotary position
embedding (rotate-half form, theta 10000) in place of the learned position
table, no bias on the dense layers, LayerNorm epsilon 1e-6.

Parameters are the system's tree (``wte/embedding``, ``h<i>/ln1|ln2/
scale|bias``, ``h<i>/attn/qkv|proj/kernel``, ``h<i>/fc_in|fc_out/kernel``,
``ln_f/scale|bias``), cast to float32.  On a TPU a float32 product runs
at reduced precision unless told otherwise, so every entry point here runs
under ``jax.default_matmul_precision("highest")``.

The contract of every ``reference/<name>.py`` (a configuration file names
its own: ``"reference": "<name>"``), each function taking the configuration
file's contents: ``init_params``, ``logits``, ``token_nll``, ``loss``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-6
ROPE_THETA = 10000.0


def _layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _rope(x, positions):
    """x: (B, S, H, D); rotate-half rotary embedding."""
    half = x.shape[-1] // 2
    freqs = ROPE_THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _block(x, p, n_head, positions):
    b, s, d = x.shape
    hd = d // n_head
    h = _layer_norm(x, p["ln1"])
    q, k, v = jnp.split(h @ p["attn"]["qkv"]["kernel"], 3, axis=-1)
    q = _rope(q.reshape(b, s, n_head, hd), positions)
    k = _rope(k.reshape(b, s, n_head, hd), positions)
    v = v.reshape(b, s, n_head, hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = x + att.reshape(b, s, d) @ p["attn"]["proj"]["kernel"]
    h = _layer_norm(x, p["ln2"])
    h = jax.nn.gelu(h @ p["fc_in"]["kernel"], approximate=True)
    return x + h @ p["fc_out"]["kernel"]


def forward(params, input_ids, n_layer: int, n_head: int):
    """Logits (B, S, V) in float32 for token ids (B, S)."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        wte = params["wte"]["embedding"]
        b, s = input_ids.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x = wte[input_ids]
        for i in range(n_layer):
            x = _block(x, params[f"h{i}"], n_head, positions)
        return _layer_norm(x, params["ln_f"]) @ wte.T


def init_params(config: dict, seed: int):
    """The weights the server makes from ``seed``: the system's own random
    init of its ``system_config`` preset, in one jitted call.  The only
    place this file touches the system under test."""
    import numpy as np

    from distributedtensorflow_tpu import models

    cfg = getattr(models, config["system_config"])()
    return jax.jit(lambda k: models.GPTLM(cfg).init(
        k, np.zeros((1, 1), np.int32), deterministic=True)["params"])(
            jax.random.PRNGKey(seed))


def logits(params, input_ids, config: dict):
    """Next-token logits (B, S, V) in float32 for token ids (B, S)."""
    return forward(params, input_ids, config["n_layer"], config["n_head"])


def token_nll(params, batch: dict, config: dict):
    """Next-token negative log-likelihood (B, S-1) of
    ``batch["input_ids"]`` at positions 0..S-2."""
    input_ids = batch["input_ids"]
    logp = jax.nn.log_softmax(logits(params, input_ids, config)[:, :-1], -1)
    return -jnp.take_along_axis(logp, input_ids[:, 1:, None], -1)[..., 0]


def loss(params, batch: dict, config: dict):
    """Mean next-token cross-entropy over positions 0..S-2."""
    return token_nll(params, batch, config).mean()
