"""Plain float32 reference of a grouped-query decoder: the rehearsal's
second architecture (``train.py --kv-heads``), which exists only under this
rehearsal root to show that a configuration brings its own reference by
name.  Written apart from ``benchmark/reference/gpt2.py``: one head at a
time, K/V head ``h // (n_head / n_kv_head)`` serving query head ``h``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _ln(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6) * p["scale"] + p["bias"]


def _rotate(x, pos):
    """x: (S, D) of one head; rotate-half rotary embedding, theta 10000."""
    half = x.shape[-1] // 2
    ang = pos[:, None] * 10000.0 ** (-jnp.arange(half) / half)
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def _sequence(params, ids, config):
    """Logits (S, V) of one sequence of token ids (S,)."""
    n_head, n_kv = config["n_head"], config["n_kv_head"]
    d = config["n_embd"]
    hd = d // n_head
    s = ids.shape[0]
    pos = jnp.arange(s, dtype=jnp.float32)
    mask = pos[None, :] <= pos[:, None]
    wte = params["wte"]["embedding"]
    x = wte[ids]
    for i in range(config["n_layer"]):
        p = params[f"h{i}"]
        qkv = _ln(x, p["ln1"]) @ p["attn"]["qkv"]["kernel"]
        heads = []
        for h in range(n_head):
            g = h // (n_head // n_kv)
            q = _rotate(qkv[:, h * hd:(h + 1) * hd], pos)
            k = _rotate(qkv[:, d + g * hd:d + (g + 1) * hd], pos)
            v = qkv[:, d + (n_kv + g) * hd:d + (n_kv + g + 1) * hd]
            w = jnp.where(mask, q @ k.T / hd ** 0.5, -jnp.inf)
            heads.append(jax.nn.softmax(w, -1) @ v)
        x = x + jnp.concatenate(heads, -1) @ p["attn"]["proj"]["kernel"]
        h = jax.nn.gelu(_ln(x, p["ln2"]) @ p["fc_in"]["kernel"],
                        approximate=True)
        x = x + h @ p["fc_out"]["kernel"]
    return _ln(x, params["ln_f"]) @ wte.T


def logits(params, ids, config):
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
        return jax.vmap(lambda row: _sequence(params, row, config))(ids)


def init_params(config: dict, seed: int):
    import numpy as np

    from distributedtensorflow_tpu import models

    cfg = getattr(models, config["system_config"])()
    return jax.jit(lambda k: models.GPTLM(cfg).init(
        k, np.zeros((1, 1), np.int32), deterministic=True)["params"])(
            jax.random.PRNGKey(seed))


def token_nll(params, batch: dict, config: dict):
    ids = batch["input_ids"]
    logp = jax.nn.log_softmax(logits(params, ids, config)[:, :-1], -1)
    return -jnp.take_along_axis(logp, ids[:, 1:, None], -1)[..., 0]


def loss(params, batch: dict, config: dict):
    return token_nll(params, batch, config).mean()
