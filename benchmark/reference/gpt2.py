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


def loss(params, input_ids, n_layer: int, n_head: int):
    """Mean next-token cross-entropy over positions 0..S-2."""
    logits = forward(params, input_ids, n_layer, n_head)[:, :-1]
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, input_ids[:, 1:, None], -1)[..., 0]
    return nll.mean()


def greedy(params, prompt, n_new: int, n_layer: int, n_head: int):
    """Greedy continuation of one prompt, recomputing the whole forward per
    token (no cache).  Returns per new token ``(top1, top2, margin)``: the
    arg-max id, the runner-up id and the logit gap between them."""
    fwd = jax.jit(forward, static_argnums=(2, 3))
    total = len(prompt) + n_new
    ids = jnp.zeros((1, total), jnp.int32).at[0, :len(prompt)].set(
        jnp.asarray(prompt, jnp.int32))
    out = []
    for t in range(len(prompt), total):
        # fixed shape (one compile): positions >= t hold zeros, which a
        # causal model cannot see from position t-1
        logits = fwd(params, ids, n_layer, n_head)[0, t - 1]
        top = jnp.argsort(logits)[-2:]
        top1, top2 = int(top[1]), int(top[0])
        out.append((top1, top2, float(logits[top1] - logits[top2])))
        ids = ids.at[0, t].set(top1)
    return out
