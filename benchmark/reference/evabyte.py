"""Plain float32 reference of EvaByte (a byte-level decoder whose every layer
is an EVA attention layer: exact attention inside a tumbling window, one
learned-pooled summary key/value a chunk for everything before it, one
softmax over both).

Straightforward ``jax.numpy``: whole sequences, no cache, no ring, no pool, no
kernel, no chunked prefill, no code of the system under test but its random
initialiser (``init_params``, imported there and nowhere else: the parameter
tree's layout is all this file shares with ``models/evabyte.py``).  The
equations are those of EvaByte's ``eva.py`` as remembered (there is no
network here), as the configuration file lists them under ``assumed``; ``d``
``hidden_size``, ``H`` ``num_attention_heads`` of ``D = d / H``, ``c``
``chunk_size``, ``w`` ``window_size``, ``C = w / c``, ``s = D ** -0.5``:

- ``x0 = E[ids]``; ``h = x + Attn(N1(x))``, ``y = h + MLP(N2(h))``; ``N(x) =
  x / sqrt(mean(x^2) + rms_norm_eps) * (1 + g)`` (``norm_add_unit_offset``);
  ``MLP(u) = (silu(u Wg) * u Wu) Wd``; ``logits = N_out(x_L) W_head[:, :V]``
  (the first of the head's ``num_pred_heads`` blocks of ``V`` columns: the
  next byte's);
- ``q_t, k_t = R_t(u Wq), R_t(u Wk)`` a head (rotate-half over the whole
  head, ``rope_theta``), ``v_t = u Wv``;
- the summary of chunk ``j`` = positions ``[c j, c j + c)``, a head:
  ``k~_j = sum_m softmax_m(s <k_m, mu_h>) k_m``, ``v~_j = sum_m softmax_m(s
  <k_m, phi_h>) v_m`` — here by a reshape of the whole sequence to ``(chunks,
  c)``;
- token ``t`` in window ``i = t // w`` attends keys ``m`` in ``[w i, t]`` and
  summaries ``j`` in ``[0, C i)``: ONE masked softmax over ``[local |
  summaries]``; output ``concat_h(o) Wo``.

Weights are the server's own (bfloat16 values), the arithmetic float32 under
``jax.default_matmul_precision("highest")``.  Departure of the stored form
from the published one, used as it is: ``q``, ``k``, ``v`` projections are one
matrix ``wqkv``, columns ``[q | k | v]``.  Queries are processed
``QUERY_BLOCK`` positions at a time (the same sums).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: queries whose scores are held at one time (a block of the same sum)
QUERY_BLOCK = 256


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _norm(x, offset, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * (1.0 + _f32(offset))


def _rope(x, theta):
    """x: (S, H, D); rotate-half rotary embedding at positions 0..S-1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _summaries(k, v, mu, phi, c):
    """(chunks, H, D) summary keys and values of the whole chunks of k, v
    (S, H, D): two softmax poolings of each chunk's ``c`` rows."""
    s, heads, dim = k.shape
    n = s // c
    kc = k[:n * c].reshape(n, c, heads, dim)
    vc = v[:n * c].reshape(n, c, heads, dim)
    scale = dim ** -0.5
    wk = jax.nn.softmax(jnp.einsum("nchd,hd->nch", kc, mu) * scale, axis=1)
    wv = jax.nn.softmax(jnp.einsum("nchd,hd->nch", kc, phi) * scale, axis=1)
    return (wk[..., None] * kc).sum(1), (wv[..., None] * vc).sum(1)


def _attention(p, u, config: dict):
    """u: (S, d) -> (S, d)."""
    s = u.shape[0]
    heads = config["num_attention_heads"]
    dim = config.get("head_dim") or config["hidden_size"] // heads
    c, w = config["chunk_size"], config["window_size"]
    qkv = u @ _f32(p["wqkv"])
    q, k, v = (qkv[:, i * heads * dim:(i + 1) * heads * dim].reshape(
        s, heads, dim) for i in range(3))
    q, k = _rope(q, config["rope_theta"]), _rope(k, config["rope_theta"])
    sk, sv = _summaries(k, v, _f32(p["mu"]), _f32(p["phi"]), c)
    keys = jnp.concatenate([k, sk])            # [local | summaries]
    values = jnp.concatenate([v, sv])
    m = jnp.arange(s)[None, :]
    j = jnp.arange(sk.shape[0])[None, :]

    def block(args):
        qb, t = args                    # (QUERY_BLOCK, H, D), positions
        first = (t // w * w)[:, None]
        ok = jnp.concatenate([(m >= first) & (m <= t[:, None]),
                              j < first // c], axis=1)
        scores = jnp.einsum("qhd,khd->hqk", qb, keys) * dim ** -0.5
        scores = jnp.where(ok[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), values)

    n_blocks = -(-s // QUERY_BLOCK)
    pad = n_blocks * QUERY_BLOCK - s    # padded queries attend as the last
    qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        n_blocks, QUERY_BLOCK, heads, dim)
    pos = jnp.minimum(jnp.arange(n_blocks * QUERY_BLOCK), s - 1).reshape(
        n_blocks, QUERY_BLOCK)
    att = jax.lax.map(block, (qs, pos)).reshape(
        n_blocks * QUERY_BLOCK, heads * dim)[:s]
    return att @ _f32(p["wo"])


def _mlp(p, u):
    return (jax.nn.silu(u @ _f32(p["w_gate"])) * (u @ _f32(p["w_up"]))) \
        @ _f32(p["w_down"])


def _forward_one(params, input_ids, config: dict):
    with jax.default_matmul_precision("highest"):
        eps = config["rms_norm_eps"]
        x = _f32(params["wte"])[input_ids]
        for i in range(config["num_hidden_layers"]):
            p = params[f"h{i}"]
            x = x + _attention(p["attn"], _norm(x, p["ln_1"], eps), config)
            x = x + _mlp(p["mlp"], _norm(x, p["ln_2"], eps))
        return _norm(x, params["ln_f"], eps) \
            @ _f32(params["head"][:, :config["vocab_size"]])


def forward(params, input_ids, config: dict):
    """Logits (B, S, V) in float32 for byte ids (B, S), one sequence after
    the other."""
    return jax.lax.map(lambda ids: _forward_one(params, ids, config),
                       input_ids)


def init_params(config: dict, seed: int):
    """The weights the server makes from ``seed``: the system's own random
    init of its ``system_config`` preset (bfloat16 values).  The only place
    this file touches the system under test."""
    from distributedtensorflow_tpu import models
    from distributedtensorflow_tpu.models import evabyte

    cfg = getattr(models, config["system_config"])()
    return evabyte.init_params(cfg, jax.random.PRNGKey(seed))


def logits(params, input_ids, config: dict):
    """Next-byte logits (B, S, V) in float32 for byte ids (B, S)."""
    return forward(params, input_ids, config)


def token_nll(params, batch: dict, config: dict):
    """Next-byte negative log-likelihood (B, S-1) of ``batch["input_ids"]``
    at positions 0..S-2."""
    input_ids = batch["input_ids"]
    logp = jax.nn.log_softmax(logits(params, input_ids, config)[:, :-1], -1)
    return -jnp.take_along_axis(logp, input_ids[:, 1:, None], -1)[..., 0]


def loss(params, batch: dict, config: dict):
    """Mean next-byte cross-entropy over positions 0..S-2."""
    return token_nll(params, batch, config).mean()
