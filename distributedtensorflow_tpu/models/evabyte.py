"""A seventh decoder family: EvaByte, a byte-level decoder whose every layer
is an EVA attention layer — exact attention inside a *tumbling* window of
``window_size`` positions, one learned-pooled summary key/value a chunk of
``chunk_size`` positions for everything before the window, one softmax over
both (Zheng et al., "Efficient Attention via Control Variates",
arXiv:2302.04542, as EvaByte's ``eva.py`` simplifies it).

The equations, as remembered (there is no network here:
``benchmark/configs/evabyte-6.5b-serve.json`` lists under ``assumed`` every
line the published ``config.json`` does not itself bear out).  ``d`` the model
width, ``H`` heads of ``D``, chunk ``c``, window ``w``, ``C = w / c``, scale
``s = D ** -0.5``, no bias anywhere:

- ``x0 = E[ids]``; pre-norm blocks ``h = x + Attn(N1(x))``, ``y = h +
  MLP(N2(h))``, the residual stream and both sums in float32
  (``fp32_skip_add``); ``N(x) = x / sqrt(mean(x^2) + eps) * (1 + g)``
  (``norm_add_unit_offset``); ``MLP(u) = (silu(u Wg) * u Wu) Wd``;
- ``q_t, k_t = R_t(u Wq), R_t(u Wk)`` a head (rotate-half over the whole
  head, ``rope_theta``), ``v_t = u Wv``; scores and the softmax in float32 on
  the stored rows (``mixedp_attn``);
- the summary of chunk ``j`` = positions ``[c j, c j + c)``, a head with
  learned ``mu_h``, ``phi_h`` of ``D``: ``k~_j = sum_m softmax_m(s <k_m,
  mu_h>) k_m``, ``v~_j = sum_m softmax_m(s <k_m, phi_h>) v_m``;
- token ``t`` in window ``i = t // w`` attends keys ``m`` in ``[w i, t]`` and
  summaries ``j`` in ``[0, C i)`` under one softmax; the output is
  ``concat_h(o) Wo``.  A window's summaries are seen by later windows only,
  a closed window's exact rows by no one; under ``w`` positions this is plain
  causal attention;
- ``logits = N_out(x_L) W_head`` in float32, ``W_head`` ``d x (heads x V)``:
  columns ``[V j, V (j + 1))`` predict byte ``t + 1 + j``.  The served byte is
  head 0's: :func:`head` multiplies by the first ``V`` columns only (the other
  ``num_pred_heads - 1`` blocks, published for multi-byte drafting, are held
  and not read).

**What is kept** (:attr:`EvaByteConfig.cache_rows`, an
``ops.attention.EvaRows``): every layer caches the K/V pair a token in a ring
of one window (group ``"window"``, reused in place when the window closes)
AND one summary pair a chunk in a pool that only grows (group ``"full"``, a
row a ``chunk_size`` tokens) — a layer in two cache groups at two rates.  The
block is written once and calls ``attend(q, k, v, mu=, phi=)``, the one hook
its caller owns.  Parameters are a plain tree of arrays created in bfloat16.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops.attention import EvaRows, chunk_summaries, softmax_over_parts
from .afmoe import _uniform, swiglu
from .afmoe import rms_norm as _rms_norm
from .gpt import rope, rope_tables

__all__ = ["EvaByteConfig", "evabyte_tiny", "evabyte_6_5b", "init_params",
           "block", "embed", "head", "forward"]


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    vocab_size: int
    hidden_size: int
    num_heads: int
    head_dim: int
    intermediate_size: int
    num_layers: int
    chunk_size: int = 16
    window_size: int = 2048
    num_pred_heads: int = 8         # blocks of ``vocab_size`` head columns
    rope_theta: float = 1e5
    norm_eps: float = 1e-5
    max_seq: int = 32768
    dtype: jnp.dtype = jnp.bfloat16
    #: "auto" = the Pallas kernels on a TPU, the plain formulations elsewhere
    kernel_impl: str = "auto"

    def window_of(self, layer: int) -> int:
        """The tumbling window of ``layer`` (every layer's)."""
        return self.window_size

    def groups_of(self, layer: int) -> tuple[str, ...]:
        """The cache groups ``layer``'s rows live in: both, at two rates."""
        rows = self.cache_rows
        return (rows.token_group, rows.summary_group)

    @property
    def cache_rows(self) -> EvaRows:
        """What a layer caches, and in which group (``ops.attention``)."""
        return EvaRows(self.num_heads, self.head_dim, self.chunk_size,
                       self.window_size)


def evabyte_tiny(**kw) -> EvaByteConfig:
    """CPU tests only: the mechanism at toy widths — 3 layers, 4 heads of
    16, chunks of 4 in windows of 16."""
    return EvaByteConfig(**{**dict(
        vocab_size=320, hidden_size=64, num_heads=4, head_dim=16,
        intermediate_size=128, num_layers=3, chunk_size=4, window_size=16,
        num_pred_heads=2, max_seq=128), **kw})


def evabyte_6_5b() -> EvaByteConfig:
    """EvaByte 6.5B at its published widths, cut in depth only: 8 of its 32
    layers (every layer is the same kind), 32 heads of 128, chunks of 16 in
    windows of 2,048, the 320 x 8 head, 32,768 positions
    (``benchmark/configs/evabyte-6.5b-serve.json``)."""
    return EvaByteConfig(
        vocab_size=320, hidden_size=4096, num_heads=32, head_dim=128,
        intermediate_size=11008, num_layers=8, chunk_size=16,
        window_size=2048, num_pred_heads=8, rope_theta=1e5, norm_eps=1e-5,
        max_seq=32768)


# -- parameters --------------------------------------------------------------

def init_params(cfg: EvaByteConfig, key, std: float = 0.02):
    """Random parameters, one jitted draw a tensor (``afmoe.init_params``'s
    scheme: exact arithmetic on uniform bits, so the CPU and the chip make
    the same values from one key).  The norms' offsets ``g`` are drawn
    around 0 (the scale is ``1 + g``) and ``mu``, ``phi`` of standard
    deviation 1, so that a chunk's pooling weights are far from uniform and
    neither is invisible to a test."""
    d, dt = cfg.hidden_size, cfg.dtype
    qd, f = cfg.num_heads * cfg.head_dim, cfg.intermediate_size
    counter = iter(range(1 << 30))

    def draw(shape, scale=std):
        return _uniform(jax.random.fold_in(key, next(counter)), shape, scale,
                        dt)

    params = {"wte": draw((cfg.vocab_size, d))}
    for i in range(cfg.num_layers):
        params[f"h{i}"] = {
            "ln_1": draw((d,), 0.05), "ln_2": draw((d,), 0.05),
            "attn": {"wqkv": draw((d, 3 * qd)), "wo": draw((qd, d)),
                     "mu": draw((cfg.num_heads, cfg.head_dim), 1.0),
                     "phi": draw((cfg.num_heads, cfg.head_dim), 1.0)},
            "mlp": {"w_gate": draw((d, f)), "w_up": draw((d, f)),
                    "w_down": draw((f, d))}}
    params["ln_f"] = draw((d,), 0.05)
    params["head"] = draw((d, cfg.num_pred_heads * cfg.vocab_size))
    return params


# -- layer functions ---------------------------------------------------------

def rms_norm(x, offset, eps, dtype):
    """RMSNorm of float32 ``x`` with the scale ``1 + offset``, in ``dtype``."""
    return _rms_norm(x, 1.0 + offset.astype(jnp.float32), eps).astype(dtype)


def _attention_inputs(p, u, cfg: EvaByteConfig, positions):
    """``u`` (T, d), ``positions`` (T,) -> q, k, v (T, H, D): rotary on q and
    k over the whole head."""
    t, qd = u.shape[0], cfg.num_heads * cfg.head_dim
    with jax.named_scope("qkv"):
        qkv = jnp.dot(u, p["wqkv"])
        q, k, v = (qkv[:, i * qd:(i + 1) * qd].reshape(
            t, cfg.num_heads, cfg.head_dim) for i in range(3))
    with jax.named_scope("rope"):
        tabs = rope_tables(positions[None], cfg.head_dim, cfg.rope_theta,
                           q.dtype)
        q = rope(q[None], positions[None], cfg.rope_theta, tabs)[0]
        k = rope(k[None], positions[None], cfg.rope_theta, tabs)[0]
    return q, k, v


def block(p, x, cfg: EvaByteConfig, layer: int, positions, attend,
          token_mask=None):
    """One decoder layer on the float32 stream ``x`` (T, d).  ``attend(q, k,
    v, mu=, phi=) -> (T, H, D)`` is the caller's: it owns where the token
    rows and the chunk summaries live and what a query sees of them.
    ``token_mask`` is taken for the other families' sake (no layer routes).
    Returns ``(x, None)``."""
    t, dt = x.shape[0], cfg.dtype
    with jax.named_scope("ln"):
        u = rms_norm(x, p["ln_1"], cfg.norm_eps, dt)
    with jax.named_scope("eva_attn"):
        a = p["attn"]
        q, k, v = _attention_inputs(a, u, cfg, positions)
        o = attend(q, k, v, mu=a["mu"], phi=a["phi"])
        with jax.named_scope("proj"):
            x = x + jnp.dot(o.reshape(t, -1).astype(dt), a["wo"],
                            preferred_element_type=jnp.float32)
    with jax.named_scope("ln"):
        u = rms_norm(x, p["ln_2"], cfg.norm_eps, dt)
    with jax.named_scope("mlp"):
        return x + swiglu(p["mlp"], u).astype(jnp.float32), None


def embed(params, ids, cfg: EvaByteConfig):
    with jax.named_scope("embed"):
        return params["wte"][ids].astype(jnp.float32)


def head(params, x, cfg: EvaByteConfig):
    """float32 logits (T, V) of the next byte: the first ``V`` of the head's
    ``num_pred_heads x V`` columns."""
    with jax.named_scope("head"):
        return jnp.dot(
            rms_norm(x, params["ln_f"], cfg.norm_eps, cfg.dtype),
            params["head"][:, :cfg.vocab_size],
            preferred_element_type=jnp.float32)


def dense_attend(cfg: EvaByteConfig):
    """``attend`` over a whole sequence from position 0, nothing cached: one
    masked softmax over the sequence's own rows (causal, the query's window
    only) and the summaries of its whole chunks (the earlier windows')."""
    c, w = cfg.chunk_size, cfg.window_size

    def attend(q, k, v, *, mu, phi):
        n = q.shape[0] // c
        pos = jnp.arange(q.shape[0])
        first = pos // w * w                    # of each query's window
        sk, sv = chunk_summaries(k[:n * c], v[:n * c], mu, phi, c)
        return softmax_over_parts(q, [
            (k, v,
             (pos[None] <= pos[:, None]) & (pos[None] >= first[:, None])),
            (sk, sv, jnp.arange(n)[None] < (first // c)[:, None])])

    return attend


def forward(params, ids, cfg: EvaByteConfig):
    """Logits (B, S, V) of whole sequences ``ids`` (B, S), nothing cached:
    the same block under :func:`dense_attend`."""
    def one(seq):
        positions = jnp.arange(seq.shape[0], dtype=jnp.int32)
        x = embed(params, seq, cfg)
        for i in range(cfg.num_layers):
            with jax.named_scope(f"h{i}"):
                x, _ = block(params[f"h{i}"], x, cfg, i, positions,
                             dense_attend(cfg))
        return head(params, x, cfg)
    return jax.lax.map(one, ids)
