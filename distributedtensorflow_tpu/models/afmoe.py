"""A second decoder family: gated grouped-query attention in sandwich norms,
window layers between full ones, a wide sigmoid router over SwiGLU experts.

The layer equations are those of ``transformers``' ``modeling_afmoe.py``
(Arcee Trinity; the widths of a preset come from the model's
``config.json``).  ``d`` the model width, ``H`` query heads and ``Hkv`` K/V
heads of ``D`` (``H * D`` need not be ``d``), RMSNorm everywhere, no biases:

- embedding ``x = E[ids] * sqrt(d)`` (``mup_enabled``); untied head
  ``logits = RMSNorm(x) Wu``;
- block, four norms: ``x = x + N_post_attn(Attn(N_in(x)))`` then ``x = x +
  N_post_mlp(FFN(N_pre_mlp(x)))``;
- attention: ``q, k, v, g = h Wq, h Wk, h Wv, h Wg``; RMSNorm over each head
  of q and of k (a learned scale of ``D``); rotary (rotate-half) on q and k
  **on ``sliding_attention`` layers only**; softmax attention at scale
  ``D**-0.5``, causal, on sliding layers over keys ``j`` with ``i - window <
  j <= i``; output ``(a * sigmoid(g)) Wo``;
- FFN of the first ``num_dense_layers`` layers: SwiGLU ``(silu(h Wgate) *
  h Wup) Wdown``;
- FFN of the others: ``s = sigmoid(h Wr)`` in float32 over the router's full
  width, the top ``k`` of ``s + b`` (``b`` a selection bias, used to select
  only), weights ``s[top] / (sum + 1e-20) * route_scale``, ``y = Shared(h)
  + sum_j w_j Expert_top_j(h)``, every expert a SwiGLU.

One definition of the block (:func:`block`) serves every caller: it takes
an ``attend(q, k, v)`` callback, so the dense forward below, the chunked
prefill and the paged decode of ``serve.model`` differ only in where K/V
live.  Parameters are a plain tree of arrays **created in bfloat16** (the
router in float32: its arithmetic is float32).  A configuration may *hold*
only ``experts_held`` of the published experts, from ``expert_first``: one
chip's share of an expert-parallel deployment; the router keeps its full
width and the absent experts' terms are left out
(``parallel.moe.dropless_moe``).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from ..ops.attention import KVRows, xla_attention
from ..parallel.moe import dropless_moe
from .gpt import rope, rope_tables

__all__ = ["AfmoeConfig", "afmoe_tiny", "trinity_large_ep8", "init_params",
           "block", "embed", "head", "forward"]

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int          # dense SwiGLU width
    moe_intermediate_size: int      # expert (and shared expert) width
    num_experts: int                # the router's width, as published
    experts_per_token: int
    layer_types: tuple[str, ...]    # one attention kind a layer
    num_dense_layers: int = 1
    experts_held: int | None = None  # None = all of them
    expert_first: int = 0
    sliding_window: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    route_scale: float = 1.0
    route_norm: bool = True
    mup_enabled: bool = True
    max_seq: int = 8192
    dtype: jnp.dtype = jnp.bfloat16
    #: "auto" = the Pallas kernels on a TPU, the plain formulations elsewhere
    kernel_impl: str = "auto"

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads

    @property
    def held(self) -> tuple[int, int]:
        return (self.expert_first, self.experts_held or self.num_experts)

    def window_of(self, layer: int) -> int | None:
        return (self.sliding_window if self.layer_types[layer] == SLIDING
                else None)

    @property
    def cache_rows(self) -> KVRows:
        """What a served layer caches a token (``ops.attention``)."""
        return KVRows(self.num_heads, self.num_kv_heads, self.head_dim)


def afmoe_tiny(**kw) -> AfmoeConfig:
    """CPU tests only: every mechanism of the family at toy widths (window
    32; a router 16 wide of whose experts 8 are held, from the fifth)."""
    return AfmoeConfig(**{**dict(
        vocab_size=128, hidden_size=64, num_heads=4, num_kv_heads=2,
        head_dim=32, intermediate_size=128, moe_intermediate_size=64,
        num_experts=16, experts_per_token=4, experts_held=8, expert_first=4,
        layer_types=(SLIDING, SLIDING, FULL), num_dense_layers=1,
        sliding_window=32, route_scale=2.448, max_seq=128), **kw})


def trinity_large_ep8() -> AfmoeConfig:
    """Trinity-Large-Preview at its published widths, cut to one chip's
    share of an 8-chip expert-parallel deployment: one dense layer, one
    whole period ``sliding x3, full`` of expert layers holding 32 of the
    256 routed experts, an eighth of the vocabulary
    (``benchmark/configs/trinity-large-ep8-serve.json``)."""
    return AfmoeConfig(
        vocab_size=25024, hidden_size=3072, num_heads=48, num_kv_heads=8,
        head_dim=128, intermediate_size=12288, moe_intermediate_size=3072,
        num_experts=256, experts_per_token=4, experts_held=32,
        layer_types=(SLIDING, SLIDING, SLIDING, SLIDING, FULL),
        num_dense_layers=1, sliding_window=4096, route_scale=2.448,
        max_seq=8192)


# -- parameters --------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _uniform(key, shape, std, dtype):
    """Uniform with standard deviation ``std``: bit operations and exact
    float32 arithmetic only, so the CPU and the chip make the same values
    from one key (a normal's inverse error function need not agree)."""
    a = std * math.sqrt(3.0)
    return jax.random.uniform(key, shape, jnp.float32, -a, a).astype(dtype)


def init_params(cfg: AfmoeConfig, key, std: float = 0.02):
    """Random parameters, one jitted draw a tensor so that no float32 copy
    of the whole model ever exists.  Norm scales are drawn around 1 and the
    selection bias around 0, so that neither is invisible to a test."""
    d, dt = cfg.hidden_size, cfg.dtype
    qd, kvd = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    m, held = cfg.moe_intermediate_size, cfg.held[1]
    counter = iter(range(1 << 30))

    def draw(shape, dtype=dt, scale=std):
        return _uniform(jax.random.fold_in(key, next(counter)), shape, scale,
                        dtype)

    def norm(n):
        return (1.0 + draw((n,), jnp.float32, 0.05)).astype(dt)

    params = {"wte": draw((cfg.vocab_size, d))}
    for i in range(cfg.num_layers):
        p = {"ln_in": norm(d), "ln_post_attn": norm(d),
             "ln_pre_mlp": norm(d), "ln_post_mlp": norm(d),
             "attn": {"wqkvg": draw((d, 2 * qd + 2 * kvd)),
                      "wo": draw((qd, d)),
                      "q_norm": norm(cfg.head_dim),
                      "k_norm": norm(cfg.head_dim)}}
        if i < cfg.num_dense_layers:
            f = cfg.intermediate_size
            p["mlp"] = {"w_gate": draw((d, f)), "w_up": draw((d, f)),
                        "w_down": draw((f, d))}
        else:
            p["moe"] = {
                # bf16-valued like every matrix, held in float32
                "router": draw((d, cfg.num_experts)).astype(jnp.float32),
                "bias": draw((cfg.num_experts,), jnp.float32, 0.05),
                "shared": {"w_gate": draw((d, m)), "w_up": draw((d, m)),
                           "w_down": draw((m, d))},
                "experts": {"w_gate": draw((held, d, m)),
                            "w_up": draw((held, d, m)),
                            "w_down": draw((held, m, d))}}
        params[f"h{i}"] = p
    params["ln_f"] = norm(d)
    params["head"] = draw((d, cfg.vocab_size))
    return params


# -- layer functions ---------------------------------------------------------

def rms_norm(x, scale, eps):
    """RMSNorm with float32 statistics, back in ``x``'s type."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def swiglu(p, h):
    g = jnp.dot(h, p["w_gate"], preferred_element_type=jnp.float32)
    u = jnp.dot(h, p["w_up"], preferred_element_type=jnp.float32)
    return jnp.dot((jax.nn.silu(g) * u).astype(h.dtype), p["w_down"])


def attention_inputs(p, h, cfg: AfmoeConfig, positions, rotary: bool):
    """``h`` (T, d), ``positions`` (T,) -> q (T, H, D), k, v (T, Hkv, D)
    and the gate (T, H * D): per-head RMSNorm on q and k, rotary only
    where ``rotary``."""
    t = h.shape[0]
    qd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    proj = jnp.dot(h, p["wqkvg"])
    q = proj[:, :qd].reshape(t, cfg.num_heads, cfg.head_dim)
    k = proj[:, qd:qd + kvd].reshape(t, cfg.num_kv_heads, cfg.head_dim)
    v = proj[:, qd + kvd:qd + 2 * kvd].reshape(
        t, cfg.num_kv_heads, cfg.head_dim)
    gate = proj[:, qd + 2 * kvd:]
    q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
    k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
    if rotary:
        tabs = rope_tables(positions[None], cfg.head_dim, cfg.rope_theta,
                           q.dtype)
        q = rope(q[None], positions[None], cfg.rope_theta, tabs)[0]
        k = rope(k[None], positions[None], cfg.rope_theta, tabs)[0]
    return q, k, v, gate


def block(p, x, cfg: AfmoeConfig, layer: int, positions, attend,
          token_mask=None):
    """One decoder layer on ``x`` (T, d).  ``attend(q, k, v)`` returns the
    attention output (T, H, D) — it owns where K/V live.  Returns ``(x,
    counters)``; ``counters`` is ``None`` on a dense layer, else the expert
    layer's ``pairs``, ``experts_hit``, ``max_load``."""
    sliding = cfg.layer_types[layer] == SLIDING
    eps = cfg.rms_norm_eps
    with jax.named_scope("ln"):
        h = rms_norm(x, p["ln_in"], eps)
    with jax.named_scope("qkv"):
        q, k, v, gate = attention_inputs(p["attn"], h, cfg, positions,
                                         rotary=sliding)
    with jax.named_scope("window_attn" if sliding else "full_attn"):
        a = attend(q, k, v).reshape(x.shape[0], -1).astype(x.dtype)
    with jax.named_scope("attn_gate"):
        a = a * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(x.dtype)
    with jax.named_scope("proj"):
        x = x + rms_norm(jnp.dot(a, p["attn"]["wo"]), p["ln_post_attn"], eps)
    with jax.named_scope("ln"):
        h = rms_norm(x, p["ln_pre_mlp"], eps)
    counters = None
    if layer < cfg.num_dense_layers:
        with jax.named_scope("mlp"):
            f = swiglu(p["mlp"], h)
    else:
        moe = p["moe"]
        routed, counters = dropless_moe(
            h, moe["router"], moe["bias"], moe["experts"], held=cfg.held,
            top_k=cfg.experts_per_token, route_norm=cfg.route_norm,
            route_scale=cfg.route_scale, token_mask=token_mask,
            impl=cfg.kernel_impl)
        with jax.named_scope("shared_expert"):
            f = swiglu(moe["shared"], h) + routed
    with jax.named_scope("ln"):
        return x + rms_norm(f, p["ln_post_mlp"], eps), counters


def embed(params, ids, cfg: AfmoeConfig):
    with jax.named_scope("embed"):
        x = params["wte"][ids]
        if cfg.mup_enabled:
            x = (x.astype(jnp.float32)
                 * math.sqrt(cfg.hidden_size)).astype(x.dtype)
        return x


def head(params, x, cfg: AfmoeConfig):
    """float32 logits of ``x`` (T, d)."""
    with jax.named_scope("head"):
        return jnp.dot(rms_norm(x, params["ln_f"], cfg.rms_norm_eps),
                       params["head"], preferred_element_type=jnp.float32)


def forward(params, ids, cfg: AfmoeConfig):
    """Logits (B, S, V) of whole sequences ``ids`` (B, S), no cache: the
    same block under dense causal (and windowed) attention."""
    def one(seq):
        s = seq.shape[0]
        positions = jnp.arange(s, dtype=jnp.int32)
        x = embed(params, seq, cfg)
        for i in range(cfg.num_layers):
            def attend(q, k, v, w=cfg.window_of(i)):
                return xla_attention(q[None], k[None], v[None], causal=True,
                                     window=w)[0]
            with jax.named_scope(f"h{i}"):
                x, _ = block(params[f"h{i}"], x, cfg, i, positions, attend)
        return head(params, x, cfg)
    return jax.lax.map(one, ids)
