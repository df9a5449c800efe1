"""A sixth decoder family: gated short-convolution layers (a depthwise
causal convolution of three taps between two multiplicative gates, which
keeps two rows a sequence and nothing a token) with a grouped-query attention
layer every fourth, a sigmoid router over SwiGLU experts in every layer past
the leading dense ones.

The layer equations are those of ``transformers``' ``modeling_lfm2_moe.py``
(LiquidAI LFM2-MoE; the widths of a preset come from the model's
``config.json``), as remembered: ``benchmark/configs/lfm2-24b-a2b-serve.json``
lists under ``assumed`` every line the ``config.json`` does not itself bear
out.  ``d`` the model width, ``K = conv_L_cache`` taps, RMSNorm with a
learned scale and ``norm_eps`` everywhere, no bias anywhere (``conv_bias``
false), pre-norm blocks:

- embedding ``x = E[ids]`` (no scale); tied head ``logits = RMSNorm(x) E^T``;
- block ``i``: ``x = x + Op_i(N_op(x))`` then ``x = x + FFN_i(N_ffn(x))``;
- ``Op_i`` on a ``conv`` layer: ``[B | C | u] = h W_in`` (``d -> 3 d``, split
  in that order); ``g = B * u``; ``c_t = sum_k w_k * g_{t - (K - 1) + k}`` a
  channel (``g`` before the sequence's start is zero); output ``(C * c)
  W_out``.  No activation function;
- ``Op_i`` on a ``full_attention`` layer: ``q, k, v = h Wq, h Wk, h Wv``
  (``H`` query heads on ``Hkv`` K/V heads of ``D``); RMSNorm over each head
  of q and of k (a learned scale of ``D``) **before** rotary; rotary
  (rotate-half, ``rope_theta``, the whole head) on q and k; causal softmax at
  scale ``D ** -0.5``; output ``concat(o) Wo``;
- ``FFN_i`` of the first ``num_dense_layers`` layers: SwiGLU ``(silu(h
  Wgate) * h Wup) Wdown`` of ``intermediate_size``;
- ``FFN_i`` of the others: ``s = sigmoid(h Wr)`` in float32, the top ``k`` of
  ``s + b`` (``expert_bias``, selects only), weights ``s[top] / (sum s[top] +
  1e-6) * routed_scaling_factor``, ``y = sum_j w_j Expert_top_j(h)``, every
  expert a SwiGLU of ``moe_intermediate_size``; no shared expert.

**What is kept**: an attention layer caches K (after its norm and rotation)
and V a token (:attr:`Lfm2Config.cache_rows`: 8 K/V heads of 64, 2,048 B a
token a layer at the published widths); a conv layer keeps the last ``K - 1``
*gated inputs* ``g`` a sequence (:attr:`Lfm2Config.state_rows`, an
``ops.ssm.ConvTail``: two rows of 2048, 8 KB a layer) and has no scan.  The
block is written once and calls ``mixer``, the one hook its caller owns:
``mixer(q, k, v)`` on an attention layer, ``mixer.conv(g, w, b, scope=)`` on
a conv layer — the caller owns where K/V and the tail live and how many of
the tokens are real.  Every expert is held (``held = (0, num_experts)``):
the published model's experts fit one chip a layer, so no share of a
deployment is taken.  Parameters are a plain tree of arrays created in
bfloat16; the router and ``expert_bias`` in float32, as the router computes.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops.attention import KVRows, xla_attention
from ..ops.ssm import ConvTail, causal_conv
from ..parallel.moe import dropless_moe
from .afmoe import _uniform, rms_norm, swiglu
from .gpt import rope, rope_tables

__all__ = ["Lfm2Config", "lfm2_tiny", "lfm2_24b_a2b", "init_params", "block",
           "embed", "head", "forward"]

CONV, FULL = "conv", "full_attention"

#: what the published router adds to the sum of a token's top scores before
#: it divides by it
ROUTE_NORM_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int          # dense SwiGLU width
    moe_intermediate_size: int      # expert width
    num_experts: int
    experts_per_token: int
    layer_types: tuple[str, ...]    # "conv" or "full_attention" a layer
    num_dense_layers: int = 2
    conv_kernel: int = 3            # the config's conv_L_cache
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    route_norm: bool = True
    route_scale: float = 1.0
    max_seq: int = 8192
    dtype: jnp.dtype = jnp.bfloat16
    #: "auto" = the Pallas kernels on a TPU, the plain formulations elsewhere
    kernel_impl: str = "auto"

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def held(self) -> tuple[int, int]:
        return (0, self.num_experts)

    def keeps_state(self, layer: int) -> bool:
        """Whether ``layer`` is a conv layer (keeps a tail a sequence) and
        not an attention layer (caches rows a token)."""
        return self.layer_types[layer] == CONV

    def window_of(self, layer: int) -> None:
        return None

    @property
    def cache_rows(self) -> KVRows:
        """What an attention layer caches a token (``ops.attention``)."""
        return KVRows(self.num_heads, self.num_kv_heads, self.head_dim)

    @property
    def state_rows(self) -> ConvTail:
        """What a conv layer keeps a sequence (``ops.ssm``)."""
        return ConvTail(self.hidden_size, self.conv_kernel)


def lfm2_tiny(**kw) -> Lfm2Config:
    """CPU tests only: every mechanism of the family at toy widths — a dense
    conv layer, then an attention layer (4 query heads on 2 K/V heads) and
    two conv layers with 8 experts top 2."""
    return Lfm2Config(**{**dict(
        vocab_size=128, hidden_size=64, num_heads=4, num_kv_heads=2,
        head_dim=16, intermediate_size=128, moe_intermediate_size=32,
        num_experts=8, experts_per_token=2,
        layer_types=(CONV, FULL, CONV, CONV), num_dense_layers=1,
        max_seq=128), **kw})


def lfm2_24b_a2b() -> Lfm2Config:
    """LFM2-24B-A2B at its published widths, cut in depth only: layer 0 (conv,
    the dense SwiGLU of 11,776: the two leading dense layers count once) and
    published layers 2-9 — attention, conv x3, attention, conv x3: two whole
    periods — each with all 64 experts of 1536, top 4; 32 query heads on 8
    K/V heads of 64, the whole vocabulary, the embedding tied to the head
    (``benchmark/configs/lfm2-24b-a2b-serve.json``)."""
    return Lfm2Config(
        vocab_size=65536, hidden_size=2048, num_heads=32, num_kv_heads=8,
        head_dim=64, intermediate_size=11776, moe_intermediate_size=1536,
        num_experts=64, experts_per_token=4,
        layer_types=(CONV, FULL, CONV, CONV, CONV, FULL, CONV, CONV, CONV),
        num_dense_layers=1, conv_kernel=3, rope_theta=1e6, norm_eps=1e-5,
        route_norm=True, route_scale=1.0, max_seq=9216)


# -- parameters --------------------------------------------------------------

def init_params(cfg: Lfm2Config, key, std: float = 0.02):
    """Random parameters, one jitted draw a tensor (``afmoe.init_params``'s
    scheme: exact arithmetic on uniform bits, so the CPU and the chip make
    the same values from one key).  Norm scales are drawn around 1 and the
    selection bias around 0, so that neither is invisible to a test; the
    convolution's taps of standard deviation 0.3, as jamba's."""
    d, dt = cfg.hidden_size, cfg.dtype
    qd, kvd = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    m, e = cfg.moe_intermediate_size, cfg.num_experts
    f32 = jnp.float32
    counter = iter(range(1 << 30))

    def draw(shape, dtype=dt, scale=std):
        return _uniform(jax.random.fold_in(key, next(counter)), shape, scale,
                        dtype)

    def norm(width):
        return (1.0 + draw((width,), f32, 0.05)).astype(dt)

    params = {"wte": draw((cfg.vocab_size, d))}
    for i in range(cfg.num_layers):
        p = {"ln_op": norm(d), "ln_ffn": norm(d)}
        if cfg.keeps_state(i):
            p["conv"] = {"w_in": draw((d, 3 * d)),
                         "conv_w": draw((cfg.conv_kernel, d), scale=0.3),
                         "w_out": draw((d, d))}
        else:
            p["attn"] = {"wqkv": draw((d, qd + 2 * kvd)),
                         "wo": draw((qd, d)),
                         "q_norm": norm(cfg.head_dim),
                         "k_norm": norm(cfg.head_dim)}
        if i < cfg.num_dense_layers:
            f = cfg.intermediate_size
            p["mlp"] = {"w_gate": draw((d, f)), "w_up": draw((d, f)),
                        "w_down": draw((f, d))}
        else:
            p["moe"] = {
                # bf16-valued like every matrix, held in float32
                "router": draw((d, e)).astype(f32),
                "bias": draw((e,), f32, 0.05),
                "experts": {"w_gate": draw((e, d, m)),
                            "w_up": draw((e, d, m)),
                            "w_down": draw((e, m, d))}}
        params[f"h{i}"] = p
    params["ln_f"] = norm(d)
    return params


# -- layer functions ---------------------------------------------------------

def _conv_op(p, h, cfg: Lfm2Config, state):
    """The gated short convolution on ``h`` (T, d).  ``state.conv`` is the
    caller's: it reads and writes the sequence's tail of gated inputs."""
    d = cfg.hidden_size
    with jax.named_scope("conv"):
        with jax.named_scope("in_proj"):
            bcu = jnp.dot(h, p["w_in"])
        with jax.named_scope("gate_in"):
            g = bcu[:, :d] * bcu[:, 2 * d:]
    c = state.conv(g, p["conv_w"], jnp.zeros((), jnp.float32), scope="conv")
    with jax.named_scope("conv"):
        with jax.named_scope("gate_out"):
            y = bcu[:, d:2 * d] * c
        with jax.named_scope("out_proj"):
            return jnp.dot(y, p["w_out"])


def _attention_inputs(p, h, cfg: Lfm2Config, positions):
    """``h`` (T, d), ``positions`` (T,) -> q (T, H, D), k, v (T, Hkv, D):
    per-head RMSNorm on q and k, then rotary on both."""
    t = h.shape[0]
    qd, kvd = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    with jax.named_scope("qkv"):
        qkv = jnp.dot(h, p["wqkv"])
        q = qkv[:, :qd].reshape(t, cfg.num_heads, cfg.head_dim)
        k = qkv[:, qd:qd + kvd].reshape(t, cfg.num_kv_heads, cfg.head_dim)
        v = qkv[:, qd + kvd:].reshape(t, cfg.num_kv_heads, cfg.head_dim)
    with jax.named_scope("qk_norm"):
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    with jax.named_scope("rope"):
        tabs = rope_tables(positions[None], cfg.head_dim, cfg.rope_theta,
                           q.dtype)
        q = rope(q[None], positions[None], cfg.rope_theta, tabs)[0]
        k = rope(k[None], positions[None], cfg.rope_theta, tabs)[0]
    return q, k, v


def block(p, x, cfg: Lfm2Config, layer: int, positions, mixer,
          token_mask=None):
    """One decoder layer on ``x`` (T, d).  ``mixer`` is the caller's hook:
    ``mixer(q, k, v) -> (T, H, D)`` on an attention layer (it owns where K/V
    live), the state's ``conv`` on a conv layer.  ``token_mask`` (T,) marks
    the real tokens: the others reach no expert and count in no counter.
    Returns ``(x, counters)``; ``counters`` is ``None`` on a dense layer,
    else the expert layer's ``pairs``, ``experts_hit``, ``max_load``."""
    eps = cfg.norm_eps
    t = x.shape[0]
    with jax.named_scope("ln"):
        h = rms_norm(x, p["ln_op"], eps)
    if cfg.keeps_state(layer):
        x = x + _conv_op(p["conv"], h, cfg, mixer)
    else:
        with jax.named_scope("attn"):
            q, k, v = _attention_inputs(p["attn"], h, cfg, positions)
            a = mixer(q, k, v).reshape(t, -1).astype(x.dtype)
            with jax.named_scope("proj"):
                x = x + jnp.dot(a, p["attn"]["wo"])
    with jax.named_scope("ln"):
        h = rms_norm(x, p["ln_ffn"], eps)
    if layer < cfg.num_dense_layers:
        with jax.named_scope("mlp"):
            return x + swiglu(p["mlp"], h), None
    moe = p["moe"]
    routed, counters = dropless_moe(
        h, moe["router"], moe["bias"], moe["experts"], held=cfg.held,
        top_k=cfg.experts_per_token, route_norm=cfg.route_norm,
        route_scale=cfg.route_scale, route_norm_eps=ROUTE_NORM_EPS,
        token_mask=token_mask, impl=cfg.kernel_impl)
    return x + routed, counters


def embed(params, ids, cfg: Lfm2Config):
    with jax.named_scope("embed"):
        return params["wte"][ids]


def head(params, x, cfg: Lfm2Config):
    """float32 logits of ``x`` (T, d): the embedding, tied."""
    with jax.named_scope("head"):
        return jax.lax.dot_general(
            rms_norm(x, params["ln_f"], cfg.norm_eps), params["wte"],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


class _FreshState:
    """The state hook of a whole sequence from a zero tail, nothing kept
    (``forward``)."""

    def __init__(self, cfg: Lfm2Config):
        self.rows = cfg.state_rows

    def conv(self, g, w, b, scope=None):
        tail = jnp.zeros(((self.rows.d_conv - 1) * g.shape[1],), g.dtype)
        return causal_conv(g, tail, w, b, g.shape[0])[0]


def forward(params, ids, cfg: Lfm2Config):
    """Logits (B, S, V) of whole sequences ``ids`` (B, S), nothing cached:
    the same block under dense causal attention and a convolution from a
    zero tail."""
    def one(seq):
        positions = jnp.arange(seq.shape[0], dtype=jnp.int32)
        x = embed(params, seq, cfg)
        for i in range(cfg.num_layers):
            def attend(q, k, v):
                return xla_attention(q[None], k[None], v[None],
                                     causal=True)[0]
            mixer = _FreshState(cfg) if cfg.keeps_state(i) else attend
            with jax.named_scope(f"h{i}"):
                x, _ = block(params[f"h{i}"], x, cfg, i, positions, mixer)
        return head(params, x, cfg)
    return jax.lax.map(one, ids)
