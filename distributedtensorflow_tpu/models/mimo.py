"""A fifth served decoder family: window layers with a learned sink between
full ones, a K/V head count a layer kind, keys wider than values, rotary on
part of a head, a wide sigmoid router over SwiGLU experts and no shared one.

The layer equations are Xiaomi MiMo-V2's (``model_type mimo_v2``; the widths
of a preset come from the model's ``config.json``, and
``benchmark/configs/mimo-v2.5-ep16-serve.json`` lists under ``assumed`` every
line that is not a key's plain meaning).  ``d`` the model width, ``H`` query
heads, RMSNorm everywhere, no biases:

- embedding ``x = E[ids]``; untied head ``logits = RMSNorm(x) Wu``;
- block, pre-norm, two norms: ``x = x + Attn(N1(x))``, then ``x = x +
  FFN(N2(x))``;
- attention of a layer of kind t (``layer_pattern``: 0 full, 1 window), ``h =
  N1(x)``: ``q = h Wq`` (H x ``head_dim``), ``k = h Wk`` (Hkv_t x
  ``head_dim``), ``v = h Wv`` (Hkv_t x ``v_head_dim``) from one stored matrix;
  ``Hkv`` is ``num_kv_heads`` on a full layer and ``swa_num_kv_heads`` on a
  window one.  Rotary (rotate-half) on the **first** ``rotary_dim`` values of
  every head of q and k, base ``rope_theta`` on full layers and
  ``swa_rope_theta`` on window ones, the rest unrotated.  ``v <- value_scale
  * v``.  Scores ``q . k * head_dim ** -0.5``, causal, on a window layer over
  keys ``j`` with ``i - window < j <= i``.  Window layers only: one learned
  scalar ``b_h`` a query head joins the softmax as a key with no value, ``p_ij
  = exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij'))``.  Output ``concat(o) Wo``
  (H x ``v_head_dim`` -> d);
- FFN of a layer with ``moe_layers[i] == 0``: SwiGLU ``(silu(h Wgate) * h
  Wup) Wdown``;
- FFN of the others: ``s = sigmoid(h Wr)`` in float32 over the router's full
  width, the top ``k`` of ``s + b`` (``b`` a selection bias, used to select
  only), weights ``s[top] / (sum + 1e-20)``, ``y = sum_j w_j Expert_top_j(h)``,
  every expert a SwiGLU, **no shared expert**.

One definition of the block (:func:`block`) serves every caller through the
``attend(q, k, v, **weights)`` hook (``weights`` is ``sink=b`` on a window
layer): the dense forward below and the programs of ``serve.model``, which
keep the two layer kinds in two groups of pools whose rows differ
(:attr:`MimoConfig.cache_rows`).  Parameters are a plain tree of arrays
created in bfloat16 (router, selection bias and sinks in float32).  A
configuration may hold only ``experts_held`` of the published experts, from
``expert_first`` (``parallel.moe.dropless_moe``).  The vision and audio
towers and the multi-token-prediction layers of the published model are not
built: the programs take token ids and there is no draft module.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops.attention import KVRows, sink_softmax
from ..parallel.moe import dropless_moe
from .afmoe import _uniform, rms_norm, swiglu
from .gpt import rope, rope_tables

__all__ = ["MimoConfig", "mimo_tiny", "mimo_v25_ep16", "init_params",
           "block", "embed", "head", "forward"]

FULL, WINDOW = 0, 1


@dataclasses.dataclass(frozen=True)
class MimoConfig:
    vocab_size: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int               # of a full layer
    swa_num_kv_heads: int           # of a window layer
    head_dim: int                   # of q and k
    v_head_dim: int
    rotary_dim: int                 # int(head_dim * partial_rotary_factor)
    intermediate_size: int          # dense SwiGLU width
    moe_intermediate_size: int      # expert width
    num_experts: int                # the router's width, as published
    experts_per_token: int
    layer_pattern: tuple[int, ...]  # a layer: 0 full attention, 1 window
    moe_layers: tuple[int, ...]     # a layer: 0 dense FFN, 1 experts
    experts_held: int | None = None  # None = all of them
    expert_first: int = 0
    sliding_window: int = 128
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    value_scale: float = 0.707
    rms_norm_eps: float = 1e-5
    max_seq: int = 8192
    dtype: jnp.dtype = jnp.bfloat16
    #: "auto" = the Pallas kernels on a TPU, the plain formulations elsewhere
    kernel_impl: str = "auto"

    @property
    def num_layers(self) -> int:
        return len(self.layer_pattern)

    @property
    def held(self) -> tuple[int, int]:
        return (self.expert_first, self.experts_held or self.num_experts)

    def window_of(self, layer: int) -> int | None:
        return (self.sliding_window if self.layer_pattern[layer] == WINDOW
                else None)

    def kv_heads_of(self, layer: int) -> int:
        return (self.swa_num_kv_heads if self.layer_pattern[layer] == WINDOW
                else self.num_kv_heads)

    @property
    def cache_rows(self) -> dict[str, KVRows]:
        """What a served layer caches a token, a group of layers
        (``serve.kv_cache.group_rows``): the two kinds differ in their K/V
        heads."""
        return {name: KVRows(self.num_heads, kv, self.head_dim,
                             self.v_head_dim)
                for name, kv in (("full", self.num_kv_heads),
                                 ("window", self.swa_num_kv_heads))}


def mimo_tiny(**kw) -> MimoConfig:
    """CPU tests only: every mechanism of the family at toy widths (both
    layer kinds with different K/V head counts, keys wider than values,
    sinks, window 32; a router 16 wide of whose experts 8 are held, from the
    fifth)."""
    return MimoConfig(**{**dict(
        vocab_size=128, hidden_size=64, num_heads=4, num_kv_heads=1,
        swa_num_kv_heads=2, head_dim=24, v_head_dim=16, rotary_dim=8,
        intermediate_size=128, moe_intermediate_size=64, num_experts=16,
        experts_per_token=4, experts_held=8, expert_first=4,
        layer_pattern=(FULL, WINDOW, WINDOW, FULL), moe_layers=(0, 1, 1, 1),
        sliding_window=32, max_seq=128), **kw})


def mimo_v25_ep16() -> MimoConfig:
    """MiMo-V2.5's language model at its published widths, cut to one chip's
    share of a 16-chip expert-parallel deployment: the dense first layer
    (full attention) and one whole period ``window x5, full`` of expert
    layers holding 16 of the 256 routed experts, an eighth of the vocabulary
    (``benchmark/configs/mimo-v2.5-ep16-serve.json``)."""
    return MimoConfig(
        vocab_size=19072, hidden_size=4096, num_heads=64, num_kv_heads=4,
        swa_num_kv_heads=8, head_dim=192, v_head_dim=128, rotary_dim=64,
        intermediate_size=16384, moe_intermediate_size=2048,
        num_experts=256, experts_per_token=8, experts_held=16,
        layer_pattern=(FULL, WINDOW, WINDOW, WINDOW, WINDOW, WINDOW, FULL),
        moe_layers=(0, 1, 1, 1, 1, 1, 1), sliding_window=128, max_seq=67584)


# -- parameters --------------------------------------------------------------

#: the sinks are drawn around this, one standard deviation wide: beside the
#: 128 keys of a window (their scores of standard deviation 1.6 under this
#: init, exp(s) summing to ~430) a sink then takes a seventh to five sixths of
#: a head's weight, half at the mean, and a program that leaves it out is far
#: outside any tolerance (at 4.0, a ninth of the weight, one was not: PERF.md)
SINK_MEAN = 6.0


def init_params(cfg: MimoConfig, key, std: float = 0.02):
    """Random parameters, one jitted draw a tensor so that no float32 copy
    of the whole model ever exists.  Norm scales are drawn around 1, the
    selection bias around 0 and the sinks around ``SINK_MEAN``, so that none
    is invisible to a test."""
    d, dt = cfg.hidden_size, cfg.dtype
    m, held = cfg.moe_intermediate_size, cfg.held[1]
    counter = iter(range(1 << 30))

    def draw(shape, dtype=dt, scale=std):
        return _uniform(jax.random.fold_in(key, next(counter)), shape, scale,
                        dtype)

    def norm(n):
        return (1.0 + draw((n,), jnp.float32, 0.05)).astype(dt)

    params = {"wte": draw((cfg.vocab_size, d))}
    for i in range(cfg.num_layers):
        kv = cfg.kv_heads_of(i)
        attn = {"wqkv": draw((d, (cfg.num_heads + kv) * cfg.head_dim
                              + kv * cfg.v_head_dim)),
                "wo": draw((cfg.num_heads * cfg.v_head_dim, d))}
        if cfg.layer_pattern[i] == WINDOW:
            attn["sink"] = SINK_MEAN + draw((cfg.num_heads,), jnp.float32,
                                            1.0)
        p = {"ln_attn": norm(d), "ln_mlp": norm(d), "attn": attn}
        if not cfg.moe_layers[i]:
            f = cfg.intermediate_size
            p["mlp"] = {"w_gate": draw((d, f)), "w_up": draw((d, f)),
                        "w_down": draw((f, d))}
        else:
            p["moe"] = {
                # bf16-valued like every matrix, held in float32
                "router": draw((d, cfg.num_experts)).astype(jnp.float32),
                "bias": draw((cfg.num_experts,), jnp.float32, 0.05),
                "experts": {"w_gate": draw((held, d, m)),
                            "w_up": draw((held, d, m)),
                            "w_down": draw((held, m, d))}}
        params[f"h{i}"] = p
    params["ln_f"] = norm(d)
    params["head"] = draw((d, cfg.vocab_size))
    return params


# -- layer functions ---------------------------------------------------------

def attention_inputs(p, h, cfg: MimoConfig, layer: int, positions):
    """``h`` (T, d), ``positions`` (T,) -> q (T, H, D), k (T, Hkv, D), v (T,
    Hkv, Dv): the first ``rotary_dim`` values of every head of q and k
    rotated at the layer kind's base, v scaled."""
    t, kv = h.shape[0], cfg.kv_heads_of(layer)
    qd, kd = cfg.num_heads * cfg.head_dim, kv * cfg.head_dim
    proj = jnp.dot(h, p["wqkv"])
    q = proj[:, :qd].reshape(t, cfg.num_heads, cfg.head_dim)
    k = proj[:, qd:qd + kd].reshape(t, kv, cfg.head_dim)
    v = proj[:, qd + kd:].reshape(t, kv, cfg.v_head_dim)
    theta = (cfg.swa_rope_theta if cfg.layer_pattern[layer] == WINDOW
             else cfg.rope_theta)
    r = cfg.rotary_dim
    tabs = rope_tables(positions[None], r, theta, q.dtype)

    def rotated(x):
        return jnp.concatenate(
            [rope(x[None, ..., :r], positions[None], theta, tabs)[0],
             x[..., r:]], axis=-1)

    v = (v.astype(jnp.float32) * cfg.value_scale).astype(v.dtype)
    return rotated(q), rotated(k), v


def block(p, x, cfg: MimoConfig, layer: int, positions, attend,
          token_mask=None):
    """One decoder layer on ``x`` (T, d).  ``attend(q, k, v, **weights)``
    returns the attention output (T, H, Dv) — it owns where K/V live;
    ``weights`` is ``sink=`` the layer's (H,) biases on a window layer.
    Returns ``(x, counters)``; ``counters`` is ``None`` on a dense layer,
    else the expert layer's ``pairs``, ``experts_hit``, ``max_load``."""
    window = cfg.layer_pattern[layer] == WINDOW
    eps = cfg.rms_norm_eps
    with jax.named_scope("ln"):
        h = rms_norm(x, p["ln_attn"], eps)
    with jax.named_scope("qkv"):
        q, k, v = attention_inputs(p["attn"], h, cfg, layer, positions)
    weights = {"sink": p["attn"]["sink"]} if window else {}
    with jax.named_scope("window_attn" if window else "full_attn"):
        a = attend(q, k, v, **weights).reshape(x.shape[0], -1).astype(x.dtype)
    with jax.named_scope("proj"):
        x = x + jnp.dot(a, p["attn"]["wo"])
    with jax.named_scope("ln"):
        h = rms_norm(x, p["ln_mlp"], eps)
    if not cfg.moe_layers[layer]:
        with jax.named_scope("mlp"):
            return x + swiglu(p["mlp"], h), None
    moe = p["moe"]
    routed, counters = dropless_moe(
        h, moe["router"], moe["bias"], moe["experts"], held=cfg.held,
        top_k=cfg.experts_per_token, route_norm=True, route_scale=1.0,
        token_mask=token_mask, impl=cfg.kernel_impl)
    return x + routed, counters


def embed(params, ids, cfg: MimoConfig):
    with jax.named_scope("embed"):
        return params["wte"][ids]


def head(params, x, cfg: MimoConfig):
    """float32 logits of ``x`` (T, d)."""
    with jax.named_scope("head"):
        return jnp.dot(rms_norm(x, params["ln_f"], cfg.rms_norm_eps),
                       params["head"], preferred_element_type=jnp.float32)


def _dense_attend(q, k, v, *, window, sink=None):
    """Causal (and windowed) attention of one whole sequence, no cache
    (``ops.attention.xla_attention`` takes neither a V narrower than K nor a
    sink)."""
    t, heads, d = q.shape
    kv = k.shape[1]
    scores = jnp.einsum("qhgd,khd->hgqk", q.reshape(t, kv, heads // kv, d),
                        k, preferred_element_type=jnp.float32) * d ** -0.5
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    ok = j <= i if window is None else (j <= i) & (j > i - window)
    scores = jnp.where(ok, scores, -jnp.inf).reshape(1, heads, t, t)
    p = (jax.nn.softmax(scores, -1) if sink is None
         else sink_softmax(scores, sink))
    return jnp.einsum("hgqk,khd->qhgd",
                      p.reshape(kv, heads // kv, t, t).astype(q.dtype), v
                      ).reshape(t, heads, v.shape[-1])


def forward(params, ids, cfg: MimoConfig):
    """Logits (B, S, V) of whole sequences ``ids`` (B, S), no cache: the
    same block under dense causal (and windowed) attention."""
    def one(seq):
        positions = jnp.arange(seq.shape[0], dtype=jnp.int32)
        x = embed(params, seq, cfg)
        for i in range(cfg.num_layers):
            def attend(q, k, v, w=cfg.window_of(i), **weights):
                return _dense_attend(q, k, v, window=w, **weights)
            with jax.named_scope(f"h{i}"):
                x, _ = block(params[f"h{i}"], x, cfg, i, positions, attend)
        return head(params, x, cfg)
    return jax.lax.map(one, ids)
