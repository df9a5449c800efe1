"""A third decoder family: latent attention over one cached row a token, a
wide sigmoid router over narrow SwiGLU experts.

The layer equations are those of the DeepSeek-V3 modelling code that the
keys of JoyAI-LLM-Flash's ``config.json`` belong to (``model_type
joyai_llm_flash``; the widths of a preset come from that file).  ``d`` the
model width, ``H`` heads, RMSNorm everywhere, no biases, pre-norm blocks:

- embedding ``x = E[ids]`` (no scale); untied head ``logits = RMSNorm(x) Wu``;
- block: ``x = x + Attn(N1(x))`` then ``x = x + FFN(N2(x))``;
- attention (``h = N1(x)``): ``c_q = RMSNorm(h W_qa)`` (``q_lora_rank``), ``q
  = c_q W_qb`` -> ``H x [q_nope | q_rope]``; ``[c | k_r] = h W_kva``
  (``kv_lora_rank | rope``), ``c_kv = RMSNorm(c)``, ``k_rope = rot(k_r)``: one
  rotated head shared by all ``H``; ``k_nope_i = c_kv W_UK_i``, ``v_i = c_kv
  W_UV_i`` (the two halves of the published ``kv_b_proj``'s columns of head
  ``i``); rotary on ``q_rope`` and ``k_r`` with the pairs interleaved
  (``rope_interleave``: de-interleaved, then rotate-half); scores ``(q_nope_i
  . k_nope_j + q_rope_i . k_rope_j) * (nope + rope) ** -0.5``, causal
  softmax, output ``concat_i(sum_j p_ij v_j) W_o``;
- FFN of the first ``num_dense_layers`` layers: SwiGLU of
  ``intermediate_size``; of the others: ``s = sigmoid(h W_r)`` in float32, the
  top ``k`` of ``s + b`` (``b`` selects only; with ``n_group`` > 1 inside the
  ``topk_group`` best groups of consecutive experts, a group scoring the sum
  of its two largest), weights ``s[top] / (sum + 1e-20) * route_scale``,
  ``y = Shared(h) + sum_j w_j Expert_top_j(h)``: ``parallel.moe.dropless_moe``,
  the one expert layer of both routed families, over the experts held here
  (``experts_held`` from ``expert_first``; all of them by default: a chip's
  share of an expert-parallel layer computes its own experts' terms of the
  sum, the router as wide as published);
- with ``index_topk`` > 0 (GLM-5, ``model_type glm_moe_dsa``: the published
  DeepSeek sparse attention) each attention layer has an *indexer*: index
  queries ``qI = c_q W_qI`` -> ``index_heads x index_head_dim``, ONE index key
  a token ``kI = LayerNorm(h W_kI)`` (weight and bias, eps 1e-6) shared by
  the index heads, rotary (the layer's ``rope_theta``, pairs interleaved) on
  the first ``qk_rope_head_dim`` values of both, head weights ``w = (h W_w) *
  index_heads ** -0.5 * index_head_dim ** -0.5`` in float32; score ``I(t, s)
  = sum_j w_tj relu(qI_tj . kI_s)``; a query attends the ``min(index_topk, t
  + 1)`` positions of largest score, and the softmax above runs over those
  alone.  (The published inference code also rotates ``qI`` and ``kI`` by a
  Hadamard matrix and stores ``kI`` in fp8: the rotation is orthonormal and
  leaves every product as it is, so it is left out, and keys are bfloat16.)

**What is cached** a token a layer is ``[c_kv | k_rope]`` (:attr:`JoyaiConfig
.cache_rows`, an ``ops.attention.LatentRows``): ``kv_lora_rank + rope``
values, 1,152 bytes at the published widths where 32 heads of K and V would
be 16,384.  The block hands ``attend((q_nope, q_rope), row, w_uk=, w_uv=)``
the row to store and the queries in two parts, and gets ``(T, H, v)`` back:
the caller owns where rows live and whether it absorbs ``W_UK`` into the
query (``ops.attention``, "Latent attention").  With an indexer the cached
rows are two, the latent row and ``kI`` after norm and rotation (an
``ops.attention.SparseLatentRows``: a second pool of the same group), and the
block calls ``attend((q_nope, q_rope, qI, w), row, kI, w_uk=, w_uv=)``.  Parameters are a plain tree
of arrays created in bfloat16, the router in float32, as ``models.afmoe``'s.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import LatentRows, SparseLatentRows
from ..parallel.moe import dropless_moe
from .afmoe import _uniform, rms_norm, swiglu
from .gpt import rope

__all__ = ["JoyaiConfig", "joyai_tiny", "joyai_llm_flash", "glm5_tiny",
           "glm5_ep16", "init_params", "block", "embed", "head",
           "latent_attention"]


@dataclasses.dataclass(frozen=True)
class JoyaiConfig:
    vocab_size: int
    hidden_size: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int          # dense SwiGLU width
    moe_intermediate_size: int      # expert (and shared expert) width
    num_experts: int
    experts_per_token: int
    num_layers: int
    num_dense_layers: int = 1
    n_group: int = 1
    topk_group: int = 1
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    route_scale: float = 1.0
    route_norm: bool = True
    max_seq: int = 8192
    dtype: jnp.dtype = jnp.bfloat16
    #: "auto" = the Pallas kernels on a TPU, the plain formulations elsewhere
    kernel_impl: str = "auto"
    #: the experts whose weights are held here, of the ``num_experts`` the
    #: router scores: ``experts_held`` from ``expert_first`` (None = all)
    experts_held: int | None = None
    expert_first: int = 0
    #: the indexer (module docstring): on where ``index_topk`` > 0
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0

    def __post_init__(self):
        if self.num_experts % self.n_group or self.topk_group > self.n_group:
            raise ValueError(
                f"group-limited routing takes topk_group of n_group equal "
                f"groups of the experts: {self.num_experts} experts, n_group "
                f"{self.n_group}, topk_group {self.topk_group}")
        if self.index_topk and self.index_head_dim < self.qk_rope_head_dim:
            raise ValueError("the indexer rotates the first qk_rope_head_dim "
                             "values of an index head: index_head_dim is "
                             "smaller")

    @property
    def held(self) -> tuple[int, int]:
        return (self.expert_first, self.experts_held or self.num_experts)

    @property
    def cache_rows(self) -> LatentRows:
        kw = dict(
            rank=self.kv_lora_rank, rope_dim=self.qk_rope_head_dim,
            scale=(self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5)
        if self.index_topk:
            return SparseLatentRows(index_dim=self.index_head_dim,
                                    topk=self.index_topk, **kw)
        return LatentRows(**kw)

    def window_of(self, layer: int) -> None:
        return None


def joyai_tiny(**kw) -> JoyaiConfig:
    """CPU tests only: every mechanism of the family at toy widths."""
    return JoyaiConfig(**{**dict(
        vocab_size=128, hidden_size=64, num_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
        num_experts=16, experts_per_token=4, num_layers=3,
        num_dense_layers=1, rope_theta=10000.0, route_scale=2.5,
        max_seq=128), **kw})


def joyai_llm_flash() -> JoyaiConfig:
    """JoyAI-LLM-Flash at its published widths, cut in depth only: the
    leading dense layer and four expert layers whole, all 256 experts and
    the whole vocabulary (``benchmark/configs/joyai-llm-flash-serve.json``:
    one stage of a ten-chip pipeline)."""
    return JoyaiConfig(
        vocab_size=129280, hidden_size=2048, num_heads=32, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, intermediate_size=7168, moe_intermediate_size=768,
        num_experts=256, experts_per_token=8, num_layers=5,
        num_dense_layers=1, rope_theta=32e6, rms_norm_eps=1e-6,
        route_scale=2.5, max_seq=16384)


def glm5_tiny(**kw) -> JoyaiConfig:
    """CPU tests only: the family with its indexer on (``index_topk`` 24,
    below the tests' contexts), two leading dense layers and half of the
    experts held, from the fifth."""
    return joyai_tiny(**{**dict(
        num_layers=4, num_dense_layers=2, experts_held=8, expert_first=4,
        index_heads=4, index_head_dim=16, index_topk=24,
        rms_norm_eps=1e-5), **kw})


def glm5_ep16() -> JoyaiConfig:
    """GLM-5 (``model_type glm_moe_dsa``) at its published widths as one
    chip's share of a 16-chip expert-parallel deployment: one dense layer
    and four expert layers, 16 of the 256 experts of each (the router 256
    wide, top 8), an eighth of the vocabulary
    (``benchmark/configs/glm5-ep16-serve.json``)."""
    return JoyaiConfig(
        vocab_size=19360, hidden_size=6144, num_heads=64, q_lora_rank=2048,
        kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
        v_head_dim=256, intermediate_size=12288, moe_intermediate_size=2048,
        num_experts=256, experts_per_token=8, experts_held=16,
        expert_first=0, num_layers=5, num_dense_layers=1, rope_theta=1e6,
        rms_norm_eps=1e-5, route_scale=2.5, max_seq=33792, index_heads=32,
        index_head_dim=128, index_topk=2048)


# -- parameters --------------------------------------------------------------

def init_params(cfg: JoyaiConfig, key, std: float = 0.02):
    """Random parameters, one jitted draw a tensor (``afmoe.init_params``'s
    scheme: bf16 values, norm scales around 1, selection bias around 0, the
    router held in float32)."""
    d, dt, h = cfg.hidden_size, cfg.dtype, cfg.num_heads
    rank, rope_dim = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    m, e, held = cfg.moe_intermediate_size, cfg.num_experts, cfg.held[1]
    counter = iter(range(1 << 30))

    def draw(shape, dtype=dt, scale=std):
        return _uniform(jax.random.fold_in(key, next(counter)), shape, scale,
                        dtype)

    def norm(n):
        return (1.0 + draw((n,), jnp.float32, 0.05)).astype(dt)

    def ffn(width):
        return {"w_gate": draw((d, width)), "w_up": draw((d, width)),
                "w_down": draw((width, d))}

    params = {"wte": draw((cfg.vocab_size, d))}
    for i in range(cfg.num_layers):
        p = {"ln_attn": norm(d), "ln_mlp": norm(d),
             "attn": {
                 "w_qa": draw((d, cfg.q_lora_rank)),
                 "q_norm": norm(cfg.q_lora_rank),
                 "w_qb": draw((cfg.q_lora_rank,
                               h * (cfg.qk_nope_head_dim + rope_dim))),
                 "w_kva": draw((d, rank + rope_dim)),
                 "kv_norm": norm(rank),
                 # kv_b_proj's columns of head i are [w_uk[:, i] | w_uv[:, i]]
                 "w_uk": draw((rank, h, cfg.qk_nope_head_dim)),
                 "w_uv": draw((rank, h, cfg.v_head_dim)),
                 "w_o": draw((h * cfg.v_head_dim, d))}}
        if cfg.index_topk:
            hi, di = cfg.index_heads, cfg.index_head_dim
            p["attn"]["indexer"] = {
                "w_q": draw((cfg.q_lora_rank, hi * di)),
                "w_k": draw((d, di)),
                "k_norm": norm(di), "k_bias": draw((di,), scale=0.05),
                # float32 like the router: the head weights are formed in it
                "w_w": draw((d, hi)).astype(jnp.float32)}
        if i < cfg.num_dense_layers:
            p["mlp"] = ffn(cfg.intermediate_size)
        else:
            p["moe"] = {
                "router": draw((d, e)).astype(jnp.float32),
                "bias": draw((e,), jnp.float32, 0.05),
                "shared": ffn(m),
                "experts": {"w_gate": draw((held, d, m)),
                            "w_up": draw((held, d, m)),
                            "w_down": draw((held, m, d))}}
        params[f"h{i}"] = p
    params["ln_f"] = norm(d)
    params["head"] = draw((d, cfg.vocab_size))
    return params


# -- layer functions ---------------------------------------------------------

def rope_interleaved(x, positions, theta: float):
    """Rotary embedding of ``x`` (T, H, D) whose ``D`` values are stored as
    interleaved pairs ``(x0, x1), (x2, x3), ...``: de-interleaved to ``[x0,
    x2, ... | x1, x3, ...]`` (a constant 0/1 matrix: exact), then the
    rotate-half rotation of ``models.gpt.rope``."""
    d = x.shape[-1]
    perm = np.zeros((d, d), np.float32)
    perm[np.r_[0:d:2, 1:d:2], np.arange(d)] = 1.0
    x = jnp.dot(x, jnp.asarray(perm, x.dtype))
    return rope(x[None], positions[None], theta)[0]


def _rope_leading(x, positions, cfg: JoyaiConfig):
    """Interleaved rotary on the first ``qk_rope_head_dim`` values of each
    head of ``x`` (T, H, D), the others as they are."""
    r = cfg.qk_rope_head_dim
    return jnp.concatenate(
        [rope_interleaved(x[..., :r], positions, cfg.rope_theta),
         x[..., r:]], axis=-1)


def index_inputs(p, h, c_q, cfg: JoyaiConfig, positions):
    """The indexer's side of a layer: ``qI`` (T, index_heads, index_head_dim)
    rotated, the float32 head weights (T, index_heads), and the index key to
    cache (T, index_head_dim), after its LayerNorm and rotation."""
    t, hi = h.shape[0], cfg.index_heads
    with jax.named_scope("indexer"):
        q = _rope_leading(jnp.dot(c_q, p["w_q"]).reshape(t, hi, -1),
                          positions, cfg)
        k = jnp.dot(h, p["w_k"]).astype(jnp.float32)
        k = k - k.mean(-1, keepdims=True)
        k = k * jax.lax.rsqrt((k * k).mean(-1, keepdims=True) + 1e-6)
        k = (k * p["k_norm"].astype(jnp.float32)
             + p["k_bias"].astype(jnp.float32)).astype(h.dtype)
        k = _rope_leading(k[:, None], positions, cfg)[:, 0]
        w = jnp.dot(h.astype(jnp.float32), p["w_w"]) \
            * (hi ** -0.5 * cfg.index_head_dim ** -0.5)
    return q, w, k


def latent_inputs(p, h, cfg: JoyaiConfig, positions):
    """``h`` (T, d) -> ``q_nope`` (T, H, nope), ``q_rope`` (T, H, rope)
    rotated, the row to cache (T, rank + rope): ``[c_kv | k_rope]``, and the
    query latent ``c_q`` (T, q_lora_rank) after its norm.  Options by what the
    config and the parameters hold: ``q_lora_rank`` None is one query
    projection ``w_q`` (no latent, ``c_q`` None), and ``q_head_norm`` an
    RMSNorm with a learned scale over each head's ``nope + rope`` values
    before rotary (``models.ling``)."""
    t = h.shape[0]
    eps, rank = cfg.rms_norm_eps, cfg.kv_lora_rank
    nope = cfg.qk_nope_head_dim
    with jax.named_scope("q_proj"):
        if cfg.q_lora_rank:
            c_q = rms_norm(jnp.dot(h, p["w_qa"]), p["q_norm"], eps)
            q = jnp.dot(c_q, p["w_qb"])
        else:       # no query rank: one projection, no query-latent norm
            c_q, q = None, jnp.dot(h, p["w_q"])
        q = q.reshape(t, cfg.num_heads, -1)
        if "q_head_norm" in p:      # each head's values, before rotary
            q = rms_norm(q, p["q_head_norm"], eps)
        q_rope = rope_interleaved(q[..., nope:], positions, cfg.rope_theta)
    with jax.named_scope("kv_down"):
        ckr = jnp.dot(h, p["w_kva"])
        c_kv = rms_norm(ckr[:, :rank], p["kv_norm"], eps)
        k_rope = rope_interleaved(ckr[:, None, rank:], positions,
                                  cfg.rope_theta)[:, 0]
        row = jnp.concatenate([c_kv, k_rope], axis=-1)
        pad = cfg.cache_rows.widths[0] - row.shape[-1]
        if pad:
            row = jnp.pad(row, ((0, 0), (0, pad)))
    return q[..., :nope], q_rope, row, c_q


def latent_attention(a, x, h, cfg, positions, attend):
    """``x`` (T, d) plus the latent attention layer on its normed ``h``,
    under scope ``latent_attn``: the inputs, ``attend``, the output projection.
    With ``w_gate`` (d, H) among the parameters each head's output is scaled
    by ``sigmoid(h w_gate)`` before the projection (``models.ling``)."""
    with jax.named_scope("latent_attn"):
        q_nope, q_rope, row, c_q = latent_inputs(a, h, cfg, positions)
        if cfg.index_topk:
            q_index, w_index, index_key = index_inputs(
                a["indexer"], h, c_q, cfg, positions)
            o = attend((q_nope, q_rope, q_index, w_index), row, index_key,
                       w_uk=a["w_uk"], w_uv=a["w_uv"])
        else:
            o = attend((q_nope, q_rope), row, w_uk=a["w_uk"], w_uv=a["w_uv"])
        if "w_gate" in a:
            with jax.named_scope("out_gate"):
                gate = jax.nn.sigmoid(jnp.dot(
                    h, a["w_gate"], preferred_element_type=jnp.float32))
                o = o.astype(jnp.float32) * gate[:, :, None]
        with jax.named_scope("out_proj"):
            return x + jnp.dot(o.reshape(x.shape[0], -1).astype(x.dtype),
                               a["w_o"])


def block(p, x, cfg: JoyaiConfig, layer: int, positions, attend,
          token_mask=None):
    """One decoder layer on ``x`` (T, d).  ``attend((q_nope, q_rope), row,
    w_uk=, w_uv=)`` stores the row and returns the attention output (T, H, v).
    Returns ``(x, counters)``: ``None`` on a dense layer, else the expert
    layer's ``pairs``, ``experts_hit``, ``max_load``."""
    eps = cfg.rms_norm_eps
    with jax.named_scope("ln"):
        h = rms_norm(x, p["ln_attn"], eps)
    x = latent_attention(p["attn"], x, h, cfg, positions, attend)
    with jax.named_scope("ln"):
        h = rms_norm(x, p["ln_mlp"], eps)
    if layer < cfg.num_dense_layers:
        with jax.named_scope("mlp"):
            return x + swiglu(p["mlp"], h), None
    moe = p["moe"]
    routed, counters = dropless_moe(
        h, moe["router"], moe["bias"], moe["experts"], held=cfg.held,
        top_k=cfg.experts_per_token, route_norm=cfg.route_norm,
        route_scale=cfg.route_scale, n_group=cfg.n_group,
        topk_group=cfg.topk_group, token_mask=token_mask,
        impl=cfg.kernel_impl)
    with jax.named_scope("shared_expert"):
        return x + swiglu(moe["shared"], h) + routed, counters


def embed(params, ids, cfg: JoyaiConfig):
    with jax.named_scope("embed"):
        return params["wte"][ids]


def head(params, x, cfg: JoyaiConfig):
    """float32 logits of ``x`` (T, d)."""
    with jax.named_scope("head"):
        return jnp.dot(rms_norm(x, params["ln_f"], cfg.rms_norm_eps),
                       params["head"], preferred_element_type=jnp.float32)
