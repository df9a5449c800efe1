"""An eighth decoder family: Kimi-Delta-Attention layers (a gated delta-rule
matrix state a head) with a latent-attention layer every sixth, group-limited
sigmoid experts.

The layer equations are those the keys of Ling-3.0-flash-VL's ``config.json``
select (the language model; ``benchmark/configs/ling-3.0-flash-vl-ep8-serve
.json`` lists under ``assumed`` what the keys do not bear out).  ``d`` the
model width, ``H`` heads of ``D = head_dim``, RMSNorm with a learned scale
everywhere, pre-norm blocks ``x = x + Mix(N1(x))``, ``x = x + FFN(N2(x))``,
untied head ``logits = RMSNorm(x) W_head``:

- a **KDA** layer (``layer_types[i] == "kda"``; Kimi Linear's, as
  ``flash-linear-attention`` computes it): ``q, k, v = silu(conv(h Wq)),
  silu(conv(h Wk)), silu(conv(h Wv))``, a causal depthwise convolution of
  ``conv_kernel`` taps a channel, no bias; ``q`` and ``k`` L2-normalised a
  head, ``q`` scaled by ``D ** -0.5``; the decay a key channel ``g = lower *
  sigmoid(exp(A_h) * (h W_f + b_f))`` (the safe gate: ``lower`` =
  ``kda_lower_bound`` -5, ``W_f`` full rank, ``A_h`` a scalar a head) and
  ``beta = sigmoid(h W_beta)`` a head, in float32; the recurrence of
  ``ops.kda``; output ``Wo concat_h(RMSNorm_D(o) * sigmoid(h W_g + b_g))``.
  No rotary: the recurrence carries position;
- an **MLA** layer (``"mla"``): ``models.joyai.latent_attention`` with no
  query rank (``q = h Wq``), each head's 192 query values RMS-normed before
  rotary, and a head-wise output gate ``sigmoid(h W_gate)``;
- FFN of the first ``num_dense_layers`` layers: SwiGLU of
  ``intermediate_size``; of the others ``parallel.moe.dropless_moe`` under
  group-limited routing (``n_group`` groups of consecutive experts, the
  ``topk_group`` best by the sum of their two largest ``s + b``, the top
  ``k`` inside them) over the experts held here (``experts_held`` from
  ``expert_first``: a chip of an 8-way expert-parallel layer holds one of the
  router's 8 groups), plus the shared expert, once.

**What is kept**: an MLA layer caches one latent row a token
(:attr:`LingConfig.cache_rows`, 1,152 B at the published widths); a KDA layer
keeps a fixed-size state a *sequence* (:attr:`LingConfig.state_rows`, an
``ops.ssm.DeltaState``: three convolution tails and ``H`` matrices of ``D x D``
float32, 2.1 MB a layer).  The block is written once and calls ``mixer``, the
one hook its caller owns: ``mixer((q_nope, q_rope), row, w_uk=, w_uv=)`` on an
MLA layer, and on a KDA layer an object with ``mixer.conv(u, w, b, scope=,
which=)`` and ``mixer.delta(q, k, v, g, beta)``.  Parameters are a plain tree
of arrays created in bfloat16; the router, its bias, ``A_log`` and the gates'
biases in float32.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from ..ops.attention import LatentRows, xla_attention
from ..ops.kda import kda_recurrent
from ..ops.ssm import DeltaState, causal_conv
from ..parallel.moe import dropless_moe
from .afmoe import _uniform, rms_norm, swiglu
from .joyai import latent_attention

__all__ = ["LingConfig", "ling_tiny", "ling3_flash_ep8", "init_params",
           "block", "embed", "head", "forward"]

KDA, MLA = "kda", "mla"

#: what the L2 norm of a head's q and k adds to the sum of squares
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class LingConfig:
    vocab_size: int
    hidden_size: int
    num_heads: int
    head_dim: int                   # a KDA head's key and value width
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int          # dense SwiGLU width
    moe_intermediate_size: int      # expert (and shared expert) width
    num_experts: int
    experts_per_token: int
    layer_types: tuple[str, ...]    # "kda" or "mla" a layer
    num_dense_layers: int = 1
    n_group: int = 8
    topk_group: int = 4
    conv_kernel: int = 4            # the config's short_conv_kernel_size
    kda_lower_bound: float = -5.0
    rope_theta: float = 6e6
    rms_norm_eps: float = 1e-6
    route_scale: float = 2.5
    route_norm: bool = True
    max_seq: int = 8192
    dtype: jnp.dtype = jnp.bfloat16
    #: "auto" = the Pallas kernels on a TPU, the plain formulations elsewhere
    kernel_impl: str = "auto"
    #: the experts whose weights are held here, of the ``num_experts`` the
    #: router scores: ``experts_held`` from ``expert_first`` (None = all)
    experts_held: int | None = None
    expert_first: int = 0
    #: the published clamp of the SwiGLU's gate and up values a layer (the
    #: config's ``expert_swiglu_limit_list`` and the shared expert's): 0 = off
    swiglu_limits: tuple[float, ...] = ()

    #: options of ``models.joyai.latent_attention`` this family leaves off
    q_lora_rank = None
    index_topk = 0

    def __post_init__(self):
        if any(self.swiglu_limits):
            raise ValueError(
                "a non-zero SwiGLU limit is not implemented: the config gives "
                "the clamp's value a layer and not its form, and every layer "
                "kept here publishes 0 (off)")
        if self.num_experts % self.n_group or self.topk_group > self.n_group:
            raise ValueError("group-limited routing takes topk_group of "
                             "n_group equal groups of the experts")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def held(self) -> tuple[int, int]:
        return (self.expert_first, self.experts_held or self.num_experts)

    def keeps_state(self, layer: int) -> bool:
        """Whether ``layer`` is a KDA layer (keeps a state a sequence) and
        not an MLA layer (caches a row a token)."""
        return self.layer_types[layer] == KDA

    def groups_of(self, layer: int) -> tuple[str, ...]:
        """The cache group of ``layer``: the state group, or the full group
        of latent rows (which keeps lengths, admission and blocks)."""
        return ("state",) if self.keeps_state(layer) else ("full",)

    def window_of(self, layer: int) -> None:
        return None

    @property
    def cache_rows(self) -> LatentRows:
        """What an MLA layer caches a token (``ops.attention``)."""
        return LatentRows(
            rank=self.kv_lora_rank, rope_dim=self.qk_rope_head_dim,
            scale=(self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5)

    @property
    def state_rows(self) -> DeltaState:
        """What a KDA layer keeps a sequence (``ops.ssm``)."""
        return DeltaState(self.num_heads, self.head_dim, self.head_dim,
                          self.conv_kernel)


def ling_tiny(**kw) -> LingConfig:
    """CPU tests only: every mechanism of the family at toy widths — a dense
    KDA layer, then KDA, MLA, KDA with 16 experts in 4 groups (the best 2),
    top 4, the second group's four experts held."""
    return LingConfig(**{**dict(
        vocab_size=128, hidden_size=64, num_heads=4, head_dim=16,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
        num_experts=16, experts_per_token=4, n_group=4, topk_group=2,
        layer_types=(KDA, KDA, MLA, KDA), num_dense_layers=1,
        rope_theta=10000.0, max_seq=256, experts_held=4, expert_first=4),
        **kw})


def ling3_flash_ep8() -> LingConfig:
    """Ling-3.0-flash-VL's language model at its published widths as one
    chip's share of an 8-chip expert-parallel stage: published layer 1 (KDA,
    the dense SwiGLU of 6,144: the two leading dense layers count once) and
    layers 2-7 (KDA, KDA, KDA, MLA, KDA, KDA: one whole period of 5 : 1), 64
    of the 512 experts of each — one of the router's 8 groups; the router 512
    wide, the best 4 groups, top 8 — and an eighth of the vocabulary
    (``benchmark/configs/ling-3.0-flash-vl-ep8-serve.json``)."""
    return LingConfig(
        vocab_size=19648, hidden_size=2560, num_heads=32, head_dim=128,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, intermediate_size=6144, moe_intermediate_size=768,
        num_experts=512, experts_per_token=8, n_group=8, topk_group=4,
        layer_types=(KDA, KDA, KDA, KDA, MLA, KDA, KDA), num_dense_layers=1,
        conv_kernel=4, kda_lower_bound=-5.0, rope_theta=6e6,
        rms_norm_eps=1e-6, route_scale=2.5, max_seq=20480, experts_held=64,
        expert_first=0, swiglu_limits=(0,) * 7)


# -- parameters --------------------------------------------------------------

def init_params(cfg: LingConfig, key, std: float = 0.02):
    """Random parameters, one jitted draw a tensor (``afmoe.init_params``'s
    scheme: exact arithmetic on uniform bits, so the CPU and the chip make
    the same values from one key).  Norm scales are drawn around 1 and the
    selection bias around 0; the convolutions' taps of standard deviation 0.3
    (as jamba's); ``A_log`` around 0 and the decay gate's bias in ``[-5.1,
    -2.9]``, so that a channel at rest keeps 0.73-0.97 of the state a token:
    a state that neither forgets at once nor never."""
    d, dt, h = cfg.hidden_size, cfg.dtype, cfg.num_heads
    c = h * cfg.head_dim
    rank, rope_dim = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    qk = cfg.qk_nope_head_dim + rope_dim
    m, e, held = cfg.moe_intermediate_size, cfg.num_experts, cfg.held[1]
    f32 = jnp.float32
    counter = iter(range(1 << 30))

    def draw(shape, dtype=dt, scale=std):
        return _uniform(jax.random.fold_in(key, next(counter)), shape, scale,
                        dtype)

    def norm(n):
        return (1.0 + draw((n,), f32, 0.05)).astype(dt)

    def ffn(width):
        return {"w_gate": draw((d, width)), "w_up": draw((d, width)),
                "w_down": draw((width, d))}

    params = {"wte": draw((cfg.vocab_size, d))}
    for i in range(cfg.num_layers):
        p = {"ln_mix": norm(d), "ln_mlp": norm(d)}
        if cfg.keeps_state(i):
            p["kda"] = {
                "w_qkv": draw((d, 3 * c)),           # columns [q | k | v]
                "conv_w": draw((3, cfg.conv_kernel, c), scale=0.3),
                "w_gates": draw((d, 2 * c + h)),     # [decay | out gate | beta]
                "b_decay": -4.0 + draw((c,), f32, 0.63),
                "a_log": draw((h,), f32, 0.3),
                "b_gate": draw((c,), f32, 0.05),
                "o_norm": norm(cfg.head_dim),
                "w_o": draw((c, d))}
        else:
            p["attn"] = {
                "w_q": draw((d, h * qk)),
                "q_head_norm": norm(qk),
                "w_kva": draw((d, rank + rope_dim)),
                "kv_norm": norm(rank),
                # kv_b_proj's columns of head i are [w_uk[:, i] | w_uv[:, i]]
                "w_uk": draw((rank, h, cfg.qk_nope_head_dim)),
                "w_uv": draw((rank, h, cfg.v_head_dim)),
                "w_gate": draw((d, h)),
                "w_o": draw((h * cfg.v_head_dim, d))}
        if i < cfg.num_dense_layers:
            p["mlp"] = ffn(cfg.intermediate_size)
        else:
            p["moe"] = {
                "router": draw((d, e)).astype(f32),
                "bias": draw((e,), f32, 0.05),
                "shared": ffn(m),
                "experts": {"w_gate": draw((held, d, m)),
                            "w_up": draw((held, d, m)),
                            "w_down": draw((held, m, d))}}
        params[f"h{i}"] = p
    params["ln_f"] = norm(d)
    params["head"] = draw((d, cfg.vocab_size))
    return params


# -- layer functions ---------------------------------------------------------

def _l2_norm(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


def _kda(p, h, cfg: LingConfig, state):
    """The KDA mixer on ``h`` (T, d).  ``state.conv`` and ``state.delta`` are
    the caller's: they read and write the sequence's tails and matrix
    state."""
    t, heads, dim = h.shape[0], cfg.num_heads, cfg.head_dim
    c = heads * dim
    f32 = jnp.float32
    with jax.named_scope("kda"), jax.named_scope("proj"):
        # rows of tokens, pinned: for a decode step of 128 slots the compiler
        # writes this product with the slots across lanes, the convolutions
        # inherit that form, and every layer of the group's three tail arrays
        # is then re-laid on the way in and out of the program
        # (serve.pool_check;
        # tests/test_kernel_export_families.py -k delta_state)
        qkv = with_layout_constraint(jnp.dot(h, p["w_qkv"]),
                                     Layout(major_to_minor=(0, 1)))
        gates = jnp.dot(h, p["w_gates"], preferred_element_type=f32)
    q, k, v = (
        state.conv(qkv[:, i * c:(i + 1) * c], p["conv_w"][i],
                   jnp.zeros((), f32), scope="kda", which=i)
        for i in range(3))
    with jax.named_scope("kda"), jax.named_scope("gate"):
        q, k, v = (jax.nn.silu(x.astype(f32)).reshape(t, heads, dim)
                   for x in (q, k, v))
        q, k = _l2_norm(q) * dim ** -0.5, _l2_norm(k)
        decay = (gates[:, :c] + p["b_decay"]).reshape(t, heads, dim)
        g = cfg.kda_lower_bound * jax.nn.sigmoid(
            jnp.exp(p["a_log"])[:, None] * decay)
        beta = jax.nn.sigmoid(gates[:, 2 * c:])
    o = state.delta(q, k, v, g, beta)
    with jax.named_scope("kda"):
        with jax.named_scope("gate"):
            gate = jax.nn.sigmoid(gates[:, c:2 * c] + p["b_gate"])
            o = rms_norm(o, p["o_norm"], cfg.rms_norm_eps).reshape(t, c) \
                * gate
        with jax.named_scope("out_proj"):
            return jnp.dot(o.astype(h.dtype), p["w_o"])


def block(p, x, cfg: LingConfig, layer: int, positions, mixer,
          token_mask=None):
    """One decoder layer on ``x`` (T, d).  ``mixer`` is the caller's hook:
    ``mixer((q_nope, q_rope), row, w_uk=, w_uv=) -> (T, H, v)`` on an MLA
    layer (it owns where rows live), the state's ``conv`` / ``delta`` on a
    KDA layer.  ``token_mask`` (T,) marks the real tokens: the others reach
    no expert and count in no counter.  Returns ``(x, counters)``: ``None``
    on a dense layer, else the expert layer's ``pairs``, ``experts_hit``,
    ``max_load``, ``groups_hit``."""
    eps = cfg.rms_norm_eps
    with jax.named_scope("ln"):
        h = rms_norm(x, p["ln_mix"], eps)
    if cfg.keeps_state(layer):
        x = x + _kda(p["kda"], h, cfg, mixer)
    else:
        x = latent_attention(p["attn"], x, h, cfg, positions, mixer)
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


def embed(params, ids, cfg: LingConfig):
    with jax.named_scope("embed"):
        return params["wte"][ids]


def head(params, x, cfg: LingConfig):
    """float32 logits of ``x`` (T, d)."""
    with jax.named_scope("head"):
        return jnp.dot(rms_norm(x, params["ln_f"], cfg.rms_norm_eps),
                       params["head"], preferred_element_type=jnp.float32)


class _FreshState:
    """The state hook of a whole sequence from zeros, nothing kept: the
    plain recurrence (``forward``)."""

    def __init__(self, cfg: LingConfig):
        self.rows = cfg.state_rows

    def conv(self, u, w, b, scope=None, which=0):
        tail = jnp.zeros(((self.rows.d_conv - 1) * u.shape[1],), u.dtype)
        return causal_conv(u, tail, w, b, u.shape[0])[0]

    def delta(self, q, k, v, g, beta):
        rows = self.rows
        state = jnp.zeros((rows.heads, rows.value_dim, rows.key_dim),
                          jnp.float32)
        return kda_recurrent(q, k, v, g, beta, state)[0]


def forward(params, ids, cfg: LingConfig):
    """Logits (B, S, V) of whole sequences ``ids`` (B, S), nothing cached:
    the same block under dense causal attention over decompressed keys and
    values, and the recurrence from zeros."""
    def one(seq):
        positions = jnp.arange(seq.shape[0], dtype=jnp.int32)
        x = embed(params, seq, cfg)
        for i in range(cfg.num_layers):
            def attend(q, row, *, w_uk, w_uv):
                q_nope, q_rope = q
                rank = cfg.kv_lora_rank
                c_kv = row[:, :rank]
                k_rope = row[:, rank:rank + cfg.qk_rope_head_dim]
                k = jnp.concatenate([
                    jnp.einsum("sr,rhn->shn", c_kv, w_uk),
                    jnp.broadcast_to(k_rope[:, None], (
                        *q_rope.shape[:2], k_rope.shape[-1]))], -1)
                v = jnp.einsum("sr,rhv->shv", c_kv, w_uv)
                qq = jnp.concatenate([q_nope, q_rope], -1)
                # its scale is the keys' width ** -0.5: the published one
                return xla_attention(qq[None], k[None], v[None],
                                     causal=True)[0]
            mixer = _FreshState(cfg) if cfg.keeps_state(i) else attend
            with jax.named_scope(f"h{i}"):
                x, _ = block(params[f"h{i}"], x, cfg, i, positions, mixer)
        return head(params, x, cfg)
    return jax.lax.map(one, ids)
