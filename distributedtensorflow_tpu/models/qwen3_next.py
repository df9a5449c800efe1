"""A tenth decoder family: Gated DeltaNet layers (the delta rule over a matrix
state a head under one unbounded scalar gate, fewer key heads than value
heads) with a gated-attention layer every fourth, softmax-routed experts and a
gated shared expert in every layer.

The layer equations are those the keys of Qwen3-Next-80B-A3B-Instruct's
``config.json`` select (``model_type`` ``qwen3_next``;
``benchmark/configs/qwen3-next-ep4-serve.json`` lists under ``assumed`` what
the keys do not bear out).  ``d`` the model width; the RMSNorm is
**zero-centred**, ``N(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)``; pre-norm
blocks ``x = x + Mixer_l(N(x))``, ``x = x + MoE(N(x))``; no bias anywhere;
untied head ``logits = N(x) W_head``.  Layer ``l`` is attention where ``(l +
1) % full_attention_interval == 0``:

- a **Gated DeltaNet** layer (``Hk`` q/k heads of ``dk`` shared by ``Hv``
  value heads of ``dv``, value head ``h`` reading q/k head ``h // (Hv /
  Hk)``): ``[q | k | v] = silu(conv(h W_qkv))``, ONE causal depthwise
  convolution of ``conv_kernel`` taps over all ``2 Hk dk + Hv dv`` channels;
  ``z = h W_z``; ``[b | a] = h W_ba``; ``beta = sigmoid(b)`` and ``g =
  -exp(A_log) * softplus(a + dt_bias)`` a value head, float32, **no lower
  bound**; ``q`` and ``k`` L2-normalised a head, ``q`` scaled by ``dk **
  -0.5``; the recurrence of ``ops.kda`` under a scalar gate; output
  ``(RMSNorm_dv(o) * w * silu(z)) W_out``, that norm a value head's with a
  plain scale.  No rotary: the recurrence carries position;
- a **gated attention** layer: ``[q_i | gate_i]`` a head from the doubled
  ``W_q``, ``k, v = h W_kv``; q and k normed a head (zero-centred); rotary
  (rotate-half) on the first ``rotary_dim`` channels; causal softmax at
  ``head_dim ** -0.5``; output ``(attn * sigmoid(gate)) W_o``;
- the **experts**, every layer: ``parallel.moe.dropless_moe`` under a softmax
  router over all ``num_experts`` (the top ``experts_per_token``, renormalised
  over the chosen) over the experts held here (``experts_held`` from
  ``expert_first``), plus ``sigmoid(h w_sg) * SwiGLU_shared(h)``, once.

**What is kept**: an attention layer caches K and V a token
(:attr:`Qwen3NextConfig.cache_rows`, 2 K/V heads of 256: 2,048 B a token a
layer at the published widths); a Gated DeltaNet layer keeps a fixed-size
state a *sequence* (:attr:`Qwen3NextConfig.state_rows`, an
``ops.ssm.GatedDeltaState``: the convolution's tail and ``Hv`` matrices of
``dv x dk`` float32, 2.1 MB a layer).  The block is written once and calls
``mixer``, the one hook its caller owns: ``mixer(q, k, v)`` on an attention
layer, and on a Gated DeltaNet layer an object with ``mixer.conv(u, w, b,
scope=)`` and ``mixer.delta(q, k, v, g, beta, scope=)``.  Parameters are a
plain tree of arrays created in bfloat16; the router, ``A_log`` and
``dt_bias`` in float32.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from ..ops.attention import KVRows, xla_attention
from ..ops.kda import kda_recurrent
from ..ops.ssm import GatedDeltaState, causal_conv
from ..parallel.moe import dropless_moe
from .afmoe import _uniform, rms_norm, swiglu
from .gpt import rope, rope_tables

__all__ = ["Qwen3NextConfig", "qwen3_next_tiny", "qwen3_next_ep4",
           "init_params", "block", "embed", "head", "forward"]

#: what the L2 norm of a head's q and k adds to the sum of squares
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    linear_key_heads: int
    linear_value_heads: int
    linear_key_dim: int
    linear_value_dim: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rotary_dim: int                 # int(head_dim * partial_rotary_factor)
    moe_intermediate_size: int      # a routed expert's width
    shared_intermediate_size: int
    num_experts: int
    experts_per_token: int
    full_attention_interval: int = 4
    conv_kernel: int = 4            # the config's linear_conv_kernel_dim
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-6
    route_norm: bool = True         # the config's norm_topk_prob
    max_seq: int = 8192
    dtype: jnp.dtype = jnp.bfloat16
    #: "auto" = the Pallas kernels on a TPU, the plain formulations elsewhere
    kernel_impl: str = "auto"
    #: the experts whose weights are held here, of the ``num_experts`` the
    #: router scores: ``experts_held`` from ``expert_first`` (None = all)
    experts_held: int | None = None
    expert_first: int = 0

    def __post_init__(self):
        if self.linear_value_heads % self.linear_key_heads:
            raise ValueError("the value heads divide among the key heads")

    @property
    def held(self) -> tuple[int, int]:
        return (self.expert_first, self.experts_held or self.num_experts)

    def keeps_state(self, layer: int) -> bool:
        """Whether ``layer`` is a Gated DeltaNet layer (keeps a state a
        sequence) and not an attention layer (caches a row a token)."""
        return (layer + 1) % self.full_attention_interval != 0

    def groups_of(self, layer: int) -> tuple[str, ...]:
        """The cache group of ``layer``: the state group, or the full group
        of K/V rows (which keeps lengths, admission and blocks)."""
        return ("state",) if self.keeps_state(layer) else ("full",)

    def window_of(self, layer: int) -> None:
        return None

    @property
    def cache_rows(self) -> KVRows:
        """What an attention layer caches a token (``ops.attention``)."""
        return KVRows(self.num_heads, self.num_kv_heads, self.head_dim)

    @property
    def state_rows(self) -> GatedDeltaState:
        """What a Gated DeltaNet layer keeps a sequence (``ops.ssm``)."""
        return GatedDeltaState(
            self.linear_key_heads, self.linear_value_heads,
            self.linear_key_dim, self.linear_value_dim, self.conv_kernel)


def qwen3_next_tiny(**kw) -> Qwen3NextConfig:
    """CPU tests only: every mechanism of the family at toy widths — G G G A
    G, 2 key heads shared by 4 value heads, 4 query heads on 2 K/V heads of
    16 with rotary on the first 4 channels, 16 experts top 3, the second
    quarter held."""
    return Qwen3NextConfig(**{**dict(
        vocab_size=128, hidden_size=64, num_layers=5, linear_key_heads=2,
        linear_value_heads=4, linear_key_dim=16, linear_value_dim=8,
        num_heads=4, num_kv_heads=2, head_dim=16, rotary_dim=4,
        moe_intermediate_size=32, shared_intermediate_size=24,
        num_experts=16, experts_per_token=3, rope_theta=10000.0,
        max_seq=256, experts_held=4, expert_first=4), **kw})


def qwen3_next_ep4() -> Qwen3NextConfig:
    """Qwen3-Next-80B-A3B-Instruct at its published widths as one chip's share
    of a 4-chip expert-parallel stage: the first 8 of the 48 published layers,
    ``G G G A G G G A`` (two whole periods of 3 : 1), 128 of the 512 experts
    of each — the router 512 wide, top 10 — and a quarter of the vocabulary
    (``benchmark/configs/qwen3-next-ep4-serve.json``)."""
    return Qwen3NextConfig(
        vocab_size=37984, hidden_size=2048, num_layers=8,
        linear_key_heads=16, linear_value_heads=32, linear_key_dim=128,
        linear_value_dim=128, num_heads=16, num_kv_heads=2, head_dim=256,
        rotary_dim=64, moe_intermediate_size=512,
        shared_intermediate_size=512, num_experts=512, experts_per_token=10,
        full_attention_interval=4, conv_kernel=4, rope_theta=1e7,
        rms_norm_eps=1e-6, max_seq=67584, experts_held=128, expert_first=0)


# -- parameters --------------------------------------------------------------

def init_params(cfg: Qwen3NextConfig, key, std: float = 0.02):
    """Random parameters, one jitted draw a tensor (``afmoe.init_params``'s
    scheme: exact arithmetic on uniform bits, so the CPU and the chip make
    the same values from one key).  The zero-centred norms' ``w`` are drawn
    around 0 and the output norm's plain scale around 1; the convolution's
    taps of standard deviation 0.3 (as jamba's); ``A`` in ``[0.06, 16]``
    (``A_log`` its logarithm) and ``dt_bias`` in ``[-6.9, -2.25]`` (a
    softplus of 0.001 to 0.1 at rest), the family's own start: a head keeps
    0.2 to 0.9999 of its state a token."""
    d, dt = cfg.hidden_size, cfg.dtype
    rows, hv = cfg.state_rows, cfg.linear_value_heads
    dim = cfg.head_dim
    qd, kvd = cfg.num_heads * dim, cfg.num_kv_heads * dim
    m, held = cfg.moe_intermediate_size, cfg.held[1]
    f32 = jnp.float32
    counter = iter(range(1 << 30))

    def draw(shape, dtype=dt, scale=std):
        return _uniform(jax.random.fold_in(key, next(counter)), shape, scale,
                        dtype)

    def centred(n):
        return draw((n,), f32, 0.05).astype(dt)

    def ffn(width):
        return {"w_gate": draw((d, width)), "w_up": draw((d, width)),
                "w_down": draw((width, d))}

    params = {"wte": draw((cfg.vocab_size, d))}
    for i in range(cfg.num_layers):
        p = {"ln_mix": centred(d), "ln_mlp": centred(d)}
        if cfg.keeps_state(i):
            p["gdn"] = {
                # the published in_proj_qkvz's columns as two matrices, [q |
                # k | v] (what the convolution reads) and [z]
                "w_qkv": draw((d, rows.conv_channels)),
                "w_z": draw((d, hv * cfg.linear_value_dim)),
                "w_ba": draw((d, 2 * hv)),           # columns [b | a]
                "conv_w": draw((cfg.conv_kernel, rows.conv_channels),
                               scale=0.3),
                "a_log": jnp.log(8.03 + draw((hv,), f32, 4.6)),
                "dt_bias": -4.575 + draw((hv,), f32, 1.342),
                "o_norm": (1.0 + draw((cfg.linear_value_dim,), f32,
                                      0.05)).astype(dt),
                "w_out": draw((hv * cfg.linear_value_dim, d))}
        else:
            p["attn"] = {
                # a head's columns are [q_i | gate_i]
                "w_q": draw((d, 2 * qd)),
                "w_kv": draw((d, 2 * kvd)),          # columns [k | v]
                "q_norm": centred(dim),
                "k_norm": centred(dim),
                "w_o": draw((qd, d))}
        p["moe"] = {
            "router": draw((d, cfg.num_experts)).astype(f32),
            "shared": ffn(cfg.shared_intermediate_size),
            "w_shared_gate": draw((d, 1)),
            "experts": {"w_gate": draw((held, d, m)),
                        "w_up": draw((held, d, m)),
                        "w_down": draw((held, m, d))}}
        params[f"h{i}"] = p
    params["ln_f"] = centred(d)
    params["head"] = draw((d, cfg.vocab_size))
    return params


# -- layer functions ---------------------------------------------------------

def _norm(x, w, eps):
    """The family's zero-centred RMSNorm: ``afmoe.rms_norm`` under the scale
    ``1 + w``."""
    return rms_norm(x, 1.0 + w.astype(jnp.float32), eps)


def _l2_norm(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


def _gdn(p, h, cfg: Qwen3NextConfig, state):
    """The Gated DeltaNet mixer on ``h`` (T, d).  ``state.conv`` and
    ``state.delta`` are the caller's: they read and write the sequence's tail
    and matrix state."""
    t, rows = h.shape[0], cfg.state_rows
    hk, hv, dk, dv = rows.key_heads, rows.heads, rows.key_dim, rows.value_dim
    f32 = jnp.float32
    with jax.named_scope("gdn"), jax.named_scope("proj"):
        # rows of tokens, pinned: at a decode step the compiler may write
        # this product with the slots across lanes, the convolution inherits
        # that form, and the group's tail array is then re-laid on the way in
        # and out of the program (serve.pool_check; models.ling)
        qkv = with_layout_constraint(jnp.dot(h, p["w_qkv"]),
                                     Layout(major_to_minor=(0, 1)))
        ba = jnp.dot(h, p["w_ba"], preferred_element_type=f32)
        z = jnp.dot(h, p["w_z"])
    qkv = state.conv(qkv, p["conv_w"], jnp.zeros((), f32), scope="gdn")
    with jax.named_scope("gdn"), jax.named_scope("gate"):
        qkv = jax.nn.silu(qkv.astype(f32))
        q = qkv[:, :hk * dk].reshape(t, hk, dk)
        k = qkv[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
        v = qkv[:, 2 * hk * dk:].reshape(t, hv, dv)
        q, k = _l2_norm(q) * dk ** -0.5, _l2_norm(k)
        beta = jax.nn.sigmoid(ba[:, :hv])
        g = -jnp.exp(p["a_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])
    o = state.delta(q, k, v, g[..., None], beta, scope="gdn")
    with jax.named_scope("gdn"):
        with jax.named_scope("gated_norm"):
            o = rms_norm(o, p["o_norm"], cfg.rms_norm_eps).reshape(t, -1) \
                * jax.nn.silu(z.astype(f32))
        with jax.named_scope("out_proj"):
            return jnp.dot(o.astype(h.dtype), p["w_out"])


def _attention(p, h, cfg: Qwen3NextConfig, positions, mixer):
    t, heads, dim = h.shape[0], cfg.num_heads, cfg.head_dim
    eps, r = cfg.rms_norm_eps, cfg.rotary_dim
    with jax.named_scope("attn"):
        with jax.named_scope("proj"):
            qg = jnp.dot(h, p["w_q"]).reshape(t, heads, 2 * dim)
            q, gate = qg[..., :dim], qg[..., dim:]
            kv = jnp.dot(h, p["w_kv"]).reshape(t, 2, cfg.num_kv_heads, dim)
            k, v = kv[:, 0], kv[:, 1]
            q, k = _norm(q, p["q_norm"], eps), _norm(k, p["k_norm"], eps)
            tabs = rope_tables(positions[None], r, cfg.rope_theta, q.dtype)

            def rotated(x):
                return jnp.concatenate(
                    [rope(x[None, ..., :r], positions[None], cfg.rope_theta,
                          tabs)[0], x[..., r:]], axis=-1)

            q, k = rotated(q), rotated(k)
        a = mixer(q, k, v)
        with jax.named_scope("gate"):
            a = (a.astype(jnp.float32)
                 * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(h.dtype)
        with jax.named_scope("out_proj"):
            return jnp.dot(a.reshape(t, heads * dim), p["w_o"])


def _moe(p, h, cfg: Qwen3NextConfig, token_mask):
    """The expert layer on ``h`` (T, d): ``(out, counters)``."""
    routed, counters = dropless_moe(
        h, p["router"], None, p["experts"], held=cfg.held,
        top_k=cfg.experts_per_token, router="softmax",
        route_norm=cfg.route_norm,
        token_mask=token_mask, impl=cfg.kernel_impl)
    with jax.named_scope("shared_expert"):
        shared = swiglu(p["shared"], h)
    with jax.named_scope("moe"), jax.named_scope("shared_gate"):
        gate = jax.nn.sigmoid(jnp.dot(h, p["w_shared_gate"],
                                      preferred_element_type=jnp.float32))
        return routed + (gate * shared).astype(h.dtype), counters


def block(p, x, cfg: Qwen3NextConfig, layer: int, positions, mixer,
          token_mask=None):
    """One decoder layer on ``x`` (T, d).  ``mixer`` is the caller's hook:
    ``mixer(q, k, v) -> (T, H, D)`` on an attention layer (it owns where K/V
    live), the state's ``conv`` / ``delta`` on a Gated DeltaNet layer.
    ``token_mask`` (T,) marks the real tokens: the others reach no expert and
    count in no counter.  Returns ``(x, counters)``: the expert layer's
    ``pairs``, ``experts_hit``, ``max_load``."""
    eps = cfg.rms_norm_eps
    with jax.named_scope("ln"):
        h = _norm(x, p["ln_mix"], eps)
    if cfg.keeps_state(layer):
        x = x + _gdn(p["gdn"], h, cfg, mixer)
    else:
        x = x + _attention(p["attn"], h, cfg, positions, mixer)
    with jax.named_scope("ln"):
        h = _norm(x, p["ln_mlp"], eps)
    out, counters = _moe(p["moe"], h, cfg, token_mask)
    return x + out, counters


def embed(params, ids, cfg: Qwen3NextConfig):
    with jax.named_scope("embed"):
        return params["wte"][ids]


def head(params, x, cfg: Qwen3NextConfig):
    """float32 logits of ``x`` (T, d)."""
    with jax.named_scope("head"):
        return jnp.dot(_norm(x, params["ln_f"], cfg.rms_norm_eps),
                       params["head"], preferred_element_type=jnp.float32)


class _FreshState:
    """The state hook of a whole sequence from zeros, nothing kept: the
    plain recurrence (``forward``)."""

    def __init__(self, cfg: Qwen3NextConfig):
        self.rows = cfg.state_rows

    def conv(self, u, w, b, scope=None):
        tail = jnp.zeros(((self.rows.d_conv - 1) * u.shape[1],), u.dtype)
        return causal_conv(u, tail, w, b, u.shape[0])[0]

    def delta(self, q, k, v, g, beta, scope=None):
        rows = self.rows
        state = jnp.zeros((rows.heads, rows.value_dim, rows.key_dim),
                          jnp.float32)
        return kda_recurrent(q, k, v, g, beta, state)[0]


def forward(params, ids, cfg: Qwen3NextConfig):
    """Logits (B, S, V) of whole sequences ``ids`` (B, S), nothing cached:
    the same block under dense causal attention and the recurrence from
    zeros."""
    def attend(q, k, v):
        return xla_attention(q[None], k[None], v[None], causal=True)[0]

    def one(seq):
        positions = jnp.arange(seq.shape[0], dtype=jnp.int32)
        x = embed(params, seq, cfg)
        for i in range(cfg.num_layers):
            mixer = _FreshState(cfg) if cfg.keeps_state(i) else attend
            with jax.named_scope(f"h{i}"):
                x, _ = block(params[f"h{i}"], x, cfg, i, positions, mixer)
        return head(params, x, cfg)
    return jax.lax.map(one, ids)
