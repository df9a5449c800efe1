"""A ninth decoder family: Mamba-2 layers (a matrix state a head under a
scalar decay), latent expert layers and a few attention layers, **one part a
layer**.

The layer equations are those the keys of NVIDIA-Nemotron-3-Super-120B-A12B's
``config.json`` select (``model_type`` ``nemotron_h``;
``benchmark/configs/nemotron3-super-ep4-serve.json`` lists under ``assumed``
what the keys do not bear out).  ``d`` the model width, RMSNorm with a learned
scale, no bias but the convolution's, untied head ``logits = RMSNorm(x)
W_head``.  A layer is ONE pre-norm part, ``x = x + Part_l(RMSNorm(x))``, its
kind the ``l``-th character of :attr:`NemotronHConfig.pattern` (the published
``hybrid_override_pattern``):

- ``M``, **Mamba-2**: ``[z | xBC | dt] = h W_in`` (stored as ``w_z`` and
  ``w_xbcdt``); ``xBC = silu(conv(xBC) +
  b)``, a causal depthwise convolution of ``conv_kernel`` taps over all of
  ``x (H x P) | B (G x N) | C (G x N)``; ``dt = softplus(dt + dt_bias)`` a
  head, ``A = -exp(A_log)`` a scalar a head; the recurrence of ``ops.ssd``
  (head ``h`` reads the ``B`` and ``C`` of group ``h // (H / G)``); ``y =
  GroupRMSNorm_G(y * silu(z))``, the norm over each of the ``G`` groups of ``H
  P / G`` channels; output ``y W_out``;
- ``E``, **a latent expert layer**: the router scores the ``d``-wide token
  (``sigmoid``, the top ``k`` of ``score + bias``, weights normalised and
  scaled), the routed experts read and write ``u = h W_down``, a
  ``moe_latent_size``-wide projection of it: ``(sum_j w_j W2_j relu(W1_j
  u)^2) W_up``; the shared expert is ``W2 relu(W1 h)^2`` on ``h`` itself.  The
  experts held here are ``experts_held`` from ``expert_first``
  (``parallel.moe.dropless_moe``);
- ``*``, **attention**: ``H_q`` query heads on ``H_kv`` K/V heads of ``D``,
  causal softmax at ``D ** -0.5``, **no rotary and no other position signal**
  (:func:`_positions`: the one call where another reading would go).

**What is kept**: an attention layer caches K and V a token
(:attr:`NemotronHConfig.cache_rows`, 2 K/V heads of 128: 1,024 B a token at
the published widths); a Mamba-2 layer keeps a fixed-size state a *sequence*
(:attr:`NemotronHConfig.state_rows`, an ``ops.ssm.SSDState``: the convolution
tail and ``H`` matrices of ``P x N`` float32, 4.19 MB a layer); an expert layer
keeps nothing and is in no cache group.  The block is written once and calls
``mixer``, the one hook its caller owns: ``mixer(q, k, v)`` on an attention
layer, and on a Mamba-2 layer an object with ``mixer.conv(u, w, b, scope=)``
and ``mixer.ssd(x, dt, a, b, c, d)``.  Parameters are a plain tree of arrays
created in bfloat16; the router, its bias, ``A_log``, ``D`` and ``dt_bias`` in
float32.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from ..ops.attention import KVRows, xla_attention
from ..ops.ssd import CHUNK, ssd_recurrent
from ..ops.ssm import SSDState, causal_conv
from ..parallel.moe import dropless_moe
from .afmoe import _uniform, rms_norm

__all__ = ["NemotronHConfig", "nemotron_h_tiny", "nemotron3_super_ep4",
           "init_params", "block", "embed", "head", "forward"]

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int
    hidden_size: int
    pattern: str                    # "M", "E" or "*" a layer
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int                   # groups of B and C, and of the gated norm
    ssm_state_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    moe_intermediate_size: int      # a routed expert's width, in the latent
    moe_latent_size: int
    shared_intermediate_size: int   # the shared expert's, on the token
    num_experts: int
    experts_per_token: int
    conv_kernel: int = 4
    chunk_size: int = 128
    norm_eps: float = 1e-5
    route_scale: float = 5.0
    route_norm: bool = True
    max_seq: int = 8192
    dtype: jnp.dtype = jnp.bfloat16
    #: "auto" = the Pallas kernels on a TPU, the plain formulations elsewhere
    kernel_impl: str = "auto"
    #: the experts whose weights are held here, of the ``num_experts`` the
    #: router scores: ``experts_held`` from ``expert_first`` (None = all)
    experts_held: int | None = None
    expert_first: int = 0

    def __post_init__(self):
        if set(self.pattern) - {MAMBA, EXPERTS, ATTENTION}:
            raise ValueError(f"a layer is M, E or *: {self.pattern!r}")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("the Mamba-2 heads divide into n_groups groups")
        if self.chunk_size != CHUNK:
            raise ValueError(f"ops.ssd scans in chunks of {CHUNK} tokens")

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def held(self) -> tuple[int, int]:
        return (self.expert_first, self.experts_held or self.num_experts)

    def keeps_state(self, layer: int) -> bool:
        """Whether ``layer`` is a Mamba-2 layer (keeps a state a sequence)."""
        return self.pattern[layer] == MAMBA

    def groups_of(self, layer: int) -> tuple[str, ...]:
        """The cache group of ``layer``: the state group, the full group of
        K/V rows (which keeps lengths, admission and blocks), or none: an
        expert layer keeps nothing."""
        return {MAMBA: ("state",), ATTENTION: ("full",),
                EXPERTS: ()}[self.pattern[layer]]

    def window_of(self, layer: int) -> None:
        return None

    @property
    def cache_rows(self) -> KVRows:
        """What an attention layer caches a token (``ops.attention``)."""
        return KVRows(self.num_heads, self.num_kv_heads, self.head_dim)

    @property
    def state_rows(self) -> SSDState:
        """What a Mamba-2 layer keeps a sequence (``ops.ssm``)."""
        return SSDState(self.mamba_num_heads, self.mamba_head_dim,
                        self.n_groups, self.ssm_state_size, self.conv_kernel)


def nemotron_h_tiny(**kw) -> NemotronHConfig:
    """CPU tests only: every kind of layer at toy widths — 8 Mamba-2 heads of
    8 in 2 groups (4 heads a group) over 16 states, 4 query heads on 2 K/V
    heads, 16 experts of 24 in a latent of 32, top 3, the second quarter held."""
    return NemotronHConfig(**{**dict(
        vocab_size=128, hidden_size=64, pattern="MEM*EM", mamba_num_heads=8,
        mamba_head_dim=8, n_groups=2, ssm_state_size=16, num_heads=4,
        num_kv_heads=2, head_dim=16, moe_intermediate_size=24,
        moe_latent_size=32, shared_intermediate_size=48, num_experts=16,
        experts_per_token=3, max_seq=256, experts_held=4,
        expert_first=4), **kw})


def nemotron3_super_ep4() -> NemotronHConfig:
    """NVIDIA-Nemotron-3-Super-120B-A12B at its published widths as one chip's
    share of a 4-chip expert-parallel stage: the first 11 of the 88 published
    layers, ``MEMEMEM*EME`` (5 Mamba-2, 5 expert, 1 attention: one whole
    period, the published 40 : 40 : 8), 128 of the 512 experts of each expert
    layer — the router 512 wide, top 22 — and a quarter of the vocabulary
    (``benchmark/configs/nemotron3-super-ep4-serve.json``)."""
    return NemotronHConfig(
        vocab_size=32768, hidden_size=4096, pattern="MEMEMEM*EME",
        mamba_num_heads=128, mamba_head_dim=64, n_groups=8,
        ssm_state_size=128, num_heads=32, num_kv_heads=2, head_dim=128,
        moe_intermediate_size=2688, moe_latent_size=1024,
        shared_intermediate_size=5376, num_experts=512, experts_per_token=22,
        conv_kernel=4, chunk_size=128, norm_eps=1e-5, route_scale=5.0,
        max_seq=18432, experts_held=128, expert_first=0)


# -- parameters --------------------------------------------------------------

def init_params(cfg: NemotronHConfig, key, std: float = 0.02):
    """Random parameters, one jitted draw a tensor (``afmoe.init_params``'s
    scheme: exact arithmetic on uniform bits, so the CPU and the chip make
    the same values from one key).  Norm scales and ``D`` are drawn around 1
    and the selection bias around 0; the convolution's taps of standard
    deviation 0.3 (as jamba's); ``A_log`` in ``[0, log 16]`` (``A`` of 1 to
    16) and ``dt_bias`` in ``[-6.9, -2.25]`` (``dt`` of ``time_step_min``
    0.001 to ``time_step_max`` 0.1 at rest), the family's own start: a head
    keeps 0.2 to 0.999 of its state a token."""
    d, dt = cfg.hidden_size, cfg.dtype
    heads, rows = cfg.mamba_num_heads, cfg.state_rows
    qd, kvd = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    m, lat, held = (cfg.moe_intermediate_size, cfg.moe_latent_size,
                    cfg.held[1])
    f32 = jnp.float32
    counter = iter(range(1 << 30))

    def draw(shape, dtype=dt, scale=std):
        return _uniform(jax.random.fold_in(key, next(counter)), shape, scale,
                        dtype)

    def norm(n):
        return (1.0 + draw((n,), f32, 0.05)).astype(dt)

    params = {"wte": draw((cfg.vocab_size, d))}
    for i, kind in enumerate(cfg.pattern):
        p = {"ln": norm(d)}
        if kind == MAMBA:
            p["mamba"] = {
                # the published in_proj's columns [z | x | B | C | dt] as two
                # matrices, [z] and [x | B | C | dt]: one product of all
                # 18,560 is re-computed by the compiler for its later
                # consumers where memory is tight (my chip run, PR 54)
                "w_z": draw((d, cfg.d_inner)),
                "w_xbcdt": draw((d, rows.conv_channels + heads)),
                "conv_w": draw((cfg.conv_kernel, rows.conv_channels),
                               scale=0.3),
                "conv_b": draw((rows.conv_channels,), scale=0.1),
                "dt_bias": -4.575 + draw((heads,), f32, 1.342),
                "a_log": 1.386 + draw((heads,), f32, 0.8),
                "d": 1.0 + draw((heads,), f32, 0.05),
                "norm": norm(cfg.d_inner),
                "w_out": draw((cfg.d_inner, d))}
        elif kind == ATTENTION:
            p["attn"] = {"wqkv": draw((d, qd + 2 * kvd)),
                         "wo": draw((qd, d))}
        else:
            p["moe"] = {
                "router": draw((d, cfg.num_experts)).astype(f32),
                "bias": draw((cfg.num_experts,), f32, 0.05),
                "w_latent_down": draw((d, lat)),
                "w_latent_up": draw((lat, d)),
                "shared": {
                    "w_up": draw((d, cfg.shared_intermediate_size)),
                    "w_down": draw((cfg.shared_intermediate_size, d))},
                "experts": {"w_up": draw((held, lat, m)),
                            "w_down": draw((held, m, lat))}}
        params[f"h{i}"] = p
    params["ln_f"] = norm(d)
    params["head"] = draw((d, cfg.vocab_size))
    return params


# -- layer functions ---------------------------------------------------------

def gated_group_norm(y, z, scale, groups: int, eps: float):
    """``RMSNorm(y * silu(z))`` over each of ``groups`` groups of consecutive
    channels, float32 statistics: ``y`` float32, ``z``, ``scale`` (C,)."""
    t, c = y.shape
    y = (y * jax.nn.silu(z.astype(jnp.float32))).reshape(t, groups, -1)
    y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + eps)
    return y.reshape(t, c) * scale.astype(jnp.float32)


def relu2(p, h):
    """The ungated feed-forward ``W_down relu(W_up h)^2``."""
    u = jnp.maximum(jnp.dot(h, p["w_up"], preferred_element_type=jnp.float32),
                    0.0)
    return jnp.dot((u * u).astype(h.dtype), p["w_down"])


def _positions(q, k, positions):
    """What an attention layer does to ``q`` and ``k`` for order: nothing (the
    family's attention layers take no position embedding and the state layers
    carry order; ``rope_theta`` and ``partial_rotary_factor`` are read by no
    layer).  The other reading of the config is a rotary here."""
    return q, k


def _mamba2(p, h, cfg: NemotronHConfig, state):
    """The Mamba-2 mixer on ``h`` (T, d).  ``state.conv`` and ``state.ssd``
    are the caller's: they read and write the sequence's tail and matrix
    state."""
    t, rows = h.shape[0], cfg.state_rows
    heads, dim, g, n = rows.heads, rows.head_dim, rows.groups, rows.d_state
    inner, f32 = cfg.d_inner, jnp.float32
    with jax.named_scope("mamba2"), jax.named_scope("in_proj"):
        # rows of tokens, pinned: at a decode step of 128 slots the compiler
        # may write the product with the slots across lanes, the convolution
        # inherits that form, and the group's tail array is then re-laid on
        # the way in and out of the program (serve.pool_check; models.ling)
        xbcdt = with_layout_constraint(jnp.dot(h, p["w_xbcdt"]),
                                       Layout(major_to_minor=(0, 1)))
        xbc = xbcdt[:, :rows.conv_channels]
        dt = jax.nn.softplus(
            xbcdt[:, rows.conv_channels:].astype(f32) + p["dt_bias"])
        z = jnp.dot(h, p["w_z"])
    xbc = state.conv(xbc, p["conv_w"], p["conv_b"], scope="mamba2")
    with jax.named_scope("mamba2"), jax.named_scope("conv"):
        xbc = jax.nn.silu(xbc.astype(f32)).astype(h.dtype)
    y = state.ssd(
        xbc[:, :inner].reshape(t, heads, dim), dt, -jnp.exp(p["a_log"]),
        xbc[:, inner:inner + g * n].reshape(t, g, n),
        xbc[:, inner + g * n:].reshape(t, g, n), p["d"])
    with jax.named_scope("mamba2"):
        with jax.named_scope("gated_norm"):
            y = gated_group_norm(y.reshape(t, inner), z, p["norm"], g,
                                 cfg.norm_eps).astype(h.dtype)
        with jax.named_scope("out_proj"):
            return jnp.dot(y, p["w_out"])


def _attention(p, h, cfg: NemotronHConfig, positions, mixer):
    t = h.shape[0]
    with jax.named_scope("attn"):
        qd = cfg.num_heads * cfg.head_dim
        kvd = cfg.num_kv_heads * cfg.head_dim
        with jax.named_scope("qkv"):
            qkv = jnp.dot(h, p["wqkv"])
            q = qkv[:, :qd].reshape(t, cfg.num_heads, cfg.head_dim)
            k = qkv[:, qd:qd + kvd].reshape(t, cfg.num_kv_heads, cfg.head_dim)
            v = qkv[:, qd + kvd:].reshape(t, cfg.num_kv_heads, cfg.head_dim)
            q, k = _positions(q, k, positions)
        a = mixer(q, k, v).reshape(t, -1).astype(h.dtype)
        with jax.named_scope("proj"):
            return jnp.dot(a, p["wo"])


def _latent_moe(p, h, cfg: NemotronHConfig, token_mask):
    """The expert layer on ``h`` (T, d): ``(out, counters)``."""
    with jax.named_scope("moe"), jax.named_scope("latent_down"):
        u = jnp.dot(h, p["w_latent_down"])
    routed, counters = dropless_moe(
        h, p["router"], p["bias"], p["experts"], held=cfg.held,
        top_k=cfg.experts_per_token, route_norm=cfg.route_norm,
        route_scale=cfg.route_scale, token_mask=token_mask,
        impl=cfg.kernel_impl, experts_in=u)
    with jax.named_scope("moe"), jax.named_scope("latent_up"):
        routed = jnp.dot(routed, p["w_latent_up"])
    with jax.named_scope("shared_expert"):
        return routed + relu2(p["shared"], h), counters


def block(p, x, cfg: NemotronHConfig, layer: int, positions, mixer,
          token_mask=None):
    """One decoder layer on ``x`` (T, d): one part.  ``mixer`` is the
    caller's hook: ``mixer(q, k, v) -> (T, H, D)`` on an attention layer (it
    owns where K/V live), the state's ``conv`` / ``ssd`` on a Mamba-2 layer,
    unused on an expert layer.  ``token_mask`` (T,) marks the real tokens: the
    others reach no expert and count in no counter.  Returns ``(x,
    counters)``: an expert layer's ``pairs``, ``experts_hit``, ``max_load``,
    else None."""
    with jax.named_scope("ln"):
        h = rms_norm(x, p["ln"], cfg.norm_eps)
    kind = cfg.pattern[layer]
    if kind == MAMBA:
        return x + _mamba2(p["mamba"], h, cfg, mixer), None
    if kind == ATTENTION:
        return x + _attention(p["attn"], h, cfg, positions, mixer), None
    out, counters = _latent_moe(p["moe"], h, cfg, token_mask)
    return x + out, counters


def embed(params, ids, cfg: NemotronHConfig):
    with jax.named_scope("embed"):
        return params["wte"][ids]


def head(params, x, cfg: NemotronHConfig):
    """float32 logits of ``x`` (T, d)."""
    with jax.named_scope("head"):
        return jnp.dot(rms_norm(x, params["ln_f"], cfg.norm_eps),
                       params["head"], preferred_element_type=jnp.float32)


class _FreshState:
    """The state hook of a whole sequence from zeros, nothing kept: the
    plain recurrence (``forward``)."""

    def __init__(self, cfg: NemotronHConfig):
        self.rows = cfg.state_rows

    def conv(self, u, w, b, scope=None):
        tail = jnp.zeros(((self.rows.d_conv - 1) * u.shape[1],), u.dtype)
        return causal_conv(u, tail, w, b, u.shape[0])[0]

    def ssd(self, x, dt, a, b, c, d):
        rows = self.rows
        state = jnp.zeros((rows.heads, rows.head_dim, rows.d_state),
                          jnp.float32)
        return ssd_recurrent(x, dt, a, b, c, d, state)[0]


def forward(params, ids, cfg: NemotronHConfig):
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
