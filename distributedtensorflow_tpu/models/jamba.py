"""A fourth decoder family: Mamba-1 layers (a selective state-space scan
over a per-sequence state) with an attention layer every
``attn_layer_period``, a dense SwiGLU in every layer.

The layer equations are those of ``transformers``' ``modeling_jamba.py`` (AI21
Jamba; the widths of a preset come from the model's ``config.json``).  ``d``
the model width, ``C = mamba_expand * d`` channels, ``N = mamba_d_state``,
``R = mamba_dt_rank``, RMSNorm everywhere, no bias but the convolution's and
``dt_proj``'s, pre-norm blocks:

- embedding ``x = E[ids]``; tied head ``logits = RMSNorm(x) E^T``;
- block ``i``: ``x = x + Mixer_i(N_in(x))`` then ``x = x + FFN(N_ff(x))``,
  ``FFN`` the dense SwiGLU (``num_experts`` 1: more are refused, not guessed);
- ``Mixer_i`` is attention where ``i % attn_layer_period ==
  attn_layer_offset``: ``q, k, v = h Wq, h Wk, h Wv`` (``H`` query heads on
  ``Hkv`` K/V heads of ``D``), **no rotary and no other position signal**,
  causal softmax at scale ``D ** -0.5``, output ``concat(o) Wo``;
- else Mamba: ``[u | z] = h W_in``; ``u' = silu(conv(u) + b_conv)``, a causal
  depthwise convolution over ``mamba_d_conv`` tokens; ``[r | B | C] = u' W_x``
  (``R | N | N``), each under its own RMSNorm with a learned scale; ``delta =
  softplus(r W_dt + b_dt)``; ``A = -exp(A_log)``; the scan of
  ``ops.ssm`` in float32, ``y_t = C_t . s_t + D u'_t``; output ``(y *
  silu(z)) W_out``.

**What is kept**: an attention layer caches K and V a token
(:attr:`JambaConfig.cache_rows`, 1 K/V head of 128: 512 B a token a layer at
the published widths); a Mamba layer keeps a fixed-size state a *sequence*
(:attr:`JambaConfig.state_rows`, an ``ops.ssm.SSMState``: the convolution
tail and the scan state, 358 KB a layer).  The block is written once and
calls ``mixer``, the one hook its caller owns: ``mixer(q, k, v)`` on an
attention layer, and on a Mamba layer an object with ``mixer.conv(u, w, b)``
and ``mixer.scan(u, delta, a, b, c, d)`` — the caller owns where K/V and the
state live, which form scans, and how many of the tokens are real.
Parameters are a plain tree of arrays created in bfloat16; ``A_log`` (stored
``(N, C)``, the published one transposed: channels across lanes), ``D`` and
``dt_bias`` in float32, as the scan computes.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import KVRows, xla_attention
from ..ops.ssm import SSMState, causal_conv, selective_scan
from .afmoe import _uniform, rms_norm, swiglu

__all__ = ["JambaConfig", "jamba_tiny", "jamba2_3b", "init_params", "block",
           "embed", "head", "forward"]


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    num_layers: int
    attn_layer_period: int
    attn_layer_offset: int
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_dt_rank: int = 160
    mamba_d_conv: int = 4
    num_experts: int = 1
    rms_norm_eps: float = 1e-6
    max_seq: int = 8192
    dtype: jnp.dtype = jnp.bfloat16
    #: "auto" = the Pallas kernels on a TPU, the plain formulations elsewhere
    kernel_impl: str = "auto"

    def __post_init__(self):
        if self.num_experts != 1:
            raise ValueError(
                "routed experts inside the jamba family (num_experts > 1) "
                "are not implemented: every layer's FFN is the dense SwiGLU "
                "(models.lfm2 is the state-group family that routes)")

    @property
    def channels(self) -> int:
        return self.mamba_expand * self.hidden_size

    def keeps_state(self, layer: int) -> bool:
        """Whether ``layer`` is a Mamba layer (keeps a state a sequence) and
        not an attention layer (caches rows a token)."""
        return layer % self.attn_layer_period != self.attn_layer_offset

    def window_of(self, layer: int) -> None:
        return None

    @property
    def cache_rows(self) -> KVRows:
        """What an attention layer caches a token (``ops.attention``)."""
        return KVRows(self.num_heads, self.num_kv_heads, self.head_dim)

    @property
    def state_rows(self) -> SSMState:
        """What a Mamba layer keeps a sequence (``ops.ssm``)."""
        return SSMState(self.channels, self.mamba_d_state, self.mamba_d_conv)


def jamba_tiny(**kw) -> JambaConfig:
    """CPU tests only: every mechanism of the family at toy widths, layer 1
    of 4 attending (4 query heads on 1 K/V head), the others Mamba."""
    return JambaConfig(**{**dict(
        vocab_size=128, hidden_size=64, num_heads=4, num_kv_heads=1,
        head_dim=16, intermediate_size=128, num_layers=4,
        attn_layer_period=3, attn_layer_offset=1, mamba_expand=2,
        mamba_d_state=16, mamba_dt_rank=8, mamba_d_conv=4, max_seq=128),
        **kw})


def jamba2_3b() -> JambaConfig:
    """AI21-Jamba2-3B whole, at its published widths: 28 layers, layers 7
    and 21 attending (20 query heads on 1 K/V head of 128), the other 26
    Mamba (5120 channels x 16 states), SwiGLU of 8192 in every layer, the
    whole vocabulary, the embedding tied to the head
    (``benchmark/configs/jamba2-3b-serve.json``: only the context is cut)."""
    return JambaConfig(
        vocab_size=65536, hidden_size=2560, num_heads=20, num_kv_heads=1,
        head_dim=128, intermediate_size=8192, num_layers=28,
        attn_layer_period=14, attn_layer_offset=7, mamba_expand=2,
        mamba_d_state=16, mamba_dt_rank=160, mamba_d_conv=4,
        rms_norm_eps=1e-6, max_seq=33792)


# -- parameters --------------------------------------------------------------

def init_params(cfg: JambaConfig, key, std: float = 0.02):
    """Random parameters, one jitted draw a tensor (``afmoe.init_params``'s
    scheme: exact arithmetic on uniform bits, so the CPU and the chip make
    the same values from one key).  Norm scales and ``D`` are drawn around 1;
    ``A_log`` around ``log(1..N)`` a channel and ``dt_bias`` in ``[-4.6,
    -2.3]`` (``delta`` of 0.01 to 0.1 at rest), the family's own start: a
    state that neither forgets at once nor never."""
    d, dt, c = cfg.hidden_size, cfg.dtype, cfg.channels
    n, r = cfg.mamba_d_state, cfg.mamba_dt_rank
    qd, kvd = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    f32 = jnp.float32
    counter = iter(range(1 << 30))

    def draw(shape, dtype=dt, scale=std):
        return _uniform(jax.random.fold_in(key, next(counter)), shape, scale,
                        dtype)

    def norm(width):
        return (1.0 + draw((width,), f32, 0.05)).astype(dt)

    a_log = jnp.asarray(np.log(np.arange(1, n + 1, dtype=np.float32)))
    params = {"wte": draw((cfg.vocab_size, d))}
    for i in range(cfg.num_layers):
        p = {"ln_in": norm(d), "ln_ff": norm(d),
             "mlp": {"w_gate": draw((d, cfg.intermediate_size)),
                     "w_up": draw((d, cfg.intermediate_size)),
                     "w_down": draw((cfg.intermediate_size, d))}}
        if cfg.keeps_state(i):
            p["mamba"] = {
                "w_in": draw((d, 2 * c)),
                "conv_w": draw((cfg.mamba_d_conv, c), scale=0.3),
                "conv_b": draw((c,), scale=0.1),
                "w_x": draw((c, r + 2 * n)),
                "dt_norm": norm(r), "b_norm": norm(n), "c_norm": norm(n),
                "w_dt": draw((r, c), scale=r ** -0.5),
                "dt_bias": -3.45 + draw((c,), f32, 0.66),
                "a_log": a_log[:, None] + draw((n, c), f32, 0.05),
                "d": 1.0 + draw((c,), f32, 0.05),
                "w_out": draw((c, d))}
        else:
            p["attn"] = {"wqkv": draw((d, qd + 2 * kvd)),
                         "wo": draw((qd, d))}
        params[f"h{i}"] = p
    params["ln_f"] = norm(d)
    return params


# -- layer functions ---------------------------------------------------------

def _mamba(p, h, cfg: JambaConfig, state):
    """The Mamba mixer on ``h`` (T, d).  ``state.conv`` and ``state.scan``
    are the caller's: they read and write the sequence's state."""
    c, n, r = cfg.channels, cfg.mamba_d_state, cfg.mamba_dt_rank
    eps = cfg.rms_norm_eps
    with jax.named_scope("mamba"), jax.named_scope("in_proj"):
        uz = jnp.dot(h, p["w_in"])
        u, z = uz[:, :c], uz[:, c:]
    u = state.conv(u, p["conv_w"], p["conv_b"])
    with jax.named_scope("mamba"):
        with jax.named_scope("conv"):
            u = jax.nn.silu(u.astype(jnp.float32)).astype(h.dtype)
        with jax.named_scope("x_proj"):
            rbc = jnp.dot(u, p["w_x"])
            rank = rms_norm(rbc[:, :r], p["dt_norm"], eps)
            b = rms_norm(rbc[:, r:r + n], p["b_norm"], eps)
            cc = rms_norm(rbc[:, r + n:], p["c_norm"], eps)
        with jax.named_scope("dt_proj"):
            delta = jax.nn.softplus(
                jnp.dot(rank, p["w_dt"], preferred_element_type=jnp.float32)
                + p["dt_bias"])
            a = -jnp.exp(p["a_log"])
    y = state.scan(u, delta, a, b, cc, p["d"])
    with jax.named_scope("mamba"):
        with jax.named_scope("gate"):
            y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(h.dtype)
        with jax.named_scope("out_proj"):
            return jnp.dot(y, p["w_out"])


def block(p, x, cfg: JambaConfig, layer: int, positions, mixer,
          token_mask=None):
    """One decoder layer on ``x`` (T, d).  ``mixer`` is the caller's hook:
    ``mixer(q, k, v) -> (T, H, D)`` on an attention layer (it owns where K/V
    live), the state's ``conv`` / ``scan`` on a Mamba layer.  ``positions``
    goes unused: no layer of the family takes a position signal.  Returns
    ``(x, None)``: no expert layer, no counters."""
    eps = cfg.rms_norm_eps
    t = x.shape[0]
    with jax.named_scope("ln"):
        h = rms_norm(x, p["ln_in"], eps)
    if cfg.keeps_state(layer):
        x = x + _mamba(p["mamba"], h, cfg, mixer)
    else:
        with jax.named_scope("attn"):
            qd = cfg.num_heads * cfg.head_dim
            kvd = cfg.num_kv_heads * cfg.head_dim
            with jax.named_scope("qkv"):
                qkv = jnp.dot(h, p["attn"]["wqkv"])
                q = qkv[:, :qd].reshape(t, cfg.num_heads, cfg.head_dim)
                k = qkv[:, qd:qd + kvd].reshape(
                    t, cfg.num_kv_heads, cfg.head_dim)
                v = qkv[:, qd + kvd:].reshape(
                    t, cfg.num_kv_heads, cfg.head_dim)
            a = mixer(q, k, v).reshape(t, -1).astype(x.dtype)
            with jax.named_scope("proj"):
                x = x + jnp.dot(a, p["attn"]["wo"])
    with jax.named_scope("ln"):
        h = rms_norm(x, p["ln_ff"], eps)
    with jax.named_scope("mlp"):
        return x + swiglu(p["mlp"], h), None


def embed(params, ids, cfg: JambaConfig):
    with jax.named_scope("embed"):
        return params["wte"][ids]


def head(params, x, cfg: JambaConfig):
    """float32 logits of ``x`` (T, d): the embedding, tied."""
    with jax.named_scope("head"):
        return jax.lax.dot_general(
            rms_norm(x, params["ln_f"], cfg.rms_norm_eps), params["wte"],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


class _FreshState:
    """The state hook of a whole sequence from zeros, nothing kept: the
    plain scan (``forward``)."""

    def __init__(self, cfg: JambaConfig):
        self.rows = cfg.state_rows

    def conv(self, u, w, b):
        tail = jnp.zeros(((self.rows.d_conv - 1) * u.shape[1],), u.dtype)
        return causal_conv(u, tail, w, b, u.shape[0])[0]

    def scan(self, u, delta, a, b, c, d):
        state = jnp.zeros((self.rows.d_state, u.shape[1]), jnp.float32)
        return selective_scan(u, delta, a, b, c, d, state)[0]


def forward(params, ids, cfg: JambaConfig):
    """Logits (B, S, V) of whole sequences ``ids`` (B, S), nothing cached:
    the same block under dense causal attention and a scan from zeros."""
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
