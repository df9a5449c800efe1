"""An eleventh decoder family: a *looped* stack.  The whole stack of layers
is run ``total_ut_steps`` times over the same weights, the final norm after
every pass, and an exit gate read after every pass.

The layer equations are those of the ``ouro`` modelling code the keys of
ByteDance/Ouro-2.6B's ``config.json`` belong to ("Scaling Latent Reasoning
via Looped Language Models", arXiv:2510.25741; the widths of a preset come
from the model's ``config.json``).  ``d`` the model width, ``L`` layers, ``H``
query heads on ``Hkv`` K/V heads of ``D``, RMSNorm with a plain learned scale
everywhere, no bias but the gate's::

    x      = E[ids]
    for u in 0 .. total_ut_steps - 1:            # the SAME L layers' weights
      for l in 0 .. L - 1:
        a  = Attn_l(N1_l(x))      # q, k, v = h Wq, h Wk, h Wv; rotary
                                  # (rotate-half, theta) on all D channels of
                                  # q and k; causal softmax(q k^T / sqrt(D)) v
                                  # in float32; out Wo.  K/V of pass u, layer
                                  # l live in cache layer slot u * L + l
        x  = x + N2_l(a)          # sandwich: the mixer's OUTPUT is normed
        m  = W_down(silu(W_gate N3_l(x)) * (W_up N3_l(x)))
        x  = x + N4_l(m)
      x    = N_f(x)               # the final norm after EVERY pass; its
                                  # output feeds pass u + 1
      g_u  = x w_g + b_g          # early_exit_gate: d -> 1, with bias
    lam_u  = sigmoid(g_u);  p_u = lam_u * prod_{j<u}(1 - lam_j) for u < last,
             p_last = prod_{j<last}(1 - lam_j)
    logits = x W_head             # x after the last pass: the untied head

**The gate is computed and does not choose**: ``early_exit_threshold`` is 1
in the published config, and the cumulative exit probability reaches 1 only
at the last pass, so the served logits are always the last pass's.  An exit
at a lower threshold is another configuration (the skipped passes' K/V would
have to be filled for later tokens) and has no knob here; ``p_u`` is what the
programs report (``serve.model``: the passes' exit mass).

**What is kept**: pass ``u`` of layer ``l`` attends the K/V that *pass u*
wrote for earlier tokens, so a token keeps ``total_ut_steps * L`` layer
slots of K and V (:attr:`OuroConfig.stack_passes`, which
``serve.kv_cache.layer_groups`` and the programs of ``serve.model`` read:
192 slots of 16 heads of 128 = 1,572,864 B a token at the published
widths).  The block is written once and calls ``attend(q, k, v)``, the one
hook its caller owns; the caller also owns the loop over the passes
(:func:`end_pass` is what a pass ends with).  Parameters are a plain tree of
arrays created in bfloat16 (``q``, ``k`` and ``v`` projections one matrix
``wqkv``, every matrix stored ``(in, out)``); the gate's bias float32.  The
residual stream is float32 (:func:`embed`), every matrix's input ``cfg.dtype``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops.attention import KVRows, xla_attention
from .afmoe import _uniform, rms_norm, swiglu
from .gpt import rope, rope_tables

__all__ = ["OuroConfig", "ouro_tiny", "ouro_2_6b", "init_params", "block",
           "embed", "end_pass", "head", "exit_distribution", "forward"]


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    vocab_size: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    num_layers: int
    #: how many times the stack of ``num_layers`` layers is run
    total_ut_steps: int = 4
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    max_seq: int = 65536
    dtype: jnp.dtype = jnp.bfloat16
    #: "auto" = the Pallas kernels on a TPU, the plain formulations elsewhere
    kernel_impl: str = "auto"

    @property
    def stack_passes(self) -> int:
        """Passes of the whole stack a token takes: the one attribute the
        cache (``passes * layers`` layer slots a group) and the programs (one
        device loop over the passes) read; a config without it runs its
        layers once."""
        return self.total_ut_steps

    def window_of(self, layer: int) -> None:
        return None

    @property
    def cache_rows(self) -> KVRows:
        """What a layer caches a token a pass (``ops.attention``)."""
        return KVRows(self.num_heads, self.num_kv_heads, self.head_dim)


def ouro_tiny(**kw) -> OuroConfig:
    """CPU tests only: every mechanism of the family at toy widths, 3 layers
    run 4 times (12 cache layer slots), 2 heads of 32."""
    return OuroConfig(**{**dict(
        vocab_size=256, hidden_size=64, num_heads=2, num_kv_heads=2,
        head_dim=32, intermediate_size=128, num_layers=3, total_ut_steps=4,
        max_seq=256), **kw})


def ouro_2_6b() -> OuroConfig:
    """ByteDance/Ouro-2.6B whole, at its published widths: 48 layers run 4
    times, 16 query = 16 K/V heads of 128, SwiGLU of 5,632, the whole
    vocabulary, the head untied: 2,667,974,657 parameters
    (``benchmark/configs/ouro-2.6b-serve.json``: only the positions are
    cut)."""
    return OuroConfig(
        vocab_size=49152, hidden_size=2048, num_heads=16, num_kv_heads=16,
        head_dim=128, intermediate_size=5632, num_layers=48,
        total_ut_steps=4, rope_theta=1e6, rms_norm_eps=1e-6, max_seq=1536)


# -- parameters --------------------------------------------------------------

def init_params(cfg: OuroConfig, key, std: float = 0.02):
    """Random parameters, one jitted draw a tensor (``afmoe.init_params``'s
    scheme: exact arithmetic on uniform bits, so the CPU and the chip make
    the same values from one key).  Norm scales are drawn around 1; the
    gate's weight like a matrix column and its bias around 0, so that the
    passes' exit mass is neither all at the first pass nor all at the last."""
    d, dt, f = cfg.hidden_size, cfg.dtype, cfg.intermediate_size
    qd, kvd = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    counter = iter(range(1 << 30))

    def draw(shape, dtype=dt, scale=std):
        return _uniform(jax.random.fold_in(key, next(counter)), shape, scale,
                        dtype)

    def norm(width):
        return (1.0 + draw((width,), jnp.float32, 0.05)).astype(dt)

    params = {"wte": draw((cfg.vocab_size, d))}
    for i in range(cfg.num_layers):
        params[f"h{i}"] = {
            "ln_in": norm(d), "ln_attn_out": norm(d),
            "ln_mlp_in": norm(d), "ln_mlp_out": norm(d),
            "attn": {"wqkv": draw((d, qd + 2 * kvd)), "wo": draw((qd, d))},
            "mlp": {"w_gate": draw((d, f)), "w_up": draw((d, f)),
                    "w_down": draw((f, d))}}
    params["ln_f"] = norm(d)
    params["exit_gate"] = {"w": draw((d,)),
                           "b": draw((1,), jnp.float32, 0.5)}
    params["head"] = draw((d, cfg.vocab_size))
    return params


# -- layer functions ---------------------------------------------------------

def block(p, x, cfg: OuroConfig, layer: int, positions, attend,
          token_mask=None):
    """One decoder layer on ``x`` (T, d), one pass.  ``attend(q, k, v) ->
    (T, H, D)`` is the caller's hook: it owns where the K/V of this pass of
    this layer live.  Returns ``(x, None)``: no expert layer, no counters."""
    eps, dt = cfg.rms_norm_eps, cfg.dtype
    t = x.shape[0]
    qd, kvd = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    with jax.named_scope("norm_in"):
        h = rms_norm(x, p["ln_in"], eps).astype(dt)
    with jax.named_scope("attn"):
        with jax.named_scope("qkv"):
            qkv = jnp.dot(h, p["attn"]["wqkv"])
            q = qkv[:, :qd].reshape(t, cfg.num_heads, cfg.head_dim)
            k = qkv[:, qd:qd + kvd].reshape(t, cfg.num_kv_heads, cfg.head_dim)
            v = qkv[:, qd + kvd:].reshape(t, cfg.num_kv_heads, cfg.head_dim)
        with jax.named_scope("rope"):
            tabs = rope_tables(positions[None], cfg.head_dim, cfg.rope_theta,
                               q.dtype)
            q = rope(q[None], positions[None], cfg.rope_theta, tabs)[0]
            k = rope(k[None], positions[None], cfg.rope_theta, tabs)[0]
        a = attend(q, k, v).reshape(t, -1).astype(dt)
        with jax.named_scope("proj"):
            a = jnp.dot(a, p["attn"]["wo"])
    with jax.named_scope("norm_attn_out"):
        x = x + rms_norm(a, p["ln_attn_out"], eps).astype(x.dtype)
    with jax.named_scope("norm_mlp_in"):
        h = rms_norm(x, p["ln_mlp_in"], eps).astype(dt)
    with jax.named_scope("mlp"):
        m = swiglu(p["mlp"], h)
    with jax.named_scope("norm_mlp_out"):
        return x + rms_norm(m, p["ln_mlp_out"], eps).astype(x.dtype), None


def embed(params, ids, cfg: OuroConfig):
    """The residual stream, float32: it is carried through ``passes x
    layers`` block applications (192), grows to an rms of ~10 inside a pass
    (two unit-rms terms a block) and would round away ~1 % of every term
    in bfloat16 (the matrices' inputs are ``cfg.dtype``)."""
    with jax.named_scope("embed"):
        return params["wte"][ids].astype(jnp.float32)


def end_pass(params, x, cfg: OuroConfig):
    """What every pass of the stack ends with, on ``x`` (T, d): the final
    norm, whose output is the next pass's input (and after the last pass the
    head's), and the exit gate's logit ``g`` (T,) in float32."""
    with jax.named_scope("ut_norm"):
        x = rms_norm(x, params["ln_f"], cfg.rms_norm_eps)
    with jax.named_scope("ut_gate"):
        gate = params["exit_gate"]
        g = jnp.dot(x.astype(jnp.float32), gate["w"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST) + gate["b"][0]
    return x, g


def head(params, x, cfg: OuroConfig):
    """float32 logits of ``x`` (T, d), the last pass's normed output: the
    untied head alone."""
    with jax.named_scope("head"):
        return jnp.dot(x.astype(cfg.dtype), params["head"],
                       preferred_element_type=jnp.float32)


def exit_distribution(gates):
    """``p`` (passes, T) from the gate logits ``gates`` (passes, T): the
    probability of leaving after pass ``u``, the last pass taking what is
    left (module text).  Sums to 1 over the passes."""
    lam = jax.nn.sigmoid(gates.astype(jnp.float32))
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([(lam * before)[:-1], before[-1:]], axis=0)


def forward(params, ids, cfg: OuroConfig, with_exit: bool = False):
    """Logits (B, S, V) of whole sequences ``ids`` (B, S), nothing cached:
    the same block under dense causal attention, a Python loop over the
    passes.  ``with_exit`` returns ``(logits, p)``, ``p`` (B, passes, S) the
    exit distribution a position."""
    def one(seq):
        positions = jnp.arange(seq.shape[0], dtype=jnp.int32)

        def attend(q, k, v):
            return xla_attention(q[None], k[None], v[None], causal=True)[0]

        x = embed(params, seq, cfg)
        gates = []
        for _ in range(cfg.stack_passes):
            for i in range(cfg.num_layers):
                with jax.named_scope(f"h{i}"):
                    x, _ = block(params[f"h{i}"], x, cfg, i, positions,
                                 attend)
            x, g = end_pass(params, x, cfg)
            gates.append(g)
        return head(params, x, cfg), exit_distribution(jnp.stack(gates))
    logits, p = jax.lax.map(one, ids)
    return (logits, p) if with_exit else logits
