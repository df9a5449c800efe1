"""GPT-style decoder LM — the long-context flagship.

No decoder LM exists in the reference stack (its longest-sequence workload
is BERT-base MLM at 512 tokens — SURVEY.md §5.7); this model is the vehicle
for the framework's first-class long-context capability: its attention is
pluggable, so the same module runs

- dense causal attention (Pallas flash kernel via ``ops.attention``), or
- **sequence-parallel** ring / Ulysses attention over the ``seq`` mesh axis
  (``parallel.ring_attention.sequence_parallel_attention_fn``) for
  sequences too long for one device's HBM.

TPU-first choices: bfloat16 activations with float32 layer-norm/softmax,
rotary position embeddings (no learned position table to shard), pre-LN
blocks, Megatron-ready kernel names for the ``model``-axis layout in
:func:`gpt_layout`, and ``jax.checkpoint`` over blocks (remat) so long
sequences trade FLOPs for HBM.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention import KVRows, dot_product_attention
from ..ops.layernorm import layer_norm
from ..ops.xent import tied_head_logits
from ..parallel import mesh as mesh_lib
from ..parallel.sharding import LayoutMap, kernel_axes
from ..runtime import on_tpu
from .layers import FusedLayerNorm, dense, sow_nonfinite

AttnFn = Callable[[jax.Array, jax.Array, jax.Array], jax.Array]


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    #: >0 chunks the MLP over the sequence (ops.blockwise): the (B, S, d_ff)
    #: intermediate never materializes whole — the blockwise-FFN half of the
    #: long-context recipe (SURVEY.md §5.7). Must divide the sequence length.
    ffn_chunk_size: int = 0
    max_seq: int = 2048
    dropout_rate: float = 0.0
    rope_theta: float = 10000.0
    dtype: jnp.dtype = jnp.bfloat16
    #: ``jax.checkpoint`` around every block (:func:`remat_block`): a layer
    #: keeps its (B, S, d) input and the backward runs the block again.
    #: Where the block's attention took the ``"qkv_tiles"`` form
    #: (:func:`attention_layout`) the layer also keeps what the flash
    #: kernel itself made, o (B, S, H*D) and the log-sum-exp (B, H, S)
    #: float32, so the backward does not run ``flash_fwd`` a second time:
    #: ``B*S*(H*D*itemsize + H*4)`` bytes a layer more (138 MB at 64 x
    #: 1024 of GPT-2 medium in bf16, 3.3 GB over its 24 layers).  Every
    #: other form keeps the input alone.
    remat: bool = True
    #: Checkpoint ONLY the attention op inside each block (meaningful when
    #: ``remat`` is False): backward recomputes the (S, S) score/softmax
    #: tensors — the bulk of a short-seq block's activation memory — for
    #: ~5% extra FLOPs, so remat-free-speed training fits ~2x the batch.
    remat_attn: bool = False
    #: Attention kernel: "auto" (Pallas flash on TPU past the evidenced
    #: seq threshold), "pallas" (force the flash kernel — its backward
    #: stores no (S, S) tensors, so remat-free training fits much larger
    #: batches), or "xla".
    attn_impl: str = "auto"
    #: Sliding-window attention (Mistral-style): token i attends keys in
    #: ``(i - attn_window, i]``.  None = full causal.  The flash kernels
    #: skip out-of-band blocks (O(S*window) cost); the decode path masks
    #: the cache the same way, so training and serving agree.  New
    #: capability beyond the reference stack.
    attn_window: int | None = None
    #: Grouped-query attention: number of K/V heads; each group of
    #: ``num_heads // num_kv_heads`` query heads shares one K/V head.
    #: None = num_heads (MHA — every existing preset, param-tree
    #: unchanged).  Shrinks the decode KV cache and its per-step HBM
    #: stream by the group factor — the binding constraint of a decode
    #: step.  New capability
    #: beyond the reference stack (tf-classic predates GQA entirely).
    num_kv_heads: int | None = None
    #: LM-head loss kernel: "auto" (Pallas fused head on TPU — ``PERF.md``
    #: section 6, PR 42 — and "chunked" elsewhere, keeping CPU
    #: tests on the fp32 golden path), "chunked" (lax.scan over token
    #: chunks, ops/xent.py), "chunked_bf16" (bf16 logits tiles), or
    #: "fused" (Pallas ops/fused_xent.py unconditionally — logits never
    #: leave VMEM; ~4.1x less head HBM traffic at equal FLOPs).
    xent_impl: str = "auto"
    #: Quantized compute (ops/quant.py): None/"none" = full-width; "int8"
    #: / "int8_stochastic" / "fp8" route every block dense matmul (qkv,
    #: proj, fc_in, fc_out) through the per-channel-absmax quantized
    #: dot with a straight-through-estimator backward.  Embeddings, layer
    #: norms, rope, and the fp32 tied head stay high-precision.  Param
    #: tree is unchanged, so checkpoints move between modes freely.
    quant: str | None = None

    def __post_init__(self):
        from ..ops.quant import validate_mode

        validate_mode(self.quant)
        kv = self.num_kv_heads
        if kv is not None and (kv <= 0 or self.num_heads % kv):
            raise ValueError(
                f"num_kv_heads={kv} must divide num_heads={self.num_heads}"
            )
        if self.attn_window is not None and self.attn_window < 1:
            raise ValueError(
                f"attn_window={self.attn_window} must be >= 1 (None = full)"
            )

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def kernel_impl(self) -> str:
        """``attn_impl`` under the name the serving programs ask a
        configuration of any family for."""
        return self.attn_impl

    def window_of(self, layer: int) -> int | None:
        return self.attn_window

    @property
    def cache_rows(self) -> KVRows:
        """What a served layer caches a token (``ops.attention``)."""
        return KVRows(self.num_heads, self.kv_heads, self.head_dim)


def gpt_small() -> GPTConfig:
    return GPTConfig()


def gpt_medium() -> GPTConfig:
    """GPT-2-medium (~350M params): 24 layers, hidden 1024, 16 heads.

    Wider matmuls (K=1024 = 8 full MXU passes vs small's 6) raise MXU
    efficiency; the measured single-chip MFU exceeds gpt_small's."""
    return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16,
                     intermediate_size=4096)


def gpt_tiny() -> GPTConfig:
    """Test-size config (2 layers, 128 hidden, short context)."""
    return GPTConfig(
        vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
        intermediate_size=256, max_seq=256, remat=False,
    )


def cached_attention_with_vars(module: nn.Module, q, k, v,
                               max_seq: int,
                               window: int | None = None) -> jax.Array:
    """Flax "cache"-collection plumbing around
    :func:`..ops.attention.cached_decode_attention` — the ONE place the
    cache layout (cached_key/cached_value/cache_index) is defined, shared
    by every serving path (GPT and seq2seq decoder self-attention)."""
    from ..ops.attention import cached_decode_attention

    b, _, _, d = q.shape
    h_kv = k.shape[2]  # kv heads: < q heads under GQA (smaller cache)
    # (B, Hkv, S, D): per-step writes are contiguous (D,) rows and the
    # Pallas decode kernel streams (Hkv, S, D) tiles — see the decode-perf
    # history on ops.attention.cached_decode_attention.
    cached_k = module.variable(
        "cache", "cached_key",
        lambda: jnp.zeros((b, h_kv, max_seq, d), k.dtype)
    )
    cached_v = module.variable(
        "cache", "cached_value",
        lambda: jnp.zeros((b, h_kv, max_seq, d), v.dtype)
    )
    cache_ix = module.variable(
        "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
    )
    out, cached_k.value, cached_v.value, cache_ix.value = (
        cached_decode_attention(
            q, k, v, cached_k.value, cached_v.value, cache_ix.value,
            window=window,
        )
    )
    return out


def rope_tables(
    positions: jax.Array, d: int, theta: float, dtype
) -> tuple[jax.Array, jax.Array]:
    """Sign-folded (B, S, 1, D) cos/sin tables for :func:`rope`.

    Split out so the trunk can compute the trig ONCE per step and share
    the tables across every layer's q and k rotation (2 x num_layers
    calls otherwise; under block remat each call is also recomputed in
    the backward, whereas hoisted tables are saved residuals).  Trig in
    fp32, then cast to the compute ``dtype`` the combine runs at."""
    d_half = d // 2
    freqs = theta ** (-jnp.arange(0, d_half, dtype=jnp.float32) / d_half)
    angles = positions[:, :, None].astype(jnp.float32) * freqs  # (B, S, Dh)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    cos_f = jnp.concatenate([cos, cos], axis=-1)[:, :, None, :]
    sin_f = jnp.concatenate([-sin, sin], axis=-1)[:, :, None, :]
    return cos_f.astype(dtype), sin_f.astype(dtype)


def rope_lane_tables(
    positions: jax.Array, d: int, theta: float
) -> tuple[jax.Array, jax.Array]:
    """:func:`rope_tables` as the flash kernels that rotate in VMEM take
    them (``ops.flash_attention.flash_attention_qkv``): float32, (B, S,
    max(128, D)), a head's ``[cos, cos]`` / ``[-sin, sin]`` repeated
    across a 128-lane tile.  Rows that all hold the same positions are
    handed over as one (``positions`` of shape (1, S)): the kernel then
    fetches the tables once, not once a row."""
    cos, sin = rope_tables(positions, d, theta, jnp.float32)
    reps = max(1, 128 // d)
    return tuple(jnp.tile(t[:, :, 0, :], (1, 1, reps)) for t in (cos, sin))


def rope(x: jax.Array, positions: jax.Array, theta: float,
         tables: tuple[jax.Array, jax.Array] | None = None) -> jax.Array:
    """Rotary embedding, (B, S, H, D) with D even.

    Who rotates here, outside any kernel: every caller but the training
    block on its ``"qkv_tiles"`` path (:func:`attention_layout`), whose
    flash kernels rotate q and k in VMEM — the served blocks (this file's
    serving definition, ``models.afmoe`` / ``joyai``), the decode path,
    sequence-parallel ``attn_fn``, GQA, the XLA attention, seq2seq.

    Lane-friendly formulation: in the textbook ``split -> 4 muls on
    (…, D/2) -> concat`` form every elementwise op runs on D/2=32-wide
    tensors (a quarter of the 128-lane VPU tile) and XLA materializes
    half-width copies around them.  Folding the signs into a full-width
    sin pattern turns it into ONE half-swap relayout plus two muls and an
    add at full D width; per-element arithmetic is bit-identical
    (x1*cos + x2*(-sin) == x1*cos - x2*sin in IEEE fp).

    The combine runs in ``x.dtype`` (round-4 retune): upcasting the
    already-bf16-rounded x to fp32 doubled the elementwise byte traffic
    for one extra rounding's worth of precision that the final
    cast-back discarded anyway.  fp32 inputs keep fully-fp32 math.
    ``tables`` are the precomputed :func:`rope_tables` (cast here if
    their dtype differs from x)."""
    d = x.shape[-1]
    d_half = d // 2
    if tables is None:
        tables = rope_tables(positions, d, theta, x.dtype)
    cos_f, sin_f = (t.astype(x.dtype) for t in tables)
    # Half-swap via a constant permutation matmul: the MXU moves the
    # halves (exact — R is 0/1), the VPU never runs a sub-lane relayout.
    r = jnp.block([
        [jnp.zeros((d_half, d_half), x.dtype),
         jnp.eye(d_half, dtype=x.dtype)],
        [jnp.eye(d_half, dtype=x.dtype),
         jnp.zeros((d_half, d_half), x.dtype)],
    ])  # x @ r == concat([x2, x1])
    x_rot = jnp.einsum("bshd,de->bshe", x, r)
    return x * cos_f + x_rot * sin_f


def attention_layout(cfg: GPTConfig, seq: int, *, n_heads: int | None = None,
                     n_kv: int | None = None) -> str:
    """The form the training block's dense causal attention lowers to over
    ``seq`` positions (``ops.flash_attention.qkv_layout``): ``"qkv_tiles"``
    (the flash kernels read the fused projection as it lies and rotate in
    VMEM), ``"bhsd"`` (split, :func:`rope`, transposes, the (B, H, S, D)
    kernels) or ``"xla"``.  Chosen by shape and mesh, read under the
    context mesh; ``n_heads`` / ``n_kv`` are a manual shard's counts."""
    from ..ops.flash_attention import qkv_layout

    return qkv_layout(
        seq, n_heads or cfg.num_heads, n_kv or cfg.kv_heads, cfg.head_dim,
        cfg.dtype, implementation=cfg.attn_impl)


def block_rope_tables(cfg: GPTConfig, positions: jax.Array | None,
                      shape: tuple[int, int], *, fused: bool):
    """The rotation's tables for every block of a trunk over ``shape`` =
    (B, S) tokens, computed once a step: lane tiles when the blocks'
    attention rotates in its kernels (``fused``), :func:`rope`'s
    otherwise.  ``positions`` None is ``arange(S)`` in every row."""
    rows_alike = positions is None
    if rows_alike:
        positions = jnp.arange(shape[1])[None]
    if fused:
        return rope_lane_tables(positions, cfg.head_dim, cfg.rope_theta)
    if rows_alike:
        positions = jnp.broadcast_to(positions, shape)
    return rope_tables(positions, cfg.head_dim, cfg.rope_theta, cfg.dtype)


def remat_block(block):
    """``block`` — a block's Module class (``__call__(x, positions,
    deterministic, rope_tabs)``) or a function that applies one — under
    ``jax.checkpoint``: the backward recomputes the block from its input,
    but for the two residuals the flash kernel of the ``"qkv_tiles"`` form
    names (``ops.flash_attention.RESIDUAL_O`` / ``RESIDUAL_LSE``), which
    are kept.  What a block keeps is the block's property, so every trunk
    that remats one (``GPTLM``, ``GPTMoELM``, the pipeline stages) comes
    here.  A block whose attention took another form holds no such name,
    saves its input alone and compiles to the program a policy-less
    checkpoint gives."""
    from ..ops.flash_attention import RESIDUAL_LSE, RESIDUAL_O

    policy = jax.checkpoint_policies.save_only_these_names(
        RESIDUAL_O, RESIDUAL_LSE)
    if isinstance(block, type) and issubclass(block, nn.Module):
        # static_argnums counts __call__'s args INCLUDING self:
        # deterministic is index 3 (rope_tabs at 4 is a traced array;
        # verified by tests/test_gpt.py::test_remat_path_trains).
        return nn.remat(block, static_argnums=(3,), policy=policy)
    return jax.checkpoint(block, policy=policy)


class CausalSelfAttention(nn.Module):
    cfg: GPTConfig
    attn_fn: AttnFn | None = None  # None = dense causal (flash-capable)
    decode: bool = False  # KV-cache incremental decoding (serving path)
    #: Manual tensor parallelism (the pipeline's full-manual shard_map
    #: region, where GSPMD cannot partition the kernels): ``n_heads`` /
    #: ``n_kv`` override the LOCAL head counts (this shard's slice of the
    #: fused qkv / proj kernels), and ``reduce_fn`` — typically
    #: ``lax.psum(., "model")`` — completes the row-parallel output
    #: projection.  Defaults (None) are exactly the historical behavior.
    n_heads: int | None = None
    n_kv: int | None = None
    reduce_fn: Any = None

    @nn.compact
    def __call__(self, x, positions, deterministic: bool, rope_tabs=None):
        cfg = self.cfg
        head_dim = cfg.hidden_size // cfg.num_heads
        nh = self.n_heads or cfg.num_heads
        n_kv = self.n_kv or cfg.kv_heads
        # Fused QKV projection: one large MXU matmul (column-parallel under
        # the model axis — gpt_layout shards the fused output dim).  Under
        # GQA (kv_heads < num_heads) the K/V column groups shrink; at the
        # MHA default the fused dim is exactly 3E and the split matches
        # the historical jnp.split(qkv, 3) — same param tree, same values.
        q_width = nh * head_dim
        kv_width = n_kv * head_dim
        qkv = dense(
            q_width + 2 * kv_width, dtype=cfg.dtype,
            quant=cfg.quant, use_bias=False, name="qkv",
        )(x)
        if (
            not self.decode and self.attn_fn is None
            and attention_layout(cfg, x.shape[1], n_heads=nh,
                                 n_kv=n_kv) == "qkv_tiles"
        ):
            # The kernels read the projection as it lies, rotate q and k
            # in VMEM and hand back o as the output projection takes it.
            from ..ops.flash_attention import flash_attention_qkv

            if rope_tabs is None or rope_tabs[0].ndim != 3:
                rope_tabs = rope_lane_tables(
                    positions, head_dim, cfg.rope_theta)
            out = flash_attention_qkv(
                qkv, nh, rope=rope_tabs, causal=True,
                window=cfg.attn_window)
            return self._project(out)
        q = qkv[..., :q_width]
        k = qkv[..., q_width:q_width + kv_width]
        v = qkv[..., q_width + kv_width:]
        q = q.reshape(*x.shape[:2], nh, head_dim)
        k = k.reshape(*x.shape[:2], n_kv, head_dim)
        v = v.reshape(*x.shape[:2], n_kv, head_dim)
        q = rope(q, positions, cfg.rope_theta, rope_tabs)
        k = rope(k, positions, cfg.rope_theta, rope_tabs)
        if self.decode:
            if self.attn_fn is not None:
                raise ValueError(
                    "decode=True uses dense cached attention; a custom "
                    "attn_fn (e.g. sequence-parallel) is not supported in "
                    "decode mode — shard the batch, not the sequence, when "
                    "serving"
                )
            out = self._cached_attention(q, k, v)
        elif self.attn_fn is not None:
            if n_kv != nh:
                raise ValueError(
                    "GQA (kv_heads < num_heads) is not supported with a "
                    "custom attn_fn (ring/Ulysses sequence parallelism "
                    "resharding assumes equal q/kv head counts) — use the "
                    "dense/flash path or set kv_heads=num_heads"
                )
            if cfg.attn_window is not None:
                raise ValueError(
                    "attn_window is not supported with a custom attn_fn "
                    "(sequence-parallel attention masks per K/V chunk) — "
                    "use the dense/flash path"
                )
            out = self.attn_fn(q, k, v)
        else:
            out = dot_product_attention(
                q, k, v, causal=True, window=cfg.attn_window,
                implementation=cfg.attn_impl,
            )
        return self._project(out.reshape(*x.shape[:2], q_width))

    def _project(self, out):
        """Row-parallel output projection (its input dim is head-sharded)."""
        cfg = self.cfg
        out = dense(
            cfg.hidden_size, dtype=cfg.dtype, quant=cfg.quant,
            use_bias=False, name="proj",
        )(out)
        if self.reduce_fn is not None:
            out = self.reduce_fn(out)
        return out

    def _cached_attention(self, q, k, v):
        """One decode step against the KV cache (shared helper)."""
        return cached_attention_with_vars(self, q, k, v, self.cfg.max_seq,
                                          window=self.cfg.attn_window)


class GPTBlock(nn.Module):
    cfg: GPTConfig
    attn_fn: AttnFn | None = None
    decode: bool = False
    #: Manual tensor parallelism (see :class:`CausalSelfAttention`):
    #: per-shard head counts / MLP width, and the cross-shard reduction
    #: applied to the attention projection and MLP outputs (row-parallel
    #: psum).  Defaults are the historical single-shard behavior.
    n_heads: int | None = None
    n_kv: int | None = None
    ffn_size: int | None = None
    reduce_fn: Any = None

    @nn.compact
    def __call__(self, x, positions, deterministic: bool, rope_tabs=None):
        cfg = self.cfg
        h = FusedLayerNorm(name="ln1")(x)
        attn_cls = CausalSelfAttention
        if cfg.remat_attn and not self.decode and not self.is_initializing():
            # static_argnums counts __call__'s args including self:
            # deterministic is index 3 (same convention as the block remat;
            # rope_tabs at 4 is a traced array input, NOT static).
            attn_cls = nn.remat(CausalSelfAttention, static_argnums=(3,))
        x = x + attn_cls(
            cfg, self.attn_fn, self.decode, name="attn",
            n_heads=self.n_heads, n_kv=self.n_kv, reduce_fn=self.reduce_fn,
        )(h, positions, deterministic, rope_tabs)
        h = FusedLayerNorm(name="ln2")(x)
        # Column- then row-parallel MLP (Megatron split over `model`).
        fc_in = dense(self.ffn_size or cfg.intermediate_size,
                      dtype=cfg.dtype,
                      quant=cfg.quant, use_bias=False, name="fc_in")
        fc_out = dense(cfg.hidden_size, dtype=cfg.dtype, quant=cfg.quant,
                       use_bias=False, name="fc_out")

        def mlp(hc):
            return fc_out(nn.gelu(fc_in(hc)))

        if cfg.ffn_chunk_size > 0 and not self.decode:
            from ..ops.blockwise import blockwise_map

            if h.shape[1] % cfg.ffn_chunk_size:
                # silent dense fallback would materialize the full
                # (B, S, d_ff) intermediate exactly when the user asked
                # for the memory bound — fail loudly instead
                raise ValueError(
                    f"ffn_chunk_size={cfg.ffn_chunk_size} does not divide "
                    f"sequence length {h.shape[1]}; pick a divisor or pad"
                )

            # remat only outside init (param creation can't happen inside
            # jax.checkpoint); per-chunk recompute bounds backward memory
            # to one (B, chunk, d_ff) tile.
            m = blockwise_map(
                mlp, h, cfg.ffn_chunk_size,
                remat=not self.is_initializing(),
            )
        else:
            m = mlp(h)
        if self.reduce_fn is not None:
            # Completes the row-parallel fc_out (manual TP): each shard
            # holds F/tp of the intermediate, its fc_out output is a
            # partial sum.  Applied before dropout/residual, mirroring
            # where GSPMD inserts the all-reduce on auto meshes.
            m = self.reduce_fn(m)
        if cfg.dropout_rate:
            m = nn.Dropout(cfg.dropout_rate)(m, deterministic=deterministic)
        return x + m


class GPTLM(nn.Module):
    """Decoder-only LM head over token ids; logits in float32.

    ``decode=True`` switches every attention to KV-cache incremental mode
    (one-token steps against a ``max_seq`` cache in the "cache" variable
    collection) — the serving path used by :func:`generate`.
    """

    cfg: GPTConfig
    attn_fn: AttnFn | None = None
    decode: bool = False

    def flash_layout(self, seq: int) -> str | None:
        """:func:`attention_layout` of this model's blocks over ``seq``
        positions; None where they do not attend through it (decode, a
        sequence-parallel ``attn_fn``).  The trainer reports it at
        start-up: the fall-back between the forms is silent."""
        if self.decode or self.attn_fn is not None:
            return None
        return attention_layout(self.cfg, seq)

    def attn_residuals(self, batch: int, seq: int
                       ) -> tuple[str | None, int | None]:
        """What the backward of a block does for the residuals of its
        attention over ``(batch, seq)`` tokens, and the bytes a layer
        keeps for it on a device: ``("saved", B*S*(H*D*itemsize + H*4))``
        where :func:`remat_block` keeps the tile kernels' o and
        log-sum-exp (B the batch a shard of the kernel holds, read under
        the context mesh), ``("recomputed", 0)`` where a checkpoint runs
        the attention again (another form under ``remat``; the
        attention-only ``remat_attn`` alone, which has no policy),
        ``(None, None)`` where nothing is rematerialised or
        :meth:`flash_layout` is None.  Beside ``flash_layout`` in the
        trainer's start-up row."""
        cfg = self.cfg
        layout = self.flash_layout(seq)
        if layout is None or not (cfg.remat or cfg.remat_attn):
            return None, None
        if layout != "qkv_tiles" or not cfg.remat:
            return "recomputed", 0
        per_token = cfg.num_heads * (
            cfg.head_dim * jnp.dtype(cfg.dtype).itemsize + 4)
        return "saved", self._kernel_batch(batch) * seq * per_token

    @staticmethod
    def _kernel_batch(batch: int) -> int:
        """The rows of ``batch`` a shard of the flash kernels holds, read
        under the context mesh (``kernel_axes``: a batch the axes do not
        divide is replicated)."""
        mesh = jax.sharding.get_abstract_mesh()
        return batch // math.prod(
            mesh.shape[a]
            for a in kernel_axes(mesh_lib.BATCH_AXES, batch) or ())

    def flash_causal_tile(self, batch: int, seq: int
                          ) -> tuple[int | None, float | None]:
        """``(T, share)``: the rows of the sub-tiles the tile kernels walk
        a block on the causal diagonal in over ``(batch, seq)`` tokens, and
        the share of such a block's square they compute
        (``ops.flash_attention.causal_tile`` / ``causal_share`` at the
        blocks the call resolves to: 256 and 0.625 at one 1024 x 1024
        block).  ``(None, None)`` where the blocks are taken whole: another
        form than ``"qkv_tiles"``, a block under two sub-tiles.  Beside
        ``flash_layout`` in the trainer's start-up row: the mechanism is
        static, so what says it engaged is a field."""
        from ..ops.flash_attention import qkv_causal_tile

        cfg = self.cfg
        if self.flash_layout(seq) != "qkv_tiles":
            return None, None
        return qkv_causal_tile(self._kernel_batch(batch), seq, cfg.num_heads,
                               cfg.head_dim, cfg.dtype)

    def xent_products(self, batch: int, seq: int
                      ) -> tuple[int | None, int | None]:
        """``(products, chunk_tokens)`` of the loss head over ``(batch,
        seq)`` tokens: the ``tokens x d x V`` MXU products a step's head
        runs, forward and backward (``ops.fused_xent.PRODUCTS_PER_STEP``:
        the backward forms its logits tile and its dlogits once), and the
        tokens a chunk of that backward holds dlogits for on a device
        (``ops.fused_xent.dlog_chunk_tokens`` of a shard's ``batch x (seq
        - 1)`` rows, read under the context mesh).  ``(None, None)`` where
        the head is not the fused one (``xent_impl``; "auto" off the TPU).
        Beside ``flash_layout`` in the trainer's start-up row."""
        from ..ops import fused_xent

        cfg = self.cfg
        if _xent_impl(cfg) != "fused":
            return None, None
        # a shard's rows, dealt out as ``parallel.sharding.token_spec``
        # deals the head's (batch, seq - 1) tokens
        mesh = jax.sharding.get_abstract_mesh()
        rows = self._kernel_batch(batch) * ((seq - 1) // math.prod(
            mesh.shape[a]
            for a in kernel_axes((mesh_lib.AXIS_SEQ,), seq - 1) or ()))
        return fused_xent.PRODUCTS_PER_STEP, fused_xent.dlog_chunk_tokens(
            rows, cfg.hidden_size, cfg.vocab_size,
            jnp.dtype(cfg.dtype).itemsize)

    @nn.compact
    def __call__(self, input_ids, *, deterministic: bool = True,
                 positions=None, return_hidden: bool = False):
        cfg = self.cfg
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_size,
            dtype=cfg.dtype, name="wte",
        )(input_ids)
        # NaN-provenance taps (obs/dynamics.py): per-module activation
        # isfinite counts sown into the "dynamics" collection.  Sown in
        # THIS scope — outside any remat'd block — so the taps are
        # remat-safe, and only when the collection is mutable (the
        # provenance re-forward), so training pays nothing.
        sow_nonfinite(self, "wte", x)
        # One trig computation per step, shared by every layer's q and k
        # rotation (and saved as a residual under remat instead of being
        # recomputed per block in the backward), in the form the blocks'
        # attention takes.
        rope_tabs = block_rope_tables(
            cfg, positions, input_ids.shape,
            fused=self.flash_layout(input_ids.shape[1]) == "qkv_tiles")
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(input_ids.shape[1]), input_ids.shape
            )
        block = GPTBlock
        if cfg.remat and not self.decode:
            # Remat each block: activations recomputed in backward — the
            # jax.checkpoint HBM/FLOPs trade for long sequences.
            block = remat_block(GPTBlock)
        for i in range(cfg.num_layers):
            x = block(cfg, self.attn_fn, self.decode, name=f"h{i}")(
                x, positions, deterministic, rope_tabs
            )
            sow_nonfinite(self, f"h{i}", x)
        x = FusedLayerNorm(out_dtype=jnp.float32, name="ln_f")(x)
        sow_nonfinite(self, "ln_f", x)
        if return_hidden:
            # Loss-side chunked head (ops/xent.py): the caller applies the
            # tied embedding per token chunk so full-vocab logits never
            # materialize.
            return x
        # Tied output head: reuse the embedding table (one less huge
        # vocab-sharded matrix; standard for decoder LMs).  Shared dtype
        # recipe (ops/xent.tied_head_logits): bf16 operands at MXU rate,
        # fp32 accumulation — identical to the chunked loss head.
        wte = self.variables["params"]["wte"]["embedding"]
        return tied_head_logits(x, wte, cfg.dtype)


# -- the serving definition ---------------------------------------------------
#
# The layer functions ``serve.model`` builds its programs from, with the
# signatures ``models.afmoe`` has, over the parameter tree ``GPTLM.init``
# makes (so a checkpoint of the trainer is served as it is).  ``GPTBlock``
# above is the training definition of the same block; tests/test_serve.py
# pins the two together.  Stored float32 weights are cast to the compute type
# at each use, under the scope ``cast_params``.

def init_params(cfg: GPTConfig, key):
    """Random parameters: ``GPTLM``'s own initialisers."""
    return GPTLM(cfg).init(key, jnp.zeros((1, 1), jnp.int32),
                           deterministic=True)["params"]


def _cast(param, dtype):
    with jax.named_scope("cast_params"):
        return param.astype(dtype)


def _norm(x, p, out_dtype=None):
    return layer_norm(x, p["scale"], p["bias"], eps=1e-6,
                      out_dtype=out_dtype or x.dtype)


def embed(params, ids, cfg: GPTConfig):
    with jax.named_scope("embed"):
        return _cast(params["wte"]["embedding"], cfg.dtype)[ids]


def block(p, x, cfg: GPTConfig, layer: int, positions, attend,
          token_mask=None):
    """One decoder layer on ``x`` (T, d), ``positions`` (T,).  ``attend(q, k,
    v)`` returns the attention output (T, H, D) — it owns where K/V live.
    Returns ``(x, None)``: no expert layer, no counters.

    Inside, the tokens are a batch of T sequences of one position, ``(T, 1,
    d)``, the training block's (batch, sequence, width): around that shape
    XLA fuses the small operations of a decode step into fewer (GPT-2
    medium's ``jit_decode`` at 4 slots: 3.24 ms against 3.42 with ``(T, d)``
    throughout; my chip run, PR 30)."""
    t, dt, d = x.shape[0], cfg.dtype, cfg.head_dim
    q_width, kv_width = cfg.num_heads * d, cfg.kv_heads * d
    x, positions = x[:, None, :], positions[:, None]
    with jax.named_scope("ln"):
        h = _norm(x, p["ln1"])
    with jax.named_scope("qkv"):
        qkv = h @ _cast(p["attn"]["qkv"]["kernel"], dt)
        q = qkv[..., :q_width].reshape(t, 1, cfg.num_heads, d)
        k = qkv[..., q_width:q_width + kv_width].reshape(
            t, 1, cfg.kv_heads, d)
        v = qkv[..., q_width + kv_width:].reshape(t, cfg.kv_heads, d)
        # the same tables in every layer: XLA keeps one copy
        tabs = rope_tables(positions, d, cfg.rope_theta, dt)
        q = rope(q, positions, cfg.rope_theta, tabs)[:, 0]
        k = rope(k, positions, cfg.rope_theta, tabs)[:, 0]
    a = attend(q, k, v).reshape(t, 1, q_width).astype(dt)
    with jax.named_scope("proj"):
        x = x + a @ _cast(p["attn"]["proj"]["kernel"], dt)
    with jax.named_scope("ln"):
        h = _norm(x, p["ln2"])
    with jax.named_scope("mlp"):
        m = jax.nn.gelu(h @ _cast(p["fc_in"]["kernel"], dt))
        x = x + m @ _cast(p["fc_out"]["kernel"], dt)
    return x[:, 0], None


def head(params, x, cfg: GPTConfig):
    """float32 logits of ``x`` (T, d): the tied head on the float32 final
    norm."""
    with jax.named_scope("head"):
        return tied_head_logits(_norm(x, params["ln_f"], jnp.float32),
                                params["wte"]["embedding"], cfg.dtype)


def nan_taps(model: GPTLM):
    """The NaN-provenance tap forward for ``obs.dynamics``: a
    ``tap_fn(params, batch) -> {"NNN_module": nonfinite_count}`` whose
    keys embed the FORWARD position (``000_wte``, ``001_h0``, ...,
    ``00N_ln_f``) — jit canonicalizes dict outputs to sorted key order,
    so a bare module-name key would silently turn "first in the forward
    pass" into "first alphabetically"; with the index prefix, sorted
    order IS forward order and the provenance binary search names the
    first module that produced a non-finite activation.  jit-able; runs
    the deterministic no-dropout forward with only the ``dynamics``
    collection mutable."""
    order = (["wte"] + [f"h{i}" for i in range(model.cfg.num_layers)]
             + ["ln_f"])

    def tap_fn(params, batch):
        _, variables = model.apply(
            {"params": params},
            batch["input_ids"],
            deterministic=True,
            return_hidden=True,
            mutable=["dynamics"],
        )
        taps = variables.get("dynamics", {})
        return {f"{i:03d}_{name}": taps[f"{name}__nf"]
                for i, name in enumerate(order) if f"{name}__nf" in taps}

    return tap_fn


def lm_loss(model: GPTLM):
    """Next-token cross-entropy; ignores the final position's prediction.

    Uses the vocab-chunked head (``ops/xent.py``): the model returns final
    hidden states and the tied-embedding logits are built and reduced one
    token chunk at a time, so the fp32 ``(B, S, V)`` logits tensor never
    exists.
    """
    xent = _pick_xent(model.cfg)

    def loss_fn(params, model_state, batch, rng):
        hidden = model.apply(
            {"params": params},
            batch["input_ids"],
            deterministic=False,
            rngs={"dropout": rng},
            return_hidden=True,
        )
        targets = batch["input_ids"][:, 1:]
        mask = batch.get("mask")
        # the head has no flax module of its own: name it for the trace
        with jax.named_scope("loss_head"):
            loss = xent(
                hidden[:, :-1],
                params["wte"]["embedding"],
                targets,
                mask[:, 1:] if mask is not None else None,
                compute_dtype=model.cfg.dtype,
            )
        return loss, ({"perplexity": jnp.exp(loss)}, model_state)

    return loss_fn


def _xent_impl(cfg: GPTConfig) -> str:
    """``cfg.xent_impl`` with "auto" resolved: fused on the TPU, chunked
    elsewhere."""
    if cfg.xent_impl == "auto":
        return "fused" if on_tpu() else "chunked"
    return cfg.xent_impl


def _pick_xent(cfg: GPTConfig):
    """Head-loss kernel for ``cfg.xent_impl``: "auto" (fused on TPU,
    chunked elsewhere), "chunked" (fp32 logits tiles), "chunked_bf16"
    (bf16 tiles — half the head HBM traffic, ~1e-2 NLL tolerance), or
    "fused" (Pallas, logits never leave VMEM)."""
    impl = _xent_impl(cfg)
    if impl == "fused":
        from ..ops.fused_xent import fused_softmax_xent

        return fused_softmax_xent
    if impl not in ("chunked", "chunked_bf16"):
        raise ValueError(
            f"xent_impl={cfg.xent_impl!r}: expected 'auto', 'chunked', "
            "'chunked_bf16', or 'fused'"
        )
    import functools

    from ..ops.xent import chunked_softmax_xent

    if impl == "chunked_bf16":
        return functools.partial(
            chunked_softmax_xent, logits_dtype=jnp.bfloat16
        )
    return chunked_softmax_xent


def lm_eval(model: GPTLM):
    """Eval metric_fn (params, model_state, batch) -> {loss, perplexity}.

    Deterministic forward (no dropout rng), same vocab-chunked head as
    ``lm_loss`` — wired into the ``gpt_lm`` preset so ``--eval-every`` and
    the sidecar evaluator work for LM workloads."""
    xent = _pick_xent(model.cfg)

    def metric_fn(params, model_state, batch):
        hidden = model.apply(
            {"params": params}, batch["input_ids"], deterministic=True,
            return_hidden=True,
        )
        mask = batch.get("mask")
        loss = xent(
            hidden[:, :-1],
            params["wte"]["embedding"],
            batch["input_ids"][:, 1:],
            mask[:, 1:] if mask is not None else None,
            compute_dtype=model.cfg.dtype,
        )
        return {"loss": loss, "perplexity": jnp.exp(loss)}

    return metric_fn


def gpt_layout() -> LayoutMap:
    """Megatron-style ``model``-axis sharding rules for :class:`GPTLM`.

    QKV and MLP-in are column-parallel (output dim sharded); proj and
    MLP-out are row-parallel (input dim sharded); the tied embedding is
    vocab-sharded.  Batch/seq sharding comes from the data/seq axes at the
    activation level, not the layout map.
    """
    return LayoutMap([
        (r".*wte/embedding", P("model", None)),
        (r".*attn/qkv/kernel", P(None, "model")),
        (r".*attn/proj/kernel", P("model", None)),
        (r".*fc_in/kernel", P(None, "model")),
        (r".*fc_out/kernel", P("model", None)),
    ])
