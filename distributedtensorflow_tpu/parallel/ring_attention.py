"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

New first-class capability absent from the reference stack (SURVEY.md §5.7):
TF-classic has only the generic ``all_to_all`` op; long-context training needs
attention over sequences sharded across devices.

Two schemes, both valid inside ``shard_map`` over the ``seq`` mesh axis:

- :func:`ring_attention` — K/V chunks rotate around the ring via
  ``lax.ppermute`` while each device's Q stays put; online-softmax
  accumulators merge each chunk's contribution.  Communication is
  neighbor-to-neighbor over ICI (the torus's cheapest pattern) and overlaps
  with the chunk matmuls.  Memory per device stays O(S/n).
- :func:`ulysses_attention` — two ``all_to_all`` s reshard seq↔heads so each
  device computes *full-sequence* attention for H/n heads (then swaps back).
  Cheaper compute structure (one big attention per device, can use the
  Pallas flash kernel), but needs heads % seq_axis == 0 and all-to-all
  bandwidth.

References: Ring Attention (Liu et al. 2023) / DeepSpeed-Ulysses patterns —
re-derived here for the jax/shard_map idiom.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..runtime import on_tpu
from . import mesh as mesh_lib

NEG_INF = -1e9


def ring_attention(
    q: jax.Array,  # (B, S_loc, H, D) — this device's seq shard
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = mesh_lib.AXIS_SEQ,
    causal: bool = False,
    impl: str | None = None,  # None=auto | "flash" | "xla"
    segment_ids: jax.Array | None = None,  # (B, S_loc) this shard's segments
) -> jax.Array:
    """Ring attention over mesh axis ``axis_name`` (shard_map-internal).

    Devices are assumed to hold *contiguous* sequence chunks in mesh-axis
    order (chunk i on position i) — the layout ``PartitionSpec(..., "seq",
    ...)`` produces.

    Chunk compute dispatches to the Pallas flash-attention kernels
    (``ops/flash_attention.py``) whenever the chunk shape supports them
    (auto) — per SURVEY.md §5.7 "ring attention with Pallas kernel": no
    (S_loc, S_loc) score tile ever reaches HBM, in forward *or* backward.
    ``impl="xla"`` forces the einsum online-softmax fallback (odd chunk
    sizes / unsupported dtypes).
    """
    if impl is None:
        from ..ops import flash_attention as fa

        # Match ops-level supported(): only auto-pick flash on real TPU
        # hardware (off-TPU the interpret-mode kernel is orders of magnitude
        # slower than the einsum ring), and only once the PER-DEVICE chunk
        # is long enough that the kernel beats XLA's fused attention
        # (``flash_attention.MIN_SEQ_FOR_PALLAS``).
        # Callers can always force impl="flash".
        ok = (
            on_tpu()
            and q.shape == k.shape == v.shape
            and q.shape[1] >= fa.MIN_SEQ_FOR_PALLAS
            and fa._pick_block_q(q.shape[1]) is not None
            and q.dtype in (jnp.bfloat16, jnp.float32)
        )
        impl = "flash" if ok else "xla"
    if impl == "flash":
        return _ring_flash(q, k, v, segment_ids, axis_name, causal,
                           not on_tpu())
    return _ring_attention_xla(q, k, v, axis_name=axis_name, causal=causal,
                               segment_ids=segment_ids)


# --- Flash-kernel ring (custom VJP) -----------------------------------------


def _ring_flash_fwd_impl(q, k, v, seg, axis_name, causal, interpret):
    """Ring forward: each chunk through the Pallas flash kernel, partials
    merged by their log-sum-exp.  Returns (out, global lse).

    ``seg`` (B, S_loc) or None: packed-segment ids; the K/V chunk's segment
    ids rotate with it, and each chunk pair is masked q-segment vs
    k-segment inside the kernel.  A chunk fully masked for some q row gets
    lse ~ -1e9 there, so the merge weights its (uniform-average) output by
    ~0 — the same mechanism that nullifies strictly-future causal chunks.
    """
    from ..ops.flash_attention import _flash_forward

    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]
    have_seg = seg is not None

    def chunk(step, kc, vc, seg_c):
        """(o_chunk fp32 (B,S,H,D), lse_chunk (B,H,S)) for this ring step."""
        kidx = (my - step) % n
        seg_kw = dict(segment_ids=seg, kv_segment_ids=seg_c) if have_seg \
            else dict(segment_ids=None)

        def diag(_):
            return _flash_forward(q, kc, vc, None, causal=True,
                                  interpret=interpret, **seg_kw)

        def past(_):
            return _flash_forward(q, kc, vc, None, causal=False,
                                  interpret=interpret, **seg_kw)

        if not causal:
            o, lse = past(None)
            return o.astype(jnp.float32), lse

        def future(_):
            # Strictly-future chunk: nothing to compute.  lse=-inf makes the
            # merge weight exp(lse - m) exactly 0.
            return (
                jnp.zeros((b, s_loc, h, d), q.dtype),
                jnp.full((b, h, s_loc), NEG_INF, jnp.float32),
            )

        o, lse = lax.cond(
            kidx > my,
            future,
            lambda _: lax.cond(kidx == my, diag, past, None),
            None,
        )
        return o.astype(jnp.float32), lse

    def merge(m, l, acc, o_c, lse_c):
        # o_c is chunk-softmax-normalized; exp(lse_c - m_new) restores the
        # un-normalized numerator so partials combine exactly.
        m_new = jnp.maximum(m, lse_c)  # (B, H, S)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(lse_c - m_new)
        acc = acc * alpha.transpose(0, 2, 1)[..., None] + (
            o_c * beta.transpose(0, 2, 1)[..., None]
        )
        l = l * alpha + beta
        return m_new, l, acc

    def body(carry, step):
        m, l, acc, kc, vc, seg_c = carry
        o_c, lse_c = chunk(step, kc, vc, seg_c)
        m, l, acc = merge(m, l, acc, o_c, lse_c)
        # rotate K/V (+ segments) to the next device; XLA overlaps this
        # with the matmuls
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        if have_seg:
            seg_c = lax.ppermute(seg_c, axis_name, perm)
        return (m, l, acc, kc, vc, seg_c), None

    m0 = jnp.full((b, h, s_loc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    acc0 = jnp.zeros((b, s_loc, h, d), jnp.float32)
    seg0 = seg if have_seg else jnp.zeros((), jnp.int32)
    # last chunk merged outside the scan: no wasted final K/V rotation
    (m, l, acc, kc, vc, seg_c), _ = lax.scan(
        body, (m0, l0, acc0, k, v, seg0), jnp.arange(n - 1)
    )
    o_c, lse_c = chunk(n - 1, kc, vc, seg_c)
    m, l, acc = merge(m, l, acc, o_c, lse_c)
    out = acc / l.transpose(0, 2, 1)[..., None]
    lse_global = m + jnp.log(l)
    return out.astype(q.dtype), lse_global


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _ring_flash(q, k, v, seg, axis_name, causal, interpret):
    out, _ = _ring_flash_fwd_impl(q, k, v, seg, axis_name, causal, interpret)
    return out


def _ring_flash_fwd(q, k, v, seg, axis_name, causal, interpret):
    out, lse = _ring_flash_fwd_impl(q, k, v, seg, axis_name, causal,
                                    interpret)
    return out, (q, k, v, seg, out, lse)


def _ring_flash_bwd(axis_name, causal, interpret, res, g):
    """Backward ring: per-chunk Pallas dq/dk/dv kernels driven by the
    *global* LSE; dk/dv partials rotate with their K/V chunk so after a
    full cycle every chunk's gradient lands back on its home device."""
    from ..ops.flash_attention import _flash_backward_pallas_core

    q, k, v, seg, out, lse = res
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    gf = g.astype(jnp.float32)
    delta = jnp.einsum("bqhd,bqhd->bhq", gf, out.astype(jnp.float32))
    have_seg = seg is not None

    def chunk_grads(step, kc, vc, seg_c):
        kidx = (my - step) % n
        seg_kw = dict(segment_ids=seg, kv_segment_ids=seg_c) if have_seg \
            else {}

        def run(causal_flag):
            def f(_):
                return _flash_backward_pallas_core(
                    q, kc, vc, None, g, lse, delta,
                    causal=causal_flag, interpret=interpret, **seg_kw,
                )
            return f

        if not causal:
            return run(False)(None)

        def future(_):
            return (
                jnp.zeros_like(q), jnp.zeros_like(kc), jnp.zeros_like(vc)
            )

        return lax.cond(
            kidx > my,
            future,
            lambda _: lax.cond(kidx == my, run(True), run(False), None),
            None,
        )

    def body(carry, step):
        dq_acc, kc, vc, seg_c, dk_ring, dv_ring = carry
        dq_c, dk_c, dv_c = chunk_grads(step, kc, vc, seg_c)
        dq_acc = dq_acc + dq_c.astype(jnp.float32)
        dk_ring = dk_ring + dk_c.astype(jnp.float32)
        dv_ring = dv_ring + dv_c.astype(jnp.float32)
        # K/V and their gradient partials travel together; n rotations is a
        # full cycle, so dk/dv end the scan on their chunk's home device.
        kc, vc, dk_ring, dv_ring = (
            lax.ppermute(x, axis_name, perm)
            for x in (kc, vc, dk_ring, dv_ring)
        )
        if have_seg:
            seg_c = lax.ppermute(seg_c, axis_name, perm)
        return (dq_acc, kc, vc, seg_c, dk_ring, dv_ring), None

    zeros_q = jnp.zeros(q.shape, jnp.float32)
    zeros_k = jnp.zeros(k.shape, jnp.float32)
    seg0 = seg if have_seg else jnp.zeros((), jnp.int32)
    (dq, _, _, _, dk, dv), _ = lax.scan(
        body,
        (zeros_q, k, v, seg0, zeros_k, jnp.zeros(v.shape, jnp.float32)),
        jnp.arange(n),
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), None


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


# --- XLA einsum fallback ----------------------------------------------------


def _ring_attention_xla(
    q: jax.Array,  # (B, S_loc, H, D) — this device's seq shard
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = mesh_lib.AXIS_SEQ,
    causal: bool = False,
    segment_ids: jax.Array | None = None,  # (B, S_loc)
) -> jax.Array:
    """Einsum online-softmax ring (chunk-granular causal masking, uniform
    control flow).  Fallback for shapes/dtypes the flash kernels reject."""
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    have_seg = segment_ids is not None

    def merge_chunk(m, l, acc, kc, vc, seg_c, step):
        # kc holds the chunk originally on device (my - step) % n
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kc.astype(jnp.float32)) * scale
        if causal:
            kidx = (my - step) % n
            q_pos = my * s_loc + jnp.arange(s_loc)
            k_pos = kidx * s_loc + jnp.arange(s_loc)
            keep = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(keep[None, None], s, NEG_INF)
        if have_seg:
            same = segment_ids[:, :, None] == seg_c[:, None, :]  # (B, Sq, Sk)
            s = jnp.where(same[:, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, vc.astype(jnp.float32))
        acc_new = acc * alpha.transpose(0, 2, 1, 3) + pv
        return m_new, l_new, acc_new

    def body(carry, step):
        m, l, acc, kc, vc, seg_c = carry
        m, l, acc = merge_chunk(m, l, acc, kc, vc, seg_c, step)
        # rotate K/V to the next device; XLA overlaps this with the matmuls
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        if have_seg:
            seg_c = lax.ppermute(seg_c, axis_name, perm)
        return (m, l, acc, kc, vc, seg_c), None

    m0 = jnp.full((b, h, s_loc, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc, 1), jnp.float32)
    acc0 = jnp.zeros((b, s_loc, h, d), jnp.float32)
    seg0 = segment_ids if have_seg else jnp.zeros((), jnp.int32)
    # scan runs only the n-1 steps that need a rotation afterwards; the last
    # chunk is merged outside so no wasted final ppermute of K and V
    (m, l, acc, kc, vc, seg_c), _ = lax.scan(
        body, (m0, l0, acc0, k, v, seg0), jnp.arange(n - 1)
    )
    m, l, acc = merge_chunk(m, l, acc, kc, vc, seg_c, n - 1)
    # l >= 1 always: the diagonal chunk contributes exp(0) per row, so no
    # division guard is needed (matches the full-attention softmax exactly)
    out = acc / l.transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


def ulysses_attention(
    q: jax.Array,  # (B, S_loc, H, D)
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = mesh_lib.AXIS_SEQ,
    causal: bool = False,
    attn_fn: Callable | None = None,
    segment_ids: jax.Array | None = None,  # (B, S_loc)
) -> jax.Array:
    """Ulysses sequence parallelism (shard_map-internal).

    all_to_all reshards (B, S/n, H, D) -> (B, S, H/n, D), runs full-sequence
    attention per device on its head subset (``attn_fn``, default the
    framework attention entry, which may pick the Pallas flash kernel), then
    reshards back.  Heads must divide the seq-axis size.  ``segment_ids``
    (packed sequences) are all-gathered along ``seq`` — each device sees the
    full-sequence ids its full-sequence attention needs (ids are int32 and
    tiny next to K/V).
    """
    n = lax.axis_size(axis_name)
    h = q.shape[2]
    if h % n:
        raise ValueError(f"heads={h} not divisible by seq axis size {n}")
    if attn_fn is None:
        from ..ops.attention import dot_product_attention

        attn_fn = functools.partial(dot_product_attention, causal=causal)
    if segment_ids is not None:
        seg_full = lax.all_gather(segment_ids, axis_name, axis=1, tiled=True)
        attn_fn = functools.partial(attn_fn, segment_ids=seg_full)

    def seq_to_heads(x):  # (B, S_loc, H, D) -> (B, S, H/n, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):  # (B, S, H/n, D) -> (B, S_loc, H, D)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    out = attn_fn(seq_to_heads(q), seq_to_heads(k), seq_to_heads(v))
    return heads_to_seq(out)


def make_sequence_parallel_attention(
    mesh: Mesh,
    *,
    scheme: str = "ring",  # "ring" | "ulysses"
    causal: bool = False,
    axis_name: str = mesh_lib.AXIS_SEQ,
) -> Callable:
    """Jit-compiled global-array entry: (B, S, H, D) sharded on ``seq``.

    The batch dim is additionally sharded over the batch axes, so this
    composes dp x sp out of the box.
    """
    return jax.jit(
        sequence_parallel_attention_fn(
            mesh, scheme=scheme, causal=causal, axis_name=axis_name
        )
    )


def sequence_parallel_attention_fn(
    mesh: Mesh,
    *,
    scheme: str = "ring",  # "ring" | "ulysses"
    causal: bool = True,
    axis_name: str = mesh_lib.AXIS_SEQ,
) -> Callable:
    """Un-jitted shard_map attention for use *inside* a jitted model.

    The manual-collectives region embedded in a GSPMD program: models (e.g.
    ``models.gpt.GPTLM``) take this as their ``attn_fn`` so the surrounding
    train step stays one ``jit`` while attention runs ring/Ulysses over the
    ``seq`` axis.  Dropping it into a mesh without a real ``seq`` axis
    (size 1) degrades to plain blockwise attention — same program, no
    collectives — so the model code never branches.
    """
    fn = {"ring": ring_attention, "ulysses": ulysses_attention}[scheme]
    kernel = functools.partial(fn, axis_name=axis_name, causal=causal)
    batch_axes = mesh_lib.data_axes(mesh)
    # Heads stay sharded over the model axis INSIDE the region: ring
    # attention is per-head independent, so on a dp x tp x sp mesh the
    # Megatron head shards never gather — each device ring-rotates only its
    # own heads' K/V (size-1 model axis makes this a no-op).
    head_axis = (
        mesh_lib.AXIS_MODEL
        if scheme == "ring" and mesh.shape.get(mesh_lib.AXIS_MODEL, 1) > 1
        else None
    )
    spec = P(batch_axes if batch_axes else None, axis_name, head_axis, None)
    seg_spec = P(batch_axes if batch_axes else None, axis_name)
    plain = jax.shard_map(
        lambda q, k, v: kernel(q, k, v),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    packed = jax.shard_map(
        lambda q, k, v, seg: kernel(q, k, v, segment_ids=seg),
        mesh=mesh,
        in_specs=(spec, spec, spec, seg_spec),
        out_specs=spec,
        check_vma=False,
    )

    def attention(q, k, v, segment_ids=None):
        if segment_ids is None:
            return plain(q, k, v)
        return packed(q, k, v, segment_ids)

    return attention
