"""Flash-attention kernel golden tests vs the XLA reference path.

Run in Pallas interpreter mode on CPU (SURVEY.md §7 "gate behind golden
tests vs full attention").
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu.ops.attention import xla_attention
from distributedtensorflow_tpu.ops.flash_attention import (
    _pick_block_q,
    flash_attention,
    supported,
)


def make_qkv(b=2, s=256, h=4, d=32, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def test_pick_block_q(monkeypatch):
    # 1024-first chain (see DEFAULT_BLOCK_Q).  A leaked override would
    # change the chain — pin the default environment.
    monkeypatch.delenv("DTFT_FLASH_BLOCK_Q", raising=False)
    assert _pick_block_q(2048) == 1024
    assert _pick_block_q(1024) == 1024
    assert _pick_block_q(256) == 256
    assert _pick_block_q(128) == 128
    assert _pick_block_q(96) == 32
    assert _pick_block_q(100) is None


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_xla(causal):
    q, k, v = make_qkv()
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_forward_with_padding_mask():
    q, k, v = make_qkv()
    mask = np.ones((2, 256), bool)
    mask[:, 200:] = False
    out = flash_attention(q, k, v, mask=jnp.asarray(mask), interpret=True)
    ref = xla_attention(q, k, v, mask=jnp.asarray(mask)[:, None, None, :])
    np.testing.assert_allclose(out[:, :200], ref[:, :200], atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_xla(causal):
    q, k, v = make_qkv(b=1, s=128, h=2, d=16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_gradients_with_mask():
    q, k, v = make_qkv(b=1, s=128, h=2, d=16)
    mask = np.ones((1, 128), bool)
    mask[:, 100:] = False
    mask = jnp.asarray(mask)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, mask=mask, interpret=True)
        return jnp.sum((out * mask[:, :, None, None]) ** 2)

    def loss_ref(q, k, v):
        out = xla_attention(q, k, v, mask=mask[:, None, None, :])
        return jnp.sum((out * mask[:, :, None, None]) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_backward_matches_xla_backward(causal):
    """A/B the two backward implementations through the same saved residuals."""
    import distributedtensorflow_tpu.ops.flash_attention as fa

    q, k, v = make_qkv(b=1, s=256, h=2, d=16, seed=3)
    mask = np.ones((1, 256), bool)
    mask[:, 240:] = False
    mask = jnp.asarray(mask)

    def loss(impl):
        def f(q, k, v):
            out = flash_attention(q, k, v, mask=mask, causal=causal,
                                  interpret=True, backward_impl=impl)
            return jnp.sum((out * mask[:, :, None, None]) ** 2)
        return f

    assert fa.BACKWARD_IMPL == "pallas"  # the default path
    g_pallas = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pallas, g_xla):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_supported_gates():
    q, k, v = make_qkv(s=100)  # indivisible seq
    assert not supported(q, k, v)
    q3 = jnp.zeros((2, 64, 4))
    assert not supported(q3, q3, q3)


def test_forced_pallas_raises_clear_errors():
    q, k, v = make_qkv(s=100)
    with pytest.raises(ValueError, match="not divisible"):
        flash_attention(q, k, v, interpret=True)
    q2, k2, v2 = make_qkv(s=128)
    bad_mask = jnp.ones((2, 4, 128, 128), bool)  # full attention mask
    with pytest.raises(ValueError, match="mask shape"):
        flash_attention(q2, k2, v2, mask=bad_mask, interpret=True)
    # mismatched seq between q and k/v: not even a valid GQA shape
    with pytest.raises(ValueError, match="BSHD"):
        flash_attention(q2, k2[:, :64], v2, interpret=True)


def test_jit_and_vmap_compose():
    q, k, v = make_qkv(b=2, s=128, h=2, d=16)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=True))
    np.testing.assert_allclose(
        f(q, k, v), xla_attention(q, k, v), atol=2e-5, rtol=2e-5
    )


def make_segments(b=2, s=256, n_segments=3, seed=3):
    """Contiguous packed segments with random boundaries per batch row."""
    rng = np.random.default_rng(seed)
    seg = np.zeros((b, s), np.int32)
    for i in range(b):
        cuts = np.sort(rng.choice(np.arange(1, s), n_segments - 1, replace=False))
        seg[i] = np.searchsorted(cuts, np.arange(s), side="right")
    return jnp.asarray(seg)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_with_segment_ids(causal):
    """Packed-sequence masking == dense attention with a block-diagonal mask."""
    q, k, v = make_qkv()
    seg = make_segments()
    out = flash_attention(q, k, v, segment_ids=seg, causal=causal, interpret=True)
    blockdiag = (seg[:, :, None] == seg[:, None, :])[:, None, :, :]
    ref = xla_attention(q, k, v, mask=blockdiag, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("backward_impl", ["pallas", "pallas_split", "xla"])
def test_gradients_with_segment_ids(backward_impl, causal):
    q, k, v = make_qkv(b=1, s=128, h=2, d=16)
    seg = make_segments(b=1, s=128, n_segments=2)
    blockdiag = (seg[:, :, None] == seg[:, None, :])[:, None, :, :]

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, segment_ids=seg, causal=causal,
                            interpret=True,
                            backward_impl=backward_impl) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(
            xla_attention(q, k, v, mask=blockdiag, causal=causal) ** 2
        )

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


def test_segment_ids_compose_with_padding_mask():
    q, k, v = make_qkv()
    seg = make_segments()
    mask = np.ones((2, 256), bool)
    mask[:, 240:] = False
    out = flash_attention(
        q, k, v, mask=jnp.asarray(mask), segment_ids=seg, interpret=True
    )
    dense = (
        (seg[:, :, None] == seg[:, None, :])[:, None, :, :]
        & jnp.asarray(mask)[:, None, None, :]
    )
    ref = xla_attention(q, k, v, mask=dense)
    np.testing.assert_allclose(out[:, :240], ref[:, :240], atol=2e-5, rtol=2e-5)


def test_segment_ids_validation():
    q, k, v = make_qkv(b=2, s=256)
    with pytest.raises(ValueError, match="segment_ids"):
        flash_attention(q, k, v, segment_ids=jnp.zeros((2, 128), jnp.int32),
                        interpret=True)
    with pytest.raises(ValueError, match="segment_ids"):
        flash_attention(q, k, v, segment_ids=jnp.zeros((2, 256), jnp.float32),
                        interpret=True)


def test_dispatch_segment_ids_xla_path_matches_flash():
    from distributedtensorflow_tpu.ops.attention import dot_product_attention

    q, k, v = make_qkv()
    seg = make_segments()
    via_xla = dot_product_attention(q, k, v, segment_ids=seg, implementation="xla")
    via_flash = dot_product_attention(
        q, k, v, segment_ids=seg, implementation="pallas"
    )
    np.testing.assert_allclose(via_flash, via_xla, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_backward_matches_split(causal, monkeypatch):
    """The fused single-sweep backward (one p-recompute, dq in a whole-
    (b,h) VMEM scratch) must agree with the original dq+dkv pair to
    fp32 tolerance, including under causal skipping — where the fused
    kernel's unconditional dq out-block writes are load-bearing (a
    skipped pair still flushes the running partial sum, never stale
    bytes).

    Blocks are pinned to 64 so s=256 yields a 4x4 block grid — without
    this the default chain picks 256-blocks and the grid is (.., 1, 1),
    which never exercises causal block skipping, cross-j dq
    accumulation, or the out-block revisit flushes."""
    monkeypatch.setenv("DTFT_FLASH_BLOCK_Q", "64")
    monkeypatch.setenv("DTFT_FLASH_BLOCK_K", "64")
    q, k, v = make_qkv(b=2, s=256, h=2, d=32, seed=7)

    def loss(impl):
        def f(q, k, v):
            out = flash_attention(q, k, v, causal=causal, interpret=True,
                                  backward_impl=impl)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return f

    g_fused = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    g_split = jax.grad(loss("pallas_split"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fused, g_split):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_backward_multiblock_matches_xla(causal, monkeypatch):
    """Multi-block fused backward vs the XLA golden path, with a padding
    mask riding along — covers the masked + multi-block combination."""
    monkeypatch.setenv("DTFT_FLASH_BLOCK_Q", "64")
    monkeypatch.setenv("DTFT_FLASH_BLOCK_K", "64")
    q, k, v = make_qkv(b=1, s=256, h=2, d=16, seed=9)
    mask = np.ones((1, 256), bool)
    mask[:, 230:] = False
    mask = jnp.asarray(mask)

    def loss(impl):
        def f(q, k, v):
            out = flash_attention(q, k, v, mask=mask, causal=causal,
                                  interpret=True, backward_impl=impl)
            return jnp.sum((out * mask[:, :, None, None]) ** 2)
        return f

    g_fused = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fused, g_xla):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_fused_backward_dispatch_budget(monkeypatch):
    """Above FUSED_BWD_DQ_SCRATCH_BYTES the default backward must fall
    back to the split pair (the (S, D) fp32 dq scratch would not fit);
    equality of gradients across the boundary proves the dispatch is
    semantics-free."""
    import distributedtensorflow_tpu.ops.flash_attention as fa

    q, k, v = make_qkv(b=1, s=256, h=2, d=32, seed=11)

    def g(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    grad_fused = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    # Shrink the budget below S*D*4 = 32 KiB so dispatch flips to split.
    monkeypatch.setattr(fa, "FUSED_BWD_DQ_SCRATCH_BYTES", 1024)
    grad_split = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grad_fused, grad_split):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


# --- Sliding-window attention ------------------------------------------------


def _dense_swa_reference(q, k, v, window):
    """Dense causal sliding-window attention (fp32 softmax)."""
    b, s, h, d = q.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / (d ** 0.5)
    qp = jnp.arange(s)[:, None]
    kp = jnp.arange(s)[None, :]
    keep = (qp >= kp) & (kp > qp - window)
    scores = jnp.where(keep[None, None], scores, -1e9)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(q.dtype), v)


@pytest.mark.parametrize("window", [1, 7, 16, 33, 64, 100])
def test_sliding_window_matches_dense(window, monkeypatch):
    monkeypatch.setenv("DTFT_FLASH_BLOCK_Q", "16")
    monkeypatch.setenv("DTFT_FLASH_BLOCK_K", "16")
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.standard_normal((2, 64, 3, 16)) * 0.5, jnp.float32)
        for _ in range(3)
    )
    got = flash_attention(q, k, v, causal=True, window=window,
                          interpret=True)
    want = _dense_swa_reference(q, k, v, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["pallas", "pallas_split", "xla"])
def test_sliding_window_grads_match_dense(impl, monkeypatch):
    monkeypatch.setenv("DTFT_FLASH_BLOCK_Q", "16")
    monkeypatch.setenv("DTFT_FLASH_BLOCK_K", "16")
    rng = np.random.default_rng(1)
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 48, 2, 8)) * 0.5, jnp.float32)
        for _ in range(3)
    )

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, window=13,
                            interpret=True, backward_impl=impl)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(
            _dense_swa_reference(q, k, v, 13).astype(jnp.float32) ** 2
        )

    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)


def test_window_geq_seq_equals_plain_causal(monkeypatch):
    monkeypatch.setenv("DTFT_FLASH_BLOCK_Q", "16")
    monkeypatch.setenv("DTFT_FLASH_BLOCK_K", "16")
    rng = np.random.default_rng(2)
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 32, 2, 8)), jnp.float32)
        for _ in range(3)
    )
    a = flash_attention(q, k, v, causal=True, window=32, interpret=True)
    b = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_window_requires_causal():
    q = jnp.zeros((1, 16, 2, 8))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, window=8, interpret=True)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, causal=True, window=0, interpret=True)


# --- the fused projection read as it lies (flash_attention_qkv) -------------


def _fused_case(d, h, *, s=128, b=2, per_row=False, seed=0):
    """A projection (B, S, 3*H*D), its positions, and the rotation's lane
    tables as the trunk hands them to the blocks."""
    from distributedtensorflow_tpu.models.gpt import rope_lane_tables

    qkv = jax.random.normal(jax.random.PRNGKey(seed + d), (b, s, 3 * h * d))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    if per_row:
        pos = pos + 7 * jnp.arange(b)[:, None]
    return qkv, pos, rope_lane_tables(pos if per_row else pos[:1], d, 1e4)


def _split_heads(qkv, h):
    b, s, w = qkv.shape
    return tuple(x.reshape(b, s, h, w // (3 * h))
                 for x in jnp.split(qkv, 3, axis=-1))


def _rope_then_dense(qkv, pos, h, *, causal, mask=None, segment_ids=None,
                     window=None):
    """What the fused entry replaces: split, ``rope`` outside, the dense
    reference."""
    from distributedtensorflow_tpu.models.gpt import rope

    q, k, v = _split_heads(qkv, h)
    q, k = rope(q, pos, 1e4), rope(k, pos, 1e4)
    keep = None if mask is None else mask[:, None, None, :]
    if segment_ids is not None:
        seg = (segment_ids[:, :, None] == segment_ids[:, None, :])[:, None]
        keep = seg if keep is None else keep & seg
    o = xla_attention(q, k, v, mask=keep, causal=causal, window=window)
    return o.reshape(qkv.shape[0], qkv.shape[1], -1)


_PAD = np.ones((2, 128), bool)
_PAD[0, 100:] = False
_SEGMENTS = (np.arange(128)[None, :] >= np.array([[40], [90]])).astype(
    np.int32)

FUSED_CASES = [
    # depth, heads, causal, rows, window, backward, per-row positions, blocks
    (32, 4, True, None, None, "pallas", False, None),
    (32, 8, False, None, None, "pallas_split", True, None),
    (64, 2, True, None, None, "pallas", False, None),
    (64, 4, False, None, None, "pallas", True, None),
    (64, 4, True, None, None, "pallas_split", True, None),
    (128, 2, True, None, None, "pallas", True, None),
    (128, 1, False, None, None, "pallas_split", False, None),
    (256, 1, True, None, None, "pallas", False, None),
    (256, 2, False, None, None, "pallas_split", True, None),
    (64, 2, False, "mask", None, "pallas", False, None),
    (64, 2, True, "mask", None, "pallas_split", True, None),
    (64, 2, True, "segments", None, "pallas", False, None),
    (32, 4, False, "segments", None, "pallas_split", True, None),
    (128, 1, True, "segments", None, "pallas", True, (64, 64)),
    (64, 2, True, None, 33, "pallas", False, (32, 32)),
    (32, 4, True, None, 70, "pallas_split", True, (64, 32)),
    # several q and k blocks of unequal size: the running softmax, k rotated
    # at every visit, dq accumulated over the k sweep
    (64, 4, True, None, None, "pallas", True, (32, 64)),
    (64, 2, False, None, None, "pallas", False, (64, 32)),
    (64, 2, True, None, None, "pallas_split", False, (32, 64)),
]


@pytest.mark.parametrize(
    "d,h,causal,rows,window,backward,per_row,blocks", FUSED_CASES,
    ids=[f"d{c[0]}-h{c[1]}-{'causal' if c[2] else 'full'}-{c[3] or 'norows'}"
         f"-w{c[4]}-{c[5]}-{'offsets' if c[6] else 'arange'}-"
         f"{'x'.join(map(str, c[7])) if c[7] else 'oneblock'}"
         for c in FUSED_CASES])
def test_fused_projection_matches_rope_then_dense(
        d, h, causal, rows, window, backward, per_row, blocks):
    """``flash_attention_qkv`` reads the projection as the matmul wrote it,
    two heads of 64 to a 128-lane tile (four of 32, one of 128 or 256),
    and rotates q and k in VMEM: o and d``qkv`` are those of split,
    ``rope`` and dense attention."""
    from distributedtensorflow_tpu.ops.flash_attention import (
        flash_attention_qkv)

    qkv, pos, tabs = _fused_case(d, h, per_row=per_row)
    kw = dict(causal=causal, window=window,
              mask=jnp.asarray(_PAD) if rows == "mask" else None,
              segment_ids=jnp.asarray(_SEGMENTS) if rows == "segments"
              else None)
    bq, bk = blocks or (None, None)
    # a padded query row attends nothing real: compare the rows that do
    live = jnp.asarray(_PAD if rows == "mask" else np.ones((2, 128), bool))
    weight = jax.random.normal(jax.random.PRNGKey(9), (2, 128, h * d))
    weight = weight * live[:, :, None]

    def fused(x):
        return flash_attention_qkv(
            x, h, rope=tabs, interpret=True, backward_impl=backward,
            block_q=bq, block_k=bk, **kw)

    def dense(x):
        return _rope_then_dense(x, pos, h, **kw)

    o, want = fused(qkv), dense(qkv)
    np.testing.assert_allclose(o * live[:, :, None], want * live[:, :, None],
                               atol=2e-5, rtol=2e-5)
    got, ref = (jax.grad(lambda x, f=f: jnp.sum(f(x) * weight))(qkv)
                for f in (fused, dense))
    np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("blocks", [None, (32, 64)],
                         ids=["oneblock", "32x64"])
@pytest.mark.parametrize("causal", [False, True])
def test_fused_projection_scores_are_the_bhsd_kernels_bit_for_bit(causal,
                                                                  blocks):
    """With the rotation off, a head's scores out of a 128-lane tile (a
    contraction 128 deep over exact zeros) and its softmax are the (B, H,
    S, D) kernels': o agrees bit for bit, the gradients to rounding (delta
    is summed in the kernel here, by XLA there)."""
    from distributedtensorflow_tpu.ops.flash_attention import (
        flash_attention_qkv)

    h = 4
    qkv, _, _ = _fused_case(64, h)
    bq, bk = blocks or (None, None)
    kw = dict(causal=causal, interpret=True, block_q=bq, block_k=bk)

    def tiles(x):
        return flash_attention_qkv(x, h, **kw)

    def bhsd(x):
        return flash_attention(*_split_heads(x, h), **kw).reshape(
            2, 128, h * 64)

    np.testing.assert_array_equal(tiles(qkv), bhsd(qkv))
    got, ref = (jax.grad(lambda x, f=f: jnp.sum(f(x) ** 2))(qkv)
                for f in (tiles, bhsd))
    np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


def test_fused_projection_in_bfloat16_rounds_the_rotation_once():
    """bf16 operands, float32 tables and accumulation: against the float32
    reference the fused entry is no further off than ``rope`` outside (three
    roundings to bf16 where it has one) and the (B, H, S, D) kernels."""
    from distributedtensorflow_tpu.models.gpt import rope
    from distributedtensorflow_tpu.ops.flash_attention import (
        flash_attention_qkv)

    h = 2
    qkv, pos, tabs = _fused_case(64, h)
    want = _rope_then_dense(qkv, pos, h, causal=True)
    x = qkv.astype(jnp.bfloat16)
    got = flash_attention_qkv(x, h, rope=tabs, causal=True, interpret=True)
    q, k, v = _split_heads(x, h)
    old = flash_attention(rope(q, pos, 1e4), rope(k, pos, 1e4), v,
                          causal=True, interpret=True).reshape(2, 128, -1)
    assert got.dtype == jnp.bfloat16

    def err(o):
        return float(jnp.sqrt(jnp.mean((o.astype(jnp.float32) - want) ** 2)))

    assert err(got) <= 1.05 * err(old) < 0.02


FALLBACKS = {
    # a head of 96 fills no lane tile; three heads of 64 leave one half
    # full; GQA's k and v thirds are narrower than q's
    "d96": dict(hidden_size=384, num_heads=4),
    "odd_heads": dict(hidden_size=192, num_heads=3),
    "gqa": dict(hidden_size=128, num_heads=4, num_kv_heads=2),
}


@pytest.mark.parametrize("case", ["tiles", *sorted(FALLBACKS)])
def test_block_falls_back_to_the_bhsd_kernels_by_shape(case, monkeypatch):
    """The block chooses the form by what it can observe: shapes whose lane
    tiles hold no whole heads of q, k and v keep split + ``rope`` + the
    (B, H, S, D) kernels — and ``attention_layout``, which the trainer
    reports at start-up, says so."""
    import dataclasses

    import distributedtensorflow_tpu.ops.flash_attention as fa
    from distributedtensorflow_tpu.models import gpt

    cfg = dataclasses.replace(
        gpt.gpt_tiny(), attn_impl="pallas", num_layers=1,
        **FALLBACKS.get(case, {}))
    taken = []
    for name in ("flash_attention", "flash_attention_qkv"):
        real = getattr(fa, name)

        def spy(*args, _real=real, _name=name, **kw):
            taken.append(_name)
            return _real(*args, **kw)

        monkeypatch.setattr(fa, name, spy)
    ids = jnp.zeros((1, 64), jnp.int32)
    model = gpt.GPTLM(cfg)
    logits = model.apply(model.init(jax.random.PRNGKey(0), ids), ids)
    assert np.isfinite(np.asarray(logits)).all()
    want = "qkv_tiles" if case == "tiles" else "bhsd"
    assert gpt.attention_layout(cfg, 64) == model.flash_layout(64) == want
    entry = {"qkv_tiles": "flash_attention_qkv", "bhsd": "flash_attention"}
    assert set(taken) == {entry[want]}, taken


_A_ROW = 64 * (128 * 2 + 4 * 4)   # o (S, H*D) bf16 + the LSE (H, S) float32


@pytest.mark.parametrize("changes, want", [
    pytest.param(dict(remat=True), ("saved", 2 * _A_ROW), id="tiles_remat"),
    pytest.param(dict(remat=True, remat_attn=True), ("saved", 2 * _A_ROW),
                 id="tiles_both_remats"),
    pytest.param(dict(remat=True, dtype=jnp.float32),
                 ("saved", 2 * 64 * (128 * 4 + 4 * 4)), id="tiles_float32"),
    pytest.param(dict(remat=True, **FALLBACKS["gqa"]), ("recomputed", 0),
                 id="bhsd_remat"),
    pytest.param(dict(remat=True, attn_impl="xla"), ("recomputed", 0),
                 id="xla_remat"),
    pytest.param(dict(remat_attn=True), ("recomputed", 0),
                 id="tiles_attn_remat_alone"),
    pytest.param(dict(), (None, None), id="tiles_no_remat"),
    pytest.param(dict(attn_impl="xla"), (None, None), id="xla_no_remat"),
])
def test_attn_residuals_says_what_the_backward_does(changes, want):
    """``GPTLM.attn_residuals``, beside ``flash_layout`` on the trainer's
    start-up row: "saved" with the bytes of o (B, S, H*D) and the
    log-sum-exp (B, H, S) float32 where a remat'd block's attention took
    the tile kernels, "recomputed" where a checkpoint runs another form
    (or the attention-only one runs this form) again, null where nothing
    is rematerialised."""
    import dataclasses

    from distributedtensorflow_tpu.models import gpt

    cfg = dataclasses.replace(
        gpt.gpt_tiny(), **{"attn_impl": "pallas", **changes})
    model = gpt.GPTLM(cfg)
    assert model.attn_residuals(2, 64) == want
    assert gpt.GPTLM(cfg, decode=True).attn_residuals(2, 64) == (None, None)


def test_attn_residuals_counts_a_devices_rows(devices):
    """Under a mesh the kernel runs per shard of the batch axes, and the
    bytes are a device's: a batch the axes do not divide is replicated
    (``kernel_axes``), every device holding all of it."""
    import dataclasses

    from distributedtensorflow_tpu.models import gpt
    from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh

    model = gpt.GPTLM(dataclasses.replace(
        gpt.gpt_tiny(), attn_impl="pallas", remat=True))
    with jax.sharding.set_mesh(build_mesh(MeshSpec(data=2, fsdp=2, model=2),
                                          devices)):
        assert model.attn_residuals(8, 64) == ("recomputed", 0)  # bhsd
    with jax.sharding.set_mesh(build_mesh(MeshSpec(data=4, fsdp=2),
                                          devices)):
        assert model.attn_residuals(16, 64) == ("saved", 2 * _A_ROW)
        assert model.attn_residuals(6, 64) == ("saved", 6 * _A_ROW)


def test_fused_projection_layout_follows_what_it_can_observe(monkeypatch):
    import distributedtensorflow_tpu.ops.flash_attention as fa

    f32 = jnp.float32
    assert fa.tile_heads(16, 16, 64) == 2
    assert fa.tile_heads(12, 12, 64) == 2
    assert fa.tile_heads(8, 8, 32) == 4
    assert fa.tile_heads(3, 3, 128) == fa.tile_heads(2, 2, 256) == 1
    assert fa.tile_heads(4, 4, 96) is None
    assert fa.tile_heads(3, 3, 64) is None
    assert fa.tile_heads(4, 2, 64) is None
    # off the TPU "auto" keeps XLA's attention; forced, the shape decides
    assert fa.qkv_layout(1024, 16, 16, 64, f32) == "xla"
    assert fa.qkv_layout(1024, 16, 16, 64, f32,
                         implementation="xla") == "xla"
    assert fa.qkv_layout(1024, 16, 16, 64, f32,
                         implementation="pallas") == "qkv_tiles"
    assert fa.qkv_layout(1024, 16, 4, 64, f32,
                         implementation="pallas") == "bhsd"
    # the golden backward is the (B, S, H, D) operands'
    assert fa.qkv_layout(1024, 16, 16, 64, f32, implementation="pallas",
                         backward_impl="xla") == "bhsd"
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    assert fa.qkv_layout(1024, 16, 16, 64, jnp.bfloat16) == "qkv_tiles"
    assert fa.qkv_layout(512, 16, 16, 64, jnp.bfloat16) == "xla"
    with pytest.raises(ValueError, match="lane tiles"):
        fa.flash_attention_qkv(jnp.zeros((1, 64, 3 * 3 * 64)), 3)
    with pytest.raises(ValueError, match="lane tiles, got"):
        fa.flash_attention_qkv(
            jnp.zeros((1, 64, 3 * 2 * 64)), 2,
            rope=(jnp.zeros((1, 64, 64)), jnp.zeros((1, 64, 64))))


# --- a causal diagonal block walked in row sub-tiles ------------------------

_LONG_WINDOW = 300   # crosses sub-tiles of 256: rows 300+ lose their oldest keys

SUB_TILE_CASES = [
    # depth, heads (one lane tile), rotation, rows, seq
    (64, 2, True, None, 1024),
    (64, 2, False, None, 1024),
    (128, 1, True, None, 1024),
    (32, 4, True, None, 1024),
    (64, 2, True, "mask", 1024),
    (128, 1, False, "segments", 1024),
    (32, 4, False, "window", 1024),
    # a row whose every visible key is masked (left padding): finite, and
    # the rows that see a key are what they were
    (64, 2, True, "leftpad", 1024),
    # two blocks a side: the diagonal steps sub-tiled, the step below the
    # diagonal whole, one running softmax and one dq over both
    (64, 2, True, None, 2048),
    (128, 1, False, "mask", 2048),
    (32, 4, True, "segments", 2048),
    (64, 2, False, "window", 2048),
]


def _sub_tile_rows(rows, s):
    """``(mask, segment_ids, window, live)`` of a case over ``s`` tokens."""
    pos = np.arange(s)[None, :]
    live = np.ones((1, s), bool)
    mask = seg = window = None
    if rows == "mask":
        mask = live = pos < s - 90
    elif rows == "leftpad":
        mask = live = pos >= 70
    elif rows == "segments":
        seg = (pos >= 410).astype(np.int32) + (pos >= 1500)
    elif rows == "window":
        window = _LONG_WINDOW
    return (None if mask is None else jnp.asarray(mask),
            None if seg is None else jnp.asarray(seg), window,
            jnp.asarray(live))


def _dense_o_and_lse(qkv, pos, h, *, mask, segment_ids, window):
    """The plain float32 reference: split, ``rope``, the (S, S) scores of
    every head under the masks, softmax; o (B, S, H*D) and the log-sum-exp
    (B, H, 1, S) as the kernels lay them out."""
    from distributedtensorflow_tpu.models.gpt import rope

    q, k, v = _split_heads(qkv, h)
    if pos is not None:
        q, k = rope(q, pos, 1e4), rope(k, pos, 1e4)
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
    back = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    keep = (back >= 0) if window is None else (back >= 0) & (back < window)
    keep = keep[None, None]
    if mask is not None:
        keep = keep & mask[:, None, None, :]
    if segment_ids is not None:
        keep = keep & (segment_ids[:, :, None]
                       == segment_ids[:, None, :])[:, None]
    scores = jnp.where(keep, scores, -1e9)
    lse = jax.nn.logsumexp(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(scores - lse[..., None]), v)
    return o.reshape(qkv.shape[0], s, -1), lse[:, :, None, :]


@pytest.mark.parametrize(
    "d,h,rotate,rows,s", SUB_TILE_CASES,
    ids=[f"d{c[0]}-h{c[1]}-{'rope' if c[2] else 'norope'}-"
         f"{c[3] or 'norows'}-s{c[4]}" for c in SUB_TILE_CASES])
def test_causal_sub_tiles_match_whole_blocks_and_the_reference(
        d, h, rotate, rows, s, monkeypatch):
    """A block on the causal diagonal walked in row sub-tiles of 256 that
    end at their own diagonal (``causal_tile``) against the same kernels
    taking the block whole, and against the plain float32 reference: o,
    the log-sum-exp and d``qkv``.  A skipped score was an exact 0.0 after
    the exponential, so the two kernels differ by the order of float32
    additions alone (row sums and products of <= 1024 terms: 1e-5, relative
    and absolute; interpreted on the CPU they come out equal); the
    reference is held to what the whole-block kernels are held to."""
    import distributedtensorflow_tpu.ops.flash_attention as fa

    qkv, pos, tabs = _fused_case(d, h, s=s, b=1)
    if not rotate:
        pos = tabs = None
    mask, seg, window, live = _sub_tile_rows(rows, s)
    weight = jax.random.normal(jax.random.PRNGKey(9), (1, s, h * d))
    weight = weight * live[:, :, None]
    kw = dict(heads=h, causal=True, interpret=True, window=window,
              block_q=1024, block_k=1024)

    def forward(tile):
        return fa._tiles_forward(qkv, tabs, mask, seg, causal_tile=tile,
                                 **kw)

    def grad(tile):
        monkeypatch.setattr(fa, "CAUSAL_TILE", tile)
        return jax.grad(lambda x: jnp.sum(fa.flash_attention_qkv(
            x, h, rope=tabs, mask=mask, segment_ids=seg, causal=True,
            window=window, interpret=True, block_q=1024, block_k=1024)
            * weight))(qkv)

    assert fa.causal_tile(1024, 1024, True) == 256
    (o, lse), (o_whole, lse_whole) = forward(256), forward(None)
    assert np.isfinite(np.asarray(o)).all()
    assert np.isfinite(np.asarray(lse)).all()
    rows_live = live[:, :, None]
    np.testing.assert_allclose(o * rows_live, o_whole * rows_live,
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(lse * live[:, None, None, :],
                               lse_whole * live[:, None, None, :],
                               atol=1e-5, rtol=1e-5)
    o_ref, lse_ref = _dense_o_and_lse(qkv, pos, h, mask=mask,
                                      segment_ids=seg, window=window)
    np.testing.assert_allclose(o * rows_live, o_ref * rows_live,
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse * live[:, None, None, :],
                               lse_ref * live[:, None, None, :],
                               atol=2e-5, rtol=2e-5)
    got, whole = grad(256), grad(0)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, whole, atol=1e-5, rtol=1e-5)
    ref = jax.grad(lambda x: jnp.sum(_dense_o_and_lse(
        x, pos, h, mask=mask, segment_ids=seg, window=window)[0]
        * weight))(qkv)
    np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


def _dot_flops(jaxpr):
    """The multiply-adds x 2 of every ``dot_general`` in ``jaxpr`` and the
    jaxprs its equations hold."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            depth = int(np.prod([eqn.invars[0].aval.shape[a]
                                 for a in contract]))
            total += 2 * depth * int(np.prod(eqn.outvars[0].aval.shape))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    total += _dot_flops(sub)
    return total


def _kernel_body(fn, *args):
    """The jaxpr of the one ``pallas_call`` ``fn(*args)`` traces to."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn.params["jaxpr"]
            for value in eqn.params.values():
                sub = getattr(value, "jaxpr", value)
                if hasattr(sub, "eqns") and (body := find(sub)) is not None:
                    return body
        return None

    return find(jax.make_jaxpr(fn)(*args).jaxpr)


def test_causal_sub_tiles_skip_the_upper_triangles_work():
    """The work is really skipped: the ``dot_general`` FLOPs in the traced
    kernel bodies at 1024 / 256 are ``causal_share`` = 0.625 of the
    whole-block bodies' (+- 2 %), forward and backward.  The backward's
    body holds a step for the diagonal and one for the blocks below it
    (``pl.when`` branches, with and without the window's edge): the steps
    on the diagonal shrink, the others are the whole block's."""
    import functools

    import distributedtensorflow_tpu.ops.flash_attention as fa

    h, d, s = 2, 64, 1024
    qkv, _, tabs = _fused_case(d, h, s=s, b=1)
    share = fa.causal_share(s, fa.causal_tile(s, s, True))
    assert share == 0.625 and fa.causal_share(s, None) == 1.0
    kw = dict(heads=h, causal=True, interpret=True, window=None,
              block_q=s, block_k=s)

    def bodies(tile):
        fwd = _kernel_body(functools.partial(
            fa._tiles_forward, causal_tile=tile, **kw), qkv, tabs, None,
            None)
        o, lse = fa._tiles_forward(qkv, tabs, None, None, **kw)
        bwd = _kernel_body(functools.partial(
            fa._tiles_backward, causal_tile=tile, force_split=False, **kw),
            qkv, tabs, None, None, o, lse, o)
        steps = [f for f in (
            _dot_flops(eqn.params["branches"][-1].jaxpr)
            for eqn in bwd.eqns if eqn.primitive.name == "cond") if f]
        return _dot_flops(fwd), steps

    (fwd, steps), (fwd_whole, steps_whole) = bodies(256), bodies(None)
    square = 2 * s * s * 128          # one product over the whole square
    assert fwd_whole == h * 2 * square
    assert fwd == pytest.approx(share * fwd_whole, rel=0.02)
    # (diagonal, diagonal + window edge, window edge, neither): five
    # products a head a step
    assert steps_whole == [h * 5 * square] * 4
    assert steps[:2] == pytest.approx([share * h * 5 * square] * 2, rel=0.02)
    assert steps[2:] == steps_whole[2:]


def test_causal_tile_is_chosen_by_what_the_call_shows():
    import distributedtensorflow_tpu.ops.flash_attention as fa

    assert fa.causal_tile(1024, 1024, True) == 256
    assert fa.causal_tile(512, 512, True) == 256
    assert fa.causal_tile(1024, 1024, False) is None   # not causal
    assert fa.causal_tile(512, 1024, True) is None     # unequal blocks
    assert fa.causal_tile(256, 256, True) is None      # one sub-tile
    assert fa.causal_tile(128, 128, True) is None
    assert fa.causal_share(512, 256) == 0.75
    assert fa.qkv_causal_tile(64, 1024, 16, 64, jnp.bfloat16) == (256, 0.625)
    assert fa.qkv_causal_tile(8, 128, 4, 64, jnp.float32) == (None, None)


# --- the backward's copies in flight (the TPU interpreter) ------------------

_IN_FLIGHT_PAD = np.arange(128)[None, :] < np.array([[100], [128], [57]])

IN_FLIGHT_CASES = [
    # depth, heads (two lane tiles), backward, blocks, causal, sub-tile,
    # window, padding mask
    (64, 4, "pallas", None, True, 32, None, False),
    (64, 4, "pallas", (32, 64), True, 0, None, False),
    (64, 4, "pallas", (64, 32), False, 0, None, False),
    (64, 4, "pallas", None, False, 0, None, True),
    (128, 2, "pallas", (64, 64), True, 32, 40, False),
    (128, 2, "pallas", (32, 64), True, 0, 33, True),
    (64, 4, "pallas_split", None, True, 0, None, False),
    (64, 4, "pallas_split", (32, 64), True, 0, None, True),
    (128, 2, "pallas_split", (64, 32), False, 0, None, False),
    (128, 2, "pallas_split", (64, 32), True, 0, 70, False),
]


@pytest.mark.parametrize(
    "d,h,backward,blocks,causal,tile,window,pad", IN_FLIGHT_CASES,
    ids=[f"d{c[0]}-{c[2]}-{'x'.join(map(str, c[3])) if c[3] else 'oneblock'}"
         f"-{'causal' if c[4] else 'full'}-t{c[5]}-w{c[6]}-"
         f"{'mask' if c[7] else 'nomask'}" for c in IN_FLIGHT_CASES])
def test_backward_copies_in_flight_land_before_the_kernel_returns(
        d, h, backward, blocks, causal, tile, window, pad, monkeypatch):
    """The tile backward leaves a finished block's copy into d``qkv`` in
    flight and waits for it where the staging block is written again; the
    grid's last step waits for what is left (``_tile_sender``).  The plain
    interpreter finishes a copy at its start and cannot tell a kernel that
    waits from one that never does.  The TPU interpreter performs a copy
    at its wait, fills memory nobody wrote with NaN and follows reads and
    writes for races: a block that was never waited for is NaN in d``qkv``,
    one re-staged under its copy lands in the wrong place.  Three
    sequences of two lane tiles: the carry crosses a tile and a sequence,
    and nothing but the last step's wait lands the last blocks.  o and
    d``qkv`` are the plain interpreter's bit for bit."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu

    import distributedtensorflow_tpu.ops.flash_attention as fa

    monkeypatch.setattr(fa, "CAUSAL_TILE", tile)
    bq, bk = blocks or (128, 128)
    assert fa.causal_tile(bq, bk, causal) == (tile or None)
    qkv, _, tabs = _fused_case(d, h, b=3)
    mask = jnp.asarray(_IN_FLIGHT_PAD) if pad else None
    g = jax.random.normal(jax.random.PRNGKey(9), (3, 128, h * d))
    if pad:
        g = g * mask[:, :, None]

    def o_and_dqkv(interpret):
        o, vjp = jax.vjp(lambda x: fa.flash_attention_qkv(
            x, h, rope=tabs, mask=mask, causal=causal, window=window,
            interpret=interpret, backward_impl=backward, block_q=bq,
            block_k=bk), qkv)
        return np.asarray(o), np.asarray(vjp(g)[0])

    o, dqkv = o_and_dqkv(pltpu.InterpretParams(
        dma_execution_mode="on_wait", detect_races=True,
        uninitialized_memory="nan"))
    races = interpret_pallas_call.races   # the last kernel's: the backward
    assert races is None or not races.races_found
    assert not np.isnan(dqkv).any() and not np.isnan(o).any()
    o_plain, dqkv_plain = o_and_dqkv(True)
    np.testing.assert_array_equal(o, o_plain)
    np.testing.assert_array_equal(dqkv, dqkv_plain)
