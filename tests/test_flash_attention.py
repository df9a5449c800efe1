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
