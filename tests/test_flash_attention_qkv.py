"""``flash_attention_qkv``, the fused projection read as it lies, against
split, ``rope`` and the dense reference and against the (B, H, S, D)
kernels; its fall-back by shape and what a block keeps for its backward.

Run in Pallas interpreter mode on CPU; the (B, H, S, D) kernels' own golden
tests are ``test_flash_attention.py``, the causal sub-tiles and the
backward's copies in flight ``test_flash_attention_tiles.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flash_qkv_cases import fused_case, split_heads

from distributedtensorflow_tpu.ops.attention import xla_attention
from distributedtensorflow_tpu.ops.flash_attention import flash_attention

# --- the fused projection read as it lies (flash_attention_qkv) -------------


def _rope_then_dense(qkv, pos, h, *, causal, mask=None, segment_ids=None,
                     window=None):
    """What the fused entry replaces: split, ``rope`` outside, the dense
    reference."""
    from distributedtensorflow_tpu.models.gpt import rope

    q, k, v = split_heads(qkv, h)
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

    qkv, pos, tabs = fused_case(d, h, per_row=per_row)
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
    qkv, _, _ = fused_case(64, h)
    bq, bk = blocks or (None, None)
    kw = dict(causal=causal, interpret=True, block_q=bq, block_k=bk)

    def tiles(x):
        return flash_attention_qkv(x, h, **kw)

    def bhsd(x):
        return flash_attention(*split_heads(x, h), **kw).reshape(
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
    qkv, pos, tabs = fused_case(64, h)
    want = _rope_then_dense(qkv, pos, h, causal=True)
    x = qkv.astype(jnp.bfloat16)
    got = flash_attention_qkv(x, h, rope=tabs, causal=True, interpret=True)
    q, k, v = split_heads(x, h)
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
