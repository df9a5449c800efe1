"""GPT decoder LM tests: forward, training, and model-level sequence
parallelism (ring attention inside the jitted step — SURVEY.md §5.7).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu.models import GPTLM, gpt_tiny
from distributedtensorflow_tpu.models.gpt import rope
from distributedtensorflow_tpu.parallel import (
    MeshSpec,
    build_mesh,
    sequence_parallel_attention_fn,
)
from distributedtensorflow_tpu.workloads import get_workload


def test_forward_shapes_and_dtype():
    cfg = gpt_tiny()
    model = GPTLM(cfg)
    ids = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    logits = model.apply({"params": params}, ids)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_remat_path_trains():
    """The production remat=True path: forward AND backward must work
    (flax static_argnums numbering regression gate)."""
    cfg = dataclasses.replace(gpt_tiny(), remat=True, dropout_rate=0.1)
    model = GPTLM(cfg)
    from distributedtensorflow_tpu.models import lm_loss

    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (2, 16), 0, cfg.vocab_size)
    params = model.init(rng, ids)["params"]
    loss_fn = lm_loss(model)
    (loss, _), grads = jax.value_and_grad(
        lambda p: loss_fn(p, {}, {"input_ids": ids}, rng)[:2], has_aux=True
    )(params)
    assert np.isfinite(float(loss))
    gnorm = jax.tree_util.tree_reduce(
        lambda a, g: a + float(jnp.sum(jnp.abs(g))), grads, 0.0
    )
    assert gnorm > 0


def test_chunked_xent_matches_naive_logits_loss():
    """lm_loss (vocab-chunked head, ops/xent.py) == log_softmax over the
    full logits tensor — values and grads, any chunking."""
    from distributedtensorflow_tpu.models import lm_loss
    from distributedtensorflow_tpu.ops.xent import chunked_softmax_xent

    cfg = gpt_tiny()
    model = GPTLM(cfg)
    rng = jax.random.PRNGKey(2)
    ids = jax.random.randint(rng, (2, 16), 0, cfg.vocab_size)
    mask = jnp.asarray(
        np.random.default_rng(0).integers(0, 2, (2, 16)), jnp.int32
    )
    params = model.init(rng, ids)["params"]
    batch = {"input_ids": ids, "mask": mask}

    def naive(p):
        logits = model.apply({"params": p}, ids)[:, :-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            logp, ids[:, 1:][..., None], axis=-1
        )[..., 0]
        m = mask[:, 1:].astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)

    chunked = lm_loss(model)
    (lc, _), gc = jax.value_and_grad(
        lambda p: chunked(p, {}, batch, rng)[:2], has_aux=True
    )(params)
    ln, gn = jax.value_and_grad(naive)(params)
    np.testing.assert_allclose(float(lc), float(ln), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(gc), jax.tree.leaves(gn)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        )
    # odd chunk sizes pad internally and still agree
    hidden = model.apply({"params": params}, ids, return_hidden=True)
    wte = params["wte"]["embedding"]
    full = chunked_softmax_xent(hidden[:, :-1], wte, ids[:, 1:],
                                mask[:, 1:])
    for chunk in (5, 7, 30):
        part = chunked_softmax_xent(hidden[:, :-1], wte, ids[:, 1:],
                                    mask[:, 1:], chunk_tokens=chunk)
        np.testing.assert_allclose(float(part), float(full), rtol=1e-6)


def test_remat_attn_matches_dense():
    """remat_attn=True (attention-only checkpoint) changes memory, not
    math: loss and grads match the plain path."""
    from distributedtensorflow_tpu.models import lm_loss

    rng = jax.random.PRNGKey(3)
    cfg = gpt_tiny()
    ids = jax.random.randint(rng, (2, 16), 0, cfg.vocab_size)
    losses, grads = [], []
    for remat_attn in (False, True):
        model = GPTLM(dataclasses.replace(cfg, remat_attn=remat_attn))
        params = model.init(rng, ids)["params"]
        loss_fn = lm_loss(model)
        (loss, _), g = jax.value_and_grad(
            lambda p: loss_fn(p, {}, {"input_ids": ids}, rng)[:2],
            has_aux=True,
        )(params)
        losses.append(float(loss))
        grads.append(g)
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(grads[0]), jax.tree.leaves(grads[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-7)


def test_causality():
    """Changing a future token must not change past logits."""
    cfg = gpt_tiny()
    model = GPTLM(cfg)
    rng = jax.random.PRNGKey(1)
    ids = jax.random.randint(rng, (1, 12), 0, cfg.vocab_size)
    params = model.init(rng, ids)["params"]
    base = model.apply({"params": params}, ids)
    changed = ids.at[0, 8].set((ids[0, 8] + 1) % cfg.vocab_size)
    out = model.apply({"params": params}, changed)
    np.testing.assert_allclose(
        np.asarray(base[0, :8]), np.asarray(out[0, :8]), rtol=2e-4, atol=2e-4
    )
    assert not np.allclose(np.asarray(base[0, 8:]), np.asarray(out[0, 8:]))


def test_rope_relative_shift_invariance():
    """RoPE scores depend on relative offsets: shifting all positions by a
    constant leaves q·k inner products unchanged."""
    rng = jax.random.PRNGKey(2)
    q = jax.random.normal(rng, (1, 6, 2, 8))
    k = jax.random.normal(jax.random.PRNGKey(3), (1, 6, 2, 8))
    pos = jnp.arange(6)[None, :]
    s0 = jnp.einsum(
        "bqhd,bkhd->bhqk", rope(q, pos, 1e4), rope(k, pos, 1e4)
    )
    s1 = jnp.einsum(
        "bqhd,bkhd->bhqk", rope(q, pos + 17, 1e4), rope(k, pos + 17, 1e4)
    )
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), atol=1e-4)


def test_rope_bf16_long_seq_tolerance():
    """Pin the bf16 rope combine's precision at long context (ADVICE r4).

    rope computes cos/sin tables and the rotate-combine in the compute
    dtype (bf16 on the training path) — a measured round-4 bandwidth win.
    The angles themselves are fp32 (rope_tables), which is what keeps
    large positions sane: bf16 positions at 32k would round by ~128 and
    the tables would be garbage.  This test bounds the bf16 path against
    the fp32 reference at positions up to 32k with a pinned tolerance so
    a regression that moves the trig or the position arithmetic to bf16
    fails loudly instead of silently corrupting long-context runs."""
    rng = jax.random.PRNGKey(7)
    x = jax.random.normal(rng, (1, 8, 2, 64), jnp.float32)
    # positions sampled across the full 32k range, not just the start
    pos = jnp.asarray([[0, 1, 1023, 4096, 8191, 16384, 30000, 32767]])
    ref = rope(x, pos, 1e4)  # fp32 end to end
    got = rope(x.astype(jnp.bfloat16), pos, 1e4).astype(jnp.float32)
    # bf16 rounding on x, the tables, and the combine: |x| ~ N(0,1) so
    # absolute error ~ few * 2^-8.  4e-2 abs is the pinned budget; the
    # bf16-angles failure mode this guards against produces O(1) errors.
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=0, atol=4e-2)


def test_workload_trains_loss_falls(devices):
    wl = get_workload("gpt_lm", test_size=True, global_batch_size=8)
    from distributedtensorflow_tpu.data import InputContext, device_put_batch
    from distributedtensorflow_tpu.train import create_sharded_state, make_train_step

    mesh = build_mesh(MeshSpec(data=-1), devices)
    wl = wl.for_mesh(mesh)
    rng = jax.random.PRNGKey(0)
    state, specs = create_sharded_state(
        wl.init_fn, wl.make_optimizer(), mesh, rng, rules=wl.layout
    )
    step = make_train_step(wl.loss_fn, mesh, specs)
    ctx = InputContext(1, 0, wl.global_batch_size)
    it = wl.input_fn(ctx, 0)
    losses = []
    for _ in range(30):
        batch = device_put_batch(next(it), mesh)
        state, metrics = step(state, batch, rng)
        losses.append(float(metrics["loss"]))
    # uniform-random init sits at ln(512)≈6.24; a clear sustained drop is
    # the signal (20 %+ needs more steps than a unit test should take)
    assert losses[-1] < losses[0] - 0.4, losses[::10]


@pytest.mark.parametrize("scheme", ["ring", "ulysses"])
def test_sequence_parallel_matches_dense(devices, scheme):
    """Same params, same input: SP attention inside the model must match the
    dense model's logits (the §7 'golden tests vs full attention' gate)."""
    # float32 so this is a true golden test (bf16 noise would swamp the
    # ring-vs-dense comparison at model depth).
    cfg = dataclasses.replace(gpt_tiny(), dropout_rate=0.0, dtype=jnp.float32)
    mesh = build_mesh(MeshSpec(data=2, seq=4), devices)
    dense = GPTLM(cfg)
    sp = GPTLM(cfg, sequence_parallel_attention_fn(mesh, scheme=scheme))
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (4, 32), 0, cfg.vocab_size)
    params = dense.init(rng, ids)["params"]

    ref = dense.apply({"params": params}, ids)
    with jax.sharding.set_mesh(mesh):
        got = jax.jit(lambda p, x: sp.apply({"params": p}, x))(params, ids)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(got), rtol=1e-4, atol=1e-4
    )


def test_gpt_lm_finalize_binds_sp(devices):
    wl = get_workload("gpt_lm", test_size=True, global_batch_size=8)
    assert wl.model.attn_fn is None
    sp_mesh = build_mesh(MeshSpec(data=2, seq=4), devices)
    bound = wl.for_mesh(sp_mesh)
    assert bound.model.attn_fn is not None
    dp_mesh = build_mesh(MeshSpec(data=-1), devices)
    assert wl.for_mesh(dp_mesh).model.attn_fn is None


def test_chunked_xent_random_shapes():
    """Property sweep: chunked == naive for random (B, S, V, chunk) combos
    including non-dividing chunk sizes and degenerate masks."""
    from distributedtensorflow_tpu.ops.xent import chunked_softmax_xent

    r = np.random.default_rng(7)
    for _ in range(6):
        b = int(r.integers(1, 4))
        s = int(r.integers(2, 23))
        d = int(r.integers(4, 17))
        v = int(r.integers(5, 61))
        chunk = int(r.integers(1, b * s + 5))
        hidden = jnp.asarray(r.normal(size=(b, s, d)), jnp.float32)
        wte = jnp.asarray(r.normal(size=(v, d)), jnp.float32)
        targets = jnp.asarray(r.integers(0, v, (b, s)), jnp.int32)
        mask = jnp.asarray(r.integers(0, 2, (b, s)), jnp.int32)
        got = chunked_softmax_xent(hidden, wte, targets, mask,
                                   chunk_tokens=chunk)
        logp = jax.nn.log_softmax(hidden @ wte.T, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
        m = mask.astype(jnp.float32)
        want = jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
        np.testing.assert_allclose(
            float(got), float(want), rtol=2e-6, atol=1e-6,
            err_msg=f"b={b} s={s} v={v} chunk={chunk}",
        )
    # all-masked-out rows: finite zero loss, no NaN from the 0/0 guard
    zero = chunked_softmax_xent(
        jnp.ones((1, 4, 8)), jnp.ones((5, 8)),
        jnp.zeros((1, 4), jnp.int32), jnp.zeros((1, 4), jnp.int32),
    )
    assert float(zero) == 0.0


def test_chunked_xent_out_of_range_targets_zero_weight():
    """Targets outside [0, V) — e.g. an unmasked -100 ignore label —
    contribute zero weight (optax integer-label semantics), not a wrong
    loss attributed to a clipped token id."""
    from distributedtensorflow_tpu.ops.xent import chunked_softmax_xent

    r = np.random.default_rng(3)
    hidden = jnp.asarray(r.normal(size=(2, 6, 8)), jnp.float32)
    wte = jnp.asarray(r.normal(size=(11, 8)), jnp.float32)
    targets = np.asarray(r.integers(0, 11, (2, 6)), np.int32)
    dirty = targets.copy()
    dirty[0, 1] = -100  # ignore-label convention, caller forgot to mask
    dirty[1, 4] = 11    # one past the vocab
    mask = np.ones((2, 6), np.int32)
    clean_mask = mask.copy()
    clean_mask[0, 1] = clean_mask[1, 4] = 0
    got = chunked_softmax_xent(hidden, wte, jnp.asarray(dirty),
                               jnp.asarray(mask))
    want = chunked_softmax_xent(hidden, wte, jnp.asarray(targets),
                                jnp.asarray(clean_mask))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    # all targets out of range -> 0/0 guard, finite zero loss
    assert float(chunked_softmax_xent(
        hidden, wte, jnp.full((2, 6), -100, jnp.int32), jnp.asarray(mask)
    )) == 0.0


def test_chunked_xent_bf16_compute_dtype_close_to_fp32():
    """compute_dtype=bf16 (the training configs' head path: bf16 operand
    matmul, fp32 accumulation via preferred_element_type) stays within
    bf16 rounding of the fp32 head, and its grads are finite."""
    from distributedtensorflow_tpu.ops.xent import chunked_softmax_xent

    r = np.random.default_rng(11)
    hidden = jnp.asarray(r.normal(size=(2, 32, 64)), jnp.float32)
    wte = jnp.asarray(r.normal(size=(211, 64)), jnp.float32)
    targets = jnp.asarray(r.integers(0, 211, (2, 32)), jnp.int32)

    f32 = chunked_softmax_xent(hidden, wte, targets, chunk_tokens=16)
    bf16 = chunked_softmax_xent(hidden, wte, targets, chunk_tokens=16,
                                compute_dtype=jnp.bfloat16)
    np.testing.assert_allclose(float(bf16), float(f32), rtol=2e-2)

    grads = jax.grad(
        lambda h, w: chunked_softmax_xent(
            h, w, targets, chunk_tokens=16, compute_dtype=jnp.bfloat16
        ),
        argnums=(0, 1),
    )(hidden, wte)
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g)))
        assert float(jnp.max(jnp.abs(g))) > 0.0


def test_workload_trains_with_fused_xent(devices):
    """gpt_lm with xent_impl="fused" (Pallas head, interpret mode on CPU)
    trains through the full engine path and the loss falls — the
    integration guard for ``--xent-impl=fused``."""
    wl = get_workload("gpt_lm", test_size=True, global_batch_size=8,
                      xent_impl="fused")
    assert wl.model.cfg.xent_impl == "fused"
    from distributedtensorflow_tpu.data import InputContext, device_put_batch
    from distributedtensorflow_tpu.train import create_sharded_state, make_train_step

    mesh = build_mesh(MeshSpec(data=-1), devices)
    wl = wl.for_mesh(mesh)
    rng = jax.random.PRNGKey(0)
    state, specs = create_sharded_state(
        wl.init_fn, wl.make_optimizer(), mesh, rng, rules=wl.layout
    )
    step = make_train_step(wl.loss_fn, mesh, specs)
    it = wl.input_fn(InputContext(1, 0, wl.global_batch_size), 0)
    losses = []
    for _ in range(12):
        batch = device_put_batch(next(it), mesh)
        state, metrics = step(state, batch, rng)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.15, losses


def test_chunked_bf16_logits_close_to_fp32():
    """logits_dtype=bf16 (half the head HBM traffic): NLL within bf16
    tolerance of the fp32-tile head, gradients finite and aligned."""
    from distributedtensorflow_tpu.ops.xent import chunked_softmax_xent

    r = np.random.default_rng(5)
    hidden = jnp.asarray(r.normal(size=(2, 32, 64)), jnp.float32)
    wte = jnp.asarray(r.normal(size=(211, 64)) * 0.3, jnp.float32)
    targets = jnp.asarray(r.integers(0, 211, (2, 32)), jnp.int32)

    f32 = chunked_softmax_xent(hidden, wte, targets, chunk_tokens=16)
    b16 = chunked_softmax_xent(hidden, wte, targets, chunk_tokens=16,
                               logits_dtype=jnp.bfloat16)
    np.testing.assert_allclose(float(b16), float(f32), rtol=2e-2)

    g32 = jax.grad(lambda h: chunked_softmax_xent(
        h, wte, targets, chunk_tokens=16))(hidden)
    g16 = jax.grad(lambda h: chunked_softmax_xent(
        h, wte, targets, chunk_tokens=16, logits_dtype=jnp.bfloat16))(hidden)
    assert bool(jnp.all(jnp.isfinite(g16)))
    # direction agreement: gradient cosine similarity near 1
    cos = float(
        jnp.vdot(g32, g16)
        / (jnp.linalg.norm(g32) * jnp.linalg.norm(g16))
    )
    assert cos > 0.999, cos


def test_workload_accepts_chunked_bf16():
    wl = get_workload("gpt_lm", test_size=True, global_batch_size=8,
                      xent_impl="chunked_bf16")
    assert wl.model.cfg.xent_impl == "chunked_bf16"
    variables = wl.init_fn(jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v) for k, v in wl.init_batch.items()}
    loss, _ = wl.loss_fn(variables["params"], {}, batch,
                         jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))


def test_sliding_window_model_matches_masked_dense():
    """attn_window at model level == full causal attention with an
    explicit band mask (same params): the windowed path is a masking
    change, not an architecture change."""
    import dataclasses

    from distributedtensorflow_tpu.models.gpt import GPTLM

    cfg = dataclasses.replace(gpt_tiny(), dtype=jnp.float32)
    cfg_w = dataclasses.replace(cfg, attn_window=9)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 24)))
    params = GPTLM(cfg).init(jax.random.PRNGKey(0), ids)["params"]
    got = GPTLM(cfg_w).apply({"params": params}, ids)

    # reference: same model, full attention, band mask injected via the
    # pluggable attn_fn
    from distributedtensorflow_tpu.ops.attention import xla_attention

    def banded(q, k, v):
        s = q.shape[1]
        qp = jnp.arange(s)[:, None]
        kp = jnp.arange(s)[None, :]
        keep = (qp >= kp) & (kp > qp - 9)
        return xla_attention(q, k, v, mask=keep[None, None])

    want = GPTLM(cfg, banded).apply({"params": params}, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_sliding_window_generate_matches_full_forward():
    """Windowed decode (cache masking) reproduces the windowed full
    forward's argmax chain — training/serving masking agreement."""
    import dataclasses

    from distributedtensorflow_tpu.models.generate import generate
    from distributedtensorflow_tpu.models.gpt import GPTLM

    cfg = dataclasses.replace(gpt_tiny(), attn_window=6)
    model = GPTLM(cfg)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 512, (2, 12)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    toks = generate(params, ids, cfg=cfg, max_new_tokens=4)
    cur = ids
    for _ in range(4):
        logits = model.apply({"params": params}, cur)
        nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        cur = jnp.concatenate([cur, nxt], axis=1)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(cur))


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("window", [None, 9], ids=["full", "window9"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_block_with_the_fused_entry_matches_rope_outside(dtype, window,
                                                         remat):
    """The training block in its two forms on the same parameters: the
    flash kernels reading the fused projection and rotating in VMEM
    (``attn_impl`` "pallas", interpreted here: ``attention_layout``
    "qkv_tiles") against split, ``rope`` and XLA's dense attention — the
    logits and every gradient, at the tolerance that holds the served block
    to the training block (``tests/test_serve.py``)."""
    from distributedtensorflow_tpu.models.gpt import attention_layout

    cfg = dataclasses.replace(gpt_tiny(), dtype=dtype, attn_window=window,
                              remat=remat)
    dense = GPTLM(dataclasses.replace(cfg, attn_impl="xla"))
    fused = GPTLM(dataclasses.replace(cfg, attn_impl="pallas"))
    assert attention_layout(fused.cfg, 48) == "qkv_tiles"
    assert attention_layout(dense.cfg, 48) == "xla"
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 48), 0,
                             cfg.vocab_size)
    params = dense.init(jax.random.PRNGKey(0), ids)["params"]
    # default init gives logits of ~0.02: scale the matrices up so that a
    # wrong block would show
    params = jax.tree.map(lambda p: p * 4 if p.ndim == 2 else p, params)

    def run(model):
        def loss(p):
            logits = model.apply({"params": p}, ids)
            picked = jnp.take_along_axis(
                jax.nn.log_softmax(logits), ids[:, :, None], axis=-1)
            return -jnp.mean(picked), logits
        (_, logits), grads = jax.value_and_grad(loss, has_aux=True)(params)
        return np.asarray(logits), grads

    flat = lambda g: np.concatenate(
        [np.asarray(x, np.float32).ravel() for x in jax.tree.leaves(g)])
    (want, g_want), (got, g_got) = run(dense), run(fused)
    assert got.dtype == want.dtype == np.float32
    g_want, g_got = flat(g_want), flat(g_got)
    if dtype == jnp.float32:
        # that test's 1e-5 is between two orders of the same sums; here a
        # row's softmax is summed in another order, over logits of +-10
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
        np.testing.assert_allclose(g_got, g_want, atol=2e-5, rtol=1e-3)
        return
    assert np.isfinite(got).all() and np.isfinite(g_got).all()
    assert (got.argmax(-1) == want.argmax(-1)).mean() >= 0.75
    assert np.median(np.abs(got - want)) < 0.1
    # bf16 rounding moves a gradient of these scaled-up weights by a fifth:
    # the fused entry (one rounding of the rotation, float32 tables) must
    # be no further from the float32 block than rope outside is
    exact, g_exact = run(GPTLM(dataclasses.replace(
        cfg, dtype=jnp.float32, attn_impl="xla")))
    g_exact = flat(g_exact)
    assert (np.linalg.norm(g_got - g_exact)
            <= 1.1 * np.linalg.norm(g_want - g_exact))
    assert (np.median(np.abs(got - exact))
            <= 1.1 * np.median(np.abs(want - exact)))


# -- what a remat'd block keeps (PR 37) ---------------------------------------

def _kernel_calls(jaxpr, out=None):
    """{kernel name: call sites} of every ``pallas_call`` in ``jaxpr``."""
    out = {} if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            out[name] = out.get(name, 0) + 1
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, out)
    return out


def _policy_less(block):
    """``models.gpt.remat_block`` as it was before the block kept anything:
    ``jax.checkpoint`` with no policy."""
    import flax.linen as nn

    if isinstance(block, type):
        return nn.remat(block, static_argnums=(3,))
    return jax.checkpoint(block)


def _loss_and_grads(loss, params, *args):
    """``(jaxpr, (loss, grads))`` of ``loss(params, *args)``, traced once."""
    traced = jax.jit(jax.value_and_grad(loss)).trace(params, *args)
    out = traced.lower().compile()(params, *args)
    return traced.jaxpr, jax.tree.map(np.asarray, out)


def _both_remats(monkeypatch, build):
    """``build()`` → ``(loss, params, *args)`` run under the block remat
    as it is and under a policy-less one; the models are built anew each
    time (the pipeline keeps a jitted function a model)."""
    from distributedtensorflow_tpu.models import gpt, gpt_moe, gpt_pipeline

    kept = _loss_and_grads(*build())
    for module in (gpt, gpt_moe, gpt_pipeline):
        monkeypatch.setattr(module, "remat_block", _policy_less)
    return kept, _loss_and_grads(*build())


def _assert_bitwise(got, want):
    flat_got, tree = jax.tree.flatten(got)
    flat_want, tree_want = jax.tree.flatten(want)
    assert tree == tree_want
    for a, b in zip(flat_got, flat_want):
        np.testing.assert_array_equal(a, b)


#: how a 3-layer tiny model is sent down each form of ``attention_layout``
REMAT_FORMS = {
    "qkv_tiles": dict(attn_impl="pallas"),
    "bhsd": dict(attn_impl="pallas", num_kv_heads=2),
    "xla": dict(attn_impl="xla"),
}


@pytest.mark.parametrize("meshed", [False, True], ids=["one_device", "data4"])
@pytest.mark.parametrize("form", sorted(REMAT_FORMS))
def test_remat_block_keeps_the_flash_residuals(form, meshed, devices,
                                               monkeypatch):
    """A remat'd block keeps o and the log-sum-exp of its tile kernel: the
    gradient program of a 3-layer model holds ``num_layers`` fewer
    ``flash_fwd`` calls than under a policy-less checkpoint, as many
    ``flash_bwd``, and gives the same bits — alone and per shard of a
    four-way ``data`` mesh.  A block that took another form holds no such
    name and traces to the policy-less program.

    float32: the kernels are interpreted here, so XLA sees their bodies
    and (``xla_allow_excess_precision``) drops a bf16 rounding between a
    recomputed o and its reader that a saved o has been through; in
    float32 there is no rounding to drop, and on the chip the kernel is a
    custom call whose o is stored either way."""
    import contextlib
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributedtensorflow_tpu.models.gpt import attention_layout

    cfg = dataclasses.replace(gpt_tiny(), num_layers=3, remat=True,
                              dtype=jnp.float32, **REMAT_FORMS[form])
    ids = jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0,
                             cfg.vocab_size)
    params = GPTLM(cfg).init(jax.random.PRNGKey(0), ids)["params"]
    mesh = contextlib.nullcontext()
    if meshed:
        mesh = build_mesh(MeshSpec(data=4), devices[:4])
        ids = jax.device_put(ids, NamedSharding(mesh, P("data")))
        mesh = jax.sharding.set_mesh(mesh)

    def build():
        model = GPTLM(cfg)

        def loss(p, ids):
            logits = model.apply({"params": p}, ids)
            return -jnp.mean(jnp.take_along_axis(
                jax.nn.log_softmax(logits), ids[:, :, None], axis=-1))
        return loss, params, ids

    with mesh:
        assert attention_layout(cfg, 32) == form
        state, kept_bytes = GPTLM(cfg).attn_residuals(*ids.shape)
        (jaxpr, got), (jaxpr_less, want) = _both_remats(monkeypatch, build)
    _assert_bitwise(got, want)
    calls, calls_less = _kernel_calls(jaxpr.jaxpr), _kernel_calls(
        jaxpr_less.jaxpr)
    if form == "qkv_tiles":
        assert calls_less["flash_fwd"] == 2 * cfg.num_layers
        assert calls["flash_fwd"] == cfg.num_layers
        assert calls["flash_bwd"] == calls_less["flash_bwd"] == cfg.num_layers
        # o (B, S, H*D) and the LSE (B, H, S) float32, of a device's rows
        rows = ids.shape[0] // (4 if meshed else 1)
        assert (state, kept_bytes) == (
            "saved", rows * 32 * (128 * 4 + cfg.num_heads * 4))
        return
    assert calls == calls_less and (state, kept_bytes) == ("recomputed", 0)
    # the two programs differ by the policy the checkpoint carries only
    strip = lambda j: re.sub(r"policy=[^\n\]]*", "policy=", str(j))
    assert strip(jaxpr) == strip(jaxpr_less)
    assert "save_only_these_names" in str(jaxpr)


def test_remat_block_is_the_moe_trunks_remat_too(monkeypatch):
    """``GPTMoELM`` remats its dense and its expert blocks through
    ``models.gpt.remat_block``: the same bits, a ``flash_fwd`` a layer
    fewer."""
    from distributedtensorflow_tpu.models.gpt_moe import (
        GPTMoELM, gpt_moe_tiny, moe_lm_loss)

    cfg = dataclasses.replace(gpt_moe_tiny(), dtype=jnp.float32, remat=True,
                              attn_impl="pallas")
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (2, 32), 0, cfg.vocab_size)
    params = GPTMoELM(cfg).init(rng, ids)["params"]

    def build():
        loss_fn = moe_lm_loss(GPTMoELM(cfg))
        return (lambda p: loss_fn(p, {}, {"input_ids": ids}, rng)[0]), params

    (jaxpr, got), (jaxpr_less, want) = _both_remats(monkeypatch, build)
    _assert_bitwise(got, want)
    calls, calls_less = _kernel_calls(jaxpr.jaxpr), _kernel_calls(
        jaxpr_less.jaxpr)
    assert calls_less["flash_fwd"] == 2 * cfg.num_layers
    assert calls["flash_fwd"] == cfg.num_layers
    assert calls["flash_bwd"] == calls_less["flash_bwd"] == cfg.num_layers


def test_remat_block_is_the_pipeline_stages_remat_too(devices, monkeypatch):
    """The pipeline's stages scan a checkpointed block function that comes
    from ``models.gpt.remat_block``: the same bits, and the scanned
    backward body no longer holds a ``flash_fwd`` (one call site in the
    program, the forward scan's, where the policy-less one has two)."""
    from distributedtensorflow_tpu.models.gpt_pipeline import (
        PipelinedGPT, pipelined_lm_loss)

    mesh = build_mesh(MeshSpec(data=4, pipe=2), devices)
    cfg = dataclasses.replace(gpt_tiny(), dtype=jnp.float32, remat=True,
                              attn_impl="pallas")
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (8, 32), 0, cfg.vocab_size)
    params = PipelinedGPT(cfg, mesh, n_microbatches=2).init(rng)["params"]

    def build():
        loss_fn = pipelined_lm_loss(
            PipelinedGPT(cfg, mesh, n_microbatches=2))
        return (lambda p: loss_fn(p, {}, {"input_ids": ids}, rng)[0]), params

    (jaxpr, got), (jaxpr_less, want) = _both_remats(monkeypatch, build)
    _assert_bitwise(got, want)
    assert _kernel_calls(jaxpr_less.jaxpr) == {"flash_fwd": 2, "flash_bwd": 1}
    assert _kernel_calls(jaxpr.jaxpr) == {"flash_fwd": 1, "flash_bwd": 1}
