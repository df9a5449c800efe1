"""``ops/kda.py`` on the CPU: the three forms of the gated delta rule are one
mathematics.  The recurrence (``kda_recurrent``, a ``lax.scan`` over tokens)
is the yardstick; the chunked form in plain ``jax.numpy``, the chunk kernel
and the step kernel (both interpreted) are held to it in float32 — the same
sums in another order, so outputs of size ~0.05 and states of size ~0.5 agree
to 1e-5 — at ragged lengths, across chunk and sub-block boundaries, and with
``g`` drawn down to the lower bound of -5 a token, where the decay ratios of a
sub-block of 16 tokens reach e^80.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu.ops import kda, ssm

TOL = 1e-5
#: sha256[:16] of the jaxprs of ling's forms at the parent commit
#: (``test_lings_programs_are_the_parents``)
PARENT_CHUNK_SCAN = "9cd0d92439b1dbc1"
PARENT_STEP = "382a0d07b93dacf1"
PARENT_STEP_KERNEL = "7b19ac263da8e1df"


@pytest.fixture(autouse=True)
def _exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def _case(seed, t, h, d, bound=False):
    """q, k L2-normalised (q scaled), v, g in (-5, 0), beta in (0, 1).
    ``bound``: half of the channels sit at the lower bound, whole sub-blocks
    of them."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q, k, v = (jax.random.normal(ks[i], (t, h, d)) for i in range(3))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (t, h, d)) * 2.0)
    if bound:
        g = jnp.where(jax.random.uniform(ks[4], (1, h, d)) < 0.5,
                      -5.0 + 1e-6, g)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (t, h)))
    state = jax.random.normal(ks[6], (h, d, d)) * 0.1
    return (q, k, v, g, beta), state


def _close(got, want, valid=None):
    (o, s), (o0, s0) = got, want
    np.testing.assert_allclose(o[:valid], o0[:valid], atol=TOL, rtol=0)
    np.testing.assert_allclose(s, s0, atol=TOL, rtol=0)


def test_the_recurrence_is_decay_correct_read():
    """One token by hand, in the published orientation (key x value)."""
    (q, k, v, g, beta), state = _case(0, 1, 2, 8)
    o, s = kda.kda_recurrent(q, k, v, g, beta, state)
    for h in range(2):
        s_kv = np.asarray(state[h]).T                   # (key, value)
        s_dec = np.exp(np.asarray(g[0, h]))[:, None] * s_kv
        s_new = s_dec + float(beta[0, h]) * np.outer(
            k[0, h], np.asarray(v[0, h]) - s_dec.T @ np.asarray(k[0, h]))
        np.testing.assert_allclose(s[h].T, s_new, atol=1e-6)
        np.testing.assert_allclose(o[0, h], s_new.T @ np.asarray(q[0, h]),
                                   atol=1e-6)


@pytest.mark.parametrize("t,valid,bound,why", [
    (64, None, False, "one whole chunk"),
    (128, None, True, "two chunks, channels at the lower bound"),
    (128, 65, True, "one token into the second chunk"),
    (128, 17, False, "one token into the second sub-block"),
    (64, 16, True, "exactly one sub-block"),
    (192, 1, False, "one real token"),
    (192, 130, True, "ragged, three chunks"),
])
def test_chunked_form_is_the_recurrence(t, valid, bound, why):
    xs, state = _case(t, t, 3, 32, bound)
    want = kda.kda_recurrent(*xs, state, valid)
    _close(kda.kda_chunked(*xs, state, valid), want, valid)


@pytest.mark.parametrize("t,valid,bound", [
    (128, None, True), (128, 70, False), (64, 5, True), (192, 129, True)])
def test_chunk_scan_at_the_published_head_width_is_the_recurrence(
        t, valid, bound):
    xs, state = _case(t + 1, t, 2, 128, bound)
    want = kda.kda_recurrent(*xs, state, valid)
    _close(kda.kda_chunk_scan(*xs, state, valid), want, valid)


def test_a_padded_chunk_leaves_the_state_of_its_real_tokens():
    """Positions past ``valid`` are identity steps: the state after a chunk
    of 40 real tokens is the state after those 40 alone, whatever the
    padding holds."""
    xs, state = _case(3, 64, 2, 32)
    _, s_pad = kda.kda_chunked(*xs, state, 40)
    _, s_40 = kda.kda_recurrent(*(x[:40] for x in xs), state)
    np.testing.assert_allclose(s_pad, s_40, atol=TOL, rtol=0)


def test_chunks_carry_the_state():
    """Two calls of one chunk each are one call of two."""
    xs, state = _case(4, 128, 2, 128, True)
    o, s = kda.kda_chunk_scan(*xs, state, None)
    o1, s1 = kda.kda_chunk_scan(*(x[:64] for x in xs), state, None)
    o2, s2 = kda.kda_chunk_scan(*(x[64:] for x in xs), s1, None)
    np.testing.assert_allclose(jnp.concatenate([o1, o2]), o, atol=TOL)
    np.testing.assert_allclose(s2, s, atol=TOL)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_step_is_one_token_of_the_recurrence(impl):
    slots, h, d = 3, 16, 128
    xs, _ = _case(5, slots, h, d, True)
    pool = jax.random.normal(jax.random.PRNGKey(6), (2, slots, h, d, d)) * 0.1
    o, after = kda.kda_step(*xs, pool, 1, impl=impl)
    assert np.array_equal(after[0], pool[0])        # the other layer
    for b in range(slots):
        o_b, s_b = kda.kda_recurrent(*(x[b:b + 1] for x in xs), pool[1, b])
        np.testing.assert_allclose(o[b], o_b[0], atol=TOL, rtol=0)
        np.testing.assert_allclose(after[1, b], s_b, atol=TOL, rtol=0)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_an_identity_step_leaves_the_state_bit_for_bit(impl):
    """``g = 0`` and ``beta = 0`` is how an inactive slot is stepped."""
    slots, h, d = 2, 16, 128
    (q, k, v, g, beta), _ = _case(7, slots, h, d)
    pool = jax.random.normal(jax.random.PRNGKey(8), (1, slots, h, d, d))
    live = jnp.asarray([True, False])
    _, after = kda.kda_step(
        q, k, v, jnp.where(live[:, None, None], g, 0.0),
        jnp.where(live[:, None], beta, 0.0), pool, 0, impl=impl)
    assert np.array_equal(after[0, 1], pool[0, 1])
    assert not np.array_equal(after[0, 0], pool[0, 0])


@pytest.mark.parametrize("key_dim,chunk,impl,want", [
    (128, 2048, "pallas", "chunked"),   # no kernel: the same on any backend
    (128, 2048, "xla", "chunked"),
    (16, 64, "auto", "chunked"),
    (128, 8, "pallas", "plain"),        # not whole chunks of 64
])
def test_chunk_scan_formulation_says_what_is_taken(key_dim, chunk, impl,
                                                   want):
    rows = ssm.DeltaState(4, key_dim, key_dim, 4)
    assert rows.chunk_formulation(chunk, impl) == want


def test_delta_state_is_three_tails_and_a_float32_matrix_a_head():
    rows = ssm.DeltaState(heads=32, key_dim=128, value_dim=128, d_conv=4)
    assert rows.names == ("q_tail", "k_tail", "v_tail", "delta_state")
    shapes = rows.arrays(jnp.bfloat16)
    assert [s for s, _ in shapes] == [(3 * 4096,)] * 3 + [(32, 128, 128)]
    assert [str(d) for _, d in shapes] == ["bfloat16"] * 3 + ["float32"]
    # 2.10 MB of matrices + 73.7 KB of tails a slot a layer
    assert rows.slot_bytes(jnp.bfloat16) == 32 * 128 * 128 * 4 + 3 * 24576
    assert rows.step_formulation("pallas") == "kda_step"
    assert ssm.DeltaState(4, 16, 16, 4).step_formulation("pallas") == "plain"


def test_the_doubled_inverse_is_forward_substitution():
    """``(I + A)^-1`` by doubling against ``numpy``'s solve, also where keys
    repeat (A all ones below the diagonal: the powers of A grow binomially
    and the Neumann product would lose every digit)."""
    rng = np.random.default_rng(0)
    for a in (np.tril(rng.standard_normal((64, 64)) * 0.3, -1),
              np.tril(np.ones((64, 64)), -1)):
        got = np.asarray(kda._unit_lower_inverse(jnp.asarray(a, jnp.float32)))
        want = np.linalg.inv(np.eye(64) + a)
        np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())


# -- a scalar gate a head, unbounded (Gated DeltaNet: models.qwen3_next) -------

def _scalar_case(seed, t, hk, h, dk, dv, strongest=1.0):
    """``_case`` under a scalar gate: ``hk`` q/k heads shared by ``h`` value
    heads, ``g`` (t, h, 1) drawn in ``(-strongest, 0)``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(ks[i], (t, hk, dk)) for i in range(2))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (t, h, dv))
    g = -strongest * jax.random.uniform(ks[3], (t, h, 1))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (t, h)))
    state = jax.random.normal(ks[5], (h, dv, dk)) * 0.1
    return (q, k, v, g, beta), state


@pytest.mark.parametrize("t,valid,strongest,why", [
    (64, None, 1.0, "one whole chunk"),
    (128, 65, 1.0, "one token into the second chunk"),
    (192, 130, 5.0, "ragged, three chunks, a carried state"),
    (192, 1, 1.0, "one real token"),
    (128, None, 60.0, "gates of -60 a token: twelve times KDA's bound"),
    (192, 150, 120.0, "gates of -120 a token, ragged"),
])
def test_scalar_gate_chunked_form_is_the_recurrence(t, valid, strongest, why):
    xs, state = _scalar_case(t, t, 2, 4, 32, 16, strongest)
    want = kda.kda_recurrent(*xs, state, valid)
    got = kda.kda_chunk_scan(*xs, state, valid)
    assert np.isfinite(got[0]).all() and np.isfinite(got[1]).all()
    _close(got, want, valid)


def test_scalar_gate_at_the_published_head_widths_carries_the_state():
    """16 / 32 heads of 128: two calls of one chunk each are one call of two,
    and both are the recurrence."""
    xs, state = _scalar_case(11, 128, 2, 4, 128, 128, 60.0)
    want = kda.kda_recurrent(*xs, state)
    o1, s1 = kda.kda_chunk_scan(*(x[:64] for x in xs), state, None)
    o2, s2 = kda.kda_chunk_scan(*(x[64:] for x in xs), s1, None)
    _close((jnp.concatenate([o1, o2]), s2), want)
    _close(kda.kda_chunk_scan(*xs, state, None), want)


def test_scalar_gate_padded_chunk_leaves_the_state_of_its_real_tokens():
    xs, state = _scalar_case(12, 64, 2, 4, 32, 16, 60.0)
    _, s_pad = kda.kda_chunked(*xs, state, 40)
    _, s_40 = kda.kda_recurrent(*(x[:40] for x in xs), state)
    np.testing.assert_allclose(s_pad, s_40, atol=TOL, rtol=0)


def test_a_scalar_gate_is_the_channel_gate_broadcast():
    """One rule: a scalar gate broadcast to the key's channels, q and k
    repeated to the value heads, through KDA's recurrence gives the same
    state and outputs, bit for bit."""
    (q, k, v, g, beta), state = _scalar_case(13, 40, 2, 4, 32, 16, 5.0)
    o, s = kda.kda_recurrent(q, k, v, g, beta, state)
    qr, kr = jnp.repeat(q, 2, axis=1), jnp.repeat(k, 2, axis=1)
    o2, s2 = kda.kda_recurrent(qr, kr, v, jnp.broadcast_to(g, qr.shape),
                               beta, state)
    assert np.array_equal(o, o2) and np.array_equal(s, s2)


def test_the_channel_body_is_unsound_where_the_scalar_body_is_not():
    """Why ``_chunk_scalar`` exists: at -60 a token the per-channel body's
    first factor overflows float32 (its precondition is ``g >= -5``)."""
    (q, k, v, g, beta), state = _scalar_case(14, 64, 4, 4, 32, 16, 60.0)
    g = jnp.minimum(g, -55.0)
    o, s = kda.kda_chunked(q, k, v, jnp.broadcast_to(g, q.shape), beta, state)
    assert not (np.isfinite(o).all() and np.isfinite(s).all())
    assert np.isfinite(kda.kda_chunked(q, k, v, g, beta, state)[0]).all()


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_scalar_gate_step_is_one_token_and_leaves_the_idle_bit_for_bit(impl):
    slots, hk, h, d = 3, 8, 16, 128
    (q, k, v, g, beta), _ = _scalar_case(15, slots, hk, h, d, d, 60.0)
    pool = jax.random.normal(jax.random.PRNGKey(16), (2, slots, h, d, d)) * .1
    live = jnp.asarray([True, False, True])
    o, after = kda.kda_step(
        q, k, v, jnp.where(live[:, None, None], g, 0.0),
        jnp.where(live[:, None], beta, 0.0), pool, 1, impl=impl)
    assert np.array_equal(after[0], pool[0])            # the other layer
    assert np.array_equal(after[1, 1], pool[1, 1])      # the idle slot
    for b in (0, 2):
        o_b, s_b = kda.kda_recurrent(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                                     g[b:b + 1], beta[b:b + 1], pool[1, b])
        np.testing.assert_allclose(o[b], o_b[0], atol=TOL, rtol=0)
        np.testing.assert_allclose(after[1, b], s_b, atol=TOL, rtol=0)


def test_gated_delta_state_is_one_tail_and_a_float32_matrix_a_value_head():
    rows = ssm.GatedDeltaState(key_heads=16, heads=32, key_dim=128,
                               value_dim=128, d_conv=4)
    assert rows.names == ("conv_tail", "delta_state")
    assert rows.conv_channels == 8192
    shapes = rows.arrays(jnp.bfloat16)
    assert [s for s, _ in shapes] == [(3 * 8192,), (32, 128, 128)]
    assert [str(d) for _, d in shapes] == ["bfloat16", "float32"]
    assert rows.slot_bytes(jnp.bfloat16) == 2097152 + 49152
    assert rows.step_formulation("pallas") == "kda_step"
    assert rows.chunk_formulation(2048, "auto") == "chunked"


def test_lings_programs_are_the_parents():
    """A channel gate traces what it traced before the scalar form existed:
    the jaxprs of the chunked scan and of the plain step at ling_tiny's
    shapes, digested at the parent commit (b2f20e3, jax as installed; a jax
    upgrade that prints jaxprs otherwise needs the digests taken again
    there)."""
    import hashlib

    xs, state = _case(0, 64, 4, 16)
    pool = jnp.zeros((2, 64, 4, 16, 16))

    def digest(fn, *args):
        return hashlib.sha256(
            str(jax.make_jaxpr(fn)(*args)).encode()).hexdigest()[:16]

    assert digest(lambda *a: kda.kda_chunk_scan(*a, 40), *xs, state) \
        == PARENT_CHUNK_SCAN
    assert digest(lambda *a: kda.kda_step(*a, 1, impl="xla"), *xs, pool) \
        == PARENT_STEP
    xs, _ = _case(5, 3, 16, 128)
    pool = jnp.zeros((2, 3, 16, 128, 128))
    assert digest(lambda *a: kda.kda_step(*a, 1, impl="pallas",
                                          interpret=True), *xs, pool) \
        == PARENT_STEP_KERNEL
