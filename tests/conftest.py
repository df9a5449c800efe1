"""Test harness: run everything on an 8-device virtual CPU mesh.

The JAX analogue of the reference's logical-device splitting
(``test_util.set_logical_devices_to_at_least`` — SURVEY.md §4): one host CPU
is split into 8 XLA devices so every multi-device code path (DP/FSDP/TP/PP/
SP/EP meshes, collectives, sharding) runs on a laptop-class machine.

Must run before any JAX backend initialization.  The platform is pinned to
the CPU here and in the environment the tests' subprocesses inherit; the
persistent compilation cache is switched off for the whole session so
neither this process nor a ``train.py`` child writes one into the
checkout (``runtime.init_compile_cache`` would place it there).  The three
``test_kernel_export*`` files each load the TPU's library to describe a
v5e: under xdist they are three processes, so more than one may load it.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

# --- fast/slow lanes (SURVEY.md §4; VERDICT r3 #8) --------------------------
# `pytest -m "not slow"` is the tier-1 lane; the full suite stays the
# landing gate.  Two sources of `slow`:
#   1. tests/slow_tests.txt — nodeids measured >= ~5s on the 1-core CI box
#      (regenerate from `pytest --durations=60` when timings drift);
#   2. _PROCESS_TEST_FILES — files that spawn OS processes (multi-process
#      collectives, PS clusters, coordinator workers, subprocess smokes):
#      structurally slow AND the natural habitat of timing flakes, so they
#      are slow-laned wholesale regardless of measured time.
_SLOW_LIST = os.path.join(os.path.dirname(__file__), "slow_tests.txt")
_PROCESS_TEST_FILES = {
    "test_multi_process.py",
    "test_param_server.py",
    "test_coordinator_process.py",
    "test_data_service.py",
    "test_pipeline_mpmd.py",
    "test_examples.py",
    "test_sidecar.py",
    "test_combined_axes.py",
    "test_train_introspection_smoke.py",
    "test_train_auto_profile_smoke.py",
    "test_train_chaos_smoke.py",
    "test_train_elastic_smoke.py",
    "test_train_dynamics_smoke.py",
    "test_train_netchaos_smoke.py",
    "test_train_zero_smoke.py",
    "test_train_quant_smoke.py",
    "test_train_data_service_smoke.py",
    "test_train_fleet_smoke.py",
    "test_train_alert_chaos_smoke.py",
    "test_serve_smoke.py",
}


def _load_slow_nodeids():
    try:
        with open(_SLOW_LIST) as f:
            return {
                line.strip() for line in f
                if line.strip() and not line.startswith("#")
            }
    except OSError:
        return set()


def pytest_collection_modifyitems(config, items):
    slow_ids = _load_slow_nodeids()
    mark = pytest.mark.slow
    for item in items:
        fname = os.path.basename(item.fspath.strpath)
        if fname in _PROCESS_TEST_FILES or item.nodeid in slow_ids:
            item.add_marker(mark)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs


@pytest.fixture()
def mesh8(devices):
    """data=2 × fsdp=2 × model=2 mesh over the 8 virtual devices."""
    from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=2, fsdp=2, model=2), devices)


@pytest.fixture()
def dp_mesh(devices):
    """Pure data-parallel mesh over all 8 devices."""
    from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=-1), devices)


#: the K/V chunk kernel's tiles in the tests: stretches of 128 rows, query
#: tiles of 16, eight query heads a grid step
KV_CHUNK_SMALL_TILES = {"KV_CHUNK_STRETCH": 128, "KV_CHUNK_QUERIES": 16,
                        "KV_CHUNK_HEADS": 8}


@pytest.fixture()
def check_kv_chunk_kernel(monkeypatch):
    """``check(heads=, kv_heads=, d=, dv=, window=, sink=, start=)``: a chunk
    of 32 queries from ``start`` through the kernel ``kv_chunk_attn``
    (``ops.attention.paged_window_chunk_attention``, interpreted, at small
    tiles) against the plain loop and, in float32, against each query's
    dense sum over the rows it attends.  The table row is scattered; every
    block the chunk does not attend — the table's later columns, the rest of
    the pool, and the scratch block that the columns of a window layer's
    freed early blocks name — holds NaN and inf."""
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflow_tpu.ops import attention

    def check(*, heads, kv_heads, d, dv, window, sink, start, t=32, bs=16,
              dtype=jnp.float32, tol=2e-5, tiles=KV_CHUNK_SMALL_TILES):
        for name, value in tiles.items():
            monkeypatch.setattr(attention, name, value)
        rng = np.random.default_rng(start + heads)
        used = -(-(start + t) // bs)
        nb = used + 3
        first = 0 if window is None else max(start - window + 1, 0) // bs
        k = rng.standard_normal((2, (nb + 1) * bs, kv_heads, d))
        v = rng.standard_normal((2, (nb + 1) * bs, kv_heads, dv))
        row = rng.permutation(nb).astype(np.int32)
        poison = np.ones(nb + 1, bool)
        poison[row[first:used]] = False
        row[:first] = nb                    # freed: the scratch block
        bad = np.where(np.arange(d) % 2, np.nan, np.inf)
        k[:, np.repeat(poison, bs)] = bad
        v[:, np.repeat(poison, bs)] = bad[:dv]
        q = rng.standard_normal((t, heads, d))
        bias = rng.standard_normal(heads) + 2.0 if sink else None
        pools = (jax.vmap(attention.lay_heads)(jnp.asarray(k, dtype)),
                 jnp.asarray(v.reshape(2, -1, kv_heads * dv), dtype))
        args = (jnp.asarray(q, dtype), jnp.int32(start))
        kw = dict(layer=1, block_size=bs, window=window,
                  sink=None if bias is None else jnp.asarray(
                      bias, jnp.float32))
        assert attention.paged_chunk_formulation(
            heads, kv_heads, d, dv, bs, t, "pallas") == "kv_chunk_attn"
        got = attention.paged_window_chunk_attention(
            *args, *pools, jnp.asarray(row), impl="pallas", interpret=True,
            **kw)
        assert got.shape == (t, heads, dv) and got.dtype == dtype
        # the plain loop multiplies a masked row's zero weight with its
        # values: it is the yardstick over pools whose unattended rows are
        # finite
        clean = [jnp.nan_to_num(p, nan=0.0, posinf=0.0) for p in pools]
        for impl in ("xla", "auto"):        # off the TPU "auto" is the loop
            want = attention.paged_window_chunk_attention(
                *args, *clean, jnp.asarray(row), impl=impl, **kw)
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(want, np.float32),
                atol=tol, rtol=0)
        if dtype != jnp.float32:
            return
        g = heads // kv_heads
        for i in range(t):
            pos = start + i
            at = np.arange(0 if window is None
                           else max(pos - window + 1, 0), pos + 1)
            at = row[at // bs] * bs + at % bs
            sc = np.einsum("hgd,khd->hgk", q[i].reshape(kv_heads, g, d),
                           k[1, at]) * d ** -0.5
            if bias is not None:
                sc = np.concatenate(
                    [sc, bias.reshape(kv_heads, g, 1)], axis=-1)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            p = (p / p.sum(-1, keepdims=True))[..., :len(at)]
            dense = np.einsum("hgk,khd->hgd", p, v[1, at])
            np.testing.assert_allclose(
                got[i], dense.reshape(heads, dv), atol=tol, rtol=0)

    return check


@pytest.fixture()
def check_paged_walk():
    """``check(lens=, cols=, heads=, kv_heads=, d=, ...)``: one query a slot
    through the kernel ``paged_attn``
    (``ops.attention.paged_window_decode_attention``, interpreted) against
    each slot's dense sum over the rows ``[lo, len)`` it attends and, where the
    plain formulation takes the options, against ``impl="xla"``.  ``lens``
    holds None for the table's whole capacity; ``lo`` (a slot's first row,
    the tumbling ring's way) or ``window`` (the last ``window`` rows) says
    where a walk starts; ``shared`` pairs of slots (a, b), b taking a's table
    row.  Tables of ``cols`` columns are scattered; every block no slot
    attends — the rest of the pool, and the scratch block that unmapped and
    freed columns name — holds NaN and inf.  The slots are padded to
    ``slots`` with slots that attend nothing and the pool holds ``nb`` blocks
    whatever the case needs, so that the cases of a test share one lowering
    of the interpreted kernel (11-15 s each).  Returns
    ``(out, lse)``."""
    import jax.numpy as jnp
    import numpy as np

    from distributedtensorflow_tpu.ops import attention

    def check(*, lens, cols, heads, kv_heads, d, dv=None, window=None,
              lo=None, sink=False, with_lse=False, shared=(), bs=16,
              interpret=True, tol=2e-5, seed=0, slots=8, nb=448):
        dv = dv or d
        rng = np.random.default_rng(seed + len(lens))
        pad = [0] * (slots - len(lens))
        lens = np.asarray([cols * bs if n is None else n for n in lens] + pad)
        lo = None if lo is None else list(lo) + pad
        first = (np.asarray(lo) if lo is not None else np.zeros_like(lens)
                 if window is None else np.maximum(lens - window, 0))
        held = [range(a // bs, -(-n // bs)) for a, n in zip(first, lens)]
        assert sum(len(r) for r in held) <= nb
        k = rng.standard_normal((2, (nb + 1) * bs, kv_heads, d))
        v = rng.standard_normal((2, (nb + 1) * bs, kv_heads, dv))
        tables = np.full((len(lens), cols), nb, np.int32)
        perm, o = rng.permutation(nb), 0
        for i, r in enumerate(held):
            tables[i, r.start:r.stop] = perm[o:o + len(r)]
            o += len(r)
        for a, b in shared:
            tables[b] = tables[a]
        poison = np.ones(nb + 1, bool)
        for i, r in enumerate(held):
            poison[tables[i, r.start:r.stop]] = False
        bad = np.where(np.arange(d) % 2, np.nan, np.inf)
        k[:, np.repeat(poison, bs)] = bad
        v[:, np.repeat(poison, bs)] = bad[:dv]
        q = rng.standard_normal((len(lens), heads, d))
        bias = rng.standard_normal(heads) + 2.0 if sink else None
        pools = (jax.vmap(attention.lay_heads)(jnp.asarray(k, jnp.float32)),
                 jnp.asarray(v.reshape(2, -1, kv_heads * dv), jnp.float32))
        args = (jnp.asarray(q, jnp.float32), *pools, jnp.asarray(tables),
                jnp.asarray(lens, jnp.int32))
        kw = dict(layer=1, block_size=bs, window=window,
                  sink=None if bias is None else jnp.asarray(
                      bias, jnp.float32))
        assert attention.paged_decode_formulation(
            heads, kv_heads, d, bs, "pallas", dv) == "paged_attn"
        got = attention.paged_window_decode_attention(
            *args, impl="pallas", interpret=interpret, with_lse=with_lse,
            lo=None if lo is None else jnp.asarray(lo, jnp.int32), **kw)
        got, lse = (np.asarray(x) for x in got) if with_lse else (
            np.asarray(got), None)
        assert got.shape == (len(lens), heads, dv)
        assert not np.isnan(got).any()
        g = heads // kv_heads
        for i, (a, n) in enumerate(zip(first, lens)):
            if n <= a:
                # a walk over no key: zeros, the denominator's log under
                # every real one
                np.testing.assert_array_equal(got[i], 0.0)
                assert lse is None or (lse[i] < attention.NEG_INF).all()
                continue
            at = np.arange(a, n)
            at = tables[i, at // bs] * bs + at % bs
            sc = np.einsum("hgd,khd->hgk", q[i].reshape(kv_heads, g, d),
                           k[1, at]) * d ** -0.5
            if bias is not None:
                sc = np.concatenate(
                    [sc, bias.reshape(kv_heads, g, 1)], axis=-1)
            m = sc.max(-1, keepdims=True)
            p = np.exp(sc - m)
            total = p.sum(-1, keepdims=True)
            dense = np.einsum("hgk,khd->hgd", (p / total)[..., :len(at)],
                              v[1, at])
            np.testing.assert_allclose(
                got[i], dense.reshape(heads, dv), atol=tol, rtol=0)
            if lse is not None:
                np.testing.assert_allclose(
                    lse[i], (m + np.log(total)).reshape(heads), atol=tol,
                    rtol=0)
        if lo is None and not with_lse:
            # the gather reads every column: give it finite rows
            clean = [jnp.nan_to_num(p, nan=0.0, posinf=0.0) for p in pools]
            live = lens > 0
            want = np.asarray(attention.paged_window_decode_attention(
                args[0], *clean, *args[3:], impl="xla", **kw))
            np.testing.assert_allclose(got[live], want[live], atol=tol,
                                       rtol=0)
        return got, lse

    return check
