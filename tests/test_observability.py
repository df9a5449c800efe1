"""Observability tests: profiler traces, watchdog, determinism helpers.

Reference model: SURVEY.md §5.1 (profiler), §5.2 (watchdog/op-determinism).
"""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedtensorflow_tpu.utils import (
    Watchdog,
    annotate,
    derive_seed,
    dump_all_stacks,
    named_scope,
    trace,
    tree_fingerprint,
)


# --- profiler ---------------------------------------------------------------


def test_trace_writes_profile(tmp_path):
    logdir = str(tmp_path / "prof")
    with trace(logdir):
        with annotate("host-region"):
            with named_scope("dev-region"):
                x = jnp.ones((32, 32))
                y = jax.jit(lambda a: a @ a)(x)
        float(y.sum())
    # XPlane output lands under plugins/profile/<run>/...
    found = []
    for root, _dirs, files in os.walk(logdir):
        found.extend(os.path.join(root, f) for f in files)
    assert found, f"no profile artifacts written under {logdir}"


def test_named_scope_in_hlo():
    def f(x):
        with named_scope("my_marker_scope"):
            return x * 2 + 1

    lowered = jax.jit(f).lower(jnp.ones((4,)))
    hlo = lowered.as_text(debug_info=True)
    assert "my_marker_scope" in hlo


# --- watchdog ---------------------------------------------------------------


def test_watchdog_fires_on_stall(capfd):
    fired = threading.Event()
    wd = Watchdog(timeout=0.3, on_timeout=fired.set, poll_interval=0.05)
    try:
        assert fired.wait(timeout=5.0), "watchdog never fired"
        assert wd.fired
        err = capfd.readouterr().err
        assert "--- thread" in err  # stack dump happened
    finally:
        wd.stop()


def test_watchdog_ping_prevents_firing():
    fired = threading.Event()
    wd = Watchdog(timeout=0.5, on_timeout=fired.set, poll_interval=0.05)
    try:
        for _ in range(6):
            time.sleep(0.15)
            wd.ping()
        assert not wd.fired
        assert not fired.is_set()
    finally:
        wd.stop()


def test_watchdog_rearms_after_ping(capfd):
    count = []
    wd = Watchdog(timeout=0.2, on_timeout=lambda: count.append(1),
                  poll_interval=0.05)
    try:
        deadline = time.monotonic() + 5.0
        while not count and time.monotonic() < deadline:
            time.sleep(0.05)
        assert count, "first firing missed"
        wd.ping()  # re-arm
        assert not wd.fired
        while len(count) < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert len(count) >= 2, "watchdog did not re-fire after re-arm"
    finally:
        wd.stop()


def test_dump_all_stacks_includes_this_frame(capfd):
    text = dump_all_stacks()
    assert "test_dump_all_stacks_includes_this_frame" in text


def test_watchdog_context_manager_stops_thread():
    """`with Watchdog(...)` must arm on entry and stop its poll thread on
    exit — the previously-untested context-manager path."""
    with Watchdog(timeout=30.0, poll_interval=0.05) as wd:
        assert wd is not None
        assert wd._thread.is_alive()
        wd.ping()
        assert not wd.fired
    assert not wd._thread.is_alive()


def test_watchdog_context_manager_stops_on_exception():
    with pytest.raises(RuntimeError):
        with Watchdog(timeout=30.0, poll_interval=0.05) as wd:
            raise RuntimeError("body failed")
    assert not wd._thread.is_alive()


def test_watchdog_exports_registry_metrics():
    from distributedtensorflow_tpu import obs

    before = obs.counter("watchdog_timeouts_total").value()
    fired = threading.Event()
    wd = Watchdog(timeout=0.2, on_timeout=fired.set, poll_interval=0.05)
    try:
        assert fired.wait(timeout=5.0)
        deadline = time.monotonic() + 5.0
        while (obs.counter("watchdog_timeouts_total").value() < before + 1
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert obs.counter("watchdog_timeouts_total").value() >= before + 1
        # the poll loop keeps the ping-age gauge fresh; the stall is visible
        assert obs.gauge("watchdog_ping_age_seconds").value() >= 0.2
        assert wd.ping_age() >= 0.2
        wd.ping()
        assert wd.ping_age() < 0.2
    finally:
        wd.stop()


# --- determinism ------------------------------------------------------------


def test_derive_seed_stable_and_distinct():
    a = derive_seed(42, "shuffle", 0)
    assert a == derive_seed(42, "shuffle", 0)
    assert a != derive_seed(42, "shuffle", 1)
    assert a != derive_seed(42, "dropout", 0)
    assert a != derive_seed(43, "shuffle", 0)
    assert 0 <= a < 2**31


def test_tree_fingerprint_detects_changes():
    t1 = {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.zeros((3,))}
    t2 = {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.zeros((3,))}
    assert tree_fingerprint(t1) == tree_fingerprint(t2)
    t3 = {"w": t1["w"].at[0, 0].set(1e-7), "b": t1["b"]}
    assert tree_fingerprint(t1) != tree_fingerprint(t3)
    # structure matters, not just values
    t4 = {"w2": t1["w"], "b": t1["b"]}
    assert tree_fingerprint(t1) != tree_fingerprint(t4)


def test_tree_fingerprint_shape_dtype_sensitivity():
    a = {"x": np.zeros((4,), np.float32)}
    b = {"x": np.zeros((2, 2), np.float32)}
    c = {"x": np.zeros((4,), np.float64)}
    assert tree_fingerprint(a) != tree_fingerprint(b)
    assert tree_fingerprint(a) != tree_fingerprint(c)


def test_same_seed_same_bits_across_shardings(dp_mesh):
    """threefry_partitionable: key bits independent of sharding layout."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    prior = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    try:
        key = jax.random.PRNGKey(7)
        full = jax.random.uniform(key, (8, 16))
        sharded_input = jax.device_put(
            jnp.zeros((8, 16)), NamedSharding(dp_mesh, P("data"))
        )

        @jax.jit
        def gen(z):
            return jax.random.uniform(key, z.shape) + z * 0

        sharded = gen(sharded_input)
        np.testing.assert_allclose(
            np.asarray(full), np.asarray(jax.device_get(sharded)), rtol=0, atol=0
        )
    finally:
        jax.config.update("jax_threefry_partitionable", prior)
