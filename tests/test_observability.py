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


# --- spans that tile their parent, and ids without a system call (ISSUE 36) --


def _tree(span):
    return (span.name, [_tree(c) for c in span.children])


def test_tiled_leaves_share_their_boundaries():
    """One clock read closes a leaf and opens the next: a leaf begins
    where its sibling ended, the first with its parents, the last ends
    with them."""
    from distributedtensorflow_tpu.obs import tracing

    with tracing.tiled("it", "admit", step=7) as t:
        assert t.root.name == "it" and t.root.t0 > 0
        chunk = t.to("prefill", "chunk")
        assert t.parent.name == "prefill"
        first = t.to("prefill", "first")
        chunk2 = t.to("prefill", "chunk")
        dispatch = t.to("decode", "decode.dispatch")
        fetch = t.to("decode", "decode.fetch")
        log = t.to("log")
    root = t.root
    assert _tree(root) == ("it", [
        ("admit", []),
        ("prefill", [("chunk", []), ("first", []), ("chunk", [])]),
        ("decode", [("decode.dispatch", []), ("decode.fetch", [])]),
        ("log", [])])
    admit, prefill, decode, _ = root.children
    assert admit.t0 == root.t0                      # no clock read between
    assert prefill.t0 == chunk.t0 == admit.t0 + admit.dur_s
    assert first.t0 == chunk.t0 + chunk.dur_s
    assert chunk2.t0 == first.t0 + first.dur_s
    assert decode.t0 == dispatch.t0 == prefill.t0 + prefill.dur_s
    assert fetch.t0 + fetch.dur_s == decode.t0 + decode.dur_s == log.t0
    assert log.t0 + log.dur_s == pytest.approx(root.t0 + root.dur_s, abs=1e-12)
    for parent in (root, prefill, decode):
        assert sum(c.dur_s for c in parent.children) == pytest.approx(
            parent.dur_s, abs=1e-9)


@pytest.mark.parametrize("case", ["sink", "recorder", "nested", "plain_inside",
                                  "exception", "clock_reads", "annotations"])
def test_tiled_is_a_span_to_everything_that_reads_spans(case, monkeypatch):
    from distributedtensorflow_tpu.obs import tracing

    def run():
        with tracing.tiled("root", "a", step=1) as t:
            t.to("b", "b1")
            t.to("b", "b2")
            t.to("c")
        return t.root

    want = ("root", [("a", []), ("b", [("b1", []), ("b2", [])]), ("c", [])])
    if case == "sink":
        got = []
        tracing.add_root_sink(got.append)
        try:
            root = run()
        finally:
            tracing.remove_root_sink(got.append)
        assert got == [root] and _tree(root) == want
    elif case == "recorder":
        with tracing.TraceRecorder() as rec:
            rec.begin_step(1)
            root = run()
            assert rec._roots == [root]
            assert rec.drain_window() == {"root": root.dur_s}
    elif case == "nested":
        with tracing.span("outer") as outer:
            root = run()
        assert _tree(outer) == ("outer", [want])
    elif case == "plain_inside":
        with tracing.tiled("root", "a") as t:
            with tracing.span("inner"):
                pass
            t.to("b")
        assert _tree(t.root) == ("root", [("a", [("inner", [])]), ("b", [])])
    elif case == "exception":
        with pytest.raises(StopIteration):
            with tracing.tiled("root", "a") as t:
                t.to("b", "b1")
                raise StopIteration
        assert _tree(t.root) == ("root", [("a", []), ("b", [("b1", [])])])
        assert tracing._tls.stack == []
        assert t.root.dur_s >= t.root.children[-1].dur_s > 0
    elif case == "clock_reads":
        reads = []
        clock = time.perf_counter
        monkeypatch.setattr(time, "perf_counter",
                            lambda: reads.append(1) or clock())
        run()
        monkeypatch.undo()
        assert len(reads) == 5      # enter, three `to`, exit
    else:
        entered = []

        class Ann:
            def __init__(self, name, **attrs):
                self.name, self.attrs = name, attrs

            def __enter__(self):
                entered.append(("enter", self.name, self.attrs))

            def __exit__(self, *exc):
                entered.append(("exit", self.name, self.attrs))

        monkeypatch.setattr(tracing, "_TraceAnnotation", Ann)
        run()
        assert [(e, n) for e, n, _ in entered] == [
            ("enter", "root"), ("enter", "a"),
            ("exit", "a"), ("enter", "b"), ("enter", "b1"),
            ("exit", "b1"), ("enter", "b2"),
            ("exit", "b2"), ("exit", "b"), ("enter", "c"),
            ("exit", "c"), ("exit", "root")]
        assert entered[0][2] == {"step": 1} and entered[1][2] == {}


def test_span_ids_are_unique_and_take_no_system_call(monkeypatch):
    from distributedtensorflow_tpu.obs import tracing

    monkeypatch.setattr(os, "urandom", None)    # a call would raise
    ids = {tracing.new_span_id() for _ in range(100_000)}
    ids |= {tracing.new_trace_id() for _ in range(1000)}
    assert len(ids) == 101_000
    assert all(len(i) == 16 and int(i, 16) >= 0 for i in ids)
    assert len({i[:8] for i in ids}) == 1       # the process's prefix
    import inspect

    assert "uuid" not in inspect.getsource(tracing).replace("uuid4", "")


@pytest.mark.parametrize("how", ["spawn", "fork"])
def test_two_processes_draw_different_id_prefixes(how):
    """A process draws its prefix when it first imports the tracer, a
    forked child again (``os.register_at_fork``).  The fork is made in a
    fresh interpreter that has not loaded JAX: a forked copy of this
    process, with JAX's threads, could deadlock."""
    import subprocess
    import sys

    script = {
        "spawn": "print(tracing.new_span_id())",
        "fork": ("import os\n"
                 "mine = tracing.new_span_id()\n"
                 "if os.fork() == 0:\n"
                 "    print(tracing.new_span_id(), flush=True)\n"
                 "    os._exit(0)\n"
                 "os.wait()\n"
                 "print(mine)"),
    }[how]
    out = subprocess.run(
        [sys.executable, "-c",
         "from distributedtensorflow_tpu.obs import tracing\n" + script],
        capture_output=True, text=True, check=True, timeout=120,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    ).stdout.split()
    ids = out + [tracing_id()]
    assert all(len(i) == 16 for i in ids) and len(ids) == len(out) + 1
    assert len({i[:8] for i in ids}) == len(ids)


def tracing_id():
    from distributedtensorflow_tpu.obs import tracing

    return tracing.new_span_id()
