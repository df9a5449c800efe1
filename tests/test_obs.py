"""obs/ telemetry subsystem tests: registry, spans, aggregation, anomaly
detection, and the Trainer integration (breakdown fields, trace.jsonl,
anomaly callback path, Prometheus snapshot).

Reference model: ISSUE 1 — the unified telemetry layer over the reference
harness's tf.summary-only floor.
"""

import json
import math
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributedtensorflow_tpu import obs
from distributedtensorflow_tpu.obs.registry import Registry
from distributedtensorflow_tpu.obs.tracing import TraceRecorder
from distributedtensorflow_tpu.train.trainer import (
    Callback,
    Trainer,
    TrainerConfig,
)


# --- registry ---------------------------------------------------------------


def test_registry_counter_gauge_histogram():
    reg = Registry()
    c = reg.counter("requests_total", "help text")
    c.inc()
    c.inc(2, kind="a")
    assert c.value() == 1
    assert c.value(kind="a") == 2
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(4)
    g.add(1)
    assert g.value() == 5
    h = reg.histogram("latency_seconds")
    h.observe(0.004)
    h.observe(2.0)
    assert h.stats()["count"] == 2
    assert h.stats()["sum"] == pytest.approx(2.004)


def test_registry_type_conflict_raises():
    reg = Registry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_registry_scalars_flat_names():
    reg = Registry()
    reg.counter("c").inc(3, kind="train_step")
    reg.gauge("g").set(1.5)
    reg.histogram("h").observe(0.1)
    s = reg.scalars()
    assert s["c.kind_train_step"] == 3.0
    assert s["g"] == 1.5
    assert s["h_count"] == 1.0
    assert s["h_sum"] == pytest.approx(0.1)
    # jsonl/TB-safe: no braces or quotes in any exported field name
    assert all(ch not in k for k in s for ch in '{}"')


def test_registry_prometheus_text(tmp_path):
    reg = Registry()
    reg.counter("events_total", "things that happened").inc(5)
    reg.histogram("wait_seconds", buckets=(0.1, 1.0)).observe(0.5)
    text = reg.to_prometheus()
    assert "# TYPE events_total counter" in text
    assert "events_total 5.0" in text
    assert '# TYPE wait_seconds histogram' in text
    assert 'wait_seconds_bucket{le="0.1"} 0' in text
    assert 'wait_seconds_bucket{le="1.0"} 1' in text
    assert 'wait_seconds_bucket{le="+Inf"} 1' in text
    assert "wait_seconds_count 1" in text
    path = tmp_path / "metrics.prom"
    reg.write_prometheus(str(path))
    assert "events_total 5.0" in path.read_text()
    assert not list(tmp_path.glob("*.tmp.*"))  # atomic: no temp leftovers


def test_histogram_quantile_interpolation():
    reg = Registry()
    h = reg.histogram("lat", buckets=(0.1, 0.5, 1.0))
    import math

    assert math.isnan(h.quantile(0.5))  # no observations
    for _ in range(50):
        h.observe(0.05)  # first bucket (0, 0.1]
    for _ in range(50):
        h.observe(0.3)  # second bucket (0.1, 0.5]
    # p50 sits at the first/second bucket boundary; within-bucket linear
    # interpolation puts it at the top of bucket one
    assert h.quantile(0.5) == pytest.approx(0.1)
    assert 0.1 < h.quantile(0.95) <= 0.5
    assert h.quantile(1.0) == pytest.approx(0.5)
    # observations past the last finite bound clamp to it (PromQL +Inf rule)
    h.observe(100.0)
    assert h.quantile(0.999) == pytest.approx(1.0)
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_histogram_quantile_respects_labels():
    reg = Registry()
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    h.observe(0.05, kind="fast")
    h.observe(0.9, kind="slow")
    assert h.quantile(0.5, kind="fast") <= 0.1
    assert h.quantile(0.5, kind="slow") > 0.1


def test_prometheus_snapshot_carries_summary_quantiles():
    reg = Registry()
    h = reg.histogram("wait_seconds", buckets=(0.1, 1.0))
    for _ in range(90):
        h.observe(0.05)
    for _ in range(10):
        h.observe(0.9)
    text = reg.to_prometheus()
    # summary-style estimates ride alongside the buckets as a SIBLING
    # gauge family (quantile samples inside the histogram family itself
    # would be invalid exposition format)
    assert "# TYPE wait_seconds_quantile gauge" in text
    assert 'wait_seconds_quantile{quantile="0.5"}' in text
    assert 'wait_seconds_quantile{quantile="0.95"}' in text
    assert 'wait_seconds_quantile{quantile="0.99"}' in text
    p50 = next(
        float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
        if line.startswith('wait_seconds_quantile{quantile="0.5"}')
    )
    assert p50 <= 0.1
    p99 = next(
        float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
        if line.startswith('wait_seconds_quantile{quantile="0.99"}')
    )
    assert p99 > 0.1
    # every histogram sample stays inside its own family: the _bucket /
    # _sum / _count block is contiguous (strict-parser requirement)
    lines = text.splitlines()
    hist_idx = [i for i, line in enumerate(lines)
                if line.startswith(("wait_seconds_bucket",
                                    "wait_seconds_sum",
                                    "wait_seconds_count"))]
    assert hist_idx == list(range(hist_idx[0], hist_idx[-1] + 1))


def test_registry_thread_safety():
    reg = Registry()
    c = reg.counter("n")

    def work():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == 4000


# --- span tracing -----------------------------------------------------------


def test_span_nesting_builds_tree():
    rec = TraceRecorder()  # accounting-only, no file
    with rec:
        with obs.span("outer") as s:
            with obs.span("inner"):
                pass
    assert s.name == "outer"
    assert [c.name for c in s.children] == ["inner"]
    totals = rec.drain_window()
    assert "outer" in totals and "inner" not in totals  # roots only


def test_span_is_exception_transparent():
    # the fit loop depends on StopIteration escaping a span unchanged
    with pytest.raises(StopIteration):
        with obs.span("data_wait"):
            raise StopIteration
    with pytest.raises(KeyError):
        with obs.span("x"):
            raise KeyError("k")


def test_span_enters_a_profiler_annotation(monkeypatch):
    """Every span is mirrored into the profiler under its own name with
    its keyword attributes (the profiler itself is stubbed: no real trace
    in tier-1), nested in span order, and stays exception-transparent."""
    from distributedtensorflow_tpu.obs import tracing

    events = []

    class FakeAnnotation:
        def __init__(self, name, **attrs):
            self.name, self.attrs = name, attrs

        def __enter__(self):
            events.append(("enter", self.name, self.attrs))

        def __exit__(self, exc_type, exc, tb):
            events.append(("exit", self.name, exc_type))

    monkeypatch.setattr(tracing, "_TraceAnnotation", FakeAnnotation)
    with obs.span("engine.step", step=7) as root:
        with obs.span("engine.decode"):
            pass
    assert events == [
        ("enter", "engine.step", {"step": 7}),
        ("enter", "engine.decode", {}),
        ("exit", "engine.decode", None),
        ("exit", "engine.step", None),
    ]
    assert [c.name for c in root.children] == ["engine.decode"]
    del events[:]
    with pytest.raises(StopIteration):
        with obs.span("data_wait"):
            raise StopIteration
    assert events[-1] == ("exit", "data_wait", StopIteration)


def test_span_annotation_is_the_profilers_own():
    # unpatched: the class a span enters is jax.profiler.TraceAnnotation
    import jax.profiler

    from distributedtensorflow_tpu.obs import tracing

    with obs.span("x", step=1):
        pass
    assert tracing._TraceAnnotation is jax.profiler.TraceAnnotation


def test_trace_recorder_without_step_rows_buffers_nothing(tmp_path):
    rec = TraceRecorder(str(tmp_path / "trace.jsonl"), step_rows=False)
    with rec:
        for _ in range(50):
            with obs.span("engine.step"):
                pass
        assert rec._roots == []
        assert rec.drain_window()["engine.step"] > 0
    assert (tmp_path / "trace.jsonl").read_text() == ""


def _rows(path):
    return [json.loads(line) for line in path.read_text().splitlines()]


def test_phase_trace_rows_tile_time_under_one_trace_id(tmp_path):
    """Start-up phases: marks made before a recorder exists wait for it,
    every row is a kind:"span" row under the one trace_id, top-level rows
    follow one another with nothing between, children point at their
    open parent, and the schema checker takes the file."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(__file__), "..", "tools"))
    import check_metrics_schema

    path = tmp_path / "trace.jsonl"
    t0 = time.time() - 5.0
    phases = obs.PhaseTrace("startup", t0)
    phases.mark("startup.imports")          # no recorder yet: waits
    phases.mark("startup.backend")
    assert not path.exists()
    with TraceRecorder(str(path)):
        phases.mark("startup.workload", workload="w")
        phases.open("startup.first_step")
        phases.mark("startup.first_batch", parent="startup.first_step")
        phases.mark("startup.compile_or_load", parent="startup.first_step")
        phases.close("startup.first_step", step=1)
    rows = _rows(path)
    assert [r["name"] for r in rows] == [
        "startup.imports", "startup.backend", "startup.workload",
        "startup.first_batch", "startup.compile_or_load",
        "startup.first_step"]
    assert {r["kind"] for r in rows} == {"span"}
    assert {r["trace_id"] for r in rows} == {"startup"}
    assert rows[0]["t0"] == pytest.approx(t0) and rows[0]["dur_s"] >= 5.0
    top = [r for r in rows if "parent_id" not in r]
    for a, b in zip(top, top[1:]):
        assert b["t0"] == pytest.approx(a["t0"] + a["dur_s"], abs=2e-6)
    first_step = rows[-1]
    kids = [r for r in rows if "parent_id" in r]
    assert {r["parent_id"] for r in kids} == {first_step["span_id"]}
    assert kids[0]["t0"] == pytest.approx(first_step["t0"], abs=2e-6)
    assert kids[1]["t0"] + kids[1]["dur_s"] <= \
        first_step["t0"] + first_step["dur_s"] + 2e-6
    assert rows[2]["workload"] == "w" and first_step["step"] == 1
    assert check_metrics_schema.check_file(str(path)) == ([], [])
    # an overlap or a foreign trace_id is an error
    bad = tmp_path / "trace_bad.jsonl"
    rows[1]["t0"] -= 1.0
    rows[2]["trace_id"] = "other"
    bad.write_text("".join(json.dumps(r) + "\n" for r in rows))
    errors, _ = check_metrics_schema.check_file(str(bad))
    assert any("before the previous start-up phase" in e for e in errors)
    assert any("not 'startup'" in e for e in errors)


def test_trace_recorder_writes_step_rows(tmp_path):
    path = tmp_path / "trace.jsonl"
    rec = TraceRecorder(str(path))
    with rec:
        for step in (1, 2):
            rec.begin_step(step)
            with obs.span("train_step"):
                pass
            rec.end_step()
        rec.write_event({"kind": "anomaly", "step": 2, "anomaly": "x"})
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    steps = [r["step"] for r in rows if "t_wall" in r]
    assert steps == [1, 2]
    assert all(
        r["spans"][0]["name"] == "train_step" for r in rows if "t_wall" in r
    )
    assert any(r.get("kind") == "anomaly" for r in rows)


def test_trace_recorder_window_totals(tmp_path):
    rec = TraceRecorder()
    with rec:
        rec.begin_step(1)
        with obs.span("a"):
            pass
        with obs.span("a"):
            pass
        with obs.span("b"):
            pass
        totals = rec.drain_window()
        assert totals["a"] > 0 and totals["b"] > 0
        assert rec.drain_window() == {}  # drained


def test_spans_dropped_without_recorder():
    # no recorder installed: spans still time, nothing accumulates anywhere
    with obs.span("orphan"):
        pass
    assert obs.active_recorder() is None


# --- cross-host aggregation -------------------------------------------------


def test_host_aggregate_single_process():
    agg = obs.host_aggregate({"t_step": 0.25, "t_data": 0.01})
    assert agg["t_step_host_min"] == 0.25
    assert agg["t_step_host_median"] == 0.25
    assert agg["t_step_host_max"] == 0.25
    assert agg["t_step_straggler"] == 0.0
    assert "straggler host 0" in obs.straggler_summary(agg, "t_step")
    assert obs.host_aggregate({}) == {}


# --- anomaly detection ------------------------------------------------------


def test_anomaly_nan_loss_fires_callback():
    fired = []
    det = obs.AnomalyDetector(on_anomaly=fired.append)
    found = det.observe(7, loss=float("nan"))
    assert [a.kind for a in found] == ["non_finite_loss"]
    assert fired and fired[0].step == 7
    found = det.observe(8, loss=float("inf"))
    assert found[0].kind == "non_finite_loss"


def test_anomaly_loss_spike_zscore():
    fired = []
    det = obs.AnomalyDetector(on_anomaly=fired.append, min_history=8)
    rng = np.random.default_rng(0)
    for i in range(20):
        assert det.observe(i, loss=1.0 + 0.01 * rng.standard_normal()) == []
    found = det.observe(20, loss=100.0)
    assert [a.kind for a in found] == ["loss_spike"]
    assert fired[-1].kind == "loss_spike"


def test_anomaly_step_time_regression():
    fired = []
    det = obs.AnomalyDetector(
        on_anomaly=fired.append, min_history=8, warmup=1
    )
    # warmup observation (the compile window) is skipped
    assert det.observe(0, step_time=10.0) == []
    for i in range(1, 10):
        assert det.observe(i, step_time=0.1) == []
    found = det.observe(10, step_time=0.5)  # > 3x the 0.1 trailing median
    assert [a.kind for a in found] == ["step_time_regression"]
    assert fired[-1].value == 0.5


def test_anomaly_steady_stream_is_quiet():
    det = obs.AnomalyDetector()
    for i in range(50):
        assert det.observe(i, loss=2.0 - i * 0.01, step_time=0.1) == []
    assert det.anomalies == []


def test_anomaly_callback_errors_are_swallowed():
    def bad(a):
        raise RuntimeError("alerting down")

    det = obs.AnomalyDetector(on_anomaly=bad)
    found = det.observe(1, loss=float("nan"))  # must not raise
    assert len(found) == 1


# --- MFU helpers ------------------------------------------------------------


def test_mfu_record_fields():
    fields = obs.mfu_record_fields(1e12, 0.1, device_kind="TPU v5 lite")
    # 1e12 FLOPs / 0.1 s / 197e12 peak ≈ 0.0508
    assert fields["mfu"] == pytest.approx(0.0508, abs=1e-3)
    assert fields["mfu_analytic"] == fields["mfu"]
    assert all(isinstance(v, float) for v in fields.values())
    assert obs.mfu_record_fields(0.0, 0.1) == {}
    assert obs.mfu_record_fields(1e12, 0.0) == {}


def test_estimate_step_flops():
    from distributedtensorflow_tpu.train import estimate_step_flops

    step = jax.jit(
        lambda s, b, r: (s + jnp.sum(b["x"] @ b["x"]), {"loss": s})
    )
    flops = estimate_step_flops(
        step,
        jnp.float32(0.0),
        {"x": jax.ShapeDtypeStruct((16, 16), np.float32)},
        jax.random.PRNGKey(0),
    )
    assert flops is None or flops > 0  # None only if the backend can't say
    if flops is not None:
        assert flops >= 2 * 16 * 16 * 16 * 0.5  # at least the matmul's MACs


# --- Trainer integration ----------------------------------------------------


class _State:
    step = 0


def _fake_batches(n, batch=4):
    for _ in range(n):
        yield {"x": np.zeros((batch, 2), np.float32)}


def test_trainer_writes_breakdown_and_trace(tmp_path):
    logdir = tmp_path / "logs"

    def train_step(state, batch, rng):
        return state, {"loss": 1.0}

    cfg = TrainerConfig(
        total_steps=4, log_every=2, global_batch_size=4,
        logdir=str(logdir), flops_per_step=1e9,
    )
    with Trainer(train_step, cfg) as trainer:
        trainer.fit(_State(), _fake_batches(4), rng=None)
    rows = [
        json.loads(line)
        for line in (logdir / "metrics.jsonl").read_text().splitlines()
    ]
    assert [r["step"] for r in rows] == [2, 4]
    for r in rows:
        # the acceptance fields: the step-time breakdown
        for key in ("t_step", "t_data", "t_dispatch", "t_host",
                    "f_data", "f_dispatch"):
            assert key in r, f"missing {key} in {sorted(r)}"
        # flops_per_step is set, but this is a CPU: no published peak,
        # so no utilization field at all (never one against a chip's peak)
        assert not [k for k in r if k.startswith("mfu")], sorted(r)
        assert r["t_step"] > 0
        assert 0 <= r["f_dispatch"] <= 1.5  # fraction, with timer slack
    trace_rows = [
        json.loads(line)
        for line in (logdir / "trace.jsonl").read_text().splitlines()
    ]
    step_rows = [r for r in trace_rows if "t_wall" in r]
    assert [r["step"] for r in step_rows] == [1, 2, 3, 4]
    names = {s["name"] for r in step_rows for s in r["spans"]}
    assert {"data_wait", "train_step", "host_block"} <= names
    assert (logdir / "metrics.prom").exists()
    # writer closed by the context manager; late writes are dropped
    trainer.writer.write(99, {"loss": 0.0})
    assert all(
        json.loads(line)["step"] != 99
        for line in (logdir / "metrics.jsonl").read_text().splitlines()
    )


def test_trainer_nan_loss_raises_anomaly_through_callbacks(tmp_path):
    logdir = tmp_path / "logs"
    seen = []

    class Watcher(Callback):
        def on_anomaly(self, trainer, anomaly):
            seen.append(anomaly)

    def train_step(state, batch, rng):
        return state, {"loss": float("nan")}

    cfg = TrainerConfig(
        total_steps=2, log_every=1, global_batch_size=4, logdir=str(logdir),
    )
    with Trainer(train_step, cfg, callbacks=[Watcher()]) as trainer:
        trainer.fit(_State(), _fake_batches(2), rng=None)
    assert seen, "NaN loss never reached Callback.on_anomaly"
    assert seen[0].kind == "non_finite_loss"
    assert trainer.anomaly_detector.anomalies
    # the live detector also records the event into trace.jsonl
    trace = (logdir / "trace.jsonl").read_text()
    assert '"anomaly": "non_finite_loss"' in trace
    # and counts into the registry
    assert obs.counter("anomalies_total").value(kind="non_finite_loss") >= 1


def test_trainer_anomaly_detection_can_be_disabled(tmp_path):
    def train_step(state, batch, rng):
        return state, {"loss": float("nan")}

    cfg = TrainerConfig(
        total_steps=1, log_every=1, global_batch_size=4,
        logdir=str(tmp_path / "logs"), anomaly_detection=False,
    )
    with Trainer(train_step, cfg) as trainer:
        trainer.fit(_State(), _fake_batches(1), rng=None)
    assert trainer.anomaly_detector is None


def test_trainer_real_model_end_to_end(tmp_path, dp_mesh):
    """One real compiled-step fit: engine dispatch counters and breakdown
    fields land in the record (the CPU acceptance-path shape)."""
    from distributedtensorflow_tpu.models import LeNet5
    from distributedtensorflow_tpu.train import (
        create_sharded_state,
        make_train_step,
    )
    from distributedtensorflow_tpu.train.losses import classification_loss

    model = LeNet5()
    state, specs = create_sharded_state(
        lambda r: model.init(r, jnp.zeros((1, 28, 28, 1))),
        optax.sgd(0.05), dp_mesh, jax.random.PRNGKey(0),
    )
    train_step = make_train_step(
        classification_loss(model), dp_mesh, specs, donate=False
    )
    assert hasattr(train_step, "lower")  # the bench AOT contract survives

    def batches(n):
        rng = np.random.default_rng(0)
        for _ in range(n):
            yield {
                "image": rng.standard_normal((16, 28, 28, 1)).astype(
                    np.float32
                ),
                "label": rng.integers(0, 10, (16,)).astype(np.int32),
            }

    logdir = tmp_path / "logs"
    cfg = TrainerConfig(
        total_steps=2, log_every=2, global_batch_size=16, logdir=str(logdir),
    )
    with Trainer(train_step, cfg) as trainer:
        trainer.fit(state, batches(2), jax.random.PRNGKey(1))
    [row] = [
        json.loads(line)
        for line in (logdir / "metrics.jsonl").read_text().splitlines()
    ]
    assert row["step"] == 2
    assert math.isfinite(row["loss"])
    assert row["t_dispatch"] > 0
    assert row["engine_dispatches_total.kind_train_step"] >= 2
    # the first dispatch's tracing and compilation: the compile log's
    # roots, by phase and by program (ISSUE 50; they took the place of
    # the gauge engine_first_dispatch_s), and the row that held them
    assert row["jit_compile_seconds_total.phase_trace"] > 0
    assert row["jit_compile_seconds_total.phase_backend"] > 0
    assert row["jit_compiles_total.cache_off.program_jit_step_"] >= 1
    assert row["compile_s"] > 0 and "step" in row["compiled"].split(",")
