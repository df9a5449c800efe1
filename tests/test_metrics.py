"""Direct unit tests for utils/metrics.py: MetricWriter + ThroughputMeter.

These previously had only incidental coverage via test_trainer/test_sidecar;
the lifecycle contract (context manager, idempotent close, chief-only
gating, TF-absent fallback) is load-bearing for every metrics.jsonl
producer, so it gets its own surface.
"""

import json
import sys
import time

import jax
import pytest

from distributedtensorflow_tpu.utils.metrics import MetricWriter, ThroughputMeter


def _rows(path, keep_t=False):
    """The file's rows; every `write` row carries `t`, the unix time it
    was written (ISSUE 24) — checked here, dropped unless asked for."""
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    for row in rows:
        if "step" in row and not keep_t:
            assert abs(row.pop("t") - time.time()) < 60
    return rows


def test_writer_jsonl_schema(tmp_path):
    with MetricWriter(str(tmp_path), use_tensorboard=False) as w:
        w.write(10, {"loss": 1.5, "accuracy": 0.25})
        w.write(20, {"loss": 1.0})
    rows = _rows(tmp_path / "metrics.jsonl")
    assert rows == [
        {"step": 10, "loss": 1.5, "accuracy": 0.25},
        {"step": 20, "loss": 1.0},
    ]
    # rows are stamped at their write, in order (jsonl only, not a scalar)
    stamps = [r["t"] for r in _rows(tmp_path / "metrics.jsonl", keep_t=True)]
    assert stamps == sorted(stamps) and all(
        isinstance(t, float) for t in stamps)
    # every value a number, step an int — the check_metrics_schema contract
    for row in rows:
        assert isinstance(row["step"], int)
        assert all(isinstance(v, (int, float)) for v in row.values())


def test_writer_encodes_non_finite_as_strict_json(tmp_path):
    with MetricWriter(str(tmp_path), use_tensorboard=False) as w:
        w.write(3, {"loss": float("nan"), "grad_norm": float("inf")})
    [line] = (tmp_path / "metrics.jsonl").read_text().splitlines()
    # strict parsers must accept the line (no bare NaN/Infinity tokens)
    row = json.loads(line, parse_constant=lambda c: pytest.fail(
        f"bare {c} token in jsonl"
    ))
    assert row.pop("t") > 0
    assert row == {"step": 3, "loss": "NaN", "grad_norm": "Infinity"}


def test_writer_skips_none_values(tmp_path):
    with MetricWriter(str(tmp_path), use_tensorboard=False) as w:
        w.write(1, {"loss": 2.0, "mfu_xla_cost": None})
    assert _rows(tmp_path / "metrics.jsonl") == [{"step": 1, "loss": 2.0}]


def test_writer_chief_only_gating(tmp_path, monkeypatch):
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    w = MetricWriter(str(tmp_path), use_tensorboard=False)
    w.write(1, {"loss": 1.0})
    w.write_record({"free": 1})
    w.close()
    assert not (tmp_path / "metrics.jsonl").exists()


def test_writer_tf_absent_falls_back_to_jsonl(tmp_path, monkeypatch):
    # a poisoned tensorflow module makes `import tensorflow` raise
    monkeypatch.setitem(sys.modules, "tensorflow", None)
    w = MetricWriter(str(tmp_path), use_tensorboard=True)
    assert w._tb is None
    w.write(5, {"loss": 0.5})
    w.close()
    assert _rows(tmp_path / "metrics.jsonl") == [{"step": 5, "loss": 0.5}]


def test_writer_close_idempotent_and_drops_late_writes(tmp_path):
    w = MetricWriter(str(tmp_path), use_tensorboard=False)
    w.write(1, {"loss": 1.0})
    w.close()
    w.close()  # second close: no error
    w.write(2, {"loss": 2.0})  # dropped, not ValueError on a closed file
    w.write_record({"x": 1})
    assert len(_rows(tmp_path / "metrics.jsonl")) == 1


def test_writer_context_manager_closes_on_error(tmp_path):
    with pytest.raises(RuntimeError):
        with MetricWriter(str(tmp_path), use_tensorboard=False) as w:
            w.write(1, {"loss": 1.0})
            raise RuntimeError("boom")
    assert w._closed
    assert len(_rows(tmp_path / "metrics.jsonl")) == 1


def test_writer_none_logdir_is_noop():
    w = MetricWriter(None)
    w.write(1, {"loss": 1.0})  # nothing to write to; must not raise
    w.close()


def test_write_record_free_form(tmp_path):
    with MetricWriter(str(tmp_path), use_tensorboard=False) as w:
        w.write_record({"time": 1.0, "staleness_hist": {"0": 3, "1": 1},
                        "final": True})
    [row] = _rows(tmp_path / "metrics.jsonl")
    assert row["staleness_hist"] == {"0": 3, "1": 1}
    assert row["final"] is True


def test_throughput_meter_rates(monkeypatch):
    import distributedtensorflow_tpu.utils.metrics as m

    clock = [100.0]
    monkeypatch.setattr(m.time, "perf_counter", lambda: clock[0])
    meter = ThroughputMeter(global_batch_size=64)
    assert meter.rates() == {}  # no steps yet
    meter.start()
    meter.update(4)
    clock[0] += 2.0
    rates = meter.rates()
    assert rates["steps_per_sec"] == pytest.approx(2.0)
    assert rates["examples_per_sec"] == pytest.approx(128.0)
    assert rates["examples_per_sec_per_chip"] == pytest.approx(
        128.0 / jax.device_count()
    )
    meter.start()  # reset
    assert meter.rates() == {}


def test_throughput_meter_update_autostarts(monkeypatch):
    import distributedtensorflow_tpu.utils.metrics as m

    clock = [10.0]
    monkeypatch.setattr(m.time, "perf_counter", lambda: clock[0])
    meter = ThroughputMeter(global_batch_size=8)
    meter.update()  # no explicit start()
    clock[0] += 1.0
    assert meter.rates()["steps_per_sec"] == pytest.approx(1.0)
