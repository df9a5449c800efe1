"""Direct unit tests for utils/metrics.py: MetricWriter + ThroughputMeter.

These previously had only incidental coverage via test_trainer/test_sidecar;
the lifecycle contract (context manager, idempotent close, chief-only
gating, event files without TensorFlow) is load-bearing for every
metrics.jsonl producer, so it gets its own surface.
"""

import json
import os
import struct
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from distributedtensorflow_tpu.utils.metrics import (
    MetricWriter, ThroughputMeter, mask_crc)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


@pytest.fixture
def no_tensorflow(monkeypatch):
    """``import tensorflow`` raises: the writer must not need it, and
    TensorBoard's loader reads with its own record reader, which keeps
    TensorFlow's import (seconds) out of the tests."""
    monkeypatch.setitem(sys.modules, "tensorflow", None)


def event_file(logdir):
    [name] = [n for n in os.listdir(logdir)
              if n.startswith("events.out.tfevents.")]
    return os.path.join(logdir, name)


def loaded_scalars(path):
    """(step, tag, float32 value) of every scalar TensorBoard's own loader
    finds in the event file ``path``, in order."""
    loader = pytest.importorskip(
        "tensorboard.backend.event_processing.event_file_loader")
    from tensorboard.util import tensor_util

    events = list(loader.EventFileLoader(path).Load())
    assert events[0].file_version == "brain.Event:2"
    assert all(e.wall_time > 0 for e in events)
    out = []
    for event in events[1:]:
        assert event.HasField("summary")
        for value in event.summary.value:
            # what the scalar dashboard shows: a float32 scalar of the
            # `scalars` plugin (the loader gives a simple_value this form)
            assert value.metadata.plugin_data.plugin_name == "scalars"
            array = tensor_util.make_ndarray(value.tensor)
            assert array.shape == () and array.dtype == np.float32
            out.append((event.step, value.tag, array[()]))
    return out


def records(path):
    """The payloads of the TFRecords in ``path``, by the format's words
    and not the writer's code; ValueError at a record that is cut short
    or whose checksums do not verify."""
    import google_crc32c

    def check(data, masked):
        c = google_crc32c.value(data)
        if ((c >> 15 | c << 17) + 0xA282EAD8) & 0xFFFFFFFF != masked:
            raise ValueError("checksum")

    with open(path, "rb") as f:
        blob = f.read()
    out, at = [], 0
    while at < len(blob):
        if at + 12 > len(blob):
            raise ValueError("cut short in a header", out)
        (length,), (crc,) = (struct.unpack_from("<Q", blob, at),
                             struct.unpack_from("<I", blob, at + 8))
        check(blob[at:at + 8], crc)
        end = at + 12 + length
        if end + 4 > len(blob):
            raise ValueError("cut short in a payload", out)
        check(blob[at + 12:end], struct.unpack_from("<I", blob, end)[0])
        out.append(blob[at + 12:end])
        at = end + 4
    return out


def test_writer_event_file_round_trip(tmp_path, no_tensorflow):
    rows = [(5, {"loss": 1.2345678, "accuracy": 0.25, "quant_mode": "int8",
                 "hbm_bytes": 12345678901.0, "mfu_xla_cost": None}),
            (10, {"loss": float("nan"), "grad_norm": float("inf"),
                  "past_float32": 1e39})]
    w = MetricWriter(str(tmp_path))
    for step, row in rows:
        w.write(step, row)
    # a row is on disk when write() returns, not at close()
    assert len(loaded_scalars(event_file(tmp_path))) == 6
    w.flush()
    w.close()
    w.close()
    w.write(15, {"loss": 0.0})  # dropped after close, as for the jsonl
    got = loaded_scalars(event_file(tmp_path))
    # one Event a write(): every number of the row at the row's step, in
    # the row's order, at float32; strings and None stay out
    assert [(step, tag) for step, tag, _ in got] == [
        (5, "loss"), (5, "accuracy"), (5, "hbm_bytes"),
        (10, "loss"), (10, "grad_norm"), (10, "past_float32")]
    want = [1.2345678, 0.25, 12345678901.0, float("nan"), float("inf"),
            float("inf")]
    np.testing.assert_array_equal(
        np.array([v for _, _, v in got]), np.array(want, np.float32))
    assert len(records(event_file(tmp_path))) == 3  # version + two rows
    # the same numbers as metrics.jsonl's numeric fields
    jsonl = _rows(tmp_path / "metrics.jsonl")
    assert jsonl[0] == {"step": 5, "loss": 1.2345678, "accuracy": 0.25,
                        "quant_mode": "int8", "hbm_bytes": 12345678901.0}
    assert name_parts(event_file(tmp_path))[1:] == [str(os.getpid()), "0"]


def name_parts(path):
    """[unix seconds, pid, n] of ``events.out.tfevents.<s>.<host>.<pid>.<n>``
    (a host name may hold dots itself)."""
    parts = os.path.basename(path).split(".")
    assert parts[:3] == ["events", "out", "tfevents"]
    assert abs(int(parts[3]) - time.time()) < 60
    return [parts[3], *parts[-2:]]


def test_two_writers_of_one_second_get_a_file_each(tmp_path, no_tensorflow):
    pytest.importorskip("tensorboard")
    with MetricWriter(str(tmp_path)) as a, MetricWriter(str(tmp_path)) as b:
        a.write(1, {"loss": 1.0})
        b.write(2, {"eval/loss": 2.0})
    files = sorted(p for p in os.listdir(tmp_path) if "tfevents" in p)
    assert len(files) == 2
    assert sorted(s for f in files for s in loaded_scalars(
        os.path.join(tmp_path, f))) == [(1, "loss", 1.0),
                                        (2, "eval/loss", 2.0)]


def test_record_framing_known_vectors(tmp_path, no_tensorflow):
    google_crc32c = pytest.importorskip("google_crc32c")
    pytest.importorskip("tensorboard")
    # CRC-32C's check value, and its TFRecord mask worked by hand
    assert google_crc32c.value(b"123456789") == 0xE3069283
    assert mask_crc(0xE3069283) == (
        ((0xE3069283 >> 15) | (0xE3069283 << 17 & 0xFFFFFFFF))
        + 0xA282EAD8) & 0xFFFFFFFF == 0xC78AB0E5
    with MetricWriter(str(tmp_path)) as w:
        w.write(1, {"loss": 1.0})
        w.write(2, {"loss": 2.0})
    path = event_file(tmp_path)
    version, first, second = records(path)  # both checksums of each verify
    # Event{wall_time (1: double), file_version (3: string)}
    assert version[0] == 0x09 and version[9:] == b"\x1a\x0dbrain.Event:2"
    # Event{wall_time, step (2: varint), summary (5) {value (1) {tag (1),
    # simple_value (2: float)}}}
    assert first[9:] == (b"\x10\x01\x2a\x0d\x0a\x0b\x0a\x04loss\x15"
                         + struct.pack("<f", 1.0))
    # a run killed inside a record: the reader says so at that record and
    # keeps the whole ones; TensorBoard's stops there without an error
    with open(path, "rb") as f:
        blob = f.read()
    for cut in (3, len(second) + 4 + 5):  # in the payload, in the header
        with open(path, "wb") as f:
            f.write(blob[:-cut])
        with pytest.raises(ValueError, match="cut short") as e:
            records(path)
        assert e.value.args[1] == [version, first]
        assert loaded_scalars(path) == [(1, "loss", 1.0)]
    with open(path, "wb") as f:  # one flipped bit of a payload
        f.write(blob[:-6] + bytes([blob[-6] ^ 1]) + blob[-5:])
    with pytest.raises(ValueError, match="checksum"):
        records(path)
    # the restarted run appends to nothing: a file of its own
    with MetricWriter(str(tmp_path)) as w:
        w.write(3, {"loss": 3.0})
    assert len([p for p in os.listdir(tmp_path) if "tfevents" in p]) == 2


def test_writer_tf_absent_still_writes_event_file(tmp_path, no_tensorflow):
    pytest.importorskip("tensorboard")
    with pytest.raises(ImportError):
        import tensorflow  # noqa: F401
    w = MetricWriter(str(tmp_path), use_tensorboard=True)
    assert w._tb is not None
    w.write(5, {"loss": 0.5})
    w.close()
    assert loaded_scalars(event_file(tmp_path)) == [(5, "loss", 0.5)]
    assert _rows(tmp_path / "metrics.jsonl") == [{"step": 5, "loss": 0.5}]


@pytest.mark.parametrize("missing", [
    "google_crc32c", "tensorboard.compat.proto.event_pb2"])
def test_writer_helper_absent_falls_back_to_jsonl(tmp_path, monkeypatch,
                                                  caplog, missing):
    monkeypatch.setitem(sys.modules, missing, None)
    with caplog.at_level("INFO", logger="distributedtensorflow_tpu"):
        w = MetricWriter(str(tmp_path), use_tensorboard=True)
    assert w._tb is None
    assert "metrics.jsonl only, no TensorBoard events" in caplog.text
    w.write(5, {"loss": 0.5})
    w.close()
    assert os.listdir(tmp_path) == ["metrics.jsonl"]
    assert _rows(tmp_path / "metrics.jsonl") == [{"step": 5, "loss": 0.5}]


def test_writer_leaves_tensorflow_unimported(tmp_path):
    """In a process of its own: a writer built, written to and closed has
    not brought TensorFlow in (14 s of a trainer's start-up, PERF.md §6
    PR 51), and the event file is there."""
    code = (
        "import os, sys\n"
        "from distributedtensorflow_tpu.utils.metrics import MetricWriter\n"
        "with MetricWriter(sys.argv[1]) as w:\n"
        "    w.write(1, {'loss': 1.0, 'mode': 'x'})\n"
        "assert w._tb is None and w._closed\n"
        "print(sorted('tfevents' in n for n in os.listdir(sys.argv[1])),\n"
        "      [m for m in sys.modules if m.split('.')[0] == 'tensorflow'])\n")
    out = subprocess.run(
        [sys.executable, "-c", code, str(tmp_path)], cwd=REPO, timeout=300,
        capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[False, True] []"


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
