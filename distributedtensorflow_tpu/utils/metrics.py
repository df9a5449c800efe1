"""Observability: chief-only metric writing + throughput counters.

Reference: ``tf.summary`` event files + Keras callbacks + chief-only
convention (SURVEY.md §5.5).  A ``metrics.jsonl`` record is always written
(the human/tool-greppable artifact); the TensorBoard event file beside it
is :class:`EventFileWriter`'s, which needs no TensorFlow: the ``tensorboard``
package's ``Event`` message and ``google_crc32c``, both imported in a tenth
of a second where ``import tensorflow`` was 14 of a trainer's start-up.

Lifecycle contract: ``MetricWriter`` is a context manager, ``close()`` is
idempotent and flushes, and every owner (``Trainer``, ``SidecarEvaluator``,
``train.py``'s async-PS role) closes its writer on shutdown — the one
append/flush/close discipline for everything that touches
``metrics.jsonl``.  Writes after ``close()`` are dropped (a late async
callback must not crash teardown).
"""

from __future__ import annotations

import itertools
import json
import logging
import math
import os
import socket
import struct
import time
from typing import Any, Mapping

import jax

logger = logging.getLogger("distributedtensorflow_tpu")


def json_sanitize(value: Any) -> Any:
    """Map non-finite floats to sentinel strings ("NaN"/"Infinity"/
    "-Infinity"), recursively.  ``json.dumps`` would otherwise emit bare
    ``NaN`` tokens — invalid strict JSON — exactly on the rows that matter
    most (a NaN loss).  Consumers (``tools/run_report.py``,
    ``tools/check_metrics_schema.py``) decode the sentinels back."""
    if isinstance(value, float) and not math.isfinite(value):
        if math.isnan(value):
            return "NaN"
        return "Infinity" if value > 0 else "-Infinity"
    if isinstance(value, dict):
        return {k: json_sanitize(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [json_sanitize(v) for v in value]
    return value


def mask_crc(c: int) -> int:
    """A CRC-32C as a TFRecord stores it: rotated right by 15 bits and
    offset, so that a checksum of bytes which themselves hold checksums
    stays well distributed."""
    return ((c >> 15 | c << 17) + 0xA282EAD8) & 0xFFFFFFFF


class EventFileWriter:
    """One TensorBoard event file in ``logdir``,
    ``events.out.tfevents.<unix seconds>.<host>.<pid>.<n>``: TFRecords
    (u64 length, u32 masked CRC-32C of the length, the payload, u32 masked
    CRC-32C of the payload; little-endian) that each hold one ``Event``,
    the first the file's version.  Raises ImportError where the
    ``tensorboard`` package or ``google_crc32c`` is not installed.
    (``native.recordio`` frames the same records in C++, but builds its
    library at first use: a compiler inside the start-up that this writer
    exists to shorten.)"""

    def __init__(self, logdir: str):
        # both before the file is made: a machine without one leaves none
        from google_crc32c import value as crc32c  # noqa: PLC0415
        from tensorboard.compat.proto.event_pb2 import Event  # noqa: PLC0415

        self._crc32c = crc32c
        self._event = Event
        stem = os.path.join(logdir, "events.out.tfevents.%d.%s.%d." % (
            time.time(), socket.gethostname(), os.getpid()))
        for n in itertools.count():
            try:  # the first n no writer of this process and second took
                self._file = open(stem + str(n), "xb")
                break
            except FileExistsError:
                continue
        self._record(Event(wall_time=time.time(),
                           file_version="brain.Event:2"))
        self._file.flush()

    def _record(self, event) -> None:
        data = event.SerializeToString()
        header = struct.pack("<Q", len(data))
        self._file.write(
            header + struct.pack("<I", mask_crc(self._crc32c(header)))
            + data + struct.pack("<I", mask_crc(self._crc32c(data))))

    def write(self, step: int, scalars: Mapping[str, float]) -> None:
        """One ``Event`` at ``step`` with every scalar a ``simple_value``
        (float32, as ``tf.summary.scalar`` stored it), flushed."""
        event = self._event(wall_time=time.time(), step=step)
        for tag, value in scalars.items():
            event.summary.value.add(tag=tag, simple_value=value)
        self._record(event)
        self._file.flush()

    def flush(self) -> None:
        self._file.flush()

    def close(self) -> None:
        self._file.close()


class MetricWriter:
    """Writes scalars; only the chief process actually emits (SURVEY.md §5.5)."""

    def __init__(self, logdir: str | None = None, *, use_tensorboard: bool = True):
        self._chief = jax.process_index() == 0
        self._tb = None
        self._jsonl = None
        self._closed = False
        if not self._chief or logdir is None:
            return
        os.makedirs(logdir, exist_ok=True)
        if use_tensorboard:
            # (a writer that imports but cannot write is an error, not a
            # quiet downgrade)
            try:
                self._tb = EventFileWriter(logdir)
            except ImportError:
                logger.info("tensorboard or google_crc32c not importable: "
                            "%s gets metrics.jsonl only, no TensorBoard "
                            "events", logdir)
        # JSONL is always written: a human/tool-greppable record of the run
        # (TensorBoard events are the reference-parity surface on top).
        self._jsonl = open(os.path.join(logdir, "metrics.jsonl"), "a")

    def write(self, step: int, scalars: Mapping[str, Any]) -> None:
        if not self._chief or self._closed:
            return
        # Strings pass through to the jsonl record (mode stamps like
        # ``quant_mode``); everything else is coerced to float.  TB only
        # understands scalars, so string fields skip that sink.
        scalars = {
            k: (v if isinstance(v, str) else float(v))
            for k, v in scalars.items() if v is not None
        }
        if self._tb is not None:
            self._tb.write(step, {k: v for k, v in scalars.items()
                                  if not isinstance(v, str)})
        if self._jsonl is not None:
            # `t`: when the row was written, as steps.jsonl and
            # requests.jsonl rows carry (jsonl only: not a TB scalar)
            self._jsonl.write(
                json.dumps(json_sanitize(
                    {"step": step, "t": round(time.time(), 6), **scalars}),
                    allow_nan=False) + "\n"
            )
            self._jsonl.flush()

    def write_record(self, record: Mapping[str, Any]) -> None:
        """Append one free-form JSON record (chief-only, flushed).

        For streams whose rows are not step-keyed scalar dicts (the
        async-PS progress records carry nested histograms); shares this
        writer's handle/flush/close discipline instead of a raw
        ``open(...)`` next to it.
        """
        if not self._chief or self._closed or self._jsonl is None:
            return
        self._jsonl.write(
            json.dumps(json_sanitize(dict(record)), allow_nan=False) + "\n"
        )
        self._jsonl.flush()

    def flush(self) -> None:
        if self._jsonl is not None and not self._closed:
            self._jsonl.flush()
        if self._tb is not None and not self._closed:
            self._tb.flush()

    def close(self) -> None:
        """Flush and release both sinks; safe to call more than once."""
        if self._closed:
            return
        self._closed = True
        if self._jsonl is not None:
            try:
                self._jsonl.flush()
            finally:
                self._jsonl.close()
                self._jsonl = None
        if self._tb is not None:
            try:
                self._tb.close()
            except OSError:  # a broken TB writer must not mask teardown
                logger.exception("tensorboard writer close failed")
            self._tb = None

    def __enter__(self) -> "MetricWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class ThroughputMeter:
    """steps/sec and examples/sec/chip — the BASELINE.json metric counter."""

    def __init__(self, global_batch_size: int):
        self.global_batch_size = global_batch_size
        self._t0: float | None = None
        self._steps = 0

    def start(self) -> None:
        self._t0 = time.perf_counter()
        self._steps = 0

    def update(self, n_steps: int = 1) -> None:
        if self._t0 is None:
            self.start()
        self._steps += n_steps

    def rates(self) -> dict[str, float]:
        if not self._t0 or not self._steps:
            return {}
        dt = time.perf_counter() - self._t0
        steps_per_sec = self._steps / dt
        ex_per_sec = steps_per_sec * self.global_batch_size
        n_chips = jax.device_count()
        return {
            "steps_per_sec": steps_per_sec,
            "examples_per_sec": ex_per_sec,
            "examples_per_sec_per_chip": ex_per_sec / n_chips,
        }
