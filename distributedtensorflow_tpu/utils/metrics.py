"""Observability: chief-only metric writing + throughput counters.

Reference: ``tf.summary`` event files + Keras callbacks + chief-only
convention (SURVEY.md §5.5).  A ``metrics.jsonl`` record is always written
(the human/tool-greppable artifact); TensorBoard-compatible event output is
layered on top through ``tf.summary`` when TF is importable.

Lifecycle contract: ``MetricWriter`` is a context manager, ``close()`` is
idempotent and flushes, and every owner (``Trainer``, ``SidecarEvaluator``,
``train.py``'s async-PS role) closes its writer on shutdown — the one
append/flush/close discipline for everything that touches
``metrics.jsonl``.  Writes after ``close()`` are dropped (a late async
callback must not crash teardown).
"""

from __future__ import annotations

import json
import logging
import math
import os
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


def import_tensorflow(logdir: str):
    """TensorFlow, whose event writer is the TensorBoard sink of a
    :class:`MetricWriter` on ``logdir``, or None where it is not installed.
    The first import is seconds of a process's start-up: an entry point
    that names its phases makes it here, under a name of its own."""
    try:
        import tensorflow as tf  # noqa: PLC0415
    except ImportError:  # no TF installed -> JSONL only
        logger.info("tensorflow not importable: %s gets metrics.jsonl "
                    "only, no TensorBoard events", logdir)
        return None
    return tf


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
            tf = import_tensorflow(logdir)
            if tf is not None:
                # a TF that imports but cannot write is an error, not a
                # quiet downgrade
                self._tb = tf.summary.create_file_writer(logdir)
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
            import tensorflow as tf  # noqa: PLC0415

            with self._tb.as_default(step=step):
                for k, v in scalars.items():
                    if not isinstance(v, str):
                        tf.summary.scalar(k, v)
            self._tb.flush()
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
                self._tb.flush()
                close = getattr(self._tb, "close", None)
                if close is not None:
                    close()
            except Exception:  # a broken TB writer must not mask teardown
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
