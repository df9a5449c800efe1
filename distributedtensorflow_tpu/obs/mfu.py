"""MFU accounting: the device peaks table and the fields built on it.

One table (:data:`DEVICE_PEAKS`) holds the published per-chip peaks, keyed
by ``jax.Device.device_kind``.  A kind that is not in it is an error for
the bench scripts (:func:`device_peaks` raises) and means *no* ``mfu*``
field in the Trainer's metric stream (:func:`mfu_record_fields` returns
``{}``) — a utilization against some other chip's peak is never emitted.

FLOP-counting convention: BOTH estimators count one multiply-add as
**2 FLOPs** — XLA's ``cost_analysis()["flops"]`` reports exactly
``2·M·N·K`` for an ``(M,K)×(K,N)`` matmul (:func:`matmul_flops`, pinned by
``tests/test_mfu.py``), so any analytic ``flops_per_step`` fed into these
fields must use the same MACs×2 convention.  The one legitimate residual
between the two: a ``lax.scan`` body is counted once regardless of trip
count — callers pass ``xla_flops_scale`` (see :func:`mfu_fields`).
"""

from __future__ import annotations

import logging

logger = logging.getLogger("distributedtensorflow_tpu")

__all__ = ["DEVICE_PEAKS", "device_peaks", "matmul_flops", "mfu_fields",
           "mfu_record_fields", "peak_flops", "peak_hbm_bytes_per_s",
           "xla_cost_analysis", "xla_cost_flops"]

#: Published per-chip peaks by ``device_kind``: dense bf16 FLOP/s and HBM
#: bytes/s.  Source: Google Cloud TPU documentation, the "System
#: architecture" page of each generation ("TPU v5e": 197 TFLOP/s bf16,
#: 819 GB/s HBM2e; "TPU v4": 275 TFLOP/s, 1,228 GB/s; "TPU v3":
#: 123 TFLOP/s, 900 GB/s).  ``"TPU v5 lite"`` is how JAX names a v5e chip.
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v4": {"flops": 275e12, "hbm_bytes_per_s": 1228e9},
    "TPU v3": {"flops": 123e12, "hbm_bytes_per_s": 900e9},
}


def device_peaks(device_kind: str) -> dict:
    """The :data:`DEVICE_PEAKS` row for ``device_kind``; an unknown kind
    raises — there is no default chip."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)} (add the kind to obs.mfu.DEVICE_PEAKS "
            "with its source)"
        ) from None


def peak_flops(device_kind: str) -> float:
    """Peak dense bf16 FLOP/s of one chip of ``device_kind``."""
    return device_peaks(device_kind)["flops"]


def peak_hbm_bytes_per_s(device_kind: str) -> float:
    """Peak HBM bandwidth of one chip of ``device_kind``."""
    return device_peaks(device_kind)["hbm_bytes_per_s"]


def matmul_flops(m: int, n: int, k: int) -> float:
    """Analytic FLOPs of an ``(m, k) @ (k, n)`` matmul under the MACs×2
    convention — the shared numerator contract between the analytic and
    xla-cost MFU paths (see module docstring)."""
    return 2.0 * m * n * k


def xla_cost_analysis(compiled) -> dict | None:
    """One best-effort ``cost_analysis()`` call: the executable's cost
    dict, or None when the backend can't answer."""
    try:
        cost = compiled.cost_analysis()
    except Exception as e:
        logger.info("xla cost analysis unavailable (%s)", e)
        return None
    return cost or None


def xla_cost_flops(compiled) -> float | None:
    """Executed FLOPs of a compiled executable per XLA's cost analysis
    (the partitioned, per-device module — the per-chip MFU numerator), or
    None when the backend can't answer."""
    cost = xla_cost_analysis(compiled)
    if not cost or not cost.get("flops"):
        return None
    return float(cost["flops"])


def mfu_fields(compiled, dt: float, n_steps: int, device_kind: str,
               analytic_flops_per_step: float,
               analytic_source: str, xla_flops_scale: float = 1.0,
               cost: dict | None = None) -> dict:
    """Both MFU accountings for a bench result, as emit-ready fields.

    ``mfu_analytic`` divides ANALYTIC per-chip model FLOPs (6·N·D-style,
    fixed by the model config, independent of the implementation) by peak —
    the stable round-over-round number, and what ``mfu`` aliases.
    ``mfu_xla_cost`` divides XLA's partitioned-module cost analysis by peak
    — it tracks what the compiled program actually executes, so it MOVES
    when the implementation changes (e.g. the vocab-chunked CE head raised
    throughput while lowering executed FLOPs).

    ``xla_flops_scale``: XLA's cost analysis counts a ``lax.scan`` body
    ONCE regardless of trip count, so a k-steps-per-dispatch executable
    (engine.make_multi_train_step) under-reports executed FLOPs by ~k;
    callers bundling k steps per call pass ``xla_flops_scale=k``.
    ``cost={}`` skips the cost analysis (no AOT executable at hand).
    Raises on a ``device_kind`` without published peaks."""
    peak = peak_flops(device_kind)
    xla_mfu = None
    if cost is None:
        cost = xla_cost_analysis(compiled)
    if cost and cost.get("flops"):
        xla_mfu = (float(cost["flops"]) * xla_flops_scale * n_steps / dt) / peak
    analytic_mfu = (analytic_flops_per_step * n_steps / dt) / peak
    return {
        "mfu": round(analytic_mfu, 4),
        "mfu_analytic": round(analytic_mfu, 4),
        "mfu_analytic_source": analytic_source,
        "mfu_xla_cost": round(xla_mfu, 4) if xla_mfu is not None else None,
    }


def mfu_record_fields(
    flops_per_step: float,
    dt_per_step: float,
    device_kind: str | None = None,
) -> dict[str, float]:
    """Numeric MFU fields for one metric record.

    ``flops_per_step`` is per-chip model FLOPs per optimizer step (analytic
    6·N·D-style, or the XLA cost-analysis estimate from
    ``train.engine.estimate_step_flops``); ``dt_per_step`` the measured
    wall seconds per step.  Returns ``{}`` when either is unknown, or when
    the device (default: this process's first local device) has no
    published peak — a CPU run carries no ``mfu`` field at all.
    """
    if not flops_per_step or not dt_per_step or dt_per_step <= 0:
        return {}
    if device_kind is None:
        import jax  # noqa: PLC0415

        device_kind = jax.local_devices()[0].device_kind
    if device_kind not in DEVICE_PEAKS:
        return {}
    mfu = flops_per_step / dt_per_step / peak_flops(device_kind)
    return {"mfu": round(mfu, 4), "mfu_analytic": round(mfu, 4)}
