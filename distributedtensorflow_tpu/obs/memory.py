"""Device-memory and host-memory telemetry.

HBM exhaustion on one host is the second dominant pod-scale failure mode
(after stalled collectives), and it creeps: fragmentation and stray live
arrays grow for hours before the OOM.  This module makes the creep visible
on three surfaces without attaching a profiler:

- per-device HBM in-use/peak via ``device.memory_stats()`` (graceful
  empty result on backends that don't report — the virtual-CPU test mesh);
- host RSS from ``/proc/self/statm`` (portable ``resource`` fallback);
- a ``jax.live_arrays()`` census — count and total bytes of every array
  the process is keeping alive, the "what is actually holding my HBM"
  answer (a leak shows as monotonic growth here long before the OOM).

Consumers: :func:`record_fields` rides the per-step ``metrics.jsonl``
record (flat scalars), :func:`update_registry` refreshes labeled gauges
for the Prometheus snapshot and ``/varz``, and :func:`memz` is the
``/memz`` endpoint's full JSON payload.  Everything here syncs no device
computation, but the live-array census is O(#arrays) — call at log
boundaries / on demand, never per dispatch; a caller feeding several
consumers at one boundary should :func:`collect` once and pass the
snapshot to each (the Trainer does).
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger("distributedtensorflow_tpu")

__all__ = [
    "collect",
    "device_memory_snapshot",
    "host_rss_bytes",
    "live_arrays_census",
    "record_fields",
    "update_registry",
    "memz",
    "tree_bytes_by_device",
    "state_bytes_report",
    "state_bytes_record_fields",
    "set_train_state_bytes",
    "train_state_record_fields",
]

_GIB = 1.0 / (1024 ** 3)


def device_memory_snapshot() -> list[dict]:
    """One dict per local device from ``memory_stats()``; devices that
    don't report (virtual CPU) contribute ``{"id", "platform"}`` only."""
    import jax  # noqa: PLC0415 — keep module importable pre-backend-init

    out = []
    for d in jax.local_devices():
        entry: dict = {"id": int(d.id), "platform": str(d.platform)}
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats:
            for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                        "largest_free_block_bytes", "num_allocs"):
                if key in stats:
                    entry[key] = int(stats[key])
        out.append(entry)
    return out


def host_rss_bytes() -> int | None:
    """Current resident set size of this process, or None if unknowable."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource  # noqa: PLC0415
        import sys  # noqa: PLC0415

        # ru_maxrss is the PEAK — a coarser fallback, but peak RSS still
        # catches host-side leaks on non-/proc platforms.  Units differ:
        # KiB on Linux, bytes on macOS.
        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return peak if sys.platform == "darwin" else peak * 1024
    except Exception:
        return None


def _resident_nbytes(a) -> int:
    """THIS host's resident bytes for one array: summed over addressable
    shards, so a pod-sharded global array counts its local slice (global
    ``size * itemsize`` would overstate per-host HBM by process_count —
    the exact scale where the census matters), and a replicated array
    counts every local device's copy."""
    try:
        shards = a.addressable_shards
    except Exception:
        shards = None
    if shards:
        return sum(
            int(s.data.size) * s.data.dtype.itemsize for s in shards
        )
    return int(a.size) * a.dtype.itemsize


def live_arrays_census(top: int = 5) -> dict:
    """Count/resident-bytes of every live ``jax.Array``, plus the ``top``
    largest (global shape, local bytes) — the "what holds my HBM" answer."""
    import jax  # noqa: PLC0415

    count = 0
    total = 0
    largest: list[tuple[int, str, str]] = []
    try:
        arrays = jax.live_arrays()
    except Exception:
        return {"count": 0, "bytes": 0, "top": []}
    for a in arrays:
        try:
            nbytes = _resident_nbytes(a)
            shape, dtype = str(tuple(a.shape)), str(a.dtype)
        except Exception:  # deleted/donated mid-iteration
            continue
        count += 1
        total += nbytes
        largest.append((nbytes, shape, dtype))
    largest.sort(key=lambda e: -e[0])
    return {
        "count": count,
        "bytes": total,
        "top": [
            {"bytes": b, "shape": s, "dtype": d}
            for b, s, d in largest[: max(0, top)]
        ],
    }


def collect(top: int = 0) -> dict:
    """One full snapshot — per-device stats, host RSS, live-array census —
    taken ONCE and fed to every consumer at a boundary (the census is the
    expensive part; don't pay it per consumer)."""
    return {
        "devices": device_memory_snapshot(),
        "host_rss_bytes": host_rss_bytes(),
        "live_arrays": live_arrays_census(top=top),
    }


def record_fields(snapshot: dict | None = None) -> dict[str, float]:
    """Flat scalars for the per-step metric record: device-0 HBM (the
    established ``hbm_in_use_gib``/``hbm_peak_gib`` names), host RSS, and
    the live-array census.  Absent sources contribute nothing."""
    snap = snapshot or collect()
    out: dict[str, float] = {}
    if snap["devices"]:
        d0 = snap["devices"][0]
        if "bytes_in_use" in d0:
            out["hbm_in_use_gib"] = d0["bytes_in_use"] * _GIB
        if "peak_bytes_in_use" in d0:
            out["hbm_peak_gib"] = d0["peak_bytes_in_use"] * _GIB
    if snap["host_rss_bytes"] is not None:
        out["host_rss_gib"] = snap["host_rss_bytes"] * _GIB
    census = snap["live_arrays"]
    out["live_arrays"] = float(census["count"])
    out["live_arrays_gib"] = census["bytes"] * _GIB
    return out


def update_registry(registry=None, snapshot: dict | None = None) -> None:
    """Refresh the labeled memory gauges (``device=<id>`` per device) in
    ``registry`` (default: the process registry) for Prometheus/``/varz``."""
    from . import registry as reglib  # noqa: PLC0415

    reg = registry or reglib.default_registry()
    snap = snapshot or collect()
    in_use = reg.gauge("device_memory_in_use_bytes", "HBM bytes in use")
    peak = reg.gauge("device_memory_peak_bytes", "peak HBM bytes in use")
    for d in snap["devices"]:
        if "bytes_in_use" in d:
            in_use.set(d["bytes_in_use"], device=str(d["id"]))
        if "peak_bytes_in_use" in d:
            peak.set(d["peak_bytes_in_use"], device=str(d["id"]))
    if snap["host_rss_bytes"] is not None:
        reg.gauge("host_rss_bytes", "process resident set size").set(
            snap["host_rss_bytes"]
        )
    census = snap["live_arrays"]
    reg.gauge("live_arrays", "live jax.Array count").set(census["count"])
    reg.gauge("live_arrays_bytes", "total bytes of live jax.Arrays").set(
        census["bytes"]
    )


def memz(top: int = 10) -> dict:
    """Full ``/memz`` payload — :func:`collect` with the ``top`` largest
    arrays itemized, plus the train-state bytes breakdown when a trainer
    has installed one (:func:`set_train_state_bytes`)."""
    out = collect(top=top)
    if _TRAIN_STATE_BYTES is not None:
        out["train_state"] = _TRAIN_STATE_BYTES
    return out


# --- train-state bytes: the number weight-update sharding shrinks -----------
#
# Shapes and shardings are fixed for a fit, so the breakdown is computed
# ONCE at fit begin (never per step) and served statically on /memz, the
# labeled registry gauges, and the per-record fields.

_TRAIN_STATE_BYTES: dict | None = None


def tree_bytes_by_device(tree) -> dict[int, int]:
    """THIS host's resident bytes of a pytree, summed per device id —
    a replicated tree charges every device its full size; a ZeRO-sharded
    optimizer state charges each device only its 1/degree chunk."""
    out: dict[int, int] = {}
    for leaf in _jax_leaves(tree):
        try:
            shards = leaf.addressable_shards
        except Exception:
            continue
        for s in shards:
            dev = int(getattr(s.device, "id", 0))
            out[dev] = out.get(dev, 0) + int(s.data.size) * s.data.dtype.itemsize
    return out


def _jax_leaves(tree):
    import jax  # noqa: PLC0415

    return [l for l in jax.tree.leaves(tree) if hasattr(l, "addressable_shards")]


def state_bytes_report(params, opt_state) -> dict:
    """The per-device train-state bytes breakdown — THE byte-accounting
    rule (one place): trainer fit-begin, bench rows, and /memz all
    derive from this shape."""
    return {
        "params": tree_bytes_by_device(params),
        "opt_state": tree_bytes_by_device(opt_state),
    }


def state_bytes_record_fields(report: dict) -> dict[str, float]:
    """Flatten a :func:`state_bytes_report` into the record/bench fields:
    the WORST (max) device's bytes of params and optimizer state."""
    out: dict[str, float] = {}
    for key, field in (("params", "params_bytes_per_device"),
                       ("opt_state", "opt_state_bytes_per_device")):
        per_dev = report.get(key)
        if per_dev:
            out[field] = float(max(per_dev.values()))
    return out


def set_train_state_bytes(report: dict | None,
                          registry=None) -> None:
    """Install (or clear, with None) the per-device train-state bytes
    breakdown: ``{"params": {dev: bytes}, "opt_state": {...}, ...}`` plus
    scalar annotations (``zero_stage``, ``zero_degree``).  Refreshes the
    ``params_bytes_per_device`` / ``optimizer_state_bytes_per_device``
    labeled gauges so /varz and metrics.prom carry the breakdown too."""
    global _TRAIN_STATE_BYTES
    _TRAIN_STATE_BYTES = report
    if report is None:
        return
    from . import registry as reglib  # noqa: PLC0415

    reg = registry or reglib.default_registry()
    gauges = {
        "params": reg.gauge(
            "params_bytes_per_device", "parameter bytes resident per device"
        ),
        "opt_state": reg.gauge(
            "optimizer_state_bytes_per_device",
            "optimizer-state bytes resident per device (the bytes "
            "weight-update sharding divides by the ZeRO degree)",
        ),
    }
    for key, gauge in gauges.items():
        for dev, nbytes in (report.get(key) or {}).items():
            gauge.set(nbytes, device=str(dev))


def train_state_record_fields() -> dict[str, float]:
    """Flat scalars for the metric record: the WORST (max) per-device
    bytes of params and optimizer state, plus the ZeRO annotations —
    what run_report and the bench rows surface so a sharding win is a
    number, not an assertion."""
    rep = _TRAIN_STATE_BYTES
    if not rep:
        return {}
    out = state_bytes_record_fields(rep)
    for key in ("zero_stage", "zero_degree"):
        if isinstance(rep.get(key), (int, float)):
            out[key] = float(rep[key])
    return out
