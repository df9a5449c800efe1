"""What the benchmark's arithmetic needs that has no architecture in it:
the table of published peaks and the roofline.

The operations and bytes a model *requires* are its own: each
configuration file names its counts module (``"counts": "<name>"`` ->
``counts/<name>.py``), which the traffic kinds and readers are handed as
``ctx["counts"]``.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} in "
            "benchmark/peaks.json; add it with its source")
    return table[device_kind]


def roofline_seconds(flops: float, bytes_: float, device_kind: str) -> dict:
    """Least time the chip could take, and which bound sets it."""
    p = peaks(device_kind)
    t_flops = flops / p["flops_per_s"]
    t_bytes = bytes_ / p["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
