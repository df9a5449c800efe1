#!/usr/bin/env python
"""Summarize a jax.profiler trace: top ops by device time, from the raw
xplane proto.

Usage::

    python tools/profile_summary.py <logdir>/captures/0 [--top 30]

Reads the ``*.xplane.pb`` a ``jax.profiler.start_trace`` /
``train.py --profile-dir`` window writes and prints, per device plane, the
top event names by summed duration with their share of the plane's busy
time.  The installed ``tensorboard_plugin_profile``
(2.13) cannot parse TF 2.21's pywrap output, so this goes straight at the
proto (schema: ``tensorflow/tsl/profiler/protobuf/xplane.proto`` in the
installed wheel — the XSpace → planes → lines → events tree with
durations in picoseconds).

Plain stdlib + the TF wheel; no network, no plugin server.

Reading the output: device planes ("/device:TPU:N") carry one flat event
per XLA op execution, so shares sum to ~100% of device busy time.  Host
planes nest Python frames inside each other, so their "busy" exceeds the
span — use them for what blocks the host (dispatch, fetches), not for
percentages.
"""

from __future__ import annotations

import argparse
import collections
import glob
import os
import sys


def find_xplane_files(path: str) -> list[str]:
    if os.path.isfile(path):
        return [path]
    hits = sorted(
        glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    )
    return hits


def load_xspace(path: str):
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception as e:  # no TF wheel on the analysis box
        raise SystemExit(
            f"profile_summary: cannot import the xplane proto ({e}); "
            "run where the tensorflow wheel is installed"
        ) from None

    xspace = xplane_pb2.XSpace()
    try:
        with open(path, "rb") as f:
            xspace.ParseFromString(f.read())
    except Exception as e:  # truncated/corrupt pb from a killed capture
        raise SystemExit(
            f"profile_summary: {path}: unreadable xplane proto ({e})"
        ) from None
    return xspace


def summarize_plane(plane, top: int) -> tuple[list, float, float]:
    """Returns (rows, busy_ms, span_ms): per-name summed durations."""
    by_name: dict[str, float] = collections.defaultdict(float)
    count: dict[str, int] = collections.defaultdict(int)
    t_min, t_max = float("inf"), 0.0
    meta = plane.event_metadata
    for line in plane.lines:
        for ev in line.events:
            name = meta[ev.metadata_id].name if ev.metadata_id in meta else "?"
            dur_ms = ev.duration_ps / 1e9
            by_name[name] += dur_ms
            count[name] += 1
            start = line.timestamp_ns * 1e3 + ev.offset_ps / 1.0  # ps
            t_min = min(t_min, start)
            t_max = max(t_max, start + ev.duration_ps)
    busy_ms = sum(by_name.values())
    span_ms = (t_max - t_min) / 1e9 if t_max > t_min else 0.0
    rows = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return [(n, ms, count[n]) for n, ms in rows], busy_ms, span_ms


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("profile_dir", help="trace dir or an .xplane.pb file")
    p.add_argument("--top", type=int, default=25)
    p.add_argument("--all-planes", action="store_true",
                   help="include host/python planes (default: device only)")
    args = p.parse_args(argv)

    if not os.path.exists(args.profile_dir):
        print(
            f"profile_summary: {args.profile_dir}: no such profile dir "
            "(did the capture run?)", file=sys.stderr,
        )
        return 1
    files = find_xplane_files(args.profile_dir)
    if not files:
        print(
            f"profile_summary: no *.xplane.pb under {args.profile_dir} "
            "(empty or partial profile dir)", file=sys.stderr,
        )
        return 1
    printed = 0
    for path in files:
        xspace = load_xspace(path)
        print(f"== {os.path.relpath(path, args.profile_dir)}")
        for plane in xspace.planes:
            is_device = (
                "/device:" in plane.name or "TPU" in plane.name
            ) and "Host" not in plane.name
            if not (is_device or args.all_planes):
                continue
            rows, busy_ms, span_ms = summarize_plane(plane, args.top)
            if not rows:
                continue
            printed += 1
            print(
                f"-- plane {plane.name!r}: busy {busy_ms:.2f} ms over "
                f"{span_ms:.2f} ms span "
                f"({100 * busy_ms / span_ms if span_ms else 0:.0f}% busy)"
            )
            width = max(len(n) for n, _, _ in rows)
            for name, ms, n in rows:
                print(
                    f"  {name[:90]:<{min(width, 90)}}  {ms:9.3f} ms  "
                    f"{100 * ms / busy_ms:5.1f}%  x{n}"
                )
    if not printed:
        # No matching plane had any events — exiting 0 with an empty table
        # used to read as "nothing is slow"; it actually means "nothing was
        # captured" (CPU-only trace without --all-planes, or a window that
        # closed before a step ran).
        print(
            f"profile_summary: no plane with events in {len(files)} "
            "xplane file(s) — CPU-only capture? (re-run with --all-planes "
            "to include host planes)", file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
