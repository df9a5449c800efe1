#!/usr/bin/env python3
"""Run a list of cells one after another and keep every result line.

    python benchmark/tools/measure.py --out chiprun_out/NAME --seconds 50 \\
        CELL:SEED:TRACE [CELL:SEED:TRACE ...]

Each run is a fresh ``run.py`` process, as the driver makes them.  The
result lines go to ``<out>/results.jsonl`` (with the run's wall seconds
and exit code); a failed run leaves the tail of its logs beside them.
``--keep-logs`` copies each run's small logs, ``--trace-dump`` also a
summary of the profiler trace's planes and lines (and the trace itself if
it is small) for building test fixtures.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


def trace_dump(cell_out: str, dst: str) -> None:
    import trace_reduce

    path = trace_reduce.find_xplane(os.path.join(cell_out, "trace"))
    if not path:
        return
    from jax.profiler import ProfileData   # reads a file; no backend

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            lines.append({"line": line.name, "events": len(events),
                          "first": [[e.name[:120], e.start_ns, e.duration_ns]
                                    for e in events[:12]]})
        planes.append({"plane": plane.name, "lines": lines})
    with open(os.path.join(dst, "trace_planes.json"), "w") as f:
        json.dump(planes, f, indent=1)
    if os.path.getsize(path) < 24 << 20:
        shutil.copy(path, os.path.join(dst, "trace.xplane.pb"))


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--out", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--manifest", default=None)
    p.add_argument("--keep-logs", action="store_true")
    p.add_argument("--trace-dump", action="store_true")
    p.add_argument("runs", nargs="+")
    args = p.parse_args()
    os.makedirs(args.out, exist_ok=True)
    results = open(os.path.join(args.out, "results.jsonl"), "a")
    for i, spec in enumerate(args.runs):
        cell, seed, trace = spec.split(":")
        cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
               cell, "--seed", seed, "--seconds", str(args.seconds),
               "--trace", trace]
        if args.manifest:
            cmd += ["--manifest", args.manifest]
        t0 = time.time()
        proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.time() - t0
        lines = proc.stdout.strip().splitlines()
        row = {"run": spec, "rc": proc.returncode, "wall_s": round(wall, 1)}
        try:
            row["result"] = json.loads(lines[-1])
        except (IndexError, json.JSONDecodeError):
            row["stdout_tail"] = lines[-5:]
        if proc.returncode:
            row["stderr_tail"] = proc.stderr[-3000:]
        results.write(json.dumps(row) + "\n")
        results.flush()
        print(json.dumps(row)[:1500], flush=True)
        cell_out = os.path.join(ROOT, "bench_out", cell)
        if args.keep_logs or proc.returncode:
            dst = os.path.join(args.out, f"{i}_{cell}_{seed}_{trace}")
            os.makedirs(dst, exist_ok=True)
            for rel in ("child.log", "child.out", "reference.log",
                        "ctl/compiles.jsonl", "ctl/preflight.json",
                        "ctl/trace_done.json", "serve/steps.jsonl",
                        "serve/requests.jsonl", "train/metrics.jsonl",
                        "client_log.json", "window_rows.jsonl"):
                src = os.path.join(cell_out, rel)
                if os.path.exists(src) and os.path.getsize(src) < 8 << 20:
                    shutil.copy(src, os.path.join(
                        dst, rel.replace("/", "_")))
            if args.trace_dump and trace == "1":
                trace_dump(cell_out, dst)
    return 0


if __name__ == "__main__":
    sys.exit(main())
