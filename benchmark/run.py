#!/usr/bin/env python3
"""One cell, once: ``python benchmark/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.

Everything about a cell is data: ``BENCHMARK.json`` names the cell, its
configuration file and its traffic mix; ``traffic/<mix>.json`` names the
traffic kind, whose generator is ``traffic_kinds/<kind>.py``; each
per-layer metric has ``layer_metrics/<metric>.json`` naming its reader,
``readers/<reader>.py``; the configuration file names its plain reference
(``reference/<name>.py``), its operation counts (``counts/<name>.py``) and
its on-chip check (``checks/<name>.py``).  The last line of standard
output is the result: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, in a traced run, ``breakdown``.  With ``--trace 0`` the
metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics.

``--manifest`` points at another manifest of the same form (the rehearsal
under ``tests/``, which runs a tiny model on the CPU and says so).
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
from harness import BenchError  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--manifest",
                   default=os.path.join(harness.ROOT, "BENCHMARK.json"))
    args = p.parse_args(argv)
    # the parent stays off the chip whatever it imports later
    os.environ["JAX_PLATFORMS"] = "cpu"

    manifest = harness.load_json(args.manifest)
    roots = [BENCH]
    extra = os.path.dirname(os.path.abspath(args.manifest))
    if extra not in (harness.ROOT, BENCH):
        roots.insert(0, extra)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        raise BenchError(f"no workload {args.workload!r} in {args.manifest};"
                         f" it has {sorted(cells)}")
    cell = cells[args.workload]
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
    config = harness.load_json(os.path.join(harness.ROOT, cfg_entry["file"]))
    traffic = harness.load_json(
        harness.find_file(roots, "traffic", cell["traffic"], ".json"))
    kind = harness.load_module(
        harness.find_file(roots, "traffic_kinds", traffic["kind"], ".py"))
    counts_file = harness.find_file(roots, "counts", config["counts"], ".py")
    counts = harness.load_module(counts_file)
    out = harness.fresh_dir(os.path.join(harness.OUT, cell["name"]))
    ctx = {"cell": cell, "config": config, "traffic": traffic, "out": out,
           "roots": roots, "counts": counts,
           "seed": args.seed, "seconds": args.seconds,
           "trace": bool(args.trace), "chips": cell["chips"],
           "t_process_start": T_PROCESS_START}
    res = kind.run(ctx)

    metrics = {}
    if not args.trace:
        values = {**res["end_to_end"], "setup_s": res["setup_s"]}
        for m in manifest["end_to_end"]:
            if harness.applies(m, cell["name"]):
                if m["name"] not in values:
                    raise BenchError(f"{cell['name']} did not produce "
                                     f"{m['name']}")
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    device = dict(res["device"])
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if args.trace:
        layer = {**res["layer"], "config": config, "counts": counts,
                 "out": out,
                 "device_kind": device["kind"]}
        import trace_reduce

        xplane = trace_reduce.find_xplane(layer["trace_dir"])
        layer["trace"] = trace_reduce.load_xplane(xplane) if xplane else None
        if layer["trace"] and layer["trace"]["devices"]:
            summary = trace_reduce.summarize(layer["trace"])
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            line["breakdown"] = summary["breakdown"]
        elif not config.get("rehearsal"):
            raise BenchError("the traced run holds no device operation")
        for m in manifest["per_layer"]:
            if not harness.applies(m, cell["name"]):
                continue
            spec = harness.load_json(harness.find_file(
                roots, "layer_metrics", m["name"], ".json"))
            reader = harness.load_module(harness.find_file(
                roots, "readers", spec["reader"], ".py"))
            value = reader.read(layer, spec.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if config.get("rehearsal"):
        line["rehearsal"] = True
    line["detail"] = {**res["correct_detail"], "counts_file": counts_file}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as e:
        print(f"benchmark: no result: {e}", file=sys.stderr)
        sys.exit(3)
