#!/usr/bin/env python3
"""What a traced run's profile holds of the program's own names, and a
small fixture cut from it.

    python benchmark/tools/trace_check.py bench_out/<cell> --out DIR \\
        [--program '^jit_decode'] [--cut START_S DUR_S]

After ``run.py --trace 1`` has left ``bench_out/<cell>``, this reads the
``.xplane.pb`` once and writes ``DIR/trace_check.json``:

- the planes and lines, the stat names the device operations' metadata
  carries and how often (the scope reader takes an operation's scope path
  from one of them), and every operation of one whole execution of
  ``--program`` with those stats: the evidence for the scope patterns in
  ``layer_metrics/``;
- the host events the program's spans left (``engine.*``, the trainer's
  ``data_wait`` / ``train_step`` / ``host_block``): count and mean per
  name, and how many ``engine.step`` spans carry a ``step`` attribute that
  is a ``step`` of ``serve/steps.jsonl``; of the device's idle seconds, the
  percent inside the spans of each name;
- the ``startup.*`` rows of the run's ``trace.jsonl``: their order, the
  interval they tile and the seconds of it that no row names.

``--cut`` also writes ``DIR/slice.json.gz``: the plain structure of
``trace_reduce.load_xplane`` restricted to that stretch of the traced
window (seconds from its start), the first device only, spans only on the
host side, and each operation with a fourth field, its scope path as
``readers/trace_scope.py`` loads it: the fixture of the reader tests.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import re
import statistics
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "readers"))

import harness  # noqa: E402
import trace_reduce  # noqa: E402
import trace_scope  # noqa: E402

SPAN = re.compile(r"^(engine\.|startup\.|data_wait$|train_step$|"
                  r"host_block$|eval$|profile_capture$)")


def _stats(event) -> dict:
    out = {}
    for key, value in event.stats:
        out[key] = value if isinstance(value, (int, float)) else str(value)
    return out


def profile_facts(path: str, program: str) -> dict:
    from jax.profiler import ProfileData   # reads a file; no backend

    data = ProfileData.from_file(path)
    facts: dict = {"planes": [], "spans": {}, "span_samples": []}
    step_attrs = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append([line.name, sum(1 for _ in line.events)])
        facts["planes"].append({"plane": plane.name, "lines": lines[:40]})
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if not SPAN.match(e.name):
                        continue
                    facts["spans"].setdefault(e.name, []).append(
                        e.duration_ns * 1e-9)
                    stats = _stats(e)
                    if e.name == "engine.step" and "step" in stats:
                        step_attrs.append(int(stats["step"]))
                    if len(facts["span_samples"]) < 40:
                        facts["span_samples"].append(
                            [line.name, e.name, e.start_ns * 1e-9,
                             e.duration_ns * 1e-9, stats])
    facts.update(device_facts(path, program))
    facts["spans"] = {
        name: {"n": len(d), "mean_ms": 1e3 * statistics.fmean(d),
               "max_ms": 1e3 * max(d)}
        for name, d in sorted(facts["spans"].items())}
    facts["engine_step_attrs"] = step_attrs
    return facts


def device_facts(path: str, program: str) -> dict:
    """What the first device plane's operations carry once per operation
    (the event metadata, which ``ProfileData`` does not hand out), and
    every operation of one whole execution of ``program`` with it."""
    plane = trace_scope.device_plane(path)
    if plane is None:
        return {}
    meta = plane["metadata"]
    ops = plane["lines"].get(trace_reduce.OPS_LINE, [])
    modules = plane["lines"].get(trace_reduce.MODULES_LINE, [])
    keys: dict[str, int] = {}
    for m, _, _ in ops:
        for key in meta[m]["stats"]:
            keys[key] = keys.get(key, 0) + 1
    facts = {"op_metadata_stat_keys": keys,
             "module_samples": [[meta[m]["name"], s, d, meta[m]["stats"]]
                                for m, s, d in modules[:12]]}
    whole = trace_reduce.whole_executions(
        [[meta[m]["name"], s, d] for m, s, d in modules], program)
    if whole:
        a, b = whole[len(whole) // 2]
        facts["one_execution"] = {
            "program": program, "start": a, "seconds": b - a,
            "ops": [[meta[m]["name"][:160], round(s - a, 9), d,
                     {k: (v[:300] if isinstance(v, str) else v)
                      for k, v in meta[m]["stats"].items()}]
                    for m, s, d in ops if a <= s < b][:8000]}
    return facts


def idle_by_span(trace: dict) -> dict:
    """Of the first device's idle seconds in the traced window, the
    percent inside the whole spans of each name (``trace_span``'s own
    arithmetic, one name at a time): which phase the chip waits in."""
    import trace_span

    out = {}
    names = {n for events in trace["host"].values() for n, _, _ in events
             if SPAN.match(n)}
    for name in sorted(names):
        outside = trace_span.read({"trace": trace}, {
            "span": re.escape(name), "stat": "idle_outside_pct"})
        if outside is not None:
            out[name] = round(100.0 - outside, 3)
    return out


def startup_facts(rows: list[dict]) -> dict:
    spans = [r for r in rows if r.get("kind") == "span"
             and str(r.get("name", "")).startswith("startup.")]
    top = [r for r in spans if "parent_id" not in r]
    if not top:
        return {"rows": []}
    t0 = min(r["t0"] for r in top)
    t1 = max(r["t0"] + r["dur_s"] for r in top)
    named = sum(b - a for a, b in trace_reduce.merge(
        [(r["t0"], r["t0"] + r["dur_s"]) for r in top]))
    return {"rows": [[r["name"], round(r["t0"] - t0, 3), r["dur_s"],
                      "child" if "parent_id" in r else "top"]
                     for r in spans],
            "interval_s": t1 - t0, "unnamed_s": (t1 - t0) - named}


def cut(trace: dict, scoped: dict, start: float, dur: float) -> dict:
    w0 = trace_reduce.window_of(trace)[0] + start
    w1 = w0 + dur

    def keep(events):
        return [[e[0], round(e[1] - w0, 9), round(e[2], 9), *e[3:]]
                for e in events if w0 <= e[1] and e[1] + e[2] <= w1]

    return {
        "recorded": "cut by benchmark/tools/trace_check.py",
        # each operation once, with its scope path as a fourth field
        "devices": {sorted(trace["devices"])[0]: {
            "ops": keep(scoped["ops"]), "modules": keep(scoped["modules"])}},
        "host": {thread: kept for thread, events in trace["host"].items()
                 if (kept := keep([e for e in events if SPAN.match(e[0])]))},
    }


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("cell_out")
    p.add_argument("--out", required=True)
    p.add_argument("--program", default="^jit_decode")
    p.add_argument("--cut", nargs=2, type=float, default=None)
    args = p.parse_args()
    os.makedirs(args.out, exist_ok=True)
    report: dict = {}
    for sub in ("serve", "train"):
        rows = harness.read_jsonl(
            os.path.join(args.cell_out, sub, "trace.jsonl"))
        if rows:
            report["startup"] = startup_facts(rows)
    path = trace_reduce.find_xplane(os.path.join(args.cell_out, "trace"))
    if path:
        report.update(profile_facts(path, args.program))
        steps = {r["step"] for r in harness.read_jsonl(
            os.path.join(args.cell_out, "serve", "steps.jsonl"))}
        attrs = report.pop("engine_step_attrs")
        report["engine_step_join"] = {
            "spans_with_step": len(attrs),
            "matching_a_steps_jsonl_row": sum(a in steps for a in attrs)}
        trace = trace_reduce.load_xplane(path)
        if trace["devices"]:
            report["idle_pct_inside_span"] = idle_by_span(trace)
        if args.cut:
            piece = cut(trace, trace_scope.load(path), *args.cut)
            with gzip.open(os.path.join(args.out, "slice.json.gz"),
                           "wt") as f:
                json.dump(piece, f)
    with open(os.path.join(args.out, "trace_check.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({k: report[k] for k in (
        "startup", "spans", "idle_pct_inside_span",
        "op_metadata_stat_keys", "engine_step_join")
        if k in report})[:6000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
