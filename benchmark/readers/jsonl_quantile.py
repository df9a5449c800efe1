"""Reader ``jsonl_quantile``: a statistic of one field over the rows of a
JSON-lines file that fall inside the window.

args: ``file`` (relative to the run's output directory), ``field``,
``stat`` (``mean``, ``median``, ``max`` or ``pNN``), ``scale`` (default 1),
``positive`` (a field that must be > 0 for a row to count), ``divide_by``
(a field to divide each row's value by), ``equals`` (``{field: value}``),
``time_field`` (default ``t``; rows outside the window are left out).
"""

import os
import statistics

import harness
import window


def read(ctx: dict, args: dict):
    rows = harness.read_jsonl(os.path.join(ctx["out"], args["file"]))
    t0, t1 = ctx["window"]
    tf = args.get("time_field", "t")
    values = []
    for r in rows:
        if tf in r and not t0 <= r[tf] <= t1:
            continue
        if args.get("positive") and not r.get(args["positive"], 0) > 0:
            continue
        if any(r.get(k) != v for k, v in args.get("equals", {}).items()):
            continue
        v = r.get(args["field"])
        if not isinstance(v, (int, float)):
            continue
        if args.get("divide_by"):
            v = v / r[args["divide_by"]]
        values.append(v * args.get("scale", 1.0))
    if not values:
        return None
    stat = args.get("stat", "mean")
    if stat == "mean":
        return statistics.fmean(values)
    if stat == "median":
        return statistics.median(values)
    if stat == "max":
        return max(values)
    return window.percentile(values, float(stat[1:]))
