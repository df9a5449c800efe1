"""Reader ``trace_span``: the program's own spans against the device lane.

``obs.tracing.span`` enters a ``jax.profiler.TraceAnnotation`` of the
same name, so a traced run holds the program's phases as host events on
the profiler's clock, beside the device operations.  This reader takes the
host events whose name matches ``span`` (a regex, matched whole) on any
host thread, keeps the ones that lie wholly inside the traced window
(first device operation's start to the last one's end), and intersects
them with the union of the first device's operation intervals.

args: ``span``; ``stat``:

- ``wall_ms``: mean duration of a span;
- ``device_ms``: mean device-busy time inside a span (union of operation
  intervals ∩ span: whatever ran on the device while the span was open);
- ``host_ms``: mean of wall − device, the time of a span during which the
  device did nothing;
- ``idle_outside_pct``: of the device's idle seconds in the traced window,
  the share that lies outside every matching span (with the leaf spans of
  an iteration as the pattern: idle time the program has no name for).

None where the trace holds no such span (a program without the spans), so
the line leaves the metric out.
"""

import bisect
import re
import statistics

import trace_reduce


def _overlap(merged: list, starts: list, a: float, b: float) -> float:
    """Seconds of ``[a, b)`` the sorted disjoint ``merged`` covers."""
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    total = 0.0
    while i < len(merged) and merged[i][0] < b:
        total += max(0.0, min(b, merged[i][1]) - max(a, merged[i][0]))
        i += 1
    return total


def read(ctx: dict, args: dict):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    w0, w1 = trace_reduce.window_of(trace)
    rx = re.compile(args["span"])
    spans = [(s, s + d) for events in trace["host"].values()
             for n, s, d in events
             if rx.fullmatch(n) and s >= w0 and s + d <= w1]
    if not spans:
        return None
    dev = trace["devices"][sorted(trace["devices"])[0]]
    _, merged = trace_reduce.busy(dev["ops"])
    stat = args["stat"]
    if stat == "idle_outside_pct":
        gaps = trace_reduce.idle_gaps(merged, (w0, w1))
        idle = sum(b - a for a, b in gaps)
        if idle <= 0:
            return None
        named = trace_reduce.merge(spans)
        starts = [s for s, _ in named]
        inside = sum(_overlap(named, starts, a, b) for a, b in gaps)
        return 100.0 * (idle - inside) / idle
    starts = [s for s, _ in merged]
    device = [_overlap(merged, starts, a, b) for a, b in spans]
    values = {"wall_ms": [b - a for a, b in spans],
              "device_ms": device,
              "host_ms": [(b - a) - d for (a, b), d in zip(spans, device)]}
    return 1e3 * statistics.fmean(values[stat])
