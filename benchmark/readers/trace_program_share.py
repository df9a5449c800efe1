"""Reader ``trace_program_share``: percent of the device's busy time spent
inside the executions of one compiled program.  args: ``program`` (regex on
the modules line).  Every execution counts, one the traced window's edge cut
with what is left of it (``trace_reduce.whole_executions`` keeps only the
longest, and a prefill chunk late in a prompt takes several times an early
one).  None where the trace has no device lane or no such program, so the
line leaves the metric out."""

import re

import trace_reduce


def read(ctx: dict, args: dict):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    rx = re.compile(args["program"])
    shares = []
    for dev in trace["devices"].values():
        busy_s, _ = trace_reduce.busy(dev["ops"])
        runs = [(s, s + d) for name, s, d in dev["modules"]
                if rx.search(name)]
        if busy_s <= 0 or not runs:
            continue
        inside = sum(trace_reduce.op_seconds(dev["ops"], within=runs).values())
        shares.append(100.0 * inside / busy_s)
    return sum(shares) / len(shares) if shares else None
