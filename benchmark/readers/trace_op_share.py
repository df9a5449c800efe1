"""Reader ``trace_op_share``: percent of device busy time spent in the
operation families that match.  args: ``pattern`` (regex on the family
name, e.g. ``^copy(-start|-done)?$``)."""

import re

import trace_reduce


def read(ctx: dict, args: dict):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    rx = re.compile(args["pattern"])
    shares = []
    for dev in trace["devices"].values():
        busy_s, _ = trace_reduce.busy(dev["ops"])
        if busy_s <= 0:
            continue
        hit = sum(s for fam, s in trace_reduce.op_seconds(dev["ops"]).items()
                  if rx.search(fam))
        shares.append(100.0 * hit / busy_s)
    return sum(shares) / len(shares) if shares else None
