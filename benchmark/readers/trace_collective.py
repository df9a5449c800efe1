"""Reader ``trace_collective``: milliseconds per step the first device
spends in collective operations, and with ``exposed: true`` only the part
during which no other operation runs on that device.  args: ``pattern``
(collective families), ``step_pattern``, ``exposed``."""

import re

import trace_reduce


def read(ctx: dict, args: dict):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    dev = trace["devices"][sorted(trace["devices"])[0]]
    steps = trace_reduce.whole_executions(dev["modules"],
                                          args["step_pattern"])
    if not steps:
        return None
    rx = re.compile(args["pattern"])
    inside = [e for e in dev["ops"]
              if any(a <= e[1] < b for a, b in steps)]
    coll = [e for e in inside if rx.search(trace_reduce.family(e[0]))]
    if not args.get("exposed"):
        total = sum(d for _, _, d in coll)
    else:
        # leaf operations only: a wrapper such as `while` spans everything
        leaves = [(n, s, d) for n, s, d, own in trace_reduce.self_times(inside)
                  if own >= 0.999 * d]
        other = trace_reduce.merge([(s, s + d) for n, s, d in leaves
                                    if not rx.search(trace_reduce.family(n))])
        total = 0.0
        for a, b in trace_reduce.merge([(s, s + d) for _, s, d in coll]):
            covered = sum(max(0.0, min(b, y) - max(a, x)) for x, y in other)
            total += (b - a) - covered
    return 1e3 * total / len(steps)
