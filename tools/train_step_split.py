#!/usr/bin/env python3
"""Where a traced training step's device time goes: self milliseconds a
whole ``jit_step`` by block part (the scopes ``train_attn_ms`` /
``train_mlp_ms`` read) and operation family, and one layer's operations in
the order they ran.

    python tools/train_step_split.py bench_out/gpt2m-train-1chip/trace
        [--layer h5] [--root .]

Reads the ``.xplane.pb`` a ``benchmark/run.py --trace 1`` run of a training
cell leaves under ``bench_out/<cell>/trace`` with the benchmark's own
loaders (``--root``: the checkout whose ``benchmark/`` to use), so the
numbers are the ones its readers see.  A family here is the operation's OWN
name (``flash_bwd.42`` -> ``flash_bwd``): ``flash_roofline_pct``'s reader
searches its pattern in the whole HLO line, operands included, so it also
counts the two products that take ``%flash_bwd.N`` as an operand
(``PERF.md`` section 7, PR 35).  No chip needed: it reads a file.
"""

from __future__ import annotations

import argparse
import collections
import os
import re
import sys

PARTS = (("attn", r"/(attn|ln1)(/|$)"), ("mlp", r"/(fc_in|fc_out|ln2)(/|$)"))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("trace_dir")
    p.add_argument("--layer", default="h5")
    p.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    args = p.parse_args()
    bench = os.path.join(args.root, "benchmark")
    sys.path[:0] = [bench, os.path.join(bench, "readers")]
    import trace_reduce
    import trace_scope

    path = trace_reduce.find_xplane(args.trace_dir)
    if not path:
        print(f"no .xplane.pb under {args.trace_dir}", file=sys.stderr)
        return 1
    scoped = trace_scope.load(path)
    steps = trace_reduce.whole_executions(scoped["modules"], "^jit_step")
    if not steps:
        print("no whole jit_step in the trace", file=sys.stderr)
        return 1
    print(f"{len(steps)} whole steps of "
          f"{[round(1e3 * (b - a), 1) for a, b in steps]} ms")
    inside = [op for op in scoped["ops"]
              if any(a <= op[1] < b for a, b in steps)]
    paths = {(name, start): scope or "" for name, start, _, scope in inside}

    def part(scope: str) -> str:
        return next((n for n, rx in PARTS if re.search(rx, scope)), "other")

    split: dict = collections.defaultdict(float)
    calls: collections.Counter = collections.Counter()
    for name, start, _, own in trace_reduce.self_times(
            [op[:3] for op in inside]):
        key = (part(paths[(name, start)]), trace_reduce.family(name))
        split[key] += own
        calls[key] += 1
    n = len(steps)
    totals: dict = collections.defaultdict(float)
    for (where, _), seconds in split.items():
        totals[where] += seconds
    print("ms a step:", {k: round(1e3 * v / n, 1) for k, v in totals.items()})
    for key, seconds in sorted(split.items(), key=lambda kv: -kv[1])[:24]:
        print(f"{key[0]:6s} {key[1]:34s} {1e3 * seconds / n:9.2f} ms a step"
              f"  x{calls[key] / n:.0f}")

    print(f"--- layer {args.layer}, first whole step, in order "
          "(ms from its start, ms)")
    a, b = steps[0]
    plane = trace_scope.device_plane(path)
    meta = plane["metadata"]
    for m, start, dur in plane["lines"].get(trace_reduce.OPS_LINE, []):
        raw = str(meta[m]["stats"].get(trace_scope.PATH_STAT) or "")
        if a <= start < b and f"/{args.layer}/" in raw and dur > 2e-4:
            line = meta[m]["name"]
            print(f"{1e3 * (start - a):9.2f} {1e3 * dur:8.3f}  "
                  f"{line.split(' = ')[0][:32]:32s} "
                  f"{trace_scope.scope_path(raw)[-56:]:56s} "
                  f"{line.split(' = ')[-1][:100]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
