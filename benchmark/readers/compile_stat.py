"""Reader ``compile_stat``: from the child's ``compiles.jsonl`` (JAX's own
monitoring events, stamped by ``child.py``).  ``stat: "seconds"`` sums the
backend-compile durations of the whole run (set-up); ``stat:
"in_window"`` counts backend compilations that ended inside the window."""

import harness

EVENT = "backend_compile"


def read(ctx: dict, args: dict):
    rows = [r for r in harness.read_jsonl(ctx["compiles"])
            if EVENT in r.get("event", "")]
    if args["stat"] == "seconds":
        return sum(r.get("secs", 0.0) for r in rows)
    t0, t1 = ctx["window"]
    return float(sum(1 for r in rows if t0 <= r["t"] <= t1))
