"""Reader ``trace_decode_kernel``: share of its roofline that a kernel of
the decode program reaches, with the requirement taken from the lengths of
the sequences that were really decoding.

Reader ``trace_roofline`` hands a counts module the *sum* of the live
tokens; a model with window layers and routed experts needs each sequence's
length (a window caps a sequence, not the sum) and the number of sequences
(which decides how many experts are hit).  This reader samples the client's
token log at 40 instants of the traced interval, asks the counts module at
each — ``decode_kernel(config, required, lives, observed)`` -> ``{"flops",
"bytes"}`` — and divides the mean roofline time by the traced device time
of the kernel per whole execution of the program.  ``observed`` holds the
means, over the step-log rows of the traced interval that decoded, of the
fields ``args["observed"]`` names (the routing counters: which experts a
batch needed is read off the program, not assumed).

args: ``program`` (regex on the modules line), ``required`` (the kernel
family's name in the counts module), ``pattern`` (regex on the operation
names of the kernel; left out, the whole program's time is taken),
``observed`` (step-log fields, default none).  None
where the trace has no such program or kernel, or the counts module no
``decode_kernel``.
"""

import os
import statistics

import flops
import harness
import trace_reduce


def observed_means(ctx: dict, fields: list, t0: float, t1: float) -> dict:
    """Mean of each of ``fields`` over the rows of ``serve/steps.jsonl``
    stamped in ``[t0, t1]`` (epoch seconds) that carry it."""
    rows = harness.read_jsonl(os.path.join(ctx["out"], "serve", "steps.jsonl"))
    out = {}
    for field in fields:
        vals = [r[field] for r in rows
                if t0 <= r.get("t", 0) <= t1 and field in r]
        if vals:
            out[field] = statistics.fmean(vals)
    return out


def live_lengths(ctx: dict, t: float) -> list[int]:
    """Tokens resident at ``t`` (seconds from the window's opening) of
    every request that has its first token and not yet its last."""
    lives = []
    for r in ctx["logs"]:
        times = r["token_times"]
        if not times or times[0] > t:
            continue
        n = sum(c for tt, c in zip(times, r["token_counts"]) if tt <= t)
        if n < r["max_new_tokens"]:
            lives.append(r["prompt_tokens"] + n)
    return lives


def read(ctx: dict, args: dict):
    trace, done = ctx.get("trace"), ctx.get("trace_done")
    counts = ctx["counts"]
    if not trace or not trace["devices"] or not done \
            or not hasattr(counts, "decode_kernel"):
        return None
    dev = trace["devices"][sorted(trace["devices"])[0]]
    runs = trace_reduce.whole_executions(dev["modules"], args["program"])
    if not runs:
        return None
    if args.get("pattern"):
        seconds, _ = trace_reduce.matching_seconds(
            dev["ops"], args["pattern"], within=runs)
    else:
        seconds = sum(e - s for s, e in runs)
    if seconds <= 0:
        return None
    a = done["t_begin"] - ctx["epoch_zero"]
    b = done["t_end"] - ctx["epoch_zero"]
    observed = observed_means(ctx, args.get("observed", []),
                              done["t_begin"], done["t_end"])
    floors = []
    for i in range(40):
        lives = live_lengths(ctx, a + (b - a) * (i + 0.5) / 40)
        if lives:
            need = counts.decode_kernel(ctx["config"], args["required"],
                                        lives, observed)
            floors.append(flops.roofline_seconds(
                need["flops"], need["bytes"], ctx["device_kind"])["seconds"])
    if not floors:
        return None
    return 100.0 * statistics.fmean(floors) * len(runs) / seconds
