"""Reader ``trace_roofline``: least time the chip could take (from shapes,
by the configuration's counts module, and the published peaks) over the
device time the trace shows, in percent.

``mode: "train_kernel"``: ``pattern`` names the kernel's events;
``required`` names the kernel family whose operations and bytes a step
the counts module gives (``step_kernel(config, required)``).  Only whole
executions of the step program (``step_pattern`` on the modules line of
the first device) count, and the requirement is per step, so recomputation
under remat lowers the share as it should.

``mode: "decode"``: bytes one decode iteration must read (weights once in
the compute type, plus the K/V of every live token, the mean over the
traced interval from the client's token log) over the mean device time of
the decode program (``pattern`` on the modules line).
"""

import re
import statistics

import flops
import trace_reduce


def _first_device(trace: dict) -> dict:
    return trace["devices"][sorted(trace["devices"])[0]]


def _live_tokens(ctx: dict) -> float | None:
    done = ctx.get("trace_done")
    if not done:
        return None
    a = done["t_begin"] - ctx["epoch_zero"]
    b = done["t_end"] - ctx["epoch_zero"]
    samples = []
    for i in range(40):
        t = a + (b - a) * (i + 0.5) / 40
        live = 0
        for r in ctx["logs"]:
            times = r["token_times"]
            if not times or times[0] > t:
                continue
            n = sum(c for tt, c in zip(times, r["token_counts"]) if tt <= t)
            if n >= r["max_new_tokens"]:
                continue
            live += r["prompt_tokens"] + n
        samples.append(live)
    return statistics.fmean(samples)


def read(ctx: dict, args: dict):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    dev = _first_device(trace)
    config, counts = ctx["config"], ctx["counts"]
    kind = ctx["device_kind"]
    if args["mode"] == "decode":
        rx = re.compile(args["pattern"])
        durs = [d for n, _, d in dev["modules"] if rx.search(n)]
        live = _live_tokens(ctx)
        if not durs or live is None:
            return None
        need = counts.decode_iter_bytes(
            config, live, config["compute_dtype_bytes"])
        floor = need / flops.peaks(kind)["hbm_bytes_per_s"]
        return 100.0 * floor / statistics.fmean(durs)
    steps = trace_reduce.whole_executions(dev["modules"],
                                          args["step_pattern"])
    if not steps:
        return None
    seconds, _ = trace_reduce.matching_seconds(
        dev["ops"], args["pattern"], within=steps)
    if seconds <= 0:
        return None
    need = counts.step_kernel(config, args["required"])
    floor = flops.roofline_seconds(need["flops"], need["bytes"],
                                   kind)["seconds"]
    return 100.0 * floor * len(steps) / seconds
