"""Reader ``trace_decode_scope``: ``trace_decode_kernel``'s share of a
roofline, with the device time taken by *scope* and not by a kernel's name.

For a formulation whose required bytes are moved by more than its kernel —
GLM-5's sparse attention gathers each query's selected rows with an XLA
fusion and hands them to the Pallas call ``sparse_latent_attn``; the kernel
alone reads rows the gather left close by, and read 117 % of the HBM
roofline (my chip run, PR 39) — the time that belongs under the requirement
is the whole scope's: the gather and the kernel.

args: ``program``, ``required``, ``observed`` as ``trace_decode_kernel``;
``scope`` (regex on the scope path, as ``trace_scope``).  None where either
of those readers would return None.
"""

import os
import statistics

import flops
import harness

_HERE = os.path.dirname(os.path.abspath(__file__))
trace_scope = harness.load_module(os.path.join(_HERE, "trace_scope.py"))
by_kernel = harness.load_module(os.path.join(_HERE, "trace_decode_kernel.py"))


def read(ctx: dict, args: dict):
    done, counts = ctx.get("trace_done"), ctx.get("counts")
    if not done or not hasattr(counts, "decode_kernel"):
        return None
    ms = trace_scope.read(ctx, {"program": args["program"],
                                "scope": args["scope"], "stat": "ms"})
    if not ms:
        return None
    a = done["t_begin"] - ctx["epoch_zero"]
    b = done["t_end"] - ctx["epoch_zero"]
    observed = by_kernel.observed_means(ctx, args.get("observed", []),
                                        done["t_begin"], done["t_end"])
    floors = []
    for i in range(40):
        lives = by_kernel.live_lengths(ctx, a + (b - a) * (i + 0.5) / 40)
        if lives:
            need = counts.decode_kernel(ctx["config"], args["required"],
                                        lives, observed)
            floors.append(flops.roofline_seconds(
                need["flops"], need["bytes"], ctx["device_kind"])["seconds"])
    if not floors:
        return None
    return 100.0 * statistics.fmean(floors) / (ms / 1e3)
