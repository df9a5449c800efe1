"""Reader ``trace_scope_roofline``: share of its roofline that the operations
of one ``jax.named_scope`` of a program reach, for work that is no single
kernel (ling's chunked scan is plain ``jax.numpy`` under scope ``kda/scan``:
products, elementwise passes and the ``lax.scan`` that carries the state).

The device time is reader ``trace_scope``'s (self milliseconds of the
scope's operations per whole execution of the program), the requirement
reader ``trace_decode_kernel``'s: the counts module's ``decode_kernel(config,
required, lives, observed)`` at 40 instants of the traced interval, with
``observed`` the step log's means of the fields ``args["observed"]`` names
(for a prefill chunk: how many of its tokens were real).  Both files are read
as they are, by path.

args: ``program``, ``scope`` (as ``trace_scope``), ``required`` (the name
the counts module knows the work by), ``observed`` (step-log fields, default
none).  None where ``trace_scope`` reads nothing (no such program or scope,
as on a checkout that lacks it), the run has no traced interval or nothing
was decoding.
"""

import os
import statistics

import flops
import harness

HERE = os.path.dirname(os.path.abspath(__file__))


def read(ctx: dict, args: dict):
    done, counts = ctx.get("trace_done"), ctx["counts"]
    if not done or not hasattr(counts, "decode_kernel"):
        return None
    scope = harness.load_module(os.path.join(HERE, "trace_scope.py"))
    ms = scope.read(ctx, {"program": args["program"], "scope": args["scope"],
                          "stat": "ms"})
    if not ms:
        return None
    kernel = harness.load_module(os.path.join(HERE, "trace_decode_kernel.py"))
    observed = kernel.observed_means(ctx, args.get("observed", []),
                                     done["t_begin"], done["t_end"])
    a = done["t_begin"] - ctx["epoch_zero"]
    b = done["t_end"] - ctx["epoch_zero"]
    floors = []
    for i in range(40):
        lives = kernel.live_lengths(ctx, a + (b - a) * (i + 0.5) / 40)
        if lives:
            need = counts.decode_kernel(ctx["config"], args["required"],
                                        lives, observed)
            floors.append(flops.roofline_seconds(
                need["flops"], need["bytes"], ctx["device_kind"])["seconds"])
    if not floors:
        return None
    return 100.0 * statistics.fmean(floors) / (ms * 1e-3)
