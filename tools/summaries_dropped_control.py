#!/usr/bin/env python3
"""Does the evabyte configuration's check see the chunk summaries?

    chiprun -- python tools/summaries_dropped_control.py CONFIG.json SEED [...]

The control that the fp8 control cannot stand in for: the configuration's own
programs and *sound* weights, with the summary term of every softmax left
out — a query attends the exact rows of its own window and nothing before it
(``ops.attention``: the merge of the two walks keeps the ring's alone, the
plain formulation's one softmax runs over the window's rows alone), which is
half the mathematics.  The check's requests go through the configuration's
engine (``benchmark/tools/control_served.serve_tokens``) and are scored as
every benchmark run's are (``reference/serve_check.py``'s scorer, the plain
float32 reference on this machine's CPU).  If the mean regret stays under the
configuration's ``mean_regret_limit`` the check does not see the summaries,
and its prompts are wrong: they must end past a window's end.  Prints a JSON
row a seed; exit 1 if a control passed.
"""

from __future__ import annotations

import sys

from state_dropped_control import run_control      # beside this file


def main(argv: list[str]) -> int:
    from distributedtensorflow_tpu.ops import attention

    merge, plain = attention.merge_softmax_parts, attention.softmax_over_parts
    # the window's rows come first in both: keep them, drop the summaries
    attention.merge_softmax_parts = lambda parts, dtype: merge(parts[:1],
                                                               dtype)
    attention.softmax_over_parts = lambda q, parts: plain(q, parts[:1])
    return run_control(argv, "summaries left out of every softmax")


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
