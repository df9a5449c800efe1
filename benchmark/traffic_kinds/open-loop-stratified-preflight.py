"""Traffic kind ``open-loop-stratified-preflight``: kind
``open-loop-stratified`` (its schedule, its phases, its window, its check of
the served tokens: that file, run as it is) behind the configuration's
on-device check, ``correctness.preflight`` (``preflight.py``: ``checks/
<name>.py``'s ``run(spec, reference)`` on the chip), as a training cell has
one.

For a configuration whose stated precision the served tokens cannot show: a
number that only float32 values on the device give (a state a slot keeps
between programs, read beside the reference's on the same inputs) takes the
programs in one process with the reference, and the server returns tokens.
The check runs in a process of its own that holds the chip *before* the
server does and exits (one process a chip); its seconds are set-up, and the
server's process, window and memory peak are those of kind
``open-loop-stratified``.  ``correct`` is that kind's and the check's
``ok``; the check's row is ``detail.preflight``.

    python open-loop-stratified-preflight.py SPEC.json OUT.json

is that process: ``preflight.run`` on the spec the parent wrote.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.dirname(BENCH))

import harness  # noqa: E402
import preflight  # noqa: E402
from harness import BenchError  # noqa: E402

#: the check's process may take this long (a cold compile of both programs
#: at the published widths is most of it)
CHECK_TIMEOUT_S = 1200


def run_check(ctx: dict) -> dict:
    """The configuration's preflight check in a process of its own, with the
    child's environment (the chip, the compile cache the server then finds)
    and its cores."""
    out, seed = ctx["out"], ctx["seed"] % (2 ** 31 - 1)
    spec_path = os.path.join(out, "preflight_spec.json")
    done_path = os.path.join(out, "preflight.json")
    with open(spec_path, "w") as f:
        json.dump(preflight.spec_for(ctx["config"], ctx["roots"], seed), f)
    with open(os.path.join(out, "preflight.log"), "w") as log:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), spec_path, done_path],
            cwd=harness.ROOT, env=harness.child_env(out), stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True)
        os.sched_setaffinity(proc.pid, harness.split_cpus()[1])
        try:
            code = proc.wait(timeout=CHECK_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            code = None
        finally:
            if proc.poll() is None:
                os.killpg(proc.pid, 9)
                proc.wait()
    if code != 0 or not os.path.exists(done_path):
        with open(os.path.join(out, "preflight.log"), errors="replace") as f:
            tail = "".join(f.readlines()[-30:])
        raise BenchError(f"the preflight check exited with {code}\n{tail}")
    return harness.load_json(done_path)


def run(ctx: dict) -> dict:
    base = harness.load_module(harness.find_file(
        ctx["roots"], "traffic_kinds", "open-loop-stratified", ".py"))
    pre = run_check(ctx)
    harness.require_device(pre["device"], ctx["config"], ctx["chips"])
    res = base.run(ctx)
    return {**res, "correct": bool(res["correct"] and pre["ok"]),
            "correct_detail": {**res["correct_detail"], "preflight": pre}}


def main(spec_path: str, done_path: str) -> None:
    import jax

    out = preflight.run(harness.load_json(spec_path))
    first = jax.devices()[0]
    out["device"] = {"platform": first.platform, "kind": first.device_kind,
                     "count": jax.device_count()}
    tmp = done_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, done_path)


if __name__ == "__main__":
    main(*sys.argv[1:3])
