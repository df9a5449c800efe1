"""The check that runs in the child, on the device, before the entry point.

The configuration names it (``correctness.preflight.check`` ->
``checks/<name>.py``) and its reference (``"reference"`` ->
``reference/<name>.py``); the parent finds both files and hands their
paths over in the spec, with the configuration itself and the seed.  A
check file offers ``run(spec, reference) -> dict`` with at least ``ok``.
This file knows no architecture and no kind of batch.
"""

from __future__ import annotations

import time

import harness


def spec_for(config: dict, roots: list[str], seed: int) -> dict:
    """What the parent hands the child: the configuration's check with the
    files it names found under ``roots``."""
    check = config["correctness"]["preflight"]
    return {**check, "seed": seed, "config": config,
            "check_file": harness.find_file(
                roots, "checks", check["check"], ".py"),
            "reference_file": harness.find_file(
                roots, "reference", config["reference"], ".py")}


def run(spec: dict) -> dict:
    check = harness.load_module(spec["check_file"])
    reference = harness.load_module(spec["reference_file"])
    t0 = time.time()
    out = check.run(spec, reference)
    return {**out, "check": spec["check"], "check_file": check.__file__,
            "reference_file": reference.__file__,
            "seconds": time.time() - t0}
