"""The rehearsal cells reading this PR's per-layer metrics on the CPU:
``rehearsal/BENCHMARK-spans.json`` is the rehearsal manifest with the
metrics ISSUE 24 added in place of the old ones.  A CPU trace has no
device lane, so the trace readers leave their metrics out without raising;
the start-up metrics are read from the child's own ``trace.jsonl``.
Slow (each case starts the program): run by hand with the other
benchmark tests."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
MANIFEST = os.path.join(HERE, "rehearsal", "BENCHMARK-spans.json")


def _run(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if cell == "tiny-train-dp4":
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         MANIFEST, "--workload", cell, "--seed", "2400000017", "--seconds",
         "6", "--trace", "1"],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,present", [
    ("tiny-serve-chat", ["setup_backend_s.serve", "setup_init_params_s"]),
    ("tiny-train", ["setup_backend_s.train", "setup_first_step_s",
                    "setup_state_init_s", "setup_trainer_s"]),
])
def test_rehearsal_cell_reads_the_startup_metrics(cell, present):
    line = _run(cell)
    assert line["rehearsal"] is True and line["correct"] is True
    for name in present:
        assert line["metrics"][name]["value"] > 0, name
        assert line["metrics"][name]["unit"] == "s"
    # no device lane on the CPU: span and scope metrics are left out
    assert not [n for n in line["metrics"] if n not in present]


def test_every_new_metric_has_its_file_and_reader():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        root = {m["name"]: m for m in json.load(f)["per_layer"]}
    with open(MANIFEST) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    assert len(names) == len(set(names)) >= 15
    for name in names:
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] in ("trace_span", "trace_scope",
                                  "jsonl_quantile")
        assert os.path.exists(os.path.join(
            BENCH, "readers", spec["reader"] + ".py"))
        assert spec["layer"] == root[name]["layer"]
        assert spec["unit"] == root[name]["unit"]
