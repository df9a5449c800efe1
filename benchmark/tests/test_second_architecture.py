"""The harness takes a second architecture by files alone.

``rehearsal/BENCHMARK-second.json`` has a grouped-query decoder whose
reference, operation counts and on-chip check exist only under the
rehearsal root; a train and a serve cell of it run through ``run.py
--manifest`` on the CPU and say in ``detail`` which modules they used.
And the files a later PR may not edit name no architecture.  Slow (each
cell starts the program): run by hand with the other benchmark tests."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REHEARSAL = os.path.join(HERE, "rehearsal")
MANIFEST = os.path.join(REHEARSAL, "BENCHMARK-second.json")


def _run(cell):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         MANIFEST, "--workload", cell, "--seed", "2700000011", "--seconds",
         "6", "--trace", "0"], capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _under_rehearsal(path, sub, name):
    return os.path.samefile(path, os.path.join(REHEARSAL, sub, name))


def test_the_named_modules_exist_only_under_the_rehearsal_root():
    for sub, name in (("reference", "gqa_decoder.py"),
                      ("counts", "gqa_decoder.py"),
                      ("checks", "gqa_loss.py")):
        assert os.path.exists(os.path.join(REHEARSAL, sub, name))
        assert not os.path.exists(os.path.join(BENCH, sub, name))


def test_train_cell_of_a_second_architecture_uses_its_own_modules():
    line = _run("gqa-train")
    assert line["rehearsal"] is True and line["correct"] is True
    detail = line["detail"]
    pre = detail["preflight"]
    assert pre["ok"] and pre["check"] == "gqa_loss" and pre["kv_heads"] == 2
    assert _under_rehearsal(pre["check_file"], "checks", "gqa_loss.py")
    assert _under_rehearsal(pre["reference_file"], "reference",
                            "gqa_decoder.py")
    assert _under_rehearsal(detail["counts_file"], "counts",
                            "gqa_decoder.py")
    assert line["metrics"]["train_mfu"]["value"] > 0


def test_serve_cell_of_a_second_architecture_uses_its_own_modules():
    line = _run("gqa-serve-chat")
    assert line["rehearsal"] is True and line["correct"] is True
    detail = line["detail"]
    assert detail["positions_checked"] == 64
    assert detail["mean_regret"] <= detail["mean_regret_limit"]
    assert _under_rehearsal(detail["reference_file"], "reference",
                            "gqa_decoder.py")
    assert _under_rehearsal(detail["counts_file"], "counts",
                            "gqa_decoder.py")


def _fixed_files():
    names = ["run.py", "harness.py", "child.py", "preflight.py", "flops.py",
             os.path.join("reference", "serve_check.py")]
    for sub in ("traffic_kinds", "readers"):
        names += [os.path.join(sub, fn) for fn in sorted(os.listdir(
            os.path.join(BENCH, sub))) if fn.endswith(".py")]
    return names


@pytest.mark.parametrize("rel", _fixed_files())
def test_harness_file_names_no_architecture(rel):
    with open(os.path.join(BENCH, rel)) as f:
        text = f.read()
    for word in ("gpt2", "GPTLM", "n_layer", "n_embd", "n_head"):
        assert word not in text, (rel, word)
