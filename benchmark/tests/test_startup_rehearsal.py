"""The start-up and compile metrics of ISSUE 50 on the CPU:
``rehearsal/BENCHMARK-startup.json`` lists them over the accepted cells and
the two tiny ones (``serve.py --config gpt_tiny``, ``train.py --workload
gpt_lm --test-size``), with the outside ``compile_s`` and
``compiles_in_window`` beside them for the same run.  ``BENCHMARK.json``'s
``per_layer`` is full (128 of 128), so each metric is a file that this
manifest lists: ``run.py --manifest .../BENCHMARK-startup.json --workload
<cell> --trace 1`` reads them on the chip.  All of them read the program's
own rows (``startup.ready`` and its phases in ``trace.jsonl``, ``compile_s``
in ``steps.jsonl`` / ``metrics.jsonl``) through the reader that exists,
``jsonl_quantile``.
Slow (each case starts the program): run by hand with the other benchmark
tests."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
MANIFEST = os.path.join(HERE, "rehearsal", "BENCHMARK-startup.json")
COMPILE, START = "XLA + Mosaic compile", "start-up"
SERVE = {
    "setup_trace_s.serve": COMPILE, "setup_lower_s.serve": COMPILE,
    "setup_backend_compile_s.serve": COMPILE,
    "setup_cache_load_s.serve": COMPILE,
    "compile_in_window_ms.serve": COMPILE, "setup_ready_s.serve": START,
    "setup_first_request_s": START, "setup_engine_build_s": START}
TRAIN = {
    "setup_trace_s.train": COMPILE, "setup_lower_s.train": COMPILE,
    "setup_backend_compile_s.train": COMPILE,
    "setup_cache_load_s.train": COMPILE,
    "compile_in_window_ms.train": COMPILE, "setup_ready_s.train": START,
    "setup_trainer_tensorflow_import_s": START}
OUTSIDE = ["compile_s", "compiles_in_window"]


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _run(cell):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         MANIFEST, "--workload", cell, "--seed", "5000000017", "--seconds",
         "6", "--trace", "1"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,named,side", [
    ("tiny-serve-chat", SERVE, "serve"), ("tiny-train", TRAIN, "train")])
def test_tiny_cell_reads_every_new_file(cell, named, side):
    line = _run(cell)
    assert line["rehearsal"] is True and line["correct"] is True
    got = line["metrics"]
    # membership, not position: other manifests' metrics may join the line
    for name in list(named) + OUTSIDE:
        assert name in got and got[name]["value"] is not None, name
    for name in named:
        unit = "ms" if name.startswith("compile_in_window") else "s"
        assert got[name]["unit"] == unit
        assert got[name]["value"] >= 0
    ready = got[f"setup_ready_s.{side}"]["value"]
    compile_sums = [got[f"setup_{p}_s.{side}"]["value"] for p in (
        "trace", "lower", "backend_compile")]
    assert all(v > 0 for v in compile_sums) and sum(compile_sums) < ready
    assert got[f"setup_cache_load_s.{side}"]["value"] <= compile_sums[2]
    # nothing compiles inside the window, by the program's own account as
    # by the child's listener
    assert got[f"compile_in_window_ms.{side}"]["value"] == 0.0
    assert got["compiles_in_window"]["value"] == 0.0
    if side == "serve":
        # the child's listener and the program's count the same programs
        assert compile_sums[2] == pytest.approx(
            got["compile_s"]["value"], rel=0.02)
        assert got["setup_first_request_s"]["value"] < ready
    else:
        # (the benchmark's preflight compiles before train.py starts: the
        # outside number holds its programs too)
        assert compile_sums[2] <= got["compile_s"]["value"] + 1e-6


def test_every_new_metric_is_a_file_of_the_reader_that_exists():
    manifest = _json(MANIFEST)
    root = _json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in root["workloads"]}
    listed = {m["name"]: m for m in manifest["per_layer"]}
    assert set(SERVE) | set(TRAIN) | set(OUTSIDE) == set(listed)
    serve_cells = sorted(c for c in cells if "serve" in c)
    train_cells = sorted(c for c in cells if "train" in c)
    assert (len(serve_cells), len(train_cells)) == (9, 2)
    for name, layer in {**SERVE, **TRAIN}.items():
        spec = _json(BENCH, "layer_metrics", name + ".json")
        entry = listed[name]
        assert spec["reader"] == "jsonl_quantile"       # no new reader
        assert spec["layer"] == entry["layer"] == layer
        assert spec["unit"] == entry["unit"]
        assert spec["moves"] == entry["moves"] == "setup_s"
        want = serve_cells if name in SERVE else train_cells
        assert sorted(spec["workloads"]) == want
        tiny = "tiny-serve-chat" if name in SERVE else "tiny-train"
        assert sorted(entry["workloads"]) == sorted(want + [tiny])
        side = "serve" if name in SERVE else "train"
        assert spec["args"]["file"].startswith(side + "/")
    # the accepted cells are the accepted benchmark's, entry for entry
    for w in root["workloads"]:
        assert w in manifest["workloads"]
    for c in root["configs"]:
        assert c in manifest["configs"]
    for name in OUTSIDE:
        assert listed[name] == next(
            m for m in root["per_layer"] if m["name"] == name)
