"""The host metrics of ISSUE 36, as files: ``rehearsal/BENCHMARK-host.json``
lists the nine a serving cell (``BENCHMARK.json``'s ``per_layer`` is at its
limit of 128, so none is listed there) beside the accepted metrics of the
same stretch, over the five accepted serving cells (so that
``--manifest benchmark/tests/rehearsal/BENCHMARK-host.json`` reads them on
the chip) and a tiny one.  The tiny cell runs ``serve.py --config gpt_tiny``
on the CPU through the harness and every step-log metric has to come out of
it; a CPU trace has no device lane, so ``idle_unnamed_pct`` is read off the
slices recorded on the chip under ``data/`` (their spans are the parent's:
no ``engine.loop``, which the pattern allows).  Slow (the first case starts
the program): run by hand with the other benchmark tests."""

import gzip
import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
MANIFEST = os.path.join(HERE, "rehearsal", "BENCHMARK-host.json")
CELLS = {"steady": "gpt2m-serve-chat-steady",
         "sat": "gpt2m-serve-chat-saturated",
         "trinity": "trinity-ep8-serve-reason-saturated",
         "joyai": "joyai-flash-serve-longdoc-saturated",
         # `.jamba2`: test_jamba_rehearsal.py holds the files that end in
         # `.jamba.json` to PR 34's closed list, and no file of the
         # benchmark that exists may be edited
         "jamba2": "jamba2-3b-serve-longdoc-saturated"}
STEP_LOG = ["decode_dispatch_ms", "decode_fetch_ms", "step_between_ms",
            "engine_offcpu_ms", "decode_commit_cpu_ms", "step_unnamed_pct",
            "step_wall_max_ms", "stream_lag_p95_ms"]
NEW = STEP_LOG + ["idle_unnamed_pct"]
ALL = [f"{name}.{cell}" for name in NEW for cell in CELLS]


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _reader(name):
    path = os.path.join(BENCH, "readers", name + ".py")
    spec = importlib.util.spec_from_file_location("host_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tiny_cell_reads_every_step_log_metric_through_the_harness():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         MANIFEST, "--workload", "tiny-serve-chat", "--seed", "3600000011",
         "--seconds", "6", "--trace", "1"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    metrics = line["metrics"]
    for name in STEP_LOG:       # a metric that reads None is left out
        assert metrics[f"{name}.steady"]["value"] is not None, name
    unit = {m["name"]: m["unit"] for m in _json(MANIFEST)["per_layer"]}
    assert all(v["unit"] == unit[k] for k, v in metrics.items())
    # no device lane on the CPU: the trace readers leave theirs out
    assert not [n for n in metrics if n.startswith(("idle_", "decode_span"))]
    assert metrics["step_unnamed_pct.steady"]["value"] < 2
    assert metrics["decode_fetch_ms.steady"]["value"] > 0
    assert metrics["stream_lag_p95_ms.steady"]["value"] > 0
    assert metrics["step_wall_max_ms.steady"]["value"] \
        >= metrics["decode_iter_wall_ms.steady"]["value"]
    steps = os.path.join(ROOT, "bench_out", "tiny-serve-chat", "serve",
                         "steps.jsonl")
    with open(steps) as f:
        rows = [json.loads(x) for x in f if x.strip()]
    assert sum(r["stream_lines"] for r in rows) > 0
    assert all(r["unnamed_s"] <= 0.02 * r["step_s"] + 1e-4 for r in rows)


@pytest.mark.parametrize("name", ALL)
def test_metric_file_is_one_accepted_cells(name):
    spec = _json(BENCH, "layer_metrics", name + ".json")
    cell = CELLS[name.rsplit(".", 1)[1]]
    assert spec["workloads"] == [cell]
    root = _json(ROOT, "BENCHMARK.json")
    assert cell in [w["name"] for w in root["workloads"]]
    # it moves an end-to-end metric that its cell reports
    e2e = {m["name"]: m for m in root["end_to_end"]}
    assert cell in e2e[spec["moves"]]["workloads"]
    assert spec["moves"] != "setup_s"
    assert os.path.exists(os.path.join(BENCH, "readers",
                                       spec["reader"] + ".py"))
    assert name not in [m["name"] for m in root["per_layer"]]
    entry = next(m for m in _json(MANIFEST)["per_layer"]
                 if m["name"] == name)
    assert (entry["layer"], entry["unit"], entry["moves"]) == (
        spec["layer"], spec["unit"], spec["moves"])
    assert cell in entry["workloads"]
    assert spec["layer"] in {m["layer"] for m in root["per_layer"]}
    twin = _json(BENCH, "layer_metrics",
                 name.rsplit(".", 1)[0] + ".steady.json")
    assert (twin["reader"], twin["args"], twin["layer"], twin["unit"]) == (
        spec["reader"], spec["args"], spec["layer"], spec["unit"])


def test_manifest_lists_the_new_files_and_the_root_manifest_none():
    manifest = _json(MANIFEST)
    listed = [m["name"] for m in manifest["per_layer"]]
    assert len(listed) == len(set(listed))
    assert set(ALL) <= set(listed)
    files = {f[:-5] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))}
    assert set(listed) <= files
    root = _json(ROOT, "BENCHMARK.json")
    assert len(root["per_layer"]) == 128      # at its limit: files only
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert set(CELLS.values()) < set(cells)
    for w in root["workloads"]:     # the accepted cells, as accepted
        if w["name"] in cells:
            assert cells[w["name"]] == w
    configs = {c["name"]: c for c in root["configs"]}
    for c in manifest["configs"]:
        if c["name"] in configs:
            assert c == configs[c["name"]]
    bounds = {m["name"]: m["bound"] for m in root["end_to_end"]}
    assert all(m["bound"] == bounds[m["name"]]
               for m in manifest["end_to_end"])
    assert manifest["run_seconds"] == root["run_seconds"]


def _rows(n=40):
    """Step-log rows as ``Engine._log_step`` writes them (the fields the
    files read), an idle gap and a stall among them."""
    rows = []
    for i in range(n):
        decode = i % 5 != 0
        rows.append({
            "t": 100.0 + 0.01 * i, "step": i + 1,
            "occupancy": 3 if decode else 0, "step_s": 0.008,
            "decode_s": 0.007 if decode else 0.0,
            "dispatch_s": 0.002 if decode else 0.0,
            "fetch_s": 0.004 if decode else 0.0,
            "commit_s": 0.001 if decode else 0.0,
            "commit_cpu_s": 0.0004 if decode else 0.0,
            "between_s": 0.0001, "offcpu_s": 0.0006, "unnamed_s": 0.00004,
            "stream_lines": 3 if decode else 0,
            "stream_lag_max_s": 0.002 + 0.0001 * i if decode else 0.0})
    rows[17]["step_s"] = 1.4
    return rows


@pytest.mark.parametrize("name,want", [
    ("decode_dispatch_ms", 2.0), ("decode_fetch_ms", 4.0),
    ("step_between_ms", 0.1), ("engine_offcpu_ms", 0.6),
    ("decode_commit_cpu_ms", 0.4), ("step_wall_max_ms", 1400.0),
])
def test_step_log_metric_is_the_statistic_it_says(tmp_path, name, want):
    os.makedirs(tmp_path / "serve")
    with open(tmp_path / "serve" / "steps.jsonl", "w") as f:
        for r in _rows():
            f.write(json.dumps(r) + "\n")
    ctx = {"out": str(tmp_path), "window": (100.0, 101.0)}
    for cell in CELLS:
        spec = _json(BENCH, "layer_metrics", f"{name}.{cell}.json")
        value = _reader(spec["reader"]).read(ctx, spec["args"])
        assert value == pytest.approx(want), (name, cell)
    # rows from before the fields (the parent's): nothing to read, no raise
    with open(tmp_path / "serve" / "steps.jsonl", "w") as f:
        for r in _rows():
            f.write(json.dumps({k: r[k] for k in ("t", "step", "occupancy",
                                                  "decode_s")}) + "\n")
    assert _reader(spec["reader"]).read(ctx, spec["args"]) is None


def test_unnamed_share_and_stream_lag_read_their_rows(tmp_path):
    os.makedirs(tmp_path / "serve")
    rows = _rows()
    with open(tmp_path / "serve" / "steps.jsonl", "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    ctx = {"out": str(tmp_path), "window": (100.0, 101.0)}
    spec = _json(BENCH, "layer_metrics", "step_unnamed_pct.trinity.json")
    share = _reader("jsonl_quantile").read(ctx, spec["args"])
    want = [100 * r["unnamed_s"] / r["step_s"] for r in rows]
    assert share == pytest.approx(sum(want) / len(want))
    spec = _json(BENCH, "layer_metrics", "stream_lag_p95_ms.trinity.json")
    lag = _reader("jsonl_quantile").read(ctx, spec["args"])
    lags = sorted(1e3 * r["stream_lag_max_s"] for r in rows
                  if r["stream_lines"])
    assert lags[-4] <= lag <= lags[-1]


@pytest.mark.parametrize("fixture,old", [
    ("serve_span_slice.json.gz", "idle_unattributed_pct.steady"),
    ("jamba_slice.json.gz", "idle_unattributed_pct.jamba"),
])
def test_idle_unnamed_reads_a_slice_recorded_on_the_chip(fixture, old):
    sys.path.insert(0, BENCH)
    with gzip.open(os.path.join(HERE, "data", fixture), "rt") as f:
        piece = json.load(f)
    dev = sorted(piece["devices"])[0]
    ctx = {"trace": {"devices": {dev: {
        "ops": [op[:3] for op in piece["devices"][dev]["ops"]],
        "modules": piece["devices"][dev]["modules"]}},
        "host": piece["host"]}}
    reader = _reader("trace_span")
    before = reader.read(ctx, _json(BENCH, "layer_metrics",
                                    old + ".json")["args"])
    for cell in CELLS:
        spec = _json(BENCH, "layer_metrics", f"idle_unnamed_pct.{cell}.json")
        value = reader.read(ctx, spec["args"])
        # the old leaves and engine.loop: never more idle outside them
        assert value is not None and 0 <= value <= before + 1e-9
    # with an engine.loop where the slice has its longest unnamed idle
    # stretch, the new pattern names it and the old one does not
    import trace_reduce

    w = trace_reduce.window_of(ctx["trace"])
    _, merged = trace_reduce.busy(ctx["trace"]["devices"][dev]["ops"])
    leaf = re.compile(spec["args"]["span"])
    named = trace_reduce.merge([
        (s, s + d) for events in piece["host"].values()
        for n, s, d in events if leaf.fullmatch(n)])
    holes = []
    for a, b in trace_reduce.idle_gaps(merged, w):
        cursor = a
        for s, e in named:
            if e <= cursor or s >= b:
                continue
            if s > cursor:
                holes.append((s - cursor, cursor, s))
            cursor = max(cursor, e)
        if cursor < b:
            holes.append((b - cursor, cursor, b))
    _, a, b = max(holes)
    ctx["trace"]["host"]["engine thread, the loop"] = [
        ["engine.loop", a, b - a]]
    assert reader.read(ctx, spec["args"]) < value - 1e-9
    assert reader.read(ctx, _json(BENCH, "layer_metrics", old + ".json")[
        "args"]) == pytest.approx(before)
