"""The manifest against the contract's limits and against the data files
the harness finds by name."""

import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
MANIFESTS = ["BENCHMARK.json", "benchmark/tests/rehearsal/BENCHMARK.json",
             "benchmark/tests/rehearsal/BENCHMARK-second.json"]


def _load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


@pytest.mark.parametrize("rel", MANIFESTS)
def test_names_units_and_keys(rel):
    m = _load(rel)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in m[k]}) == len(m[k])
    metrics = m["end_to_end"] + m["per_layer"]
    assert len({x["name"] for x in metrics}) == len(metrics)
    for x in metrics:
        assert UNIT.match(x["unit"]), x
        assert x["better"] in ("lower", "higher")
        assert x["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for x in m["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    for x in m["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith("benchmark/")
    assert 1 <= m["run_seconds"] <= 51
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)
    assert any(x["name"] == "setup_s" and "workloads" not in x
               for x in m["end_to_end"])


@pytest.mark.parametrize("rel", MANIFESTS)
def test_every_cell_reports_what_its_layer_metrics_move(rel):
    m = _load(rel)
    cells = [w["name"] for w in m["workloads"]]

    def cells_of(metric):
        return set(metric.get("workloads", cells))

    e2e = {x["name"]: cells_of(x) for x in m["end_to_end"]}
    for x in m["per_layer"]:
        assert x["moves"] in e2e, x
        assert cells_of(x) <= e2e[x["moves"]], x
        assert cells_of(x) <= set(cells), x
    for cell in cells:
        mine = [n for n, cs in e2e.items() if cell in cs]
        assert "setup_s" in mine and len(mine) >= 2, cell
        assert any(cell in cells_of(x) for x in m["per_layer"]), cell
    configs = {c["name"] for c in m["configs"]}
    assert {w["config"] for w in m["workloads"]} == configs
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) == len(
        cells)


@pytest.mark.parametrize("rel", MANIFESTS)
def test_data_files_are_found_by_name(rel):
    m = _load(rel)
    roots = [os.path.dirname(os.path.join(ROOT, rel)), BENCH]

    def find(sub, name, ext):
        for root in roots:
            path = os.path.join(root, sub, name + ext)
            if os.path.exists(path):
                return path
        raise AssertionError(f"no {sub}/{name}{ext}")

    for w in m["workloads"]:
        with open(find("traffic", w["traffic"], ".json")) as f:
            find("traffic_kinds", json.load(f)["kind"], ".py")
    for c in m["configs"]:
        config = _load(c["file"])
        find("reference", config["reference"], ".py")
        find("counts", config["counts"], ".py")
        check = config["correctness"].get("preflight")
        if check:
            find("checks", check["check"], ".py")
    layers = {}
    for x in m["per_layer"]:
        with open(find("layer_metrics", x["name"], ".json")) as f:
            spec = json.load(f)
        find("readers", spec["reader"], ".py")
        if rel == "BENCHMARK.json":
            assert spec["layer"] == x["layer"] and spec["unit"] == x["unit"]
            assert spec["moves"] == x["moves"]
            assert spec["workloads"] == x.get("workloads", "all")
        layers.setdefault(x["layer"], []).append(x["name"])
    assert all(len(layer) <= 200 and "\n" not in layer for layer in layers)
    for dirpath, _, files in os.walk(BENCH):
        if "__pycache__" in dirpath:
            continue
        for fn in files:
            assert re.match(r"^[A-Za-z0-9_.-]+$", fn), os.path.join(
                dirpath, fn)
