"""The compile log (ISSUE 50, ``obs/tracing.py:install_compile_log``): what
JAX traces, lowers, compiles or loads is a ``kind: "span"`` row of
``trace.jsonl``; an event that begins inside another is its child and sums
count roots only; rows made before a recorder exists wait for it, in order;
until ``startup.ready`` the roots are children of the start-up phase they
ended in and its row carries their sums; and the listeners are registered
once a process."""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from distributedtensorflow_tpu.obs import registry, tracing
from distributedtensorflow_tpu.obs.tracing import PhaseTrace, TraceRecorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import check_metrics_schema as checker  # noqa: E402

SUMS = ("trace_s", "lower_s", "backend_s", "cache_load_s", "programs")


@pytest.fixture
def compile_log():
    """The listeners on and nothing taken yet, and a registry of the
    test's own; a log some earlier test of the worker left installed goes
    first, so that this one's ``phases`` start clean."""
    tracing.uninstall_compile_log()
    prev = registry.set_default_registry(registry.Registry())
    tracing.install_compile_log()
    yield
    tracing.uninstall_compile_log()
    registry.set_default_registry(prev)


def _rows(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _programs(tag):
    """A jitted function that calls a jitted helper; the helper's trace
    lasts past ``COMPILE_CHILD_MIN_S``, so its row is written.  ``tag``
    names both, a test its own (JAX caches traces by function)."""
    def helper(x):
        time.sleep(3 * tracing.COMPILE_CHILD_MIN_S)     # at trace time only
        return x * 2 + jnp.arange(x.shape[0], dtype=x.dtype)

    def outer(x):
        return helper_jit(x) + 1

    helper.__name__, outer.__name__ = f"helper_{tag}", f"outer_{tag}"
    helper_jit = jax.jit(helper)
    return jax.jit(outer)


def test_a_nested_trace_is_a_child_and_sums_count_roots_only(
        compile_log, tmp_path):
    outer = _programs("nested")
    x = jnp.ones(4)         # (the constant's own programs come before)
    tracing.take_compiled()
    before = dict(registry.default_registry().scalars())
    with TraceRecorder(str(tmp_path / "trace.jsonl"), step_rows=False):
        with tracing.span("train_step") as step:
            outer(x)
    rows = [r for r in _rows(tmp_path / "trace.jsonl")
            if r["name"].startswith("compile.")]
    assert {r["trace_id"] for r in rows} == {"compile"}
    traces = [r for r in rows if r["name"] == "compile.trace"
              and r["program"] in ("outer_nested", "helper_nested")]
    child, root = traces                  # a child ends before its parent
    assert (child["program"], root["program"]) == (
        "helper_nested", "outer_nested")
    assert child["parent_id"] == root["span_id"] and "parent_id" not in root
    assert root["t0"] <= child["t0"] and child["dur_s"] <= root["dur_s"]
    assert child["dur_s"] >= 3 * tracing.COMPILE_CHILD_MIN_S
    # the jnp calls inside are trace events too, and far too short a row
    assert all(r["dur_s"] >= tracing.COMPILE_CHILD_MIN_S
               or r["name"] != "compile.trace" for r in rows
               if "parent_id" in r)
    roots = [r for r in rows if "parent_id" not in r]
    assert [r["name"] for r in roots if r["program"].endswith(
        "outer_nested)") or r["program"] == "outer_nested"] == [
        "compile.trace", "compile.lower", "compile.backend"]
    backend = roots[-1]
    assert backend["cache"] == "off" and backend["cache_load_s"] == 0.0
    # sums: the registry's two counters, the iteration's account and the
    # open span's children all count the roots, and the roots only
    after = registry.default_registry().scalars()
    moved = {k: v - before.get(k, 0.0) for k, v in after.items()
             if k.startswith("jit_") and v != before.get(k, 0.0)}
    by_phase = {p: sum(r["dur_s"] for r in roots
                       if r["name"] == "compile." + p)
                for p in ("trace", "lower", "backend")}
    for phase, secs in by_phase.items():
        assert moved[f"jit_compile_seconds_total.phase_{phase}"] == \
            pytest.approx(secs, abs=1e-5)
    assert by_phase["trace"] < root["dur_s"] + child["dur_s"]
    assert moved["jit_compiles_total.cache_off.program_jit_outer_nested_"] \
        == 1.0
    seconds, names = tracing.take_compiled()
    assert seconds == pytest.approx(sum(by_phase.values()), abs=1e-5)
    assert "outer_nested" in names.split(",") and "helper_nested" not in names
    assert tracing.take_compiled() == (0.0, "")
    assert [c.name for c in step.children] == [r["name"] for r in roots]
    assert sum(c.dur_s for c in step.children) <= step.dur_s
    assert checker.check_file(str(tmp_path / "trace.jsonl")) == ([], [])


def test_rows_made_before_a_recorder_wait_for_it_in_order(compile_log,
                                                          tmp_path):
    """A start-up trace owns the log: the rows wait for their phase's name
    (and with it for a recorder), come out before it as its children under
    its ``trace_id``, and its row and ``startup.ready`` carry their
    sums."""
    outer = _programs("early")
    path = tmp_path / "trace.jsonl"
    startup = PhaseTrace("startup", time.time() - 1.0)
    tracing.install_compile_log(startup)
    startup.mark("startup.imports")
    outer(jnp.ones(3))                      # no recorder yet: all waits
    startup.mark("startup.backend")
    assert not path.exists()
    with TraceRecorder(str(path), step_rows=False):
        startup.open("startup.first_step")
        outer(jnp.ones(5))
        startup.mark("startup.compile_or_load", parent="startup.first_step")
        startup.close("startup.first_step", step=1)
        startup.ready()
        outer(jnp.ones(6))                  # start-up is over
    rows = _rows(path)
    ends = [r["t0"] + r["dur_s"] for r in rows if r["name"] != "startup.ready"]
    assert ends == sorted(ends)             # file order is time order
    by_name = {r["name"]: r for r in rows if r["name"].startswith("startup.")}
    assert list(by_name) == [
        "startup.imports", "startup.backend", "startup.compile_or_load",
        "startup.first_step", "startup.ready"]
    compiles = [r for r in rows if r["name"].startswith("compile.")]
    roots = {}      # phase span id -> its compile roots
    ids = {r["span_id"] for r in compiles}
    for r in compiles:
        if r.get("parent_id") not in ids:
            roots.setdefault(r.get("parent_id"), []).append(r)
    assert set(roots) == {by_name["startup.backend"]["span_id"],
                          by_name["startup.compile_or_load"]["span_id"],
                          None}
    at = rows.index(by_name["startup.ready"])
    assert {r["trace_id"] for r in rows[:at]} == {"startup"}
    assert {r["trace_id"] for r in rows[at + 1:]} == {"compile"}
    for name in ("startup.backend", "startup.compile_or_load"):
        phase, mine = by_name[name], roots[by_name[name]["span_id"]]
        for p in ("trace", "lower", "backend"):
            assert phase[p + "_s"] == pytest.approx(sum(
                r["dur_s"] for r in mine if r["name"] == "compile." + p),
                abs=1e-5)
        assert phase["programs"] == sum(
            r["name"] == "compile.backend" for r in mine) >= 1
        assert all(r["t0"] + r["dur_s"] <= phase["t0"] + phase["dur_s"] + 1e-5
                   for r in mine)
    assert by_name["startup.imports"]["programs"] == 0
    first_step, ready = by_name["startup.first_step"], by_name["startup.ready"]
    top = [by_name[n] for n in ("startup.imports", "startup.backend",
                                "startup.first_step")]
    for key in SUMS:
        assert first_step[key] == by_name["startup.compile_or_load"][key]
        assert ready[key] == pytest.approx(sum(r[key] for r in top), abs=1e-5)
    assert ready["unnamed_s"] == 0.0
    assert ready["total_s"] == ready["dur_s"] == pytest.approx(
        sum(r["dur_s"] for r in top), abs=1e-5)
    assert ready["total_s"] >= 1.0 and "parent_id" not in ready
    assert ready["cache_hits"] == 0 and ready["cache_misses"] == 0
    assert checker.check_file(str(path)) == ([], [])


def test_installing_twice_registers_once(compile_log):
    from jax._src import monitoring

    listeners = (monitoring._scalar_listeners, monitoring._event_listeners,
                 monitoring._event_duration_secs_listeners,
                 monitoring._event_time_span_listeners)
    counts = [len(ls) for ls in listeners]
    tracing.install_compile_log()
    tracing.install_compile_log(PhaseTrace("startup"))
    assert [len(ls) for ls in listeners] == counts
    tracing.uninstall_compile_log()
    assert [len(ls) for ls in listeners] == [n - 1 for n in counts]
    tracing.uninstall_compile_log()         # nothing left to remove
    assert tracing.take_compiled() == (0.0, "")
    tracing.install_compile_log()
    assert [len(ls) for ls in listeners] == counts


@pytest.mark.parametrize("edit,message", [
    (lambda r: r.update(name="compile.link"), "unknown compile row"),
    (lambda r: r.pop("program"), "names no 'program'"),
    (lambda r: r.update(trace_id="startup"), "no phase's child"),
    (lambda r: r.update(cache="warm"), "'cache' 'warm'"),
    (lambda r: r.update(cache_load_s=9.0), "not a part of dur_s"),
])
def test_schema_checker_holds_the_compile_rows(tmp_path, edit, message):
    path = tmp_path / "trace.jsonl"
    row = {"kind": "span", "name": "compile.backend", "trace_id": "compile",
           "span_id": "a1", "t0": 10.0, "dur_s": 0.5, "proc": 1,
           "program": "jit(step)", "cache": "hit", "cache_load_s": 0.4}
    path.write_text(json.dumps(row) + "\n")
    assert checker.check_file(str(path)) == ([], [])
    edit(row)
    path.write_text(json.dumps(row) + "\n")
    errors, _ = checker.check_file(str(path))
    assert len(errors) == 1 and message in errors[0], errors


@pytest.mark.parametrize("edit,message", [
    (lambda rows: rows[2].update(unnamed_s=0.2, total_s=3.2),
     "leaves unnamed_s"),
    (lambda rows: rows[2].update(total_s=4.0, dur_s=4.0),
     "is not the top-level phases'"),
    (lambda rows: rows[2].update(lower_s=0.9), "'lower_s' 0.9"),
    (lambda rows: rows[2].pop("cache_hits"), "'cache_hits' None"),
    (lambda rows: rows[1].update(t0=10.5), "before the previous start-up"),
])
def test_schema_checker_holds_startup_ready(tmp_path, edit, message):
    """``startup.ready`` is the summary and no tile: it overlaps every
    phase, and its seconds and sums are the top-level phases'."""
    sums = {"trace_s": 0.5, "lower_s": 0.25, "backend_s": 0.125,
            "cache_load_s": 0.0, "programs": 1}
    base = {"kind": "span", "trace_id": "startup", "proc": 1}
    rows = [
        {**base, "name": "startup.listen", "span_id": "a", "t0": 10.0,
         "dur_s": 1.0, **{k: 0 for k in sums}},
        {**base, "name": "startup.first_request", "span_id": "b",
         "t0": 11.0, "dur_s": 2.0, **sums},
        {**base, "name": "startup.ready", "span_id": "c", "t0": 10.0,
         "dur_s": 3.0, "total_s": 3.0, **sums, "cache_hits": 1,
         "cache_misses": 0, "unnamed_s": 0.0},
    ]
    path = tmp_path / "trace.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    assert checker.check_file(str(path)) == ([], [])
    edit(rows)
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    errors, _ = checker.check_file(str(path))
    assert len(errors) == 1 and message in errors[0], errors
