"""``trace_reduce`` on a small recorded trace (``data/``: 178 ms of the
serving cell on the chip) and on hand-made intervals."""

import gzip
import json
import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace():
    with gzip.open(os.path.join(HERE, "data", "serve_slice_trace.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_busy_union_and_idle_on_the_recorded_slice(trace):
    ops = trace["devices"]["/device:TPU:0"]["ops"]
    busy_s, merged = tr.busy(ops)
    window = tr.window_of(trace)
    gaps = tr.idle_gaps(merged, window)
    idle = sum(b - a for a, b in gaps)
    assert busy_s + idle == pytest.approx(window[1] - window[0], rel=1e-9)
    assert busy_s == pytest.approx(0.161354513, rel=1e-6)
    assert window[1] - window[0] == pytest.approx(0.175861627, rel=1e-6)
    # the op line is sequential here, so self times add up to the union
    assert sum(own for *_, own in tr.self_times(ops)) == pytest.approx(
        busy_s, rel=1e-3)
    # the longest gap is the host sampling between two decode programs
    assert max(b - a for a, b in gaps) == pytest.approx(0.010052553, rel=1e-6)


def test_op_share_and_gap_attribution_on_the_recorded_slice(trace):
    s = tr.summarize(trace)
    ops = dict(s["breakdown"]["device_ops"])
    assert list(ops)[0] == "copy"                  # the serving finding
    assert ops["copy"] / s["busy_s"] == pytest.approx(0.45896, rel=1e-3)
    assert len(s["breakdown"]["device_ops"]) <= 10
    gaps = dict(s["breakdown"]["idle_gaps"])
    assert max(gaps, key=gaps.get) == "_<unknown>_argmax"
    assert tr.dispatch_thread(trace["host"]) == "python"
    mods = trace["devices"]["/device:TPU:0"]["modules"]
    assert [round(e - s, 4) for s, e in tr.whole_executions(
        mods, "^jit_decode")] == [0.1207]


def test_nested_events_are_not_counted_twice():
    ev = [["%while.1 = ...", 0.0, 1.0], ["%fusion.2 = x", 0.1, 0.3],
          ["%copy.3 = y", 0.5, 0.4], ["%fusion.9 = z", 1.5, 0.5]]
    assert tr.op_seconds(ev) == pytest.approx(
        {"while": 0.3, "fusion": 0.8, "copy": 0.4})
    busy_s, merged = tr.busy(ev)
    assert busy_s == pytest.approx(1.5) and merged == [[0.0, 1.0], [1.5, 2.0]]
    assert tr.idle_gaps(merged, (0.0, 2.5)) == [(1.0, 1.5), (2.0, 2.5)]
    assert tr.matching_seconds(ev, "fusion", within=[(1.0, 2.0)]) == (
        pytest.approx(0.5), 1)
    assert tr.family("%copy-start.230 = (bf16[32,1024]") == "copy-start"
    assert tr.family("jit_step(123)") == "jit_step"


def test_gaps_go_to_the_innermost_host_frame():
    host = [["$engine.py:718 step", 0.0, 3.0], ["$time sleep", 1.1, 0.3],
            ["PjitFunction(decode)", 2.0, 0.2]]
    got = tr.attribute_gaps([(1.0, 1.5), (2.05, 2.1), (5.0, 5.2)], host)
    assert got == pytest.approx({"_time_sleep": 0.5,
                                 "PjitFunction(decode)": 0.05,
                                 "(no_host_event)": 0.2})
    rest = tr.attribute_gaps([(1.0, 1.5), (2.05, 2.1)], host, longest=1)
    assert rest["(shorter gaps)"] == pytest.approx(0.05)


def test_clipped_executions_are_dropped():
    mods = [["jit_step(1)", 0.0, 0.9], ["jit_step(1)", 1.0, 2.0],
            ["jit_step(1)", 3.1, 1.99], ["jit_step(1)", 5.2, 1.0],
            ["jit_other(2)", 0.0, 9.0]]
    assert tr.whole_executions(mods, "^jit_step") == [(1.0, 3.0),
                                                       (3.1, 5.09)]
    assert tr.whole_executions(mods, "^jit_none") == []
