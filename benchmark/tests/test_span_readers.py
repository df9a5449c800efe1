"""The readers of the program's own names (``trace_span``,
``trace_scope``) on hand-made intervals; on a small recorded trace cut
from this PR's chip run they are checked in ``test_span_slices.py``."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "readers"))

import trace_scope  # noqa: E402
import trace_span  # noqa: E402


def _trace():
    # device busy [0,1) [2,3) [3.5,4); window [0,4); idle [1,2) [3,3.5)
    ops = [["%fusion.1", 0.0, 1.0], ["%copy.2", 2.0, 1.0],
           ["%fusion.3", 3.5, 0.5]]
    host = {"python": [["engine.decode", 0.5, 2.0],      # device 1.0
                       ["engine.decode", 2.75, 1.0],     # device 0.5
                       ["engine.decode", 3.9, 0.5],      # cut by the window
                       ["engine.decode.fetch", 0.6, 1.0],
                       ["$engine.py:1 step", 0.0, 4.0]],
            "python'": [["engine.log", 1.25, 0.5]]}
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
            "host": host}


def _read(span, stat, trace=None):
    return trace_span.read({"trace": trace or _trace()},
                           {"span": span, "stat": stat})


def test_span_wall_device_and_host_time():
    assert _read(r"engine\.decode", "wall_ms") == pytest.approx(1500.0)
    assert _read(r"engine\.decode", "device_ms") == pytest.approx(750.0)
    assert _read(r"engine\.decode", "host_ms") == pytest.approx(750.0)
    # the pattern is matched whole: `engine.decode` is not its children
    assert _read(r"engine\.decode\.fetch", "device_ms") == pytest.approx(
        400.0)
    assert _read(r"engine\.log", "wall_ms") == pytest.approx(500.0)


def test_idle_outside_the_named_spans():
    # idle 1.5 s; engine.log covers 0.5 s of [1,2); fetch covers [1,1.6)
    assert _read(r"engine\.log", "idle_outside_pct") == pytest.approx(
        100 * 1.0 / 1.5)
    assert _read(r"engine\.(log|decode\.fetch)",
                 "idle_outside_pct") == pytest.approx(
        100 * (1.5 - 0.75) / 1.5)
    # the two whole decode spans cover both gaps; the cut one is not used
    assert _read(r"engine\.decode", "idle_outside_pct") == pytest.approx(0)

def test_a_program_without_spans_or_devices_reads_nothing():
    assert _read(r"engine\.nothing", "wall_ms") is None
    assert _read(r"engine\.decode", "wall_ms",
                 {"devices": {}, "host": {}}) is None
    assert trace_span.read({}, {"span": "x", "stat": "wall_ms"}) is None


def test_scope_path_strips_frames_wrappers_and_the_primitive():
    f = trace_scope.scope_path
    assert f("jit(decode)/jit(main)/h3/kv_write/scatter") == "h3/kv_write"
    assert f("jit(decode)/jit(main)/convert_element_type") == ""
    assert f("jit(step)/jit(main)/transpose(jvp(GPTLM))/h3/attn/qkv/"
             "dot_general") == "GPTLM/h3/attn/qkv"
    assert f("jit(step)/jit(main)/jvp(loss_head)/pallas_call") == "loss_head"
    assert f("") == ""


def _scoped():
    # two whole executions [0,1) and [2,3) and one the window cut
    mods = [["jit_decode(1)", 0.0, 1.0], ["jit_decode(1)", 2.0, 1.0],
            ["jit_decode(1)", 4.0, 0.3], ["jit_other(2)", 1.2, 0.5]]
    ops = [["%while.1", 0.0, 1.0, "h0"],           # wraps: self 0.3
           ["%copy.2", 0.1, 0.4, "h0/kv_write"],
           ["%fusion.3", 0.6, 0.3, ""],
           ["%copy.4", 2.0, 0.6, "h1/kv_write"],
           ["%convert.5", 2.6, 0.4, "h1/qkv/cast_params"],
           ["%copy.6", 1.2, 0.5, "kv_write"],      # another program
           ["%copy.7", 4.0, 0.3, "h0/kv_write"]]   # a cut execution
    return {"ops": ops, "modules": mods}


def test_scope_seconds_per_whole_execution():
    ctx = {"scoped": _scoped()}

    def read(scope, stat="ms"):
        return trace_scope.read(ctx, {"program": "^jit_decode",
                                      "scope": scope, "stat": stat})

    assert read("kv_write") == pytest.approx(1e3 * (0.4 + 0.6) / 2)
    assert read("cast_params") == pytest.approx(1e3 * 0.4 / 2)
    assert read(None) == pytest.approx(1e3 * 0.3 / 2)
    assert read(None, "pct") == pytest.approx(100 * 0.3 / 2.0)
    assert read("^h0$") == pytest.approx(1e3 * 0.3 / 2)   # self, not wrapped
    assert trace_scope.read(ctx, {"program": "^jit_nothing",
                                  "scope": "kv_write"}) is None


def test_a_trace_without_paths_reads_nothing():
    bare = _scoped()
    bare["ops"] = [[n, s, d, None] for n, s, d, _ in bare["ops"]]
    assert trace_scope.read({"scoped": bare}, {
        "program": "^jit_decode", "scope": "kv_write"}) is None
    assert trace_scope.read({"trace_dir": "/nonexistent"}, {
        "program": "^jit_decode", "scope": None}) is None
