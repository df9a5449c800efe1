"""``trace_span`` and ``trace_scope`` on two small recorded traces cut from
this PR's own chip run (``tools/trace_check.py --cut``; TPU v5 lite, PR 24):
0.57 s of the steady serving cell (two first tokens, prefill chunks, one
decode iteration) and 2.1 s of the one-chip training cell (one whole
``jit_step``).  The layer metrics' own argument files are what is read,
so a change of a pattern shows here."""

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(BENCH, "readers"))

import trace_reduce as tr  # noqa: E402
import trace_scope  # noqa: E402
import trace_span  # noqa: E402

READERS = {"trace_span": trace_span, "trace_scope": trace_scope}


def _ctx(fixture):
    with gzip.open(os.path.join(HERE, "data", fixture), "rt") as f:
        piece = json.load(f)
    name = sorted(piece["devices"])[0]
    dev = piece["devices"][name]
    return {
        "scoped": {"ops": dev["ops"], "modules": dev["modules"]},
        "trace": {"devices": {name: {"ops": [op[:3] for op in dev["ops"]],
                                     "modules": dev["modules"]}},
                  "host": piece["host"]},
    }


def _metric(ctx, name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    return READERS[spec["reader"]].read(ctx, spec["args"])


@pytest.fixture(scope="module")
def serve():
    return _ctx("serve_span_slice.json.gz")


@pytest.fixture(scope="module")
def train():
    return _ctx("train_scope_slice.json.gz")


@pytest.mark.parametrize("name,value", [
    ("decode_span_device_ms.steady", 121.462648),
    ("decode_span_host_ms.steady", 11.688863),
    ("first_token_host_ms.steady", 3.723648),
    ("engine_log_ms.steady", 0.83123),
    ("idle_unattributed_pct.steady", 1.3204418),
    ("prefill_chunk_device_ms.steady", 40.2934167),
    ("decode_paged_attn_ms.steady", 51.157774),
    ("decode_kv_write_ms.steady", 0.172646),
    ("decode_cast_params_ms.steady", 0.272746),
    ("decode_unscoped_pct.steady", 57.21777),
])
def test_serving_metrics_on_the_recorded_slice(serve, name, value):
    assert _metric(serve, name) == pytest.approx(value, rel=1e-6)
    # the saturated cell's twin reads the same thing
    twin = name.replace(".steady", ".sat")
    if os.path.exists(os.path.join(BENCH, "layer_metrics", twin + ".json")):
        assert _metric(serve, twin) == pytest.approx(value, rel=1e-6)


def test_serving_numbers_cohere_on_the_recorded_slice(serve):
    """Device + host time of the decode span is its wall; the span's
    device time is the decode program's (plus the eager operations that
    ran inside the span); the named leaves leave little idle unnamed."""
    trace = serve["trace"]
    spans = [(s, d) for n, s, d in trace["host"]["python3"]
             if n == "engine.decode"]
    assert len(spans) == 1
    wall = 1e3 * spans[0][1]
    device = _metric(serve, "decode_span_device_ms.steady")
    assert device + _metric(serve, "decode_span_host_ms.steady") == \
        pytest.approx(wall, rel=1e-9)
    dev = trace["devices"]["/device:TPU:0"]
    (a, b), = tr.whole_executions(
        [m for m in dev["modules"] if m[1] >= spans[0][0]], "^jit_decode")
    assert device == pytest.approx(1e3 * (b - a), rel=0.03)
    # what the program's scopes can and cannot see in the decode program
    assert _metric(serve, "decode_kv_write_ms.steady") < 0.5
    assert _metric(serve, "decode_paged_attn_ms.steady") > 40
    # a program without these names reads nothing, and does not raise
    bare = {"scoped": {"ops": [[n, s, d, None] for n, s, d, _ in
                               serve["scoped"]["ops"]],
                       "modules": serve["scoped"]["modules"]},
            "trace": {**trace, "host": {}}}
    for name in ("decode_span_device_ms.steady", "decode_kv_write_ms.steady",
                 "idle_unattributed_pct.steady"):
        assert _metric(bare, name) is None


TRAIN = {"train_attn_ms": 1202.088753, "train_mlp_ms": 561.691749,
         "train_head_ms": 195.496261, "train_optimizer_ms": 2.185199,
         "train_unscoped_ms": 2.237224}


def test_training_scopes_tile_the_step(train):
    values = {name: _metric(train, name) for name in TRAIN}
    assert values == pytest.approx(TRAIN, rel=1e-6)
    dev = train["trace"]["devices"]["/device:TPU:0"]
    (a, b), = tr.whole_executions(dev["modules"], "^jit_step")
    busy_s, _ = tr.busy([op for op in dev["ops"] if a <= op[1] < b])
    assert sum(values.values()) == pytest.approx(1e3 * busy_s, rel=0.03)
    # every scope pattern is exclusive of the others: nothing counted twice
    assert sum(values.values()) <= 1e3 * busy_s
