"""Start-up as spans (ISSUE 24): ``train.py`` run in-process leaves the
``startup.*`` rows in its ``trace.jsonl`` — one trace_id, in order, tiling
the time from the top of the file to the end of the first optimizer step —
and its ``metrics.jsonl`` rows carry the time they were written.
(``serve.py`` installs signal handlers, so its rows are checked where it
runs as a process: tests/test_serve_smoke.py.)"""

import json
import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import check_metrics_schema as checker  # noqa: E402

TRAIN_PHASES = [
    "startup.imports", "startup.backend", "startup.workload",
    "startup.state_init", "startup.trainer", "startup.data",
    "startup.first_step",
]


def startup_rows(path):
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return [r for r in rows if r.get("kind") == "span"
            and r["name"].startswith("startup.")]


#: The children that tile ``startup.trainer`` and ``startup.first_step``
#: (ISSUE 50), in order.
TRAIN_CHILDREN = {
    "startup.trainer": [
        "startup.trainer.services", "startup.trainer.tensorflow_import",
        "startup.trainer.construct"],
    "startup.first_step": [
        "startup.first_batch", "startup.compile_or_load",
        "startup.first_step_run"],
}
COMPILE_SUMS = ("trace_s", "lower_s", "backend_s", "cache_load_s",
                "programs")


def assert_tiles(rows, phases, max_unnamed_s=0.05):
    """Top-level start-up rows are exactly ``phases``, in order, each
    starting where the one before ends, and ``startup.ready``, the summary
    of them all, last."""
    assert {r["trace_id"] for r in rows} == {"startup"}
    assert len({r["proc"] for r in rows}) == 1
    top = [r for r in rows if "parent_id" not in r]
    assert [r["name"] for r in top] == phases + ["startup.ready"]
    ready = top.pop()
    assert ready["unnamed_s"] == 0.0
    assert ready["total_s"] == ready["dur_s"] == pytest.approx(
        sum(r["dur_s"] for r in top), abs=1e-4)
    assert ready["t0"] == top[0]["t0"]
    for key in COMPILE_SUMS:
        assert ready[key] == pytest.approx(sum(r[key] for r in top), abs=1e-4)
    for a, b in zip(top, top[1:]):
        gap = b["t0"] - (a["t0"] + a["dur_s"])
        assert -2e-6 <= gap <= max_unnamed_s, (a["name"], b["name"], gap)
    return top


@pytest.fixture
def own_registry():
    """A process-default metrics registry of the test's own: ``train.main()``
    writes the default one's snapshot into ``metrics.jsonl``, and another
    test of the same xdist worker may have left a series there that the
    schema checker (rightly) refuses in a trainer's rows
    (``breaker_state.endpoint_127_0_0_1:<port>``, CHANGES.md PR 27)."""
    from distributedtensorflow_tpu.obs import registry

    prev = registry.set_default_registry(registry.Registry())
    yield
    registry.set_default_registry(prev)


def test_train_py_startup_rows_and_metrics_time(tmp_path, monkeypatch,
                                                own_registry):
    import train

    # the run needs no TensorFlow (and TensorBoard's loader, below, then
    # reads with its own record reader)
    monkeypatch.setitem(sys.modules, "tensorflow", None)
    logdir = tmp_path / "run"
    monkeypatch.setattr(sys, "argv", [
        "train.py", "--workload", "mnist_lenet", "--test-size", "--steps",
        "4", "--log-every", "2", "--batch-size", "16", "--device", "cpu",
        "--logdir", str(logdir)])
    t_before = time.time()
    train.main()
    t_after = time.time()
    rows = startup_rows(logdir / "trace.jsonl")
    top = assert_tiles(rows, TRAIN_PHASES)
    # the interval starts at the module's own stamp and ends inside main()
    assert top[0]["t0"] == pytest.approx(train.T_PROCESS_START, abs=1e-5)
    first_step = top[-1]
    assert t_before <= first_step["t0"] + first_step["dur_s"] <= t_after
    assert first_step["step"] == 1
    for parent in top:
        kids = [r for r in rows if r.get("parent_id") == parent["span_id"]]
        assert [r["name"] for r in kids] == TRAIN_CHILDREN.get(
            parent["name"], [])
        if kids:    # they tile it, and their compile sums are its own
            assert kids[0]["t0"] == parent["t0"]
            assert sum(r["dur_s"] for r in kids) == pytest.approx(
                parent["dur_s"], abs=1e-3)
            for key in COMPILE_SUMS:
                assert parent[key] == pytest.approx(
                    sum(r[key] for r in kids), abs=1e-4)
    # the mark that timed `import tensorflow` (14.3 s on the chip) times
    # nothing since the metric writer writes its event files itself
    (tf_import,) = [r for r in rows
                    if r["name"] == "startup.trainer.tensorflow_import"]
    assert tf_import["dur_s"] < 0.5
    # rows written at their phase's end: file order is time order
    ends = [r["t0"] + r["dur_s"] for r in rows if r["name"] != "startup.ready"]
    assert ends == sorted(ends)
    # what JAX compiled on the way: every root a child of the phase it
    # ended in, the phase's sums theirs; the step's own program is the
    # first step's, and nothing compiles after it
    with open(logdir / "trace.jsonl") as f:
        spans = [json.loads(line) for line in f if line.strip()]
    compiles = [r for r in spans if r.get("kind") == "span"
                and r["name"].startswith("compile.")]
    assert compiles and {r["trace_id"] for r in compiles} == {"startup"}
    phases = {r["span_id"]: r for r in rows}
    roots = [r for r in compiles if r["parent_id"] in phases]
    for key, name in (("trace_s", "compile.trace"), ("lower_s",
                      "compile.lower"), ("backend_s", "compile.backend")):
        assert sum(r["dur_s"] for r in roots if r["name"] == name) == \
            pytest.approx(sum(r[key] for r in top), abs=1e-4)
    # (on the CPU --estimate-flops compiles the step once before, ahead
    # of time, inside startup.state_init)
    step_rows = [r for r in roots if phases[r["parent_id"]]["name"]
                 == "startup.compile_or_load"]
    assert [(r["name"], r["program"]) for r in step_rows] == [
        ("compile.trace", "step"), ("compile.lower", "jit(step)"),
        ("compile.backend", "jit(step)")]
    assert checker.check_file(str(logdir / "trace.jsonl")) == ([], [])
    with open(logdir / "metrics.jsonl") as f:
        metrics = [json.loads(line) for line in f if line.strip()]
    assert [m["step"] for m in metrics] == [2, 4]
    # the first row's steps held the compilation, and the row says so
    assert metrics[0]["compile_s"] > 0
    assert "step" in metrics[0]["compiled"].split(",")
    assert metrics[1]["compile_s"] == 0.0 and "compiled" not in metrics[1]
    ts = [m["t"] for m in metrics]
    assert ts == sorted(ts) and t_before <= ts[0] and ts[-1] <= t_after
    assert checker.check_file(str(logdir / "metrics.jsonl"))[0] == []
    # every logged row's write is a span of its step's trace row, and the
    # one event file holds the rows' numbers as TensorBoard's loader
    # reads them: same tags, same steps, at float32
    step_rows = [r for r in spans if r.get("step") in (2, 4)]
    assert [[s["name"] for s in r["spans"]].count("metric_write")
            for r in step_rows] == [1, 1]
    loader = pytest.importorskip(
        "tensorboard.backend.event_processing.event_file_loader")
    from tensorboard.util import tensor_util

    (events,) = logdir.glob("events.out.tfevents.*")
    got = {}
    for event in loader.EventFileLoader(str(events)).Load():
        for value in event.summary.value:
            got.setdefault(event.step, {})[value.tag] = (
                tensor_util.make_ndarray(value.tensor)[()])
    want = {m["step"]: {k: np.float32(v) for k, v in m.items()
                        if k not in ("step", "t") and not isinstance(v, str)}
            for m in metrics}
    assert got == want and all(len(row) > 20 for row in got.values())


def test_trainer_row_says_the_update_is_separate(tmp_path, monkeypatch,
                                                 own_registry):
    """``gpt_medium_lm`` at test size through ``train.main()``: the
    ``startup.trainer`` row carries ``optimizer_update`` ("separate": the
    step's gradients pass a barrier before ``apply_gradients``,
    ``train.engine.separate_update``) and ``optimizer_update_leaves`` (one
    a parameter leaf) beside ``flash_layout``, and the schema checker takes
    the row."""
    import jax
    import train

    from distributedtensorflow_tpu.workloads import get_workload

    logdir = tmp_path / "run"
    monkeypatch.setattr(sys, "argv", [
        "train.py", "--workload", "gpt_medium_lm", "--test-size", "--steps",
        "2", "--log-every", "1", "--batch-size", "4", "--device", "cpu",
        "--mesh", "data=1", "--logdir", str(logdir)])
    train.main()
    (row,) = [r for r in startup_rows(logdir / "trace.jsonl")
              if r["name"] == "startup.trainer"]
    wl = get_workload("gpt_medium_lm", test_size=True)
    leaves = len(jax.tree.leaves(
        jax.eval_shape(wl.init_fn, jax.random.PRNGKey(0))["params"]))
    assert leaves > 1
    assert (row["optimizer_update"], row["optimizer_update_leaves"]) == (
        "separate", leaves)
    assert row["flash_layout"] == "xla"  # this CPU, 64 positions
    assert checker.check_file(str(logdir / "trace.jsonl")) == ([], [])


@pytest.mark.parametrize("changes, seq, want", [
    pytest.param(dict(remat=True, attn_impl="pallas"), 64,
                 ("qkv_tiles", "saved", 4 * 64 * (128 * 2 + 4 * 4),
                  None, None), id="tiles_under_remat"),
    pytest.param(dict(remat=True, attn_impl="xla"), 64,
                 ("xla", "recomputed", 0, None, None),
                 id="another_form_under_remat"),
    pytest.param(dict(remat=False, attn_impl="pallas"), 64,
                 ("qkv_tiles", None, None, None, None), id="remat_off"),
    # one 1024 x 1024 block a sequence: the diagonal block in sub-tiles
    pytest.param(dict(remat=True, attn_impl="pallas"), 1024,
                 ("qkv_tiles", "saved", 4 * 1024 * (128 * 2 + 4 * 4),
                  256, 0.625), id="tiles_at_1024_sub_tiled"),
    pytest.param(dict(remat=True, attn_impl="pallas", num_kv_heads=2), 1024,
                 ("bhsd", "recomputed", 0, None, None),
                 id="bhsd_at_1024_whole_blocks"),
    # the fused head: four products a step, and 4 x 63 tokens make one
    # chunk of one 2048-token block (the dw kernel's token tile)
    pytest.param(dict(remat=False, attn_impl="xla", xent_impl="fused"), 64,
                 ("xla", None, None, None, None, 4, 2048),
                 id="fused_head_four_products"),
])
def test_trainer_row_says_what_a_block_keeps(changes, seq, want, tmp_path):
    """The ``startup.trainer`` row carries ``attn_residuals`` and
    ``attn_residual_bytes_per_layer`` beside ``flash_layout``, and
    ``flash_causal_tile`` / ``flash_causal_share`` (the rows of the
    sub-tiles a causal diagonal block is walked in and the share of its
    square that is computed; null where blocks are taken whole: a block
    under two sub-tiles, ``bhsd``, ``xla``) — what ``train._flash_layout``
    reads off the model under the trainer's mesh —, and
    ``xent_products_per_step`` / ``xent_dlog_chunk_tokens`` (the head's
    ``tokens x d x V`` products a step and the tokens a chunk of its
    backward holds dlogits for; null where the head is not the fused one,
    as "auto" is not on this CPU), and the schema checker takes the row,
    nulls included."""
    import dataclasses
    import types

    import jax
    import numpy as np
    import train

    from distributedtensorflow_tpu.models import GPTLM, gpt_tiny
    from distributedtensorflow_tpu.obs import tracing
    from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh

    wl = types.SimpleNamespace(
        model=GPTLM(dataclasses.replace(gpt_tiny(), **changes)),
        # the example batch has two rows; a step has the global batch's
        init_batch={"input_ids": np.zeros((2, seq), np.int32)},
        global_batch_size=4)
    fields = train._flash_layout(
        wl, build_mesh(MeshSpec(data=1), jax.devices()[:1]))
    assert fields == dict(zip(
        ("flash_layout", "attn_residuals", "attn_residual_bytes_per_layer",
         "flash_causal_tile", "flash_causal_share",
         "xent_products_per_step", "xent_dlog_chunk_tokens"),
        want + (None, None)[len(want) - 5:]))
    path = tmp_path / "trace.jsonl"
    with tracing.TraceRecorder(str(path), chief_only=False):
        tracing.PhaseTrace("startup").mark("startup.trainer", **fields)
    (row,) = startup_rows(path)
    assert {k: row[k] for k in fields} == fields
    assert checker.check_file(str(path)) == ([], [])
    # a model with no such choice leaves the row as it was
    wl.model = object()
    assert train._flash_layout(wl, None) == {}
