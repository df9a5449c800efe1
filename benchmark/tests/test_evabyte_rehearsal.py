"""A rehearsal of ``evabyte-serve-bytes32k-saturated`` on the CPU:
``rehearsal/BENCHMARK-evabyte.json`` runs ``serve.py --config evabyte_tiny``
(3 EVA layers, 4 heads of 16, chunks of 4 in windows of 16; a chunk then a
decode step an iteration) under a tiny ``open-loop-stratified`` mix with the
cell's own reference, counts, readers and layer-metric files.  A CPU trace has
no device lane, so the trace readers leave their metrics out without raising;
the step-log and host metrics are read.  The trace metrics are read off a
slice recorded on the chip (``data/evabyte_slice.json.gz``: a part of this
PR's traced run of the cell, cut by ``tools/trace_check.py --cut``), and a
pattern that matches nothing there fails.  And the data files of the real cell
agree with each other, with the catalog and with ISSUE 48's parameters.

``BENCHMARK.json``'s ``per_layer`` is full (128 of 128): every ``.evabyte``
metric is a file that the rehearsal's manifest lists, beside the real cell
itself, so ``run.py --manifest .../BENCHMARK-evabyte.json --workload
evabyte-serve-bytes32k-saturated --trace 1`` reads them on the chip.
Slow (the first case starts the program): run by hand with the other
benchmark tests."""

import glob
import gzip
import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
MANIFEST = os.path.join(HERE, "rehearsal", "BENCHMARK-evabyte.json")
SLICE = os.path.join(HERE, "data", "evabyte_slice.json.gz")
CELL = "evabyte-serve-bytes32k-saturated"
TINY = "evabyte-tiny-serve-bytes"
CONFIG = "evabyte-6.5b-serve"
#: what ISSUE 48 names, each a file
NAMED = [
    "decode_eva_attn_ms", "decode_summarise_ms", "prefill_eva_attn_ms",
    "prefill_summarise_ms", "prefill_chunk_device_ms", "decode_roofline_pct",
    "eva_attn_roofline_pct", "eva_chunk_attn_roofline_pct",
    "eva_ring_rows_read_mean", "eva_summary_rows_read_mean",
    "eva_chunks_closed_per_s", "eva_windows_closed_per_s",
    "kv_blocks_used_peak_pct.window", "kv_blocks_used_peak_pct.full",
    "kv_window_blocks_freed_per_s",
    # the .lfm2 set's engine and host metrics
    "decode_kv_write_ms", "prefill_device_share_pct", "decode_iter_wall_ms",
    "prefill_iter_wall_ms", "decode_span_device_ms", "decode_span_host_ms",
    "decode_occupancy_mean", "decode_unscoped_pct", "idle_unattributed_pct",
    "loadgen_late_p95_ms", "itl_p95_ms", "setup_backend_s",
    "setup_init_params_s", "decode_dispatch_ms", "step_between_ms",
    "decode_commit_cpu_ms", "step_unnamed_pct", "step_wall_max_ms",
    "decode_fetch_ms", "engine_offcpu_ms", "stream_lag_p95_ms",
    "idle_unnamed_pct"]
STEP_LOG_METRICS = [
    "decode_iter_wall_ms.evabyte", "decode_occupancy_mean.evabyte",
    "decode_device_sampled_pct.evabyte", "prefill_iter_wall_ms.evabyte",
    "decode_commit_cpu_ms.evabyte", "decode_dispatch_ms.evabyte",
    "decode_fetch_ms.evabyte", "engine_offcpu_ms.evabyte",
    "step_between_ms.evabyte", "step_unnamed_pct.evabyte",
    "step_wall_max_ms.evabyte", "stream_lag_p95_ms.evabyte",
    "eva_ring_rows_read_mean.evabyte", "eva_summary_rows_read_mean.evabyte",
    "eva_chunks_closed_per_s.evabyte", "eva_windows_closed_per_s.evabyte",
    "kv_blocks_used_peak_pct.window.evabyte",
    "kv_blocks_used_peak_pct.full.evabyte",
    "kv_window_blocks_freed_per_s.evabyte"]
HOST_METRICS = [
    "loadgen_late_p95_ms.evabyte", "itl_p95_ms.evabyte",
    "ttft_mean_ms.evabyte", "setup_backend_s.evabyte",
    "setup_init_params_s.evabyte", "compile_s", "compiles_in_window"]
#: device time by scope, read off the recorded slice
SLICE_METRICS = [
    "decode_eva_attn_ms.evabyte", "decode_summarise_ms.evabyte",
    "decode_kv_write_ms.evabyte", "decode_mlp_ms.evabyte",
    "prefill_eva_attn_ms.evabyte", "prefill_summarise_ms.evabyte",
    "prefill_chunk_device_ms.evabyte"]


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _module(path):
    spec = importlib.util.spec_from_file_location("m_" + os.path.basename(
        path)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_rehearsal_cell_serves_checks_and_reads_its_metrics():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         MANIFEST, "--workload", TINY, "--seed", "4800000019", "--seconds",
         "6", "--trace", "1"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    detail = line["detail"]
    assert detail["positions_checked"] == 32
    assert os.path.samefile(detail["reference_file"],
                            os.path.join(BENCH, "reference", "evabyte.py"))
    assert os.path.samefile(detail["counts_file"],
                            os.path.join(BENCH, "counts", "evabyte.py"))
    # no device lane on the CPU: the step-log and host metrics, and only
    # those; the trace readers return nothing and do not raise
    assert sorted(line["metrics"]) == sorted(STEP_LOG_METRICS + HOST_METRICS)
    # both pools are read and both rates are seen: prompts of 4-80 cross
    # windows of 16
    for name in ("eva_ring_rows_read_mean", "eva_summary_rows_read_mean",
                 "eva_chunks_closed_per_s", "eva_windows_closed_per_s",
                 "kv_window_blocks_freed_per_s"):
        assert line["metrics"][name + ".evabyte"]["value"] > 0, name


def test_cell_traffic_and_config_are_as_the_issue_gives_them():
    manifest = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "bytes32k-saturated", 1)
    assert len(cell["why"]) <= 200
    tok = next(m for m in manifest["end_to_end"]
               if m["name"] == "serve_tok_per_s")
    assert CELL in tok["workloads"]
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    traffic = _json(BENCH, "traffic", "bytes32k-saturated.json")
    assert traffic["kind"] == "open-loop-stratified"
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 6144,
                                     "sigma": 0.8, "min": 512, "max": 30720}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 1024,
                                     "sigma": 0.6, "min": 128, "max": 2048}
    assert traffic["gaps"] == {"dist": "exponential"}
    assert traffic["sampling"] == {"temperature": 0.0}
    assert traffic["judge_ttft"] is False
    assert (traffic["trace_at_s"], traffic["trace_seconds"],
            traffic["order_seed"], traffic["rotate_by_seed"]) == (
        10, 3, 48, False)
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert (traffic["warm_in_s"], traffic["warm_in_burst"]) == (
        30, config["max_slots"])
    assert traffic["rate_per_s"] == pytest.approx(
        1.25 * traffic["knee_per_s"])
    assert config["reduced"] == ["num_hidden_layers"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert len(entry["why"]) <= 200
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert (config["num_hidden_layers"], config["max_position_embeddings"],
            config["vocab_size"], config["num_pred_heads"]) == (
        8, 32768, 320, 8)
    check = config["correctness"]
    # two chunks, ending inside one, ten rows into a summary chunk; the
    # served bytes cross a window's end
    assert check["prompt_tokens"] == 4090
    assert check["prompt_tokens"] % config["chunk_size"] == 10
    assert (check["prompt_tokens"] + check["new_tokens"]) \
        // config["window_size"] == 2
    assert check["requests"] >= 2 and check["new_tokens"] >= 128
    longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert longest <= config["max_context"] == 32768
    for key in ("assumed", "departures", "deployment", "reduced_why",
                "cache_bytes_why", "argv_why"):
        assert config[key], key
    for key in ("equations_from", "head_dim", "embedding", "summaries",
                "attention", "traffic"):
        assert config["assumed"][key], key


def test_config_keeps_every_number_of_the_catalog_entry():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    # no width among the cuts
    for key in config["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")), key


def test_server_preset_is_the_configuration_file():
    sys.path.insert(0, ROOT)
    from distributedtensorflow_tpu import models

    config = _json(BENCH, "configs", CONFIG + ".json")
    cfg = getattr(models, config["system_config"])()
    assert (cfg.hidden_size, cfg.num_heads, cfg.intermediate_size,
            cfg.vocab_size, cfg.num_layers, cfg.chunk_size, cfg.window_size,
            cfg.num_pred_heads, cfg.max_seq, cfg.norm_eps, cfg.rope_theta
            ) == tuple(config[k] for k in (
                "hidden_size", "num_attention_heads", "intermediate_size",
                "vocab_size", "num_hidden_layers", "chunk_size",
                "window_size", "num_pred_heads", "max_position_embeddings",
                "rms_norm_eps", "rope_theta"))
    assert cfg.head_dim == cfg.hidden_size // cfg.num_heads == 128
    assert config["num_key_value_heads"] == cfg.num_heads
    rows = cfg.cache_rows.groups
    per_layer = sum(rows["window"].widths) * 2 \
        + sum(rows["full"].widths) * 2 // cfg.chunk_size
    assert per_layer * cfg.num_layers == config["cache_bytes_per_token"]
    argv = config["argv"]
    for flag, key in (("--max-slots", "max_slots"),
                      ("--block-size", "block_size"),
                      ("--max-context", "max_context"),
                      ("--prefill-chunk", "prefill_chunk"),
                      ("--prefill-budget", "prefill_budget"),
                      ("--kv-blocks", "kv_blocks"),
                      ("--max-queue", "max_queue")):
        assert argv[argv.index(flag) + 1] == str(config[key]), flag
    # the ring is left to its default: a window and a block a slot
    assert "--kv-window-blocks" not in argv
    assert config["kv_window_blocks"] == config["max_slots"] * (
        config["window_size"] // config["block_size"] + 1)


def test_counts_are_the_issues_arithmetic():
    counts = _module(os.path.join(BENCH, "counts", "evabyte.py"))
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert round(counts.layer_params(config) / 1e6, 2) == 202.39
    assert counts.params(config) == config["parameters"] == 1630932992
    assert round(counts.params(config) * 2 / 1e9, 2) == 3.26
    assert counts.row_bytes(config) == 16384
    # a context of 8.6 k: 408 ring rows and 512 summary rows a layer
    assert (counts.ring_rows(config, 8600), counts.summary_rows(config, 8600)
            ) == (8599 % 2048 + 1, 512)
    slots = config["max_slots"]
    lives = [8600] * slots
    attn = counts.decode_kernel(config, "eva_attn", lives)
    rows = slots * (408 + 512)
    assert attn["bytes"] == 8 * (rows * 16384 + slots * 2 * 4096 * 2)
    whole = counts.decode_kernel(config, "decode_iter", lives)
    weights = (counts.params(config) - counts.unread_params(config)) * 2
    assert whole["bytes"] == weights + 8 * rows * 16384
    # the issue's prediction: the cache is over half of a step's bytes
    assert 0.4 < 8 * rows * 16384 / whole["bytes"] < 0.6
    assert counts.step_kernel(config, "eva_attn") == attn
    chunk = counts.step_kernel(config, "eva_chunk_attn")
    assert chunk["flops"] == 8 * (2048 * 2049 / 2 + 2048 * 256) * 4 * 4096
    assert counts.decode_kernel(config, "summarise", lives)["bytes"] > 0
    assert counts.decode_kernel(config, "chunk_summarise", lives)["bytes"] \
        == 8 * (2048 + 128) * 16384
    with pytest.raises(NotImplementedError):
        counts.train_flops_per_token(config, 1024)
    with pytest.raises(KeyError):
        counts.decode_kernel(config, "moe_grouped", lives)


def test_every_evabyte_metric_is_a_file_the_rehearsal_lists_and_none_is_listed():
    names = sorted(os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(BENCH, "layer_metrics", "*.evabyte.json")))
    for name in NAMED:
        assert name + ".evabyte" in names, name
    rehearsal = _json(MANIFEST)
    listed = [m["name"] for m in rehearsal["per_layer"]]
    assert sorted(n for n in listed if n.endswith(".evabyte")) == names
    for m in rehearsal["per_layer"]:
        if m["name"].endswith(".evabyte"):
            spec = _json(BENCH, "layer_metrics", m["name"] + ".json")
            assert spec["workloads"] == [CELL]
            assert m["workloads"] == [TINY, CELL]
            assert (m["unit"], m["layer"], m["moves"]) == (
                spec["unit"], spec["layer"], spec["moves"])
            assert spec["moves"] == ("setup_s" if m["name"].startswith(
                "setup_") else "serve_tok_per_s")
            assert os.path.exists(os.path.join(
                BENCH, "readers", spec["reader"] + ".py"))
    manifest = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert rehearsal["workloads"][-1] == cell
    assert len(manifest["per_layer"]) == 128
    assert not [m["name"] for m in manifest["per_layer"]
                if m["name"].endswith(".evabyte")]
    # the kernels' roofline shares are held to counts/evabyte.py's names
    counts = _module(os.path.join(BENCH, "counts", "evabyte.py"))
    config = _json(BENCH, "configs", CONFIG + ".json")
    for name in ("eva_attn_roofline_pct", "eva_chunk_attn_roofline_pct",
                 "decode_roofline_pct"):
        spec = _json(BENCH, "layer_metrics", name + ".evabyte.json")
        assert spec["reader"] == "trace_decode_kernel"
        need = counts.decode_kernel(config, spec["args"]["required"],
                                    [900, 9000])
        assert need["bytes"] > 0
    # the scales of the pool metrics are this cell's two pools
    spec = _json(BENCH, "layer_metrics",
                 "kv_blocks_used_peak_pct.full.evabyte.json")
    assert spec["args"]["scale"] == pytest.approx(100 / config["kv_blocks"])
    spec = _json(BENCH, "layer_metrics",
                 "kv_blocks_used_peak_pct.window.evabyte.json")
    assert spec["args"]["scale"] == pytest.approx(
        100 / config["kv_window_blocks"])


@pytest.fixture(scope="module")
def slice_ctx():
    if not os.path.exists(SLICE):
        pytest.skip("no recorded slice of the cell's traced run")
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(BENCH, "readers"))
    with gzip.open(SLICE, "rt") as f:
        piece = json.load(f)
    name = sorted(piece["devices"])[0]
    dev = piece["devices"][name]
    return {
        "scoped": {"ops": dev["ops"], "modules": dev["modules"]},
        "trace": {"devices": {name: {"ops": [op[:3] for op in dev["ops"]],
                                     "modules": dev["modules"]}},
                  "host": piece["host"]},
    }


@pytest.mark.parametrize("name", SLICE_METRICS)
def test_trace_metric_reads_the_recorded_slice(slice_ctx, name):
    spec = _json(BENCH, "layer_metrics", name + ".json")
    reader = _module(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    value = reader.read(slice_ctx, spec["args"])
    assert value is not None and value > 0, name
    # a pattern that matches nothing in the slice reads nothing
    nothing = dict(spec["args"], scope="/no_such_scope(/|$)")
    assert not reader.read(slice_ctx, nothing)


@pytest.mark.parametrize("name,observed", [
    ("eva_attn_roofline_pct", None),
    ("eva_chunk_attn_roofline_pct", 512.0),
])
def test_the_kernels_roofline_shares_read_the_recorded_slice(
        slice_ctx, name, observed, tmp_path):
    """Both shares off the slice with 24 sequences of 8.6 k decoding (and
    prefill chunks that saw four closed windows): between 1 and 100 %, and
    nothing where the pattern matches no kernel."""
    spec = _json(BENCH, "layer_metrics", name + ".evabyte.json")
    reader = _module(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    live = {"token_times": [0.0], "token_counts": [1],
            "max_new_tokens": 1200, "prompt_tokens": 8600}
    out = tmp_path / "out"
    (out / "serve").mkdir(parents=True)
    if observed is not None:
        (out / "serve" / "steps.jsonl").write_text(json.dumps(
            {"t": 11.0, "chunk_summary_rows_read": observed}) + "\n")
    ctx = dict(slice_ctx, trace=slice_ctx["trace"],
               trace_done={"t_begin": 10.0, "t_end": 13.0},
               epoch_zero=0.0, logs=[live] * 24, out=str(out),
               config=_json(BENCH, "configs", CONFIG + ".json"),
               counts=_module(os.path.join(BENCH, "counts", "evabyte.py")),
               device_kind="TPU v5 lite")
    share = reader.read(ctx, spec["args"])
    assert 1.0 < share < 100.0, share
    assert reader.read(ctx, dict(spec["args"], pattern="no_such_kernel")) \
        is None
