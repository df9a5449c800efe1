"""A rehearsal of ``ouro-2.6b-serve-reason1k-saturated`` on the CPU:
``rehearsal/BENCHMARK-ouro.json`` runs ``serve.py --config ouro_tiny`` (3
layers run 4 times: 12 cache layer slots, 2 heads of 32, a pool of 48 blocks
of 4) under a tiny ``open-loop-stratified`` mix with the cell's own
reference, counts, readers and layer-metric files.  A CPU trace has no device
lane, so the trace readers leave their metrics out without raising; the
step-log and host metrics are read.  The trace metrics are read off a slice
recorded on the chip (``data/ouro_slice.json.gz``: a part of this PR's traced
run of the cell, cut by ``tools/trace_check.py --cut``: one execution of
``jit_prefill_chunk`` and the ``jit_decode`` after it — the loop's ``while``,
its body's operations under ``ut_loop/while/body/h<l>/...``), and a
pattern that matches nothing there fails.  And the data files of the real
cell agree with each other, with the catalog and with ISSUE 61's parameters.

``BENCHMARK.json``'s ``per_layer`` is full (128 of 128): every ``.ouro``
metric is a file that the rehearsal's manifest lists, beside the real cell
itself, so ``run.py --manifest .../BENCHMARK-ouro.json --workload
ouro-2.6b-serve-reason1k-saturated --trace 1`` reads them on the chip.
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
MANIFEST = os.path.join(HERE, "rehearsal", "BENCHMARK-ouro.json")
SLICE = os.path.join(HERE, "data", "ouro_slice.json.gz")
CELL = "ouro-2.6b-serve-reason1k-saturated"
TINY = "ouro-tiny-serve-reason"
CONFIG = "ouro-2.6b-serve"
#: what ISSUE 61 names, each a file
NAMED = [
    "decode_iter_wall_ms", "decode_span_device_ms", "decode_span_host_ms",
    "decode_dispatch_ms", "decode_fetch_ms", "decode_commit_ms",
    "decode_commit_cpu_ms", "decode_occupancy_mean", "decode_unscoped_pct",
    "prefill_chunk_device_ms", "prefill_iter_wall_ms",
    "prefill_device_share_pct", "kv_blocks_used_peak_pct", "ttft_mean_ms",
    "itl_p95_ms", "loadgen_late_p95_ms", "step_between_ms",
    "idle_unattributed_pct", "setup_backend_s", "setup_init_params_s",
    # its own
    "decode_ut_loop_ms", "decode_attn_proj_ms", "decode_mlp_ms",
    "decode_norms_ms", "decode_paged_attn_ms", "decode_kv_write_ms",
    "decode_head_ms", "ut_exit_mass_last_pct", "decode_roofline_pct",
    "paged_attn_roofline_pct", "kv_chunk_attn_roofline_pct"]
STEP_LOG_METRICS = [
    "decode_iter_wall_ms.ouro", "decode_occupancy_mean.ouro",
    "decode_device_sampled_pct.ouro", "kv_blocks_used_peak_pct.ouro",
    "prefill_iter_wall_ms.ouro", "decode_commit_cpu_ms.ouro",
    "decode_dispatch_ms.ouro", "decode_fetch_ms.ouro",
    "engine_offcpu_ms.ouro", "step_between_ms.ouro", "step_unnamed_pct.ouro",
    "step_wall_max_ms.ouro", "stream_lag_p95_ms.ouro",
    "ut_exit_mass_last_pct.ouro", "ut_steps.ouro"]
HOST_METRICS = [
    "loadgen_late_p95_ms.ouro", "itl_p95_ms.ouro", "ttft_mean_ms.ouro",
    "setup_backend_s.ouro", "setup_init_params_s.ouro", "compile_s",
    "compiles_in_window"]
#: device time by scope, read off the recorded slice
SLICE_METRICS = [
    "decode_ut_loop_ms.ouro", "decode_attn_proj_ms.ouro",
    "decode_mlp_ms.ouro", "decode_norms_ms.ouro",
    "decode_paged_attn_ms.ouro", "decode_kv_write_ms.ouro",
    "decode_head_ms.ouro", "prefill_ut_loop_ms.ouro",
    "prefill_attn_proj_ms.ouro", "prefill_mlp_ms.ouro",
    "prefill_paged_attn_ms.ouro", "prefill_chunk_device_ms.ouro"]


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
         MANIFEST, "--workload", TINY, "--seed", "4100000061", "--seconds",
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
                            os.path.join(BENCH, "reference", "ouro.py"))
    assert os.path.samefile(detail["counts_file"],
                            os.path.join(BENCH, "counts", "ouro.py"))
    # no device lane on the CPU: the step-log and host metrics, and only
    # those; the trace readers return nothing and do not raise
    assert sorted(line["metrics"]) == sorted(STEP_LOG_METRICS + HOST_METRICS)
    assert line["metrics"]["ut_steps.ouro"]["value"] == 4.0
    assert 0.0 < line["metrics"]["ut_exit_mass_last_pct.ouro"]["value"] < 100


def test_cell_traffic_and_config_are_as_the_issue_gives_them():
    manifest = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert manifest["workloads"][-1] is cell
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "reason1k-saturated", 1)
    assert len(cell["why"]) <= 200
    tok = next(m for m in manifest["end_to_end"]
               if m["name"] == "serve_tok_per_s")
    assert tok["workloads"][-1] == CELL
    traffic = _json(BENCH, "traffic", "reason1k-saturated.json")
    assert traffic["kind"] == "open-loop-stratified"
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 192,
                                     "sigma": 0.6, "min": 64, "max": 512}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 320,
                                     "sigma": 0.5, "min": 128, "max": 1024}
    assert traffic["gaps"] == {"dist": "exponential"}
    assert traffic["sampling"] == {"temperature": 0.0}
    assert traffic["judge_ttft"] is False
    assert (traffic["trace_at_s"], traffic["trace_seconds"],
            traffic["order_seed"], traffic["rotate_by_seed"]) == (
        10, 3, 61, False)
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert (traffic["warm_in_s"], traffic["warm_in_burst"]) == (
        30, config["max_slots"]) == (30, 16)
    assert traffic["rate_per_s"] == pytest.approx(
        1.25 * traffic["knee_per_s"])
    assert config["reduced"] == ["max_position_embeddings"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert manifest["configs"][-1] is entry
    assert len(entry["why"]) <= 200
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert (config["max_position_embeddings"],
            config["max_position_embeddings_published"]) == (1536, 65536)
    check = config["correctness"]
    # three prefill chunks, the third of one real token
    assert check["prompt_tokens"] == 2 * config["prefill_chunk"] + 1
    assert check["requests"] == 1 and check["new_tokens"] >= 128
    assert check["min_positions"] == 128
    longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert longest == config["max_context"] == 1536
    assert config["max_context"] % config["prefill_chunk"] == 0
    for key in ("assumed", "departures", "deployment", "reduced_why",
                "left_out", "argv_why"):
        assert config[key], key
    assert "nothing of the language model" in config["left_out"]
    for key in ("loop", "block", "attention", "cache", "exit_gate"):
        assert config["assumed"][key], key


def test_config_keeps_every_number_of_the_catalog_entry():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
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
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.vocab_size, cfg.num_layers,
            cfg.total_ut_steps, cfg.stack_passes, cfg.max_seq,
            cfg.rope_theta, cfg.rms_norm_eps) == tuple(
        config[k] for k in (
            "hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "vocab_size",
            "num_hidden_layers", "total_ut_steps", "total_ut_steps",
            "max_position_embeddings", "rope_theta", "rms_norm_eps"))
    assert config["early_exit_threshold"] == 1
    assert config["tie_word_embeddings"] is False
    assert config["use_sliding_window"] is False
    assert 2 * sum(cfg.cache_rows.values) * cfg.num_layers \
        * cfg.stack_passes == config["cache_bytes_per_token"] == 1_572_864
    argv = config["argv"]
    for flag, key in (("--max-slots", "max_slots"),
                      ("--block-size", "block_size"),
                      ("--max-context", "max_context"),
                      ("--prefill-chunk", "prefill_chunk"),
                      ("--prefill-budget", "prefill_budget"),
                      ("--kv-blocks", "kv_blocks"),
                      ("--max-queue", "max_queue")):
        assert argv[argv.index(flag) + 1] == str(config[key]), flag


def test_counts_are_the_issues_arithmetic():
    counts = _module(os.path.join(BENCH, "counts", "ouro.py"))
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert counts.layer_params(config) + 4 * 2048 == 51_388_416
    assert counts.params_exact(config) == config["parameters"] \
        == 2_667_974_657
    assert round(counts.params_exact(config) * 2 / 1e9, 2) == 5.34
    assert counts.kv_bytes_per_token(config) == 1_572_864
    assert counts.layer_slots(config) == 192
    # a decode step streams the layers' weights four times: 19.7 GB
    layers = 48 * counts.layer_params(config)
    assert round(4 * layers * 2 / 1e9, 1) == 19.7
    lives = [410] * 9
    whole = counts.decode_kernel(config, "decode_iter", lives)
    assert whole["bytes"] == (4 * layers + 49152 * 2048) * 2 \
        + (9 * 410 + 9) * 1_572_864
    need = counts.decode_kernel(config, "paged_attn", lives)
    assert need["bytes"] == 9 * 410 * 1_572_864 + 192 * 9 * 2 * 2048 * 2
    assert need["flops"] == 192 * 9 * 410 * 16 * 4 * 128
    assert round(counts.flops_per_token(config) / 1e9, 1) == 19.9
    with pytest.raises(NotImplementedError):
        counts.train_flops_per_token(config, 1024)
    with pytest.raises(KeyError):
        counts.decode_kernel(config, "paged_latent_attn", lives)


def test_every_ouro_metric_is_a_file_the_rehearsal_lists_and_none_is_listed():
    names = sorted(os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(BENCH, "layer_metrics", "*.ouro.json")))
    for name in NAMED:
        assert name + ".ouro" in names, name
    rehearsal = _json(MANIFEST)
    listed = [m["name"] for m in rehearsal["per_layer"]]
    assert sorted(n for n in listed if n.endswith(".ouro")) == names
    for m in rehearsal["per_layer"]:
        if m["name"].endswith(".ouro"):
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
    assert rehearsal["workloads"][-1] == manifest["workloads"][-1]
    assert rehearsal["configs"][-1] == manifest["configs"][-1]
    assert len(manifest["per_layer"]) == 128
    assert not [m["name"] for m in manifest["per_layer"]
                if m["name"].endswith(".ouro")]
    # the roofline shares are held to counts/ouro.py's names
    counts = _module(os.path.join(BENCH, "counts", "ouro.py"))
    config = _json(BENCH, "configs", CONFIG + ".json")
    for name, reader in (
            ("paged_attn_roofline_pct", "trace_decode_kernel"),
            ("decode_roofline_pct", "trace_decode_kernel"),
            ("kv_chunk_attn_roofline_pct", "trace_scope_roofline")):
        spec = _json(BENCH, "layer_metrics", name + ".ouro.json")
        assert spec["reader"] == reader and spec["unit"] == "%"
        need = counts.decode_kernel(config, spec["args"]["required"],
                                    [300, 500])
        assert need["bytes"] > 0
    # the scale of the pool metric is this cell's pool
    spec = _json(BENCH, "layer_metrics", "kv_blocks_used_peak_pct.ouro.json")
    assert spec["args"]["scale"] == pytest.approx(100 / config["kv_blocks"])


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
def test_trace_metric_reads_a_span_in_the_loop_body(slice_ctx, name):
    """No new reader: ``trace_scope`` keeps ``ut_loop/while/body/h3/attn``
    of an operation's path, so a scope inside the device loop is matched as
    one outside it is, and the ``while`` itself is no operation's parent
    twice (``trace_reduce.self_times``)."""
    spec = _json(BENCH, "layer_metrics", name + ".json")
    reader = _module(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    value = reader.read(slice_ctx, spec["args"])
    assert value is not None and value > 0, name
    # a pattern that matches nothing in the slice reads nothing
    nothing = dict(spec["args"], scope="/no_such_scope(/|$)")
    assert not reader.read(slice_ctx, nothing)


def test_the_loop_holds_nearly_all_of_a_decode_step(slice_ctx):
    loop = _json(BENCH, "layer_metrics", "decode_ut_loop_ms.ouro.json")
    reader = _module(os.path.join(BENCH, "readers", "trace_scope.py"))
    inside = reader.read(slice_ctx, loop["args"])
    whole = reader.read(slice_ctx, dict(loop["args"], scope=""))
    assert 0.9 * whole < inside <= whole
    parts = sum(reader.read(slice_ctx, _json(
        BENCH, "layer_metrics", name + ".json")["args"]) for name in (
        "decode_attn_proj_ms.ouro", "decode_mlp_ms.ouro",
        "decode_norms_ms.ouro", "decode_paged_attn_ms.ouro",
        "decode_kv_write_ms.ouro"))
    assert parts <= inside * 1.001


def test_the_kernels_roofline_share_reads_the_recorded_slice(slice_ctx):
    """``paged_attn_roofline_pct.ouro`` and ``decode_roofline_pct.ouro`` off
    the slice with 9 sequences of 410 decoding: between 1 and 100 %, and
    nothing where the pattern matches no kernel."""
    live = {"token_times": [0.0], "token_counts": [1],
            "max_new_tokens": 600, "prompt_tokens": 410}
    ctx = dict(slice_ctx, trace=slice_ctx["trace"],
               trace_done={"t_begin": 10.0, "t_end": 13.0},
               epoch_zero=0.0, logs=[live] * 9, out="/nonexistent",
               config=_json(BENCH, "configs", CONFIG + ".json"),
               counts=_module(os.path.join(BENCH, "counts", "ouro.py")),
               device_kind="TPU v5 lite")
    for name in ("paged_attn_roofline_pct", "decode_roofline_pct"):
        spec = _json(BENCH, "layer_metrics", name + ".ouro.json")
        reader = _module(os.path.join(BENCH, "readers",
                                      spec["reader"] + ".py"))
        share = reader.read(ctx, spec["args"])
        assert 1.0 < share < 100.0, (name, share)
    spec = _json(BENCH, "layer_metrics", "paged_attn_roofline_pct.ouro.json")
    assert reader.read(ctx, dict(spec["args"], pattern="no_such_kernel")) \
        is None
