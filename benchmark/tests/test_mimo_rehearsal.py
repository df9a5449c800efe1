"""A rehearsal of ``mimo-v2.5-ep16-serve-agent64k-saturated`` on the CPU:
``rehearsal/BENCHMARK-mimo.json`` runs ``serve.py --config mimo_tiny`` (both
layer kinds with 1 and 2 K/V heads, keys 24 over values 16, sinks, window 32
of contexts to 120, 8 of 16 experts held) under a tiny
``open-loop-stratified`` mix with the cell's own reference, counts, readers
and layer-metric files.  A CPU trace has no device lane, so the trace readers
leave their metrics out without raising; the step-log and host metrics are
read.  The trace metrics are read off a slice recorded on the chip
(``data/mimo_slice.json.gz``: a part of this PR's traced run of the cell, cut
by ``tools/trace_check.py --cut``), and a pattern that matches nothing there
fails.  And the data files of the real cell agree with each other, with the
catalog and with ISSUE 41's parameters.

``BENCHMARK.json``'s ``per_layer`` is full (128 of 128): every ``.mimo``
metric is a file that the rehearsal's manifest lists, beside the real cell
itself, so ``run.py --manifest .../BENCHMARK-mimo.json --workload
mimo-v2.5-ep16-serve-agent64k-saturated --trace 1`` reads them on the chip.
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
MANIFEST = os.path.join(HERE, "rehearsal", "BENCHMARK-mimo.json")
SLICE = os.path.join(HERE, "data", "mimo_slice.json.gz")
CELL = "mimo-v2.5-ep16-serve-agent64k-saturated"
TINY = "mimo-tiny-serve-agent"
CONFIG = "mimo-v2.5-ep16-serve"
#: what ISSUE 41 names, each a file
NAMED = [
    "decode_window_attn_ms", "decode_full_attn_ms", "decode_experts_ms",
    "decode_router_ms", "decode_roofline_pct", "paged_attn_roofline_pct",
    "moe_grouped_roofline_pct", "moe_experts_hit_pct", "moe_max_expert_load",
    "kv_blocks_used_peak_pct.window", "kv_blocks_used_peak_pct.full",
    "kv_window_blocks_freed_per_s", "decode_span_host_ms",
    "decode_span_device_ms", "decode_occupancy_mean", "itl_p95_ms",
    "setup_backend_s", "setup_init_params_s",
    # PR 36's nine host leaves
    "decode_dispatch_ms", "step_between_ms", "decode_commit_cpu_ms",
    "step_unnamed_pct", "step_wall_max_ms", "decode_fetch_ms",
    "engine_offcpu_ms", "stream_lag_p95_ms", "idle_unnamed_pct",
    # new with this cell
    "prefill_window_attn_ms", "prefill_full_attn_ms", "prefill_experts_ms",
    "prefill_device_share_pct", "decode_full_rows_read_mean"]
STEP_LOG_METRICS = [
    "decode_iter_wall_ms.mimo", "decode_occupancy_mean.mimo",
    "decode_device_sampled_pct.mimo", "moe_experts_hit_pct.mimo",
    "moe_max_expert_load.mimo", "kv_blocks_used_peak_pct.full.mimo",
    "kv_blocks_used_peak_pct.window.mimo",
    "kv_window_blocks_freed_per_s.mimo", "decode_full_rows_read_mean.mimo",
    "prefill_iter_wall_ms.mimo", "decode_commit_cpu_ms.mimo",
    "decode_dispatch_ms.mimo", "decode_fetch_ms.mimo",
    "engine_offcpu_ms.mimo", "step_between_ms.mimo",
    "step_unnamed_pct.mimo", "step_wall_max_ms.mimo",
    "stream_lag_p95_ms.mimo"]
HOST_METRICS = [
    "loadgen_late_p95_ms.mimo", "itl_p95_ms.mimo", "setup_backend_s.mimo",
    "setup_init_params_s.mimo", "compile_s", "compiles_in_window"]
#: device time by scope, read off the recorded slice
SLICE_METRICS = [
    "decode_window_attn_ms.mimo", "decode_full_attn_ms.mimo",
    "decode_experts_ms.mimo", "decode_router_ms.mimo",
    "decode_kv_write_ms.mimo", "prefill_window_attn_ms.mimo",
    "prefill_full_attn_ms.mimo", "prefill_experts_ms.mimo",
    "prefill_chunk_device_ms.mimo"]


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
         MANIFEST, "--workload", TINY, "--seed", "4100000019", "--seconds",
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
                            os.path.join(BENCH, "reference", "mimo.py"))
    assert os.path.samefile(detail["counts_file"],
                            os.path.join(BENCH, "counts", "mimo.py"))
    # no device lane on the CPU: the step-log and host metrics, and only
    # those; the trace readers return nothing and do not raise
    assert sorted(line["metrics"]) == sorted(STEP_LOG_METRICS + HOST_METRICS)
    # two full layers: rows read are twice the live tokens
    assert line["metrics"]["decode_full_rows_read_mean.mimo"]["value"] > 0
    assert line["metrics"]["kv_window_blocks_freed_per_s.mimo"]["value"] > 0


def test_cell_traffic_and_config_are_as_the_issue_gives_them():
    manifest = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert manifest["workloads"][-1] is cell
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "agent64k-saturated", 1)
    assert len(cell["why"]) <= 200
    tok = next(m for m in manifest["end_to_end"]
               if m["name"] == "serve_tok_per_s")
    assert tok["workloads"][-1] == CELL
    traffic = _json(BENCH, "traffic", "agent64k-saturated.json")
    assert traffic["kind"] == "open-loop-stratified"
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 16384,
                                     "sigma": 0.8, "min": 2048, "max": 65536}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 512,
                                     "sigma": 0.6, "min": 128, "max": 2048}
    assert traffic["gaps"] == {"dist": "exponential"}
    assert traffic["sampling"] == {"temperature": 0.0}
    assert traffic["judge_ttft"] is False
    assert (traffic["trace_at_s"], traffic["trace_seconds"],
            traffic["order_seed"], traffic["rotate_by_seed"]) == (
        10, 3, 41, False)
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert (traffic["warm_in_s"], traffic["warm_in_burst"]) == (
        30, config["max_slots"])
    assert traffic["rate_per_s"] == pytest.approx(
        1.25 * traffic["knee_per_s"])
    # every token, prefilled or decoded, lies past the window
    assert traffic["prompt_len"]["min"] > 2 * config["sliding_window"]
    assert sorted(config["reduced"]) == sorted([
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size", "max_position_embeddings"])
    assert set(config["reduced_why"]) == set(config["reduced"])
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert manifest["configs"][-1] is entry
    assert len(entry["why"]) <= 200
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    for key, want in (("num_hidden_layers", 7),
                      ("hybrid_layer_pattern", [0, 1, 1, 1, 1, 1, 0]),
                      ("moe_layer_freq", [0, 1, 1, 1, 1, 1, 1]),
                      ("n_routed_experts", 16),
                      ("n_routed_experts_published", 256),
                      ("vocab_size", 19072),
                      ("max_position_embeddings", 67584)):
        assert config[key] == want, key
    check = config["correctness"]
    assert check["prompt_tokens"] > 2 * config["prefill_chunk"]
    assert check["requests"] >= 2 and check["new_tokens"] >= 128
    longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert longest <= config["max_context"] == 67584
    for key in ("assumed", "departures", "deployment", "reduced_why",
                "left_out"):
        assert config[key], key
    assert "tower" in config["left_out"] and "prediction" in config["left_out"]


def test_config_keeps_every_number_of_the_catalog_entry():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2.5")
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    # no width among the cuts
    for key in config["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")) \
            or key == "vocab_size", key


def test_server_preset_is_the_configuration_file():
    sys.path.insert(0, ROOT)
    from distributedtensorflow_tpu import models

    config = _json(BENCH, "configs", CONFIG + ".json")
    cfg = getattr(models, config["system_config"])()
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.swa_num_kv_heads, cfg.head_dim, cfg.v_head_dim,
            cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.num_experts, cfg.experts_per_token, cfg.vocab_size,
            cfg.num_layers, cfg.sliding_window, cfg.max_seq) == tuple(
        config[k] for k in (
            "hidden_size", "num_attention_heads", "num_key_value_heads",
            "swa_num_key_value_heads", "head_dim", "v_head_dim",
            "intermediate_size", "moe_intermediate_size",
            "n_routed_experts_published", "num_experts_per_tok",
            "vocab_size", "num_hidden_layers", "sliding_window",
            "max_position_embeddings"))
    assert (config["swa_head_dim"], config["swa_v_head_dim"],
            config["swa_num_attention_heads"]) == (
        cfg.head_dim, cfg.v_head_dim, cfg.num_heads)
    assert cfg.rotary_dim == int(
        config["head_dim"] * config["partial_rotary_factor"]) == 64
    assert cfg.held == (config["expert_first"], config["n_routed_experts"])
    assert (list(cfg.layer_pattern), list(cfg.moe_layers)) == (
        config["hybrid_layer_pattern"], config["moe_layer_freq"])
    assert (cfg.rope_theta, cfg.swa_rope_theta, cfg.value_scale,
            cfg.rms_norm_eps) == (
        config["rope_theta"], config["swa_rope_theta"],
        config["attention_value_scale"], config["layernorm_epsilon"])
    assert config["n_group"] == config["topk_group"] == 1
    assert config["n_shared_experts"] is None
    rows = cfg.cache_rows
    for name, laid in config["cache_bytes_per_token"].items():
        assert 2 * sum(rows[name].values) == laid["values"]
        assert 2 * sum(rows[name].widths) == laid["laid_out"]
    argv = config["argv"]
    for flag, key in (("--max-slots", "max_slots"),
                      ("--block-size", "block_size"),
                      ("--max-context", "max_context"),
                      ("--prefill-chunk", "prefill_chunk"),
                      ("--kv-blocks", "kv_blocks"),
                      ("--kv-window-blocks", "kv_window_blocks"),
                      ("--max-queue", "max_queue")):
        assert argv[argv.index(flag) + 1] == str(config[key]), flag
    # a slot's ring: the window, a chunk of write-ahead, one more block
    ring = -(-(config["sliding_window"] + config["prefill_chunk"])
             // config["block_size"]) + 1
    assert config["kv_window_blocks"] == ring * config["max_slots"]


def test_counts_are_the_issues_arithmetic():
    counts = _module(os.path.join(BENCH, "counts", "mimo.py"))
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert round(counts.attention_params(config, 0) / 1e6, 2) == 89.13
    assert round(counts.attention_params(config, 1) / 1e6, 2) == 94.37
    assert round(counts.expert_params(config) / 1e6, 2) == 25.17
    assert round(counts.weight_params(config) / 1e6, 1) == 3430.0
    assert counts.weight_params(config) == config["parameters"]
    assert round(counts.weight_params(config) * 2 / 1e9, 2) == 6.86
    assert counts.kv_bytes_per_token_layer(config, 0) == 2560
    assert counts.kv_bytes_per_token_layer(config, 1) == 5120
    lives = [25000] * 32
    need = counts.decode_kernel(config, "paged_attn", lives)
    assert need["bytes"] == 32 * (2 * 25000 * 2560 + 5 * 128 * 5120)
    assert need["flops"] == 32 * 2 * 64 * 320 * (2 * 25000 + 5 * 128)
    whole = counts.decode_kernel(config, "decode_iter", lives)
    assert whole["bytes"] > need["bytes"] + 2 * counts.params_outside_experts(
        config)
    with pytest.raises(NotImplementedError):
        counts.train_flops_per_token(config, 1024)
    with pytest.raises(KeyError):
        counts.decode_kernel(config, "paged_latent_attn", lives)


def test_every_mimo_metric_is_a_file_the_rehearsal_lists_and_none_is_listed():
    names = sorted(os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(BENCH, "layer_metrics", "*.mimo.json")))
    for name in NAMED:
        assert name + ".mimo" in names, name
    rehearsal = _json(MANIFEST)
    listed = [m["name"] for m in rehearsal["per_layer"]]
    assert sorted(n for n in listed if n.endswith(".mimo")) == names
    for m in rehearsal["per_layer"]:
        if m["name"].endswith(".mimo"):
            spec = _json(BENCH, "layer_metrics", m["name"] + ".json")
            assert spec["workloads"] == [CELL]
            assert m["workloads"] == [TINY, CELL]
            assert (m["unit"], m["layer"], m["moves"]) == (
                spec["unit"], spec["layer"], spec["moves"])
            assert spec["moves"] == ("setup_s" if m["name"].startswith(
                "setup_") else "serve_tok_per_s")
            assert os.path.exists(os.path.join(
                BENCH, "readers", spec["reader"] + ".py"))
    assert rehearsal["workloads"][-1] == _json(ROOT, "BENCHMARK.json")[
        "workloads"][-1]
    manifest = _json(ROOT, "BENCHMARK.json")
    assert len(manifest["per_layer"]) == 128
    assert not [m["name"] for m in manifest["per_layer"]
                if m["name"].endswith(".mimo")]
    # the kernels' roofline shares are held to counts/mimo.py's names
    counts = _module(os.path.join(BENCH, "counts", "mimo.py"))
    config = _json(BENCH, "configs", CONFIG + ".json")
    for name in ("paged_attn_roofline_pct", "moe_grouped_roofline_pct",
                 "decode_roofline_pct"):
        spec = _json(BENCH, "layer_metrics", name + ".mimo.json")
        assert spec["reader"] == "trace_decode_kernel"
        need = counts.decode_kernel(config, spec["args"]["required"],
                                    [9000, 40000])
        assert need["bytes"] > 0
    # the scales of the pool metrics are this cell's pools
    for group, key in (("full", "kv_blocks"), ("window", "kv_window_blocks")):
        spec = _json(BENCH, "layer_metrics",
                     f"kv_blocks_used_peak_pct.{group}.mimo.json")
        assert spec["args"]["scale"] == pytest.approx(100 / config[key])
    spec = _json(BENCH, "layer_metrics", "moe_experts_hit_pct.mimo.json")
    assert spec["args"]["scale"] == pytest.approx(
        100 / (sum(config["moe_layer_freq"]) * config["n_routed_experts"]))


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


def test_the_kernels_roofline_share_reads_the_recorded_slice(slice_ctx):
    """``paged_attn_roofline_pct.mimo`` off the slice with 24 sequences of
    20 k decoding: between 1 and 100 % — the kernel is bound by the matrix
    unit's weight loads, not by HBM (PERF.md section 5) — and nothing where
    the pattern matches no kernel."""
    spec = _json(BENCH, "layer_metrics", "paged_attn_roofline_pct.mimo.json")
    reader = _module(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    live = {"token_times": [0.0], "token_counts": [1],
            "max_new_tokens": 600, "prompt_tokens": 20000}
    ctx = dict(slice_ctx, trace=slice_ctx["trace"],
               trace_done={"t_begin": 10.0, "t_end": 13.0},
               epoch_zero=0.0, logs=[live] * 24, out="/nonexistent",
               config=_json(BENCH, "configs", CONFIG + ".json"),
               counts=_module(os.path.join(BENCH, "counts", "mimo.py")),
               device_kind="TPU v5 lite")
    share = reader.read(ctx, spec["args"])
    assert 1.0 < share < 100.0, share
    assert reader.read(ctx, dict(spec["args"], pattern="no_such_kernel")) \
        is None
