"""A rehearsal of ``lfm2-24b-serve-assist-saturated`` on the CPU:
``rehearsal/BENCHMARK-lfm2.json`` runs ``serve.py --config lfm2_tiny`` (a
dense conv layer, an attention layer of 4 query heads on 2 K/V heads, two conv
layers of 8 experts top 2; a chunk then a decode step an iteration) under a
tiny ``open-loop-stratified`` mix with the cell's own reference, counts,
readers and layer-metric files.  A CPU trace has no device lane, so the trace
readers leave their metrics out without raising; the step-log and host
metrics are read.  The trace metrics are read off a slice recorded on the
chip (``data/lfm2_slice.json.gz``: a part of this PR's traced run of the cell,
cut by ``tools/trace_check.py --cut``), and a pattern that matches nothing
there fails.  And the data files of the real cell agree with each other, with
the catalog and with ISSUE 45's parameters.

``BENCHMARK.json``'s ``per_layer`` is full (128 of 128): every ``.lfm2``
metric is a file that the rehearsal's manifest lists, beside the real cell
itself, so ``run.py --manifest .../BENCHMARK-lfm2.json --workload
lfm2-24b-serve-assist-saturated --trace 1`` reads them on the chip.
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
MANIFEST = os.path.join(HERE, "rehearsal", "BENCHMARK-lfm2.json")
SLICE = os.path.join(HERE, "data", "lfm2_slice.json.gz")
CELL = "lfm2-24b-serve-assist-saturated"
TINY = "lfm2-tiny-serve-assist"
CONFIG = "lfm2-24b-a2b-serve"
#: what ISSUE 45 names, each a file
NAMED = [
    "decode_experts_ms", "decode_router_ms", "prefill_experts_ms",
    "moe_experts_hit_pct", "moe_max_expert_load", "moe_grouped_roofline_pct",
    "paged_attn_roofline_pct", "decode_roofline_pct", "decode_paged_attn_ms",
    "prefill_attn_ms", "decode_kv_write_ms", "kv_blocks_used_peak_pct",
    "state_slots_used_peak_pct", "prefill_chunk_device_ms",
    "prefill_device_share_pct", "decode_iter_wall_ms", "prefill_iter_wall_ms",
    "decode_span_device_ms", "decode_span_host_ms", "decode_occupancy_mean",
    "decode_unscoped_pct", "idle_unattributed_pct", "loadgen_late_p95_ms",
    "itl_p95_ms", "setup_backend_s", "setup_init_params_s",
    # PR 36's nine host leaves
    "decode_dispatch_ms", "step_between_ms", "decode_commit_cpu_ms",
    "step_unnamed_pct", "step_wall_max_ms", "decode_fetch_ms",
    "engine_offcpu_ms", "stream_lag_p95_ms", "idle_unnamed_pct",
    # new with this cell
    "decode_conv_ms", "prefill_conv_ms"]
STEP_LOG_METRICS = [
    "decode_iter_wall_ms.lfm2", "decode_occupancy_mean.lfm2",
    "decode_device_sampled_pct.lfm2", "moe_experts_hit_pct.lfm2",
    "moe_max_expert_load.lfm2", "kv_blocks_used_peak_pct.lfm2",
    "state_slots_used_peak_pct.lfm2", "prefill_iter_wall_ms.lfm2",
    "decode_commit_cpu_ms.lfm2", "decode_dispatch_ms.lfm2",
    "decode_fetch_ms.lfm2", "engine_offcpu_ms.lfm2", "step_between_ms.lfm2",
    "step_unnamed_pct.lfm2", "step_wall_max_ms.lfm2",
    "stream_lag_p95_ms.lfm2"]
HOST_METRICS = [
    "loadgen_late_p95_ms.lfm2", "itl_p95_ms.lfm2", "ttft_mean_ms.lfm2",
    "setup_backend_s.lfm2", "setup_init_params_s.lfm2", "compile_s",
    "compiles_in_window"]
#: device time by scope, read off the recorded slice
SLICE_METRICS = [
    "decode_conv_ms.lfm2", "decode_experts_ms.lfm2", "decode_router_ms.lfm2",
    "decode_paged_attn_ms.lfm2", "decode_kv_write_ms.lfm2",
    "prefill_conv_ms.lfm2", "prefill_attn_ms.lfm2", "prefill_experts_ms.lfm2",
    "prefill_chunk_device_ms.lfm2"]


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
         MANIFEST, "--workload", TINY, "--seed", "4500000019", "--seconds",
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
                            os.path.join(BENCH, "reference", "lfm2.py"))
    assert os.path.samefile(detail["counts_file"],
                            os.path.join(BENCH, "counts", "lfm2.py"))
    # no device lane on the CPU: the step-log and host metrics, and only
    # those; the trace readers return nothing and do not raise
    assert sorted(line["metrics"]) == sorted(STEP_LOG_METRICS + HOST_METRICS)
    # one engine: the routing counters beside the state group's
    assert line["metrics"]["moe_experts_hit_pct.lfm2"]["value"] > 0
    assert line["metrics"]["state_slots_used_peak_pct.lfm2"]["value"] > 0


def test_cell_traffic_and_config_are_as_the_issue_gives_them():
    manifest = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "assist8k-saturated", 1)
    assert len(cell["why"]) <= 200
    tok = next(m for m in manifest["end_to_end"]
               if m["name"] == "serve_tok_per_s")
    assert CELL in tok["workloads"]
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    traffic = _json(BENCH, "traffic", "assist8k-saturated.json")
    assert traffic["kind"] == "open-loop-stratified"
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 1536,
                                     "sigma": 1.0, "min": 128, "max": 8192}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 256,
                                     "sigma": 0.7, "min": 32, "max": 1024}
    assert traffic["gaps"] == {"dist": "exponential"}
    assert traffic["sampling"] == {"temperature": 0.0}
    assert traffic["judge_ttft"] is False
    assert (traffic["trace_at_s"], traffic["trace_seconds"],
            traffic["order_seed"], traffic["rotate_by_seed"]) == (
        10, 3, 45, False)
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert (traffic["warm_in_s"], traffic["warm_in_burst"]) == (
        30, config["max_slots"])
    assert traffic["rate_per_s"] == pytest.approx(
        1.25 * traffic["knee_per_s"])
    assert sorted(config["reduced"]) == sorted([
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "max_position_embeddings"])
    assert set(config["reduced_why"]) == set(config["reduced"])
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert len(entry["why"]) <= 200
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    # layer 0 and published layers 2-9: two whole periods
    assert config["layer_types"] == [
        "conv", "full_attention", "conv", "conv", "conv", "full_attention",
        "conv", "conv", "conv"]
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["max_position_embeddings"]) == (9, 1, 9216)
    check = config["correctness"]
    # more than two chunks, ending inside one
    assert check["prompt_tokens"] > 2 * config["prefill_chunk"]
    assert check["prompt_tokens"] % config["prefill_chunk"]
    assert check["requests"] >= 2 and check["new_tokens"] >= 128
    longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert longest <= config["max_context"] == 9216
    for key in ("assumed", "departures", "deployment", "reduced_why"):
        assert config[key], key
    for key in ("equations_from", "head_dim", "embedding", "traffic"):
        assert config["assumed"][key], key


def test_config_keeps_every_number_of_the_catalog_entry():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    # the cut layer pattern is a stretch of the published one
    assert config["layer_types"][1:] == row["config"]["layer_types"][2:10]
    assert config["layer_types"][0] == row["config"]["layer_types"][0]
    # no width among the cuts
    for key in config["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")), key


def test_server_preset_is_the_configuration_file():
    sys.path.insert(0, ROOT)
    from distributedtensorflow_tpu import models

    config = _json(BENCH, "configs", CONFIG + ".json")
    cfg = getattr(models, config["system_config"])()
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.num_experts, cfg.experts_per_token, cfg.vocab_size,
            cfg.num_layers, cfg.num_dense_layers, cfg.conv_kernel,
            cfg.max_seq, cfg.norm_eps, cfg.route_norm, cfg.route_scale
            ) == tuple(config[k] for k in (
                "hidden_size", "num_attention_heads", "num_key_value_heads",
                "intermediate_size", "moe_intermediate_size", "num_experts",
                "num_experts_per_tok", "vocab_size", "num_hidden_layers",
                "num_dense_layers", "conv_L_cache",
                "max_position_embeddings", "norm_eps", "norm_topk_prob",
                "routed_scaling_factor"))
    assert list(cfg.layer_types) == config["layer_types"]
    assert cfg.head_dim == cfg.hidden_size // cfg.num_heads == 64
    assert cfg.rope_theta == config["rope_parameters"]["rope_theta"]
    assert cfg.held == (0, config["num_experts"])
    assert config["conv_bias"] is False and config["use_expert_bias"] is True
    assert 2 * sum(cfg.cache_rows.widths) * 2 \
        == config["cache_bytes_per_token"] == 4096
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
    counts = _module(os.path.join(BENCH, "counts", "lfm2.py"))
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert round(counts.conv_params(config) / 1e6, 1) == 16.8
    assert round(counts.attention_params(config) / 1e6, 1) == 10.5
    assert round(counts.expert_params(config) / 1e6, 2) == 9.44
    assert round(counts.params(config) / 1e6) == 5178
    assert counts.params(config) == config["parameters"]
    assert round(counts.params(config) * 2 / 1e9, 2) == 10.36
    assert counts.kv_bytes_per_token(config) == 4096
    assert counts.state_bytes_per_slot(config) == 7 * 8192
    # 96 slots: 384 picks over 64 experts, 63.8 hit under uniform routing
    assert round(counts.experts_hit(config, 96), 1) == 63.9
    lives = [2800] * 96
    need = counts.decode_kernel(config, "moe_grouped", lives)
    assert need["flops"] == 2.0 * 8 * 96 * 4 * counts.expert_params(config)
    hit = {"moe_experts_hit": 400.0, "moe_pairs": 3072.0}
    seen = counts.decode_kernel(config, "moe_grouped", lives, hit)
    assert seen["bytes"] == 400 * counts.expert_params(config) * 2
    attn = counts.decode_kernel(config, "paged_attn", lives)
    assert attn["bytes"] == 96 * 2800 * 4096 + 2 * 96 * 2 * 2048 * 2
    whole = counts.decode_kernel(config, "decode_iter", lives, hit)
    assert whole["bytes"] == (
        (counts.params_outside_experts(config)
         + 400 * counts.expert_params(config)) * 2
        + 2 * 96 * 7 * 8192 + 96 * 2800 * 4096)
    assert counts.step_kernel(config, "paged_attn") == attn
    with pytest.raises(NotImplementedError):
        counts.train_flops_per_token(config, 1024)
    with pytest.raises(KeyError):
        counts.decode_kernel(config, "ssm_chunk_scan", lives)


def test_every_lfm2_metric_is_a_file_the_rehearsal_lists_and_none_is_listed():
    names = sorted(os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(BENCH, "layer_metrics", "*.lfm2.json")))
    for name in NAMED:
        assert name + ".lfm2" in names, name
    rehearsal = _json(MANIFEST)
    listed = [m["name"] for m in rehearsal["per_layer"]]
    assert sorted(n for n in listed if n.endswith(".lfm2")) == names
    for m in rehearsal["per_layer"]:
        if m["name"].endswith(".lfm2"):
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
                if m["name"].endswith(".lfm2")]
    # the kernels' roofline shares are held to counts/lfm2.py's names
    counts = _module(os.path.join(BENCH, "counts", "lfm2.py"))
    config = _json(BENCH, "configs", CONFIG + ".json")
    for name in ("paged_attn_roofline_pct", "moe_grouped_roofline_pct",
                 "decode_roofline_pct"):
        spec = _json(BENCH, "layer_metrics", name + ".lfm2.json")
        assert spec["reader"] == "trace_decode_kernel"
        need = counts.decode_kernel(config, spec["args"]["required"],
                                    [900, 4000])
        assert need["bytes"] > 0
    # the scales of the pool metrics are this cell's pool, slots and experts
    spec = _json(BENCH, "layer_metrics", "kv_blocks_used_peak_pct.lfm2.json")
    assert spec["args"]["scale"] == pytest.approx(100 / config["kv_blocks"])
    spec = _json(BENCH, "layer_metrics",
                 "state_slots_used_peak_pct.lfm2.json")
    assert spec["args"]["scale"] == pytest.approx(100 / config["max_slots"])
    spec = _json(BENCH, "layer_metrics", "moe_experts_hit_pct.lfm2.json")
    assert spec["args"]["scale"] == pytest.approx(
        100 / (counts.expert_layers(config) * config["num_experts"]))
    # the conv metrics read the operator's scopes and the tails' traffic
    for name in ("decode_conv_ms", "prefill_conv_ms"):
        scope = _json(BENCH, "layer_metrics",
                      name + ".lfm2.json")["args"]["scope"]
        for part in ("/conv(", "/state_read(", "/state_write("):
            assert part in scope


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


def test_the_grouped_kernels_roofline_share_reads_the_recorded_slice(
        slice_ctx):
    """``moe_grouped_roofline_pct.lfm2`` off the slice with 96 sequences
    decoding (six tokens an expert, every expert hit): between 1 and 100 %,
    and nothing where the pattern matches no kernel."""
    spec = _json(BENCH, "layer_metrics", "moe_grouped_roofline_pct.lfm2.json")
    reader = _module(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    live = {"token_times": [0.0], "token_counts": [1],
            "max_new_tokens": 300, "prompt_tokens": 2500}
    ctx = dict(slice_ctx, trace=slice_ctx["trace"],
               trace_done={"t_begin": 10.0, "t_end": 13.0},
               epoch_zero=0.0, logs=[live] * 96, out="/nonexistent",
               config=_json(BENCH, "configs", CONFIG + ".json"),
               counts=_module(os.path.join(BENCH, "counts", "lfm2.py")),
               device_kind="TPU v5 lite")
    share = reader.read(ctx, spec["args"])
    assert 1.0 < share < 100.0, share
    assert reader.read(ctx, dict(spec["args"], pattern="no_such_kernel")) \
        is None
