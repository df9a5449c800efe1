"""A rehearsal of ``trinity-ep8-serve-reason-saturated`` on the CPU:
``rehearsal/BENCHMARK-trinity.json`` runs ``serve.py --config afmoe_tiny``
(8 of 16 experts held, window 32) under a tiny ``open-loop-stratified`` mix
with the cell's own reference, counts, readers and layer-metric files, its
check prompts across the window as the cell's are.  A CPU trace has no
device lane, so the trace readers leave their metrics out without raising;
the step-log metrics are read.  And the data files of the real cell agree
with each other and with ISSUE 28's parameters.  Slow (the first case
starts the program): run by hand with the other benchmark tests."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
MANIFEST = os.path.join(HERE, "rehearsal", "BENCHMARK-trinity.json")
CELL = "trinity-ep8-serve-reason-saturated"
STEP_LOG_METRICS = [
    "decode_iter_wall_ms.trinity", "decode_occupancy_mean.trinity",
    "moe_experts_hit_pct.trinity", "moe_max_expert_load.trinity",
    "kv_window_blocks_freed_per_s.trinity",
    "kv_blocks_used_peak_pct.window.trinity",
    "kv_blocks_used_peak_pct.full.trinity", "prefill_iter_wall_ms.trinity"]
#: read from the client's log and from the server's ``trace.jsonl``
HOST_METRICS = [
    "loadgen_late_p95_ms.trinity", "itl_p95_ms.trinity",
    "ttft_mean_ms.trinity", "setup_backend_s.trinity",
    "setup_init_params_s.trinity"]


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
         MANIFEST, "--workload", "trinity-tiny-serve-reason", "--seed",
         "2800000019", "--seconds", "6", "--trace", "1"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    detail = line["detail"]
    assert detail["positions_checked"] == 32
    assert os.path.samefile(detail["reference_file"],
                            os.path.join(BENCH, "reference", "trinity.py"))
    assert os.path.samefile(detail["counts_file"],
                            os.path.join(BENCH, "counts", "trinity.py"))
    # no device lane on the CPU: the step-log and host metrics, and only
    # those
    assert sorted(line["metrics"]) == sorted(STEP_LOG_METRICS + HOST_METRICS)
    assert line["metrics"]["kv_window_blocks_freed_per_s.trinity"][
        "value"] > 0
    assert 0 < line["metrics"]["moe_experts_hit_pct.trinity"]["value"] <= 100


def test_busy_control_scores_tokens_served_from_full_batches(capsys):
    """``tools/control_served.py --busy``: the check's requests beside a
    short request in every other slot of the configuration's own engine."""
    tool = _module(os.path.join(BENCH, "tools", "control_served.py"))
    tool.main(["--busy", "--sound", os.path.join(
        HERE, "rehearsal", "configs", "trinity-tiny-serve.json"), "5"])
    row = json.loads(capsys.readouterr().out.strip().splitlines()[0])
    assert row["sound_ok"] and row["sound_busy_occupancy"] == [4, 4.0]
    # 2 x 16 of the check, 2 x 96 of the fillers (4 slots, contexts of 128)
    assert row["sound"]["positions_checked"] == 32 + 192
    assert row["control"]["mean_regret"] > row["sound"]["mean_regret"]


def test_cell_traffic_and_config_are_as_the_issue_gives_them():
    manifest = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-large-ep8-serve", "reason-saturated", 1)
    traffic = _json(BENCH, "traffic", "reason-saturated.json")
    assert traffic["kind"] == "open-loop-stratified"
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 512,
                                     "sigma": 1.4, "min": 32, "max": 6144}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 768,
                                     "sigma": 0.6, "min": 128, "max": 2048}
    assert traffic["gaps"] == {"dist": "exponential"}
    assert (traffic["warm_in_s"], traffic["warm_in_burst"]) == (30, 64)
    assert traffic["rotate_by_seed"] is False
    assert traffic["judge_ttft"] is False
    assert traffic["sampling"] == {"temperature": 0.0}
    assert traffic["rate_per_s"] == pytest.approx(
        1.25 * traffic["knee_per_s"])
    config = _json(BENCH, "configs", "trinity-large-ep8-serve.json")
    for key, want in (
            ("hidden_size", 3072), ("num_attention_heads", 48),
            ("num_key_value_heads", 8), ("head_dim", 128),
            ("intermediate_size", 12288), ("moe_intermediate_size", 3072),
            ("num_experts_published", 256), ("num_experts_per_tok", 4),
            ("route_scale", 2.448), ("sliding_window", 4096),
            ("rope_theta", 10000), ("rms_norm_eps", 1e-5),
            ("num_hidden_layers", 5), ("num_dense_layers", 1),
            ("num_experts", 32), ("expert_first", 0),
            ("vocab_size", 25024), ("max_position_embeddings", 8192),
            ("param_dtype_bytes", 2), ("compute_dtype_bytes", 2)):
        assert config[key] == want, key
    assert config["layer_types"] == ["sliding_attention"] * 4 \
        + ["full_attention"]
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cell["config"])
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    check = config["correctness"]
    assert check["prompt_tokens"] >= config["sliding_window"] \
        + 2 * config["block_size"]
    assert check["requests"] * check["new_tokens"] >= 96


def test_config_keeps_every_number_of_the_catalog_entry():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Large-Preview")
    config = _json(BENCH, "configs", "trinity-large-ep8-serve.json")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key


def test_server_preset_is_the_configuration_file():
    sys.path.insert(0, ROOT)
    from distributedtensorflow_tpu import models

    config = _json(BENCH, "configs", "trinity-large-ep8-serve.json")
    cfg = getattr(models, config["system_config"])()
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.num_experts, cfg.experts_per_token, cfg.vocab_size) == tuple(
        config[k] for k in (
            "hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "moe_intermediate_size",
            "num_experts_published", "num_experts_per_tok", "vocab_size"))
    assert cfg.held == (config["expert_first"], config["num_experts"])
    assert list(cfg.layer_types) == config["layer_types"]
    assert (cfg.sliding_window, cfg.route_scale, cfg.rms_norm_eps,
            cfg.rope_theta, cfg.num_dense_layers) == (
        config["sliding_window"], config["route_scale"],
        config["rms_norm_eps"], config["rope_theta"],
        config["num_dense_layers"])
    argv = config["argv"]
    assert argv[argv.index("--max-slots") + 1] == str(config["max_slots"])
    assert argv[argv.index("--prefill-chunk") + 1] == "512"


def test_counts_are_the_issues_bytes():
    counts = _module(os.path.join(BENCH, "counts", "trinity.py"))
    config = _json(BENCH, "configs", "trinity-large-ep8-serve.json")
    outside = counts.params_outside_experts(config)
    held = 4 * 32 * counts.expert_params(config)
    assert round(outside * 2 / 1e9, 2) == 1.24          # GB outside experts
    assert round(32 * counts.expert_params(config) * 2 / 1e9, 2) == 1.81
    # + the embedding table (a gather, not a matmul): 4,322 M parameters
    total = outside + held + config["vocab_size"] * config["hidden_size"]
    assert round(total / 1e6) == 4322
    assert counts.kv_bytes_per_token_layer(config) == 4096
    assert round(counts.experts_hit(config, 64) / 32, 2) == 0.64
    lives = [6000, 100]
    assert counts.attended_tokens(config, lives) == 4 * (4096 + 100) + 6100
    with pytest.raises(NotImplementedError, match="no trainer"):
        counts.train_flops_per_token(config, 1024)
    need = counts.decode_kernel(config, "decode_iter", [1500] * 64)
    assert need["bytes"] == pytest.approx(
        counts.decode_iter_bytes(config, 64 * 1500, 2))


@pytest.mark.parametrize("name", STEP_LOG_METRICS + HOST_METRICS + [
    "idle_unattributed_pct.trinity", "engine_log_ms.trinity",
    "decode_kv_write_ms.trinity",
    "decode_span_device_ms.trinity", "decode_span_host_ms.trinity",
    "decode_experts_ms.trinity", "decode_router_ms.trinity",
    "decode_shared_expert_ms.trinity", "decode_window_attn_ms.trinity",
    "decode_full_attn_ms.trinity", "decode_unscoped_pct.trinity",
    "prefill_chunk_device_ms.trinity", "decode_roofline_pct.trinity",
    "moe_grouped_roofline_pct.trinity", "paged_attn_roofline_pct.trinity"])
def test_layer_metric_file_matches_its_manifest_entry(name):
    manifest = _json(ROOT, "BENCHMARK.json")
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    spec = _json(BENCH, "layer_metrics", name + ".json")
    assert entry["workloads"] == spec["workloads"] == [CELL]
    assert entry["moves"] == spec["moves"] == (
        "setup_s" if name.startswith("setup_") else "serve_tok_per_s")
    assert (entry["layer"], entry["unit"]) == (spec["layer"], spec["unit"])
    assert os.path.exists(os.path.join(BENCH, "readers",
                                       spec["reader"] + ".py"))
