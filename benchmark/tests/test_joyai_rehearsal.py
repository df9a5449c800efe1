"""A rehearsal of ``joyai-flash-serve-longdoc-saturated`` on the CPU:
``rehearsal/BENCHMARK-joyai.json`` runs ``serve.py --config joyai_tiny``
(latent rows of 32 + 8, 16 experts top 4) under a tiny
``open-loop-stratified`` mix with the cell's own reference, counts, readers
and layer-metric files.  A CPU trace has no device lane, so the trace readers
leave their metrics out without raising; the step-log metrics are read.  And
the data files of the real cell agree with each other and with ISSUE 32's
parameters.  Slow (the first case starts the program): run by hand with the
other benchmark tests."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
MANIFEST = os.path.join(HERE, "rehearsal", "BENCHMARK-joyai.json")
CELL = "joyai-flash-serve-longdoc-saturated"
CONFIG = "joyai-llm-flash-serve"
STEP_LOG_METRICS = [
    "decode_iter_wall_ms.joyai", "decode_occupancy_mean.joyai",
    "decode_device_sampled_pct.joyai", "moe_experts_hit_pct.joyai",
    "moe_max_expert_load.joyai", "kv_latent_blocks_used_peak_pct.joyai",
    "decode_latent_rows_read_mean.joyai", "prefill_iter_wall_ms.joyai"]
#: read from the client's log and from the server's ``trace.jsonl``
HOST_METRICS = [
    "loadgen_late_p95_ms.joyai", "itl_p95_ms.joyai", "ttft_mean_ms.joyai",
    "setup_backend_s.joyai", "setup_init_params_s.joyai"]
TRACE_METRICS = [
    "decode_latent_attn_ms.joyai", "latent_attn_roofline_pct.joyai",
    "decode_latent_proj_ms.joyai", "prefill_latent_attn_ms.joyai",
    "prefill_experts_ms.joyai", "prefill_device_share_pct.joyai",
    "decode_experts_ms.joyai", "decode_router_ms.joyai",
    "decode_shared_expert_ms.joyai", "moe_grouped_roofline_pct.joyai",
    "decode_span_host_ms.joyai", "decode_span_device_ms.joyai",
    "decode_commit_ms.joyai", "decode_roofline_pct.joyai",
    "decode_unscoped_pct.joyai", "decode_kv_write_ms.joyai",
    "prefill_chunk_device_ms.joyai", "idle_unattributed_pct.joyai",
    "engine_log_ms.joyai"]


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
         MANIFEST, "--workload", "joyai-tiny-serve-longdoc", "--seed",
         "3200000019", "--seconds", "6", "--trace", "1"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    detail = line["detail"]
    assert detail["positions_checked"] == 32
    assert os.path.samefile(detail["reference_file"],
                            os.path.join(BENCH, "reference", "joyai.py"))
    assert os.path.samefile(detail["counts_file"],
                            os.path.join(BENCH, "counts", "joyai.py"))
    # no device lane on the CPU: the step-log and host metrics, and only
    # those; the trace readers return nothing and do not raise
    assert sorted(line["metrics"]) == sorted(STEP_LOG_METRICS + HOST_METRICS)
    assert line["metrics"]["decode_latent_rows_read_mean.joyai"]["value"] > 0
    assert 0 < line["metrics"]["moe_experts_hit_pct.joyai"]["value"] <= 100


def test_cell_traffic_and_config_are_as_the_issue_gives_them():
    manifest = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longdoc-saturated", 1)
    assert manifest["workloads"][-1] is cell        # appended, not inserted
    tok = next(m for m in manifest["end_to_end"]
               if m["name"] == "serve_tok_per_s")
    assert tok["workloads"][-1] == CELL
    traffic = _json(BENCH, "traffic", "longdoc-saturated.json")
    assert traffic["kind"] == "open-loop-stratified"
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 8192,
                                     "sigma": 0.7, "min": 1024, "max": 15360}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 256,
                                     "sigma": 0.6, "min": 64, "max": 768}
    assert traffic["gaps"] == {"dist": "exponential"}
    assert (traffic["warm_in_s"], traffic["warm_in_burst"]) == (30, 32)
    assert (traffic["trace_at_s"], traffic["trace_seconds"]) == (10, 3)
    assert traffic["rotate_by_seed"] is False
    assert traffic["judge_ttft"] is False
    assert traffic["sampling"] == {"temperature": 0.0}
    assert traffic["rate_per_s"] == pytest.approx(
        1.25 * traffic["knee_per_s"])
    config = _json(BENCH, "configs", CONFIG + ".json")
    for key, want in (
            ("hidden_size", 2048), ("num_attention_heads", 32),
            ("q_lora_rank", 1536), ("kv_lora_rank", 512),
            ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64),
            ("v_head_dim", 128), ("intermediate_size", 7168),
            ("moe_intermediate_size", 768), ("n_routed_experts", 256),
            ("num_experts_per_tok", 8), ("n_shared_experts", 1),
            ("routed_scaling_factor", 2.5), ("rope_theta", 32000000),
            ("rms_norm_eps", 1e-6), ("first_k_dense_replace", 1),
            ("vocab_size", 129280), ("num_hidden_layers", 5),
            ("num_nextn_predict_layers", 0),
            ("max_position_embeddings", 16384),
            ("param_dtype_bytes", 2), ("compute_dtype_bytes", 2)):
        assert config[key] == want, key
    assert sorted(config["reduced"]) == [
        "max_position_embeddings", "num_hidden_layers",
        "num_nextn_predict_layers"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert manifest["configs"][-1] is entry
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    check = config["correctness"]
    assert check["prompt_tokens"] >= 4096 + config["block_size"] + 1
    assert check["requests"] >= 2 and check["new_tokens"] >= 256
    assert check["min_positions"] >= 512
    # the longest request fits a slot, and the pool holds every slot's mean
    longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert longest <= config["max_context"] == 16384


def test_config_keeps_every_number_of_the_catalog_entry():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "JoyAI-LLM-Flash")
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key


def test_server_preset_is_the_configuration_file():
    sys.path.insert(0, ROOT)
    from distributedtensorflow_tpu import models

    config = _json(BENCH, "configs", CONFIG + ".json")
    cfg = getattr(models, config["system_config"])()
    assert (cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.num_experts,
            cfg.experts_per_token, cfg.vocab_size, cfg.num_layers,
            cfg.num_dense_layers, cfg.n_group, cfg.topk_group) == tuple(
        config[k] for k in (
            "hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "moe_intermediate_size",
            "n_routed_experts", "num_experts_per_tok", "vocab_size",
            "num_hidden_layers", "first_k_dense_replace", "n_group",
            "topk_group"))
    assert cfg.held == (0, 256)
    assert (cfg.route_scale, cfg.route_norm, cfg.rms_norm_eps,
            cfg.rope_theta) == (
        config["routed_scaling_factor"], config["norm_topk_prob"],
        config["rms_norm_eps"], config["rope_theta"])
    assert config["rope_interleave"] is True and config["rope_scaling"] is None
    assert cfg.cache_rows.values == (576,)
    argv = config["argv"]
    for flag, key in (("--max-slots", "max_slots"),
                      ("--block-size", "block_size"),
                      ("--max-context", "max_context"),
                      ("--prefill-chunk", "prefill_chunk"),
                      ("--kv-blocks", "kv_blocks"),
                      ("--max-queue", "max_queue")):
        assert argv[argv.index(flag) + 1] == str(config[key]), flag


def test_counts_are_the_issues_bytes():
    counts = _module(os.path.join(BENCH, "counts", "joyai.py"))
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert round(counts.attention_params(config) / 1e6, 2) == 26.35
    assert round(counts.expert_params(config) / 1e6, 2) == 4.72
    outside = counts.params_outside_experts(config)
    experts = 4 * 256 * counts.expert_params(config)
    # + the embedding table (a gather, not a matmul): 5,558 M parameters
    total = outside + experts + config["vocab_size"] * config["hidden_size"]
    assert round(total / 1e6) == 5558
    assert round(total * 2 / 1e9, 2) == 11.12
    assert counts.latent_row_bytes(config) == 1152
    assert counts.latent_attn_flops_per_row(config) == 2 * 32 * (576 + 512)
    lives = [6000, 100]
    assert counts.attended_rows(config, lives) == 5 * 6100
    need = counts.decode_kernel(config, "paged_latent_attn", lives)
    assert need["flops"] == 5 * 6100 * 2 * 32 * 1088
    assert need["bytes"] == 5 * 6100 * 1152 + 5 * 2 * 32 * 1088 * 2
    assert round(counts.experts_hit(config, 32) / 256, 2) == 0.64
    observed = {"moe_experts_hit": 400.0, "moe_pairs": 1024.0}
    grouped = counts.decode_kernel(config, "moe_grouped", [9000] * 32,
                                   observed)
    assert grouped == {"flops": 2.0 * 1024 * 3 * 2048 * 768,
                       "bytes": 400 * 3 * 2048 * 768 * 2}
    with pytest.raises(NotImplementedError, match="no trainer"):
        counts.train_flops_per_token(config, 1024)
    need = counts.decode_kernel(config, "decode_iter", [9000] * 32)
    assert need["bytes"] == pytest.approx(
        counts.decode_iter_bytes(config, 32 * 9000, 2))
    assert counts.step_kernel(config, "paged_latent_attn")["bytes"] > 0
    with pytest.raises(KeyError):
        counts.decode_kernel(config, "paged_attn", lives)


@pytest.mark.parametrize(
    "name", STEP_LOG_METRICS + HOST_METRICS + TRACE_METRICS)
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
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_every_joyai_metric_is_listed_and_scaled_to_the_cell():
    manifest = _json(ROOT, "BENCHMARK.json")
    mine = [m["name"] for m in manifest["per_layer"]
            if m["name"].endswith(".joyai")]
    assert sorted(mine) == sorted(
        STEP_LOG_METRICS + HOST_METRICS + TRACE_METRICS)
    assert [m["name"] for m in manifest["per_layer"]][-len(mine):] == mine
    config = _json(BENCH, "configs", CONFIG + ".json")
    peak = _json(BENCH, "layer_metrics",
                 "kv_latent_blocks_used_peak_pct.joyai.json")
    assert peak["args"]["scale"] == pytest.approx(100 / config["kv_blocks"])
    hit = _json(BENCH, "layer_metrics", "moe_experts_hit_pct.joyai.json")
    assert hit["args"]["scale"] == pytest.approx(100 / (4 * 256))


def test_program_share_reader_counts_every_execution():
    sys.path.insert(0, BENCH)
    reader = _module(os.path.join(BENCH, "readers",
                                  "trace_program_share.py"))
    assert reader.read({"trace": None}, {"program": "^jit_x"}) is None
    assert reader.read({"trace": {"devices": {}}}, {"program": "x"}) is None
    dev = {"modules": [("jit_prefill_chunk(1)", 0.0, 1.0),
                       ("jit_decode(2)", 1.0, 1.0),
                       ("jit_prefill_chunk(1)", 2.0, 0.2)],
           "ops": [("fusion.1", 0.0, 1.0), ("fusion.2", 1.0, 0.5),
                   ("fusion.3", 2.0, 0.2)]}
    ctx = {"trace": {"devices": {"/device:TPU:0": dev}}}
    # 1.0 + 0.2 s of 1.7 busy, the short execution counted too
    assert reader.read(ctx, {"program": "^jit_prefill_chunk"}) \
        == pytest.approx(100 * 1.2 / 1.7)
    assert reader.read(ctx, {"program": "^jit_absent"}) is None


def test_busy_check_in_two_processes_scores_full_batches():
    """``tools/busy_served.py``: the check's requests beside a short request
    in every other slot, served here and scored by a child on the CPU (at
    the real size ``control_served.py --busy --sound`` passes the host's
    memory).  At width 64 the fp8 control passes the rehearsal's loose
    limit, which is exit code 1."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "busy_served.py"),
         os.path.join(HERE, "rehearsal", "configs", "joyai-tiny-serve.json"),
         "5"], capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == (0 if not row["control_ok"] else 1)
    assert row["sound_ok"] and row["sound_busy_occupancy"] == [4, 4.0]
    # 2 x 16 of the check, 2 x 96 of the fillers (4 slots, contexts of 128)
    assert row["sound"]["positions_checked"] == 32 + 192
    assert row["control"]["mean_regret"] > row["sound"]["mean_regret"]
