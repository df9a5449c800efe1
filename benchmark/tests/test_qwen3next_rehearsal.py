"""A rehearsal of ``qwen3-next-ep4-serve-longdoc64k-saturated`` on the CPU:
``rehearsal/BENCHMARK-qwen3next.json`` runs ``serve.py --config
qwen3_next_tiny`` (``G G G A G``: 2 key heads shared by 4 value heads, 16
experts of which 4 are held, top 3; a prefill chunk of 64 = one scan chunk,
then a decode step an iteration) under a tiny
``open-loop-stratified-preflight`` mix with the cell's own reference, counts,
check, readers and layer-metric files.  A CPU trace has no device lane, so the
trace readers leave their metrics out without raising; the step-log and host
metrics are read.  And the data files of the real cell agree with each other,
with the catalog and with ISSUE 58's parameters.

``BENCHMARK.json``'s ``per_layer`` is full (128 of 128): every ``.qwen3next``
metric is a file that the rehearsal's manifest lists, beside the real cell
itself, so ``run.py --manifest .../BENCHMARK-qwen3next.json --workload
qwen3-next-ep4-serve-longdoc64k-saturated --trace 1`` reads them on the chip.
Slow (the first case starts the program): run by hand with the other
benchmark tests."""

import glob
import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
MANIFEST = os.path.join(HERE, "rehearsal", "BENCHMARK-qwen3next.json")
CELL = "qwen3-next-ep4-serve-longdoc64k-saturated"
TINY = "qwen3next-tiny-serve-longdoc"
CONFIG = "qwen3-next-ep4-serve"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "max_position_embeddings"]
#: what ISSUE 58 names, each a file
NAMED = [
    "decode_iter_wall_ms", "decode_dispatch_ms", "decode_commit_ms",
    "decode_fetch_ms", "decode_occupancy_mean", "prefill_iter_wall_ms",
    "prefill_chunk_device_ms", "prefill_device_share_pct", "ttft_mean_ms",
    "itl_p95_ms", "loadgen_late_p95_ms", "setup_backend_s",
    "setup_init_params_s", "idle_unattributed_pct", "idle_unnamed_pct",
    "decode_gdn_step_ms", "decode_gdn_proj_ms", "prefill_gdn_scan_ms",
    "prefill_gdn_proj_ms", "gdn_state_bytes_step", "gdn_chunks_scanned",
    "state_slots_used_peak_pct", "decode_paged_attn_ms",
    "prefill_paged_attn_ms", "decode_kv_write_ms", "kv_blocks_used_peak_pct",
    "decode_router_ms", "decode_experts_ms", "decode_shared_expert_ms",
    "prefill_experts_ms", "prefill_moe_rest_ms", "moe_tokens_held_mean",
    "moe_experts_hit_pct", "moe_max_expert_load", "gdn_step_roofline_pct",
    "gdn_scan_roofline_pct", "paged_attn_roofline_pct",
    "kv_chunk_attn_roofline_pct", "moe_grouped_roofline_pct",
    "decode_roofline_pct"]
STEP_LOG_METRICS = [
    "decode_iter_wall_ms", "decode_occupancy_mean",
    "decode_device_sampled_pct", "prefill_iter_wall_ms",
    "decode_commit_cpu_ms", "decode_dispatch_ms", "decode_fetch_ms",
    "engine_offcpu_ms", "step_between_ms", "step_unnamed_pct",
    "step_wall_max_ms", "stream_lag_p95_ms", "gdn_state_bytes_step",
    "gdn_chunks_scanned", "state_slots_used_peak_pct",
    "kv_blocks_used_peak_pct", "moe_tokens_held_mean", "moe_max_expert_load",
    "moe_experts_hit_pct"]
HOST_METRICS = ["loadgen_late_p95_ms", "itl_p95_ms", "ttft_mean_ms",
                "setup_backend_s", "setup_init_params_s"]


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
         MANIFEST, "--workload", TINY, "--seed", "5800000019", "--seconds",
         "6", "--trace", "1"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    detail = line["detail"]
    assert detail["positions_checked"] == 32
    # the on-device check ran before the server, on the same kind's files
    pre = detail["preflight"]
    assert pre["ok"] is True and pre["check"] == "gdn_state"
    assert pre["state_rel_err"] <= pre["state_rel_err_limit"] == 1e-4
    assert pre["slots_checked"] == [0, 1]
    assert pre["layers_checked"] == [0, 1, 2, 4]
    assert pre["chunk_scan"] == "chunked"
    assert pre["programs_checked"]["prefill"] >= 4
    assert pre["programs_checked"]["decode"] >= 8
    assert os.path.samefile(detail["reference_file"],
                            os.path.join(BENCH, "reference", "qwen3_next.py"))
    assert os.path.samefile(detail["counts_file"],
                            os.path.join(BENCH, "counts", "qwen3_next.py"))
    # no device lane on the CPU: the step-log and host metrics, and only
    # those; the trace readers return nothing and do not raise
    assert sorted(line["metrics"]) == sorted(
        [m + ".qwen3next" for m in STEP_LOG_METRICS + HOST_METRICS]
        + ["compile_s", "compiles_in_window"])
    value = {name[:-10]: m["value"] for name, m in line["metrics"].items()
             if name.endswith(".qwen3next")}
    assert value["gdn_state_bytes_step"] > 0 < value["gdn_chunks_scanned"]
    assert value["moe_tokens_held_mean"] > 0


def test_cell_traffic_and_config_are_as_the_issue_gives_them():
    manifest = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    # a member of the benchmark, wherever later PRs append theirs
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longdoc64k-saturated", 1)
    assert len(cell["why"]) <= 200
    tok = next(m for m in manifest["end_to_end"]
               if m["name"] == "serve_tok_per_s")
    assert CELL in tok["workloads"]
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    traffic = _json(BENCH, "traffic", "longdoc64k-saturated.json")
    assert traffic["kind"] == "open-loop-stratified-preflight"
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 8192,
                                     "sigma": 1.0, "min": 512, "max": 65536}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 256,
                                     "sigma": 0.7, "min": 32, "max": 1024}
    assert traffic["gaps"] == {"dist": "exponential"}
    assert traffic["sampling"] == {"temperature": 0.0}
    assert traffic["judge_ttft"] is False
    assert (traffic["trace_at_s"], traffic["trace_seconds"],
            traffic["order_seed"], traffic["rotate_by_seed"]) == (
        10, 3, 58, False)
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert (traffic["warm_in_s"], traffic["warm_in_burst"]) == (
        30, config["max_slots"])
    assert traffic["rate_per_s"] == pytest.approx(
        1.25 * traffic["knee_per_s"])
    assert "unshared" in traffic["what"]
    assert config["reduced"] == REDUCED
    assert set(config["reduced_why"]) == set(config["reduced"])
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert len(entry["why"]) <= 200
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert (config["num_hidden_layers"], config["max_position_embeddings"],
            config["vocab_size"], config["num_experts"],
            config["num_experts_published"]) == (8, 67584, 37984, 128, 512)
    assert config["vocab_size"] * 4 == config["vocab_size_published"]
    check = config["correctness"]
    # two prefill chunks; the last holds one whole scan chunk and one
    # token of a second
    chunk = config["prefill_chunk"]
    assert check["prompt_tokens"] == chunk + 64 + 1
    assert "GIVEN THEIR INPUTS" in check["what"]
    assert check["requests"] * check["new_tokens"] >= 256
    # two slots side by side, each prompt over a prefill-chunk boundary and
    # ending one token into a second scan chunk
    state = check["preflight"]
    assert state["check"] == "gdn_state" and state["requests"] >= 2
    assert state["prompt_tokens"] % chunk == 64 + 1 < state["prompt_tokens"]
    assert 1e-4 <= state["state_rel_err_limit"] <= 5e-3
    longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert longest <= config["max_context"] == 67584
    assert config["max_context"] % chunk == 0
    for key in ("assumed", "departures", "deployment", "reduced_why",
                "cache_bytes_why", "argv_why", "left_out"):
        assert config[key], key
    for key in ("equations_from", "norm", "block", "gdn", "head_order",
                "gates", "attention", "router", "state_dtype", "weights",
                "traffic"):
        assert config["assumed"][key], key


def test_config_keeps_every_number_of_the_catalog_entry():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key + "_published"] == value, key
        else:
            assert config[key] == value, key
    # no width among the cuts
    for key in config["reduced"]:
        assert not key.endswith(("_dim", "_rank", "_size")) \
            or key == "vocab_size", key
        assert key not in ("num_experts_per_tok", "num_attention_heads",
                           "linear_num_key_heads", "linear_num_value_heads",
                           "num_key_value_heads"), key


def test_server_preset_is_the_configuration_file():
    sys.path.insert(0, ROOT)
    from distributedtensorflow_tpu import models

    config = _json(BENCH, "configs", CONFIG + ".json")
    cfg = getattr(models, config["system_config"])()
    assert (cfg.hidden_size, cfg.num_layers, cfg.full_attention_interval,
            cfg.linear_key_heads, cfg.linear_value_heads, cfg.linear_key_dim,
            cfg.linear_value_dim, cfg.conv_kernel, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim, cfg.moe_intermediate_size,
            cfg.shared_intermediate_size, cfg.num_experts,
            cfg.experts_per_token, cfg.route_norm, cfg.rms_norm_eps,
            cfg.rope_theta, cfg.vocab_size, cfg.max_seq
            ) == tuple(config[k] for k in (
                "hidden_size", "num_hidden_layers", "full_attention_interval",
                "linear_num_key_heads", "linear_num_value_heads",
                "linear_key_head_dim", "linear_value_head_dim",
                "linear_conv_kernel_dim", "num_attention_heads",
                "num_key_value_heads", "head_dim", "moe_intermediate_size",
                "shared_expert_intermediate_size", "num_experts_published",
                "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
                "rope_theta", "vocab_size", "max_position_embeddings"))
    assert cfg.rotary_dim == config["head_dim"] \
        * config["partial_rotary_factor"]
    assert cfg.held == (config["expert_first"], config["num_experts"])
    layers = sum(not cfg.keeps_state(i) for i in range(cfg.num_layers))
    assert layers * sum(cfg.cache_rows.widths) * 2 \
        == config["cache_bytes_per_token"]
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
    counts = _module(os.path.join(BENCH, "counts", "qwen3_next.py"))
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert round(counts.gdn_params(config) / 1e6, 2) == 33.72
    assert round(counts.attention_params(config) / 1e6, 2) == 27.26
    assert round(counts.moe_params_outside(config) / 1e6, 2) == 4.20
    assert round(counts.expert_params(config) / 1e6, 3) == 3.146
    assert counts.params(config) == config["parameters"]
    assert round(counts.params(config) * 2 / 1e9, 2) == 7.33
    assert round(counts.published_params(config) / 1e9, 2) == 79.67
    assert round(counts.published_active_params(config) / 1e9, 1) == 3.3
    assert counts.kv_bytes_per_token(config) == 4096
    assert counts.matrix_state_bytes(config) == 32 * 128 * 128 * 4
    assert counts.state_bytes_per_slot(config) == 6 * (2097152 + 49152)
    slots = config["max_slots"]
    lives = [13000] * slots
    step = counts.decode_kernel(config, "gdn_step", lives)
    assert step["bytes"] == 6 * slots * (
        2 * 2097152 + (2 * 2048 + 2 * 4096 + 64) * 4)
    assert step["flops"] == 6 * slots * 7 * 32 * 128 * 128
    whole = counts.decode_kernel(config, "decode_iter", lives)
    # the issue's prediction at 48 slots of ~13 k: ~8.4 GB a step
    assert 7.5e9 < whole["bytes"] < 9.5e9
    assert counts.step_kernel(config, "gdn_step") == step
    scan = counts.step_kernel(config, "gdn_chunk_scan")
    assert scan["flops"] == 6 * 2048 * 7 * 32 * 128 * 128
    attn = counts.decode_kernel(config, "kv_chunk_attn", lives, {
        "chunk_tokens": 2048.0, "prefill_chunks": 1.0,
        "chunk_pairs": 2048.0 * 32768 + 2048 * 2049 / 2})
    # the issue's 2 x 4 x 2048 x c x 16 x 256 FLOP at c = 32 k (+ the chunk)
    assert attn["flops"] == pytest.approx(
        2 * 4 * 2048 * (32768 + 1024.5) * 16 * 256)
    assert counts.decode_kernel(config, "paged_attn", lives)[
        "bytes"] > slots * 13000 * 4096
    assert counts.decode_kernel(config, "moe_grouped", lives)["bytes"] > 0
    with pytest.raises(NotImplementedError):
        counts.train_flops_per_token(config, 1024)
    with pytest.raises(KeyError):
        counts.decode_kernel(config, "paged_latent_attn", lives)


def test_every_metric_is_a_file_the_rehearsal_lists_and_none_is_listed():
    names = sorted(os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(BENCH, "layer_metrics", "*.qwen3next.json")))
    for name in NAMED:
        assert name + ".qwen3next" in names, name
    rehearsal = _json(MANIFEST)
    listed = [m["name"] for m in rehearsal["per_layer"]]
    assert sorted(n for n in listed if n.endswith(".qwen3next")) == names
    for m in rehearsal["per_layer"]:
        if m["name"].endswith(".qwen3next"):
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
    assert rehearsal["configs"][-1] == next(
        c for c in manifest["configs"] if c["name"] == CONFIG)
    assert len(manifest["per_layer"]) == 128
    assert not [m["name"] for m in manifest["per_layer"]
                if m["name"].endswith(".qwen3next")]
    # the roofline shares are held to counts/qwen3_next.py's names
    counts = _module(os.path.join(BENCH, "counts", "qwen3_next.py"))
    config = _json(BENCH, "configs", CONFIG + ".json")
    for name, reader in (("gdn_step_roofline_pct", "trace_scope_roofline"),
                         ("gdn_scan_roofline_pct", "trace_scope_roofline"),
                         ("kv_chunk_attn_roofline_pct",
                          "trace_scope_roofline"),
                         ("moe_grouped_roofline_pct", "trace_decode_kernel"),
                         ("paged_attn_roofline_pct", "trace_decode_kernel"),
                         ("decode_roofline_pct", "trace_decode_kernel")):
        spec = _json(BENCH, "layer_metrics", name + ".qwen3next.json")
        assert spec["reader"] == reader
        # a reader hands the counts the fields its file lists, no other
        logged = {"chunk_tokens": 512.0, "chunk_pairs": 512.0 * 4096,
                  "prefill_chunks": 0.5, "moe_pairs": 800.0,
                  "moe_experts_hit": 500.0}
        need = counts.decode_kernel(
            config, spec["args"]["required"], [900, 9000],
            {f: logged[f] for f in spec["args"].get("observed", [])})
        assert need["bytes"] > 0
    half = counts.decode_kernel(config, "gdn_chunk_scan", [900], {
        "chunk_tokens": 512.0, "chunk_pairs": 512.0 * 4096,
        "scan_tokens": 536.0, "prefill_chunks": 0.5})
    assert half == counts.scan_chunk(config, 1024)
    # the scales of the pool metrics are this cell's pool, slots and layers
    for name, scale in (
            ("kv_blocks_used_peak_pct", 100 / config["kv_blocks"]),
            ("state_slots_used_peak_pct", 100 / config["max_slots"]),
            ("gdn_state_bytes_step", 2 * 6 * 2097152),
            ("gdn_chunks_scanned", 1 / 64),
            ("moe_tokens_held_mean", 1 / 8),
            ("moe_experts_hit_pct", 100 / (8 * config["num_experts"]))):
        spec = _json(BENCH, "layer_metrics", name + ".qwen3next.json")
        assert spec["args"]["scale"] == pytest.approx(scale), name
