"""A rehearsal of ``ling3-flash-ep8-serve-reason4k-saturated`` on the CPU:
``rehearsal/BENCHMARK-ling.json`` runs ``serve.py --config ling_tiny`` (KDA,
KDA, MLA, KDA: 4 heads of 16, 16 experts in 4 groups of which one is held; a
prefill chunk of 64 = one scan chunk, then a decode step an iteration) under
a tiny ``open-loop-stratified`` mix with the cell's own reference, counts,
readers and layer-metric files.  A CPU trace has no device lane, so the trace
readers leave their metrics out without raising; the step-log and host
metrics are read.  The trace metrics are read off a slice recorded on the
chip (``data/ling_slice.json.gz``: a part of this PR's traced run of the
cell, cut by ``tools/trace_check.py --cut``), and a pattern that matches
nothing there fails.  And the data files of the real cell agree with each
other, with the catalog and with ISSUE 52's parameters.

``BENCHMARK.json``'s ``per_layer`` is full (128 of 128): every ``.ling3``
metric is a file that the rehearsal's manifest lists, beside the real cell
itself, so ``run.py --manifest .../BENCHMARK-ling.json --workload
ling3-flash-ep8-serve-reason4k-saturated --trace 1`` reads them on the chip.
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
MANIFEST = os.path.join(HERE, "rehearsal", "BENCHMARK-ling.json")
SLICE = os.path.join(HERE, "data", "ling_slice.json.gz")
CELL = "ling3-flash-ep8-serve-reason4k-saturated"
TINY = "ling-tiny-serve-reason"
CONFIG = "ling-3.0-flash-vl-ep8-serve"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "layer_types",
           "num_experts", "vocab_size", "max_position_embeddings",
           "expert_swiglu_limit_list", "share_expert_swiglu_limit_list"]
#: what ISSUE 52 names, each a file
NAMED = [
    "decode_iter_wall_ms", "prefill_iter_wall_ms", "decode_occupancy_mean",
    "decode_roofline_pct", "step_between_ms", "ttft_mean_ms", "itl_p95_ms",
    "decode_dispatch_ms", "decode_fetch_ms", "decode_commit_cpu_ms",
    "engine_offcpu_ms", "idle_unattributed_pct", "idle_unnamed_pct",
    "decode_kda_step_ms", "decode_kda_proj_ms", "decode_latent_attn_ms",
    "decode_moe_ms", "prefill_kda_scan_ms", "prefill_latent_attn_ms",
    "kda_step_roofline_pct", "kda_scan_roofline_pct", "kda_state_bytes_step",
    "kda_chunks_scanned", "state_slots_used_peak_pct",
    "kv_blocks_used_peak_pct.latent", "moe_groups_hit_mean",
    "moe_tokens_held_mean", "moe_max_expert_load"]
STEP_LOG_METRICS = [
    "decode_iter_wall_ms", "decode_occupancy_mean",
    "decode_device_sampled_pct", "prefill_iter_wall_ms",
    "decode_commit_cpu_ms", "decode_dispatch_ms", "decode_fetch_ms",
    "engine_offcpu_ms", "step_between_ms", "step_unnamed_pct",
    "step_wall_max_ms", "stream_lag_p95_ms", "kda_state_bytes_step",
    "kda_chunks_scanned", "state_slots_used_peak_pct",
    "kv_blocks_used_peak_pct.latent", "moe_groups_hit_mean",
    "moe_tokens_held_mean", "moe_max_expert_load", "moe_experts_hit_pct",
    "decode_latent_rows_read_mean"]
HOST_METRICS = ["loadgen_late_p95_ms", "itl_p95_ms", "ttft_mean_ms",
                "setup_backend_s", "setup_init_params_s"]
#: device time by scope, read off the recorded slice
SLICE_METRICS = [
    "decode_kda_step_ms", "decode_kda_proj_ms", "decode_latent_attn_ms",
    "decode_moe_ms", "decode_experts_ms", "decode_router_ms",
    "decode_shared_expert_ms", "decode_kv_write_ms",
    "prefill_kda_scan_ms", "prefill_kda_proj_ms", "prefill_latent_attn_ms",
    "prefill_experts_ms", "prefill_chunk_device_ms"]


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
         MANIFEST, "--workload", TINY, "--seed", "5200000019", "--seconds",
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
    assert pre["ok"] is True and pre["check"] == "kda_state"
    assert pre["state_rel_err"] <= pre["state_rel_err_limit"] == 1e-4
    assert pre["slots_checked"] == [0, 1]
    assert pre["layers_checked"] == [0, 1, 3]
    assert pre["programs_checked"]["prefill"] >= 4
    assert pre["programs_checked"]["decode"] >= 8
    assert os.path.samefile(detail["reference_file"],
                            os.path.join(BENCH, "reference", "ling.py"))
    assert os.path.samefile(detail["counts_file"],
                            os.path.join(BENCH, "counts", "ling.py"))
    # no device lane on the CPU: the step-log and host metrics, and only
    # those; the trace readers return nothing and do not raise
    assert sorted(line["metrics"]) == sorted(
        [m + ".ling3" for m in STEP_LOG_METRICS + HOST_METRICS]
        + ["compile_s", "compiles_in_window"])
    value = {name[:-6]: m["value"] for name, m in line["metrics"].items()
             if name.endswith(".ling3")}
    # the state group and the latent group are both read; a token's choices
    # fall in 1 to topk_group (2) of the router's groups a layer (the file's
    # scale is the cell's 6 expert layers over the rehearsal's 3)
    assert value["kda_state_bytes_step"] > 0 < value["kda_chunks_scanned"]
    assert value["decode_latent_rows_read_mean"] > 0
    assert 0.5 <= value["moe_groups_hit_mean"] <= 1.0


def test_cell_traffic_and_config_are_as_the_issue_gives_them():
    manifest = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert manifest["workloads"][-1] == cell
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "reason4k-saturated", 1)
    assert len(cell["why"]) <= 200
    tok = next(m for m in manifest["end_to_end"]
               if m["name"] == "serve_tok_per_s")
    assert tok["workloads"][-1] == CELL
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert (len(manifest["configs"]), len(manifest["workloads"])) == (10, 12)
    traffic = _json(BENCH, "traffic", "reason4k-saturated.json")
    # the issue's kind, behind the configuration's on-device check (the
    # served tokens cannot show the state's precision: checks/kda_state.py)
    assert traffic["kind"] == "open-loop-stratified-preflight"
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                     "sigma": 1.2, "min": 64, "max": 16384}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 1024,
                                     "sigma": 0.6, "min": 256, "max": 4096}
    assert traffic["gaps"] == {"dist": "exponential"}
    assert traffic["sampling"] == {"temperature": 0.0}
    assert traffic["judge_ttft"] is False
    assert (traffic["trace_at_s"], traffic["trace_seconds"],
            traffic["order_seed"], traffic["rotate_by_seed"]) == (
        10, 3, 52, False)
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert (traffic["warm_in_s"], traffic["warm_in_burst"]) == (
        30, config["max_slots"])
    assert traffic["rate_per_s"] == pytest.approx(
        1.25 * traffic["knee_per_s"])
    assert config["reduced"] == REDUCED
    assert set(config["reduced_why"]) == set(config["reduced"])
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert len(entry["why"]) <= 200
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert (config["num_hidden_layers"], config["max_position_embeddings"],
            config["vocab_size"], config["num_experts"],
            config["num_experts_published"]) == (7, 20480, 19648, 64, 512)
    assert config["vocab_size"] * 8 == config["vocab_size_published"]
    assert config["layer_types"] == ["kda"] * 4 + ["mla"] + ["kda"] * 2
    check = config["correctness"]
    # three prefill chunks; the last holds one whole scan chunk and one
    # token of a second; the served tokens cross latent blocks
    chunk = config["prefill_chunk"]
    assert check["prompt_tokens"] > 2 * chunk
    assert check["prompt_tokens"] % chunk == 64 + 1
    assert check["requests"] * check["new_tokens"] >= 256
    # two slots side by side, each prompt over a prefill-chunk boundary and
    # ending one token into a second scan chunk
    state = check["preflight"]
    assert state["check"] == "kda_state" and state["requests"] >= 2
    assert state["prompt_tokens"] % chunk == 64 + 1 < state["prompt_tokens"]
    assert 1e-4 <= state["state_rel_err_limit"] <= 5e-3
    longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert longest <= config["max_context"] == 20480
    for key in ("assumed", "departures", "deployment", "reduced_why",
                "cache_bytes_why", "argv_why"):
        assert config[key], key
    for key in ("equations_from", "layer_pattern", "kda_decay", "kda_inputs",
                "kda_output", "kda_no_rotary", "kda_qk_norm",
                "mla_query_norm", "mla_rotary", "mla_gate", "router",
                "swiglu_limit", "state_dtype", "weights", "traffic",
                "left_out"):
        assert config["assumed"][key], key


def test_config_keeps_every_number_of_the_catalog_entry():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash-VL")
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    # what is cut is a prefix of the published list, or a count
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert config[key] == row["config"][key][:7]
    # no width among the cuts
    for key in config["reduced"]:
        assert not key.endswith(("_dim", "_rank")), key
        assert key not in ("hidden_size", "intermediate_size",
                           "moe_intermediate_size",
                           "moe_shared_expert_intermediate_size",
                           "num_experts_per_tok", "num_attention_heads"), key


def test_server_preset_is_the_configuration_file():
    sys.path.insert(0, ROOT)
    from distributedtensorflow_tpu import models

    config = _json(BENCH, "configs", CONFIG + ".json")
    cfg = getattr(models, config["system_config"])()
    assert (cfg.hidden_size, cfg.num_heads, cfg.head_dim, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.intermediate_size, cfg.moe_intermediate_size, cfg.vocab_size,
            cfg.num_layers, cfg.num_dense_layers, cfg.num_experts,
            cfg.experts_per_token, cfg.n_group, cfg.topk_group,
            cfg.conv_kernel, cfg.kda_lower_bound, cfg.max_seq,
            cfg.rms_norm_eps, cfg.rope_theta, cfg.route_scale
            ) == tuple(config[k] for k in (
                "hidden_size", "num_attention_heads", "head_dim",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "intermediate_size", "moe_intermediate_size",
                "vocab_size", "num_hidden_layers", "first_k_dense_replace",
                "num_experts_published", "num_experts_per_tok", "n_group",
                "topk_group", "short_conv_kernel_size", "kda_lower_bound",
                "max_position_embeddings", "rms_norm_eps", "rope_theta",
                "routed_scaling_factor"))
    assert list(cfg.layer_types) == config["layer_types"]
    assert cfg.held == (config["expert_first"], config["num_experts"])
    assert config["q_lora_rank"] is None and cfg.q_lora_rank is None
    assert sum(cfg.cache_rows.values) * 2 == config["cache_bytes_per_token"]
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
    counts = _module(os.path.join(BENCH, "counts", "ling.py"))
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert round(counts.kda_params(config) / 1e6, 1) == 63.1
    assert round(counts.mla_params(config) / 1e6, 1) == 32.0
    assert round(counts.expert_params(config) / 1e6, 2) == 5.90
    assert counts.params(config) == config["parameters"]
    assert round(counts.params(config) * 2 / 1e9, 2) == 5.73
    assert counts.kv_bytes_per_token(config) == 1152
    # 2.10 MB of matrices + 73.7 KB of tails a slot a layer, six layers
    assert counts.matrix_state_bytes(config) == 32 * 128 * 128 * 4
    assert counts.state_bytes_per_slot(config) == 6 * (2097152 + 73728)
    slots = config["max_slots"]
    lives = [2600] * slots
    step = counts.decode_kernel(config, "kda_step", lives)
    assert step["bytes"] == 6 * slots * (2 * 2097152 + (5 * 4096 + 32) * 4)
    assert step["flops"] == 6 * slots * 7 * 4096 * 128
    whole = counts.decode_kernel(config, "decode_iter", lives)
    # the issue's prediction: the state's traffic (3.2 GB of ~8.8) is the
    # largest single part of a step's bytes
    state = 2 * slots * counts.state_bytes_per_slot(config)
    assert round(state / 1e9, 1) == 3.3
    assert 0.3 < state / whole["bytes"] < 0.5
    assert counts.step_kernel(config, "kda_step") == step
    scan = counts.step_kernel(config, "kda_chunk_scan")
    assert scan["flops"] == 6 * 2048 * 7 * 4096 * 128
    assert scan["bytes"] == 6 * (2048 * (5 * 4096 + 32) * 4 + 2 * 2097152)
    assert counts.decode_kernel(config, "paged_latent_attn", lives)[
        "bytes"] > slots * 2600 * 1152
    assert counts.decode_kernel(config, "moe_grouped", lives)["bytes"] > 0
    assert counts.decode_step_bytes is counts.decode_iter_bytes
    with pytest.raises(NotImplementedError):
        counts.train_flops_per_token(config, 1024)
    with pytest.raises(KeyError):
        counts.decode_kernel(config, "paged_attn", lives)


def test_every_ling_metric_is_a_file_the_rehearsal_lists_and_none_is_listed():
    names = sorted(os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(BENCH, "layer_metrics", "*.ling3.json")))
    for name in NAMED:
        assert name + ".ling3" in names, name
    rehearsal = _json(MANIFEST)
    listed = [m["name"] for m in rehearsal["per_layer"]]
    assert sorted(n for n in listed if n.endswith(".ling3")) == names
    for m in rehearsal["per_layer"]:
        if m["name"].endswith(".ling3"):
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
    assert rehearsal["configs"][-1] == manifest["configs"][-1]
    assert len(manifest["per_layer"]) == 128
    assert not [m["name"] for m in manifest["per_layer"]
                if m["name"].endswith(".ling3")]
    # the kernels' roofline shares are held to counts/ling.py's names
    counts = _module(os.path.join(BENCH, "counts", "ling.py"))
    config = _json(BENCH, "configs", CONFIG + ".json")
    for name in ("kda_step_roofline_pct", "latent_attn_roofline_pct",
                 "moe_grouped_roofline_pct", "decode_roofline_pct",
                 "kda_scan_roofline_pct"):
        spec = _json(BENCH, "layer_metrics", name + ".ling3.json")
        # the scan is no kernel: its time is a scope's, its requirement the
        # counts module's all the same
        assert spec["reader"] == (
            "trace_scope_roofline" if name == "kda_scan_roofline_pct"
            else "trace_decode_kernel")
        need = counts.decode_kernel(config, spec["args"]["required"],
                                    [900, 9000])
        assert need["bytes"] > 0
    # the scan's requirement is that of the real tokens the step log counts
    spec = _json(BENCH, "layer_metrics", "kda_scan_roofline_pct.ling3.json")
    assert spec["args"]["observed"] == ["scan_tokens", "prefill_chunks"]
    half = counts.decode_kernel(config, "kda_chunk_scan", [900], {
        "scan_tokens": 512.0, "prefill_chunks": 0.5})
    assert half == counts.scan_chunk(config, 1024)
    assert half["flops"] * 2 == counts.step_kernel(
        config, "kda_chunk_scan")["flops"]
    # the scales of the pool metrics are this cell's pool and slots
    spec = _json(BENCH, "layer_metrics",
                 "kv_blocks_used_peak_pct.latent.ling3.json")
    assert spec["args"]["scale"] == pytest.approx(100 / config["kv_blocks"])
    spec = _json(BENCH, "layer_metrics",
                 "state_slots_used_peak_pct.ling3.json")
    assert spec["args"]["scale"] == pytest.approx(100 / config["max_slots"])


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
    spec = _json(BENCH, "layer_metrics", name + ".ling3.json")
    reader = _module(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    value = reader.read(slice_ctx, spec["args"])
    assert value is not None and value > 0, name
    # a pattern that matches nothing in the slice reads nothing
    nothing = dict(spec["args"], scope="/no_such_scope(/|$)")
    assert not reader.read(slice_ctx, nothing)


@pytest.mark.parametrize("name", ["kda_step_roofline_pct",
                                  "kda_scan_roofline_pct",
                                  "latent_attn_roofline_pct"])
def test_the_kernels_roofline_shares_read_the_recorded_slice(
        slice_ctx, name, tmp_path):
    """The shares off the slice with 128 sequences of 2.6 k decoding:
    between 1 and 100 %, and nothing where the pattern matches no kernel."""
    spec = _json(BENCH, "layer_metrics", name + ".ling3.json")
    reader = _module(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    live = {"token_times": [0.0], "token_counts": [1],
            "max_new_tokens": 1200, "prompt_tokens": 2600}
    out = tmp_path / "out"
    (out / "serve").mkdir(parents=True)
    ctx = dict(slice_ctx, trace=slice_ctx["trace"],
               trace_done={"t_begin": 10.0, "t_end": 13.0},
               epoch_zero=0.0, logs=[live] * 128, out=str(out),
               config=_json(BENCH, "configs", CONFIG + ".json"),
               counts=_module(os.path.join(BENCH, "counts", "ling.py")),
               device_kind="TPU v5 lite")
    share = reader.read(ctx, spec["args"])
    assert 1.0 < share < 100.0, share
    nothing = {"pattern": "no_such_kernel", "scope": "/no_such_scope(/|$)"}
    assert reader.read(ctx, {**spec["args"], **{
        k: v for k, v in nothing.items() if k in spec["args"]}}) is None
