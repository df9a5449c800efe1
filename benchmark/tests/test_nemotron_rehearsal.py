"""A rehearsal of ``nemotron3-super-ep4-serve-agent16k-saturated`` on the CPU:
``rehearsal/BENCHMARK-nemotron.json`` runs ``serve.py --config
nemotron_h_tiny`` (``MEM*EM``: 8 Mamba-2 heads of 8 in 2 groups, 16 experts of
which 4 are held, top 3; a prefill chunk of 128 = one scan chunk, then a decode
step an iteration) under a tiny ``open-loop-stratified-preflight`` mix with the
cell's own reference, counts, check, readers and layer-metric files.  A CPU
trace has no device lane, so the trace readers leave their metrics out without
raising; the step-log and host metrics are read.  The trace metrics are read
off a slice recorded on the chip (``data/nemotron_slice.json.gz``: a part of
this PR's traced run of the cell, cut by ``tools/trace_check.py --cut``), and a
pattern that matches nothing there fails.  And the data files of the real cell
agree with each other, with the catalog and with ISSUE 54's parameters.

``BENCHMARK.json``'s ``per_layer`` is full (128 of 128): every ``.nemotron3``
metric is a file that the rehearsal's manifest lists, beside the real cell
itself, so ``run.py --manifest .../BENCHMARK-nemotron.json --workload
nemotron3-super-ep4-serve-agent16k-saturated --trace 1`` reads them on the
chip.  Slow (the first case starts the program): run by hand with the other
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
MANIFEST = os.path.join(HERE, "rehearsal", "BENCHMARK-nemotron.json")
SLICE = os.path.join(HERE, "data", "nemotron_slice.json.gz")
CELL = "nemotron3-super-ep4-serve-agent16k-saturated"
TINY = "nemotron-tiny-serve-agent"
CONFIG = "nemotron3-super-ep4-serve"
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size", "max_position_embeddings",
           "num_nextn_predict_layers"]
#: what ISSUE 54 names, each a file
NAMED = [
    "decode_ssd_step_ms", "decode_ssd_proj_ms", "prefill_ssd_scan_ms",
    "prefill_ssd_proj_ms", "ssd_state_bytes_step", "ssd_chunks_scanned",
    "state_slots_used_peak_pct", "decode_moe_latent_ms", "decode_router_ms",
    "decode_experts_ms", "decode_shared_expert_ms", "prefill_experts_ms",
    "moe_tokens_held_mean", "moe_experts_hit_pct", "moe_max_expert_load",
    "decode_paged_attn_ms", "kv_blocks_used_peak_pct",
    "ssd_step_roofline_pct", "ssd_scan_roofline_pct",
    "moe_grouped_roofline_pct", "paged_attn_roofline_pct",
    "decode_roofline_pct", "decode_iter_wall_ms", "prefill_iter_wall_ms",
    "decode_occupancy_mean", "step_between_ms", "ttft_mean_ms", "itl_p95_ms",
    "idle_unattributed_pct", "idle_unnamed_pct"]
STEP_LOG_METRICS = [
    "decode_iter_wall_ms", "decode_occupancy_mean",
    "decode_device_sampled_pct", "prefill_iter_wall_ms",
    "decode_commit_cpu_ms", "decode_dispatch_ms", "decode_fetch_ms",
    "engine_offcpu_ms", "step_between_ms", "step_unnamed_pct",
    "step_wall_max_ms", "stream_lag_p95_ms", "ssd_state_bytes_step",
    "ssd_chunks_scanned", "state_slots_used_peak_pct",
    "kv_blocks_used_peak_pct", "moe_tokens_held_mean", "moe_max_expert_load",
    "moe_experts_hit_pct"]
HOST_METRICS = ["loadgen_late_p95_ms", "itl_p95_ms", "ttft_mean_ms",
                "setup_backend_s", "setup_init_params_s"]
#: device time by scope, read off the recorded slice
SLICE_METRICS = [
    "decode_ssd_step_ms", "decode_ssd_proj_ms", "decode_paged_attn_ms",
    "decode_moe_ms", "decode_experts_ms", "decode_router_ms",
    "decode_shared_expert_ms", "decode_moe_latent_ms", "decode_kv_write_ms",
    "prefill_ssd_scan_ms", "prefill_ssd_proj_ms", "prefill_paged_attn_ms",
    "prefill_experts_ms", "prefill_moe_rest_ms", "prefill_chunk_device_ms"]


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
         MANIFEST, "--workload", TINY, "--seed", "5400000019", "--seconds",
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
    assert pre["ok"] is True and pre["check"] == "ssd_state"
    assert pre["state_rel_err"] <= pre["state_rel_err_limit"] == 1e-4
    assert pre["slots_checked"] == [0, 1]
    assert pre["layers_checked"] == [0, 2, 5]
    assert pre["chunk_scan"] == "chunked"
    assert pre["programs_checked"]["prefill"] >= 4
    assert pre["programs_checked"]["decode"] >= 8
    assert os.path.samefile(detail["reference_file"],
                            os.path.join(BENCH, "reference", "nemotron_h.py"))
    assert os.path.samefile(detail["counts_file"],
                            os.path.join(BENCH, "counts", "nemotron_h.py"))
    # no device lane on the CPU: the step-log and host metrics, and only
    # those; the trace readers return nothing and do not raise
    assert sorted(line["metrics"]) == sorted(
        [m + ".nemotron3" for m in STEP_LOG_METRICS + HOST_METRICS]
        + ["compile_s", "compiles_in_window"])
    value = {name[:-10]: m["value"] for name, m in line["metrics"].items()
             if name.endswith(".nemotron3")}
    assert value["ssd_state_bytes_step"] > 0 < value["ssd_chunks_scanned"]
    assert value["moe_tokens_held_mean"] > 0


def test_cell_traffic_and_config_are_as_the_issue_gives_them():
    manifest = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    # a member of the benchmark, wherever later PRs append theirs
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "agent16k-saturated", 1)
    assert len(cell["why"]) <= 200
    tok = next(m for m in manifest["end_to_end"]
               if m["name"] == "serve_tok_per_s")
    assert CELL in tok["workloads"]
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    traffic = _json(BENCH, "traffic", "agent16k-saturated.json")
    assert traffic["kind"] == "open-loop-stratified-preflight"
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 2048,
                                     "sigma": 1.0, "min": 256, "max": 16384}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 384,
                                     "sigma": 0.7, "min": 64, "max": 2048}
    assert traffic["gaps"] == {"dist": "exponential"}
    assert traffic["sampling"] == {"temperature": 0.0}
    assert traffic["judge_ttft"] is False
    assert (traffic["trace_at_s"], traffic["trace_seconds"],
            traffic["order_seed"], traffic["rotate_by_seed"]) == (
        10, 3, 54, False)
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
            config["vocab_size"], config["n_routed_experts"],
            config["n_routed_experts_published"],
            config["num_nextn_predict_layers"]) == (
        11, 18432, 32768, 128, 512, 0)
    assert config["vocab_size"] * 4 == config["vocab_size_published"]
    assert config["hybrid_override_pattern"] == "MEMEMEM*EME" \
        == config["hybrid_override_pattern_published"][:11]
    check = config["correctness"]
    # three prefill chunks; the last holds one whole scan chunk and one
    # token of a second
    chunk = config["prefill_chunk"]
    assert check["prompt_tokens"] == 2 * chunk + 128 + 1
    assert check["requests"] * check["new_tokens"] >= 256
    # two slots side by side, each prompt over a prefill-chunk boundary and
    # ending one token into a second scan chunk
    state = check["preflight"]
    assert state["check"] == "ssd_state" and state["requests"] >= 2
    assert state["prompt_tokens"] % chunk == 128 + 1 < state["prompt_tokens"]
    assert 1e-4 <= state["state_rel_err_limit"] <= 5e-3
    longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert longest <= config["max_context"] == 18432
    for key in ("assumed", "departures", "deployment", "reduced_why",
                "cache_bytes_why", "argv_why", "left_out"):
        assert config[key], key
    for key in ("equations_from", "block", "mamba2", "dt_clamp",
                "router_input", "router", "no_rotary", "state_dtype",
                "weights", "traffic"):
        assert config["assumed"][key], key


def test_config_keeps_every_number_of_the_catalog_entry():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
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
                           "mamba_num_heads", "n_groups", "expand"), key


def test_server_preset_is_the_configuration_file():
    sys.path.insert(0, ROOT)
    from distributedtensorflow_tpu import models

    config = _json(BENCH, "configs", CONFIG + ".json")
    cfg = getattr(models, config["system_config"])()
    assert (cfg.hidden_size, cfg.pattern, cfg.mamba_num_heads,
            cfg.mamba_head_dim, cfg.n_groups, cfg.ssm_state_size,
            cfg.conv_kernel, cfg.chunk_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.moe_intermediate_size, cfg.moe_latent_size,
            cfg.shared_intermediate_size, cfg.num_experts,
            cfg.experts_per_token, cfg.route_scale, cfg.norm_eps,
            cfg.vocab_size, cfg.max_seq, cfg.num_layers
            ) == tuple(config[k] for k in (
                "hidden_size", "hybrid_override_pattern", "mamba_num_heads",
                "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
                "chunk_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "moe_intermediate_size", "moe_latent_size",
                "moe_shared_expert_intermediate_size",
                "n_routed_experts_published", "num_experts_per_tok",
                "routed_scaling_factor", "layer_norm_epsilon", "vocab_size",
                "max_position_embeddings", "num_hidden_layers"))
    assert cfg.held == (config["expert_first"], config["n_routed_experts"])
    assert cfg.d_inner == config["expand"] * config["hidden_size"]
    assert sum(cfg.cache_rows.widths) * 2 == config["cache_bytes_per_token"]
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
    counts = _module(os.path.join(BENCH, "counts", "nemotron_h.py"))
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert round(counts.mamba_params(config) / 1e6, 2) == 109.63
    assert round(counts.attention_params(config) / 1e6, 2) == 35.65
    assert round(counts.expert_layer_params_outside(config) / 1e6, 2) == 54.53
    assert round(counts.expert_params(config) / 1e6, 3) == 5.505
    assert counts.params(config) == config["parameters"]
    assert round(counts.params(config) * 2 / 1e9, 2) == 9.30
    assert round(counts.published_params(config) / 1e9, 2) == 120.67
    assert round(counts.published_active_params(config) / 1e9, 1) == 12.2
    assert counts.kv_bytes_per_token(config) == 1024
    assert counts.matrix_state_bytes(config) == 128 * 64 * 128 * 4
    assert counts.state_bytes_per_slot(config) == 5 * (4194304 + 61440)
    slots = config["max_slots"]
    lives = [3300] * slots
    step = counts.decode_kernel(config, "ssd_step", lives)
    assert step["bytes"] == 5 * slots * (
        2 * 4194304 + (2 * 8192 + 128 + 2 * 1024) * 4)
    assert step["flops"] == 5 * slots * 5 * 8192 * 128
    whole = counts.decode_kernel(config, "decode_iter", lives)
    # the issue's prediction: ~14.9 GB a step, the state and the experts
    # ~83 % of it
    assert 14.5e9 < whole["bytes"] < 15.5e9
    state = 2 * slots * counts.state_bytes_per_slot(config)
    experts = 5 * counts.experts_hit(config, slots) \
        * counts.expert_params(config) * 2
    assert 0.78 < (state + experts) / whole["bytes"] < 0.88
    assert counts.step_kernel(config, "ssd_step") == step
    scan = counts.step_kernel(config, "ssd_chunk_scan")
    assert scan["flops"] == 5 * 2048 * 5 * 8192 * 128
    assert counts.decode_kernel(config, "paged_attn", lives)[
        "bytes"] > slots * 3300 * 1024
    assert counts.decode_kernel(config, "moe_grouped", lives)["bytes"] > 0
    with pytest.raises(NotImplementedError):
        counts.train_flops_per_token(config, 1024)
    with pytest.raises(KeyError):
        counts.decode_kernel(config, "paged_latent_attn", lives)


def test_every_metric_is_a_file_the_rehearsal_lists_and_none_is_listed():
    names = sorted(os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(BENCH, "layer_metrics", "*.nemotron3.json")))
    for name in NAMED:
        assert name + ".nemotron3" in names, name
    rehearsal = _json(MANIFEST)
    listed = [m["name"] for m in rehearsal["per_layer"]]
    assert sorted(n for n in listed if n.endswith(".nemotron3")) == names
    for m in rehearsal["per_layer"]:
        if m["name"].endswith(".nemotron3"):
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
                if m["name"].endswith(".nemotron3")]
    # the roofline shares are held to counts/nemotron_h.py's names
    counts = _module(os.path.join(BENCH, "counts", "nemotron_h.py"))
    config = _json(BENCH, "configs", CONFIG + ".json")
    for name, reader in (("ssd_step_roofline_pct", "trace_scope_roofline"),
                         ("ssd_scan_roofline_pct", "trace_scope_roofline"),
                         ("moe_grouped_roofline_pct", "trace_decode_kernel"),
                         ("paged_attn_roofline_pct", "trace_decode_kernel"),
                         ("decode_roofline_pct", "trace_decode_kernel")):
        spec = _json(BENCH, "layer_metrics", name + ".nemotron3.json")
        assert spec["reader"] == reader
        need = counts.decode_kernel(
            config, spec["args"]["required"], [900, 9000],
            {"scan_tokens": 512.0, "prefill_chunks": 0.5})
        assert need["bytes"] > 0
    half = counts.decode_kernel(config, "ssd_chunk_scan", [900], {
        "scan_tokens": 512.0, "prefill_chunks": 0.5})
    assert half == counts.scan_chunk(config, 1024)
    # the scales of the pool metrics are this cell's pool, slots and layers
    for name, scale in (
            ("kv_blocks_used_peak_pct", 100 / config["kv_blocks"]),
            ("state_slots_used_peak_pct", 100 / config["max_slots"]),
            ("ssd_state_bytes_step", 2 * 5 * 4194304),
            ("ssd_chunks_scanned", 1 / config["chunk_size"]),
            ("moe_tokens_held_mean", 1 / 5),
            ("moe_experts_hit_pct", 100 / (5 * config["n_routed_experts"]))):
        spec = _json(BENCH, "layer_metrics", name + ".nemotron3.json")
        assert spec["args"]["scale"] == pytest.approx(scale), name


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
    spec = _json(BENCH, "layer_metrics", name + ".nemotron3.json")
    reader = _module(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    value = reader.read(slice_ctx, spec["args"])
    assert value is not None and value > 0, name
    # a pattern that matches nothing in the slice reads nothing
    nothing = dict(spec["args"], scope="/no_such_scope(/|$)")
    assert not reader.read(slice_ctx, nothing)


@pytest.mark.parametrize("name", ["ssd_step_roofline_pct",
                                  "ssd_scan_roofline_pct",
                                  "paged_attn_roofline_pct"])
def test_the_roofline_shares_read_the_recorded_slice(slice_ctx, name,
                                                     tmp_path):
    """The shares off the slice with 128 sequences of 3.3 k decoding: between
    1 and 100 %, and nothing where the pattern matches nothing."""
    spec = _json(BENCH, "layer_metrics", name + ".nemotron3.json")
    reader = _module(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    live = {"token_times": [0.0], "token_counts": [1],
            "max_new_tokens": 490, "prompt_tokens": 3300}
    out = tmp_path / "out"
    (out / "serve").mkdir(parents=True)
    ctx = dict(slice_ctx, trace=slice_ctx["trace"],
               trace_done={"t_begin": 10.0, "t_end": 13.0},
               epoch_zero=0.0, logs=[live] * 128, out=str(out),
               config=_json(BENCH, "configs", CONFIG + ".json"),
               counts=_module(os.path.join(BENCH, "counts", "nemotron_h.py")),
               device_kind="TPU v5 lite")
    share = reader.read(ctx, spec["args"])
    assert 1.0 < share < 100.0, share
    nothing = {"pattern": "no_such_kernel", "scope": "/no_such_scope(/|$)"}
    assert reader.read(ctx, {**spec["args"], **{
        k: v for k, v in nothing.items() if k in spec["args"]}}) is None
