"""A rehearsal of ``jamba2-3b-serve-longdoc-saturated`` on the CPU:
``rehearsal/BENCHMARK-jamba.json`` runs ``serve.py --config jamba_tiny`` (three
Mamba layers of 128 channels x 16 states, one attention layer) under a tiny
``open-loop-stratified`` mix with the cell's own reference, counts, readers
and layer-metric files.  A CPU trace has no device lane, so the trace readers
leave their metrics out without raising; the step-log metrics are read.  The
trace metrics are read off a slice recorded on the chip (``data/
jamba_slice.json.gz``: 0.25 s of this PR's traced run of the cell, cut by
``tools/trace_check.py --cut``).  And the data files of the real cell agree
with each other and with ISSUE 34's parameters.

``BENCHMARK.json``'s ``per_layer`` holds 128 entries at most and held 121
before this cell: seven ``.jamba`` metrics are listed there (``LISTED``); the
others are files that the rehearsal's manifest lists, for the benchmark PR
that merges sibling entries to take up.  Slow (the first case starts the
program): run by hand with the other benchmark tests."""

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
MANIFEST = os.path.join(HERE, "rehearsal", "BENCHMARK-jamba.json")
CELL = "jamba2-3b-serve-longdoc-saturated"
CONFIG = "jamba2-3b-serve"
#: what ``BENCHMARK.json`` lists, in its order
LISTED = [
    "ssm_scan_roofline_pct.jamba", "paged_attn_roofline_pct.jamba",
    "decode_roofline_pct.jamba", "prefill_scan_ms.jamba",
    "prefill_chunk_device_ms.jamba", "decode_ssm_step_ms.jamba",
    "state_slots_used_peak_pct.jamba"]
STEP_LOG_METRICS = [
    "state_slots_used_peak_pct.jamba", "kv_blocks_used_peak_pct.jamba",
    "decode_iter_wall_ms.jamba", "decode_occupancy_mean.jamba",
    "decode_device_sampled_pct.jamba", "prefill_iter_wall_ms.jamba"]
#: read from the client's log and from the server's ``trace.jsonl``
HOST_METRICS = [
    "loadgen_late_p95_ms.jamba", "itl_p95_ms.jamba", "ttft_mean_ms.jamba",
    "setup_backend_s.jamba", "setup_init_params_s.jamba"]
#: device time by scope or span, read off the recorded slice below
SLICE_METRICS = [
    "prefill_scan_ms.jamba", "prefill_mamba_proj_ms.jamba",
    "prefill_mlp_ms.jamba", "prefill_attn_ms.jamba",
    "prefill_chunk_device_ms.jamba", "decode_ssm_step_ms.jamba",
    "decode_mamba_proj_ms.jamba", "decode_mlp_ms.jamba",
    "decode_attn_ms.jamba", "decode_kv_write_ms.jamba",
    "decode_unscoped_pct.jamba", "decode_span_device_ms.jamba",
    "decode_span_host_ms.jamba", "decode_commit_ms.jamba",
    "engine_log_ms.jamba", "idle_unattributed_pct.jamba"]
TRACE_METRICS = SLICE_METRICS + [
    "ssm_scan_roofline_pct.jamba", "paged_attn_roofline_pct.jamba",
    "decode_roofline_pct.jamba", "prefill_scan_kernel_share_pct.jamba",
    "prefill_device_share_pct.jamba"]
ALL = sorted(set(STEP_LOG_METRICS + HOST_METRICS + TRACE_METRICS))


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
         MANIFEST, "--workload", "jamba-tiny-serve-longdoc", "--seed",
         "3400000019", "--seconds", "6", "--trace", "1"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    detail = line["detail"]
    assert detail["positions_checked"] == 32
    assert os.path.samefile(detail["reference_file"],
                            os.path.join(BENCH, "reference", "jamba.py"))
    assert os.path.samefile(detail["counts_file"],
                            os.path.join(BENCH, "counts", "jamba.py"))
    # no device lane on the CPU: the step-log and host metrics, and only
    # those; the trace readers return nothing and do not raise
    assert sorted(line["metrics"]) == sorted(STEP_LOG_METRICS + HOST_METRICS)
    assert line["metrics"]["state_slots_used_peak_pct.jamba"]["value"] > 0
    steps = os.path.join(ROOT, "bench_out", "jamba-tiny-serve-longdoc",
                         "serve", "steps.jsonl")
    with open(steps) as f:
        rows = [json.loads(x) for x in f if x.strip()]
    assert sum(r["scan_tokens"] for r in rows) > 0
    assert max(r["state_slots_used"] for r in rows) >= 1


def test_cell_traffic_and_config_are_as_the_issue_gives_them():
    manifest = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longdoc32k-saturated", 1)
    assert manifest["workloads"][-1] is cell        # appended, not inserted
    assert len(cell["why"]) <= 200
    tok = next(m for m in manifest["end_to_end"]
               if m["name"] == "serve_tok_per_s")
    assert tok["workloads"][-1] == CELL
    traffic = _json(BENCH, "traffic", "longdoc32k-saturated.json")
    assert traffic["kind"] == "open-loop-stratified"
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 8192,
                                     "sigma": 0.7, "min": 1024, "max": 32768}
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
    assert "knee_sweep.py" in traffic["what"]
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert config["reduced"] == ["max_position_embeddings"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert config["max_position_embeddings"] == 33792 == config["max_context"]
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert manifest["configs"][-1] is entry
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    for key in ("layer_order", "mixer_norms", "state", "attention",
                "weights"):
        assert key in config["assumed"], key
    check = config["correctness"]
    chunk = config["prefill_chunk"]
    # past three prefill chunks, the last of them partly padding
    assert check["prompt_tokens"] > 2 * chunk and check["prompt_tokens"] % chunk
    assert check["requests"] >= 2 and check["new_tokens"] >= 128
    assert check["min_positions"] == check["requests"] * check["new_tokens"]
    # the longest request fits a slot in whole chunks; K/V fully provisioned
    longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert longest <= config["max_context"] and not config["max_context"] % chunk
    assert config["kv_blocks"] * config["block_size"] \
        == config["max_slots"] * config["max_context"]


def test_config_keeps_every_number_of_the_catalog_entry():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "AI21-Jamba2-3B")
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
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.intermediate_size, cfg.num_layers, cfg.vocab_size,
            cfg.attn_layer_period, cfg.attn_layer_offset, cfg.mamba_expand,
            cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv,
            cfg.num_experts, cfg.rms_norm_eps, cfg.max_seq) == tuple(
        config[k] for k in (
            "hidden_size", "num_attention_heads", "num_key_value_heads",
            "intermediate_size", "num_hidden_layers", "vocab_size",
            "attn_layer_period", "attn_layer_offset", "mamba_expand",
            "mamba_d_state", "mamba_dt_rank", "mamba_d_conv", "num_experts",
            "rms_norm_eps", "max_position_embeddings"))
    assert cfg.head_dim * cfg.num_heads == cfg.hidden_size
    assert config["tie_word_embeddings"] is True
    assert config["sliding_window"] is None
    argv = config["argv"]
    for flag, key in (("--max-slots", "max_slots"),
                      ("--block-size", "block_size"),
                      ("--max-context", "max_context"),
                      ("--prefill-chunk", "prefill_chunk"),
                      ("--max-queue", "max_queue")):
        assert argv[argv.index(flag) + 1] == str(config[key]), flag
    assert "--kv-blocks" not in argv        # every slot's worst case


def test_counts_are_the_issues_arithmetic():
    counts = _module(os.path.join(BENCH, "counts", "jamba.py"))
    config = _json(BENCH, "configs", CONFIG + ".json")
    # ISSUE 34's count, term by term
    assert counts.mamba_params(config) == (
        2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 + 5120
        + 5120 * 16 + 5120 + 5120 * 2560) == 41_241_600
    assert counts.ffn_params(config) == 3 * 2560 * 8192
    assert counts.attention_params(config) == 2 * 2560 * 2560 + 2 * 2560 * 128
    assert (counts.mamba_layers(config), counts.attention_layers(config)) \
        == (26, 2)
    assert counts.params(config) == 26 * (41_241_600 + 62_914_560) + 2 * (
        13_762_560 + 62_914_560) + 65536 * 2560 == 3_029_186_560
    assert round(counts.params(config) * 2 / 1e9, 2) == 6.06
    assert counts.state_bytes_per_slot(config) == 26 * (
        5120 * 16 * 4 + 3 * 5120 * 2) == 9_318_400
    assert counts.kv_bytes_per_token(config) == 1024
    # the scan: 7 operations a (channel, state) a token, 3 a channel
    assert counts.scan_flops_per_token(config) == 7 * 5120 * 16 + 3 * 5120
    assert counts.scan_bytes_per_token(config) == (3 * 5120 + 32) * 2
    chunk = counts.step_kernel(config, "ssm_chunk_scan")
    assert chunk["flops"] == 26 * 1024 * (7 * 5120 * 16 + 3 * 5120)
    assert chunk["bytes"] == 26 * (1024 * (3 * 5120 + 32) * 2
                                   + 2 * 16 * 5120 * 4)
    lives = [6000, 100]
    need = counts.decode_kernel(config, "paged_attn", lives)
    assert need["flops"] == 2 * 6100 * 20 * 4 * 128
    assert need["bytes"] == 6100 * 1024 + 2 * 2 * 2 * 20 * 128 * 2
    need = counts.decode_kernel(config, "decode_iter", [9000] * 32)
    assert need["bytes"] == 3_029_186_560 * 2 + 2 * 32 * 9_318_400 \
        + 32 * 9000 * 1024
    assert need["bytes"] == pytest.approx(
        counts.decode_iter_bytes(config, 32 * 9000, 2))
    # an inactive slot's state is neither read nor written: 3 live slots
    assert counts.decode_kernel(config, "decode_iter", [10] * 3)["bytes"] \
        == 3_029_186_560 * 2 + 2 * 3 * 9_318_400 + 30 * 1024
    assert counts.step_kernel(config, "paged_attn")["bytes"] > 0
    with pytest.raises(NotImplementedError, match="no trainer"):
        counts.train_flops_per_token(config, 1024)
    with pytest.raises(KeyError):
        counts.decode_kernel(config, "moe_grouped", lives)


@pytest.mark.parametrize("name", ALL)
def test_layer_metric_file_is_the_cells(name):
    spec = _json(BENCH, "layer_metrics", name + ".json")
    assert spec["workloads"] == [CELL]
    assert spec["moves"] == (
        "setup_s" if name.startswith("setup_") else "serve_tok_per_s")
    assert os.path.exists(os.path.join(BENCH, "readers",
                                       spec["reader"] + ".py"))
    rehearsal = next(m for m in _json(MANIFEST)["per_layer"]
                     if m["name"] == name)
    assert (rehearsal["layer"], rehearsal["unit"]) == (spec["layer"],
                                                       spec["unit"])
    manifest = _json(ROOT, "BENCHMARK.json")
    entry = [m for m in manifest["per_layer"] if m["name"] == name]
    assert bool(entry) == (name in LISTED)
    for m in entry:
        assert m["workloads"] == [CELL]
        assert (m["layer"], m["unit"], m["moves"]) == (
            spec["layer"], spec["unit"], spec["moves"])
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    sibling = os.path.join(BENCH, "layer_metrics",
                           name.replace(".jamba", ".joyai") + ".json")
    if os.path.exists(sibling) and name not in (
            "decode_roofline_pct.jamba",):
        twin = _json(sibling)       # the same reader, the same arguments
        assert (twin["reader"], twin["args"]) == (spec["reader"],
                                                  spec["args"])


def test_every_jamba_metric_is_a_file_and_seven_are_listed():
    manifest = _json(ROOT, "BENCHMARK.json")
    mine = [m["name"] for m in manifest["per_layer"]
            if m["name"].endswith(".jamba")]
    assert mine == LISTED
    assert [m["name"] for m in manifest["per_layer"]][-len(mine):] == mine
    assert len(manifest["per_layer"]) <= 128
    files = sorted(f[:-5] for f in os.listdir(
        os.path.join(BENCH, "layer_metrics")) if f.endswith(".jamba.json"))
    assert files == ALL
    assert sorted(m["name"] for m in _json(MANIFEST)["per_layer"]) == ALL
    config = _json(BENCH, "configs", CONFIG + ".json")
    peak = _json(BENCH, "layer_metrics", "kv_blocks_used_peak_pct.jamba.json")
    assert peak["args"]["scale"] == pytest.approx(100 / config["kv_blocks"])
    slots = _json(BENCH, "layer_metrics",
                  "state_slots_used_peak_pct.jamba.json")
    assert slots["args"]["scale"] == pytest.approx(100 / config["max_slots"])
    scan = _json(BENCH, "layer_metrics", "ssm_scan_roofline_pct.jamba.json")
    assert scan["args"]["required"] == scan["args"]["pattern"] \
        == "ssm_chunk_scan"


# -- the trace metrics on a slice recorded on the chip ----------------------

@pytest.fixture(scope="module")
def slice_ctx():
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(BENCH, "readers"))
    with gzip.open(os.path.join(HERE, "data", "jamba_slice.json.gz"),
                   "rt") as f:
        piece = json.load(f)
    name = sorted(piece["devices"])[0]
    dev = piece["devices"][name]
    return {
        "scoped": {"ops": dev["ops"], "modules": dev["modules"]},
        "trace": {"devices": {name: {"ops": [op[:3] for op in dev["ops"]],
                                     "modules": dev["modules"]}},
                  "host": piece["host"]},
    }


def _read(ctx, name):
    spec = _json(BENCH, "layer_metrics", name + ".json")
    reader = _module(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    return reader.read(ctx, spec["args"])


@pytest.mark.parametrize("name", SLICE_METRICS)
def test_trace_metric_reads_the_recorded_slice(slice_ctx, name):
    value = _read(slice_ctx, name)
    assert value is not None and value > 0, name


def test_scopes_tile_the_programs_on_the_recorded_slice(slice_ctx):
    """The parts of a chunk stay under the whole, and the scan is where the
    kernel's time is: the scope ``mamba/scan`` holds the kernel and the
    spreading of ``B`` and ``C`` before it."""
    chunk = _read(slice_ctx, "prefill_chunk_device_ms.jamba")
    parts = [_read(slice_ctx, f"prefill_{p}_ms.jamba")
             for p in ("scan", "mamba_proj", "mlp", "attn")]
    assert 0.8 * chunk < sum(parts) < chunk
    # (the span ``engine.decode`` also holds the tail of the chunk launched
    # before it, so the whole is the program's own time)
    scope = _module(os.path.join(BENCH, "readers", "trace_scope.py"))
    decode = scope.read(slice_ctx, {"program": "^jit_decode", "scope": "",
                                    "stat": "ms"})
    parts = [_read(slice_ctx, f"decode_{p}_ms.jamba")
             for p in ("ssm_step", "mamba_proj", "mlp", "attn")]
    assert 0.7 * decode < sum(parts) < decode
    assert _read(slice_ctx, "decode_span_device_ms.jamba") > decode
    # the kernel runs under ``mamba/scan`` and is most of the scope
    scan = [op for op in slice_ctx["scoped"]["ops"]
            if op[3] and "/mamba/scan" in op[3]]
    kernel = [op for op in scan if op[0].startswith("%ssm_chunk_scan")]
    assert len(kernel) == 26        # one chunk in the slice, 26 Mamba layers
    assert 0.8 * sum(op[2] for op in scan) < sum(op[2] for op in kernel)
