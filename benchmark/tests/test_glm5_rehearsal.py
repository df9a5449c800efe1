"""A rehearsal of ``glm5-ep16-serve-longctx-saturated`` on the CPU:
``rehearsal/BENCHMARK-glm5.json`` runs ``serve.py --config glm5_tiny`` (the
joyai family with its indexer on: ``index_topk`` 24 of contexts to 120, 8 of
16 experts held, two leading dense layers) under a tiny
``open-loop-stratified`` mix with the cell's own reference, counts, readers
and layer-metric files.  A CPU trace has no device lane, so the trace readers
leave their metrics out without raising; the step-log and host metrics are
read.  The trace metrics are read off a slice recorded on the chip
(``data/glm5_slice.json.gz``: a part of this PR's traced run of the cell, cut
by ``tools/trace_check.py --cut``).  And the data files of the real cell
agree with each other, with the catalog and with ISSUE 39's parameters.

``BENCHMARK.json``'s ``per_layer`` is full (128 of 128): every ``.glm5``
metric is a file that the rehearsal's manifest lists, beside the real cell
itself, so ``run.py --manifest .../BENCHMARK-glm5.json --workload
glm5-ep16-serve-longctx-saturated --trace 1`` reads them on the chip.  Slow
(the first case starts the program): run by hand with the other benchmark
tests."""

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
MANIFEST = os.path.join(HERE, "rehearsal", "BENCHMARK-glm5.json")
SLICE = os.path.join(HERE, "data", "glm5_slice.json.gz")
CELL = "glm5-ep16-serve-longctx-saturated"
TINY = "glm5-tiny-serve-longctx"
CONFIG = "glm5-ep16-serve"
#: what ISSUE 39 names, each a file
NAMED = [
    "decode_indexer_ms", "decode_select_ms", "decode_sparse_attn_ms",
    "prefill_indexer_ms", "prefill_select_ms", "prefill_sparse_attn_ms",
    "sparse_latent_attn_roofline_pct", "indexer_roofline_pct",
    "index_rows_scored_mean", "decode_latent_rows_read_mean",
    "kv_latent_blocks_used_peak_pct"]
STEP_LOG_METRICS = [
    "decode_iter_wall_ms.glm5", "decode_occupancy_mean.glm5",
    "decode_device_sampled_pct.glm5", "moe_experts_hit_pct.glm5",
    "moe_max_expert_load.glm5", "kv_latent_blocks_used_peak_pct.glm5",
    "decode_latent_rows_read_mean.glm5", "index_rows_scored_mean.glm5",
    "prefill_iter_wall_ms.glm5", "decode_commit_cpu_ms.glm5",
    "decode_dispatch_ms.glm5", "decode_fetch_ms.glm5",
    "engine_offcpu_ms.glm5", "step_between_ms.glm5",
    "step_unnamed_pct.glm5", "step_wall_max_ms.glm5",
    "stream_lag_p95_ms.glm5"]
HOST_METRICS = [
    "loadgen_late_p95_ms.glm5", "itl_p95_ms.glm5", "ttft_mean_ms.glm5",
    "setup_backend_s.glm5", "setup_init_params_s.glm5", "compile_s",
    "compiles_in_window"]
#: device time by scope, read off the recorded slice
SLICE_METRICS = [
    "decode_indexer_ms.glm5", "decode_select_ms.glm5",
    "decode_sparse_attn_ms.glm5", "prefill_indexer_ms.glm5",
    "prefill_select_ms.glm5", "prefill_sparse_attn_ms.glm5",
    "decode_latent_proj_ms.glm5", "decode_experts_ms.glm5",
    "decode_kv_write_ms.glm5", "prefill_experts_ms.glm5",
    "prefill_chunk_device_ms.glm5"]


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
         MANIFEST, "--workload", TINY, "--seed", "3900000019", "--seconds",
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
                            os.path.join(BENCH, "reference", "glm5.py"))
    assert os.path.samefile(detail["counts_file"],
                            os.path.join(BENCH, "counts", "glm5.py"))
    # no device lane on the CPU: the step-log and host metrics, and only
    # those; the trace readers return nothing and do not raise
    assert sorted(line["metrics"]) == sorted(STEP_LOG_METRICS + HOST_METRICS)
    read = line["metrics"]["decode_latent_rows_read_mean.glm5"]["value"]
    scored = line["metrics"]["index_rows_scored_mean.glm5"]["value"]
    # contexts pass index_topk 24: fewer rows attended than keys scored
    assert 0 < read < scored


def test_cell_traffic_and_config_are_as_the_issue_gives_them():
    manifest = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert manifest["workloads"][-1] is cell
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longctx32k-saturated", 1)
    assert len(cell["why"]) <= 200
    tok = next(m for m in manifest["end_to_end"]
               if m["name"] == "serve_tok_per_s")
    assert tok["workloads"][-1] == CELL
    traffic = _json(BENCH, "traffic", "longctx32k-saturated.json")
    assert traffic["kind"] == "open-loop-stratified"
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 12288,
                                     "sigma": 0.6, "min": 4096, "max": 32768}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 256,
                                     "sigma": 0.6, "min": 64, "max": 768}
    assert traffic["gaps"] == {"dist": "exponential"}
    assert traffic["sampling"] == {"temperature": 0.0}
    assert traffic["judge_ttft"] is False
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert (traffic["warm_in_s"], traffic["warm_in_burst"]) == (
        30, config["max_slots"])
    assert traffic["rate_per_s"] == pytest.approx(
        1.25 * traffic["knee_per_s"])
    # every request is past 2 x index_topk before its first token
    assert traffic["prompt_len"]["min"] >= 2 * config["index_topk"]
    assert sorted(config["reduced"]) == sorted([
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "max_position_embeddings", "num_nextn_predict_layers"])
    assert set(config["reduced_why"]) == set(config["reduced"])
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert manifest["configs"][-1] is entry
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    for key, want in (("num_hidden_layers", 5), ("first_k_dense_replace", 1),
                      ("n_routed_experts", 16),
                      ("n_routed_experts_published", 256),
                      ("vocab_size", 19360), ("vocab_size_published", 154880),
                      ("max_position_embeddings", 33792),
                      ("num_nextn_predict_layers", 0)):
        assert config[key] == want, key
    assert config["vocab_size"] * 8 == config["vocab_size_published"]
    check = config["correctness"]
    assert check["prompt_tokens"] >= config["index_topk"] \
        + config["block_size"] + 1
    assert check["prompt_tokens"] > 2 * config["prefill_chunk"]
    assert check["requests"] >= 2 and check["new_tokens"] >= 128
    longest = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert longest <= config["max_context"] == 33792
    for key in ("assumed", "departures", "deployment", "reduced_why"):
        assert config[key], key


def test_config_keeps_every_number_of_the_catalog_entry():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
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
    assert (cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.num_experts,
            cfg.experts_per_token, cfg.vocab_size, cfg.num_layers,
            cfg.num_dense_layers, cfg.index_heads, cfg.index_head_dim,
            cfg.index_topk) == tuple(
        config[k] for k in (
            "hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "moe_intermediate_size",
            "n_routed_experts_published", "num_experts_per_tok",
            "vocab_size", "num_hidden_layers", "first_k_dense_replace",
            "index_n_heads", "index_head_dim", "index_topk"))
    assert cfg.held == (config["expert_first"], config["n_routed_experts"])
    assert (cfg.route_scale, cfg.route_norm, cfg.rms_norm_eps,
            cfg.rope_theta) == (
        config["routed_scaling_factor"], config["norm_topk_prob"],
        config["rms_norm_eps"], config["rope_parameters"]["rope_theta"])
    assert config["rope_interleave"] and config["indexer_rope_interleave"]
    assert cfg.cache_rows.values == (576, 128)
    argv = config["argv"]
    for flag, key in (("--max-slots", "max_slots"),
                      ("--block-size", "block_size"),
                      ("--max-context", "max_context"),
                      ("--prefill-chunk", "prefill_chunk"),
                      ("--kv-blocks", "kv_blocks"),
                      ("--max-queue", "max_queue")):
        assert argv[argv.index(flag) + 1] == str(config[key]), flag


def test_counts_are_the_issues_arithmetic():
    counts = _module(os.path.join(BENCH, "counts", "glm5.py"))
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert round(counts.attention_params(config) / 1e6, 1) == 165.0
    assert round(counts.indexer_params(config) / 1e6, 1) == 9.4
    assert round(counts.expert_params(config) / 1e6, 2) == 37.75
    assert round(counts.matmul_params(config) / 1e6, 1) == 3909.6
    assert round(counts.matmul_params(config) * 2 / 1e9, 2) == 7.82
    assert counts.latent_row_bytes(config) == 1280
    assert counts.index_key_bytes(config) == 256
    assert counts.cache_bytes_per_token_layer(config) == 1536
    lives = [15000] * 24
    need = counts.decode_kernel(config, "sparse_latent_attn", lives)
    assert need["flops"] == 5 * 24 * 2048 * 2 * 64 * (576 + 512)
    need = counts.decode_kernel(config, "index_scores", lives)
    assert need["flops"] == 5 * 24 * 15000 * 2 * 32 * 128
    assert need["bytes"] >= 5 * 24 * 15000 * 256
    with pytest.raises(NotImplementedError):
        counts.train_flops_per_token(config, 1024)
    with pytest.raises(KeyError):
        counts.decode_kernel(config, "paged_latent_attn", lives)


def test_every_glm5_metric_is_a_file_the_rehearsal_lists_and_none_is_listed():
    names = sorted(os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(BENCH, "layer_metrics", "*.glm5.json")))
    for name in NAMED:
        assert name + ".glm5" in names, name
    rehearsal = _json(MANIFEST)
    listed = [m["name"] for m in rehearsal["per_layer"]]
    assert sorted(n for n in listed if n.endswith(".glm5")) == names
    for m in rehearsal["per_layer"]:
        if m["name"].endswith(".glm5"):
            spec = _json(BENCH, "layer_metrics", m["name"] + ".json")
            assert spec["workloads"] == [CELL]
            assert m["workloads"] == [TINY, CELL]
            assert (m["unit"], m["layer"], m["moves"]) == (
                spec["unit"], spec["layer"], spec["moves"])
            assert os.path.exists(os.path.join(
                BENCH, "readers", spec["reader"] + ".py"))
    manifest = _json(ROOT, "BENCHMARK.json")
    assert len(manifest["per_layer"]) == 128
    assert not [m["name"] for m in manifest["per_layer"]
                if m["name"].endswith(".glm5")]
    # the kernels' roofline shares are held to counts/glm5.py's names
    counts = _module(os.path.join(BENCH, "counts", "glm5.py"))
    config = _json(BENCH, "configs", CONFIG + ".json")
    for name in ("sparse_latent_attn_roofline_pct", "indexer_roofline_pct",
                 "moe_grouped_roofline_pct", "decode_roofline_pct"):
        spec = _json(BENCH, "layer_metrics", name + ".glm5.json")
        assert spec["reader"] in ("trace_decode_kernel",
                                  "trace_decode_scope")
        need = counts.decode_kernel(config, spec["args"]["required"],
                                    [9000, 20000])
        assert need["bytes"] > 0


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


def test_sparse_roofline_takes_the_scope_not_the_kernel_alone(slice_ctx):
    """The kernel alone read 117 % of the HBM roofline in the traced run (its
    rows lie where the gather left them): the share is of the whole scope,
    gather and kernel, and stays under 100 % on the recorded slice with 21
    sequences of 15 k decoding."""
    spec = _json(BENCH, "layer_metrics",
                 "sparse_latent_attn_roofline_pct.glm5.json")
    assert spec["reader"] == "trace_decode_scope"
    reader = _module(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    assert reader.read(dict(slice_ctx), spec["args"]) is None  # no window
    live = {"token_times": [0.0], "token_counts": [1],
            "max_new_tokens": 300, "prompt_tokens": 15000}
    ctx = dict(slice_ctx, trace_done={"t_begin": 10.0, "t_end": 13.0},
               epoch_zero=0.0, logs=[live] * 21, out="/nonexistent",
               config=_json(BENCH, "configs", CONFIG + ".json"),
               counts=_module(os.path.join(BENCH, "counts", "glm5.py")),
               device_kind="TPU v5 lite")
    share = reader.read(ctx, spec["args"])
    assert 2.0 < share < 100.0, share
    alone = _module(os.path.join(BENCH, "readers", "trace_decode_kernel.py"))
    ctx["trace"] = slice_ctx["trace"]
    assert alone.read(ctx, {"program": "^jit_decode", "required":
                            "sparse_latent_attn", "pattern":
                            "sparse_latent_attn"}) > share
