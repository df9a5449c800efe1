#!/usr/bin/env python
"""Pipeline-parallel GPT throughput vs the dense step (VERDICT r2 #8).

Runs the SAME model (8-layer GPT, fp32) at the SAME global batch through
four mesh shapes on the 8-device virtual CPU mesh and reports step
throughput ratios plus the schedule's predicted bubble fraction:

- ``dense_dp8``   — data=8, plain GPTLM (the baseline)
- ``dp2_pipe4``   — data=2 x pipe=4 GPipe (the data x pipe composition)
- ``pipe4_tp2``   — pipe=4 x model=2 (Megatron kernels inside stages)
- ``pipe4_virt2`` — pipe=4 circular schedule, n_virtual=2

HONESTY CAVEAT (emitted as ``host_oversubscribed``): the 8 "devices" are
XLA virtual CPU devices timesharing ONE physical core, so a pipeline
bubble — which is device *idleness* — costs ~no wall-clock here; what
these ratios DO measure is the pipelining *overhead* (per-microbatch
dispatch, ppermute handoffs, shard_map partitioning, smaller matmuls) at
equal global work.  The predicted bubble fractions (the model's own
``PipelinedGPT.bubble_fraction``, schedule-aware) are printed next
to each row; on genuinely parallel chips the observed efficiency is
bounded by ``(1 - bubble) x (1 - overhead)``.

Prints one JSON line like the other benches.  CPU-only by design (it is
a ratio bench; absolute numbers are meaningless on an emulated backend).
"""

from __future__ import annotations

import json
import os
import time

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import dataclasses  # noqa: E402

import numpy as np  # noqa: E402
import optax  # noqa: E402


def main() -> None:
    from distributedtensorflow_tpu import runtime

    runtime.init_compile_cache()
    from distributedtensorflow_tpu.models.gpt import (
        GPTConfig,
        GPTLM,
        lm_loss,
    )
    from distributedtensorflow_tpu.models.gpt_pipeline import (
        PipelinedGPT,
        pipelined_lm_loss,
    )
    from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh
    from distributedtensorflow_tpu.train import (
        create_sharded_state,
        make_train_step,
    )

    test = os.environ.get("BENCH_PIPE_TEST") == "1"
    cfg = GPTConfig(
        vocab_size=1024,
        hidden_size=64 if test else 128,
        num_layers=8,  # divisible by pipe=4 x n_virtual=2
        num_heads=4 if test else 8,
        max_seq=128,
        dtype=jax.numpy.float32,  # CPU ratio bench: no emulated-bf16 noise
    )
    seq, global_batch = 128, (16 if test else 32)
    n_steps, warmup = (2, 1) if test else (10, 2)
    n_micro = 8  # microbatch size 2 at data=1; 1 at data=2 — see rows

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(global_batch, seq))
    batch = {"input_ids": ids.astype(np.int32)}

    def device0_bytes(tree) -> int:
        return sum(
            x.addressable_shards[0].data.nbytes
            for x in jax.tree.leaves(tree)
            if hasattr(x, "addressable_shards")
        )

    def measure(mesh, model, loss_fn, init_fn, layout):
        from distributedtensorflow_tpu.obs import memory as obs_memory

        state, specs = create_sharded_state(
            init_fn, optax.sgd(1e-3), mesh, jax.random.PRNGKey(0),
            rules=layout,
        )
        # Per-rank state residency (params + optimizer slots on device 0):
        # evidences the placement story — e.g. the pipe-sharded embedding
        # table vs n_stages-fold replication (gpt_pipeline.layout).
        state_bytes = device0_bytes(state.params) + device0_bytes(
            state.opt_state
        )
        step = make_train_step(loss_fn, mesh, specs)
        key = jax.random.PRNGKey(1)
        compiled = step.lower(state, batch, key).compile()
        # XLA's own within-step scratch accounting: the live-activation
        # number the fb schedules exist to shrink (O(stages) slot ring vs
        # GPipe's O(n_micro) saved scan residuals).
        try:
            temp_bytes = compiled.memory_analysis().temp_size_in_bytes
        except Exception:
            temp_bytes = None
        for _ in range(warmup):
            state, m = compiled(state, batch, key)
            float(m["loss"])
        t0 = time.perf_counter()
        for _ in range(n_steps):
            state, m = compiled(state, batch, key)
        float(m["loss"])
        dt = time.perf_counter() - t0
        live_gib = obs_memory.live_arrays_census(top=0)["bytes"] / 1024**3
        return n_steps / dt, state_bytes, temp_bytes, live_gib

    devices = jax.devices()[:8]
    rows = {}

    # dense baseline: pure data parallel
    mesh = build_mesh(MeshSpec(data=8), devices)
    dense = GPTLM(cfg)
    sps, sbytes, tbytes, live_gib = measure(
        mesh, dense, lm_loss(dense),
        lambda r: dense.init(r, jax.numpy.zeros((2, seq), jax.numpy.int32)),
        None,
    )
    rows["dense_dp8"] = {
        "steps_per_sec": sps,
        "predicted_bubble": 0.0,
        "state_bytes_per_device": sbytes,
        "temp_bytes_per_device": tbytes,
        "live_arrays_gib": round(live_gib, 5),
    }

    configs = [
        # (row, mesh_spec, n_virtual, schedule)
        ("dp2_pipe4", MeshSpec(data=2, pipe=4), 1, "gpipe"),
        ("dp2_pipe4_1f1b", MeshSpec(data=2, pipe=4), 1, "1f1b"),
        ("pipe4_tp2", MeshSpec(pipe=4, model=2), 1, "gpipe"),
        ("pipe4_virt2", MeshSpec(data=2, pipe=4), 2, "gpipe"),
    ]
    for row, spec, n_virtual, schedule in configs:
        mesh = build_mesh(spec, devices)
        pp = PipelinedGPT(
            cfg, mesh, n_microbatches=n_micro, n_virtual=n_virtual,
            schedule=schedule,
        )
        sps, sbytes, tbytes, live_gib = measure(
            mesh, pp, pipelined_lm_loss(pp), pp.init, pp.layout()
        )
        rows[row] = {
            "steps_per_sec": sps,
            # the model's own schedule-aware formula
            "predicted_bubble": pp.bubble_fraction(),
            "schedule": schedule,
            "state_bytes_per_device": sbytes,
            # temp bytes = XLA's within-step scratch (live activations):
            # the number 1f1b exists to shrink vs gpipe at equal model
            "temp_bytes_per_device": tbytes,
            "live_arrays_gib": round(live_gib, 5),
        }

    # MPMD stage-per-process variant (parallel/pipeline_mpmd.py): the
    # SAME 8-layer model as 4 stage processes streaming activations over
    # loopback wire frames.  A different execution model (per-stage
    # untied head, per-process optimizer, real sockets), so the ratio
    # carries the same oversubscription caveat PLUS process overhead —
    # reported for trajectory, not apples-to-apples step parity.
    from distributedtensorflow_tpu.parallel.pipeline_mpmd import (
        MPMDConfig,
        run_mpmd_pipeline,
    )

    mpmd_steps = 3 if test else 8
    mcfg = MPMDConfig(
        n_stages=4, n_steps=mpmd_steps + 1, n_microbatches=n_micro,
        microbatch_size=global_batch // n_micro, seq_len=seq,
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_layers=cfg.num_layers, num_heads=cfg.num_heads,
        window=4,
    )
    import tempfile

    with tempfile.TemporaryDirectory(prefix="bench_mpmd_") as mpmd_dir:
        out = run_mpmd_pipeline(mcfg, mpmd_dir, join_timeout_s=600)
    steady = out["step_seconds"][1:]  # first step carries the compiles
    rows["mpmd_pipe4"] = {
        "steps_per_sec": 1.0 / (sum(steady) / len(steady)),
        "predicted_bubble": PipelinedGPT(
            cfg, build_mesh(MeshSpec(data=2, pipe=4), devices),
            n_microbatches=n_micro, schedule="1f1b",
        ).bubble_fraction(),
        "schedule": "mpmd",
        "final_loss": round(out["losses"][-1], 4),
        "note": "stage-per-process over loopback wire; separate "
                "execution model (untied head, per-stage optimizer)",
    }

    memory = None
    if os.environ.get("BENCH_PIPE_MEM") == "1":
        # Memory-headroom row (VERDICT r3 #6): compile — don't run — the
        # dp2 x pipe4 train step at REAL GPT-2 vocab with the table (a)
        # row-sharded over pipe (gpt_pipeline.layout's ZeRO-style
        # placement) and (b) replicated, and read XLA's per-device memory
        # analysis.  Headroom is quoted against the v5e's 16 GB HBM.
        import re

        from jax.sharding import PartitionSpec as P

        v5e_hbm = 16 * 1024**3
        mem_cfg = dataclasses.replace(
            cfg, vocab_size=50264, hidden_size=256, num_layers=4,
        )
        mesh = build_mesh(MeshSpec(data=2, pipe=4), devices)
        pp = PipelinedGPT(mem_cfg, mesh, n_microbatches=4)
        base_rule = pp.layout()

        def replicated_rule(path, shape):
            if path.endswith("wte/embedding"):
                return P()
            return base_rule(path, shape)

        mem_batch = {
            "input_ids": np.zeros((8, seq), np.int32)
        }
        memory = {
            "config": "gpt_vocab50264_h256_L4_dp2xpipe4_b8",
            "v5e_hbm_bytes": v5e_hbm,
        }
        for name, rule in [("table_sharded_pipe", base_rule),
                           ("table_replicated", replicated_rule)]:
            state, specs = create_sharded_state(
                pp.init, optax.adamw(1e-3), mesh, jax.random.PRNGKey(0),
                rules=rule,
            )
            comp = make_train_step(
                pipelined_lm_loss(pp), mesh, specs
            ).lower(state, mem_batch, jax.random.PRNGKey(1)).compile()
            ma = comp.memory_analysis()
            full_vocab = sorted(set(re.findall(
                r"\w+\[[\d,]*\b50264\b[\d,]*\]", comp.as_text()
            )))
            per_dev = ma.argument_size_in_bytes + ma.temp_size_in_bytes
            memory[name] = {
                "argument_bytes_per_device": ma.argument_size_in_bytes,
                "temp_bytes_per_device": ma.temp_size_in_bytes,
                "full_vocab_tensors_in_hlo": full_vocab[:4],
                "headroom_vs_v5e_16gb": round(v5e_hbm / per_dev, 1),
            }
        sh, rp = memory["table_sharded_pipe"], memory["table_replicated"]
        memory["sharded_saves_factor"] = round(
            (rp["argument_bytes_per_device"] + rp["temp_bytes_per_device"])
            / (sh["argument_bytes_per_device"] + sh["temp_bytes_per_device"]),
            2,
        )

    base = rows["dense_dp8"]["steps_per_sec"]
    for row in rows.values():
        row["vs_dense"] = round(row["steps_per_sec"] / base, 4)
        row["steps_per_sec"] = round(row["steps_per_sec"], 3)
        row["predicted_bubble"] = round(row["predicted_bubble"], 4)

    result = {
        "metric": "gpt8l_pipeline_vs_dense_steps_per_sec",
        "value": rows["dp2_pipe4"]["vs_dense"],
        "unit": "ratio_pipelined_over_dense",
        "vs_baseline": rows["dp2_pipe4"]["vs_dense"],
        "rows": rows,
        "n_microbatches": n_micro,
        "global_batch": global_batch,
        "seq": seq,
        "memory": memory,
        "host_oversubscribed": True,
        "note": (
            "8 virtual devices on one core: ratios measure pipelining "
            "overhead at equal global work, not bubble idleness; real-chip "
            "efficiency bound is (1-bubble)*(1-overhead)"
        ),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    from bench_common import persist_result

    if not test:
        persist_result("pipeline", result)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
