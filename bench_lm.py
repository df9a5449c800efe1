#!/usr/bin/env python
"""Secondary benchmark: GPT decoder-LM training tokens/sec/chip (+ MFU).

Not the driver's headline metric (that is bench.py's ResNet-50
images/sec/chip) — this measures the long-context/LM path: a GPT-small
train step (bf16, fused QKV) on synthetic data.  Prints one JSON line in
the same shape as bench.py.

Knobs (env): ``BENCH_LM_WORKLOAD`` preset (``gpt_lm`` default /
``gpt_medium_lm`` / ``lm_long_context`` — presets keep their OWN
seq/remat defaults unless the envs below explicitly override),
``BENCH_LM_BATCH`` per-chip batch (default 8), ``BENCH_LM_SEQ`` sequence
length (gpt_lm default 1024), ``BENCH_LM_REMAT`` 0/1/attn (gpt_lm
default 0 — the A100 anchor number is remat-off), ``BENCH_LM_ATTN`` /
``BENCH_LM_XENT`` kernel selectors, ``BENCH_LM_WINDOW`` sliding-window size, ``BENCH_LM_INNER`` steps/dispatch.
Exits non-zero without a TPU, and with 3 when the configuration fails to
compile or run (the error is in the JSON line).
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench_common import persist_result, start, timed_steps


def main() -> None:
    start("bench_lm")
    from distributedtensorflow_tpu.data import device_put_batch
    from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh
    from distributedtensorflow_tpu.train import create_sharded_state, make_train_step
    from distributedtensorflow_tpu.workloads import get_workload

    mesh = build_mesh(MeshSpec(data=-1))
    n_chips = mesh.size
    test_size = os.environ.get("BENCH_LM_TEST") == "1"  # tiny wiring check
    # BENCH_LM_WORKLOAD: gpt_lm (default) | gpt_medium_lm | lm_long_context
    workload = os.environ.get("BENCH_LM_WORKLOAD", "gpt_lm")
    model_tag = {"gpt_lm": "gpt_small",
                 "gpt_medium_lm": "gpt_medium"}.get(workload, workload)
    # seq/remat: only override the preset when EXPLICITLY set — always
    # passing bench defaults would silently defeat lm_long_context's own
    # seq-8192/remat-attn defaults while labeling the record with the
    # preset's name.  gpt_lm keeps the historical bench default of 1024.
    seq_env = os.environ.get("BENCH_LM_SEQ")
    if seq_env:
        seq = int(seq_env)
    elif test_size:
        seq = 128
    elif workload == "lm_long_context":
        seq = None  # the preset's default (8192)
    else:
        seq = 1024
    per_chip_batch = int(
        os.environ.get("BENCH_LM_BATCH", "2" if test_size else "8")
    )
    # "0"/"1"/"attn" — attn = checkpoint only the attention op per block.
    # Unknown values must FAIL here: workloads' remat plumbing treats any
    # other string as remat-off, which once mislabeled a 32k artifact as
    # "remat on" (BENCH_LM_REMAT=on, 2026-08-01).
    remat_env = os.environ.get("BENCH_LM_REMAT")
    if remat_env is None:
        remat = False if workload != "lm_long_context" else None
    elif remat_env in ("0", "1", "attn"):
        remat = {"0": False, "1": True}.get(remat_env, remat_env)
    else:
        raise SystemExit(f"BENCH_LM_REMAT={remat_env!r}: expected 0, 1, or attn")
    attn_impl = os.environ.get("BENCH_LM_ATTN") or None
    xent_impl = os.environ.get("BENCH_LM_XENT") or None
    window_env = os.environ.get("BENCH_LM_WINDOW")
    attn_window = int(window_env) if window_env else None
    # BENCH_LM_QUANT: int8 / int8_stochastic / fp8 (ops/quant.py) —
    # validated by get_workload; BENCH_LM_OVERLAP=1: bucketed backward
    # gradient sync (parallel/overlap.py).
    quant = os.environ.get("BENCH_LM_QUANT") or None
    if quant == "none":
        quant = None
    overlap = os.environ.get("BENCH_LM_OVERLAP") == "1"
    wl = get_workload(
        workload, test_size=test_size,
        global_batch_size=per_chip_batch * n_chips,
        seq_len=seq, remat=remat, attn_impl=attn_impl, xent_impl=xent_impl,
        attn_window=attn_window, quant=quant,
    )
    wl = wl.for_mesh(mesh)
    if seq is None:  # resolved by the preset; recover it for data + MFU
        seq = int(wl.init_batch["input_ids"].shape[1])
    # Record labels must reflect what the preset RESOLVED, not what the
    # envs happened to pass (an lm_long_context record with remat null
    # while the run used remat="attn" is the mislabeling class the
    # BENCH_LM_REMAT validation above exists to prevent).
    _cfg = wl.model.cfg
    if remat is None:
        remat = "attn" if _cfg.remat_attn else bool(_cfg.remat)
    attn_label = attn_impl or _cfg.attn_impl
    xent_label = xent_impl or _cfg.xent_impl

    rng = jax.random.PRNGKey(0)
    state, specs = create_sharded_state(
        wl.init_fn, wl.make_optimizer(), mesh, rng, rules=wl.layout
    )
    overlap_plan = None
    if overlap and mesh.size > 1:
        from distributedtensorflow_tpu.parallel.overlap import OverlapPlan
        from distributedtensorflow_tpu.train.state import split_variables

        param_shapes, _ = split_variables(jax.eval_shape(wl.init_fn, rng))
        overlap_plan = OverlapPlan.build(
            mesh, param_shapes, specs.params,
            bucket_bytes=int(float(
                os.environ.get("BENCH_LM_OVERLAP_MB", "4")) * 2 ** 20),
        )
    step = make_train_step(wl.loss_fn, mesh, specs, overlap=overlap_plan)
    ids = np.random.default_rng(0).integers(
        0, wl.model.cfg.vocab_size, size=(wl.global_batch_size, seq)
    ).astype(np.int32)
    batch = device_put_batch({"input_ids": ids}, mesh)

    # AOT-compile once; reuse for warmup, timing, and cost analysis.
    # BENCH_LM_INNER=K bundles K optimizer steps into one dispatch
    # (engine.make_multi_train_step): the A/B against the default
    # measures how much of the step time is host dispatch rather than
    # chip time.
    inner = int(os.environ.get("BENCH_LM_INNER", "1"))
    n_steps = 20
    if inner > 1:
        from distributedtensorflow_tpu.train import make_multi_train_step

        step = make_multi_train_step(
            wl.loss_fn, mesh, specs, steps_per_call=inner,
            overlap=overlap_plan,
        )
        batch = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (inner,) + x.shape), batch
        )
        n_steps = -(-n_steps // inner)  # outer dispatches
    from distributedtensorflow_tpu.obs.mfu import mfu_fields

    try:
        compiled = step.lower(state, batch, rng).compile()
        state, dt = timed_steps(compiled, state, batch, rng,
                                n_steps=n_steps, warmup=max(1, 3 // inner))
    except Exception as e:
        # A config that doesn't fit lands as a machine-readable record
        # AND a non-zero exit.
        text = str(e)
        result = {
            "metric": f"{model_tag}_train_tokens_per_sec_per_chip",
            "value": None,
            "error": f"{type(e).__name__}: "
                     f"{text.splitlines()[0][:200] if text else ''}",
            "platform": jax.devices()[0].platform,
            "seq": seq,
            "global_batch": wl.global_batch_size,
            "remat": remat,
            "attn_impl": attn_label,
            "attn_window": _cfg.attn_window,
            "xent_impl": xent_label,
            "quant": quant or "none",
            "overlap": overlap_plan is not None,
            "steps_per_call": inner,
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }
        if not test_size:
            persist_result("lm", result)
        print(json.dumps(result))
        raise SystemExit(3)
    n_opt_steps = n_steps * inner
    tokens_per_sec = n_opt_steps * wl.global_batch_size * seq / dt
    per_chip = tokens_per_sec / n_chips

    # Analytic MODEL FLOPs per token, PaLM-style MFU convention: 6N for
    # the param matmuls fwd+bwd plus the quadratic attention term
    # 12·L·H·S (Chinchilla appendix accounting — at seq≥4k no longer
    # negligible against 6N).  Remat RECOMPUTE is deliberately excluded
    # (that would be HFU): remat configs honestly show a lower MFU for
    # the same model, keeping the denominator fixed across impl/remat
    # changes — the stability VERDICT r2 #3 asked for.
    n_params = sum(
        int(np.prod(l.shape)) for l in jax.tree.leaves(state.params)
    )
    cfg = wl.model.cfg
    attn_per_token = 12.0 * cfg.num_layers * cfg.hidden_size * seq
    per_token = 6.0 * n_params + attn_per_token
    device_kind = jax.devices()[0].device_kind
    mfu = mfu_fields(
        compiled, dt, n_steps, device_kind,
        inner * per_token * wl.global_batch_size * seq / n_chips,
        "analytic_model_flops_6N_plus_12LHS_palm_mfu",
        xla_flops_scale=inner,
    )

    # Anchor: an A100 trains GPT-2-small (~124M params) at roughly 150k
    # tokens/sec with remat off; used as the vs_baseline denominator for
    # the gpt_lm preset (other workloads have no public anchor — their
    # vs_baseline is null and the metric name carries the model size).
    result = {
        "metric": f"{model_tag}_train_tokens_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": (round(per_chip / 150_000.0, 4)
                        if workload == "gpt_lm" else None),
        **mfu,
        "platform": jax.devices()[0].platform,
        "device_kind": device_kind,
        "seq": seq,
        "global_batch": wl.global_batch_size,
        "remat": remat,
        "attn_impl": attn_label,
        "attn_window": _cfg.attn_window,
        "xent_impl": xent_label,
        "quant": quant or "none",
        "overlap": overlap_plan is not None,
        "overlap_buckets": (
            len(overlap_plan.buckets) if overlap_plan is not None else 0
        ),
        "step_time_ms": round(1000 * dt / n_opt_steps, 2),
        "steps_per_call": inner,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    if not test_size:
        persist_result("lm", result)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
