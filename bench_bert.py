#!/usr/bin/env python
"""Benchmark for reference config #4: BERT-base MLM examples/sec/chip.

The reference trains BERT-base MLM (512 tokens) with gradient accumulation
over CollectiveAllReduce (BASELINE.json configs[3]).  This measures the raw
train-step throughput of our preset on one chip (accumulation is a lax.scan
over the same compiled step — per-example cost is identical, so the raw
step is the honest unit).

Knobs (env): ``BENCH_BERT_BATCH`` per-chip batch (default 16),
``BENCH_BERT_SEQ`` (default 512).  Prints one JSON line like bench.py;
exits non-zero without a TPU.
"""

from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from bench_common import persist_result, start

def main() -> None:
    start("bench_bert")
    from distributedtensorflow_tpu.data import InputContext, device_put_batch
    from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh
    from distributedtensorflow_tpu.train import create_sharded_state, make_train_step
    from distributedtensorflow_tpu.workloads import get_workload

    mesh = build_mesh(MeshSpec(data=-1))
    n_chips = mesh.size
    test_size = os.environ.get("BENCH_BERT_TEST") == "1"
    per_chip_batch = int(
        os.environ.get("BENCH_BERT_BATCH", "2" if test_size else "16")
    )
    seq = int(os.environ.get("BENCH_BERT_SEQ", "128" if test_size else "512"))
    wl = get_workload(
        "bert_mlm", test_size=test_size,
        global_batch_size=per_chip_batch * n_chips,
        seq_len=seq,
    )

    rng = jax.random.PRNGKey(0)
    state, specs = create_sharded_state(
        wl.init_fn, wl.make_optimizer(), mesh, rng, rules=wl.layout
    )
    # BENCH_BERT_INNER=K: K optimizer steps per dispatch (the same
    # host-dispatch A/B bench_lm/bench.py run via their INNER knobs).
    inner = int(os.environ.get("BENCH_BERT_INNER", "1"))
    if inner > 1:
        from distributedtensorflow_tpu.train import make_multi_train_step

        step = make_multi_train_step(wl.loss_fn, mesh, specs,
                                     steps_per_call=inner)
    else:
        step = make_train_step(wl.loss_fn, mesh, specs)
    ctx = InputContext(1, 0, wl.global_batch_size)
    batch = device_put_batch(next(iter(wl.input_fn(ctx, 0))), mesh)
    if inner > 1:
        import jax.numpy as jnp

        batch = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (inner,) + x.shape), batch
        )

    compiled = step.lower(state, batch, rng).compile()
    n_steps = -(-20 // inner)
    from bench_common import timed_steps
    from distributedtensorflow_tpu.obs.mfu import mfu_fields

    state, dt = timed_steps(compiled, state, batch, rng,
                            n_steps=n_steps, warmup=max(1, 3 // inner))
    n_opt = n_steps * inner
    per_chip = n_opt * wl.global_batch_size / dt / n_chips

    # Analytic model FLOPs honoring the GATHERED head: encoder matmul params
    # run at all S positions, the mlm_* head params only at the P gathered
    # positions, and embedding tables are lookups (no matmul FLOPs).
    n_encoder = n_head = 0
    for path, leaf in jax.tree.leaves_with_path(state.params):
        key = "/".join(str(getattr(k, "key", k)) for k in path)
        if "embed" in key:
            continue
        n = int(np.prod(leaf.shape))
        if "mlm_" in key:
            n_head += n
        else:
            n_encoder += n
    from distributedtensorflow_tpu.models import max_predictions_for

    p_gathered = max_predictions_for(seq)  # the preset's gathered-head size
    # + the quadratic attention term: 12·L·H·S analytic FLOPs per token.
    cfg = wl.model.cfg
    attn = 12.0 * cfg.num_layers * cfg.hidden_size * seq * seq
    fallback = (
        wl.global_batch_size
        * (6.0 * (n_encoder * seq + n_head * p_gathered) + attn) / n_chips
    )
    device_kind = jax.devices()[0].device_kind
    mfu = mfu_fields(
        compiled, dt, n_steps, device_kind, inner * fallback,
        "analytic_6N_enc_at_S_head_at_P",
        xla_flops_scale=inner,
    )

    # Anchor: an A100 pretrains BERT-base (seq 512) at roughly 200
    # examples/sec (MLPerf-class phase-2 throughput).
    result = {
        "metric": "bert_base_mlm_examples_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "examples/sec/chip",
        "vs_baseline": round(per_chip / 200.0, 4),
        **mfu,
        "platform": jax.devices()[0].platform,
        "device_kind": device_kind,
        "seq": seq,
        "global_batch": wl.global_batch_size,
        "step_time_ms": round(1000 * dt / n_opt, 2),
        "steps_per_call": inner,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    # Flash-threshold experiment rows (DTF_MIN_SEQ_FOR_PALLAS, the
    # attn_512/BERT A/B) label themselves and persist under bertab_* so
    # they never compete with the headline bert_* cache.
    flash_thresh = os.environ.get("DTF_MIN_SEQ_FOR_PALLAS")
    if flash_thresh:
        result["min_seq_for_pallas"] = int(flash_thresh)
    if not test_size:
        persist_result("bertab" if flash_thresh else "bert", result)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
