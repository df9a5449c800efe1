#!/usr/bin/env python
"""Headline benchmark: ResNet-50 synthetic-ImageNet images/sec/chip (+ MFU).

BASELINE.json metric: "ResNet-50/ImageNet images/sec/chip".  The reference
publishes no numbers (``published: {}``); the north-star wall-clock anchor is
"match 8×A100 NCCL reference wall-clock" — per-chip that is ~2,500 images/sec
(MLPerf-class A100 ResNet-50 throughput), used here as ``vs_baseline``
denominator so the ratio reads "fraction of an A100's ResNet-50 throughput
per TPU chip".

Measures on the TPU or not at all: without one it exits non-zero and
prints no result (there is no cached re-emission and no CPU fallback).
Every measurement is also persisted to ``BENCH_RESULTS/``.

Prints exactly one JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}
"""

from __future__ import annotations

import json
import os
import time

REPO = os.path.dirname(os.path.abspath(__file__))
RESULTS_DIR = os.path.join(REPO, "BENCH_RESULTS")

A100_IMAGES_PER_SEC = 2500.0  # per-GPU anchor (see module docstring)

#: ResNet-50 @224 fwd ≈ 4.1 GMACs/image = 8.2 GFLOPs (multiply-add = 2
#: FLOPs — the convention XLA's cost analysis uses; obs/mfu.py pins both
#: paths to it on a known matmul); train step ≈ 3× fwd.  The previous
#: value (12.3e9) treated the 4.1e9 MAC count as if it were already
#: MACs×2 — exactly the 2× by which mfu_analytic (0.16) undershot
#: mfu_xla_cost (0.32) on BENCH_r02.
RESNET50_TRAIN_FLOPS_PER_IMAGE = 24.6e9

#: The resnet step is HBM-roofline-bound (docs/RESNET_PERF.md §1), so the
#: roofline axis it lives on is bandwidth utilization, not MFU — emitted as
#: ``hbm_bw_util`` alongside both MFUs, against the peaks in obs/mfu.py.


def apply_experiment_flags() -> dict:
    """Apply the A/B compiler-flag env knobs (docs/RESNET_PERF.md §3 L1).

    Must run BEFORE the first jax import in this process.  Appends
    ``BENCH_LIBTPU_FLAGS`` to ``LIBTPU_INIT_ARGS`` and ``BENCH_XLA_FLAGS``
    to ``XLA_FLAGS`` (runtime env of THIS bench process only — never an
    import side effect; see the round-4 PS-deadlock post-mortem).
    Returns the experiment-identifying fields for the result JSON.
    """
    fields = {}
    libtpu = os.environ.get("BENCH_LIBTPU_FLAGS", "")
    if libtpu:
        if libtpu not in os.environ.get("LIBTPU_INIT_ARGS", ""):
            os.environ["LIBTPU_INIT_ARGS"] = (
                os.environ.get("LIBTPU_INIT_ARGS", "") + " " + libtpu
            ).strip()
        fields["libtpu_flags"] = libtpu
    xla = os.environ.get("BENCH_XLA_FLAGS", "")
    if xla:
        if xla not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " " + xla
            ).strip()
        fields["xla_flags"] = xla
    if os.environ.get("BENCH_S2D") == "1":
        fields["space_to_depth"] = True
    return fields


def _is_experiment() -> bool:
    """A/B rows must not compete with the headline cache (main())."""
    return bool(
        os.environ.get("BENCH_LIBTPU_FLAGS")
        or os.environ.get("BENCH_XLA_FLAGS")
        or os.environ.get("BENCH_S2D") == "1"
    )


def run_bench(per_chip_batch: int, n_steps: int, warmup: int,
              image_size: int = 224) -> dict:
    experiment_fields = apply_experiment_flags()  # before first jax import

    import jax
    import jax.numpy as jnp

    import optax
    from jax.sharding import NamedSharding

    from distributedtensorflow_tpu.models import ResNet50
    from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh
    from distributedtensorflow_tpu.parallel.sharding import batch_spec
    from distributedtensorflow_tpu.train import (
        classification_loss,
        create_sharded_state,
        make_train_step,
    )

    mesh = build_mesh(MeshSpec(data=-1))
    n_chips = mesh.size
    global_batch = per_chip_batch * n_chips
    platform = jax.devices()[0].platform
    device_kind = jax.devices()[0].device_kind

    # The conv trunk has no quantizable dense path — a BENCH_QUANT request
    # here must fail loudly (bench_lm owns the quantized-LM rows), not
    # silently label a full-width run as int8.
    if os.environ.get("BENCH_QUANT") not in (None, "", "none"):
        raise SystemExit(
            f"BENCH_QUANT={os.environ['BENCH_QUANT']!r}: resnet50 has no "
            "quantized path; use bench_lm.py with BENCH_LM_QUANT"
        )
    overlap = os.environ.get("BENCH_OVERLAP") == "1"

    model = ResNet50(
        dtype=jnp.bfloat16,
        space_to_depth=bool(experiment_fields.get("space_to_depth")),
    )
    init_fn = lambda r: model.init(r, jnp.zeros((2, image_size, image_size, 3)))
    rng = jax.random.PRNGKey(0)
    state, specs = create_sharded_state(
        init_fn, optax.sgd(0.1, momentum=0.9, nesterov=True), mesh, rng
    )
    # BENCH_OVERLAP=1: bucketed backward-pass gradient sync
    # (parallel/overlap.py) — the collective-matmul overlap A/B.
    overlap_plan = None
    if overlap and mesh.size > 1:
        from distributedtensorflow_tpu.parallel.overlap import OverlapPlan
        from distributedtensorflow_tpu.train.state import split_variables

        param_shapes, _ = split_variables(jax.eval_shape(init_fn, rng))
        overlap_plan = OverlapPlan.build(
            mesh, param_shapes, specs.params,
            bucket_bytes=int(float(
                os.environ.get("BENCH_OVERLAP_MB", "4")) * 2 ** 20),
        )
    # BENCH_INNER=K bundles K optimizer steps per dispatch (the same
    # host-dispatch/RTT A/B bench_lm runs via BENCH_LM_INNER).
    inner = int(os.environ.get("BENCH_INNER", "1"))
    loss_fn = classification_loss(model, weight_decay=1e-4)
    if inner > 1:
        from distributedtensorflow_tpu.train import make_multi_train_step

        step = make_multi_train_step(loss_fn, mesh, specs,
                                     steps_per_call=inner,
                                     overlap=overlap_plan)
    else:
        step = make_train_step(loss_fn, mesh, specs, overlap=overlap_plan)

    # Device-resident synthetic batch: measures the compute+collective path
    # (host input is benchmarked separately by the input-pipeline tests).
    sharding = NamedSharding(mesh, batch_spec(mesh))
    batch = {
        "image": jax.device_put(
            jax.random.normal(
                rng, (global_batch, image_size, image_size, 3), jnp.bfloat16
            ),
            sharding,
        ),
        "label": jax.device_put(
            jax.random.randint(rng, (global_batch,), 0, 1000, jnp.int32),
            sharding,
        ),
    }

    # AOT-compile ONCE and reuse the executable for warmup, timing, and
    # cost analysis (a separate lower().compile() for cost analysis alone
    # would pay a second full ResNet-50 compile).
    if inner > 1:
        batch = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (inner,) + x.shape), batch
        )
        n_steps = -(-n_steps // inner)
        warmup = max(1, warmup // inner)
    compiled = step.lower(state, batch, rng).compile()
    from bench_common import state_bytes_fields, timed_steps
    from distributedtensorflow_tpu.obs.mfu import (
        mfu_fields,
        peak_hbm_bytes_per_s,
        xla_cost_analysis,
    )

    cost = xla_cost_analysis(compiled)
    state, dt = timed_steps(compiled, state, batch, rng,
                            n_steps=n_steps, warmup=warmup)
    images_per_sec = n_steps * inner * global_batch / dt
    per_chip = images_per_sec / n_chips

    # Model-FLOPs utilization, computed per chip on both sides: XLA's cost
    # analysis counts the PARTITIONED (per-device) module's FLOPs, which is
    # exactly the per-chip numerator; the analytic number is global and
    # divided down by n_chips (224px constant scaled by conv-FLOP area).
    mfu = mfu_fields(
        compiled, dt, n_steps, device_kind,
        inner * RESNET50_TRAIN_FLOPS_PER_IMAGE * global_batch
        * (image_size / 224.0) ** 2 / n_chips,
        "analytic_24.6GF_per_image",
        xla_flops_scale=inner,
        cost=cost,
    )

    # HBM roofline axis (docs/RESNET_PERF.md): achieved bandwidth from
    # XLA's cost analysis over measured step time, as a fraction of peak.
    hbm_bw_util = None
    ba = float(cost.get("bytes accessed", 0)) if cost else 0.0
    if ba > 0:
        hbm_bw_util = (
            (ba * inner * n_steps / dt) / peak_hbm_bytes_per_s(device_kind)
        )

    return {
        "metric": "resnet50_synthetic_imagenet_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / A100_IMAGES_PER_SEC, 4),
        **mfu,
        "hbm_bw_util": round(hbm_bw_util, 4) if hbm_bw_util else None,
        **state_bytes_fields(state),
        **experiment_fields,
        "platform": platform,
        "device_kind": device_kind,
        "n_chips": n_chips,
        "global_batch": global_batch,
        "n_steps": n_steps * inner,
        "image_size": image_size,
        "step_time_ms": round(1000 * dt / (n_steps * inner), 2),
        "steps_per_call": inner,
        "quant": "none",  # resnet50 has no quantized path (see above)
        "overlap": overlap_plan is not None,
        "overlap_buckets": (
            len(overlap_plan.buckets) if overlap_plan is not None else 0
        ),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }


def _ensure_imagenet_records(root: str, *, n_images: int, image_size: int,
                             num_shards: int = 4) -> list:
    """ImageNet-shaped record shards (synthetic content, REAL decode path).

    Raw fixed-shape format — 4-byte little-endian int32 label followed by
    the uint8 HWC image bytes — rather than npz: the framework's stance
    (like every production TPU input pipeline) is that training data is
    pre-processed into a tensor-ready layout once, so the hot path decodes
    with one ``np.frombuffer`` per record instead of a zip-container parse.
    Written once and reused across bench runs (content is seeded).
    """
    import numpy as np

    from distributedtensorflow_tpu.native.recordio import RecordWriter

    paths = [os.path.join(root, f"train-{i:05d}.rec")
             for i in range(num_shards)]
    # .done marker (written LAST, after close) is the integrity gate: a
    # timeout/crash mid-write leaves truncated shards that exist on disk,
    # and a changed n_images must regenerate rather than silently reuse.
    done = os.path.join(root, ".done")
    spec = f"{n_images}x{image_size}x{num_shards}"
    try:
        with open(done) as f:
            if f.read().strip() == spec and all(
                    os.path.exists(p) for p in paths):
                return paths
    except OSError:
        pass
    os.makedirs(root, exist_ok=True)
    if os.path.exists(done):
        os.unlink(done)
    rng = np.random.default_rng(0)
    writers = [RecordWriter(p) for p in paths]
    try:
        for i in range(n_images):
            img = rng.integers(0, 256, (image_size, image_size, 3),
                               dtype=np.uint8)
            label = np.int32(rng.integers(0, 1000)).tobytes()
            writers[i % num_shards].write(label + img.tobytes())
    finally:
        for w in writers:
            w.close()
    with open(done, "w") as f:
        f.write(spec)
    return paths


def _decode_raw_image(image_size: int):
    import numpy as np

    def decode(record: bytes) -> dict:
        label = np.frombuffer(record, np.int32, count=1)[0]
        img = np.frombuffer(record, np.uint8, offset=4).reshape(
            image_size, image_size, 3
        )
        return {"image": img, "label": label}

    return decode


def run_bench_records(per_chip_batch: int, n_steps: int, warmup: int,
                      image_size: int = 224) -> dict:
    """The headline step with the INPUT PIPELINE IN THE LOOP (VERDICT r4
    #3): native record reader -> decode -> per-host batch -> Prefetcher
    (background host->device transfer) -> train step, per-step batches —
    the reference's north-star shape (SURVEY.md §1 L5, §3.4) instead of a
    device-resident synthetic batch.  uint8 on the wire (one in-graph
    cast, 4x less host->device traffic than bf16-on-host)."""
    experiment_fields = apply_experiment_flags()

    import jax
    import jax.numpy as jnp

    import optax

    from distributedtensorflow_tpu.data import Prefetcher
    from distributedtensorflow_tpu.data.recordio_dataset import (
        repeated_record_dataset,
    )
    from distributedtensorflow_tpu.models import ResNet50
    from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh
    from distributedtensorflow_tpu.train import (
        classification_loss,
        create_sharded_state,
        make_train_step,
    )

    mesh = build_mesh(MeshSpec(data=-1))
    n_chips = mesh.size
    global_batch = per_chip_batch * n_chips
    platform = jax.devices()[0].platform
    device_kind = jax.devices()[0].device_kind

    n_images = max(4 * global_batch, 2048 if image_size == 224 else 256)
    records_root = os.path.join(
        RESULTS_DIR, f".imagenet_records_{image_size}"
    )
    paths = _ensure_imagenet_records(
        records_root, n_images=n_images, image_size=image_size
    )

    model = ResNet50(
        dtype=jnp.bfloat16,
        space_to_depth=bool(experiment_fields.get("space_to_depth")),
    )
    init_fn = lambda r: model.init(r, jnp.zeros((2, image_size, image_size, 3)))
    rng = jax.random.PRNGKey(0)
    state, specs = create_sharded_state(
        init_fn, optax.sgd(0.1, momentum=0.9, nesterov=True), mesh, rng
    )
    step = make_train_step(classification_loss(model, weight_decay=1e-4),
                           mesh, specs)

    it = repeated_record_dataset(
        paths, batch_size=global_batch,
        decode_fn=_decode_raw_image(image_size), shuffle_buffer=0,
    )
    with Prefetcher(it, mesh, buffer_size=3) as pf:
        # warmup compiles with a real pipeline batch
        for _ in range(warmup):
            state, metrics = step(state, next(pf), rng)
        jax.block_until_ready(metrics["loss"])
        t0 = time.perf_counter()
        for _ in range(n_steps):
            state, metrics = step(state, next(pf), rng)
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0

    images_per_sec = n_steps * global_batch / dt
    per_chip = images_per_sec / n_chips
    # Same MFU triple as the synthetic row (the gap between the two rows
    # IS the input-pipeline cost).  No AOT executable here, so cost={}
    # skips XLA cost analysis and mfu_xla_cost emits as None.
    from distributedtensorflow_tpu.obs.mfu import mfu_fields

    mfu = mfu_fields(
        None, dt, n_steps, device_kind,
        RESNET50_TRAIN_FLOPS_PER_IMAGE * global_batch
        * (image_size / 224.0) ** 2 / n_chips,
        "analytic_24.6GF_per_image", cost={},
    )
    return {
        "metric": "resnet50_records_imagenet_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / A100_IMAGES_PER_SEC, 4),
        **mfu,
        "input": "records",
        "record_format": "raw_u8_label32",
        "n_record_images": n_images,
        **experiment_fields,
        "platform": platform,
        "device_kind": device_kind,
        "n_chips": n_chips,
        "global_batch": global_batch,
        "n_steps": n_steps,
        "image_size": image_size,
        "step_time_ms": round(1000 * dt / n_steps, 2),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }


def main() -> None:
    from bench_common import persist_result, start

    start("bench")
    records = os.environ.get("BENCH_INPUT") == "records"
    bench_fn = run_bench_records if records else run_bench
    result = bench_fn(
        per_chip_batch=int(os.environ.get("BENCH_BATCH", "128")),
        n_steps=int(os.environ.get("BENCH_STEPS", "30")),
        warmup=3,
    )
    # Experiment rows (flags / s2d) and the records-input row persist
    # under their own prefixes, so they never pass for the headline row.
    prefix = ("resnet50rec" if records
              else "resnet50ab" if _is_experiment() else "resnet50")
    persist_result(prefix, result)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
