#!/usr/bin/env python
"""train.py — CLI entrypoint (reference parity: the repo's train.py, SURVEY.md §1 L7).

Picks a workload preset (the five BASELINE.json configs), builds the mesh
(the strategy choice), and runs the SPMD training loop.  Works identically
on one chip or a multi-host pod; multi-host bootstrap is automatic from
JAX/TF_CONFIG env (run_distributed.sh semantics — SURVEY.md §5.6).

Examples:
  python train.py --workload mnist_lenet --steps 200
  python train.py --workload imagenet_resnet50 --steps 100 --mesh data=-1
  python train.py --workload bert_mlm --steps 50 --mesh data=2,model=4
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()  # start-up spans count from here

import argparse  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402


#: --remat CLI choice -> get_workload(remat=...) value; single mapping
#: shared by the trainer and evaluator roles so their graphs can't diverge.
REMAT_FLAG = {"on": True, "off": False, "attn": "attn", None: None}
_PP_HANDOFF = {"fp32": None, "bf16": "bfloat16"}


def _is_pipelined(wl) -> bool:
    """True when the mesh-bound workload runs the pipeline-parallel model
    (the record-stamping hook for the pipeline_* metric fields)."""
    from distributedtensorflow_tpu.models.gpt_pipeline import PipelinedGPT

    return isinstance(wl.model, PipelinedGPT)


def parse_mesh(s: str | None):
    from distributedtensorflow_tpu.parallel import MeshSpec

    if not s:
        return None
    kw = {}
    for part in s.split(","):
        k, v = part.split("=")
        kw[k.strip()] = int(v)
    return MeshSpec(**kw)


def apply_config_file(
    p: argparse.ArgumentParser, args: argparse.Namespace, argv: list[str]
):
    """JSON config-tree support (SURVEY.md §5.6): file supplies defaults,
    explicitly-passed CLI flags win (even when passed their default value),
    and file values go through each flag's argparse type conversion."""
    import json

    # dests the user actually typed on the command line
    explicit: set[str] = set()
    for action in p._actions:
        for opt in action.option_strings:
            if any(a == opt or a.startswith(opt + "=") for a in argv):
                explicit.add(action.dest)
    by_dest = {a.dest: a for a in p._actions}

    with open(args.config) as f:
        cfg = json.load(f)
    for k, v in cfg.items():
        key = k.replace("-", "_")
        action = by_dest.get(key)
        if action is None:
            raise SystemExit(f"config file key {k!r} is not a known flag")
        if key in explicit:
            continue  # CLI wins
        if action.type is not None and v is not None:
            try:
                v = action.type(v)
            except (TypeError, ValueError) as e:
                raise SystemExit(
                    f"config file key {k!r}: invalid value {v!r} ({e})"
                )
        elif isinstance(action.const, bool):  # store_true/false flags
            v = bool(v)
        setattr(args, key, v)
    return args


def record_files(data_dir):
    """Record files under ``data_dir`` (TFRecord-compatible framing)."""
    import glob as globlib

    files = sorted(
        f for pat in ("*.tfrecord", "*.rio", "*.rec")
        for f in globlib.glob(os.path.join(data_dir, pat))
    )
    if not files:
        raise SystemExit(f"{data_dir}: no record files")
    return files


def shardable_batches(it, mesh):
    """Truncate a ragged final batch to a multiple of the mesh batch
    divisor — ``device_put_batch`` cannot shard e.g. 5 rows over data=2.
    Drops < shard_div examples (vs < batch_size under drop_remainder=True);
    the weighted eval counts the short batch by its true size."""
    from distributedtensorflow_tpu.parallel.mesh import replica_count

    shard_div = replica_count(mesh)
    for batch in it:
        n = len(next(iter(batch.values())))
        keep = n - n % shard_div
        if keep == 0:
            continue
        if keep != n:
            logging.info(
                "eval: truncated ragged final batch %d -> %d "
                "(mesh batch divisor %d)", n, keep, shard_div,
            )
            batch = {k: v[:keep] for k, v in batch.items()}
        yield batch


def apply_optimizer_flags(wl, args):
    """--optimizer/--lr/--schedule override the preset's optax chain.

    Used by BOTH roles: the sidecar evaluator's state template must build
    the same optimizer as the trainer for opt_state restore to match.
    """
    if not args.optimizer:
        if args.lr is not None:
            raise SystemExit(
                "--lr requires --optimizer (which family to build)"
            )
        if (args.schedule != "constant" or args.warmup_steps
                or args.weight_decay or args.clipnorm
                or args.decay_mask != "none"):
            raise SystemExit(
                "--schedule/--warmup-steps/--weight-decay/--clipnorm/"
                "--decay-mask require --optimizer (they parameterize the "
                "override, not the preset's own optax chain)"
            )
        return wl
    if args.lr is None:
        raise SystemExit("--optimizer requires --lr")
    import dataclasses

    from distributedtensorflow_tpu.train.optimizers import (
        _DECAY_CAPABLE,
        build_optimizer,
        build_schedule,
    )

    # Fail flag misuse HERE (clean SystemExit) rather than as a deep
    # ValueError when the deferred make_optimizer first runs.
    if args.weight_decay and args.optimizer not in _DECAY_CAPABLE:
        raise SystemExit(
            f"--optimizer {args.optimizer} has no decoupled weight decay "
            f"(supported: {', '.join(_DECAY_CAPABLE)})"
        )
    if args.clipnorm < 0:
        raise SystemExit(
            f"--clipnorm must be >= 0 (0 disables clipping), got {args.clipnorm}"
        )
    try:
        lr = build_schedule(
            args.schedule, args.lr,
            warmup_steps=args.warmup_steps, total_steps=args.steps,
        )
    except ValueError as e:
        raise SystemExit(str(e)) from None
    mask = None
    if args.decay_mask == "bias-norm":
        if not args.weight_decay:
            raise SystemExit("--decay-mask requires --weight-decay > 0")
        if args.optimizer not in ("adamw", "lamb", "lion"):
            raise SystemExit(
                f"--decay-mask is supported for adamw/lamb/lion, not "
                f"{args.optimizer}"
            )
        from distributedtensorflow_tpu.train.optimizers import (
            exclude_bias_and_norm_mask as mask,
        )
    opt_name, wd, clip = args.optimizer, args.weight_decay, args.clipnorm
    return dataclasses.replace(
        wl,
        # decay_mask is overridable so --zero can swap the callable for a
        # concrete pytree resolved on the UNCHUNKED shapes (see
        # _concrete_decay_mask).
        make_optimizer=lambda decay_mask=mask: build_optimizer(
            opt_name, lr, weight_decay=wd, global_clipnorm=clip,
            decay_mask=decay_mask,
        ),
    )


def _concrete_decay_mask(wl, rng):
    """Resolve the bias-norm decay mask into a concrete bool pytree on the
    workload's UNCHUNKED param shapes.

    Under ``--zero`` optax re-evaluates a *callable* mask on whatever tree
    ``tx`` sees — the chunked ``(degree, chunk)`` view, where every leaf
    is rank-2, so ``exclude_bias_and_norm_mask``'s rank<=1 exclusion would
    silently start decaying unnamed 1-D parameters and diverge from the
    replicated trajectory.  A concrete pytree is layout-invariant
    (chunking preserves the treedef)."""
    from distributedtensorflow_tpu.train.optimizers import (
        exclude_bias_and_norm_mask,
    )
    from distributedtensorflow_tpu.train.state import split_variables

    params, _ = split_variables(jax.eval_shape(wl.init_fn, rng))
    return exclude_bias_and_norm_mask(params)


def run_evaluator(args) -> None:
    """Sidecar-evaluator role: poll --checkpoint-dir, evaluate new
    checkpoints on this process's local devices (standalone — never joins
    the training cluster, mirroring the reference's evaluator-task
    semantics)."""
    from distributedtensorflow_tpu import parallel
    from distributedtensorflow_tpu.checkpoint import CheckpointManager
    from distributedtensorflow_tpu.data import InputContext, Prefetcher
    from distributedtensorflow_tpu.train import (
        SidecarEvaluator,
        create_sharded_state,
        make_eval_step,
    )
    from distributedtensorflow_tpu.workloads import get_workload

    if not args.checkpoint_dir:
        raise SystemExit("--job evaluator requires --checkpoint-dir")
    wl = get_workload(
        args.workload, test_size=args.test_size,
        global_batch_size=args.batch_size, sp_scheme=args.sp_scheme,
        pp_virtual=args.pp_virtual, seq_len=args.seq_len,
        pp_handoff=_PP_HANDOFF[args.pp_handoff_dtype],
        pp_schedule=args.pipeline_schedule,
        attn_impl=args.attn_impl,
        xent_impl=args.xent_impl,
        kv_heads=args.kv_heads,
        attn_window=args.attn_window,
        remat=REMAT_FLAG[args.remat],
        # the restore template's param tree is quant-invariant, but the
        # eval forward should run the trainer's compute mode
        quant=None if args.quant == "none" else args.quant,
    )
    if wl.eval_fn is None:
        raise SystemExit(f"workload {wl.name!r} has no eval_fn to sidecar")
    wl = apply_optimizer_flags(wl, args)
    spec = parse_mesh(args.mesh) or parallel.MeshSpec(data=-1)
    mesh = parallel.build_mesh(spec)
    wl = wl.for_mesh(mesh)
    logging.info("evaluator: workload=%s mesh=%s watching %s",
                 wl.name, dict(mesh.shape), args.checkpoint_dir)

    rng = jax.random.PRNGKey(args.seed)
    # Mirror the trainer's --zero so the restore template's optimizer
    # state matches the watched checkpoints' chunked layout.
    zero_sharder = None
    if args.zero:
        from distributedtensorflow_tpu.parallel.mesh import replica_count
        from distributedtensorflow_tpu.parallel.zero import ZeroSharder

        if replica_count(mesh) > 1:
            zero_sharder = ZeroSharder(mesh)
    # Same decay-mask resolution as the trainer: the restore template's
    # optax MaskedState treedef must match the watched checkpoints'.
    if zero_sharder is not None and args.decay_mask == "bias-norm":
        tx = wl.make_optimizer(_concrete_decay_mask(wl, rng))
    else:
        tx = wl.make_optimizer()
    state, specs = create_sharded_state(
        wl.init_fn, tx, mesh, rng,
        rules=wl.layout, fsdp=wl.fsdp, zero=zero_sharder,
    )
    eval_step = make_eval_step(wl.eval_fn, mesh, specs)
    ctx = InputContext(1, 0, wl.global_batch_size)

    if args.eval_data_dir or args.data_dir:
        from distributedtensorflow_tpu.data import record_dataset

        files = record_files(args.eval_data_dir or args.data_dir)
        eval_iter_fn = lambda: Prefetcher(  # one finite unshuffled pass
            shardable_batches(record_dataset(
                files, ctx, batch_size=ctx.per_host_batch_size,
                policy=args.autoshard, shuffle_buffer=0,
                drop_remainder=False,
            ), mesh),
            mesh,
        )
        eval_steps = 0  # dataset-wide exact eval
    else:
        eval_iter_fn = lambda: Prefetcher(
            wl.input_fn(ctx, args.seed + 999), mesh
        )
        eval_steps = 10  # synthetic iterators are infinite; stay bounded

    sidecar = SidecarEvaluator(
        CheckpointManager(args.checkpoint_dir),
        eval_step,
        eval_iter_fn,
        state,
        eval_steps=eval_steps,
        poll_interval_s=args.poll_interval,
        max_evaluations=args.max_evaluations,
        stop_after_step=args.steps if args.steps > 0 else None,
        idle_timeout_s=args.idle_timeout,
        logdir=args.logdir,
    )
    history = sidecar.run()
    logging.info("evaluator: done; evaluated %d checkpoints", len(history))


def run_async_ps(args) -> None:
    """Async parameter-server role (reference config #5 semantics).

    Chief process: hosts the PS shards, spawns ``--num-workers`` grad-worker
    processes, and reports progress while pushes are applied barrier-free
    (stale gradients).  The reference's ``ClusterCoordinator``-driven
    ``ParameterServerStrategyV2`` path (SURVEY.md §3.3) — host-side by
    design; the TPU stays with the sync engine (see parallel/param_server.py
    module docstring)."""
    import time as time_mod

    from distributedtensorflow_tpu.parallel.param_server import AsyncPSTrainer
    from distributedtensorflow_tpu.parallel.sharding import MinSizePartitioner
    from distributedtensorflow_tpu.workloads import get_workload

    if args.target_metric and args.target_value is None:
        raise SystemExit("--target-metric requires --target-value")
    batch = args.batch_size or 256
    # Same flag semantics/validation as the train and evaluator roles
    # (--lr-without---optimizer, --schedule/--warmup-steps, _DECAY_CAPABLE).
    base_wl = get_workload(
        args.workload, test_size=args.test_size,
        global_batch_size=batch * args.num_workers,
    )
    flagged_wl = apply_optimizer_flags(base_wl, args)
    kwargs = {}
    if flagged_wl is not base_wl:
        kwargs["make_optimizer"] = flagged_wl.make_optimizer
    trainer = AsyncPSTrainer(
        args.workload,
        num_ps=args.num_ps,
        num_workers=args.num_workers,
        steps=args.steps,
        batch_size=batch,
        test_size=args.test_size,
        partitioner=MinSizePartitioner(min_shard_bytes=64 << 10),
        seed=args.seed,
        **kwargs,
    )
    logging.info(
        "async-ps: workload=%s ps=%d workers=%d steps=%d batch=%d/worker",
        args.workload, args.num_ps, args.num_workers, args.steps, batch,
    )
    from distributedtensorflow_tpu.utils.metrics import MetricWriter

    # Routed through MetricWriter (not a raw open()) so every metrics.jsonl
    # producer shares one append/flush/close discipline; records here are
    # free-form (nested staleness histogram), hence write_record.
    writer = MetricWriter(args.logdir, use_tensorboard=False)
    total = args.num_workers * args.num_ps * args.steps
    with writer, trainer:
        trainer.start()
        last = -1
        while True:
            try:
                trainer.join(timeout=2.0)
                break
            except TimeoutError:
                pass
            v = trainer.global_version()
            if v != last:
                writer.write_record(
                    {"time": time_mod.time(), "global_version": v,
                     "of": total})
                logging.info("async-ps: %d/%d updates applied", v, total)
            last = v
        metrics = (
            trainer.evaluate(batches=4) if trainer.workload.eval_fn else {}
        )
        stats = trainer.ps_stats()
        hist: dict[str, int] = {}
        for s in stats:
            for k, n in s["staleness_hist"].items():
                hist[k] = hist.get(k, 0) + n
        first, last_loss = trainer.first_last_mean_loss()
        logging.info(
            "async-ps: done — %d updates, loss %.4f -> %.4f, staleness %s, "
            "eval %s",
            trainer.global_version(), first, last_loss,
            dict(sorted(hist.items(), key=lambda kv: int(kv[0]))),
            {k: round(v, 4) for k, v in metrics.items()},
        )
        writer.write_record({
            "time": time_mod.time(), "final": True,
            "loss_first": first, "loss_last": last_loss,
            "staleness_hist": hist, **metrics,
        })
        if args.target_metric:
            got = metrics.get(args.target_metric)
            if got is None:
                raise SystemExit(
                    f"--target-metric {args.target_metric} not in {metrics}"
                )
            ok = (got >= args.target_value if args.target_mode == "max"
                  else got <= args.target_value)
            if not ok:
                raise SystemExit(
                    f"async-ps: target {args.target_metric}="
                    f"{args.target_value} not reached (got {got:.4f})"
                )
            logging.info("async-ps: target %s=%s reached (%.4f)",
                         args.target_metric, args.target_value, got)


def _ps_wait_s() -> float:
    """Worker-side PS-reachability wait (seconds).  ONE definition: the
    ps tier's startup grace is derived from this same number so the two
    clocks cannot silently diverge (the startup-race deadlock class)."""
    return float(os.environ.get("DTFT_PS_WAIT_S", "180"))


def run_ps_cluster_task(args, cluster, task_type, task_index) -> None:
    """One task of a TF_CONFIG parameter-server cluster.

    The reference's legacy launcher path (SURVEY.md §1 L7: one process per
    ``tf.train.ClusterSpec`` task via run_distributed.sh + per-task
    TF_CONFIG): a ``ps`` task serves its parameter shard until the job's
    push budget is absorbed; ``chief``/``worker`` tasks run the async
    pull → grad → push loop.  All tasks derive byte-identical shards and
    placement from the shared CLI flags (``build_cluster_pieces``), so
    bootstrap needs no parameter transfer — the same same-flags-per-task
    contract the reference's TF_CONFIG scripts rely on.
    """
    from distributedtensorflow_tpu.parallel.param_server import (
        AsyncPSClient,
        PSServer,
        PSUnavailableError,
        build_cluster_pieces,
        worker_loop,
    )
    from distributedtensorflow_tpu.parallel.sharding import MinSizePartitioner
    from distributedtensorflow_tpu.workloads import get_workload

    # The PS tier is host-side by design: every role computes on CPU and
    # the accelerator stays with the sync engine (param_server.py docs).
    jax.config.update("jax_platforms", "cpu")

    if task_type not in ("ps", "chief", "worker"):
        raise SystemExit(
            f"TF_CONFIG task.type {task_type!r} has no role in a ps "
            "cluster (expected ps, chief, or worker)"
        )
    ps_addrs = list(cluster["ps"])
    chiefs = list(cluster.get("chief", []))
    workers = chiefs + list(cluster.get("worker", []))
    num_ps, num_workers = len(ps_addrs), len(workers)
    if num_workers == 0:
        raise SystemExit("TF_CONFIG ps cluster has no chief/worker tasks")
    batch = args.batch_size or 256
    spec = {
        "workload": args.workload, "steps": args.steps,
        "batch_size": batch, "test_size": args.test_size,
        "seed": args.seed, "sleep_s": 0.0,
    }
    base_wl = get_workload(
        args.workload, test_size=args.test_size,
        global_batch_size=batch * num_workers,
    )
    flagged = apply_optimizer_flags(base_wl, args)
    make_opt = flagged.make_optimizer if flagged is not base_wl else None
    _wl, shards, plan, make_opt = build_cluster_pieces(
        spec, num_ps, num_workers,
        MinSizePartitioner(min_shard_bytes=64 << 10), make_opt,
        workload_obj=base_wl,
    )

    if task_type == "ps":
        host, port = ps_addrs[task_index].rsplit(":", 1)
        bind = host if host in ("127.0.0.1", "localhost") else "0.0.0.0"
        server = PSServer(shards[task_index], make_opt,
                          port=int(port), bind=bind)
        total = num_workers * args.steps  # one push per worker-step
        logging.info(
            "ps task %d/%d serving %d vars on %s (budget %d pushes)",
            task_index, num_ps, len(shards[task_index]),
            ps_addrs[task_index], total,
        )
        # Startup grace: cover the workers' own bounded reachability
        # wait (DTFT_PS_WAIT_S) plus build slack, so the ps tier never
        # idles out while a slow worker is still starting (both clocks
        # race otherwise — see PSServer.serve_until).
        grace = max(
            float(args.idle_timeout or 0),
            _ps_wait_s() + 120,
        )
        version = server.serve_until(
            total, idle_timeout_s=args.idle_timeout, startup_grace_s=grace
        )
        logging.info("ps task %d done at version %d", task_index, version)
        server.stop()
        return

    # chief/worker: run the async loop.  chief is worker 0 (trains too,
    # the common TF arrangement); "worker" indices shift past the chiefs.
    worker_id = (
        task_index if task_type == "chief"
        else task_index + len(chiefs)
    )
    # Bounded wait for the PS tier to come up (tasks start unordered).
    # 180s, not 60: at 60 the 4-process e2e test flaked once under a
    # fully loaded 1-core box (suite + watcher competing, 2026-08-01) —
    # each PS process needs its own jax/numpy import before it binds,
    # and those imports serialize under oversubscription.
    # DTFT_PS_WAIT_S overrides (e.g. to shorten a deliberate
    # unreachable-PS scenario).
    client = AsyncPSClient(ps_addrs, plan, worker_id=worker_id)
    wait_s = _ps_wait_s()
    deadline = time.time() + wait_s
    while True:
        try:
            client.stats()
            break
        except PSUnavailableError:
            if time.time() > deadline:
                raise SystemExit(f"PS tasks unreachable after {wait_s:.0f}s")
            time.sleep(0.5)
    logging.info(
        "%s task %d = async worker %d/%d against ps=%s",
        task_type, task_index, worker_id, num_workers, ps_addrs,
    )
    losses, staleness = worker_loop(
        worker_id, num_workers, ps_addrs, plan, spec
    )
    hist: dict[int, int] = {}
    for s in staleness:
        hist[s] = hist.get(s, 0) + 1
    logging.info(
        "worker %d done: loss %.4f -> %.4f over %d steps, staleness %s",
        worker_id,
        losses[0] if losses else float("nan"),
        losses[-1] if losses else float("nan"),
        len(losses), dict(sorted(hist.items())),
    )


def _flash_layout(wl, mesh) -> dict:
    """``flash_layout``, ``attn_residuals``,
    ``attn_residual_bytes_per_layer``, ``flash_causal_tile``,
    ``flash_causal_share``, ``xent_products_per_step`` and
    ``xent_dlog_chunk_tokens`` for the ``startup.trainer`` row and the
    start-up log: the form the step's dense attention lowers to
    (``models.gpt.attention_layout``: "qkv_tiles", "bhsd" or "xla"), what
    a remat'd block does for that attention's residuals in the backward
    (``GPTLM.attn_residuals``: "saved" with the bytes a layer keeps on a
    device, "recomputed", or null where nothing is rematerialised) and the
    rows of the sub-tiles the tile kernels walk a causal diagonal block in
    with the share of its square they compute
    (``GPTLM.flash_causal_tile``; null where blocks are taken whole), and
    the ``tokens x d x V`` products a step's loss head runs with the tokens
    a chunk of its backward holds dlogits for (``GPTLM.xent_products``: 4;
    null where the head is not the fused one), read under the trainer's
    mesh as the step is traced.
    The fall-back from one form to the next is silent and costs a tenth of
    a step, and with it goes the saving, so a run's trace says which it
    got.  Empty for a model that has no such choice."""
    ask = getattr(wl.model, "flash_layout", None)
    ids = wl.init_batch.get("input_ids")
    if ask is None or ids is None:
        return {}
    with jax.sharding.set_mesh(mesh):
        layout = ask(ids.shape[1])
        residuals, kept = wl.model.attn_residuals(
            wl.global_batch_size, ids.shape[1])
        tile, share = wl.model.flash_causal_tile(
            wl.global_batch_size, ids.shape[1])
        products, chunk = wl.model.xent_products(
            wl.global_batch_size, ids.shape[1])
    if layout is None:
        return {}
    logging.info("flash_layout: %s", layout)
    logging.info("attn_residuals: %s (%s bytes a layer)", residuals, kept)
    logging.info("flash_causal_tile: %s (share %s of a diagonal block)",
                 tile, share)
    logging.info("xent_products_per_step: %s (dlog chunks of %s tokens)",
                 products, chunk)
    return {"flash_layout": layout, "attn_residuals": residuals,
            "attn_residual_bytes_per_layer": kept,
            "flash_causal_tile": tile, "flash_causal_share": share,
            "xent_products_per_step": products,
            "xent_dlog_chunk_tokens": chunk}


def _optimizer_update(params) -> dict:
    """``optimizer_update`` and ``optimizer_update_leaves`` for the
    ``startup.trainer`` row and the start-up log, beside ``flash_layout``:
    how the step's update stands to the backward that feeds it
    (``train.engine.optimizer_update``: "separate" — the gradients pass a
    ``lax.optimization_barrier`` before ``apply_gradients``, so the update
    is a region of its own and cannot fuse into the weight-gradient
    products — with the number of gradient leaves behind it)."""
    from distributedtensorflow_tpu.train import engine

    update, leaves = engine.optimizer_update(params)
    logging.info("optimizer_update: %s (%d gradient leaves)", update, leaves)
    return {"optimizer_update": update, "optimizer_update_leaves": leaves}


def main() -> None:
    # allow_abbrev=False: apply_config_file detects explicitly-typed flags
    # by matching argv against option strings; prefix abbreviations would
    # dodge that match and get silently overridden by config-file values.
    p = argparse.ArgumentParser(description=__doc__, allow_abbrev=False)
    p.add_argument("--config", default=None,
                   help="a JSON file of flag defaults (CLI flags override), "
                        "or a workload preset name (reference --config alias)")
    p.add_argument("--workload", default="mnist_lenet")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch-size", type=int, default=None,
                   help="global batch size (default: workload preset)")
    p.add_argument("--mesh", default=None,
                   help="mesh axes, e.g. 'data=-1' or 'data=2,model=4' "
                        "(default: workload preset = its reference strategy)")
    p.add_argument("--accum-steps", type=int, default=None)
    p.add_argument("--zero", action="store_true",
                   help="cross-replica weight-update sharding (ZeRO stage "
                        "1, arxiv 2004.13336): reduce-scatter gradients, "
                        "shard the optimizer state + update 1/N per "
                        "data-parallel replica, all-gather updated params "
                        "— per-device optimizer-state bytes shrink by the "
                        "replica count; exact for elementwise optimizers "
                        "(sgd/momentum/adam/adamw/adagrad/lion)")
    p.add_argument("--steps-per-call", type=int, default=1,
                   help="optimizer steps bundled into one XLA dispatch"
                        " (Keras steps_per_execution analogue; amortizes"
                        " host dispatch/RTT, hooks fire every k steps)")
    p.add_argument("--quant",
                   choices=("none", "int8", "int8_stochastic", "fp8"),
                   default="none",
                   help="quantized compute (ops/quant.py): run the "
                        "transformer presets' block matmuls as int8 (or "
                        "fp8) with per-channel absmax scales and a "
                        "straight-through-estimator backward (QAT-safe); "
                        "embeddings/layernorms/heads stay high-precision; "
                        "stamps quant_mode into every metric record")
    p.add_argument("--overlap", action="store_true",
                   help="collective-matmul overlap (parallel/overlap.py): "
                        "issue the backward-pass gradient all-reduce "
                        "(reduce-scatter under --zero) in per-layer-group "
                        "buckets as each gradient is produced, so the sync "
                        "hides under the remaining backward matmuls; "
                        "numerically identical to the unbucketed step")
    p.add_argument("--overlap-bucket-mb", type=float, default=4.0,
                   help="greedy merge threshold (MiB of parameter bytes) "
                        "for --overlap's per-layer-group gradient buckets")
    p.add_argument("--log-every", type=int, default=20)
    p.add_argument("--dynamics-every", type=int, default=0,
                   help="training-dynamics telemetry cadence (obs.dynamics): "
                        "every N optimizer steps the train step computes "
                        "per-module grad/param/update statistics in-graph "
                        "(lax.cond-gated — off-cadence steps pay ~nothing), "
                        "flushed at log boundaries into dynamics.jsonl, the "
                        "dynamics_* metric families, and GET /dynamicz; a "
                        "non-finite loss or grad triggers the NaN-provenance "
                        "pass.  0 disables")
    p.add_argument("--eval-every", type=int, default=0)
    p.add_argument("--target-metric", default=None,
                   help="stop when this eval metric reaches --target-value "
                        "(the reference's accuracy-parity gate)")
    p.add_argument("--target-value", type=float, default=None)
    p.add_argument("--target-mode", choices=("max", "min"), default="max",
                   help="'max': stop when metric >= value; 'min': <= (losses)")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=0)
    p.add_argument("--logdir", default=None)
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler trace of a few steps here")
    p.add_argument("--profile-start", type=int, default=10,
                   help="steps into this run before the trace window opens")
    p.add_argument("--profile-steps", type=int, default=5,
                   help="number of steps to trace (also the window length "
                        "of --auto-profile and POST /profilez captures)")
    p.add_argument("--auto-profile", action="store_true",
                   help="reactive profiling: capture a jax.profiler window "
                        "of the next --profile-steps steps the moment the "
                        "anomaly detector flags a step-time regression (or, "
                        "multi-host, the t_step spread blows up); captures "
                        "land in <logdir>/captures/<id>/ with a manifest "
                        "row in <logdir>/captures.jsonl")
    p.add_argument("--max-captures", type=int, default=8,
                   help="per-run budget of reactive/on-demand profiler "
                        "captures (--auto-profile, POST /profilez); the "
                        "static --profile-dir window is exempt")
    p.add_argument("--capture-cooldown", type=float, default=120.0,
                   help="seconds between triggered captures (repeat "
                        "anomalies within the cooldown don't re-capture; "
                        "POST /profilez skips it)")
    p.add_argument("--watchdog-timeout", type=float, default=0.0,
                   help="dump all stacks if no step completes for N seconds")
    p.add_argument("--status-port", type=int, default=None, metavar="PORT",
                   help="start the live introspection HTTP server on this "
                        "port (0 = ephemeral): /healthz /statusz /varz "
                        "/threadz /memz /flightz — curl a wedged run")
    p.add_argument("--status-host", default="127.0.0.1", metavar="ADDR",
                   help="bind address for --status-port; the loopback "
                        "default keeps /threadz stacks private — set "
                        "0.0.0.0 only on a trusted cluster network")
    p.add_argument("--fleet", action="store_true",
                   help="fleet observability plane (obs.fleet): scrape "
                        "the /varz of every registered peer StatusServer "
                        "(this process + the --data-service workers' "
                        "embedded servers) on a background thread, merge "
                        "into a min/median/max/sum view with per-peer "
                        "up/stale/down liveness + spread_ratio straggler "
                        "detection, served at GET /fleetz on "
                        "--status-port and persisted to <logdir>/"
                        "fleet.json (requires --status-port)")
    p.add_argument("--fleet-interval", type=float, default=2.0,
                   help="seconds between fleet /varz scrape rounds")
    p.add_argument("--fleet-peer", action="append", default=None,
                   metavar="NAME=HOST:PORT",
                   help="extra fleet scrape target (repeatable): another "
                        "trainer host's --status-port, a serve.py server, "
                        "a remote data worker's embedded status server")
    p.add_argument("--slo-rules", default=None, metavar="JSON",
                   help="SLO rule file (obs.slo schema): evaluate "
                        "multi-window burn rates over registry histograms"
                        "/gauges on a background thread, expose "
                        "slo_burn_rate{slo=,window=} gauges + GET /sloz, "
                        "raise slo_violation flight events on threshold "
                        "trips, and (with --auto-profile) arm a slo_burn "
                        "reactive capture on a fast-burn trip")
    p.add_argument("--slo-interval", type=float, default=5.0,
                   help="seconds between SLO burn-rate evaluations")
    p.add_argument("--alert-rules", default=None, metavar="JSON",
                   help="alert rule file (obs.alerts schema): evaluate "
                        "threshold/burn/absence/anomaly rules over the "
                        "registry (and the SLO monitor / history store / "
                        "fleet view when present) on a background thread; "
                        "firings append <logdir>/alerts.jsonl, write "
                        "incident evidence bundles under "
                        "<logdir>/incidents/, raise alert flight events, "
                        "and serve GET /alertz + /healthz?deep=1")
    p.add_argument("--alert-interval", type=float, default=5.0,
                   help="seconds between alert rule evaluations")
    p.add_argument("--alert-webhook", default=None, metavar="URL",
                   help="POST every alert transition to this http:// URL "
                        "as JSON (through net.rpc: deadline, retries, "
                        "circuit breaker)")
    p.add_argument("--profiler-port", type=int, default=None, metavar="PORT",
                   help="start the jax.profiler server for on-demand remote "
                        "trace capture (TensorBoard 'capture profile' / "
                        "jax.profiler.trace_remote against this port)")
    p.add_argument("--fault-plan", default=None, metavar="JSON",
                   help="chaos fault plan: inject NaN losses, checkpoint "
                        "truncation, worker kills, data stalls, and "
                        "synthetic preemptions at planned steps "
                        "(resilience.chaos schema); implies supervised "
                        "restarts and writes <logdir>/faults.jsonl")
    p.add_argument("--max-restarts", type=int, default=0,
                   help="supervised self-healing: restart the fit (restore "
                        "from the last VERIFIED checkpoint, exponential "
                        "backoff) up to N times on NaN loss, worker crash, "
                        "data stall, or injected fault before exiting "
                        "non-zero. 0 = die on first failure (unless "
                        "--fault-plan sets a budget)")
    p.add_argument("--restart-backoff", type=float, default=1.0,
                   help="base seconds of the supervised-restart exponential "
                        "backoff (doubles per restart)")
    p.add_argument("--restart-backoff-max", type=float, default=60.0,
                   help="clamp on the supervised-restart backoff")
    p.add_argument("--elastic", action="store_true",
                   help="live replica resize without a cold restart: on "
                        "SIGUSR2 (target device count read from "
                        "<logdir>/resize_devices) or POST /resizez?devices=N "
                        "on --status-port, drain to the next checkpoint "
                        "boundary, re-form the mesh at N devices, rechunk "
                        "ZeRO optimizer state, and resume the SAME "
                        "data-service epoch with exactly-once batch "
                        "continuity. Requires --checkpoint-dir")
    p.add_argument("--flight-recorder", action="store_true",
                   help="record a bounded ring of structured events (step/"
                        "checkpoint/anomaly/preemption/compile markers), "
                        "dumped to <logdir>/flight.jsonl on watchdog "
                        "timeout, crash, anomaly, preemption, and exit")
    p.add_argument("--goodput", action="store_true",
                   help="account every wall-second of the run into exclusive"
                        " goodput buckets (init/compile/train_step/data_wait/"
                        "checkpoint/eval/lost_work/...), persisted to "
                        "<logdir>/goodput.json and MERGED across restarts; "
                        "surfaces goodput_fraction in the registry and "
                        "/goodputz on --status-port")
    p.add_argument("--flops-per-step", type=float, default=0.0,
                   help="per-chip model FLOPs per optimizer step (analytic "
                        "6·N·D-style); enables the mfu fields in "
                        "metrics.jsonl")
    p.add_argument("--estimate-flops", choices=("auto", "on", "off"),
                   default="auto",
                   help="estimate --flops-per-step from XLA's compiled cost "
                        "analysis (one extra AOT compile, absorbed by the "
                        "persistent cache). auto = on for the CPU backend "
                        "only (an extra TPU compile is not free)")
    p.add_argument("--no-trace", action="store_true",
                   help="disable span tracing (trace.jsonl + the per-step "
                        "t_data/t_step breakdown fields)")
    p.add_argument("--no-anomaly-detection", action="store_true",
                   help="disable the streaming anomaly detector (NaN loss, "
                        "loss spikes, step-time regression)")
    p.add_argument("--deterministic", action="store_true",
                   help="pin PRNG partitioning + matmul precision for "
                        "cross-topology reproducibility")
    p.add_argument("--test-size", action="store_true",
                   help="shrink the model (CI / smoke tests)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--device", choices=("tpu", "cpu"), default=None,
                   help="tpu: fail unless JAX's devices are TPU chips; "
                        "cpu: force the CPU backend (tests); default: "
                        "whatever JAX finds, named in the start-up log")
    p.add_argument("--sp-scheme", choices=("ring", "ulysses"), default="ring",
                   help="sequence-parallel attention for gpt_lm on seq meshes")
    p.add_argument("--data-dir", default=None, metavar="DIR",
                   help="train from record files (*.tfrecord/*.rio, written "
                        "by data.write_record_shards) instead of the "
                        "workload's synthetic input; keys must match the "
                        "workload's batch keys")
    p.add_argument("--eval-data-dir", default=None, metavar="DIR",
                   help="record files for eval; defaults to --data-dir "
                        "(use a held-out split for honest numbers)")
    p.add_argument("--autoshard", choices=("AUTO", "FILE", "DATA", "OFF"),
                   default="AUTO", help="per-host input sharding policy for "
                                        "--data-dir (reference AutoShardPolicy)")
    p.add_argument("--shuffle-buffer", type=int, default=4096,
                   help="record shuffle buffer for --data-dir (0 = off)")
    p.add_argument("--data-service", type=int, default=0, metavar="N",
                   help="disaggregated input: spawn a loopback dispatcher "
                        "plus N in-process data workers serving the "
                        "workload input (or --data-dir records, partitioned "
                        "N ways under this host's slice) and consume via "
                        "the streaming DataServiceClient — persistent "
                        "pipelined connections, credit window, elastic "
                        "re-sharding on worker death. 0 = direct host input")
    p.add_argument("--data-service-wire", choices=("raw", "npz"),
                   default="raw",
                   help="data-service batch wire format: 'raw' "
                        "(dtype/shape header + raw tensor bytes, the fast "
                        "path) or 'npz' (legacy per-batch archive)")
    p.add_argument("--data-service-window", type=int, default=0,
                   metavar="W",
                   help="per-split credit window of outstanding pipelined "
                        "get_next requests (0 = adaptive: autotuned from "
                        "consumer waits within --prefetch-budget-mb)")
    p.add_argument("--prefetch-depth", type=int, default=2,
                   help="host->device prefetch buffer depth (batches in "
                        "flight; the Prefetcher's buffer_size)")
    p.add_argument("--adaptive-prefetch", action="store_true",
                   help="autotune the prefetch depth from consumer "
                        "blocking time (grow while the trainer waits on "
                        "data, shrink when waits are ~0), bounded by "
                        "--prefetch-budget-mb; live depth exported as the "
                        "data_prefetch_depth gauge + per-record field")
    p.add_argument("--prefetch-budget-mb", type=float, default=256.0,
                   help="host-bytes budget bounding the adaptive prefetch "
                        "depth and data-service credit window")
    p.add_argument("--pp-virtual", type=int, default=1,
                   help="virtual pipeline chunks per rank (>1 = circular/"
                        "interleaved schedule, smaller bubble)")
    p.add_argument("--pipeline-schedule",
                   choices=("gpipe", "1f1b", "interleaved"),
                   default="gpipe",
                   help="pipeline training schedule on meshes with a pipe "
                        "axis: gpipe (all forwards, then autodiff — "
                        "O(n_micro) live microbatch activations), 1f1b "
                        "(forward/backward interleaved — O(stages) live "
                        "stage inputs), or interleaved (interleaved-1F1B "
                        "over --pp-virtual>=2 chunks per rank — smaller "
                        "bubble, O(stages*virtual) live stage inputs)")
    p.add_argument("--pp-handoff-dtype", choices=("fp32", "bf16"),
                   default="fp32",
                   help="dtype of the inter-stage ppermute PAYLOAD: bf16 "
                        "halves the pipeline's wire (ICI) traffic and is "
                        "bit-exact for bf16 models (requires one); scan "
                        "carries and schedule buffers stay fp32 (fp32 "
                        "cross-stage residual accumulation)")
    p.add_argument("--job", choices=("auto", "train", "evaluator",
                                     "async-ps"),
                   default="auto",
                   help="role of this process: train, sidecar evaluator "
                        "(polls --checkpoint-dir and evaluates new "
                        "checkpoints), or async-ps (host-side stale-"
                        "gradient parameter-server training, reference "
                        "config #5). auto = evaluator iff TF_CONFIG "
                        "task.type == 'evaluator'; a TF_CONFIG cluster "
                        "WITH a 'ps' job routes ps/chief/worker tasks to "
                        "the async-PS tier (legacy PS launcher semantics)")
    p.add_argument("--num-ps", type=int, default=2,
                   help="async-ps: number of parameter-server shards")
    p.add_argument("--num-workers", type=int, default=2,
                   help="async-ps: number of gradient-worker processes")
    p.add_argument("--poll-interval", type=float, default=10.0,
                   help="evaluator: seconds between checkpoint-dir polls")
    p.add_argument("--max-evaluations", type=int, default=None,
                   help="evaluator: stop after N evaluations")
    p.add_argument("--idle-timeout", type=float, default=600.0,
                   help="evaluator: stop after this long with no new "
                        "checkpoint; ps-cluster ps task: exit after this "
                        "long with no gradient push")
    p.add_argument("--seq-len", type=int, default=None,
                   help="LM presets: override sequence length")
    from distributedtensorflow_tpu.train.optimizers import (
        OPTIMIZERS,
        SCHEDULES,
    )

    p.add_argument("--optimizer", default=None, choices=OPTIMIZERS,
                   help="override the preset's optimizer (requires --lr)")
    p.add_argument("--lr", type=float, default=None,
                   help="peak learning rate for --optimizer")
    p.add_argument("--schedule", choices=SCHEDULES, default="constant",
                   help="LR schedule for --optimizer (decay over --steps)")
    p.add_argument("--warmup-steps", type=int, default=0,
                   help="linear LR warmup steps for --optimizer")
    p.add_argument("--decay-mask", choices=("none", "bias-norm"),
                   default="none",
                   help="scope --weight-decay: bias-norm = skip biases and"
                        " norm scales (exclude_from_weight_decay semantics)")
    p.add_argument("--clipnorm", type=float, default=0.0,
                   help="clip gradients by GLOBAL norm before the optimizer"
                        " (Keras global_clipnorm; BERT recipes use 1.0)")
    p.add_argument("--weight-decay", type=float, default=0.0,
                   help="weight decay for --optimizer (adamw/lamb/lars/lion)")
    p.add_argument("--remat", choices=("on", "off", "attn"), default=None,
                   help="LM presets: rematerialization — whole blocks (on),"
                        " none (off), or attention-only (attn: remat-free"
                        " speed at ~2x the batch)")
    p.add_argument("--attn-impl", choices=("auto", "xla", "pallas"),
                   default=None,
                   help="LM presets: attention kernel (auto = Pallas flash"
                        " on TPU past the evidenced seq threshold)")
    p.add_argument("--attn-window", type=int, default=None,
                   help="sliding-window attention for the gpt family "
                        "(token i sees the last N keys; None = full causal; "
                        "flash kernels skip out-of-band blocks, decode masks "
                        "the KV cache identically)")
    p.add_argument("--kv-heads", type=int, default=None,
                   help="GQA: number of K/V heads (gpt family and "
                        "t5_seq2seq; must divide the model's head count; "
                        "shrinks the serving KV cache "
                        "num_heads/kv_heads-fold)")
    p.add_argument("--xent-impl",
                   choices=("auto", "chunked", "chunked_bf16", "fused"),
                   default=None,
                   help="LM presets: head-loss kernel (auto = Pallas"
                        " fused_xent on TPU / chunked elsewhere; chunked ="
                        " lax.scan over token chunks; fused = fused_xent"
                        " unconditionally, logits never leave VMEM)")
    args = p.parse_args()
    if args.config:
        import sys

        if os.path.exists(args.config):
            args = apply_config_file(p, args, sys.argv[1:])
        else:  # reference semantics: --config <preset name>
            args.workload = args.config

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(message)s",
    )
    from distributedtensorflow_tpu import runtime

    if args.device == "cpu":
        jax.config.update("jax_platforms", "cpu")
    runtime.init_compile_cache()
    if args.deterministic:
        from distributedtensorflow_tpu.utils import enable_determinism

        enable_determinism()

    job = args.job
    ps_cluster = None
    if job == "auto":
        # Reference semantics (SURVEY.md §5.6): an "evaluator" task in
        # TF_CONFIG is outside the training cluster and runs the sidecar
        # loop; a cluster WITH a "ps" job is the legacy parameter-server
        # launcher path — ps tasks serve shards, worker/chief tasks run the
        # async pull/push loop.  Clusters without "ps" stay sync SPMD.
        import json as jsonlib

        tf_config = os.environ.get("TF_CONFIG")
        task_type, task_index, cluster = None, 0, {}
        try:
            if tf_config:
                parsed = jsonlib.loads(tf_config)
                cluster = parsed.get("cluster", {}) or {}
                task = parsed.get("task", {}) or {}
                task_type = task.get("type")
                task_index = int(task.get("index", 0))
        except (ValueError, AttributeError, TypeError):
            # Malformed TF_CONFIG: fall through to plain training (the
            # long-standing evaluator-detection behavior) — including NOT
            # routing into the PS tier on a half-parsed cluster.
            task_type, task_index, cluster = None, 0, {}
        if task_type == "evaluator":
            job = "evaluator"
        elif cluster.get("ps"):
            job = "ps-cluster"
            ps_cluster = (cluster, task_type, task_index)
        else:
            job = "train"
    if job == "evaluator":
        run_evaluator(args)
        return
    if job == "async-ps":
        run_async_ps(args)
        return
    if job == "ps-cluster":
        run_ps_cluster_task(args, *ps_cluster)
        return

    from distributedtensorflow_tpu import parallel
    from distributedtensorflow_tpu.data import (
        InputContext,
        Prefetcher,
        current_input_context,
    )
    from distributedtensorflow_tpu.train import (
        create_sharded_state,
        make_eval_step,
        make_train_step,
    )
    from distributedtensorflow_tpu.obs.tracing import (
        PhaseTrace,
        install_compile_log,
    )
    from distributedtensorflow_tpu.train.trainer import Trainer, TrainerConfig
    from distributedtensorflow_tpu.workloads import get_workload

    # Start-up as spans with absolute time (trace_id "startup" in
    # <logdir>/trace.jsonl): each mark names the stretch since the last,
    # and what JAX traces, lowers, compiles or loads inside it is its
    # child; rows wait until the pre-fit recorder below exists.
    startup = PhaseTrace("startup", T_PROCESS_START)
    install_compile_log(startup)
    startup.mark("startup.imports")

    # Goodput ledger FIRST (before mesh/state/restore) so setup time is
    # honestly booked as `init` — the generation starts here.  Re-loads a
    # prior <logdir>/goodput.json so a restarted run keeps one ledger.
    goodput_ledger = None
    if args.goodput:
        from distributedtensorflow_tpu.obs import goodput as goodput_lib

        goodput_ledger = goodput_lib.GoodputLedger(
            os.path.join(args.logdir, "goodput.json")
            if args.logdir else None
        ).install()

    cluster = parallel.initialize()
    # One line naming what the run is on (chip_smoke.py reads it); with
    # --device tpu anything else stops here instead of training on it.
    device = (runtime.require_tpu() if args.device == "tpu"
              else runtime.device_summary())
    logging.info("device: %s", json.dumps(device))
    startup.mark("startup.backend")
    if args.profiler_port is not None:
        from distributedtensorflow_tpu.utils import profiler

        # Held for the process lifetime; a TensorBoard "capture profile"
        # request (or jax.profiler.trace_remote) pulls traces on demand.
        _profiler_server = profiler.start_server(args.profiler_port)  # noqa: F841
    wl = get_workload(
        args.workload, test_size=args.test_size,
        global_batch_size=args.batch_size, sp_scheme=args.sp_scheme,
        pp_virtual=args.pp_virtual,
        pp_handoff=_PP_HANDOFF[args.pp_handoff_dtype],
        pp_schedule=args.pipeline_schedule,
        seq_len=args.seq_len,
        remat=REMAT_FLAG[args.remat],
        attn_impl=args.attn_impl,
        xent_impl=args.xent_impl,
        kv_heads=args.kv_heads,
        attn_window=args.attn_window,
        quant=None if args.quant == "none" else args.quant,
    )
    wl = apply_optimizer_flags(wl, args)
    spec = parse_mesh(args.mesh) or wl.mesh_spec
    mesh = parallel.build_mesh(spec)
    if args.elastic and not args.checkpoint_dir:
        raise SystemExit(
            "--elastic requires --checkpoint-dir (the resize drains to a "
            "checkpoint boundary and restores through the verified-"
            "manifest path at the new device count)"
        )
    # Keep the mesh-unbound workload: an elastic resize re-binds it
    # against the re-formed mesh (for_mesh may specialise per-mesh).
    base_wl = wl
    wl = wl.for_mesh(mesh)  # e.g. gpt_lm binds seq-parallel attention
    from distributedtensorflow_tpu.parallel.mesh import replica_count

    shard_div = replica_count(mesh)
    if wl.global_batch_size % shard_div:
        raise SystemExit(
            f"global batch {wl.global_batch_size} is not divisible by the "
            f"mesh's batch-sharding factor {shard_div} (data x fsdp axes); "
            f"pick --batch-size as a multiple of {shard_div}"
        )
    accum = args.accum_steps if args.accum_steps is not None else wl.accum_steps
    logging.info(
        "workload=%s mesh=%s devices=%d processes=%d global_batch=%d accum=%d",
        wl.name, dict(mesh.shape), mesh.size, jax.process_count(),
        wl.global_batch_size, accum,
    )

    rng = jax.random.PRNGKey(args.seed)
    # --zero: cross-replica weight-update sharding (parallel/zero.py).
    # ONE sharder instance for the same treedef-identity reason as the
    # optimizer: the supervised-restart template must chunk identically.
    zero_sharder = None
    if args.zero:
        if shard_div <= 1:
            logging.warning(
                "--zero: mesh %s has a single data-parallel replica; "
                "nothing to shard the weight update over — running "
                "replicated", dict(mesh.shape),
            )
        else:
            from distributedtensorflow_tpu.parallel.zero import ZeroSharder
            from distributedtensorflow_tpu.train.optimizers import ZERO_SAFE

            if args.optimizer and args.optimizer not in ZERO_SAFE:
                logging.warning(
                    "--zero with --optimizer %s: its update is not "
                    "elementwise (per-shard norms/factored stats), so the "
                    "trajectory will deviate from replicated data "
                    "parallelism; elementwise optimizers (%s) are exact",
                    args.optimizer, ", ".join(ZERO_SAFE),
                )
            zero_sharder = ZeroSharder(mesh)
            logging.info(
                "zero: sharding optimizer state + weight update %d-way "
                "over axes %s", zero_sharder.degree, zero_sharder.axes,
            )
    # ONE optimizer instance: a supervised restart rebuilds the state
    # template, and a fresh make_optimizer() would carry new optax
    # function identities in the TrainState treedef — a pytree-metadata
    # mismatch against the already-compiled step's in_shardings.
    if zero_sharder is not None and args.decay_mask == "bias-norm":
        optimizer = wl.make_optimizer(_concrete_decay_mask(wl, rng))
    else:
        optimizer = wl.make_optimizer()
    startup.mark("startup.workload", workload=wl.name)
    state, specs = create_sharded_state(
        wl.init_fn, optimizer, mesh, rng,
        rules=wl.layout, fsdp=wl.fsdp, zero=zero_sharder,
    )
    # Collective-matmul overlap: bucket the backward-pass gradient sync
    # per layer group so it hides under the remaining backward matmuls.
    overlap_plan = None
    if args.overlap:
        if shard_div <= 1:
            logging.warning(
                "--overlap: mesh %s has a single data-parallel replica; "
                "there is no gradient collective to overlap — running "
                "without bucketing", dict(mesh.shape),
            )
        else:
            from distributedtensorflow_tpu.parallel.overlap import (
                OverlapPlan,
            )
            from distributedtensorflow_tpu.train.state import (
                split_variables,
            )

            param_shapes, _ = split_variables(
                jax.eval_shape(wl.init_fn, rng)
            )
            overlap_plan = OverlapPlan.build(
                mesh, param_shapes, specs.params, zero=zero_sharder,
                bucket_bytes=int(args.overlap_bucket_mb * 2 ** 20),
            )
            logging.info(
                "overlap: %d gradient bucket(s), mode=%s, coverage=%.0f%%",
                len(overlap_plan.buckets),
                overlap_plan.describe()["mode"],
                100 * overlap_plan.coverage,
            )
    if args.steps_per_call > 1:
        from distributedtensorflow_tpu.train import make_multi_train_step

        train_step = make_multi_train_step(
            wl.loss_fn, mesh, specs,
            steps_per_call=args.steps_per_call, accum_steps=accum,
            overlap=overlap_plan, dynamics_every=args.dynamics_every,
        )
    else:
        train_step = make_train_step(
            wl.loss_fn, mesh, specs, accum_steps=accum,
            overlap=overlap_plan, dynamics_every=args.dynamics_every,
        )
    eval_step = (
        make_eval_step(wl.eval_fn, mesh, specs) if wl.eval_fn else None
    )
    flops_per_step = args.flops_per_step
    if not flops_per_step and (
        args.estimate_flops == "on"
        or (args.estimate_flops == "auto"
            and jax.default_backend() == "cpu")
    ):
        from distributedtensorflow_tpu.train import estimate_step_flops

        lead = (args.steps_per_call,) if args.steps_per_call > 1 else ()
        batch_sds = {
            k: jax.ShapeDtypeStruct(
                lead + (wl.global_batch_size,) + np.shape(v)[1:],
                np.asarray(v).dtype,
            )
            for k, v in wl.init_batch.items()
        }
        flops_per_step = estimate_step_flops(
            train_step, state, batch_sds, jax.random.PRNGKey(args.seed)
        ) or 0.0
        if flops_per_step:
            logging.info(
                "mfu: XLA cost analysis estimates %.3g FLOPs/step",
                flops_per_step,
            )
    if args.target_metric:  # the gate must be able to fire (fail at setup)
        if args.target_value is None:
            raise SystemExit("--target-metric requires --target-value")
        if not args.eval_every:
            raise SystemExit("--target-metric requires --eval-every > 0")
        if eval_step is None:
            raise SystemExit(
                f"workload {wl.name!r} has no eval_fn; --target-metric "
                "cannot fire"
            )

    jax.block_until_ready(state)  # init is asynchronous: charge it here
    startup.mark("startup.state_init")
    startup.open("startup.trainer")
    ctx = current_input_context(wl.global_batch_size)

    # Disaggregated input (--data-service N): a loopback dispatcher + N
    # in-process data workers each serving full per-host batches; the
    # trainer consumes through the streaming DataServiceClient (pipelined
    # credit window, raw tensor wire, elastic re-sharding).  In-process
    # loopback is the CPU-verifiable topology; a real pod points the
    # client at a remote dispatcher and runs WorkerServer on input hosts.
    data_service = None
    _workers: list = []
    if args.data_service:
        from distributedtensorflow_tpu.data import DispatchServer, WorkerServer

        def _worker_input_fn(split, num_shards):
            if args.data_dir:
                from distributedtensorflow_tpu.data import (
                    repeated_record_dataset,
                )

                files = record_files(args.data_dir)
                # Partition the files/records num_shards ways UNDER this
                # host's slice: worker `split` of this host behaves as
                # input pipeline (host_id * N + split) of (hosts * N).
                wctx = InputContext(
                    num_input_pipelines=ctx.num_input_pipelines * num_shards,
                    input_pipeline_id=(
                        ctx.input_pipeline_id * num_shards + split
                    ),
                    global_batch_size=wl.global_batch_size * num_shards,
                )
                return repeated_record_dataset(
                    files, wctx, batch_size=ctx.per_host_batch_size,
                    policy=args.autoshard,
                    shuffle_buffer=args.shuffle_buffer,
                    seed=args.seed + split,
                )
            # Synthetic sources: each worker generates a distinct
            # deterministic stream (seed offset by split) of full
            # per-host batches.
            return wl.input_fn(ctx, args.seed + 1009 * (split + 1))

        # Durable dispatcher state: with a logdir, every control-plane
        # mutation (worker registration, epoch start, reshard, client
        # progress) is journaled and replayed on restart — a dispatcher
        # crash mid-epoch no longer orphans the fetchers.
        _ds_journal = (
            os.path.join(args.logdir, "dispatcher.journal")
            if args.logdir else None
        )
        _dispatch = DispatchServer(port=0, journal_path=_ds_journal)
        _workers = [
            WorkerServer(
                _dispatch.target(), _worker_input_fn, port=0,
                # Under --fleet every worker embeds an ephemeral loopback
                # StatusServer and registers as a scrape target, so worker
                # health stops being inferable only from client-side
                # fetch histograms.
                status_port=0 if args.fleet else None,
            )
            for _ in range(args.data_service)
        ]
        data_service = _dispatch
        logging.info("data service: dispatcher %s + %d loopback worker(s), "
                     "wire=%s", _dispatch.target(), len(_workers),
                     args.data_service_wire)

    # Cross-process trace spans are emitted through the ACTIVE recorder,
    # but the Trainer's own TraceRecorder only exists per fit — and the
    # DataServiceClient's epoch-start handshake (client/dispatcher/worker
    # spans) happens at iterator construction, BEFORE fit.  A pre-fit
    # recorder on the same trace.jsonl (append mode) catches those; the
    # Trainer's recorder takes over for the fit itself.
    _prefit_tracer = None
    if args.logdir and not args.no_trace:
        from distributedtensorflow_tpu.obs.tracing import TraceRecorder

        _prefit_tracer = TraceRecorder(
            os.path.join(args.logdir, "trace.jsonl")
        ).install()

    # Each (re)start consumes a FRESH service epoch so worker iterators
    # restart from batch 0 and the resume fast-forward lands correctly.
    # An elastic resize is the exception: it resumes the SAME epoch, and
    # the dispatcher's journaled per-split consumed counts (not a batch
    # skip) position the successor client — exactly-once across the
    # resize.  _live_iter tracks the current Prefetcher so the resize
    # can close it deterministically (close flushes the consumed ledger
    # to the dispatcher BEFORE the successor seeds from it).
    _ds_epoch = [0]
    _elastic_resume = [False]
    _live_iter: list = [None]

    def make_raw_iter():
        if data_service is not None:
            from distributedtensorflow_tpu.data import DataServiceClient

            if _elastic_resume[0]:
                _elastic_resume[0] = False
                epoch = _ds_epoch[0] - 1  # SAME epoch: journal-seeded
            else:
                epoch = _ds_epoch[0]
                _ds_epoch[0] += 1
            return DataServiceClient(
                data_service.target(),
                epoch=epoch,
                wire=args.data_service_wire,
                window=args.data_service_window or 2,
                adaptive_window=args.data_service_window == 0,
                bytes_budget=int(args.prefetch_budget_mb * 2**20),
            )
        if args.data_dir:
            from distributedtensorflow_tpu.data import repeated_record_dataset

            files = record_files(args.data_dir)
            logging.info("reading %d record files (%s sharding)",
                         len(files), args.autoshard)
            return repeated_record_dataset(
                files, ctx, batch_size=ctx.per_host_batch_size,
                policy=args.autoshard, shuffle_buffer=args.shuffle_buffer,
                seed=args.seed,
                on_epoch=lambda e: logging.info("input epoch %d complete", e),
            )
        return wl.input_fn(ctx, args.seed)

    def make_train_iter(start_step: int):
        """Fresh train iterator positioned after ``start_step`` consumed
        batches — called once per (re)start, so a supervised restart
        resumes the input at the restored step (tf.data iterator-
        checkpoint semantics).  steps_per_call: the Prefetcher stacks k
        host batches into one (k, B, ...) bundle per dispatch (host-side,
        BEFORE placement — the only ordering that works multi-host) and
        buffers 2 bundles so the transfer overlaps compute."""
        # Elastic same-epoch resume: the dispatcher journal supplies the
        # per-split position, so a step-count skip would double-skip.
        same_epoch = _elastic_resume[0] and data_service is not None
        raw_iter = make_raw_iter()
        if start_step > 0 and not same_epoch:
            from distributedtensorflow_tpu.data import skip_batches

            logging.info("fast-forwarding input %d batches", start_step)
            raw_iter = skip_batches(iter(raw_iter), start_step)
        it = Prefetcher(
            raw_iter, mesh, buffer_size=args.prefetch_depth,
            bundle=args.steps_per_call,
            adaptive=args.adaptive_prefetch,
            bytes_budget=int(args.prefetch_budget_mb * 2**20),
        )
        _live_iter[0] = it
        return it

    # Chaos fault injection (resilience tentpole): a --fault-plan run
    # exercises the whole recovery stack — NaN restarts, checkpoint
    # fallback, preemption resume — deterministically, on CPU in CI.
    chaos = None
    if args.fault_plan:
        from distributedtensorflow_tpu.resilience import (
            ChaosInjector,
            FaultPlan,
        )

        chaos = ChaosInjector(FaultPlan.load(args.fault_plan),
                              logdir=args.logdir)
        logging.warning(
            "chaos: %d fault(s) planned from %s; faults.jsonl in %s",
            len(chaos.plan), args.fault_plan, args.logdir,
        )
        if data_service is not None:
            # dispatcher_kill faults: kill the live dispatcher, restart
            # it on the SAME port from the journal, and probe the
            # endpoint breaker through a full open->half_open->closed
            # cycle.
            _ds_port = data_service.port
            chaos.attach_data_service(
                data_service,
                lambda: DispatchServer(port=_ds_port,
                                       journal_path=_ds_journal),
            )

    checkpointer = None
    preemption = None
    if args.checkpoint_dir:
        from distributedtensorflow_tpu.checkpoint import (
            CheckpointManager,
            PreemptionHandler,
        )

        checkpointer = CheckpointManager(args.checkpoint_dir)
        if chaos is not None:
            # The truncation fault tears the bytes at the storage layer,
            # exactly where the real fault lives.
            checkpointer = chaos.wrap_checkpointer(checkpointer)
        # SIGTERM (GCE/Borg preemption notice) -> cluster-consistent save
        # at the next step boundary, then a clean stop; the launcher's
        # restart resumes from that exact step + input position.
        preemption = PreemptionHandler(checkpointer, mesh=mesh)
        if chaos is not None:
            chaos.attach_preemption(preemption)
        # The ZeRO-aware restore handles a checkpoint saved at a DIFFERENT
        # weight-update-sharding degree (or none) by rechunking the
        # verified optimizer state; matching layouts take the manager's
        # own fast path unchanged.
        from distributedtensorflow_tpu.parallel.zero import (
            restore_latest_zero,
        )

        state = restore_latest_zero(
            checkpointer, state, mesh, zero_sharder
        ) or state
    restored_step = int(state.step)
    train_iter = None  # supervised runs build theirs via make_train_iter
    if chaos is not None:
        train_step = chaos.wrap_train_step(train_step)
    dynamics_monitor = None
    if args.dynamics_every > 0:
        from distributedtensorflow_tpu.models import make_nan_taps
        from distributedtensorflow_tpu.obs.dynamics import DynamicsMonitor

        dynamics_monitor = DynamicsMonitor(
            args.dynamics_every,
            logdir=args.logdir,
            loss_fn=wl.loss_fn,
            tap_fn=make_nan_taps(wl.model),
            log_every=args.log_every,
            steps_per_call=args.steps_per_call,
        )
        # OUTSIDE the chaos wrapper: the provenance pass must probe the
        # post-injection state the optimizer actually consumed, not the
        # clean state chaos was about to poison.
        train_step = dynamics_monitor.wrap_train_step(train_step)
        logging.info(
            "dynamics: in-graph module telemetry every %d step(s) -> "
            "%s/dynamics.jsonl", args.dynamics_every, args.logdir,
        )

    # Elastic resize controller: a Callback that, on a resize request,
    # drains the fit to the checkpoint boundary (stop_training) and hands
    # the mesh re-formation to _perform_resize below (bound after the
    # closures it needs exist).
    elastic = None
    if args.elastic:
        from distributedtensorflow_tpu.resilience import ElasticController

        elastic = ElasticController(
            current_devices_fn=lambda: mesh.size,
            logdir=args.logdir,
        )

    # the input-plane services, the fault plan, the checkpoint manager and
    # the restore, the monitors: 8 ms on the chip where none is asked for
    startup.mark("startup.trainer.services", parent="startup.trainer")
    # Times nothing and reads ~0: `import tensorflow` stood here, 14.3 of
    # startup.trainer's 14.4 s on the chip, until the metric writer wrote
    # its event files itself (PERF.md §6, PR 51).  The mark stays because
    # benchmark/layer_metrics/setup_trainer_tensorflow_import_s.json reads
    # it; it goes with that file (ROADMAP S0 g).
    startup.mark("startup.trainer.tensorflow_import",
                 parent="startup.trainer")
    trainer = Trainer(
        train_step,
        TrainerConfig(
            total_steps=args.steps,
            log_every=args.log_every,
            eval_every=args.eval_every,
            # an explicit held-out record split is evaluated exactly (one
            # full pass); eval on the training files stays bounded so large
            # datasets don't pay a full re-read every eval_every steps
            eval_steps=0 if args.eval_data_dir else 10,
            checkpoint_every=args.checkpoint_every,
            steps_per_call=args.steps_per_call,
            dynamics_every=args.dynamics_every,
            input_prebundled=args.steps_per_call > 1,
            zero_stage=1 if zero_sharder is not None else 0,
            quant=args.quant,
            **(
                dict(
                    pipeline_schedule=wl.model.schedule,
                    pipeline_stages=wl.model.n_stages,
                    pipeline_microbatches=wl.model.n_microbatches,
                    pipeline_virtual=wl.model.n_virtual,
                    pipeline_bubble=wl.model.bubble_fraction(),
                )
                if _is_pipelined(wl) else {}
            ),
            overlap_buckets=(
                len(overlap_plan.buckets) if overlap_plan is not None else 0
            ),
            overlap_coverage=(
                overlap_plan.coverage if overlap_plan is not None else 0.0
            ),
            global_batch_size=wl.global_batch_size,
            logdir=args.logdir,
            profile_dir=args.profile_dir,
            profile_start=args.profile_start,
            profile_steps=args.profile_steps,
            auto_profile=args.auto_profile,
            max_captures=args.max_captures,
            capture_cooldown_s=args.capture_cooldown,
            watchdog_timeout=args.watchdog_timeout,
            target_metric=args.target_metric,
            target_value=args.target_value,
            target_mode=args.target_mode,
            trace=not args.no_trace,
            flops_per_step=flops_per_step,
            anomaly_detection=not args.no_anomaly_detection,
            status_port=args.status_port,
            status_host=args.status_host,
            flight_recorder=args.flight_recorder,
        ),
        eval_step=eval_step,
        checkpointer=checkpointer,
        preemption=preemption,
        # The injector is a Callback: its on_step_end fires the
        # worker-kill / data-stall / preemption triggers.  The dynamics
        # monitor rides the same protocol (books cadence rows, flushes
        # at log boundaries, runs NaN provenance on anomalies).  Chaos
        # rides BEFORE elastic so a chaos-planned resize request drains
        # at the very dispatch that fired it.
        callbacks=[cb for cb in (chaos, dynamics_monitor, elastic)
                   if cb is not None] or None,
    )
    layout = {**_flash_layout(wl, mesh), **_optimizer_update(state.params)}
    # the trainer with its metric writer, status server and flight
    # recorder, and what the step will lower to
    startup.mark("startup.trainer.construct", parent="startup.trainer")
    startup.close("startup.trainer", **layout)
    if dynamics_monitor is not None and trainer.status_server is not None:
        dynamics_monitor.install(trainer.status_server)
    if elastic is not None:
        elastic.install_signal_handler()
        if trainer.status_server is not None:
            trainer.status_server.routes.update(elastic.routes())
        if chaos is not None:
            chaos.attach_elastic(elastic)

    # Fleet observability plane (ISSUE 11): the chief scrapes every peer
    # StatusServer — itself, the data-service workers' embedded servers,
    # and any --fleet-peer extras — into one /fleetz view; the SLO monitor
    # watches registry metrics for burn-rate breaches next to it.
    fleet_agg = None
    slo_monitor = None
    if args.fleet:
        if trainer.status_server is None:
            raise SystemExit(
                "--fleet requires --status-port (the aggregator serves "
                "/fleetz on the chief's StatusServer and scrapes its "
                "/varz as the chief peer)"
            )
        from distributedtensorflow_tpu.obs.fleet import FleetAggregator

        fleet_agg = FleetAggregator(
            interval_s=args.fleet_interval, logdir=args.logdir
        )
        # Scrape the chief on the interface it actually bound (loopback
        # only when it bound the wildcard or the default).
        chief_host = ("127.0.0.1"
                      if args.status_host in ("0.0.0.0", "")
                      else args.status_host)
        fleet_agg.add_peer(
            "chief", f"{chief_host}:{trainer.status_server.port}"
        )
        for i, w in enumerate(_workers):
            if w.status_addr is not None:
                fleet_agg.add_peer(f"data_worker{i}", w.status_addr)
        for spec_str in args.fleet_peer or []:
            name, sep, addr = spec_str.partition("=")
            if not sep or not name or not addr:
                raise SystemExit(
                    f"--fleet-peer {spec_str!r}: expected NAME=HOST:PORT"
                )
            fleet_agg.add_peer(name, addr)
        fleet_agg.install(trainer.status_server).start()
        logging.info(
            "fleet: aggregating %d peer(s) every %.1fs (GET /fleetz on "
            "port %d)", len(fleet_agg.peers()), args.fleet_interval,
            trainer.status_server.port,
        )
    if args.slo_rules:
        import json as jsonlib2

        from distributedtensorflow_tpu.obs.slo import SLOMonitor, load_rules

        try:
            slo_rules = load_rules(args.slo_rules)
        except (OSError, ValueError, jsonlib2.JSONDecodeError) as e:
            raise SystemExit(f"--slo-rules {args.slo_rules}: {e}")
        slo_monitor = SLOMonitor(
            slo_rules,
            interval_s=args.slo_interval,
            # --auto-profile: a fast-burn trip arms a slo_burn capture so
            # the breach profiles itself.
            capture_engine=trainer.capture if args.auto_profile else None,
        )
        if trainer.status_server is not None:
            slo_monitor.install(trainer.status_server)
        slo_monitor.start()
        logging.info("slo monitor: %d rule(s) from %s evaluated every "
                     "%.1fs", len(slo_rules), args.slo_rules,
                     args.slo_interval)
    metrics_history = None
    if fleet_agg is not None:
        from distributedtensorflow_tpu.obs.tsdb import MetricsHistory

        # Embedded history store over the fleet plane: the chief keeps a
        # windowed, fixed-memory history of its own registry AND the
        # fleet-merged per-key median/max (plus SLO good/total snapshots
        # when rules are loaded), served at GET /histz and persisted to
        # <logdir>/history.jsonl for offline burn recomputation.
        metrics_history = MetricsHistory(
            interval_s=args.fleet_interval,
            logdir=args.logdir,
            rules=slo_monitor.rules if slo_monitor is not None else None,
            fleet=fleet_agg,
        ).install(trainer.status_server).start()
        logging.info("metrics history: fleet-merged sampling every %.1fs "
                     "(GET /histz)", args.fleet_interval)
        if dynamics_monitor is not None:
            # Late attach: the monitor pins every dynamics_* series at its
            # first flush so the cap never evicts the divergence signal.
            dynamics_monitor.attach_history(metrics_history)
    alert_manager = None
    if args.alert_rules:
        import json as jsonlib3

        from distributedtensorflow_tpu.obs import alerts as alertslib

        try:
            alert_rules = alertslib.load_rules(args.alert_rules)
        except (OSError, ValueError, jsonlib3.JSONDecodeError) as e:
            raise SystemExit(f"--alert-rules {args.alert_rules}: {e}")
        sinks = [alertslib.log_sink]
        if args.alert_webhook:
            sinks.append(alertslib.make_webhook_sink(args.alert_webhook))
        alert_manager = alertslib.AlertManager(
            alert_rules,
            interval_s=args.alert_interval,
            logdir=args.logdir,
            history=metrics_history,
            fleet=fleet_agg,
            slo_monitor=slo_monitor,
            capture_engine=trainer.capture if args.auto_profile else None,
            sinks=sinks,
        )
        if trainer.status_server is not None:
            alert_manager.install(trainer.status_server)
            # /healthz?deep=1 — the shallow watchdog verdict is already in
            # the base health; deep adds the alerting/SLO/fleet planes.
            components = {"alerts": alert_manager.health_component}
            if slo_monitor is not None:
                components["slo"] = alertslib.slo_health_component(
                    slo_monitor)
            if fleet_agg is not None:
                components["fleet"] = alertslib.fleet_health_component(
                    fleet_agg)
            trainer.status_server.deep_health_fn = \
                alertslib.compose_deep_health(components)
        alert_manager.start()
        logging.info(
            "alerts: %d rule(s) from %s evaluated every %.1fs%s",
            len(alert_rules), args.alert_rules, args.alert_interval,
            f" (webhook {args.alert_webhook})" if args.alert_webhook
            else "",
        )

    eval_iter_fn = None
    if args.eval_every and eval_step is not None:
        if args.data_dir or args.eval_data_dir:
            from distributedtensorflow_tpu.data import record_dataset

            eval_files = record_files(args.eval_data_dir or args.data_dir)

            # one finite unshuffled pass
            eval_iter_fn = lambda: Prefetcher(
                shardable_batches(record_dataset(
                    eval_files, ctx, batch_size=ctx.per_host_batch_size,
                    policy=args.autoshard, shuffle_buffer=0,
                    drop_remainder=False,
                ), mesh),
                mesh,
            )
            if not args.eval_data_dir:
                logging.warning(
                    "no --eval-data-dir: eval reads the TRAINING files "
                    "(bounded to eval_steps batches; pass a held-out split "
                    "for a dataset-wide exact eval)"
                )
        else:
            eval_iter_fn = lambda: Prefetcher(
                wl.input_fn(ctx, args.seed + 999), mesh
            )

    def _perform_resize(n: int, cur_state):
        """Re-form the run at ``n`` devices, in-process (elastic tentpole).

        Runs BETWEEN fits: the drained state is already checkpointed
        (Trainer's post-loop force-save).  Everything is staged against
        fresh locals and committed only at the very end, so a failure
        anywhere leaves the pre-resize bindings intact for the
        supervisor's fallback restart."""
        nonlocal mesh, wl, specs, zero_sharder, shard_div
        nonlocal overlap_plan, train_step, eval_step
        # 1) Close the live input iterator FIRST: Prefetcher.close()
        #    closes the DataServiceClient underneath, which synchronously
        #    flushes its CONSUMED-batch ledger to the dispatcher journal —
        #    the successor client seeds its position from exactly that,
        #    so buffered-but-untrained batches get re-served (no loss)
        #    and trained ones never repeat (no duplicates).
        it, _live_iter[0] = _live_iter[0], None
        if it is not None:
            try:
                it.close()
            except Exception:
                logging.exception("resize: closing the old input iterator")
        avail = len(jax.devices())
        if not 0 < n <= avail:
            raise ValueError(
                f"resize to {n} devices: {avail} visible on this host"
            )
        # 2) Re-form the mesh from the SAME spec over a device prefix;
        #    re-bind the mesh-unbound workload against it.
        new_mesh = parallel.build_mesh(spec, jax.devices()[:n])
        new_wl = base_wl.for_mesh(new_mesh)
        new_div = replica_count(new_mesh)
        if new_wl.global_batch_size % new_div:
            raise ValueError(
                f"resize to {n} devices: global batch "
                f"{new_wl.global_batch_size} is not divisible by the new "
                f"batch-sharding factor {new_div}"
            )
        new_zero = None
        if args.zero and new_div > 1:
            from distributedtensorflow_tpu.parallel.zero import ZeroSharder

            new_zero = ZeroSharder(new_mesh)
        # 3) Fresh sharded template at the new layout (same optimizer
        #    INSTANCE — treedef identity), then the cross-degree restore:
        #    restore_latest_zero rechunks the verified optimizer state
        #    from the pre-resize ZeRO degree to the new one.
        new_state, new_specs = create_sharded_state(
            new_wl.init_fn, optimizer, new_mesh,
            jax.random.PRNGKey(args.seed),
            rules=new_wl.layout, fsdp=new_wl.fsdp, zero=new_zero,
        )
        from distributedtensorflow_tpu.parallel.zero import (
            restore_latest_zero as _restore_z,
        )

        restored = _restore_z(checkpointer, new_state, new_mesh, new_zero)
        if restored is None:
            raise RuntimeError(
                "resize: no usable checkpoint to restore at the new "
                "device count (drain save missing or corrupt)"
            )
        if chaos is not None:
            # A composed mid-resize worker_kill fires HERE — after the
            # rechunk, before the commit — so the supervisor's fallback
            # must recover to the PRE-resize bindings.
            chaos.mid_resize_fault()
        new_overlap = None
        if args.overlap and new_div > 1:
            from distributedtensorflow_tpu.parallel.overlap import (
                OverlapPlan,
            )
            from distributedtensorflow_tpu.train.state import (
                split_variables,
            )

            param_shapes, _ = split_variables(
                jax.eval_shape(new_wl.init_fn, jax.random.PRNGKey(args.seed))
            )
            new_overlap = OverlapPlan.build(
                new_mesh, param_shapes, new_specs.params, zero=new_zero,
                bucket_bytes=int(args.overlap_bucket_mb * 2 ** 20),
            )
        if args.steps_per_call > 1:
            from distributedtensorflow_tpu.train import make_multi_train_step

            new_step = make_multi_train_step(
                new_wl.loss_fn, new_mesh, new_specs,
                steps_per_call=args.steps_per_call, accum_steps=accum,
                overlap=new_overlap, dynamics_every=args.dynamics_every,
            )
        else:
            new_step = make_train_step(
                new_wl.loss_fn, new_mesh, new_specs, accum_steps=accum,
                overlap=new_overlap, dynamics_every=args.dynamics_every,
            )
        if chaos is not None:
            new_step = chaos.wrap_train_step(new_step)
        if dynamics_monitor is not None:
            new_step = dynamics_monitor.wrap_train_step(new_step)
        new_eval = (
            make_eval_step(new_wl.eval_fn, new_mesh, new_specs)
            if new_wl.eval_fn else None
        )
        # 4) COMMIT — from here on the run IS at the new device count.
        mesh, wl, specs = new_mesh, new_wl, new_specs
        zero_sharder, shard_div, overlap_plan = new_zero, new_div, new_overlap
        train_step, eval_step = new_step, new_eval
        trainer.train_step = new_step
        trainer.eval_step = new_eval
        if preemption is not None:
            preemption._mesh = new_mesh
        if data_service is not None:
            _elastic_resume[0] = True  # next iterator: SAME epoch, no skip
        logging.warning(
            "elastic: resized to %d device(s) (batch-sharding %d-way, "
            "zero=%s) at step %d", n, new_div,
            new_zero.degree if new_zero is not None else 0,
            int(cur_state.step),
        )
        return restored

    if elastic is not None:
        elastic.resize_fn = _perform_resize

    supervise = chaos is not None or args.max_restarts > 0
    try:
        with trainer:  # closes the metric writer on every exit path
            if not supervise:
                train_iter = make_train_iter(restored_step)
            # the monitors behind the trainer and the input iterator.
            # The trainer ends start-up: it closes startup.first_step
            # after its first step.
            startup.mark("startup.data")
            startup.open("startup.first_step")
            trainer.startup_trace = startup
            if supervise:
                from distributedtensorflow_tpu.resilience import (
                    RestartBudgetExhausted,
                    Supervisor,
                    SupervisorConfig,
                )

                def state_template_fn():
                    # The state fed to a failed fit was DONATED to the
                    # device; restores need a pristine sharded template
                    # (same optimizer INSTANCE — see the note at the
                    # original create_sharded_state call).
                    template, _ = create_sharded_state(
                        wl.init_fn, optimizer, mesh,
                        jax.random.PRNGKey(args.seed),
                        rules=wl.layout, fsdp=wl.fsdp, zero=zero_sharder,
                    )
                    return template

                budget = args.max_restarts
                if budget <= 0:  # a fault plan implies a restart budget
                    budget = len(chaos.plan) + 2
                supervisor = Supervisor(
                    trainer,
                    make_train_iter=make_train_iter,
                    state_template_fn=state_template_fn,
                    eval_iter_fn=eval_iter_fn,
                    config=SupervisorConfig(
                        max_restarts=budget,
                        backoff_base_s=args.restart_backoff,
                        backoff_max_s=args.restart_backoff_max,
                    ),
                    chaos=chaos,
                    elastic=elastic,
                )
                try:
                    state = supervisor.run(state, rng)
                except RestartBudgetExhausted as e:
                    # The escalation contract: a clean non-zero exit the
                    # job scheduler can act on, with the failure history
                    # in the log (and in flight.jsonl / faults.jsonl).
                    logging.error(
                        "supervisor gave up: %s; failures: %s",
                        e, e.failures,
                    )
                    if goodput_ledger is not None:
                        goodput_ledger.close(ended="failed")
                    raise SystemExit(3) from e
                if chaos is not None and chaos.unrecovered():
                    logging.error(
                        "chaos: run finished with UNRECOVERED faults: %s",
                        chaos.unrecovered(),
                    )
                    if goodput_ledger is not None:
                        # The run DID end (at its target step, even) —
                        # close the generation so the ledger doesn't later
                        # merge it as died-mid-flight.
                        goodput_ledger.close(ended="failed")
                    raise SystemExit(4)
            else:
                while True:
                    state = trainer.fit(
                        state, train_iter, rng, eval_iter_fn=eval_iter_fn
                    )
                    # An elastic drain ends the fit early (stop_training
                    # after the boundary save); perform the resize and
                    # re-enter at the restored step, same process.
                    if elastic is not None and elastic.should_perform(
                        int(state.step), args.steps
                    ):
                        state = elastic.perform(state)
                        train_iter = make_train_iter(int(state.step))
                        continue
                    break
    except SystemExit:
        raise
    except BaseException:
        if goodput_ledger is not None:
            # Crash path: stamp the last heartbeat but leave the generation
            # open — the restart's merge treats it as died-mid-flight.
            goodput_ledger.heartbeat()
        raise
    finally:
        # One last evaluation/scrape, then re-export the registry
        # snapshot: the trainer's own metrics.prom export ran at the last
        # log boundary, BEFORE these final gauge updates — without the
        # rewrite a run shorter than --slo-interval would end with no
        # slo_burn_rate samples on disk at all.
        if alert_manager is not None:
            # Before the SLO monitor: stop() runs one final evaluation so
            # resolve rows land, and burn rules read the monitor's state.
            alert_manager.stop()
        if slo_monitor is not None:
            slo_monitor.stop()
            try:
                slo_monitor.evaluate()
            except Exception:
                logging.exception("final slo evaluation failed")
        if metrics_history is not None:
            metrics_history.stop()
        if fleet_agg is not None:
            fleet_agg.stop()
        if dynamics_monitor is not None:
            dynamics_monitor.close()
        if (slo_monitor is not None or fleet_agg is not None
                or alert_manager is not None) and args.logdir:
            from distributedtensorflow_tpu.obs import registry as _reglib

            try:
                _reglib.default_registry().write_prometheus(
                    os.path.join(args.logdir, "metrics.prom")
                )
            except OSError:
                logging.exception("final metrics.prom export failed")
        if _prefit_tracer is not None:
            _prefit_tracer.uninstall()
            _prefit_tracer.close()
    if goodput_ledger is not None:
        # A preemption already closed the generation as "preempted" (first
        # mark wins); otherwise this run ended cleanly.
        goodput_ledger.close(ended="clean")
    logging.info("done at step %d", int(state.step))


if __name__ == "__main__":
    main()
