#!/usr/bin/env python3
"""Compile a workload's whole training step as ``train.py`` builds it and
say what the compiler says of it: the bytes a device holds while it runs
(``memory_analysis``) and the Pallas kernels it calls, by name.

    chiprun -- python tools/train_step_memory.py [--workload gpt_medium_lm]
        [--per-chip-batch 64] [--seq-len 1024] [--chips 1]
        [--describe v5e:2x2] [--policy-less]

On the chip the step is compiled for the devices that are there.  With
``--describe`` it is compiled for a *described* topology, no chip attached
(``tests/test_kernel_export_gpt2.py`` holds GPT-2 medium's step to its kernel
counts and to 14.0 GB that way; a described compile says nothing of time).
``--policy-less`` compiles the same step with the blocks under a
``jax.checkpoint`` that keeps nothing (``models.gpt.remat_block`` before
PR 37): the step a saved residual is weighed against.

One JSON row to stdout and ``chiprun_out/train_step_memory.jsonl``:
``total_bytes`` = arguments + outputs - aliased + temporaries, a device;
``kernels`` = custom calls in the compiled module by kernel name (per
shard on a mesh); ``attn_residuals``, ``flash_causal_tile`` /
``flash_causal_share`` and ``xent_products_per_step`` /
``xent_dlog_chunk_tokens`` as the trainer's start-up row has them.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def compile_step(workload: str, per_chip_batch: int, seq_len: int, devices):
    """``(compiled step, mesh, workload)`` for a ``data`` mesh over
    ``devices``: state and step as ``train.py`` makes them, lowered against
    shapes (nothing is placed, so described devices will do)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh
    from distributedtensorflow_tpu.parallel import sharding as shardlib
    from distributedtensorflow_tpu.train import (abstract_sharded_state,
                                                 make_train_step)
    from distributedtensorflow_tpu.workloads import get_workload

    mesh = build_mesh(MeshSpec(data=len(devices)), devices)
    wl = get_workload(
        workload, seq_len=seq_len,
        global_batch_size=per_chip_batch * len(devices)).for_mesh(mesh)
    state, specs = abstract_sharded_state(
        wl.init_fn, wl.make_optimizer(), mesh, jax.random.PRNGKey(0),
        rules=wl.layout, fsdp=wl.fsdp)
    rows = NamedSharding(mesh, shardlib.batch_spec(mesh))
    batch = {
        k: jax.ShapeDtypeStruct(
            (wl.global_batch_size, *v.shape[1:]), v.dtype, sharding=rows)
        for k, v in wl.init_batch.items()}
    rng = jax.ShapeDtypeStruct((2,), "uint32",
                               sharding=NamedSharding(mesh, P()))
    step = make_train_step(wl.loss_fn, mesh, specs,
                           accum_steps=wl.accum_steps)
    return step.lower(state, batch, rng).compile(), mesh, wl


def report(compiled, mesh, wl) -> dict:
    import jax

    m = compiled.memory_analysis()
    kernels: dict[str, int] = {}
    for name in re.findall(r'custom_call_target="tpu_custom_call"[^\n]*?'
                           r'op_name="[^"\n]*?(\w+)/pallas_call"',
                           compiled.as_text()):
        kernels[name] = kernels.get(name, 0) + 1
    row = {
        "argument_bytes": m.argument_size_in_bytes,
        "output_bytes": m.output_size_in_bytes,
        "alias_bytes": m.alias_size_in_bytes,
        "temp_bytes": m.temp_size_in_bytes,
        "total_bytes": (m.argument_size_in_bytes + m.output_size_in_bytes
                        - m.alias_size_in_bytes + m.temp_size_in_bytes),
        "kernels": dict(sorted(kernels.items())),
    }
    ids = wl.init_batch.get("input_ids")
    if ids is not None and hasattr(wl.model, "attn_residuals"):
        with jax.sharding.set_mesh(mesh):
            row["flash_layout"] = wl.model.flash_layout(ids.shape[1])
            row["attn_residuals"], row["attn_residual_bytes_per_layer"] = (
                wl.model.attn_residuals(wl.global_batch_size, ids.shape[1]))
            row["flash_causal_tile"], row["flash_causal_share"] = (
                wl.model.flash_causal_tile(wl.global_batch_size,
                                           ids.shape[1]))
            row["xent_products_per_step"], row["xent_dlog_chunk_tokens"] = (
                wl.model.xent_products(wl.global_batch_size, ids.shape[1]))
    return row


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="gpt_medium_lm")
    p.add_argument("--per-chip-batch", type=int, default=64)
    p.add_argument("--seq-len", type=int, default=1024)
    p.add_argument("--chips", type=int, default=1)
    p.add_argument("--describe", default=None, metavar="TOPOLOGY")
    p.add_argument("--policy-less", action="store_true")
    args = p.parse_args()

    import jax

    if args.describe:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies

        import distributedtensorflow_tpu.workloads  # noqa: F401
        import distributedtensorflow_tpu.models  # noqa: F401

        devices = topologies.get_topology_desc(
            platform="tpu", topology_name=args.describe).devices
        # the program asks the attached backend which kernels to take:
        # answer for the described chip
        for name, module in list(sys.modules.items()):
            if name.startswith("distributedtensorflow_tpu") \
                    and hasattr(module, "on_tpu"):
                module.on_tpu = lambda: True
    else:
        devices = jax.devices()
        if devices[0].platform != "tpu":
            print("train_step_memory: no TPU (--describe v5e:2x2 compiles "
                  "for a described one)", file=sys.stderr)
            return 1
    if args.policy_less:
        import flax.linen as nn

        from distributedtensorflow_tpu.models import (gpt, gpt_moe,
                                                      gpt_pipeline)

        def policy_less(block):
            if isinstance(block, type):
                return nn.remat(block, static_argnums=(3,))
            return jax.checkpoint(block)

        for module in (gpt, gpt_moe, gpt_pipeline):
            module.remat_block = policy_less
    compiled, mesh, wl = compile_step(
        args.workload, args.per_chip_batch, args.seq_len,
        list(devices)[:args.chips])
    row = {"workload": args.workload, "per_chip_batch": args.per_chip_batch,
           "seq_len": args.seq_len, "chips": args.chips,
           "device_kind": devices[0].device_kind,
           "described": bool(args.describe),
           "policy_less": args.policy_less, **report(compiled, mesh, wl)}
    if args.policy_less:  # the model's answer is for its own remat_block
        del row["attn_residuals"], row["attn_residual_bytes_per_layer"]
    line = json.dumps(row)
    print(line, flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/train_step_memory.jsonl", "a") as f:
        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
