#!/usr/bin/env python3
"""Time one routed expert layer alone on the chip, whole and by its parts,
in this tree and in a second checkout beside it, and hold the two to each
other: ``parallel.moe.dropless_moe`` at a configuration's published widths
for a prefill chunk's tokens and a decode batch's.

    chiprun -- python tools/moe_forms.py [--beside .bench_checkout/parent]
        [--cases nemotron3_super_ep4:2048,nemotron3_super_ep4:128,...]

No engine and no model: random activations, router and experts of the
configuration's shapes (``hidden_size``, ``num_experts``,
``experts_per_token``, ``experts_held``, ``moe_intermediate_size``, a latent
expert's ``moe_latent_size``), each program launched ``--reps`` times under
the profiler and timed by its executions on the device (``ms`` their median,
``ops`` its largest operations a call: the host's launch, ~0.2 ms, is in
neither).  One JSON row a measurement: ``what``
is ``layer`` (the whole of ``dropless_moe``, router and ``top_k`` included),
``route`` (the router alone), ``plan`` (``group_plan`` on the layer's own
choices), ``gather`` (the row buffer), ``kernels`` (the two grouped
matmuls), ``pick`` (the weighted sum of a token's rows: the tree's own form
of it); the ``against`` rows carry the largest difference of the two trees'
outputs and whether their counters agree.  Exits non-zero without a TPU.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

CASES = ("nemotron3_super_ep4:2048,nemotron3_super_ep4:128,"
         "joyai_llm_flash:1024,joyai_llm_flash:32,"
         "trinity_large_ep8:512,trinity_large_ep8:64,"
         "ling3_flash_ep8:2048,lfm2_24b_a2b:96")


PACKAGE = "distributedtensorflow_tpu"


def _loaded():
    return {name: module for name, module in sys.modules.items()
            if name.split(".")[0] == PACKAGE}


def load_tree(root):
    """The package of the checkout at ``root`` and the modules it loaded:
    two trees side by side in one process, one of them under the package's
    name at a time (:func:`enter`)."""
    for name in _loaded():
        del sys.modules[name]
    sys.path.insert(0, root)
    try:
        tree = {name: importlib.import_module(f"{PACKAGE}.{name}")
                for name in ("models", "runtime", "parallel.moe",
                             "ops.grouped_matmul")}
    finally:
        sys.path.remove(root)
    assert os.path.realpath(tree["runtime"].__file__).startswith(
        os.path.realpath(root) + os.sep), tree["runtime"].__file__
    return {**tree, "loaded": _loaded()}


def enter(tree):
    """Put ``tree``'s modules under the package's name: what a function of
    it imports while it is traced is its own tree's."""
    for name in _loaded():
        del sys.modules[name]
    sys.modules.update(tree["loaded"])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--beside", default=None,
                   help="a second checkout to time beside this one")
    p.add_argument("--cases", default=CASES,
                   help="CONFIG:TOKENS, comma separated")
    p.add_argument("--reps", type=int, default=8)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    trees = {"here": load_tree(ROOT)}
    if args.beside:
        trees["beside"] = load_tree(os.path.abspath(args.beside))

    import jax
    import jax.numpy as jnp
    import trace_reduce

    runtime = trees["here"]["runtime"]
    runtime.init_compile_cache()
    if not runtime.on_tpu():
        print("moe_forms: no TPU", file=sys.stderr)
        return 1

    def emit(**row):
        print(json.dumps(row), flush=True)

    def traced(programs):
        """``{name: (median ms, {operation: ms a call})}`` of each program's
        executions on the device, from one profiler trace of ``--reps``
        calls each (the host's launch is not in it)."""
        trace_dir = tempfile.mkdtemp(prefix="moe_forms_")
        jax.profiler.start_trace(trace_dir)
        for fn, *xs in programs.values():
            for _ in range(args.reps):
                out = fn(*xs)
            jax.block_until_ready(out)
        jax.profiler.stop_trace()
        device = next(iter(trace_reduce.load_xplane(
            trace_reduce.find_xplane(trace_dir))["devices"].values()))
        shutil.rmtree(trace_dir, ignore_errors=True)
        out = {}
        for name in programs:
            runs = [(s, s + d) for n, s, d in device["modules"]
                    if n.startswith(f"jit_{name}(")]
            ops = trace_reduce.op_seconds(device["ops"], within=runs)
            out[name] = (
                statistics.median(e - s for s, e in runs) * 1e3,
                {op: round(sec / len(runs) * 1e3, 4)
                 for op, sec in trace_reduce.top(ops, 6)})
        return out

    for case in args.cases.split(","):
        name, tokens = case.split(":")
        tokens = int(tokens)
        cfg = getattr(trees["here"]["models"], name)()
        d, e, k = cfg.hidden_size, cfg.num_experts, cfg.experts_per_token
        count = getattr(cfg, "experts_held", None) or e
        d_in = getattr(cfg, "moe_latent_size", None) or d
        m = cfg.moe_intermediate_size
        gated = d_in == d
        key = jax.random.split(jax.random.PRNGKey(args.seed), 8)
        bf16 = jnp.bfloat16
        h = jax.random.normal(key[0], (tokens, d), bf16)
        x = h if gated else jax.random.normal(key[1], (tokens, d_in), bf16)
        router = jax.random.normal(key[2], (d, e), jnp.float32) * d ** -0.5
        bias = jnp.zeros((e,), jnp.float32)
        experts = {"w_up": jax.random.normal(key[3], (count, d_in, m), bf16)
                   * d_in ** -0.5,
                   "w_down": jax.random.normal(key[4], (count, m, d_in), bf16)
                   * m ** -0.5}
        if gated:
            experts["w_gate"] = jax.random.normal(
                key[5], (count, d_in, m), bf16) * d_in ** -0.5
        names = [n for n in ("w_gate", "w_up", "w_down") if n in experts]
        shape = {"case": name, "tokens": tokens, "pairs_routed": tokens * k,
                 "held": count, "published": e}
        outs = {}
        for tree, mods in trees.items():
            enter(mods)
            moe, gmm = mods["parallel.moe"], mods["ops.grouped_matmul"]
            tile = moe.group_tile(tokens, k, e)

            def layer(h, x, router, experts, moe=moe):
                return moe.dropless_moe(
                    h, router, bias, experts, held=(0, count), top_k=k,
                    experts_in=None if gated else x)

            def route(h, router, moe=moe):
                return moe.sigmoid_topk_route(h, router, bias, top_k=k)

            def plan_of(idx, moe=moe, tile=tile):
                plan = moe.group_plan(idx, (0, count), None, tile)
                return {k: v for k, v in plan.items() if k != "rows"}

            def gather(x, src):
                return jnp.concatenate(
                    [x, jnp.zeros((1, x.shape[-1]), x.dtype)])[src]

            def kernels(x_rows, plan, experts, gmm=gmm, tile=tile):
                grouped = gmm.grouped_swiglu if gated else gmm.grouped_relu2
                return grouped(x_rows, *[experts[n] for n in names],
                               plan["tile_expert"], plan["tiles_used"],
                               tile=tile)

            def pick(y_rows, plan, w, gmm=gmm, tile=tile):
                # the tree's own form, as its ``dropless_moe`` takes it
                if hasattr(gmm, "combine_rows") and count < e:
                    return gmm.combine_rows(
                        y_rows, plan["src"], plan["pair"], w,
                        plan["tiles_used"] * tile).astype(bf16)
                if not hasattr(gmm, "combine_rows"):
                    y_rows = jnp.concatenate([y_rows, jnp.zeros(
                        (1, y_rows.shape[-1]), y_rows.dtype)])
                picked = y_rows[plan["dest"]].astype(jnp.float32)
                return (picked * w[..., None]).sum(1).astype(bf16)

            fns = {"layer": layer, "route": route, "plan": plan_of,
                   "gather": gather, "kernels": kernels, "pick": pick}
            for what, fn in fns.items():
                fn.__name__ = f"{tree}_{what}"     # the program's name
            layer, route, plan_of, gather, kernels, pick = map(
                jax.jit, fns.values())
            outs[tree] = out, counters = layer(h, x, router, experts)
            idx, w = route(h, router)
            plan = plan_of(idx)
            x_rows = gather(x, plan["src"])
            y_rows = kernels(x_rows, plan, experts)
            parts = {f"{tree}_layer": (layer, h, x, router, experts),
                     f"{tree}_route": (route, h, router),
                     f"{tree}_plan": (plan_of, idx),
                     f"{tree}_gather": (gather, x, plan["src"]),
                     f"{tree}_kernels": (kernels, x_rows, plan, experts),
                     f"{tree}_pick": (pick, y_rows, plan, w)}
            got = pick(y_rows, plan, w)
            emit(**shape, tree=tree, what="parts_against_layer", tile=tile,
                 rows=int(plan["src"].shape[0]),
                 rows_used=int(plan["tiles_used"]) * tile,
                 pairs=int(counters["pairs"]),
                 max_abs=float(jnp.abs(got.astype(jnp.float32)
                                       - out.astype(jnp.float32)).max()))
            for name, (ms, ops) in traced(parts).items():
                emit(**shape, tree=tree, what=name.split("_", 1)[1],
                     ms=round(ms, 4), ops=ops)
        if len(outs) == 2:
            (a, ca), (b, cb) = outs["here"], outs["beside"]
            emit(**shape, against="beside",
                 max_abs=float(jnp.abs(a.astype(jnp.float32)
                                       - b.astype(jnp.float32)).max()),
                 scale=float(jnp.abs(b.astype(jnp.float32)).max()),
                 counters_equal=all(int(ca[c]) == int(cb[c]) for c in cb))
    return 0


if __name__ == "__main__":
    sys.exit(main())
