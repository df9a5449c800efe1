#!/usr/bin/env python3
"""The untraced split of a decode iteration on the engine thread.

    python tools/engine_iter_split.py [ROOT] [--streams] [--profile]
        [--host-only] [--iterations N] [--config NAME] [--slots N]
        [--tokens N] [--sample N] [--budget TOKENS --fillers N]

The profiler's Python tracer slows exactly the host code between two
launches of ``jit_decode`` (PERF.md §5), so the traced ``decode_span_host_ms``
is a direction, not a size.  This takes the size: ``--slots`` requests
(prompt ``--prompt``) decode together in an in-process ``Engine``, no HTTP,
and the means of the step ring's seconds are printed (``step_ms``: the
leaves of the iteration, the engine thread's CPU beside its wall, the
streams' lag, as ``steps.jsonl`` has them; a tree from before ISSUE 36
has four of them).  With ``--streams`` every request is a stream, and one
consumer thread does for all of them what ``serve/server.py``'s writer does
(a ``get`` an iteration; ``json.dumps``, a write and the lag a line) — in a
tree from before ISSUE 46, a consumer thread a stream, as its frontend had:
the interpreter lock's part shows as the difference, in ``iter_ms`` and in
``offcpu_s``.  ``--sample N`` gives N
of the requests ``temperature`` 0.8 (the
iteration then fetches the logits).  ``--profile`` runs the engine thread
under ``cProfile`` — inflated like a traced run: read the order, not the
sizes.  ROOT (default: this checkout) is the tree to import the program
from, so a parent commit unpacked beside it is measured by the same script.
``--host-only`` hands the engine its decode program's last result in place
of a launch, so that an iteration is the engine thread's own work and
nothing else (the requests never end, so ``--iterations`` may pass
``--tokens``).  Two processes on this box differ by a tenth from one run to
the next, more than a change to the bookkeeping costs, so ``--beside ROOT2``
(implies ``--host-only``) takes that cost in ONE process: ROOT2's
``serve/engine.py`` is loaded as a second module of ROOT's package (same
tracer, same cache, same everything else), one engine is built from each
with a log directory of its own (``steps.jsonl`` is written, as under
``serve.py``),
and ``--pairs`` stretches of ``--iterations`` alternate; the row is the
paired difference of the engine thread's CPU time an iteration (ISSUE 36's
budget of 30 us is read this way).
``--budget TOKENS`` gives the engine a prefill budget and ``--fillers N``
holds N of the requests back, with prompts of ``--filler-prompt`` tokens,
until the others decode: their chunks then run in the timed iterations, one
budget each, and since ISSUE 60 under the decode step of the iteration
before.  The row's ``prefill`` says how many of the timed records' chunks
were launched that way (``prelaunched_share``) and what share of the
iterations' wall the launch took (``prelaunch_share_of_step``: the new leaf
``prelaunch_s`` of ``step_ms`` over ``step_s``); in a tree from before it
the same command prices the stretch it put under a chunk (``prefill_s`` of
those records).
Defaults are the trinity cell's; a tiny one runs on the CPU:
``--config afmoe_tiny --slots 4 --prompt 20 --tokens 90 --block 4 --chunk 8
--context 128 --kv-blocks 0 --kv-window-blocks 0``.  Prints one JSON row
(and the profile's head); a time it prints on the CPU is no device number.
"""

from __future__ import annotations

import argparse
import cProfile
import gc
import importlib.util
import io
import json
import os
import pstats
import queue
import statistics
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def beside(args, build, engine_mod) -> int:
    """``--beside``: ROOT's engine and ROOT2's in this process, alternating
    stretches; the paired difference of thread CPU an iteration."""
    path = os.path.join(os.path.abspath(args.beside),
                        "distributedtensorflow_tpu", "serve", "engine.py")
    spec = importlib.util.spec_from_file_location(
        "distributedtensorflow_tpu.serve._engine_beside", path)
    other = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = other
    spec.loader.exec_module(other)
    engines = {args.root: build(engine_mod), args.beside: build(other)}
    gc.collect()
    gc.freeze()
    n = args.iterations or 300

    def stretch(eng):
        cpu0 = time.thread_time()
        for _ in range(n):
            eng.step()
        return 1e6 * (time.thread_time() - cpu0) / n

    for eng in engines.values():
        stretch(eng)
    us = {root: [] for root in engines}
    for _ in range(args.pairs):
        for root, eng in engines.items():
            us[root].append(stretch(eng))
    diff = [a - b for a, b in zip(us[args.root], us[args.beside])]
    print(json.dumps({
        "root": args.root, "beside": args.beside, "pairs": args.pairs,
        "iterations_a_stretch": n, "slots": args.slots,
        "iter_cpu_us": {root: {"median": statistics.median(v),
                               "min": min(v)} for root, v in us.items()},
        "root_minus_beside_us": {
            "median": statistics.median(diff),
            "quartiles": statistics.quantiles(diff, n=4)},
    }), flush=True)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("root", nargs="?", default=ROOT)
    p.add_argument("--streams", action="store_true")
    p.add_argument("--profile", action="store_true")
    p.add_argument("--host-only", action="store_true")
    p.add_argument("--beside", metavar="ROOT2", default=None)
    p.add_argument("--pairs", type=int, default=40)
    p.add_argument("--iterations", type=int, default=0,
                   help="decode iterations to time (default: --tokens - 60)")
    p.add_argument("--config", default="trinity_large_ep8")
    p.add_argument("--slots", type=int, default=64)
    p.add_argument("--prompt", type=int, default=600)
    p.add_argument("--tokens", type=int, default=400)
    p.add_argument("--block", type=int, default=16)
    p.add_argument("--chunk", type=int, default=512)
    p.add_argument("--context", type=int, default=8192)
    p.add_argument("--kv-blocks", type=int, default=24576)
    p.add_argument("--kv-window-blocks", type=int, default=11264)
    p.add_argument("--sample", type=int, default=0,
                   help="how many of the requests sample (temperature 0.8)")
    p.add_argument("--budget", type=int, default=None,
                   help="the engine's prefill budget in tokens (none)")
    p.add_argument("--fillers", type=int, default=0,
                   help="requests that arrive once the others decode")
    p.add_argument("--filler-prompt", type=int, default=0,
                   help="their prompt's tokens (default: 8 chunks)")
    args = p.parse_args(argv)
    args.host_only = args.host_only or bool(args.beside)
    if args.fillers and args.host_only:
        p.error("--fillers needs the device: not with --host-only/--beside")
    sys.path.insert(0, os.path.abspath(args.root))

    import jax
    import numpy as np

    from distributedtensorflow_tpu import models, runtime
    from distributedtensorflow_tpu.obs import registry
    from distributedtensorflow_tpu.serve import engine as engine_mod
    from distributedtensorflow_tpu.serve.model import family_of

    runtime.init_compile_cache()
    cfg = getattr(models, args.config)()
    params = family_of(cfg).init_params(cfg, jax.random.PRNGKey(3))
    jax.block_until_ready(params)

    sink = open(os.devnull, "w")

    def build(mod):
        """An engine of ``mod.Engine`` with ``--slots`` requests past their
        prefill and every program compiled."""
        eng = mod.Engine(
            params, cfg, max_slots=args.slots, block_size=args.block,
            prefill_chunk=args.chunk, prefill_budget=args.budget,
            max_context=args.context,
            num_blocks=args.kv_blocks, window_blocks=args.kv_window_blocks,
            max_queue=512, step_ring=max(args.tokens, args.iterations, 64),
            registry=registry.Registry(),
            # --beside: with the step log on disk, as serve.py runs
            logdir=tempfile.mkdtemp(prefix="iter_split_")
            if args.beside else None)
        rng = np.random.default_rng(5)

        def line(tokens, stamp=None):
            sink.write(json.dumps({"tokens": tokens}) + "\n")
            sink.flush()
            if stamp is not None:
                eng.note_stream_line(stamp)

        def consume_all(batches):
            while True:
                for _, tokens, stamp in batches.get():
                    if tokens is not None:
                        line(tokens, stamp)

        def consume(req):       # a tree from before ISSUE 46
            while True:
                kind, payload, *stamp = req._events.get()
                if kind != "tokens":
                    return
                line(payload, *stamp)

        def submit(i, prompt):
            return eng.submit(
                rng.integers(0, cfg.vocab_size, prompt).tolist(),
                max_new_tokens=args.tokens, stream=args.streams,
                temperature=0.8 if i < args.sample else 0.0, seed=i)

        reqs = [submit(i, args.prompt)
                for i in range(args.slots - args.fillers)]
        if args.streams and hasattr(eng, "stream_sink"):
            batches = queue.SimpleQueue()
            eng.stream_sink = batches.put
            threading.Thread(target=consume_all, args=(batches,),
                             daemon=True).start()
        elif args.streams:
            for r in reqs:
                threading.Thread(target=consume, args=(r,),
                                 daemon=True).start()
        while eng._filling or eng._queue:
            eng.step()
        for _ in range(20):
            eng.step()
        # the fillers' prompts fill under the timed decode steps
        reqs += [submit(i, args.filler_prompt or 8 * args.chunk)
                 for i in range(args.slots - args.fillers, args.slots)]
        if args.host_only:
            # the pools it hands back are the live ones; its token is no
            # EOS and max_new_tokens is out of reach: the batch stays whole
            result = eng.programs.decode(
                eng.params, eng.kv.pools(), eng._last_tokens,
                eng._tables_dev(), eng.kv.seq_lens, eng._dev_active)
            eng.kv.set_pools(result[2])
            eng.programs.decode = lambda *a: result
            eng.kv.note_written = lambda *a: None
            for r in reqs:
                r.max_new_tokens = 10 ** 9
        return eng

    if args.beside:
        return beside(args, build, engine_mod)
    eng = build(engine_mod)
    if args.host_only:
        # one full collection of JAX's half a million objects is 60 ms;
        # whether it falls inside the stretch would decide the comparison
        gc.collect()
        gc.freeze()
    prof = cProfile.Profile() if args.profile else None
    n0 = eng.decode_steps

    cpu = [0.0]

    def loop():
        if prof:
            prof.enable()
        cpu0 = time.thread_time()
        while eng.decode_steps - n0 < (args.iterations or args.tokens - 60):
            eng.step()
        cpu[0] = time.thread_time() - cpu0
        if prof:
            prof.disable()

    t0 = time.perf_counter()
    engine_thread = threading.Thread(target=loop)
    engine_thread.start()
    engine_thread.join()
    wall = time.perf_counter() - t0
    n = eng.decode_steps - n0
    recs = [r for r in eng.step_records() if r["occupancy"]][-n:]
    chunks = sum(r["prefill_chunks"] for r in recs)
    ahead = sum(r.get("prefill_prelaunched", 0) for r in recs)
    print(json.dumps({
        "root": args.root, "streams": args.streams, "profile": args.profile,
        "host_only": args.host_only,
        "sample": args.sample, "device": jax.devices()[0].device_kind,
        "iterations": n,
        "occupancy_mean": sum(r["occupancy"] for r in recs) / len(recs),
        "iter_ms": 1e3 * wall / n,
        "iter_cpu_ms": 1e3 * cpu[0] / n,    # the engine thread's own
        "step_ms": {k: round(1e3 * statistics.fmean(r[k] for r in recs), 4)
                    for k in recs[-1] if k.endswith("_s")},
        # the timed records' chunks, those of them launched under the
        # decode step before, and the launch's share of the iterations
        "prefill": {
            "chunks": chunks, "prelaunched": ahead,
            "prelaunched_share": round(ahead / chunks, 4) if chunks else None,
            "prelaunch_share_of_step": round(
                sum(r.get("prelaunch_s", 0.0) for r in recs)
                / sum(r["step_s"] for r in recs), 4),
            "filling_iterations": sum(r["filling_slots"] > 0 for r in recs)},
        "stream_lines_per_iter": statistics.fmean(
            r.get("stream_lines", 0) for r in recs),
        "counters": {k: v for k, v in eng.counters.items() if k in (
            "decode_tokens", "host_sample_rounds", "device_sampled_tokens",
            "logit_fetches")},
    }), flush=True)
    if prof:
        out = io.StringIO()
        pstats.Stats(prof, stream=out).sort_stats("tottime").print_stats(28)
        print(out.getvalue()[:6000], flush=True)
    # the consumers are daemons blocked on their queues: leave at once
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
