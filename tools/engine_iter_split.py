#!/usr/bin/env python3
"""The untraced split of a decode iteration on the engine thread.

    python tools/engine_iter_split.py [ROOT] [--streams] [--profile]
        [--config NAME] [--slots N] [--tokens N] [--sample N]

The profiler's Python tracer slows exactly the host code between two
launches of ``jit_decode`` (PERF.md §5), so the traced ``decode_span_host_ms``
is a direction, not a size.  This takes the size: ``--slots`` requests
(prompt ``--prompt``) decode together in an in-process ``Engine``, no HTTP,
and the engine's own spans are summed by name (``Span.dur_s``, read in
``span.__exit__``: dispatch / fetch / commit / log), beside the time inside
``_stream_emit``.  With ``--streams`` every request is a stream with a
consumer thread doing what ``serve/server.py``'s generator does (``get``,
``json.dumps``, a write): the interpreter lock's part shows as the
difference.  ``--sample N`` gives N of the requests ``temperature`` 0.8 (the
iteration then fetches the logits).  ``--profile`` runs the engine thread
under ``cProfile`` — inflated like a traced run: read the order, not the
sizes.  ROOT (default: this checkout) is the tree to import the program
from, so a parent commit unpacked beside it is measured by the same script.
Defaults are the trinity cell's; a tiny one runs on the CPU:
``--config afmoe_tiny --slots 4 --prompt 20 --tokens 90 --block 4 --chunk 8
--context 128 --kv-blocks 0 --kv-window-blocks 0``.  Prints one JSON row
(and the profile's head); a time it prints on the CPU is no device number.
"""

from __future__ import annotations

import argparse
import collections
import cProfile
import io
import json
import os
import pstats
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("root", nargs="?", default=ROOT)
    p.add_argument("--streams", action="store_true")
    p.add_argument("--profile", action="store_true")
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
    args = p.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.root))

    import jax
    import numpy as np

    from distributedtensorflow_tpu import models, runtime
    from distributedtensorflow_tpu.obs import tracing
    from distributedtensorflow_tpu.serve import engine as engine_mod
    from distributedtensorflow_tpu.serve.model import family_of

    runtime.init_compile_cache()
    cfg = getattr(models, args.config)()
    params = family_of(cfg).init_params(cfg, jax.random.PRNGKey(3))
    jax.block_until_ready(params)

    # the wall clocks: every span's duration by name, and the time inside
    # _stream_emit (the hand-over of a line to its stream)
    spans = collections.defaultdict(list)
    span_exit = tracing.span.__exit__

    def timed_exit(self, *exc):
        result = span_exit(self, *exc)
        spans[self._span.name].append(self._span.dur_s)
        return result

    tracing.span.__exit__ = timed_exit
    emit_s = [0.0, 0]
    stream_emit = engine_mod.Engine._stream_emit

    def timed_emit(self, req, toks):
        t = time.perf_counter()
        stream_emit(self, req, toks)
        emit_s[0] += time.perf_counter() - t
        emit_s[1] += 1

    engine_mod.Engine._stream_emit = timed_emit

    eng = engine_mod.Engine(
        params, cfg, max_slots=args.slots, block_size=args.block,
        prefill_chunk=args.chunk, max_context=args.context,
        num_blocks=args.kv_blocks, window_blocks=args.kv_window_blocks,
        max_queue=512)
    rng = np.random.default_rng(5)
    sink = open(os.devnull, "w")

    def consume(req):
        while True:
            kind, payload = req._events.get()
            if kind != "tokens":
                return
            sink.write(json.dumps({"tokens": payload}) + "\n")
            sink.flush()

    reqs = [eng.submit(
        rng.integers(0, cfg.vocab_size, args.prompt).tolist(),
        max_new_tokens=args.tokens, stream=args.streams,
        temperature=0.8 if i < args.sample else 0.0, seed=i)
        for i in range(args.slots)]
    if args.streams:
        for r in reqs:
            threading.Thread(target=consume, args=(r,), daemon=True).start()

    # every prompt prefilled and every program compiled before the stretch
    while eng._filling or eng._queue:
        eng.step()
    for _ in range(20):
        eng.step()
    for durations in spans.values():
        durations.clear()
    emit_s[:] = [0.0, 0]
    prof = cProfile.Profile() if args.profile else None
    n0 = eng.decode_steps

    def loop():
        if prof:
            prof.enable()
        while eng.decode_steps - n0 < args.tokens - 60:
            eng.step()
        if prof:
            prof.disable()

    t0 = time.perf_counter()
    engine_thread = threading.Thread(target=loop)
    engine_thread.start()
    engine_thread.join()
    wall = time.perf_counter() - t0
    n = eng.decode_steps - n0
    recs = [r for r in eng.step_records() if r["occupancy"]]
    print(json.dumps({
        "root": args.root, "streams": args.streams, "profile": args.profile,
        "sample": args.sample, "device": jax.devices()[0].device_kind,
        "iterations": n,
        "occupancy_mean": sum(r["occupancy"] for r in recs) / len(recs),
        "iter_ms": 1e3 * wall / n,
        "span_ms": {k: round(1e3 * sum(v) / n, 4)
                    for k, v in sorted(spans.items()) if v},
        "stream_emit_ms": round(1e3 * emit_s[0] / n, 4),
        "stream_emit_calls_per_iter": emit_s[1] / n,
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
