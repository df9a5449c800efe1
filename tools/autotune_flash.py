#!/usr/bin/env python
"""Autotune the flash-attention block tiling and persist the winner.

Puts a cache the kernel consults at trace time (``ops/flash_tuning.py``)
before the static ``DEFAULT_BLOCK_Q/K``: run this tool once per
(shape, dtype, platform) of interest and every subsequent
``flash_attention`` call on that shape picks the measured-best tiling
automatically (env overrides still win; see
``flash_attention._resolve_blocks``).

Two population paths:

**Sweep** (default) — a timing microbench over candidate (block_q,
block_k) pairs::

    python tools/autotune_flash.py --shape 4,8,1024,64 --dtype bfloat16
    python tools/autotune_flash.py --shape 16,12,4096,64 --bwd \
        --blocks 256,512,1024 --steps 10

Each candidate times ``flash_attention`` forward (and ``--bwd`` adds the
full backward) with the blocks pinned explicitly; best-of-3 repeats, each
window ending in ``block_until_ready``.  The winner is stored with
``source: "sweep"`` and the platform that produced the timing.

**XPlane** — harvest a reactive-profiler capture
(``obs.capture`` / ``--auto-profile`` windows, or any
``jax.profiler.trace`` dir)::

    python tools/autotune_flash.py --from-xplane <logdir>/captures/3 \
        --shape 16,12,4096,64 --dtype bfloat16

Sums the device time of events whose name matches ``--kernel-re``
(default: the Pallas flash kernels) via a self-contained XPlane
wire-format reader (no tensorflow proto dependency), and stores the
per-step cost for the tiling that was in force during the capture
(``--block-q/--block-k``, defaulting to the currently-resolved blocks)
with ``source: "xplane"`` — certifying the production tiling's measured
cost so a later sweep has a baseline to beat.

Cache: ``--cache`` path, else ``DTFT_FLASH_TUNE_CACHE``, else
``flash_blocks.json`` beside ``ops/flash_tuning.py``.  Exactly one
JSON line is printed with the stored entry.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

#: Candidate block sizes swept by default (pruned to divisors of seq).
DEFAULT_CANDIDATES = (128, 256, 512, 1024)

#: Event names counted by --from-xplane by default: the Pallas flash
#: kernels (fwd + both backward flavors).
DEFAULT_KERNEL_RE = r"flash|_fwd_kernel|_bwd_(fused|dq|dkv)_kernel"


# --- minimal protobuf wire reader (XPlane has no importable proto here) -----


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = 0
    out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """Yield (field_number, wire_type, value) over one message's wire
    bytes; LEN fields yield their raw sub-buffer."""
    i = 0
    n = len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        fnum, wt = tag >> 3, tag & 7
        if wt == 0:  # varint
            v, i = _varint(buf, i)
        elif wt == 1:  # fixed64
            v, i = buf[i:i + 8], i + 8
        elif wt == 2:  # LEN
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt == 5:  # fixed32
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield fnum, wt, v


def xplane_kernel_ms(path: str, kernel_re: str) -> tuple[float, int]:
    """(total device milliseconds, event count) of matching events in one
    ``*.xplane.pb`` file.

    XPlane schema (tsl/profiler/protobuf/xplane.proto, stable field
    numbers): XSpace.planes=1 → XPlane{name=2, lines=3,
    event_metadata=4 (map: key=1, value=2 → XEventMetadata{name=2})} →
    XLine{events=4} → XEvent{metadata_id=1, duration_ps=3}.
    """
    pat = re.compile(kernel_re)
    with open(path, "rb") as f:
        space = f.read()
    total_ps = 0
    count = 0
    for fnum, wt, plane in _fields(space):
        if fnum != 1 or wt != 2:
            continue
        meta_names: dict[int, str] = {}
        lines = []
        for pf, pw, pv in _fields(plane):
            if pf == 4 and pw == 2:  # event_metadata map entry
                key = None
                name = None
                for mf, mw, mv in _fields(pv):
                    if mf == 1 and mw == 0:
                        key = mv
                    elif mf == 2 and mw == 2:  # XEventMetadata
                        for ef, ew, ev in _fields(mv):
                            if ef == 2 and ew == 2:
                                name = ev.decode("utf-8", "replace")
                if key is not None and name:
                    meta_names[key] = name
            elif pf == 3 and pw == 2:  # XLine
                lines.append(pv)
        matching = {k for k, v in meta_names.items() if pat.search(v)}
        if not matching:
            continue
        for line in lines:
            for lf, lw, lv in _fields(line):
                if lf != 4 or lw != 2:  # XEvent
                    continue
                mid = None
                dur = 0
                for ef, ew, ev in _fields(lv):
                    if ef == 1 and ew == 0:
                        mid = ev
                    elif ef == 3 and ew == 0:
                        dur = ev
                if mid in matching:
                    total_ps += dur
                    count += 1
    return total_ps / 1e9, count


def harvest_xplane(xplane_dir: str, kernel_re: str) -> tuple[float, int]:
    paths = sorted(
        glob.glob(os.path.join(xplane_dir, "**", "*.xplane.pb"),
                  recursive=True)
    )
    if not paths:
        raise SystemExit(
            f"{xplane_dir}: no *.xplane.pb files (is this a capture/"
            "profiler dir?)"
        )
    total = 0.0
    count = 0
    for p in paths:
        ms, n = xplane_kernel_ms(p, kernel_re)
        total += ms
        count += n
    if count == 0:
        raise SystemExit(
            f"{xplane_dir}: no events matching {kernel_re!r} — pass "
            "--kernel-re, or was the capture taken without the flash "
            "kernel in the hot path?"
        )
    return total, count


# --- the timing sweep --------------------------------------------------------


def time_config(q, k, v, *, causal, bwd, block_q, block_k, steps,
                repeats=3) -> float:
    """Best-of-repeats mean milliseconds for one tiling."""
    import jax
    import jax.numpy as jnp

    from distributedtensorflow_tpu.ops.flash_attention import flash_attention

    if bwd:
        fn = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=causal,
                                block_q=block_q,
                                block_k=block_k).astype(jnp.float32) ** 2
            ),
            argnums=(0, 1, 2),
        ))
    else:
        fn = jax.jit(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, block_q=block_q, block_k=block_k
            )
        )

    out = None
    for _ in range(2):
        out = fn(q, k, v)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(q, k, v)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / steps)
    return 1e3 * best


def run_sweep(args, shape, dtype_name) -> dict:
    import jax
    import jax.numpy as jnp

    b, h, s, d = shape
    dtype = jnp.dtype(dtype_name)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (
        jax.random.normal(kk, (b, s, h, d), dtype) for kk in ks
    )
    if args.blocks:
        candidates = [int(x) for x in args.blocks.split(",")]
    else:
        candidates = list(DEFAULT_CANDIDATES)
    candidates = sorted({c for c in candidates if c > 0 and s % c == 0})
    if not candidates:
        raise SystemExit(
            f"no candidate block sizes divide seq {s} (candidates "
            f"{args.blocks or DEFAULT_CANDIDATES})"
        )
    rows = []
    best = None
    for bq in candidates:
        for bk in candidates:
            try:
                ms = time_config(
                    q, k, v, causal=args.causal, bwd=args.bwd,
                    block_q=bq, block_k=bk, steps=args.steps,
                )
            except Exception as e:
                rows.append({"block_q": bq, "block_k": bk,
                             "error": f"{type(e).__name__}: {str(e)[:120]}"})
                continue
            rows.append({"block_q": bq, "block_k": bk,
                         "ms": round(ms, 3)})
            if best is None or ms < best["ms"]:
                best = {"block_q": bq, "block_k": bk, "ms": ms}
            print(f"autotune_flash: bq={bq:5d} bk={bk:5d}  {ms:9.3f} ms",
                  file=sys.stderr)
    if best is None:
        raise SystemExit("every candidate tiling failed to run")
    return {"best": best, "rows": rows}


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--shape", required=True, metavar="B,H,S,D",
                   help="attention shape: batch,heads,seq,head_dim")
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--blocks", default=None,
                   help="comma list of candidate block sizes "
                        f"(default {','.join(map(str, DEFAULT_CANDIDATES))};"
                        " non-divisors of seq are pruned)")
    p.add_argument("--steps", type=int, default=5,
                   help="timed dispatches per candidate (best of 3 repeats)")
    p.add_argument("--bwd", action="store_true",
                   help="time forward + full backward (the training shape)")
    p.add_argument("--causal", action="store_true", default=True)
    p.add_argument("--no-causal", dest="causal", action="store_false")
    p.add_argument("--cache", default=None,
                   help="cache file (default: DTFT_FLASH_TUNE_CACHE or "
                        "flash_blocks.json inside the package's ops/ — "
                        "a file to commit)")
    p.add_argument("--from-xplane", default=None, metavar="DIR",
                   help="harvest a CaptureEngine/jax.profiler XPlane dir "
                        "instead of sweeping: record the matched kernels' "
                        "measured cost for the tiling in force")
    p.add_argument("--platform", default=None,
                   help="platform tag for the stored entry (default: the "
                        "local jax backend).  REQUIRED knowledge for "
                        "--from-xplane harvests done off-box: a TPU "
                        "capture analyzed on a CPU workstation must be "
                        "stored as --platform tpu or the TPU process "
                        "will never match the entry")
    p.add_argument("--kernel-re", default=DEFAULT_KERNEL_RE,
                   help="event-name regex counted by --from-xplane")
    p.add_argument("--block-q", type=int, default=None,
                   help="--from-xplane: the tiling the capture ran "
                        "(default: what the resolver picks now)")
    p.add_argument("--block-k", type=int, default=None)
    args = p.parse_args(argv)

    try:
        shape = tuple(int(x) for x in args.shape.split(","))
        b, h, s, d = shape
    except ValueError:
        raise SystemExit(f"--shape {args.shape!r}: expected B,H,S,D ints")

    if args.from_xplane:
        # No devices needed: pure file analysis + a resolver call.
        import jax

        from distributedtensorflow_tpu.ops import flash_tuning
        from distributedtensorflow_tpu.ops.flash_attention import (
            _resolve_blocks,
        )

        total_ms, n_events = harvest_xplane(args.from_xplane,
                                            args.kernel_re)
        import jax.numpy as jnp

        bq, bk = args.block_q, args.block_k
        if bq is None or bk is None:
            bq, bk = _resolve_blocks(b, h, s, d, jnp.dtype(args.dtype),
                                     bq, bk)
        entry = {
            "platform": args.platform or jax.default_backend(),
            "dtype": args.dtype,
            "batch": b, "heads": h, "seq": s, "depth": d,
            "block_q": bq, "block_k": bk,
            "ms": round(total_ms, 3),
            "source": "xplane",
        }
        path = flash_tuning.store(entry, args.cache)
        print(json.dumps({
            "metric": "flash_block_autotune",
            "mode": "xplane",
            "events_matched": n_events,
            "cache": path,
            **entry,
        }))
        return 0

    if args.platform:
        # A sweep times THIS process's backend; storing its numbers under
        # another platform tag would be a lie the cache consults forever.
        raise SystemExit(
            "--platform is only meaningful with --from-xplane (offline "
            "harvest); sweep entries are tagged with the backend that "
            "produced the timings"
        )

    import jax

    from distributedtensorflow_tpu import runtime
    from distributedtensorflow_tpu.ops import flash_tuning

    runtime.init_compile_cache()

    sweep = run_sweep(args, shape, args.dtype)
    best = sweep["best"]
    entry = {
        "platform": jax.default_backend(),
        "dtype": args.dtype,
        "batch": b, "heads": h, "seq": s, "depth": d,
        "block_q": best["block_q"], "block_k": best["block_k"],
        "ms": round(best["ms"], 3),
        "source": "sweep",
    }
    path = flash_tuning.store(entry, args.cache)
    print(json.dumps({
        "metric": "flash_block_autotune",
        "mode": "sweep",
        "bwd": args.bwd,
        "rows": sweep["rows"],
        "cache": path,
        **entry,
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
