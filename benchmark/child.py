"""The process that holds the chip: the program's own entry point, run as
``__main__`` in this process, with the benchmark's instruments beside it.

    python benchmark/child.py --ctl DIR [--preflight FILE] ENTRY [ARGV...]

``ENTRY`` is ``train.py`` or ``serve.py`` at the root of the checkout and
runs exactly as ``python ENTRY ARGV`` would.  What this file adds, all of
it outside the program (the program has no way to be traced or asked for
its device memory from outside, PERF.md section 7):

- ``DIR/compiles.jsonl``: one row per program JAX compiles or loads from
  its persistent cache (``jax.monitoring``'s backend-compile event),
  stamped with ``time.time()``, so the parent can sum those seconds and
  count compilations inside the window;
- a control thread that polls ``DIR`` for commands: ``trace.json``
  (``{"dir", "seconds"}``) takes a ``jax.profiler`` trace of that length
  and answers with ``trace_done.json``; ``mem.json`` is answered with
  ``mem_done.json``, the per-device ``memory_stats()``;
- ``--preflight FILE``: before the entry point starts, a check described
  by ``FILE`` runs on the same device (see ``preflight.py``) and writes
  ``DIR/preflight.json``;
- ``DIR/exit.json`` when the entry point returns.
"""

from __future__ import annotations

import json
import os
import runpy
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write(path: str, obj) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def memory_stats() -> dict:
    import jax

    per_device = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        per_device.append({"id": d.id,
                           "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                           "bytes_in_use": stats.get("bytes_in_use"),
                           "bytes_limit": stats.get("bytes_limit")})
    first = jax.local_devices()[0]
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(jax.devices()), "devices": per_device}


def _control_loop(ctl: str) -> None:
    import jax

    while True:
        time.sleep(0.05)
        cmd = os.path.join(ctl, "trace.json")
        if os.path.exists(cmd):
            with open(cmd) as f:
                req = json.load(f)
            os.remove(cmd)
            t0 = time.time()
            jax.profiler.start_trace(req["dir"])
            t1 = time.time()
            time.sleep(req["seconds"])
            t2 = time.time()
            jax.profiler.stop_trace()
            _write(os.path.join(ctl, "trace_done.json"),
                   {"t_begin": t1, "t_end": t2, "start_s": t1 - t0,
                    "stop_s": time.time() - t2})
        cmd = os.path.join(ctl, "mem.json")
        if os.path.exists(cmd):
            os.remove(cmd)
            _write(os.path.join(ctl, "mem_done.json"), memory_stats())


def main() -> None:
    args = sys.argv[1:]
    ctl = preflight = None
    while args and args[0].startswith("--"):
        flag = args.pop(0)
        if flag == "--ctl":
            ctl = args.pop(0)
        elif flag == "--preflight":
            preflight = args.pop(0)
        else:
            raise SystemExit(f"child.py: unknown flag {flag}")
    if ctl is None or not args:
        raise SystemExit(__doc__)
    entry, argv = args[0], args[1:]
    os.makedirs(ctl, exist_ok=True)
    os.chdir(ROOT)
    sys.path.insert(0, ROOT)

    import jax.monitoring

    events = open(os.path.join(ctl, "compiles.jsonl"), "a", buffering=1)
    lock = threading.Lock()

    def on_duration(name: str, secs: float, **kw) -> None:
        # one per program compiled or loaded from the persistent cache
        if name.endswith("backend_compile_duration"):
            with lock:
                events.write(json.dumps(
                    {"t": time.time(), "event": name, "secs": secs}) + "\n")

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    threading.Thread(target=_control_loop, args=(ctl,), daemon=True,
                     name="bench-control").start()
    if preflight:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import preflight as preflight_mod

        with open(preflight) as f:
            _write(os.path.join(ctl, "preflight.json"),
                   preflight_mod.run(json.load(f)))
    sys.argv = [os.path.join(ROOT, entry), *argv]
    code = 0
    try:
        runpy.run_path(sys.argv[0], run_name="__main__")
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else (1 if e.code else 0)
        if code:
            print(f"child.py: {entry} exited: {e.code}", file=sys.stderr)
    finally:
        _write(os.path.join(ctl, "exit.json"),
               {"code": code, "t": time.time(), "memory": memory_stats()})
    sys.exit(code)


if __name__ == "__main__":
    main()
