"""Shared plumbing of the benchmark: manifest and data-file lookup, the
child process that holds the chip, and its instruments' files.

The parent (``run.py`` and everything it imports) never initialises a JAX
backend other than the CPU's: one process uses the chip, and that is the
child started here through the program's own entry point.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import signal
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, "bench_out")
#: What the caller's environment said before ``run.py`` pinned the parent
#: to the CPU; the child gets this back, so it finds the chip.
CALLER_JAX_PLATFORMS = os.environ.get("JAX_PLATFORMS")


class BenchError(Exception):
    """The run cannot produce a result: exit non-zero, print no line."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_file(roots: list[str], sub: str, name: str, ext: str) -> str:
    """``<root>/<sub>/<name><ext>`` in the first root that has it: a later
    PR (or a rehearsal under ``tests/``) adds a file, never edits one."""
    for root in roots:
        path = os.path.join(root, sub, name + ext)
        if os.path.exists(path):
            return path
    raise BenchError(f"no {sub}/{name}{ext} under {roots}")


def load_module(path: str):
    name = "bench_" + os.path.basename(path)[:-3].replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def child_env(out: str) -> dict:
    """Environment of the child.  The compile cache sits at a fixed path
    inside the checkout (the path is part of the cache key) unless the
    machine came with ``JAX_COMPILATION_CACHE_DIR`` set; with the minimum
    compile time at 0 the hundreds of sub-second programs of start-up are
    served from it too on every run after the first."""
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    env.pop("JAX_PLATFORMS", None)
    if CALLER_JAX_PLATFORMS is not None:
        env["JAX_PLATFORMS"] = CALLER_JAX_PLATFORMS
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(ROOT, ".jax_cache"))
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    env["TPU_LOG_DIR"] = os.path.join(out, "tpu_logs")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def split_cpus() -> tuple[set[int], set[int]]:
    """(generator's CPUs, child's CPUs): the generator keeps the last core
    to itself when there are at least four, so the server's threads and
    the tracer do not delay its sends."""
    cpus = sorted(os.sched_getaffinity(0))
    if len(cpus) < 4:
        return set(cpus), set(cpus)
    return {cpus[-1]}, set(cpus[:-1])


class Child:
    """``python benchmark/child.py --ctl <ctl> <entry> <argv>``, its output
    in ``<out>/child.log`` (stderr) and ``<out>/child.out`` (stdout)."""

    def __init__(self, out: str, entry: str, argv: list[str],
                 preflight: dict | None = None, env: dict | None = None,
                 cpus: set[int] | None = None):
        self.out = out
        self.ctl = os.path.join(out, "ctl")
        os.makedirs(self.ctl, exist_ok=True)
        if not os.path.exists(os.path.join(ROOT, entry)):
            raise BenchError(f"{entry} is not in this checkout: there is "
                             "no program to measure")
        cmd = [sys.executable, os.path.join(BENCH, "child.py"),
               "--ctl", self.ctl]
        if preflight is not None:
            path = os.path.join(self.ctl, "preflight_spec.json")
            with open(path, "w") as f:
                json.dump(preflight, f)
            cmd += ["--preflight", path]
        cmd += [entry, *argv]
        self.stdout_path = os.path.join(out, "child.out")
        self.log_path = os.path.join(out, "child.log")
        self._stdout = open(self.stdout_path, "w")
        self._log = open(self.log_path, "w")
        self.t_start = time.time()
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, stdout=self._stdout, stderr=self._log,
            env=env or child_env(out), start_new_session=True)
        if cpus:
            os.sched_setaffinity(self.proc.pid, cpus)

    def alive(self) -> bool:
        return self.proc.poll() is None

    def tail(self, n: int = 30) -> str:
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])

    def require_alive(self, what: str) -> None:
        if not self.alive():
            raise BenchError(f"child exited with {self.proc.returncode} "
                             f"while {what}\n{self.tail()}")

    def send(self, name: str, payload: dict) -> None:
        """Hand ``<ctl>/<name>.json`` to the child's control thread."""
        tmp = os.path.join(self.ctl, name + ".tmp")
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, os.path.join(self.ctl, name + ".json"))

    def command(self, name: str, payload: dict, timeout: float) -> dict:
        """``send`` and wait for the answer, ``<name>_done.json``."""
        done = os.path.join(self.ctl, name + "_done.json")
        if os.path.exists(done):
            os.remove(done)
        self.send(name, payload)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if os.path.exists(done):
                return load_json(done)
            self.require_alive(f"answering {name}")
            time.sleep(0.02)
        raise BenchError(f"child did not answer {name} in {timeout}s")

    def result(self, name: str, wait_s: float = 0.0):
        """``<ctl>/<name>.json`` if the child has written it, waiting up
        to ``wait_s`` for it; else None."""
        path = os.path.join(self.ctl, name + ".json")
        deadline = time.monotonic() + wait_s
        while not os.path.exists(path):
            if time.monotonic() >= deadline or not self.alive():
                return None
            time.sleep(0.1)
        return load_json(path)

    def stop(self, grace: float = 15.0) -> None:
        """SIGTERM, wait, SIGKILL the whole session; always waits."""
        if self.alive():
            try:
                os.killpg(self.proc.pid, signal.SIGTERM)
            except ProcessLookupError:
                pass
            try:
                self.proc.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()
        self._stdout.close()
        self._log.close()


def require_device(device: dict, config: dict, chips: int) -> None:
    """A measurement needs the chip; only a rehearsal configuration (one
    that says ``"rehearsal": true``) may run elsewhere, and its line then
    names the platform it ran on."""
    if config.get("rehearsal"):
        return
    if device.get("platform") != "tpu":
        raise BenchError(f"no accelerator: the child runs on {device}")
    if device.get("count", 0) < chips:
        raise BenchError(f"cell needs {chips} chip(s), found {device}")


def memory_peak(mem: dict | None) -> int | None:
    if not mem:
        return None
    peaks = [d["peak_bytes_in_use"] for d in mem["devices"]
             if d.get("peak_bytes_in_use") is not None]
    return max(peaks) if peaks else None


def fresh_dir(path: str) -> str:
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def read_jsonl(path: str) -> list[dict]:
    if not os.path.exists(path):
        return []
    rows = []
    with open(path, errors="replace") as f:
        for line in f:
            line = line.strip()
            if line.endswith("}"):      # a killed writer may leave half a row
                try:
                    rows.append(json.loads(line))
                except json.JSONDecodeError:
                    pass
    return rows
