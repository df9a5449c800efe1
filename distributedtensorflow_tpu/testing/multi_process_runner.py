"""Multi-process cluster runner for distributed tests.

Replaces the reference's ``MultiProcessRunner``
(``tf/python/distribute/multi_process_runner.py:107``, SURVEY.md §4): forks
one OS process per cluster task, wires the cluster env (here: the JAX
coordination-service env instead of ``TF_CONFIG`` — though callers may pass
any env, including ``TF_CONFIG``, to exercise the resolver chain), collects
per-task return values, enforces timeouts, and injects failures by killing
tasks mid-run (``SubprocessTimeoutError`` :1173,
``UnexpectedSubprocessExitError`` :1191 equivalents).

Children run on the CPU platform so multi-host tests need no hardware —
the JAX analogue of the reference's in-process fake clusters
(``multi_worker_test_base.py:123``); real collectives still run (Gloo
cross-process), so this tests the actual distributed runtime, not a mock.
"""

from __future__ import annotations

import dataclasses
import multiprocessing as mp
import os
import queue as queue_lib
import socket
import time
from typing import Any, Callable, Mapping, Sequence

_mp = mp.get_context("spawn")  # children must re-init JAX from scratch


class SubprocessTimeoutError(RuntimeError):
    """join() timed out; stragglers were killed."""

    def __init__(self, msg: str, result: "MultiProcessResult"):
        super().__init__(msg)
        self.result = result


class UnexpectedSubprocessExitError(RuntimeError):
    """A task exited nonzero (and was not an expected kill)."""

    def __init__(self, msg: str, result: "MultiProcessResult"):
        super().__init__(msg)
        self.result = result


@dataclasses.dataclass
class MultiProcessResult:
    """Per-task outcomes.

    ``return_values[i]`` holds task i's return value (missing if it died or
    raised); ``failures[i]`` holds the ``repr`` of the exception a failed
    task raised (missing if it succeeded or was killed before reporting).
    """

    return_values: dict[int, Any]
    failures: dict[int, str]
    exit_codes: dict[int, int | None]


_handed_out_ports: set[int] = set()


def pick_unused_port() -> int:
    """Pick a free localhost port, never repeating within this process.

    The socket closes before the caller binds the port, so an unrelated
    process could still steal it (inherent to port-picking); the dedupe set
    closes the much more likely race of two consecutive calls getting the
    same ephemeral port back from the kernel.
    """
    while True:
        with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("localhost", 0))
            port = s.getsockname()[1]
        if port not in _handed_out_ports:
            _handed_out_ports.add(port)
            return port


def _child_main(
    fn: Callable,
    task_id: int,
    num_processes: int,
    env: Mapping[str, str],
    init_distributed: bool,
    args: tuple,
    kwargs: dict,
    result_queue,
) -> None:
    # Env must be in place before JAX initializes a backend in this process.
    # The platform is forced (default: cpu) — the parent may hold a TPU
    # that children must not try to take: a chip belongs to one process.
    os.environ.update(env)
    os.environ["JAX_PLATFORMS"] = env.get("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
    try:
        if init_distributed:
            from ..parallel import bootstrap

            bootstrap.initialize()
        value = fn(task_id, *args, **kwargs)
        result_queue.put((task_id, True, value))
    except BaseException as e:  # noqa: BLE001 — reported to the parent
        result_queue.put((task_id, False, repr(e)))
        raise


class MultiProcessRunner:
    """Run ``fn(task_id, *args)`` in ``num_processes`` cluster tasks.

    By default each child calls ``bootstrap.initialize()`` — resolving the
    cluster from the env this runner wrote (or any env the caller injected),
    which exercises the real resolver chain + coordination service.
    """

    def __init__(
        self,
        fn: Callable,
        num_processes: int,
        *,
        args: tuple = (),
        kwargs: dict | None = None,
        env: Mapping[str, str] | None = None,
        per_task_env: Sequence[Mapping[str, str]] | None = None,
        init_distributed: bool = True,
        timeout: float = 300.0,
    ):
        self._fn = fn
        self._n = num_processes
        self._args = args
        self._kwargs = kwargs or {}
        self._timeout = timeout
        self._queue = _mp.Queue()
        self._expected_kills: set[int] = set()
        port = pick_unused_port()
        base_env = {
            "JAX_COORDINATOR_ADDRESS": f"localhost:{port}",
            "JAX_NUM_PROCESSES": str(num_processes),
        }
        base_env.update(env or {})
        self._procs: list[mp.Process] = []
        for i in range(num_processes):
            child_env = dict(base_env, JAX_PROCESS_ID=str(i))
            if per_task_env:
                child_env.update(per_task_env[i])
            self._procs.append(
                _mp.Process(
                    target=_child_main,
                    args=(fn, i, num_processes, child_env, init_distributed,
                          self._args, self._kwargs, self._queue),
                    name=f"cluster-task-{i}",
                )
            )

    def start(self) -> "MultiProcessRunner":
        for p in self._procs:
            p.start()
        return self

    def terminate(self, task_id: int, *, expected: bool = True) -> None:
        """Fault injection: SIGKILL a task (reference process-kill path)."""
        if expected:
            self._expected_kills.add(task_id)
        self._procs[task_id].kill()

    def join(self, timeout: float | None = None) -> MultiProcessResult:
        timeout = self._timeout if timeout is None else timeout
        deadline = time.monotonic() + timeout
        values: dict[int, Any] = {}
        failures: dict[int, str] = {}
        # Drain while waiting: a child whose return value exceeds the queue's
        # pipe buffer blocks in its feeder thread until the parent reads, so
        # joining before draining would deadlock (then falsely time out).
        while (
            any(p.is_alive() for p in self._procs)
            and time.monotonic() < deadline
        ):
            self._drain(values, failures, wait=0.05)
        for p in self._procs:
            p.join(max(0.0, deadline - time.monotonic()))
        timed_out = [p for p in self._procs if p.is_alive()]
        for p in timed_out:
            p.kill()
            p.join(10)
        self._drain(values, failures)
        result = MultiProcessResult(
            return_values=values,
            failures=failures,
            exit_codes={i: p.exitcode for i, p in enumerate(self._procs)},
        )
        if timed_out:
            raise SubprocessTimeoutError(
                f"tasks {[p.name for p in timed_out]} timed out after "
                f"{timeout}s", result,
            )
        bad = {
            i: code
            for i, code in result.exit_codes.items()
            if code != 0 and i not in self._expected_kills
        }
        if bad:
            raise UnexpectedSubprocessExitError(
                f"tasks exited nonzero: {bad}; failures: {failures}", result,
            )
        return result

    def _drain(
        self,
        values: dict[int, Any],
        failures: dict[int, str],
        wait: float = 0.0,
    ) -> None:
        block = wait > 0
        while True:
            try:
                task_id, ok, value = self._queue.get(block, wait or None)
            except queue_lib.Empty:
                return
            block = False  # only the first read waits
            if ok:
                values[task_id] = value
            else:
                failures[task_id] = value


def run(
    fn: Callable,
    num_processes: int,
    *,
    args: tuple = (),
    timeout: float = 300.0,
    env: Mapping[str, str] | None = None,
    per_task_env: Sequence[Mapping[str, str]] | None = None,
    init_distributed: bool = True,
) -> MultiProcessResult:
    """One-shot convenience (reference ``multi_process_runner.run``, :1245)."""
    return MultiProcessRunner(
        fn, num_processes, args=args, timeout=timeout, env=env,
        per_task_env=per_task_env, init_distributed=init_distributed,
    ).start().join()
