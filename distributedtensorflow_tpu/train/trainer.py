"""Trainer: the fit-loop around the compiled SPMD step.

Replaces the reference's Keras ``Model.fit`` layer (SURVEY.md §2.3 "Keras
trainer"): step loop, periodic logging/eval, throughput counters, checkpoint
hooks.  Deliberately thin — all the distribution lives in the compiled step;
the loop is plain host Python and identical on 1 chip or a pod.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..data.adaptive import input_record_fields
from ..utils.metrics import MetricWriter, ThroughputMeter
from .state import TrainState

logger = logging.getLogger("distributedtensorflow_tpu")

PyTree = Any


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int
    log_every: int = 50
    eval_every: int = 0  # 0 = no eval
    eval_steps: int = 10
    checkpoint_every: int = 0  # 0 = no checkpointing
    #: Optimizer steps bundled into one dispatch (Keras steps_per_execution
    #: analogue).  > 1 requires a make_multi_train_step-built train_step;
    #: hooks fire on period boundary-crossings with up-to-k-step latency.
    steps_per_call: int = 1
    #: The input iterator already yields (steps_per_call, B, ...) bundles
    #: (data.device_put_bundle / Prefetcher(bundle=k)).  REQUIRED for
    #: multi-host steps_per_call: stacking k already-placed global arrays
    #: host-side is impossible, and the trainer's own stacking is only
    #: correct for host-numpy batches.
    #:
    #: Tail semantics: a SHORT trailing bundle (< steps_per_call) is
    #: trained as a shrunk dispatch (no data discarded).  A bundle LONGER
    #: than the steps remaining before total_steps has its excess sliced
    #: off — those batches are consumed from the stream but never trained,
    #: so a resume whose fast-forward assumes one consumed batch per
    #: optimizer step can sit up to steps_per_call-1 batches ahead of the
    #: per-step-equivalent position at that final boundary.  Keep
    #: total_steps a multiple of steps_per_call to avoid the drift.
    input_prebundled: bool = False
    global_batch_size: int = 0
    logdir: str | None = None
    # Profiling window (SURVEY.md §5.1): capture a jax.profiler trace of
    # steps [profile_start, profile_start + profile_steps) into profile_dir.
    # Routed through the reactive CaptureEngine (obs.capture) as its
    # "static" trigger — one capture code path for static, triggered, and
    # on-demand (/profilez) windows.
    profile_dir: str | None = None
    profile_start: int = 10
    profile_steps: int = 5
    # Reactive profiling (obs.CaptureEngine): arm a jax.profiler capture
    # of the next profile_steps steps the moment the anomaly detector
    # flags a step-time regression, or — multi-host — the cross-host
    # t_step spread blows past capture_spread_factor× the median.  Every
    # capture writes <logdir>/captures/<id>/ plus a manifest row in
    # <logdir>/captures.jsonl, emits capture_begin/capture_end flight
    # events, and books its overhead into the goodput profile_capture
    # bucket.  max_captures bounds the per-run artifact budget;
    # capture_cooldown_s spaces triggered captures (manual /profilez
    # requests skip the cooldown but not the budget).
    auto_profile: bool = False
    max_captures: int = 8
    capture_cooldown_s: float = 120.0
    capture_spread_factor: float = 3.0
    # Weight-update sharding (parallel/zero.py): informational — the
    # sharding itself is compiled into the train step at state-creation
    # time.  zero_stage > 0 stamps the mode into every metric record and
    # /statusz so run_report can attribute the optimizer-state-bytes
    # numbers to the mode that produced them.
    zero_stage: int = 0
    # Quantized compute (ops/quant.py): informational — the mode is
    # compiled into the model at workload-build time.  Anything but
    # "none" stamps ``quant_mode`` into every metric record (a string
    # field; check_metrics_schema knows the set) so run_report's
    # step-time section can attribute throughput to the mode.
    quant: str = "none"
    # Collective-matmul overlap (parallel/overlap.py): informational —
    # the bucketed backward-pass gradient sync is compiled into the step.
    # buckets > 0 stamps ``overlap_buckets`` / ``overlap_coverage``
    # (fraction of parameter bytes whose gradient sync is issued inside
    # the backward) into every metric record.
    overlap_buckets: int = 0
    overlap_coverage: float = 0.0
    # Pipeline parallelism (parallel/pipeline.py): informational — the
    # schedule is compiled into the workload loss at build time.  stages
    # > 0 stamps ``pipeline_schedule`` (a string field, like quant_mode),
    # ``pipeline_stages``/``pipeline_microbatches``/``pipeline_virtual``
    # and the schedule's predicted ``pipeline_bubble`` into every metric
    # record, so run_report's pipeline section can attribute step time to
    # the schedule that produced it.
    pipeline_schedule: str = "none"
    pipeline_stages: int = 0
    pipeline_microbatches: int = 0
    pipeline_virtual: int = 1
    pipeline_bubble: float = 0.0
    # Hang watchdog (SURVEY.md §5.2): dump all thread stacks if no step
    # completes for this many seconds.  0 disables.
    watchdog_timeout: float = 0.0
    # Accuracy gate (BASELINE.json "top-1 parity" pattern): stop as soon as
    # eval metric `target_metric` reaches `target_value` (``target_mode``
    # "max": metric >= value; "min": metric <= value, for losses).
    # Needs eval_every > 0 and an eval_fn.
    target_metric: str | None = None
    target_value: float | None = None
    target_mode: str = "max"
    # Telemetry (obs/): span tracing writes <logdir>/trace.jsonl and feeds
    # the per-step breakdown fields (t_data/t_step/f_data/...) into every
    # train record; the registry snapshot rides the same record and a
    # Prometheus text snapshot lands at <logdir>/metrics.prom.
    trace: bool = True
    # Per-chip model FLOPs per optimizer step — enables the mfu fields in
    # the metric stream (analytic 6·N·D-style, or train.py's
    # --estimate-flops XLA-cost estimate).  0 = no MFU accounting.
    flops_per_step: float = 0.0
    # Streaming anomaly detection (obs.AnomalyDetector) at log boundaries:
    # NaN/Inf loss, loss z-spike, step-time regression vs trailing median.
    # Anomalies log, count into the registry, land in trace.jsonl, and fan
    # out to Callback.on_anomaly.  False disables.
    anomaly_detection: bool = True
    # Live introspection server (obs.StatusServer): /healthz /statusz /varz
    # /threadz /memz /flightz on this port (0 = ephemeral; the bound port is
    # trainer.status_server.port).  None disables.  status_host defaults to
    # loopback — set "0.0.0.0" only on a trusted cluster network (/threadz
    # and /flightz leak paths and exception text; no auth).
    status_port: int | None = None
    status_host: str = "127.0.0.1"
    # Crash/hang flight recorder (obs.FlightRecorder): bounded ring of
    # structured events (step boundaries, checkpoint begin/end, anomalies,
    # preemption, compile/coordinator markers), dumped to
    # <logdir>/flight.jsonl on watchdog timeout, unhandled exception,
    # anomaly, preemption, and clean fit exit.  Installed as the process
    # default so deep layers' markers flow in.
    flight_recorder: bool = False
    flight_capacity: int = 2048
    # Training-dynamics telemetry (obs.dynamics): informational — the
    # cadence is compiled into the train step (engine dynamics_every) and
    # the DynamicsMonitor callback books the stats.  > 0 stamps the
    # cadence into /statusz so a live run advertises which steps carry
    # the per-module grad/param/update statistics.
    dynamics_every: int = 0

    def __post_init__(self):
        if self.dynamics_every < 0:
            raise ValueError(
                f"dynamics_every must be >= 0, got {self.dynamics_every}")
        # Fail a dead-on-arrival gate at setup, not after the first eval.
        if self.target_metric:
            if self.target_value is None:
                raise ValueError("target_metric set but target_value is None")
            if not self.eval_every:
                raise ValueError(
                    "target_metric set but eval_every is 0 — the gate can "
                    "never fire"
                )
        if self.target_mode not in ("max", "min"):
            raise ValueError(f"target_mode must be max|min, got {self.target_mode!r}")


class Callback:
    """Trainer extension hook — the Keras-callbacks analogue (SURVEY.md
    §5.5: "Keras callbacks drive per-epoch logging").  Subclass and
    override any subset; every method is a no-op by default.

    Granularity contract: ``on_step_end`` fires once per DISPATCH (so
    every ``steps_per_call`` optimizer steps when step-bundling is on)
    with the just-completed global step count and that step's metrics
    (device arrays — call ``float()`` to fetch).  Set
    ``trainer.stop_training = True`` from any hook to end the fit after
    the current dispatch (the Keras ``model.stop_training`` contract);
    the final checkpoint still saves.
    """

    def on_fit_begin(self, trainer: "Trainer", state) -> None: ...

    def on_step_end(self, trainer: "Trainer", step: int, state,
                    metrics: dict) -> None: ...

    def on_eval_end(self, trainer: "Trainer", step: int, state,
                    eval_metrics: dict) -> None: ...

    def on_checkpoint(self, trainer: "Trainer", step: int, state) -> None: ...

    def on_anomaly(self, trainer: "Trainer", anomaly) -> None:
        """Fires per detected :class:`~..obs.Anomaly` (NaN loss, loss
        spike, step-time regression).  Runs under the Watchdog callback
        guard: exceptions are logged, never fatal to the fit."""
        ...

    def on_fit_end(self, trainer: "Trainer", state) -> None: ...


class Trainer:
    def __init__(
        self,
        train_step: Callable[[TrainState, PyTree, jax.Array], tuple[TrainState, dict]],
        config: TrainerConfig,
        *,
        eval_step: Callable[[TrainState, PyTree], dict] | None = None,
        checkpointer=None,  # checkpoint.CheckpointManager-compatible
        preemption=None,  # checkpoint.PreemptionHandler-compatible
        callbacks: list[Callback] | None = None,
    ):
        self.train_step = train_step
        self.eval_step = eval_step
        self.config = config
        self.checkpointer = checkpointer
        self.preemption = preemption
        self.callbacks = list(callbacks or [])
        #: Callbacks set this to end the fit after the current dispatch.
        self.stop_training = False
        self.writer = MetricWriter(config.logdir)
        self.meter = ThroughputMeter(config.global_batch_size)
        #: The entry point's start-up phases (obs.PhaseTrace) with
        #: ``startup.first_step`` open, or None: the first dispatch of the
        #: first fit names its batch wait, its compile-or-load and the
        #: step's run, waited for once, closes it, and ends start-up
        #: (``startup.ready``).
        self.startup_trace: obs.PhaseTrace | None = None
        # a logged row says what JAX compiled since the row before it
        obs.install_compile_log()
        #: Span recorder for the current fit (obs.TraceRecorder); feeds the
        #: step-time breakdown and writes <logdir>/trace.jsonl.
        self.tracer: obs.TraceRecorder | None = None
        #: Streaming anomaly detector, fed at log boundaries.
        self.anomaly_detector = (
            obs.AnomalyDetector(on_anomaly=self._record_anomaly)
            if config.anomaly_detection else None
        )
        self._anomaly_counter = obs.counter(
            "anomalies_total", "anomalies detected by kind"
        )
        # Breakdown window clocks (reset at every log boundary).
        self._window_t0 = time.perf_counter()
        self._window_step0 = 0
        # Latest eval metrics, threaded into checkpointer.save() so a
        # best_metric (keep-best) manager works under the Trainer.
        self._last_eval_metrics: dict | None = None
        self._preempted = False
        #: The fit's hang watchdog while a fit is running (health surface).
        self.watchdog = None
        #: Whether the LAST fit's watchdog fired (the fit's ``finally``
        #: nulls ``self.watchdog``, so post-fit failure classification —
        #: resilience.classify_failure's data-stall-via-watchdog rule —
        #: needs the flag to outlive the watchdog object).
        self.watchdog_fired = False
        #: Set by resilience.Supervisor while it owns this trainer:
        #: {"restarts", "max_restarts", "last_failure", ...} — surfaced on
        #: /statusz so a curl of a restarting run shows the retry budget.
        self.supervisor_status: dict | None = None
        #: Set by resilience.ElasticController.on_fit_begin while one is
        #: attached: /statusz reports live resize state under "elastic".
        self.elastic = None
        # Last log-boundary record + step — what /statusz and /healthz
        # report (plain dict reads under the GIL; handlers never sync).
        self._last_record: dict = {}
        self._last_step = 0
        self._fit_t0: float | None = None
        # Checkpoint state tracked trainer-side so /statusz never does
        # storage I/O (an all_steps() listing would block on exactly the
        # stalled mount a wedged job is being probed about).
        self._ckpt_count = 0
        self._last_ckpt_step: int | None = None
        #: Flight recorder (obs.FlightRecorder), installed as the process
        #: default so markers from the engine/checkpoint/coordinator/
        #: preemption layers land in the same ring.  Chief writes
        #: <logdir>/flight.jsonl; other hosts flight.<proc>.jsonl (a hang
        #: post-mortem needs EVERY host's record, not just the chief's).
        self.flight: obs.FlightRecorder | None = None
        if config.flight_recorder:
            path = None
            if config.logdir is not None:
                idx = jax.process_index()
                name = "flight.jsonl" if idx == 0 else f"flight.{idx}.jsonl"
                path = os.path.join(config.logdir, name)
            self.flight = obs.FlightRecorder(config.flight_capacity, path)
            obs.install_recorder(self.flight)
            self.flight.install_crash_hooks()
        #: Reactive profiler (obs.CaptureEngine): owns every jax.profiler
        #: window of the fit — the static --profile-dir window, anomaly-/
        #: straggler-triggered captures (auto_profile), and on-demand
        #: /profilez requests.  Created whenever any of those paths can
        #: fire; installed as the process default so a standalone
        #: StatusServer can find it.
        self.capture: obs.CaptureEngine | None = None
        if (config.profile_dir or config.auto_profile
                or config.status_port is not None):
            self.capture = obs.CaptureEngine(
                config.logdir,
                max_captures=config.max_captures,
                cooldown_s=config.capture_cooldown_s,
                window_steps=config.profile_steps,
            )
            obs.capture.install_engine(self.capture)
        #: Live introspection server (obs.StatusServer); alive for the
        #: trainer's whole lifetime so a wedged fit can still be probed.
        self.status_server: obs.StatusServer | None = None
        if config.status_port is not None:
            # Multi-process-per-host launches would all bind the same
            # configured port: offset a fixed port by process index (so
            # every process stays probeable at a predictable address);
            # port 0 is ephemeral and needs none.  A failed bind degrades
            # to a warning — introspection must never kill the job it is
            # meant to debug.
            port = config.status_port
            if port:
                port += jax.process_index()
            try:
                self.status_server = obs.StatusServer(
                    port,
                    host=config.status_host,
                    flight=self.flight,
                    capture=self.capture,
                    status_fn=self.status,
                    health_fn=self.health,
                ).start()
            except OSError:
                logger.exception(
                    "introspection server failed to bind %s:%d; "
                    "continuing without it", config.status_host, port,
                )

    def fit(
        self,
        state: TrainState,
        train_iter: Iterable[PyTree],
        rng: jax.Array,
        *,
        eval_iter_fn: Callable[[], Iterable[PyTree]] | None = None,
    ) -> TrainState:
        cfg = self.config
        it = iter(train_iter)
        # A fresh fit clears a prior run's early-stop request (the Keras
        # Model.fit contract: stop_training resets on entry).
        self.stop_training = False
        self.watchdog_fired = False
        self.meter.start()
        self._window_t0 = time.perf_counter()
        self._window_step0 = int(state.step)
        obs.take_compiled()     # what compiled before the fit is not a row's
        self._last_step = int(state.step)
        self._fit_t0 = time.time()
        if self.flight is not None:
            self.flight.record(
                "fit_begin", step=int(state.step),
                total_steps=cfg.total_steps,
            )
        # Per-device params/optimizer-state bytes: shapes and shardings are
        # fixed for the whole fit, so the breakdown is computed ONCE here
        # and served statically (/memz "train_state" section, labeled
        # gauges, per-record fields) — the measurement that makes a
        # --zero memory win a number instead of an assertion.
        try:
            report: dict = obs.memory.state_bytes_report(
                state.params, state.opt_state
            )
            if cfg.zero_stage:
                report["zero_stage"] = cfg.zero_stage
                zero = getattr(state, "zero", None)
                if zero is not None:
                    report["zero_degree"] = zero.degree
            obs.memory.set_train_state_bytes(report)
        except Exception:
            logger.exception("train-state bytes accounting failed")
        ledger = obs.goodput.default_ledger()
        if ledger is not None:  # close the goodput `init` window
            ledger.mark_fit_begin(int(state.step))
        watchdog = None
        if cfg.watchdog_timeout > 0:
            from ..utils.watchdog import Watchdog

            watchdog = Watchdog(
                cfg.watchdog_timeout, flight_recorder=self.flight
            )
        self.watchdog = watchdog
        if cfg.trace:
            trace_path = (
                os.path.join(cfg.logdir, "trace.jsonl") if cfg.logdir else None
            )
            self.tracer = obs.TraceRecorder(trace_path).install()
        fit_exc: BaseException | None = None
        try:
            try:
                for cb in self.callbacks:
                    cb.on_fit_begin(self, state)
                state = self._fit_loop(state, it, rng, eval_iter_fn, watchdog)
            finally:
                if self.tracer is not None:
                    # Early returns (target gate, preemption, stop_training)
                    # leave the last step row open; flush it HERE so the
                    # post-loop force-checkpoint's spans land unanchored
                    # instead of inflating that step's t_wall.
                    self.tracer.end_step()
                if watchdog is not None:
                    self.watchdog_fired = watchdog.fired
                    watchdog.stop()
                    self.watchdog = None
                close = getattr(train_iter, "close", None)
                if close is not None:
                    close()
            if self.checkpointer is not None and not self._preempted:
                # Label with the step actually reached (an accuracy-gate
                # early stop must not save under the total_steps slot).  A
                # preemption exit already force-saved inside the loop.
                self.checkpointer.save(
                    int(state.step), state, force=True,
                    metrics=self._ckpt_metrics(),
                )
                self.checkpointer.wait()
                self._ckpt_count += 1
                self._last_ckpt_step = int(state.step)
            for cb in self.callbacks:
                cb.on_fit_end(self, state)
            return state
        except BaseException as e:
            # Captured explicitly, NOT via sys.exc_info() in the finally:
            # there exc_info also reports an OUTER in-flight exception
            # (fit() called inside an except block), which would stamp a
            # bogus crash verdict on a clean fit.
            fit_exc = e
            raise
        finally:
            if self.tracer is not None:
                self.tracer.uninstall()
                self.tracer.close()
                self.tracer = None
            if self.flight is not None:
                # Clean exits leave a record too; an exception unwinding
                # through here is recorded before the dump (the top-level
                # excepthook would only fire after close() uninstalls it).
                # fit_end marks CLEAN exits only — run_report's clean-exit
                # verdict keys on the last event being fit_end, so a
                # crashed fit must end on its exception event instead.
                if fit_exc is not None:
                    self.flight.record(
                        "exception", exc_type=type(fit_exc).__name__,
                        message=str(fit_exc)[:500],
                    )
                    self.flight.dump(reason=type(fit_exc).__name__)
                else:
                    self.flight.record(
                        "fit_end", step=int(state.step),
                        preempted=self._preempted,
                    )
                    self.flight.dump()
            ledger = obs.goodput.default_ledger()
            if ledger is not None:
                # Final-boundary flush (last heartbeat = this generation's
                # measured end); the entrypoint owns close(ended=...).
                ledger.heartbeat(step=self._last_step)

    def close(self) -> None:
        """Release owned resources — the metric writer, the introspection
        server, and the flight recorder's default-installation/crash hooks.

        Idempotent; ``with Trainer(...) as t: t.fit(...)`` guarantees the
        ``metrics.jsonl`` handle is released on any exit path (it used to
        leak on every non-happy path)."""
        self.writer.close()
        obs.memory.set_train_state_bytes(None)
        if self.status_server is not None:
            self.status_server.stop()
        if self.capture is not None:
            if obs.capture.default_engine() is self.capture:
                obs.capture.install_engine(None)
        if self.flight is not None:
            self.flight.uninstall_crash_hooks()
            if obs.default_recorder() is self.flight:
                obs.install_recorder(None)

    def __enter__(self) -> "Trainer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @property
    def preempted(self) -> bool:
        """Whether the last fit exited via the preemption save path."""
        return self._preempted

    def clear_preempted(self) -> None:
        """Re-arm after a supervised in-process resume (the launcher-kill
        never came — e.g. a synthetic/chaos preemption): the next fit must
        not inherit the consumed notice."""
        self._preempted = False
        if self.preemption is not None:
            reset = getattr(self.preemption, "reset", None)
            if reset is not None:
                reset()

    def _record_anomaly(self, anomaly) -> None:
        """Default anomaly sink: log, count, trace, flight-record, fan out
        to callbacks — the Watchdog on_timeout convention (never fatal to
        the fit)."""
        logger.error("anomaly: %s", anomaly.message)
        self._anomaly_counter.inc(kind=anomaly.kind)
        if self.tracer is not None:
            self.tracer.write_event({
                "kind": "anomaly", "step": anomaly.step,
                "anomaly": anomaly.kind, "message": anomaly.message,
                "value": anomaly.value,
            })
        if self.flight is not None:  # records the event AND dumps the ring
            self.flight.record_anomaly(anomaly)
        if (
            self.capture is not None
            and self.config.auto_profile
            and anomaly.kind == "step_time_regression"
        ):
            # The reactive-profiling loop: a regression arms a capture of
            # the very next steps — the slow ones, not the average ones.
            # Budget/cooldown refusals are normal on repeat anomalies.
            self.capture.request(
                "step_time_regression", reason=anomaly.message
            )
        for cb in self.callbacks:
            try:
                cb.on_anomaly(self, anomaly)
            except Exception:
                logger.exception("on_anomaly callback failed")

    def _ckpt_metrics(self, manager=None) -> dict | None:
        """Metrics to attach to a save through ``manager`` (default: the
        periodic checkpointer; the preemption handler may save through a
        DIFFERENT manager, whose keep-best key must be honored).

        A keep-best manager (``best_metric`` set) requires its metric on
        EVERY save; when eval hasn't run yet — or ran but didn't produce
        that metric (wrong eval_fn, empty eval iterator) — substitute the
        worst possible score rather than killing a long fit mid-run.
        """
        manager = manager if manager is not None else self.checkpointer
        metrics = dict(self._last_eval_metrics or {})
        best_metric = getattr(manager, "best_metric", None)
        if best_metric is not None and best_metric not in metrics:
            worst = float("-inf") if getattr(
                manager, "best_mode", "max"
            ) == "max" else float("inf")
            if self._last_eval_metrics is not None:
                logger.warning(
                    "checkpoint keep-best metric %r missing from eval "
                    "metrics %s; saving with worst-possible score",
                    best_metric, sorted(metrics),
                )
            metrics[best_metric] = worst
        return metrics or None

    def _fit_loop(self, state, it, rng, eval_iter_fn, watchdog=None):
        cfg = self.config
        start_step = int(state.step)
        # steps_per_call > 1: self.train_step is a multi-step executable
        # (engine.make_multi_train_step) consuming k stacked batches per
        # dispatch; every hook below fires on BOUNDARY CROSSINGS of its
        # period, which reduces to the classic (step+1) % every == 0 at
        # k = 1.  The final chunk clamps to the steps remaining, so
        # total_steps is always exact; hook latency (log/eval/checkpoint/
        # preemption reaction) becomes up to k steps — the same trade
        # Keras documents for steps_per_execution.
        k = max(1, cfg.steps_per_call)

        def crosses(lo, hi, every):  # does (lo, hi] contain a multiple?
            return every and (hi // every) > (lo // every)

        # Profile window is relative to THIS run's first step, so resuming
        # from a checkpoint past profile_start still produces a trace.
        profile_at = start_step + cfg.profile_start
        if cfg.profile_dir and self.capture is not None:
            # The classic static window, routed through the CaptureEngine
            # (budget/cooldown-exempt: it was explicitly configured).
            self.capture.request(
                "static", steps=cfg.profile_steps, dir=cfg.profile_dir,
                at_step=profile_at, budget=False, cooldown=False,
                reason=f"--profile-dir window at step {profile_at}",
            )
        try:
            step_i = start_step
            while step_i < cfg.total_steps:
                # Clamp the final chunk so a resume at an unaligned step or
                # a non-divisible total never overruns total_steps (the
                # shorter stack recompiles the scanned program once).
                k_eff = min(k, cfg.total_steps - step_i)
                # Capture starts BEFORE the host batch fetch/stacking so
                # the profile captures input-pipeline time (its purpose is
                # to split host from chip time).  Uses the pre-shrink
                # k_eff bound: a short prebundled tail can only shrink the
                # dispatch, which at worst opens the trace one dispatch
                # early — never skips the window.
                if self.capture is not None:
                    self.capture.maybe_start(step_i, k_eff)
                if self.tracer is not None:
                    self.tracer.begin_step(step_i + k_eff, k_eff)
                # data_wait is a plain-class span (obs.span): it must be
                # exception-transparent — StopIteration from next(it) ends
                # the fit and has to escape unchanged.
                with obs.span("data_wait"):
                    if k == 1:
                        batch = next(it)
                    elif cfg.input_prebundled:
                        batch = next(it)  # already (k', B, ...) global arrays
                        k_have = jax.tree.leaves(batch)[0].shape[0]
                        if k_have == 0:
                            raise StopIteration
                        if k_have < k_eff:
                            # Short trailing bundle: TRAIN it (shrinking this
                            # dispatch; one extra compile) rather than raising
                            # StopIteration and silently discarding up to k-1
                            # trainable batches.  The stream then surfaces its
                            # genuine end on the next next(it).
                            k_eff = k_have
                        elif k_have > k_eff:
                            # Tail: slice the REPLICATED leading step dim.
                            # Under jit (one extra tail compile) because an
                            # eager slice of a non-fully-addressable global
                            # array is illegal in multi-controller JAX.
                            batch = jax.jit(
                                lambda b: jax.tree.map(
                                    lambda x: x[:k_eff], b
                                )
                            )(batch)
                    else:
                        # Explicit loop, not a genexp: an exhausted iterator
                        # must surface as StopIteration (the k=1 behavior),
                        # not PEP-479's RuntimeError.  np.stack for host
                        # batches (keeps them uncommitted so the jit can shard
                        # them); jnp.stack only for already-device single-
                        # process arrays.
                        bundle = []
                        for _ in range(k_eff):
                            bundle.append(next(it))
                        batch = jax.tree.map(
                            lambda *xs: (
                                np.stack(xs)
                                if isinstance(xs[0], np.ndarray)
                                else jnp.stack(xs)
                            ),
                            *bundle,
                        )
                step_next = step_i + k_eff
                if self.tracer is not None:
                    # k_eff may have shrunk during the fetch (short
                    # prebundled tail); relabel the row with final values.
                    self.tracer.adjust_step(step_next, k_eff)
                startup, self.startup_trace = self.startup_trace, None
                if startup is not None:
                    startup.mark("startup.first_batch",
                                 parent="startup.first_step")
                with obs.span("train_step"):
                    state, metrics = self.train_step(state, batch, rng)
                if startup is not None:
                    # the call returns once the program is compiled (or
                    # loaded) and launched; the step itself ends when
                    # its metrics exist
                    startup.mark("startup.compile_or_load",
                                 parent="startup.first_step")
                    jax.block_until_ready(metrics)
                    startup.mark("startup.first_step_run",
                                 parent="startup.first_step")
                    startup.close("startup.first_step", step=step_next)
                    startup.ready()
                if k > 1:  # stacked (k_eff, ...) metrics; report the last
                    metrics = jax.tree.map(lambda v: v[-1], metrics)
                self.meter.update(k_eff)
                self._last_step = step_next
                if self.flight is not None:
                    # Step-boundary breadcrumb: dispatch returned (async —
                    # the device may still be computing), no metric fetch.
                    self.flight.record("step", step=step_next, k=k_eff)
                for cb in self.callbacks:
                    cb.on_step_end(self, step_next, state, metrics)
                if watchdog is not None:
                    watchdog.ping()
                if self.capture is not None:
                    # Closes the window once it has covered its steps.
                    # fetch= forces the profiled steps to actually execute
                    # before the trace closes.
                    self.capture.maybe_stop(
                        step_next,
                        fetch=lambda m=metrics: jax.tree.map(float, m),
                    )
                step_i = step_next - 1  # hooks below address the last step
                if crosses(step_next - k_eff, step_next, cfg.log_every):
                    # jax.Array fetches sync here, off the critical cadence
                    with obs.span("host_block"):
                        last_metrics = {
                            k: float(v) for k, v in metrics.items()
                        }
                    last_metrics.update(self.meter.rates())
                    # HBM + host RSS + live-array census ride every logged
                    # record; the labeled per-device gauges refresh for
                    # /varz and the metrics.prom snapshot.  One collect()
                    # feeds both — the census is O(#live arrays).
                    mem_snap = obs.memory.collect()
                    last_metrics.update(obs.memory.record_fields(mem_snap))
                    last_metrics.update(obs.memory.train_state_record_fields())
                    # live input-plane depths (adaptive prefetch / credit
                    # window) ride every logged record
                    last_metrics.update(input_record_fields())
                    obs.memory.update_registry(snapshot=mem_snap)
                    breakdown = self._window_breakdown(step_next)
                    last_metrics.update(breakdown)
                    compile_s, compiled = obs.take_compiled()
                    last_metrics["compile_s"] = compile_s
                    if compile_s:
                        last_metrics["compiled"] = compiled
                    if jax.process_count() > 1:
                        # Every host reaches this branch, so the allgather
                        # is globally consistent; chief-only would hang it.
                        agg = obs.host_aggregate({
                            "t_step": breakdown.get("t_step", 0.0),
                            "t_data": breakdown.get("t_data", 0.0),
                        })
                        last_metrics.update(agg)
                        summary = obs.straggler_summary(agg, "t_step")
                        logger.info(summary)
                        if self.capture is not None and cfg.auto_profile:
                            # Spread blowup: one host is dragging every
                            # collective — capture the evidence.  The
                            # ratio derives from the allgathered fields,
                            # identical on every host, so all hosts arm
                            # (and open their windows) consistently.
                            ratio = obs.spread_ratio(agg, "t_step")
                            if ratio >= cfg.capture_spread_factor:
                                self.capture.request(
                                    "straggler_spread",
                                    reason=f"t_step spread {ratio:.1f}x "
                                           f"median: {summary}",
                                )
                    last_metrics.update(obs.default_registry().scalars())
                    if cfg.quant and cfg.quant != "none":
                        last_metrics["quant_mode"] = cfg.quant
                    if cfg.overlap_buckets:
                        last_metrics["overlap_buckets"] = float(
                            cfg.overlap_buckets
                        )
                        last_metrics["overlap_coverage"] = float(
                            cfg.overlap_coverage
                        )
                    if cfg.pipeline_stages:
                        last_metrics["pipeline_schedule"] = (
                            cfg.pipeline_schedule
                        )
                        last_metrics["pipeline_stages"] = float(
                            cfg.pipeline_stages
                        )
                        last_metrics["pipeline_microbatches"] = float(
                            cfg.pipeline_microbatches
                        )
                        last_metrics["pipeline_virtual"] = float(
                            cfg.pipeline_virtual
                        )
                        last_metrics["pipeline_bubble"] = float(
                            cfg.pipeline_bubble
                        )
                    if self.anomaly_detector is not None:
                        self.anomaly_detector.observe(
                            step_i + 1,
                            loss=last_metrics.get("loss"),
                            step_time=breakdown.get("t_step"),
                        )
                    with obs.span("metric_write"):
                        self.writer.write(step_i + 1, last_metrics)
                    self._export_prometheus()
                    ledger = obs.goodput.default_ledger()
                    if ledger is not None:
                        # Advances the restart-detection heartbeat, updates
                        # the goodput_* registry metrics, persists
                        # goodput.json, and emits the periodic `goodput`
                        # flight event.
                        ledger.heartbeat(step=step_i + 1)
                    logger.info("step %d: %s", step_i + 1, _fmt(last_metrics))
                    self._last_record = last_metrics  # /statusz snapshot
                    if self.flight is not None:
                        self.flight.record(
                            "log", step=step_i + 1,
                            loss=last_metrics.get("loss"),
                            t_step=breakdown.get("t_step"),
                        )
                    self.meter.start()
                if (
                    self.eval_step is not None
                    and eval_iter_fn is not None
                    and crosses(step_next - k_eff, step_next, cfg.eval_every)
                ):
                    with obs.span("eval"):
                        eval_metrics = self.evaluate(state, eval_iter_fn())
                    self._last_eval_metrics = eval_metrics
                    if self.flight is not None:
                        self.flight.record("eval", step=step_i + 1)
                    self.writer.write(
                        step_i + 1,
                        {f"eval_{k}": v for k, v in eval_metrics.items()},
                    )
                    logger.info("eval @ %d: %s", step_i + 1, _fmt(eval_metrics))
                    for cb in self.callbacks:
                        cb.on_eval_end(self, step_i + 1, state, eval_metrics)
                    if watchdog is not None:  # a long eval is progress
                        watchdog.ping()
                    if cfg.target_metric and self._target_reached(
                        eval_metrics, step_i + 1
                    ):
                        return state
                if (
                    self.checkpointer is not None
                    and crosses(step_next - k_eff, step_next,
                                cfg.checkpoint_every)
                ):
                    self.checkpointer.save(
                        step_i + 1, state, metrics=self._ckpt_metrics()
                    )
                    self._ckpt_count += 1
                    self._last_ckpt_step = step_i + 1
                    for cb in self.callbacks:
                        cb.on_checkpoint(self, step_i + 1, state)
                    if watchdog is not None:  # so is a synchronous save
                        watchdog.ping()
                # Preemption check LAST so a signal landing mid-step is
                # observed at the next step boundary — every host agrees on
                # the save step (the reference's cluster-wise gossip).
                if self.preemption is not None and self.preemption.should_save(
                    step_i + 1
                ):
                    logger.warning(
                        "preemption: consistent save at step %d, stopping",
                        step_i + 1,
                    )
                    self.preemption.save_and_exit(
                        step_i + 1, state,
                        metrics=self._ckpt_metrics(self.preemption.manager),
                    )
                    self._preempted = True
                    return state
                if self.stop_training:
                    logger.info(
                        "callback requested stop at step %d", step_i + 1
                    )
                    return state
                if self.tracer is not None:
                    self.tracer.end_step()
                step_i = step_next
        finally:
            if self.capture is not None:
                # Exception mid-window, or a window past total_steps: close
                # the trace (manifest row marked aborted when incomplete)
                # and drop any armed-but-never-started request.
                self.capture.abort(self._last_step)
        if cfg.profile_dir and cfg.total_steps <= profile_at:
            logger.warning(
                "profile window never opened: run ended at step %d before "
                "profile_start step %d — lower --profile-start",
                cfg.total_steps, profile_at,
            )
        return state

    def _window_breakdown(self, step_next: int) -> dict[str, float]:
        """Per-optimizer-step time breakdown since the last log boundary.

        ``t_step`` is wall seconds per step; ``t_data`` / ``t_dispatch`` /
        ``t_host`` are the span totals (data-wait, compute dispatch, host
        metric-fetch blocking) divided by the window's step count, with
        ``f_*`` their fractions of ``t_step``.  ``t_eval`` / ``t_ckpt``
        appear when the window contained eval/checkpoint work (those hooks
        run after the log write, so their spans land in the FOLLOWING
        window — one-boundary shift, steady-state exact).  MFU fields ride
        along when ``TrainerConfig.flops_per_step`` is set
        (``obs.mfu`` accounting).
        """
        now = time.perf_counter()
        n = max(step_next - self._window_step0, 1)
        wall = max(now - self._window_t0, 1e-12)
        self._window_t0 = now
        self._window_step0 = step_next
        t_step = wall / n
        if self.tracer is None:
            # trace=False still reports wall-clock-per-step (and MFU, which
            # derives from it) — neither needs spans, and the step-time-
            # regression detector feeds on t_step.
            return {
                "t_step": t_step,
                **obs.mfu_record_fields(self.config.flops_per_step, t_step),
            }
        totals = self.tracer.drain_window()
        out = {
            "t_step": t_step,
            "t_data": totals.get("data_wait", 0.0) / n,
            "t_dispatch": totals.get("train_step", 0.0) / n,
            "t_host": totals.get("host_block", 0.0) / n,
        }
        if totals.get("eval"):
            out["t_eval"] = totals["eval"] / n
        if totals.get("checkpoint_save"):
            out["t_ckpt"] = totals["checkpoint_save"] / n
        for part in ("data", "dispatch", "host"):
            out[f"f_{part}"] = out[f"t_{part}"] / t_step
        out.update(
            obs.mfu_record_fields(self.config.flops_per_step, t_step)
        )
        return out

    def status(self) -> dict:
        """/statusz payload: run position, last logged metrics, breakdown
        fractions, straggler spread, checkpoint state.  Reads plain
        attributes only — never syncs the device, so it answers mid-hang."""
        rec = self._last_record
        out: dict = {
            "run": {
                "step": self._last_step,
                "total_steps": self.config.total_steps,
                "fit_elapsed_s": (
                    round(time.time() - self._fit_t0, 1)
                    if self._fit_t0 else None
                ),
                "preempted": self._preempted,
                "stop_requested": self.stop_training,
            },
        }
        if self.config.zero_stage:
            out["run"]["zero_stage"] = self.config.zero_stage
        if self.config.quant and self.config.quant != "none":
            out["run"]["quant"] = self.config.quant
        if self.config.overlap_buckets:
            out["run"]["overlap_buckets"] = self.config.overlap_buckets
        if self.config.dynamics_every:
            out["run"]["dynamics_every"] = self.config.dynamics_every
        if self.config.pipeline_stages:
            out["run"]["pipeline"] = {
                "schedule": self.config.pipeline_schedule,
                "stages": self.config.pipeline_stages,
                "microbatches": self.config.pipeline_microbatches,
                "virtual": self.config.pipeline_virtual,
                "bubble": round(self.config.pipeline_bubble, 4),
            }
        core = {
            k: rec[k] for k in (
                "loss", "accuracy", "steps_per_sec",
                "examples_per_sec_per_chip", "mfu", "hbm_in_use_gib",
                "hbm_peak_gib", "host_rss_gib", "live_arrays_gib",
            ) if k in rec
        }
        if core:
            out["last_log"] = core
        breakdown = {
            k: rec[k] for k in (
                "t_step", "t_data", "t_dispatch", "t_host", "t_eval",
                "t_ckpt", "f_data", "f_dispatch", "f_host",
            ) if k in rec
        }
        if breakdown:
            out["breakdown"] = breakdown
        spread = {k: v for k, v in rec.items() if "_host_" in k
                  or k.endswith("_straggler")}
        if spread:
            out["host_spread"] = spread
        if self.anomaly_detector is not None:
            out["anomalies"] = len(self.anomaly_detector.anomalies)
        wd = self.watchdog  # snapshot: fit's finally nulls it concurrently
        if wd is not None:
            out["watchdog"] = {
                "ping_age_s": round(wd.ping_age(), 1),
                "timeout_s": wd.timeout,
                "fired": wd.fired,
            }
        if self.checkpointer is not None:
            out["checkpoint"] = {
                "saves": self._ckpt_count,
                "last_saved_step": self._last_ckpt_step,
            }
        if self.supervisor_status:
            out["supervisor"] = dict(self.supervisor_status)
        if self.elastic is not None:
            out["elastic"] = self.elastic.status()
        if self.capture is not None:
            cap_state = self.capture.state()
            out["captures"] = {
                "completed": len(cap_state["captures"]),
                "budget": (
                    f"{cap_state['used']}/{cap_state['max_captures']}"
                ),
                "active": cap_state["active"] is not None,
                "armed": (cap_state["armed"] is not None
                          or cap_state["scheduled"] is not None),
            }
        if self._last_eval_metrics:
            out["last_eval"] = dict(self._last_eval_metrics)
        return out

    def health(self) -> dict:
        """/healthz payload; ``ok`` False (HTTP 503) once the watchdog has
        fired — the signal a pod-level prober keys on."""
        out: dict = {"ok": True, "last_step": self._last_step}
        wd = self.watchdog  # snapshot: fit's finally nulls it concurrently
        if wd is not None:
            out["watchdog_ping_age_s"] = round(wd.ping_age(), 1)
            out["watchdog_timeout_s"] = wd.timeout
            out["ok"] = not wd.fired
        return out

    def _export_prometheus(self) -> None:
        if self.config.logdir is None or jax.process_index() != 0:
            return
        try:
            obs.default_registry().write_prometheus(
                os.path.join(self.config.logdir, "metrics.prom")
            )
        except OSError:  # a full/readonly disk must not kill the fit
            logger.exception("prometheus snapshot write failed")

    def _target_reached(self, eval_metrics: dict, step: int) -> bool:
        cfg = self.config
        if cfg.target_metric not in eval_metrics:
            logger.warning(
                "target metric %r not in eval metrics %s; gate cannot fire",
                cfg.target_metric, sorted(eval_metrics),
            )
            return False
        value = eval_metrics[cfg.target_metric]
        hit = (
            value <= cfg.target_value
            if cfg.target_mode == "min"
            else value >= cfg.target_value
        )
        if hit:
            logger.info(
                "target reached: %s=%.4f %s %.4f at step %d; stopping",
                cfg.target_metric, value,
                "<=" if cfg.target_mode == "min" else ">=",
                cfg.target_value, step,
            )
        return hit

    def evaluate(self, state: TrainState, eval_iter: Iterable[PyTree]) -> dict:
        """Average eval metrics, weighted by per-batch example count.

        Metrics are per-example means (the loss_fn convention), so weighting
        by batch size makes a ragged final batch count exactly once per
        example instead of skewing the mean.  ``eval_steps <= 0`` means
        "the whole iterator" (dataset-wide exact eval on finite iterators).
        """
        return weighted_evaluate(
            self.eval_step, state, eval_iter, max_steps=self.config.eval_steps
        )


def device_memory_stats() -> dict[str, float]:
    """Device-0 HBM usage (GiB), for the periodic metric stream.

    Back-compat surface: the fit loop now records the fuller
    ``obs.memory.record_fields()`` (HBM + host RSS + live-array census);
    this keeps the original cheap HBM-only read — no O(#arrays) census —
    for external callers.  Backends without ``memory_stats`` (virtual
    CPU) contribute nothing.
    """
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return {}
    if not stats:
        return {}
    gib = 1 / (1024 ** 3)
    out = {}
    if "bytes_in_use" in stats:
        out["hbm_in_use_gib"] = stats["bytes_in_use"] * gib
    if "peak_bytes_in_use" in stats:
        out["hbm_peak_gib"] = stats["peak_bytes_in_use"] * gib
    return out


def weighted_evaluate(
    eval_step: Callable[[TrainState, PyTree], dict],
    state: TrainState,
    eval_iter: Iterable[PyTree],
    *,
    max_steps: int = 0,
) -> dict:
    """Batch-size-weighted metric averaging (shared by Trainer and the
    sidecar evaluator).  ``max_steps <= 0`` consumes the whole iterator."""
    sums: dict[str, float] = {}
    total_w = 0.0
    try:
        for i, batch in enumerate(eval_iter):
            if max_steps > 0 and i >= max_steps:
                break
            w = float(jax.tree.leaves(batch)[0].shape[0])
            metrics = eval_step(state, batch)
            for k, v in metrics.items():
                sums[k] = sums.get(k, 0.0) + w * float(v)
            total_w += w
    finally:
        close = getattr(eval_iter, "close", None)
        if close is not None:  # release prefetch threads/device buffers
            close()
    return {k: v / max(total_w, 1.0) for k, v in sums.items()}


def _fmt(metrics: dict) -> str:
    return " ".join(
        f"{k}={v}" if isinstance(v, str) else f"{k}={v:.4g}"
        for k, v in metrics.items()
    )
