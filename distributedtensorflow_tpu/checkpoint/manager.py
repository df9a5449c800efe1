"""Sharded async checkpointing with rotation.

Replaces the reference's ``tf.train.Checkpoint`` + ``CheckpointManager`` +
async helper (SURVEY.md §5.4: ``checkpoint.py:2061``,
``checkpoint_management.py:519``, ``async_checkpoint_helper.py``) with Orbax:

- saves are *sharded* — each host writes only its shards, with sharding
  metadata alongside (the ``ShardedVariable`` save-as-one-logical-tensor
  behavior, generalized to any NamedSharding);
- async by default — the train loop keeps running while the previous step's
  state flushes;
- restore takes the *target* state (with its shardings) and lays the saved
  tensors out accordingly, so restoring to a different mesh/topology works
  (elastic re-sharding on restore — SURVEY.md §5.4 build requirement);
- integrity-checked (resilience tentpole): every save writes a per-array
  checksum manifest sidecar (``integrity.py``; atomic temp-file + rename),
  and :meth:`restore_latest` *verifies* the restored bytes against it,
  transparently falling back to the newest checkpoint that verifies when
  the latest is truncated or corrupt — recording a ``checkpoint_corrupt``
  flight event and a ``checkpoint_verify_failures_total`` counter per
  rejected step.
"""

from __future__ import annotations

import logging
import os
from typing import Any

import orbax.checkpoint as ocp

from .. import obs
from ..train.state import TrainState
from . import integrity
from .integrity import CheckpointCorruptError

logger = logging.getLogger("distributedtensorflow_tpu")

# Registry metrics (obs/): checkpoint IO health.  The save gauge records
# the BLOCKING portion only — with async_save the Orbax commit continues in
# the background and the train loop is already running again.
_M_SAVES = obs.counter("checkpoint_saves_total", "checkpoint saves accepted")
_M_RESTORES = obs.counter("checkpoint_restores_total", "checkpoint restores")
_M_SAVE_S = obs.gauge(
    "checkpoint_last_save_blocking_s", "blocking seconds of the last save call"
)
_M_VERIFY_FAILURES = obs.counter(
    "checkpoint_verify_failures_total",
    "checkpoints rejected at restore (truncated, corrupt, or checksum "
    "mismatch) before falling back to an older verified step",
)

PyTree = Any


def _is_chief() -> bool:
    import jax  # noqa: PLC0415 — deferred: keep module import light

    return jax.process_index() == 0


def _as_tree(state: TrainState) -> dict:
    return {
        "step": state.step,
        "params": state.params,
        "model_state": state.model_state,
        "opt_state": state.opt_state,
    }


class CheckpointManager:
    """Rotating, async, sharded checkpoint manager."""

    def __init__(
        self,
        directory: str,
        *,
        max_to_keep: int = 3,
        async_save: bool = True,
        save_interval_steps: int = 1,
        best_metric: str | None = None,
        best_mode: str = "max",
        integrity_manifest: bool = True,
    ):
        """``best_metric`` switches retention from keep-latest to keep-best:
        rotation keeps the ``max_to_keep`` checkpoints with the best value
        of that metric (pass metrics to :meth:`save`), ``best_mode``
        "max"/"min" — the keep-best policy of the reference's
        CheckpointManager idiom.  ``integrity_manifest=False`` skips the
        per-array checksum sidecar (one host pass over the state per save)
        — restores then verify only via the storage layer's own errors."""
        self._directory = str(directory)
        self._integrity = integrity_manifest
        #: Set by :meth:`restore_latest`: ``{"restored_step": int | None,
        #: "rejected": [{"step", "reason"}, ...]}`` — how the last restore
        #: went (the supervisor pairs chaos-injected truncations with the
        #: fallback that recovered from them through this).
        self.last_restore_report: dict | None = None
        self._mgr = ocp.CheckpointManager(
            directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                enable_async_checkpointing=async_save,
                save_interval_steps=save_interval_steps,
                best_fn=(
                    (lambda m: float(m[best_metric])) if best_metric else None
                ),
                best_mode=best_mode,
                create=True,
            ),
            # Pre-register the state's handler so a FRESH process (the
            # restore side of a restart) can answer item_metadata() —
            # the ZeRO-degree probe — before its first save/restore;
            # without it orbax only learns the handler lazily from the
            # first args=StandardSave/StandardRestore call.
            item_handlers=ocp.StandardCheckpointHandler(),
        )
        self._best_metric = best_metric
        self._best_mode = best_mode

    @property
    def best_metric(self) -> str | None:
        """Metric name driving keep-best retention (None = keep-latest)."""
        return self._best_metric

    @property
    def best_mode(self) -> str:
        return self._best_mode

    def save(self, step: int, state: TrainState, *, force: bool = False,
             metrics: dict | None = None) -> bool:
        if step in self._mgr.all_steps():
            return False  # already saved (e.g. periodic save + final save)
        if self._best_metric and not (metrics and self._best_metric in metrics):
            raise ValueError(
                f"best_metric={self._best_metric!r} retention needs "
                f"metrics[{self._best_metric!r}] passed to save()"
            )
        obs.record_event("checkpoint_begin", step=step)
        with obs.span("checkpoint_save") as sp:
            saved = self._mgr.save(
                step, args=ocp.args.StandardSave(_as_tree(state)), force=force,
                metrics=(
                    {k: float(v) for k, v in metrics.items()}
                    if metrics else None
                ),
            )
        obs.record_event(
            "checkpoint_end", step=step, saved=bool(saved),
            blocking_s=round(sp.dur_s, 4),
        )
        if saved:
            _M_SAVES.inc()
            _M_SAVE_S.set(sp.dur_s)
            # Goodput lost-work anchor: a resume is measured against the
            # newest save at or before its restored step.
            obs.goodput.note_checkpoint(step)
            if self._integrity and _is_chief():
                # Chief-only END TO END: the checksum pass fetches the
                # whole state to host, so non-chief hosts must not pay it
                # just to have write_manifest discard the result (and
                # prune must not race N hosts' listdir+unlink on shared
                # storage).  Checksums come from the IN-MEMORY state, so
                # the sidecar never races the (possibly async) storage
                # commit; the write itself is atomic and must never fail
                # the save.
                try:
                    integrity.write_manifest(
                        self._directory, step,
                        integrity.tree_checksums(_as_tree(state)),
                    )
                    integrity.prune_manifests(
                        self._directory, self._mgr.all_steps()
                    )
                except Exception:
                    logger.exception(
                        "checkpoint manifest write failed for step %d "
                        "(step stays restorable, just unverified)", step,
                    )
            logger.info("checkpoint saved at step %d", step)
        return saved

    def best_step(self) -> int | None:
        """Step of the best checkpoint under the best_metric policy."""
        return self._mgr.best_step()

    def restore_latest(self, target: TrainState,
                       *, before_step: int | None = None) -> TrainState | None:
        """Restore the newest *verified* checkpoint into ``target``.

        Returns None when no usable checkpoint exists (cold start, or every
        candidate failed verification).  ``target`` may live on a different
        mesh than the writer used — Orbax reshards on read
        (restore-to-different-topology).

        Integrity fallback (resilience tentpole): a step whose restore
        raises (truncated/torn files) or whose restored bytes mismatch the
        save-time checksum manifest is *rejected* — ``checkpoint_corrupt``
        flight event + ``checkpoint_verify_failures_total`` counter — and
        the next-newest step is tried, so one bad write never strands a
        run that has older good checkpoints.  ``before_step`` restricts
        candidates to strictly earlier steps (the supervisor's NaN-recovery
        path: resume from *before* the poisoned state, not the stop-save).
        """
        steps = sorted(self.all_steps(), reverse=True)
        if before_step is not None:
            steps = [s for s in steps if s < before_step]
        rejected: list[dict] = []
        result: TrainState | None = None
        good_step: int | None = None
        for step in steps:
            try:
                result = self._restore_verified(step, target)
                good_step = step
                break
            except CheckpointCorruptError as e:
                reason = str(e)[:300]
                rejected.append({"step": step, "reason": reason})
                _M_VERIFY_FAILURES.inc()
                obs.record_event("checkpoint_corrupt", step=step,
                                 reason=reason)
                logger.error(
                    "checkpoint step %d failed verification (%s); falling "
                    "back to the next-newest checkpoint", step, reason,
                )
        self.last_restore_report = {
            "restored_step": good_step,
            "rejected": rejected,
        }
        if result is not None:
            if rejected:
                logger.warning(
                    "restored VERIFIED checkpoint step %d after rejecting "
                    "%d corrupt step(s): %s", good_step, len(rejected),
                    [r["step"] for r in rejected],
                )
        elif rejected:
            logger.error(
                "no verifiable checkpoint left (rejected %s); cold start",
                [r["step"] for r in rejected],
            )
        return result

    def _restore_verified(self, step: int, target: TrainState) -> TrainState:
        """Restore ``step`` and verify it against its manifest; raises
        :class:`CheckpointCorruptError` on a failed restore or a checksum
        mismatch.  A step without a manifest (legacy dirs, or saves with
        ``integrity_manifest=False``) restores unverified."""
        with obs.span("checkpoint_restore"):
            try:
                restored = self._mgr.restore(
                    step, args=ocp.args.StandardRestore(_as_tree(target))
                )
            except Exception as e:
                raise CheckpointCorruptError(
                    f"restore raised {type(e).__name__}: {str(e)[:200]}"
                ) from e
        result = target.replace(
            step=restored["step"],
            params=restored["params"],
            model_state=restored["model_state"],
            opt_state=restored["opt_state"],
        )
        manifest = integrity.load_manifest(self._directory, step)
        if manifest is not None:
            problems = integrity.verify_tree(_as_tree(result), manifest)
            if problems:
                shown = "; ".join(problems[:3])
                if len(problems) > 3:
                    shown += f"; ... {len(problems) - 3} more"
                raise CheckpointCorruptError(shown)
        else:
            logger.info(
                "checkpoint step %d has no integrity manifest; restoring "
                "unverified", step,
            )
        _M_RESTORES.inc()
        obs.goodput.note_restore(step)
        logger.info("restored checkpoint step %d", step)
        return result

    def restore(self, step: int, target: TrainState) -> TrainState:
        """Restore a specific step into ``target``'s shardings.

        Verifies against the step's checksum manifest when one exists;
        raises :class:`CheckpointCorruptError` (no fallback — the caller
        asked for THIS step) on a failed restore or mismatch.  A
        ``FileNotFoundError`` re-raises AS ITSELF: a polling reader (the
        sidecar evaluator) racing a live writer's multi-file finalize
        sees missing files, which is "not fully visible yet" — an OSError
        its retry loop already handles — not corruption, and must not
        count into ``checkpoint_verify_failures_total``.
        """
        try:
            return self._restore_verified(step, target)
        except CheckpointCorruptError as e:
            if isinstance(e.__cause__, FileNotFoundError):
                raise e.__cause__
            _M_VERIFY_FAILURES.inc()
            obs.record_event("checkpoint_corrupt", step=step,
                             reason=str(e)[:300])
            raise

    def item_metadata(self, step: int) -> dict:
        """Array metadata (shapes/dtypes, no tensor I/O) of a saved step's
        tree, as the nested dict orbax's tree metadata holds — the probe
        :func:`~..parallel.zero.saved_opt_layout` uses to detect which
        ZeRO degree a checkpoint's optimizer state was saved at before
        building a restore target."""
        return self._mgr.item_metadata(step).tree

    def latest_step(self) -> int | None:
        steps = self.all_steps()
        return max(steps) if steps else None

    def all_steps(self) -> list[int]:
        """Committed steps only.  Belt-and-braces over orbax's own
        tmp-dir filtering: a step dir missing its ``_CHECKPOINT_METADATA``
        commit marker (a half-written dir left by a kill on a filesystem
        without atomic rename) is treated as not-a-checkpoint, so a
        preemption mid-save can never make a torn "latest" step visible."""
        steps = []
        for s in self._mgr.all_steps():
            d = os.path.join(self._directory, str(int(s)))
            if os.path.isdir(d) and not os.path.exists(
                os.path.join(d, "_CHECKPOINT_METADATA")
            ):
                logger.warning(
                    "ignoring half-written checkpoint dir %s (no commit "
                    "marker)", d,
                )
                continue
            steps.append(int(s))
        return steps

    def reload(self) -> None:
        """Re-scan the directory for checkpoints written by OTHER processes
        (Orbax caches the step list; a sidecar evaluator polling a training
        job's directory must reload before ``latest_step``)."""
        self._mgr.reload()

    def wait(self) -> None:
        with obs.span("checkpoint_wait"):
            self._mgr.wait_until_finished()

    def close(self) -> None:
        self._mgr.close()
