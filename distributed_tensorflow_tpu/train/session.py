"""TrainSession — the TPU-native ``MonitoredTrainingSession``.

Capability parity with reference example.py:187-228:
  * chief semantics: only the chief writes checkpoints/summaries
    (``is_chief=(task_index == 0)``, example.py:190 — here
    ``jax.process_index() == 0`` without the str/int bug, SURVEY.md §7);
  * auto-restore of the latest checkpoint in ``checkpoint_dir`` on entry and
    periodic saves during training (MTS behavior at example.py:191);
  * the ``while not sess.should_stop():`` loop protocol (example.py:198) with
    a hook list (``StopAtStepHook`` etc., example.py:187,192).

What changed for TPU: there is no session/master and no graph — the unit of
execution is a *compiled step function* over an explicit ``TrainState``
pytree.  ``session.run_step(batch)`` invokes it and advances the step
cursor; dispatch is async (jax arrays returned un-pulled) so hooks that
don't fire never force a device sync.
"""
from __future__ import annotations

import logging
import os
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..obs import goodput as goodput_lib
from ..obs import trace as trace_lib
from ..parallel import cluster
from ..resilience import faults as faults_lib
from . import checkpoint as ckpt_lib
from . import sharded_checkpoint as sharded_lib
from .hooks import Hook

log = logging.getLogger(__name__)

__all__ = ["TrainState", "TrainSession"]


class TrainState(NamedTuple):
    """The full training state pytree: the unit of checkpoint/restore.

    ``step`` is the ``global_step`` analogue (reference example.py:169): in
    sync-DP it counts globally synchronized updates.  ``model_state`` holds
    non-trainable stats (BatchNorm moments); empty dict for pure models.
    """
    step: jnp.ndarray
    params: Any
    opt_state: Any
    model_state: Any = ()

    @classmethod
    def create(cls, params, opt_state, model_state=()):
        return cls(step=jnp.zeros((), jnp.int32), params=params,
                   opt_state=opt_state, model_state=model_state)


StepFn = Callable[..., Tuple[TrainState, Dict[str, Any]]]


class TrainSession:
    """Monitored training loop driver.

    Usage (the reference's loop shape, example.py:189-219)::

        with TrainSession(state, step_fn, checkpoint_dir=logdir,
                          hooks=[StopAtStepHook(30000)]) as sess:
            for batch in data:
                if sess.should_stop():
                    break
                metrics = sess.run_step(batch)

    ``step_fn(state, batch) -> (new_state, metrics)`` is typically a jitted
    (or pjit-sharded) function built by ``train.make_train_step``.
    """

    def __init__(self, state: TrainState, step_fn: StepFn,
                 checkpoint_dir: Optional[str] = None,
                 hooks: Sequence[Hook] = (),
                 is_chief: Optional[bool] = None,
                 max_to_keep: int = 5,
                 restore: bool = True,
                 async_checkpoint: bool = False,
                 sharded_checkpoint: bool = False,
                 telemetry=None):
        self.state = state
        self.step_fn = step_fn
        self.checkpoint_dir = checkpoint_dir
        self.hooks = list(hooks)
        # Optional obs.Telemetry: entering the session starts it (its
        # tracer becomes the active one, so run_step's "train.step" /
        # "train.dispatch" spans and save()'s "checkpoint" span land on
        # it) and save() feeds its save-duration histogram.  Pair with
        # train.TraceHook/MetricsExportHook for the host-timeline and
        # /metrics halves; the session never closes a user-provided
        # telemetry object.
        self.telemetry = telemetry
        # steps this session has dispatched: the spans' step index (the
        # global step lives on the device; reading it would be a sync)
        self._steps_run = 0
        self.is_chief = cluster.is_chief() if is_chief is None else is_chief
        self.max_to_keep = max_to_keep
        self.last_saved_step = None
        self._stop = False
        self._entered = False
        # Sharded: every process writes its own chunks (scale path for
        # pjit-sharded states, train/sharded_checkpoint.py); restore
        # reassembles only locally-addressable slices.
        self.sharded = sharded_checkpoint
        # Async: disk writes happen on a background thread (the device->host
        # snapshot still happens inline); drained on session exit.  The
        # sharded variant needs no cross-process barrier (structural
        # completeness), which is what makes it background-safe on a pod.
        self._async_ckpt = None
        if async_checkpoint:
            self._async_ckpt = (sharded_lib.AsyncShardedCheckpointer()
                                if sharded_checkpoint
                                else ckpt_lib.AsyncCheckpointer())

        if restore and checkpoint_dir:
            # Verified restore (docs/RESILIENCE.md): walk newest->oldest,
            # quarantine anything that fails checksums/structure, fall
            # back to the previous good step.  A corrupt newest
            # checkpoint costs one save interval, not the run.
            if sharded_checkpoint:
                restored, latest = sharded_lib.restore_latest_good_sharded(
                    self.state, checkpoint_dir)
            else:
                restored, latest = ckpt_lib.restore_latest_good(
                    self.state, checkpoint_dir)
            if restored is not None:
                self.state = restored
                self.last_saved_step = self.step  # disk already has this step
                log.info("restored checkpoint %s (step %d)", latest,
                         self.step)
                print(f"Restored checkpoint {os.path.basename(latest)} at "
                      f"step {self.step}", flush=True)

    # -- loop protocol ----------------------------------------------------
    @property
    def step(self) -> int:
        return int(self.state.step)

    def should_stop(self) -> bool:
        return self._stop

    def request_stop(self) -> None:
        self._stop = True

    def run_step(self, *args, **kwargs) -> Dict[str, Any]:
        """One training step: hooks, compiled step fn, cursor advance."""
        self._steps_run += 1
        with trace_lib.span("train.step", step=self._steps_run):
            plan = faults_lib.active()
            if plan is not None:
                # chaos runs only: evaluating a step-indexed fault
                # trigger reads the device step scalar (a host sync);
                # with no plan active this is one module-global None
                # check.
                args = plan.on_step(self.step, args)
            for hook in self.hooks:
                hook.before_step(self)
            # goodput "step" frame == the "train.dispatch" span: with an
            # active accountant this is where productive time accrues (a
            # retrace inside the dispatch lands in "compile" instead —
            # frames are exclusive); the call is async, so the span is
            # the host's cost of a step, not the device's
            with goodput_lib.account("step"):
                new_state, metrics = self.step_fn(self.state, *args,
                                                  **kwargs)
            self.state = new_state
            for hook in self.hooks:
                hook.after_step(self, metrics)
        return metrics

    # -- checkpointing ----------------------------------------------------
    def save(self) -> Optional[str]:
        """Chief-only checkpoint write (reference chief role,
        example.py:74-76); non-chief calls are no-ops — except in sharded
        mode, where EVERY process writes the chunks it owns and only the
        manifest is chief-only (inside save_sharded)."""
        # one frame: the goodput bucket, the "checkpoint" span and the
        # save-duration histogram all read its one measurement
        with goodput_lib.account("checkpoint_save",
                                 measure=self.telemetry is not None,
                                 step=self.step) as frame:
            path = self._save_impl()
        if self.telemetry is not None:
            self.telemetry.checkpoint_seconds().observe(frame.duration_s)
        return path

    def _save_impl(self) -> Optional[str]:
        if not self.checkpoint_dir:
            return None
        if self.sharded:
            if self._async_ckpt is not None:
                # NO barrier on the background thread: its collectives
                # would race the main thread's training collectives and
                # can deadlock a pod — completeness is structural instead
                self._async_ckpt.save(self.checkpoint_dir, self.step,
                                      self.state,
                                      max_to_keep=self.max_to_keep)
                path = ckpt_lib.ckpt_path(self.checkpoint_dir, self.step)
                self.last_saved_step = self.step
                log.info("queued async sharded checkpoint %s", path)
                return path
            sync_fn = None
            if jax.process_count() > 1:
                # sync path keeps the barrier so "save returned" means
                # "checkpoint globally complete" — what a preemption save
                # racing shutdown needs (completeness itself no longer
                # depends on it)
                from jax.experimental import multihost_utils
                step_now = int(self.step)
                sync_fn = lambda: multihost_utils.sync_global_devices(
                    f"dttpu-sharded-ckpt-{step_now}")
            path = sharded_lib.save_sharded(self.checkpoint_dir, self.step,
                                            self.state,
                                            max_to_keep=self.max_to_keep,
                                            sync_fn=sync_fn)
            self.last_saved_step = self.step
            log.info("saved sharded checkpoint %s", path)
            return path
        if not self.is_chief:
            return None
        if self._async_ckpt is not None:
            self._async_ckpt.save(self.checkpoint_dir, self.step, self.state,
                                  max_to_keep=self.max_to_keep)
            path = ckpt_lib.ckpt_path(self.checkpoint_dir, self.step)
        else:
            path = ckpt_lib.save(self.checkpoint_dir, self.step, self.state,
                                 max_to_keep=self.max_to_keep)
        self.last_saved_step = self.step
        log.info("saved checkpoint %s", path)
        return path

    def drain_checkpoints(self) -> None:
        """Block until every queued async checkpoint write is on disk
        (no-op without async) — what a preemption save needs: 'save
        returned' must mean durable before the grace window closes."""
        if self._async_ckpt is not None:
            self._async_ckpt.wait()

    # -- context manager --------------------------------------------------
    def __enter__(self) -> "TrainSession":
        self._entered = True
        if self.telemetry is not None:
            self.telemetry.start()   # idempotent; hooks also call it
        for hook in self.hooks:
            hook.begin(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        # On clean exit run end-hooks (summary flush etc.), then make sure a
        # final checkpoint exists — MTS saves on close whenever a
        # checkpoint_dir was given (reference example.py:191), with or
        # without an explicit CheckpointHook.  Cleanup hooks (``close``:
        # signal handlers, watchdog threads, profiler traces) run
        # UNCONDITIONALLY — an exception must not leave a dead session's
        # SIGTERM handler installed or a watchdog thread polling.
        try:
            if exc_type is None:
                for hook in self.hooks:
                    hook.end(self)
                # last_saved_step (not disk state) is the dedup cursor: an
                # async write for this step may not have landed yet.
                if (self.checkpoint_dir and
                        (self.is_chief or self.sharded) and
                        self.last_saved_step != self.step):
                    self.save()
        finally:
            for hook in self.hooks:
                try:
                    hook.close(self)
                except Exception:  # pragma: no cover
                    log.exception("hook %r close() raised", hook)
            if self._async_ckpt is not None:
                try:
                    self._async_ckpt.close()  # drain pending writes
                except Exception:
                    if exc_type is None:
                        raise  # clean exit: a lost checkpoint must be loud
                    # don't mask the original in-flight exception
                    log.exception("async checkpoint write failed during "
                                  "exception unwind")
            self._entered = False
