"""Preemption-aware checkpointing.

The reference has no failure-detection/elastic story (SURVEY.md §5:
"Absent... recovery story = checkpoint/resume"); this module exceeds it
with the piece cloud TPU training actually needs: when the host receives
a preemption signal (SIGTERM — what GCE/GKE sends before reclaiming a
spot/preemptible VM), finish the in-flight step and write a full
ShardedTrainer checkpoint at the next ``step()`` boundary; the training
loop then exits on the True return (the handler never kills the process
itself — checkpointing must come first).

Usage::

    guard = PreemptionGuard(trainer, "ckpt/run1.npz")
    for step, (x, y) in enumerate(data):
        trainer.step(x, y)
        if guard.step():          # returns True once the checkpoint is cut
            break                  # exit cleanly; resume with load_states

or, with rolling versioned checkpoints (docs/resilience.md)::

    mgr = resilience.CheckpointManager("ckpt/run1", trainer)
    guard = PreemptionGuard(trainer, manager=mgr)

Elastic topology (shrink-and-resume): construct with a ``rebuild``
factory and a ``heartbeat_every`` cadence and the guard probes
``dist.heartbeat()`` between steps — a failed probe (dead host, wedged
collective, or injected ``dist.heartbeat`` chaos) is treated exactly
like a preemption signal: checkpoint at this step boundary, ``step()``
returns True, and the loop calls :meth:`PreemptionGuard.migrate` to
rebuild the trainer on the surviving devices and restore onto the
shrunken mesh (the manifest-v2 slice reader does the resharding; see
docs/resilience.md "Manifest v2 + resharding")::

    guard = PreemptionGuard(trainer, manager=mgr,
                            rebuild=make_trainer, heartbeat_every=10)
    for step, (x, y) in enumerate(data):
        guard.trainer.step(x, y)
        if guard.step():
            if guard.heartbeat_error is None:
                break                   # real preemption: exit, resume later
            guard.migrate(devices=surviving_devices())   # shrink + go on

Design notes (TPU-first): the signal handler itself only sets a flag —
checkpointing from inside a signal handler would race the jit step's
donated buffers; the write happens at the next step() boundary, where
trainer state is consistent. The loop must therefore keep calling
``step()``; a SIGTERM while the loop is stalled elsewhere is only
recorded, not acted on (pair with an external watchdog if your data
pipeline can hang).

Multi-process SPMD: preemption notices are per-VM — one host may be
signaled while the others are not. ``step()`` agrees on the flag across
processes (an allgather) so EVERY rank checkpoints and exits at the same
step boundary; otherwise the unsignaled ranks would block forever in the
next collective. Rank 0 writes (save_states gathers a global view), and
every rank joins a durability barrier before ``step()`` returns True —
a non-zero rank must not exit (and get its VM reclaimed) while rank 0
is still writing, which was exactly the hole the pre-resilience version
had.

Durability: the file write itself is atomic (the shared
``resilience.atomic_write`` tmp+fsync+rename primitive inside
``save_states``; this module no longer hand-rolls its own tmp+rename),
so a second preemption DURING the checkpoint write leaves the previous
file intact.  A failed write is loud: ``ckpt.save_failures`` ticks and
the exception is kept on ``guard.save_error`` so train loops and tests
can assert on it instead of grepping logs.
"""
from __future__ import annotations

import logging
import os
import signal
import threading
from typing import Optional

from .. import telemetry as _tel

__all__ = ["PreemptionGuard"]


class PreemptionGuard:
    def __init__(self, trainer, path: Optional[str] = None,
                 signals=(signal.SIGTERM,),
                 save_on_rank0_only: bool = True, check_every: int = 1,
                 manager=None, rebuild=None, heartbeat_every: int = 0):
        from ..base import MXNetError

        if path is None and manager is None:
            raise MXNetError(
                "PreemptionGuard needs a checkpoint path or a "
                "resilience.CheckpointManager (manager=)")
        self.trainer = trainer
        self.path = path
        self.manager = manager
        #: trainer factory for :meth:`migrate` — ``rebuild(devices) ->
        #: trainer`` builds a fresh trainer (fresh mesh) on the
        #: surviving device list
        self.rebuild = rebuild
        #: the exception of a failed preemption checkpoint (None = clean)
        self.save_error: Optional[BaseException] = None
        #: the exception of a failed liveness probe (None = healthy)
        self.heartbeat_error: Optional[BaseException] = None
        self._flag = threading.Event()
        self._saved = False
        self._save_on_rank0_only = save_on_rank0_only
        # multi-process agreement is an allgather; check_every>1 amortizes
        # it (a preemption grace period is ~30s — checking every few steps
        # is plenty)
        self._check_every = max(1, int(check_every))
        # heartbeat_every>0 probes dist.heartbeat at that step cadence;
        # a failed probe is treated exactly like a preemption signal
        # (checkpoint at this boundary, then migrate() to shrink).  The
        # cadence is step-count based so every rank probes together.
        self._heartbeat_every = max(0, int(heartbeat_every))
        self._step_count = 0
        self._prev = {}
        for sig in signals:
            self._prev[sig] = signal.signal(sig, self._on_signal)

    # -- signal side (async-signal context: flag only) ----------------------
    def _on_signal(self, signum, frame):
        self._flag.set()

    @property
    def preempted(self) -> bool:
        return self._flag.is_set()

    # -- step-boundary side --------------------------------------------------
    def step(self) -> bool:
        """Call once per training step, after trainer.step(). Returns True
        when a preemption checkpoint was written (train loop should exit).
        On a failed write it STILL returns True (the run is being
        reclaimed either way) with the exception on ``save_error`` and a
        ``ckpt.save_failures`` tick."""
        if self._saved:
            return True
        import jax

        self._step_count += 1
        if self._heartbeat_every and not self._flag.is_set() and \
                self._step_count % self._heartbeat_every == 0:
            from . import dist

            try:
                dist.heartbeat()
            except Exception as e:  # noqa: BLE001 — probe, not trainer
                # a dead/wedged host (or injected chaos standing in for
                # one): checkpoint at THIS boundary like a preemption
                # signal; the train loop then calls migrate() to resume
                # on the survivors
                self.heartbeat_error = e
                self._flag.set()
                _tel.inc("resilience.heartbeat_failures")
                logging.warning(
                    "dist.heartbeat failed (%s); treating as preemption "
                    "— checkpointing for mesh migration", e)
        if jax.process_count() > 1:
            # the gate must depend ONLY on the step count (identical on
            # every rank): letting a signaled rank enter the allgather on
            # an off-step while unsignaled ranks skip it would deadlock
            if self._step_count % self._check_every:
                return False
            # per-VM signals: agree across ranks so all exit together
            from jax.experimental import multihost_utils
            import numpy as onp

            flags = multihost_utils.process_allgather(
                onp.asarray(1 if self._flag.is_set() else 0))
            if int(onp.max(flags)) == 0:
                return False
            self._flag.set()
        elif not self._flag.is_set():
            return False

        if self.manager is not None:
            # rolling versioned checkpoint: the manager does the rank-0
            # gating, the atomic commit, AND the all-rank durability
            # barrier (and ticks ckpt.save_failures itself on error)
            try:
                step = getattr(self.trainer, "_t", self._step_count)
                self.manager.save(step, trainer=self.trainer)
                # an async_save manager returns with the write pending;
                # a preemption exit must not outrun its own checkpoint
                self.manager.wait()
                logging.warning(
                    "preemption checkpoint written under %s (step %d)",
                    self.manager.directory, step)
            except Exception as e:
                self.save_error = e
                logging.exception(
                    "preemption checkpoint FAILED; exiting WITHOUT a "
                    "new checkpoint version (older intact versions, if "
                    "any, remain restorable)")
            self._saved = True
            return True

        rank = jax.process_index()
        if not self._save_on_rank0_only or rank == 0:
            try:
                from ..resilience.checkpoint import atomic_replace

                # atomic at THIS level too (the stack's trainers are
                # already atomic inside save_states, but the guard
                # accepts any duck-typed trainer — one that writes the
                # path directly must not tear the checkpoint when the
                # grace period expires mid-write)
                with atomic_replace(os.path.abspath(self.path)) as tmp:
                    self.trainer.save_states(tmp)
                logging.warning(
                    "preemption checkpoint written to %s (step %d)",
                    self.path, self.trainer._t)
            except Exception as e:
                # params sharded across non-addressable devices (e.g. tp
                # across hosts) cannot be gathered by save_states; be
                # loud AND assertable — the preempted run exits either
                # way, but the operator must know there is NO checkpoint
                self.save_error = e
                _tel.inc("ckpt.save_failures")
                logging.exception(
                    "preemption checkpoint FAILED (params not "
                    "process-addressable? see save_states); exiting "
                    "WITHOUT a checkpoint")
        if jax.process_count() > 1:
            # durability barrier: non-zero ranks used to return True (and
            # potentially exit, taking their VM) while rank 0 was still
            # writing — every rank now waits for the write to finish
            from . import dist

            dist.barrier("mx_preemption_ckpt")
        self._saved = True
        return True

    def migrate(self, devices=None, trainer_factory=None):
        """Shrink-and-resume mesh migration (docs/resilience.md):
        rebuild the trainer on the surviving ``devices`` via the rebuild
        factory, restore the newest intact checkpoint onto the new mesh
        — the manifest-v2 reader re-slices every leaf to the shrunken
        dp/mp factors, each rank reading only the slices its shards
        intersect — re-arm the guard, and return the new trainer.

        Call after :meth:`step` returned True on a heartbeat failure or
        preemption notice (the checkpoint is already cut then); calling
        with no checkpoint cut yet saves one first.  ``devices``
        defaults to the current mesh minus its last device — on a real
        pod pass the post-loss ``jax.devices()`` after re-initializing
        the process group.  Ticks ``resilience.mesh_shrinks``; the whole
        resume is one ``resilience.migrate`` trace span."""
        from ..base import MXNetError
        from ..trace import recorder as _tr

        factory = trainer_factory if trainer_factory is not None \
            else self.rebuild
        if factory is None:
            raise MXNetError(
                "migrate() needs a trainer factory: pass rebuild= at "
                "construction or trainer_factory= here")
        if self.manager is None:
            raise MXNetError(
                "migrate() needs versioned checkpoints — construct the "
                "guard with a resilience.CheckpointManager (manager=)")
        if devices is None:
            devices = list(self.trainer.mesh.devices.ravel())[:-1]
        if not devices:
            raise MXNetError("migrate(): no surviving devices")
        with _tr.span("resilience.migrate", devices=len(devices)):
            if not self._saved:
                self.manager.save(
                    getattr(self.trainer, "_t", self._step_count),
                    trainer=self.trainer)
                self.manager.wait()
            trainer = factory(devices)
            step = self.manager.restore_latest(trainer)
            if step is None:
                raise MXNetError(
                    "migrate(): no intact checkpoint version to resume "
                    "from")
            self.trainer = trainer
            # the manager follows the guard onto the new trainer so
            # later save()/restore_latest() calls default correctly
            self.manager._trainer = trainer
            self._saved = False
            self._flag.clear()
            self.save_error = None
            self.heartbeat_error = None
            _tel.inc("resilience.mesh_shrinks")
            _tel.set_gauge("resilience.mesh_devices", len(devices))
            logging.warning(
                "mesh migration: resumed from step %d on %d device(s)",
                step, len(devices))
        return trainer

    def restore(self):
        """Put the original signal handlers back."""
        for sig, prev in self._prev.items():
            signal.signal(sig, prev)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.restore()
