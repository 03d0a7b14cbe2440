"""Estimator — the high-level fit loop (ref gluon/contrib/estimator/
estimator.py).

TPU-first divergences from the reference (docs/divergences.md):
- no per-GPU context lists or ``split_and_load``: ONE global batch flows
  through the (hybridized → jitted) net, device placement is jit's job.
  ``device`` is accepted for API compatibility and validated, but there
  is exactly one logical TPU computation.
- ``pred``/``loss`` passed to handlers are single arrays, not shard
  lists (BatchProcessor docstring).

Everything else — handler classes, default handler injection, priority
ordering, metric-name prefixing, stop semantics — matches the reference
behavior test-for-test.
"""
from __future__ import annotations

import copy
import logging
import sys
import warnings

from ... import loss as gluon_loss
from ...data import DataLoader
from ...trainer import Trainer
from .batch_processor import BatchProcessor
from .event_handler import (GradientUpdateHandler, LoggingHandler,
                            MetricHandler, StoppingHandler,
                            ValidationHandler, _check_event_handlers)
from .event_handler import (BatchBegin, BatchEnd, EpochBegin, EpochEnd,
                            TrainBegin, TrainEnd)
from .utils import (_check_handler_metric_ref, _check_metrics,
                    _suggest_metric_for_loss)

__all__ = ["Estimator"]


class Estimator:
    """Train/evaluate a gluon net with event handlers.

    Parameters mirror the reference estimator: net, loss (a
    ``gluon.loss.Loss``), optional train/val metrics, initializer,
    trainer, device, and an overridable ``batch_processor``.
    """

    def __init__(self, net, loss, train_metrics=None, val_metrics=None,
                 initializer=None, trainer=None, device=None, val_net=None,
                 val_loss=None, batch_processor=None):
        self.net = net
        self.loss = self._check_loss(loss)
        self._train_metrics = _check_metrics(train_metrics)
        self._val_metrics = _check_metrics(val_metrics)
        self._add_default_training_metrics()
        self._add_validation_metrics()
        self.val_loss = self._check_loss(val_loss) if val_loss is not None \
            else self.loss
        self.val_net = val_net if val_net is not None else self.net

        self.logger = logging.Logger(name="Estimator", level=logging.INFO)
        self.logger.addHandler(logging.StreamHandler(sys.stdout))

        self.device = self._check_device(device)
        self.context = self.device            # legacy alias
        self._initialize(initializer)
        self.trainer = self._check_trainer(trainer)
        self.batch_processor = self._check_batch_processor(batch_processor)
        self.max_epoch = None
        self.max_batch = None
        self.batch_axis = 0

    # -- argument checks ---------------------------------------------------

    @staticmethod
    def _check_loss(loss):
        if not isinstance(loss, gluon_loss.Loss):
            raise ValueError(
                f"loss must be a gluon.loss.Loss, got {loss!r}")
        return loss

    @staticmethod
    def _check_device(device):
        from .... import context as ctx_mod

        if device is None:
            return [ctx_mod.current_context()]
        devices = device if isinstance(device, (list, tuple)) else [device]
        if not all(isinstance(d, ctx_mod.Context) for d in devices):
            raise ValueError(
                "device must be a Context or list of Contexts, got "
                f"{device!r}")
        return list(devices)

    @staticmethod
    def _check_batch_processor(bp):
        if bp is None:
            return BatchProcessor()
        if not callable(getattr(bp, "fit_batch", None)) or \
                not callable(getattr(bp, "evaluate_batch", None)):
            raise ValueError("custom batch processor must implement "
                             "fit_batch() and evaluate_batch()")
        return bp

    def _is_initialized(self):
        for p in self.net.collect_params().values():
            try:
                p.data()
            except Exception:
                return False
        return True

    def _initialize(self, initializer):
        if not self._is_initialized():
            if initializer:
                self.net.initialize(init=initializer)
            else:
                self.net.initialize()
        elif initializer:
            warnings.warn(
                "Network already initialized, skipping initialization; "
                "use net.initialize(force_reinit=True) to re-init")

    def _check_trainer(self, trainer):
        if not trainer:
            warnings.warn("No trainer specified, default SGD optimizer "
                          "with learning rate 0.001 is used.")
            return Trainer(self.net.collect_params(), "sgd",
                           {"learning_rate": 0.001})
        if not isinstance(trainer, Trainer):
            raise ValueError(
                f"trainer must be a gluon.Trainer, got {trainer!r}")
        return trainer

    # -- metric plumbing ---------------------------------------------------

    def _add_default_training_metrics(self):
        if not self._train_metrics:
            suggested = _suggest_metric_for_loss(self.loss)
            self._train_metrics = [suggested] if suggested else []
            from ...metric import Loss as LossMetric

            self._train_metrics.append(
                LossMetric(type(self.loss).__name__))
        for m in self._train_metrics:
            m.name = "training " + m.name

    def _add_validation_metrics(self):
        if not self._val_metrics:
            self._val_metrics = [copy.deepcopy(m)
                                 for m in self._train_metrics]
        for m in self._val_metrics:
            if "training" in m.name:
                m.name = m.name.replace("training", "validation")
            else:
                m.name = "validation " + m.name

    @property
    def train_metrics(self):
        return self._train_metrics

    @property
    def val_metrics(self):
        return self._val_metrics

    # -- evaluation --------------------------------------------------------

    def evaluate(self, val_data, batch_axis=0, event_handlers=None):
        """Run ``batch_processor.evaluate_batch`` over the loader with
        validation metric/logging handlers."""
        if not isinstance(val_data, DataLoader):
            raise ValueError(
                "Estimator only supports gluon DataLoader input; wrap "
                "your arrays/DataIter in a DataLoader")
        for m in self.val_metrics:
            m.reset()
        handlers = self._default_validation_handlers(event_handlers)
        _, epoch_begin, batch_begin, batch_end, epoch_end, _ = \
            self._categorize_handlers(handlers)

        for h in epoch_begin:
            h.epoch_begin(self)
        for batch in val_data:
            for h in batch_begin:
                h.batch_begin(self, batch=batch)
            _, label, pred, loss = self.batch_processor.evaluate_batch(
                self, batch, batch_axis)
            for h in batch_end:
                h.batch_end(self, batch=batch, pred=pred, label=label,
                            loss=loss)
        for h in epoch_end:
            h.epoch_end(self)

    # -- training ----------------------------------------------------------

    def fit(self, train_data, val_data=None, epochs=None,
            event_handlers=None, batches=None, batch_axis=0):
        """Train for exactly one of ``epochs`` or ``batches``."""
        if not isinstance(train_data, DataLoader):
            raise ValueError(
                "Estimator only supports gluon DataLoader input; wrap "
                "your arrays/DataIter in a DataLoader")
        if (not epochs) == (not batches):
            raise ValueError("specify exactly one of: epochs or batches")

        self.max_epoch = epochs
        self.max_batch = batches
        self.batch_axis = batch_axis

        handlers = self._default_handlers(val_data, event_handlers)
        train_begin, epoch_begin, batch_begin, batch_end, epoch_end, \
            train_end = self._categorize_handlers(handlers)

        for h in train_begin:
            h.train_begin(self)
        while True:
            for h in epoch_begin:
                h.epoch_begin(self)
            for batch in train_data:
                for h in batch_begin:
                    h.batch_begin(self, batch=batch)
                _, label, pred, loss = self.batch_processor.fit_batch(
                    self, batch, batch_axis)
                if any([h.batch_end(self, batch=batch, pred=pred,
                                    label=label, loss=loss)
                        for h in batch_end]):
                    break
            if any([h.epoch_end(self) for h in epoch_end]):
                break
        for h in train_end:
            h.train_end(self)

    # -- handler plumbing --------------------------------------------------

    def _default_handlers(self, val_data, event_handlers):
        handlers = _check_event_handlers(event_handlers)
        added = [StoppingHandler(self.max_epoch, self.max_batch)]
        if not any(isinstance(h, GradientUpdateHandler) for h in handlers):
            added.append(GradientUpdateHandler())
        if not any(isinstance(h, MetricHandler) for h in handlers):
            added.append(MetricHandler(metrics=self.train_metrics))
        if val_data and not any(isinstance(h, ValidationHandler)
                                for h in handlers):
            added.append(ValidationHandler(val_data=val_data,
                                           eval_fn=self.evaluate))
        if not any(isinstance(h, LoggingHandler) for h in handlers):
            added.append(LoggingHandler(metrics=self.train_metrics))
        mixing = bool(handlers) and bool(added)
        handlers.extend(added)
        if mixing:
            known = set(self.train_metrics + self.val_metrics)
            for h in handlers:
                _check_handler_metric_ref(h, known)
        handlers.sort(key=lambda h: getattr(h, "priority", 0))
        return handlers

    def _default_validation_handlers(self, event_handlers):
        handlers = _check_event_handlers(event_handlers)
        added = []
        if not any(isinstance(h, MetricHandler) for h in handlers):
            added.append(MetricHandler(metrics=self.val_metrics))
        if not any(isinstance(h, LoggingHandler) for h in handlers):
            added.append(LoggingHandler(metrics=self.val_metrics))
        mixing = bool(handlers) and bool(added)
        handlers.extend(added)
        if mixing:
            for h in handlers:
                _check_handler_metric_ref(h, set(self.val_metrics))
        handlers.sort(key=lambda h: getattr(h, "priority", 0))
        return handlers

    @staticmethod
    def _categorize_handlers(handlers):
        buckets = ([], [], [], [], [], [])
        kinds = (TrainBegin, EpochBegin, BatchBegin, BatchEnd, EpochEnd,
                 TrainEnd)
        for h in handlers:
            for bucket, kind in zip(buckets, kinds):
                if isinstance(h, kind):
                    bucket.append(h)
        return buckets
