"""Estimator event handlers (ref gluon/contrib/estimator/event_handler.py).

Same event classes and priority contract as the reference: handlers mix in
TrainBegin/TrainEnd/EpochBegin/EpochEnd/BatchBegin/BatchEnd; ``Estimator``
sorts each bucket ascending by ``priority`` (gradient update -2000 →
metrics -1000 → user handlers 0 → logging +inf), and a truthy return from
``batch_end``/``epoch_end`` stops training.

Divergence (documented in docs/divergences.md): the reference's 'auto'
monitor mode contains the classic ``'acc' or 'f1' in name`` truthiness bug
making auto ALWAYS mean max; here auto genuinely selects max for
accuracy/f1-family monitors and min otherwise.
"""
from __future__ import annotations

import math
import os
import time
import warnings

from ...metric import CompositeEvalMetric, EvalMetric
from ...metric import Loss as _LossMetric
from .utils import _check_metrics

__all__ = ["EventHandler", "TrainBegin", "TrainEnd", "EpochBegin",
           "EpochEnd", "BatchBegin", "BatchEnd", "StoppingHandler",
           "MetricHandler", "ValidationHandler", "LoggingHandler",
           "CheckpointHandler", "EarlyStoppingHandler",
           "GradientUpdateHandler"]


class EventHandler:
    pass


def _check_event_handlers(handlers):
    if isinstance(handlers, EventHandler):
        return [handlers]
    handlers = list(handlers or [])
    if not all(isinstance(h, EventHandler) for h in handlers):
        raise ValueError("event_handlers must be EventHandler instances, "
                         f"got {handlers!r}")
    return handlers


class TrainBegin(EventHandler):
    """Mix in to run at training start."""

    def train_begin(self, estimator, *args, **kwargs):
        pass


class TrainEnd(EventHandler):
    """Mix in to run after the final epoch/batch."""

    def train_end(self, estimator, *args, **kwargs):
        pass


class EpochBegin(EventHandler):
    """Mix in to run before each epoch's first batch."""

    def epoch_begin(self, estimator, *args, **kwargs):
        pass


class EpochEnd(EventHandler):
    """Mix in to run after each epoch; truthy return stops training."""

    def epoch_end(self, estimator, *args, **kwargs):
        pass


class BatchBegin(EventHandler):
    """Mix in to run before every batch."""

    def batch_begin(self, estimator, *args, **kwargs):
        pass


class BatchEnd(EventHandler):
    """Mix in to run after every batch; truthy return stops training."""

    def batch_end(self, estimator, *args, **kwargs):
        pass


def _due(count, period):
    """True when a periodic action fires at this (1-based) count."""
    return bool(period) and count % period == 0


def _monitor_op(mode, monitor, owner):
    """Resolve {'auto','min','max'} to a comparison; auto keys off the
    metric name (max for accuracy/f1 family, min otherwise)."""
    if mode not in ("auto", "min", "max"):
        warnings.warn(f"{owner} mode {mode!r} is unknown, falling back to "
                      "auto", RuntimeWarning)
        mode = "auto"
    if mode == "auto":
        name = monitor.get()[0].lower()
        mode = "max" if ("acc" in name or "f1" in name) else "min"
    if mode == "max":
        return lambda a, b: a > b, -math.inf
    return lambda a, b: a < b, math.inf


class StoppingHandler(TrainBegin, BatchEnd, EpochEnd):
    """Stop at estimator.max_epoch epochs or estimator.max_batch batches.

    The stop flag is sticky: once either limit is hit, every later hook
    keeps answering True so a mid-epoch break also ends the epoch loop.
    """

    def __init__(self, max_epoch=None, max_batch=None):
        self.max_epoch, self.max_batch = max_epoch, max_batch
        self.stop_training = False
        self.current_batch = self.current_epoch = 0

    def train_begin(self, estimator, *args, **kwargs):
        # fit() owns the limits; counters restart per fit
        self.max_epoch, self.max_batch = estimator.max_epoch, \
            estimator.max_batch
        self.current_batch = self.current_epoch = 0

    def _advance(self, counter_attr, limit):
        n = getattr(self, counter_attr) + 1
        setattr(self, counter_attr, n)
        self.stop_training |= n == limit
        return self.stop_training

    def batch_end(self, estimator, *args, **kwargs):
        return self._advance("current_batch", self.max_batch)

    def epoch_end(self, estimator, *args, **kwargs):
        return self._advance("current_epoch", self.max_epoch)


class MetricHandler(EpochBegin, BatchEnd):
    """Reset metrics at epoch begin, update them at batch end.  Loss
    metrics are fed loss values; the rest get (label, pred)."""

    def __init__(self, metrics, priority=-1000):
        self.metrics = _check_metrics(metrics)
        self.priority = priority

    def epoch_begin(self, estimator, *args, **kwargs):
        for m in self.metrics:
            m.reset()

    def batch_end(self, estimator, *args, **kwargs):
        pred, label, loss = kwargs["pred"], kwargs["label"], kwargs["loss"]
        for m in self.metrics:
            if isinstance(m, _LossMetric):
                m.update(0, loss)
            else:
                m.update(label, pred)


class ValidationHandler(TrainBegin, BatchEnd, EpochEnd):
    """Run ``eval_fn(val_data)`` every ``epoch_period`` epochs and/or
    every ``batch_period`` batches.  Priority -1000 so validation
    metrics exist before later handlers (logging, early stopping,
    checkpoint monitors) read them."""

    def __init__(self, val_data, eval_fn, epoch_period=1, batch_period=None,
                 priority=-1000, event_handlers=None):
        self.val_data, self.eval_fn = val_data, eval_fn
        self.epoch_period, self.batch_period = epoch_period, batch_period
        self.priority = priority
        self.event_handlers = event_handlers
        self.current_batch = self.current_epoch = 0

    def train_begin(self, estimator, *args, **kwargs):
        self.current_batch = self.current_epoch = 0

    def _validate(self, estimator):
        self.eval_fn(val_data=self.val_data,
                     batch_axis=estimator.batch_axis,
                     event_handlers=self.event_handlers)

    def batch_end(self, estimator, *args, **kwargs):
        self.current_batch += 1
        if _due(self.current_batch, self.batch_period):
            self._validate(estimator)

    def epoch_end(self, estimator, *args, **kwargs):
        self.current_epoch += 1
        if _due(self.current_epoch, self.epoch_period):
            self._validate(estimator)


class LoggingHandler(TrainBegin, TrainEnd, EpochBegin, EpochEnd,
                     BatchBegin, BatchEnd):
    """Log hyperparameters and metric values through estimator.logger.

    ``log_interval='epoch'`` logs at epoch boundaries; an integer logs
    every that many batches.  Runs at +inf priority so every other
    handler has updated its state first.
    """

    def __init__(self, log_interval="epoch", metrics=None,
                 priority=math.inf):
        if not isinstance(log_interval, int) and log_interval != "epoch":
            raise ValueError("log_interval must be an integer or 'epoch'")
        self.metrics = _check_metrics(metrics)
        self.log_interval = log_interval
        self.priority = priority
        self.batch_index = 0
        self.current_epoch = 0
        self.processed_samples = 0
        self._interval_time = 0.0

    def _fmt_metrics(self):
        return ", ".join("%s: %.4f" % m.get() for m in self.metrics)

    def train_begin(self, estimator, *args, **kwargs):
        self._train_start = time.time()
        opt = type(estimator.trainer.optimizer).__name__
        estimator.logger.info(
            "Training begin: using optimizer %s with current learning "
            "rate %.4f", opt, estimator.trainer.learning_rate)
        if estimator.max_epoch:
            estimator.logger.info("Train for %d epochs.",
                                  estimator.max_epoch)
        else:
            estimator.logger.info("Train for %d batches.",
                                  estimator.max_batch)
        self.current_epoch = 0
        self.batch_index = 0
        self.processed_samples = 0
        self._interval_time = 0.0

    def train_end(self, estimator, *args, **kwargs):
        secs = time.time() - self._train_start
        msg = "Train finished using total %ds with %d epochs. " % (
            secs, self.current_epoch)
        estimator.logger.info((msg + self._fmt_metrics()).rstrip(", "))

    def batch_begin(self, estimator, *args, **kwargs):
        if isinstance(self.log_interval, int):
            self._batch_start = time.time()

    def batch_end(self, estimator, *args, **kwargs):
        if isinstance(self.log_interval, int):
            self._interval_time += time.time() - self._batch_start
            self.processed_samples += kwargs["batch"][0].shape[0]
            if self.batch_index % self.log_interval == 0:
                msg = "[Epoch %d][Batch %d][Samples %s] time/interval: " \
                      "%.3fs " % (self.current_epoch, self.batch_index,
                                  self.processed_samples,
                                  self._interval_time)
                self._interval_time = 0.0
                estimator.logger.info((msg + self._fmt_metrics())
                                      .rstrip(", "))
        self.batch_index += 1

    def epoch_begin(self, estimator, *args, **kwargs):
        self._epoch_start = time.time()
        if any("training" in m.name for m in self.metrics):
            estimator.logger.info(
                "[Epoch %d] Begin, current learning rate: %.4f",
                self.current_epoch, estimator.trainer.learning_rate)
        else:
            estimator.logger.info("Validation Begin")

    def epoch_end(self, estimator, *args, **kwargs):
        secs = time.time() - self._epoch_start
        msg = "[Epoch %d] Finished in %.3fs, " % (self.current_epoch, secs)
        estimator.logger.info((msg + self._fmt_metrics()).rstrip(", "))
        self.current_epoch += 1
        self.batch_index = 0


class CheckpointHandler(TrainBegin, BatchEnd, EpochEnd):
    """Save parameters (+ trainer states) every ``epoch_period`` epochs /
    ``batch_period`` batches as ``{prefix}-epoch{E}batch{B}.params`` /
    ``.states``; keep at most ``max_checkpoints`` (best excluded); with
    ``save_best`` also track ``{prefix}-best`` by a monitored metric;
    optionally resume from the newest checkpoint in ``model_dir``.

    Durability is CheckpointManager's write layer (docs/resilience.md):
    every artifact lands through ``resilience``'s atomic tmp + fsync +
    rename primitive — ``.states`` via ``trainer.save_states`` (itself
    atomic) and ``.params`` via :func:`resilience.atomic_replace` — so a
    crash mid-save never tears a checkpoint the resume path then
    ``load_parameters``'s into a half-restored net.  The file naming and
    retention here stay estimator-contract (``_resume`` parses them)."""

    def __init__(self, model_dir, model_prefix="model", monitor=None,
                 verbose=0, save_best=False, mode="auto", epoch_period=1,
                 batch_period=None, max_checkpoints=5,
                 resume_from_checkpoint=False):
        os.makedirs(model_dir, exist_ok=True)
        self.model_dir, self.model_prefix = model_dir, model_prefix
        self.monitor, self.verbose = monitor, verbose
        self.save_best = save_best
        if save_best and not isinstance(monitor, EvalMetric):
            raise ValueError(
                "save_best requires a monitor metric from "
                "estimator.train_metrics or estimator.val_metrics")
        self.epoch_period, self.batch_period = epoch_period, batch_period
        self.max_checkpoints = max_checkpoints
        self.resume_from_checkpoint = resume_from_checkpoint
        self.saved_checkpoints = []
        self.current_batch = self.current_epoch = 0
        self.trained_epoch = self.trained_batch = -1
        if save_best:
            self.monitor_op, self.best = _monitor_op(mode, monitor,
                                                     "CheckpointHandler")

    def train_begin(self, estimator, *args, **kwargs):
        self.current_epoch = 0
        self.current_batch = 0
        if self.save_best:
            self.best = -math.inf if self.monitor_op(1, 0) else math.inf
        if self.resume_from_checkpoint:
            period_msg = ("resume requires saving with the same period "
                          "type as training: epoch_period with epochs, "
                          "batch_period with batches")
            if estimator.max_batch:
                assert self.batch_period and not self.epoch_period, \
                    period_msg
            if estimator.max_epoch:
                assert self.epoch_period and not self.batch_period, \
                    period_msg
            self._resume(estimator)

    def batch_end(self, estimator, *args, **kwargs):
        if self.current_batch == 0:
            self._save_symbol(estimator)
        if _due(self.current_batch + 1, self.batch_period):
            self._save_checkpoint(estimator)
        self.current_batch += 1

    def epoch_end(self, estimator, *args, **kwargs):
        if _due(self.current_epoch + 1, self.epoch_period):
            self._save_checkpoint(estimator)
        self.current_epoch += 1

    def _save_checkpoint(self, estimator):
        epoch, batch = self.current_epoch, self.current_batch
        if self.resume_from_checkpoint and self.trained_epoch >= 0:
            epoch += self.trained_epoch + 1
            batch += self.trained_batch + (0 if estimator.max_epoch else 1)
        prefix = "%s-epoch%dbatch%d" % (self.model_prefix, epoch, batch)
        self._save_params_and_trainer(estimator, prefix)
        if self.verbose > 0:
            estimator.logger.info(
                "[Epoch %d] CheckpointHandler: trained total %d batches, "
                "saving model at %s with prefix: %s", self.current_epoch,
                self.current_batch + 1, self.model_dir, prefix)
        if not self.save_best:
            return
        name, value = self.monitor.get()
        if math.isnan(value):
            warnings.warn(RuntimeWarning(
                f"save_best skipped: {name} was never updated; monitor "
                "one of estimator.train_metrics / val_metrics"))
        elif self.monitor_op(value, self.best):
            if self.verbose > 0:
                estimator.logger.info(
                    "[Epoch %d] CheckpointHandler: %s improved from "
                    "%0.5f to %0.5f, updating best model",
                    self.current_epoch, name, self.best, value)
            self.best = value
            self._save_params_and_trainer(estimator,
                                          self.model_prefix + "-best")
        elif self.verbose > 0:
            estimator.logger.info(
                "[Epoch %d] CheckpointHandler: %s did not improve from "
                "%0.5f, skipping best model", self.current_epoch, name,
                self.best)

    def _save_symbol(self, estimator):
        path = os.path.join(self.model_dir, self.model_prefix)
        net = estimator.net
        if getattr(net, "_active", False):  # hybridized -> exportable
            try:
                net.export(path)
                return
            except Exception:  # unencodable graph: fall through to advice
                pass
        estimator.logger.info(
            "Model architecture (symbol file) not saved; hybridize() the "
            "net before fitting to export %s-symbol.json", path)

    def _save_params_and_trainer(self, estimator, prefix):
        from ....resilience import atomic_replace

        # save_parameters takes a filename, so it rides the tmp-path
        # flavor of the shared atomic primitive; save_states is atomic
        # internally (resilience.write_payload)
        with atomic_replace(
                os.path.join(self.model_dir, prefix + ".params")) as tmp:
            estimator.net.save_parameters(tmp)
        estimator.trainer.save_states(
            os.path.join(self.model_dir, prefix + ".states"))
        if not prefix.endswith("-best"):
            self.saved_checkpoints.append(prefix)
        while len(self.saved_checkpoints) > self.max_checkpoints:
            old = self.saved_checkpoints.pop(0)
            for fname in os.listdir(self.model_dir):
                # '.'-anchored: plain startswith(old) would also match
                # epoch0batch2 against epoch0batch20.params
                if fname.startswith(old + "."):
                    os.remove(os.path.join(self.model_dir, fname))

    def _resume(self, estimator):
        self.trained_epoch = self._max_iteration(
            self.model_prefix + "-epoch", "epoch", "batch",
            record=self.saved_checkpoints)
        self.trained_batch = self._max_iteration(
            "%s-epoch%d" % (self.model_prefix, self.trained_epoch),
            "batch", ".params")
        if self.trained_epoch == -1:
            n = estimator.max_batch or estimator.max_epoch
            unit = "batches" if estimator.max_batch else "epochs"
            estimator.logger.info(
                "CheckpointHandler: no checkpoint found, training from "
                "scratch for %d %s", n, unit)
            return
        if estimator.max_epoch:
            if self.trained_epoch >= estimator.max_epoch - 1:
                raise ValueError(
                    f"checkpoint already at max_epoch "
                    f"{estimator.max_epoch}; pass "
                    "resume_from_checkpoint=False to train from scratch")
            estimator.max_epoch -= self.trained_epoch + 1
        if estimator.max_batch:
            if self.trained_batch >= estimator.max_batch - 1:
                raise ValueError(
                    f"checkpoint already at max_batch "
                    f"{estimator.max_batch}; pass "
                    "resume_from_checkpoint=False to train from scratch")
            estimator.max_batch -= self.trained_batch + 1
        stem = "%s-epoch%dbatch%d" % (self.model_prefix,
                                      self.trained_epoch,
                                      self.trained_batch)
        param_file = os.path.join(self.model_dir, stem + ".params")
        states_file = os.path.join(self.model_dir, stem + ".states")
        for f in (param_file, states_file):
            assert os.path.exists(f), f"resume failed: {f} does not exist"
        estimator.net.load_parameters(param_file)
        estimator.trainer.load_states(states_file)
        estimator.logger.warning(
            "CheckpointHandler: resumed from epoch %d batch %d",
            self.trained_epoch, self.trained_batch)

    def _max_iteration(self, prefix, start, end, record=None):
        best = -1
        for fname in os.listdir(self.model_dir):
            if not (fname.startswith(prefix) and ".params" in fname):
                continue
            if record is not None:
                record.append(fname[:fname.find(".params")])
            try:
                # search only from the prefix's tail onward: a
                # model_prefix containing 'epoch'/'batch' (e.g.
                # 'batchnorm_model') must not hijack the iteration
                # fields.  The callers' prefix may itself END with the
                # start token ('<model_prefix>-epoch'), so the search
                # begins len(start) before the prefix boundary.
                base = max(0, len(prefix) - len(start))
                it = int(fname[fname.find(start, base) + len(start):
                               fname.find(end, base + len(start))])
            except ValueError:
                raise ValueError(
                    "unparseable checkpoint file name "
                    f"{fname!r}; expected "
                    "{prefix}-epoch{E}batch{B}.params")
            best = max(best, it)
        return best


class EarlyStoppingHandler(TrainBegin, EpochEnd, TrainEnd):
    """Stop when the monitored metric stops improving by ``min_delta``
    for ``patience`` epochs (optionally against a ``baseline``)."""

    def __init__(self, monitor, min_delta=0, patience=0, mode="auto",
                 baseline=None):
        if not isinstance(monitor, EvalMetric):
            raise ValueError(
                "monitor must be a metric from estimator.train_metrics "
                "or estimator.val_metrics")
        if isinstance(monitor, CompositeEvalMetric):
            raise ValueError("CompositeEvalMetric is not supported; "
                             "monitor a simple metric")
        self.monitor = monitor
        self.baseline = baseline
        self.patience = patience
        self.monitor_op, self._worst = _monitor_op(
            mode, monitor, "EarlyStoppingHandler")
        # improvement must clear min_delta in the monitored direction
        self.min_delta = min_delta if self.monitor_op(1, 0) else -min_delta
        self._arm()

    def _arm(self):
        """Reset the plateau tracker (constructor + every train_begin)."""
        self.wait = 0
        self.stopped_epoch = 0
        self.current_epoch = 0
        self.stop_training = False
        self.best = self._worst if self.baseline is None else self.baseline

    def train_begin(self, estimator, *args, **kwargs):
        self._arm()

    def epoch_end(self, estimator, *args, **kwargs):
        name, value = self.monitor.get()
        if math.isnan(value):
            warnings.warn(RuntimeWarning(
                f"{name} was never updated; monitor one of "
                "estimator.train_metrics / val_metrics"))
        else:
            improved = self.monitor_op(value - self.min_delta, self.best)
            if improved:
                self.best, self.wait = value, 0
            else:
                self.wait += 1
                if self.wait >= self.patience:
                    self.stopped_epoch = self.current_epoch
                    self.stop_training = True
        self.current_epoch += 1
        return self.stop_training

    def train_end(self, estimator, *args, **kwargs):
        if self.stopped_epoch > 0:
            estimator.logger.info(
                "[Epoch %d] EarlyStoppingHandler: early stopping due to "
                "%s not improving", self.stopped_epoch,
                self.monitor.get()[0])


class GradientUpdateHandler(BatchEnd):
    """Apply the optimizer step at batch end; priority -2000 so it runs
    before metrics and user handlers read post-update state."""

    def __init__(self, priority=-2000):
        self.priority = priority

    def batch_end(self, estimator, *args, **kwargs):
        loss = kwargs["loss"]
        batch_size = sum(l.shape[0] for l in (
            loss if isinstance(loss, (list, tuple)) else [loss]))
        estimator.trainer.step(batch_size)
