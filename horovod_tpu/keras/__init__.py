"""Keras adapter — parity with ``horovod/keras/__init__.py`` for Keras 3.

A reference user writes ``import horovod.keras as hvd``; this module gives
the same surface over the TPU-native core:

* :func:`DistributedOptimizer` — a **dynamically created subclass of the
  user's optimizer class** (keeping the class name so checkpoints restore
  without this framework installed — the reference's trick,
  ``keras/__init__.py:81-87``) whose ``apply_gradients`` averages gradients
  across ranks first (``keras/__init__.py:41-63`` overrode
  ``get_gradients``; Keras 3 hooks ``apply_gradients``).
* eager ``allreduce/allgather/broadcast(value)`` helpers
  (``keras/__init__.py:90-144`` ran them through ``K.get_session().run``;
  here they dispatch the framework's eager plane directly).
* ``broadcast_global_variables(model, root_rank)`` — weight sync from rank
  0 into a built Keras model.
* re-exported ``init/size/rank/local_rank`` process API.

Works with any Keras 3 backend (tensorflow / jax / torch): values cross
into the collective plane via numpy and return as numpy.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from .. import runtime
from ..ops.collectives import Op
from ..utils.lr_schedule import LRScheduleCore, warmup_multiplier
from ..ops.collectives import allgather as _allgather
from ..ops.collectives import allreduce as _allreduce
from ..ops.collectives import broadcast as _broadcast
from ..optimizer import Compression  # noqa: F401  (compression= convenience)
from ..runtime import (  # noqa: F401  (re-exports, reference parity)
    init,
    is_initialized,
    local_rank,
    process_count,
    process_index,
    rank,
    shutdown,
    size,
)


def allreduce(value, average: bool = True, name: Optional[str] = None):
    """Eager allreduce of a value/array; returns numpy
    (parity: ``keras/__init__.py:117-126``)."""
    return np.asarray(_allreduce(np.asarray(value), average=average,
                                 name=name))


def allgather(value, name: Optional[str] = None):
    """Eager allgather along dim 0; returns numpy
    (parity: ``keras/__init__.py:129-136``)."""
    return np.asarray(_allgather(np.asarray(value), name=name))


def broadcast(value, root_rank: int = 0, name: Optional[str] = None):
    """Eager broadcast from ``root_rank``; returns numpy
    (parity: ``keras/__init__.py:139-144``)."""
    return np.asarray(_broadcast(np.asarray(value), root_rank=root_rank,
                                 name=name))


def broadcast_global_variables(model, root_rank: int = 0) -> None:
    """Sync a built Keras model's weights (and optimizer variables, if
    built) from ``root_rank`` (parity: ``keras/__init__.py:90-96`` +
    ``BroadcastGlobalVariablesCallback``)."""
    for v in model.weights:
        v.assign(broadcast(np.asarray(v), root_rank,
                           name=f"bcast.{v.path if hasattr(v, 'path') else v.name}"))
    opt = getattr(model, "optimizer", None)
    if opt is not None and getattr(opt, "built", False):
        for v in opt.variables:
            v.assign(broadcast(np.asarray(v), root_rank,
                               name=f"bcast.opt.{getattr(v, 'path', v.name)}"))


def DistributedOptimizer(optimizer, *, average: bool = True,
                         compression=None,
                         name: Optional[str] = None):
    """Wrap a Keras 3 optimizer so gradients are averaged across ranks
    before being applied.

    Returns an instance of a dynamically created subclass of
    ``type(optimizer)`` with the same class name, so saved configs/
    checkpoints deserialize with plain Keras when this framework is absent
    (reference: ``keras/__init__.py:81-87``). A no-op wrapper when
    ``size() == 1``. ``compression=hvd.Compression.bf16`` halves allreduce
    bytes (same semantics as the core optimizer wrapper).
    """
    import keras

    cls_name = optimizer.__class__.__name__
    compression = compression if compression is not None else Compression.none

    class _Distributed(optimizer.__class__):
        _hvd_average = average
        _hvd_compression = compression

        def apply(self, grads, trainable_variables=None):
            # `apply` is the single funnel in Keras 3: the TF trainer's
            # `apply_gradients` and the jax trainer's `stateless_apply`
            # both land here, so hooking it covers every backend's
            # compiled train step.
            if runtime.is_initialized() and runtime.size() > 1:
                grads = list(grads)
                variables = (list(trainable_variables)
                             if trainable_variables is not None
                             else list(self._trainable_variables))
                idx = [i for i, g in enumerate(grads) if g is not None]
                if idx:
                    reduced = self._hvd_allreduce_grads(
                        [grads[i] for i in idx],
                        [variables[i] for i in idx])
                    for i, g in zip(idx, reduced):
                        grads[i] = g
            return super().apply(grads, trainable_variables)

        def _hvd_allreduce_grads(self, grads, variables):
            """Allreduce the whole gradient list through ONE host callback.

            A single callback (not one per gradient) matters in
            multi-process worlds: independent per-tensor callbacks may
            execute in different orders on different ranks, each blocking
            on a different collective — a deadlock the reference's
            coordinator avoids because TF's enqueue is asynchronous
            (mpi_ops.cc:1752-1772). One callback per step keeps every rank
            announcing the same batch, and the async submit-all/wait-all
            inside feeds the coordinator's response fusion.
            """
            names = [f"grad.{getattr(v, 'path', v.name)}" for v in variables]

            def _reduce_all_np(*gs):
                arrs = [np.asarray(g) for g in gs]
                w = runtime.world()
                if w.coord is not None:
                    # Multi-process: overlap every announcement (fusion),
                    # then redeem in order.
                    compressed = [self._hvd_compression.compress(a)
                                  for a in arrs]
                    handles = [
                        w.coord.submit("allreduce", c, name,
                                       op=Op.AVERAGE if self._hvd_average
                                       else Op.SUM)
                        for (c, _), name in zip(compressed, names)]
                    outs = [
                        np.asarray(self._hvd_compression.decompress(
                            w.coord.wait(h), ctx))
                        for h, (_, ctx) in zip(handles, compressed)]
                else:
                    outs = []
                    for a, name in zip(arrs, names):
                        c, ctx = self._hvd_compression.compress(a)
                        out = _allreduce(c, average=self._hvd_average,
                                         name=name)
                        outs.append(np.asarray(
                            self._hvd_compression.decompress(out, ctx)))
                return tuple(np.ascontiguousarray(o.astype(a.dtype))
                             for o, a in zip(outs, arrs))

            # Keras compiles train steps per backend; bridge the collective
            # through the backend's host-callback mechanism so it works
            # inside tf.function / jax.jit, and directly when eager.
            backend = keras.backend.backend()
            if backend == "tensorflow":
                import tensorflow as tf
                if not tf.executing_eagerly():  # inside tf.function
                    outs = tf.py_function(
                        lambda *gs: [tf.constant(o) for o in
                                     _reduce_all_np(*[g.numpy()
                                                      for g in gs])],
                        list(grads), Tout=[g.dtype for g in grads])
                    for o, g in zip(outs, grads):
                        o.set_shape(g.shape)
                    return list(outs)
            elif backend == "jax":
                import jax as _jax
                from ..utils.compat import is_tracer
                if any(is_tracer(g) for g in grads):
                    out_shapes = tuple(
                        _jax.ShapeDtypeStruct(g.shape, g.dtype)
                        for g in grads)
                    return list(_jax.pure_callback(
                        _reduce_all_np, out_shapes, *grads))
            outs = _reduce_all_np(*[keras.ops.convert_to_numpy(g)
                                    for g in grads])
            return [keras.ops.convert_to_tensor(o, dtype=g.dtype)
                    for o, g in zip(outs, grads)]

    _Distributed.__name__ = cls_name
    _Distributed.__qualname__ = cls_name

    config = optimizer.get_config()
    return _Distributed.from_config(config)


class BroadcastGlobalVariablesCallback:
    """Keras callback: broadcast model + optimizer state from ``root_rank``
    at train begin (parity: ``horovod/keras/callbacks.py:8-34``)."""

    def __new__(cls, root_rank: int = 0):
        import keras

        class _CB(keras.callbacks.Callback):
            def __init__(self, root):
                super().__init__()
                self.root_rank = root

            def on_train_begin(self, logs=None):
                broadcast_global_variables(self.model, self.root_rank)

        return _CB(root_rank)


class MetricAverageCallback:
    """Keras callback: average epoch-end metrics over ranks (parity:
    ``horovod/keras/callbacks.py:37-87``); place before callbacks that
    consume metrics (ReduceLROnPlateau, loggers)."""

    def __new__(cls):
        import keras

        class _CB(keras.callbacks.Callback):
            def on_epoch_end(self, epoch, logs=None):
                if not logs:
                    return
                for k, v in list(logs.items()):
                    if isinstance(v, (int, float, np.floating, np.integer)):
                        logs[k] = float(allreduce(
                            np.float32(v), average=True,
                            name=f"metric.{k}"))

        return _CB()


class LearningRateScheduleCallback:
    """Keras callback: LR = ``initial_lr * multiplier(epoch)`` between
    ``start_epoch`` and ``end_epoch`` (parity:
    ``horovod/keras/callbacks.py:90-199``). ``staircase=False`` adjusts
    every batch at fractional epochs; with ``momentum_correction`` the
    optimizer momentum is scaled by ``new_lr/old_lr`` for the adjusted
    batch and restored after it."""

    def __new__(cls, multiplier, start_epoch: int = 0,
                end_epoch: Optional[int] = None, staircase: bool = True,
                momentum_correction: bool = True,
                steps_per_epoch: Optional[int] = None):
        import keras

        # The schedule/momentum-correction math is shared with the core
        # callback layer (utils/lr_schedule.py); this adapter owns only the
        # Keras 3 optimizer-variable plumbing.
        core = LRScheduleCore(
            multiplier, start_epoch=start_epoch, end_epoch=end_epoch,
            staircase=staircase, momentum_correction=momentum_correction,
            steps_per_epoch=steps_per_epoch)

        class _CB(keras.callbacks.Callback):
            def __init__(self):
                super().__init__()
                self.core = core

            # -- optimizer plumbing (Keras 3 variables) -------------------
            def _get_lr(self):
                return float(keras.ops.convert_to_numpy(
                    self.model.optimizer.learning_rate))

            def _set_lr(self, v):
                self.model.optimizer.learning_rate = v

            def _get_momentum(self):
                m = getattr(self.model.optimizer, "momentum", None)
                return float(m) if m is not None else None

            def _set_momentum(self, v):
                self.model.optimizer.momentum = v

            # -- hooks (decisions delegated to the shared core) -----------
            def on_train_begin(self, logs=None):
                self.core.train_begin(self._get_lr())

            def on_epoch_begin(self, epoch, logs=None):
                self.core.epoch_begin(epoch)

            def on_train_batch_begin(self, batch, logs=None):
                new_lr = self.core.target_lr(batch)
                if new_lr is None:
                    return
                old_lr = self._get_lr()
                self._set_lr(new_lr)
                m = self.core.corrected_momentum(old_lr, new_lr,
                                                 self._get_momentum())
                if m is not None:
                    self._set_momentum(m)

            def on_train_batch_end(self, batch, logs=None):
                m = self.core.momentum_to_restore()
                if m is not None:
                    self._set_momentum(m)

            def on_epoch_end(self, epoch, logs=None):
                if logs is not None:
                    logs["lr"] = self._get_lr()

        return _CB()


class LearningRateWarmupCallback:
    """Keras callback: gradual warmup ``lr/size → lr`` over
    ``warmup_epochs`` (parity: ``horovod/keras/callbacks.py:202-259``;
    Goyal et al. 1706.02677)."""

    def __new__(cls, warmup_epochs: int = 5,
                momentum_correction: bool = True,
                steps_per_epoch: Optional[int] = None, verbose: int = 0):
        if not steps_per_epoch:
            raise ValueError("steps_per_epoch is required for warmup "
                             "(per-batch fractional-epoch adjustment)")

        cb = LearningRateScheduleCallback(
            warmup_multiplier(
                warmup_epochs, lambda: steps_per_epoch,
                lambda: size() if runtime.is_initialized() else 1),
            start_epoch=0, end_epoch=warmup_epochs,
            staircase=False, momentum_correction=momentum_correction,
            steps_per_epoch=steps_per_epoch)

        if verbose:
            base_epoch_end = cb.on_epoch_end

            def on_epoch_end(epoch, logs=None):
                base_epoch_end(epoch, logs)
                if epoch == warmup_epochs - 1 and (
                        not runtime.is_initialized()
                        or runtime.world().controller_rank == 0):
                    print(f"\nEpoch {epoch + 1}: finished gradual learning "
                          f"rate warmup to {cb._get_lr():g}.")

            cb.on_epoch_end = on_epoch_end
        return cb
