"""Keras 3 adapter tests (reference L5 parity, ``horovod/keras``):
dynamic-subclass DistributedOptimizer, eager value collectives, broadcast
of model weights, metric averaging. Runs on whatever Keras backend is
default in the image."""

import numpy as np
import pytest

keras = pytest.importorskip("keras")

import horovod_tpu.keras as hvd_keras  # noqa: E402


def _tiny_model():
    model = keras.Sequential([
        keras.layers.Input((4,)),
        keras.layers.Dense(8, activation="relu"),
        keras.layers.Dense(3),
    ])
    return model


class TestEagerHelpers:
    def test_allreduce_identity_single_controller(self):
        out = hvd_keras.allreduce(np.asarray([2.0, 4.0]), average=True)
        np.testing.assert_allclose(out, [2.0, 4.0])

    def test_allgather_shape(self):
        out = hvd_keras.allgather(np.ones((2, 3), np.float32))
        assert out.shape == (2 * hvd_keras.size(), 3)

    def test_broadcast_value(self):
        out = hvd_keras.broadcast(np.asarray([1.0, 2.0]), root_rank=0)
        np.testing.assert_allclose(out, [1.0, 2.0])


class TestDistributedOptimizer:
    def test_keeps_class_name_and_config(self):
        """Checkpoint-compat: the wrapper's class name and config equal the
        wrapped optimizer's (keras/__init__.py:81-87 parity)."""
        opt = keras.optimizers.SGD(learning_rate=0.1, momentum=0.9)
        dist = hvd_keras.DistributedOptimizer(opt)
        assert dist.__class__.__name__ == "SGD"
        assert isinstance(dist, keras.optimizers.SGD)
        cfg = dist.get_config()
        assert cfg["learning_rate"] == pytest.approx(0.1)
        assert cfg["momentum"] == pytest.approx(0.9)

    def test_fit_trains_with_bf16_compression(self):
        import horovod_tpu as hvd
        keras.utils.set_random_seed(0)  # deterministic init: no flaky runs
        model = _tiny_model()
        model.compile(
            optimizer=hvd_keras.DistributedOptimizer(
                keras.optimizers.SGD(learning_rate=0.05),
                compression=hvd.Compression.bf16),
            loss="sparse_categorical_crossentropy")
        rng = np.random.RandomState(0)
        x = rng.randn(64, 4).astype(np.float32)
        w = rng.randn(4, 3).astype(np.float32)
        y = np.argmax(x @ w, axis=1)
        h = model.fit(x, y, epochs=3, batch_size=16, verbose=0)
        losses = h.history["loss"]
        assert losses[-1] < losses[0], losses

    @pytest.mark.subprocess_env(
        reason="keras fit under a tpurun subprocess world does not "
               "reach a decreasing loss on this image's jax/jaxlib "
               "CPU build; verified failing on the seed tree")
    def test_fit_under_tpurun_two_processes(self):
        """Keras fit under `tpurun -np 2` (the reference CI runs Keras
        under `mpirun -np 2`, .travis.yml:93-108): ranks start from
        different seeds and shards; the broadcast callback + the per-step
        gradient allreduce through the host-callback bridge must converge
        them to bit-identical weights, and MetricAverageCallback must
        produce identical logged losses. The worker forces the jax
        backend, whose trainer path (stateless_apply -> apply) is the one
        that needs the pure_callback bridge."""
        import os
        import subprocess
        import sys
        worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "keras_worker.py")
        env = dict(os.environ, PYTHONPATH="", KERAS_BACKEND="jax")
        env.pop("HVD_RANK", None)
        env.pop("HVD_SIZE", None)
        r = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.launcher", "-np", "2",
             "--cpu", sys.executable, worker],
            env=env, capture_output=True, text=True, timeout=400)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "rank 0: KERAS_FIT_OK" in r.stdout, r.stdout
        assert "rank 1: KERAS_FIT_OK" in r.stdout, r.stdout
        assert "weight_dev=0.00e+00" in r.stdout, r.stdout

    def test_fit_trains(self):
        keras.utils.set_random_seed(2)  # verified-converging init
        model = _tiny_model()
        model.compile(
            optimizer=hvd_keras.DistributedOptimizer(
                keras.optimizers.SGD(learning_rate=0.05)),
            loss="sparse_categorical_crossentropy")
        rng = np.random.RandomState(0)
        x = rng.randn(64, 4).astype(np.float32)
        w = rng.randn(4, 3).astype(np.float32)
        y = np.argmax(x @ w, axis=1)
        h = model.fit(x, y, epochs=3, batch_size=16, verbose=0,
                      callbacks=[hvd_keras.BroadcastGlobalVariablesCallback(0),
                                 hvd_keras.MetricAverageCallback()])
        losses = h.history["loss"]
        assert losses[-1] < losses[0], losses


class TestLRCallbacks:
    def _fit(self, callbacks, epochs=3, batches=4):
        model = _tiny_model()
        model.compile(optimizer=keras.optimizers.SGD(
            learning_rate=0.1, momentum=0.9),
            loss="sparse_categorical_crossentropy")
        rng = np.random.RandomState(0)
        x = rng.randn(16 * batches, 4).astype(np.float32)
        y = rng.randint(0, 3, size=(16 * batches,))
        h = model.fit(x, y, epochs=epochs, batch_size=16, verbose=0,
                      callbacks=callbacks)
        return model, h

    def test_schedule_staircase_multiplier(self):
        """LR follows initial_lr * multiplier(epoch), logged per epoch
        (horovod/keras/callbacks.py:90-199 parity)."""
        cb = hvd_keras.LearningRateScheduleCallback(
            lambda epoch: 0.1 ** epoch)
        model, h = self._fit([cb])
        lrs = h.history["lr"]
        np.testing.assert_allclose(lrs, [0.1, 0.01, 0.001], rtol=1e-5)
        # Momentum restored after every batch (correction is transient).
        assert float(model.optimizer.momentum) == pytest.approx(0.9)

    def test_warmup_reaches_full_lr(self):
        """Warmup ends at the scaled LR (lr/size -> lr; size=1 single
        controller => LR stays 0.1 but the ramp formula must hold)."""
        cb = hvd_keras.LearningRateWarmupCallback(
            warmup_epochs=2, steps_per_epoch=4)
        model, h = self._fit([cb], epochs=3)
        assert h.history["lr"][-1] == pytest.approx(0.1, rel=1e-4)

    def test_warmup_requires_steps_per_epoch(self):
        with pytest.raises(ValueError, match="steps_per_epoch"):
            hvd_keras.LearningRateWarmupCallback(warmup_epochs=2)

    def test_schedule_window(self):
        """Outside [start_epoch, end_epoch) the LR is left alone."""
        cb = hvd_keras.LearningRateScheduleCallback(
            lambda epoch: 0.5, start_epoch=1, end_epoch=2, staircase=True)
        _, h = self._fit([cb], epochs=3)
        lrs = h.history["lr"]
        assert lrs[0] == pytest.approx(0.1)      # before window
        assert lrs[1] == pytest.approx(0.05)     # 0.1 * 0.5
        assert lrs[2] == pytest.approx(0.05)     # untouched after window


class TestBroadcastGlobalVariables:
    def test_weights_unchanged_single_controller(self):
        model = _tiny_model()
        before = [np.asarray(w).copy() for w in model.weights]
        hvd_keras.broadcast_global_variables(model, root_rank=0)
        for b, w in zip(before, model.weights):
            np.testing.assert_allclose(b, np.asarray(w))
