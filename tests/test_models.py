"""Model family tests: forward shapes + a compiled data-parallel train step
that actually learns (loss decreases) — the analog of the reference's
examples-as-integration-tests CI (``.travis.yml:93-108`` runs shrunken
MNIST/Keras examples end-to-end)."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu import models, training
from horovod_tpu.models import resnet


class TestModelShapes:
    def test_mnist_cnn(self):
        m = models.MnistCNN()
        v = m.init(jax.random.PRNGKey(0), jnp.zeros((2, 784)), train=False)
        out = m.apply(v, jnp.zeros((2, 784)), train=False)
        assert out.shape == (2, 10)

    @pytest.mark.parametrize("depth", [20, 56])
    def test_cifar_v1(self, depth):
        m = models.cifar_resnet_v1(depth, dtype=jnp.float32)
        x = jnp.zeros((2, 32, 32, 3))
        v = m.init(jax.random.PRNGKey(0), x, train=False)
        out = m.apply(v, x, train=False)
        assert out.shape == (2, 10)
        assert "batch_stats" in v

    def test_cifar_v2(self):
        m = models.cifar_resnet_v2(56, dtype=jnp.float32)
        x = jnp.zeros((2, 32, 32, 3))
        v = m.init(jax.random.PRNGKey(0), x, train=False)
        out = m.apply(v, x, train=False)
        assert out.shape == (2, 10)

    def test_v1_v2_depth_validation(self):
        with pytest.raises(ValueError):
            models.cifar_resnet_v1(21)
        with pytest.raises(ValueError):
            models.cifar_resnet_v2(22)

    def test_resnet50_tiny_input(self):
        m = models.resnet50(num_classes=7, dtype=jnp.float32)
        x = jnp.zeros((2, 64, 64, 3))
        v = m.init(jax.random.PRNGKey(0), x, train=False)
        out = m.apply(v, x, train=False)
        assert out.shape == (2, 7)

    def test_resnet50_space_to_depth_stem(self):
        """The s2d stem (MLPerf-style 4x4/s1 conv on the 2x2-folded input)
        must keep the downstream geometry identical: same logits shape,
        same feature-map sizes (stem out H/2, then maxpool H/4)."""
        m = models.resnet50(num_classes=7, dtype=jnp.float32,
                            stem_space_to_depth=True)
        x = jnp.ones((2, 64, 64, 3))
        v = m.init(jax.random.PRNGKey(0), x, train=False)
        out = m.apply(v, x, train=False)
        assert out.shape == (2, 7)
        assert jnp.isfinite(out).all()
        # Kernel is the 4x4x12 reparametrization of the 7x7x3 stem.
        assert v["params"]["stem_s2d"]["kernel"].shape == (4, 4, 12, 64)

    def test_vgg16(self):
        m = models.vgg16(num_classes=5, dtype=jnp.float32)
        x = jnp.zeros((2, 64, 64, 3))
        v = m.init(jax.random.PRNGKey(0), x, train=False)
        out = m.apply(v, x, train=False)
        assert out.shape == (2, 5)
        # The dense head dominates params — VGG's defining property (what
        # drags its allreduce scaling to 79% in the reference table).
        n_head = sum(p.size for name, p in
                     jax.tree_util.tree_leaves_with_path(v["params"])
                     if "fc" in str(name) or "head" in str(name))
        n_total = sum(p.size for p in jax.tree_util.tree_leaves(v["params"]))
        assert n_head / n_total > 0.5

    def test_vgg_depth_validation(self):
        with pytest.raises(ValueError):
            models.VGG(depth=15).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)),
                train=False)

    def test_inception_v3(self):
        m = models.inception_v3(num_classes=6, dtype=jnp.float32)
        x = jnp.zeros((2, 128, 128, 3))
        v = m.init(jax.random.PRNGKey(0), x, train=False)
        out = m.apply(v, x, train=False)
        assert out.shape == (2, 6)
        assert "batch_stats" in v  # BN after every conv (slim parity)

    def test_inception_v3_trains(self):
        m = models.inception_v3(num_classes=4, dtype=jnp.float32)
        x = jnp.zeros((4, 96, 96, 3))
        state, dist_opt = training.create_train_state(
            m, jax.random.PRNGKey(0), x, optax.sgd(0.05))
        step = training.make_train_step(m, dist_opt)
        rng = np.random.RandomState(0)
        batch = training.shard_batch(
            (jnp.asarray(rng.randn(8, 96, 96, 3), jnp.float32),
             jnp.asarray(rng.randint(0, 4, size=(8,)))))
        state, metrics = step(state, batch)
        assert jnp.isfinite(metrics["loss"])

    def test_word2vec_loss_scalar(self):
        m = models.SkipGram(vocab_size=100, embedding_size=16)
        center = jnp.array([1, 2, 3])
        context = jnp.array([4, 5, 6])
        neg = jnp.array([[7, 8], [9, 10], [11, 12]])
        v = m.init(jax.random.PRNGKey(0), center, context, neg)
        loss = m.apply(v, center, context, neg)
        assert loss.shape == ()
        assert jnp.isfinite(loss)


class TestTrainStep:
    def _toy_batch(self, n=16, key=0):
        rng = np.random.RandomState(key)
        x = rng.randn(n, 784).astype(np.float32)
        y = rng.randint(0, 10, size=(n,))
        return jnp.asarray(x), jnp.asarray(y)

    def test_mnist_train_step_learns(self):
        model = models.MnistCNN()
        state, dist_opt = training.create_train_state(
            model, jax.random.PRNGKey(0), jnp.zeros((2, 784)),
            optax.sgd(0.05))
        step = training.make_train_step(model, dist_opt)
        batch = training.shard_batch(self._toy_batch())
        losses = []
        for _ in range(8):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]
        assert int(state.step) == 8

    def test_resnet_train_step_runs_with_batch_stats(self):
        model = models.cifar_resnet_v1(20, dtype=jnp.float32,
                                       axis_name=hvd.AXIS)
        x = jnp.zeros((8, 32, 32, 3))
        state, dist_opt = training.create_train_state(
            model, jax.random.PRNGKey(0), x, optax.sgd(0.1, momentum=0.9))
        assert state.batch_stats is not None
        step = training.make_train_step(model, dist_opt)
        rng = np.random.RandomState(0)
        batch = training.shard_batch(
            (jnp.asarray(rng.randn(8, 32, 32, 3), jnp.float32),
             jnp.asarray(rng.randint(0, 10, size=(8,)))))
        # Copy out before the step: donate_argnums invalidates state buffers.
        old_stats = np.asarray(jax.tree_util.tree_leaves(state.batch_stats)[0])
        state, metrics = step(state, batch)
        assert jnp.isfinite(metrics["loss"])
        new_stats = np.asarray(jax.tree_util.tree_leaves(state.batch_stats)[0])
        # BN running stats must update (mutable collection threaded through).
        assert not np.allclose(old_stats, new_stats)

    def test_eval_step_metrics_finite(self):
        model = models.MnistCNN()
        state, dist_opt = training.create_train_state(
            model, jax.random.PRNGKey(0), jnp.zeros((2, 784)),
            optax.sgd(0.05))
        eval_step = training.make_eval_step(model)
        batch = training.shard_batch(self._toy_batch())
        metrics = eval_step(state, batch)
        assert 0.0 <= float(metrics["accuracy"]) <= 1.0
        assert jnp.isfinite(metrics["loss"])

    def test_optimizer_state_is_plain_optax(self):
        """Checkpoint-compat parity: DistributedOptimizer state must be
        bit-identical in structure to the wrapped optimizer's state
        (the reference's Keras dynamic-subclass trick,
        keras/__init__.py:81-87)."""
        model = models.MnistCNN()
        inner = optax.sgd(0.05, momentum=0.9)
        state, _ = training.create_train_state(
            model, jax.random.PRNGKey(0), jnp.zeros((2, 784)), inner)
        plain = inner.init(state.params)
        assert (jax.tree_util.tree_structure(state.opt_state)
                == jax.tree_util.tree_structure(plain))


# ---------------------------------------------------------------------------
# The bottleneck block and the ImageNet ResNets: what a checkpoint written by
# any earlier build, the Goyal et al. recipe and the bf16 training path rely on.
# ---------------------------------------------------------------------------

def _bottleneck(filters, strides, *, train=True, dtype=jnp.float32):
    """A block built the way ``ResNet.__call__`` builds its blocks."""
    conv = functools.partial(nn.Conv, use_bias=False, dtype=dtype)
    norm = functools.partial(nn.BatchNorm, use_running_average=not train,
                             momentum=0.9, epsilon=1e-5, dtype=dtype)
    return resnet.BottleneckBlock(filters, strides=strides, conv=conv,
                                  norm=norm)


# (strides, input channels) at filters=8: the first keeps its shape (4f in,
# no projection), the second halves the map and projects the shortcut.
_BLOCK_CASES = [pytest.param((1, 1), 32, id="stride1"),
                pytest.param((2, 2), 16, id="stride2_projection")]


@pytest.mark.parametrize("factory,count", [
    pytest.param(models.resnet50, 25_557_032, id="resnet50"),
    pytest.param(models.resnet101, 44_549_160, id="resnet101")])
def test_imagenet_resnet_parameter_count(factory, count):
    """He et al. 2015, Table 1 (benchmarks/configs/resnet50_imagenet.json
    carries the 50-layer number)."""
    model = factory(num_classes=1000)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 224, 224, 3)), train=False))
    leaves = jax.tree_util.tree_leaves(shapes["params"])
    assert sum(int(np.prod(l.shape)) for l in leaves) == count
    assert all(l.dtype == jnp.float32 for l in leaves)


@pytest.mark.parametrize("strides,cin", _BLOCK_CASES)
def test_bottleneck_variable_tree(strides, cin):
    """flax's automatic names and the kernels' layouts are the checkpoint
    format: every saved ResNet-50 restores by them."""
    f = 8
    block = _bottleneck(f, strides)
    v = block.init(jax.random.PRNGKey(0), jnp.zeros((2, 8, 8, cin)))
    convs = {"Conv_0": (1, 1, cin, f), "Conv_1": (3, 3, f, f),
             "Conv_2": (1, 1, f, 4 * f)}
    norms = {"BatchNorm_0": f, "BatchNorm_1": f, "BatchNorm_2": 4 * f}
    if strides != (1, 1) or cin != 4 * f:
        convs["shortcut"] = (1, 1, cin, 4 * f)
        norms["shortcut_bn"] = 4 * f
    assert set(v) == {"params", "batch_stats"}
    assert set(v["params"]) == set(convs) | set(norms)
    assert set(v["batch_stats"]) == set(norms)
    for name, shape in convs.items():
        assert set(v["params"][name]) == {"kernel"}
        assert v["params"][name]["kernel"].shape == shape
    for name, width in norms.items():
        assert set(v["params"][name]) == {"scale", "bias"}
        assert set(v["batch_stats"][name]) == {"mean", "var"}
        for leaf in (*v["params"][name].values(),
                     *v["batch_stats"][name].values()):
            assert leaf.shape == (width,)
    assert all(l.dtype == jnp.float32 for l in jax.tree_util.tree_leaves(v))


@pytest.mark.parametrize("strides,cin", _BLOCK_CASES)
def test_bottleneck_batch_stats(strides, cin):
    """Training mode moves the running statistics by (1 - momentum) x the
    batch's and leaves ``params`` alone; eval mode reads them and mutates
    nothing."""
    f = 8
    x = jnp.asarray(np.random.RandomState(2).randn(4, 8, 8, cin), jnp.float32)
    block = _bottleneck(f, strides)
    v = block.init(jax.random.PRNGKey(0), x)
    # BatchNorm_2's scale starts at zero: set it so the statistics reach
    # the output.
    bn2 = v["params"]["BatchNorm_2"]
    params = {**v["params"],
              "BatchNorm_2": {**bn2, "scale": jnp.ones_like(bn2["scale"])}}
    v = {"params": params, "batch_stats": v["batch_stats"]}

    _, upd = block.apply(v, x, mutable=["batch_stats"])
    assert set(upd) == {"batch_stats"}
    # Conv_0 is a 1x1 at stride 1: its output is x @ kernel, pixel by pixel.
    y0 = np.einsum("nhwc,cf->nhwf", np.asarray(x),
                   np.asarray(params["Conv_0"]["kernel"][0, 0]))
    got = upd["batch_stats"]["BatchNorm_0"]
    np.testing.assert_allclose(np.asarray(got["mean"]),
                               0.1 * y0.mean(axis=(0, 1, 2)),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got["var"]),
                               0.9 + 0.1 * y0.var(axis=(0, 1, 2)),
                               rtol=1e-4, atol=1e-6)
    if "shortcut_bn" in upd["batch_stats"]:
        ys = np.einsum("nhwc,cf->nhwf",
                       np.asarray(x)[:, ::strides[0], ::strides[1]],
                       np.asarray(params["shortcut"]["kernel"][0, 0]))
        np.testing.assert_allclose(
            np.asarray(upd["batch_stats"]["shortcut_bn"]["mean"]),
            0.1 * ys.mean(axis=(0, 1, 2)), rtol=1e-4, atol=1e-6)

    eval_block = _bottleneck(f, strides, train=False)
    trained = {"params": params, "batch_stats": upd["batch_stats"]}
    first, kept = eval_block.apply(trained, x, mutable=["batch_stats"])
    for a, b in zip(jax.tree_util.tree_leaves(kept["batch_stats"]),
                    jax.tree_util.tree_leaves(upd["batch_stats"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(eval_block.apply(trained, x)),
                                  np.asarray(first))
    # It reads them: other running statistics, another output.
    assert not np.allclose(np.asarray(eval_block.apply(v, x)),
                           np.asarray(first))


def test_bottleneck_starts_as_identity():
    """BatchNorm_2's scale is zero at init (Goyal et al.), so a fresh block
    that keeps its shape returns relu(x)."""
    x = jnp.asarray(np.random.RandomState(3).randn(2, 8, 8, 32), jnp.float32)
    block = _bottleneck(8, (1, 1))
    v = block.init(jax.random.PRNGKey(0), x)
    assert not np.asarray(v["params"]["BatchNorm_2"]["scale"]).any()
    assert np.asarray(v["params"]["BatchNorm_1"]["scale"]).all()
    out, _ = block.apply(v, x, mutable=["batch_stats"])
    np.testing.assert_array_equal(np.asarray(out), np.maximum(x, 0))


def test_bottleneck_bf16_is_finite_and_keeps_dtype():
    x = jnp.asarray(np.random.RandomState(5).randn(2, 16, 16, 16),
                    jnp.bfloat16)
    block = _bottleneck(8, (1, 1), dtype=jnp.bfloat16)
    v = block.init(jax.random.PRNGKey(0), x)
    assert all(l.dtype == jnp.float32 for l in jax.tree_util.tree_leaves(v))
    out, _ = block.apply(v, x, mutable=["batch_stats"])
    assert out.dtype == jnp.bfloat16 and out.shape == (2, 16, 16, 32)

    def loss(p):
        y, _ = block.apply({"params": p, "batch_stats": v["batch_stats"]},
                           x, mutable=["batch_stats"])
        return jnp.sum(y.astype(jnp.float32))

    grads = jax.grad(loss)(v["params"])
    for leaf in jax.tree_util.tree_leaves(grads):
        assert leaf.dtype == jnp.float32
        assert np.isfinite(np.asarray(leaf)).all()
