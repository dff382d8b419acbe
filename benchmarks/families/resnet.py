"""Family ``resnet``: ``horovod_tpu.models.ResNet`` through
``create_train_state`` -> ``make_train_step``, the way the examples and
``chip_smoke.phase_train_resnet50`` build it (local batch norm, SGD with
momentum, float32 parameters)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax

from reference import resnet as reference

RATE_METRIC = "images_per_s_per_chip"
_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

# The bf16 module against the same module in float32 at `highest`, per image
# over 8 seeded images in training mode. Measured on the chip (PERF.md,
# PR 23): mean |difference| of the per-image loss 0.004. The bound sits
# about 3x above; 8-bit activations (3 significant bits fewer than bf16)
# would exceed it several times over.
TOL_MEAN_ABS_IMAGE = 0.015


def make_model(config, dtype=None):
    from horovod_tpu.models import resnet
    blocks = {"bottleneck": resnet.BottleneckBlock,
              "basic": resnet.BasicBlock}
    return resnet.ResNet(
        stage_sizes=config["stage_sizes"], block_cls=blocks[config["block"]],
        num_classes=config["num_classes"], num_filters=config["num_filters"],
        cifar_stem=config.get("cifar_stem", False),
        dtype=dtype or _DTYPES[config["training"]["activation_dtype"]])


def make_optimizer(config):
    o = config["training"]["optimizer"]
    return optax.sgd(o["lr"], momentum=o["momentum"])


class Family:
    def __init__(self, ctx):
        c, t = ctx.config, ctx.traffic
        self.ctx = ctx
        self.model = make_model(c)
        self.batch = t["batch"]
        self.shape = (self.batch, c["image_size"], c["image_size"], 3)
        self.classes = c["num_classes"]
        self.units_per_step = self.batch
        self.train_step = None

    def make_pool(self, n: int):
        """n host batches of float32 images and int32 labels."""
        rng = np.random.default_rng(self.ctx.seed)
        return [(rng.standard_normal(self.shape, dtype=np.float32),
                 rng.integers(0, self.classes, size=(self.batch,),
                              dtype=np.int32)) for _ in range(n)]

    def init(self, shapes_only: bool = False):
        """Weights and optimizer state made on the device in one jitted
        call from the seed; builds the train step. ``shapes_only`` traces
        instead of running (``compile_rehearsal.py``)."""
        from horovod_tpu import training
        made = {}

        def init(key):
            state, made["opt"] = training.create_train_state(
                self.model, key, jnp.zeros(self.shape, jnp.float32),
                make_optimizer(self.ctx.config))
            return state

        key = jax.random.PRNGKey(self.ctx.seed % (2 ** 31))
        if shapes_only:
            state = jax.eval_shape(init, key)
        else:
            with self.ctx.compiling("init_state"):
                state = jax.block_until_ready(jax.jit(init)(key))
        self.train_step = training.make_train_step(self.model, made["opt"])
        return state

    def reference_check(self, state) -> bool:
        rng = np.random.default_rng(self.ctx.seed + 1)
        n = self.ctx.traffic.get("reference_images", 8)
        images = jnp.asarray(rng.standard_normal((n,) + self.shape[1:],
                                                 dtype=np.float32))
        labels = jnp.asarray(rng.integers(0, self.classes, size=(n,),
                                          dtype=np.int32))
        variables = {"params": state.params,
                     "batch_stats": state.batch_stats}
        plain_model = make_model(self.ctx.config, dtype=jnp.float32)

        def system(v, x, y):
            logits, _ = self.model.apply(v, x, train=True,
                                         mutable=["batch_stats"])
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]

        def plain(v, x, y):
            return reference.image_nll(plain_model, v, x, y)

        with self.ctx.compiling("reference_check"):
            got = np.asarray(jax.jit(system)(variables, images, labels))
            want = np.asarray(jax.jit(plain)(variables, images, labels))
        err = float(np.mean(np.abs(got - want)))
        ok = bool(np.all(np.isfinite(got)) and err <= TOL_MEAN_ABS_IMAGE)
        self.ctx.log(event="reference_check", ok=ok,
                     system_loss=float(got.mean()),
                     reference_loss=float(want.mean()),
                     mean_abs_image_err=err,
                     max_abs_image_err=float(np.max(np.abs(got - want))),
                     tol_mean_abs_image=TOL_MEAN_ABS_IMAGE)
        return ok


def build(ctx) -> Family:
    return Family(ctx)
