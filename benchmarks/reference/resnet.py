"""Reference for the ``resnet`` family: the same flax module evaluated in
float32 with matmuls and convolutions at ``highest`` precision, training
mode (batch statistics of the sample). It shares the module's code with the
system, so it checks the precision and the compiled path, not the
architecture; an independent ``jax.numpy`` ResNet is an open question in
PERF.md."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def image_nll(model_f32, variables, images, labels):
    """-log p(label) per image: [N] float32."""
    with jax.default_matmul_precision("highest"):
        logits, _ = model_f32.apply(variables, images, train=True,
                                    mutable=["batch_stats"])
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
