"""Plain reference of the decoder the ``lm`` family trains: forward and
per-token loss in straightforward ``jax.numpy``, float32, matmuls at
``highest`` precision, no kernel, no sharding, written from the block's
equations (sequential pre-norm, RMSNorm eps 1e-6, causal softmax attention
scaled by d_head^-1/2 with head-major [H, 3, d_head] qkv columns, tanh-GELU
feed-forward, tied unembedding, no biases, no positions) and NOT from
``horovod_tpu/parallel/transformer.py``."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _rms_norm(x, scale):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * scale


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def token_nll(params, tokens, labels, n_heads: int):
    """-log p(label) for every position: [B, T] float32."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
        x = f32(params["embed"])[tokens]                       # [B, T, D]
        B, T, D = x.shape
        d_head = D // n_heads
        causal = jnp.tril(jnp.ones((T, T), bool))
        for layer in params["layers"]:
            h = _rms_norm(x, f32(layer["ln1"]))
            qkv = (h @ f32(layer["wqkv"])).reshape(B, T, n_heads, 3, d_head)
            q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
            s = jnp.einsum("bthd,bshd->bhts", q, k) * d_head ** -0.5
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            o = jnp.einsum("bhts,bshd->bthd", p, v).reshape(B, T, D)
            x = x + o @ f32(layer["wo"])
            h = _rms_norm(x, f32(layer["ln2"]))
            x = x + _gelu_tanh(h @ f32(layer["w1"])) @ f32(layer["w2"])
        x = _rms_norm(x, f32(params["lnf"]))
        logits = x @ f32(params["embed"]).T                    # [B, T, V]
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return lse - picked
