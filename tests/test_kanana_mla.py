"""Kanana-2-30B-A3B's block (the DeepSeek-V3 block) at a small size on the
CPU: latent attention on every layer with its decoupled key part ROTATED
by position, a leading dense layer, then sigmoid-routed experts with a
selection bias and a scaling factor beside an ungated shared MLP, against
``benchmarks/reference/lm_mla_moe.py`` in logits, loss and the gradient of
every leaf; the published interleaved layout taken by a fixed permutation
(and its wrong readings refused); θ = 0 lowering to the program the
unrotated mixer had; the shares of an expert-parallel group adding up to
the uncut layer; no [.., T, T] scores at 192 / 128 under the kernels."""

import dataclasses
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

from reference import lm_mla_moe as reference  # noqa: E402

from horovod_tpu.parallel import create_hybrid_mesh, moe_ffn  # noqa: E402
from horovod_tpu.parallel import transformer as tf  # noqa: E402
from horovod_tpu.parallel.transformer import (  # noqa: E402
    LatentAttention, TransformerConfig, dense_nll, forward,
    forward_with_stats, init_params, make_parallel_train_step,
    mla_from_interleaved)

V, D, E, F = 96, 64, 16, 16
F32 = jnp.float32
THETA = 1e4


def toy(**over):
    """The cut at a small size: a leading dense layer (width 96), then
    expert layers, every one latent attention of 4 heads at 16 + 8 / 16
    over a latent of 24, its 8 rotated columns at base 1e4; 16 experts of
    16, top-6 under sigmoid scores with a selection bias and the scaling
    factor, of which 4 are held from expert 2 on; two shared experts as
    one ungated MLP of 32."""
    base = dict(vocab=V, d_model=D, n_heads=4, n_layers=5, mlp="swiglu",
                tied_head=False, d_ff=F, n_experts=E, moe_top_k=6,
                moe_renormalize=True, experts_held=4, first_expert=2,
                shared_expert_ff=2 * F, shared_expert_gate=False,
                moe_score="sigmoid", moe_select_bias=True, moe_scale=2.448,
                dense_layers=1, dense_ff=96, layer_pattern=("mla",),
                mla=LatentAttention(24, 16, 8, 16, rope_theta=THETA),
                dtype=F32, attn_backend="xla", unembed_dtype=F32)
    return TransformerConfig(**{**base, **over})


def sizes(cfg):
    return dict(n_heads=cfg.n_heads, kv_rank=cfg.mla.kv_rank,
                d_nope=cfg.mla.d_nope, d_rope=cfg.mla.d_shared,
                d_v=cfg.mla.d_v, rope_theta=cfg.mla.rope_theta,
                experts_per_tok=cfg.moe_top_k, first_expert=cfg.first_expert,
                scaling=cfg.moe_scale, eps=cfg.norm_eps)


def one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("dp",))


def batch(T=64, B=2, seed=0):
    tok = np.random.default_rng(seed).integers(0, V, (B, T + 1))
    return jnp.asarray(tok[:, :-1], jnp.int32), jnp.asarray(tok[:, 1:],
                                                            jnp.int32)


def published(cfg, seed=0):
    """Seeded weights, read as a checkpoint in the PUBLISHED layout (the
    rotated columns interleaved), with every vector leaf moved off its
    birth value (norm weights and the selection bias, so that it
    selects)."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return jax.tree_util.tree_map(
        lambda a: (a + 0.1 * jax.random.normal(jax.random.PRNGKey(5),
                                               a.shape)).astype(F32)
        if a.ndim == 1 else a.astype(F32), params)


def system_logits(params, cfg, tokens):
    return forward(params, tokens, cfg, one_device_mesh())[0]


def _gap(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


# -- the model against the reference ------------------------------------------


@pytest.mark.parametrize("layers", [2, 5], ids=["dense_and_experts",
                                               "five_layers"])
def test_model_matches_the_reference_in_logits_loss_and_every_gradient(
        layers):
    """The system on the published weights through the loader's
    permutation, rotation on, against the reference on the published
    weights as they are; the gradients of the published leaves, so the
    permutation is on the path that is checked."""
    cfg = toy(n_layers=layers)
    params = published(cfg)
    tokens, labels = batch()

    def system_loss(p):
        logits = system_logits(mla_from_interleaved(p, cfg), cfg, tokens)
        return jnp.mean(dense_nll(logits, labels)), logits
    (loss, logits), grads = jax.value_and_grad(system_loss, has_aux=True)(
        params)
    want = reference.forward(params, tokens, labels, sizes(cfg), q_block=32)
    np.testing.assert_allclose(logits, want["logits"], atol=2e-4)
    np.testing.assert_allclose(loss, want["loss"], atol=1e-5)
    want_grads = jax.grad(lambda p: reference.forward(
        p, tokens, labels, sizes(cfg), q_block=32)["loss"])(params)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(got, ref, atol=2e-3 * scale + 1e-7,
                                   err_msg=name)
        if "router" in name:
            assert scale == 0.0     # a share does not train its router
        else:
            assert scale > 0.0, name


def test_check_outputs_of_the_training_forward():
    """What the chip's check compares: each layer's attention output and
    each expert layer's routing sets."""
    cfg = toy()
    params = published(cfg)
    tokens, labels = batch()
    _, layers = jax.jit(lambda p, t: forward_with_stats(
        p, t, cfg, one_device_mesh()))(mla_from_interleaved(params, cfg),
                                       tokens)
    want = reference.forward(params, tokens, labels, sizes(cfg), q_block=32)
    assert len(want["mla_o"]) == 5 and all("mla_o" in e for e in layers)
    for extras, ref in zip(layers, want["mla_o"]):
        np.testing.assert_allclose(extras["mla_o"], ref, atol=1e-5)
    assert "ids" not in layers[0] and len(want["routed"]) == 4
    for extras, own in zip(layers[1:], want["routed"]):
        assert bool(jnp.all(jnp.sort(extras["ids"], -1)
                            == jnp.sort(own, -1)))


# -- the published layout -----------------------------------------------------


def test_the_loader_permutes_the_rotated_columns_and_nothing_else():
    cfg = toy(n_layers=2)
    params = published(cfg)
    mine = mla_from_interleaved(params, cfg)
    back = mla_from_interleaved(mine, cfg, inverse=True)
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)
    wq, got = params["layers"][0]["mla_wq"], mine["layers"][0]["mla_wq"]
    head = lambda w, h: w[:, h * 24:(h + 1) * 24]  # noqa: E731
    for h in range(4):
        np.testing.assert_array_equal(head(got, h)[:, :16],
                                      head(wq, h)[:, :16])
        np.testing.assert_array_equal(head(got, h)[:, 16:20],
                                      head(wq, h)[:, 16::2])
        np.testing.assert_array_equal(head(got, h)[:, 20:],
                                      head(wq, h)[:, 17::2])
    kva, got = params["layers"][0]["mla_wkva"], mine["layers"][0]["mla_wkva"]
    np.testing.assert_array_equal(got[:, :24], kva[:, :24])
    np.testing.assert_array_equal(got[:, 24:], np.concatenate(
        [kva[:, 24::2], kva[:, 25::2]], axis=1))
    # Without positions there is nothing to reorder.
    flat = toy(mla=LatentAttention(24, 16, 8, 16))
    assert mla_from_interleaved(params, flat) is params


@pytest.mark.parametrize("wrong", ["rotating_the_wrong_pairs",
                                   "permuting_without_rotating",
                                   "another_base", "key_part_unrotated"])
def test_a_wrong_reading_of_the_layout_does_not_agree(monkeypatch, wrong):
    """The reference on the published columns against the system on the
    same weights read wrongly: the published columns as they are (the
    system's pairs (i, i + 4) are then not the published pairs), the
    permutation with no rotation, the rotation at base 1e6 for 1e4, the
    query rotated and the shared key part not."""
    cfg = toy(n_layers=2)
    params = published(cfg)
    tokens, labels = batch()
    want = reference.forward(params, tokens, labels, sizes(cfg),
                             q_block=32)["logits"]
    right = system_logits(mla_from_interleaved(params, cfg), cfg, tokens)
    assert _gap(right, want) < 1e-5
    if wrong == "rotating_the_wrong_pairs":
        got = system_logits(params, cfg, tokens)
    elif wrong == "permuting_without_rotating":
        got = system_logits(mla_from_interleaved(params, cfg),
                            toy(n_layers=2, mla=LatentAttention(24, 16, 8,
                                                                16)), tokens)
    elif wrong == "another_base":
        other = toy(n_layers=2, mla=LatentAttention(24, 16, 8, 16,
                                                    rope_theta=1e6))
        got = system_logits(mla_from_interleaved(params, other), other,
                            tokens)
    else:
        rope = tf._rope
        monkeypatch.setattr(tf, "_rope", lambda x, theta: x if x.shape[2] == 1
                            else rope(x, theta))
        got = system_logits(mla_from_interleaved(params, cfg), cfg, tokens)
    assert _gap(got, want) > 1e-3, wrong


# θ = 0: the unrotated mixer's lowered program, as the parent commit of
# PR 38 lowered it (the same function of this file's toy, x64 on as in the
# suite, float32), by sha256 of the lowered text. The toy holds an expert
# layer, whose bookkeeping lost its scatters and gathers by design after
# commit ba93722 (14af0c6d... there): the hash is the unrotated mixer's
# beside the new bookkeeping.
UNROTATED_SHA = \
    "3d51796573df302411077b54ea89782c8741a2feb56e8b9d775cb03717582417"


def lowered_sha(cfg):
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)

    def loss(p, t):
        logits = system_logits(p, cfg, t)
        return jnp.mean(dense_nll(logits, t))
    text = jax.jit(jax.grad(loss)).lower(shapes, tokens).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def test_theta_zero_lowers_the_mixer_it_had():
    """No positions: the training gradient's lowered program is the one the
    mixer without a rotation lowered to, bit for bit; and the rotation
    adds its ops under the scope ``mla.rope`` inside ``attn.mla``."""
    flat = toy(mla=LatentAttention(24, 16, 8, 16))
    assert lowered_sha(flat) == UNROTATED_SHA
    assert lowered_sha(dataclasses.replace(
        flat, mla=LatentAttention(24, 16, 8, 16, rope_theta=0.0))) \
        == UNROTATED_SHA
    text = jax.jit(lambda p, t: system_logits(p, toy(), t)).lower(
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), toy())),
        jax.ShapeDtypeStruct((2, 64), jnp.int32)).as_text(debug_info=True)
    assert "attn.mla/mla.rope" in text
    assert "mla.rope" not in jax.jit(
        lambda p, t: system_logits(p, flat, t)).lower(
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), flat)),
        jax.ShapeDtypeStruct((2, 64), jnp.int32)).as_text(debug_info=True)


# -- the share and the model ---------------------------------------------------


def _expert_layer(n_tokens=48, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    draw = lambda i, *s: jax.random.normal(ks[i], s, F32) * s[-2] ** -0.5  # noqa: E731
    layer = {"router": draw(0, D, E),
             "router_bias": 0.3 * jax.random.normal(ks[1], (E,), F32),
             "w_gate": draw(2, E, D, F), "w_up": draw(3, E, D, F),
             "w_down": draw(4, E, F, D), "shared_gate": draw(5, D, 2 * F),
             "shared_up": draw(6, D, 2 * F),
             "shared_down": draw(7, 2 * F, D)}
    return layer, jax.random.normal(ks[8], (n_tokens, D), F32)


def test_eight_shares_add_up_to_the_uncut_layer():
    """ep = 8 at the toy size: eight shares of 2 of the 16 experts, top-6
    of score + bias, renormalised, x 2.448, and the shared MLP of two
    experts' width counted once, are the reference's uncut layer."""
    layer, x = _expert_layer()
    hp = dict(experts_per_tok=6, first_expert=0, scaling=2.448)
    with jax.default_matmul_precision("highest"):
        want, own = reference._experts(x, layer, hp, None)
        total = tf.shared_expert(layer, x, F32)
        for first in range(0, E, 2):
            cut = lambda w: w[first:first + 2]  # noqa: E731
            y, stats = moe_ffn(
                x, layer["router"], cut(layer["w_up"]), cut(layer["w_down"]),
                w_gate=cut(layer["w_gate"]), top_k=6, renormalize=True,
                first_expert=first, score="sigmoid",
                select_bias=layer["router_bias"], scale=2.448)
            total = total + y
            assert int(stats["absent"]) + int(stats["held_load"].sum()) \
                == 6 * x.shape[0]
            assert bool(jnp.all(jnp.sort(stats["ids"], -1)
                                == jnp.sort(own, -1)))
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_train_step_learns_on_one_device_and_over_ep():
    cfg = toy()
    tokens, labels = batch(T=32, B=4)
    losses = {}
    for name, mesh in (("one", create_hybrid_mesh(
            devices=jax.devices()[:1], dp=1)), ("ep2", create_hybrid_mesh(
                devices=jax.devices()[:2], dp=1, ep=2))):
        init_state, step = make_parallel_train_step(
            cfg, mesh, optax.adamw(1e-2), aux_weight=0.0)
        params, opt = init_state(jax.random.PRNGKey(0))
        seen = []
        for _ in range(4):
            params, opt, loss = step(params, opt, tokens, labels)
            seen.append(float(loss))
        assert seen[-1] < seen[0], seen
        losses[name] = seen
    np.testing.assert_allclose(losses["one"][0], losses["ep2"][0], rtol=1e-5)


def test_no_scores_leave_the_kernels_at_192_over_128():
    """The published head widths (q and k 128 + 64, v 128) under
    ``attn_backend="pallas"``, T twice the kernels' block: the training
    gradient's program calls the flash kernels forward and backward and
    holds no array [.., T, T], in the kernels' bodies or outside them."""
    T = 1024
    cfg = toy(n_layers=2, n_heads=2, mla=LatentAttention(
        32, 128, 64, 128, rope_theta=1e6), attn_backend="pallas",
        dtype=jnp.bfloat16)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((1, T), jnp.int32)

    def loss(p, t):
        return jnp.mean(dense_nll(system_logits(p, cfg, t), t))
    text = str(jax.make_jaxpr(jax.grad(loss))(shapes, tokens))
    kernels = set(re.findall(r"name=(flash_\w+)", text))
    assert "flash_fwd" in kernels \
        and any(k.startswith("flash_bwd") for k in kernels), kernels
    assert not re.search(rf"\[(?:\d+,)*{T},{T}\]", text)
