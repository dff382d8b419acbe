"""Kimi-Linear's block at a small size on the CPU (``test_kda_rule.py`` has
the rule and the flash kernels by themselves): the latent-attention mixer,
the delta attention mixer and the five-layer cut (a leading dense layer, then
sigmoid-routed experts with a selection bias, a scaling factor and an
ungated shared expert) against ``benchmarks/reference/lm_kda_mla_moe.py``
in logits, loss and the gradient of every leaf; the shares of an
expert-parallel group adding up to the uncut layer; the refusals."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

from reference import lm_kda_mla_moe as reference  # noqa: E402

from horovod_tpu.parallel import create_hybrid_mesh, moe_ffn  # noqa: E402
from horovod_tpu.parallel import transformer as tf  # noqa: E402
from horovod_tpu.parallel.transformer import (  # noqa: E402
    KimiDeltaAttention, LatentAttention, TransformerConfig, dense_nll,
    forward, forward_with_stats, init_params, layer_kind,
    make_parallel_train_step, param_specs)

V, D, E, F = 96, 64, 8, 32
F32 = jnp.float32


def toy(**over):
    """The five-layer cut: kda + dense, kda, kda, mla, kda. Hidden 64; 2
    delta-attention heads of 16 (their gates' rank), chunks of 16; latent
    attention of 4 heads at 16 + 8 / 16 over a latent of 24; dense width
    96; 8 experts top-2 under sigmoid scores, a selection bias and a
    scaling factor, of which 4 are held from expert 2 on; an ungated
    shared expert."""
    base = dict(vocab=V, d_model=D, n_heads=4, n_layers=5, mlp="swiglu",
                tied_head=False, d_ff=F, n_experts=E, moe_top_k=2,
                moe_renormalize=True, experts_held=4, first_expert=2,
                shared_expert_ff=F, shared_expert_gate=False,
                moe_score="sigmoid", moe_select_bias=True, moe_scale=2.446,
                dense_layers=1, dense_ff=96, norm_eps=1e-5,
                layer_pattern=("kda", "kda", "kda", "mla"),
                kda=KimiDeltaAttention(2, 16, chunk=16, backend="xla"),
                mla=LatentAttention(24, 16, 8, 16),
                dtype=F32, attn_backend="xla", unembed_dtype=F32)
    return TransformerConfig(**{**base, **over})


def sizes(cfg):
    return dict(n_heads=cfg.n_heads, kv_rank=cfg.mla.kv_rank,
                d_nope=cfg.mla.d_nope, d_shared=cfg.mla.d_shared,
                d_v=cfg.mla.d_v, kda_heads=cfg.kda.n_heads,
                kda_head_dim=cfg.kda.d_head, experts_per_tok=cfg.moe_top_k,
                first_expert=cfg.first_expert, scaling=cfg.moe_scale,
                eps=cfg.norm_eps)


def one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("dp",))


def batch(T=64, B=2, seed=0):
    tok = np.random.default_rng(seed).integers(0, V, (B, T + 1))
    return jnp.asarray(tok[:, :-1], jnp.int32), jnp.asarray(tok[:, 1:],
                                                            jnp.int32)


def seeded_params(cfg, seed=0):
    """Seeded weights with every vector leaf moved off its birth value
    (norm weights, dt_bias, and the selection bias, so that it selects)."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return jax.tree_util.tree_map(
        lambda a: (a + 0.1 * jax.random.normal(jax.random.PRNGKey(5),
                                               a.shape)).astype(F32)
        if a.ndim == 1 else a.astype(F32), params)


# -- the model ----------------------------------------------------------------


def test_layer_kinds_leaves_and_specs_agree():
    cfg = toy()
    assert [layer_kind(cfg, i) for i in range(5)] == [
        "kda", "kda", "kda", "mla", "kda"]
    params = init_params(jax.random.PRNGKey(0), cfg)
    specs = param_specs(cfg, create_hybrid_mesh(devices=jax.devices()[:2],
                                                dp=1, ep=2))
    is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)  # noqa: E731
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(specs, is_leaf=is_spec)
    for i, layer in enumerate(params["layers"]):
        assert ("kda_wqkv" in layer) == (i != 3)
        assert ("mla_wq" in layer) == (i == 3)
        assert ("router" in layer) == ("router_bias" in layer) \
            == ("shared_gate" in layer) == (i > 0)
        assert "shared_w" not in layer
        assert layer["w_up"].shape == ((D, 96) if i == 0 else (4, D, F))
    assert specs["layers"][1]["w_up"] == jax.sharding.PartitionSpec(
        "ep", None, None)
    assert specs["layers"][0]["w_up"] == jax.sharding.PartitionSpec(
        None, None)
    first = params["layers"][0]
    assert first["kda_wf_down"].shape == (D, 16) \
        and first["kda_wf_up"].shape == (16, 32) \
        and first["kda_a_log"].shape == (2,) \
        and first["kda_dt_bias"].shape == (32,)
    assert 0.0 <= float(first["kda_a_log"].min()) \
        and float(first["kda_a_log"].max()) <= np.log(16.0)
    assert float(jnp.abs(params["layers"][1]["router_bias"]).max()) == 0.0
    for name, what in (("kda", "cfg.kda"), ("mla", "cfg.mla")):
        with pytest.raises(ValueError, match=what):
            init_params(jax.random.PRNGKey(0), toy(**{name: None}))
    with pytest.raises(ValueError, match="dense_ff"):
        init_params(jax.random.PRNGKey(0), toy(dense_ff=0))


@pytest.mark.parametrize("pattern,layers", [(("mla",), 1), (("kda",), 1),
                                            (None, 5)],
                         ids=["mla_mixer", "kda_mixer", "five_layers"])
def test_model_matches_the_reference_in_logits_loss_and_every_gradient(
        pattern, layers):
    """One latent-attention layer, one delta-attention layer (each with its
    expert layer), and the five-layer cut with its leading dense layer."""
    cfg = toy() if pattern is None else toy(
        layer_pattern=pattern, n_layers=layers, dense_layers=0, dense_ff=0)
    params = seeded_params(cfg)
    tokens, labels = batch()
    mesh = one_device_mesh()

    def system_loss(p):
        logits, _ = forward(p, tokens, cfg, mesh)
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
    cfg = toy()
    params = seeded_params(cfg)
    tokens, labels = batch()
    _, layers = jax.jit(lambda p, t: forward_with_stats(
        p, t, cfg, one_device_mesh()))(params, tokens)
    want = reference.forward(params, tokens, labels, sizes(cfg), q_block=32)
    got_o = [e["kda_o"] for e in layers if "kda_o" in e]
    assert len(got_o) == 4 and "kda_o" not in layers[3]
    for got, ref in zip(got_o, want["kda_o"]):
        np.testing.assert_allclose(got, ref, atol=1e-5)
    np.testing.assert_allclose(layers[3]["mla_o"], want["mla_o"][0],
                               atol=1e-5)
    assert "ids" not in layers[0] and len(want["routed"]) == 4
    for extras, own in zip(layers[1:], want["routed"]):
        assert bool(jnp.all(jnp.sort(extras["ids"], -1)
                            == jnp.sort(own, -1)))


def test_the_pallas_backends_run_the_same_model():
    """The kernels (interpreted) in place of the XLA forms, T = 128 so that
    the flash kernels tile: the same logits."""
    cfg = toy()
    params = seeded_params(cfg)
    tokens, _ = batch(T=128, B=1)
    kernels = dataclasses.replace(
        cfg, attn_backend="pallas",
        kda=dataclasses.replace(cfg.kda, backend="pallas"),
        mla=LatentAttention(24, 16, 8, 128))
    plain = dataclasses.replace(cfg, mla=kernels.mla)
    params = seeded_params(plain)
    a, _ = forward(params, tokens, kernels, one_device_mesh())
    b, _ = forward(params, tokens, plain, one_device_mesh())
    np.testing.assert_allclose(a, b, atol=2e-4)
    # A value width the kernels cannot tile is an error there, not [T, T].
    with pytest.raises(ValueError, match="no fallback"):
        forward(seeded_params(cfg), tokens,
                dataclasses.replace(cfg, attn_backend="pallas"),
                one_device_mesh())


# -- the share and the model ---------------------------------------------------


def _expert_layer(n_tokens=48, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    draw = lambda i, *s: jax.random.normal(ks[i], s, F32) * s[-2] ** -0.5  # noqa: E731
    layer = {"router": draw(0, D, E),
             "router_bias": 0.3 * jax.random.normal(ks[1], (E,), F32),
             "w_gate": draw(2, E, D, F), "w_up": draw(3, E, D, F),
             "w_down": draw(4, E, F, D), "shared_gate": draw(5, D, F),
             "shared_up": draw(6, D, F), "shared_down": draw(7, F, D)}
    return layer, jax.random.normal(ks[8], (n_tokens, D), F32)


HP = dict(experts_per_tok=2, first_expert=0, scaling=2.446)


def _share(layer, x, first, held, axis_name=None):
    cut = lambda w: w[first:first + held]  # noqa: E731
    y, stats = moe_ffn(x, layer["router"], cut(layer["w_up"]),
                       cut(layer["w_down"]), w_gate=cut(layer["w_gate"]),
                       top_k=2, renormalize=True, first_expert=first,
                       axis_name=axis_name, score="sigmoid",
                       select_bias=layer["router_bias"], scale=2.446)
    return y, stats


def test_the_shares_add_up_to_the_uncut_layer():
    """Sigmoid scores, the selection bias, renormalisation and the scaling
    factor: the outputs of all four shares of the experts, with the
    ungated shared expert counted once, are the reference's uncut layer."""
    layer, x = _expert_layer()
    with jax.default_matmul_precision("highest"):
        want, own = reference._experts(x, layer, HP, None)
        total = tf.shared_expert(layer, x, F32)
        for first in range(0, E, 2):
            y, stats = _share(layer, x, first, 2)
            total = total + y
            assert int(stats["absent"]) + int(stats["held_load"].sum()) \
                == 2 * x.shape[0]
            assert bool(jnp.all(jnp.sort(stats["ids"], -1)
                                == jnp.sort(own, -1)))
    np.testing.assert_allclose(total, want, atol=2e-5)
    # The bias selects: without it other experts are kept.
    unbiased = reference._experts(
        x, dict(layer, router_bias=jnp.zeros((E,))), HP, None)[1]
    assert bool(jnp.any(jnp.sort(unbiased, -1) != jnp.sort(own, -1)))


def test_ep4_exchange_gives_the_uncut_layer():
    layer, x = _expert_layer(n_tokens=64)
    mesh = Mesh(np.array(jax.devices()[:4]), ("ep",))
    P = jax.sharding.PartitionSpec
    specs = {k: P("ep") if k in ("w_gate", "w_up", "w_down") else P()
             for k in layer}

    def local(layer, x):
        y, _ = moe_ffn(x, layer["router"], layer["w_up"], layer["w_down"],
                       w_gate=layer["w_gate"], top_k=2, renormalize=True,
                       axis_name="ep", score="sigmoid",
                       select_bias=layer["router_bias"], scale=2.446)
        return y + tf.shared_expert(layer, x, F32)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.shard_map(
            local, mesh=mesh, in_specs=(specs, P("ep")), out_specs=P("ep"),
            check_vma=False))(layer, x)
        want, _ = reference._experts(x, layer, HP, None)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_moe_ffn_refuses_an_unknown_score():
    layer, x = _expert_layer()
    with pytest.raises(ValueError, match="sigmoid"):
        moe_ffn(x, layer["router"], layer["w_up"], layer["w_down"],
                score="tanh")


# -- the step, and what is refused ---------------------------------------------


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
        bias = params["layers"][1]["router_bias"]
        seen = []
        for _ in range(4):
            params, opt, loss = step(params, opt, tokens, labels)
            seen.append(float(loss))
        assert seen[-1] < seen[0], seen
        # The selection bias gets no gradient and stays where it was.
        np.testing.assert_array_equal(params["layers"][1]["router_bias"],
                                      bias)
        losses[name] = seen
    np.testing.assert_allclose(losses["one"][0], losses["ep2"][0], rtol=1e-5)


@pytest.mark.parametrize("axes", [{"dp": 1, "tp": 2}, {"dp": 1, "sp": 2}],
                         ids=["tp", "sp"])
def test_meshes_the_new_kinds_cannot_take_are_refused(axes):
    mesh = create_hybrid_mesh(devices=jax.devices()[:2], **axes)
    for pattern, kind in ((("kda",), "kda"), (("mla",), "mla")):
        with pytest.raises(NotImplementedError, match=f"'{kind}' layer"):
            make_parallel_train_step(
                toy(layer_pattern=pattern, dense_layers=0, dense_ff=0),
                mesh, optax.adamw(1e-3))


def test_serving_and_the_pipeline_refuse_the_new_kinds():
    from horovod_tpu.parallel import make_pp_transformer_train_step
    dense = dict(n_experts=0, experts_held=0, first_expert=0,
                 shared_expert_ff=0, dense_layers=0, dense_ff=0, mlp="gelu",
                 tied_head=True, moe_score="softmax", moe_select_bias=False,
                 moe_scale=1.0, shared_expert_gate=True, norm_eps=1e-6)
    for pattern in (("kda",), ("mla",)):
        cfg = toy(layer_pattern=pattern, **dense)
        with pytest.raises(NotImplementedError, match="'kda' layer's state"):
            tf.init_kv_cache(cfg, 2, 16)
        with pytest.raises(NotImplementedError, match="'mla' layer's latent"):
            make_pp_transformer_train_step(
                cfg, create_hybrid_mesh(devices=jax.devices()[:2], pp=2),
                optax.adamw(1e-3), 2)
    with pytest.raises(NotImplementedError, match="leading dense layers"):
        tf.prefill(None, None, None, 0, toy(
            n_experts=0, experts_held=0, first_expert=0, shared_expert_ff=0))
