"""The expert layers' load-balancing loss in the training step
(``make_parallel_train_step``'s ``aux_weight``): built where its weight is
not a static zero, and left out of the program where it is. XLA folds no
``0.0 * x`` of floats, so a zero-weighted term would keep the loss's
forward and pay the router's two transposed products for a gradient of
zeros. The step's numbers are the same either way.

The toy's expert layer is a share (4 of 8 experts held), as in every
expert-layer cell of the benchmark: the weights are constants to the
backward there, so the balance loss is the router's only gradient."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from horovod_tpu.parallel import TransformerConfig, make_parallel_train_step
from horovod_tpu.parallel.transformer import _balance_counter, dense_nll, \
    forward

V, LAYERS = 96, 2
ROUTERS = {
    "softmax": {},
    "sigmoid_select_bias": dict(moe_score="sigmoid", moe_select_bias=True),
}


def toy(router, **over):
    base = dict(vocab=V, d_model=64, n_heads=4, n_layers=LAYERS, d_ff=32,
                n_experts=8, moe_top_k=2, moe_renormalize=True,
                experts_held=4, first_expert=2, dtype=jnp.float32,
                attn_backend="xla", unembed_dtype=jnp.float32)
    return TransformerConfig(**{**base, **ROUTERS[router], **over})


def one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("dp",))


def batch(seed=0, B=2, T=32):
    tok = np.random.default_rng(seed).integers(0, V, (B, T + 1))
    return (jnp.asarray(tok[:, :-1], jnp.int32),
            jnp.asarray(tok[:, 1:], jnp.int32))


def start(cfg, step_init):
    """Seeded parameters; a selection bias, where there is one, drawn off
    zero so that it moves the picks."""
    params, opt_state = step_init(jax.random.PRNGKey(0))
    for k, layer in enumerate(params["layers"]):
        if "router_bias" in layer:
            layer["router_bias"] = 0.5 * jax.random.normal(
                jax.random.PRNGKey(10 + k), layer["router_bias"].shape)
    return params, opt_state


def train(cfg, aux_weight, optimizer, steps):
    init_state, step = make_parallel_train_step(
        cfg, one_device_mesh(), optimizer, aux_weight=aux_weight)
    params, opt_state = start(cfg, init_state)
    losses = []
    for s in range(steps):
        tokens, labels = batch(seed=s)
        params, opt_state, loss = step(params, opt_state, tokens, labels)
        losses.append(float(loss))
    return losses, jax.device_get(params)


def router_products(cfg, aux_weight):
    """The ``dot_general``s of the lowered step whose name carries
    ``moe.route``, forward and backward."""
    init_state, step = make_parallel_train_step(
        cfg, one_device_mesh(), optax.sgd(0.1), aux_weight=aux_weight)
    state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    text = step.lower(*state, tok, tok).as_text(debug_info=True)
    named = set(re.findall(r'^(#loc\d+) = loc\("[^"]*/moe\.route/'
                           r'dot_general"', text, re.M))
    return sum(1 for ref in re.findall(
        r'stablehlo\.dot_general .* loc\((#loc\d+)\)$', text, re.M)
        if ref in named)


@pytest.mark.parametrize("router", list(ROUTERS))
def test_a_static_zero_gives_the_numbers_of_the_zero_weighted_term(router):
    """Three AdamW steps at ``aux_weight=0.0`` against the loss written as
    ``jnp.mean(nll) + 0.0 * aux``: the zero handed over as an array, which
    the builder cannot read as a static zero, so the term is built (the
    lowered step holds the router's backward, three products a layer).
    The term adds zero to the loss, and zeros to every gradient. On the
    CPU the two programs fuse differently where the router's ``dx`` (zeros)
    joins the cotangent of the layer's input, and a sum there can round in
    its last place (one entry of a norm's weight, 1e-16 relative, after
    three steps of float64 parameters): losses and every parameter, the
    router's included, are held to 1e-6 relative."""
    cfg = toy(router)
    zero = jnp.zeros((), jnp.float32)
    assert router_products(cfg, zero) == 3 * LAYERS
    got_losses, got = train(cfg, 0.0, optax.adamw(1e-2), steps=3)
    want_losses, want = train(cfg, zero, optax.adamw(1e-2), steps=3)
    np.testing.assert_allclose(got_losses, want_losses, rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-6, err_msg=str(path))


@pytest.mark.parametrize("router", list(ROUTERS))
def test_a_non_zero_weight_builds_the_term_as_before(router):
    """At 0.01 the step's loss is the NLL plus 0.01 x the balance loss of
    the forward, and the router moves under SGD: its one gradient is the
    balance loss's. At 0.0 the router does not move."""
    cfg = toy(router)
    init_state, _ = make_parallel_train_step(
        cfg, one_device_mesh(), optax.sgd(0.1))
    params, _ = start(cfg, init_state)
    tokens, labels = batch(seed=0)
    logits, aux = forward(params, tokens, cfg, one_device_mesh())
    nll = float(jnp.mean(dense_nll(logits, labels)))
    assert float(aux) > 0.5

    (loss,), moved = train(cfg, 0.01, optax.sgd(0.1), steps=1)
    np.testing.assert_allclose(loss, nll + 0.01 * float(aux), rtol=1e-6)
    assert abs(loss - nll) > 0.5 * 0.01 * float(aux)
    (loss0,), still = train(cfg, 0.0, optax.sgd(0.1), steps=1)
    np.testing.assert_allclose(loss0, nll, rtol=1e-6)
    for k, layer in enumerate(params["layers"]):
        before = np.asarray(layer["router"])
        assert np.abs(moved["layers"][k]["router"] - before).max() > 0
        np.testing.assert_array_equal(still["layers"][k]["router"], before)


@pytest.mark.parametrize("aux_weight,products", [(0.0, 1), (0.01, 3)],
                         ids=["zero", "non_zero"])
@pytest.mark.parametrize("router", list(ROUTERS))
def test_the_router_products_in_the_lowered_step(router, aux_weight,
                                                 products):
    """Under ``moe.route``: the forward product alone where the weight is a
    static zero; with the balance loss's backward, ``dW = x^T dlogits`` and
    ``dx = dlogits W^T``, where it is not."""
    assert router_products(toy(router), aux_weight) == products * LAYERS


@pytest.mark.parametrize("aux_weight,built", [(0.0, "no"), (0.01, "yes")],
                         ids=["zero", "non_zero"])
def test_the_counter_says_whether_the_term_was_built(aux_weight, built):
    """``hvd_moe_balance_loss_total{built=}`` ticks once a trace of the
    loss of a model with expert layers; a dense model ticks neither."""
    counters = {b: _balance_counter().labels(built=b) for b in ("yes", "no")}
    before = {b: c.value for b, c in counters.items()}
    router_products(toy("softmax"), aux_weight)
    router_products(toy("softmax", n_experts=0, experts_held=0,
                        first_expert=0), aux_weight)
    assert {b: c.value - before[b] for b, c in counters.items()} == {
        b: float(b == built) for b in counters}
