"""Layers of several kinds at a small size on the CPU: the gated delta rule
in chunked form (both backends; the Pallas kernels in interpret mode)
against its token-by-token recurrence, forward and every gradient; a
4-layer model of one period (three Gated DeltaNet layers, one gated
attention layer, experts with a shared expert) against the plain reference
``benchmarks/reference/lm_gdn_moe.py`` in logits, loss and the gradient of
every leaf; and what the descriptions promise: the published layer pattern,
partial RoPE, the shares of an expert-parallel group adding up with the
shared expert counted once, the refusals, and the dense and Keye-shaped
descriptions initialising and lowering exactly as before."""

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

from reference import lm_gdn_moe as reference  # noqa: E402

from horovod_tpu.obs.registry import parse_exposition, registry  # noqa: E402
from horovod_tpu.ops import gated_delta as gd  # noqa: E402
from horovod_tpu.parallel import create_hybrid_mesh, moe_ffn  # noqa: E402
from horovod_tpu.parallel import transformer as tf  # noqa: E402
from horovod_tpu.parallel.transformer import (  # noqa: E402
    GatedDeltaNet, Indexer, TransformerConfig, dense_nll, forward,
    forward_with_stats, init_params, layer_kind, make_parallel_train_step)

V, D, E, F = 96, 64, 8, 32


def toy(**over):
    """One period: gdn, gdn, gdn, attn. Hidden 64; attention 4/2 heads of
    16 with an output gate and RoPE over a quarter of a head; DeltaNet 2
    key and 4 value heads of 16, chunks of 16; 8 experts top-2 of which 4
    are held from expert 2 on, and a shared expert."""
    base = dict(vocab=V, d_model=D, n_heads=4, n_kv_heads=2, d_head=16,
                n_layers=4, qk_norm=True, rope_theta=1e7, rope_fraction=0.25,
                attn_gate=True, norm_offset=True, mlp="swiglu",
                tied_head=False, d_ff=F, n_experts=E, moe_top_k=2,
                moe_renormalize=True, experts_held=4, first_expert=2,
                shared_expert_ff=F,
                layer_pattern=("gdn", "gdn", "gdn", "attn"),
                gdn=GatedDeltaNet(2, 4, 16, 16, chunk=16, backend="xla"),
                dtype=jnp.float32, attn_backend="xla",
                unembed_dtype=jnp.float32)
    return TransformerConfig(**{**base, **over})


def sizes(cfg):
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                d_head=cfg.d_head,
                rotary_dim=int(cfg.d_head * cfg.rope_fraction),
                rope_theta=cfg.rope_theta,
                full_interval=len(cfg.layer_pattern),
                gdn_k_heads=cfg.gdn.n_k_heads, gdn_v_heads=cfg.gdn.n_v_heads,
                gdn_dk=cfg.gdn.d_k, gdn_dv=cfg.gdn.d_v,
                experts_per_tok=cfg.moe_top_k, first_expert=cfg.first_expert)


def one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("dp",))


def batch(T=64, B=2, seed=0):
    tok = np.random.default_rng(seed).integers(0, V, (B, T + 1))
    return jnp.asarray(tok[:, :-1], jnp.int32), jnp.asarray(tok[:, 1:],
                                                            jnp.int32)


def seeded_params(cfg, seed=0):
    """Seeded weights with every norm weight moved off its birth value, so
    that ``1 + w`` against ``w`` shows."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(5), a.shape)
        if a.ndim == 1 else a, params)


# -- the rule -----------------------------------------------------------------


def rule_inputs(T, seed=0, B=2, Hk=2, Hv=4, dk=16, dv=32, fast=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, Hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, Hk, dk)))
    v = jax.random.normal(ks[2], (B, T, Hv, dv))
    # ``fast``: heads that forget within a few rows (decays of e^-8 a row:
    # nothing may overflow, whatever the chunk).
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, Hv))) * (
        8.0 if fast else 0.3)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, Hv)))
    return q, k, v, g, beta


def recurrence(q, k, v, g, beta):
    """The reference's token-by-token rule; each key head serves its
    consecutive value heads."""
    rep = v.shape[2] // q.shape[2]
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    return reference.delta_rule(jnp.repeat(q, rep, axis=2),
                                jnp.repeat(k, rep, axis=2), v, g, beta)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("chunk,T,fast", [(16, 96, False), (64, 128, False),
                                          (64, 96, True), (16, 40, True)],
                         ids=["c16", "c64", "c64_padded_fast",
                              "c16_padded_fast"])
def test_chunked_rule_matches_the_recurrence(backend, chunk, T, fast):
    """Forward and the gradients of q, k, v, g and beta; T that is no
    multiple of the chunk is padded with rows that write nothing."""
    args = rule_inputs(T, fast=fast)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def chunked(*a):
        return gd.gated_delta_rule(*a, chunk=chunk, backend=backend)
    got, want = chunked(*args), recurrence(*args)
    assert got.shape == want.shape == args[2].shape
    np.testing.assert_allclose(got, want, atol=2e-6)
    g_got = jax.grad(lambda *a: jnp.sum(chunked(*a) * weight),
                     argnums=(0, 1, 2, 3, 4))(*args)
    g_want = jax.grad(lambda *a: jnp.sum(recurrence(*a) * weight),
                      argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v g beta".split(), g_got, g_want):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.max(
            jnp.abs(b))) + 1e-6, err_msg=name)


def stage_inputs(chunk, n, dtype, rep, fast, seed=0, B=1, Hk=2, dk=16,
                 dv=32):
    """The rule's inputs as ``_rule`` has them: in chunks, value heads
    grouped under their key head."""
    q, k, v, g, beta = rule_inputs(n * chunk, seed, B, Hk, Hk * rep, dk, dv,
                                   fast)

    def chunked(x, heads):
        x = jnp.moveaxis(x, 2, 1)
        return x.reshape(B, *heads, n, chunk, *x.shape[3:])
    return (chunked(q, (Hk,)).astype(dtype), chunked(k, (Hk,)).astype(dtype),
            chunked(v, (Hk, rep)).astype(dtype),
            chunked(g, (Hk, rep)).astype(jnp.float32),
            chunked(beta, (Hk, rep)).astype(jnp.float32))


def stage_oracle(q, k, v, g, beta):
    """The ``jax.numpy`` stage: what ``_prepare`` returns, and the solve."""
    heads = gd._per_value_head(q, k, v, g, beta)
    t = gd._unit_lower_inverse(gd._a_matrix(heads[1], heads[3], heads[4]))
    return gd._prepare(t, *heads) + (t,)


def unpacked(t, chunk):
    """The kernels' T, a tile's chunks side by side, as [B, Hv, N, C, C]."""
    B, Hk, rep, tiles, _, R = t.shape
    t = t.reshape(B, Hk * rep, tiles, chunk, R // chunk, chunk)
    return jnp.moveaxis(t, 4, 3).reshape(B, Hk * rep, -1, chunk, chunk)


def assert_close_by_dtype(got, want, names):
    """float32 results to float32's rounding, bf16 results to bf16's."""
    for name, a, b in zip(names, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        tol = 2e-5 if b.dtype == jnp.float32 else 2e-2
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, atol=tol * np.abs(b).max() + 1e-30,
                                   err_msg=name)


@pytest.mark.parametrize("fast", [False, True], ids=["slow", "fast"])
@pytest.mark.parametrize("rep", [1, 2], ids=["rep1", "rep2"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("chunk,n", [(16, 16), (64, 4)], ids=["c16", "c64"])
def test_local_kernels_match_the_stage_and_its_transpose(chunk, n, dtype, rep,
                                                         fast):
    """``gdn_local_fwd`` (interpreted) against ``_a_matrix``, the solve and
    ``_prepare``, all seven results, with the solve and from a given T;
    ``gdn_local_bwd`` against jax's own transpose of them, every cotangent,
    from random cotangents of all six of the walk's inputs. Two tiles (of
    eight chunks of 16, of two of 64), so a grid step works more than one."""
    from horovod_tpu.ops import pallas_gated_delta as pgd
    args = stage_inputs(chunk, n, dtype, rep, fast)
    want = stage_oracle(*args)
    *got, t = pgd.local_fwd(*args)
    assert t.shape[-1] == 128
    assert_close_by_dtype(got + [unpacked(t, chunk)], want,
                          "qg kd w u aqk e t".split())
    for a, b in zip(pgd.local_fwd(*args, t), got):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    cts = [jax.random.normal(key, x.shape).astype(x.dtype) for key, x in zip(
        jax.random.split(jax.random.PRNGKey(3), 6), want)]
    _, transpose = jax.vjp(lambda *a: stage_oracle(*a)[:6], *args)
    assert_close_by_dtype(pgd.local_bwd(*args, t, *cts),
                          transpose(tuple(cts)), "dq dk dv dg dbeta".split())


def test_float32_products_of_the_kernels_are_three_bf16_passes(monkeypatch):
    """Compiled for a chip, a float32 product of the stage is hi hi + hi lo
    + lo hi of bf16 parts (``Precision.HIGH``): run here as it would be
    there, it is within 2^-14 of the product's size and a hundred times
    nearer than one pass."""
    from horovod_tpu.ops import pallas_gated_delta as pgd
    monkeypatch.setattr(pgd, "_interpret", lambda: False)
    a, b = (jax.random.normal(key, (64, 64), jnp.float32)
            for key in jax.random.split(jax.random.PRNGKey(0)))
    want = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    hi, lo = pgd._split(a)
    assert hi.dtype == lo.dtype == jnp.bfloat16
    three = np.abs(pgd._dot3(pgd._split(a), pgd._split(b), 1, 0) - want).max()
    one = np.abs(pgd._dot(hi, pgd._split(b)[0], 1, 0) - want).max()
    assert three < 2 ** -14 * np.abs(want).max() and 100 * three < one
    # A bf16 operand is exact in its one part: two passes.
    assert pgd._split(hi) == (hi, None)
    np.testing.assert_allclose(
        pgd._dot3(pgd._split(a), (hi, None), 1, 0),
        np.asarray(a, np.float64) @ np.asarray(hi, np.float64),
        atol=2 ** -14 * np.abs(want).max())


def local_kernel_gauge(layer):
    return parse_exposition(registry().render())[
        ("hvd_gdn_local_kernel", (("layer", str(layer)),))]


def test_chunks_the_kernels_cannot_tile_fall_to_the_jax_numpy_stage():
    """Chunks of 24 do not pack into 128 rows: the Pallas backend runs its
    walk and the ``jax.numpy`` stage, gives the XLA backend's numbers, and
    the gauge says 0; the published chunk of 64 in bf16 says 1."""
    from horovod_tpu.ops import pallas_gated_delta as pgd
    assert pgd.local_tile(4, 24) is None and pgd.local_tile(128, 64) == 2
    assert pgd.local_tile(3, 64) == 1 and pgd.local_tile(12, 16) == 6
    args = rule_inputs(96)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def value_and_grads(backend, layer):
        return jax.value_and_grad(lambda *a: jnp.sum(gd.gated_delta_rule(
            *a, chunk=24, backend=backend, layer=layer) * weight),
            argnums=(0, 1, 2, 3, 4))(*args)
    got, want = value_and_grads("pallas", 5), value_and_grads("xla", None)
    assert local_kernel_gauge(5) == 0
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5 * float(jnp.max(
            jnp.abs(b))) + 1e-6)
    cell = tuple(jax.ShapeDtypeStruct(s, d) for s, d in (
        ((2, 8192, 16, 128), jnp.bfloat16), ((2, 8192, 16, 128), jnp.bfloat16),
        ((2, 8192, 32, 128), jnp.bfloat16), ((2, 8192, 32), jnp.float32),
        ((2, 8192, 32), jnp.float32)))
    jax.eval_shape(lambda *a: gd.gated_delta_rule(
        *a, chunk=64, backend="pallas", layer=6), *cell)
    assert local_kernel_gauge(6) == 1
    # The XLA backend has no kernels to run it by.
    jax.eval_shape(lambda *a: gd.gated_delta_rule(
        *a, chunk=64, backend="xla", layer=6), *cell)
    assert local_kernel_gauge(6) == 0


def test_rule_in_bf16_keeps_its_state_and_decays_in_float32():
    """bf16 operands, float32 inside: close to the float32 recurrence of
    the same (rounded) inputs over 256 rows, where a bf16 state would have
    drifted; and the output comes back in v's dtype."""
    args = rule_inputs(256, seed=3)
    low = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
    got = gd.gated_delta_rule(*low, chunk=64, backend="xla")
    assert got.dtype == jnp.bfloat16
    want = recurrence(*(a.astype(jnp.float32) for a in low))
    err = jnp.mean(jnp.abs(got.astype(jnp.float32) - want)) \
        / jnp.mean(jnp.abs(want))
    assert float(err) < 0.01


def test_rule_refuses_what_it_cannot_compute():
    q, k, v, g, beta = rule_inputs(32, Hk=3, Hv=4)
    with pytest.raises(ValueError, match="value heads"):
        gd.gated_delta_rule(q, k, v, g, beta)
    with pytest.raises(ValueError, match="backend"):
        gd.gated_delta_rule(*rule_inputs(32), backend="triton")


def test_saved_bytes_and_gauges_follow_the_shapes():
    # q, k [2, 100 -> 112, 2, 16], v [.., 4, 32] in bf16; g, beta and a
    # row of the [16, 16] solve in float32; 7 chunks x 4 heads of [16, 32]
    # states a sequence in bf16.
    want = 224 * (2 * 32 + 128) * 2 + 224 * 4 * 18 * 4 + 14 * 4 * 512 * 2
    assert gd.saved_bytes((2, 100, 2, 16), 4, 32, 2, chunk=16) == want
    gd.record_saved(7, (2, 100, 2, 16), 4, 32, 2, 16)
    samples = parse_exposition(registry().render())
    assert samples[("hvd_gdn_saved_state_bytes", (("layer", "7"),))] == want
    assert samples[("hvd_gdn_chunk", ())] == 16


# -- the model against the reference -------------------------------------------


def test_layer_kinds_follow_the_published_pattern():
    cfg = toy(n_layers=48)
    assert [i for i in range(48) if layer_kind(cfg, i) == "attn"] == \
        [i for i in range(48) if (i + 1) % 4 == 0]
    params = init_params(jax.random.PRNGKey(0), toy())
    for i, layer in enumerate(params["layers"]):
        assert ("gdn_wqkvz" in layer) == (i < 3)
        assert ("wq" in layer) == (i == 3)
        assert "shared_w" in layer and "router" in layer
    # The doubled query projection, and the norms from zero.
    assert params["layers"][3]["wq"].shape == (D, 2 * 4 * 16)
    assert float(jnp.abs(params["lnf"]).max()) == 0.0
    assert float(params["layers"][0]["gdn_norm"].min()) == 1.0
    # A description without a pattern is one kind throughout.
    assert {layer_kind(TransformerConfig(), i) for i in range(4)} == {"attn"}
    with pytest.raises(ValueError, match="kinds"):
        init_params(jax.random.PRNGKey(0), toy(layer_pattern=("ssm",)))
    with pytest.raises(ValueError, match="cfg.gdn"):
        init_params(jax.random.PRNGKey(0), toy(gdn=None))


def test_model_matches_the_reference_in_logits_loss_and_every_gradient():
    cfg = toy()
    params = seeded_params(cfg)
    tokens, labels = batch(seed=1)

    def system(p):
        logits, _ = forward(p, tokens, cfg, one_device_mesh())
        return jnp.mean(dense_nll(logits, labels)), logits

    def plain(p):
        out = reference.forward(p, tokens, labels, sizes(cfg))
        return out["loss"], out["logits"]

    (loss, logits), grads = jax.value_and_grad(system, has_aux=True)(params)
    (want_loss, want_logits), want = jax.value_and_grad(
        plain, has_aux=True)(params)
    np.testing.assert_allclose(logits, want_logits, atol=1e-4)
    assert float(loss) == pytest.approx(float(want_loss), abs=1e-5)
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    assert len(leaves) == len(jax.tree_util.tree_leaves(params))
    for (path, got), ref in zip(leaves, jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        if "router" in name:
            # In a share the router gets no gradient, here and there.
            assert float(jnp.abs(got).max()) == float(jnp.abs(ref).max()) == 0
            continue
        assert float(jnp.abs(ref).max()) > 0, name
        np.testing.assert_allclose(got, ref, atol=1e-3 * float(
            jnp.abs(ref).max()) + 1e-7, err_msg=name)


def test_check_outputs_of_the_training_forward():
    """``forward_with_stats``: each DeltaNet layer's rule output and every
    layer's routing sets, which the cell's reference check compares."""
    cfg = toy()
    params = seeded_params(cfg)
    tokens, labels = batch(seed=2)
    _, layers = forward_with_stats(params, tokens, cfg, one_device_mesh())
    want = reference.forward(params, tokens, labels, sizes(cfg))
    got_o = [e["gdn_o"] for e in layers if "gdn_o" in e]
    assert len(got_o) == 3 and "gdn_o" not in layers[3]
    for got, ref in zip(got_o, want["gdn_o"]):
        np.testing.assert_allclose(got, ref, atol=1e-5)
    for e, ref in zip(layers, want["routed"]):
        assert np.array_equal(np.sort(e["ids"], -1), np.sort(ref, -1))
        assert int(jnp.sum(e["held_load"])) + int(e["absent"]) == 2 * 64 * 2


def test_partial_rope_leaves_the_rest_of_a_head_untouched():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 3, 16))
    out = tf._rope(x, 1e7, 0.25)
    np.testing.assert_array_equal(out[..., 4:], x[..., 4:])
    np.testing.assert_allclose(out[..., :4], tf._rope(x[..., :4], 1e7))
    assert float(jnp.abs(out[:, 1:, :, :4] - x[:, 1:, :, :4]).max()) > 0.1
    np.testing.assert_allclose(out, reference._rope(x, 1e7, 4), atol=1e-6)


def test_the_shares_add_up_with_the_shared_expert_counted_once():
    """8 experts over 4 shares: the shares' routed parts plus the shared
    expert ONCE equal the uncut reference's expert layer."""
    cfg = toy(n_layers=1, layer_pattern=(), experts_held=0, first_expert=0)
    layer = init_params(jax.random.PRNGKey(3), cfg)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(4), (48, D))
    whole, _ = reference._experts(x, layer, {"experts_per_tok": 2,
                                             "first_expert": 0}, None)
    routed = []
    for first in range(0, E, 2):
        held = slice(first, first + 2)
        y, stats = moe_ffn(x, layer["router"], layer["w_up"][held],
                           layer["w_down"][held],
                           w_gate=layer["w_gate"][held], top_k=2,
                           renormalize=True, first_expert=first)
        routed.append(y)
        assert int(jnp.sum(stats["held_load"])) + int(stats["absent"]) == 96
    total = sum(routed) + tf.shared_expert(layer, x, jnp.float32)
    np.testing.assert_allclose(total, whole, atol=2e-5)
    # Counted with every share it would not.
    assert float(jnp.abs(total + 3 * tf.shared_expert(layer, x, jnp.float32)
                         - whole).max()) > 1e-2


def _routed(n, held, first, rows_sent, M=128, seed=11):
    """``M`` tokens top-2 over ``n`` experts of which ``held`` from
    ``first`` are held here, a router that sends ABOUT ``rows_sent`` of the
    2 M assignments to the held ones (a balanced one where that is less
    than a balanced load), and the held experts' weights."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (M, D))
    router = jax.random.normal(ks[1], (D, n)) * D ** -0.5
    # The first m tokens carry a mark that the router's first row reads for
    # the held experts alone: both of their assignments fall here.
    balanced = 2 * held / n
    m = max(0, min(M, round((rows_sent - M * balanced) / (2 - balanced))))
    x = x.at[:, 0].set(jnp.where(jnp.arange(M) < m, 1.0, 0.0))
    router = router.at[0].set(0.0).at[0, first:first + held].set(9.0)
    w = {"w_gate": jax.random.normal(ks[2], (held, D, F)) * D ** -0.5,
         "w_up": jax.random.normal(ks[3], (held, D, F)) * D ** -0.5,
         "w_down": jax.random.normal(ks[4], (held, F, D)) * F ** -0.5}
    return x, router, w


def _plain_experts(first):
    """``reference._experts`` with no shared expert, as a loss of (x, w,
    router)."""
    def plain(x, w, router):
        layer = dict(w, router=router, shared_gate=jnp.zeros((D, 1)),
                     shared_up=jnp.zeros((D, 1)),
                     shared_down=jnp.zeros((1, D)),
                     shared_w=jnp.zeros((D, 1)))
        y, _ = reference._experts(x, layer, {"experts_per_tok": 2,
                                             "first_expert": first}, None)
        return jnp.sum(y * jnp.cos(y)), y
    return plain


def _close_at_each_leafs_scale(grads, want_grads):
    """float32 sums of up to 250 rows an expert: 2e-5 of a leaf's scale."""
    for got, ref in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(
            got, ref, atol=2e-5 * max(1.0, float(jnp.abs(ref).max())))


def chunks_gauge(layer):
    return parse_exposition(registry().render())[
        ("hvd_moe_chunks_entered", (("layer", str(layer)),))]


@pytest.mark.parametrize("load", ["balanced", "one_chunk_over",
                                  "nearly_every_token"])
@pytest.mark.parametrize("n,held,n_chunks", [(8, 4, 2), (16, 2, 6),
                                             (64, 2, 16)],
                         ids=["a_half", "an_eighth", "a_thirty_second"])
def test_a_small_share_drops_nothing_whatever_its_load(n, held, n_chunks,
                                                       load):
    """A half, an eighth (the Keye cell's: six chunks of 1.5 x a balanced
    load) and a thirty-second of the experts held, under a balanced router
    (chunk 0 alone is worked), one that sends a chunk's worth too many, and
    one that sends nearly every token here: every assignment is worked
    whatever the load, ``stats["chunks"]`` and the gauge say how many
    chunks that took, forward and the gradients of x and of every expert
    weight against each held expert applied to every token."""
    from horovod_tpu.parallel import moe
    first, M = 5 if n > 8 else 2, 128
    n_rows = M * 2
    cap = max(8, -(-n_rows * held * 3 // (n * 2) // 8) * 8)
    assert -(-n_rows // cap) == n_chunks
    sent = {"balanced": 0, "one_chunk_over": min(1.5 * cap,
                                                 (cap + n_rows) / 2),
            "nearly_every_token": n_rows - 6}[load]
    x, router, w = _routed(n, held, first, sent)

    def system(x, w):
        y, stats = moe_ffn(x, router, w["w_up"], w["w_down"],
                           w_gate=w["w_gate"], top_k=2, renormalize=True,
                           first_expert=first)
        return jnp.sum(y * jnp.cos(y)), (y, stats)

    (_, (y, stats)), grads = jax.value_and_grad(
        system, argnums=(0, 1), has_aux=True)(x, w)
    (_, want), want_grads = jax.value_and_grad(
        _plain_experts(first), argnums=(0, 1), has_aux=True)(x, w, router)
    rows = int(jnp.sum(stats["held_load"]))
    assert rows + int(stats["absent"]) == n_rows
    entered = {"balanced": 1, "one_chunk_over": 2,
               "nearly_every_token": n_chunks}[load]
    assert -(-rows // cap) == entered, (rows, cap)
    assert int(stats["chunks"]) == entered
    moe.record_routing(40 + n_chunks, stats["held_load"], stats["absent"],
                       chunks=stats["chunks"])
    assert chunks_gauge(40 + n_chunks) == entered
    np.testing.assert_allclose(y, want, atol=2e-5)
    _close_at_each_leafs_scale(grads, want_grads)


def _scatter_counted_held_experts(x, ids, gates, w_gate, w_up, w_down,
                                  first, n_experts):
    """``moe._held_experts`` with the bookkeeping it had before it lost its
    scatters and gathers: ``argsort`` of the groups, ``bincount`` of them
    (a scatter-add), the weights gathered by the sort's permutation."""
    from horovod_tpu.parallel import moe
    M, _ = x.shape
    k, held = ids.shape[1], w_up.shape[0]
    local = ids.reshape(-1) - first
    here = (local >= 0) & (local < held)
    group = jnp.where(here, local, held).astype(jnp.int32)
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    weight = gates.reshape(-1)[order]
    n_rows = M * k
    unit = moe._GMM_ROWS if n_rows % moe._GMM_ROWS == 0 else 8
    cap = min(n_rows, max(unit, -(-n_rows * held * 3 // (n_experts * 2)
                                  // unit) * unit))
    n_chunks = -(-n_rows // cap)
    pad = n_chunks * cap - n_rows
    order = jnp.pad(order, (0, pad)).reshape(n_chunks, cap)
    weight = jnp.pad(weight, (0, pad)).reshape(n_chunks, cap)
    y = moe._chunks(k, x, (w_gate, w_up, w_down), weight, order, sizes, ends)
    return y.astype(x.dtype), sizes, moe._entered(ends, cap)


# The shares and loads of ``test_a_small_share_drops_nothing_whatever_its_load``
# as (n, held, first, rows sent), and every expert held (one chunk).
BOOKKEEPING = {
    **{f"{share}-{load}": (n, held, 5 if n > 8 else 2, sent)
       for share, (n, held) in (("a_half", (8, 4)), ("an_eighth", (16, 2)),
                                ("a_thirty_second", (64, 2)))
       for load, sent in (("balanced", 0), ("one_chunk_over", None),
                          ("nearly_every_token", 256 - 6))},
    "every_expert_held": (8, 8, 0, 0),
}


def _bookkeeping_case(name):
    """(x, router, w, n, first) of a case of ``BOOKKEEPING``: the rows sent
    as ``test_a_small_share_drops_nothing_whatever_its_load`` sends them;
    every expert held as ``test_ep2_router_gradient_against_the_reference``
    builds its eight."""
    n, held, first, sent = BOOKKEEPING[name]
    if held == n:
        x, router, w = _routed(n, n // 2, 0, 0)
        return (x, router, {k: jnp.concatenate([v, v[::-1] * 0.5])
                            for k, v in w.items()}, n, first)
    if sent is None:
        cap = max(8, -(-256 * held * 3 // (n * 2) // 8) * 8)
        sent = min(1.5 * cap, (cap + 256) / 2)
    x, router, w = _routed(n, held, first, sent)
    return x, router, w, n, first


def _top2(x, router):
    """``moe_ffn``'s softmax router, top-2 renormalised: (probs, ids,
    gates)."""
    probs = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), -1)
    top, ids = jax.lax.top_k(probs, 2)
    return probs, ids, top / jnp.sum(top, -1, keepdims=True)


@pytest.mark.parametrize("case", list(BOOKKEEPING))
def test_the_bookkeeping_without_scatters_is_bit_for_bit_the_same(case):
    """One stable sort that carries the weights, and counts by a dense
    compare-and-sum, in place of ``argsort``, ``bincount`` and a gather:
    the same permutation, so y, the assignments per held expert, the chunks
    entered and the gradients of x, of every expert weight and of the
    assignments' weights are equal bit for bit."""
    from horovod_tpu.parallel import moe
    x, router, w, n, first = _bookkeeping_case(case)
    _, ids, gates = _top2(x, router)

    def run(held_experts):
        def loss(x, w, gates):
            y, sizes, chunks = held_experts(
                x, ids, gates, w["w_gate"], w["w_up"], w["w_down"], first, n)
            return jnp.sum(y * jnp.cos(y)), (y, sizes, chunks)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))(x, w, gates)

    (_, got), got_grads = run(moe._held_experts)
    (_, want), want_grads = run(_scatter_counted_held_experts)
    assert int(jnp.sum(got[1])) > 0
    for a, b in zip(jax.tree_util.tree_leaves((got, got_grads)),
                    jax.tree_util.tree_leaves((want, want_grads))):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("case", list(BOOKKEEPING))
def test_the_balance_loss_counts_what_the_scatter_counted(case):
    """``moe_ffn``'s balance loss from assignments counted by a compare-
    and-sum: its share of each expert is the ``bincount`` of the kept ids
    over N·k, and the loss the one the scatter-add of N·k constants gave,
    to rounding; ``held_load`` is the held groups' ``bincount`` exactly."""
    x, router, w, n, first = _bookkeeping_case(case)
    held = w["w_up"].shape[0]
    _, stats = moe_ffn(x, router, w["w_up"], w["w_down"], w_gate=w["w_gate"],
                       top_k=2, renormalize=True, first_expert=first)
    probs, ids, _ = _top2(x, router)
    np.testing.assert_array_equal(stats["ids"], ids)
    flat = ids.reshape(-1)
    scattered = jnp.zeros((n,), jnp.float32).at[flat].add(1.0 / flat.size)
    np.testing.assert_allclose(
        stats["aux"], jnp.sum(scattered * jnp.mean(probs, axis=0)) * n,
        rtol=1e-6)
    np.testing.assert_array_equal(
        stats["held_load"],
        jnp.bincount(flat, length=n)[first:first + held].astype(jnp.int32))


def _biased_sigmoid(x, router, w, first):
    """``moe_ffn``'s keyword arguments for sigmoid scores picked through a
    selection bias (top-2, renormalised, x 2.5), and the same routing as
    the kept scores were taken before they were read off a dense compare:
    gathered by ``take_along_axis``, (ids, gates)."""
    n = router.shape[1]
    bias = jnp.linspace(-0.05, 0.05, n, dtype=jnp.float32)
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    ids = jax.lax.top_k(scores + bias, 2)[1]
    top = jnp.take_along_axis(scores, ids, axis=-1)
    gates = top / jnp.sum(top, -1, keepdims=True) * 2.5
    if w["w_up"].shape[0] < n:
        gates = jax.lax.stop_gradient(gates)
    return (dict(top_k=2, renormalize=True, first_expert=first,
                 score="sigmoid", select_bias=bias, scale=2.5), ids, gates)


@pytest.mark.parametrize("case", list(BOOKKEEPING))
def test_the_kept_scores_under_a_selection_bias_are_the_gathered_ones(case):
    """Sigmoid scores picked through a selection bias: the kept scores read
    off the dense compare of the ids against the experts, in place of
    ``take_along_axis``, give y and the gradients of x, of every expert
    weight and of the router equal bit for bit (the router's are zeros in
    a share, whose weights are constants to the backward)."""
    from horovod_tpu.parallel import moe
    x, router, w, n, first = _bookkeeping_case(case)

    def got(x, w, router):
        kw = _biased_sigmoid(x, router, w, first)[0]
        y, _ = moe_ffn(x, router, w["w_up"], w["w_down"], w_gate=w["w_gate"],
                       **kw)
        return jnp.sum(y * jnp.cos(y)), y

    def want(x, w, router):
        _, ids, gates = _biased_sigmoid(x, router, w, first)
        y = moe._held_experts(x, ids, gates, w["w_gate"], w["w_up"],
                              w["w_down"], first, n)[0]
        return jnp.sum(y * jnp.cos(y)), y

    runs = [jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True))(
        x, w, router) for f in (got, want)]
    assert float(jnp.abs(runs[0][0][1]).max()) > 0
    for a, b in zip(*map(jax.tree_util.tree_leaves, runs)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def _result_types(op, text):
    """The result type of every ``stablehlo.<op>`` in lowered text (generic
    form: a scatter's update region comes before its signature)."""
    signature = r"\(.*?\)" if op == "gather" else r"\(.*?\}\) : \(.*?\)"
    return re.findall(rf'"stablehlo\.{op}"{signature} -> (tensor<[^>]*>)',
                      text, re.S)


@pytest.mark.parametrize("router_kind", ["softmax", "sigmoid_biased"])
@pytest.mark.parametrize("case", ["a_half-balanced", "an_eighth-balanced",
                                  "a_thirty_second-balanced"])
def test_the_only_scatters_are_the_rows_scatter_adds(case, router_kind):
    """A share's value and gradient, lowered, under either router: every
    scatter is a scatter-add of rows into [M, D] (y forward, dx backward,
    chunk 0's and the loop's), and no gather has a result of M·k single
    values (the weights are carried through the sort, and the kept scores
    under a selection bias read off a compare); nor is the balance loss's
    share a scatter."""
    x, router, w, n, first = _bookkeeping_case(case)
    M, D = x.shape
    kw = (dict(top_k=2, renormalize=True, first_expert=first)
          if router_kind == "softmax"
          else _biased_sigmoid(x, router, w, first)[0])

    def loss(x, w):
        y, stats = moe_ffn(x, router, w["w_up"], w["w_down"],
                           w_gate=w["w_gate"], **kw)
        return jnp.sum(y * jnp.cos(y)) + stats["aux"]
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        x, w).as_text()
    assert _result_types("scatter", text) == [f"tensor<{M}x{D}xf32>"] * 4
    gathers = _result_types("gather", text)
    assert gathers and all(re.fullmatch(rf"tensor<\d+x{D}xf(32|64)>", t)
                           for t in gathers), gathers
    assert text.count('"stablehlo.sort"') == 1


def test_chunks_that_hold_no_row_are_not_in_the_program():
    """The gradient of the six-chunk share, lowered: ONE loop each way
    (its trip count is the load) and nothing conditional, so no chunk is a
    skipped turn of a scan or an untaken branch whose transpose writes
    zeros the size of x and of every weight (PERF.md PRs 32 and 37); and
    no zeros of a weight's shape are made to be added to."""
    x, router, w = _routed(16, 2, 5, 0)

    def loss(x, w):
        y, _ = moe_ffn(x, router, w["w_up"], w["w_down"], w_gate=w["w_gate"],
                       top_k=2, renormalize=True, first_expert=5)
        return jnp.sum(y * jnp.cos(y))
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w).as_text()
    assert text.count("stablehlo.while") == 2
    assert not re.search(r"stablehlo\.(case|if)\b", text)
    made = re.findall(r"stablehlo\.(?:broadcast_in_dim|constant).*-> "
                      r"tensor<2x(?:64x32|32x64)xf32>", text)
    assert not made, made


@pytest.mark.parametrize("popular", [False, True],
                         ids=["balanced", "second_chunk_entered"])
def test_ep2_router_gradient_against_the_reference(popular):
    """Two ranks hold half the experts each, so every expert is present and
    the assignments' weights are differentiable: forward and the gradients
    of x, the ROUTER and every expert weight against the reference holding
    all eight, under a balanced router (each rank works chunk 0 alone) and
    one that sends rank 0 more than a chunk (its loop is entered, and the
    custom backward returns that chunk's weights' cotangent too)."""
    from jax.sharding import PartitionSpec as P
    n, M = 8, 128
    x, router, w = _routed(n, n // 2, 0, 230 if popular else 0)
    w = {k: jnp.concatenate([v, v[::-1] * 0.5]) for k, v in w.items()}
    mesh = create_hybrid_mesh(ep=2, devices=jax.devices()[:2])

    def local(x, w, router):
        y, stats = moe_ffn(x, router, w["w_up"], w["w_down"],
                           w_gate=w["w_gate"], top_k=2, renormalize=True,
                           axis_name="ep")
        return y, stats["chunks"][None]

    def system(x, w, router):
        y, chunks = jax.shard_map(
            local, mesh=mesh, in_specs=(P("ep"), P("ep"), P()),
            out_specs=(P("ep"), P("ep")), check_vma=False)(x, w, router)
        return jnp.sum(y * jnp.cos(y)), (y, chunks)

    (_, (y, chunks)), grads = jax.jit(jax.value_and_grad(
        system, argnums=(0, 1, 2), has_aux=True))(x, w, router)
    (_, want), want_grads = jax.value_and_grad(
        _plain_experts(0), argnums=(0, 1, 2), has_aux=True)(x, w, router)
    assert chunks.tolist() == ([2, 1] if popular else [1, 1])
    np.testing.assert_allclose(y, want, atol=2e-5)
    assert float(jnp.abs(want_grads[2]).max()) > 1e-2
    _close_at_each_leafs_scale(grads, want_grads)


# -- the step -----------------------------------------------------------------


def test_train_step_learns_and_remat_changes_nothing():
    tokens, labels = batch(seed=3)
    losses = {}
    for remat in (False, True):
        cfg = toy(remat=remat)
        init_state, step = make_parallel_train_step(
            cfg, one_device_mesh(), optax.adamw(1e-2), aux_weight=0.0)
        params, opt_state = init_state(jax.random.PRNGKey(0))
        losses[remat] = []
        for _ in range(4):
            params, opt_state, loss = step(params, opt_state, tokens, labels)
            losses[remat].append(float(loss))
    assert losses[False][-1] < losses[False][0] - 1.0
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)


def test_dp2_reduces_after_the_backward_and_equals_one_device():
    """Layers of two kinds keep the plan that reduces after the backward
    (one carry serves uniform layers only); the result is the one-device
    step's."""
    cfg = toy()
    tokens, labels = batch(B=4, seed=4)
    out = []
    for mesh in (one_device_mesh(),
                 create_hybrid_mesh(dp=2, devices=jax.devices()[:2])):
        init_state, step = make_parallel_train_step(
            cfg, mesh, optax.sgd(0.1), aux_weight=0.0)
        params, opt_state = init_state(jax.random.PRNGKey(7))
        with jax.default_matmul_precision("highest"):
            params, _, loss = step(params, opt_state, tokens, labels)
        out.append((float(loss), jax.device_get(params)))
    (loss1, p1), (loss2, p2) = out
    assert loss1 == pytest.approx(loss2, rel=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(p1),
                            jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(a, b, atol=5e-6, err_msg=str(path))


@pytest.mark.parametrize("axes", [{"tp": 2}, {"sp": 2}])
def test_meshes_the_new_kinds_cannot_take_are_refused(axes):
    mesh = create_hybrid_mesh(devices=jax.devices()[:2], **axes)
    with pytest.raises(NotImplementedError, match="gdn"):
        make_parallel_train_step(toy(), mesh, optax.sgd(0.1))
    with pytest.raises(NotImplementedError):
        forward(init_params(jax.random.PRNGKey(0), toy()), batch()[0], toy(),
                mesh)


def test_serving_refuses_the_new_kinds():
    from horovod_tpu.parallel.transformer import init_kv_cache
    dense_ffn = toy(n_experts=0, experts_held=0, first_expert=0,
                    shared_expert_ff=0)
    with pytest.raises(NotImplementedError, match="several kinds"):
        init_kv_cache(dense_ffn, 2, 32)
    with pytest.raises(NotImplementedError):
        init_kv_cache(TransformerConfig(norm_offset=True), 2, 32)
    with pytest.raises(ValueError, match="shared_expert_ff"):
        init_params(jax.random.PRNGKey(0),
                    dataclasses.replace(dense_ffn, shared_expert_ff=8))


# -- what was there stays as it was -------------------------------------------

# sha256 of ``step.lower(...).as_text()`` and of the seeded parameters' bytes
# on the parent of PR 32 (commit b488b94), this installation, under this
# suite's conftest (x64 on). ResNet-50's builders (``training.py``,
# ``models/``) are files PR 32 does not touch. The two ``gdn_shaped``
# entries: on the parent of PR 34 (commit fa1c571), whose rule gained a
# second gate beside this one (``toy()`` on the XLA backend, and on the
# kernels, interpreted, at a chunk they tile). ``gdn_shaped_kernels`` was
# replaced by PR 35 (eb742929... on its parent): under ``"pallas"`` the
# mixer's convolution + SiLU is now the kernels ``conv_silu_fwd`` /
# ``conv_silu_bwd`` (128 columns and 64 rows tile); ``gdn_shaped``, on the
# XLA backend, held through the move of ``_causal_conv`` to ``ops/``.
# The three that hold an expert layer were replaced by PR 37 (1d1cf750...,
# 936eb950..., 0094aa78... on its parent): a share's chunks after the first
# are a loop under a custom VJP (``parallel/moe._chunks``), where they were
# turns of a scan under ``jax.checkpoint``. The drawn parameters held.
# The same three were replaced again by design when the expert layer's
# bookkeeping lost its scatters and gathers (9fb6b3a3..., c4f4eb67...,
# 816ee8f0... on its parent, commit ba93722): one stable sort carries the
# weights, and the assignments are counted by compare-and-sum. All three
# are built at ``aux_weight=0.0`` and were replaced again by design when a
# static zero weight stopped building the balance loss (58237b18...,
# affdf708..., 3c9e9ba2... on its parent, commit 8aae949): no router
# backward, no share and no mean of the probabilities. The ``lm`` entry,
# built at the default weight, held.
BEFORE = {
    "lm": ("dfa287d6c7f2df30b47a56f3a974f52d6c5439d08b6458204ab7a720766602c7",
           "3b085b22eeff759f2bc5510aee823ac7371bdff9ed119cdc635d6a1cfe64f42b"),
    "keye_shaped": (
        "702ac09c72d10f229c49e443f299da77fa06eaafe78c281a1be014c0428d9a31",
        "4e2db50b6056ce5652824f4e44e1891ddad1472eef652f54a0e99bc6e07a846d"),
    "gdn_shaped": (
        "489e8b4f303de4f64f61142ea0d314e567248c12dbdf62e232fcebc9cd0bcc1f",
        "0160a1c24e722d75c6e593c53e04b9b046be8f483611eb864e13f6de1ab3d248"),
    "gdn_shaped_kernels": (
        "593158f5f1dc5372011419aed88fdeafa8d2255dd1542f99a669e6842f48569e",
        "0160a1c24e722d75c6e593c53e04b9b046be8f483611eb864e13f6de1ab3d248"),
}
DESCRIPTIONS = {
    "lm": (dict(vocab=256, d_model=64, n_heads=4, n_layers=2, d_ff=128,
                attn_backend="xla"), {}),
    "keye_shaped": (dict(
        vocab=96, d_model=64, n_heads=4, n_kv_heads=2, d_head=16, n_layers=2,
        qk_norm=True, rope_theta=1e7, mlp="swiglu", tied_head=False,
        indexer=Indexer(2, 8, 16), d_ff=32, n_experts=8, moe_top_k=2,
        moe_renormalize=True, experts_held=4, first_expert=2,
        dtype=jnp.float32, attn_backend="xla", unembed_dtype=jnp.float32),
        {"aux_weight": 0.0}),
    "gdn_shaped": (toy, {"aux_weight": 0.0}),
    "gdn_shaped_kernels": (
        lambda: toy(gdn=GatedDeltaNet(2, 4, 16, 16, chunk=64,
                                      backend="pallas")),
        {"aux_weight": 0.0}),
}


@pytest.mark.parametrize("name", list(DESCRIPTIONS))
def test_descriptions_of_one_kind_initialise_and_lower_as_before(name):
    fields, step_args = DESCRIPTIONS[name]
    cfg = fields() if callable(fields) else TransformerConfig(**fields)
    init_state, step = make_parallel_train_step(
        cfg, create_hybrid_mesh(devices=jax.devices()[:1], dp=1),
        optax.adamw(3e-4), **step_args)
    state = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    text = step.lower(*state, tok, tok).as_text()
    params = init_params(jax.random.PRNGKey(7), cfg)
    drawn = hashlib.sha256(b"".join(
        jnp.asarray(x).tobytes()
        for x in jax.tree_util.tree_leaves(params))).hexdigest()
    assert (hashlib.sha256(text.encode()).hexdigest(), drawn) == BEFORE[name]
