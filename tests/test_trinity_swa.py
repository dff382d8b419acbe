"""Trinity-Mini's block (the afmoe block) at a small size on the CPU, and the
windowed flash kernels it brought: the kernels, interpreted, against banded
float32 attention in every schedule the gate can pick, forward and the
three gradients, at windows that are and are not a multiple of the tile;
a window of W +- 1 telling apart; the causal kernels' programs as they were
(``window=None``); the schedule visiting only the band's tile pairs; the
model (window layers with RoPE, a full layer without positions, the output
gate from a projection of its own, sandwich norms, the scaled embedding,
sigmoid-routed experts) against ``benchmarks/reference/lm_swa_moe.py`` in
logits, loss and the gradient of every leaf; sixteen shares of the expert
layer adding up to the uncut layer."""

import dataclasses
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

from reference import lm_swa_moe as reference  # noqa: E402

import horovod_tpu.ops.pallas_attention as pa  # noqa: E402
from horovod_tpu.parallel import moe_ffn  # noqa: E402
from horovod_tpu.parallel import transformer as tf  # noqa: E402
from horovod_tpu.parallel.transformer import (  # noqa: E402
    SlidingWindow, TransformerConfig, dense_nll, forward, forward_with_stats,
    gate_from_projection, init_params)

F32 = jnp.float32


def _force_plan(monkeypatch, plan):
    """Pin the VMEM gate to one schedule (``tests/test_pallas_attention``'s
    way); the [B,T,H,D] entry is jitted, so older traces are dropped."""
    def forced(T, D, itemsize, *, b, bwd, packed=False):
        if bwd:
            return plan, None if plan == "split" else pa._VMEM_DEFAULT
        if plan == "resident":
            return plan, pa._VMEM_DEFAULT
        return "streamed", None
    monkeypatch.setattr(pa, "_plan", forced)
    jax.clear_caches()


def _qkv(T, seed, H=2, G=2, D=128):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(1, T, H, D), F32) * 0.5
    k, v = (jnp.asarray(rng.randn(1, T, G, D), F32) * 0.5 for _ in range(2))
    return q, k, v, jnp.asarray(rng.randn(1, T, H, D), F32)


def _kernel(q, k, v, window):
    return pa.flash_attention(q, k, v, causal=True, backend="pallas",
                              interpret=True, fallback=False, window=window)


def _dense(q, k, v, window):
    return pa._xla_attention(q, k, v, True, q.shape[-1] ** -0.5, window)


# (T, preferred tile, window): a window a multiple of the tile (one band
# edge), one that is not (two), and one below the preferred tile, which
# the window caps (128); T spans several bands in each.
_WINDOWS = [(1024, 128, 256), (1024, 256, 300), (512, 256, 200)]


@pytest.mark.parametrize("T,want,window", _WINDOWS,
                         ids=[f"T{t}-b{b}-w{w}" for t, b, w in _WINDOWS])
@pytest.mark.parametrize("plan", ["resident", "streamed", "split"])
def test_windowed_kernels_match_banded_attention(monkeypatch, plan, T, want,
                                                 window):
    """Output and the three gradients of every schedule, against dense
    attention masked to the band, at the module's standing tolerances."""
    monkeypatch.setattr(pa, "_WANT_BLOCK", want)
    _force_plan(monkeypatch, plan)
    q, k, v, cot = _qkv(T, T + window)
    np.testing.assert_allclose(_kernel(q, k, v, window),
                               _dense(q, k, v, window), rtol=2e-4, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(_kernel(*a, window) * cot),
                   argnums=(0, 1, 2))(q, k, v)
    want_g = jax.grad(lambda *a: jnp.sum(_dense(*a, window) * cot),
                      argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want_g, "qkv"):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-4,
                                   err_msg="d" + name)
    jax.clear_caches()


def test_a_window_one_key_wider_or_narrower_is_another_answer(monkeypatch):
    """At float32 and a window of 130 keys, the kernels agree with the
    band of 130 to 2e-5 and with the bands of 129 and 131 by no less than
    a hundred times that: the band's edge is exact to the key."""
    _force_plan(monkeypatch, "resident")
    q, k, v, _ = _qkv(512, 7)
    got = _kernel(q, k, v, 130)
    assert float(jnp.max(jnp.abs(got - _dense(q, k, v, 130)))) < 2e-5
    for other in (129, 131):
        assert float(jnp.max(jnp.abs(got - _dense(q, k, v, other)))) > 2e-3
    jax.clear_caches()


def test_windows_the_kernels_cannot_tile_raise_or_fall_back():
    q, k, v, _ = _qkv(256, 3)
    with pytest.raises(ValueError, match="window"):
        _kernel(q, k, v, 100)
    with pytest.raises(ValueError, match="causal"):
        pa.flash_attention(q, k, v, causal=False, window=128)
    np.testing.assert_allclose(
        pa.flash_attention(q, k, v, causal=True, backend="pallas",
                           interpret=True, window=100),
        _dense(q, k, v, 100), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("T,window,want,visited,causal", [
    (8192, 2048, 512, 70, 136),       # the cell: 5 of 16 k tiles at most
    (1024, 256, 128, 21, 36),
    (1024, 300, 256, 9, 10),
    (512, 200, 512, 9, 10),           # the window caps the tile at 128
    (1024, 1024, 512, 3, 3),          # a window of T is causal attention
])
def test_the_schedule_visits_the_bands_tile_pairs(monkeypatch, T, window,
                                                  want, visited, causal):
    """``tile_pairs`` (what the gauge ``hvd_swa_tile_pairs`` reads) from
    the schedule, and the schedule's pairs covering every (query, key) of
    the band exactly once, with a mask wherever they reach outside it."""
    monkeypatch.setattr(pa, "_WANT_BLOCK", want)
    assert pa.tile_pairs(T, window) == (visited, causal)
    b, sub = pa._blocks(T, window)
    n = T // b
    if window >= T or T > 2048:
        return
    last, plain, edges, _ = pa._band(b, window, n)
    seen = np.zeros((T, T), np.int32)
    masked = np.zeros((T, T), bool)
    for qi in range(n):
        for d in range(min(qi, last) + 1):
            kind = 0 if d == 0 else (None if d <= plain else d)
            assert d == 0 or (d <= plain) != (d in edges)
            for r0, rows, cols, m, band in pa._pair_blocks(b, sub, kind,
                                                           window):
                blk = (slice(qi * b + r0, qi * b + r0 + rows),
                       slice((qi - d) * b, (qi - d) * b + cols))
                seen[blk] += 1
                masked[blk] |= m or band is not None
    i, j = np.indices((T, T))
    inside = (j <= i) & (i - j < window)
    assert (seen[inside] == 1).all() and (seen <= 1).all()
    assert masked[~inside & (seen > 0)].all()


# The causal kernels' training programs (value and gradient of the [B, T,
# H, D] and the packed entries, every schedule, interpreted, x64 on as in
# the suite), by sha256 of the lowered text, as the parent commit of the
# windowed kernels lowered them: ``window=None`` hands XLA the same
# program, so the same outputs and gradients bit for bit.
CAUSAL_SHA = {
    "resident-bthd":
        "67ddf6deebcf6a2c7921a3e84d9e392d6bb577980bf2d0b1b270b1004e1eb4dd",
    "resident-packed":
        "d2180254c386d3a92cd50adea5b682db2d98239261dc675889a82956585bfcfb",
    "streamed-bthd":
        "b8ad4e7d2e7b02d87063ecf3274b325721f7db7b210bc81114c44282d8bca986",
    "streamed-packed":
        "d6d5b5531a88e84ce3373921ce0dc8f69be523f124a1624a09b5ee1b3d314fa4",
    "split-bthd":
        "c6d1eaa086a7c7a395cb20dc11f23b384cf1fcf5a8f433ab2da8fa892262c064",
    "split-packed":
        "32d74c07fdd4f3b036f4e1b58ddfa8eb2ca1f3fd7b3ccb7e13e765f55f64ba59",
}


@pytest.mark.parametrize("case", list(CAUSAL_SHA))
def test_window_none_is_the_causal_program_it_was(monkeypatch, case):
    plan, entry = case.split("-")
    monkeypatch.setattr(pa, "_WANT_BLOCK", 128)
    _force_plan(monkeypatch, plan)
    B, T, H, D = 1, 512, 2, 128
    rng = np.random.RandomState(40)
    x = jnp.asarray(rng.randn(B, T, H * 3 * D), F32) * 0.5
    cot = jnp.asarray(rng.randn(B, T, H * D), F32)

    def kern(x, **window):
        if entry == "packed":
            return pa.flash_attention_qkv(x, H, causal=True, interpret=True)
        r = x.reshape(B, T, H, 3 * D)
        return pa.flash_attention(
            r[..., :D], r[..., D:2 * D], r[..., 2 * D:], causal=True,
            backend="pallas", interpret=True, fallback=False,
            **window).reshape(B, T, H * D)
    f = jax.jit(jax.value_and_grad(lambda x: jnp.sum(kern(x) * cot)))
    text = f.lower(x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == CAUSAL_SHA[case]
    if entry == "bthd":
        # A window of T or more is the same call, bit for bit.
        g = jax.jit(jax.value_and_grad(
            lambda x: jnp.sum(kern(x, window=T) * cot)))
        for a, b in zip(jax.tree_util.tree_leaves(f(x)),
                        jax.tree_util.tree_leaves(g(x))):
            np.testing.assert_array_equal(a, b)
    jax.clear_caches()


# -- the block against the reference ------------------------------------------

V, D, E, F = 96, 64, 16, 16
WINDOW = 40


def toy(**over):
    """The cut at a small size: a leading dense window layer (width 96),
    then window, full, window, window layers, 4 query and 2 key/value heads
    of 16, a window of 40 keys with RoPE at base 1e4 and no positions on
    the full layer, the output gate, sandwich norms, the embedding times
    sqrt(64); 16 experts of 16, top-4 under sigmoid scores with a selection
    bias, renormalised, x 2.826, of which 4 are held from expert 2 on,
    beside one ungated shared expert."""
    base = dict(vocab=V, d_model=D, n_heads=4, n_kv_heads=2, d_head=16,
                n_layers=5, qk_norm=True, mlp="swiglu", tied_head=False,
                norm_eps=1e-5, layer_pattern=("swa", "swa", "attn", "swa",
                                              "swa"),
                swa=SlidingWindow(WINDOW, 1e4), attn_gate=True,
                post_norms=True, embed_scale=D ** 0.5, dense_layers=1,
                dense_ff=96, d_ff=F, n_experts=E, moe_top_k=4,
                moe_renormalize=True, moe_score="sigmoid",
                moe_select_bias=True, moe_scale=2.826, experts_held=4,
                first_expert=2, shared_expert_ff=F, shared_expert_gate=False,
                dtype=F32, attn_backend="xla", unembed_dtype=F32)
    return TransformerConfig(**{**base, **over})


def sizes(cfg):
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                d_head=cfg.d_head, window=cfg.swa.window,
                rope_theta=cfg.swa.rope_theta,
                kinds=["window" if k == "swa" else "full"
                       for k in (tf.layer_kind(cfg, i)
                                 for i in range(cfg.n_layers))],
                embed_scale=cfg.embed_scale, experts_per_tok=cfg.moe_top_k,
                first_expert=cfg.first_expert, scaling=cfg.moe_scale,
                eps=cfg.norm_eps)


def one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("dp",))


def batch(T=64, B=2, seed=0):
    tok = np.random.default_rng(seed).integers(0, V, (B, T + 1))
    return jnp.asarray(tok[:, :-1], jnp.int32), jnp.asarray(tok[:, 1:],
                                                            jnp.int32)


def published(cfg, seed=0):
    """Seeded weights in the PUBLISHED layout (the gate a projection of its
    own), with every vector leaf moved off its birth value (norm weights
    and the selection bias, so that it selects)."""
    params = gate_from_projection(init_params(jax.random.PRNGKey(seed), cfg),
                                  cfg, inverse=True)
    return jax.tree_util.tree_map(
        lambda a: (a + 0.1 * jax.random.normal(jax.random.PRNGKey(5),
                                               a.shape)).astype(F32)
        if a.ndim == 1 else a.astype(F32), params)


def system_logits(params, cfg, tokens):
    return forward(params, tokens, cfg, one_device_mesh())[0]


def test_model_matches_the_reference_in_logits_loss_and_every_gradient():
    """The system on the published weights through the gate's permutation
    against the reference on the published weights as they are; the
    gradients of the published leaves, so the permutation is on the path
    that is checked. T 64 spans the window 40 and a query block of 32."""
    cfg = toy()
    params = published(cfg)
    tokens, labels = batch()

    def system_loss(p):
        logits = system_logits(gate_from_projection(p, cfg), cfg, tokens)
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


@pytest.mark.parametrize("wrong", ["no_window", "window_plus_one",
                                   "rope_on_full", "no_rope", "no_gate",
                                   "no_post_norms", "no_embed_scale",
                                   "gate_as_query"])
def test_a_wrong_block_does_not_agree(monkeypatch, wrong):
    """The reference against the system with one part of the block wrong:
    the window left out or one key wider, RoPE on the full layer too or on
    no layer, the gate or the post norms dropped, the embedding unscaled,
    the published gate and query projections swapped."""
    cfg = toy(n_layers=3)
    params = published(cfg)
    tokens, labels = batch()
    want = reference.forward(params, tokens, labels, sizes(cfg),
                             q_block=32)["logits"]

    def gap(cfg, p=params):
        got = system_logits(gate_from_projection(p, cfg), cfg, tokens)
        return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert gap(cfg) < 1e-4
    swa = cfg.swa
    if wrong == "no_gate":
        monkeypatch.setattr(tf, "_output_gate", lambda o, gate: o)
        got = gap(cfg)
    elif wrong == "gate_as_query":
        layers = [dict(l, wq=l["w_attn_gate"], w_attn_gate=l["wq"])
                  for l in params["layers"]]
        got = gap(cfg, dict(params, layers=layers))
    else:
        got = gap(dataclasses.replace(cfg, **{
            "no_window": {"swa": SlidingWindow(64, swa.rope_theta)},
            "window_plus_one": {"swa": SlidingWindow(WINDOW + 1,
                                                     swa.rope_theta)},
            "rope_on_full": {"rope_theta": swa.rope_theta},
            "no_rope": {"swa": SlidingWindow(WINDOW)},
            "no_post_norms": {"post_norms": False},
            "no_embed_scale": {"embed_scale": 1.0},
        }[wrong]))
    assert got > 1e-2, wrong


def test_check_outputs_of_the_training_forward():
    """What the chip's check compares: each layer's attention output and
    the q, k, v its kernels got, and each expert layer's routing sets; the
    gauge of the tile pairs stamped for the window layers alone."""
    from horovod_tpu.obs.registry import parse_exposition, registry
    cfg = toy()
    params = published(cfg)
    tokens, labels = batch()
    _, layers = jax.jit(lambda p, t: forward_with_stats(
        p, t, cfg, one_device_mesh()))(gate_from_projection(params, cfg),
                                       tokens)
    want = reference.forward(params, tokens, labels, sizes(cfg), q_block=32)
    assert len(want["attn_o"]) == 5 and all("attn_o" in e for e in layers)
    for extras, ref in zip(layers, want["attn_o"]):
        np.testing.assert_allclose(extras["attn_o"], ref, atol=1e-4)
        assert [x.shape for x in extras["attn_in"]] == [(2, 64, 4, 16)] * 3
    assert "ids" not in layers[0] and len(want["routed"]) == 4
    for extras, own in zip(layers[1:], want["routed"]):
        assert bool(jnp.all(jnp.sort(extras["ids"], -1)
                            == jnp.sort(own, -1)))
    stamped = {dict(labels)["layer"] for (name, labels) in parse_exposition(
        registry().render()) if name == "hvd_swa_tile_pairs"}
    assert {"0", "1", "3", "4"} <= stamped


def test_the_gate_layout_is_a_permutation_of_columns():
    cfg = toy(n_layers=2)
    params = published(cfg)
    mine = gate_from_projection(params, cfg)
    back = gate_from_projection(mine, cfg, inverse=True)
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)
    layer, got = params["layers"][0], mine["layers"][0]["wq"]
    assert "w_attn_gate" not in mine["layers"][0] and got.shape == (D, 128)
    for h in range(4):
        np.testing.assert_array_equal(got[:, h * 32:h * 32 + 16],
                                      layer["wq"][:, h * 16:(h + 1) * 16])
        np.testing.assert_array_equal(
            got[:, h * 32 + 16:(h + 1) * 32],
            layer["w_attn_gate"][:, h * 16:(h + 1) * 16])
    assert gate_from_projection(params, toy(attn_gate=False)) is params


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """ep = 16 at the toy size: sixteen shares of 1 of the 16 experts,
    top-4 of score + bias, renormalised, x 2.826, and the shared expert
    counted once, are the reference's uncut layer."""
    ks = jax.random.split(jax.random.PRNGKey(1), 9)
    draw = lambda i, *s: jax.random.normal(ks[i], s, F32) * s[-2] ** -0.5  # noqa: E731
    layer = {"router": draw(0, D, E),
             "router_bias": 0.3 * jax.random.normal(ks[1], (E,), F32),
             "w_gate": draw(2, E, D, F), "w_up": draw(3, E, D, F),
             "w_down": draw(4, E, F, D), "shared_gate": draw(5, D, F),
             "shared_up": draw(6, D, F), "shared_down": draw(7, F, D)}
    x = jax.random.normal(ks[8], (48, D), F32)
    hp = dict(experts_per_tok=4, first_expert=0, scaling=2.826)
    from reference.lm_kda_mla_moe import _experts
    with jax.default_matmul_precision("highest"):
        want, own = _experts(x, layer, hp, None)
        total = tf.shared_expert(layer, x, F32)
        for first in range(E):
            cut = lambda w: w[first:first + 1]  # noqa: E731
            y, stats = moe_ffn(
                x, layer["router"], cut(layer["w_up"]), cut(layer["w_down"]),
                w_gate=cut(layer["w_gate"]), top_k=4, renormalize=True,
                first_expert=first, score="sigmoid",
                select_bias=layer["router_bias"], scale=2.826)
            total = total + y
            assert int(stats["absent"]) + int(stats["held_load"].sum()) \
                == 4 * x.shape[0]
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_no_scores_leave_the_kernels_in_a_window_layer():
    """The published head width under ``attn_backend="pallas"``, T four
    times the window: the training gradient's program calls the flash
    kernels forward and backward in the window layer and the full one, and
    holds no array [.., T, T]."""
    T = 1024
    cfg = toy(n_layers=3, n_heads=2, n_kv_heads=1, d_head=128,
              swa=SlidingWindow(256, 1e4), attn_backend="pallas",
              dtype=jnp.bfloat16, layer_pattern=("swa", "swa", "attn"))
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((1, T), jnp.int32)

    def loss(p, t):
        return jnp.mean(dense_nll(system_logits(p, cfg, t), t))
    text = str(jax.make_jaxpr(jax.grad(loss))(shapes, tokens))
    kernels = re.findall(r"name=(flash_\w+)", text)
    assert "flash_fwd" in kernels \
        and any(k.startswith("flash_bwd") for k in kernels), kernels
    assert not re.search(rf"\[(?:\d+,)*{T},{T}\]", text)
