"""The linear-attention mixers' causal convolution + SiLU as one op
(``ops/causal_conv.py``) on the CPU: the kernels ``conv_silu_fwd`` /
``conv_silu_bwd`` (interpreted) against ``jax.vjp`` of the float32
``jax.numpy`` form, forward, dx and dw, over shapes that cross every edge
the kernels have (a block of rows' first rows from the block before, the
zeros before the start, the rows past the end, several blocks of columns,
several steps inside a block, an input wider than the taps); an impulse;
the fall to the XLA form and the gauge that says so; and toy Qwen3-Next-
and Kimi-shaped models whose loss and every gradient leaf agree between
the two backends."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

from horovod_tpu.obs.registry import parse_exposition, registry  # noqa: E402
from horovod_tpu.ops import causal_conv as cc  # noqa: E402
from horovod_tpu.ops import pallas_causal_conv as pcc  # noqa: E402
from horovod_tpu.parallel.transformer import (  # noqa: E402
    GatedDeltaNet, KimiDeltaAttention, LatentAttention, TransformerConfig,
    dense_nll, forward, init_params, layer_kind)

F32 = jnp.float32


def plain(x, w):
    """The float32 XLA form over the columns the taps cover."""
    return jax.nn.silu(cc._causal_conv(x[..., :w.shape[1]].astype(F32),
                                       w.astype(F32)))


def inputs(B, T, C, Cx, W, dtype, seed=0):
    kx, kw, ky = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kx, (B, T, Cx), F32).astype(dtype),
            0.5 * jax.random.normal(kw, (W, C), F32),
            jax.random.normal(ky, (B, T, C), F32).astype(dtype))


def conv_gauge(layer):
    return parse_exposition(registry().render())[
        ("hvd_conv_kernel", (("layer", str(layer)),))]


# B, T, C, Cx, W: one block and one step; three blocks of 16 rows (the rows
# before a block, the zeros before the first, nothing after the last);
# blocks of 64 rows under an input wider than the taps; two steps in a
# block, five blocks of columns, two taps; four steps, two lane groups.
SHAPES = [(1, 16, 128, 128, 4), (2, 48, 128, 128, 4), (1, 192, 256, 384, 4),
          (2, 128, 640, 640, 2), (1, 256, 512, 512, 4)]


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_kernels_match_the_float32_form_and_its_vjp(shape, dtype):
    B, T, C, Cx, W = shape
    x, w, dy = inputs(*shape, dtype)
    assert pcc.tile(T, C, W, dtype) is not None
    y, vjp = jax.vjp(lambda x, w: cc.causal_conv_silu(x, w, backend="pallas"),
                     x, w)
    dx, dw = vjp(dy)
    want, want_vjp = jax.vjp(plain, x.astype(F32), w)
    want_dx, want_dw = want_vjp(dy.astype(F32))
    assert (y.shape, y.dtype) == ((B, T, C), dtype)
    assert (dx.shape, dx.dtype) == ((B, T, Cx), dtype)
    assert (dw.shape, dw.dtype) == ((W, C), F32)
    # One rounding to the activations' dtype at the store; dw is a float32
    # sum of float32 products either way.
    rtol, atol = (2.0 ** -8, 2.0 ** -14) if dtype == jnp.bfloat16 \
        else (2e-6, 2e-6)
    for name, got, ref in (("y", y, want), ("dx", dx, want_dx)):
        np.testing.assert_allclose(
            got.astype(F32), ref, rtol=rtol,
            atol=atol * float(jnp.max(jnp.abs(ref))), err_msg=name)
    np.testing.assert_allclose(dw, want_dw, atol=2e-6 * float(
        jnp.max(jnp.abs(want_dw))), err_msg="dw")
    # The columns past the taps are another consumer's: no gradient here.
    assert not np.asarray(dx[..., C:]).any()


@pytest.mark.parametrize("row", [0, 14, 16, 46], ids=lambda r: f"row{r}")
def test_an_impulse_touches_w_rows_and_none_on_the_wrong_side(row):
    """x one row of ones: y is the taps (under SiLU) on that row and the
    W - 1 after it, inside the sequence, and nothing before; dy one row:
    dx on that row and the W - 1 BEFORE it, and nothing after."""
    T, C, W = 48, 128, 4
    _, w, _ = inputs(1, T, C, C, W, F32)
    one = jnp.zeros((1, T, C), F32).at[0, row].set(1.0)

    def op(x):
        return cc.causal_conv_silu(x, w, backend="pallas")
    y = np.asarray(op(one))[0]
    touched = np.flatnonzero(np.abs(y).max(axis=1))
    assert list(touched) == list(range(row, min(row + W, T)))
    for t in touched:     # tap W - 1 meets the row itself, tap 0 the last
        np.testing.assert_allclose(y[t], jax.nn.silu(w[W - 1 - (t - row)]),
                                   rtol=1e-6)
    x, _, _ = inputs(1, T, C, C, W, F32, seed=3)
    dx = np.asarray(jax.vjp(op, x)[1](one)[0])[0]
    assert list(np.flatnonzero(np.abs(dx).max(axis=1))) == list(
        range(max(row - W + 1, 0), row + 1))


@pytest.mark.parametrize("shape,why", [
    ((1, 64, 96, 96, 4), "columns"), ((1, 24, 128, 128, 4), "rows"),
    ((1, 64, 128, 128, 9), "taps")], ids=lambda v: v if isinstance(v, str)
    else "")
def test_what_the_kernels_cannot_tile_takes_the_xla_form(shape, why):
    """96 columns are no lane group, 24 rows no packed tile of 16, nine
    taps reach past one 8-row group: the Pallas backend runs the
    ``jax.numpy`` form, bit for bit, and the gauge says 0."""
    B, T, C, Cx, W = shape
    x, w, dy = inputs(*shape, jnp.bfloat16)
    assert pcc.tile(T, C, W, x.dtype) is None
    assert pcc.tile(T, C, W, jnp.float16 if why == "taps" else x.dtype) \
        is None

    def run(backend, layer):
        y, vjp = jax.vjp(lambda x, w: cc.causal_conv_silu(
            x, w, backend=backend, layer=layer), x, w)
        return (y,) + vjp(dy)
    got, want = run("pallas", 7), run("xla", None)
    assert conv_gauge(7) == 0
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    # The published shapes tile, and float16 does not.
    assert pcc.tile(8192, 8192, 4, jnp.bfloat16) == (2048, 512)
    assert pcc.tile(8192, 12288, 4, jnp.bfloat16) == (2048, 512)
    assert pcc.tile(8192, 8192, 4, jnp.float16) is None
    with pytest.raises(ValueError, match="backend"):
        cc.causal_conv_silu(x, w, backend="mosaic")


# -- the two mixers ----------------------------------------------------------

V, D, E, F = 96, 64, 8, 32


def toy(kind, backend):
    """A Qwen3-Next-shaped period (gdn, gdn, gdn, attn; 2 key and 4 value
    heads of 16: 128 columns through the convolution) or a Kimi-shaped cut
    (kda + dense, kda, kda, mla, kda; 4 heads of 32: 384 columns), float32,
    experts with a shared expert; ``backend`` for the rule AND the
    convolution."""
    base = dict(vocab=V, d_model=D, n_heads=4, mlp="swiglu", tied_head=False,
                d_ff=F, n_experts=E, moe_top_k=2, moe_renormalize=True,
                experts_held=4, first_expert=2, shared_expert_ff=F,
                dtype=F32, attn_backend="xla", unembed_dtype=F32)
    if kind == "gdn":
        return TransformerConfig(**base, n_layers=4, n_kv_heads=2, d_head=16,
                                 qk_norm=True, rope_theta=1e7,
                                 rope_fraction=0.25, attn_gate=True,
                                 norm_offset=True,
                                 layer_pattern=("gdn", "gdn", "gdn", "attn"),
                                 gdn=GatedDeltaNet(2, 4, 16, 16, chunk=16,
                                                   backend=backend))
    return TransformerConfig(**base, n_layers=5, shared_expert_gate=False,
                             moe_score="sigmoid", moe_select_bias=True,
                             moe_scale=2.446, dense_layers=1, dense_ff=96,
                             norm_eps=1e-5,
                             layer_pattern=("kda", "kda", "kda", "mla"),
                             kda=KimiDeltaAttention(4, 32, chunk=16,
                                                    backend=backend),
                             mla=LatentAttention(24, 16, 8, 16))


def loss_and_grads(cfg):
    params = jax.tree_util.tree_map(
        lambda a: (a + 0.1 * jax.random.normal(jax.random.PRNGKey(5), a.shape)
                   ).astype(F32) if a.ndim == 1 else a.astype(F32),
        init_params(jax.random.PRNGKey(0), cfg))
    tok = np.random.default_rng(1).integers(0, V, (2, 65))
    tokens, labels = (jnp.asarray(t, jnp.int32)
                      for t in (tok[:, :-1], tok[:, 1:]))
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    return jax.jit(jax.value_and_grad(lambda p: jnp.mean(dense_nll(
        forward(p, tokens, cfg, mesh)[0], labels))))(params)


@pytest.mark.parametrize("kind,tol", [("gdn", 1e-3), ("kda", 2e-3)])
def test_toy_models_agree_between_the_backends_and_say_which_ran(kind, tol):
    """Loss and every gradient leaf, to ``test_gated_delta.py``'s and
    ``test_kimi_linear.py``'s tolerance against the reference; the gauge
    reads 1 on every mixer layer under ``"pallas"`` and 0 under ``"xla"``."""
    results = {}
    for backend, flag in (("pallas", 1), ("xla", 0)):
        cfg = toy(kind, backend)
        results[backend] = loss_and_grads(cfg)
        mixers = [li for li in range(cfg.n_layers)
                  if layer_kind(cfg, li) == kind]
        assert len(mixers) == (3 if kind == "gdn" else 4)
        assert [conv_gauge(li) for li in mixers] == [flag] * len(mixers)
    (loss, grads), (want_loss, want) = results["pallas"], results["xla"]
    assert float(loss) == pytest.approx(float(want_loss), abs=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            got, ref, atol=tol * float(jnp.max(jnp.abs(ref))) + 1e-7,
            err_msg=jax.tree_util.keystr(path))


def test_the_xla_backend_is_the_code_the_mixers_had():
    """``"xla"`` lowers to the slice, the W shifted float32 copies and
    ``jax.nn.silu`` that ``parallel/transformer.py`` held before PR 35:
    no custom call, no custom VJP (``test_gated_delta.py`` pins a whole toy
    step's text to the parent's)."""
    x, w, _ = inputs(1, 64, 128, 192, 4, jnp.bfloat16)
    text = jax.jit(lambda x, w: cc.causal_conv_silu(
        x, w, backend="xla")).lower(x, w).as_text()
    before = jax.jit(lambda x, w: jax.nn.silu(cc._causal_conv(
        x[..., :128], w))).lower(x, w).as_text()
    assert text == before and "custom_call" not in text
    # Interpreted, the kernels lower to plain HLO too: told apart by name.
    assert "conv_silu_fwd" in jax.jit(lambda x, w: cc.causal_conv_silu(
        x, w, backend="pallas")).lower(x, w).as_text(debug_info=True)
