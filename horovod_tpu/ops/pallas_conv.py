"""Pallas TPU fused 1x1-conv + BatchNorm + activation — ResNet's hot path.

Why this kernel exists
----------------------
The reference's north-star workload is ResNet-50 training
(``examples/keras_imagenet_resnet50.py``). On TPU the measured per-op
roofline (``docs/benchmarks.md``, "the measured roofline bound") shows the
step executing at ~100% of its per-op floor, with the stage-1/2 1x1 convs
pinned at the HBM ceiling (750-900 GB/s, ~50 FLOP/byte): the MFU ceiling is
set by *memory traffic*, not compute. XLA cannot cross convolution HLO
boundaries, so every bottleneck-block chain pays

    conv(write y) -> BN stats(read y) -> BN norm+relu(read y, write z)
    -> next conv(read z)

i.e. four HBM transits per intermediate activation map. This module fuses
the chain into ONE Pallas pass per conv:

    [affine+ReLU prologue] -> matmul (the 1x1 conv) -> [stats epilogue]

so each intermediate makes exactly two transits (one write by its producer,
one read by its consumer). The per-channel BatchNorm arithmetic (mu/sigma
from the streamed sum/sum-of-squares, running-average updates, gamma/beta
folding into a per-channel affine ``a*x + b``) stays in plain jax between
kernels — it is O(C) work, and routing the *stats* (not the normalized
tensor) between ops is what makes jax's chain rule produce the exact
training-mode BatchNorm backward through this op's custom VJP: the
normalize's dependence on mu/sigma flows through the tiny stats graph,
while the VJP handles only the big-tensor terms (one fused backward pass
computing dx, dW, d_affine and injecting the stats cotangents
``dy_eff = dy + ds1 + 2*y*ds2``).

The backward is a single kernel pass reading (x, y, dy) and writing dx,
with dW / da / db accumulated in VMEM across the grid — versus the
unfused path's separate dW matmul, dx matmul, BN-backward reductions and
elementwise passes.

Used by :class:`horovod_tpu.models.resnet.BottleneckBlock` when
``conv_backend="fused"`` (the ``--conv-backend`` knob of the bench/
examples). Off-TPU the kernels run in interpreter mode, bit-matching the
compiled math (tests: ``tests/test_pallas_conv.py``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows of [M, C] processed per grid step. 1024 amortizes Mosaic's per-step
# overhead while keeping the worst-case working set (stage-2 convC,
# C_out=512) around ~3 MB with double buffering; _pick_rows shrinks it for
# small batches.
_WANT_BM = 1024
# Sublane height of the per-channel stat tensors (s1/s2, ds1/ds2, dab):
# one f32 sublane tile; only row 0 carries data.
_STAT_ROWS = 8


def _pick_rows(m: int, want: int = _WANT_BM) -> int:
    b = want
    while b > 128 and m % b:
        b //= 2
    return b


def fusable(m: int) -> bool:
    """Whether the fused kernel tiles an [M, C] problem (M = N*H*W)."""
    return m % 128 == 0


def _fwd_kernel(x_ref, w_ref, ab_ref, y_ref, s1_ref, s2_ref, *,
                prologue: bool, relu: bool):
    i = pl.program_id(0)
    x = x_ref[...]
    if prologue:
        a = ab_ref[0:1, :]
        b = ab_ref[1:2, :]
        u = a * x.astype(jnp.float32) + b
        if relu:
            u = jnp.maximum(u, 0.0)
        # Cast back to the conv input dtype: the unfused graph materializes
        # z = relu(bn(y)) in bf16 before the next conv reads it, so the
        # fused matmul must consume the same rounded values.
        u = u.astype(x_ref.dtype)
    else:
        u = x
    w = w_ref[...].astype(x_ref.dtype)
    y = jax.lax.dot_general(u, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    yc = y.astype(y_ref.dtype)
    y_ref[...] = yc

    @pl.when(i == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    # Stats of the CAST output (what the unfused BatchNorm sees), f32
    # accumulation. Outputs have a grid-constant index map, so they live in
    # VMEM across the whole grid and are flushed once at the end.
    yf = yc.astype(jnp.float32)
    s1_ref[:1, :] += jnp.sum(yf, axis=0)[None, :]
    s2_ref[:1, :] += jnp.sum(yf * yf, axis=0)[None, :]


def _bwd_kernel(x_ref, y_ref, dy_ref, w_ref, ab_ref, ds_ref,
                dx_ref, dw_ref, dab_ref, *, prologue: bool, relu: bool):
    i = pl.program_id(0)
    x = x_ref[...]
    dy = dy_ref[...].astype(jnp.float32)
    # Stats cotangents: d/dy of (s1 = sum y, s2 = sum y^2).
    ds1 = ds_ref[0:1, :]
    ds2 = ds_ref[1:2, :]
    dy = dy + ds1 + 2.0 * y_ref[...].astype(jnp.float32) * ds2

    if prologue:
        a = ab_ref[0:1, :]
        b = ab_ref[1:2, :]
        xf = x.astype(jnp.float32)
        pre = a * xf + b
        u = jnp.maximum(pre, 0.0) if relu else pre
        u = u.astype(x_ref.dtype)
    else:
        u = x
    dyc = dy.astype(x_ref.dtype)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        dab_ref[...] = jnp.zeros_like(dab_ref)

    # dW += u^T dy  (f32 accumulation in the grid-persistent output block)
    dw_ref[...] += jax.lax.dot_general(
        u, dyc, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # du = dy W^T
    w = w_ref[...].astype(x_ref.dtype)
    du = jax.lax.dot_general(dyc, w, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if prologue:
        if relu:
            du = jnp.where(pre > 0.0, du, 0.0)
        dx_ref[...] = (du * a).astype(dx_ref.dtype)
        dab_ref[:1, :] += jnp.sum(du * xf, axis=0)[None, :]
        dab_ref[1:2, :] += jnp.sum(du, axis=0)[None, :]
    else:
        dx_ref[...] = du.astype(dx_ref.dtype)


def _call_fwd(x, w, ab, prologue, relu, interpret):
    m, cin = x.shape
    cout = w.shape[1]
    bm = _pick_rows(m)
    grid = (m // bm,)
    full = lambda i: (0, 0)
    y, s1, s2 = pl.pallas_call(
        functools.partial(_fwd_kernel, prologue=prologue, relu=relu),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, cin), lambda i: (i, 0)),
            pl.BlockSpec((cin, cout), full),
            pl.BlockSpec((_STAT_ROWS, cin), full),
        ],
        out_specs=[
            pl.BlockSpec((bm, cout), lambda i: (i, 0)),
            pl.BlockSpec((_STAT_ROWS, cout), full),
            pl.BlockSpec((_STAT_ROWS, cout), full),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, cout), x.dtype),
            jax.ShapeDtypeStruct((_STAT_ROWS, cout), jnp.float32),
            jax.ShapeDtypeStruct((_STAT_ROWS, cout), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x, w, ab)
    return y, s1, s2


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_core(x, w, ab, prologue: bool, relu: bool, interpret: bool):
    return _call_fwd(x, w, ab, prologue, relu, interpret)


def _fused_core_fwd(x, w, ab, prologue, relu, interpret):
    y, s1, s2 = _call_fwd(x, w, ab, prologue, relu, interpret)
    return (y, s1, s2), (x, w, ab, y)


def _fused_core_bwd(prologue, relu, interpret, res, cot):
    x, w, ab, y = res
    dy, ds1, ds2 = cot
    m, cin = x.shape
    cout = w.shape[1]
    bm = _pick_rows(m)
    # ds row 0 = ds1, row 1 = ds2 (rows 2+ of the primal stat outputs carry
    # no data, so their cotangents are zero by construction).
    ds = jnp.concatenate([ds1[:1, :], ds2[:1, :],
                          jnp.zeros((_STAT_ROWS - 2, cout), jnp.float32)],
                         axis=0)
    full = lambda i: (0, 0)
    dx, dw, dab = pl.pallas_call(
        functools.partial(_bwd_kernel, prologue=prologue, relu=relu),
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, cin), lambda i: (i, 0)),     # x
            pl.BlockSpec((bm, cout), lambda i: (i, 0)),    # y
            pl.BlockSpec((bm, cout), lambda i: (i, 0)),    # dy
            pl.BlockSpec((cin, cout), full),               # w
            pl.BlockSpec((_STAT_ROWS, cin), full),         # ab
            pl.BlockSpec((_STAT_ROWS, cout), full),        # ds
        ],
        out_specs=[
            pl.BlockSpec((bm, cin), lambda i: (i, 0)),
            pl.BlockSpec((cin, cout), full),
            pl.BlockSpec((_STAT_ROWS, cin), full),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, cin), x.dtype),
            jax.ShapeDtypeStruct((cin, cout), jnp.float32),
            jax.ShapeDtypeStruct((_STAT_ROWS, cin), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x, y, dy, w, ab, ds)
    dw = dw.astype(w.dtype)
    dab = dab.astype(ab.dtype)
    if not prologue:
        dab = jnp.zeros_like(dab)
    return dx, dw, dab


_fused_core.defvjp(_fused_core_fwd, _fused_core_bwd)


def fused_linear_bn_act(x2, w, ab: Optional[jax.Array] = None, *,
                        relu: bool = True,
                        interpret: Optional[bool] = None
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused [prologue-affine+ReLU] -> 1x1 conv -> stats epilogue.

    Args:
      x2: [M, Cin] activations (M = N*H*W; a [N,H,W,C] map reshaped —
        layout-free on TPU). M must be a multiple of 128 (``fusable``).
      w: [Cin, Cout] float32 conv weight (cast to ``x2.dtype`` on the MXU).
      ab: None (no prologue — the conv consumes ``x2`` raw), or a
        [>=2, Cin] float32 array with row 0 = per-channel scale ``a`` and
        row 1 = shift ``b``: the conv consumes ``relu(a*x + b)`` (the
        folded form of a trained BatchNorm + ReLU) without materializing it.
      relu: apply ReLU in the prologue (ignored without ``ab``).

    Returns ``(y, s1, s2)``: the conv output [M, Cout] in ``x2.dtype`` and
    its per-channel sum / sum-of-squares (f32, shape [8, Cout], row 0
    carries the data) for the consumer-side BatchNorm. Differentiable via a
    single-pass fused backward kernel; cotangents flowing into s1/s2 (i.e.
    the training-mode BatchNorm's dependence on its batch stats) are folded
    into the gradient exactly.
    """
    m, cin = x2.shape
    if not fusable(m):
        raise ValueError(
            f"fused_linear_bn_act needs M % 128 == 0, got M={m} "
            f"(fall back to the XLA path)")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    prologue = ab is not None
    if ab is None:
        ab = jnp.zeros((_STAT_ROWS, cin), jnp.float32)
    elif ab.shape[0] != _STAT_ROWS:
        ab = jnp.concatenate(
            [ab[:2].astype(jnp.float32),
             jnp.zeros((_STAT_ROWS - 2, cin), jnp.float32)], axis=0)
    return _fused_core(x2, w.astype(jnp.float32), ab, prologue, relu,
                       interpret)
