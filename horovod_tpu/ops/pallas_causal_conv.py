"""Pallas kernels of the linear-attention mixers' causal convolution + SiLU
(``ops/causal_conv.py`` has the op, its ``jax.numpy`` form and the choice
between them).

``conv_silu_fwd`` / ``conv_silu_bwd``: x [B, T, Cx] (the convolution takes
its first C columns, read where they lie), taps w [W, C]. Grid (batch,
blocks of columns, blocks of rows), the rows innermost. A block's first
rows need the W - 1 rows before it: the same array comes in a second time
through a BlockSpec of ``_HALO`` rows whose index is the block's start less
one (clamped; zeros in a sequence's first block). The backward is
anti-causal: it also takes the ``_HALO`` rows after the block of x and of
dy (zeros past the end).

A block is worked ``_ROWS`` rows and ``_LANES`` columns at a time, so that
a step's arrays stay in registers: everything is float32 from the load to
the ONE rounding at the store. A shift along time is a sublane rotation of
(the 8 rows before ‖ the rows), or (the rows ‖ the 8 rows after), from
which the aligned part is kept: no packed bf16 is cut at an odd sublane.

* forward: ``pre_t = sum_j w[j] x[t - (W - 1) + j]``, ``y = pre sigmoid(pre)``;
* backward, from x, w and dy alone: ``pre`` again, ``dpre = dy (s + pre s
  (1 - s))`` with ``s = sigmoid(pre)``, ``dx[t] = sum_j w[j] dpre[t + (W - 1)
  - j]`` and ``dw[j] = sum_t dpre[t] x[t - (W - 1) + j]``. The rows are
  walked from a block's last to its first, carrying the 8 rows of dpre
  after the ones at hand; dw is summed over a block's rows in registers
  and over the blocks of rows into a [B, 8, C] float32 output (tap j in row
  j), whose sum over B the caller takes. dy comes in and dx goes out as
  [B * T, C] (:func:`conv_silu_bwd` says why).

Kernel names (``pallas_call(name=)``; a device trace and the compiled HLO
find the kernels by them, so they are API): ``conv_silu_fwd``,
``conv_silu_bwd``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _VMEM_LIMIT, _grid_params
from .pallas_gated_delta import _traced_once

_HALO = 16      # rows of the blocks before and after: a packed bf16 tile
_TAPS = 8       # taps at most: the shifts come out of ONE 8-row group
_ROW_TILES = (2048, 1024, 512, 256, 128, 64, 32, 16)
_COLUMN_TILES = (512, 256, 128)
_ROWS = 64      # rows worked at a time, at most
_LANES = 256    # columns worked at a time, at most


def tile(T: int, C: int, W: int, dtype):
    """(rows, columns) of a block for T rows, C columns and W taps, or None
    where the kernels cannot tile them: C in lanes of 128, T in packed
    tiles of 16 rows, the taps within one 8-row group, bf16 or float32."""
    if (C % 128 or T % _HALO or not 1 <= W <= _TAPS
            or dtype not in (jnp.bfloat16, jnp.float32)):
        return None
    return (next(t for t in _ROW_TILES if T % t == 0),
            next(c for c in _COLUMN_TILES if C % c == 0))


def _before(prev, cur, s: int):
    """``cur`` shifted s rows down: row t holds (prev ‖ cur)[8 + t - s];
    prev is the 8 rows before cur."""
    if not s:
        return cur
    return pltpu.roll(jnp.concatenate([prev, cur], axis=0), s, 0)[8:]


def _after(cur, nxt, s: int):
    """``cur`` shifted s rows up: row t holds (cur ‖ nxt)[t + s]; nxt is the
    8 rows after cur."""
    if not s:
        return cur
    n = cur.shape[0]
    return pltpu.roll(jnp.concatenate([cur, nxt], axis=0), n + 8 - s, 0)[:n]


def _last8(ref, start, cols):
    """The 8 rows of ``ref`` before row ``start`` (a multiple of _HALO), in
    float32."""
    at = start - _HALO
    if not isinstance(at, int):
        at = pl.multiple_of(at, _HALO)
    return ref[pl.ds(at, _HALO), cols].astype(jnp.float32)[_HALO - 8:]


def _taps(w_ref, cols, W: int, rows: int):
    """Tap j as [rows, lanes]: one register a lane group, whatever the
    rows."""
    return [jnp.broadcast_to(w_ref[pl.ds(j, 1), cols],
                             (rows, cols.stop - cols.start))
            for j in range(W)]


def _pre(xs, w):
    """``sum_j w[j] x[t - (W - 1) + j]`` from the shifted copies xs[s][t] =
    x[t - s]."""
    W = len(w)
    pre = w[W - 1] * xs[0]
    for j in range(W - 1):
        pre += w[j] * xs[W - 1 - j]
    return pre


def _plan(ref):
    """(rows a step, steps, the column slices) of a block."""
    tT, tC = ref.shape
    rows, lanes = min(_ROWS, tT), min(_LANES, tC)
    return rows, tT // rows, [slice(c, c + lanes)
                              for c in range(0, tC, lanes)]


def _fwd_kernel(x_ref, before_ref, w_ref, y_ref, *, W: int):
    first = pl.program_id(2) == 0
    rows, steps, columns = _plan(x_ref)
    for cols in columns:
        w = _taps(w_ref, cols, W, rows)

        def work(r, prev):
            cur = x_ref[pl.ds(r, rows), cols].astype(jnp.float32)
            pre = _pre([_before(prev, cur, s) for s in range(W)], w)
            y_ref[pl.ds(r, rows), cols] = (
                pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)

        work(0, jnp.where(
            first, 0.0, before_ref[:, cols].astype(jnp.float32)[_HALO - 8:]))

        def step(i, carry):
            r = pl.multiple_of(i * rows, rows)
            work(r, _last8(x_ref, r, cols))
            return carry
        lax.fori_loop(1, steps, step, 0)


def _bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref,
                dx_ref, dw_ref, *, W: int):
    f32 = jnp.float32
    first = pl.program_id(2) == 0
    last = pl.program_id(2) == pl.num_programs(2) - 1
    rows, steps, columns = _plan(x_ref)
    tT = x_ref.shape[0]

    @pl.when(first)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def dpre_of(xs, w, dy):
        pre = _pre(xs, w)
        s = jax.nn.sigmoid(pre)
        return dy * (s * (1.0 + pre * (1.0 - s)))

    for cols in columns:
        w = _taps(w_ref, cols, W, rows)
        # dpre of the 8 rows after the block: zero past the sequence's end.
        nxt = after_ref[:, cols].astype(f32)[:8]
        head = dpre_of(
            [_before(_last8(x_ref, tT, cols), nxt, s) for s in range(W)],
            [t[:8] for t in w],
            jnp.where(last, 0.0, dy_after_ref[:, cols].astype(f32)[:8]))

        def work(r, prev, carry):
            head, sums = carry
            cur = x_ref[pl.ds(r, rows), cols].astype(f32)
            xs = [_before(prev, cur, s) for s in range(W)]
            dpre = dpre_of(xs, w, dy_ref[pl.ds(r, rows), cols].astype(f32))
            dx = w[W - 1] * dpre
            for j in range(W - 1):
                dx += w[j] * _after(dpre, head, W - 1 - j)
            dx_ref[pl.ds(r, rows), cols] = dx.astype(dx_ref.dtype)

            def by8(p):
                return sum(p[k:k + 8] for k in range(0, rows, 8))
            return dpre[:8], tuple(
                a + by8(dpre * xs[W - 1 - j]) for j, a in enumerate(sums))

        def step(i, carry):
            r = pl.multiple_of((steps - 1 - i) * rows, rows)
            return work(r, _last8(x_ref, r, cols), carry)
        zeros = jnp.zeros((8, cols.stop - cols.start), f32)
        carry = lax.fori_loop(0, steps - 1, step, (head, (zeros,) * W))
        _, sums = work(0, jnp.where(
            first, 0.0, before_ref[:, cols].astype(f32)[_HALO - 8:]), carry)
        for j, a in enumerate(sums):
            dw_ref[pl.ds(j, 1), cols] += jnp.sum(a, axis=0, keepdims=True)


def _specs(T: int, tT: int, tC: int, flat: bool = False):
    """BlockSpecs of (a block of rows, the _HALO rows before it, the _HALO
    rows after it, the taps) over the grid (batch, columns, rows), for a
    [B, T, .] array or, ``flat``, the same array as [B * T, .]."""
    per, n = tT // _HALO, T // _HALO

    def spec(rows, at):
        if flat:
            return pl.BlockSpec((rows, tC), lambda b, c, t: (
                b * (T // rows) + at(t), c))
        return pl.BlockSpec((None, rows, tC), lambda b, c, t: (b, at(t), c))
    return (spec(tT, lambda t: t),
            spec(_HALO, lambda t: jnp.maximum(t * per - 1, 0)),
            spec(_HALO, lambda t: jnp.minimum((t + 1) * per, n - 1)),
            pl.BlockSpec((_TAPS, tC), lambda b, c, t: (0, c)))


def _padded(w):
    """The taps as [_TAPS, C] float32 (zero rows under the W there are)."""
    return jnp.pad(w.astype(jnp.float32), ((0, _TAPS - w.shape[0]), (0, 0)))


def _launch(kernel, name: str, x, w, interpret: bool):
    """(``pallas_call`` but for its specs and shapes, ``_specs`` with the
    block's sizes filled in) of a kernel over the grid (batch, blocks of
    columns, blocks of rows)."""
    B, T, _ = x.shape
    W, C = w.shape
    tT, tC = tile(T, C, W, x.dtype)
    return functools.partial(
        pl.pallas_call, functools.partial(kernel, W=W),
        grid=(B, C // tC, T // tT), name=name, interpret=interpret,
        compiler_params=_grid_params(("parallel", "parallel", "arbitrary"),
                                     _VMEM_LIMIT)), functools.partial(
        _specs, T, tT, tC)


@_traced_once
def conv_silu_fwd(x, w, *, interpret):
    """``silu(conv(x[..., :C], w))`` [B, T, C] in x's dtype, for x [B, T, Cx]
    and w [W, C] that :func:`tile` takes."""
    call, specs = _launch(_fwd_kernel, "conv_silu_fwd", x, w, interpret)
    rows, before, _, taps = specs()
    return call(
        in_specs=[rows, before, taps], out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(x.shape[:2] + w.shape[1:], x.dtype),
    )(x, x, _padded(w))


@_traced_once
def conv_silu_bwd(x, w, dy, *, interpret):
    """(dx [B, T, C] in x's dtype, dw [W, C] float32) of
    :func:`conv_silu_fwd` under the cotangent dy [B, T, C].

    The two cotangents cross the kernel's boundary as [B * T, C]: the
    reshapes are free, and they are what keeps XLA's layouts elsewhere as
    they were. A custom call's operands and results have fixed layouts,
    which layout assignment spreads from the LAST such call backwards and
    depth first through every elementwise op, pad and add it meets, but
    defers at a reshape that splits a dimension. Through dx, which is added
    to the cotangent of the projection's other consumer (Gated DeltaNet's z),
    that spread reached the gated norm's backward before the rule's own
    head-major output did, and turned the whole gated norm and the value
    heads' cotangents row-major: 22 ms a step of relayouts in
    ``qwen3next_gdn_train_8k_1chip`` (PERF.md, PR 35)."""
    B, T, C = dy.shape
    call, specs = _launch(_bwd_kernel, "conv_silu_bwd", x, w, interpret)
    rows, before, after, taps = specs()
    flat, _, flat_after, _ = specs(flat=True)
    dx, dw = call(
        in_specs=[rows, before, after, flat, flat_after, taps],
        out_specs=[flat, pl.BlockSpec((None, _TAPS, taps.block_shape[1]),
                                      lambda b, c, t: (b, 0, c))],
        out_shape=[jax.ShapeDtypeStruct((B * T, C), x.dtype),
                   jax.ShapeDtypeStruct((B, _TAPS, C), jnp.float32)],
    )(x, x, x, *[dy.reshape(B * T, C)] * 2, _padded(w))
    return dx.reshape(B, T, C), jnp.sum(dw, axis=0)[:w.shape[0]]
