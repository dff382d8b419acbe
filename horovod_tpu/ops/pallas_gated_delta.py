"""Pallas kernels of the gated delta rule (``ops/gated_delta.py`` has the
rule, its ``jax.numpy`` form and the choice between them).

**The walk over the chunks**, ``gdn_fwd`` / ``gdn_bwd``. Grid (batch x head,
blocks of chunks); the chunk axis is sequential and the state ``S`` (the
backward's ``dS``) [dk, dv] float32 is a VMEM scratch that lives across it,
so the state never goes through HBM between chunks. A grid step works
``_BLOCK`` chunks one after the other (a step of this grid costs about a
third of a microsecond before it computes anything, and one chunk is four
small products). The forward writes each chunk's START state out once, in
the activations' dtype: that is what the backward walks back from.

**The chunk-local stage**, ``gdn_local_fwd`` / ``gdn_local_bwd``
(:func:`local_fwd`, :func:`local_bwd`; the section below says how a tile
is laid out). Grid (batch x key head, blocks of tiles), every step
independent. From q, k, v, g, beta of a tile the forward makes gam, the
decays, A, the solve T = (I + A)^-1, W, U, Aqk, Q e^gam, K e^{gam_C - gam}
without any of the [C, C] arrays leaving VMEM, for each of the key head's
value heads in turn (K K^T and Q K^T once for all of them); the backward
is the transpose of all that but the solve's inside (T is an input). The
float32 products (the solve, W, U, dA's two) are three bf16 passes
(``_dot3``), as ``Precision.HIGH`` is in the ``jax.numpy`` form.

**A decay per channel** (Kimi Delta Attention; g [.., dk] a row). The walk
is the same kernels under the names ``kda_fwd`` / ``kda_bwd`` with
e^{gam_C} a [dk] vector a chunk, laid over the state's rows. The stage is
``kda_local_fwd`` / ``kda_local_bwd`` (:func:`kda_local_fwd`,
:func:`kda_local_bwd`): grid (batch x head, blocks of tiles), a tile's
body ``kda_tile.tile_fwd`` / ``tile_bwd`` itself, with this file's
products; nothing of a tile but its inputs and outputs leaves VMEM.

Kernel names (``pallas_call(name=)``; a device trace and the compiled HLO
find the kernels by them, so they are API): ``gdn_fwd``, ``gdn_bwd``,
``gdn_local_fwd``, ``gdn_local_bwd``; ``kda_fwd``, ``kda_bwd``,
``kda_local_fwd`` and ``kda_local_bwd``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kda_tile
from .pallas_attention import _VMEM_LIMIT, _dot, _grid_params

_BLOCK = 8           # chunks worked in one grid step, at most


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _block(n: int) -> int:
    return next(b for b in (_BLOCK, 4, 2, 1) if n % b == 0)


def _rows_and_columns(n: int):
    """(a row [1, n] as a column [n, 1], a column as a row), by masked sums
    over the other axis (exact; no relayout)."""
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return (lambda row: jnp.sum(jnp.where(eye, row, 0.0), axis=1,
                                keepdims=True),
            lambda col: jnp.sum(jnp.where(eye, col, 0.0), axis=0,
                                keepdims=True))


def _fwd_kernel(qg_ref, kd_ref, w_ref, u_ref, a_ref, e_ref, o_ref, st_ref,
                s_ref, *, block: int, channels: bool = False):
    """``channels``: e_ref[j] is a row [1, dk] of one decay a key channel,
    laid over the state's rows; else a number, replicated [1, dv]."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    dt = u_ref.dtype
    decay = _rows_and_columns(s_ref.shape[0])[0] if channels \
        else (lambda e: e)
    for j in range(block):
        S = s_ref[...]
        s = S.astype(dt)
        st_ref[j] = s
        un = (u_ref[j].astype(jnp.float32)
              - _dot(w_ref[j], s, 1, 0)).astype(dt)
        o_ref[j] = (_dot(qg_ref[j], s, 1, 0)
                    + _dot(a_ref[j], un, 1, 0)).astype(o_ref.dtype)
        s_ref[...] = S * decay(e_ref[j]) + _dot(kd_ref[j], un, 0, 0)


def _bwd_kernel(qg_ref, kd_ref, w_ref, u_ref, a_ref, e_ref, st_ref, do_ref,
                dqg_ref, dkd_ref, dw_ref, du_ref, da_ref, de_ref, ds_ref, *,
                block: int, channels: bool = False):
    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    dt = u_ref.dtype
    if channels:
        decay, as_row = _rows_and_columns(ds_ref.shape[0])
    else:
        decay = lambda e: e                                 # noqa: E731
    for j in reversed(range(block)):
        s, dS = st_ref[j], ds_ref[...]
        ds, do = dS.astype(dt), do_ref[j]
        un = (u_ref[j].astype(jnp.float32)
              - _dot(w_ref[j], s, 1, 0)).astype(dt)
        dun = _dot(a_ref[j], do, 0, 0) + _dot(kd_ref[j], ds, 1, 0)
        dund = dun.astype(dt)
        dqg_ref[j] = _dot(do, s, 1, 1).astype(dt)
        dkd_ref[j] = _dot(un, ds, 1, 1).astype(dt)
        dw_ref[j] = (-_dot(dund, s, 1, 1)).astype(dt)
        du_ref[j] = dund
        da_ref[j] = _dot(do, un, 1, 1).astype(dt)
        if channels:
            de_ref[j] = as_row(jnp.sum(s.astype(jnp.float32) * dS, axis=1,
                                       keepdims=True))
        else:
            de_ref[j] = jnp.broadcast_to(
                jnp.sum(jnp.sum(s.astype(jnp.float32) * dS, axis=0,
                                keepdims=True), axis=1,
                        keepdims=True), de_ref.shape[1:])
        ds_ref[...] = (dS * decay(e_ref[j]) + _dot(qg_ref[j], do, 0, 0)
                       - _dot(w_ref[j], dund, 0, 0))


def _flat(x):
    """[B, H, N, ...] -> [B*H, N, ...]."""
    return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])


def _lanes_of(e_last, dv: int):
    """e^{gam_C} [B, H, N] as rows [B*H, N, 1, dv] that multiply a state's
    rows without a lane broadcast; [B, H, N, dk] (one a key channel) as
    rows [B*H, N, 1, dk]."""
    if e_last.ndim == 4:
        return _flat(e_last)[:, :, None, :].astype(jnp.float32)
    return jnp.broadcast_to(_flat(e_last)[..., None, None],
                            (e_last.shape[0] * e_last.shape[1],
                             e_last.shape[2], 1, dv)).astype(jnp.float32)


def _spec(block: int, *tail, at):
    return pl.BlockSpec((None, block, *tail),
                        lambda b, n: (b, at(n)) + (0,) * len(tail))


def _traced_once(fn):
    """``fn(*arrays)`` as ``jax.jit`` would cache it, with the platform's
    choice of the interpreter in the key: a model's layers of one shape
    then trace and lower a kernel's body once, not once a layer (a body
    written out for several tiles is hundreds of operations; lowering the
    cell's step took 42 s of host time in place of 15 without this)."""
    jitted = jax.jit(fn, static_argnames=("interpret",))
    return functools.wraps(fn)(
        lambda *args: jitted(*args, interpret=_interpret()))


@_traced_once
def scan_fwd(qg, kd, w, u, aqk, e_last, *, interpret):
    """As ``gated_delta.scan_fwd_xla``: (o, chunk-start states)."""
    B, H, N, C, dk = qg.shape
    dv = u.shape[-1]
    blk = _block(N)
    spec = functools.partial(_spec, blk, at=lambda n: n)
    channels = e_last.ndim == 4
    o, states = pl.pallas_call(
        functools.partial(_fwd_kernel, block=blk, channels=channels),
        grid=(B * H, N // blk),
        name="kda_fwd" if channels else "gdn_fwd", interpret=interpret,
        compiler_params=_grid_params(("parallel", "arbitrary"), _VMEM_LIMIT),
        in_specs=[spec(C, dk), spec(C, dk), spec(C, dk), spec(C, dv),
                  spec(C, C), spec(1, dk if channels else dv)],
        out_specs=[spec(C, dv), spec(dk, dv)],
        out_shape=[jax.ShapeDtypeStruct((B * H, N, C, dv), u.dtype),
                   jax.ShapeDtypeStruct((B * H, N, dk, dv), u.dtype)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
    )(*(_flat(x) for x in (qg, kd, w, u, aqk)), _lanes_of(e_last, dv))
    return o.reshape(B, H, N, C, dv), states.reshape(B, H, N, dk, dv)


@_traced_once
def scan_bwd(qg, kd, w, u, aqk, e_last, states, do, *, interpret):
    """As ``gated_delta.scan_bwd_xla``: the cotangents of the six inputs."""
    B, H, N, C, dk = qg.shape
    dv = u.shape[-1]
    blk = _block(N)
    last = N // blk - 1
    spec = functools.partial(_spec, blk, at=lambda n: last - n)
    dt = u.dtype
    channels = e_last.ndim == 4
    de_lanes = dk if channels else dv

    def like(*tail, dtype=dt):
        return jax.ShapeDtypeStruct((B * H, N, *tail), dtype)
    *grads, de = pl.pallas_call(
        functools.partial(_bwd_kernel, block=blk, channels=channels),
        grid=(B * H, N // blk),
        name="kda_bwd" if channels else "gdn_bwd", interpret=interpret,
        compiler_params=_grid_params(("parallel", "arbitrary"), _VMEM_LIMIT),
        in_specs=[spec(C, dk), spec(C, dk), spec(C, dk), spec(C, dv),
                  spec(C, C), spec(1, de_lanes), spec(dk, dv), spec(C, dv)],
        out_specs=[spec(C, dk), spec(C, dk), spec(C, dk), spec(C, dv),
                   spec(C, C), spec(1, de_lanes)],
        out_shape=[like(C, dk), like(C, dk), like(C, dk), like(C, dv),
                   like(C, C), like(1, de_lanes, dtype=jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
    )(*(_flat(x) for x in (qg, kd, w, u, aqk)), _lanes_of(e_last, dv),
      _flat(states), _flat(do))
    grads = tuple(g.reshape(B, H, *g.shape[1:]) for g in grads)
    if channels:
        return grads + (de[:, :, 0].reshape(B, H, N, dk),)
    return grads + (de[:, :, 0, 0].reshape(B, H, N),)


# -- the chunk-local stage -------------------------------------------------------
#
# A TILE is the P = 128 // C consecutive chunks of one head that fill 128
# rows (two chunks of 64). Every [C, C] array of the stage is worked as the
# block-diagonal [R, R] array of its tile (R = P C; ``same`` masks the
# blocks), so that a product has the MXU's own shape and no array is cut
# along the lanes. Such an array enters or leaves COLLAPSED, [R, C] (the
# chunks one under the other: the [N, C, C] array itself), or PACKED,
# [C, R] (the chunks side by side: how T is saved, 128 float32 lanes wide,
# and how the solve multiplies, C rows through the MXU for P chunks).
# g and beta come as rows [1, R]; a column [R, 1] is made of a row, and a
# row of a column, by a masked sum over the other axis (exact).

# Tiles worked in one grid step, at most, their (tile, value head) pairs in
# turns (``_in_turns``). On a v5e at 2 x 8192 rows, 16 key and 32 value
# heads, a layer (PERF.md PR 33): the forward 5.81 ms a pair after the
# other, 2.87 in turns; the backward 3.86 and 3.07; 8 tiles a step 2.79 and
# 3.04, 2 tiles 3.26 and 3.04, 1 tile 4.16 and 3.27. Two, not four: a
# kernel's body is written out once a pair, and the host lowers the cell's
# step in 14.8 s with two tiles (the parent's 14.9) and in 18.8 with four,
# before every run, whatever the compile cache holds.
_LOCAL_BLOCK = 2


def local_tile(n: int, chunk: int):
    """Chunks a tile (fewer than 128 // chunk where the chunk count has no
    such divisor), or None where the kernels cannot tile chunks of this
    length: a tile packs them into 128 rows, in sublanes of 8."""
    if chunk % 8 or 128 % chunk:
        return None
    return next(p for p in range(128 // chunk, 0, -1) if n % p == 0)


def _split(x):
    """float32 -> (its bf16 high part, its bf16 low part); bf16 is its own
    high part, and so is float32 interpreted on a CPU, whose products are
    exact."""
    if x.dtype != jnp.float32 or _interpret():
        return x, None
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _dot3(a, b, ca: int, cb: int):
    """A float32 product of ``_split`` parts in three bf16 passes, hi hi +
    hi lo + lo hi: what ``Precision.HIGH`` is on a TPU (Mosaic takes no
    such precision)."""
    (ah, al), (bh, bl) = a, b
    if ah.dtype != bh.dtype:        # interpreted: float32 by bf16
        ah, bh = ah.astype(jnp.float32), bh.astype(jnp.float32)
    out = _dot(ah, bh, ca, cb)
    if bl is not None:
        out += _dot(ah, bl, ca, cb)
    if al is not None:
        out += _dot(al, bh, ca, cb)
    return out


class _Tile:
    """The masks of a tile of P chunks of C rows, and the moves between
    its forms."""

    def __init__(self, C: int, P: int):
        R = C * P
        row = jax.lax.broadcasted_iota(jnp.int32, (R, R), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (R, R), 1)
        self.C, self.P = C, P
        # C is a power of two (``local_tile``): no integer division.
        self.same = (row ^ col) < C
        self.eye = row == col
        self.tril = self.same & (row >= col)
        self.stril = self.same & (row > col)
        # [i, j]: i is the last row (j the last column) of the other's chunk.
        self.last_row = self.same & ((row & (C - 1)) == C - 1)
        self.last_col = self.same & ((col & (C - 1)) == C - 1)
        self.packed_eye = (
            jax.lax.broadcasted_iota(jnp.int32, (C, R), 0)
            == (jax.lax.broadcasted_iota(jnp.int32, (C, R), 1) & (C - 1))
        ).astype(jnp.float32)

    @staticmethod
    def over_lanes(mask, row_vec):
        """[R, 1]: the sum over j of the [1, R] vector where mask[i, j]."""
        return jnp.sum(jnp.where(mask, row_vec, 0.0), axis=1, keepdims=True)

    @staticmethod
    def over_rows(mask, col_vec):
        """[1, R]: the sum over i of the [R, 1] vector where mask[i, j]."""
        return jnp.sum(jnp.where(mask, col_vec, 0.0), axis=0, keepdims=True)

    def from_packed(self, x):
        """[C, R] -> the block-diagonal [R, R]."""
        return jnp.where(self.same, jnp.concatenate([x] * self.P, axis=0),
                         0.0)

    def to_packed(self, x):
        return sum(x[p * self.C:(p + 1) * self.C] for p in range(self.P))

    def from_collapsed(self, x):
        """[R, C] -> the block-diagonal [R, R], float32."""
        return jnp.where(self.same, jnp.concatenate(
            [x.astype(jnp.float32)] * self.P, axis=1), 0.0)

    def to_collapsed(self, x):
        return sum(x[:, p * self.C:(p + 1) * self.C] for p in range(self.P))

    def decays(self, g_row):
        """g [1, R] -> gam [R, 1], its running sum inside each chunk;
        exp(gam_i - gam_j) for i >= j of one chunk, 0 elsewhere (only
        decays of the past are formed); gam at the end of each row's chunk
        as [R, 1] and [1, R]."""
        gam_c = self.over_lanes(self.tril, g_row)
        gam_r = self.over_rows(self.eye, gam_c)
        decay = jnp.where(self.tril, jnp.exp(jnp.where(
            self.tril, gam_c - gam_r, 0.0)), 0.0)
        return (gam_c, decay, self.over_lanes(self.last_col, gam_r),
                self.over_rows(self.last_row, gam_c))


def _in_turns(chains):
    """Run generators a step each in turn until all are done. A chain of
    dependent products (the solve's ten, the transpose's) leaves the MXU
    waiting; the compiler keeps the order the program was written in, so
    the independent chains of a grid step are written interleaved."""
    chains = list(chains)
    while chains:
        chains = [c for c in chains if next(c, c) is not c]


def _unit_lower_inverse(tile: _Tile, a):
    """(I + a)^-1, PACKED, for the block-diagonal strictly lower a [R, R]
    of a tile: with n = -a nilpotent, the product of (I + n^(2^j)) as in
    ``gated_delta._unit_lower_inverse``, every product packed [C, R] by
    block-diagonal [R, R]. A generator (``_in_turns``): yields between
    products, returns the inverse."""
    n = tile.to_packed(-a)
    inv = tile.packed_eye + n
    n_bd = _split(-a)
    for _ in range(max(0, (tile.C - 1).bit_length() - 1)):
        n = _dot3(_split(n), n_bd, 1, 0)
        yield
        n_bd = _split(tile.from_packed(n))
        inv = inv + _dot3(_split(inv), n_bd, 1, 0)
        yield
    return inv


def _key_heads(q_ref, k_ref, block: int):
    """What a key head's value heads share, once a tile: (j, q, k, their
    float32 forms, K K^T, Q K^T)."""
    out = []
    for j in range(block):
        q, k = q_ref[j], k_ref[j]
        out.append((j, q, k, q.astype(jnp.float32), k.astype(jnp.float32),
                    _dot(k, k, 1, 1), _dot(q, k, 1, 1)))
    return out


def _local_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, *rest, C: int,
                      P: int, rep: int, block: int, solve: bool):
    if solve:
        (qg_ref, kd_ref, w_ref, u_ref, aqk_ref, e_ref, t_ref) = rest
    else:
        (t_ref, qg_ref, kd_ref, w_ref, u_ref, aqk_ref, e_ref) = rest
    tile = _Tile(C, P)
    dt, f32 = q_ref.dtype, jnp.float32

    def pair(r, j, q, k, qf, kf, kk, qk):
        gam, decay, end, end_row = tile.decays(g_ref[r, j])
        beta = tile.over_lanes(tile.eye, beta_ref[r, j])
        yield
        if solve:
            packed = yield from _unit_lower_inverse(tile, jnp.where(
                tile.stril, beta * kk * decay, 0.0))
            t_ref[r, j] = packed
        else:
            packed = t_ref[r, j]
        e_gam = jnp.exp(gam)
        t = _split(tile.from_packed(packed))
        yield
        w_ref[r, j] = _dot3(t, _split(kf * (beta * e_gam)), 1, 0).astype(dt)
        yield
        u_ref[r, j] = _dot3(t, _split(v_ref[r, j].astype(f32) * beta), 1,
                            0).astype(dt)
        yield
        aqk_ref[r, j] = tile.to_collapsed(qk * decay).astype(dt)
        qg_ref[r, j] = (qf * e_gam).astype(dt)
        kd_ref[r, j] = (kf * jnp.exp(end - gam)).astype(dt)
        e_ref[r, j] = jnp.exp(end_row)
    _in_turns(pair(r, *head) for head in _key_heads(q_ref, k_ref, block)
              for r in range(rep))


def _local_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, t_ref, dqg_ref,
                      dkd_ref, dw_ref, du_ref, daqk_ref, de_ref, dq_ref,
                      dk_ref, dv_ref, dg_ref, dbeta_ref, *, C: int, P: int,
                      rep: int, block: int):
    tile = _Tile(C, P)
    dt, f32 = q_ref.dtype, jnp.float32

    def rowsum(x, y):
        return jnp.sum(x.astype(f32) * y.astype(f32), axis=1, keepdims=True)

    def pair(r, d_qk, j, q, k, qf, kf, kk, qk):
        """Adds its d q and d k to d_qk, the key head's [dq, dk]."""
        gam, decay, end, end_row = tile.decays(g_ref[r, j])
        beta = tile.over_lanes(tile.eye, beta_ref[r, j])
        e_gam, e_end = jnp.exp(gam), jnp.exp(end - gam)
        v = v_ref[r, j].astype(f32)
        t = _split(tile.from_packed(t_ref[r, j]))
        dw, du = _split(dw_ref[r, j]), _split(du_ref[r, j])
        dqg, dkd = dqg_ref[r, j].astype(f32), dkd_ref[r, j].astype(f32)
        yield
        # W = T Kt, U = T Vt with Kt = beta e^gam K, Vt = beta V.
        dkt = _dot3(t, dw, 0, 0)
        yield
        dvt = _dot3(t, du, 0, 0)
        yield
        d_t = (_dot3(dw, _split(kf * (beta * e_gam)), 1, 1)
               + _dot3(du, _split(v * beta), 1, 1))
        yield
        # T = (I + A)^-1: dA = -T^T dT T^T.
        half = _split(_dot3(_split(d_t), t, 1, 1))
        yield
        da = jnp.where(tile.stril, -_dot3(t, half, 0, 0), 0.0)
        yield
        m = da * beta * decay                               # d (K K^T)
        p = tile.from_collapsed(daqk_ref[r, j]) * decay     # d (Q K^T)
        md, pd = m.astype(dt), p.astype(dt)
        d_qk[1] += (_dot(md, k, 1, 0) + _dot(md, k, 0, 0) + _dot(pd, q, 0, 0)
                    + dkd * e_end + dkt * (beta * e_gam))
        d_qk[0] += _dot(pd, k, 1, 0) + dqg * e_gam
        yield
        dv_ref[r, j] = (dvt * beta).astype(dt)
        dbeta_ref[r, j] = tile.over_rows(tile.eye, jnp.sum(
            da * kk * decay, axis=1, keepdims=True)
            + rowsum(dkt, kf) * e_gam + rowsum(dvt, v))
        yield
        # gam: through the decays (a row's sum less a column's), every
        # e^gam, and e^{gam_C - gam} and e^{gam_C} at the chunk's end.
        through = m * kk + p * qk
        s_end = rowsum(dkd, kf) * e_end
        d_gam = (jnp.sum(through, axis=1, keepdims=True)
                 - tile.over_lanes(tile.eye, jnp.sum(
                     through, axis=0, keepdims=True))
                 + rowsum(dqg, qf) * e_gam
                 + rowsum(dkt, kf) * (beta * e_gam) - s_end)
        dg_ref[r, j] = (tile.over_rows(tile.tril, d_gam)
                        + tile.over_rows(tile.same, s_end)
                        + de_ref[r, j] * jnp.exp(end_row))
    sums = [[0.0, 0.0] for _ in range(block)]
    _in_turns(pair(r, sums[head[0]], *head)
              for head in _key_heads(q_ref, k_ref, block) for r in range(rep))
    for j, (dq, dk) in enumerate(sums):
        dq_ref[j] = dq.astype(dt)
        dk_ref[j] = dk.astype(dt)


def _tiles(x, P: int, lead: int):
    """[B, Hk, (rep,) N, C, ...] -> [B*Hk, (rep,) N/P, P*C, ...] (``lead``
    axes before N)."""
    n, c = x.shape[lead], x.shape[lead + 1]
    return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:lead], n // P,
                     P * c, *x.shape[lead + 2:])


def _rows(x, P: int):
    """g, beta, de [B, Hk, rep, N, C] -> rows [B*Hk, rep, N/P, 1, P*C]."""
    B, Hk, rep, N, C = x.shape
    return x.reshape(B * Hk, rep, N // P, 1, P * C)


def _local_plan(q, v):
    """Of q [B, Hk, N, C, dk] and v [B, Hk, rep, N, C, dv]: chunks a tile,
    tiles a head, tiles a grid step, and the block specs of an array a key
    head ([B*Hk, N/P, ..]) and a value head ([B*Hk, rep, N/P, ..])."""
    rep, N, C = v.shape[2:5]
    P = local_tile(N, C)
    blk = next(b for b in (_LOCAL_BLOCK, 1) if N // P % b == 0)

    def per_key(*tail):
        return pl.BlockSpec((None, blk, *tail),
                            lambda b, n: (b, n) + (0,) * len(tail))

    def per_value(*tail):
        return pl.BlockSpec((None, rep, blk, *tail),
                            lambda b, n: (b, 0, n) + (0,) * len(tail))
    return P, N // P, blk, per_key, per_value


@_traced_once
def local_fwd(q, k, v, g, beta, t=None, *, interpret):
    """The chunk-local stage (``gated_delta._a_matrix``, the solve,
    ``_prepare``) for q, k [B, Hk, N, C, dk], v [B, Hk, rep, N, C, dv], g,
    beta [B, Hk, rep, N, C] float32: (Q e^gam, K e^{gam_C - gam}, W, U, Aqk
    [B, Hk*rep, N, C, .] in q's dtype, e^{gam_C} [B, Hk*rep, N] float32)
    and T = (I + A)^-1 float32 PACKED [B, Hk, rep, N/P, C, P*C] (P =
    ``local_tile``: a tile's chunks side by side). Given ``t`` (the
    backward's recomputation) the solve is skipped and t not returned."""
    B, Hk, rep, N, C, dv = v.shape
    dk, dt, f32 = q.shape[-1], q.dtype, jnp.float32
    P, NT, blk, per_key, per_value = _local_plan(q, v)
    R = P * C

    def like(*tail, dtype=dt):
        return jax.ShapeDtypeStruct((B * Hk, rep, NT, *tail), dtype)
    inputs = [_tiles(q, P, 2), _tiles(k, P, 2), _tiles(v, P, 3),
              _rows(g, P), _rows(beta, P)]
    in_specs = [per_key(R, dk), per_key(R, dk), per_value(R, dv),
                per_value(1, R), per_value(1, R)]
    out_specs = [per_value(R, dk), per_value(R, dk), per_value(R, dk),
                 per_value(R, dv), per_value(R, C), per_value(1, R)]
    out_shape = [like(R, dk), like(R, dk), like(R, dk), like(R, dv),
                 like(R, C), like(1, R, dtype=f32)]
    if t is None:
        out_specs.append(per_value(C, R))
        out_shape.append(like(C, R, dtype=f32))
    else:
        inputs.append(t.reshape(B * Hk, rep, NT, C, R))
        in_specs.append(per_value(C, R))
    qg, kd, w, u, aqk, e, *solved = pl.pallas_call(
        functools.partial(_local_fwd_kernel, C=C, P=P, rep=rep, block=blk,
                          solve=t is None),
        grid=(B * Hk, NT // blk), name="gdn_local_fwd",
        interpret=interpret,
        compiler_params=_grid_params(("parallel", "parallel"), _VMEM_LIMIT),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
    )(*inputs)
    out = tuple(x.reshape(B, Hk * rep, N, C, x.shape[-1])
                for x in (qg, kd, w, u, aqk))
    # Every row of a chunk's stretch of e holds its e^{gam_C}.
    out += (e.reshape(B, Hk * rep, N, C)[..., 0],)
    return out + tuple(s.reshape(B, Hk, rep, NT, C, R) for s in solved)


@_traced_once
def local_bwd(q, k, v, g, beta, t, dqg, dkd, dw, du, daqk, de, *, interpret):
    """The transpose of ``local_fwd``: from its inputs, the saved T
    (packed) and the cotangents of its six outputs ([B, Hk*rep, N, ..] as
    the walk's backward returns them) to dq, dk (summed over a key head's
    value heads), dv, dg, dbeta, shaped as q, k, v, g, beta."""
    B, Hk, rep, N, C, dv = v.shape
    dk, dt, f32 = q.shape[-1], q.dtype, jnp.float32
    P, NT, blk, per_key, per_value = _local_plan(q, v)
    R = P * C

    def heads(x):
        return x.reshape(B, Hk, rep, *x.shape[2:])
    d_q, d_k, d_v, d_g, d_beta = pl.pallas_call(
        functools.partial(_local_bwd_kernel, C=C, P=P, rep=rep, block=blk),
        grid=(B * Hk, NT // blk), name="gdn_local_bwd",
        interpret=interpret,
        compiler_params=_grid_params(("parallel", "parallel"), _VMEM_LIMIT),
        in_specs=[per_key(R, dk), per_key(R, dk), per_value(R, dv),
                  per_value(1, R), per_value(1, R), per_value(C, R),
                  per_value(R, dk), per_value(R, dk), per_value(R, dk),
                  per_value(R, dv), per_value(R, C), per_value(1, R)],
        out_specs=[per_key(R, dk), per_key(R, dk), per_value(R, dv),
                   per_value(1, R), per_value(1, R)],
        out_shape=[jax.ShapeDtypeStruct((B * Hk, NT, R, dk), dt),
                   jax.ShapeDtypeStruct((B * Hk, NT, R, dk), dt),
                   jax.ShapeDtypeStruct((B * Hk, rep, NT, R, dv), dt),
                   jax.ShapeDtypeStruct((B * Hk, rep, NT, 1, R), f32),
                   jax.ShapeDtypeStruct((B * Hk, rep, NT, 1, R), f32)],
    )(_tiles(q, P, 2), _tiles(k, P, 2), _tiles(v, P, 3), _rows(g, P),
      _rows(beta, P), t.reshape(B * Hk, rep, NT, C, R),
      *(_tiles(heads(x), P, 3) for x in (dqg, dkd, dw, du, daqk)),
      _rows(jnp.broadcast_to(heads(de)[..., None], g.shape), P))
    return (d_q.reshape(q.shape), d_k.reshape(k.shape), d_v.reshape(v.shape),
            d_g.reshape(g.shape), d_beta.reshape(g.shape))


# -- the chunk-local stage under a decay per channel ---------------------------
#
# Tiles as above (P chunks in 128 rows, block-diagonal [R, R] arrays); the
# body is ``kda_tile.tile_fwd`` / ``tile_bwd`` with the products below. g
# enters as [R, dk] float32 beside k; beta, as in the kernels above, as a
# row [1, R].


class _MosaicOps:
    """The products of ``kda_tile``'s functions, as Mosaic takes them."""
    dot = staticmethod(_dot)

    @staticmethod
    def dot_hi(a, b, ca, cb):
        return _dot3(_split(a), _split(b), ca, cb)

    @staticmethod
    def dot_sum(mask, x, ca=1):
        # 0/1 is exact in bf16: the sum is x's high part plus its low
        # part, 16 bits of mantissa (an exponent's error: 2^-17 of it).
        return _dot3((mask.astype(jnp.bfloat16), None), _split(x), ca, 0)


def _channel_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, *rest, C: int,
                        P: int, block: int, solve: bool):
    if solve:
        (qg_ref, kd_ref, w_ref, u_ref, aqk_ref, e_ref, t_ref) = rest
    else:
        (t_ref, qg_ref, kd_ref, w_ref, u_ref, aqk_ref, e_ref) = rest
    tile = _Tile(C, P)
    dt = q_ref.dtype
    for j in range(block):
        beta = tile.over_lanes(tile.eye, beta_ref[j])
        t = None if solve else tile.from_packed(t_ref[j])
        qg, kd, w, u, aqk, e, t = kda_tile.tile_fwd(
            _MosaicOps, C, P, q_ref[j], k_ref[j], v_ref[j], g_ref[j], beta,
            t)
        qg_ref[j] = qg.astype(dt)
        kd_ref[j] = kd.astype(dt)
        w_ref[j] = w.astype(dt)
        u_ref[j] = u.astype(dt)
        aqk_ref[j] = tile.to_collapsed(aqk).astype(dt)
        e_ref[j] = e
        if solve:
            t_ref[j] = tile.to_packed(t)


def _channel_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, t_ref, dqg_ref,
                        dkd_ref, dw_ref, du_ref, daqk_ref, de_ref, dq_ref,
                        dk_ref, dv_ref, dg_ref, dbeta_ref, *, C: int, P: int,
                        block: int):
    tile = _Tile(C, P)
    dt = q_ref.dtype
    for j in range(block):
        dq, dk, dv, dg, dbeta = kda_tile.tile_bwd(
            _MosaicOps, C, P, q_ref[j], k_ref[j], v_ref[j], g_ref[j],
            tile.over_lanes(tile.eye, beta_ref[j]),
            tile.from_packed(t_ref[j]), dqg_ref[j], dkd_ref[j], dw_ref[j],
            du_ref[j], tile.from_collapsed(daqk_ref[j]), de_ref[j])
        dq_ref[j] = dq.astype(dt)
        dk_ref[j] = dk.astype(dt)
        dv_ref[j] = dv.astype(dt)
        dg_ref[j] = dg
        dbeta_ref[j] = tile.over_rows(tile.eye, dbeta)


def _channel_plan(q):
    """Of q [B, H, N, C, dk]: chunks a tile, tiles a head, tiles a grid
    step, and the block spec of an array [B*H, N/P, ..]."""
    N, C = q.shape[2:4]
    P = local_tile(N, C)
    blk = next(b for b in (_LOCAL_BLOCK, 1) if N // P % b == 0)

    def spec(*tail):
        return pl.BlockSpec((None, blk, *tail),
                            lambda b, n: (b, n) + (0,) * len(tail))
    return P, N // P, blk, spec


@_traced_once
def kda_local_fwd(q, k, v, g, beta, t=None, *, interpret):
    """``gated_delta._channel_fwd_xla`` as the kernel ``kda_local_fwd``:
    q, k, g [B, H, N, C, dk] (g float32), v [B, H, N, C, dv], beta
    [B, H, N, C] float32 -> (Q e^gam, K e^{gam_C - gam}, W, U, Aqk
    [B, H, N, C, .] in q's dtype, e^{gam_C} [B, H, N, dk] float32) and T
    float32 PACKED [B, H, N/P, C, P*C]. Given ``t`` the solve is skipped
    (and t handed back as it came)."""
    B, H, N, C, dk = q.shape
    dv, dt, f32 = v.shape[-1], q.dtype, jnp.float32
    P, NT, blk, spec = _channel_plan(q)
    R = P * C

    def like(*tail, dtype=dt):
        return jax.ShapeDtypeStruct((B * H, NT, *tail), dtype)
    inputs = [_tiles(x, P, 2) for x in (q, k, v, g)] \
        + [beta.reshape(B * H, NT, 1, R)]
    in_specs = [spec(R, dk), spec(R, dk), spec(R, dv), spec(R, dk),
                spec(1, R)]
    out_specs = [spec(R, dk), spec(R, dk), spec(R, dk), spec(R, dv),
                 spec(R, C), spec(P, dk)]
    out_shape = [like(R, dk), like(R, dk), like(R, dk), like(R, dv),
                 like(R, C), like(P, dk, dtype=f32)]
    if t is None:
        out_specs.append(spec(C, R))
        out_shape.append(like(C, R, dtype=f32))
    else:
        inputs.append(t.reshape(B * H, NT, C, R))
        in_specs.append(spec(C, R))
    qg, kd, w, u, aqk, e, *solved = pl.pallas_call(
        functools.partial(_channel_fwd_kernel, C=C, P=P, block=blk,
                          solve=t is None),
        grid=(B * H, NT // blk), name="kda_local_fwd", interpret=interpret,
        compiler_params=_grid_params(("parallel", "parallel"), _VMEM_LIMIT),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
    )(*inputs)
    out = tuple(x.reshape(B, H, N, C, x.shape[-1])
                for x in (qg, kd, w, u, aqk))
    out += (e.reshape(B, H, N, dk),)
    return out + ((solved[0].reshape(B, H, NT, C, R),) if solved else (t,))


@_traced_once
def kda_local_bwd(q, k, v, g, beta, t, dqg, dkd, dw, du, daqk, de, *,
                  interpret):
    """The transpose of :func:`kda_local_fwd` as the kernel
    ``kda_local_bwd``: (dq, dk, dv in q's dtype, dg [B, H, N, C, dk] and
    dbeta [B, H, N, C] float32)."""
    B, H, N, C, dk = q.shape
    dv, dt, f32 = v.shape[-1], q.dtype, jnp.float32
    P, NT, blk, spec = _channel_plan(q)
    R = P * C

    def like(*tail, dtype=dt):
        return jax.ShapeDtypeStruct((B * H, NT, *tail), dtype)
    d_q, d_k, d_v, d_g, d_beta = pl.pallas_call(
        functools.partial(_channel_bwd_kernel, C=C, P=P, block=blk),
        grid=(B * H, NT // blk), name="kda_local_bwd", interpret=interpret,
        compiler_params=_grid_params(("parallel", "parallel"), _VMEM_LIMIT),
        in_specs=[spec(R, dk), spec(R, dk), spec(R, dv), spec(R, dk),
                  spec(1, R), spec(C, R), spec(R, dk), spec(R, dk),
                  spec(R, dk), spec(R, dv), spec(R, C), spec(P, dk)],
        out_specs=[spec(R, dk), spec(R, dk), spec(R, dv), spec(R, dk),
                   spec(1, R)],
        out_shape=[like(R, dk), like(R, dk), like(R, dv),
                   like(R, dk, dtype=f32), like(1, R, dtype=f32)],
    )(*(_tiles(x, P, 2) for x in (q, k, v, g)),
      beta.reshape(B * H, NT, 1, R), t.reshape(B * H, NT, C, R),
      *(_tiles(x, P, 2) for x in (dqg, dkd, dw, du, daqk)),
      de.reshape(B * H, NT, P, dk))
    return (d_q.reshape(q.shape), d_k.reshape(k.shape), d_v.reshape(v.shape),
            d_g.reshape(g.shape), d_beta.reshape(beta.shape))
