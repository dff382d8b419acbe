"""The chunk-local stage of the gated delta rule under a decay per key
channel (Kimi Delta Attention), for one TILE: P consecutive chunks of C
rows of one head as 2-D arrays [R, .] (R = P C). ``ops/gated_delta.py``
maps it over the chunks with P = 1 (the ``"xla"`` backend) and
``ops/pallas_gated_delta.py`` calls it on 128 rows as the body of the
kernels ``kda_local_fwd`` / ``kda_local_bwd``; the equations, and why the
pairs of a chunk are split by levels, are in ``gated_delta``'s header. A
[C, C] array of the stage is the block-diagonal [R, R] array of its tile.

Every product goes through ``ops``, which the caller brings:
``dot(a, b, ca, cb)`` in the operands' dtype with float32 accumulation,
``dot_hi`` of float32 operands in three bf16 passes, ``dot_sum(mask, x,
ca)`` of a 0/1 matrix with float32 ``x`` to float32's digits (sums of rows
of ``x``).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


class ChannelTile:
    """The masks and decays of a tile under a gate per channel, g [R, dk]
    float32: ``e_gam`` = e^gam and ``e_end`` = e^{gam_C - gam} [R, dk], and
    per level l (pairs whose highest differing bit is l) the decay
    ``e`` [R, dk] of every row against its block's reference row and the
    mask ``pair`` [R, R] of the level's pairs (i below, j above)."""

    def __init__(self, ops, C: int, P: int, g, dtype):
        R = C * P
        i = lax.broadcasted_iota(jnp.int32, (R, R), 0)
        t = lax.broadcasted_iota(jnp.int32, (R, R), 1)
        same = (i ^ t) < C          # C is a power of two: no division
        self.ops, self.dtype, self.C, self.P = ops, dtype, C, P
        self.eye = i == t
        self.tril = same & (i >= t)
        self.stril = same & (i > t)
        self.after = same & (t > i)
        self.e_gam = jnp.exp(ops.dot_sum(self.tril, g))
        self.e_end = jnp.exp(ops.dot_sum(self.after, g))
        self.levels = []
        s = 1
        while s < C:
            below = (i & s) != 0
            start = i & -s                    # the row its half starts at
            stretch = (below & (t > start) & (t <= i)) \
                | (~below & (t > i) & (t <= (start | s)))
            self.levels.append((
                jnp.exp(ops.dot_sum(stretch, g)),
                ((i ^ t) < 2 * s) & below & ((t & s) == 0)))
            s *= 2

    def _cast(self, x):
        return x.astype(self.dtype)

    def scores(self, y, z, diagonal: bool):
        """sum_c y_ic z_jc exp(gam_ic - gam_jc) for i > j of one chunk
        (``diagonal``: and i = j), 0 elsewhere: [R, R] float32."""
        out = jnp.where(self.eye, self.ops.dot(
            self._cast(y), self._cast(z), 1, 1), 0.0) if diagonal else 0.0
        for e, pair in self.levels:
            out = out + jnp.where(pair, self.ops.dot(
                self._cast(y * e), self._cast(z * e), 1, 1), 0.0)
        return out

    def _weighted(self, x, y, contract: int, diagonal: bool):
        out = self.ops.dot(self._cast(jnp.where(self.eye, x, 0.0)),
                           self._cast(y), contract, 0) if diagonal else 0.0
        for e, pair in self.levels:
            out = out + e * self.ops.dot(
                self._cast(jnp.where(pair, x, 0.0)), self._cast(y * e),
                contract, 0)
        return out

    def over_columns(self, x, y, diagonal: bool):
        """sum_j x_ij y_jc exp(gam_ic - gam_jc) over the same pairs:
        [R, dk] float32 (the transpose of ``scores`` towards its rows)."""
        return self._weighted(x, y, 1, diagonal)

    def over_rows(self, x, y, diagonal: bool):
        """sum_i x_ij y_ic exp(gam_ic - gam_jc): towards its columns."""
        return self._weighted(x, y, 0, diagonal)

    def inverse(self, a):
        """``(I + a)^-1`` for the block-diagonal strictly lower a, as
        ``gated_delta._unit_lower_inverse``."""
        n = -a
        inv = jnp.where(self.eye, 1.0, 0.0) + n
        for _ in range(max(0, (self.C - 1).bit_length() - 1)):
            n = self.ops.dot_hi(n, n, 1, 0)
            inv = inv + self.ops.dot_hi(n, inv, 1, 0)
        return inv

    def chunk_ends(self, x):
        """[R, .] -> the last row of each chunk, [P, .]."""
        C = self.C
        return jnp.concatenate([x[(p + 1) * C - 1:(p + 1) * C]
                                for p in range(self.P)], axis=0)

    def over_chunks(self, x):
        """[P, .] -> [R, .]: each row under all the rows of its chunk."""
        return jnp.concatenate([jnp.broadcast_to(
            x[p:p + 1], (self.C, x.shape[1])) for p in range(self.P)], axis=0)


def tile_fwd(ops, C: int, P: int, q, k, v, g, beta, t=None):
    """One tile's stage: q, k [R, dk], v [R, dv], g [R, dk] float32, beta
    [R, 1] float32, t the solve [R, R] or None (then it is made) -> float32
    (Q e^gam, K e^{gam_C - gam}, W, U, Aqk [R, R], e^{gam_C} [P, dk], T)."""
    f32 = jnp.float32
    tile = ChannelTile(ops, C, P, g.astype(f32), q.dtype)
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    if t is None:
        t = tile.inverse(jnp.where(
            tile.stril, beta * tile.scores(kf, kf, False), 0.0))
    w = ops.dot_hi(t, kf * (beta * tile.e_gam), 1, 0)
    u = ops.dot_hi(t, vf * beta, 1, 0)
    aqk = jnp.where(tile.tril, tile.scores(qf, kf, True), 0.0)
    return (qf * tile.e_gam, kf * tile.e_end, w, u, aqk,
            tile.chunk_ends(tile.e_gam), t)


def tile_bwd(ops, C: int, P: int, q, k, v, g, beta, t, dqg, dkd, dw,
                      du, daqk, de):
    """The transpose of :func:`tile_fwd`, written out: from its
    inputs, the solve and the cotangents of its six outputs (daqk [R, R],
    de [P, dk]) to float32 (dq, dk [R, dk], dv [R, dv], dg [R, dk], dbeta
    [R, 1]). gam enters through differences only, so its cotangent is
    (what a pair's row side received) - (what its column side received),
    each a product the transposes already made."""
    f32 = jnp.float32
    tile = ChannelTile(ops, C, P, g.astype(f32), q.dtype)
    qf, kf, vf = q.astype(f32), k.astype(f32), v.astype(f32)
    dqg, dkd, dw, du = (x.astype(f32) for x in (dqg, dkd, dw, du))
    e_gam, e_end = tile.e_gam, tile.e_end
    kt = kf * (beta * e_gam)                   # W = T Kt, U = T Vt
    dkt = ops.dot_hi(t, dw, 0, 0)
    dvt = ops.dot_hi(t, du, 0, 0)
    d_t = ops.dot_hi(dw, kt, 1, 1) + ops.dot_hi(du, vf * beta, 1, 1)
    # T = (I + A)^-1: dA = -T^T dT T^T.
    da = jnp.where(tile.stril, -ops.dot_hi(
        t, ops.dot_hi(d_t, t, 1, 1), 0, 0), 0.0)
    m = tile.scores(kf, kf, False)             # A = beta M
    dm = da * beta
    p = jnp.where(tile.tril, daqk.astype(f32), 0.0)
    dk_i = tile.over_columns(dm, kf, False)
    dk_j = tile.over_rows(dm, kf, False)
    dq_a = tile.over_columns(p, kf, True)
    dk_a = tile.over_rows(p, qf, True)
    dq = dq_a + dqg * e_gam
    dk = dk_i + dk_j + dk_a + dkd * e_end + dkt * (beta * e_gam)
    dbeta = (jnp.sum(da * m, axis=1, keepdims=True)
             + jnp.sum(dkt * kf * e_gam, axis=1, keepdims=True)
             + jnp.sum(dvt * vf, axis=1, keepdims=True))
    d_gam = (kf * (dk_i - dk_j - dk_a) + qf * dq_a + dqg * qf * e_gam
             + dkt * kt)
    dg = (ops.dot_sum(tile.tril, d_gam, 0)
          + ops.dot_sum(tile.after, dkd * kf * e_end, 0)
          + tile.over_chunks(de * tile.chunk_ends(e_gam)))
    return dq, dk, dvt * beta, dg, dbeta
