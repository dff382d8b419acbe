"""Pallas TPU kernels of attention over a per-row selected set of keys with
grouped key/value heads (``ops/sparse_attention.py`` has the mathematics
and the XLA forms): flash attention's online softmax, on the causal tile
schedule, with the selection read as an int8 ``[tq, tk]`` tile that all the
heads of a group share.

One visit works one (batch, key/value head, q tile, k tile) and loops over
the group's query heads inside: q arrives as the projection's output
``[B, T, Hq*d]`` (head-major columns), so a block of ``G*d`` columns is one
key/value head's group and each head a lane-aligned slice of it; nothing is
transposed on either side. K/V tiles above the diagonal are neither
fetched nor computed. A tile that holds no selected pair is computed like
any other: at top-2048 of 8192 keys nearly every tile holds some.

The forward and the split backward stream K/V tiles over a fourth grid
axis whose block index is clamped at the diagonal. The backward is chosen
from the shape by ``_bwd_plan``: ``fused`` — one kernel on grid (b, g, qi)
with K, V and the float32 dk/dv accumulators of the whole sequence in VMEM
and an in-kernel loop over the k tiles, dq, dk and dv from one visit of
each pair — wherever those residents fit ``_VMEM_LIMIT`` (bf16, d 128, a
group of 8: to T 15360), and ``split`` — a dq kernel and a dkv kernel that
each rebuild the probabilities — beyond.
``hvd_dsa_bwd_plan_total{plan=}`` counts the traces of each.

Kernel names (``pallas_call(name=)``; a device trace and the compiled HLO
find the kernels by them, so they are API): ``dsa_fwd``; ``dsa_bwd``
(fused) or ``dsa_bwd_dq`` and ``dsa_bwd_dkv`` (split); and the indexer's
loss ``dsa_kl`` (below).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _VMEM_LIMIT, _dot, _grid_params, _lanes

_TQ, _TK = 256, 512
_STAT_LANES = 8          # lse / delta: one lane per head of the group
# Width of the strips the fused backward cuts the DIAGONAL k tile into (a
# q tile sees the first strip of its diagonal tile, or both). Measured on
# a v5e (PERF.md PR 29, the kernel alone at the Keye shape): 256 -> 15.38
# ms a layer, 512 (the tile whole) -> 15.63.
_DIAG = 256


def tilable(T: int, d: int) -> bool:
    return T % _TK == 0 and d % 128 == 0


def _last_k(qi):
    """The last k tile a q tile needs (causal)."""
    return (qi * _TQ + _TQ - 1) // _TK


def _first_q(kb):
    """The first q tile that sees a k tile."""
    return (kb * _TK) // _TQ


def _head(ref, h: int, d: int):
    return ref[0, :, h * d:(h + 1) * d]


def _scaled_head(q_ref, h: int, d: int, scale: float):
    """Head ``h`` of a q block times the softmax scale, in q's dtype (the
    same rounding in every kernel, so the backward rebuilds the forward's
    scores)."""
    q = _head(q_ref, h, d)
    return (q.astype(jnp.float32) * scale).astype(q.dtype)


def _col(ref, h: int):
    """Column ``h`` of a [tq, 8] stat block as [tq, 1]."""
    return ref[0, 0][:, h:h + 1]


def _selected(mask_ref):
    return mask_ref[0].astype(jnp.int32) != 0


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, G: int, d: int, scale: float):
    qi, kb, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(kb == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -1e30)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(kb <= _last_k(qi))
    def _():
        sel = _selected(mask_ref)
        k, v = k_ref[0], v_ref[0]
        for h in range(G):
            q = _scaled_head(q_ref, h, d, scale)
            s = jnp.where(sel, _dot(q, k, 1, 1), -1e30)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # A row with nothing selected so far has m = -1e30 and s - m = 0
            # on its unselected keys: they must stay out of the sum.
            p = jnp.where(sel, jnp.exp(s - _lanes(m_new, s.shape[1])), 0.0)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * _lanes(alpha, d) + _dot(
                p.astype(v.dtype), v, 1, 0)
            m_ref[h] = m_new

    @pl.when(kb == nk - 1)
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, (_TQ, _STAT_LANES), 1)
        lse = jnp.zeros((_TQ, _STAT_LANES), jnp.float32)
        for h in range(G):
            l = l_ref[h]
            o_ref[0, :, h * d:(h + 1) * d] = (
                acc_ref[h] / _lanes(l, d)).astype(o_ref.dtype)
            lse = jnp.where(lane == h, (m_ref[h] + jnp.log(l))[:, :_STAT_LANES],
                            lse)
        lse_ref[0, 0] = lse


def _probabilities(q_ref, k, sel, lse_ref, h, d, scale):
    q = _scaled_head(q_ref, h, d, scale)
    return q, jnp.where(sel, jnp.exp(_dot(q, k, 1, 1) - _col(lse_ref, h)),
                        0.0)


def _dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
               dq_ref, acc_ref, *, G: int, d: int, scale: float):
    qi, kb, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(kb == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(kb <= _last_k(qi))
    def _():
        sel = _selected(mask_ref)
        k, v = k_ref[0], v_ref[0]
        for h in range(G):
            _, p = _probabilities(q_ref, k, sel, lse_ref, h, d, scale)
            dp = _dot(_head(do_ref, h, d), v, 1, 1)
            ds = p * (dp - _col(delta_ref, h))
            acc_ref[:, h * d:(h + 1) * d] += _dot(ds.astype(k.dtype), k, 1, 0)

    @pl.when(kb == nk - 1)
    def _():
        dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, G: int, d: int,
                scale: float):
    kb, qi, nq = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(qi >= _first_q(kb))
    def _():
        sel = _selected(mask_ref)
        k, v = k_ref[0], v_ref[0]
        for h in range(G):
            q, p = _probabilities(q_ref, k, sel, lse_ref, h, d, scale)
            do = _head(do_ref, h, d)
            dv_acc[:] += _dot(p.astype(do.dtype), do, 0, 0)
            ds = p * (_dot(do, v, 1, 1) - _col(delta_ref, h))
            dk_acc[:] += _dot(ds.astype(q.dtype), q, 0, 0)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                      delta_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc,
                      dv_acc, qs_ref, lse_rep, delta_rep, *, G: int, d: int,
                      scale: float):
    """dq, dk and dv from ONE visit of each (q tile, k tile) pair, grid
    (b, g, qi): K and V of the (batch, key/value head) are whole in VMEM
    (fetched once a head, not once a q tile), an in-kernel loop walks the
    k tiles up to the diagonal and none beyond, and per head s, p, dp and
    ds are computed once and spent three ways: 5 matmuls and 1 exp pass a
    head and pair, against 7 and 2 over ``_dq_kernel`` + ``_dkv_kernel``.
    dq accumulates per q tile; dk and dv over the whole visit in [T, d]
    float32 scratch, zeroed at the first q tile and written at the last.
    The diagonal k tile is visited in strips of ``_DIAG`` columns, and of
    those only the ones a row of the q tile can see."""
    qi, nq = pl.program_id(2), pl.num_programs(2)

    @pl.when(qi == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # Once a q tile: q scaled as the forward scales it, and the stats
    # replicated over their lanes (a [tq, 1] column would pay a lane
    # broadcast against every [tq, tk] block).
    dq_acc[:] = jnp.zeros_like(dq_acc)
    for h in range(G):
        qs_ref[:, h * d:(h + 1) * d] = _scaled_head(q_ref, h, d, scale)
        lse_rep[h] = jnp.broadcast_to(_col(lse_ref, h), (_TQ, 128))
        delta_rep[h] = jnp.broadcast_to(_col(delta_ref, h), (_TQ, 128))

    def visit(start, width: int):
        """The q tile against the key rows [start, start + width): the
        selection is widened once and shared by the group's heads."""
        rows = pl.ds(pl.multiple_of(start, width), width)
        k, v = k_ref[0, rows, :], v_ref[0, rows, :]
        sel = mask_ref[0, :, rows].astype(jnp.int32) != 0
        for h in range(G):
            cols = slice(h * d, (h + 1) * d)
            q, do = qs_ref[:, cols], do_ref[0, :, cols]
            p = jnp.where(sel, jnp.exp(_dot(q, k, 1, 1)
                                       - _lanes(lse_rep[h], width)), 0.0)
            ds = (p * (_dot(do, v, 1, 1) - _lanes(delta_rep[h], width))
                  ).astype(k.dtype)
            dq_acc[:, cols] += _dot(ds, k, 1, 0)
            dv_acc[rows, :] += _dot(p.astype(do.dtype), do, 0, 0)
            dk_acc[rows, :] += _dot(ds, q, 0, 0)

    def below(kb, carry):
        visit(kb * _TK, _TK)
        return carry
    last = _last_k(qi)
    jax.lax.fori_loop(0, last, below, 0)
    for j in range(_TK // _DIAG):
        start = last * _TK + j * _DIAG
        pl.when(start < (qi + 1) * _TQ)(functools.partial(visit, start, _DIAG))
    dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _call(kernel, name: str, grid, **specs):
    """``pallas_call`` with what every kernel here shares: the innermost
    grid axis carries the accumulation, the others are independent."""
    return pl.pallas_call(
        kernel, grid=grid, interpret=_interpret(), name=name,
        compiler_params=_grid_params(
            ("parallel",) * (len(grid) - 1) + ("arbitrary",), _VMEM_LIMIT),
        **specs)


def _specs(G: int, d: int, q_major: bool):
    """Block specs on grid (b, g, qi, kb) (``q_major``) or (b, g, kb, qi):
    the q-side blocks, the K/V tiles, the mask tile and the stat blocks,
    with the tile that moves in the inner loop clamped at the diagonal."""
    if q_major:
        def at(b, g, qi, kb):
            return b, g, qi, jnp.minimum(kb, _last_k(qi))
    else:
        def at(b, g, kb, qi):
            return b, g, jnp.maximum(qi, _first_q(kb)), kb

    def spec(shape, pick):
        return pl.BlockSpec(shape, lambda *ids: pick(*at(*ids)))
    return {
        "q": spec((1, _TQ, G * d), lambda b, g, qi, kb: (b, qi, g)),
        "kv": spec((1, _TK, d), lambda b, g, qi, kb: (b, kb, g)),
        "mask": spec((1, _TQ, _TK), lambda b, g, qi, kb: (b, qi, kb)),
        "stat": spec((1, 1, _TQ, _STAT_LANES),
                     lambda b, g, qi, kb: (b, g, qi, 0)),
    }


@functools.partial(jax.jit, static_argnames=("Hkv",))
def _fwd(q, k, v, mask, *, Hkv: int):
    """q [B, T, Hq*d], k/v [B, T, Hkv*d], mask int8 [B, T, T] ->
    (o [B, T, Hq*d], lse [B, Hkv, T, 8] float32, one lane a head)."""
    B, T, _ = q.shape
    d = k.shape[2] // Hkv
    G = q.shape[2] // (Hkv * d)
    sp = _specs(G, d, q_major=True)
    return _call(
        functools.partial(_fwd_kernel, G=G, d=d, scale=d ** -0.5),
        "dsa_fwd", (B, Hkv, T // _TQ, T // _TK),
        in_specs=[sp["q"], sp["kv"], sp["kv"], sp["mask"]],
        out_specs=[sp["q"], sp["stat"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, Hkv, T, _STAT_LANES),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((G, _TQ, d), jnp.float32),
                        pltpu.VMEM((G, _TQ, 128), jnp.float32),
                        pltpu.VMEM((G, _TQ, 128), jnp.float32)],
    )(q, k, v, mask)


def _bwd_plan(T: int, d: int, G: int, itemsize: int) -> str:
    """Which backward a shape gets: ``fused`` (``dsa_bwd``) where its
    whole-sequence residents fit ``_VMEM_LIMIT``, ``split``
    (``dsa_bwd_dq`` + ``dsa_bwd_dkv``, tiles only) beyond.

    The sum bounds what the v5e compiler allocates from above: the
    smallest ``vmem_limit_bytes`` that compiles sat 2.2-2.7 MiB under it
    at the Keye shape (T 8192, d 128, G 8, bf16: 35.7 against 38.0 MiB)
    and at T 12288 and 16384, and further under it elsewhere (bf16 and
    float32, G 1 to 8, T 1024 to 17408; ``tests/test_tpu_compile.py``
    keeps both sides of the boundary compiling):

    * scratch: the float32 accumulators dk/dv 2x[T, d] and dq [tq, G*d],
      q scaled [tq, G*d], and the two stats lane-replicated [G, tq, 128];
    * pipelined operands, DOUBLE-buffered even where they are constant
      over a (batch, head) visit: K, V and the dk, dv outputs [T, d], the
      q, dO and dq blocks [tq, G*d], the q tile's int8 selection rows
      [tq, T] and two stat blocks (a full 128-lane tile in VMEM);
    * stack: the [tq, tk] float32 intermediates (s/p, dp, ds and their
      casts) and the selection widened to 32 bits.
    """
    block = _TQ * G * d
    scratch = 4 * (2 * T * d + block) + itemsize * block \
        + 2 * G * _TQ * 128 * 4
    piped = itemsize * (4 * T * d + 3 * block) + _TQ * T \
        + 2 * _TQ * 128 * 4
    stack = 6 * _TQ * _TK * 4
    return "fused" if scratch + 2 * piped + stack <= _VMEM_LIMIT else "split"


def _plan_counter():
    from ..obs.registry import registry
    return registry().counter(
        "hvd_dsa_bwd_plan_total",
        "traces of the sparse-attention backward, by the kernels its shape "
        "got: fused (dsa_bwd) or split (dsa_bwd_dq + dsa_bwd_dkv)",
        labels=("plan",))


@functools.partial(jax.jit, static_argnames=("Hkv",))
def _bwd(q, k, v, mask, o, lse, do, *, Hkv: int):
    B, T, _ = q.shape
    d = k.shape[2] // Hkv
    G = q.shape[2] // (Hkv * d)
    # delta[t, n] = sum_d do * o: [B, T, Hq] -> the stats' [B, Hkv, T, 8].
    delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32)
                     ).reshape(B, T, Hkv, G, d), axis=-1)
    delta = jnp.pad(delta.transpose(0, 2, 1, 3),
                    ((0, 0),) * 3 + ((0, _STAT_LANES - G),))
    args = (q, k, v, mask, do, lse, delta)
    scale = d ** -0.5
    plan = _bwd_plan(T, d, G, q.dtype.itemsize)
    _plan_counter().labels(plan=plan).inc()
    kv_like = [jax.ShapeDtypeStruct(k.shape, k.dtype),
               jax.ShapeDtypeStruct(v.shape, v.dtype)]
    if plan == "fused":
        q_side = pl.BlockSpec((1, _TQ, G * d), lambda b, g, qi: (b, qi, g))
        whole = pl.BlockSpec((1, T, d), lambda b, g, qi: (b, 0, g))
        stat = pl.BlockSpec((1, 1, _TQ, _STAT_LANES),
                            lambda b, g, qi: (b, g, qi, 0))
        return _call(
            functools.partial(_bwd_fused_kernel, G=G, d=d, scale=scale),
            "dsa_bwd", (B, Hkv, T // _TQ),
            in_specs=[q_side, whole, whole,
                      pl.BlockSpec((1, _TQ, T), lambda b, g, qi: (b, qi, 0)),
                      q_side, stat, stat],
            out_specs=[q_side, whole, whole],
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] + kv_like,
            scratch_shapes=[pltpu.VMEM((_TQ, G * d), jnp.float32),
                            pltpu.VMEM((T, d), jnp.float32),
                            pltpu.VMEM((T, d), jnp.float32),
                            pltpu.VMEM((_TQ, G * d), q.dtype),
                            pltpu.VMEM((G, _TQ, 128), jnp.float32),
                            pltpu.VMEM((G, _TQ, 128), jnp.float32)],
        )(*args)

    def ins(sp):
        return [sp["q"], sp["kv"], sp["kv"], sp["mask"], sp["q"],
                sp["stat"], sp["stat"]]
    sp = _specs(G, d, q_major=True)
    dq = _call(
        functools.partial(_dq_kernel, G=G, d=d, scale=scale),
        "dsa_bwd_dq", (B, Hkv, T // _TQ, T // _TK),
        in_specs=ins(sp), out_specs=sp["q"],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((_TQ, G * d), jnp.float32)],
    )(*args)
    sp = _specs(G, d, q_major=False)
    dk, dv = _call(
        functools.partial(_dkv_kernel, G=G, d=d, scale=scale),
        "dsa_bwd_dkv", (B, Hkv, T // _TK, T // _TQ),
        in_specs=ins(sp), out_specs=[sp["kv"], sp["kv"]],
        out_shape=kv_like,
        scratch_shapes=[pltpu.VMEM((_TK, d), jnp.float32),
                        pltpu.VMEM((_TK, d), jnp.float32)],
    )(*args)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _core(q, k, v, mask, Hkv):
    return _fwd(q, k, v, mask, Hkv=Hkv)


def _core_fwd(q, k, v, mask, Hkv):
    o, lse = _fwd(q, k, v, mask, Hkv=Hkv)
    return (o, lse), (q, k, v, mask, o, lse)


def _core_bwd(Hkv, res, cts):
    # The log-sum-exp goes on to constants only (the KL's head-mean of P is
    # behind a stop-gradient), so its cotangent is not used.
    dq, dk, dv = _bwd(*res, cts[0], Hkv=Hkv)
    return dq, dk, dv, None


_core.defvjp(_core_fwd, _core_bwd)


def attend(q, k, v, mask):
    """q [B, T, Hq, d], k/v [B, T, Hkv, d], mask int8 [B, T, T] ->
    (o [B, T, Hq, d], lse [B, T, Hq] float32): attention of each row over
    its selected keys, differentiable in q, k and v."""
    B, T, Hq, d = q.shape
    Hkv = k.shape[2]
    o, lse = _core(q.reshape(B, T, Hq * d), k.reshape(B, T, Hkv * d),
                   v.reshape(B, T, Hkv * d), mask, Hkv)
    lse = lse[..., :Hq // Hkv].transpose(0, 2, 1, 3).reshape(B, T, Hq)
    return o.reshape(B, T, Hq, d), lse


# ---------------------------------------------------------------------------
# The indexer's loss. For one (batch, q tile, k tile): the head-mean of P is
# rebuilt from q, k and the saved log-sum-exp of every head, the index
# scores from the indexer's inputs, and from the two the tile's part of
# each row's KL AND of its gradient with respect to the indexer's inputs —
# d KL_t / d I[t, s] = softmax(I)[t, s] - mean_n P[n, t, s] on S_t — so the
# backward pass has nothing left to recompute. Rows are weighted by
# ``row_weight`` (the differentiated quantity is the weighted sum over a
# sequence's rows).
# ---------------------------------------------------------------------------


def _kl_kernel(q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref, lsei_ref,
               rw_ref, mask_ref, kl_ref, dqi_ref, dw_ref, dki_ref,
               kl_acc, dqi_acc, dw_acc, *, Hkv: int, G: int, d: int,
               Hi: int, di: int, scale: float):
    qi_, kb, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)

    @pl.when(kb == 0)
    def _():
        kl_acc[:] = jnp.zeros_like(kl_acc)
        dqi_acc[:] = jnp.zeros_like(dqi_acc)
        dw_acc[:] = jnp.zeros_like(dw_acc)

    @pl.when(kb <= _last_k(qi_))
    def _():
        sel = _selected(mask_ref)
        p_sum = jnp.zeros((_TQ, _TK), jnp.float32)
        for g in range(Hkv):
            k = k_ref[0, :, g * d:(g + 1) * d]
            for h in range(G):
                q = _scaled_head(q_ref, g * G + h, d, scale)
                p_sum += jnp.exp(_dot(q, k, 1, 1)
                                 - lse_ref[0, g][:, h:h + 1])
        p_mean = jnp.where(sel, p_sum * (1.0 / (Hkv * G)), 0.0)

        ki = ki_ref[0]
        w = w_ref[0]

        def index_head(j):
            return _dot(qi_ref[0, :, j * di:(j + 1) * di], ki, 1, 1)
        index = jnp.zeros((_TQ, _TK), jnp.float32)
        for j in range(Hi):
            index += w[:, j:j + 1] * jnp.maximum(index_head(j), 0.0)
        log_pi = index - lsei_ref[0][:, :1]
        pi = jnp.where(sel, jnp.exp(log_pi), 0.0)
        log_p = jnp.log(jnp.where(p_mean > 0, p_mean, 1.0))
        kl_acc[:] += jnp.sum(p_mean * (log_p - jnp.where(sel, log_pi, 0.0)),
                             axis=-1, keepdims=True)

        d_index = (pi - p_mean) * rw_ref[0][:, :1]
        lane = jax.lax.broadcasted_iota(jnp.int32, dw_acc.shape, 1)
        dki = jnp.zeros((_TK, di), jnp.float32)
        for j in range(Hi):
            s = index_head(j)
            dw_acc[:] += jnp.where(lane == j, jnp.sum(
                d_index * jnp.maximum(s, 0.0), axis=-1, keepdims=True), 0.0)
            ds = jnp.where(s > 0, d_index * w[:, j:j + 1], 0.0
                           ).astype(ki.dtype)
            dqi_acc[:, j * di:(j + 1) * di] += _dot(ds, ki, 1, 0)
            dki += _dot(ds, qi_ref[0, :, j * di:(j + 1) * di], 0, 0)
        dki_ref[0, 0] = dki

    @pl.when(kb == nk - 1)
    def _():
        kl_ref[0] = kl_acc[:, :_STAT_LANES]
        dqi_ref[0] = dqi_acc[:]
        dw_ref[0] = dw_acc[:, :Hi]


@functools.partial(jax.jit, static_argnames=("Hkv",))
def _kl(q, k, lse, qi, ki, w, lse_i, row_weight, mask, *, Hkv: int):
    """-> (KL_t [B, T], and of sum_t row_weight[t] KL_t the gradients with
    respect to qi [B, T, Hi*di], ki [B, T, di] and w [B, T, Hi], float32)."""
    B, T, _ = q.shape
    d = k.shape[2] // Hkv
    G = q.shape[2] // (Hkv * d)
    di, Hi = ki.shape[2], w.shape[2]
    nq, nk = T // _TQ, T // _TK

    def stat(x):                       # [B, T] -> [B, T, 8], lane-replicated
        return jnp.broadcast_to(x.astype(jnp.float32)[..., None],
                                (B, T, _STAT_LANES))

    def q_side(width):
        return pl.BlockSpec((1, _TQ, width), lambda b, qi_, kb: (b, qi_, 0))

    def k_side(width):
        return pl.BlockSpec((1, _TK, width), lambda b, qi_, kb: (
            b, jnp.minimum(kb, _last_k(qi_)), 0))
    kl, dqi, dw, dki = _call(
        functools.partial(_kl_kernel, Hkv=Hkv, G=G, d=d, Hi=Hi, di=di,
                          scale=d ** -0.5),
        "dsa_kl", (B, nq, nk),
        in_specs=[q_side(q.shape[2]), k_side(k.shape[2]),
                  pl.BlockSpec((1, Hkv, _TQ, _STAT_LANES),
                               lambda b, qi_, kb: (b, 0, qi_, 0)),
                  q_side(Hi * di), k_side(di), q_side(Hi),
                  q_side(_STAT_LANES), q_side(_STAT_LANES),
                  pl.BlockSpec((1, _TQ, _TK), lambda b, qi_, kb: (
                      b, qi_, jnp.minimum(kb, _last_k(qi_))))],
        out_specs=[q_side(_STAT_LANES), q_side(Hi * di), q_side(Hi),
                   pl.BlockSpec((1, 1, _TK, di), lambda b, qi_, kb: (
                       b, qi_, jnp.minimum(kb, _last_k(qi_)), 0))],
        out_shape=[jax.ShapeDtypeStruct((B, T, _STAT_LANES), jnp.float32),
                   jax.ShapeDtypeStruct((B, T, Hi * di), jnp.float32),
                   jax.ShapeDtypeStruct((B, T, Hi), jnp.float32),
                   jax.ShapeDtypeStruct((B, nq, T, di), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_TQ, 128), jnp.float32),
                        pltpu.VMEM((_TQ, Hi * di), jnp.float32),
                        pltpu.VMEM((_TQ, 128), jnp.float32)],
    )(q, k, lse, qi, ki, w, stat(lse_i), stat(row_weight), mask)
    # A q tile's part of dki exists for the k tiles it sees; the blocks
    # above the diagonal were never written.
    seen = jnp.arange(nk)[None, :] <= _last_k(jnp.arange(nq))[:, None]
    dki = jnp.where(seen[None, :, :, None, None],
                    dki.reshape(B, nq, nk, _TK, di), 0.0).sum(1)
    return kl[..., 0], dqi, dki.reshape(B, T, di), dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(9,))
def _kl_core(q, k, lse, qi, ki, w, lse_i, row_weight, mask, Hkv):
    kl, *_ = _kl(q, k, lse, qi, ki, w, lse_i, row_weight, mask, Hkv=Hkv)
    return jnp.sum(kl * row_weight, axis=-1), kl


def _kl_core_fwd(q, k, lse, qi, ki, w, lse_i, row_weight, mask, Hkv):
    kl, dqi, dki, dw = _kl(q, k, lse, qi, ki, w, lse_i, row_weight, mask,
                           Hkv=Hkv)
    return (jnp.sum(kl * row_weight, axis=-1), kl), (
        dqi.astype(qi.dtype), dki.astype(ki.dtype), dw.astype(w.dtype))


def _kl_core_bwd(Hkv, res, cts):
    g = cts[0][:, None, None]          # of each sequence's weighted sum
    dqi, dki, dw = res
    return (None, None, None, (g * dqi).astype(dqi.dtype),
            (g * dki).astype(dki.dtype), (g * dw).astype(dw.dtype),
            None, None, None)


_kl_core.defvjp(_kl_core_fwd, _kl_core_bwd)


def indexer_kl(q, k, lse, qi, ki, w, lse_i, row_weight, mask):
    """(sum over each sequence's rows of row_weight x KL_t [B], KL_t
    [B, T]). q [B, T, Hq, d], k [B, T, Hkv, d] and lse [B, T, Hq] (of
    :func:`attend`) are constants; the sum is differentiable in qi
    [B, T, Hi, di], ki [B, T, di] and w [B, T, Hi]; lse_i [B, T] is the
    log-sum-exp of each row's index scores over its selected keys."""
    B, T, Hq, d = q.shape
    Hkv, G = k.shape[2], Hq // k.shape[2]
    lse = jnp.pad(lse.reshape(B, T, Hkv, G).transpose(0, 2, 1, 3),
                  ((0, 0),) * 3 + ((0, _STAT_LANES - G),))
    return _kl_core(q.reshape(B, T, Hq * d), k.reshape(B, T, Hkv * d), lse,
                    qi.reshape(B, T, -1), ki, w, lse_i, row_weight, mask, Hkv)
