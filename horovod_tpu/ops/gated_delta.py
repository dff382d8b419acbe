"""The gated delta rule (Gated DeltaNet's linear attention) in chunked form.

Per sequence and head, with a state ``S`` [dk, dv] from zero and, per row t,
a query ``q_t`` and a key ``k_t`` [dk], a value ``v_t`` [dv], a log-decay
``g_t <= 0`` and a write strength ``beta_t``:

    S   <- exp(g_t) S          one g_t a head (Gated DeltaNet), or
    S   <- Diag(exp g_t) S     a [dk] vector g_t: one decay a key CHANNEL
                               (Kimi Delta Attention)
    u_t  = (v_t - S^T k_t) beta_t
    S   <- S + k_t u_t^T
    o_t  = S^T q_t

:func:`gated_delta_rule` computes it a chunk of C rows at a time (the WY /
UT-transform form). Inside a chunk, with ``gam`` the running sum of ``g``:

    A   = tril(beta_i (k_i . k_j) exp(gam_i - gam_j), -1)      [C, C]
    T   = (I + A)^-1
    W   = T (beta e^gam K)        U  = T (beta V)
    Aqk = tril((q_i . k_j) exp(gam_i - gam_j))                 [C, C]

and across chunks the state is carried:

    U'  = U - W S
    O   = (Q e^gam) S + Aqk U'
    S  <- e^{gam_C} S + (K e^{gam_C - gam})^T U'

``gam``, every ``exp`` of a difference of it, ``T`` and ``S`` are float32
whatever the activations' dtype (float32 products in three bf16 passes,
``_HI``); the other matmul operands are the activations' dtype with
float32 accumulation. Only decays of the past are formed
(``exp(gam_i - gam_j)`` for ``i >= j``), so nothing overflows however fast
a head forgets.

Two stages, and a backward of its own (``jax.custom_vjp``):

* the chunk-local stage (A, the solve, W, U, Aqk for every chunk). On the
  ``"pallas"`` backend, where the chunk length packs into tiles of 128 rows
  (:func:`_local_kernels`; gauge ``hvd_gdn_local_kernel{layer}``), it is
  the kernels ``gdn_local_fwd`` / ``gdn_local_bwd``
  (``ops/pallas_gated_delta.py``): a tile's arrays stay in VMEM from A to
  W, U and Aqk, a key head is read once for its value heads, and the
  backward is the stage's transpose written out. Elsewhere it is plain
  ``jax.numpy`` (``_a_matrix``, ``_unit_lower_inverse``, ``_prepare``:
  batched einsums, a group of heads at a time) and its backward jax's own
  transpose of that, which is also what the tests hold the kernels to.
  Either way the backward recomputes the stage from q, k, v, g, beta, but
  for the solve: T is saved and its transpose is ``dA = -T^T dT T^T``, two
  products in place of the transposes of the ten that made it;
* the state's walk over the chunks is ``lax.scan`` (backend ``"xla"``: runs
  anywhere) or the Pallas kernels ``gdn_fwd`` / ``gdn_bwd`` (backend
  ``"pallas"``: the state stays in VMEM between chunks). Its backward walks
  the chunks in reverse carrying ``dS``, from the chunk-start states the
  forward saved: every chunk's, in the activations' dtype, which is how the
  backward's products take them (32 KB a chunk and head at 128 x 128 in
  bf16; the CARRIED state is float32 throughout).

What the custom VJP keeps for the backward is q, k, v, g, beta, those
states and T (:func:`saved_bytes`; gauge ``hvd_gdn_saved_state_bytes{layer}``
or ``hvd_kda_saved_state_bytes{layer}``, stamped by the layer that calls the
rule, and ``hvd_gdn_chunk``).

**The gate per channel** (g [B, T, H, dk]; the section "a decay per
channel" below). The decay then sits INSIDE the contractions,

    A_ij   = beta_i sum_c k_ic k_jc exp(gam_ic - gam_jc)       (i > j)
    Aqk_ij =        sum_c q_ic k_jc exp(gam_ic - gam_jc)       (i >= j)
    W = T (beta K e^gam),   O = (Q e^gam) S + Aqk U',
    S <- Diag(e^{gam_C}) S + (K e^{gam_C - gam})^T U'

and ``(K e^gam)(K e^-gam)^T`` would overflow for a channel that forgets
fast. The pairs (i, j) of a chunk are split by the highest bit in which i
and j differ: at level l the rows whose bit l is set meet the rows of
their block's other half, against the reference row r where that half
starts, ``exp(gam_i - gam_r) exp(gam_r - gam_j)``, both exponents <= 0 and
both sums of g over a stretch of rows (a 0/1 matrix times g, to float32's
digits). log2(C) products of the chunk's shape make A, as many Aqk, and as
many each of the four sums the transpose needs; ``e^-gam`` is never
formed. The same code (``ops/kda_tile.py``) is the ``"xla"`` backend
(mapped over the chunks) and the body of the kernels ``kda_local_fwd`` /
``kda_local_bwd``; the walk's kernels ``kda_fwd`` / ``kda_bwd`` are
``gdn_fwd`` / ``gdn_bwd`` with a [dk] vector where those have a number;
the backward is written out (no transpose by jax) and returns dg
[B, T, H, dk].
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.registry import registry as _registry
from . import kda_tile

# Float32 products (the solve, W and U, the solve's transpose): three bf16
# passes. Measured on a v5e at the Qwen3-Next cell's shape (PERF.md PR 32):
# six passes (HIGHEST) cost 10 ms a step more and the float32 reference
# read the same error for both (o off by 0.94% of its size in the middle
# layer, bf16's rounding of the operands, to three digits).
_HI = lax.Precision.HIGH


def _mm(spec: str, a, b, precision=None):
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32,
                      precision=precision)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` [..., C, C],
    float32: with n = -a nilpotent, the sum of its powers as the product
    of (I + n^(2^j)), log2(C) squarings."""
    C = a.shape[-1]
    n = -a
    inv = jnp.eye(C, dtype=a.dtype) + n
    for _ in range(max(0, math.ceil(math.log2(C)) - 1)):
        n = _mm("...ij,...jk->...ik", n, n, _HI)
        inv = inv + _mm("...ij,...jk->...ik", n, inv, _HI)
    return inv


def _decays(g):
    """g [..., C] -> (gam, its running sum; exp(gam_i - gam_j) for
    i >= j, 0 above the diagonal), float32."""
    C = g.shape[-1]
    gam = jnp.cumsum(g.astype(jnp.float32), axis=-1)
    row = jnp.arange(C)
    past = row[:, None] >= row[None, :]
    return gam, jnp.exp(jnp.where(
        past, gam[..., :, None] - gam[..., None, :], -jnp.inf))


def _a_matrix(k, g, beta):
    """A = tril(beta_i (k_i . k_j) exp(gam_i - gam_j), -1), float32."""
    row = jnp.arange(k.shape[-2])
    _, decay = _decays(g)
    return jnp.where(row[:, None] > row[None, :],
                     beta.astype(jnp.float32)[..., :, None]
                     * _mm("...id,...jd->...ij", k, k) * decay, 0.0)


def _prepare(t, q, k, v, g, beta):
    """The chunk-local stage after the solve. t = (I + A)^-1 [B, H, N, C, C]
    float32, q, k [B, H, N, C, dk], v [B, H, N, C, dv], g, beta
    [B, H, N, C] float32 -> (Q e^gam, K e^{gam_C - gam}, W, U, Aqk in the
    activations' dtype; e^{gam_C} [B, H, N] float32)."""
    dt, f32 = q.dtype, jnp.float32
    gam, decay = _decays(g)
    beta = beta.astype(f32)
    e_gam = jnp.exp(gam)
    w = _mm("...ij,...jd->...id", t,
            k.astype(f32) * (beta * e_gam)[..., None], _HI)
    u = _mm("...ij,...jd->...id", t, v.astype(f32) * beta[..., None], _HI)
    aqk = _mm("...id,...jd->...ij", q, k) * decay
    last = gam[..., -1:]
    qg = q.astype(f32) * e_gam[..., None]
    kd = k.astype(f32) * jnp.exp(last - gam)[..., None]
    return (qg.astype(dt), kd.astype(dt), w.astype(dt), u.astype(dt),
            aqk.astype(dt), jnp.exp(last[..., 0]))


def _over_state(e):
    """e^{gam_C} of a chunk, [B, H] (a number a head) or [B, H, dk] (one a
    key channel), as it multiplies the state [B, H, dk, dv]."""
    return e[..., None, None] if e.ndim == 2 else e[..., None]


def _chunks_first(*xs):
    return [jnp.moveaxis(x, 2, 0) for x in xs]


def scan_fwd_xla(qg, kd, w, u, aqk, e_last):
    """The state's walk, forward: (o [B, H, N, C, dv] and the chunk-start
    states [B, H, N, dk, dv], both in u's dtype: the state is carried in
    float32 and saved as the matmul operand the backward takes it as)."""
    dt = u.dtype
    B, H, _, _, dk = qg.shape
    dv = u.shape[-1]

    def chunk(S, xs):
        qg_n, kd_n, w_n, u_n, a_n, e_n = xs
        s = S.astype(dt)
        un = (u_n - _mm("bhck,bhkv->bhcv", w_n, s)).astype(dt)
        o = _mm("bhck,bhkv->bhcv", qg_n, s) + _mm("bhij,bhjv->bhiv", a_n, un)
        nxt = _over_state(e_n) * S + _mm("bhck,bhcv->bhkv", kd_n, un)
        return nxt, (o.astype(dt), s)

    _, (o, states) = lax.scan(
        chunk, jnp.zeros((B, H, dk, dv), jnp.float32),
        tuple(_chunks_first(qg, kd, w, u, aqk, e_last)))
    return jnp.moveaxis(o, 0, 2), jnp.moveaxis(states, 0, 2)


def scan_bwd_xla(qg, kd, w, u, aqk, e_last, states, do):
    """The state's walk, backward: the cotangents of qg, kd, w, u, aqk (in
    their dtype) and of e_last (float32), from ``do`` and the chunk-start
    states, carrying dS from the last chunk to the first."""
    dt = u.dtype
    B, H, _, _, dk = qg.shape
    dv = u.shape[-1]

    def chunk(dS, xs):
        qg_n, kd_n, w_n, u_n, a_n, e_n, S, do_n = xs
        s, ds = S, dS.astype(dt)
        un = (u_n - _mm("bhck,bhkv->bhcv", w_n, s)).astype(dt)
        dun = (_mm("bhij,bhiv->bhjv", a_n, do_n)
               + _mm("bhck,bhkv->bhcv", kd_n, ds))
        dund = dun.astype(dt)
        out = (_mm("bhcv,bhkv->bhck", do_n, s),            # d qg
               _mm("bhcv,bhkv->bhck", un, ds),             # d kd
               -_mm("bhcv,bhkv->bhck", dund, s),           # d w
               dun,                                        # d u
               _mm("bhiv,bhjv->bhij", do_n, un),           # d aqk
               jnp.sum(S.astype(jnp.float32) * dS,                 # d e
                       axis=(-2, -1) if e_n.ndim == 2 else -1))
        nxt = (_over_state(e_n) * dS
               + _mm("bhck,bhcv->bhkv", qg_n, do_n)
               - _mm("bhck,bhcv->bhkv", w_n, dund))
        return nxt, out

    _, grads = lax.scan(
        chunk, jnp.zeros((B, H, dk, dv), jnp.float32),
        tuple(_chunks_first(qg, kd, w, u, aqk, e_last, states, do)),
        reverse=True)
    grads = [jnp.moveaxis(x, 0, 2) for x in grads]
    return tuple(x.astype(dt) for x in grads[:5]) + (grads[5],)


def _scans(backend: str):
    if backend == "xla":
        return scan_fwd_xla, scan_bwd_xla
    from . import pallas_gated_delta as pgd
    return pgd.scan_fwd, pgd.scan_bwd


# Rows x heads worked at once. The chunk-local stage and its transpose hold
# a score of float32 [C, C] and [C, d] arrays a chunk and head (5.5 GB at
# 2 x 8192 rows and 32 heads of 128, compiled for a v5e): the key heads are
# taken in groups of at most this many rows x value heads, one after the
# other.
_GROUP_ROWS = 1 << 16


def _by_head_groups(fn, *xs):
    """``fn`` of arrays [B, Hk, ...] (the same of its outputs), a group of
    key heads at a time; ``xs[2]`` is v [B, Hk, rep, N, C, dv]."""
    B, Hk, rep, N, C = xs[2].shape[:5]
    hb = next(h for h in range(Hk, 0, -1) if Hk % h == 0 and (
        h == 1 or B * N * C * h * rep <= _GROUP_ROWS))
    if hb == Hk:
        return fn(*xs)
    out = lax.map(lambda group: fn(*group), tuple(
        jnp.moveaxis(x.reshape(B, Hk // hb, hb, *x.shape[2:]), 1, 0)
        for x in xs))
    return jax.tree_util.tree_map(
        lambda y: jnp.moveaxis(y, 0, 1).reshape(B, Hk, *y.shape[3:]), out)


def _flat_heads(x):
    """[B, Hk, rep, ...] -> [B, Hk * rep, ...]."""
    return x.reshape(x.shape[0], -1, *x.shape[3:])


def _value_heads(x, rep: int):
    """[B, Hk * rep, ...] -> [B, Hk, rep, ...]."""
    return x.reshape(x.shape[0], x.shape[1] // rep, rep, *x.shape[2:])


def _per_value_head(q, k, v, g, beta):
    """q, k [B, Hk, N, C, dk] and v, g, beta [B, Hk, rep, N, C, ...] as
    arrays [B, Hk * rep, ...]: each key head serves its ``rep`` consecutive
    value heads."""
    rep = v.shape[2]
    return (jnp.repeat(q, rep, axis=1), jnp.repeat(k, rep, axis=1),
            _flat_heads(v), _flat_heads(g), _flat_heads(beta))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, g, beta, backend, local):
    return _rule_fwd(q, k, v, g, beta, backend, local)[0]


def _rule_fwd(q, k, v, g, beta, backend, local):
    """``local``: the chunk-local stage by the kernels ``gdn_local_fwd`` /
    ``gdn_local_bwd`` (:func:`_local_kernels`), which read a key head once
    for its value heads and hold no float32 transient in HBM: no
    ``_per_value_head``, no ``_by_head_groups``."""
    if local:
        from . import pallas_gated_delta as pgd
        *prepared, t = pgd.local_fwd(q, k, v, g, beta)
        o, states = pgd.scan_fwd(*prepared)
        rep = v.shape[2]
        return _value_heads(o, rep), (q, k, v, g, beta,
                                      _value_heads(states, rep), t)

    def group(*inputs):
        q, k, v, g, beta = _per_value_head(*inputs)
        t = _unit_lower_inverse(_a_matrix(k, g, beta))
        o, states = _scans(backend)[0](*_prepare(t, q, k, v, g, beta))
        # T leaves flattened to [.., C * C]: rows of 64 float32 would be
        # padded to the 128 lanes of a tile.
        return tuple(_value_heads(x, inputs[2].shape[2]) for x in (
            o, states, t.reshape(*t.shape[:-2], -1)))
    o, states, t = _by_head_groups(group, q, k, v, g, beta)
    return o, (q, k, v, g, beta, states, t)


def _rule_bwd(backend, local, res, do):
    """From the saved solve t = (I + A)^-1: the rest of the chunk-local
    stage is recomputed (by the kernel again, or by ``_prepare``), the
    walk goes back over the saved states, and the stage is transposed: by
    ``gdn_local_bwd``, or by jax with the solve's own transpose
    dA = -t^T dt t^T."""
    do = do.astype(res[0].dtype)
    if local:
        from . import pallas_gated_delta as pgd
        *inputs, states, t = res
        return pgd.local_bwd(*inputs, t, *pgd.scan_bwd(
            *pgd.local_fwd(*inputs, t), _flat_heads(states),
            _flat_heads(do)))

    def group(q, k, v, g, beta, states, t, do):
        heads, to_key_heads = jax.vjp(_per_value_head, q, k, v, g, beta)
        C = k.shape[-2]
        t = _flat_heads(t).reshape(*heads[0].shape[:3], C, C)
        prepared, transpose = jax.vjp(_prepare, t, *heads)
        dt, *d_heads = transpose(_scans(backend)[1](
            *prepared, _flat_heads(states), _flat_heads(do)))
        da = -_mm("...ji,...jk->...ik", t,
                  _mm("...ij,...kj->...ik", dt, t, _HI), _HI)
        dk, dg, dbeta = jax.vjp(_a_matrix, *heads[1:2], *heads[3:])[1](da)
        d_heads[1] += dk
        d_heads[3] += dg
        d_heads[4] += dbeta
        return to_key_heads(tuple(d_heads))
    return _by_head_groups(group, *res, do)


_rule.defvjp(_rule_fwd, _rule_bwd)

# -- a decay per channel ---------------------------------------------------------
#
# The chunk-local stage is ``kda_tile``'s, a tile of one chunk at a time
# (mapped over the chunks) with the products as XLA takes them, or the
# kernels ``kda_local_fwd`` / ``kda_local_bwd`` with the same tile functions
# as their body.


class _XlaOps:
    """The products as XLA takes them."""

    @staticmethod
    def dot(a, b, ca, cb, precision=None):
        return lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)

    @classmethod
    def dot_hi(cls, a, b, ca, cb):
        return cls.dot(a, b, ca, cb, _HI)

    @classmethod
    def dot_sum(cls, mask, x, ca=1):
        return cls.dot(mask.astype(jnp.float32), x, ca, 0,
                       lax.Precision.HIGHEST)


def _over_chunks_xla(fn, *xs):
    """``fn`` of one chunk's arrays, over [B, H, N, ...]."""
    for _ in range(3):
        fn = jax.vmap(fn)
    return fn(*xs)


def _channel_fwd_xla(q, k, v, g, beta, t=None):
    """The stage for q, k, g [B, H, N, C, dk], v [B, H, N, C, dv], beta
    [B, H, N, C]: (qg, kd, w, u, aqk in q's dtype, e^{gam_C} [B, H, N, dk]
    float32, T [B, H, N, C, C] float32)."""
    C = q.shape[-2]
    fn = functools.partial(kda_tile.tile_fwd, _XlaOps, C, 1)
    args = (q, k, v, g, beta[..., None]) + (() if t is None else (t,))
    *prepared, e, t = _over_chunks_xla(fn, *args)
    return (*(x.astype(q.dtype) for x in prepared), e[..., 0, :], t)


def _channel_bwd_xla(q, k, v, g, beta, t, dqg, dkd, dw, du, daqk, de):
    C = q.shape[-2]
    *grads, dbeta = _over_chunks_xla(
        functools.partial(kda_tile.tile_bwd, _XlaOps, C, 1), q, k, v, g,
        beta[..., None], t, dqg, dkd, dw, du, daqk, de[..., None, :])
    return (*grads, dbeta[..., 0])


def _channel_stage(backend: str, chunk: int, dtype):
    """(the stage, its transpose, whether they are the kernels
    ``kda_local_fwd`` / ``kda_local_bwd``) for a backend."""
    if _local_kernels(backend, chunk, dtype):
        from . import pallas_gated_delta as pgd
        return pgd.kda_local_fwd, pgd.kda_local_bwd, True
    return _channel_fwd_xla, _channel_bwd_xla, False


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _channel_rule(q, k, v, g, beta, backend):
    return _channel_rule_fwd(q, k, v, g, beta, backend)[0]


def _channel_rule_fwd(q, k, v, g, beta, backend):
    """q, k, g [B, H, N, C, dk], v [B, H, N, C, dv], beta [B, H, N, C]."""
    stage = _channel_stage(backend, q.shape[-2], q.dtype)[0]
    *prepared, t = stage(q, k, v, g, beta)
    o, states = _scans(backend)[0](*prepared)
    return o, (q, k, v, g, beta, states, t)


def _channel_rule_bwd(backend, res, do):
    """As ``_rule_bwd``: the stage again from the saved solve, the walk
    back over the saved states, the stage's transpose."""
    *inputs, states, t = res
    stage, transpose, _ = _channel_stage(backend, inputs[0].shape[-2],
                                         inputs[0].dtype)
    dq, dk, dv, dg, dbeta = transpose(*inputs, t, *_scans(backend)[1](
        *stage(*inputs, t)[:6], states, do.astype(inputs[0].dtype)))
    dt = inputs[0].dtype
    return (dq.astype(dt), dk.astype(dt), dv.astype(dt),
            dg.astype(jnp.float32), dbeta.astype(jnp.float32))


_channel_rule.defvjp(_channel_rule_fwd, _channel_rule_bwd)


def resolve_backend(backend: str) -> str:
    """``"auto"``: the Pallas kernels on a TPU, the XLA scan elsewhere."""
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend not in ("xla", "pallas"):
        raise ValueError(f"gated_delta_rule: backend {backend!r} is not "
                         f"'xla', 'pallas' or 'auto'")
    return backend


def _local_kernels(backend: str, chunk: int, dtype) -> bool:
    """Whether the chunk-local stage runs as the kernels: the Pallas
    backend, chunks the kernels can tile, activations they take."""
    if backend != "pallas":
        return False
    from . import pallas_gated_delta as pgd
    return (pgd.local_tile(1, chunk) is not None
            and dtype in (jnp.float32, jnp.bfloat16))


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64,
                     backend: str = "auto", layer=None):
    """The rule above for q, k [B, T, Hk, dk] (normalised and scaled by the
    caller), v [B, T, Hv, dv], g (log-decay, <= 0) [B, T, Hv] (one a head)
    or [B, T, Hv, dk] (one a key channel; then Hk = Hv: the decays differ
    by value head) and beta [B, T, Hv]; each of the Hk key heads serves
    Hv / Hk consecutive value heads.
    Returns o [B, T, Hv, dv] in v's dtype. T is padded to a multiple of
    ``chunk`` with rows that write nothing (k = 0, beta = 0, g = 0) and
    whose outputs are dropped. ``backend``: ``"xla"``, ``"pallas"`` (on a
    CPU: the kernels in interpret mode) or ``"auto"``. ``layer``: the
    calling layer's index, for the gauge ``hvd_gdn_local_kernel{layer}``."""
    backend = resolve_backend(backend)
    per_channel = g.ndim == 4
    if per_channel and (chunk & (chunk - 1) or g.shape[2:] != q.shape[2:]
                        or v.shape[2] != q.shape[2]):
        raise ValueError(
            f"gated_delta_rule: a gate per channel needs a chunk that is a "
            f"power of two, a key head a value head and g of q's shape; got "
            f"chunk {chunk}, g {g.shape} and v {v.shape} for q {q.shape}")
    local = _channel_stage(backend, chunk, q.dtype)[2] if per_channel \
        else _local_kernels(backend, chunk, q.dtype)
    if layer is not None:
        _m_local.labels(layer=str(layer)).set(int(local))
    B, T, Hk, _ = q.shape
    Hv = v.shape[2]
    if Hv % Hk:
        raise ValueError(f"gated_delta_rule: {Hv} value heads do not "
                         f"divide over {Hk} key heads")
    pad = -T % chunk
    n = (T + pad) // chunk

    def chunked(x, heads):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x, 2, 1)                     # [B, H, T, ...]
        return x.reshape(B, *heads, n, chunk, *x.shape[3:])
    per_key = (Hk, Hv // Hk)
    if per_channel:
        o = _channel_rule(
            chunked(q, (Hv,)), chunked(k.astype(q.dtype), (Hv,)),
            chunked(v.astype(q.dtype), (Hv,)),
            chunked(g.astype(jnp.float32), (Hv,)),
            chunked(beta.astype(jnp.float32), (Hv,)), backend)
    else:
        o = _rule(chunked(q, (Hk,)), chunked(k.astype(q.dtype), (Hk,)),
                  chunked(v.astype(q.dtype), per_key),
                  chunked(g.astype(jnp.float32), per_key),
                  chunked(beta.astype(jnp.float32), per_key), backend, local)
    o = jnp.moveaxis(o.reshape(B, Hv, n * chunk, -1), 1, 2)
    return o[:, :T].astype(v.dtype)


def saved_bytes(q_shape, n_v_heads: int, dv: int, itemsize: int,
                chunk: int = 64, per_channel: bool = False) -> int:
    """Bytes the rule's custom VJP keeps for the backward of one call
    (what ``_rule_fwd`` returns beside o), from shapes: q, k ([B, T, Hk,
    dk]), v and a [dk, dv] chunk-start state for every chunk and value head
    in the activations' dtype; g, beta and the solve T, ``chunk`` float32 a
    row and value head (T leaves flattened [.., chunk * chunk], or from
    the kernels packed [.., chunk, 128]: full lanes, the same bytes).
    ``per_channel``: g is dk float32 a row and head (a key head a value
    head)."""
    B, T, Hk, dk = q_shape
    rows = B * (T + -T % chunk)
    if per_channel:
        return (rows * n_v_heads * (2 * dk + dv) * itemsize
                + rows * n_v_heads * (dk + 1 + chunk) * 4
                + rows // chunk * n_v_heads * dk * dv * itemsize)
    return (rows * (2 * Hk * dk + n_v_heads * dv) * itemsize
            + rows * n_v_heads * (2 + chunk) * 4
            + rows // chunk * n_v_heads * dk * dv * itemsize)


_m_saved = _registry().gauge(
    "hvd_gdn_saved_state_bytes",
    "bytes the gated delta rule's custom VJP keeps for the backward of one "
    "layer's call (inputs and chunk-start states), from shapes at trace "
    "time", labels=("layer",))
_m_saved_kda = _registry().gauge(
    "hvd_kda_saved_state_bytes",
    "as hvd_gdn_saved_state_bytes, for a layer whose gate is per channel "
    "(Kimi Delta Attention): g counts dk float32 a row and head",
    labels=("layer",))
_m_local = _registry().gauge(
    "hvd_gdn_local_kernel",
    "1 where the layer's chunk-local stage was traced as the kernels "
    "gdn_local_fwd / gdn_local_bwd (kda_local_fwd / kda_local_bwd under a "
    "gate per channel), 0 where its shapes sent it to jax.numpy",
    labels=("layer",))
_m_chunk = _registry().gauge(
    "hvd_gdn_chunk", "chunk length the gated delta rule runs with, as last "
    "traced")


def record_saved(layer: int, q_shape, n_v_heads: int, dv: int,
                 itemsize: int, chunk: int, per_channel: bool = False) -> None:
    """Stamp the two gauges for one layer's call (trace time: shapes)."""
    (_m_saved_kda if per_channel else _m_saved).labels(layer=str(layer)).set(
        saved_bytes(q_shape, n_v_heads, dv, itemsize, chunk, per_channel))
    _m_chunk.set(chunk)
