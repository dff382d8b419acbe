"""Attention over a set of keys selected per query row by a learned indexer
(DeepSeek-V3.2's sparse attention, "lightning indexer"), with grouped
key/value heads.

For one sequence, query row t and key row s <= t:

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])       the index score
    S_t     = the min(topk, t+1) rows s <= t of largest I[t, s];
              equal scores go to the smaller s. Exact: no approximate top-k.
    P[n, t, s] = softmax over S_t of q[t, n] . k[s, n // group] / sqrt(d)
    o[t, n]    = sum over S_t of P[n, t, s] v[s, n // group]
    KL_t = KL( sg(mean_n P[n, t, .]) || softmax over S_t of I[t, .] )

The selection is shared by all heads and is carried as an int8 ``[B, T, T]``
mask (1 = selected) from the forward to the backward pass. Top-k passes no
gradient: q, k, v learn from ``o`` only, and the indexer's inputs (qI, kI,
w) from the KL only, whose gradient with respect to I is
``softmax(I) - mean_n P`` on S_t.

Four phases, each under a named scope that a device trace splits the step
by (they are API, like the kernels' names): ``attn.indexer`` (I),
``attn.select`` (S), ``attn.sparse`` (o) and ``attn.indexer_loss`` (KL).
The XLA forms below work a block of query rows at a time, so that no
``[heads, T, T]`` tensor exists; on a TPU ``attn.sparse`` runs the Pallas
kernels ``dsa_fwd`` and ``dsa_bwd`` (dq, dk and dv from one visit of each
tile pair; beyond the sequence lengths whose K, V and dk/dv fit the
kernel's VMEM, the split ``dsa_bwd_dq`` and ``dsa_bwd_dkv``:
``pallas_sparse_attention._bwd_plan``) and ``attn.indexer_loss`` the
kernel ``dsa_kl`` (``pallas_call(name=)``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# Query rows worked at once by the XLA forms: [heads, 256, T] float32
# scores are 0.27 GB at 32 heads and T 8192.
_ROWS = 256


def _row_blocks(fn, T: int, *args):
    """``fn(r0, *blocks)`` over blocks of ``_ROWS`` query rows of
    ``args`` ([B, T, ...] each), outputs concatenated back along T."""
    R = min(_ROWS, T)
    if T % R:
        raise ValueError(f"sequence length {T} is not a multiple of {R}")
    nb = T // R
    blocked = [a.reshape(a.shape[0], nb, R, *a.shape[2:]).swapaxes(0, 1)
               for a in args]
    out = lax.map(lambda xs: fn(xs[0] * R, *xs[1:]),
                  (jnp.arange(nb), *blocked))
    return jax.tree_util.tree_map(
        lambda o: o.swapaxes(0, 1).reshape(o.shape[1], T, *o.shape[3:]), out)


def _causal(r0, R: int, T: int):
    return jnp.arange(T)[None, :] <= (r0 + jnp.arange(R))[:, None]


def _index_block(qi, ki, w):
    """qi [B, R, Hi, di], ki [B, T, di], w [B, R, Hi] -> I [B, R, T] f32."""
    s = jnp.einsum("brjd,bsd->brjs", qi, ki,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("brj,brjs->brs", w.astype(jnp.float32),
                      jax.nn.relu(s))


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7fffffff), bits)
    return lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)


def _select_block(r0, scores, topk: int):
    """scores [B, R, T] (any value where s > t) -> (int8 [B, R, T]: the
    min(topk, t+1) largest causal scores of each row, ties to the smaller
    s; float32 [B, R]: the log-sum-exp of the selected scores). The k-th
    largest is found exactly without a sort, by bisection on the bits of
    the float (32 compare-and-count passes)."""
    B, R, T = scores.shape
    causal = _causal(r0, R, T)[None]
    key = jnp.where(causal, _ordered_bits(scores), jnp.uint32(0))
    want = jnp.minimum(topk, r0 + jnp.arange(R) + 1)[None, :, None]

    def bit(i, kth):
        cand = kth | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = jnp.sum(key >= cand, axis=-1, keepdims=True) >= want
        return jnp.where(enough, cand, kth)
    kth = lax.fori_loop(0, 32, bit, jnp.zeros((B, R, 1), jnp.uint32))
    above = key > kth
    tied = (key == kth) & causal
    room = want - jnp.sum(above, axis=-1, keepdims=True)
    sel = above | (tied & (jnp.cumsum(tied, axis=-1) <= room))
    return sel.astype(jnp.int8), jax.nn.logsumexp(
        jnp.where(sel, scores, -1e30), axis=-1)


def select_topk(qi, ki, w, topk: int):
    """The selection as an int8 mask [B, T, T] (1 = s in S_t), and the
    log-sum-exp [B, T] of each row's index scores over it."""
    T = qi.shape[1]

    def block(r0, qi_b, w_b):
        with jax.named_scope("attn.indexer"):
            scores = _index_block(qi_b, ki, w_b)
        with jax.named_scope("attn.select"):
            return _select_block(r0, scores, topk)
    return _row_blocks(block, T, qi, w)


def _scores_block(q, k):
    """q [B, R, Hq, d], k [B, T, Hkv, d] -> [B, Hkv, G, R, T] f32 scaled."""
    B, R, Hq, d = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, R, Hkv, Hq // Hkv, d)
    return jnp.einsum("brhgd,bshd->bhgrs", qg, k,
                      preferred_element_type=jnp.float32) * d ** -0.5


def _attend_xla(q, k, v, mask):
    """(o [B, T, Hq, d], lse [B, T, Hq] float32) by blocks of query rows;
    differentiable by autodiff, each block recomputed in the backward."""
    B, T, Hq, d = q.shape

    @jax.checkpoint
    def block(r0, q_b, m_b):
        s = jnp.where(m_b[:, None, None] != 0, _scores_block(q_b, k), -1e30)
        lse = jax.nn.logsumexp(s, axis=-1)                 # [B, Hkv, G, R]
        p = jnp.exp(s - lse[..., None]).astype(v.dtype)
        o = jnp.einsum("bhgrs,bshd->brhgd", p, v,
                       preferred_element_type=jnp.float32)
        return (o.reshape(B, -1, Hq, d).astype(q.dtype),
                lse.transpose(0, 3, 1, 2).reshape(B, -1, Hq))
    return _row_blocks(block, T, q, mask)


def _kl_xla(q, k, lse, qi, ki, w, mask):
    """KL_t [B, T] float32 by blocks of query rows, differentiable in qi,
    ki and w by autodiff."""
    B, T, Hq, d = q.shape

    @jax.checkpoint
    def block(r0, q_b, lse_b, qi_b, w_b, m_b):
        sel = m_b != 0
        s = _scores_block(q_b, k)                          # [B, Hkv, G, R, T]
        lse_g = lse_b.reshape(B, -1, s.shape[1], s.shape[2])
        p = jnp.exp(s - lse_g.transpose(0, 2, 3, 1)[..., None])
        p_mean = jnp.where(sel, jnp.sum(p, axis=(1, 2)) / Hq, 0.0)
        index = jnp.where(sel, _index_block(qi_b, ki, w_b), -1e30)
        log_pi = index - jax.nn.logsumexp(index, axis=-1, keepdims=True)
        log_p = jnp.log(jnp.where(p_mean > 0, p_mean, 1.0))
        return jnp.sum(p_mean * (log_p - jnp.where(sel, log_pi, 0.0)), -1)
    return _row_blocks(block, T, q, lse, qi, w, mask)


def indexer_kl(q, k, lse, qi, ki, w, lse_i, mask, *, backend: str = "pallas"):
    """(sum_t KL_t [B], KL_t [B, T]) float32. The SUM over a sequence's
    rows is the differentiable quantity, in qi, ki and w; q, k and lse are
    constants (the head-mean of P is recomputed from them tile by tile)
    and the rows come back behind a stop-gradient, for counting. On a TPU
    one kernel, ``dsa_kl``, gives the rows and the sum's gradient in one
    pass over the tiles."""
    q, k, lse = map(lax.stop_gradient, (q, k, lse))
    with jax.named_scope("attn.indexer_loss"):
        if backend == "pallas":
            from . import pallas_sparse_attention as kernels
            if kernels.tilable(q.shape[1], q.shape[3]):
                total, rows = kernels.indexer_kl(
                    q, k, lse, qi, ki, w, lse_i, jnp.ones_like(lse_i), mask)
                return total, lax.stop_gradient(rows)
        rows = _kl_xla(q, k, lse, qi, ki, w, mask)
        return jnp.sum(rows, axis=-1), lax.stop_gradient(rows)


def sparse_attention(q, k, v, mask, *, backend: str = "pallas"):
    """o [B, T, Hq, d] and the log-sum-exp [B, T, Hq] of each row's scores
    over its selected keys (for :func:`indexer_kl`). ``backend="pallas"``
    runs the kernels where the shapes tile, XLA otherwise."""
    with jax.named_scope("attn.sparse"):
        if backend == "pallas":
            from . import pallas_sparse_attention as kernels
            if kernels.tilable(q.shape[1], q.shape[3]):
                return kernels.attend(q, k, v, mask)
        return _attend_xla(q, k, v, mask)


def dsa_attention(q, k, v, qi, ki, w, *, topk: int, backend: str = "pallas"):
    """The whole sparse-attention block. q [B, T, Hq, d]; k, v
    [B, T, Hkv, d]; indexer queries qi [B, T, Hi, di], its one key head ki
    [B, T, di] and head weights w [B, T, Hi] (callers stop the gradient
    into them from everything but the KL). Returns (o [B, T, Hq, d],
    sum_t KL_t [B] (differentiable), KL_t [B, T] (not), mask int8
    [B, T, T])."""
    mask, lse_i = select_topk(*map(lax.stop_gradient, (qi, ki, w)), topk)
    o, lse = sparse_attention(q, k, v, mask, backend=backend)
    kl_sum, kl_rows = indexer_kl(q, k, lse, qi, ki, w, lse_i, mask,
                                 backend=backend)
    return o, kl_sum, kl_rows, mask


def _pair_gauges():
    from ..obs.registry import registry
    return (registry().gauge(
        "hvd_dsa_selected_pairs",
        "(query row, key) pairs the indexer selected, by layer, as last "
        "recorded", labels=("layer",)),
        registry().gauge(
        "hvd_dsa_causal_pairs",
        "(query row, key) pairs with key <= row among which it selected, "
        "by layer, as last recorded", labels=("layer",)))


def record_selection(layer: int, selected: int, causal: int) -> None:
    """Stamp one layer's count of selected pairs beside the causal pairs
    they were chosen from (host values, off the dispatch path: summed from
    a forward's masks after it completed)."""
    chosen, among = _pair_gauges()
    chosen.labels(layer=str(layer)).set(float(selected))
    among.labels(layer=str(layer)).set(float(causal))
