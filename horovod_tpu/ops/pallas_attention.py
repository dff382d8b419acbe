"""Pallas TPU flash attention — the hot-op kernel for the transformer path.

Blockwise attention computed entirely in VMEM with an online softmax
(running max/sum), so the [T, T] score matrix never touches HBM: per grid
step a [b, D] query tile meets K/V tiles with MXU matmuls (f32
accumulation). Differentiable end to end: a custom VJP recomputes the
probability blocks from (q, k, lse) inside the backward kernels, so the
backward pass never materializes scores either. Used by the parallel
transformer's single-shard attention path (``parallel/transformer.py``);
the sequence-parallel path (:func:`horovod_tpu.parallel.ring.ring_attention`)
keeps its own blockwise accumulation across chips.

Causal calls pay for no masked score beyond the diagonal's own strips: no
grid step, no DMA and no arithmetic for a tile pair above the diagonal,
and of a pair on it only the row strips up to the diagonal
(``_tile_schedule``). Which schedule runs, and the scoped VMEM its kernel
asks for, is chosen from the shape by ONE gate (``_plan``): K/V whole in
VMEM with an in-kernel loop over k tiles where that fits, K/V tiles
streamed over a grid axis clamped at the diagonal where it does not, and
the fused or the split backward. The gate has two rungs: the compiler's
default 16 MiB, and only where that refuses the whole-sequence residents
the 64 MiB every other Pallas kernel here asks for (T 8192 at a width of
256); ``hvd_flash_bwd_plan_total{plan=, grant=}`` counts the backward's
traces by both.

Off-TPU (CPU tests) the kernels run in interpreter mode, bit-matching the
compiled path's math. `flash_attention` falls back to plain XLA attention
for shapes the kernel doesn't tile (tiny head_dim or sequences not divisible
by the block) unless the caller forbids it (``fallback=False``: the
latent-attention layers). q and k may be of another width than v (192
against 128): the [BH, T, .] layout's kernels take the two widths apart.

Kernel names (``pallas_call(name=)``; a device trace and the compiled HLO
find the kernels by them, so they are API): ``flash_fwd``, ``flash_bwd``
(fused dq/dk/dv), and the split backward's ``flash_bwd_dq`` and
``flash_bwd_dkv`` — the same four for the packed-qkv and the BHTD layouts.

Speeds quoted in this file are of one machine and one day each: PERF.md
(PR 27) has this PR's kernel-alone table; older figures are history.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q = 128    # minimum tile (tilability floor)
BLOCK_K = 128
# Preferred (square) tile side: the knee of a v5e sweep over {256..2048}²
# of the kernels this file had before PR 27 (docs/benchmarks.md; history,
# that machine is gone). _pick_block shrinks it to fit short sequences.
_WANT_BLOCK = 512
# Height of the row strips a DIAGONAL tile pair is cut into. Measured on a
# v5e (PERF.md PR 27, kernels alone at B8 H16 T2048 D128, forward +
# backward): 256 -> 4.08 ms, 128 -> 4.16, 512 (the pair whole) -> 4.13.
_DIAG_SUB = 256


def _pick_block(t: int, want: int) -> int:
    """Largest power-of-two block <= ``want`` dividing ``t``. Bigger tiles
    amortize Mosaic's per-step overhead; 128 is the floor the tilability
    check guarantees."""
    b = want
    while b > 128 and t % b:
        b //= 2
    return b


def _blocks(T: int, window: Optional[int] = None):
    """(tile side, diagonal strip height) for sequence length T. A window
    caps the tile at its largest power of two, so that no score of a
    diagonal tile falls out of the band."""
    want = _WANT_BLOCK if window is None \
        else 1 << (min(window, _WANT_BLOCK).bit_length() - 1)
    b = _pick_block(T, want)
    return b, min(b, _DIAG_SUB)


def _grid_params(semantics, vmem_limit_bytes=None):
    """dimension_semantics lets Mosaic pipeline HBM tile copies against
    compute across grid steps. ``vmem_limit_bytes`` pins the kernel's
    scoped-VMEM limit (None = the compiler's default)."""
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=vmem_limit_bytes)


# ---------------------------------------------------------------------------
# The causal tile schedule. With square tiles of side b, q tile qi needs
# the k tiles kb < qi whole and unmasked ("plain" pairs), part of the pair
# kb == qi ("diagonal") and nothing of kb > qi. A plain pair is one [b, b]
# block. A diagonal pair is cut into row strips of height ``sub``; strip i
# is computed against the first (i+1)·sub columns only and masked by one
# static triangle — the rest of the pair is never built. No pair above
# the diagonal is fetched, computed or masked.
# ---------------------------------------------------------------------------


def _tile_schedule(b: int, sub: int, diagonal: bool):
    """The blocks of one [b, b] tile pair that are computed, as
    ``[(row0, rows, cols, masked), ...]``: rows [row0, row0+rows) against
    the pair's first ``cols`` columns."""
    if not diagonal:
        return [(0, b, b, False)]
    return [(i * sub, sub, (i + 1) * sub, True) for i in range(b // sub)]


# ---------------------------------------------------------------------------
# The banded (sliding-window) schedule. Query i sees key j iff
# i - window < j <= i. With tiles of side b <= window (``_blocks``), q tile
# qi meets the k tiles qi - d for d = 0 (the diagonal pair, cut as above),
# d = 1..plain (whole and unmasked) and d in ``edges`` (the band's left
# edge: one [b, b] block masked by a second static triangle, d·b + row -
# col < window). No tile further left is fetched, computed or masked.
# ---------------------------------------------------------------------------


def _band(b: int, window: int, n_t: int):
    """-> (last, plain, edges, window) of the band over ``n_t`` tiles: the
    largest distance d = qi - kb of a visited pair, the largest one whose
    pair is whole in the band, and the distances of the pairs the band's
    edge cuts. The kernels take it whole (static)."""
    last = min(-(-(window - 1) // b), n_t - 1)
    plain = min(window // b - 1, last)
    return last, plain, tuple(range(plain + 1, last + 1)), window


def _pair_blocks(b: int, sub: int, d: Optional[int], window=None):
    """The blocks of the pair at distance ``d`` (None: any pair under the
    diagonal that is whole) as ``[(row0, rows, cols, masked, band), ...]``:
    ``_tile_schedule``'s, and ``band`` = (shift, window) where the band's
    edge cuts the pair (keep d·b + row - col < window)."""
    if d is None or d == 0:
        return [s + (None,) for s in _tile_schedule(b, sub, d == 0)]
    return [(0, b, b, False, (d * b, window))]


def tile_pairs(T: int, window: Optional[int] = None):
    """(tile pairs the kernels visit, causal tile pairs at the same tile
    side) of one (batch, head) at length T, from the schedule itself."""
    b, _ = _blocks(T, window)
    n = T // b
    causal = n * (n + 1) // 2
    if window is None or window >= T:
        return causal, causal
    last = _band(b, window, n)[0]
    return sum(min(qi, last) + 1 for qi in range(n)), causal


def record_tile_pairs(layer, T: int, window: Optional[int]) -> None:
    """Stamp ``hvd_swa_tile_pairs{layer, kind}`` for one windowed layer's
    call (trace time: shapes): the tile pairs its kernels visit a (batch,
    head), and the causal kernels' at the same length."""
    from ..obs.registry import registry
    gauge = registry().gauge(
        "hvd_swa_tile_pairs",
        "tile pairs a windowed layer's flash kernels visit (kind=visited) "
        "and the causal kernels' (kind=causal), one (batch, head), from "
        "the schedule at trace time", labels=("layer", "kind"))
    visited, causal = tile_pairs(T, window)
    gauge.labels(layer=str(layer), kind="visited").set(visited)
    gauge.labels(layer=str(layer), kind="causal").set(causal)


def _dot(a, b, ca: int, cb: int):
    """MXU matmul contracting a's dim ``ca`` with b's ``cb`` in the input
    dtype (bf16 passes), f32 accumulation."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _scores(q, k, masked: bool, band=None):
    """Log2-domain score block [rows, cols] (q arrives scaled), shared by
    the forward and every backward kernel so the mask cannot
    desynchronize. A masked block is a diagonal strip: its LAST row sees
    all its columns, so the mask is a static triangle. ``band`` = (shift,
    window): a pair the window's left edge cuts, whose row r keeps the
    columns c with shift + r - c < window, another static triangle."""
    s = _dot(q, k, 1, 1)
    if masked:
        rows, cols = s.shape
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(row + (cols - rows) >= col, s, -1e30)
    if band is not None:
        shift, window = band
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(row + (shift - window) < col, s, -1e30)
    return s


# The kernels work in the LOG2 domain: q tiles are scaled by
# sm_scale*log2(e) on load (a [b, D] pass), so the per-block [b, b] scale
# multiply disappears and exp becomes the VPU's native exp2. True scores
# A = ln2 * s; probabilities exp2(s-m) == exp(A-A_max) are IDENTICAL, and
# the chain rule through the scale leaves sm_scale on dq and dk.
LOG2E = 1.4426950408889634


def _scaled(q_ref, q_scale: float):
    return (q_ref[0].astype(jnp.float32) * q_scale).astype(q_ref.dtype)


def _lanes(x, width: int):
    """A lane-replicated [rows, 128] stat widened to ``width`` lanes: whole
    vregs repeated, no broadcast."""
    return jnp.tile(x, (1, width // 128))


def _softmax_update(q, k, v, masked, band, acc_ref, m_ref, l_ref, rows):
    """One online-softmax step of the state rows ``rows`` (a static slice)
    for the q rows ``q`` against one K/V block. The running max and sum
    are kept REPLICATED over their 128 lanes: a [rows, 1] column costs as
    many vregs, and every use of it against a [rows, cols] block would pay
    a lane broadcast (measured on a v5e, PERF.md PR 27: the forward kernel
    2.31 -> 1.43 ms against lane-0 stats, strips of 128)."""
    s = _scores(q, k, masked, band)
    m_prev = m_ref[rows, :]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp2(m_prev - m_new)
    p = jnp.exp2(s - _lanes(m_new, s.shape[1]))
    l_ref[rows, :] = l_ref[rows, :] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
    acc_ref[rows, :] = acc_ref[rows, :] * _lanes(alpha, v.shape[1]) + _dot(
        p.astype(v.dtype), v, 1, 0)
    m_ref[rows, :] = m_new


def _for_each_pair(k_ref, v_ref, *, b: int, sub: int, causal: bool,
                   resident: bool, begin, visit, end, band=None):
    """The pairs of q tile ``program_id(2)``, in k order: ``begin()``,
    ``visit(kb, kv, blocks)`` for every pair that contributes —
    ``kv(w)`` hands out the first w rows of K/V tile kb, ``blocks`` are
    the pair's (``_pair_blocks``) — then ``end()``.

    ``resident``: K and V of the (batch, head) are whole in VMEM (fetched
    once per head, not once per q tile) and an in-kernel loop walks the k
    tiles up to the diagonal — no grid step exists for a pair above it.
    Otherwise K/V tiles stream over a kb grid axis (``program_id(3)``)
    whose index maps are clamped at the diagonal (``_kv_row``), so the
    steps above it fetch nothing and do nothing. ``band`` (``_band``): the
    band's pairs alone; streamed, the kb axis is ``last + 1`` steps wide,
    step j meeting tile qi - last + j, and the steps left of tile 0
    re-name it and do nothing."""
    qi = pl.program_id(2)
    plain_blocks = _pair_blocks(b, sub, None)
    diag_blocks = _pair_blocks(b, sub, 0)
    if resident:
        def tile(kb):
            def kv(w):
                rows = pl.ds(pl.multiple_of(kb * b, b), w)
                return k_ref[0, rows, :], v_ref[0, rows, :]
            return kv

        def plain(kb, carry):
            visit(kb, tile(kb), plain_blocks)
            return carry

        begin()
        if band is None:
            first = 0
        else:
            _, n_plain, edges, window = band
            for d in reversed(edges):
                pl.when(qi >= d)(functools.partial(
                    lambda d: visit(qi - d, tile(qi - d),
                                    _pair_blocks(b, sub, d, window)), d))
            first = jnp.maximum(qi - n_plain, 0)
        jax.lax.fori_loop(first, qi if causal else k_ref.shape[1] // b,
                          plain, 0)
        if causal:
            visit(qi, tile(qi), diag_blocks)
        end()
        return

    def kv(w):
        return k_ref[0, :w, :], v_ref[0, :w, :]

    if band is not None:
        last, n_plain, edges, window = band
        j = pl.program_id(3)
        d, kb = last - j, qi - last + j
        pl.when(j == 0)(begin)
        if n_plain:
            pl.when((d <= n_plain) & (d >= 1) & (kb >= 0))(
                lambda: visit(kb, kv, plain_blocks))
        for e in edges:
            pl.when((d == e) & (kb >= 0))(functools.partial(
                lambda e: visit(kb, kv, _pair_blocks(b, sub, e, window)),
                e))

        @pl.when(j == last)
        def _diagonal():
            visit(qi, kv, diag_blocks)
            end()
        return

    kb = pl.program_id(3)
    last = qi if causal else pl.num_programs(3) - 1
    pl.when(kb == 0)(begin)
    pl.when(kb < last)(lambda: visit(kb, kv, plain_blocks))

    @pl.when(kb == last)
    def _last():
        visit(kb, kv, diag_blocks if causal else plain_blocks)
        end()


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, causal: bool, b: int, sub: int,
                resident: bool, q_scale: float, with_lse: bool,
                band=None):
    """Forward for one [b, D] q tile, grid (g0, g1, qi[, kb]) (the two
    schedules: ``_for_each_pair``). The online-softmax state (acc/m/l)
    lives in scratch; the normalized output and the row log2-sum-exp2
    (saved for the backward) are written after the last contributing
    pair."""
    if with_lse:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        (o_ref, acc_ref, m_ref, l_ref), lse_ref = rest, None
    q = _scaled(q_ref, q_scale)

    def init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -1e30)
        l_ref[:] = jnp.zeros_like(l_ref)

    def visit(kb, kv, blocks):
        for r0, n, w, masked, band in blocks:
            _softmax_update(q[r0:r0 + n], *kv(w), masked, band, acc_ref,
                            m_ref, l_ref, slice(r0, r0 + n))

    def finish():
        l = l_ref[:]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / _lanes(safe, acc_ref.shape[1])).astype(
            o_ref.dtype)
        if lse_ref is not None:
            # log2 domain, matching the backward's exp2 recompute.
            lse = jnp.where(l == 0.0, -1e30, m_ref[:] + jnp.log2(safe))
            lse_ref[0] = lse[:, :_STAT_LANES]

    _for_each_pair(k_ref, v_ref, b=b, sub=sub, causal=causal,
                   resident=resident, begin=init, visit=visit, end=finish,
                   band=band)


def _bwd_visit(qs, q, do, lse, delta, kv, schedule, add_dq, add_dkv):
    """The backward of one tile pair, block by block of ``schedule``
    (``_pair_blocks``).

    Recomputes each probability block from (q, k, lse) — the
    flash-backward trade: score blocks never leave VMEM.
    dA = P ∘ (dO·Vᵀ − Δ), Δ = rowsum(dO ∘ O); unscaled contributions
    dA·K go to ``add_dq(rows, ·)``, dAᵀ·Q (raw q) and Pᵀ·dO — for the
    pair's first ``cols`` K/V rows — to ``add_dkv(cols, ·, ·)``; either
    may be None (the split kernels)."""
    for r0, n, w, masked, band in schedule:
        r = slice(r0, r0 + n)
        k, v = kv(w)
        p = jnp.exp2(_scores(qs[r], k, masked, band) - lse[r])
        ds = (p * (_dot(do[r], v, 1, 1) - delta[r])).astype(k.dtype)
        if add_dq is not None:
            add_dq(r, _dot(ds, k, 1, 0))
        if add_dkv is not None:
            add_dkv(w, _dot(ds, q[r], 0, 0),
                    _dot(p.astype(do.dtype), do[r], 0, 0))


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                causal: bool, b: int, sub: int, resident: bool,
                fused: bool, packed: bool, q_scale: float,
                grad_scale: float, band=None):
    """Backward for one [b, D] q tile, grid (g0, g1, qi[, kb]), with the
    forward's two schedules (``_for_each_pair``).

    ``fused``: dq, dk and dv from ONE visit of each tile pair — s, p, dp
    and ds are computed once (5 matmuls + 1 exp2 pass per block, against
    7 + 2 over the split dq and dkv kernels). dq accumulates per q tile
    in a [b, D] scratch; dk/dv accumulate over the whole (batch, head)
    visit in full-T [T, D] f32 scratch and flush at its last step. That
    costs 2·T·D f32 of VMEM, so callers fall back to the split kernels
    when ``_plan`` says so. ``packed``: the single output block is
    head h's column stripe ``[1, T, 3D]`` (q|k|v) of the packed gradient,
    resident for the whole visit, so the gradient exists in exactly one
    materialization. Not ``fused``: the split path's dq kernel."""
    n_out = 1 if (packed or not fused) else 3
    outs, dq_acc = rest[:n_out], rest[n_out]
    dk_acc, dv_acc = rest[n_out + 1:] if fused else (None, None)
    qi = pl.program_id(2)
    n_qi = pl.num_programs(2)
    d = q_ref.shape[-1]
    q, do = q_ref[0], do_ref[0]
    qs = _scaled(q_ref, q_scale)
    lse, delta = lse_ref[0][:, :1], delta_ref[0][:, :1]

    def add_dq(r, x):
        dq_acc[r, :] += x

    def visit(kb, kv, blocks):
        def add_dkv(w, dk, dv):
            rows = pl.ds(pl.multiple_of(kb * b, b), w)
            dk_acc[rows, :] += dk
            dv_acc[rows, :] += dv
        _bwd_visit(qs, q, do, lse, delta, kv, blocks, add_dq,
                   add_dkv if fused else None)

    def init_kv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def write_q():
        dq = (dq_acc[:] * grad_scale).astype(outs[0].dtype)
        if packed and fused:
            outs[0][0, pl.ds(pl.multiple_of(qi * b, b), b), 0:d] = dq
        else:
            outs[0][0] = dq

    def write_kv():
        dk = (dk_acc[:] * grad_scale).astype(outs[0].dtype)
        dv = dv_acc[:].astype(outs[0].dtype)
        if packed:
            outs[0][0, :, d:2 * d] = dk
            outs[0][0, :, 2 * d:3 * d] = dv
        else:
            outs[1][0] = dk
            outs[2][0] = dv

    def begin():
        if fused:
            pl.when(qi == 0)(init_kv)
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def end():
        write_q()
        if fused:
            pl.when(qi == n_qi - 1)(write_kv)

    _for_each_pair(k_ref, v_ref, b=b, sub=sub, causal=causal,
                   resident=resident, begin=begin, visit=visit, end=end,
                   band=band)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, causal: bool, b: int, sub: int,
                q_scale: float, grad_scale: float, band=None):
    """Split path, grid (g0, g1, kb, qi): dk/dv of one K/V tile over the
    q tiles at and below the diagonal (the steps above it — they come
    FIRST here — fetch nothing: ``_q_row`` clamps). ``band``: the qi
    axis is ``last + 1`` steps wide, step j meeting q tile kb + j; the
    steps past the last tile re-name it and do nothing."""
    kb = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def add_dkv(w, dk, dv):
        dk_acc[:w, :] += dk
        dv_acc[:w, :] += dv

    def visit(d):
        _bwd_visit(_scaled(q_ref, q_scale), q_ref[0], do_ref[0],
                   lse_ref[0][:, :1], delta_ref[0][:, :1],
                   lambda w: (k_ref[0, :w, :], v_ref[0, :w, :]),
                   _pair_blocks(b, sub, d, None if band is None else band[3]),
                   None, add_dkv)

    if band is not None:
        # Here the step is the distance d = qi - kb itself.
        d, n_t = qi, pl.num_programs(2)
        _, n_plain, edges, _ = band
        pl.when(d == 0)(lambda: visit(0))
        if n_plain:
            pl.when((d >= 1) & (d <= n_plain) & (kb + d < n_t))(
                lambda: visit(None))
        for e in edges:
            pl.when((d == e) & (kb + d < n_t))(
                functools.partial(visit, e))
    elif causal:
        pl.when(qi == kb)(lambda: visit(0))
        pl.when(qi > kb)(lambda: visit(None))
    else:
        visit(None)

    @pl.when(qi == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0] = (dk_acc[:] * grad_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# Scoped VMEM a kernel with whole-sequence residents may claim, in two
# rungs: the v5e compiler's own default, and the limit every other Pallas
# kernel of the package asks for (of the chip's 128 MiB). Such a kernel
# passes the rung its sum fits as ``vmem_limit_bytes``, so the gate and the
# compiler hold the same number.
_VMEM_DEFAULT = 16 * 2 ** 20
_VMEM_LIMIT = 64 * 2 ** 20


def _vmem_need(T: int, D: int, itemsize: int, *, b: int, bwd: bool,
               kv_resident: bool, packed: bool = False) -> int:
    """Bytes of scoped VMEM a kernel with whole-sequence residents needs:
    the forward (``bwd=False``) or the fused backward, K/V resident
    (in-kernel loop) or streamed.

    The sum bounds what the v5e compiler allocates from above: the
    smallest ``vmem_limit_bytes`` that compiles sat under it at H=16 for D
    in {128, 256}, bf16 and f32, T = 128..16384 near the default rung,
    and near the 64 MiB rung (bisected to 0.25 MiB for both directions
    and every schedule, D 128 and 256, bf16 and f32, T 1024..32768, both
    layouts) by 0.25-4.25 MiB, and by 6.25-26.5 MiB where v is narrower
    than q and k or the backward's K/V are resident in the [BH, T, D]
    layout (the latent cells' backward: 29.0 against 55.5).
    ``tests/test_tpu_compile.py`` keeps the near-boundary shapes
    compiling:

    * scratch, f32: forward acc [b, D] + two stat tiles; fused backward
      dq_acc [b, D] + the dk/dv accumulators 2×[T, D];
    * pipelined operands, DOUBLE-buffered even where they are constant
      over a (batch, head) visit: the q (and do) tile, the f32 stat tiles
      (a full 128-lane tile in VMEM whatever ``_STAT_LANES`` says), K and
      V — whole [T, D] when resident, one tile each when streamed — and
      the outputs: forward o tile + lse; backward packed [T, 3D], else
      the dq tile + full-T dk/dv;
    * stack: the [b, b] f32 intermediates (scores/probabilities, dp, ds)
      the compiler keeps there; the backward's [b, D] f32 products fit in
      their slack at D = 128 and need room of their own beyond it.
    """
    tile, full = b * D * itemsize, T * D * itemsize
    stat, stack = b * 128 * 4, 3 * b * b * 4
    kv = 2 * (full if kv_resident else tile)
    if bwd:
        scratch = 4 * (b * D + 2 * T * D)
        piped = (2 * tile + 2 * stat + kv
                 + (3 * full if packed else tile + 2 * full))
        stack += 6 * b * (D - 128) * 4
    else:
        scratch = 4 * b * D + 2 * stat
        piped = 2 * tile + stat + kv
    return scratch + 2 * piped + stack


def _plan(T: int, D: int, itemsize: int, *, b: int, bwd: bool,
          packed: bool = False) -> tuple[str, Optional[int]]:
    """-> (schedule, ``vmem_limit_bytes``): the ONE gate that picks both
    from the shape. The forward is ``resident`` or ``streamed``; the
    backward the fused kernel with K/V ``resident``, the fused kernel with
    K/V ``streamed``, or the ``split`` dq and dkv kernels.

    The first rung whose limit holds a schedule's sum wins, resident before
    streamed within a rung: every shape the compiler's default holds keeps
    the schedule and the limit it had with that rung alone, and the 64 MiB
    rung serves only shapes the default refuses. The streamed forward and
    the split backward keep tiles alone in VMEM and ask for no limit."""
    kinds = (("resident", True), ("streamed", False)) if bwd \
        else (("resident", True),)
    for limit in (_VMEM_DEFAULT, _VMEM_LIMIT):
        for plan, kv_resident in kinds:
            if _vmem_need(T, D, itemsize, b=b, bwd=bwd,
                          kv_resident=kv_resident, packed=packed) <= limit:
                return plan, limit
    return ("split" if bwd else "streamed"), None


def _plan_counter():
    from ..obs.registry import registry
    return registry().counter(
        "hvd_flash_bwd_plan_total",
        "traces of the flash backward, by the kernels its shape got "
        "(resident, streamed: flash_bwd; split: flash_bwd_dq + "
        "flash_bwd_dkv) and the scoped VMEM they asked for (default: the "
        "compiler's 16 MiB; 64MiB)", labels=("plan", "grant"))


# Lane width of the per-row stat tensors (lse, delta) on the wire between
# kernels. Only lane 0 carries data; 8 lanes (one f32 sublane tile) keeps
# Mosaic layouts happy while cutting the streamed stat traffic 16x vs the
# old 128-lane replication: at the bench config BH = B*H = 8*16 = 128,
# T = 2048, so a 128-lane f32 stat was 128*2048*128*4 = 134 MB per stat
# per kernel per layer — pure HBM burn for a [BH, T] statistic.
_STAT_LANES = 8


# ---------------------------------------------------------------------------
# Two array layouts, one set of kernels. Every kernel's grid starts
# (g0, g1): the packed layout consumes the fused QKV projection output
# [B, T, H*3*D] (HEAD-major columns, i.e. reshape [B, T, H, 3, D])
# DIRECTLY through BlockSpec index maps with (g0, g1) = (batch, head) — no
# [B,T,H,D] <-> [BH,T,D] transposes on either side of the kernels — and
# returns the attention output as [B, T, H*D], exactly what the output
# projection consumes. The [BH, T, D] layout runs the same kernels with
# (g0, g1) = (batch·head, 0). Stats are [B*H, T, _STAT_LANES] in both.
# ---------------------------------------------------------------------------


class _Layout:
    """Block index maps of one array layout. ``H`` None: q/k/v/o are
    separate [BH, T, .] arrays, q and k ``D`` wide and v and o ``Dv``;
    else packed columns for H heads of one width."""

    def __init__(self, H: Optional[int], D: int, Dv: Optional[int] = None):
        self.H, self.D, self.Dv = H, D, D if Dv is None else Dv

    def grid(self, lead: int):
        return (lead, 1) if self.H is None else (lead, self.H)

    def spec(self, kind: str, rows: int, row):
        """BlockSpec of ``rows`` rows of operand ``kind`` (q, k, v: the
        inputs; o: an output-shaped [.., H*D] array; stat); ``row`` maps
        the grid's trailing indices to the row-block index."""
        H = self.H
        if kind == "stat":
            return pl.BlockSpec(
                (1, rows, _STAT_LANES),
                lambda g0, g1, *ij: (g0 if H is None else g0 * H + g1,
                                     row(*ij), 0))
        col = {"q": 0, "k": 1, "v": 2}.get(kind)
        return pl.BlockSpec(
            (1, rows, self.D if kind in ("q", "k", "dqk") else self.Dv),
            lambda g0, g1, *ij: (
                g0, row(*ij),
                0 if H is None else (g1 if col is None else g1 * 3 + col)))

    @property
    def gate_d(self):
        """The width the schedule's gate budgets for: the wider of the
        two (a narrower v only leaves room)."""
        return max(self.D, self.Dv)


def _qi_row(qi, *kb):
    """Row-block map of the q-side tiles on grid (.., qi[, kb])."""
    return qi


def _kv_row(causal, band=None):
    """Row-block map of a streamed K/V tile on grid (.., qi, kb): clamped
    at the diagonal, so a step above it re-names the tile already in VMEM
    and nothing is fetched. ``band``: grid (.., qi, j) over the band, step
    j meeting tile qi - last + j, clamped from below at tile 0."""
    if band is not None:
        return lambda qi, j: jnp.maximum(qi - band[0] + j, 0)
    return (lambda qi, kb: jnp.minimum(kb, qi)) if causal \
        else (lambda qi, kb: kb)


def _q_row(causal, band=None, n_t=0):
    """The same for the q-side tiles of the dkv kernel's grid (.., kb, qi),
    where the dead steps come first. ``band``: grid (.., kb, j) over the
    band, step j meeting q tile kb + j, clamped from above at the last of
    the ``n_t``; there the dead steps come last."""
    if band is not None:
        return lambda kb, j: jnp.minimum(kb + j, n_t - 1)
    return (lambda kb, qi: jnp.maximum(qi, kb)) if causal \
        else (lambda kb, qi: qi)


def _layout_of(q, H: Optional[int], v=None):
    """(_Layout, T) of the q array (or of the packed array standing in for
    q, k and v alike); ``v``: the value array, where its width is its
    own."""
    D = q.shape[-1] if H is None else q.shape[-1] // (3 * H)
    return _Layout(H, D, None if v is None else v.shape[-1]), q.shape[1]


# The forward and the backward are jitted by themselves: a model calls them
# once per layer with the same shapes, and jit's cache then traces and
# lowers each kernel body ONCE per program instead of once per layer — the
# unrolled diagonal strips make the bodies several times longer to trace
# than one tile pair's (PERF.md PR 27: the LM cell's set-up).
@functools.partial(jax.jit, static_argnames=(
    "H", "causal", "q_scale", "interpret", "with_lse", "window"))
def _fwd(q, k, v, *, H: Optional[int], causal: bool, q_scale: float,
         interpret: bool, with_lse: bool = True,
         window: Optional[int] = None):
    """-> (o, lse2 [B*H, T, _STAT_LANES] f32 | None). ``with_lse=False``
    (the no-grad primal) drops the lse output — Mosaic can't
    dead-code-eliminate an output buffer, and at long T the f32 lse write
    outweighs the bf16 output itself. ``window``: causal attention over
    the band of that many keys (``_band``)."""
    lay, T = _layout_of(q, H, None if H is not None else v)
    D = lay.Dv
    b, sub = _blocks(T, window)
    band = None if window is None else _band(b, window, T // b)
    lead = q.shape[0]
    n_heads = lead if lay.H is None else lead * lay.H
    plan, vmem = _plan(T, lay.gate_d, q.dtype.itemsize, b=b, bwd=False)
    resident = plan == "resident"
    if resident:
        grid = lay.grid(lead) + (T // b,)
        kv_specs = [lay.spec(x, T, lambda qi: 0) for x in "kv"]
        semantics = ("parallel",) * 3
    else:
        grid = lay.grid(lead) + (T // b, T // b if band is None
                                 else band[0] + 1)
        kv_specs = [lay.spec(x, b, _kv_row(causal, band)) for x in "kv"]
        semantics = ("parallel",) * 3 + ("arbitrary",)
    o_cols = D if lay.H is None else lay.H * D
    out_specs = [lay.spec("o", b, _qi_row)]
    out_shape = [jax.ShapeDtypeStruct(q.shape[:2] + (o_cols,), q.dtype)]
    if with_lse:
        out_specs.append(lay.spec("stat", b, _qi_row))
        out_shape.append(jax.ShapeDtypeStruct((n_heads, T, _STAT_LANES),
                                              jnp.float32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, b=b, sub=sub,
                          resident=resident, q_scale=q_scale,
                          with_lse=with_lse, band=band),
        grid=grid,
        in_specs=[lay.spec("q", b, _qi_row)] + kv_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((b, D), jnp.float32),            # acc
            pltpu.VMEM((b, 128), jnp.float32),          # running max
            pltpu.VMEM((b, 128), jnp.float32),          # running sum
        ],
        compiler_params=_grid_params(semantics, vmem),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out if with_lse else (out[0], None)


@functools.partial(jax.jit, static_argnames=(
    "H", "causal", "q_scale", "grad_scale", "interpret", "window"))
def _bwd(q, k, v, o, lse, do, *, H: Optional[int], causal: bool,
         q_scale: float, grad_scale: float, interpret: bool,
         window: Optional[int] = None):
    """Gradients of the three inputs: one packed [B, T, H*3*D] array from
    the fused kernel in the packed layout, else (dq, dk, dv) shaped like
    ``o``, by the kernels ``_plan`` picks; ``window`` as ``_fwd``'s. The
    gate sizes a windowed kernel as a causal one: it keeps the same
    whole-sequence residents (K/V, and the fused kernel's dk/dv
    accumulators), through which its band moves."""
    lay, T = _layout_of(q, H, None if H is not None else v)
    D, Dv, packed = lay.D, lay.Dv, H is not None
    b, sub = _blocks(T, window)
    lead, n_t = q.shape[0], T // b
    band = None if window is None else _band(b, window, n_t)
    n_k = n_t if band is None else band[0] + 1
    # Δ_i = Σ_d dO ∘ O — cheap elementwise reduction, XLA fuses it;
    # widened to _STAT_LANES like lse so the kernels read [b, 8] tiles.
    prod = do.astype(jnp.float32) * o.astype(jnp.float32)
    if packed:
        delta = prod.reshape(lead, T, lay.H, Dv).sum(-1).transpose(0, 2, 1)
    else:
        delta = prod.sum(-1)
    delta = jnp.broadcast_to(delta.reshape(-1, T, 1),
                             (lse.shape[0], T, _STAT_LANES))
    plan, vmem = _plan(T, lay.gate_d, q.dtype.itemsize, b=b, bwd=True,
                       packed=packed)
    _plan_counter().labels(
        plan=plan, grant="64MiB" if vmem == _VMEM_LIMIT else "default").inc()
    resident, fused = plan == "resident", plan != "split"
    kernel = functools.partial(
        _bwd_kernel, causal=causal, b=b, sub=sub, resident=resident,
        fused=fused, packed=packed, q_scale=q_scale, grad_scale=grad_scale,
        band=band)
    q_side = [lay.spec("q", b, _qi_row)]
    tail = [lay.spec("o", b, _qi_row), lay.spec("stat", b, _qi_row),
            lay.spec("stat", b, _qi_row)]
    if resident:
        grid = lay.grid(lead) + (n_t,)
        kv_specs = [lay.spec(x, T, lambda qi: 0) for x in "kv"]
        semantics = ("parallel", "parallel", "arbitrary")
    else:
        grid = lay.grid(lead) + (n_t, n_k)
        kv_specs = [lay.spec(x, b, _kv_row(causal, band)) for x in "kv"]
        semantics = ("parallel", "parallel",
                     "arbitrary" if fused else "parallel", "arbitrary")
    # dq and dk are as wide as q and k, dv as v (one width in the packed
    # layout, where "dqk" and "o" name the same columns).
    qk_like = jax.ShapeDtypeStruct(o.shape[:-1] + (
        o.shape[-1] // Dv * D,), q.dtype)
    o_like = jax.ShapeDtypeStruct(o.shape, q.dtype)
    dq_acc = pltpu.VMEM((b, D), jnp.float32)
    if fused:
        if packed:
            out_specs = pl.BlockSpec((1, T, 3 * D),
                                     lambda g0, g1, *ij: (g0, 0, g1))
            out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
        else:
            out_specs = [lay.spec("dqk", b, _qi_row),
                         lay.spec("dqk", T, lambda *ij: 0),
                         lay.spec("o", T, lambda *ij: 0)]
            out_shape = [qk_like, qk_like, o_like]
        return pl.pallas_call(
            kernel, grid=grid, in_specs=q_side + kv_specs + tail,
            out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=[dq_acc, pltpu.VMEM((T, D), jnp.float32),
                            pltpu.VMEM((T, Dv), jnp.float32)],
            compiler_params=_grid_params(semantics, vmem),
            interpret=interpret, name="flash_bwd",
        )(q, k, v, do, lse, delta)
    dq = pl.pallas_call(
        kernel, grid=grid, in_specs=q_side + kv_specs + tail,
        out_specs=lay.spec("dqk", b, _qi_row), out_shape=qk_like,
        scratch_shapes=[dq_acc],
        compiler_params=_grid_params(semantics),
        interpret=interpret, name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)
    # dk/dv iterate the OTHER way: grid (.., kb, qi), one K/V tile
    # accumulated over q tiles.
    krow = lambda kb, qi: kb                                 # noqa: E731
    q_row = _q_row(causal, band, n_t)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, b=b, sub=sub,
                          q_scale=q_scale, grad_scale=grad_scale, band=band),
        grid=lay.grid(lead) + (n_t, n_k),
        in_specs=[lay.spec("q", b, q_row),
                  lay.spec("k", b, krow), lay.spec("v", b, krow),
                  lay.spec("o", b, q_row),
                  lay.spec("stat", b, q_row),
                  lay.spec("stat", b, q_row)],
        out_specs=[lay.spec("dqk", b, krow), lay.spec("o", b, krow)],
        out_shape=[qk_like, o_like],
        scratch_shapes=[pltpu.VMEM((b, D), jnp.float32),
                        pltpu.VMEM((b, Dv), jnp.float32)],
        compiler_params=_grid_params(
            ("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret, name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)
    if not packed:
        return dq, dk, dv
    # Interleave back into the packed head-major (H, 3, D) column layout.
    return jnp.stack([g.reshape(lead, T, lay.H, D) for g in (dq, dk, dv)],
                     axis=3).reshape(q.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_core(q, k, v, causal: bool, sm_scale: float, interpret: bool,
                window: Optional[int] = None):
    """q/k/v: [BH, T, D] -> o [BH, T, D]."""
    return _flash_core_fwd(q, k, v, causal, sm_scale, interpret, window,
                           with_lse=False)[0]


def _flash_core_fwd(q, k, v, causal, sm_scale, interpret, window=None,
                    with_lse=True):
    o, lse = _fwd(q, k, v, H=None, causal=causal, q_scale=sm_scale * LOG2E,
                  interpret=interpret, with_lse=with_lse, window=window)
    # lse stays in the narrow [BH, T, _STAT_LANES] wire format in the
    # residuals (slicing to one lane and re-broadcasting in backward would
    # cost two device copies to save 7 f32 lanes).
    return o, (q, k, v, o, lse)


def _flash_core_bwd(causal, sm_scale, interpret, window, res, do):
    q, k, v, o, lse = res
    return _bwd(q, k, v, o, lse, do, H=None, causal=causal,
                q_scale=sm_scale * LOG2E, grad_scale=sm_scale,
                interpret=interpret, window=window)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _flash_qkv_core(qkv, H: int, causal: bool, sm_scale: float,
                    interpret: bool):
    """qkv: packed [B, T, H*3*D] -> o [B, T, H*D]."""
    return _flash_qkv_core_fwd(qkv, H, causal, sm_scale, interpret,
                               with_lse=False)[0]


def _flash_qkv_core_fwd(qkv, H, causal, sm_scale, interpret, with_lse=True):
    o, lse = _fwd(qkv, qkv, qkv, H=H, causal=causal,
                  q_scale=sm_scale * LOG2E, interpret=interpret,
                  with_lse=with_lse)
    return o, (qkv, o, lse)


def _flash_qkv_core_bwd(H, causal, sm_scale, interpret, res, do):
    qkv, o, lse = res
    return (_bwd(qkv, qkv, qkv, o, lse, do, H=H, causal=causal,
                 q_scale=sm_scale * LOG2E, grad_scale=sm_scale,
                 interpret=interpret),)


_flash_qkv_core.defvjp(_flash_qkv_core_fwd, _flash_qkv_core_bwd)


def qkv_flash_tilable(T: int, d_head: int) -> bool:
    """Whether the packed-qkv kernel path tiles these dims."""
    return T % BLOCK_Q == 0 and T % BLOCK_K == 0 and d_head % 128 == 0


def flash_attention_qkv(qkv, n_heads: int, *, causal: bool = False,
                        sm_scale: Optional[float] = None,
                        interpret: Optional[bool] = None):
    """Attention straight from the packed QKV projection output.

    Args:
      qkv: [B, T, n_heads*3*d_head], HEAD-major columns (i.e. reshapes to
        [B, T, n_heads, 3, d_head] — the layout the parallel transformer's
        fused projection produces).
      n_heads: head count (d_head inferred).
    Returns: [B, T, n_heads*d_head] attention output, ready for the output
    projection. Differentiable (custom VJP; dq/dk/dv re-interleave into
    the packed gradient). Requires ``qkv_flash_tilable``; callers fall
    back to the split path otherwise.
    """
    B, T, cols = qkv.shape
    D = cols // (3 * n_heads)
    if sm_scale is None:
        sm_scale = float(D) ** -0.5
    if not qkv_flash_tilable(T, D):
        raise ValueError(
            f"flash_attention_qkv needs T%128==0 and d_head%128==0; got "
            f"T={T}, d_head={D} (use the split flash_attention fallback)")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _flash_qkv_core(qkv, n_heads, causal, sm_scale, interpret)


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale",
                                             "interpret", "window"))
def _flash_bhtd(q, k, v, causal: bool, sm_scale: float, interpret: bool,
                window: Optional[int] = None):
    """q/k/v: [BH, T, D] -> [BH, T, D]. Differentiable (custom VJP with
    Pallas backward kernels — the score matrix never touches HBM in
    either direction)."""
    return _flash_core(q, k, v, causal, sm_scale, interpret, window)


# Above roughly this many bytes of [B, H, T, T] f32 scores, the dense XLA
# path risks HBM exhaustion and the blockwise kernel wins by never
# materializing them. Measured on a v5e chip before PR 6 (history; the
# kernels have since been rewritten — B=1 H=8 D=128, causal,
# bf16): XLA is FASTER wherever the dense scores fit (8k: 19 vs 24 ms;
# 16k: 52 vs 69 ms) and the kernel is within ~1.3x; at 32k (34 GB of
# scores > 16 GB HBM) only the kernel runs (232 ms). So "auto" switches
# for MEMORY, not speed — 4 GiB leaves room for params/activations/
# optimizer state sharing HBM with the scores in a real training step.
# NOTE those numbers are inference-only; for TRAINING the dense path also
# saves the score tensors for backward, so memory binds far earlier than
# this forward-pass cutover — training code should pass backend="pallas"
# explicitly (TransformerConfig.attn_backend defaults to it).
_SCORE_BYTES_CUTOVER = 4 * 1024 ** 3


def flash_attention(q, k, v, *, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    backend: str = "auto",
                    interpret: Optional[bool] = None,
                    fallback: bool = True,
                    window: Optional[int] = None):
    """Multi-head attention: XLA by default, Pallas kernel for long context.

    Args:
      q, k: [B, T, H, D]; v: [B, T, H, Dv] (Dv = D, or a width of its own:
        latent attention's heads are 192 wide for q and k, 128 for v).
      causal: apply the causal mask.
      sm_scale: softmax scale (default 1/sqrt(D), D the width of q and k).
      backend: "auto" (XLA unless the score tensor would exceed ~4 GiB —
        measured on the target platform XLA's fused attention outruns
        Mosaic until memory becomes the binding constraint), "pallas", or
        "xla".
      interpret: force kernel interpreter mode (defaults to True off-TPU).
      fallback: what ``backend="pallas"`` does with a shape the kernels do
        not tile: True (the packed block's callers), the XLA path below,
        whose [B, H, T, T] float32 scores exist in HBM; False, a
        ValueError (the layers described by ``MLA`` and the windowed
        layers: they never fall back silently).
      window: causal attention over a sliding window: query i sees key j
        iff i - window < j <= i (``window`` keys, itself among them). The
        kernels visit only the tile pairs that touch the band
        (``_band``); a window of T or more is plain causal attention.

    Which shapes tile: T a multiple of 128 and Dv a multiple of 128, D any
    width, and a window of at least 128. q and k of a width that is no
    multiple of 128 enter the kernels padded with zero columns to the next
    one (192 -> 256: on a 128-wide
    MXU the contraction takes two passes either way), and their gradients
    leave cut back; the scale stays that of the true width. Differentiable on
    every path (the Pallas path via a custom VJP whose dq/dk/dv are
    themselves Pallas kernels).
    """
    B, T, H, D = q.shape
    Dv = v.shape[-1]
    if sm_scale is None:
        sm_scale = float(D) ** -0.5
    if window is not None:
        if not causal:
            raise ValueError("flash_attention: a window bounds CAUSAL "
                             "attention from below; pass causal=True")
        if window < 1:
            raise ValueError(f"flash_attention: window={window} holds no key")
        if window >= T:
            window = None
    tilable = qkv_flash_tilable(T, Dv) and (window is None
                                            or window >= BLOCK_K)
    if backend == "auto":
        score_bytes = 4 * B * H * T * T
        backend = "pallas" if (tilable
                               and score_bytes > _SCORE_BYTES_CUTOVER) \
            else "xla"
    if backend == "pallas" and not tilable and not fallback:
        raise ValueError(
            f"flash_attention: the kernels tile T % 128 == 0, a value "
            f"width that is a multiple of 128 and a window of at least 128; "
            f"got T={T}, q/k width {D}, v width {Dv}, window {window}, and "
            f"no fallback to [T, T] scores was allowed")
    if backend == "xla" or not tilable:
        return _xla_attention(q, k, v, causal, sm_scale, window)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def to_bhtd(x):
        x = x.transpose(0, 2, 1, 3).reshape(B * H, T, x.shape[-1])
        if x.shape[-1] % 128:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, -x.shape[-1] % 128)))
        return x

    out = _flash_bhtd(to_bhtd(q), to_bhtd(k), to_bhtd(v), causal, sm_scale,
                      interpret, window)
    return out.reshape(B, H, T, Dv).transpose(0, 2, 1, 3)


def _xla_attention(q, k, v, causal, sm_scale, window=None):
    """Dense attention, [B, H, T, T] float32 scores; ``window`` as
    :func:`flash_attention`'s (causal)."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * sm_scale
    if causal:
        pos = jnp.arange(q.shape[1])
        seen = pos[:, None] >= pos[None, :]
        if window is not None:
            seen = seen & (pos[:, None] - pos[None, :] < window)
        scores = jnp.where(seen, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
