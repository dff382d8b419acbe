"""Pallas TPU flash attention — the hot-op kernel for the transformer path.

Blockwise causal attention computed entirely in VMEM with an online softmax
(running max/sum), so the [T, T] score matrix never touches HBM: per grid
step a [BQ, D] query tile is streamed against K/V tiles with MXU matmuls
(f32 accumulation). Differentiable end to end: a custom VJP recomputes the
probability tiles from (q, k, lse) inside dq/dkv kernels, so the backward
pass never materializes scores either. Used by the parallel transformer's
single-shard attention path (``parallel/transformer.py``); the
sequence-parallel path (:func:`horovod_tpu.parallel.ring.ring_attention`)
keeps its own blockwise accumulation across chips.

Off-TPU (CPU tests) the kernels run in interpreter mode, bit-matching the
compiled path's math. `flash_attention` falls back to plain XLA attention
for shapes the kernel doesn't tile (tiny head_dim or sequences not divisible
by the block).

Kernel names (``pallas_call(name=)``; a device trace and the compiled HLO
find the kernels by them, so they are API): ``flash_fwd``, ``flash_bwd``
(fused dq/dk/dv), and the split backward's ``flash_bwd_dq`` and
``flash_bwd_dkv`` — the same four for the packed-qkv and the BHTD layouts.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q = 128    # minimum tile (tilability floor)
BLOCK_K = 128
# Preferred tile sizes (swept on a v5e chip; _pick_block shrinks them to
# fit short sequences).
_WANT_BQ = 512
_WANT_BK = 512


def _pick_block(t: int, want: int) -> int:
    """Largest power-of-two block <= ``want`` dividing ``t``. Bigger tiles
    amortize Mosaic's per-grid-step overhead; 128 is the floor the
    tilability check guarantees."""
    b = want
    while b > 128 and t % b:
        b //= 2
    return b


def _grid_params(semantics, vmem_limit_bytes=None):
    """dimension_semantics lets Mosaic pipeline HBM tile copies against
    compute across grid steps — without it every step stalls on its loads
    (measured ~4x on the backward at T=2048). ``vmem_limit_bytes`` pins
    the kernel's scoped-VMEM limit (None = the compiler's default)."""
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=vmem_limit_bytes)


def _causal_run(qi, kb, bq, bk):
    """A (qi, kb) tile pair contributes under the causal mask iff its
    lowest k position is <= its highest q position."""
    return kb * bk <= qi * bq + bq - 1


def _tile_mask(s, qi, kb, bq, bk):
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(q_pos >= k_pos, s, -1e30)


# The kernels work in the LOG2 domain: the caller pre-scales q by
# sm_scale*log2(e) ONCE (a [BH,T,D] pass), so the per-tile [BQ,BK] scale
# multiply disappears and exp becomes the VPU's native exp2. True scores
# A = ln2 * s; probabilities exp2(s-m) == exp(A-A_max) are IDENTICAL, and
# the backward's dq/dk epilogues become *ln2 (ln2 * the caller's c folds
# back to sm_scale). The kernels are VPU-softmax-bound at D=128 (measured:
# fwd 41 TF/s vs matmul passes at 157), so per-tile elementwise passes are
# exactly what to shave.
_LN2 = 0.6931471805599453
LOG2E = 1.4426950408889634


def _scores(q, k, qi, kb, *, causal, bq, bk):
    """Masked log2-domain score tile [BQ, BK] (q arrives pre-scaled),
    shared by forward and both backward kernels so the mask math cannot
    desynchronize. The matmul stays in the input dtype (bf16 MXU passes
    with f32 accumulation); only diagonal-crossing tiles pay the
    iota/select mask."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if causal:
        s = jax.lax.cond(
            kb * bk + bk > qi * bq,
            lambda s: _tile_mask(s, qi, kb, bq, bk),
            lambda s: s, s)
    return s


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                 l_ref, *, causal: bool, bq: int, bk: int,
                 qi_axis: int = 1, kb_axis: int = 2,
                 q_scale: Optional[float] = None):
    """Grid (..., qi, kb): one [BQ, D] × [BK, D] tile pair.

    K/V tiles stream through VMEM (no whole-sequence residency); the
    online-softmax state (acc/m/l) persists in scratch across the kb axis,
    and the normalized output plus the row log2-sum-exp2 (saved for the
    backward pass) are written at the last kb step. Above-diagonal tile
    pairs skip all compute under causal.

    ``q_scale``: the packed-qkv path ships RAW q tiles and scales them on
    load (a [BQ,D] pass) instead of pre-scaling the whole tensor; None =
    q already pre-scaled by the caller (the split-q/k/v path).
    """
    qi = pl.program_id(qi_axis)
    kb = pl.program_id(kb_axis)
    n_kb = pl.num_programs(kb_axis)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -1e30)
        l_ref[:] = jnp.zeros_like(l_ref)

    run = _causal_run(qi, kb, bq, bk) if causal else True

    @pl.when(run)
    def _compute():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        if q_scale is not None:
            q = (q.astype(jnp.float32) * q_scale).astype(q_ref.dtype)
        s = _scores(q, k, qi, kb, causal=causal, bq=bq, bk=bk)  # [BQ, BK]
        m_prev = m_ref[:, 0]                             # [BQ]
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp2(s - m_new[:, None])
        alpha = jnp.exp2(m_prev - m_new)
        # Running stats live in lane 0 only (reads are [:, 0]); the full
        # 128-lane broadcast write was two extra [BQ,128] VPU passes per
        # tile (~10% of fwd kernel time on v5e). Only the FINAL lse output
        # below is lane-replicated — that's the wire format the backward's
        # _row_spec tiles expect. (On-chip numerics + bench validated.)
        l_ref[:, :1] = (l_ref[:, 0] * alpha
                        + jnp.sum(p, axis=-1))[:, None]
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:, :1] = m_new[:, None]

    @pl.when(kb == n_kb - 1)
    def _finish():
        l = l_ref[:, 0]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / safe[:, None]).astype(o_ref.dtype)
        if lse_ref is not None:
            # log2 domain, matching the backward's exp2 recompute.
            lse = jnp.where(l == 0.0, -1e30, m_ref[:, 0] + jnp.log2(safe))
            lse_ref[0] = lse[:, None] * jnp.ones_like(lse_ref[0])


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, causal: bool, bq: int, bk: int,
               qi_axis: int = 1, kb_axis: int = 2,
               q_scale: Optional[float] = None,
               dq_scale: float = _LN2):
    """Grid (..., qi, kb): accumulate dq over the kb axis.

    Recomputes the probability tile from (q, k, lse) — the flash-backward
    trade: [BQ, BK] tiles never leave VMEM.
    dA = P ∘ (dO·Vᵀ − Δ), Δ = rowsum(dO ∘ O). Split path: q arrives
    pre-scaled, dq_scale = ln2 (the caller's log2e·sm_scale prescale folds
    the chain rule back to sm_scale). Packed path: q raw + q_scale set,
    dq_scale = sm_scale directly.
    """
    qi = pl.program_id(qi_axis)
    kb = pl.program_id(kb_axis)
    n_kb = pl.num_programs(kb_axis)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = _causal_run(qi, kb, bq, bk) if causal else True

    @pl.when(run)
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        if q_scale is not None:
            q = (q.astype(jnp.float32) * q_scale).astype(q_ref.dtype)
        s = _scores(q, k, qi, kb, causal=causal, bq=bq, bk=bk)
        p = jnp.exp2(s - lse_ref[0][:, :1])              # [BQ, BK]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1])
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == n_kb - 1)
    def _finish():
        dq_ref[0] = (acc_ref[:] * dq_scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, causal: bool,
                bq: int, bk: int, kb_axis: int = 1, qi_axis: int = 2,
                q_scale: Optional[float] = None,
                dk_scale: float = _LN2):
    """Grid (..., kb, qi): accumulate dk/dv for one K/V tile over all
    contributing Q tiles. dV = Pᵀ·dO. Split path: dK = ln2 · dAᵀ·Q_scaled
    (prescaled q makes ln2 the correct chain factor). Packed path: q raw
    (scaled only for the score recompute), dK = sm_scale · dAᵀ·Q."""
    kb = pl.program_id(kb_axis)
    qi = pl.program_id(qi_axis)
    n_qi = pl.num_programs(qi_axis)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = _causal_run(qi, kb, bq, bk) if causal else True

    @pl.when(run)
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        qs = q
        if q_scale is not None:
            qs = (q.astype(jnp.float32) * q_scale).astype(q_ref.dtype)
        s = _scores(qs, k, qi, kb, causal=causal, bq=bq, bk=bk)
        p = jnp.exp2(s - lse_ref[0][:, :1])              # [BQ, BK]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # Pᵀ·dO [BK, D]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1])
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # dAᵀ·Q [BK, D]

    @pl.when(qi == n_qi - 1)
    def _finish():
        dk_ref[0] = (dk_acc[:] * dk_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _dqkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                 causal: bool, bq: int, bk: int,
                 qi_axis: int = 1, kb_axis: int = 2,
                 q_scale: Optional[float] = None,
                 grad_scale: float = _LN2):
    """Fused single-pass backward: dq, dk and dv from ONE visit of each
    (qi, kb) tile pair.

    The split dq / dkv kernels each recompute the probability tile and the
    dO·Vᵀ matmul and each stream q/k/v/do from HBM — and the kernels are
    VPU-softmax-bound (measured fwd 41 vs matmul 157 TF/s), so the second
    exp2 recompute pass is pure waste. Here one grid (…, qi, kb) computes
    s/p/dp/ds once per pair: dq accumulates per-qi in a [BQ, D] scratch
    (written at the kb edge, as before), while dk/dv accumulate into
    full-T [T, D] f32 VMEM scratch across the whole (qi, kb) space and
    are flushed once per (batch, head) at the final step. Halves the
    softmax recompute, the dp matmul and the HBM streaming of the backward
    (7 matmuls + 2 exp2 passes per pair across two kernels -> 5 + 1).
    Costs 2·T·D f32 of VMEM (1 MiB per 2048×128) — callers fall back to
    the split kernels when ``_fused_bwd_fits`` says the residents exceed
    the per-core VMEM budget.
    """
    qi = pl.program_id(qi_axis)
    kb = pl.program_id(kb_axis)
    n_qi = pl.num_programs(qi_axis)
    n_kb = pl.num_programs(kb_axis)

    @pl.when((qi == 0) & (kb == 0))
    def _init_kv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(kb == 0)
    def _init_q():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = _causal_run(qi, kb, bq, bk) if causal else True

    @pl.when(run)
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        qs = q
        if q_scale is not None:
            qs = (q.astype(jnp.float32) * q_scale).astype(q_ref.dtype)
        s = _scores(qs, k, qi, kb, causal=causal, bq=bq, bk=bk)
        p = jnp.exp2(s - lse_ref[0][:, :1])              # [BQ, BK]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1])
        dsc = ds.astype(k.dtype)
        dq_acc[:] += jax.lax.dot_general(
            dsc, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        rows = pl.ds(kb * bk, bk)
        dv_acc[rows, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # Pᵀ·dO
        dk_acc[rows, :] += jax.lax.dot_general(
            dsc, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # dSᵀ·Q

    @pl.when(kb == n_kb - 1)
    def _fin_q():
        dq_ref[0] = (dq_acc[:] * grad_scale).astype(dq_ref.dtype)

    @pl.when((qi == n_qi - 1) & (kb == n_kb - 1))
    def _fin_kv():
        dk_ref[0] = (dk_acc[:] * grad_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _dqkv_packed_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dqkv_ref, dq_acc, dk_acc, dv_acc, *,
                        causal: bool, bq: int, bk: int, d: int,
                        q_scale: float, grad_scale: float):
    """Packed-path fused backward writing the gradient DIRECTLY in the
    projection's packed column layout.

    Grid (B, H, qi, kb); the single output block is head h's full packed
    column stripe ``[1, T, 3D]`` of d_qkv (columns q|k|v), grid-constant
    over (qi, kb) so it lives in VMEM for the whole (batch, head) visit:
    dq rows land at each qi edge, dk/dv flush from the full-T accumulators
    at the end. This removes the stack+reshape interleave the previous
    backward needed (measured ~0.52 ms/layer of concatenate fusions plus
    the copies around three [B,T,H*D] intermediates — the gradient now
    exists in exactly one materialization).
    """
    qi = pl.program_id(2)
    kb = pl.program_id(3)
    n_qi = pl.num_programs(2)
    n_kb = pl.num_programs(3)

    @pl.when((qi == 0) & (kb == 0))
    def _init_kv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(kb == 0)
    def _init_q():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = _causal_run(qi, kb, bq, bk) if causal else True

    @pl.when(run)
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        qs = (q.astype(jnp.float32) * q_scale).astype(q_ref.dtype)
        s = _scores(qs, k, qi, kb, causal=causal, bq=bq, bk=bk)
        p = jnp.exp2(s - lse_ref[0][:, :1])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1])
        dsc = ds.astype(k.dtype)
        dq_acc[:] += jax.lax.dot_general(
            dsc, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        rows = pl.ds(kb * bk, bk)
        dv_acc[rows, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[rows, :] += jax.lax.dot_general(
            dsc, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kb == n_kb - 1)
    def _fin_q():
        dqkv_ref[0, pl.ds(qi * bq, bq), 0:d] = \
            (dq_acc[:] * grad_scale).astype(dqkv_ref.dtype)

    @pl.when((qi == n_qi - 1) & (kb == n_kb - 1))
    def _fin_kv():
        dqkv_ref[0, :, d:2 * d] = \
            (dk_acc[:] * grad_scale).astype(dqkv_ref.dtype)
        dqkv_ref[0, :, 2 * d:3 * d] = dv_acc[:].astype(dqkv_ref.dtype)


# Scoped VMEM one fused-backward kernel may claim. 16 MiB is the v5e
# compiler's own default scoped limit; the fused kernels also pass this
# number as their ``vmem_limit_bytes``, so the gate below and the compiler
# hold the same limit (an override moves both). Read once at import so
# every rank traces the same graph — a trace-time env read could diverge
# across ranks (the HVD_FUSED_PARTS lesson, ADVICE r5).
_VMEM_BUDGET_BYTES = int(os.environ.get("HVD_VMEM_BUDGET_MB", "16")) * 2**20


def _fused_bwd_fits(T: int, D: int, itemsize: int, *, bq: int, bk: int,
                    packed: bool) -> bool:
    """Whether the fused single-pass backward fits the kernel's scoped
    VMEM limit — the gate deciding fused vs split dq/dkv kernels.

    The fused kernel's full-T dk/dv accumulators make its footprint grow
    with sequence length. The sum below is what the v5e compiler
    allocates, checked against it at H=16 for D in {128, 256}, bf16 and
    f32, T = 128..16384 (the smallest ``vmem_limit_bytes`` that compiles
    sits within the margin of this sum; ``tests/test_tpu_compile.py``
    keeps the near-boundary shapes compiling):

    * scratch: dq_acc [bq, D] + dk/dv accumulators 2×[T, D], all f32;
    * output block(s) in the input dtype, DOUBLE-buffered like every
      pipelined operand even though they are grid-constant over a
      (batch, head) visit: packed [T, 3D] vs split dq [bq, D] + full-T
      dk/dv 2×[T, D];
    * streamed input tiles (q/do [bq, D], k/v [bk, D]) and the two f32
      stat tiles, which occupy a full 128-lane tile in VMEM whatever
      ``_STAT_LANES`` says — all double-buffered;
    * margin: three [bq, bk] f32 intermediates (scores/probabilities, dp,
      ds) the compiler keeps on its stack.
    """
    scratch = 4 * (bq * D + 2 * T * D)
    out = (T * 3 * D if packed else (bq + 2 * T) * D) * itemsize
    tiles = ((2 * bq + 2 * bk) * D * itemsize
             + 2 * bq * 128 * 4)
    margin = 3 * bq * bk * 4
    return scratch + 2 * out + 2 * tiles + margin <= _VMEM_BUDGET_BYTES


# Lane width of the per-row stat tensors (lse, delta) on the wire between
# kernels. Only lane 0 carries data; 8 lanes (one f32 sublane tile) keeps
# Mosaic layouts happy while cutting the streamed stat traffic 16x vs the
# old 128-lane replication: at the bench config BH = B*H = 8*16 = 128,
# T = 2048, so a 128-lane f32 stat was 128*2048*128*4 = 134 MB per stat
# per kernel per layer — pure HBM burn for a [BH, T] statistic.
_STAT_LANES = 8


def _row_spec(block_rows, which):
    """BlockSpec for per-row stats [BH, T, _STAT_LANES]; kernels read
    column 0 only."""
    return pl.BlockSpec((1, block_rows, _STAT_LANES), which)


def _fwd_pallas(q, k, v, causal: bool, interpret: bool,
                with_lse: bool = True):
    """q/k/v: [BH, T, D], q PRE-SCALED by sm_scale*log2e ->
    (o [BH, T, D], lse2 [BH, T, _STAT_LANES] f32 | None).

    ``with_lse=False`` (the no-grad primal) drops the lse output — Mosaic
    can't dead-code-eliminate an output buffer, and at long T the f32 lse
    write outweighs the bf16 output itself."""
    BH, T, D = q.shape
    bq = _pick_block(T, _WANT_BQ)
    bk = _pick_block(T, _WANT_BK)
    grid = (BH, T // bq, T // bk)
    base = functools.partial(_attn_kernel, causal=causal, bq=bq, bk=bk)
    if with_lse:
        kernel = base
        out_specs = [
            pl.BlockSpec((1, bq, D), lambda bh, qi, kb: (bh, qi, 0)),
            _row_spec(bq, lambda bh, qi, kb: (bh, qi, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, T, _STAT_LANES), jnp.float32),
        ]
    else:
        def kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
            base(q_ref, k_ref, v_ref, o_ref, None, acc_ref, m_ref, l_ref)
        out_specs = pl.BlockSpec((1, bq, D), lambda bh, qi, kb: (bh, qi, 0))
        out_shape = jax.ShapeDtypeStruct((BH, T, D), q.dtype)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, qi, kb: (bh, kb, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, qi, kb: (bh, kb, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),            # acc
            pltpu.VMEM((bq, 128), jnp.float32),          # running max
            pltpu.VMEM((bq, 128), jnp.float32),          # running sum
        ],
        compiler_params=_grid_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return (out if with_lse else (out, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_core(q, k, v, causal: bool, interpret: bool):
    """q arrives pre-scaled by sm_scale*log2e (see _flash_bhtd); the VJP
    therefore returns dq in the SCALED domain and jax's chain rule through
    the caller's multiply restores the true dq."""
    o, _ = _fwd_pallas(q, k, v, causal, interpret, with_lse=False)
    return o


def _flash_core_fwd(q, k, v, causal, interpret):
    o, lse = _fwd_pallas(q, k, v, causal, interpret)
    # lse is already the narrow [BH, T, _STAT_LANES] wire format; keep it
    # whole in the residuals (slicing to one lane and re-broadcasting in
    # backward would cost two device copies to save 7 f32 lanes).
    return o, (q, k, v, o, lse)


def _flash_core_bwd(causal, interpret, res, do):
    q, k, v, o, lse = res
    BH, T, D = q.shape
    bq = _pick_block(T, _WANT_BQ)
    bk = _pick_block(T, _WANT_BK)
    # Δ_i = Σ_d dO ∘ O — cheap elementwise reduction, XLA fuses it;
    # widened to _STAT_LANES like lse so the kernels read [BQ, 8] tiles.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)              # [BH, T, 1]
    delta = jnp.broadcast_to(delta, (BH, T, _STAT_LANES))
    qkv_spec_q = pl.BlockSpec((1, bq, D), lambda bh, qi, kb: (bh, qi, 0))
    qkv_spec_k = pl.BlockSpec((1, bk, D), lambda bh, qi, kb: (bh, kb, 0))
    if _fused_bwd_fits(T, D, q.dtype.itemsize, bq=bq, bk=bk, packed=False):
        full = pl.BlockSpec((1, T, D), lambda bh, qi, kb: (bh, 0, 0))
        return pl.pallas_call(
            functools.partial(_dqkv_kernel, causal=causal, bq=bq, bk=bk),
            grid=(BH, T // bq, T // bk),
            in_specs=[qkv_spec_q, qkv_spec_k, qkv_spec_k, qkv_spec_q,
                      _row_spec(bq, lambda bh, qi, kb: (bh, qi, 0)),
                      _row_spec(bq, lambda bh, qi, kb: (bh, qi, 0))],
            out_specs=[qkv_spec_q, full, full],
            out_shape=[
                jax.ShapeDtypeStruct((BH, T, D), q.dtype),
                jax.ShapeDtypeStruct((BH, T, D), k.dtype),
                jax.ShapeDtypeStruct((BH, T, D), v.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                            pltpu.VMEM((T, D), jnp.float32),
                            pltpu.VMEM((T, D), jnp.float32)],
            compiler_params=_grid_params(
                ("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_BUDGET_BYTES),
            interpret=interpret,
            name="flash_bwd",
        )(q, k, v, do, lse, delta)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, bq=bq, bk=bk),
        grid=(BH, T // bq, T // bk),
        in_specs=[qkv_spec_q, qkv_spec_k, qkv_spec_k, qkv_spec_q,
                  _row_spec(bq, lambda bh, qi, kb: (bh, qi, 0)),
                  _row_spec(bq, lambda bh, qi, kb: (bh, qi, 0))],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, qi, kb: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_grid_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    # dk/dv iterate the OTHER way: one K/V tile accumulated over Q tiles.
    kv_q = pl.BlockSpec((1, bq, D), lambda bh, kb, qi: (bh, qi, 0))
    kv_k = pl.BlockSpec((1, bk, D), lambda bh, kb, qi: (bh, kb, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, bq=bq, bk=bk),
        grid=(BH, T // bk, T // bq),
        in_specs=[kv_q, kv_k, kv_k, kv_q,
                  _row_spec(bq, lambda bh, kb, qi: (bh, qi, 0)),
                  _row_spec(bq, lambda bh, kb, qi: (bh, qi, 0))],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda bh, kb, qi: (bh, kb, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, kb, qi: (bh, kb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), k.dtype),
            jax.ShapeDtypeStruct((BH, T, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=_grid_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# ---------------------------------------------------------------------------
# Packed-qkv path: consume the fused QKV projection output [B, T, H*3*D]
# (HEAD-major columns, i.e. reshape [B, T, H, 3, D]) DIRECTLY via BlockSpec
# index maps — no [B,T,H,D] -> [BH,T,D] transposes on either side of the
# kernels (measured ~11 ms/step of layout copies at the LM bench config).
# The attention output comes back as [B, T, H*D], exactly what the output
# projection consumes. q is scaled inside the kernels (a [BQ,D] pass).
# ---------------------------------------------------------------------------


def _qkv_specs(H, D, bq, bk):
    """BlockSpecs into the packed [B, T, H*3*D] array for grid
    (B, H, qi, kb): column block (h*3 + kind) is head h's q/k/v slice."""
    q = pl.BlockSpec((1, bq, D), lambda b, h, qi, kb: (b, qi, h * 3 + 0))
    k = pl.BlockSpec((1, bk, D), lambda b, h, qi, kb: (b, kb, h * 3 + 1))
    v = pl.BlockSpec((1, bk, D), lambda b, h, qi, kb: (b, kb, h * 3 + 2))
    return q, k, v


def _fwd_pallas_qkv(qkv, H, D, causal, sm_scale, interpret,
                    with_lse=True):
    B, T, _ = qkv.shape
    bq = _pick_block(T, _WANT_BQ)
    bk = _pick_block(T, _WANT_BK)
    grid = (B, H, T // bq, T // bk)
    c = sm_scale * LOG2E
    base = functools.partial(_attn_kernel, causal=causal, bq=bq, bk=bk,
                             qi_axis=2, kb_axis=3, q_scale=c)
    sq, sk, sv = _qkv_specs(H, D, bq, bk)
    o_spec = pl.BlockSpec((1, bq, D), lambda b, h, qi, kb: (b, qi, h))
    # Stats shaped [B*H, T, S]: index maps may do arithmetic on grid ids.
    stat_spec = pl.BlockSpec((1, bq, _STAT_LANES),
                             lambda b, h, qi, kb: (b * H + h, qi, 0))
    if with_lse:
        kernel = base
        out_specs = [o_spec, stat_spec]
        out_shape = [
            jax.ShapeDtypeStruct((B, T, H * D), qkv.dtype),
            jax.ShapeDtypeStruct((B * H, T, _STAT_LANES), jnp.float32),
        ]
    else:
        def kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
            base(q_ref, k_ref, v_ref, o_ref, None, acc_ref, m_ref, l_ref)
        out_specs = o_spec
        out_shape = jax.ShapeDtypeStruct((B, T, H * D), qkv.dtype)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[sq, sk, sv],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=_grid_params(
            ("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(qkv, qkv, qkv)
    return (out if with_lse else (out, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _flash_qkv_core(qkv, H: int, causal: bool, sm_scale: float,
                    interpret: bool):
    D = qkv.shape[-1] // (3 * H)
    o, _ = _fwd_pallas_qkv(qkv, H, D, causal, sm_scale, interpret,
                           with_lse=False)
    return o


def _flash_qkv_core_fwd(qkv, H, causal, sm_scale, interpret):
    D = qkv.shape[-1] // (3 * H)
    o, lse = _fwd_pallas_qkv(qkv, H, D, causal, sm_scale, interpret)
    return o, (qkv, o, lse)


def _flash_qkv_core_bwd(H, causal, sm_scale, interpret, res, do):
    qkv, o, lse = res
    B, T, _ = qkv.shape
    D = qkv.shape[-1] // (3 * H)
    bq = _pick_block(T, _WANT_BQ)
    bk = _pick_block(T, _WANT_BK)
    c = sm_scale * LOG2E
    delta = jnp.sum(
        (do.astype(jnp.float32) * o.astype(jnp.float32)).reshape(
            B, T, H, D),
        axis=-1)                                        # [B, T, H]
    delta = jnp.broadcast_to(
        delta.transpose(0, 2, 1).reshape(B * H, T, 1),
        (B * H, T, _STAT_LANES))
    sq, sk, sv = _qkv_specs(H, D, bq, bk)
    do_q = pl.BlockSpec((1, bq, D), lambda b, h, qi, kb: (b, qi, h))
    stat_q = pl.BlockSpec((1, bq, _STAT_LANES),
                          lambda b, h, qi, kb: (b * H + h, qi, 0))
    if _fused_bwd_fits(T, D, qkv.dtype.itemsize, bq=bq, bk=bk, packed=True):
        packed = pl.BlockSpec((1, T, 3 * D), lambda b, h, qi, kb: (b, 0, h))
        d_qkv = pl.pallas_call(
            functools.partial(_dqkv_packed_kernel, causal=causal, bq=bq,
                              bk=bk, d=D, q_scale=c, grad_scale=sm_scale),
            grid=(B, H, T // bq, T // bk),
            in_specs=[sq, sk, sv, do_q, stat_q, stat_q],
            out_specs=packed,
            out_shape=jax.ShapeDtypeStruct((B, T, H * 3 * D), qkv.dtype),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                            pltpu.VMEM((T, D), jnp.float32),
                            pltpu.VMEM((T, D), jnp.float32)],
            compiler_params=_grid_params(
                ("parallel", "parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_BUDGET_BYTES),
            interpret=interpret,
            name="flash_bwd",
        )(qkv, qkv, qkv, do, lse, delta)
        return (d_qkv,)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, bq=bq, bk=bk,
                          qi_axis=2, kb_axis=3, q_scale=c,
                          dq_scale=sm_scale),
        grid=(B, H, T // bq, T // bk),
        in_specs=[sq, sk, sv, do_q, stat_q, stat_q],
        out_specs=pl.BlockSpec((1, bq, D),
                               lambda b, h, qi, kb: (b, qi, h)),
        out_shape=jax.ShapeDtypeStruct((B, T, H * D), qkv.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_grid_params(
            ("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qkv, qkv, qkv, do, lse, delta)

    # dk/dv iterate the OTHER way: grid (B, H, kb, qi).
    kv_sq = pl.BlockSpec((1, bq, D), lambda b, h, kb, qi: (b, qi, h * 3))
    kv_sk = pl.BlockSpec((1, bk, D),
                         lambda b, h, kb, qi: (b, kb, h * 3 + 1))
    kv_sv = pl.BlockSpec((1, bk, D),
                         lambda b, h, kb, qi: (b, kb, h * 3 + 2))
    kv_do = pl.BlockSpec((1, bq, D), lambda b, h, kb, qi: (b, qi, h))
    kv_stat = pl.BlockSpec((1, bq, _STAT_LANES),
                           lambda b, h, kb, qi: (b * H + h, qi, 0))
    kv_out = pl.BlockSpec((1, bk, D), lambda b, h, kb, qi: (b, kb, h))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, bq=bq, bk=bk,
                          kb_axis=2, qi_axis=3, q_scale=c,
                          dk_scale=sm_scale),
        grid=(B, H, T // bk, T // bq),
        in_specs=[kv_sq, kv_sk, kv_sv, kv_do, kv_stat, kv_stat],
        out_specs=[kv_out, kv_out],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, H * D), qkv.dtype),
            jax.ShapeDtypeStruct((B, T, H * D), qkv.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=_grid_params(
            ("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qkv, qkv, qkv, do, lse, delta)
    # Interleave back into the packed head-major (H, 3, D) column layout.
    d_qkv = jnp.stack(
        [g.reshape(B, T, H, D) for g in (dq, dk, dv)],
        axis=3).reshape(B, T, H * 3 * D)
    return (d_qkv,)


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
                                             "interpret"))
def _flash_bhtd(q, k, v, causal: bool, sm_scale: float, interpret: bool):
    """q/k/v: [BH, T, D] -> [BH, T, D]. Differentiable (custom VJP with
    Pallas dq/dkv kernels — the score matrix never touches HBM in either
    direction). q is pre-scaled here (one cheap [BH,T,D] pass) so the
    kernels run scale-free in the log2 domain; jax's chain rule through
    this multiply restores the true dq from the kernel's scaled-domain
    output."""
    q = (q.astype(jnp.float32) * (sm_scale * LOG2E)).astype(q.dtype)
    return _flash_core(q, k, v, causal, interpret)


# Above roughly this many bytes of [B, H, T, T] f32 scores, the dense XLA
# path risks HBM exhaustion and the blockwise kernel wins by never
# materializing them. Measured on a v5e chip (B=1 H=8 D=128, causal,
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
                    interpret: Optional[bool] = None):
    """Multi-head attention: XLA by default, Pallas kernel for long context.

    Args:
      q, k, v: [B, T, H, D].
      causal: apply the causal mask.
      sm_scale: softmax scale (default 1/sqrt(D)).
      backend: "auto" (XLA unless the score tensor would exceed ~4 GiB —
        measured on the target platform XLA's fused attention outruns
        Mosaic until memory becomes the binding constraint), "pallas", or
        "xla".
      interpret: force kernel interpreter mode (defaults to True off-TPU).

    Differentiable on every path (the Pallas path via a custom VJP whose
    dq/dk/dv are themselves Pallas kernels). The kernel requires T
    divisible by 128 and D a multiple of 128; other shapes always take the
    XLA path.
    """
    B, T, H, D = q.shape
    if sm_scale is None:
        sm_scale = float(D) ** -0.5
    tilable = qkv_flash_tilable(T, D)
    if backend == "auto":
        score_bytes = 4 * B * H * T * T
        backend = "pallas" if (tilable
                               and score_bytes > _SCORE_BYTES_CUTOVER) \
            else "xla"
    if backend == "xla" or not tilable:
        return _xla_attention(q, k, v, causal, sm_scale)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def to_bhtd(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)

    out = _flash_bhtd(to_bhtd(q), to_bhtd(k), to_bhtd(v), causal, sm_scale,
                      interpret)
    return out.reshape(B, H, T, D).transpose(0, 2, 1, 3)


def _xla_attention(q, k, v, causal, sm_scale):
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * sm_scale
    if causal:
        pos = jnp.arange(q.shape[1])
        scores = jnp.where(pos[:, None] >= pos[None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
