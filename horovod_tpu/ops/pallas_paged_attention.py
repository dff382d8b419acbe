"""Pallas paged decode-attention — the decode-path sibling of the
training flash kernels in :mod:`.pallas_attention`.

One query token per slot attends over that slot's KV *blocks*, gathered
directly from the paged pool via the block table: the grid walks
``(slot, logical_block)`` and a scalar-prefetched block table resolves
each logical block to its physical pool index INSIDE the BlockSpec index
map — the kernel never materializes the per-slot
``[max_blocks·block_size, H, dh]`` contiguous view the pure-lax fallback
gathers (at real configs that view is the whole cache re-laid-out per
step; the kernel streams exactly the blocks each slot owns). Each program
takes ALL heads of one block: the q/out block is ``[H, d]`` and the K/V
block ``[bs, H, d]``, so the last two block dims are the arrays' full
``(H, d)`` extent — the TPU's (8, 128) tiling rule for block shapes — and
one K/V fetch is a contiguous ``bs·H·d`` run of the pool. Online softmax
(running max/sum per head, fp32 accumulation) across the block axis,
per-slot length masking, blocks past the slot's position skipped
entirely.

The engine turns the kernel on only when asked (``paged_kernel=True``)
and REFUSES at construction when :func:`paged_attention_supported` says
the shapes cannot run on the backend (``d_head`` a lane multiple on real
TPUs; anything goes in interpreter mode). The pure-lax gather path is
the bit-identity-bearing reference; :func:`paged_attention_reference` IS
its math. ``tests/test_paged_kv.py`` pins kernel-vs-reference allclose on
CPU (interpret mode executes the same kernel program the TPU would run)
and ``tests/test_tpu_compile.py`` compiles it for the described v5e.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def paged_attention_supported(d_head: int, block_size: int,
                              interpret: Optional[bool] = None) -> bool:
    """Whether the kernel path runs these shapes: interpreter mode (CPU
    tests) takes anything; a real TPU needs a lane-aligned ``d_head``
    (heads and block rows ride whole in every block, so neither is
    constrained)."""
    del block_size  # whole blocks per program: any size tiles
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if interpret:
        return True
    return d_head % 128 == 0


def _paged_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, bs: int, scale: float):
    """Grid (slot, logical_block): the slot's [H, d] query rows against
    one [bs, H, d] K/V block (resolved physical by the index maps).
    Everything stays in the [H, d] tile layout — scores are a lane
    reduction kept as [bs, H, 1], probabilities broadcast back over the
    lanes — so no in-kernel transpose is needed. Softmax state (acc/m/l)
    persists in scratch across the block axis; blocks entirely past the
    slot's position — and every block of an inactive (position < 0) slot
    — skip all compute, and the normalized output is written at the last
    block step (zeros for a fully-masked row, via the safe divide)."""
    s = pl.program_id(0)
    b = pl.program_id(1)
    n_b = pl.num_programs(1)

    @pl.when(b == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -1e30)
        l_ref[:] = jnp.zeros_like(l_ref)

    pos = pos_ref[s]

    @pl.when((pos >= 0) & (b * bs <= pos))
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale            # [H, d]
        k = k_ref[0].astype(jnp.float32)                    # [bs, H, d]
        v = v_ref[0].astype(jnp.float32)
        sc = jnp.sum(q[None] * k, axis=-1, keepdims=True)   # [bs, H, 1]
        kpos = b * bs + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
        sc = jnp.where(kpos <= pos, sc, -1e30)
        m_prev = m_ref[:]                                   # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0))
        p = jnp.exp(sc - m_new[None])                       # [bs, H, 1]
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=0)
        acc_ref[:] = acc_ref[:] * alpha + jnp.sum(p * v, axis=0)  # [H, d]
        m_ref[:] = m_new

    @pl.when(b == n_b - 1)
    def _finish():
        l = l_ref[:]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _paged_call(q, k_pool, v_pool, block_tables, positions,
                sm_scale: float, interpret: bool):
    S, H, d = q.shape
    bs = k_pool.shape[1]
    nb = block_tables.shape[1]
    kv_spec = pl.BlockSpec((1, bs, H, d),
                           lambda s, b, tbl, pos: (tbl[s, b], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, nb),
        in_specs=[
            pl.BlockSpec((1, H, d), lambda s, b, tbl, pos: (s, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, H, d), lambda s, b, tbl, pos: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, d), jnp.float32),        # acc
            pltpu.VMEM((H, 1), jnp.float32),        # running max
            pltpu.VMEM((H, 1), jnp.float32),        # running sum
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, bs=bs, scale=sm_scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, positions, q, k_pool, v_pool)


def paged_decode_attention(q, k_pool, v_pool, block_tables, positions, *,
                           sm_scale: Optional[float] = None,
                           interpret: Optional[bool] = None):
    """Decode attention straight from the paged pool.

    Args:
      q: [S, H, d] — one query token per slot.
      k_pool, v_pool: [n_blocks, block_size, H, d] — ONE layer's view of
        the pool (callers index ``cache["k"][layer]``).
      block_tables: [S, max_blocks] int32 physical block per logical
        block, trash-padded past each slot's allocation.
      positions: [S] int32 — attend keys ``0..positions[s]`` inclusive
        (the just-written token); ``< 0`` = inactive row (output zeros).
      sm_scale: softmax scale (default ``1/sqrt(d)``).
      interpret: force interpreter mode (defaults to True off-TPU).

    Returns [S, H, d] in ``q.dtype``. Forward-only (decode never
    differentiates); allclose-pinned against
    :func:`paged_attention_reference`.
    """
    S, H, d = q.shape
    if sm_scale is None:
        sm_scale = float(d) ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not paged_attention_supported(d, k_pool.shape[1],
                                     interpret=interpret):
        raise ValueError(
            f"paged_decode_attention needs d_head%128==0 on TPU; got "
            f"d_head={d} (use the lax gather path, kernel=False)")
    return _paged_call(q, k_pool, v_pool,
                       jnp.asarray(block_tables, jnp.int32),
                       jnp.asarray(positions, jnp.int32),
                       float(sm_scale), bool(interpret))


def paged_attention_reference(q, k_pool, v_pool, block_tables, positions,
                              sm_scale: Optional[float] = None):
    """The pure-lax gather fallback's math, standalone: gather each
    slot's blocks into the contiguous [S, M, H, d] view and run the
    ``_cached_attention`` einsum (f32 scores, -1e30 mask, f32 softmax) —
    the same function the contiguous cache path computes, which is the
    whole bit-identity story. Inactive rows (positions < 0) return
    zeros, matching the kernel."""
    S, H, d = q.shape
    nb = block_tables.shape[1]
    bs = k_pool.shape[1]
    if sm_scale is None:
        sm_scale = float(d) ** -0.5
    kg = k_pool[block_tables].reshape(S, nb * bs, H, d)
    vg = v_pool[block_tables].reshape(S, nb * bs, H, d)
    s = jnp.einsum("shd,smhd->shm", q.astype(jnp.float32),
                   kg.astype(jnp.float32)) * sm_scale
    m = jnp.arange(nb * bs, dtype=jnp.int32)
    s = jnp.where(m[None, None, :] <= positions[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("shm,smhd->shd", p, vg.astype(jnp.float32))
    out = jnp.where(positions[:, None, None] >= 0, out, 0.0)
    return out.astype(q.dtype)
