"""Pallas kernels of the gated delta rule's walk over the chunks
(``ops/gated_delta.py`` has the rule and its chunk-local stage).

Grid (batch x head, blocks of chunks); the chunk axis is sequential and the
state ``S`` (the backward's ``dS``) [dk, dv] float32 is a VMEM scratch that
lives across it, so the state never goes through HBM between chunks. A grid
step works ``_BLOCK`` chunks one after the other (a step of this grid costs
about a third of a microsecond before it computes anything, and one chunk
is four small products). The forward writes each chunk's START state out
once, in the activations' dtype: that is what the backward walks back
from.

Kernel names (``pallas_call(name=)``; a device trace and the compiled HLO
find the kernels by them, so they are API): ``gdn_fwd`` and ``gdn_bwd``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _dot, _grid_params

_BLOCK = 8           # chunks worked in one grid step, at most
_VMEM_LIMIT = 64 * 2 ** 20


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _block(n: int) -> int:
    return next(b for b in (_BLOCK, 4, 2, 1) if n % b == 0)


def _fwd_kernel(qg_ref, kd_ref, w_ref, u_ref, a_ref, e_ref, o_ref, st_ref,
                s_ref, *, block: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    dt = u_ref.dtype
    for j in range(block):
        S = s_ref[...]
        s = S.astype(dt)
        st_ref[j] = s
        un = (u_ref[j].astype(jnp.float32)
              - _dot(w_ref[j], s, 1, 0)).astype(dt)
        o_ref[j] = (_dot(qg_ref[j], s, 1, 0)
                    + _dot(a_ref[j], un, 1, 0)).astype(o_ref.dtype)
        s_ref[...] = S * e_ref[j] + _dot(kd_ref[j], un, 0, 0)


def _bwd_kernel(qg_ref, kd_ref, w_ref, u_ref, a_ref, e_ref, st_ref, do_ref,
                dqg_ref, dkd_ref, dw_ref, du_ref, da_ref, de_ref, ds_ref, *,
                block: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    dt = u_ref.dtype
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
        de_ref[j] = jnp.broadcast_to(
            jnp.sum(jnp.sum(s.astype(jnp.float32) * dS, axis=0,
                            keepdims=True), axis=1,
                    keepdims=True), de_ref.shape[1:])
        ds_ref[...] = (dS * e_ref[j] + _dot(qg_ref[j], do, 0, 0)
                       - _dot(w_ref[j], dund, 0, 0))


def _flat(x):
    """[B, H, N, ...] -> [B*H, N, ...]."""
    return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])


def _lanes_of(e_last, dv: int):
    """e^{gam_C} [B, H, N] as rows [B*H, N, 1, dv] that multiply a state's
    rows without a lane broadcast."""
    return jnp.broadcast_to(_flat(e_last)[..., None, None],
                            (e_last.shape[0] * e_last.shape[1],
                             e_last.shape[2], 1, dv)).astype(jnp.float32)


def _spec(block: int, *tail, at):
    return pl.BlockSpec((None, block, *tail),
                        lambda b, n: (b, at(n)) + (0,) * len(tail))


def scan_fwd(qg, kd, w, u, aqk, e_last):
    """As ``gated_delta.scan_fwd_xla``: (o, chunk-start states)."""
    B, H, N, C, dk = qg.shape
    dv = u.shape[-1]
    blk = _block(N)
    spec = functools.partial(_spec, blk, at=lambda n: n)
    o, states = pl.pallas_call(
        functools.partial(_fwd_kernel, block=blk), grid=(B * H, N // blk),
        name="gdn_fwd", interpret=_interpret(),
        compiler_params=_grid_params(("parallel", "arbitrary"), _VMEM_LIMIT),
        in_specs=[spec(C, dk), spec(C, dk), spec(C, dk), spec(C, dv),
                  spec(C, C), spec(1, dv)],
        out_specs=[spec(C, dv), spec(dk, dv)],
        out_shape=[jax.ShapeDtypeStruct((B * H, N, C, dv), u.dtype),
                   jax.ShapeDtypeStruct((B * H, N, dk, dv), u.dtype)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
    )(*(_flat(x) for x in (qg, kd, w, u, aqk)), _lanes_of(e_last, dv))
    return o.reshape(B, H, N, C, dv), states.reshape(B, H, N, dk, dv)


def scan_bwd(qg, kd, w, u, aqk, e_last, states, do):
    """As ``gated_delta.scan_bwd_xla``: the cotangents of the six inputs."""
    B, H, N, C, dk = qg.shape
    dv = u.shape[-1]
    blk = _block(N)
    last = N // blk - 1
    spec = functools.partial(_spec, blk, at=lambda n: last - n)
    dt = u.dtype

    def like(*tail, dtype=dt):
        return jax.ShapeDtypeStruct((B * H, N, *tail), dtype)
    *grads, de = pl.pallas_call(
        functools.partial(_bwd_kernel, block=blk), grid=(B * H, N // blk),
        name="gdn_bwd", interpret=_interpret(),
        compiler_params=_grid_params(("parallel", "arbitrary"), _VMEM_LIMIT),
        in_specs=[spec(C, dk), spec(C, dk), spec(C, dk), spec(C, dv),
                  spec(C, C), spec(1, dv), spec(dk, dv), spec(C, dv)],
        out_specs=[spec(C, dk), spec(C, dk), spec(C, dk), spec(C, dv),
                   spec(C, C), spec(1, dv)],
        out_shape=[like(C, dk), like(C, dk), like(C, dk), like(C, dv),
                   like(C, C), like(1, dv, dtype=jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
    )(*(_flat(x) for x in (qg, kd, w, u, aqk)), _lanes_of(e_last, dv),
      _flat(states), _flat(do))
    return tuple(g.reshape(B, H, *g.shape[1:]) for g in grads) \
        + (de[:, :, 0, 0].reshape(B, H, N),)
