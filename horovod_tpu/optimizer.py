"""DistributedOptimizer and variable broadcast — the framework adapter layer.

Reference parity
----------------
* ``hvd.DistributedOptimizer`` wraps any ``tf.train.Optimizer`` and
  allreduces each gradient before the wrapped optimizer applies it, only when
  ``size() > 1`` (``horovod/tensorflow/__init__.py:127-226``); the Keras
  variant dynamically subclasses the user's optimizer class so checkpoints
  restore without Horovod installed (``horovod/keras/__init__.py:66-87``).
* ``hvd.broadcast_global_variables(root)`` = grouped assign of
  ``broadcast(var, root)`` over every variable
  (``horovod/tensorflow/__init__.py:82-90``);
  ``BroadcastGlobalVariablesHook`` runs it right after session creation
  (``__init__.py:93-124``).

TPU-native design
-----------------
The optimizer layer is an **optax gradient transformation**: composable,
functional, and jit-traceable. ``DistributedOptimizer(opt)`` returns an optax
``GradientTransformation`` whose ``update`` first allreduces gradients over
the ``"hvd"`` ICI axis — with reference-semantics fusion bucketing
(64 MiB / same-dtype / order-preserving, see ``ops/fusion.py``) — then
defers to the wrapped transformation. Sparse gradients
(:class:`~horovod_tpu.ops.sparse.IndexedSlices` leaves) take the
two-allgather path (``horovod/tensorflow/__init__.py:61-72``) unless
``sparse_as_dense=True`` densifies them first.

Because optax state is a pure pytree, the Keras "dynamic subclass"
checkpoint-compatibility trick has a simpler equivalent: the wrapped
transformation's state **is** the inner optimizer's state, unchanged, so
checkpoints restore with plain optax, without this framework installed.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from . import runtime
from .ops.collectives import broadcast as _broadcast
from .ops.fusion import (ZeroPlan, fused_allgather_params, fused_allreduce,
                         fused_reduce_scatter, plan_exchange, plan_grad_sync,
                         plan_zero, resolve_wire_dtype, shard_params,
                         wire_dtype_name, zero_emit_order, zero_stack_global,
                         zero_stacked_spec, zero_unstack_global)
from .runtime import AXIS
from .ops.sparse import IndexedSlices, allreduce_indexed_slices
from .utils import config as _config
from .utils.compat import is_tracer


def _is_sparse_leaf(x) -> bool:
    return isinstance(x, IndexedSlices)


class Compression:
    """Gradient compression for the cross-chip allreduce.

    TPU-era extra (no analog in reference v0.11.2; later Horovod grew
    ``Compression.fp16``): ``Compression.bf16`` casts float gradients wider
    than 16 bits to bfloat16 — the MXU/ICI-native 16-bit type — before the
    fused allreduce and restores the original dtype after, halving
    interconnect bytes per step. Accumulation inside the XLA all-reduce is
    f32 on TPU, so the loss of precision is the single round-trip cast.

    Prefer ``wire_dtype=`` for new code: it casts at the BUCKET level
    (the fusion plan is unchanged, scales are applied in fp32, and the
    reduced result returns to fp32 before anything downstream touches
    it), adds an ``fp8`` format, and composes with ``zero=True`` — on the
    ZeRO plane ``Compression.bf16`` is accepted as an alias for
    ``wire_dtype="bf16"`` (see :func:`DistributedOptimizer`).
    """

    class none:  # noqa: N801 — enum-style namespace
        @staticmethod
        def compress(t):
            return t, None

        @staticmethod
        def decompress(t, ctx):
            return t

    class bf16:  # noqa: N801
        @staticmethod
        def compress(t):
            if (hasattr(t, "dtype")
                    and jnp.issubdtype(t.dtype, jnp.floating)
                    and jnp.dtype(t.dtype).itemsize > 2):
                return t.astype(jnp.bfloat16), t.dtype
            return t, None

        @staticmethod
        def decompress(t, ctx):
            return t.astype(ctx) if ctx is not None else t


# ---------------------------------------------------------------------------
# ZeRO-1 sharded optimizer (Rajbhandari et al. 2020 stage 1; Xu et al. 2020's
# weight-update sharding): every rank holds 1/N of the optimizer state, the
# gradient exchange becomes reduce-scatter + all-gather over the same fused
# buckets (same bytes on the wire as the all-reduce), and the optimizer math
# runs on 1/N of the elements. See ops/fusion.py for the bucket plane and
# docs/performance.md for when to flip it on.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ZeroShardedState:
    """Rank-sharded optimizer state: the wrapped transformation's state over
    this world's flat bucket shards, plus the static bucket layout.

    ``inner`` is the wrapped optax state whose array leaves live in the
    stacked-shard layout ``[nshards, shard_len]`` — the leading axis is
    split one shard per rank over the world mesh (``P(AXIS)``), so each
    device holds ``1/nshards`` of every optimizer-state array. In a tpurun
    env-world each independent process holds only its OWN shard
    (``[1, shard_len]`` locally). Scalar leaves (e.g. Adam's step count)
    stay replicated. ``plan`` (static aux data) records the bucket layout
    so update/checkpoint can rebuild full trees.
    """

    inner: Any
    plan: ZeroPlan


jax.tree_util.register_dataclass(
    ZeroShardedState, data_fields=("inner",), meta_fields=("plan",))


def _zero_shard_leaf_buckets(inner, plan: ZeroPlan) -> List[Optional[int]]:
    """Map each flattened leaf of ``inner`` to the bucket whose stacked
    shard array it mirrors, or None for non-shard leaves (scalars).

    Elementwise optax transformations keep per-parameter state in subtrees
    shaped exactly like the params they were initialized on — here, the
    tuple of stacked ``(nshards, shard_len_i)`` bucket arrays — and those
    subtrees flatten contiguously in bucket order. Two buckets can share a
    stacked shape while differing in true (unpadded) length, so shape
    alone cannot identify a bucket; position within a contiguous run can,
    and is what checkpoint canonicalization needs to strip each bucket's
    padding correctly (:func:`zero_to_canonical`).
    """
    shard_shapes = plan.shard_shapes()
    nb = len(shard_shapes)
    out: List[Optional[int]] = []
    run = 0  # next bucket index expected in the current params-shaped run
    for leaf in jax.tree_util.tree_leaves(inner):
        shape = tuple(np.shape(leaf))
        if nb and shape == shard_shapes[run]:
            out.append(run)
            run = (run + 1) % nb
        elif nb and shape == shard_shapes[0]:
            out.append(0)
            run = 1 % nb
        else:
            out.append(None)
            run = 0
    return out


def zero_to_canonical(state: ZeroShardedState, *,
                      placeholders: bool = False) -> ZeroShardedState:
    """World-agnostic checkpoint form of a ZeRO state: every stacked
    ``[nshards, shard_len]`` shard leaf becomes the flat UNPADDED
    ``[true_len]`` vector, which is identical regardless of the world size
    that wrote it — so a checkpoint saved at world N restores (re-sharded)
    at world M. Scalar leaves pass through. ``placeholders=True`` emits
    ``np.zeros`` stand-ins (for building orbax restore templates without
    touching device data). No-op for env-world local-shard states (their
    leaves are ``[1, shard_len]`` with ``nshards > 1`` — only this rank's
    slice exists locally, so there is nothing world-agnostic to write).

    Hybrid (N-D mesh) plans extend the form to 2-D: the canonical vector
    is the flat concatenation of the bucket's GLOBAL leaves — the
    per-tp-coordinate dp stacks are unstacked and reassembled into the
    unsharded arrays first (:func:`~horovod_tpu.ops.fusion.
    zero_unstack_global`) — so the bytes are identical across BOTH world
    sizes and (dp, tp) mesh reshapes: a ``(dp=4, tp=2)`` checkpoint
    restores at ``(dp=2, tp=4)``."""
    plan = state.plan
    ids = _zero_shard_leaf_buckets(state.inner, plan)
    leaves, treedef = jax.tree_util.tree_flatten(state.inner)
    canon_sizes = plan.canonical_sizes()
    out = []
    for leaf, b in zip(leaves, ids):
        if b is None:
            out.append(leaf)
        elif placeholders:
            out.append(np.zeros((canon_sizes[b],),
                                np.dtype(plan.dtypes[plan.buckets[b][0]])))
        elif plan.hybrid:
            globals_ = zero_unstack_global(np.asarray(leaf), plan, b)
            out.append(np.concatenate([np.ravel(g) for g in globals_])
                       if len(globals_) > 1 else np.ravel(globals_[0]))
        else:
            out.append(jnp.reshape(leaf, (-1,))[:plan.sizes[b]])
    return ZeroShardedState(inner=treedef.unflatten(out), plan=plan)


def zero_from_canonical(canonical: Any,
                        template: ZeroShardedState) -> ZeroShardedState:
    """Re-shard a canonical (flat, unpadded) ZeRO state onto ``template``'s
    world: each flat leaf is zero-padded to the template plan's padded
    length, stacked ``[nshards, shard_len]``, and placed with the template
    leaf's sharding when it has one (the live state's ``P(AXIS)`` layout).
    ``canonical`` may be the structurally-restored orbax tree (containers
    as dicts/lists) — leaves are paired positionally with the template's.
    """
    plan = template.plan
    ids = _zero_shard_leaf_buckets(template.inner, plan)
    t_leaves, treedef = jax.tree_util.tree_flatten(template.inner)
    c_leaves = jax.tree_util.tree_leaves(canonical)
    if len(c_leaves) != len(t_leaves):
        raise ValueError(
            f"ZeRO state mismatch: checkpoint has {len(c_leaves)} "
            f"optimizer-state leaves, this world's template has "
            f"{len(t_leaves)} — was the checkpoint written by a different "
            f"optimizer?")
    canon_sizes = plan.canonical_sizes()
    out = []
    for c, t, b in zip(c_leaves, t_leaves, ids):
        if b is None:
            out.append(c)
            continue
        flat = np.asarray(c).reshape(-1)
        if flat.size != canon_sizes[b]:
            raise ValueError(
                f"ZeRO shard length mismatch: checkpoint leaf has "
                f"{flat.size} elements, this world's bucket {b} expects "
                f"{canon_sizes[b]} — the fusion bucket plan differs "
                f"(HOROVOD_FUSION_THRESHOLD and the mesh AXIS NAMES must "
                f"match the saving run, and the model must be unchanged; "
                f"dp/tp SIZE reshapes are fine, dropping or adding an "
                f"axis name changes the spec groups and is not)")
        if plan.hybrid:
            # 2-D canonical: split the flat global vector back into the
            # bucket's global leaves, then re-stack for THIS mesh's
            # (dp, tp) split.
            globals_full = [None] * len(plan.shapes)
            off = 0
            for j in plan.buckets[b]:
                n = int(np.prod(plan.global_shapes[j]))
                globals_full[j] = flat[off:off + n].reshape(
                    plan.global_shapes[j])
                off += n
            stacked = zero_stack_global(globals_full, plan, b)
        else:
            pad = plan.padded[b] - plan.sizes[b]
            if pad:
                flat = np.concatenate([flat, np.zeros((pad,), flat.dtype)])
            stacked = flat.reshape(plan.nshards, plan.shard_len(b))
        if isinstance(t, jax.Array):
            stacked = jax.device_put(stacked, t.sharding)
        out.append(stacked)
    return ZeroShardedState(inner=treedef.unflatten(out), plan=plan)


def _axes_bound(names) -> bool:
    """True when every named mesh axis is bound in the current trace —
    the generalization of ``runtime._in_world_trace`` to hybrid meshes."""
    try:
        for n in ((names,) if isinstance(names, str) else tuple(names)):
            jax.lax.axis_size(n)
        return True
    except Exception:  # noqa: BLE001 — unbound axis raises NameError-ish
        return False


def partition_optimizer(optimizer: optax.GradientTransformation,
                        *,
                        average: bool = True,
                        fusion_threshold: Optional[int] = None,
                        accum_steps: int = 1,
                        wire_dtype=None,
                        overlap: bool = False,
                        axis_name: str = AXIS,
                        mesh=None,
                        param_specs=None,
                        scatter_axis: str = "dp",
                        skip_axes: Tuple[str, ...] = ()
                        ) -> optax.GradientTransformation:
    """Wrap an optax optimizer with ZeRO-1 sharded updates.

    ``init_fn`` materializes only this rank's optimizer-state shard
    (``1/size()`` of the bytes per device — single-controller worlds place
    the stacked shards ``P(AXIS)`` over the mesh, env-world processes
    build just their own slice). ``update_fn`` reduce-scatters the
    gradient tree over the fused buckets
    (:func:`~horovod_tpu.ops.fusion.fused_reduce_scatter`), runs the
    wrapped transformation on the local flat shards, and all-gathers the
    updated shards back into a full update tree — so
    ``optax.apply_updates(params, updates)`` keeps its contract and every
    replica ends bit-identical.

    Constraints (raised eagerly): dense gradients only (no
    ``IndexedSlices`` leaves — densify upstream), and the wrapped
    transformation must be ELEMENTWISE over its parameters (sgd, momentum,
    adam, adamw, ... — anything whose update of element ``i`` depends only
    on element ``i``'s gradient/state/param): the optimizer math sees flat
    bucket shards, not the original tree, so per-layer logic (multi-
    transform masks keyed on the tree, global-norm clipping) would compute
    per-SHARD instead. ``update`` must run inside the compiled step
    (``make_train_step(zero=True)``) when the world is larger than one.

    ``wire_dtype`` (``"bf16"``/``"fp8"``) runs the reduce-scatter in
    reduced precision with the received shard cast back to fp32 before
    the optax update (fp32 shard accumulation); the update all-gather
    stays at full precision so every replica still ends bit-identical.
    ``overlap=True`` issues the per-bucket scatters in backward-readiness
    order behind ``optimization_barrier`` pins (bucket membership — and
    therefore the sharded-state layout and checkpoint canonical form —
    never changes); pair it with ``make_train_step(overlap=True)``, which
    supplies the backward-completion order probe.

    ``mesh=`` + ``param_specs=`` switch to the N-D hybrid plane: the
    optimizer state shards over the mesh's ``scatter_axis`` (dp) for
    tp-sharded and replicated params alike — the plan groups leaves by
    their PartitionSpec so each bucket's reduce-scatter runs over ``dp``
    only, replicated buckets take their tp-side psum on the 1/dp shard,
    and tp-sharded buckets' stacked state arrays split over BOTH axes
    (``P(dp, tp)``), so no chip ever materializes another tp rank's
    state. ``param_specs`` may be the spec tree or a callable
    ``params -> spec tree``. Pair with ``make_train_step(mesh=,
    param_specs=)``; env-world (tpurun) hybrid is not supported.
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    prescale = None if accum_steps <= 1 else 1.0 / accum_steps
    wire = resolve_wire_dtype(wire_dtype)
    if mesh is not None and param_specs is None:
        raise ValueError(
            "partition_optimizer(mesh=...) requires param_specs= — the "
            "spec tree is what keys the per-leaf collective plan")
    if mesh is not None and not average:
        raise ValueError(
            "the spec-grouped hybrid plane defines averaging semantics "
            "via per-group denominators — average=False has no meaning "
            "there")

    def _nshards() -> int:
        return runtime.size() if runtime.is_initialized() else 1

    def _hybrid_init(params):
        from jax.sharding import NamedSharding, PartitionSpec as P
        if runtime.is_initialized() and runtime.world().env_world:
            raise ValueError(
                "hybrid (mesh=) ZeRO is single-controller only: the "
                "env-world plane has no tp axis to shard weights over — "
                "run without tpurun, one process driving all chips")
        specs = param_specs(params) if callable(param_specs) \
            else param_specs
        n = int(mesh.shape[scatter_axis])
        plan = plan_zero(params, n, fusion_threshold, specs=specs,
                         mesh=mesh, scatter_axis=scatter_axis,
                         skip_axes=skip_axes)
        leaves = plan.treedef.flatten_up_to(params)
        if any(is_tracer(l) for l in leaves):
            raise ValueError(
                "hybrid ZeRO state must be initialized eagerly (the "
                "stacked shard layout is assembled host-side from the "
                "global params) — call init outside jit")
        stacked = []
        for i in range(len(plan.buckets)):
            arr = zero_stack_global(leaves, plan, i)
            stacked.append(jax.device_put(
                arr, NamedSharding(mesh, zero_stacked_spec(plan, i))))
        inner = optimizer.init(tuple(stacked))
        # Commit every inner leaf to the hybrid mesh: shard leaves keep
        # the stacked layout (dp × the bucket's tp-like axes), scalars
        # (Adam count) replicate — one device set for jit dispatch AND
        # for these trees to work as restore templates.
        ids = _zero_shard_leaf_buckets(inner, plan)
        ileaves, itd = jax.tree_util.tree_flatten(inner)
        placed = []
        for leaf, b in zip(ileaves, ids):
            sharding = NamedSharding(
                mesh, P() if b is None else zero_stacked_spec(plan, b))
            placed.append(jax.device_put(jnp.asarray(leaf), sharding))
        return ZeroShardedState(inner=itd.unflatten(placed), plan=plan)

    def init_fn(params):
        if mesh is not None:
            return _hybrid_init(params)
        n = _nshards()
        plan = plan_zero(params, n, fusion_threshold)
        env_world = runtime.is_initialized() and runtime.world().env_world
        rank = runtime.world().controller_rank if env_world else None
        leaves = plan.treedef.flatten_up_to(params)
        from .ops.fusion import _fuse_bucket
        stacked = []
        for i in range(len(plan.buckets)):
            flat = _fuse_bucket(leaves, plan, i)
            s = plan.shard_len(i)
            if env_world:
                # One independent process per rank: materialize ONLY this
                # rank's slice — true 1/N host+device memory.
                arr = flat[rank * s:(rank + 1) * s].reshape(1, s)
            else:
                arr = jnp.reshape(flat, (n, s))
                if (runtime.is_initialized() and n > 1
                        and not is_tracer(arr)):
                    # Place the stacked shards split over the world mesh
                    # up front: each device holds 1/N of every
                    # optimizer-state array from step 0.
                    from jax.sharding import NamedSharding
                    from jax.sharding import PartitionSpec as P
                    arr = jax.device_put(
                        arr, NamedSharding(runtime.mesh(), P(axis_name)))
            stacked.append(arr)
        inner = optimizer.init(tuple(stacked))
        if (not env_world and runtime.is_initialized() and n > 1):
            # Shard leaves inherited the stacked arrays' P(AXIS) layout
            # through the inner init's zeros_like; commit the scalar
            # leaves (e.g. Adam's count) to the same mesh replicated, so
            # the whole state shares one device set — required both for
            # jit dispatch and for these trees to serve as restore
            # templates (restore_sharded places leaves from the
            # template's sharding).
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            shard_shapes = set(plan.shard_shapes())
            rep = NamedSharding(runtime.mesh(), P())
            inner = jax.tree_util.tree_map(
                lambda l: l if (is_tracer(l)
                                or tuple(np.shape(l)) in shard_shapes)
                else jax.device_put(l, rep), inner)
        return ZeroShardedState(inner=inner, plan=plan)

    def update_fn(grads, state: ZeroShardedState, params=None, **extra):
        if params is None:
            raise ValueError(
                "ZeRO update requires params: each rank slices its flat "
                "parameter shard locally for the wrapped optimizer "
                "(weight decay etc.) — call update(grads, state, params)")
        finite_out = extra.pop("finite_out", None)
        grad_order = extra.pop("grad_order", None)
        plan = state.plan
        axis = plan.scatter_axis if plan.scatter_axis is not None \
            else axis_name
        needs_trace = plan.nshards > 1 or bool(plan.nonscatter)
        if needs_trace and not _axes_bound(axis):
            raise ValueError(
                "ZeRO updates must run inside the compiled step (the "
                "reduce-scatter/all-gather pair is an in-trace collective "
                "over the mesh) — build the step with "
                "make_train_step(zero=True) (hybrid: make_train_step("
                "mesh=, param_specs=)), or use the env-world plane "
                "which drives the exchange from the host")
        if _axes_bound(axis):
            world = int(jax.lax.axis_size(axis))
            if world != plan.nshards:
                raise ValueError(
                    f"optimizer state was partitioned for a world of "
                    f"{plan.nshards} but this step runs over {world} "
                    f"{axis!r} rank(s) — initialize the state after "
                    f"hvd.init() / on the mesh the step runs over")
        need_finite = finite_out is not None
        emit = zero_emit_order(plan, grad_order) \
            if (overlap or grad_order is not None) else None
        out = fused_reduce_scatter(
            grads, plan, average=average, axis_name=axis,
            prescale=prescale, return_finite=need_finite,
            wire_dtype=wire, emit_order=emit)
        grad_shards, local_finite = out if need_finite else (out, None)
        p_shards = shard_params(params, plan, axis_name=axis)
        # The inner state's array leaves are per-device [1, shard_len]
        # blocks of the stacked layout; present the flat shards the same
        # way so elementwise state updates broadcast shape-exactly.
        gs = tuple(g.reshape(1, -1) for g in grad_shards)
        ps = tuple(p.reshape(1, -1) for p in p_shards)
        upd_shards, new_inner = optimizer.update(gs, state.inner, ps)
        flat_upd = [u.reshape(-1) for u in upd_shards]
        gathered = fused_allgather_params(
            flat_upd, plan, axis_name=axis,
            and_finite=local_finite if need_finite else None)
        if need_finite:
            updates, all_finite = gathered
            finite_out["all_finite"] = all_finite
        else:
            updates = gathered
        return updates, ZeroShardedState(inner=new_inner, plan=plan)

    update_fn.accum_steps = accum_steps
    update_fn.supports_finite_out = True
    update_fn.zero = True
    # Knob stamps: make_train_step reads these to thread the backward-
    # completion probe (overlap) and the env-world plane reads wire_dtype
    # to cast its host payloads.
    update_fn.wire_dtype = wire_dtype_name(wire)
    update_fn.overlap = overlap
    update_fn.supports_grad_order = True
    # The env-world plane drives the collectives from the host and needs
    # direct access to the wrapped transformation's shard update.
    update_fn.inner_update = optimizer.update
    # Hybrid stamps: make_train_step auto-detects the mesh/spec plane from
    # the optimizer exactly like it auto-detects zero.
    update_fn.mesh = mesh
    update_fn.param_specs = param_specs
    update_fn.scatter_axis = scatter_axis
    update_fn.hybrid = mesh is not None
    return optax.GradientTransformation(init_fn, update_fn)


def _hybrid_allreduce_optimizer(optimizer, *, mesh, param_specs, skip_axes,
                                fusion_threshold, accum_steps, wire,
                                overlap) -> optax.GradientTransformation:
    """The replicated-update half of the hybrid plane (zero=False):
    gradients ride the spec-grouped fused psum plan
    (:func:`~horovod_tpu.ops.fusion.fused_allreduce` with
    ``reduce_axes=``), the wrapped transformation updates a full replica.
    State leaves mirror the params, so they are committed to the hybrid
    mesh with the SAME PartitionSpecs — tp-sharded weights' momenta shard
    over tp too. ``update(..., presynced=tree)`` names the leaves a step
    already reduced inside its backward (the bucket's number, or -1): they
    are not reduced again."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    prescale = None if accum_steps <= 1 else 1.0 / accum_steps

    def _specs_for(params):
        return param_specs(params) if callable(param_specs) else param_specs

    def init_fn(params):
        state = optimizer.init(params)
        specs = _specs_for(params)

        def _place(leaf, spec):
            if is_tracer(leaf):
                return leaf
            return jax.device_put(jnp.asarray(leaf),
                                  NamedSharding(mesh, spec))

        return optax.tree_map_params(
            optimizer, lambda s, sp: _place(s, sp), state, specs,
            transform_non_params=lambda s: _place(s, P()))

    def update_fn(grads, state, params=None, **extra):
        finite_out = extra.pop("finite_out", None)
        grad_order = extra.pop("grad_order", None)
        presynced = extra.pop("presynced", None)
        specs = _specs_for(params if params is not None else grads)
        spec_leaves = jax.tree_util.tree_flatten(
            specs, is_leaf=lambda x: isinstance(x, P))[0]
        syncs = plan_grad_sync(spec_leaves, mesh, skip_axes=skip_axes)
        first_bucket = 0
        if presynced is not None:
            # ``presynced``: per leaf, the bucket in which the backward
            # already reduced it (ops/fusion.reduce_in_backward), or -1.
            # Such a leaf has nothing left to exchange and no average to
            # take: it passes through (and the guard still reads it).
            ks = jax.tree_util.tree_leaves(presynced)
            syncs = [s if k < 0 else dataclasses.replace(
                s, psum=(), shard=s.shard + s.psum, denom=1)
                for s, k in zip(syncs, ks)]
            first_bucket = max(ks) + 1
        kw = dict(average=True, fusion_threshold=fusion_threshold,
                  prescale=prescale, wire_dtype=wire,
                  overlap=overlap, grad_order=grad_order,
                  reduce_axes=syncs, first_bucket=first_bucket)
        if finite_out is None:
            grads = fused_allreduce(grads, **kw)
        else:
            grads, all_finite = fused_allreduce(
                grads, return_finite=True, **kw)
            finite_out["all_finite"] = all_finite
        return optimizer.update(grads, state, params, **extra)

    update_fn.accum_steps = accum_steps
    update_fn.supports_finite_out = True
    update_fn.wire_dtype = wire_dtype_name(wire)
    update_fn.overlap = overlap
    update_fn.supports_grad_order = True
    update_fn.mesh = mesh
    update_fn.param_specs = param_specs
    update_fn.skip_axes = tuple(skip_axes)
    update_fn.hybrid = True
    # Uniform stamp with the 1-D plane: a host-plane executor driving
    # this optimizer reads the same planner (the hybrid ICI executor
    # builds its richer spec-grouped syncs in update_fn itself).
    update_fn.exchange_plan = functools.partial(
        plan_exchange, fusion_threshold=fusion_threshold)
    # The step builder derives opt-state PartitionSpecs by mapping the
    # param specs over the state with optax.tree_map_params — that needs
    # the WRAPPED transformation (this wrapper's init would device_put
    # optax's structure-probe placeholders).
    update_fn.inner_transform = optimizer
    return optax.GradientTransformation(init_fn, update_fn)


def DistributedOptimizer(optimizer: optax.GradientTransformation,
                         *,
                         average: bool = True,
                         fusion_threshold: Optional[int] = None,
                         sparse_as_dense: bool = False,
                         compression: Any = Compression.none,
                         accum_steps: int = 1,
                         zero: bool = False,
                         wire_dtype=None,
                         overlap: Optional[bool] = None,
                         axis_name: str = AXIS,
                         mesh=None,
                         param_specs=None,
                         skip_axes: Tuple[str, ...] = ()
                         ) -> optax.GradientTransformation:
    """Wrap an optax optimizer with fused gradient allreduce.

    Parity: ``hvd.DistributedOptimizer`` (``horovod/tensorflow/__init__.py:
    127-186``) — gradients are averaged across ranks before being applied;
    a no-op when ``size() == 1`` (``__init__.py:180-182``). Call inside the
    jitted train step under ``shard_map`` over the world mesh.
    ``compression=Compression.bf16`` halves allreduce bytes (see
    :class:`Compression`).

    ``accum_steps`` is the reference's ``backward_passes_per_step``: the
    caller feeds ``update`` the *sum* of N per-microbatch gradients and one
    fused allreduce fires per accumulated step, averaged by the **global
    microbatch count** (``accum_steps × size``) — the ``1/accum_steps`` is
    folded into the fused bucket traversal (:func:`fused_allreduce`'s
    ``prescale``) and ``average=True`` supplies the ``1/size``. Drive your
    own accumulation loop with this knob, or use
    ``make_train_step(accum_steps=N)`` which scans microbatches inside the
    compiled step and performs the microbatch mean itself (do NOT set both:
    the gradients would be divided by N twice).

    ``wire_dtype`` (``"bf16"``, ``"fp8"``; default ``HVD_WIRE_DTYPE``) puts
    float gradient buckets on the wire in reduced precision: scales are
    applied in fp32, one cast on send, and the reduced result is cast back
    to the gradient dtype immediately after — fp32 accumulation everywhere
    downstream (``docs/performance.md`` "Overlap & wire formats"). Unlike
    ``compression`` it never changes the bucket plan; don't set both on
    the all-reduce plane (the double cast would be ambiguous — it raises).

    ``overlap`` (default ``HVD_OVERLAP``) arms backward-overlapped bucket
    emission: per-bucket collectives issue in backward-completion order
    behind ``optimization_barrier`` pins so wire time hides behind the
    remaining backward compute. The completion order itself is probed by
    ``make_train_step(overlap=True)`` — set it there (or via the env var)
    and this wrapper picks it up from the step's ``grad_order`` channel.

    ``zero=True`` switches to ZeRO-1 sharded updates
    (:func:`partition_optimizer`): the fused all-reduce becomes a fused
    reduce-scatter + all-gather over the SAME buckets (same bytes on the
    wire), each rank holds and updates ``1/size()`` of the optimizer state,
    and the returned state is a :class:`ZeroShardedState`. Build the step
    with ``make_train_step(zero=True)`` (or ``HVD_ZERO=1``). Composes with
    ``accum_steps``, the bad-step guard, ``wire_dtype`` (the scatter rides
    the wire dtype with fp32 shard accumulation before the optax update;
    the update all-gather stays full-precision so replicas end
    bit-identical) and ``overlap``; ``compression=Compression.bf16`` is
    accepted as an alias for ``wire_dtype="bf16"`` here. Sparse gradients
    must be densified (``sparse_as_dense=True``).

    ``mesh=`` + ``param_specs=`` arm the N-D hybrid plane (ISSUE 8): the
    gradient exchange becomes the spec-grouped collective plan — each
    leaf psums over exactly the mesh axes it is replicated across
    (tp-sharded weight grads over ``dp`` only, with the psum-transpose
    correction folded into the bucket prescale), leaves bucket within
    their spec group, and with ``zero=True`` the optimizer state shards
    over ``dp`` for tp-sharded params too (:func:`partition_optimizer`).
    ``param_specs`` is a PartitionSpec tree mirroring the params (or a
    callable ``params -> tree``); pair with ``make_train_step(mesh=,
    param_specs=)``, which auto-detects the plane from this optimizer's
    stamp.
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    wire = resolve_wire_dtype(
        wire_dtype if wire_dtype is not None
        else _config.wire_dtype_default())
    if overlap is None:
        overlap = _config.overlap_enabled()
    if mesh is not None:
        if param_specs is None:
            raise ValueError(
                "DistributedOptimizer(mesh=...) requires param_specs= — "
                "the spec tree keys the per-leaf collective plan")
        if not average:
            raise ValueError(
                "the spec-grouped hybrid plane defines averaging "
                "semantics via per-group denominators — average=False "
                "has no meaning there")
        if sparse_as_dense or (not zero
                               and compression is not Compression.none):
            raise ValueError(
                "the hybrid (mesh=) plane supports dense gradients and "
                "wire_dtype= only (compression= casts whole leaves "
                "before bucketing, which the spec-grouped plan replaces)")

    if zero:
        if compression is Compression.bf16:
            # The old eager rejection is gone: a bf16-compressed scatter
            # IS the bf16 wire format — the received shard is cast back to
            # fp32 before the optax update, so the f32 accumulation the
            # fused all-reduce path keeps is preserved here too.
            if wire is None:
                wire = jnp.dtype(jnp.bfloat16)
            elif wire != jnp.dtype(jnp.bfloat16):
                raise ValueError(
                    f"compression=Compression.bf16 (the bf16 wire alias) "
                    f"conflicts with wire_dtype={wire_dtype_name(wire)!r} "
                    f"— set wire_dtype alone")
        elif compression is not Compression.none:
            raise ValueError(
                "unsupported compression for zero=True: the ZeRO plane "
                "expresses compression as a wire format — use "
                "wire_dtype='bf16'/'fp8' (Compression.bf16 is accepted "
                "as an alias)")
        part = partition_optimizer(
            optimizer, average=average, fusion_threshold=fusion_threshold,
            accum_steps=accum_steps, wire_dtype=wire, overlap=overlap,
            axis_name=axis_name, mesh=mesh, param_specs=param_specs,
            skip_axes=skip_axes)
        if not sparse_as_dense:
            return part

        def _densify(grads):
            return jax.tree_util.tree_map(
                lambda l: l.to_dense() if _is_sparse_leaf(l) else l,
                grads, is_leaf=_is_sparse_leaf)

        def zero_update(grads, state, params=None, **extra):
            return part.update(_densify(grads), state, params, **extra)

        for attr in ("accum_steps", "supports_finite_out", "zero",
                     "inner_update", "wire_dtype", "overlap",
                     "supports_grad_order", "mesh", "param_specs",
                     "scatter_axis", "hybrid"):
            setattr(zero_update, attr, getattr(part.update, attr))
        # The env-world plane flattens grads itself (it never enters this
        # wrapper) and consults the stamp to densify before bucketing.
        zero_update.sparse_as_dense = True
        return optax.GradientTransformation(part.init, zero_update)

    if wire is not None and compression is not Compression.none:
        raise ValueError(
            "compression= and wire_dtype= both set: compression casts "
            "whole leaves before bucketing while wire_dtype casts each "
            "bucket at the collective (fp32 scales and accumulation) — "
            "pick one (wire_dtype is the recommended form)")

    if mesh is not None:
        return _hybrid_allreduce_optimizer(
            optimizer, mesh=mesh, param_specs=param_specs,
            skip_axes=skip_axes, fusion_threshold=fusion_threshold,
            accum_steps=accum_steps, wire=wire, overlap=overlap)

    def init_fn(params):
        return optimizer.init(params)

    def update_fn(grads, state, params=None, **extra):
        # ``finite_out``: the bad-step guard's side channel. When
        # ``make_train_step(guard_nonfinite=True)`` passes a dict here,
        # the fused allreduce additionally derives the world-wide
        # all-finite flag from the ALREADY-reduced buckets (same psum
        # round, zero extra collectives — see fused_allreduce) and this
        # function deposits it under ``"all_finite"`` for the step to
        # gate params/opt_state on. In-trace only: the dict holds a
        # tracer for the duration of the surrounding trace.
        finite_out = extra.pop("finite_out", None)
        grad_order = extra.pop("grad_order", None)
        kw = dict(average=average, fusion_threshold=fusion_threshold,
                  sparse_as_dense=sparse_as_dense, compression=compression,
                  accum_steps=accum_steps, axis_name=axis_name,
                  wire_dtype=wire, overlap=overlap, grad_order=grad_order)
        if finite_out is None:
            grads = allreduce_gradients(grads, **kw)
        else:
            grads, all_finite = allreduce_gradients(
                grads, return_finite=True, **kw)
            finite_out["all_finite"] = all_finite
        return optimizer.update(grads, state, params, **extra)

    # Stamp the knob where make_train_step can see it: setting accum_steps
    # on BOTH layers would silently divide gradients by N twice.
    update_fn.accum_steps = accum_steps
    # Capability stamp for the guard: make_train_step only threads the
    # finite_out channel into optimizers that declare it (a plain optax
    # transformation would choke on the unknown kwarg).
    update_fn.supports_finite_out = True
    # Knob stamps: the step builder reads overlap to arm its grad-order
    # probe; the env-world plane reads wire_dtype to cast host payloads.
    update_fn.wire_dtype = wire_dtype_name(wire)
    update_fn.overlap = overlap
    update_fn.supports_grad_order = True
    # The env-world executor interprets THIS plan (one planner, two
    # executors): same membership and denominators as the compiled
    # fused-allreduce, carried by the stamped optimizer so the two planes
    # cannot drift (the ZeRO state carries its ZeroPlan the same way).
    update_fn.exchange_plan = functools.partial(
        plan_exchange, axis_name=axis_name,
        fusion_threshold=fusion_threshold)
    return optax.GradientTransformation(init_fn, update_fn)


def allreduce_gradients(grads,
                        average: bool = True,
                        fusion_threshold: Optional[int] = None,
                        sparse_as_dense: bool = False,
                        compression: Any = Compression.none,
                        accum_steps: int = 1,
                        axis_name: str = AXIS,
                        return_finite: bool = False,
                        wire_dtype=None,
                        overlap: bool = False,
                        grad_order: Optional[Tuple[int, ...]] = None):
    """Allreduce a gradient pytree: dense leaves via fused flat buckets,
    sparse leaves via allgather (``horovod/tensorflow/__init__.py:61-79``).
    ``accum_steps > 1`` divides by the local microbatch count (the caller
    passes a gradient *sum* over N backward passes) as a prescale fused
    into the bucket traversal. ``return_finite=True`` additionally
    returns the world-wide all-finite scalar derived inside the same
    traversal (see :func:`~horovod_tpu.ops.fusion.fused_allreduce`).
    ``wire_dtype``/``overlap``/``grad_order`` pass through to the fused
    traversal (low-precision wire + backward-overlapped emission); the
    size-1 fast path ignores the wire — nothing travels, so nothing
    quantizes."""
    prescale = None if accum_steps <= 1 else 1.0 / accum_steps
    if runtime.is_initialized() and runtime.size() == 1 \
            and not runtime._in_world_trace():
        # size()==1 fast path (__init__.py:180-182) — but the microbatch
        # mean is not a cross-rank concern and must still happen, and
        # neither is finiteness: check the (scaled) local tree directly.
        if prescale is None and not return_finite:
            return grads
        from .ops.fusion import _prescale_array

        def _scale(l):
            if prescale is None:
                return l
            if _is_sparse_leaf(l):
                return IndexedSlices(_prescale_array(l.values, prescale),
                                     l.indices, l.dense_shape)
            return _prescale_array(l, prescale)
        scaled = jax.tree_util.tree_map(_scale, grads,
                                        is_leaf=_is_sparse_leaf)
        if not return_finite:
            return scaled
        finite = jnp.ones((), jnp.bool_)
        for l in jax.tree_util.tree_leaves(scaled,
                                           is_leaf=_is_sparse_leaf):
            v = l.values if _is_sparse_leaf(l) else l
            if jnp.issubdtype(v.dtype, jnp.inexact):
                finite = finite & jnp.all(jnp.isfinite(v))
        return scaled, finite

    if sparse_as_dense:
        grads = jax.tree_util.tree_map(
            lambda l: l.to_dense() if _is_sparse_leaf(l) else l,
            grads, is_leaf=_is_sparse_leaf)

    # Structural (tree_map) compression round-trip: the ctx tree mirrors the
    # gradient tree leaf-for-leaf (wrapped in an opaque holder so a None ctx
    # is still a leaf), so restoration cannot depend on flatten ordering.
    class _Ctx:
        __slots__ = ("dtype",)

        def __init__(self, dtype):
            self.dtype = dtype

    ctx_tree = jax.tree_util.tree_map(
        lambda l: _Ctx(None if _is_sparse_leaf(l)
                       else compression.compress(l)[1]),
        grads, is_leaf=_is_sparse_leaf)
    compressed = jax.tree_util.tree_map(
        lambda l: l if _is_sparse_leaf(l) else compression.compress(l)[0],
        grads, is_leaf=_is_sparse_leaf)
    # fused_allreduce buckets dense leaves and routes IndexedSlices leaves
    # through the two-allgather sparse path.
    reduced = fused_allreduce(compressed, average=average,
                              fusion_threshold=fusion_threshold,
                              axis_name=axis_name, prescale=prescale,
                              return_finite=return_finite,
                              wire_dtype=wire_dtype, overlap=overlap,
                              grad_order=grad_order)
    if return_finite:
        reduced, all_finite = reduced
    out = jax.tree_util.tree_map(
        lambda l, c: l if _is_sparse_leaf(l)
        else compression.decompress(l, c.dtype),
        reduced, ctx_tree, is_leaf=_is_sparse_leaf)
    return (out, all_finite) if return_finite else out


def broadcast_global_variables(variables, root_rank: int = 0,
                               axis_name: str = AXIS):
    """Broadcast every leaf of a pytree from ``root_rank``.

    Parity: ``hvd.broadcast_global_variables``
    (``horovod/tensorflow/__init__.py:82-90``) — used right after
    initialization or checkpoint restore so all ranks start from rank 0's
    weights (§5.4 consistency protocol).
    """
    return jax.tree_util.tree_map(
        lambda v: _broadcast(v, root_rank=root_rank, axis_name=axis_name),
        variables)


# Alias matching modern naming; same semantics.
broadcast_parameters = broadcast_global_variables


def broadcast_optimizer_state(opt_state, root_rank: int = 0,
                              axis_name: str = AXIS):
    """Broadcast optimizer state (momenta etc.) from ``root_rank`` — the
    optax analog of broadcasting optimizer slot variables, which the
    reference gets for free because slots are global variables
    (``horovod/tensorflow/__init__.py:82-90``)."""
    return broadcast_global_variables(opt_state, root_rank, axis_name)
