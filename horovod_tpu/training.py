"""Train-step builder: the compiled data-parallel hot path.

Reference parity
----------------
The reference's training step is: TF computes per-replica gradients,
``DistributedOptimizer.compute_gradients`` allreduces each one
(``horovod/tensorflow/__init__.py:164-186``), then the wrapped optimizer
applies them — launched as one process per GPU (``README.md:62-64``).

TPU-native design
-----------------
One compiled SPMD program over the world mesh replaces the per-process
choreography: ``make_train_step`` returns a jitted ``shard_map`` function in
which each chip computes gradients on its batch shard, the
``DistributedOptimizer`` transformation does a fused ``psum`` over the
``"hvd"`` ICI axis (see ``ops/fusion.py`` for the 64 MiB bucketing parity),
and every chip applies identical updates. Parameters are replicated
(pure data parallelism, the reference's only strategy — SURVEY §2.4); the
batch is sharded on its leading axis.

All collectives live inside the compiled step, so there is no negotiation
latency floor (the reference pays a 5 ms tick per round,
``mpi_ops.cc:1295``); XLA schedules and overlaps the gradient all-reduce
with backprop.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from . import runtime
from .optimizer import Compression, DistributedOptimizer
from .runtime import AXIS
from .utils import timeline as _timeline


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    """Replicated training state: params + optimizer state (+ BN stats)."""

    step: jax.Array
    params: Any
    opt_state: Any
    batch_stats: Any = None


def cross_entropy_loss(logits, labels):
    """Mean softmax cross entropy over integer labels (float32 reduction)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def accuracy(logits, labels):
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels)
                    .astype(jnp.float32))


# ---------------------------------------------------------------------------
# In-step gradient accumulation — the TPU-native ``backward_passes_per_step``
# (Sergeev & Del Balso 2018 §4; GPipe microbatching, Huang et al. 2019).
# The scan lives INSIDE the compiled SPMD program: gradients for N
# microbatches are summed on-device and the fused psum fires once per
# accumulated step, so interconnect traffic per sample drops by N and the
# per-chip batch can exceed HBM limits via the optional remat policy.
# ---------------------------------------------------------------------------

def _acc_dtype(dtype):
    """Accumulator dtype: fp32 for sub-fp32 floats (bf16 microbatch grads
    summed in bf16 lose ~3 bits over 4 microbatches), unchanged otherwise."""
    if jnp.issubdtype(dtype, jnp.floating) \
            and jnp.dtype(dtype).itemsize < 4:
        return jnp.float32
    return jnp.dtype(dtype)


def _split_microbatches(tree, n: int):
    """Reshape every leaf ``(B, ...) -> (n, B // n, ...)`` (leading-axis
    contiguous split; the mean over equal microbatches equals the full-batch
    mean regardless of row order)."""
    return jax.tree_util.tree_map(
        lambda x: jnp.reshape(x, (n, x.shape[0] // n) + x.shape[1:]), tree)


def _default_accum_unroll(accum_steps: int) -> int:
    """Scan unroll for the microbatch loop. On TPU the rolled ``while`` is
    right (compile time stays O(1) in N; XLA pipelines the body). XLA:CPU
    executes while-loop bodies WITHOUT intra-op parallelism — measured 8×
    slower per microbatch on the bench host — so off-TPU the loop is fully
    unrolled, trading compile time for the multi-core step."""
    return 1 if jax.default_backend() == "tpu" else accum_steps


def _accumulate_grads(vag: Callable, params, batch_stats, inputs, labels,
                      rng_for: Callable, accum_steps: int,
                      metrics_fn: Optional[Callable],
                      unroll: Optional[int] = None):
    """Scan ``accum_steps`` microbatches, summing gradients on-device.

    ``vag`` is ``jax.value_and_grad(loss, has_aux=True)`` with signature
    ``(params, batch_stats, inputs, labels, rng) -> ((loss, (logits,
    new_stats)), grads)``; ``rng_for(i)`` derives the i-th microbatch's
    dropout key. Returns ``(mean_loss, new_batch_stats, mean_grads,
    mean_extras)`` where the means are over microbatches — composed with the
    ``average=True`` world pmean downstream, gradients end up divided by the
    global microbatch count (``accum_steps × size``), exactly the full-batch
    scaling. Integer metric leaves (e.g. counts) keep the microbatch sum —
    the full-batch value — instead of a flooring integer mean.
    Gradients accumulate in fp32 when their dtype is narrower and
    are cast back after the mean; batch statistics thread sequentially
    through the microbatches (N momentum updates per step — the defined
    semantics for BN under accumulation, not bit-equal to one full-batch
    update).
    """
    n = accum_steps
    mb_in = _split_microbatches(inputs, n)
    mb_lab = _split_microbatches(labels, n)
    first = (jax.tree_util.tree_map(lambda x: x[0], mb_in),
             jax.tree_util.tree_map(lambda x: x[0], mb_lab))

    # Structure probe (no FLOPs): shapes/dtypes of grads, logits and metric
    # extras, to build type-stable zero carries for the scan.
    (_, (logits_s, _)), grads_s = jax.eval_shape(
        vag, params, batch_stats, first[0], first[1], rng_for(0))
    extras_s = (jax.eval_shape(metrics_fn, logits_s, first[1])
                if metrics_fn is not None else None)

    def _zeros(s):
        return jnp.zeros(s.shape, _acc_dtype(s.dtype))

    carry = (
        jax.tree_util.tree_map(_zeros, grads_s),
        batch_stats,
        jnp.zeros((), jnp.float32),
        (jax.tree_util.tree_map(_zeros, extras_s)
         if metrics_fn is not None else None),
    )

    def _body(carry, xs):
        gacc, stats, lacc, macc = carry
        i, x, y = xs
        (loss, (logits, new_stats)), grads = vag(
            params, stats, x, y, rng_for(i))
        gacc = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(a.dtype), gacc, grads)
        lacc = lacc + loss.astype(jnp.float32)
        if metrics_fn is not None:
            macc = jax.tree_util.tree_map(
                lambda a, m: a + jnp.asarray(m).astype(a.dtype),
                macc, metrics_fn(logits, y))
        return (gacc,
                new_stats if new_stats is not None else stats,
                lacc, macc), None

    (gacc, stats, lacc, macc), _ = jax.lax.scan(
        _body, carry, (jnp.arange(n), mb_in, mb_lab),
        unroll=_default_accum_unroll(n) if unroll is None else unroll)

    inv = 1.0 / n
    grads = jax.tree_util.tree_map(
        lambda a, s: (a * jnp.asarray(inv, a.dtype)).astype(s.dtype),
        gacc, grads_s)

    def _mean_extra(a, s):
        # Integer metric leaves keep the microbatch SUM: jnp.asarray(1/n,
        # int_dtype) is 0 (same guard as fusion._prescale_array), and for a
        # count-style metric the sum over microbatches IS the full-batch
        # value the accum_steps=1 path reports.
        if not jnp.issubdtype(s.dtype, jnp.inexact):
            return a.astype(s.dtype)
        return (a * jnp.asarray(inv, a.dtype)).astype(s.dtype)

    extras = None
    if metrics_fn is not None:
        extras = jax.tree_util.tree_map(_mean_extra, macc, extras_s)
    return lacc * inv, stats, grads, extras


def _check_accum_batch(inputs, accum_steps: int, shards: int) -> None:
    """Leading-dim divisibility check for the accumulated step — raised
    eagerly with the full arithmetic instead of a reshape error from deep
    inside the trace."""
    leaves = jax.tree_util.tree_leaves(inputs)
    if not leaves:
        return
    rows = leaves[0].shape[0]
    if rows % (shards * accum_steps):
        raise ValueError(
            f"global batch of {rows} rows cannot be split into "
            f"{shards} shard(s) x {accum_steps} microbatches "
            f"(needs divisibility by {shards * accum_steps}); adjust the "
            f"batch size or accum_steps")


def _build_value_and_grad(model, loss_fn, remat):
    """Shared loss/grad builder for BOTH execution planes (the compiled
    SPMD step and the env-world grads half): variables-dict assembly,
    mutable batch_stats, dropout rng plumbing, optional remat wrap. One
    definition so a change to loss semantics cannot silently diverge the
    two planes."""

    def _loss(params, batch_stats, inputs, labels, step_rng):
        variables = {"params": params}
        if batch_stats is not None:
            variables["batch_stats"] = batch_stats
        # Every op of the loss carries "forward" in its name, and its
        # transposes "transpose(jvp(forward))": how a device trace splits
        # the step (docs/timeline.md).
        with jax.named_scope("forward"):
            out = model.apply(
                variables, inputs, train=True,
                mutable=["batch_stats"] if batch_stats is not None else [],
                rngs={"dropout": step_rng},
            )
            logits, new_vars = out if isinstance(out, tuple) else (out, {})
            loss = loss_fn(logits, labels)
        return loss, (logits, new_vars.get("batch_stats"))

    if remat:
        _loss = jax.checkpoint(
            _loss, policy=None if remat is True else remat)
    return jax.value_and_grad(_loss, has_aux=True)


def create_train_state(model, rng, sample_input, optimizer,
                       *, average: bool = True,
                       fusion_threshold: Optional[int] = None,
                       compression: Any = Compression.none,
                       zero: Optional[bool] = None,
                       wire_dtype=None,
                       overlap: Optional[bool] = None,
                       has_batch_stats: Optional[bool] = None,
                       mesh: Optional[jax.sharding.Mesh] = None,
                       param_specs=None,
                       model_kwargs: Optional[dict] = None) -> Tuple[
                           TrainState, optax.GradientTransformation]:
    """Initialize model + DistributedOptimizer state.

    Returns ``(state, dist_opt)`` where ``dist_opt`` is the optimizer wrapped
    with the fused gradient allreduce (``DistributedOptimizer``); its state is
    bit-identical to plain optax state so checkpoints restore without this
    framework (the Keras dynamic-subclass parity property,
    ``horovod/keras/__init__.py:81-87``).

    ``zero`` (default: ``HVD_ZERO``) wraps the optimizer with ZeRO-1
    sharded updates instead (``DistributedOptimizer(zero=True)``): the
    optimizer state is rank-sharded (1/size() per device) and the step
    must be built with ``make_train_step(zero=True)`` — which it picks up
    automatically from the optimizer's capability stamp.

    ``wire_dtype`` (default: ``HVD_WIRE_DTYPE``) and ``overlap`` (default:
    ``HVD_OVERLAP``) pass through to the ``DistributedOptimizer`` — the
    low-precision wire format and backward-overlapped bucket emission
    (``docs/performance.md`` "Overlap & wire formats").

    ``mesh=`` + ``param_specs=`` build the state for the N-D hybrid
    plane (``docs/performance.md`` "Hybrid dp×tp"): params are placed as
    global arrays laid out by the spec tree (``param_specs`` may be a
    callable ``params -> spec tree``), the optimizer carries the
    spec-grouped collective plan, and with ``zero=True`` its state
    shards over the mesh's ``dp`` axis for tp-sharded params too. Build
    the step with ``make_train_step`` as usual — it auto-detects the
    plane from the optimizer's stamp.
    """
    from .utils import config as _config
    if zero is None:
        zero = _config.zero_enabled()
    variables = model.init(rng, sample_input, **(model_kwargs or {}))
    params = variables.get("params", variables)
    batch_stats = variables.get("batch_stats")
    if has_batch_stats is not None and not has_batch_stats:
        batch_stats = None
    if param_specs is not None or mesh is not None:
        if param_specs is None or mesh is None:
            raise ValueError(
                "hybrid state needs BOTH mesh= and param_specs= — the "
                "mesh names the axes the specs refer to")
        specs = param_specs(params) if callable(param_specs) \
            else param_specs
        params = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, specs, is_leaf=lambda x: isinstance(x, P))
        if batch_stats is not None:
            batch_stats = jax.device_put(
                batch_stats, NamedSharding(mesh, P()))
        dist_opt = DistributedOptimizer(
            optimizer, average=average, fusion_threshold=fusion_threshold,
            compression=compression, zero=zero, wire_dtype=wire_dtype,
            overlap=overlap, mesh=mesh, param_specs=specs)
        state = TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=dist_opt.init(params),
            batch_stats=batch_stats,
        )
        return state, dist_opt
    dist_opt = DistributedOptimizer(
        optimizer, average=average, fusion_threshold=fusion_threshold,
        compression=compression, zero=zero, wire_dtype=wire_dtype,
        overlap=overlap)
    if (zero and runtime.is_initialized() and runtime.size() > 1
            and not runtime.world().env_world):
        # The ZeRO opt state is committed to the world mesh (stacked
        # shards, P(AXIS)); commit the replicated half to the same mesh so
        # the state is device-consistent from step 0 — and so these trees
        # work as restore TEMPLATES (restore_sharded lays leaves out from
        # the template's sharding, and a mixed dev0/mesh commitment would
        # be rejected by jit).
        rep = runtime.replicated_sharding()
        params = jax.device_put(params, rep)
        if batch_stats is not None:
            batch_stats = jax.device_put(batch_stats, rep)
    state = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=dist_opt.init(params),
        batch_stats=batch_stats,
    )
    return state, dist_opt


def make_train_step(model,
                    dist_opt: optax.GradientTransformation,
                    loss_fn: Callable = cross_entropy_loss,
                    *,
                    mesh: Optional[jax.sharding.Mesh] = None,
                    axis_name: str = AXIS,
                    donate: bool = True,
                    metrics_fn: Optional[Callable] = None,
                    accum_steps: int = 1,
                    accum_unroll: Optional[int] = None,
                    remat: Any = False,
                    guard_nonfinite: Optional[bool] = None,
                    zero: Optional[bool] = None,
                    overlap: Optional[bool] = None,
                    param_specs=None,
                    batch_spec=None,
                    _value_and_grad: Optional[Callable] = None):
    """Build the compiled SPMD train step.

    The returned function has signature ``step(state, batch) -> (state,
    metrics)`` where ``batch = (inputs, labels)`` is sharded on its leading
    axis over the world mesh and ``state`` is replicated. ``metrics`` (loss,
    plus ``metrics_fn(logits, labels)`` extras) are already globally averaged
    via ``pmean`` — the in-step equivalent of ``MetricAverageCallback``
    (``horovod/keras/callbacks.py:37-87``).

    ``accum_steps=N`` is the TPU-native ``backward_passes_per_step``
    (Sergeev & Del Balso 2018 §4): each shard's batch slice is split into N
    microbatches scanned INSIDE the compiled program, gradients are summed
    on-device (fp32 accumulation for sub-fp32 grads) and the fused psum
    fires **once** per accumulated step on the microbatch-mean tree — so
    the global batch can grow N× without growing peak activation memory or
    interconnect traffic per step. The step owns the ``1/N`` scaling; leave
    the ``DistributedOptimizer`` at its default ``accum_steps=1``.
    ``accum_unroll`` overrides the microbatch-scan unroll (default: rolled
    on TPU, fully unrolled elsewhere — see ``_default_accum_unroll``).

    ``remat`` checkpoints each microbatch's forward pass (``jax.checkpoint``;
    pass ``True`` or a ``jax.checkpoint_policies`` policy) — activations are
    recomputed during backprop, trading ~⅓ more FLOPs for microbatch-sized
    rather than batch-sized activation memory (GPipe, Huang et al. 2019).

    ``guard_nonfinite`` (default: ``HVD_GUARD_NONFINITE``) arms the in-jit
    bad-step guard: the world-wide all-finite flag is derived from the
    ALREADY-reduced fusion buckets (same psum round, zero extra
    collectives — :func:`~horovod_tpu.ops.fusion.fused_allreduce`) and a
    non-finite gradient tree on ANY replica leaves params, opt_state and
    batch_stats bit-unchanged (skip-step; the step counter still
    advances, so the next step's dropout keys differ). The step's metrics
    gain a replica-identical ``bad_step`` scalar (1.0 = skipped) and the
    other metric values are zeroed on skipped steps so a NaN loss cannot
    poison the epoch mean; ``Trainer.fit`` turns consecutive skips into
    rollback/abort containment (``HVD_MAX_BAD_STEPS``).

    ``zero`` (default: ``HVD_ZERO``, or auto-detected from a
    ``DistributedOptimizer(zero=True)`` optimizer) runs the ZeRO-1
    sharded-update plane: the gradient exchange is one fused
    reduce-scatter + one all-gather per bucket (no full-tree all-reduce),
    the optimizer state rides the step rank-sharded (``P(AXIS)`` stacked
    shards — 1/size() of the bytes per device), and every replica's
    params stay bit-identical. Composes with ``accum_steps`` (the scatter
    still fires once per accumulated step), ``remat``, and
    ``guard_nonfinite`` (the world-wide all-finite flag rides the
    all-gather the updated shards already take — zero extra collectives —
    and a skip leaves the SHARDED opt state bit-unchanged).

    ``overlap`` (default: ``HVD_OVERLAP``, or the optimizer's stamp) arms
    backward-overlapped bucket collectives: a one-time traced-jaxpr probe
    (:func:`~horovod_tpu.ops.fusion.probe_grad_order`, cached per input
    shapes) records the order the backward pass materializes each
    gradient leaf, and the fused exchange issues one collective per
    bucket in that order behind ``optimization_barrier`` pins — so XLA
    schedules each bucket's wire time behind the remaining backward
    compute instead of serializing one post-backward blob. Total
    collective count is unchanged (overlap reorders, never adds); on the
    ZeRO plane bucket membership is pinned by the plan and only emission
    order changes. Composes with ``wire_dtype`` on the optimizer
    (``docs/performance.md`` "Overlap & wire formats").

    ``param_specs`` (with ``mesh`` an N-D hybrid mesh from
    ``create_hybrid_mesh``) runs the step on the hybrid dp×tp plane: the
    state's params are global arrays laid out by the spec tree, the
    gradient exchange is the spec-grouped collective plan (tp-sharded
    weight grads psum over ``dp`` only; replicated leaves over the full
    mesh), ZeRO shards the optimizer state over ``dp`` for tp-sharded
    params too, and ``accum_steps``/``guard_nonfinite``/``overlap``/the
    optimizer's ``wire_dtype`` all compose unchanged. Auto-detected from
    a ``DistributedOptimizer(mesh=, param_specs=)`` stamp — build the
    state with ``create_train_state(mesh=, param_specs=)`` and this knob
    resolves itself. ``batch_spec`` overrides the batch layout (default:
    leading axis over ``dp``/``ep``). ``_value_and_grad`` swaps the flax
    loss builder for a custom ``(params, batch_stats, inputs, labels,
    rng) -> ((loss, (logits, new_stats)), grads)`` — the hook
    ``parallel/transformer.py`` re-targets through so both families run
    ONE step implementation. Single-controller only.
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    stamp_mesh = getattr(dist_opt.update, "mesh", None)
    hybrid = param_specs is not None \
        or getattr(dist_opt.update, "hybrid", False)
    if hybrid:
        if param_specs is None:
            param_specs = getattr(dist_opt.update, "param_specs", None)
        if mesh is None:
            mesh = stamp_mesh
        if mesh is None or param_specs is None:
            raise ValueError(
                "hybrid step needs BOTH mesh= and param_specs= (or a "
                "DistributedOptimizer(mesh=, param_specs=) whose stamps "
                "supply them)")
        if stamp_mesh is not None and mesh is not stamp_mesh:
            raise ValueError(
                "make_train_step(mesh=...) differs from the mesh this "
                "DistributedOptimizer was built for — the collective "
                "plan is keyed to one mesh; pass the same object")
        if runtime.is_initialized() and runtime.world().env_world:
            raise ValueError(
                "the hybrid dp×tp plane is single-controller only: the "
                "tpurun env-world has no tp axis for compiled collectives "
                "to span — run one process driving all chips")
    zero_stamped = getattr(dist_opt.update, "zero", False)
    if zero is None:
        from .utils import config as _config
        zero = zero_stamped or _config.zero_enabled()
    if zero and not zero_stamped:
        raise ValueError(
            "zero=True (or HVD_ZERO=1) requires a ZeRO-sharded optimizer: "
            "the step's opt-state sharding specs come from its "
            "partitioned state — build it with "
            "DistributedOptimizer(opt, zero=True) / partition_optimizer "
            "(create_train_state(zero=True) does this for you)")
    if zero_stamped and not zero:
        raise ValueError(
            "this DistributedOptimizer was built with zero=True — its "
            "state is rank-sharded and the step must be built with "
            "make_train_step(zero=True) (leave zero unset to auto-detect)")
    if guard_nonfinite is None:
        from .utils import config as _config
        guard_nonfinite = _config.guard_nonfinite()
    if guard_nonfinite and not getattr(dist_opt.update,
                                       "supports_finite_out", False):
        raise ValueError(
            "guard_nonfinite requires a DistributedOptimizer-wrapped "
            "optimizer: the all-finite flag is derived inside its fused "
            "allreduce so every replica agrees on the skip decision with "
            "no extra collective; a plain optax transformation has no "
            "such channel (wrap it with "
            "horovod_tpu.DistributedOptimizer(...))")
    if accum_steps > 1 and getattr(dist_opt.update, "accum_steps", 1) > 1:
        raise ValueError(
            "accum_steps is set on BOTH make_train_step and "
            "DistributedOptimizer — the gradients would be divided by N "
            "twice; set it in one place (make_train_step owns the "
            "microbatch scan and its 1/N)")
    if overlap is None:
        from .utils import config as _config
        overlap = bool(getattr(dist_opt.update, "overlap", False)) \
            or _config.overlap_enabled()
    if overlap and not getattr(dist_opt.update, "supports_grad_order",
                               False):
        raise ValueError(
            "overlap=True (or HVD_OVERLAP=1) requires a "
            "DistributedOptimizer-wrapped optimizer: the backward-"
            "completion order is threaded into its fused collective "
            "traversal (the grad_order channel); a plain optax "
            "transformation has no collectives to overlap (wrap it with "
            "horovod_tpu.DistributedOptimizer(...))")
    mesh = mesh if mesh is not None else runtime.mesh()
    if _value_and_grad is not None:
        if remat:
            raise ValueError(
                "a custom _value_and_grad owns its own remat policy "
                "(wrap the loss before differentiating) — "
                "make_train_step(remat=) only applies to the flax model "
                "path")
        vag = _value_and_grad
    else:
        vag = _build_value_and_grad(model, loss_fn, remat)

    if hybrid:
        hybrid_axes = tuple(mesh.axis_names)
        if batch_spec is None:
            ba = tuple(a for a in ("dp", "ep") if a in hybrid_axes)
            batch_spec = P(ba if len(ba) > 1
                           else (ba[0] if ba else None))
        # Dropout rng folds the BATCH-plane position (dp/sp/ep) only: tp
        # ranks replicate the same rows and must draw identical masks or
        # the activations they exchange would diverge.
        rng_axes = tuple(
            a for e in batch_spec if e is not None
            for a in ((e,) if isinstance(e, str) else e))
        metric_axes: Any = hybrid_axes
    else:
        rng_axes = (axis_name,)
        metric_axes = axis_name

    # Backward-completion probe (overlap mode): one abstract trace per
    # input-shape signature, host-side and OUTSIDE the step trace, so the
    # jitted program reads a plain static tuple. The order is a pure
    # function of the traced program — identical across processes and
    # across re-traces of the same shapes, so the jit cache key does not
    # need to carry it.
    _overlap_probe: dict = {"key": None, "order": None}

    def _probe_overlap(state, inputs, labels):
        if not overlap:
            return None
        key = (
            tuple((tuple(jnp.shape(l)), str(jnp.result_type(l)))
                  for l in jax.tree_util.tree_leaves(state.params)),
            tuple((tuple(jnp.shape(l)), str(jnp.result_type(l)))
                  for l in jax.tree_util.tree_leaves((inputs, labels))),
        )
        if key != _overlap_probe["key"]:
            from .ops.fusion import probe_grad_order
            _overlap_probe["order"] = probe_grad_order(
                lambda p: vag(p, state.batch_stats, inputs, labels,
                              jax.random.PRNGKey(0))[1], state.params)
            _overlap_probe["key"] = key
        return None

    def _overlap_kwargs(grads):
        """Static grad_order kwarg for the optimizer update (trace time).
        Falls back to flatten order — plan-order emission with barrier
        pins, still unmergeable and deterministic — when the probe could
        not rank the leaves or the tree carries sparse leaves (whose
        flatten arity differs from the probe's)."""
        if not overlap:
            return {}
        from .optimizer import _is_sparse_leaf
        n = len(jax.tree_util.tree_leaves(grads, is_leaf=_is_sparse_leaf))
        order = _overlap_probe["order"]
        if order is None or len(order) != n:
            order = tuple(range(n))
        return {"grad_order": order}

    def _step(state: TrainState, inputs, labels):
        # Fresh dropout mask per step and per rank: fold the step counter
        # and rank into the key (identical masks every step would starve
        # the dropped units of gradient for the whole run). On the hybrid
        # plane only the batch-plane axes fold in (tp ranks share masks).
        step_rng = jax.random.fold_in(jax.random.PRNGKey(0), state.step)
        for _a in rng_axes:
            step_rng = jax.random.fold_in(
                step_rng, jax.lax.axis_index(_a))
        if accum_steps == 1:
            (loss, (logits, new_stats)), grads = vag(
                state.params, state.batch_stats, inputs, labels, step_rng)
            extras = (metrics_fn(logits, labels)
                      if metrics_fn is not None else None)
        else:
            loss, new_stats, grads, extras = _accumulate_grads(
                vag, state.params, state.batch_stats, inputs, labels,
                lambda i: jax.random.fold_in(step_rng, i),
                accum_steps, metrics_fn, unroll=accum_unroll)
        # DistributedOptimizer performs the fused allreduce over `axis_name`
        # — on the accumulated (microbatch-mean) tree, once per step.
        upd_kwargs = _overlap_kwargs(grads)
        if getattr(vag, "presynced", None) is not None:
            # Leaves the custom value-and-grad reduced inside its backward
            # (parallel/transformer.py): the optimizer must not again.
            upd_kwargs["presynced"] = vag.presynced
        with jax.named_scope("optimizer"):
            if guard_nonfinite:
                finite_out: dict = {}
                updates, new_opt_state = dist_opt.update(
                    grads, state.opt_state, state.params,
                    finite_out=finite_out, **upd_kwargs)
                all_finite = finite_out["all_finite"]
            else:
                updates, new_opt_state = dist_opt.update(
                    grads, state.opt_state, state.params, **upd_kwargs)
            new_params = optax.apply_updates(state.params, updates)
        new_stats = new_stats if new_stats is not None else state.batch_stats
        metrics = {"loss": jax.lax.pmean(loss, metric_axes)}
        if extras is not None:
            metrics.update(jax.tree_util.tree_map(
                lambda m: jax.lax.pmean(m, metric_axes), extras))
        if guard_nonfinite:
            # Skip-step select: a scalar where() per leaf, which XLA fuses
            # into the update elementwise ops — params/opt_state/batch_stats
            # are bit-unchanged when any replica saw NaN/Inf. all_finite is
            # replica-identical by construction (derived from the psum'd
            # buckets), so every replica takes the same branch and NO extra
            # collective is needed for the decision itself.
            def _keep(new, old):
                return jnp.where(all_finite, new, old)
            new_params = jax.tree_util.tree_map(
                _keep, new_params, state.params)
            new_opt_state = jax.tree_util.tree_map(
                _keep, new_opt_state, state.opt_state)
            if state.batch_stats is not None:
                new_stats = jax.tree_util.tree_map(
                    _keep, new_stats, state.batch_stats)
            # Metric hygiene: a skipped step's loss/extras are NaN-bearing
            # by definition — zero them so the trainer's epoch accumulator
            # stays finite (it divides by the GOOD step count), and expose
            # the flag itself (already identical on every replica; a pmean
            # here would add the very all-reduce the guard is pinned not
            # to add).
            metrics = jax.tree_util.tree_map(
                lambda m: jnp.where(all_finite, m,
                                    jnp.zeros_like(m)), metrics)
            metrics["bad_step"] = 1.0 - all_finite.astype(jnp.float32)
        new_state = TrainState(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt_state,
            batch_stats=new_stats,
        )
        return new_state, metrics

    if hybrid:
        # Hybrid plane: one jit per state structure, specs resolved lazily
        # from the live state (the opt-state layout is only known once the
        # state exists — same pattern as the 1-D ZeRO plane below).
        ba0 = batch_spec[0] if len(batch_spec) else None
        lead_axes = () if ba0 is None else (
            (ba0,) if isinstance(ba0, str) else tuple(ba0))
        n_lead = 1
        for _a in lead_axes:
            n_lead *= int(mesh.shape[_a])
        _hy_exec: dict = {}

        def _hy_jitted(state: TrainState):
            key = (jax.tree_util.tree_structure(state.params),
                   jax.tree_util.tree_structure(state.opt_state),
                   state.batch_stats is not None)
            fn = _hy_exec.get(key)
            if fn is None:
                pspecs = param_specs(state.params) \
                    if callable(param_specs) else param_specs
                ospecs = _hybrid_opt_specs(dist_opt, state.opt_state,
                                           pspecs)
                st_spec = TrainState(step=P(), params=pspecs,
                                     opt_state=ospecs, batch_stats=P())
                fn = jax.jit(
                    lambda s, x, y: jax.shard_map(
                        _step, mesh=mesh,
                        in_specs=(st_spec, batch_spec, batch_spec),
                        out_specs=(st_spec, P()),
                        check_vma=False,
                    )(s, x, y),
                    donate_argnums=(0,) if donate else ())
                _hy_exec[key] = fn
            return fn

        def hybrid_step(state: TrainState, batch):
            inputs, labels = batch
            if accum_steps > 1:
                _check_accum_batch(inputs, accum_steps, n_lead)
            _probe_overlap(state, inputs, labels)
            with _timeline.span("step.dispatch"):
                return _hy_jitted(state)(state, inputs, labels)

        hybrid_step.lower = lambda state, batch: (
            _probe_overlap(state, *batch)
            or _hy_jitted(state).lower(state, *batch))
        return hybrid_step

    def _sharded(state, inputs, labels):
        return jax.shard_map(
            _step, mesh=mesh,
            in_specs=(P(), P(axis_name), P(axis_name)),
            out_specs=P(),
            check_vma=False,
        )(state, inputs, labels)

    jitted = jax.jit(_sharded, donate_argnums=(0,) if donate else ())

    if _is_env_world(mesh):
        return _make_env_world_step(model, dist_opt, loss_fn, mesh,
                                    axis_name, metrics_fn,
                                    accum_steps=accum_steps,
                                    accum_unroll=accum_unroll, remat=remat,
                                    guard_nonfinite=guard_nonfinite,
                                    zero=zero)

    n_shards = int(mesh.shape[axis_name]) if accum_steps > 1 else 1

    if zero:
        # ZeRO plane: the optimizer state rides the step rank-sharded —
        # its stacked [size, shard] leaves get P(axis) in/out specs so
        # each device holds (and the donate reuses) 1/size of the bytes.
        # The spec tree depends on the wrapped optimizer's state
        # STRUCTURE, known only when the state first arrives; built once
        # per structure and cached.
        _zero_exec: dict = {}

        def _zero_jitted(state: TrainState):
            key = jax.tree_util.tree_structure(state.opt_state)
            fn = _zero_exec.get(key)
            if fn is None:
                ospec = _zero_state_spec(state.opt_state, axis_name)
                st_spec = TrainState(step=P(), params=P(),
                                     opt_state=ospec, batch_stats=P())
                fn = jax.jit(
                    lambda s, x, y: jax.shard_map(
                        _step, mesh=mesh,
                        in_specs=(st_spec, P(axis_name), P(axis_name)),
                        out_specs=(st_spec, P()),
                        check_vma=False,
                    )(s, x, y),
                    donate_argnums=(0,) if donate else ())
                _zero_exec[key] = fn
            return fn

        def step(state: TrainState, batch):
            inputs, labels = batch
            if accum_steps > 1:
                _check_accum_batch(inputs, accum_steps, n_shards)
            _probe_overlap(state, inputs, labels)
            with _timeline.span("step.dispatch"):
                return _zero_jitted(state)(state, inputs, labels)

        step.lower = lambda state, batch: (
            _probe_overlap(state, *batch)
            or _zero_jitted(state).lower(state, *batch))
        return step

    @functools.wraps(jitted)
    def step(state: TrainState, batch):
        inputs, labels = batch
        if accum_steps > 1:
            _check_accum_batch(inputs, accum_steps, n_shards)
        _probe_overlap(state, inputs, labels)
        with _timeline.span("step.dispatch"):
            return jitted(state, inputs, labels)

    # AOT handle (jax .lower convention): lets callers inspect the compiled
    # artifact — e.g. count the all-reduce ops to verify fusion bucketing
    # survived compilation (tests/test_fusion.py pins this; with
    # accum_steps > 1 the count proves the psum sits outside the scan).
    step.lower = lambda state, batch: (
        _probe_overlap(state, *batch) or jitted.lower(state, *batch))
    return step


def _zero_state_spec(opt_state, axis_name: str):
    """PartitionSpec tree for a ZeRO optimizer state: ``P(axis)`` on the
    stacked ``[nshards, shard_len]`` shard leaves (leading axis split one
    shard per rank), ``P()`` on everything else (scalars like Adam's step
    count stay replicated)."""
    from .optimizer import ZeroShardedState

    def _one(zs: ZeroShardedState):
        shard_shapes = set(zs.plan.shard_shapes())
        inner = jax.tree_util.tree_map(
            lambda l: P(axis_name)
            if tuple(getattr(l, "shape", ())) in shard_shapes else P(),
            zs.inner)
        return ZeroShardedState(inner=inner, plan=zs.plan)

    return jax.tree_util.tree_map(
        _one, opt_state,
        is_leaf=lambda x: isinstance(x, ZeroShardedState))


def _hybrid_opt_specs(dist_opt, opt_state, pspecs):
    """PartitionSpec tree for a hybrid-plane optimizer state. ZeRO states
    spec their stacked leaves by bucket (``P(dp, shard_axes)`` — the
    leaf→bucket mapping reuses the canonicalization's contiguous-run
    logic, since two buckets can share a stacked shape with different
    specs); replicated-update states mirror the PARAM specs leaf-for-leaf
    (a tp-sharded weight's momentum shards over tp too), with scalar
    state (Adam's count) replicated."""
    from .optimizer import ZeroShardedState, _zero_shard_leaf_buckets
    from .ops.fusion import zero_stacked_spec

    def _is_z(x):
        return isinstance(x, ZeroShardedState)

    if any(_is_z(l) for l in jax.tree_util.tree_leaves(
            opt_state, is_leaf=_is_z)):
        def _one(zs: "ZeroShardedState"):
            ids = _zero_shard_leaf_buckets(zs.inner, zs.plan)
            _, td = jax.tree_util.tree_flatten(zs.inner)
            specs = [P() if b is None else zero_stacked_spec(zs.plan, b)
                     for b in ids]
            return ZeroShardedState(inner=td.unflatten(specs),
                                    plan=zs.plan)
        return jax.tree_util.tree_map(_one, opt_state, is_leaf=_is_z)
    inner = getattr(dist_opt.update, "inner_transform", None) or dist_opt
    return optax.tree_map_params(
        inner, lambda _, s: s, opt_state, pspecs,
        transform_non_params=lambda _: P())


def _is_env_world(mesh) -> bool:
    """True in tpurun env-world mode: independent JAX processes whose world
    size (launcher env) exceeds the local mesh — compiled collectives cannot
    cross processes, so gradients must ride the host coordination plane
    (exactly the reference's model: per-process TF graphs + MPI allreduce)."""
    if not runtime.is_initialized():
        return False
    w = runtime.world()
    return w.env_world and w.coord is not None


def _env_wire_np(dist_opt):
    """Resolve the optimizer's wire stamp for the host coordination plane:
    bf16 payloads ride the coordinator wire natively (its reduction widens
    to f32 and narrows back — the same fp32-accumulation guarantee the
    compiled plane pins); fp8 has no host wire dtype and is rejected with
    the remedy named rather than silently training at full precision."""
    import numpy as np
    wire_name = getattr(dist_opt.update, "wire_dtype", "fp32")
    if wire_name == "fp8":
        raise ValueError(
            "wire_dtype='fp8' is compiled-plane only: the host "
            "coordinator wire carries bf16 (reduced with f32 "
            "accumulation) but has no fp8 dtype — use wire_dtype='bf16' "
            "under tpurun")
    if wire_name == "bf16":
        return np.dtype(jnp.bfloat16)
    return None


def _env_wire_cast(payload, wire_np):
    """Cast one host bucket payload onto the wire dtype; returns
    ``(payload, orig_dtype_or_None)`` — the receive side casts back so
    everything downstream of the wire stays full precision."""
    import numpy as np
    if (wire_np is not None
            and np.issubdtype(payload.dtype, np.floating)
            and payload.dtype.itemsize > wire_np.itemsize):
        return payload.astype(wire_np), payload.dtype
    return payload, None


_env_exchange_metrics = None


def _obs_exchange(n_submits: int, n_bytes: int, tag: int) -> None:
    """Host-plane collective telemetry for the env-world step: the
    compiled planes' collectives live inside XLA where nothing host-side
    can count them, but here every exchange IS a host submit — one
    counter bump per step (aggregated, not per bucket) plus a
    flight-recorder event, so a dead rank's post-mortem shows whether it
    died inside an exchange and how much wire the job was moving.

    ``tag`` is the 1-based exchange counter (the collective-name
    namespace), NOT the trainer's global step — the event deliberately
    records it under ``tag=`` so a dump's ``last_step`` (derived from
    the newest ``step``-bearing event) never misreports an exchange
    tag as a completed training step."""
    global _env_exchange_metrics
    if _env_exchange_metrics is None:
        from .obs.registry import registry as _registry_fn
        reg = _registry_fn()
        _env_exchange_metrics = (
            reg.counter("hvd_collective_submits_total",
                        "Host-plane collective submissions (env-world "
                        "gradient/metric exchanges)"),
            reg.counter("hvd_collective_bytes_total",
                        "Bytes submitted to host-plane collectives "
                        "(post wire-cast, padding included)"))
    _env_exchange_metrics[0].inc(n_submits)
    _env_exchange_metrics[1].inc(n_bytes)
    from .obs import flightrec
    flightrec.record("exchange", tag=tag, submits=n_submits,
                     bytes=n_bytes)


def _make_env_world_step(model, dist_opt, loss_fn, mesh, axis_name,
                         metrics_fn, accum_steps: int = 1,
                         accum_unroll: Optional[int] = None,
                         remat: Any = False,
                         guard_nonfinite: bool = False,
                         zero: bool = False):
    """Env-world train step: jit(grads) → host fused allreduce → jit(apply).

    The host gradient exchange INTERPRETS the gradient-sync plan stamped
    on the optimizer (``dist_opt.update.exchange_plan`` →
    :func:`~horovod_tpu.ops.fusion.plan_exchange`): the same ``GradSync``
    data the compiled executors read, so bucket membership and averaging
    denominators can never drift between the ICI-psum and
    coordinator-wire executors — one planner, two executors. Membership
    follows the same fusion scan as the compiled path (64 MiB /
    same-dtype / order-preserving, ``HOROVOD_FUSION_THRESHOLD``), so the
    reference's tensor-fusion contract (``docs/tensor-fusion.md``) holds
    for this plane too. ``accum_steps``
    scans microbatches inside the jitted gradient half exactly like the
    single-controller step, and the per-step host round trip count is
    unchanged — the accumulated tree rides one fused exchange, which is the
    whole point of ``backward_passes_per_step`` on a negotiated plane.

    ``guard_nonfinite`` checks the REDUCED host buckets (the averaged sum
    already carries every rank's NaN/Inf, so all ranks agree) and skips
    the jitted apply half entirely on a bad step — params/opt_state stay
    the same arrays, the step counter advances, and ``bad_step`` rides
    the metrics dict exactly like the compiled plane.

    ``zero`` routes the exchange through the coordinator's
    ``reducescatter`` instead: each rank receives the reduced 1/size
    slice of every fused bucket, updates its LOCAL optimizer-state shard
    (this process physically holds only its own ``[1, shard_len]`` slice
    — true 1/size host memory), and the updated shards ride one
    ``allgather`` back into the full update tree. Same bytes on the wire
    as the all-reduce (reduce-scatter + all-gather IS the ring
    all-reduce), two host rounds instead of one. With the guard, each
    rank's local finite verdict rides the update all-gather (one extra
    ELEMENT, not an extra collective) so every rank takes the same skip
    decision — a skipped step discards the speculative shard update and
    keeps opt state bit-unchanged.
    """
    from .ops.fusion import plan_exchange

    w = runtime.world()
    vag = _build_value_and_grad(model, loss_fn, remat)
    wire_np = _env_wire_np(dist_opt)
    # The stamped planner (DistributedOptimizer carries it); a plain
    # optax optimizer falls back to the same planner at default knobs.
    exchange_plan = getattr(dist_opt.update, "exchange_plan", None) \
        or plan_exchange

    def _grads(state: TrainState, inputs, labels):
        step_rng = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(0), state.step),
            w.controller_rank)
        if accum_steps == 1:
            (loss, (logits, new_stats)), grads = vag(
                state.params, state.batch_stats, inputs, labels, step_rng)
            extras = (metrics_fn(logits, labels)
                      if metrics_fn is not None else {})
        else:
            loss, new_stats, grads, extras = _accumulate_grads(
                vag, state.params, state.batch_stats, inputs, labels,
                lambda i: jax.random.fold_in(step_rng, i),
                accum_steps, metrics_fn, unroll=accum_unroll)
            extras = extras if extras is not None else {}
        return loss, extras, new_stats, grads

    def _apply(state: TrainState, grads, new_stats):
        updates, new_opt_state = dist_opt.update(
            grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        return TrainState(
            step=state.step + 1, params=new_params,
            opt_state=new_opt_state,
            batch_stats=new_stats if new_stats is not None
            else state.batch_stats)

    # Both halves run under shard_map over the 1-device local mesh so the
    # world axis is bound: models built with axis_name (cross-replica
    # BatchNorm) trace lax.pmean(AXIS) inside _grads, and dist_opt's
    # in-trace psum appears in _apply. Over one local device both are the
    # identity — the real cross-rank averaging is the host-plane fused
    # allreduce between the two calls.
    grads_jit = jax.jit(jax.shard_map(
        _grads, mesh=mesh, in_specs=(P(), P(AXIS), P(AXIS)),
        out_specs=P(), check_vma=False))
    apply_jit = jax.jit(jax.shard_map(
        _apply, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=False))
    counter = {"n": 0}

    if zero:
        return _make_env_world_zero_step(
            dist_opt, grads_jit, counter, w,
            accum_steps=accum_steps, guard_nonfinite=guard_nonfinite,
            wire_np=wire_np)

    def step(state: TrainState, batch):
        import numpy as np
        inputs, labels = batch
        if accum_steps > 1:
            _check_accum_batch(inputs, accum_steps, 1)
        loss, extras, new_stats, grads = grads_jit(state, inputs, labels)

        # Host-plane fused gradient averaging (the MPI_Allreduce analog).
        # Every bucket and metric is SUBMITTED before anything is waited on:
        # overlapped announcements negotiate concurrently and the
        # coordinator answers them in fused response frames — the
        # ComputeAsync concurrency model that feeds fusion in the reference
        # (mpi_ops.cc:1752-1772, 1395-1422). One synchronous round trip per
        # step instead of one per bucket.
        from .ops.collectives import Op
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        counter["n"] += 1
        tag = counter["n"]
        # Interpret the stamped GradSync plan: membership AND
        # denominators come from the one planner the compiled executors
        # read. The coordinator's AVERAGE op realizes denom == world
        # size; any other denominator rides an explicit post-scale.
        buckets, syncs = exchange_plan(leaves, world_size=w.size)
        handles = []
        wire_origs = []
        post_scale = []
        xbytes = 0
        for bi, bucket in enumerate(buckets):
            sync = syncs[bucket[0]]
            if len(bucket) == 1:
                payload = np.asarray(leaves[bucket[0]])
            else:
                payload = np.concatenate(
                    [np.ravel(np.asarray(leaves[j])) for j in bucket])
            payload, orig = _env_wire_cast(payload, wire_np)
            wire_origs.append(orig)
            if sync.denom == w.size:
                op, scale = Op.AVERAGE, None
            else:
                op, scale = Op.SUM, 1.0 / sync.denom
            post_scale.append(scale)
            xbytes += payload.nbytes
            handles.append(w.coord.submit(
                "allreduce", payload, f"grad.{tag}.{bi}", op=op))
        metric_handles = {"loss": w.coord.submit(
            "allreduce", np.asarray(loss, np.float32),
            f"metric.loss.{tag}", op=Op.AVERAGE)}
        for k, v in extras.items():
            metric_handles[k] = w.coord.submit(
                "allreduce", np.asarray(v, np.float32),
                f"metric.{k}.{tag}", op=Op.AVERAGE)
        _obs_exchange(len(handles) + len(metric_handles), xbytes, tag)

        reduced = [None] * len(leaves)
        all_finite = True
        for bi, bucket in enumerate(buckets):
            out = np.asarray(w.coord.wait(handles[bi]))
            if wire_origs[bi] is not None:
                # Off the wire, back to full precision: the coordinator
                # reduced the bf16 payload in f32 and narrowed once; the
                # gradient tree downstream stays in its original dtype.
                out = out.astype(wire_origs[bi])
            if post_scale[bi] is not None:
                # The plan's denominator, when the coordinator's AVERAGE
                # couldn't realize it directly.
                out = out * np.asarray(post_scale[bi], out.dtype)
            if guard_nonfinite and np.issubdtype(out.dtype, np.inexact):
                # Checked while still flat — one pass per REDUCED bucket,
                # mirroring the compiled plane's in-trace check. The
                # coordinator's average propagates any rank's NaN/Inf, so
                # this flag is identical on every rank by construction.
                all_finite = all_finite and bool(np.all(np.isfinite(out)))
            if len(bucket) == 1:
                j = bucket[0]
                reduced[j] = out.reshape(leaves[j].shape)
            else:
                off = 0
                for j in bucket:
                    n = leaves[j].size
                    reduced[j] = out[off:off + n].reshape(leaves[j].shape)
                    off += n
        grads = jax.tree_util.tree_unflatten(treedef, reduced)

        if guard_nonfinite and not all_finite:
            # Skip-step: drain the metric collectives (every rank
            # submitted them — the protocol must stay balanced), zero the
            # NaN-bearing values, advance only the step counter.
            for h in metric_handles.values():
                w.coord.wait(h)
            metrics = {k: np.zeros((), np.float32) for k in metric_handles}
            metrics["bad_step"] = np.ones((), np.float32)
            return dataclasses.replace(state, step=state.step + 1), metrics

        state = apply_jit(state, grads, new_stats)
        metrics = {k: w.coord.wait(h) for k, h in metric_handles.items()}
        if guard_nonfinite:
            metrics["bad_step"] = np.zeros((), np.float32)
        return state, metrics

    return step


def _make_env_world_zero_step(dist_opt, grads_jit, counter, w,
                              accum_steps: int,
                              guard_nonfinite: bool,
                              wire_np=None):
    """The ZeRO half of the env-world plane (see
    :func:`_make_env_world_step`): coordinator reduce-scatter → jitted
    local-shard optimizer update → coordinator all-gather of the updated
    shards (+ the guard's finite flag) → jitted apply. ``wire_np`` (bf16)
    casts the scatter payloads on send; the received shard is cast back
    to its original dtype BEFORE the jitted shard update — fp32 shard
    accumulation, mirroring the compiled plane — while the update
    all-gather stays full-precision so every rank rebuilds bit-identical
    params."""
    import numpy as np

    from .ops.collectives import Op
    from .optimizer import ZeroShardedState

    @jax.jit
    def zero_update_jit(state: TrainState, grad_shards):
        # plan is the state's static aux data — a trace-time constant.
        from .ops.fusion import shard_params
        plan = state.opt_state.plan
        gs = tuple(g.reshape(1, -1) for g in grad_shards)
        ps = tuple(p.reshape(1, -1) for p in shard_params(
            state.params, plan, rank=w.controller_rank))
        upd, new_inner = dist_opt.update.inner_update(
            gs, state.opt_state.inner, ps)
        return tuple(u.reshape(-1) for u in upd), new_inner

    @jax.jit
    def zero_apply_jit(state: TrainState, new_inner, updates, new_stats):
        new_params = optax.apply_updates(state.params, updates)
        return TrainState(
            step=state.step + 1, params=new_params,
            opt_state=ZeroShardedState(inner=new_inner,
                                       plan=state.opt_state.plan),
            batch_stats=new_stats if new_stats is not None
            else state.batch_stats)

    def step(state: TrainState, batch):
        from .ops.fusion import _unfuse_flat
        from .optimizer import _is_sparse_leaf
        inputs, labels = batch
        if accum_steps > 1:
            _check_accum_batch(inputs, accum_steps, 1)
        loss, extras, new_stats, grads = grads_jit(state, inputs, labels)

        if any(_is_sparse_leaf(l) for l in jax.tree_util.tree_leaves(
                grads, is_leaf=_is_sparse_leaf)):
            # This plane flattens grads itself (dist_opt.update's densify
            # wrapper is bypassed), so honor the stamp here — or fail with
            # the remedy named instead of a np.asarray TypeError below.
            if not getattr(dist_opt.update, "sparse_as_dense", False):
                raise ValueError(
                    "ZeRO sharded updates require dense gradients: an "
                    "IndexedSlices leaf cannot be flattened into "
                    "rank-sharded buckets — build the optimizer with "
                    "DistributedOptimizer(zero=True, sparse_as_dense="
                    "True), or use the replicated optimizer for sparse "
                    "models")
            grads = jax.tree_util.tree_map(
                lambda l: l.to_dense() if _is_sparse_leaf(l) else l,
                grads, is_leaf=_is_sparse_leaf)

        plan = state.opt_state.plan
        if plan.nshards != w.size:
            raise ValueError(
                f"ZeRO optimizer state was partitioned for a world of "
                f"{plan.nshards} but this env-world has {w.size} rank(s) "
                f"— initialize the state after hvd.init() under the "
                f"launcher (or restore through restore_sharded, which "
                f"re-shards)")
        leaves = plan.treedef.flatten_up_to(grads)
        counter["n"] += 1
        tag = counter["n"]
        # User-driven accumulation (DistributedOptimizer(accum_steps=N)):
        # fold the 1/N into the flat bucket before the scatter, exactly
        # where the compiled plane's prescale sits.
        pres = getattr(dist_opt.update, "accum_steps", 1)

        handles = []
        wire_origs = []
        xbytes = 0
        for bi, bucket in enumerate(plan.buckets):
            if len(bucket) == 1:
                flat = np.ravel(np.asarray(leaves[bucket[0]]))
            else:
                flat = np.concatenate(
                    [np.ravel(np.asarray(leaves[j])) for j in bucket])
            if pres > 1 and np.issubdtype(flat.dtype, np.inexact):
                if flat.dtype.itemsize < 4:
                    # Sub-fp32 buckets scale in fp32, one cast at the end
                    # (same rule as fusion._prescale_array).
                    flat = (flat.astype(np.float32)
                            * np.float32(1.0 / pres)).astype(flat.dtype)
                else:
                    flat = flat * flat.dtype.type(1.0 / pres)
            flat, orig = _env_wire_cast(flat, wire_np)
            wire_origs.append(orig)
            pad = plan.padded[bi] - plan.sizes[bi]
            if pad:
                flat = np.concatenate([flat, np.zeros(pad, flat.dtype)])
            xbytes += flat.nbytes
            handles.append(w.coord.submit(
                "reducescatter", flat, f"zgrad.{tag}.{bi}",
                op=Op.AVERAGE))
        metric_handles = {"loss": w.coord.submit(
            "allreduce", np.asarray(loss, np.float32),
            f"metric.loss.{tag}", op=Op.AVERAGE)}
        for k, v in extras.items():
            metric_handles[k] = w.coord.submit(
                "allreduce", np.asarray(v, np.float32),
                f"metric.{k}.{tag}", op=Op.AVERAGE)
        _obs_exchange(len(handles) + len(metric_handles), xbytes, tag)

        shards = [np.asarray(w.coord.wait(h)) for h in handles]
        shards = [s if wire_origs[bi] is None
                  else s.astype(wire_origs[bi])
                  for bi, s in enumerate(shards)]
        local_finite = True
        if guard_nonfinite:
            # Mirrors the compiled plane: the reduced shard carries every
            # rank's NaN/Inf for the slice THIS rank owns; the verdict
            # for the whole tree is the AND over ranks, which rides the
            # update all-gather below.
            for s in shards:
                if np.issubdtype(s.dtype, np.inexact):
                    local_finite = local_finite and \
                        bool(np.all(np.isfinite(s)))

        upd_shards, new_inner = zero_update_jit(
            state, tuple(jnp.asarray(s) for s in shards))

        flag_bucket = None
        if guard_nonfinite:
            flag_bucket = next(
                (i for i in range(len(plan.buckets))
                 if np.issubdtype(np.dtype(plan.dtypes[plan.buckets[i][0]]),
                                  np.inexact)), None)
        gather_handles = []
        for bi in range(len(plan.buckets)):
            payload = np.asarray(upd_shards[bi])
            if bi == flag_bucket:
                payload = np.concatenate(
                    [payload, np.asarray([1.0 if local_finite else 0.0],
                                         payload.dtype)])
            gather_handles.append(w.coord.submit(
                "allgather", payload, f"zupd.{tag}.{bi}"))

        flats = []
        all_finite = local_finite
        for bi in range(len(plan.buckets)):
            out = np.asarray(w.coord.wait(gather_handles[bi]))
            if bi == flag_bucket:
                s = plan.shard_len(bi)
                blocks = out.reshape(w.size, s + 1)
                all_finite = bool(np.all(
                    blocks[:, -1].astype(np.float64) > 0.5))
                out = blocks[:, :s].reshape(-1)
            flats.append(out[:plan.sizes[bi]])

        if guard_nonfinite and not all_finite:
            # Skip-step: the speculative shard update is discarded (opt
            # state stays the same arrays), the drained metrics keep the
            # protocol balanced, only the step counter advances.
            for h in metric_handles.values():
                w.coord.wait(h)
            metrics = {k: np.zeros((), np.float32) for k in metric_handles}
            metrics["bad_step"] = np.ones((), np.float32)
            return dataclasses.replace(state, step=state.step + 1), metrics

        updates = _unfuse_flat([jnp.asarray(f) for f in flats], plan)
        state = zero_apply_jit(state, new_inner, updates, new_stats)
        metrics = {k: w.coord.wait(h) for k, h in metric_handles.items()}
        if guard_nonfinite:
            metrics["bad_step"] = np.zeros((), np.float32)
        return state, metrics

    return step


def make_eval_step(model, *, mesh: Optional[jax.sharding.Mesh] = None,
                   axis_name: str = AXIS,
                   loss_fn: Callable = cross_entropy_loss):
    """Compiled eval step: globally averaged loss + accuracy (the analog of
    the reference's allreduced final eval,
    ``keras_imagenet_resnet50.py:150``)."""
    mesh = mesh if mesh is not None else runtime.mesh()

    def _eval(state: TrainState, inputs, labels):
        variables = {"params": state.params}
        if state.batch_stats is not None:
            variables["batch_stats"] = state.batch_stats
        logits = model.apply(variables, inputs, train=False)
        return {
            "loss": jax.lax.pmean(loss_fn(logits, labels), axis_name),
            "accuracy": jax.lax.pmean(accuracy(logits, labels), axis_name),
        }

    def _sharded(state, inputs, labels):
        return jax.shard_map(
            _eval, mesh=mesh,
            in_specs=(P(), P(axis_name), P(axis_name)),
            out_specs=P(),
            check_vma=False,
        )(state, inputs, labels)

    jitted = jax.jit(_sharded)

    if _is_env_world(mesh):
        # Independent processes: the in-step pmean is the identity over the
        # 1-device local mesh, so the cross-rank average must ride the host
        # plane — same split as the env-world train step. All metrics are
        # submitted before any is waited (they fuse).
        import numpy as np
        from .ops.collectives import Op
        w = runtime.world()
        counter = {"n": 0}

        def step(state: TrainState, batch):
            inputs, labels = batch
            local = jitted(state, inputs, labels)
            counter["n"] += 1
            tag = counter["n"]
            handles = {k: w.coord.submit(
                "allreduce", np.asarray(v, np.float32),
                f"evalmetric.{k}.{tag}", op=Op.AVERAGE)
                for k, v in local.items()}
            return {k: w.coord.wait(h) for k, h in handles.items()}

        return step

    def step(state: TrainState, batch):
        inputs, labels = batch
        return jitted(state, inputs, labels)

    return step


def shard_batch(batch, mesh: Optional[jax.sharding.Mesh] = None):
    """Place a global host batch onto the world, leading axis split across
    ranks. In env-world mode (independent processes) each process takes its
    own contiguous slice — the multi-process encoding of the same split."""
    return make_batch_placer(mesh)(batch)


def make_batch_placer(mesh: Optional[jax.sharding.Mesh] = None) -> Callable:
    """Build a reusable host-batch placer (the hoisted form of
    :func:`shard_batch`): the mesh lookup, env-world probe and
    ``NamedSharding`` construction happen ONCE, and the returned callable
    just ``device_put``s — so a per-batch loop (eval, prefetch) does no
    re-sharding bookkeeping on the host per batch."""
    mesh = mesh if mesh is not None else runtime.mesh()
    if _is_env_world(mesh):
        w = runtime.world()

        def _slice_batch(batch):
            def _slice(x):
                per = x.shape[0] // w.size
                r = w.controller_rank
                return jax.device_put(x[r * per:(r + 1) * per])
            return jax.tree_util.tree_map(_slice, batch)
        return _slice_batch
    sharding = NamedSharding(mesh, P(AXIS))

    def _place(batch):
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sharding), batch)
    return _place
