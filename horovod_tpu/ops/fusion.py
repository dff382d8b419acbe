"""Tensor fusion: bucket many small tensors into one flat collective.

Reference semantics (``docs/tensor-fusion.md:6-28``, fusion decision
``mpi_ops.cc:1395-1422``, data movement ``mpi_ops.cc:1024-1096``):

* Only tensors of the **same dtype** fuse (and same device set — moot here:
  everything lives on the world mesh).
* A bucket's total byte size is capped by the fusion threshold
  (default 64 MiB, ``mpi_ops.cc:165``; env ``HOROVOD_FUSION_THRESHOLD``,
  0 disables fusion, ``docs/tensor-fusion.md:24-28``).
* **Request order is preserved**: scanning stops at the first non-fusable
  tensor rather than skipping ahead (``mpi_ops.cc:1414-1419``), so fusion
  never reorders collectives.

TPU-native design: instead of memcpy loops into a persistent staging buffer,
bucketing happens at trace time — each bucket's members are flattened and
concatenated into one flat vector in HBM, reduced with a single XLA
``all-reduce`` over ICI, and split back. XLA fuses the (de)concatenation with
neighbors, so the "fusion buffer" never exists as a separate persistent
allocation. An oversized tensor becomes its own bucket (the reference
likewise falls back to a direct non-fused collective for tensors above the
threshold, ``mpi_ops.cc:1101-1105``).

The same bucket planner also feeds the ZeRO-1 sharded-update plane
(:class:`ZeroPlan`, :func:`fused_reduce_scatter`,
:func:`fused_allgather_params`): reduce-scatter + all-gather spend the same
bytes on the wire as the fused all-reduce while cutting optimizer-state
memory and update FLOPs by the world size (``docs/performance.md``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np

from ..runtime import AXIS
from ..utils import config as _config
from ..utils.compat import all_gather_invariant
from .collectives import Op, _reduce_in_trace


def _greedy_scan(key, order, fusion_threshold: int):
    """The fusion scan over leaves visited in ``order``: fuse while the
    dtype (and, on an N-D mesh, the reduce-axis group — see
    :func:`plan_grad_sync`) matches and cumulative bytes stay within the
    threshold; close the bucket at the first non-fusable tensor
    (``mpi_ops.cc:1414-1419`` — never look ahead, never reorder within the
    visit order). ``key[i]`` is ``(shape, dtype)`` or
    ``(shape, dtype, group)``; two leaves fuse only when BOTH dtype and
    group agree — a bucket rides exactly one collective, so its members
    must share the axes that collective reduces over."""
    buckets: List[List[int]] = []
    cur: List[int] = []
    cur_dtype = None
    cur_bytes = 0
    for i in order:
        shape, dtype = key[i][0], key[i][1:]
        nbytes = int(math.prod(shape)) * np.dtype(key[i][1]).itemsize
        fusable = (
            fusion_threshold > 0
            and cur
            and dtype == cur_dtype
            and cur_bytes + nbytes <= fusion_threshold
        )
        if fusable:
            cur.append(i)
            cur_bytes += nbytes
        else:
            if cur:
                buckets.append(cur)
            cur = [i]
            cur_dtype = dtype
            cur_bytes = nbytes
    if cur:
        buckets.append(cur)
    return tuple(tuple(b) for b in buckets)


@functools.lru_cache(maxsize=512)
def _plan_cached(key: Tuple[Tuple[Tuple[int, ...], str], ...],
                 fusion_threshold: int) -> Tuple[Tuple[int, ...], ...]:
    """The fusion scan, memoized. The plan is a pure function of the leaf
    (shape, dtype) sequence and the threshold, so repeated traces and
    eager per-step calls over the same gradient tree (every step of the
    env-world plane, every re-trace of the compiled one) stop re-walking
    the whole tree. Keyed on resolved values only — the env-var default
    is resolved by the caller, so changing ``HOROVOD_FUSION_THRESHOLD``
    between calls still takes effect."""
    return _greedy_scan(key, range(len(key)), fusion_threshold)


def plan_buckets(leaves: Sequence[jax.Array],
                 fusion_threshold: Optional[int] = None,
                 groups: Optional[Sequence[Any]] = None) -> List[List[int]]:
    """Partition leaf indices into fusion buckets, preserving order.

    Mirrors the coordinator's fusion scan (``mpi_ops.cc:1395-1422``): walk the
    queue in order; fuse while dtype matches and cumulative bytes stay within
    the threshold; close the bucket at the first non-fusable tensor.
    ``fusion_threshold=0`` disables fusion (one bucket per tensor).

    ``groups`` (optional, one hashable per leaf) adds a second fusion key
    next to dtype: leaves fuse only within the same group. This is how the
    N-D mesh plane keeps tp-sharded weight gradients (psum over ``dp``
    only) out of the buckets carrying replicated leaves (psum over the
    full mesh) — a bucket rides ONE collective, so its members must agree
    on the reduce axes (:func:`plan_grad_sync` builds the keys).

    The scan is cached per ``(shapes, dtypes, groups, threshold)`` — see
    :func:`_plan_cached`; callers get a fresh mutable copy each call, so
    mutating a returned plan cannot poison the cache.
    """
    if fusion_threshold is None:
        fusion_threshold = _config.fusion_threshold_bytes()
    if groups is None:
        key = tuple((tuple(leaf.shape), str(jnp.dtype(leaf.dtype)))
                    for leaf in leaves)
    else:
        if len(groups) != len(leaves):
            raise ValueError(
                f"groups must align with leaves: {len(groups)} group keys "
                f"for {len(leaves)} leaves")
        key = tuple((tuple(leaf.shape), str(jnp.dtype(leaf.dtype)), g)
                    for leaf, g in zip(leaves, groups))
    return [list(b) for b in _plan_cached(key, int(fusion_threshold))]


# ---------------------------------------------------------------------------
# Backward-overlapped emission (ISSUE 6 tentpole; the core Horovod trick,
# Sergeev & Del Balso 2018 §3): issue one collective per bucket AS ITS
# GRADIENTS COMPLETE instead of one fused traversal after backward. On the
# compiled plane the mechanism is data dependencies + optimization_barrier
# pins: buckets group leaves ADJACENT IN BACKWARD-COMPLETION ORDER (so a
# bucket's collective depends only on an early prefix of the backward), and
# each bucket's operand is barrier-chained to the previous bucket's result —
# which (a) fixes the issue order deterministically, (b) stops XLA's
# all-reduce combiner from re-merging the buckets into one post-backward
# blob, and (c) leaves XLA's latency-hiding scheduler free to hoist every
# collective behind the remaining backward compute (it does: the HLO pin in
# tests/test_overlap_wire.py shows each bucket's collective scheduled before
# the last backward op of the module).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BucketSchedule:
    """An ordered fusion plan: ``buckets`` are leaf-index groups (over the
    same flattened tree ``plan_buckets`` scans) built by walking the leaves
    in ``order`` — backward-completion order from
    :func:`probe_grad_order` — so bucket k's members finish together and
    its collective can fire while buckets k+1... are still back-propagating.
    A pure function of (shapes, dtypes, threshold, order): deterministic
    across processes and across cache hits."""

    buckets: Tuple[Tuple[int, ...], ...]
    order: Tuple[int, ...]
    threshold: int


@functools.lru_cache(maxsize=512)
def _schedule_cached(key, order, fusion_threshold: int):
    return _greedy_scan(key, order, fusion_threshold)


def plan_schedule(leaves: Sequence[jax.Array],
                  grad_order: Optional[Sequence[int]] = None,
                  fusion_threshold: Optional[int] = None,
                  groups: Optional[Sequence[Any]] = None) -> BucketSchedule:
    """Build the overlap emission schedule for ``leaves``.

    ``grad_order`` is the backward-completion permutation of leaf indices
    (:func:`probe_grad_order`); None falls back to flatten order, which
    degrades to the non-overlapped grouping. ``groups`` adds the same
    per-leaf reduce-axis fusion key :func:`plan_buckets` takes — on an N-D
    mesh leaves only fuse within their spec group. Same caching contract
    as :func:`plan_buckets` — keyed on resolved (shapes, dtypes, groups,
    order, threshold), so an env-var threshold flip between calls still
    invalidates."""
    if fusion_threshold is None:
        fusion_threshold = _config.fusion_threshold_bytes()
    if groups is None:
        key = tuple((tuple(leaf.shape), str(jnp.dtype(leaf.dtype)))
                    for leaf in leaves)
    else:
        key = tuple((tuple(leaf.shape), str(jnp.dtype(leaf.dtype)), g)
                    for leaf, g in zip(leaves, groups))
    order = (tuple(range(len(key))) if grad_order is None
             else tuple(int(i) for i in grad_order))
    if sorted(order) != list(range(len(key))):
        raise ValueError(
            f"grad_order must be a permutation of the {len(key)} leaf "
            f"indices; got {order}")
    return BucketSchedule(
        buckets=_schedule_cached(key, order, int(fusion_threshold)),
        order=order, threshold=int(fusion_threshold))


def probe_grad_order(grad_fn, *args, **kwargs) -> Optional[Tuple[int, ...]]:
    """Backward-completion order of a gradient tree's leaves, from a
    one-time abstract trace (no FLOPs): ``grad_fn(*args)`` must return the
    grad tree; each output leaf is ranked by the position of its defining
    equation in the traced jaxpr — the order the backward pass materializes
    it. Leaves whose producer cannot be identified (literals, forwarded
    inputs, leaves fused into one opaque sub-jaxpr such as a rolled scan)
    keep flatten order as a stable tie-break, so the probe degrades to the
    non-overlapped schedule rather than guessing. Returns None when the
    function cannot be traced outside its collective context (e.g. a model
    with cross-replica BatchNorm probed without its axis bound) — callers
    fall back to flatten order."""
    try:
        closed = jax.make_jaxpr(grad_fn)(*args, **kwargs)
    except Exception:  # noqa: BLE001 — probe is best-effort by contract
        return None
    jaxpr = closed.jaxpr
    pos = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            pos[v] = i
    outvars = jaxpr.outvars

    def _rank(k):
        v = outvars[k]
        # Literal outvars (e.g. the zero cotangent of a leaf the loss never
        # reads) take the flatten-order fallback, same as any other
        # unrankable leaf.
        if not isinstance(v, jax.extend.core.Var):
            return (-1, k)
        return (pos.get(v, -1), k)

    return tuple(sorted(range(len(outvars)), key=_rank))


@functools.lru_cache(maxsize=512)
def _emit_order_cached(buckets, grad_order):
    ready = []
    pos = {leaf: p for p, leaf in enumerate(grad_order)}
    for b in buckets:
        ready.append(max(pos.get(j, j) for j in b))
    return tuple(sorted(range(len(buckets)),
                        key=lambda i: (ready[i], i)))


def zero_emit_order(plan: "ZeroPlan",
                    grad_order: Optional[Sequence[int]]) -> Tuple[int, ...]:
    """Emission order of a :class:`ZeroPlan`'s buckets under overlap:
    sorted by READINESS (the latest backward-completion position among the
    bucket's members). Unlike the all-reduce plane's
    :class:`BucketSchedule`, ZeRO bucket MEMBERSHIP never changes — the
    plan defines the sharded optimizer-state layout and the world-agnostic
    checkpoint form, so overlap may only reorder which bucket's
    reduce-scatter issues first, never regroup leaves."""
    if grad_order is None:
        return tuple(range(len(plan.buckets)))
    return _emit_order_cached(plan.buckets, tuple(int(i)
                                                  for i in grad_order))


def _barrier_chain(operand, prev):
    """Pin emission order: barrier the next bucket's operand against the
    previous bucket's reduced result. Creates the data dependency that (a)
    makes the cross-bucket issue order deterministic and (b) keeps XLA's
    collective combiner from merging the per-bucket collectives back into
    one post-backward blob (combining requires independence)."""
    if prev is None:
        return operand
    operand, _ = jax.lax.optimization_barrier((operand, prev))
    return operand


def _fuse(leaves: Sequence[jax.Array]) -> jax.Array:
    return jnp.concatenate([jnp.ravel(l) for l in leaves])


def _unfuse(flat: jax.Array, leaves: Sequence[jax.Array]) -> List[jax.Array]:
    out = []
    offset = 0
    for l in leaves:
        n = int(math.prod(l.shape))
        out.append(jnp.reshape(flat[offset:offset + n], l.shape))
        offset += n
    return out


def _prescale_array(x, prescale):
    """Scale one flat/bucketed array before its collective. Dtype-preserving
    on the outside (the result returns in the operand dtype, so bf16 buckets
    stay bf16 on the wire), but sub-fp32 buckets are scaled IN fp32 — a
    bf16 multiply quantizes the scale itself (bf16(1/3) carries 8 mantissa
    bits) and double-rounds, so the fp32 product with a single final cast
    is strictly more accurate for the same wire bytes. Integer leaves pass
    through untouched — a fractional scale would silently floor them."""
    if prescale is None or not jnp.issubdtype(x.dtype, jnp.inexact):
        return x
    if jnp.dtype(x.dtype).itemsize < 4:
        return (x.astype(jnp.float32)
                * jnp.asarray(prescale, jnp.float32)).astype(x.dtype)
    return x * jnp.asarray(prescale, x.dtype)


# ---------------------------------------------------------------------------
# Low-precision wire formats: cast-on-send, fp32-accumulated results.
# The collective itself runs in the wire dtype (half/quarter the ICI bytes);
# every scale that touches the bucket (average's 1/size, accumulation's 1/N,
# fp8's dynamic scale) is applied in fp32 BEFORE the cast, and the reduced
# result is cast back to the bucket's original dtype immediately after — so
# everything downstream of the wire (shard updates, optimizer math) runs at
# full precision and the only loss is the one quantization on send.
# ---------------------------------------------------------------------------

# fp8 (e4m3) headroom: values are scaled so the WORST-CASE reduced sum
# (every rank at amax, same sign) lands at half of the 448 format max —
# range is cheap in e4m3 (17 binades) and the margin keeps rounding in the
# reduction from saturating into NaN (e4m3fn has no Inf).
_FP8_MARGIN = 224.0

_WIRE_ALIASES = {
    "bf16": "bfloat16", "bfloat16": "bfloat16",
    "fp8": "float8_e4m3fn", "fp8_e4m3": "float8_e4m3fn",
    "f8e4m3": "float8_e4m3fn", "float8_e4m3fn": "float8_e4m3fn",
}
_WIRE_NONE = (None, "", "none", "fp32", "f32", "float32")


def resolve_wire_dtype(spec):
    """Normalize a wire-format spec to a jnp dtype (or None = full
    precision). Accepts the knob spellings (``"bf16"``, ``"fp8"``), the
    canonical dtype names, actual dtypes, or None/``"fp32"``. Unknown
    specs raise eagerly with the supported set named — a typo must not
    silently train at full precision."""
    if spec in _WIRE_NONE:
        return None
    key = spec if isinstance(spec, str) else jnp.dtype(spec).name
    key = key.strip().lower()
    if key in _WIRE_NONE:
        return None
    name = _WIRE_ALIASES.get(key)
    if name is None:
        raise ValueError(
            f"unknown wire_dtype {spec!r}: supported are 'bf16', 'fp8' "
            f"(e4m3 with per-bucket dynamic scaling), or None/'fp32' for "
            f"full precision")
    return jnp.dtype(name)


def wire_dtype_name(wire) -> str:
    """Knob spelling of a resolved wire dtype (for stamps/JSON lines)."""
    w = resolve_wire_dtype(wire)
    if w is None:
        return "fp32"
    return "bf16" if w == jnp.dtype(jnp.bfloat16) else "fp8"


def _wire_applies(dtype, wire) -> bool:
    """A bucket rides the wire format only when it is float and strictly
    wider than the wire dtype — bf16 buckets under a bf16 wire are already
    at wire width (no cast), integers never quantize."""
    return (wire is not None
            and jnp.issubdtype(dtype, jnp.floating)
            and jnp.dtype(dtype).itemsize > jnp.dtype(wire).itemsize)


def _wire_exchange(flat, axis_names, wire, world, reduce_fn, prescale=None):
    """One wire-format reduction, shared by the all-reduce and ZeRO
    planes: fp32 prescale → (fp8: dynamic scale) → ONE cast on send →
    ``reduce_fn`` in the wire dtype → fp32 result, scale divided back out,
    cast to the original dtype — fp32 accumulation for everything
    downstream of the wire.

    fp8 additionally exchanges one scalar ``pmax`` per bucket (the only
    collective any wire format adds): the per-bucket dynamic scale must be
    identical on every rank or the scaled values would not share a unit,
    and the sum of ``world`` in-range values must stay in range — so the
    scale is ``margin / (world * global_amax)``, applied in fp32 and
    divided back out of the fp32 result."""
    orig = flat.dtype
    x = flat.astype(jnp.float32) if orig != jnp.float32 else flat
    if prescale is not None:
        x = x * jnp.asarray(prescale, jnp.float32)
    scale = None
    if jnp.dtype(wire).itemsize == 1:
        amax = jax.lax.pmax(jnp.max(jnp.abs(x)), axis_names)
        scale = jnp.where(amax > 0, _FP8_MARGIN / (world * amax), 1.0)
        x = x * scale
    out = reduce_fn(x.astype(wire)).astype(jnp.float32)
    if scale is not None:
        out = out / scale
    return out.astype(orig)


def _wire_sum(flat, axis_names, wire, prescale=None):
    """Wire-format psum over ``axis_names`` (see :func:`_wire_exchange`)."""
    world = 1
    for a in ((axis_names,) if isinstance(axis_names, str)
              else tuple(axis_names)):
        world *= int(jax.lax.axis_size(a))
    return _wire_exchange(
        flat, axis_names, wire, world,
        lambda w: jax.lax.psum(w, axis_names), prescale=prescale)


def _wire_scatter(flat, axis_name, wire, nshards, prescale=None):
    """Wire-format ``psum_scatter`` (see :func:`_wire_exchange`): this
    rank's shard comes back in the bucket's original dtype, so the
    optimizer update accumulates in fp32 even when the wire carried
    bf16/fp8."""
    return _wire_exchange(
        flat, axis_name, wire, nshards,
        lambda w: jax.lax.psum_scatter(w, axis_name, tiled=True),
        prescale=prescale)


# ---------------------------------------------------------------------------
# Axis-aware collective planning (ISSUE 8 tentpole): on an N-D named mesh
# ('dp', 'tp', ...) the per-leaf gradient-sync decision is a PLAN, not a
# hard-coded world axis. Each leaf's PartitionSpec determines (a) which axes
# its gradient must be summed over — every mesh axis the leaf is REPLICATED
# across — and (b) the averaging denominator, including the tp
# psum-transpose correction (under full-manual shard_map the transpose of
# the row-parallel psum is psum, so tp-sharded weight grads arrive
# multiplied by tp — the rule parallel/mesh.grad_sync_by_spec pinned
# empirically). Leaves group by that decision: tp-sharded weight grads psum
# over dp ONLY, replicated leaves keep the full-mesh path, and the two
# never share a bucket.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GradSync:
    """One leaf's gradient-sync decision on an N-D mesh (hashable — it is
    the fusion-group key :func:`plan_buckets` scans on).

    ``psum``: mesh axes the gradient is summed over (the leaf is
    replicated across exactly these). ``shard``: mesh axes the leaf itself
    is sharded over (``psum`` ∪ ``shard`` = the mesh axes minus
    ``skip_axes``). ``denom``: the averaging denominator — the product of
    the ``psum`` axis sizes times the tp correction for tp-sharded leaves.
    """

    psum: Tuple[str, ...]
    shard: Tuple[str, ...]
    denom: int


def _spec_axes(spec) -> set:
    """Mesh axis names a PartitionSpec references (entries may be a name,
    a tuple of names, or None)."""
    axes = set()
    for s in (spec or ()):
        if s is None:
            continue
        axes.update((s,) if isinstance(s, str) else s)
    return axes


def plan_grad_sync(specs: Sequence[Any], mesh,
                   *, skip_axes: Tuple[str, ...] = ()) -> List[GradSync]:
    """Per-leaf :class:`GradSync` for a flat list of ``PartitionSpec``s
    over ``mesh`` (a named N-D mesh). The decision mirrors
    ``parallel/mesh.grad_sync_by_spec`` exactly — psum over every mesh
    axis the leaf is replicated across (minus ``skip_axes``), averaged by
    the product of those axis sizes, with the extra ``1/tp`` on tp-sharded
    leaves (the psum-transpose factor) and ``1/ep`` on ep-sharded ones (the
    exchange's sum over the ranks' batches) folded into ``denom`` so the whole
    correction rides the bucket's one fused prescale multiply."""
    mesh_axes = tuple(mesh.axis_names)
    sizes = dict(mesh.shape)
    out = []
    for spec in specs:
        leaf_axes = _spec_axes(spec)
        over = tuple(a for a in mesh_axes
                     if a not in leaf_axes and a not in skip_axes)
        shard = tuple(a for a in mesh_axes
                      if a in leaf_axes and a not in skip_axes)
        denom = 1
        for a in over:
            denom *= int(sizes[a])
        if "tp" in leaf_axes and "tp" in sizes:
            denom *= int(sizes["tp"])
        if "ep" in leaf_axes and "ep" in sizes:
            # Experts' gradients arrive summed over the ep ranks whose
            # tokens they served (each from its own batch's mean loss).
            denom *= int(sizes["ep"])
        out.append(GradSync(psum=over, shard=shard, denom=denom))
    return out


def plan_exchange(leaves: Sequence[Any], *, world_size: int,
                  axis_name: str = AXIS,
                  fusion_threshold: Optional[int] = None):
    """The host-plane (env-world) view of the gradient-sync plan: the
    SAME :class:`GradSync` data the compiled executors interpret,
    specialized to the coordinator's 1-D world. Every rank computes a
    full local gradient — every leaf is replicated across the whole
    world — so each leaf's decision is
    ``GradSync(psum=(axis_name,), shard=(), denom=world_size)`` and
    bucket membership comes from the same fusion scan
    (:func:`plan_buckets` with the sync as the group key; one group, so
    the scan degrades to the classic dtype+threshold walk and existing
    bucket layouts are unchanged). Returns ``(buckets, syncs)``.

    One planner, two executors: the compiled plane realizes a sync with
    ``lax.psum`` + a ``1/denom`` prescale; the host executor realizes
    the identical denominator through the coordinator's AVERAGE op (an
    explicit post-scale if a future planner's denom ever disagrees with
    the world size) — membership and denominators can never drift
    between the two because both read this object."""
    syncs = [GradSync(psum=(axis_name,), shard=(),
                      denom=int(world_size)) for _ in leaves]
    return plan_buckets(leaves, fusion_threshold, groups=syncs), syncs


def _count_bucket(where: str) -> None:
    """``hvd_grad_sync_buckets_total{where=}``, at trace time: one tick for
    each gradient bucket the plan gives a collective, by where the plan
    issues it: ``backward`` (:func:`reduce_in_backward`) or ``after``."""
    from ..obs.registry import registry
    registry().counter(
        "hvd_grad_sync_buckets_total",
        "traces of a gradient bucket's collective, by where the plan "
        "issues it: inside the backward pass or after it",
        labels=("where",)).labels(where=where).inc()


def _reduce_one(operand, sync: GradSync, prescale, wire):
    """One operand's exchange by its :class:`GradSync`: the ``1/denom``
    average folded into the fp32 prescale, then one ``psum`` over the
    sync's axes in the operand's dtype or the wire's. An operand that is
    sharded over every axis is only scaled."""
    eff = prescale
    if sync.denom > 1:
        inv = 1.0 / sync.denom
        eff = inv if eff is None else eff * inv
    if not sync.psum:
        return _prescale_array(operand, eff)
    if _wire_applies(operand.dtype, wire):
        return _wire_sum(operand, sync.psum, wire, prescale=eff)
    return jax.lax.psum(_prescale_array(operand, eff), sync.psum)


# Leaves up to this size share an operand inside a backward bucket (norm
# scales, biases: the copy is nothing); a larger leaf rides alone, uncopied.
_SMALL_LEAF_BYTES = 1 << 20


def _fusion_groups(syncs: Sequence[GradSync]) -> List[Any]:
    """The fusion-group key of each leaf: its :class:`GradSync` (frozen and
    hashable, so the allreduce and ZeRO planes cannot drift on what "same
    group" means; plan_zero passes the same objects). A leaf with nothing
    to exchange (sharded over every axis, or reduced in the backward
    already) is a group of its own: fusing it would be a copy for no
    collective."""
    return [s if s.psum else (s, i) for i, s in enumerate(syncs)]


def _backward_operands(leaves, syncs: Sequence[GradSync]):
    """The operands of one backward bucket, as leaf-index groups in issue
    order."""
    return plan_buckets(leaves, _SMALL_LEAF_BYTES,
                        groups=_fusion_groups(syncs))


def backward_carry(tree, syncs: Sequence[GradSync]):
    """The value :func:`reduce_in_backward` threads through the forward,
    from the lowest layer to the highest: zeros shaped like the last
    operand a layer's bucket exchanges, which no forward op reads. Its
    COTANGENT runs through the backward the other way and IS that operand's
    reduced result: what the next bucket's first collective, and the
    backward below it, are made to wait for. (A constant scalar would do
    for jax; the TPU compiler forwards it through the barriers and the
    chain is gone.) Layers must be uniform: one carry serves them all."""
    leaves = jax.tree_util.tree_leaves(tree)
    exchanged = [m for m in _backward_operands(leaves, syncs)
                 if syncs[m[0]].psum]
    if not exchanged:
        raise ValueError("no leaf of the tree is exchanged: nothing to "
                         "reduce in the backward")
    last = [leaves[j] for j in exchanged[-1]]
    shape = last[0].shape if len(last) == 1 \
        else (sum(int(math.prod(l.shape)) for l in last),)
    return jnp.zeros(shape, last[0].dtype)


def reduce_in_backward(tree, x, carry, syncs: Sequence[GradSync],
                       bucket: int, *, wire=None):
    """Identity on ``(tree, x, carry)`` whose BACKWARD is bucket ``bucket``
    of the gradient exchange.

    The cotangent of ``tree`` (a layer's parameters, so the layer's
    complete gradient, as soon as the layer's backward has produced it) is
    reduced there as ``syncs`` say (:func:`plan_grad_sync` of the tree's
    specs). Each leaf over ``_SMALL_LEAF_BYTES`` is one collective of its
    own with no concatenation copy; the small ones share one. Every operand
    is barrier-chained on the result before it, and the first on the
    carry's cotangent, which is the last result of the bucket before
    (:func:`backward_carry`; nothing precedes bucket 0). So the whole
    exchange is ONE chain in backward order: the compiler's combiner cannot
    merge two links of it into a variadic all-reduce, which this libtpu
    leaves synchronous (PERF.md section 6, PR 31).

    ``x`` is the activation that enters the layer. The barrier that chains
    this bucket's first operand on the carry holds ``x``'s cotangent too,
    and the backward below needs that: so the bucket BEFORE this one must
    be done before the backward goes on below this layer, not only before
    the optimizer. Its operands were complete when the backward entered
    this layer; the layer's backward is the compute the compiler's
    scheduler runs that collective under (asynchronously where
    ``utils/chips.enable_async_collectives`` switched that on).

    Returns ``(tree, x, carry)``. The leaves come out of the gradient
    ALREADY reduced: tell the optimizer (``update(..., presynced=)``)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if len(syncs) != len(leaves):
        raise ValueError(
            f"syncs must align with the tree: {len(syncs)} GradSync "
            f"entries for {len(leaves)} leaves")
    wire = resolve_wire_dtype(wire)
    operands = _backward_operands(leaves, syncs)

    def _bwd(_, cts):
        g_leaves, g_x, carried = cts
        # Nothing precedes bucket 0: its carry is the zero nobody set.
        prev, tie = (carried, True) if bucket else (None, False)
        reduced = [None] * len(leaves)
        with jax.named_scope("optimizer"), \
                jax.named_scope(f"allreduce.bucket{bucket}"):
            for members in operands:
                sync = syncs[members[0]]
                gs = [g_leaves[j] for j in members]
                operand = gs[0] if len(gs) == 1 else _fuse(gs)
                if sync.psum and tie:
                    # One barrier, not two in a row: the compiler drops the
                    # unused half of a barrier that feeds a barrier. The
                    # cotangent goes through it as [rows, features]: as
                    # [B, T, d] the TPU's layout assignment gave the
                    # barrier's operand T-minor tiles and carried them into
                    # every activation of the step (15 ms of 408 on the
                    # chip, PERF.md section 6, PR 31); merging the leading
                    # dims is a bitcast and leaves it no such choice.
                    shape = g_x.shape
                    operand, g_x, _ = jax.lax.optimization_barrier(
                        (operand, g_x.reshape(-1, shape[-1]), prev))
                    g_x, tie = g_x.reshape(shape), False
                elif sync.psum:
                    operand = _barrier_chain(operand, prev)
                r = _reduce_one(operand, sync, None, wire)
                if sync.psum:
                    prev = r
                for j, rr in zip(members,
                                 [r] if len(gs) == 1 else _unfuse(r, gs)):
                    reduced[j] = rr
            _count_bucket("backward")
        return tuple(reduced), g_x, prev

    tap = jax.custom_vjp(lambda *args: args)
    tap.defvjp(lambda *args: (args, None), _bwd)
    leaves, x, carry = tap(tuple(leaves), x, carry)
    return treedef.unflatten(leaves), x, carry


def _grouped_allreduce(leaves, treedef, syncs: Sequence[GradSync],
                       fusion_threshold, prescale, return_finite, wire,
                       overlap_on: bool, grad_order, first_bucket: int = 0):
    """The N-D (spec-grouped) half of :func:`fused_allreduce`: leaves
    bucket within their :class:`GradSync` group (same psum axes, same
    denominator), each bucket rides ONE ``lax.psum`` over its group's
    axes, and the group's ``1/denom`` average folds into the same fp32
    prescale multiply the accumulation scale uses.

    ``return_finite``: buckets psum'd over the FULL reduce set propagate
    any rank's NaN/Inf to every rank, so their flags are mesh-consistent
    for free; buckets reduced over a strict subset (tp-sharded weight
    grads, psum over dp only) leave per-rank flags — those are folded
    with one scalar ``pmin`` over the missing axes, the only collective
    the guard adds on the hybrid plane (documented in
    docs/performance.md; the 1-D plane stays at zero extra)."""
    groups = _fusion_groups(syncs)
    if overlap_on:
        order = None if grad_order is None \
            else tuple(int(i) for i in grad_order)
        buckets = [list(b) for b in
                   plan_schedule(leaves, order, fusion_threshold,
                                 groups=groups).buckets]
    else:
        buckets = plan_buckets(leaves, fusion_threshold, groups=groups)

    # The full reduce set: flags from buckets summed over all of it are
    # identical on every rank; anything less needs the pmin fold below.
    all_axes = set()
    for s in syncs:
        all_axes.update(s.psum)
    reduced: List[Optional[jax.Array]] = [None] * len(leaves)
    finite_full = jnp.ones((), jnp.bool_)
    finite_partial = jnp.ones((), jnp.bool_)
    missing_union: set = set()
    prev = None
    k = first_bucket - 1
    for bucket in buckets:
        sync = syncs[bucket[0]]
        # The same per-bucket scope as fused_allreduce's 1-D loop, numbered
        # over the buckets that exchange something.
        k += bool(sync.psum)
        with jax.named_scope(f"allreduce.bucket{k}") if sync.psum \
                else contextlib.nullcontext():
            if len(bucket) == 1:
                operand = leaves[bucket[0]]
            else:
                operand = _fuse([leaves[j] for j in bucket])
            if overlap_on and len(buckets) > 1:
                operand = _barrier_chain(operand, prev)
            r = _reduce_one(operand, sync, prescale, wire)
            if sync.psum:
                _count_bucket("after")
            if overlap_on:
                prev = r
            if return_finite and jnp.issubdtype(r.dtype, jnp.inexact):
                flag = jnp.all(jnp.isfinite(r))
                missing = all_axes - set(sync.psum)
                if missing:
                    finite_partial = finite_partial & flag
                    missing_union.update(missing)
                else:
                    finite_full = finite_full & flag
            if len(bucket) == 1:
                reduced[bucket[0]] = r
            else:
                members = [leaves[j] for j in bucket]
                for j, rr in zip(bucket, _unfuse(r, members)):
                    reduced[j] = rr
    out = treedef.unflatten(reduced)
    if not return_finite:
        return out
    if missing_union:
        finite_partial = jax.lax.pmin(
            finite_partial.astype(jnp.int32),
            tuple(sorted(missing_union))) > 0
    return out, finite_full & finite_partial


def fused_allreduce(tree, average: bool = True,
                    fusion_threshold: Optional[int] = None,
                    axis_name: str = AXIS,
                    prescale: Optional[float] = None,
                    return_finite: bool = False,
                    wire_dtype=None,
                    overlap: bool = False,
                    grad_order: Optional[Sequence[int]] = None,
                    reduce_axes: Optional[Sequence[GradSync]] = None,
                    first_bucket: int = 0):
    """Allreduce a pytree with fusion bucketing. Compiled-context only
    (it is the gradient hot path inside the jitted train step).

    ``reduce_axes`` (a per-leaf :class:`GradSync` list from
    :func:`plan_grad_sync`, aligned with the tree's flatten order) switches
    to the N-D spec-grouped plane: leaves bucket within their reduce-axis
    group, each bucket psums over ITS group's axes (tp-sharded weight grads
    over ``dp`` only; replicated leaves over the full mesh), and the
    group's averaging denominator — including the tp psum-transpose
    correction — folds into the bucket's one fused prescale. Requires
    ``average=True`` (the denominators define the averaging semantics) and
    dense leaves (sparse trees stay on the 1-D plane); ``axis_name`` is
    ignored in this mode. ``first_bucket`` numbers this call's bucket
    scopes from there on (the buckets :func:`reduce_in_backward` issued
    came first).

    Sparse (:class:`~horovod_tpu.ops.sparse.IndexedSlices`) leaves are kept
    whole and routed through the two-allgather sparse path — never flattened
    into dense buckets (their integer indices must not be summed).

    ``prescale`` multiplies every bucket by a scalar *before* the reduce —
    one fused multiply on the already-flattened bucket, not one per leaf —
    which is how gradient accumulation folds its ``1/accum_steps`` into the
    same traversal (the reference's ``backward_passes_per_step`` divides by
    the global microbatch count at the same point). The reduce is linear, so
    pre- and post-scaling are equivalent; prescaling keeps the bucketed tree
    the single thing the collective ever sees.

    ``return_finite=True`` returns ``(reduced_tree, all_finite)`` where
    ``all_finite`` is a scalar bool, True iff every float leaf of EVERY
    rank's input was finite — the in-jit bad-step guard's signal. It is
    folded into the same bucket traversal with **zero extra collectives**:
    the reduce is a sum, and IEEE754 sums propagate any NaN/Inf operand
    into the result (Inf−Inf pairs become NaN, overflow becomes Inf), so
    checking ``isfinite`` on each REDUCED bucket while still flat — one
    pass per bucket, before unfusing — sees every rank's poison through
    the psum that already happened. The flag is therefore identical on
    all replicas, which is exactly what a divergence-free skip-step
    decision needs.

    ``wire_dtype`` (``"bf16"`` / ``"fp8"``) puts float buckets on the wire
    in reduced precision: every scale is applied in fp32 before ONE cast on
    send, the collective runs in the wire dtype, and the result is cast
    back to the bucket's original dtype immediately after (fp32
    accumulation downstream; see :func:`_wire_sum` — fp8 adds one scalar
    ``pmax`` per bucket for its dynamic scale, the only extra collective
    any wire format introduces). The bucket PLAN is unchanged — a wire
    cast never merges or splits buckets.

    ``overlap=True`` (or a ``grad_order`` from :func:`probe_grad_order`)
    switches to the backward-overlapped emission: buckets group leaves by
    backward-completion order (:func:`plan_schedule`) and each bucket's
    collective is barrier-chained behind the previous one's result, so the
    per-bucket collectives issue as their gradients complete and XLA hides
    wire time behind the remaining backward compute. Same total collective
    count as the non-overlapped plan over the same leaf multiset — overlap
    reorders, never adds."""
    from .sparse import IndexedSlices, allreduce_indexed_slices

    wire = resolve_wire_dtype(wire_dtype)
    leaves, treedef = jax.tree_util.tree_flatten(
        tree, is_leaf=lambda x: isinstance(x, IndexedSlices))
    if not leaves:
        return (tree, jnp.ones((), jnp.bool_)) if return_finite else tree
    if reduce_axes is not None:
        if not average:
            raise ValueError(
                "reduce_axes= (the spec-grouped N-D plane) defines "
                "averaging semantics via per-group denominators — "
                "average=False has no meaning there")
        if any(isinstance(l, IndexedSlices) for l in leaves):
            raise ValueError(
                "reduce_axes= requires dense gradients: IndexedSlices "
                "leaves have no per-axis spec grouping (densify with "
                "sparse_as_dense=True)")
        if len(reduce_axes) != len(leaves):
            raise ValueError(
                f"reduce_axes must align with the gradient tree: "
                f"{len(reduce_axes)} GradSync entries for {len(leaves)} "
                f"leaves")
        return _grouped_allreduce(
            leaves, treedef, reduce_axes, fusion_threshold, prescale,
            return_finite, wire, overlap or grad_order is not None,
            grad_order, first_bucket)
    op = Op.AVERAGE if average else Op.SUM
    reduced: List[Optional[jax.Array]] = [None] * len(leaves)
    finite = jnp.ones((), jnp.bool_)

    def _check(x):
        nonlocal finite
        if return_finite and jnp.issubdtype(x.dtype, jnp.inexact):
            finite = finite & jnp.all(jnp.isfinite(x))

    dense_idx = [i for i, l in enumerate(leaves)
                 if not isinstance(l, IndexedSlices)]
    for i in (i for i in range(len(leaves)) if i not in dense_idx):
        s = leaves[i]
        if prescale is not None:
            s = IndexedSlices(_prescale_array(s.values, prescale),
                              s.indices, s.dense_shape)
        r = allreduce_indexed_slices(
            s, average=average, axis_name=axis_name)
        # Allgathered slices carry every rank's raw values, so a local
        # NaN is literally present in each rank's gathered copy.
        _check(r.values)
        reduced[i] = r

    dense = [leaves[i] for i in dense_idx]
    overlap_on = overlap or grad_order is not None
    if overlap_on:
        order_d = None
        if grad_order is not None:
            # Project the full-tree completion order onto the dense
            # subsequence (sparse leaves ride their own allgather path).
            full_to_dense = {fi: di for di, fi in enumerate(dense_idx)}
            order_d = tuple(full_to_dense[i] for i in grad_order
                            if i in full_to_dense)
        buckets = [list(b) for b in
                   plan_schedule(dense, order_d, fusion_threshold).buckets]
    else:
        buckets = plan_buckets(dense, fusion_threshold)

    prev = None
    for k, bucket in enumerate(buckets):
        # One scope per bucket of the plan: a device trace then says which
        # bucket a collective (and its fuse/unfuse copies) belongs to.
        with jax.named_scope(f"allreduce.bucket{k}"):
            if len(bucket) == 1:
                operand = dense[bucket[0]]
            else:
                operand = _fuse([dense[j] for j in bucket])
            if overlap_on and len(buckets) > 1:
                operand = _barrier_chain(operand, prev)
            if _wire_applies(operand.dtype, wire):
                eff = prescale
                if op is Op.AVERAGE:
                    inv = 1.0 / int(jax.lax.axis_size(axis_name))
                    eff = inv if eff is None else eff * inv
                r = _wire_sum(operand, axis_name, wire, prescale=eff)
            else:
                r = _reduce_in_trace(
                    _prescale_array(operand, prescale), op, axis_name)
            if overlap_on:
                prev = r
            _check(r)
            if len(bucket) == 1:
                reduced[dense_idx[bucket[0]]] = r
            else:
                members = [dense[j] for j in bucket]
                for j, rr in zip(bucket, _unfuse(r, members)):
                    reduced[dense_idx[j]] = rr
    out = jax.tree_util.tree_unflatten(treedef, reduced)
    return (out, finite) if return_finite else out


# ---------------------------------------------------------------------------
# ZeRO-1 sharded-update plane (Rajbhandari et al. 2020; Xu et al. 2020,
# "Automatic Cross-Replica Sharding of Weight Update Computation"): the same
# bucket planner that feeds the fused all-reduce instead feeds a
# reduce-scatter — every rank receives the REDUCED 1/N slice of each flat
# bucket, applies the optimizer update to its slice only, and the updated
# slices ride one all-gather back into the full tree. Bytes on the wire are
# unchanged (ring all-reduce = reduce-scatter + all-gather); optimizer-state
# memory and update FLOPs drop by the world size.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ZeroPlan:
    """Static layout of a tree's rank-sharded flat buckets.

    Everything here is trace-time constant (hashable, usable as pytree aux
    data): ``buckets`` are :func:`plan_buckets` index groups over the
    flattened tree, ``sizes``/``padded`` the true and rank-padded flat
    length per bucket (``padded[i]`` is the smallest multiple of
    ``nshards`` >= ``sizes[i]``, so ``lax.psum_scatter(tiled=True)`` splits
    evenly), ``shapes``/``dtypes`` the member leaves' layout for unfusing,
    and ``treedef`` the original tree structure.

    On an N-D mesh (``plan_zero(specs=, mesh=)``) the plan is keyed by the
    reduce-axis tuple of each leaf's PartitionSpec: buckets group within a
    spec group (tp-sharded weight grads never share a bucket with
    replicated leaves), ``shapes``/``sizes``/``padded`` describe the
    LOCAL (per-tp-shard) blocks the in-trace collectives see while
    ``global_shapes`` keeps the mesh-agnostic layout the 2-D canonical
    checkpoint form is defined on, and the per-bucket ``extra_axes`` /
    ``shard_axes`` / ``denoms`` record the group's collective plan:
    reduce-scatter over ``scatter_axis`` (dp), an extra psum over the axes
    the bucket is replicated across, averaged by the group denominator
    (including the tp psum-transpose correction). Bucket MEMBERSHIP is
    planned on global shapes, so it is identical across (dp, tp) reshapes
    of the same axis set."""

    buckets: Tuple[Tuple[int, ...], ...]
    sizes: Tuple[int, ...]
    padded: Tuple[int, ...]
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[str, ...]
    treedef: Any
    nshards: int
    # --- N-D (hybrid-mesh) extension; defaults = the 1-D world plan. ---
    scatter_axis: Optional[str] = None
    denoms: Optional[Tuple[int, ...]] = None
    extra_axes: Optional[Tuple[Tuple[str, ...], ...]] = None
    shard_axes: Optional[Tuple[Tuple[str, ...], ...]] = None
    nonscatter: Tuple[Tuple[str, int], ...] = ()
    leaf_specs: Optional[Tuple[Any, ...]] = None
    global_shapes: Optional[Tuple[Tuple[int, ...], ...]] = None

    @property
    def hybrid(self) -> bool:
        return self.leaf_specs is not None

    def shard_len(self, i: int) -> int:
        return self.padded[i] // self.nshards

    def bucket_denom(self, i: int) -> int:
        return self.nshards if self.denoms is None else self.denoms[i]

    def bucket_extra(self, i: int) -> Tuple[str, ...]:
        return () if self.extra_axes is None else self.extra_axes[i]

    def bucket_shard_axes(self, i: int) -> Tuple[str, ...]:
        return () if self.shard_axes is None else self.shard_axes[i]

    def bucket_ns(self, i: int) -> int:
        """Product of the sizes of the nonscatter axes bucket ``i``'s
        leaves are sharded over — the stacked array's tp-fold factor."""
        sizes = dict(self.nonscatter)
        n = 1
        for a in self.bucket_shard_axes(i):
            n *= int(sizes[a])
        return n

    def shard_shapes(self):
        """Per-bucket stacked-array shape: ``(nshards, shard_len)`` on the
        1-D world; ``(nshards, ns · shard_len)`` on a hybrid mesh, where
        ``ns`` folds the bucket's tp-like shard axes into the trailing dim
        (block ``[:, c·s:(c+1)·s]`` is nonscatter-coordinate ``c``'s dp
        stack). Replicated buckets keep ``ns == 1`` — their state is
        stored once and REPLICATED over tp by sharding, not materialized
        per tp rank."""
        return tuple((self.nshards, self.bucket_ns(i) * self.shard_len(i))
                     for i in range(len(self.buckets)))

    def canonical_sizes(self):
        """Per-bucket length of the world- AND mesh-agnostic canonical
        form: the flat concatenation of the bucket's GLOBAL leaves —
        identical no matter how the saving run split (dp, tp)."""
        if not self.hybrid:
            return self.sizes
        out = []
        for b in self.buckets:
            out.append(sum(int(math.prod(self.global_shapes[j]))
                           for j in b))
        return tuple(out)


def _local_shape(shape, spec, axis_sizes) -> Tuple[int, ...]:
    """The per-device block shape of a leaf laid out by ``spec`` (one mesh
    axis per dim at most — the Megatron layouts this plane supports)."""
    out = list(shape)
    for d, s in enumerate(spec or ()):
        if s is None:
            continue
        axes = (s,) if isinstance(s, str) else tuple(s)
        if len(axes) > 1:
            raise ValueError(
                f"ZeRO spec-grouped plans support one mesh axis per "
                f"tensor dim; got {spec} (dim {d} sharded over {axes})")
        n = int(axis_sizes[axes[0]])
        if out[d] % n:
            raise ValueError(
                f"dim {d} of shape {tuple(shape)} does not divide by the "
                f"{axes[0]}={n} mesh axis (spec {spec})")
        out[d] //= n
    return tuple(out)


def plan_zero(tree, nshards: int,
              fusion_threshold: Optional[int] = None,
              *, specs=None, mesh=None, scatter_axis: str = "dp",
              skip_axes: Tuple[str, ...] = ()) -> ZeroPlan:
    """Build the sharded-update layout for ``tree`` over ``nshards`` ranks.

    Sparse (:class:`~horovod_tpu.ops.sparse.IndexedSlices`) leaves cannot
    be flattened into rank-sharded dense buckets (their integer indices
    must not be summed, and a slice of a slice has no owner rank) — a tree
    carrying them raises; densify first (``sparse_as_dense``) or keep the
    replicated optimizer for sparse models.

    ``specs=`` + ``mesh=`` build the N-D (hybrid-mesh) plan: leaves group
    by their :class:`GradSync` spec group (:func:`plan_grad_sync`), bucket
    membership is scanned on GLOBAL shapes — so the plan (and therefore
    the canonical checkpoint form) is identical across (dp, tp) reshapes
    of the same axis names — and the optimizer state shards over
    ``scatter_axis`` (dp) for tp-sharded and replicated leaves alike.
    ``tree`` holds the global params; ``nshards`` must equal the mesh's
    ``scatter_axis`` size."""
    from .sparse import IndexedSlices
    leaves, treedef = jax.tree_util.tree_flatten(
        tree, is_leaf=lambda x: isinstance(x, IndexedSlices))
    if any(isinstance(l, IndexedSlices) for l in leaves):
        raise ValueError(
            "ZeRO sharded updates require dense gradients: an "
            "IndexedSlices leaf cannot be flattened into rank-sharded "
            "buckets (densify with sparse_as_dense=True, or use the "
            "replicated DistributedOptimizer for sparse models)")
    if nshards < 1:
        raise ValueError(f"nshards must be >= 1, got {nshards}")

    if specs is None:
        buckets = plan_buckets(leaves, fusion_threshold)
        sizes = []
        padded = []
        for b in buckets:
            n = sum(int(math.prod(leaves[j].shape)) for j in b)
            sizes.append(n)
            padded.append(-(-n // nshards) * nshards)
        return ZeroPlan(
            buckets=tuple(tuple(b) for b in buckets),
            sizes=tuple(sizes),
            padded=tuple(padded),
            shapes=tuple(tuple(l.shape) for l in leaves),
            dtypes=tuple(str(jnp.dtype(l.dtype)) for l in leaves),
            treedef=treedef,
            nshards=nshards,
        )

    if mesh is None:
        raise ValueError("plan_zero(specs=...) requires mesh= (the named "
                         "hybrid mesh the specs refer to)")
    if scatter_axis not in mesh.shape:
        raise ValueError(
            f"scatter_axis {scatter_axis!r} is not an axis of the mesh "
            f"{dict(mesh.shape)} — ZeRO shards the optimizer state over "
            f"the data-parallel axis")
    if nshards != int(mesh.shape[scatter_axis]):
        raise ValueError(
            f"nshards={nshards} does not match the mesh's "
            f"{scatter_axis}={mesh.shape[scatter_axis]} — the ZeRO shard "
            f"count IS the {scatter_axis} axis size on a hybrid mesh")
    from jax.sharding import PartitionSpec as P
    spec_leaves = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    if len(spec_leaves) != len(leaves):
        raise ValueError(
            f"param_specs tree has {len(spec_leaves)} specs for "
            f"{len(leaves)} parameter leaves — the trees must mirror")
    syncs = plan_grad_sync(spec_leaves, mesh, skip_axes=skip_axes)
    axis_sizes = dict(mesh.shape)
    for spec, sync in zip(spec_leaves, syncs):
        if scatter_axis not in sync.psum:
            raise ValueError(
                f"a parameter with spec {spec} is sharded over the "
                f"scatter axis {scatter_axis!r} — ZeRO-over-{scatter_axis}"
                f" requires params replicated across it (shard weights "
                f"over tp/sp/ep, data over {scatter_axis})")
    buckets = plan_buckets(leaves, fusion_threshold, groups=list(syncs))
    local_shapes = [
        _local_shape(l.shape, spec, axis_sizes)
        for l, spec in zip(leaves, spec_leaves)]
    sizes = []
    padded = []
    denoms = []
    extra = []
    shard_ax = []
    for b in buckets:
        n = sum(int(math.prod(local_shapes[j])) for j in b)
        sizes.append(n)
        padded.append(-(-n // nshards) * nshards)
        sync = syncs[b[0]]
        denoms.append(sync.denom)
        extra.append(tuple(a for a in sync.psum if a != scatter_axis))
        shard_ax.append(sync.shard)
    nonscatter = tuple(
        (a, int(axis_sizes[a])) for a in mesh.axis_names
        if a != scatter_axis and a not in skip_axes)
    return ZeroPlan(
        buckets=tuple(tuple(b) for b in buckets),
        sizes=tuple(sizes),
        padded=tuple(padded),
        shapes=tuple(local_shapes),
        dtypes=tuple(str(jnp.dtype(l.dtype)) for l in leaves),
        treedef=treedef,
        nshards=nshards,
        scatter_axis=scatter_axis,
        denoms=tuple(denoms),
        extra_axes=tuple(extra),
        shard_axes=tuple(shard_ax),
        nonscatter=nonscatter,
        leaf_specs=tuple(spec_leaves),
        global_shapes=tuple(tuple(l.shape) for l in leaves),
    )


def _fuse_bucket(leaves, plan: ZeroPlan, i: int):
    """Flatten bucket ``i``'s members into one rank-padded flat vector."""
    flat = _fuse([leaves[j] for j in plan.buckets[i]])
    pad = plan.padded[i] - plan.sizes[i]
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat


def fused_reduce_scatter(tree, plan: ZeroPlan, *,
                         average: bool = True,
                         axis_name: str = AXIS,
                         prescale: Optional[float] = None,
                         return_finite: bool = False,
                         wire_dtype=None,
                         emit_order: Optional[Sequence[int]] = None):
    """Reduce-scatter a pytree into this rank's flat bucket shards.

    Each bucket is flattened, zero-padded to a multiple of the world size,
    optionally prescaled (one fused multiply on the flat bucket — gradient
    accumulation's ``1/accum_steps`` and ``average``'s ``1/size`` fold into
    the same scalar), and fed to one ``lax.psum_scatter`` — rank ``r``
    receives the REDUCED slice ``flat[r*s:(r+1)*s]``. Returns the per-bucket
    shard list (order = plan order).

    ``return_finite=True`` additionally returns a **rank-local** all-finite
    scalar derived from the already-reduced shards: IEEE sums propagate any
    rank's NaN/Inf into the reduced value at that position, which lands on
    exactly one rank's shard — so the flag differs per rank and only the
    AND over ranks is the world-wide verdict. :func:`fused_allgather_params`
    folds that AND into the all-gather the updated shards already ride
    (``and_finite=``), keeping the bad-step guard at zero extra collectives
    in ZeRO mode too.

    ``wire_dtype`` (``"bf16"`` / ``"fp8"``) runs the scatter in reduced
    precision — fp32 prescale, one cast on send, and the received shard
    cast straight back to the bucket's dtype so the optimizer update
    accumulates in fp32 (:func:`_wire_scatter`). ``emit_order`` (a bucket
    permutation from :func:`zero_emit_order`) issues the scatters in
    backward-readiness order behind ``optimization_barrier`` pins — bucket
    MEMBERSHIP (and therefore the sharded state layout and the checkpoint
    canonical form) never changes, only which collective fires first. The
    returned shard list is always in PLAN order.

    Hybrid (N-D) plans: the scatter runs over the plan's ``scatter_axis``
    (dp) with the GROUP denominator — including the tp psum-transpose
    correction — folded into the fp32 prescale; buckets replicated across
    the nonscatter axes take one extra ``lax.psum`` over those axes on the
    already-scattered 1/dp shard (the cheapest place for the Megatron-side
    sum). With ``return_finite`` the rank-local flag is folded with one
    scalar ``pmin`` over the nonscatter axes — tp-sharded buckets take no
    tp collective, so a NaN there is visible to one tp rank only; the
    pmin is the only collective the guard adds on the hybrid plane (the
    1-D plane stays at zero extra).
    """
    wire = resolve_wire_dtype(wire_dtype)
    leaves = plan.treedef.flatten_up_to(tree)
    nb = len(plan.buckets)
    order = tuple(range(nb)) if emit_order is None \
        else tuple(int(i) for i in emit_order)
    if sorted(order) != list(range(nb)):
        raise ValueError(
            f"emit_order must be a permutation of the {nb} bucket "
            f"indices; got {order}")
    shards: List[Optional[jax.Array]] = [None] * nb
    finite = jnp.ones((), jnp.bool_)
    prev = None
    for i in order:
        scale = None
        denom = plan.bucket_denom(i)
        if average and denom > 1:
            scale = 1.0 / denom
        if prescale is not None:
            scale = prescale if scale is None else scale * prescale
        flat = _fuse_bucket(leaves, plan, i)
        if emit_order is not None and nb > 1:
            flat = _barrier_chain(flat, prev)
        if plan.nshards > 1:
            if _wire_applies(flat.dtype, wire):
                shard = _wire_scatter(flat, axis_name, wire, plan.nshards,
                                      prescale=scale)
            else:
                shard = jax.lax.psum_scatter(
                    _prescale_array(flat, scale), axis_name, tiled=True)
        else:
            # Single shard: the reduce is the identity, and nothing rides
            # the wire — no quantization round-trip either.
            shard = _prescale_array(flat, scale)
        extra = plan.bucket_extra(i)
        if extra:
            # Replicated-group bucket on a hybrid mesh: the tp-side sum,
            # taken on the 1/dp shard (dp-fold fewer elements than a
            # pre-scatter psum would touch).
            shard = jax.lax.psum(shard, extra)
        if emit_order is not None:
            prev = shard
        if return_finite and jnp.issubdtype(shard.dtype, jnp.inexact):
            finite = finite & jnp.all(jnp.isfinite(shard))
        shards[i] = shard
    if return_finite and plan.nonscatter:
        finite = jax.lax.pmin(
            finite.astype(jnp.int32),
            tuple(a for a, _ in plan.nonscatter)) > 0
    return (shards, finite) if return_finite else shards


def shard_params(tree, plan: ZeroPlan, *, axis_name: str = AXIS,
                 rank: Optional[int] = None):
    """Slice this rank's flat bucket shards out of a replicated pytree
    (no collective — each rank takes ``flat[rank*s:(rank+1)*s]``). The
    owner index is ``lax.axis_index`` in-trace, or the static ``rank``
    the env-world plane passes (one process = one shard)."""
    leaves = plan.treedef.flatten_up_to(tree)
    idx = jax.lax.axis_index(axis_name) if rank is None else rank
    shards = []
    for i in range(len(plan.buckets)):
        flat = _fuse_bucket(leaves, plan, i)
        s = plan.shard_len(i)
        if plan.nshards == 1:
            shards.append(flat)
        elif rank is None:
            shards.append(jax.lax.dynamic_slice(flat, (idx * s,), (s,)))
        else:
            shards.append(flat[rank * s:(rank + 1) * s])
    return shards


def _unfuse_flat(flats, plan: ZeroPlan):
    """Rebuild the original tree from per-bucket UNPADDED flat vectors."""
    reduced: List[Optional[jax.Array]] = [None] * len(plan.shapes)
    for i, bucket in enumerate(plan.buckets):
        flat = flats[i]
        offset = 0
        for j in bucket:
            n = int(math.prod(plan.shapes[j]))
            reduced[j] = jnp.reshape(flat[offset:offset + n], plan.shapes[j])
            offset += n
    return plan.treedef.unflatten(reduced)


def zero_stacked_spec(plan: ZeroPlan, i: int, axis_name: str = AXIS):
    """PartitionSpec of bucket ``i``'s stacked optimizer-state array:
    ``P(scatter)`` on the 1-D world (``axis_name``), ``P(dp, shard_axes)``
    on a hybrid mesh — the leading dim splits one shard per dp rank, the
    trailing dim splits over the tp-like axes the bucket's leaves are
    sharded over (replicated buckets leave it whole: their state is
    replicated over tp by SHARDING, not materialized per tp rank)."""
    from jax.sharding import PartitionSpec as P
    scatter = plan.scatter_axis if plan.scatter_axis is not None \
        else axis_name
    sa = plan.bucket_shard_axes(i)
    return P(scatter, sa) if sa else P(scatter)


def _ns_coords(plan: ZeroPlan, i: int):
    """Nonscatter coordinates of bucket ``i``'s shard axes, in the
    row-major order ``PartitionSpec(scatter, shard_axes)`` splits the
    stacked array's trailing dim — block ``[:, c·s:(c+1)·s]`` of the
    stacked array is coordinate ``c``'s dp stack."""
    import itertools
    axes = plan.bucket_shard_axes(i)
    sizes = dict(plan.nonscatter)
    for coord in itertools.product(*[range(int(sizes[a])) for a in axes]):
        yield dict(zip(axes, coord))


def _block_index(shape, spec, coord, axis_sizes):
    """Slice tuple selecting the local block of a global array at
    nonscatter coordinate ``coord`` under ``spec``."""
    idx = []
    for d in range(len(shape)):
        s = spec[d] if spec is not None and d < len(spec) else None
        if s is None:
            idx.append(slice(None))
            continue
        a = s if isinstance(s, str) else tuple(s)[0]
        if a not in coord:
            idx.append(slice(None))
            continue
        w = shape[d] // int(axis_sizes[a])
        idx.append(slice(coord[a] * w, (coord[a] + 1) * w))
    return tuple(idx)


def zero_stack_global(leaves, plan: ZeroPlan, i: int) -> np.ndarray:
    """Build bucket ``i``'s stacked optimizer-state array from GLOBAL
    leaves (host-side; init and checkpoint-restore both use it): for each
    nonscatter coordinate, slice the bucket members' local blocks, flatten
    + rank-pad + stack ``[nshards, shard_len]``, and concatenate the
    coordinates along the trailing dim. 1-D plans degrade to the plain
    flatten-pad-stack."""
    axis_sizes = dict(plan.nonscatter)
    s = plan.shard_len(i)
    pad = plan.padded[i] - plan.sizes[i]
    cols = []
    for coord in (_ns_coords(plan, i) if plan.hybrid else ({},)):
        parts = []
        for j in plan.buckets[i]:
            arr = np.asarray(leaves[j])
            if plan.hybrid:
                arr = arr[_block_index(arr.shape, plan.leaf_specs[j],
                                       coord, axis_sizes)]
            parts.append(np.ravel(arr))
        flat = np.concatenate(parts) if len(parts) > 1 else parts[0]
        if pad:
            flat = np.concatenate([flat, np.zeros((pad,), flat.dtype)])
        cols.append(flat.reshape(plan.nshards, s))
    return cols[0] if len(cols) == 1 else np.concatenate(cols, axis=1)


def zero_unstack_global(stacked, plan: ZeroPlan, i: int) -> List[np.ndarray]:
    """Inverse of :func:`zero_stack_global`: bucket ``i``'s GLOBAL leaves
    from its stacked ``[nshards, ns·shard_len]`` array."""
    axis_sizes = dict(plan.nonscatter)
    stacked = np.asarray(stacked)
    s = plan.shard_len(i)
    out = [np.zeros(plan.global_shapes[j] if plan.hybrid
                    else plan.shapes[j], stacked.dtype)
           for j in plan.buckets[i]]
    for ci, coord in enumerate(_ns_coords(plan, i) if plan.hybrid
                               else ({},)):
        flat = stacked[:, ci * s:(ci + 1) * s].reshape(-1)[:plan.sizes[i]]
        off = 0
        for k, j in enumerate(plan.buckets[i]):
            n = int(math.prod(plan.shapes[j]))
            block = flat[off:off + n].reshape(plan.shapes[j])
            off += n
            if plan.hybrid:
                out[k][_block_index(out[k].shape, plan.leaf_specs[j],
                                    coord, axis_sizes)] = block
            else:
                out[k] = block
    return out


def fused_allgather_params(shards, plan: ZeroPlan, *,
                           axis_name: str = AXIS,
                           and_finite: Optional[jax.Array] = None):
    """Rebuild a full pytree from every rank's updated flat bucket shards:
    one ``all_gather`` per bucket, padding stripped, leaves reshaped.

    ``and_finite`` (a rank-LOCAL boolean from
    :func:`fused_reduce_scatter`'s ``return_finite``) rides the same
    gather: the scalar is appended as one extra element to the first
    inexact bucket's shard, so after gathering every rank sees every
    rank's flag and the AND is replica-identical — the world-wide
    bad-step verdict with **zero** extra collectives. Returns
    ``(tree, all_finite)`` in that case, else just ``tree``.
    """
    nb = len(plan.buckets)
    flag_bucket = None
    if and_finite is not None:
        flag_bucket = next(
            (i for i in range(nb)
             if jnp.issubdtype(jnp.dtype(plan.dtypes[plan.buckets[i][0]]),
                               jnp.inexact)), None)
    shards = list(shards)
    if flag_bucket is not None:
        flag = and_finite.astype(shards[flag_bucket].dtype).reshape(1)
        shards[flag_bucket] = jnp.concatenate([shards[flag_bucket], flag])
    flats = []
    all_finite = None
    for i in range(nb):
        if plan.nshards > 1:
            gathered = all_gather_invariant(shards[i], axis_name, tiled=True)
        else:
            gathered = shards[i]
        if i == flag_bucket:
            s = plan.shard_len(i)
            blocks = gathered.reshape(plan.nshards, s + 1)
            # 1.0/0.0 flags by construction (isfinite output cast to the
            # bucket dtype) — exactly representable in every float dtype.
            all_finite = jnp.all(blocks[:, -1].astype(jnp.float32) > 0.5)
            gathered = blocks[:, :s].reshape(-1)
        flats.append(gathered[:plan.sizes[i]])
    out = _unfuse_flat(flats, plan)
    if and_finite is None:
        return out
    if all_finite is None:
        # No inexact bucket: an all-integer tree is finite by construction,
        # so the local flag (constant True) is already the global verdict.
        all_finite = and_finite
    return out, all_finite
