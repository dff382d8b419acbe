"""Named collectives: allreduce / allgather / broadcast (+ TPU-era extras).

Reference parity
----------------
* Graph-op wrappers ``_allreduce/allgather/broadcast`` with auto-generated
  cross-rank matching names (``mpi_ops.py:127-190``); semantic ``allreduce``
  with average-vs-sum and the sparse path (``horovod/tensorflow/__init__.py:
  43-79``); ``HorovodAllreduce/Allgather/Broadcast`` kernels
  (``mpi_ops.cc:1752-1915``).
* Allgather concatenates along the first dimension (``MPI_Allgatherv``
  executor, ``mpi_ops.cc:735-812``).
* Broadcast takes a ``root_rank`` and the root's tensor passes through
  (``mpi_ops.cc:1855-1893``).

TPU-native design
-----------------
Two execution contexts, one API:

1. **Inside compiled code** (``shard_map`` over the world mesh — the hot
   path, used by ``DistributedOptimizer`` inside the jitted train step):
   the call lowers directly to an XLA collective over the ``"hvd"`` ICI axis
   (``lax.psum`` / ``lax.all_gather`` / one-hot-mask ``psum`` broadcast).
   XLA schedules and overlaps these; no negotiation is needed because SPMD
   tracing already imposes one global order (SURVEY §7 design stance —
   the reference's coordinator exists only because TF 1.x graph execution is
   cross-rank nondeterministic, ``mpi_ops.cc:1198-1247``).

2. **Eager, op-at-a-time** (outside jit — metrics averaging, epoch
   broadcast, checkpoint-resume sync): the call is dispatched through a
   cached single-collective executable on the mesh. Per-rank inputs are
   jax.Arrays sharded over the world axis on their leading dim (the
   single-controller encoding of "each rank passes its own tensor");
   replicated/host inputs mean every rank contributes the same value. In
   multi-process mode the host coordination plane (``horovod_tpu.coord``)
   additionally validates name-keyed requests across processes, with the
   reference's exact error classification (``ConstructMPIResponse``,
   ``mpi_ops.cc:266-474``).
"""

from __future__ import annotations

import enum
import functools
import re
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import runtime
from ..runtime import AXIS
from ..utils.compat import all_gather_invariant, is_tracer


class Op(enum.Enum):
    """Reduction op. The reference supports summation with optional
    averaging (``average=`` bool, ``horovod/tensorflow/__init__.py:43``);
    MIN/MAX/PRODUCT are TPU-era extras."""

    SUM = "sum"
    AVERAGE = "average"
    MIN = "min"
    MAX = "max"
    PRODUCT = "product"


_name_counter = 0


def _auto_name(kind: str, name: Optional[str]) -> str:
    """Auto-generate the cross-rank matching key (parity: ``mpi_ops.py:132-145``
    names ops ``HorovodAllreduce_<sanitized tensor name>``)."""
    global _name_counter
    if name is None:
        _name_counter += 1
        name = f"tensor_{_name_counter}"
    return f"Horovod{kind}_" + re.sub(r"[^a-zA-Z0-9_]", "_", str(name))


def _in_trace() -> bool:
    return runtime._in_world_trace()


# ---------------------------------------------------------------------------
# In-trace primitives (compiled data plane over ICI).
# ---------------------------------------------------------------------------

def _reduce_in_trace(x, op: Op, axis_name: str = AXIS):
    if op is Op.AVERAGE:
        return lax.pmean(x, axis_name)
    if op is Op.SUM:
        return lax.psum(x, axis_name)
    if op is Op.MIN:
        return lax.pmin(x, axis_name)
    if op is Op.MAX:
        return lax.pmax(x, axis_name)
    if op is Op.PRODUCT:
        # No lax.pprod; exp/log is lossy — use all_gather+prod (rarely hot).
        return jnp.prod(all_gather_invariant(x, axis_name), axis=0)
    raise ValueError(f"unknown op {op}")


def _broadcast_in_trace(x, root_rank: int, axis_name: str = AXIS):
    """One-hot-mask ``psum`` broadcast (SURVEY §2.5 TPU equivalent of
    ``MPI_Bcast``, ``mpi_ops.cc:1134-1136``): zero everywhere but the root,
    then sum over the axis. The root's tensor passes through bit-exact for
    ints; for floats, +0.0 of zeros is exact."""
    idx = lax.axis_index(axis_name)
    orig_dtype = x.dtype
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.int8)
    # where(), not x*mask: multiply-by-zero would propagate NaN/Inf from
    # non-root ranks — and re-syncing diverged replicas is broadcast's main
    # job (§5.4 consistency protocol).
    out = lax.psum(jnp.where(idx == root_rank, x, jnp.zeros_like(x)),
                   axis_name)
    return out.astype(orig_dtype)


# ---------------------------------------------------------------------------
# Eager dispatch: cached single-collective executables on the world mesh.
# Parity note: the reference caches nothing (every session.run re-hits the
# negotiation); we cache compiled executables per (kind, shape, dtype, flags)
# — SURVEY §7 "per-(shape,dtype) executable caching".
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _eager_fn(mesh_key, kind: str, per_rank: bool, squeeze: bool, op: Op,
              root_rank: int):
    mesh = runtime.mesh()
    in_spec = P(AXIS) if per_rank else P()

    out_spec = P()
    if kind == "allreduce":
        def f(x):
            return _reduce_in_trace(x, op)
    elif kind == "allgather":
        def f(x):
            return all_gather_invariant(x, AXIS, tiled=True)
    elif kind == "broadcast":
        def f(x):
            return _broadcast_in_trace(x, root_rank)
    elif kind == "alltoall":
        # Per-rank results differ; the output stays sharded over the world
        # axis (each rank's block is its own exchange result).
        out_spec = P(AXIS)

        def f(x):
            return lax.all_to_all(x, AXIS, 0, 0, tiled=True)
    elif kind == "reducescatter":
        out_spec = P(AXIS)
        if op not in (Op.SUM, Op.AVERAGE):
            raise ValueError(
                f"compiled reducescatter supports SUM/AVERAGE; got {op}")

        def f(x):
            out = lax.psum_scatter(x, AXIS, tiled=True)
            return out / runtime.size() if op is Op.AVERAGE else out
    else:
        raise ValueError(kind)

    if squeeze:
        # Stacked per-rank encoding: the [size, ...] leading axis shards to a
        # size-1 block per rank; the rank's tensor is block[0].
        inner = f
        f = lambda x: inner(x[0])  # noqa: E731

    return jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=in_spec, out_specs=out_spec))


def _is_per_rank(x) -> bool:
    """A jax.Array whose leading dim is split over the world axis encodes
    "each rank passes its own tensor" under a single controller."""
    sharding = getattr(x, "sharding", None)
    if not isinstance(sharding, NamedSharding):
        return False
    spec = sharding.spec
    return len(spec) > 0 and (
        spec[0] == AXIS or (isinstance(spec[0], tuple) and AXIS in spec[0]))


def _eager_dispatch(kind: str, x, name: str, *, op: Op = Op.SUM,
                    root_rank: int = 0, plane: str = "auto"):
    w = runtime.world()
    x = jnp.asarray(x)
    per_rank = _is_per_rank(x)

    if w.coord is not None:
        # Multi-process eager plane: negotiate + validate the name-keyed
        # request across processes before dispatch (host DCN plane).
        return w.coord.collective(kind, x, name, op=op, root_rank=root_rank,
                                  plane=plane)
    if plane != "auto":
        raise ValueError(
            f"plane={plane!r} is a multi-process eager-plane knob (star vs "
            f"client-to-client ring); this world has no coordination plane")

    if kind in ("alltoall", "reducescatter"):
        if not per_rank:
            raise ValueError(
                f"eager single-controller {kind} needs input sharded over "
                f"the world axis on dim 0 (each rank's block is its tensor); "
                f"got a replicated/host value — use shard_batch or a "
                f"NamedSharding(P('{AXIS}'))")
        # Global dim 0 = size × per-rank block; each block must again split
        # `size` ways inside the exchange, so the global dim needs size².
        if x.ndim < 1 or x.shape[0] % (w.size * w.size):
            raise ValueError(
                f"single-controller eager {kind} needs a global first "
                f"dimension divisible by size²={w.size * w.size} (per-rank "
                f"blocks of size a multiple of {w.size}); got shape "
                f"{tuple(x.shape)}")
        squeeze = False
    else:
        squeeze = per_rank and x.ndim >= 1 and x.shape[0] == w.size

    tl = w.timeline
    if tl is not None:
        # Single-controller: negotiation is synthesized (SPMD needs none);
        # the processing phase wraps the real dispatch activities
        # (docs/timeline.md nested-activity model, mpi_ops.cc:623-635).
        tl.negotiate_instant(name, kind.upper(), ready_ranks=range(w.size))
        tl.start(name, kind.upper())
        tl.activity_start(name, "SCHEDULE")
    try:
        fn = _eager_fn(runtime._generation, kind, per_rank, squeeze, op,
                       root_rank)
        if tl is not None:
            tl.activity_end(name)
            tl.activity_start(name, "XLA_EXECUTE")
        out = fn(x)
    except BaseException as e:
        # Close every opened B event so a failed dispatch (invalid op for
        # the kind, XLA error) cannot leave the trace unbalanced.
        if tl is not None:
            tl.abort(name, error=str(e))
        raise
    if tl is not None:
        tl.activity_end(name)
        tl.end(name, out)
    return out


# ---------------------------------------------------------------------------
# Public API.
# ---------------------------------------------------------------------------

def allreduce(tensor, average: bool = True, name: Optional[str] = None,
              op: Optional[Op] = None, axis_name: str = AXIS,
              plane: str = "auto"):
    """Sum (or average) ``tensor`` across all ranks.

    Parity: ``hvd.allreduce`` (``horovod/tensorflow/__init__.py:43-79``) —
    ``average=True`` divides by ``size()``. The sparse
    ``tf.IndexedSlices`` branch (allgather of values+indices,
    ``__init__.py:61-72``) lives in :func:`horovod_tpu.ops.sparse.
    allreduce_indexed_slices` and is auto-taken for
    :class:`~horovod_tpu.ops.sparse.IndexedSlices` inputs.

    Inside a ``shard_map`` over the world mesh this is a single XLA
    ``all-reduce`` over ICI; eagerly it dispatches a cached compiled
    collective (single-controller) or the host coordination plane
    (multi-process). ``plane`` routes the multi-process eager data plane
    per call — ``"auto"`` (``HOROVOD_RING_THRESHOLD`` elects), ``"star"``
    (coordinator relay) or ``"ring"`` (client-to-client) — the analog of
    the reference's per-call ``device_dense=`` placement knob
    (``horovod/tensorflow/__init__.py:43-55``, ``docs/gpus.md:40-45``);
    ignored in-trace (XLA owns the compiled plane).
    """
    from .sparse import IndexedSlices, allreduce_indexed_slices
    resolved = op if op is not None else (Op.AVERAGE if average else Op.SUM)
    if isinstance(tensor, IndexedSlices):
        if resolved not in (Op.SUM, Op.AVERAGE):
            raise ValueError(
                f"op={resolved} is not supported for sparse (IndexedSlices) "
                "allreduce; the sliced form only composes under SUM/AVERAGE "
                "(reference semantics, horovod/tensorflow/__init__.py:61-72)")
        return allreduce_indexed_slices(
            tensor, average=(resolved is Op.AVERAGE), name=name)

    if _in_trace():
        return _reduce_in_trace(tensor, resolved, axis_name)
    return _eager_dispatch("allreduce", tensor,
                           _auto_name("Allreduce", name), op=resolved,
                           plane=plane)


def allgather(tensor, name: Optional[str] = None, axis_name: str = AXIS,
              plane: str = "auto"):
    """Concatenate each rank's tensor along dim 0.

    Parity: ``hvd.allgather`` (``mpi_ops.py:151-167``) / ``MPI_Allgatherv``
    executor (``mpi_ops.cc:735-812``). Ranks may differ in the first
    dimension only — in compiled SPMD code shapes are static and equal; the
    variable-first-dim case is served eagerly by the coordination plane
    (negotiated sizes, ``mpi_ops.cc:345-405``) or in-trace via
    :func:`allgather_ragged`.
    """
    if _in_trace():
        return all_gather_invariant(tensor, axis_name, tiled=True)
    return _eager_dispatch("allgather", tensor, _auto_name("Allgather", name),
                           plane=plane)


def allgather_ragged(tensor, valid_size, max_size: int,
                     name: Optional[str] = None, axis_name: str = AXIS):
    """Variable-first-dim allgather under XLA static shapes.

    Each rank holds ``tensor`` padded to ``max_size`` rows, of which
    ``valid_size`` are real. Returns ``(gathered, sizes)`` where
    ``gathered`` is ``[size * max_size, ...]`` with each rank's block
    zero-padded past its ``valid_size``, and ``sizes`` is the per-rank
    valid-size vector — the in-trace analog of the negotiated
    ``tensor_sizes`` in the reference's allgather response
    (``mpi_message.h:94-139``, ``mpi_ops.cc:345-405``).
    """
    del name
    n = jnp.shape(tensor)[0]
    if n > max_size:
        # Error parity with the coordinator's negotiated-size path: an
        # input larger than the negotiated maximum is a validation error
        # (ConstructMPIResponse allgather sizing, mpi_ops.cc:345-405), not
        # a silent truncation.
        raise ValueError(
            f"Mismatched ALLGATHER tensor shapes: tensor has {n} rows but "
            f"max_size is {max_size}; allgather_ragged cannot truncate "
            f"(grow max_size or slice the input)")
    if not is_tracer(valid_size):
        vs = int(valid_size)
        if not 0 <= vs <= max_size:
            raise ValueError(
                f"Mismatched ALLGATHER tensor shapes: valid_size {vs} is "
                f"outside [0, max_size={max_size}]; an oversized "
                f"valid_size would silently drop rows past max_size "
                f"(negotiated-size parity, mpi_ops.cc:345-405)")
    else:
        # Data-dependent valid_size inside jit cannot raise; clamp so an
        # out-of-range value cannot corrupt the mask or the sizes vector.
        valid_size = jnp.clip(valid_size, 0, max_size)
    if n != max_size:
        pad = [(0, max_size - n)] + [(0, 0)] * (tensor.ndim - 1)
        tensor = jnp.pad(tensor, pad)
    row = jnp.arange(max_size)
    keep = (row < valid_size).reshape((max_size,) + (1,) * (tensor.ndim - 1))
    tensor = jnp.where(keep, tensor, jnp.zeros_like(tensor))
    gathered = all_gather_invariant(tensor, axis_name, tiled=True)
    sizes = all_gather_invariant(jnp.asarray(valid_size, jnp.int32), axis_name)
    return gathered, sizes


def broadcast(tensor, root_rank: int = 0, name: Optional[str] = None,
              axis_name: str = AXIS, plane: str = "auto"):
    """Every rank receives the root's tensor.

    Parity: ``hvd.broadcast`` (``mpi_ops.py:170-190``) / ``MPI_Bcast``
    executor (``mpi_ops.cc:1113-1140``; root passes input through,
    ``mpi_ops.cc:1869-1870``).
    """
    if runtime.is_initialized() and not 0 <= root_rank < runtime.size():
        # Parity: the coordinator validates root_rank (ConstructMPIResponse,
        # mpi_ops.cc:408-435); an impossible root must fail loudly, not
        # silently produce zeros from an all-false mask.
        raise ValueError(
            f"root_rank {root_rank} is out of range for world size "
            f"{runtime.size()}")
    if _in_trace():
        return _broadcast_in_trace(tensor, root_rank, axis_name)
    return _eager_dispatch("broadcast", tensor,
                           _auto_name("Broadcast", name), root_rank=root_rank,
                           plane=plane)


def alltoall(tensor, split_axis: int = 0, concat_axis: int = 0,
             name: Optional[str] = None, axis_name: str = AXIS,
             plane: str = "auto"):
    """All-to-all exchange (TPU-era extra; not in reference v0.11.2 —
    needed by all-to-all sequence/context parallelism, SURVEY §5.7).

    In-trace: ``lax.all_to_all`` over ICI. Eagerly: dim 0 is split into
    ``size`` blocks and rank ``r`` receives block ``r`` from every rank,
    concatenated — via the host coordination plane (multi-process) or a
    compiled exchange on the mesh (single-controller; the input must be
    sharded over the world axis, each rank's block being its tensor).
    """
    if _in_trace():
        return lax.all_to_all(tensor, axis_name, split_axis, concat_axis,
                              tiled=True)
    if split_axis != 0 or concat_axis != 0:
        raise NotImplementedError(
            "eager alltoall supports split_axis=0/concat_axis=0; transpose "
            "first or call in-trace under shard_map")
    return _eager_dispatch("alltoall", tensor, _auto_name("Alltoall", name),
                           plane=plane)


def reducescatter(tensor, average: bool = False,
                  name: Optional[str] = None, op: Optional[Op] = None,
                  axis_name: str = AXIS, plane: str = "auto"):
    """Reduce-scatter (TPU-era extra): reduce across ranks, then rank ``r``
    keeps block ``r`` of the first dimension.

    In-trace: ``lax.psum_scatter`` over ICI (SUM/AVERAGE). Eagerly:
    host coordination plane (multi-process; any reduction op) or compiled
    exchange (single-controller, input sharded over the world axis).
    """
    resolved = op if op is not None else (Op.AVERAGE if average else Op.SUM)
    if _in_trace():
        if resolved not in (Op.SUM, Op.AVERAGE):
            raise ValueError(
                f"in-trace reducescatter supports SUM/AVERAGE (XLA "
                f"reduce-scatter is a sum); got {resolved}")
        out = lax.psum_scatter(tensor, axis_name, tiled=True)
        if resolved is Op.AVERAGE:
            out = out / runtime.size()
        return out
    return _eager_dispatch("reducescatter", tensor,
                           _auto_name("Reducescatter", name), op=resolved,
                           plane=plane)


# ---------------------------------------------------------------------------
# Async eager API (reference model: ComputeAsync kernels + done callbacks,
# mpi_ops.cc:1752-1772 — dozens of collectives negotiate concurrently from
# TF's executor threads, feeding coordinator-side fusion). Handles are
# redeemed out-of-order-safe with synchronize().
# ---------------------------------------------------------------------------

class _DoneHandle:
    """Pre-completed handle (single-controller eager dispatch is already a
    single compiled call; there is nothing to overlap)."""

    def __init__(self, result):
        self._result = result


def _submit_async(kind: str, x, name: Optional[str], *, op: Op = Op.SUM,
                  root_rank: int = 0):
    if _in_trace():
        raise RuntimeError(
            f"{kind}_async_ is an eager API; inside compiled code use the "
            f"synchronous form — XLA already overlaps collectives")
    w = runtime.world()
    full_name = _auto_name(kind.capitalize(), name)
    if w.coord is not None:
        return w.coord.submit(kind, jnp.asarray(x), full_name, op=op,
                              root_rank=root_rank)
    return _DoneHandle(_eager_dispatch(kind, jnp.asarray(x), full_name,
                                       op=op, root_rank=root_rank))


def allreduce_async_(tensor, average: bool = True,
                     name: Optional[str] = None, op: Optional[Op] = None):
    """Non-blocking :func:`allreduce`; returns a handle for
    :func:`synchronize`. Overlapped submissions negotiate concurrently and
    are fused by the coordinator (64 MiB same-dtype batching)."""
    resolved = op if op is not None else (Op.AVERAGE if average else Op.SUM)
    return _submit_async("allreduce", tensor, name, op=resolved)


def allgather_async_(tensor, name: Optional[str] = None):
    """Non-blocking :func:`allgather`; returns a handle."""
    return _submit_async("allgather", tensor, name)


def broadcast_async_(tensor, root_rank: int = 0,
                     name: Optional[str] = None):
    """Non-blocking :func:`broadcast`; returns a handle."""
    if runtime.is_initialized() and not 0 <= root_rank < runtime.size():
        raise ValueError(
            f"root_rank {root_rank} is out of range for world size "
            f"{runtime.size()}")
    return _submit_async("broadcast", tensor, name, root_rank=root_rank)


def synchronize(handle):
    """Block until an async handle's collective completes; returns the
    result. Handles may be synchronized in any order."""
    if isinstance(handle, _DoneHandle):
        return handle._result
    return handle.client.wait(handle)


# ---------------------------------------------------------------------------
# Object collectives (TPU-era extras; later Horovod's broadcast_object /
# allgather_object). Arbitrary picklable Python objects ride the eager
# plane as uint8 payloads — epoch metadata, config dicts, vocabularies.
# ---------------------------------------------------------------------------

def broadcast_object(obj=None, root_rank: int = 0,
                     name: Optional[str] = None):
    """Every process receives the root process's picklable object.

    Object collectives operate over PROCESSES (objects are host-side
    metadata — resume epochs, config dicts, vocabularies), so ``root_rank``
    is a PROCESS index; under a single controller there is one host and
    this is the identity. Non-root ranks may pass anything (ignored). Two
    rounds: the payload length first (non-roots cannot know it), then the
    bytes.
    """
    import pickle

    import numpy as np

    w = runtime.world()
    if w.process_count == 1:
        return obj
    base = _auto_name("BroadcastObject", name)
    # Root test must use process_index, not controller_rank: with >1 device
    # per process the controller_rank is process_index * local_device_count,
    # and the coord-plane broadcast below keys roots by process index.
    payload = np.frombuffer(pickle.dumps(obj), np.uint8) \
        if w.process_index == root_rank else np.zeros(0, np.uint8)
    n = broadcast(jnp.asarray([payload.size], jnp.int32),
                  root_rank=root_rank, name=base + ".len")
    length = int(np.asarray(n)[0])
    buf = np.zeros(length, np.uint8)
    buf[:payload.size] = payload[:length]
    out = broadcast(jnp.asarray(buf), root_rank=root_rank,
                    name=base + ".bytes")
    return pickle.loads(np.asarray(out).tobytes())


def allgather_object(obj, name: Optional[str] = None) -> list:
    """Gather every process's picklable object; returns the process-ordered
    list on all processes (ragged payloads ride the negotiated-size
    allgather)."""
    import pickle

    import numpy as np

    w = runtime.world()
    if w.process_count == 1:
        return [obj]
    payload = np.frombuffer(pickle.dumps(obj), np.uint8).reshape(-1, 1)
    base = _auto_name("AllgatherObject", name)
    lens = np.asarray(allgather(jnp.asarray([payload.shape[0]], jnp.int32),
                                name=base + ".len"))
    blob = np.asarray(allgather(jnp.asarray(payload), name=base + ".bytes"))
    out, off = [], 0
    for ln in lens.reshape(-1):
        ln = int(ln)
        out.append(pickle.loads(blob[off:off + ln].tobytes()))
        off += ln
    return out


def grouped_allreduce(tensors, average: bool = True,
                      name: Optional[str] = None,
                      fusion_threshold: Optional[int] = None,
                      axis_name: str = AXIS):
    """Allreduce a pytree of tensors as fused flat buckets.

    This is the TPU-native tensor fusion (reference: coordinator-side fusion
    of consecutive same-dtype responses into one 64 MiB-capped buffer,
    ``mpi_ops.cc:1395-1422``; semantics doc ``docs/tensor-fusion.md:6-28``).
    See :mod:`horovod_tpu.ops.fusion`.
    """
    from .fusion import fused_allreduce
    del name
    return fused_allreduce(tensors, average=average,
                           fusion_threshold=fusion_threshold,
                           axis_name=axis_name)
