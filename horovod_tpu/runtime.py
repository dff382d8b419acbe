"""World bootstrap and process API: ``init / shutdown / size / rank / local_rank``.

Reference parity
----------------
* ``hvd.init()`` → ``InitializeHorovodOnce`` (``mpi_ops.cc:1516-1527``):
  idempotent via an atomic flag, spawns the background runtime, and the caller
  waits until initialization is done. Here, ``init()`` is idempotent under a
  lock, builds the global device **mesh** (the TPU-native "world"), and —
  in multi-process mode — starts the host coordination client (DCN control
  plane), the analog of the reference's background MPI thread
  (``BackgroundThreadLoop``, ``mpi_ops.cc:1248-1512``).
* ``size()/rank()/local_rank()`` → C ABI ``horovod_tensorflow_{size,rank,
  local_rank}`` (``mpi_ops.cc:1539-1566``), raising when uninitialized
  (``mpi_ops.py:80-124``).

TPU-native design
-----------------
Horovod's world is "1 MPI process = 1 GPU" (``README.md:62-64``). The
TPU-native world is a 1-D ``jax.sharding.Mesh`` over every chip of the slice,
with axis name ``"hvd"``:

* ``size()``  = number of chips in the mesh (== MPI world size).
* ``rank()``  = chip index. Inside compiled code (``shard_map`` over the mesh)
  this is ``lax.axis_index('hvd')`` — a per-chip value, exactly Horovod's
  per-process rank. Outside compiled code, a controller process "speaks for"
  its local chips and ``rank()`` returns the global index of its first local
  chip (so launched one-process-per-chip by ``tpurun``, it equals the MPI
  rank; single-controller, it is 0).
* ``local_rank()`` = index of the chip among chips on the same host — the
  analog of ``MPI_Comm_split_type(MPI_COMM_TYPE_SHARED)`` rank
  (``mpi_ops.cc:1263-1267``) — derived from launcher env or the process's
  local device list.

Multi-host: when the launcher has set up ``jax.distributed``, ``jax.devices()``
spans every process, compiled collectives ride ICI/DCN automatically, and the
mesh is global. No NCCL-style communicator bootstrap is needed: ICI collectives
are compiler-scheduled (SURVEY §2.5).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .exceptions import NotInitializedError
from .utils import config as _config
from .utils.timeline import record_span as _record_span

# The world axis name. Every collective in this framework reduces over it.
AXIS: str = "hvd"


@dataclasses.dataclass(frozen=True)
class World:
    """Global state (parity: ``HorovodGlobalState``, ``mpi_ops.cc:132-216``).

    Unlike the reference — whose global state carries a tensor table, message
    queue and CUDA stream pool — the compiled data plane needs only the mesh;
    the eager control plane (coordination client, timeline) hangs off this
    object when enabled.
    """

    mesh: Mesh
    size: int
    controller_rank: int        # global index of this process's first device
    local_rank: int
    process_index: int
    process_count: int
    coord: Any = None           # coordination client (multi-process eager plane)
    timeline: Any = None        # Timeline writer (rank 0 only)
    env_world: bool = False     # tpurun env-world (independent JAX processes)


_lock = threading.Lock()
_world: Optional[World] = None
# Monotonic world generation — bumped on every init(); used (instead of
# object identity, which can be reused after GC) to key caches of compiled
# collective executables across shutdown/re-init cycles.
_generation = 0
# Per-rank metrics HTTP listener (HVD_METRICS_PORT; horovod_tpu.obs.http).
# Module-level, not a World field: it must survive the frozen dataclass
# and be restartable across the shutdown/re-init cycle a live resize runs.
_metrics_listener = None


def init(devices: Optional[Sequence[jax.Device]] = None,
         *,
         coordinator: bool | None = None) -> World:
    """Initialize the world. Idempotent (parity: ``mpi_ops.cc:1516-1527``).

    Args:
      devices: explicit device list forming the world (defaults to every
        device visible to JAX — all chips of the slice across processes).
      coordinator: force-enable/disable the host coordination service for the
        eager op-at-a-time path. Default: enabled iff multi-process.
    """
    global _world, _generation
    with _lock:
        if _world is not None:
            return _world
        # The call that does the work is the span ``hvd.init``: for a user
        # who has not touched jax yet, the backend's start-up is in here.
        start_ns = time.perf_counter_ns()
        _generation += 1

        _maybe_init_jax_distributed()
        devs = list(devices) if devices is not None else list(jax.devices())
        mesh = Mesh(np.array(devs), (AXIS,))
        size = len(devs)

        process_index = jax.process_index()
        process_count = jax.process_count()

        # tpurun env-world: one *independent* JAX process per chip (the
        # reference's "1 MPI process = 1 GPU" model, README.md:62-64) —
        # jax.distributed is not set up, rank/size come from launcher env
        # and ALL cross-rank collectives ride the host coordination plane.
        env_size = _config.launcher_size(default=1)
        env_world = process_count == 1 and env_size > 1 and devices is None
        if env_world:
            size = env_size
            process_index = _config.launcher_rank(default=0)
            process_count = env_size
            controller_rank = process_index
            # 1 process = 1 chip (README.md:62-64): the local mesh is this
            # rank's own device; cross-rank exchange rides the host plane.
            # tpurun pins exactly one chip to each rank (its
            # TPU_VISIBLE_CHIPS environment): a rank that sees several
            # would share them with its siblings, so that is an error,
            # not something to index into. Virtual CPU devices (--cpu
            # worlds under a forced host device count) are
            # interchangeable stand-ins and any one will do.
            local = jax.local_devices()
            if len(local) != 1 and local[0].platform != "cpu":
                raise RuntimeError(
                    f"env-world rank {process_index} sees {len(local)} "
                    f"local {local[0].platform} devices; one process "
                    f"drives one chip — launch through tpurun, which "
                    f"gives each rank its own chip")
            own = local[0]
            mesh = Mesh(np.array([own]), (AXIS,))
        else:
            # Controller rank: global index of the first device owned by
            # this process (jax.distributed multi-host, or single
            # controller). One-process-per-chip → the MPI-style rank.
            controller_rank = 0
            for i, d in enumerate(devs):
                if d.process_index == process_index:
                    controller_rank = i
                    break

        local_rank = _config.launcher_local_rank(default=_infer_local_rank(devs, process_index))

        coord = None
        if coordinator is None:
            coordinator = process_count > 1
        elif coordinator and process_count == 1:
            raise ValueError(
                "init(coordinator=True) requires a multi-process world; "
                "single-controller mode has no cross-process negotiation "
                "to coordinate")

        timeline = None
        tl_path = _config.timeline_path()
        if tl_path and controller_rank == 0 and not coordinator:
            # Single-controller: Python writes the timeline. In coord mode
            # the native coordinator owns the file (coordinator.cc Timeline)
            # — opening it here too would corrupt it.
            from .utils.timeline import Timeline
            timeline = Timeline(tl_path)

        if coordinator and process_count > 1:
            from .coord.client import CoordClient
            coord = CoordClient.from_env(
                rank=process_index, size=process_count, timeline=timeline)

        _world = World(
            mesh=mesh,
            size=size,
            controller_rank=controller_rank,
            local_rank=local_rank,
            process_index=process_index,
            process_count=process_count,
            coord=coord,
            timeline=timeline,
            env_world=env_world,
        )
        _start_observability(_world)
        _record_span("hvd.init", start_ns, time.perf_counter_ns(), size=size)
        return _world


def _start_observability(w: World) -> None:
    """Bring the telemetry plane up for this world: the per-rank
    ``/metrics`` listener (HVD_METRICS_PORT; no-op when unset), the
    world-shape gauges every scrape carries, the fatal-signal
    flight-recorder dump, and the init event itself. Failures here warn
    — telemetry must never kill a training job."""
    global _metrics_listener
    from .obs import flightrec, http as _obs_http
    from .obs.registry import registry as _registry_fn
    try:
        flightrec.install_signal_dump()
        flightrec.record("init", rank=w.process_index, world=w.size,
                         env_world=w.env_world)
        reg = _registry_fn()
        reg.gauge("hvd_world_size",
                  "Number of ranks (chips) in the world").set(w.size)
        reg.gauge("hvd_rank", "This process's rank").set(w.process_index)
        if _metrics_listener is None:
            _metrics_listener = _obs_http.start_from_env(w.process_index)
        if w.timeline is not None:
            # A killed rank's chrome trace should survive alongside its
            # flight record (utils/timeline.py registers its own atexit
            # close; this covers the fatal-signal path).
            flightrec.add_crash_hook(w.timeline.flush)
    except Exception as e:  # noqa: BLE001 — observability is best-effort
        import warnings
        warnings.warn(f"observability startup failed: {e!r} — the world "
                      f"runs without a metrics listener")


def _maybe_init_jax_distributed() -> None:
    """Form the jax.distributed world from tpurun's env when requested.

    tpurun --jax-distributed exports JAX_COORDINATOR_ADDRESS /
    JAX_NUM_PROCESSES / JAX_PROCESS_ID; ``jax.distributed.initialize``
    needs them passed explicitly. Idempotent; silently skipped if the
    world is already up or the env is absent.
    """
    import os
    addr = os.environ.get("JAX_COORDINATOR_ADDRESS")
    nproc = os.environ.get("JAX_NUM_PROCESSES")
    pid = os.environ.get("JAX_PROCESS_ID")
    if not (addr and nproc and pid):
        return
    # NB: do NOT probe jax.process_count() here — it would initialize the
    # backend single-process and make distributed init impossible.
    if jax.distributed.is_initialized():
        return
    try:
        jax.distributed.initialize(coordinator_address=addr,
                                   num_processes=int(nproc),
                                   process_id=int(pid))
    except RuntimeError as e:
        # Only tolerate "backend already initialized" (the user touched
        # devices before init() — distributed formation is impossible but
        # single-process still works). A coordinator-connection failure
        # must NOT be swallowed: proceeding would silently train without
        # gradient exchange.
        if "already" in str(e).lower():
            import warnings
            warnings.warn(
                "jax backend was initialized before hvd.init(); the "
                "jax.distributed world requested by the launcher could not "
                "be formed — compiled collectives will not span processes "
                f"({e})")
        else:
            raise


def _infer_local_rank(devs: Sequence[jax.Device], process_index: int) -> int:
    """Chips-per-host index (parity: shared-comm split, mpi_ops.cc:1263-1267)."""
    try:
        first_local = next(d for d in devs if d.process_index == process_index)
    except StopIteration:
        return 0
    lid = getattr(first_local, "local_hardware_id", None)
    if lid is not None and lid >= 0:
        return int(lid)
    return 0


def shutdown(error: Optional[BaseException] = None) -> None:
    """Tear the world down (parity: ``HorovodGlobalState`` destructor →
    SHUTDOWN broadcast → ``MPI_Finalize``; ``mpi_ops.cc:207-215, 1437-1447,
    1511``). Safe to call multiple times.

    ``error=`` marks this teardown as a FAILURE path: the flight
    recorder's ring is dumped to ``hvd_flightrec.rank{N}.json`` before
    anything else is torn down, so the rank leaves a post-mortem naming
    its last completed step (:mod:`horovod_tpu.obs.flightrec`).
    :func:`horovod_tpu.elastic.run_with_recovery` routes every
    recoverable world failure through here.
    """
    global _world, _metrics_listener
    if error is not None:
        from .obs import flightrec
        flightrec.record("shutdown_error", error=repr(error))
        flightrec.dump(reason=f"runtime.shutdown(error={error!r})")
        flightrec.run_crash_hooks()
    with _lock:
        if _world is None:
            return
        if _metrics_listener is not None:
            try:
                _metrics_listener.stop()
            except Exception:  # noqa: BLE001 — teardown must finish
                pass
            _metrics_listener = None
        if _world.timeline is not None:
            from .obs import flightrec
            flightrec.remove_crash_hook(_world.timeline.flush)
        if _world.coord is not None:
            try:
                _world.coord.shutdown()
            except Exception as e:  # noqa: BLE001 — teardown must finish
                # Crash-safe teardown: a dead coordinator (worker failure,
                # aborted world) must not wedge the rest of the teardown —
                # the timeline close and world reset below still run, so a
                # supervised restart starts from a clean slate.
                import warnings
                warnings.warn(
                    f"coordination-plane shutdown failed (coordinator "
                    f"already dead?): {e!r} — continuing world teardown")
        if _world.timeline is not None:
            try:
                _world.timeline.close()
            except Exception as e:  # noqa: BLE001
                import warnings
                warnings.warn(f"timeline close failed: {e!r} — continuing "
                              f"world teardown")
        _world = None
        # Drop compiled eager-collective executables from the dead world —
        # their cache keys (generation) can never hit again.
        from .ops import collectives as _c
        _c._eager_fn.cache_clear()


def is_initialized() -> bool:
    return _world is not None


def world() -> World:
    if _world is None:
        raise NotInitializedError()
    return _world


def mesh() -> Mesh:
    """The world mesh. Collectives reduce over its ``"hvd"`` axis."""
    return world().mesh


def size() -> int:
    """World size = number of chips (parity: ``horovod_tensorflow_size``,
    ``mpi_ops.cc:1560-1566``)."""
    return world().size


def _in_world_trace() -> bool:
    """True when called under a trace with the ``hvd`` axis bound
    (i.e. inside ``shard_map`` over the world mesh)."""
    try:
        jax.lax.axis_index(AXIS)
        return True
    except NameError:
        return False
    except Exception:
        return False


def rank():
    """This rank's index in [0, size).

    Inside compiled code over the world mesh → per-chip ``lax.axis_index``
    (a traced value). Outside → the controller's first local chip index
    (parity: ``horovod_tensorflow_rank``, ``mpi_ops.cc:1546-1552``).
    """
    w = world()
    if _in_world_trace():
        return jax.lax.axis_index(AXIS)
    return w.controller_rank


def local_rank() -> int:
    """Index of this chip among chips on the same host (parity:
    ``horovod_tensorflow_local_rank``, ``mpi_ops.cc:1553-1559``)."""
    return world().local_rank


def process_index() -> int:
    return world().process_index


def process_count() -> int:
    return world().process_count


# ---------------------------------------------------------------------------
# Sharding helpers used across the framework.
# ---------------------------------------------------------------------------

def replicated_sharding() -> NamedSharding:
    return NamedSharding(mesh(), P())


def ranked_sharding() -> NamedSharding:
    """Leading axis split one-slice-per-rank over the world axis."""
    return NamedSharding(mesh(), P(AXIS))
