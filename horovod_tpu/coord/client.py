"""Host coordination client — Python binding over the native core.

This is the TPU-native analog of the reference's background-thread MPI
negotiation (``BackgroundThreadLoop``, ``mpi_ops.cc:1248-1512``): name-keyed
Request/Response messages to a rank-0 coordinator over DCN/TCP, cross-rank
validation with the reference's error classification (``ConstructMPIResponse``,
``mpi_ops.cc:266-474``), stall detection, tensor-fusion response batching and
host-side execution of eager op-at-a-time collectives. The native core lives
in ``coordinator.cc`` (built lazily into ``libhvdcoord.so``); this module is
the ctypes binding (parity: ``mpi_ops.py:68-124`` loads the native lib via
ctypes with a thin wrapper).

Only the *eager* op-at-a-time API (metrics, epoch broadcast, init-time weight
sync) uses this plane. Compiled collectives (``shard_map`` over the global
mesh) span processes via XLA itself.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import socket
import struct
import subprocess
from typing import NamedTuple, Optional

import numpy as np

from ..exceptions import (FailedPreconditionError, StalledError,
                          TransportError, WorkerFailureError)
from ..testing import faults as _faults
from ..utils import config as _config


class PendingResize(NamedTuple):
    """A live resize announced by the coordinator (v7 admin plane)."""

    target_world: int   # new world size the job must quiesce into
    coord_port: int     # coordinator port reserved for the NEW world
    generation: int     # monotonically increasing resize counter


# ---------------------------------------------------------------------------
# Admin RPC (v7) — pure-socket client, deliberately ctypes-free so the
# supervising tpurun (which must not load jax OR build the native core) and
# one-line operator invocations can speak it. The wire format mirrors
# coordinator.cc: 8-byte native-order length prefix, then
# {u8 kResizeRequest, i32 target}; reply {u8 kResizeReply, u8 ok, str msg,
# i32 world, i32 pending_target, i32 new_port, i32 generation} where str is
# {i64 len, bytes}.
# ---------------------------------------------------------------------------

_MSG_RESIZE_REQUEST = 7
_MSG_RESIZE_REPLY = 8


def _admin_rpc(addr: str, target: int, timeout: float) -> dict:
    import time as _time
    host, _, port_s = addr.partition(":")
    port = int(port_s) if port_s else 29521
    # The timeout is a WALL-CLOCK budget for the whole exchange, not a
    # per-recv bound — a foreign process that re-bound the polled port
    # must not be able to park the supervisor by dripping one byte per
    # second inside a per-recv window.
    deadline = _time.monotonic() + timeout

    def _recv_exact(s, n, what):
        buf = b""
        while len(buf) < n:
            left = deadline - _time.monotonic()
            if left <= 0:
                raise TransportError(
                    f"admin exchange with {addr} exceeded its {timeout}s "
                    f"budget while reading the {what}")
            s.settimeout(min(left, timeout))
            chunk = s.recv(n - len(buf))
            if not chunk:
                raise TransportError(
                    f"coordinator at {addr} closed the admin connection "
                    f"while sending the {what}")
            buf += chunk
        return buf

    with socket.create_connection((host or "127.0.0.1", port),
                                  timeout=timeout) as s:
        body = struct.pack("<Bi", _MSG_RESIZE_REQUEST, int(target))
        s.sendall(struct.pack("<Q", len(body)) + body)
        (length,) = struct.unpack("<Q", _recv_exact(s, 8, "length prefix"))
        if length > 4096:
            # Mirror the server's admin frame cap.
            raise TransportError(
                f"oversized admin reply ({length} bytes) from {addr} — "
                f"not a horovod_tpu coordinator?")
        reply = _recv_exact(s, length, "reply frame")
    # Parse defensively: the reply may come from a foreign process that
    # re-bound the port, or be truncated — surface the documented
    # TransportError, never a bare struct.error or garbage field values.
    try:
        tag, ok = struct.unpack_from("<BB", reply, 0)
        if tag != _MSG_RESIZE_REPLY:
            raise TransportError(
                f"unexpected admin reply tag {tag} from {addr} (mixed "
                f"horovod_tpu builds? the admin plane is protocol v7+)")
        (msg_len,) = struct.unpack_from("<q", reply, 2)
        off = 10
        if msg_len < 0 or off + msg_len + 16 > len(reply):
            raise TransportError(
                f"malformed admin reply from {addr} (message length "
                f"{msg_len} does not fit the {len(reply)}-byte frame)")
        msg = reply[off:off + msg_len].decode(errors="replace")
        off += msg_len
        world, pending, new_port, generation = struct.unpack_from(
            "<iiii", reply, off)
    except struct.error as e:
        raise TransportError(
            f"truncated admin reply from coordinator at {addr}: {e}"
        ) from None
    return {"ok": bool(ok), "message": msg, "world": world,
            "pending_target": pending, "coord_port": new_port,
            "generation": generation}


def resize_status(addr: str, *, timeout: float = 5.0,
                  supervisor: bool = False) -> dict:
    """Query the coordinator's world size and pending resize (if any).

    Returns ``{"world": N, "pending_target": K-or-0, "coord_port": P,
    "generation": G, ...}``. Raises :class:`TransportError`/``OSError``
    when the coordinator is unreachable (callers that poll — tpurun's
    supervision loop — treat that as "not ready, retry").

    ``supervisor=True`` marks the query as the SUPERVISING launcher's
    poll: it releases the coordinator's teardown-handoff linger (the
    pending-resize triple has reached the party that spawns grow ranks).
    Operator/observability queries must leave it False."""
    return _admin_rpc(addr, -1 if supervisor else 0, timeout)


def request_resize(addr: str, target_world: int, *,
                   timeout: float = 10.0) -> dict:
    """Ask the running world at ``addr`` to resize itself to
    ``target_world`` ranks — the operator/admin ingress of the live-resize
    plane (``docs/fault_tolerance.md``). Idempotent for the same target;
    raises :class:`TransportError` when the coordinator refuses (resize to
    a different size already pending, target == current size, ...).

    One-liner for operators::

        python -c "from horovod_tpu.coord.client import request_resize; \\
                   print(request_resize('127.0.0.1:29521', 2))"
    """
    if int(target_world) < 1:
        raise ValueError(
            f"resize target must be >= 1 rank, got {target_world}")
    out = _admin_rpc(addr, int(target_world), timeout)
    if not out["ok"]:
        raise TransportError(
            f"coordinator at {addr} refused resize to {target_world}: "
            f"{out['message']}")
    return out

_REQ_TYPES = {"allreduce": 0, "allgather": 1, "broadcast": 2,
              "alltoall": 3, "reducescatter": 4}

# numpy dtype -> wire enum (coordinator.cc DType; the reference's nine dtypes
# of mpi_message.h:26-36 plus bfloat16).
_DTYPES = {
    "uint8": 0, "int8": 1, "uint16": 2, "int16": 3, "int32": 4,
    "int64": 5, "float32": 6, "float64": 7, "bool": 8, "bfloat16": 9,
}


def _build_and_load() -> ctypes.CDLL:
    here = os.path.dirname(os.path.abspath(__file__))
    so = os.path.join(here, "libhvdcoord.so")
    stamp = os.path.join(here, "libhvdcoord.src.sha256")
    with open(os.path.join(here, "coordinator.cc"), "rb") as f:
        want = hashlib.sha256(f.read()).hexdigest()

    def _stale() -> bool:
        # Keyed by the source's CONTENT, not mtimes: after a copy or a
        # checkout a stale binary can look newer than coordinator.cc.
        if not (os.path.exists(so) and os.path.exists(stamp)):
            return True
        with open(stamp) as f:
            return f.read().strip() != want

    if _stale():
        # Concurrently launched ranks all reach this on a fresh checkout;
        # serialize the build with an exclusive lock so nobody dlopens a
        # half-written .so.
        import fcntl
        with open(os.path.join(here, ".build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            try:
                if _stale():
                    subprocess.run(["make", "-C", here, "clean"],
                                   check=True, capture_output=True,
                                   text=True)
                    subprocess.run(["make", "-C", here], check=True,
                                   capture_output=True, text=True)
                    with open(stamp, "w") as f:
                        f.write(want + "\n")
            finally:
                fcntl.flock(lock, fcntl.LOCK_UN)
    lib = ctypes.CDLL(so)
    lib.hvdcoord_init.restype = ctypes.c_int
    lib.hvdcoord_init.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
        ctypes.c_longlong, ctypes.c_double, ctypes.c_char_p,
        ctypes.c_char_p, ctypes.c_int]
    lib.hvdcoord_submit.restype = ctypes.c_int
    lib.hvdcoord_submit.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_longlong),
        ctypes.c_void_p, ctypes.c_longlong, ctypes.c_int,
        ctypes.c_char_p, ctypes.c_int]
    lib.hvdcoord_wait.restype = ctypes.c_int
    lib.hvdcoord_wait.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_longlong),
        ctypes.POINTER(ctypes.c_longlong), ctypes.c_char_p, ctypes.c_int]
    lib.hvdcoord_free.argtypes = [ctypes.c_void_p]
    lib.hvdcoord_shutdown.restype = None
    lib.hvdcoord_responses_received.restype = ctypes.c_longlong
    lib.hvdcoord_responses_received.argtypes = []
    lib.hvdcoord_ops_completed.restype = ctypes.c_longlong
    lib.hvdcoord_ops_completed.argtypes = []
    lib.hvdcoord_ring_ops.restype = ctypes.c_longlong
    lib.hvdcoord_ring_ops.argtypes = []
    lib.hvdcoord_ring_bytes_sent.restype = ctypes.c_longlong
    lib.hvdcoord_ring_bytes_sent.argtypes = []
    # Liveness-plane fault-injection/observability hooks (v6).
    lib.hvdcoord_mute_heartbeats.restype = None
    lib.hvdcoord_mute_heartbeats.argtypes = [ctypes.c_int]
    lib.hvdcoord_coord_mute_acks.restype = None
    lib.hvdcoord_coord_mute_acks.argtypes = [ctypes.c_int]
    lib.hvdcoord_aborted.restype = ctypes.c_int
    lib.hvdcoord_aborted.argtypes = []
    # Live-resize plane (v7).
    lib.hvdcoord_pending_resize.restype = ctypes.c_int
    lib.hvdcoord_pending_resize.argtypes = [
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int)]
    return lib


class CoordClient:
    """Per-process handle on the coordination plane."""

    def __init__(self, rank: int, size: int, host: str, port: int,
                 timeline=None):
        self._lib = _build_and_load()
        self.rank = rank
        self.size = size
        tl_path = _config.timeline_path() if rank == 0 else None
        err = ctypes.create_string_buffer(1024)
        rc = self._lib.hvdcoord_init(
            rank, size, host.encode(), port,
            _config.fusion_threshold_bytes(),
            _config.stall_warning_secs(),
            tl_path.encode() if tl_path else None, err, len(err))
        if rc != 0:
            detail = err.value.decode() or f"rc={rc}"
            raise TransportError(
                f"coordination plane init failed (rank {rank}, "
                f"{host}:{port}): {detail}")
        # Names currently announced-but-unwaited by THIS rank. The
        # coordinator drops duplicate same-rank announcements of an
        # in-flight name (Ingest), so a second submit under the same name
        # would wait forever; fail fast here instead.
        self._inflight: set = set()
        # Names whose wait raised StalledError: still half-announced at
        # the coordinator, permanently unusable for resubmission.
        self._stalled: set = set()
        # The coordinator (not Python) writes the timeline in coord mode.
        self.timeline = None

    @classmethod
    def from_env(cls, rank: int, size: int, timeline=None) -> "CoordClient":
        addr = _config.coordinator_address()
        if addr is None:
            raise TransportError(
                "multi-process world without HVD_COORD_ADDR; launch via "
                "tpurun or set HVD_COORD_ADDR=host:port")
        host, _, port_s = addr.partition(":")
        try:
            port = int(port_s) if port_s else 29521
        except ValueError:
            raise ValueError(
                f"malformed HVD_COORD_ADDR {addr!r}: the port part "
                f"{port_s!r} is not an integer (expected host:port, e.g. "
                f"10.0.0.1:29521)") from None
        if not 1 <= port <= 65535:
            raise ValueError(
                f"malformed HVD_COORD_ADDR {addr!r}: port {port} outside "
                f"1-65535")
        return cls(rank, size, host or "127.0.0.1", port,
                   timeline=timeline)

    # -- eager collectives -------------------------------------------------
    def collective(self, kind: str, x, name: str, *, op=None, root_rank=0,
                   plane: str = "auto"):
        """Run one named eager collective through the host plane.

        Semantics parity: eager ``hvd.allreduce/allgather/broadcast(value)``
        (``horovod/keras/__init__.py:90-144``); errors surface as
        FailedPreconditionError (``mpi_ops.cc:1141-1148``).
        """
        return self.wait(self.submit(kind, x, name, op=op,
                                     root_rank=root_rank, plane=plane))

    def submit(self, kind: str, x, name: str, *, op=None,
               root_rank=0, plane: str = "auto") -> "CoordHandle":
        """Non-blocking announce+send (the reference's ``ComputeAsync`` +
        ``EnqueueTensor*`` model, ``mpi_ops.cc:1752-1772``): many submits can
        be in flight at once, which is what feeds coordinator-side response
        fusion. Complete with :meth:`wait`.

        ``plane`` is the per-call placement override, the analog of the
        reference's per-call ``device_dense=``/``device_sparse=`` knobs
        (``horovod/tensorflow/__init__.py:43-55``): ``"auto"`` lets
        ``HOROVOD_RING_THRESHOLD`` elect, ``"star"`` forces the coordinator
        star, ``"ring"`` forces the client-to-client peer plane (must agree
        across ranks; a non-root broadcast always announces star — the root
        alone elects the plane)."""
        from ..ops.collectives import Op

        arr = np.asarray(x)
        average = False
        red_op = 0
        if kind in ("allreduce", "reducescatter"):
            resolved = op if op is not None else Op.SUM
            average = resolved is Op.AVERAGE
            red_op = {Op.SUM: 0, Op.AVERAGE: 0, Op.MIN: 1, Op.MAX: 2,
                      Op.PRODUCT: 3}[resolved]
        dtype_name = arr.dtype.name
        if dtype_name not in _DTYPES:
            raise TypeError(f"unsupported dtype {dtype_name} for eager "
                            f"coordination-plane collective")

        if name in self._stalled:
            # The earlier collective under this name timed out
            # (HOROVOD_STALL_TIMEOUT) but is STILL half-announced at the
            # coordinator; re-announcing would be silently dropped as a
            # duplicate and could pair this step's payload on other ranks
            # with our stale one. Fail fast with the reason.
            raise ValueError(
                f"tensor name {name!r} previously raised StalledError and "
                f"is still pending at the coordinator; a stalled "
                f"collective cannot be retried under the same name — use "
                f"a fresh name (name=None auto-names)")
        if name in self._inflight:
            raise ValueError(
                f"tensor name {name!r} is already in flight on rank "
                f"{self.rank}; synchronize() the first handle before "
                f"reusing the name (or pass name=None for auto-naming)")

        planes = {"auto": 0, "star": 1, "ring": 2}
        if plane not in planes:
            raise ValueError(f"plane must be one of {sorted(planes)}, "
                             f"got {plane!r}")

        # Deterministic fault injection (HVD_FAULT_SPEC coord:delay_ms=N):
        # no-op unless the spec targets the coordination plane.
        _faults.coord_delay()

        send_payload = not (kind == "broadcast" and self.rank != root_rank)
        data = np.ascontiguousarray(arr) if send_payload else None

        shape = (ctypes.c_longlong * max(arr.ndim, 1))(*arr.shape)
        err = ctypes.create_string_buffer(4096)
        rc = self._lib.hvdcoord_submit(
            name.encode(), _REQ_TYPES[kind], _DTYPES[dtype_name], red_op,
            root_rank, arr.ndim, shape,
            data.ctypes.data if data is not None else None,
            data.nbytes if data is not None else 0, planes[plane],
            err, len(err))
        if rc == 4:
            # World already aborted (a rank or the coordinator died):
            # fail fast with the original diagnosis instead of feeding a
            # dead coordinator and hanging in wait.
            raise WorkerFailureError(self._abort_record(err.value.decode()))
        if rc != 0:
            raise TransportError(err.value.decode())
        self._inflight.add(name)
        return CoordHandle(self, kind, name, tuple(arr.shape), arr.dtype,
                           average)

    def wait(self, handle: "CoordHandle"):
        """Block until ``handle``'s collective completes; returns the result
        (out-of-order safe — any in-flight handle may be waited first)."""
        import jax.numpy as jnp

        if handle._result is not None:
            return handle._result
        out = ctypes.c_void_p()
        out_nbytes = ctypes.c_longlong()
        sizes = (ctypes.c_longlong * self.size)()
        err = ctypes.create_string_buffer(4096)
        try:
            rc = self._lib.hvdcoord_wait(
                handle.name.encode(), ctypes.byref(out),
                ctypes.byref(out_nbytes), sizes, err, len(err))
        finally:
            self._inflight.discard(handle.name)
        if rc == 1:
            raise FailedPreconditionError(err.value.decode())
        if rc == 3:
            # HOROVOD_STALL_TIMEOUT strict mode (the reference only warns,
            # mpi_ops.cc:1153-1196; the hard deadline is a TPU-era extra).
            self._stalled.add(handle.name)
            raise StalledError(err.value.decode())
        if rc == 4:
            # World abort: a rank died / went silent (or the coordinator
            # did). The message names the dead party; the collective can
            # never complete — recovery is a world restart
            # (tpurun --restarts + horovod_tpu.elastic).
            raise WorkerFailureError(self._abort_record(err.value.decode()))
        if rc != 0:
            raise TransportError(err.value.decode())

        raw = ctypes.string_at(out.value, out_nbytes.value)
        self._lib.hvdcoord_free(out)
        result = np.frombuffer(raw, dtype=handle.dtype)

        kind, shape = handle.kind, handle.shape
        if kind == "allreduce":
            result = result.reshape(shape)
            if handle.average:
                # True division; integers promote to float exactly as the
                # compiled plane's lax.pmean does (jnp.asarray then applies
                # the session's x64 policy, so both planes agree bit-for-bit
                # on dtype).
                result = result / self.size
        elif kind == "allgather":
            total_rows = int(sum(sizes[i] for i in range(self.size)))
            result = result.reshape((total_rows,) + tuple(shape[1:]))
        elif kind == "alltoall":
            result = result.reshape(shape)
        elif kind == "reducescatter":
            result = result.reshape((shape[0] // self.size,)
                                    + tuple(shape[1:]))
            if handle.average:
                result = result / self.size
        else:  # broadcast
            result = result.reshape(shape)
        handle._result = jnp.asarray(result)
        return handle._result

    # -- fusion observability (fused-path test support, the analog of the
    # reference's deliberately-fused mpi_ops_test.py:116-148) ---------------
    def responses_received(self) -> int:
        return int(self._lib.hvdcoord_responses_received())

    def ops_completed(self) -> int:
        return int(self._lib.hvdcoord_ops_completed())

    # -- ring-plane observability (large allreduces ride a client-to-client
    # chunked ring, 2·(N-1)/N bytes/rank — the byte-accounting test's
    # evidence; threshold: HOROVOD_RING_THRESHOLD) ------------------------
    def ring_ops(self) -> int:
        return int(self._lib.hvdcoord_ring_ops())

    def ring_bytes_sent(self) -> int:
        return int(self._lib.hvdcoord_ring_bytes_sent())

    # -- liveness plane (fault injection + observability) -----------------
    def aborted(self) -> bool:
        """Whether the world has aborted (a rank or the coordinator died)."""
        return bool(self._lib.hvdcoord_aborted())

    def _abort_record(self, msg: str) -> str:
        """Leave this rank's post-mortem the moment a world ABORT
        surfaces: one ``abort`` flight-recorder event plus a dump of the
        ring (``hvd_flightrec.rank{N}.json``, :mod:`horovod_tpu.obs.
        flightrec`) — every SURVIVING rank of a dead world records the
        diagnosis (the message names the dead party) and its own last
        completed step, so an operator reads files, not scrollback.
        Returns ``msg`` unchanged so the raise sites stay one-liners;
        repeated aborts just overwrite the dump (last record wins)."""
        try:
            from ..obs import flightrec
            flightrec.record("abort", rank=self.rank, error=msg)
            flightrec.dump(reason=f"coordinator abort: {msg}",
                           rank=self.rank)
        except Exception:  # noqa: BLE001 — never mask the abort itself
            pass
        return msg

    def mute_heartbeats(self, mute: bool = True) -> None:
        """Fault hook: stop this rank's heartbeats while the process (and
        its socket) stays alive — the coordinator must detect the silence
        after ``HVD_HEARTBEAT_TIMEOUT`` and abort the world."""
        self._lib.hvdcoord_mute_heartbeats(1 if mute else 0)

    def mute_coordinator_acks(self, mute: bool = True) -> None:
        """Fault hook (rank 0 only): stop the coordinator's heartbeat-acks
        so every client independently detects a dead coordinator."""
        self._lib.hvdcoord_coord_mute_acks(1 if mute else 0)

    def pending_resize(self) -> Optional["PendingResize"]:
        """The live resize announced by the coordinator, if one is pending
        (v7 admin plane): ``(target_world, coord_port, generation)``, or
        ``None``. One atomic load — cheap enough to poll at every training
        step boundary (the quiesce ingress of
        :class:`horovod_tpu.elastic.ResizeCoordinator`)."""
        t = ctypes.c_int(0)
        p = ctypes.c_int(0)
        gen = ctypes.c_int(0)
        if not self._lib.hvdcoord_pending_resize(
                ctypes.byref(t), ctypes.byref(p), ctypes.byref(gen)):
            return None
        return PendingResize(target_world=int(t.value),
                             coord_port=int(p.value),
                             generation=int(gen.value))

    def shutdown(self):
        self._lib.hvdcoord_shutdown()


class CoordHandle:
    """In-flight eager collective (async API, reference ``ComputeAsync``
    callback model). Obtain via :meth:`CoordClient.submit`; redeem with
    :meth:`CoordClient.wait` (or ``horovod_tpu.synchronize``)."""

    def __init__(self, client: CoordClient, kind: str, name: str,
                 shape: tuple, dtype, average: bool):
        self.client = client
        self.kind = kind
        self.name = name
        self.shape = shape
        self.dtype = dtype
        self.average = average
        self._result = None
