"""Horovod Timeline: Chrome-tracing (catapult) JSON writer.

Reference parity (``timeline.h``/``timeline.cc``, SURVEY §5.1):

* Enabled by ``HOROVOD_TIMELINE=<file>``, written by the coordinator
  (rank 0) only, yet shows all workers' readiness
  (``mpi_ops.cc:1275-1278``, ``docs/timeline.md:7-11``).
* Each tensor is a fake "process" (pid) with a metadata event naming it
  (``timeline.cc:59-76``); a tensor-name→pid table keeps files small
  (``timeline.h:83``).
* Per-tensor state machine UNKNOWN→NEGOTIATING→TOP_LEVEL→ACTIVITY
  (``timeline.h:37-42``).
* Phase 1 "NEGOTIATE_<OP>": begin event on first request, an instant event
  per rank as it reports ready (``NegotiateRankReady``,
  ``timeline.cc:118-125``), end when all ranks are in.
* Phase 2: top-level op event with nested activities (QUEUE, SCHEDULE,
  MEMCPY_IN_FUSION_BUFFER, …; ``mpi_ops.cc:623-635``,
  ``docs/timeline.md:25-43``).
* ``End`` logs the output dtype+shape (``timeline.cc:203-220``); writes are
  mutex-guarded; ~1 s flush interval (``timeline.h:35``).

TPU adaptation: negotiation events come from the host coordination plane
(or are synthesized instantly in single-controller mode where no negotiation
exists); compute-phase boundaries come from dispatch timestamps — XLA owns
on-chip scheduling, so fine-grained on-device phases belong to the JAX
profiler.

**Program spans** (the one span mechanism of the training path). Whether or
not a :class:`Timeline` is on, :func:`span` records ``(name, start, end,
thread, parent, ids)`` into a bounded in-memory ring (:data:`RING_SPANS`),
stamped with ``time.perf_counter_ns()`` and handed out by :func:`spans` in
wall-clock Unix nanoseconds — the clock on which a profiler trace's
``profile_start_time`` stat is given, so a span can be laid on a device
trace without the profiler's host tracer. A :class:`Timeline` writes the
same spans as complete (``"ph": "X"``) events from a drain thread of its
own, and one ``hvd_clock_origin`` metadata event holding the Unix
nanoseconds of its ``ts`` 0: add it to a ``ts`` and the event sits on the
profiler trace's clock (``docs/timeline.md``).
"""

from __future__ import annotations

import atexit
import contextlib
import gc
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, Iterable, List, NamedTuple, Optional

import jax

from ..obs.registry import registry as _metrics_registry


class _State:
    UNKNOWN = 0
    NEGOTIATING = 1
    TOP_LEVEL = 2
    ACTIVITY = 3


# Host-plane phase names (beyond the reference's collective activities):
# the overlapped training hot path emits these so a trace shows WHAT the
# host was doing while the device ran — input staging and checkpointing,
# the two host activities PR 3 moved off the step's critical path.
H2D = "H2D"                      # prefetch thread: host→device batch copy
CKPT_SNAPSHOT = "CKPT_SNAPSHOT"  # step loop: device→host state snapshot
CKPT_WRITE = "CKPT_WRITE"        # background writer: orbax write + GC
BAD_STEP = "BAD_STEP"            # guard: non-finite grads, update skipped


# ---------------------------------------------------------------------------
# Program spans: one bounded in-memory recorder, always on.
# ---------------------------------------------------------------------------

RING_SPANS = 65536   # spans kept; the oldest fall out


class Span(NamedTuple):
    """One closed span as :func:`spans` hands it out. Times are wall-clock
    Unix nanoseconds; ``parent`` is the ``id`` of the span that was open on
    the same thread when this one began (0: none)."""
    id: int
    name: str
    start_ns: int
    end_ns: int
    thread: int
    parent: int
    ids: Dict[str, Any]


# perf_counter_ns -> wall clock: one anchor pair for the process. The two
# reads are ~100 ns apart, far under what a span is read to.
_ANCHOR_WALL_NS = time.time_ns()
_ANCHOR_PERF_NS = time.perf_counter_ns()

_ring: deque = deque(maxlen=RING_SPANS)   # append is atomic under the GIL
_next_id = itertools.count(1).__next__
_now_ns = time.perf_counter_ns
_TraceAnnotation = jax.profiler.TraceAnnotation
_tls = threading.local()
_thread_names: Dict[int, str] = {}
# Open Timelines' pending deques: a closed span is appended to each (the
# Timeline's own thread formats and writes it later).
_sinks: tuple = ()
_sinks_lock = threading.Lock()


def _stack() -> list:
    """This thread's stack of open spans (made at its first span)."""
    try:
        return _tls.stack
    except AttributeError:
        t = threading.current_thread()
        _tls.tid = t.ident
        _thread_names[t.ident] = t.name
        _tls.stack = []
        return _tls.stack


class span:
    """``with span("fit.step", step=n):`` — record one span of the calling
    thread. Nothing is formatted or written here: two clock reads, a
    thread-local stack push/pop and one ring append. ``ids`` carry what
    joins the spans of one step or batch across threads (``step=``,
    ``batch=``); :func:`annotate` adds more while the span is open. The
    span also enters a ``jax.profiler.TraceAnnotation`` of the same name,
    so a profiler session run WITH the host tracer shows the program's
    spans in its own viewer."""

    __slots__ = ("name", "ids", "id", "parent", "start_ns", "end_ns",
                 "to_timelines", "_annotation", "_dropped")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ids = ids
        self.to_timelines = True
        self._dropped = False

    def __enter__(self) -> "span":
        try:
            stack = _tls.stack
        except AttributeError:
            stack = _stack()
        self.parent = stack[-1].id if stack else 0
        self.id = _next_id()
        stack.append(self)
        self._annotation = a = _TraceAnnotation(self.name)
        a.__enter__()
        self.start_ns = _now_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.end_ns = end = _now_ns()
        self._annotation.__exit__(exc_type, exc, tb)
        _tls.stack.pop()
        if not self._dropped:
            rec = (self.id, self.name, self.start_ns, end, _tls.tid,
                   self.parent, self.ids)
            _ring.append(rec)
            if _sinks and self.to_timelines:
                for sink in _sinks:
                    sink.append(rec)
        return False

    def drop(self) -> None:
        """Leave no record of this span (the turn that found the stream
        at its end did no work worth a row)."""
        self._dropped = True


def annotate(**ids) -> None:
    """Add ``ids`` to the innermost span open on this thread (no-op when
    none is): how a callee names what its caller's span turned out to hold
    — the prefetch iterator stamps ``batch=`` and ``queue_depth=`` on the
    ``fit.next_batch`` span it is called inside."""
    stack = _stack()
    if stack:
        stack[-1].ids.update(ids)


def record_span(name: str, start_ns: int, end_ns: int, **ids) -> None:
    """Record a span measured elsewhere (``perf_counter_ns`` readings): a
    compile the runtime reported after the fact."""
    stack = _stack()
    rec = (_next_id(), name, start_ns, end_ns, _tls.tid,
           stack[-1].id if stack else 0, ids)
    _ring.append(rec)
    for sink in _sinks:
        sink.append(rec)


def _snapshot() -> list:
    while True:
        try:
            return list(_ring)
        except RuntimeError:      # another thread appended mid-copy
            continue


def spans() -> List[Span]:
    """The ring's closed spans, oldest first (by end), on the wall clock.
    ``hvd.shutdown()`` does not clear it: a reader may run after the
    world is gone."""
    shift = _ANCHOR_WALL_NS - _ANCHOR_PERF_NS
    return [Span(i, n, s + shift, e + shift, t, p, ids)
            for i, n, s, e, t, p, ids in _snapshot()]


def thread_names() -> Dict[int, str]:
    """``Span.thread`` -> the thread's name when it first opened a span."""
    return dict(_thread_names)


# What jax does before a program's first step, and recompilations where
# they happen: the runtime reports each trace, each lowering to MLIR and
# each backend compile after the fact, with the function's name. Each
# becomes a span ending now on the thread that did the work and seconds on
# a counter, so a slow set-up, a gap or a slow step can be put down to a
# phase and a function. The persistent cache says on the same thread,
# before the compile's report, whether it was asked and whether it had
# the executable: that is the ``cache`` id of ``xla.compile``.
_PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
           "/jax/core/compile/backend_compile_duration": "compile"}
_CACHE_NOTES = {"/jax/compilation_cache/compile_requests_use_cache": "miss",
                "/jax/compilation_cache/cache_hits": "hit"}
_m_compiles = _metrics_registry().counter(
    "hvd_compiles_total",
    "XLA backend compile requests in this process: a program traced for "
    "a new shape, compiled or loaded from the persistent cache. A repeat "
    "of a shape already compiled is none")
_m_compile_seconds = _metrics_registry().counter(
    "hvd_compile_seconds_total",
    "Seconds jax spent tracing functions to jaxprs (phase=trace), lowering "
    "them to MLIR (lower) and in backend compile requests, cache loads "
    "included (compile): the sum of the xla.trace, xla.lower and "
    "xla.compile spans, nested ones counted in both", labels=("phase",))
_m_compile_cache = _metrics_registry().counter(
    "hvd_compile_cache_total",
    "Backend compile requests that asked the persistent compilation "
    "cache, by what it had: result=hit loaded the executable, miss "
    "compiled it", labels=("result",))
_phase_seconds = {phase: _m_compile_seconds.labels(phase=phase)
                  for phase in _PHASES.values()}


def _on_event(event: str, **_kw) -> None:
    note = _CACHE_NOTES.get(event)
    # jax "asks" its cache also where it was given no directory to keep
    # one in: that request is no miss.
    if note is not None and jax.config.jax_compilation_cache_dir:
        _tls.cache = note


def _on_event_duration(event: str, duration_s: float, fun_name: str = "",
                       **_kw) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        return
    now = time.perf_counter_ns()
    ids = {"fun": fun_name}
    if phase == "compile":
        _m_compiles.inc()
        ids["cache"] = cache = getattr(_tls, "cache", "off")
        _tls.cache = "off"
        if cache != "off":
            _m_compile_cache.labels(result=cache).inc()
    _phase_seconds[phase].inc(duration_s)
    record_span("xla." + phase, now - int(duration_s * 1e9), now, **ids)


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_event_duration)

# Collector pauses: a full (generation-2) collection, and any collection
# of a millisecond or more, is the span ``host.gc`` on the thread it
# stopped. The rest cost two clock reads and leave nothing.
_GC_SPAN_NS = 1_000_000
_gc_start_ns = 0


def _on_gc(phase: str, info: dict) -> None:
    global _gc_start_ns
    if phase == "start":
        _gc_start_ns = _now_ns()
        return
    end = _now_ns()
    if info["generation"] == 2 or end - _gc_start_ns >= _GC_SPAN_NS:
        record_span("host.gc", _gc_start_ns, end,
                    generation=info["generation"],
                    collected=info["collected"])


gc.callbacks.append(_on_gc)


@contextlib.contextmanager
def maybe_op(tl: Optional["Timeline"], tensor_name: str, op_kind: str):
    """A program span named ``op_kind`` and, when ``tl`` is a Timeline,
    also the scoped B/E :meth:`Timeline.op` on row ``tensor_name`` — the
    checkpoint and guard phases exist with or without ``HOROVOD_TIMELINE``
    and their call sites do not branch. Each concurrent emitter uses its
    own ``tensor_name`` row, so the per-row state machine never sees
    interleaved ops from two threads."""
    with span(op_kind) as sp:
        if tl is None:
            yield None
            return
        sp.to_timelines = False      # written below, as B/E on its row
        with tl.op(tensor_name, op_kind):
            yield tl


class TimelineStateError(RuntimeError):
    """Illegal timeline transition — a B event would be left unbalanced
    (the reference asserts these transitions, ``timeline.h:37-42`` enforced
    in ``timeline.cc:118-135``)."""


class Timeline:
    """Chrome-tracing writer (JSON array format, streaming).

    The per-tensor state machine UNKNOWN→NEGOTIATING→TOP_LEVEL→ACTIVITY is
    ENFORCED (not just tracked): a call out of order raises
    :class:`TimelineStateError` instead of silently writing an unbalanced
    B/E stream. Activities nest; ``_depth`` counts open activity frames.
    Every duration/instant event carries ``tid: 0`` — Perfetto and some
    catapult builds require a tid to pair B/E events within a pid.
    """

    FLUSH_INTERVAL_SECS = 1.0  # timeline.h:35

    def __init__(self, path: str):
        self._lock = threading.Lock()
        self._pid_lock = threading.Lock()
        self._file = open(path, "w")
        self._file.write("[\n")
        # ``ts`` 0 on the recorder's clock, and what it is on the wall
        # clock: written once so the file can be laid on a profiler trace.
        self._start_ns = time.perf_counter_ns()
        self._pids: dict[str, int] = {}
        self._states: dict[str, int] = {}
        self._depth: dict[str, int] = {}
        self._last_flush = time.monotonic()
        self._closed = False
        self._emit({"name": "hvd_clock_origin", "ph": "M", "pid": 0,
                    "args": {"unix_ns": self._start_ns - _ANCHOR_PERF_NS
                             + _ANCHOR_WALL_NS}})
        # Program spans closed from now on, written by a thread of this
        # writer's own at the flush cadence — never by the thread a span
        # measured.
        self._pending: deque = deque(maxlen=RING_SPANS)
        self._span_threads: set = set()
        self._stop = threading.Event()
        global _sinks
        with _sinks_lock:
            _sinks = _sinks + (self._pending,)
        self._drainer = threading.Thread(
            target=self._drain_loop, name="hvd-timeline-spans", daemon=True)
        self._drainer.start()
        # Crash safety: the ~1 s flush cadence means a killed rank loses
        # the buffered tail of its trace — the very events that explain
        # the death. An atexit close catches normal-but-uncloseed exits;
        # the fatal-signal path is covered by the flight recorder's
        # crash hooks (runtime.init registers self.flush there).
        atexit.register(self.close)

    # -- low-level ---------------------------------------------------------

    def _ts_us(self) -> int:
        """Microseconds since this writer's origin, on the span recorder's
        clock (``perf_counter_ns``): B/E events and drained spans share
        one time base, and ``hvd_clock_origin`` ties it to the wall."""
        return (time.perf_counter_ns() - self._start_ns) // 1000

    def _drain_loop(self) -> None:
        while not self._stop.wait(self.FLUSH_INTERVAL_SECS):
            self._drain_spans()

    def _drain_spans(self) -> None:
        """Write the program spans closed since the last drain as complete
        events, one Chrome "thread" per recording thread under the
        ``program spans`` row."""
        while True:
            try:
                _id, name, start, end, tid, _parent, ids = \
                    self._pending.popleft()
            except IndexError:
                return
            pid = self._pid("program spans")
            if tid not in self._span_threads:
                self._span_threads.add(tid)
                self._emit({"name": "thread_name", "ph": "M", "pid": pid,
                            "tid": tid,
                            "args": {"name": _thread_names.get(tid, "")}})
            ev = {"name": name, "ph": "X", "pid": pid, "tid": tid,
                  "ts": (start - self._start_ns) // 1000,
                  "dur": (end - start) // 1000}
            if ids:
                ev["args"] = dict(ids)
            self._emit(ev)

    def _emit(self, ev: dict) -> None:
        with self._lock:
            if self._closed:
                return
            self._file.write(json.dumps(ev, default=str) + ",\n")
            now = time.monotonic()
            if now - self._last_flush > self.FLUSH_INTERVAL_SECS:
                self._file.flush()
                self._last_flush = now

    def flush(self, fsync: bool = True) -> None:
        """Push buffered events to disk NOW (fsync by default): called
        from error paths (:meth:`abort`) and crash hooks, where "the OS
        probably would have written it" is not good enough — the reader
        is a post-mortem."""
        self._drain_spans()
        with self._lock:
            if self._closed:
                return
            try:
                self._file.flush()
                if fsync:
                    os.fsync(self._file.fileno())
            except (OSError, ValueError):
                pass  # a dying process keeps dying
            self._last_flush = time.monotonic()

    def _pid(self, tensor_name: str) -> int:
        pid = self._pids.get(tensor_name)
        if pid is not None:
            return pid
        with self._pid_lock:     # the span drainer registers a row too
            pid = self._pids.get(tensor_name)
            if pid is not None:
                return pid
            pid = len(self._pids)
            self._pids[tensor_name] = pid
            # Metadata event registering the tensor as a pseudo-process
            # (timeline.cc:59-76).
            self._emit({"name": "process_name", "ph": "M", "pid": pid,
                        "args": {"name": tensor_name}})
            self._emit({"name": "process_sort_index", "ph": "M", "pid": pid,
                        "args": {"sort_index": pid}})
        return pid

    def _expect(self, tensor_name: str, allowed: tuple, call: str) -> None:
        state = self._states.get(tensor_name, _State.UNKNOWN)
        if state not in allowed:
            names = {0: "UNKNOWN", 1: "NEGOTIATING", 2: "TOP_LEVEL",
                     3: "ACTIVITY"}
            raise TimelineStateError(
                f"timeline: {call}({tensor_name!r}) illegal in state "
                f"{names[state]} (allowed: "
                f"{'/'.join(names[s] for s in allowed)})")

    # -- negotiation phase (timeline.cc:107-140) ---------------------------

    def negotiate_start(self, tensor_name: str, op_kind: str) -> None:
        pid = self._pid(tensor_name)
        self._expect(tensor_name, (_State.UNKNOWN,), "negotiate_start")
        self._states[tensor_name] = _State.NEGOTIATING
        self._emit({"name": f"NEGOTIATE_{op_kind}", "ph": "B", "pid": pid,
                    "tid": 0, "ts": self._ts_us()})

    def negotiate_rank_ready(self, tensor_name: str, rank: int) -> None:
        pid = self._pid(tensor_name)
        self._expect(tensor_name, (_State.NEGOTIATING,),
                     "negotiate_rank_ready")
        self._emit({"name": str(rank), "ph": "i", "pid": pid, "tid": 0,
                    "ts": self._ts_us(), "s": "p"})

    def negotiate_end(self, tensor_name: str) -> None:
        pid = self._pid(tensor_name)
        self._expect(tensor_name, (_State.NEGOTIATING,), "negotiate_end")
        self._states[tensor_name] = _State.UNKNOWN
        self._emit({"name": "", "ph": "E", "pid": pid, "tid": 0,
                    "ts": self._ts_us()})

    def negotiate_instant(self, tensor_name: str, op_kind: str,
                          ready_ranks: Iterable[int] = ()) -> None:
        """Single-controller mode: SPMD needs no negotiation; record the
        would-be negotiation as an instantaneous phase for trace parity."""
        self.negotiate_start(tensor_name, op_kind)
        for r in ready_ranks:
            self.negotiate_rank_ready(tensor_name, r)
        self.negotiate_end(tensor_name)

    # -- processing phase (timeline.cc:142-220) ----------------------------

    def start(self, tensor_name: str, op_kind: str) -> None:
        pid = self._pid(tensor_name)
        self._expect(tensor_name, (_State.UNKNOWN,), "start")
        self._states[tensor_name] = _State.TOP_LEVEL
        self._depth[tensor_name] = 0
        self._emit({"name": op_kind, "ph": "B", "pid": pid, "tid": 0,
                    "ts": self._ts_us()})

    def activity_start(self, tensor_name: str, activity: str) -> None:
        pid = self._pid(tensor_name)
        self._expect(tensor_name, (_State.TOP_LEVEL, _State.ACTIVITY),
                     "activity_start")
        self._states[tensor_name] = _State.ACTIVITY
        self._depth[tensor_name] = self._depth.get(tensor_name, 0) + 1
        self._emit({"name": activity, "ph": "B", "pid": pid, "tid": 0,
                    "ts": self._ts_us()})

    def activity_end(self, tensor_name: str) -> None:
        pid = self._pid(tensor_name)
        self._expect(tensor_name, (_State.ACTIVITY,), "activity_end")
        depth = self._depth.get(tensor_name, 1) - 1
        self._depth[tensor_name] = depth
        self._states[tensor_name] = (
            _State.TOP_LEVEL if depth == 0 else _State.ACTIVITY)
        self._emit({"name": "", "ph": "E", "pid": pid, "tid": 0,
                    "ts": self._ts_us()})

    def end(self, tensor_name: str, output=None) -> None:
        """End the top-level event, logging output dtype+shape
        (timeline.cc:203-220)."""
        pid = self._pid(tensor_name)
        self._expect(tensor_name, (_State.TOP_LEVEL,), "end")
        args = {}
        if output is not None:
            shape = getattr(output, "shape", None)
            dtype = getattr(output, "dtype", None)
            if shape is not None:
                args["shape"] = list(shape)
            if dtype is not None:
                args["dtype"] = str(dtype)
        self._states[tensor_name] = _State.UNKNOWN
        ev = {"name": "", "ph": "E", "pid": pid, "tid": 0,
              "ts": self._ts_us()}
        if args:
            ev["args"] = args
        self._emit(ev)

    def abort(self, tensor_name: str, error: Optional[str] = None) -> None:
        """Close every open B event for ``tensor_name`` after a dispatch
        failure so the trace stays balanced (error paths must not corrupt
        the stream). Safe to call in any state."""
        state = self._states.get(tensor_name, _State.UNKNOWN)
        if state == _State.UNKNOWN:
            return
        pid = self._pid(tensor_name)
        if state == _State.NEGOTIATING:
            self.negotiate_end(tensor_name)
            self.flush(fsync=True)
            return
        while self._depth.get(tensor_name, 0) > 0:
            self.activity_end(tensor_name)
        ev = {"name": "", "ph": "E", "pid": pid, "tid": 0,
              "ts": self._ts_us()}
        if error:
            ev["args"] = {"error": error}
        self._states[tensor_name] = _State.UNKNOWN
        self._emit(ev)
        # An abort usually precedes a death (dispatch failure, world
        # ABORT): make the trace durable now instead of trusting the
        # 1 s cadence to get another turn.
        self.flush(fsync=True)

    # -- scoped helpers (serving plane) ------------------------------------
    #
    # The training-side emitters drive the state machine from callbacks
    # spread across the dispatch path, so they use the raw begin/end calls
    # above. The serving plane (horovod_tpu.serve) brackets whole code
    # regions — QUEUE → PAD → XLA_EXECUTE → RESPOND inside one INFERENCE
    # op — where scope-exit safety matters more: an exception mid-phase
    # must not leave a B event unbalanced.

    @contextlib.contextmanager
    def op(self, tensor_name: str, op_kind: str):
        """Scoped top-level event; aborts (balanced close + error arg) if
        the body raises."""
        self.start(tensor_name, op_kind)
        try:
            yield self
        except BaseException as e:
            self.abort(tensor_name, error=repr(e))
            raise
        else:
            self.end(tensor_name)

    @contextlib.contextmanager
    def activity(self, tensor_name: str, name: str):
        """Scoped nested activity under an open :meth:`op`."""
        self.activity_start(tensor_name, name)
        try:
            yield self
        finally:
            if self._states.get(tensor_name) == _State.ACTIVITY:
                self.activity_end(tensor_name)

    def close(self) -> None:
        global _sinks
        with _sinks_lock:
            _sinks = tuple(q for q in _sinks if q is not self._pending)
        self._stop.set()
        self._drain_spans()
        with self._lock:
            if self._closed:
                return
            self._closed = True
            try:
                atexit.unregister(self.close)
            except Exception:  # noqa: BLE001 — interpreter may be exiting
                pass
            # Chrome's trace viewer tolerates the trailing comma; close the
            # array for strict-JSON consumers.
            self._file.write("{}]\n")
            self._file.close()
