"""The program's own spans (``horovod_tpu.utils.timeline.spans()``) laid on
a profiler trace's clock, and the device ops' framework names: what the
readers ``layer_metrics/program_spans.py`` and
``layer_metrics/device_scopes.py`` (and ``clock_check.py``) share.

The host's clock. A trace's event times are nanoseconds since the
session's start; the plane ``Task Environment`` carries
``profile_start_time`` and ``profile_stop_time`` in Unix nanoseconds. The
program stamps its spans on the wall clock, so ``span.start_ns -
profile_start_time`` is the span on the trace's clock — with the
profiler's host tracer off. On the chip the two records of one span agree
to 2 us (``clock_check.py``, PERF.md section 6). A reader that finds no
anchor returns nothing; it never guesses one.

The device's clock is NOT that clock: the TPU's planes count from a start
of their own, which ``clock_check.py`` bounds per session: 0.1 to 1.8 ms
before the host's in the sessions of PERF.md section 6, never the same.
Nothing in a trace taken with the host tracer off says by how much, so
:func:`device_clock_lag` bounds it from causality: the host cannot see a
step complete before the device completed it.

Everything here works on plain tuples, so the arithmetic is tested on
hand-made spans and traces with no profiler.
"""

from __future__ import annotations

import re
import statistics
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from lib import trace as tr

Interval = Tuple[float, float]


class S(NamedTuple):
    """A program span on the trace's clock (ns since the session's start)."""
    id: int
    name: str
    start: float
    end: float
    thread: int
    parent: int
    ids: dict


def program_spans() -> list:
    """The recorder's snapshot (wall clock), or nothing where the program
    has no recorder (a parent commit from before it)."""
    try:
        from horovod_tpu.utils import timeline
        return list(timeline.spans())
    except (ImportError, AttributeError):
        return []


def anchor(xplane_path: str) -> Optional[Tuple[int, int]]:
    """``(profile_start_time, profile_stop_time)`` in Unix ns from the
    trace's ``Task Environment`` plane, or None."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            if "profile_start_time" in stats and \
                    "profile_stop_time" in stats:
                return (int(stats["profile_start_time"]),
                        int(stats["profile_stop_time"]))
    return None


def on_trace_clock(spans: Iterable, start_ns: int, stop_ns: int) -> List[S]:
    """Keep the spans that lie inside the session and shift them onto its
    clock."""
    return [S(s.id, s.name, s.start_ns - start_ns, s.end_ns - start_ns,
              s.thread, s.parent, dict(s.ids))
            for s in spans if s.start_ns >= start_ns and s.end_ns <= stop_ns]


def median_ms(spans: Iterable[S], name: str) -> Optional[float]:
    d = [(s.end - s.start) / 1e6 for s in spans if s.name == name]
    return statistics.median(d) if d else None


def self_ms(spans: List[S], name: str) -> List[float]:
    """Per span called ``name``: its duration less its direct children's."""
    child_ns: Dict[int, float] = {}
    for s in spans:
        child_ns[s.parent] = child_ns.get(s.parent, 0.0) + (s.end - s.start)
    return [((s.end - s.start) - child_ns.get(s.id, 0.0)) / 1e6
            for s in spans if s.name == name]


def uncovered(lo: float, hi: float, covered: Iterable[Interval]
              ) -> List[Interval]:
    """What of ``[lo, hi]`` the intervals leave free: the gaps between
    device ops, or a span's time outside its children."""
    taken = tr.intersect(covered, [(lo, hi)])
    edges = [lo] + [t for iv in taken for t in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def device_clock_lag(completions: Iterable[float],
                     observed: Iterable[float]) -> Optional[float]:
    """How far behind the host's clock the device's runs, at most (ns):
    the least ``observed[k] - completions[k]`` over the pairs, where
    ``completions[k]`` is when the device (on its clock) finished some work
    and ``observed[k]`` when the host (on its) came back from waiting for
    that work. The true lag is smaller by the quickest wake-up of the
    host, tens of microseconds. None without pairs."""
    diffs = [o - c for c, o in zip(completions, observed)]
    return min(diffs) if diffs else None


# -- device ops by named scope ------------------------------------------------

FORWARD, BACKWARD, OPTIMIZER, UNSCOPED = ("forward", "backward",
                                          "optimizer", "unscoped")
# The scope is a path component of the op's framework name:
# ``jit(step)/jvp(forward)/dot_general`` is forward,
# ``.../transpose(jvp(forward))/...`` backward, ``.../optimizer/...`` the
# update (its ``allreduce.bucket<k>`` scopes nest under it).
_BACKWARD = re.compile(r"transpose\(jvp\(forward\)\)")
_FORWARD = re.compile(r"(?:^|[/\"=])(?:jvp\()?forward\)?(?:[/\"]|$)")
_OPTIMIZER = re.compile(r"(?:^|[/\"=])optimizer(?:[/\"]|$)")


def scope_of(text: str) -> str:
    if _BACKWARD.search(text):
        return BACKWARD
    if _FORWARD.search(text):
        return FORWARD
    if _OPTIMIZER.search(text):
        return OPTIMIZER
    return UNSCOPED


def framework_names(xplane_path: str, plane_name: str = "/device:TPU:0"
                    ) -> Dict[str, str]:
    """Event name (the HLO instruction's text) -> the op's framework name
    (``jit(step)/jvp(forward)/dot_general:``) for one plane.

    On the TPU the framework name is the stat ``tf_op`` of the event's
    METADATA, which ``jax.profiler.ProfileData`` does not hand out (an
    event's ``stats`` are its own three: offset, duration, time scale). So
    this walks the file's protobuf wire format itself, for the plane's
    ``event_metadata`` and ``stat_metadata`` maps only: the lines, which are
    nearly all of the file, are skipped unread. Fields (xplane.proto):
    XSpace.planes=1; XPlane.name=2, .event_metadata=4, .stat_metadata=5;
    map entries key=1, value=2; XEventMetadata.name=2, .stats=5;
    XStatMetadata.name=2; XStat.metadata_id=1, .str_value=5, .ref_value=7.
    An instruction without the stat (a copy the compiler added) is left
    out."""
    with open(xplane_path, "rb") as fh:
        data = fh.read()
    for number, plane in _fields(data):
        if number != 1 or not isinstance(plane, bytes):
            continue
        fields = list(_fields(plane, skip=(3,)))
        if _first(fields, 2, b"").decode() != plane_name:
            continue
        stat_names = {}
        for entry in (v for n, v in fields if n == 5):
            kv = list(_fields(entry))
            stat_names[_first(kv, 1, 0)] = _first(
                list(_fields(_first(kv, 2, b""))), 2, b"").decode()
        out = {}
        for entry in (v for n, v in fields if n == 4):
            meta = list(_fields(_first(list(_fields(entry)), 2, b"")))
            for stat in (v for n, v in meta if n == 5):
                st = list(_fields(stat))
                if stat_names.get(_first(st, 1, 0)) != "tf_op":
                    continue
                text = _first(st, 5, None)
                if text is None:         # a reference to a stat's name
                    text = stat_names.get(_first(st, 7, 0), "").encode()
                out[_first(meta, 2, b"").decode()] = text.decode()
        return out
    return {}


def _first(fields, number, default):
    return next((v for n, v in fields if n == number), default)


def _fields(buf: bytes, skip=()):
    """``(field number, value)`` of one protobuf message: ints for varints
    and fixed-width fields, bytes for length-delimited ones. Fields in
    ``skip`` are stepped over without a copy."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = (None if number in skip else buf[i:i + size]), i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + size], "little"), i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        if number not in skip:
            yield number, value


def _varint(buf: bytes, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7
