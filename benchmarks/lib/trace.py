"""Reduction of a profiler trace to the few quantities the per-layer
readers need. The idea is ``bin/profile_step.py``'s (device op line, module
line, per-op totals); the source is the ``.xplane.pb`` the profiler writes,
read with ``jax.profiler.ProfileData``, and busy time is the UNION of the op
intervals, never their sum.

A :class:`Trace` is plain data (lists of ``(name, start_ns, end_ns)``), so
the arithmetic is checked in the tests on hand-made traces with no profiler.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]          # name, start_ns, end_ns

# Device lines as the TPU profiler names them.
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
# Control-flow parents enclose their body's ops on the same line: counting
# both would book every nested op twice in a SUM (the union is immune).
_PARENT_OPS = {"while", "conditional", "call"}
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(text: str) -> str:
    """``fusion.12`` from the event's name, which on the TPU is the whole
    HLO instruction: ``%fusion.12 = f32[8]{0} fusion(...), kind=...``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _shape_and_rest(text: str):
    """Split ``<shape> <opcode>(...`` after the `` = ``: the shape ends at
    the first space outside brackets."""
    body = text.split(" = ", 1)[1] if " = " in text else ""
    depth = 0
    for i, ch in enumerate(body):
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            return body[:i], body[i + 1:]
    return "", body


def opcode(text: str) -> str:
    """The HLO opcode (``fusion``, ``custom-call``, ``all-reduce-start``);
    for a name that is not an instruction's text, the name without its
    instance number."""
    if " = " not in text:
        return re.sub(r"\.\d+$", "", text.lstrip("%"))
    return _shape_and_rest(text)[1].split("(", 1)[0]


def custom_call_target(text: str) -> str:
    m = _TARGET.search(text)
    return m.group(1) if m else ""


def label(text: str) -> str:
    """A name a reader of the ledger can use: instruction, opcode (with a
    custom call's target) and result shape without layouts."""
    if " = " not in text:
        return text
    shape = _LAYOUT.sub("", _shape_and_rest(text)[0])
    op = opcode(text)
    target = custom_call_target(text)
    return f"{short_name(text)} {op}{':' + target if target else ''} " \
           f"-> {shape}"[:160]


@dataclasses.dataclass
class DevicePlane:
    name: str
    ops: List[Event]                  # the op line: what occupies the core
    modules: List[Event]
    # Asynchronous ops (copies, collectives) from start to done; they run
    # beside the op line and do not count as busy time.
    async_ops: List[Event] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Trace:
    devices: List[DevicePlane]
    # Host spans by name, from jax.profiler.TraceAnnotation in the harness.
    host: Dict[str, List[Interval]]


def union(intervals) -> List[Interval]:
    """Merge overlapping intervals; sorted, disjoint."""
    out: List[List[float]] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def length(intervals) -> float:
    return sum(hi - lo for lo, hi in union(intervals))


def intersect(a, b) -> List[Interval]:
    """Intersection of two interval sets."""
    a, b = union(a), union(b)
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def is_parent(name: str) -> bool:
    return opcode(name) in _PARENT_OPS


def leaf_ops(plane: DevicePlane) -> List[Event]:
    return [e for e in plane.ops if not is_parent(e[0])]


def busy_ns(plane: DevicePlane) -> float:
    """Time in which some operation ran on the device: union, so nested
    and concurrent ops count once."""
    return length((s, e) for _, s, e in plane.ops)


def mean_busy_s(trace: "Trace") -> float:
    """Busy seconds averaged over the devices traced."""
    return sum(busy_ns(p) for p in trace.devices) / len(trace.devices) / 1e9


def matching_ns(plane: DevicePlane, pred: Callable[[str], bool]) -> float:
    """Summed device durations of the leaf ops whose name matches."""
    return sum(e - s for n, s, e in leaf_ops(plane) if pred(n))


def collective_ns(plane: DevicePlane, pred: Callable[[str], bool]):
    """(total, exposed) time of the matching collectives on one device.
    They are looked for on the op line and on the asynchronous line (where
    a start..done pair is one event), and their intervals are merged, so a
    collective seen on both counts once. Exposed is the part during which
    no other op ran on the op line."""
    mine = union((s, e) for n, s, e in leaf_ops(plane) + plane.async_ops
                 if pred(n))
    rest = [(s, e) for n, s, e in leaf_ops(plane) if not pred(n)]
    return length(mine), length(mine) - length(intersect(mine, rest))


def top_ops(plane: DevicePlane, k: int = 10) -> List[List]:
    """The k leaf ops (one instruction of the compiled program each, summed
    over the steps traced) that took most device time, as
    ``[label, seconds]``."""
    total: Dict[str, float] = {}
    for n, s, e in leaf_ops(plane):
        total[n] = total.get(n, 0.0) + (e - s)
    best = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[label(n), ns / 1e9] for n, ns in best]


def idle_gaps(plane: DevicePlane, host: Dict[str, List[Interval]],
              window: Optional[Interval], k: int = 10) -> List[List]:
    """The k longest gaps between device ops inside ``window``, each named
    by the harness span that covers most of it (``unattributed`` if none),
    summed per name, as ``[name, seconds]``. Gaps only: a span that overlaps
    busy time is not charged."""
    busy = union((s, e) for _, s, e in plane.ops)
    if not busy:
        return []
    lo, hi = window if window else (busy[0][0], busy[-1][1])
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    total: Dict[str, float] = {}
    for gap in sorted(gaps, key=lambda g: g[0] - g[1])[:50]:
        name, cover = "unattributed", 0.0
        for span, ivs in host.items():
            c = length(intersect([gap], ivs))
            if c > cover:
                name, cover = span, c
        total[name] = total.get(name, 0.0) + (gap[1] - gap[0])
    best = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in best]


def find_xplane(trace_dir: str) -> str:
    """The newest profile under a trace directory (or in it)."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        + glob.glob(os.path.join(trace_dir, "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def from_xplane(path: str, span_prefix: str = "bench.",
                cpu_as_device: bool = False) -> Trace:
    """Read the profiler's file. Device planes are ``/device:TPU:<n>``;
    harness spans are the host events whose name starts with
    ``span_prefix``. ``cpu_as_device`` (rehearsals only) stands the CPU
    backend's executor threads in for one device, so that the readers'
    plumbing runs where there is no chip."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host, cpu_ops = [], {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            devices.append(DevicePlane(
                plane.name,
                ops=_events(lines.get(OPS_LINE)),
                modules=_events(lines.get(MODULES_LINE)),
                async_ops=_events(lines.get(ASYNC_LINE))))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for n, s, e in _events(line):
                    if n.startswith(span_prefix):
                        host.setdefault(n, []).append((s, e))
                    elif line.name.startswith("tf_XLA") and e > s:
                        cpu_ops.append((n, s, e))
    if cpu_as_device and not devices:
        devices.append(DevicePlane("/device:TPU:0", cpu_ops, []))
    devices.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    return Trace(devices, host)


def _events(line) -> List[Event]:
    if line is None:
        return []
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def describe(path: str, limit: int = 12) -> str:
    """Planes, lines and the first events of a trace file: what a builder
    looks at by hand before writing a reader against it."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            for e in evs[:limit]:
                stats = {k: v for k, v in e.stats}
                out.append(f"    {e.name} start={e.start_ns} "
                           f"dur={e.duration_ns} {stats}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(describe(find_xplane(sys.argv[1])))
