"""Set-up from inside the program: the seven ``setup.*`` metrics, read from
the spans ``hvd.import``, ``hvd.init``, ``xla.trace``, ``xla.lower`` and
``xla.compile`` (``horovod_tpu.utils.timeline``; docs/timeline.md). Layer:
entry and launcher. And, from the same snapshot and anchor,
``host.gc_ms_per_step`` (layer: host loop), at the end of this text.

Set-up is the interval from the start of ``hvd.import`` (the program's first
statement) to the profiler session's ``profile_start_time``
(``lib/spans.anchor``). On the thread of ``hvd.import``, each instant of it
belongs to the INNERMOST of those spans open there, or to none:

* ``setup.import_s``: ``hvd.import``, less what jax traced and compiled
  while the modules loaded;
* ``setup.trace_s``, ``setup.lower_s``: ``xla.trace`` (a function to a
  jaxpr) and ``xla.lower`` (a jaxpr to MLIR; a trace inside a lowering is
  the trace's);
* ``setup.cache_load_s``: ``xla.compile`` whose ``cache`` id is ``"hit"``;
* ``setup.backend_compile_s`` and ``setup.cache_misses``: the seconds and
  the number of the ``xla.compile`` spans whose ``cache`` is ``"miss"`` or
  ``"off"``: 0 says the run was warm;
* ``setup.unnamed_s``: the rest, less ``hvd.init``: the backend's start-up
  where the harness touched the devices first, its pool, the first
  executions, the reference's execution, the warm-up steps and (a traced
  run only) the profiler's own start.

The spans are reported after the fact, so ``parent`` cannot say which of
them lie inside which: nesting is read from the times, and of the spans
open at one instant the shortest is the inner. A span of another
thread, or one that ends after the anchor, is left out. The seven and
``hvd.init`` add up to the interval. The functions behind the seconds go on
an earlier line, ``{"event": "setup_phases", ..., "by_fun": [[fun, trace_s,
lower_s, compile_s, cache], ...]}``, the ten largest, each with the time in
which IT was the innermost (lowerings and compiles are named
``jit(<fun>)`` by jax: the wrapper is dropped so that a function's three
phases share a row), and ``unnamed_gaps``, the six longest stretches under
no span with the span that ended last before each. Where the program
records no ``hvd.import`` (a tree from before these spans) or the trace has
no anchor, set-up is not read and a line of its own says so.

``host.gc_ms_per_step``: the collector's pauses (span ``host.gc``: every
generation-2 collection and any collection of a millisecond or more) inside
the profiler session, summed over every thread, ÷ the steps of the window.
The pauses themselves go on the line ``{"event": "host_gc", ...}``: a pause
that comes back at one step COUNT in every run is an allocation-counted full
collection. Where the ring holds no ``host.gc`` span at all (a tree from
before it: importing jax alone leaves several) the metric is left out and
the line says so."""

import json
import os
import re

from lib import cell as cell_mod, spans as sp, trace as tr

IMPORT, INIT = "hvd.import", "hvd.init"
TRACE, LOWER, COMPILE = "xla.trace", "xla.lower", "xla.compile"
NAMED = (IMPORT, INIT, TRACE, LOWER, COMPILE)
GC = "host.gc"
_WRAPPED = re.compile(r"^\w+\((.*)\)$")
_WORST = {"miss": 3, "off": 2, "hit": 1, None: 0}
TOLERANCE_S = 1e-3


def say(**fields):
    print(json.dumps(fields), flush=True)


def innermost_ns(intervals):
    """Per interval ``(start, end)`` of one thread: the time in which it was
    the innermost one open: of those open at an instant the SHORTEST (of
    two as long, the later in the list). Among nested spans that is the
    inner one, also where a report's microsecond of lateness puts an edge
    of the inner one just outside the outer."""
    events = sorted((t, opening, i) for i, (s, e) in enumerate(intervals)
                    if e > s for t, opening in ((s, 1), (e, 0)))
    length = [e - s for s, e in intervals]
    own, open_now, last = [0] * len(intervals), set(), 0
    for t, opening, i in events:
        if open_now and t > last:
            own[max(open_now, key=lambda j: (-length[j], j))] += t - last
        last = t
        if opening:
            open_now.add(i)
        else:
            open_now.discard(i)
    return own


def phases(spans, stop_ns):
    """``(metrics, line)`` from the recorder's spans (wall clock) and the
    session's start, or None where there is no ``hvd.import``."""
    first = min((s for s in spans if s.name == IMPORT),
                key=lambda s: s.start_ns, default=None)
    if first is None or first.start_ns >= stop_ns:
        return None
    mine = [s for s in spans if s.name in NAMED and s.thread == first.thread
            and s.start_ns >= first.start_ns and s.end_ns <= stop_ns]
    # The interval itself is the outermost: its own time has no name.
    own = innermost_ns([(first.start_ns, stop_ns)]
                       + [(s.start_ns, s.end_ns) for s in mine])
    seconds = dict.fromkeys(
        ("unnamed", IMPORT, INIT, TRACE, LOWER, "hit", "miss"), 0.0)
    seconds["unnamed"] = own[0] / 1e9
    misses, by_fun = 0, {}
    for s, ns in zip(mine, own[1:]):
        key = s.name
        if s.name == COMPILE:
            cache = s.ids.get("cache", "off")
            key = "hit" if cache == "hit" else "miss"
            misses += key == "miss"
        seconds[key] += ns / 1e9
        if s.name in (TRACE, LOWER, COMPILE):
            fun = str(s.ids.get("fun", ""))
            wrapped = _WRAPPED.match(fun)
            row = by_fun.setdefault(wrapped.group(1) if wrapped else fun,
                                    [0.0, 0.0, 0.0, None])
            row[(TRACE, LOWER, COMPILE).index(s.name)] += ns / 1e9
            if s.name == COMPILE and _WORST[cache] > _WORST[row[3]]:
                row[3] = cache
    interval_s = (stop_ns - first.start_ns) / 1e9
    assert abs(sum(seconds.values()) - interval_s) < TOLERANCE_S, (
        seconds, interval_s)
    rows = sorted(([fun] + row for fun, row in by_fun.items()),
                  key=lambda r: -(r[1] + r[2] + r[3]))
    # Where the time without a name lies: the longest stretches under no
    # span, each with the span that ended last before it.
    gaps = []
    for lo, hi in sorted(sp.uncovered(first.start_ns, stop_ns,
                                      [(s.start_ns, s.end_ns) for s in mine]),
                         key=lambda g: g[0] - g[1])[:6]:
        before = max((s for s in mine if s.end_ns <= lo),
                     key=lambda s: s.end_ns, default=None)
        gaps.append([(lo - first.start_ns) / 1e9, (hi - lo) / 1e9,
                     "" if before is None else
                     f"{before.name} {before.ids.get('fun', '')}".strip()])
    metrics = {"setup.import_s": seconds[IMPORT],
               "setup.trace_s": seconds[TRACE],
               "setup.lower_s": seconds[LOWER],
               "setup.cache_load_s": seconds["hit"],
               "setup.backend_compile_s": seconds["miss"],
               "setup.cache_misses": misses,
               "setup.unnamed_s": seconds["unnamed"]}
    line = {"interval_s": interval_s, "spans": len(mine),
            "hvd.init_s": seconds[INIT], "functions": len(by_fun),
            "by_fun": rows[:10],
            "unnamed_gaps": gaps,
            "columns": {"by_fun": ["fun", "trace_s", "lower_s", "compile_s",
                                   "cache"],
                        "unnamed_gaps": ["at_s", "s", "after"]}}
    return metrics, line


def collector_pauses(spans, start_ns, stop_ns, steps):
    """``(metrics, line)`` from the ``host.gc`` spans and the session."""
    pauses = [s for s in spans if s.name == GC]
    if not pauses:
        return {}, {"found": 0, "note": "the program recorded no host.gc "
                                        "span; nothing is read"}
    inside = sp.on_trace_clock(pauses, start_ns, stop_ns)
    line = {"found": len(pauses), "in_session": len(inside),
            "pauses": [[s.start / 1e9, (s.end - s.start) / 1e6,
                        s.ids.get("generation"), s.ids.get("collected")]
                       for s in inside][:50],
            "columns": ["at_s", "ms", "generation", "collected"]}
    return {"host.gc_ms_per_step":
            sum(s.end - s.start for s in inside) / 1e6 / steps}, line


def read(trace, run, cell):
    raw = sp.program_spans()
    if not raw:
        say(event="setup_phases", found=0,
            note="the program has no span recorder; nothing is read")
        return {}
    xplane = tr.find_xplane(os.path.join(cell_mod.TRACE_DIR, cell["name"]))
    found = sp.anchor(xplane)
    if found is None:
        say(event="setup_phases", found=len(raw),
            note="the trace has no Task Environment anchor: set-up has no "
                 "end and nothing is laid on the trace's clock; nothing is "
                 "read")
        return {}
    metrics, line = collector_pauses(raw, *found, run["steps"])
    say(event="host_gc", **line)
    got = phases(raw, found[0])
    if got is None:
        say(event="setup_phases", found=len(raw),
            note="the program records no hvd.import span (a tree from "
                 "before it): set-up has no start; nothing is read")
        return metrics
    say(event="setup_phases", **got[1])
    return dict(metrics, **got[0])
