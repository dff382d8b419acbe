"""What the program's own spans and counters say about the traced window
(``horovod_tpu.utils.timeline``; PERF.md section 3 names each span):

* host loop: ``fit.next_batch_ms``, ``fit.train_step_ms`` (medians of the
  spans of those names) and ``fit.self_ms`` (median of ``fit.step`` less its
  children: the loop's own bookkeeping);
* input thread: ``input.source_ms``, ``input.h2d_ms`` (medians of
  ``input.source`` and ``H2D``) and ``input.queue_depth`` (mean of the
  batches ready at each dequeue, the ``queue_depth`` id on
  ``fit.next_batch``);
* step builders: ``step.dispatch_ms`` (median ``step.dispatch``) and
  ``step.compiles_in_window`` (``xla.compile`` spans ending in the session);
* device: ``device.idle_input_pct`` and ``device.idle_host_pct``, the share
  of the window in which device 0 is idle AND the loop thread is inside
  ``fit.next_batch``, or inside any other span of its own.

The spans are taken from the recorder's snapshot in this process, kept if
they lie inside the profiler session and shifted onto its clock
(``lib/spans.py``). Where the program has no recorder, or the trace no
anchor, this returns nothing and says so on a line of its own.

The device's planes run behind the host's clock by 0.1 to 1.8 ms, a
different amount in every session (``clock_check.py``). The idle metrics
therefore shift the device's ops by the most that causality allows: in the
``fit_host`` driver the end-of-batch callbacks of turn k come back from
blocking on step k - LAG, so no step module can end, on the host's clock,
after that span does (``lib/spans.device_clock_lag``). What is left unknown
is the host's quickest wake-up, tens of microseconds: gaps shorter than
that cannot be attributed, longer ones can. The idle time by innermost
program span goes on an earlier line, ``{"event":
"idle_gaps_by_program_span", ...}``, with the lag applied:
``run.py``'s ``breakdown.idle_gaps`` reads the profiler's host planes only."""

import json
import os
import statistics

from lib import cell as cell_mod, spans as sp, trace as tr

LOOP = "fit.step"
WAIT = "fit.next_batch"
HOOKS = "fit.callbacks"
FIT_HOST_LAG = 2          # drivers/fit_host.py LAG


def say(**fields):
    print(json.dumps(fields), flush=True)


def self_intervals(spans, thread):
    """Per span name of one thread: the intervals in which a span of that
    name was the innermost one open."""
    mine = [s for s in spans if s.thread == thread]
    kids = {}
    for s in mine:
        kids.setdefault(s.parent, []).append((s.start, s.end))
    out = {}
    for s in mine:
        out.setdefault(s.name, []).extend(
            sp.uncovered(s.start, s.end, kids.get(s.id, [])))
    return out


def step_modules(plane):
    """The programs of the module line that took most of its time, in
    order: the train step's executions."""
    total = {}
    for name, start, end in plane.modules:
        total[name] = total.get(name, 0.0) + (end - start)
    if not total:
        return []
    step = max(total, key=total.get)
    return sorted((s, e) for n, s, e in plane.modules if n == step)


def fit_host_lag(spans, plane):
    """The device clock's lag (ns) from the ``fit_host`` driver's
    causality, or None: the k-th end-of-batch ``fit.callbacks`` returns
    after the device finished step k - LAG."""
    turns = sorted((s for s in spans if s.name == LOOP),
                   key=lambda s: s.start)
    last_hooks = {}                    # per turn, the later of its two
    for s in spans:
        if s.name == HOOKS:
            last_hooks[s.parent] = max(s.end, last_hooks.get(s.parent, 0))
    modules = step_modules(plane)
    observed = [last_hooks[t.id] for t in turns[FIT_HOST_LAG:]
                if t.id in last_hooks]
    if len(observed) != len(turns) - FIT_HOST_LAG or not modules:
        return None
    return sp.device_clock_lag([end for _, end in modules], observed)


def idle_by_span(spans, ops, window_s, lag_ns=0.0):
    """``(idle_input_pct, idle_host_pct, rows)`` for the loop thread, the
    device's ops shifted ``lag_ns`` later; None without a loop."""
    turns = [s for s in spans if s.name == LOOP]
    if not turns or not ops:
        return None
    thread = turns[0].thread
    lo, hi = min(s.start for s in turns), max(s.end for s in turns)
    idle = sp.uncovered(lo, hi, [(s + lag_ns, e + lag_ns)
                                 for _, s, e in ops])
    table = {name: tr.length(tr.intersect(idle, ivs))
             for name, ivs in self_intervals(spans, thread).items()}
    named = sum(table.values())
    table["outside_any_span"] = max(0.0, tr.length(idle) - named)
    waiting = table.get(WAIT, 0.0)
    rows = sorted(((n, ns / 1e9) for n, ns in table.items() if ns > 0),
                  key=lambda kv: -kv[1])
    return (100.0 * waiting / 1e9 / window_s,
            100.0 * (named - waiting) / 1e9 / window_s, rows)


def read(trace, run, cell):
    raw = sp.program_spans()
    if not raw:
        say(event="program_spans", found=0,
            note="the program has no span recorder; nothing is read")
        return {}
    xplane = tr.find_xplane(os.path.join(cell_mod.TRACE_DIR, cell["name"]))
    found = sp.anchor(xplane)
    if found is None:
        say(event="program_spans", found=len(raw),
            note="the trace has no Task Environment anchor; nothing is "
                 "laid on its clock")
        return {}
    start_ns, stop_ns = found
    spans = sp.on_trace_clock(raw, start_ns, stop_ns)
    out = {"step.compiles_in_window": sum(
        1 for s in raw if s.name == "xla.compile"
        and start_ns <= s.end_ns <= stop_ns)}
    for metric, name in (("fit.next_batch_ms", WAIT),
                         ("fit.train_step_ms", "fit.train_step"),
                         ("input.source_ms", "input.source"),
                         ("input.h2d_ms", "H2D"),
                         ("step.dispatch_ms", "step.dispatch")):
        value = sp.median_ms(spans, name)
        if value is not None:
            out[metric] = value
    own = sp.self_ms(spans, LOOP)
    if own:
        out["fit.self_ms"] = statistics.median(own)
    depth = [s.ids["queue_depth"] for s in spans
             if s.name == WAIT and "queue_depth" in s.ids]
    if depth:
        out["input.queue_depth"] = statistics.fmean(depth)
    plane = trace.devices[0]
    lag = fit_host_lag(spans, plane) \
        if cell["traffic"].get("driver") == "fit_host" else None
    idle = idle_by_span(spans, plane.ops, run["window_s"], lag or 0.0)
    if idle:
        out["device.idle_input_pct"], out["device.idle_host_pct"], rows = idle
        say(event="idle_gaps_by_program_span", seconds_by_span=rows,
            device_clock_lag_us=None if lag is None else lag / 1e3,
            note="device ops shifted later by the lag; without one the "
                 "device's clock is taken as the host's")
    say(event="program_spans", found=len(raw), in_session=len(spans),
        names=sorted({s.name for s in spans}))
    return out
