#!/usr/bin/env python
"""Per-op device-time profile of the benchmark training step.

The measurement tool behind the ResNet-50 roofline analysis in
``docs/benchmarks.md``: runs the same compiled train step as ``bench.py``,
captures one multi-step dispatch under ``jax.profiler.trace``, and
aggregates the per-HLO device events (``hlo_category``,
``device_duration_ps``, ``model_flops``, ``raw_bytes_accessed``) into a
per-step table — device-busy breakdown by category, then the top ops.

    python bin/profile_step.py --model resnet50
    python bin/profile_step.py --model resnet50 --conv-backend fused
    python bin/profile_step.py --model transformer_lm

Real-TPU only (the per-op device track needs the TPU profiler plugin).
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import re
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from horovod_tpu.utils.chips import enable_compile_cache  # noqa: E402

enable_compile_cache()

import jax  # noqa: E402


def _capture(run_dispatch):
    """Run ``run_dispatch`` once under the profiler. The callable is a full
    bench ``measure`` (compile + warmup + timed dispatches); compilation is
    host-side and invisible to the device track, so the report divides by
    the TOTAL device steps executed (warmup + iters) x steps_per_call."""
    d = tempfile.mkdtemp(prefix="hvdprof")
    with jax.profiler.trace(d):
        run_dispatch()
    files = sorted(glob.glob(d + "/**/*.trace.json.gz", recursive=True))
    if not files:
        raise SystemExit("no trace produced (TPU profiler plugin missing?)")
    with gzip.open(files[-1]) as fh:
        tr = json.load(fh)
    return tr["traceEvents"]


def _track(events, track_name):
    tids = set()
    for e in events:
        if (e.get("ph") == "M" and e.get("name") == "thread_name"
                and e["args"].get("name") == track_name):
            tids.add((e["pid"], e["tid"]))
    return [e for e in events
            if e.get("ph") == "X" and (e.get("pid"), e.get("tid")) in tids]


def _xla_op_events(events):
    """Events on the device 'XLA Ops' thread."""
    return _track(events, "XLA Ops")


def _dispatch_count(events):
    """How many launches of the dominant executable the trace captured
    ('XLA Modules' track) — the robust step divisor: traces can start
    mid-run and buffer limits can drop early dispatches, so trusting the
    requested warmup+iters count mis-scales every per-step number."""
    mods = collections.defaultdict(lambda: [0, 0.0])
    for e in _track(events, "XLA Modules"):
        m = mods[e["name"]]
        m[0] += 1
        m[1] += float(e.get("dur", 0.0))
    if not mods:
        return None
    return max(mods.values(), key=lambda m: m[1])[0]


# Control-flow parents whose device time ENCLOSES their body ops — the
# body is attributed separately on the same track, so counting the parent
# double-books every nested op (a lax.scan-driven step would double).
_PARENT_OPS = {"while", "conditional", "call"}


def report(events, steps_per_call, requested_dispatches):
    n_disp = _dispatch_count(events) or requested_dispatches
    k = steps_per_call * n_disp
    print(f"(trace captured {n_disp} dispatches x {steps_per_call} steps)")
    cats = collections.defaultdict(lambda: [0.0, 0, 0])  # ps, flops, bytes
    ops = collections.defaultdict(lambda: [0.0, 0, 0, "", 0])
    t_min, t_max = float("inf"), 0.0
    busy = 0.0
    for e in _xla_op_events(events):
        if re.sub(r"\.\d+$", "", e["name"]) in _PARENT_OPS:
            continue
        a = e["args"]
        dur = int(a.get("device_duration_ps", 0))
        off = int(a.get("device_offset_ps", 0))
        t_min = min(t_min, off)
        t_max = max(t_max, off + dur)
        busy += dur
        fl = int(a.get("model_flops", 0) or 0)
        by = int(a.get("raw_bytes_accessed", 0) or 0)
        cat = a.get("hlo_category", e["name"])
        cats[cat][0] += dur
        cats[cat][1] += fl
        cats[cat][2] += by
        name = a.get("long_name", e["name"]).split(" = ")[0]
        # Collapse instances: %fusion.123 -> fusion, keep pallas kernel ids
        key = re.sub(r"\.\d+$", "", name.lstrip("%"))
        o = ops[key]
        o[0] += dur
        o[1] += fl
        o[2] += by
        o[3] = cat
        o[4] += 1

    # A trace with no per-op device track (CPU backend, or a TPU plugin
    # that dropped the 'XLA Ops' thread) yields busy == 0; a trace that
    # missed every module dispatch yields k == 0. Either way every
    # per-step figure below would divide by zero — fail with the remedy
    # instead of a bare ZeroDivisionError.
    if k == 0:
        raise SystemExit(
            "profile_step: trace captured 0 dispatches of the step on the "
            "'XLA Modules' track — the profiler likely started after the "
            "run or the buffer dropped them; re-run with more --steps or "
            "on a quieter host")
    if busy == 0:
        raise SystemExit(
            "profile_step: no per-op device time on the 'XLA Ops' track — "
            "this tool needs the TPU profiler plugin's device events "
            "(JAX_PLATFORMS=cpu traces carry none); run on a real TPU, or "
            "use bench.py for host-side wall-clock numbers")
    env = (t_max - t_min) / 1e12
    print(f"device busy: {busy/1e12/k*1e3:.2f} ms/step "
          f"(envelope {env/k*1e3:.2f}); idle = {(env - busy/1e12)/k*1e3:.2f} ms")
    print(f"{'category':<28}{'ms/step':>9}{'%busy':>7}{'TFLOP/s':>9}"
          f"{'GB/s':>8}")
    for cat, (ps, fl, by) in sorted(cats.items(), key=lambda kv: -kv[1][0]):
        s = ps / 1e12
        print(f"{cat:<28}{s/k*1e3:>9.2f}{ps/busy*100:>7.1f}"
              f"{fl/s/1e12 if s else 0:>9.1f}{by/s/1e9 if s else 0:>8.0f}")
    print()
    print(f"top ops (per step): {'ms':>8} {'TF/s':>7} {'GB/s':>6}  n  "
          f"category / name")
    for name, (ps, fl, by, cat, n) in sorted(
            ops.items(), key=lambda kv: -kv[1][0])[:24]:
        s = ps / 1e12
        print(f"{'':>8}{s/k*1e3:>10.3f} {fl/s/1e12 if s else 0:>7.1f} "
              f"{by/s/1e9 if s else 0:>6.0f} {n//k if k else n:>3}  "
              f"{cat} / {name[:70]}")


def timeline_host_report(path):
    """Host-plane attribution from a ``HOROVOD_TIMELINE`` Chrome trace.

    The device-side tables above say where MXU time goes; this says what
    the HOST was doing meanwhile: ``H2D`` rows come from the prefetch
    thread (input staging), ``CKPT_SNAPSHOT``/``CKPT_WRITE`` from the
    checkpoint path. A run whose summed H2D time approaches its wall clock
    is input-bound — grow the prefetch depth or the input workers before
    touching the model; large CKPT_WRITE with small CKPT_SNAPSHOT means
    async checkpointing is doing its job (the write overlaps training).
    """
    with open(path) as fh:
        text = fh.read()
    try:
        events = json.loads(text)
    except json.JSONDecodeError:
        # The trace is a terminated JSON array only after Timeline.close();
        # a still-running or killed run leaves "[{...},\n{...},\n" — apply
        # the trailing-comma-tolerant completion Chrome's viewer uses.
        events = json.loads(text.rstrip().rstrip(",") + "]")
    open_ev = {}
    totals = collections.defaultdict(lambda: [0.0, 0])  # name -> [us, n]
    t_min, t_max = float("inf"), 0.0
    for e in events:
        if not isinstance(e, dict) or "ph" not in e:
            continue
        ts = e.get("ts")
        if ts is not None:
            t_min, t_max = min(t_min, ts), max(t_max, ts)
        if e["ph"] == "B":
            open_ev.setdefault(e["pid"], []).append((e["name"], ts))
        elif e["ph"] == "E":
            stack = open_ev.get(e["pid"])
            if stack:
                name, ts0 = stack.pop()
                totals[name][0] += ts - ts0
                totals[name][1] += 1
    host = {k: v for k, v in totals.items()
            if k in ("H2D", "CKPT_SNAPSHOT", "CKPT_WRITE")}
    if not host:
        raise SystemExit(
            f"profile_step: no host-plane phases (H2D/CKPT_*) in {path} — "
            "run training with HOROVOD_TIMELINE set, prefetch enabled "
            "(Trainer(prefetch>=1) passes the world sharding through) "
            "and/or an AsyncCheckpointer attached")
    span_ms = (t_max - t_min) / 1e3
    print(f"host-plane phases ({path}; trace span {span_ms:.1f} ms):")
    print(f"{'phase':<16}{'total ms':>10}{'n':>6}{'mean ms':>10}"
          f"{'% span':>8}")
    for name, (us, n) in sorted(host.items(), key=lambda kv: -kv[1][0]):
        ms = us / 1e3
        print(f"{name:<16}{ms:>10.2f}{n:>6}{ms / n:>10.2f}"
              f"{100 * ms / span_ms if span_ms else 0:>8.1f}")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50")
    p.add_argument("--conv-backend", default="xla",
                   choices=["xla", "fused"])
    p.add_argument("--steps", type=int, default=None,
                   help="steps per dispatch (default: the bench config)")
    p.add_argument("--timeline", default=None, metavar="FILE",
                   help="summarize host-plane phases (H2D, CKPT_*) from a "
                        "HOROVOD_TIMELINE trace instead of profiling — "
                        "works on any host, no TPU needed")
    args = p.parse_args()

    if args.timeline:
        timeline_host_report(args.timeline)
        return

    import bench

    if args.model == "transformer_lm":
        cfg = bench._lm_config()
        if args.steps:
            cfg["steps_per_call"] = args.steps
        cfg["warmup"], cfg["iters"], cfg["rounds"] = 2, 1, 1
        events = _capture(lambda: bench.measure_lm(cfg))
        report(events, cfg["steps_per_call"],
               cfg["warmup"] + cfg["iters"])
        return

    cfg = bench._bench_config(args.model)
    if args.conv_backend != "xla":
        # Same mislabel guard as bench.py: a run that silently profiles
        # stock convs must not be recorded as a fused measurement.
        if args.model not in ("resnet50", "resnet101") \
                or cfg["model"] not in ("resnet50", "resnet101"):
            raise SystemExit(
                "--conv-backend fused applies to resnet50/resnet101 on "
                "real TPU only")
    cfg["conv_backend"] = args.conv_backend
    if args.steps:
        cfg["steps_per_call"] = args.steps
    cfg["warmup"], cfg["iters"], cfg["rounds"] = 2, 1, 1
    events = _capture(lambda: bench.measure(cfg=cfg))
    report(events, cfg["steps_per_call"],
           cfg["warmup"] + cfg["iters"])


if __name__ == "__main__":
    main()
