#!/usr/bin/env python3
"""Do the program's spans and the profiler's trace share a clock?

    python benchmarks/clock_check.py [--probes 200] [--describe 40]

No cell and no result line: a check that the per-layer readers which lay
program spans on the device trace (``layer_metrics/program_spans.py``) rest
on. A small jitted train step (a flash kernel under ``forward`` /
``optimizer`` scopes, so the trace also shows how scopes and kernel names
arrive) runs on the chip under a profiler session of its own WITH the host
tracer on. Each dispatch-and-block sits inside
``timeline.span("clock.probe")``, the dispatch alone inside
``clock.dispatch``, so every probe is in the recorder's ring and, as the
span's ``TraceAnnotation``, in the trace. Printed, as JSON lines:

* ``skew_us``: median and largest |ring start - trace start| of the probes
  after the shift by the trace's ``profile_start_time``: the HOST's two
  records of one span;
* ``device_clock_lag_us``: how far the DEVICE plane's clock runs behind the
  host's. Probe i's module cannot begin before its dispatch began
  (``at_least``; ``at_least_by_launch`` from the runtime's own
  ``tpu::System::Execute`` host event, which is later and so tighter) nor
  end after the probe's block returned (``at_most``).
  ``raw_causality_held`` counts the probes whose module lies inside the
  probe on the clocks as recorded: all of them if the planes shared a
  base;
* ``empty_span_us``: what one empty ``timeline.span`` costs on this host.

Exit 0 if the host's records agree to 50 us and the device's lag is
bounded consistently (``at_least <= at_most``). ``--describe N`` writes the
first N events of every line, with their stats, to
``chiprun_out/clock_check/describe.txt`` (to look at by hand).
``--rehearse`` runs on whatever backend is there (the CPU's executor
threads stand in for the device), says so, and exits 3: never a measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from lib import cell as cell_mod, spans as sp, trace as tr  # noqa: E402


def say(**fields):
    print(json.dumps(fields), flush=True)


def build_step(interpret: bool):
    import jax
    import jax.numpy as jnp
    from horovod_tpu.ops.pallas_attention import flash_attention_qkv
    heads, d_head, t, width = 4, 128, 512, 512

    def loss(w, x):
        with jax.named_scope("forward"):
            qkv = (x @ w).astype(jnp.bfloat16)        # [B, T, 3*H*D]
            o = flash_attention_qkv(qkv, heads, causal=True,
                                    interpret=interpret)
            return jnp.mean(jnp.square(o.astype(jnp.float32)))

    def step(w, x):
        value, grad = jax.value_and_grad(loss)(w, x)
        with jax.named_scope("optimizer"):
            w = w - 0.1 * grad
        return w, value

    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (width, 3 * heads * d_head), jnp.float32) * .02
    x = jax.random.normal(key, (2, t, width), jnp.float32)
    return jax.jit(step), w, x


def empty_span_us(n: int = 200000) -> float:
    from horovod_tpu.utils import timeline
    t0 = time.perf_counter()
    for i in range(n):
        with timeline.span("clock.empty", step=i):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--probes", type=int, default=200)
    ap.add_argument("--describe", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from horovod_tpu.utils.chips import enable_compile_cache
    enable_compile_cache()
    import jax
    from horovod_tpu.utils import timeline
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        sys.stderr.write(f"clock_check.py needs a TPU; jax found "
                         f"{dev.platform!r}. Nothing was run.\n")
        return 2
    note = {"rehearsal": True} if args.rehearse else {}
    say(event="start", device={"platform": dev.platform,
                               "kind": dev.device_kind}, **note)

    step, w, x = build_step(interpret=dev.platform != "tpu")
    for _ in range(3):
        w, value = step(w, x)
    jax.block_until_ready(value)

    trace_dir = os.path.join(cell_mod.TRACE_DIR, "clock_check")
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for i in range(args.probes):
        with timeline.span("clock.probe", probe=i):
            with timeline.span("clock.dispatch"):
                w, value = step(w, x)
            jax.block_until_ready(value)
        time.sleep(0.002)     # a gap an offset would have to jump
    jax.profiler.stop_trace()

    xplane = tr.find_xplane(trace_dir)
    if args.describe:
        out = os.path.join(ROOT, "chiprun_out", "clock_check")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, "describe.txt"), "w") as fh:
            fh.write(tr.describe(xplane, limit=args.describe))
    found = sp.anchor(xplane)
    if found is None:
        say(event="no_anchor", note="the trace has no Task Environment "
            "plane with profile_start_time", **note)
        return 1
    start_ns, stop_ns = found
    on_clock = sp.on_trace_clock(timeline.spans(), start_ns, stop_ns)
    ring = [s for s in on_clock if s.name == "clock.probe"]
    dispatch = [s for s in on_clock if s.name == "clock.dispatch"]
    trace = tr.from_xplane(xplane, span_prefix="clock.",
                           cpu_as_device=args.rehearse)
    in_trace = sorted(trace.host.get("clock.probe", []))
    say(event="probes", in_ring=len(ring), in_trace=len(in_trace), **note)
    if len(ring) != len(in_trace) or not ring:
        return 1
    skew = [abs(s.start - lo) / 1e3 for s, (lo, _) in zip(ring, in_trace)]
    say(event="skew_us", median=statistics.median(skew), largest=max(skew),
        **note)

    # One module per probe; a rehearsal's stand-in device has no module
    # line, so its ops are grouped by the probe they fall in.
    modules = sorted((s, e) for _, s, e in trace.devices[0].modules)
    if args.rehearse:
        ops = sorted((s, e) for _, s, e in tr.leaf_ops(trace.devices[0]))
        starts = [p.start for p in ring] + [float("inf")]
        groups = [[(s, e) for s, e in ops if lo <= s < hi]
                  for lo, hi in zip(starts, starts[1:])]
        modules = [(g[0][0], max(e for _, e in g)) for g in groups if g]
    if len(modules) != len(ring):
        say(event="modules", found=len(modules), probes=len(ring), **note)
        return 1
    launches = sorted(tr.from_xplane(
        xplane, span_prefix="tpu::System::Execute").host.get(
        "tpu::System::Execute", []))
    at_least = max(d.start - m[0] for d, m in zip(dispatch, modules))
    at_most = sp.device_clock_lag([m[1] for m in modules],
                                  [p.end for p in ring])
    say(event="device_clock_lag_us", at_least=at_least / 1e3,
        at_least_by_launch=max(
            lo - m[0] for (lo, _), m in zip(launches, modules)) / 1e3
        if len(launches) == len(modules) else None,
        at_most=at_most / 1e3,
        raw_causality_held=sum(p.start <= m[0] and m[1] <= p.end
                               for p, m in zip(ring, modules)),
        probes=len(ring), **note)
    say(event="empty_span_us", value=empty_span_us(), **note)
    ok = max(skew) < 50 and at_least <= at_most
    say(event="done", host_clock_shared=max(skew) < 50,
        device_lag_bounded=at_least <= at_most, **note)
    if args.rehearse:
        return 3
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
