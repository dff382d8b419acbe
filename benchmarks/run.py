#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip(s) itself and starts no child. Everything
that belongs to one cell is found BY NAME from ``BENCHMARK.json``:

    workloads[<cell>]            -> config, traffic, chips
    configs[<config>].file       -> the sizes as run, and its "family"
    benchmarks/traffic/<traffic>.json   -> the traffic's parameters, "driver"
    benchmarks/families/<family>.py     -> builds state + step for a config
    benchmarks/drivers/<driver>.py      -> drives the step for the window
    benchmarks/layer_metrics/*.py       -> one reader per per-layer quantity

so a later PR adds a cell, a configuration, a traffic mix or a per-layer
metric by adding files and ``BENCHMARK.json`` entries, and edits nothing.

The last line of stdout is one JSON object with the keys ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``). With ``--trace 0`` the metrics are the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, from a short traced
window. Per-step times and losses go on earlier lines. A run that finds no
TPU, fewer chips than the cell asks for, or a ``device_kind`` missing from
``lib/peaks.json`` exits non-zero and prints no result. ``--rehearse``
(never a default) runs on whatever backend is there, says so on every
line, and exits 3: it finds wrong paths before chip time is spent and is
never a measurement.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()     # set-up is counted from here

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# The checkout's own program first; a copy of the benchmark made elsewhere
# (the tests' "cell added as data") finds the program through PYTHONPATH.
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from lib import stats  # noqa: E402
from lib.cell import Context, Window  # noqa: E402

REHEARSAL_EXIT = 3


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"run.py: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str):
    with open(path) as fh:
        return json.load(fh)


def named(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"run.py: BENCHMARK.json has no {what} {name!r}; it has "
                     f"{[e['name'] for e in entries]}")


def end_to_end(window: Window, chips: int) -> dict:
    """The end-to-end metrics, over ALL the work and ALL the time of the
    window: it starts when the first step may be dispatched and ends when
    the device completes the last one."""
    steps = len(window.done_t)
    span = window.done_t[-1] - window.t_start
    step_s = [b - a for a, b in zip(window.done_t, window.done_t[1:])]
    return {
        window.rate_metric: steps * window.units_per_step / span / chips,
        "step_ms_p90": stats.percentile(step_s, 90) * 1e3,
        "setup_s": window.t_start - T_PROCESS_START,
    }


def falling(losses) -> bool:
    """Every loss finite, and the mean of the last tenth below the mean of
    the first tenth."""
    if not losses or not all(math.isfinite(x) for x in losses):
        return False
    k = max(1, len(losses) // 10)
    return sum(losses[-k:]) / k < sum(losses[:k]) / k


def per_layer(ctx: Context, window: Window, peaks: dict):
    """Run every reader under ``layer_metrics/`` on the traced window.
    Returns (metrics, busy_s, breakdown)."""
    from lib import trace as tr
    trace = tr.from_xplane(tr.find_xplane(window.trace_dir),
                           cpu_as_device=ctx.rehearse)
    if not trace.devices or not any(p.ops for p in trace.devices):
        raise SystemExit("run.py: the trace holds no device operation")
    run = dict(window.extra, steps=len(window.done_t),
               window_s=window.done_t[-1] - window.t_start,
               units_per_step=window.units_per_step,
               compile_s=ctx.compile_s)
    cell = dict(ctx.cell, config=ctx.config, traffic=ctx.traffic,
                peaks=peaks)
    found = {}
    for path in sorted(glob.glob(os.path.join(HERE, "layer_metrics",
                                              "*.py"))):
        name = os.path.basename(path)[:-3]
        found.update(load_module("layer_metrics", name).read(trace, run,
                                                             cell) or {})
    first = trace.devices[0]
    window_span = (trace.host.get("bench.window") or [None])[0]
    breakdown = {"device_ops": tr.top_ops(first),
                 "idle_gaps": tr.idle_gaps(first, trace.host, window_span)}
    return found, tr.mean_busy_s(trace), breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: "
                         "BENCHMARK.json's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on whatever backend is there; never a "
                         "measurement: says so on every line, exits 3")
    args = ap.parse_args(argv)

    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = named(bench["workloads"], args.workload, "workload")
    config_entry = named(bench["configs"], cell["config"], "config")
    config = read_json(os.path.join(ROOT, config_entry["file"]))
    traffic = read_json(os.path.join(HERE, "traffic",
                                      cell["traffic"] + ".json"))
    peaks_table = read_json(os.path.join(HERE, "lib", "peaks.json"))
    seconds = bench["run_seconds"] if args.seconds is None else args.seconds

    # The program's helper: JAX_COMPILATION_CACHE_DIR where it is set, the
    # fixed in-checkout .jax_compile_cache/ where it is not. Before jax
    # reads its configuration.
    from horovod_tpu.utils.chips import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    # Every program of a run goes to the cache, also the small ones that
    # compile in under jax's default threshold of a second: set-up is then
    # the same work in every run after the first.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if not args.rehearse:
        if platform != "tpu":
            sys.stderr.write(
                f"run.py: cell {cell['name']!r} needs a TPU and jax found "
                f"platform {platform!r} ({kind}, {len(devices)} device(s)); "
                f"nothing was run.\n")
            return 2
        if kind not in peaks_table:
            sys.stderr.write(
                f"run.py: device_kind {kind!r} is not in lib/peaks.json; "
                f"add it with its source, never a default.\n")
            return 2
    if len(devices) < cell["chips"]:
        sys.stderr.write(
            f"run.py: cell {cell['name']!r} needs {cell['chips']} chip(s) "
            f"and jax reports {len(devices)}; nothing was run.\n")
        return 2
    devices = devices[:cell["chips"]]
    # A rehearsal has no peak of its own; it borrows the first so that the
    # readers run, and its numbers mean nothing.
    peaks = peaks_table.get(kind) or next(iter(peaks_table.values()))

    ctx = Context(cell=cell, config=config, traffic=traffic,
                  seed=args.seed, seconds=seconds, trace=bool(args.trace),
                  rehearse=args.rehearse, devices=devices)
    ctx.log(event="start", workload=cell["name"], seed=args.seed,
            seconds=seconds, trace=args.trace, compile_cache_dir=cache_dir,
            device={"platform": platform, "kind": kind,
                    "count": len(devices)})

    family = load_module("families", config["family"])
    driver = load_module("drivers", traffic["driver"])
    window: Window = driver.run(ctx, family)

    checks = dict(window.checks, loss_finite_and_falling=falling(
        window.losses))
    step_ms = [(b - a) * 1e3 for a, b in zip(window.done_t,
                                             window.done_t[1:])]
    ctx.log(event="window", steps=len(window.done_t), checks=checks,
            losses=window.losses, step_ms=step_ms,
            compile_s=ctx.compile_s,
            memory_stats=devices[0].memory_stats())

    # On this runtime `peak_bytes_in_use` leaves out the compiled programs'
    # temporaries, which the allocator holds as `peak_bytes_reserved`
    # (PERF.md section 6): the chip's peak is both, as the step runs with
    # the state live.
    def peak_bytes(d):
        stats = d.memory_stats() or {}
        return stats.get("peak_bytes_in_use", 0) \
            + stats.get("peak_bytes_reserved", 0)
    peak = max(peak_bytes(d) for d in devices)
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak}
    if args.rehearse:
        device["rehearsal"] = True
    result = {"correct": all(checks.values()),
              "attempted": len(window.done_t) + window.failed,
              "failed": window.failed}

    wanted = bench["per_layer"] if args.trace else bench["end_to_end"]
    wanted = [m for m in wanted
              if "workloads" not in m or cell["name"] in m["workloads"]]
    if args.trace:
        values, busy_s, breakdown = per_layer(ctx, window, peaks)
        device["busy_s"] = busy_s
        device["window_s"] = window.done_t[-1] - window.t_start
        result["breakdown"] = breakdown
    else:
        values = end_to_end(window, cell["chips"])
    result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                     "unit": m["unit"]}
                         for m in wanted if m["name"] in values}
    result["device"] = device
    print(json.dumps(result), flush=True)
    return REHEARSAL_EXIT if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
