#!/usr/bin/env python3
"""All the runs of one cell in ONE chip call.

    python benchmarks/prove.py --workload lm_step_1chip --sets 2 --runs 6 --trace 1

From a parent that never imports jax (so it never holds the chip): fresh
processes of ``run.py``, one after another — first a run marked ``cold`` (it
compiles; its set-up is recorded apart), then ``--sets`` sets of ``--runs``
runs with the same seeds in every set, then an optional traced run. Every
result line, each run's whole output, and per metric the medians and
spreads (first-to-third quartile over median, ``statistics.quantiles``) go
into ``<out>/<workload>/``; the chip tool copies ``chiprun_out/`` back. The
bounds in ``BENCHMARK.json`` are set from ``summary.json``: about five times
the wider of the two sets' spreads.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from lib import stats  # noqa: E402


def one_run(workload, seed, seconds, trace, out_dir, tag, timeout):
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
           workload, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                           timeout=timeout)
        rc, out, err = p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        rc, out, err = 124, e.stdout or "", e.stderr or ""
        out = out.decode() if isinstance(out, bytes) else out
        err = err.decode() if isinstance(err, bytes) else err
    wall = time.time() - t0
    with open(os.path.join(out_dir, f"{tag}.out"), "w") as fh:
        fh.write(out)
    with open(os.path.join(out_dir, f"{tag}.err"), "w") as fh:
        fh.write(err[-200000:])
    result = None
    lines = out.strip().splitlines()
    if rc == 0 and lines:
        result = json.loads(lines[-1])
    return {"tag": tag, "seed": seed, "trace": trace, "rc": rc,
            "wall_s": wall, "result": result}


def summarise(records, n_sets):
    """Per metric: each set's values, median and spread; the wider spread;
    the second set's median against the first's."""
    summary = {}
    sets = [[r for r in records if r["tag"].startswith(f"set{s}_")
             and r["result"]] for s in range(n_sets)]
    names = sorted({m for rs in sets for r in rs
                    for m in r["result"]["metrics"]})
    for name in names:
        per_set = []
        for rs in sets:
            vals = [r["result"]["metrics"][name]["value"] for r in rs
                    if name in r["result"]["metrics"]]
            if len(vals) >= 2:
                per_set.append({"values": vals,
                                "median": statistics.median(vals),
                                "spread": stats.spread(vals)})
        if not per_set:
            continue
        entry = {"sets": per_set,
                 "widest_spread": max(s["spread"] for s in per_set)}
        entry["bound_at_5x"] = 5 * entry["widest_spread"]
        if len(per_set) > 1:
            entry["second_median_over_first"] = \
                per_set[1]["median"] / per_set[0]["median"] - 1.0
        summary[name] = entry
    return summary


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seed0", type=int, default=2400000000)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--cold", type=int, choices=(0, 1), default=1)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--describe-trace", action="store_true",
                    help="also write planes, lines and first events of the "
                         "traced run's file (to look at by hand)")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "prove"))
    ap.add_argument("--timeout", type=float, default=1200)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        seconds = args.seconds or json.load(fh)["run_seconds"]
    out_dir = os.path.join(args.out, args.workload)
    os.makedirs(out_dir, exist_ok=True)

    plan = []
    if args.cold:
        plan.append(("cold", args.seed0, 0))
    for s in range(args.sets):
        for r in range(args.runs):
            plan.append((f"set{s}_run{r}", args.seed0 + 1 + r, 0))
    if args.trace:
        plan.append(("traced", args.seed0 + 1, 1))

    records = []
    with open(os.path.join(out_dir, "runs.jsonl"), "w") as log:
        for tag, seed, trace in plan:
            rec = one_run(args.workload, seed, seconds, trace, out_dir, tag,
                          args.timeout)
            records.append(rec)
            log.write(json.dumps(rec) + "\n")
            log.flush()
            print(json.dumps({k: rec[k] for k in ("tag", "seed", "rc",
                                                  "wall_s")}
                             | {"result": rec["result"]}), flush=True)
    summary = summarise(records, args.sets)
    with open(os.path.join(out_dir, "summary.json"), "w") as fh:
        json.dump(summary, fh, indent=1)
    for name, e in summary.items():
        print(f"{name}: medians {[s['median'] for s in e['sets']]} spreads "
              f"{[round(s['spread'], 5) for s in e['sets']]} -> 5x widest "
              f"{e['bound_at_5x']:.4f}", flush=True)
    if args.trace and args.describe_trace:
        # The traced run's file itself (gzipped; the chip tool brings back
        # 64 MiB) and what is in it, to write and check a reader against.
        from lib import trace
        xplane = trace.find_xplane(os.path.join(ROOT, ".bench_trace",
                                                args.workload))
        with open(xplane, "rb") as src, gzip.open(
                os.path.join(out_dir, "traced.xplane.pb.gz"), "wb") as dst:
            shutil.copyfileobj(src, dst)
        p = subprocess.run(
            [sys.executable, os.path.join(HERE, "lib", "trace.py"),
             os.path.dirname(xplane)], capture_output=True, text=True,
            timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
        with open(os.path.join(out_dir, "trace_describe.txt"), "w") as fh:
            fh.write(p.stdout[:4_000_000] + "\n" + p.stderr[-5000:])
    return 0 if all(r["rc"] == 0 for r in records) else 1


if __name__ == "__main__":
    sys.exit(main())
