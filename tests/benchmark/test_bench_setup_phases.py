"""Set-up and the collector's pauses read from the program's spans
(``layer_metrics/setup_phases.py``): the arithmetic on
hand-made spans, this PR's entries in the manifest, and a traced rehearsal
of the toy LM cell on a copy of the benchmark of this file's own."""

import collections
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_paths import BENCH, DATA, ROOT

S = 1_000_000_000          # ns
T0 = 1_790_000_000 * S     # a wall clock's reading
MAIN, OTHER = 11, 22
Span = collections.namedtuple("Span",
                              "id name start_ns end_ns thread parent ids")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(name, start_s, end_s, thread=MAIN, **ids):
    return Span(0, name, T0 + int(start_s * S), T0 + int(end_s * S), thread,
                0, ids)


def a_set_up():
    """30 s from the import's first statement to the session's start (s)."""
    return [
        span("hvd.import", 0, 5),
        span("xla.trace", 1, 1.5, fun="while_loading"),
        span("hvd.init", 6, 6.25, size=1),
        # The step: traced, then lowered with a kernel's trace inside.
        span("xla.trace", 8, 10, fun="update"),
        span("xla.trace", 8.5, 9, fun="inner"),
        span("xla.lower", 10, 14, fun="jit(update)"),
        span("xla.trace", 11, 12, fun="kernel_body"),
        span("xla.compile", 14, 15, fun="jit(update)", cache="hit"),
        # A small program that the cache did not hold, and one it is not
        # asked for.
        span("xla.trace", 16, 16.25, fun="check"),
        span("xla.lower", 16.25, 16.5, fun="jit(check)"),
        span("xla.compile", 16.5, 19.5, fun="jit(check)", cache="miss"),
        span("xla.compile", 20, 20.5, fun="jit(uncached)", cache="off"),
        # Another thread's compile, a span of no set-up name, a span that
        # ends after the session starts: all left out.
        span("xla.compile", 21, 29, thread=OTHER, fun="jit(elsewhere)",
             cache="miss"),
        span("host.gc", 22, 23, generation=2, collected=0),
        span("xla.trace", 29.5, 30.5, fun="late"),
    ]


def test_the_seven_add_up_and_nesting_is_counted_once():
    sp = reader("setup_phases")
    metrics, line = sp.phases(a_set_up(), T0 + 30 * S)
    assert metrics == {
        "setup.import_s": pytest.approx(4.5),     # less the trace inside
        "setup.trace_s": pytest.approx(0.5 + 2 + 1 + 0.25),
        "setup.lower_s": pytest.approx(3 + 0.25),  # less the kernel's trace
        "setup.cache_load_s": pytest.approx(1.0),
        "setup.backend_compile_s": pytest.approx(3.5),
        "setup.cache_misses": 2,
        "setup.unnamed_s": pytest.approx(30 - 4.5 - 3.75 - 3.25 - 1 - 3.5
                                         - 0.25)}
    assert line["interval_s"] == pytest.approx(30.0)
    assert line["hvd.init_s"] == pytest.approx(0.25)
    assert line["spans"] == 12
    seconds = sum(v for k, v in metrics.items() if k != "setup.cache_misses")
    assert seconds + line["hvd.init_s"] == pytest.approx(30.0, abs=1e-3)
    # A function's three phases share a row; each row holds the time in
    # which that function's span was the innermost.
    rows = {r[0]: r[1:] for r in line["by_fun"]}
    assert rows["update"] == [pytest.approx(1.5), pytest.approx(3.0),
                              pytest.approx(1.0), "hit"]
    assert rows["check"] == [pytest.approx(0.25), pytest.approx(0.25),
                             pytest.approx(3.0), "miss"]
    assert rows["kernel_body"][:3] == [pytest.approx(1.0), 0.0, 0.0]
    assert rows["uncached"][3] == "off" and rows["inner"][3] is None
    assert line["by_fun"][0][0] == "update" and "elsewhere" not in rows \
        and "late" not in rows
    assert line["functions"] == 6
    # The longest stretches under no span, and what ended before each.
    assert line["unnamed_gaps"][0] == [pytest.approx(20.5),
                                       pytest.approx(9.5),
                                       "xla.compile jit(uncached)"]
    assert line["unnamed_gaps"][1][1:] == [pytest.approx(1.75), "hvd.init"]
    assert sum(g[1] for g in line["unnamed_gaps"]) <= \
        metrics["setup.unnamed_s"] + 1e-9


def test_a_warm_run_reads_no_miss_and_a_tree_without_the_span_nothing():
    sp = reader("setup_phases")
    warm = [s for s in a_set_up() if s.ids.get("cache") in (None, "hit")]
    metrics, _ = sp.phases(warm, T0 + 30 * S)
    assert metrics["setup.cache_misses"] == 0
    assert metrics["setup.backend_compile_s"] == 0.0
    assert metrics["setup.cache_load_s"] == pytest.approx(1.0)
    before = [s for s in a_set_up() if s.name != "hvd.import"]
    assert sp.phases(before, T0 + 30 * S) is None
    # Zero-length and overlapping reports do not upset the sweep.
    odd = [span("hvd.import", 0, 1), span("xla.trace", 2, 2, fun="empty"),
           span("xla.lower", 3, 5, fun="jit(f)"),
           span("xla.trace", 2.999999, 4, fun="f"),
           span("xla.trace", 4.5, 5.000001, fun="g")]
    metrics, line = sp.phases(odd, T0 + 6 * S)
    assert metrics["setup.trace_s"] == pytest.approx(1.5, abs=1e-5)
    assert metrics["setup.lower_s"] == pytest.approx(0.5, abs=1e-5)
    assert metrics["setup.unnamed_s"] == pytest.approx(3.0, abs=1e-5)


def test_no_anchor_or_no_recorder_reads_nothing(monkeypatch, capsys):
    sp = reader("setup_phases")
    cell, run = {"name": "nothing_traced_here"}, {"steps": 10}
    monkeypatch.setattr(sp.sp, "program_spans", a_set_up)
    monkeypatch.setattr(sp.tr, "find_xplane", lambda d: d)
    monkeypatch.setattr(sp.sp, "anchor", lambda path: None)
    assert sp.read(None, run, cell) == {}
    monkeypatch.setattr(sp.sp, "program_spans", lambda: [])
    assert sp.read(None, run, cell) == {}
    said = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [l["event"] for l in said] == ["setup_phases"] * 2
    assert all("nothing is" in l["note"] for l in said)
    # With an anchor: the pauses inside the session, over the steps; a tree
    # that records no hvd.import still has its pauses read, and one that
    # records no pause leaves that metric out.
    late = [span("host.gc", 31, 31.05, thread=OTHER, generation=2,
                 collected=7),
            span("host.gc", 39.9, 40.1, generation=0, collected=0)]
    monkeypatch.setattr(sp.sp, "anchor",
                        lambda path: (T0 + 30 * S, T0 + 40 * S))
    monkeypatch.setattr(sp.sp, "program_spans", lambda: a_set_up() + late)
    got = sp.read(None, run, cell)
    assert got["host.gc_ms_per_step"] == pytest.approx(5.0)
    assert got["setup.cache_misses"] == 2 and len(got) == 8
    pauses, phases = [json.loads(l)
                      for l in capsys.readouterr().out.splitlines()]
    assert (pauses["event"], phases["event"]) == ("host_gc", "setup_phases")
    assert pauses["found"] == 3 and pauses["in_session"] == 1 \
        and pauses["pauses"] == [[pytest.approx(1.0), pytest.approx(50.0),
                                  2, 7]]
    monkeypatch.setattr(sp.sp, "program_spans", lambda: late)
    assert sp.read(None, run, cell) == {
        "host.gc_ms_per_step": pytest.approx(5.0)}
    monkeypatch.setattr(sp.sp, "program_spans", lambda: [
        s for s in a_set_up() if s.name != "host.gc"])
    assert set(sp.read(None, run, cell)) == {
        m for m in ADDED_PER_LAYER if m.startswith("setup.")}
    notes = [json.loads(l)["note"] for l in
             capsys.readouterr().out.splitlines() if '"note"' in l]
    assert len(notes) == 2 and "no hvd.import" in notes[0] \
        and "no host.gc" in notes[1]


# -- the manifest --------------------------------------------------------------

# This PR's: name -> (unit, layer, moves).
ADDED_PER_LAYER = {
    "setup.import_s": ("s", "entry and launcher", "setup_s"),
    "setup.trace_s": ("s", "entry and launcher", "setup_s"),
    "setup.lower_s": ("s", "entry and launcher", "setup_s"),
    "setup.cache_load_s": ("s", "entry and launcher", "setup_s"),
    "setup.backend_compile_s": ("s", "entry and launcher", "setup_s"),
    "setup.cache_misses": ("count", "entry and launcher", "setup_s"),
    "setup.unnamed_s": ("s", "entry and launcher", "setup_s"),
    "host.gc_ms_per_step": ("ms", "host loop", "step_ms_p90"),
}
# What the benchmark had before this PR, in its order.
HAD_PER_LAYER_HEAD = ["setup.compile_s", "fit.input_wait_ms",
                      "fit.dispatch_ms"]
HAD_PER_LAYER_TAIL = ["kda_scan_roofline", "mla_attend_roofline",
                      "kda.saved_state_mb"]


def test_entries_of_this_pr_and_the_order_of_what_the_benchmark_had():
    """This PR's entries by name and content, wherever later PRs put
    theirs; what the benchmark had before keeps its order. All cells: no
    ``workloads`` list, as ``setup.compile_s`` has none."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    names = [m["name"] for m in bench["per_layer"]]
    had = [n for n in names if n not in ADDED_PER_LAYER]
    assert had[:3] == HAD_PER_LAYER_HEAD
    old = HAD_PER_LAYER_HEAD + HAD_PER_LAYER_TAIL
    assert [n for n in names if n in old] == old
    assert len(names) == len(set(names))
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, layer, moves) in ADDED_PER_LAYER.items():
        assert entries[name] == {
            "name": name, "unit": unit, "better": "lower",
            "source": "program_span", "layer": layer, "moves": moves}
    added = [n for n in names if n in ADDED_PER_LAYER]
    assert added == list(ADDED_PER_LAYER)
    assert names.index(added[0]) > names.index("kda.saved_state_mb")
    assert "workloads" not in entries["setup.compile_s"]
    for metric in ("setup_s", "step_ms_p90"):
        assert "workloads" not in next(
            m for m in bench["end_to_end"] if m["name"] == metric)


# -- a traced toy rehearsal of the LM cell -------------------------------------

@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A copy of the benchmark with the toy LM cell added as data (the
    fixture of test_bench_spans.py, for this file)."""
    root = tmp_path_factory.mktemp("bench_copy_setup")
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    shutil.copy(os.path.join(DATA, "toy_step_loop.json"),
                root / "benchmarks" / "traffic")
    shutil.copy(os.path.join(DATA, "toy_lm.json"),
                root / "benchmarks" / "configs")
    bench["configs"].append({
        "name": "toy_lm", "source": "tests/benchmark/data",
        "file": "benchmarks/configs/toy_lm.json", "reduced": [],
        "why": "toy"})
    bench["workloads"].append({"name": "toy_lm_step", "config": "toy_lm",
                               "traffic": "toy_step_loop", "chips": 1,
                               "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "lm_step_1chip" in m.get("workloads", []):
            m["workloads"].append("toy_lm_step")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_traced_rehearsal_of_the_lm_cell_reports_the_set_up_metrics(
        copy, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, str(copy / "benchmarks" / "run.py"), "--workload",
         "toy_lm_step", "--seed", "3600000001", "--seconds", "1",
         "--trace", "1", "--rehearse"], cwd=copy, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 3, p.stderr[-3000:]
    lines = [json.loads(l) for l in p.stdout.strip().splitlines()]
    got = {n: m["value"] for n, m in lines[-1]["metrics"].items()}
    assert set(ADDED_PER_LAYER) <= set(got), sorted(got)
    said = [l for l in lines if l.get("event") == "setup_phases"]
    assert len(said) == 1 and said[0]["spans"] > 10
    seconds = sum(got[n] for n in ADDED_PER_LAYER
                  if n.startswith("setup.") and n != "setup.cache_misses")
    assert seconds + said[0]["hvd.init_s"] == pytest.approx(
        said[0]["interval_s"], abs=1e-3)
    assert got["setup.import_s"] > 0 and got["setup.trace_s"] > 0 \
        and got["setup.lower_s"] > 0 and got["setup.unnamed_s"] > 0
    # A fresh cache: every program was compiled, none loaded.
    assert got["setup.cache_misses"] >= 1 \
        and got["setup.backend_compile_s"] > 0 \
        and got["setup.cache_load_s"] == 0
    # The interval starts after the harness's own clock does and ends
    # after the window's first step may be dispatched: the profiler's
    # start lies between.
    assert 0 < said[0]["interval_s"]
    # The line names functions: the step and the reference check.
    funs = [row[0] for row in said[0]["by_fun"]]
    assert "update" in funs and all(len(row) == 5
                                    for row in said[0]["by_fun"])
    assert got["host.gc_ms_per_step"] >= 0
    pauses = [l for l in lines if l.get("event") == "host_gc"]
    assert len(pauses) == 1 and pauses[0]["found"] >= 1
    # What was there reads what it read.
    assert got["step.compiles_in_window"] == 0 and got["setup.compile_s"] > 0
