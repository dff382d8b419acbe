"""The readers that lay the program's spans on the device trace
(``lib/spans.py``, ``layer_metrics/program_spans.py``,
``layer_metrics/device_scopes.py``), on hand-made spans and traces: self
time, idle-and-span intersection, the split by named scope adding up, the
kernels by name; then a traced toy rehearsal of the fit cell, on a copy of
the benchmark of this file's own."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_paths import BENCH, DATA, ROOT
from lib import spans as sp, trace as tr

MS = 1e6   # ns
LOOP, WORKER = 11, 22


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def S(id, name, start, end, thread=LOOP, parent=0, **ids):
    return sp.S(id, name, start * MS, end * MS, thread, parent, ids)


def two_turns():
    """Two turns of a fit loop (ms): next_batch, callbacks, train_step with
    its dispatch inside, callbacks; the second turn waits 6 ms for input."""
    return [
        S(1, "fit.step", 0, 20, step=5),
        S(2, "fit.next_batch", 0, 1, parent=1, batch=0, queue_depth=2),
        S(3, "fit.callbacks", 1, 2, parent=1),
        S(4, "fit.train_step", 2, 6, parent=1),
        S(5, "step.dispatch", 3, 5, parent=4),
        S(6, "fit.callbacks", 6, 16, parent=1),
        S(7, "fit.step", 20, 40, step=6),
        S(8, "fit.next_batch", 20, 26, parent=7, batch=1, queue_depth=0),
        S(9, "fit.train_step", 26, 30, parent=7),
        S(10, "step.dispatch", 27, 29, parent=9),
        S(20, "input.source", 0, 3, thread=WORKER, batch=1),
        S(21, "H2D", 3, 25, thread=WORKER, batch=1),
        S(22, "H2D", 30, 38, thread=WORKER, batch=2),
    ]


def test_medians_self_time_and_on_trace_clock():
    spans = two_turns()
    assert sp.median_ms(spans, "fit.next_batch") == pytest.approx(3.5)
    assert sp.median_ms(spans, "H2D") == pytest.approx(15.0)
    assert sp.median_ms(spans, "nothing.so.named") is None
    # Turn 1: 20 - (1 + 1 + 4 + 10) = 4; turn 2: 20 - (6 + 4) = 10. The
    # dispatch is a grandchild and is not taken off twice.
    assert sp.self_ms(spans, "fit.step") == pytest.approx([4.0, 10.0])
    assert sp.self_ms(spans, "fit.train_step") == pytest.approx([2.0, 2.0])

    class Wall:      # what timeline.spans() hands out
        def __init__(self, id, start_ns, end_ns):
            self.id, self.name, self.thread, self.parent = id, "x", 1, 0
            self.start_ns, self.end_ns, self.ids = start_ns, end_ns, {}
    kept = sp.on_trace_clock([Wall(1, 90, 120), Wall(2, 100, 150),
                              Wall(3, 150, 210)], 100, 200)
    assert [(s.id, s.start, s.end) for s in kept] == [(2, 0, 50)]


def test_idle_is_attributed_to_the_innermost_span_open():
    ps = reader("program_spans")
    spans = two_turns()
    # Device busy 0-21 and 24-40: one idle gap, 21-24, inside the second
    # turn's fit.next_batch. Then busy 2-8 and 10-40: the gap 0-2 is half
    # next_batch, half callbacks, and 8-10 is inside the end callbacks.
    ops = [("%fusion.1 = f32[8] fusion()", 0 * MS, 21 * MS),
           ("%fusion.2 = f32[8] fusion()", 24 * MS, 40 * MS)]
    input_pct, host_pct, rows = ps.idle_by_span(spans, ops, window_s=0.040)
    assert input_pct == pytest.approx(100 * 3 / 40)
    assert host_pct == pytest.approx(0.0)
    assert rows == [("fit.next_batch", pytest.approx(0.003))]
    ops = [("%fusion.1 = f32[8] fusion()", 2 * MS, 8 * MS),
           ("%fusion.2 = f32[8] fusion()", 10 * MS, 40 * MS)]
    input_pct, host_pct, rows = ps.idle_by_span(spans, ops, window_s=0.040)
    assert input_pct == pytest.approx(100 * 1 / 40)
    assert host_pct == pytest.approx(100 * 3 / 40)
    assert dict(rows) == {"fit.callbacks": pytest.approx(0.003),
                          "fit.next_batch": pytest.approx(0.001)}
    # Never more than the device's idle time in the loop's range.
    assert (input_pct + host_pct) * 0.4 <= 4.0 + 1e-9
    assert ps.idle_by_span([s for s in spans if s.thread == WORKER], ops,
                           0.04) is None      # no loop, no attribution


def test_self_intervals_leave_the_children_out():
    ps = reader("program_spans")
    own = ps.self_intervals(two_turns(), LOOP)
    assert own["fit.train_step"] == [(2 * MS, 3 * MS), (5 * MS, 6 * MS),
                                     (26 * MS, 27 * MS), (29 * MS, 30 * MS)]
    assert tr.length(own["fit.step"]) == pytest.approx(14 * MS)


FWD = ('%fusion.3 = bf16[8,2048,2048]{2,1,0} fusion(bf16[8,2048,2048]{2,1,0} '
       '%p), kind=kOutput, calls=%fused_computation.3')
SCOPES = {
    "jit(step)/jit(main)/jvp(forward)/ResNet/conv_general_dilated": "forward",
    "jit(step)/jit(main)/transpose(jvp(forward))/ResNet/dot_general":
        "backward",
    "jit(step)/jit(main)/transpose(jvp(forward))/checkpoint/"
    "rematted_computation/forward/mul": "backward",
    "jit(step)/jit(main)/optimizer/allreduce.bucket0/psum": "optimizer",
    "jit(step)/jit(main)/optimizer/add": "optimizer",
    "optimizer/mul": "optimizer",
    "jvp(forward)/jit(relu)": "forward",
    "jit(step)/jit(main)/forward_hidden/mul": "unscoped",
    "jit(step)/jit(main)/my_optimizer_state/add": "unscoped",
    "jit(step)/jit(main)/pmean": "unscoped",
    "": "unscoped",
}


@pytest.mark.parametrize("text,scope", SCOPES.items(),
                         ids=[str(i) for i in range(len(SCOPES))])
def test_scope_is_a_path_component_of_the_framework_name(text, scope):
    assert sp.scope_of(text) == scope
    assert sp.scope_of(FWD + f', metadata={{op_name="{text}"}}') == scope


def test_split_by_scope_adds_up_and_kernels_go_by_name():
    ds = reader("device_scopes")
    k = ('%{}.{} = bf16[8,2048,2048]{{2,1,0}} custom-call(bf16[8,2048,6144]'
         '{{2,1,0}} %p), custom_call_target="tpu_custom_call"')

    def fusion(i):
        return FWD.replace("%fusion.3", f"%fusion.{i}")
    scoped = [   # name, framework name, start, end (ms)
        (fusion(1), "jit(s)/jvp(forward)/dot_general:", 0, 10),
        (k.format("flash_fwd", "1.remat"), "jit(s)/jvp(forward)/flash_fwd/"
         "pallas_call:", 10, 13),
        (k.format("flash_bwd", 1), "jit(s)/transpose(jvp(forward))/"
         "flash_bwd/pallas_call:", 13, 18),
        (k.format("flash_bwd_dq", 7), "jit(s)/transpose(jvp(forward))/"
         "flash_bwd_dq/pallas_call:", 18, 19),
        (k.format("flash_bwd_dkv", "7.remat"), "jit(s)/transpose(jvp("
         "forward))/flash_bwd_dkv/pallas_call:", 19, 21),
        (fusion(2), "jit(s)/transpose(jvp(forward))/dot_general:", 21, 41),
        (fusion(3), "jit(s)/optimizer/allreduce.bucket0/psum:", 41, 45),
        (fusion(4), "jit(s)/pmean:", 45, 45.5),
        ("%copy-done.2 = f32[8] copy-done(%copy-start.2)", None, 45.5, 46),
    ]
    ops = [(n, s * MS, e * MS) for n, _, s, e in scoped]
    names = {n: text for n, text, _, _ in scoped if text}
    got = ds.split(ops, names, steps=2)
    assert got == {
        "device_step.forward_ms": pytest.approx(6.5),
        "device_step.backward_ms": pytest.approx(14.0),
        "device_step.optimizer_ms": pytest.approx(2.0),
        "device_step.unscoped_ms": pytest.approx(0.5),
        "flash_attn.fwd_ms_per_step": pytest.approx(1.5),
        "flash_attn.bwd_ms_per_step": pytest.approx(4.0)}
    assert sum(v for n, v in got.items() if n.startswith("device_step.")) \
        == pytest.approx(46 / 2)
    # A program from before the scopes and the kernel names: nothing.
    bare = [(fusion(1), 0, MS),
            (k.format("transpose_jvp___", 13), MS, 2 * MS)]
    assert ds.split(bare, {fusion(1): "jit(s)/dot_general:"}, 1) == {}


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(number, payload):
    """A protobuf field: a varint for an int, length-delimited for bytes."""
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def test_framework_names_are_read_from_the_event_metadata(tmp_path):
    """A hand-made XSpace: two planes; the device's has a line (skipped
    unread), a stat table and three event metadata, one without ``tf_op``
    and one whose ``tf_op`` is a reference into the stat table."""
    def stat_meta(i, name):
        return _field(5, _field(1, i) + _field(2, _field(1, i)
                                                + _field(2, name)))

    def event_meta(i, name, *stats):
        body = _field(1, i) + _field(2, name) + b"".join(
            _field(5, st) for st in stats)
        return _field(4, _field(1, i) + _field(2, body))
    device = (_field(1, 7) + _field(2, b"/device:TPU:0")
              + _field(3, b"\xff" * 300)              # a line: never parsed
              + stat_meta(1, b"flops") + stat_meta(2, b"tf_op")
              + stat_meta(3, b"jit(s)/optimizer/add:")
              + event_meta(10, b"%fusion.1 = f32[8] fusion()",
                           _field(1, 1) + _field(4, 99),
                           _field(1, 2) + _field(5, b"jit(s)/jvp(forward)"
                                                 b"/dot_general:"))
              + event_meta(11, b"%copy.1 = f32[8] copy()",
                           _field(1, 1) + _field(4, 5))
              + event_meta(12, b"%fusion.2 = f32[8] fusion()",
                           _field(1, 2) + _field(7, 3)))
    other = _field(2, b"/host:CPU") + event_meta(
        1, b"%fusion.1 = f32[8] fusion()", _field(1, 2) + _field(5, b"no"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, other) + _field(1, device))
    assert sp.framework_names(str(path)) == {
        "%fusion.1 = f32[8] fusion()": "jit(s)/jvp(forward)/dot_general:",
        "%fusion.2 = f32[8] fusion()": "jit(s)/optimizer/add:"}
    assert sp.framework_names(str(path), "/device:TPU:1") == {}


def test_device_clock_lag_is_the_least_wait_seen():
    ps = reader("program_spans")
    assert sp.device_clock_lag([], []) is None
    assert sp.device_clock_lag([10, 20, 30], [12.5, 21, 34, 99]) == 1
    # Two turns ahead of the device: turn k's end callbacks return after
    # step k - 2. Steps of 10 ms end, on the device's clock, at 10, 20, 30
    # ...; its clock lags by 1 ms and the host wakes 0.3, 0.1 and 0.2 ms
    # after the fact, so the least it ever saw, 1.1 ms, is the bound.
    ends = [1.0, 2.0, 11.3, 21.1, 31.2]
    spans, t = [], 0.0
    for k, end in enumerate(ends):
        spans += [S(100 + k, "fit.step", t, end, step=k),
                  S(200 + k, "fit.callbacks", t, t + 0.1, parent=100 + k),
                  S(300 + k, "fit.callbacks", t + 0.5, end, parent=100 + k)]
        t = end
    plane = tr.DevicePlane("/device:TPU:0", [], [])
    assert ps.fit_host_lag(spans, plane) is None          # no module line
    plane.modules = [("jit_step(1)", 10 * k * MS, 10 * (k + 1) * MS)
                     for k in range(5)] + [("jit_add(2)", 1 * MS, 1.1 * MS)]
    assert ps.fit_host_lag(spans, plane) == pytest.approx(1.1 * MS)
    # Shifted by the lag, a gap that seemed to fall in the callbacks falls
    # where the loop was really waiting for its batch.
    loop = [S(1, "fit.step", 0, 10), S(2, "fit.next_batch", 0, 4, parent=1),
            S(3, "fit.callbacks", 4, 10, parent=1)]
    ops = [("%a = f32[] fusion()", -2 * MS, 1 * MS),
           ("%b = f32[] fusion()", 3 * MS, 20 * MS)]
    assert ps.idle_by_span(loop, ops, 0.010)[0] == pytest.approx(20.0)
    assert ps.idle_by_span(loop, ops, 0.010, lag_ns=2 * MS)[:2] == (
        pytest.approx(10.0), pytest.approx(10.0))


# -- a traced toy rehearsal of the fit cell ------------------------------------

@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A copy of the benchmark with the toy fit cell added as data (the
    fixture of test_bench_rehearse.py, for this file)."""
    root = tmp_path_factory.mktemp("bench_copy_spans")
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    shutil.copy(os.path.join(DATA, "toy_fit_host.json"),
                root / "benchmarks" / "traffic")
    shutil.copy(os.path.join(DATA, "toy_resnet.json"),
                root / "benchmarks" / "configs")
    bench["configs"].append({
        "name": "toy_resnet", "source": "tests/benchmark/data",
        "file": "benchmarks/configs/toy_resnet.json", "reduced": [],
        "why": "toy"})
    bench["workloads"].append({"name": "toy_resnet_fit",
                               "config": "toy_resnet",
                               "traffic": "toy_fit_host", "chips": 1,
                               "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "resnet50_fit_1chip" in m.get("workloads", []):
            m["workloads"].append("toy_resnet_fit")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_traced_rehearsal_of_the_fit_cell_reports_the_span_metrics(
        copy, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, str(copy / "benchmarks" / "run.py"), "--workload",
         "toy_resnet_fit", "--seed", "2600000003", "--seconds", "1",
         "--trace", "1", "--rehearse"], cwd=copy, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 3, p.stderr[-3000:]
    lines = [json.loads(l) for l in p.stdout.strip().splitlines()]
    got = {n: m["value"] for n, m in lines[-1]["metrics"].items()}
    for name in ("fit.next_batch_ms", "fit.train_step_ms", "fit.self_ms",
                 "input.source_ms", "input.h2d_ms", "input.queue_depth",
                 "step.dispatch_ms", "step.compiles_in_window",
                 "device.idle_input_pct", "device.idle_host_pct"):
        assert name in got, (name, sorted(got))
    # The inside readings split what the harness's clock takes from outside.
    assert 0 < got["step.dispatch_ms"] <= got["fit.train_step_ms"] \
        <= got["fit.dispatch_ms"]
    assert 0 < got["fit.next_batch_ms"] <= got["fit.input_wait_ms"]
    assert got["fit.self_ms"] > 0 and got["input.h2d_ms"] > 0
    assert 0 <= got["input.queue_depth"] <= 2
    assert got["step.compiles_in_window"] == 0
    # Never more than the device's idle share; two clocks (the window is
    # the harness's perf_counter, the gaps the trace's) leave a hair.
    assert 0 <= got["device.idle_input_pct"] + got["device.idle_host_pct"] \
        <= got["device.idle_pct"] * 1.01 + 0.01
    table = [l for l in lines if l.get("event")
             == "idle_gaps_by_program_span"]
    assert len(table) == 1 and table[0]["seconds_by_span"]
    said = [l for l in lines if l.get("event") == "program_spans"]
    assert {"fit.step", "H2D", "input.source", "step.dispatch"} \
        <= set(said[0]["names"])
