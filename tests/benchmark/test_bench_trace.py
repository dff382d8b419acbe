"""The trace reduction, on hand-made traces: union for busy time, idle
share, a kernel's sum, exposed collective time with and without overlap,
top operations, gap attribution; and the readers on the same traces."""

import importlib.util
import os

import pytest

from bench_paths import BENCH
from lib import stats, trace as tr

MS = 1e6  # ns
# Event names as the TPU profiler gives them: the whole HLO instruction.
KERNEL = ('%transpose_jvp___.13 = bf16[8,2048,6144]{2,1,0:T(8,128)(2,1)} '
          'custom-call(bf16[8,2048,6144]{2,1,0} %fusion.542, f32[128,2048,8]'
          '{2,1,0} %pallas_call.22), custom_call_target="tpu_custom_call", '
          'operand_layout_constraints={bf16[8,2048,6144]{2,1,0}}')
CONSUMER = ('%fusion.7 = (f32[2048]{0:T(1024)S(1)}, bf16[8,2048]{1,0}) '
            'fusion(f32[8]{0} %pallas_call.22, f32[8]{0} %all-reduce.3), '
            'kind=kLoop, calls=%fused_computation.9')
ALLREDUCE = ('%all-reduce-start.3 = f32[1024]{0} all-reduce-start(f32[1024]'
             '{0} %fusion.7), replica_groups={{0,1,2,3}}, to_apply=%add')
WHILE = '%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.3)'


def plane(ops, name="/device:TPU:0"):
    return tr.DevicePlane(name, [(n, s * MS, e * MS) for n, s, e in ops], [])


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_union_merges_nested_touching_and_disjoint():
    assert tr.union([(0, 4), (1, 2), (4, 6), (8, 9), (9, 9)]) == \
        [(0, 6), (8, 9)]
    assert tr.length([(0, 4), (1, 2), (4, 6), (8, 9)]) == 7


def test_intersect():
    assert tr.intersect([(0, 5), (7, 9)], [(3, 8)]) == [(3, 5), (7, 8)]
    assert tr.intersect([(0, 1)], [(2, 3)]) == []


@pytest.mark.parametrize("text,short,op,target", [
    (KERNEL, "transpose_jvp___.13", "custom-call", "tpu_custom_call"),
    (CONSUMER, "fusion.7", "fusion", ""),
    (ALLREDUCE, "all-reduce-start.3", "all-reduce-start", ""),
    (WHILE, "while.1", "while", ""),
    ("dot_general.1", "dot_general.1", "dot_general", ""),
], ids=["kernel", "consumer", "allreduce", "while", "plain"])
def test_names_are_parsed_from_the_hlo_text(text, short, op, target):
    assert tr.short_name(text) == short
    assert tr.opcode(text) == op
    assert tr.custom_call_target(text) == target


def test_label_is_short_and_keeps_what_matters():
    assert tr.label(KERNEL) == \
        "transpose_jvp___.13 custom-call:tpu_custom_call -> bf16[8,2048,6144]"
    assert tr.label(CONSUMER) == \
        "fusion.7 fusion -> (f32[2048], bf16[8,2048])"
    assert tr.label("plain") == "plain"


def test_matchers_go_by_instruction_not_by_operands():
    # The consumer names %pallas_call.22 and %all-reduce.3 as operands and
    # is neither a kernel nor a collective.
    assert reader("flash_attn").is_kernel(KERNEL)
    assert not reader("flash_attn").is_kernel(CONSUMER)
    assert reader("allreduce").is_collective(ALLREDUCE)
    assert not reader("allreduce").is_collective(CONSUMER)
    assert tr.is_parent(WHILE) and not tr.is_parent(CONSUMER)


def test_busy_is_the_union_not_the_sum():
    # A while parent encloses its two body ops; a third op follows a gap.
    p = plane([("while.1", 0, 10), ("fusion.1", 0, 4), ("fusion.2", 5, 10),
               ("fusion.3", 12, 14)])
    assert tr.busy_ns(p) == 12 * MS
    # The sum of leaf ops leaves the parent out: 4 + 5 + 2.
    assert tr.matching_ns(p, lambda n: True) == 11 * MS


def test_kernel_sum_and_top_ops():
    p = plane([(KERNEL, 0, 3), (CONSUMER, 3, 5), (KERNEL, 10, 13),
               ("flash_bwd", 13, 20), (WHILE, 0, 20)])
    assert tr.matching_ns(p, reader("flash_attn").is_kernel) == 6 * MS
    assert tr.top_ops(p, 2) == [["flash_bwd", 0.007],
                                [tr.label(KERNEL), 0.006]]


@pytest.mark.parametrize("other,exposed_ms", [
    ([], 10.0),                                  # nothing else runs
    ([("fusion.1", 0, 10)], 0.0),                # fully hidden
    ([("fusion.1", 2, 6)], 6.0),                 # partly overlapped
    ([("fusion.1", 20, 30)], 10.0),              # other work elsewhere
], ids=["alone", "hidden", "partial", "disjoint"])
def test_exposed_collective_time(other, exposed_ms):
    is_ar = reader("allreduce").is_collective
    p = plane([("all-reduce.3", 0, 10)] + other)
    assert tr.collective_ns(p, is_ar) == (10 * MS, exposed_ms * MS)
    # The same collective as a start..done pair on the asynchronous line,
    # with its -done on the op line: counted once.
    p = plane([("all-reduce-done.3", 9, 10)] + other)
    p.async_ops = [(ALLREDUCE, 0, 10 * MS)]
    assert tr.collective_ns(p, is_ar) == (10 * MS, exposed_ms * MS)


def test_idle_gaps_are_named_by_the_covering_span():
    p = plane([("a", 0, 2), ("b", 10, 12), ("c", 13, 20)])
    host = {"bench.next_batch": [(2 * MS, 9 * MS)],
            "bench.sync": [(12.2 * MS, 12.9 * MS)]}
    gaps = dict(tr.idle_gaps(p, host, (0, 22 * MS)))
    assert gaps["bench.next_batch"] == pytest.approx(0.008)
    assert gaps["bench.sync"] == pytest.approx(0.001)
    assert gaps["unattributed"] == pytest.approx(0.002)   # 20..22


def test_readers_on_a_hand_made_two_step_trace():
    # Two steps of 40 ms busy in a 100 ms window on two devices.
    ops = [("fusion.1", 0, 30), (KERNEL, 30, 35),
           ("all-reduce.1", 35, 40), ("fusion.1", 50, 80),
           (KERNEL, 80, 85), ("all-reduce.1", 85, 90)]
    t = tr.Trace([plane(ops), plane(ops, "/device:TPU:1")], {})
    config = {"family": "lm", "hidden_size": 256, "num_attention_heads": 2,
              "intermediate_size": 512, "vocab_size": 1024,
              "num_hidden_layers": 2}
    run = {"steps": 2, "window_s": 0.1, "units_per_step": 4 * 128,
           "compile_s": 1.5, "batch_per_chip": 2, "seq_len": 128}
    cell = {"name": "x", "chips": 2, "config": config, "traffic": {},
            "peaks": {"bf16_flop_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
    assert reader("device_step").read(t, run, cell) == \
        {"device_step_ms.lm": pytest.approx(40.0)}
    assert reader("device_idle").read(t, run, cell) == \
        {"device.idle_pct": pytest.approx(20.0)}
    ar = reader("allreduce").read(t, run, cell)
    assert ar["allreduce.ms_per_step"] == pytest.approx(5.0)
    assert ar["allreduce.exposed_ms"] == pytest.approx(5.0)
    fa = reader("flash_attn").read(t, run, cell)
    assert fa["flash_attn.ms_per_step"] == pytest.approx(5.0)
    # 2 layers x 3 x (2 B H T^2 d) = 2*3*2*2*2*128*128*128 FLOP at 1e12/s
    least_s = 2 * 3 * 2 * 2 * 2 * 128 * 128 * 128 / 1e12
    assert fa["flash_attn_roofline"] == pytest.approx(
        100 * least_s / 0.005)
    mfu = reader("mfu_lm").read(t, run, cell)["mfu_pct.lm"]
    fwd = 2 * (8 * 256 * 256 + 4 * 256 * 512 + 2 * 128 * 256) \
        + 2 * 256 * 1024
    assert mfu == pytest.approx(100 * (2 * 512 / 0.1) * 3 * fwd / 2e12)
    assert reader("setup_compile").read(t, run, cell) == \
        {"setup.compile_s": 1.5}
    # Readers that find nothing to read return nothing.
    assert reader("fit_loop").read(t, run, cell) == {}
    assert reader("allreduce").read(t, run, dict(cell, chips=1)) == {}
    vision = dict(cell, config=dict(config, family="resnet"))
    assert reader("flash_attn").read(t, run, vision) == {}
    assert reader("mfu_lm").read(t, run, vision) == {}
    fit = {"fit": {"input_wait_ms": [1.0, 3.0, 2.0],
                   "dispatch_ms": [4.0, 6.0, 5.0, 7.0]}}
    assert reader("fit_loop").read(t, dict(run, **fit), cell) == \
        {"fit.input_wait_ms": 2.0, "fit.dispatch_ms": 5.5}


def test_percentile_and_spread():
    xs = [10, 20, 30, 40, 50]
    assert stats.percentile(xs, 0) == 10 and stats.percentile(xs, 100) == 50
    assert stats.percentile(xs, 90) == pytest.approx(46.0)
    # statistics.quantiles(n=4), exclusive: q1 = 15, q3 = 45 -> 30 / 30
    assert stats.spread(xs) == pytest.approx(1.0)
