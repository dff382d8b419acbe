"""``layer_metrics/gdn_stages.py`` on a hand-made trace: the walk's kernels
by their names, everything else under ``gdn.scan`` as the chunk-local
stage, the two adding up to what ``lm_gdn_moe.py`` reads for the scope; and
its entries in ``BENCHMARK.json``."""

import json
import os
import sys

import pytest

from bench_paths import BENCH, ROOT

CELL = "qwen3next_gdn_train_8k_1chip"
SCOPE = "jit(step)/jvp(forward)/gdn.scan/jit(_rule)/"
BACK = "jit(step)/transpose(jvp(forward))/gdn.scan/"

# instruction text as the TPU's trace names an event -> framework name
NAMES = {
    "%gdn_fwd.1 = bf16[64,128]{1,0} custom-call(%a), "
    "custom_call_target=\"tpu_custom_call\"": SCOPE + "gdn_fwd",
    "%gdn_bwd.1 = bf16[64,128]{1,0} custom-call(%a)": BACK + "gdn_bwd",
    "%gdn_local_fwd.2 = bf16[64,128]{1,0} custom-call(%a)":
        SCOPE + "gdn_local_fwd",
    "%gdn_local_fwd.3 = bf16[64,128]{1,0} custom-call(%a)":
        BACK + "gdn_local_fwd",
    "%gdn_local_bwd.1 = bf16[64,128]{1,0} custom-call(%a)":
        BACK + "gdn_local_bwd",
    "%fusion.7 = f32[8]{0} fusion(%gdn_fwd.1)": SCOPE + "reduce_sum",
    "%fusion.8 = f32[8]{0} fusion(%b)":
        "jit(step)/jvp(forward)/checkpoint/gdn.scan/rsqrt",
    "%fusion.9 = f32[8]{0} fusion(%c)": "jit(step)/jvp(forward)/gdn.proj/dot",
    "%gdn_fwd_lookalike = f32[8]{0} fusion(%c)": "jit(step)/optimizer/mul",
    "%copy.3 = f32[8]{0} copy(%d)": "",
}


@pytest.fixture()
def reader():
    sys.path.insert(0, BENCH)
    from layer_metrics import gdn_stages, lm_gdn_moe
    return gdn_stages, lm_gdn_moe


def test_walk_and_local_stage_add_up_to_the_scope(reader):
    stages, family = reader
    ops = [(name, 1e6 * i, 1e6 * i + 3e6) for i, name in enumerate(NAMES)]
    got = stages.by_stage(ops, NAMES, steps=2)
    # Two walk kernels; three local kernels and two fusions; 1.5 ms each.
    assert got == {"gdn.scan_walk_ms": 3.0, "gdn.scan_local_ms": 7.5}
    assert sum(got.values()) == family.by_scope(ops, NAMES, 2)["gdn.scan"]


def test_program_without_the_kernels_or_the_scope(reader):
    stages, _ = reader
    # The parent's program: the walk's kernels, the stage as XLA's fusions.
    parent = {n: f for n, f in NAMES.items() if "gdn_local" not in n}
    ops = [(name, 0.0, 2e6) for name in parent]
    assert stages.by_stage(ops, parent, steps=1) == {
        "gdn.scan_walk_ms": 4.0, "gdn.scan_local_ms": 4.0}
    # The XLA backend: no kernel at all, every op the stage's.
    xla = {"%while.1 = f32[8]{0} fusion(%a)": BACK + "while/body/dot"}
    assert stages.by_stage([(n, 0.0, 1e6) for n in xla], xla, 1) == {
        "gdn.scan_walk_ms": 0.0, "gdn.scan_local_ms": 1.0}
    # A CPU's trace carries no framework name: both left out, no raise.
    assert stages.by_stage(ops, {}, steps=1) == {}
    assert stages.read(None, {}, {"config": {"family": "lm"}}) == {}


def test_entries_in_the_manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entries = {m["name"]: m for m in bench["per_layer"]}
    scan = entries["gdn.scan_ms"]
    for name in ("gdn.scan_walk_ms", "gdn.scan_local_ms"):
        assert {k: v for k, v in entries[name].items() if k != "name"} == \
            {k: v for k, v in scan.items() if k != "name"}
        assert entries[name]["workloads"] == [CELL]
    # Added at the end: nothing the benchmark had has moved.
    assert [m["name"] for m in bench["per_layer"]][-2:] == [
        "gdn.scan_walk_ms", "gdn.scan_local_ms"]
