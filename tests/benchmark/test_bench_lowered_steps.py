"""The lowered training step of each LM-family cell the benchmark had before
PR 34, for a described v5e, hashed by ``benchmarks/lowered_sha.py`` with the
kernels' debug locations taken out: what PR 34 (which gave the rule, the
flash kernels, the expert layer and the block new options beside the ones
these cells use) found on its parent, commit fa1c571, and left as it was. A
PR that means to change a cell's program replaces that cell's hash with
what the tool prints, and says so."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT

LOWERED = {
    "lm_step_1chip":
        "21136d621a6c02ac3e7aa77fc80d96b799810e810ba46ed0271db8515aa89f64",
    "lm_dp4_4chip":
        "fe9e000c8d7e546e40a3c5bb97228eb4fb399fc43024d3c067a4319d609464b7",
    "keye_dsa_train_8k_1chip":
        "98606d7344ae24dff11a0dbb91263c3c13771cab56021e4b8d80b3023230483d",
    "qwen3next_gdn_train_8k_1chip":
        "168056f2dc3b2ead08509e18059d1f0ddd1fcc8e0f0c3786addc88f9b819f84e",
}


@pytest.fixture(scope="module")
def lines():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "lowered_sha.py"), *LOWERED],
        cwd=ROOT, env=dict(env, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return {d["cell"]: d for d in map(json.loads,
                                      p.stdout.strip().splitlines())}


@pytest.mark.parametrize("cell", list(LOWERED))
def test_lowered_step_is_the_one_pinned(lines, cell):
    line = lines[cell]
    # Every cell holds kernels: the raw hash would carry this checkout's
    # paths.
    assert line["kernels"] >= 2
    assert line["sha256_without_kernel_locations"] == LOWERED[cell]
