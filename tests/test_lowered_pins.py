"""The lowered training step of the two hybrid linear-attention cells, for a
described v5e, hashed by ``benchmarks/lowered_sha.py`` with the kernels'
debug locations taken out: what PR 35 left, which put the kernels
``conv_silu_fwd`` / ``conv_silu_bwd`` into both. (The benchmark's own
``tests/benchmark/test_bench_lowered_steps.py`` pins the four older cells
to PR 34's programs and is not this PR's to edit: its Qwen3-Next case is
reported as expected by ``tests/conftest.py`` and its guard lives on here.)
A PR that means to change a cell's program replaces that cell's hash with
what the tool prints, and says so."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LOWERED = {
    "qwen3next_gdn_train_8k_1chip":
        "6d48876077ab9bdd2999f176a874557827423da0cc7e9e2351562fddd9158ce3",
    "kimi_kda_train_8k_1chip":
        "a0c1afe30da160ad98266f8d00832e5e24939eee7e80ba751c075d61f1c42344",
}


@pytest.fixture(scope="module")
def lines():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "lowered_sha.py"),
         *LOWERED],
        cwd=ROOT, env=dict(env, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return {d["cell"]: d for d in map(json.loads,
                                      p.stdout.strip().splitlines())}


@pytest.mark.parametrize("cell", list(LOWERED))
def test_lowered_step_is_the_one_pinned(lines, cell):
    assert lines[cell]["sha256_without_kernel_locations"] == LOWERED[cell]


@pytest.mark.parametrize("cell", list(LOWERED))
def test_the_cell_holds_the_convolutions_kernels(lines, cell):
    """Three kernel bodies more than the parent's lowered program had (16
    in either cell): ``conv_silu_fwd``, the same again inside the
    checkpoint's recomputation, ``conv_silu_bwd``; the layers of a cell
    share them (``_traced_once``)."""
    assert lines[cell]["kernels"] == 19
