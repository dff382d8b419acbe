"""The lowered training step of the five expert-layer cells, for a
described v5e, hashed by ``benchmarks/lowered_sha.py`` with the kernels'
debug locations taken out: what PR 37 left, which made the expert layer's
chunks after the first a loop whose trip count is the load, forward and
backward, in all three (PR 35 had put the kernels ``conv_silu_fwd`` /
``conv_silu_bwd`` into the two hybrid cells); and PR 38's latent-attention
cell, whose rotation left the other three as they were. Then the flash
gate's 64 MiB rung changed the programs of the three cells with flash
attention at T 8192 (Qwen3-Next, Kimi, kanana2): the fused backward
``flash_bwd`` in place of ``flash_bwd_dq`` + ``flash_bwd_dkv``, and the
forward with K/V resident; Keye's program is as it was. All four changed
again by design when the expert layer's bookkeeping lost its scatters and
gathers (one stable sort carries the weights; the assignments are counted
by compare-and-sum; in the two cells whose sigmoid router picks through a
selection bias, Kimi and kanana2, the kept scores are read off a compare
too; on the parent, commit ba93722: 324b2fc3..., 5f172e52..., b4efd8d9...,
4f1a3610...). All four changed again by design when a static zero weight
stopped building the expert layers' balance loss, which every one of these
cells builds at ``aux_weight=0.0``: no router backward, no share and no
mean of the probabilities (on the parent, commit 8aae949: 397ed6b0...,
7c4e48e1..., e8a76fbd..., a05c1fba...). (The benchmark's own
``tests/benchmark/test_bench_lowered_steps.py`` pins the four older cells
to PR 34's programs and is not this PR's to edit: its Qwen3-Next and Keye
cases are reported as expected by ``tests/conftest.py`` and their guard
lives on here.) A PR that means to change a cell's program replaces that
cell's hash with what the tool prints, and says so."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HYBRID = {
    "qwen3next_gdn_train_8k_1chip":
        "623de17c7e66aa8abf2e131a79b1423ba09f6a4e9998a2c15904ae6764887bc7",
    "kimi_kda_train_8k_1chip":
        "5219123fc0f6ba4844c7b9343ab94ed46fad66a7a98d63e8b61ac994b921d754",
}
KEYE = {
    "keye_dsa_train_8k_1chip":
        "9ede3859d2d92620a44ffe867fee65cdff1e9c0eb731ed27a29027a22bded343",
}
# PR 38's cell: latent attention on every layer, its key part rotated.
LATENT = {
    "kanana2_mla_train_8k_1chip":
        "a8232a5937ea6a2f53d8eda45bfb40e5a737558a1a6afc64df91a62439610e1f",
}
# The Trinity cell: three window layers to one full layer, as it lowered
# on commit f3d735e, before the layer kinds became one table.
WINDOWED = {
    "trinity_swa_train_8k_1chip":
        "73034e664479c1331e6c529c7d007e4bc9b8339257d5f477620f180b6592a131",
}
LOWERED = {**HYBRID, **KEYE, **LATENT, **WINDOWED}


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


@pytest.mark.parametrize("cell", list(HYBRID))
def test_the_cell_holds_the_convolutions_kernels(lines, cell):
    """Three kernel bodies more than PR 35's parent had (16 in either
    cell): ``conv_silu_fwd``, the same again inside the checkpoint's
    recomputation, ``conv_silu_bwd``; the layers of a cell share them
    (``_traced_once``). And four more since PR 37: the grouped products'
    bodies are lowered once for chunk 0 and once inside the loops. One
    fewer since the flash backward at T 8192 is one kernel, not two."""
    assert lines[cell]["kernels"] == 22
