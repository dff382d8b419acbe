"""Family ``lm_moe_dsa`` in the harness: the toy configuration and traffic
that live with these tests, added AS DATA to a temporary copy of the
benchmark and rehearsed on the CPU; the family's reader on hand-made ops;
the FLOP counts against the arithmetic of ISSUE 28."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_paths import BENCH, DATA, ROOT

CELL, CONFIG, TRAFFIC = ("toy_moe_dsa_step", "toy_lm_moe_dsa",
                         "toy_step_loop_moe_dsa")
REAL_CELL = "keye_dsa_train_8k_1chip"


def _load(path):
    with open(path) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_copy_moe_dsa")
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(DATA, CONFIG + ".json"),
                root / "benchmarks" / "configs")
    shutil.copy(os.path.join(DATA, TRAFFIC + ".json"),
                root / "benchmarks" / "traffic")
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"].append({
        "name": CONFIG, "source": "tests/benchmark/data",
        "file": f"benchmarks/configs/{CONFIG}.json", "reduced": [],
        "why": "toy"})
    bench["workloads"].append({"name": CELL, "config": CONFIG,
                               "traffic": TRAFFIC, "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_cell(root, tmp, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    return subprocess.run(
        [sys.executable, str(root / "benchmarks" / "run.py"), "--workload",
         CELL, *args], cwd=root, env=env, capture_output=True, text=True,
        timeout=600)


@pytest.mark.parametrize("trace", [0, 1], ids=["end_to_end", "traced"])
def test_rehearsal_of_the_toy_cell(copy, tmp_path, trace):
    p = run_cell(copy, tmp_path, "--seed", "2400000001", "--seconds", "1",
                 "--trace", str(trace), "--rehearse")
    assert p.returncode == 3, p.stderr[-2000:]
    lines = [json.loads(l) for l in p.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    check = next(l for l in lines if l.get("event") == "reference_check")
    # float32 on the CPU: the system IS the reference up to rounding, and
    # chooses the same keys.
    assert check["mean_abs_token_err"] < 1e-5
    assert max(check["kl_rel_err"]) < 1e-4
    assert min(check["selection_overlap"]) == 1.0
    assert all(sum(load) + absent == 2 * 64 * 2 for load, absent in zip(
        check["held_load"], check["absent_assignments"]))
    if trace:
        assert {"setup.compile_s", "device.idle_pct",
                "device_step_ms.lm_moe_dsa", "mfu_pct.lm_moe_dsa",
                "moe.load_max_over_mean", "dsa.selected_pairs_pct",
                "step.compiles_in_window"} <= set(last["metrics"])
        # T 64, topk 16: sum of min(16, t + 1) over sum of (t + 1).
        assert last["metrics"]["dsa.selected_pairs_pct"]["value"] == \
            pytest.approx(100 * (136 + 48 * 16) / 2080)
        assert last["metrics"]["moe.load_max_over_mean"]["value"] >= 1.0
        # The routing load is read again after the window, from the
        # parameters the last step left: every assignment is counted.
        after = next(l for l in lines
                     if l.get("event") == "routing_after_window")
        assert all(sum(load) + absent == pytest.approx(2 * 64 * 2)
                   for load, absent in zip(after["held_load"],
                                           after["absent_assignments"]))
    else:
        assert set(last["metrics"]) == {"tokens_per_s_per_chip",
                                        "step_ms_p90", "setup_s"}


def test_the_real_cell_names_files_that_are_there():
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(c for c in bench["workloads"] if c["name"] == REAL_CELL)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _load(os.path.join(ROOT, entry["file"]))
    traffic = _load(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    assert config["family"] == "lm_moe_dsa" and traffic["driver"] == "step_loop"
    # Published widths stand; what is held here has keys of its own.
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["num_local_experts"], config["vocab_size"]) == \
        (2048, 32, 4, 128, 768, 8, 128, 151936)
    assert config["sa_config"]["topk"] == 2048
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == \
        ["num_experts", "num_hidden_layers", "vocab_rows_held"]
    assert config["num_experts"] * 8 == config["num_local_experts"]
    assert config["vocab_rows_held"] * 8 == config["vocab_size"]
    assert config["num_hidden_layers"] >= 4
    # Two departures, said aloud: no gradient to the router in a share,
    # and a warm-up before the LM cell's learning rate.
    assert len(config["departs"]) == 2 and "router" in config["departs"][0]


def test_flop_counts_follow_the_issues_arithmetic():
    from lib import flops_moe_dsa as flops
    config = _load(os.path.join(BENCH, "configs",
                                "keye_vl2_30b_a3b_ep8.json"))
    assert flops.selected_pairs_per_row(2048, 8192) == pytest.approx(1792.125)
    assert flops.selected_pairs_per_row(2048, 1024) == pytest.approx(512.5)
    layers = config["num_hidden_layers"]
    per_layer = 37.75e6 + 4.52e6 + 8.39e6 + 29.36e6 + 0.52e6 + 9.44e6
    want = 3 * (layers * per_layer + 77.79e6)
    assert flops.lm_moe_dsa_train_flop_per_token(config, 8192) == \
        pytest.approx(want, rel=2e-3)
    # Twice the assignments, one expert's three products more per token.
    more = flops.lm_moe_dsa_train_flop_per_token(config, 8192, 2.0)
    assert more - flops.lm_moe_dsa_train_flop_per_token(config, 8192, 1.0) \
        == pytest.approx(3 * layers * 6 * 2048 * 768)
    step = flops.dsa_attend_flop_per_step(config, 2, 8192)
    assert step == pytest.approx(
        layers * 3 * 2 * 8192 * 1792.125 * 4 * 32 * 128)
    # Compute binds: the bytes take a sixth of the time the FLOP take.
    assert flops.dsa_attend_bytes_per_step(config, 2, 8192) / 819e9 \
        < 0.2 * step / 197e12


def test_reader_sums_ops_by_scope_and_finds_the_kernels():
    sys.path.insert(0, BENCH)
    from layer_metrics import lm_moe_dsa as reader
    names = {
        "%a": "jit(step)/jvp(forward)/attn.indexer/dot_general",
        "%b": "jit(step)/jvp(forward)/attn.sparse/jit(_fwd)/dsa_fwd",
        "%c": "jit(step)/transpose(jvp(forward))/transpose(jvp(attn.sparse))"
              "/jit(_bwd)/dsa_bwd_dq",
        "%d": "jit(step)/jvp(forward)/attn.indexer_loss/while/body/exp",
        "%e": "jit(step)/jvp(forward)/moe.experts/ragged_dot",
        "%f": "jit(step)/optimizer/mul",
    }
    ops = [(n, 0.0, 2e6) for n in names]
    got = reader.by_scope(ops, names, steps=2)
    assert got == {"dsa.indexer_ms": 1.0, "dsa.attend_ms": 2.0,
                   "dsa.indexer_loss_ms": 1.0, "moe.experts_ms": 1.0}
    assert not reader.in_scope("jit(step)/jvp(forward)/attn.sparsely/x",
                               "attn.sparse")


@pytest.fixture(scope="module")
def toy_family():
    """The family on the toy configuration, in this process (the session's
    ``hvd.init()`` stands; the family is given its one device and builds
    its own mesh), with its seeded weights: (family, state)."""
    sys.path.insert(0, BENCH)
    import jax
    from lib.cell import Context
    from run import load_module
    ctx = Context(cell={"name": CELL, "chips": 1},
                  config=_load(os.path.join(DATA, CONFIG + ".json")),
                  traffic=_load(os.path.join(DATA, TRAFFIC + ".json")),
                  seed=2400000003, seconds=0, trace=False, rehearse=True,
                  devices=jax.devices()[:1])
    family = load_module("families", "lm_moe_dsa").build(ctx)
    return family, family.init()


WRONG_BLOCKS = {"no_renormalisation": {"moe_renormalize": False},
                "no_rope": {"rope_theta": 0.0},
                "no_qk_norm": {"qk_norm": False}}


@pytest.mark.parametrize("wrong", [None, *WRONG_BLOCKS])
def test_reference_check_passes_the_block_and_fails_a_wrong_one(
        toy_family, capsys, wrong):
    import dataclasses
    family, state = toy_family
    cfg = None if wrong is None else dataclasses.replace(
        family.cfg, **WRONG_BLOCKS[wrong])
    ok = family.reference_check(state, cfg=cfg)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["event"] == "reference_check" and line["ok"] is ok
    assert ok is (wrong is None), line
