"""Family ``lm_gdn_moe`` in the harness: the toy configuration and traffic
that live with these tests, added AS DATA to a temporary copy of the
benchmark and rehearsed on the CPU; the family's reader on hand-made ops;
the FLOP and byte counts against hand counts; the real cell's files."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_paths import BENCH, DATA, ROOT

CELL, CONFIG, TRAFFIC = ("toy_gdn_moe_step", "toy_lm_gdn_moe",
                         "toy_step_loop_gdn_moe")
REAL_CELL = "qwen3next_gdn_train_8k_1chip"
REAL_CONFIG = "qwen3_next_80b_a3b_ep32"


def _load(path):
    with open(path) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_copy_gdn_moe")
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
        timeout=900)


def _cell_metrics(bench):
    return {m["name"] for m in bench["per_layer"]
            if REAL_CELL in m.get("workloads", [REAL_CELL])}


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
    # routes every token as the reference does.
    assert check["mean_abs_token_err"] < 1e-5
    assert max(check["gdn_o_rel_err"]) < 1e-4 and \
        len(check["gdn_o_rel_err"]) == 3
    assert min(check["routing_overlap"]) == 1.0
    assert all(sum(load) + absent == 2 * 64 * 2 for load, absent in zip(
        check["held_load"], check["absent_assignments"]))
    if trace:
        # Every per-layer metric BENCHMARK.json lists for the real cell that
        # a CPU's trace can give: its ops carry no framework name, so what
        # is split by named scope is read on the chip alone.
        wanted = _cell_metrics(_load(os.path.join(ROOT, "BENCHMARK.json")))
        by_scope = {m for m in wanted if m.startswith((
            "device_step.", "gdn.proj", "gdn.conv", "gdn.scan", "gdn.out",
            "attn.full", "moe.shared", "moe.route", "moe.experts",
            "gdn_scan_roofline"))}
        assert wanted - by_scope <= set(last["metrics"]), \
            wanted - by_scope - set(last["metrics"])
        assert {"setup.compile_s", "device.idle_pct",
                "device_step_ms.lm_gdn_moe", "mfu_pct.lm_gdn_moe",
                "moe.load_max_over_mean", "gdn.saved_state_mb",
                "step.compiles_in_window"} <= wanted - by_scope
        assert last["metrics"]["moe.load_max_over_mean"]["value"] >= 1.0
        # Three layers' calls: q, k [2, 64, 2, 16], v [2, 64, 4, 16] and a
        # [16, 16] state a chunk of 16 and value head, float32; g, beta
        # and a row of the [16, 16] solve a row and value head.
        call = 128 * (2 * 32 + 64) * 4 + 128 * 4 * 18 * 4 + 8 * 4 * 256 * 4
        assert last["metrics"]["gdn.saved_state_mb"]["value"] == \
            pytest.approx(3 * call / 1e6)
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
    assert cell["chips"] == 1 and cell["traffic"] == "step_loop_gdn_8k"
    config = _load(os.path.join(ROOT, entry["file"]))
    traffic = _load(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    assert config["family"] == "lm_gdn_moe" \
        and traffic["driver"] == "step_loop"
    assert (traffic["batch_per_chip"], traffic["seq_len"], traffic["pool"],
            traffic["warmup_steps"]) == (2, 8192, 4, 3)
    # Published widths stand; what is held here has keys of its own.
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["linear_num_key_heads"], config["linear_num_value_heads"],
            config["linear_key_head_dim"], config["linear_value_head_dim"],
            config["linear_conv_kernel_dim"], config["moe_intermediate_size"],
            config["shared_expert_intermediate_size"],
            config["num_experts_per_tok"], config["router_experts"],
            config["partial_rotary_factor"], config["rope_theta"],
            config["full_attention_interval"], config["vocab_size"]) == \
        (2048, 16, 2, 256, 16, 32, 128, 128, 4, 512, 512, 10, 512, 0.25,
         10000000, 4, 151936)
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == \
        ["num_experts", "num_hidden_layers", "vocab_rows_held"]
    assert config["num_experts"] * 32 == config["router_experts"]
    assert config["vocab_rows_held"] * 8 == config["vocab_size"]
    # One whole period of the layer pattern.
    assert config["num_hidden_layers"] == config["full_attention_interval"]
    assert len(config["departs"]) == 2 and "router" in config["departs"][0]


def test_model_config_follows_the_published_pattern():
    sys.path.insert(0, BENCH)
    from run import load_module
    from horovod_tpu.parallel.transformer import layer_kind
    config = _load(os.path.join(BENCH, "configs", REAL_CONFIG + ".json"))
    cfg = load_module("families", "lm_gdn_moe").model_config(
        dict(config, num_hidden_layers=48))
    kinds = [layer_kind(cfg, i) for i in range(48)]
    assert all((kind == "attn") == ((i + 1) % 4 == 0)
               for i, kind in enumerate(kinds))
    assert kinds.count("gdn") == 36
    assert (cfg.n_experts, cfg.experts_held, cfg.moe_top_k,
            cfg.shared_expert_ff, cfg.rope_fraction) == (512, 16, 10, 512,
                                                         0.25)


def test_flop_and_byte_counts_against_hand_counts():
    from lib import flops_gdn_moe as flops
    config = _load(os.path.join(BENCH, "configs", REAL_CONFIG + ".json"))
    assert flops.layer_counts(config) == (3, 1)
    assert flops.layer_counts(dict(config, num_hidden_layers=48)) == (36, 12)
    # The recurrence: 32 heads x 7 x 128 x 128 a token and layer.
    assert flops.gdn_rule_flop_per_token(config) == 32 * 7 * 16384
    assert flops.gdn_rule_flop_per_step(config, 2, 8192) == \
        3 * 3 * 16384 * 32 * 7 * 16384
    # A token's row, bf16: q, k 2 x 2048, v and o 4096 each, g and beta
    # 32 float32 each: in 16640, out 8192; forward in + out, backward the
    # same again and the inputs' gradients.
    assert flops.gdn_rule_bytes_per_step(config, 2, 8192) == \
        3 * 16384 * (2 * (16640 + 8192) + 16640)
    # The bytes bind: 4.4 ms a step against the FLOP's 2.7.
    assert flops.gdn_rule_bytes_per_step(config, 2, 8192) / 819e9 > \
        flops.gdn_rule_flop_per_step(config, 2, 8192) / 197e12
    # Forward FLOP a token, by hand (ISSUE 32's table): a DeltaNet layer's
    # projections 2 x (25.17 M + 0.13 M + 8.39 M), convolution 65.5 k, rule
    # 3.67 M; the attention layer's projections 2 x 27.26 M and 8192.5 keys
    # a row x 4 x 4096; every layer's router 2.10 M, shared expert 6.30 M,
    # 10 x 16 / 512 assignments x 6.29 M; the head 77.79 M.
    gdn = 2 * (25.166e6 + 0.131e6 + 8.389e6) + 65.5e3 + 3.670e6
    full = 2 * 27.263e6 + 4 * 4096 * 4096.5
    experts = 2.097e6 + 6.296e6 + 0.3125 * 6.291e6
    want = 3 * (3 * gdn + full + 4 * experts + 77.791e6)
    assert flops.lm_gdn_moe_train_flop_per_token(config, 8192) == \
        pytest.approx(want, rel=1e-3)
    more = flops.lm_gdn_moe_train_flop_per_token(config, 8192, 2.0)
    assert more - flops.lm_gdn_moe_train_flop_per_token(config, 8192, 1.0) \
        == pytest.approx(3 * 4 * 6 * 2048 * 512)


def test_reader_sums_ops_by_scope_and_reads_one_work_for_any_backend():
    sys.path.insert(0, BENCH)
    from layer_metrics import lm_gdn_moe as reader
    names = {
        "%a": "jit(step)/jvp(forward)/gdn.proj/dot_general",
        "%b": "jit(step)/jvp(forward)/gdn.scan/jit(_rule)/gdn_fwd",
        "%c": "jit(step)/transpose(jvp(forward))/gdn.scan/while/body/dot",
        "%d": "jit(step)/jvp(forward)/checkpoint/gdn.conv/mul",
        "%e": "jit(step)/jvp(forward)/attn.full/flash_fwd",
        "%f": "jit(step)/jvp(forward)/moe.shared/dot_general",
        "%g": "jit(step)/optimizer/mul",
    }
    ops = [(n, 0.0, 2e6) for n in names]
    assert reader.by_scope(ops, names, steps=2) == {
        "gdn.proj": 1.0, "gdn.scan": 2.0, "gdn.conv": 1.0, "attn.full": 1.0,
        "moe.shared": 1.0}
    # Nothing for another family.
    assert reader.read(None, {}, {"config": {"family": "lm"}}) == {}


@pytest.fixture(scope="module")
def toy_family():
    """The family on the toy configuration, in this process, with its
    seeded weights: (family, state)."""
    sys.path.insert(0, BENCH)
    import jax
    from lib.cell import Context
    from run import load_module
    config = _load(os.path.join(DATA, CONFIG + ".json"))
    # The XLA forms: the interpreter's kernels are the rehearsal's.
    config["training"].update(attn_backend="xla", gdn_backend="xla")
    ctx = Context(cell={"name": CELL, "chips": 1}, config=config,
                  traffic=_load(os.path.join(DATA, TRAFFIC + ".json")),
                  seed=2400000003, seconds=0, trace=False, rehearse=True,
                  devices=jax.devices()[:1])
    family = load_module("families", "lm_gdn_moe").build(ctx)
    return family, family.init()


WRONG_BLOCKS = {"no_renormalisation": {"moe_renormalize": False},
                "whole_rope": {"rope_fraction": 1.0},
                "no_output_gate_norm_offset": {"norm_offset": False}}
WRONG_RULES = ("no_l2_norm", "no_decay")


@pytest.mark.parametrize("wrong", [None, *WRONG_BLOCKS, *WRONG_RULES])
def test_reference_check_passes_the_block_and_fails_a_wrong_one(
        toy_family, capsys, monkeypatch, wrong):
    import dataclasses
    from horovod_tpu.ops import gated_delta
    from horovod_tpu.parallel import transformer
    family, state = toy_family
    cfg = None if wrong is None else dataclasses.replace(
        family.cfg, **WRONG_BLOCKS.get(wrong, {}))
    if wrong == "no_l2_norm":
        monkeypatch.setattr(transformer, "_l2_norm", lambda x: x)
    elif wrong == "no_decay":
        rule = gated_delta.gated_delta_rule
        monkeypatch.setattr(
            gated_delta, "gated_delta_rule",
            lambda q, k, v, g, beta, **kw: rule(q, k, v, g * 0, beta, **kw))
    ok = family.reference_check(state, cfg=cfg)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["event"] == "reference_check" and line["ok"] is ok
    assert ok is (wrong is None), line
