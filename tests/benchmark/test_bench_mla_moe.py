"""Family ``lm_mla_moe`` in the harness: the toy configuration and traffic
that live with these tests, added AS DATA to a temporary copy of the
benchmark and rehearsed on the CPU; the family's reader on hand-made ops;
the FLOP and byte counts against hand counts; the real cell's files and
entries; the check's controls."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_paths import BENCH, DATA, ROOT

CELL, CONFIG, TRAFFIC = ("toy_mla_moe_step", "toy_lm_mla_moe",
                         "toy_step_loop_mla_moe")
REAL_CELL = "kanana2_mla_train_8k_1chip"
REAL_CONFIG = "kanana2_30b_a3b_ep8"


def _load(path):
    with open(path) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_copy_mla_moe")
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
    p = run_cell(copy, tmp_path, "--seed", "3800000001", "--seconds", "1",
                 "--trace", str(trace), "--rehearse")
    assert p.returncode == 3, p.stderr[-2000:]
    lines = [json.loads(l) for l in p.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    check = next(l for l in lines if l.get("event") == "reference_check")
    # float32 on the CPU: the system IS the reference up to rounding, and
    # routes every token as the reference does.
    assert check["mean_abs_token_err"] < 1e-5
    assert max(check["mla_o_rel_err"]) < 1e-4 and \
        len(check["mla_o_rel_err"]) == 5
    assert min(check["routing_overlap"]) == 1.0
    # The backward of the first layer's attention call (the kernels,
    # interpreted), from the same inputs.
    assert set(check["attend_grad_rel_err"]) == {"q", "k", "v"} \
        and max(check["attend_grad_rel_err"].values()) < 1e-4
    # Four expert layers (the first layer is dense): 128 tokens x top-3.
    assert len(check["held_load"]) == 4
    assert all(sum(load) + absent == 128 * 3 for load, absent in zip(
        check["held_load"], check["absent_assignments"]))
    compiled = next(l for l in lines if l.get("event") == "compiled_step")
    assert "score_arrays" in compiled and "kda_local_kernels" not in compiled
    if trace:
        # Every per-layer metric BENCHMARK.json lists for the real cell that
        # a CPU's trace can give: its ops carry no framework name, so what
        # is split by named scope is read on the chip alone.
        wanted = _cell_metrics(_load(os.path.join(ROOT, "BENCHMARK.json")))
        by_scope = {m for m in wanted if m.startswith((
            "device_step.", "mla.rope", "attn.mla", "ffn.dense",
            "moe.shared", "moe.route", "moe.experts",
            "mla_attend_roofline"))}
        assert wanted - by_scope <= set(last["metrics"]), \
            wanted - by_scope - set(last["metrics"])
        assert {"setup.compile_s", "device.idle_pct",
                "device_step_ms.lm_mla_moe", "mfu_pct.lm_mla_moe",
                "moe.load_max_over_mean",
                "step.compiles_in_window"} <= wanted - by_scope
        assert last["metrics"]["moe.load_max_over_mean"]["value"] >= 1.0
        assert not any(k.startswith("kda") for k in last["metrics"])
        after = next(l for l in lines
                     if l.get("event") == "routing_after_window")
        assert all(sum(load) + absent == pytest.approx(128 * 3)
                   for load, absent in zip(after["held_load"],
                                           after["absent_assignments"]))
    else:
        assert set(last["metrics"]) == {"tokens_per_s_per_chip",
                                        "step_ms_p90", "setup_s"}


def test_the_real_cell_names_files_that_are_there():
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(c for c in bench["workloads"] if c["name"] == REAL_CELL)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "step_loop_mla_8k"
    config = _load(os.path.join(ROOT, entry["file"]))
    traffic = _load(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    assert config["family"] == "lm_mla_moe" \
        and traffic["driver"] == "step_loop"
    assert (traffic["batch_per_chip"], traffic["seq_len"], traffic["pool"],
            traffic["warmup_steps"], traffic["reference_sequences"],
            traffic["mesh"]) == (1, 8192, 4, 3, 1, {"dp": 1})
    # Published widths stand; what is held here has keys of its own.
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["qk_head_dim"], config["v_head_dim"],
            config["num_attention_heads"], config["num_experts_per_tok"],
            config["router_experts"], config["n_shared_experts"],
            config["routed_scaling_factor"], config["rms_norm_eps"],
            config["rope_theta"], config["rope_interleave"],
            config["first_k_dense_replace"], config["vocab_size"],
            config["max_position_embeddings"]) == \
        (2048, 6144, 768, 512, 128, 64, 192, 128, 32, 6, 128, 2, 2.448,
         1e-6, 1000000, True, 1, 128256, 32768)
    assert config["q_lora_rank"] is None and config["rope_scaling"] is None
    assert (config["scoring_func"], config["topk_method"]) == (
        "sigmoid", "noaux_tc")
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == \
        ["n_routed_experts", "num_hidden_layers", "vocab_rows_held"]
    assert config["n_routed_experts"] * 8 == config["router_experts"]
    assert config["vocab_rows_held"] * 8 == config["vocab_size"]
    assert config["num_hidden_layers"] == 5
    assert "ep = 8" in config["deployment"]
    assert len(config["departs"]) == 2 and "router" in config["departs"][0]
    assert "selection bias" in config["assumed"]["not_built"]
    assert "rope_layout" in config["assumed"]


# This PR's: name -> (unit, better, source, layer).
ADDED_PER_LAYER = {
    "device_step_ms.lm_mla_moe": ("ms", "lower", "device_trace",
                                  "step builders"),
    "mfu_pct.lm_mla_moe": ("%", "higher", "host_clock", "step builders"),
    "mla.rope_ms": ("ms", "lower", "device_trace", "attention"),
}
# What the benchmark had before PR 38, in its order (the cells).
HAD_CELLS = ["lm_step_1chip", "resnet50_fit_1chip", "lm_dp4_4chip",
             "keye_dsa_train_8k_1chip", "qwen3next_gdn_train_8k_1chip",
             "kimi_kda_train_8k_1chip"]


def test_entries_of_this_cell():
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[:len(HAD_CELLS)] == HAD_CELLS and REAL_CELL in cells
    assert REAL_CONFIG in [c["name"] for c in bench["configs"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, content in ADDED_PER_LAYER.items():
        m = entries[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == content
        assert m["moves"] == "tokens_per_s_per_chip" \
            and m["workloads"] == [REAL_CELL]
    # The shared metrics ISSUE 38 names list the cell.
    joined = ["step.compiles_in_window", "device_step.forward_ms",
              "device_step.backward_ms", "device_step.optimizer_ms",
              "device_step.unscoped_ms", "attn.mla_ms",
              "mla_attend_roofline", "ffn.dense_ms", "moe.route_ms",
              "moe.experts_ms", "moe.shared_ms", "moe.load_max_over_mean"]
    assert all(REAL_CELL in entries[n]["workloads"] for n in joined)
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "tokens_per_s_per_chip")
    assert REAL_CELL in rate["workloads"]


def test_model_config_and_the_parameters_the_file_counts():
    sys.path.insert(0, BENCH)
    from run import load_module
    from horovod_tpu.parallel.transformer import layer_kind
    family = load_module("families", "lm_mla_moe")
    config = _load(os.path.join(BENCH, "configs", REAL_CONFIG + ".json"))
    cfg = family.model_config(config)
    assert [layer_kind(cfg, i) for i in range(5)] == ["mla"] * 5
    assert (cfg.dense_layers, cfg.dense_ff, cfg.n_experts, cfg.experts_held,
            cfg.moe_top_k, cfg.moe_score, cfg.moe_select_bias, cfg.moe_scale,
            cfg.moe_renormalize, cfg.shared_expert_ff, cfg.shared_expert_gate,
            cfg.norm_eps, cfg.vocab) == (
        1, 6144, 128, 16, 6, "sigmoid", True, 2.448, True, 1536, False,
        1e-6, 16032)
    assert (cfg.mla.kv_rank, cfg.mla.d_nope, cfg.mla.d_shared, cfg.mla.d_v,
            cfg.mla.rope_theta) == (512, 128, 64, 128, 1e6)
    with pytest.raises(ValueError, match="one projection"):
        family.model_config(dict(config, q_lora_rank=1536))
    # 576.0 M parameters, as the configuration's file says.
    import jax
    from horovod_tpu.parallel.transformer import init_params
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == pytest.approx(575.97e6, rel=1e-4)


def test_flop_and_byte_counts_against_hand_counts():
    from lib import flops_mla_moe as flops
    config = _load(os.path.join(BENCH, "configs", REAL_CONFIG + ".json"))
    # Causal attention at 192 + 128, 32 heads, 8192 x 8193 / 2 pairs, five
    # layers; forward and twice that backward.
    pairs = 8192 * 8193 / 2
    assert flops.mla_attend_flop_per_step(config, 1, 8192) == \
        5 * 3 * 32 * pairs * 2 * 320
    assert flops.mla_attend_bytes_per_step(config, 1, 8192) == \
        5 * 8192 * 32 * 2 * ((384 + 256) + (384 + 384) + (384 + 128))
    # Compute binds: 52.3 ms of FLOP against 6.1 ms of bytes.
    assert flops.mla_attend_flop_per_step(config, 1, 8192) / 197e12 > \
        flops.mla_attend_bytes_per_step(config, 1, 8192) / 819e9
    # Forward FLOP a token, by hand: a layer's projections 2 x (12.58 M +
    # 1.18 M + 4.19 M + 8.39 M) and 4096.5 keys a row x 2 x 320 x 32; the
    # dense layer 6 x 12.58 M; every other layer's router 0.26 M x 2,
    # shared experts 6 x 3.15 M, 6 x 16 / 128 assignments x 6 x 1.57 M;
    # the head 2 x 32.83 M.
    mla = 2 * (12.583e6 + 1.1796e6 + 4.1943e6 + 8.3886e6) \
        + 4096.5 * 2 * 320 * 32
    experts = 2 * 0.26214e6 + 6 * 3.1457e6 + 0.75 * 6 * 1.5729e6
    want = 3 * (5 * mla + 6 * 12.583e6 + 4 * experts + 2 * 32.834e6)
    assert flops.lm_mla_moe_train_flop_per_token(config, 8192) == \
        pytest.approx(want, rel=1e-3)
    more = flops.lm_mla_moe_train_flop_per_token(config, 8192, 2.0)
    assert more - flops.lm_mla_moe_train_flop_per_token(
        config, 8192, 1.0) == pytest.approx(3 * 4 * 6 * 2048 * 768)


def test_reader_sums_ops_by_scope_and_finds_the_flash_kernels():
    sys.path.insert(0, BENCH)
    from layer_metrics import lm_mla_moe as reader
    names = {
        "%a": "jit(step)/jvp(forward)/attn.mla/dot_general",
        "%b": "jit(step)/jvp(forward)/attn.mla/mla.rope/mul",
        "%c": "jit(step)/transpose(jvp(forward))/attn.mla/mla.rope/add",
        "%flash_fwd.2": "jit(step)/jvp(forward)/attn.mla/flash_fwd",
        "%flash_bwd_dkv.1": "jit(step)/transpose(jvp(forward))/attn.mla/x",
        "%d": "jit(step)/jvp(forward)/ffn.dense/dot_general",
        "%e": "jit(step)/jvp(forward)/moe.shared/dot_general",
        "%g": "jit(step)/optimizer/mul",
        "%h": "jit(step)/jvp(forward)/attn.mla.rope/mul",
    }
    ops = [(n, 0.0, 2e6) for n in names]
    assert reader.by_scope(ops, names, steps=2) == {
        "mla.rope": 2.0, "attn.mla": 5.0, "ffn.dense": 1.0,
        "moe.shared": 1.0, "flash": 2.0}
    # Nothing for another family.
    assert reader.read(None, {}, {"config": {"family": "lm_kda_mla_moe"}}) \
        == {}


@pytest.fixture(scope="module")
def toy_family():
    """The family on the toy configuration, in this process, with its
    seeded weights: (family, state)."""
    sys.path.insert(0, BENCH)
    import jax
    from lib.cell import Context
    from run import load_module
    config = _load(os.path.join(DATA, CONFIG + ".json"))
    # The XLA form: the interpreter's kernels are the rehearsal's.
    config["training"].update(attn_backend="xla")
    ctx = Context(cell={"name": CELL, "chips": 1}, config=config,
                  traffic=_load(os.path.join(DATA, TRAFFIC + ".json")),
                  seed=3800000003, seconds=0, trace=False, rehearse=True,
                  devices=jax.devices()[:1])
    family = load_module("families", "lm_mla_moe").build(ctx)
    return family, family.init()


CONTROLS = ("no_rotation", "wrong_pairs", "theta_1e4", "key_part_unrotated",
            "key_part_without_gradient", "fp8_operands", "fp8_norm_outputs")


@pytest.mark.parametrize("wrong", [None, *CONTROLS])
def test_reference_check_passes_the_block_and_fails_a_wrong_one(
        toy_family, capsys, wrong):
    """Every control of ``mla_moe_controls.py`` (the chip's readings of the
    same set the limits) comes out not correct by the check itself; the
    one whose forward is the block's, by the gradients alone."""
    import mla_moe_controls as controls
    family, state = toy_family
    assert set(CONTROLS) == set(controls.controls(family.cfg))
    with controls.in_place(family, wrong) as cfg:
        ok = family.reference_check(state, cfg=cfg)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["event"] == "reference_check" and line["ok"] is ok
    assert ok is (wrong is None), line
    forward = (line["mean_abs_token_err"] <= line["tol_mean_abs_token"]
               and max(line["mla_o_rel_err"]) <= line["tol_mla_o_rel"])
    attend = max(line["attend_grad_rel_err"].values())
    if wrong is None:
        assert attend < 1e-4
    elif wrong == "key_part_without_gradient":
        assert forward and line["attend_grad_rel_err"]["q"] > 0.1
    else:
        assert not forward, line
