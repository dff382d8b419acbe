"""Family ``lm_kda_mla_moe`` in the harness: the toy configuration and
traffic that live with these tests, added AS DATA to a temporary copy of
the benchmark and rehearsed on the CPU; the family's reader on hand-made
ops; the FLOP and byte counts against hand counts; the real cell's files."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_paths import BENCH, DATA, ROOT

CELL, CONFIG, TRAFFIC = ("toy_kda_mla_moe_step", "toy_lm_kda_mla_moe",
                         "toy_step_loop_kda_mla_moe")
REAL_CELL = "kimi_kda_train_8k_1chip"
REAL_CONFIG = "kimi_linear_48b_a3b_ep32"


def _load(path):
    with open(path) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_copy_kda_mla_moe")
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
    assert max(check["kda_o_rel_err"]) < 1e-4 and \
        len(check["kda_o_rel_err"]) == 4
    assert max(check["mla_o_rel_err"]) < 1e-4 and \
        len(check["mla_o_rel_err"]) == 1
    assert min(check["routing_overlap"]) == 1.0
    # The backward of the first delta-attention layer's rule (the kernels,
    # interpreted) and of the latent attention, from the same inputs.
    assert set(check["rule_grad_rel_err"]) == {"q", "k", "v", "g", "beta"} \
        and max(check["rule_grad_rel_err"].values()) < 1e-4
    assert set(check["attend_grad_rel_err"]) == {"q", "k", "v"} \
        and max(check["attend_grad_rel_err"].values()) < 1e-4
    # Four expert layers (the first layer is dense): 128 tokens x top-2.
    assert len(check["held_load"]) == 4
    assert all(sum(load) + absent == 128 * 2 for load, absent in zip(
        check["held_load"], check["absent_assignments"]))
    # (Arrays [.., heads, T, T] are the chip's to refuse: the interpreter's
    # kernels are full of them.)
    compiled = next(l for l in lines if l.get("event") == "compiled_step")
    assert compiled["kda_backend"] == "pallas" and "score_arrays" in compiled
    if trace:
        # Every per-layer metric BENCHMARK.json lists for the real cell that
        # a CPU's trace can give: its ops carry no framework name, so what
        # is split by named scope is read on the chip alone.
        wanted = _cell_metrics(_load(os.path.join(ROOT, "BENCHMARK.json")))
        by_scope = {m for m in wanted if m.startswith((
            "device_step.", "kda.proj", "kda.conv", "kda.scan", "kda.out",
            "attn.mla", "ffn.dense", "moe.shared", "moe.route",
            "moe.experts", "kda_scan_roofline", "mla_attend_roofline"))}
        assert wanted - by_scope <= set(last["metrics"]), \
            wanted - by_scope - set(last["metrics"])
        assert {"setup.compile_s", "device.idle_pct",
                "device_step_ms.lm_kda_mla_moe", "mfu_pct.lm_kda_mla_moe",
                "moe.load_max_over_mean", "kda.saved_state_mb",
                "step.compiles_in_window"} <= wanted - by_scope
        assert last["metrics"]["moe.load_max_over_mean"]["value"] >= 1.0
        # Four layers' calls: q, k, v [1, 128, 2, 16] and a [16, 16] state
        # a chunk of 16 and head, float32; g (16), beta and a row of the
        # [16, 16] solve a row and head.
        call = 128 * 2 * 48 * 4 + 128 * 2 * 33 * 4 + 8 * 2 * 256 * 4
        assert last["metrics"]["kda.saved_state_mb"]["value"] == \
            pytest.approx(4 * call / 1e6)
        after = next(l for l in lines
                     if l.get("event") == "routing_after_window")
        assert all(sum(load) + absent == pytest.approx(128 * 2)
                   for load, absent in zip(after["held_load"],
                                           after["absent_assignments"]))
    else:
        assert set(last["metrics"]) == {"tokens_per_s_per_chip",
                                        "step_ms_p90", "setup_s"}


def test_the_real_cell_names_files_that_are_there():
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(c for c in bench["workloads"] if c["name"] == REAL_CELL)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "step_loop_kda_8k"
    # Six cells with this one; a quarter of them at most, or one, on four.
    assert len(bench["workloads"]) >= 6 \
        and sum(c["chips"] == 4 for c in bench["workloads"]) <= max(
            1, len(bench["workloads"]) // 4)
    config = _load(os.path.join(ROOT, entry["file"]))
    traffic = _load(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    assert config["family"] == "lm_kda_mla_moe" \
        and traffic["driver"] == "step_loop"
    assert (traffic["batch_per_chip"], traffic["seq_len"], traffic["pool"],
            traffic["warmup_steps"], traffic["reference_sequences"]) == \
        (1, 8192, 4, 3, 1)
    # Published widths stand; what is held here has keys of its own.
    lin = config["linear_attn_config"]
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["num_attention_heads"],
            lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"],
            config["num_experts_per_token"], config["router_experts"],
            config["routed_scaling_factor"], config["rms_norm_eps"],
            config["first_k_dense_replace"], config["vocab_size"]) == \
        (2304, 9216, 1024, 512, 128, 64, 128, 32, 32, 128, 4, 8, 256, 2.446,
         1e-5, 1, 163840)
    assert lin["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27] \
        and len(lin["kda_layers"]) == 20
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == \
        ["num_experts", "num_hidden_layers", "vocab_rows_held"]
    assert config["num_experts"] * 32 == config["router_experts"]
    assert config["vocab_rows_held"] * 8 == config["vocab_size"]
    assert config["num_hidden_layers"] == 5
    assert len(config["departs"]) == 2 and "router" in config["departs"][0]
    assert "selection bias" in config["assumed"]["not_built"]


# What the benchmark had before PR 34, in its order.
HAD_PER_LAYER = (
    "setup.compile_s fit.input_wait_ms fit.dispatch_ms device_step_ms.resnet "
    "device_step_ms.lm mfu_pct.lm flash_attn.ms_per_step flash_attn_roofline "
    "device.idle_pct fit.next_batch_ms fit.train_step_ms fit.self_ms "
    "input.source_ms input.h2d_ms input.queue_depth step.dispatch_ms "
    "step.compiles_in_window device_step.forward_ms device_step.backward_ms "
    "device_step.optimizer_ms device_step.unscoped_ms "
    "flash_attn.fwd_ms_per_step flash_attn.bwd_ms_per_step "
    "device.idle_input_pct device.idle_host_pct allreduce.ms_per_step "
    "allreduce.exposed_ms device_step_ms.lm_moe_dsa mfu_pct.lm_moe_dsa "
    "dsa.indexer_ms dsa.select_ms dsa.attend_ms dsa.indexer_loss_ms "
    "moe.route_ms moe.experts_ms dsa_attend_roofline moe.load_max_over_mean "
    "dsa.selected_pairs_pct device_step_ms.lm_gdn_moe mfu_pct.lm_gdn_moe "
    "gdn.proj_ms gdn.conv_ms gdn.scan_ms gdn.out_ms attn.full_ms "
    "moe.shared_ms gdn_scan_roofline gdn.saved_state_mb gdn.scan_walk_ms "
    "gdn.scan_local_ms").split()
HAD_CELLS = ["lm_step_1chip", "resnet50_fit_1chip", "lm_dp4_4chip",
             "keye_dsa_train_8k_1chip", "qwen3next_gdn_train_8k_1chip"]
# This PR's: name -> (unit, better, source, layer).
ADDED_PER_LAYER = {
    "device_step_ms.lm_kda_mla_moe": ("ms", "lower", "device_trace",
                                      "step builders"),
    "mfu_pct.lm_kda_mla_moe": ("%", "higher", "host_clock", "step builders"),
    "kda.proj_ms": ("ms", "lower", "device_trace", "linear attention"),
    "kda.conv_ms": ("ms", "lower", "device_trace", "linear attention"),
    "kda.scan_ms": ("ms", "lower", "device_trace", "linear attention"),
    "kda.out_ms": ("ms", "lower", "device_trace", "linear attention"),
    "attn.mla_ms": ("ms", "lower", "device_trace", "attention"),
    "ffn.dense_ms": ("ms", "lower", "device_trace", "step builders"),
    "kda_scan_roofline": ("%", "higher", "device_trace", "kernels"),
    "mla_attend_roofline": ("%", "higher", "device_trace", "kernels"),
    "kda.saved_state_mb": ("MB", "lower", "program_counter",
                           "linear attention"),
}


def test_entries_of_this_cell_and_the_order_of_what_the_benchmark_had():
    """This PR's entries by name and content, wherever later PRs put
    theirs; what the benchmark had before it keeps its order."""
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    names = [m["name"] for m in bench["per_layer"]]
    assert [n for n in names if n in HAD_PER_LAYER] == HAD_PER_LAYER
    cells = [w["name"] for w in bench["workloads"]]
    assert [c for c in cells if c in HAD_CELLS] == HAD_CELLS \
        and REAL_CELL in cells
    assert REAL_CONFIG in [c["name"] for c in bench["configs"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, content in ADDED_PER_LAYER.items():
        m = entries[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == content
        assert m["moves"] == "tokens_per_s_per_chip" \
            and REAL_CELL in m["workloads"]
    # The lists ISSUE 34 section 4 names.
    joined = ["step.compiles_in_window", "device_step.forward_ms",
              "device_step.backward_ms", "device_step.optimizer_ms",
              "device_step.unscoped_ms", "moe.route_ms", "moe.experts_ms",
              "moe.shared_ms", "moe.load_max_over_mean"]
    assert all(REAL_CELL in entries[n]["workloads"] for n in joined)
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "tokens_per_s_per_chip")
    assert REAL_CELL in rate["workloads"]


def test_model_config_follows_the_published_lists():
    sys.path.insert(0, BENCH)
    from run import load_module
    from horovod_tpu.parallel.transformer import layer_kind
    family = load_module("families", "lm_kda_mla_moe")
    config = _load(os.path.join(BENCH, "configs", REAL_CONFIG + ".json"))
    cfg = family.model_config(config)
    assert [layer_kind(cfg, i) for i in range(5)] == [
        "kda", "kda", "kda", "mla", "kda"]
    assert (cfg.dense_layers, cfg.dense_ff, cfg.n_experts, cfg.experts_held,
            cfg.moe_top_k, cfg.moe_score, cfg.moe_select_bias, cfg.moe_scale,
            cfg.shared_expert_ff, cfg.shared_expert_gate, cfg.norm_eps) == (
        1, 9216, 256, 8, 8, "sigmoid", True, 2.446, 1024, False, 1e-5)
    assert (cfg.kda.n_heads, cfg.kda.d_head, cfg.kda.conv_width) == (
        32, 128, 4)
    assert (cfg.mla.kv_rank, cfg.mla.d_nope, cfg.mla.d_shared,
            cfg.mla.d_v) == (512, 128, 64, 128)
    # The whole model: 20 delta-attention layers to 7 of latent attention,
    # the last of them out of period.
    kinds = family.layer_kinds(dict(config, num_hidden_layers=27))
    assert kinds.count("kda") == 20 and kinds.count("mla") == 7
    assert kinds[26] == "mla" and kinds[25] == "kda"
    # 602 M parameters, as the configuration's file says.
    import jax
    from horovod_tpu.parallel.transformer import init_params
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == pytest.approx(602.4e6, rel=2e-3)


def test_flop_and_byte_counts_against_hand_counts():
    from lib import flops_kda_mla_moe as flops
    config = _load(os.path.join(BENCH, "configs", REAL_CONFIG + ".json"))
    assert flops.layer_counts(config) == (4, 1)
    assert flops.layer_counts(dict(config, num_hidden_layers=27)) == (20, 7)
    # The recurrence: 32 heads x 7 x 128 x 128 a token and layer.
    assert flops.kda_rule_flop_per_token(config) == 32 * 7 * 16384
    assert flops.kda_rule_flop_per_step(config, 1, 8192) == \
        4 * 3 * 8192 * 32 * 7 * 16384
    # A token's row, bf16: q, k, v and o 4096 x 2 each, g 4096 float32,
    # beta 32 float32: in 41088, out 8192; forward in + out, backward the
    # same again and the inputs' gradients.
    assert flops.kda_rule_bytes_per_step(config, 1, 8192) == \
        4 * 8192 * (2 * (41088 + 8192) + 41088)
    # The bytes bind: 5.6 ms a step against the FLOP's 1.8.
    assert flops.kda_rule_bytes_per_step(config, 1, 8192) / 819e9 > \
        flops.kda_rule_flop_per_step(config, 1, 8192) / 197e12
    # Causal attention at 192 + 128, 32 heads, 8192 x 8193 / 2 pairs.
    pairs = 8192 * 8193 / 2
    assert flops.mla_attend_flop_per_step(config, 1, 8192) == \
        3 * 32 * pairs * 2 * 320
    assert flops.mla_attend_bytes_per_step(config, 1, 8192) == \
        8192 * 32 * 2 * ((384 + 256) + (384 + 384) + (384 + 128))
    # Forward FLOP a token, by hand: a delta-attention layer's projections
    # 2 x (28.31 M + 1.64 M + 0.07 M + 9.44 M), convolution 98 k, rule
    # 3.67 M; the latent layer's projections 2 x 29.10 M and 4096.5 keys a
    # row x 2 x 320 x 32; the dense layer 6 x 21.23 M; every other layer's
    # router 1.18 M, shared expert 14.16 M, 8 x 8 / 256 assignments x
    # 14.16 M; the head 94.37 M.
    kda = 2 * (28.311e6 + 1.6384e6 + 0.0737e6 + 9.437e6) + 98.3e3 + 3.670e6
    mla = 2 * 29.098e6 + 4096.5 * 2 * 320 * 32
    experts = 1.180e6 + 14.156e6 + 0.25 * 14.156e6
    want = 3 * (4 * kda + mla + 127.40e6 + 4 * experts + 94.372e6)
    assert flops.lm_kda_mla_moe_train_flop_per_token(config, 8192) == \
        pytest.approx(want, rel=1e-3)
    more = flops.lm_kda_mla_moe_train_flop_per_token(config, 8192, 2.0)
    assert more - flops.lm_kda_mla_moe_train_flop_per_token(
        config, 8192, 1.0) == pytest.approx(3 * 4 * 6 * 2304 * 1024)


def test_reader_sums_ops_by_scope_and_finds_the_flash_kernels():
    sys.path.insert(0, BENCH)
    from layer_metrics import lm_kda_mla_moe as reader
    names = {
        "%a": "jit(step)/jvp(forward)/kda.proj/dot_general",
        "%kda_fwd.1": "jit(step)/jvp(forward)/kda.scan/jit(_rule)/kda_fwd",
        "%c": "jit(step)/transpose(jvp(forward))/kda.scan/while/body/dot",
        "%d": "jit(step)/jvp(forward)/checkpoint/kda.conv/mul",
        "%flash_fwd.2": "jit(step)/jvp(forward)/attn.mla/flash_fwd",
        "%e": "jit(step)/jvp(forward)/attn.mla/dot_general",
        "%flash_bwd_dq.1": "jit(step)/transpose(jvp(forward))/attn.mla/x",
        "%f": "jit(step)/jvp(forward)/ffn.dense/dot_general",
        "%g": "jit(step)/optimizer/mul",
    }
    ops = [(n, 0.0, 2e6) for n in names]
    assert reader.by_scope(ops, names, steps=2) == {
        "kda.proj": 1.0, "kda.scan": 2.0, "kda.conv": 1.0, "attn.mla": 3.0,
        "ffn.dense": 1.0, "flash": 2.0}
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
    config["training"].update(attn_backend="xla", kda_backend="xla")
    ctx = Context(cell={"name": CELL, "chips": 1}, config=config,
                  traffic=_load(os.path.join(DATA, TRAFFIC + ".json")),
                  seed=2400000003, seconds=0, trace=False, rehearse=True,
                  devices=jax.devices()[:1])
    family = load_module("families", "lm_kda_mla_moe").build(ctx)
    return family, family.init()


CONTROLS = ("no_decay", "decay_per_head", "no_shared_key_part",
            "softmax_scores", "no_scaling_factor", "wrong_norm_eps",
            "dg_per_head", "shared_key_part_without_gradient",
            "fp8_norm_outputs", "fp8_mixer_operands")


@pytest.mark.parametrize("wrong", [None, *CONTROLS])
def test_reference_check_passes_the_block_and_fails_a_wrong_one(
        toy_family, capsys, wrong):
    """Every control of ``kda_mla_moe_controls.py`` (the chip's readings of
    the same set the limits) comes out not correct by the check itself;
    the two whose forward is the block's, by the gradients alone."""
    import kda_mla_moe_controls as controls
    family, state = toy_family
    assert set(CONTROLS) == set(controls.controls(family.cfg))
    with controls.in_place(family, wrong) as cfg:
        ok = family.reference_check(state, cfg=cfg)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["event"] == "reference_check" and line["ok"] is ok
    assert ok is (wrong is None), line
    forward = (line["mean_abs_token_err"] <= line["tol_mean_abs_token"]
               and max(line["kda_o_rel_err"]) <= line["tol_kda_o_rel"]
               and max(line["mla_o_rel_err"]) <= line["tol_mla_o_rel"])
    rule, attend = (max(line[k].values()) for k in (
        "rule_grad_rel_err", "attend_grad_rel_err"))
    if wrong is None:
        assert rule < 1e-4 and attend < 1e-4
    elif wrong == "dg_per_head":
        assert forward and line["rule_grad_rel_err"]["g"] > 0.5 \
            and attend < 1e-4
    elif wrong == "shared_key_part_without_gradient":
        assert forward and rule < 1e-4 \
            and line["attend_grad_rel_err"]["q"] > 0.1
