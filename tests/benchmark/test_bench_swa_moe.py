"""Family ``lm_swa_moe`` in the harness: the toy configuration and traffic
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

CELL, CONFIG, TRAFFIC = ("toy_swa_moe_step", "toy_lm_swa_moe",
                         "toy_step_loop_swa_moe")
REAL_CELL = "trinity_swa_train_8k_1chip"
REAL_CONFIG = "trinity_mini_ep16"


def _load(path):
    with open(path) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_copy_swa_moe")
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
    p = run_cell(copy, tmp_path, "--seed", "4000000001", "--seconds", "1",
                 "--trace", str(trace), "--rehearse")
    assert p.returncode == 3, p.stderr[-2000:]
    lines = [json.loads(l) for l in p.stdout.strip().splitlines()]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    check = next(l for l in lines if l.get("event") == "reference_check")
    # float32 on the CPU: the system IS the reference up to rounding, and
    # routes every token as the reference does but where two scores tie
    # to rounding.
    assert check["mean_abs_token_err"] < 1e-4
    assert max(check["attn_o_rel_err"]) < 1e-4 and \
        check["layer_kinds"] == ["swa", "swa", "attn", "swa", "swa"]
    assert min(check["routing_overlap"]) > 0.999
    # The backward of the first window layer's attention call and of the
    # full layer's (the kernels, interpreted), from the same inputs.
    assert set(check["attend_grad_rel_err"]) == {"swa", "full"}
    assert all(set(g) == {"q", "k", "v"} and max(g.values()) < 1e-4
               for g in check["attend_grad_rel_err"].values())
    # Four expert layers (the first layer is dense): 2 x 512 tokens x top-3.
    assert len(check["held_load"]) == 4
    assert all(sum(load) + absent == 2 * 512 * 3 for load, absent in zip(
        check["held_load"], check["absent_assignments"]))
    compiled = next(l for l in lines if l.get("event") == "compiled_step")
    assert "score_arrays" in compiled and "kda_local_kernels" not in compiled
    if trace:
        # Every per-layer metric BENCHMARK.json lists for the real cell that
        # a CPU's trace can give: its ops carry no framework name, so what
        # is split by named scope is read on the chip alone.
        wanted = _cell_metrics(_load(os.path.join(ROOT, "BENCHMARK.json")))
        by_scope = {m for m in wanted if m.startswith((
            "device_step.", "attn.swa", "attn.full", "ffn.dense",
            "moe.shared", "moe.route", "moe.experts",
            "swa_attend_roofline"))}
        assert wanted - by_scope <= set(last["metrics"]), \
            wanted - by_scope - set(last["metrics"])
        assert {"setup.compile_s", "device.idle_pct",
                "device_step_ms.lm_swa_moe", "mfu_pct.lm_swa_moe",
                "moe.load_max_over_mean", "swa.tiles_visited_pct",
                "step.compiles_in_window"} <= wanted - by_scope
        # T 512, a window of 200: tiles of 128, 9 of the 10 causal pairs.
        assert last["metrics"]["swa.tiles_visited_pct"]["value"] == 90.0
        assert last["metrics"]["moe.load_max_over_mean"]["value"] >= 1.0
        after = next(l for l in lines
                     if l.get("event") == "routing_after_window")
        assert all(sum(load) + absent == pytest.approx(2 * 512 * 3)
                   for load, absent in zip(after["held_load"],
                                           after["absent_assignments"]))
    else:
        assert set(last["metrics"]) == {"tokens_per_s_per_chip",
                                        "step_ms_p90", "setup_s"}


def test_the_real_cell_names_files_that_are_there():
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(c for c in bench["workloads"] if c["name"] == REAL_CELL)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "step_loop_swa_8k"
    config = _load(os.path.join(ROOT, entry["file"]))
    traffic = _load(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    assert config["family"] == "lm_swa_moe" \
        and traffic["driver"] == "step_loop"
    assert (traffic["batch_per_chip"], traffic["seq_len"], traffic["pool"],
            traffic["warmup_steps"], traffic["reference_sequences"],
            traffic["mesh"]) == (1, 8192, 4, 3, 2, {"dp": 1})
    # Every number of the catalog's row as published but the three cut;
    # what is held here has keys of its own.
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["head_dim"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["num_experts_per_tok"], config["router_experts"],
            config["num_shared_experts"], config["route_scale"],
            config["rms_norm_eps"], config["rope_theta"],
            config["sliding_window"], config["num_dense_layers"],
            config["global_attn_every_n_layers"], config["vocab_size"],
            config["max_position_embeddings"]) == \
        (2048, 6144, 1024, 128, 32, 4, 8, 128, 1, 2.826, 1e-5, 10000, 2048,
         2, 4, 200192, 131072)
    assert config["layer_types"] == (["sliding_attention"] * 3
                                     + ["full_attention"]) * 8
    assert config["rope_scaling"] is None and config["mup_enabled"]
    assert (config["score_func"], config["route_norm"],
            config["model_type"]) == ("sigmoid", True, "afmoe")
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == \
        ["num_experts", "num_hidden_layers", "vocab_rows_held"]
    assert config["num_experts"] * 16 == config["router_experts"]
    assert config["vocab_rows_held"] * 8 == config["vocab_size"]
    assert config["num_hidden_layers"] == 5 \
        and config["layers_held"] == [1, 2, 3, 4, 5]
    assert "ep = 16" in config["deployment"]
    assert len(config["departs"]) == 3 and "router" in config["departs"][0]
    assert "selection bias" in config["assumed"]["not_built"]
    assert {"output_gate", "positions", "sandwich_norms", "mup",
            "window"} <= set(config["assumed"])


# This PR's: name -> (unit, better, source, layer).
ADDED_PER_LAYER = {
    "device_step_ms.lm_swa_moe": ("ms", "lower", "device_trace",
                                  "step builders"),
    "mfu_pct.lm_swa_moe": ("%", "higher", "host_clock", "step builders"),
    "attn.swa_ms": ("ms", "lower", "device_trace", "attention"),
    "swa_attend_roofline": ("%", "higher", "device_trace", "kernels"),
    "swa.tiles_visited_pct": ("%", "lower", "program_counter", "attention"),
}
# What the benchmark had before this cell, in its order (the cells).
HAD_CELLS = ["lm_step_1chip", "resnet50_fit_1chip", "lm_dp4_4chip",
             "keye_dsa_train_8k_1chip", "qwen3next_gdn_train_8k_1chip",
             "kimi_kda_train_8k_1chip", "kanana2_mla_train_8k_1chip"]


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
    # The shared metrics list the cell.
    joined = ["step.compiles_in_window", "device_step.forward_ms",
              "device_step.backward_ms", "device_step.optimizer_ms",
              "device_step.unscoped_ms", "attn.full_ms", "ffn.dense_ms",
              "moe.route_ms", "moe.experts_ms", "moe.shared_ms",
              "moe.load_max_over_mean"]
    assert all(REAL_CELL in entries[n]["workloads"] for n in joined)
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "tokens_per_s_per_chip")
    assert REAL_CELL in rate["workloads"]


def test_model_config_and_the_parameters_the_file_counts():
    sys.path.insert(0, BENCH)
    from run import load_module
    from horovod_tpu.parallel.transformer import layer_kind
    family = load_module("families", "lm_swa_moe")
    config = _load(os.path.join(BENCH, "configs", REAL_CONFIG + ".json"))
    cfg = family.model_config(config)
    assert [layer_kind(cfg, i) for i in range(5)] == \
        ["swa", "swa", "attn", "swa", "swa"]
    assert (cfg.dense_layers, cfg.dense_ff, cfg.n_experts, cfg.experts_held,
            cfg.moe_top_k, cfg.moe_score, cfg.moe_select_bias, cfg.moe_scale,
            cfg.moe_renormalize, cfg.shared_expert_ff, cfg.shared_expert_gate,
            cfg.norm_eps, cfg.vocab, cfg.rope_theta, cfg.attn_gate,
            cfg.post_norms) == (
        1, 6144, 128, 8, 8, "sigmoid", True, 2.826, True, 1024, False,
        1e-5, 25024, 0.0, True, True)
    assert (cfg.swa.window, cfg.swa.rope_theta) == (2048, 1e4)
    assert cfg.embed_scale == pytest.approx(2048 ** 0.5)
    with pytest.raises(ValueError, match="plain top-k"):
        family.model_config(dict(config, n_group=8))
    with pytest.raises(ValueError, match="consecutive"):
        family.model_config(dict(config, layers_held=[1, 2, 3, 5, 6]))
    # 504.1 M parameters, as the configuration's file says.
    import jax
    from horovod_tpu.parallel.transformer import init_params
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == pytest.approx(504.1e6, rel=1e-3)


def test_flop_and_byte_counts_against_hand_counts():
    from lib import flops_swa_moe as flops
    config = _load(os.path.join(BENCH, "configs", REAL_CONFIG + ".json"))
    # A window of 2048 keys at T 8192: 2048 x 2049 / 2 + 6144 x 2048
    # pairs, 43.75% of the causal 8192 x 8193 / 2.
    band = 2048 * 2049 / 2 + 6144 * 2048
    assert flops.band_pairs(8192, 2048) == band
    assert band / (8192 * 8193 / 2) == pytest.approx(0.4375, abs=1e-4)
    assert flops.layer_counts(config) == (4, 1)
    # Four window layers, 32 heads of 128: forward and twice that backward.
    assert flops.swa_attend_flop_per_step(config, 1, 8192) == \
        4 * 3 * 32 * band * 4 * 128 == pytest.approx(2.886e12, rel=1e-3)
    assert flops.swa_attend_bytes_per_step(config, 1, 8192) == \
        4 * 8192 * 2 * 128 * ((64 + 8) + (96 + 8) + (32 + 8))
    # Compute binds: 14.7 ms of FLOP against 3.5 ms of bytes.
    assert flops.swa_attend_flop_per_step(config, 1, 8192) / 197e12 > \
        flops.swa_attend_bytes_per_step(config, 1, 8192) / 819e9
    # Forward FLOP a token, by hand: a layer's projections 2 x (2 x 8.39 M
    # + 2 x 1.05 M + 8.39 M); window layers 1792.1 keys a row and the full
    # layer 4096.5, x 4 x 128 x 32; the dense layer 6 x 12.58 M; every
    # other layer's router 0.26 M x 2, shared expert 6 x 2.10 M, 8 x 8 /
    # 128 assignments x 6 x 2.10 M; the head 2 x 51.25 M.
    proj = 2 * (2 * 8.3886e6 + 2 * 1.0486e6 + 8.3886e6)
    pair = 4 * 128 * 32
    want = 3 * (5 * proj + 4 * band / 8192 * pair + 4096.5 * pair
                + 6 * 12.583e6
                + 4 * (2 * 0.26214e6 + 6 * 2.0972e6 + 0.5 * 6 * 2.0972e6)
                + 2 * 51.249e6)
    assert flops.lm_swa_moe_train_flop_per_token(config, 8192) == \
        pytest.approx(want, rel=1e-3) == pytest.approx(2136e6, rel=2e-3)
    more = flops.lm_swa_moe_train_flop_per_token(config, 8192, 2.0)
    assert more - flops.lm_swa_moe_train_flop_per_token(
        config, 8192, 1.0) == pytest.approx(3 * 4 * 6 * 2048 * 1024)


def test_reader_sums_ops_by_scope_and_finds_the_flash_kernels():
    sys.path.insert(0, BENCH)
    from layer_metrics import lm_swa_moe as reader
    names = {
        "%a": "jit(step)/jvp(forward)/attn.swa/dot_general",
        "%b": "jit(step)/transpose(jvp(forward))/attn.swa/mul",
        "%flash_fwd.2": "jit(step)/jvp(forward)/attn.swa/flash_fwd",
        "%flash_bwd.1": "jit(step)/transpose(jvp(forward))/attn.swa/x",
        "%flash_fwd.3": "jit(step)/jvp(forward)/attn.full/flash_fwd",
        "%c": "jit(step)/jvp(forward)/attn.full/dot_general",
        "%d": "jit(step)/jvp(forward)/ffn.dense/dot_general",
        "%e": "jit(step)/jvp(forward)/moe.shared/dot_general",
        "%g": "jit(step)/optimizer/mul",
        "%h": "jit(step)/jvp(forward)/attn.swa_x/mul",
    }
    ops = [(n, 0.0, 2e6) for n in names]
    assert reader.by_scope(ops, names, steps=2) == {
        "attn.swa": 4.0, "attn.full": 2.0, "ffn.dense": 1.0,
        "moe.shared": 1.0, "flash": 2.0}
    # Nothing for another family.
    assert reader.read(None, {}, {"config": {"family": "lm_mla_moe"}}) == {}


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
                  seed=4000000003, seconds=0, trace=False, rehearse=True,
                  devices=jax.devices()[:1])
    family = load_module("families", "lm_swa_moe").build(ctx)
    return family, family.init()


def test_post_mixer_norms_start_at_the_configured_gain(toy_family):
    """The one weight the family draws apart from the program's
    ``init_params`` (the configuration's ``departs``); every other norm
    weight starts at one."""
    import numpy as np
    family, state = toy_family
    gain = family.ctx.config["training"]["post_mixer_norm_init"]
    for layer in state[0]["layers"]:
        np.testing.assert_array_equal(layer["post_ln1"], gain)
        for name in ("ln1", "ln2", "post_ln2"):
            np.testing.assert_array_equal(layer[name], 1.0)


CONTROLS = ("no_window", "window_one_tile_wider", "rope_on_full", "no_gate",
            "no_post_norms", "fp8_operands", "fp8_norm_outputs")


@pytest.mark.parametrize("wrong", [None, *CONTROLS])
def test_reference_check_passes_the_block_and_fails_a_wrong_one(
        toy_family, capsys, wrong):
    """Every control of ``swa_moe_controls.py`` (the chip's readings of the
    same set the limits) comes out not correct by the check itself."""
    import swa_moe_controls as controls
    family, state = toy_family
    assert set(CONTROLS) == set(controls.controls(family.cfg,
                                                  family.seq_len))
    with controls.in_place(family, wrong) as cfg:
        ok = family.reference_check(state, cfg=cfg)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["event"] == "reference_check" and line["ok"] is ok
    assert ok is (wrong is None), line
    attend = max(max(g.values())
                 for g in line["attend_grad_rel_err"].values())
    if wrong is None:
        assert attend < 1e-4
    else:
        assert (line["mean_abs_token_err"] > line["tol_mean_abs_token"]
                or max(line["attn_o_rel_err"]) > line["tol_attn_o_rel"]
                or attend > line["tol_attend_grad_rel"]), line
