"""The command end to end on the CPU, on toy configurations that live with
these tests and are added to a temporary copy of the benchmark AS DATA: new
configuration and traffic files and new ``BENCHMARK.json`` entries, with no
edit to a file that was there. ``--rehearse`` ends in one JSON line with
exactly the contract's keys and a non-zero exit; without it a run that
finds no chip prints no result line."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_paths import BENCH, DATA, ROOT

TOY_CELLS = [
    # name, config, traffic, chips, host devices
    ("toy_lm_step", "toy_lm", "toy_step_loop", 1, 1),
    ("toy_lm_dp4", "toy_lm", "toy_step_loop_dp4", 4, 4),
    ("toy_resnet_fit", "toy_resnet", "toy_fit_host", 1, 1),
]
RATE = {"toy_lm": "tokens_per_s_per_chip",
        "toy_resnet": "images_per_s_per_chip"}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A copy of the benchmark with the toy cells added as data."""
    root = tmp_path_factory.mktemp("bench_copy")
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
              if p.is_file()}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for name in sorted(os.listdir(DATA)):
        kind = "traffic" if "step_loop" in name or "fit_host" in name \
            else "configs"
        shutil.copy(os.path.join(DATA, name),
                    root / "benchmarks" / kind / name)
    for config in ("toy_lm", "toy_resnet"):
        bench["configs"].append({
            "name": config, "source": "tests/benchmark/data",
            "file": f"benchmarks/configs/{config}.json", "reduced": [],
            "why": "toy"})
    for name, config, traffic, chips, _ in TOY_CELLS:
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": chips,
                                   "why": "toy"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if m["name"] == RATE[config] or (
                    config == "toy_lm" and m["name"].endswith(".lm")):
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    yield root
    # Nothing that was there was edited.
    for p, data in before.items():
        assert p.read_bytes() == data, p


def run_cell(root, tmp, *args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               BENCH_RUN="7")
    return subprocess.run(
        [sys.executable, str(root / "benchmarks" / "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("name,config,traffic,chips,devices", TOY_CELLS,
                         ids=[c[0] for c in TOY_CELLS])
def test_rehearsal_of_a_cell_added_as_data(copy, tmp_path, name, config,
                                           traffic, chips, devices):
    p = run_cell(copy, tmp_path, "--workload", name, "--seed", "2400000001",
                 "--seconds", "1", "--trace", "0", "--rehearse",
                 devices=devices)
    assert p.returncode == 3, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert all(json.loads(l).get("rehearsal") for l in lines[:-1])
    last = json.loads(lines[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 3
    assert set(last["metrics"]) == {RATE[config], "step_ms_p90", "setup_s"}
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert last["device"]["count"] == chips
    assert last["device"]["platform"] == "cpu" \
        and last["device"]["rehearsal"] is True


def test_traced_rehearsal_reports_per_layer_metrics(copy, tmp_path):
    p = run_cell(copy, tmp_path, "--workload", "toy_lm_step", "--seed", "1",
                 "--seconds", "1", "--trace", "1", "--rehearse")
    assert p.returncode == 3, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    assert {"setup.compile_s", "device_step_ms.lm", "mfu_pct.lm",
            "device.idle_pct"} <= set(last["metrics"])
    assert last["device"]["busy_s"] > 0 and last["device"]["window_s"] > 0
    assert 1 <= len(last["breakdown"]["device_ops"]) <= 10
    assert len(last["breakdown"]["idle_gaps"]) <= 10


def test_same_seed_same_inputs(copy, tmp_path):
    losses = []
    for _ in range(2):
        p = run_cell(copy, tmp_path, "--workload", "toy_lm_step", "--seed",
                     "9", "--seconds", "0.2", "--rehearse")
        window = [json.loads(l) for l in p.stdout.splitlines()
                  if '"event": "window"' in l][0]
        losses.append(window["losses"][:3])
    assert losses[0] == losses[1]


@pytest.mark.parametrize("args,why", [
    (["--workload", "toy_lm_step"], "no TPU"),
    (["--workload", "toy_lm_dp4", "--rehearse"], "fewer chips than asked"),
    (["--workload", "no_such_cell", "--rehearse"], "unknown cell"),
], ids=["no_chip", "too_few_chips", "unknown_cell"])
def test_no_result_line_without_what_the_cell_needs(copy, tmp_path, args,
                                                    why):
    p = run_cell(copy, tmp_path, *args, "--seed", "1", "--seconds", "1")
    assert p.returncode not in (0, 3), why
    assert not any(l.startswith('{"correct"')
                   for l in p.stdout.splitlines())


def test_fails_where_only_the_benchmark_is(copy, tmp_path):
    """A directory that holds only BENCHMARK.json and the paths: the
    program is missing, so no result and a non-zero exit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, str(copy / "benchmarks" / "run.py"), "--workload",
         "toy_lm_step", "--rehearse"], cwd=copy, env=env,
        capture_output=True, text=True, timeout=120)
    assert p.returncode not in (0, 3) and '"correct"' not in p.stdout


def test_prove_summary():
    sys.path.insert(0, BENCH)
    import prove

    def rec(tag, v):
        return {"tag": tag, "result": {"metrics": {
            "m": {"value": v, "unit": "x"}}}}
    records = [rec(f"set0_run{i}", v) for i, v in enumerate(
        [100, 101, 102, 103, 104, 105])] + \
        [rec(f"set1_run{i}", v) for i, v in enumerate(
            [110, 110, 110, 110, 110, 121])] + [rec("cold", 999)]
    s = prove.summarise(records, 2)["m"]
    assert [x["median"] for x in s["sets"]] == [102.5, 110]
    # exclusive quartiles of 100..105: 100.75 and 104.25
    assert s["sets"][0]["spread"] == pytest.approx(3.5 / 102.5)
    assert s["widest_spread"] == pytest.approx(3.5 / 102.5)
    assert s["bound_at_5x"] == pytest.approx(5 * 3.5 / 102.5)
    assert s["second_median_over_first"] == pytest.approx(110 / 102.5 - 1)
