"""BENCHMARK.json agrees with the files it names, and keeps to the
contract's limits that a file can be checked for."""

import json
import os
import re

import pytest

from bench_paths import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _load(path):
    with open(path) as fh:
        return json.load(fh)


BENCHMARK = _load(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = BENCHMARK["workloads"]
METRICS = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]


def test_top_level_keys_and_limits():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    for p in BENCHMARK["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert os.path.exists(os.path.join(ROOT, BENCHMARK["command"][1]))


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_cell_finds_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    entry = next(c for c in BENCHMARK["configs"]
                 if c["name"] == cell["config"])
    config = _load(os.path.join(ROOT, entry["file"]))
    traffic = _load(os.path.join(BENCH, "traffic",
                                 cell["traffic"] + ".json"))
    for kind, name in (("families", config["family"]),
                       ("drivers", traffic["driver"]),
                       ("reference", config["family"])):
        assert os.path.exists(os.path.join(BENCH, kind, name + ".py"))
    # What the configuration's file says was reduced is what the manifest
    # lists, and no width is among it.
    assert sorted(config.get("reduced", {})) == sorted(entry["reduced"])
    for key in entry["reduced"]:
        assert not re.search(r"(_dim$|_rank$|_size$|head|expansion|per_tok)", key)


def test_configs_used_and_distinct():
    used = {c["config"] for c in CELLS}
    names = [c["name"] for c in BENCHMARK["configs"]]
    files = [c["file"] for c in BENCHMARK["configs"]]
    assert set(names) == used and len(set(files)) == len(files)
    pairs = [(c["config"], c["traffic"]) for c in CELLS]
    assert len(set(pairs)) == len(pairs)
    for c in BENCHMARK["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in
                                          BENCHMARK["paths"]))


def test_at_most_a_quarter_or_one_cell_on_four_chips():
    four = sum(c["chips"] == 4 for c in CELLS)
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {c["name"] for c in CELLS}
    assert set(metric.get("workloads", cells)) <= cells
    if metric in BENCHMARK["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        moved = next(m for m in BENCHMARK["end_to_end"]
                     if m["name"] == metric["moves"])
        # The metric it should move is reported wherever this one is.
        assert set(metric.get("workloads", cells)) \
            <= set(moved.get("workloads", cells))
        if "roofline" in metric["name"] or "mfu" in metric["name"]:
            assert metric["unit"] == "%"


def test_names_unique_and_every_cell_covered():
    for group in (METRICS, CELLS, BENCHMARK["configs"]):
        names = [g["name"] for g in group]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    assert any(m["name"] == "setup_s" for m in BENCHMARK["end_to_end"])
    for cell in CELLS:
        def has(group):
            return [m["name"] for m in group
                    if cell["name"] in m.get("workloads", [cell["name"]])]
        e2e = has(BENCHMARK["end_to_end"])
        assert "setup_s" in e2e and len(e2e) >= 2
        assert has(BENCHMARK["per_layer"])


def test_files_under_paths_are_named_from_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in BENCHMARK["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert ok.match(rel), rel
