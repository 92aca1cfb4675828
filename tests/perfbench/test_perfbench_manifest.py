"""BENCHMARK.json against the benchmark's contract and against the files
it names: every cell, configuration and metric is found by name, every
``moves`` is an end-to-end metric each reporting cell reports, names and
units use only the allowed characters."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

MANIFEST = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
BENCH = os.path.join(ROOT, MANIFEST["paths"][0])
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(_dim|_rank)$|^(dim|k|nnz|compute_dtype|hidden|head)")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
E2E = {m["name"]: m for m in MANIFEST["end_to_end"]}
LAYER = {m["name"]: m for m in MANIFEST["per_layer"]}


def reporting(metric: dict) -> list[str]:
    return metric.get("workloads", CELLS)


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert isinstance(MANIFEST["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= len(MANIFEST["configs"]) <= 24
    assert 1 <= len(MANIFEST["workloads"]) <= 24
    assert 1 <= len(MANIFEST["end_to_end"]) <= 16
    assert 1 <= len(MANIFEST["per_layer"]) <= 128


def test_command_and_paths():
    cmd = MANIFEST["command"]
    assert 1 <= len(cmd) <= 32 and all(1 <= len(w) <= 200 for w in cmd)
    assert 1 <= len(MANIFEST["paths"]) <= 16
    for path in MANIFEST["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", path)
        assert not path.startswith("/") and ".." not in path.split("/")
        assert os.path.isdir(os.path.join(ROOT, path))
    for word in cmd[1:]:
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in MANIFEST["paths"])


def test_files_under_paths_are_named_from_the_allowed_characters():
    for path in MANIFEST["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


@pytest.mark.parametrize("kind,entry", [
    (kind, e) for kind in ("configs", "workloads", "end_to_end", "per_layer")
    for e in MANIFEST[kind]], ids=lambda v: v if isinstance(v, str)
    else v["name"])
def test_names_units_and_entry_keys(kind, entry):
    assert NAME.match(entry["name"])
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source",
                       "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}}[kind]
    assert set(entry) <= allowed
    assert allowed - {"workloads"} <= set(entry)
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200
            assert "\n" not in entry[key] and "\t" not in entry[key]
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    if kind == "end_to_end":
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0 < entry["bound"] <= 0.1
    if kind == "per_layer":
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
        assert "bound" not in entry


def test_no_name_twice():
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[kind]]
        assert len(names) == len(set(names))
    metrics = list(E2E) + list(LAYER)
    assert len(MANIFEST["end_to_end"]) + len(MANIFEST["per_layer"]) \
        == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_setup_s_is_an_end_to_end_metric_of_every_cell():
    assert "setup_s" in E2E and "workloads" not in E2E["setup_s"]
    assert E2E["setup_s"]["bound"] <= 0.1


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in MANIFEST["workloads"])
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 4)


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_configuration_file(config):
    assert any(config["file"].startswith(p + "/") for p in MANIFEST["paths"])
    cfg = harness.read_json(os.path.join(ROOT, config["file"]))
    assert cfg["source"] == config["source"] and len(cfg["source"]) <= 200
    assert cfg["reduced"] == config["reduced"] and len(cfg["reduced"]) <= 16
    for key in config["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
        assert key in cfg["assumed"], f"{key}: cut, with no reason given"
    assert any(w["config"] == config["name"] for w in MANIFEST["workloads"])
    assert os.path.exists(os.path.join(
        BENCH, "learners", cfg["learner"] + ".py"))
    assert os.path.exists(os.path.join(
        BENCH, "kernels", cfg["kernel"] + ".py"))
    assert cfg["guarantees"] and cfg["precision"]
    for name, limit in cfg["correct"]["limits"].items():
        assert isinstance(limit["limit"], (int, float)), name
        assert limit["why"], name
    files = [c["file"] for c in MANIFEST["configs"]]
    assert files.count(config["file"]) == 1


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_cell_is_found_by_name(cell):
    loaded = harness.load_cell(cell["name"])
    assert loaded["traffic"]["world"] == cell["chips"]
    assert NAME.match(cell["traffic"]) and NAME.match(cell["config"])
    got = {m["name"] for m in harness.metrics_of(loaded, "end_to_end")}
    assert "setup_s" in got and len(got) >= 2
    assert harness.metrics_of(loaded, "per_layer")


@pytest.mark.parametrize("name", list(E2E) + list(LAYER))
def test_metric_has_a_reader_of_its_own(name):
    sub = "end_to_end" if name in E2E else "layers"
    base = os.path.join(BENCH, sub, name)
    assert os.path.exists(base + ".json") != os.path.exists(base + ".py")
    if os.path.exists(base + ".json"):
        from perfbench import readers

        spec = harness.read_json(base + ".json")
        assert spec["kind"] in readers.KINDS
        if name in LAYER and "layer" in spec:
            assert spec["layer"] == LAYER[name]["layer"]


@pytest.mark.parametrize("name", list(LAYER))
def test_moves_is_reported_by_every_reporting_cell(name):
    metric = LAYER[name]
    moved = E2E[metric["moves"]]
    for cell in reporting(metric):
        assert cell in CELLS
        assert cell in reporting(moved), (
            f"{name} is read in {cell}, which does not report "
            f"{moved['name']}")


@pytest.mark.parametrize("name", [n for n, m in {**E2E, **LAYER}.items()
                                  if "workloads" in m])
def test_listed_cells_exist(name):
    metric = {**E2E, **LAYER}[name]
    assert metric["workloads"] and set(metric["workloads"]) <= set(CELLS)


def test_layers_are_those_of_perf_md():
    text = open(os.path.join(ROOT, "PERF.md")).read()
    for metric in MANIFEST["per_layer"]:
        assert metric["layer"] in text, metric["layer"]
