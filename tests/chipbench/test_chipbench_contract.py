"""BENCHMARK.json against the contract: names, units and lengths within
the allowed characters, every ``moves`` an end-to-end metric that each
listed cell reports, every cell's and metric's files found by name."""

import importlib
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def cells_of(metric):
    return metric.get("workloads", CELLS)


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["command"]) <= 32
    assert all(one_line(w) and not w.startswith("/") and ".." not in w
               for w in BENCH["command"])
    assert 1 <= len(BENCH["paths"]) <= 16
    assert all(PATH.match(p) and os.path.isdir(os.path.join(ROOT, p))
               for p in BENCH["paths"])
    assert 1 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    # the whole budget of a full check with 24 cells
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("config", BENCH["configs"],
                         ids=lambda c: c["name"])
def test_configuration_entry_and_files(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"]) and one_line(config["source"])
    assert one_line(config["why"]) and len(config["reduced"]) <= 16
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    with open(os.path.join(ROOT, config["file"])) as f:
        body = json.load(f)
    assert body["reduced"] == config["reduced"]
    assert all(NAME.match(k) for k in config["reduced"])
    # no width is ever reduced
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank")) or "hidden" in k
                or k in ("d_model", "d_inner", "n_head")]
    for key in ("source", "runner", "reference", "assumed", "departures",
                "stands_for", "build", "published", "check"):
        assert key in body, key
    importlib.import_module("chipbench.runners." + body["runner"])
    importlib.import_module("chipbench.reference." + body["reference"])
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    files = [c["file"] for c in BENCH["configs"]]
    assert files.count(config["file"]) == 1


def test_published_widths_are_served_as_published():
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        pub, build = body["published"], body["build"]
        for ours, theirs in (("d_model", "d_model"), ("d_model", "n_embd"),
                             ("d_inner", "d_inner"), ("d_inner", "n_inner"),
                             ("n_head", "n_head"), ("vocab", "vocab_size")):
            if theirs in pub and ours in build:
                assert build[ours] == pub[theirs], (c["name"], ours)
        if build["n_layer"] != pub["n_layer"]:
            assert "n_layer" in c["reduced"]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert cell["chips"] in (1, 4) and one_line(cell["why"])
    _cell, config, traffic = harness.load_cell(BENCH, cell["name"])
    gen = harness.generator_of(traffic)
    assert hasattr(gen, "make")
    assert hasattr(harness.runner_of(config), "run")
    assert traffic["trace_seconds"] <= BENCH["run_seconds"]
    reported = [m for m in BENCH["end_to_end"] if cell["name"] in cells_of(m)]
    assert "setup_s" in [m["name"] for m in reported] and len(reported) >= 2
    assert any(cell["name"] in cells_of(m) for m in BENCH["per_layer"])


def test_four_chip_cells_within_the_quota():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("metric", BENCH["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "bound", "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    assert set(cells_of(metric)) <= set(CELLS) and cells_of(metric)
    if metric["name"] == "setup_s":
        assert "workloads" not in metric and metric["bound"] <= 0.1


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_moves_what_its_cells_report(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "source", "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES and one_line(metric["layer"])
    moved = E2E[metric["moves"]]
    assert set(cells_of(metric)) <= set(cells_of(moved))
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"
    spec = harness.load_json("layer_metrics", metric["name"] + ".json")
    reader = importlib.import_module(
        "chipbench.layer_metrics." + spec["reader"])
    assert callable(reader.read) and set(spec) <= {"reader", "args"}


def test_layers_are_the_ones_perf_md_lists():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in BENCH["per_layer"]}:
        assert f"`{layer}`" in perf, layer


def test_files_under_paths_are_named_from_allowed_characters():
    for base in BENCH["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, base)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert PATH.match(rel), rel


def test_every_traffic_file_is_data_for_a_generator():
    tdir = os.path.join(ROOT, "chipbench", "traffic")
    for f in os.listdir(tdir):
        assert f.endswith((".json", ".jsonl", ".toml", ".txt", ".csv"))
        with open(os.path.join(tdir, f)) as fh:
            body = json.load(fh)
        importlib.import_module("chipbench.generators." + body["generator"])
