"""BENCHMARK.json against the contract's rules that a test can hold, and
against the files it names."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def all_metrics():
    return BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    assert len(json.dumps(BENCH)) < 64 * 1024
    four = sum(1 for c in BENCH["workloads"] if c["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("name", [
    n for group in ("configs", "workloads", "end_to_end", "per_layer")
    for n in [e["name"] for e in BENCH[group]]]
    + [c["traffic"] for c in BENCH["workloads"]]
    + [k for c in BENCH["configs"] for k in c["reduced"]])
def test_names(name):
    assert NAME.match(name), name


def test_names_are_unique():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in all_metrics()]
    assert len(names) == len(set(names))
    pairs = [(c["config"], c["traffic"]) for c in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", all_metrics(), ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {c["name"] for c in BENCH["workloads"]}
    assert set(metric.get("workloads", [])) <= cells
    if metric in BENCH["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
        package = "metrics"
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
        if metric["name"].endswith("_roofline"):
            assert metric["unit"] == "%"
        package = "layers"
    assert os.path.isfile(os.path.join(
        ROOT, "chipbench", package, metric["name"] + ".py"))


def test_setup_s_is_there():
    (setup,) = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert setup["bound"] <= 0.25 and "workloads" not in setup


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_resolves_to_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    (config,) = [c for c in BENCH["configs"] if c["name"] == cell["config"]]
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["file"].startswith("chipbench/")
    assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    body = json.load(open(os.path.join(ROOT, config["file"])))
    assert body["name"] == config["name"]
    assert body["source"] == config["source"]
    for key in config["reduced"]:
        assert key in body, key
    traffic = json.load(open(os.path.join(
        ROOT, "chipbench", "traffic", cell["traffic"] + ".json")))
    assert os.path.isfile(os.path.join(
        ROOT, "chipbench", "drivers", traffic["driver"] + ".py"))
    # every cell reports setup_s, one more end-to-end metric, one layer's
    for group in ("end_to_end", "per_layer"):
        mine = [m for m in BENCH[group]
                if "workloads" not in m or cell["name"] in m["workloads"]]
        assert len(mine) >= (2 if group == "end_to_end" else 1)


def test_every_config_is_used_and_files_are_distinct():
    used = {c["config"] for c in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))


def test_peaks_name_their_source():
    peaks = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert all(row["source"] for row in peaks.values())
