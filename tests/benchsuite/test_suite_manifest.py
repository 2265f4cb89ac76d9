"""``BENCHMARK.json`` against the contract's rules that a test can hold: the
characters of names and units, what each cell reports, that every ``moves``
names an end-to-end metric each listed cell reports, that every file a name
points to is there, and that a full check fits its time."""

from __future__ import annotations

import importlib
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden_size|intermediate_size|head_dim|"
                   r"num_experts_per_tok|expansion")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def _cells_of(metric, bench):
    return metric.get("workloads", [w["name"] for w in bench["workloads"]])


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(bench["paths"]) <= 16
    assert 1 <= len(bench["configs"]) <= 24
    assert 1 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128
    assert all(len(word) <= 200 for word in bench["command"])
    assert bench["command"][1].startswith(bench["paths"][0] + "/")


def test_names_units_and_entry_keys(bench):
    seen = set()
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(metric["name"]), metric["name"]
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
        assert metric["name"] not in seen
        seen.add(metric["name"])
    for metric in bench["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    for metric in bench["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
    for config in bench["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(config["name"])
        assert len(config["reduced"]) <= 16
        assert not [k for k in config["reduced"] if WIDTH.search(k)]
    for cell in bench["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        assert cell["chips"] in (1, 4)
        assert 1 <= len(cell["why"]) <= 200, len(cell["why"])
    pairs = [(c["config"], c["traffic"]) for c in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for c in bench["workloads"] if c["chips"] == 4)
    assert four <= max(len(bench["workloads"]) // 4, 1)


def test_every_cell_reports_set_up_one_more_and_a_layer(bench):
    end_names = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in end_names
    used = {c["config"] for c in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for cell in bench["workloads"]:
        mine = [m["name"] for m in bench["end_to_end"]
                if cell["name"] in _cells_of(m, bench)]
        assert "setup_s" in mine and len(mine) >= 2, cell["name"]
        assert any(cell["name"] in _cells_of(m, bench)
                   for m in bench["per_layer"]), cell["name"]
        # A roofline that moves a metric stands beside the whole step's
        # share of the peak, moving the same metric.
        layers = [m for m in bench["per_layer"]
                  if cell["name"] in _cells_of(m, bench)]
        for m in layers:
            if "roofline" in m["name"]:
                assert any("mfu" in re.split(r"[._]", o["name"])
                           and o["moves"] == m["moves"] for o in layers), m


def test_moves_names_a_metric_each_listed_cell_reports(bench):
    end = {m["name"]: m for m in bench["end_to_end"]}
    cells = {c["name"] for c in bench["workloads"]}
    for metric in bench["per_layer"]:
        assert metric["moves"] in end, metric
        for cell in _cells_of(metric, bench):
            assert cell in cells
            assert cell in _cells_of(end[metric["moves"]], bench), (
                metric["name"], cell)


def test_every_named_file_is_there_and_every_reader_imports(bench):
    home = os.path.join(REPO, bench["paths"][0])
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    for config in bench["configs"]:
        assert config["file"].startswith(bench["paths"][0] + "/")
        with open(os.path.join(REPO, config["file"]), encoding="utf-8") as f:
            held = json.load(f)
        assert set(config["reduced"]) <= set(held)
    for cell in bench["workloads"]:
        with open(os.path.join(home, "traffic", cell["traffic"] + ".json"),
                  encoding="utf-8") as f:
            kind = json.load(f)["kind"]
        importlib.import_module(f"benchmarks.suite.kinds.{kind}")
        assert os.path.exists(
            os.path.join(home, "limits", cell["name"] + ".json"))
    for metric in bench["per_layer"]:
        with open(os.path.join(home, "metrics", metric["name"] + ".json"),
                  encoding="utf-8") as f:
            reader = json.load(f)["reader"]
        module = importlib.import_module(f"benchmarks.suite.readers.{reader}")
        assert callable(module.read)


def test_a_full_check_with_24_cells_fits_its_time(bench):
    seconds = bench["run_seconds"]
    assert isinstance(seconds, int) and 1 <= seconds <= 51
    runs = 2 + 14 * 24
    assert runs * (seconds + 60) + 24 * 2 * 90 + 1200 <= 43200
