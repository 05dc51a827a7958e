"""``BENCHMARK.json`` keeps to its contract, and every name in it resolves
to the files the harness loads: a cell to its configuration, traffic and
driver, a per-layer metric to its reader."""
import json
import os
import re

import pytest

from bench import harness

BM = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
CELLS = {w["name"]: w for w in BM["workloads"]}
E2E = {m["name"]: m for m in BM["end_to_end"]}


def test_top_level_keys_and_size():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024


def test_command_and_paths_stay_inside_the_benchmark():
    assert 1 <= len(BM["paths"]) <= 16
    for p in BM["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(harness.ROOT, p))
    assert 1 <= len(BM["command"]) <= 32
    for word in BM["command"]:
        assert TEXT.match(word) and not word.startswith("/")
        if os.path.exists(os.path.join(harness.ROOT, word)):
            assert any(word.startswith(p + "/") for p in BM["paths"])


def test_run_seconds_fit_the_full_check_at_24_cells():
    r = BM["run_seconds"]
    assert isinstance(r, int) and 1 <= r <= 51
    assert (2 + 14 * 24) * (r + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_are_unique_and_use_only_allowed_characters(kind):
    names = [e["name"] for e in BM[kind]]
    assert len(names) == len(set(names))
    for e in BM[kind]:
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
        for key in ("why", "layer", "source"):
            if key in e:
                assert TEXT.match(e[key]), (e["name"], key)


def test_configs_resolve_to_their_files():
    used = {w["config"] for w in BM["workloads"]}
    files = [c["file"] for c in BM["configs"]]
    assert len(files) == len(set(files))
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in BM["paths"])
        cfg = harness.load_json(c["file"])
        assert cfg["name"] == c["name"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and key in cfg
            assert not re.search(r"(_dim|_rank|hidden|width|size)$", key)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_each_cell_resolves_and_reports_what_the_contract_asks(cell):
    w = CELLS[cell]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4)
    harness.find(BM["configs"], w["config"], "config")
    traffic = harness.load_json(harness.traffic_file(w["traffic"]))
    driver = harness.load_module(harness.driver_file(traffic["driver"]),
                                 "driver")
    for attr in ("evals_per_call", "call", "release", "check", "work"):
        assert hasattr(driver.Cell, attr)
    e2e = [m["name"] for m in harness.cell_metrics(BM, cell, "end_to_end")]
    assert "setup_s" in e2e and traffic["per_call"] in e2e
    assert len(e2e) >= 2
    assert harness.cell_metrics(BM, cell, "per_layer")


def test_pairs_of_config_and_traffic_are_unique_and_few_cells_take_four():
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BM["workloads"])
    assert four <= max(1, len(BM["workloads"]) // 2)


def test_end_to_end_metrics_and_bounds():
    assert "setup_s" in E2E
    for m in BM["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        for cell in m.get("workloads", []):
            assert cell in CELLS


def test_each_per_layer_metric_resolves_its_reader():
    for m in BM["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in E2E
        reader = harness.load_module(harness.reader_file(m["name"]), "r")
        assert callable(reader.read)
        for cell in m.get("workloads", []):
            assert m["moves"] in [e["name"] for e in harness.cell_metrics(
                BM, cell, "end_to_end")]


def test_metrics_of_one_layer_name_it_alike():
    layers = {}
    for m in BM["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_roofline_and_mfu_shares_are_percent():
    for m in BM["per_layer"]:
        if m["name"].split(".")[0].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"


def test_traffic_and_config_files_are_data():
    for d in ("traffic", "configs"):
        for f in os.listdir(os.path.join(harness.BENCH_DIR, d)):
            assert f.endswith(".json")
            with open(os.path.join(harness.BENCH_DIR, d, f)) as fh:
                json.load(fh)
