"""BENCHMARK.json keeps to its contract, and every name in it leads to
a file of its own."""

import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden_size", "intermediate", "head_dim", "latent", "state",
               "proj", "experts_per_tok", "expansion")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _metrics(bench):
    return bench["end_to_end"] + bench["per_layer"]


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["paths"]) <= 16
    assert len(bench["command"]) <= 32
    assert 1 <= len(bench["configs"]) <= 24
    assert 1 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_names_units_and_keys(bench):
    names = [m["name"] for m in _metrics(bench)]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for m in _metrics(bench):
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\t" not in w["why"]
    cells = [w["name"] for w in bench["workloads"]]
    assert len(cells) == len(set(cells))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert len(c["reduced"]) <= 16
        assert any(w["config"] == c["name"] for w in bench["workloads"])
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
            assert not any(word in key for word in WIDTH_WORDS), key


def test_setup_is_an_end_to_end_metric_of_every_cell(bench):
    setup = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.1
    assert len(bench["end_to_end"]) <= 5


def test_every_cell_reports_enough(bench):
    for w in bench["workloads"]:
        e2e = [m for m in bench["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert len(e2e) >= 2, w["name"]
        layer = [m for m in bench["per_layer"]
                 if w["name"] in m.get("workloads", [w["name"]])]
        assert layer, w["name"]


def test_moves_names_a_metric_its_cells_report(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        listed = set(m.get("workloads", e2e[m["moves"]]))
        assert listed and listed <= cells, m["name"]
        assert listed <= e2e[m["moves"]], m["name"]
    for m in bench["end_to_end"]:
        assert set(m.get("workloads", cells)) <= cells


def test_every_name_leads_to_a_file_of_its_own(bench):
    path = os.path.join(ROOT, bench["paths"][0])
    files = []
    for c in bench["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        full = os.path.join(ROOT, c["file"])
        files.append(full)
        with open(full) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        importlib.import_module(f"perfbench.families.{cfg['family']}")
    assert len(files) == len(set(files))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(path, "traffic", w["traffic"] + ".json"))
    for m in _metrics(bench):
        with open(os.path.join(path, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["name"] == m["name"] and spec["unit"] == m["unit"]
        for key in ("layer", "moves"):
            if key in m:
                assert spec[key] == m[key]
        reader = importlib.import_module(f"perfbench.readers.{spec['reader']}")
        assert callable(reader.read)


def test_the_command_names_nothing_outside_paths(bench):
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word
        assert 1 <= len(word) <= 200
    assert bench["command"][-1] == "perfbench.run"


def test_roofline_and_mfu_metrics_are_shares(bench):
    for m in bench["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"
