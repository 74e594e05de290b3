"""``BENCHMARK.json`` against the contract's form, and every name it gives
against the files the harness will look for."""
import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert isinstance(manifest["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert manifest["paths"] == ["benchmark"]
    assert all(not w.startswith("/") and ".." not in w
               for w in manifest["command"])


def test_names_units_and_keys(manifest):
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert "\n" not in w["why"] and "\t" not in w["why"]
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and 1 <= len(m["layer"]) <= 200
    every = manifest["end_to_end"] + manifest["per_layer"]
    for m in every:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in every]
    assert len(names) == len(set(names))
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


def cells_of(manifest, metric):
    return set(metric.get("workloads",
                          [w["name"] for w in manifest["workloads"]]))


def test_every_cell_reports_setup_another_metric_and_a_layer(manifest):
    end = {m["name"]: m for m in manifest["end_to_end"]}
    assert "workloads" not in end["setup_s"]
    for w in manifest["workloads"]:
        mine = [m for m in end.values() if w["name"] in cells_of(manifest, m)]
        assert len(mine) >= 2
        assert any(w["name"] in cells_of(manifest, m)
                   for m in manifest["per_layer"])


def test_each_per_layer_metric_moves_a_metric_its_cells_report(manifest):
    end = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in end, m
        assert cells_of(manifest, m) <= cells_of(manifest, end[m["moves"]]), m


def test_roofline_and_mfu_names(manifest):
    for w in manifest["workloads"]:
        mfu = [m for m in manifest["per_layer"]
               if "mfu" in re.split(r"[_.\-]", m["name"])
               and w["name"] in cells_of(manifest, m)]
        assert mfu, "no whole-step mfu in %s" % w["name"]
        idle = [m for m in manifest["per_layer"]
                if m["name"].endswith("idle_share")
                and w["name"] in cells_of(manifest, m)]
        assert idle and all(m["source"] == "device_trace" for m in idle)
    for m in manifest["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"


def test_every_name_has_its_file(manifest):
    for w in manifest["workloads"]:
        for kind, name in (("traffic", w["traffic"]), ("limits", w["name"])):
            assert os.path.isfile(os.path.join(
                ROOT, "benchmark", kind, name + ".json")), (kind, name)
    for m in manifest["per_layer"]:
        path = os.path.join(ROOT, "benchmark", "metrics", m["name"] + ".json")
        with open(path) as f:
            spec = json.load(f)
        for key in ("unit", "layer", "moves", "source", "better"):
            assert spec[key] == m[key], (m["name"], key)
        assert callable(importlib.import_module(spec["reader"]).read)
    for c in manifest["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] and cfg["source"]
        importlib.import_module("benchmark.references." + cfg["reference"])
        importlib.import_module("benchmark.builders." + cfg["builder"])


def test_layers_are_the_ones_perf_md_lists(manifest):
    with open(os.path.join(ROOT, "PERF.md")) as f:
        text = f.read()
    for layer in {m["layer"] for m in manifest["per_layer"]}:
        assert layer in text, layer
