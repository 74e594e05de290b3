"""``readers/device_scope_ms.py`` on a hand-made ``obs`` and table, its
twelve metric files against the manifest, and every toy cell traced on the
CPU: each metric its cell lists is on the line, and the mechanisms' device
time adds up to no more than the device's operations took."""
import argparse
import json
import os
import sys

import pytest

from benchmark import harness, peaks, run
from benchmark.readers import device_scope_ms
from benchmark.tests import toy, toy_joyai, toy_lm, toy_trinity
from benchmark.tests.test_manifest import ROOT

METRICS = {
    "fit_device_ms_per_step_attn": {"mechanism": "mx:attn"},
    "fit_device_ms_per_step_gdn": {"mechanism": "mx:gdn"},
    "fit_device_ms_per_step_gdn_local": {"detail": "mx:gdn:local"},
    "fit_device_ms_per_step_moe": {"mechanism": "mx:moe"},
    "fit_device_ms_per_step_moe_rows": {"detail": "mx:moe:(gather|scatter)"},
    "fit_device_ms_per_step_mlp": {"mechanism": "mx:mlp"},
    "fit_device_ms_per_step_head": {"mechanism": "mx:head"},
    "fit_device_ms_per_step_update": {"mechanism": "mx:update"},
    "fit_device_ms_per_step_recomputed": {"pass": "recomputed"},
    "fit_device_ms_per_step_bn": {"detail": "mx:op:BatchNorm"},
    "fit_device_ms_per_step_conv": {"detail": "mx:op:Convolution"},
    "fit_device_unscoped_share": {"mechanism": None},
}
# whose rows share no instruction: their sum is at most the busy time
DISJOINT = ["attn", "gdn", "moe", "mlp", "head", "update", "bn", "conv"]


def _row(mechanism, detail, which, seconds):
    return {"mechanism": mechanism, "detail": detail, "pass": which,
            "seconds": seconds}


ROWS = [_row("mx:attn", "mx:attn:full", "forward", 0.5),
        _row("mx:attn", "mx:attn:window", "backward", 0.25),
        _row("mx:attn", "mx:attn:full", "recomputed", 0.125),
        _row("mx:moe", "mx:moe:gather", "recomputed", 1.0),
        _row("mx:moe", "mx:moe:scatter", "backward", 2.0),
        _row("mx:moe", "mx:moe:experts", "forward", 4.0),
        _row("mx:moe", "mx:moe:gathered", "forward", 8.0),
        _row(None, None, "forward", 0.0625),
        _row(None, None, None, 0.0625)]
OBS = {"trace": {"op_seconds": {"any.1 fusion": 16.0}}, "steps": 4}


@pytest.mark.parametrize("select, seconds", [
    ({"mechanism": "mx:attn"}, 0.875),
    ({"detail": "mx:attn:full"}, 0.625),
    ({"detail": "mx:moe:(gather|scatter)"}, 3.0),     # not ``gathered``
    ({"pass": "recomputed"}, 1.125),
    ({"mechanism": "mx:moe", "pass": "forward"}, 12.0),
    ({"mechanism": None}, 0.125),
    ({"mechanism": "mx:gdn"}, 0.0),                   # a lost scope reads 0
    ({"detail": "mx:gdn:local"}, 0.0),
])
def test_selection_by_mechanism_detail_and_pass(select, seconds):
    got = device_scope_ms.read(OBS, select, rows=ROWS)
    assert got == 1e3 * seconds / 4
    share = device_scope_ms.read(OBS, select, share=True, rows=ROWS)
    assert share == 100.0 * seconds / 16.0


def test_nothing_to_read(monkeypatch):
    select = {"mechanism": "mx:attn"}
    assert device_scope_ms.read({"trace": None, "steps": 4}, select) is None
    assert device_scope_ms.read({"steps": 4}, select, rows=ROWS) is None
    from mxnet_tpu.observability import instrument
    # no table captured (an untraced fit, telemetry off)
    monkeypatch.setattr(instrument, "_op_scopes", {})
    assert device_scope_ms.read(OBS, select) is None
    # the parent commit's shape: no such function
    monkeypatch.delattr(instrument, "device_op_scopes")
    assert device_scope_ms.read(OBS, select) is None
    monkeypatch.delattr(instrument, "device_seconds_by_scope")
    assert device_scope_ms.read(OBS, select) is None


def test_reads_through_the_captured_table(monkeypatch):
    from mxnet_tpu.observability import instrument
    row = instrument.scope_of_op_name
    monkeypatch.setattr(instrument, "_op_scopes", {"fused@x": {
        "fusion.1": row("jit(_step)/jvp(mx:head)/dot_general"),
        "fusion.2": row("jit(_step)/mx:update/sub")}})
    obs = {"steps": 2, "trace": {"op_seconds": {
        "fusion.1 fusion": 0.5, "fusion.2 fusion": 0.25, "copy.7 copy": 0.25}}}
    assert device_scope_ms.read(obs, {"mechanism": "mx:head"}) == 250.0
    assert device_scope_ms.read(obs, {"mechanism": "mx:update"}) == 125.0
    assert device_scope_ms.read(obs, {"mechanism": None}, share=True) == 25.0


def test_the_twelve_files_and_their_manifest_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert list(entries)[-12:] == list(METRICS)       # appended, in order
    cells = [w["name"] for w in manifest["workloads"]]
    for name, select in METRICS.items():
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "benchmark.readers.device_scope_ms"
        assert spec["args"]["select"] == select
        assert bool(spec["args"].get("share")) == name.endswith("_share")
        entry = entries[name]
        assert entry["source"] == "device_trace" \
            and entry["better"] == "lower" \
            and entry["moves"] == "train_samples_per_s"
        assert entry["workloads"] and set(entry["workloads"]) <= set(cells)
    assert entries["fit_device_unscoped_share"]["workloads"] == cells
    assert entries["fit_device_ms_per_step_update"]["workloads"] == cells


# -- the toy cells, traced -------------------------------------------------------

TOYS = [("toy-train", toy), ("toy-train-dp4", toy), ("toy-train-lm", toy_lm),
        (toy_trinity.CELL, toy_trinity), (toy_joyai.CELL, toy_joyai)]


@pytest.fixture(scope="module")
def manifests(tmp_path_factory):
    made = {}

    def get(maker):
        if maker not in made:
            tmp = tmp_path_factory.mktemp(maker.__name__.rsplit(".", 1)[-1])
            sys.path.insert(0, str(tmp))        # the toy metric's reader
            made[maker] = maker.make(tmp)
        return made[maker]
    return get


@pytest.mark.parametrize("cell, maker", TOYS, ids=[c for c, _ in TOYS])
def test_traced_toy_cell_reads_every_device_metric_it_lists(
        cell, maker, manifests, monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    manifest = manifests(maker)
    args = argparse.Namespace(workload=cell, seed=2600000003, seconds=1.0,
                              trace=1)
    seen, plain = {}, harness.read_per_layer
    monkeypatch.setattr(harness, "read_per_layer", lambda loaded, obs: (
        seen.update(obs=obs), plain(loaded, obs))[1])
    result, _, _ = run.run_cell(args, manifest_path=manifest,
                                require_chip=False)
    with open(manifest) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]
                  if cell in m.get("workloads", [cell])}
    want = listed & set(METRICS)
    assert {"fit_device_unscoped_share",
            "fit_device_ms_per_step_update"} <= want
    got = result["metrics"]
    assert want <= set(got), want - set(got)
    assert not (set(METRICS) - want) & set(got)     # and no other cell's
    lm = maker is not toy       # ``toy.py`` lists every ``fit_`` metric
    assert lm or want == set(METRICS)
    # all the window's device seconds: the busy time on a chip, whose core
    # runs one operation at a time (a CPU's threads run several, and a loop's
    # event holds its body's)
    busy_ms = 1e3 * sum(seen["obs"]["trace"]["op_seconds"].values()) \
        / result["attempted"]
    parts = [got["fit_device_ms_per_step_" + k]["value"] for k in DISJOINT
             if "fit_device_ms_per_step_" + k in got]
    assert all(v >= 0 for v in parts) and 0 < sum(parts) <= busy_ms * 1.001
    assert got["fit_device_ms_per_step_update"]["value"] > 0
    # the join holds: nearly every device second finds its instruction
    assert 0 <= got["fit_device_unscoped_share"]["value"] < 5
    value = lambda k: got["fit_device_ms_per_step_" + k]["value"]
    if lm:
        assert 0 < value("recomputed") < busy_ms
        assert 0 < value("moe_rows") < value("moe")
        assert value("attn") > 0 and value("head") > 0
    else:
        assert value("conv") > 0 and value("bn") > 0
        # a scope the program does not hold reads nought, not a gap
        assert value("recomputed") == value("moe") == value("gdn") == 0
    if "fit_device_ms_per_step_gdn_local" in want and lm:
        assert 0 < value("gdn_local") < value("gdn")
    assert result["correct"] is True
