"""The readers of what the program itself records: the window found in a
synthetic ring of step records, and the device's idle gaps charged to the
program's ``mx:`` spans on a recording written by hand."""
import json
import os

import numpy as np
import pytest

from benchmark import harness, trace_reduce
from benchmark.readers import program_steps
from benchmark.tools import idle_by_program_span as tool

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = ("fit_starved_ms_per_step", "fit_starved_in_input_ms_per_step",
         "fit_starved_in_dispatch_ms_per_step", "fit_steps_run_ahead")


def ring_of(lengths, closing_ms=None, start=100.0):
    """Records whose ends are ``lengths`` apart; ``closing_ms`` {step: ms}
    is what that step's callback did after the harness read its clock."""
    ring, t = [], start
    for k, length in enumerate(lengths):
        t += length
        ring.append({
            "step": k, "epoch": 0, "total_ms": 1.0, "components_ms": {},
            "end_s": t + (closing_ms or {}).get(k, 0.0) / 1e3,
            "phases_ms": {"sync:callbacks": 7.0},
            "starved_ms": 10.0 + k, "ran_ahead": k == 7,
            "starved_by_ms": {"step:data_wait": 1.0, "fused:load": 4.0 + k,
                              "fused:dispatch": 2.0, "step:glue": 3.0}})
    return ring


RNG = np.random.RandomState(5)
LENGTHS = list(0.36 + 0.04 * RNG.rand(12))
# the step that closes the window snapshots the registry in its callback
# after reading the clock: its record ends 5 ms later than the harness's
CLOSING = {8: 5.0}


def test_reader_finds_the_planted_window():
    ring = ring_of(LENGTHS, CLOSING)
    # the window opens in step 3's callback: steps 4..8 are in it, the first
    # of them measured from the harness's own opening instant
    obs = {"step_seconds": np.array([LENGTHS[4] - 0.04] + LENGTHS[5:9])}
    found = program_steps.window_records(obs["step_seconds"], ring)
    assert [r["step"] for r in found] == [4, 5, 6, 7, 8]
    read = lambda **kw: program_steps.read(obs, ring=ring, **kw)
    assert read(what="starved_ms") == pytest.approx(10.0 + 6.0)
    assert read(what="starved_ms", under=["step:data_wait", "fused:load"]) \
        == pytest.approx(1.0 + 4.0 + 6.0)
    assert read(what="starved_ms", under=["fused:dispatch", "absent"]) \
        == pytest.approx(2.0)
    assert read(what="ran_ahead") == 1


@pytest.mark.parametrize("why", ["lengths_differ", "last_step_shorter",
                                 "twice", "no_ring", "no_starved_figure",
                                 "one_step"])
def test_reader_returns_none_and_does_not_raise(why):
    ring = ring_of(LENGTHS, CLOSING)
    lengths = [0.3] + LENGTHS[5:9]
    if why == "lengths_differ":
        lengths[2] += 0.002
    elif why == "last_step_shorter":    # a record never ends before the
        lengths[-1] += 0.002 + 0.005    # harness has read its clock
    elif why == "twice":        # every step alike: two offsets fit
        ring = ring_of([0.4] * 12)
        lengths = [0.4] * 5
    elif why == "no_ring":
        ring = []
    elif why == "no_starved_figure":
        ring[6]["starved_ms"] = None
    elif why == "one_step":
        lengths = lengths[:1]
    obs = {"step_seconds": np.array(lengths)}
    assert program_steps.read(obs, what="starved_ms", ring=ring) is None
    # nor where the driver hands no step lengths (the decode driver)
    assert program_steps.read({}, what="ran_ahead", ring=ring) is None


def test_the_four_metric_files_load_through_the_harness(monkeypatch):
    loaded = harness.load_cell("resnet50-train-b256")
    per_layer = {m["name"]: m for m in harness.metrics_for(loaded,
                                                          "per_layer")}
    assert set(NAMES) <= set(per_layer)
    ring = ring_of(LENGTHS, CLOSING)
    monkeypatch.setattr(program_steps, "_ring", lambda: ring)
    obs = {"step_seconds": np.array([0.3] + LENGTHS[5:9])}
    out = harness.read_per_layer(loaded, obs)
    assert out["fit_starved_ms_per_step"] == {"value": 16.0, "unit": "ms"}
    assert out["fit_starved_in_input_ms_per_step"]["value"] == 11.0
    # only fused:dispatch of the dispatch spans is in the synthetic ring
    assert out["fit_starved_in_dispatch_ms_per_step"]["value"] == 2.0
    assert out["fit_steps_run_ahead"] == {"value": 1.0, "unit": "count"}
    # a program that keeps no such records (the parent): left out, no raise
    monkeypatch.setattr(program_steps, "_ring", lambda: None)
    assert not set(NAMES) & set(harness.read_per_layer(loaded, obs))


def test_idle_gaps_by_program_span_on_the_hand_written_recording():
    with open(os.path.join(HERE, "data",
                           "program_spans_recording.json")) as f:
        recording = json.load(f)
    found = tool.attribute(recording)
    us = 1e-6
    assert found["window_s"] == pytest.approx(420 * us)
    assert found["steps"] == 2
    # busy 0-50, 51-100, 140-240, 300-400: idle 1 + 40 + 60 + 20
    assert found["idle_s"] == pytest.approx(121 * us)
    by = dict(found["by_span"])
    want = {
        "mx:fused:load": 18 + 30, "mx:fused:dispatch": 10 + 10,
        "mx:step:metric": 5 + 10, "mx:step:fwd_bwd_dispatch": 2 + 2,
        # the step's own time: between its components
        "mx:step": 5 + 3 + 5, "mx:step:sync": 1 + 1,
        "mx:sync:callbacks": 3,
        # 100-105, 250-255 and 415-420 lie between two steps
        tool.OUTSIDE: 5 + 5 + 5, tool.BETWEEN: 1}
    assert set(by) == set(want)
    for name, micros in want.items():
        assert by[name] == pytest.approx(micros * us), name
    assert found["outside_share"] == pytest.approx(15 / 121)
    assert "mx:fused:load" in tool.table(found)
    # the harness's reduction puts all but the between-ops hair outside its
    # own spans: what the tool is for
    gaps = dict(trace_reduce.reduce(recording)["idle_gaps"])
    assert gaps["host:outside_benchmark_spans"] == pytest.approx(120 * us)


def test_the_tool_says_so_when_there_is_nothing_to_read(tmp_path, capsys):
    path = tmp_path / "empty.json"
    path.write_text(json.dumps({"planes": []}))
    assert tool.main([str(path)]) == 1
    assert "no device operation" in capsys.readouterr().err
