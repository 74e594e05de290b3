"""``trinity-mini-train-s8k-b1`` rehearsed end to end at toy size on the CPU
up to the result line, its fp8 control, and the mistakes a model of window and
full layers with a sigmoid router invites, each of which has to come out as
not correct."""
import argparse
import json

import pytest

from benchmark import harness, peaks, run
from benchmark.references import lowprec, trinity
from benchmark.tests import toy_trinity
from benchmark.tools import calibrate_lm

CELL = toy_trinity.CELL


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return toy_trinity.make(tmp_path_factory.mktemp("toyswa"))


@pytest.fixture(autouse=True)
def cpu_peak_row(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))


def rehearse(manifest, trace=0, seed=2147483659):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=1.0,
                              trace=trace)
    return run.run_cell(args, manifest_path=manifest, require_chip=False)


@pytest.fixture(scope="module")
def plain(manifest):
    return rehearse(manifest)


def test_end_to_end_line(plain, capsys):
    result, checks, _ = plain
    harness.emit(result, checks)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert set(line["compared"]) == set(toy_trinity.LIMITS)


def test_traced_line_reads_the_per_layer_metrics(manifest, cpu_peak_row):
    result, _, _ = rehearse(manifest, trace=1, seed=2500000001)
    got = result["metrics"]
    with open(manifest) as f:
        want = {m["name"] for m in json.load(f)["per_layer"]}
    # no flash kernel runs on the CPU: its roofline share has nothing to
    # read there and is left out, as on a checkout without the kernel
    assert want - set(got) == {"fit_flash_attn_fwd_roofline"}
    assert got["fit_retraces_in_window"]["value"] == 0
    # 4 layers, each half of a block (attention, MLP) a stage of its own
    assert got["fit_recompute_blocks_per_step"]["value"] == 8
    # 4 of 16 experts held: a quarter of the choices, give or take sampling
    assert 15 < got["fit_moe_held_selection_share"]["value"] < 35
    assert got["fit_moe_expert_load_max_over_mean"]["value"] >= 1
    # the XLA reference computes all 96 x 96 scores both ways; the masks let
    # 3 x (528 + 64 x 32) + 4,656 through, once each way
    assert got["fit_attn_pairs_computed_over_visible"]["value"] \
        == pytest.approx(4 * 96 * 96 / (3 * 2576 + 4656.0))
    assert 0 < got["fit_step_mfu"]["value"]
    assert result["correct"] is True


def _instead_of_the_program(manifest, **how):
    """The numbers compared when the reference, altered, stands where the
    program stood, each beside its limit."""
    numbers = calibrate_lm.readings(
        harness.load_cell(CELL, manifest),
        harness.find_chip(1, require_chip=False), 2147483659,
        [("altered", how)])["altered"]
    return {k: [numbers[k], v] for k, v in toy_trinity.LIMITS.items()}


def test_fp8_control_reads_above_the_program(manifest, plain):
    control = _instead_of_the_program(
        manifest, hooks=(lowprec.q_operand, lowprec.q_cotangent))
    assert not harness.checks_ok(control), control
    program = plain[1]
    assert any(control[k][0] >= 3 * program[k][0]
               for k in toy_trinity.LIMITS)


@pytest.mark.parametrize("fault", trinity.FAULTS)
def test_a_planted_fault_is_not_correct(manifest, fault):
    checks = _instead_of_the_program(manifest, fault=fault)
    assert not harness.checks_ok(checks), checks
