"""``granite-h-train-s8k-b1`` rehearsed end to end at toy size on the CPU up
to the result line, its fp8 control, and the mistakes a Mamba-2 / attention
hybrid invites, each of which has to come out as not correct."""
import argparse
import json

import pytest

from benchmark import harness, peaks, run
from benchmark.references import granite_hybrid, lowprec
from benchmark.tests import toy_granite
from benchmark.tools import calibrate_lm

CELL = toy_granite.CELL


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return toy_granite.make(tmp_path_factory.mktemp("toyssm"))


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
    result, checks, notes = plain
    harness.emit(result, checks)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert set(line["compared"]) == set(toy_granite.LIMITS)
    # on the CPU every lowering of the recurrence is the lax.scan's
    lowered = [n for n in notes if n.startswith("lowerings of")]
    assert lowered and "ops.ssm.lowered_kernel 0" in lowered[0]


def test_traced_line_reads_the_per_layer_metrics(manifest, cpu_peak_row):
    result, _, _ = rehearse(manifest, trace=1, seed=2500000001)
    got = result["metrics"]
    with open(manifest) as f:
        want = {m["name"] for m in json.load(f)["per_layer"]}
    # no state-space kernel runs on the CPU: their roofline shares have
    # nothing to read there and are left out, as on a checkout without them
    assert want - set(got) == {"fit_ssm_scan_fwd_roofline",
                               "fit_ssm_scan_bwd_roofline"}
    assert got["fit_retraces_in_window"]["value"] == 0
    # 6 layers, each half of a block (mixer, MLP) a stage of its own
    assert got["fit_recompute_blocks_per_step"]["value"] == 12
    assert got["fit_device_ms_per_step_ssm"]["value"] > 0
    assert 0 < got["fit_step_mfu"]["value"]
    assert result["correct"] is True


def _instead_of_the_program(manifest, **how):
    """The numbers compared when the reference, altered, stands where the
    program stood, each beside its limit."""
    numbers = calibrate_lm.readings(
        harness.load_cell(CELL, manifest),
        harness.find_chip(1, require_chip=False), 2147483659,
        [("altered", how)])["altered"]
    return {k: [numbers[k], v] for k, v in toy_granite.LIMITS.items()}


def test_fp8_control_reads_above_the_program(manifest, plain):
    control = _instead_of_the_program(
        manifest, hooks=(lowprec.q_operand, lowprec.q_cotangent))
    assert not harness.checks_ok(control), control
    program = plain[1]
    assert any(control[k][0] >= 3 * program[k][0]
               for k in toy_granite.LIMITS)


@pytest.mark.parametrize("fault", granite_hybrid.FAULTS)
def test_a_planted_fault_is_not_correct(manifest, fault):
    checks = _instead_of_the_program(manifest, fault=fault)
    assert not harness.checks_ok(checks), checks
