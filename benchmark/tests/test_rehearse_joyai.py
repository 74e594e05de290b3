"""``joyai-flash-train-s8k-b1`` rehearsed end to end at toy size on the CPU
up to the result line, its fp8 control, and the mistakes a model of latent
attention with a second loss head invites, each of which has to come out as
not correct or be shown to lie where these numbers cannot see."""
import argparse
import json

import pytest

from mxnet_tpu.observability import telemetry

from benchmark import harness, peaks, run
from benchmark.references import joyai_flash, lowprec
from benchmark.tests import toy_joyai
from benchmark.tools import calibrate_lm

CELL = toy_joyai.CELL
# the head's gradient through the module is one leaf of sixty: no median
# moves, and Adam's normalised step hardly follows a gradient's scale, so the
# whole change does not either.  tests/test_joyai_flash.py holds the program
# to the sum of both uses leaf by leaf
ONE_LEAF = "head_gradient_from_mtp_dropped"
STEP_RECORDS = {"fit_starved_ms_per_step", "fit_starved_in_input_ms_per_step",
                "fit_starved_in_dispatch_ms_per_step", "fit_steps_run_ahead"}


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return toy_joyai.make(tmp_path_factory.mktemp("toymla"))


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
    assert set(line["compared"]) == set(toy_joyai.LIMITS)


def test_traced_line_reads_the_per_layer_metrics(manifest, cpu_peak_row):
    result, _, _ = rehearse(manifest, trace=1, seed=2500000001)
    got = result["metrics"]
    with open(manifest) as f:
        want = {m["name"] for m in json.load(f)["per_layer"]}
    # no flash kernel runs on the CPU: its two roofline shares have nothing
    # to read there and are left out, as on a checkout without the kernels.
    # The per-step records' window is found by step lengths to a millisecond,
    # which a toy's steps on a shared CPU may fit twice: those four are read
    # or left out together (test_program_steps.py holds their reader)
    assert want - set(got) - STEP_RECORDS == {"fit_flash_attn_fwd_roofline",
                                              "fit_flash_attn_bwd_roofline"}
    assert STEP_RECORDS <= want
    assert len(STEP_RECORDS & set(got)) in (0, len(STEP_RECORDS))
    assert got["fit_retraces_in_window"]["value"] == 0
    # 3 blocks and the module's, each half (attention, MLP) a stage
    assert got["fit_recompute_blocks_per_step"]["value"] == 8
    # 4 of 16 experts held: a quarter of the choices, give or take sampling
    assert 15 < got["fit_moe_held_selection_share"]["value"] < 35
    assert got["fit_moe_expert_load_max_over_mean"]["value"] >= 1
    # the XLA reference computes all 96 x 96 scores both ways; the causal
    # mask lets 4,656 through, once each way
    assert got["fit_attn_pairs_computed_over_visible"]["value"] \
        == pytest.approx(96 * 96 / 4656.0)
    # random weights: both heads read about log(vocabulary); a dead module
    # would leave its counter at 0
    snap = telemetry.snapshot()
    main, mtp = (snap["module.lm.loss_" + part]["value"]
                 for part in ("main", "mtp"))
    assert 0.8 < mtp / main < 1.3
    assert 0 < got["fit_step_mfu"]["value"]
    assert result["correct"] is True


def _instead_of_the_program(manifest, **how):
    """Everything compared when the reference, altered, stands where the
    program stood, and the limited numbers each beside its limit."""
    numbers = calibrate_lm.readings(
        harness.load_cell(CELL, manifest),
        harness.find_chip(1, require_chip=False), 2147483659,
        [("altered", how)])["altered"]
    return numbers, {k: [numbers[k], v] for k, v in toy_joyai.LIMITS.items()}


def test_fp8_control_reads_above_the_program(manifest, plain):
    _, control = _instead_of_the_program(
        manifest, hooks=(lowprec.q_operand, lowprec.q_cotangent))
    assert not harness.checks_ok(control), control
    program = plain[1]
    assert any(control[k][0] >= 3 * program[k][0] for k in toy_joyai.LIMITS)


@pytest.mark.parametrize("fault", [f for f in joyai_flash.FAULTS
                                   if f != ONE_LEAF])
def test_a_planted_fault_is_not_correct(manifest, fault):
    _, checks = _instead_of_the_program(manifest, fault=fault)
    assert not harness.checks_ok(checks), checks


def test_a_fault_in_one_leaf_shows_in_that_leaf_alone(manifest):
    numbers, checks = _instead_of_the_program(manifest, fault=ONE_LEAF)
    assert harness.checks_ok(checks), checks
    assert numbers["grad_norm_gap"] > 0.02 \
        and numbers["grad_norm_gap_median"] == 0
