"""``sdar-train-bd4-s8k-b1`` rehearsed end to end at toy size on the CPU up
to the result line, its fp8 control, and the mistakes a block-diffusion
objective invites, each of which has to come out as not correct."""
import argparse
import json

import numpy as np
import pytest

from benchmark import harness, peaks, run
from benchmark.references import lowprec, sdar
from benchmark.tests import toy_sdar
from benchmark.tools import calibrate_bd

CELL = toy_sdar.CELL


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return toy_sdar.make(tmp_path_factory.mktemp("toybd"))


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
    assert set(line["compared"]) == set(toy_sdar.LIMITS)


def test_traced_line_reads_the_per_layer_metrics(manifest, cpu_peak_row):
    result, _, _ = rehearse(manifest, trace=1, seed=2500000001)
    got = result["metrics"]
    with open(manifest) as f:
        want = {m["name"] for m in json.load(f)["per_layer"]}
    # no flash kernel runs on the CPU: its two roofline shares have nothing
    # to read there and are left out, as on a checkout without the kernels
    assert want - set(got) == {"fit_flash_attn_fwd_roofline",
                               "fit_flash_attn_bwd_roofline"}
    assert got["fit_retraces_in_window"]["value"] == 0
    # 2 layers, each half of a block (attention, expert layer) a stage
    assert got["fit_recompute_blocks_per_step"]["value"] == 4
    # 4 of 16 experts held: a quarter of the choices, give or take sampling
    assert 15 < got["fit_moe_held_selection_share"]["value"] < 35
    # t ~ U[0.001, 1]: about half the noisy positions are masked
    assert 30 < got["fit_bd_masked_share"]["value"] < 70
    # the XLA reference computes all 96 x 96 scores both ways; the mask lets
    # 16 x 12 x 12 + 48 x 4 = 2,496 through, once each way
    assert got["fit_attn_pairs_computed_over_visible"]["value"] \
        == pytest.approx(96 * 96 / 2496.0)
    assert 0 < got["fit_step_mfu"]["value"]
    assert result["correct"] is True


def _instead_of_the_program(manifest, **how):
    """The numbers compared when the reference, altered, stands where the
    program stood, each beside its limit."""
    numbers = calibrate_bd.readings(
        harness.load_cell(CELL, manifest),
        harness.find_chip(1, require_chip=False), 2147483659,
        [("altered", how)])["altered"]
    return {k: [numbers[k], v] for k, v in toy_sdar.LIMITS.items()}


def test_fp8_control_reads_above_the_program(manifest, plain):
    control = _instead_of_the_program(
        manifest, hooks=(lowprec.q_operand, lowprec.q_cotangent))
    assert not harness.checks_ok(control), control
    program = plain[1]
    assert any(control[k][0] >= 3 * program[k][0] for k in toy_sdar.LIMITS)


@pytest.mark.parametrize("fault", sdar.FAULTS)
def test_a_planted_fault_is_not_correct(manifest, fault):
    checks = _instead_of_the_program(manifest, fault=fault)
    assert not harness.checks_ok(checks), checks


@pytest.mark.parametrize("fault", sorted(sdar.WEIGHT_FAULTS))
def test_a_weight_fault_is_the_plain_reference_on_altered_labels(manifest,
                                                                 fault):
    """What the calibration reads for the weight's faults without compiling
    them: the same numbers as the fault planted in the loss."""
    relabelled = _instead_of_the_program(
        manifest, relabel=sdar.WEIGHT_FAULTS[fault])
    planted = _instead_of_the_program(manifest, fault=fault)
    assert relabelled.keys() == planted.keys()
    for k, (value, _) in planted.items():
        assert relabelled[k][0] == pytest.approx(value, rel=1e-4, abs=1e-7)


def _t_per_token(ids, rng, block_length, mask_id, t_min):
    rows, length = ids.shape
    t = rng.uniform(t_min, 1.0, size=(rows, length))
    masked = rng.random((rows, length)) < t
    weight = np.zeros((rows, 2 * length), np.float32)
    weight[:, :length] = np.where(masked, 1.0 / t, 0.0)
    data = np.concatenate([np.where(masked, mask_id, ids), ids], axis=1)
    return data.astype(np.float32), weight


def _weight_one(ids, rng, block_length, mask_id, t_min):
    data, weight = mx_noise(ids, rng, block_length, mask_id, t_min)
    return data, (weight > 0).astype(np.float32)


def _clean_half_masked(ids, rng, block_length, mask_id, t_min):
    data, weight = mx_noise(ids, rng, block_length, mask_id, t_min)
    half = ids.shape[1]
    data[:, half:] = np.where(weight[:, :half] > 0, mask_id, ids)
    return data, weight


def mx_noise(*args):
    from mxnet_tpu.models.sdar import noise
    return noise(*args)


@pytest.mark.parametrize("noise, gap", [
    (mx_noise, 0), (_t_per_token, 1), (_weight_one, 1),
    (_clean_half_masked, 1)])
def test_the_ring_is_held_to_the_references_own_noising(manifest, noise, gap):
    """The program's noising against ``references/sdar.py:noise`` on the
    same ids and seed: equal, and a fault of the noise (``t`` drawn a token,
    the ``1/t`` weight lost, the clean half masked) reads above the limit
    0."""
    from benchmark.drivers import fit_lm, fit_lm_bd
    loaded = harness.load_cell(CELL, manifest)
    model = fit_lm.model_of(loaded["config"])
    mix = loaded["traffic"]
    ring = fit_lm_bd.bd_ring(mix, model, 2147483659, noise)
    got = fit_lm_bd.noise_gap(mix, model, 2147483659, *ring, sdar)
    assert (got > toy_sdar.LIMITS["noise_gap"]) == bool(gap), got
