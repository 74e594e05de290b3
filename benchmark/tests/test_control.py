"""The controls, at a size a test run can hold: the plain reference
recomputed in the precision below the one the configuration states, put in
the program's place, has to read well above what the program reads."""
import argparse

import numpy as np
import pytest

from benchmark import compare, harness
from benchmark.compile_clock import CompileClock
from benchmark.references import lowprec
from benchmark.tests import toy


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return toy.make(tmp_path_factory.mktemp("toycontrol"))


def drive(manifest, workload, seed):
    import importlib
    import time
    loaded = harness.load_cell(workload, manifest)
    devices = harness.find_chip(1, require_chip=False)
    driver = importlib.import_module(
        "benchmark.drivers." + loaded["traffic"]["driver"])
    args = argparse.Namespace(workload=workload, seed=seed, seconds=0.5,
                              trace=0)
    out = driver.run(loaded, args, devices, harness.Spans(False),
                     harness.Tracer(False), CompileClock(),
                     time.perf_counter())
    return loaded, devices, driver, out


@pytest.mark.parametrize("seed", [1, 2147483700, 2500000000])
def test_fp8_training_reads_above_the_program(manifest, seed):
    loaded, devices, driver, out = drive(manifest, "toy-train", seed)
    w0, ring_x, ring_y = out["inputs"]
    r = out["refs"]
    losses, g, d = driver.reference_norms(
        loaded["config"], loaded["traffic"], devices, w0, ring_x, ring_y,
        r["names"], hooks=(lowprec.q_operand, lowprec.q_cotangent))
    control, _ = compare.training_numbers(
        losses, r["losses"], g, r["grad_norms"], d, r["update_norms"])
    program = out["numbers"]
    limits = toy.TRAIN_LIMITS
    assert harness.checks_ok({k: [program[k], limits[k]] for k in limits})
    assert not harness.checks_ok({k: [control[k], limits[k]]
                                  for k in limits}), control
    assert any(control[k] >= 3 * program[k] for k in limits)


@pytest.mark.parametrize("seed", [3, 2147483711, 2600000000])
def test_fp8_decoding_reads_above_the_program(manifest, seed):
    loaded, _, driver, out = drive(manifest, "toy-decode", seed)
    served, control = driver.served_gaps(
        loaded["config"], loaded["traffic"], out["weights"], out["sample"],
        lowprec.q_operand)
    assert len(served) == len(control) > 0
    assert served.max() <= toy.DECODE_LIMITS["logit_gap_max"]
    assert control.max() > toy.DECODE_LIMITS["logit_gap_max"]
    assert control.max() >= 3 * max(served.max(), 1e-6)


def test_fake_fp8_rounds_as_the_format_does():
    import jax.numpy as jnp
    x = jnp.asarray([448.0, 240.0, 17.0, 1.0, 0.0, -3.3], jnp.float32)
    got = np.asarray(lowprec.fake_fp8(x, lowprec.E4M3))
    # e4m3 keeps 3 mantissa bits: 17 -> 16, 3.3 -> 3.25, the rest exact
    assert got.tolist() == [448.0, 240.0, 16.0, 1.0, 0.0, -3.25]
    got = np.asarray(lowprec.bf16(jnp.asarray([1.00390625, 3.0])))
    assert got.tolist() == [1.0, 3.0]
