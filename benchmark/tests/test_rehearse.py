"""Every cell rehearsed end to end at toy size on the CPU (the dp cell on
four virtual devices) up to the result line; the look for a chip; and the
timed path broken underneath, which has to come out as not correct."""
import argparse
import json
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness, peaks, run
from benchmark.tests import toy

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "compared"}


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("toybench")
    sys.path.insert(0, str(tmp))        # the toy metric's reader
    return toy.make(tmp)


@pytest.fixture(autouse=True)
def cpu_peak_row(monkeypatch):
    # a rehearsal's "device" numbers are never reported; the readers still
    # have to run, and they refuse a kind with no peak row
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))


def rehearse(manifest, workload, trace=0, seed=2147483659, fault=None,
             seconds=1.0):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace)
    return run.run_cell(args, manifest_path=manifest, require_chip=False,
                        fault=fault)


def last_line(result, checks, capsys):
    harness.emit(result, checks)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,metrics", [
    ("toy-train", {"train_samples_per_s", "setup_s"}),
    ("toy-train-dp4", {"train_samples_per_s", "setup_s"}),
    ("toy-decode", {"decode_output_tokens_per_s", "decode_ttft_p95_ms",
                    "decode_itl_p95_ms", "setup_s"}),
])
def test_end_to_end_line(manifest, workload, metrics, capsys):
    result, checks, _ = rehearse(manifest, workload)
    line = last_line(result, checks, capsys)
    assert set(line) == LINE_KEYS and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == metrics
    assert all(m["value"] > 0 and set(m) == {"value", "unit"}
               for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["count"] == (4 if workload.endswith("dp4") else 1)


@pytest.mark.parametrize("workload,has,lacks", [
    ("toy-train", {"fit_step_ms_p50", "fit_step_mfu", "fit_device_idle_share",
                   "fit_retraces_in_window", "compile_s", "toy_steps"},
     {"fit_collective_exposed_share", "decode_step_mfu"}),
    ("toy-train-dp4", {"fit_collective_exposed_share", "fit_step_mfu",
                       "fit_device_idle_share"},
     {"fit_pallas_roofline", "toy_steps"}),
    ("toy-decode", {"decode_step_mfu", "decode_step_hbm_roofline",
                    "decode_device_idle_share", "decode_host_ms_per_iter",
                    "decode_ttft_ms_per_prompt_token",
                    "decode_retraces_in_window", "decode_step_device_ms"},
     {"fit_step_mfu"}),
])
def test_traced_line(manifest, workload, has, lacks, capsys):
    result, checks, _ = rehearse(manifest, workload, trace=1)
    line = last_line(result, checks, capsys)
    assert set(line) == LINE_KEYS | {"breakdown"}
    assert has <= set(line["metrics"]) and not lacks & set(line["metrics"])
    assert line["metrics"][[k for k in line["metrics"]
                            if k.endswith("retraces_in_window")][0]][
        "value"] == 0
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in line["breakdown"].values())
    assert line["correct"] is True


def test_command_refuses_a_backend_that_is_no_tpu():
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "resnet50-train-b256", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=toy.ROOT, capture_output=True, text=True, timeout=300)
    assert out.returncode == 2 and out.stdout.strip() == ""
    assert "not a TPU" in out.stderr


def test_unknown_workload_is_refused(manifest):
    with pytest.raises(harness.Refused):
        rehearse(manifest, "no-such-cell")


# -- the timed path broken underneath ------------------------------------------

def _patch_run(monkeypatch, wrapper):
    from mxnet_tpu.module import fused_step
    real = fused_step.FusedTrainStep.run
    monkeypatch.setattr(fused_step.FusedTrainStep, "run",
                        lambda self, batch: wrapper(real, self, batch))


def _state_unchanged(real, step, batch):
    masters, states = list(step._masters), list(step.states)
    real(step, batch)
    step._masters, step.states = masters, states


def _only_first_rows(share):
    """The step computed on the first ``share`` of the batch alone (the
    other rows left out, the mean taken over the rest): what one chip of
    ``1/share`` computes when the exchange is left out."""
    def wrapper(real, step, batch):
        import mxnet_tpu as mx
        for arrays in (batch.data, batch.label):
            for i, a in enumerate(arrays):
                host = a.asnumpy()
                keep = host[:int(len(host) * share)]
                arrays[i] = mx.nd.array(np.concatenate(
                    [keep] * int(round(1 / share))))
        real(step, batch)
    return wrapper


@pytest.mark.parametrize("workload,wrapper", [
    ("toy-train", _state_unchanged),
    ("toy-train", _only_first_rows(0.5)),
    ("toy-train-dp4", _only_first_rows(0.25)),
], ids=["state-unchanged", "half-the-batch-left-out",
        "exchange-between-chips-left-out"])
def test_a_broken_training_step_is_not_correct(manifest, monkeypatch,
                                               workload, wrapper):
    _patch_run(monkeypatch, wrapper)
    result, checks, _ = rehearse(manifest, workload)
    assert result["correct"] is False
    assert any(v > limit for v, limit in checks.values())


def test_an_altered_token_is_not_correct(manifest):
    def fault(dec):
        real = dec._step_fn

        def altered(*a):
            k, v, nxt, logits = real(*a)
            return k, v, (nxt + 1) % dec.vocab_size, logits
        dec._step_fn = altered
    result, checks, _ = rehearse(manifest, "toy-decode", fault=fault)
    assert result["correct"] is False
    assert checks["logit_gap_max"][0] > checks["logit_gap_max"][1]
