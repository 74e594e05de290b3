"""chip_smoke.py at toy size on the CPU (the on-chip guide's rule: make the
command run end to end here first).  The same phase functions the chip run
drives at full width — control flow, assertions and reports are checked
here; every time, rate and device fact comes only from the chip run."""
import importlib.util
import json
import os
import subprocess
import sys

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_phases_run_at_toy_size():
    smoke = _load()
    report, trained = smoke.train_phase(smoke.TOY, [mx.cpu(0)])
    assert report["fused_step_ran"] and report["param_platforms"] == ["cpu"]
    assert report["loss_last"] < report["loss_first"]
    served = smoke.serve_phase(smoke.TOY, trained)
    assert served["retraces_after_warmup"] == 0
    assert served["max_rel_logprob_diff_vs_cpu"] <= served["tolerance"]
    assert served["argmax_checked"] > 0
    decoded = smoke.decode_phase(smoke.TOY)
    assert decoded["tokens_checked"] > 0
    assert decoded["prefix_pages"] == [2, 1] and decoded["cow_clones"] == 1


def test_train_phase_data_parallel_holds_an_all_reduce():
    smoke = _load()
    report, _ = smoke.train_phase(
        smoke.TOY, [mx.cpu(i) for i in range(4)], kvstore="tpu_ici")
    assert len(set(report["shard_devices"])) == 4
    assert report["collectives"]["all-reduce"] >= 1


def test_result_line_holds_exactly_the_contract_keys():
    """The driver rejects any other key on the last line of stdout."""
    smoke = _load()
    device = dict(smoke.device_report())
    assert "versions" in device          # extra keys in must not leak out
    line = smoke.result_line(device)
    assert "\n" not in line
    got = json.loads(line)
    assert sorted(got) == ["device", "ok"] and got["ok"] is True
    assert sorted(got["device"]) == ["count", "kind", "platform"]
    assert isinstance(got["device"]["count"], int)
    assert isinstance(got["device"]["platform"], str)
    assert isinstance(got["device"]["kind"], str)


def test_refuses_the_cpu_without_running_a_phase():
    """``JAX_PLATFORMS=cpu python chip_smoke.py``: non-zero exit, no result
    line, no phase started."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "refuses" in proc.stderr
