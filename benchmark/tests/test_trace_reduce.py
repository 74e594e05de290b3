"""The reduction from a recording to numbers: on a recording written by
hand, whose numbers are worked out by hand below, and on a slice of a real
one (82 ms round a step boundary of ``resnet50-train-b256`` on a v5e, chip
run of PR 24; device events under their short names, host events cut to the
harness's own spans), whose numbers were written down when it was cut and
are checked against a brute-force rasterisation too."""
import json
import os

import numpy as np
import pytest

from benchmark import trace_reduce as tr

US = 1000.0     # the recording's unit is the nanosecond


def ev(name, start_us, end_us):
    return [name, start_us * US, (end_us - start_us) * US, False]


HAND = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ev("fusion.1 fusion", 0, 10), ev("conv.2 convolution", 10, 30),
            ev("all-reduce-done.1 all-reduce-done", 50, 60),
            ev("fusion.3 fusion", 60, 100)]},
        {"name": "Async XLA Ops", "events": [
            ev("all-reduce-start.1 all-reduce-start", 20, 60)]},
        {"name": "XLA Modules", "events": [ev("jit__step(1)", 0, 100)]}]},
    {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [ev("fusion.1 fusion", 0, 60)]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [
            ev("bench:window", 0, 120), ev("bench:input:next", 28, 40),
            ev("bench:metric:update", 45, 49),
            ev("PjitFunction(step)", 0, 5)]}]},
]}


def test_by_hand():
    r = tr.reduce(HAND)
    us = 1e-6
    assert r["window_s"] == pytest.approx(120 * us)
    # device 0 is busy 10+20+10+40 = 80, device 1 60: mean 70
    assert r["busy_s"] == pytest.approx(70 * us)
    assert r["idle_share"] == pytest.approx(1 - 70 / 120)
    assert r["idle_share_fullest"] == pytest.approx(1 - 80 / 120)
    assert r["devices"] == 2
    # per-op time is the mean over devices
    assert r["op_seconds"]["fusion.1 fusion"] == pytest.approx(35 * us)
    assert r["op_seconds"]["conv.2 convolution"] == pytest.approx(10 * us)
    assert r["device_ops"][0] == ["fusion.1 fusion", pytest.approx(35 * us)]
    # the all-reduce is in flight 20..60 and waited for 50..60: 40 on device
    # 0, of which 30..60 has no other operation beside it; halved by the mean
    assert r["collective_s"] == pytest.approx(20 * us)
    assert r["collective_exposed_s"] == pytest.approx(15 * us)
    # the fullest device idles 30..50 and 100..120: 10 under input:next, 4
    # under metric:update, 6 + 20 outside every span of the harness
    gaps = dict(r["idle_gaps"])
    assert gaps["bench:input:next"] == pytest.approx(10 * us)
    assert gaps["bench:metric:update"] == pytest.approx(4 * us)
    assert gaps["host:outside_benchmark_spans"] == pytest.approx(26 * us)
    assert tr.op_seconds_matching(r, "all-reduce") == pytest.approx(5 * us)


def test_a_gap_between_operations_is_the_devices_own():
    rec = json.loads(json.dumps(HAND))
    rec["planes"][0]["lines"][0]["events"].append(
        ["fusion.4 fusion", 100.5 * US, 19.5 * US, False])
    gaps = dict(tr.reduce(rec)["idle_gaps"])
    assert gaps["device:between_ops"] == pytest.approx(0.5e-6)


def test_nothing_to_read():
    assert tr.reduce({"planes": []}) is None
    no_window = {"planes": [p for p in HAND["planes"]
                            if not p["name"].startswith("/host")]}
    assert tr.reduce(no_window) is None


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) \
        == [(0, 2), (3, 5), (7, 9)]
    assert tr.overlap([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == 4
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_short_names():
    hlo = ('%transpose_jvp.2 = bf16[524288,1,7,7]{3,2,1,0:T(8,128)(2,1)} '
           'custom-call(bf16[524288,1,1]{2,1,0} %reshape.5375), '
           'custom_call_target="tpu_custom_call", operand_layout={}')
    assert tr.short_name(hlo) \
        == "transpose_jvp.2 custom-call:tpu_custom_call"
    assert tr.short_name("%fusion.95 = bf16[2,3]{1,0} fusion(bf16[2,3] %p)") \
        == "fusion.95 fusion"
    assert tr.short_name("bench:window") == "bench:window"


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "recorded_trace.json")) as f:
        return json.load(f)


def test_recorded_slice_as_written_down(recorded):
    r = tr.reduce(recorded)
    assert r["window_s"] == pytest.approx(0.082071646, rel=1e-6)
    assert r["busy_s"] == pytest.approx(0.009994265, rel=1e-6)
    assert r["idle_share_fullest"] == pytest.approx(0.878225118, rel=1e-6)
    assert r["device_ops"][0][0] == "fusion.1205 fusion"
    assert r["device_ops"][0][1] == pytest.approx(0.002015326, rel=1e-6)
    assert tr.op_seconds_matching(r, "custom-call") \
        == pytest.approx(sum(s for n, s in r["op_seconds"].items()
                             if "tpu_custom_call" in n))
    gaps = dict(r["idle_gaps"])
    assert gaps["host:outside_benchmark_spans"] \
        == pytest.approx(0.066706174, rel=1e-6)
    assert gaps["bench:metric:update"] == pytest.approx(0.005370675, rel=1e-6)
    assert r["collective_s"] == 0.0


def test_recorded_slice_against_a_rasterisation(recorded):
    """Busy time and the attribution of idle time, counted cell by cell on a
    100 ns grid: no interval arithmetic shared with the reduction."""
    r = tr.reduce(recorded)
    grid = 100.0
    n = int(round(r["window_s"] * 1e9 / grid))
    busy = np.zeros(n, bool)
    under = {}
    for plane in recorded["planes"]:
        for line in plane["lines"]:
            for name, start, dur, _ in line["events"]:
                a, b = int(round(start / grid)), int(round((start + dur) / grid))
                if plane["name"].startswith("/device") \
                        and line["name"] == "XLA Ops":
                    busy[a:b] = True
                elif name.startswith("bench:") and name != "bench:window":
                    under.setdefault(name, np.zeros(n, bool))[a:b] = True
    assert busy.sum() * grid * 1e-9 == pytest.approx(r["busy_s"], rel=2e-3)
    gaps = dict(r["idle_gaps"])
    for name, mask in under.items():
        want = (mask & ~busy).sum() * grid * 1e-9
        assert gaps.get(name, 0.0) == pytest.approx(want, abs=3e-6)
