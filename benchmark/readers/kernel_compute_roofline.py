"""A kernel's share of its roofline where either side may bind: the larger of
the operations its calls must do in the traced window over the published
matmul peak and the bytes they must move over the published bandwidth
(``obs["kernel_work"][pattern]``: {"flops", "bytes"}, benchmark/
shapes_window.py), over the summed device time of the operations whose name
matches ``pattern``.  Nothing to read (None) where the driver hands no such
work or no such operation ran."""
from .. import peaks, trace_reduce


def read(obs, pattern):
    trace = obs.get("trace")
    work = (obs.get("kernel_work") or {}).get(pattern)
    if not trace or not work:
        return None
    seconds = trace_reduce.op_seconds_matching(trace, pattern)
    if seconds <= 0:
        return None
    peak = peaks.peak(obs["device_kind"])
    at_the_roof = max(work["flops"] / peak["flops_per_s"],
                      work["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * at_the_roof / seconds
