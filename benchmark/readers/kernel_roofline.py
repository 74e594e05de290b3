"""A kernel family's share of its HBM roofline: the bytes its calls must
move in the traced window (benchmark/shapes.py) over the published
bandwidth, over the summed device time of the operations whose name matches
``pattern``.  Nothing to read (None) where no such operation ran."""
from .. import peaks, trace_reduce


def read(obs, pattern):
    trace = obs.get("trace")
    need = obs.get("kernel_bytes", {}).get(pattern)
    if not trace or not need:
        return None
    seconds = trace_reduce.op_seconds_matching(trace, pattern)
    if seconds <= 0:
        return None
    bandwidth = peaks.peak(obs["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bandwidth) / seconds
