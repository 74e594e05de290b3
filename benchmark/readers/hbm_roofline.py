"""The step's share of the HBM roofline: bytes the iterations of the traced
window must move (benchmark/shapes.py) over the published bandwidth, over
the device-busy seconds of the trace."""
from .. import peaks


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace["busy_s"] or not obs.get("required_bytes"):
        return None
    bandwidth = peaks.peak(obs["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (obs["required_bytes"] / bandwidth) / trace["busy_s"]
