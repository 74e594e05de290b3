"""The whole step's share of the chip's matmul peak: operations the model
REQUIRES in the traced window (benchmark/shapes.py) over the device-busy
seconds of the trace, over chips x the published bf16 peak.  For a float32
model that is still the bf16 peak: the only matmul peak published."""
from .. import peaks


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace["busy_s"] or not obs.get("required_flops"):
        return None
    peak = peaks.peak(obs["device_kind"])["flops_per_s"]
    return 100.0 * obs["required_flops"] / trace["busy_s"] \
        / (obs["chips"] * peak)
