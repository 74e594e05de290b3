"""Collective time during which no other operation ran on that device, as a
share of the traced window (mean over devices).  None on one chip."""


def read(obs):
    trace = obs.get("trace")
    if not trace or trace["collective_s"] <= 0:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
