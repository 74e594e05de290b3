"""Host clock of ``step()`` minus the device-busy time of its program, per
iteration of the traced window."""


def read(obs):
    trace = obs.get("trace")
    if not trace or not obs.get("iterations"):
        return None
    return 1e3 * (obs["step_call_s"] - trace["busy_s"]) / obs["iterations"]
