"""Device-busy ms per iteration of the traced window."""


def read(obs):
    trace = obs.get("trace")
    if not trace or not obs.get("iterations"):
        return None
    return 1e3 * trace["busy_s"] / obs["iterations"]
