"""1 - the union of device-operation intervals over the traced window, on
the fullest device."""


def read(obs):
    trace = obs.get("trace")
    return None if not trace else 100.0 * trace["idle_share_fullest"]
