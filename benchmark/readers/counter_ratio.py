"""A ratio of two of the program's counters over the window (their values
at its two edges, ``obs["counters"]``), times ``scale``; over the window's
steps where no denominator is named.  None where the program keeps no such
counter (the parent commit) or the denominator did not move."""


def read(obs, numerator, denominator=None, scale=1.0):
    counters = obs.get("counters") or {}
    if numerator not in counters:
        return None
    below = counters.get(denominator) if denominator else obs.get("steps")
    if not below:
        return None
    return scale * counters[numerator] / below
