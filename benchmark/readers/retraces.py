"""Retraces the program counted inside the window
(``executor_cache.trace_counts`` at its two edges)."""


def read(obs):
    return obs.get("retraces_in_window")
