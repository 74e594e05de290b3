"""Median length of the window's steps on the host clock, in ms."""
import numpy as np


def read(obs):
    steps = obs.get("step_seconds")
    return None if steps is None or not len(steps) \
        else 1e3 * float(np.median(steps))
