"""Median over the window's requests of time to first token over the prompt
tokens that were not served from the prefix cache."""
import numpy as np


def read(obs):
    rows = obs.get("ttft_rows")
    if rows is None or not len(rows):
        return None
    ttft_s, prefilled = np.asarray(rows, np.float64).T
    return 1e3 * float(np.median(ttft_s / np.maximum(prefilled, 1.0)))
