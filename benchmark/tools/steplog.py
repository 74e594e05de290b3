"""Off the chip, from the per-step logs alone (``BENCH_STEP_LOG``: the
window's opening instant, then every step's end): the rate over the first
10, 20, 30, 40 and all seconds of each run, cut on step boundaries, and what
the spread between runs is made of.  ``python3 -m benchmark.tools.steplog
<batch> <log.npy> ...``"""
from __future__ import annotations

import statistics
import sys

import numpy as np


def spread(values):
    """Distance between the first and third quartile over the median."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def rate_over(log, seconds, batch):
    """Samples per second of the steps up to the first that ends at or
    after ``seconds``."""
    t = log[1:] - log[0]
    k = int(np.searchsorted(t, seconds, side="left"))
    k = min(k, len(t) - 1)
    return (k + 1) * batch / t[k]


def main(argv):
    batch = int(argv[0])
    logs = [np.load(p) for p in argv[1:]]
    lengths = [10, 20, 30, 40, float(min(l[-1] - l[0] for l in logs)) - 1e-9]
    print("window_s " + " ".join("run%d" % (i + 1) for i in range(len(logs)))
          + "  spread(IQR/median)")
    for s in lengths:
        rates = [rate_over(l, s, batch) for l in logs]
        print("%7.1f  %s  %.4f" % (s, " ".join("%.2f" % r for r in rates),
                                   spread(rates) if len(rates) > 3 else -1))
    print("per run: steps, median step ms, p95, max, steps over 1.2x median, "
          "seconds lost to them")
    for i, l in enumerate(logs):
        d = np.diff(l) * 1e3
        med = float(np.median(d))
        slow = d[d > 1.2 * med]
        print("run%d  %d  %.2f  %.2f  %.2f  %d  %.3f" % (
            i + 1, len(d), med, float(np.quantile(d, 0.95)), float(d.max()),
            len(slow), float((slow - med).sum()) / 1e3))
    meds = [float(np.median(np.diff(l))) for l in logs]
    print("medians of step time differ between runs by %.2f%% (max/min - 1)"
          % (100 * (max(meds) / min(meds) - 1)))


if __name__ == "__main__":
    main(sys.argv[1:])
