"""The measured window, cut on step boundaries.

It opens at an instant the driver names (after the ``block_until_ready``
that closes the warm-up) and closes at the end of the first step that ends
at or after ``seconds``.  Every step that ends inside it counts, so a stall
lowers the rate; the rate is taken over the time between the two instants,
never over ``seconds``.  The log is preallocated: recording a step is one
array store."""
from __future__ import annotations

import numpy as np


class StepWindow:
    def __init__(self, seconds, capacity=1 << 16):
        self.seconds = float(seconds)
        self.ends = np.zeros(int(capacity), np.float64)
        self.n = 0
        self.t_open = None
        self.closed = False

    def open(self, now):
        self.t_open = float(now)
        self.n = 0
        self.closed = False

    @property
    def is_open(self):
        return self.t_open is not None and not self.closed

    def step_end(self, now):
        """Record a step that ended at ``now``.  Returns True when this
        step closes the window (it is the last one counted)."""
        if not self.is_open:
            return False
        self.ends[self.n] = now
        self.n += 1
        if now - self.t_open >= self.seconds or self.n == len(self.ends):
            self.closed = True
        return self.closed

    @property
    def elapsed(self):
        """Seconds between the opening instant and the last counted end."""
        return float(self.ends[self.n - 1] - self.t_open) if self.n else 0.0

    def step_seconds(self):
        """Length of each counted step (the first from the opening)."""
        ends = self.ends[:self.n]
        return np.diff(np.concatenate(([self.t_open], ends)))

    def rate(self, work_per_step):
        """All the work of all counted steps over the measured length."""
        return self.n * float(work_per_step) / self.elapsed


def quantile(values, q):
    """The q-quantile by linear interpolation (numpy's default), over all
    values: no chunking, no trimming."""
    return float(np.quantile(np.asarray(values, np.float64), q))
