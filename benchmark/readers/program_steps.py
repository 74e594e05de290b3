"""What the program's own per-step records say about the window: device
starvation as ``StepTracker`` measured it, and the steps that ran ahead.

The records live in the program's memory
(``mxnet_tpu.observability.instrument.recent_steps()``, one a step).  The
harness hands a reader only ``obs``, and ``obs`` holds no instant of the
window, so the window's records are found by what ``obs`` does hold: the
one run of records whose step-end differences equal ``obs["step_seconds"]``
— all but the first, which the harness starts at its own opening instant —
within a millisecond.  (The harness reads its clock in its batch-end
callback, microseconds before the record's own end; in the step that closes
the window it then takes its closing snapshots inside that callback, so the
last record may run longer than the harness's last step, never shorter.)
No such run, two of them, a record without a starved figure, or a program
that keeps no records (the parent commit): ``None``, and the metric is left
out of the line."""
from __future__ import annotations

TOLERANCE_S = 1e-3
# what the closing callback may do after it has read the clock
CLOSING_SLACK_S = 0.05


def _ring():
    try:
        from mxnet_tpu.observability import instrument
        return instrument.recent_steps("train")
    except (ImportError, AttributeError, TypeError):
        return None


def window_records(step_seconds, ring):
    """The ``len(step_seconds)`` consecutive records of ``ring`` that the
    window counted, or None."""
    n = len(step_seconds)
    if n < 2 or not ring or len(ring) < n:
        return None
    ends = [r["end_s"] for r in ring]
    want = [float(s) for s in step_seconds[1:]]

    def fits(k):
        over = [ends[k + j + 1] - ends[k + j] - want[j] for j in range(n - 1)]
        return all(abs(d) <= TOLERANCE_S for d in over[:-1]) \
            and -TOLERANCE_S <= over[-1] <= CLOSING_SLACK_S

    found = [k for k in range(len(ring) - n + 1) if fits(k)]
    return ring[found[0]:found[0] + n] if len(found) == 1 else None


def read(obs, what, under=None, ring=None):
    """``what="starved_ms"``: ms a step the device had no step program to
    run, summed over the window's records and divided by its steps — all of
    it, or the part charged to the spans named in ``under``.
    ``what="ran_ahead"``: how many of the window's steps were dispatched
    while an earlier one was still in flight."""
    lengths = obs.get("step_seconds")
    if lengths is None:
        return None
    records = window_records(lengths, _ring() if ring is None else ring)
    if records is None:
        return None
    if what == "ran_ahead":
        return sum(1 for r in records if r["ran_ahead"])
    if any(r["starved_ms"] is None for r in records):
        return None
    if under is None:
        return sum(r["starved_ms"] for r in records) / len(records)
    return sum(r["starved_by_ms"].get(name, 0.0)
               for r in records for name in under) / len(records)
