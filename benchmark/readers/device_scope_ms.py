"""Device time by mechanism: the traced window's seconds per device
operation (``obs["trace"]["op_seconds"]``, keyed ``<instruction name>
<opcode>``) joined with the table the program made of its own compiled step,
from instruction to the ``mx:`` scope and the pass it was traced under
(``mxnet_tpu.observability.instrument.device_seconds_by_scope``: a
partition of the input), summed over the rows that ``select`` takes and
divided by the window's steps: ms a step, or with ``share`` the percentage of
all the window's device seconds.

``select``: {"mechanism": ..., "detail": ..., "pass": ...}, any part of it;
``detail`` is a regular expression the whole detail must match, the others
are equal or not, and ``null`` selects what no scope claims.  None (the
metric is left out of the line) with no trace, on a program that hands out no
such table (the parent commit) or where none was captured; 0.0 where the
table is there and nothing matched, so that a lost scope reads 0, not a
gap."""
import re


def _rows(op_seconds):
    try:
        from mxnet_tpu.observability import instrument
        table = instrument.device_op_scopes()
        if not table:
            return None
        return instrument.device_seconds_by_scope(op_seconds, table)
    except (ImportError, AttributeError):
        return None


def read(obs, select, share=False, rows=None):
    trace = obs.get("trace")
    if not trace or not trace.get("op_seconds") or not obs.get("steps"):
        return None
    if rows is None:
        rows = _rows(trace["op_seconds"])
    if rows is None:
        return None

    def taken(row):
        return all(
            (row[k] is not None and re.fullmatch(want, row[k]) is not None)
            if k == "detail" and want is not None else row[k] == want
            for k, want in select.items())

    seconds = sum(r["seconds"] for r in rows if taken(r))
    if share:
        return 100.0 * seconds / sum(r["seconds"] for r in rows)
    return 1e3 * seconds / obs["steps"]
