"""Which of the program's own spans the device waited under.

Over a recording kept from a traced run (``BENCH_KEEP_RECORDING=<file>
python3 -m benchmark.run ... --trace 1``): the idle gaps of the fullest
device inside ``bench:window``, each charged to the innermost ``mx:`` span
open on the host at that instant (``mxnet_tpu``'s ``StepTracker``
components and phases are ``jax.profiler.TraceAnnotation``s under that
prefix, on the trace's own clock).  What no ``mx:`` span covers is
``host:outside_program_spans``; gaps under 2 us are the device stepping from
one operation to the next.  ``python3 -m benchmark.tools.idle_by_program_span
<recording.json>`` prints the table; ``attribute`` is the function."""
from __future__ import annotations

import json
import sys

from .. import trace_reduce as tr

PREFIX = "mx:"
OUTSIDE = "host:outside_program_spans"
BETWEEN = "device:between_ops"


def self_intervals(spans):
    """{name: [(start, end)]} of the time each named span is the innermost
    one open: of the spans covering an instant, the one that began last."""
    edges = sorted({t for ivs in spans.values() for iv in ivs for t in iv})
    opened = sorted((s, e, n) for n, ivs in spans.items() for s, e in ivs)
    out, active, k = {}, [], 0
    for lo, hi in zip(edges, edges[1:]):
        while k < len(opened) and opened[k][0] <= lo:
            active.append(opened[k])
            k += 1
        active = [a for a in active if a[1] > lo]
        if active:
            inner = max(active, key=lambda a: (a[0], -a[1]))
            out.setdefault(inner[2], []).append((lo, hi))
    return out


def attribute(recording):
    """{"window_s", "idle_s", "steps", "by_span": [[name, s]],
    "outside_share"} of the fullest device's idle time inside
    ``bench:window``, or None where the recording holds no device operation
    or no window."""
    window = tr.host_spans(recording).get(tr.WINDOW_SPAN)
    devices = tr._device_ops(recording)
    if not window or not devices:
        return None
    lo = min(s for s, _ in window)
    hi = max(e for _, e in window)
    busy = {dev: tr.union(tr.clip([(s, e) for _, s, e in ops], lo, hi))
            for dev, (ops, _) in devices.items()}
    fullest = max(busy, key=lambda d: tr.total(busy[d]))
    gaps = tr.subtract([(lo, hi)], busy[fullest])
    long_gaps = [g for g in gaps if g[1] - g[0] >= tr.BETWEEN_OPS_NS]
    spans = tr.host_spans(recording, prefix=PREFIX)
    what = {BETWEEN: tr.total(gaps) - tr.total(long_gaps)}
    left = long_gaps
    for name, ivs in self_intervals(spans).items():
        covered = tr.overlap(long_gaps, ivs)
        if covered > 0:
            what[name] = covered
            left = tr.subtract(left, ivs)
    what[OUTSIDE] = tr.total(left)
    idle = tr.total(gaps)
    # the window opens and closes inside a step's batch-end callback: the
    # steps it counts are the ones that BEGIN inside it
    steps = sum(1 for s, _ in spans.get(PREFIX + "step", []) if lo <= s < hi)
    ns = 1e-9
    return {"window_s": (hi - lo) * ns, "idle_s": idle * ns, "steps": steps,
            "by_span": [[n, s * ns] for n, s in
                        sorted(what.items(), key=lambda kv: -kv[1]) if s > 0],
            "outside_share": what[OUTSIDE] / idle if idle else 0.0}


def table(found):
    per = 1e3 / found["steps"] if found["steps"] else float("nan")
    lines = ["idle %.4f s of a %.4f s window (%.2f%%), %d steps: %.2f ms a "
             "step" % (found["idle_s"], found["window_s"],
                       100.0 * found["idle_s"] / found["window_s"],
                       found["steps"], found["idle_s"] * per),
             "%-32s %10s %8s %12s" % ("innermost span", "idle s", "share",
                                      "ms a step")]
    for name, s in found["by_span"]:
        lines.append("%-32s %10.4f %7.1f%% %12.3f"
                     % (name, s, 100.0 * s / found["idle_s"], s * per))
    return "\n".join(lines)


def main(argv):
    with open(argv[0]) as f:
        found = attribute(json.load(f))
    if found is None:
        print("no device operation or no %s span in %s"
              % (tr.WINDOW_SPAN, argv[0]), file=sys.stderr)
        return 1
    print(table(found))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
