"""From a profiler trace to numbers: the one reduction every PR shares.

``load_xplane`` turns an ``.xplane.pb`` (``jax.profiler.ProfileData``) into
a plain recording (device events under short names); ``reduce`` turns a recording into device busy and idle
time, time per named operation, collective time and its exposed part, and
the idle gaps attributed to what the host was doing.  ``reduce`` sees only
the recording, so it is checked on a small recorded trace kept beside it
(``tests/data/recorded_trace.json``).

A recording is ``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns, is_hlo], ...]}]}]}``."""
from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"            # what the core executes, one at a time
ASYNC_LINE = "Async XLA Ops"    # copies and collectives in flight beside it
HLO_NAME = re.compile(r"^%(\S+) = .*?\s([\w\-]+)\(")
HLO_TARGET = re.compile(r'custom_call_target="([^"]+)"')
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
WINDOW_SPAN = "bench:window"
# an idle gap shorter than this is the device stepping from one operation
# to the next, not the host holding it back
BETWEEN_OPS_NS = 2000.0


def short_name(name):
    """A device event's name is the whole HLO instruction; keep
    ``<result name> <opcode>[:<custom-call target>]``."""
    m = HLO_NAME.match(name)
    if not m:
        return name[:120]
    target = HLO_TARGET.search(name)
    return "%s %s%s" % (m.group(1), m.group(2),
                        ":" + target.group(1) if target else "")


def load_xplane(path):
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                is_hlo = any(k == "hlo_op" for k, _ in e.stats)
                events.append([short_name(e.name), float(e.start_ns),
                               float(e.duration_ns), is_hlo])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# -- interval arithmetic (lists of (start, end), ns) -------------------------

def union(intervals):
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals):
    return float(sum(e - s for s, e in intervals))


def subtract(a, b):
    """The part of union(a) not covered by union(b)."""
    out, b = [], union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def overlap(a, b):
    """Length of union(a) that union(b) covers."""
    return total(union(a)) - total(subtract(a, b))


# -- the reduction ------------------------------------------------------------

def _device_ops(recording):
    """{device name: (ops, in flight)}, each [(op name, start, end)]: the
    ``XLA Ops`` and ``Async XLA Ops`` lines of each TPU plane; on a CPU
    rehearsal, the host's HLO events as one device."""
    devices = {}
    for plane in recording["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            found = {line["name"]: [(n, s, s + d)
                                    for n, s, d, *_ in line["events"]]
                     for line in plane["lines"]
                     if line["name"] in (OPS_LINE, ASYNC_LINE)}
            if found.get(OPS_LINE):
                devices[plane["name"]] = (found[OPS_LINE],
                                          found.get(ASYNC_LINE, []))
    if not devices:
        ops = [(n, s, s + d) for plane in recording["planes"]
               for line in plane["lines"]
               for n, s, d, is_hlo in line["events"] if is_hlo]
        if ops:
            devices["/host:CPU(rehearsal)"] = (ops, [])
    return devices


def host_spans(recording, prefix="bench:"):
    """{span name: [(start, end)]} of the harness's own annotations."""
    spans = {}
    for plane in recording["planes"]:
        if plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                for n, s, d, *_ in line["events"]:
                    if n.startswith(prefix):
                        spans.setdefault(n, []).append((s, s + d))
    return spans


def reduce(recording, top=10):
    """Reduce a recording to the harness's device numbers, or None where it
    holds no device operation or no ``bench:window`` span.

    Returns ``window_s``; ``busy_s`` and ``idle_share`` (mean over devices,
    and ``idle_share_fullest`` of the busiest device); ``op_seconds``
    {name: s} (mean over devices) and ``device_ops`` (the ``top`` of them);
    ``collective_s`` and ``collective_exposed_s`` (mean over devices; the
    exposed part is collective time during which no other operation runs on
    that device: a collective in flight on the async line beside other work is
    hidden, the wait for it on the ops line is not); ``idle_gaps`` [[what the host was doing, s]]."""
    spans = host_spans(recording)
    devices = _device_ops(recording)
    if not devices or WINDOW_SPAN not in spans:
        return None
    lo = min(s for s, _ in spans[WINDOW_SPAN])
    hi = max(e for _, e in spans[WINDOW_SPAN])
    n_dev = len(devices)
    busy, op_s, coll, exposed = {}, {}, 0.0, 0.0
    for dev, (ops, in_flight) in devices.items():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if min(e, hi) > max(s, lo)]
        busy[dev] = union([(s, e) for _, s, e in inside])
        for n, s, e in inside:
            op_s[n] = op_s.get(n, 0.0) + (e - s) / n_dev
        c = [(s, e) for n, s, e in inside if COLLECTIVE.search(n)] \
            + clip([(s, e) for n, s, e in in_flight
                    if COLLECTIVE.search(n)], lo, hi)
        rest = [(s, e) for n, s, e in inside if not COLLECTIVE.search(n)]
        coll += total(union(c)) / n_dev
        exposed += total(subtract(c, rest)) / n_dev
    if not any(busy.values()):
        return None
    window = hi - lo
    busy_s = sum(total(b) for b in busy.values()) / n_dev
    fullest = max(busy, key=lambda d: total(busy[d]))
    gaps = subtract([(lo, hi)], busy[fullest])
    what = {}
    for gap in gaps:
        if gap[1] - gap[0] < BETWEEN_OPS_NS:
            what["device:between_ops"] = \
                what.get("device:between_ops", 0.0) + gap[1] - gap[0]
            continue
        left = [gap]
        for name, ivs in spans.items():
            if name == WINDOW_SPAN:
                continue
            covered = overlap(left, ivs)
            if covered > 0:
                what[name] = what.get(name, 0.0) + covered
                left = subtract(left, ivs)
        if left:
            what["host:outside_benchmark_spans"] = \
                what.get("host:outside_benchmark_spans", 0.0) + total(left)
    ns = 1e-9
    ranked = sorted(op_s.items(), key=lambda kv: -kv[1])
    return {
        "window_s": window * ns,
        "busy_s": busy_s * ns,
        "idle_share": 1.0 - busy_s / window,
        "idle_share_fullest": 1.0 - total(busy[fullest]) / window,
        "devices": n_dev,
        "op_seconds": {n: s * ns for n, s in op_s.items()},
        "device_ops": [[n, s * ns] for n, s in ranked[:top]],
        "collective_s": coll * ns,
        "collective_exposed_s": exposed * ns,
        "idle_gaps": [[n, s * ns] for n, s in
                      sorted(what.items(), key=lambda kv: -kv[1])[:top]],
    }


def op_seconds_matching(reduced, pattern):
    """Summed seconds of the operations whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(s for n, s in reduced["op_seconds"].items() if rx.search(n))
