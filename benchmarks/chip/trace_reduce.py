"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time and idle share, seconds per program, and idle gaps
attributed to what the host was doing.  ``jax.profiler.ProfileData`` only.

What the trace of a TPU holds (looked at by hand, PR 25): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed HLO
operation and whose line ``XLA Modules`` has one per executed program
(``jit_fused_agg(1234...)``); and the plane ``/host:CPU`` with one line per
host thread, which holds the harness's own ``TraceAnnotation`` spans (``in
q3``, ``traced window``) on the same clock.  A CPU rehearsal has no device
plane: there the host-plane events that carry an ``hlo_op`` stat stand in, so
that the code path can be rehearsed; the harness never reports such a run as
a device's.

Busy is the union of the ``XLA Ops`` intervals inside the traced window, so
overlapping operations (four task threads feed one chip) count once.  The
line ``Async XLA Ops`` (starts of copies and slices that run beside the
operations) is not counted as busy.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "traced window"
QUERY_SPAN = re.compile(r"^in (\S+)$")
Interval = Tuple[float, float]           # seconds, on the trace's clock
Named = Tuple[float, float, str]


@dataclass
class RawTrace:
    """What the reduction needs of a trace, whatever it was read from."""
    ops: Dict[str, List[Named]] = field(default_factory=dict)       # per device
    programs: Dict[str, List[Named]] = field(default_factory=dict)  # per device
    host_spans: List[Named] = field(default_factory=list)
    simulated_device: bool = False      # host events standing in (CPU)


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def _program_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def read_xplane(path: str) -> RawTrace:
    from jax.profiler import ProfileData

    return extract(ProfileData.from_file(path))


def extract(profile) -> RawTrace:
    raw = RawTrace()
    host = None
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            for line in plane.lines:
                if line.name == "XLA Ops":
                    raw.ops[plane.name] = [
                        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                         e.name) for e in line.events]
                elif line.name == "XLA Modules":
                    raw.programs[plane.name] = [
                        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                         _program_name(e.name)) for e in line.events]
        elif plane.name == "/host:CPU":
            host = plane
    if host is None:
        return raw
    stand_in: List[Named] = []
    for line in host.lines:
        for e in line.events:
            name = e.name
            if name == WINDOW_SPAN or QUERY_SPAN.match(name):
                raw.host_spans.append(
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                     name))
            elif not raw.ops and e.duration_ns and not name.startswith(
                    ("$", "end:", "Threadpool", "Pjit", "Python")):
                stats = dict(e.stats)
                if "hlo_op" in stats:
                    stand_in.append(
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9,
                         str(stats.get("hlo_module", name))))
    if not raw.ops and stand_in:
        raw.simulated_device = True
        raw.ops["/host:CPU"] = stand_in
        raw.programs["/host:CPU"] = stand_in
    return raw


def union(intervals: List[Interval]) -> List[Interval]:
    """Overlapping and touching intervals merged, in order of start."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b, *_ in intervals
            if b > lo and a < hi]


def reduce(raw: RawTrace, host_lo: Optional[float] = None,
           query_records: Optional[List[Named]] = None,
           max_entries: int = 10) -> Optional[dict]:
    """The reduced trace, or None where the trace has no device operation or
    no ``traced window`` span to measure against.

    Queries are the trace's own ``in <q>`` spans.  A query still in flight
    when the profiler stops leaves no span, so the harness may hand over its
    own records instead: ``query_records`` as ``(start, end, name)`` on the
    host's clock, and ``host_lo``, that clock's reading as the ``traced
    window`` span opened, which puts them on the trace's clock."""
    windows = [s for s in raw.host_spans if s[2] == WINDOW_SPAN]
    if not windows or not raw.ops:
        return None
    lo, hi = windows[0][0], windows[0][1]
    window_s = hi - lo
    if query_records is not None and host_lo is not None:
        queries = sorted((a - host_lo + lo, b - host_lo + lo, q)
                         for a, b, q in query_records)
    else:
        queries = sorted((a, b, QUERY_SPAN.match(n).group(1))
                         for a, b, n in raw.host_spans
                         if QUERY_SPAN.match(n))

    cuts = sorted({t for a, b, _ in queries for t in (a, b)})
    busy_per_device, gap_totals = [], {}
    program_s: Dict[str, float] = {}
    for device in sorted(raw.ops):
        busy = union(clip(raw.ops[device], lo, hi))
        busy_per_device.append(sum(b - a for a, b in busy))
        progs = sorted(raw.programs.get(device, []), key=lambda p: p[1])
        for a, b, name in progs:
            a2, b2 = max(a, lo), min(b, hi)
            if b2 > a2:
                program_s[name] = program_s.get(name, 0.0) + (b2 - a2)
        prog_ends = [p[1] for p in progs]
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            # a gap that runs across the start or end of a query is cut
            # there, so each part goes to what the host was doing in it
            i, j = bisect.bisect_right(cuts, g0), bisect.bisect_left(cuts, g1)
            parts = [g0] + cuts[i:j] + [g1]
            for p0, p1 in zip(parts, parts[1:]):
                if p1 > p0:
                    label = _gap_label(p0, p1, g0, queries, progs, prog_ends)
                    gap_totals[label] = gap_totals.get(label, 0.0) + p1 - p0
    if not any(busy_per_device):
        return None
    n = len(busy_per_device)
    # a query cut by an end of the window counts by the share of its span
    # that lies inside, so the bytes set against busy time match it
    shares: Dict[str, float] = {}
    for a, b, q in queries:
        inside = min(b, hi) - max(a, lo)
        if inside > 0 and b > a:
            shares[q] = shares.get(q, 0.0) + inside / (b - a)

    def top(d: Dict[str, float]):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:max_entries]]

    return {
        "window_s": window_s,
        "busy_s": sum(busy_per_device) / n,
        "devices": n,
        "device_ops": top(program_s),
        "idle_gaps": top(gap_totals),
        "query_shares": shares,
        "simulated_device": raw.simulated_device,
    }


def _gap_label(p0: float, p1: float, g0: float, queries, progs,
               prog_ends) -> str:
    """What the host was doing in (a part of) an idle gap: the queries in
    flight at its middle, and the program that last finished before the gap
    began."""
    mid = (p0 + p1) / 2
    inside = sorted({q for a, b, q in queries if a <= mid <= b})
    what = "in " + "+".join(inside) if inside else "between queries"
    i = bisect.bisect_right(prog_ends, g0 + 1e-9) - 1
    return f"{what} after {progs[i][2]}" if i >= 0 else f"{what} at start"
