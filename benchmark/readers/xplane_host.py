"""The device's idle time given to what the host was doing, from one capture.

Host events (the program's ``TraceAnnotation`` spans) and the device's
operations share the capture's clock. The idle time of the first device is the
gaps between its merged busy intervals, inside the traced window (first
operation's start to last one's end).

- ``idle_attributed_share``: 100 x the idle seconds that lie inside a host
  event whose name is in ``phases`` and not in ``exclude`` / all idle seconds.
  ``exclude`` holds the parent span (it covers its children) and the phase in
  which the host itself waits for the device. As an observation line it prints
  the idle seconds by phase, ``exclude``d ones too, and what no phase covers.

A capture without any such host event (a program that has no phase spans)
gives None.
"""

import harness

xplane = harness.load_module("readers", "xplane")


def overlap(intervals, gaps) -> float:
    """ns of ``gaps`` (sorted, disjoint) covered by the union of ``intervals``."""
    total = 0.0
    merged = xplane.union(intervals)
    for g0, g1 in gaps:
        for a, b in merged:
            if b <= g0:
                continue
            if a >= g1:
                break
            total += min(b, g1) - max(a, g0)
    return total


def read(evidence: dict, what: str, phases=(), exclude=(), **where):
    capture = evidence.get("xplane")
    if not capture:
        return None
    if what != "idle_attributed_share":
        raise ValueError(f"unknown host reading {what!r}")
    lines = xplane.device_lines(capture, **where)
    if not lines:
        return None
    busy = xplane.busy_and_window(lines[0])[2]
    gaps = [(end, start) for (_, end), (start, _) in zip(busy, busy[1:])]
    idle = sum(b - a for a, b in gaps)
    spans = {}
    for name, start, dur in xplane.host_events(
            capture, where.get("device_prefix", xplane.DEVICE_PREFIX)):
        if name in phases:
            spans.setdefault(name, []).append((start, start + dur))
    if not spans or not idle:
        return None
    everything = [iv for ivs in spans.values() for iv in ivs]
    counted = [iv for n, ivs in spans.items() if n not in exclude for iv in ivs]
    harness.note(
        idle_s=idle / 1e9,
        idle_s_by_phase={n: overlap(ivs, gaps) / 1e9 for n, ivs in sorted(spans.items())},
        idle_s_outside_every_phase=(idle - overlap(everything, gaps)) / 1e9)
    return 100.0 * overlap(counted, gaps) / idle
