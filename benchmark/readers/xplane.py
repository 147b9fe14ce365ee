"""From a ``jax.profiler`` capture to busy/idle shares and a breakdown.

A capture is normalised to plain data (what the fixture under
``benchmark/tests/fixtures`` holds, cut from a chip trace):

    {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [[name, start_ns, duration_ns], ...]}]}]}

Device planes are those whose name starts with ``device_prefix``; the line
that holds one event per executed operation is ``ops_line``. Busy time is the
UNION of that line's intervals (operations nest and overlap), the traced
window runs from the first operation's start to the last one's end, and the
idle share is 1 - busy / window. Host events (TraceAnnotations; the Python
tracer's ``$``-prefixed frames are dropped at load) attribute idle gaps.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU"
OPS_LINE = "XLA Ops"
TOP = 10


_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(name: str) -> str:
    """An XLA operation's event name is its whole HLO line. Keep what tells
    operations apart and adds up usefully: the operation without its serial
    number, a custom call's target (a Mosaic/Pallas kernel is
    ``tpu_custom_call``), and the output type without layouts, e.g.
    ``fusion -> bf16[12,2048,5504]`` or
    ``attn._kernel_bh tpu_custom_call -> (f32[192,2048,128], ...``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    op = re.sub(r"\.\d+$", "", head.lstrip("%"))
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    out = _LAYOUT.sub("", rest)
    out = out[:out.index(")") + 1] if out.startswith("(") and ")" in out else out.split(" ", 1)[0]
    if len(out) > 72:
        out = out[:69] + "..."
    return f"{op}{' ' + target.group(1) if target else ''} -> {out}"


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[short_name(e.name), float(e.start_ns), float(e.duration_ns)]
                      for e in line.events if not e.name.startswith("$")]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def newest(logdir: str) -> Optional[str]:
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def load_newest(logdir: str) -> Optional[dict]:
    path = newest(logdir)
    return load(path) if path else None


def summary(capture: dict, sample: int = 4) -> List[dict]:
    """Planes, lines, event counts and a few names: what to look at by hand
    before trusting a reducer."""
    out = []
    for plane in capture["planes"]:
        for line in plane["lines"]:
            ev = line["events"]
            out.append({
                "plane": plane["name"], "line": line["name"], "events": len(ev),
                "first_ns": min(e[1] for e in ev),
                "last_ns": max(e[1] + e[2] for e in ev),
                "sample": [e[0][:80] for e in ev[:sample]],
            })
    return out


def device_lines(capture: dict, device_prefix: str = DEVICE_PREFIX,
                 ops_line: str = OPS_LINE) -> List[List[list]]:
    """The operations line of each device plane (one list per chip)."""
    return [line["events"] for plane in capture["planes"]
            if plane["name"].startswith(device_prefix)
            for line in plane["lines"] if line["name"] == ops_line]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def busy_and_window(events: List[list]) -> Tuple[float, float, List[Tuple[float, float]]]:
    """(busy ns, window ns, merged busy intervals) of one device's line."""
    merged = union([(e[1], e[1] + e[2]) for e in events if e[2] > 0])
    if not merged:
        return 0.0, 0.0, []
    busy = sum(b - a for a, b in merged)
    return busy, merged[-1][1] - merged[0][0], merged


def device_seconds(capture: Optional[dict], **where) -> Optional[Dict[str, float]]:
    """busy_s and window_s, averaged over the chips that ran anything."""
    if not capture:
        return None
    per_chip = [busy_and_window(ev)[:2] for ev in device_lines(capture, **where)]
    per_chip = [p for p in per_chip if p[1] > 0]
    if not per_chip:
        return None
    n = len(per_chip)
    return {"busy_s": sum(p[0] for p in per_chip) / n / 1e9,
            "window_s": sum(p[1] for p in per_chip) / n / 1e9}


def name_seconds(events: List[list], leaves_only: bool = True) -> Dict[str, float]:
    """Seconds by operation name. With ``leaves_only`` an event that
    encloses others (a while loop, a fusion's region) is skipped, so the
    seconds add up to at most the busy time."""
    ev = sorted((e for e in events if e[2] > 0), key=lambda e: (e[1], -e[2]))
    out: Dict[str, float] = {}
    for i, (name, start, dur) in enumerate(ev):
        if leaves_only and i + 1 < len(ev) and ev[i + 1][1] < start + dur:
            continue  # the next event starts inside this one: a parent
        out[name] = out.get(name, 0.0) + dur / 1e9
    return out


def host_events(capture: dict, device_prefix: str = DEVICE_PREFIX) -> List[list]:
    return [e for plane in capture["planes"]
            if not plane["name"].startswith(device_prefix)
            for line in plane["lines"] for e in line["events"] if e[2] > 0]


def idle_gaps(capture: dict, annotations: List[str], **where) -> List[list]:
    """The longest idle gaps of the first device, each named by the
    benchmark-owned host annotation that covers most of it, else
    ``unattributed``. Host and device events share the capture's clock."""
    lines = device_lines(capture, **where)
    if not lines:
        return []
    merged = busy_and_window(lines[0])[2]
    spans = [e for e in host_events(capture, where.get("device_prefix", DEVICE_PREFIX))
             if e[0] in annotations]
    gaps = []
    for (_, end), (start, _) in zip(merged, merged[1:]):
        best, best_cover = "unattributed", 0.0
        for name, s, d in spans:
            cover = min(start, s + d) - max(end, s)
            if cover > best_cover and cover >= 0.5 * (start - end):
                best, best_cover = name, cover
        gaps.append([best, (start - end) / 1e9])
    gaps.sort(key=lambda g: -g[1])
    return gaps[:TOP]


def breakdown(capture: Optional[dict], annotations: List[str], **where) -> Optional[dict]:
    if not capture:
        return None
    lines = device_lines(capture, **where)
    if not lines:
        return None
    ops = sorted(name_seconds(lines[0]).items(), key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": idle_gaps(capture, annotations, **where)}


def read(evidence: dict, what: str, pattern: str = "", **where) -> Optional[float]:
    """``idle_share``: 100 x (1 - busy / window). ``name_share``: 100 x the
    leaf seconds of operations whose name matches ``pattern`` / busy."""
    capture = evidence.get("xplane")
    if not capture:
        return None
    if what == "idle_share":
        s = device_seconds(capture, **where)
        return None if s is None else 100.0 * (1.0 - s["busy_s"] / s["window_s"])
    if what == "name_share":
        lines = device_lines(capture, **where)
        if not lines:
            return None
        busy = busy_and_window(lines[0])[0]
        hit = sum(v for k, v in name_seconds(lines[0]).items() if re.search(pattern, k))
        return 100.0 * hit * 1e9 / busy if busy else None
    raise ValueError(f"unknown xplane reading {what!r}")
