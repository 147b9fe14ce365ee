"""Readings of the program's own ``Tracer`` events inside the window.

The scheduler emits one complete (``X``) event per resident slot per chunk
boundary, all with the boundary's start and duration, so a BOUNDARY is one
distinct timestamp among the events named in ``names``.

- ``boundary_ms``: the ``q``-th percentile of boundary durations, ms;
- ``boundary_share``: 100 x boundaries that carry an event named ``having``
  / all boundaries;
- ``async_ms``: the ``q``-th percentile of (end - begin) of the async span
  ``name``, over the requests in ``evidence["rids"]``, ms.
"""

from harness import percentile


def read(evidence: dict, what: str, names=(), having: str = "", name: str = "", q: float = 50):
    events = evidence.get("tracer")
    if not events:
        return None
    if what in ("boundary_ms", "boundary_share"):
        boundaries = {}
        for e in events:
            if e["ph"] == "X" and e["name"] in names:
                boundaries.setdefault(e["ts"], (e["dur"], set()))[1].add(e["name"])
        if not boundaries:
            return None
        if what == "boundary_ms":
            return percentile([d / 1e3 for d, _ in boundaries.values()], q)
        return 100.0 * sum(having in n for _, n in boundaries.values()) / len(boundaries)
    if what == "async_ms":
        rids = evidence.get("rids") or set()
        begin, waits = {}, []
        for e in events:
            if e["name"] != name or e.get("id") not in rids:
                continue
            if e["ph"] == "b":
                begin[e["id"]] = e["ts"]
            elif e["ph"] == "e" and e["id"] in begin:
                waits.append((e["ts"] - begin.pop(e["id"])) / 1e3)
        return percentile(waits, q)
    raise ValueError(f"unknown tracer reading {what!r}")
