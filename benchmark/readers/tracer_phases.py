"""Readings of the serve loop's phase spans inside the window.

The program's scheduler thread writes one complete (``X``) event of category
``phase`` per phase of a served boundary (``orion_tpu.serving.PHASES``): a
parent (``parent``) from the top of a loop iteration that steps the engine to
the top of the next, and children inside it that carry the parent's
``boundary`` index. A program without these spans gives nothing to read, and
every reading is then None.

- ``phase_ms``: per boundary, the time inside the child ``name`` (a boundary
  without it counts 0); the ``q``-th percentile over boundaries, ms;
- ``self_ms``: per boundary, the parent's duration minus the time inside the
  children named in ``minus``; the ``q``-th percentile, ms;
- ``period_ms``: the start-to-start period of consecutive boundaries (the
  next one's index, and no idle wait between the two: the next starts where
  this one ends) divided by the parent's ``steps`` (tokens a decoding row
  emits per boundary): the gap between output tokens a streaming client
  would see; the ``q``-th percentile, ms.

The window's last boundary may be cut by the window's edge (its children are
stamped later than its start) and is left out of the first two.
"""

from harness import percentile

ADJACENT_US = 1000.0  # a parent runs to the top of the next iteration


def boundaries(events, parent: str):
    """[(parent event, {child name: ms inside this boundary})], by start."""
    phases = [e for e in events if e["ph"] == "X" and e.get("cat") == "phase"]
    parents = sorted((e for e in phases if e["name"] == parent), key=lambda e: e["ts"])
    by_index = {e["args"]["boundary"]: (e, {}) for e in parents}
    for e in phases:
        hit = by_index.get(e.get("args", {}).get("boundary"))
        if hit is None or e["name"] == parent:
            continue
        p, kids = hit
        if p["ts"] <= e["ts"] and e["ts"] + e["dur"] <= p["ts"] + p["dur"] + 1.0:
            kids[e["name"]] = kids.get(e["name"], 0.0) + e["dur"] / 1e3
    return [by_index[e["args"]["boundary"]] for e in parents]


def read(evidence: dict, what: str, parent: str, name: str = "", minus=(), q: float = 50):
    events = evidence.get("tracer")
    if not events:
        return None
    found = boundaries(events, parent)
    if what == "period_ms":
        gaps = [(b["ts"] - a["ts"]) / 1e3 / a["args"]["steps"]
                for (a, _), (b, _) in zip(found, found[1:])
                if b["args"]["boundary"] == a["args"]["boundary"] + 1
                and b["ts"] - (a["ts"] + a["dur"]) < ADJACENT_US]
        return percentile(gaps, q)
    whole = found[:-1]
    if what == "phase_ms":
        return percentile([kids.get(name, 0.0) for _, kids in whole], q)
    if what == "self_ms":
        return percentile([p["dur"] / 1e3 - sum(kids.get(n, 0.0) for n in minus)
                           for p, kids in whole], q)
    raise ValueError(f"unknown phase reading {what!r}")
