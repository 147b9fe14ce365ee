"""Readings of the program's set-up: its own ``setup`` and ``compile``
events, from process start to the start of the measured window.

The kinds hand readers only events INSIDE the window, and set-up ends where
the window starts, so this reader asks the program for its process-wide
record itself (``orion_tpu.obs.trace.setup_record()``; ``read_metrics`` runs
in the process that ran the cell) and keeps what lies before the first
in-window event the kind handed over: the first timestamp of
``evidence["tracer"]`` (a serving cell) or the first start of
``evidence["spans"]`` (a training cell), both on ``time.monotonic``. An event
that straddles that moment counts up to it. A program without the record
gives nothing to read, and every reading is then None.

Events are Chrome-format dicts (``ts`` / ``dur`` in microseconds): complete
(``X``) events of category ``setup`` (the program's spans around its own
start: import, building its server or trainer, the first launch of each
program) and ``compile`` (one per stage of every program jax built:
``compile.trace``, ``compile.lower``, ``compile.backend`` with ``fun_name``
and ``source``, ``compiled`` or ``cache``).

- ``span_s``: the seconds inside the events named in ``names``;
- ``count``: how many events named in ``names`` carry ``source`` in their
  arguments; where it is not 0 their ``fun_name``s are printed;
- ``uncovered_share``: 100 x (``values.setup_seconds`` - the union of every
  ``setup`` and ``compile`` interval before the window) / ``values.setup_seconds``:
  the share of set-up that no span of the program covers; the seconds by
  span that is inside no other are printed beside it.
"""

import harness


def window_start_us(evidence: dict):
    """The first in-window event the kind handed over, in microseconds."""
    tracer = [e["ts"] for e in evidence.get("tracer") or []]
    spans = [s[1] * 1e6 for s in evidence.get("spans") or []]
    return min(tracer or spans, default=None)


def before_window(evidence: dict):
    """The record's complete events that start before the window, cut at it;
    None without a record, a window or an event."""
    try:
        from orion_tpu.obs import trace
    except ImportError:
        return None
    record = getattr(trace, "setup_record", None)
    cut = window_start_us(evidence)
    if record is None or cut is None:
        return None
    events = [dict(e, dur=min(e["dur"], cut - e["ts"]))
              for e in record() if e["ph"] == "X" and e["ts"] < cut]
    return events or None


def union_us(events) -> float:
    total, end = 0.0, float("-inf")
    for e in sorted(events, key=lambda e: e["ts"]):
        stop = e["ts"] + e["dur"]
        if stop > end:
            total += stop - max(e["ts"], end)
            end = stop
    return total


def outermost(events):
    """Seconds by name of the ``setup`` spans that lie inside no other."""
    spans = [e for e in events if e["cat"] == "setup"]
    out = {}
    for e in spans:
        if not any(o is not e and o["ts"] <= e["ts"]
                   and e["ts"] + e["dur"] <= o["ts"] + o["dur"] for o in spans):
            out[e["name"]] = round(out.get(e["name"], 0.0) + e["dur"] / 1e6, 3)
    return out


def read(evidence: dict, what: str, names=(), source: str = ""):
    events = before_window(evidence)
    if events is None:
        return None
    named = [e for e in events if e["name"] in names]
    if what == "span_s":
        return sum(e["dur"] for e in named) / 1e6
    if what == "count":
        hits = [e for e in named if e.get("args", {}).get("source") == source]
        if hits:
            harness.note(setup_record=what, source=source, programs=[
                [e["args"].get("fun_name"), round(e["dur"] / 1e6, 3)]
                for e in sorted(hits, key=lambda e: -e["dur"])])
        return len(hits)
    if what == "uncovered_share":
        total = (evidence.get("values") or {}).get("setup_seconds")
        if not total:
            return None
        covered = union_us(events) / 1e6
        harness.note(setup_record=what, setup_seconds=total, covered_s=covered,
                     seconds_by_span=outermost(events))
        return 100.0 * max(total - covered, 0.0) / total
    raise ValueError(f"unknown set-up reading {what!r}")
