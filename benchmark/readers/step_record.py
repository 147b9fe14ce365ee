"""Readings of the training loop's own step record: every iteration of
``Trainer.train`` inside the measured window, on the program's clock.

The kinds hand readers the BENCHMARK's spans (``loader`` round the data
iterator, ``block`` round the hook's wait); what the loop itself did is in
the program's process-wide record, so this reader asks for it
(``orion_tpu.obs.trace.step_record()``; ``read_metrics`` runs in the process
that ran the cell), as ``readers/setup_record.py`` asks for the set-up's. A
program without the record gives nothing to read, and every reading is then
None.

Events are Chrome-format dicts (``ts`` / ``dur`` in microseconds on
``time.monotonic``), category ``step``: ``train.step`` (args ``step``,
``tokens``), one an iteration, from its top to the top of the next, so they
tile the loop; inside each, none overlapping and each with the parent's
``step``: ``train.next_batch`` (``ready``: batches the loader held when
asked), ``train.dispatch``, ``train.log_readback``, ``train.eval``,
``train.checkpoint``, ``train.hook`` (the caller's time: in this benchmark
the wait for the step before); and ``host.gc``, a collection of the
interpreter of 1 ms or more. ``compile.*`` events come from
``setup_record()``, on the same clock.

The window runs from the first start of ``evidence["spans"]`` for
``evidence["window_s"]`` seconds. A *period* is the duration of a parent
that holds or follows the window's start and starts inside the window,
the last of them left out: it is the one the window's edge cuts or, where
the loop left inside the window, the iteration that left it, which reads
the last step's metrics back on its way out. *Waiting* is the time inside
``train.next_batch``, ``train.hook`` and ``train.log_readback`` (for the
loader, the caller, the device); a period's *host* time is the rest of it.

- ``period_ms``: the median (``pick="p50"``) or the longest (``"max"``);
- ``long_periods``: how many exceed 1.25 x the median; each is printed:
  its ``step``, its excess over the median, the ms by child, its host time,
  and the ``host.gc`` and ``compile.*`` events inside it;
- ``max_excess_host_ms``: the longest period's host time minus the median
  host time: ~0 where the device or the loader was late, ~the excess where
  the interpreter was;
- ``child_share``: 100 x the time inside the children named ``name`` / the
  periods' sum (the longest of them is printed with its step);
  ``child_ms_p50``: their median duration;
- ``starved_share``: 100 x the ``train.next_batch`` spans with ``ready`` 0 /
  those that carry ``ready``;
- ``host_share``: 100 x the periods' host time / the periods' sum;
- ``gc_ms``: the ``host.gc`` milliseconds inside the periods.
"""

import statistics

import harness

WAITING = ("train.next_batch", "train.hook", "train.log_readback")
LONG = 1.25


def _record(name: str):
    try:
        from orion_tpu.obs import trace
    except ImportError:
        return None
    read = getattr(trace, name, None)
    return None if read is None else read()


def _inside(e: dict, p: dict) -> bool:
    return p["ts"] <= e["ts"] and e["ts"] + e["dur"] <= p["ts"] + p["dur"]


def periods(evidence: dict):
    """The window's whole periods, oldest first: (parent, its children, the
    collections inside it). None without a record, a window or a period."""
    events = _record("step_record")
    starts = [s[1] * 1e6 for s in evidence.get("spans") or []]
    window = evidence.get("window_s")
    if not events or not starts or not window:
        return None
    start = min(starts)
    end = start + window * 1e6
    spans = [e for e in events if e["ph"] == "X"]
    parents = sorted(
        (e for e in spans if e["name"] == "train.step"
         and e["ts"] + e["dur"] > start and e["ts"] < end),
        key=lambda e: e["ts"])[:-1]
    by_step = {}
    for e in spans:
        if e["name"].startswith("train.") and e["name"] != "train.step":
            by_step.setdefault(e["args"]["step"], []).append(e)
    collections = [e for e in spans if e["name"] == "host.gc"]
    out = [(p, [e for e in by_step.get(p["args"]["step"], []) if _inside(e, p)],
            [e for e in collections if _inside(e, p)]) for p in parents]
    return out or None


def _ms(events) -> float:
    return sum(e["dur"] for e in events) / 1e3


def _host_ms(parent: dict, children) -> float:
    return parent["dur"] / 1e3 - _ms(e for e in children if e["name"] in WAITING)


def _note_long(parent, children, collections, median_ms: float) -> None:
    by_child = {}
    for e in children:
        by_child[e["name"]] = round(by_child.get(e["name"], 0.0) + e["dur"] / 1e3, 3)
    compiles = [e for e in _record("setup_record") or []
                if e["cat"] == "compile" and e["ph"] == "X" and _inside(e, parent)]
    inside = []
    for e in collections + compiles:  # a program's name, a collection's generation
        args = e.get("args") or {}
        inside.append([e["name"], args.get("fun_name", args.get("generation")),
                       round(e["dur"] / 1e3, 3)])
    harness.note(
        long_period=parent["args"]["step"],
        excess_ms=round(parent["dur"] / 1e3 - median_ms, 3),
        period_ms=round(parent["dur"] / 1e3, 3), median_ms=round(median_ms, 3),
        ms_by_child=by_child,
        self_ms=round(parent["dur"] / 1e3 - _ms(children), 3),
        host_ms=round(_host_ms(parent, children), 3), inside=inside)


def read(evidence: dict, what: str, name: str = "", pick: str = "p50"):
    kept = periods(evidence)
    if kept is None:
        return None
    durs = [p["dur"] / 1e3 for p, _, _ in kept]
    median, total = statistics.median(durs), sum(durs)
    if what == "period_ms":
        return median if pick == "p50" else max(durs)
    if what == "long_periods":
        long = [k for k in kept if k[0]["dur"] / 1e3 > LONG * median]
        for k in long:
            _note_long(*k, median)
        return len(long)
    if what == "max_excess_host_ms":
        host = [_host_ms(p, kids) for p, kids, _ in kept]
        return host[durs.index(max(durs))] - statistics.median(host)
    if what == "host_share":
        return 100.0 * sum(_host_ms(p, kids) for p, kids, _ in kept) / total
    if what == "gc_ms":
        return _ms(e for _, _, collections in kept for e in collections)
    named = [e for _, kids, _ in kept for e in kids if e["name"] == name]
    if what == "child_share":
        if named:  # the one long child a share or a median hides
            longest = max(named, key=lambda e: e["dur"])
            harness.note(step_record=name, spans=len(named),
                         max_ms=round(longest["dur"] / 1e3, 3),
                         max_at_step=longest["args"]["step"])
        return 100.0 * _ms(named) / total
    if what == "child_ms_p50":
        return statistics.median(e["dur"] / 1e3 for e in named) if named else None
    if what == "starved_share":
        ready = [e["args"]["ready"] for e in named if "ready" in e["args"]]
        return 100.0 * sum(r == 0 for r in ready) / len(ready) if ready else None
    raise ValueError(f"unknown step reading {what!r}")
