"""Request tracing: Chrome trace-event JSONL from chunk-boundary state.

A request's latency story — queue wait, admission/staging, each prefill
piece, each decode chunk, eviction/suspension/failure — is recorded
entirely from host-side state the scheduler already holds at chunk
boundaries: the O(1)-state engine's host mirrors (positions, remaining
prompt, done flags) make every interesting transition visible WITHOUT a
device readback, so full tracing costs host timestamps, never a sync.
(Lint rule ``obs-device-sync``: this module never imports jax; values
entering it must already be host numbers.)

Event model (Chrome trace-event format, ``ts``/``dur`` in microseconds):

- **async spans** (``ph`` ``b``/``e``) keyed by ``(cat, id)`` — the
  request lifecycle (``request``: submit -> result released) and its
  nested ``queue`` wait (submit -> admission). The FLEET router opens a
  ``turn`` root span under the same id before placement, so a
  conversation turn that migrates across replicas is one connected
  trace: ids are stable strings (``<session_id>:<turn>`` for session
  turns), and every span carries the session id in ``args``, which is
  what links a resumed turn back to the conversation it continues.
- **complete events** (``ph`` ``X``) — one per resident slot per chunk
  boundary, named ``decode_chunk`` or ``prefill_piece`` by the slot's
  lifecycle phase, carrying ``{req, slot, chunk}``. The duration is the
  boundary's batched-scan wall time (slots share one fused scan; the
  per-slot split does not exist on the device and is not invented here).
- **instants** (``ph`` ``i``) — point events: staging, ladder rungs,
  eviction, suspension, dispatch.
- **phase spans** (``ph`` ``X``, ``cat`` ``phase``) — what the scheduler
  thread does inside one served boundary (``serving.PHASES``), written
  through :meth:`Tracer.span`: ONE call site records the interval in
  this ring and, while a ``jax.profiler`` capture runs, as a profiler
  annotation of the same name — the same span on the program's clock
  (every boundary) and on the device trace's clock (the captured ones).
  This module never imports jax: the ``Server`` hands the annotation
  factory in (:attr:`Tracer.annotate`) for the length of a capture.
- **set-up spans** (``ph`` ``X`` / ``i``, ``cat`` ``setup``) — a
  process's life before its steady state, through the same
  :meth:`Tracer.span`: ``setup.import`` (the first line of
  ``orion_tpu/__init__.py`` to the end of the import of
  ``orion_tpu.serving`` / ``.training``), ``setup.server`` around
  ``Server.__init__`` with ``setup.quantize`` / ``.engine`` / ``.stores``
  / ``.cost_harvest`` inside, ``setup.first_launch`` around the first
  launch of each boundary program, ``setup.trainer`` with
  ``setup.init_state`` inside, ``setup.restore`` / ``.loader`` /
  ``.first_step`` / ``.first_eval``, the CLIs' ``setup.weights``, and an
  instant ``setup.ready`` at the first boundary that emitted a token or
  the first step whose loss is back.
- **compile events** (``ph`` ``X``, ``cat`` ``compile``) — one per stage
  of every program jax builds, at any time: ``compile.trace``,
  ``compile.lower``, ``compile.backend`` with ``fun_name`` and, on the
  last, ``source`` (``compiled`` or ``cache``) and ``cache_load_ms``.
  ``utils/profiling.py`` hears them from ``jax.monitoring`` and hands
  them to :func:`compile_event`; ``compile.kernel`` (``fun_name`` the
  ``pallas_call``'s name, ``source`` ``traced``) comes from
  ``ops/pallas.kernel_entry`` each time a Mosaic kernel's Python body is
  traced, inside the ``compile.trace`` of the program that called it; a
  ``Server`` that is serving hears them
  from here (:func:`on_compile`) and writes them into its ring with the
  boundary they fell in.
- **step spans** (``ph`` ``X``, ``cat`` ``step``) — what the training
  loop does in one iteration of ``Trainer.train``, through the same
  :meth:`Tracer.span`: ``train.step`` (``step``, ``tokens``) from the top
  of one iteration to the top of the next, so consecutive ones tile the
  loop, and inside it, none overlapping and each with its ``step``,
  ``train.next_batch`` (``ready``: the batches the ``DataLoader`` held
  when asked), ``train.dispatch``, ``train.log_readback``,
  ``train.eval``, ``train.checkpoint`` and ``train.hook`` (the caller's
  time); ``host.gc`` (``generation``, ``collected``) for each collection
  of the interpreter that took 1 ms or more while the loop ran.

The last three categories must outlive the ring's turnover, so they are
ALSO kept in bounded process-wide lists, whatever ``Tracer`` wrote them
and whether or not it is enabled: :func:`setup_record` (``setup`` and
``compile``: hundreds a process) and :func:`step_record` (``step``: the
newest ~3,000 steps), all on ``time.monotonic``.

Wire format: one JSON object per line (JSONL), appended live — files
from several processes (fleet parent + children) concatenate trivially.
:func:`merge_traces` wraps any set of JSONL files into the
``{"traceEvents": [...]}`` document Perfetto / chrome://tracing load
directly (``python -m orion_tpu.obs.trace merge a.jsonl b.jsonl -o
trace.json``).

Hot-path cost: when disabled, every record call is one attribute check.
When enabled, a record is a tuple append into a bounded deque;
serialization (json.dumps) happens only at :meth:`flush`/:meth:`close`,
which the serving loop calls at drain — never inside the timed chunk
walk.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import threading
import time
import weakref
from collections import deque
from typing import Callable, Dict, List, Optional

# (name, cat, ph, ts_us, id or None, args or None)
_EVENT_FIELDS = ("name", "cat", "ph", "ts", "id", "args")

# the categories kept in the process-wide record beside the ring, each in
# a bounded list of its own: a process that goes on compiling (a prompt
# length nobody warmed up, every hour) turns its compile events over and
# keeps how it came up
RECORD_CATS = ("setup", "compile", "step")
_RECORD: Dict[str, deque] = {
    "setup": deque(maxlen=1 << 12), "compile": deque(maxlen=1 << 13),
    "step": deque(maxlen=1 << 14),
}
# what the compile events add up to over the process's life: the
# counters a registry shows (``Server.metrics``, ``MetricsLogger.registry``)
COMPILE_COUNTERS = (
    "programs_traced", "programs_compiled", "programs_cache_loaded",
    "compile_ms_total", "trace_lower_ms_total", "kernel_bodies_traced",
)
# beside them the calls of kernel entries, traced or found in jax's trace
# cache, and the residuals a rematted block's names policy kept with their
# bytes: no event each (a program holds hundreds), so only the process counts
_COMPILE_TOTALS: Dict[str, float] = dict.fromkeys(
    COMPILE_COUNTERS
    + ("kernel_call_sites", "remat_kept_residuals", "remat_kept_bytes"), 0)
_COMPILE_SINKS: List[weakref.WeakMethod] = []
# entry packages whose import is under way (one may import the other), and
# whether the process's first ``setup.import`` has been written
_imports_open: List[float] = []
_import_written = False
# per thread: the innermost :class:`Span` that is open (``.span``), which
# holds the one round it (``Span._outer``)
_thread = threading.local()


class Span:
    """A closed interval of the calling thread, written twice from one
    ``with``: a complete event in the tracer's ring (when the tracer is
    enabled and ``record`` is still true at exit) and an annotation in
    the profiler's capture (when the tracer holds an annotation factory
    at entry). ``record=False`` leaves the ring write to :meth:`write` —
    the serve loop learns only later whether an iteration was a boundary."""

    __slots__ = ("_tracer", "name", "cat", "args", "record", "start", "dur",
                 "_ann", "_open", "_outer")

    def __init__(self, tracer, name, cat, record, args):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.record = record
        self.start = self.dur = 0.0
        self._ann = self._outer = None
        self._open = False

    def __enter__(self):
        annotate = self._tracer.annotate
        if annotate is not None:
            self._ann = annotate(self.name)
            self._ann.__enter__()
        self._open = True
        self._outer = getattr(_thread, "span", None)
        _thread.span = self
        self.start = self._tracer._clock()
        return self

    def __exit__(self, *exc):
        self.dur = self._tracer._clock() - self.start
        self._open = False
        _thread.span = self._outer
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self.record:
            self.write()
        return False

    def note(self, **args) -> None:
        """Arguments known only once the span's work is done."""
        self.args.update(args)

    def write(self) -> None:
        """Into the ring: now if the span has closed, at its exit if it
        is still open."""
        if self._open:
            self.record = True
            return
        if self.cat in RECORD_CATS:
            self._tracer.keep(self.name, self.cat, "X", self.start, self.dur,
                              **self.args)
            return
        self._tracer.complete(self.name, self.start, self.dur, cat=self.cat,
                              **self.args)


def note_open(name: str, **args) -> None:
    """Arguments for the span ``name`` if one is open on the calling
    thread, from code below it that was handed no span (the
    ``DataLoader``, asked for a batch inside the loop's
    ``train.next_batch`` through whatever iterator wraps it)."""
    span = getattr(_thread, "span", None)
    while span is not None and span.name != name:
        span = span._outer
    if span is not None:
        span.args.update(args)


class _NullSpan:
    """What a caller holds instead of a :class:`Span` when nothing would
    be written: no ring, no capture (one shared instance)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **args) -> None:
        pass

    def write(self) -> None:
        pass


NULL_SPAN = _NullSpan()


class Tracer:
    """One per process (or per Server in tests). ``path=None`` keeps
    events in the bounded in-memory ring only (tests read them via
    :meth:`events`); with a path, :meth:`flush` appends JSONL."""

    def __init__(
        self,
        path: Optional[str] = None,
        clock: Callable[[], float] = time.perf_counter,
        enabled: bool = True,
        capacity: int = 1 << 17,
        pid: Optional[int] = None,
    ):
        self.path = path
        self.enabled = enabled
        self._clock = clock
        self._pid = pid if pid is not None else os.getpid()
        self._lock = threading.Lock()
        self._buf: deque = deque(maxlen=capacity)
        self.dropped = 0  # events that aged out before a flush
        # name -> context manager that writes the span into a running
        # profiler capture; set by the owner of the capture for its
        # length, None otherwise (this module stays free of jax)
        self.annotate: Optional[Callable[[str], object]] = None
        if path:
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)

    # -- recording (hot path: tuple append, no serialization) -----------------

    def _emit(self, name, cat, ph, id=None, args=None, ts=None, dur=None):
        if not self.enabled:
            return
        if ts is None:
            ts = self._clock() * 1e6
        tid = threading.get_ident() & 0xFFFF
        # lock-free: deque.append is atomic under the GIL, and this runs
        # once per slot per chunk boundary on the scheduler's hot path —
        # readers (flush/events) retry the rare mutated-mid-copy snapshot
        # instead of making every event pay a lock round-trip (`dropped`
        # is an approximate count under concurrent writers, exact
        # single-threaded)
        if len(self._buf) == self._buf.maxlen:
            self.dropped += 1
        self._buf.append((name, cat, ph, ts, dur, id, args, tid))

    def begin(self, name: str, id: str, cat: str = "request", **args) -> None:
        """Open an async span (``ph`` ``b``); pair with :meth:`end` on the
        same (cat, id, name)."""
        self._emit(name, cat, "b", id=id, args=args or None)

    def end(self, name: str, id: str, cat: str = "request", at=None,
            **args) -> None:
        """Close an async span; ``at`` (seconds on the tracer's clock)
        stamps the end where the caller already holds the moment."""
        self._emit(name, cat, "e", id=id, args=args or None,
                   ts=None if at is None else at * 1e6)

    def complete(self, name: str, start_s, dur_s, cat: str = "chunk",
                 **args) -> None:
        """A closed interval (``ph`` ``X``) from host timestamps."""
        self._emit(name, cat, "X", args=args or None,
                   ts=start_s * 1e6, dur=dur_s * 1e6)

    def span(self, name: str, cat: str = "phase", record: bool = True,
             **args) -> Span:
        """``with tracer.span(name):`` — see :class:`Span`. Callers on a
        hot path check ``enabled``/``annotate`` first and hold
        :data:`NULL_SPAN` when both are off."""
        return Span(self, name, cat, record, args)

    def instant(self, name: str, cat: str = "event", id=None, **args) -> None:
        if cat in RECORD_CATS:
            self.keep(name, cat, "i", self._clock(), **args)
            return
        self._emit(name, cat, "i", id=id, args=args or None)

    def keep(self, name: str, cat: str, ph: str, start_s, dur_s=None,
             **args) -> None:
        """An event of a kept category (:data:`RECORD_CATS`): into the
        process-wide record (:func:`setup_record`, :func:`step_record`)
        whether or not this tracer is enabled, and into the ring when it
        is. A tuple and a dict each: set-up and compile events are off the
        hot path, and a training step writes a handful."""
        row = (name, cat, ph, start_s * 1e6,
               None if dur_s is None else dur_s * 1e6, None, args or None,
               threading.get_ident() & 0xFFFF)
        _RECORD[cat].append(self._to_dict(row))
        if self.enabled:
            self._buf.append(row)

    @contextlib.contextmanager
    def gc_events(self, min_s: float = 1e-3):
        """While the ``with`` runs, each collection of the interpreter
        that took ``min_s`` or longer, on whatever thread set it off, is
        a ``host.gc`` complete event of category ``step`` (args
        ``generation``, ``collected``); ``gc.callbacks`` is as found
        afterwards."""
        began = 0.0

        def on_gc(phase, info):
            nonlocal began
            now = self._clock()
            if phase == "start":
                began = now
            elif now - began >= min_s:
                self.keep("host.gc", "step", "X", began, now - began,
                          generation=info["generation"],
                          collected=info["collected"])

        gc.callbacks.append(on_gc)
        try:
            yield
        finally:
            gc.callbacks.remove(on_gc)

    # -- draining -------------------------------------------------------------

    def _snapshot_rows(self, clear: bool) -> list:
        with self._lock:
            for _ in range(8):
                try:
                    rows = list(self._buf)
                    break
                except RuntimeError:
                    continue  # a lock-free append landed mid-copy
            else:
                rows = []
            if clear:
                # drop exactly what was copied, from the left — an event
                # appended after the copy (or a copy that never
                # succeeded) stays buffered for the next flush instead
                # of being silently destroyed. Caveat: with the ring AT
                # capacity, a concurrent append evicts a copied row
                # before we pop it, so one popleft lands on an uncopied
                # event — that regime is already lossy by definition
                # (every such append bumped `dropped`), and the ring is
                # sized (2^17) far above any drain's backlog.
                for _ in range(len(rows)):
                    try:
                        self._buf.popleft()
                    except IndexError:
                        break
        return rows

    def events(self) -> List[dict]:
        """The buffered (unflushed) events as Chrome-format dicts — what
        tests assert on without touching the filesystem."""
        return [self._to_dict(r) for r in self._snapshot_rows(clear=False)]

    def _to_dict(self, row) -> dict:
        name, cat, ph, ts, dur, id, args, tid = row
        ev = {"name": name, "cat": cat, "ph": ph, "ts": ts,
              "pid": self._pid, "tid": tid}
        if dur is not None:
            ev["dur"] = dur
        if id is not None:
            ev["id"] = id
        if args:
            ev["args"] = args
        if ph == "i":
            ev["s"] = "t"  # instant scope: thread
        return ev

    def flush(self) -> int:
        """Serialize and append everything buffered to ``path`` (JSONL,
        one event per line); returns the number written. No-op without a
        path — the in-memory ring stays readable either way."""
        rows = self._snapshot_rows(clear=bool(self.path))
        if not self.path or not rows:
            return 0
        dumps = json.dumps
        lines = [
            dumps(self._to_dict(r), default=repr, separators=(",", ":"))
            for r in rows
        ]
        with open(self.path, "a") as f:
            f.write("\n".join(lines) + "\n")
        return len(rows)

    def close(self) -> None:
        self.flush()


# the process's own tracer: what writes set-up spans where no Server or
# Trainer was handed one (the import stamps, the CLIs, a Trainer built
# without ``tracer=``). Its ring is off; the record is always on.
PROCESS_TRACER = Tracer(path=None, clock=time.monotonic, enabled=False)


def _kept(*cats: str) -> List[dict]:
    events: List[dict] = []
    for cat in cats:
        for _ in range(8):
            try:
                events += list(_RECORD[cat])
                break
            except RuntimeError:
                continue  # an append on another thread landed mid-copy
    events.sort(key=lambda e: e["ts"] + e.get("dur", 0.0))
    return events


def setup_record() -> List[dict]:
    """The ``setup`` and ``compile`` events of this process so far (the
    newest 4,096 and 8,192), as Chrome-format dicts in the order they
    ended (a complete event is written at its END, so a parent follows
    its children)."""
    return _kept("setup", "compile")


def step_record() -> List[dict]:
    """The ``step`` events of this process so far (the newest 16,384:
    some 3,000 steps of ``Trainer.train``), in the same form and order.
    The ``compile`` events of a program built inside the loop are in
    :func:`setup_record`, on the same clock: a reader places them in a
    step by their timestamps."""
    return _kept("step")


def import_begin() -> None:
    """The first line of an entry package (``orion_tpu.serving`` /
    ``.training``); one may import the other."""
    _imports_open.append(time.monotonic())


def import_done(module: str, started_s: float) -> None:
    """The last line of an entry package: ``setup.import``, written by
    the outermost package of an import. The process's first runs from
    ``started_s``, the stamp at the first line of ``orion_tpu/__init__.py``;
    a package imported later adds its own stretch."""
    global _import_written
    began = _imports_open.pop()
    if _imports_open:
        return
    if not _import_written:
        _import_written, began = True, started_s
    PROCESS_TRACER.keep("setup.import", "setup", "X", began,
                        time.monotonic() - began, module=module)


def on_compile(sink) -> None:
    """``sink(name, start_s, dur_s, args)`` (a bound method, held weakly)
    hears every compile event from now on, on the thread that compiled."""
    _COMPILE_SINKS[:] = [r for r in _COMPILE_SINKS if r() is not None]
    _COMPILE_SINKS.append(weakref.WeakMethod(sink))


def compile_counts(name: str, dur_s: float, args: dict):
    """What one compile event adds to which of :data:`COMPILE_COUNTERS`."""
    if name == "compile.backend":
        loaded = args.get("source") == "cache"
        return (("programs_cache_loaded" if loaded else "programs_compiled", 1),
                ("compile_ms_total", dur_s * 1e3))
    if name == "compile.kernel":  # its seconds lie inside a compile.trace
        return (("kernel_bodies_traced", 1),)
    return (("programs_traced", 1 if name == "compile.trace" else 0),
            ("trace_lower_ms_total", dur_s * 1e3))


def compile_event(name: str, start_s: float, dur_s: float, **args) -> None:
    """One stage of one program jax built (``utils/profiling.py`` calls
    this from its ``jax.monitoring`` listeners, on ``time.monotonic``):
    kept in the record, added to the totals, handed to the sinks."""
    PROCESS_TRACER.keep(name, "compile", "X", start_s, dur_s, **args)
    for key, n in compile_counts(name, dur_s, args):
        _COMPILE_TOTALS[key] += n
    for ref in list(_COMPILE_SINKS):
        sink = ref()
        if sink is not None:  # a dead one goes at the next on_compile
            sink(name, start_s, dur_s, args)


def kernel_call_site() -> None:
    """One call of a Mosaic kernel entry (``ops/pallas.kernel_entry``),
    whether jax then traces its body (a ``compile.kernel`` event) or binds
    the jaxpr it kept."""
    _COMPILE_TOTALS["kernel_call_sites"] += 1


def remat_kept(nbytes: int) -> None:
    """One named residual of ``nbytes`` that a rematted block's policy kept
    for its backward (``models/transformer.py::_keeps``, while jax traces a
    gradient program): what that program holds so as not to compute it twice."""
    _COMPILE_TOTALS["remat_kept_residuals"] += 1
    _COMPILE_TOTALS["remat_kept_bytes"] += nbytes


def compile_totals() -> Dict[str, float]:
    """The process's :data:`COMPILE_COUNTERS` so far, ``kernel_call_sites``
    (``kernel_bodies_traced`` over it is the share of kernel calls whose body
    had to be traced) and ``remat_kept_residuals`` / ``remat_kept_bytes``."""
    return dict(_COMPILE_TOTALS)


def setup_summary(events: Optional[List[dict]] = None) -> dict:
    """What an operator asks of a slow start: seconds by top-level
    ``setup`` span (one not inside another), the programs jax compiled
    and those it loaded from its cache with their seconds, and the
    slowest compiled programs by name."""
    events = setup_record() if events is None else events
    spans = [e for e in events if e["cat"] == "setup" and e["ph"] == "X"]
    by_span: Dict[str, float] = {}
    for e in spans:
        inside = any(
            o is not e and o["ts"] <= e["ts"]
            and e["ts"] + e["dur"] <= o["ts"] + o["dur"] for o in spans
        )
        if not inside:
            by_span[e["name"]] = round(
                by_span.get(e["name"], 0.0) + e["dur"] / 1e6, 3)
    backend = [e for e in events if e["name"] == "compile.backend"]
    compiled = [e for e in backend if e["args"].get("source") != "cache"]
    staged = [e for e in events if e["name"] in ("compile.trace", "compile.lower")]
    return {
        "seconds_by_span": by_span,
        "programs_compiled": len(compiled),
        "programs_cache_loaded": len(backend) - len(compiled),
        "compile_or_load_s": round(sum(e["dur"] for e in backend) / 1e6, 3),
        "trace_lower_s": round(sum(e["dur"] for e in staged) / 1e6, 3),
        "slowest_compiled": [
            (e["args"].get("fun_name"), round(e["dur"] / 1e6, 3))
            for e in sorted(compiled, key=lambda e: -e["dur"])[:5]
        ],
        "ready": any(e["name"] == "setup.ready" for e in events),
    }


def read_jsonl(path: str) -> List[dict]:
    """Parse one tracer JSONL file back into event dicts (skips blank
    lines; raises on malformed ones — a trace that doesn't parse is a
    finding, not something to paper over)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def merge_traces(paths: List[str], out_path: str) -> int:
    """Concatenate N JSONL trace files (fleet parent + every replica)
    into ONE Perfetto-loadable ``{"traceEvents": [...]}`` document,
    sorted by ``ts``. Missing files are skipped (a replica that never
    flushed is absence, not an error). Returns the event count."""
    events: List[dict] = []
    for p in paths:
        if p and os.path.exists(p):
            events.extend(read_jsonl(p))
    events.sort(key=lambda e: e.get("ts", 0))
    doc = {"traceEvents": events, "displayTimeUnit": "ms"}
    d = os.path.dirname(os.path.abspath(out_path))
    os.makedirs(d, exist_ok=True)
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, out_path)
    return len(events)


def span_pairs(events: List[dict]) -> dict:
    """Index async b/e events by (cat, id, name) -> {"b": [...], "e":
    [...]} — the test helper behind the span-pairing acceptance (every
    opened span must close, exactly once per open)."""
    out: dict = {}
    for ev in events:
        if ev.get("ph") in ("b", "e"):
            key = (ev.get("cat"), ev.get("id"), ev.get("name"))
            out.setdefault(key, {"b": [], "e": []})[ev["ph"]].append(ev)
    return out


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser("orion_tpu.obs.trace")
    sub = p.add_subparsers(dest="cmd", required=True)
    m = sub.add_parser("merge", help="merge JSONL traces into a "
                                     "Perfetto-loadable JSON document")
    m.add_argument("paths", nargs="+")
    m.add_argument("-o", "--out", required=True)
    args = p.parse_args(argv)
    n = merge_traces(args.paths, args.out)
    print(f"wrote {n} events to {args.out}")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())


__all__ = [
    "Tracer", "Span", "NULL_SPAN", "read_jsonl", "merge_traces", "span_pairs",
    "PROCESS_TRACER", "setup_record", "step_record", "note_open",
    "setup_summary", "compile_event",
    "compile_counts", "compile_totals", "kernel_call_site", "on_compile", "import_begin",
    "import_done",
    "COMPILE_COUNTERS",
]
