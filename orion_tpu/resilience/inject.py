"""Deterministic fault injection: test-controlled failures at named
production hook points.

The production code carries permanent, near-zero-cost hooks — a
``fire(site, step=...)`` call at each faultable operation — that are inert
until a test arms a :class:`FaultPlan` via the :func:`inject` context
manager. Faults are addressed by ``(site, step, occurrence count)``, so a
chaos test can say "the checkpoint write at step 2 fails twice, then
succeeds" and get exactly that, every run.

Hook sites wired today:

========================  ====================================================
``"ckpt.save"``           training/checkpoint.py, inside the retry region
``"ckpt.restore"``        training/checkpoint.py, inside the retry region
``"data.batch"``          training/data.py prefetch worker, inside the retry
                          region
``"train.step_boundary"`` trainer loop, after bookkeeping for each step —
                          where :meth:`FaultPlan.preempt_at` delivers a real
                          SIGTERM (the installed PreemptionGuard then drives
                          the graceful-stop path end to end)
``"train.nan"``           consumed via :func:`nan_armed` by ``Trainer.step``
                          to poison one step's gradients to NaN
``"serve.ckpt_load"``     generate.load_params, inside the retry region —
                          serving-side checkpoint restore
``"serve.tokenizer_io"``  serving/server.py tokenizer load, inside the retry
                          region
``"serve.chunk"``         serving/batching.py SlotEngine.step, at each decode
                          chunk boundary (step = the engine's boundary
                          index) — where :meth:`FaultPlan.preempt_at_chunk`
                          delivers a real SIGTERM mid-request
``"decode.state_nan"``    consumed via :func:`decode_nan_armed` by
                          SlotEngine._attempt to poison one chunk's (S, z)/KV
                          decode state to NaN, whichever slot is at that
                          chunk index — each rung of the serving
                          degradation ladder is reached by arming 1, 2, or
                          unlimited deliveries at the same chunk
``"decode.slot_nan.K"``   consumed via :func:`decode_slot_nan_armed` by the
                          slot-multiplexed SlotEngine (serving/batching.py)
                          to poison ONLY slot K's rows of the batched decode
                          state at that request's chunk index — the per-slot
                          ladder's chaos address
``"serve.session_save"``  serving/session_store.py SessionStore.save, inside
                          the retried write of one session generation
                          (step = the generation number)
``"serve.session_load"``  serving/session_store.py SessionStore.load, inside
                          the retried read of one session generation
                          (step = the generation number)
``"serve.prefix_save"``   serving/prefix_store.py PrefixStore.publish, inside
                          the retried write of one prefix generation
                          (step = the generation number) — a kill here must
                          leave the previous generation the newest committed
``"serve.prefix_load"``   serving/prefix_store.py PrefixStore lookup, inside
                          the retried read of one candidate generation
                          (step = the generation number) — a fault here must
                          fall back to a cold prefill, never fail the request
``"fleet.dispatch"``      fleet/router.py Router.submit, before each
                          replica-placement attempt (step = the fleet-wide
                          dispatch ordinal) — an injected fault here fails
                          over to the next candidate replica
``"fleet.replica_spawn"`` fleet/supervisor.py replica spawn, inside the
                          retry region (step = the spawn ordinal)
``"fleet.control_io"``    fleet/replica.py ProcessReplica control-channel
                          writes (parent side) — an injected OSError models
                          a broken pipe to a dead child
========================  ====================================================

Every wired site is REGISTERED in :data:`SITES` (dynamic per-slot sites by
prefix in :data:`SITE_PREFIXES`); :meth:`FaultPlan.add` rejects unknown
names so a chaos test can't silently arm a typo that never fires, and the
meta-test in tests/test_resilience.py asserts every registered site is
exercised by at least one chaos test — a new hook can't rot untested.

Also here: :func:`corrupt_step` / :func:`truncate_step`, which damage a
written orbax step directory on disk the way flaky storage does — the
integrity-verified restore path (training/checkpoint.py) is tested against
both — and their session-store analogues :func:`corrupt_session` /
:func:`truncate_session` (serving/session_store.py restore fallback).
"""

from __future__ import annotations

import contextlib
import dataclasses
import errno
import os
import signal
import threading
import time
from typing import Callable, Dict, List, Optional

_NAN_SITE = "train.nan"
_DECODE_NAN_SITE = "decode.state_nan"
_CHUNK_SITE = "serve.chunk"

# The registry of every wired hook site (site -> where it fires). Keeping
# this table beside the delivery machinery makes two guarantees cheap:
# FaultPlan.add rejects typo'd site names at authoring time, and the
# chaos-coverage meta-test (tests/test_resilience.py) can assert each
# entry is exercised by at least one chaos test.
SITES = {
    "ckpt.save": "training/checkpoint.py maybe_save, inside retry",
    "ckpt.restore": "training/checkpoint.py restore, inside retry",
    "data.batch": "training/data.py prefetch worker, inside retry",
    "train.step_boundary": "trainer loop, each step boundary",
    "train.nan": "Trainer.step NaN-gradient poisoning marker",
    "serve.ckpt_load": "generate.load_params, inside retry",
    "serve.tokenizer_io": "serving/server.py tokenizer load, inside retry",
    "serve.chunk": "serving/batching.py SlotEngine.step, each chunk boundary",
    "serve.chunk_delay": "serving/server.py _step_chunk, INSIDE the timed "
                         "chunk boundary (step = server-lifetime chunk "
                         "ordinal) — added host latency for SLO chaos",
    "decode.state_nan": "SlotEngine per-chunk decode-state poisoning marker",
    "serve.session_save": "serving/session_store.py save, inside retry",
    "serve.session_load": "serving/session_store.py load, inside retry",
    "serve.session_scan": "serving/session_store.py generations(), before "
                          "the directory listing — the staleness probe a "
                          "shared-store replica pays per lookup",
    "serve.prefix_scan": "serving/prefix_store.py generations(), before "
                         "the directory listing — the per-candidate "
                         "existence probe of a prefix lookup",
    "serve.prefix_save": "serving/prefix_store.py publish, inside the "
                         "retried write of one prefix generation",
    "serve.prefix_load": "serving/prefix_store.py lookup, inside the "
                         "retried read of one candidate generation",
    "serve.exec_scan": "serving/exec_store.py _io_listdir, before the "
                       "directory listing — the existence probe of an "
                       "executable lookup/publish",
    "serve.exec_save": "serving/exec_store.py publish, inside the retried "
                       "write of one serialized-executable generation",
    "serve.exec_load": "serving/exec_store.py lookup, inside the retried "
                       "read of one candidate generation",
    "fleet.dispatch": "fleet/router.py submit, before each placement "
                      "attempt (step = fleet-wide dispatch ordinal)",
    "fleet.replica_spawn": "fleet/supervisor.py _spawn, inside the spawn "
                           "retry region (step = spawn ordinal)",
    "fleet.control_io": "fleet/replica.py control-channel write (parent "
                        "side), before the pipe I/O",
}
# dynamically-addressed site families (matched by prefix)
SITE_PREFIXES = ("decode.slot_nan.",)

# Sustained-regime fault kinds (FaultPlan.degrade_site): how a degraded
# site fails for the whole regime window, not just one occurrence.
# ``eio``/``enospc`` raise the matching OSError (media failure / full
# disk), ``partition`` raises ETIMEDOUT (the store is network-attached
# and the network is gone), ``latency`` adds host delay but succeeds —
# the regime a breaker must catch WITHOUT an error ever surfacing.
REGIME_KINDS = ("eio", "enospc", "latency", "partition")

_REGIME_ERRNO = {
    "eio": errno.EIO,
    "enospc": errno.ENOSPC,
    "partition": errno.ETIMEDOUT,
}


def known_site(site: str) -> bool:
    return site in SITES or site.startswith(SITE_PREFIXES)


def known_regime_prefix(prefix: str) -> bool:
    """A regime prefix must cover at least one registered site (or site
    family) — a regime that can never fire is a typo, same contract as
    :meth:`FaultPlan.add`."""
    return (
        any(s == prefix or s.startswith(prefix) for s in SITES)
        or any(p == prefix or p.startswith(prefix) for p in SITE_PREFIXES)
        or prefix.startswith(SITE_PREFIXES)
    )


def _decode_slot_site(slot: int) -> str:
    """Slot-addressed decode-state poisoning site (the batched engine's
    per-slot analogue of ``decode.state_nan``)."""
    return f"decode.slot_nan.{slot}"


@dataclasses.dataclass
class _Fault:
    site: str
    step: Optional[int]  # None = any step
    times: int  # remaining deliveries; <0 = unlimited
    action: Optional[Callable[[], None]]  # None = marker (consumed via query)


@dataclasses.dataclass
class _Regime:
    """A sustained outage: every fire() on a site matching ``prefix``
    fails (or stalls) while the regime clock is inside
    ``[from_step, until_step)``. The clock is the last step observed at
    ``clock_site`` — by default ``serve.chunk_delay``, the server's
    lifetime chunk ordinal, so "the store is down for chunks 10..30" is
    one deterministic sentence regardless of how each store site numbers
    its own steps (generation numbers, spawn ordinals, ...)."""

    prefix: str
    kind: str  # one of REGIME_KINDS
    from_step: int
    until_step: Optional[int]  # exclusive; None = never ends
    latency: float
    clock_site: str


# delivery observers (the telemetry spine's black box): every DELIVERED
# fault — marker or action, any site — is reported to each subscribed
# callback as (site, step) AFTER the plan lock is released (an observer
# that records, dumps, or logs must never run under the delivery lock).
# The flight recorder (orion_tpu/obs/flight.py) subscribes here so an
# injected fault can never fire without leaving a trace in the ring —
# the site⇄event parity the chaos meta-test asserts.
_observers: List[Callable[[str, Optional[int]], None]] = []


def add_observer(fn: Callable[[str, Optional[int]], None]) -> None:
    if fn not in _observers:
        _observers.append(fn)


def remove_observer(fn: Callable[[str, Optional[int]], None]) -> None:
    try:
        _observers.remove(fn)
    except ValueError:
        pass


def _notify_delivery(site: str, step: Optional[int]) -> None:
    for fn in list(_observers):
        try:
            fn(site, step)
        except Exception:
            pass  # a broken observer must never mask the fault itself


class FaultPlan:
    """An ordered set of faults to deliver. Thread-safe: the data-loader
    worker and the main thread both fire hooks."""

    def __init__(self):
        self._faults: List[_Fault] = []
        self._regimes: List[_Regime] = []
        self._regime_clock: Dict[str, int] = {}  # clock_site -> last step
        self._lock = threading.Lock()
        self.delivered: List[str] = []  # "(site, step)" log for assertions
        self.sleep: Callable[[float], None] = time.sleep  # latency regimes

    # -- authoring -----------------------------------------------------------

    def add(
        self,
        site: str,
        step: Optional[int] = None,
        times: int = 1,
        action: Optional[Callable[[], None]] = None,
    ) -> "FaultPlan":
        if not known_site(site):
            raise ValueError(
                f"unknown fault-injection site {site!r}: a fault armed at a "
                "site no hook fires never delivers — register it in "
                "inject.SITES (and cover it in a chaos test) first"
            )
        self._faults.append(_Fault(site, step, times, action))
        return self

    def fail_io(
        self,
        site: str,
        step: Optional[int] = None,
        times: int = 1,
        exc: type = OSError,
        msg: str = "injected I/O fault",
    ) -> "FaultPlan":
        """Raise ``exc`` from the hook — the retry layer sees a transient
        storage error exactly where a real one would surface."""

        def raise_():
            raise exc(f"{msg} [site={site}]")

        return self.add(site, step, times, raise_)

    def degrade_site(
        self,
        prefix: str,
        kind: str = "eio",
        from_step: int = 0,
        until_step: Optional[int] = None,
        latency: float = 0.05,
        clock_site: str = "serve.chunk_delay",
    ) -> "FaultPlan":
        """Arm a SUSTAINED fault regime: every hook whose site starts
        with ``prefix`` fails (``kind`` in :data:`REGIME_KINDS`) for as
        long as the regime clock sits in ``[from_step, until_step)`` —
        the clock being the last step fired at ``clock_site`` (default
        ``serve.chunk_delay``, the server-lifetime chunk ordinal), so an
        outage window is phrased in one fleet-visible unit instead of
        each site's private step numbering. ``until_step=None`` never
        recovers (the SIGTERM-mid-outage drill). ``latency`` is the added
        host delay per operation for ``kind="latency"`` (the operation
        then SUCCEEDS — the brownout a breaker must catch without any
        error surfacing). Regimes layer UNDER one-shot faults: an armed
        one-shot at the same (site, step) takes precedence."""
        if kind not in REGIME_KINDS:
            raise ValueError(
                f"unknown regime kind {kind!r}; expected one of "
                f"{REGIME_KINDS}"
            )
        if not known_regime_prefix(prefix):
            raise ValueError(
                f"regime prefix {prefix!r} covers no registered "
                "fault-injection site: a regime no hook can enter never "
                "delivers — register the site(s) in inject.SITES first"
            )
        if not known_site(clock_site):
            raise ValueError(f"unknown regime clock site {clock_site!r}")
        if until_step is not None and until_step <= from_step:
            raise ValueError(
                f"empty regime window [{from_step}, {until_step})"
            )
        self._regimes.append(_Regime(
            prefix, kind, int(from_step),
            None if until_step is None else int(until_step),
            float(latency), clock_site,
        ))
        return self

    def preempt_at(self, step: int, sig: int = signal.SIGTERM) -> "FaultPlan":
        """Deliver a real OS signal at the given step's boundary. With a
        PreemptionGuard installed this exercises the whole graceful-stop
        path: handler -> stop request -> emergency checkpoint -> resumable
        exit."""
        return self.add(
            "train.step_boundary", step, 1, lambda: signal.raise_signal(sig)
        )

    def poison_nan_at(self, step: int) -> "FaultPlan":
        """Arm a NaN-gradient poisoning for one training step (consumed by
        ``Trainer.step`` via :func:`nan_armed`)."""
        return self.add(_NAN_SITE, step, 1, None)

    def preempt_at_chunk(self, chunk: int, sig: int = signal.SIGTERM) -> "FaultPlan":
        """Deliver a real OS signal at a serving request's decode-chunk
        boundary. With the Server's PreemptionGuard installed this drives
        the DRAINING path end to end: the in-flight request completes, new
        requests are rejected, the process exits 0."""
        return self.add(
            _CHUNK_SITE, chunk, 1, lambda: signal.raise_signal(sig)
        )

    def delay_chunk(
        self, seconds: float, chunk: Optional[int] = None, times: int = 1
    ) -> "FaultPlan":
        """Add ``seconds`` of host latency at a serving chunk boundary
        (site ``serve.chunk_delay``; step = the server-lifetime chunk
        ordinal, ``None`` = every boundary; ``times < 0`` = unlimited).
        Latency-shaped degradation becomes deterministically
        reproducible: the SLO engine's burn-rate alerts, the router's
        windowed-p99 tie-break, and the supervisor's drain-and-respawn
        are all chaos-addressable through this one site."""
        return self.add(
            "serve.chunk_delay", chunk, times, lambda: time.sleep(seconds)
        )

    def poison_decode_state_at(self, chunk: int, times: int = 1) -> "FaultPlan":
        """Arm NaN-poisoning of the decode state at a chunk boundary
        (consumed by serving's SlotEngine via :func:`decode_nan_armed`
        after each attempt at that chunk). ``times=1`` exercises the
        rewind rung of the degradation ladder, ``times=2`` forces the
        re-prefill rung, ``times<0`` (unlimited) exhausts the ladder and
        fails the request — never the process."""
        return self.add(_DECODE_NAN_SITE, chunk, times, None)

    def poison_decode_slot_at(
        self, slot: int, chunk: int, times: int = 1
    ) -> "FaultPlan":
        """Arm NaN-poisoning of ONE slot's rows of the slot-multiplexed
        batched decode state (serving/batching.py SlotEngine), at that
        slot's request-local chunk index. The per-slot ladder semantics
        mirror :meth:`poison_decode_state_at` — but only request ``slot``
        walks the ladder; co-resident slots must keep streaming
        untouched (the chaos acceptance in tests/test_batching.py)."""
        return self.add(_decode_slot_site(slot), chunk, times, None)

    # -- delivery ------------------------------------------------------------

    def _take(self, site: str, step: Optional[int]) -> Optional[_Fault]:
        taken = None
        with self._lock:
            for f in self._faults:
                if f.site != site or f.times == 0:
                    continue
                if f.step is not None and step is not None and f.step != step:
                    continue
                if f.step is not None and step is None:
                    continue
                if f.times > 0:
                    f.times -= 1
                self.delivered.append(f"{site}@{step}")
                taken = f
                break
        if taken is not None:
            # outside the lock: observers (the flight recorder) may take
            # their own locks or write files
            _notify_delivery(site, step)
        return taken

    def fire(self, site: str, step: Optional[int] = None) -> None:
        if self._regimes:
            self._advance_regime_clock(site, step)
        f = self._take(site, step)
        if f is not None:
            if f.action is not None:
                f.action()
            return
        if self._regimes:
            self._fire_regime(site, step)

    def _advance_regime_clock(self, site: str, step: Optional[int]) -> None:
        if step is None:
            return
        with self._lock:
            for r in self._regimes:
                if r.clock_site == site:
                    prev = self._regime_clock.get(site, -1)
                    self._regime_clock[site] = max(prev, int(step))

    def _fire_regime(self, site: str, step: Optional[int]) -> None:
        """Deliver the first matching active regime (recorded in
        ``delivered`` and reported to observers exactly like a one-shot
        fault — the flight-recorder parity meta-test covers regimes for
        free). ``eio``/``enospc``/``partition`` raise; ``latency`` sleeps
        outside the lock, then succeeds."""
        match = None
        with self._lock:
            for r in self._regimes:
                if not site.startswith(r.prefix):
                    continue
                # before the clock site ever fires, the regime clock
                # reads 0: a from_step=0 regime is live from process
                # start (the store can be down before the first chunk)
                now = self._regime_clock.get(r.clock_site, 0)
                if now < r.from_step:
                    continue
                if r.until_step is not None and now >= r.until_step:
                    continue
                self.delivered.append(f"{site}@{step}")
                match = r
                break
        if match is None:
            return
        _notify_delivery(site, step)
        if match.kind == "latency":
            self.sleep(match.latency)
            return
        raise OSError(
            _REGIME_ERRNO[match.kind],
            f"injected sustained {match.kind} regime "
            f"[site={site} prefix={match.prefix}]",
        )

    def consume_marker(self, site: str, step: Optional[int] = None) -> bool:
        return self._take(site, step) is not None


_active: Optional[FaultPlan] = None


@contextlib.contextmanager
def inject(plan: FaultPlan):
    """Arm ``plan`` for the duration of the block (not reentrant-safe per
    thread, but plans themselves are thread-safe)."""
    global _active
    prev = _active
    _active = plan
    try:
        yield plan
    finally:
        _active = prev


def active() -> bool:
    """Is any fault plan armed? Hot-path callers gate on this BEFORE
    computing hook arguments (e.g. the trainer's step number is a device
    scalar — reading it unconditionally would sync every step)."""
    return _active is not None


def fire(site: str, step: Optional[int] = None) -> None:
    """Production hook: no-op (one global read) unless a plan is armed."""
    plan = _active
    if plan is not None:
        plan.fire(site, step)


def nan_armed(step: int) -> bool:
    """Is a NaN-gradient poisoning armed for ``step``? Consumes it."""
    plan = _active
    return plan is not None and plan.consume_marker(_NAN_SITE, step)


def decode_nan_armed(chunk: int) -> bool:
    """Is a decode-state NaN-poisoning armed for this chunk? Consumes one
    delivery — the SlotEngine asks again after every ladder rung's
    retry of the same chunk, so multi-delivery plans poison each attempt
    in turn."""
    plan = _active
    return plan is not None and plan.consume_marker(_DECODE_NAN_SITE, chunk)


def decode_slot_nan_armed(slot: int, chunk: int) -> bool:
    """Is a slot-addressed decode-state poisoning armed for (slot, that
    request's chunk index)? Consumed per attempt, like
    :func:`decode_nan_armed` (the SlotEngine consumes the unaddressed
    site too: a single-request plan needs no slot index)."""
    plan = _active
    return plan is not None and plan.consume_marker(_decode_slot_site(slot), chunk)


# -- on-disk checkpoint corruption (test control, not a hook) -----------------


def _step_files(ckpt_dir: str, step: int) -> List[str]:
    step_dir = os.path.join(ckpt_dir, str(step))
    if not os.path.isdir(step_dir):
        raise FileNotFoundError(f"no step directory {step_dir}")
    out = []
    for dirpath, _, filenames in os.walk(step_dir):
        for f in sorted(filenames):
            out.append(os.path.join(dirpath, f))
    return sorted(out)


def corrupt_step(ckpt_dir: str, step: int) -> List[str]:
    """Flip bytes in the middle of every file of a written orbax step —
    the bit-rot / torn-write failure mode. Returns the files touched."""
    touched = []
    for path in _step_files(ckpt_dir, step):
        size = os.path.getsize(path)
        if size == 0:
            continue
        with open(path, "r+b") as f:
            f.seek(size // 2)
            chunk = f.read(min(64, size - size // 2))
            f.seek(size // 2)
            f.write(bytes(b ^ 0xFF for b in chunk))
        touched.append(path)
    return touched


def truncate_step(ckpt_dir: str, step: int) -> List[str]:
    """Truncate the step's largest file to half — the preempted-mid-write
    failure mode (an incomplete step directory)."""
    files = [p for p in _step_files(ckpt_dir, step) if os.path.getsize(p) > 0]
    target = max(files, key=os.path.getsize)
    with open(target, "r+b") as f:
        f.truncate(os.path.getsize(target) // 2)
    return [target]


# -- on-disk session corruption (test control, not a hook) --------------------


def _session_gen_bin(session_dir: str, session_id: str,
                     generation: Optional[int]) -> str:
    """Path of one session generation's payload file (default: newest)."""
    d = os.path.join(session_dir, session_id)
    gens = sorted(
        int(n[len("gen-"):-len(".bin")])
        for n in os.listdir(d)
        if n.startswith("gen-") and n.endswith(".bin")
    )
    if not gens:
        raise FileNotFoundError(f"no session generations under {d}")
    g = generation if generation is not None else gens[-1]
    return os.path.join(d, f"gen-{g:06d}.bin")


def corrupt_session(
    session_dir: str, session_id: str, generation: Optional[int] = None
) -> str:
    """Flip bytes in the middle of a saved session generation's payload
    (default: the newest) — the bit-rot failure the manifest's per-leaf
    crc32 exists to catch. The restore path must fall back to the previous
    intact generation with a loud warning, exactly like checkpoint
    restore. Returns the damaged path."""
    path = _session_gen_bin(session_dir, session_id, generation)
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.seek(size // 2)
        chunk = f.read(min(64, size - size // 2))
        f.seek(size // 2)
        f.write(bytes(b ^ 0xFF for b in chunk))
    return path


def truncate_session(
    session_dir: str, session_id: str, generation: Optional[int] = None
) -> str:
    """Truncate a saved session generation's payload to half — the torn
    write a kill mid-save leaves behind when it lands between the payload
    rename and the manifest rename. Returns the damaged path."""
    path = _session_gen_bin(session_dir, session_id, generation)
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 2)
    return path


__all__ = [
    "FaultPlan", "inject", "active", "fire", "nan_armed",
    "decode_nan_armed", "decode_slot_nan_armed", "corrupt_step",
    "truncate_step", "corrupt_session", "truncate_session",
    "SITES", "SITE_PREFIXES", "known_site",
    "REGIME_KINDS", "known_regime_prefix",
    "add_observer", "remove_observer",
]
