"""Declared lock hierarchy for the threaded serving stack — the Tier D
contract (`analysis/concurrency_audit.py` is the auditor).

PRs 4-15 accumulated a body of prose-only concurrency contracts: "the
router lock covers bookkeeping only, never the wire round-trip" (PR 8),
"SLO readers run before the engine lock, never nested under it" (PR 10),
"the HealthMachine shares the Server's stats RLock so snapshot() is ONE
atomic read" (PR 8/9), `_TP_EXEC_LOCK` serializing mesh launches after a
real XLA-CPU rendezvous deadlock (PR 14). Each was a bug or a near-miss
found by chaos testing. This module turns them into DATA, in the
`parallel/budgets.py` idiom: every lock in `serving/`, `fleet/`, `obs/`,
and `resilience/` is declared here with

- its **site** (module / class-or-function scope / attribute name) and
  any **aliases** — other sites that hold *the same object* (the Server
  injects its stats RLock into HealthMachine and MetricsRegistry, so all
  three are ONE node in the hierarchy);
- the partial acquisition **ORDER** over nodes (outer before inner);
- the fields it **guards** (written only while held; `__init__` and
  module-level construction paths are exempt by declaration);
- per-lock **held-scope bans** (categories from :data:`BAN_CATEGORIES`:
  wire I/O under the router lock, disk/subprocess/sleep under the stats
  lock, device syncs under any obs lock);
- whether its held scope is **strict** — a strict lock may not be held
  across a call the auditor has no summary for (`lock-scope-creep`),
  beyond builtins, constructors, container methods, same-module code,
  and the lock's declared `allow_calls`.

The auditor never imports the audited modules (pure AST) and this module
never imports them either — it is data, importable from anywhere without
dragging in jax. tests/test_concurrency_audit.py asserts every declared
site resolves to a real attribute assignment in the declaring module, so
dead declarations cannot rot (the `inject.SITES` registry idiom).

Deliberately **lock-free** designs are declared by omission and recorded
here so the next reader does not "fix" them:

- ``Tracer._emit`` appends to its deque without the tracer lock —
  ``deque.append`` is atomic under the GIL and the emit path runs at
  chunk cadence; only snapshot/rotate take ``obs.trace``.
- ``FlightRecorder.record_signal_safe`` skips the ring lock (a signal
  handler that blocks on a lock the interrupted code holds deadlocks at
  preemption time); the ``dropped`` counter is skipped rather than raced.
- ``ProcessReplica``'s ``_eof``/``last_status``/``last_heartbeat`` are
  written by the reader thread and read by callers without a lock:
  single-writer, GIL-published, staleness-tolerant by design.
- The SlotEngine's bookkeeping is guarded by ``engine.exec`` only for
  mesh engines; unsharded engines swap in a ``nullcontext`` because the
  scheduler thread is the sole writer (thread confinement, PR 14).
- ``ExecStore.stats`` (serving/exec_store.py) takes no lock: the int
  slots are written only by the store's owner thread (the engine
  scheduler at serving time, the CLI main thread under ``aot warm``)
  and read by metrics gauge closures — single-writer, GIL-published,
  staleness-tolerant, same contract as ``ProcessReplica.last_status``.
  The resident-executable LRU is owner-thread-confined the same way.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, FrozenSet, Tuple

__all__ = [
    "Ban",
    "BAN_CATEGORIES",
    "GuardedField",
    "LockDecl",
    "LockSite",
    "LOCKS",
    "ORDER",
    "obs_lock_attrs",
]


@dataclass(frozen=True)
class LockSite:
    """Where a lock object lives: ``module`` is the repo-relative path of
    the declaring module, ``scope`` the class (or, for function-local
    locks, the function) that owns it ('' = module level), ``attr`` the
    attribute / variable name bound to the lock object."""

    module: str
    scope: str
    attr: str


@dataclass(frozen=True)
class GuardedField:
    """A field that must only be WRITTEN while the declaring lock is
    held. Matching is (module, field) over attribute-assignment targets
    (subscript stores included: ``self._slots[i] = ...`` writes
    ``_slots``); mutation through container methods (``.append``) is out
    of the auditor's scope — declare the intent in ``note`` instead."""

    module: str
    scope: str
    fields: Tuple[str, ...]
    note: str = ""


@dataclass(frozen=True)
class Ban:
    """One held-scope ban category: call shapes that must never execute
    while a lock declaring the category is held. ``names`` are bare
    callables, ``dotted`` exact dotted calls, ``dotted_prefixes`` dotted
    prefixes (must end with '.'), ``attrs`` method names on non-``self``
    receivers. ``classifier`` names a special matcher implemented by the
    auditor (``device_sync`` reuses obs-device-sync's sync classifier)."""

    category: str
    note: str
    names: Tuple[str, ...] = ()
    dotted: Tuple[str, ...] = ()
    dotted_prefixes: Tuple[str, ...] = ()
    attrs: Tuple[str, ...] = ()
    classifier: str = ""


@dataclass(frozen=True)
class LockDecl:
    name: str
    site: LockSite
    kind: str  # "Lock" | "RLock"
    note: str
    aliases: Tuple[LockSite, ...] = ()
    guards: Tuple[GuardedField, ...] = ()
    # method names (within the guarding module) whose writes are
    # construction-path exempt; module-level statements are always exempt
    guard_exempt: Tuple[str, ...] = ("__init__",)
    bans: Tuple[str, ...] = ()
    # strict held scope: no calls to unknown code while held
    strict_scope: bool = False
    # names/attrs/dotted calls additionally allowed under a strict scope
    allow_calls: Tuple[str, ...] = ()
    # decorator names whose wrapped method body runs with this lock held
    # (batching's @_serialized takes the exec guard in the wrapper, so
    # the decorated body's own AST shows no `with`)
    decorators: Tuple[str, ...] = ()


# -- held-scope ban categories -------------------------------------------------
#
# Categories are defined once and referenced by name from each LockDecl;
# the auditor (`blocking-under-lock`) matches call sites against the
# union of every held lock's categories. The sets are deliberately
# narrow: each entry is a call that can block for SECONDS (wire, disk,
# child processes) or stall every resident slot (a device sync), not a
# style preference.

BAN_CATEGORIES: Dict[str, Ban] = {
    "wire": Ban(
        category="wire",
        note="a wire round-trip to a replica child can block for seconds "
        "on a wedged process; holding a bookkeeping lock across it "
        "stalls every other submitter and the supervisor's healing "
        "path (the PR 8 router contract, now checkable)",
        attrs=(
            "submit", "cancel", "status", "scrape_metrics",
            "request_profile", "send", "sendall", "recv", "_send", "_rpc",
        ),
        dotted_prefixes=("socket.",),
    ),
    "disk-io": Ban(
        category="disk-io",
        note="filesystem latency is unbounded (NFS, a full disk); state "
        "files and dumps are written OUTSIDE locks from a snapshot "
        "taken under them",
        names=("open",),
        dotted=(
            "os.replace", "os.makedirs", "os.remove", "os.rename",
            "os.unlink", "os.fsync", "json.dump",
        ),
        dotted_prefixes=("shutil.",),
    ),
    "subprocess": Ban(
        category="subprocess",
        note="spawning or reaping a child under a lock serializes every "
        "other holder behind fork/exec and an unbounded wait",
        names=("Popen",),
        dotted_prefixes=("subprocess.",),
        attrs=("communicate",),
    ),
    "sleep": Ban(
        category="sleep",
        note="a sleep (or a retry/backoff loop, which is a sleep in a "
        "loop) under a lock converts every waiter's latency floor "
        "into the sleep duration",
        names=("sleep",),
        dotted=("time.sleep",),
    ),
    "device-sync": Ban(
        category="device-sync",
        note="one device sync under a telemetry lock stalls every "
        "resident slot for the transfer; the obs spine is host-only "
        "(obs-device-sync) and its locks must stay that way even "
        "when aliased into non-obs modules",
        classifier="device_sync",
    ),
}


# -- the lock table ------------------------------------------------------------

_SERVER = "orion_tpu/serving/server.py"
_BATCHING = "orion_tpu/serving/batching.py"
_HEALTH = "orion_tpu/serving/health.py"
_ROUTER = "orion_tpu/fleet/router.py"
_REPLICA = "orion_tpu/fleet/replica.py"
_METRICS = "orion_tpu/obs/metrics.py"
_TRACE = "orion_tpu/obs/trace.py"
_SLO = "orion_tpu/obs/slo.py"
_COST = "orion_tpu/obs/cost.py"
_FLIGHT = "orion_tpu/obs/flight.py"
_WATCHDOG = "orion_tpu/resilience/watchdog.py"
_INJECT = "orion_tpu/resilience/inject.py"
_BREAKER = "orion_tpu/resilience/breaker.py"

LOCKS: Dict[str, LockDecl] = {
    decl.name: decl
    for decl in [
        # -- serving ----------------------------------------------------------
        LockDecl(
            name="server.stats",
            site=LockSite(_SERVER, "Server", "_stats_lock"),
            kind="RLock",
            note="the Server's metrics/health/profiling lock. Reentrant "
            "and SHARED: the Server injects it into HealthMachine and "
            "MetricsRegistry (lock= kwarg) so Server.snapshot() reads "
            "health + gauges as one atomic pair — all three sites are "
            "this ONE node. Standalone HealthMachine/MetricsRegistry "
            "instances default-construct their own lock; the discipline "
            "is identical either way.",
            aliases=(
                LockSite(_HEALTH, "HealthMachine", "_lock"),
                LockSite(_METRICS, "MetricsRegistry", "_lock"),
            ),
            guards=(
                GuardedField(
                    _SERVER, "Server",
                    ("_profile_pending", "_profile_left"),
                    note="the /profilez arm handshake: a scrape thread "
                    "arms, the scheduler consumes — the 409 guarantee "
                    "('one capture at a time') is exactly these two "
                    "fields read-modify-written under one lock",
                ),
                GuardedField(
                    _HEALTH, "HealthMachine",
                    ("_state", "_since", "dropped"),
                    note="the signal path and the serve loop both drive "
                    "transitions; history append rides the same scope",
                ),
                GuardedField(
                    _METRICS, "MetricsRegistry",
                    ("_counters", "_gauges", "_hists"),
                    note="cell mutation from any thread (Counter.inc et "
                    "al. all take the registry lock)",
                ),
            ),
            # Server.__init__ runs its whole body as _build, inside the
            # ``setup.server`` span
            guard_exempt=("__init__", "_build"),
            bans=("wire", "disk-io", "subprocess", "sleep", "device-sync"),
        ),
        LockDecl(
            name="server.admission",
            site=LockSite(_SERVER, "Server", "_admission_lock"),
            kind="Lock",
            note="serializes submit()'s accept/reject decision against "
            "drain: health gate, rid sequencing, root-span begin, and "
            "the queue put are one atomic admission. Nests OUTSIDE "
            "server.stats (serve()'s drain path transitions health — "
            "which takes the stats lock — while holding admission).",
            guards=(
                GuardedField(
                    _SERVER, "Server", ("_rid_seq",),
                    note="request ids must be unique across concurrent "
                    "submit threads",
                ),
            ),
            guard_exempt=("__init__", "_build"),
            bans=("disk-io", "subprocess", "sleep", "device-sync"),
        ),
        LockDecl(
            name="engine.exec",
            site=LockSite(_BATCHING, "", "_TP_EXEC_LOCK"),
            kind="RLock",
            note="process-wide serialization of collective-program "
            "launches from co-resident mesh engines (XLA-CPU rendezvous "
            "deadlock, PR 14). Reentrant: entry points nest through the "
            "ladder. Unsharded engines alias a nullcontext — there the "
            "scheduler thread is the sole writer (thread confinement). "
            "Device work under this lock is its PURPOSE, so it has no "
            "held-scope bans.",
            aliases=(LockSite(_BATCHING, "SlotEngine", "_exec_lock"),),
            guards=(
                GuardedField(
                    _BATCHING, "SlotEngine",
                    ("_slots", "_carry", "_rngs", "_plen", "_pfold",
                     "_staged"),
                    note="slot table + the O(1) decode carry + the "
                    "admissions not yet staged into it: every "
                    "mutation happens inside a @_serialized entry point "
                    "or a helper it calls",
                ),
            ),
            decorators=("_serialized",),
        ),
        # -- fleet ------------------------------------------------------------
        LockDecl(
            name="router.lock",
            site=LockSite(_ROUTER, "Router", "_lock"),
            kind="RLock",
            note="the fleet's outermost lock: session fence, admission "
            "count, dispatch counters. Covers BOOKKEEPING ONLY — never "
            "the wire round-trip, and never a replica-handle method "
            "call (a wedged child must not stall other submitters, the "
            "gauges, or the supervisor). Strict scope: the auditor "
            "flags any unknown call while it is held.",
            guards=(
                GuardedField(
                    _ROUTER, "Router",
                    ("_active_sessions", "_dispatches", "_dispatching",
                     "_turn_seq", "stats", "replicas"),
                    note="all router state; submitter threads and the "
                    "supervisor's replace() race on it",
                ),
            ),
            bans=("wire", "disk-io", "subprocess", "sleep", "device-sync"),
            strict_scope=True,
        ),
        LockDecl(
            name="router.turn_once",
            site=LockSite(_ROUTER, "_attach_turn_close", "once"),
            kind="Lock",
            note="per-turn close arbitration: a non-blocking try-acquire "
            "that is deliberately never released — exactly one of the "
            "two possible closers (on_done callback vs the already-done "
            "fast path) wins it, so the root span can neither "
            "double-close nor leak. Holding it across the trace emit is "
            "the design.",
        ),
        LockDecl(
            name="replica.send",
            site=LockSite(_REPLICA, "ProcessReplica", "_send_lock"),
            kind="Lock",
            note="serializes writes to the child's stdin pipe — wire I/O "
            "UNDER this lock is its purpose (interleaved partial JSON "
            "lines would corrupt the control channel), so 'wire' is "
            "deliberately absent from its bans.",
            bans=("disk-io", "subprocess", "sleep", "device-sync"),
        ),
        LockDecl(
            name="replica.state",
            site=LockSite(_REPLICA, "ProcessReplica", "_state_lock"),
            kind="Lock",
            note="request bookkeeping (pending map, reply routing, "
            "inflight count, id sequence). The wire round-trip happens "
            "OUTSIDE it — submit/_rpc reserve under the lock, release, "
            "then touch the pipe (the same shape as the router lock, "
            "one level down).",
            guards=(
                GuardedField(
                    _REPLICA, "ProcessReplica",
                    ("_pendings", "_replies", "_next_id"),
                    note="submit threads and the reader thread race on "
                    "these maps",
                ),
            ),
            bans=("wire", "sleep", "device-sync"),
        ),
        LockDecl(
            name="replica.local",
            site=LockSite(_REPLICA, "LocalReplica", "_lock"),
            kind="Lock",
            note="in-process replica's outstanding-request ledger.",
            guards=(
                GuardedField(
                    _REPLICA, "LocalReplica", ("_outstanding",),
                    note="submitters and worker completions race on it",
                ),
            ),
            bans=("wire", "sleep", "device-sync"),
        ),
        LockDecl(
            name="replica.child_out",
            site=LockSite(_REPLICA, "_child_main", "out_lock"),
            kind="Lock",
            note="child-process side: serializes result/heartbeat lines "
            "onto the one stdout pipe (the mirror image of "
            "replica.send in the parent).",
        ),
        # -- obs --------------------------------------------------------------
        LockDecl(
            name="obs.trace",
            site=LockSite(_TRACE, "Tracer", "_lock"),
            kind="Lock",
            note="snapshot/rotate arbitration only. The emit hot path is "
            "deliberately LOCK-FREE (deque.append is atomic under the "
            "GIL); guarding the buffer here would put a lock on every "
            "chunk boundary — declared by omission, see module "
            "docstring.",
            bans=("device-sync",),
        ),
        LockDecl(
            name="obs.slo",
            site=LockSite(_SLO, "SLOEngine", "_lock"),
            kind="Lock",
            note="publishes tick()'s payload for lock-cheap state() "
            "reads. tick() runs its READERS first, then takes this lock "
            "(PR 10): a reader that blocked under it would weld scrape "
            "liveness to the scheduler. Nests INSIDE server.stats "
            "(Server.snapshot() calls slo.state() while holding stats).",
            guards=(
                GuardedField(
                    _SLO, "SLOEngine", ("_state",),
                    note="the published payload; scrape threads read it "
                    "under the same lock",
                ),
            ),
            bans=("device-sync",),
        ),
        LockDecl(
            name="obs.cost.ledger",
            site=LockSite(_COST, "CostLedger", "_lock"),
            kind="Lock",
            note="program-cost entries + compile-time observations; "
            "written at trace/compile time, read by /costz scrapes.",
            bans=("device-sync",),
        ),
        LockDecl(
            name="obs.cost.capacity",
            site=LockSite(_COST, "CapacityModel", "_lock"),
            kind="Lock",
            note="capacity headroom state: tick() reads its counters "
            "BEFORE the lock (the slo.tick shape), publishes under it.",
            guards=(
                GuardedField(_COST, "CapacityModel", ("_state",)),
            ),
            bans=("device-sync",),
        ),
        LockDecl(
            name="obs.flight",
            site=LockSite(_FLIGHT, "FlightRecorder", "_lock"),
            kind="Lock",
            note="ring append/snapshot. record_signal_safe skips it by "
            "design (signal context must never block on a lock) and "
            "skips the dropped counter rather than racing it. dump() "
            "snapshots under the lock and writes the file OUTSIDE it — "
            "the disk-io ban keeps that true.",
            guards=(
                GuardedField(
                    _FLIGHT, "FlightRecorder", ("dropped", "_seq"),
                    note="recorders are shared across scheduler, "
                    "watchdog, and signal-adjacent paths; "
                    "record_signal_safe deliberately skips dropped",
                ),
            ),
            guard_exempt=("__init__", "record_signal_safe"),
            bans=("disk-io", "device-sync"),
        ),
        LockDecl(
            name="obs.flight.default",
            site=LockSite(_FLIGHT, "", "_default_lock"),
            kind="Lock",
            note="guards swaps of the module-default recorder in "
            "configure() — a resize replaces the instance, and two "
            "configuring threads must not interleave the swap.",
            guards=(
                GuardedField(_FLIGHT, "", ("_default",)),
            ),
            bans=("device-sync",),
        ),
        # -- resilience -------------------------------------------------------
        LockDecl(
            name="watchdog.lock",
            site=LockSite(_WATCHDOG, "Watchdog", "_lock"),
            kind="Lock",
            note="heartbeat bookkeeping only; the stall DIAGNOSIS and "
            "every callback/stderr dump run after release (a callback "
            "that beat() the watchdog from another thread would "
            "otherwise deadlock). Strict scope enforces that.",
            guards=(
                GuardedField(
                    _WATCHDOG, "Watchdog",
                    ("_last", "_beats", "_tripped", "_trip_at",
                     "trip_attempt", "_armed", "_label"),
                    note="the monitor thread and every beating owner "
                    "thread race on the heartbeat window",
                ),
            ),
            bans=("sleep", "disk-io", "device-sync"),
            strict_scope=True,
        ),
        LockDecl(
            name="breaker.lock",
            site=LockSite(_BREAKER, "CircuitBreaker", "_lock"),
            kind="Lock",
            note="the circuit breaker's state machine (ISSUE 17): "
            "state/window/probe bookkeeping only. This lock sits on "
            "EVERY store syscall's fast path (blocked() per _io_* "
            "helper) and on the scheduler's per-boundary outage check, "
            "so its held scope is one branch and a clock read — "
            "transition observers (flight ring, metrics, the health "
            "latch) run AFTER release via _notify, and store I/O "
            "obviously never runs under the gate that exists to avoid "
            "it. Strict scope enforces all of that.",
            guards=(
                GuardedField(
                    _BREAKER, "CircuitBreaker",
                    ("_state", "_consec", "_trips", "_probe_at",
                     "_opened_at", "_open_count", "_last_reason"),
                    note="the scheduler thread, submit threads (prefix "
                    "lookups), and scrape threads (snapshot) all read/"
                    "write breaker state",
                ),
            ),
            bans=("wire", "sleep", "disk-io", "subprocess", "device-sync"),
            strict_scope=True,
            # the jittered dwell draws from the breaker's own seeded rng
            # inside _open_locked: O(1) host arithmetic, and drawing
            # under the lock keeps the deterministic jitter sequence
            # well-defined when concurrent operations race to trip
            allow_calls=("random",),
        ),
        LockDecl(
            name="inject.plan",
            site=LockSite(_INJECT, "FaultPlan", "_lock"),
            kind="Lock",
            note="fault matching/consumption only; delivery observers "
            "and the fault ACTION itself run after release (an observer "
            "— the flight recorder — takes its own locks and may write "
            "files). Strict scope enforces that.",
            bans=("wire", "sleep", "disk-io", "device-sync"),
            strict_scope=True,
        ),
    ]
}


# -- the partial acquisition order ---------------------------------------------
#
# (outer, inner): `outer` may be held while acquiring `inner`; acquiring
# `outer` while `inner` is held is a `lock-order-inversion` finding. The
# auditor takes the transitive closure. Pairs not listed are UNORDERED —
# holding both in either order is an inversion against nothing, but a
# new nesting should be declared here when it becomes load-bearing.

ORDER: Tuple[Tuple[str, str], ...] = (
    # serve()'s drain path transitions health (stats lock) while holding
    # the admission lock; submit()'s _bump does the same for counters
    ("server.admission", "server.stats"),
    # Server.snapshot() calls slo.state() while holding the stats lock —
    # the ONE place the slo lock nests, and it nests inside (PR 10)
    ("server.stats", "obs.slo"),
    # flight.record from stats-held telemetry blocks is legal; a flight
    # callback taking the stats lock back is not
    ("server.stats", "obs.flight"),
    # the scheduler runs engine entry points (exec guard) and then
    # records under stats; a metrics path must never re-enter the engine
    ("engine.exec", "server.stats"),
    # the router lock is the fleet's outermost: replica-internal locks
    # (inflight gauges) may be read below it, never above it
    ("router.lock", "replica.state"),
    ("router.lock", "replica.local"),
    # a metrics scrape evaluates the breaker_state gauge_fn (which takes
    # the breaker lock to read .state) while holding the registry lock;
    # the reverse never happens — breaker observers run after release
    # and the strict scope bans foreign calls under the breaker lock
    ("server.stats", "breaker.lock"),
)


def obs_lock_attrs() -> FrozenSet[str]:
    """Attribute names of every lock declared in an ``orion_tpu/obs/``
    module (aliases included). The single source of truth for the
    `unbounded-wait` rule's widened obs scope: a bare ``.acquire()`` on
    one of THESE names in obs code is a scrape-liveness hazard; a
    receiver that is not a declared obs lock is not in the widened set
    (and, if it is a lock at all, `undeclared-lock` already flags it)."""
    out = set()
    for decl in LOCKS.values():
        for site in (decl.site, *decl.aliases):
            if site.module.startswith("orion_tpu/obs/"):
                out.add(site.attr)
    return frozenset(out)


def _validate() -> None:
    names = set(LOCKS)
    for outer, inner in ORDER:
        assert outer in names and inner in names, (outer, inner)
        assert outer != inner, outer
    for decl in LOCKS.values():
        for cat in decl.bans:
            assert cat in BAN_CATEGORIES, (decl.name, cat)
        assert decl.kind in ("Lock", "RLock"), decl.name
    # the declared order must be acyclic (it feeds a transitive closure)
    succ: Dict[str, set] = {}
    for outer, inner in ORDER:
        succ.setdefault(outer, set()).add(inner)
    seen: Dict[str, int] = {}

    def walk(n: str, stack: Tuple[str, ...]) -> None:
        assert n not in stack, f"ORDER cycle through {n}"
        if seen.get(n):
            return
        seen[n] = 1
        for m in succ.get(n, ()):
            walk(m, stack + (n,))

    for n in list(succ):
        walk(n, ())


_validate()
