"""Supervised replica handles: one ``serving.Server`` behind one wire.

A *replica* is a whole serving stack — SlotEngine, health machine, durable
sessions, SIGTERM drain — addressed through a tiny uniform interface
(:class:`ReplicaHandle`): ``submit`` a request, poll ``status`` (the
server's atomic health+occupancy snapshot), ``drain`` it gracefully,
``kill`` it dead, ``join`` its exit. The router and supervisor speak only
this interface, so the same fleet logic runs over both transports:

- :class:`ProcessReplica` — the production shape: the server runs in a
  REAL child OS process (own interpreter, own device client, own crash
  domain) started as ``python -m orion_tpu.fleet._child``. The parent
  talks to it over a line-delimited JSON control channel on the child's
  stdin/stdout: ops down (``status``/``submit``/``shutdown``), replies
  and asynchronous ``result`` events back up. SIGTERM to the child is the
  drain (the server's PreemptionGuard suspends resident sessions to the
  shared store and exits 0); SIGKILL is the crash the session store's
  generation commit protects against. EOF on stdin (parent died) drains
  too — a fleet never leaks orphan decoders.
- :class:`LocalReplica` — the same server driven by an in-process thread
  behind the same interface: the quick-tier test and ``--local`` debug
  transport. ``drain()`` flips a stop flag the serve loop treats exactly
  like SIGTERM; ``kill()`` makes the loop raise at its next boundary
  check — the abrupt-death model (no suspension, pendings fail, the last
  committed session generation on disk stays the conversation's truth).

Every wait on the control path carries a timeout (the ``unbounded-wait``
lint rule covers this package: a dead child must surface as a missed
heartbeat, never as a parent thread parked forever on a pipe).

Bitwise note: replicas build their params from the same
``PRNGKey(init_seed)`` (or the same checkpoint), and the decode path is
deterministic per request seed — so WHICH replica serves a request never
changes its tokens, and a conversation suspended on one replica resumes
bitwise on another (tests/test_fleet.py pins both).
"""

from __future__ import annotations

import dataclasses
import json
import os
import queue
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from orion_tpu.obs import flight
from orion_tpu.resilience.inject import fire
from orion_tpu.serving.session import DecodeRequest, DecodeResult

# how long a parent waits for a submit's admission ack before declaring
# the control channel dead (results themselves arrive asynchronously)
ACK_TIMEOUT_S = 30.0


class ReplicaGone(RuntimeError):
    """The replica's process/loop is dead or its control channel broke;
    the caller (router) should re-dispatch elsewhere and let the
    supervisor respawn."""


@dataclasses.dataclass(frozen=True)
class ReplicaSpec:
    """Everything a child process needs to become a replica, as one
    JSON-able value: the model (config name + field overrides + either a
    seeded random init or a checkpoint) and the ServeConfig knobs. Every
    replica of a fleet gets the SAME spec — identical params are what
    make dispatch placement invisible in the tokens. Quantized serving
    and the prefix cache ride the ``serve`` dict (``qmode``,
    ``prefix_dir``, ``params_id`` — every child quantizes the same fp32
    params the same deterministic way, and a shared ``prefix_dir`` means
    a prefix published by one replica admits O(suffix) on all of them).

    ``faults``: chaos-only — fault-plan entries armed INSIDE the child
    (e.g. ``[{"kind": "poison_decode_state_at", "args": [1, -1]}]``), so
    a test can poison one replica of a live fleet without the plan
    leaking into its siblings or the parent.

    ``compute_cpus``: pin the replica's XLA CPU compute pool to these
    cores (None = backend default: a pool spanning every advertised
    CPU). With N replicas on one box the default means N pools × ncpu
    threads fighting for ncpu cores — ONE replica silently eats the
    whole machine and replication measures as noise. One distinct core
    per replica is the production deployment shape, and what makes
    replicas=2 real process parallelism on one box (see
    :func:`pin_compute_pool`).

    ``tp``: the replica's device-mesh FOOTPRINT (ISSUE 14) — 0/1 serves
    unsharded, N shards the batched decode over an N-device tp mesh
    (``ServeConfig.tp``). A fleet may mix footprints behind one router:
    tokens are pinned bitwise across footprints and the session store
    holds the logical (footprint-free) carry row, so a conversation
    suspended on a tp=2 replica resumes on a tp=4 or unsharded sibling
    as a host-side reshape. A CPU child provisions
    ``xla_force_host_platform_device_count=tp`` for itself before its
    backend initializes (``_child_main``)."""

    config: str = "tiny"
    overrides: Optional[Dict[str, Any]] = None  # ModelConfig field -> value
    init_seed: int = 0
    ckpt_dir: Optional[str] = None
    serve: Optional[Dict[str, Any]] = None  # ServeConfig kwargs
    faults: Optional[List[Dict[str, Any]]] = None
    compute_cpus: Optional[List[int]] = None
    tp: int = 0  # device-mesh footprint (0/1 = unsharded)
    # jax.config.update entries applied in the child before building the
    # model — a replica must decode under the SAME numerics flags as its
    # siblings (and as any in-parent reference), or "which replica served
    # it" becomes visible in sampled tokens (e.g. threefry partitioning)
    jax_flags: Optional[Dict[str, Any]] = None

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @staticmethod
    def from_json(text: str) -> "ReplicaSpec":
        return ReplicaSpec(**json.loads(text))


def pin_compute_pool(cpus: List[int]) -> None:
    """Latch the (not-yet-created) XLA CPU client's compute pool onto
    ``cpus``: the client sizes its Eigen pool from the schedulable-CPU
    count at creation, and the pool threads inherit the creating
    thread's affinity — so narrow this thread's affinity, force the
    backend up, and restore. After the restore the pool's compute
    threads stay on ``cpus`` while the Python/dispatch thread schedules
    freely. Must run before anything touches a jax device; no-op where
    affinity is unsupported or the request isn't a real narrowing."""
    if not hasattr(os, "sched_getaffinity"):
        return
    import jax

    allowed = sorted(os.sched_getaffinity(0))
    want = {c for c in cpus if c in allowed}
    if not want or len(want) >= len(allowed):
        return
    os.sched_setaffinity(0, want)
    try:
        jax.devices()  # client creation reads the narrowed affinity
    finally:
        os.sched_setaffinity(0, set(allowed))


def build_model(spec: ReplicaSpec):
    """(model, params, params_id) for a replica: the named config with
    field overrides applied, params from the checkpoint when given, else
    a deterministic seeded init (identical across every process that
    runs this function with the same spec).

    ``params_id`` is the weights' provenance for prefix-cache addressing
    — config + overrides + (checkpoint dir AND the step a default-latest
    load actually RESOLVED to, or the init seed). The resolved step must
    ride the id: a fleet restarted after training advanced loads newer
    weights, and hitting the previous step's prefix snapshots would
    silently serve stale state (serving/prefix_store.py)."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM
    from orion_tpu.serving.prefix_store import overrides_fingerprint

    cfg = get_config(spec.config)
    if spec.overrides:
        from orion_tpu.utils.config import apply_overrides

        cfg = apply_overrides(cfg, {
            # JSON has no tuples; ModelConfig fields are hashable statics
            k: (tuple(v) if isinstance(v, list) else v)
            for k, v in spec.overrides.items()
        })
    ov = overrides_fingerprint(spec.overrides)
    if spec.ckpt_dir:
        from orion_tpu.generate import (
            adapt_config_to_params,
            load_params,
            unstack_if_pipeline,
        )

        params, step = load_params(spec.ckpt_dir)
        cfg = adapt_config_to_params(cfg, params)
        model = TransformerLM(cfg)
        params, _ = unstack_if_pipeline(model, params)
        pid = f"{spec.config}:ov={ov}:ckpt={spec.ckpt_dir}:step={step}"
        return model, params, pid
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(spec.init_seed), jnp.zeros((1, 8), jnp.int32)
    )
    return model, params, f"{spec.config}:ov={ov}:seed={spec.init_seed}"


def replica_footprint(spec: ReplicaSpec) -> int:
    """The replica's EFFECTIVE device-mesh footprint: ``spec.tp`` when
    set, else a ``tp`` riding in the serve dict (``ServeConfig.tp`` is
    public — a footprint expressed only there must still provision its
    devices in ``_child_main``, or the child's Server dies at
    construction and the supervisor respawns into the same crash)."""
    if spec.tp and spec.tp > 1:
        return int(spec.tp)
    return int((spec.serve or {}).get("tp", 0) or 0)


def serve_config(spec: ReplicaSpec, params_id: Optional[str] = None):
    """ServeConfig from the spec; ``params_id`` (from
    :func:`build_model`) fills the prefix-addressing identity unless the
    spec pinned one explicitly, and the spec's mesh footprint
    (:func:`replica_footprint` — ``spec.tp`` winning over the serve
    dict) is stamped onto the config: the footprint is a placement
    property of the REPLICA, not a serving knob two sources may
    disagree on."""
    from orion_tpu.serving.server import ServeConfig

    cfg = ServeConfig(**(spec.serve or {}))
    if params_id and not cfg.params_id:
        cfg = dataclasses.replace(cfg, params_id=params_id)
    fp = replica_footprint(spec)
    if fp > 1:
        cfg = dataclasses.replace(cfg, tp=fp)
    return cfg


# -- wire helpers -------------------------------------------------------------


_ERROR_TYPES: Dict[str, type] = {}


def _error_types() -> Dict[str, type]:
    """Exception classes a result event may name; resolved lazily so the
    wire layer doesn't import the serving stack at module load."""
    if not _ERROR_TYPES:
        from orion_tpu.serving.server import OverloadError, RejectedError
        from orion_tpu.serving.session_store import SessionIntegrityError

        _ERROR_TYPES.update({
            "OverloadError": OverloadError,
            "RejectedError": RejectedError,
            "SessionIntegrityError": SessionIntegrityError,
            "ValueError": ValueError,
            "TimeoutError": TimeoutError,
            # parent-side synthetic reply from _fail_outstanding (a child
            # never sends this): must rebuild as ReplicaGone or the
            # router's failover except-clause won't catch it
            "ReplicaGone": ReplicaGone,
        })
    return _ERROR_TYPES


def _rebuild_error(type_name: str, message: str) -> Exception:
    cls = _error_types().get(type_name)
    if cls is not None:
        return cls(message)
    return RuntimeError(f"{type_name}: {message}")


def _request_to_wire(request: DecodeRequest) -> Dict[str, Any]:
    prompt = np.asarray(request.prompt, np.int32)
    if prompt.ndim == 1:
        prompt = prompt[None]
    return {
        "prompt": prompt.tolist(),
        "max_new_tokens": int(request.max_new_tokens),
        "sample": dataclasses.asdict(request.sample),
        "seed": int(request.seed),
        "deadline_ms": float(request.deadline_ms),
        "session_id": request.session_id,
        "prefix_len": int(request.prefix_len),
    }


def _request_from_wire(msg: Dict[str, Any]) -> DecodeRequest:
    from orion_tpu.generate import SampleConfig

    return DecodeRequest(
        prompt=np.asarray(msg["prompt"], np.int32),
        max_new_tokens=int(msg["max_new_tokens"]),
        sample=SampleConfig(**msg["sample"]),
        seed=int(msg.get("seed", 0)),
        deadline_ms=float(msg.get("deadline_ms", 0.0)),
        session_id=msg.get("session_id"),
        prefix_len=int(msg.get("prefix_len", 0)),
    )


def _result_to_wire(result: DecodeResult) -> Dict[str, Any]:
    return {
        "status": result.status,
        "tokens": np.asarray(result.tokens).tolist(),
        "new_tokens": int(result.new_tokens),
        "chunks": int(result.chunks),
        "rewinds": int(result.rewinds),
        "reprefills": int(result.reprefills),
    }


def _result_from_wire(msg: Dict[str, Any]) -> DecodeResult:
    return DecodeResult(
        tokens=np.asarray(msg["tokens"], np.int32).reshape(
            len(msg["tokens"]), -1
        ),
        status=msg["status"],
        new_tokens=int(msg["new_tokens"]),
        chunks=int(msg["chunks"]),
        rewinds=int(msg.get("rewinds", 0)),
        reprefills=int(msg.get("reprefills", 0)),
    )


@dataclasses.dataclass
class FleetPending:
    """The parent-side handle for one request dispatched to a process
    replica — same contract as the server's Pending: ``done`` fires
    exactly once with either ``result`` or ``error`` filled."""

    session_id: Optional[str]
    done: threading.Event
    submitted_at: float = 0.0
    done_at: float = 0.0
    result: Optional[DecodeResult] = None
    error: Optional[Exception] = None
    replica: str = ""
    # invoked exactly once right after ``done`` fires (result OR error) —
    # the router closes its root ``turn`` trace span here; host-only,
    # exceptions swallowed by the caller
    on_done: Optional[Callable[["FleetPending"], None]] = None

    def wait(self, timeout: Optional[float] = None) -> Optional[DecodeResult]:
        if not self.done.wait(timeout=timeout):
            return None
        if self.error is not None:
            raise self.error
        return self.result

    def _release(self) -> None:
        self.done.set()
        cb = self.on_done
        if cb is not None:
            try:
                cb(self)
            except Exception:
                pass  # telemetry must never break completion


# -- the uniform handle interface ---------------------------------------------


class ReplicaHandle:
    """What the router and supervisor program against. Subclasses fill in
    the transport; the shared part is routing metadata."""

    name: str = "replica"

    @property
    def alive(self) -> bool:  # pragma: no cover - abstract
        raise NotImplementedError

    @property
    def inflight(self) -> int:  # pragma: no cover - abstract
        raise NotImplementedError

    def health_state(self) -> str:  # pragma: no cover - abstract
        raise NotImplementedError

    def wait_ready(self, timeout: float) -> None:  # pragma: no cover
        raise NotImplementedError

    def submit(self, request: DecodeRequest):  # pragma: no cover
        raise NotImplementedError

    def status(self, timeout: float = 2.0) -> Optional[dict]:  # pragma: no cover
        raise NotImplementedError

    def drain(self) -> None:  # pragma: no cover - abstract
        raise NotImplementedError

    def kill(self) -> None:  # pragma: no cover - abstract
        raise NotImplementedError

    def join(self, timeout: float) -> bool:  # pragma: no cover - abstract
        raise NotImplementedError

    @property
    def routable(self) -> bool:
        """May the router place NEW work here? DEGRADED stays routable
        (the router deprioritizes it; shedding a limping-but-correct
        replica outright is the supervisor's call) — DRAINING/DEAD never.
        """
        return self.alive and self.health_state() in (
            "starting", "serving", "degraded"
        )

    def slo_penalty(self):
        """Latency-aware routing tie-break, applied AFTER (health rank,
        inflight): ``(fast-burn firing?, windowed p99 ms)`` from the
        replica's last status snapshot (the ``slo`` section every
        ``Server.snapshot()`` carries since ISSUE 10). Deliberately
        stale-tolerant — the supervisor heartbeat refreshes
        ``last_status`` once per tick, and a balancer acting on a
        second-old p99 still beats one acting on none. A replica with no
        SLO data yet sorts neutral ``(0, 0.0)``: new capacity must not
        be penalized for having no history."""
        status = getattr(self, "last_status", None)
        slo = (status or {}).get("slo") or {}
        firing = 1 if slo.get("firing_fast") else 0
        p99 = slo.get("p99_ms")
        return (firing, p99 if p99 is not None else 0.0)


# -- process replica: the real thing ------------------------------------------


class ProcessReplica(ReplicaHandle):
    """A serving.Server in a child OS process behind the line-JSON
    control channel. ``start()`` spawns (fire point for the
    ``fleet.replica_spawn`` chaos site lives in the supervisor's retry
    wrapper); ``wait_ready`` blocks until the child reports its model
    built and its serve loop entered."""

    def __init__(
        self,
        spec: ReplicaSpec,
        name: str = "replica-0",
        clock: Callable[[], float] = time.monotonic,
        ack_timeout: float = ACK_TIMEOUT_S,
    ):
        self.spec = spec
        self.name = name
        self._clock = clock
        self._ack_timeout = ack_timeout
        self._proc: Optional[subprocess.Popen] = None
        self._send_lock = threading.Lock()
        self._state_lock = threading.Lock()
        self._next_id = 0
        self._pendings: Dict[int, FleetPending] = {}
        self._replies: Dict[int, "queue.Queue[dict]"] = {}
        self._ready = threading.Event()
        self._eof = False
        self._inflight = 0
        self.last_status: Optional[dict] = None
        self.last_heartbeat: float = 0.0
        self.exit_rc: Optional[int] = None
        self.fatal: Optional[str] = None  # the child's own last words

    # -- lifecycle ------------------------------------------------------------

    def start(self) -> "ProcessReplica":
        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        self._proc = subprocess.Popen(
            [sys.executable, "-m", "orion_tpu.fleet._child"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=None,
            text=True, cwd=repo_root, env=env,
        )
        try:
            self._send_raw(self.spec.to_json())
        except Exception:
            # spec never reached the child (broken pipe, injected
            # fleet.control_io fault): reap it here or the spawn-retry
            # loop would leak one live process per attempt
            self._proc.kill()
            self._proc.wait(timeout=10.0)
            raise
        t = threading.Thread(
            target=self._read_loop, name=f"{self.name}-reader", daemon=True
        )
        t.start()
        return self

    def wait_ready(self, timeout: float = 180.0) -> None:
        if not self._ready.wait(timeout=timeout):
            self.kill()
            raise ReplicaGone(
                f"{self.name}: child not ready within {timeout}s"
            )
        if not self.alive:
            raise ReplicaGone(
                f"{self.name}: child died during startup"
                + (f" — {self.fatal}" if self.fatal else "")
            )

    @property
    def pid(self) -> Optional[int]:
        return self._proc.pid if self._proc is not None else None

    @property
    def alive(self) -> bool:
        return (
            self._proc is not None
            and self._proc.poll() is None
            and not self._eof
        )

    @property
    def inflight(self) -> int:
        with self._state_lock:
            return self._inflight

    def health_state(self) -> str:
        if not self.alive:
            return "dead"
        if self.last_status is not None:
            return self.last_status.get("state", "serving")
        return "serving" if self._ready.is_set() else "starting"

    # -- control channel ------------------------------------------------------

    def _send_raw(self, line: str) -> None:
        fire("fleet.control_io")
        with self._send_lock:
            assert self._proc is not None and self._proc.stdin is not None
            self._proc.stdin.write(line + "\n")
            self._proc.stdin.flush()

    def _send(self, obj: dict) -> None:
        # black-box every control-channel op (parent side): after a chaos
        # event the ring shows the op sequence the child saw last
        flight.record("control_op", replica=self.name, op=obj.get("op"))
        try:
            self._send_raw(json.dumps(obj))
        except (OSError, ValueError, BrokenPipeError, AssertionError) as e:
            flight.record("control_io_error", replica=self.name,
                          error=type(e).__name__)
            raise ReplicaGone(
                f"{self.name}: control channel write failed ({e})"
            ) from e

    def _read_loop(self) -> None:
        proc = self._proc
        assert proc is not None and proc.stdout is not None
        for line in proc.stdout:
            line = line.strip()
            if not line:
                continue
            try:
                msg = json.loads(line)
            except ValueError:
                continue  # stray non-protocol output: ignore, never die
            self._dispatch(msg)
        # EOF: the child exited (clean drain or crash)
        self._eof = True
        self.exit_rc = proc.poll()
        flight.record("replica_exit", replica=self.name, rc=self.exit_rc)
        if self.exit_rc not in (0, None):
            # unhandled child exit: a flight-recorder dump trigger — the
            # parent's ring holds the control ops that preceded the death
            flight.recorder().dump(f"child-exit-{self.name}")
        self._fail_outstanding(
            ReplicaGone(f"{self.name}: replica exited (rc={self.exit_rc})")
        )
        self._ready.set()  # unblock any wait_ready (alive check fails it)

    def _dispatch(self, msg: dict) -> None:
        if "reply_to" in msg:
            q = self._replies.pop(int(msg["reply_to"]), None)
            if q is not None:
                q.put(msg)
            return
        event = msg.get("event")
        if event == "ready":
            self._ready.set()
        elif event == "fatal":
            self.fatal = str(msg.get("message", ""))
        elif event == "result":
            with self._state_lock:
                pending = self._pendings.pop(int(msg["id"]), None)
                if pending is not None:
                    self._inflight -= 1
            if pending is None:
                return
            if "error" in msg:
                pending.error = _rebuild_error(
                    msg["error"], msg.get("message", "")
                )
            else:
                pending.result = _result_from_wire(msg)
            pending.done_at = self._clock()
            pending.replica = self.name
            pending._release()

    def _fail_outstanding(self, err: Exception) -> None:
        with self._state_lock:
            pendings = list(self._pendings.values())
            self._pendings.clear()
            self._inflight = 0
            replies = list(self._replies.values())
            self._replies.clear()
        for p in pendings:
            if not p.done.is_set():
                p.error = err
                p.done_at = self._clock()
                p._release()
        for q in replies:
            q.put({"ok": False, "error": "ReplicaGone", "message": str(err)})

    def _rpc(self, obj: dict, timeout: float) -> Optional[dict]:
        """Send one op and wait for its reply (bounded); None = timed
        out — the caller's missed-heartbeat signal."""
        with self._state_lock:
            self._next_id += 1
            rid = self._next_id
            q: "queue.Queue[dict]" = queue.Queue()
            self._replies[rid] = q
        obj = dict(obj, id=rid)
        try:
            self._send(obj)
        except ReplicaGone:
            self._replies.pop(rid, None)
            raise
        try:
            return q.get(timeout=timeout)
        except queue.Empty:
            self._replies.pop(rid, None)
            return None

    # -- the handle interface -------------------------------------------------

    def submit(self, request: DecodeRequest) -> FleetPending:
        if not self.alive:
            raise ReplicaGone(f"{self.name}: not alive")
        pending = FleetPending(
            session_id=request.session_id, done=threading.Event(),
            submitted_at=self._clock(), replica=self.name,
        )
        with self._state_lock:
            self._next_id += 1
            rid = self._next_id
            self._pendings[rid] = pending
            self._inflight += 1
            q: "queue.Queue[dict]" = queue.Queue()
            self._replies[rid] = q
        msg = dict(_request_to_wire(request), op="submit", id=rid)
        try:
            self._send(msg)
            reply = q.get(timeout=self._ack_timeout)
        except (ReplicaGone, queue.Empty) as e:
            if isinstance(e, queue.Empty) and request.session_id is not None:
                # a SESSION submit was written but never acknowledged:
                # it may still be sitting in the wedged child's stdin,
                # and the caller (router) will fail over and re-dispatch
                # — letting this child wake up later and execute the
                # orphaned copy would fork the conversation, so kill the
                # child to FENCE it (the supervisor respawns). A
                # sessionless duplicate is harmless (its late result is
                # dropped — the pending was popped) and doesn't justify
                # killing a replica full of healthy work; a ReplicaGone
                # send failure needs no fence either: the pipe's read
                # end is gone, nothing will execute the message.
                self.kill()
            with self._state_lock:
                if self._pendings.pop(rid, None) is not None:
                    self._inflight -= 1
            self._replies.pop(rid, None)
            raise ReplicaGone(
                f"{self.name}: submit not acknowledged ({type(e).__name__})"
            ) from e
        if not reply.get("ok"):
            with self._state_lock:
                if self._pendings.pop(rid, None) is not None:
                    self._inflight -= 1
            raise _rebuild_error(
                reply.get("error", "RuntimeError"), reply.get("message", "")
            )
        return pending

    def status(self, timeout: float = 2.0) -> Optional[dict]:
        if not self.alive:
            return None
        try:
            reply = self._rpc({"op": "status"}, timeout=timeout)
        except ReplicaGone:
            return None
        if reply is None or not reply.get("ok"):
            return None
        self.last_status = reply["status"]
        self.last_heartbeat = self._clock()
        return self.last_status

    def drain(self) -> None:
        """Graceful: real SIGTERM to the child — the server's
        PreemptionGuard turns it into DRAINING (sessions suspend to the
        shared store, sessionless work completes, exit 0)."""
        if self._proc is not None and self._proc.poll() is None:
            try:
                os.kill(self._proc.pid, signal.SIGTERM)
            except OSError:
                pass

    def kill(self) -> None:
        if self._proc is not None and self._proc.poll() is None:
            self._proc.kill()

    def join(self, timeout: float = 10.0) -> bool:
        if self._proc is None:
            return True
        try:
            self.exit_rc = self._proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            return False
        return True


# -- local replica: same interface, in-process --------------------------------


class ReplicaKilled(RuntimeError):
    """Raised inside a LocalReplica's serve loop by ``kill()`` — models a
    SIGKILL'd process: no drain, no suspension, pendings fail with their
    partial tokens, on-disk session generations stay as they were."""


class _LoopGuard:
    """Duck-typed PreemptionGuard for the thread transport: ``drain``
    flips ``should_stop`` (the serve loop's SIGTERM path), ``kill`` makes
    the NEXT ``should_stop`` read raise once (the loop dies mid-flight,
    its finally-block failure path runs, and the thread exits)."""

    signum = signal.SIGTERM

    def __init__(self):
        self._stop = False
        self._kill = False
        self._raised = False

    def request_stop(self) -> None:
        self._stop = True

    def request_kill(self) -> None:
        self._kill = True
        self._stop = True

    @property
    def should_stop(self) -> bool:
        if self._kill and not self._raised:
            self._raised = True
            raise ReplicaKilled("replica killed")
        return self._stop


class LocalReplica(ReplicaHandle):
    """The server on a thread behind the ReplicaHandle interface — the
    quick-tier fleet transport (and ``--local`` CLI mode). Shares the
    process's model/params and jit caches, so a fleet of these costs no
    extra compiles."""

    def __init__(self, model, params, cfg, name: str = "local-0",
                 clock: Callable[[], float] = time.monotonic):
        from orion_tpu.serving.server import Server

        self.name = name
        self._clock = clock
        self.server = Server(model, params, cfg, clock=clock)
        self._guard = _LoopGuard()
        self._thread: Optional[threading.Thread] = None
        self._outstanding: List[Any] = []
        self._lock = threading.Lock()
        self.crashed = False
        self.last_heartbeat: float = 0.0
        self.last_status: Optional[dict] = None

    def start(self) -> "LocalReplica":
        self._thread = threading.Thread(
            target=self._run, name=f"{self.name}-serve", daemon=True
        )
        self._thread.start()
        return self

    def _run(self) -> None:
        try:
            self.server.serve(guard=self._guard)
        except ReplicaKilled:
            self.crashed = True
        except Exception:
            self.crashed = True
            raise

    def wait_ready(self, timeout: float = 60.0) -> None:
        deadline = self._clock() + timeout
        while self._clock() < deadline:
            if self._thread is not None and self._thread.is_alive():
                return
            time.sleep(0.01)
        raise ReplicaGone(f"{self.name}: serve thread did not start")

    @property
    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    @property
    def inflight(self) -> int:
        with self._lock:
            self._outstanding = [
                p for p in self._outstanding if not p.done.is_set()
            ]
            return len(self._outstanding)

    def health_state(self) -> str:
        if not self.alive:
            return "dead"
        return self.server.health.state.value

    def submit(self, request: DecodeRequest):
        if not self.alive:
            raise ReplicaGone(f"{self.name}: not alive")
        pending = self.server.submit(request)
        with self._lock:
            self._outstanding.append(pending)
        return pending

    def status(self, timeout: float = 2.0) -> Optional[dict]:
        if not self.alive:
            return None
        snap = self.server.snapshot()
        self.last_heartbeat = self._clock()
        # same contract as ProcessReplica: the freshest snapshot hangs
        # off the handle, where the router's slo_penalty tie-break and
        # health_state read it without another round-trip
        self.last_status = snap
        return snap

    def drain(self) -> None:
        self._guard.request_stop()

    def kill(self) -> None:
        self._guard.request_kill()

    def join(self, timeout: float = 10.0) -> bool:
        if self._thread is None:
            return True
        self._thread.join(timeout=timeout)
        return not self._thread.is_alive()


# -- the child process --------------------------------------------------------


def _child_main() -> int:
    """``python -m orion_tpu.fleet.replica``: read the ReplicaSpec as the
    first stdin line, build the server, report ready, then serve until a
    SIGTERM / ``shutdown`` op / stdin EOF drains the loop. Control ops
    arrive as subsequent stdin lines; replies, ``result`` events, and the
    final ``exit`` event go to stdout (one JSON object per line — stdout
    is the protocol, all diagnostics go to stderr)."""
    import jax

    from orion_tpu.utils.cache import enable_compile_cache

    enable_compile_cache()

    spec = ReplicaSpec.from_json(sys.stdin.readline())
    for flag, value in (spec.jax_flags or {}).items():
        jax.config.update(flag, value)
    # the effective footprint (spec.tp OR a tp riding the serve dict)
    # needs that many devices in THIS process — provision before anything
    # touches a device (nothing above did), or the child's Server dies at
    # serving_mesh construction and the supervisor respawns into the
    # same crash
    from orion_tpu.utils.devices import ensure_virtual_devices

    ensure_virtual_devices(replica_footprint(spec))
    if spec.compute_cpus:
        pin_compute_pool(spec.compute_cpus)

    from orion_tpu.resilience import inject
    from orion_tpu.resilience.preempt import PreemptionGuard
    from orion_tpu.serving.server import Server

    out_lock = threading.Lock()

    def emit(obj: dict) -> None:
        with out_lock:
            sys.stdout.write(json.dumps(obj) + "\n")
            sys.stdout.flush()

    # an accelerator belongs to ONE process: a second replica on the same
    # chip cannot get it. Say so on the protocol before dying — the parent
    # cannot ask jax how many chips there are without taking one, so this
    # is the only place the cause is known; it fails the spawn with it
    # instead of respawning into the same wall
    try:
        jax.devices()
    except RuntimeError as e:
        emit({"event": "fatal",
              "message": f"no device for this replica: {e}"})
        return 3

    plan = None
    if spec.faults:
        plan = inject.FaultPlan()
        for entry in spec.faults:
            getattr(plan, entry["kind"])(*entry.get("args", []))

    model, params, params_id = build_model(spec)
    server = Server(model, params, serve_config(spec, params_id=params_id))
    del params  # the server holds its own serving tree; free the loaded one
    watchers: List[threading.Thread] = []

    def watch(rid: int, pending) -> None:
        # bounded waits only (unbounded-wait rule): the loop re-arms
        # until the pending resolves — serve()'s finally guarantees it
        # always does, even on a crashing loop
        while not pending.done.wait(timeout=1.0):
            pass
        if pending.error is not None:
            emit({"event": "result", "id": rid,
                  "error": type(pending.error).__name__,
                  "message": str(pending.error)})
        else:
            emit(dict(_result_to_wire(pending.result),
                      event="result", id=rid))

    with PreemptionGuard(grace=serve_config(spec).grace) as guard:

        def control() -> None:
            for line in sys.stdin:
                line = line.strip()
                if not line:
                    continue
                try:
                    msg = json.loads(line)
                except ValueError:
                    continue
                rid = int(msg.get("id", 0))
                op = msg.get("op")
                if op == "status":
                    emit({"reply_to": rid, "ok": True, "replica": True,
                          "status": server.snapshot()})
                elif op == "submit":
                    try:
                        pending = server.submit(_request_from_wire(msg))
                    except Exception as e:
                        emit({"reply_to": rid, "ok": False,
                              "error": type(e).__name__, "message": str(e)})
                        continue
                    t = threading.Thread(
                        target=watch, args=(rid, pending), daemon=True
                    )
                    watchers[:] = [w for w in watchers if w.is_alive()]
                    watchers.append(t)
                    t.start()
                    emit({"reply_to": rid, "ok": True})
                elif op == "shutdown":
                    emit({"reply_to": rid, "ok": True})
                    guard.request_stop()
                else:
                    emit({"reply_to": rid, "ok": False,
                          "error": "ValueError",
                          "message": f"unknown op {op!r}"})
            # parent hung up: drain, don't orphan
            guard.request_stop()

        threading.Thread(target=control, daemon=True).start()
        emit({"event": "ready", "pid": os.getpid()})
        rc = 1
        try:
            if plan is not None:
                with inject.inject(plan):
                    rc = server.serve(guard=guard)
            else:
                rc = server.serve(guard=guard)
        finally:
            server.close()
            # a drain resolves every pending (suspended / completed /
            # rejected) — give their watcher threads a bounded window to
            # EMIT those results before the process exit reaps them, or
            # the parent would see an exit with results missing
            for t in watchers:
                t.join(timeout=5.0)
    emit({"event": "exit", "rc": rc})
    return rc


if __name__ == "__main__":
    sys.exit(_child_main())
