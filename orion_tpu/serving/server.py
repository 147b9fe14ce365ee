"""Bounded-admission continuous-batching server over a SlotEngine.

The serving counterpart of the trainer's resilience stack (PR 2): the
same primitives — PreemptionGuard, Watchdog, retry, fault hooks — wired
around the decode path instead of the step loop. Since PR 5 the serve
loop is a SCHEDULER over the slot-multiplexed batched decode engine
(:class:`~orion_tpu.serving.batching.SlotEngine`): up to ``slots``
requests decode concurrently in one jitted scan, and admission, drain,
deadlines, and watchdog beats all happen at chunk boundaries.

- **admission** — a bounded queue (``max_inflight`` bounds the QUEUED
  backlog; up to ``slots`` more are resident in the engine); a full
  queue SHEDS the request with :class:`OverloadError` at submit time
  instead of growing an unbounded backlog whose tail latency is all
  deadline misses anyway. A draining/dead server REJECTS with
  :class:`RejectedError`. Queued requests move into free slots at every
  chunk boundary — a late arrival joins mid-stream at its own position
  without waiting for the batch to drain.
- **health** — the :class:`~orion_tpu.serving.health.HealthMachine`
  drives admission: SERVING/DEGRADED accept, DRAINING/DEAD reject.
  Requests that needed the degradation ladder (or a watchdog stall) move
  SERVING -> DEGRADED; a clean completion recovers to SERVING.
- **SIGTERM** — the PreemptionGuard installed around the serve loop maps
  the first signal to DRAINING at the next chunk boundary: in-flight
  slots AND already-admitted requests complete, new submits are
  rejected, the loop exits 0. A second signal kills, as everywhere else
  in the stack.
- **watchdog** — ``stall_timeout`` arms a heartbeat watchdog beaten at
  every chunk boundary; a stalled chunk (wedged DMA, deadlocked
  collective) degrades health and writes a diagnosis instead of hanging
  the replica silently.
- **request isolation** — a request the engine cannot multiplex (batch
  > 1, over-capacity prompt, mismatched SampleConfig) or whose slot
  exhausts the per-slot degradation ladder becomes an error/failed
  RESULT on its Pending; co-resident slots keep streaming and the
  process never dies for one request.
- **durable sessions** — with ``session_dir`` set, a request carrying a
  ``session_id`` becomes a conversation turn: its decode state is
  suspended at turn end as one O(1) snapshot (write-through to the
  integrity-manifested :class:`~orion_tpu.serving.session_store.SessionStore`,
  LRU-capped host cache in front, idle eviction at chunk boundaries),
  and a later turn resumes it — bitwise-identical to having kept the
  slot resident, across server restarts. SIGTERM drain SUSPENDS
  resident sessions instead of decoding their remaining tokens; a
  corrupt on-disk session fails only its own request.
"""

from __future__ import annotations

import contextlib
import dataclasses
import queue
import sys
import threading
import time
import uuid
import warnings
from collections import OrderedDict
from typing import Callable, Dict, Optional

import numpy as np

from orion_tpu.obs import cost as obs_cost
from orion_tpu.obs import slo as obs_slo
from orion_tpu.obs.flight import FlightRecorder
from orion_tpu.obs.http import ObsHTTPServer
from orion_tpu.obs.metrics import MetricsRegistry
from orion_tpu.obs.trace import (
    COMPILE_COUNTERS,
    NULL_SPAN,
    Tracer,
    compile_counts,
    on_compile,
    setup_record,
    setup_summary,
)
from orion_tpu.resilience.breaker import CircuitBreaker, StoreUnavailableError
from orion_tpu.resilience.inject import fire
from orion_tpu.resilience.preempt import PreemptionGuard
from orion_tpu.resilience.retry import RetryPolicy, call_with_retries
from orion_tpu.resilience.watchdog import Watchdog
from orion_tpu.serving.batching import PREFILL_CHUNK, SlotEngine, parse_buckets
from orion_tpu.serving.health import HTTP_STATUS, Health, HealthMachine
from orion_tpu.serving.session import DecodeRequest, DecodeResult
from orion_tpu.serving.session_store import SessionState, SessionStore

# the Server.stats contract (PR 4-8): these counter names, unlabelled,
# as one flat dict — now cells of the metrics registry instead of a
# hand-rolled dict, so they ride every exposition path for free
_STAT_KEYS = (
    "admitted", "shed", "rejected",
    "ok", "deadline", "failed",
    "rewinds", "reprefills", "stalls",
    "chunks", "slot_steps_active", "slot_steps_total",
    "suspended", "resumed", "session_saves",
)


# What the scheduler thread does in one served boundary, as span names:
# ``serve.boundary`` runs from the top of a serve-loop iteration that
# steps the engine to the top of the next; the others are its children,
# in this order, none overlapping (``serve.idle_wait`` lies outside any
# boundary: the queue poll of an idle engine). ``serve.probe`` is the one
# phase in which the host waits for the device; the time in all the
# others is time the device's next program is held back. Each is written
# by :meth:`Server._phase` to the Tracer ring (``cat="phase"``, every
# boundary) and, while ``arm_profile``'s capture runs, to the profiler's
# trace under the same name.
PHASES = (
    "serve.boundary", "serve.tick", "serve.admit", "serve.dispatch",
    "serve.probe", "serve.finish", "serve.complete", "serve.idle_wait",
)
# slot_steps_active split by what the slot did at the boundary (their sum
# IS slot_steps_active): consumed one of the boundary's prefill pieces /
# emitted tokens without consuming one / neither (resident mid-prompt
# and passed over — and the rare slot evicted by its deadline or the
# ladder); slot_steps_prefilling / chunks is pieces per boundary
_SLOT_CLASS_KEYS = (
    "slot_steps_prefilling", "slot_steps_decoding", "slot_steps_frozen",
)
# KV-cache rows at each boundary, summed over slots (``SlotEngine.kv_rows``):
# what the slots hold live / what they reserve / what a layer's decode
# attention streams a step (the reservation in the XLA form, the emitting
# slots' live KV blocks under ``ops.dispatch.cache_attention``'s kernel; all
# three stay 0 for a model without a cached layer); the slots that emitted
# tokens at the boundary, which is the rows the decode scan's state kernels
# stepped; and the times ONE ``linear`` layer wrote those rows' ``(S, z)``
# in it (``SlotEngine.state_writes_per_chunk`` each: 1 where the scan reads
# the state and one flush writes it, ``chunk`` where every step writes)
_KV_ROW_KEYS = (
    "kv_rows_live", "kv_rows_reserved", "kv_rows_read", "slot_steps_emitting",
    "state_row_writes",
)
# the emitting slots' live cache rows counted to the row, without the
# kernel's block rounding (``SlotEngine.kv_rows_attended``): the work a
# decode attention kernel cannot avoid, which its roofline share counts
_KV_ATTENDED_KEYS = ("kv_rows_attended",)
# a window layer's RING at each boundary, per ring layer
# (``SlotEngine.ring_rows``): rows the resident slots hold live (min(position,
# window) each) / rows they reserve (the window each) / the emitting slots'
# live ring rows, to the row, which the ring's decode attention cannot avoid
# (0 for a model without a ring; ``kv_rows_*`` count the caches that grow)
_RING_KEYS = ("ring_rows_live", "ring_rows_reserved", "ring_rows_attended")
# the engine's staging dispatches (``SlotEngine.staging_dispatches``) since
# the last boundary: admit_dispatches / chunks is device calls of admission
# a boundary, at most one while no boundary admits more than
# ``batching.STAGE_ROWS`` prompts
_ADMIT_KEYS = ("admit_dispatches",)
# the block-sparse layers' cache blocks at each boundary, per emitting slot
# and sparse layer (``SlotEngine.kv_blocks``): blocks the slot holds live /
# blocks its decode attention lists; and the emitting slots past / under
# ``sparse_dense_len`` (0 for a model without such a layer)
_KV_BLOCK_KEYS = (
    "kv_blocks_live", "kv_blocks_read", "sparse_steps", "dense_steps",
)
# the indexed layers' cache rows at each boundary, per emitting slot and
# indexed layer (``SlotEngine.kv_rows_listed``): rows the slot's decode
# attention lists / live rows its indexer scores to choose them; and the
# (query, key) pairs of the boundary's prompt pieces, per indexed layer
# (``SlotEngine.index_piece_pairs``): pairs the indexer scores / pairs the
# attention keeps (0 for a model without such a layer)
_KV_LIST_KEYS = (
    "kv_rows_listed", "index_rows_scored", "index_pairs_visible",
    "index_pairs_selected",
)
# the state-space layers' work at each boundary, summed over ``ssm`` layers
# (0 for a model without one): the row-steps their decode step ran (the
# slots that emitted x the scan's steps: a listed row steps at every one) and
# the real prompt rows their chunked scan consumed
_SSM_KEYS = ("ssm_row_steps", "ssm_piece_rows")
# the held-experts layers' rows at each boundary, summed on the device over
# its pieces, decode steps and layers and read with the probe's transfer
# (``SlotEngine.moe_rows``; 0 for a model without such a layer): (token,
# expert) pairs the router sent out for rows that count / those whose expert
# is held here / the busiest held expert's, a layer and call / rows dropped
# past the buffer (0 by construction where the buffer holds every pair) / the
# row tiles the grouped product visits / the experts that got a row (tiles /
# experts: how often an output-blocked product streams an expert's weights)
_MOE_KEYS = (
    "moe_rows_routed", "moe_rows_held", "moe_rows_max_expert", "moe_rows_dropped",
    "moe_tiles_live", "moe_experts_live",
)


class OverloadError(RuntimeError):
    """Admission queue full: the request was shed, not queued."""


class RejectedError(RuntimeError):
    """The server is draining or dead and accepts no new requests."""


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    chunk: int = 16  # decode chunk length (deadline/abort granularity)
    slots: int = 8  # concurrent decode slots (one batched-scan row each)
    max_inflight: int = 8  # admission bound on the QUEUED backlog
    deadline_ms: float = 0.0  # default per-request deadline (0 = none)
    stall_timeout: float = 0.0  # watchdog heartbeat budget (0 = off)
    grace: float = 30.0  # SIGTERM drain budget, as in training
    poll: float = 0.05  # idle queue poll cadence (seconds)
    # pad-to-bucket prompt lengths, the staged buffers' widths
    prefill_buckets: str = "pow2"
    # in-scan chunked prefill: the width of ONE slot's prompt piece
    # (rounded up to the linear-attention chunk); a boundary runs a piece
    # for each waiting slot, up to slots // chunk of them, before its
    # decode scan. Must be > 0: there is no other admission path.
    prefill_chunk: int = PREFILL_CHUNK
    # prompts longer than the largest prefill bucket: "error" refuses the
    # request cleanly; "clamp" serves the newest bucket-sized context
    prompt_overflow: str = "error"
    # -- quantized serving (orion_tpu/quant.py): "off" | "int8" | "int4".
    # The fp32 params handed to the Server are quantized ONCE at
    # construction (per-out-channel scales, weights stored int8 /
    # nibble-packed int4) and shared by every slot — each decode step
    # then streams 1/4 (1/8) of the fp32 weight bytes. The state stays
    # fp32/bf16 (only weights quantize), so every contract — batched
    # tokens == the solo scan's, ladder rewind and session suspend/resume
    # bit for bit — holds unchanged PER qmode: quantization changes the
    # numbers, never the determinism.
    qmode: str = "off"
    # -- content-addressed prefix cache (serving/prefix_store.py);
    # None = disabled. A hit admits as one cached-state row copy + in-scan
    # prefill of only the uncached suffix — O(prompt) admission becomes
    # O(suffix). Shared by every replica pointing at the same directory.
    prefix_dir: Optional[str] = None
    prefix_keep: int = 2  # retained generations per prefix entry
    # identity of the WEIGHTS for prefix-cache addressing (config name +
    # checkpoint step / init seed). None = a config-hash default — fine
    # for one model per store, but pin it when several checkpoints of
    # one config share a prefix_dir (the CLIs do).
    params_id: Optional[str] = None
    # -- AOT executable store (serving/exec_store.py); None = disabled.
    # A spawned replica DOWNLOADS its decode programs (serialized by
    # `python -m orion_tpu.aot warm`) instead of compiling them —
    # spawn-to-first-token drops from a compile storm to milliseconds of
    # deserialization. Every miss, version skew, or damaged entry
    # degrades to the jit compile with a counter, never an error.
    exec_dir: Optional[str] = None
    # node-local warm tier in front of the shared exec_dir (write-through
    # on shared hits); None = two tiers only (in-process LRU + shared)
    exec_local_dir: Optional[str] = None
    exec_max_resident: int = 32  # LRU cap on loaded executables
    # -- durable sessions (session_store.py); None = sessions disabled --
    session_dir: Optional[str] = None  # on-disk session store root
    session_idle_s: float = 300.0  # resident-cache idle eviction (0 = off)
    max_resident_sessions: int = 64  # LRU cap on the host-resident cache
    session_keep: int = 2  # retained generations per session on disk
    # -- storage failure domains (ISSUE 17; resilience/breaker.py) --
    # Each shared store (session, prefix) gets its own circuit breaker:
    # after breaker_failures consecutive failed operations the breaker
    # OPENS and every store touch fails in O(1) host work (no syscalls
    # against dead storage) until a jittered backoff expires and one
    # half-open probe operation tests recovery. An open breaker reports
    # health DEGRADED with reason "store-outage:<store>"; requests keep
    # serving (prefix = cold prefill, sessions = write-behind).
    breaker_failures: int = 3
    breaker_backoff: float = 0.5  # open dwell before the first probe
    breaker_max_backoff: float = 30.0  # probe backoff ceiling
    # Write-behind bound during a session-store outage: DIRTY sessions
    # (their save failed; the resident copy is the only up-to-date one)
    # pin themselves in host memory until a save lands. Beyond this many
    # dirty pins, NEW session-carrying admissions shed with a retriable
    # OverloadError citing the store — bounding the turns this process
    # can lose on a crash mid-outage. 0 = unbounded (trust the host).
    max_dirty_sessions: int = 32
    # -- telemetry (orion_tpu/obs/): all host-side, zero device syncs --
    # Prometheus text dumped here (+ .json sibling) every
    # metrics_interval_s at chunk boundaries and always on drain/exit;
    # None = no exposition (the registry still records)
    metrics_path: Optional[str] = None
    metrics_interval_s: float = 10.0  # <= 0: dump on drain only
    # Chrome trace-event JSONL of request/queue/chunk spans; None = off
    # (merge files with `python -m orion_tpu.obs.trace merge` for
    # Perfetto)
    trace_path: Optional[str] = None
    # flight-recorder auto-dumps (DEGRADED/DRAINING/DEAD transitions,
    # ladder exhaustion, watchdog stalls) land here; None = ring only,
    # no dumps
    flight_dir: Optional[str] = None
    # -- live exposition + SLO control loop (obs/http.py, obs/slo.py) --
    # TCP port for the per-process /metrics /healthz /statusz /slo
    # endpoints (-1 = no HTTP server; 0 = ephemeral — the bound port is
    # Server.http_port). The handlers read host-side snapshots only
    # (lint rule obs-device-sync covers every registered provider), so
    # a scrape mid-stream costs the scraper's thread, never a device
    # sync or a compile.
    metrics_port: int = -1
    # declarative SLOs: a list/tuple of obs.slo.Objective kwarg dicts
    # (JSON-able — rides ReplicaSpec.serve unchanged). None = the
    # observe-only defaults (error rate + availability at 99%): burn
    # rates are computed and exposed either way, but ACTUATION
    # (DEGRADED + early shedding) arms only for explicitly declared
    # objectives — a default must never shed traffic the operator
    # didn't define "slow" for.
    slo: Optional[tuple] = None
    # consecutive chunk-boundary evaluations with a fast-burn alert
    # firing before the server degrades itself and sheds early
    slo_degrade_ticks: int = 3
    # -- self-speculative decode (ISSUE 13): the hybrid's global-linear
    # sublayers draft up to spec_depth tokens per slot and the full
    # model verifies them in ONE batched piece at pure-decode
    # boundaries. Emitted tokens are BITWISE the plain walk's (greedy
    # AND sampled — verification re-samples from the full model's
    # logits at the same rng folds), so speculation changes speed,
    # never output. 0 = off. Dense models with >= 1 linear layer only;
    # needs spec_depth + 1 <= window on swa configs.
    spec_depth: int = 0
    # per-slot adaptive floor: when a slot's rolling (EWMA) acceptance
    # drops below this, it falls back to plain decode for the rest of
    # its residency instead of paying a losing draft. The default is
    # conservative — a draft accepting under ~1 token in 5 costs more
    # than it saves on any realistic cost ratio. 0 disables the floor.
    spec_min_accept: float = 0.2
    # -- tensor-parallel decode (ISSUE 14): shard the batched decode
    # over a tp-device mesh — weights by the training rules (heads/
    # hidden on tp, wo/down psum-at-output: two all-reduces per block
    # per step, golden decode_batched_tp{2,4}), the O(1) state on the
    # head dimension, per-slot carry replicated. Emitted tokens are
    # BITWISE the unsharded server's at the same seeds, and suspended
    # sessions stay portable across footprints (the store holds the
    # logical row; resharding is a host-side reshape at resume).
    # 0/1 = unsharded. The process must expose >= tp devices.
    tp: int = 0
    # compile the pure decode program once at startup to report the
    # collectives GSPMD actually inserted vs the declared budget
    # (/statusz "mesh" section — a misconfigured mesh is visible before
    # it is slow). Costs one extra AOT compile; tp>1 only.
    mesh_audit: bool = True
    # -- cost attribution + capacity observability (ISSUE 15; obs/cost.py).
    # cost=True arms per-request attribution (each boundary's measured
    # chunk_ms split across resident slots by ledger-weighted work class,
    # accumulated as device_ms/cost_flops/token counts on every result,
    # histogram'd at completion) and the live CapacityModel
    # (capacity_tokens_per_s / capacity_headroom gauges + the /costz and
    # /statusz sections). Pure host arithmetic at chunk boundaries —
    # zero device syncs, zero compiles (cache-stat-asserted).
    cost: bool = True
    # harvest XLA cost_analysis() flops/bytes for this engine shape's
    # decode programs at construction (aot.decode_cost_entries —
    # LOWER-only, the jit caches are untouched; memoized process-wide).
    # Off by default in the library (a construction-time lowering is a
    # startup cost unit tests shouldn't pay); the CLIs default it on.
    # Without it, attribution weights fall back to token counts and
    # flops to an analytic 2 x params estimate.
    cost_ledger: bool = False
    # the CapacityModel's rolling window over chunk_ms / token counters
    capacity_window_s: float = 30.0
    # -- on-demand profiling: directory for jax.profiler trace artifacts.
    # None = /profilez refuses (off by default). Arming (/profilez?
    # chunks=K or Server.arm_profile) captures the next K chunk
    # boundaries into one linkable TensorBoard-loadable artifact; the
    # arm/start/stop walk is flight-recorded. The profiler itself only
    # ever starts/stops on the scheduler thread at boundaries — never
    # from the scrape handler.
    profile_dir: Optional[str] = None


@dataclasses.dataclass
class Pending:
    """A submitted request's handle; ``done`` is set exactly once, with
    either ``result`` or ``error`` filled. ``admitted_at`` anchors the
    request's deadline: queue wait counts against the budget;
    ``done_at`` records completion (the serving bench's latency stamp).
    ``first_token_at`` (0.0 until set) is the end of the boundary whose
    scan emitted the request's first tokens — the earliest moment a
    streaming client could have seen one; it stays 0.0 for a request that
    ended before any."""

    request: DecodeRequest
    done: threading.Event
    admitted_at: float = 0.0
    first_token_at: float = 0.0
    result: Optional[DecodeResult] = None
    error: Optional[Exception] = None
    done_at: float = 0.0
    # trace identity: the async-span id every event of this request's
    # lifecycle carries (``<session_id>:<seq>`` for session turns, so a
    # resumed conversation links across replicas by prefix)
    rid: str = ""
    # -- cost-attribution accumulators (ISSUE 15): the scheduler folds
    # each boundary's attributed share in here; _complete stamps the
    # totals onto the DecodeResult and _finalize histograms them
    device_ms: float = 0.0
    cost_flops: float = 0.0
    prefill_tokens: int = 0
    decode_tokens: int = 0
    # called exactly once, right after ``done`` fires — the fleet router
    # ends its root ``turn`` span here; must be host-only and non-raising
    on_done: Optional[Callable[["Pending"], None]] = None

    def wait(self, timeout: Optional[float] = None) -> Optional[DecodeResult]:
        """Block for the outcome: returns the DecodeResult, RAISES the
        request's recorded error (rejection at shutdown, a raising
        request), or returns None only on timeout — so a dropped request
        can't be mistaken for a slow one."""
        if not self.done.wait(timeout=timeout):
            return None
        if self.error is not None:
            raise self.error
        return self.result


def load_tokenizer(path: Optional[str] = None, retry: Optional[RetryPolicy] = None):
    """Tokenizer I/O behind the same jittered-backoff retry as the
    checkpoint load — a 2-second storage blip on the tokenizer JSON must
    not kill a replica that survived everything else. ``None`` path =
    the byte-level tokenizer (no I/O beyond the hook)."""

    def _load():
        fire("serve.tokenizer_io")
        if path:
            from orion_tpu.utils.bpe import BPETokenizer

            return BPETokenizer.load(path)
        from orion_tpu.utils.tokenizer import ByteTokenizer

        return ByteTokenizer()

    return call_with_retries(
        _load, retry if retry is not None else RetryPolicy(),
        describe="tokenizer load",
    )


class Server:
    """Single-worker scheduler loop (decode serializes on the device
    anyway); ``submit`` is thread-safe and may be called from feeder
    threads."""

    def __init__(
        self,
        model,
        params,
        cfg: ServeConfig = ServeConfig(),
        clock: Callable[[], float] = time.monotonic,
        tracer: Optional[Tracer] = None,
        flight: Optional[FlightRecorder] = None,
    ):
        self.trace = tracer if tracer is not None else Tracer(
            path=cfg.trace_path, clock=clock, enabled=bool(cfg.trace_path),
        )
        # the set-up tree (obs/trace.py, ``cat="setup"``): this span and
        # its children reach the process-wide record whether or not the
        # tracer is enabled
        with self.trace.span("setup.server", "setup", slots=cfg.slots,
                             chunk=cfg.chunk):
            self._build(model, params, cfg, clock, flight)

    def _cast_once(self, model, params):
        """Unquantized serving: the matmul weights in the dtype they are
        multiplied in, cast ONCE at set-up and not once a call inside every
        boundary program (``generate.serving_params``). Returns the tree
        the engine serves and the bytes of the handed leaves it re-holds.
        The server keeps no reference to the handed tree: whether it lives
        on is the caller's business."""
        import jax

        from orion_tpu import generate as _gen
        from orion_tpu.serving.batching import tree_nbytes

        with self.trace.span("setup.cast", "setup") as cast:
            t0 = self._clock()
            served = _gen.serving_params(model, params)
            recast = [
                (a, b) for a, b in zip(jax.tree.leaves(params),
                                       jax.tree.leaves(served)) if a is not b
            ]
            jax.block_until_ready([b for _, b in recast])
            cast.note(leaves=len(recast),
                      seconds=round(self._clock() - t0, 6))
        return served, tree_nbytes([a for a, _ in recast])

    def _build(self, model, params, cfg, clock, flight) -> None:
        from orion_tpu import generate as _gen

        self.cfg = cfg
        self._clock = clock
        # quantized serving: quantize ONCE here, before any engine or jit
        # wrapper sees the params — every slot then shares the same
        # int8/int4 tree, and the jit caches key on the quant model, so
        # the engine's lifetime still costs one decode compile per
        # (slots, chunk, bucket, qmode)
        self.qmode = (cfg.qmode or "off").lower()
        if self.qmode not in ("off", "int8", "int4"):
            raise ValueError(
                f"qmode must be one of off|int8|int4, got {cfg.qmode!r}"
            )
        cast_bytes = 0
        if self.qmode != "off":
            with self.trace.span("setup.quantize", "setup", qmode=self.qmode):
                model, params = _gen.quantize_for_decode(
                    model, params, mode=self.qmode
                )
        else:
            params, cast_bytes = self._cast_once(model, params)
        # the weights' identity stamps BOTH stores: prefix entries are
        # keyed by it (content addressing) and session generations carry
        # it (a suspended state resumed under different weights or qmode
        # would silently diverge — the store refuses the mismatch)
        from orion_tpu.serving.prefix_store import params_identity

        self.params_id = cfg.params_id or params_identity(
            model.cfg, self.qmode
        )
        self._weights_identity = f"{self.params_id}|{self.qmode}"
        # ONE reentrant lock guards the metrics registry AND the health
        # machine: `snapshot()` reads both under a single acquisition, so
        # a fleet router polling /healthz can never observe a torn pair
        # (e.g. the old health state with the new slot gauges). Reentrant
        # because snapshot() holds it while calling health.snapshot().
        self._stats_lock = threading.RLock()
        # -- telemetry spine (orion_tpu/obs/): every instrumentation
        # point below records HOST values the scheduler already holds at
        # chunk boundaries — no device syncs, no new compiles (lint rule
        # obs-device-sync + the cache-stat asserts in tests/test_obs.py)
        self.metrics = MetricsRegistry(clock=clock, lock=self._stats_lock)
        for key in (_STAT_KEYS + _SLOT_CLASS_KEYS + _KV_ROW_KEYS
                    + _KV_BLOCK_KEYS + _KV_LIST_KEYS + _ADMIT_KEYS + _SSM_KEYS
                    + _MOE_KEYS
                    + _KV_ATTENDED_KEYS + _RING_KEYS):
            self.metrics.counter(key)  # the legacy stats dict's cells
        # what jax built while this server lived (obs/trace.py
        # ``compile_event``), by stage; the open ``setup.first_launch``
        # and the backend stages that fell inside it
        self._c_compile = {
            key: self.metrics.counter(key) for key in COMPILE_COUNTERS
        }
        self._launch = None
        self._setup_ready = False
        # the boundary the running serve-loop iteration steps (or would):
        # what every phase span and compile event carries as ``boundary``
        self._boundary = 1
        on_compile(self._on_compile)
        self.flight = flight if flight is not None else FlightRecorder(
            clock=clock, dump_dir=cfg.flight_dir,
        )
        self._h_chunk_ms = self.metrics.histogram("chunk_ms")
        self._h_turn_ms = self.metrics.histogram("turn_latency_ms")
        self._h_session_save_ms = self.metrics.histogram("session_save_ms")
        self._h_session_load_ms = self.metrics.histogram("session_load_ms")
        self._c_ladder = self.metrics.counter("ladder_rungs")
        self._c_health = self.metrics.counter("health_transitions")
        self._c_slo_alerts = self.metrics.counter("slo_alerts")
        self._rid_seq = 0
        # per-server token inside every trace id: two replicas (or one
        # replica restarted) sharing a trace file must never collide on
        # span ids — the session id stays the LINKING key, the token
        # keeps the spans distinct
        self._rid_token = uuid.uuid4().hex[:6]
        self._metrics_next = 0.0
        self.health = HealthMachine(
            clock=clock, lock=self._stats_lock,
            on_transition=self._on_health,
        )
        # tensor-parallel decode (ISSUE 14): build the tp mesh BEFORE the
        # engine so placement fails loudly at construction (too few
        # devices, never an opaque GSPMD error at the first chunk). The
        # mesh report is computed here too — all host-side by the time
        # any request arrives, so /statusz serves it without a device op.
        self.tp = max(int(cfg.tp), 1)
        self.mesh = None
        self.mesh_info: Optional[dict] = None
        if self.tp > 1:
            from orion_tpu.parallel.decode import mesh_report, serving_mesh

            self.mesh = serving_mesh(self.tp)
            # the probe compiles the greedy-default program: the
            # collective structure is sampling-independent (the
            # all-reduces live in the blocks), and the engine's real
            # SampleConfig is not known until the first admission
            self.mesh_info = mesh_report(
                model, params, self.mesh, cfg.slots, cfg.chunk,
                _gen.SampleConfig(), compile_probe=cfg.mesh_audit,
            )
            if self.mesh_info.get("budget_ok") is False:
                warnings.warn(
                    "tp mesh audit: observed decode collectives "
                    f"{self.mesh_info.get('observed_collectives')} do not "
                    "match the declared per-step budget "
                    f"({self.mesh_info.get('allreduces_per_step_budget')} "
                    "all-reduces) — the mesh may not be engaging (head "
                    "count not divisible by tp?); serving continues but "
                    "the footprint is suspect (/statusz mesh section)",
                    stacklevel=2,
                )
        with self.trace.span("setup.engine", "setup") as built:
            self.engine = SlotEngine(
                model, params, slots=cfg.slots, chunk=cfg.chunk, clock=clock,
                prefill_buckets=parse_buckets(
                    cfg.prefill_buckets, model.cfg.max_seq_len
                ),
                prefill_chunk=cfg.prefill_chunk,
                prompt_overflow=cfg.prompt_overflow,
                on_event=self._on_engine_event,
                spec_depth=cfg.spec_depth,
                spec_min_accept=cfg.spec_min_accept,
                mesh=self.mesh,
            )
            built.note(donate_carry=self.engine.donate_carry,
                       params_cast_bytes=cast_bytes,
                       **self.engine.held_bytes)
        self.metrics.gauge("params_bytes_held").set(
            self.engine.held_bytes["params_bytes"]
        )
        # the engine's staging_dispatches as of the last boundary
        self._staged_seen = 0
        # self-speculation telemetry (ISSUE 13): totals for the SLO
        # engine's rate views plus a per-turn acceptance-rate histogram
        # — when speculation stops paying, the acceptance collapse is
        # visible before the latency regression is
        self._c_spec_accepted = self.metrics.counter("spec_accepted_total")
        self._c_spec_rejected = self.metrics.counter("spec_rejected_total")
        self._c_spec_floors = self.metrics.counter("spec_floor_total")
        self._h_spec_accept = self.metrics.histogram(
            "spec_accept_rate",
            buckets=(0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0),
        )
        # content-addressed prefix cache: one store per prefix_dir,
        # shared across replicas; entries are aligned to the engine's
        # linear-attention chunk so a hit's suffix pieces stay on the
        # in-scan bitwise contract
        self.prefix_store = None
        self._c_prefix_hits = self.metrics.counter("prefix_hits")
        self._c_prefix_misses = self.metrics.counter("prefix_misses")
        self._c_prefix_publishes = self.metrics.counter("prefix_publishes")
        self._c_prefix_bytes = self.metrics.counter("prefix_bytes")
        self._h_prefix_load_ms = self.metrics.histogram("prefix_load_ms")
        self._h_prefix_save_ms = self.metrics.histogram("prefix_save_ms")
        # -- storage failure domains (ISSUE 17): one breaker per shared
        # store, constructed on the server's clock with an observer that
        # black-boxes every transition; the health latch (_tick_store_
        # health) and the status op read them from this registry
        self._breakers: Dict[str, CircuitBreaker] = {}
        self._c_store_errors = self.metrics.counter("store_errors")
        self._c_prefix_drops = self.metrics.counter("prefix_publish_drops")
        self.session_store: Optional[SessionStore] = None
        self.exec_store = None
        with self.trace.span("setup.stores", "setup") as opened:
            if cfg.prefix_dir:
                from orion_tpu.serving.prefix_store import PrefixStore

                self.prefix_store = PrefixStore(
                    cfg.prefix_dir, params_id=self.params_id, qmode=self.qmode,
                    align=max(self.engine.chunk_align, 1),
                    keep=cfg.prefix_keep,
                    should_abort=lambda: not self.health.accepting,
                    observer=self._on_prefix_io, clock=clock,
                    breaker=self._make_breaker("prefix"),
                )
                self.engine.attach_prefix_store(self.prefix_store)
            # -- AOT executable store (ROADMAP item 1): the engine's first
            # launch of each program consults it and a hit installs the
            # deserialized executable — a warmed replica reaches its first
            # token without one compile. Its breaker joins the failure-
            # domain registry: an outage degrades to cold compiles (counted
            # misses), never failed requests, and health reports
            # store-outage:exec so the supervisor doesn't churn the replica.
            self._h_exec_load_ms = self.metrics.histogram("exec_load_ms")
            self._h_exec_save_ms = self.metrics.histogram("exec_save_ms")
            if cfg.exec_dir:
                from orion_tpu.serving.exec_store import ExecStore

                self.exec_store = ExecStore(
                    cfg.exec_dir, identity=self._weights_identity,
                    local_dir=cfg.exec_local_dir,
                    max_resident=cfg.exec_max_resident,
                    should_abort=lambda: not self.health.accepting,
                    observer=self._on_exec_io, clock=clock,
                    breaker=self._make_breaker("exec"),
                )
                self.engine.attach_exec_store(self.exec_store, qmode=self.qmode)
                for stat in ("hits", "misses", "publishes",
                             "fallback_compiles", "errors"):
                    # single-writer int reads (the scheduler owns the stats
                    # dict) — host-only, like every gauge_fn provider
                    self.metrics.gauge_fn(
                        "exec_store_events",
                        lambda s=stat: self.exec_store.stats[s],
                        labels={"event": stat},
                    )
                self.metrics.gauge_fn(
                    "exec_store_resident",
                    lambda: self.exec_store.resident_count(),
                )
            if cfg.session_dir:
                self.session_store = SessionStore(
                    cfg.session_dir, keep=cfg.session_keep,
                    # a DRAINING/DEAD server must not burn its drain grace
                    # backing off on session I/O (resilience/retry.py)
                    should_abort=lambda: not self.health.accepting,
                    observer=self._on_store_io, clock=clock,
                    identity=self._weights_identity,
                    breaker=self._make_breaker("session"),
                )
            opened.note(prefix=bool(cfg.prefix_dir), exec=bool(cfg.exec_dir),
                        session=bool(cfg.session_dir))
        # the gauges we used to fly blind on — all callable (evaluated at
        # scrape time from live host state) and all free: queue depth,
        # per-slot prefill-vs-decode occupancy, compile-cache sizes
        self.metrics.gauge_fn("queue_depth", self._q_depth)
        for key in ("active", "free", "prefilling", "decoding"):
            self.metrics.gauge_fn(
                "slots", self._slot_gauge(key), labels={"state": key}
            )
        self.metrics.gauge_fn("sessions_resident",
                              lambda: len(self._sessions))
        self.metrics.gauge_fn("sessions_in_slots",
                              lambda: len(self._active_sessions))
        self.metrics.gauge_fn("dirty_backlog",
                              lambda: len(self._dirty_sessions))
        for label, jitted in _gen.DECODE_PROGRAMS.items():
            # host-side executable-cache introspection, not a device op —
            # the gauge that proves telemetry added zero compiles. The tp
            # label says which footprint's programs fill the cache (each
            # tp is its own compile key — the cache entries scale with
            # the footprints a process hosts, and a mixed-footprint
            # LocalReplica fleet must be attributable per mesh).
            self.metrics.gauge_fn(
                "compile_cache_entries", jitted._cache_size,
                labels={"cache": label, "tp": str(self.tp)},
            )
        # -- cost attribution + capacity (ISSUE 15; obs/cost.py): the
        # ledger prices this engine shape's programs, attribution splits
        # every boundary's measured wall time across resident slots, and
        # the capacity model folds the windowed chunk_ms quantiles into a
        # live tokens/s ceiling + headroom. All host arithmetic over
        # values the scheduler already holds.
        self.cost_enabled = bool(cfg.cost)
        self.cost_ledger: Optional[obs_cost.CostLedger] = None
        self.capacity: Optional[obs_cost.CapacityModel] = None
        if self.cost_enabled:
            # analytic fallback flops/token (~2 per weight): host-side
            # metadata over the (possibly quantized) param tree, no sync
            import jax as _jax

            n_params = sum(
                int(x.size) for x in _jax.tree.leaves(params)
            )
            self.cost_ledger = obs_cost.CostLedger(
                slots=cfg.slots, chunk=cfg.chunk,
                prefill_chunk=self.engine.prefill_chunk,
                spec_depth=cfg.spec_depth,
                fallback_flops_per_token=2.0 * n_params,
            )
            if cfg.cost_ledger:
                with self.trace.span("setup.cost_harvest", "setup"):
                    self._harvest_cost_ledger(model)
            self._h_req_device_ms = self.metrics.histogram(
                "request_device_ms"
            )
            self._h_req_flops = self.metrics.histogram(
                "request_cost_flops", buckets=obs_cost.FLOPS_BUCKETS
            )
            self._c_attr_ms = self.metrics.counter("attributed_ms_total")
            self._c_decode_tokens = self.metrics.counter(
                "decode_tokens_total"
            )
            self._c_prefill_tokens = self.metrics.counter(
                "prefill_tokens_total"
            )
            self.capacity = obs_cost.CapacityModel(
                slots=cfg.slots, chunk=cfg.chunk,
                buckets=self._h_chunk_ms.buckets,
                read_chunk_counts=self._read_chunk_counts,
                read_tokens=self._read_device_tokens,
                clock=clock, window_s=cfg.capacity_window_s,
            )
            for field, name in (
                ("ceiling_tokens_per_s", "capacity_tokens_per_s"),
                ("current_tokens_per_s", "capacity_current_tokens_per_s"),
                ("headroom", "capacity_headroom"),
            ):
                # lazily-evaluated; RAISES (cell absent) until the model
                # has data — the check gate's no_data semantics
                self.metrics.gauge_fn(name, self.capacity.gauge(field))
        # -- on-demand profiling (ISSUE 15): armed via /profilez or
        # arm_profile(); the jax.profiler start/stop runs ONLY on the
        # scheduler thread at chunk boundaries
        self._profile_pending = 0
        self._profile_left = 0
        self._profile_path: Optional[str] = None
        self._profile_seq = 0
        # durable sessions: write-through disk store + a host-resident LRU
        # cache in front of it (resident entries are ALWAYS also on disk,
        # so idle/LRU eviction is pure cache management, and the race
        # "idle eviction at the same boundary a continuation re-admits"
        # degrades to a disk read, never a lost session)
        self._sessions: "OrderedDict[str, SessionState]" = OrderedDict()
        self._session_last_use: Dict[str, float] = {}
        self._active_sessions: set = set()  # ids resident in engine slots
        # ids whose last save FAILED: their resident copy is the only
        # up-to-date one, so cache eviction must not drop them (and the
        # tick loop keeps retrying the save until disk catches up)
        self._dirty_sessions: set = set()
        self._dirty_retry_at: float = 0.0
        # SIGTERM drain budget anchor: set when health enters DRAINING;
        # a drain with dirty sessions holds residency (retrying via the
        # breaker's half-open probes) until this deadline, then reports
        # the still-dirty ids loudly and exits 0
        self._drain_deadline: float = 0.0
        self._q: "queue.Queue[Pending]" = queue.Queue(maxsize=cfg.max_inflight)
        self._guard: Optional[PreemptionGuard] = None
        # submit() is documented thread-safe for feeder threads. The
        # admission lock makes (accepting check -> enqueue) atomic against
        # the drain path's final (reject leftovers -> DEAD): without it a
        # put landing between the serve loop's last empty-check and DEAD
        # would strand a Pending whose done event never fires.
        self._admission_lock = threading.Lock()
        # -- SLO control loop (obs/slo.py): windowed views over the SAME
        # registry cells, evaluated at chunk boundaries. tick() reads the
        # cells under the stats lock FIRST, then updates its own state
        # under the engine's private lock — the two are never held
        # together, so a scrape thread reading state() can't deadlock
        # against the scheduler.
        declared = bool(cfg.slo)  # slo=[]/() is "nothing declared" too
        objectives = (
            [obs_slo.Objective(**dict(d)) for d in cfg.slo]
            if declared else obs_slo.default_objectives()
        )
        self.slo = obs_slo.SLOEngine(
            objectives, obs_slo.registry_readers(self.metrics), clock=clock,
        )
        self._slo_actuate = declared
        self._slo_burn_ticks = 0
        self._slo_shedding = False
        self._slo_slow_prev = False
        self._chunk_seq = 0  # serve.chunk_delay's step address
        # the engine phase span open on the scheduler thread (dispatch ->
        # probe -> finish, switched by the engine's "phase" events), and
        # the first poll of the idle stretch in progress
        self._engine_phase = NULL_SPAN
        self._idle_first = None
        # -- live exposition (obs/http.py): /metrics /healthz /statusz
        # /slo on a daemon thread; stays up across serve() calls (a
        # balancer must see DRAINING/DEAD as 503, not connection
        # refused) and closes with close()
        self.http: Optional[ObsHTTPServer] = None
        self.http_port: Optional[int] = None
        if cfg.metrics_port >= 0:
            self.http = ObsHTTPServer(
                port=cfg.metrics_port,
                metrics_fn=self.metrics.snapshot,
                health_fn=self._healthz,
                statusz_fn=self._statusz,
                slo_fn=self.slo.state,
                costz_fn=self._costz,
                profilez_fn=self._profilez,
            )
            self.http_port = self.http.start()

    @property
    def stats(self) -> Dict[str, int]:
        """The PR 4-8 stats dict, read from the registry's unlabelled
        counter cells (one consistent acquisition). A snapshot — mutate
        through the registry, not this dict."""
        flat = self.metrics.counters_flat()
        return {k: flat.get(k, 0) for k in _STAT_KEYS}

    def _bump(self, key: str, n: int = 1) -> None:
        self.metrics.counter(key).inc(n)

    def _phase(self, name: str, record: bool = True, **args):
        """The span helper of the served boundary: ``with
        self._phase("serve.admit"):`` writes the interval into the Tracer
        ring and, while a profiler capture runs, as an annotation of the
        same name into the device trace (obs/trace.py ``Span``). With
        the tracer disabled and no capture running it hands back the one
        shared null span: nothing is built, nothing is timed."""
        tr = self.trace
        if not tr.enabled and tr.annotate is None:
            return NULL_SPAN
        return tr.span(name, "phase", record, boundary=self._boundary, **args)

    def _on_compile(self, name: str, start_s: float, dur_s: float,
                    args: dict) -> None:
        """One stage of a program jax built somewhere in this process
        (obs/trace.py ``compile_event``; it is in the process-wide record
        already): into this server's counters and, with the tracer on,
        its ring, under the boundary the scheduler thread is in — an
        in-window compile then lies inside the ``serve.admit`` or
        ``serve.dispatch`` span that caused it. jax reports a stage once
        it is over, so a running capture gets a marker where it ENDED;
        the enclosing phase span is the annotation that covers it."""
        for key, n in compile_counts(name, dur_s, args):
            self._c_compile[key].inc(n)
        launch = self._launch
        if (name == "compile.backend" and launch is not None
                and launch[1] == threading.get_ident()):
            launch[2].append((args.get("source"), dur_s))
        tr = self.trace
        if tr.enabled:
            tr.complete(name, start_s, dur_s, cat="compile",
                        boundary=self._boundary, **args)
        if tr.annotate is not None:
            with tr.annotate(name):
                pass

    def _first_launch(self, fields: dict) -> None:
        """The engine's two edges around the FIRST launch of a boundary
        program kind (and staged width): ``setup.first_launch``. What the
        launch cost is learned from the backend stages that fell inside
        it on this thread: jax ``compiled`` the program, loaded it from
        its ``cache``, the ``exec_store`` handed a stored executable
        over, or the process had built it already (``resident``). A
        launch that built something is the program's observed compile
        cost — into the ledger (the /costz "compile_ms" column) and the
        black box (a mid-serve compile is always worth explaining)."""
        if fields.pop("edge") == "begin":
            span = self.trace.span("setup.first_launch", "setup", **fields)
            self._launch = (span, threading.get_ident(), [])
            span.__enter__()
            return
        if self._launch is None:
            return
        (span, _, built), self._launch = self._launch, None
        sources = {source for source, _ in built}
        source = ("exec_store" if fields.get("warm")
                  else "compiled" if "compiled" in sources
                  else "cache" if sources else "resident")
        span.note(source=source, programs=len(built))
        span.__exit__(None, None, None)
        if not built:
            return
        program, ms = span.args["program"], round(span.dur * 1e3, 3)
        if self.cost_ledger is not None:
            self.cost_ledger.note_compile(program, ms)
            self.metrics.gauge("cost_ledger_compile_ms").set(
                ms, labels={"program": program},
            )
        self.flight.record("program_compile", program=program, ms=ms,
                           source=source)

    # -- telemetry hooks (all host-only; see obs-device-sync) -----------------

    def _q_depth(self) -> int:
        return self._q.qsize()

    def _slot_gauge(self, key: str) -> Callable[[], int]:
        return lambda: self.engine.occupancy()[key]

    def _on_store_io(self, op: str, ms: float) -> None:
        (self._h_session_save_ms if op == "save"
         else self._h_session_load_ms).observe(ms)

    def _on_prefix_io(self, op: str, ms: float, nbytes: int) -> None:
        (self._h_prefix_save_ms if op == "save"
         else self._h_prefix_load_ms).observe(ms)
        self._c_prefix_bytes.inc(nbytes, labels={"op": op})

    def _on_exec_io(self, op: str, ms: float, nbytes: int) -> None:
        (self._h_exec_save_ms if op == "save"
         else self._h_exec_load_ms).observe(ms)

    # -- storage failure domains (ISSUE 17) -----------------------------------

    _BREAKER_GAUGE = {"closed": 0, "half_open": 1, "open": 2}

    def _make_breaker(self, name: str) -> CircuitBreaker:
        """One circuit breaker per shared store, on the server's clock,
        registered for the health latch / status op / breaker_state
        gauge. The observer runs OUTSIDE the breaker lock (breaker.py's
        contract) so recording to the flight ring is safe."""
        br = CircuitBreaker(
            name,
            consecutive_failures=max(self.cfg.breaker_failures, 1),
            backoff=self.cfg.breaker_backoff,
            max_backoff=self.cfg.breaker_max_backoff,
            clock=self._clock, observer=self._on_breaker,
        )
        self._breakers[name] = br
        self.metrics.gauge_fn(
            "breaker_state",
            lambda b=br: self._BREAKER_GAUGE[b.state],
            labels={"store": name},
        )
        return br

    def _on_breaker(self, name: str, old: str, new: str,
                    reason: str) -> None:
        """Breaker transition tap: every edge into the black box, and a
        trip counts one store_errors tick (the windowed failure detail
        lives in the breaker snapshot on /statusz)."""
        self.flight.record("breaker", store=name, frm=old, to=new,
                           reason=reason)
        if new == "open":
            self._c_store_errors.inc(labels={"store": name})

    def _store_outage(self) -> Optional[str]:
        """Name of a store whose breaker is not known-good (open or
        probing), or None when all storage domains are healthy."""
        for name, br in self._breakers.items():
            if br.is_open:
                return name
        return None

    def _store_outage_latched(self) -> bool:
        """Must DEGRADED stay latched for storage reasons? True while
        any breaker is open OR the dirty write-behind backlog from a
        store outage has not drained — recovery to SERVING requires
        both the store back AND every turn it missed on disk."""
        if self._store_outage() is not None:
            return True
        return (self.health.reason.startswith("store-outage:")
                and bool(self._dirty_sessions))

    def _tick_store_health(self) -> None:
        """Chunk-boundary storage-domain health: an open breaker drives
        SERVING -> DEGRADED (reason ``store-outage:<store>`` — the
        supervisor reads that reason and does NOT respawn: a fresh
        process meets the same dead store); breakers closed AND dirty
        backlog drained recovers to SERVING."""
        self._probe_idle_breakers()
        name = self._store_outage()
        if name is not None:
            reason = f"store-outage:{name}"
            self._degrade(reason)
            if (self.health.state is Health.DEGRADED
                    and not self.health.reason.startswith("store-outage:")):
                # already DEGRADED under a blunter reason (the save
                # failure that tripped the breaker degraded first):
                # sharpen it — the supervisor's respawn suppression and
                # /healthz read the reason, and "store-outage:<name>"
                # is the one that means "don't respawn, a fresh process
                # meets the same dead store"
                self.health.restate(reason)
        elif (self.health.state is Health.DEGRADED
              and self.health.reason.startswith("store-outage:")
              and not self._dirty_sessions
              and not self._slo_shedding):
            self.health.to(Health.SERVING,
                           "store recovered; dirty backlog drained")

    def _probe_idle_breakers(self) -> None:
        """Recovery evidence for a TRAFFIC-LESS outage: an open
        breaker's probe normally rides real store work — the dirty-retry
        sweep (session) or lookups and queued publishes (prefix) — but a
        breaker that tripped with no such work pending has no probe
        driver at all, so the replica would sit DEGRADED forever after
        the store recovered. One cheap half-open directory scan per
        dwell closes that hole; while the store is still dead the failed
        probe re-opens with the doubled backoff, so an extended outage
        costs one scan per dwell, not one per chunk. Stores whose
        natural probe IS pending (dirty sessions, queued publishes)
        are skipped — the real operation is the better probe."""
        probes = []
        if (self.prefix_store is not None
                and not self.engine.pending_prefix_count):
            probes.append(("prefix", self.prefix_store.list_keys))
        if self.session_store is not None and not self._dirty_sessions:
            probes.append(("session", self.session_store.list_sessions))
        if self.exec_store is not None:
            # the exec store NEVER has pending work after the engine's
            # per-key lookups ran once — without this probe a breaker
            # that tripped during warm-up would pin DEGRADED forever
            probes.append(("exec", self.exec_store.list_keys))
        for name, scan in probes:
            br = self._breakers.get(name)
            if br is None or not br.is_open or not br.allow():
                continue
            try:
                scan()
            except OSError as e:
                br.record_failure(f"probe: {type(e).__name__}: {e}")
            else:
                br.record_success()

    def _healthz(self) -> dict:
        """/healthz payload: the health snapshot stamped with the
        documented HTTP code for its state (health.HTTP_STATUS) — the
        code answers "route traffic here?", the body says why."""
        snap = self.health.snapshot()
        snap["code"] = HTTP_STATUS[Health(snap["state"])]
        # the one-line answer a human (or a probe's log line) wants:
        # the state, and WHY when the state needs explaining — e.g.
        # "degraded: store-outage:session" tells the on-caller which
        # failure domain to look at without a /statusz round trip
        snap["status"] = (
            snap["state"]
            if snap["state"] == "serving" or not snap["reason"]
            else f"{snap['state']}: {snap['reason']}"
        )
        return snap

    def _statusz(self) -> dict:
        """/statusz payload (rendered as the human debug page): the
        atomic server snapshot — health, stats, slot phases, resident
        sessions — plus SLO budgets and the flight ring's tail. All
        host-side reads; the registry's full cell dump stays on
        /metrics where a scraper wants it."""
        snap = self.snapshot()
        snap.pop("metrics", None)
        if self.mesh_info is not None:
            # the mesh section: axis sizes, per-device weight/state
            # bytes, and declared-vs-observed per-step collectives — a
            # replicating (misconfigured) mesh shows budget_ok=False and
            # an un-divided param_bytes_per_device here, long before it
            # shows up as a latency regression. Computed once at
            # construction; this is a host dict read, never a device op.
            snap["mesh"] = self.mesh_info
        if self.cfg.spec_depth:
            flat = self.metrics.counters_flat()
            snap["speculation"] = {
                "depth": self.cfg.spec_depth,
                "min_accept": self.cfg.spec_min_accept,
                "accepted_total": flat.get("spec_accepted_total", 0),
                "rejected_total": flat.get("spec_rejected_total", 0),
                "floors_total": flat.get("spec_floor_total", 0),
                "slots": self.engine.spec_info(),
            }
        if self.cost_enabled:
            # the capacity figure an operator (or balancer) wants on the
            # debug page; the full price sheet stays on /costz
            flat = self.metrics.counters_flat()
            snap["cost"] = {
                "capacity": self.capacity.state(),
                "attributed_ms_total": round(
                    flat.get("attributed_ms_total", 0), 3
                ),
                "ledger_programs": len(self.cost_ledger.entries()),
            }
        if self._breakers:
            # the failure-domain section: per-store breaker state (with
            # probe countdowns), the dirty write-behind backlog against
            # its bound, and the publish queue's counted drops — the
            # page an operator reads DURING a store outage
            flat = self.metrics.counters_flat()
            snap["failure_domains"] = {
                "breakers": {
                    n: b.snapshot() for n, b in self._breakers.items()
                },
                "dirty_backlog": len(self._dirty_sessions),
                "dirty_sessions": sorted(self._dirty_sessions)[:16],
                "max_dirty_sessions": self.cfg.max_dirty_sessions,
                "prefix_publish_drops": flat.get("prefix_publish_drops", 0),
                "pending_prefix_publishes": self.engine.pending_prefix_count,
            }
        if self.exec_store is not None:
            # the warm-start section: hit/miss/fallback tallies answer
            # "did this replica compile anything it shouldn't have?" —
            # fallback_compiles > 0 after an aot warm pass is the signal
            # that the store's identity and the engine's diverged
            snap["exec_store"] = {
                "identity": self.exec_store.identity,
                "stats": dict(self.exec_store.stats),
                "resident": self.exec_store.resident_count(),
            }
        # why did this replica take so long to come up, and which program
        # missed the cache: the process's set-up and compile events
        # (obs/trace.py ``setup_record``), summed and in full
        record = setup_record()
        snap["setup"] = {"summary": setup_summary(record), "events": record}
        snap["flight_tail"] = self.flight.events()[-20:]
        return snap

    # -- cost attribution + capacity (ISSUE 15) -------------------------------

    def _harvest_cost_ledger(self, model) -> None:
        """Price this engine shape's decode programs into the ledger:
        ``aot.decode_cost_entries`` LOWERS each program (the jit caches
        are untouched — the zero-compile acceptance covers this) and
        extracts XLA cost_analysis flops/bytes; the figures land as
        ``cost_ledger_*`` gauges keyed by the program identity. A failed
        harvest degrades to the analytic fallback with a warning —
        serving must come up regardless."""
        try:
            from orion_tpu.aot import decode_cost_entries

            entries = decode_cost_entries(
                model.cfg, slots=self.cfg.slots, chunk=self.cfg.chunk,
                bucket=max(self.engine.buckets),
                prefill_chunk=self.engine.prefill_chunk,
                qmode=self.qmode, tp=self.tp,
                spec_depth=self.cfg.spec_depth,
            )
        except Exception as e:
            warnings.warn(
                f"cost-ledger harvest failed ({type(e).__name__}: {e}); "
                "attribution falls back to the analytic estimate",
                stacklevel=2,
            )
            return
        g_flops = self.metrics.gauge("cost_ledger_flops")
        g_bytes = self.metrics.gauge("cost_ledger_bytes")
        for e in entries:
            self.cost_ledger.record(
                e["kind"], e["key"], flops=e.get("flops"),
                bytes_accessed=e.get("bytes_accessed"),
                transcendentals=e.get("transcendentals"),
                lower_ms=e.get("lower_ms"), error=e.get("error"),
            )
            labels = {"program": e["kind"], "key": e["key"]}
            if e.get("flops") is not None:
                g_flops.set(e["flops"], labels=labels)
            if e.get("bytes_accessed") is not None:
                g_bytes.set(e["bytes_accessed"], labels=labels)

    def _read_chunk_counts(self):
        """CapacityModel reader: the chunk_ms histogram's label-summed
        per-bucket counts (tp cells included — the window is over every
        chunk this server ran)."""
        cell = self._h_chunk_ms.cell_total()
        if cell is None:
            return (0,) * len(self._h_chunk_ms.buckets)
        return tuple(cell["counts"])

    def _read_device_tokens(self):
        """CapacityModel reader: cumulative device tokens the boundaries
        produced (decode + prefill — both are slot-steps of real work)."""
        flat = self.metrics.counters_flat()
        return flat.get("decode_tokens_total", 0) + flat.get(
            "prefill_tokens_total", 0
        )

    def _attribute_chunk(self, dt_ms: float) -> None:
        """Split one boundary's measured wall time across the resident
        slots (obs/cost.py rule; shares sum to exactly ``dt_ms`` —
        conservation, gated by ``obs.cost check``) and fold each share
        into its request's accumulators. MUST run before the boundary's
        finished results are completed so a request's final chunk still
        lands on its result."""
        shares = obs_cost.attribute_chunk(
            self.cost_ledger, dt_ms, self.engine.last_boundary
        )
        if not shares:
            return
        d_tokens = p_tokens = 0
        for entry, share_ms, flops in shares:
            d_tokens += entry.get("decode_tokens", 0)
            p_tokens += entry.get("prefill_tokens", 0)
            tag = entry.get("tag")
            if isinstance(tag, Pending):
                tag.device_ms += share_ms
                tag.cost_flops += flops
                tag.decode_tokens += entry.get("decode_tokens", 0)
                tag.prefill_tokens += entry.get("prefill_tokens", 0)
        with self._stats_lock:
            self._c_attr_ms.inc(dt_ms)
            if d_tokens:
                self._c_decode_tokens.inc(d_tokens)
            if p_tokens:
                self._c_prefill_tokens.inc(p_tokens)

    def _tick_cost(self) -> None:
        if self.capacity is not None:
            self.capacity.tick()

    def _costz(self) -> dict:
        """/costz payload: the program price sheet, the attribution
        totals, and the live capacity state — all host dict reads."""
        out: dict = {"enabled": self.cost_enabled}
        if not self.cost_enabled:
            return out
        flat = self.metrics.counters_flat()
        out["ledger"] = self.cost_ledger.entries()
        out["compile_ms"] = self.cost_ledger.compile_times()
        out["attribution"] = {
            "attributed_ms_total": round(
                flat.get("attributed_ms_total", 0), 3
            ),
            "decode_tokens_total": flat.get("decode_tokens_total", 0),
            "prefill_tokens_total": flat.get("prefill_tokens_total", 0),
            "flops_per_decode_step": self.cost_ledger.flops_per_decode_step(),
            "flops_per_prefill_token":
                self.cost_ledger.flops_per_prefill_token(),
        }
        if self.cfg.spec_depth:
            out["attribution"]["flops_per_spec_round"] = (
                self.cost_ledger.flops_per_spec_round()
            )
        out["capacity"] = self.capacity.state()
        out["profile"] = {
            "dir": self.cfg.profile_dir,
            "pending_chunks": self._profile_pending,
            "active_chunks_left": self._profile_left,
            "last_artifact": self._profile_path,
        }
        return out

    # -- on-demand profiling (ISSUE 15) ---------------------------------------

    def arm_profile(self, chunks: int) -> dict:
        """Arm a ``jax.profiler`` trace capture for the next ``chunks``
        chunk boundaries. This only SETS host flags (callable from the
        /profilez scrape thread); the profiler itself starts and stops
        on the scheduler thread at boundaries. One capture at a time;
        refused (409) when disabled or already armed/active."""
        if not self.cfg.profile_dir:
            return {"error": "profiling disabled: set ServeConfig."
                             "profile_dir (--profile-dir)", "code": 409}
        try:
            chunks = int(chunks)
        except (TypeError, ValueError):
            return {"error": f"bad chunks={chunks!r}", "code": 400}
        if chunks <= 0:
            return {"error": f"chunks must be >= 1, got {chunks}",
                    "code": 400}
        with self._stats_lock:
            if self._profile_pending or self._profile_left:
                return {"error": "a profile capture is already armed or "
                                 "active", "code": 409}
            self._profile_pending = chunks
        self.flight.record("profile", event="armed", chunks=chunks)
        return {"armed": chunks, "dir": self.cfg.profile_dir}

    def _profilez(self, params: dict) -> dict:
        # registered as the /profilez provider (banned-sync hook scope):
        # pure flag-setting — arm_profile owns the str->int parse and
        # every refusal path, nothing here can touch a device
        return self.arm_profile(params.get("chunks", 8))

    def _profile_maybe_start(self) -> None:
        """Scheduler thread, before the boundary's timed window: consume
        a pending arm and start the capture (the start cost must not be
        billed as chunk latency; the K profiled chunks' overhead lands
        in chunk_ms honestly)."""
        if not self._profile_pending or self._profile_left:
            return
        with self._stats_lock:
            if not self._profile_pending or self._profile_left:
                return
            chunks, self._profile_pending = self._profile_pending, 0
            # reserve the capture BEFORE start_trace returns: arm_profile
            # checks _profile_left under this lock, so a /profilez racing
            # the (milliseconds-long) profiler init still gets its 409
            # instead of silently queueing a second capture
            self._profile_left = chunks
        import os as _os

        import jax.profiler as _profiler

        self._profile_seq += 1
        path = _os.path.join(
            self.cfg.profile_dir,
            f"profile-{self._rid_token}-{self._profile_seq}",
        )
        try:
            _os.makedirs(path, exist_ok=True)
            _profiler.start_trace(path)
        except Exception as e:
            with self._stats_lock:
                self._profile_left = 0  # release the reservation
            warnings.warn(f"profiler start failed: {e}", stacklevel=2)
            self.flight.record("profile", event="start_failed",
                               error=type(e).__name__)
            return
        self._profile_path = path
        self.flight.record("profile", event="start", chunks=chunks,
                           dir=path)
        # from here to the stop every phase span is ALSO a host event of
        # the capture, on the device trace's clock; the instant says which
        # boundaries of the ring the capture holds (the first is the one
        # about to step), so one span present in both gives the offset
        # between the two clocks
        from orion_tpu.utils.profiling import annotate

        self.trace.annotate = annotate
        self.trace.instant("profile_start", chunk_seq=self._chunk_seq + 1,
                           chunks=chunks, path=path)

    def _profile_maybe_stop(self, force: bool = False) -> None:
        """Scheduler thread, after a boundary (or on drain with
        ``force`` — a capture must never outlive the loop that armed
        it): count the boundary down and close the artifact.

        The lock-free fast-path read keeps the idle boundary cost at
        one attribute load; the countdown itself happens under the
        stats lock (``_profile_left`` is declared guarded-by it) with a
        re-check, so a concurrent drain and a boundary can never both
        take the stop path. ``stop_trace`` stays OUTSIDE the lock —
        same rule as ``start_trace`` on the arm side."""
        if not self._profile_left:
            return
        with self._stats_lock:
            if not self._profile_left:
                return  # the other caller already took the countdown
            self._profile_left -= 1
            if self._profile_left > 0 and not force:
                return
            self._profile_left = 0
        import jax.profiler as _profiler

        self.trace.annotate = None
        self.trace.instant("profile_stop", chunk_seq=self._chunk_seq,
                           path=self._profile_path)
        try:
            _profiler.stop_trace()
        except Exception as e:
            warnings.warn(f"profiler stop failed: {e}", stacklevel=2)
            self.flight.record("profile", event="stop_failed",
                               error=type(e).__name__)
            return
        self.flight.record("profile", event="stop", dir=self._profile_path,
                           forced=bool(force))

    def _on_health(self, old, new, reason: str) -> None:
        """HealthMachine transition tap (runs AFTER the machine released
        the shared lock): black-box record + counter, and the flight
        recorder's auto-dump triggers — DEGRADED (something engaged the
        ladder / stalled), DRAINING (SIGTERM drain), DEAD."""
        self.flight.record(
            "health", frm=old.value if old else None, to=new.value,
            reason=reason,
        )
        self._c_health.inc(labels={"to": new.value})
        if new is Health.DRAINING:
            # anchor the drain budget: a drain holding dirty sessions
            # through a store outage spends at most this long retrying
            self._drain_deadline = self._clock() + self.cfg.grace
        if new in (Health.DEGRADED, Health.DRAINING, Health.DEAD):
            self.flight.dump(f"health-{new.value}")

    def _on_engine_event(self, kind: str, fields: dict) -> None:
        """SlotEngine tap: admissions, resumes, prefill pieces, ladder
        rungs, evictions — recorded to the flight ring (tag swapped for
        the request's trace id) and folded into the registry."""
        if kind == "phase":
            # an edge inside engine.step: close the engine phase that is
            # open, open the next (the engine knows no tracer; these two
            # events are its whole part in the spans)
            if self._engine_phase is not NULL_SPAN:
                if fields.get("ladder"):
                    self._engine_phase.note(ladder=True)
                self._engine_phase.__exit__(None, None, None)
                self._engine_phase = self._phase(
                    "serve." + fields["name"]).__enter__()
            return
        tag = fields.pop("tag", None)
        rid = getattr(tag, "rid", None)
        if rid is not None:
            fields["req"] = rid
        if kind == "first_launch":
            self._first_launch(fields)
            return
        if kind == "spec_round":
            # totals every round; the flight ring records only rounds
            # with draft REJECTIONS (each is a rewind-shaped event — the
            # carry clamped at the accepted prefix) so the black box
            # keeps signal, not a per-round heartbeat
            self._c_spec_accepted.inc(fields.get("accepted", 0))
            self._c_spec_rejected.inc(fields.get("rejected", 0))
            if fields.get("rejected", 0):
                self.flight.record("spec_reject", **fields)
            return
        self.flight.record(kind, **fields)
        if kind == "spec_floor":
            self._c_spec_floors.inc()
            self.trace.instant("spec_floor", id=rid,
                               slot=fields.get("slot"),
                               accept=fields.get("accept_ewma"))
            return
        if kind == "evict" and fields.get("spec_drafted", 0):
            # per-turn acceptance: one observation per request that
            # actually speculated — the histogram the SLO engine can
            # window to see acceptance collapse
            self._h_spec_accept.observe(
                fields["spec_accepted"] / fields["spec_drafted"]
            )
        if kind == "ladder":
            self._c_ladder.inc(labels={"rung": fields.get("rung", "?")})
            self.trace.instant("ladder", id=rid, rung=fields.get("rung"),
                               slot=fields.get("slot"))
        elif kind in ("admit", "resume"):
            self.trace.instant(kind, id=rid,
                               session=fields.get("session"),
                               slot=fields.get("slot"))
        elif kind == "prefix_hit":
            self._c_prefix_hits.inc()
            self.trace.instant("prefix_hit", id=rid,
                               prefix_len=fields.get("prefix_len"),
                               suffix=fields.get("suffix"))
        elif kind == "prefix_miss":
            self._c_prefix_misses.inc()
        elif kind == "prefix_publish":
            self._c_prefix_publishes.inc()
        elif kind == "prefix_drop":
            # the bounded publish queue shed a novel prefix during a
            # store outage: a counted drop (a later cold prefill), never
            # a correctness event
            self._c_prefix_drops.inc()

    # -- admission ------------------------------------------------------------

    def submit(self, request: DecodeRequest) -> Pending:
        """Admit a request or refuse loudly: RejectedError when draining/
        dead, OverloadError when the bounded queue is full (shed — the
        caller retries elsewhere; an unbounded backlog would just convert
        overload into deadline misses later)."""
        if request.deadline_ms <= 0 and self.cfg.deadline_ms > 0:
            request = dataclasses.replace(
                request, deadline_ms=self.cfg.deadline_ms
            )
        # normalize the prompt to a HOST array on the submit thread: the
        # scheduler — and the prefix cache's content hashing — must never
        # pay a device readback for token bytes on the admission path
        request = dataclasses.replace(
            request, prompt=np.asarray(request.prompt, np.int32)
        )
        pending = Pending(
            request, threading.Event(), admitted_at=self._clock()
        )
        with self._admission_lock:
            if not self.health.accepting:
                self._bump("rejected")
                raise RejectedError(f"server is {self.health.state.value}")
            self._rid_seq += 1
            pending.rid = (
                f"{request.session_id}:{self._rid_token}.{self._rid_seq}"
                if request.session_id is not None
                else f"req-{self._rid_token}.{self._rid_seq}"
            )
            # the request-lifecycle root span + its queue-wait child
            # open BEFORE the enqueue: the serve loop may pop the
            # Pending (and emit the matching end events) the instant
            # put_nowait returns — begins recorded after that would
            # timestamp after their own ends. ``first_token`` (submit ->
            # the end of the boundary that emitted the first tokens)
            # opens with them. A shed request closes all three right
            # here, so pairing stays complete on every path.
            self.trace.begin("request", pending.rid,
                             session=request.session_id)
            self.trace.begin("queue", pending.rid)
            self.trace.begin("first_token", pending.rid)
            try:
                # SLO actuation, admission half: while the fast-burn
                # alert is sustained the effective queue bound HALVES —
                # a replica that is already missing its latency
                # objective must not absorb a deep backlog whose tail is
                # all deadline misses; shedding earlier pushes the
                # router's failover to a healthy peer NOW
                if (self._slo_shedding and self._q.qsize()
                        >= max(1, self.cfg.max_inflight // 2)):
                    raise queue.Full
                self._q.put_nowait(pending)
            except queue.Full:
                self._bump("shed")
                self.trace.end("queue", pending.rid)
                self.trace.end("first_token", pending.rid, status="shed")
                self.trace.end("request", pending.rid, status="shed")
                why = (
                    "slo fast burn: shedding at half the admission bound"
                    if self._slo_shedding
                    else f"admission queue full ({self.cfg.max_inflight} "
                         f"queued + up to {self.cfg.slots} resident in "
                         "slots)"
                )
                raise OverloadError(why) from None
        self._bump("admitted")
        return pending

    # -- serve loop -----------------------------------------------------------

    def serve(
        self,
        drain_when_idle: bool = False,
        guard: Optional[PreemptionGuard] = None,
    ) -> int:
        """Run the serve loop. Returns 0 on a graceful exit: either a
        SIGTERM-initiated drain completed (health ends DEAD) or
        ``drain_when_idle`` found the queue empty (health stays SERVING —
        callers may submit and serve again; ``close()`` finalizes).

        ``guard``: an already-installed PreemptionGuard to poll instead of
        installing one per serve() call — the CLI passes its whole-
        lifecycle guard so a SIGTERM during submission (between waves)
        still maps to a drain instead of the default kill."""
        cfg = self.cfg
        wd = None
        if cfg.stall_timeout > 0:
            wd = Watchdog(
                cfg.stall_timeout, on_stall=self._on_stall, monitor=True,
                label="serve loop", observer=self._on_wd,
            )
        with contextlib.ExitStack() as stack:
            if guard is None:
                guard = stack.enter_context(
                    PreemptionGuard(grace=cfg.grace, clock=self._clock)
                )
            self._guard = guard
            # black-box the serve lifetime: every delivered fault (any
            # inject site) leaves a ring event, detached on exit so a
            # test that builds many servers doesn't accrete observers
            self.flight.attach_inject()
            stack.callback(self.flight.detach_inject)
            if self.health.state is Health.STARTING:
                self.health.to(Health.SERVING, "serve loop running")
            clean_exit = False
            try:
                # the scheduler: admit queued requests into free slots,
                # advance every resident slot one chunk, complete the
                # finished — all at chunk-boundary granularity. DRAINING
                # still admits the already-queued backlog (PR 4's drain
                # contract: in-flight AND admitted requests complete);
                # only submit() is closed.
                while self._serve_once(wd, guard, drain_when_idle):
                    pass
                clean_exit = True
            finally:
                if not clean_exit:
                    # the loop RAISED mid-chunk (device OOM, runtime
                    # error): keep the done-exactly-once contract
                    # _run_one's finally used to give — a Pending whose
                    # event never fires hangs its caller forever. Resident
                    # slots complete as 'failed' with their partial
                    # tokens; still-QUEUED Pendings are rejected loudly
                    # (the loop that would have served them is dead).
                    for pending, result in self.engine.drain_evict_all(
                        "failed"
                    ):
                        self._complete(pending, result)
                    self._reject_leftovers()
                if wd is not None:
                    wd.close()
                if self.cfg.profile_dir:
                    # a capture armed mid-drain must not outlive the loop
                    self._profile_maybe_stop(force=True)
                self._guard = None
                # under the admission lock: once DEAD is published, no
                # submit can slip a Pending into the dead queue (and any
                # that landed between the loop's last empty-check and
                # here is rejected, its done event set)
                with self._admission_lock:
                    self._maybe_drain(guard)
                    if self.health.state is Health.DRAINING:
                        self._reject_leftovers()
                        if self._dirty_sessions:
                            # the grace window ran out with saves still
                            # failing: NEVER drop turns silently — name
                            # the sessions whose last turn exists only
                            # in this process's memory, in the warning,
                            # the flight ring, and the DEAD dump below.
                            # The exit code stays 0: the drain itself
                            # completed; data at risk is an operator
                            # page, not a crash.
                            lost = sorted(self._dirty_sessions)
                            self.flight.record(
                                "drain_dirty", count=len(lost),
                                sessions=lost[:16],
                            )
                            warnings.warn(
                                f"drain exiting with {len(lost)} dirty "
                                f"session(s) unsaved: {lost[:16]} — the "
                                "store outage outlasted the grace "
                                "window; their last turn is lost if "
                                "this process's memory goes away",
                                stacklevel=2,
                            )
                        self.health.to(Health.DEAD, "drained")
                # exposition on the way out, whatever the exit path:
                # final metrics scrape + the trace file's tail (both
                # host-side, both OUTSIDE the timed chunk walk)
                self._tick_metrics(force=True)
                self.trace.flush()
        return 0

    def _serve_once(self, wd, guard, drain_when_idle: bool) -> bool:
        """One iteration of the serve loop (False ends it): tick, admit,
        then either step the engine one chunk or, with nothing resident,
        wait on the queue. An iteration that steps is one BOUNDARY; its
        ``serve.boundary`` span and the tick and admit phases inside it
        reach the Tracer ring only then, so an idle server polling its
        queue writes nothing that could age requests out of the ring."""
        cfg = self.cfg
        self._boundary = self._chunk_seq + 1
        with self._phase("serve.boundary", record=False) as boundary:
            with self._phase("serve.tick", record=False) as tick:
                self._maybe_drain(guard)
                draining = self.health.state is Health.DRAINING
                if draining:
                    # durable sessions don't hold the drain hostage:
                    # every resident session slot is SUSPENDED at this
                    # boundary (one O(1) snapshot each, persisted
                    # before the result is released) instead of
                    # decoding its remaining tokens; sessionless
                    # slots drain to completion as always
                    for pending, result in self.engine.suspend_sessions():
                        self._complete(pending, result)
                self._tick_sessions()
                self._tick_store_health()
                self._tick_metrics()
                self._tick_slo()
                self._tick_cost()
            with self._phase("serve.admit", record=False) as admit:
                admitted = self._admit_from_queue(wd)
                admit.note(n=admitted)
                if (self.prefix_store is not None
                        and self.engine.has_pending_prefixes):
                    # miss-path declarations: prefill + publish the
                    # queued shared prefixes (one-time per novel
                    # prefix, outside the admission path). Beat the
                    # watchdog first — the publish is a solo prefill
                    # plus possibly a first-time bucket compile, the
                    # same cost class the admission beat covers; a
                    # healthy replica must not read as stalled for
                    # caching a prefix.
                    if wd is not None:
                        wd.beat("prefix publish")
                    self.engine.publish_pending_prefixes()
            if self.engine.busy:
                tick.write()
                admit.write()
                self._step_chunk(wd, guard, boundary, admitted)
                return True
        if (draining or drain_when_idle) and self._q.empty():
            if not (draining and self._dirty_sessions
                    and self._clock() < self._drain_deadline):
                return False
            # drain mid-outage: DIRTY sessions are the ONLY up-to-date
            # copy of their conversations — hold them resident through
            # the grace window, retrying saves via the breaker's
            # half-open probes (_tick_sessions above), instead of
            # silently dropping turns. The deadline bounds the hold;
            # whatever is still dirty then is reported loudly on the
            # way out.
            time.sleep(min(max(cfg.poll, 0.001), 0.05))
            return True
        wait = self._phase("serve.idle_wait", record=False)
        try:
            with wait:
                pending = self._q.get(timeout=cfg.poll)
        except queue.Empty:
            if self._idle_first is None:
                self._idle_first = wait
            return True
        first, self._idle_first = self._idle_first or wait, None
        if self.trace.enabled:
            # ONE ring event for the idle stretch, however many polls
            # it took (each poll is its own annotation in a capture)
            self.trace.complete(
                "serve.idle_wait", first.start,
                wait.start + wait.dur - first.start, cat="phase",
            )
        with self._phase("serve.admit", n=1):
            self._admit(pending, wd)
        return True

    def _tick_slo(self) -> None:
        """Chunk-boundary SLO evaluation + actuation. Evaluation always
        runs (the burn rates feed /slo, snapshot()['slo'], the router's
        tie-break and the supervisor's respawn trigger); ACTUATION —
        health DEGRADED plus earlier admission shedding — arms only for
        explicitly declared objectives and only after
        ``slo_degrade_ticks`` consecutive boundaries with a fast-burn
        alert firing, so one bad window can't flap the health machine."""
        st = self.slo.tick()
        # availability measures OUR OWN admission decisions (bad events
        # are sheds/rejects), so it must never drive more shedding: a
        # saturated server that sheds at its normal bound would fire the
        # availability burn, halve the bound, shed MORE, and latch
        # half-capacity until offered load drops — a self-sustaining
        # feedback loop. Availability burn still reports (and the router
        # still routes away from it); only ACTUATION excludes it. The
        # supervisor applies the same filter on its side.
        firing = [
            n for n in st["firing_fast"]
            if st["objectives"][n]["kind"] != "availability"
        ]
        if firing:
            self._slo_burn_ticks += 1
            if self._slo_burn_ticks == 1:
                # rising edge: count + black-box the alert
                self._c_slo_alerts.inc(labels={"alert": "fast"})
                self.flight.record(
                    "slo", alert="fast", firing=list(firing),
                    burn=st["worst_burn_fast"],
                )
        else:
            self._slo_burn_ticks = 0
            if self._slo_shedding:
                self._slo_shedding = False
                self.flight.record("slo", alert="clear")
        slow = bool(st["firing_slow"])
        if slow and not self._slo_slow_prev:
            self._c_slo_alerts.inc(labels={"alert": "slow"})
        self._slo_slow_prev = slow
        if (self._slo_actuate
                and self._slo_burn_ticks
                >= max(self.cfg.slo_degrade_ticks, 1)):
            if not self._slo_shedding:
                self._slo_shedding = True
                self.flight.record(
                    "slo", alert="shedding", firing=list(firing),
                )
            self._degrade("slo fast burn: " + ",".join(firing))

    def _tick_metrics(self, force: bool = False) -> None:
        """Periodic metrics exposition at chunk-boundary cadence (and
        forced on drain/exit). Interval <= 0 means on-drain only; a
        failing dump never takes the serve loop down."""
        path = self.cfg.metrics_path
        if not path:
            return
        now = self._clock()
        if not force and (self.cfg.metrics_interval_s <= 0
                          or now < self._metrics_next):
            return
        self._metrics_next = now + max(self.cfg.metrics_interval_s, 1.0)
        try:
            self.metrics.dump(path)
        except OSError as e:
            warnings.warn(f"metrics dump failed: {e}", stacklevel=2)

    def close(self) -> None:
        """Finalize a server whose loop exited idle: reject anything still
        queued, go DEAD, and take the exposition endpoint down (it stays
        up through drains so balancers see 503, not connection refused)."""
        with self._admission_lock:
            self._reject_leftovers()
            if self.health.state is not Health.DEAD:
                self.health.to(Health.DEAD, "closed")
        if self.http is not None:
            self.http.close()
            self.http = None

    # -- scheduler internals --------------------------------------------------

    def _admit_from_queue(self, wd=None) -> int:
        """Move queued requests into free slots (called at every chunk
        boundary — this is where a late arrival joins the running batch);
        returns how many left the queue."""
        n = 0
        while self.engine.has_free_slot:
            try:
                pending = self._q.get_nowait()
            except queue.Empty:
                break
            self._admit(pending, wd)
            n += 1
        # ONE staging dispatch for everything admitted since the last
        # boundary (this loop's and an idle wake-up's), inside serve.admit
        self.engine.flush_admissions()
        return n

    def _admit(self, pending: Pending, wd=None) -> None:
        """Place one Pending into a slot: solo prefill + row insert. A
        request whose whole deadline elapsed in the queue completes as
        'deadline' with zero tokens (no prefill paid); one the engine
        cannot multiplex becomes an error RESULT (isolation) — the batch
        keeps streaming either way."""
        if wd is not None:
            # a cold-start admission burst runs up to `slots` solo
            # prefills (each possibly a fresh bucket compile) before the
            # next chunk beat — without a beat per admission that wait
            # reads as a stall on a healthy replica
            wd.beat("request admission")
        self.trace.end("queue", pending.rid)  # queue wait over, either way
        deadline_at = (
            pending.admitted_at + pending.request.deadline_ms / 1000.0
            if pending.request.deadline_ms > 0
            else None
        )
        if deadline_at is not None and self._clock() >= deadline_at:
            self._complete(pending, DecodeResult(
                tokens=np.zeros((1, 0), np.int32), status="deadline",
                new_tokens=0, chunks=0,
            ))
            return
        try:
            if pending.request.session_id is not None:
                self._admit_session(pending, deadline_at)
            else:
                self.engine.admit(
                    pending.request, tag=pending, deadline_at=deadline_at
                )
        except (OverloadError, StoreUnavailableError) as e:
            # a RETRIABLE shed, never a failure: the turn was refused
            # because the session store is down (a non-resident session
            # needs a disk load nothing can serve right now) or the
            # dirty write-behind backlog is at its bound. Nothing was
            # lost — the conversation's last committed generation is
            # intact wherever it lives — so the caller retries against
            # another replica (one holding the session resident wins)
            # or after recovery.
            pending.error = (
                e if isinstance(e, OverloadError)
                else OverloadError(f"retriable: {e}")
            )
            self._bump("shed")
            self.flight.record("session_shed", req=pending.rid,
                               why=str(e))
            self._finalize(pending, "shed")
        except Exception as e:
            # request isolation: an unadmittable request is an error
            # RESULT, never a dead process (and never a stuck batch) —
            # this is also where a session whose every on-disk generation
            # is corrupt fails ITS request only
            pending.error = e
            self._bump("failed")
            self.flight.record("refused", req=pending.rid,
                               error=type(e).__name__)
            self._degrade(f"request refused: {type(e).__name__}: {e}")
            self._finalize(pending, "error")

    # -- durable sessions -----------------------------------------------------

    def _admit_session(self, pending: Pending, deadline_at) -> None:
        """Route a session-tagged request: resume a suspended session
        (O(1) row insert; empty-prompt continuations are bitwise what one
        longer uninterrupted request would have produced), rebase it when
        the turn carries new prompt tokens (full-history re-prefill), or
        start a fresh session. Raises into :meth:`_admit`'s isolation
        handler on anything unadmittable."""
        request = pending.request
        sid = request.session_id
        if self.session_store is None:
            raise ValueError(
                "request carries a session_id but sessions are disabled "
                "(ServeConfig.session_dir is unset)"
            )
        if self.health.state is Health.DRAINING:
            # queued session turns don't start work during a drain — they
            # come back "suspended" untouched (nothing on disk changes;
            # the client re-submits against the restarted server)
            self._complete(pending, DecodeResult(
                tokens=np.zeros((1, 0), np.int32), status="suspended",
                new_tokens=0, chunks=0,
            ))
            return
        if sid in self._active_sessions:
            raise ValueError(
                f"session {sid!r} is already resident in a slot; one turn "
                "at a time per conversation"
            )
        cap = self.cfg.max_dirty_sessions
        if (cap > 0 and sid not in self._dirty_sessions
                and len(self._dirty_sessions) >= cap):
            # write-behind bound: every turn served during a session-
            # store outage becomes one more DIRTY pin this process could
            # lose on a crash; at the bound, shed retriable instead of
            # growing the at-risk set (sessions ALREADY dirty here keep
            # serving — their risk exists either way, and affinity
            # keeps their turns in order)
            raise OverloadError(
                f"session store not accepting writes and the dirty "
                f"backlog is at its bound ({cap}): retry on another "
                "replica or after the store recovers"
            )
        sess = self._session_lookup(sid)
        if sess is None:  # fresh conversation
            self.engine.admit(
                request, tag=pending, deadline_at=deadline_at, session_id=sid
            )
            self._active_sessions.add(sid)
            return
        prompt = np.asarray(request.prompt, np.int32).reshape(1, -1)
        want = request.max_new_tokens
        try:
            if prompt.shape[1] > 0:
                # new user tokens: rebase the context (original prompt +
                # everything emitted + the new tokens) and re-prefill —
                # O(history); the rng walk stays anchored at the carry's
                # absolute fold index and the session's own seed
                full = np.concatenate(
                    [np.asarray(sess.prompt), np.asarray(sess.emitted), prompt],
                    axis=1,
                )
                self.engine.admit(
                    dataclasses.replace(request, prompt=full),
                    tag=pending, deadline_at=deadline_at, session_id=sid,
                    sample_index=int(sess.emit), seed=int(sess.seed),
                )
            elif sess.buffered >= want:
                # the suspended carry's chunk overshoot already covers
                # this turn: serve it host-side, no slot, no device work —
                # the cheapest continuation there is
                toks = np.asarray(
                    sess.emitted[:, sess.served:sess.served + want]
                )
                sess.served += want
                self._store_session(sess)
                self._complete(pending, DecodeResult(
                    tokens=toks, status="ok", new_tokens=want, chunks=0,
                ))
                return
            else:
                self.engine.resume(
                    sess, request, tag=pending, deadline_at=deadline_at
                )
            self._active_sessions.add(sid)
            self._bump("resumed")
        except Exception:
            # nothing was admitted: the session stays suspended exactly
            # as loaded — put it back in the resident cache
            self._cache_session(sess)
            raise

    def _session_lookup(self, sid: str) -> Optional[SessionState]:
        """Resident cache first (popped while active), then the newest
        intact on-disk generation (corrupt latest falls back inside the
        store; all-corrupt raises — isolated to this request).

        The resident copy is only trusted when it is still the newest
        COMMITTED generation on disk: in a fleet, every replica shares
        one session_dir and a later turn may have landed on a different
        replica — its save makes this replica's cached copy stale, and
        resuming from it would silently fork the conversation. The
        generation check is one directory listing; a DIRTY copy (its
        save failed, so it is newer than anything on disk) stays
        authoritative — the single-writer-per-turn contract the router
        enforces means nobody else could have advanced it."""
        sess = self._sessions.pop(sid, None)
        if sess is not None:
            self._session_last_use.pop(sid, None)
            if self.session_store is None or sid in self._dirty_sessions:
                return sess
            try:
                newest = self.session_store.newest_generation(sid)
            except (StoreUnavailableError, OSError):
                # store outage: the staleness probe cannot run (breaker
                # refusal, or the raw store error that is about to TRIP
                # it — the probe was one breaker sample either way), and
                # the resident copy is the best copy reachable ANYWHERE
                # right now — serve it (outage affinity; the router
                # prefers residency for the same reason). Single-writer-
                # per-turn means a peer can only be ahead if a turn
                # landed there, which the router avoids during outage.
                return sess
            if sess.generation >= newest:
                return sess
            # stale: another replica advanced the conversation on disk
        if self.session_store is None:
            return None
        try:
            return self.session_store.load(sid)
        except OSError as e:
            # a NON-resident session needs a disk read nothing can serve
            # during an outage: surface it as the retriable store refusal
            # (_admit sheds it; the conversation's committed generations
            # are intact wherever the store lives) — an OSError here is
            # store-shaped, unlike a corrupt-payload integrity error,
            # which stays a per-request failure
            raise StoreUnavailableError(
                "session", f"{type(e).__name__}: {e}"
            ) from e

    def _cache_session(self, sess: SessionState) -> None:
        self._sessions[sess.session_id] = sess
        self._sessions.move_to_end(sess.session_id)
        self._session_last_use[sess.session_id] = self._clock()
        cap = max(self.cfg.max_resident_sessions, 1)
        while len(self._sessions) > cap:
            # LRU-evict the oldest CLEAN entry; a dirty one (save failed)
            # is the only up-to-date copy of its conversation — dropping
            # it would silently lose a turn the client already saw, so
            # dirty sessions pin themselves resident until a save lands
            victim = next(
                (s for s in self._sessions if s not in self._dirty_sessions),
                None,
            )
            if victim is None:
                break  # everything dirty: hold memory over losing turns
            self._sessions.pop(victim, None)
            self._session_last_use.pop(victim, None)

    def _store_session(self, sess: SessionState) -> None:
        """Write-through persist + resident-cache refresh. A failed save
        degrades health, marks the session DIRTY (pinned resident,
        re-saved at tick boundaries), and keeps the resident copy so
        in-process continuations still work — never raises into the
        scheduler."""
        self._active_sessions.discard(sess.session_id)
        try:
            if self.session_store is not None:
                self.session_store.save(sess)
                self._bump("session_saves")
            self._dirty_sessions.discard(sess.session_id)
        except StoreUnavailableError:
            # breaker open: refused in O(1) before any disk syscall, and
            # the trip itself already hit the flight ring + health latch
            # — a warning per turn would be outage spam. DIRTY pin; the
            # tick loop's retry rides the breaker's half-open probe.
            self._dirty_sessions.add(sess.session_id)
        except Exception as e:
            warnings.warn(
                f"session {sess.session_id} save failed "
                f"({type(e).__name__}: {e}); keeping the resident copy "
                "dirty — a restart before the next successful save loses "
                "this turn",
                stacklevel=2,
            )
            self._c_store_errors.inc(labels={"store": "session"})
            self._dirty_sessions.add(sess.session_id)
            self._degrade(f"session save failed: {type(e).__name__}")
        self._cache_session(sess)

    def _tick_sessions(self) -> None:
        """Chunk-boundary cache maintenance: retry dirty sessions' saves,
        and drop CLEAN resident entries idle past the timeout (those are
        already on disk — eviction frees host memory, it never loses
        state; dirty entries stay pinned until their save lands).

        The dirty retry RIDES THE BREAKER: while the session breaker is
        open and the probe is not due, the whole sweep is one O(1) host
        check — no disk syscalls, no retry backoff burned on the
        scheduler thread at every boundary. When the probe IS due, the
        first save attempt is the half-open probe: success closes the
        breaker and the same sweep drains the rest of the backlog;
        failure re-opens it (backoff doubled) and the sweep stops at the
        first StoreUnavailableError. Without a breaker (dirty from a
        transient non-outage failure) the old time throttle applies."""
        now = self._clock()
        if self.session_store is not None and self._dirty_sessions:
            br = self.session_store.breaker
            retry_now = now >= self._dirty_retry_at
            if br is not None and br.blocked():
                retry_now = False  # outage confirmed, probe not due
            elif br is not None and br.is_open:
                retry_now = True  # probe due: one save IS the probe
            if retry_now:
                self._dirty_retry_at = now + max(1.0, self.cfg.poll)
                for sid in list(self._dirty_sessions):
                    sess = self._sessions.get(sid)
                    if sess is None or sid in self._active_sessions:
                        continue
                    try:
                        self.session_store.save(sess)
                        self._bump("session_saves")
                        self._dirty_sessions.discard(sid)
                    except StoreUnavailableError:
                        break  # probe failed/refused: stop the sweep now
                    except Exception:
                        continue  # still dirty, still pinned; retry later
        idle = self.cfg.session_idle_s
        if idle <= 0 or not self._sessions:
            return
        for sid in list(self._sessions):
            if sid in self._dirty_sessions:
                continue
            if now - self._session_last_use.get(sid, now) > idle:
                self._sessions.pop(sid, None)
                self._session_last_use.pop(sid, None)

    def _step_chunk(self, wd, guard, boundary=NULL_SPAN,
                    admitted: int = 0) -> None:
        """One engine boundary: watchdog beat, advance all slots a chunk,
        complete whatever finished, refresh the occupancy gauges. The
        boundary's wall time becomes one ``chunk_ms`` observation and —
        with tracing on — one per-resident-slot complete event (the
        per-slot host mirrors say which slots were mid-prefill vs
        decoding; the duration is the shared batched scan's, because the
        per-slot split does not exist on the device). Around them the
        phase spans: ``serve.dispatch`` opens at ``engine.step``'s entry,
        the engine's two edges switch it to ``serve.probe`` and
        ``serve.finish``, and ``serve.complete`` covers the rest."""
        if wd is not None:
            wd.beat("decode chunk")
        self._maybe_drain(guard)
        if self.cfg.profile_dir:
            self._profile_maybe_start()
        occupied = self.engine.active_count
        kv_rows = self.engine.kv_rows()
        kv_attended = self.engine.kv_rows_attended()
        ring_rows = self.engine.ring_rows()
        kv_blocks = self.engine.kv_blocks()
        kv_listed = self.engine.kv_rows_listed() + self.engine.index_piece_pairs()
        infos = self.engine.slot_info() if self.trace.enabled else ()
        t0 = self._clock()
        finished = ()
        self._engine_phase = self._phase("serve.dispatch").__enter__()
        try:
            finished = self.engine.step()
        finally:
            # whichever phase the engine left open (``serve.finish``,
            # or ``serve.dispatch`` still when nothing was resident
            # after the deadline scan, or the step raised)
            last, self._engine_phase = self._engine_phase, NULL_SPAN
            last.note(n=len(finished))
            last.__exit__(None, None, None)
        self._chunk_seq += 1
        # INSIDE the timed window: injected latency lands in chunk_ms
        # (and every resident turn's latency) exactly like a slow scan
        # would — the deterministic address for latency-shaped chaos
        fire("serve.chunk_delay", step=self._chunk_seq)
        t_end = self._clock()
        dt = t_end - t0
        if self.cfg.profile_dir:
            self._profile_maybe_stop()
        with self._phase("serve.complete", n=len(finished)):
            prefilling = decoding = emitting = piece_rows = 0
            for entry in self.engine.last_boundary:
                emitted = entry.get("decode_tokens", 0) > 0
                emitting += emitted
                piece_rows += entry.get("prefill_tokens", 0)
                if entry.get("prefill_tokens", 0) > 0:
                    prefilling += 1
                elif emitted:
                    decoding += 1
                tag = entry.get("tag")
                if (emitted and isinstance(tag, Pending)
                        and not tag.first_token_at):
                    # this boundary's scan emitted the request's first
                    # tokens; its end is when a client could see one
                    self._first_token(tag, t_end)
            if emitting and not self._setup_ready:
                # the first boundary that emitted a token ends set-up
                self._setup_ready = True
                self.trace.instant("setup.ready", "setup",
                                   boundary=self._boundary)
            with self._stats_lock:
                self._bump("chunks")
                staged = self.engine.staging_dispatches
                self._bump("admit_dispatches", staged - self._staged_seen)
                self._staged_seen = staged
                self._bump("slot_steps_active", occupied)
                self._bump("slot_steps_total", self.engine.slots)
                self._bump("slot_steps_prefilling", prefilling)
                self._bump("slot_steps_decoding", decoding)
                self._bump("slot_steps_frozen",
                           occupied - prefilling - decoding)
                writes = emitting * self.engine.state_writes_per_chunk
                for key, rows in zip(_KV_ROW_KEYS, kv_rows + (emitting, writes)):
                    self._bump(key, rows)
                kinds = self.engine.model.cfg.resolved_layer_types
                ssm = kinds.count("ssm")
                self._bump("ssm_row_steps", emitting * self.engine.chunk * ssm)
                self._bump("ssm_piece_rows", piece_rows * ssm)
                self._bump("kv_rows_attended", kv_attended)
                for key, rows in zip(_RING_KEYS, ring_rows):
                    self._bump(key, rows)
                for key, n in zip(_MOE_KEYS, self.engine.moe_rows):
                    self._bump(key, int(n))
                layers = kinds.count("block_sparse")
                live, read, sparse, dense = kv_blocks
                for key, n in zip(
                    _KV_BLOCK_KEYS, (live * layers, read * layers, sparse, dense)
                ):
                    self._bump(key, n)
                for key, n in zip(_KV_LIST_KEYS, kv_listed):
                    self._bump(key, n * kinds.count("indexed"))
                # the tp label makes a fleet's per-footprint boundary cost
                # separable at the aggregated endpoint (a tp=4 replica's
                # chunks cost collectives a tp=1 replica's don't)
                self._h_chunk_ms.observe(dt * 1e3,
                                         labels={"tp": str(self.tp)})
            if self.cost_enabled:
                # attribution BEFORE completing the finished results, so
                # a request's final boundary still lands on its
                # accumulators; dt*1e3 is the SAME value chunk_ms observed
                # — conservation is float-exact per boundary by
                # construction
                self._attribute_chunk(dt * 1e3)
            for i, tag, phase, k in infos:
                self.trace.complete(
                    "decode_chunk" if phase == "decode" else "prefill_piece",
                    t0, dt, req=getattr(tag, "rid", None), slot=i, chunk=k,
                )
            for pending, result in finished:
                self._complete(pending, result)
        boundary.note(steps=self.engine.chunk, resident=occupied,
                      admitted=admitted, finished=len(finished))
        boundary.write()

    def _complete(self, pending: Pending, result: DecodeResult) -> None:
        if result.session is not None:
            # durability before visibility: the session generation is on
            # disk BEFORE the caller can observe these tokens (a crash
            # right after must not unremember a turn the client saw)
            self._store_session(result.session)
        elif pending.request.session_id is not None:
            # a session turn that finished WITHOUT a snapshot (ladder
            # exhausted -> "failed", abnormal-exit eviction): release the
            # conversation so the next turn can resume from the last
            # good on-disk generation — a failed turn must never lock a
            # session out until restart
            self._active_sessions.discard(pending.request.session_id)
        if self.cost_enabled:
            # stamp the attribution totals onto the result the caller
            # sees (shares over this request's boundaries; co-residents'
            # stamps sum to the measured chunk wall time)
            result.device_ms = round(pending.device_ms, 6)
            result.cost_flops = pending.cost_flops
            result.prefill_tokens = pending.prefill_tokens
            result.decode_tokens = pending.decode_tokens
        pending.result = result
        self._bump(result.status)
        self._bump("rewinds", result.rewinds)
        self._bump("reprefills", result.reprefills)
        if result.status == "failed":
            # ladder exhaustion: one of the flight recorder's dump
            # triggers — the black box must capture the rungs that led
            # here before anything else scrolls them off
            self.flight.dump("ladder-exhausted")
        if result.status == "failed" or result.degraded:
            self._degrade(
                f"request needed the ladder (rewinds={result.rewinds}, "
                f"reprefills={result.reprefills}, status={result.status})"
            )
        elif (self.health.state is Health.DEGRADED
              and not self._slo_shedding
              and not self._store_outage_latched()):
            # the SLO latch holds DEGRADED while the burn persists:
            # without the gate, clean-but-slow completions would flap
            # DEGRADED<->SERVING once per request — and every re-entry
            # into DEGRADED writes a fresh flight dump on the scheduler
            # thread, disk I/O that worsens the very latency being
            # alarmed on. Burn clears -> _slo_shedding drops -> the next
            # clean completion recovers as before.
            self.health.to(Health.SERVING, "clean request completed")
        self._finalize(pending, result.status)

    def _first_token(self, pending: Pending, at: float) -> None:
        pending.first_token_at = at
        self.trace.end("first_token", pending.rid, at=at)

    def _finalize(self, pending: Pending, status: str) -> None:
        """The one place a Pending's done event fires: stamps done_at,
        closes the request's trace span, releases the waiter, and runs
        the ``on_done`` tap (the fleet router's root-span close)."""
        pending.done_at = self._clock()
        if not pending.first_token_at:
            # no boundary gave this request a token: it ended first
            # (shed at admission, its deadline gone in the queue,
            # refused, failed, rejected at shutdown) and the stamp stays
            # 0.0 — or a session's buffer answered it without a slot,
            # and its tokens exist as of now
            if pending.result is not None and pending.result.new_tokens > 0:
                pending.first_token_at = pending.done_at
            self.trace.end("first_token", pending.rid, at=pending.done_at,
                           status=status)
        cost_args = {}
        if pending.result is not None:
            # per-turn latency (admission -> release, queue wait
            # included): the SLO engine's primary windowed signal.
            # Rejected-at-shutdown pendings carry no result and record
            # nothing — a drain is not a latency event.
            self._h_turn_ms.observe(
                (pending.done_at - pending.admitted_at) * 1e3
            )
            if self.cost_enabled:
                # per-request cost at the one place done fires: the
                # request_device_ms/request_cost_flops histograms (the
                # SLO engine can window them) and the trace span's args
                # — Perfetto shows what the turn COST, not just how
                # long it waited
                self._h_req_device_ms.observe(pending.device_ms)
                self._h_req_flops.observe(pending.cost_flops)
                cost_args = {
                    "device_ms": round(pending.device_ms, 3),
                    "cost_flops": round(pending.cost_flops, 1),
                    "decode_tokens": pending.decode_tokens,
                    "prefill_tokens": pending.prefill_tokens,
                }
        self.trace.end("request", pending.rid, status=status,
                       session=pending.request.session_id, **cost_args)
        pending.done.set()
        cb = pending.on_done
        if cb is not None:
            try:
                cb(pending)
            except Exception:
                pass  # telemetry must never break completion

    def occupancy(self) -> float:
        """INSTANTANEOUS slot utilization: the fraction of slots holding
        a live request right now, straight from the engine's host-side
        gauges. This is what a load balancer wants — the old behaviour
        (a lifetime average that still read 0.9 on a server that went
        idle an hour ago) lives on as :meth:`occupancy_lifetime`."""
        occ = self.engine.occupancy()
        return occ["active"] / occ["slots"] if occ["slots"] else 0.0

    def occupancy_lifetime(self) -> float:
        """Lifetime fraction of slot-chunks that carried a live request
        (1.0 = perfectly packed) — the continuous-batching utilization
        figure the serving bench reports."""
        with self._stats_lock:
            flat = self.metrics.counters_flat()
            total = flat.get("slot_steps_total", 0)
            return flat.get("slot_steps_active", 0) / total if total else 0.0

    def snapshot(self) -> dict:
        """Health + scheduler gauges in one payload (the /healthz body).

        ONE lock acquisition covers the whole read — the health machine
        shares the server's stats lock, so the health state, the stats
        dict, and the prefilling/decoding slot counts are a consistent
        instant: a fleet router acting on this payload never routes on a
        torn (health, occupancy) pair."""
        with self._stats_lock:
            snap = self.health.snapshot()
            snap["stats"] = dict(self.stats)
            snap["occupancy"] = self.occupancy_lifetime()  # RLock: nested
            snap["occupancy_now"] = self.occupancy()
            snap["slots"] = self.engine.occupancy()
            snap["sessions"] = {
                "resident": len(self._sessions),
                "in_slots": len(self._active_sessions),
                "dirty": len(self._dirty_sessions),
                # the ids ride the status op for the router's outage
                # affinity: a session-carrying turn during a store
                # outage must land on the replica already holding that
                # session resident (anywhere else is a guaranteed shed).
                # Bounded by max_resident_sessions, so the payload is.
                "resident_ids": list(self._sessions),
            }
            snap["queued"] = self._q.qsize()
            # the SLO state rides the snapshot so the fleet layer can
            # act on burn rates over the EXISTING status op: the
            # router's latency tie-break and the supervisor's
            # persistent-fast-burn respawn both read this section.
            # state() is the last tick's payload — no reader runs here,
            # so the slo lock nests under the stats lock without a
            # cycle (tick() never holds its lock while taking ours).
            # "actuate" carries the declared-objectives bit: the
            # supervisor must not drain-respawn on the observe-only
            # defaults' burn any more than the server itself sheds on
            # them.
            snap["slo"] = dict(self.slo.state(), actuate=self._slo_actuate)
            if self.capacity is not None:
                # the live ceiling/headroom ride the snapshot so the
                # fleet layer (and the future autoscaler) read them over
                # the EXISTING status op; state() is the last tick's
                # payload — no reader runs here
                snap["capacity"] = self.capacity.state()
            # the full registry rides along so a fleet supervisor can
            # aggregate child registries over the existing status op
            snap["metrics"] = self.metrics.snapshot()
        return snap

    def _maybe_drain(self, guard) -> None:
        if guard is not None and guard.should_stop and self.health.state in (
            Health.STARTING, Health.SERVING, Health.DEGRADED
        ):
            self.health.to(
                Health.DRAINING,
                f"signal {guard.signum}: finish in-flight, reject new",
            )

    def _degrade(self, reason: str) -> None:
        if self.health.state is Health.SERVING:
            self.health.to(Health.DEGRADED, reason)

    def _on_wd(self, event: str, detail: str) -> None:
        # watchdog tap: beats + stalls into the black box (the ring is
        # bounded, so per-chunk beats are cheap context, not a leak)
        self.flight.record("watchdog", event=event, detail=detail)
        if event == "stall":
            # a hang is exactly when the black box matters most — PR 9
            # dumped on health transitions, ladder exhaustion and
            # nan-halt, but a StallError detection itself left no
            # artifact (the DEGRADED transition it may cause is
            # suppressed when the server is already degraded). Dump on
            # the tap, before anything scrolls the stall's context off.
            self.flight.dump("watchdog-stall")

    def _on_stall(self, diag: str) -> None:
        # watchdog monitor thread, NOT a signal handler: buffered io is fine
        self._bump("stalls")
        sys.stderr.write(f"[serve] {diag}\n")
        self._degrade(f"watchdog: {diag}")

    def _reject_leftovers(self) -> None:
        while True:
            try:
                pending = self._q.get_nowait()
            except queue.Empty:
                return
            pending.error = RejectedError("server shut down before execution")
            self._bump("rejected")
            self.trace.end("queue", pending.rid)
            self._finalize(pending, "rejected")


__all__ = [
    "Server", "ServeConfig", "Pending", "OverloadError", "RejectedError",
    "load_tokenizer",
]
