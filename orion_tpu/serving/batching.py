"""SlotEngine: slot-multiplexed continuous batching for the decode path.

The one serving engine: serving one request at a time would leave (N-1)/N of
the hardware's batch throughput on the table, and the paper's recurrent
formulation makes the fix cheap: every sequence's decode state is O(1) — a
few (S, z) matrices and fixed-size caches per layer — so a "slot" is nothing
but one ROW of a batched state pytree. No paged KV, no block tables, no
attention-kernel surgery: Orca-style iteration-level scheduling reduces to
row inserts and row evictions on one carry. ``slots=1`` is the solo case.

- **slots** — a fixed number of rows share ONE jitted chunked decode scan
  (``generate.decode_batched_chunk``). The slot count is static, so the
  whole serving lifetime costs one decode compile per (slots, chunk)
  regardless of arrival order; per-slot positions (vector ``t``), per-slot
  rng streams, and the active mask all ride in traced.
- **admission** — at chunk boundaries only, and an O(1) row insert: the
  prompt is STAGED into the carry (padded to its bucket on the host; all
  of a boundary's admissions in ONE donated dispatch,
  ``_stage_rows_carry``) and consumed INSIDE the batched scan
  (``generate.decode_batched_prefill_chunk``) — each boundary runs one
  ``prefill_chunk``-token piece for each waiting slot, up to
  ``slots // chunk`` of them (shortest remaining first; a slot passed
  over too long goes first), each a batch-1 parallel-forward piece, while
  co-resident decoders never wait behind a long prompt (the
  Sarathi-style head-of-line fix, without a scheduler: O(1) state makes
  chunked prefill a mask). There is no other admission path. Against a
  whole-prompt prefill (``generate.prefill_carry``: the ladder's
  re-prefill rung and the prefix store's publish, never admission) the
  staged slot's TOKENS are equal on every pinned seed and its state equal
  to fp32 rounding on XLA:CPU; on the chip, agreement is the cells'
  `correct` tolerance (ROADMAP C12). Mid-stream admission at a nonzero
  position is the normal case, not an edge case.
- **eviction** — a slot is freed at the boundary where its request
  finishes: per-slot EOS (every later token is PAD by construction, so the
  tail is filled host-side, bitwise what the solo scan emits), max-tokens,
  or its deadline. Freed rows keep computing inside the scan (static shape)
  but emit PAD and hold their position.
- **per-slot ladder** — the finite probe is per-SEQUENCE
  (``transformer.decode_state_finite_per_slot``): one poisoned slot walks
  the degradation ladder — rewind (redo the chunk from the boundary
  snapshot: ONE program run twice, so co-resident slots recompute
  bitwise-identical tokens) → re-prefill that request from its prompt +
  emitted tokens → fail THAT request, never the process — while the other
  slots keep streaming. One host sync per chunk attempt, a [slots]-bool
  vector (``_probe_bad``, the decode loop's DESIGNATED sync point;
  analysis rule ``decode-host-sync`` flags any other).
- **deadline** — enforced at chunk granularity against an injectable
  clock, before the chunk is paid for; an expired request returns its
  partial tokens with status ``"deadline"``.
- **fault hooks** — ``inject.fire("serve.chunk", step=boundary)`` at every
  boundary (where chaos tests deliver a real mid-request SIGTERM) and the
  ``decode.state_nan`` / ``decode.slot_nan`` markers consumed after each
  chunk attempt, so every rung of the ladder is deterministically
  reachable.
- **parity with the solo scan** — every device op in the batched body is
  batch-row independent and each slot folds its own request's seed, so N
  multiplexed requests produce the TOKENS of N solo ``generate()`` runs at
  the same seeds (tests/test_batching.py pins this on XLA:CPU for slots
  {2, 4, 8}, greedy and sampled, including late admission; on the chip a
  request's ids can depend on which programs serve it, ROADMAP C12).
- **self-speculation** (ISSUE 13) — with ``spec_depth > 0``, pure-decode
  boundaries run a speculative round instead of the plain chunk: the
  model's own global-linear layers draft up to k tokens per slot
  (``transformer.draft_step``, shadow (S, z), no cache growth) and the
  full model verifies them all in ONE batched piece whose logits are
  BITWISE the plain walk's (``transformer.verify_step``), so emitted
  tokens never change — only ms/tok does. Accepted counts ride the
  per-boundary probe transfer; a per-slot rolling-acceptance floor
  (``spec_min_accept``) drops losing slots back to plain decode; the
  ladder, sessions, and qmode contracts all re-pin under speculation
  (tests/test_spec_decode.py).

The engine owns no threads and installs no handlers; the Server drives it
from its scheduler loop and maps finished slots back onto Pendings.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import operator
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from orion_tpu.generate import (
    SampleConfig,
    bucket_for,
    decode_batched_chunk,
    decode_batched_prefill_chunk,
    decode_boundary_donated,
    decode_batched_spec_round,
    prefill_carry,
    prefill_overdue_after,
    prefill_piece_cap,
    reprefill_carry,
)
from orion_tpu.models.mixers import MIXERS
from orion_tpu.models.moe import STAT_NAMES
from orion_tpu.models.transformer import (
    decode_state_finite_per_slot,
    extract_decode_slot,
    init_decode_state,
    insert_decode_slot,
    linear_layer_indices,
    snapshot_decode_state,
)
from orion_tpu.ops.dispatch import (
    cache_copy_nbytes,
    resolve,
    resolve_chunk,
    row_sparse,
)
from orion_tpu.resilience import inject
from orion_tpu.resilience.breaker import StoreUnavailableError
from orion_tpu.serving.session import DecodeRequest, DecodeResult
from orion_tpu.serving.session_store import SessionState

Array = jax.Array

# XLA-CPU executes a multi-device program by rendezvousing one thread per
# device at each collective. Two mesh engines in ONE process (LocalReplica
# fleets over shared virtual devices) launching collective programs
# concurrently can interleave their rendezvous — rank 0 joins replica A's
# all-reduce while rank 1 joins replica B's — and deadlock. Every
# program-launching entry point of a mesh-backed engine therefore
# serializes on this process-wide lock (reentrant: entry points nest
# through the ladder). Unsharded engines never touch it, and in the
# production shape — one server per process (ProcessReplica children own
# their devices) — it is simply uncontended. Declared as `engine.exec`
# in serving/locks.py; the Tier D auditor (`--tier concurrency`) checks
# the engine's slot bookkeeping is only written under it.
_TP_EXEC_LOCK = threading.RLock()


def _serialized(method):
    """Hold the engine's exec guard (the process-wide _TP_EXEC_LOCK for
    mesh engines, a nullcontext otherwise) across a program-launching
    entry point.

    The lock declaration (serving/locks.py `engine.exec`) lists this
    decorator by name: the `with` lives here in the wrapper, not in the
    decorated bodies, so the Tier D auditor seeds decorated methods'
    entry held-set from the declaration instead of seeing the scope."""

    @functools.wraps(method)
    def wrapper(self, *args, **kwargs):
        with self._exec_lock:
            return method(self, *args, **kwargs)

    return wrapper


@jax.jit
def _slot_flags(states, done) -> Array:
    """[2, slots] bool: per-slot finite mask stacked with the done flags —
    the engine's whole per-chunk host readback in ONE device transfer."""
    return jnp.stack([decode_state_finite_per_slot(states), done])


@jax.jit
def _counted_flags(states, done, counted) -> Array:
    """[2 slots + 6] int32: the finite mask, the done flags and the
    boundary's MoE row counters (``counted``: the [6] vectors its programs
    returned, ``models/moe.py::STAT_NAMES``, summed here) — a row-counting MoE
    model's whole host readback, still ONE device transfer a boundary."""
    return jnp.concatenate([
        decode_state_finite_per_slot(states).astype(jnp.int32),
        done.astype(jnp.int32),
        sum(counted),
    ])


@jax.jit
def _spec_flags(states, done, accepted) -> Array:
    """[3, slots] int32: the speculative boundary's whole host readback —
    finite mask, done flags, AND per-slot accepted-draft counts — still
    ONE device transfer per round (the accept/reject decision rides the
    existing probe, never a second readback)."""
    return jnp.stack([
        decode_state_finite_per_slot(states).astype(jnp.int32),
        done.astype(jnp.int32),
        accepted,
    ])


@jax.jit
def _insert_carry(carry, rngs, plen, pfold, sub_carry, rng, i, n_emitted):
    """Row-write one READY solo carry (batch 1: a resumed session's, or the
    ladder's re-prefill) + its rng key into slot ``i`` of the batched
    carry — ONE fused dispatch (a dozen eager ``.at`` updates would cost
    more host time than the row is worth on the scheduler's hot path).
    ``i`` and ``n_emitted`` ride traced: one compile, ever. The slot's
    staged-prompt length is zeroed — a row inserted with a READY carry is
    past its prompt by definition, so the unified in-scan program must
    never treat it as prefilling."""
    token, states, t, emit, done = carry
    tok1, st1, t1, done1 = sub_carry
    new_carry = (
        token.at[i].set(tok1[0]),
        insert_decode_slot(states, st1, i),
        t.at[i].set(t1.astype(jnp.int32)),
        emit.at[i].set(n_emitted.astype(jnp.int32)),
        done.at[i].set(done1[0]),
    )
    return (
        new_carry, rngs.at[i].set(rng), plen.at[i].set(0),
        pfold.at[i].set(n_emitted.astype(jnp.int32)),
    )


# How many admissions ONE staging dispatch writes (:func:`_stage_rows_carry`);
# a boundary that admits more makes more calls of the same program.
STAGE_ROWS = 8
# a packed staging row's columns ahead of its padded prompt: slot (-1 = no
# entry), prompt length, first-token rng-fold index, the rng key's two words
_ROW_HEAD = 5


def _seed_key(seed) -> np.ndarray:
    """The two uint32 words of ``jax.random.PRNGKey(seed)`` (threefry2x32)
    made on the host, where PRNGKey is an eager device program a request.
    jax reads the seed as a C long (the same OverflowError beyond 64 bits,
    the same TypeError for a non-integer) and keeps its low word, and the
    high word too only in 64-bit mode."""
    seed = operator.index(seed)
    if not -(1 << 63) <= seed < 1 << 63:
        raise OverflowError("Python int too large to convert to C long")
    hi = seed >> 32 if jax.config.jax_enable_x64 else 0
    return np.array([hi & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def _host_prompt(prompt) -> np.ndarray:
    """A request's prompt as a host ``[B, T]`` int32 array. The Server's
    requests hold host arrays already (normalised at submit, off the
    scheduler thread), so this is a view; a device array handed in by a
    direct caller is read back once, here."""
    prompt = np.asarray(prompt, np.int32)
    return prompt[None] if prompt.ndim == 1 else prompt


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def _stage_rows_carry(carry, rngs, plen, pfold, pbuf, rows):
    """O(1) in-scan admission of up to ``STAGE_ROWS`` prompts in ONE
    dispatch: for each entry of ``rows`` (``[STAGE_ROWS, _ROW_HEAD +
    width]`` int32, packed on the host by ``SlotEngine._flush_staged``)
    zero the slot's carry row, set its rng key and park its padded prompt
    in the staging buffer — NO prefill runs here and no host sync happens;
    the unified chunk program consumes the prompt ``prefill_chunk`` tokens
    per boundary from inside the batched scan. One compile per
    staged-buffer width, none per prompt length or per count: the loop
    visits the valid entries only (they come first; the rest hold slot -1
    and are never written), in order, so device work is the rows admitted.
    The carry, the per-slot vectors and the staging buffer are DONATED:
    the rows are written in place and the caller's buffers are gone (an
    undonated call copied the whole decode state, 1.6 GB at 64 slots of
    lm_1b3, and cannot run at all beside a KV cache of GBs). Every leaf
    of a row is zeroed, a KV cache's too: rows past a slot's position
    are masked, but the per-slot finite probe reads them."""
    keys = jax.lax.bitcast_convert_type(rows[:, 3:_ROW_HEAD], jnp.uint32)

    def write(k, staged):
        (token, states, t, emit, done), rngs, plen, pfold, pbuf = staged
        i, length, fold = rows[k, 0], rows[k, 1], rows[k, 2]
        states = jax.tree.map(
            lambda x: x.at[i].set(jnp.zeros(x.shape[1:], x.dtype)), states
        )
        new_carry = (
            token.at[i].set(0),
            states,
            t.at[i].set(0),
            emit.at[i].set(fold),
            done.at[i].set(False),
        )
        return (
            new_carry, rngs.at[i].set(keys[k]), plen.at[i].set(length),
            pfold.at[i].set(fold), pbuf.at[i].set(rows[k, _ROW_HEAD:]),
        )

    return jax.lax.fori_loop(
        0, jnp.sum(rows[:, 0] >= 0), write, (carry, rngs, plen, pfold, pbuf)
    )


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def _stage_prefix_carry(carry, rngs, plen, pfold, pbuf, st1, row, rng, i,
                        length, fold, t0):
    """O(suffix) in-scan admission on a prefix-cache HIT: slot ``i`` gets
    the cached prefix's decode-state row (``st1``, batch 1 — the
    ``insert_decode_slot`` snapshot copy that IS the prefix cache) at
    position ``t0 = len(prefix)``, with the FULL padded prompt parked in
    the staging buffer and ``plen`` the full prompt length. The unified
    chunk program consumes from ``t`` onward, i.e. exactly the uncached
    suffix ``prompt[t0:]`` — no new device program, no host sync, one
    fused row write (one entry of :func:`_stage_rows_carry` plus the state
    insert), donated like it."""
    token, states, t, emit, done = carry
    states = insert_decode_slot(states, st1, i)
    new_carry = (
        token.at[i].set(0),
        states,
        t.at[i].set(t0),
        emit.at[i].set(fold),
        done.at[i].set(False),
    )
    return (
        new_carry, rngs.at[i].set(rng), plen.at[i].set(length),
        pfold.at[i].set(fold), pbuf.at[i].set(row),
    )


@jax.jit
def _restart_prefill_row(carry, i):
    """Ladder rung 2 for a slot still MID-prefill: zero its state row and
    rewind its position to 0 so the in-scan prefill replays from scratch
    (deterministic — the final tokens are bitwise what the unfaulted run
    emits, just a few boundaries later). The staged prompt buffer is the
    one known-good input and is left untouched."""
    token, states, t, emit, done = carry
    states = jax.tree.map(
        lambda x: x.at[i].set(jnp.zeros(x.shape[1:], x.dtype)), states
    )
    return (
        token.at[i].set(0), states, t.at[i].set(0), emit,
        done.at[i].set(False),
    )


@jax.jit
def _extract_carry(carry, i):
    """Row-read slot ``i`` of the batched carry as the batch-1 sub-carry
    shape :func:`_insert_carry` takes — the suspend half of the durable
    session round trip (insert(extract(i)) is bitwise-identity by
    construction). ``i`` rides traced: one compile, ever. Returns
    (token [1], state batch-1, t [], emit [], done [1])."""
    token, states, t, emit, done = carry
    return (
        jax.lax.dynamic_slice_in_dim(token, i, 1),
        extract_decode_slot(states, i),
        jax.lax.dynamic_index_in_dim(t, i, keepdims=False),
        jax.lax.dynamic_index_in_dim(emit, i, keepdims=False),
        jax.lax.dynamic_slice_in_dim(done, i, 1),
    )


def tree_nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


# The width of ONE slot's in-scan prompt piece where the caller names none
# (``ServeConfig.prefill_chunk`` is this constant too); the engine rounds it
# up to the linear-attention chunk.
PREFILL_CHUNK = 64

_NO_HOST_PREFILL = (
    "host-side prefill at admission is gone: every prompt is staged into "
    "the carry and consumed in-scan, which needs prompt buckets (e.g. "
    "'pow2') and prefill_chunk > 0"
)

# the share of a device's memory left to the boundary programs' own
# temporaries when the engine decides whether the carry fits twice: they
# took 0.8 to 2.3 GB of a v5e's 16.9 in the served configurations' compiles
# (tests/test_chip_compile.py), and a carry that fits twice by 0.3 GB beside
# the weights leaves them nothing (PERF.md section 6, PR 43)
PROGRAM_RESERVE = 0.125


def fits_once_only(carry, params, device) -> bool:
    """Does ``device`` hold the carry once beside the weights, but not
    twice (and the copies the step kernels read of its narrow caches, which
    the kernels' own layer counts: ``ops.dispatch.cache_copy_nbytes``) with
    :data:`PROGRAM_RESERVE` of it left for the programs? From its
    ``memory_stats()["bytes_limit"]``; False where the backend reports
    none. Arrays or their shapes."""
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if not limit:
        return False
    held = 2 * tree_nbytes(carry) + cache_copy_nbytes(carry) + tree_nbytes(params)
    return held > (1 - PROGRAM_RESERVE) * limit


def parse_buckets(spec: str, max_seq_len: int) -> Tuple[int, ...]:
    """``--prefill-buckets`` spec -> sorted bucket lengths. ``"pow2"``:
    powers of two from 16 up to max_seq_len; ``"a,b,c"``: explicit. An
    empty spec or ``"off"`` is refused: the staged buffers' widths are
    the buckets."""
    if not spec or spec == "off":
        raise ValueError(_NO_HOST_PREFILL)
    if spec == "pow2":
        out, b = [], 16
        while b < max_seq_len:
            out.append(b)
            b *= 2
        out.append(max_seq_len)
        return tuple(out)
    buckets = sorted({int(x) for x in spec.split(",") if x.strip()})
    if any(b <= 0 or b > max_seq_len for b in buckets):
        raise ValueError(
            f"prefill buckets must be in (0, max_seq_len={max_seq_len}]: {buckets}"
        )
    return tuple(buckets)


@dataclasses.dataclass
class _Slot:
    """Host-side bookkeeping for one resident request."""

    request: DecodeRequest
    tag: Any
    deadline_at: Optional[float]
    prompt: np.ndarray  # [1, T] int32, host (kept for the re-prefill rung)
    # per-boundary (tokens [S, W], my row, valid count) — the row is NOT
    # sliced at the boundary (that would cost O(slots) device calls per
    # chunk on the scheduler's hot path) but lazily at eviction/
    # re-prefill; the valid count is ``chunk`` for plain boundaries and
    # the accepted prefix + 1 for speculative rounds
    toks: List[Tuple[Array, int, int]]
    n_emitted: int = 0
    chunks: int = 0  # request-local chunk index (fault-hook address)
    # -- self-speculation bookkeeping (host mirrors of the probe row) --
    spec_rounds: int = 0
    spec_accepted: int = 0  # drafts accepted across this slot's rounds
    spec_drafted: int = 0  # drafts proposed (rounds x depth while on)
    # prompt tokens the in-scan prefill has yet to consume (0 = decoding,
    # as a resumed session always is). The host mirror of the device-side
    # ``plen - t`` — deterministic, so no readback is needed to know when
    # a slot starts emitting.
    prompt_remaining: int = 0
    # boundaries this slot waited mid-prompt and was passed over, since
    # admission or its last piece: the unified program's ``pwait`` row
    passed_over: int = 0
    rewinds: int = 0
    reprefills: int = 0
    # -- durable-session bookkeeping (all inert for sessionless requests) --
    session_id: Optional[str] = None
    seed: int = 0  # the PRNGKey seed the slot's rng stream folds from
    # tokens emitted between `prompt` and this turn's insert point (the
    # re-prefill rung needs the FULL history, not just this turn's chunks)
    prior: List[Any] = dataclasses.field(default_factory=list)
    # emitted-but-unserved tokens from the suspended carry's chunk
    # overshoot: a continuation serves these host-side BEFORE decoding,
    # which is what keeps turn boundaries bitwise-transparent
    prefix: Optional[np.ndarray] = None
    target_new: int = 0  # device tokens to decode THIS turn
    # the carry's absolute emit (rng-fold) index at this turn's insert —
    # fold_base + n_emitted is the fold index at any later boundary
    fold_base: int = 0
    served_base: int = 0  # session.served at resume (0 for fresh turns)


class SlotEngine:
    """Fixed-slot batched decode engine. One engine serves many requests
    over its lifetime; all resident requests share one static
    :class:`SampleConfig` (the jitted scan body's static argument — a
    mismatched request must be refused at admission, the Server surfaces
    it as that request's error)."""

    def __init__(
        self,
        model,
        params,
        *,
        slots: int = 8,
        chunk: int = 16,
        clock: Callable[[], float] = time.monotonic,
        prefill_buckets: Optional[Tuple[int, ...]] = None,
        prefill_chunk: int = PREFILL_CHUNK,
        prompt_overflow: str = "error",
        on_event: Optional[Callable[[str, dict], None]] = None,
        prefix_store: Optional[Any] = None,
        spec_depth: int = 0,
        spec_min_accept: float = 0.0,
        mesh: Optional[Any] = None,
    ):
        assert slots > 0, slots
        assert chunk > 0, chunk
        assert prompt_overflow in ("error", "clamp"), prompt_overflow
        self.model = model
        # tensor-parallel serving (ISSUE 14): with a mesh, the params are
        # placed by the training sharding rules (heads/hidden on tp,
        # wo/down psum-at-output) and the decode state shards on the
        # head dimension — the SAME four jit wrappers then run under
        # GSPMD, which inserts the two per-block all-reduces per step
        # (golden decode_batched_tp{2,4}). Emitted tokens are pinned
        # BITWISE the unsharded engine's; the per-slot carry vectors
        # stay replicated so admission, ladder snapshots, and session
        # suspend/resume remain plain row operations on any footprint.
        self.mesh = mesh
        self.tp = int(mesh.shape.get("tp", 1)) if mesh is not None else 1
        # see _TP_EXEC_LOCK: collective-program launches from co-resident
        # mesh engines must not interleave their device rendezvous
        self._exec_lock = (
            _TP_EXEC_LOCK if mesh is not None else contextlib.nullcontext()
        )
        if mesh is not None:
            from orion_tpu.parallel.decode import (
                mesh_model,
                place_decode_params,
            )

            self.model = model = mesh_model(model, mesh)
            params = place_decode_params(params, mesh)
        self.params = params
        self.slots = int(slots)
        self.chunk = int(chunk)
        self._clock = clock
        # self-speculative decode (ISSUE 13): at pure-decode boundaries
        # the model's own global-linear sublayers draft up to spec_depth
        # tokens per slot and the full hybrid verifies them in ONE
        # batched piece — emitted tokens stay BITWISE the plain walk's
        # (verification re-samples from the full model's logits at the
        # same rng folds), so speculative and plain boundaries compose
        # freely. spec_min_accept > 0 arms the per-slot adaptive floor:
        # a slot whose rolling acceptance drops below it falls back to
        # plain decode instead of paying a losing draft.
        self.spec_depth = int(spec_depth)
        self.spec_min_accept = float(spec_min_accept)
        if self.spec_depth:
            cfg_ = model.cfg
            if self.spec_depth < 1:
                raise ValueError(f"spec_depth must be >= 0: {spec_depth}")
            if not linear_layer_indices(cfg_):
                raise ValueError(
                    "self-speculative decode drafts with the model's "
                    "global-linear layers; this config has none "
                    f"(layer_types={cfg_.resolved_layer_types})"
                )
            if cfg_.n_experts > 0:
                raise ValueError(
                    "self-speculative decode is dense-model only: MoE "
                    "routing groups tokens across the verify piece's "
                    "batch, so the piece cannot replay the per-token "
                    "walk bitwise"
                )
            if (any(lt == "swa" for lt in cfg_.resolved_layer_types)
                    and self.spec_depth + 1 > cfg_.window):
                raise ValueError(
                    f"spec_depth {self.spec_depth} + 1 exceeds the swa "
                    f"window {cfg_.window}: a round's positions must hit "
                    "distinct ring slots for the clamped advance to "
                    "equal the sequential writes"
                )
        # per-slot rolling acceptance (EWMA) + the speculation enable
        # mask the adaptive floor maintains; both reset at admission
        self._accept_ewma: List[Optional[float]] = [None] * self.slots
        self._spec_on_np = np.ones((self.slots,), bool)
        self._accept_np: Optional[np.ndarray] = None
        # telemetry tap (obs/): called with (kind, fields) at admissions,
        # prefill-piece consumption, ladder rungs, and evictions — every
        # field is a HOST value the scheduler already holds (slot index,
        # chunk ordinal, the tag), so the hook costs dict construction,
        # never a device sync (lint rules decode-host-sync +
        # obs-device-sync gate this). The Server wires it to its flight
        # recorder / metrics registry.
        self._on_event = on_event
        cfg = model.cfg
        # the staged buffers' widths (and the re-prefill rung's and the
        # prefix publish's pad-to lengths): a bounded set, so the unified
        # program's compile key is bounded too
        self.buckets = (
            parse_buckets("pow2", cfg.max_seq_len)
            if prefill_buckets is None else tuple(prefill_buckets)
        )
        if not self.buckets or int(prefill_chunk) <= 0:
            raise ValueError(
                f"{_NO_HOST_PREFILL}; got prefill_buckets="
                f"{self.buckets}, prefill_chunk={prefill_chunk}"
            )
        self.prompt_overflow = prompt_overflow
        # in-scan chunked prefill: admission stages the prompt into the
        # carry and the unified chunk program runs a piece of at most
        # prefill_chunk tokens for each waiting slot, up to slots // chunk
        # a boundary — no prefill call on the host thread, no
        # head-of-line stall. Piece boundaries must land on
        # linear-attention chunk boundaries (the left fold — see
        # ops/linear_attention.py return_zcum): round the knob up.
        # ``chunk_align`` is also the prefix store's entry alignment (a
        # cached state at a non-chunk position could not extend)
        self.chunk_align = resolve_chunk(
            cfg.chunk, cfg.max_seq_len, resolve(cfg.backend)
        )
        self.prefill_chunk = (
            -(-int(prefill_chunk) // self.chunk_align) * self.chunk_align
        )
        # content-addressed prefix cache (serving/prefix_store.py): a hit
        # stages the cached state row at its position and in-scan
        # prefills only the suffix — O(prompt) admission becomes
        # O(suffix). Lookup/publish are hash + disk only on this side;
        # the store owns the (publish-side) serialization syncs.
        self.prefix_store = None
        if prefix_store is not None:
            self.attach_prefix_store(prefix_store)
        self._pending_prefix: List[Tuple[str, Any]] = []  # (key, tokens)
        # the publish queue is BOUNDED: during a store outage novel
        # prefixes keep arriving but nothing drains, and an unbounded
        # queue would hold every queued prompt's token rows in host
        # memory for the whole outage. Beyond the cap the prefix is
        # dropped (a counted drop, surfaced via the prefix_drop event
        # and /statusz) — dropping a CACHE entry costs a later cold
        # prefill, never correctness.
        self.max_pending_prefixes = 32
        self.dropped_prefixes = 0  # lifetime counted drops
        # a row-counting MoE model (``moe.masks_rows``): the MoE row counters
        # of the boundary just probed ([routed, held, busiest expert's, dropped,
        # row tiles visited, experts with a row], summed over its pieces,
        # steps and layers; ``models/moe.py::STAT_NAMES``), read with the
        # probe's own transfer; zeros for every other model
        self.moe_rows = np.zeros((len(STAT_NAMES),), np.int64)
        self._moe_counted: Tuple[Array, ...] = ()
        self._moe_zero: Optional[Array] = None
        self._sample: Optional[SampleConfig] = None  # set by first admit
        self._slots: List[Optional[_Slot]] = [None] * self.slots
        self._chunk_counter = 0  # global boundary index (serve.chunk hook)
        # device carry: (token [S], states, t [S], emit [S], done [S])
        self._carry = (
            jnp.zeros((self.slots,), jnp.int32),
            init_decode_state(cfg, self.slots),
            jnp.zeros((self.slots,), jnp.int32),
            jnp.zeros((self.slots,), jnp.int32),
            jnp.ones((self.slots,), bool),  # free slots are "done"
        )
        # A decode state that the device cannot hold twice beside the
        # weights is DONATED to the boundary programs
        # (``generate.decode_boundary_donated``): updated in place, held
        # once. The price is the ladder:
        # a rewind needs the boundary's snapshot, which donation gives
        # up, so a slot whose state turns non-finite fails its request
        # at that boundary and the others stream on. Decided once, from
        # what the device reports; a device that reports no limit (the
        # CPU) never donates.
        self.donate_carry = fits_once_only(
            self._carry, params, next(iter(self._carry[0].devices()))
        )
        # what that decision weighed (host metadata of the two trees)
        caches = [
            (MIXERS[lt].cache_is_ring(cfg, lt),
             tree_nbytes([st[n] for n in MIXERS[lt].cache_leaves]))
            for lt, st in zip(cfg.resolved_layer_types, self._carry[1])
        ]
        kv_bytes = sum(n for _, n in caches)
        tail_bytes = sum(
            tree_nbytes([st[n] for n in MIXERS[lt].tail_leaves])
            for lt, st in zip(cfg.resolved_layer_types, self._carry[1])
        )
        self.held_bytes = {
            "carry_bytes": tree_nbytes(self._carry),
            "params_bytes": tree_nbytes(params),
            # the carry by kind: the KV caches' leaves (of which the rings',
            # whatever the prompts' lengths), and every other leaf of the
            # per-layer states (recurrent states, conv tails)
            "kv_bytes": kv_bytes,
            "ring_bytes": sum(n for ring, n in caches if ring),
            "state_bytes": tree_nbytes(self._carry[1]) - kv_bytes,
            # of which the short convolutions' tails: a fixed few rows a
            # slot and layer, whatever the prompt
            "tail_bytes": tail_bytes,
        }
        self.state_writes_per_chunk = self._state_writes_per_chunk()
        self._rngs = jnp.tile(
            jax.random.PRNGKey(0)[None], (self.slots, 1)
        )
        # in-scan prefill staging: per-slot real prompt length, first-
        # token rng-fold index, and the padded prompt buffer (allocated
        # lazily at the first staged admission; width = the largest
        # bucket seen, the unified program's prompt_bucket compile key)
        self._plen = jnp.zeros((self.slots,), jnp.int32)
        self._pfold = jnp.zeros((self.slots,), jnp.int32)
        self._pbuf: Optional[Array] = None
        # admissions whose row is not on the device yet: (slot, prompt
        # [T] int32, first rng-fold index, the key's two words), all host
        # values, written by the boundary's one staging dispatch
        # (:meth:`_flush_staged`), which every reader of the carry calls
        # first
        self._staged: List[Tuple[int, np.ndarray, int, np.ndarray]] = []
        # lifetime count of staging dispatches (the K-row program's and
        # the prefix hit's); the Server's ``admit_dispatches`` follows it
        self.staging_dispatches = 0
        self._done_np = np.ones((self.slots,), bool)
        # cost attribution (ISSUE 15): per-boundary host report of what
        # each resident slot DID — work class + token counts, all values
        # the scheduler already holds. The Server splits the boundary's
        # measured wall time across these entries (obs/cost.py); rebuilt
        # at every step(), read immediately after, never on the device.
        self.last_boundary: List[dict] = []
        # program kinds whose first launch was announced (the owner's
        # ``setup.first_launch`` span); unified keys include the
        # staged-buffer width — a wider bucket is a new program
        self._compile_seen: set = set()
        # AOT warm start (serving/exec_store.py): per-key deserialized
        # executables installed in place of the jit wrappers, and the
        # keys already consulted (one store lookup per program per
        # engine lifetime — a miss means this engine compiles the
        # program exactly once, so miss == fallback compile, counted)
        self._exec_store: Optional[Any] = None
        self._exec_qmode = "off"
        self._warm_execs: Dict[Any, Any] = {}
        self._warm_checked: set = set()
        if mesh is not None:
            from orion_tpu.parallel.decode import (
                place_decode_carry,
                place_replicated,
            )

            self._carry = place_decode_carry(self._carry, mesh)
            self._rngs = place_replicated(self._rngs, mesh)
            self._plen = place_replicated(self._plen, mesh)
            self._pfold = place_replicated(self._pfold, mesh)

    def _emit(self, kind: str, **fields) -> None:
        if self._on_event is not None:
            self._on_event(kind, fields)

    def attach_prefix_store(self, store) -> None:
        """Wire a :class:`~orion_tpu.serving.prefix_store.PrefixStore`
        (a hit stages the cached state at position t0 and the scan
        consumes the suffix). Requires an entry alignment on this
        engine's linear-attention chunk boundaries."""
        if store.align % self.chunk_align != 0:
            raise ValueError(
                f"prefix store alignment {store.align} is not a multiple "
                f"of the linear-attention chunk {self.chunk_align}: "
                "entries at non-chunk positions cannot extend"
            )
        self.prefix_store = store

    def attach_exec_store(self, store, qmode: str = "off") -> None:
        """Wire an :class:`~orion_tpu.serving.exec_store.ExecStore`:
        each program's FIRST launch consults the store (once per key
        per engine lifetime) and a hit installs the deserialized
        executable in place of the jit wrapper — same program, same
        compiler, bitwise outputs, milliseconds instead of a compile. A
        miss (or any store damage) falls through to jit and is counted
        as the fallback compile it implies; the request path NEVER
        fails here. ``qmode`` names the quantization layout the params
        already carry — part of every executable's content address."""
        self._exec_store = store
        self._exec_qmode = str(qmode or "off")

    def _sample_fp(self) -> str:
        from orion_tpu.serving.exec_store import sample_fingerprint

        return sample_fingerprint(
            self._sample if self._sample is not None else SampleConfig()
        )

    def _warm_boundary_exec(self, kind: str, seen_key) -> Optional[Any]:
        """The warm executable for one boundary program, or None. The
        ident dict is built EXACTLY as ``aot.decode_plan`` keys its
        inventory (Tier E's closed universe) — that equality is what
        makes a warmed footprint hit on all of its programs."""
        if self._exec_store is None:
            return None
        exe = self._warm_execs.get(seen_key)
        if exe is not None or seen_key in self._warm_checked:
            return exe
        self._warm_checked.add(seen_key)
        if kind == "spec_round":
            ident = {"kind": kind, "slots": self.slots,
                     "spec_depth": self.spec_depth,
                     "qmode": self._exec_qmode, "tp": self.tp}
        else:
            ident = {"kind": kind, "slots": self.slots,
                     "chunk": self.chunk, "qmode": self._exec_qmode,
                     "tp": self.tp}
            if kind == "unified_prefill":
                ident["bucket"] = int(self._pbuf.shape[1])
                ident["prefill_chunk"] = self.prefill_chunk
        t0 = time.monotonic()
        exe = self._exec_store.lookup(ident, self._sample_fp())
        if exe is None:
            # one-compile-per-key contract: this miss is exactly one
            # jit compile this engine now pays
            self._exec_store.count_fallback()
            return None
        self._warm_execs[seen_key] = exe
        self._emit("program_warm", program=kind,
                   ms=round((time.monotonic() - t0) * 1e3, 3))
        return exe

    def _warm_prefill_exec(self, bucket: int) -> Optional[Any]:
        """``exec_lookup`` callback for :func:`generate.prefill_carry`:
        the warm bucketed-prefill executable for ``bucket``, or None."""
        if self._exec_store is None:
            return None
        seen_key = ("prefill_bucketed", int(bucket))
        exe = self._warm_execs.get(seen_key)
        if exe is not None or seen_key in self._warm_checked:
            return exe
        self._warm_checked.add(seen_key)
        ident = {"kind": "prefill_bucketed", "bucket": int(bucket),
                 "qmode": self._exec_qmode, "tp": self.tp}
        t0 = time.monotonic()
        exe = self._exec_store.lookup(ident, self._sample_fp())
        if exe is None:
            self._exec_store.count_fallback()
            return None
        self._warm_execs[seen_key] = exe
        self._emit("program_warm", program="prefill_bucketed",
                   ms=round((time.monotonic() - t0) * 1e3, 3))
        return exe

    # -- occupancy ------------------------------------------------------------

    @property
    def active_count(self) -> int:
        return sum(s is not None for s in self._slots)

    @property
    def busy(self) -> bool:
        return self.active_count > 0

    @property
    def has_free_slot(self) -> bool:
        return self.active_count < self.slots

    @property
    def prefilling_count(self) -> int:
        """Slots whose staged prompt is not yet fully consumed."""
        return sum(
            s is not None and s.prompt_remaining > 0 for s in self._slots
        )

    def occupancy(self) -> Dict[str, int]:
        """Slot gauges for health/stats reporting; ``prefilling`` vs
        ``decoding`` splits the active count by slot lifecycle phase."""
        prefilling = self.prefilling_count
        return {
            "slots": self.slots,
            "active": self.active_count,
            "free": self.slots - self.active_count,
            "prefilling": prefilling,
            "decoding": self.active_count - prefilling,
        }

    def _slot_ends(self) -> Dict[int, int]:
        """slot -> its position once its staged prompt is consumed, from the
        host's mirror (prompt consumed + tokens emitted), no readback."""
        return {
            i: s.prompt.shape[1] + sum(p.shape[1] for p in s.prior) + s.n_emitted
            for i, s in enumerate(self._slots) if s is not None
        }

    def _emitting_ends(self, ends: Dict[int, int]) -> List[int]:
        """The positions of the slots that will emit at the boundary about
        to run: a slot emits once its prompt is consumed, at this boundary
        if a piece of it ends the prompt."""
        served = self._selected_prefill_slots([s is not None for s in self._slots])
        piece = self._piece_tokens()
        return [
            end for i, end in ends.items()
            if self._slots[i].prompt_remaining <= (piece if i in served else 0)
        ]

    def kv_rows(self) -> Tuple[int, int, int]:
        """(live, reserved, read) rows of the KV caches, summed over slots:
        a slot reserves the cache's rows (``max_seq_len``, or the window of
        a ring) and holds as many live as its position, from the host's
        mirror of positions (prompt consumed + tokens emitted), no
        readback. ``read`` is what a layer's decode attention streams a
        step at the boundary about to run: every slot's reservation in
        the XLA form; under a row-list backend what the layer's class says
        its kernel streams (``Mixer.cache_rows_read``: the live cache blocks
        of each slot that will emit, at the position its last step attends
        from; the blocks a ``block_sparse`` layer's list holds, :meth:
        `kv_blocks`; a latent layer's live latent blocks) and nothing for
        the others. A window's RING is counted apart (:meth:`ring_rows`): these
        are the rows of a cache that grows with the position. (0, 0, 0) for
        a model without such a layer."""
        cfg = self.model.cfg
        cap, lt = self._longest_cache(ring=False)
        ends = self._slot_ends()
        live = self._live_rows(cap, ends)
        read = cap * self.slots
        # the donated scan reads the cache as it stood at the scan's start;
        # the scan that carries the cache reads the rows it wrote too
        grown = 0 if self.donate_carry else self.chunk
        if (cap and row_sparse(cfg.backend)
                and MIXERS[lt].cache_rows_read(cfg, lt, 0) is not None):
            read = sum(
                MIXERS[lt].cache_rows_read(cfg, lt, min(cap, end + grown))
                for end in self._emitting_ends(ends)
            )
        return live, cap * self.slots, read

    def kv_rows_attended(self) -> int:
        """The live cache rows the boundary about to run attends FROM a step,
        summed over the slots that will emit and counted to the row (no
        block rounding): what :meth:`kv_rows`' ``read`` would be if a kernel
        fetched not one row past a slot's position."""
        cap, _ = self._longest_cache(ring=False)
        return self._rows_attended(cap, [0 if self.donate_carry else self.chunk])

    def _rows_attended(self, cap: int, steps) -> int:
        """Live rows of a cache of ``cap`` rows (``Mixer.cache_rows``: a
        ring's window or a growing cache's reservation) that the slots about
        to emit attend over, to the row, summed over the offsets ``steps``
        past each slot's position: ``min(cap, position)`` whichever kind."""
        return sum(
            min(cap, end + j)
            for end in self._emitting_ends(self._slot_ends()) for j in steps
        )

    def _longest_cache(self, ring: bool) -> Tuple[int, str]:
        """(rows, layer type) of the cached layer kind with the longest
        reservation among the rings, or among the caches that grow, asked of
        the layers' classes; (0, first layer's type) where there is none."""
        cfg = self.model.cfg
        kinds = list(dict.fromkeys(cfg.resolved_layer_types))
        return max(
            ((MIXERS[lt].cache_rows(cfg, lt)
              if MIXERS[lt].cache_is_ring(cfg, lt) == ring else 0, lt)
             for lt in kinds),
            key=lambda c: c[0],
        )

    def _live_rows(self, cap: int, ends: Dict[int, int]) -> int:
        """Rows the resident slots hold live in a cache of ``cap`` rows: the
        positions they have consumed (``ends`` less the prompt still staged),
        at most ``cap`` each."""
        return sum(
            min(cap, end - self._slots[i].prompt_remaining)
            for i, end in ends.items()
        )

    def ring_rows(self) -> Tuple[int, int, int]:
        """(live, reserved, attended) rows of ONE ring layer (a window's
        cache, ``Mixer.cache_is_ring``) at the boundary about to run, from
        the host's mirror of positions: a slot reserves the window and holds
        ``min(position, window)`` of it live, whatever its prompt's length;
        ``attended`` is the live ring rows the slots that will emit attend
        over, to the row, summed over the boundary's ``chunk`` steps (step
        ``j`` of a slot at position ``end`` sees ``min(end + j, window)``; a
        ring is carried through the scan, which reads the rows it wrote).
        Zeros for a model without such a layer."""
        cap, _ = self._longest_cache(ring=True)
        if not cap:
            return 0, 0, 0
        live = self._live_rows(cap, self._slot_ends())
        attended = self._rows_attended(cap, range(1, self.chunk + 1))
        return live, cap * self.slots, attended

    def _state_writes_per_chunk(self) -> int:
        """How many times ONE ``linear`` layer writes an emitting slot's
        ``(S, z)`` row at a boundary's decode scan: once where the layer's
        ``chunk_split`` holds the state (the scan reads it and one flush
        writes it), ``chunk`` times where every step writes (the XLA
        form), 0 for a model without such a layer."""
        kinds = self.model.cfg.resolved_layer_types
        if "linear" not in kinds:
            return 0
        held, _ = jax.eval_shape(
            lambda st, t: MIXERS["linear"].chunk_split(
                self.model.cfg, "linear", st, self.chunk, t, self.donate_carry
            ),
            self._carry[1][kinds.index("linear")], self._carry[2],
        )
        return 1 if "s" in held else self.chunk

    def kv_blocks(self) -> Tuple[int, int, int, int]:
        """(live, read, sparse, dense) for ONE ``block_sparse`` layer at the
        boundary about to run, over the slots that will emit, each at the
        position its last step attends from: the cache blocks the slot holds
        live, the blocks its decode attention lists (all of them under
        ``sparse_dense_len``, ``sparse_topk`` past it), and how many of
        those slots are past / under the switch. Zeros for a model without
        such a layer."""
        cfg = self.model.cfg
        if "block_sparse" not in cfg.resolved_layer_types:
            return 0, 0, 0, 0
        from orion_tpu.models.mixers.block_sparse import blocks_read

        grown = 0 if self.donate_carry else self.chunk
        lengths = [
            min(cfg.max_seq_len, end + grown)
            for end in self._emitting_ends(self._slot_ends())
        ]
        sparse = sum(n > cfg.sparse_dense_len for n in lengths)
        return (
            sum(-(-n // cfg.sparse_block) for n in lengths),
            sum(blocks_read(cfg, n) for n in lengths),
            sparse, len(lengths) - sparse,
        )

    def kv_rows_listed(self) -> Tuple[int, int]:
        """(listed, scored) for ONE ``indexed`` layer at the boundary about to
        run, over the slots that will emit, each at the position its last
        step attends from: the cache rows its decode attention lists (all of
        them under ``index_topk``) and the live rows its indexer scores to
        choose them, which are all the slot holds. Zeros for a model without
        such a layer."""
        cfg = self.model.cfg
        if "indexed" not in cfg.resolved_layer_types:
            return 0, 0
        from orion_tpu.models.mixers.indexed import rows_listed

        grown = 0 if self.donate_carry else self.chunk
        lengths = [
            min(cfg.max_seq_len, end + grown)
            for end in self._emitting_ends(self._slot_ends())
        ]
        return sum(rows_listed(cfg, n) for n in lengths), sum(lengths)

    def index_piece_pairs(self) -> Tuple[int, int]:
        """(visible, selected) (query, key) pairs of ONE ``indexed`` layer in
        the prompt pieces the boundary about to run serves: a piece's query at
        position ``i`` sees ``i + 1`` keys, which its indexer scores, and
        attends to ``min(index_topk, i + 1)`` of them. Zeros for a model
        without such a layer."""
        cfg = self.model.cfg
        if "indexed" not in cfg.resolved_layer_types:
            return 0, 0
        piece, visible, selected = self._piece_tokens(), 0, 0
        ends = self._slot_ends()
        for i in self._selected_prefill_slots([s is not None for s in self._slots]):
            slot = self._slots[i]
            if slot is None or slot.prompt_remaining <= 0:
                continue
            start = ends[i] - slot.n_emitted - slot.prompt_remaining
            n = min(piece, slot.prompt_remaining)
            visible += n * start + n * (n + 1) // 2
            full = max(0, min(n, cfg.index_topk - start))  # rows that list all they see
            selected += full * start + full * (full + 1) // 2 + (n - full) * cfg.index_topk
        return visible, selected

    def slot_info(self) -> List[Tuple[int, Any, str, int]]:
        """Per-resident-slot (index, tag, phase, request-local chunk
        ordinal) — the host-side view the tracer turns into per-chunk
        spans. ``phase`` splits the lifecycle the way the trace taxonomy
        does: ``"prefill"`` while the staged prompt is unconsumed,
        ``"decode"`` after. Pure host bookkeeping, no readback."""
        out = []
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            phase = "prefill" if slot.prompt_remaining > 0 else "decode"
            out.append((i, slot.tag, phase, slot.chunks))
        return out

    # -- admission ------------------------------------------------------------

    def _claim_slot(self, sample) -> int:
        """Shared admission validation: a free slot must exist and the
        request's SampleConfig must match the resident batch's static
        config (the jitted scan body's static argument)."""
        free = [i for i, s in enumerate(self._slots) if s is None]
        if not free:
            raise RuntimeError("no free slot")
        if self._sample is None or not self.busy:
            self._sample = sample
        elif sample != self._sample:
            raise ValueError(
                "request's SampleConfig differs from the resident batch's; "
                "the slot scan's sampling parameters are static per batch"
            )
        # a fresh occupant speculates from a clean slate: the previous
        # request's rolling acceptance must not pre-floor it
        self._accept_ewma[free[0]] = None
        self._spec_on_np[free[0]] = True
        return free[0]

    @_serialized
    def admit(
        self,
        request: DecodeRequest,
        tag: Any = None,
        deadline_at: Optional[float] = None,
        session_id: Optional[str] = None,
        sample_index: int = 0,
        seed: Optional[int] = None,
    ) -> int:
        """Admit ``request`` into a free slot. Without a prefix hit this
        is the HOST half only: the slot is claimed and the prompt joins
        the rows the boundary's one staging dispatch writes
        (:meth:`_flush_staged`); a prefix hit stages its cached row at
        once. No prefill runs here. Raises ValueError for requests the engine cannot multiplex (no
        free slot, batch != 1, over-capacity, or a SampleConfig differing
        from the resident batch's static config); the caller decides
        whether that fails the request or reroutes it.

        ``session_id`` tags the slot for suspension (its final state
        rides out on the DecodeResult); ``sample_index``/``seed`` anchor
        the rng walk for a REBASED session turn — one whose prompt is the
        full context (original prompt + everything emitted + new user
        tokens) of a conversation that already folded ``sample_index``
        draws from ``PRNGKey(seed)``."""
        prompt = _host_prompt(request.prompt)
        if prompt.shape[0] != 1:
            raise ValueError(
                f"slot-multiplexed serving takes one sequence per request; "
                f"got a batch of {prompt.shape[0]} (split it into requests)"
            )
        # bucket check (and clamp) FIRST: in clamp mode an over-bucket
        # prompt is cut to the largest bucket that still leaves room for
        # max_new under the cap, so the cap check below sees the prompt
        # that would actually be served
        prompt = self._check_bucket(prompt, request.max_new_tokens)
        cap = self.model.cfg.max_seq_len
        if prompt.shape[1] + request.max_new_tokens > cap:
            raise ValueError(
                f"prompt {prompt.shape[1]} + new {request.max_new_tokens} "
                f"exceeds max_seq_len {cap}"
            )
        i = self._claim_slot(request.sample)
        if session_id is None:
            session_id = request.session_id
        seed = request.seed if seed is None else seed
        key = _seed_key(seed)
        remaining = prompt.shape[1]
        # O(1) in-scan admission: no prefill here — the prompt is staged
        # into the carry and consumed prefill_chunk tokens per boundary
        # inside the batched scan. With a prefix store, a content hit
        # stages the cached state row at its position instead, so the
        # scan consumes only the uncached suffix.
        entry = self._prefix_lookup(request, prompt, tag)
        if entry is not None:
            self._stage_prefix(i, prompt, jnp.asarray(key), sample_index,
                               entry)
            remaining -= entry.t
        else:
            # the host half only: the row waits for the boundary's ONE
            # staging dispatch (:meth:`_flush_staged`)
            self._grow_staging(prompt.shape[1])
            self._staged.append((i, prompt[0], sample_index, key))
            self._queue_prefix_publish(request, int(prompt.shape[1]))
        self._slots[i] = _Slot(
            request=request,
            tag=tag,
            deadline_at=deadline_at,
            prompt=prompt,
            toks=[],
            prompt_remaining=remaining,
            session_id=session_id,
            seed=seed,
            target_new=request.max_new_tokens,
            fold_base=sample_index,
        )
        self._emit(
            "admit", slot=i, tag=tag,
            prompt_len=int(prompt.shape[1]),
            session=session_id,
        )
        return i

    def _check_bucket(self, prompt: np.ndarray, max_new: int) -> np.ndarray:
        """A prompt longer than the largest prefill bucket never reaches
        jit: it is REFUSED with a clean single-request error (default) or
        clamped to the newest tokens of context (``prompt_overflow=
        "clamp"``) — either way the compile cache stays bounded by the
        bucket count. The clamp target is the largest bucket that still
        leaves room for ``max_new`` under max_seq_len (with pow2 buckets
        the largest bucket IS max_seq_len, so clamping to it would just
        trip the capacity check instead of serving the request); if no
        bucket leaves room, the request is refused like the error mode."""
        if bucket_for(prompt.shape[1], self.buckets) is not None:
            return prompt
        if self.prompt_overflow == "clamp":
            cap = self.model.cfg.max_seq_len
            fit = [b for b in self.buckets if b + max_new <= cap]
            if fit:
                return prompt[:, -max(fit):]
            raise ValueError(
                f"prompt length {prompt.shape[1]} exceeds the largest "
                f"prefill bucket {self.buckets[-1]} and no bucket leaves "
                f"room for {max_new} new tokens under max_seq_len {cap}"
            )
        raise ValueError(
            f"prompt length {prompt.shape[1]} exceeds the largest prefill "
            f"bucket {self.buckets[-1]}; refuse (default) or serve the "
            "newest bucket-sized context with prompt_overflow='clamp'"
        )

    def _grow_staging(self, length: int) -> None:
        """Grow the staging buffer to the bucket of a ``length``-token
        prompt if it is narrower (widths take bucket values only — the
        unified program's compile key stays bounded). Rows still pending
        are written at whatever width the buffer has when they flush."""
        b = bucket_for(length, self.buckets)
        width = 0 if self._pbuf is None else self._pbuf.shape[1]
        if b <= width:
            return
        if self._pbuf is None:
            self._pbuf = jnp.zeros((self.slots, b), jnp.int32)
        else:
            self._pbuf = jnp.pad(self._pbuf, ((0, 0), (0, b - width)))
        if self.mesh is not None:
            # a freshly (re)allocated staging buffer lands on the
            # default device; the unified program wants it replicated
            # over the mesh like every other per-slot input
            from orion_tpu.parallel.decode import place_replicated

            self._pbuf = place_replicated(self._pbuf, self.mesh)

    def _flush_staged(self) -> None:
        """Write every pending admission into the carry: ONE dispatch of
        :func:`_stage_rows_carry` for up to ``STAGE_ROWS`` of them, every
        input packed here in numpy (no pad program a prompt length, no
        scalar copies a request). Runs before anything reads or writes
        the carry, the per-slot vectors or the staging buffer, in the
        order of admission."""
        staged, self._staged = self._staged, []
        for at in range(0, len(staged), STAGE_ROWS):
            rows = np.zeros(
                (STAGE_ROWS, _ROW_HEAD + self._pbuf.shape[1]), np.int32
            )
            rows[:, 0] = -1
            for row, (i, prompt, fold, key) in zip(
                rows, staged[at : at + STAGE_ROWS]
            ):
                row[:3] = i, prompt.size, fold
                row[3:_ROW_HEAD] = key.view(np.int32)
                row[_ROW_HEAD : _ROW_HEAD + prompt.size] = prompt
            (self._carry, self._rngs, self._plen, self._pfold,
             self._pbuf) = _stage_rows_carry(
                self._carry, self._rngs, self._plen, self._pfold,
                self._pbuf, rows,
            )
            self.staging_dispatches += 1

    @_serialized
    def flush_admissions(self) -> None:
        """Stage the prompts admitted since the last flush (the Server
        calls this at the end of its admission loop, so the dispatch
        lies inside ``serve.admit``). Every method that touches the
        carry flushes first on its own; only a caller that reads the
        engine's private device state right after ``admit`` needs it."""
        self._flush_staged()

    # -- content-addressed prefix cache (serving/prefix_store.py) -------------
    # Everything on this side of the store boundary is hash + disk + one
    # fused jitted dispatch — the decode-host-sync lint's admission scope
    # covers *prefix*-named functions of this module, so the store owns
    # any host<->device serialization (publish-side device_get).

    def _prefix_lookup(self, request: DecodeRequest, prompt: np.ndarray,
                       tag):
        """Longest cached aligned prefix of this request's prompt, or
        None. The lookup keys off the REQUEST's host tokens (the Server
        normalizes prompts to host arrays at submit, off the scheduler
        thread); a clamped prompt (overflow mode) skips the lookup —
        its served tokens differ from the submitted ones."""
        if self.prefix_store is None:
            return None
        raw = request.prompt
        if getattr(raw, "ndim", 2) == 1:
            raw = raw.reshape(1, -1)
        if raw.shape[-1] != prompt.shape[1]:
            # clamped: the served prompt is not the submitted one, so no
            # lookup runs — still a MISS for the hit-rate denominator
            # (these are exactly the longest prompts, which always pay
            # the cold prefill; hiding them would inflate the ratio)
            self._emit("prefix_miss", tag=tag,
                       prompt_len=int(prompt.shape[1]), clamped=True)
            return None
        entry = self.prefix_store.lookup(
            raw, declared=max(request.prefix_len, 0)
        )
        if entry is not None and entry.t % max(self.chunk_align, 1) != 0:
            entry = None  # foreign alignment: unusable for in-scan pieces
        if entry is None:
            self._emit("prefix_miss", tag=tag,
                       prompt_len=int(prompt.shape[1]))
            return None
        self._emit("prefix_hit", tag=tag, prefix_len=int(entry.t),
                   suffix=int(prompt.shape[1]) - int(entry.t),
                   key=entry.key, generation=int(entry.generation))
        return entry

    def _stage_prefix(self, i: int, prompt: np.ndarray, rng: Array,
                      sample_index: int, entry) -> None:
        """O(suffix) admission on a prefix hit: the FULL prompt is staged
        (so the ladder's restart rung can replay from scratch) but the
        carry row starts at ``t = entry.t`` with the cached state — one
        fused row write, the snapshot copy that IS the prefix cache."""
        self._flush_staged()
        self._grow_staging(prompt.shape[1])
        row = np.zeros((self._pbuf.shape[1],), np.int32)
        row[: prompt.shape[1]] = prompt[0]
        (self._carry, self._rngs, self._plen, self._pfold,
         self._pbuf) = _stage_prefix_carry(
            self._carry, self._rngs, self._plen, self._pfold, self._pbuf,
            entry.state, row, rng, jnp.int32(i),
            jnp.int32(prompt.shape[1]), jnp.int32(sample_index),
            jnp.int32(entry.t),
        )
        self.staging_dispatches += 1

    def _queue_prefix_publish(self, request: DecodeRequest,
                              prompt_len: int) -> None:
        """A miss on a request DECLARING a shared prefix queues that
        aligned prefix for publication (deduped by content key; skipped
        when another replica already committed it). The actual prefill +
        store write runs via :meth:`publish_pending_prefixes` — outside
        the admission hot path."""
        if self.prefix_store is None or request.prefix_len <= 0:
            return
        pub = self.prefix_store.publish_length(
            prompt_len, request.prefix_len
        )
        if pub <= 0:
            return
        raw = request.prompt
        if getattr(raw, "ndim", 2) == 1:
            raw = raw.reshape(1, -1)
        row = raw[:, :pub]
        key = self.prefix_store.key_for(row)
        if any(k == key for k, _ in self._pending_prefix):
            return
        br = self.prefix_store.breaker
        if br is not None and br.is_open:
            # store outage: NO per-request disk probe (the dedup scan
            # below would block on dead storage on the admission path).
            # Queue blind — the publish pass re-checks existence after
            # recovery, and the bounded queue caps what we hold.
            pass
        else:
            try:
                if self.prefix_store.generations(key):
                    return  # already committed (here or another replica)
            except StoreUnavailableError:
                pass  # breaker tripped mid-check: queue blind, as above
        if len(self._pending_prefix) >= self.max_pending_prefixes:
            self.dropped_prefixes += 1
            self._emit("prefix_drop", key=key,
                       dropped_total=self.dropped_prefixes)
            return
        self._pending_prefix.append((key, row))

    @property
    def has_pending_prefixes(self) -> bool:
        """Queued publishes awaiting :meth:`publish_pending_prefixes` —
        the Server checks this to beat its watchdog first (a publish is
        a solo prefill + possibly a fresh bucket compile, the same cost
        class admission beats for)."""
        return bool(self._pending_prefix)

    @property
    def pending_prefix_count(self) -> int:
        """Depth of the bounded publish queue (the /statusz failure-
        domain section reads it next to ``dropped_prefixes``)."""
        return len(self._pending_prefix)

    @_serialized
    def publish_pending_prefixes(self) -> int:
        """Publish queued prefix snapshots: prefill the prefix solo (the
        bucketed whole-prompt program, one compile per bucket) and hand the
        state to the store, which serializes on its side. A failed
        publish degrades to "not cached" with a warning — the cache must
        never fail the serving path. Returns how many entries written.

        Cost honesty: this runs on the scheduler thread between chunk
        boundaries, so the FIRST declared novel prefix stalls co-resident
        slots for one solo prefill (+ a first-time bucket compile) — a
        one-time cost per (prefix, store) that every later hit on every
        replica amortizes. It cannot ride the cold request's own in-scan
        prefill: pieces advance ``t`` by ``prefill_chunk`` steps, so the
        scan's state never sits exactly at the declared aligned length
        to be extracted for free (and the publish must not change the
        piece schedule: a replayed boundary is bit-for-bit only under the
        schedule it first ran)."""
        self._flush_staged()
        done = 0
        br = self.prefix_store.breaker
        if br is not None and br.blocked():
            # outage, probe not yet due: O(1) host check and out — the
            # queued entries wait (bounded) for the half-open probe;
            # calling further down would just burn a warning per boundary
            return 0
        while self._pending_prefix:
            key, row = self._pending_prefix.pop(0)
            try:
                if self.prefix_store.generations(key):
                    # another replica committed it since queue time: the
                    # re-check is one listdir, the prefill it saves is
                    # the whole stall this path costs
                    continue
                carry = prefill_carry(
                    self.model, self.params, row, self._sample,
                    jax.random.PRNGKey(0), self.buckets,
                    exec_lookup=self._warm_prefill_exec,
                )
                gen = self.prefix_store.publish(row, carry[1])
                if gen is None:
                    continue  # raced: a peer committed mid-prefill
                done += 1
                self._emit("prefix_publish", key=key,
                           length=int(row.shape[1]), generation=gen)
            except StoreUnavailableError:
                # breaker open (or the probe this pass rode just
                # failed): requeue and stop — no warning spam, the
                # entry publishes after recovery
                self._pending_prefix.insert(0, (key, row))
                break
            except Exception as e:
                import warnings

                if br is not None and br.is_open:
                    # this failure is the one that TRIPPED the breaker
                    # (or rode a failed probe): keep the entry — it
                    # publishes after recovery, and retrying it is the
                    # natural half-open probe that closes the breaker
                    self._pending_prefix.insert(0, (key, row))
                    warnings.warn(
                        f"prefix publish failed ({type(e).__name__}); "
                        "store breaker open — entry queued for recovery",
                        stacklevel=2,
                    )
                    break
                warnings.warn(
                    f"prefix publish failed ({type(e).__name__}: {e}); "
                    "serving continues uncached",
                    stacklevel=2,
                )
        return done

    @_serialized
    def resume(
        self,
        sess: SessionState,
        request: DecodeRequest,
        tag: Any = None,
        deadline_at: Optional[float] = None,
    ) -> int:
        """Re-admit a suspended session into a free slot: O(1) row insert
        of the saved carry at the saved position and rng-fold index — no
        prefill, no new compiles, bitwise-identical to having kept the
        slot resident. The saved chunk-overshoot buffer rides as the
        slot's ``prefix`` (served host-side before any device token
        counts against this turn)."""
        if request.sample != sess.sample:
            raise ValueError(
                "continuation SampleConfig differs from the session's: the "
                "resumed rng walk is only bitwise with the sampling "
                "parameters it was suspended under"
            )
        prefix = np.asarray(sess.emitted[:, sess.served:])
        target_new = request.max_new_tokens - prefix.shape[1]
        if target_new <= 0:
            raise ValueError(
                "continuation fully covered by the session's buffered "
                "tokens; the caller should serve it without a slot"
            )
        cap = self.model.cfg.max_seq_len
        if int(sess.t) + target_new > cap:
            raise ValueError(
                f"session at position {int(sess.t)} + new {target_new} "
                f"exceeds max_seq_len {cap}"
            )
        i = self._claim_slot(request.sample)
        rng = jax.random.PRNGKey(sess.seed)
        sub = (sess.token, sess.state, sess.t, sess.done)
        self._insert(i, sub, rng, n_emitted=int(sess.emit))
        self._slots[i] = _Slot(
            request=request,
            tag=tag,
            deadline_at=deadline_at,
            prompt=jnp.asarray(sess.prompt, jnp.int32),
            toks=[],
            session_id=sess.session_id,
            seed=int(sess.seed),
            prior=[np.asarray(sess.emitted)] if sess.emitted.size else [],
            prefix=prefix if prefix.size else None,
            target_new=target_new,
            fold_base=int(sess.emit),
            served_base=int(sess.served),
        )
        self._emit(
            "resume", slot=i, tag=tag, session=sess.session_id,
            t=int(sess.t), generation=int(sess.generation),
        )
        return i

    def _insert(self, i: int, sub_carry, rng: Array, n_emitted: int = 0) -> None:
        """Row-write a solo carry (batch 1) into slot ``i`` of the batched
        carry (one fused jitted dispatch; see :func:`_insert_carry`)."""
        self._flush_staged()
        (self._carry, self._rngs, self._plen,
         self._pfold) = _insert_carry(
            self._carry, self._rngs, self._plen, self._pfold, sub_carry,
            rng, jnp.int32(i), jnp.int32(n_emitted),
        )

    # -- the chunk step -------------------------------------------------------

    @_serialized
    def step(self) -> List[Tuple[Any, DecodeResult]]:
        """Advance every resident slot by one chunk (the scheduler calls
        this only when ``busy``). Returns (tag, DecodeResult) for every
        request that FINISHED at this boundary — ok, deadline, or
        ladder-exhausted failed. Raises nothing for decode-state faults."""
        inject.fire("serve.chunk", step=self._chunk_counter)
        finished: List[Tuple[Any, DecodeResult]] = []
        self.last_boundary = []
        self.moe_rows = np.zeros((len(STAT_NAMES),), np.int64)
        # deadlines are checked BEFORE paying for the chunk
        now = self._clock()
        for i, slot in enumerate(self._slots):
            if slot is not None and slot.deadline_at is not None and now >= slot.deadline_at:
                finished.append((slot.tag, self._finish(i, "deadline")))
        if not self.busy:
            self._chunk_counter += 1
            return finished
        self._flush_staged()
        active = np.array([s is not None for s in self._slots])
        active_dev = jnp.asarray(active)
        unified = self.prefilling_count > 0
        # speculative rounds run at PURE-DECODE boundaries only (the
        # unified program owns mid-prefill boundaries); the bitwise
        # contract makes the two interleave token-transparently. With
        # every active slot floored the plain chunk program runs — full
        # chunk per boundary, and its compiled bytes stay untouched.
        spec = None
        if self.spec_depth and not unified and bool(
            np.any(active & self._spec_on_np)
        ):
            spec = jnp.asarray(self._spec_on_np)
        # with the carry donated the attempt consumes it: there is no
        # snapshot to rewind to, and ``self._carry`` is dead until the
        # boundary's result replaces it below
        snap = self._carry if self.donate_carry else self._snapshot()
        carry, toks, accepted = self._attempt(snap, active_dev, unified, spec)
        # the boundary's two inner edges, for whoever times its phases
        # (the Server's spans): everything up to here only ENQUEUED work;
        # the probe below is where the host waits for the device
        self._emit("phase", name="probe")
        bad = self._probe_bad(carry, active, accepted)
        ladder = bool(bad) and not self.donate_carry
        if ladder:
            carry, toks, bad = self._ladder(
                snap, active_dev, active, carry, toks, bad, unified, spec
            )
        for i in sorted(bad) if self.donate_carry else ():
            self._emit("ladder", rung="exhausted", slot=i,
                       chunk=self._slots[i].chunks, tag=self._slots[i].tag)
        # a failed slot may not read the carry it no longer has (``failed``
        # never suspends; the other evictions below come after)
        self._carry = carry
        self._emit("phase", name="finish", ladder=ladder)
        for i in sorted(bad):  # ladder exhausted: fail those requests
            slot = self._slots[i]
            # the failed slot's boundary work still ran — bill it by
            # its class so attribution stays conservative. Mid-prefill
            # failures weigh zero (the host cannot know which replay
            # fed their piece); nothing was EMITTED either way.
            self.last_boundary.append({
                "slot": i, "tag": slot.tag, "failed": True,
                "frozen": spec is None and slot.prompt_remaining > 0,
                "spec_round": spec is not None,
                "decode_steps": (
                    0 if spec is not None or slot.prompt_remaining > 0
                    else self.chunk
                ),
                "prefill_tokens": 0, "decode_tokens": 0,
            })
            finished.append((slot.tag, self._finish(i, "failed")))
            active[i] = False
        done_np = self._done_np
        piece = self._piece_tokens()
        # host mirror of the in-scan pieces: deterministic, no readback —
        # the ACCEPTED attempt's selection (same rule over the same
        # host-mirrored inputs) tells which slots consumed a piece at
        # this boundary and hence the boundary each starts emitting
        served = self._selected_prefill_slots(active)
        spec_stats = None if spec is None else {"accepted": 0, "rejected": 0,
                                                "slots": 0}
        for i, slot in enumerate(self._slots):
            if slot is None or not active[i]:
                continue
            if slot.prompt_remaining > 0:
                slot.chunks += 1
                if i not in served:
                    slot.passed_over += 1
                    self.last_boundary.append({
                        "slot": i, "tag": slot.tag, "frozen": True,
                        "decode_steps": 0, "prefill_tokens": 0,
                        "decode_tokens": 0,
                    })
                    continue  # frozen: the boundary's pieces went elsewhere
                slot.passed_over = 0
                consumed = min(piece, slot.prompt_remaining)
                slot.prompt_remaining -= consumed
                self._emit("prefill_piece", slot=i, tag=slot.tag,
                           consumed=consumed,
                           remaining=slot.prompt_remaining)
                if slot.prompt_remaining > 0:
                    self.last_boundary.append({
                        "slot": i, "tag": slot.tag,
                        "decode_steps": 0, "prefill_tokens": consumed,
                        "decode_tokens": 0,
                    })
                    continue  # still mid-prefill: emitted nothing yet
                slot.toks.append((toks, i, self.chunk))
                slot.n_emitted += self.chunk
                self.last_boundary.append({
                    "slot": i, "tag": slot.tag,
                    "decode_steps": self.chunk, "prefill_tokens": consumed,
                    "decode_tokens": self.chunk,
                })
            elif spec is not None:
                # speculative round: the probe's accepted row says how
                # far this slot advanced (accepted drafts + the pending
                # token); the host mirror drives the rolling-acceptance
                # floor without any extra readback
                v = int(self._accept_np[i]) + 1
                slot.toks.append((toks, i, v))
                slot.n_emitted += v
                slot.chunks += 1
                self.last_boundary.append({
                    "slot": i, "tag": slot.tag, "spec_round": True,
                    "decode_steps": 0, "prefill_tokens": 0,
                    "decode_tokens": v,
                })
                if self._spec_on_np[i]:
                    spec_stats["slots"] += 1
                    spec_stats["accepted"] += v - 1
                    spec_stats["rejected"] += self.spec_depth - (v - 1)
                    self._update_spec_accept(i, v - 1)
            else:
                slot.toks.append((toks, i, self.chunk))
                slot.n_emitted += self.chunk
                slot.chunks += 1
                self.last_boundary.append({
                    "slot": i, "tag": slot.tag,
                    "decode_steps": self.chunk, "prefill_tokens": 0,
                    "decode_tokens": self.chunk,
                })
            if slot.n_emitted >= slot.target_new or done_np[i]:
                finished.append((slot.tag, self._finish(i, "ok")))
        if spec_stats is not None and spec_stats["slots"]:
            self._emit("spec_round", depth=self.spec_depth, **spec_stats)
        self._chunk_counter += 1
        return finished

    def _update_spec_accept(self, i: int, accepted: int) -> None:
        """Fold one round's acceptance into slot ``i``'s rolling EWMA and
        apply the adaptive floor: a slot paying for drafts that keep
        being rejected falls back to plain decode for the rest of its
        residency (``spec_min_accept``; 0 never floors). Pure host
        arithmetic on the probe row the boundary already paid for."""
        slot = self._slots[i]
        slot.spec_rounds += 1
        slot.spec_accepted += accepted
        slot.spec_drafted += self.spec_depth
        rate = accepted / max(self.spec_depth, 1)
        prev = self._accept_ewma[i]
        ewma = rate if prev is None else 0.5 * prev + 0.5 * rate
        self._accept_ewma[i] = ewma
        # >= 2 rounds before flooring: one unlucky first round must not
        # permanently disable a slot's speculation
        if (self.spec_min_accept > 0.0 and slot.spec_rounds >= 2
                and self._spec_on_np[i]
                and ewma < self.spec_min_accept):
            self._spec_on_np[i] = False
            self._emit("spec_floor", slot=i, tag=slot.tag,
                       accept_ewma=round(ewma, 4),
                       rounds=slot.spec_rounds)

    def spec_info(self) -> List[dict]:
        """Per-resident-slot speculation view for /statusz: depth, the
        enable bit, rolling acceptance, and lifetime accept counts. Pure
        host bookkeeping, no readback."""
        out = []
        if not self.spec_depth:
            return out
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            e = self._accept_ewma[i]
            out.append({
                "slot": i, "depth": self.spec_depth,
                "on": bool(self._spec_on_np[i]),
                "accept_ewma": None if e is None else round(e, 4),
                "rounds": slot.spec_rounds,
                "accepted": slot.spec_accepted,
                "drafted": slot.spec_drafted,
            })
        return out

    def _piece_tokens(self) -> int:
        """The width of ONE slot's piece: ``prefill_chunk``, capped at
        the staged buffer's width (a single piece then covers any prompt
        the buffer holds — which also keeps piece boundaries trivially
        chunk-aligned). How many pieces a boundary runs is
        ``generate.prefill_piece_cap(slots, chunk)``."""
        if self._pbuf is None:
            return self.prefill_chunk
        return min(self.prefill_chunk, self._pbuf.shape[1])

    def _selected_prefill_slots(self, active) -> List[int]:
        """Host mirror of the unified program's stage-1 schedule, in the
        order the device loop walks it: overdue slots first (the longest
        passed over first), then shortest remaining prompt, ties to the
        lowest slot index; the first ``prefill_piece_cap`` of them —
        computed from the same inputs ``generate._prefill_selection``
        sees (the host-tracked remaining and passed-over counts), so the
        schedule is known without a device round-trip. Must be evaluated
        against the mask of the ACCEPTED attempt (ladder rung 3 can mask
        a prefilling slot out, which moves the next slot up in the
        replay)."""
        overdue_after = prefill_overdue_after(self.slots, self.chunk)

        def key(i):
            slot = self._slots[i]
            overdue = slot.passed_over >= overdue_after
            return (-slot.passed_over if overdue else 0,
                    slot.prompt_remaining, i)

        waiting = [
            i for i, slot in enumerate(self._slots)
            if slot is not None and active[i] and slot.prompt_remaining > 0
        ]
        return sorted(waiting, key=key)[
            :prefill_piece_cap(self.slots, self.chunk)
        ]

    def _snapshot(self):
        """Container-fresh snapshot of the batched carry (O(1): jax arrays
        are immutable; the rewind target must not alias mutated dicts,
        ``transformer.snapshot_decode_state``)."""
        token, states, t, emit, done = self._carry
        return (token, snapshot_decode_state(states), t, emit, done)

    def _attempt(self, carry, active_dev, unified=False, spec=None):
        """One batched boundary attempt — the UNIFIED prefill+decode
        program while any slot is mid-prefill, the SPECULATIVE round
        when ``spec`` (the per-slot speculation mask) is armed, the pure
        decode program otherwise (whose compiled bytes this feature must
        not perturb; golden ``decode_batched_tiny``). Returns
        (carry, emitted, accepted-or-None). Applies any armed per-slot
        (or per-chunk) decode-state poisoning afterwards so each
        ladder rung is deterministically reachable per slot."""
        # the FIRST launch of each program kind (per staged-buffer width
        # for the unified program — a wider bucket is a new executable)
        # is announced to the owner by two edges, so its
        # ``setup.first_launch`` span surrounds whatever jax traces,
        # lowers, compiles or loads for it (serving/server.py
        # ``_first_launch``). One-time host bookkeeping per kind — later
        # boundaries pay one set lookup.
        kind = ("spec_round" if spec is not None
                else "unified_prefill" if unified else "decode_batched")
        donate = self.donate_carry and spec is None
        width = int(self._pbuf.shape[1]) if kind == "unified_prefill" else 0
        seen_key = (kind, width) if width else kind
        first = seen_key not in self._compile_seen
        if first:
            self._compile_seen.add(seen_key)
            self._emit("first_launch", edge="begin", program=kind,
                       width=width)
        # AOT warm start: a stored executable (same program, same
        # compiler) replaces the jit dispatch — statics are baked into
        # the artifact, so the warm calls pass only the dynamic operands
        # in the wrapper's positional order. The store holds the
        # undonated programs only.
        warm = None if donate else self._warm_boundary_exec(kind, seen_key)
        accepted = None
        # the programs of a model whose MoE layers count their rows
        # (``moe.masks_rows``) also return those counters, [6] on the device
        # (one vector, or the donated boundary's tuple of them):
        # ``_probe_bad`` reads them
        counted = ()
        try:
            if donate:
                live = np.array([s is not None for s in self._slots])
                out, toks, *counted = decode_boundary_donated(
                    self.model, self.params, carry, self._rngs, active_dev,
                    self._pbuf, self._plen, self._pfold,
                    tuple(self._selected_prefill_slots(live))
                    if unified else (),
                    self.chunk, self.prefill_chunk, self._sample,
                )
            elif spec is not None:
                if warm is not None:
                    out, toks, accepted = warm(
                        self.params, carry, self._rngs, active_dev, spec
                    )
                else:
                    out, toks, accepted = decode_batched_spec_round(
                        self.model, self.params, carry, self._rngs, active_dev,
                        spec, self.spec_depth, self._sample,
                    )
            elif unified:
                pwait = jnp.asarray(np.array(
                    [0 if s is None else s.passed_over for s in self._slots],
                    np.int32,
                ))
                if warm is not None:
                    out, toks, *counted = warm(
                        self.params, carry, self._rngs, active_dev,
                        self._pbuf, self._plen, self._pfold, pwait,
                    )
                else:
                    out, toks, *counted = decode_batched_prefill_chunk(
                        self.model, self.params, carry, self._rngs, active_dev,
                        self._pbuf, self._plen, self._pfold, pwait, self.chunk,
                        self.prefill_chunk, self._sample,
                    )
            else:
                if warm is not None:
                    out, toks, *counted = warm(
                        self.params, carry, self._rngs, active_dev
                    )
                else:
                    out, toks, *counted = decode_batched_chunk(
                        self.model, self.params, carry, self._rngs, active_dev,
                        self.chunk, self._sample,
                    )
        finally:
            if first:
                self._emit("first_launch", edge="end",
                           warm=warm is not None)
        if inject.active():
            for i, slot in enumerate(self._slots):
                if slot is None:
                    continue
                if inject.decode_slot_nan_armed(i, slot.chunks) or (
                    inject.decode_nan_armed(slot.chunks)
                ):
                    out = self._poison_slot(out, i)
        if counted:
            self._moe_counted = counted[0] if donate else tuple(counted)
        return out, toks, accepted

    @staticmethod
    def _poison_slot(carry, i: int):
        token, states, t, emit, done = carry
        states = jax.tree.map(
            lambda x: x.at[i].set(jnp.nan)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            states,
        )
        return (token, states, t, emit, done)

    def _probe_bad(self, carry, active: np.ndarray, accepted=None) -> set:
        """The designated per-chunk host sync: ONE transfer carrying the
        per-slot finite mask (free slots masked — a failed request's NaN
        remains in its row until the next admission overwrites it) AND
        the done flags (EOS already emitted -> every later token is PAD,
        so the slot can be freed and the tail filled host-side); the
        done row is stashed for the eviction pass. At a speculative
        boundary the per-slot accepted counts ride the SAME transfer
        ([3, slots] int32 instead of [2, slots] bool) — the accept/
        reject decision never costs a second readback. So do the MoE row
        counters of a model whose layers count (``moe.masks_rows``;
        ``moe_rows``: the boundary's [routed, held, busiest expert's,
        dropped, tiles visited, experts with a row], the programs' vectors
        padded to one length so that one program sums them)."""
        if self._moe_counted:
            pad = 1 + prefill_piece_cap(self.slots, self.chunk) - len(self._moe_counted)
            if self._moe_zero is None:
                self._moe_zero = jnp.zeros((len(STAT_NAMES),), jnp.int32)
            flags = np.asarray(_counted_flags(
                carry[1], carry[4], self._moe_counted + (self._moe_zero,) * pad
            ))
            self._moe_counted = ()
            self.moe_rows = flags[2 * self.slots:]
            self._done_np = flags[self.slots:2 * self.slots].astype(bool)
            self._accept_np = None
            finite = flags[:self.slots].astype(bool)
        elif accepted is None:
            flags = np.asarray(_slot_flags(carry[1], carry[4]))
            self._done_np = flags[1]
            self._accept_np = None
            finite = flags[0]
        else:
            flags = np.asarray(_spec_flags(carry[1], carry[4], accepted))
            self._done_np = flags[1].astype(bool)
            self._accept_np = flags[2]
            finite = flags[0].astype(bool)
        return {i for i in range(self.slots) if active[i] and not finite[i]}

    def _ladder(self, snap, active_dev, active, carry, toks, bad,
                unified=False, spec=None):
        """Walk the per-slot degradation ladder. Redoing the WHOLE batched
        chunk from the boundary snapshot is the rewind: deterministic
        row-independent compute means untouched slots reproduce their
        tokens bitwise (a co-resident slot MID-prefill replays its piece
        identically — the staged prompt and its position are part of the
        snapshot's inputs; a co-resident slot MID-SPECULATION re-drafts
        and re-verifies identically — drafts are a pure function of the
        snapshot carry), and the poisoned slot gets its retry. Returns
        the accepted (carry, toks) and the set of slots whose ladder is
        exhausted (their requests fail; everyone else streams on)."""
        # rung 1: rewind — redo from the snapshot
        carry, toks, accepted = self._attempt(snap, active_dev, unified, spec)
        bad2 = self._probe_bad(carry, active, accepted)
        for i in bad:
            self._slots[i].rewinds += 1
            self._emit("ladder", rung="rewind", slot=i,
                       chunk=self._slots[i].chunks, tag=self._slots[i].tag)
        if not bad2:
            return carry, toks, set()
        # rung 2: the snapshot itself is poisoned for the still-bad slots —
        # rebuild each from its prompt + emitted tokens (the one thing
        # known good), row-write into the snapshot, redo
        snap2 = snap
        for i in sorted(bad2):
            snap2 = self._reprefill_into(snap2, i)
            self._slots[i].reprefills += 1
            rung = ("prefill_restart" if self._slots[i].prompt_remaining > 0
                    else "reprefill")
            self._emit("ladder", rung=rung, slot=i,
                       chunk=self._slots[i].chunks, tag=self._slots[i].tag)
        carry, toks, accepted = self._attempt(snap2, active_dev, unified, spec)
        bad3 = self._probe_bad(carry, active, accepted)
        if not bad3:
            return carry, toks, set()
        # rung 3: fail the exhausted slots and redo once more with them
        # masked out, so the surviving slots still get their chunk
        still = np.array(active)
        for i in bad3:
            still[i] = False
            self._emit("ladder", rung="exhausted", slot=i,
                       chunk=self._slots[i].chunks, tag=self._slots[i].tag)
        if still.any():
            # the surviving slots' tokens, done flags, and accepted
            # counts replay bitwise (row-independence), so the stashed
            # probe rows from the accepted attempt above stay valid —
            # no extra readback for the rung-3 replay
            carry, toks, _ = self._attempt(
                snap2, jnp.asarray(still), unified, spec
            )
        return carry, toks, bad3

    def _reprefill_into(self, snap, i: int):
        """Ladder rung 2 for slot ``i``: solo re-prefill of prompt + the
        tokens emitted so far (:func:`generate.reprefill_carry`, which
        keeps the rng/done alignment of the uninterrupted walk),
        row-written over the slot's poisoned snapshot state. For a
        resumed session the history spans turns: ``prior`` (earlier
        turns' emissions) precedes this turn's chunks, and the fold index
        is anchored at ``fold_base`` so the rebuilt rng walk matches the
        carry the snapshot held."""
        self._flush_staged()
        slot = self._slots[i]
        if slot.prompt_remaining > 0:
            # mid-prefill: nothing emitted yet — the one known-good input
            # is the staged prompt itself, so this rung RESTARTS the
            # in-scan prefill from a zero state row (the same program over
            # the same staged prompt: the tokens come out bitwise-identical,
            # a few boundaries later)
            slot.prompt_remaining = slot.prompt.shape[1]
            return _restart_prefill_row(snap, jnp.int32(i))
        emitted = list(slot.prior) + [
            arr[row : row + 1, :n] for arr, row, n in slot.toks
        ]
        rng = jax.random.PRNGKey(slot.seed)
        fold = slot.fold_base + slot.n_emitted
        sub = reprefill_carry(
            self.model, self.params, slot.prompt, emitted, self._sample,
            rng, self.buckets, sample_index=fold,
            exec_lookup=self._warm_prefill_exec,
        )
        new_snap, self._rngs, self._plen, self._pfold = _insert_carry(
            snap, self._rngs, self._plen, self._pfold, sub, rng,
            jnp.int32(i), jnp.int32(fold),
        )
        return new_snap

    # -- eviction -------------------------------------------------------------

    def _evict(self, i: int, status: str) -> DecodeResult:
        """Free slot ``i`` and materialize its request's result — the one
        sync per REQUEST lifetime (not per chunk), outside the scheduler's
        per-chunk probe budget. A resumed session's host-side buffer
        (``prefix``) precedes this turn's device chunks; the total is
        trimmed to max_new_tokens (the engine always runs whole chunks)
        and an early-EOS eviction PAD-fills the tail, exactly what the
        solo scan would have emitted."""
        self._flush_staged()
        slot = self._slots[i]
        self._slots[i] = None
        req = slot.request
        want = req.max_new_tokens
        parts = [] if slot.prefix is None else [slot.prefix]
        parts += [
            np.asarray(arr)[row : row + 1, :n] for arr, row, n in slot.toks
        ]
        if parts:
            tokens = np.concatenate(parts, axis=1)[:, :want]
        else:
            tokens = np.zeros((1, 0), np.int32)
        n = tokens.shape[1]
        if status == "ok" and n < want:
            pad = np.full((1, want - n), req.sample.pad_token, tokens.dtype)
            tokens = np.concatenate([tokens, pad], axis=1)
            n = want
        return DecodeResult(
            tokens=tokens,
            status=status,
            new_tokens=n,
            chunks=slot.chunks,
            rewinds=slot.rewinds,
            reprefills=slot.reprefills,
        )

    def _finish(self, i: int, status: str) -> DecodeResult:
        """Evict slot ``i`` — via suspension (state extracted and attached
        to the result as a :class:`SessionState`) when the slot carries a
        session id and its state is trustworthy. ``failed`` never
        suspends: a ladder-exhausted slot's state is exactly what a
        continuation must NOT resume from (the previous generation on
        disk stays the session's truth). A slot still MID-prefill never
        suspends either — its carry is a partial prompt, not a turn
        boundary; it evicts with zero tokens and whatever the session
        store already holds stays that conversation's truth (the client
        re-submits the turn)."""
        slot = self._slots[i]
        self._emit(
            "evict", slot=i, tag=slot.tag, status=status,
            session=slot.session_id, chunks=slot.chunks,
            suspended=(slot.session_id is not None and status != "failed"
                       and slot.prompt_remaining == 0),
            spec_accepted=slot.spec_accepted,
            spec_drafted=slot.spec_drafted,
        )
        if (slot.session_id is None or status == "failed"
                or slot.prompt_remaining > 0):
            return self._evict(i, status)
        return self._suspend(i, status)

    def _suspend(self, i: int, status: str) -> DecodeResult:
        """Suspend slot ``i``: extract its carry row (one fused jitted
        row-read, ``_extract_carry``), pull the O(1) state to host, and
        free the slot. The SessionState rides out on the DecodeResult so
        the server can persist it BEFORE releasing the result — a client
        must never see tokens a crash could unremember."""
        self._flush_staged()
        slot = self._slots[i]
        token, state, t, emit, done = jax.device_get(
            _extract_carry(self._carry, jnp.int32(i))
        )
        prior = [np.asarray(a) for a in slot.prior]
        rows = [
            np.asarray(arr)[row : row + 1, :n] for arr, row, n in slot.toks
        ]
        emitted = (
            np.concatenate(prior + rows, axis=1)
            if prior or rows
            else np.zeros((1, 0), np.int32)
        )
        prompt = np.asarray(slot.prompt)
        served_base = slot.served_base
        result = self._evict(i, status)
        result.session = SessionState(
            session_id=slot.session_id,
            seed=slot.seed,
            sample=self._sample,
            served=min(served_base + result.new_tokens, emitted.shape[1]),
            token=np.asarray(token),
            state=state,
            t=np.asarray(t),
            emit=np.asarray(emit),
            done=np.asarray(done),
            prompt=prompt,
            emitted=emitted,
        )
        return result

    @_serialized
    def suspend_sessions(self) -> List[Tuple[Any, DecodeResult]]:
        """Suspend EVERY resident session-tagged slot mid-stream with
        status ``"suspended"`` (partial tokens + the session attached) —
        the SIGTERM drain path: conversations survive the restart as one
        O(1) snapshot each instead of holding the drain hostage for their
        remaining tokens. Sessionless slots are untouched (they drain to
        completion)."""
        out = []
        for i, slot in enumerate(self._slots):
            if slot is not None and slot.session_id is not None:
                out.append((slot.tag, self._finish(i, "suspended")))
        return out

    @_serialized
    def drain_evict_all(self, status: str = "failed") -> List[Tuple[Any, DecodeResult]]:
        """Forcibly evict every resident request with partial tokens (the
        Server's last-resort path when the loop must exit NOW; the normal
        SIGTERM drain finishes slots instead)."""
        out = []
        for i, slot in enumerate(self._slots):
            if slot is not None:
                self._emit("evict", slot=i, tag=slot.tag, status=status,
                           session=slot.session_id, chunks=slot.chunks,
                           suspended=False, forced=True)
                out.append((slot.tag, self._evict(i, status)))
        return out


__all__ = ["SlotEngine", "fits_once_only", "parse_buckets"]
