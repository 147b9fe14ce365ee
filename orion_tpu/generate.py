"""`python -m orion_tpu.generate` — recurrent O(1)-state autoregressive
decode (SURVEY.md I1–I5).

TPU-native counterpart of the reference's `orion.generate` (BASELINE.json
"recurrent autoregressive decode (O(1) state)"; reference checkout never
mounted — SURVEY.md §0). The pipeline:

1. **prefill** — one jitted parallel forward over the prompt (chunked linear
   attention / flash softmax), returning per-layer decode state: (S, z)
   kv-cumsum states for linear layers, KV caches for softmax, ring-buffer
   window caches for swa.
2. **decode** — ONE jitted ``lax.scan`` over all steps (no per-step
   retrace/dispatch): carry = (token, states, rng, t); body = embed →
   per-layer recurrent_step / cache-append attention → logits → sample.
   Linear-layer memory stays O(Dk·Dv) per head regardless of length.
3. **sampling** — greedy / temperature / top-k / top-p, batched.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
from functools import partial
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from orion_tpu.models.configs import ModelConfig, get_config
from orion_tpu.models.mixers import MIXERS
from orion_tpu.models.transformer import TransformerLM, init_decode_state
from orion_tpu.ops.dispatch import decode_live_rows

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SampleConfig:
    temperature: float = 1.0
    top_k: int = 0  # 0 = off
    top_p: float = 1.0  # 1.0 = off
    eos_token: int = -1  # >= 0: stop sequences at EOS (pad with pad_token)
    pad_token: int = 0

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


def sample_logits(logits: Array, rng: Array, cfg: SampleConfig) -> Array:
    """logits [B, V] -> token ids [B]."""
    if cfg.greedy:
        return jnp.argmax(logits, axis=-1)
    logits = logits / cfg.temperature
    # clamp top_k to the vocab: a caller's top_k >= V means "no filtering",
    # not an out-of-range [-top_k] index into the sorted row
    k = min(cfg.top_k, logits.shape[-1]) if cfg.top_k > 0 else 0
    if k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if cfg.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative prob >= top_p; cutoff =
        # lowest logit inside that prefix. The argmax survives
        # unconditionally: a degenerate top_p <= 0 would otherwise mask
        # every candidate and hand categorical an all--inf row (it then
        # samples uniformly from garbage)
        keep = cum - probs < cfg.top_p
        keep = keep.at[:, 0].set(True)
        cutoff = jnp.min(
            jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1)


def _decode_body(model, params, sample_cfg: SampleConfig, rng, carry, i):
    """One recurrent decode step, the body of ``_generate_jit``'s scan.
    ``i`` is the ABSOLUTE emitted-token index (the rng fold_in key)."""
    token, states, t, done = carry
    logits, states = model.apply(params, token, states, t, method="decode_step")
    nxt = sample_logits(logits, jax.random.fold_in(rng, i + 1), sample_cfg)
    if sample_cfg.eos_token >= 0:
        # emit EOS itself, pad everything after it
        emitted = jnp.where(done, sample_cfg.pad_token, token)
        done = done | (emitted == sample_cfg.eos_token)
    else:
        emitted = token
    return (nxt, states, t + 1, done), emitted


@partial(jax.jit, static_argnums=(0, 3, 4))
def _generate_jit(
    model: TransformerLM,
    params: Any,
    prompt: Array,
    max_new_tokens: int,
    sample_cfg: SampleConfig,
    rng: Array,
) -> Array:
    """prompt [B, T0] -> generated [B, max_new_tokens]."""
    t0 = prompt.shape[1]
    # last-position-only head: the full-prompt [B, T, V] logits would cost
    # a T x D x V matmul + 4.3GB fp32 at T=32k for values generation drops
    logits, states = model.apply(params, prompt, method="prefill_last")
    first = sample_logits(logits, jax.random.fold_in(rng, 0), sample_cfg)
    done0 = jnp.zeros(first.shape, bool)
    body = partial(_decode_body, model, params, sample_cfg, rng)
    (_, _, _, _), tokens = jax.lax.scan(
        body,
        (first, states, jnp.int32(t0), done0),
        jnp.arange(max_new_tokens),
        length=max_new_tokens,
    )
    return jnp.moveaxis(tokens, 0, 1)  # [B, N]


# -- monolithic prefill (serving's repair and publish paths) -------------------
# Admission never prefills here: the SlotEngine stages a prompt into its carry
# and the unified program consumes it in pieces (below). A whole-prompt
# prefill has exactly two callers, both off the admission path: the
# degradation ladder's re-prefill rung and the prefix store's publish
# (serving/batching.py). Both need the piecewise and the monolithic state to
# agree only to rounding: they are two XLA programs, and what holds between
# them is equal tokens on every pinned seed and states equal to fp32 rounding
# on XLA:CPU (tests/test_prefill_inscan.py states the bound); on the chip,
# agreement is the cells' `correct` tolerance (ROADMAP C12). What is
# bit-for-bit is what ONE program gives twice: a rewind's replay, a
# suspend/resume row copy, a stored executable against its jit twin.


@partial(jax.jit, static_argnums=(0, 3))
def _prefill_carry_bucketed_jit(
    model: TransformerLM,
    params: Any,
    tokens: Array,
    sample_cfg: SampleConfig,
    rng: Array,
    sample_index: Array,
    done: Array,
    length: Array,
) -> Tuple[Array, Any, Array, Array]:
    """Bucketed prefill: ``tokens`` is right-padded to a bucket length and
    ``length`` (traced) is the real prompt length — ONE compile per bucket
    instead of one per novel prompt length (the compile-cache leak real
    traffic would otherwise hit). Padding is masked out of the state
    (masking contract: mixers.Mixer.prefill)."""
    logits, states = model.apply(params, tokens, length, method="prefill_last")
    nxt = sample_logits(
        logits, jax.random.fold_in(rng, sample_index), sample_cfg
    )
    return (nxt, states, length, done)


def bucket_for(length: int, buckets: Tuple[int, ...]) -> Optional[int]:
    """Smallest bucket >= length, or None."""
    for b in buckets:
        if b >= length:
            return b
    return None


def reprefill_carry(
    model: TransformerLM,
    params: Any,
    prompt: Array,
    emitted: List[Array],
    sample_cfg: SampleConfig,
    rng: Array,
    buckets: Tuple[int, ...],
    sample_index: Optional[int] = None,
    exec_lookup: Optional[Callable[[int], Any]] = None,
):
    """Rebuild a decode carry from prompt + the tokens already emitted —
    the degradation ladder's re-prefill rung: ``sample_index = n`` keeps
    the rng fold_in sequence aligned with the uninterrupted walk, and
    ``done`` is recomputed from the emitted tokens (rows that already hit
    EOS stay done). The rebuilt state is the uninterrupted walk's to
    rounding, not to the bit (see the section note above).

    ``sample_index`` overrides the default fold index (= the number of
    emitted tokens) for callers whose ``prompt`` is itself a rebased
    context containing earlier emissions — a resumed durable session's
    rng walk is anchored at the carry's absolute emit count, not at this
    segment's length (serving/session_store.py).

    Caveat: rows that emitted EOS are rebuilt from their PAD-filled tail
    rather than the post-EOS samples the uninterrupted carry held — those
    rows keep emitting PAD either way, but their dead-state contents
    differ from an uninterrupted run's."""
    seq = (
        jnp.concatenate([jnp.asarray(prompt, jnp.int32)]
                        + [jnp.asarray(e, jnp.int32) for e in emitted], axis=1)
        if emitted
        else jnp.asarray(prompt, jnp.int32)
    )
    n = seq.shape[1] - prompt.shape[1]
    done = None
    if sample_cfg.eos_token >= 0:
        done = (seq[:, prompt.shape[1]:] == sample_cfg.eos_token).any(axis=1)
    return prefill_carry(
        model, params, seq, sample_cfg, rng, buckets,
        sample_index=n if sample_index is None else sample_index,
        done=done, exec_lookup=exec_lookup,
    )


def prefill_carry(
    model: TransformerLM,
    params: Any,
    tokens: Array,
    sample_cfg: SampleConfig,
    rng: Array,
    buckets: Tuple[int, ...],
    sample_index: int = 0,
    done: Optional[Array] = None,
    exec_lookup: Optional[Callable[[int], Any]] = None,
):
    """tokens [B, T] -> the decode carry (next_token, states, t, done), by
    ONE whole-prompt forward. Not the admission path (see the section note):
    its callers are the ladder's re-prefill rung and the prefix store's
    publish, and both need it to agree with the in-scan pieces only to
    rounding.

    ``sample_index`` is the rng fold_in key for the first sampled token —
    0 for a fresh prompt (matching ``generate()``), or ``n`` when
    re-prefilling after ``n`` tokens were already emitted.

    ``buckets``: sorted pad-to lengths. The prompt is right-padded to the
    smallest bucket >= T and the real length rides in traced, so the jit
    cache stays bounded by the bucket count: ONE cache entry per bucket,
    a bucket-exact prompt included. A sequence longer than every bucket (a
    re-prefill of prompt + emitted on an engine whose buckets stop short
    of ``max_seq_len``) pads to ``max_seq_len``: one more entry, never one
    per length.

    ``exec_lookup``: bucket width -> an AOT-deserialized executable of
    THIS program (serving/exec_store.py) or None. A hit replaces the jit
    dispatch — the stored artifact was compiled from the identical
    program by the identical compiler, so its outputs are bitwise the
    wrapper's; statics (model, sample_cfg) are baked into it, the call
    passes only the dynamic operands."""
    tokens = jnp.asarray(tokens, jnp.int32)
    if done is None:
        done = jnp.zeros((tokens.shape[0],), bool)
    t = tokens.shape[1]
    pad_to = bucket_for(t, buckets) or model.cfg.max_seq_len
    padded = jnp.pad(tokens, ((0, 0), (0, pad_to - t)))
    exe = exec_lookup(pad_to) if exec_lookup is not None else None
    if exe is not None:
        return exe(
            params, padded, rng, jnp.int32(sample_index), done, jnp.int32(t),
        )
    return _prefill_carry_bucketed_jit(
        model, params, padded, sample_cfg, rng,
        jnp.int32(sample_index), done, jnp.int32(t),
    )


# -- slot-multiplexed batched decode (continuous batching) --------------------
# The SlotEngine (orion_tpu/serving/batching.py) multiplexes independent
# requests over the rows of ONE batched carry: per-slot positions (vector
# t), per-slot rng streams folded from each request's own seed, and a
# per-slot active mask. The body below is _decode_body generalized row-wise
# — every op is batch-row-independent, so each slot's walk is
# bitwise-identical to serving that request alone (the acceptance property
# tests/test_batching.py pins for slot counts {2, 4, 8}).
#
# Tensor parallelism (ISSUE 14) adds NO program variants here: the same
# jit wrappers are mesh-aware through their INPUTS. When the engine
# places params by the training sharding rules and the state head-sharded
# (parallel/decode.py), the jit cache keys on those shardings and GSPMD
# partitions each program — two all-reduces per block per decode step
# (wo/down psum-at-output; golden decode_batched_tp{2,4}.json), zero
# state collectives. Tokens stay bitwise the unsharded walk's
# (tests/test_tp_serving.py); anything per-slot stays replicated so the
# admission/eviction row ops below work unchanged on any footprint.


def _sample_rows(logits: Array, keys: Array, cfg: SampleConfig) -> Array:
    """Per-row sampling with per-row keys: row b is bitwise what
    ``sample_logits(logits[b:b+1], keys[b], cfg)`` returns solo (threefry
    is counter-based, so the vmapped draw equals the unbatched one)."""
    if cfg.greedy:
        return jnp.argmax(logits, axis=-1)
    return jax.vmap(lambda lg, k: sample_logits(lg[None], k, cfg)[0])(
        logits, keys
    )


def _moe_counted(model) -> bool:
    """Do the model's MoE layers honour ``live`` and count their rows
    (``models/moe.py::masks_rows``: the dropless layers one device holds)?
    Its decode programs then hand them the rows whose token counts and sum
    the counters they sow."""
    from orion_tpu.models.moe import masks_rows

    return masks_rows(model.cfg, model.quant, model.mesh)


def _counted(model, params, *args, method: str):
    """``model.apply(params, *args, method=method)`` and, for a model whose
    MoE layers count their rows (``_moe_counted``), the row counters they
    sowed as ``[4]`` int32 (``models/moe.py::stats_vector``); None for every
    other model, whose programs stay what they were."""
    if not _moe_counted(model):
        return model.apply(params, *args, method=method), None
    from orion_tpu.models.moe import stats_vector

    out, sown = model.apply(
        params, *args, method=method, mutable=["moe_stats"]
    )
    return out, stats_vector(sown.get("moe_stats", {}))


def _decode_step_counted(model, params, token, states, t, rows, live):
    """One decode step; MoE layers that count their rows get ``live`` [S],
    the rows whose token counts."""
    args = (token, states, t, rows) + ((live,) if _moe_counted(model) else ())
    return _counted(model, params, *args, method="decode_step")


def _scan_outputs(model, ys):
    """A chunk scan's stacked outputs -> (tokens [S, n_steps], the steps'
    MoE row counters summed [4] or None)."""
    tokens, stats = ys if _moe_counted(model) else (ys, None)
    return jnp.moveaxis(tokens, 0, 1), None if stats is None else stats.sum(0)


def _decode_batched_body(
    model, params, sample_cfg: SampleConfig, rngs, active, rows, carry, _
):
    """One slot-multiplexed decode step. carry = (token [S], states,
    t [S], emit [S], done [S]); ``rngs`` [S, 2] are per-slot PRNG keys
    (each request's own seed — REQUIRED for batched-vs-solo bitwise
    parity), ``emit`` the per-slot absolute emitted-token index (each
    slot's rng fold_in key, the vector form of _decode_body's ``i``),
    ``active`` [S] masks free slots (their rows still compute — the scan
    shape is static — but emit PAD and hold their position). With
    ``rows`` (``ops.dispatch.decode_live_rows`` of ``active``) the linear
    layers skip the free rows' (S, z) altogether: admission overwrites
    those rows."""
    token, states, t, emit, done = carry
    (logits, states), stats = _decode_step_counted(
        model, params, token, states, t, rows, active
    )
    keys = jax.vmap(jax.random.fold_in)(rngs, emit + 1)
    nxt = _sample_rows(logits, keys, sample_cfg)
    if sample_cfg.eos_token >= 0:
        emitted = jnp.where(done, sample_cfg.pad_token, token)
        done = done | (emitted == sample_cfg.eos_token)
    else:
        emitted = token
    emitted = jnp.where(active, emitted, sample_cfg.pad_token)
    t = jnp.where(active, t + 1, t)  # free slots must not walk off the
    emit = emit + 1                  # positional/rotary tables
    out = emitted if stats is None else (emitted, stats)
    return (nxt, states, t, emit, done), out


@partial(jax.jit, static_argnums=(0, 5, 6))
def _decode_batched_chunk_jit(
    model: TransformerLM,
    params: Any,
    carry: Any,
    rngs: Array,
    active: Array,
    n_steps: int,
    sample_cfg: SampleConfig,
) -> Tuple[Any, Array]:
    body = partial(
        _decode_batched_body, model, params, sample_cfg, rngs, active,
        decode_live_rows(active, backend=model.cfg.backend),
    )
    carry, ys = _scan_chunk(model, body, carry, n_steps, active, False)
    tokens, stats = _scan_outputs(model, ys)  # [S, n_steps]
    return (carry, tokens) if stats is None else (carry, tokens, stats)


def decode_batched_chunk(
    model: TransformerLM,
    params: Any,
    carry: Any,
    rngs: Array,
    active: Array,
    n_steps: int,
    sample_cfg: SampleConfig,
):
    """Advance the slot-multiplexed carry by ``n_steps`` tokens (one
    bounded scan over ALL slots). Everything per-slot — positions, emit
    indices, rng keys, the active mask — rides in traced, so the engine's
    whole serving lifetime costs ONE compile per (slot count, chunk
    length) regardless of arrival order (asserted via jit cache stats in
    tests/test_batching.py). Returns (carry, tokens [S, n_steps]); for a
    row-counting MoE model (``_moe_counted``) also the boundary's MoE row
    counters [4] int32 (``models/moe.py::STAT_NAMES``), as every
    slot-multiplexed program below does."""
    return _decode_batched_chunk_jit(
        model, params, carry, rngs, active, int(n_steps), sample_cfg
    )


# -- in-scan chunked prefill (continuous batching, ISSUE 7) -------------------
# A solo prefill on the host thread between chunk boundaries would stall
# every resident slot behind one long prompt (head-of-line blocking;
# Orca/Sarathi-Serve territory). Because prefill and decode share the same
# recurrent carry, a prefilling request instead OCCUPIES a slot and consumes
# its prompt inside the batched program: each unified chunk first runs one
# ``prefill_chunk``-token parallel-forward PIECE for each waiting slot, up
# to a cap a boundary (transformer.prefill_extend_step — chunk-aligned
# pieces walk the monolithic prefill's left fold, so the carry is what
# ``prefill_carry`` builds to fp32 rounding on XLA:CPU and the tokens are
# the solo scan's on every pinned seed; see the note above
# ``prefill_carry``), then runs the decode scan with the rows still
# mid-prompt frozen (state/position/emit held, PAD emitted).
# ``prefill_chunk`` is the width of ONE slot's piece; each piece is a
# batch-1 forward, so a boundary pays for the slots it serves and for no
# other, and its co-resident decoders wait for at most ``cap`` pieces.
# Token-by-token prompt feeding inside the scan body would drift further —
# a single-row matvec accumulates differently from the prefill gemm —
# which is why the prompt is consumed as parallel pieces at the top of the
# chunk rather than as masked scan steps.


def _where_rows(mask: Array, new: Any, old: Any) -> Any:
    """Per-row select over a state pytree: row b takes ``new`` where
    ``mask[b]``; frozen rows keep ``old`` BITWISE (select, not blend)."""
    return jax.tree.map(
        lambda n, o: jnp.where(
            mask.reshape(mask.shape + (1,) * (n.ndim - 1)), n, o
        ),
        new, old,
    )


def _freeze_rows(model, rows, mask: Array, new: Any, old: Any) -> Any:
    """The per-layer states with rows outside ``mask`` held at ``old``.
    Without a row list that is :func:`_where_rows` over every layer. With
    one (``decode_live_rows`` under a Pallas backend) a mixer with
    ``rows_in_place`` (the linear layers' kernel) never touched those
    rows, and a select — which reads old and new — would bring the
    full-width state traffic back: only the other layers are selected."""
    if rows is None:
        return _where_rows(mask, new, old)
    return [
        n if MIXERS[lt].rows_in_place else _where_rows(mask, n, o)
        for lt, n, o in zip(model.cfg.resolved_layer_types, new, old)
    ]


def _scan_chunk(model, step, carry, n_steps: int, live: Array, donated: bool):
    """The chunk's decode scan, shared by the three slot-multiplexed
    programs: ``n_steps`` of ``step`` (a decode body with everything but
    ``(carry, _)`` bound) from ``carry``. Each layer's state is split by
    its ``Mixer.chunk_split``: the scan carries one part and closes over
    the other, which it only reads (the linear layers' ``(S, z)`` under a
    row-list backend, written once a chunk; a KV cache where the program's
    carry is ``donated`` and held once), and ``Mixer.chunk_merge`` puts the
    two together after it for the rows of ``live`` [S]. Where no layer
    holds anything this is the plain scan over the whole carry. Returns
    (carry, tokens [n_steps, S])."""
    token, states, t, emit, done = carry
    kinds = model.cfg.resolved_layer_types
    split = [
        MIXERS[lt].chunk_split(model.cfg, lt, st, n_steps, t, donated)
        for lt, st in zip(kinds, states)
    ]
    held = [h for h, _ in split]
    if not any(held):
        return jax.lax.scan(step, carry, None, length=n_steps)

    def body(c, _):
        token, carried, t, emit, done = c
        whole = [{**h, **cc} for h, cc in zip(held, carried)]
        (token, new, t, emit, done), emitted = step(
            (token, whole, t, emit, done), None
        )
        carried = [{k: st[k] for k in cc} for st, cc in zip(new, carried)]
        return (token, carried, t, emit, done), emitted

    (token, carried, t, emit, done), tokens = jax.lax.scan(
        body, (token, [c for _, c in split], t, emit, done), None,
        length=n_steps,
    )
    states = [
        MIXERS[lt].chunk_merge(model.cfg, lt, h, c, live)
        for lt, h, c in zip(kinds, held, carried)
    ]
    return (token, states, t, emit, done), tokens


@partial(jax.jit, static_argnums=(0, 7))
def _prefill_extend_row(
    model: TransformerLM,
    params: Any,
    pbuf: Array,
    states: Any,
    sel: Array,
    offset: Array,
    length: Array,
    pchunk: int,
):
    """Advance ONE slot's decode-state row by a prompt piece: row ``sel``
    consumes ``length`` tokens of ``pbuf[sel]`` starting at ``offset``
    as a batch-1 parallel forward (bitwise the solo
    ``prefill_extend_step``'s op sequence; ``length`` 0 is a bitwise
    no-op and the caller guards the write-back anyway). Batch-1 is the
    point: the piece costs one slot's forward, not slots x one — a
    vmapped all-rows piece was measured 2-4x a pure-decode boundary on
    the tiny config, which is exactly the co-resident latency tax this
    path exists to kill. Jitted so that the unified program, which holds
    the piece twice (inline and in its loop), traces and lowers the
    model's forward once. Returns (last-real-row logits [V], the
    advanced state row), and for a row-counting MoE model
    (``_moe_counted``) the piece's MoE row counters [4] (its ``length`` real
    rows route; padding does not)."""
    idx = jnp.clip(offset + jnp.arange(pchunk), 0, pbuf.shape[1] - 1)
    piece = jnp.take(pbuf[sel], idx)[None]
    st1 = jax.tree.map(lambda x: x[sel][None], states)
    (lg, st), stats = _counted(
        model, params, piece, st1, offset, length, method="prefill_extend_step"
    )
    out = lg[0], jax.tree.map(lambda x: x[0], st)
    return out if stats is None else out + (stats,)


def _decode_batched_prefill_body(
    model, params, sample_cfg: SampleConfig, rngs, emitting, rows, carry, _
):
    """The slot-multiplexed decode step with still-prefilling rows FROZEN:
    ``emitting`` [S] is ``active & (t >= prompt_len)`` — rows past their
    prompt decode exactly as in :func:`_decode_batched_body` (every op on
    an emitting row computes the identical value, so the pure-decode walk
    is reproduced bitwise), while mid-prefill rows hold their state,
    position, emit index, and done flag, and emit PAD. With ``rows``
    (``decode_live_rows`` of ``emitting``) the linear layers never touch a
    frozen row's (S, z) in the first place (:func:`_freeze_rows`). The
    pure body's compiled program must stay byte-identical on the XLA
    path (golden ``decode_batched_tiny``)."""
    token, states, t, emit, done = carry
    (logits, new_states), stats = _decode_step_counted(
        model, params, token, states, t, rows, emitting
    )
    keys = jax.vmap(jax.random.fold_in)(rngs, emit + 1)
    nxt = _sample_rows(logits, keys, sample_cfg)
    if sample_cfg.eos_token >= 0:
        emitted = jnp.where(done, sample_cfg.pad_token, token)
        # guard with ``emitting``: a mid-prefill row's token slot holds
        # garbage that must not latch the done flag
        done = done | (emitting & (emitted == sample_cfg.eos_token))
    else:
        emitted = token
    emitted = jnp.where(emitting, emitted, sample_cfg.pad_token)
    states = _freeze_rows(model, rows, emitting, new_states, states)
    token = jnp.where(emitting, nxt, token)
    t = jnp.where(emitting, t + 1, t)
    emit = jnp.where(emitting, emit + 1, emit)
    out = emitted if stats is None else (emitted, stats)
    return (token, states, t, emit, done), out


def prefill_piece_cap(slots: int, chunk: int) -> int:
    """How many prompt pieces one boundary of the unified program may run:
    ``slots // chunk``, at least 1. A full server finishes about
    ``slots / (mean output / chunk)`` requests a boundary, so a cap of
    that order keeps up with steady state while it bounds what a burst
    of admissions adds to the boundary its co-resident decoders share."""
    return max(1, slots // chunk)


def prefill_overdue_after(slots: int, chunk: int) -> int:
    """Boundaries a waiting slot may be passed over before it is OVERDUE
    and goes ahead of every slot that is not:
    ``ceil(slots / prefill_piece_cap)``, the boundaries a full house of
    waiting slots needs at the cap."""
    return -(-slots // prefill_piece_cap(slots, chunk))


def _prefill_selection(active: Array, rem: Array, pwait: Array, chunk: int):
    """Stage 1's schedule: (``order`` [S], the slot indices in serving
    order, waiting slots first; ``n``, how many of them this boundary
    serves). See :func:`_decode_batched_prefill_chunk_jit`."""
    slots = active.shape[0]
    waiting = active & (rem > 0)
    overdue = jnp.where(
        pwait >= prefill_overdue_after(slots, chunk), pwait, 0
    )
    order = jnp.lexsort(  # the last key is the first compared
        (jnp.arange(slots), rem, -overdue, (~waiting).astype(jnp.int32))
    )
    return order, jnp.minimum(waiting.sum(), prefill_piece_cap(slots, chunk))


@partial(jax.jit, static_argnums=(0, 9, 10, 11))
def _decode_batched_prefill_chunk_jit(
    model: TransformerLM,
    params: Any,
    carry: Any,
    rngs: Array,
    active: Array,
    pbuf: Array,
    plen: Array,
    pfold: Array,
    pwait: Array,
    n_steps: int,
    pchunk: int,
    sample_cfg: SampleConfig,
) -> Tuple[Any, Array]:
    """One UNIFIED chunk: a prompt piece for each waiting slot, then the
    decode scan.

    Stage 1 — every slot with prompt left (``active & t < plen``) is
    WAITING; the boundary serves the first ``n = min(waiting, cap)`` of
    them in this order, one batch-1 piece of at most ``pchunk`` tokens
    each (:func:`_prefill_extend_row`), and a slot gets at most one
    piece a boundary, so piece boundaries stay chunk-aligned:

    - *order*: overdue slots first, the longest passed over first; then
      shortest remaining prompt first; ties to the lowest slot index
      (the slot closest to emitting frees its output stream soonest).
      ``pwait`` [S] is the number of boundaries each slot has been
      passed over since it was admitted or last served, and a slot is
      overdue from ``pwait >= prefill_overdue_after(slots, n_steps)``.
    - *cap*: ``prefill_piece_cap(slots, n_steps)`` pieces a boundary.
    - *the wait's bound*: slots that are overdue leave in the order they
      became so, ``cap`` a boundary, and a slot admitted later cannot
      overtake them; at most ``slots - 1`` others are ahead, so no slot
      is passed over more than ``prefill_overdue_after + (slots - 1) //
      cap`` boundaries in a row.

    The order is a function of carry-resident values and ``pwait``, which
    the host itself counts, so the scheduler mirrors it with no readback
    (``SlotEngine._selected_prefill_slots``). ``n`` is traced: the first
    piece runs inline and a loop of ``n - 1`` trips the others, so a
    boundary with one waiting slot does exactly the work of the
    one-piece program this replaced, and one with none (a rung-3 replay
    can mask the only one out) discards its piece as that program did.
    A slot whose prompt completes samples its first token from its
    piece's last-real-row logits at rng-fold ``pfold`` (the token
    ``generate()`` samples first at that seed). Stage 2 — the chunk's decode
    scan, with rows still mid-prompt frozen. Everything per-slot rides
    traced, so mixed prefill/decode traffic costs ONE compile per
    (slots, chunk, prompt_bucket) — ``prompt_bucket`` being the staged
    buffer's width. A piece never exceeds that width (a single piece
    covers any prompt the buffer can hold)."""
    token, states, t, emit, done = carry
    piece = min(pchunk, pbuf.shape[1])  # both static: piece <= the bucket
    rem = jnp.maximum(plen - t, 0)
    order, n = _prefill_selection(active, rem, pwait, n_steps)

    held = _moe_counted(model)

    def serve(k, served):
        token, states, t, emit, *counted = served
        sel = order[k]
        # false only for the inline first piece when no slot waits: its
        # garbage is then discarded bitwise, as a replay needs it to be
        live = k < n
        cons = jnp.where(live, jnp.minimum(rem[sel], piece), 0)
        logits1, fed, *stats = _prefill_extend_row(
            model, params, pbuf, states, sel, t[sel], cons, piece
        )
        counted = [c + s for c, s in zip(counted, stats)]
        states = jax.tree.map(
            lambda x, new: x.at[sel].set(jnp.where(live, new, x[sel])),
            states, fed,
        )
        completed = live & (rem[sel] <= piece)
        key = jax.random.fold_in(rngs[sel], pfold[sel])
        first = _sample_rows(logits1[None], key[None], sample_cfg)[0]
        token = token.at[sel].set(jnp.where(completed, first, token[sel]))
        emit = emit.at[sel].set(jnp.where(completed, pfold[sel], emit[sel]))
        return (token, states, t.at[sel].set(t[sel] + cons), emit, *counted)

    # the first piece runs inline, where XLA schedules it among the
    # program's opening copies and casts as it did the one piece this
    # program used to run (inside the loop a piece measured 9.5 ms on
    # the chip against 5.2 ms inline); the loop runs the pieces after it
    zero = (jnp.zeros((4,), jnp.int32),) if held else ()
    served = serve(0, (token, states, t, emit, *zero))
    token, states, t, emit, *counted = jax.lax.fori_loop(
        1, jnp.maximum(n, 1), serve, served
    )
    emitting = active & (t >= plen)
    body = partial(
        _decode_batched_prefill_body, model, params, sample_cfg, rngs,
        emitting, decode_live_rows(emitting, backend=model.cfg.backend),
    )
    carry, ys = _scan_chunk(
        model, body, (token, states, t, emit, done), n_steps, emitting, False
    )
    tokens, stats = _scan_outputs(model, ys)  # [S, n_steps]
    return (carry, tokens) if stats is None else (carry, tokens, stats + counted[0])


# -- the carry held once (ISSUE 35) -------------------------------------------
# A decode state of GBs (a KV cache per slot) fits the device once, not
# twice. The two programs above return a NEW carry beside the one they were
# given, and XLA copies whatever a loop carries at the loop's entry, so a
# program that walks the cache through its piece loop or its scan needs a
# second cache even when its operands are donated. An engine whose state
# does not fit twice (``SlotEngine.donate_carry``) therefore runs a boundary
# as separate DONATED programs, dispatched back to back (the device runs
# them in order while the host enqueues the next): one
# ``_prefill_piece_donated_jit`` for each slot the boundary serves — the
# host knows the schedule, ``SlotEngine._selected_prefill_slots`` — whose
# row writes are plain in-place slice updates, then one
# ``_decode_scan_donated_jit`` whose scan carries only what each mixer's
# ``chunk_split`` says it must (the delta rule's state, a chunk's new cache
# rows) and closes over the rest, merged back after the scan in place.
# Same mathematics, same schedule; what it gives up is the boundary
# snapshot the ladder rewinds to.


@partial(jax.jit, static_argnums=(0, 8, 9), donate_argnums=(2,))
def _prefill_piece_donated_jit(
    model: TransformerLM,
    params: Any,
    carry: Any,
    rngs: Array,
    pbuf: Array,
    plen: Array,
    pfold: Array,
    sel: Array,
    pchunk: int,
    sample_cfg: SampleConfig,
) -> Any:
    """Slot ``sel`` consumes its next prompt piece, in place: stage 1 of
    :func:`_decode_batched_prefill_chunk_jit` for one slot the host chose.
    The carry is donated and comes back with that row advanced; a slot
    whose prompt completes samples its first token as there."""
    token, states, t, emit, done = carry
    piece = min(pchunk, pbuf.shape[1])
    rem = jnp.maximum(plen[sel] - t[sel], 0)
    cons = jnp.minimum(rem, piece)
    logits1, fed, *stats = _prefill_extend_row(
        model, params, pbuf, states, sel, t[sel], cons, piece
    )
    states = jax.tree.map(lambda x, new: x.at[sel].set(new), states, fed)
    completed = (rem > 0) & (rem <= piece)
    key = jax.random.fold_in(rngs[sel], pfold[sel])
    first = _sample_rows(logits1[None], key[None], sample_cfg)[0]
    token = token.at[sel].set(jnp.where(completed, first, token[sel]))
    emit = emit.at[sel].set(jnp.where(completed, pfold[sel], emit[sel]))
    carry = token, states, t.at[sel].set(t[sel] + cons), emit, done
    return (carry, stats[0]) if stats else carry


@partial(jax.jit, static_argnums=(0, 6, 7), donate_argnums=(2,))
def _decode_scan_donated_jit(
    model: TransformerLM,
    params: Any,
    carry: Any,
    rngs: Array,
    active: Array,
    plen: Array,
    n_steps: int,
    sample_cfg: SampleConfig,
) -> Tuple[Any, Array]:
    """Stage 2 of :func:`_decode_batched_prefill_chunk_jit`, the chunk's
    decode scan with rows still mid-prompt frozen, on a donated carry: the
    scan reads the KV caches and carries a chunk's new rows
    (:func:`_scan_chunk`), so nothing of a cache is copied."""
    token, states, t, emit, done = carry
    emitting = active & (t >= plen)
    step = partial(
        _decode_batched_prefill_body, model, params, sample_cfg, rngs,
        emitting, decode_live_rows(emitting, backend=model.cfg.backend),
    )
    carry, ys = _scan_chunk(model, step, carry, n_steps, emitting, True)
    tokens, stats = _scan_outputs(model, ys)
    return (carry, tokens) if stats is None else (carry, tokens, stats)


def decode_boundary_donated(
    model: TransformerLM,
    params: Any,
    carry: Any,
    rngs: Array,
    active: Array,
    pbuf: Optional[Array],
    plen: Array,
    pfold: Array,
    served: Tuple[int, ...],
    n_steps: int,
    pchunk: int,
    sample_cfg: SampleConfig,
):
    """One boundary on a donated carry: a prompt piece for each slot of
    ``served`` (the host's schedule, in its order), then the decode scan.
    ``carry`` is consumed. Returns (carry, tokens [S, n_steps]); for a
    row-counting MoE model (``_moe_counted``) also the TUPLE of its
    programs' MoE row counters ([4] each, on the device: whoever syncs next
    sums them)."""
    counted = []
    for sel in served:
        carry = _prefill_piece_donated_jit(
            model, params, carry, rngs, pbuf, plen, pfold, jnp.int32(sel),
            int(pchunk), sample_cfg,
        )
        if _moe_counted(model):
            carry, stats = carry
            counted.append(stats)
    out = _decode_scan_donated_jit(
        model, params, carry, rngs, active, plen, int(n_steps), sample_cfg
    )
    if not _moe_counted(model):
        return out
    return out[0], out[1], tuple(counted) + (out[2],)


def decode_batched_prefill_chunk(
    model: TransformerLM,
    params: Any,
    carry: Any,
    rngs: Array,
    active: Array,
    pbuf: Array,
    plen: Array,
    pfold: Array,
    pwait: Array,
    n_steps: int,
    pchunk: int,
    sample_cfg: SampleConfig,
):
    """Advance the slot-multiplexed carry by one unified prefill+decode
    chunk (see :func:`_decode_batched_prefill_chunk_jit`). The engine
    calls this only while at least one slot is mid-prefill; pure-decode
    boundaries stay on :func:`decode_batched_chunk`, whose compiled
    program this addition must not perturb."""
    return _decode_batched_prefill_chunk_jit(
        model, params, carry, rngs, active, pbuf, plen, pfold, pwait,
        int(n_steps), int(pchunk), sample_cfg,
    )


# -- self-speculative decode (ISSUE 13) ---------------------------------------
# The hybrid config contains its own draft model for free: the global-
# linear layers are pure O(1) recurrence, so they can run ahead k tokens
# (transformer.draft_step — embed -> linear blocks only -> head, shadow
# (S, z), no cache touched) at a fraction of the full forward's cost.
# The full model then verifies ALL k drafts in ONE batched piece
# (transformer.verify_step): every weight matmul runs once as a k-row
# gemm — the speculative win on weight-bandwidth-bound hardware — while
# the state recurrence replays decode_step's exact per-token op sequence,
# so the verify logits are BITWISE the plain decode walk's logits.
# Verification is token-matching against the full model's samples at the
# SAME rng folds the plain walk uses (the draft samples with the same
# folds too — shared randomness maximizes matches in sampled mode): the
# emitted tokens are therefore ALWAYS the plain walk's tokens, greedy
# and sampled alike — the draft can only change speed, never output —
# which is strictly stronger than the distribution-identity classical
# leftover-rejection speculation offers. Rejected drafts never touch the
# carry: the clamped advance (transformer.advance_verified_states)
# re-applies exactly the accepted prefix's updates.


def _spec_round_body(
    model, params, sample_cfg: SampleConfig, rngs, active, spec_on,
    depth: int, carry,
):
    """One speculative round over the slot-multiplexed carry: draft up
    to ``depth`` tokens per slot, verify them all in one batched piece,
    advance each slot by its accepted prefix + 1. Returns
    (new_carry, emitted [S, depth+1], accepted [S]).

    Per-slot: the round consumes ``keep = accepted + 1`` fed tokens
    (the pending token always verifies — its logits consumed only real
    context) and emits ``keep`` values with the plain body's EOS/PAD
    semantics; the new pending token is the full model's sample at fold
    ``emit + keep`` — exactly the invariant the plain body maintains, so
    speculative and plain boundaries interleave bitwise-transparently
    (mid-prefill boundaries ride the unified program, non-speculating
    slots ride with ``spec_on`` False and advance one token per round)."""
    from orion_tpu.models.transformer import linear_layer_indices

    token, states, t, emit, done = carry
    k = depth
    lin = linear_layer_indices(model.cfg)
    lin_states = [states[i] for i in lin]

    # 1) draft: k cheap linear-trunk steps; the shadow (S, z) dies here
    def draft_body(c, _):
        tok, lst, tt, em = c
        lg, lst = model.apply(params, tok, lst, tt, method="draft_step")
        keys = jax.vmap(jax.random.fold_in)(rngs, em + 1)
        nxt = _sample_rows(lg, keys, sample_cfg)
        return (nxt, lst, tt + 1, em + 1), nxt

    if k:
        _, drafts = jax.lax.scan(
            draft_body, (token, lin_states, t, emit), None, length=k
        )
        drafts = jnp.moveaxis(drafts, 0, 1)  # [S, k]
    else:
        drafts = jnp.zeros((token.shape[0], 0), token.dtype)
    fed = jnp.concatenate([token[:, None], drafts], axis=1)  # [S, k+1]

    # 2) verify: full-model logits at every fed position, one piece
    logits, upds = model.apply(params, fed, states, t, method="verify_step")

    # 3) re-sample at the exact folds the plain walk burns
    def samp_body(em, lg_j):
        keys = jax.vmap(jax.random.fold_in)(rngs, em + 1)
        return em + 1, _sample_rows(lg_j, keys, sample_cfg)

    _, cs = jax.lax.scan(samp_body, emit, jnp.moveaxis(logits, 1, 0))
    cs = jnp.moveaxis(cs, 0, 1)  # [S, k+1]; cs[:, j] is the fold-emit+1+j draw

    # 4) accepted prefix: token-match, clamped for non-speculating rows
    if k:
        match = (drafts == cs[:, :k]).astype(jnp.int32)
        n = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
    else:
        n = jnp.zeros(token.shape, jnp.int32)
    n = jnp.where(spec_on & active, n, 0)
    keep = jnp.where(active, n + 1, 0)  # fed tokens consumed per row

    # 5) emitted values, replaying the plain body's done/EOS walk
    if sample_cfg.eos_token >= 0:
        def emit_body(dn, j):
            live = active & (j < keep)
            e = jnp.where(dn | ~live, sample_cfg.pad_token, fed[:, j])
            dn = dn | (live & (e == sample_cfg.eos_token))
            return dn, e

        done2, es = jax.lax.scan(emit_body, done, jnp.arange(k + 1))
        emitted = jnp.moveaxis(es, 0, 1)
    else:
        live = active[:, None] & (jnp.arange(k + 1)[None, :] < keep[:, None])
        emitted = jnp.where(live, fed, sample_cfg.pad_token)
        done2 = done

    # 6) clamped advance: exactly the accepted prefix's updates land
    states = model.apply(
        params, states, upds, t, keep, method="advance_verified_states"
    )

    # 7) the new pending token: the full model's fold-(emit+keep) sample
    nxt = jnp.take_along_axis(cs, n[:, None], axis=1)[:, 0]
    token = jnp.where(active, nxt, token)
    return (token, states, t + keep, emit + keep, done2), emitted, n


@partial(jax.jit, static_argnums=(0, 6, 7))
def _decode_batched_spec_round_jit(
    model: TransformerLM,
    params: Any,
    carry: Any,
    rngs: Array,
    active: Array,
    spec_on: Array,
    depth: int,
    sample_cfg: SampleConfig,
) -> Tuple[Any, Array, Array]:
    return _spec_round_body(
        model, params, sample_cfg, rngs, active, spec_on, depth, carry
    )


def decode_batched_spec_round(
    model: TransformerLM,
    params: Any,
    carry: Any,
    rngs: Array,
    active: Array,
    spec_on: Array,
    depth: int,
    sample_cfg: SampleConfig,
):
    """Advance the slot-multiplexed carry by one speculative round (see
    :func:`_spec_round_body`). Everything per-slot — positions, folds,
    the active and per-slot speculation masks — rides traced, so the
    engine's lifetime costs ONE compile per (slots, spec depth, qmode);
    the plain and unified programs' compiled bytes are untouched (golden
    ``decode_batched_tiny`` / ``decode_batched_prefill_tiny``)."""
    return _decode_batched_spec_round_jit(
        model, params, carry, rngs, active, spec_on, int(depth), sample_cfg
    )


# -- serving program identities (ISSUE 15) ------------------------------------
# The canonical name -> jit-wrapper registry for every program the serving
# path launches. Observability keys off these names: the Server's
# compile_cache_entries gauges iterate it, the cost ledger's harvest
# (aot.decode_cost_entries) and the engine's first-call compile-time
# observations use the same kinds, and obs.cost.program_key() renders the
# (slots, chunk, bucket, qmode, tp) identity string the golden snapshots
# and aot.decode_plan pin — ONE vocabulary from compiled program to fleet
# endpoint, so a /costz row, a cache gauge, and a golden snapshot can
# never name the same program three different ways.

DECODE_PROGRAMS = {
    "decode_batched": _decode_batched_chunk_jit,
    "unified_prefill": _decode_batched_prefill_chunk_jit,
    "prefill_piece_donated": _prefill_piece_donated_jit,
    "decode_scan_donated": _decode_scan_donated_jit,
    "spec_round": _decode_batched_spec_round_jit,
    "prefill_bucketed": _prefill_carry_bucketed_jit,
}


def _compute_dtype_modules(model: TransformerLM, params: Any) -> set:
    """The ``nn.Dense`` / ``DenseGeneral`` / ``Einsum`` modules a decode
    step calls whose ``dtype`` is the model's compute dtype, by module path:
    flax's ``promote_dtype`` casts their parameters to it before the
    contraction. Seen, not named: one ``jax.eval_shape`` of ``decode_step``
    under ``nn.intercept_methods``; a projection whose ``dtype`` is fp32
    (the MoE router) is not in it, nor a parameter read by hand. Blocks
    that are the same module but for their name (24 of ``lm_1b3``'s 24) are
    observed ONCE: the others' steps are skipped and given the first one's
    paths under their own name."""
    import flax.linen as nn

    from orion_tpu.models.transformer import Block, _dtype

    cdt = jnp.dtype(_dtype(model.cfg.dtype))
    seen, blocks = set(), {}

    def watch(call, args, kwargs, context):
        m = context.module
        if isinstance(m, Block) and context.method_name == "decode_step":
            kind = (type(m),) + tuple(
                getattr(m, f.name) for f in dataclasses.fields(m)
                if f.name not in ("name", "parent")
            )
            same = blocks.setdefault(kind, [])
            same.append(tuple(m.path))
            if len(same) > 1:
                return args[:2]  # (x, state): a block's step keeps both shapes
        elif (
            context.method_name == "__call__"
            and isinstance(m, (nn.Dense, nn.DenseGeneral, nn.Einsum))
            and m.dtype is not None
            and jnp.dtype(m.dtype) == cdt
        ):
            seen.add(tuple(m.path))
        return call(*args, **kwargs)

    def step(variables):
        one = jnp.zeros((1,), jnp.int32)
        states = init_decode_state(model.cfg, 1)
        return model.apply(variables, one, states, one[0], method="decode_step")

    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params
    )
    with nn.intercept_methods(watch):
        jax.eval_shape(step, abstract)
    for first, *others in blocks.values():
        inside = [p[len(first):] for p in seen if p[:len(first)] == first]
        seen.update(other + rest for other in others for rest in inside)
    return seen


@partial(jax.jit, static_argnums=(0,))
def _cast_leaves_jit(model: TransformerLM, leaves):
    from orion_tpu.models.transformer import _dtype

    return [x.astype(_dtype(model.cfg.dtype)) for x in leaves]


def serving_params(model: TransformerLM, params: Any) -> Any:
    """The tree the serving programs are handed: ``params`` with every leaf
    that ITS OWN MODULE would cast to a narrower compute dtype before its
    first arithmetic use (:func:`_compute_dtype_modules`) replaced by that
    cast, made once, in one jitted program. The programs then hold no cast
    of it and compute the same values: ``promote_dtype`` does exactly this
    cast inside them otherwise, once a CALL (XLA hoists it out of the step
    scan: 169 converts in ``lm_1b3``'s ENTRY, 7.3 GB and 10.6 ms a decode
    boundary on one v5e; the 16 steps themselves stream bf16 copies either
    way, 59.56 ms over fp32 parameters and 58.96 over the copy made
    beforehand; PERF.md section 6, PR 44).

    Everything else comes back as handed in, the same objects: norm scales,
    embedding and position tables, decay parameters, fp32 projections,
    parameters a module reads by hand. A tree with no matmul weight to cast
    (bf16 parameters, a quantized model, fp32 compute) comes back ``is`` the
    same and traces nothing. Arrays or their ``ShapeDtypeStruct``s
    (``aot.py`` keys and lowers the programs on the latter).

    The answers are the handed tree's to the bit on the CPU
    (``tests/test_serving_weights.py``) and, on the TPU, for a request
    served alone (every layer's state at every boundary, 513 ids) and for
    every module's output of a prompt piece. Among 48 co-resident requests
    4 answer a near-tie differently (one bf16 ulp of a logit, downstream of
    every state update, inside a ``unified_prefill`` call that XLA compiles
    differently around bf16 arguments): the gap to the fp32 reference is the
    same on both sides."""
    from orion_tpu.models.transformer import _dtype

    if model.quant:
        return params
    cdt = jnp.dtype(_dtype(model.cfg.dtype))
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    # a contraction's weight has two axes or more: a bf16 tree's fp32 leaves
    # are vectors (norm scales, decay parameters), and it pays no abstract step
    wide = [
        i for i, (_, x) in enumerate(flat)
        if x.ndim >= 2 and x.dtype.itemsize > cdt.itemsize
    ]
    if not wide:
        return params
    modules = _compute_dtype_modules(model, params)
    picked = [
        i for i in wide
        if tuple(getattr(k, "key", None) for k in flat[i][0][1:-1]) in modules
    ]
    if not picked:
        return params
    leaves = [x for _, x in flat]
    if isinstance(leaves[picked[0]], jax.ShapeDtypeStruct):
        cast = [
            jax.ShapeDtypeStruct(
                leaves[i].shape, cdt, sharding=leaves[i].sharding
            )
            for i in picked
        ]
    else:
        cast = _cast_leaves_jit(model, [leaves[i] for i in picked])
    for i, y in zip(picked, cast):
        leaves[i] = y
    return jax.tree_util.tree_unflatten(treedef, leaves)


def quantize_for_decode(model: TransformerLM, params: Any, mode: str = "int8"):
    """(model, fp32 params) -> (quantized model, params): weights stored
    int8 — or nibble-packed int4 for the matmuls with ``mode="int4"`` —
    with per-out-channel scales, so each decode step streams 1/4 (1/8) of
    the fp32 HBM bytes (orion_tpu/quant.py). Reusable across generate
    calls — quantize once, serve many."""
    from orion_tpu.quant import quantize_params_for_decode

    cfg = model.cfg
    if (
        cfg.n_experts
        and cfg.moe_dropless
        and model.mesh is not None
        and model.mesh.shape.get("ep", 1) > 1
    ):
        # ADVICE r4: fail at setup, not as an AssertionError deep inside
        # jit tracing (models/moe.py keeps the assert as a backstop)
        raise ValueError(
            "quantized serving of a dropless MoE is single-host only: the "
            "per-row scale tables don't ride _dropless_ep's budgeted "
            "ragged form. Serve on an ep=1 mesh, or use the capacity path "
            "(moe_dropless=False) on ep meshes."
        )
    qmodel = TransformerLM(model.cfg, mesh=model.mesh, quant=mode)
    example = jnp.zeros((1, 8), jnp.int32)
    qparams = jax.jit(
        lambda p: quantize_params_for_decode(qmodel, p, example)
    )(params)
    return qmodel, qparams


def generate(
    model: TransformerLM,
    params: Any,
    prompt: Array,
    max_new_tokens: int,
    sample: Optional[SampleConfig] = None,
    rng: Optional[Array] = None,
    mesh: Optional[Any] = None,
    cast_params: bool = False,
    quant: str = "",
) -> Array:
    """Batched generation; one compile per (prompt_len, max_new_tokens).

    ``quant="int8"``: quantize weights for this call (for repeated serving,
    call :func:`quantize_for_decode` once and pass its results instead).
    ``cast_params``: decode from :func:`serving_params`'s tree (the matmul
    weights in the compute dtype: half their bytes for an fp32 master, the
    same answers); a caller that decodes more than once casts once itself.

    ``mesh``: decode over a device mesh (SURVEY.md P1–P4 applied to
    inference). Params are placed by the training sharding rules (fsdp
    feature sharding + Megatron tp head sharding), the prompt batch is
    sharded over (dp, fsdp), and GSPMD propagates those layouts through
    prefill and the decode scan — KV/ring caches come out batch- and
    head-sharded with no model changes. A batch that doesn't divide
    dp*fsdp is placed replicated instead (tp sharding still applies).

    MoE models are served in the NO-DROP regime: training-time capacity
    factors drop tokens in the parallel pass, but decode_step never drops
    (capacity = batch), so serving with training capacity would make the
    prompt's prefill inconsistent with its own continuation. Capacity
    factor is raised to E/k for inference (capacity == group size — the
    parallel forward then provably keeps every token; models/moe.py).
    """
    cfg = model.cfg
    if (
        cfg.n_experts > 0
        and not cfg.moe_dropless  # dropless has no capacity to bump
        and cfg.moe_capacity_factor < cfg.n_experts / max(cfg.moe_top_k, 1)
    ):
        model = TransformerLM(
            dataclasses.replace(
                cfg,
                moe_capacity_factor=float(cfg.n_experts)
                / max(cfg.moe_top_k, 1),
            ),
            mesh=model.mesh,
            quant=model.quant,
        )
    if prompt.ndim == 1:
        prompt = prompt[None]
    cap = model.cfg.max_seq_len
    assert prompt.shape[1] + max_new_tokens <= cap, (
        f"prompt {prompt.shape[1]} + new {max_new_tokens} exceeds max_seq_len {cap}"
    )
    prompt = jnp.asarray(prompt, jnp.int32)
    if quant:
        assert quant in ("int8", "int4"), quant
        if not model.quant:
            model, params = quantize_for_decode(model, params, mode=quant)
        else:
            # an already-quantized model cannot be re-quantized to another
            # mode — silently serving the wrong precision would corrupt
            # latency/quality measurements
            assert model.quant == quant, (
                f"model is already quantized as {model.quant!r}; "
                f"requested quant={quant!r}"
            )
    if cast_params:
        # a quantized tree comes back as it is: already minimal, and its
        # fp32 *_s scale vectors are the exact per-out-channel dequant
        params = serving_params(model, params)
    if mesh is not None:
        from orion_tpu.parallel.sharding import (
            batch_sharding,
            replicated,
            shard_params,
        )

        n_data = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
        params = shard_params(params, mesh)
        spec = (
            batch_sharding(mesh)
            if prompt.shape[0] % n_data == 0
            else replicated(mesh)
        )
        prompt = jax.device_put(prompt, spec)
    return _generate_jit(
        model,
        params,
        prompt,
        int(max_new_tokens),
        sample or SampleConfig(),
        rng if rng is not None else jax.random.PRNGKey(0),
    )


def generate_unconditional(
    model: TransformerLM,
    params: Any,
    batch_size: int,
    max_new_tokens: int,
    bos_token: int = 0,
    **kw,
) -> Array:
    prompt = jnp.full((batch_size, 1), bos_token, jnp.int32)
    return generate(model, params, prompt, max_new_tokens, **kw)


def _load_step_params(mngr, ckpt_dir: str, step: int, retry, verify: bool):
    """Restore + manifest-verify ONE step's params (helper of
    :func:`load_params`). I/O is retried (OSError-only, jittered backoff);
    the ``serve.ckpt_load`` fault hook fires inside the retried region so
    chaos tests drive the real path."""
    import orbax.checkpoint as ocp

    from orion_tpu.resilience.inject import fire
    from orion_tpu.resilience.retry import call_with_retries
    from orion_tpu.training.checkpoint import (
        manifest_subtree,
        read_manifest,
        verify_manifest,
    )

    def _restore():
        fire("serve.ckpt_load", step=step)
        try:
            return mngr.restore(step)
        except KeyError:
            # orbax versions that saved via StandardSave refuse a bare
            # restore(step) ("provide a CheckpointHandlerRegistry or
            # CheckpointArgs"); StandardRestore with no target restores the
            # saved tree structure as-is
            return mngr.restore(step, args=ocp.args.StandardRestore())

    restored = call_with_retries(
        _restore, retry, describe=f"serving param load (step {step})"
    )
    params = restored["params"]
    if verify:
        import warnings

        manifest = read_manifest(ckpt_dir, step)
        sub = None if manifest is None else manifest_subtree(manifest, ".params")
        if sub is None:
            warnings.warn(
                f"checkpoint step {step} has no params integrity manifest "
                "(pre-manifest checkpoint?); serving it unverified",
                stacklevel=3,
            )
        else:
            verify_manifest(params, sub)  # raises CheckpointIntegrityError
    return params


def load_params(
    ckpt_dir: str,
    step: Optional[int] = None,
    retry: Optional[Any] = None,
    verify: bool = True,
) -> Tuple[Any, int]:
    """Pull just the params subtree out of a training checkpoint — the
    serving-side loader, hardened the same way the trainer's restore is
    (training/checkpoint.py): orbax I/O retried with jittered backoff
    (OSError-only), the restored params re-checksummed against the step's
    integrity manifest, and a default-latest load FALLING BACK to the
    newest intact retained step (loud warning) when the latest is torn or
    corrupt, instead of taking the serving process down on its first
    request. An explicitly pinned ``step`` never falls back — the caller
    asked for exactly that step, so corruption there raises."""
    import os
    import warnings

    import orbax.checkpoint as ocp

    from orion_tpu.resilience.retry import RetryPolicy
    from orion_tpu.training.checkpoint import CheckpointIntegrityError

    policy = retry if retry is not None else RetryPolicy()
    # orbax requires absolute paths; the Trainer-side Checkpointer already
    # abspaths, this CLI-side loader must too ("--ckpt-dir ck" otherwise
    # dies deep in tensorstore)
    root = os.path.abspath(ckpt_dir)
    mngr = ocp.CheckpointManager(root)
    try:
        if step is not None:
            return _load_step_params(mngr, root, step, policy, verify), step
        steps = sorted(mngr.all_steps(), reverse=True)
        if not steps:
            raise FileNotFoundError(f"no checkpoint in {ckpt_dir}")
        failures = []
        for s in steps:
            try:
                params = _load_step_params(mngr, root, s, policy, verify)
            except Exception as e:  # orbax corruption surfaces as many types
                failures.append((s, e))
                warnings.warn(
                    f"checkpoint step {s} is corrupt or incomplete "
                    f"({type(e).__name__}: {str(e)[:200]}); serving falls "
                    "back to the next retained step",
                    stacklevel=2,
                )
                continue
            if failures:
                warnings.warn(
                    f"serving params from step {s} after skipping corrupt "
                    f"step(s) {[f[0] for f in failures]}",
                    stacklevel=2,
                )
            return params, s
        raise CheckpointIntegrityError(
            f"no intact checkpoint in {ckpt_dir}; tried "
            + ", ".join(f"{s} ({type(e).__name__})" for s, e in failures)
        ) from failures[-1][1]
    finally:
        mngr.close()


def adapt_config_to_params(cfg: ModelConfig, params: Any) -> ModelConfig:
    """Match a named config to the checkpoint's ACTUAL capacities — the
    architecture must follow the checkpoint, not the config name:
    train.py auto-bumps max_seq_len when seq_len >= max_seq_len (read the
    real positional capacity off the stored pos_embed table), and
    ``--set vocab_size=...`` runs change the embedding rows. Shared by
    the generate / evaluate / serving CLIs so the adaptation can't drift
    between them. Unknown layouts (quantized trees) pass through as-is."""
    try:
        pos_rows = params["params"]["pos_embed"]["embedding"].shape[0]
        if pos_rows != cfg.max_seq_len:
            cfg = dataclasses.replace(cfg, max_seq_len=pos_rows)
        vocab = params["params"]["embed"]["embedding"].shape[0]
        if vocab != cfg.vocab_size:
            cfg = dataclasses.replace(cfg, vocab_size=vocab)
    except (KeyError, TypeError):
        pass
    return cfg


def unstack_if_pipeline(model: TransformerLM, params: Any) -> Tuple[Any, bool]:
    """Convert a pipeline-trained checkpoint (stacked per-stage block
    params) to the standard serving layout; no-op on standard
    checkpoints. Returns (params, was_pipeline)."""
    if "blocks_stacked" in params.get("params", {}):
        from orion_tpu.parallel.pipeline_lm import unstack_lm_params

        return unstack_lm_params(model, params), True
    return params, False


def main(argv=None) -> int:
    from orion_tpu.utils.cache import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser("orion_tpu.generate")
    p.add_argument("--config", default="tiny")
    p.add_argument("--ckpt-dir", required=False, default=None)
    p.add_argument("--prompt", default="Hello")
    p.add_argument("--max-new-tokens", type=int, default=128)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=1.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--tokenizer",
        default=None,
        help="BPE tokenizer JSON (from prepare_data --train-tokenizer) for "
        "32k-vocab checkpoints; default byte-level",
    )
    p.add_argument("--eos", action="store_true",
                   help="stop sequences at the tokenizer's <eos>")
    p.add_argument("--quant", default="", choices=["", "int8", "int4"],
                   help="weight-streamed decode: int8 quarters the weight "
                        "HBM traffic, int4 halves it again (orion_tpu/quant.py)")
    p.add_argument("--ckpt-attempts", type=int, default=4,
                   help="total tries for the checkpoint load (transient "
                        "I/O retried with jittered backoff; 1 = no retry)")
    # same mesh flags as train.py / aot.py; any axis > 1 builds a mesh
    p.add_argument("--dp", type=int, default=1)
    p.add_argument("--fsdp", type=int, default=1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1)
    p.add_argument(
        "--set", action="append", default=[], metavar="KEY=VALUE",
        help="ModelConfig override, e.g. --set n_experts=8 (must match how "
        "the checkpoint was trained)",
    )
    args = p.parse_args(argv)
    for ax in ("dp", "fsdp", "tp", "sp"):
        if getattr(args, ax) < 1:
            p.error(f"--{ax} must be >= 1")

    cfg = get_config(args.config)
    if args.set:
        from orion_tpu.utils.config import apply_overrides, parse_set_overrides

        cfg = apply_overrides(cfg, parse_set_overrides(args.set))
    eos_token = -1
    if args.tokenizer:
        from orion_tpu.utils.bpe import BPETokenizer

        tok = BPETokenizer.load(args.tokenizer)
        assert tok.vocab_size <= cfg.vocab_size, (
            f"tokenizer vocab {tok.vocab_size} > model vocab {cfg.vocab_size}"
        )
        if args.eos:
            eos_token = tok.eos
    else:
        from orion_tpu.utils.tokenizer import ByteTokenizer

        tok = ByteTokenizer()
    prompt = jnp.asarray([tok.encode(args.prompt)], jnp.int32)

    from orion_tpu.obs.trace import PROCESS_TRACER

    with PROCESS_TRACER.span("setup.weights", "setup",
                             source="checkpoint" if args.ckpt_dir else "init"):
        if args.ckpt_dir:
            from orion_tpu.resilience.retry import RetryPolicy

            params, step = load_params(
                args.ckpt_dir,
                retry=RetryPolicy(attempts=max(args.ckpt_attempts, 1)),
            )
            cfg = adapt_config_to_params(cfg, params)
            print(f"loaded step {step} from {args.ckpt_dir}", file=sys.stderr)
            model = TransformerLM(cfg)
            params, was_pp = unstack_if_pipeline(model, params)
            if was_pp:
                print("unstacked pipeline-layout checkpoint", file=sys.stderr)
        else:
            model = TransformerLM(cfg)
            params = model.init(jax.random.PRNGKey(0), prompt)
            print("no --ckpt-dir: random params (smoke test)", file=sys.stderr)

    mesh = None
    if args.dp * args.fsdp * args.tp * args.sp > 1:
        from orion_tpu.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(
            MeshConfig(dp=args.dp, fsdp=args.fsdp, tp=args.tp, sp=args.sp)
        )
        print(f"mesh: {dict(mesh.shape)}", file=sys.stderr)

    out = generate(
        model,
        params,
        prompt,
        args.max_new_tokens,
        SampleConfig(args.temperature, args.top_k, args.top_p, eos_token=eos_token),
        jax.random.PRNGKey(args.seed),
        mesh=mesh,
        quant=args.quant,
    )
    ids = [int(t) for t in out[0]]
    if eos_token >= 0 and eos_token in ids:
        ids = ids[: ids.index(eos_token)]
    print(args.prompt + tok.decode(ids))
    return 0


if __name__ == "__main__":
    sys.exit(main())
