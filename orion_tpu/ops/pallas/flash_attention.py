"""Pallas TPU flash attention: causal / bidirectional / sliding-window.

TPU-native replacement for the reference's CUDA softmax-attention path
(BASELINE.json north_star: LRA softmax configs and the 7B hybrid's
sliding-window softmax layers; the reference checkout was never mounted —
SURVEY.md §0). Online-softmax tiling: never materializes the T×T score
matrix, accumulates in fp32 VMEM scratch.

Forward:  grid (B·H, Tq/Bq, Tk/Bk), k-axis innermost (sequential on a TPU
core), scratch carries the running row-max m, row-sum l, and output
accumulator; finalized on the last k-block. Saves the log-sum-exp for the
backward as a [B·H, T, 1] column (the trailing unit dim keeps the block
shape legal under TPU (8,128) tiling).

Backward (custom VJP, two kernels — the standard flash decomposition):
    delta = rowsum(dO ⊙ O)                       (XLA, one fused reduce)
    dQ kernel (grid B·H × Tq/Bq × Tk/Bk):  P = exp(S − lse);
        dS = P ⊙ (dO Vᵀ − delta);  dQ += dS K · scale
    dK/dV kernel (grid B·H × Tk/Bk × Tq/Bq): the tile is K-MAJOR, Sᵀ = K Qᵀ
        (Bk, Bq), so that Pᵀ and dSᵀ leave the MXU the way the two k-side
        products read them (no transposed-lhs contraction) and lse / delta
        come in as (1, Bq) rows;  dV += PᵀdO;  dK += dSᵀQ·scale
Both recompute P from (q, k, lse) — O(T) memory, matmuls on the MXU.

``window=w`` = each query sees keys s ∈ (t−w, t]. Masks are structural
(computed from block indices + iota), so sliding-window skips every tile
outside the band — cost O(T·w), not O(T²).

Precision: every product hands the MXU its operands in the dtype the caller
STORED them in, and accumulates in fp32. Q Kᵀ and dO Vᵀ read q, k, v, dO as
they are; the tile-shaped operand of the second products — P for P V and
Pᵀ dO, dS for dS K and dSᵀ Q — is computed in fp32 and cast to the other
operand's dtype on its way in (``p.astype(v.dtype)``), as the serving kernels
(piece_attention, indexed_attention, cache_attention) and jax's own TPU flash
kernel do. With bf16 inputs that is what the chip did already: Mosaic
multiplies fp32 operands at default precision in ONE bf16 pass, so the
up-casts this replaced changed neither a bit nor a millisecond there (PERF.md
§6 PR 54); with fp32 inputs the casts are no-ops. The scores, the running max
and sum, ``exp``, ``lse``, ``delta``, P and dS before their cast and every
accumulator stay fp32.

When a tile is masked: a grid step's tile is SKIPPED (not computed) where the
structural mask is all-false over it (`_skip_tile`, decided from the grid
indices for all three kernels, the banded grid and the ring callers'
``shift`` / ``q_offset`` alike), and every computed tile builds the mask from
two iotas. A second body without the mask for the tiles it leaves whole was
measured and taken out: the mask hides under the products (PERF.md §6 PR 54).

What these kernels were bound by, and no longer pay for (the products alone
take nearly the whole kernel's time: the softmax hides under them): the
forward's per-row statistics broadcast across lanes at every tile — m and l
live lane-replicated (Bq, 128) (`_stat_lanes`, `_across`); transposed-lhs
contractions and column broadcasts in dK/dV (its k-major tile); and the K / V
(or Q / dO) blocks of a SKIPPED step: its index map names the row's nearest
computed tile, a block already resident (`_fetched_k`, `_fetched_q`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas import kernel_entry
from orion_tpu.ops.pallas.causal_dot import _sds  # vma-carrying out_shape:
# lets these kernels compose with shard_map(check_vma=True) bodies
# (parallel/kernel_shard.py, parallel/pipeline.py) the same way the
# causal_dot kernels do

Array = jax.Array

_NEG = -1e30


def _tile_mask(rows: Array, cols: Array, causal: bool, window: Optional[int],
               t_k: int, shift: int = 0, q_offset: int = 0):
    """Boolean (Bq, Bk) tile of the structural mask at absolute row/col ids.
    ``shift`` strengthens the causal bound to rows >= cols + shift:
    shift=1 is the STRICT triangle a striped ring block needs when the kv
    stripe's phase is ahead of the query stripe's (parallel/ring.py)."""
    m = cols < t_k  # mask out key padding
    rows = rows + q_offset
    if causal:
        m &= rows >= cols + shift
    if window is not None:
        m &= (rows - cols) < window
    return m


def _skip_tile(qi, ki, bq, bk, causal, window, shift: int = 0,
               q_offset: int = 0):
    """True if tile (qi, ki) is entirely masked (static-shape predicate)."""
    skip = jnp.bool_(False)
    if causal:
        # first key row past the last query it may attend to
        skip |= ki * bk > qi * bq + q_offset + (bq - 1) - shift
    if window is not None:
        # band entirely left of the tile
        skip |= (qi * bq + q_offset) - (ki * bk + bk - 1) >= window
    return skip


def _fetched_k(qi, j, nk, bq, bk, causal, window, shift: int = 0,
               q_offset: int = 0):
    """The k-tile that step (qi, j) of the classic grid FETCHES: j itself
    wherever :func:`_skip_tile` computes the tile, and where it skips, the
    nearest computed tile of the row — the block the step before fetched (past
    the causal bound) or the one the next computed step needs (before the
    window). A block index that does not change moves no bytes: fetched by
    its own index, a skipped step waited ~0.47 us for a K and a V tile with no
    product to hide the DMA under, 7.2 ms of a 44 ms forward at T 8,192
    (PERF.md §6 PR 54)."""
    if causal:
        j = jnp.minimum(j, (qi * bq + q_offset + (bq - 1) - shift) // bk)
    if window is not None:
        j = jnp.maximum(j, (qi * bq + q_offset - window + 1) // bk)
    return jnp.clip(j, 0, nk - 1)


def _fetched_q(ki, j, nq, bq, bk, causal, window, shift: int = 0,
               q_offset: int = 0):
    """:func:`_fetched_k` for the dK/dV kernel's sweep over q tiles."""
    if causal:
        j = jnp.maximum(j, (ki * bk + shift - q_offset) // bq)
    if window is not None:
        j = jnp.minimum(j, (ki * bk + bk + window - 2 - q_offset) // bq)
    return jnp.clip(j, 0, nq - 1)


def _live(qi, ki, oob, geo):
    """True where grid step (qi, ki) computes its tile: inside the banded
    sweep (not ``oob``) and not entirely masked."""
    return jnp.logical_not(oob | _skip_tile(
        qi, ki, geo["bq"], geo["bk"], geo["causal"], geo["window"], geo["shift"],
        geo["q_offset"],
    ))


def _mask_of(qi, ki, geo, k_major: bool = False):
    """The structural mask's tile at grid step (qi, ki): (Bq, Bk), or (Bk, Bq)
    for the dK/dV kernel's k-major tile."""
    bq, bk = geo["bq"], geo["bk"]
    shape, q_axis = ((bk, bq), 1) if k_major else ((bq, bk), 0)
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return _tile_mask(rows, cols, geo["causal"], geo["window"], geo["t_k"],
                      geo["shift"], geo["q_offset"])


_NT = (((1,), (1,)), ((), ()))  # a bᵀ: both operands contract their last dim
_LANES = 128


def _nt(a, b):
    return jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)


def _stat_lanes(*widths: int) -> int:
    """Lanes the forward's per-row statistics (m, l) are kept over in VMEM: 128,
    every lane holding the row's value, where each width it is laid across is
    whole vregs; else 1 (the interpret-mode tests' small blocks, a short
    sequence's ragged block)."""
    return _LANES if all(w % _LANES == 0 for w in widths) else 1


def _across(stat, width: int):
    """A per-row statistic laid across ``width`` lanes. Held (Bq, 1) it is
    broadcast across lanes at every use — a pass through the cross-lane unit
    over Bq / 8 vregs, which at (512, 512) tiles cost the forward kernel more
    than its mask (PERF.md §6 PR 54); held lane-replicated (Bq, 128) it is the
    same vregs read again."""
    if stat.shape[1] == 1:
        return stat
    return pltpu.repeat(stat, width // _LANES, 1)


def _banded_ok(causal, window, shift, q_offset, t_q, t_k) -> bool:
    """Use the BANDED grid (VERDICT r4 #6 — clip, don't mask): the k sweep
    per q-tile covers only tiles intersecting the (window, causal) band
    via a qi-dependent BlockSpec index map. Cuts the swept area from
    O(T^2) grid steps to O(T*window) AND makes small block_k affordable —
    the boundary tiles' masked padding shrinks with bk, which the full
    quadratic grid couldn't exploit (its step count scaled with 1/bk over
    the WHOLE row). Plain single-shard swa only: the ring/halo callers
    (shift/q_offset) keep the classic grid, whose skip predicate already
    serves their offset geometry."""
    return (
        causal and window is not None and shift == 0 and q_offset == 0
        and t_q == t_k and window < t_k
    )


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _banded_base(qi, bq, bk, window):
    """First k-tile of query tile ``qi``'s band (may be negative near the
    sequence start — callers clip the fetch and skip the compute)."""
    return (qi * bq - window + 1) // bk


def _banded_nj(nq: int, bq: int, bk: int, window: int) -> int:
    """Grid extent of the banded k sweep: max tiles any q-tile's band
    touches (exact python max, not a bound — nq is at most thousands)."""
    m = 1
    for qi in range(nq):
        base = (qi * bq - window + 1) // bk
        m = max(m, (qi * bq + bq - 1) // bk - base + 1)
    return m


def _banded_q_nj(nk: int, bq: int, bk: int, window: int) -> int:
    """Grid extent of the banded q sweep (dk/dv kernel): max q-tiles any
    k-tile's band touches."""
    m = 1
    for ki in range(nk):
        base = (ki * bk) // bq
        m = max(m, (ki * bk + bk + window - 2) // bq - base + 1)
    return m


def _k_sweep(banded, nq, nk, bq, bk, causal, window, shift, q_offset):
    """(steps, K / V index map) of a q tile's sweep over k tiles, for the
    forward and the dQ kernel: the band's tiles alone on the banded grid, all
    ``nk`` on the classic one, a skipped step naming a resident block."""
    if banded:
        return _banded_nj(nq, bq, bk, window), lambda b, i, j: (
            b, jnp.clip(_banded_base(i, bq, bk, window) + j, 0, nk - 1), 0
        )
    return nk, lambda b, i, j: (
        b, _fetched_k(i, j, nk, bq, bk, causal, window, shift, q_offset), 0
    )


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale, nk, banded, nk_real, **geo,
):
    qi, j = pl.program_id(1), pl.program_id(2)
    if banded:  # k-tile index is band-relative (swa clip, module docstring)
        ki = _banded_base(qi, geo["bq"], geo["bk"], geo["window"]) + j
        oob = (ki < 0) | (ki >= nk_real)
    else:
        ki = j
        oob = jnp.bool_(False)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(_live(qi, ki, oob, geo))
    def _():
        s = _nt(q_ref[0], k_ref[0]) * scale  # (Bq, Bk) fp32
        s = jnp.where(_mask_of(qi, ki, geo), s, _NEG)
        m_prev = m_scr[:]  # (Bq, 1) or lane-replicated (Bq, 128): _stat_lanes
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _across(m_new, s.shape[1]))  # (Bq, Bk) fp32
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * _across(alpha, acc_scr.shape[1]) + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32
        )
        m_scr[:] = m_new

    @pl.when(j == nk - 1)
    def _():
        l = l_scr[:, :1]
        safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows (padding) -> 0
        o_ref[0] = (acc_scr[:] / safe).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:, :1] + jnp.log(safe)  # (Bq, 1)


@kernel_entry(
    "flash_attn_fwd", "scale", "causal", "window", "bq", "bk", "interpret", "shift",
    "q_offset",
)
def _flash_fwd_flat(q, k, v, scale, causal, window, bq, bk, interpret, shift=0,
                    q_offset=0):
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    dv = v.shape[-1]
    pq, pk = (-t_q) % bq, (-t_k) % bk
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0))) if pk else v
    nq, nk = qp.shape[1] // bq, kp.shape[1] // bk

    banded = _banded_ok(causal, window, shift, q_offset, t_q, t_k)
    grid_k, kvmap = _k_sweep(banded, nq, nk, bq, bk, causal, window, shift, q_offset)

    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window, shift=shift,
        q_offset=q_offset,
        t_k=t_k, bq=bq, bk=bk, nk=grid_k, banded=banded, nk_real=nk,
    )
    out, lse = pl.pallas_call(
        kern,
        name="flash_attn_fwd",
        grid=(bh, nq, grid_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), kvmap, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, dv), kvmap, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _sds((bh, nq * bq, dv), q.dtype, q),
            _sds((bh, nq * bq, 1), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _stat_lanes(bk, dv)), jnp.float32),
            pltpu.VMEM((bq, _stat_lanes(bk, dv)), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :t_q, :], lse[:, :t_q, :]


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, scale, nk, banded, nk_real, **geo,
):
    qi, j = pl.program_id(1), pl.program_id(2)
    if banded:
        ki = _banded_base(qi, geo["bq"], geo["bk"], geo["window"]) + j
        oob = (ki < 0) | (ki >= nk_real)
    else:
        ki = j
        oob = jnp.bool_(False)

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(_live(qi, ki, oob, geo))
    def _():
        s = _nt(q_ref[0], k_ref[0]) * scale  # (Bq, Bk) fp32
        # lse, delta: (Bq, 1) columns. Laid across lanes once a q tile
        # instead (as the forward keeps m and l) this kernel ran 1-5% SLOWER:
        # its three products hide the two broadcasts (PERF.md §6 PR 54)
        p = jnp.where(_mask_of(qi, ki, geo), jnp.exp(s - lse_ref[0]), 0.0)
        ds = p * (_nt(do_ref[0], v_ref[0]) - delta_ref[0]) * scale
        dq_scr[:] = dq_scr[:] + jnp.dot(
            ds.astype(k_ref.dtype), k_ref[0], preferred_element_type=jnp.float32
        )

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale, nq, banded, nq_real, **geo,
):
    ki, j = pl.program_id(1), pl.program_id(2)
    if banded:  # q-tile index is band-relative: q rows in [ki*bk, ki*bk+bk+w)
        qi = (ki * geo["bk"]) // geo["bq"] + j
        oob = qi >= nq_real
    else:
        qi = j
        oob = jnp.bool_(False)

    @pl.when(j == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(_live(qi, ki, oob, geo))
    def _():
        # k-major (Bk, Bq) tile: Pᵀ and dSᵀ come out of the MXU the way the
        # two k-side products read them, and lse / delta are (1, Bq) ROWS
        st = _nt(k_ref[0], q_ref[0]) * scale
        pt = jnp.where(
            _mask_of(qi, ki, geo, k_major=True), jnp.exp(st - lse_ref[0, 0]), 0.0
        )
        dv_scr[:] = dv_scr[:] + jnp.dot(  # Pᵀ dO
            pt.astype(do_ref.dtype), do_ref[0], preferred_element_type=jnp.float32
        )
        dpt = _nt(v_ref[0], do_ref[0])
        dst = pt * (dpt - delta_ref[0, 0]) * scale
        dk_scr[:] = dk_scr[:] + jnp.dot(  # dSᵀ Q
            dst.astype(q_ref.dtype), q_ref[0], preferred_element_type=jnp.float32
        )

    @pl.when(j == nq - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@kernel_entry(
    "flash_attn_bwd", "scale", "causal", "window", "bq", "bk", "interpret", "shift",
    "q_offset",
)
def _flash_bwd_flat(q, k, v, out, lse, g, scale, causal, window, bq, bk, interpret,
                    shift=0, dlse=None, q_offset=0):
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    dv = v.shape[-1]
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
    )  # (BH, Tq, 1)
    if dlse is not None:
        # lse cotangent (flash_attention_lse): dS_ij = P̂_ij (dP_ij − Δ_i +
        # dlse_i), since ∂lse_i/∂S_ij = P̂_ij — folds into the delta column,
        # so the kernels themselves are unchanged
        delta = delta - dlse.astype(jnp.float32)

    pq, pk = (-t_q) % bq, (-t_k) % bk
    padq = lambda x: jnp.pad(x, ((0, 0), (0, pq), (0, 0))) if pq else x  # noqa: E731
    padk = lambda x: jnp.pad(x, ((0, 0), (0, pk), (0, 0))) if pk else x  # noqa: E731
    qp, kp, vp, gp, deltap = padq(q), padk(k), padk(v), padq(g), padq(delta)
    # padded query rows get lse=+inf so their recomputed P is exactly zero
    lsep = (
        jnp.pad(lse, ((0, 0), (0, pq), (0, 0)), constant_values=jnp.inf)
        if pq
        else lse
    )
    nq, nk = qp.shape[1] // bq, kp.shape[1] // bk

    banded = _banded_ok(causal, window, shift, q_offset, t_q, t_k)
    grid_k, kvmap = _k_sweep(banded, nq, nk, bq, bk, causal, window, shift, q_offset)

    col_spec_q = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM)

    dq_kern = functools.partial(
        _dq_kernel, scale=scale, causal=causal, window=window, shift=shift,
        q_offset=q_offset,
        t_k=t_k, bq=bq, bk=bk, nk=grid_k, banded=banded, nk_real=nk,
    )
    dq = pl.pallas_call(
        dq_kern,
        name="flash_attn_dq",
        grid=(bh, nq, grid_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), kvmap, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, dv), kvmap, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM),
            col_spec_q,
            col_spec_q,
        ],
        out_specs=pl.BlockSpec(
            (1, bq, d), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=_sds((bh, nq * bq, d), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp, gp, lsep, deltap)

    if banded:
        grid_q = _banded_q_nj(nk, bq, bk, window)
        qtile = lambda j, i: jnp.clip((j * bk) // bq + i, 0, nq - 1)  # noqa: E731
    else:
        grid_q = nq
        qtile = lambda j, i: _fetched_q(  # noqa: E731
            j, i, nq, bq, bk, causal, window, shift, q_offset
        )
    qmap = lambda b, j, i: (b, qtile(j, i), 0)  # noqa: E731

    # the k-major tile reads lse / delta as (1, Bq) rows, one a q tile:
    # [BH, nq, 1, Bq], whose block spans its last two dims whole, so any Bq
    # the sublane rounding of `_blocks` gives is a legal block (a (1, 1, Bq)
    # block of [BH, 1, Tq] needs Bq in whole vregs of 128 lanes). A relayout
    # of 4 bytes a query, one sublane of 8 used: what it adds to a training
    # step's peak is in PERF.md §6 PR 54
    as_rows = lambda x: x.reshape(bh, nq, 1, bq)  # noqa: E731
    row_spec_q = pl.BlockSpec(
        (1, 1, 1, bq), lambda b, j, i: (b, qtile(j, i), 0, 0), memory_space=pltpu.VMEM
    )
    dkv_kern = functools.partial(
        _dkv_kernel, scale=scale, causal=causal, window=window, shift=shift,
        q_offset=q_offset,
        t_k=t_k, bq=bq, bk=bk, nq=grid_q, banded=banded, nq_real=nq,
    )
    dk, dv_ = pl.pallas_call(
        dkv_kern,
        name="flash_attn_dkv",
        grid=(bh, nk, grid_q),
        in_specs=[
            pl.BlockSpec((1, bq, d), qmap, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, dv), lambda b, j, i: (b, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, dv), qmap, memory_space=pltpu.VMEM),
            row_spec_q,
            row_spec_q,
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, dv), lambda b, j, i: (b, j, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _sds((bh, nk * bk, d), k.dtype, k),
            _sds((bh, nk * bk, dv), v.dtype, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp, gp, as_rows(lsep), as_rows(deltap))
    return dq[:, :t_q, :], dk[:, :t_k, :], dv_[:, :t_k, :]


# ---------------------------------------------------------------------------
# custom_vjp wiring + public entry
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_lse(q, k, v, scale, causal, window, shift, q_offset, bq, bk,
               interpret):
    return _flash_fwd_flat(
        q, k, v, scale, causal, window, bq, bk, interpret, shift=shift,
        q_offset=q_offset,
    )


def _flash_lse_vjp_fwd(q, k, v, scale, causal, window, shift, q_offset, bq,
                       bk, interpret):
    out, lse = _flash_fwd_flat(
        q, k, v, scale, causal, window, bq, bk, interpret, shift=shift,
        q_offset=q_offset,
    )
    # the two residuals only this kernel can give carry a name: a rematted
    # block whose policy lists them (models/transformer.py::REMAT_KEEPS) runs
    # this forward once a step and not again in its backward; under any other
    # policy, and outside a checkpoint, a name lowers to nothing. lse is named
    # as [BH, T] rows, 4 bytes a query (the kernel's [BH, T, 1] column is a
    # 128-lane tile a query in HBM); q, k, v carry none: a caller recomputes them
    out = checkpoint_name(out, "flash_out")
    rows = checkpoint_name(lse[..., 0], "flash_lse")
    return (out, lse), (q, k, v, out, rows)


def _flash_lse_vjp_bwd(scale, causal, window, shift, q_offset, bq, bk,
                       interpret, res, gs):
    q, k, v, out, rows = res
    g, dlse = gs
    dq, dk, dv = _flash_bwd_flat(
        q, k, v, out, rows[..., None], g.astype(q.dtype), scale, causal, window,
        bq, bk, interpret, shift=shift, dlse=dlse, q_offset=q_offset,
    )
    return dq, dk, dv


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def _blocks(q, block_q, block_k, t_q, t_k):
    # clamp to the sequence length, then round up to the TPU sublane tile
    # (8 rows fp32, 16 bf16) — Mosaic may reject/deoptimize ragged blocks;
    # the existing tail padding + t_k masking absorbs the overshoot
    tile = 16 if q.dtype == jnp.bfloat16 else 8
    rup = lambda x: -(-x // tile) * tile  # noqa: E731
    return rup(min(block_q, max(t_q, 8))), rup(min(block_k, max(t_k, 8)))


def flash_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> Array:
    """Flash attention over [..., T, D] per-head tensors. Differentiable."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    batch_shape = q.shape[:-2]
    t_q, d = q.shape[-2:]
    t_k, dv = k.shape[-2], v.shape[-1]
    bh = 1
    for s in batch_shape:
        bh *= s
    bq, bk = _blocks(q, block_q, block_k, t_q, t_k)
    # one custom_vjp path serves both entries: the dropped lse output is
    # DCE'd by XLA and its zero cotangent costs one subtraction in the bwd
    out, _ = _flash_lse(
        q.reshape(bh, t_q, d),
        k.reshape(bh, t_k, d),
        v.reshape(bh, t_k, dv),
        float(scale), causal, window, 0, 0, bq, bk, interpret,
    )
    return out.reshape(*batch_shape, t_q, dv)


def flash_attention_lse(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    shift: int = 0,
    q_offset: int = 0,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
):
    """Flash attention that ALSO returns the row log-sum-exp
    ([..., T, 1] fp32) and is differentiable in both outputs — the block
    primitive for cross-shard online-softmax merges (parallel/ring.py):
    merging partial results needs lse, and the merged output's gradient
    flows through it (∂lse/∂S = P̂, folded into the backward's delta
    column). ``shift=1`` strengthens causal to the strict triangle."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    batch_shape = q.shape[:-2]
    t_q, d = q.shape[-2:]
    t_k, dv = k.shape[-2], v.shape[-1]
    bh = 1
    for s in batch_shape:
        bh *= s
    bq, bk = _blocks(q, block_q, block_k, t_q, t_k)
    out, lse = _flash_lse(
        q.reshape(bh, t_q, d),
        k.reshape(bh, t_k, d),
        v.reshape(bh, t_k, dv),
        float(scale), causal, window, shift, q_offset, bq, bk, interpret,
    )
    return (
        out.reshape(*batch_shape, t_q, dv),
        lse.reshape(*batch_shape, t_q, 1),
    )


__all__ = ["flash_attention", "flash_attention_lse"]
