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
    dK/dV kernel (grid B·H × Tk/Bk × Tq/Bq): same q-major (Bq, Bk) tile
        orientation — PᵀdO and dSᵀQ come out of dot_general by contracting
        the q dim, so no in-kernel transposes;  dV += PᵀdO;  dK += dSᵀQ·scale
Both recompute P from (q, k, lse) — O(T) memory, matmuls on the MXU.

``window=w`` = each query sees keys s ∈ (t−w, t]. Masks are structural
(computed from block indices + iota), so sliding-window skips every tile
outside the band — cost O(T·w), not O(T²).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
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


def _rowscol(qi, ki, bq, bk):
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return rows, cols


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


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale, causal, window, shift, q_offset, t_k, bq, bk, nk, banded,
    nk_real,
):
    qi, j = pl.program_id(1), pl.program_id(2)
    if banded:  # k-tile index is band-relative (swa clip, module docstring)
        ki = _banded_base(qi, bq, bk, window) + j
        oob = (ki < 0) | (ki >= nk_real)
    else:
        ki = j
        oob = jnp.bool_(False)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(jnp.logical_not(
        oob | _skip_tile(qi, ki, bq, bk, causal, window, shift, q_offset)
    ))
    def _():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (Bq, Bk)
        rows, cols = _rowscol(qi, ki, bq, bk)
        s = jnp.where(_tile_mask(rows, cols, causal, window, t_k, shift, q_offset), s, _NEG)

        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # (Bq, Bk) fp32
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32
        )
        m_scr[:] = m_new

    @pl.when(j == nk - 1)
    def _():
        l = l_scr[:]
        safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows (padding) -> 0
        o_ref[0] = (acc_scr[:] / safe).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(safe)  # (Bq, 1)


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
    if banded:
        grid_k = _banded_nj(nq, bq, bk, window)
        kvmap = lambda b, i, j: (  # noqa: E731
            b, jnp.clip(_banded_base(i, bq, bk, window) + j, 0, nk - 1), 0
        )
    else:
        grid_k = nk
        kvmap = lambda b, i, j: (b, j, 0)  # noqa: E731

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
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
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
    *, scale, causal, window, shift, q_offset, t_k, bq, bk, nk, banded,
    nk_real,
):
    qi, j = pl.program_id(1), pl.program_id(2)
    if banded:
        ki = _banded_base(qi, bq, bk, window) + j
        oob = (ki < 0) | (ki >= nk_real)
    else:
        ki = j
        oob = jnp.bool_(False)

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(jnp.logical_not(
        oob | _skip_tile(qi, ki, bq, bk, causal, window, shift, q_offset)
    ))
    def _():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        rows, cols = _rowscol(qi, ki, bq, bk)
        mask = _tile_mask(rows, cols, causal, window, t_k, shift, q_offset)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0]), 0.0)  # lse: (Bq, 1)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0]) * scale
        dq_scr[:] = dq_scr[:] + jnp.dot(
            ds, k_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32
        )

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr,
    *, scale, causal, window, shift, q_offset, t_k, bq, bk, nq, banded,
    nq_real,
):
    ki, j = pl.program_id(1), pl.program_id(2)
    if banded:  # q-tile index is band-relative: q rows in [ki*bk, ki*bk+bk+w)
        qi = (ki * bk) // bq + j
        oob = qi >= nq_real
    else:
        qi = j
        oob = jnp.bool_(False)

    @pl.when(j == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(jnp.logical_not(
        oob | _skip_tile(qi, ki, bq, bk, causal, window, shift, q_offset)
    ))
    def _():
        # q-major (Bq, Bk) tile; k-side grads via contraction over the q dim
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        rows, cols = _rowscol(qi, ki, bq, bk)
        mask = _tile_mask(rows, cols, causal, window, t_k, shift, q_offset)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0]), 0.0)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p, do_ref[0].astype(jnp.float32),
            dimension_numbers=(((0,), (0,)), ((), ())),  # Pᵀ dO
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0]) * scale
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q_ref[0].astype(jnp.float32),
            dimension_numbers=(((0,), (0,)), ((), ())),  # dSᵀ Q
            preferred_element_type=jnp.float32,
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
    if banded:
        grid_k = _banded_nj(nq, bq, bk, window)
        kvmap = lambda b, i, j: (  # noqa: E731
            b, jnp.clip(_banded_base(i, bq, bk, window) + j, 0, nk - 1), 0
        )
    else:
        grid_k = nk
        kvmap = lambda b, i, j: (b, j, 0)  # noqa: E731

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
        qmap = lambda b, j, i: (  # noqa: E731
            b, jnp.clip((j * bk) // bq + i, 0, nq - 1), 0
        )
    else:
        grid_q = nq
        qmap = lambda b, j, i: (b, i, 0)  # noqa: E731

    col_spec_q_inner = pl.BlockSpec((1, bq, 1), qmap, memory_space=pltpu.VMEM)
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
            col_spec_q_inner,
            col_spec_q_inner,
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
    )(qp, kp, vp, gp, lsep, deltap)
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
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_vjp_bwd(scale, causal, window, shift, q_offset, bq, bk,
                       interpret, res, gs):
    q, k, v, out, lse = res
    g, dlse = gs
    dq, dk, dv = _flash_bwd_flat(
        q, k, v, out, lse, g.astype(q.dtype), scale, causal, window, bq, bk,
        interpret, shift=shift, dlse=dlse, q_offset=q_offset,
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
