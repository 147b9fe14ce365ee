"""Row-sparse, in-place decode-state step: ``recurrent_step`` for the rows
of a slot-multiplexed carry that are live in this chunk, and nothing at
all for the others.

The slot-multiplexed decode programs (generate.py) run every step of
every linear layer over ALL slots of the carry and then select the old
state back for rows that are not emitting; XLA's fused update therefore
reads and writes the whole fp32 ``S [B, H, Dk, Dv]`` (1 MiB a row at
lm_1b3 widths) whatever the occupancy: half of the served programs'
device time with a third of 64 slots decoding (PERF.md, PR 29).

This kernel walks a COMPACTED list of live rows instead: one grid step a
live row, the grid's bound is the live count (a dynamic grid: zero live
rows run zero steps), and the row of each block comes from the
scalar-prefetched list through the BlockSpec index maps. ``S`` and ``z``
are aliased input to output, so a row that is not listed is neither read
nor written and keeps its bits; inside a ``lax.scan`` the carry is
updated in place. The attention output is aliased onto ``v``: a dead
row's output is its ``v`` row, finite and the same on every replay (it
feeds row-independent matmuls whose results the caller discards).

Mathematics per live row, all in fp32, exactly ``recurrent_step``'s::

    S += k (x) v;  z += k;  out = (q . S) / (q . z + eps)

on the VPU (no MXU: a rank-1 update and a matvec per head move 2 MiB for
~1 MFLOP). Only the reduction ORDER of ``q . S`` differs from XLA's
einsum, so results agree to fp32 rounding, not bitwise.

``gated_delta_step`` is the same walk for the gated delta rule's state
(``ops/gated_delta.py``): per live row and head, in fp32,

    S <- e^g S;  u = beta (v - S^T k);  S <- S + k (x) u;  out = S^T q

with ``S [B, H, Dk, Dv]`` aliased in place and the output aliased onto
``v``. The decay and the write strength arrive broadcast to the widths they
multiply (``e^g`` as ``[B, H, Dk]``, ``beta`` as ``[B, H, Dv]``: a few KB a
row), so every block is one whole row and nothing in the kernel moves a
per-head scalar between lanes and sublanes.

reference: none (the reference's decode is a Python loop over
``recurrent_step``; checkout never mounted, SURVEY.md s0).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.linear_attention import _DEFAULT_EPS

Array = jax.Array


def live_rows(mask: Array) -> Tuple[Array, Array]:
    """(indices [B] int32, count [1] int32) of the True rows of ``mask``
    [B], ascending; entries past the count are padding and never read."""
    idx = jnp.nonzero(mask, size=mask.shape[0], fill_value=0)[0]
    return idx.astype(jnp.int32), jnp.sum(mask, dtype=jnp.int32)[None]


def check_operands(q, k, v, s, z, idx) -> None:
    """Every block is one whole row (no axis is tiled, so nothing has to
    divide anything); what the kernel does rely on is the state's dtype,
    one dtype for q, k, v (``out`` is aliased onto ``v``) and one row
    count everywhere."""
    if s.dtype != jnp.float32 or z.dtype != jnp.float32:
        raise ValueError(f"decode state must be float32, got {s.dtype}/{z.dtype}")
    if not (q.dtype == k.dtype == v.dtype):
        raise ValueError(f"q, k, v must share a dtype: {q.dtype}/{k.dtype}/{v.dtype}")
    b, h, dk, dv = s.shape
    shapes = (q.shape, k.shape, v.shape, z.shape, idx.shape)
    if shapes != ((b, h, dk), (b, h, dk), (b, h, dv), (b, h, dk), (b,)):
        raise ValueError(f"operands do not fit S {s.shape}: {shapes}")


def _kernel(eps, rows_ref, s_ref, z_ref, q_ref, k_ref, v_ref,
            s_out, z_out, o_ref):
    del rows_ref  # consumed by the index maps
    qf = q_ref[0].astype(jnp.float32)  # [H, Dk]
    kf = k_ref[0].astype(jnp.float32)
    vf = v_ref[0].astype(jnp.float32)  # [H, Dv]
    sf = s_ref[0] + kf[:, :, None] * vf[:, None, :]
    zf = z_ref[0] + kf
    s_out[0] = sf
    z_out[0] = zf
    num = jnp.sum(qf[:, :, None] * sf, axis=1)
    den = jnp.sum(qf * zf, axis=-1, keepdims=True) + eps
    o_ref[0] = (num / den).astype(o_ref.dtype)


def decode_state_step(
    q: Array,
    k: Array,
    v: Array,
    state: Tuple[Array, Array],
    rows: Tuple[Array, Array],
    *,
    eps: float = _DEFAULT_EPS,
    interpret: bool = False,
) -> Tuple[Array, Tuple[Array, Array]]:
    """``recurrent_step(q, k, v, state, eps)`` for the rows ``rows`` lists.

    q, k: [B, H, Dk]; v: [B, H, Dv] (one dtype, the model's compute
    dtype); state = (S [B, H, Dk, Dv], z [B, H, Dk]) in fp32; rows =
    :func:`live_rows` of the row mask. Returns (out [B, H, Dv], (S, z)):
    listed rows updated, every other row of S and z bitwise the input's
    (never touched) and of ``out`` its ``v`` row.
    """
    s, z = state
    idx, count = rows
    check_operands(q, k, v, s, z, idx)
    b, h, dk, dv = s.shape
    row3 = lambda i, rows: (rows[i], 0, 0)  # noqa: E731
    row4 = lambda i, rows: (rows[i], 0, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(count[0],),
        in_specs=[
            pl.BlockSpec((1, h, dk, dv), row4),
            pl.BlockSpec((1, h, dk), row3),
            pl.BlockSpec((1, h, dk), row3),
            pl.BlockSpec((1, h, dk), row3),
            pl.BlockSpec((1, h, dv), row3),
        ],
        out_specs=[
            pl.BlockSpec((1, h, dk, dv), row4),
            pl.BlockSpec((1, h, dk), row3),
            pl.BlockSpec((1, h, dv), row3),
        ],
    )
    s, z, out = pl.pallas_call(
        functools.partial(_kernel, eps),
        name="decode_state_step",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(s.shape, s.dtype),
            jax.ShapeDtypeStruct(z.shape, z.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        # operand numbering counts the scalar-prefetch list: S, z and v
        # are operands 1, 2 and 5
        input_output_aliases={1: 0, 2: 1, 5: 2},
        interpret=interpret,
    )(idx, s, z, q, k, v)
    return out, (s, z)


def check_delta_operands(q, k, v, beta, g, s, idx) -> None:
    """As :func:`check_operands`: every block is one whole row, so nothing
    has to divide anything; the state is fp32 and one row count runs
    through every operand (the kernel casts q, k, v up itself)."""
    if s.dtype != jnp.float32:
        raise ValueError(f"decode state must be float32, got {s.dtype}")
    b, h, dk, dv = s.shape
    shapes = (q.shape, k.shape, v.shape, beta.shape, g.shape, idx.shape)
    if shapes != ((b, h, dk), (b, h, dk), (b, h, dv), (b, h), (b, h), (b,)):
        raise ValueError(f"operands do not fit S {s.shape}: {shapes}")


def _delta_kernel(rows_ref, s_ref, q_ref, k_ref, eg_ref, v_ref, b_ref, s_out, o_ref):
    del rows_ref  # consumed by the index maps
    k = k_ref[0]  # [H, Dk]
    s = s_ref[0] * eg_ref[0][:, :, None]
    u = b_ref[0] * (v_ref[0] - jnp.sum(s * k[:, :, None], axis=1))  # [H, Dv]
    s = s + k[:, :, None] * u[:, None, :]
    s_out[0] = s
    o_ref[0] = jnp.sum(s * q_ref[0][:, :, None], axis=1)


def gated_delta_step(
    q: Array, k: Array, v: Array, beta: Array, g: Array, s: Array,
    rows: Tuple[Array, Array], *, interpret: bool = False,
) -> Tuple[Array, Array]:
    """``ops.gated_delta.gated_delta_step`` for the rows ``rows`` lists.

    q, k: [B, H, Dk]; v: [B, H, Dv]; beta, g: [B, H]; ``s`` [B, H, Dk, Dv]
    fp32; rows = :func:`live_rows` of the row mask. Returns (out [B, H, Dv]
    in v's dtype, s): listed rows updated, every other row of ``s`` bitwise
    the input's (never touched) and of ``out`` its ``v`` row."""
    idx, count = rows
    check_delta_operands(q, k, v, beta, g, s, idx)
    b, h, dk, dv = s.shape
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    eg = jnp.broadcast_to(jnp.exp(f32(g))[..., None], (b, h, dk))
    bv = jnp.broadcast_to(f32(beta)[..., None], (b, h, dv))
    row3 = lambda i, rows: (rows[i], 0, 0)  # noqa: E731
    row4 = lambda i, rows: (rows[i], 0, 0, 0)  # noqa: E731
    key, val = pl.BlockSpec((1, h, dk), row3), pl.BlockSpec((1, h, dv), row3)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(count[0],),
        in_specs=[pl.BlockSpec((1, h, dk, dv), row4), key, key, key, val, val],
        out_specs=[pl.BlockSpec((1, h, dk, dv), row4), val],
    )
    s, out = pl.pallas_call(
        _delta_kernel,
        name="gated_delta_step",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(s.shape, s.dtype),
            jax.ShapeDtypeStruct(v.shape, jnp.float32),
        ],
        # operand numbering counts the scalar-prefetch list: S and v are
        # operands 1 and 5
        input_output_aliases={1: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_DELTA_VMEM_BYTES),
        interpret=interpret,
    )(idx, s, f32(q), f32(k), eg, f32(v), bv)
    return out.astype(v.dtype), s


# a row's S block at 30 heads x 96 x 192 is 2.2 MB (2.9 with its lanes padded
# to whole tiles), double-buffered in and out, beside the kernel's own
# intermediates of that size: past the compiler's default scoped limit
_DELTA_VMEM_BYTES = 64 << 20


def _decay_kernel(rows_ref, s_ref, lam_ref, q_ref, k_ref, v_ref, s_out, o_ref):
    del rows_ref  # consumed by the index maps
    qf = q_ref[0].astype(jnp.float32)  # [H, Dk]
    kf = k_ref[0].astype(jnp.float32)
    vf = v_ref[0].astype(jnp.float32)  # [H, Dv]
    sf = s_ref[0] * lam_ref[...][:, :, None] + kf[:, :, None] * vf[:, None, :]
    s_out[0] = sf
    o_ref[0] = jnp.sum(qf[:, :, None] * sf, axis=1).astype(o_ref.dtype)


def decay_state_step(
    q: Array, k: Array, v: Array, s: Array, slopes: Array,
    rows: Tuple[Array, Array], *, interpret: bool = False,
) -> Tuple[Array, Array]:
    """``ops.linear_attention.decayed_recurrent_step`` for the rows ``rows``
    lists: ``S <- lam S + k (x) v; out = q . S`` with ``lam = exp(-slopes)``
    per head and no normaliser. q, k: [B, H, Dk]; v: [B, H, Dv] (one
    dtype); ``s`` [B, H, Dk, Dv] fp32; ``slopes`` [H]. Returns (out [B, H,
    Dv] in v's dtype, s): listed rows updated, every other row of ``s``
    bitwise the input's (never touched) and of ``out`` its ``v`` row."""
    idx, count = rows
    b, h, dk, dv = s.shape
    check_operands(q, k, v, s, jnp.zeros((b, h, dk), jnp.float32), idx)
    lam = jnp.broadcast_to(
        jnp.exp(-slopes.astype(jnp.float32))[:, None], (h, dk)
    )
    row3 = lambda i, rows: (rows[i], 0, 0)  # noqa: E731
    row4 = lambda i, rows: (rows[i], 0, 0, 0)  # noqa: E731
    key, val = pl.BlockSpec((1, h, dk), row3), pl.BlockSpec((1, h, dv), row3)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(count[0],),
        in_specs=[
            pl.BlockSpec((1, h, dk, dv), row4),
            pl.BlockSpec((h, dk), lambda i, rows: (0, 0)),
            key, key, val,
        ],
        out_specs=[pl.BlockSpec((1, h, dk, dv), row4), val],
    )
    s, out = pl.pallas_call(
        _decay_kernel,
        name="decay_state_step",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(s.shape, s.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        # operand numbering counts the scalar-prefetch list: S and v are
        # operands 1 and 5
        input_output_aliases={1: 0, 5: 1},
        interpret=interpret,
    )(idx, s, lam, q, k, v)
    return out, s


__all__ = ["decay_state_step", "decode_state_step", "gated_delta_step", "live_rows"]
