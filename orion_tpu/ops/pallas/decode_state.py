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


__all__ = ["decode_state_step", "live_rows"]
