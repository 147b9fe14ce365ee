"""The state-space layers' one-token step for the rows of a
slot-multiplexed carry that are live in this chunk, and nothing at all for
the others: ``ops/ssm.py::ssm_step_packed`` as the row walk of
``ops/pallas/decode_state.py`` (a scalar-prefetched compacted list of live
rows, one grid step a listed row, the grid's bound the live count, every
block one whole row taken through the list by its index map).

Per listed row, on the state as it is held, ``S [H / k, N, k P]`` fp32 (the
state width on sublanes, ``k`` heads of one group side by side on lanes),

    S <- decay * S + B (x) u;    y = sum_n S[:, n, :] C[n]

with ``decay = exp(dt A)`` and ``u = dt x`` arriving per LANE ``[H / k, k
P]`` (a broadcast over sublanes) and the group's ``B``, ``C`` per SUBLANE
``[H / k, N]`` (a few KB a row, repeated for every packed row by the
wrapper): the shapes of ``decode_state.py``'s delta-rule step, so nothing
in the kernel reduces across lanes. ``S`` is aliased in place and the output
onto ``u``: an unlisted row keeps its state's bits and reads back its ``u``
row, finite and the same on every replay. All on the VPU: a row moves 2 MB
each way for 1.5 MFLOP (64 heads; 4.19 MB and 3 MFLOP at 128). With several
groups the wrapper repeats each group's ``B`` and ``C`` for the ``H / (k
G)`` packed rows of the group (``packed_step_operands``): eight pairs a row
become 64 sublane rows of ``N``, 64 KB beside the 4 MB of state.

reference: none (the reference has no state-space layer; checkout never
mounted, SURVEY.md s0).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas import kernel_entry
from orion_tpu.ops.ssm import packed_step_operands

Array = jax.Array

# a row's S block is 2 MB at 32 x 128 x 128 (64 heads in one group) and 4.19
# MB at 64 x 128 x 128 (128 heads in 8 groups, two of a group side by side):
# double-buffered in and out that is 8 or 16.8 MB, beside the kernel's own
# intermediates of a row's size (the updated S and S * C before its sum). The
# larger compiles for a v5e under this limit (tests/test_chip_compile.py), so
# a row is not split over head groups
_VMEM_BYTES = 64 << 20


def _step_kernel(rows_ref, s_ref, decay_ref, b_ref, c_ref, u_ref, s_out, y_ref):
    del rows_ref  # consumed by the index maps
    s = (
        s_ref[0] * decay_ref[0][:, None, :]
        + b_ref[0][:, :, None] * u_ref[0][:, None, :]
    )
    s_out[0] = s
    y_ref[0] = jnp.sum(s * c_ref[0][:, :, None], axis=1)


def check_step_operands(x, bm, s, idx) -> None:
    """As ``decode_state.check_operands``: every block is one whole row (no
    axis is tiled, so nothing has to divide anything); what the kernel does
    rely on is the state's dtype and one row count and one set of widths
    everywhere."""
    if s.dtype != jnp.float32:
        raise ValueError(f"decode state must be float32, got {s.dtype}")
    b, hg, n, lanes = s.shape
    shapes = (x.shape[0], x.shape[1] * x.shape[2], bm.shape[-1], idx.shape)
    if shapes != (b, hg * lanes, n, (b,)):
        raise ValueError(f"operands do not fit S {s.shape}: {shapes}")


@kernel_entry("ssm_state_step", "pack", "interpret")
def ssm_state_step(
    x: Array, dt: Array, a: Array, bm: Array, cm: Array, s: Array, pack: int,
    rows: Tuple[Array, Array], *, interpret: bool = False,
) -> Tuple[Array, Array]:
    """``ops.ssm.ssm_step_packed`` for the rows ``rows`` lists. x ``[B, H,
    P]``; dt ``[B, H]``; ``a`` [H]; bm, cm ``[B, G, N]``; ``s`` ``[B, H /
    pack, N, pack P]`` fp32; rows = ``decode_state.live_rows`` of the row
    mask. Returns (y [B, H, P] in x's dtype, s): listed rows updated, every
    other row of ``s`` bitwise the input's (never touched) and of ``y`` its
    ``dt x`` row."""
    idx, count = rows
    check_step_operands(x, bm, s, idx)
    b, hg, n, lanes = s.shape
    decay, u, bk, ck = packed_step_operands(x, dt, a, bm, cm, pack)
    row3 = lambda i, rows: (rows[i], 0, 0)  # noqa: E731
    row4 = lambda i, rows: (rows[i], 0, 0, 0)  # noqa: E731
    lane, sub = pl.BlockSpec((1, hg, lanes), row3), pl.BlockSpec((1, hg, n), row3)
    state = pl.BlockSpec((1, hg, n, lanes), row4)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(count[0],),
        in_specs=[state, lane, sub, sub, lane],
        out_specs=[state, lane],
    )
    s, y = pl.pallas_call(
        _step_kernel,
        name="ssm_state_step",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(s.shape, s.dtype),
            jax.ShapeDtypeStruct(u.shape, jnp.float32),
        ],
        # operand numbering counts the scalar-prefetch list: S and u are
        # operands 1 and 5
        input_output_aliases={1: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(idx, s, decay, bk, ck, u)
    return y.reshape(x.shape).astype(x.dtype), s


__all__ = ["ssm_state_step"]
