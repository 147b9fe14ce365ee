"""Row-sparse decode-state kernels: ``recurrent_step`` for the rows of a
slot-multiplexed carry that are live in this chunk, and nothing at all for
the others.

The slot-multiplexed decode programs (generate.py) run every step of
every linear layer over ALL slots of the carry and then select the old
state back for rows that are not emitting; XLA's fused update therefore
reads and writes the whole fp32 ``S [B, H, Dk, Dv]`` (1 MiB a row at
lm_1b3 widths) whatever the occupancy: half of the served programs'
device time with a third of 64 slots decoding (PERF.md, PR 29).

These kernels walk a COMPACTED list of live rows instead: one grid step a
live row, the grid's bound is the live count (a dynamic grid: zero live
rows run zero steps), and the row of each block comes from the
scalar-prefetched list through the BlockSpec index maps, so a row that is
not listed is neither read nor written and keeps its bits. The attention
output is aliased onto ``v``: a dead row's output is its ``v`` row, finite
and the same on every replay (it feeds row-independent matmuls whose
results the caller discards).

The linear layers' ``(S, z)`` is WRITTEN once a chunk, not once a step
(PERF.md, PR 38). Inside a chunk of ``n`` steps that starts from ``(S0,
z0)``, step ``j`` of a row is, all in fp32,

    out_j = (q_j . S0 + sum_{s<=j} (q_j . k_s) v_s)
            / (q_j . z0 + sum_{s<=j} q_j . k_s + eps)

which is ``recurrent_step``'s ``q . S_j / (q . z_j + eps)`` with ``S_j = S0
+ sum_{s<=j} k_s (x) v_s`` multiplied out: the same products, another
order of the sums, so results agree to fp32 rounding, not bitwise.
``decode_state_step`` only READS ``(S0, z0)`` and the chunk's own rows
``kc``, ``vc`` ``[B, n, H, D]`` (the model's compute dtype, so a row's
buffer is 1/16 of its ``S`` at 16 steps) and writes the output and row
``j`` of ``kc``, ``vc``, on the VPU (no MXU: a matvec per head moves 1
MiB for ~0.5 MFLOP); ``decode_state_flush`` (end of the file), after the
scan, adds the chunk's ``n`` rank-1 terms to ``S`` and its keys to ``z``
in place, as one ``K^T V`` a head on the MXU.

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
from orion_tpu.ops.pallas import kernel_entry

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


def check_chunk_operands(s, z, kc, vc, idx) -> None:
    """The chunk's own rows fit the state: ``kc`` [B, n, H, Dk] and ``vc``
    [B, n, H, Dv] of one dtype, the state fp32."""
    if s.dtype != jnp.float32 or z.dtype != jnp.float32:
        raise ValueError(f"decode state must be float32, got {s.dtype}/{z.dtype}")
    if kc.dtype != vc.dtype:
        raise ValueError(f"kc, vc must share a dtype: {kc.dtype}/{vc.dtype}")
    b, h, dk, dv = s.shape
    n = kc.shape[1]
    shapes = (z.shape, kc.shape, vc.shape, idx.shape)
    if shapes != ((b, h, dk), (b, n, h, dk), (b, n, h, dv), (b,)):
        raise ValueError(f"operands do not fit S {s.shape}: {shapes}")


def _step_kernel(eps, rows_ref, j_ref, s_ref, z_ref, q_ref, k_ref, kc_ref,
                 v_ref, vc_ref, kc_out, o_ref, vc_out):
    j = j_ref[rows_ref[pl.program_id(0)]]
    k, v = k_ref[0], v_ref[0]
    # before the output is written: ``out`` is aliased onto ``v``, and on
    # the chip a read of ``v_ref`` after that write returns the output
    kc_out[0, 0] = k.astype(kc_out.dtype)
    vc_out[0, 0] = v.astype(vc_out.dtype)
    qf = q_ref[0].astype(jnp.float32)  # [H, Dk]
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)  # [H, Dv]
    kcf = kc_ref[0].astype(jnp.float32)  # [n, H, Dk]
    vcf = vc_ref[0].astype(jnp.float32)
    # q . k_s of the chunk's earlier steps, [n, H, 1]; rows from j on hold
    # nothing of this chunk yet
    a = jnp.sum(kcf * qf[None], axis=-1, keepdims=True)
    step = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    a = jnp.where(step < j, a, 0.0)
    now = jnp.sum(qf * kf, axis=-1, keepdims=True)  # [H, 1]
    num = (
        jnp.sum(qf[:, :, None] * s_ref[0], axis=1)
        + jnp.sum(a * vcf, axis=0) + now * vf
    )
    den = (
        jnp.sum(qf * z_ref[0], axis=-1, keepdims=True)
        + jnp.sum(a, axis=0) + now + eps
    )
    o_ref[0] = (num / den).astype(o_ref.dtype)


@kernel_entry("decode_state_step", "eps", "interpret")
def decode_state_step(
    q: Array,
    k: Array,
    v: Array,
    state: Tuple[Array, Array],
    chunk: Tuple[Array, Array],
    j: Array,
    rows: Tuple[Array, Array],
    *,
    eps: float = _DEFAULT_EPS,
    interpret: bool = False,
) -> Tuple[Array, Tuple[Array, Array]]:
    """Step ``j`` of a chunk's decode scan for the rows ``rows`` lists, the
    state read and not written.

    q, k: [B, H, Dk]; v: [B, H, Dv] (one dtype, the model's compute
    dtype); state = (S [B, H, Dk, Dv], z [B, H, Dk]) in fp32 as the chunk
    found it; chunk = (kc [B, n, H, Dk], vc [B, n, H, Dv]), whose rows
    ``< j`` hold the k and v of the chunk's earlier steps; ``j`` [B] int32,
    each row's step in the chunk; rows = :func:`live_rows` of the row
    mask. Returns (out [B, H, Dv], (kc, vc)): a listed row's output is
    ``recurrent_step``'s after its ``j + 1`` steps and row ``j`` of its kc,
    vc now holds this k, v; every other row of kc, vc is bitwise the
    input's (never touched) and of ``out`` its ``v`` row."""
    s, z = state
    kc, vc = chunk
    idx, count = rows
    check_operands(q, k, v, s, z, idx)
    check_chunk_operands(s, z, kc, vc, idx)
    if j.shape != idx.shape:
        raise ValueError(f"one step index a row: {j.shape} against {idx.shape}")
    b, h, dk, dv = s.shape
    n = kc.shape[1]
    row3 = lambda i, rows, j: (rows[i], 0, 0)  # noqa: E731
    row4 = lambda i, rows, j: (rows[i], 0, 0, 0)  # noqa: E731
    at_j = lambda i, rows, j: (rows[i], j[rows[i]], 0, 0)  # noqa: E731
    key, val = pl.BlockSpec((1, h, dk), row3), pl.BlockSpec((1, h, dv), row3)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(count[0],),
        in_specs=[
            pl.BlockSpec((1, h, dk, dv), row4), key, key, key,
            pl.BlockSpec((1, n, h, dk), row4), val,
            pl.BlockSpec((1, n, h, dv), row4),
        ],
        # row j of a listed row's buffers is a block of its own: the rest
        # of the 2 x n x H x D buffer is not written back
        out_specs=[
            pl.BlockSpec((1, 1, h, dk), at_j), val,
            pl.BlockSpec((1, 1, h, dv), at_j),
        ],
    )
    kc, out, vc = pl.pallas_call(
        functools.partial(_step_kernel, eps),
        name="decode_state_step",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(kc.shape, kc.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(vc.shape, vc.dtype),
        ],
        # operand numbering counts the two scalar-prefetch operands: kc, v
        # and vc are operands 6, 7 and 8
        input_output_aliases={6: 0, 7: 1, 8: 2},
        interpret=interpret,
    )(idx, jnp.clip(j, 0, n - 1).astype(jnp.int32), s, z, q, k, kc, v, vc)
    return out, (kc, vc)


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


@kernel_entry("gated_delta_step", "interpret")
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


@kernel_entry("decay_state_step", "interpret")
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


def _flush_kernel(precision, rows_ref, s_ref, z_ref, kt_ref, vt_ref, s_out, z_out):
    del rows_ref  # consumed by the index maps
    for h in range(s_ref.shape[1]):
        s_out[0, h] = s_ref[0, h] + jax.lax.dot_general(
            kt_ref[0, h], vt_ref[0, h], (((0,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32,
        )
    z_out[0] = z_ref[0] + jnp.sum(kt_ref[0].astype(jnp.float32), axis=1)


@kernel_entry("decode_state_flush", "interpret")
def decode_state_flush(
    state: Tuple[Array, Array],
    chunk: Tuple[Array, Array],
    rows: Tuple[Array, Array],
    *,
    interpret: bool = False,
) -> Tuple[Array, Array]:
    """The chunk's rows into the state, once, after the scan: for the rows
    ``rows`` lists ``S += sum_s k_s (x) v_s`` and ``z += sum_s k_s`` over
    ALL ``n`` rows of ``chunk`` = (kc [B, n, H, Dk], vc [B, n, H, Dv]) (a
    listed row stepped at every step of the scan), in fp32 and in place.
    Returns (S, z): an unlisted row is never touched and keeps its bits.

    ``K^T V`` a head on the MXU with fp32 accumulation: bf16 products are
    exact in fp32, so only the order of the ``n`` adds differs from ``n``
    in-place steps (which, as rank-1 adds on the VPU, measured 9.4 us a row
    against 2.7: PERF.md, PR 38). The rows arrive head-major ``[B, H, n,
    D]``, ``n`` zero-padded to whole sublane tiles: XLA transposes 1/16 of
    the state's bytes once a chunk."""
    s, z = state
    kc, vc = chunk
    idx, count = rows
    check_chunk_operands(s, z, kc, vc, idx)
    b, h, dk, dv = s.shape
    tile = 32 // kc.dtype.itemsize  # sublanes of a tile: 8 fp32, 16 bf16
    pad = ((0, 0), (0, 0), (0, -kc.shape[1] % tile), (0, 0))
    kt, vt = (jnp.pad(jnp.swapaxes(x, 1, 2), pad) for x in (kc, vc))
    n = kt.shape[2]
    exact = jax.lax.Precision.HIGHEST if kc.dtype == jnp.float32 else None
    row3 = lambda i, rows: (rows[i], 0, 0)  # noqa: E731
    row4 = lambda i, rows: (rows[i], 0, 0, 0)  # noqa: E731
    state_specs = [pl.BlockSpec((1, h, dk, dv), row4), pl.BlockSpec((1, h, dk), row3)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(count[0],),
        in_specs=state_specs + [
            pl.BlockSpec((1, h, n, dk), row4), pl.BlockSpec((1, h, n, dv), row4),
        ],
        out_specs=state_specs,
    )
    return tuple(pl.pallas_call(
        functools.partial(_flush_kernel, exact),
        name="decode_state_flush",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(s.shape, s.dtype),
            jax.ShapeDtypeStruct(z.shape, z.dtype),
        ],
        # operand numbering counts the scalar-prefetch list: S and z are
        # operands 1 and 2
        input_output_aliases={1: 0, 2: 1},
        interpret=interpret,
    )(idx, s, z, kt, vt))


__all__ = [
    "decay_state_step", "decode_state_flush", "decode_state_step",
    "gated_delta_step", "live_rows",
]
