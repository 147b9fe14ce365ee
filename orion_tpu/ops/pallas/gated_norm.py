"""The delta-rule layer's output gate, ``rms(o) x out_norm x silu(z)``, as a
Mosaic kernel pair, forward and backward.

``ops/gated_delta.py::gated_rms_norm`` is the specification: ``o [B, Hv, T,
Dv]`` head-major, as the rule's kernel leaves it, ``z [B, T, Hv Dv]``
time-major, as the projection makes it, ``w [Dv]`` -> ``[B, T, Hv Dv]`` in
``z``'s dtype, fp32 inside, one rounding at the end. As XLA fusions that is
five passes a forward and seven a backward, with ``o`` and ``z`` written
out in fp32 and three physical relayouts between head-major and time-major
(PERF.md s5). Here time stays on sublanes on both sides: a grid step reads
the ``(tile, Dv)`` slab of each head of a group and ``z``'s matching
lanes, and stores at lane offset ``h x Dv`` of a ``(tile, heads x Dv)``
block, so head-major -> time-major costs nothing, and a head's reduction is
over its own lane tiles.

- *Forward* (``gated_norm_fwd``). Grid ``(batch, time tiles, head
  groups)``, every step independent.
- *Backward* (``gated_norm_bwd``). Residuals are ``o``, ``z`` and ``w``:
  ``rsqrt(mean(o^2) + eps)`` is recomputed. The same grid reads ``dy`` (in
  the output's dtype: the cotangent of a bf16 output is bf16), ``o``, ``z``
  and writes ``do`` head-major in ``o``'s dtype, ``dz`` in ``z``'s, and ``dw``
  as eight fp32 partial sums a grid step, added up outside.
- *Shapes.* ``Dv`` a multiple of 128 and ``T`` of at least one sublane tile
  (``supports``); the last time tile may be ragged (its rows past ``T`` are
  never written, and masked out of ``dw``). ``ops/dispatch.py`` sends
  anything else to the XLA form. Leading axes are merged into the batch.

reference: none (the reference has no gated norm; checkout never mounted,
SURVEY.md s0).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas import kernel_entry

Array = jax.Array

# rows of a sublane tile of bf16 (16) and two of fp32 (8)
_ROWS = 16
# the largest time tile and the lanes of a head group's block (swept on the
# chip at [8, 32, 8192, 128], PERF.md s6 PR 45: from 256 to 2,048 rows and
# 512 to 4,096 lanes every pair that fits reads within 3%)
_TILE_T, _GROUP_LANES = 512, 1024
_VMEM_BYTES = 64 << 20


def time_tile(t: int) -> Optional[int]:
    """Rows of a time tile for a sequence of ``t`` rows: ``_TILE_T``, or
    all the whole sublane tiles of a shorter one. None under one."""
    return min(_TILE_T, t // _ROWS * _ROWS) or None


def _group(heads: int, dv: int) -> int:
    """Heads of a grid step: the most that divide ``heads`` and fill no
    more than ``_GROUP_LANES`` lanes."""
    return next(n for n in range(max(_GROUP_LANES // dv, 1), 0, -1) if heads % n == 0)


def supports(o: Array, z: Array) -> bool:
    """Whether the kernels take ``o [..., Hv, T, Dv]`` beside ``z [..., T,
    Hv Dv]``."""
    return (
        o.ndim >= 3 and o.shape[-1] % 128 == 0
        and time_tile(o.shape[-2]) is not None
        and z.shape[-2:] == (o.shape[-2], o.shape[-3] * o.shape[-1])
    )


def _each_head(o_ref, visit, carry=0):
    """``visit(h, lanes, carry) -> carry`` for every head ``h`` of the block,
    ``lanes`` its columns in a time-major block. A loop, not unrolled: eight
    heads unrolled ran 2.61 ms a forward and 4.05 a backward against 2.67
    and 4.11, and took 2.3 s to compile against 0.34 (my chip run, PR 45).
    A head's whole ``(tile, Dv)`` slab is taken at once: walked in strips
    of rows, as the conv's kernels walk theirs, the forward took 2.76 ms at
    128 rows a strip and the backward 4.85: nothing here is shifted, so
    nothing needs to stay in registers."""
    heads, _, dv = o_ref.shape[1:]

    def body(h, carry):
        return visit(h, pl.ds(pl.multiple_of(h * dv, 128), dv), carry)

    return jax.lax.fori_loop(0, heads, body, carry)


def _normed(o, eps):
    """fp32 ``o`` -> (``o`` normalised, the ``rsqrt`` that did it)."""
    r = jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + eps)
    return o * r, r


def _fwd_kernel(o_ref, z_ref, w_ref, y_ref, *, eps):
    f32 = jnp.float32
    w = w_ref[...]

    def visit(h, lanes, carry):
        n, _ = _normed(o_ref[0, h].astype(f32), eps)
        z = z_ref[0, :, lanes].astype(f32)
        y_ref[0, :, lanes] = (n * w * (z * jax.nn.sigmoid(z))).astype(y_ref.dtype)
        return carry

    _each_head(o_ref, visit)


def _bwd_kernel(dy_ref, o_ref, z_ref, w_ref, do_ref, dz_ref, dw_ref, *, eps, length):
    f32 = jnp.float32
    w = w_ref[...]
    tile, dv = o_ref.shape[2:]
    if length % tile:  # a ragged last tile: its rows past the sequence's end
        row = jax.lax.broadcasted_iota(jnp.int32, (tile, dv), 0)
        real = row < length - pl.program_id(1) * tile

    def visit(h, lanes, acc):
        n, r = _normed(o_ref[0, h].astype(f32), eps)
        z = z_ref[0, :, lanes].astype(f32)
        dy = dy_ref[0, :, lanes].astype(f32)
        s = jax.nn.sigmoid(z)
        gate, dyn = z * s, dy * n
        dz_ref[0, :, lanes] = (dyn * w * (s * (1.0 + z * (1.0 - s)))).astype(dz_ref.dtype)
        dn = dy * gate * w
        do_ref[0, h] = (
            r * (dn - n * jnp.mean(dn * n, -1, keepdims=True))
        ).astype(do_ref.dtype)
        dw = dyn * gate
        if length % tile:
            dw = jnp.where(real, dw, 0.0)
        return acc + dw.reshape(tile // 8, 8, dv).sum(axis=0)

    dw_ref[0, 0, 0] = _each_head(o_ref, visit, jnp.zeros((8, dv), f32))


def _blocks(o4: Array):
    """The grid ``(batch, time tiles, head groups)`` over o ``[B, Hv, T,
    Dv]`` and its operands' blocks: a group's ``heads`` slabs, the same
    rows and columns of a time-major ``[B, T, Hv Dv]`` array (``rows``),
    the norm's ``weights`` and a step's eight partial sums of ``dw``."""
    b, hv, t, dv = o4.shape
    tile, group = time_tile(t), _group(hv, dv)
    return (b, pl.cdiv(t, tile), hv // group), {
        "heads": pl.BlockSpec((1, group, tile, dv), lambda i, s, g: (i, g, s, 0)),
        "rows": pl.BlockSpec((1, tile, group * dv), lambda i, s, g: (i, s, g)),
        "weights": pl.BlockSpec((1, dv), lambda i, s, g: (0, 0)),
        "sums": pl.BlockSpec((1, 1, 1, 8, dv), lambda i, s, g: (i, s, g, 0, 0)),
    }


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * 3, vmem_limit_bytes=_VMEM_BYTES
    )


def _operands(o, z, w):
    """o as ``[B, Hv, T, Dv]``, z as ``[B, T, Hv Dv]``, w as fp32 ``[1, Dv]``."""
    return (
        o.reshape((-1,) + o.shape[-3:]), z.reshape((-1,) + z.shape[-2:]),
        w.astype(jnp.float32).reshape(1, -1),
    )


@kernel_entry("gated_norm_fwd", "eps", "interpret")
def _forward(o, z, w, eps, interpret):
    o4, z3, w2 = _operands(o, z, w)
    grid, block = _blocks(o4)
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        name="gated_norm_fwd",
        grid=grid,
        in_specs=[block[k] for k in ("heads", "rows", "weights")],
        out_specs=block["rows"],
        out_shape=jax.ShapeDtypeStruct(z3.shape, z.dtype),
        compiler_params=_params(),
        interpret=interpret,
    )(o4, z3, w2)
    return y.reshape(z.shape)


@kernel_entry("gated_norm_bwd", "eps", "interpret")
def _backward(o, z, w, dy, eps, interpret):
    o4, z3, w2 = _operands(o, z, w)
    grid, block = _blocks(o4)
    do, dz, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, length=o4.shape[2]),
        name="gated_norm_bwd",
        grid=grid,
        in_specs=[block[k] for k in ("rows", "heads", "rows", "weights")],
        out_specs=[block[k] for k in ("heads", "rows", "sums")],
        out_shape=[
            jax.ShapeDtypeStruct(o4.shape, o.dtype),
            jax.ShapeDtypeStruct(z3.shape, z.dtype),
            jax.ShapeDtypeStruct(grid + (8, o4.shape[-1]), jnp.float32),
        ],
        compiler_params=_params(),
        interpret=interpret,
    )(dy.reshape(z3.shape), o4, z3, w2)
    return do.reshape(o.shape), dz.reshape(z.shape), dw.sum(axis=(0, 1, 2, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gated_norm(o, z, w, eps, interpret):
    return _forward(o, z, w, eps, interpret)


def _gated_norm_fwd(o, z, w, eps, interpret):
    return _forward(o, z, w, eps, interpret), (o, z, w)


def _gated_norm_bwd(eps, interpret, residuals, dy):
    o, z, w = residuals
    do, dz, dw = _backward(o, z, w, dy, eps, interpret)
    return do, dz, dw.astype(w.dtype)


_gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)


def gated_rms_norm_pallas(
    o: Array, z: Array, w: Array, *, eps: float, interpret: bool = False
) -> Array:
    """``ops/gated_delta.py::gated_rms_norm`` as the kernels above, for
    operands that ``supports`` takes; differentiable in o, z and w."""
    if not supports(o, z):
        raise ValueError(f"gated_norm kernels do not take o {o.shape}, z {z.shape}")
    return _gated_norm(o, z, w, eps, interpret)


__all__ = ["gated_rms_norm_pallas", "supports", "time_tile"]
