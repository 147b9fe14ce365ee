"""The short causal depthwise convolution + SiLU of the delta-rule and
state-space layers as a Mosaic kernel pair, forward and backward.

``ops/gated_delta.py::causal_short_conv`` is the specification: ``y_t =
silu(sum_j w[j] x_{t - (W - 1) + j} + bias)``. There the ``W`` shifted views
are row slices of a padded copy; row is the sublane axis, so XLA relays
each view that starts off a tile, and autodiff turns each into a pad of an
fp32 ``[B, T, C]`` array. Here a grid step loads one ``[tT, tC]`` tile of
``x`` once, with the ``HALO`` rows before it (a second ``BlockSpec`` on the
same array; the first tile's are zeros or the carried ``tail``), and makes
the views in VMEM: sublane rotations, on the XLU. A tile is walked in
strips of ``_STRIP`` rows x ``_LANES`` lanes, each read with the sublane
tile before it, so that a strip's views, sum and SiLU stay in the vector
registers (taken a whole tile at a time every intermediate went through
VMEM: 6.1 ms a forward against 4.3, 12.1 a backward against 7.0, at 512 x
512 tiles; my chip run, PR 42).

- *Forward* (``short_conv_fwd``). Grid ``(channel tiles, batch, time
  tiles)``, every step independent. fp32 accumulation in ``j`` order as the
  XLA form sums, ``bias`` added, SiLU, one rounding to ``x.dtype``.
- *Backward* (``short_conv_bwd``). Residuals are ``x``, ``w``, ``bias`` and
  ``tail``: the pre-activation is recomputed in VMEM, for the tile and the
  ``HALO`` rows after it, whose ``g = dy silu'(y)`` the tile's ``dx_t =
  sum_j w[j] g_{t + (W - 1) - j}`` reads (zeros past the last tile): one
  walk writes ``g`` to an fp32 VMEM scratch, a second reads it back a strip
  and the eight rows after it at a time. The same grid: ``dw[j] = sum g_t x_{t - (W - 1)
  + j}`` and ``dbias = sum g_t`` accumulate in fp32 over the two inner
  axes into one resident ``[W + 1, 8, tC]`` block a channel tile (eight
  partial sums a channel, added up outside). ``dtail`` reads ``g``'s first
  ``W - 1`` rows alone: autodiff of the XLA form on those rows, a few KB.
- *Shapes.* ``C`` a multiple of 128, ``T`` a whole number of time tiles of
  ``time_tile(T)`` rows (``supports``); ``ops/dispatch.py`` sends anything
  else to the XLA form. Leading axes are merged into the batch.

reference: none (the reference has no convolution; checkout never mounted,
SURVEY.md s0).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.gated_delta import causal_short_conv as _xla_form
from orion_tpu.ops.pallas import kernel_entry

Array = jax.Array

# rows of a halo block: a whole sublane tile of bf16 (16) and of fp32 (8)
HALO = 16
# the largest tile, rows x channels, and the rows x lanes of the strips a
# tile is walked in (swept on the chip at [8, 8192, 8192], PERF.md s6 PR 42:
# a grid step costs ~0.4 us, so 512 x 512 tiles lose 0.6 ms of 3.7 a call)
_TILE_T, _TILE_C = 2048, 512
_STRIP, _LANES = 64, 256
# rows of the weights' block: the window's W rows, then the bias
_W_ROWS = 8
_VMEM_BYTES = 64 << 20


def time_tile(t: int) -> Optional[int]:
    """Rows of a time tile for a sequence of ``t`` rows: all of a short one,
    else the largest whole divisor from 128 to ``_TILE_T``; a multiple of
    ``HALO``. None where there is none."""
    if t % HALO:
        return None
    if t <= _TILE_T:
        return t
    for rows in range(_TILE_T, 127, -HALO):
        if t % rows == 0:
            return rows
    return None


def _channel_tile(c: int) -> int:
    return next(n for n in range(_TILE_C, 0, -128) if c % n == 0)


def supports(x: Array, w: Array) -> bool:
    """Whether the kernels take ``x [..., T, C]`` under ``w [W, C]``."""
    return (
        x.ndim >= 2 and x.shape[-1] % 128 == 0 and w.shape[0] < _W_ROWS
        and w.shape[1] == x.shape[-1]
        and time_tile(x.shape[-2]) is not None
    )


def _strip(rows: int) -> int:
    """Rows of the strips a tile is walked in: what one strip keeps live
    stays in the vector registers."""
    return next(n for n in (_STRIP, 32, HALO) if rows % n == 0)


def _views(xe: Array, width: int, rows: int):
    """``xe`` = HALO rows, then the rows the views are taken for: view ``j``
    (of ``rows`` rows) holds ``x_{t - (W - 1) + j}`` at row ``t``. fp32: the
    rotations need only the last sublane tile of the halo."""
    xe = xe[HALO - 8:]
    return [
        xe[8:8 + rows] if j == width - 1
        else pltpu.roll(xe, width - 1 - j, axis=0)[8:8 + rows]
        for j in range(width)
    ]


def _pre_activation(views, w_rows, width: int) -> Array:
    y = views[0] * w_rows[0]
    for j in range(1, width):
        y = y + views[j] * w_rows[j]
    return y + w_rows[width]


def _lane_chunks(tc: int):
    step = _LANES if tc % _LANES == 0 else 128
    return [slice(at, at + step) for at in range(0, tc, step)]


def _fwd_kernel(x_ref, prev_ref, tail_ref, wb_ref, o_ref, *, width, activation):
    f32 = jnp.float32
    rows = x_ref.shape[1]
    strip = _strip(rows)
    first = pl.program_id(2) == 0
    for lanes in _lane_chunks(x_ref.shape[2]):
        w_rows = [wb_ref[j:j + 1, lanes] for j in range(width + 1)]

        def out(xe):
            y = _pre_activation(_views(xe, width, strip), w_rows, width)
            return (y * jax.nn.sigmoid(y) if activation else y).astype(o_ref.dtype)

        prev = jnp.where(
            first, tail_ref[0, :, lanes].astype(f32), prev_ref[0, :, lanes].astype(f32)
        )
        o_ref[0, 0:strip, lanes] = out(
            jnp.concatenate([prev, x_ref[0, 0:strip, lanes].astype(f32)], axis=0)
        )

        def body(i, carry):
            r0 = pl.multiple_of(i * strip, strip)
            xe = x_ref[0, pl.ds(r0 - HALO, strip + HALO), lanes].astype(f32)
            o_ref[0, pl.ds(r0, strip), lanes] = out(xe)
            return carry

        if rows > strip:  # a tile of one strip has no room for the read
            jax.lax.fori_loop(1, rows // strip, body, 0)


def _bwd_kernel(
    x_ref, prev_ref, next_ref, tail_ref, dy_ref, dy_next_ref, wb_ref,
    dx_ref, dwb_ref, g_ref, *, width, activation,
):
    f32 = jnp.float32
    rows = x_ref.shape[1]
    strip = _strip(rows)
    step, steps = pl.program_id(2), pl.num_programs(2)

    @pl.when((pl.program_id(1) == 0) & (step == 0))
    def _():
        dwb_ref[...] = jnp.zeros_like(dwb_ref)

    by8 = lambda a: a.reshape(a.shape[0] // 8, 8, a.shape[1]).sum(axis=0)  # noqa: E731
    for lanes in _lane_chunks(x_ref.shape[2]):
        w_rows = [wb_ref[j:j + 1, lanes] for j in range(width + 1)]

        def grad(xe, dy):
            """``g = dy silu'(y)`` for the rows after ``xe``'s halo, and the
            views of x that made ``y``."""
            views = _views(xe, width, dy.shape[0])
            if not activation:
                return dy, views
            y = _pre_activation(views, w_rows, width)
            s = jax.nn.sigmoid(y)
            return dy * (s * (1.0 + y * (1.0 - s))), views

        def summed(acc, g, views):
            return tuple(a + by8(g * v) for a, v in zip(acc, views)) + (acc[width] + by8(g),)

        # first the tile's g, a strip at a time, into the scratch; dw and
        # dbias ride along in registers
        prev = jnp.where(
            step == 0, tail_ref[0, :, lanes].astype(f32), prev_ref[0, :, lanes].astype(f32)
        )
        g, views = grad(
            jnp.concatenate([prev, x_ref[0, 0:strip, lanes].astype(f32)], axis=0),
            dy_ref[0, 0:strip, lanes].astype(f32),
        )
        g_ref[0:strip, lanes] = g
        zero = jnp.zeros((8, g.shape[1]), f32)
        acc = summed((zero,) * (width + 1), g, views)

        def body(i, acc):
            r0 = pl.multiple_of(i * strip, strip)
            g, views = grad(
                x_ref[0, pl.ds(r0 - HALO, strip + HALO), lanes].astype(f32),
                dy_ref[0, pl.ds(r0, strip), lanes].astype(f32),
            )
            g_ref[pl.ds(r0, strip), lanes] = g
            return summed(acc, g, views)

        if rows > strip:
            acc = jax.lax.fori_loop(1, rows // strip, body, acc)
        for j in range(width + 1):
            dwb_ref[j, :, lanes] += acc[j]
        # the rows after the tile, whose g the tile's last dx rows read:
        # past the sequence's end there is no output for a gradient to reach
        after, _ = grad(
            jnp.concatenate(
                [x_ref[0, rows - HALO:rows, lanes].astype(f32),
                 next_ref[0, :, lanes].astype(f32)], axis=0,
            ),
            dy_next_ref[0, :, lanes].astype(f32),
        )
        g_ref[rows:rows + HALO, lanes] = jnp.where(step < steps - 1, after, 0.0)

        # then dx_t = sum_j w[j] g_{t + (W - 1) - j}, a strip at a time
        def dx_body(i, carry):
            r0 = pl.multiple_of(i * strip, strip)
            ge = g_ref[pl.ds(r0, strip + 8), lanes]
            dx = ge[:strip] * w_rows[width - 1]
            for j in range(width - 1):
                ahead = pltpu.roll(ge, strip + 8 - (width - 1 - j), axis=0)[:strip]
                dx = dx + ahead * w_rows[j]
            dx_ref[0, pl.ds(r0, strip), lanes] = dx.astype(dx_ref.dtype)
            return carry

        jax.lax.fori_loop(0, rows // strip, dx_body, 0)


def _operands(x, w, tail, bias):
    """The kernels' operands: x as ``[B, T, C]``, the tail as the last rows
    of a zero halo block ``[B, HALO, C]``, and the weights' fp32 block
    ``[_W_ROWS, C]``: the window's rows, then the bias (or zeros)."""
    width, c = w.shape
    x3 = x.reshape((-1,) + x.shape[-2:])
    halo = jnp.zeros((x3.shape[0], HALO, c), x.dtype)
    if tail is not None:
        halo = halo.at[:, HALO - (width - 1):].set(
            tail.reshape(-1, width - 1, c).astype(x.dtype)
        )
    wb = jnp.zeros((_W_ROWS, c), jnp.float32).at[:width].set(w.astype(jnp.float32))
    if bias is not None:
        wb = wb.at[width].set(bias.astype(jnp.float32))
    return x3, halo, wb


def _grid(x3: Array):
    """The grid ``(channel tiles, batch, time tiles)`` over x ``[B, T, C]``
    and the blocks its operands come in: a ``tile``, the HALO rows
    ``before`` or ``after`` it (clamped at the sequence's ends, where the
    kernels do not read them), a row's ``tail`` block, the ``weights``."""
    b, t, c = x3.shape
    tt, tc = time_tile(t), _channel_tile(c)
    per, last = tt // HALO, t // HALO - 1
    halo = lambda at: pl.BlockSpec((1, HALO, tc), lambda k, i, s: (i, at(s), k))  # noqa: E731
    return (c // tc, b, t // tt), {
        "tile": pl.BlockSpec((1, tt, tc), lambda k, i, s: (i, s, k)),
        "before": halo(lambda s: jnp.maximum(s * per - 1, 0)),
        "after": halo(lambda s: jnp.minimum((s + 1) * per, last)),
        "tail": halo(lambda s: 0),
        "weights": pl.BlockSpec((_W_ROWS, tc), lambda k, i, s: (0, k)),
    }


def _params(*semantics: str):
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=_VMEM_BYTES
    )


@kernel_entry("short_conv_fwd", "activation", "interpret")
def _forward(x, w, tail, bias, activation, interpret):
    x3, halo, wb = _operands(x, w, tail, bias)
    grid, block = _grid(x3)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, width=w.shape[0], activation=activation),
        name="short_conv_fwd",
        grid=grid,
        in_specs=[block[k] for k in ("tile", "before", "tail", "weights")],
        out_specs=block["tile"],
        out_shape=jax.ShapeDtypeStruct(x3.shape, x.dtype),
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret,
    )(x3, x3, halo, wb)
    return out.reshape(x.shape)


@kernel_entry("short_conv_bwd", "activation", "interpret")
def _backward(x, w, tail, bias, dy, activation, interpret):
    x3, halo, wb = _operands(x, w, tail, bias)
    dy3 = dy.reshape(x3.shape)
    width, c = w.shape
    grid, block = _grid(x3)
    tt, tc = block["tile"].block_shape[1:]
    dx, dwb = pl.pallas_call(
        functools.partial(_bwd_kernel, width=width, activation=activation),
        name="short_conv_bwd",
        grid=grid,
        in_specs=[
            block[k] for k in
            ("tile", "before", "after", "tail", "tile", "after", "weights")
        ],
        out_specs=[
            block["tile"],
            pl.BlockSpec((width + 1, 8, tc), lambda k, i, s: (0, 0, k)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x3.shape, x.dtype),
            jax.ShapeDtypeStruct((width + 1, 8, c), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((tt + HALO, tc), jnp.float32)],
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
    )(x3, x3, x3, halo, dy3, dy3, wb)
    dwb = dwb.sum(axis=1)
    return dx.reshape(x.shape), dwb[:width], dwb[width]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _short_conv(x, w, tail, bias, activation, interpret):
    return _forward(x, w, tail, bias, activation, interpret)


def _short_conv_fwd(x, w, tail, bias, activation, interpret):
    return _forward(x, w, tail, bias, activation, interpret), (x, w, tail, bias)


def _short_conv_bwd(activation, interpret, residuals, dy):
    x, w, tail, bias = residuals
    dx, dw, dbias = _backward(x, w, tail, bias, dy, activation, interpret)
    dtail = None
    if tail is not None:
        # the tail reaches the first W - 1 outputs alone
        head = lambda a: jax.lax.slice_in_dim(a, 0, w.shape[0] - 1, axis=-2)  # noqa: E731
        _, pull = jax.vjp(
            lambda tl: _xla_form(head(x), w, activation, tl, bias), tail
        )
        (dtail,) = pull(head(dy))
    return (
        dx, dw.astype(w.dtype), dtail,
        None if bias is None else dbias.astype(bias.dtype),
    )


_short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)


def causal_short_conv_pallas(
    x: Array, w: Array, activation: bool = True, tail: Optional[Array] = None,
    bias: Optional[Array] = None, *, interpret: bool = False,
) -> Array:
    """``ops/gated_delta.py::causal_short_conv`` as the kernels above, for
    an input that ``supports`` takes; differentiable in x, w, tail, bias."""
    if not supports(x, w):
        raise ValueError(f"short_conv kernels do not take x {x.shape}, w {w.shape}")
    return _short_conv(x, w, tail, bias, activation, interpret)


__all__ = ["HALO", "causal_short_conv_pallas", "supports", "time_tile"]
