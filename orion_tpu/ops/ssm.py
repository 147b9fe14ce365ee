"""State-space mixing with a scalar decay per head that depends on the
token (the Mamba-2 family's recurrence), in three pure-XLA forms.

Per head ``h`` with a ``[P, N]`` state ``S`` (``S_0`` given or 0), a step
``dt_t > 0``, a rate ``A_h < 0``, an input ``x_t`` [P] and the group's
``B_t``, ``C_t`` [N] (one pair for the ``H / G`` heads of a group):

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T,    y_t = S_t C_t

1. ``ssm_recurrent``: that recurrence, token by token (a ``lax.scan``),
   fp32 throughout. The form every other path must equal.
2. ``ssm_chunked``: the serving form of a prompt and its pieces, a state
   in and out. Inside a chunk of ``C`` rows, ``c_i = sum_{r<=i} dt_r A``
   counted from the chunk's first row,

       y_i   = exp(c_i) S_in C_i + sum_{s<=i} exp(c_i - c_s) (C_i . B_s) dt_s x_s
       S_out = exp(c_last) S_in + sum_s exp(c_last - c_s) dt_s x_s B_s^T

   Every decay is ``exp`` of a non-positive DIFFERENCE of the cumulative
   sum, never a ratio of two exponentials; ``C_i . B_s`` is one ``[C, C]``
   product a chunk and group, for all of its heads. A row at or past
   ``length`` has ``dt = 0`` and ``x = 0``: it passes the state through.
   Between chunks a ``lax.scan`` carries ``S`` in fp32. Differentiable by
   autodiff (no cell trains it).
3. ``ssm_step_packed``: one token on a decode state, every row; under a
   Pallas backend ``ops/dispatch.py::ssm_state_step`` runs the row-sparse
   in-place kernel (``ops/pallas/ssm.py``) instead. Both read and write
   the state as it is HELD between steps, ``[B, H / k, N, k P]``
   (:func:`pack_state`): the state width on sublanes and ``k`` heads of one
   group side by side on lanes, ``k P`` a whole lane tile where ``P`` is
   half of one. A ``[.., P, N]`` leaf would need its output reduced across
   lanes and its decay moved from lanes to sublanes at every step; a
   ``[.., N, P]`` leaf of 64 lanes pads to 128 on the chip, twice its size.

The skip ``D_h x_t``, the conv, the gate and the norm are the mixer's
(``models/mixers/ssm.py``).

Conventions: x ``[B, T, H, P]``; dt ``[B, T, H]`` fp32, after its softplus;
``a`` [H] fp32, negative; bm, cm ``[B, T, G, N]``; S ``[B, H, P, N]`` fp32.
Outputs take x's dtype.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

DEFAULT_CHUNK = 256


def _per_head(y: Array, heads: int, axis: int = -2) -> Array:
    """``[..., G, N]`` -> ``[..., H, N]``: each group's row for its heads."""
    return jnp.repeat(y, heads // y.shape[axis], axis=axis)


def ssm_recurrent(
    x: Array, dt: Array, a: Array, bm: Array, cm: Array,
    initial_state: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """The token recurrence in fp32 -> (y [B, T, H, P], final S)."""
    f32 = jnp.float32
    b, t, h, p = x.shape
    n = bm.shape[-1]
    s0 = (
        jnp.zeros((b, h, p, n), f32) if initial_state is None
        else initial_state.astype(f32)
    )

    def body(s, xs):
        y, s = ssm_step(*xs[:2], a, *xs[2:], s)
        return s, y

    steps = tuple(
        jnp.moveaxis(v, 1, 0) for v in (x.astype(f32), dt.astype(f32), bm, cm)
    )
    s, y = jax.lax.scan(body, s0, steps)
    return jnp.moveaxis(y, 0, 1).astype(x.dtype), s


def ssm_step(
    x: Array, dt: Array, a: Array, bm: Array, cm: Array, s: Array
) -> Tuple[Array, Array]:
    """One token: x ``[B, H, P]``, dt ``[B, H]``, bm, cm ``[B, G, N]``, S
    ``[B, H, P, N]`` fp32 -> (y [B, H, P] in x's dtype, S)."""
    f32 = jnp.float32
    h = x.shape[-2]
    dtf = dt.astype(f32)
    decay = jnp.exp(dtf * a.astype(f32))[..., None, None]
    u = dtf[..., None] * x.astype(f32)  # [B, H, P]
    bh, ch = _per_head(bm.astype(f32), h), _per_head(cm.astype(f32), h)
    s = decay * s.astype(f32) + u[..., :, None] * bh[..., None, :]
    return jnp.sum(s * ch[..., None, :], axis=-1).astype(x.dtype), s


def state_pack(heads: int, head_dim: int, groups: int) -> int:
    """How many heads the held state lays side by side on lanes: as many
    as fill a lane tile, all of one group."""
    return math.gcd(heads // groups, max(1, 128 // head_dim))


def pack_state(s: Array, pack: int) -> Array:
    """``[B, H, P, N]`` -> the held layout ``[B, H / pack, N, pack P]``."""
    b, h, p, n = s.shape
    s = s.reshape(b, h // pack, pack, p, n)
    return jnp.transpose(s, (0, 1, 4, 2, 3)).reshape(b, h // pack, n, pack * p)


def unpack_state(s: Array, pack: int) -> Array:
    """The inverse of :func:`pack_state`."""
    b, hg, n, lanes = s.shape
    s = s.reshape(b, hg, n, pack, lanes // pack)
    return jnp.transpose(s, (0, 1, 3, 4, 2)).reshape(b, hg * pack, lanes // pack, n)


def packed_step_operands(x, dt, a, bm, cm, pack: int):
    """One token's operands at the widths the held state multiplies by, all
    fp32: (decay ``[B, H / pack, pack P]``, ``exp(dt A)`` of each lane's
    head; u, ``dt x`` likewise; B and C ``[B, H / pack, N]``, each packed
    row's group's)."""
    f32 = jnp.float32
    b, h, p = x.shape
    dtf = dt.astype(f32)
    lanes = (b, h // pack, pack * p)
    decay = jnp.broadcast_to(jnp.exp(dtf * a.astype(f32))[..., None], x.shape)
    u = dtf[..., None] * x.astype(f32)
    bk, ck = (_per_head(y.astype(f32), h // pack) for y in (bm, cm))
    return decay.reshape(lanes), u.reshape(lanes), bk, ck


def ssm_step_packed(
    x: Array, dt: Array, a: Array, bm: Array, cm: Array, s: Array, pack: int
) -> Tuple[Array, Array]:
    """:func:`ssm_step` on the held layout: S ``[B, H / pack, N, pack P]``
    -> (y [B, H, P] in x's dtype, S), every row."""
    decay, u, bk, ck = packed_step_operands(x, dt, a, bm, cm, pack)
    s = s * decay[:, :, None, :] + bk[..., None] * u[:, :, None, :]
    return jnp.sum(s * ck[..., None], axis=2).reshape(x.shape).astype(x.dtype), s


@partial(jax.jit, static_argnames=("chunk",))
def ssm_chunked(
    x: Array, dt: Array, a: Array, bm: Array, cm: Array,
    chunk: int = DEFAULT_CHUNK, initial_state: Optional[Array] = None,
    length: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """The chunked form -> (y [B, T, H, P] in x's dtype, S after ``length``
    rows, fp32); ``length`` (a traced scalar; default T) is how many of the
    T rows are real, the rest right-padding whose outputs mean nothing."""
    f32 = jnp.float32
    b, t, h, p = x.shape
    g, n = bm.shape[-2:]
    c = min(chunk, -(-t // 8) * 8)
    real = jnp.arange(t) < (t if length is None else length)
    u = jnp.where(real[None, :, None, None], dt.astype(f32)[..., None] * x.astype(f32), 0.0)
    la = jnp.where(real[None, :, None], dt.astype(f32) * a.astype(f32), 0.0)
    pad = -t % c
    nc = (t + pad) // c

    def chunks(v):  # [B, T, ...] -> [nc, B, C, ...]
        v = jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        return jnp.moveaxis(v.reshape((b, nc, c) + v.shape[2:]), 1, 0)

    s0 = (
        jnp.zeros((b, h, p, n), f32) if initial_state is None
        else initial_state.astype(f32)
    )
    lower = jnp.tril(jnp.ones((c, c), bool))

    def body(s, xs):
        ui, lai, bi, ci = xs  # [B, C, H, P], [B, C, H], [B, C, G, N] x 2
        cum = jnp.cumsum(lai, axis=1)  # [B, C, H]
        gap = cum[:, :, None, :] - cum[:, None, :, :]  # c_i - c_s: [B, C, C, H]
        within = jnp.where(lower[None, :, :, None], jnp.exp(jnp.minimum(gap, 0.0)), 0.0)
        cb = jnp.einsum("bign,bsgn->bisg", ci, bi)  # [B, C, C, G]
        w = within * _per_head(cb, h, axis=-1)
        intra = jnp.einsum("bish,bshp->bihp", w, ui)
        ch = _per_head(ci, h)  # [B, C, H, N]
        inter = jnp.exp(cum)[..., None] * jnp.einsum("bihn,bhpn->bihp", ch, s)
        into = jnp.exp(cum[:, -1:, :] - cum)  # [B, C, H]
        s = jnp.exp(cum[:, -1, :])[..., None, None] * s + jnp.einsum(
            "bshp,bshn->bhpn", ui * into[..., None], _per_head(bi, h)
        )
        return s, intra + inter

    s, y = jax.lax.scan(
        body, s0, (chunks(u), chunks(la), chunks(bm.astype(f32)), chunks(cm.astype(f32)))
    )
    y = jnp.moveaxis(y, 0, 1).reshape(b, nc * c, h, p)
    return y[:, :t].astype(x.dtype), s


__all__ = [
    "DEFAULT_CHUNK", "pack_state", "packed_step_operands", "ssm_chunked",
    "ssm_recurrent", "ssm_step", "ssm_step_packed", "state_pack", "unpack_state",
]
