"""Gated delta rule — the linear-attention layer whose state update is not a
cumulative sum, in two mathematically equal pure-XLA forms.

Per head, with a ``[Dk, Dv]`` state ``S`` (``S_0 = 0``), a log-decay
``g_t <= 0`` and a write strength ``beta_t`` in (0, 1):

    S   <- exp(g_t) * S
    u_t  = beta_t * (v_t - S^T k_t)        # what the state gets wrong at k_t
    S   <- S + k_t u_t^T
    o_t  = S^T q_t

1. ``gated_delta_recurrent`` — that recurrence, token by token (a
   ``lax.scan``), fp32 throughout. The form every other path must equal.
2. ``gated_delta_chunked`` — the training form. Inside a chunk of ``C``
   tokens the ``u_t`` solve a unit lower-triangular system
   ``(I + A) U = beta * (V - decay * K S_in)`` with
   ``A_ij = beta_i (k_i . k_j) exp(G_i - G_j)`` for ``i > j`` (``G`` the
   in-chunk cumulative log-decay): the WY form. ``(I + A)^-1`` is built
   for every chunk at once from ``C x C`` matmuls (``_unit_lower_inverse``:
   two short exact Neumann products, of the 16 x 16 diagonal blocks and of
   what couples them); between chunks a ``lax.scan`` carries ``S`` in
   fp32. Every decay enters as ``exp`` of a non-positive difference, so a
   strongly negative ``g`` underflows to 0 and never overflows.
   Differentiable by autodiff; the scan keeps one ``S`` per chunk.

Which form runs where (``ops/dispatch.py::gated_delta_rule``): backend
``eager`` is form 1; ``xla`` (the CPU; on the chip, head widths off a
multiple of 128) is form 2 through ``gated_delta_by_rows``; ``pallas`` and
``pallas_interpret`` run form 2's equations as Mosaic kernels, forward and
backward (``ops/pallas/gated_delta.py``), which keep a chunk's ``A``, ``T``
and ``S`` in VMEM and take the whole batch at once. This file is their
specification and the parity form of their tests.

3. ``gated_delta_step`` — one token of the recurrence on a decode state
   ``S [B, H, Dk, Dv]`` (serving's decode step): the XLA form, every row;
   under a Pallas backend ``ops/dispatch.py::gated_delta_step`` runs the
   row-sparse in-place kernel (``ops/pallas/decode_state.py``) instead.

The chunked forms take an ``initial_state`` and return the final one
(``return_state``): a prompt consumed in pieces carries ``S`` from piece to
piece, and a piece boundary on a multiple of the chunk replays the
monolithic pass's op sequence.

``causal_short_conv`` is the depthwise causal convolution (+ SiLU) that
feeds the layer's q, k and v; with ``tail`` it continues a sequence whose
last ``W - 1`` inputs the caller kept; ``gated_rms_norm`` is the gate its
output passes before ``wo``: an RMS norm per head times ``silu(z)``.

Conventions: q, k ``[..., T, Dk]``; v ``[..., T, Dv]``; beta, g
``[..., T]``. Matmul operands stay in the input dtype with fp32
accumulation; decays, the triangular inverse and ``S`` are fp32. Outputs
take v's dtype.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array

DEFAULT_CHUNK = 64
_HI = jax.lax.Precision.HIGHEST
# the triangular inverse: three bf16 passes (~2^-17 relative) at half the
# cost of HIGHEST; its result is rounded to the compute dtype right after
_INV_PRECISION = jax.lax.Precision.HIGH
_INV_NAME = "gated_delta_tinv"  # what gated_delta_by_rows keeps per row


def gated_delta_recurrent(
    q: Array, k: Array, v: Array, beta: Array, g: Array,
    initial_state: Optional[Array] = None, return_state: bool = False,
):
    """The token-by-token recurrence in fp32 (the parity form)."""
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    bf, gf = beta.astype(jnp.float32), g.astype(jnp.float32)
    lead = v.shape[:-2]
    s0 = (
        jnp.zeros(lead + (q.shape[-1], v.shape[-1]), jnp.float32)
        if initial_state is None else initial_state.astype(jnp.float32)
    )

    def step(s, xs):
        qt, kt, vt, bt, gt = xs
        s = s * jnp.exp(gt)[..., None, None]
        u = bt[..., None] * (vt - jnp.einsum("...kv,...k->...v", s, kt, precision=_HI))
        s = s + kt[..., :, None] * u[..., None, :]
        return s, jnp.einsum("...kv,...k->...v", s, qt, precision=_HI)

    time_first = lambda x, n: jnp.moveaxis(x, -n, 0)  # noqa: E731
    xs = (time_first(qf, 2), time_first(kf, 2), time_first(vf, 2),
          time_first(bf, 1), time_first(gf, 1))
    s, out = jax.lax.scan(step, s0, xs)
    out = jnp.moveaxis(out, 0, -2).astype(v.dtype)
    return (out, s) if return_state else out


_BASE = 16  # diagonal blocks inverted by a Neumann product (A^16 = 0)


def _unit_lower_inverse_fwd(a: Array) -> Array:
    """``(I + A)^-1`` for strictly lower-triangular ``A [..., C, C]``, as
    full-width ``C x C`` matmuls only (small blocks waste the chip's tiles).

    With ``D`` the 16 x 16 diagonal blocks of ``A`` and ``L = A - D``:
    ``I + A = (I + D)(I + M)``, ``M = (I + D)^-1 L``. ``D^16 = 0``, so
    ``(I + D)^-1 = (I - D)(I + D^2)(I + D^4)(I + D^8)`` exactly; ``M`` is
    strictly block-lower, nilpotent of order ``C / 16``, so ``(I + M)^-1``
    is the same product over its powers. Exact in exact arithmetic; in
    fp32 the terms of the first product grow at most like the binomials
    of 15 (6435, for identical keys at beta 1), of the second like those
    of ``C / 16 - 1``: three-pass matmuls keep that to ~1e-2 of the result's
    own rounding to bf16."""
    c = a.shape[-1]
    assert c % _BASE == 0 and (c // _BASE) & (c // _BASE - 1) == 0, c
    eye = jnp.eye(c, dtype=a.dtype)
    mm = lambda x, y: jnp.matmul(x, y, precision=_INV_PRECISION)  # noqa: E731

    def nilpotent_inverse(n, order):
        """(I + N)^-1 for N^order = 0, order a power of two."""
        inv, power, reach = eye - n, n, 2
        while reach < order:
            power = mm(power, power)
            inv = mm(inv, eye + power)
            reach *= 2
        return inv

    idx = jnp.arange(c) // _BASE
    d = jnp.where(idx[:, None] == idx[None, :], a, 0.0)
    dinv = nilpotent_inverse(d, _BASE)
    if c == _BASE:
        return dinv
    m = mm(dinv, a - d)
    return mm(nilpotent_inverse(m, c // _BASE), dinv)


@jax.custom_vjp
def _unit_lower_inverse(a: Array) -> Array:
    """``T = (I + A)^-1`` with the backward pass of an inverse, ``dA = -T^T
    dT T^T``: two matmuls and no residual but ``T`` itself, where autodiff
    of the products above would keep and revisit every power."""
    return _unit_lower_inverse_fwd(a)


def _inverse_vjp_fwd(a):
    from jax.ad_checkpoint import checkpoint_name

    t = checkpoint_name(_unit_lower_inverse_fwd(a), _INV_NAME)
    return t, t


def _inverse_vjp_bwd(t, g):
    tt = jnp.swapaxes(t, -1, -2)
    mm = lambda x, y: jnp.matmul(x, y, precision=_INV_PRECISION)  # noqa: E731
    return (-mm(mm(tt, g), tt),)


_unit_lower_inverse.defvjp(_inverse_vjp_fwd, _inverse_vjp_bwd)


def gated_delta_chunked(
    q: Array, k: Array, v: Array, beta: Array, g: Array, *,
    chunk: int = DEFAULT_CHUNK, initial_state: Optional[Array] = None,
    return_state: bool = False,
):
    """The chunked (WY) form; equals ``gated_delta_recurrent``."""
    t, dk, dv = q.shape[-2], q.shape[-1], v.shape[-1]
    lead = v.shape[:-2]
    c = chunk
    pad = (-t) % c
    if pad:
        # k = v = beta = g = 0 on the tail: the state passes through
        widths = [(0, 0)] * len(lead)
        q, k, v = (jnp.pad(x, widths + [(0, pad), (0, 0)]) for x in (q, k, v))
        beta, g = (jnp.pad(x, widths + [(0, pad)]) for x in (beta, g))
    n = (t + pad) // c
    cdt = v.dtype
    f32 = jnp.float32

    def chunks(x, *tail):
        return x.reshape(lead + (n, c) + tail)

    qc, kc, vc = chunks(q, dk), chunks(k, dk), chunks(v, dv)
    bc = chunks(beta.astype(f32))
    gc = jnp.cumsum(chunks(g.astype(f32)), axis=-1)  # G_i, in-chunk
    diff = gc[..., :, None] - gc[..., None, :]  # G_i - G_j
    row, col = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.where(row >= col, diff, -jnp.inf))  # 0 above the diagonal
    kk = jnp.einsum("...id,...jd->...ij", kc, kc, preferred_element_type=f32)
    a = jnp.where(row > col, kk * decay * bc[..., :, None], 0.0)
    tinv = _unit_lower_inverse(a)  # [..., n, C, C] fp32
    tb = (tinv * bc[..., None, :]).astype(cdt)  # (I + A)^-1 diag(beta)
    # u_t = U0 - W S_in: both solved once for every chunk, outside the scan
    u0 = jnp.einsum("...ij,...jd->...id", tb, vc, preferred_element_type=f32)
    kdec = (kc.astype(f32) * jnp.exp(gc)[..., None]).astype(cdt)
    w = jnp.einsum("...ij,...jd->...id", tb, kdec, preferred_element_type=f32)
    qk = jnp.einsum("...id,...jd->...ij", qc, kc, preferred_element_type=f32)
    qk = (qk * decay).astype(cdt)  # causal, decayed in-chunk scores
    qdec = (qc.astype(f32) * jnp.exp(gc)[..., None]).astype(cdt)
    g_last = gc[..., -1]
    kend = (kc.astype(f32) * jnp.exp(g_last[..., None] - gc)[..., None]).astype(cdt)
    w = w.astype(cdt)

    s0 = (
        jnp.zeros(lead + (dk, dv), f32)
        if initial_state is None else initial_state.astype(f32)
    )
    ax = len(lead)  # the chunk axis

    def step(s, xs):
        u0_i, w_i, qk_i, qdec_i, kend_i, gl_i = xs
        sc = s.astype(cdt)  # the state as a matmul operand; it accumulates in fp32
        u = u0_i - jnp.einsum("...ik,...kv->...iv", w_i, sc, preferred_element_type=f32)
        uc = u.astype(cdt)
        o = jnp.einsum(
            "...ik,...kv->...iv", qdec_i, sc, preferred_element_type=f32
        ) + jnp.einsum("...ij,...jv->...iv", qk_i, uc, preferred_element_type=f32)
        s = s * jnp.exp(gl_i)[..., None, None] + jnp.einsum(
            "...ik,...iv->...kv", kend_i, uc, preferred_element_type=f32
        )
        return s, o.astype(cdt)

    xs = tuple(jnp.moveaxis(x, ax, 0) for x in (u0, w, qk, qdec, kend, g_last))
    s, out = jax.lax.scan(step, s0, xs)
    out = jnp.moveaxis(out, 0, ax).reshape(lead + (n * c, dv))[..., :t, :]
    return (out, s) if return_state else out


# heads x tokens of one block of ``gated_delta_by_rows``: one row of 32
# heads at T 8192. Autodiff of the chunked form keeps some twenty [heads,
# T, 64 or 128] fp32 arrays and one fp32 state per chunk; at 8 x 32 x 8192
# that is over 15 GB, at this bound about 2 GB.
_ROWS_HEADS_X_TOKENS = 1 << 18


def gated_delta_by_rows(q, k, v, beta, g, *, chunk: int = DEFAULT_CHUNK):
    """``gated_delta_chunked`` over the leading (batch) axis a block of rows
    at a time, each block under ``jax.checkpoint``: the backward pass
    recomputes one block's chunk-local arrays while it needs them instead of
    holding every row's at once. Same values; one more forward of the op
    in the backward, but for its triangular inverses, which are kept. Inputs ``[B, H, T, D]``; a batch that fits one block
    is the plain call."""
    if q.ndim != 4:
        return gated_delta_chunked(q, k, v, beta, g, chunk=chunk)
    b, h, t = q.shape[:3]
    rows = max(1, _ROWS_HEADS_X_TOKENS // (h * t))
    while b % rows:
        rows -= 1
    if rows >= b:
        return gated_delta_chunked(q, k, v, beta, g, chunk=chunk)
    # each row keeps its triangular inverses (the costly part of a forward;
    # 67 MB of fp32 a row at 32 heads x T 8192) and recomputes the rest
    block = jax.checkpoint(
        lambda xs: gated_delta_chunked(*xs, chunk=chunk),
        policy=jax.checkpoint_policies.save_only_these_names(_INV_NAME),
    )
    split = lambda x: x.reshape((b // rows, rows) + x.shape[1:])  # noqa: E731
    out = jax.lax.map(block, tuple(split(x) for x in (q, k, v, beta, g)))
    return out.reshape((b,) + out.shape[2:])


def l2norm(x: Array, eps: float) -> Array:
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, in fp32."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(jnp.square(xf), -1, keepdims=True) + eps)


def qkv_operands(qkv: Array, key_heads: int, key_dim: int, value_dim: int, eps: float):
    """The rule's operands from the short conv's output ``qkv [..., T, C]``,
    columns ``[q | k | v]`` (``key_heads`` x ``key_dim`` twice, then the
    value heads x ``value_dim``): q l2-normalised and scaled by ``key_dim **
    -0.5``, k l2-normalised, both rounded to qkv's dtype; heads first. ->
    q, k ``[..., Hk, T, Dk]``, v ``[..., Hv, T, Dv]``."""
    kd, lead = key_heads * key_dim, qkv.shape[:-1]
    heads_first = lambda x, d: jnp.swapaxes(x.reshape(lead + (-1, d)), -3, -2)  # noqa: E731
    q = l2norm(heads_first(qkv[..., :kd], key_dim), eps) * key_dim ** -0.5
    k = l2norm(heads_first(qkv[..., kd: 2 * kd], key_dim), eps)
    return q.astype(qkv.dtype), k.astype(qkv.dtype), heads_first(qkv[..., 2 * kd:], value_dim)


def gated_delta_qkv(
    qkv: Array, beta: Array, g: Array, *, key_heads: int, key_dim: int, value_dim: int,
    eps: float, rule=gated_delta_recurrent,
):
    """The delta-rule layer from its short conv's output to the rule's:
    ``qkv_operands``, each key head repeated to the value heads it serves,
    then ``rule`` (one of this file's forms). qkv ``[..., T, C]``, beta, g
    ``[..., Hv, T]`` -> ``o [..., Hv, T, Dv]``. The specification of the
    kernels that read ``qkv`` as it lies and write its cotangent in the same
    layout (``ops/pallas/gated_delta.py::gated_delta_qkv_pallas``)."""
    q, k, v = qkv_operands(qkv, key_heads, key_dim, value_dim, eps)
    group = v.shape[-3] // key_heads
    if group > 1:
        q, k = (jnp.repeat(x, group, axis=-3) for x in (q, k))
    return rule(q, k, v, beta, g)


def gated_delta_step(q: Array, k: Array, v: Array, beta: Array, g: Array, s: Array):
    """One token of the recurrence for every row, fp32: q, k ``[B, H, Dk]``,
    v ``[B, H, Dv]``, beta, g ``[B, H]``, ``s [B, H, Dk, Dv]`` fp32 ->
    ``(o [B, H, Dv] in v's dtype, s)``."""
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    s = s * jnp.exp(g.astype(jnp.float32))[..., None, None]
    u = beta.astype(jnp.float32)[..., None] * (vf - jnp.sum(s * kf[..., None], axis=-2))
    s = s + kf[..., :, None] * u[..., None, :]
    return jnp.sum(s * qf[..., None], axis=-2).astype(v.dtype), s


def causal_short_conv(
    x: Array, w: Array, activation: bool = True, tail: Optional[Array] = None,
    bias: Optional[Array] = None,
) -> Array:
    """Depthwise causal convolution over time: ``y_t = sum_j w[j] *
    x_{t - (W - 1) + j}`` (``w[W - 1]`` weighs the current token), plus
    ``bias`` [C] where given, then SiLU. x ``[..., T, C]``, w ``[W, C]``.
    What precedes ``x`` is zeros, or ``tail [..., W - 1, C]``: the inputs
    just before it."""
    width, t = w.shape[0], x.shape[-2]
    if tail is None:
        xp = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(width - 1, 0), (0, 0)])
    else:
        xp = jnp.concatenate([tail.astype(x.dtype), x], axis=-2)
    wf = w.astype(jnp.float32)
    y = sum(
        jax.lax.slice_in_dim(xp, j, j + t, axis=-2).astype(jnp.float32) * wf[j]
        for j in range(width)
    )
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return (jax.nn.silu(y) if activation else y).astype(x.dtype)


def gated_rms_norm(o: Array, z: Array, w: Array, eps: float) -> Array:
    """The layer's output gate: ``rms(o) * w * silu(z)`` per head, fp32
    inside, rounded once to ``z``'s dtype. o ``[..., Hv, T, Dv]`` head-major,
    as the rule leaves it; z ``[..., T, Hv Dv]``; w ``[Dv]`` -> ``[..., T, Hv
    Dv]``."""
    of = jnp.swapaxes(o, -3, -2).astype(jnp.float32)
    n = of * jax.lax.rsqrt(jnp.mean(jnp.square(of), -1, keepdims=True) + eps)
    y = n * w.astype(jnp.float32) * jax.nn.silu(z.reshape(of.shape).astype(jnp.float32))
    return y.reshape(z.shape).astype(z.dtype)


__all__ = [
    "DEFAULT_CHUNK",
    "causal_short_conv",
    "gated_delta_by_rows",
    "gated_delta_chunked",
    "gated_delta_qkv",
    "gated_delta_recurrent",
    "gated_delta_step",
    "gated_rms_norm",
    "l2norm",
    "qkv_operands",
]
