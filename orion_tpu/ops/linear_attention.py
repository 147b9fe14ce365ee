"""Causal linear attention — pure-XLA implementations of all three forms.

The reference exposes a CUDA kernel ``causal_dot_product`` computing

    out[t] = sum_{s <= t} (q_t . k_s) v_s

plus a chunked "kv-cumsum" recurrence and an O(1)-state recurrent decode
step (BASELINE.json north_star; the reference checkout was never mounted —
SURVEY.md §0). This module provides the same three mathematically equivalent
forms as pure-XLA JAX:

1. ``causal_dot_product_eager``   — materializes the T×T matrix. O(T^2)
   memory; the CPU-parity reference implementation ("CPU eager ref" config).
2. ``causal_dot_product_chunked`` — chunked recurrence: intra-chunk term via
   masked C×C matmuls (MXU), inter-chunk term via a carried state
   S = cumsum(k ⊗ v). O(T·C) memory, O(T·C·D) time. This is the training
   form; the Pallas kernel in ``ops/pallas/causal_dot.py`` is its
   hand-scheduled twin.
3. ``recurrent_step``             — single-token update S += k⊗v, z += k,
   used by the constant-memory decode path.

Conventions: q, k are post-feature-map ("phi space") with shape
[..., T, Dk]; v is [..., T, Dv]. All accumulation is fp32 regardless of
input dtype; outputs match the input dtype.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

_DEFAULT_EPS = 1e-6


def _f32(*xs):
    return tuple(x.astype(jnp.float32) for x in xs)


# ---------------------------------------------------------------------------
# 1. Eager (quadratic) reference form
# ---------------------------------------------------------------------------


def causal_dot_product_eager(q: Array, k: Array, v: Array) -> Array:
    """out[t] = sum_{s<=t} (q_t . k_s) v_s, materializing the T×T scores.

    The parity reference for every other path. fp32 throughout.
    """
    qf, kf, vf = _f32(q, k, v)
    scores = jnp.einsum("...td,...sd->...ts", qf, kf)
    t = q.shape[-2]
    mask = jnp.tril(jnp.ones((t, t), dtype=jnp.float32))
    out = jnp.einsum("...ts,...sd->...td", scores * mask, vf)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# 2. Chunked (kv-cumsum) training form
# ---------------------------------------------------------------------------


def _pad_chunks(x: Array, chunk: int) -> Tuple[Array, int]:
    t = x.shape[-2]
    rem = (-t) % chunk
    if rem:
        pad = [(0, 0)] * (x.ndim - 2) + [(0, rem), (0, 0)]
        x = jnp.pad(x, pad)
    return x, t


@partial(jax.jit, static_argnames=("chunk", "return_state", "return_zcum"))
def causal_dot_product_chunked(
    q: Array,
    k: Array,
    v: Array,
    chunk: int = 128,
    return_state: bool = False,
    initial_state: Optional[Array] = None,
    initial_z: Optional[Array] = None,
    return_zcum: bool = False,
):
    """Chunked causal dot product via lax.scan over sequence chunks.

    Per chunk c (size C): with carried state S = sum_{s < c·C} k_s ⊗ v_s,
        intra = (Q_c K_c^T ⊙ M) V_c      (M = causal mask, s <= t)
        inter = Q_c S
        S    += K_c^T V_c
    Both terms are dense matmuls that tile onto the MXU; the scan carries
    only the [Dk, Dv] state. Equivalent to the eager form exactly (fp32).

    If ``return_state``, also returns the final state S (for prefill →
    recurrent decode handoff). ``initial_state`` seeds S (default zeros).

    ``return_zcum`` additionally threads the key normalizer z = Σ k_s
    through the SAME scan carry and emits its per-position prefix rows:
    returns ``(out, zcum, s_final, z_final)`` (``initial_z`` seeds z).
    The point is ASSOCIATIVITY, not speed: a global ``jnp.cumsum`` lowers
    to a parallel-prefix tree whose grouping depends on the total length,
    so a prompt prefilled in pieces (serving's chunked prefill,
    generate.prefill_extend_carry) could never reproduce the monolithic
    normalizer bitwise. Per-chunk ``z + cumsum(k_chunk)`` with z carried
    by the scan is a strict left fold over chunk totals — any split of
    the sequence at chunk boundaries replays the identical op sequence,
    which is what makes piecewise prefill == monolithic prefill an
    identity instead of an allclose. The default path (no zcum) is left
    byte-identical to keep the training program unchanged.
    """
    orig_dtype = q.dtype
    qf, kf, vf = _f32(q, k, v)
    qf, t = _pad_chunks(qf, chunk)
    kf, _ = _pad_chunks(kf, chunk)
    vf, _ = _pad_chunks(vf, chunk)

    batch_shape = qf.shape[:-2]
    n = qf.shape[-2] // chunk
    dk, dv = qf.shape[-1], vf.shape[-1]

    # [..., n, C, d] -> [n, ..., C, d] so scan's leading axis is chunks.
    def to_chunks(x, d):
        x = x.reshape(*batch_shape, n, chunk, d)
        return jnp.moveaxis(x, -3, 0)

    qc, kc, vc = to_chunks(qf, dk), to_chunks(kf, dk), to_chunks(vf, dv)

    mask = jnp.tril(jnp.ones((chunk, chunk), dtype=jnp.float32))
    if initial_state is None:
        from orion_tpu.ops.pallas.causal_dot import vma_zeros_state

        s0 = vma_zeros_state(kf, vf)
    else:
        s0 = initial_state.astype(jnp.float32)

    if return_zcum:
        z0 = (
            jnp.zeros_like(kf[..., 0, :])
            if initial_z is None
            else initial_z.astype(jnp.float32)
        )

        def body_z(carry, qkv):
            s, z = carry
            qi, ki, vi = qkv
            scores = jnp.einsum("...td,...sd->...ts", qi, ki) * mask
            intra = jnp.einsum("...ts,...sd->...td", scores, vi)
            inter = jnp.einsum("...td,...de->...te", qi, s)
            s_new = s + jnp.einsum("...td,...te->...de", ki, vi)
            zc = z[..., None, :] + jnp.cumsum(ki, axis=-2)
            return (s_new, zc[..., -1, :]), (intra + inter, zc)

        (s_final, z_final), (out, zcum) = jax.lax.scan(
            body_z, (s0, z0), (qc, kc, vc)
        )
        out = jnp.moveaxis(out, 0, -3).reshape(*batch_shape, n * chunk, dv)
        zcum = jnp.moveaxis(zcum, 0, -3).reshape(*batch_shape, n * chunk, dk)
        return (
            out[..., :t, :].astype(orig_dtype),
            zcum[..., :t, :],
            s_final,
            z_final,
        )

    def body(s, qkv):
        qi, ki, vi = qkv
        scores = jnp.einsum("...td,...sd->...ts", qi, ki) * mask
        intra = jnp.einsum("...ts,...sd->...td", scores, vi)
        inter = jnp.einsum("...td,...de->...te", qi, s)
        s_new = s + jnp.einsum("...td,...te->...de", ki, vi)
        return s_new, intra + inter

    s_final, out = jax.lax.scan(body, s0, (qc, kc, vc))
    out = jnp.moveaxis(out, 0, -3).reshape(*batch_shape, n * chunk, dv)
    out = out[..., :t, :].astype(orig_dtype)
    if return_state:
        return out, s_final  # state stays fp32 for the decode handoff
    return out


def kv_state(
    k: Array,
    v: Array,
    initial_state: Optional[Tuple[Array, Array]] = None,
) -> Tuple[Array, Array]:
    """Final kv-cumsum state (S = sum_s k_s ⊗ v_s, z = sum_s k_s).

    The "kv-cumsum" reduction the reference ships as a CUDA kernel; on TPU
    these are two einsum reductions XLA fuses. Used to initialize the
    recurrent decode state from a processed prompt.
    """
    kf, vf = _f32(k, v)
    s = jnp.einsum("...td,...te->...de", kf, vf)
    z = jnp.sum(kf, axis=-2)
    if initial_state is not None:
        s0, z0 = initial_state
        s = s + s0.astype(jnp.float32)
        z = z + z0.astype(jnp.float32)
    return s, z  # fp32, matching the decode-state convention


# ---------------------------------------------------------------------------
# 3. Recurrent (O(1)-state) decode form
# ---------------------------------------------------------------------------


def recurrent_step(
    q: Array,
    k: Array,
    v: Array,
    state: Tuple[Array, Array],
    eps: float = _DEFAULT_EPS,
) -> Tuple[Array, Tuple[Array, Array]]:
    """One decode step: S += k ⊗ v, z += k, out = (q·S) / (q·z + eps).

    q, k: [..., Dk]; v: [..., Dv]; state = (S [..., Dk, Dv], z [..., Dk]).
    State is carried in fp32. The normalized output equals row t of
    ``linear_attention`` run over the full prefix — the decisive invariant
    tested in tests/test_linear_attention.py.
    """
    s, z = state
    qf, kf, vf = _f32(q, k, v)
    sf, zf = s.astype(jnp.float32), z.astype(jnp.float32)
    sf = sf + kf[..., :, None] * vf[..., None, :]
    zf = zf + kf
    num = jnp.einsum("...d,...de->...e", qf, sf)
    den = jnp.einsum("...d,...d->...", qf, zf)[..., None] + eps
    out = (num / den).astype(q.dtype)
    return out, (sf, zf)


def init_recurrent_state(batch_shape, dk: int, dv: int) -> Tuple[Array, Array]:
    """Zero decode state (S, z) in fp32."""
    return (
        jnp.zeros((*batch_shape, dk, dv), dtype=jnp.float32),
        jnp.zeros((*batch_shape, dk), dtype=jnp.float32),
    )


# ---------------------------------------------------------------------------
# Normalized linear attention (what models call)
# ---------------------------------------------------------------------------


def linear_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    backend: str = "auto",
    chunk: Optional[int] = None,
    eps: float = _DEFAULT_EPS,
    initial_state: Optional[Tuple[Array, Array]] = None,
    return_state: bool = False,
):
    """Normalized causal linear attention over feature-mapped q, k.

    out[t] = (q_t · S_t) / (q_t · z_t + eps),  S_t = Σ_{s<=t} k_s⊗v_s,
    z_t = Σ_{s<=t} k_s. On the Pallas backend the whole op — numerator,
    normalizer, and both carried states — is one fused kernel pass
    (``linear_attention_pallas_fused``). On XLA, the numerator goes through
    ``causal_dot_product`` and the normalizer is a cumulative sum.
    ``chunk=None`` picks the backend's tuned default (dispatch.resolve_chunk).
    """
    from orion_tpu.ops.dispatch import (  # cycle-free
        causal_dot_product,
        resolve,
        resolve_chunk,
    )

    b = resolve(backend)
    chunk = resolve_chunk(chunk, q.shape[-2], b)
    if b in ("pallas", "pallas_interpret"):
        from orion_tpu.ops.pallas.causal_dot import linear_attention_pallas_fused

        return linear_attention_pallas_fused(
            q, k, v, chunk=chunk, eps=eps, initial_state=initial_state,
            return_state=return_state, interpret=(b == "pallas_interpret"),
        )

    s0 = z0 = None
    if initial_state is not None:
        s0, z0 = initial_state

    if return_state and b == "xla":
        # state-handoff path (prefill / chunked-prefill pieces): numerator
        # AND normalizer ride the same chunk-granular scan, so splitting
        # the sequence at chunk boundaries and threading (S, z) replays the
        # identical op sequence — piecewise prefill is bitwise-equal to
        # monolithic by construction (causal_dot_product_chunked docstring).
        # Training forward (return_state=False) keeps the original program.
        num, zcum, s_final, z_final = causal_dot_product_chunked(
            q, k, v, chunk=chunk, initial_state=s0, initial_z=z0,
            return_zcum=True,
        )
        den = jnp.einsum("...td,...td->...t", q.astype(jnp.float32), zcum)
        out = (num.astype(jnp.float32) / (den[..., None] + eps)).astype(
            q.dtype
        )
        return out, (s_final.astype(jnp.float32), z_final)

    if return_state:
        num, s_final = causal_dot_product(
            q, k, v, backend=backend, chunk=chunk, return_state=True,
            initial_state=s0,
        )
    else:
        num = causal_dot_product(
            q, k, v, backend=backend, chunk=chunk, initial_state=s0
        )
        s_final = None

    kf = k.astype(jnp.float32)
    zcum = jnp.cumsum(kf, axis=-2)
    if z0 is not None:
        zcum = zcum + z0.astype(jnp.float32)[..., None, :]
    den = jnp.einsum("...td,...td->...t", q.astype(jnp.float32), zcum)
    out = (num.astype(jnp.float32) / (den[..., None] + eps)).astype(q.dtype)

    if return_state:
        z_final = zcum[..., -1, :]
        return out, (s_final.astype(jnp.float32), z_final)
    return out


def linear_attention_noncausal(
    q: Array,
    k: Array,
    v: Array,
    *,
    eps: float = _DEFAULT_EPS,
    mask: Optional[Array] = None,
) -> Array:
    """Bidirectional (non-causal) linear attention, for encoder/LRA models.

    out = phi(Q) (phi(K)^T V) / (phi(Q) · Σ_s phi(k_s)). With an optional
    boolean padding mask [..., T] applied to keys. O(T·D^2): the whole point
    of linear attention on LRA-length sequences.
    """
    qf, kf, vf = _f32(q, k, v)
    if mask is not None:
        m = mask.astype(jnp.float32)[..., None]
        kf = kf * m
        vf = vf * m
    kv = jnp.einsum("...td,...te->...de", kf, vf)
    z = jnp.sum(kf, axis=-2)
    num = jnp.einsum("...td,...de->...te", qf, kv)
    den = jnp.einsum("...td,...d->...t", qf, z)[..., None] + eps
    return (num / den).astype(q.dtype)


# ---------------------------------------------------------------------------
# 4. Decayed linear attention: a per-head scalar decay, no normaliser
# ---------------------------------------------------------------------------
# S_t = lam_h S_{t-1} + k_t^T v_t,  out_t = q_t S_t,  lam_h = exp(-slope_h).
# Every power of lam is built as exp(-slope * n) with n >= 0, never as a
# ratio of powers (lam^t underflows long before a sequence ends).


def decay_slopes(n_heads: int, exponent: float = 8.0) -> Array:
    """[H] fp32 ``-log lam_h`` of the fixed per-head decays ``lam_h =
    exp(-2^(-exponent h / H))``, h = 1..H: head 1 forgets fastest."""
    h = jnp.arange(1, n_heads + 1, dtype=jnp.float32)
    return jnp.exp2(-exponent * h / n_heads)


def decayed_causal_dot_eager(
    q: Array, k: Array, v: Array, slopes: Array, initial_state=None,
) -> Tuple[Array, Array]:
    """The token recurrence itself, a scan over T: q, k ``[..., H, T, Dk]``,
    v ``[..., H, T, Dv]``, ``slopes`` [H] -> (out, final S fp32)."""
    qf, kf, vf = _f32(q, k, v)
    lam = jnp.exp(-slopes.astype(jnp.float32))[:, None, None]
    s0 = (
        jnp.zeros(qf.shape[:-2] + (qf.shape[-1], vf.shape[-1]), jnp.float32)
        if initial_state is None else initial_state.astype(jnp.float32)
    )

    def body(s, qkv):
        qi, ki, vi = qkv
        s = lam * s + ki[..., :, None] * vi[..., None, :]
        return s, jnp.einsum("...d,...de->...e", qi, s)

    steps = tuple(jnp.moveaxis(x, -2, 0) for x in (qf, kf, vf))
    s, out = jax.lax.scan(body, s0, steps)
    return jnp.moveaxis(out, 0, -2).astype(q.dtype), s


def decay_chunk_terms(slopes: Array, chunk: int, real: Array):
    """What one chunk of the decayed form multiplies by, from ``slopes``
    [H] and the number ``real`` (traced, 0..chunk) of the chunk's rows that
    are not padding: (``within`` [H, C, C], ``exp(-a (i - s))`` for ``s <=
    i`` else 0; ``carried`` [H, C], ``exp(-a (i + 1))``, what the state
    carried in has decayed by at row i; ``into`` [H, C], ``exp(-a (real -
    1 - s))`` for ``s < real`` else 0, a row's weight in the state carried
    out; ``through`` [H], ``exp(-a real)``)."""
    a = slopes.astype(jnp.float32)[:, None]
    i = jnp.arange(chunk, dtype=jnp.float32)
    gap = i[:, None] - i[None, :]
    within = jnp.where(gap >= 0, jnp.exp(-a[..., None] * jnp.maximum(gap, 0)), 0.0)
    carried = jnp.exp(-a * (i + 1))
    left = real.astype(jnp.float32) - 1 - i
    into = jnp.where(left >= 0, jnp.exp(-a * jnp.maximum(left, 0)), 0.0)
    through = jnp.exp(-a[:, 0] * real.astype(jnp.float32))
    return within, carried, into, through


@partial(jax.jit, static_argnames=("chunk",))
def decayed_causal_dot_chunked(
    q: Array, k: Array, v: Array, slopes: Array, chunk: int = 128,
    initial_state: Optional[Array] = None, length: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """Chunked form with a state in and out: per chunk of C rows, ``i``,
    ``s`` counted from its first row,

        out_i = lam^(i+1) q_i S + sum_{s<=i} lam^(i-s) (q_i . k_s) v_s
        S    <- lam^n S + sum_{s<n} lam^(n-1-s) k_s^T v_s

    with ``n`` the chunk's real rows: ``length`` (traced scalar; default T)
    is how many of the T rows are real, the rest right-padding whose
    outputs mean nothing and which leave the state as the last real row
    left it. Returns (out in q's dtype, S after ``length`` rows, fp32)."""
    orig_dtype = q.dtype
    qf, kf, vf = _f32(q, k, v)
    qf, t = _pad_chunks(qf, chunk)
    kf, _ = _pad_chunks(kf, chunk)
    vf, _ = _pad_chunks(vf, chunk)
    batch_shape = qf.shape[:-2]
    n = qf.shape[-2] // chunk
    dk, dv = qf.shape[-1], vf.shape[-1]
    length = jnp.asarray(t if length is None else length, jnp.int32)

    def to_chunks(x, d):
        return jnp.moveaxis(x.reshape(*batch_shape, n, chunk, d), -3, 0)

    s0 = (
        jnp.zeros(batch_shape + (dk, dv), jnp.float32)
        if initial_state is None else initial_state.astype(jnp.float32)
    )

    def body(s, xs):
        qi, ki, vi, c = xs
        real = jnp.clip(length - c * chunk, 0, chunk)
        within, carried, into, through = decay_chunk_terms(slopes, chunk, real)
        scores = jnp.einsum("...td,...sd->...ts", qi, ki) * within
        intra = jnp.einsum("...ts,...sd->...td", scores, vi)
        inter = jnp.einsum("...td,...de->...te", qi * carried[..., None], s)
        s = through[:, None, None] * s + jnp.einsum(
            "...td,...te->...de", ki * into[..., None], vi
        )
        return s, intra + inter

    s, out = jax.lax.scan(
        body, s0,
        (to_chunks(qf, dk), to_chunks(kf, dk), to_chunks(vf, dv), jnp.arange(n)),
    )
    out = jnp.moveaxis(out, 0, -3).reshape(*batch_shape, n * chunk, dv)
    return out[..., :t, :].astype(orig_dtype), s


def decayed_recurrent_step(
    q: Array, k: Array, v: Array, s: Array, slopes: Array
) -> Tuple[Array, Array]:
    """One decode step: ``S <- lam S + k (x) v; out = q . S`` for q, k
    ``[..., H, Dk]``, v ``[..., H, Dv]``, S ``[..., H, Dk, Dv]`` fp32."""
    qf, kf, vf = _f32(q, k, v)
    lam = jnp.exp(-slopes.astype(jnp.float32))[:, None, None]
    sf = lam * s.astype(jnp.float32) + kf[..., :, None] * vf[..., None, :]
    return jnp.einsum("...d,...de->...e", qf, sf).astype(q.dtype), sf


__all__ = [
    "decay_slopes",
    "decayed_causal_dot_eager",
    "decayed_causal_dot_chunked",
    "decayed_recurrent_step",
    "causal_dot_product_eager",
    "causal_dot_product_chunked",
    "kv_state",
    "recurrent_step",
    "init_recurrent_state",
    "linear_attention",
    "linear_attention_noncausal",
]
