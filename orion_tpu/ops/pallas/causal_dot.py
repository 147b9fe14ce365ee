"""Pallas TPU kernel for the causal dot product (linear attention core).

TPU-native replacement for the reference's CUDA ``causal_dot_product`` /
kv-cumsum kernels (BASELINE.json north_star). Computes, per (batch·head):

    out[t]  = sum_{s<=t} (q_t . k_s) v_s  (+ q_t @ S0 for a carried-in state)
    S_final = S0 + sum_s k_s (x) v_s

Design (chunked kv-cumsum recurrence mapped onto the TPU):
- grid = (B*H, T/C) with the chunk axis innermost: TPU grids execute
  sequentially on a core, so a VMEM scratch accumulator carries the running
  [Dk, Dv] state S across chunk steps — the Pallas analogue of the CUDA
  kernel's shared-memory running state. S resets from S0 at chunk 0 of each
  (batch·head) program.
- per chunk, three MXU matmuls: scores = Q_c K_c^T (masked causally),
  intra = scores @ V_c, inter = Q_c @ S; then S += K_c^T V_c.
- all accumulation in fp32 regardless of input dtype (bf16 inputs hit the
  MXU natively with ``preferred_element_type=float32``).

The backward is two kernel passes (no time-flip copies):
    dq pass — the forward kernel on (g, v, k) with S0^T as carried state:
        dq[t] = sum_{s<=t} (g_t·v_s) k_s + g_t @ S0^T
    reverse pass (_bwd_rev_kernel) — grid walks chunks last->first with one
    carried state R_t = dSf^T + sum_{s>=t} g_s (x) q_s, emitting both
        dk[t] = v_t @ R_t   and   dv[t] = k_t @ R_t^T
    and dS0 = (final R)^T for free.
Wired up via jax.custom_vjp so the op is fully differentiable, including
through the carried state — which is what makes sequence-parallel training
(parallel/sequence.py) differentiable too.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas import kernel_entry

Array = jax.Array


def vma_zeros_state(kf: Array, vf: Array) -> Array:
    """[.., Dk, Dv] zeros *derived from k/v* (0 * k1^T v1) so the result
    inherits their varying-mesh-axes type: a plain jnp.zeros initial state
    trips shard_map(check_vma=True) bodies (carry/input unvarying while the
    data is varying). XLA folds the zero-multiply. One helper so the
    workaround has a single place to die when jnp.zeros grows a vma arg."""
    return 0.0 * jnp.einsum(
        "...td,...te->...de",
        kf[..., :1, :].astype(jnp.float32),
        vf[..., :1, :].astype(jnp.float32),
    )


def _sds(shape, dtype, like: Array):
    """ShapeDtypeStruct for a pallas_call output, inheriting ``like``'s
    varying-mesh-axes type so the kernels compose with
    shard_map(check_vma=True) bodies (sequence/pipeline parallel)."""
    try:
        vma = jax.api_util.shaped_abstractify(like).vma
    except Exception:
        vma = None
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _tri_mask(cdim: int, anti: bool = False):
    """Boolean (C, C) in-chunk time mask: causal ``s <= t`` rows>=cols, or
    anti-causal ``s >= t`` with ``anti=True``. One definition shared by all
    five chunk kernels so the numerator recurrences can't drift apart."""
    row = jax.lax.broadcasted_iota(jnp.int32, (cdim, cdim), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (cdim, cdim), 1)
    return row <= col if anti else row >= col


def _kernel(q_ref, k_ref, v_ref, s0_ref, out_ref, sf_ref, s_scr):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_scr[:] = s0_ref[0].astype(jnp.float32)

    qi = q_ref[0]  # (C, Dk) input dtype
    ki = k_ref[0]
    vi = v_ref[0]

    scores = jax.lax.dot_general(
        qi,
        ki,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (C, C) fp32
    scores = jnp.where(_tri_mask(scores.shape[0]), scores, 0.0)

    intra = jnp.dot(scores, vi.astype(jnp.float32), preferred_element_type=jnp.float32)
    inter = jnp.dot(
        qi.astype(jnp.float32), s_scr[:], preferred_element_type=jnp.float32
    )
    out_ref[0] = (intra + inter).astype(out_ref.dtype)

    s_scr[:] = s_scr[:] + jax.lax.dot_general(
        ki,
        vi,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    sf_ref[0] = s_scr[:]


@kernel_entry("causal_dot_fwd", "chunk", "interpret")
def _cdp_flat(
    q: Array, k: Array, v: Array, s0: Array, chunk: int, interpret: bool
) -> Tuple[Array, Array]:
    """Unnormalized causal dot product on flat [BH, T, D] inputs (T % chunk == 0)."""
    bh, t, dk = q.shape
    dv = v.shape[-1]
    nc = t // chunk

    grid = (bh, nc)
    out, sf = pl.pallas_call(
        _kernel,
        name="causal_dot_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, dk, dv), lambda b, c: (b, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, dk, dv), lambda b, c: (b, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _sds((bh, t, dv), q.dtype, q),
            _sds((bh, dk, dv), jnp.float32, q),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * bh * t * (chunk * dk + chunk * dv + 2 * dk * dv),
            bytes_accessed=q.size * q.dtype.itemsize * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(q, k, v, s0)
    return out, sf


def _bwd_rev_core(
    q_ref, k_ref, v_ref, g_ref, gden_ref, rinit_ref, zr0_ref,
    dk_ref, dv_ref, rfin_ref, zrfin_ref, r_scr, zr_scr,
):
    """Reverse-walking fused backward body: one pass emits dk AND dv.

        dk[t] = v_t @ R_t,   dv[t] = k_t @ R_t^T,
        R_t   = dSf^T + sum_{s>=t} g_s (x) q_s   (Dv, Dk)

    The grid's chunk axis is index-mapped last->first, so the carried VMEM
    state R accumulates "later" chunks without materializing any time-flip
    (the previous formulation spent 3 kernel passes + 6 jnp.flip HBM copies;
    measured 0.64-0.79x vs XLA on-chip — this pass + the dq pass replace it).
    dS0 = (final R)^T falls out for free.

    With the denominator refs non-None (the normalized path), the dk part

        dk_den[t] = gzf + Σ_{s>=t} gden_s q_s

    rides as a second (1, Dk) suffix state over the same walk (zr0 = gzf,
    so the broadcast-to-every-t gzf term comes for free and the final
    state IS dz0 = gzf + Σ_t gden_t q_t). One body serves both kernels so
    the numerator recurrence cannot drift between the normalized and
    unnormalized backwards.
    """
    with_den = gden_ref is not None
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        r_scr[:] = rinit_ref[0].astype(jnp.float32)  # dSf^T
        if with_den:
            zr_scr[:] = zr0_ref[0].astype(jnp.float32)  # gzf (1, Dk)

    qi = q_ref[0]  # (C, Dk)
    ki = k_ref[0]
    vi = v_ref[0]
    gi = g_ref[0]  # (C, Dv)

    # within-chunk "s >= t" (anti-causal) contributions
    svg = jax.lax.dot_general(
        vi, gi, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (C, C): v_t · g_s
    anti = _tri_mask(svg.shape[0], anti=True)  # s >= t
    # jnp.where (not a float-mask multiply): a non-finite masked-out entry
    # must hard-zero, not turn into inf*0 = NaN — same style as _kernel
    svg = jnp.where(anti, svg, 0.0)
    skq = jnp.where(
        anti,
        jax.lax.dot_general(
            ki, qi, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ),
        0.0,
    )  # (C, C): k_t · q_s

    dk = (
        jnp.dot(svg, qi.astype(jnp.float32), preferred_element_type=jnp.float32)
        + jnp.dot(vi.astype(jnp.float32), r_scr[:], preferred_element_type=jnp.float32)
    )
    if with_den:
        gd = gden_ref[0].astype(jnp.float32)  # (C, 1)
        gq = gd * qi.astype(jnp.float32)  # (C, Dk)
        sufx = jnp.dot(
            anti.astype(jnp.float32), gq, preferred_element_type=jnp.float32
        )
        dk = dk + zr_scr[:] + sufx
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = (
        jnp.dot(skq, gi.astype(jnp.float32), preferred_element_type=jnp.float32)
        + jax.lax.dot_general(
            ki.astype(jnp.float32), r_scr[:],
            dimension_numbers=(((1,), (1,)), ((), ())),  # k_t @ R^T
            preferred_element_type=jnp.float32,
        )
    ).astype(dv_ref.dtype)

    r_scr[:] = r_scr[:] + jax.lax.dot_general(
        gi, qi, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # += sum_t g_t (x) q_t
    rfin_ref[0] = r_scr[:]
    if with_den:
        zr_scr[:] = zr_scr[:] + jnp.sum(gq, axis=0, keepdims=True)
        zrfin_ref[0] = zr_scr[:]


def _bwd_rev_kernel(q_ref, k_ref, v_ref, g_ref, rinit_ref, dk_ref, dv_ref, rfin_ref, r_scr):
    """Unnormalized-path arity adapter over ``_bwd_rev_core``."""
    _bwd_rev_core(
        q_ref, k_ref, v_ref, g_ref, None, rinit_ref, None,
        dk_ref, dv_ref, rfin_ref, None, r_scr, None,
    )


def _bwd_dq_den_kernel(
    g_ref, v_ref, k_ref, s0t_ref, gden_ref, z0_ref, dq_ref, s_scr, z_scr
):
    """Forward-walking fused dq for the NORMALIZED backward: the numerator
    part (same math as ``_kernel`` on (g, v, k) with S0^T carried in) plus
    the denominator part ``gden_t * (z0 + Σ_{s<=t} k_s)`` — the prefix-z
    state rides the same pass instead of a separate XLA cumsum over
    [BH, T, Dk] fp32 (measured: the two den cumsum passes were ~30% of
    fused-backward wall time at long T). In-chunk prefix sums are a
    lower-triangular matmul on the MXU."""
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_scr[:] = s0t_ref[0].astype(jnp.float32)  # (Dv, Dk)
        z_scr[:] = z0_ref[0].astype(jnp.float32)  # (1, Dk)

    gi = g_ref[0]  # (C, Dv)
    vi = v_ref[0]  # (C, Dv)
    ki = k_ref[0]  # (C, Dk)
    gd = gden_ref[0].astype(jnp.float32)  # (C, 1)

    scores = jax.lax.dot_general(
        gi, vi, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (C, C): g_t · v_s
    causal = _tri_mask(scores.shape[0]).astype(jnp.float32)  # s <= t
    scores = scores * causal

    kf = ki.astype(jnp.float32)
    intra = jnp.dot(scores, kf, preferred_element_type=jnp.float32)
    inter = jnp.dot(
        gi.astype(jnp.float32), s_scr[:], preferred_element_type=jnp.float32
    )
    kcum = jnp.dot(causal, kf, preferred_element_type=jnp.float32)  # prefix-incl
    dq_ref[0] = (intra + inter + gd * (z_scr[:] + kcum)).astype(dq_ref.dtype)

    s_scr[:] = s_scr[:] + jax.lax.dot_general(
        vi, ki, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # += Σ v_s (x) k_s
    z_scr[:] = z_scr[:] + jnp.sum(kf, axis=0, keepdims=True)


@kernel_entry("causal_dot_dq", "chunk", "interpret")
def _cdp_dq_den_flat(g, v, k, s0t, gden, z0, chunk, interpret):
    """dq (numerator + denominator parts) on flat inputs, emitted directly
    in ``g``'s dtype — nothing downstream adds to it."""
    bh, t, dk = k.shape
    dv = v.shape[-1]
    nc = t // chunk

    (dq,) = pl.pallas_call(
        _bwd_dq_den_kernel,
        name="causal_dot_dq",
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, dv, dk), lambda b, c: (b, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, dk), lambda b, c: (b, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[_sds((bh, t, dk), g.dtype, g)],
        scratch_shapes=[
            pltpu.VMEM((dv, dk), jnp.float32),
            pltpu.VMEM((1, dk), jnp.float32),
        ],
        interpret=interpret,
    )(g, v, k, s0t, gden, z0)
    return dq


# normalized path: _bwd_rev_core's full signature IS the kernel (all den
# refs live; dk/dv come out in the input dtype — they are final values)
_bwd_rev_den_kernel = _bwd_rev_core


@kernel_entry("causal_dot_norm_dkv", "chunk", "interpret")
def _cdp_rev_den_flat(q, k, v, g, gden, rinit, zr0, chunk, interpret):
    """Fused (dk, dv, ds0, dz0) for the normalized backward. dk/dv in the
    input dtypes (final values); ds0 [BH, Dk, Dv] and dz0 [BH, 1, Dk] fp32."""
    bh, t, dk = q.shape
    dv = v.shape[-1]
    nc = t // chunk
    rev = lambda b, c: (b, nc - 1 - c, 0)  # noqa: E731

    dk_out, dv_out, rfin, zrfin = pl.pallas_call(
        _bwd_rev_den_kernel,
        name="causal_dot_norm_dkv",
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk, dk), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk, dv), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk, dv), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk, 1), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, dv, dk), lambda b, c: (b, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, dk), lambda b, c: (b, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dk), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk, dv), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, dv, dk), lambda b, c: (b, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, dk), lambda b, c: (b, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _sds((bh, t, dk), k.dtype, q),
            _sds((bh, t, dv), v.dtype, q),
            _sds((bh, dv, dk), jnp.float32, q),
            _sds((bh, 1, dk), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((dv, dk), jnp.float32),
            pltpu.VMEM((1, dk), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, g, gden, rinit, zr0)
    ds0 = jnp.swapaxes(rfin, -1, -2)
    return dk_out, dv_out, ds0, zrfin


@kernel_entry("causal_dot_dkv", "chunk", "interpret")
def _cdp_rev_flat(q, k, v, g, rinit, chunk, interpret):
    """Fused (dk, dv, ds0) on flat [BH, T, D] inputs (T % chunk == 0).
    ``rinit`` = dSf^T [BH, Dv, Dk] fp32; returns ds0 [BH, Dk, Dv] fp32."""
    bh, t, dk = q.shape
    dv = v.shape[-1]
    nc = t // chunk
    rev = lambda b, c: (b, nc - 1 - c, 0)  # noqa: E731

    dk_out, dv_out, rfin = pl.pallas_call(
        _bwd_rev_kernel,
        name="causal_dot_dkv",
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk, dk), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk, dv), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk, dv), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, dv, dk), lambda b, c: (b, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dk), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk, dv), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, dv, dk), lambda b, c: (b, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _sds((bh, t, dk), jnp.float32, q),
            _sds((bh, t, dv), jnp.float32, q),
            _sds((bh, dv, dk), jnp.float32, q),
        ],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        interpret=interpret,
    )(q, k, v, g, rinit)
    ds0 = jnp.swapaxes(rfin, -1, -2)
    return dk_out, dv_out, ds0


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _cdp(q, k, v, s0, chunk, interpret):
    return _cdp_flat(q, k, v, s0, chunk, interpret)


def _cdp_fwd(q, k, v, s0, chunk, interpret):
    out, sf = _cdp_flat(q, k, v, s0, chunk, interpret)
    return (out, sf), (q, k, v, s0)


def _cdp_bwd(chunk, interpret, res, cts):
    q, k, v, s0 = res
    g, dsf = cts
    g = g.astype(q.dtype)
    # dq pass: same forward kernel on (g, v, k), with S0^T as its carried-in
    # state (out[t] = sum_{s<=t}(g_t.v_s) k_s + g_t @ S0^T)
    s0t = jnp.swapaxes(s0.astype(jnp.float32), -1, -2)
    dq, _ = _cdp_flat(g, v, k, s0t, chunk, interpret)
    # dk + dv + ds0: one reverse-walking fused pass, dSf^T seeding the state
    rinit = jnp.swapaxes(dsf.astype(jnp.float32), -1, -2)
    dk, dv, ds0 = _cdp_rev_flat(q, k, v, g, rinit, chunk, interpret)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), ds0


_cdp.defvjp(_cdp_fwd, _cdp_bwd)


def causal_dot_product_pallas(
    q: Array,
    k: Array,
    v: Array,
    *,
    chunk: Optional[int] = None,
    return_state: bool = False,
    initial_state: Optional[Array] = None,
    interpret: bool = False,
):
    """Public entry: arbitrary batch dims [..., T, Dk/Dv], auto pad/reshape.

    Differentiable (custom VJP), including through ``initial_state`` and the
    returned state. Zero-padding the tail chunk is safe: padded k/v rows
    contribute nothing to S, and padded outputs are sliced off.
    """
    batch_shape = q.shape[:-2]
    t, dk = q.shape[-2], q.shape[-1]
    dv = v.shape[-1]
    chunk = _auto_chunk(chunk, t)
    bh = 1
    for s in batch_shape:
        bh *= s

    qf = q.reshape(bh, t, dk)
    kf = k.reshape(bh, t, dk)
    vf = v.reshape(bh, t, dv)
    rem = (-t) % chunk
    if rem:
        pad = ((0, 0), (0, rem), (0, 0))
        qf, kf, vf = jnp.pad(qf, pad), jnp.pad(kf, pad), jnp.pad(vf, pad)

    if initial_state is None:
        s0 = vma_zeros_state(kf, vf)
    else:
        s0 = initial_state.astype(jnp.float32).reshape(bh, dk, dv)

    out, sf = _cdp(qf, kf, vf, s0, chunk, interpret)
    out = out[:, :t, :].reshape(*batch_shape, t, dv)
    if return_state:
        return out, sf.reshape(*batch_shape, dk, dv)
    return out


# ---------------------------------------------------------------------------
# Fused normalized linear attention: numerator, denominator, and both carried
# states (S, z) in ONE kernel pass — no separate fp32 cumsum over HBM for the
# normalizer (the reference fuses the same way inside its CUDA kernel pair:
# causal_dot_product + kv-cumsum; BASELINE.json north_star).
# ---------------------------------------------------------------------------


def _kernel_norm(
    q_ref, k_ref, v_ref, s0_ref, z0_ref,
    num_ref, den_ref, sf_ref, zf_ref,
    s_scr, z_scr,
):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_scr[:] = s0_ref[0].astype(jnp.float32)
        z_scr[:] = z0_ref[0].astype(jnp.float32)

    qi = q_ref[0]  # (C, Dk)
    ki = k_ref[0]
    vi = v_ref[0]

    scores = jax.lax.dot_general(
        qi, ki,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    scores = jnp.where(_tri_mask(scores.shape[0]), scores, 0.0)

    intra = jnp.dot(scores, vi.astype(jnp.float32), preferred_element_type=jnp.float32)
    inter = jnp.dot(qi.astype(jnp.float32), s_scr[:], preferred_element_type=jnp.float32)
    num_ref[0] = intra + inter

    den_intra = jnp.sum(scores, axis=1, keepdims=True)  # (C, 1)
    den_inter = jax.lax.dot_general(
        qi.astype(jnp.float32), z_scr[:],  # same-dtype operands for Mosaic
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (C, 1)
    den_ref[0] = den_intra + den_inter

    s_scr[:] = s_scr[:] + jax.lax.dot_general(
        ki, vi,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    z_scr[:] = z_scr[:] + jnp.sum(
        ki.astype(jnp.float32), axis=0, keepdims=True
    )
    sf_ref[0] = s_scr[:]
    zf_ref[0] = z_scr[:]


@kernel_entry("causal_dot_norm_fwd", "chunk", "interpret")
def _cdpn_flat(q, k, v, s0, z0, chunk, interpret):
    """Fused pass on flat [BH, T, D] inputs (T % chunk == 0): returns
    (num fp32, den fp32 [BH,T,1], sf fp32, zf fp32 [BH,1,Dk])."""
    bh, t, dk = q.shape
    dv = v.shape[-1]
    nc = t // chunk

    num, den, sf, zf = pl.pallas_call(
        _kernel_norm,
        name="causal_dot_norm_fwd",
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, dk, dv), lambda b, c: (b, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, dk), lambda b, c: (b, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, dk, dv), lambda b, c: (b, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, dk), lambda b, c: (b, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _sds((bh, t, dv), jnp.float32, q),
            _sds((bh, t, 1), jnp.float32, q),
            _sds((bh, dk, dv), jnp.float32, q),
            _sds((bh, 1, dk), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((dk, dv), jnp.float32),
            pltpu.VMEM((1, dk), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, s0, z0)
    return num, den, sf, zf


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _lin_attn_fused(q, k, v, s0, z0, chunk, eps, interpret):
    num, den, sf, zf = _cdpn_flat(q, k, v, s0, z0, chunk, interpret)
    out = (num / (den + eps)).astype(q.dtype)
    return out, sf, zf, den


def _lin_attn_fused_fwd(q, k, v, s0, z0, chunk, eps, interpret):
    num, den, sf, zf = _cdpn_flat(q, k, v, s0, z0, chunk, interpret)
    out = (num / (den + eps)).astype(q.dtype)
    return (out, sf, zf, den), (q, k, v, s0, z0, num, den)


def _fused_bwd_core(q, k, v, s0, z0, gnum, gden, gsf, gzf, chunk, interpret):
    """Shared backward for the fused pass given cotangents of the fp32
    numerator (gnum, already cast to q.dtype for the kernel), denominator
    (gden [BH,T,1] fp32), and final states (gsf, gzf).

    Two kernel passes, with the denominator backward FUSED into both (the
    earlier formulation ran it as two XLA cumsums over [BH,T,Dk] fp32 plus
    elementwise combines — pure HBM traffic):

    - forward walk (_bwd_dq_den_kernel): dq = numerator part + gden·zcum,
      the prefix-z carried in VMEM; emitted directly in q.dtype.
    - reverse walk (_bwd_rev_den_kernel): dk (incl. suffix Σ gden·q and
      the broadcast gzf, both riding a (1,Dk) carried state), dv, ds0;
      the final suffix state IS dz0.
    """
    gsf32 = gsf.astype(jnp.float32)
    gzf32 = gzf.astype(jnp.float32)
    gden32 = gden.astype(jnp.float32)

    s0t = jnp.swapaxes(s0.astype(jnp.float32), -1, -2)
    z032 = z0.astype(jnp.float32)
    dq = _cdp_dq_den_flat(gnum, v, k, s0t, gden32, z032, chunk, interpret)
    rinit = jnp.swapaxes(gsf32, -1, -2)
    dk, dv, ds0, dz0 = _cdp_rev_den_flat(
        q, k, v, gnum, gden32, rinit, gzf32, chunk, interpret
    )
    return dq.astype(q.dtype), dk, dv, ds0, dz0


def _lin_attn_fused_bwd(chunk, eps, interpret, res, cts):
    q, k, v, s0, z0, num, den = res
    gout, gsf, gzf, gden_ext = cts
    gout = gout.astype(jnp.float32)
    d = den + eps  # (BH, T, 1) fp32
    gnum = (gout / d).astype(q.dtype)
    gden = (
        -jnp.sum(gout * num, axis=-1, keepdims=True) / (d * d)
        + gden_ext.astype(jnp.float32)
    )  # (BH, T, 1)
    return _fused_bwd_core(q, k, v, s0, z0, gnum, gden, gsf, gzf, chunk, interpret)


_lin_attn_fused.defvjp(_lin_attn_fused_fwd, _lin_attn_fused_bwd)


# Raw (unnormalized) fused pass: hands back the fp32 numerator itself, so
# sequence parallelism can apply the cross-shard prefix correction without a
# bf16 round-trip through the normalized output (ADVICE r1).
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _lin_attn_fused_raw(q, k, v, s0, z0, chunk, interpret):
    return _cdpn_flat(q, k, v, s0, z0, chunk, interpret)


def _lin_attn_fused_raw_fwd(q, k, v, s0, z0, chunk, interpret):
    num, den, sf, zf = _cdpn_flat(q, k, v, s0, z0, chunk, interpret)
    return (num, den, sf, zf), (q, k, v, s0, z0)


def _lin_attn_fused_raw_bwd(chunk, interpret, res, cts):
    q, k, v, s0, z0 = res
    gnum32, gden, gsf, gzf = cts
    gnum = gnum32.astype(q.dtype)
    gden = gden.astype(jnp.float32)
    return _fused_bwd_core(q, k, v, s0, z0, gnum, gden, gsf, gzf, chunk, interpret)


_lin_attn_fused_raw.defvjp(_lin_attn_fused_raw_fwd, _lin_attn_fused_raw_bwd)


def _auto_chunk(chunk: Optional[int], t: int) -> int:
    from orion_tpu.ops.dispatch import resolve_chunk

    return resolve_chunk(chunk, t, "pallas")


def _prep_fused(q, k, v, chunk, initial_state):
    """Shared flatten + tail-pad + state-init for the fused entry points.
    Returns (qf, kf, vf, s0, z0, batch_shape, t, chunk) with chunk resolved
    to the tuned default when None."""
    chunk = _auto_chunk(chunk, q.shape[-2])
    batch_shape = q.shape[:-2]
    t, dk = q.shape[-2], q.shape[-1]
    dv = v.shape[-1]
    chunk = _auto_chunk(chunk, t)
    bh = 1
    for s in batch_shape:
        bh *= s

    qf = q.reshape(bh, t, dk)
    kf = k.reshape(bh, t, dk)
    vf = v.reshape(bh, t, dv)
    rem = (-t) % chunk
    if rem:
        pad = ((0, 0), (0, rem), (0, 0))
        qf, kf, vf = jnp.pad(qf, pad), jnp.pad(kf, pad), jnp.pad(vf, pad)

    if initial_state is None:
        s0 = vma_zeros_state(kf, vf)
        z0 = 0.0 * kf[:, :1].astype(jnp.float32)
    else:
        s0 = initial_state[0].astype(jnp.float32).reshape(bh, dk, dv)
        z0 = initial_state[1].astype(jnp.float32).reshape(bh, 1, dk)
    return qf, kf, vf, s0, z0, batch_shape, t, chunk


def linear_attention_pallas_fused(
    q: Array,
    k: Array,
    v: Array,
    *,
    chunk: Optional[int] = None,
    eps: float = 1e-6,
    initial_state: Optional[Tuple[Array, Array]] = None,
    return_state: bool = False,
    return_den: bool = False,
    interpret: bool = False,
):
    """Normalized causal linear attention, fully fused in one Pallas pass.

    ``return_den`` additionally returns the fp32 normalizer den[t] =
    q_t·(z0 + Σ_{s<=t} k_s) as [..., T] — what lets sequence parallelism
    correct a locally-normalized shard in O(T·D) after one kernel pass
    (parallel/sequence.py).

    out[t] = q_t·S_t / (q_t·z_t + eps) with S, z the kv-cumsum states;
    optionally seeded by ``initial_state=(S0 [..,Dk,Dv], z0 [..,Dk])`` and
    returning the final (S, z) — the prefill→decode handoff. Differentiable
    through everything including the states (custom VJP: two kernel passes,
    with the denominator backward fused in as carried (1, Dk) VMEM states —
    see ``_fused_bwd_core``)."""
    qf, kf, vf, s0, z0, batch_shape, t, chunk = _prep_fused(q, k, v, chunk, initial_state)
    dk, dv = q.shape[-1], v.shape[-1]

    out, sf, zf, den = _lin_attn_fused(qf, kf, vf, s0, z0, chunk, eps, interpret)
    out = out[:, :t, :].reshape(*batch_shape, t, dv)
    results = [out]
    if return_state:
        results.append(
            (sf.reshape(*batch_shape, dk, dv), zf.reshape(*batch_shape, dk))
        )
    if return_den:
        results.append(den[:, :t, 0].reshape(*batch_shape, t))
    return results[0] if len(results) == 1 else tuple(results)


def linear_attention_pallas_parts(
    q: Array,
    k: Array,
    v: Array,
    *,
    chunk: Optional[int] = None,
    initial_state: Optional[Tuple[Array, Array]] = None,
    interpret: bool = False,
):
    """One fused kernel pass, returning the raw fp32 parts:
    (num [..., T, Dv] fp32, den [..., T] fp32, (S [..,Dk,Dv], z [..,Dk])).

    The sequence-parallel path (parallel/sequence.py) consumes these: the
    exact fp32 numerator lets the cross-shard prefix correction avoid
    inheriting bf16 rounding from the locally-normalized output.
    Differentiable via custom VJP (same kernel identities, no quotient
    rule needed)."""
    qf, kf, vf, s0, z0, batch_shape, t, chunk = _prep_fused(q, k, v, chunk, initial_state)
    dk, dv = q.shape[-1], v.shape[-1]

    num, den, sf, zf = _lin_attn_fused_raw(qf, kf, vf, s0, z0, chunk, interpret)
    num = num[:, :t, :].reshape(*batch_shape, t, dv)
    den = den[:, :t, 0].reshape(*batch_shape, t)
    state = (sf.reshape(*batch_shape, dk, dv), zf.reshape(*batch_shape, dk))
    return num, den, state


__all__ = [
    "causal_dot_product_pallas",
    "linear_attention_pallas_fused",
    "linear_attention_pallas_parts",
]


# ---------------------------------------------------------------------------
# Decayed causal dot product: a per-head scalar decay on the carried state,
# no normaliser, a state in and out (ops/linear_attention.py section 4 has
# the equations; serving's prompt pieces run this, nothing trains it).
# ---------------------------------------------------------------------------


def _decay_kernel(len_ref, a_ref, q_ref, k_ref, v_ref, s0_ref, out_ref, sf_ref, s_scr):
    """One chunk of C rows of one (batch, head): ``a`` is the head's slope
    ``-log lam``; every power of lam is ``exp(-a n)`` with ``n >= 0``."""
    c = pl.program_id(1)
    f32 = jnp.float32

    @pl.when(c == 0)
    def _():
        s_scr[:] = s0_ref[0].astype(f32)

    qi, ki, vi = q_ref[0], k_ref[0], v_ref[0]
    cdim = qi.shape[0]
    a = a_ref[0]  # (1, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (cdim, cdim), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (cdim, cdim), 1)
    gap = (row - col).astype(f32)
    within = jnp.where(gap >= 0, jnp.exp(-a * jnp.maximum(gap, 0.0)), 0.0)
    i = jax.lax.broadcasted_iota(jnp.int32, (cdim, 1), 0).astype(f32)
    # rows of this chunk that are not right-padding
    real = jnp.clip(len_ref[0] - c * cdim, 0, cdim).astype(f32)
    left = real - 1.0 - i
    into = jnp.where(left >= 0, jnp.exp(-a * jnp.maximum(left, 0.0)), 0.0)

    scores = jax.lax.dot_general(
        qi, ki, (((1,), (1,)), ((), ())), preferred_element_type=f32
    ) * within
    intra = jnp.dot(scores.astype(vi.dtype), vi, preferred_element_type=f32)
    inter = jnp.dot(
        qi.astype(f32) * jnp.exp(-a * (i + 1.0)), s_scr[:],
        preferred_element_type=f32,
    )
    out_ref[0] = (intra + inter).astype(out_ref.dtype)
    s_scr[:] = jnp.exp(-a * real) * s_scr[:] + jax.lax.dot_general(
        (ki.astype(f32) * into).astype(vi.dtype), vi,
        (((0,), (0,)), ((), ())), preferred_element_type=f32,
    )
    sf_ref[0] = s_scr[:]


@kernel_entry("causal_dot_decay_fwd", "chunk", "interpret")
def decayed_causal_dot_pallas(
    q: Array, k: Array, v: Array, slopes: Array, *, chunk: Optional[int] = None,
    initial_state: Optional[Array] = None, length=None, interpret: bool = False,
) -> Tuple[Array, Array]:
    """``ops.linear_attention.decayed_causal_dot_chunked`` as a Mosaic
    kernel: q, k ``[..., H, T, Dk]``, v ``[..., H, T, Dv]``, ``slopes`` [H]
    fp32, ``initial_state`` ``[..., H, Dk, Dv]`` (zeros if None), ``length``
    the traced number of real rows (default T) -> (out in q's dtype, the
    state after ``length`` rows in fp32). The products against v take v's
    dtype on the MXU with fp32 sums; the carried state and what multiplies
    it stay fp32. Forward only."""
    batch_shape = q.shape[:-2]
    t, dk = q.shape[-2], q.shape[-1]
    dv, h = v.shape[-1], q.shape[-3]
    chunk = _auto_chunk(chunk, t)
    bh = 1
    for s in batch_shape:
        bh *= s
    qf, kf, vf = q.reshape(bh, t, dk), k.reshape(bh, t, dk), v.reshape(bh, t, dv)
    rem = (-t) % chunk
    if rem:
        pad = ((0, 0), (0, rem), (0, 0))
        qf, kf, vf = jnp.pad(qf, pad), jnp.pad(kf, pad), jnp.pad(vf, pad)
    s0 = (
        jnp.zeros((bh, dk, dv), jnp.float32) if initial_state is None
        else initial_state.astype(jnp.float32).reshape(bh, dk, dv)
    )
    a = jnp.broadcast_to(
        slopes.astype(jnp.float32), batch_shape[:-1] + (h,)
    ).reshape(bh, 1, 1)
    n = jnp.asarray(t if length is None else length, jnp.int32).reshape(1)
    tp = t + rem
    blk = lambda d: pl.BlockSpec((1, chunk, d), lambda b, c, n: (b, c, 0))  # noqa: E731
    state = pl.BlockSpec((1, dk, dv), lambda b, c, n: (b, 0, 0))
    out, sf = pl.pallas_call(
        _decay_kernel,
        name="causal_dot_decay_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, tp // chunk),
            in_specs=[
                pl.BlockSpec((1, 1, 1), lambda b, c, n: (b, 0, 0)),
                blk(dk), blk(dk), blk(dv), state,
            ],
            out_specs=[blk(dv), state],
            scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, tp, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
        ],
        interpret=interpret,
    )(n, a, qf, kf, vf, s0)
    out = out[:, :t, :].reshape(*batch_shape, t, dv)
    return out, sf.reshape(*batch_shape, dk, dv)
