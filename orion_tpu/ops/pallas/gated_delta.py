"""Pallas TPU kernels for the chunked gated delta rule, forward and backward.

The mathematics is ``ops/gated_delta.py``'s chunked (WY) form, unchanged;
its module docstring is the specification. What changes is where a chunk's
arrays live. The XLA form carries some twenty chunk-local arrays (``A``,
``T``, ``u0``, ``w``, the decayed q and k, ...) through HBM between its
fusions and a scan; here a grid step loads a block of up to
``BLOCK_CHUNKS`` chunks of q, k, v, beta and the in-chunk cumulative
log-decay ``G`` of one value head, forms everything else in VMEM and writes
only ``o``.

- *Forward* (``gated_delta_fwd``). Grid ``(batch x value heads, blocks of
  chunks)``, the second axis sequential with the fp32 state ``S`` in a VMEM
  scratch (as ``causal_dot.py::_kernel`` walks its chunks). Per chunk:
  ``decay``, ``A``, ``T = (I + A)^-1`` (``_unit_lower_inverses``), ``u = T
  diag(beta) (v - k_dec S)``, ``o = q_dec S + (q k^T * decay) u``, ``S <-
  e^{G_last} S + k_end^T u``. What does not wait on ``S`` is formed for the
  whole block first, a level of the solve at a time across its chunks, so
  that the MXU has independent products to take while one chunk's wait on
  each other. Under ``jax.grad`` it also writes each chunk's ``T`` and
  ``u`` in the input dtype (the operands they are used as) and the state
  that enters each block (fp32): the custom VJP's residuals besides its
  inputs, 1.2 GB a layer at 8 x 32 heads x T 8192 against the ~2 GB of
  chunk-local arrays the XLA form kept per batch row.
- *Backward* (``gated_delta_bwd``). The blocks walked last to first with
  ``dS`` in an fp32 scratch. A grid step replays its chunks' incoming
  states from the block's saved one (one product a chunk, from the saved
  ``u``), then walks the chunks in reverse. No solve is repeated, and the
  inverse's VJP ``dA = -T^T dT T^T`` collapses, with ``dT = dU R^T`` and
  ``U = T R``, to ``dA = -(T^T dU) U^T``: no product of ``T``'s. It emits
  ``dq``, ``dk``, ``dv``, ``dbeta`` and ``dG``; the in-chunk cumulative sum
  ``g -> G`` stays outside, differentiated by autodiff.
- *Grouped heads.* q and k keep their ``Hk`` key heads: the value head
  ``hv`` reads key head ``hv // (Hv / Hk)`` through the ``BlockSpec``
  index map, so nothing is repeated in HBM. On head-major operands ``dq`` /
  ``dk`` come out per value head and the wrapper sums each group; read in
  place (next) the sum is inside the kernel.
- *q, k, v where the short conv left them* (``gated_delta_qkv_pallas``: a
  training call with no state, ``reads_qkv``). The operands are three
  ``BlockSpec``s on the ONE array ``qkv [B, T, C]``, columns ``[q | k |
  v]``: a head is a column block, time stays on sublanes, and ``o`` leaves
  head-major as before (the gate's kernel reads it there). The l2 norm of
  q and k and q's ``Dk ** -0.5`` (``ops/gated_delta.py::qkv_operands``, the
  specification) are formed from each chunk's rows in VMEM, fp32 inside,
  rounded to the input dtype where the mixer's ``_operands`` rounds: the
  forward adds and removes no rounding. The backward's grid is ``(batch x
  KEY heads, blocks last to first, column blocks of the cotangent)``. At the
  innermost axis's first step a key head's ``Hv / Hk`` value heads are
  walked side by side, chunk by chunk (two independent chains: one's
  products fill the MXU while the other's wait), their ``dq`` and ``dk``
  summed in fp32, passed through the norm's VJP (``dx = r (dy - xn (xn .
  dy))``, the row scales recomputed from the same block) and rounded ONCE
  into a VMEM scratch beside each head's ``dv``; every step of that axis
  then hands one column block to the ONE cotangent ``d qkv [B, T, C]``, so
  an output block is visited once and nothing around the kernels relays,
  sums, pads or re-converts (136.8 ms of a 1,710.6 ms step at
  ``qwen3_next_80b.train``, PERF.md s5).
- *Precision.* Matmul operands in the input dtype with fp32 accumulation,
  rounded where the XLA form rounds them (``T diag(beta)`` once, from the
  fp32 ``T``); decays, ``A``, the solve and ``S`` in fp32. The solve's
  products are three bf16 passes of a hi/lo split (~2^-17 relative: what
  ``Precision.HIGH`` means) for every input dtype. Every decay is ``exp``
  of a non-positive difference. In the solve's VJP (``dr = T^T du``,
  ``dA = -dr u^T``) ``du`` and ``dr`` enter as hi + lo operands; ``T`` is
  read as saved, in the input dtype: saved in fp32 and multiplied in three
  passes it moved no gradient's error (0.268 against 0.269% of ``dv``,
  the others equal; my chip run, PR 31) for 0.54 GB more a layer.
- *Shapes.* ``CHUNK`` and ``BLOCK_CHUNKS`` are this file's tiling (chosen
  on the chip: chunks of 128 against 64, 39 against 49 ms a layer forward;
  8 chunks a step against 4, 16.1 against 17.1), not a knob. T off a
  multiple of a block is zero-padded (k = v = beta = g = 0: the state passes
  through); a T shorter than a block is one smaller block. Compiled for a
  TPU the differentiable kernels need Dk and Dv to be multiples of 128
  (``supports``); ``ops/dispatch.py`` sends a training call at other widths
  to the XLA form. Interpret mode takes any width.
- *A state in and out* (``gated_delta_fwd_state``, serving's prefill and its
  pieces; forward only). The same block walk, with ``S`` loaded from
  ``initial_state`` at a row's first block and written out after its last.
  Widths off a multiple of 128 are zero-padded to the next one in the
  wrapper (96 x 192 runs as 128 x 256): zero key columns add nothing to
  ``k k^T`` or ``q k^T`` and their rows of ``S`` stay zero; zero value columns
  stay zero in ``u``, ``S`` and ``o``. The call without a state is the
  program it was: the training path compiles as before.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas import kernel_entry
from orion_tpu.ops.pallas.causal_dot import _sds

Array = jax.Array

CHUNK = 128  # tokens a chunk: the solve is C x C, a whole MXU tile
BLOCK_CHUNKS = 8  # chunks a grid step (fewer when T is short); one saved state a block
_BASE = 16  # diagonal blocks inverted by a Neumann product (A^16 = 0)
_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))  # x @ y^T
_TN = (((0,), (0,)), ((), ()))  # x^T @ y


def supports(dk: int, dv: int) -> bool:
    """Widths the compiled kernels take: whole 128-lane tiles."""
    return dk % 128 == 0 and dv % 128 == 0


def reads_qkv(hk: int, hv: int, dk: int, dv: int) -> bool:
    """Whether q, k and v can be read as column blocks of ``[q | k | v]``
    channels: v's columns start on a whole block of a key head's ``hv / hk``
    value heads (q's and k's always do)."""
    return hv % hk == 0 and (2 * hk * dk) % (hv // hk * dv) == 0


def _dot(x, y, dims=None):
    if dims is None:
        return jnp.dot(x, y, preferred_element_type=_F32)
    return jax.lax.dot_general(x, y, dims, preferred_element_type=_F32)


def _parts(x, dtype=jnp.bfloat16):
    """fp32 ``x`` as matmul operands of ``dtype`` that sum to it: itself
    for fp32, else its rounding and what the rounding lost."""
    if dtype == _F32:
        return (x,)
    hi = x.astype(dtype)
    return hi, (x - hi.astype(_F32)).astype(dtype)


def _solve_dot(x, y):
    """An fp32 x fp32 product of the triangular solve: three bf16 passes of
    a hi/lo split (~2^-17 relative), what ``Precision.HIGH`` asks for in
    ``ops/gated_delta.py``, for every input dtype."""
    (xh, xl), (yh, yl) = _parts(x), _parts(y)
    return (_dot(xh, yl) + _dot(xl, yh)) + _dot(xh, yh)


def _unit_lower_inverses(mats):
    """``(I + A)^-1`` for each strictly lower-triangular ``A [C, C]`` fp32
    of ``mats``.

    ``A = [[A11, 0], [A21, A22]]`` in halves of ``h = C / 2``. The two
    diagonal halves are inverted side by side, lane-packed as ``[A11 | A22]``
    (``h x C``), by the products of ``ops/gated_delta.py::
    _unit_lower_inverse_fwd`` (Neumann over the 16 x 16 diagonal blocks, then
    over what couples them): a packed product ``[X1 Y1 | X2 Y2]`` is ``X``
    times the block-diagonal of ``Y``, one ``h``-row pass through a full MXU
    tile where two half-filled ones were. Then the exact merge
    ``[[T1, 0], [-T2 A21 T1, T2]]``. Every product is taken a level at a
    time across ``mats``: one matrix's products wait on each other, its
    neighbours' fill the MXU meanwhile (a chunk alone, unpacked, ran the
    solve at a quarter of this pace: 29 against 7.4 ms a layer at the
    cell's shape, my chip runs, PR 31)."""
    c = mats[0].shape[-1]
    h = c // 2
    assert h % _BASE == 0 and h & (h - 1) == 0, c
    prow = jax.lax.broadcasted_iota(jnp.int32, (h, c), 0)
    pcol = jax.lax.broadcasted_iota(jnp.int32, (h, c), 1)
    left, inner = pcol < h, pcol & (h - 1)  # a lane's half, its column there
    eye = (inner == prow).astype(_F32)
    stack = lambda top, bottom: jnp.concatenate([top, bottom], axis=0)  # noqa: E731

    def pmm(x, y):  # [x1 y1 | x2 y2] of two lane-packed pairs
        return _solve_dot(x, stack(jnp.where(left, y, 0.0), jnp.where(left, 0.0, y)))

    def nilpotent_inverses(ns, order):
        """(I + N)^-1 for N^order = 0, order a power of two."""
        invs, powers, reach = [eye - n for n in ns], ns, 2
        while reach < order:
            powers = [pmm(p, p) for p in powers]
            invs = [pmm(i, eye + p) for i, p in zip(invs, powers)]
            reach *= 2
        return invs

    shift = _BASE.bit_length() - 1
    diag = (prow >> shift) == (inner >> shift)
    packed = [jnp.where(left, a[:h], a[h:]) for a in mats]
    ds = [jnp.where(diag, p, 0.0) for p in packed]
    dinvs = nilpotent_inverses(ds, _BASE)
    ms = [pmm(dinv, p - d) for dinv, p, d in zip(dinvs, packed, ds)]
    ts = [pmm(inv, dinv) for inv, dinv in zip(nilpotent_inverses(ms, h // _BASE), dinvs)]
    # [A21 | 0] [[T1, 0], [T1, 0]] = [A21 T1 | 0] = y;  [0 | T2] [[y], [y]] = [T2 A21 T1 | 0]
    t1s = [jnp.where(left, t, 0.0) for t in ts]
    ys = [_solve_dot(jnp.where(left, a[h:], 0.0), stack(t1, t1)) for a, t1 in zip(mats, t1s)]
    xs = [_solve_dot(jnp.where(left, 0.0, t), stack(y, y)) for t, y in zip(ts, ys)]
    return [stack(t1, jnp.where(left, -x, t)) for t1, t, x in zip(t1s, ts, xs)]


def _decays(gr, br):
    """A chunk's masks and decays from its ``[1, C]`` rows of ``G`` and
    beta. A ``[C, 1]`` column is the row masked to the diagonal and summed
    along lanes (a transpose of the broadcast row measured slower)."""
    c = gr.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = row == col
    to_col = lambda r: jnp.sum(jnp.where(eye, r, 0.0), axis=1, keepdims=True)  # noqa: E731
    gc = to_col(gr)
    last = col[:1] == c - 1
    gl = jnp.sum(jnp.where(last, gr, 0.0), axis=1, keepdims=True)  # G_last [1, 1]
    return dict(
        row=row, col=col, eye=eye, last=last, bc=to_col(br),
        decay=jnp.exp(jnp.where(row >= col, gc - gr, -jnp.inf)),  # 0 above the diagonal
        eg=jnp.exp(gc), rho=jnp.exp(gl - gc), egl=jnp.exp(gl),
    )


def _unit(x, eps, scale=1.0):
    """A chunk's rows of q or k as the layer hands them to the rule:
    ``ops/gated_delta.py::l2norm`` in fp32, times ``scale``, rounded to
    ``x``'s dtype where the mixer's ``_operands`` rounds -> ``(operand, the
    fp32 unit rows, the rsqrt that made them)``."""
    xf = x.astype(_F32)
    r = jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)
    xn = xf * r
    return (xn if scale == 1.0 else xn * scale).astype(x.dtype), xn, r


def _unit_vjp(dy, xn, r):
    """``l2norm``'s VJP on fp32 rows: ``dx = r (dy - xn (xn . dy))``."""
    return r * (dy - xn * jnp.sum(xn * dy, axis=-1, keepdims=True))


def _block_fwd(chunks, s):
    """A block's chunks ``(q, k, v, G row, beta row)`` from the state ``s``
    (fp32 ``[Dk, Dv]``): ``(S_out, [(o, T, u)])``, ``T`` and ``u`` in v's
    dtype, the matmul operands the backward reads them as. Everything that
    does not wait on the state is formed for the whole block first."""
    cdt = chunks[0][2].dtype
    xs = [_decays(gr, br) for _, _, _, gr, br in chunks]
    ts = _unit_lower_inverses(
        [jnp.where(x["row"] > x["col"], _dot(k, k, _NT) * x["decay"] * x["bc"], 0.0)
         for (_, k, _, _, _), x in zip(chunks, xs)]
    )
    ready = []
    for (q, k, v, _, br), x, t in zip(chunks, xs, ts):
        qf, kf, eg = q.astype(_F32), k.astype(_F32), x["eg"]
        tb = (t * br).astype(cdt)  # (I + A)^-1 diag(beta), rounded once from the fp32 T
        kdec, kend, qdec = (y.astype(cdt) for y in (kf * eg, kf * x["rho"], qf * eg))
        qk = (_dot(q, k, _NT) * x["decay"]).astype(cdt)  # causal, decayed in-chunk scores
        ready.append((t.astype(cdt), _dot(tb, v), _dot(tb, kdec).astype(cdt), qk, qdec, kend, x["egl"]))
    outs = []
    for t, u0, w, qk, qdec, kend, egl in ready:
        sc = s.astype(cdt)  # the state as a matmul operand; it accumulates in fp32
        u = (u0 - _dot(w, sc)).astype(cdt)
        outs.append((_dot(qdec, sc) + _dot(qk, u), t, u))
        s = s * egl + _dot(kend, u, _TN)
    return s, outs


def _chunk_bwd(q, k, v, gr, br, do, t, u, s, ds):
    """One chunk's gradients given the cotangents ``do`` of its output and
    ``ds`` of its outgoing state: ``(dq, dk, dv, dbeta row, dG row,
    dS_in)``. ``t``, ``u`` are the forward's, ``s`` its incoming state."""
    cdt = v.dtype
    x = _decays(gr, br)
    rowsum = lambda y: jnp.sum(y, axis=1, keepdims=True)  # noqa: E731
    colsum = lambda y: jnp.sum(y, axis=0, keepdims=True)  # noqa: E731
    to_row = lambda y: colsum(jnp.where(x["eye"], y, 0.0))  # noqa: E731
    bc, eg, rho, egl, decay = (x[n] for n in ("bc", "eg", "rho", "egl", "decay"))
    strict = x["row"] > x["col"]
    qf, kf = q.astype(_F32), k.astype(_F32)
    kdec, qdec, kend = (y.astype(cdt) for y in (kf * eg, qf * eg, kf * rho))
    qkf = _dot(q, k, _NT) * decay
    sc, dsc = s.astype(cdt), ds.astype(cdt)
    # The three products that carry dS from chunk to chunk (du's second,
    # dr, dS_in's last) each wait on the one before; the MXU takes work in
    # program order, so what does not wait on them is placed between them
    # (1.4 ms of 18.6 a layer at the cell's shape, my chip run, PR 31).
    # o = q_dec S + qk u;  S_out = e^{G_last} S + k_end^T u
    du = _dot(qkf.astype(cdt), do, _TN) + _dot(kend, dsc)
    kkd = _dot(k, k, _NT) * decay  # A = strict lower of beta_i * kkd
    p = v.astype(_F32) - _dot(kdec, sc)  # u = T diag(beta) p
    dqk = _dot(do, u, _NT)
    dqdec = _dot(do, sc, _NT)
    # u = T r, r = beta * p:  dr = T^T du;  dA = -T^T (du r^T) T^T = -dr u^T.
    # The solve's VJP: du and dr enter whole (hi + lo), T and u as saved.
    dr = sum(_dot(t, part, _TN) for part in _parts(du, cdt))
    dkend = _dot(u, dsc, _NT)
    dqkd = (dqk * decay).astype(cdt)
    dq = eg * dqdec + _dot(dqkd, k)
    dk = rho * dkend + _dot(dqkd, q, _TN)
    dp = (bc * dr).astype(cdt)
    ds_in = ds * egl + _dot(qdec, do, _TN) - _dot(kdec, dp, _TN)
    dkdec = -_dot(dp, sc, _NT)
    da = jnp.where(strict, -sum(_dot(part, u, _NT) for part in _parts(dr, cdt)), 0.0)
    dbeta = rowsum(dr * p) + rowsum(da * kkd)
    dkk = (da * decay * bc).astype(cdt)
    dk = dk + eg * dkdec + _dot(dkk, k) + _dot(dkk, k, _TN)
    # every decay is exp(G_i - G_j), exp(G_i) or exp(G_last - G_i)
    e = dqk * qkf + da * bc * kkd
    drho = rho * rowsum(dkend * kf)
    dg_col = rowsum(e) + eg * (rowsum(dqdec * qf) + rowsum(dkdec * kf)) - drho
    dgl = colsum(drho) + egl * colsum(rowsum(ds * s))
    dg = to_row(dg_col) - colsum(e) + jnp.where(x["last"], dgl, 0.0)
    return dq, dk, dp, to_row(dbeta), dg, ds_in


def _chunks(*refs):
    """``(token slice, each ref's part)`` for a block's chunks in order;
    refs are ``[1, tokens, D]`` or, one row a chunk, ``[1, 1, chunks, C]``."""
    for i in range(refs[0].shape[1] // CHUNK):
        tok = slice(i * CHUNK, (i + 1) * CHUNK)
        yield tok, [r[0, tok, :] if len(r.shape) == 3 else r[0, 0, i:i + 1, :] for r in refs]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, unit=None):
    """``unit`` = (eps, q's scale): q_ref and k_ref hold the short conv's
    rows, normalised here chunk by chunk."""
    s_scr = rest[-1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[:] = jnp.zeros_like(s_scr)

    s = s_scr[:]
    if len(rest) > 1:  # for the backward: the state entering this block
        rest[0][0, 0] = s
    toks, chunks = zip(*_chunks(q_ref, k_ref, v_ref, g_ref, b_ref))
    if unit is not None:
        eps, scale = unit
        chunks = [(_unit(q, eps, scale)[0], _unit(k, eps)[0], *rest_) for q, k, *rest_ in chunks]
    s_scr[:], outs = _block_fwd(chunks, s)
    for tok, (o, t, u) in zip(toks, outs):
        o_ref[0, tok, :] = o.astype(o_ref.dtype)
        if len(rest) > 1:  # ... and each chunk's T and u
            rest[1][0, tok, :], rest[2][0, tok, :] = t, u


def _fwd_state_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref, o_ref, sf_ref, s_scr):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[:] = s0_ref[0]

    toks, chunks = zip(*_chunks(q_ref, k_ref, v_ref, g_ref, b_ref))
    s, outs = _block_fwd(chunks, s_scr[:])
    s_scr[:] = s
    sf_ref[0] = s  # the row's block stays resident: what its last step leaves is written back
    for tok, (o, _, _) in zip(toks, outs):
        o_ref[0, tok, :] = o.astype(o_ref.dtype)


def _bwd_kernel(
    q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, s_ref, t_ref, u_ref,
    dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_scr, sin_scr,
):
    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_scr[:] = jnp.zeros_like(ds_scr)

    s = s_ref[0, 0]
    chunks = list(_chunks(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, t_ref, u_ref))
    for i, (_, (_, k, _, gr, br, _, _, u)) in enumerate(chunks):
        sin_scr[i] = s  # each chunk's incoming state, replayed from the block's
        x = _decays(gr, br)
        kend = (k.astype(_F32) * x["rho"]).astype(u.dtype)
        s = s * x["egl"] + _dot(kend, u, _TN)
    ds = ds_scr[:]
    for i, (tok, args) in reversed(list(enumerate(chunks))):
        dq, dk, dv, db, dg, ds = _chunk_bwd(*args, sin_scr[i], ds)
        dq_ref[0, tok, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, tok, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, tok, :] = dv.astype(dv_ref.dtype)
        db_ref[0, 0, i:i + 1, :] = db
        dg_ref[0, 0, i:i + 1, :] = dg
    ds_scr[:] = ds


def _specs(group, beta, dk, dv, reverse):
    """Block specs of (q or k, v, a chunk's T, a per-token scalar, a saved
    state) for ``beta [BH, blocks, chunks a block, C]``; with ``reverse``
    the block axis is walked last to first."""
    nblk, chunks = beta.shape[1:3]
    blk = (lambda c: nblk - 1 - c) if reverse else (lambda c: c)
    tokens = chunks * CHUNK
    rows = lambda d, of=lambda b: b: pl.BlockSpec(  # noqa: E731
        (1, tokens, d), lambda b, c: (of(b), blk(c), 0), memory_space=pltpu.VMEM
    )
    per_block = lambda *shape: pl.BlockSpec(  # noqa: E731
        (1, 1) + shape, lambda b, c: (b, blk(c), 0, 0), memory_space=pltpu.VMEM
    )
    return (
        rows(dk, lambda b: b // group), rows(dv), rows(CHUNK),
        per_block(chunks, CHUNK), per_block(dk, dv),
    )


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _forward_call(specs, operands, dk, dv, beta, gcum, save, interpret, unit=None):
    """The forward kernel on q, k, v as ``operands`` under ``specs`` (three
    arrays head-major, or the one ``qkv`` array three times, then ``unit``
    says how its q and k rows are normalised): ``o [B Hv, T, dv]``; with
    ``save`` also ``(states, T, u)`` for the backward."""
    bh, nblk, chunks = beta.shape[:3]
    tp, dtype = nblk * chunks * CHUNK, operands[2].dtype
    _, v_spec, t_spec, tok_spec, s_spec = _specs(1, beta, dk, dv, False)
    sds = lambda shape, dt: _sds(shape, dt, operands[2])  # noqa: E731
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, unit=unit),
        name="gated_delta_fwd",
        grid=(bh, nblk),
        in_specs=[*specs, tok_spec, tok_spec],
        out_specs=[v_spec] + [s_spec, t_spec, v_spec] * save,
        out_shape=[sds((bh, tp, dv), dtype)] + [
            sds((bh, nblk, dk, dv), _F32), sds((bh, tp, CHUNK), dtype), sds((bh, tp, dv), dtype),
        ] * save,
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(*operands, gcum, beta)
    return (outs[0], outs[1:]) if save else outs[0]


@kernel_entry("gated_delta_fwd", "save", "interpret")
def _forward(q, k, v, beta, gcum, save, interpret):
    dk, dv = q.shape[-1], v.shape[-1]
    qk_spec, v_spec = _specs(v.shape[0] // q.shape[0], beta, dk, dv, False)[:2]
    return _forward_call(
        (qk_spec, qk_spec, v_spec), (q, k, v), dk, dv, beta, gcum, save, interpret
    )


@kernel_entry("gated_delta_fwd_state", "interpret")
def _forward_state(q, k, v, beta, gcum, s0, interpret):
    """``(o, final state)`` from the state ``s0 [BH, Dk, Dv]`` fp32."""
    bh, _, dv = v.shape
    dk = q.shape[-1]
    nblk = beta.shape[1]
    qk_spec, v_spec, _, tok_spec, _ = _specs(bh // q.shape[0], beta, dk, dv, False)
    s_spec = pl.BlockSpec((1, dk, dv), lambda b, c: (b, 0, 0), memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _fwd_state_kernel,
        name="gated_delta_fwd_state",
        grid=(bh, nblk),
        in_specs=[qk_spec, qk_spec, v_spec, tok_spec, tok_spec, s_spec],
        out_specs=[v_spec, s_spec],
        out_shape=[_sds(v.shape, v.dtype, v), _sds(s0.shape, _F32, v)],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(q, k, v, gcum, beta, s0)


@kernel_entry("gated_delta_bwd", "interpret")
def _backward(q, k, v, beta, gcum, do, saved, interpret):
    bh, tp, dv = v.shape
    dk = q.shape[-1]
    nblk = beta.shape[1]
    qk_spec, v_spec, t_spec, tok_spec, s_spec = _specs(bh // q.shape[0], beta, dk, dv, True)
    dqk_spec = _specs(1, beta, dk, dv, True)[0]  # dq, dk: one a value head
    sds = lambda shape, dtype: _sds(shape, dtype, v)  # noqa: E731
    return pl.pallas_call(
        _bwd_kernel,
        name="gated_delta_bwd",
        grid=(bh, nblk),
        in_specs=[qk_spec, qk_spec, v_spec, tok_spec, tok_spec, v_spec,
                  s_spec, t_spec, v_spec],
        out_specs=[dqk_spec, dqk_spec, v_spec, tok_spec, tok_spec],
        out_shape=[
            sds((bh, tp, dk), q.dtype), sds((bh, tp, dk), k.dtype),
            sds(v.shape, v.dtype), sds(beta.shape, _F32), sds(beta.shape, _F32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dk, dv), _F32), pltpu.VMEM((beta.shape[2], dk, dv), _F32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(q, k, v, gcum, beta, do, *saved)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, beta, gcum, interpret):
    return _forward(q, k, v, beta, gcum, False, interpret)


def _rule_fwd(q, k, v, beta, gcum, interpret):
    out, saved = _forward(q, k, v, beta, gcum, True, interpret)
    return out, (q, k, v, beta, gcum, saved)


def _rule_bwd(interpret, res, do):
    q, k, v, beta, gcum, saved = res
    dq, dk, dv, dg, db = _backward(
        q, k, v, beta, gcum, do.astype(v.dtype), saved, interpret
    )
    group = v.shape[0] // q.shape[0]
    if group > 1:  # a key head's gradient: the sum over the value heads it serves
        dq, dk = (
            y.reshape(q.shape[0], group, *y.shape[1:]).astype(_F32).sum(1).astype(y.dtype)
            for y in (dq, dk)
        )
    return dq, dk, dv, db, dg


_rule.defvjp(_rule_fwd, _rule_bwd)


# -- q, k, v read where the short conv left them ------------------------------


def _bwd_qkv_kernel(
    q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, s_ref, t_ref, u_ref,
    dx_ref, dg_ref, db_ref, ds_scr, sin_scr, dx_scr, sems, *, eps, scale, hk,
):
    """A key head's block: its ``group`` value heads walked side by side
    (their chains are independent: the MXU takes one's products while the
    other's wait), ``dq`` and ``dk`` summed over them in fp32, passed
    through the norm's VJP and rounded once. ``dx_ref`` is the whole
    cotangent ``[B, T, C]`` in HBM: the block's column blocks (``dq``,
    ``dk``, each head's ``dv``) are formed in ``dx_scr`` and copied out
    while the next block's states are replayed."""
    group, tokens, dv = do_ref.shape
    dk, width = q_ref.shape[-1], dx_scr.shape[-1]
    qp, vp = dk // width, dv // width  # column blocks of dq (and dk), of a head's dv
    n = tokens // CHUNK
    toks = [slice(i * CHUNK, (i + 1) * CHUNK) for i in range(n)]
    row, step, last = pl.program_id(0), pl.program_id(1), pl.num_programs(1) - 1
    key = row % hk
    first = (key * qp, (hk + key) * qp, 2 * hk * qp + key * group * vp)  # of dq, dk, dv

    def copies():  # this step's column blocks, scratch -> their place in HBM
        rows = pl.ds((last - step) * tokens, tokens)
        for p in range(2 * qp + group * vp):
            col = first[min(p // qp, 2)] + (p % qp if p < 2 * qp else p - 2 * qp)
            yield pltpu.make_async_copy(
                dx_scr.at[p], dx_ref.at[row // hk, rows, pl.ds(col * width, width)], sems.at[p]
            )

    def wait():
        for copy in copies():  # the same sizes: what a wait counts
            copy.wait()

    def put(first, tok, y):  # fp32 [C, D] -> D / width column blocks
        for p in range(y.shape[-1] // width):
            dx_scr[first + p, tok, :] = y[:, p * width:(p + 1) * width].astype(dx_scr.dtype)

    @pl.when(step == 0)
    def _():
        ds_scr[:] = jnp.zeros_like(ds_scr)

    heads = range(group)
    ss = [s_ref[h, 0] for h in heads]
    for i, tok in enumerate(toks):  # each chunk's incoming states, replayed
        kf = _unit(k_ref[0, tok, :], eps)[0].astype(_F32)
        for h in heads:
            sin_scr[h, i] = ss[h]
            x = _decays(g_ref[h, 0, i:i + 1, :], b_ref[h, 0, i:i + 1, :])
            u = u_ref[h, tok, :]
            ss[h] = ss[h] * x["egl"] + _dot((kf * x["rho"]).astype(u.dtype), u, _TN)
    pl.when(step > 0)(wait)  # the block before this one has left dx_scr
    dss = [ds_scr[h] for h in heads]
    for i, tok in reversed(list(enumerate(toks))):
        q, qn, qr = _unit(q_ref[0, tok, :], eps, scale)
        k, kn, kr = _unit(k_ref[0, tok, :], eps)
        dq = dk_ = 0.0
        for h in heads:
            dq_h, dk_h, dv_h, db, dg, dss[h] = _chunk_bwd(
                q, k, v_ref[0, tok, h * dv:(h + 1) * dv], g_ref[h, 0, i:i + 1, :],
                b_ref[h, 0, i:i + 1, :], do_ref[h, tok, :], t_ref[h, tok, :],
                u_ref[h, tok, :], sin_scr[h, i], dss[h],
            )
            dq, dk_ = dq + dq_h, dk_ + dk_h
            put(2 * qp + h * vp, tok, dv_h)
            db_ref[h, 0, i:i + 1, :] = db
            dg_ref[h, 0, i:i + 1, :] = dg
        put(0, tok, _unit_vjp(dq * scale, qn, qr))
        put(qp, tok, _unit_vjp(dk_, kn, kr))
    for h in heads:
        ds_scr[h] = dss[h]
    for copy in copies():
        copy.start()
    pl.when(step == last)(wait)  # a row's last block: nothing outlives the row


def _qkv_specs(dims, beta, reverse, each):
    """Block specs of q, k and v on ``qkv [B, T, C]`` for the grid ``(B x
    heads, blocks)`` with ``each`` value heads a grid step: 1 (a value head
    a step, q and k read at its key head's columns) or ``Hv / Hk`` (a key
    head a step, its value heads' columns side by side)."""
    hk, hv, dk, dv = dims
    nblk, chunks = beta.shape[1:3]
    blk = (lambda c: nblk - 1 - c) if reverse else (lambda c: c)
    per = hv // each  # grid rows a batch row
    key = lambda i: i % per // (per // hk)  # noqa: E731
    cols = lambda d, of: pl.BlockSpec(  # noqa: E731
        (1, chunks * CHUNK, d), lambda i, c: (i // per, blk(c), of(i)), memory_space=pltpu.VMEM
    )
    return (
        cols(dk, key), cols(dk, lambda i: hk + key(i)),
        cols(each * dv, lambda i: 2 * hk * dk // (each * dv) + i % per),
    )


_VMEM_BYTES = 64 << 20  # a key head's step holds its value heads' operands


@kernel_entry("gated_delta_fwd", "dims", "eps", "save", "interpret")
def _forward_qkv(qkv, beta, gcum, dims, eps, save, interpret):
    _, _, dk, dv = dims
    return _forward_call(
        _qkv_specs(dims, beta, False, 1), (qkv,) * 3, dk, dv, beta, gcum, save, interpret,
        unit=(eps, dk ** -0.5),
    )


@kernel_entry("gated_delta_bwd", "dims", "eps", "interpret")
def _backward_qkv(qkv, beta, gcum, do, saved, dims, eps, interpret):
    """``(d qkv [B, T, C], dG, dbeta)``: the grid ``(B x key heads, blocks
    last to first)``; the cotangent stays in HBM and the kernel copies its
    column blocks there."""
    hk, hv, dk, dv = dims
    group = hv // hk
    nblk, chunks = beta.shape[1:3]
    tokens = chunks * CHUNK
    width = math.gcd(dk, dv)
    pieces = (2 * dk + group * dv) // width
    heads = lambda *shape: pl.BlockSpec(  # noqa: E731
        (group,) + shape, lambda i, c: (i, nblk - 1 - c) + (0,) * (len(shape) - 1),
        memory_space=pltpu.VMEM,
    )
    rows = lambda d: heads(tokens, d)  # noqa: E731
    per_block = lambda *shape: heads(1, *shape)  # noqa: E731
    sds = lambda shape, dtype: _sds(shape, dtype, qkv)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_qkv_kernel, eps=eps, scale=dk ** -0.5, hk=hk),
        name="gated_delta_bwd",
        grid=(beta.shape[0] // group, nblk),
        in_specs=[
            *_qkv_specs(dims, beta, True, group), per_block(chunks, CHUNK),
            per_block(chunks, CHUNK), rows(dv), per_block(dk, dv), rows(CHUNK), rows(dv),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY), per_block(chunks, CHUNK), per_block(chunks, CHUNK),
        ],
        out_shape=[sds(qkv.shape, qkv.dtype), sds(beta.shape, _F32), sds(beta.shape, _F32)],
        scratch_shapes=[
            pltpu.VMEM((group, dk, dv), _F32), pltpu.VMEM((group, chunks, dk, dv), _F32),
            pltpu.VMEM((pieces, tokens, width), qkv.dtype), pltpu.SemaphoreType.DMA((pieces,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_BYTES
        ),
        interpret=interpret,
    )(qkv, qkv, qkv, gcum, beta, do, *saved)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rule_qkv(qkv, beta, gcum, dims, eps, interpret):
    return _forward_qkv(qkv, beta, gcum, dims, eps, False, interpret)


def _rule_qkv_fwd(qkv, beta, gcum, dims, eps, interpret):
    out, saved = _forward_qkv(qkv, beta, gcum, dims, eps, True, interpret)
    return out, (qkv, beta, gcum, saved)


def _rule_qkv_bwd(dims, eps, interpret, res, do):
    qkv, beta, gcum, saved = res
    dx, dg, db = _backward_qkv(
        qkv, beta, gcum, do.astype(qkv.dtype), saved, dims, eps, interpret
    )
    return dx, db, dg


_rule_qkv.defvjp(_rule_qkv_fwd, _rule_qkv_bwd)


def _tiling(t: int):
    """``(chunks a block, rows of zero padding, blocks)`` for ``t`` tokens:
    a short T is one smaller block."""
    chunks = min(BLOCK_CHUNKS, -(-t // CHUNK))
    pad = (-t) % (chunks * CHUNK)
    return chunks, pad, (t + pad) // (chunks * CHUNK)


def _per_chunk(x, t):
    """A per-token scalar ``[..., T]`` as fp32 ``[BH, blocks, chunks, C]``."""
    chunks, pad, nblk = _tiling(t)
    x = x.astype(_F32).reshape((-1, t))
    x = jnp.pad(x, [(0, 0), (0, pad)]) if pad else x
    return x.reshape(-1, nblk, chunks, CHUNK)


def gated_delta_rule_pallas(
    q: Array, k: Array, v: Array, beta: Array, g: Array, *, interpret: bool = False,
    initial_state=None, return_state: bool = False,
):
    """The gated delta rule on q, k ``[..., Hk, T, Dk]``, v ``[..., Hv, T,
    Dv]``, beta, g ``[..., Hv, T]`` with ``Hv`` a multiple of ``Hk`` (value
    head ``h`` reads key head ``h // (Hv / Hk)``). Output ``[..., Hv, T,
    Dv]`` in v's dtype, differentiable in all five. With ``initial_state``
    ``[..., Hv, Dk, Dv]`` or ``return_state`` (-> ``(out, final state)``,
    fp32) the forward-only kernel that carries the state runs, at any
    width."""
    lead, (hv, t, dv) = v.shape[:-3], v.shape[-3:]
    hk, dk = q.shape[-3], q.shape[-1]
    assert q.shape == k.shape and q.shape[:-3] == lead and hv % hk == 0, (q.shape, k.shape, v.shape)
    assert beta.shape == g.shape == lead + (hv, t), (beta.shape, g.shape)
    pad = _tiling(t)[1]
    stateful = initial_state is not None or return_state
    # the state-carrying kernel takes every width, as whole lane tiles
    wk, wv = ((-dk) % 128, (-dv) % 128) if stateful else (0, 0)

    def flat(x, d, wide):  # [B * H, T (padded), D (padded)]
        x = x.reshape((-1, t, d))
        return jnp.pad(x, [(0, 0), (0, pad), (0, wide)]) if pad or wide else x

    args = (
        flat(q.astype(v.dtype), dk, wk), flat(k.astype(v.dtype), dk, wk), flat(v, dv, wv),
        _per_chunk(beta, t), jnp.cumsum(_per_chunk(g, t), axis=-1),
    )
    if not stateful:
        return _rule(*args, interpret)[:, :t].reshape(lead + (hv, t, dv))
    s0 = (
        jnp.zeros((args[2].shape[0], dk, dv), _F32) if initial_state is None
        else initial_state.astype(_F32).reshape((-1, dk, dv))
    )
    if wk or wv:
        s0 = jnp.pad(s0, [(0, 0), (0, wk), (0, wv)])
    out, s = _forward_state(*args, s0, interpret)
    out = out[:, :t, :dv].reshape(lead + (hv, t, dv))
    return (out, s[:, :dk, :dv].reshape(lead + (hv, dk, dv))) if return_state else out


def gated_delta_qkv_pallas(
    qkv: Array, beta: Array, g: Array, *, key_heads: int, key_dim: int, value_dim: int,
    eps: float, interpret: bool = False,
):
    """``ops/gated_delta.py::gated_delta_qkv`` as the kernels above: the
    short conv's output ``qkv [..., T, C]`` (columns ``[q | k | v]``) read
    as it lies, beta, g ``[..., Hv, T]`` -> ``o [..., Hv, T, Dv]`` head-major
    in qkv's dtype; differentiable in all three, the cotangent of ``qkv``
    written in its layout. For heads that ``reads_qkv`` takes."""
    lead, (t, c) = qkv.shape[:-2], qkv.shape[-2:]
    hk, dk, dv = key_heads, key_dim, value_dim
    hv = (c - 2 * hk * dk) // dv
    assert c == 2 * hk * dk + hv * dv and reads_qkv(hk, hv, dk, dv), (c, hk, hv, dk, dv)
    assert beta.shape == g.shape == lead + (hv, t), (beta.shape, g.shape)
    pad = _tiling(t)[1]
    x = qkv.reshape((-1, t, c))
    if pad:  # zero rows: k = v = 0 under the norm too, the state passes through
        x = jnp.pad(x, [(0, 0), (0, pad), (0, 0)])
    o = _rule_qkv(
        x, _per_chunk(beta, t), jnp.cumsum(_per_chunk(g, t), axis=-1),
        (hk, hv, dk, dv), eps, interpret,
    )
    return o[:, :t].reshape(lead + (hv, t, dv))


__all__ = [
    "BLOCK_CHUNKS", "CHUNK", "gated_delta_qkv_pallas", "gated_delta_rule_pallas",
    "reads_qkv", "supports",
]
