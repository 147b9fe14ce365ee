"""Pallas TPU kernels of the ``indexed`` layers' prompt pieces
(``models/mixers/indexed.py``): the indexer's scores and the attention under
the selection's mask, neither of which leaves a ``[heads, rows, keys]`` fp32
intermediate in HBM as the XLA forms do.

``index_scores``: ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` a (query
tile, key tile) a grid step, the ``IH`` heads' products accumulated in
registers: HBM sees the operands and ``I`` only.

``masked_attention``: flash attention (online softmax, fp32 accumulators in
VMEM) of a group of ``G`` query heads over ONE KV head's rows of a cache laid
out ``[S, KV Dh]`` (the KV head is a 128-lane block of a row: no transpose),
under an int8 mask ``[P, S]`` that all heads share; grid (batch, KV head,
query tile, key tile), the key axis innermost. A query row whose mask is
empty gives 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas import kernel_entry

Array = jax.Array

_NEG = -1e30
_NT = (((1,), (1,)), ((), ()))  # a @ b^T


def _tile(n: int, most: int, least: int = 8) -> int:
    """The largest power-of-two tile <= ``most`` that divides ``n`` (``n``
    itself where none >= ``least`` does)."""
    t = most
    while t >= least:
        if n % t == 0:
            return t
        t //= 2
    return n


def _score_kernel(q_ref, w_ref, k_ref, o_ref, *, heads: int):
    k = k_ref[0]  # [tk, ID]
    acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
    for j in range(heads):
        s = jax.lax.dot_general(q_ref[0, j], k, _NT, preferred_element_type=jnp.float32)
        acc = acc + w_ref[0, :, j:j + 1] * jnp.maximum(s, 0.0)
    o_ref[0] = acc


@kernel_entry("index_scores", "interpret")
def index_scores(qi: Array, w: Array, ki: Array, *, interpret: bool = False) -> Array:
    """qi ``[B, P, IH, ID]``, w ``[B, P, IH]`` fp32, ki ``[B, S, ID]`` -> ``I``
    ``[B, P, S]`` fp32 (``mixers/indexed.py::index_scores``)."""
    b, p, ih, idim = qi.shape
    s = ki.shape[1]
    tq, tk = _tile(p, 256), _tile(s, 512, 128)
    q = jnp.moveaxis(qi, 2, 1)  # [B, IH, P, ID]
    return pl.pallas_call(
        functools.partial(_score_kernel, heads=ih),
        grid=(b, p // tq, s // tk),
        in_specs=[
            pl.BlockSpec((1, ih, tq, idim), lambda n, i, j: (n, 0, i, 0)),
            pl.BlockSpec((1, tq, ih), lambda n, i, j: (n, i, 0)),
            pl.BlockSpec((1, tk, idim), lambda n, i, j: (n, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, tq, tk), lambda n, i, j: (n, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, p, s), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="index_scores",
    )(q, w.astype(jnp.float32), ki.astype(qi.dtype))


def _attend_kernel(q_ref, k_ref, v_ref, keep_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, group: int, scale: float, nk: int):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, _NEG, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    keep = keep_ref[0] != 0  # [tq, tk]
    k, v = k_ref[0], v_ref[0]  # [tk, Dh]

    def head(g, carry):
        s = jax.lax.dot_general(q_ref[0, 0, g], k, _NT, preferred_element_type=jnp.float32)
        s = jnp.where(keep, s * scale, _NEG)
        m_prev = m_scr[g]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[g] = alpha * l_scr[g] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[g] = alpha * acc_scr[g] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[g] = m_new
        return carry

    jax.lax.fori_loop(0, group, head, 0)

    @pl.when(j == nk - 1)
    def _():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@kernel_entry("indexed_attention", "interpret")
def masked_attention(q: Array, k_rows: Array, v_rows: Array, keep: Array,
                     *, interpret: bool = False) -> Array:
    """q ``[B, KV, G, P, Dh]``, caches ``[B, S, KV Dh]``, ``keep`` int8 ``[B,
    P, S]`` (nonzero: query row ``t`` attends to cache row ``s``) -> ``[B, KV,
    G, P, Dh]`` in q's dtype, scores scaled by ``Dh^-1/2``."""
    b, kvh, g, p, d = q.shape
    s = k_rows.shape[1]
    tq, tk = _tile(p, 256), _tile(s, 512, 128)
    nk = s // tk
    return pl.pallas_call(
        functools.partial(_attend_kernel, group=g, scale=d ** -0.5, nk=nk),
        grid=(b, kvh, p // tq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, tq, d), lambda n, h, i, j: (n, h, 0, i, 0)),
            pl.BlockSpec((1, tk, d), lambda n, h, i, j: (n, j, h)),
            pl.BlockSpec((1, tk, d), lambda n, h, i, j: (n, j, h)),
            pl.BlockSpec((1, tq, tk), lambda n, h, i, j: (n, i, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, tq, d), lambda n, h, i, j: (n, h, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, tq, 1), jnp.float32),
            pltpu.VMEM((g, tq, 1), jnp.float32),
            pltpu.VMEM((g, tq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="indexed_attention",
    )(q, k_rows.astype(q.dtype), v_rows.astype(q.dtype), keep)


__all__ = ["index_scores", "masked_attention"]
