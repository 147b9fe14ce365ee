"""Decode attention over a slot's LIVE cache rows: one query a sequence
against the first ``lengths[b]`` rows of its reserved KV cache, for the
sequences a row list names, and nothing at all for the others.

The slot-multiplexed decode programs (generate.py) reserve ``max_seq_len``
cache rows a slot. XLA's form of the step (``ops/softmax_attention.py::
cached_attention``) multiplies and reduces over the whole reservation and
masks what lies past the position: at a third of the reservation live it
streams three times the bytes that hold a token (PERF.md, PR 36).

This kernel walks the row list of ``decode_state.live_rows`` as the two
state kernels do: the grid is listed rows x KV blocks, and both the list
and the per-row lengths are scalar-prefetched, so the K and V index maps
take the row from the list and CLAMP the block index to the row's last
live block. A block past it repeats the index before it, which the
pipeline does not fetch again, and its step computes nothing
(``pl.when``); an unlisted row is never visited: its output is the zero
row and its log-sum-exp ``-1e30`` the caller handed in (both aliased
input to output), finite and the same on every replay.

Mathematics per listed row and head, an online softmax in fp32 scratch::

    s = (q * Dh^-1/2) . K[:length];  out = softmax(s) V[:length]
    lse = log sum exp s

with the last live block masked at ``position < length``, V too (a dead
position may hold anything: 0 x NaN is NaN). A row of length 0 comes out
as ``out = 0, lse = -1e30``: merged by log-sum-exps with another key set
it weighs exactly nothing.

Both products run on the MXU with every product and sum in fp32, and no
operand is rounded that the XLA form does not round: the cache's bf16
values are exact, and the fp32 side (q, then p) is SPLIT into three bf16
terms that sum to it exactly (8 + 8 + 8 mantissa bits), stacked as three
rows of one matmul. A bf16 x bf16 product is exact in fp32 and the MXU
accumulates in fp32, so this is the fp32 dot product up to summation
order, at one pass over the cache block instead of the six an
fp32 x fp32 matmul takes. A cache in another dtype takes that matmul
(``Precision.HIGHEST``).

reference: none (the reference has no cache; checkout never mounted,
SURVEY.md s0).
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas import kernel_entry
from orion_tpu.ops.softmax_attention import _NEG

Array = jax.Array

# KV rows a grid step, all heads of the row at once: K and V blocks of
# [H, 256, Dh] (2 MB each in bf16 at 30 heads x 128), double-buffered. On
# the v5e at 64 x 30 x 4,096 x 128 and the served cell's lengths, alone:
# 2.32 ms a call at 256 rows, 2.55 at 512, 3.04 at 1,024 (a row's last
# block is read whole) against 7.02 for the XLA form (PR 36)
BLOCK_KV = 256
# the split fp32 operand's rows, padded to one bf16 tile of sublanes
_SPLIT_ROWS = 16
_VMEM_BYTES = 64 << 20


def kv_block(cap: int) -> int:
    """Rows of a KV block for a cache of ``cap`` rows: :data:`BLOCK_KV`
    where it divides ``cap``, the whole cache where that is smaller."""
    if cap <= BLOCK_KV:
        return cap
    bk = math.gcd(cap, BLOCK_KV)
    if bk % 16:
        raise ValueError(
            f"a cache of {cap} rows does not tile: rows past {BLOCK_KV} "
            "must come in multiples of 16"
        )
    return bk


def rows_read(length: int, cap: int) -> int:
    """Cache rows the kernel streams for a listed row of ``length`` live
    rows: its live blocks, and one block at least (the pipeline fetches
    the block an index map names whether or not the step computes)."""
    bk = kv_block(cap)
    return min(cap, max(1, -(-length // bk)) * bk)


def _rows_dot(a: Array, b: Array, dims) -> Array:
    """``a`` fp32 [H, 1, X] against a cache block ``b`` [H, ., .] as a
    per-head matmul contracting ``dims``, products and sums in fp32 (the
    module docstring's split) -> fp32 [H, 1, N]."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    dims = (dims, ((0,), (0,)))
    if b.dtype != bf16:
        return jax.lax.dot_general(
            a, b.astype(f32), dims, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=f32,
        )
    hi = a.astype(bf16).astype(f32)
    rest = a - hi
    mid = rest.astype(bf16).astype(f32)
    low = rest - mid
    h, _, x = a.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (h, _SPLIT_ROWS, x), 1)
    parts = jnp.where(
        row == 0, hi, jnp.where(row == 1, mid, jnp.where(row == 2, low, 0.0))
    )
    out = jax.lax.dot_general(
        parts.astype(bf16), b, dims, preferred_element_type=f32
    )
    return jnp.sum(out, axis=1, keepdims=True)  # rows 3.. are zero


# query rows a KV head of a GROUPED cache: the group's heads, zero-padded
# to one bf16 tile of sublanes, so that the split's three terms are three
# whole tiles of one matmul
_GROUP_ROWS = 16


def _group_rows_dot(a: Array, b: Array, dims) -> Array:
    """:func:`_rows_dot` for ``a`` fp32 [KV, R, X], R query rows (a group's
    heads) to each KV head's cache block -> fp32 [KV, R, N]."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    dims = (dims, ((0,), (0,)))
    if b.dtype != bf16:
        return jax.lax.dot_general(
            a, b.astype(f32), dims, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=f32,
        )
    hi = a.astype(bf16)
    rest = a - hi.astype(f32)
    mid = rest.astype(bf16)
    low = (rest - mid.astype(f32)).astype(bf16)
    r = a.shape[1]
    out = jax.lax.dot_general(
        jnp.concatenate([hi, mid, low], axis=1), b, dims,
        preferred_element_type=f32,
    )
    return out[:, :r] + out[:, r:2 * r] + out[:, 2 * r:]


_QK = ((2,), (2,))  # [H, 1, Dh] x [H, bk, Dh] -> [H, 1, bk]
_PV = ((2,), (1,))  # [H, 1, bk] x [H, bk, Dh] -> [H, 1, Dh]


def _kernel(dot, bk, nblk, idx_ref, len_ref, q_ref, k_ref, v_ref, o_in, lse_in,
            o_ref, lse_ref, m_scr, l_scr, acc_scr):
    """``dot``: :func:`_rows_dot` (a query row a head) or
    :func:`_group_rows_dot` (a group's rows a KV head)."""
    del o_in, lse_in  # aliased onto the outputs: what an unlisted row keeps
    i, j = pl.program_id(0), pl.program_id(1)
    length = len_ref[idx_ref[i]]

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def block(partial):
        s = dot(q_ref[0], k_ref[0], _QK)  # [H, 1, bk]
        v = v_ref[0]
        if partial:
            at = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            s = jnp.where(at < length, s, _NEG)
            at = j * bk + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
            v = jnp.where(at < length, v, jnp.zeros_like(v))
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + dot(p, v, _PV)
        m_scr[...] = m_new

    # a block wholly below the length needs no mask; the row's last live
    # block does, unless the length ends it
    pl.when((j + 1) * bk <= length)(lambda: block(False))
    pl.when((j * bk < length) & (length < (j + 1) * bk))(lambda: block(True))

    @pl.when(j == nblk - 1)
    def _():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)  # no live row: out 0, lse _NEG
        o_ref[0] = acc_scr[...] / safe
        lse_ref[0] = m_scr[...] + jnp.log(safe)


@kernel_entry("cache_attention", "interpret")
def cache_attention(
    q: Array, k_cache: Array, v_cache: Array, lengths: Array,
    rows: Tuple[Array, Array], *, interpret: bool = False,
) -> Tuple[Array, Array]:
    """q ``[B, H, Dh]``; caches ``[B, KV, cap, Dh]`` (one dtype; ``H / KV``
    query heads to each KV head, ``KV = H`` a cache a query head); lengths
    ``[B]`` int32, the live rows of each sequence's cache; rows =
    ``decode_state.live_rows`` of the row mask. Returns (out ``[B, H, Dh]``,
    lse ``[B, H]``), both fp32: for a listed row the softmax of its scaled
    scores over cache rows ``[0, length)`` applied to V, and their
    log-sum-exp; ``(0, -1e30)`` for a listed row of length 0 and for every
    unlisted row, whose cache is never read."""
    idx, count = rows
    b, h, cap, d = k_cache.shape
    group = q.shape[1] // h
    shapes = (q.shape, v_cache.shape, lengths.shape, idx.shape)
    if shapes != ((b, group * h, d), k_cache.shape, (b,), (b,)) or group > _GROUP_ROWS:
        raise ValueError(f"operands do not fit K {k_cache.shape}: {shapes}")
    if k_cache.dtype != v_cache.dtype:
        raise ValueError(f"one cache dtype: {k_cache.dtype}/{v_cache.dtype}")
    bk = kv_block(cap)
    nblk = cap // bk
    f32 = jnp.float32
    # every block's last two dims are whole dims of its array: a head's
    # query, output and statistics sit one row a head, [B, H, 1, .]; a
    # group's sit _GROUP_ROWS rows a KV head, the rows past the group zero
    # queries whose results are dropped
    qf = (q.astype(f32) * d ** -0.5).reshape(b, h, group, d)
    r = 1 if group == 1 else _GROUP_ROWS
    qf = jnp.pad(qf, ((0, 0), (0, 0), (0, r - group), (0, 0)))

    def row(i, j, idx, lens):
        return (idx[i], 0, 0, 0)

    def kv(i, j, idx, lens):
        r = idx[i]
        last = jnp.maximum((lens[r] + bk - 1) // bk - 1, 0)
        return (r, 0, jnp.minimum(j, last), 0)

    vec = pl.BlockSpec((1, h, r, d), row)
    one = pl.BlockSpec((1, h, r, 1), row)
    blk = pl.BlockSpec((1, h, bk, d), kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(count[0], nblk),
        in_specs=[vec, blk, blk, vec, one],
        out_specs=[vec, one],
        scratch_shapes=[
            pltpu.VMEM((h, r, 1), f32),
            pltpu.VMEM((h, r, 1), f32),
            pltpu.VMEM((h, r, d), f32),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(
            _kernel, _rows_dot if group == 1 else _group_rows_dot, bk, nblk
        ),
        name="cache_attention",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, r, d), f32),
            jax.ShapeDtypeStruct((b, h, r, 1), f32),
        ],
        # operand numbering counts the two scalar-prefetch lists: the
        # unlisted rows' output and log-sum-exp are operands 5 and 6
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(
        idx, lengths.astype(jnp.int32), qf, k_cache, v_cache,
        jnp.zeros((b, h, r, d), f32), jnp.full((b, h, r, 1), _NEG, f32),
    )
    if group == 1:
        return out[:, :, 0, :], lse[:, :, 0, 0]
    return (
        out[:, :, :group].reshape(b, h * group, d),
        lse[:, :, :group, 0].reshape(b, h * group),
    )


# ---------------------------------------------------------------------------
# Decode attention over a LIST of cache blocks per (listed row, KV head): the
# block-sparse layers' query group against the blocks their selector chose.
# ---------------------------------------------------------------------------


def _group_dot(a: Array, b: Array, dims) -> Array:
    """``a`` fp32 [G, X] against a cache block ``b`` [N, X'] contracting
    ``dims``, products and sums in fp32: the module docstring's split of
    the fp32 side into three bf16 terms, stacked as 3 G rows of one matmul."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    dims = (dims, ((), ()))
    if b.dtype != bf16:
        return jax.lax.dot_general(
            a, b.astype(f32), dims, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=f32,
        )
    hi = a.astype(bf16)
    rest = a - hi.astype(f32)
    mid = rest.astype(bf16)
    low = (rest - mid.astype(f32)).astype(bf16)
    g = a.shape[0]
    out = jax.lax.dot_general(
        jnp.concatenate([hi, mid, low], axis=0), b, dims,
        preferred_element_type=f32,
    )
    return out[:g] + out[g:2 * g] + out[2 * g:]


def _block_kernel(bs, width, kvh, idx_ref, len_ref, cnt_ref, blk_ref,
                  q_ref, k_ref, v_ref, o_in, lse_in, o_ref, lse_ref,
                  m_scr, l_scr, acc_scr):
    del o_in, lse_in  # aliased onto the outputs: what an unlisted row keeps
    i, g, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    r = idx_ref[i]
    length = len_ref[r]
    n = cnt_ref[r * kvh + g]

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j < n)
    def _():
        first = blk_ref[(r * kvh + g) * width + j] * bs
        s = _group_dot(q_ref[0, 0], k_ref[0, 0], ((1,), (1,)))  # [G, bs]
        at = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(at < length, s, _NEG)
        v = v_ref[0, 0]
        at = first + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(at < length, v, jnp.zeros_like(v))
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a block wholly past the length leaves the running maximum at
        # _NEG: its weights are then exp(0), so zero them outright
        p = jnp.where(s > 0.5 * _NEG, jnp.exp(s - m_new), 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + _group_dot(p, v, ((1,), (0,)))
        m_scr[...] = m_new

    @pl.when(j == width - 1)
    def _():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)  # no live key: out 0, lse _NEG
        o_ref[0, 0] = acc_scr[...] / safe
        lse_ref[0, 0] = m_scr[...] + jnp.log(safe)


@kernel_entry("block_attention", "block", "interpret")
def block_attention(
    q: Array, k_cache: Array, v_cache: Array, lengths: Array, blocks: Array,
    counts: Array, rows: Tuple[Array, Array], *, block: int,
    interpret: bool = False,
) -> Tuple[Array, Array]:
    """q ``[B, KV, G, Dh]`` (G query heads a KV head); caches ``[B, KV, cap,
    Dh]``; ``lengths`` [B] int32, each sequence's live cache rows;
    ``blocks`` ``[B, KV, L]`` int32, for each (sequence, KV head) the cache
    blocks of ``block`` rows to attend to, of which the first ``counts``
    ``[B, KV]`` count (distinct, any order); rows = ``decode_state.
    live_rows`` of the row mask. Returns (out ``[B, KV, G, Dh]``, lse ``[B,
    KV, G]``), both fp32: for a listed row the softmax of its scaled scores
    over the rows ``< length`` of its listed blocks applied to V, and their
    log-sum-exp; ``(0, -1e30)`` where that key set is empty and for every
    unlisted row, whose cache is never read. One grid step a listed block:
    the K and V index maps take the block from the scalar-prefetched list,
    and past the count repeat the last one, which is not fetched again."""
    idx, count = rows
    b, kvh, cap, d = k_cache.shape
    g, width = q.shape[2], blocks.shape[-1]
    shapes = (q.shape, v_cache.shape, lengths.shape, idx.shape, blocks.shape, counts.shape)
    if shapes != ((b, kvh, g, d), k_cache.shape, (b,), (b,), (b, kvh, width), (b, kvh)):
        raise ValueError(f"operands do not fit K {k_cache.shape}: {shapes}")
    if k_cache.dtype != v_cache.dtype:
        raise ValueError(f"one cache dtype: {k_cache.dtype}/{v_cache.dtype}")
    if cap % block:
        raise ValueError(f"a cache of {cap} rows is not whole blocks of {block}")
    f32 = jnp.float32
    qf = q.astype(f32) * d ** -0.5

    def row(i, gg, j, idx, lens, cnt, blk):
        return (idx[i], gg, 0, 0)

    def kv(i, gg, j, idx, lens, cnt, blk):
        r = idx[i]
        at = r * kvh + gg
        last = jnp.maximum(cnt[at] - 1, 0)
        return (r, gg, blk[at * width + jnp.minimum(j, last)], 0)

    vec = pl.BlockSpec((1, 1, g, d), row)
    one = pl.BlockSpec((1, 1, g, 1), row)
    tile = pl.BlockSpec((1, 1, block, d), kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(count[0], kvh, width),
        in_specs=[vec, tile, tile, vec, one],
        out_specs=[vec, one],
        scratch_shapes=[
            pltpu.VMEM((g, 1), f32), pltpu.VMEM((g, 1), f32), pltpu.VMEM((g, d), f32),
        ],
    )
    i32 = jnp.int32
    out, lse = pl.pallas_call(
        functools.partial(_block_kernel, block, width, kvh),
        name="block_attention",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kvh, g, d), f32),
            jax.ShapeDtypeStruct((b, kvh, g, 1), f32),
        ],
        # operand numbering counts the four scalar-prefetch lists: the
        # unlisted rows' output and log-sum-exp are operands 7 and 8
        input_output_aliases={7: 0, 8: 1},
        interpret=interpret,
    )(
        idx, lengths.astype(i32), counts.astype(i32).reshape(-1),
        blocks.astype(i32).reshape(-1), qf, k_cache, v_cache,
        jnp.zeros((b, kvh, g, d), f32), jnp.full((b, kvh, g, 1), _NEG, f32),
    )
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# Decode attention over a LATENT cache: every head's key is the cache row
# ``[c | k_rope]`` and its value the same row's ``c`` (the latent layers'
# absorbed step, models/mixers/latent.py).
# ---------------------------------------------------------------------------

# latent rows a grid step: one [512, 512 + 64] block serves all heads' scores
# and, its first part again, their values
LATENT_BLOCK = 512


def latent_block(cap: int) -> int:
    """:func:`kv_block` at :data:`LATENT_BLOCK` rows."""
    if cap <= LATENT_BLOCK:
        return cap
    bk = math.gcd(cap, LATENT_BLOCK)
    if bk % 16:
        raise ValueError(f"a latent cache of {cap} rows does not tile by 16")
    return bk


def latent_rows_read(length: int, cap: int) -> int:
    """:func:`rows_read` for the latent kernel's blocks."""
    bk = latent_block(cap)
    return min(cap, max(1, -(-length // bk)) * bk)


def _latent_kernel(bk, nblk, idx_ref, len_ref, qt_ref, qr_ref, c_ref, kr_ref,
                   o_in, lse_in, o_ref, lse_ref, m_scr, l_scr, acc_scr):
    del o_in, lse_in  # aliased onto the outputs: what an unlisted row keeps
    i, j = pl.program_id(0), pl.program_id(1)
    length = len_ref[idx_ref[i]]
    f32 = jnp.float32
    nt = (((1,), (1,)), ((), ()))  # [H, X] x [bk, X] -> [H, bk]

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def block(partial):
        c = c_ref[0]  # [bk, kv_rank]: the keys' first part AND the values
        s = jax.lax.dot_general(qt_ref[0], c, nt, preferred_element_type=f32)
        s = s + jax.lax.dot_general(qr_ref[0], kr_ref[0], nt, preferred_element_type=f32)
        if partial:
            at = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(at < length, s, _NEG)
            at = j * bk + jax.lax.broadcasted_iota(jnp.int32, c.shape, 0)
            c = jnp.where(at < length, c, jnp.zeros_like(c))
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p.astype(c.dtype), c, preferred_element_type=f32
        )
        m_scr[...] = m_new

    pl.when((j + 1) * bk <= length)(lambda: block(False))
    pl.when((j * bk < length) & (length < (j + 1) * bk))(lambda: block(True))

    @pl.when(j == nblk - 1)
    def _():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)  # no live row: out 0, lse _NEG
        o_ref[0] = acc_scr[...] / safe
        lse_ref[0] = m_scr[...] + jnp.log(safe)


@kernel_entry("latent_attention", "scale", "interpret")
def latent_attention(
    qt: Array, qr: Array, c_cache: Array, kr_cache: Array, lengths: Array,
    rows: Tuple[Array, Array], *, scale: float, interpret: bool = False,
) -> Tuple[Array, Array]:
    """qt ``[B, H, R]`` (the query with the key up-projection absorbed), qr
    ``[B, H, Dr]`` (its rotary part); caches ``[B, cap, R]`` and ``[B, cap,
    Dr]`` (one dtype); lengths ``[B]`` int32, each sequence's live rows; rows
    = ``decode_state.live_rows`` of the row mask. Returns (u ``[B, H, R]``,
    lse ``[B, H]``), fp32: for a listed row and each head the softmax of
    ``scale (qt . c + qr . k_rope)`` over cache rows ``[0, length)`` applied
    to ``c``, and its log-sum-exp; ``(0, -1e30)`` for a listed row of length
    0 and for every unlisted row, whose cache is never read. All H heads are
    the rows of ONE product a block: at 128 heads the MXU sees a [128, R + Dr]
    x [R + Dr, 512] matmul and a [128, 512] x [512, R] one per 512 latent
    rows, both operands in the cache's dtype (bf16 on the chip: one pass),
    sums in fp32. Each live block is fetched once."""
    idx, count = rows
    b, cap, r = c_cache.shape
    h, dr = qt.shape[1], qr.shape[-1]
    shapes = (qt.shape, qr.shape, kr_cache.shape, lengths.shape, idx.shape)
    if shapes != ((b, h, r), (b, h, dr), (b, cap, dr), (b,), (b,)):
        raise ValueError(f"operands do not fit the latent {c_cache.shape}: {shapes}")
    if c_cache.dtype != kr_cache.dtype:
        raise ValueError(f"one cache dtype: {c_cache.dtype}/{kr_cache.dtype}")
    bk = latent_block(cap)
    nblk = cap // bk
    f32 = jnp.float32
    dt = c_cache.dtype
    qt = (qt.astype(f32) * scale).astype(dt)
    qr = (qr.astype(f32) * scale).astype(dt)

    def row(i, j, idx, lens):
        return (idx[i], 0, 0)

    def kv(i, j, idx, lens):
        r_ = idx[i]
        last = jnp.maximum((lens[r_] + bk - 1) // bk - 1, 0)
        return (r_, jnp.minimum(j, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(count[0], nblk),
        in_specs=[
            pl.BlockSpec((1, h, r), row), pl.BlockSpec((1, h, dr), row),
            pl.BlockSpec((1, bk, r), kv), pl.BlockSpec((1, bk, dr), kv),
            pl.BlockSpec((1, h, r), row), pl.BlockSpec((1, h, 1), row),
        ],
        out_specs=[pl.BlockSpec((1, h, r), row), pl.BlockSpec((1, h, 1), row)],
        scratch_shapes=[
            pltpu.VMEM((h, 1), f32), pltpu.VMEM((h, 1), f32), pltpu.VMEM((h, r), f32),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(_latent_kernel, bk, nblk),
        name="latent_attention",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, r), f32),
            jax.ShapeDtypeStruct((b, h, 1), f32),
        ],
        # operand numbering counts the two scalar-prefetch lists: the
        # unlisted rows' output and log-sum-exp are operands 6 and 7
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(
        idx, lengths.astype(jnp.int32), qt, qr, c_cache, kr_cache,
        jnp.zeros((b, h, r), f32), jnp.full((b, h, 1), _NEG, f32),
    )
    return out, lse[..., 0]


__all__ = [
    "BLOCK_KV", "LATENT_BLOCK", "block_attention", "cache_attention", "kv_block",
    "latent_attention", "latent_block", "latent_rows_read", "rows_read",
]
