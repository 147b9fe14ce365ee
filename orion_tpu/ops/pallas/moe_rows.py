"""Rows moved by LIST: the held experts' buffer filled from the tokens and
summed back into them (``models/moe.py::_held_rows_ffn``, the training form),
as Mosaic kernels in place of XLA's gather, mask, convert and scatter-add.

``moe_rows_gather``: ``out[r] = src[idx[r]]``, zeros where ``idx[r] < 0``.
``moe_rows_combine``: ``y[t] = sum of gate[e] x src[row[e]]`` over the entries
``e`` listed for token ``t``, accumulated and written in fp32. Each is the
other's transpose, so the two serve four uses: :func:`gather_rows` fills the
buffer and its backward combines ``d xs`` into ``d x2`` under gates of 1;
:func:`combine_rows` sums the experts' rows into the tokens and its backward
gathers ``dy`` by the same list. The residuals are index lists, the gates and
``ys`` (which the XLA form keeps too, for ``d gate``).

A row cannot be copied out of a tiled ``[rows, d]`` array by itself (Mosaic
takes slices of whole 8-row tiles), so a source is first laid out a row a
tile, ``moe_rows_pack``: ``[rows, S, L]`` uint32, a 16-bit row two columns a
word (column ``j`` beside column ``d / 2 + j``), ``L`` = 128 lanes where the
width allows. At ``d`` 2,048 in bf16 a row is ONE ``[8, 128]`` tile, 4 KiB in
one piece, which a grid step copies by DMA for each entry of its block of the
list (the list a block at a time in SMEM). The kernels take the rows apart
again in VMEM (sublane-strided loads), so the buffer and ``y`` leave in the
layout the grouped product and the model read.

Both walk COUNTS, not flags: a loop that tests every entry costs the scalar
core more for the entries it skips than the DMAs cost. The gather's listed
rows lead every tile of the grouped product (its segments start on tiles and
fill from the front), so a grid step walks each tile's count. The combine
walks from the TOKENS' side so that no two grid steps write one row: pairs
are token-major already, so :func:`combine_lists` only moves each token's
held pairs to the front of its slots (a select over ``[slots, k, N]``: no
sort, scatter or gather of ``[M]`` scalars) and counts them; a grid step owns
a block of tokens, fetches every listed row of it, sums each token's rows in
registers and writes the block once. The order of a token's additions is its
slots': deterministic, which XLA's scatter-add is not.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas import kernel_entry
from orion_tpu.ops.pallas.indexed_attention import _tile

Array = jax.Array

_F32, _U32 = jnp.float32, jnp.uint32
_HIGH = 0xFFFF0000
# rows a grid step of the gather and of the pack, tokens a grid step of the combine
_GATHER_ROWS = 256
_PACK_ROWS = 256
_TOKEN_BLOCK = 128
# XLA's tile of a 1-D 32-bit array, which a list's block in SMEM may not cut
_LIST_TILE = 1024
# the gather's DMAs a trip of its issue loop, the combine's tokens a trip (a
# v5e at qwen3_next_80b.train's widths: PERF.md section 6, PR 52)
_GATHER_UNROLL = 8
_TOKEN_UNROLL = 2
# the kernels' VMEM: the combine holds every row a block of tokens may list
_VMEM_BYTES = 48 << 20


def _out(shape, dtype, *operands: Array) -> jax.ShapeDtypeStruct:
    """A kernel's ``out_shape``, varying over every mesh axis an operand varies
    over (the union, read off the types: no traced operation), so that the
    kernels here and ``gmm.py``'s stand inside ``shard_map``'s ``check_vma``."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma) if vma else jax.ShapeDtypeStruct(shape, dtype)


def _whole_tiles(values: Array, fill=0) -> Array:
    """A list longer than one of XLA's 1,024-entry tiles padded to whole
    tiles, so that it can be blocked (a list kept whole in SMEM may hold
    262,144 entries, less what else is there)."""
    size = values.shape[0]
    if size <= _LIST_TILE or size % _LIST_TILE == 0:
        return values
    return jnp.pad(values, (0, -size % _LIST_TILE), constant_values=fill)


def _list_block(size: int, count: int) -> int:
    """The block in SMEM of a list of ``size`` entries (before
    :func:`_whole_tiles`), for grid steps that walk ``count`` entries each:
    Mosaic takes no block that cuts a tile, so steps that walk fewer share a
    block with their neighbours (``_step_entries``); a list of one tile or
    less, or walked in steps that neither divide a tile nor are whole tiles,
    is one block."""
    if size > _LIST_TILE and (count % _LIST_TILE == 0 or _LIST_TILE % count == 0):
        return max(_LIST_TILE, count)
    return size


def _list_spec(size: int, count: int) -> pl.BlockSpec:
    block = _list_block(size, count)
    per = block // count
    return pl.BlockSpec((block,), lambda i, *_: (i // per,), memory_space=pltpu.SMEM)


def _step_entries(refs, count: int):
    """Readers of this grid step's ``count`` entries of each list in ``refs``
    (blocks of :func:`_list_spec`)."""
    per = refs[0].shape[0] // count
    base = (pl.program_id(0) % per) * count
    return [lambda j, ref=ref: ref[base + j] for ref in refs]


def _lanes(words: int) -> int:
    return 128 if words % 128 == 0 else words


def _pack_kernel(live_ref, x_ref, out_ref, *, sub: int, lanes: int):
    rows = x_ref.shape[0]
    bits = jax.lax.bitcast_convert_type

    @pl.when(pl.program_id(0) * rows < live_ref[0])
    def _():
        for c in range(sub):  # column block c of every row -> sublane c of its tile
            at = slice(c * lanes, (c + 1) * lanes)
            if x_ref.dtype == _F32:
                words = bits(x_ref[:, at], _U32)
            else:
                low = bits(x_ref[:, at].astype(_F32), _U32) >> 16
                at = slice((sub + c) * lanes, (sub + c + 1) * lanes)
                words = low | (bits(x_ref[:, at].astype(_F32), _U32) & jnp.uint32(_HIGH))
            out_ref[pl.ds(c, rows, stride=sub), :] = words


@kernel_entry("moe_rows_pack", "interpret")
def pack_rows(x: Array, live: Array, *, interpret: bool = False) -> Array:
    """``[rows, d]`` bf16 or fp32 -> ``[rows, S, L]`` uint32, a row a tile,
    in one pass (XLA makes three of it: a widened copy, the words, and their
    relayout, 2 GB of traffic for the 0.26 GB of ``x2``). ``live`` (int32
    scalar): the rows past it are not read and their tiles not written (the
    buffer's spare rows, a third of it)."""
    rows, d = x.shape
    if x.dtype not in (jnp.bfloat16, _F32):
        raise ValueError(f"rows move as bfloat16 or float32, not {x.dtype}")
    if x.dtype == jnp.bfloat16 and d % 2:
        raise ValueError(f"a 16-bit row packs two columns a word: width {d} is odd")
    words = d // 2 if x.dtype == jnp.bfloat16 else d
    lanes = _lanes(words)
    sub = words // lanes
    block = _tile(rows, _PACK_ROWS)
    # a step past the live rows keeps the last live block's windows: no copy
    at = lambda i, live: (jnp.minimum(i, jnp.maximum(live[0] - 1, 0) // block), 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // block,),
        in_specs=[pl.BlockSpec((block, d), at)],
        out_specs=pl.BlockSpec((block * sub, lanes), at),
    )
    out = pl.pallas_call(
        functools.partial(_pack_kernel, sub=sub, lanes=lanes),
        name="moe_rows_pack",
        grid_spec=grid_spec,
        out_shape=_out((rows * sub, lanes), _U32, x, live),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(live.astype(jnp.int32).reshape(1), x)
    return out.reshape(rows, sub, lanes)


def _listed_rows(idx: Array) -> Array:
    """One past the last row of the buffer that ``idx`` lists."""
    at = jnp.arange(1, idx.shape[0] + 1, dtype=jnp.int32)
    return jnp.max(jnp.where(idx >= 0, at, 0))


def _halves(words: Array):
    """A packed tile's two column blocks as fp32 (exact: the low bits are 0)."""
    bits = jax.lax.bitcast_convert_type
    return bits(words << 16, _F32), bits(words & jnp.uint32(_HIGH), _F32)


def _row_copy(src_ref, buf, sem, j, row):
    """The DMA of ``src[row]`` into rows ``[j sub, (j + 1) sub)`` of ``buf``."""
    sub = src_ref.shape[1]
    at = pl.ds(pl.multiple_of(j * sub, sub), sub)
    return pltpu.make_async_copy(src_ref.at[row], buf.at[at, :], sem)


def _start_rows(entry, src_ref, buf, sem, first, count, unroll: int = 1):
    """Start one DMA a listed row: entries ``[first, first + count)`` of this
    step, ``count`` read at run time, each into its place in ``buf``;
    ``unroll`` a trip where counts are long (the scalar core then overlaps
    their address sums: 3.0 -> 2.0 ms a gather at 80 rows a tile, and a loss
    at a token's one or two)."""

    def start(q, carry=0):
        _row_copy(src_ref, buf, sem, first + q, entry(first + q)).start()
        return carry

    def some(i, carry):
        for u in range(unroll):
            start(i * unroll + u)
        return carry

    whole = jax.lax.div(count, unroll) if unroll > 1 else 0  # counts are not negative
    if unroll > 1:
        jax.lax.fori_loop(0, whole, some, 0)
    jax.lax.fori_loop(whole * unroll, count, start, 0)


def _await_rows(src_ref, buf, sem, count):
    """Await ``count`` of the row DMAs. A wait counts bytes, so one wait on
    ``2^i`` rows of ``buf`` stands for that many copies: a wait a set bit of
    ``count``, not a wait a row."""
    sub = src_ref.shape[1]
    bit = 1 << ((buf.shape[0] // sub).bit_length() - 1)
    while bit:
        part = buf.at[pl.ds(0, bit * sub), :]
        pl.when((count & bit) != 0)(pltpu.make_async_copy(part, part, sem).wait)
        bit //= 2


def _gather_kernel(cnt_ref, idx_ref, src_ref, out_ref, buf, sem, *, packed: bool, tile: int):
    rows = out_ref.shape[0]
    sub, lanes = src_ref.shape[1:]
    groups = rows // tile
    (entry,) = _step_entries([idx_ref], rows)
    first = pl.program_id(0) * groups

    def fill(g, total):  # a group's listed rows lead it: copy those, zero the rest
        live = cnt_ref[first + g]
        _start_rows(entry, src_ref, buf, sem, g * tile, live, _GATHER_UNROLL)

        def zero(q, carry):
            at = pl.ds(pl.multiple_of((g * tile + q) * sub, sub), sub)
            buf[at, :] = jnp.zeros((sub, lanes), _U32)
            return carry

        jax.lax.fori_loop(live, tile, zero, 0)
        return total + live

    listed = jax.lax.fori_loop(0, groups, lambda g, total: total + cnt_ref[first + g], 0)

    @pl.when(listed == 0)  # the buffer's spare rows: a third of the steps
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(listed > 0)
    def _():
        _await_rows(src_ref, buf, sem, jax.lax.fori_loop(0, groups, fill, 0))
        for c in range(sub):  # sublane c of every row's tile: one column block
            words = buf[pl.ds(c, rows, stride=sub), :]
            at = slice(c * lanes, (c + 1) * lanes)
            if packed:
                low, high = _halves(words)
                out_ref[:, at] = low.astype(out_ref.dtype)
                at = slice((sub + c) * lanes, (sub + c + 1) * lanes)
                out_ref[:, at] = high.astype(out_ref.dtype)
            else:
                out_ref[:, at] = jax.lax.bitcast_convert_type(words, out_ref.dtype)


@kernel_entry("moe_rows_gather", "tile", "dtype", "interpret")
def moe_rows_gather(src: Array, idx: Array, *, tile: int, dtype: str,
                    interpret: bool = False) -> Array:
    """``src`` :func:`pack_rows` of ``[N, d]`` in ``dtype``; ``idx`` ``[R]``
    int32 -> ``[R, d]`` in ``dtype``: row ``idx[r]``, zeros where negative.
    The listed rows LEAD every group of ``tile`` rows (a grouped product's
    segments start on its tiles and fill from the front; a ``tile`` of 1 asks
    nothing): a grid step then walks each group's count, not its rows."""
    dt = jnp.dtype(dtype)
    _, sub, lanes = src.shape
    packed = dt.itemsize == 2
    r, d = idx.shape[0], sub * lanes * (2 if packed else 1)
    rows = _tile(math.gcd(r, _list_block(r, _LIST_TILE)), _GATHER_ROWS)
    if r % tile or rows % tile:
        raise ValueError(f"groups of {tile} rows do not tile {r} rows, {rows} a grid step")
    count = jnp.sum((idx >= 0).reshape(r // tile, tile), axis=1, dtype=jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r // rows,),
        in_specs=[_list_spec(r, rows), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((rows, d), lambda i, cnt: (i, 0)),
        scratch_shapes=[pltpu.VMEM((rows * sub, lanes), _U32), pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        functools.partial(_gather_kernel, packed=packed, tile=tile),
        name="moe_rows_gather",
        grid_spec=grid_spec,
        out_shape=_out((r, d), dt, src, idx),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(count, _whole_tiles(idx.astype(jnp.int32)), src)


def _each_token(tokens: int, body):
    """``body(t, q) -> q`` over a block's tokens, a few a trip."""
    u = _TOKEN_UNROLL if tokens % _TOKEN_UNROLL == 0 else 1

    def some(i, q):
        for v in range(u):
            q = body(i * u + v, q)
        return q

    return jax.lax.fori_loop(0, tokens // u, some, 0)


def _combine_kernel(cnt_ref, row_ref, gate_ref, src_ref, out_ref, buf, acc, sem,
                    *, packed: bool, slots: int):
    tokens = out_ref.shape[0]
    sub, lanes = src_ref.shape[1:]
    per = sub * (2 if packed else 1)  # fp32 tiles' sublanes a token in ``acc``
    (count,) = _step_entries([cnt_ref], tokens)
    row, gate_of = _step_entries([row_ref, gate_ref], tokens * slots)

    def start(t, q):  # a token's listed rows lead its slots: entries q, q + 1, ... of buf
        live = count(t)
        _start_rows(lambda j: row((j - q) * tokens + t), src_ref, buf, sem, q, live)
        return q + live

    _await_rows(src_ref, buf, sem, _each_token(tokens, start))

    def add(t, q):  # a token's sum stays in registers and is stored once
        def one(j, sums):
            words = buf[pl.ds(pl.multiple_of((q + j) * sub, sub), sub), :]
            parts = _halves(words) if packed else (jax.lax.bitcast_convert_type(words, _F32),)
            gate = gate_of(j * tokens + t)
            return tuple(total + gate * part for total, part in zip(sums, parts))

        live = count(t)
        zero = (jnp.zeros((sub, lanes), _F32),) * (per // sub)
        for p, total in enumerate(jax.lax.fori_loop(0, live, one, zero)):
            acc[pl.ds(pl.multiple_of(t * per, sub) + p * sub, sub), :] = total
        return q + live

    _each_token(tokens, add)
    for c in range(per):
        out_ref[:, c * lanes:(c + 1) * lanes] = acc[pl.ds(c, tokens, stride=per), :]


class Lists(NamedTuple):
    """What :func:`combine_lists` hands the combine, from the TOKENS' side:
    token ``t`` sums ``count[t]`` buffer rows, its slots ``j = 0, 1, ...`` of
    ``S`` (its ``k`` pairs, those with a row first, padded to whole eights).
    ``row`` and ``gate`` are ``[blocks of tokens, S, tokens a block]``, so
    that a slot of a block's tokens is one run of lanes to XLA."""

    count: Array  # [N] int32
    row: Array  # [N S] int32
    gate: Array  # [N S] fp32


def _token_block(n: int) -> int:
    return _tile(math.gcd(n, _list_block(n, _LIST_TILE)), _TOKEN_BLOCK)


def combine_lists(held: Array, row: Array, gate: Array, n: int) -> Lists:
    """The combine's lists from the pairs' side. ``held [M]`` (``M = n k``,
    token-major: pair ``p`` is slot ``p % k`` of token ``p // k``): the pair
    has a row of the buffer; ``row [M]``: which; ``gate [M]``: its weight.
    Each token's held pairs move to the front of its slots, in order (the
    rest 0): a select and a sum over ``[slots, k, N]`` with a slot of every
    token one lane-dense vector, no gather or scatter."""
    k = held.shape[0] // n
    slots, tokens = -(-k // 8) * 8, _token_block(n)
    by_slot = lambda x, dtype: x.astype(dtype).reshape(n, k).T  # noqa: E731
    held = by_slot(held, jnp.int32)
    # held pairs before this slot of its token: a [k, k] product (0s and 1s and
    # sums under 2^8 are exact in any matmul precision; a cumsum over 10 rows
    # takes the chip's compiler 3 s)
    earlier = jnp.tril(jnp.ones((k, k), _F32), -1)
    before = jnp.dot(earlier, held.astype(_F32)).astype(jnp.int32)
    here = (held[None] > 0) & (before[None] == jnp.arange(slots)[:, None, None])

    def leading(values):  # [k, N] -> [blocks of tokens, slots, tokens a block]
        front = jnp.sum(jnp.where(here, values[None], 0), axis=1)
        return front.reshape(slots, n // tokens, tokens).swapaxes(0, 1).reshape(-1)

    return Lists(
        jnp.sum(held, axis=0), leading(by_slot(row, jnp.int32)), leading(by_slot(gate, _F32))
    )


@kernel_entry("moe_rows_combine", "dtype", "interpret")
def moe_rows_combine(src: Array, lists: Lists, *, dtype: str, interpret: bool = False) -> Array:
    """``src`` :func:`pack_rows` of the buffer ``[R, d]`` in ``dtype`` ->
    ``[N, d]`` fp32: every token's listed rows summed under their gates, a
    block of tokens a grid step (so no two steps write one row), each row
    fetched by its own DMA."""
    _, sub, lanes = src.shape
    packed = jnp.dtype(dtype).itemsize == 2
    per = sub * (2 if packed else 1)
    n = lists.count.shape[0]
    slots = lists.row.shape[0] // n
    tokens = _token_block(n)
    entries = lambda: _list_spec(n * slots, tokens * slots)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_combine_kernel, packed=packed, slots=slots),
        name="moe_rows_combine",
        grid=(n // tokens,),
        in_specs=[
            _list_spec(n, tokens), entries(), entries(), pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((tokens, per * lanes), lambda i: (i, 0)),
        out_shape=_out((n, per * lanes), _F32, src, *lists),
        scratch_shapes=[
            pltpu.VMEM((tokens * slots * sub, lanes), _U32),
            pltpu.VMEM((tokens * per, lanes), _F32), pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_BYTES
        ),
        interpret=interpret,
    )(*(_whole_tiles(x) for x in lists), src)


def _vary_like(x: Array, idx: Array) -> Array:
    """``x`` made to vary over the mesh axes the list varies over (an ep
    shard's list depends on the shard, its tokens do not): under
    ``shard_map``'s ``check_vma`` a ``custom_vjp``'s cotangent has its
    primal's type, and this cast's transpose is the sum over those axes that
    XLA's own gather would be given. No axes outside ``shard_map``."""
    more = tuple(sorted(set(jax.typeof(idx).vma) - set(jax.typeof(x).vma)))
    return jax.lax.pcast(x, more, to="varying") if more else x


def gather_rows(x: Array, idx: Array, lists: Lists, tile: int = 1,
                interpret: bool = False) -> Array:
    """``x [N, d]`` -> the buffer ``[R, d]``: row ``idx[r]`` of ``x``, zeros
    where ``idx[r] < 0``; the listed rows lead every group of ``tile`` rows
    (:func:`moe_rows_gather`). ``lists``: :func:`combine_lists` of the same
    rows, by which the backward sums ``d buffer`` into ``d x``."""
    return _gather_rows(_vary_like(x, idx), idx, lists, tile, interpret)


def _gathered(x, idx, tile, interpret):
    packed = pack_rows(x, jnp.full((), x.shape[0], jnp.int32), interpret=interpret)
    return moe_rows_gather(packed, idx, tile=tile, dtype=x.dtype.name, interpret=interpret)


def _combined(buffer, idx, lists, interpret):
    packed = pack_rows(buffer, _listed_rows(idx), interpret=interpret)
    return moe_rows_combine(packed, lists, dtype=buffer.dtype.name, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gather_rows(x, idx, lists, tile, interpret):
    return _gathered(x, idx, tile, interpret)


def _gather_rows_fwd(x, idx, lists, tile, interpret):
    # residuals are jax types: a zero-size array carries x's rows and dtype
    return _gather_rows(x, idx, lists, tile, interpret), (idx, lists, x[:, :0])


def _gather_rows_bwd(tile, interpret, res, dxs):
    idx, lists, like = res
    ones = lists._replace(gate=jnp.ones_like(lists.gate))
    dx = _combined(dxs.astype(like.dtype), idx, ones, interpret)
    return dx.astype(like.dtype), None, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


def combine_rows(ys: Array, gate: Array, idx: Array, lists: Lists, tile: int = 1,
                 interpret: bool = False) -> Array:
    """The buffer ``ys [R, d]`` summed into the tokens: ``y [N, d]`` fp32,
    ``y[idx[r]] += gate[r] ys[r]`` over the rows with ``idx[r] >= 0``, in
    ``lists``' order and under ITS gates, which are ``gate [R]``'s from the
    tokens' side; ``gate``, ``idx`` and ``tile`` (as :func:`gather_rows`
    takes them) are what the backward gathers ``dy`` and forms ``d gate`` by."""
    ys, gate = _vary_like(ys, idx), _vary_like(gate, idx)
    return _combine_rows(ys, gate, idx, lists, tile, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _combine_rows(ys, gate, idx, lists, tile, interpret):
    return _combined(ys, idx, lists, interpret)


def _combine_rows_fwd(ys, gate, idx, lists, tile, interpret):
    return _combine_rows(ys, gate, idx, lists, tile, interpret), (ys, gate, idx)


def _combine_rows_bwd(tile, interpret, res, dy):
    ys, gate, idx = res
    # dy in the compute dtype, as the XLA form's transpose takes it: the
    # gather then meets the forward's shapes and dtypes, and its body
    g = _gathered(dy.astype(ys.dtype), idx, tile, interpret).astype(_F32)
    dys = (g * gate.astype(_F32)[:, None]).astype(ys.dtype)
    dgate = jnp.sum(g * ys.astype(_F32), axis=1).astype(gate.dtype)
    return dys, dgate, None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


__all__ = ["Lists", "combine_lists", "combine_rows", "gather_rows", "moe_rows_combine",
           "moe_rows_gather", "pack_rows"]
