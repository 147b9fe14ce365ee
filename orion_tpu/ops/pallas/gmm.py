"""Grouped expert matmul (gmm) — the dropless-MoE hot path as ONE Mosaic
kernel (VERDICT r3 #3a: the ragged_dot+sort formulation cost the dropless
path 14.3% vs capacity dispatch at the 1.3B operating point).

Contract: ``gmm(x, w, group_sizes, tile_rows)`` computes
``y[i] = x[i] @ w[g(i)]`` where rows of ``x`` are laid out in
TILE-ALIGNED expert segments: the caller pads each expert's row block up
to a multiple of ``tile_rows`` (models/moe.py::_dropless does this with
its counting-sort scatter), so every ``tile_rows``-row tile belongs to
exactly ONE expert. The tile->expert table is scalar-prefetched
(pltpu.PrefetchScalarGridSpec) and drives the weight BlockSpec's index
map — the kernel is then a plain MXU matmul per (row-tile, out-tile)
with zero dynamic control flow inside the body.

Why this beats ragged_dot here: XLA's ragged_dot must handle arbitrary
group boundaries inside a tile (masked multi-expert accumulation);
tile-aligning the segments moves that irregularity OUT of the kernel
into a cheap one-time scatter and leaves Mosaic a dense, perfectly-tiled
matmul stream. What the alignment wastes is <= E*(tile_rows-1) rows INSIDE
the segments: ~2% of ``_dropless_gmm``'s buffer at the flagship shapes,
which is full but for that. The held rows' buffer
(``models/moe.py::_held_rows_ffn``) is sized for a router 1.5 x even and
under a real one 31% of its tiles lie PAST the last segment
(``qwen3_next_80b.train``, PERF.md section 6, PR 58).

Those tiles are not visited, by any kernel here: the grid of the forward,
of ``dx`` and of ``dw`` is ``sum(group_sizes) / tile_rows`` long, read at
run time (:func:`gmm_live`'s, the serving form's, since PR 43; :func:`gmm`'s,
the training form's, since PR 58), and where two buffers of an expert's
whole ``[d, h]`` matrix fit the kernel's VMEM (:func:`live_whole_width_fits`)
that matrix is ONE block, resident across the expert's consecutive tiles.
So the rows past the last segment are never read and never written, in ``y``
and in ``dx``: they hold whatever the buffer held, NaN included, and the
caller reads its buffer by list or masks it.

Backward: dx rides the same kernel against swapaxes(w, 1, 2); dw is a
second kernel accumulating x_tile^T @ dy_tile into the expert's [d, h]
block — tiles of one expert are consecutive, so the output block is
revisited consecutively (the Pallas TPU revisiting rule) with a
first-tile zero-init; an expert without a tile is never written, and
masked by the caller of the kernel (``_gmm_bwd``).

reference: none — BASELINE.json names no MoE; this kernel exists for the
framework's own dropless formulation (reference checkout never mounted,
SURVEY.md §0).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas import kernel_entry
from orion_tpu.ops.pallas.moe_rows import _out  # an out_shape that varies over
# the mesh axes its operands vary over (x over the data axes, w over ep), read
# off their types: these kernels stand inside shard_map(check_vma=True) (the
# dropless-ep gmm region, models/moe.py::_dropless_ep_gmm)

Array = jax.Array

# dw-kernel output tile where an expert's whole [d, h] does not fit (see
# _dw_call): tuned by an earlier round's sweep (BASELINE.md); not measured on
# the current installation
_DW_BLOCK_D = 1024
_DW_BLOCK_H = 1024


def tile_expert_table(group_sizes: Array, n_tiles: int, tile_rows: int) -> Array:
    """[n_tiles] int32: owning expert of each row tile, given TILE-ALIGNED
    segment sizes (every entry of ``group_sizes`` divisible by tile_rows;
    trailing tiles beyond the last segment map to the last expert — no kernel
    here visits them)."""
    starts = jnp.cumsum(group_sizes) - group_sizes  # [E] segment starts
    rows = jnp.arange(n_tiles, dtype=jnp.int32) * tile_rows
    return (
        jnp.sum(rows[:, None] >= starts[None, :], axis=1).astype(jnp.int32) - 1
    ).clip(0)


# [7,680, 512] bf16 weight blocks, double-buffered, pass the 16 MB default
_LIVE_VMEM_BYTES = 64 << 20
# what a whole-width weight block may take of that, double-buffered: a quarter,
# so the x and out tiles and the fp32 product have the rest ([2048, 1024] bf16
# = 8 MB fits; [7,680, 2,048] = 63 MB does not)
_LIVE_WHOLE_WIDTH_BYTES = _LIVE_VMEM_BYTES // 4


def live_whole_width_fits(d: int, h: int, itemsize: int) -> bool:
    """May a kernel here hold an expert's whole ``[d, h]`` matrix as ONE block
    (two buffers of it) beside its tiles? :func:`gmm_live`'s and the training
    forward's weights, and ``gmm_dw``'s fp32 output."""
    return 2 * d * h * itemsize <= _LIVE_WHOLE_WIDTH_BYTES


def _last_live(i, live_ref):
    """Row tile ``i``, or the last that holds a segment: a grid step past it
    keeps that tile's windows (no copy) and is skipped (``pl.when``)."""
    return jnp.minimum(i, jnp.maximum(live_ref[0] - 1, 0))


def _fwd_kernel(te_ref, x_ref, w_ref, o_ref):
    o_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[0],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def _fwd_to_live_kernel(te_ref, live_ref, x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(1) < live_ref[0])
    def _():
        _fwd_kernel(te_ref, x_ref, w_ref, o_ref)


@kernel_entry("gmm_fwd", "tile_rows", "block_h", "interpret")
def _gmm_call(x, w, tables, tile_rows, block_h, interpret):
    """The training forward (and ``dx``, against the transposed stacks) over
    the row tiles that hold a segment. Which form is decided from ``d``, ``h``
    and the itemsize alone (:func:`live_whole_width_fits`): :func:`gmm_live`'s
    grid, the live tiles leading, with an expert's whole matrix the block, so
    that the expert's consecutive tiles find it in VMEM; or, where two buffers
    of it do not fit (``[2048, 5504]``), column blocks outer and every row
    tile inner, the tiles past the last segment skipped."""
    te, live = tables
    m, d = x.shape
    _, _, h = w.shape
    if live_whole_width_fits(d, h, x.dtype.itemsize):
        return _live_blocked(x, w, te, live, tile_rows, h, interpret, "gmm_fwd")
    nt, nh = m // tile_rows, -(-h // block_h)
    hp = nh * block_h
    if hp != h:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, hp - h)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # h-tiles OUTER, row-tiles INNER: consecutive same-expert row
        # tiles then hit the SAME weight block index, and Mosaic skips the
        # reload — weight HBM traffic is O(E·d·h) per h-sweep instead of
        # O(n_tiles·d·block_h) (measured: the (nt, nh) order re-streamed
        # 4.3GB of expert weights per gmm at the 1.3B MoE shapes)
        grid=(nh, nt),
        in_specs=[
            pl.BlockSpec((tile_rows, d), lambda j, i, te, live: (_last_live(i, live), 0)),
            pl.BlockSpec(
                (1, d, block_h), lambda j, i, te, live: (te[_last_live(i, live)], 0, j)
            ),
        ],
        out_specs=pl.BlockSpec(
            (tile_rows, block_h), lambda j, i, te, live: (_last_live(i, live), j)
        ),
    )
    out = pl.pallas_call(
        _fwd_to_live_kernel,
        name="gmm_fwd",
        out_shape=_out((m, hp), x.dtype, x, w),
        grid_spec=grid_spec,
        interpret=interpret,
    )(te, live.reshape(1), x, w)
    return out[:, :h] if hp != h else out


def _dw_kernel(te_ref, live_ref, x_ref, g_ref, dw_ref, *, axis: int):
    i = pl.program_id(axis)

    @pl.when(i < live_ref[0])
    def _():
        first = jnp.logical_or(i == 0, te_ref[i] != te_ref[jnp.maximum(i - 1, 0)])

        @pl.when(first)
        def _():
            dw_ref[...] = jnp.zeros_like(dw_ref)

        dw_ref[...] += jax.lax.dot_general(
            x_ref[...], g_ref[...],
            (((0,), (0,)), ((), ())),  # [tm, bd]^T @ [tm, bh] -> [bd, bh]
            preferred_element_type=jnp.float32,
        )[None]


@kernel_entry("gmm_dw", "n_experts", "tile_rows", "interpret")
def _dw_call(x, g, tables, n_experts, tile_rows, interpret):
    """dw[e] = sum over e's rows of x^T g, over the row tiles that hold a
    segment. Where two buffers of an expert's whole fp32 ``[d, h]`` fit
    (:func:`live_whole_width_fits`: ``[2048, 512]`` is 4 MB) that is the
    output block and the grid is the live tiles alone: x and g cross HBM once.
    Else BOTH output dims are tiled: the 2D-grid form either blew the VMEM
    stack (full-d blocks at d=5504) or, at small block_h, re-streamed the x
    rows h/block_h ~= 43 times — ~13GB of HBM per MoE layer's backward at the
    1.3B shapes. The dw stream traffic is nd*nh*(M*(block_d+block_h)) — x
    re-read nh times, dy re-read nd times — so bigger blocks directly cut the
    backward's HBM bill; the (1, bd, bh) fp32 dw block is the VMEM bound."""
    te, live = tables
    m, d = x.shape
    h = g.shape[1]
    nt = m // tile_rows
    whole = live_whole_width_fits(d, h, 4)
    block_d, block_h = (d, h) if whole else (min(_DW_BLOCK_D, d), min(_DW_BLOCK_H, h))
    nd, nh = -(-d // block_d), -(-h // block_h)
    if nd * block_d != d:
        x = jnp.pad(x, ((0, 0), (0, nd * block_d - d)))
    if nh * block_h != h:
        g = jnp.pad(g, ((0, 0), (0, nh * block_h - h)))
    if whole:
        grid, axis = (live,), 0
        x_at = g_at = lambda i, te, live: (i, 0)  # noqa: E731
        dw_at = lambda i, te, live: (te[i], 0, 0)  # noqa: E731
    else:
        # row-tiles INNER: each expert's dw block is revisited over
        # consecutive iterations (the Pallas revisiting rule the
        # accumulation relies on)
        grid, axis = (nd, nh, nt), 2
        x_at = lambda jd, jh, i, te, live: (_last_live(i, live), jd)  # noqa: E731
        g_at = lambda jd, jh, i, te, live: (_last_live(i, live), jh)  # noqa: E731
        dw_at = lambda jd, jh, i, te, live: (te[_last_live(i, live)], jd, jh)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_rows, block_d), x_at),
            pl.BlockSpec((tile_rows, block_h), g_at),
        ],
        out_specs=pl.BlockSpec((1, block_d, block_h), dw_at),
    )
    dw = pl.pallas_call(
        functools.partial(_dw_kernel, axis=axis),
        name="gmm_dw",
        out_shape=_out((n_experts, nd * block_d, nh * block_h), jnp.float32, x, g),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_LIVE_VMEM_BYTES),
        interpret=interpret,
    )(te, live.reshape(1), x, g)
    return dw[:, :d, :h]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def gmm(
    x: Array,
    w: Array,
    group_sizes: Array,
    tile_rows: int = 128,
    block_h: int = 512,
    interpret: bool = False,
) -> Array:
    """y[i] = x[i] @ w[g(i)] over tile-aligned expert segments.

    x: [M, d] rows sorted into expert segments, each segment a multiple of
       ``tile_rows`` (M divisible by tile_rows). The forward, ``dx`` and ``dw``
       visit the ``sum(group_sizes) / tile_rows`` tiles that hold a segment,
       read at run time: rows past the last segment are never READ and their
       rows of ``y`` and of ``dx`` never WRITTEN (they hold what the buffer
       held), so the caller reads its buffer by list or masks it; padding
       rows inside a segment compute against their segment's expert.
    w: [E, d, h] stacked expert weights; group_sizes: [E] int32
       tile-aligned segment sizes summing to <= M.
    """
    out, _ = _gmm_fwd(x, w, group_sizes, tile_rows, block_h, interpret)
    return out


def _gmm_fwd(x, w, group_sizes, tile_rows, block_h, interpret):
    m = x.shape[0]
    assert m % tile_rows == 0, (m, tile_rows)
    wc = w.astype(x.dtype)
    # what the three kernels' grids are built from: the tile -> expert table and
    # the row tiles that hold a segment, their run-time bound
    tables = (
        tile_expert_table(group_sizes, m // tile_rows, tile_rows),
        jax.lax.div(jnp.sum(group_sizes), tile_rows).astype(jnp.int32),
    )
    out = _gmm_call(x, wc, tables, tile_rows, block_h, interpret)
    # residuals must be jax types: a zero-size array carries w's dtype
    return out, (x, wc, tables, group_sizes > 0, jnp.zeros((0,), w.dtype))


def _gmm_bwd(tile_rows, block_h, interpret, res, dy):
    x, wc, tables, present, w_dtype_probe = res
    dyc = dy.astype(x.dtype)
    # dx[i] = dy[i] @ w[g(i)]^T — the same kernel against transposed stacks
    dx = _gmm_call(
        dyc, jnp.swapaxes(wc, 1, 2), tables, tile_rows, block_h, interpret
    ).astype(x.dtype)
    dw = _dw_call(x, dyc, tables, wc.shape[0], tile_rows, interpret)
    # an expert with ZERO tiles never has its dw block written — the out
    # buffer holds uninitialized memory there, so mask by presence (pad
    # rows inside real tiles are zeros and need no mask)
    dw = jnp.where(present[:, None, None], dw, 0.0).astype(w_dtype_probe.dtype)
    return dx, dw, None


gmm.defvjp(_gmm_fwd, _gmm_bwd)


@kernel_entry("gmm_live", "tile_rows", "block_h", "interpret")
def gmm_live(
    x: Array, w: Array, group_sizes: Array, tile_rows: int = 128,
    block_h: Optional[int] = 512, interpret: bool = False,
) -> Array:
    """:func:`gmm`'s forward over the row tiles that HOLD a segment only: the
    grid's first dimension is ``sum(group_sizes) / tile_rows``, read at run
    time, so a buffer sized for the worst router costs what the rows in it
    cost (the serving path of ``models/moe.py::_dropless_held``). Rows past
    the last segment are NOT written: the caller masks them. No backward.

    What is read how often. Row tiles are the outer dimension (a dynamic
    bound leads the grid): an x tile ``[tile_rows, d]`` is fetched once and
    stays put while its expert's output blocks sweep inside it, so with
    ``block_h < h`` an expert's whole ``[d, h]`` matrix crosses HBM once for
    EVERY row tile the expert has. That is the right order where an expert
    has one tile: a decode step (512 pairs over 32 or 128 experts on tiles of
    32 or 16: 87-95% of the chip's bandwidth, PERF.md section 5) and a lone
    prompt piece (64 rows an expert). It is the wrong one where a call gives
    an expert several tiles (four 1,024-row pieces to a program: 256 rows an
    expert, ~2.6 tiles under a real router, 3.9 GB read a layer where the
    experts are 1.6). There the caller asks for the WHOLE width as the block
    (``block_h=None``) and an expert's matrix stays in VMEM across its
    consecutive tiles: a call streams each live expert's weights once
    whatever the number of its tiles, and a repeated tile moves its x and out
    tiles alone (:func:`_live_resident`). The products are the same either
    way: one ``dot_general`` over the whole contraction, fp32 accumulation,
    so both forms give the same bits.

    Which form a call takes is decided from shapes alone by
    ``models/moe.py::serve_tiles`` (the pairs over the router's width against
    the row tile, and :func:`live_whole_width_fits`), in ``_dropless_held``:
    no configuration names it."""
    m, d = x.shape
    _, _, h = w.shape
    assert m % tile_rows == 0, (m, tile_rows)
    te = tile_expert_table(group_sizes, m // tile_rows, tile_rows)
    live = (jnp.sum(group_sizes) // tile_rows).astype(jnp.int32)
    if block_h is None:
        return _live_resident(x, w.astype(x.dtype), group_sizes, te, live, tile_rows, interpret)
    # a block of whole lanes that divides the width, where there is one: a
    # padded copy of the weights costs their bytes again at every call
    # (experts 768 wide under blocks of 512: 19% of a boundary's busy time)
    block_h = next((b for b in range(block_h, 127, -128) if h % b == 0), block_h)
    nh = -(-h // block_h)
    hp = nh * block_h
    wc = w.astype(x.dtype)
    if hp != h:
        wc = jnp.pad(wc, ((0, 0), (0, 0), (0, hp - h)))
    out = _live_blocked(x, wc, te, live, tile_rows, block_h, interpret, "gmm_live")
    return out[:, :h] if hp != h else out


def _live_blocked(x, w, te, live, tile_rows, block_h, interpret, name):
    """The product over the ``live`` leading row tiles, output blocks of
    ``block_h`` (which divides ``h``) inside a tile: :func:`gmm_live`'s blocked
    form and, with ``block_h = h``, the training forward's."""
    m, d = x.shape
    _, _, h = w.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(live, h // block_h),
        in_specs=[
            pl.BlockSpec((tile_rows, d), lambda i, j, te: (i, 0)),
            pl.BlockSpec((1, d, block_h), lambda i, j, te: (te[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((tile_rows, block_h), lambda i, j, te: (i, j)),
    )
    return pl.pallas_call(
        _fwd_kernel,
        name=name,
        out_shape=_out((m, h), x.dtype, x, w),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_LIVE_VMEM_BYTES),
        interpret=interpret,
    )(te, x, w)


def _resident_kernel(te_ref, next_ref, slot_ref, x_ref, w_hbm, o_ref, w_vmem, sem):
    i = pl.program_id(0)
    expert, slot = te_ref[i], slot_ref[i]
    first = jnp.logical_or(i == 0, expert != te_ref[jnp.maximum(i - 1, 0)])

    def copy(e, s):
        return pltpu.make_async_copy(w_hbm.at[e], w_vmem.at[s], sem.at[s])

    @pl.when(i == 0)
    def _():
        copy(expert, slot).start()

    @pl.when(first)
    def _():
        copy(expert, slot).wait()
        following = next_ref[i]

        @pl.when(following >= 0)
        def _():
            copy(following, 1 - slot).start()

    o_ref[...] = jax.lax.dot_general(
        x_ref[...], w_vmem[slot],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def _live_resident(x, w, group_sizes, te, live, tile_rows, interpret):
    """:func:`gmm_live` with an expert's whole ``[d, h]`` matrix resident: the
    weights stay in HBM and the kernel keeps TWO VMEM buffers of its own, the
    current expert's and the next live expert's, whose copy it starts at the
    current expert's FIRST tile (every expert with a row has a tile, so every
    copy started is waited for, at its expert's first tile). A ``BlockSpec``
    of the whole width gives the same traffic, but the pipeline fetches ONE
    grid step ahead, so a new expert's 4 MB (5-6 us) hid behind one tile's
    product (~3.6 us) and not behind the expert's run: 1.52 ms against 1.36
    here at ``[49152, 2048] x [128, 2048, 1024]`` under a skewed split, 1.39
    against 1.05 under an even one (PERF.md section 6, PR 56)."""
    m, d = x.shape
    e, _, h = w.shape
    has_rows = group_sizes > 0
    ids = jnp.where(has_rows, jnp.arange(e), e)
    after = jnp.concatenate([jax.lax.cummin(ids, reverse=True)[1:], jnp.array([e])])
    next_live = jnp.where(after < e, after, -1)[te].astype(jnp.int32)
    slot = ((jnp.cumsum(has_rows) - 1) % 2)[te].astype(jnp.int32)  # experts alternate
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(live,),
        in_specs=[
            pl.BlockSpec((tile_rows, d), lambda i, *_: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((tile_rows, h), lambda i, *_: (i, 0)),
        scratch_shapes=[pltpu.VMEM((2, d, h), x.dtype), pltpu.SemaphoreType.DMA((2,))],
    )
    return pl.pallas_call(
        _resident_kernel,
        name="gmm_live",
        out_shape=jax.ShapeDtypeStruct((m, h), x.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_LIVE_VMEM_BYTES),
        interpret=interpret,
    )(te, next_live, slot, x, w)


def pad_group_sizes(counts: Array, tile_rows: int) -> Tuple[Array, Array]:
    """(tile-aligned segment sizes, exclusive segment starts) for raw
    per-expert row counts."""
    seg = -(-counts // tile_rows) * tile_rows
    starts = jnp.cumsum(seg) - seg
    return seg.astype(jnp.int32), starts.astype(jnp.int32)


__all__ = [
    "gmm", "gmm_live", "live_whole_width_fits", "pad_group_sizes", "tile_expert_table",
]
