"""Exact top-k selection of a row of scores as a SEARCH, not a sort.

``lax.top_k(k=2048)`` over ``[1,024, 33,280]`` fp32 sorts each row; the
k-th largest value of a row can instead be found by bisection on the
float's ordered bit pattern: 32 passes of compare-and-count, no data
movement. The selection is then everything above that value plus, of the
entries EQUAL to it, the lowest-indexed ones that fill the count: what
``lax.top_k`` returns as a set, ties included. The list of a selection is a
prefix sum over its mask and one binary search an entry.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def ordered_bits(x: Array) -> Array:
    """fp32 -> uint32 whose unsigned order is the floats' (``-0.0`` counted
    as ``+0.0``); never 0, which :func:`top_k_mask` keeps for an entry that
    is not a candidate."""
    x = jnp.where(x == 0, 0.0, x.astype(jnp.float32))
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    flipped = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    return jnp.maximum(flipped, jnp.uint32(1))


def kth_largest(keys: Array, k: int) -> Array:
    """keys uint32 ``[..., N]`` -> ``[...]``: the largest value ``v`` with
    at least ``k`` keys ``>= v`` (0 where fewer than ``k`` keys are
    nonzero), one bit of it a pass from the top."""

    def narrow(i, prefix):
        cand = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(keys >= cand[..., None], axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, prefix)

    return jax.lax.fori_loop(
        0, 32, narrow, jnp.zeros(keys.shape[:-1], jnp.uint32)
    )


def top_k_mask(scores: Array, valid: Array, k: int) -> Array:
    """bool ``[..., N]``: the ``min(k, number valid)`` entries of each row
    of ``scores`` (fp32) with the largest score among those ``valid``, equal
    scores going to the lower index."""
    keys = jnp.where(valid, ordered_bits(scores), jnp.uint32(0))
    kth = kth_largest(keys, k)[..., None]
    above = keys > kth
    equal = (keys == kth) & valid
    room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    # the prefix sum over a row is the dear part, and only a row with MORE
    # entries at the k-th value than places left needs it
    crowded = jnp.any(jnp.sum(equal, axis=-1, keepdims=True, dtype=jnp.int32) > room)
    return above | jax.lax.cond(
        crowded,
        lambda: equal & (jnp.cumsum(equal, axis=-1, dtype=jnp.int32) <= room),
        lambda: equal,
    )


_LANES = 128


def mask_to_list(mask: Array, width: int):
    """bool ``[B, N]`` -> (list ``[B, width]`` int32, counts ``[B]``): the
    set positions of each row in ascending order (``min(count, width)`` of
    them; the entries past the count are 0). A prefix sum in two levels,
    both products against a triangle (exact: the counts are whole numbers
    under 2^24 in fp32): within blocks of 128 and over the blocks' totals;
    entry ``j`` is then found in the block whose running total first
    reaches ``j + 1`` and at the lane where that block's does (a binary
    search over the whole row an entry was 5.5 ms a layer and step on the
    chip, PERF.md section 6, PR 47)."""
    f32 = jnp.float32
    b, n = mask.shape
    pad = (-n) % _LANES
    blocks = jnp.pad(mask, ((0, 0), (0, pad))).reshape(b, -1, _LANES).astype(jnp.bfloat16)
    nb = blocks.shape[1]
    upto = (jnp.arange(_LANES)[:, None] <= jnp.arange(_LANES)).astype(jnp.bfloat16)
    inside = jnp.einsum("bnl,lm->bnm", blocks, upto, preferred_element_type=f32)
    totals = inside[..., -1]  # [B, nb]
    before = jnp.einsum(
        "bn,nm->bm", totals, (jnp.arange(nb)[:, None] < jnp.arange(nb)).astype(f32),
        precision=jax.lax.Precision.HIGHEST,
    )
    want = jnp.arange(1, width + 1, dtype=f32)
    block = jnp.sum((before + totals)[:, None, :] < want[None, :, None], axis=-1)  # [B, width]
    block = jnp.minimum(block, nb - 1)
    running = jnp.take_along_axis(inside, block[..., None], axis=1) + jnp.take_along_axis(
        before, block, axis=1
    )[..., None]  # [B, width, 128]
    lane = jnp.sum(running < want[None, :, None], axis=-1)
    counts = jnp.minimum((before[:, -1] + totals[:, -1]).astype(jnp.int32), width)
    live = jnp.arange(width)[None, :] < counts[:, None]
    return jnp.where(live, block * _LANES + lane, 0).astype(jnp.int32), counts


__all__ = ["ordered_bits", "kth_largest", "top_k_mask", "mask_to_list"]
