"""Cross-shard combine primitives used inside ``shard_map`` bodies (SURVEY.md P8).

The reference speaks NCCL (allreduce / allgather / reduce_scatter /
sendrecv; BASELINE.json NCCL DP wrapper — reference checkout never
mounted, SURVEY.md §0). Here that vocabulary splits in two:

- the GSPMD training path never calls collectives at all — jit inserts
  psum/all_gather/reduce_scatter/all_to_all from the shardings
  (parallel/sharding.py, models/moe.py), which is the point of the design;
- manual ``shard_map`` bodies (sequence.py, ring.py, pipeline.py) call
  ``jax.lax`` collectives directly, plus the two composite primitives
  below that encode actual cross-shard logic.

Earlier revisions also re-exported one-line ``lax.*`` delegates here; they
had no callers and no added semantics, so they were removed — this module
keeps only primitives that earn their name.
"""

from __future__ import annotations

from typing import Union

import jax
from jax import lax


Array = jax.Array
Axis = Union[str, tuple]


def ppermute_shift(x: Array, axis: str, shift: int = 1) -> Array:
    """Rotate shards around the ring: device i -> device (i+shift) % n —
    the neighbor-to-neighbor ICI hop ring attention (ring.py) runs on.
    (pipeline.py's stage rotation builds the same perm inline.)"""
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def exclusive_prefix_sum(x_local: Array, axis: Axis) -> Array:
    """Σ over shards j < my_index of per-shard partials — the cross-shard
    combine for sequence-parallel linear attention (sequence.py): each
    shard's kv-cumsum state is corrected by the sum of every earlier
    shard's. all_gather the tiny per-shard tensors, then a masked sum
    (axis sizes are small; O(sp) memory is nothing)."""
    import jax.numpy as jnp

    gathered = lax.all_gather(x_local, axis)  # [sp, ...]
    n = gathered.shape[0]
    idx = lax.axis_index(axis)
    mask = (jnp.arange(n) < idx).astype(gathered.dtype)
    mask = mask.reshape((n,) + (1,) * (gathered.ndim - 1))
    return jnp.sum(gathered * mask, axis=0)


__all__ = ["ppermute_shift", "exclusive_prefix_sum"]
