"""Ring attention: exact softmax attention over sp-sharded sequences
(SURVEY.md P6).

Long-context softmax layers can't use the kv-cumsum trick — the keys
themselves must visit every query. Ring attention streams them: each sp
shard holds its local Q and rotates the (K, V) block around the ring via
``ppermute`` (neighbor-to-neighbor over ICI — the TPU-native form of the
reference's long-context NCCL path; reference checkout never mounted —
SURVEY.md §0), folding each incoming block into a running online-softmax
accumulator (m, l, acc) — flash attention with the block loop unrolled
across chips, compute and ICI transfers overlapping.

Causal masking by block index: an incoming block j (vs my index i) is
fully visible if j < i, diagonal (intra-block causal) if j == i, and
skipped if j > i — skipped blocks still rotate (the ring must complete)
but contribute zero compute via ``lax.cond``.

That skip is load-IMBALANCED: shard 0 skips n-1 of its n steps while
shard n-1 skips none, and the per-step ppermute chains each step onto the
busiest shard — the causal ring's critical path is ~2× its average work.
``striped=True`` fixes it with the striped layout (tokens dealt
round-robin: global token g lives on shard g % n at local row g // n, via
one in-ring all_to_all per tensor): every (i, j) block pair is then a
near-triangular mask of the SAME size, so all shards do equal work on
every step and no block is ever fully masked. Exact (softmax is
permutation-invariant over keys; the online accumulator handles any
arrival order); full-causal only (a sliding window striped across shards
would touch every block and lose swa's locality — window keeps the
contiguous ring).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from orion_tpu.parallel.collectives import ppermute_shift

Array = jax.Array

_NEG = -1e30


def _block_attend(q, k, v, m, l, acc, scale, mask):
    """Fold one (K, V) block into the online-softmax accumulator."""
    s = jnp.einsum(
        "...td,...sd->...ts", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.einsum("...ts,...sd->...td", p, v.astype(jnp.float32))
    return m_new, l_new, acc_new


def _lse_merge(m, l, acc, o_j, lse_j):
    """Fold one flash block result (o_j normalized within block, lse_j)
    into the running (m, l, acc) online-softmax accumulator. The explicit
    empty-block guard (rather than trusting exp(lse - m_new) to
    underflow) keeps the merge correct even while the running m is still
    at its -1e30 init — i.e. independent of block visit order."""
    m_new = jnp.maximum(m, lse_j)
    alpha = jnp.exp(m - m_new)
    w_j = jnp.where(lse_j <= _NEG / 2, 0.0, jnp.exp(lse_j - m_new))
    l = l * alpha + w_j
    acc = acc * alpha + o_j.astype(jnp.float32) * w_j
    return m_new, l, acc


def swa_halo_attention_local(
    q: Array,
    k: Array,
    v: Array,
    axis: str = "sp",
    *,
    window: int,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> Array:
    """Sliding-window attention over sp-sharded tokens as a HALO exchange,
    not a ring: a query only reaches W-1 tokens back, so shard i needs the
    previous h = ceil((W-1) / T_local) blocks, nothing more. Gather them with
    h neighbor ppermutes and run h+1 flash kernel calls — the local
    causal+window block plus one per halo block at STATIC query offset
    m*T_local (ops/pallas/flash_attention.py q_offset) — merged by
    log-sum-exp. Cost: O(h) collectives per layer instead of the ring's
    n, and every matmul is a Mosaic kernel (this runs inside the fully
    manual sp shard_map).

    Shards with fewer than h predecessors skip the missing blocks via
    lax.cond (their contribution is exactly empty), so no wrapped garbage
    is ever read. Exact vs the global windowed softmax; differentiable
    (kernel VJP incl. the lse cotangent).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    from orion_tpu.ops.pallas.flash_attention import flash_attention_lse

    n = lax.axis_size(axis)
    i = lax.axis_index(axis)
    t_loc = q.shape[-2]
    # a query reaches back window-1 tokens, so the deepest halo block is
    # ceil((window-1)/t_loc) — W % t_loc == 1 (incl. W=1) needs one FEWER
    # block than ceil(W/t_loc) would fetch
    h = min(-(-(window - 1) // t_loc), n - 1)

    o, lse = flash_attention_lse(
        q, k, v, causal=True, window=window, scale=scale, interpret=interpret
    )
    m_run = jnp.full_like(lse, _NEG)
    l = jnp.zeros_like(lse)
    acc = jnp.zeros_like(o, dtype=jnp.float32)
    m_run, l, acc = _lse_merge(m_run, l, acc, o, lse)

    k_m, v_m = k, v
    for m in range(1, h + 1):
        # after m shifts this holds the block of shard i - m
        k_m = ppermute_shift(k_m, axis)
        v_m = ppermute_shift(v_m, axis)

        def blk(_, k_blk=k_m, v_blk=v_m, off=m * t_loc):
            return flash_attention_lse(
                q, k_blk, v_blk, causal=True, window=window,
                q_offset=off, scale=scale, interpret=interpret,
            )

        def empty(_):
            return jnp.zeros_like(o), jnp.full_like(lse, _NEG)

        o_m, lse_m = lax.cond(i >= m, blk, empty, None)
        m_run, l, acc = _lse_merge(m_run, l, acc, o_m, lse_m)

    safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / safe).astype(q.dtype)


def _to_striped(x: Array, axis: str, n: int) -> Array:
    """Contiguous shard layout -> striped: local row p ends up holding
    global token p*n + i. One all_to_all; NOT self-inverse — the local
    shuffle differs on the way back (``_from_striped``)."""
    t_loc, d = x.shape[-2], x.shape[-1]
    x4 = x.reshape(*x.shape[:-2], t_loc // n, n, d)
    x4 = jnp.swapaxes(x4, -3, -2)  # [..., n(dest), t_loc/n, d]
    y = lax.all_to_all(x4, axis, split_axis=x4.ndim - 3,
                       concat_axis=x4.ndim - 3, tiled=False)
    return y.reshape(*x.shape[:-2], t_loc, d)


def _from_striped(x: Array, axis: str, n: int) -> Array:
    """Inverse of ``_to_striped`` (the same exchange, inverse local
    shuffle: received chunk from source s goes back to rows s*n-strided)."""
    t_loc, d = x.shape[-2], x.shape[-1]
    x4 = x.reshape(*x.shape[:-2], n, t_loc // n, d)
    y = lax.all_to_all(x4, axis, split_axis=x4.ndim - 3,
                       concat_axis=x4.ndim - 3, tiled=False)
    y = jnp.swapaxes(y, -3, -2)  # [..., t_loc/n, n(src), d]
    return y.reshape(*x.shape[:-2], t_loc, d)


def ring_attention_local(
    q: Array,
    k: Array,
    v: Array,
    axis: str = "sp",
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    striped: bool = False,
    backend: str = "xla",
) -> Array:
    """shard_map body: q,k,v LOCAL [..., T/sp, D] shards; exact softmax
    attention over the full (global) sequence. ``window`` gives the
    sliding-window variant (query t sees keys (t-window, t]) so the 7B
    hybrid's swa layers can ride the same ring. ``striped`` switches to
    the load-balanced striped layout (module docstring) — full-causal
    only.

    ``backend="pallas"`` (striped only) runs each per-step block through
    the flash kernel (ops/pallas/flash_attention.py::flash_attention_lse —
    legal here: the enclosing sp shard_map is fully manual, so Mosaic
    lowers) and merges blocks by log-sum-exp; gradients flow through the
    kernel's custom VJP including the lse cotangent. The default XLA body
    is the einsum online-softmax fold."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n = lax.axis_size(axis)
    i = lax.axis_index(axis)
    t_loc = q.shape[-2]
    if striped:
        # real raises (not asserts): wrong numerics under -O would be silent
        if not causal or window is not None:
            raise ValueError(
                "striped ring is the full-causal form; swa keeps the "
                "contiguous ring (a striped window loses locality)"
            )
        if t_loc % n != 0:
            raise ValueError(
                f"striped ring needs T/sp divisible by sp (T_local={t_loc}, "
                f"sp={n}) so the layout exchange tiles evenly"
            )
        q, k, v = (_to_striped(x, axis, n) for x in (q, k, v))

    from orion_tpu.ops.dispatch import resolve

    b = resolve(backend)
    use_kernel = striped and b in ("pallas", "pallas_interpret")

    local_row = jnp.arange(t_loc)[:, None]
    local_col = jnp.arange(t_loc)[None, :]

    # derive initializers from q so they carry the same device-varying type
    # as the loop-body outputs (shard_map vma rules for lax.cond branches)
    zq = q[..., :1].astype(jnp.float32) * 0.0
    m0 = zq + _NEG
    l0 = zq
    acc0 = zq * jnp.zeros((v.shape[-1],), jnp.float32)

    def body(step, carry):
        k_blk, v_blk, m, l, acc = carry
        j = (i - step) % n  # origin shard of the block currently held
        if striped and use_kernel:
            # flash-kernel block + lse merge. The causal shift (strict
            # triangle when the kv stripe's phase is ahead) must be STATIC
            # for the kernel's tile-skip predicates, so both variants are
            # compiled and lax.cond picks per step — still one kernel
            # execution per step.
            from orion_tpu.ops.pallas.flash_attention import (
                flash_attention_lse,
            )

            def blk(shift):
                def f(_):
                    return flash_attention_lse(
                        q, k_blk, v_blk, causal=True, shift=shift,
                        scale=scale, interpret=(b == "pallas_interpret"),
                    )

                return f

            o_j, lse_j = lax.cond(j <= i, blk(0), blk(1), None)
            m, l, acc = _lse_merge(m, l, acc, o_j, lse_j)
        elif striped:
            # striped layout: my row p holds global token p*n + i, the
            # block's col c holds c*n + j -> attend iff c < p, plus the
            # diagonal c == p when j <= i. Near-triangular EVERY step:
            # equal work on every shard, nothing to skip.
            mask = (local_col < local_row) | (
                (local_col == local_row) & (j <= i)
            )
            m, l, acc = _block_attend(
                q, k_blk, v_blk, m, l, acc, scale, mask
            )
        else:
            rows = i * t_loc + local_row  # absolute positions (via i, j)
            cols = j * t_loc + local_col
            mask = jnp.ones((t_loc, t_loc), bool)
            if causal:
                mask &= rows >= cols
            if window is not None:
                mask &= (rows - cols) < window
            needs_mask = causal or window is not None

            def attend(args):
                m, l, acc = args
                return _block_attend(
                    q, k_blk, v_blk, m, l, acc, scale,
                    mask if needs_mask else None,
                )

            def skip(args):
                return args

            if needs_mask:
                m, l, acc = lax.cond(jnp.any(mask), attend, skip, (m, l, acc))
            else:
                m, l, acc = attend((m, l, acc))

        # rotate kv to the next device; after n-1 steps every block visited
        k_nxt = ppermute_shift(k_blk, axis)
        v_nxt = ppermute_shift(v_blk, axis)
        return k_nxt, v_nxt, m, l, acc

    _, _, m, l, acc = lax.fori_loop(0, n, body, (k, v, m0, l0, acc0))
    safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc / safe).astype(q.dtype)
    if striped:
        out = _from_striped(out, axis, n)
    return out


def ring_attention(
    q: Array,
    k: Array,
    v: Array,
    mesh: Mesh,
    *,
    axis: str = "sp",
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    striped: bool = False,
    backend: str = "xla",
) -> Array:
    """Global entry: q,k,v [B, H, T, D] with T sharded over ``axis``."""
    from orion_tpu.ops.dispatch import resolve

    spec = P(("dp", "fsdp"), "tp", axis, None)
    fn = shard_map(
        partial(
            ring_attention_local, axis=axis, causal=causal, window=window,
            scale=scale, striped=striped, backend=backend,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # vma on except under interpret-mode kernels, which cannot trace
        # under the check (same constraint and reasoning as sequence.py)
        check_vma=(resolve(backend) != "pallas_interpret"),
    )
    return fn(q, k, v)


def swa_halo_attention(
    q: Array,
    k: Array,
    v: Array,
    mesh: Mesh,
    *,
    axis: str = "sp",
    window: int,
    scale: Optional[float] = None,
    backend: str = "auto",
) -> Array:
    """Global entry for the halo form of sp sliding-window attention:
    q,k,v [B, H, T, D] with T sharded over ``axis``. Non-pallas resolved
    backends (xla, or auto off-TPU) delegate to the windowed contiguous
    ring — the halo body is kernel-only."""
    from orion_tpu.ops.dispatch import resolve

    b = resolve(backend)
    if not b.startswith("pallas"):
        return ring_attention(
            q, k, v, mesh, axis=axis, causal=True, window=window,
            scale=scale, backend=b,
        )
    spec = P(("dp", "fsdp"), "tp", axis, None)
    fn = shard_map(
        partial(
            swa_halo_attention_local, axis=axis, window=window, scale=scale,
            interpret=(b == "pallas_interpret"),
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=(b != "pallas_interpret"),
    )
    return fn(q, k, v)


__all__ = [
    "ring_attention",
    "ring_attention_local",
    "swa_halo_attention",
    "swa_halo_attention_local",
]
