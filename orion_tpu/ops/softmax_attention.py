"""Exact softmax attention: full, causal, and sliding-window — XLA path.

The reference runs softmax attention for the LRA comparison configs and
sliding-window softmax layers inside the 7B hybrid model (BASELINE.json
north_star; the reference checkout was never mounted — SURVEY.md §0). This
module is the pure-XLA implementation used as (a) the parity reference for
the Pallas flash kernel and (b) the fallback on CPU and for mask shapes the
kernel doesn't cover. ``ops/pallas/flash_attention.py`` is the TPU-native
fast path (online softmax, no T×T materialization).

Conventions: q, k, v are per-head tensors [..., T, D]; softmax in fp32;
output in input dtype. ``window=w`` means each query attends to keys
s ∈ (t-w, t] (its own position plus w-1 predecessors).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array

_NEG = -1e30  # large-negative instead of -inf: keeps all-masked rows NaN-free


def _build_mask(
    t_q: int,
    t_k: int,
    causal: bool,
    window: Optional[int],
    offset: int = 0,
) -> Optional[Array]:
    """Boolean [Tq, Tk] mask (True = attend). ``offset`` shifts query rows,
    for decode-time queries positioned at the end of a longer key sequence."""
    if not causal and window is None:
        return None
    row = jnp.arange(t_q)[:, None] + offset
    col = jnp.arange(t_k)[None, :]
    m = jnp.ones((t_q, t_k), dtype=bool)
    if causal:
        m &= row >= col
    if window is not None:
        m &= (row - col) < window
    return m


def softmax_attention_xla(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    mask: Optional[Array] = None,
    scale: Optional[float] = None,
) -> Array:
    """Materializing softmax attention (the parity/fallback path).

    ``mask``: optional boolean, broadcastable to [..., Tq, Tk] (True=attend);
    combined with the causal/window mask. A key-padding mask [..., Tk] is
    accepted and broadcast over queries.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    qf = q.astype(jnp.float32) * scale
    scores = jnp.einsum("...td,...sd->...ts", qf, k.astype(jnp.float32))

    m = _build_mask(q.shape[-2], k.shape[-2], causal, window)
    if mask is not None:
        # accept key-padding [..., Tk] (expand over queries) or anything
        # already broadcastable against [..., Tq, Tk] (dim -2 == Tq or 1)
        if mask.ndim < 2 or mask.shape[-2] not in (1, q.shape[-2]):
            mask = mask[..., None, :]
        m = mask if m is None else (m & mask)
    if m is not None:
        scores = jnp.where(m, scores, _NEG)

    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("...ts,...sd->...td", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def softmax_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    mask: Optional[Array] = None,
    scale: Optional[float] = None,
    backend: str = "auto",
    block_q: int = 512,
    block_k: int = 512,
) -> Array:
    """Dispatching softmax attention: Pallas flash on TPU, XLA elsewhere.

    Arbitrary ``mask`` tensors force the XLA path (the flash kernel covers
    the structured causal/window masks only).
    """
    from orion_tpu.ops.dispatch import resolve

    b = resolve(backend)
    if b in ("pallas", "pallas_interpret") and mask is None:
        from orion_tpu.ops.pallas import flash_attention as fa

        return fa.flash_attention(
            q,
            k,
            v,
            causal=causal,
            window=window,
            scale=scale,
            block_q=block_q,
            block_k=block_k,
            interpret=(b == "pallas_interpret"),
        )
    return softmax_attention_xla(
        q, k, v, causal=causal, window=window, mask=mask, scale=scale
    )


def cached_attention(
    q: Array,
    k_cache: Array,
    v_cache: Array,
    valid: Array,
    *,
    scale: Optional[float] = None,
    with_lse: bool = False,
):
    """Decode-step attention of a single query over a KV cache.

    q: [..., D]; caches: [..., S, D]; valid: boolean [..., S] marking filled
    slots (works for both the growing full cache and the sliding-window ring
    buffer, where slot order ≠ time order — softmax is permutation-invariant
    over keys, so ring-buffer rotation needs no unrotation). ``with_lse``:
    (the output in fp32, the log-sum-exp of the valid scores [...]), for a
    caller that merges this key set with another. q ``[..., H, D]`` over
    caches ``[..., KV, S, D]`` of fewer heads is a GROUPED cache: ``H / KV``
    query heads to each KV head, head ``h`` reading ``h // (H / KV)``.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    shape = q.shape
    grouped = k_cache.ndim == q.ndim + 1 and shape[-2] != k_cache.shape[-3]
    if grouped:
        kvh = k_cache.shape[-3]
        q = q.reshape(shape[:-2] + (kvh, shape[-2] // kvh, shape[-1]))
        k_cache, v_cache = k_cache[..., None, :, :], v_cache[..., None, :, :]
        valid = valid[..., None, :]
    qf = q.astype(jnp.float32) * scale
    scores = jnp.einsum("...d,...sd->...s", qf, k_cache.astype(jnp.float32))
    scores = jnp.where(valid, scores, _NEG)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("...s,...sd->...d", p, v_cache.astype(jnp.float32))
    if grouped:
        out, scores = out.reshape(shape), scores.reshape(shape[:-1] + scores.shape[-1:])
    if with_lse:
        return out, jax.nn.logsumexp(scores, axis=-1)
    return out.astype(q.dtype)


def cached_block_attention(
    q: Array, k_cache: Array, v_cache: Array, lengths: Array, lists: Array,
    counts: Array, size: int,
):
    """Decode-step attention of a query GROUP over listed cache blocks: q
    ``[B, KV, G, Dh]``, caches ``[B, KV, cap, Dh]``, ``lists`` ``[B, KV, L]``
    the blocks of ``size`` rows each (sequence, KV head) attends to, of
    which the first ``counts`` ``[B, KV]`` count and rows ``< lengths`` [B]
    are live. Gathers the listed blocks ``[B, KV, L, size, Dh]``, masks what
    lies past the count or the length -> (out ``[B, KV, G, Dh]``, lse ``[B,
    KV, G]``) in fp32; ``(0, -1e30)`` where the key set is empty."""
    f32 = jnp.float32
    b, kvh, cap, d = k_cache.shape
    width = lists.shape[-1]

    def tiles(c):
        return jnp.take_along_axis(
            c.reshape(b, kvh, cap // size, size, d), lists[..., None, None], axis=2
        ).astype(f32)

    at = lists[..., None] * size + jnp.arange(size)  # [B, KV, L, size]
    live = (jnp.arange(width)[None, None, :, None] < counts[..., None, None]) & (
        at < lengths[:, None, None, None]
    )
    s = jnp.einsum("bkgd,bklsd->bkgls", q.astype(f32), tiles(k_cache)) * d ** -0.5
    s = jnp.where(live[:, :, None], s, _NEG).reshape(*s.shape[:3], -1)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.where(live[:, :, None].reshape(b, kvh, 1, -1), jnp.exp(s - lse[..., None]), 0.0)
    v = jnp.where(live[..., None], tiles(v_cache), 0.0).reshape(b, kvh, -1, d)
    empty = ~jnp.any(live, axis=(-2, -1))[..., None]
    out = jnp.where(empty[..., None], 0.0, jnp.einsum("bkgs,bksd->bkgd", p, v))
    return out, jnp.where(empty, _NEG, lse)


def cached_row_attention(
    q: Array, k_cache: Array, v_cache: Array, lists: Array, counts: Array,
):
    """Decode-step attention over a LIST OF ROWS a sequence, shared by every
    KV head: q ``[B, KV, G, Dh]``, caches ``[B, cap, KV Dh]`` (a token's K,
    and its V, one contiguous row), ``lists`` ``[B, L]`` the cache rows
    sequence ``b`` attends to, of which the first ``counts`` ``[B]`` count.
    Gathers the listed rows ``[B, L, KV Dh]`` and nothing else of the cache
    -> (out ``[B, KV, G, Dh]``, lse ``[B, KV, G]``) in fp32; ``(0, -1e30)``
    where the list is empty."""
    f32 = jnp.float32
    b, kvh, _, d = q.shape

    def listed(c):
        return jnp.take_along_axis(c, lists[..., None], axis=1).reshape(b, -1, kvh, d)

    live = (jnp.arange(lists.shape[-1]) < counts[:, None])[:, None, None]  # [B, 1, 1, L]
    s = jnp.einsum(
        "bkgd,blkd->bkgl", q, listed(k_cache).astype(q.dtype), preferred_element_type=f32
    ) * d ** -0.5
    s = jnp.where(live, s, _NEG)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.where(live, jnp.exp(s - lse[..., None]), 0.0)
    out = jnp.einsum(
        "bkgl,blkd->bkgd", p.astype(v_cache.dtype), listed(v_cache),
        preferred_element_type=f32,
    )
    empty = (counts == 0)[:, None, None]
    return jnp.where(empty[..., None], 0.0, out), jnp.where(empty, _NEG, lse)


__all__ = [
    "softmax_attention",
    "softmax_attention_xla",
    "cached_attention",
    "cached_block_attention",
    "cached_row_attention",
]
