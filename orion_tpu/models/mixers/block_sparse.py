"""``block_sparse``: causal softmax attention of ``n_heads`` query heads over
``n_kv_heads`` grouped KV heads (``G = n_heads / n_kv_heads`` query heads
share one), with per-head RMSNorm of q and k (``cfg.qk_norm == "head"``), no
rotary, a sigmoid output gate, and past ``cfg.sparse_dense_len`` positions a
learned selection of key BLOCKS (the InfLLM-v2 / MiniCPM4 family):

    q = rmsh(W_q u),  k = rmsh(W_k u),  v = W_v u
    kp_j = mean(k[stride j : stride j + kernel])            (pooled keys)
    p^h_ij = softmax_j(q^h_i . kp_j / sqrt(Dh))  over j with stride j + kernel <= i + 1
    s_ij = sum_{h in group} p^h_ij
    score_ib = max of s_ij over the pooled keys that overlap block b
    selected(i) = the first ``init_blocks`` blocks, the blocks of the last
        ``window`` tokens, and the highest-scoring others, ``topk`` in all
    o^h_i = softmax over s <= i in selected(i) blocks of (q^h_i . k_s / sqrt(Dh)) v_s
    out = W_o( merge(o) * sigmoid(W_g u) )

one selection per (token, KV head). A position with ``i + 1 <=
sparse_dense_len`` attends to every ``s <= i``: the switch is taken PER
POSITION, so a piece, a decode step and a whole forward agree whatever the
chunking.

The decode state has three leaves: ``{"k", "v"}`` ``[B, KV, cap, Dh]`` and
the selector's pooled keys ``{"kp"}`` ``[B, KV, cap / stride, Dh]`` in the
cache dtype; pooled row ``j`` is written when its last key arrives (a piece
writes all that complete inside it). One token: the selector scores the
slot's live pooled rows, takes the overlap maximum, forces the initial and
local blocks and takes ``top_k``: a list of at most ``L = max(topk,
dense_len / block)`` blocks per (sequence, KV head), which ``ops.dispatch.
cache_attention(blocks=...)`` attends over (a kernel that fetches only the
listed blocks under a Pallas backend with a row list; a gather otherwise).
A position still under ``dense_len`` lists all of its blocks, so one call
serves both sides of the switch. Given a row list the step writes cache
rows for the LISTED sequences only (``rows_in_place``). A prompt piece
attends densely under a mask built from the same selection, a tile of
query rows at a time over the shortest of a few static key lengths that
holds the piece's end. A decode scan that holds the carry once
(``chunk_split``) reads K and V and carries a chunk's own rows and the
pooled keys, as ``softmax.py`` does for its cache. Speculative decode is
not built: the base class's raise.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.mixers import Mixer, State, _dense_factory
from orion_tpu.models.mixers.softmax import chunk_local_attention, merge_chunk_rows
from orion_tpu.ops.dispatch import cache_attention, decode_rows_mask
from orion_tpu.utils.profiling import scope, scoped

Array = jax.Array

_FORCED = 1e4  # above any score: a score is a sum of G probabilities
_QUERY_TILE = 256  # query rows of a prompt piece scored at a time
_KEY_LENGTHS = 4  # static key lengths a prompt piece chooses from


_scoped = scoped("sparse_attention")


def list_width(cfg: ModelConfig) -> int:
    """Blocks a decode step's list holds: ``topk`` past ``dense_len``, every
    block of a position still under it."""
    return max(cfg.sparse_topk, -(-cfg.sparse_dense_len // cfg.sparse_block))


def blocks_read(cfg: ModelConfig, length: int) -> int:
    """Blocks one decode step attends to for a sequence of ``length`` live
    cache rows (the position attended from is ``length - 1``)."""
    live = -(-length // cfg.sparse_block)
    return live if length <= cfg.sparse_dense_len else min(live, cfg.sparse_topk)


def block_priority(cfg: ModelConfig, q: Array, kp: Array, pos: Array) -> Array:
    """What the selector ranks blocks by: q ``[B, KV, G, Q, Dh]``, pooled
    keys ``kp`` ``[B, KV, NP, Dh]``, query positions ``pos`` ``[B, Q]`` ->
    ``[B, KV, Q, NB]`` fp32: ``_FORCED`` for a forced block, the block's
    score for another block at or before the query's own, -1 past it."""
    f32 = jnp.float32
    stride, kernel, block = cfg.sparse_stride, cfg.sparse_kernel, cfg.sparse_block
    ratio = block // stride
    n_pooled = kp.shape[-2]
    nb = n_pooled // ratio
    with scope("sparse_select"):
        s = jnp.einsum(
            "bkgqd,bkjd->bkgqj", q, kp.astype(q.dtype), preferred_element_type=f32
        ) * q.shape[-1] ** -0.5
        # pooled key j is visible once its last token is: stride j + kernel <= i + 1
        visible = (jnp.arange(n_pooled) * stride + kernel) <= (pos[..., None] + 1)
        visible = visible[:, None, None]  # [B, 1, 1, Q, NP]
        s = jnp.where(visible, s, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.where(visible, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
        den = jnp.sum(e, axis=-1, keepdims=True)
        p = e / jnp.where(den == 0.0, 1.0, den)
        shared = jnp.where(visible[:, :, 0], jnp.sum(p, axis=2), -jnp.inf)  # [B, KV, Q, NP]
        # block b overlaps pooled keys ratio b - 1 .. ratio b + ratio - 1
        groups = shared[..., : nb * ratio].reshape(*shared.shape[:-1], nb, ratio)
        inside = jnp.max(groups, axis=-1)
        before = jnp.concatenate(
            [jnp.full_like(inside[..., :1], -jnp.inf), groups[..., :-1, -1]], axis=-1
        )
        score = jnp.maximum(inside, before)  # [B, KV, Q, NB]
        b = jnp.arange(nb)
        cur = (pos // block)[:, None, :, None]
        forced = (b < cfg.sparse_init_blocks) | (
            (b >= cur - (cfg.sparse_window // block - 1)) & (b <= cur)
        )
        ranked = jnp.where((b <= cur) & jnp.isfinite(score), score, -1.0)
        return jnp.where(forced, _FORCED, ranked)


class BlockSparseAttention(Mixer):
    layer_type: str = "block_sparse"

    rows_in_place = True

    def setup(self):
        cfg = self.cfg
        assert self.causal, "block_sparse is causal-LM only"
        assert not self.sp_local and not self._sp_active(), "no sequence parallel form"
        h, kvh = cfg.n_heads, cfg.n_kv_heads or cfg.n_heads
        assert h % kvh == 0, (h, kvh)
        _check_selector(cfg)
        self._setup_qkvo(kv_heads=kvh)
        self.wg = _dense_factory(cfg, self.quant, self.mesh)(
            "wg", h * cfg.resolved_head_dim
        )

    cache_leaves = ("k", "v")

    @staticmethod
    def cache_rows(cfg: ModelConfig, layer_type: str) -> int:
        return cfg.max_seq_len

    @staticmethod
    def cache_rows_read(cfg: ModelConfig, layer_type: str, length: int):
        return cfg.sparse_block * blocks_read(cfg, length)

    @staticmethod
    def decode_state(
        cfg: ModelConfig, layer_type: str, batch: int, dtype: Any
    ) -> State:
        _check_selector(cfg)
        kvh, dh, cap = cfg.n_kv_heads or cfg.n_heads, cfg.resolved_head_dim, cfg.max_seq_len
        return {
            "k": jnp.zeros((batch, kvh, cap, dh), dtype),
            "v": jnp.zeros((batch, kvh, cap, dh), dtype),
            "kp": jnp.zeros((batch, kvh, cap // cfg.sparse_stride, dh), dtype),
        }

    @staticmethod
    def chunk_split(
        cfg: ModelConfig, layer_type: str, state: State, n_steps: int,
        t: Array, donated: bool,
    ) -> Tuple[State, State]:
        """Where the carry is ``donated`` K and V are held (read-only in the
        scan); the scan carries the chunk's own rows ``kn``, ``vn`` [B, KV,
        n_steps, Dh], the positions ``t0`` it started at and the pooled
        keys whole (1 / 32 of K + V). The chunk's rows lie in the blocks
        every one of its steps is forced to select. A program that returns
        a new carry carries everything."""
        if not donated:
            return {}, state
        assert n_steps <= cfg.sparse_window, (n_steps, cfg.sparse_window)
        b, kvh, _, dh = state["k"].shape
        new = {
            n + "n": jnp.zeros((b, kvh, n_steps, dh), state[n].dtype)
            for n in ("k", "v")
        }
        held = {"k": state["k"], "v": state["v"]}
        return held, {**new, "kp": state["kp"], "t0": t}

    @staticmethod
    def chunk_merge(
        cfg: ModelConfig, layer_type: str, held: State, carried: State,
        live: Array,
    ) -> State:
        if not held:
            return carried
        merged = {
            n: merge_chunk_rows(held[n], carried[n + "n"], carried["t0"], live)
            for n in ("k", "v")
        }
        return {**merged, "kp": carried["kp"]}

    def _out(self, o: Array, x: Array) -> Array:
        """o [B, H, T, Dh] (or [B, H, Dh]) -> gated and projected back."""
        if x.ndim == 3:
            o = jnp.swapaxes(o, -3, -2)
        merged = o.reshape(*o.shape[:-2], -1).astype(x.dtype)
        return self.wo(merged * jax.nn.sigmoid(self.wg(x)))

    def _grouped(self, q: Array) -> Array:
        """[B, H, ...] -> [B, KV, G, ...]."""
        kvh = self.cfg.n_kv_heads or self.cfg.n_heads
        return q.reshape(q.shape[0], kvh, q.shape[1] // kvh, *q.shape[2:])

    # -- the parallel forms: a prompt, or one piece of it ---------------------

    def _extend(self, x, state, offset, length):
        cfg = self.cfg
        q, k, v = self._heads(x)  # [B, H, P, Dh], [B, KV, P, Dh] x 2
        p = x.shape[-2]
        real = (jnp.arange(p) < length)[None, None, :, None]
        kc = _write_rows(state["k"], k, offset, real)
        vc = _write_rows(state["v"], v, offset, real)
        kp = _pool_completed(cfg, state["kp"], kc, offset, length, p)
        cap = kc.shape[-2]
        pos = jnp.clip(offset + jnp.arange(p), 0, cap - 1)
        qg = self._grouped(q)  # [B, KV, G, P, Dh]
        tile = min(p, _QUERY_TILE)
        pad = (-p) % tile
        if pad:
            qg = jnp.pad(qg, ((0, 0),) * 3 + ((0, pad), (0, 0)))
            pos = jnp.pad(pos, (0, pad), mode="edge")
        # the shortest static key length that holds the piece's last row
        nb = cap // cfg.sparse_block
        step = -(-nb // _KEY_LENGTHS) * cfg.sparse_block
        sizes = [min(cap, (i + 1) * step) for i in range(-(-cap // step))]

        def attend_upto(size):
            def run(qg, pos, kc, vc, kp):
                return _masked_attention(
                    cfg, qg, pos, kc[:, :, :size], vc[:, :, :size],
                    kp[:, :, : size // cfg.sparse_stride], tile,
                )
            return run

        need = jnp.clip(offset + p, 1, cap)
        o = jax.lax.switch(
            (need - 1) // step, [attend_upto(n) for n in sizes], qg, pos, kc, vc, kp
        )
        o = o[..., :p, :].reshape(q.shape)
        return self._out(o, x), {"k": kc, "v": vc, "kp": kp}

    @_scoped
    def __call__(self, x: Array, mask: Optional[Array] = None) -> Array:
        assert mask is None, "block_sparse has no masked forward"
        return self.prefill(x)[0]

    @_scoped
    def prefill(self, x: Array, length: Optional[Array] = None) -> Tuple[Array, State]:
        """The piece form from an empty cache: rows past ``length`` (bucket
        padding) are not written, so the state is an unpadded prefill's."""
        t = x.shape[-2]
        state = self.decode_state(self.cfg, self.layer_type, x.shape[0], x.dtype)
        return self._extend(x, state, 0, t if length is None else length)

    @_scoped
    def prefill_extend(
        self, x: Array, state: State, offset: Array, length: Array
    ) -> Tuple[Array, State]:
        return self._extend(x, state, offset, length)

    # -- one-token decode -------------------------------------------------------

    @_scoped
    def decode_step(
        self, x: Array, state: State, t: Array, rows: Optional[Any] = None
    ) -> Tuple[Array, State]:
        cfg = self.cfg
        b = x.shape[0]
        t = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (b,))
        q, k, v = self._heads(x)  # [B, H, Dh], [B, KV, Dh] x 2
        local = "kn" in state  # inside a scan that holds K and V: chunk_split
        write = _write_chunk_token if local else _write_token
        new = write(cfg, state, k, v, t, rows)
        qg = self._grouped(q)[:, :, :, None, :]  # [B, KV, G, 1, Dh]
        priority = block_priority(cfg, qg, new["kp"], t[:, None])[:, :, 0]  # [B, KV, NB]
        with scope("sparse_select"):
            width = list_width(cfg)
            cur = t // cfg.sparse_block
            dense = (t + 1 <= cfg.sparse_dense_len)[:, None, None]
            chosen = jax.lax.top_k(priority, min(cfg.sparse_topk, priority.shape[-1]))[1]
            chosen = jnp.pad(chosen, ((0, 0), (0, 0), (0, width - chosen.shape[-1])))
            lists = jnp.where(dense, jnp.arange(width), chosen).astype(jnp.int32)
            counts = jnp.where(
                dense[..., 0], (cur + 1)[:, None], jnp.minimum(cur + 1, cfg.sparse_topk)[:, None]
            )
            counts = jnp.broadcast_to(counts, lists.shape[:2]).astype(jnp.int32)
        blocks = (lists, counts, cfg.sparse_block)
        if local:
            o = chunk_local_attention(q, new, t, rows, cfg.backend, blocks)
        else:
            o, _ = cache_attention(
                q, new["k"], new["v"], t + 1, rows, backend=cfg.backend, blocks=blocks
            )
        return self._out(o, x), new


def _check_selector(cfg: ModelConfig) -> None:
    k, s, b = cfg.sparse_kernel, cfg.sparse_stride, cfg.sparse_block
    forced = cfg.sparse_init_blocks + cfg.sparse_window // b
    ok = (
        k == 2 * s and b % s == 0 and cfg.sparse_window % b == 0
        and cfg.max_seq_len % b == 0 and forced <= cfg.sparse_topk
        and cfg.sparse_dense_len >= cfg.sparse_topk * b
    )
    if not ok:
        raise ValueError(
            "block_sparse needs kernel = 2 stride, block and window whole "
            "strides / blocks, max_seq_len whole blocks, init_blocks + window "
            "/ block <= topk and dense_len >= topk x block; got "
            f"kernel {k} stride {s} block {b} window {cfg.sparse_window} topk "
            f"{cfg.sparse_topk} dense_len {cfg.sparse_dense_len} max_seq_len "
            f"{cfg.max_seq_len}"
        )


def _write_rows(cache: Array, rows: Array, offset: Array, real: Array) -> Array:
    """Rows ``[B, KV, P, Dh]`` into the cache at ``offset``; pad rows
    (``real`` False) and rows past the cache keep what it held (a scatter
    that writes the cache's own value back, as ``softmax._window_write``)."""
    p = rows.shape[-2]
    pos = jnp.clip(offset + jnp.arange(p), 0, cache.shape[-2] - 1)
    cur = jnp.take(cache, pos, axis=2)
    return cache.at[:, :, pos, :].set(jnp.where(real, rows.astype(cache.dtype), cur))


def _pool_completed(cfg, kp, kc, offset, length, p):
    """The pooled rows whose last key lies in ``[offset, offset + length)``,
    each the mean of its ``kernel`` keys in the updated cache ``kc``, written
    into ``kp``; every other pooled row keeps its bits."""
    stride, kernel = cfg.sparse_stride, cfg.sparse_kernel
    n_pooled = kp.shape[-2]
    first = jnp.maximum(-(-(offset - kernel + 1) // stride), 0)
    j = first + jnp.arange(p // stride + 2)
    last = j * stride + kernel - 1  # the row's last key
    done = (last >= offset) & (last < offset + length) & (j < n_pooled)
    at = jnp.clip(j[:, None] * stride + jnp.arange(kernel), 0, kc.shape[-2] - 1)
    keys = jnp.take(kc, at.reshape(-1), axis=2).reshape(*kc.shape[:2], *at.shape, -1)
    pooled = jnp.mean(keys.astype(jnp.float32), axis=-2).astype(kp.dtype)
    cur = jnp.take(kp, jnp.clip(j, 0, n_pooled - 1), axis=2)
    new = jnp.where(done[None, None, :, None], pooled, cur)
    return kp.at[:, :, jnp.where(j < n_pooled, j, n_pooled), :].set(new, mode="drop")


def _masked_attention(cfg, qg, pos, kc, vc, kp, tile):
    """A piece's queries ``qg`` [B, KV, G, P, Dh] at positions ``pos`` [P]
    over the first rows of the cache given, ``tile`` query rows at a time:
    dense scores under the selection's mask -> [B, KV, G, P, Dh] fp32."""
    f32 = jnp.float32
    b, kvh, g, p, d = qg.shape
    block, nb = cfg.sparse_block, kc.shape[-2] // cfg.sparse_block
    col = jnp.arange(kc.shape[-2])

    def one(args):
        qt, pt = args  # [B, KV, G, tile, Dh], [tile]
        where = jnp.broadcast_to(pt[None], (b, tile))
        priority = block_priority(cfg, qt, kp, where)  # [B, KV, tile, NB]
        with scope("sparse_select"):
            chosen = jax.lax.top_k(priority, min(cfg.sparse_topk, nb))[1]
            picked = jnp.any(chosen[..., None] == jnp.arange(nb), axis=-2)
            dense = (pt + 1 <= cfg.sparse_dense_len)[None, None, :, None]
            blocks = jnp.where(dense, True, picked)  # [B, KV, tile, NB]
        mask = jnp.repeat(blocks, block, axis=-1) & (col <= pt[:, None])
        s = jnp.einsum("bkgqd,bksd->bkgqs", qt, kc.astype(qt.dtype),
                       preferred_element_type=f32) * d ** -0.5
        s = jnp.where(mask[:, :, None], s, -1e30)
        s = s - jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s)
        w = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(vc.dtype)
        return jnp.einsum("bkgqs,bksd->bkgqd", w, vc, preferred_element_type=f32)

    tiles = jnp.moveaxis(qg.reshape(b, kvh, g, p // tile, tile, d), 3, 0)
    out = jax.lax.map(one, (tiles, pos.reshape(p // tile, tile)))
    return jnp.moveaxis(out, 0, 3).reshape(b, kvh, g, p, d)


def _write_token(cfg, state, k, v, t, rows):
    """This token's k and v into cache row ``t`` of each sequence, and the
    pooled row whose last key it is (if any): for the LISTED sequences only
    where a row list is given (one in-place slice update each, as
    ``softmax.SoftmaxAttention.decode_step``), for all otherwise."""
    kernel = cfg.sparse_kernel
    kc, vc, kp = state["k"], state["v"], state["kp"]
    ends, j, first = _pooled_row_ending(cfg, kp, t)
    if rows is not None:
        idx, count = rows

        def write(i, caches):
            kc, vc, kp = caches
            b = idx[i]
            kc, vc = (
                jax.lax.dynamic_update_slice(
                    c, new[b][None, :, None, :].astype(c.dtype), (b, 0, t[b], 0)
                )
                for c, new in ((kc, k), (vc, v))
            )
            keys = jax.lax.dynamic_slice(
                kc, (b, 0, first[b], 0), (1, kc.shape[1], kernel, kc.shape[-1])
            )
            pooled = jnp.mean(keys.astype(jnp.float32), axis=2, keepdims=True)
            old = jax.lax.dynamic_slice(kp, (b, 0, j[b], 0), (1, kp.shape[1], 1, kp.shape[-1]))
            kp = jax.lax.dynamic_update_slice(
                kp, jnp.where(ends[b], pooled.astype(kp.dtype), old), (b, 0, j[b], 0)
            )
            return kc, vc, kp

        kc, vc, kp = jax.lax.fori_loop(0, count[0], write, (kc, vc, kp))
        return {"k": kc, "v": vc, "kp": kp}
    b_idx = jnp.arange(t.shape[0])
    kc = kc.at[b_idx, :, t, :].set(k.astype(kc.dtype))
    vc = vc.at[b_idx, :, t, :].set(v.astype(vc.dtype))
    at = first[:, None] + jnp.arange(kernel)
    return {"k": kc, "v": vc, "kp": _pool_keys(kp, _rows_at(kc, at), ends, j)}


def _pooled_row_ending(cfg, kp, t):
    """For positions ``t`` [B]: whether ``t`` is the LAST key of a pooled
    row, that row (clipped), and the position of the row's first key."""
    stride, kernel = cfg.sparse_stride, cfg.sparse_kernel
    n_pooled = kp.shape[-2]
    row = (t + 1 - kernel) // stride
    ends = ((t + 1 - kernel) % stride == 0) & (t + 1 >= kernel) & (row < n_pooled)
    return ends, jnp.clip(row, 0, n_pooled - 1), jnp.maximum(t + 1 - kernel, 0)


def _rows_at(cache, at):
    """``cache`` [B, KV, N, Dh] at positions ``at`` [B, n] (clipped)."""
    at = jnp.clip(at, 0, cache.shape[-2] - 1)
    return jnp.take_along_axis(cache, at[:, None, :, None], axis=2)


def _pool_keys(kp, keys, ends, j):
    """The mean of ``keys`` [B, KV, kernel, Dh] into pooled row ``j`` [B] of
    the sequences where ``ends``; the others keep theirs."""
    b_idx = jnp.arange(j.shape[0])
    pooled = jnp.mean(keys.astype(jnp.float32), axis=2).astype(kp.dtype)
    new = jnp.where(ends[:, None, None], pooled, kp[b_idx, :, j, :])
    return kp.at[b_idx, :, j, :].set(new)


def _write_chunk_token(cfg, state, k, v, t, rows):
    """The step's writes inside a scan that holds K and V read-only
    (``chunk_split``): this token's k and v go to row ``t - t0`` of the
    chunk's own rows, and the pooled row whose last key it is takes its
    keys from the held cache before ``t0`` and from the chunk's rows from
    there on. A sequence that is not emitting holds its ``t``: it rewrites
    one row of ``kn`` / ``vn`` that ``chunk_merge`` never reads; given a row
    list, the pooled keys of an unlisted sequence keep their bits
    (``rows_in_place``)."""
    kn, vn, t0 = state["kn"], state["vn"], state["t0"]
    b_idx = jnp.arange(t.shape[0])
    kn = kn.at[b_idx, :, t - t0, :].set(k.astype(kn.dtype))
    vn = vn.at[b_idx, :, t - t0, :].set(v.astype(vn.dtype))
    ends, j, first = _pooled_row_ending(cfg, state["kp"], t)
    if rows is not None:
        ends = ends & decode_rows_mask(rows, t.shape[0])
    kernel = cfg.sparse_kernel
    at = first[:, None] + jnp.arange(kernel)

    def held_keys(b):
        # a slice a sequence: for a gather the TPU compiler lays the held
        # cache out anew, a copy of it
        size = (1, kn.shape[1], kernel, kn.shape[-1])
        return jax.lax.dynamic_slice(state["k"], (b, 0, first[b], 0), size)[0]

    keys = jnp.where(
        (at < t0[:, None])[:, None, :, None],
        jax.lax.map(held_keys, b_idx), _rows_at(kn, at - t0[:, None]),
    )
    return dict(state, kn=kn, vn=vn, kp=_pool_keys(state["kp"], keys, ends, j))
