"""``indexed``: grouped causal softmax attention in which every token attends
to the ``cfg.index_topk`` earlier tokens that a learned INDEXER scores
highest for it (the DeepSeek-sparse-attention family), one selection a token
shared by all of its heads. With ``u`` the layer's normed input:

    q = rmsh(W_q u) [H x Dh]   k = rmsh(W_k u) [KV x Dh]   v = W_v u [KV x Dh]
    q, k rotated by position over the whole head (halves paired, cfg.rotary_base)
    qI_t = rot(W_qI u_t) [IH x ID]   kI_s = rot(LN(W_kI u_s)) [ID]
    w_t = W_w u_t * IH^-1/2 * ID^-1/2 [IH]
    I_ts = sum_j w_tj relu(qI_tj . kI_s),  s <= t            (fp32)
    S_t  = the min(index_topk, t + 1) positions s <= t of largest I_ts
           (equal scores: the lower s)
    o_t^h = sum_{s in S_t} softmax_{s in S_t}(q_t^h . k_s^{h // G} / sqrt(Dh)) v_s^{h // G}
    out = W_o merge(o)

built on ``softmax.py``'s class (its q / k / v / o projections, per-head
norms and grouped heads). A position under ``index_topk`` selects all of its
rows, so one program serves both sides.

The decode state has three leaves, each a token a ROW: ``{"k", "v"}`` ``[B,
cap, KV Dh]`` (a listed token is one contiguous row of K and one of V) and
the indexer's key ``{"ki"}`` ``[B, cap, ID]``, in the cache dtype, written
together at the token's position. One token: the indexer scores the slot's
live ``ki`` rows (read whole: 1 / 16 of K + V at the served widths), the
exact top-``index_topk`` is found by a search on the scores' bit patterns
(``ops/topk_select.py``: no sort) and turned into a list, and
``ops.dispatch.cache_attention(row_list=...)`` attends over the LISTED rows
of K and V only. Given a row list of sequences the step writes cache rows
for the listed sequences only (``rows_in_place``). A prompt piece attends
densely under the same selection's mask (a per-row threshold = the k-th
largest score, ties cut to the lower position) over the shortest of a few
static key lengths that holds the piece's end, as ``block_sparse.py`` does:
under a Pallas backend through two Mosaic kernels
(``ops/pallas/indexed_attention.py``: the index scores, and flash attention
under the mask), otherwise as XLA's form, a tile of query rows at a time. A decode scan that holds the carry once
(``chunk_split``) reads all three leaves and carries a chunk's own rows of
each, as ``softmax.py`` does for its cache: those rows are scored, selected
and attended beside the held ones. Speculative decode is not built: the
base class's raise.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.mixers import State, _dense_factory, _dtype
from orion_tpu.models.mixers.softmax import (
    SoftmaxAttention, _window_write, merge_chunk_rows,
)
from orion_tpu.ops.dispatch import cache_attention, resolve
from orion_tpu.ops.rotary import rotary_freqs
from orion_tpu.ops.softmax_attention import _NEG
from orion_tpu.ops.topk_select import mask_to_list, top_k_mask
from orion_tpu.utils.profiling import scope, scoped

Array = jax.Array

_QUERY_TILE = 128  # query rows of a prompt piece scored at a time
_KEY_LENGTHS = 8  # static key lengths a prompt piece chooses from
_KEY_TILE = 512  # ... each a whole number of the attention kernel's key tiles
_LEAVES = ("k", "v", "ki")

_scoped = scoped("indexed_attention")


def rows_listed(cfg: ModelConfig, length: int) -> int:
    """Cache rows one decode step lists for a sequence of ``length`` live
    rows (the position attended from is ``length - 1``)."""
    return min(cfg.index_topk, length)


def rotate_half(x: Array, ang: Array) -> Array:
    """x ``[..., D]`` rotated by ``ang`` ``[..., D / 2]`` (broadcast), dim
    ``j`` paired with ``j + D / 2``."""
    half = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def index_scores(qi: Array, w: Array, ki: Array) -> Array:
    """qi ``[B, Q, IH, ID]``, w ``[B, Q, IH]`` fp32, index keys ``ki`` ``[B,
    S, ID]`` -> ``I`` ``[B, Q, S]`` fp32."""
    s = jnp.einsum(
        "bqhd,bsd->bqhs", qi, ki.astype(qi.dtype), preferred_element_type=jnp.float32
    )
    return jnp.einsum("bqhs,bqh->bqs", jax.nn.relu(s), w)


class IndexedAttention(SoftmaxAttention):
    layer_type: str = "indexed"

    rows_in_place = True
    cache_leaves = _LEAVES

    @staticmethod
    def cache_rows(cfg: ModelConfig, layer_type: str) -> int:
        return cfg.max_seq_len

    @staticmethod
    def cache_rows_read(cfg: ModelConfig, layer_type: str, length: int):
        return rows_listed(cfg, length)

    def setup(self):
        cfg = self.cfg
        assert self.causal, "indexed is causal-LM only"
        assert not self.sp_local and not self._sp_active(), "no sequence parallel form"
        h, kvh = cfg.n_heads, cfg.n_kv_heads or cfg.n_heads
        ih, idim = cfg.index_heads, cfg.index_dim
        assert h % kvh == 0 and cfg.index_topk > 0 and ih > 0 and idim % 2 == 0, (
            h, kvh, cfg.index_topk, ih, idim
        )
        self._setup_qkvo(kv_heads=kvh)
        self.freqs = rotary_freqs(cfg.resolved_head_dim, cfg.max_seq_len, cfg.rotary_base)
        self.index_freqs = rotary_freqs(idim, cfg.max_seq_len, cfg.rotary_base)
        dense = _dense_factory(cfg, self.quant, self.mesh)
        self.wqi = dense("wqi", ih * idim)
        self.wki = dense("wki", idim)
        self.ww = dense("ww", ih)
        self.ki_norm = nn.LayerNorm(
            epsilon=1e-6, dtype=_dtype(cfg.dtype), param_dtype=_dtype(cfg.param_dtype),
            name="ki_norm",
        )

    def _rot(self, x: Array, ang: Array) -> Array:
        return rotate_half(x, ang)

    def _rot_at(self, x: Array, pos: Array) -> Array:
        return rotate_half(x, self.freqs[pos])

    def _indexer(self, x: Array, pos: Array) -> Tuple[Array, Array, Array]:
        """x ``[B, P, D]`` at positions ``pos`` [P] (or ``[B, D]`` at ``[B]``)
        -> (qI ``[..., IH, ID]``, kI ``[..., ID]``, w ``[..., IH]`` fp32)."""
        cfg = self.cfg
        ih, idim = cfg.index_heads, cfg.index_dim
        ang = jnp.take(self.index_freqs, pos, axis=0)
        qi = self.wqi(x).reshape(*x.shape[:-1], ih, idim)
        qi = rotate_half(qi, ang[..., None, :])
        ki = rotate_half(self.ki_norm(self.wki(x)), ang)
        w = self.ww(x).astype(jnp.float32) * (ih * idim) ** -0.5
        return qi, ki, w

    @staticmethod
    def decode_state(
        cfg: ModelConfig, layer_type: str, batch: int, dtype: Any
    ) -> State:
        kvh, dh, cap = cfg.n_kv_heads or cfg.n_heads, cfg.resolved_head_dim, cfg.max_seq_len
        return {
            "k": jnp.zeros((batch, cap, kvh * dh), dtype),
            "v": jnp.zeros((batch, cap, kvh * dh), dtype),
            "ki": jnp.zeros((batch, cap, cfg.index_dim), dtype),
        }

    @staticmethod
    def chunk_split(
        cfg: ModelConfig, layer_type: str, state: State, n_steps: int,
        t: Array, donated: bool,
    ) -> Tuple[State, State]:
        """Where the carry is ``donated`` the three caches are held (read-only
        in the scan); the scan carries the chunk's own rows ``kn``, ``vn``,
        ``kin`` ``[B, n_steps, width]`` and the positions ``t0`` it started
        at. A program that returns a new carry carries everything."""
        if not donated:
            return {}, state
        new = {
            n + "n": jnp.zeros((state[n].shape[0], n_steps, state[n].shape[-1]), state[n].dtype)
            for n in _LEAVES
        }
        return dict(state), {**new, "t0": t}

    @staticmethod
    def chunk_merge(
        cfg: ModelConfig, layer_type: str, held: State, carried: State,
        live: Array,
    ) -> State:
        if not held:
            return carried
        return {
            # softmax's in-place slice update a sequence, on a cache of one head
            n: merge_chunk_rows(held[n][:, None], carried[n + "n"][:, None], carried["t0"], live)[:, 0]
            for n in _LEAVES
        }

    def verify_extend(self, x, state, t):
        self._train_only()

    def advance_verified(self, state, upd, t, keep):
        self._train_only()

    # -- the parallel forms: a prompt, or one piece of it ---------------------

    def _extend(self, x, state, offset, length):
        cfg = self.cfg
        q, k, v = self._heads(x)  # [B, H, P, Dh], [B, KV, P, Dh] x 2
        b, h, p, d = q.shape
        cap = state["k"].shape[1]
        pos = jnp.clip(offset + jnp.arange(p), 0, cap - 1)
        ang = jnp.take(self.freqs, pos, axis=0)
        qr, kr = self._rot(q, ang), self._rot(k, ang)
        qi, ki, w = self._indexer(x, pos)
        real = (jnp.arange(p) < length)[None, :, None]
        as_rows = lambda a: jnp.swapaxes(a, 1, 2).reshape(b, p, -1)  # noqa: E731
        kc = _write_rows(state["k"], as_rows(kr), offset, real)
        vc = _write_rows(state["v"], as_rows(v), offset, real)
        kic = _write_rows(state["ki"], ki, offset, real)
        kvh = kc.shape[-1] // d
        qg = qr.reshape(b, kvh, h // kvh, p, d)
        # the shortest static key length that holds the piece's last row
        step = -(-cap // (_KEY_LENGTHS * _KEY_TILE)) * _KEY_TILE
        sizes = [min(cap, (i + 1) * step) for i in range(-(-cap // step))]
        backend = resolve(cfg.backend)
        # the Mosaic kernels want a KV head a 128-lane block of a cache row
        kernels = backend == "pallas_interpret" or (
            backend == "pallas" and d % 128 == 0 and p % 8 == 0
        )

        def attend_upto(size):
            def run(qg, qi, w, pos, kc, vc, kic):
                caches = kc[:, :size], vc[:, :size], kic[:, :size]
                if kernels:
                    return _selected_attention(
                        cfg, qg, qi, w, pos, *caches, backend == "pallas_interpret"
                    )
                return _masked_attention(cfg, qg, qi, w, pos, *caches)
            return run

        need = jnp.clip(offset + p, 1, cap)
        o = jax.lax.switch(
            (need - 1) // step, [attend_upto(n) for n in sizes], qg, qi, w, pos, kc, vc, kic
        )
        o = o.reshape(q.shape).astype(x.dtype)
        return self._merge(o, single=False), {"k": kc, "v": vc, "ki": kic}

    @_scoped
    def __call__(self, x: Array, mask: Optional[Array] = None) -> Array:
        assert mask is None, "indexed has no masked forward"
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
        qr, kr = self._rot_at(q, t[:, None]), self._rot_at(k, t[:, None])
        qi, ki, w = self._indexer(x, t)
        token = (kr.reshape(b, -1), v.reshape(b, -1), ki)
        local = "kn" in state  # inside a scan that holds the caches: chunk_split
        if local:
            new = _write_chunk_token(state, token, t)
            held = state["t0"]  # the held caches' rows count up to the chunk's start
        else:
            new = _write_token(state, token, t, rows)
            held = t + 1
        cap = new["ki"].shape[1]
        with scope("index_score"):
            scores = index_scores(qi[:, None], w[:, None], new["ki"])[:, 0]  # [B, cap]
            valid = jnp.arange(cap) < held[:, None]
            if local:
                own = index_scores(qi[:, None], w[:, None], new["kin"])[:, 0]
                j = (t - state["t0"])[:, None]
                scores = jnp.concatenate([scores, own], axis=-1)
                valid = jnp.concatenate([valid, jnp.arange(own.shape[-1]) <= j], axis=-1)
        with scope("index_select"):
            # held rows come first and lie at lower positions than the chunk's
            chosen = top_k_mask(scores, valid, cfg.index_topk)
            row_list = mask_to_list(chosen[:, :cap], cfg.index_topk)
        with scope("index_attend"):
            o, lse = cache_attention(
                qr, new["k"], new["v"], held, rows, backend=cfg.backend, row_list=row_list
            )
            if local:
                o = _merge_own_rows(qr, o, lse, new["kn"], new["vn"], chosen[:, cap:])
        return self._merge(o.astype(x.dtype), single=True), new


def _write_rows(cache: Array, rows: Array, offset: Array, real: Array) -> Array:
    """Rows ``[B, P, W]`` into the cache ``[B, cap, W]`` at ``offset``; pad
    rows (``real`` False) and rows past the cache keep what it held:
    ``softmax._window_write`` on a cache of one head."""
    return _window_write(cache[:, None], rows[:, None], offset, real[:, None])[:, 0]


def _selected_attention(cfg, qg, qi, w, pos, kc, vc, kic, interpret):
    """A piece's attention through the Mosaic kernels
    (``ops/pallas/indexed_attention.py``): the whole piece's index scores
    ``[B, P, S]``, the selection's mask from them, and flash attention under
    that mask; no ``[heads, rows, keys]`` intermediate reaches HBM."""
    from orion_tpu.ops.pallas import indexed_attention as pia

    visible = jnp.arange(kc.shape[1]) <= pos[:, None]
    with scope("index_score"):
        scores = pia.index_scores(qi, w, kic, interpret=interpret)
    with scope("index_select"):
        chosen = top_k_mask(scores, visible[None], cfg.index_topk)
    with scope("index_attend"):
        return pia.masked_attention(qg, kc, vc, chosen.astype(jnp.int8), interpret=interpret)


def _masked_attention(cfg, qg, qi, w, pos, kc, vc, kic):
    """The XLA form of a piece's attention: queries ``qg`` [B, KV, G, P, Dh]
    (index queries ``qi`` [B, P, IH, ID], weights ``w`` [B, P, IH]) at
    positions ``pos`` [P] over the first rows of the caches given,
    ``_QUERY_TILE`` query rows at a time: dense scores under the selection's
    mask -> [B, KV, G, P, Dh] fp32."""
    f32 = jnp.float32
    b, kvh, g, p, d = qg.shape
    size = kc.shape[1]
    tile = min(p, _QUERY_TILE)
    pad = (-p) % tile
    if pad:
        qg = jnp.pad(qg, ((0, 0),) * 3 + ((0, pad), (0, 0)))
        qi = jnp.pad(qi, ((0, 0), (0, pad), (0, 0), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0)))
        pos = jnp.pad(pos, (0, pad), mode="edge")
    k4, v4 = kc.reshape(b, size, kvh, d), vc.reshape(b, size, kvh, d)
    col = jnp.arange(size)

    def one(args):
        qt, qit, wt, pt = args  # [B, KV, G, tile, Dh], [B, tile, IH, ID], [B, tile, IH], [tile]
        visible = jnp.broadcast_to(col <= pt[:, None], (b, tile, size))
        with scope("index_score"):
            scores = index_scores(qit, wt, kic)  # [B, tile, size]
        with scope("index_select"):
            chosen = top_k_mask(scores, visible, cfg.index_topk)
        with scope("index_attend"):
            s = jnp.einsum("bkgqd,bskd->bkgqs", qt, k4.astype(qt.dtype),
                           preferred_element_type=f32) * d ** -0.5
            s = jnp.where(chosen[:, None, None], s, _NEG)
            s = s - jnp.max(s, axis=-1, keepdims=True)
            e = jnp.exp(s)
            pr = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(v4.dtype)
            return jnp.einsum("bkgqs,bskd->bkgqd", pr, v4, preferred_element_type=f32)

    n = (p + pad) // tile
    tiles = (
        jnp.moveaxis(qg.reshape(b, kvh, g, n, tile, d), 3, 0),
        jnp.moveaxis(qi.reshape(b, n, tile, *qi.shape[2:]), 1, 0),
        jnp.moveaxis(w.reshape(b, n, tile, w.shape[-1]), 1, 0),
        pos.reshape(n, tile),
    )
    out = jax.lax.map(one, tiles)  # [n, B, KV, G, tile, Dh]
    return jnp.moveaxis(out, 0, 3).reshape(b, kvh, g, p + pad, d)[..., :p, :]


def _write_token(state, token, t, rows):
    """This token's k, v and index key into cache row ``t`` of each
    sequence: for the LISTED sequences only where a row list is given (one
    in-place slice update each, as ``softmax.SoftmaxAttention.decode_step``),
    for all otherwise."""
    caches = tuple(state[n] for n in _LEAVES)
    if rows is not None:
        idx, count = rows

        def write(i, caches):
            b = idx[i]
            return tuple(
                jax.lax.dynamic_update_slice(c, new[b][None, None].astype(c.dtype), (b, t[b], 0))
                for c, new in zip(caches, token)
            )

        caches = jax.lax.fori_loop(0, count[0], write, caches)
    else:
        b_idx = jnp.arange(t.shape[0])
        caches = tuple(
            c.at[b_idx, t, :].set(new.astype(c.dtype)) for c, new in zip(caches, token)
        )
    return dict(zip(_LEAVES, caches))


def _write_chunk_token(state, token, t):
    """The step's writes inside a scan that holds the caches read-only
    (``chunk_split``): this token's rows go to row ``t - t0`` of the
    chunk's own. A sequence that is not emitting holds its ``t``: it
    rewrites one row that ``chunk_merge`` never reads."""
    b_idx, j = jnp.arange(t.shape[0]), t - state["t0"]
    own = {
        n + "n": state[n + "n"].at[b_idx, j, :].set(new.astype(state[n + "n"].dtype))
        for n, new in zip(_LEAVES, token)
    }
    return dict(state, **own)


def _merge_own_rows(q, held, lse_held, kn, vn, chosen):
    """The attention over the held caches' listed rows (``held`` [B, H, Dh]
    fp32 and its log-sum-exp) joined with the chunk's own rows ``kn``, ``vn``
    [B, n, KV Dh] that the selection ``chosen`` [B, n] names: the softmax
    over the two key sets side by side (``softmax.chunk_local_attention``'s
    merge). An empty side weighs nothing."""
    f32 = jnp.float32
    b, h, d = q.shape
    n = kn.shape[1]
    kvh = kn.shape[-1] // d
    qg = (q.astype(f32) * d ** -0.5).reshape(b, kvh, h // kvh, d)
    s = jnp.einsum("bkgd,bnkd->bkgn", qg, kn.reshape(b, n, kvh, d).astype(f32))
    s = jnp.where(chosen[:, None, None], s, _NEG)
    lse_own = jax.nn.logsumexp(s, axis=-1)
    p = jnp.where(chosen[:, None, None], jnp.exp(s - lse_own[..., None]), 0.0)
    own = jnp.einsum("bkgn,bnkd->bkgd", p, vn.reshape(b, n, kvh, d).astype(f32)).reshape(b, h, d)
    share = jax.nn.sigmoid(lse_held - lse_own.reshape(b, h))[..., None]
    return own + share * (held - own)
