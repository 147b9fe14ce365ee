"""``latent``: causal softmax attention whose keys and values pass a shared
low-rank LATENT (multi-head latent attention), and whose decode state is
that latent instead of what attention multiplies by.

Per token at position p (``H`` heads; widths ``cfg.latent_*``)::

    c_q = N(x W_qa)                       [q_rank]
    q_h = c_q W_qb                        [nope | rope] a head
    [c_kv | k_r] = x W_kva;  c = N(c_kv)  [kv_rank], [rope]
    q_rope, k_rope = RoPE_p(q_rope), RoPE_p(k_r)   one k_rope for all heads
    [k_nope_h | v_h] = c W_kvb            a head
    score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . k_rope(s))
                    / sqrt(nope + rope)
    out = [softmax_s(score_h) v_h]_h W_o

The cache holds ``(c, k_rope)`` and nothing else: ``kv_rank + rope`` numbers
a token (576 at the served widths against 40,960 for 128 heads of K and V).
Two forms of the same mathematics:

- **expand** (``__call__``, ``prefill``, ``prefill_extend``): the latent is
  multiplied out into per-head K and V and attention runs over those. A
  prompt piece expands the latent rows before it a block at a time, as many
  blocks as its offset has (not the reservation), and merges the blocks'
  softmaxes by their log-sum-exps.
- **absorb** (``decode_step``): ``W_kvb``'s key half goes into the query,
  ``qt_h = q_nope_h (W_kvb^K_h)^T`` [kv_rank], its value half onto the
  output, ``o_h = (sum_s p c(s)) W_kvb^V_h``, and attention runs over the
  latent itself: every head's key is the row ``[c | k_rope]`` and its value
  the same row's ``c`` (``ops.dispatch.latent_cache_attention``: a kernel
  under a Pallas backend with a row list, one fetch of a latent block for
  scores and values).

The slot-multiplexed decode step writes one cache row per LISTED sequence
(``rows_in_place``), and under a donated carry the scan holds the cache and
carries a chunk's own rows (``chunk_split`` / ``chunk_merge``), as
``softmax.py`` does for K and V.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.mixers import Mixer, State, _dense_factory, _dtype, drawn_in
from orion_tpu.models.mixers.softmax import _window_write, merge_chunk_rows
from orion_tpu.ops.dispatch import latent_cache_attention, resolve
from orion_tpu.ops.rotary import apply_rotary, rotary_freqs
from orion_tpu.ops.softmax_attention import _NEG
from orion_tpu.utils.profiling import scope, scoped

Array = jax.Array

_scoped = scoped("latent_attention")
# the flash kernel's q / k width: [nope | rope | 1 mask column | zeros]
_LANE = 128


def _merge(a: Tuple[Array, Array], b: Tuple[Array, Array]) -> Tuple[Array, Array]:
    """Two softmaxes over disjoint key sets, each (out fp32, lse [..., 1]),
    as the softmax over their union."""
    (oa, la), (ob, lb) = a, b
    w = jax.nn.sigmoid(la - lb)  # a's share of the joint mass
    return ob + w * (oa - ob), jnp.logaddexp(la, lb)


class LatentAttention(Mixer):
    layer_type: str = "latent"

    rows_in_place = True
    cache_leaves = ("c", "kr")

    def setup(self):
        cfg = self.cfg
        assert not self.quant, "the latent layers have no weight-streamed form"
        h = cfg.n_heads
        dn, dr, dv = cfg.latent_nope_dim, cfg.latent_rope_dim, cfg.latent_value_dim
        dt = _dtype(cfg.dtype)
        dense = _dense_factory(cfg)
        self.wq_a = dense("wq_a", cfg.latent_q_rank)
        self.q_norm = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=dt, name="q_norm")
        self.wq_b = dense("wq_b", h * (dn + dr))
        self.wkv_a = dense("wkv_a", cfg.latent_kv_rank + dr)
        self.kv_norm = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=dt, name="kv_norm")
        # held as an array: decode multiplies by its halves a head
        self.wkv_b = self.param(
            "wkv_b", drawn_in(cfg, nn.linear.default_kernel_init),
            (cfg.latent_kv_rank, h * (dn + dv)), _dtype(cfg.param_dtype),
        )
        self.wo = dense("wo", cfg.d_model)
        self.freqs = rotary_freqs(dr, cfg.max_seq_len, cfg.rotary_base)

    @property
    def _scale(self) -> float:
        return (self.cfg.latent_nope_dim + self.cfg.latent_rope_dim) ** -0.5

    # -- projections ----------------------------------------------------------

    def _project(self, x: Array, ang: Array):
        """x ``[..., D]`` at angles ``ang`` (broadcastable to ``[..., rope /
        2]``) -> q_nope ``[..., H, nope]``, q_rope ``[..., H, rope]`` and the
        cache row's two parts c ``[..., kv_rank]``, k_rope ``[..., rope]``."""
        cfg = self.cfg
        dn, dr, r = cfg.latent_nope_dim, cfg.latent_rope_dim, cfg.latent_kv_rank
        q = self.wq_b(self.q_norm(self.wq_a(x)))
        q = q.reshape(*x.shape[:-1], cfg.n_heads, dn + dr)
        kva = self.wkv_a(x)
        c, kr = self.kv_norm(kva[..., :r]), kva[..., r:]
        q_rope = apply_rotary(q[..., dn:], ang[..., None, :])
        return q[..., :dn], q_rope, c, apply_rotary(kr, ang)

    def _kv_b(self) -> Tuple[Array, Array]:
        """``W_kvb`` a head: keys ``[kv_rank, H, nope]``, values ``[kv_rank,
        H, value]``."""
        cfg = self.cfg
        dn = cfg.latent_nope_dim
        w = self.wkv_b.astype(_dtype(cfg.dtype)).reshape(
            cfg.latent_kv_rank, cfg.n_heads, dn + cfg.latent_value_dim
        )
        return w[..., :dn], w[..., dn:]

    def _expand(self, c: Array) -> Tuple[Array, Array]:
        """Latent rows ``[B, S, kv_rank]`` -> k_nope ``[B, H, S, nope]``, v
        ``[B, H, S, value]``."""
        with scope("latent_expand"):
            wk, wv = self._kv_b()
            c = c.astype(wk.dtype)
            return (
                jnp.einsum("bsc,chd->bhsd", c, wk),
                jnp.einsum("bsc,chd->bhsd", c, wv),
            )

    def _attend(self, q_nope, q_rope, k_nope, kr, v, *, causal: bool,
                valid: Optional[Array] = None) -> Tuple[Array, Array]:
        """Expanded attention: q ``[B, H, T, .]`` over keys ``[k_nope_h |
        k_rope]`` ``[B, H, S, .]`` (``kr`` ``[B, S, rope]`` shared by the
        heads) and v ``[B, H, S, value]``; ``causal`` with query row i at key
        row i; ``valid`` ``[S]`` bool masks key rows -> (out ``[B, H, T,
        value]`` fp32, lse ``[B, H, T, 1]``). A Pallas backend runs the flash
        kernel at q / k width 256: the two parts, one column that carries the
        key mask (q 1, k 0 or -1e30) and zeros."""
        f32 = jnp.float32
        b = resolve(self.cfg.backend)
        if b.startswith("pallas"):
            from orion_tpu.ops.pallas.flash_attention import flash_attention_lse

            dt = q_nope.dtype
            h, s = k_nope.shape[1], k_nope.shape[2]
            width = q_nope.shape[-1] + q_rope.shape[-1]
            pad = -(-(width + 1) // _LANE) * _LANE - width - 1
            one = jnp.ones(q_nope.shape[:-1] + (1,), dt)
            q = jnp.concatenate(
                [q_nope, q_rope, one, jnp.zeros(one.shape[:-1] + (pad,), dt)], -1
            )
            mask = jnp.zeros((s,), f32) if valid is None else jnp.where(valid, 0.0, _NEG)
            mask = jnp.broadcast_to(mask.astype(dt)[None, None, :, None], k_nope.shape[:-1] + (1,))
            k = jnp.concatenate([
                k_nope, jnp.broadcast_to(kr[:, None].astype(dt), (kr.shape[0], h) + kr.shape[1:]),
                mask, jnp.zeros(mask.shape[:-1] + (pad,), dt),
            ], -1)
            out, lse = flash_attention_lse(
                q, k, v.astype(dt), causal=causal, scale=self._scale,
                block_q=self.cfg.attn_block_q, block_k=self.cfg.attn_block_k,
                interpret=(b == "pallas_interpret"),
            )
            return out.astype(f32), lse
        keep = jnp.ones((q_nope.shape[2], k_nope.shape[2]), bool)
        if causal:
            keep = jnp.tril(keep)
        if valid is not None:
            keep = keep & valid[None, :]
        return self._masked(q_nope, q_rope, k_nope, kr, v, keep)

    def _masked(self, q_nope, q_rope, k_nope, kr, v, keep: Array):
        """The XLA form of :meth:`_attend` under an arbitrary ``[T, S]`` mask."""
        f32 = jnp.float32
        s = jnp.einsum("bhtd,bhsd->bhts", q_nope.astype(f32), k_nope.astype(f32))
        s = s + jnp.einsum("bhtd,bsd->bhts", q_rope.astype(f32), kr.astype(f32))
        s = jnp.where(keep, s * self._scale, _NEG)
        lse = jax.nn.logsumexp(s, axis=-1, keepdims=True)
        out = jnp.einsum("bhts,bhsd->bhtd", jnp.exp(s - lse), v.astype(f32))
        return out, lse

    def _out(self, o: Array) -> Array:
        """Per-head values ``[B, H, T, value]`` -> ``[B, T, D]``."""
        o = jnp.swapaxes(o, 1, 2).astype(_dtype(self.cfg.dtype))
        return self.wo(o.reshape(*o.shape[:2], -1))

    # -- parallel forward -----------------------------------------------------

    def _forward(self, x: Array):
        t = x.shape[-2]
        q_nope, q_rope, c, kr = self._project(x, self.freqs[:t])
        k_nope, v = self._expand(c)
        out, _ = self._attend(
            jnp.swapaxes(q_nope, 1, 2), jnp.swapaxes(q_rope, 1, 2),
            k_nope, kr, v, causal=True,
        )
        return self._out(out), c, kr

    @_scoped
    def __call__(self, x: Array, mask: Optional[Array] = None) -> Array:
        assert mask is None and self.causal, "latent attention is causal-LM only"
        assert not self.sp_local and not self._sp_active(), "no sequence-parallel form"
        return self._forward(x)[0]

    # -- serving --------------------------------------------------------------

    @staticmethod
    def cache_rows(cfg: ModelConfig, layer_type: str) -> int:
        return cfg.max_seq_len

    @staticmethod
    def cache_rows_read(cfg: ModelConfig, layer_type: str, length: int):
        from orion_tpu.ops.pallas.cache_attention import latent_rows_read

        return latent_rows_read(length, cfg.max_seq_len)

    @staticmethod
    def decode_state(
        cfg: ModelConfig, layer_type: str, batch: int, dtype: Any
    ) -> State:
        cap = cfg.max_seq_len
        return {
            "c": jnp.zeros((batch, cap, cfg.latent_kv_rank), dtype),
            "kr": jnp.zeros((batch, cap, cfg.latent_rope_dim), dtype),
        }

    @staticmethod
    def chunk_split(
        cfg: ModelConfig, layer_type: str, state: State, n_steps: int,
        t: Array, donated: bool,
    ) -> Tuple[State, State]:
        """Where the carry is ``donated`` the latent cache is held (read-only
        in the scan); the scan carries the chunk's own rows ``cn``, ``krn``
        ``[B, n_steps, .]`` and the positions ``t0`` it started at."""
        if not donated:
            return {}, state
        new = {
            n + "n": jnp.zeros((state[n].shape[0], n_steps, state[n].shape[-1]), state[n].dtype)
            for n in ("c", "kr")
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
            n: merge_chunk_rows(
                held[n][:, None], carried[n + "n"][:, None], carried["t0"], live
            )[:, 0]
            for n in ("c", "kr")
        }

    @_scoped
    def prefill(self, x: Array, length: Optional[Array] = None) -> Tuple[Array, State]:
        """With ``length``: the padded rows land at cache rows >= length,
        which decode never reads (step t overwrites row t before attending
        and reads rows <= t)."""
        out, c, kr = self._forward(x)
        pad = ((0, 0), (0, self.cfg.max_seq_len - x.shape[-2]), (0, 0))
        return out, {"c": jnp.pad(c, pad), "kr": jnp.pad(kr, pad)}

    @_scoped
    def prefill_extend(
        self, x: Array, state: State, offset: Array, length: Array
    ) -> Tuple[Array, State]:
        """A piece EXPANDS: its own rows (causal), and the latent rows before
        ``offset`` a block of the piece's width at a time, as many blocks as
        ``offset`` has. Under an XLA backend one softmax over the whole
        reservation under a mask (the sizes the CPU runs)."""
        p = x.shape[-2]
        cap = state["c"].shape[1]
        pos = jnp.clip(offset + jnp.arange(p), 0, self.freqs.shape[0] - 1)
        q_nope, q_rope, c, kr = self._project(x, jnp.take(self.freqs, pos, axis=0))
        q_nope, q_rope = jnp.swapaxes(q_nope, 1, 2), jnp.swapaxes(q_rope, 1, 2)
        real = (jnp.arange(p) < length)[None, None, :, None]
        new = {
            n: _window_write(state[n][:, None], rows[:, None], offset, real)[:, 0]
            for n, rows in (("c", c), ("kr", kr))
        }
        if not resolve(self.cfg.backend).startswith("pallas") or cap < p:
            k_nope, v = self._expand(new["c"])
            row = jnp.arange(p)[:, None] + offset
            keep = row >= jnp.arange(cap)[None, :]
            out, _ = self._masked(q_nope, q_rope, k_nope, new["kr"], v, keep)
            return self._out(out), new
        k_nope, v = self._expand(c)
        own = self._attend(q_nope, q_rope, k_nope, kr, v, causal=True)

        def block(j, acc):
            # the reservation's last block starts where it still fits: the
            # rows it shares with the block before count once
            at = jnp.minimum(j * p, cap - p)
            cb = jax.lax.dynamic_slice_in_dim(state["c"], at, p, axis=1)
            kb = jax.lax.dynamic_slice_in_dim(state["kr"], at, p, axis=1)
            kn, vb = self._expand(cb)
            rows = at + jnp.arange(p)
            part = self._attend(
                q_nope, q_rope, kn, kb, vb, causal=False,
                valid=(rows >= j * p) & (rows < offset),
            )
            return _merge(part, acc)

        n_blocks = (jnp.clip(offset, 0, cap) + p - 1) // p
        out, _ = jax.lax.fori_loop(0, n_blocks, block, own)
        return self._out(out), new

    def _chunk_local(self, qt, qr, c, kr, state, t, rows):
        """The absorbed step inside a scan that holds the cache read-only
        (:meth:`chunk_split`): this token's latent goes to row ``t - t0`` of
        the chunk's own rows; the held rows before ``t0`` and the chunk's
        rows up to it are attended apart and merged by their log-sum-exps."""
        f32 = jnp.float32
        b_idx = jnp.arange(qt.shape[0])
        j = t - state["t0"]
        new = dict(
            state,
            cn=state["cn"].at[b_idx, j].set(c.astype(state["cn"].dtype)),
            krn=state["krn"].at[b_idx, j].set(kr.astype(state["krn"].dtype)),
        )
        held = latent_cache_attention(
            qt, qr, state["c"], state["kr"], state["t0"], rows,
            scale=self._scale, backend=self.cfg.backend,
        )
        cn = new["cn"].astype(f32)
        s = jnp.einsum("bhc,bnc->bhn", qt.astype(f32), cn)
        s = s + jnp.einsum("bhr,bnr->bhn", qr.astype(f32), new["krn"].astype(f32))
        s = jnp.where(jnp.arange(s.shape[-1]) <= j[:, None, None], s * self._scale, _NEG)
        lse = jax.nn.logsumexp(s, axis=-1, keepdims=True)
        own = jnp.einsum("bhn,bnc->bhc", jnp.exp(s - lse), cn)
        u, _ = _merge((held[0], held[1][..., None]), (own, lse))
        return u, new

    @_scoped
    def decode_step(
        self, x: Array, state: State, t: Array, rows: Optional[Any] = None
    ) -> Tuple[Array, State]:
        t = jnp.asarray(t)
        per_seq = t.ndim == 1
        dt = _dtype(self.cfg.dtype)
        q_nope, q_rope, c, kr = self._project(x, self.freqs[t])
        with scope("latent_absorb"):
            wk, wv = self._kv_b()
            qt = jnp.einsum(
                "bhd,chd->bhc", q_nope, wk, preferred_element_type=jnp.float32
            ).astype(dt)
            if "cn" in state:
                u, new = self._chunk_local(qt, q_rope, c, kr, state, t, rows)
            else:
                new = self._write_row(state, c, kr, t, rows)
                lengths = (t if per_seq else jnp.full((x.shape[0],), t)) + 1
                u, _ = latent_cache_attention(
                    qt, q_rope, new["c"], new["kr"], lengths,
                    rows if per_seq else None,
                    scale=self._scale, backend=self.cfg.backend,
                )
            o = jnp.einsum(
                "bhc,chd->bhd", u.astype(dt), wv, preferred_element_type=jnp.float32
            ).astype(dt)
        return self.wo(o.reshape(o.shape[0], -1)), new

    @staticmethod
    def _write_row(state: State, c: Array, kr: Array, t: Array, rows) -> State:
        """This token's latent into cache row ``t``: one in-place slice
        update per LISTED sequence (an unlisted one writes nothing), one
        scatter row a sequence without a list, one slice at a scalar ``t``."""
        parts = {"c": c, "kr": kr}
        if t.ndim == 1 and rows is not None:
            idx, count = rows

            def write(i, caches):
                b = idx[i]
                return {
                    n: jax.lax.dynamic_update_slice(
                        cache, parts[n][b][None, None].astype(cache.dtype), (b, t[b], 0)
                    )
                    for n, cache in caches.items()
                }

            return jax.lax.fori_loop(0, count[0], write, dict(state))
        if t.ndim == 1:
            b_idx = jnp.arange(c.shape[0])
            return {
                n: state[n].at[b_idx, t].set(parts[n].astype(state[n].dtype))
                for n in parts
            }
        return {
            n: jax.lax.dynamic_update_slice_in_dim(
                state[n], parts[n][:, None].astype(state[n].dtype), t, axis=1
            )
            for n in parts
        }
