"""``softmax`` and ``swa``: causal softmax attention over the whole prefix
or over a sliding window of ``cfg.window`` tokens; q and k are rotated by
position unless ``cfg.rotary`` is off, and RMS-normalised over the whole
projection first under ``cfg.qk_norm`` (``Mixer._heads``). Either
layer takes ``cfg.n_kv_heads`` KV heads where set, ``n_heads / n_kv_heads``
query heads to each (head ``h`` reads KV head ``h // group``), and scales
its scores by ``cfg.attn_scale`` where set instead of ``Dh^-1/2``: q is
multiplied by ``attn_scale * Dh^1/2`` once, before every form below (a power
of two at the served widths: exact).
The decode state is a KV cache ``{"k", "v"}`` of [B, KV, cap, Dh] each:
``cap`` is ``max_seq_len`` rows written at their position, or, for the
window, a ring of ``window`` rows written at position % window. The
slot-multiplexed decode step, given a row list, writes one cache row per
LISTED sequence and nothing for the others (``rows_in_place``): a dense
``[B, H, cap, Dh]`` select over the unlisted rows would read and write the
whole cache a step. With that list, per-sequence positions and the full
cache, decode attention reads a listed sequence's cache up to its position
and nothing of the others (``ops.dispatch.cache_attention``: a kernel under
a Pallas backend); a ring, a scalar position and every XLA backend take
``cached_attention`` over the whole reservation under a mask.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.mixers import Mixer, State
from orion_tpu.ops.dispatch import cache_attention
from orion_tpu.ops.rotary import apply_rotary, apply_rotary_at, rotary_freqs
from orion_tpu.ops.softmax_attention import (
    _NEG, cached_attention, softmax_attention,
)
from orion_tpu.utils.profiling import scoped

Array = jax.Array


_scoped = scoped("full_attention")


def _window(cfg: ModelConfig, layer_type: str) -> Optional[int]:
    return cfg.window if layer_type == "swa" else None


def _kv_heads(cfg: ModelConfig) -> int:
    return cfg.n_kv_heads or cfg.n_heads


class SoftmaxAttention(Mixer):
    layer_type: str = "softmax"

    rows_in_place = True
    cache_leaves = ("k", "v")

    @staticmethod
    def cache_rows(cfg: ModelConfig, layer_type: str) -> int:
        return _window(cfg, layer_type) or cfg.max_seq_len

    @staticmethod
    def cache_rows_read(cfg: ModelConfig, layer_type: str, length: int):
        if _window(cfg, layer_type) is not None:
            return None  # the ring is read whole, under a mask
        from orion_tpu.ops.pallas.cache_attention import rows_read

        return rows_read(length, cfg.max_seq_len)

    def setup(self):
        cfg = self.cfg
        self._setup_qkvo(kv_heads=cfg.n_kv_heads)
        # rotary angle table, a trace-time constant
        self.freqs = rotary_freqs(cfg.resolved_head_dim, cfg.max_seq_len)

    @property
    def window(self) -> Optional[int]:
        return _window(self.cfg, self.layer_type)

    def _heads(self, x: Array) -> Tuple[Array, Array, Array]:
        q, k, v = super()._heads(x)
        a = self.cfg.attn_scale
        if a is not None:  # every form below scales by Dh^-1/2
            q = q * jnp.asarray(a * q.shape[-1] ** 0.5, q.dtype)
        return q, k, v

    def _per_query_head(self, kv: Array) -> Array:
        """``[B, KV, T, Dh]`` -> ``[B, H, T, Dh]``: each KV head repeated for
        its group (the parallel forms; a cache is never repeated)."""
        group = self.cfg.n_heads // kv.shape[1]
        return kv if group == 1 else jnp.repeat(kv, group, axis=1)

    def _rot(self, x: Array, ang: Array) -> Array:
        return apply_rotary(x, ang) if self.cfg.rotary else x

    def _rot_at(self, x: Array, pos: Array) -> Array:
        return apply_rotary_at(x, self.freqs, pos) if self.cfg.rotary else x

    @staticmethod
    def decode_state(
        cfg: ModelConfig, layer_type: str, batch: int, dtype: Any
    ) -> State:
        h, dh = _kv_heads(cfg), cfg.resolved_head_dim
        cap = _window(cfg, layer_type) or cfg.max_seq_len
        return {
            "k": jnp.zeros((batch, h, cap, dh), dtype),
            "v": jnp.zeros((batch, h, cap, dh), dtype),
        }

    @staticmethod
    def chunk_split(
        cfg: ModelConfig, layer_type: str, state: State, n_steps: int,
        t: Array, donated: bool,
    ) -> Tuple[State, State]:
        """Where the carry is ``donated`` the full cache is held (read-only
        in the scan); the scan carries the chunk's own rows ``kn``, ``vn``
        [B, H, n_steps, Dh] and the positions ``t0`` it started at. A
        window's ring wraps inside a chunk and is small: carried whole, as
        is every cache of a program that returns a new carry."""
        if not donated or _window(cfg, layer_type) is not None:
            return {}, state
        b, h, _, dh = state["k"].shape
        new = {
            n + "n": jnp.zeros((b, h, n_steps, dh), state[n].dtype)
            for n in ("k", "v")
        }
        return dict(state), {**new, "t0": t}

    @staticmethod
    def chunk_merge(
        cfg: ModelConfig, layer_type: str, held: State, carried: State,
        live: Array,
    ) -> State:
        """Each live row's chunk of new rows written into its cache at
        ``t0``, one in-place slice update a row, outside any loop (a loop
        that carried the cache would copy it at its entry)."""
        if not held:
            return carried
        return {
            n: merge_chunk_rows(held[n], carried[n + "n"], carried["t0"], live)
            for n in ("k", "v")
        }

    # -- parallel forward ---------------------------------------------------

    @_scoped
    def __call__(self, x: Array, mask: Optional[Array] = None) -> Array:
        cfg = self.cfg
        q, k, v = self._heads(x)
        k, v = self._per_query_head(k), self._per_query_head(v)
        t = x.shape[-2]
        sp = self._sp_active()
        if sp:
            assert t % self.mesh.shape["sp"] == 0, (t, dict(self.mesh.shape))
        if self.sp_local:
            # x is the sp-LOCAL token shard: rotary needs the global
            # positions of this shard's rows
            i = jax.lax.axis_index("sp")
            ang = jax.lax.dynamic_slice_in_dim(self.freqs, i * t, t, axis=0)
        else:
            ang = self.freqs[:t]
        q = self._rot(q, ang)
        k = self._rot(k, ang)
        window = self.window
        # striped = the load-balanced ring (parallel/ring.py): full-
        # causal softmax only; swa keeps the contiguous ring (striping
        # a window loses its locality)
        striped = cfg.ring_striped and window is None
        if self.sp_local and self.causal:
            from orion_tpu.ops.dispatch import resolve
            from orion_tpu.parallel.ring import (
                ring_attention_local,
                swa_halo_attention_local,
            )

            # sp_local_kernels (full-manual pipeline): kernel-backed
            # forms — halo for swa; full-causal softmax gets flash
            # blocks only when cfg.ring_striped is set (the contiguous
            # ring body is XLA regardless of backend). Partial-manual
            # pipelines always use the XLA bodies.
            b = resolve(cfg.backend) if self.sp_local_kernels else "xla"
            if window is not None and b.startswith("pallas"):
                out = swa_halo_attention_local(
                    q, k, v, window=window,
                    interpret=(b == "pallas_interpret"),
                )
            else:
                out = ring_attention_local(
                    q, k, v, causal=True, window=window,
                    striped=striped, backend=b,
                )
        elif sp:
            from orion_tpu.ops.dispatch import resolve
            from orion_tpu.parallel.ring import (
                ring_attention,
                swa_halo_attention,
            )

            if window is not None and resolve(cfg.backend).startswith(
                "pallas"
            ):
                # swa under sp with kernels: halo exchange (O(h)
                # ppermutes + flash blocks at static q_offset) beats
                # the n-step ring — ring.py::swa_halo_attention_local
                out = swa_halo_attention(
                    q, k, v, self.mesh, window=window,
                    backend=cfg.backend,
                )
            else:
                out = ring_attention(
                    q, k, v, self.mesh, causal=True, window=window,
                    striped=striped, backend=cfg.backend,
                )
        elif mask is None and self.causal:
            out = self._kernel_bh(
                lambda a, b, c: softmax_attention(
                    a, b, c, causal=True, window=window,
                    backend=cfg.backend,
                    block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
                ),
                q, k, v,
            )
        else:
            # masked / bidirectional (classifier): mask shapes don't fit
            # the [B, H, ...] manualization — stays on the GSPMD path
            # (xla backend; LRA configs are xla anyway)
            am = None if mask is None else mask[:, None, None, :]
            out = softmax_attention(
                q, k, v, causal=self.causal, window=window,
                mask=am, backend=cfg.backend,
            )
        return self._merge(out, single=False)

    # -- prefill: forward + decode state ------------------------------------

    @_scoped
    def prefill(self, x: Array, length: Optional[Array] = None) -> Tuple[Array, State]:
        """With ``length``: the full cache needs no masking — the padded
        KV rows land at cache slots >= length, which decode never reads:
        step t overwrites slot t before attending and masks slots > t (see
        decode_step); the ring is built from the last ``window`` REAL
        positions via a traced gather/scatter
        (:func:`_swa_cache_from_prefill_dynamic`)."""
        cfg = self.cfg
        q, k, v = self._heads(x)
        t = x.shape[-2]
        ang = self.freqs[:t]
        qr = self._rot(q, ang)
        kr = self._rot(k, ang)
        if self.window is not None:
            out = self._kernel_bh(
                lambda a, b, c: softmax_attention(
                    a, b, c, causal=True, window=cfg.window,
                    backend=cfg.backend,
                    block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
                ),
                qr, self._per_query_head(kr), self._per_query_head(v),
            )
            if length is not None:
                state = _swa_cache_from_prefill_dynamic(
                    kr, v, length, cfg.window
                )
            else:
                state = _swa_cache_from_prefill(kr, v, t, cfg.window)
        else:
            out = self._kernel_bh(
                lambda a, b, c: softmax_attention(
                    a, b, c, causal=True, backend=cfg.backend,
                    block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
                ),
                qr, self._per_query_head(kr), self._per_query_head(v),
            )
            smax = cfg.max_seq_len
            pad = ((0, 0), (0, 0), (0, smax - t), (0, 0))
            state = {"k": jnp.pad(kr, pad), "v": jnp.pad(v, pad)}
        return self._merge(out, single=False), state

    # -- chunked prefill: advance decode state by one prompt piece -----------

    @_scoped
    def prefill_extend(
        self, x: Array, state: State, offset: Array, length: Array
    ) -> Tuple[Array, State]:
        """Per-token projections and rotary are row-stable, so:

        - full cache — the piece's KV rows are written into the cache
          (masked read-modify-write) and the piece's queries attend over
          the WHOLE cache under an offset causal mask; masked lanes are
          exact zeros after softmax, so key-axis padding to the cache
          capacity is reduction-neutral.
        - window — the piece attends over a [W + P] context assembled from
          the ring (position-ordered gather) plus its own rows; the ring
          is then rebuilt from the last W real positions, sourcing each
          row from the piece or the previous ring."""
        from orion_tpu.ops.softmax_attention import softmax_attention_xla

        q, k, v = self._heads(x)
        p = x.shape[-2]
        real = (jnp.arange(p) < length)[None, None, :, None]
        # clipped gather, not dynamic_slice: a garbage offset (the
        # batched stage computes pieces for NON-prefilling rows too,
        # then discards them) must not clamp-shift anything; real rows
        # always sit at in-range positions
        pos = jnp.clip(offset + jnp.arange(p), 0, self.freqs.shape[0] - 1)
        ang = jnp.take(self.freqs, pos, axis=0)
        qr = self._rot(q, ang)
        kr = self._rot(k, ang)
        if self.window is not None:
            out, new_state = self._swa_extend(
                qr, kr, v, state, offset, length, self.window
            )
        else:
            kc = _window_write(state["k"], kr, offset, real)
            vc = _window_write(state["v"], v, offset, real)
            row = jnp.arange(p)[:, None] + offset
            col = jnp.arange(kc.shape[-2])[None, :]
            b, h, _, d = qr.shape
            group = h // kc.shape[1]
            if group == 1:  # traced as it was: no reshape, no tile
                out = softmax_attention_xla(
                    qr, kc, vc, causal=False, mask=row >= col
                )
            else:
                # a group's queries are rows of ONE product against its KV
                # head's cache, which is never repeated
                out = softmax_attention_xla(
                    qr.reshape(b, h // group, group * p, d), kc, vc,
                    causal=False, mask=jnp.tile(row >= col, (group, 1)),
                ).reshape(b, h, p, d)
            new_state = {"k": kc, "v": vc}
        return self._merge(out, single=False), new_state

    def _swa_extend(
        self, qr: Array, kr: Array, v: Array, state: State,
        offset: Array, length: Array, window: int,
    ) -> Tuple[Array, State]:
        """Sliding-window piece attention + ring-buffer advance (see
        :meth:`prefill_extend`). The context is the W positions before the
        piece (gathered from the ring in position order) plus the piece's
        own rows; negative/garbage positions are masked, never read."""
        from orion_tpu.ops.softmax_attention import softmax_attention_xla

        p = qr.shape[-2]
        w = window
        pos_prev = offset - w + jnp.arange(w)  # may be < 0 (masked below)
        slots_prev = pos_prev % w
        kprev = jnp.take(state["k"], slots_prev, axis=2)
        vprev = jnp.take(state["v"], slots_prev, axis=2)
        kctx = jnp.concatenate(
            [kprev, kr.astype(state["k"].dtype)], axis=2
        )
        vctx = jnp.concatenate([vprev, v.astype(state["v"].dtype)], axis=2)
        row = (jnp.arange(p)[:, None] + offset)
        colpos = jnp.concatenate(
            [pos_prev, offset + jnp.arange(p)]
        )[None, :]
        m = (row >= colpos) & (row - colpos < w) & (colpos >= 0)
        out = softmax_attention_xla(
            qr, self._per_query_head(kctx), self._per_query_head(vctx),
            causal=False, mask=m,
        )
        # rebuild the ring as the last W positions before offset+length:
        # rows from this piece where they cover, the previous ring where
        # they don't; slots (pos % W) of W consecutive positions are a
        # permutation, so the scatter is collision-free and deterministic
        t_cur = offset + length
        pos_new = t_cur - w + jnp.arange(w)
        slots_new = pos_new % w
        take = jnp.clip(pos_new - offset, 0, p - 1)
        sel = (pos_new >= offset)[None, None, :, None]
        kc = state["k"].at[:, :, slots_new, :].set(jnp.where(
            sel,
            jnp.take(kr.astype(state["k"].dtype), take, axis=2),
            jnp.take(state["k"], slots_new, axis=2),
        ))
        vc = state["v"].at[:, :, slots_new, :].set(jnp.where(
            sel,
            jnp.take(v.astype(state["v"].dtype), take, axis=2),
            jnp.take(state["v"], slots_new, axis=2),
        ))
        return out, {"k": kc, "v": vc}

    # -- speculative verify: batched re-walk of k decode steps ----------------

    def verify_extend(
        self, x: Array, state: State, t: Array
    ) -> Tuple[Array, State]:
        q, k, v = self._heads(x)  # [B, H, P, Dh]
        to_steps = lambda a: jnp.moveaxis(a, 2, 0)  # noqa: E731
        cap = state["k"].shape[-2]
        b_idx = jnp.arange(x.shape[0])

        def body(carry, qkv):
            kc, vc, tj = carry
            qj, kj, vj = qkv
            # the decode_step per-seq path, one token at a time
            qr = self._rot_at(qj, tj[:, None])
            kr = self._rot_at(kj, tj[:, None])
            slot = tj % cap if self.window is not None else tj
            kc = kc.at[b_idx, :, slot, :].set(kr.astype(kc.dtype))
            vc = vc.at[b_idx, :, slot, :].set(vj.astype(vc.dtype))
            valid = jnp.arange(cap)[None, None, :] <= tj[:, None, None]
            outj = cached_attention(qr, kc, vc, valid)
            return (kc, vc, tj + 1), (outj, kr)

        _, (outs, krs) = jax.lax.scan(
            body, (state["k"], state["v"], t),
            (to_steps(q), to_steps(k), to_steps(v)),
        )
        out = jnp.moveaxis(outs, 0, 2)
        upd = {"k": jnp.moveaxis(krs, 0, 2), "v": v}
        return self._merge(out, single=False), upd

    def advance_verified(
        self, state: State, upd: State, t: Array, keep: Array
    ) -> State:
        """One masked batched scatter: token j writes its (rotary'd) row
        at its own slot when ``j < keep``, else writes the CURRENT cache
        row back (a bitwise no-op). P consecutive positions hit P distinct
        slots (the engine enforces spec depth + 1 <= window), so the
        scatter equals the sequential writes."""
        p = upd["v"].shape[2]
        cap = state["k"].shape[-2]
        pos = t[:, None] + jnp.arange(p)[None, :]  # [B, P]
        # UNclipped for the full cache, exactly like decode_step's slot = t:
        # an overshoot position past the cache capacity must DROP (jax
        # out-of-bounds scatter semantics), not clamp-write — bitwise
        # with the sequential walk either way
        slot = pos % cap if self.window is not None else pos
        b_idx = jnp.arange(t.shape[0])[:, None]
        m = (jnp.arange(p)[None, :] < keep[:, None])[:, :, None, None]
        cur_k = state["k"][b_idx, :, slot, :]  # [B, P, H, Dh]
        cur_v = state["v"][b_idx, :, slot, :]
        new_k = jnp.where(
            m, jnp.moveaxis(upd["k"], 2, 1).astype(state["k"].dtype), cur_k
        )
        new_v = jnp.where(
            m, jnp.moveaxis(upd["v"], 2, 1).astype(state["v"].dtype), cur_v
        )
        return {
            "k": state["k"].at[b_idx, :, slot, :].set(new_k),
            "v": state["v"].at[b_idx, :, slot, :].set(new_v),
        }

    # -- one-token decode ---------------------------------------------------

    def _chunk_local_step(self, qr, kr, v, state, t, rows):
        """The decode step inside a scan that holds the cache read-only
        (:meth:`chunk_split`): this token's k and v go to row ``t - t0`` of
        the chunk's own rows. A sequence that is not emitting holds its
        ``t``, so it rewrites one row of its ``kn`` / ``vn``, which
        :meth:`chunk_merge` then never reads."""
        b_idx = jnp.arange(qr.shape[0])
        j = t - state["t0"]
        new = dict(
            state,
            kn=state["kn"].at[b_idx, :, j, :].set(kr.astype(state["kn"].dtype)),
            vn=state["vn"].at[b_idx, :, j, :].set(v.astype(state["vn"].dtype)),
        )
        out = chunk_local_attention(qr, new, t, rows, self.cfg.backend)
        return out, new

    @_scoped
    def decode_step(
        self, x: Array, state: State, t: Array, rows: Optional[Any] = None
    ) -> Tuple[Array, State]:
        t = jnp.asarray(t)
        per_seq = t.ndim == 1
        q, k, v = self._heads(x)  # [B, H, Dh]
        # per-seq positions: angles gather [B, 1, Dh/2] broadcasts over
        # heads the way the scalar gather's [Dh/2] row does
        pos = t[:, None] if per_seq else t
        qr = self._rot_at(q, pos)
        kr = self._rot_at(k, pos)
        cap = state["k"].shape[-2]  # window W or max_seq_len
        if "kn" in state:
            out, new = self._chunk_local_step(qr, kr, v, state, t, rows)
            return self._merge(out, single=True), new
        slot = t % cap if self.window is not None else t
        if per_seq and rows is not None:
            # one cache row per LISTED sequence, each an in-place slice
            # update at its own slot; an unlisted sequence writes nothing
            # (rows_in_place). Not a scatter: the TPU compiler lays a
            # scattered cache out heads-minor, and converting the carried
            # [B, H, cap, Dh] buffers to that costs a second copy of them
            idx, count = rows

            def write(i, caches):
                b = idx[i]
                return tuple(
                    jax.lax.dynamic_update_slice(
                        c, new[b][None, :, None, :].astype(c.dtype),
                        (b, 0, slot[b], 0),
                    )
                    for c, new in zip(caches, (kr, v))
                )

            kc, vc = jax.lax.fori_loop(
                0, count[0], write, (state["k"], state["v"])
            )
        elif per_seq:
            # one scatter row per sequence at its own slot
            b_idx = jnp.arange(x.shape[0])
            kc = state["k"].at[b_idx, :, slot, :].set(
                kr.astype(state["k"].dtype)
            )
            vc = state["v"].at[b_idx, :, slot, :].set(
                v.astype(state["v"].dtype)
            )
        else:
            kc = jax.lax.dynamic_update_slice_in_dim(
                state["k"], kr[:, :, None, :].astype(state["k"].dtype), slot, axis=2
            )
            vc = jax.lax.dynamic_update_slice_in_dim(
                state["v"], v[:, :, None, :].astype(state["v"].dtype), slot, axis=2
            )
        if per_seq and self.window is None:
            # a growing cache at per-sequence positions: rows [0, t] are
            # live, and with a row list under a Pallas backend only they
            # are read (ops.dispatch.cache_attention)
            out, _ = cache_attention(
                qr, kc, vc, t + 1, rows, backend=self.cfg.backend
            )
            out = out.astype(qr.dtype)
        else:
            # ring slots hold positions (t-W, t] once warm; before that,
            # slots (t, W) are still unwritten — in both cases exactly the
            # slots with index <= t are valid (softmax is permutation-
            # invariant over keys, so rotation needs no unrotation).
            bound = t[:, None, None] if per_seq else t
            valid = jnp.arange(cap)[None, None, :] <= bound
            out = cached_attention(qr, kc, vc, valid)
        return self._merge(out, single=True), {"k": kc, "v": vc}


def merge_chunk_rows(cache: Array, new: Array, t0: Array, live: Array) -> Array:
    """A chunk's own rows ``new`` [B, H, n, Dh] into ``cache`` [B, H, cap,
    Dh] at each sequence's ``t0``, the rows of a sequence outside ``live``
    keeping their bits: one in-place slice update a sequence."""
    for b in range(cache.shape[0]):
        at = (b, 0, t0[b], 0)
        old = jax.lax.dynamic_slice(cache, at, (1,) + new.shape[1:])
        cache = jax.lax.dynamic_update_slice(
            cache, jnp.where(live[b], new[b][None], old), at
        )
    return cache


def chunk_local_attention(q, state, t, rows, backend, blocks=None):
    """One query per sequence over the held cache's rows before ``t0``
    (:func:`ops.dispatch.cache_attention`; of the listed ``blocks`` only,
    where given) and the chunk's own rows up to ``t - t0`` (4 MB at the
    served widths: scored here), merged by their log-sum-exps: the softmax
    over the two key sets side by side. q is ``[B, H, Dh]`` and the caches
    hold ``KV`` heads, ``H / KV`` query heads to each. A sequence with no
    held rows, or one the row list leaves out, weighs its held part
    ``sigmoid(-1e30 - lse) = 0``: the chunk's part alone."""
    f32 = jnp.float32
    b, h, d = q.shape
    kvh = state["kn"].shape[1]
    j = t - state["t0"]  # [B]: this step's row in the chunk
    held, lse_held = cache_attention(
        q, state["k"], state["v"], state["t0"], rows, backend=backend, blocks=blocks
    )
    qg = (q.astype(f32) * d ** -0.5).reshape(b, kvh, h // kvh, d)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, state["kn"].astype(f32))
    s = jnp.where(jnp.arange(s.shape[-1]) <= j[:, None, None, None], s, _NEG)
    lse_own = jax.nn.logsumexp(s, axis=-1)
    own = jnp.einsum(
        "bkgs,bksd->bkgd", jnp.exp(s - lse_own[..., None]), state["vn"].astype(f32)
    ).reshape(b, h, d)
    # the held part's share of the joint softmax's mass
    w = jax.nn.sigmoid(lse_held - lse_own.reshape(b, h))[..., None]
    return (own + w * (held - own)).astype(q.dtype)


def _window_write(
    cache: Array, rows: Array, offset: Array, real: Array
) -> Array:
    """Masked read-modify-write of a [B, H, P, Dh] row block into the full
    KV cache at traced ``offset``: pad rows (``real`` False) keep whatever
    the cache held, so a partial final piece never clobbers slots the
    decode's ``slot <= t`` rule may later expose. Scatter at clipped
    per-row positions, NOT dynamic_update_slice: an out-of-range offset
    (pieces are computed for non-prefilling rows too, then discarded)
    would make dynamic_update_slice clamp the window and silently shift
    every row; here pad/garbage rows write the cache's own value back —
    a bitwise no-op even when clipping collides their positions."""
    p = rows.shape[-2]
    pos = jnp.clip(offset + jnp.arange(p), 0, cache.shape[-2] - 1)
    cur = jnp.take(cache, pos, axis=2)
    new = jnp.where(real, rows.astype(cache.dtype), cur)
    return cache.at[:, :, pos, :].set(new)


def _swa_cache_from_prefill(kr: Array, v: Array, t: int, window: int) -> State:
    """Build the ring-buffer cache from the last ``window`` prompt tokens,
    each at slot (position % window); unwritten slots stay zero (they are
    masked by the slot <= t rule in decode_step)."""
    b, h, _, dh = kr.shape
    start = max(0, t - window)
    positions = jnp.arange(start, t)
    slots = positions % window
    kc = jnp.zeros((b, h, window, dh), kr.dtype).at[:, :, slots, :].set(
        kr[:, :, start:t, :]
    )
    vc = jnp.zeros((b, h, window, v.shape[-1]), v.dtype).at[:, :, slots, :].set(
        v[:, :, start:t, :]
    )
    return {"k": kc, "v": vc}


def _swa_cache_from_prefill_dynamic(
    kr: Array, v: Array, length: Array, window: int
) -> State:
    """:func:`_swa_cache_from_prefill` with a TRACED real length (bucketed
    prefill pads the prompt, so the ring must be built from the last
    ``window`` positions BEFORE ``length``, not before the padded end).
    Positions < 0 (prompt shorter than the window) write a clipped-gather
    row into their slot; those slots are never read — decode's
    ``slot <= t`` rule excludes a slot until the step that overwrites it
    (see decode_step) — so the garbage is harmless and the readable
    entries are bitwise-identical to the static builder's."""
    b, h, t_pad, dh = kr.shape
    positions = length - window + jnp.arange(window)  # [W], may be < 0
    slots = positions % window
    safe = jnp.clip(positions, 0, t_pad - 1)
    kc = jnp.zeros((b, h, window, dh), kr.dtype).at[:, :, slots, :].set(
        jnp.take(kr, safe, axis=2)
    )
    vc = jnp.zeros((b, h, window, v.shape[-1]), v.dtype).at[:, :, slots, :].set(
        jnp.take(v, safe, axis=2)
    )
    return {"k": kc, "v": vc}
