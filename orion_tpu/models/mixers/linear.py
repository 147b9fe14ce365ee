"""``linear``: causal linear attention, phi(q) (phi(k)^T v) normalised by
phi(q) . sum phi(k). The decode state is the fp32 kv-cumsum ``(S, z)`` —
[B, H, Dh, Dh] and [B, H, Dh], constant in the sequence length — and the
one-token step is ``ops.dispatch.decode_state_step``. Under a Pallas
backend the slot-multiplexed scans only READ ``(S, z)``, for the rows a
row list names (hence ``rows_in_place``), beside the chunk's own k, v rows,
and write it once a chunk (``chunk_split`` / ``chunk_merge``).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.mixers import Mixer, State, _dense_factory, _dtype
from orion_tpu.ops.dispatch import (
    decode_live_rows,
    decode_state_flush,
    decode_state_step,
    row_sparse,
)
from orion_tpu.ops.feature_maps import make_feature_map
from orion_tpu.ops.linear_attention import (
    linear_attention,
    linear_attention_noncausal,
    recurrent_step,
)

Array = jax.Array


def _favor_proj_init(rng: Array, dh: int) -> Array:
    from orion_tpu.ops.feature_maps import _orthogonal_gaussian

    return _orthogonal_gaussian(rng, dh, dh)


class LinearAttention(Mixer):
    layer_type: str = "linear"

    rows_in_place = True

    def setup(self):
        cfg = self.cfg
        dh = cfg.resolved_head_dim
        self._setup_qkvo()
        if cfg.feature_map == "learnable":
            self.phi_proj = _dense_factory(cfg)("phi_proj", dh)
            self._phi = lambda x: jax.nn.elu(x) + 1.0
        elif cfg.feature_map == "favor":
            self.favor_w = self.param(
                "favor_proj",
                lambda rng: _favor_proj_init(rng, dh),
            )
            self._phi = None
        else:
            self._phi = make_feature_map(cfg.feature_map)

    def _phi_map(self, x: Array) -> Array:
        cfg = self.cfg
        if cfg.feature_map == "learnable":
            return self._phi(self.phi_proj(x))
        if cfg.feature_map == "favor":
            w = jax.lax.stop_gradient(self.favor_w)  # fixed random features
            xf = x.astype(jnp.float32) / (x.shape[-1] ** 0.25)
            proj = jnp.einsum("...d,md->...m", xf, w)
            sq = 0.5 * jnp.sum(xf * xf, axis=-1, keepdims=True)
            return (jnp.exp(proj - sq) / jnp.sqrt(w.shape[0])).astype(x.dtype)
        return self._phi(x)

    @staticmethod
    def decode_state(
        cfg: ModelConfig, layer_type: str, batch: int, dtype: Any
    ) -> State:
        h, dh = cfg.n_heads, cfg.resolved_head_dim
        return {
            "s": jnp.zeros((batch, h, dh, dh), jnp.float32),
            "z": jnp.zeros((batch, h, dh), jnp.float32),
        }

    @staticmethod
    def chunk_split(
        cfg: ModelConfig, layer_type: str, state: State, n_steps: int,
        t: Array, donated: bool,
    ) -> Tuple[State, State]:
        """Where the decode step takes the row-list kernel, in every
        program: ``(S, z)`` is held and only read; the scan carries the
        chunk's own rows ``kc`` (phi(k)) and ``vc`` [B, n_steps, H, Dh] in
        the compute dtype and the positions ``t0`` it started at. On the
        XLA path ``recurrent_step`` carries the state as ever."""
        if not row_sparse(cfg.backend):
            return {}, state
        b, h, dk, dv = state["s"].shape
        dt = _dtype(cfg.dtype)
        return dict(state), {
            "kc": jnp.zeros((b, n_steps, h, dk), dt),
            "vc": jnp.zeros((b, n_steps, h, dv), dt),
            "t0": t,
        }

    @staticmethod
    def chunk_merge(
        cfg: ModelConfig, layer_type: str, held: State, carried: State,
        live: Array,
    ) -> State:
        """The chunk's rows added into each live row's ``(S, z)``, once and
        in place: a live row stepped at every step of the scan, so all of
        its ``kc``, ``vc`` rows are this chunk's."""
        if not held:
            return carried
        s, z = decode_state_flush(
            (held["s"], held["z"]), (carried["kc"], carried["vc"]),
            decode_live_rows(live, backend=cfg.backend), backend=cfg.backend,
        )
        return {"s": s, "z": z}

    # -- parallel forward ---------------------------------------------------

    def __call__(self, x: Array, mask: Optional[Array] = None) -> Array:
        cfg = self.cfg
        q, k, v = self._heads(x)
        t = x.shape[-2]
        sp = self._sp_active()
        if sp:
            assert t % self.mesh.shape["sp"] == 0, (t, dict(self.mesh.shape))
        qf, kf = self._phi_map(q), self._phi_map(k)
        if self.sp_local and self.causal:
            from orion_tpu.parallel.sequence import sp_linear_attention_local

            # In the partial-manual pipeline the XLA chunked form is
            # STRUCTURAL, not a fallback: jax rejects Mosaic kernels in
            # any partial-manual region ("cannot be automatically
            # partitioned"), and that pipeline leaves dp/fsdp/tp to
            # GSPMD by design. The FULL-manual pipeline
            # (pipeline_lm.py full_manual) sets sp_local_kernels and
            # the requested backend goes through — every other
            # fully-manual composition already carries kernels
            # (kernel_shard.py; sequence.py/ring.py).
            out = sp_linear_attention_local(
                qf, kf, v,
                backend=cfg.backend if self.sp_local_kernels else "xla",
                chunk=cfg.chunk,
            )
        elif sp:
            from orion_tpu.parallel.sequence import sp_linear_attention

            out = sp_linear_attention(
                qf, kf, v, self.mesh, backend=cfg.backend, chunk=cfg.chunk
            )
        elif self.causal:
            out = self._kernel_bh(
                lambda a, b, c: linear_attention(
                    a, b, c, backend=cfg.backend, chunk=cfg.chunk
                ),
                qf, kf, v,
            )
        else:
            km = None if mask is None else mask[:, None, :]
            out = linear_attention_noncausal(qf, kf, v, mask=km)
        return self._merge(out, single=False)

    # -- prefill: forward + decode state ------------------------------------

    def prefill(self, x: Array, length: Optional[Array] = None) -> Tuple[Array, State]:
        """With ``length``, pad positions' phi(k)/v rows are zeroed BEFORE
        the kv-cumsum, so S/z accumulate only real contributions (adding
        exact zeros is bitwise-exact) and every real position's output is
        untouched (causal: it never sees later rows)."""
        cfg = self.cfg
        q, k, v = self._heads(x)
        t = x.shape[-2]
        qf, kf = self._phi_map(q), self._phi_map(k)
        if length is not None:
            # where (not multiply): 0*nan from a degenerate feature
            # map must not poison the masked state
            real = (jnp.arange(t) < length)[None, None, :, None]
            kf = jnp.where(real, kf, jnp.zeros_like(kf))
            v = jnp.where(real, v, jnp.zeros_like(v))
        out, (s, z) = self._kernel_bh(
            lambda a, b, c: linear_attention(
                a, b, c, backend=cfg.backend, chunk=cfg.chunk,
                return_state=True,
            ),
            qf, kf, v,
        )
        return self._merge(out, single=False), {"s": s, "z": z}

    # -- chunked prefill: advance decode state by one prompt piece -----------

    def prefill_extend(
        self, x: Array, state: State, offset: Array, length: Array
    ) -> Tuple[Array, State]:
        """The numerator state AND the z normalizer thread through
        ``linear_attention(initial_state=...)``'s chunk-granular scan (a
        strict left fold — splitting at chunk boundaries replays the
        identical op sequence; ops/linear_attention.py return_zcum). Pad
        rows' phi(k)/v are zeroed exactly like bucketed prefill."""
        cfg = self.cfg
        q, k, v = self._heads(x)
        p = x.shape[-2]
        real = (jnp.arange(p) < length)[None, None, :, None]
        qf, kf = self._phi_map(q), self._phi_map(k)
        # where (not multiply): 0*nan from a degenerate feature map
        # must not poison the masked state (same as bucketed prefill)
        kf = jnp.where(real, kf, jnp.zeros_like(kf))
        vm = jnp.where(real, v, jnp.zeros_like(v))
        out, (s, z) = linear_attention(
            qf, kf, vm, backend=cfg.backend, chunk=cfg.chunk,
            initial_state=(state["s"], state["z"]), return_state=True,
        )
        return self._merge(out, single=False), {"s": s, "z": z}

    # -- speculative verify: batched re-walk of k decode steps ----------------

    def verify_extend(
        self, x: Array, state: State, t: Array
    ) -> Tuple[Array, State]:
        q, k, v = self._heads(x)  # [B, H, P, Dh]
        to_steps = lambda a: jnp.moveaxis(a, 2, 0)  # noqa: E731
        qf, kf = self._phi_map(q), self._phi_map(k)

        def body(carry, qkv):
            qj, kj, vj = qkv  # [B, H, Dh] — decode_step's shapes
            out, carry = recurrent_step(qj, kj, vj, carry)
            return carry, out

        _, outs = jax.lax.scan(
            body, (state["s"], state["z"]),
            (to_steps(qf), to_steps(kf), to_steps(v)),
        )
        out = jnp.moveaxis(outs, 0, 2)  # [B, H, P, Dh]
        return self._merge(out, single=False), {"k": kf, "v": v}

    def advance_verified(
        self, state: State, upd: State, t: Array, keep: Array
    ) -> State:
        """Replay recurrent_step's fp32 rank-1 adds in sequence, each
        behind a where-select on ``j < keep``: elementwise ops on identical
        operands, so the kept prefix is bitwise the sequential walk and a
        skipped add leaves (S, z) exactly as it was."""
        p = upd["v"].shape[2]
        kf = upd["k"].astype(jnp.float32)
        vf = upd["v"].astype(jnp.float32)
        m = keep.reshape(keep.shape + (1,) * 3)

        def body(carry, inp):
            s, z = carry
            kj, vj, j = inp
            s2 = s + kj[..., :, None] * vj[..., None, :]
            z2 = z + kj
            take = j < m
            return (
                jnp.where(take, s2, s),
                jnp.where(take[..., 0], z2, z),
            ), None

        (s, z), _ = jax.lax.scan(
            body, (state["s"], state["z"]),
            (jnp.moveaxis(kf, 2, 0), jnp.moveaxis(vf, 2, 0),
             jnp.arange(p)),
        )
        return {"s": s, "z": z}

    # -- one-token decode ---------------------------------------------------

    def decode_step(
        self, x: Array, state: State, t: Array, rows: Optional[Any] = None
    ) -> Tuple[Array, State]:
        """Inside a scan that holds ``(S, z)`` (:meth:`chunk_split`) the
        listed rows read it and this token's k and v go to row ``t - t0``
        of the chunk's own rows; the state of an unlisted row is not
        touched. Otherwise every row steps ``recurrent_step``."""
        q, k, v = self._heads(x)  # [B, H, Dh]
        qf, kf = self._phi_map(q), self._phi_map(k)
        if "kc" in state:
            out, (kc, vc) = decode_state_step(
                qf, kf, v, (state["s"], state["z"]), rows,
                backend=self.cfg.backend,
                chunk=(state["kc"], state["vc"], t - state["t0"]),
            )
            return self._merge(out, single=True), dict(state, kc=kc, vc=vc)
        out, (s, z) = decode_state_step(
            qf, kf, v, (state["s"], state["z"]), rows,
            backend=self.cfg.backend,
        )
        return self._merge(out, single=True), {"s": s, "z": z}
