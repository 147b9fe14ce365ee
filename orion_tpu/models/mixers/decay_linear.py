"""``decay_linear``: linear attention with a fixed per-head scalar decay,
no feature map and no normaliser (the Lightning Attention family):

    q = rope(rmsh(W_q u)),  k = rope(rmsh(W_k u)),  v = W_v u
    S_t = lam_h S_{t-1} + k_t^T v_t,   o_t = q_t S_t / sqrt(Dh)
    out = W_o( rms(merge(o)) * sigmoid(W_g u) )

``rmsh`` is an RMSNorm over each head's own width with one learned
``[Dh]`` weight (``cfg.qk_norm == "head"``), ``rope`` the rotate-half
rotary over the whole head (base ``cfg.rotary_base``), ``lam_h =
exp(-2^(-cfg.decay_exponent h / H))`` (``ops.linear_attention.
decay_slopes``), ``rms`` a learned RMSNorm over the merged heads.

The decode state is ``{"s": [B, H, Dh, Dh] fp32}`` alone, constant in the
sequence length. The serving forms go through ``ops/dispatch.py``:
``causal_dot_product(decay=...)`` for the prompt and its pieces (a state in
and out; a padded piece stops the state at its real ``length``) and
``decode_state_step(decay=...)`` for one token (under a Pallas backend the
row-sparse in-place kernel, hence ``rows_in_place``). The training forward
``__call__`` runs the chunked ``jnp`` form whatever the backend: the Mosaic
kernel has no backward, and autodiff of the ``jnp`` form is the gradient.
Speculative decode is not built for this mixer: the base class's raise.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.mixers import Mixer, State, _dense_factory, _dtype
from orion_tpu.ops.dispatch import causal_dot_product, decode_state_step
from orion_tpu.ops.linear_attention import decay_slopes
from orion_tpu.utils.profiling import scoped

Array = jax.Array


_scoped = scoped("lightning")


def rotate_half(x: Array, ang: Array) -> Array:
    """Rotate-half rotary over the whole head: dim ``j`` pairs with ``j +
    Dh / 2``; ``ang`` [..., Dh / 2] broadcasts against x's leading dims."""
    half = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


class DecayLinearAttention(Mixer):
    layer_type: str = "decay_linear"

    rows_in_place = True

    def setup(self):
        cfg = self.cfg
        assert self.causal, "decay_linear is causal-LM only"
        assert not self.sp_local and not self._sp_active(), "no sequence parallel form"
        self._setup_qkvo()
        h, dh = cfg.n_heads, cfg.resolved_head_dim
        self.wg = _dense_factory(cfg, self.quant, self.mesh)("wg", h * dh)
        self.out_norm = nn.RMSNorm(dtype=_dtype(cfg.dtype), name="out_norm")
        # trace-time constants: the rotary frequencies and the decays
        self.inv_freq = cfg.rotary_base ** (
            -jnp.arange(0, dh, 2, dtype=jnp.float32) / dh
        )
        self.slopes = decay_slopes(h, cfg.decay_exponent)

    @staticmethod
    def decode_state(
        cfg: ModelConfig, layer_type: str, batch: int, dtype: Any
    ) -> State:
        h, dh = cfg.n_heads, cfg.resolved_head_dim
        return {"s": jnp.zeros((batch, h, dh, dh), jnp.float32)}

    def _qkv(self, x: Array, pos: Array) -> Tuple[Array, Array, Array]:
        """q (scaled by Dh^-1/2), k, v at positions ``pos``: [P] for x [B,
        P, D], a scalar or [B] for one token x [B, D]."""
        q, k, v = self._heads(x)
        ang = jnp.asarray(pos, jnp.float32)[..., None] * self.inv_freq
        if x.ndim == 2 and ang.ndim == 2:
            ang = ang[:, None, :]  # per-sequence positions, over heads
        q, k = rotate_half(q, ang), rotate_half(k, ang)
        return q * jnp.asarray(q.shape[-1] ** -0.5, q.dtype), k, v

    def _out(self, o: Array, x: Array) -> Array:
        single = x.ndim == 2
        if not single:
            o = jnp.swapaxes(o, -3, -2)  # [B, T, H, Dh]
        merged = o.reshape(*o.shape[:-2], -1)
        return self.wo(self.out_norm(merged) * jax.nn.sigmoid(self.wg(x)))

    def _mix(self, x, state, offset, length, backend):
        q, k, v = self._qkv(x, offset + jnp.arange(x.shape[-2]))
        o, s = causal_dot_product(
            q, k, v, backend=backend, chunk=self.cfg.chunk, decay=self.slopes,
            initial_state=None if state is None else state["s"], length=length,
        )
        return self._out(o, x), {"s": s}

    @_scoped
    def __call__(self, x: Array, mask: Optional[Array] = None) -> Array:
        assert mask is None, "decay_linear has no masked forward"
        return self._mix(x, None, 0, None, "xla")[0]

    @_scoped
    def prefill(self, x: Array, length: Optional[Array] = None) -> Tuple[Array, State]:
        return self._mix(x, None, 0, length, self.cfg.backend)

    @_scoped
    def prefill_extend(
        self, x: Array, state: State, offset: Array, length: Array
    ) -> Tuple[Array, State]:
        return self._mix(x, state, offset, length, self.cfg.backend)

    @_scoped
    def decode_step(
        self, x: Array, state: State, t: Array, rows: Optional[Any] = None
    ) -> Tuple[Array, State]:
        """Given ``rows``, under a Pallas backend only those rows' ``S`` are
        stepped, in place, and the others are returned untouched."""
        q, k, v = self._qkv(x, jnp.asarray(t))
        o, s = decode_state_step(
            q, k, v, state["s"], rows, backend=self.cfg.backend, decay=self.slopes
        )
        return self._out(o, x), {"s": s}
