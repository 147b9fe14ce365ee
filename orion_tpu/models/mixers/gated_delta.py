"""``gated_delta``: q, k, v and an output gate z from one projection, a
write strength and a decay from another; q, k and v pass a causal depthwise
convolution + SiLU; q and k are l2-normalised and each key head serves
``value_heads / key_heads`` value heads; the gated delta rule
(``ops/gated_delta.py``) mixes along time; the output is RMS-normalised per
head, gated by ``silu(z)`` and projected back. ``beta = sigmoid(b)``, or
``2 sigmoid(b)`` under ``cfg.gdn_allow_neg_eigval``.

The parallel forward hands the conv's output ``[B, T, C]`` to
``ops.dispatch.gated_delta_qkv`` where its kernels read q, k and v in place
(``gated_delta_reads_qkv``: a Pallas backend, whole lane tiles, no mesh
whose data axes split): the norm, q's scale and the group sum of the
cotangents are inside them. Every other call (a state in or out, other
widths, ``xla`` / ``eager``, meshes) forms the operands in ``_operands`` and
runs ``_rule`` on them head-major, the program it always was.

Served, the decode state is ``{"s": [B, Hv, dk, dv] fp32, "conv": [B, (W - 1)
x channels]}``: the rule's state and the conv's last ``W - 1`` PRE-conv
``[q | k | v]`` rows, oldest first, side by side (a ``[B, W - 1, channels]``
array would put 3 rows on tiles of 8 or 16: 43x its size on the chip); a
piece takes them at its real ``length``. A padded
piece masks its pad rows to k = v = beta = g = 0, which passes the state
through. The one-token step is ``ops.dispatch.gated_delta_step`` (under a
Pallas backend the row-sparse in-place kernel, hence ``rows_in_place``; the
conv tail of an unlisted row is selected back, 3 rows of channels).
Speculative decode (``verify_extend`` / ``advance_verified``) is not built
for this mixer: the base class's raise.

The plain references it is tested against are ``benchmark/reference/
plain_gdn_moe.py`` and ``plain_olmo_hybrid.py``, which read the same
parameter layout: ``in_qkvz`` columns are ``[q | k | v | z]`` (key_heads x
key_dim, the same, value_heads x value_dim twice), ``in_ba`` columns
``[b | a]``, ``conv`` is ``[width, channels]`` over the ``[q | k | v]``
channels with row ``width - 1`` on the current token.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.mixers import (
    NORM_EPS, Mixer, State, _dense_factory, _dtype, kernel_bh,
    whole_array_backend,
)
from orion_tpu.ops.dispatch import (
    causal_short_conv, decode_rows_mask, gated_delta_qkv, gated_delta_reads_qkv,
    gated_delta_rule, gated_delta_step, gated_rms_norm,
)
from orion_tpu.ops.gated_delta import l2norm
from orion_tpu.utils.profiling import scope

Array = jax.Array


def _widths(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    hk, hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    dk, dv = cfg.gdn_key_dim, cfg.gdn_value_dim
    assert hk > 0 and hv % hk == 0 and dk > 0 and dv > 0, (hk, hv, dk, dv)
    return hk, hv, dk, dv


class GatedDeltaNet(Mixer):
    layer_type: str = "gated_delta"

    rows_in_place = True
    tail_leaves = ("conv",)

    def setup(self):
        cfg = self.cfg
        assert self.causal, "gated_delta is causal-LM only"
        assert not self.sp_local and not self.quant, (self.sp_local, self.quant)
        pdt = _dtype(cfg.param_dtype)
        hk, hv, dk, dv = _widths(cfg)
        dense = _dense_factory(cfg)
        self.in_qkvz = dense("in_qkvz", 2 * hk * dk + 2 * hv * dv)
        self.in_ba = dense("in_ba", 2 * hv)
        self.conv = self.param(
            "conv",
            nn.initializers.variance_scaling(1.0, "fan_in", "uniform", in_axis=0, out_axis=1),
            (cfg.gdn_conv_width, 2 * hk * dk + hv * dv), pdt,
        )
        self.a_log = self.param(
            "A_log",
            lambda rng, shape: jnp.log(jax.random.uniform(rng, shape, minval=1.0, maxval=16.0)),
            (hv,),
        )
        self.dt_bias = self.param("dt_bias", nn.initializers.ones_init(), (hv,))
        self.out_norm = self.param("out_norm", nn.initializers.ones_init(), (dv,), pdt)
        self.wo = dense("wo", cfg.d_model)

    @staticmethod
    def decode_state(
        cfg: ModelConfig, layer_type: str, batch: int, dtype: Any
    ) -> State:
        hk, hv, dk, dv = _widths(cfg)
        return {
            "s": jnp.zeros((batch, hv, dk, dv), jnp.float32),
            "conv": jnp.zeros(
                (batch, (cfg.gdn_conv_width - 1) * (2 * hk * dk + hv * dv)), dtype
            ),
        }

    # -- what every entry point shares ---------------------------------------

    def _project(self, x: Array) -> Tuple[Array, Array, Array]:
        """x [..., D] -> (pre-conv [q | k | v] channels, z, [b | a] fp32)."""
        hk, hv, dk, dv = _widths(self.cfg)
        p = self.in_qkvz(x)
        c = 2 * hk * dk + hv * dv
        return p[..., :c], p[..., c:], self.in_ba(x).astype(jnp.float32)

    def _operands(self, qkv: Array, ba: Array):
        """Post-conv channels [..., C] and [b | a] [..., 2 Hv] -> q, k
        [..., Hk, dk], v [..., Hv, dv] in the compute dtype, beta, g
        [..., Hv] fp32."""
        cfg = self.cfg
        dt = _dtype(cfg.dtype)
        hk, hv, dk, dv = _widths(cfg)
        kd, lead = hk * dk, qkv.shape[:-1]
        q = qkv[..., :kd].reshape(lead + (hk, dk))
        k = qkv[..., kd: 2 * kd].reshape(lead + (hk, dk))
        v = qkv[..., 2 * kd:].reshape(lead + (hv, dv))
        beta, g = self._gates(ba)
        q = (l2norm(q, NORM_EPS) * dk ** -0.5).astype(dt)
        return q, l2norm(k, NORM_EPS).astype(dt), v, beta, g

    def _gates(self, ba: Array) -> Tuple[Array, Array]:
        """[b | a] [..., 2 Hv] fp32 -> the write strength beta and the
        log-decay g, [..., Hv] fp32."""
        hv = self.cfg.gdn_value_heads
        beta = jax.nn.sigmoid(ba[..., :hv])
        if self.cfg.gdn_allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(self.a_log.astype(jnp.float32)) * jax.nn.softplus(
            ba[..., hv:] + self.dt_bias.astype(jnp.float32)
        )
        return beta, g

    def _output(self, o: Array, z: Array) -> Array:
        """o [B, Hv, T, dv] as the rule leaves it, z [B, T, Hv dv] -> the
        layer's output [B, T, D]: the gate (under a Pallas backend one
        kernel that reads both where they lie), then ``wo``. A decode
        step's o [B, Hv, dv] and z [B, Hv dv] are one row of that."""
        single = o.ndim == 3
        if single:
            o, z = o[:, :, None], z[:, None]
        y = gated_rms_norm(
            o, z, self.out_norm, eps=NORM_EPS,
            backend=whole_array_backend(self.cfg, self.mesh),
        )
        return self.wo(y[:, 0] if single else y)

    def _rule(self, q, k, v, beta, g, **state):
        """The rule over time on [B, T, H, ...] operands -> o [B, Hv, T, dv],
        head-major as its kernel leaves it (and the final state with
        ``return_state``)."""
        cfg = self.cfg
        heads_first = lambda y: jnp.swapaxes(y, 1, 2)  # noqa: E731
        # key head j serves value heads j * (hv / hk) ... + hv / hk - 1:
        # the op repeats q and k, or its kernel reads them in place. Its
        # chunking is its own: cfg.chunk is linear attention's knob
        return kernel_bh(
            cfg, self.mesh,
            lambda *a: gated_delta_rule(*a, backend=cfg.backend, **state),
            *(heads_first(y) for y in (q, k, v, beta, g)),
        )

    # -- parallel forward ---------------------------------------------------

    def __call__(self, x: Array, mask: Optional[Array] = None) -> Array:
        assert mask is None, "gated_delta is causal-LM only"
        hk, hv, dk, dv = _widths(self.cfg)
        backend = whole_array_backend(self.cfg, self.mesh)
        with scope("gated_delta"):
            pre, z, ba = self._project(x)
            with scope("short_conv"):
                qkv = causal_short_conv(pre, self.conv, backend=backend)
            if gated_delta_reads_qkv(hk, hv, dk, dv, backend=backend):
                # the rule's kernels read q, k and v where the conv left
                # them and hand its cotangent back in the same layout
                o = gated_delta_qkv(
                    qkv, *(jnp.swapaxes(y, 1, 2) for y in self._gates(ba)),
                    key_heads=hk, key_dim=dk, value_dim=dv, eps=NORM_EPS, backend=backend,
                )
            else:
                o = self._rule(*self._operands(qkv, ba))
            return self._output(o, z)

    # -- prefill and its pieces -----------------------------------------------

    def prefill(self, x: Array, length: Optional[Array] = None) -> Tuple[Array, State]:
        zero = self.decode_state(self.cfg, self.layer_type, x.shape[0], x.dtype)
        n = x.shape[1] if length is None else length
        return self.prefill_extend(x, zero, 0, n)

    def prefill_extend(
        self, x: Array, state: State, offset: Array, length: Array
    ) -> Tuple[Array, State]:
        """The piece's conv reads the tail the pieces before left; pad rows
        (``>= length``) leave the rule's state as it is; the new tail is the
        last ``W - 1`` pre-conv rows before ``length`` (the old tail's, where
        the piece is shorter than that)."""
        del offset  # position enters through the state alone
        w1 = self.cfg.gdn_conv_width - 1
        with scope("gated_delta"):
            pre, z, ba = self._project(x)
            old = state["conv"].reshape(x.shape[0], w1, -1)
            with scope("short_conv"):
                qkv = causal_short_conv(
                    pre, self.conv, tail=old,
                    backend=whole_array_backend(self.cfg, self.mesh),
                )
            q, k, v, beta, g = self._operands(qkv, ba)
            real = jnp.arange(x.shape[1]) < length  # [P]
            pad0 = lambda y: jnp.where(  # noqa: E731
                real.reshape((1, -1) + (1,) * (y.ndim - 2)), y, jnp.zeros_like(y)
            )
            o, s = self._rule(
                q, pad0(k), pad0(v), pad0(beta), pad0(g),
                initial_state=state["s"], return_state=True,
            )
            seen = jnp.concatenate([old, pre.astype(old.dtype)], axis=1)
            tail = jax.lax.dynamic_slice_in_dim(seen, length, w1, axis=1)
            return self._output(o, z), {"s": s, "conv": tail.reshape(state["conv"].shape)}

    # -- one-token decode ---------------------------------------------------

    def decode_step(
        self, x: Array, state: State, t: Array, rows: Optional[Any] = None
    ) -> Tuple[Array, State]:
        """Given ``rows``, under a Pallas backend only those rows' state is
        stepped, in place; the others keep their ``s`` and their conv tail."""
        with scope("gated_delta"):
            pre, z, ba = self._project(x)  # [B, C]
            c = pre.shape[-1]
            seen = jnp.concatenate(
                [state["conv"], pre.astype(state["conv"].dtype)], axis=1
            )  # [B, W x C]: the window's rows side by side
            with scope("short_conv"):
                wf = self.conv.astype(jnp.float32)
                y = sum(
                    seen[:, j * c:(j + 1) * c].astype(jnp.float32) * wf[j]
                    for j in range(wf.shape[0])
                )
                qkv = jax.nn.silu(y).astype(pre.dtype)
            tail = seen[:, c:]
            if rows is not None:
                live = decode_rows_mask(rows, x.shape[0])
                tail = jnp.where(live[:, None], tail, state["conv"])
            q, k, v, beta, g = self._operands(qkv, ba)
            group = v.shape[1] // q.shape[1]
            if group > 1:
                q, k = (jnp.repeat(y, group, axis=1) for y in (q, k))
            o, s = gated_delta_step(
                q, k, v, beta, g, state["s"], rows, backend=self.cfg.backend
            )
            return self._output(o, z), {"s": s, "conv": tail}
