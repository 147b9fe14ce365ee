"""``ssm``: a state-space layer whose per-head scalar decay depends on the
token (the Mamba-2 family). With ``d_inner = H P`` (``cfg.ssm_heads`` heads
of ``cfg.ssm_head_dim``), a state width ``N`` (``cfg.ssm_state``) and ``G``
groups (``cfg.ssm_groups``):

    [z | xBC | dt] = W_in u                 widths d_inner | d_inner + 2 G N | H
    xBC_t = silu(b + sum_j w_j xBC_{t-(W-1)+j})     causal, depthwise, biased
    dt_t  = softplus(dt_t + dt_bias);   A = -exp(A_log)
    S_t^h = exp(dt_t^h A_h) S_{t-1}^h + dt_t^h x_t^h B_t^T;  y_t^h = S_t^h C_t + D_h x_t^h
    out   = W_out( rms_g(merge(y_t) * silu(z_t)) * w_norm )

``B_t``, ``C_t`` [N] are one pair for the ``H / G`` heads of a group; the
gate multiplies BEFORE the norm, which is taken over EACH GROUP's ``d_inner
/ G`` merged channels (``rms_g``, eps ``cfg.norm_eps``, one ``[d_inner]``
weight: the family's gated norm; at ``G = 1`` it is one norm over all of
``d_inner``); ``dt`` is not clamped.

Served, the decode state is ``{"s": [B, H / k, N, k P] fp32, "conv": [B, (W
- 1) x channels]}`` (128 heads of 64 x 128 in 8 groups: ``k`` = 2, ``S [B,
64, 128, 128]``, 4.19 MB a row a layer, which the step kernel takes whole;
64 heads in one group: 2.1 MB): the recurrence's state as ``ops/ssm.py::pack_state``
holds it (``k`` heads of a group side by side on lanes) and the conv's last
``W - 1`` PRE-conv ``xBC`` rows, oldest first, side by side (as
``gated_delta.py`` holds its own). The prompt and its pieces go through
``ops.dispatch.ssm_scan`` (a state in and out; a padded piece stops the
state at its real ``length`` and takes the conv tail there); the one-token
step is ``ops.dispatch.ssm_state_step`` (under a Pallas backend the
row-sparse in-place kernel, hence ``rows_in_place``; the conv tail of an
unlisted row is selected back). The training forward is the same chunked
form: autodiff of it is the gradient. Speculative decode is not built for
this mixer: the base class's raise.

The plain references it is tested against are ``benchmark/reference/
plain_granite_hybrid.py`` (one group) and ``plain_nemotron_h.py`` (eight),
which read the same parameter layout:
``in_proj`` columns are ``[z | x | B | C | dt]``, ``conv`` is ``[width,
channels]`` over the ``[x | B | C]`` channels with row ``width - 1`` on the
current token, ``conv_bias`` [channels].
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.mixers import (
    Mixer, State, _dense_factory, _dtype, drawn_in, whole_array_backend,
)
from orion_tpu.ops.dispatch import (
    causal_short_conv, decode_rows_mask, ssm_scan, ssm_state_step,
)
from orion_tpu.ops.ssm import pack_state, state_pack, unpack_state
from orion_tpu.utils.profiling import scope, scoped

Array = jax.Array

_scoped = scoped("ssm")


def _widths(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    h, p, n, g = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    assert h > 0 and p > 0 and n > 0 and g > 0 and h % g == 0, (h, p, n, g)
    return h, p, n, g


def _pack(cfg: ModelConfig) -> int:
    h, p, _, g = _widths(cfg)
    return state_pack(h, p, g)


def _dt_bias_init(rng, shape):
    """The inverse softplus of steps drawn log-uniform in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(rng, shape, minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class StateSpace(Mixer):
    layer_type: str = "ssm"

    rows_in_place = True
    tail_leaves = ("conv",)

    def setup(self):
        cfg = self.cfg
        assert self.causal, "ssm is causal-LM only"
        assert not self.sp_local and not self.quant, (self.sp_local, self.quant)
        assert not self._sp_active(), "no sequence parallel form"
        pdt = _dtype(cfg.param_dtype)
        h, p, n, g = _widths(cfg)
        w = cfg.ssm_conv_width
        dense = _dense_factory(cfg)
        self.in_proj = dense("in_proj", 2 * h * p + 2 * g * n + h)
        self.conv = self.param(
            "conv",
            drawn_in(cfg, nn.initializers.variance_scaling(
                1.0, "fan_in", "uniform", in_axis=0, out_axis=1)),
            (w, h * p + 2 * g * n), pdt,
        )
        self.conv_bias = self.param(
            "conv_bias",
            drawn_in(cfg, lambda rng, shape, dtype: jax.random.uniform(
                rng, shape, dtype, -(w ** -0.5), w ** -0.5
            )),
            (h * p + 2 * g * n,), pdt,
        )
        self.a_log = self.param(
            "A_log",
            lambda rng, shape: jnp.log(jax.random.uniform(rng, shape, minval=1.0, maxval=16.0)),
            (h,),
        )
        self.dt_bias = self.param("dt_bias", _dt_bias_init, (h,))
        self.d_skip = self.param("D", nn.initializers.ones_init(), (h,))
        self.out_norm = self.param("out_norm", nn.initializers.ones_init(), (h * p,), pdt)
        self.wo = dense("wo", cfg.d_model)

    @staticmethod
    def decode_state(
        cfg: ModelConfig, layer_type: str, batch: int, dtype: Any
    ) -> State:
        h, p, n, g = _widths(cfg)
        k = _pack(cfg)
        return {
            "s": jnp.zeros((batch, h // k, n, k * p), jnp.float32),
            "conv": jnp.zeros(
                (batch, (cfg.ssm_conv_width - 1) * (h * p + 2 * g * n)), dtype
            ),
        }

    # -- what every entry point shares ---------------------------------------

    def _project(self, x: Array) -> Tuple[Array, Array, Array]:
        """x [..., D] -> (z, pre-conv [x | B | C] channels, dt fp32)."""
        h, p, n, g = _widths(self.cfg)
        proj = self.in_proj(x)
        d, c = h * p, h * p + 2 * g * n
        return proj[..., :d], proj[..., d: d + c], proj[..., d + c:].astype(jnp.float32)

    def _operands(self, xbc: Array, dt: Array):
        """Post-conv channels [..., C] and raw dt [..., H] -> x [..., H, P],
        B, C [..., G, N] in the compute dtype, dt (after its softplus) and
        ``A`` [H] fp32."""
        h, p, n, g = _widths(self.cfg)
        d, lead = h * p, xbc.shape[:-1]
        xh = xbc[..., :d].reshape(lead + (h, p))
        bm = xbc[..., d: d + g * n].reshape(lead + (g, n))
        cm = xbc[..., d + g * n:].reshape(lead + (g, n))
        dt = jax.nn.softplus(dt + self.dt_bias.astype(jnp.float32))
        return xh, dt, -jnp.exp(self.a_log.astype(jnp.float32)), bm, cm

    def _output(self, y: Array, xh: Array, z: Array) -> Array:
        """y, xh [..., H, P], z [..., H P] -> the layer's output [..., D]:
        the skip, the gate, then a norm over each group's merged heads (one
        norm over all of them where there is one group)."""
        f32 = jnp.float32
        g = self.cfg.ssm_groups
        # a reshape to the shape an array has is no operation: one group
        # traces as the one norm it is
        by_group = z.shape[:-1] + ((g, -1) if g > 1 else (-1,))
        with scope("ssm_gate_norm"):
            y = y.astype(f32) + self.d_skip.astype(f32)[:, None] * xh.astype(f32)
            y = (y.reshape(z.shape) * jax.nn.silu(z.astype(f32))).reshape(by_group)
            y = y * jax.lax.rsqrt(
                jnp.mean(jnp.square(y), -1, keepdims=True) + self.cfg.norm_eps
            )
            y = (y.reshape(z.shape) * self.out_norm.astype(f32)).astype(_dtype(self.cfg.dtype))
        return self.wo(y)

    def _conv(self, pre: Array, tail: Optional[Array]) -> Array:
        with scope("short_conv"):
            return causal_short_conv(
                pre, self.conv, tail=tail, bias=self.conv_bias,
                backend=whole_array_backend(self.cfg, self.mesh),
            )

    # -- parallel forward ---------------------------------------------------

    @_scoped
    def __call__(self, x: Array, mask: Optional[Array] = None) -> Array:
        assert mask is None, "ssm is causal-LM only"
        z, pre, dt = self._project(x)
        xh, *rest = self._operands(self._conv(pre, None), dt)
        y, _ = ssm_scan(xh, *rest, backend=self.cfg.backend)
        return self._output(y, xh, z)

    # -- prefill and its pieces -----------------------------------------------

    def prefill(self, x: Array, length: Optional[Array] = None) -> Tuple[Array, State]:
        zero = self.decode_state(self.cfg, self.layer_type, x.shape[0], x.dtype)
        n = x.shape[1] if length is None else length
        return self.prefill_extend(x, zero, 0, n)

    @_scoped
    def prefill_extend(
        self, x: Array, state: State, offset: Array, length: Array
    ) -> Tuple[Array, State]:
        """The piece's conv reads the tail the pieces before left; rows at
        or past ``length`` pass the recurrence's state through; the new
        tail is the last ``W - 1`` pre-conv rows before ``length`` (the old
        tail's, where the piece is shorter than that)."""
        del offset  # position enters through the state alone
        w1 = self.cfg.ssm_conv_width - 1
        k = _pack(self.cfg)
        z, pre, dt = self._project(x)
        old = state["conv"].reshape(x.shape[0], w1, -1)
        xh, *rest = self._operands(self._conv(pre, old), dt)
        y, s = ssm_scan(
            xh, *rest, backend=self.cfg.backend,
            initial_state=unpack_state(state["s"], k), length=length,
        )
        seen = jnp.concatenate([old, pre.astype(old.dtype)], axis=1)
        tail = jax.lax.dynamic_slice_in_dim(seen, length, w1, axis=1)
        return self._output(y, xh, z), {
            "s": pack_state(s, k), "conv": tail.reshape(state["conv"].shape),
        }

    # -- one-token decode ---------------------------------------------------

    @_scoped
    def decode_step(
        self, x: Array, state: State, t: Array, rows: Optional[Any] = None
    ) -> Tuple[Array, State]:
        """Given ``rows``, under a Pallas backend only those rows' state is
        stepped, in place; the others keep their ``s`` and their conv tail."""
        del t  # position enters through the state alone
        z, pre, dt = self._project(x)  # pre [B, C]
        c = pre.shape[-1]
        seen = jnp.concatenate(
            [state["conv"], pre.astype(state["conv"].dtype)], axis=1
        )  # [B, W x C]: the window's rows side by side
        with scope("short_conv"):
            wf = self.conv.astype(jnp.float32)
            acc = sum(
                seen[:, j * c:(j + 1) * c].astype(jnp.float32) * wf[j]
                for j in range(wf.shape[0])
            )
            xbc = jax.nn.silu(acc + self.conv_bias.astype(jnp.float32)).astype(pre.dtype)
        tail = seen[:, c:]
        if rows is not None:
            live = decode_rows_mask(rows, x.shape[0])
            tail = jnp.where(live[:, None], tail, state["conv"])
        xh, *rest = self._operands(xbc, dt)
        y, s = ssm_state_step(
            xh, *rest, state["s"], _pack(self.cfg), rows, backend=self.cfg.backend
        )
        return self._output(y, xh, z), {"s": s, "conv": tail}
