"""``gated_delta``: q, k, v and an output gate z from one projection, a
write strength and a decay from another; q, k and v pass a causal depthwise
convolution + SiLU; q and k are l2-normalised and each key head serves
``value_heads / key_heads`` value heads; the gated delta rule
(``ops/gated_delta.py``) mixes along time; the output is RMS-normalised per
head, gated by ``silu(z)`` and projected back.

A training forward only: a recurrent ``[Hv, dk, dv]`` state and the conv's
last inputs in the slot carry are serving work not done yet (PERF.md s7), so
the serving entry points are the base class's, which raise. The plain
reference it is tested against is ``benchmark/reference/plain_gdn_moe.py``,
which reads the same parameter layout: ``in_qkvz`` columns are
``[q | k | v | z]`` (key_heads x key_dim, the same, value_heads x value_dim
twice), ``in_ba`` columns ``[b | a]``, ``conv`` is ``[width, channels]``
over the ``[q | k | v]`` channels with row ``width - 1`` on the current
token.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from orion_tpu.models.mixers import (
    NORM_EPS, Mixer, _dense_factory, _dtype, _rms, kernel_bh,
)
from orion_tpu.ops.dispatch import gated_delta_rule
from orion_tpu.ops.gated_delta import causal_short_conv
from orion_tpu.utils.profiling import scope

Array = jax.Array


def _l2norm(x: Array) -> Array:
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(jnp.square(xf), -1, keepdims=True) + NORM_EPS)


class GatedDeltaNet(Mixer):
    layer_type: str = "gated_delta"

    @nn.compact
    def __call__(self, x: Array, mask: Optional[Array] = None) -> Array:
        assert mask is None and self.causal, "gated_delta is causal-LM only"
        assert not self.sp_local and not self.quant, (self.sp_local, self.quant)
        cfg = self.cfg
        dt, pdt = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        hk, hv = cfg.gdn_key_heads, cfg.gdn_value_heads
        dk, dv = cfg.gdn_key_dim, cfg.gdn_value_dim
        assert hk > 0 and hv % hk == 0 and dk > 0 and dv > 0, (hk, hv, dk, dv)
        kd, vd = hk * dk, hv * dv
        b, t, _ = x.shape
        dense = _dense_factory(cfg)
        with scope("gated_delta"):
            p = dense("in_qkvz", 2 * kd + 2 * vd)(x)
            qkv, z = p[..., : 2 * kd + vd], p[..., 2 * kd + vd:]
            ba = dense("in_ba", 2 * hv)(x).astype(jnp.float32)
            conv = self.param(
                "conv",
                nn.initializers.variance_scaling(1.0, "fan_in", "uniform", in_axis=0, out_axis=1),
                (cfg.gdn_conv_width, 2 * kd + vd), pdt,
            )
            a_log = self.param(
                "A_log",
                lambda rng, shape: jnp.log(jax.random.uniform(rng, shape, minval=1.0, maxval=16.0)),
                (hv,),
            )
            dt_bias = self.param("dt_bias", nn.initializers.ones_init(), (hv,))
            with scope("short_conv"):
                qkv = causal_short_conv(qkv, conv)
            q = qkv[..., :kd].reshape(b, t, hk, dk)
            k = qkv[..., kd: 2 * kd].reshape(b, t, hk, dk)
            v = qkv[..., 2 * kd:].reshape(b, t, hv, dv)
            beta = jax.nn.sigmoid(ba[..., :hv])  # [B, T, Hv] fp32
            g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
                ba[..., hv:] + dt_bias.astype(jnp.float32)
            )
            q = (_l2norm(q) * dk ** -0.5).astype(dt)
            k = _l2norm(k).astype(dt)
            heads_first = lambda y: jnp.swapaxes(y, 1, 2)  # noqa: E731
            # key head j serves value heads j * (hv / hk) ... + hv / hk - 1:
            # the op repeats q and k, or its kernel reads them in place. Its
            # chunking is its own: cfg.chunk is linear attention's knob
            o = kernel_bh(
                cfg, self.mesh,
                lambda *a: gated_delta_rule(*a, backend=cfg.backend),
                *(heads_first(y) for y in (q, k, v, beta, g)),
            )  # [B, Hv, T, Dv]
            o = heads_first(o)  # [B, T, Hv, Dv]
            w_n = self.param("out_norm", nn.initializers.ones_init(), (dv,), pdt)
            o = _rms(o) * w_n.astype(jnp.float32)
            o = o * jax.nn.silu(z.reshape(b, t, hv, dv).astype(jnp.float32))
            return dense("wo", cfg.d_model)(o.reshape(b, t, vd).astype(dt))
