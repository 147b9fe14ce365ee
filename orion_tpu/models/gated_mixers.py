"""The two gated token mixers of the delta-rule hybrids, and their norm.

- ``GatedDeltaNet`` (layer type ``gated_delta``): q, k, v and an output gate
  z from one projection, a write strength and a decay from another; q, k
  and v pass a causal depthwise convolution + SiLU; q and k are l2-normalised
  and each key head serves ``value_heads / key_heads`` value heads; the gated
  delta rule (``ops/gated_delta.py``) mixes along time; the output is
  RMS-normalised per head, gated by ``silu(z)`` and projected back.
- ``GatedSoftmaxAttention`` (``gated_softmax``): causal softmax attention
  with grouped KV heads, a per-head zero-centred RMSNorm of q and k, rotary
  on the first ``rotary_dims`` of each head (halves rotated, not interleaved
  pairs) and a sigmoid output gate taken from a doubled q projection.

Both are training forwards only: there is no decode state for them yet, and
the serving entry points raise. The plain reference they are tested
against is ``benchmark/reference/plain_gdn_moe.py``.

Fixed parameter layouts (the reference reads the same): ``in_qkvz`` columns
are ``[q | k | v | z]`` (key_heads x key_dim, the same, value_heads x
value_dim twice), ``in_ba`` columns ``[b | a]``, ``conv`` is ``[width,
channels]`` over the ``[q | k | v]`` channels with row ``width - 1`` on the
current token, ``wq`` columns are per head ``[q (head_dim) | gate
(head_dim)]``.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.transformer import _dtype, kernel_bh
from orion_tpu.ops.dispatch import gated_delta_rule
from orion_tpu.ops.gated_delta import causal_short_conv
from orion_tpu.ops.rotary import apply_rotary_half
from orion_tpu.ops.softmax_attention import softmax_attention
from orion_tpu.utils.profiling import scope

Array = jax.Array

NORM_EPS = 1e-6


def _rms(x: Array) -> Array:
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + NORM_EPS)


def _l2norm(x: Array) -> Array:
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(jnp.square(xf), -1, keepdims=True) + NORM_EPS)


class ZeroCentredRMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + 1e-6) * (1 + w)`` over the last axis, fp32
    inside, ``w`` initialised 0."""

    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Array:
        w = self.param(
            "scale", nn.initializers.zeros_init(), (x.shape[-1],), self.param_dtype
        )
        return (_rms(x) * (1.0 + w.astype(jnp.float32))).astype(self.dtype)


class _TrainOnly:
    """No decode state exists for these mixers: a recurrent state, a conv
    state and a growing grouped-KV cache in one slot carry is serving work
    not done yet (PERF.md s7)."""

    def _no_serving(self, *_, **__):
        raise NotImplementedError(
            f"layer type {self.layer_type!r} has a training forward only: "
            "prefill / decode state for it is not built"
        )

    prefill = prefill_extend = decode_step = verify_extend = _no_serving
    advance_verified = _no_serving


class GatedSoftmaxAttention(_TrainOnly, nn.Module):
    cfg: ModelConfig
    mesh: Optional[Any] = None
    layer_type: str = "gated_softmax"

    @nn.compact
    def __call__(self, x: Array, mask: Optional[Array] = None) -> Array:
        assert mask is None, "gated_softmax is causal-LM only"
        cfg = self.cfg
        dt, pdt = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        h, dh = cfg.n_heads, cfg.resolved_head_dim
        hkv = cfg.n_kv_heads or h
        assert h % hkv == 0, (h, hkv)
        b, t, _ = x.shape
        dense = lambda n, feats: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=dt, param_dtype=pdt, name=n
        )
        with scope("gated_softmax"):
            qg = dense("wq", h * dh * 2)(x).reshape(b, t, h, 2 * dh)
            q, gate = qg[..., :dh], qg[..., dh:]
            k = dense("wk", hkv * dh)(x).reshape(b, t, hkv, dh)
            v = dense("wv", hkv * dh)(x).reshape(b, t, hkv, dh)
            q = ZeroCentredRMSNorm(dt, pdt, name="q_norm")(q)
            k = ZeroCentredRMSNorm(dt, pdt, name="k_norm")(k)
            q, k, v = (jnp.swapaxes(y, 1, 2) for y in (q, k, v))  # [B, H, T, Dh]
            rd = cfg.rotary_dims or dh
            q = apply_rotary_half(q, rd, cfg.rotary_base)
            k = apply_rotary_half(k, rd, cfg.rotary_base)
            # each KV head serves h / hkv consecutive query heads
            k, v = (jnp.repeat(y, h // hkv, axis=1) for y in (k, v))
            out = kernel_bh(
                cfg, self.mesh,
                lambda a, b_, c: softmax_attention(
                    a, b_, c, causal=True, backend=cfg.backend,
                    block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
                ),
                q, k, v,
            )
            out = jnp.swapaxes(out, 1, 2).reshape(b, t, h * dh)
            out = out * jax.nn.sigmoid(gate.reshape(b, t, h * dh).astype(jnp.float32)).astype(dt)
            return dense("wo", cfg.d_model)(out)


class GatedDeltaNet(_TrainOnly, nn.Module):
    cfg: ModelConfig
    mesh: Optional[Any] = None
    layer_type: str = "gated_delta"

    @nn.compact
    def __call__(self, x: Array, mask: Optional[Array] = None) -> Array:
        assert mask is None, "gated_delta is causal-LM only"
        cfg = self.cfg
        dt, pdt = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        hk, hv = cfg.gdn_key_heads, cfg.gdn_value_heads
        dk, dv = cfg.gdn_key_dim, cfg.gdn_value_dim
        assert hk > 0 and hv % hk == 0 and dk > 0 and dv > 0, (hk, hv, dk, dv)
        kd, vd = hk * dk, hv * dv
        b, t, _ = x.shape
        dense = lambda n, feats: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=dt, param_dtype=pdt, name=n
        )
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


MIXERS = {"gated_delta": GatedDeltaNet, "gated_softmax": GatedSoftmaxAttention}

__all__ = [
    "GatedDeltaNet", "GatedSoftmaxAttention", "ZeroCentredRMSNorm", "MIXERS",
]
