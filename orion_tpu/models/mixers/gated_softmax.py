"""``gated_softmax``: causal softmax attention with grouped KV heads, a
per-head zero-centred RMSNorm of q and k, rotary on the first
``rotary_dims`` of each head (halves rotated, not interleaved pairs) and a
sigmoid output gate taken from a doubled q projection.

A training forward only: a growing grouped-KV cache in the slot carry is
serving work not done yet (PERF.md s7), so the serving entry points are the
base class's, which raise. The plain reference it is tested against is
``benchmark/reference/plain_gdn_moe.py``, which reads the same parameter
layout: ``wq`` columns are per head ``[q (head_dim) | gate (head_dim)]``.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from orion_tpu.models.mixers import (
    Mixer, ZeroCentredRMSNorm, _dense_factory, _dtype, kernel_bh,
)
from orion_tpu.ops.rotary import apply_rotary_half
from orion_tpu.ops.softmax_attention import softmax_attention
from orion_tpu.utils.profiling import scope

Array = jax.Array


class GatedSoftmaxAttention(Mixer):
    layer_type: str = "gated_softmax"

    @nn.compact
    def __call__(self, x: Array, mask: Optional[Array] = None) -> Array:
        assert mask is None and self.causal, "gated_softmax is causal-LM only"
        assert not self.sp_local and not self.quant, (self.sp_local, self.quant)
        cfg = self.cfg
        dt, pdt = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        h, dh = cfg.n_heads, cfg.resolved_head_dim
        hkv = cfg.n_kv_heads or h
        assert h % hkv == 0, (h, hkv)
        b, t, _ = x.shape
        dense = _dense_factory(cfg)
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
