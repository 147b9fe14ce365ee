"""LRA classifier: bidirectional encoder + CLS pooling + linear head.

The reference's LRA eval configs compare causal-free linear attention vs
softmax attention on ListOps and Text (BASELINE.json; the reference checkout
was never mounted — SURVEY.md §0). Reuses the same Block stack as the LM
with ``causal=False``; a key-padding mask rides through to both attention
families (linear: masked keys drop out of the kv-sum; softmax: additive
mask)."""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.transformer import Block, _dtype, _norm

Array = jax.Array


class LRAClassifier(nn.Module):
    """tokens [B, T] (+ optional mask [B, T]) -> logits [B, n_classes]."""

    cfg: ModelConfig

    def setup(self):
        cfg = self.cfg
        assert cfg.n_classes > 0, "classifier config needs n_classes > 0"
        pdt = _dtype(cfg.param_dtype)
        self.embed = nn.Embed(cfg.vocab_size, cfg.d_model, param_dtype=pdt)
        self.pos_embed = nn.Embed(cfg.max_seq_len, cfg.d_model, param_dtype=pdt)
        self.cls_embed = self.param(
            "cls", nn.initializers.normal(0.02), (cfg.d_model,), pdt
        )
        self.blocks = [
            Block(
                cfg, lt, causal=False, name=f"block_{i}", **cfg.block_form(i)
            )
            for i, lt in enumerate(cfg.resolved_layer_types)
        ]
        self.final_norm = _norm(cfg, "final_norm")
        self.head = nn.Dense(
            cfg.n_classes, dtype=jnp.float32, param_dtype=pdt, name="head"
        )

    def __call__(
        self,
        tokens: Array,
        mask: Optional[Array] = None,
        deterministic: bool = True,
    ) -> Array:
        cfg = self.cfg
        b, t = tokens.shape
        x = self.embed(tokens) + self.pos_embed(jnp.arange(t))
        cls = jnp.broadcast_to(self.cls_embed, (b, 1, cfg.d_model))
        x = jnp.concatenate([cls, x.astype(cls.dtype)], axis=1)
        x = x.astype(_dtype(cfg.dtype))
        if mask is not None:
            mask = jnp.concatenate(
                [jnp.ones((b, 1), dtype=bool), mask.astype(bool)], axis=1
            )
        for blk in self.blocks:
            x = blk(x, mask, deterministic)
        pooled = self.final_norm(x[:, 0])  # CLS token
        return self.head(pooled.astype(jnp.float32))


__all__ = ["LRAClassifier"]
