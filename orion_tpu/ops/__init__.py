"""Compute ops: attention kernels, feature maps, rotary embeddings.

Layout:
- ``feature_maps``: kernel feature maps phi(.) for linear attention.
- ``linear_attention``: causal/non-causal linear attention in eager,
  chunked, and recurrent forms (pure XLA).
- ``gated_delta``: the gated delta rule (chunked WY form and the
  token-by-token recurrence) and the causal short convolution.
- ``pallas``: TPU Pallas kernels (causal_dot_product, flash attention).
- ``softmax_attention``: exact softmax attention (full + sliding window).
- ``dispatch``: backend="xla"|"pallas"|"auto" selection.
"""

from orion_tpu.ops.feature_maps import make_feature_map, register_feature_map
from orion_tpu.ops.linear_attention import (
    causal_dot_product_eager,
    causal_dot_product_chunked,
    kv_state,
    linear_attention,
    linear_attention_noncausal,
    recurrent_step,
)
from orion_tpu.ops.dispatch import causal_dot_product, gated_delta_rule
from orion_tpu.ops.gated_delta import causal_short_conv
from orion_tpu.ops.softmax_attention import (
    cached_attention,
    softmax_attention,
    softmax_attention_xla,
)
from orion_tpu.ops.rotary import apply_rotary, apply_rotary_at, rotary_freqs

__all__ = [
    "softmax_attention",
    "softmax_attention_xla",
    "cached_attention",
    "apply_rotary",
    "apply_rotary_at",
    "rotary_freqs",
    "make_feature_map",
    "register_feature_map",
    "causal_dot_product",
    "causal_short_conv",
    "gated_delta_rule",
    "causal_dot_product_eager",
    "causal_dot_product_chunked",
    "kv_state",
    "linear_attention",
    "linear_attention_noncausal",
    "recurrent_step",
]
