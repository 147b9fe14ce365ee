"""Pallas TPU kernels — the hand-scheduled twins of the XLA ops.

- ``causal_dot``: chunked causal linear attention (causal_dot_product +
  kv-cumsum state), replacing the reference's CUDA kernels.
- ``flash_attention``: online-softmax attention, full-causal and
  sliding-window.
- ``decode_state``: the slot-multiplexed decode programs' (S, z) step
  for the rows live in a chunk only, in place.
"""
