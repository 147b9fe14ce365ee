"""The chip's own compiler, without the chip: compile the main path for a
DESCRIBED ``v5e:2x2`` at ``lm_1b3`` widths, and ``qwen3_next_80b``'s
kernels at its train point's (on-chip-measurement guide §2.3).

Interpret-mode kernel tests cannot see what the TPU compiler refuses: a
slice not aligned to the tiling, more fast memory than a kernel may use, a
program that does not fit the device, a kernel that cannot be partitioned
(``parallel/decode.py::mesh_backend`` exists because this file's tp=4
serving compile was refused). The quick tier keeps the kernels on
``chip_smoke.py``'s path (about a second each); the rest of the main path
and the whole-program compiles are ``slow``. A compile that passes is not a
chip run: nothing here says anything about results or times.
"""

import dataclasses
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# lm_1b3 attention geometry at the smoke's train point: batch 12, 16 heads,
# T 2048, head dim 128, kernel chunk 512
BHTD = (12, 16, 2048, 128)
CHUNK = 512


@pytest.fixture(scope="module")
def v5e():
    """Devices of a described v5e:2x2 (no hardware attached)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except (RuntimeError, ValueError) as e:
        # skip ONLY for a genuinely absent TPU toolchain (the rule of
        # tests/test_aot.py::_topo_mesh_or_skip)
        msg = str(e).lower()
        if any(w in msg for w in ("topolog", "plugin", "tpu", "pjrt")):
            pytest.skip(f"tpu topology unavailable: {e}")
        raise
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip (the next one warns and
    # compiles again): cache off around these tests
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(devices, fn, *shapes):
    """Compile ``fn`` for the first described chip; (shape, dtype) args."""
    one = SingleDeviceSharding(devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _f32sum(x):
    return jnp.sum(x.astype(jnp.float32))


def _grad3(fn):
    return jax.grad(lambda q, k, v: _f32sum(fn(q, k, v)), argnums=(0, 1, 2))


def _fused(q, k, v):
    from orion_tpu.ops.pallas.causal_dot import linear_attention_pallas_fused

    return linear_attention_pallas_fused(q, k, v, chunk=CHUNK)


def _plain(q, k, v):
    from orion_tpu.ops.pallas.causal_dot import causal_dot_product_pallas

    return causal_dot_product_pallas(q, k, v, chunk=CHUNK)


def _raw(q, k, v):
    from orion_tpu.ops.pallas.causal_dot import linear_attention_pallas_parts

    num, den, _ = linear_attention_pallas_parts(q, k, v, chunk=CHUNK)
    return _f32sum(num) + _f32sum(den)


def _flash(window, **blocks):
    def fn(q, k, v):
        from orion_tpu.ops.pallas.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=True, window=window, **blocks)

    return fn


def _q4(x, p, s):
    from orion_tpu.quant import q4_matmul

    return q4_matmul(x, p, s)


def _gmm(x, w, sizes):
    from orion_tpu.ops.pallas.gmm import gmm

    return gmm(x, w, sizes, tile_rows=128, block_h=512)


def _decode_state(s, z, q, k, v, kc, vc, j, live):
    from orion_tpu.ops.pallas.decode_state import decode_state_step, live_rows

    return decode_state_step(q, k, v, (s, z), (kc, vc), j, live_rows(live))


def _decode_state_flush(s, z, kc, vc, live):
    from orion_tpu.ops.pallas.decode_state import decode_state_flush, live_rows

    return decode_state_flush((s, z), (kc, vc), live_rows(live))


def _gated_delta(q, k, v, beta, g):
    from orion_tpu.ops.dispatch import gated_delta_rule

    return gated_delta_rule(q, k, v, beta, g, backend="pallas")


def _gated_delta_qkv(qkv, beta, g):
    from orion_tpu.ops.dispatch import gated_delta_qkv

    return gated_delta_qkv(
        qkv, beta, g, key_heads=16, key_dim=128, value_dim=128, eps=1e-6, backend="pallas"
    )


def _gated_delta_state(q, k, v, beta, g, s0):
    from orion_tpu.ops.dispatch import gated_delta_rule

    return gated_delta_rule(
        q, k, v, beta, g, backend="pallas", initial_state=s0, return_state=True
    )


def _delta_step(s, q, k, v, beta, g, live):
    from orion_tpu.ops.pallas.decode_state import gated_delta_step, live_rows

    return gated_delta_step(q, k, v, beta, g, s, live_rows(live))


def _cache_attention(q, k, v, lengths, live):
    from orion_tpu.ops.pallas.cache_attention import cache_attention
    from orion_tpu.ops.pallas.decode_state import live_rows

    return cache_attention(q, k, v, lengths, live_rows(live))


def _decay_step(s, q, k, v, slopes, live):
    from orion_tpu.ops.pallas.decode_state import decay_state_step, live_rows

    return decay_state_step(q, k, v, s, slopes, live_rows(live))


def _decay_piece(q, k, v, slopes, s0, n):
    from orion_tpu.ops.pallas.causal_dot import decayed_causal_dot_pallas

    return decayed_causal_dot_pallas(q, k, v, slopes, chunk=CHUNK, initial_state=s0, length=n)


def _block_attention(q, k, v, lengths, lists, counts, live):
    from orion_tpu.ops.pallas.cache_attention import block_attention
    from orion_tpu.ops.pallas.decode_state import live_rows

    return block_attention(q, k, v, lengths, lists, counts, live_rows(live), block=64)


def _ssm_step(s, x, dt, a, bm, cm, live):
    from orion_tpu.ops.pallas.decode_state import live_rows
    from orion_tpu.ops.pallas.ssm import ssm_state_step

    return ssm_state_step(x, dt, a, bm, cm, s, 2, live_rows(live))


def _latent_attention(qt, qr, c, kr, lengths, live):
    from orion_tpu.ops.pallas.cache_attention import latent_attention
    from orion_tpu.ops.pallas.decode_state import live_rows

    return latent_attention(qt, qr, c, kr, lengths, live_rows(live), scale=192 ** -0.5)


def _gmm_live(x, w, sizes):
    from orion_tpu.ops.pallas.gmm import gmm_live

    return gmm_live(x, w, sizes, tile_rows=128, block_h=512)


def _gmm_live_tiled(tile_rows, block_h=512):
    def fn(x, w, sizes):
        from orion_tpu.ops.pallas.gmm import gmm_live

        return gmm_live(x, w, sizes, tile_rows=tile_rows, block_h=block_h)

    return fn


_gmm_live_step, _gmm_live_tile32 = _gmm_live_tiled(16), _gmm_live_tiled(32)
# the whole output width held resident: an expert's [d, h] stays in VMEM
# across its consecutive row tiles (``models/moe.py::serve_tiles``)
_gmm_live_whole = _gmm_live_tiled(128, None)


def _index_scores(qi, w, ki):
    from orion_tpu.ops.pallas.indexed_attention import index_scores

    return index_scores(qi, w, ki)


def _indexed_masked(q, k, v, keep):
    from orion_tpu.ops.pallas.indexed_attention import masked_attention

    return masked_attention(q, k, v, keep)


def _ring_step(q, k, v, lengths, live):
    from orion_tpu.ops.pallas.cache_attention import cache_attention
    from orion_tpu.ops.pallas.decode_state import live_rows

    return cache_attention(q, k, v, lengths, live_rows(live), name="window_step_attention")


def _ring_write(k, v, kn, vn, slots, live):
    from orion_tpu.ops.pallas.cache_attention import ring_row_write
    from orion_tpu.ops.pallas.decode_state import live_rows

    return ring_row_write(k, v, kn, vn, slots, live_rows(live))


def _window_piece(q, k, v, c0, hi):
    from orion_tpu.ops.pallas.piece_attention import piece_attention

    return piece_attention(q, k, v, 2048, c0, hi, window=2048, name="window_piece_attention")


def _full_piece(q, k, v, offset, hi):
    from orion_tpu.ops.pallas.piece_attention import piece_attention

    return piece_attention(q, k, v, offset, 0, hi, name="full_piece_attention")


def _moe_rows(x, gate, held, row, idx):
    """``qwen3_next_80b.train``'s held rows moved by list: the buffer filled
    from the tokens (``idx``: a row's token), then summed back into them
    (``held``, ``row``: a pair's row, ten pairs a token)."""
    from orion_tpu.ops.pallas import moe_rows

    lists = moe_rows.combine_lists(held, row, jnp.ones(held.shape), x.shape[0])
    xs = moe_rows.gather_rows(x, idx, lists, 128)
    return moe_rows.combine_rows(xs, gate, idx, lists, 128)


def _latent_flash(q, k, v):
    from orion_tpu.ops.pallas.flash_attention import flash_attention_lse

    return flash_attention_lse(q, k, v, causal=True, scale=192 ** -0.5)


def _short_conv(x, w):
    from orion_tpu.ops.dispatch import causal_short_conv

    return causal_short_conv(x, w, backend="pallas")


def _bare_conv(x, w, tail):
    from orion_tpu.ops.dispatch import causal_short_conv

    return causal_short_conv(x, w, activation=False, tail=tail, backend="pallas")


def _gated_norm(o, z, w):
    from orion_tpu.ops.dispatch import gated_rms_norm

    return gated_rms_norm(o, z, w, eps=1e-6, backend="pallas")


_QKV = [(BHTD, jnp.bfloat16)] * 3
# qwen3_next_80b's train point (batch 8, T 8192): its softmax layer's 16
# heads of 256 after the KV heads are repeated; its held experts' buffer
# (1.5 x 81,920 rows + a tile an expert) through 64 experts of 2048 x 512;
# two rows of its delta-rule layer (16 key heads serving 32 value heads of
# 128, bf16 q/k/v, fp32 beta and log-decay)
_QKV_GQA = [((8, 16, 8192, 256), jnp.bfloat16)] * 3
_QKV_SMALL = [((1, 2, 1000, 128), jnp.bfloat16)] * 3
_GMM_HELD = [((131072, 2048), jnp.bfloat16), ((64, 2048, 512), jnp.bfloat16),
             ((64,), jnp.int32)]
_GMM_HELD_DOWN = [((131072, 512), jnp.bfloat16), ((64, 512, 2048), jnp.bfloat16),
                  ((64,), jnp.int32)]
# its 65,536 tokens' rows into the 131,072-row buffer and back: x2, a gate a
# row, the held pairs of 655,360, the row a pair has and the token a row holds
_MOE_ROWS = [((65536, 2048), jnp.bfloat16), ((131072,), jnp.float32),
             ((655360,), jnp.bool_), ((655360,), jnp.int32), ((131072,), jnp.int32)]
_DELTA = [*[((2, 16, 8192, 128), jnp.bfloat16)] * 2,
          ((2, 32, 8192, 128), jnp.bfloat16),
          *[((2, 32, 8192), jnp.float32)] * 2]
# the whole batch of it read where the short conv leaves q, k and v
_DELTA_QKV = [((8, 8192, 8192), jnp.bfloat16), *[((8, 32, 8192), jnp.float32)] * 2]
# and that layer's short conv over its [q | k | v] channels, window 4
_CONV = [((8, 8192, 8192), jnp.bfloat16), ((4, 8192), jnp.bfloat16)]
# and its output gate: the rule's o head-major, z time-major, the norm's scale
_GATE = [((8, 32, 8192, 128), jnp.bfloat16), ((8, 8192, 4096), jnp.bfloat16),
         ((128,), jnp.float32)]
# the serve cells' decode carry: 64 slots of lm_1b3's fp32 (S, z), one
# token's bf16 q, k, v a slot, a 16-step chunk's own bf16 k, v rows with
# each slot's step in it, and the chunk's row mask
_STATE = [((64, 16, 128, 128), jnp.float32), ((64, 16, 128), jnp.float32)]
_CHUNK_ROWS = [((64, 16, 16, 128), jnp.bfloat16)] * 2
_STATE_STEP = [*_STATE, *[((64, 16, 128), jnp.bfloat16)] * 3, *_CHUNK_ROWS,
               ((64,), jnp.int32), ((64,), jnp.bool_)]
_STATE_FLUSH = [*_STATE, *_CHUNK_ROWS, ((64,), jnp.bool_)]
# olmo_hybrid_7b served: one slot's 1,024-token prompt piece through the
# state-carrying delta-rule kernel at 30 heads of 96 x 192 (zero-padded to
# 128 x 256 inside), and the 64-slot decode step of its fp32 state
_DELTA_PIECE = [*[((1, 30, 1024, 96), jnp.bfloat16)] * 2,
                ((1, 30, 1024, 192), jnp.bfloat16),
                *[((1, 30, 1024), jnp.float32)] * 2,
                ((1, 30, 96, 192), jnp.float32)]
_DELTA_STATE = [((64, 30, 96, 192), jnp.float32),
                *[((64, 30, 96), jnp.bfloat16)] * 2,
                ((64, 30, 192), jnp.bfloat16),
                *[((64, 30), jnp.float32)] * 2, ((64,), jnp.bool_)]
# and of one full-attention layer's held KV cache: a token's bf16 query a
# slot against 64 x 30 heads x 4,096 reserved rows of 128, the positions
_KV_CACHE = [((64, 30, 128), jnp.bfloat16),
             *[((64, 30, 4096, 128), jnp.bfloat16)] * 2,
             ((64,), jnp.int32), ((64,), jnp.bool_)]
# minicpm_sala served (32 slots x 16,896): the decayed step of 32 heads'
# fp32 128 x 128 state; one slot's 1,024-token prompt piece through the
# decayed chunk kernel with a state in and out; a token's 16-head query
# group a KV head against the 128 listed 64-row blocks of a 2-head cache
_DECAY_STATE = [((32, 32, 128, 128), jnp.float32),
                *[((32, 32, 128), jnp.bfloat16)] * 3,
                ((32,), jnp.float32), ((32,), jnp.bool_)]
_DECAY_PIECE = [*[((1, 32, 1024, 128), jnp.bfloat16)] * 3, ((32,), jnp.float32),
                ((1, 32, 128, 128), jnp.float32), ((), jnp.int32)]
_BLOCK_LIST = [((32, 2, 16, 128), jnp.bfloat16),
               *[((32, 2, 16896, 128), jnp.bfloat16)] * 2,
               ((32,), jnp.int32), ((32, 2, 128), jnp.int32),
               ((32, 2), jnp.int32), ((32,), jnp.bool_)]
# granite_4_0_h_micro served (64 slots x 2,048): the state-space step of 64
# heads' fp32 64 x 128 state as it is held (two heads side by side on lanes),
# one token's bf16 x, B, C and fp32 dt a slot; a token's 32-head query over
# the first rows of an 8-head cache of 64-wide rows, four query heads a KV head
_SSM_STATE = [((64, 32, 128, 128), jnp.float32), ((64, 64, 64), jnp.bfloat16),
              ((64, 64), jnp.float32), ((64,), jnp.float32),
              *[((64, 1, 128), jnp.bfloat16)] * 2, ((64,), jnp.bool_)]
_KV_GROUPED = [((64, 32, 64), jnp.bfloat16),
               *[((64, 8, 2048, 64), jnp.bfloat16)] * 2,
               ((64,), jnp.int32), ((64,), jnp.bool_)]
# openpangu_ultra_moe_718b served (128 slots x 4,608): a token's absorbed
# 128-head query (512 + 64 wide) a slot over its held latent cache; the held
# rows' buffer of a decode step (1,024 pairs + a tile an expert) through 16
# experts of 7,680 x 2,048 and back; one 1,024-token piece's expanded
# attention at q / k width 256 (192 + the mask column, padded) and v 128
_LATENT = [((128, 128, 512), jnp.bfloat16), ((128, 128, 64), jnp.bfloat16),
           ((128, 4608, 512), jnp.bfloat16), ((128, 4608, 64), jnp.bfloat16),
           ((128,), jnp.int32), ((128,), jnp.bool_)]
_GMM_LIVE_UP = [((3072, 7680), jnp.bfloat16), ((16, 7680, 2048), jnp.bfloat16),
                ((16,), jnp.int32)]
_GMM_LIVE_DOWN = [((3072, 2048), jnp.bfloat16), ((16, 2048, 7680), jnp.bfloat16),
                  ((16,), jnp.int32)]
# keye_vl_2_0_30b_a3b.serve_long: a 1,024-token piece over the longest static
# key length; 16 index heads x 64, 4 KV heads x 8 query heads x 128; experts 768 wide
_INDEX_PIECE = [((1, 1024, 16, 64), jnp.bfloat16), ((1, 1024, 16), jnp.float32),
                ((1, 33280, 64), jnp.bfloat16)]
_INDEXED_PIECE = [((1, 4, 8, 1024, 128), jnp.bfloat16), *[((1, 33280, 512), jnp.bfloat16)] * 2,
                  ((1, 1024, 33280), jnp.int8)]
_GMM_LIVE_768 = [((24576, 2048), jnp.bfloat16), ((128, 2048, 768), jnp.bfloat16),
                 ((128,), jnp.int32)]
# trinity_mini.serve_mixed (64 slots x 17,408): a token's 32-head query a slot
# over the live rows of a 4-head ring of 2,048, and its one new row into it; a
# 1,024-token piece's 8 query heads a KV head over the ring's rows before its
# own (3,072 columns), and over the growing cache it was written into
# ... and its steps' grouped product at ``moe_step_tile`` 16: 512 pairs in a
# buffer of 2,560 rows, up and down; four pieces' 32,768 pairs in 49,152
_GMM_LIVE_STEP_UP = [((2560, 2048), jnp.bfloat16), ((128, 2048, 1024), jnp.bfloat16),
                     ((128,), jnp.int32)]
_GMM_LIVE_STEP_DOWN = [((2560, 1024), jnp.bfloat16), ((128, 1024, 2048), jnp.bfloat16),
                       ((128,), jnp.int32)]
_GMM_LIVE_GROUP = [((49152, 2048), jnp.bfloat16), ((128, 2048, 1024), jnp.bfloat16),
                   ((128,), jnp.int32)]
_GMM_LIVE_GROUP_DOWN = [((49152, 1024), jnp.bfloat16), ((128, 1024, 2048), jnp.bfloat16),
                        ((128,), jnp.int32)]
_RING_STEP = [((64, 32, 128), jnp.bfloat16),
              *[((64, 4, 2048, 128), jnp.bfloat16)] * 2,
              ((64,), jnp.int32), ((64,), jnp.bool_)]
_RING_WRITE = [*[((64, 4, 2048, 128), jnp.bfloat16)] * 2, *[((64, 4, 128), jnp.bfloat16)] * 2,
               ((64,), jnp.int32), ((64,), jnp.bool_)]
_PIECE_Q = ((1, 4, 8, 1024, 128), jnp.bfloat16)
_WINDOW_PIECE = [_PIECE_Q, *[((1, 4, 3072, 128), jnp.bfloat16)] * 2,
                 ((), jnp.int32), ((), jnp.int32)]
_FULL_PIECE = [_PIECE_Q, *[((1, 4, 17408, 128), jnp.bfloat16)] * 2,
               ((), jnp.int32), ((), jnp.int32)]
# lfm2_8b_a1b.serve_batch (128 slots x 2,560): a 1,024-row piece's bare
# three-tap conv over 2,048 channels against the two-row tail; a step's 512
# pairs over 32 experts of 1,792 (not a multiple of 512: 256-wide blocks) on
# 32-row tiles, up and down; a piece's 4,096 pairs on 128-row tiles; a token's
# 32-head query a slot over an 8-head cache of 64-wide rows
_BARE_CONV = [((1, 1024, 2048), jnp.bfloat16), ((3, 2048), jnp.bfloat16),
              ((1, 2, 2048), jnp.bfloat16)]
_GMM_LIVE_1792_UP = [((1536, 2048), jnp.bfloat16), ((32, 2048, 1792), jnp.bfloat16),
                     ((32,), jnp.int32)]
_GMM_LIVE_1792_DOWN = [((1536, 1792), jnp.bfloat16), ((32, 1792, 2048), jnp.bfloat16),
                       ((32,), jnp.int32)]
_GMM_LIVE_1792_PIECE = [((8192, 2048), jnp.bfloat16), ((32, 2048, 1792), jnp.bfloat16),
                        ((32,), jnp.int32)]
_KV_64WIDE = [((128, 32, 64), jnp.bfloat16),
              *[((128, 8, 2560, 64), jnp.bfloat16)] * 2,
              ((128,), jnp.int32), ((128,), jnp.bool_)]
_LATENT_PIECE = [*[((1, 128, 1024, 256), jnp.bfloat16)] * 2,
                 ((1, 128, 1024, 128), jnp.bfloat16)]
# moe_1b3_4e dropless: 24576 padded rows through 4 experts of 2048 x 5504
_GMM = [((24576, 2048), jnp.bfloat16), ((4, 2048, 5504), jnp.bfloat16),
        ((4,), jnp.int32)]

# nemotron_3_super_120b.serve_batch (128 slots x 4,096): the state-space step
# of 128 heads in 8 groups as it is held (two heads of a group side by side:
# a 4.19 MB row a grid step, in and out double-buffered under the kernel's
# 64 MB); a step's 2,816 pairs in a buffer of 4,864 rows on 16-row tiles
# through 128 experts of 1,024 x 2,688 (21 x 128 wide: blocks of 384, not 512)
# and back; a 512-row piece's 11,264 pairs in 27,648 rows; a token's
# 32-head query a slot over a 2-head cache of 128-wide rows, 16 query heads a
# KV head
_SSM_STATE_8G = [((128, 64, 128, 128), jnp.float32), ((128, 128, 64), jnp.bfloat16),
                 ((128, 128), jnp.float32), ((128,), jnp.float32),
                 *[((128, 8, 128), jnp.bfloat16)] * 2, ((128,), jnp.bool_)]
_GMM_LIVE_LATENT_UP = [((4864, 1024), jnp.bfloat16), ((128, 1024, 2688), jnp.bfloat16),
                       ((128,), jnp.int32)]
_GMM_LIVE_LATENT_DOWN = [((4864, 2688), jnp.bfloat16), ((128, 2688, 1024), jnp.bfloat16),
                         ((128,), jnp.int32)]
_GMM_LIVE_LATENT_PIECE = [((27648, 1024), jnp.bfloat16), ((128, 1024, 2688), jnp.bfloat16),
                          ((128,), jnp.int32)]
_KV_16_TO_1 = [((128, 32, 128), jnp.bfloat16),
               *[((128, 2, 4096, 128), jnp.bfloat16)] * 2,
               ((128,), jnp.int32), ((128,), jnp.bool_)]

slow = pytest.mark.slow
KERNELS = [
    # -- on chip_smoke.py's path: the quick tier -----------------------------
    pytest.param(_fused, _QKV, id="causal_dot-fused-fwd"),
    pytest.param(_grad3(_fused), _QKV, id="causal_dot-fused-bwd"),
    pytest.param(_flash(1024), _QKV, id="flash-w1024-fwd"),
    pytest.param(_grad3(_flash(1024)), _QKV, id="flash-w1024-bwd"),
    pytest.param(
        _q4,
        [((8, 2048), jnp.bfloat16), ((1024, 5504), jnp.int8),
         ((5504,), jnp.float32)],
        id="q4_matmul",
    ),
    pytest.param(_decode_state, _STATE_STEP, id="decode_state_step-64slots-chunk16"),
    pytest.param(_decode_state_flush, _STATE_FLUSH,
                 id="decode_state_flush-64slots-chunk16"),
    pytest.param(_flash(None), _QKV_GQA, id="flash-causal-d256-T8192-fwd"),
    pytest.param(_grad3(_flash(None)), _QKV_GQA,
                 id="flash-causal-d256-T8192-bwd"),
    # `attn_block_q` is a public field: several q tiles of a block that is
    # whole sublanes but not whole vregs (dK/dV reads lse / delta as rows)
    pytest.param(_grad3(_flash(None, block_q=64, block_k=64)), _QKV_SMALL,
                 id="flash-causal-block64-bwd"),
    pytest.param(_grad3(_flash(256, block_q=192, block_k=128)), _QKV_SMALL,
                 id="flash-w256-block192-bwd"),
    pytest.param(_gmm, _GMM_HELD, id="gmm-held64-fwd"),
    pytest.param(
        jax.grad(lambda x, w, g: _f32sum(_gmm(x, w, g)), argnums=(0, 1)),
        _GMM_HELD, id="gmm-held64-bwd",
    ),
    # the down product (and dx of gate and up): the grid's length is read at
    # run time and an expert's whole matrix is the block, in all three kernels
    pytest.param(_gmm, _GMM_HELD_DOWN, id="gmm-held64-down-fwd"),
    pytest.param(
        jax.grad(lambda x, w, g: _f32sum(_gmm(x, w, g)), argnums=(0, 1)),
        _GMM_HELD_DOWN, id="gmm-held64-down-bwd",
    ),
    pytest.param(_gated_delta, _DELTA, id="gated_delta-T8192-fwd"),
    pytest.param(
        jax.grad(lambda *a: _f32sum(_gated_delta(*a)), argnums=(0, 1, 2, 3, 4)),
        _DELTA, id="gated_delta-T8192-bwd",
    ),
    pytest.param(_gated_delta_qkv, _DELTA_QKV, id="gated_delta-T8192-qkv-in-place-fwd"),
    pytest.param(
        jax.grad(lambda *a: _f32sum(_gated_delta_qkv(*a)), argnums=(0, 1, 2)),
        _DELTA_QKV, id="gated_delta-T8192-qkv-in-place-bwd",
    ),
    pytest.param(_short_conv, _CONV, id="short_conv-fwd"),
    pytest.param(
        jax.grad(lambda x, w: _f32sum(_short_conv(x, w)), argnums=(0, 1)),
        _CONV, id="short_conv-bwd",
    ),
    pytest.param(_gated_norm, _GATE, id="gated_norm-T8192-fwd"),
    pytest.param(
        jax.grad(lambda *a: _f32sum(_gated_norm(*a)), argnums=(0, 1, 2)),
        _GATE, id="gated_norm-T8192-bwd",
    ),
    pytest.param(_gated_delta_state, _DELTA_PIECE,
                 id="gated_delta-state-96x192-piece1024"),
    pytest.param(_delta_step, _DELTA_STATE, id="gated_delta_step-64slots"),
    pytest.param(_cache_attention, _KV_CACHE, id="cache_attention-64slots"),
    pytest.param(_decay_step, _DECAY_STATE, id="decay_state_step-32slots"),
    pytest.param(_decay_piece, _DECAY_PIECE, id="causal_dot_decay-piece1024"),
    pytest.param(_block_attention, _BLOCK_LIST, id="block_attention-32slots-128blocks"),
    pytest.param(_ssm_step, _SSM_STATE, id="ssm_state_step-64slots"),
    pytest.param(_cache_attention, _KV_GROUPED, id="cache_attention-grouped-64slots"),
    pytest.param(_latent_attention, _LATENT, id="latent_attention-128slots"),
    pytest.param(_gmm_live, _GMM_LIVE_UP, id="gmm_live-held16-7680x2048"),
    pytest.param(_gmm_live, _GMM_LIVE_DOWN, id="gmm_live-held16-2048x7680"),
    pytest.param(_latent_flash, _LATENT_PIECE, id="flash-latent-piece1024-d256-v128"),
    pytest.param(_index_scores, _INDEX_PIECE, id="index_scores-piece1024-33280keys"),
    pytest.param(_indexed_masked, _INDEXED_PIECE, id="indexed_attention-piece1024-33280keys"),
    pytest.param(_gmm_live, _GMM_LIVE_768, id="gmm_live-held128-2048x768"),
    pytest.param(_gmm_live_step, _GMM_LIVE_STEP_UP, id="gmm_live-tile16-held128-2048x1024"),
    pytest.param(_gmm_live_step, _GMM_LIVE_STEP_DOWN, id="gmm_live-tile16-held128-1024x2048"),
    pytest.param(_gmm_live, _GMM_LIVE_GROUP, id="gmm_live-group4-held128-2048x1024"),
    pytest.param(_gmm_live_whole, _GMM_LIVE_GROUP, id="gmm_live-group4-whole-width-2048x1024"),
    pytest.param(_gmm_live_whole, _GMM_LIVE_GROUP_DOWN,
                 id="gmm_live-group4-whole-width-1024x2048"),
    pytest.param(_bare_conv, _BARE_CONV, id="short_conv-bare-3taps-tail-piece1024"),
    pytest.param(_gmm_live_tile32, _GMM_LIVE_1792_UP, id="gmm_live-tile32-held32-2048x1792"),
    pytest.param(_gmm_live_tile32, _GMM_LIVE_1792_DOWN, id="gmm_live-tile32-held32-1792x2048"),
    pytest.param(_gmm_live, _GMM_LIVE_1792_PIECE, id="gmm_live-piece1024-held32-2048x1792"),
    pytest.param(_cache_attention, _KV_64WIDE, id="cache_attention-grouped-64wide-128slots"),
    pytest.param(_ring_step, _RING_STEP, id="window_step_attention-64slots-ring2048"),
    pytest.param(_ring_write, _RING_WRITE, id="ring_row_write-64slots-ring2048"),
    pytest.param(_window_piece, _WINDOW_PIECE, id="window_piece_attention-piece1024-ring2048"),
    pytest.param(_full_piece, _FULL_PIECE, id="full_piece_attention-piece1024-17408keys"),
    pytest.param(_ssm_step, _SSM_STATE_8G, id="ssm_state_step-8groups-4MB-rows-128slots"),
    pytest.param(_gmm_live_step, _GMM_LIVE_LATENT_UP, id="gmm_live-tile16-held128-1024x2688"),
    pytest.param(_gmm_live_step, _GMM_LIVE_LATENT_DOWN, id="gmm_live-tile16-held128-2688x1024"),
    pytest.param(_gmm_live, _GMM_LIVE_LATENT_PIECE, id="gmm_live-piece512-held128-1024x2688"),
    pytest.param(_cache_attention, _KV_16_TO_1, id="cache_attention-16-to-1-128slots"),
    pytest.param(_moe_rows, _MOE_ROWS, id="moe_rows-65536tokens-131072rows-fwd"),
    pytest.param(
        jax.grad(lambda *a: _f32sum(_moe_rows(*a)), argnums=(0, 1)),
        _MOE_ROWS, id="moe_rows-65536tokens-131072rows-bwd",
    ),
    # -- the rest of the main path's kernels ---------------------------------
    pytest.param(_plain, _QKV, id="causal_dot-plain-fwd", marks=slow),
    pytest.param(_grad3(_plain), _QKV, id="causal_dot-plain-bwd", marks=slow),
    pytest.param(_raw, _QKV, id="causal_dot-raw-fwd", marks=slow),
    pytest.param(jax.grad(_raw, argnums=(0, 1, 2)), _QKV,
                 id="causal_dot-raw-bwd", marks=slow),
    pytest.param(_flash(None), _QKV, id="flash-causal-fwd", marks=slow),
    pytest.param(_grad3(_flash(None)), _QKV, id="flash-causal-bwd",
                 marks=slow),
    pytest.param(_gmm, _GMM, id="gmm-128x512-fwd", marks=slow),
    pytest.param(
        jax.grad(lambda x, w, g: _f32sum(_gmm(x, w, g)), argnums=(0, 1)),
        _GMM, id="gmm-128x512-bwd", marks=slow,
    ),
]


@pytest.mark.parametrize("fn,shapes", KERNELS)
def test_kernel_compiles_for_v5e(v5e, fn, shapes):
    """The TPU compiler accepts the kernel at its case's widths (lm_1b3's
    or qwen3_next_80b's; an unaligned slice or too much VMEM shows here),
    the kernel is really in the program (no silent XLA form) and the
    program around it keeps its temporaries bound (the delta rule's
    backward, which once held some twenty chunk-local arrays a row, keeps
    three a layer)."""
    compiled = _compile(v5e, fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9


def test_moe_rows_compile_inside_the_ep_region(v5e):
    """An ep shard of ``_dropless_ep_gmm`` on a described dp2 x ep2: the row
    kernels under the fully manual ``shard_map`` with ``check_vma`` on (which
    interpret mode cannot run: the tokens vary over dp, the lists over dp and
    ep, and ``d x`` has to come back varying over dp alone), forward and
    backward, at ``qwen3_next_80b``'s row width."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from orion_tpu.models.configs import ModelConfig
    from orion_tpu.models.moe import MoEMLP
    from orion_tpu.parallel.mesh import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(dp=2, ep=2).resolve(4), devices=v5e)
    cfg = ModelConfig(
        name="t", d_model=2048, n_experts=8, moe_top_k=2, moe_hidden=512, dtype="bfloat16",
        moe_dropless=True, moe_ep_buffer=2.0, backend="pallas",
    )
    layer = MoEMLP(cfg, mesh=mesh)
    put = lambda l, spec: jax.ShapeDtypeStruct(  # noqa: E731
        l.shape, l.dtype, sharding=NamedSharding(mesh, spec))
    params = jax.tree.map(
        lambda l: put(l, P("ep", None, None) if l.ndim == 3 else P()),
        jax.eval_shape(lambda: layer.init(jax.random.key(0), jnp.zeros((2, 16, 2048), jnp.bfloat16))),
    )
    x = put(jax.ShapeDtypeStruct((4, 2048, 2048), jnp.bfloat16), P("dp", None, None))

    def loss(p, x):
        return _f32sum(layer.apply(p, x, mutable=["losses", "moe_stats"])[0] ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).compile().as_text()
    assert "moe_rows_gather" in text and "moe_rows_combine" in text


@pytest.mark.parametrize("hidden", [512, 5504])
def test_gmm_compiles_inside_the_ep_region_with_a_run_time_bound(v5e, hidden):
    """The same ep shard's grouped products, forward and backward: their grids
    stop at the last row tile that holds a segment, a number read on the chip,
    and their ``out_shape``s still vary over what their operands vary over
    under ``check_vma``. Experts ``[2048, 512]`` are held whole (the grid IS
    the live tiles); ``[2048, 5504]`` are not (column blocks outer, the tail's
    steps skipped)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from orion_tpu.models.configs import ModelConfig
    from orion_tpu.models.moe import MoEMLP
    from orion_tpu.ops.pallas.gmm import live_whole_width_fits
    from orion_tpu.parallel.mesh import MeshConfig, make_mesh

    assert live_whole_width_fits(2048, hidden, 2) == (hidden == 512)
    mesh = make_mesh(MeshConfig(dp=2, ep=2).resolve(4), devices=v5e)
    cfg = ModelConfig(
        name="t", d_model=2048, n_experts=8, moe_top_k=2, moe_hidden=hidden, dtype="bfloat16",
        moe_dropless=True, moe_ep_buffer=2.0, backend="pallas",
    )
    layer = MoEMLP(cfg, mesh=mesh)
    put = lambda l, spec: jax.ShapeDtypeStruct(  # noqa: E731
        l.shape, l.dtype, sharding=NamedSharding(mesh, spec))
    params = jax.tree.map(
        lambda l: put(l, P("ep", None, None) if l.ndim == 3 else P()),
        jax.eval_shape(lambda: layer.init(jax.random.key(0), jnp.zeros((2, 16, 2048), jnp.bfloat16))),
    )
    x = put(jax.ShapeDtypeStruct((4, 2048, 2048), jnp.bfloat16), P("dp", None, None))

    def loss(p, x):
        return _f32sum(layer.apply(p, x, mutable=["losses", "moe_stats"])[0] ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).compile().as_text()
    assert "gmm_fwd" in text and "gmm_dw" in text


def _delta_block_lines(v5e, fn):
    """The compiled program's lines for ``fn(block, params, x)`` on one of
    ``qwen3_next_80b``'s delta-rule blocks, two rows of the train point's
    8,192, for the described chip."""
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import Block

    cfg = dataclasses.replace(get_config("qwen3_next_80b"), backend="pallas", remat=False)
    block = Block(cfg, "gated_delta")
    x = jax.ShapeDtypeStruct((2, 8192, cfg.d_model), jnp.bfloat16)
    params = jax.eval_shape(block.init, jax.random.key(0), x)
    one = SingleDeviceSharding(v5e[0])
    on_chip = lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one)  # noqa: E731
    return jax.jit(lambda p, y: fn(block, p, y)).lower(
        jax.tree.map(on_chip, params), on_chip(x)
    ).compile().as_text().splitlines()


def _result_sizes(line, dtype=r"\w+"):
    """Elements of each array of ``dtype`` in an instruction's result."""
    result = line.split(" = ", 1)[1].split("(%", 1)[0] if " = " in line else ""
    return [
        math.prod(int(n) for n in m.group(1).split(","))
        for m in re.finditer(dtype + r"\[([\d,]+)\]", result)
    ]


def test_delta_rule_block_gates_its_output_in_one_kernel(v5e):
    """The compiled forward of one of ``qwen3_next_80b``'s delta-rule blocks
    (two rows of the train point's 8,192): between the rule's kernel and
    ``wo`` stands ``gated_norm_fwd`` alone, and under ``attn._output`` no
    fp32 array the size of a head's slab or more (as XLA fusions the gate
    wrote ``o`` and ``z`` out in fp32: ``f32[.., 4096]``, ``f32[.., 8192,
    128]``, ``f32[.., 32, 128]``; PERF.md s5)."""
    lines = _delta_block_lines(v5e, lambda block, p, y: block.apply(p, y))
    gate = [line for line in lines if "attn._output" in line]
    assert sum("custom_call_target" in line and "gated_norm_fwd" in line for line in gate) == 1
    wide = [line for line in gate if max(_result_sizes(line, "f32"), default=0) >= 8192 * 128]
    assert not wide, wide


def test_delta_rule_block_hands_qkv_to_the_rule_where_the_conv_left_it(v5e):
    """The compiled GRADIENT of the same block: ``gated_delta_fwd`` reads
    ``short_conv_fwd``'s output itself and ``short_conv_bwd`` reads
    ``gated_delta_bwd``'s cotangent itself, and in the layer's scope outside
    its projections, conv and gate nothing but those two kernels makes an
    array the size of q (``[2, 8192, 2048]``) or more: no fp32 copy of q or
    k (``f32[.., 8192, 2048]``, ``f32[.., 8192, 128]``), no head-major copy
    or transpose of q, k, v or their cotangents, no reduce over a group's
    value heads, no pad to ``[.., 8192, 8192]`` (PERF.md s5, PR 45's table;
    the parent's program held 69 such lines, its fusions' bodies counted)."""
    lines = _delta_block_lines(
        v5e, lambda block, p, y: jax.grad(
            lambda *a: _f32sum(block.apply(*a)), argnums=(0, 1))(p, y))
    call = lambda kernel: [  # noqa: E731
        line for line in lines if "custom_call_target" in line and f"/{kernel}/" in line]
    name = lambda line: line.split(" = ")[0].split()[-1]  # noqa: E731
    conv_fwd, rule_fwd, rule_bwd, conv_bwd = (
        call(k) for k in ("short_conv_fwd", "gated_delta_fwd", "gated_delta_bwd", "short_conv_bwd"))
    assert [len(c) for c in (conv_fwd, rule_fwd, rule_bwd, conv_bwd)] == [1, 1, 1, 1]
    assert rule_fwd[0].count(name(conv_fwd[0]) + ",") == 3  # q, k and v: the one array
    dqkv = [
        name(line) for line in lines
        if "get-tuple-element(" + name(rule_bwd[0]) + ")" in line and "bf16[2,8192,8192]" in line]
    assert len(dqkv) == 1 and re.search(re.escape(dqkv[0]) + r"[,)]", conv_bwd[0])
    own = ("short_conv", "attn._project", "attn._output", "/in_qkvz/", "/in_ba/", "/wo/")
    around = [
        line for line in lines
        if "attn/gated_delta/" in line and not any(s in line for s in own)
        and "/pallas_call" not in line
        and max(_result_sizes(line), default=0) >= 2 * 8192 * 2048
    ]
    assert not around, around


def test_stacked_delta_rule_blocks_keep_their_own_name_stacks(v5e):
    """Two of ``qwen3_next_80b``'s delta-rule blocks one after the other,
    the gradient lowered for the described chip: every ``tpu_custom_call``
    carries the name stack of the layer that calls it, forward and backward,
    though the second layer's kernels bind jaxprs that the first layer's
    calls traced (``ops/pallas.kernel_entry``: inlined; one shared callee a
    kernel would give them all to ``layers_0``, and the scope readers of
    ``benchmark/readers/scope_share.py`` with it); and there are as many
    custom calls as before the kernels' entries were jitted: twelve."""
    import flax.linen as nn

    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import Block

    cfg = dataclasses.replace(get_config("qwen3_next_80b"), backend="pallas", remat=False)

    class Two(nn.Module):
        @nn.compact
        def __call__(self, x):
            for i in range(2):
                x = Block(cfg, "gated_delta", name=f"layers_{i}")(x)
            return x

    two = Two()
    x = jax.ShapeDtypeStruct((2, 8192, cfg.d_model), jnp.bfloat16)
    one = SingleDeviceSharding(v5e[0])
    on_chip = lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one)  # noqa: E731
    params = jax.tree.map(on_chip, jax.eval_shape(two.init, jax.random.key(0), x))
    text = jax.jit(jax.grad(lambda *a: _f32sum(two.apply(*a)), argnums=(0, 1))).lower(
        params, on_chip(x)).as_text(debug_info=True)
    stacks = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    calls = [
        stacks[loc] for loc in
        re.findall(r"stablehlo\.custom_call @tpu_custom_call.*?loc\((#loc\d+)\)", text)]
    assert len(calls) == 12
    kernels = ("short_conv_fwd", "gated_delta_fwd", "gated_norm_fwd",
               "gated_norm_bwd", "gated_delta_bwd", "short_conv_bwd")
    for layer in ("layers_0", "layers_1"):
        own = [c for c in calls if f"/{layer}/" in c]
        assert sorted(k for c in own for k in kernels if f"/{k}/" in c) == sorted(kernels), own
        assert not any(other in c for c in own for other in {"layers_0", "layers_1"} - {layer})
    assert any("transpose(jvp(" in c for c in calls)  # the backward's are among them


# -- whole programs (slow: ~20 s to ~4 min each) -----------------------------


def _smoke_train_cfg(mesh_cfg):
    """chip_smoke.py's train point: b12 x T2048, adafactor, bfloat16_sr
    storage, 6 un-rematted blocks. ``backend`` is spelled out: this process
    sees the CPU, where "auto" means the XLA scan."""
    from orion_tpu.models.configs import get_config
    from orion_tpu.training.trainer import TrainConfig

    model = dataclasses.replace(
        get_config("lm_1b3"), backend="pallas", remat_skip=6,
        max_seq_len=2049,
    )
    return TrainConfig(
        model=model, batch_size=12, seq_len=2048, optimizer="adafactor",
        param_storage="bfloat16_sr", mesh=mesh_cfg,
    )


@slow
@pytest.mark.parametrize("layout,collective", [
    ("dp1", None), ("dp4", "all-reduce"), ("fsdp4", "all-gather"),
])
def test_smoke_train_step_compiles_and_fits(v5e, layout, collective):
    """The smoke's train step fits 16 GB of HBM on one chip and under both
    four-chip layouts, with the kernels in it (under dp/fsdp through
    parallel/kernel_shard.py's shard_map, check_vma=True)."""
    from orion_tpu.aot import plan
    from orion_tpu.parallel.mesh import MeshConfig, make_mesh

    mc = {"dp1": MeshConfig(dp=1), "dp4": MeshConfig(dp=-1),
          "fsdp4": MeshConfig(dp=1, fsdp=4)}[layout]
    mesh = make_mesh(mc.resolve(len(v5e)), devices=v5e)
    rep = plan(_smoke_train_cfg(mc), compile_step=True, mesh=mesh)
    assert rep["compiled"]  # a step that does not fit HBM fails to compile
    assert rep["collectives"]["mosaic_kernels"] > 0, rep["collectives"]
    if collective:
        assert rep["collectives"][collective] > 0, rep["collectives"]
    if layout == "fsdp4":
        one_chip = 2 * 1284083712  # bf16 params, unsharded
        assert rep["param_bytes_per_device"] < 0.3 * one_chip, rep


@slow
def test_qwen3_next_train_step_compiles_and_fits(v5e):
    """benchmark/workloads/qwen3_next_80b.train.json's step — b8 x T8192,
    adafactor, bfloat16_sr, every block rematted — fits one chip with the
    flash, grouped-matmul, row-mover, delta-rule, short-conv and gate
    kernels in it (the memory point known before a chip call, by the
    compiler's count at PR 52: 2.07 GB of arguments + 11.25 GB of
    temporaries, of which the donated state's 2.07 GB is counted twice, and
    318.1 MB of generated code; 11.53 GB and 354.6 MB on PR 52's parent,
    which moved the held experts' rows through XLA's gather and scatter-add
    and held an fp32 copy of the buffer; 12.62 GB and 365.6 MB on PR 48's
    parent, which held q, k and v head-major and in fp32 beside the conv's
    output; 13.1 GB before the gate's kernels, 13.4 GB with the conv as XLA
    fusions, whose backward held fp32 pads, 14.3 GB with the delta rule in
    its XLA form too; the chip's own peak while it runs is PERF.md s5's
    ``hbm_gb``). 118 Mosaic calls: 78 and 40 ``moe_rows_*``, a layer's
    forward 4 (a pack and a gather, a pack and a combine), its recompute 2
    and its backward 4; 79 until PR 60, when the rematted ``gated_softmax``
    block began to keep the flash forward's output and rows by name
    (``models/transformer.py::REMAT_KEEPS``) and stopped calling
    ``flash_attn_fwd`` again in its backward: the compiler's temporaries fell
    with it, 11.25 -> 10.98 GB, the kept 0.54 GB lying under the old peak."""
    from orion_tpu.aot import plan
    from orion_tpu.models.configs import get_config
    from orion_tpu.parallel.mesh import MeshConfig, make_mesh
    from orion_tpu.training.trainer import TrainConfig

    mc = MeshConfig(dp=1)
    model = dataclasses.replace(
        get_config("qwen3_next_80b"), backend="pallas", remat_skip=0,
        max_seq_len=8192,
    )
    cfg = TrainConfig(
        model=model, batch_size=8, seq_len=8192, optimizer="adafactor",
        param_storage="bfloat16_sr", mesh=mc,
    )
    mesh = make_mesh(mc.resolve(1), devices=v5e[:1])
    rep = plan(cfg, compile_step=True, mesh=mesh)
    assert rep["compiled"] and rep["n_params"] == 1028320320, rep
    assert rep["collectives"]["mosaic_kernels"] == 78 + 4 * 10, rep["collectives"]


def test_olmo_hybrid_boundary_programs_hold_the_carry_once(v5e):
    """``olmo_hybrid_7b.serve_batch``'s programs at 64 slots x 4,096 for the
    chip, the carry donated: admission staging, one slot's prompt piece and
    the decode scan. Each fits 16 GB with its arguments (weights 4.87 GB +
    carry 9.2 GB) and holds the KV cache once: no temporary of a cache's
    size. A compile, not a chip run."""
    from orion_tpu import generate as gen
    from orion_tpu.generate import SampleConfig
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM, init_decode_state
    from orion_tpu.ops.pallas.cache_attention import _VMEM_BYTES as _KERNEL_VMEM
    from orion_tpu.serving import batching

    slots, chunk, piece, width = 64, 8, 1024, 4096
    cfg = dataclasses.replace(get_config("olmo_hybrid_7b"), backend="pallas")
    model = TransformerLM(cfg)
    one = SingleDeviceSharding(v5e[0])
    put = lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one)  # noqa: E731
    arr = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    params = jax.tree.map(put, jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 16), jnp.int32))))
    states = jax.tree.map(put, jax.eval_shape(lambda: init_decode_state(cfg, slots)))
    ints, flags = arr((slots,), jnp.int32), arr((slots,), jnp.bool_)
    carry = (ints, states, ints, ints, flags)
    rngs, pbuf = arr((slots, 2), jnp.uint32), arr((slots, width), jnp.int32)
    scalar, sample = arr((), jnp.int32), SampleConfig(temperature=0.0)
    programs = {
        "stage": batching._stage_rows_carry.lower(
            carry, rngs, ints, ints, pbuf,
            arr((batching.STAGE_ROWS, batching._ROW_HEAD + width), jnp.int32)),
        "piece": gen._prefill_piece_donated_jit.lower(
            model, params, carry, rngs, pbuf, ints, ints, scalar, piece, sample),
        "scan": gen._decode_scan_donated_jit.lower(
            model, params, carry, rngs, flags, ints, chunk, sample),
    }
    cache_bytes = 64 * 30 * 4096 * 128 * 2
    for name, lowered in programs.items():
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        live = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        assert live < 16e9, (name, live)
        assert m.temp_size_in_bytes < cache_bytes, (name, m.temp_size_in_bytes)
        assert m.alias_size_in_bytes > 9e9, (name, m.alias_size_in_bytes)
        if name != "stage":
            assert "gated_delta_" in compiled.as_text(), name
        if name == "scan":
            # decode attention is the row-list kernel over the held cache,
            # and beside the 0.07 GB the scan held before it nothing grew
            # but what the kernel stages in VMEM (its K and V blocks)
            assert "cache_attention" in compiled.as_text()
            assert m.temp_size_in_bytes < 0.08e9 + _KERNEL_VMEM, m.temp_size_in_bytes


@slow
def test_granite_boundary_programs_hold_the_carry_once(v5e):
    """``granite_4_0_h_micro.serve_batch``'s programs at 64 slots x 2,048 for
    the chip, all 40 layers, the carry donated: one slot's prompt piece and
    the decode scan. Each fits 16 GB with its arguments (weights 6.38 GB +
    the carry) and holds the state-space step's kernel; the scan attends
    through the row-list kernel over the grouped cache. A compile, not a
    chip run."""
    from orion_tpu import generate as gen
    from orion_tpu.generate import SampleConfig
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM, init_decode_state

    slots, chunk, piece, width = 64, 8, 512, 1024
    cfg = dataclasses.replace(get_config("granite_4_0_h_micro"), backend="pallas")
    model = TransformerLM(cfg)
    one = SingleDeviceSharding(v5e[0])
    put = lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one)  # noqa: E731
    arr = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    params = jax.tree.map(put, jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 16), jnp.int32))))
    assert sum(l.size for l in jax.tree.leaves(params)) == 3191396096
    states = jax.tree.map(put, jax.eval_shape(lambda: init_decode_state(cfg, slots)))
    ints, flags = arr((slots,), jnp.int32), arr((slots,), jnp.bool_)
    carry = (ints, states, ints, ints, flags)
    rngs, pbuf = arr((slots, 2), jnp.uint32), arr((slots, width), jnp.int32)
    scalar, sample = arr((), jnp.int32), SampleConfig(temperature=0.0)
    programs = {
        "piece": gen._prefill_piece_donated_jit.lower(
            model, params, carry, rngs, pbuf, ints, ints, scalar, piece, sample),
        "scan": gen._decode_scan_donated_jit.lower(
            model, params, carry, rngs, flags, ints, chunk, sample),
    }
    for name, lowered in programs.items():
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        live = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        assert live < 16e9, (name, live)
        assert m.alias_size_in_bytes > 5.9e9, (name, m.alias_size_in_bytes)
        if name == "scan":
            text = compiled.as_text()
            assert "ssm_state_step" in text and "cache_attention" in text


@slow
def test_openpangu_boundary_programs_hold_the_carry_once(v5e):
    """``openpangu_ultra_moe_718b.serve_batch``'s programs at 128 slots x
    4,608 for the chip, the carry donated: one slot's 1,024-token prompt
    piece and the decode scan. Each fits 16 GB with its arguments (weights
    9.84 GB + the latent cache 3.40 GB as counted) and aliases the carry;
    the scan attends through the latent kernel and both run the held experts
    through the grouped product over live tiles. A compile, not a chip run."""
    from orion_tpu import generate as gen
    from orion_tpu.generate import SampleConfig
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM, init_decode_state

    slots, chunk, piece, width = 128, 8, 1024, 4096
    cfg = dataclasses.replace(get_config("openpangu_ultra_moe_718b"), backend="pallas")
    model = TransformerLM(cfg)
    one = SingleDeviceSharding(v5e[0])
    put = lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one)  # noqa: E731
    arr = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    params = jax.tree.map(put, jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 16), jnp.int32))))
    assert sum(l.size for l in jax.tree.leaves(params)) == 4919139840
    states = jax.tree.map(put, jax.eval_shape(lambda: init_decode_state(cfg, slots)))
    ints, flags = arr((slots,), jnp.int32), arr((slots,), jnp.bool_)
    carry = (ints, states, ints, ints, flags)
    rngs, pbuf = arr((slots, 2), jnp.uint32), arr((slots, width), jnp.int32)
    scalar, sample = arr((), jnp.int32), SampleConfig(temperature=0.0)
    programs = {
        "piece": gen._prefill_piece_donated_jit.lower(
            model, params, carry, rngs, pbuf, ints, ints, scalar, piece, sample),
        "scan": gen._decode_scan_donated_jit.lower(
            model, params, carry, rngs, flags, ints, chunk, sample),
    }
    for name, lowered in programs.items():
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        live = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        assert live < 16e9, (name, live)
        assert m.alias_size_in_bytes > 3.3e9, (name, m.alias_size_in_bytes)
        text = compiled.as_text()
        assert "gmm_live" in text, name
        assert ("latent_attention" if name == "scan" else "flash_attn_fwd") in text


@slow
def test_keye_vl2_boundary_programs_hold_the_carry_once(v5e):
    """``keye_vl_2_0_30b_a3b.serve_long``'s programs at 16 slots x 33,280 for
    the chip, the carry donated: one slot's 1,024-token prompt piece and the
    decode scan. Each fits 16 GB with its arguments (weights 6.25 GB + the
    three caches 4.63 GB as counted), aliases the carry and holds K, V and
    the index keys once (``chunk_split``: no temporary of a cache's size in
    the scan), and both run the 128 held experts through the grouped product
    over live tiles. A compile, not a chip run."""
    from orion_tpu import generate as gen
    from orion_tpu.generate import SampleConfig
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM, init_decode_state

    slots, chunk, piece, width = 16, 4, 1024, 32768
    cfg = dataclasses.replace(get_config("keye_vl_2_0_30b_a3b"), backend="pallas")
    model = TransformerLM(cfg)
    one = SingleDeviceSharding(v5e[0])
    put = lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one)  # noqa: E731
    arr = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    params = jax.tree.map(put, jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 16), jnp.int32))))
    assert sum(l.size for l in jax.tree.leaves(params)) == 3123858944
    states = jax.tree.map(put, jax.eval_shape(lambda: init_decode_state(cfg, slots)))
    ints, flags = arr((slots,), jnp.int32), arr((slots,), jnp.bool_)
    carry = (ints, states, ints, ints, flags)
    rngs, pbuf = arr((slots, 2), jnp.uint32), arr((slots, width), jnp.int32)
    scalar, sample = arr((), jnp.int32), SampleConfig(temperature=0.0)
    programs = {
        "piece": gen._prefill_piece_donated_jit.lower(
            model, params, carry, rngs, pbuf, ints, ints, scalar, piece, sample),
        "scan": gen._decode_scan_donated_jit.lower(
            model, params, carry, rngs, flags, ints, chunk, sample),
    }
    for name, lowered in programs.items():
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        live = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        assert live < 14e9, (name, live)
        assert m.alias_size_in_bytes > 4.6e9, (name, m.alias_size_in_bytes)
        assert "gmm_live" in compiled.as_text(), name
        if name == "scan":  # one slot's K is 0.034 GB, all slots' 0.55 GB a layer
            assert m.temp_size_in_bytes < 1.0e9, m.temp_size_in_bytes


@slow
def test_trinity_mini_boundary_programs_hold_the_carry_once(v5e, monkeypatch):
    """``trinity_mini.serve_mixed``'s programs at 64 slots x 17,408 for the
    chip, the carry donated: four slots' 1,024-token prompt pieces in one
    program (``prefill_group``) and the decode scan, whose grouped product
    takes 16-row tiles (``moe_step_tile``). Each fits 16 GB with its arguments (weights 8.48 GB + four
    rings and one growing cache a slot 3.36 GB as counted) and aliases the
    carry; the growing cache is held once (``chunk_split``) and the rings,
    1.07 GB in all, are what the scan carries, with NO copy of them: their
    row writes and their attention are both Mosaic calls that take the ring
    as it lies (XLA's slice update had them relaid at every step, 1.09 GB of
    temporaries); the ring's step, the ring's piece and the growing cache's
    piece are the Mosaic kernels by their names, and both programs run the
    128 held experts through the grouped product over live tiles: the group's
    with each product's WHOLE width as its block (PR 56: 256 rows an expert
    on an even router, so an expert's weights stay in VMEM across its tiles),
    the scan's in blocks of 512 on 16-row tiles as before. A compile, not a
    chip run."""
    from orion_tpu import generate as gen
    from orion_tpu.generate import SampleConfig
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM, init_decode_state
    from orion_tpu.ops.pallas import gmm as gmm_mod

    blocks, real = {}, gmm_mod.gmm_live
    monkeypatch.setattr(gmm_mod, "gmm_live", lambda x, w, gs, tm, bh, interpret: (
        blocks.setdefault((x.shape, w.shape[1:]), (tm, bh)), real(x, w, gs, tm, bh, interpret))[1])
    slots, chunk, piece, width = 64, 8, 1024, 16384
    cfg = dataclasses.replace(get_config("trinity_mini"), backend="pallas")
    model = TransformerLM(cfg)
    one = SingleDeviceSharding(v5e[0])
    put = lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one)  # noqa: E731
    arr = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    params = jax.tree.map(put, jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 16), jnp.int32))))
    assert sum(l.size for l in jax.tree.leaves(params)) == 4241534720
    states = jax.tree.map(put, jax.eval_shape(lambda: init_decode_state(cfg, slots)))
    ints, flags = arr((slots,), jnp.int32), arr((slots,), jnp.bool_)
    carry = (ints, states, ints, ints, flags)
    rngs, pbuf = arr((slots, 2), jnp.uint32), arr((slots, width), jnp.int32)
    scalar, sample = arr((), jnp.int32), SampleConfig(temperature=0.0)
    programs = {
        "piece": gen._prefill_piece_donated_jit.lower(
            model, params, carry, rngs, pbuf, ints, ints, scalar, piece, sample),
        "scan": gen._decode_scan_donated_jit.lower(
            model, params, carry, rngs, flags, ints, chunk, sample),
    }
    assert blocks == {
        ((49152, 2048), (2048, 1024)): (128, None), ((49152, 1024), (1024, 2048)): (128, None),
        ((2560, 2048), (2048, 1024)): (16, 512), ((2560, 1024), (1024, 2048)): (16, 512),
    }
    kernels = {"piece": ("window_piece_attention", "full_piece_attention"),
               "scan": ("window_step_attention", "ring_row_write", "cache_attention")}
    for name, lowered in programs.items():
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        live = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        assert live < 14.8e9, (name, live)
        assert m.alias_size_in_bytes > 3.3e9, (name, m.alias_size_in_bytes)
        text = compiled.as_text()
        assert "gmm_live" in text, name
        for kernel in kernels[name]:
            assert kernel in text, (name, kernel)
        # no ring (0.13 GB each of eight) relaid or copied: the piece program
        # writes four slots' rows back (``generate._write_row``)
        assert not re.search(r"bf16\[64,4,2048,128\]\S* copy\(", text), name
        assert "mini-gather" not in text, name  # nor every slot's cache read for four rows
        if name == "scan":  # no ring (0.13 GB each of eight) among the temporaries
            assert m.temp_size_in_bytes < 0.5e9, m.temp_size_in_bytes


def _lfm2_programs(v5e, slots: int):
    """``lfm2_8b_a1b.serve_batch``'s two donated programs at ``slots`` x
    2,560 for the described chip, all 13 layers, lowered and not yet
    compiled: one slot's 512-token prompt piece and the decode scan of 8
    steps. Returns (lowered by name, the states' shapes)."""
    from orion_tpu import generate as gen
    from orion_tpu.generate import SampleConfig
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM, init_decode_state

    chunk, piece, width = 8, 512, 2048
    cfg = dataclasses.replace(get_config("lfm2_8b_a1b"), backend="pallas")
    assert cfg.prefill_group == 1
    model = TransformerLM(cfg)
    one = SingleDeviceSharding(v5e[0])
    put = lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one)  # noqa: E731
    arr = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    params = jax.tree.map(put, jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 16), jnp.int32))))
    assert sum(l.size for l in jax.tree.leaves(params)) == 4606249728
    states = jax.tree.map(put, jax.eval_shape(lambda: init_decode_state(cfg, slots)))
    ints, flags = arr((slots,), jnp.int32), arr((slots,), jnp.bool_)
    carry = (ints, states, ints, ints, flags)
    rngs, pbuf = arr((slots, 2), jnp.uint32), arr((slots, width), jnp.int32)
    scalar, sample = arr((), jnp.int32), SampleConfig(temperature=0.0)
    return {
        "piece": gen._prefill_piece_donated_jit.lower(
            model, params, carry, rngs, pbuf, ints, ints, scalar, piece, sample),
        "scan": gen._decode_scan_donated_jit.lower(
            model, params, carry, rngs, flags, ints, chunk, sample),
    }, states


@slow
def test_lfm2_boundary_programs_hold_the_carry_once(v5e):
    """``lfm2_8b_a1b.serve_batch``'s programs at 128 slots x 2,560 for the
    chip, all 13 layers, the carry donated: one slot's 512-token prompt
    piece and the decode scan on 32-row tiles (``moe_step_tile``). Each fits
    the chip with its arguments (weights 9.21 GB + three growing caches and
    ten two-row tails a slot, 2.02 GB as counted) and aliases the carry; the
    piece runs the bare conv's Mosaic kernel in the ten conv layers, both
    programs the 32 held experts through the grouped product over live
    tiles, the scan attends by row list. What 64-wide heads cost is pinned
    too: the device holds ``[128, 8, 2560, 64]`` with the rows minor; the
    PIECE updates a slot's rows in that layout, in place (with
    ``prefill_group`` 4 the compiler relaid every cache whole, slots minor,
    around the four write-backs: 46% of the cell's busy time on the chip);
    the SCAN's row-list kernel wants rows of lanes, so each of the six caches
    is COPIED, padded to 128 lanes (0.67 GB from 0.34), once a scan:
    ``ops.dispatch.cache_copy_nbytes`` counts exactly that. A compile, not a
    chip run."""
    from orion_tpu.ops.dispatch import cache_copy_nbytes
    from orion_tpu.serving.batching import tree_nbytes

    programs, states = _lfm2_programs(v5e, 128)
    assert tree_nbytes(states) == 2023751680 and cache_copy_nbytes(states) == 2 * 2013265920
    for name, lowered in programs.items():
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        live = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        assert live < 15.75 * 2 ** 30 - 258e6, (name, live)
        assert m.alias_size_in_bytes > 2.0e9, (name, m.alias_size_in_bytes)
        text = compiled.as_text()
        assert "gmm_live" in text, name
        copies = len(re.findall(r"bf16\[128,8,2560,64\]\S* copy\(", text))
        if name == "piece":
            assert "short_conv_fwd" in text and copies == 0  # no cache relaid whole
            assert m.temp_size_in_bytes < 1.0e9, m.temp_size_in_bytes
        else:
            assert "cache_attention" in text and copies == 6
            assert m.temp_size_in_bytes < cache_copy_nbytes(states) + 0.3e9, m.temp_size_in_bytes
            assert re.search(r'op_name="[^"]*while/body[^"]*/gated_conv/[^"]*gated_conv_conv', text)


@slow
@pytest.mark.parametrize("slots,gib", [(192, 17.5), (256, 20.5)])
def test_lfm2_scan_does_not_fit_the_chip_past_128_slots(v5e, slots, gib):
    """Why the cell has 128 slots and not ISSUE 55's 256, nor 192: the decode
    scan at those slot counts is REFUSED by the chip's compiler, which wants
    ``gib`` GiB of the chip's 15.75 (weights 9.21 GB, the caches once, and
    the six padded copies ``ops.dispatch.cache_copy_nbytes`` counts: twice
    the caches). The arrays alone would fit (13.24 GB at 256 slots, the
    ISSUE's arithmetic). When the row-list kernel reads 64-wide rows where
    they lie this test fails, and the cell can take the ISSUE's slots."""
    from orion_tpu.ops.dispatch import cache_copy_nbytes
    from orion_tpu.serving.batching import tree_nbytes

    programs, states = _lfm2_programs(v5e, slots)
    caches = slots * 3 * 2 * 8 * 2560 * 64 * 2
    assert cache_copy_nbytes(states) == 2 * caches
    assert tree_nbytes(states) + 9212499456 < 13.3e9  # the arrays alone fit
    with pytest.raises(Exception) as refused:
        programs["scan"].compile()
    said = str(refused.value)
    assert re.search(r"RESOURCE_EXHAUSTED|[Rr]an out of memory|exceed", said), said[:600]
    used = re.search(r"[Uu]sed ([0-9.]+)G of ([0-9.]+)G", said)
    assert used and float(used.group(2)) == 15.75, said[:600]
    assert abs(float(used.group(1)) - gib) < 0.3, said[:600]


@slow
def test_nemotron_boundary_programs_hold_the_carry_once(v5e, monkeypatch):
    """``nemotron_3_super_120b.serve_batch``'s programs at 128 slots x 4,096
    for the chip, all six blocks, the carry donated: one slot's 512-token
    prompt piece (``prefill_group`` 1: a group's write-back had the compiler
    relay every layer's state whole) and the decode scan of 8 steps on 16-row
    tiles (``moe_step_tile``). Each fits the chip with its
    arguments (weights 9.30 GB + five 4.19 MB state rows, five conv tails and
    one grouped cache a slot, 3.26 GB as counted) and aliases the carry. The
    scan steps the state in the row kernel at its 4 MB rows and attends by row
    list; both run the 128 held experts' TWO products a layer through the
    grouped product over live tiles, in the 1,024-wide latent: blocks of 512
    for the way back and of 384 for the way in (2,688 = 7 x 384). The block
    without a feed-forward part passes through both. A compile, not a chip
    run."""
    from orion_tpu import generate as gen
    from orion_tpu.generate import SampleConfig
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM, init_decode_state
    from orion_tpu.ops.pallas import gmm as gmm_mod
    from orion_tpu.serving.batching import tree_nbytes

    blocks, real = {}, gmm_mod.gmm_live
    monkeypatch.setattr(gmm_mod, "gmm_live", lambda x, w, gs, tm, bh, interpret: (
        blocks.setdefault((x.shape, w.shape[1:]), (tm, bh)), real(x, w, gs, tm, bh, interpret))[1])
    slots, chunk, piece, width = 128, 8, 512, 2048
    cfg = dataclasses.replace(get_config("nemotron_3_super_120b"), backend="pallas")
    assert cfg.prefill_group == 1 and cfg.moe_step_tile == 16
    model = TransformerLM(cfg)
    one = SingleDeviceSharding(v5e[0])
    put = lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one)  # noqa: E731
    arr = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    params = jax.tree.map(put, jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 16), jnp.int32))))
    assert sum(l.size for l in jax.tree.leaves(params)) == 4648163712
    states = jax.tree.map(put, jax.eval_shape(lambda: init_decode_state(cfg, slots)))
    assert tree_nbytes(states) == 3260547072
    ints, flags = arr((slots,), jnp.int32), arr((slots,), jnp.bool_)
    carry = (ints, states, ints, ints, flags)
    rngs, pbuf = arr((slots, 2), jnp.uint32), arr((slots, width), jnp.int32)
    scalar, sample = arr((), jnp.int32), SampleConfig(temperature=0.0)
    programs = {
        "piece": gen._prefill_piece_donated_jit.lower(
            model, params, carry, rngs, pbuf, ints, ints, scalar, piece, sample),
        "scan": gen._decode_scan_donated_jit.lower(
            model, params, carry, rngs, flags, ints, chunk, sample),
    }
    assert blocks == {
        ((27648, 1024), (1024, 2688)): (128, 512), ((27648, 2688), (2688, 1024)): (128, 512),
        ((4864, 1024), (1024, 2688)): (16, 512), ((4864, 2688), (2688, 1024)): (16, 512),
    }
    kernels = {"piece": ("full_piece_attention",), "scan": ("ssm_state_step", "cache_attention")}
    for name, lowered in programs.items():
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        live = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        assert live < 14.8e9, (name, live)
        assert m.alias_size_in_bytes > 3.2e9, (name, m.alias_size_in_bytes)
        text = compiled.as_text()
        assert "gmm_live" in text and "moe_latent" in text, name
        assert "experts_gate" not in text, name  # two products a layer, not three
        for kernel in kernels[name]:
            assert kernel in text, (name, kernel)
        # no layer's state (0.54 GB) copied or relaid whole, in either program
        assert not re.search(r"f32\[128,8192,128\]\S* copy\(", text), name
        if name == "scan":
            assert m.temp_size_in_bytes < 0.3e9, m.temp_size_in_bytes


def test_minicpm_sala_boundary_programs_compile_and_fit(v5e):
    """``minicpm_sala.serve_long``'s programs at 32 slots x 16,896 for the
    chip: the unified prefill + decode boundary and the decode-only one (the
    carry, 0.8 GB, fits twice beside 3.4 GB of weights, so the cell donates
    nothing), and the donated scan an engine with more or longer slots
    would run, which holds K and V once (``chunk_split``: no temporary of a
    cache's size). Each fits 16 GB and holds the new kernels. A compile,
    not a chip run."""
    from orion_tpu import generate as gen
    from orion_tpu.generate import SampleConfig
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM, init_decode_state

    slots, chunk, piece, width = 32, 4, 1024, 16384
    cfg = dataclasses.replace(get_config("minicpm_sala"), backend="pallas")
    model = TransformerLM(cfg)
    one = SingleDeviceSharding(v5e[0])
    put = lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one)  # noqa: E731
    arr = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    params = jax.tree.map(put, jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 16), jnp.int32))))
    states = jax.tree.map(put, jax.eval_shape(lambda: init_decode_state(cfg, slots)))
    ints, flags = arr((slots,), jnp.int32), arr((slots,), jnp.bool_)
    carry = (ints, states, ints, ints, flags)
    rngs, pbuf = arr((slots, 2), jnp.uint32), arr((slots, width), jnp.int32)
    sample = SampleConfig(temperature=0.0)
    programs = {
        "unified": gen._decode_batched_prefill_chunk_jit.lower(
            model, params, carry, rngs, flags, pbuf, ints, ints, ints, chunk, piece, sample),
        "decode": gen._decode_batched_chunk_jit.lower(
            model, params, carry, rngs, flags, chunk, sample),
        "donated scan": gen._decode_scan_donated_jit.lower(
            model, params, carry, rngs, flags, ints, chunk, sample),
    }
    for name, lowered in programs.items():
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        live = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        assert live < 16e9, (name, live)
        text = compiled.as_text()
        assert "decay_state_step" in text and "block_attention" in text, name
        if name == "unified":
            assert "causal_dot_decay_fwd" in text
        if name == "donated scan":
            # K or V alone is 0.277 GB; a gather of the held keys had the
            # compiler copy K (0.2835 GB of temporaries), slices do not
            assert m.temp_size_in_bytes < 0.03e9, m.temp_size_in_bytes
            assert m.alias_size_in_bytes > 0.77e9, m.alias_size_in_bytes


@slow
@pytest.mark.parametrize("qmode,tp", [("off", 1), ("int8", 1), ("off", 4)])
def test_serving_programs_compile(v5e, monkeypatch, qmode, tp):
    """The programs ``python -m orion_tpu.aot --decode --config lm_1b3``
    lists for the smoke's serving footprint — batched decode chunk, in-scan
    prefill chunk and host prefill at the bucket the smoke's prompts hit —
    compile for the chip, on one device and on the tp=4 mesh."""
    import orion_tpu.parallel.decode as pdec
    from orion_tpu import aot
    from orion_tpu.generate import SampleConfig
    from orion_tpu.models.configs import get_config

    serving_mesh, abstracts = pdec.serving_mesh, aot._decode_abstracts
    monkeypatch.setattr(
        pdec, "serving_mesh", lambda tp, devices=None: serving_mesh(tp, v5e)
    )

    def on_topology(model_cfg, slots, qmode, tp):
        model, params, carry, rngs, active, shaped = abstracts(
            model_cfg, slots, qmode, tp
        )
        if tp > 1:  # already NamedShardings over the described mesh
            return model, params, carry, rngs, active, shaped
        one = SingleDeviceSharding(v5e[0])
        put = lambda l: jax.ShapeDtypeStruct(  # noqa: E731
            l.shape, l.dtype, sharding=one)
        return (
            model, jax.tree.map(put, params), jax.tree.map(put, carry),
            put(rngs), put(active),
            lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one),
        )

    monkeypatch.setattr(aot, "_decode_abstracts", on_topology)
    cfg = dataclasses.replace(get_config("lm_1b3"), backend="pallas")
    rep = aot.decode_plan(
        cfg, slots=8, chunk=16, prefill_buckets=(1024,), prefill_chunk=64,
        qmode=qmode, tp=tp, sample=SampleConfig(temperature=0.0),
        compile_step=True,
    )
    for prog in rep["programs"]:
        assert prog.get("compiled"), prog
    by_kind = {p["kind"]: p["collectives"] for p in rep["programs"]}
    if tp > 1:
        # two all-reduces per block per decode step, no kernel to partition
        assert by_kind["decode_batched"]["all-reduce"] == 2 * cfg.n_layers
        assert by_kind["unified_prefill"]["mosaic_kernels"] == 0
    else:
        assert by_kind["unified_prefill"]["mosaic_kernels"] > 0
