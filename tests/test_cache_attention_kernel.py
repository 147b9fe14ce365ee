"""The row-list decode attention kernel (ops/pallas/cache_attention.py) in
interpret mode on the CPU, and the dispatch entry and mixer that call it.

Kernel level: a listed row's output and log-sum-exp are the softmax over
its cache rows ``[0, length)`` (fp32 rounding apart: the summation order
differs), whatever lies at or past ``length`` (NaN planted there changes
nothing: a dead block is never read and the last live one is masked); an
unlisted row comes out as ``(0, -1e30)`` and its cache is never read.
Merged with the chunk's own rows (``models/mixers/softmax.py::
chunk_local_attention``) that is the concatenated form the mixer used to
compute over the whole reservation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.models.mixers.softmax import chunk_local_attention
from orion_tpu.ops import dispatch
from orion_tpu.ops.pallas.cache_attention import (
    BLOCK_KV, cache_attention, kv_block, rows_read,
)
from orion_tpu.ops.pallas.decode_state import live_rows
from orion_tpu.ops.softmax_attention import _NEG

# the served widths' head count (not a multiple of 8) and head size; three
# KV blocks; a chunk of 4 own rows
B, H, D, CAP, N = 4, 30, 128, 3 * BLOCK_KV, 4
LENGTHS = [0, 1, BLOCK_KV - 1, BLOCK_KV, BLOCK_KV + 1, CAP]


def _inputs(dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (B, H, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, H, CAP, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, H, CAP, D)).astype(dtype)
    kn = jax.random.normal(ks[3], (B, H, N, D)).astype(dtype)
    vn = jax.random.normal(ks[4], (B, H, N, D)).astype(dtype)
    return q, k, v, kn, vn


def _poison(cache, lengths):
    """NaN at every position >= the row's length."""
    dead = jnp.arange(CAP)[None, None, :, None] >= lengths[:, None, None, None]
    return jnp.where(dead, jnp.nan, cache.astype(jnp.float32)).astype(cache.dtype)


def _concatenated(q, state, t):
    """The mixer's decode attention before the kernel: one softmax over the
    held cache's rows before ``t0`` and the chunk's own rows up to
    ``t - t0``, side by side, over the whole reservation under a mask."""
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    j = t - state["t0"]
    qf = q.astype(f32) * q.shape[-1] ** -0.5
    old = jnp.einsum("bhd,bhsd->bhs", qf, state["k"].astype(f32), precision=hi)
    new = jnp.einsum("bhd,bhsd->bhs", qf, state["kn"].astype(f32), precision=hi)
    cap, n = old.shape[-1], new.shape[-1]
    old = jnp.where(jnp.arange(cap)[None, None] < state["t0"][:, None, None], old, _NEG)
    new = jnp.where(jnp.arange(n)[None, None] <= j[:, None, None], new, _NEG)
    p = jax.nn.softmax(jnp.concatenate([old, new], axis=-1), axis=-1)
    out = jnp.einsum("bhs,bhsd->bhd", p[..., :cap], state["v"].astype(f32), precision=hi)
    return out + jnp.einsum("bhs,bhsd->bhd", p[..., cap:], state["vn"].astype(f32), precision=hi)


@jax.jit
def _kernel(q, k, v, lengths, mask):
    return cache_attention(q, k, v, lengths, live_rows(mask), interpret=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("length", LENGTHS)
def test_kernel_and_merge_equal_the_concatenated_form(length, dtype):
    """Rows 0 and 1 at ``length``, row 3 at another; row 2 unlisted with a
    cache that is NaN throughout. Dead positions of every row hold NaN."""
    q, k, v, kn, vn = _inputs(dtype)
    t0 = jnp.array([length, length, 300, 2 * BLOCK_KV + 7], jnp.int32)
    mask = jnp.array([True, True, False, True])
    j = jnp.array([0, N - 1, 1, 2], jnp.int32)  # this step's row in the chunk
    clean = {"k": k, "v": v, "kn": kn, "vn": vn, "t0": t0}
    want = np.asarray(_concatenated(q, clean, t0 + j))
    dead = jnp.where(mask, t0, 0)  # the unlisted row: NaN from position 0
    state = dict(clean, k=_poison(k, dead), v=_poison(v, dead))
    rows = live_rows(mask)
    got = jax.jit(
        lambda q, s, t: chunk_local_attention(q, s, t, rows, "pallas_interpret")
    )(q, state, t0 + j)
    assert got.dtype == q.dtype
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    listed = np.asarray(mask)
    tol = 1e-5 if dtype == jnp.float32 else 2 ** -7  # one bf16 ulp of the output
    np.testing.assert_allclose(got[listed], want[listed], rtol=tol, atol=tol)
    # the unlisted row: its chunk's own rows alone, the same on every call
    own = np.asarray(_concatenated(q, dict(clean, t0=jnp.zeros_like(t0)), j))
    np.testing.assert_allclose(got[~listed], own[~listed], rtol=tol, atol=tol)


@pytest.mark.parametrize("length", LENGTHS)
def test_bf16_cache_products_are_fp32(length):
    """The kernel alone on a bf16 cache against the fp32 softmax of the
    same bf16 values: the split operand rounds nothing (1e-5 relative),
    where a bf16 ``p`` would be off by 4e-3."""
    q, k, v, _, _ = _inputs(jnp.bfloat16, seed=3)
    lengths = jnp.array([length, 5, CAP, 2 * BLOCK_KV + 7], jnp.int32)
    mask = jnp.array([True, True, True, False])
    out, lse = _kernel(q, _poison(k, lengths), _poison(v, lengths), lengths, mask)
    assert out.dtype == lse.dtype == jnp.float32
    f32 = jnp.float32
    valid = jnp.arange(CAP)[None, None, :] < lengths[:, None, None]
    s = jnp.einsum("bhd,bhsd->bhs", q.astype(f32) * D ** -0.5, k.astype(f32),
                   precision=jax.lax.Precision.HIGHEST)
    s = jnp.where(valid, s, -jnp.inf)
    want = jnp.einsum("bhs,bhsd->bhd", jax.nn.softmax(s, -1), v.astype(f32),
                      precision=jax.lax.Precision.HIGHEST)
    rows = [b for b in range(3) if int(lengths[b]) > 0]
    np.testing.assert_allclose(
        np.asarray(out)[rows], np.asarray(want)[rows], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(lse)[rows], np.asarray(jax.nn.logsumexp(s, -1))[rows], rtol=1e-5, atol=1e-5)
    empty = [b for b in range(B) if b not in rows]  # length 0, and unlisted
    assert (np.asarray(out)[empty] == 0).all()
    assert (np.asarray(lse)[empty] == np.float32(_NEG)).all()


@pytest.mark.parametrize("pattern", [[], [2], [0, 1, 3], [0, 1, 2, 3]])
def test_three_steps_inside_a_scan_over_a_held_cache(pattern):
    """The donated decode scan's shape: the row list and the lengths built
    once outside a ``lax.scan`` that closes over the cache."""
    q, k, v, _, _ = _inputs()
    qs = jnp.stack([q, q[::-1], 2 * q])
    lengths = jnp.array([7, BLOCK_KV, BLOCK_KV + 9, CAP], jnp.int32)
    mask = np.zeros(B, bool)
    mask[pattern] = True

    @jax.jit
    def run(qs, k, v, lengths, mask):
        rows = live_rows(mask)
        step = lambda c, q: (c, cache_attention(q, k, v, lengths, rows, interpret=True))  # noqa: E731
        return jax.lax.scan(step, 0, qs)[1]

    outs, lses = run(qs, k, v, lengths, jnp.asarray(mask))
    for i in range(3):
        want, want_lse = dispatch.cache_attention(qs[i], k, v, lengths, backend="xla")
        np.testing.assert_allclose(
            np.asarray(outs[i])[mask], np.asarray(want)[mask], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(lses[i])[mask], np.asarray(want_lse)[mask], rtol=1e-5, atol=1e-5)
        assert (np.asarray(outs[i])[~mask] == 0).all()


def test_dispatch_runs_the_kernel_only_with_a_row_list_under_pallas():
    q, k, v, _, _ = _inputs()
    lengths = jnp.array([3, BLOCK_KV + 1, CAP, 40], jnp.int32)
    mask = jnp.ones(B, bool)

    def text(backend, with_rows):
        rows = dispatch.decode_live_rows(mask, backend=backend) if with_rows else None
        return str(jax.make_jaxpr(
            lambda q, k, v, n: dispatch.cache_attention(q, k, v, n, rows, backend=backend)
        )(q, k, v, lengths))

    assert "pallas_call" in text("pallas_interpret", True)
    assert "pallas_call" not in text("pallas_interpret", False)
    assert "pallas_call" not in text("xla", True)  # decode_live_rows gives None
    a = dispatch.cache_attention(q, k, v, lengths, backend="xla")
    rows = dispatch.decode_live_rows(mask, backend="pallas_interpret")
    b = dispatch.cache_attention(q, k, v, lengths, rows, backend="pallas_interpret")
    for x, y in zip(a, b):
        assert x.dtype == y.dtype == jnp.float32 and x.shape == y.shape
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-5)


def test_blocks_and_the_rows_a_listed_row_streams():
    assert kv_block(4096) == BLOCK_KV and kv_block(64) == 64
    assert kv_block(BLOCK_KV + 16 * 3) == 16
    with pytest.raises(ValueError, match="does not tile"):
        kv_block(BLOCK_KV + 8)
    assert rows_read(0, 4096) == BLOCK_KV  # a listed row fetches one block
    assert rows_read(1, 4096) == rows_read(BLOCK_KV, 4096) == BLOCK_KV
    assert rows_read(BLOCK_KV + 1, 4096) == 2 * BLOCK_KV
    assert rows_read(5000, 4096) == 4096 and rows_read(10, 64) == 64


def test_operands_must_fit_the_cache():
    q, k, v, _, _ = _inputs()
    rows = live_rows(jnp.ones(B, bool))
    lengths = jnp.zeros(B, jnp.int32)
    with pytest.raises(ValueError, match="do not fit"):
        cache_attention(q[:, :2], k, v, lengths, rows, interpret=True)
    with pytest.raises(ValueError, match="one cache dtype"):
        cache_attention(q, k, v.astype(jnp.bfloat16), lengths, rows, interpret=True)
