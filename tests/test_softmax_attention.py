"""Parity tests: XLA softmax attention vs Pallas flash (interpret mode),
values and grads, across causal/bidirectional/sliding-window; plus the
decode-time cached-attention invariant. Mirrors the reference's
CPU-vs-CUDA parity fixtures (SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.ops.pallas.flash_attention import flash_attention
from orion_tpu.ops.softmax_attention import (
    cached_attention,
    softmax_attention_xla,
)


def _rand(key, *shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=jnp.float32).astype(dtype)


@pytest.mark.parametrize("causal,window", [(True, None), (False, None), (True, 7)])
@pytest.mark.parametrize("t", [32, 50])
def test_flash_matches_xla(causal, window, t):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(k1, 2, 3, t, 16)
    k = _rand(k2, 2, 3, t, 16)
    v = _rand(k3, 2, 3, t, 16)
    ref = softmax_attention_xla(q, k, v, causal=causal, window=window)
    got = flash_attention(
        q, k, v, causal=causal, window=window, block_q=16, block_k=16, interpret=True
    )
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)


def test_flash_bf16():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(k1, 2, 24, 16, dtype=jnp.bfloat16)
    k = _rand(k2, 2, 24, 16, dtype=jnp.bfloat16)
    v = _rand(k3, 2, 24, 16, dtype=jnp.bfloat16)
    ref = softmax_attention_xla(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, block_q=8, block_k=8, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        got.astype(np.float32), ref.astype(np.float32), atol=3e-2, rtol=3e-2
    )


@pytest.mark.parametrize("causal,window", [(True, None), (False, None), (True, 5)])
def test_flash_grads_match_xla(causal, window):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(2), 4)
    t = 20
    q = _rand(k1, 2, t, 8)
    k = _rand(k2, 2, t, 8)
    v = _rand(k3, 2, t, 8)
    w = _rand(k4, 2, t, 8)

    def loss_ref(q, k, v):
        return jnp.sum(
            softmax_attention_xla(q, k, v, causal=causal, window=window) * w
        )

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(
                q, k, v, causal=causal, window=window,
                block_q=8, block_k=8, interpret=True,
            )
            * w
        )

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)


def test_key_padding_mask():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(k1, 2, 10, 8)
    k = _rand(k2, 2, 10, 8)
    v = _rand(k3, 2, 10, 8)
    mask = jnp.arange(10)[None, :] < jnp.array([6, 9])[:, None]  # [B, Tk]
    out = softmax_attention_xla(q, k, v, causal=False, mask=mask)
    # truncating to the valid prefix must give the same rows
    out6 = softmax_attention_xla(q[0:1], k[0:1, :6], v[0:1, :6], causal=False)
    np.testing.assert_allclose(out[0], out6[0], atol=1e-5, rtol=1e-5)


def test_cached_attention_matches_full():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    t, d = 12, 8
    q = _rand(k1, 2, t, d)
    k = _rand(k2, 2, t, d)
    v = _rand(k3, 2, t, d)
    full = softmax_attention_xla(q, k, v, causal=True)
    smax = 16  # cache capacity > t
    kc = jnp.pad(k, ((0, 0), (0, smax - t), (0, 0)))
    vc = jnp.pad(v, ((0, 0), (0, smax - t), (0, 0)))
    for step in [0, 3, t - 1]:
        valid = jnp.arange(smax)[None, :] <= step
        got = cached_attention(q[:, step], kc, vc, valid)
        np.testing.assert_allclose(got, full[:, step], atol=1e-5, rtol=1e-5)


def test_cached_attention_ring_buffer_window():
    """Sliding-window decode with a rotated ring buffer == windowed attention."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    t, d, w = 10, 8, 4
    q = _rand(k1, 1, t, d)
    k = _rand(k2, 1, t, d)
    v = _rand(k3, 1, t, d)
    full = softmax_attention_xla(q, k, v, causal=True, window=w)
    step = 7  # attends to positions 4..7, ring slots hold 4,5,6,7 rotated
    slots = [(step - i) % w for i in range(w)]  # slot for position step-i
    kc = jnp.zeros((1, w, d)).at[:, [s % w for s in range(step - w + 1, step + 1)]].set(
        k[:, step - w + 1 : step + 1]
    )
    vc = jnp.zeros((1, w, d)).at[:, [s % w for s in range(step - w + 1, step + 1)]].set(
        v[:, step - w + 1 : step + 1]
    )
    del slots
    valid = jnp.ones((1, w), dtype=bool)
    got = cached_attention(q[:, step], kc, vc, valid)
    np.testing.assert_allclose(got, full[:, step], atol=1e-5, rtol=1e-5)


def test_dispatch_backend_xla():
    from orion_tpu.ops.softmax_attention import softmax_attention

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(6), 3)
    q, k, v = _rand(k1, 1, 9, 8), _rand(k2, 1, 9, 8), _rand(k3, 1, 9, 8)
    a = softmax_attention(q, k, v, backend="xla")
    b = softmax_attention(q, k, v, backend="pallas_interpret", block_q=8, block_k=8)
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


# -- banded swa grid (VERDICT r4 #6: clip, don't mask) -----------------------


@pytest.mark.parametrize("t,w,bq,bk", [
    (256, 64, 32, 16),   # small bk: the boundary-clip configuration
    (256, 64, 32, 32),
    (192, 48, 64, 16),   # T not a bq multiple; w not a bk multiple
    (130, 96, 32, 16),   # ragged tail + window near T
])
def test_banded_swa_matches_xla(t, w, bq, bk):
    """The banded grid (k sweep covers only the band via a qi-dependent
    index map) must be value- and grad-identical to the XLA reference —
    including near the sequence start, where band tiles clip at 0."""
    import jax

    from orion_tpu.ops.pallas.flash_attention import _banded_ok
    from orion_tpu.ops.softmax_attention import softmax_attention_xla

    assert _banded_ok(True, w, 0, 0, t, t)  # the path under test engages
    key = jax.random.PRNGKey(t + w)
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (1, 2, t, 16))
        for i in range(3)
    )
    wgt = jax.random.normal(jax.random.fold_in(key, 7), (1, 2, t, 16))

    def f_ref(q, k, v):
        return jnp.sum(softmax_attention_xla(q, k, v, causal=True, window=w) * wgt)

    def f_banded(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, window=w, block_q=bq,
                            block_k=bk, interpret=True) * wgt
        )

    np.testing.assert_allclose(
        float(f_banded(q, k, v)), float(f_ref(q, k, v)), atol=2e-4, rtol=2e-4
    )
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(f_banded, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gb):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4
        )


# -- bf16 operands, lane-replicated statistics, k-major dK/dV (PR 54)
# Five q tiles by five k tiles, the last of each ragged (T 300 under 64 x 64
# blocks, T 600 under 128 x 128), so that one call holds tiles the mask leaves
# whole, tiles an edge of the mask crosses, a tile with padded keys and skipped
# tiles at once. Blocks and head of 128 take the forward's lane-replicated form
# of m and l (``_stat_lanes``), 64 the column form; dK/dV reads lse and delta
# as (1, Bq) rows in both.

_TILE_CASES = {  # lengths in units of a block
    "causal_full": dict(causal=True),
    "bidirectional_ragged_keys": dict(causal=False, t_k=4.375),
    "swa_banded": dict(causal=True, window=2.34375),
    "swa_unbanded": dict(causal=True, window=5),
    "shift_1": dict(causal=True, shift=1),
    # the halo caller's geometry (parallel/ring.py): the keys are the shard
    # before the queries', so the causal bound never bites and the window does
    "q_offset": dict(causal=True, window=3.125, q_offset=4.6875),
}
_T_BLOCKS = 4.6875  # 300 / 64
# max |error| against the fp32 reference, the inputs' rounding excluded (the
# reference reads the same bf16 inputs as fp32): P, dS and every output are
# rounded to bf16 (2^-9 relative) where the fp32 path rounds nothing
_TOL = {"float32": dict(atol=3e-5, rtol=3e-5), "bfloat16": dict(atol=2e-2, rtol=2e-2)}


def _tile_case(case, blk):
    """(t_q, t_k, the kernels' mask arguments) of ``case`` at block ``blk``."""
    geo = {k: (v if isinstance(v, bool) or k == "shift" else int(v * blk))
           for k, v in _TILE_CASES[case].items()}
    t_q = int(_T_BLOCKS * blk)
    return t_q, geo.pop("t_k", t_q), geo


def _visible(t_q, t_k, causal=True, window=None, shift=0, q_offset=0):
    """The structural mask as the kernels define it, [t_q, t_k]."""
    rows = np.arange(t_q)[:, None] + q_offset
    cols = np.arange(t_k)[None, :]
    m = np.ones((t_q, t_k), bool)
    if causal:
        m &= rows >= cols + shift
    if window is not None:
        m &= (rows - cols) < window
    return m


def _tile_kinds(t_q, t_k, blk, **geo):
    """How many tiles of the grid the mask leaves whole, crosses (or pads) and
    empties."""
    nq, nk = -(-t_q // blk), -(-t_k // blk)
    vis = np.zeros((nq * blk, nk * blk), bool)
    vis[:, :t_k] = _visible(nq * blk, t_k, **geo)
    seen = vis.reshape(nq, blk, nk, blk).sum(axis=(1, 3))
    return {"interior": int((seen == blk * blk).sum()),
            "skipped": int((seen == 0).sum()),
            "edge": int(((seen > 0) & (seen < blk * blk)).sum())}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("blk", [64, 128], ids=["columns", "lanes"])
@pytest.mark.parametrize("case", list(_TILE_CASES))
def test_flash_tiles_match_xla(case, blk, dtype):
    """Forward and ``jax.grad`` of the interpret-mode kernels against the
    materialising fp32 reference, over every kind of tile in one call."""
    from orion_tpu.ops.pallas.flash_attention import _stat_lanes, flash_attention_lse

    t_q, t_k, geo = _tile_case(case, blk)
    kinds = _tile_kinds(t_q, t_k, blk, **geo)
    assert kinds["interior"] and kinds["edge"], kinds
    assert kinds["skipped"] or not geo["causal"], kinds
    assert _stat_lanes(blk, blk) == (128 if blk == 128 else 1)

    ks = jax.random.split(jax.random.PRNGKey(54), 4)
    q = _rand(ks[0], 2, t_q, blk, dtype=dtype)  # head dim = the block
    k = _rand(ks[1], 2, t_k, blk, dtype=dtype)
    v = _rand(ks[2], 2, t_k, blk, dtype=dtype)
    vis = _visible(t_q, t_k, **geo)
    # a query that sees no key at all (row 0 under shift 1, the halo's far
    # rows) has no softmax to compare: it carries no weight
    seen = vis.any(axis=1)
    w = _rand(ks[3], 2, t_q, blk) * seen[None, :, None]

    def loss_ref(q, k, v):
        out = softmax_attention_xla(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
            causal=False, mask=jnp.asarray(vis),
        )
        return jnp.sum(out * w), out

    def loss_flash(q, k, v):
        out, _ = flash_attention_lse(q, k, v, block_q=blk, block_k=blk, interpret=True,
                                     **geo)
        return jnp.sum(out.astype(jnp.float32) * w), out

    (_, out_r), gr = jax.value_and_grad(loss_ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, out_f), gf = jax.value_and_grad(loss_flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    assert out_f.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(out_f, np.float32)[:, seen], np.asarray(out_r)[:, seen], **_TOL[dtype]
    )
    for a, b in zip(gf, gr):
        assert a.dtype == q.dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), **_TOL[dtype]
        )


@pytest.mark.parametrize("case", list(_TILE_CASES))
def test_flash_fwd_lane_replicated_stats_bit_identical_to_columns(case, monkeypatch):
    """m and l held lane-replicated (Bq, 128) are the same numbers read from
    more lanes: with fp32 inputs (the operand casts are no-ops) the forward
    gives the BITS of the (Bq, 1) column form it had before PR 54."""
    from orion_tpu.ops.pallas import flash_attention as fa

    blk = 128
    t_q, t_k, geo = _tile_case(case, blk)
    ks = jax.random.split(jax.random.PRNGKey(5454), 3)
    q, k, v = _rand(ks[0], 2, t_q, blk), _rand(ks[1], 2, t_k, blk), _rand(ks[2], 2, t_k, blk)

    def run():  # the function as written: no trace cache between the two
        return fa._flash_fwd_flat.__wrapped__(
            q, k, v, 0.125, geo["causal"], geo.get("window"), blk, blk, True,
            shift=geo.get("shift", 0), q_offset=geo.get("q_offset", 0))

    assert fa._stat_lanes(blk, blk) == 128
    lanes = run()
    monkeypatch.setattr(fa, "_stat_lanes", lambda *widths: 1)
    columns = run()
    for a, b in zip(lanes, columns):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("geo", [
    dict(causal=True),
    dict(causal=False),
    dict(causal=True, window=40),
    dict(causal=True, window=16),      # window == a block: no tile left whole
    dict(causal=False, window=24),
    dict(causal=True, shift=1),
    dict(causal=True, q_offset=21),
    dict(causal=True, window=40, q_offset=48),
    dict(causal=True, window=30, shift=1, q_offset=5),
], ids=lambda g: "-".join(f"{k}{v}" for k, v in g.items()))
@pytest.mark.parametrize("t_k", [96, 90], ids=["whole", "padded_keys"])
@pytest.mark.parametrize("bq,bk", [(16, 16), (32, 16), (16, 32)])
def test_skip_and_fetch_predicates_are_the_mask(geo, t_k, bq, bk):
    """For every (qi, ki): ``_skip_tile`` is true exactly when the structural
    mask over REAL keys' columns is all-false (a skipped tile is never read;
    a tile of padded keys alone does not exist: nk = ceil(t_k / bk)). A
    computed tile fetches its own blocks; a skipped step fetches the row's
    NEAREST computed tile (the block already resident, which moves no bytes),
    or any block of the grid where its row computes nothing."""
    from orion_tpu.ops.pallas.flash_attention import (
        _fetched_k, _fetched_q, _skip_tile, _tile_mask,
    )

    causal, window = geo["causal"], geo.get("window")
    shift, q_offset = geo.get("shift", 0), geo.get("q_offset", 0)
    t_q = 96
    nq, nk = t_q // bq, -(-t_k // bk)
    skips = np.array([[bool(_skip_tile(qi, ki, bq, bk, causal, window, shift, q_offset))
                       for ki in range(nk)] for qi in range(nq)])

    def nearest(computed, at):
        return {c for c in computed if abs(c - at) == min(abs(computed - at))}

    for qi in range(nq):
        for ki in range(nk):
            rows, cols = np.meshgrid(qi * bq + np.arange(bq), ki * bk + np.arange(bk),
                                     indexing="ij")
            skip = skips[qi, ki]
            # key padding apart (it never empties a tile on its own)
            unpadded = _tile_mask(rows, cols, causal, window, 10 ** 9, shift, q_offset)
            assert skip == (not unpadded.any()), (qi, ki)
            fk = int(_fetched_k(qi, ki, nk, bq, bk, causal, window, shift, q_offset))
            fq = int(_fetched_q(ki, qi, nq, bq, bk, causal, window, shift, q_offset))
            assert 0 <= fk < nk and 0 <= fq < nq, (qi, ki, fk, fq)
            row, col = np.flatnonzero(~skips[qi]), np.flatnonzero(~skips[:, ki])
            assert not row.size or fk in nearest(row, ki), (qi, ki, fk)
            assert not col.size or fq in nearest(col, qi), (qi, ki, fq)
