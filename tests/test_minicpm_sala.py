"""The served decayed-linear / block-sparse hybrid (ISSUE 37): decayed linear
attention with no normaliser (rotary, per-head q/k norm, output norm and
gate) x3 : block-sparse attention over a grouped KV cache (no rotary, a
learned block selection past ``sparse_dense_len``, an output gate) x1, with
a scaled embedding, scaled residual branches and scaled logits, against
``benchmark/reference/plain_minicpm_sala.py``; tiny, CPU, fp32. The contract
every served configuration takes is ``tests/served_contract.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from served_contract import (
    ByBackend, Cell, Scan, ServedCase, ServedContract, Walk, served_fixture, tiny_cfg,
)

from orion_tpu.models.configs import get_config
from orion_tpu.models.mixers import MIXERS
from orion_tpu.models.mixers.block_sparse import blocks_read, list_width
from orion_tpu.models.transformer import TransformerLM, init_decode_state
from orion_tpu.ops import dispatch
from orion_tpu.ops.linear_attention import (
    causal_dot_product_chunked, decay_slopes, decayed_causal_dot_eager,
    recurrent_step,
)

# the configuration's rehearse block is the selector scaled down 4x: blocks of
# 16 tokens, pooled keys of 8 every 4, top-4 blocks (1 initial + 2 local + 1
# chosen) past 64 positions; T = 203 is 13 blocks, three times ``topk``
T, N = 203, 150
CASE = ServedCase(
    "minicpm_sala", seq=T,
    logit_tol=2e-5,  # fp32 against fp32 on logits of ~0.3: summation order only
    over=dict(max_seq_len=256, chunk=16),
    constants=dict(query_tile=64),
    bites=dict(dense_len=10 ** 6),  # the switch never taken
    # pieces of 48 (three chunks), the last one 6 real rows of 48. XLA: pieces
    # at chunk multiples replay prefill's operations (exact); the walk and the
    # kernels agree to fp32 summation order (S reaches ~20)
    walk=Walk(n=N, piece=48, steps=T - N, cold=80,
              states=ByBackend(xla=dict(atol=0, rtol=0), pallas_interpret=dict(atol=2e-5, rtol=2e-5)),
              cold_states=dict(atol=2e-5, rtol=2e-5)),
    row_list=127,  # position 127 ends a pooled key too
    # twelve steps past ``dense_len``: three pooled keys complete inside the
    # chunk, the first from keys on both sides of ``t0``
    scan=Scan(n=N, steps=12, layer=-1, held=("k", "v"), carried=("kn", "vn", "kp", "t0")),
    server=True,
    cell=Cell("minicpm_sala.serve_long", seed=2 ** 31 + 37),
)
served = served_fixture(CASE)


class TestServed(ServedContract):
    case = CASE

    def published(self, cfg):
        assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (4096, 32, 2, 128)
        assert (cfg.mlp_hidden, cfg.vocab_size, cfg.tie_embeddings) == (16384, 73448, False)
        assert cfg.resolved_layer_types == ("decay_linear",) * 3 + ("block_sparse",)
        assert (cfg.sparse_kernel, cfg.sparse_stride, cfg.sparse_block, cfg.sparse_init_blocks,
                cfg.sparse_window, cfg.sparse_topk, cfg.sparse_dense_len) == (32, 16, 64, 1, 2048, 64, 8192)
        assert cfg.embed_scale == 12 and cfg.logit_scale == 1 / 16
        assert abs(cfg.residual_scale - 1.4 / 32 ** 0.5) < 1e-12  # the PUBLISHED depth
        assert list_width(cfg) == 128 and cfg.max_seq_len == 16384 + 512
        shapes = jax.eval_shape(lambda: init_decode_state(cfg, 2))
        assert [sorted(s) for s in shapes] == [["s"]] * 3 + [["k", "kp", "v"]]
        assert shapes[0]["s"].shape == (2, 32, 128, 128) and shapes[0]["s"].dtype == jnp.float32
        assert shapes[3]["k"].shape == (2, 2, 16896, 128) and shapes[3]["kp"].shape == (2, 2, 1056, 128)
        n = sum(x.size for x in jax.tree.leaves(jax.eval_shape(
            lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))))
        assert abs(n - (1109e6 + 601.7e6)) < 2e6, n

    def after_scan(self, start, merged):
        assert not bool((merged[-1]["kp"] == start[-1]["kp"]).all())

    def before_boundary(self, engine):
        return engine.kv_blocks()

    def after_engine(self, served, run, backend, donate):
        """The block counters follow the positions; with the carry donated
        the scan reads K and V, carries a chunk's own rows and the pooled
        keys (``chunk_split``; a pooled row completes inside a chunk from
        keys on both sides of ``t0``)."""
        live, read, sparse, dense = (sum(x) for x in zip(*run.seen))
        assert dense > 0 and sparse > 0 and read < live
        # the last boundaries: the long request alone, past dense_len
        assert run.seen[-1][1:] == (run.cfg.sparse_topk, 1, 0) and run.seen[-1][0] >= 12

    def after_server(self, served, counters, prompts):
        assert counters["sparse_steps"] > 0 and counters["dense_steps"] > 0
        assert 0 < counters["kv_blocks_read"] < counters["kv_blocks_live"]

    def after_cell(self, result, lines):
        assert 0 < result["metrics"]["kv_block_read_share.long"]["value"] < 100


@pytest.mark.parametrize("what,over", [
    ("no decay", {"decay_exponent": 60.0}),
    ("an unscaled residual", {"residual_scale": 1.0}),
    ("an unscaled embedding", {"embed_scale": 1.0}),
    ("unscaled logits", {"logit_scale": 1.0}),
    ("unforced local blocks", {"window": 16}),
    ("no initial block", {"init_blocks": 0}),
    ("another topk", {"topk": 5}),
])
def test_the_comparison_sees(served, what, over):
    """The tolerance is tight enough to tell the model from a reference
    that differs in one of the mechanisms."""
    served.differs(served.spec(**over))


@pytest.mark.parametrize("patch", ["a normaliser", "rotary on the sparse layer",
                                   "a selection per head"])
def test_the_comparison_sees_a_changed_layer(served, monkeypatch, patch):
    ref = served.ref
    if patch == "a normaliser":
        plain = ref.lightning_out

        def normalised(spec, p, x, o):
            q, k, _ = ref.lightning_qkv(spec, p, x)
            z = jnp.einsum("bhtd,bhtd->bht", q, jnp.cumsum(k, axis=2))[..., None]
            return plain(spec, p, x, o / (jnp.abs(z) + 1.0))

        monkeypatch.setattr(ref, "lightning_out", normalised)
    elif patch == "rotary on the sparse layer":
        plain_heads = ref.heads
        monkeypatch.setattr(
            ref, "heads",
            lambda y, n, dh: ref.rope(plain_heads(y, n, dh), 10000.0) if n == 2 else plain_heads(y, n, dh))
    else:
        plain_sel = ref.selected_blocks

        def per_head(spec, q, kp, pos, n_blocks):  # every head its own list: the first head's here
            return plain_sel(spec, q[:, :, :1], kp, pos, n_blocks)

        monkeypatch.setattr(ref, "selected_blocks", per_head)
    monkeypatch.setitem(ref.MIXERS, "decay_linear", ref.lightning)
    served.differs()


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_the_cache_is_split_by_the_program_the_linear_state_by_the_backend(backend):
    """``chunk_split``: K and V are held only where the program's carry is
    donated (the cell's unified program carries them whole, as before PR
    38), whatever the backend; a ``linear`` layer's ``(S, z)`` is held where
    the step takes the row-list kernel, in both kinds of program; the
    decayed ``S`` is written at every step and is never split."""
    cfg = tiny_cfg(CASE, backend, n_layers=3, layer_types=("decay_linear", "block_sparse", "linear"))
    states, t = init_decode_state(cfg, 2), jnp.zeros((2,), jnp.int32)
    for donated in (False, True):
        (dh, dc), (bh, bc), (lh, lc) = (
            MIXERS[lt].chunk_split(cfg, lt, st, 4, t, donated)
            for lt, st in zip(cfg.resolved_layer_types, states)
        )
        assert dh == {} and dc is states[0]
        if donated:
            assert set(bh) == {"k", "v"} and set(bc) == {"kn", "vn", "kp", "t0"}
        else:
            assert bh == {} and bc is states[1]
        if backend == "xla":
            assert lh == {} and lc is states[2]
        else:
            assert set(lh) == {"s", "z"} and set(lc) == {"kc", "vc", "t0"}
            assert lc["kc"].shape == (2, 4, cfg.n_heads, cfg.head_dim)


def decay_inputs(b=2, h=4, t=70, d=16, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    q, k, v = (jax.random.normal(kk, (b, h, t, d)) for kk in keys[:3])
    return q, k, v, jax.random.normal(keys[3], (b, h, d, d)), decay_slopes(h)


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("case", ["whole", "state in", "padded"])
def test_decayed_chunked_forms_against_the_recurrence(backend, case):
    q, k, v, s0, slopes = decay_inputs()
    s_in = None if case == "whole" else s0
    real = 53 if case == "padded" else 70
    want, s_want = decayed_causal_dot_eager(
        q[:, :, :real], k[:, :, :real], v[:, :, :real], slopes, s_in)
    got, s_got = dispatch.causal_dot_product(
        q, k, v, backend=backend, chunk=16, decay=slopes, initial_state=s_in,
        length=jnp.int32(real) if case == "padded" else None)
    assert s_got.dtype == jnp.float32
    np.testing.assert_allclose(got[:, :, :real], want, atol=2e-4)
    np.testing.assert_allclose(s_got, s_want, atol=2e-4)
    # a decay that is left out shows
    plain = causal_dot_product_chunked(q, k, v, chunk=16)
    assert float(jnp.abs(plain[:, :, :real] - want).max()) > 1.0


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_decayed_step_against_the_recurrence(backend):
    q, k, v, s0, slopes = decay_inputs(b=5, t=6)
    mask = jnp.array([True, False, True, True, False])
    rows = dispatch.decode_live_rows(mask, backend=backend)
    assert (rows is None) == (backend == "xla")
    want, s_want = decayed_causal_dot_eager(q, k, v, slopes, s0)
    s = s0
    for i in range(6):
        o, s = dispatch.decode_state_step(
            q[:, :, i], k[:, :, i], v[:, :, i], s, rows, backend=backend, decay=slopes)
        live = mask if rows is not None else jnp.ones((5,), bool)
        np.testing.assert_allclose(o[live], want[:, :, i][live], atol=1e-4)
    np.testing.assert_allclose(s[live], s_want[live], atol=1e-4)
    if rows is not None:
        assert bool((s[~mask] == s0[~mask]).all())


@pytest.mark.parametrize("op", ["step-xla", "step-kernel", "chunked-xla", "chunked-kernel"])
def test_without_a_decay_the_ops_are_the_parents(op):
    """``decay=None``: ``decode_state_step`` and ``causal_dot_product`` give
    the bits of the functions they dispatched to before this PR."""
    from orion_tpu.ops.pallas import causal_dot as pcd
    from orion_tpu.ops.pallas import decode_state as pds

    q, k, v, s0, _ = decay_inputs(b=3)
    z0 = jnp.abs(s0[..., 0])
    phi = lambda x: jax.nn.elu(x) + 1.0  # noqa: E731
    if op.startswith("step"):
        args = (phi(q[:, :, 0]), phi(k[:, :, 0]), v[:, :, 0], (s0, z0))
        if op == "step-xla":
            got = dispatch.decode_state_step(*args, None, backend="xla")
            want = recurrent_step(*args)
        else:
            # since PR 38 the row-list step reads (S, z) beside the chunk's own rows
            rows = dispatch.decode_live_rows(jnp.array([True, True, False]), backend="pallas_interpret")
            chunk = tuple(jnp.zeros((3, 2, *x.shape[1:])) for x in (args[1], args[2]))  # [B, n, H, D]
            j = jnp.zeros((3,), jnp.int32)
            got = dispatch.decode_state_step(*args, rows, backend="pallas_interpret", chunk=(*chunk, j))
            want = pds.decode_state_step(*args, chunk, j, rows, interpret=True)
    elif op == "chunked-xla":
        got = dispatch.causal_dot_product(q, k, v, backend="xla", chunk=16, return_state=True, initial_state=s0)
        want = causal_dot_product_chunked(q, k, v, chunk=16, return_state=True, initial_state=s0)
    else:
        got = dispatch.causal_dot_product(q, k, v, backend="pallas_interpret", chunk=16, return_state=True)
        want = pcd.causal_dot_product_pallas(q, k, v, chunk=16, return_state=True, interpret=True)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_block_list_attention_kernel_against_the_gather(dtype):
    """``cache_attention(blocks=...)``: the kernel (interpret mode) against
    the XLA gather, lists in any order, counts short of the list, a length
    inside a listed block, an unlisted row never read."""
    b, kvh, g, d, cap, size, width = 3, 2, 4, 16, 128, 16, 6
    keys = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(keys[0], (b, kvh * g, d))
    kc = jax.random.normal(keys[1], (b, kvh, cap, d)).astype(dtype)
    vc = jax.random.normal(keys[2], (b, kvh, cap, d)).astype(dtype)
    rng = np.random.default_rng(0)
    lists = jnp.asarray(np.stack([[rng.permutation(8)[:width] for _ in range(kvh)] for _ in range(b)]))
    lists = lists.at[1].set(jnp.arange(width))
    counts = jnp.array([[6, 4], [2, 2], [5, 6]])
    lengths = jnp.array([101, 29, 128])
    rows = dispatch.decode_live_rows(jnp.array([True, True, False]), backend="pallas_interpret")
    want, lse_want = dispatch.cache_attention(
        q, kc, vc, lengths, None, backend="xla", blocks=(lists, counts, size))
    got, lse_got = dispatch.cache_attention(
        q, kc, vc, lengths, rows, backend="pallas_interpret", blocks=(lists, counts, size))
    np.testing.assert_allclose(got[:2], want[:2], atol=2e-6)
    np.testing.assert_allclose(lse_got[:2], lse_want[:2], atol=2e-6)
    assert float(jnp.abs(got[2]).max()) == 0.0 and float(lse_got[2].max()) < -9e29
    # every block listed = plain attention over the first ``lengths`` rows
    everything = jnp.broadcast_to(jnp.arange(8), (b, kvh, 8))
    full, _ = dispatch.cache_attention(
        q, kc, vc, lengths, None, backend="xla",
        blocks=(everything, jnp.full((b, kvh), 8), size))
    plain, _ = dispatch.cache_attention(
        jnp.repeat(q.reshape(b, kvh, g, d), 1, axis=1).reshape(b, kvh * g, d),
        jnp.repeat(kc, g, axis=1), jnp.repeat(vc, g, axis=1), lengths, None, backend="xla")
    np.testing.assert_allclose(full, plain, atol=2e-5)


def test_blocks_read_is_the_list_the_step_builds():
    cfg = tiny_cfg(CASE)
    assert [blocks_read(cfg, n) for n in (1, 16, 17, 64, 65, 200)] == [1, 1, 2, 4, 4, 4]
    big = get_config("minicpm_sala")
    assert blocks_read(big, 8192) == 128 and blocks_read(big, 8193) == 64


