"""The served decayed-linear / block-sparse hybrid (ISSUE 37): decayed linear
attention with no normaliser (rotary, per-head q/k norm, output norm and
gate) x3 : block-sparse attention over a grouped KV cache (no rotary, a
learned block selection past ``sparse_dense_len``, an output gate) x1, with
a scaled embedding, scaled residual branches and scaled logits, against
``benchmark/reference/plain_minicpm_sala.py``; tiny, CPU, fp32."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.generate import SampleConfig, generate
from orion_tpu.models.configs import get_config
from orion_tpu.models.mixers import MIXERS
from orion_tpu.models.mixers.block_sparse import blocks_read, list_width
from orion_tpu.models.transformer import TransformerLM, init_decode_state
from orion_tpu.ops import dispatch
from orion_tpu.ops.linear_attention import (
    causal_dot_product_chunked, decay_slopes, decayed_causal_dot_eager,
    recurrent_step,
)
from orion_tpu.serving import DecodeRequest, ServeConfig, Server, SlotEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from reference import plain_minicpm_sala as ref  # noqa: E402

# the selector scaled down 4x: blocks of 16 tokens, pooled keys of 8 every 4,
# top-4 blocks (1 initial + 2 local + 1 chosen) past 64 positions; T = 203 is
# 13 blocks, three times ``topk``
TINY = dict(vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
            mlp_hidden=128, max_seq_len=256, dtype="float32", param_dtype="float32",
            sparse_kernel=8, sparse_stride=4, sparse_block=16, sparse_window=32,
            sparse_topk=4, sparse_dense_len=64, chunk=16)
T = 203
# fp32 against fp32 on logits of ~0.3: summation order only
LOGIT_TOL = 2e-5
GREEDY = SampleConfig(temperature=0.0)


def tiny_cfg(backend="xla", **over):
    return dataclasses.replace(get_config("minicpm_sala"), backend=backend, **{**TINY, **over})


def spec_of(cfg, **over):
    return {**dict(
        layer_types=cfg.resolved_layer_types, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, embed_scale=cfg.embed_scale, residual_scale=cfg.residual_scale,
        logit_scale=cfg.logit_scale, decay_exponent=cfg.decay_exponent, rope_base=cfg.rotary_base,
        kernel=cfg.sparse_kernel, stride=cfg.sparse_stride, block=cfg.sparse_block,
        init_blocks=cfg.sparse_init_blocks, window=cfg.sparse_window, topk=cfg.sparse_topk,
        dense_len=cfg.sparse_dense_len, query_tile=64), **over}


@pytest.fixture(scope="module")
def model_params():
    cfg = tiny_cfg()
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, T), 0, cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.key(0), toks[:, :16])
    # norm weights off 1, so that a norm left out or misplaced shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.3 * jax.random.normal(jax.random.key(len(str(path))), x.shape)
        if "scale" in str(path) else x, params)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(spec_of(cfg), params, toks)
        got = model.apply(params, toks)
    return cfg, params, toks, want, got


def test_preset_is_the_published_shape():
    cfg = get_config("minicpm_sala")
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


def test_model_matches_the_reference(model_params):
    """Logits of the whole forward, T past ``dense_len`` and three times
    more blocks than ``topk``; and the selection bites: the reference with
    the switch never taken reads differently."""
    cfg, params, toks, want, got = model_params
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL)
    with jax.default_matmul_precision("highest"):
        dense = ref.forward(spec_of(cfg, dense_len=10 ** 6), params, toks)
    assert float(jnp.abs(dense - want).max()) > 100 * LOGIT_TOL


@pytest.mark.parametrize("what,over", [
    ("no decay", {"decay_exponent": 60.0}),
    ("an unscaled residual", {"residual_scale": 1.0}),
    ("an unscaled embedding", {"embed_scale": 1.0}),
    ("unscaled logits", {"logit_scale": 1.0}),
    ("unforced local blocks", {"window": 16}),
    ("no initial block", {"init_blocks": 0}),
    ("another topk", {"topk": 5}),
])
def test_the_comparison_sees(model_params, what, over):
    """The tolerance is tight enough to tell the model from a reference
    that differs in one of the mechanisms."""
    cfg, params, toks, want, got = model_params
    with jax.default_matmul_precision("highest"):
        other = ref.forward({**spec_of(cfg), **over}, params, toks)
    assert float(jnp.abs(other - got).max()) > 20 * LOGIT_TOL, what


@pytest.mark.parametrize("patch", ["a normaliser", "rotary on the sparse layer",
                                   "a selection per head"])
def test_the_comparison_sees_a_changed_layer(model_params, monkeypatch, patch):
    cfg, params, toks, want, got = model_params
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
    with jax.default_matmul_precision("highest"):
        other = ref.forward(spec_of(cfg), params, toks)
    assert float(jnp.abs(other - got).max()) > 20 * LOGIT_TOL, patch


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_prefill_equals_pieces_equals_the_decode_walk(model_params, backend):
    """``prefill`` = pieces of ``prefill_extend`` (a padded last piece) =
    ``decode_step`` token by token, for both mixers: every state leaf and
    the logits. XLA: pieces at chunk multiples replay prefill's operations
    (exact); the walk and the kernels agree to fp32 summation order."""
    cfg, params, toks, _, full = model_params
    cfg = dataclasses.replace(cfg, backend=backend)
    model = TransformerLM(cfg)
    n = 150
    logits, states = model.apply(params, toks[:, :n], method="prefill")
    np.testing.assert_allclose(logits, full[:, :n], atol=LOGIT_TOL)
    # pieces of 48 (three chunks), the last one 6 real rows of 48
    st, off = init_decode_state(cfg, 2), 0
    for real in (48, 48, 48, 6):
        piece = jnp.pad(toks[:, off:off + real], ((0, 0), (0, 48 - real)))
        last, st = model.apply(params, piece, st, jnp.int32(off), jnp.int32(real),
                               method="prefill_extend_step")
        off += real
    np.testing.assert_allclose(last, full[:, n - 1], atol=LOGIT_TOL)
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(states)):
        np.testing.assert_allclose(a, b, **(dict(atol=2e-5, rtol=2e-5) if backend != "xla" else dict(atol=0, rtol=0)))
    # the walk: from the prompt's state, then from nothing
    rows = dispatch.decode_live_rows(jnp.ones((2,), bool), backend=backend)
    for t in range(n, T):
        out, states = model.apply(params, toks[:, t], states, jnp.full((2,), t), rows,
                                  method="decode_step")
        np.testing.assert_allclose(out, full[:, t], atol=LOGIT_TOL)
    st = init_decode_state(cfg, 2)
    for t in range(80):
        out, st = model.apply(params, toks[:, t], st, jnp.int32(t), method="decode_step")
    np.testing.assert_allclose(out, full[:, 79], atol=LOGIT_TOL)
    _, want = model.apply(params, toks[:, :80], method="prefill")
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)  # S reaches ~20


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_a_scan_that_holds_the_cache_walks_as_one_that_carries_it(model_params, backend):
    """``chunk_split`` / ``chunk_merge``: twelve decode steps past
    ``dense_len`` over K and V held read-only, with the chunk's own rows,
    the positions it started at and the pooled keys carried (three pooled
    keys complete inside the chunk, the first from keys on both sides of
    ``t0``), give the plain walk's logits and, merged, its state; with a
    row list the sequence it leaves out keeps every bit."""
    cfg, params, toks, _, _ = model_params
    cfg = dataclasses.replace(cfg, backend=backend)
    model, kinds = TransformerLM(cfg), cfg.resolved_layer_types
    n, steps = 150, 12
    _, start = model.apply(params, toks[:, :n], method="prefill")
    for mask in ([True, True], [True, False]):
        live = jnp.array(mask)
        rows = dispatch.decode_live_rows(live, backend=backend)
        if rows is None and not all(mask):
            continue  # without a list the decode programs freeze rows themselves
        split = [MIXERS[lt].chunk_split(cfg, lt, st, steps, jnp.full((2,), n), True)
                 for lt, st in zip(kinds, start)]
        held, carried, plain = [h for h, _ in split], [c for _, c in split], start
        assert set(held[-1]) == {"k", "v"} and set(carried[-1]) == {"kn", "vn", "kp", "t0"}
        for t in range(n, n + steps):
            at = jnp.where(live, t, n)  # a sequence that is not emitting holds its position
            want, plain = model.apply(params, toks[:, t], plain, at, rows, method="decode_step")
            out, new = model.apply(params, toks[:, t], [{**h, **c} for h, c in zip(held, carried)],
                                   at, rows, method="decode_step")
            carried = [{name: st[name] for name in c} for st, c in zip(new, carried)]
            np.testing.assert_allclose(out[live], want[live], atol=LOGIT_TOL)
        merged = [MIXERS[lt].chunk_merge(cfg, lt, h, c, live)
                  for lt, h, c in zip(kinds, held, carried)]
        for got, want, old in zip(*(jax.tree.leaves(x) for x in (merged, plain, start))):
            np.testing.assert_allclose(got[live], want[live], atol=2e-5, rtol=2e-5)
            assert bool((got[~live] == old[~live]).all())
        assert not bool((merged[-1]["kp"] == start[-1]["kp"]).all())


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_the_cache_is_split_by_the_program_the_linear_state_by_the_backend(backend):
    """``chunk_split``: K and V are held only where the program's carry is
    donated (the cell's unified program carries them whole, as before PR
    38), whatever the backend; a ``linear`` layer's ``(S, z)`` is held where
    the step takes the row-list kernel, in both kinds of program; the
    decayed ``S`` is written at every step and is never split."""
    cfg = tiny_cfg(backend, n_layers=3, layer_types=("decay_linear", "block_sparse", "linear"))
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


def test_decode_step_with_a_row_list_touches_no_other_row(model_params):
    cfg, params, toks, _, _ = model_params
    model = TransformerLM(dataclasses.replace(cfg, backend="pallas_interpret"))
    _, states = model.apply(params, toks[:, :127], method="prefill")
    states = jax.tree.map(lambda x: jnp.concatenate([x, x[:1] + 1], axis=0), states)  # 3 rows
    rows = dispatch.decode_live_rows(jnp.array([True, False, True]), backend="pallas_interpret")
    _, new = model.apply(params, jnp.array([5, 6, 7]), states, jnp.array([127, 127, 127]), rows,
                         method="decode_step")
    for old, now in zip(jax.tree.leaves(states), jax.tree.leaves(new)):
        assert bool((now[1] == old[1]).all())
        assert not bool((now[0] == old[0]).all())  # position 127 ends a pooled key too


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
    cfg = tiny_cfg()
    assert [blocks_read(cfg, n) for n in (1, 16, 17, 64, 65, 200)] == [1, 1, 2, 4, 4, 4]
    big = get_config("minicpm_sala")
    assert blocks_read(big, 8192) == 128 and blocks_read(big, 8193) == 64


def serve(cfg, params, prompts, max_new, donate=False):
    engine = SlotEngine(TransformerLM(cfg), params, slots=4, chunk=4,
                        prefill_buckets=(64, 128, 256), prefill_chunk=32)
    engine.donate_carry = donate
    for i, p in enumerate(prompts):
        engine.admit(DecodeRequest(prompt=p, max_new_tokens=max_new, sample=GREEDY, seed=i), tag=i)
    done, seen = {}, []
    while engine.busy:
        seen.append(engine.kv_blocks())
        for tag, res in engine.step():
            assert res.status == "ok", res.status
            done[tag] = np.asarray(res.tokens).reshape(-1)
    return [done[i] for i in range(len(prompts))], seen


@pytest.mark.parametrize("backend,donate", [
    ("xla", False), ("pallas_interpret", False), ("xla", True), ("pallas_interpret", True)])
def test_engine_serves_as_generate(model_params, backend, donate):
    """Through ``SlotEngine``: three requests of one, three and six pieces
    resident together, pieces and decode interleaved; each request's ids are
    ``generate()``'s for it alone (XLA; under the kernels the XLA engine's),
    and the block counters follow the positions. With the carry donated the
    scan reads K and V, carries a chunk's own rows and the pooled keys
    (``chunk_split``; a pooled row completes inside a chunk from keys on
    both sides of ``t0``) and gives the same ids."""
    cfg, params, toks, _, _ = model_params
    cfg = dataclasses.replace(cfg, backend=backend)
    prompts = [np.asarray(toks[0, :30]), np.asarray(toks[1, :90]), np.asarray(toks[0, 20:190])]
    together, seen = serve(cfg, params, prompts, 9, donate)
    xla = dataclasses.replace(cfg, backend="xla")
    for p, ids in zip(prompts, together):
        alone = generate(TransformerLM(xla), params, jnp.asarray(p)[None], 9, GREEDY)
        np.testing.assert_array_equal(ids, np.asarray(alone)[0, -9:])
    live, read, sparse, dense = (sum(x) for x in zip(*seen))
    assert dense > 0 and sparse > 0 and read < live
    # the last boundaries: the long request alone, past dense_len
    assert seen[-1][1:] == (cfg.sparse_topk, 1, 0) and seen[-1][0] >= 12


def test_server_answers_as_generate(model_params):
    """The ``Server`` over the tiny preset: 4 slots, five requests, pieces
    and decode interleaved; every answer is ``generate()``'s."""
    cfg, params, toks, _, _ = model_params
    model = TransformerLM(cfg)
    srv = Server(model, params, ServeConfig(chunk=4, slots=4, max_inflight=8, prefill_chunk=32,
                                            prefill_buckets="64,128,256", cost=False))
    prompts = [np.asarray(toks[i % 2, a:b]) for i, (a, b) in
               enumerate([(0, 100), (0, 20), (50, 200), (10, 75), (3, 150)])]
    handles = [srv.submit(DecodeRequest(prompt=p, max_new_tokens=7, sample=GREEDY, seed=i))
               for i, p in enumerate(prompts)]
    srv.serve(drain_when_idle=True)
    counters = srv.metrics.counters_flat()
    srv.close()
    for p, h in zip(prompts, handles):
        assert h.result.status == "ok"
        alone = generate(model, params, jnp.asarray(p)[None], 7, GREEDY)
        np.testing.assert_array_equal(np.asarray(h.result.tokens).reshape(-1), np.asarray(alone)[0, -7:])
    assert counters["sparse_steps"] > 0 and counters["dense_steps"] > 0
    assert 0 < counters["kv_blocks_read"] < counters["kv_blocks_live"]


def test_cell_rehearses_on_the_cpu(tmp_path):
    """``minicpm_sala.serve_long`` end to end at tiny sizes: the served kind,
    the reference named by the configuration's file, the check on what was
    served in the window, the new counters' metric."""
    import json
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "minicpm_sala.serve_long",
         "--seed", str(2 ** 31 + 37), "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    assert 0 < line["metrics"]["kv_block_read_share.long"]["value"] < 100
