"""The served gated grouped-attention mixture of experts (ISSUE 51): window
layers that hold a RING and rotate q and k beside a full layer that holds a
growing cache and carries no position term, per-head q / k norms, a sigmoid
output gate, and a sigmoid top-k mixture whose router has a per-expert
selection bias beside a shared expert, every expert held; against
``benchmark/reference/plain_afmoe.py``; tiny, CPU, fp32.

No depth-share test is needed: every expert and the whole vocabulary are
held, so there is no share whose parts would have to add up."""

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
from orion_tpu.models.moe import MoEMLP, masks_rows, top_k_choice
from orion_tpu.models.transformer import TransformerLM, init_decode_state
from orion_tpu.ops import dispatch
from orion_tpu.serving import DecodeRequest, SlotEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from reference import plain_afmoe as ref  # noqa: E402

# window 8: prompts of one, two and three windows wrap the ring. Three layers
# hold every kind: a dense window layer, an expert window layer, an expert
# full layer
TINY = dict(n_layers=3, layer_types=("swa", "swa", "softmax"), vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, window=8,
            mlp_hidden=128, moe_hidden=32, moe_shared_hidden=32, n_experts=8, moe_top_k=2,
            moe_route_bias=0.5, max_seq_len=64, dtype="float32", param_dtype="float32")
T = 29
LOGIT_TOL = 5e-5  # fp32 against fp32 on logits of ~4: summation order only
GREEDY = SampleConfig(temperature=0.0)


def tiny_cfg(backend="xla", **over):
    return dataclasses.replace(get_config("trinity_mini"), backend=backend, **{**TINY, **over})


def spec_of(cfg, **over):
    return {**dict(
        layer_types=cfg.resolved_layer_types, rotary_layers=cfg.rotary_layers,
        window_layers="swa",
        window=cfg.window, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, rope_base=cfg.rotary_base, norm_eps=cfg.norm_eps,
        embed_scale=cfg.embed_scale, top_k=cfg.moe_top_k, route_scale=cfg.moe_route_scale,
        query_tile=16), **over}


@pytest.fixture(scope="module")
def model_params():
    cfg = tiny_cfg()
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, T), 0, cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.key(0), toks[:, :16])
    # norm weights off 1, so that a norm left out shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.3 * jax.random.normal(jax.random.key(len(str(path))), x.shape)
        if "scale" in str(path) else x, params)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(spec_of(cfg), params, toks)
        got = model.apply(params, toks)
    yield cfg, params, toks, want, got
    jax.clear_caches()  # ROADMAP C13: a worker's compiled programs map memory


def test_preset_is_the_published_shape():
    cfg = get_config("trinity_mini")
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (2048, 32, 4, 128)
    assert cfg.resolved_layer_types == ("swa",) * 4 + ("softmax",) and cfg.window == 2048
    assert cfg.rotary_layers == "swa" and cfg.attn_gate and cfg.qk_norm == "head"
    assert (cfg.n_experts, cfg.moe_top_k, cfg.moe_hidden, cfg.moe_shared_hidden) == (128, 8, 1024, 1024)
    assert (cfg.moe_score, cfg.moe_route_scale, cfg.moe_first_dense) == ("sigmoid", 2.826, 1)
    assert cfg.moe_route_bias > 0 and not cfg.moe_shared_gated and cfg.mlp_hidden == 6144
    assert not cfg.moe_held and masks_rows(cfg) and cfg.resolved_router_width == 128
    assert [cfg.moe_at(i) for i in range(5)] == [False, True, True, True, True]
    assert (cfg.vocab_size, cfg.tie_embeddings, cfg.max_seq_len) == (200192, False, 16384 + 1024)
    assert cfg.embed_scale == pytest.approx(2048 ** 0.5) and cfg.norm_placement == "sandwich"
    shapes = jax.eval_shape(lambda: init_decode_state(cfg, 2))
    assert [s["k"].shape for s in shapes] == [(2, 4, 2048, 128)] * 4 + [(2, 4, 17408, 128)]
    tree = jax.eval_shape(
        lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    count = lambda t: sum(x.size for x in jax.tree.leaves(t))  # noqa: E731
    assert count(tree["params"]["block_0"]) == 65_020_160
    assert count(tree["params"]["block_4"]) == 839_131_520
    assert count(tree) == 4_241_534_720
    blk = tree["params"]["block_1"]
    assert blk["attn"]["wg"]["kernel"].shape == (2048, 4096)
    assert blk["mlp"]["router_bias"].shape == (128,) and blk["mlp"]["router_bias"].dtype == jnp.float32
    # the new fields are no part of any other preset's programs
    others = [get_config(n) for n in ("lm_1b3", "hybrid_1b3", "olmo_hybrid_7b", "granite_4_0_h_micro",
                                      "openpangu_ultra_moe_718b", "keye_vl_2_0_30b_a3b", "qwen3_next_80b")]
    assert all(not c.attn_gate and c.rotary_layers is None and not c.moe_route_bias for c in others)


def test_model_matches_the_reference(model_params):
    """Logits of the whole forward through every layer kind, T more than
    three windows; and the window bites: a reference with a window that
    never closes reads differently."""
    cfg, params, toks, want, got = model_params
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL)
    with jax.default_matmul_precision("highest"):
        wide = ref.forward(spec_of(cfg, window=10 ** 6), params, toks)
    assert float(jnp.abs(wide - want).max()) > 100 * LOGIT_TOL


@pytest.mark.parametrize("patch", [
    "no gate", "a gate of 1/2", "no q norm", "no k norm", "rotary on the full layer",
    "no rotary on the window layers", "a window one wider", "the bias in the weights",
    "no bias", "no normalisation over the chosen", "the shared expert twice", "no embed scale"])
def test_the_comparison_sees(model_params, monkeypatch, patch):
    """The tolerance is tight enough to tell the model from a reference that
    differs in one of the mechanisms (a changed constant fails it)."""
    cfg, params, toks, want, got = model_params
    spec = spec_of(cfg)
    if patch == "no gate":
        monkeypatch.setattr(ref, "gate_activation", jnp.ones_like)
    elif patch == "a gate of 1/2":
        monkeypatch.setattr(ref, "gate_activation", lambda x: 0.5 * jnp.ones_like(x))
    elif patch in ("no q norm", "no k norm"):
        plain, heads = ref.rms, cfg.n_heads if patch == "no q norm" else cfg.n_kv_heads
        monkeypatch.setattr(ref, "rms", lambda spec, x, w: x if x.ndim == 4 and x.shape[1] == heads
                            else plain(spec, x, w))
    elif patch == "rotary on the full layer":
        spec = {**spec, "rotary_layers": "swa,softmax"}
    elif patch == "no rotary on the window layers":
        monkeypatch.setattr(ref, "rope", lambda x, base: x)
    elif patch == "a window one wider":
        spec = {**spec, "window": cfg.window + 1}
    elif patch == "the bias in the weights":
        def biased(spec, p, x):
            scores = jax.nn.sigmoid(x @ jnp.asarray(p["router"]["kernel"], jnp.float32)) + p["router_bias"]
            top, ids = jax.lax.top_k(scores, spec["top_k"])
            top = spec["route_scale"] * top / (top.sum(-1, keepdims=True) + 1e-20)
            return jnp.einsum("nk,nke->ne", top, jax.nn.one_hot(ids, scores.shape[-1]))
        monkeypatch.setattr(ref, "routing_weights", biased)
    elif patch == "no bias":
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.zeros_like(x) if "router_bias" in str(path) else x, params)
    elif patch == "no normalisation over the chosen":
        def unnormed(spec, p, x):
            scores = jax.nn.sigmoid(x @ jnp.asarray(p["router"]["kernel"], jnp.float32))
            _, ids = jax.lax.top_k(scores + p["router_bias"], spec["top_k"])
            top = spec["route_scale"] * jnp.take_along_axis(scores, ids, axis=-1)
            return jnp.einsum("nk,nke->ne", top, jax.nn.one_hot(ids, scores.shape[-1]))
        monkeypatch.setattr(ref, "routing_weights", unnormed)
    elif patch == "the shared expert twice":
        plain = ref.dense
        monkeypatch.setattr(ref, "dense", lambda spec, p, x, names=("gate", "up", "down"):
                            plain(spec, p, x, names) * (2.0 if names[0].startswith("shared") else 1.0))
    elif patch == "no embed scale":
        spec = {**spec, "embed_scale": 1.0}
    with jax.default_matmul_precision("highest"):
        other = ref.forward(spec, params, toks)
    assert float(jnp.abs(other - got).max()) > 20 * LOGIT_TOL, patch


def test_window_layers_rotate_and_full_layers_do_not(model_params):
    """Moving a prompt's positions (a piece consumed at another offset over
    empty caches: the same tokens, later positions) leaves a full layer's
    output as it was and changes a window layer's not at all EITHER, since
    rotary is relative, unless the offset reaches the queries alone: so the
    two kinds are told apart where it shows, in the keys they CACHE."""
    cfg, params, toks, _, _ = model_params
    x = jax.random.normal(jax.random.key(5), (1, 6, cfg.d_model))
    for lt, moves in (("swa", True), ("softmax", False)):
        mixer = MIXERS[lt](cfg, lt)
        p = {"params": params["params"]["block_2" if lt == "softmax" else "block_1"]["attn"]}
        zero = MIXERS[lt].decode_state(cfg, lt, 1, jnp.float32)
        run = jax.jit(lambda off: mixer.apply(  # noqa: E731
            p, x, zero, off, jnp.int32(6), method="prefill_extend"))
        (out0, st0), (out3, st3) = run(jnp.int32(0)), run(jnp.int32(3))
        cap = st0["k"].shape[2]
        k0 = st0["k"][:, :, jnp.arange(6) % cap]
        k3 = st3["k"][:, :, (3 + jnp.arange(6)) % cap]
        assert bool(jnp.abs(k0 - k3).max() > 1e-3) == moves, lt
        np.testing.assert_allclose(st0["v"][:, :, jnp.arange(6) % cap],
                                   st3["v"][:, :, (3 + jnp.arange(6)) % cap], atol=1e-6)


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_prefill_pieces_and_the_decode_walk_agree_through_a_ring_that_wraps(model_params, backend):
    """Prompts of one, two and three windows and a bit: the monolithic
    prefill, pieces of 5 (which straddle the ring's wrap, the last one
    partial) and the decode walk give the full forward's logits, and the
    pieces leave the walk's state (the ring compared slot for slot)."""
    cfg, params, toks, _, full = model_params
    cfg = dataclasses.replace(cfg, backend=backend)
    model = TransformerLM(cfg)
    extend = jax.jit(lambda piece, st, off, n: model.apply(
        params, piece, st, off, n, method="prefill_extend_step"))
    step = jax.jit(lambda tok, st, t, rows: model.apply(params, tok, st, t, rows, method="decode_step"))
    rows = dispatch.decode_live_rows(jnp.ones((2,), bool), backend=backend)
    walked = init_decode_state(cfg, 2)
    at = {}
    for t in range(T):
        if t in (8, 17, 27):
            at[t] = walked
        out, walked = step(toks[:, t], walked, jnp.full((2,), t), rows)
        np.testing.assert_allclose(out, full[:, t], atol=LOGIT_TOL)
    for n in (8, 17, 27):
        last, st = jax.jit(lambda x: model.apply(params, x, method="prefill_last"))(toks[:, :n])
        np.testing.assert_allclose(last, full[:, n - 1], atol=LOGIT_TOL)
        pieces, off = init_decode_state(cfg, 2), 0
        while off < n:
            real = min(5, n - off)
            piece = jnp.pad(toks[:, off:off + real], ((0, 0), (0, 5 - real)))
            last, pieces = extend(piece, pieces, jnp.int32(off), jnp.int32(real))
            off += real
        np.testing.assert_allclose(last, full[:, n - 1], atol=LOGIT_TOL)
        for kind, a, b, c in zip(cfg.resolved_layer_types, pieces, at[n], st):
            live = min(n, cfg.window) if kind == "swa" else n
            for name in ("k", "v"):
                np.testing.assert_allclose(a[name][:, :, :live], b[name][:, :, :live], atol=2e-5)
                np.testing.assert_allclose(c[name][:, :, :live], b[name][:, :, :live], atol=2e-5)


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_a_group_of_pieces_is_each_piece_alone(model_params, backend):
    """``prefill_extend_group``: three sequences at three offsets (one of them
    mid-ring, its piece partial) and an empty place; each sequence's logits
    and state are what its own ``prefill_extend_step`` gives, and the empty
    place's state keeps its bits."""
    cfg, params, toks, _, _ = model_params
    cfg = dataclasses.replace(cfg, backend=backend)
    model = TransformerLM(cfg)
    extend = jax.jit(lambda piece, st, off, n: model.apply(
        params, piece, st, off, n, method="prefill_extend_step"))
    group = jax.jit(lambda piece, st, off, n: model.apply(
        params, piece, st, off, n, method="prefill_extend_group"))
    row = lambda st, i: jax.tree.map(lambda x: x[i:i + 1], st)  # noqa: E731
    seqs = jnp.stack([toks[0], toks[1], toks[0, ::-1], toks[1, ::-1]])
    before = init_decode_state(cfg, 4)
    for i, n in enumerate((0, 5, 15, 10)):  # what each sequence has consumed
        for off in range(0, n, 5):
            _, st = extend(seqs[i:i + 1, off:off + 5], row(before, i), jnp.int32(off), jnp.int32(5))
            before = jax.tree.map(lambda x, new: x.at[i].set(new[0]), before, st)
    offsets, lengths = jnp.array([0, 5, 15, 10]), jnp.array([5, 5, 3, 0])
    pieces = jnp.stack([seqs[i, o:o + 5] for i, o in enumerate((0, 5, 15, 10))])
    logits, after = group(pieces, [row(before, i) for i in range(4)], offsets, lengths)
    for i in range(3):
        want, st = extend(pieces[i:i + 1], row(before, i), offsets[i], lengths[i])
        np.testing.assert_allclose(logits[i], want[0], atol=LOGIT_TOL)
        for a, b in zip(jax.tree.leaves(after[i]), jax.tree.leaves(st)):
            np.testing.assert_allclose(a, b, atol=2e-5)
    for a, b in zip(jax.tree.leaves(after[3]), jax.tree.leaves(row(before, 3))):
        np.testing.assert_array_equal(a, b)


def test_the_rings_kernels_give_the_xla_forms(model_params):
    """One window layer, a ring that has wrapped: the piece through
    ``window_piece_attention`` and the step through ``window_step_attention``
    (interpreted) against XLA's masked forms, outputs and ring alike; and the
    step of a sequence the row list leaves out keeps every bit of its ring."""
    cfg, params, toks, _, _ = model_params
    p = {"params": params["params"]["block_1"]["attn"]}
    x = jax.random.normal(jax.random.key(7), (3, 21, cfg.d_model))
    outs = {}
    for backend in ("xla", "pallas_interpret"):
        c = dataclasses.replace(cfg, backend=backend)
        mixer = MIXERS["swa"](c, "swa")
        st = MIXERS["swa"].decode_state(c, "swa", 3, jnp.float32)
        got = []
        extend = jax.jit(lambda piece, st, off: mixer.apply(
            p, piece, st, off, jnp.int32(7), method="prefill_extend"))
        for off in (0, 7, 14):  # pieces of 7 over a window of 8: every one straddles
            o, st = extend(x[:, off:off + 7], st, jnp.int32(off))
            got.append(o)
        live = jnp.array([True, False, True])
        rows = dispatch.decode_live_rows(live, backend=backend)
        o, new = jax.jit(lambda tok, st, rows: mixer.apply(
            p, tok, st, jnp.full((3,), 21), rows, method="decode_step"))(x[:, 0], st, rows)
        outs[backend] = (jnp.concatenate(got, 1), st, o, new)
        if rows is not None:
            assert bool((new["k"][1] == st["k"][1]).all() and (new["v"][1] == st["v"][1]).all())
            assert not bool((new["k"][0] == st["k"][0]).all())
    (pa, sa, oa, na), (pb, sb, ob, nb) = outs["xla"], outs["pallas_interpret"]
    np.testing.assert_allclose(pa, pb, atol=2e-5)
    np.testing.assert_allclose(oa[jnp.array([0, 2])], ob[jnp.array([0, 2])], atol=2e-5)
    for name in ("k", "v"):
        np.testing.assert_array_equal(sa[name], sb[name])
        np.testing.assert_array_equal(na[name][jnp.array([0, 2])], nb[name][jnp.array([0, 2])])


def test_the_bias_moves_the_choice_and_not_the_weights():
    """``top_k_choice`` under ``select``: the chosen set is the top-k of the
    scores plus the bias, the gates are the chosen experts' scores without
    it, renormalised; and a zero bias gives the router as it was."""
    s = jax.nn.sigmoid(jax.random.normal(jax.random.key(3), (64, 16)))
    b = 0.5 * jax.random.normal(jax.random.key(4), (16,))
    ids0, g0 = top_k_choice(s, 4)
    ids, g = top_k_choice(s, 4, s + b)
    assert bool((jnp.sort(ids, 1) != jnp.sort(ids0, 1)).any())
    want = jax.lax.top_k(s + b, 4)[1]
    np.testing.assert_array_equal(jnp.sort(ids, 1), jnp.sort(want, 1))
    picked = jnp.take_along_axis(s, ids, axis=1)
    np.testing.assert_allclose(g, picked / picked.sum(1, keepdims=True), rtol=1e-6)
    same_ids, same_g = top_k_choice(s, 4, s + 0.0)
    np.testing.assert_array_equal(same_ids, ids0)
    np.testing.assert_array_equal(same_g, g0)
    # and the layer: its bias zeroed, it is the sigmoid router PR 43 built
    cfg = tiny_cfg()
    x = jax.random.normal(jax.random.key(2), (1, 24, cfg.d_model))
    layer, plain = MoEMLP(cfg), MoEMLP(dataclasses.replace(cfg, moe_route_bias=0.0))
    params = jax.jit(layer.init)(jax.random.key(0), x)
    assert float(jnp.abs(params["params"]["router_bias"]).max()) > 0
    bare = {"params": {k: v for k, v in params["params"].items() if k != "router_bias"}}
    with_bias, without = jax.jit(layer.apply), jax.jit(plain.apply)
    assert float(jnp.abs(with_bias(params, x) - without(bare, x)).max()) > 1e-3
    zeroed = {"params": {**params["params"], "router_bias": jnp.zeros((8,))}}
    np.testing.assert_array_equal(with_bias(zeroed, x), without(bare, x))
    grads = jax.jit(jax.grad(lambda p: layer.apply(p, x).sum()))(params)
    assert not bool(jnp.any(grads["params"]["router_bias"]))  # a buffer: no gradient reaches it


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_an_adversarial_router_drops_nothing(backend):
    """A bias that sends every token to the same two experts: the buffer
    holds every pair, so none drops, and the rows outside ``live`` count
    nowhere."""
    cfg = tiny_cfg(backend)
    x = jax.random.normal(jax.random.key(2), (1, 24, cfg.d_model))
    layer = MoEMLP(cfg)
    params = jax.jit(layer.init)(jax.random.key(0), x)
    bias = jnp.zeros((8,)).at[jnp.array([2, 5])].set(10.0)
    params = {"params": {**params["params"], "router_bias": bias}}
    live = jnp.arange(24)[None, :] < 17
    y, sown = jax.jit(lambda p, x, live: layer.apply(p, x, live, mutable=["moe_stats"]))(params, x, live)
    stats = {k: int(v[0]) for k, v in sown["moe_stats"].items()}
    assert stats["rows_routed"] == 17 * 2 == stats["rows_held"]
    assert stats["dropless_overflow"] == 0 and stats["rows_max_expert"] == 17
    with jax.default_matmul_precision("highest"):
        want = ref.mlp(spec_of(cfg), params["params"], x)
    np.testing.assert_allclose(y[0, :17], want[0, :17], atol=5e-5)


def serve(cfg, params, prompts, max_new, donate):
    engine = SlotEngine(TransformerLM(cfg), params, slots=4, chunk=4,
                        prefill_buckets=(8, 16, 32), prefill_chunk=8)
    engine.donate_carry = donate
    for i, p in enumerate(prompts):
        engine.admit(DecodeRequest(prompt=p, max_new_tokens=max_new, sample=GREEDY, seed=i), tag=i)
    done, seen = {}, []
    while engine.busy:
        ends = engine._slot_ends()
        consumed = {i: end - engine._slots[i].prompt_remaining for i, end in ends.items()}
        emitting = engine._emitting_ends(ends)
        seen.append((engine.ring_rows(), engine.kv_rows(), consumed, emitting))
        for tag, res in engine.step():
            assert res.status == "ok", res.status
            done[tag] = np.asarray(res.tokens).reshape(-1)
    return [done[i] for i in range(len(prompts))], seen, engine


@pytest.mark.parametrize("backend,donate,group", [
    ("xla", False, 4), ("pallas_interpret", False, 4), ("pallas_interpret", True, 4),
    ("xla", True, 2), ("xla", True, 1)])
def test_engine_serves_as_generate(model_params, backend, donate, group):
    """Through ``SlotEngine``: three requests under, at and past the window
    resident together, pieces and decode interleaved; each request's ids are
    ``generate()``'s for it alone, and the ring's and the growing cache's row
    counters add up by hand from the positions. With the carry donated the
    scan holds the growing cache and carries the rings (``chunk_split``), and
    a boundary's pieces run ``prefill_group`` to a program: 4 (one program,
    a place left empty), 2 (two programs) or 1 (a program a piece)."""
    cfg, params, toks, _, _ = model_params
    cfg = dataclasses.replace(cfg, backend=backend, prefill_group=group)
    # piece alignment: the engine rounds prefill_chunk up to the linear chunk
    prompts = [np.asarray(toks[0, :5]), np.asarray(toks[1, :8]), np.asarray(toks[0, 3:29])]
    together, seen, engine = serve(cfg, params, prompts, 9, donate)
    xla = dataclasses.replace(cfg, backend="xla")
    for p, ids in zip(prompts, together):
        alone = generate(TransformerLM(xla), params, jnp.asarray(p)[None], 9, GREEDY)
        np.testing.assert_array_equal(ids, np.asarray(alone)[0, -9:])
    w, chunk = cfg.window, engine.chunk
    for (ring_live, ring_res, ring_att), (kv_live, kv_res, _), consumed, emitting in seen:
        assert ring_res == 4 * w and kv_res == 4 * cfg.max_seq_len
        assert ring_live == sum(min(n, w) for n in consumed.values())
        assert kv_live == sum(consumed.values())
        assert ring_att == sum(min(w, end + j) for end in emitting for j in range(1, chunk + 1))
    assert any(live < res for (live, res, _), *_ in seen)  # a ring that has not filled
    assert max(att for (_, _, att), *_ in seen) > 0
    split = [MIXERS[lt].chunk_split(cfg, lt, st, chunk, jnp.zeros((4,), jnp.int32), True)
             for lt, st in zip(cfg.resolved_layer_types, engine._carry[1])]
    assert [set(h) for h, _ in split] == [set()] * 2 + [{"k", "v"}]  # rings carried, the cache held
    ring = 2 * 2 * w * 16 * 4  # K + V, 2 KV heads, fp32
    assert engine.held_bytes["ring_bytes"] == 4 * 2 * ring
    assert engine.held_bytes["kv_bytes"] == 4 * (2 * ring + 2 * 2 * cfg.max_seq_len * 16 * 4)


def test_the_pieces_roofline_counts_the_tiles_the_kernel_visits():
    """``benchmark/readers/window_roofline.py::pairs_multiplied`` (kept with
    the benchmark, which imports nothing of the program) against the kernel's
    own tiling and band: the key tiles ``key_tiles`` gives each query tile of
    the cell's piece, 2,048 rows over a ring of 2,048 and its own."""
    from orion_tpu.ops.pallas.piece_attention import key_tiles, tiles
    from readers import window_roofline

    for p, w in ((2048, 2048), (1024, 2048), (512, 1024)):
        tq, tk = tiles(p, w + p)
        visited = 0
        for i in range(p // tq):
            first, last = key_tiles(jnp.int32(i), jnp.int32(w), tq=tq, tk=tk, nk=(w + p) // tk, window=w)
            visited += (int(last) - int(first) + 1) * tq * tk
        assert visited == window_roofline.pairs_multiplied(p, w, tq, tk)
        assert p * w <= visited <= p * (w + p)  # what a piece needs <= what it multiplies <= all
    assert tiles(2048, 4096) == (128, 512)  # the metric file's tile_q, tile_k


def test_the_classes_say_what_a_slot_holds():
    cfg = get_config("trinity_mini")
    swa, full = MIXERS["swa"], MIXERS["softmax"]
    assert swa.cache_is_ring(cfg, "swa") and not full.cache_is_ring(cfg, "softmax")
    assert swa.cache_rows(cfg, "swa") == 2048 and full.cache_rows(cfg, "softmax") == 17408
    # a step of a 1,024-token prompt reads its ring's live blocks, not 2,048 rows
    assert swa.cache_rows_read(cfg, "swa", 1024) == 1024
    assert swa.cache_rows_read(cfg, "swa", 1025) == 1280
    assert swa.cache_rows_read(cfg, "swa", 9000) == 2048
    assert full.cache_rows_read(cfg, "softmax", 9000) == 9216
    assert not any(MIXERS[lt].cache_is_ring(get_config("keye_vl_2_0_30b_a3b"), lt)
                   for lt in ("indexed", "latent", "block_sparse", "linear", "ssm"))


# -- what the other served presets trace is the parent's ---------------------------

# sha256 (first 16 hex) of the jaxpr text of three programs of three presets at
# tiny widths (the training forward, a prompt piece, a decode step handed
# ``live``), read on the PARENT of PR 51 (ec19850) and equal on its tree: with
# ``attn_gate``, ``rotary_layers`` and ``moe_route_bias`` absent nothing new is
# traced. (``qwen3_next_80b``'s train forward is pinned in
# tests/test_openpangu_moe.py; ``olmo_hybrid_7b``, ``lm_1b3`` and
# ``hybrid_1b3`` in tests/test_granite_hybrid.py, where ``hybrid_1b3``'s piece
# changed with this PR and says why.) A PR that changes one of these programs
# on purpose reads the new value from the assertion and replaces it here.
# PR 56 did, for the two mixtures' piece and step: a served held layer now sows
# ``tiles_live`` and ``experts_live`` (two scalar sums of the counts it had,
# seven equations a layer; the diff of the jaxpr text against the parent's
# holds nothing else), and at these widths no call takes the whole-width block.
# PR 58 did, for ``openpangu_ultra_moe_718b``'s TRAINING forward alone (no cell
# runs it): a held layer that is not served sows ``tiles_live`` too, the row
# tiles its grouped product visits (seven equations a layer: the in-budget
# counts and the sum of their tiles; the diff holds nothing else). The pieces
# and steps, which the cells run, are the parent's.
_PRESETS = {
    "openpangu_ultra_moe_718b": dict(
        vocab_size=256, d_model=64, n_layers=3, layer_types=("latent",) * 3, n_heads=4, head_dim=24,
        latent_q_rank=32, latent_kv_rank=16, latent_nope_dim=16, latent_rope_dim=8,
        latent_value_dim=16, mlp_hidden=128, moe_hidden=32, moe_shared_hidden=32, n_experts=4,
        moe_router_width=16, moe_top_k=4, moe_ep_buffer=4.0, max_seq_len=256, dtype="float32",
        param_dtype="float32"),
    "keye_vl_2_0_30b_a3b": dict(
        vocab_size=256, d_model=64, n_layers=2, layer_types=("indexed",) * 2, n_heads=4,
        n_kv_heads=2, head_dim=16, index_heads=4, index_dim=8, index_topk=24, moe_hidden=32,
        n_experts=8, moe_top_k=2, max_seq_len=256, dtype="float32", param_dtype="float32"),
    "granite_4_0_h_micro": dict(
        vocab_size=256, d_model=64, n_layers=4, layer_types=("ssm", "softmax", "ssm", "ssm"),
        n_heads=4, n_kv_heads=2, head_dim=16, attn_scale=0.0625, ssm_heads=8, ssm_head_dim=16,
        ssm_state=16, mlp_hidden=128, max_seq_len=256, dtype="float32", param_dtype="float32"),
}
_TRACED = {
    "openpangu_ultra_moe_718b.forward": "105f7bf64f3cf720",
    "openpangu_ultra_moe_718b.piece": "787bb6ca7e979b3e",
    "openpangu_ultra_moe_718b.step": "9f9a9594f33b461f",
    "keye_vl_2_0_30b_a3b.forward": "a731e1d746341ad8",
    "keye_vl_2_0_30b_a3b.piece": "40b4fe50520885e3",
    "keye_vl_2_0_30b_a3b.step": "8c89c16d05a18f1b",
    "granite_4_0_h_micro.forward": "d3b8ef058abe58e9",
    "granite_4_0_h_micro.piece": "72ba2232c8e4fee1",
    "granite_4_0_h_micro.step": "26b3f5ecbf5d81f0",
}


@pytest.mark.parametrize("which", sorted(_TRACED))
def test_other_served_presets_trace_the_parents_programs(which):
    import hashlib

    preset, program = which.split(".")
    cfg = dataclasses.replace(get_config(preset), **_PRESETS[preset])
    model = TransformerLM(cfg)
    toks = jnp.zeros((2, 48), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0), toks))
    states = jax.eval_shape(lambda: init_decode_state(cfg, 2, jnp.float32))
    live = jnp.ones((2,), bool)
    traced = {
        "forward": lambda: jax.make_jaxpr(lambda p, x: model.apply(
            p, x, mutable=["losses", "moe_stats"]))(params, toks),
        "piece": lambda: jax.make_jaxpr(lambda p, x, st: model.apply(
            p, x[:, :16], st, jnp.int32(16), jnp.int32(9), method=model.prefill_extend_step,
            mutable=["moe_stats"]))(params, toks, states),
        "step": lambda: jax.make_jaxpr(lambda p, x, st: model.apply(
            p, x[:, 0], st, jnp.full((2,), 5, jnp.int32), None, live, method=model.decode_step,
            mutable=["moe_stats"]))(params, toks, states),
    }[program]()
    assert hashlib.sha256(str(traced).encode()).hexdigest()[:16] == _TRACED[which]


def test_cell_rehearses_on_the_cpu(tmp_path):
    """``trinity_mini.serve_mixed`` end to end at tiny sizes: the served kind,
    the reference named by the configuration's file, the check on what was
    served in the window, the new counters' metric."""
    import json
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "trinity_mini.serve_mixed",
         "--seed", str(2 ** 31 + 51), "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    assert 0 < line["metrics"]["ring_live_share.mixed"]["value"] <= 100
    assert line["metrics"]["moe_rows_dropped.batch"]["value"] == 0
