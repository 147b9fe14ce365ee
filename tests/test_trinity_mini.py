"""The served gated grouped-attention mixture of experts (ISSUE 51): window
layers that hold a RING and rotate q and k beside a full layer that holds a
growing cache and carries no position term, per-head q / k norms, a sigmoid
output gate, and a sigmoid top-k mixture whose router has a per-expert
selection bias beside a shared expert, every expert held; against
``benchmark/reference/plain_afmoe.py``; tiny, CPU, fp32. The contract every
served configuration takes is ``tests/served_contract.py``'s.

No depth-share test is needed (``share=None``): every expert and the whole
vocabulary are held, so there is no share whose parts would have to add up."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from served_contract import (
    BACKENDS, Because, Cell, ServedCase, ServedContract, other_presets, served_fixture, tiny_cfg,
)

from orion_tpu.models.configs import get_config
from orion_tpu.models.mixers import MIXERS
from orion_tpu.models.moe import MoEMLP, masks_rows, top_k_choice
from orion_tpu.models.transformer import TransformerLM, init_decode_state
from orion_tpu.ops import dispatch

# Three layers hold every kind: a dense window layer, an expert window layer,
# an expert full layer
T = 29
CASE = ServedCase(
    "trinity_mini", seq=T,
    logit_tol=5e-5,  # fp32 against fp32 on logits of ~4: summation order only
    over=dict(
        n_layers=3, layer_types=("swa", "swa", "softmax"), max_seq_len=64,
        window=Because(8, "prompts of one, two and three windows wrap the ring inside T = 29"),
        moe_route_bias=Because(0.5, "large enough that the bias moves a choice")),
    constants=dict(query_tile=16),
    bites=dict(window=10 ** 6),  # a window that never closes
    # the carry donated: a boundary's pieces run ``prefill_group`` to a program:
    # 4 (one program, a place left empty), 2 (two programs) or 1 (a program a piece)
    engines=(("xla", False, {"prefill_group": 4}), ("pallas_interpret", False, {"prefill_group": 4}),
             ("pallas_interpret", True, {"prefill_group": 4}), ("xla", True, {"prefill_group": 2}),
             ("xla", True, {"prefill_group": 1})),
    # piece alignment: the engine rounds prefill_chunk up to the linear chunk
    engine=dict(slots=4, chunk=4, prefill_buckets=(8, 16, 32), prefill_chunk=8),
    prompts=((0, 0, 5), (1, 0, 8), (0, 3, 29)),  # under, at and past the window
    cell=Cell("trinity_mini.serve_mixed", seed=2 ** 31 + 51),
)
served = served_fixture(CASE)


class TestServed(ServedContract):
    case = CASE

    def published(self, cfg):
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
        # the new fields are no part of any other preset's programs; the
        # selection bias is, of the two later mixtures that publish one
        others = other_presets(cfg.name)
        assert all(not c.attn_gate and c.rotary_layers is None for c in others)
        assert {c.name for c in others if c.moe_route_bias} == {"lfm2_8b_a1b", "nemotron_3_super_120b"}

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_prefill_equals_pieces_equals_the_decode_walk(self, served, backend):
        """Through a ring that wraps. Prompts of one, two and three windows
        and a bit: the monolithic prefill, pieces of 5 (which straddle the
        ring's wrap, the last one partial) and the decode walk give the full
        forward's logits, and the pieces leave the walk's state (the ring
        compared slot for slot)."""
        prog, params, toks, full = served.programs(backend), served.params, served.toks, served.got
        cfg, tol = prog.cfg, CASE.logit_tol
        rows = dispatch.decode_live_rows(jnp.ones((2,), bool), backend=backend)
        walked = init_decode_state(cfg, 2)
        at = {}
        for t in range(T):
            if t in (8, 17, 27):
                at[t] = walked
            out, walked = prog.step(params, toks[:, t], walked, jnp.full((2,), t), rows)
            np.testing.assert_allclose(out, full[:, t], atol=tol)
        for n in (8, 17, 27):
            last, st = prog.prefill_last(params, toks[:, :n])
            np.testing.assert_allclose(last, full[:, n - 1], atol=tol)
            pieces, off = init_decode_state(cfg, 2), 0
            while off < n:
                real = min(5, n - off)
                piece = jnp.pad(toks[:, off:off + real], ((0, 0), (0, 5 - real)))
                last, pieces = prog.piece(params, piece, pieces, jnp.int32(off), jnp.int32(real))
                off += real
            np.testing.assert_allclose(last, full[:, n - 1], atol=tol)
            for kind, a, b, c in zip(cfg.resolved_layer_types, pieces, at[n], st):
                live = min(n, cfg.window) if kind == "swa" else n
                for name in ("k", "v"):
                    np.testing.assert_allclose(a[name][:, :, :live], b[name][:, :, :live], atol=2e-5)
                    np.testing.assert_allclose(c[name][:, :, :live], b[name][:, :, :live], atol=2e-5)

    def before_boundary(self, engine):
        ends = engine._slot_ends()
        consumed = {i: end - engine._slots[i].prompt_remaining for i, end in ends.items()}
        return engine.ring_rows(), engine.kv_rows(), consumed, engine._emitting_ends(ends)

    def after_engine(self, served, run, backend, donate):
        """The ring's and the growing cache's row counters add up by hand
        from the positions. With the carry donated the scan holds the
        growing cache and carries the rings (``chunk_split``)."""
        cfg, engine = run.cfg, run.engine
        w, chunk = cfg.window, engine.chunk
        for (ring_live, ring_res, ring_att), (kv_live, kv_res, _), consumed, emitting in run.seen:
            assert ring_res == 4 * w and kv_res == 4 * cfg.max_seq_len
            assert ring_live == sum(min(n, w) for n in consumed.values())
            assert kv_live == sum(consumed.values())
            assert ring_att == sum(min(w, end + j) for end in emitting for j in range(1, chunk + 1))
        assert any(live < res for (live, res, _), *_ in run.seen)  # a ring that has not filled
        assert max(att for (_, _, att), *_ in run.seen) > 0
        split = [MIXERS[lt].chunk_split(cfg, lt, st, chunk, jnp.zeros((4,), jnp.int32), True)
                 for lt, st in zip(cfg.resolved_layer_types, engine._carry[1])]
        assert [set(h) for h, _ in split] == [set()] * 2 + [{"k", "v"}]  # rings carried, the cache held
        ring = 2 * 2 * w * 16 * 4  # K + V, 2 KV heads, fp32
        assert engine.held_bytes["ring_bytes"] == 4 * 2 * ring
        assert engine.held_bytes["kv_bytes"] == 4 * (2 * ring + 2 * 2 * cfg.max_seq_len * 16 * 4)

    def after_cell(self, result, lines):
        assert 0 < result["metrics"]["ring_live_share.mixed"]["value"] <= 100
        assert result["metrics"]["moe_rows_dropped.batch"]["value"] == 0


@pytest.mark.parametrize("patch", [
    "no gate", "a gate of 1/2", "no q norm", "no k norm", "rotary on the full layer",
    "no rotary on the window layers", "a window one wider", "the bias in the weights",
    "no bias", "no normalisation over the chosen", "the shared expert twice", "no embed scale"])
def test_the_comparison_sees(served, monkeypatch, patch):
    """The tolerance is tight enough to tell the model from a reference that
    differs in one of the mechanisms (a changed constant fails it)."""
    ref, cfg, params, spec = served.ref, served.cfg, served.params, served.spec()
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
    served.differs(spec, params)


def test_window_layers_rotate_and_full_layers_do_not(served):
    """Moving a prompt's positions (a piece consumed at another offset over
    empty caches: the same tokens, later positions) leaves a full layer's
    output as it was and changes a window layer's not at all EITHER, since
    rotary is relative, unless the offset reaches the queries alone: so the
    two kinds are told apart where it shows, in the keys they CACHE."""
    cfg, params = served.cfg, served.params
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
def test_a_group_of_pieces_is_each_piece_alone(served, backend):
    """``prefill_extend_group``: three sequences at three offsets (one of them
    mid-ring, its piece partial) and an empty place; each sequence's logits
    and state are what its own ``prefill_extend_step`` gives, and the empty
    place's state keeps its bits."""
    prog, params, toks = served.programs(backend), served.params, served.toks
    cfg = prog.cfg

    def extend(*args):
        return prog.piece(params, *args)

    row = lambda st, i: jax.tree.map(lambda x: x[i:i + 1], st)  # noqa: E731
    seqs = jnp.stack([toks[0], toks[1], toks[0, ::-1], toks[1, ::-1]])
    before = init_decode_state(cfg, 4)
    for i, n in enumerate((0, 5, 15, 10)):  # what each sequence has consumed
        for off in range(0, n, 5):
            _, st = extend(seqs[i:i + 1, off:off + 5], row(before, i), jnp.int32(off), jnp.int32(5))
            before = jax.tree.map(lambda x, new: x.at[i].set(new[0]), before, st)
    offsets, lengths = jnp.array([0, 5, 15, 10]), jnp.array([5, 5, 3, 0])
    pieces = jnp.stack([seqs[i, o:o + 5] for i, o in enumerate((0, 5, 15, 10))])
    logits, after = prog.group(params, pieces, [row(before, i) for i in range(4)], offsets, lengths)
    for i in range(3):
        want, st = extend(pieces[i:i + 1], row(before, i), offsets[i], lengths[i])
        np.testing.assert_allclose(logits[i], want[0], atol=CASE.logit_tol)
        for a, b in zip(jax.tree.leaves(after[i]), jax.tree.leaves(st)):
            np.testing.assert_allclose(a, b, atol=2e-5)
    for a, b in zip(jax.tree.leaves(after[3]), jax.tree.leaves(row(before, 3))):
        np.testing.assert_array_equal(a, b)


def test_the_rings_kernels_give_the_xla_forms(served):
    """One window layer, a ring that has wrapped: the piece through
    ``window_piece_attention`` and the step through ``window_step_attention``
    (interpreted) against XLA's masked forms, outputs and ring alike; and the
    step of a sequence the row list leaves out keeps every bit of its ring."""
    cfg, params = served.cfg, served.params
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
    cfg = tiny_cfg(CASE)
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
def test_an_adversarial_router_drops_nothing(served, backend):
    """A bias that sends every token to the same two experts: the buffer
    holds every pair, so none drops, and the rows outside ``live`` count
    nowhere."""
    cfg = tiny_cfg(CASE, backend)
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
        want = served.ref.mlp(served.spec(cfg), params["params"], x)
    np.testing.assert_allclose(y[0, :17], want[0, :17], atol=5e-5)


def test_the_pieces_roofline_counts_the_tiles_the_kernel_visits():
    """``benchmark/readers/window_roofline.py::pairs_multiplied`` (kept with
    the benchmark, which imports nothing of the program) against the kernel's
    own tiling and band: the key tiles ``key_tiles`` gives each query tile of
    the cell's piece, 2,048 rows over a ring of 2,048 and its own."""
    from readers import window_roofline

    from orion_tpu.ops.pallas.piece_attention import key_tiles, tiles

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
