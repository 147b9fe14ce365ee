"""The served state-space / grouped full-attention hybrid (ISSUE 41):
state-space layers with a token-dependent scalar decay per head (B and C
shared by the heads of a group, a biased short conv, the gate before one
norm) : un-rotated softmax attention over a grouped KV cache with a config
scale, a scaled embedding, scaled residual branches, scaled logits, a tied
head, against ``benchmark/reference/plain_granite_hybrid.py``; tiny, CPU,
fp32."""

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
from orion_tpu.models.transformer import TransformerLM, init_decode_state
from orion_tpu.ops import dispatch
from orion_tpu.ops import ssm as ssm_ops
from orion_tpu.serving import DecodeRequest, ServeConfig, Server, SlotEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from reference import plain_granite_hybrid as ref  # noqa: E402

# both layer kinds in the pattern, ONE group of eight heads as the model
# publishes (since PR 57 the gated norm is taken over each group, and this
# family's reference norms all channels at once: the two are one at one group;
# two groups with the norm by groups are tests/test_nemotron_h.py's), four
# query heads to two KV heads; T = 600 is over two scan chunks of 256
TINY = dict(vocab_size=256, d_model=64, n_layers=5,
            layer_types=("ssm", "ssm", "softmax", "ssm", "softmax"),
            n_heads=4, n_kv_heads=2, head_dim=16, attn_scale=1 / 32,
            ssm_heads=8, ssm_head_dim=8, ssm_state=16, ssm_groups=1,
            mlp_hidden=128, max_seq_len=768, dtype="float32", param_dtype="float32",
            embed_init_std=None)  # flax's own: at 64 wide the preset's leaves x0 ~ 0
T = 600
# fp32 against fp32 on logits of ~1: summation order only
LOGIT_TOL = 5e-5
GREEDY = SampleConfig(temperature=0.0)


def tiny_cfg(backend="xla", **over):
    return dataclasses.replace(
        get_config("granite_4_0_h_micro"), backend=backend, **{**TINY, **over})


def spec_of(cfg, **over):
    keys = ("n_heads", "n_kv_heads", "head_dim", "attn_scale", "ssm_heads", "ssm_head_dim",
            "ssm_state", "ssm_groups", "embed_scale", "residual_scale", "logit_scale", "norm_eps")
    return {"layer_types": cfg.resolved_layer_types, **{k: getattr(cfg, k) for k in keys}, **over}


@pytest.fixture(scope="module")
def model_params():
    cfg = tiny_cfg()
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, T), 0, cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.key(0), toks[:, :16])
    # norm weights and the skip off 1, so that one left out or misplaced shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.3 * jax.random.normal(jax.random.key(len(str(path))), x.shape)
        if any(n in str(path) for n in ("scale", "out_norm", "'D'")) else x, params)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(spec_of(cfg), params, toks)
        got = model.apply(params, toks)
    return cfg, params, toks, want, got


def test_preset_is_the_published_shape():
    cfg = get_config("granite_4_0_h_micro")
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (2048, 40, 32, 8, 64)
    assert (cfg.mlp_hidden, cfg.vocab_size, cfg.tie_embeddings) == (8192, 100352, True)
    kinds = cfg.resolved_layer_types
    assert [i for i, k in enumerate(kinds) if k == "softmax"] == [5, 15, 25, 35]
    assert kinds.count("ssm") == 36
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_conv_width) == (64, 64, 128, 1, 4)
    assert (cfg.embed_scale, cfg.residual_scale, cfg.logit_scale) == (12, 0.22, 1 / 8)
    assert cfg.attn_scale == 1 / 64 and cfg.norm_eps == 1e-5 and not cfg.rotary and cfg.pos_embed == "none"
    assert cfg.embed_init_std == 0.005
    shapes = jax.eval_shape(lambda: init_decode_state(cfg, 2))
    assert [sorted(s) for s in shapes] == [["k", "v"] if k == "softmax" else ["conv", "s"] for k in kinds]
    # two heads side by side on lanes; 3 pre-conv rows of 4,352 channels
    assert shapes[0]["s"].shape == (2, 32, 128, 128) and shapes[0]["s"].dtype == jnp.float32
    assert shapes[0]["conv"].shape == (2, 3 * 4352)
    assert shapes[5]["k"].shape == (2, 8, 2048, 64)
    n = sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))))
    assert n == 36 * 76182976 + 4 * 60821504 + 100352 * 2048 + 2048, n


def test_other_presets_build_what_they_built():
    """``n_kv_heads``, ``attn_scale`` and the conv's bias absent: the served
    delta-rule hybrid's cache stays a cache a query head, and its norms'
    epsilon the constant it was."""
    cfg = get_config("olmo_hybrid_7b")
    assert cfg.n_kv_heads is None and cfg.attn_scale is None and cfg.norm_eps == 1e-6
    shapes = jax.eval_shape(lambda: init_decode_state(cfg, 1))
    assert shapes[3]["k"].shape == (1, 30, 4096, 128)


def test_model_matches_the_reference(model_params):
    cfg, params, toks, want, got = model_params
    assert float(jnp.abs(want).max()) > 0.3
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL)


@pytest.mark.parametrize("what,over", [
    ("an unscaled residual", {"residual_scale": 1.0}),
    ("an unscaled embedding", {"embed_scale": 1.0}),
    ("unscaled logits", {"logit_scale": 1.0}),
    ("a scale of head_dim^-1/2 in attention", {"attn_scale": 16 ** -0.5}),
    ("another epsilon", {"norm_eps": 1e-2}),
])
def test_the_comparison_sees(model_params, what, over):
    """The tolerance is tight enough to tell the model from a reference
    that differs in one of the mechanisms."""
    cfg, params, toks, want, got = model_params
    with jax.default_matmul_precision("highest"):
        other = ref.forward({**spec_of(cfg), **over}, params, toks)
    assert float(jnp.abs(other - got).max()) > 20 * LOGIT_TOL, what


@pytest.mark.parametrize("patch", [
    "dt missing from the input term", "no D skip", "no conv bias", "B and C per head",
    "the norm before the gate", "rotary applied", "K and V per query head"])
def test_the_comparison_sees_a_changed_layer(model_params, monkeypatch, patch):
    """The reference with one line of a layer changed reads differently from
    the model, by far more than the tolerance."""
    cfg, params, toks, want, got = model_params
    spec = spec_of(cfg)
    if patch == "dt missing from the input term":
        plain = ref.ssm_recurrence
        monkeypatch.setattr(ref, "ssm_recurrence", lambda x, dt, a, bm, cm: plain(
            x / jnp.maximum(dt, 1e-6)[..., None], dt, a, bm, cm))
    elif patch == "no D skip":
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.zeros_like(x) if "'D'" in str(path) else x, params)
    elif patch == "no conv bias":
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.zeros_like(x) if "conv_bias" in str(path) else x, params)
    elif patch == "B and C per head":
        plain = ref.ssm_recurrence

        def per_head(y, heads):  # head h of a group reads its group's B rolled by h
            rep = heads // y.shape[2]
            return jnp.stack([jnp.roll(y[:, :, h // rep], h % rep, axis=-1)
                              for h in range(heads)], axis=2)

        monkeypatch.setattr(ref, "ssm_recurrence", lambda x, dt, a, bm, cm: plain(
            x, dt, a, per_head(bm, x.shape[2]),
            jnp.repeat(cm, x.shape[2] // cm.shape[2], axis=2)))
    elif patch == "the norm before the gate":
        monkeypatch.setattr(ref, "gated_norm", lambda spec, y, z, w: (
            ref.rms(spec, y, w) * jax.nn.silu(z)))
    elif patch == "rotary applied":
        plain = ref.qkv

        def rotated(spec, p, u):
            q, k, v = plain(spec, p, u)
            half = q.shape[-1] // 2
            ang = jnp.arange(u.shape[1], dtype=jnp.float32)[:, None] * (
                10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half))

            def rot(y):
                a = ang.reshape((1, -1) + (1,) * (y.ndim - 3) + (half,))
                y1, y2 = y[..., :half], y[..., half:]
                return jnp.concatenate([y1 * jnp.cos(a) - y2 * jnp.sin(a),
                                        y2 * jnp.cos(a) + y1 * jnp.sin(a)], -1)

            return rot(q), rot(k), v

        monkeypatch.setattr(ref, "qkv", rotated)
    elif patch == "K and V per query head":
        # query head h reads KV head h % KV instead of h // group
        plain = ref.qkv

        def regrouped(spec, p, u):
            q, k, v = plain(spec, p, u)
            b, t, kvh, g, dh = q.shape
            q = q.reshape(b, t, g, kvh, dh).swapaxes(2, 3)
            return q, k, v

        monkeypatch.setattr(ref, "qkv", regrouped)
    with jax.default_matmul_precision("highest"):
        other = ref.forward(spec, params, toks)
    assert float(jnp.abs(other - got).max()) > 20 * LOGIT_TOL, patch


# -- the serving entry points --------------------------------------------------


def walk(model, params, cfg, toks):
    states = init_decode_state(cfg, toks.shape[0], jnp.float32)
    step = jax.jit(lambda p, tok, st, t: model.apply(p, tok, st, t, method=model.decode_step))
    outs = []
    for t in range(toks.shape[1]):
        lg, states = step(params, toks[:, t], states, jnp.int32(t))
        outs.append(lg)
    return jnp.stack(outs, 1), states


def assert_states_close(got, want, rows, atol):
    """Recurrent states and conv tails whole; caches up to ``rows``."""
    for g, w in zip(got, want):
        for name in w:
            a, b = np.asarray(g[name]), np.asarray(w[name])
            if name in ("k", "v"):
                a, b = a[:, :, :rows], b[:, :, :rows]
            np.testing.assert_allclose(a, b, atol=atol, err_msg=name)


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_prefill_equals_pieces_equals_the_decode_walk(model_params, backend):
    """``prefill`` = pieces of ``prefill_extend`` (the last one padded: it
    stops at its ``length``) = the ``decode_step`` walk, for the state-space
    mixer and the grouped softmax: logits to 2e-4, states to 1e-4 (fp32;
    the chunked form sums in another order than the recurrence)."""
    cfg, params, toks, want, _ = model_params
    cfg = dataclasses.replace(cfg, backend=backend)
    model = TransformerLM(cfg)
    n = 300 if backend == "xla" else 70  # the interpreter is slow
    toks = toks[:, :n]
    logits, states = jax.jit(lambda p, x: model.apply(p, x, method=model.prefill))(params, toks)
    np.testing.assert_allclose(logits, want[:, :n], atol=2e-4)
    # padded whole: the state stops at the real length
    padded = jnp.pad(toks, ((0, 0), (0, 20)))
    _, stopped = model.apply(params, padded, jnp.int32(n), method=model.prefill_last)
    assert_states_close(stopped, states, n, 1e-5)
    # pieces of 64, the last one partly padding
    piece = jax.jit(lambda p, x, st, off, ln: model.apply(
        p, x, st, off, ln, method=model.prefill_extend_step))
    st = init_decode_state(cfg, toks.shape[0], jnp.float32)
    for off in range(0, n, 64):
        real = min(64, n - off)
        x = jnp.zeros((toks.shape[0], 64), toks.dtype).at[:, :real].set(toks[:, off:off + real])
        last, st = piece(params, x, st, jnp.int32(off), jnp.int32(real))
    np.testing.assert_allclose(last, logits[:, -1], atol=2e-4)
    assert_states_close(st, states, n, 1e-4)
    walked, wst = walk(model, params, cfg, toks[:, :70])
    np.testing.assert_allclose(walked, logits[:, :70], atol=2e-4)
    if n == 70:
        assert_states_close(wst, states, n, 1e-4)


def test_decode_step_with_a_row_list_touches_no_other_row(model_params):
    """Under the kernels, given a row list, the state-space step and the
    cache write leave an unlisted row's state bitwise alone, conv tail
    included; a listed row steps as the XLA form does."""
    cfg, params, toks, _, _ = model_params
    model = TransformerLM(dataclasses.replace(cfg, backend="pallas_interpret"))
    plain = TransformerLM(cfg)
    toks = jnp.concatenate([toks[:, :40], toks[:, 100:140]], 0)  # 4 rows
    _, states = plain.apply(params, toks, method=plain.prefill)
    mask = jnp.asarray([True, False, True, False])
    rows = dispatch.decode_live_rows(mask, backend="pallas_interpret")
    t = jnp.full((4,), 40, jnp.int32)
    lg, new = model.apply(params, toks[:, 0], states, t, rows, method=model.decode_step)
    want_lg, want = plain.apply(params, toks[:, 0], states, t, method=plain.decode_step)
    for layer, (n, o, w) in enumerate(zip(new, states, want)):
        for name in o:
            got = np.asarray(n[name])
            np.testing.assert_array_equal(got[1::2], np.asarray(o[name])[1::2], err_msg=f"{layer}.{name}")
            np.testing.assert_allclose(got[0::2], np.asarray(w[name])[0::2], atol=1e-5)
    np.testing.assert_allclose(lg[0::2], want_lg[0::2], atol=2e-4)
    assert all(MIXERS[k].rows_in_place for k in cfg.resolved_layer_types)


# -- the ops -------------------------------------------------------------------


def ssm_inputs(b=2, t=70, h=8, p=8, g=2, n=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(ks[0], (b, t, h, p)), jax.nn.softplus(jax.random.normal(ks[1], (b, t, h))),
            -jnp.exp(jax.random.normal(ks[2], (h,))), jax.random.normal(ks[3], (b, t, g, n)),
            jax.random.normal(ks[4], (b, t, g, n)), jax.random.normal(ks[5], (b, h, p, n)))


@pytest.mark.parametrize("case", ["whole", "state in", "padded", "one chunk"])
def test_chunked_scan_against_the_recurrence(case):
    x, dt, a, bm, cm, s0 = ssm_inputs()
    chunk = 128 if case == "one chunk" else 16
    s_in = None if case == "whole" else s0
    n = 37 if case == "padded" else x.shape[1]
    want, s_want = ssm_ops.ssm_recurrent(x[:, :n], dt[:, :n], a, bm[:, :n], cm[:, :n], s_in)
    got, s_got = dispatch.ssm_scan(
        x, dt, a, bm, cm, backend="xla", chunk=chunk, initial_state=s_in,
        length=jnp.int32(n) if case == "padded" else None)
    np.testing.assert_allclose(got[:, :n], want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(s_got, s_want, rtol=2e-5, atol=2e-5)


def test_a_strong_decay_underflows_and_never_overflows():
    """Decays enter as exp of non-positive differences: a rate of -1e4 gives
    exact zeros, no inf, no nan, where a ratio of exponentials would not."""
    x, dt, a, bm, cm, s0 = ssm_inputs()
    got, s = dispatch.ssm_scan(x, dt, a * 1e4, bm, cm, backend="xla", chunk=16, initial_state=s0)
    assert bool(jnp.all(jnp.isfinite(got))) and bool(jnp.all(jnp.isfinite(s)))


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_state_step_against_the_recurrence(backend):
    """One token on the held layout, every row or the listed ones."""
    x, dt, a, bm, cm, s0 = ssm_inputs(b=5)
    pack = ssm_ops.state_pack(8, 8, 2)
    assert pack == 4 and ssm_ops.state_pack(64, 64, 1) == 2
    held = ssm_ops.pack_state(s0, pack)
    np.testing.assert_array_equal(ssm_ops.unpack_state(held, pack), s0)
    want, s_want = ssm_ops.ssm_step(x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], s0)
    mask = jnp.asarray([True, True, False, True, False])
    rows = dispatch.decode_live_rows(mask, backend=backend)
    got, s_got = dispatch.ssm_state_step(
        x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], held, pack, rows, backend=backend)
    live = np.asarray(mask) if rows is not None else np.ones(5, bool)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], atol=1e-5)
    np.testing.assert_allclose(np.asarray(ssm_ops.unpack_state(s_got, pack))[live],
                               np.asarray(s_want)[live], atol=1e-6)
    if rows is not None:
        np.testing.assert_array_equal(np.asarray(s_got)[~live], np.asarray(held)[~live])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_cache_attention_kernel_with_a_group_against_the_xla_form(dtype):
    """Four query heads a KV head, no block list: the kernel reads a listed
    row's live blocks of the grouped cache and equals the XLA form, which
    equals attention over the cache repeated a query head."""
    ks = jax.random.split(jax.random.key(3), 3)
    b, h, kvh, cap, d = 5, 8, 2, 512, 64
    q = jax.random.normal(ks[0], (b, h, d)).astype(dtype)
    kc, vc = (jax.random.normal(k, (b, kvh, cap, d)).astype(dtype) for k in ks[1:])
    lengths = jnp.asarray([3, 300, 0, 512, 77], jnp.int32)
    mask = jnp.asarray([True, True, True, True, False])
    rows = dispatch.decode_live_rows(mask, backend="pallas_interpret")
    out, lse = dispatch.cache_attention(q, kc, vc, lengths, rows, backend="pallas_interpret")
    want, want_lse = dispatch.cache_attention(q, kc, vc, lengths, None, backend="xla")
    rep, rep_lse = dispatch.cache_attention(
        q, jnp.repeat(kc, 4, 1), jnp.repeat(vc, 4, 1), lengths, None, backend="xla")
    seen = np.asarray([True, True, False, True, False])  # listed, and not empty
    np.testing.assert_allclose(np.asarray(out)[seen], np.asarray(want)[seen], atol=2e-6)
    np.testing.assert_allclose(np.asarray(lse)[seen], np.asarray(want_lse)[seen], atol=2e-6)
    np.testing.assert_allclose(np.asarray(want)[seen], np.asarray(rep)[seen], atol=2e-6)
    np.testing.assert_allclose(np.asarray(want_lse)[seen], np.asarray(rep_lse)[seen], atol=2e-6)
    assert float(jnp.abs(out[2]).max()) == 0 and float(lse[2].max()) == np.float32(-1e30)  # length 0
    assert float(jnp.abs(out[4]).max()) == 0 and float(lse[4].max()) == np.float32(-1e30)  # unlisted


def test_conv_bias_enters_before_the_silu():
    from orion_tpu.ops.gated_delta import causal_short_conv

    x = jnp.asarray([[[1.0, -2.0], [0.5, 3.0], [2.0, 1.0]]])
    w = jnp.asarray([[0.0, 0.0], [0.0, 0.0], [0.5, -1.0], [2.0, 1.0]])
    bias = jnp.asarray([0.25, -0.5])
    got = causal_short_conv(x, w, bias=bias)
    pre = np.asarray([[2.0 + 0.25, -2.0 - 0.5], [1.0 + 0.5 + 0.25, 3.0 + 2.0 - 0.5],
                      [4.0 + 0.25 + 0.25, 1.0 - 3.0 - 0.5]])
    np.testing.assert_allclose(got[0], pre / (1 + np.exp(-pre)), atol=1e-6)
    np.testing.assert_array_equal(causal_short_conv(x, w), causal_short_conv(x, w, bias=None))


# -- through the engine and the server -----------------------------------------


def serve(cfg, params, prompts, max_new, donate=False):
    engine = SlotEngine(TransformerLM(cfg), params, slots=4, chunk=4,
                        prefill_buckets=(64, 128, 256), prefill_chunk=32)
    engine.donate_carry = donate
    for i, p in enumerate(prompts):
        engine.admit(DecodeRequest(prompt=p, max_new_tokens=max_new, sample=GREEDY, seed=i), tag=i)
    done = {}
    while engine.busy:
        for tag, res in engine.step():
            assert res.status == "ok", res.status
            done[tag] = np.asarray(res.tokens).reshape(-1)
    return [done[i] for i in range(len(prompts))], engine


@pytest.mark.parametrize("backend,donate", [
    ("xla", False), ("pallas_interpret", False), ("xla", True), ("pallas_interpret", True)])
def test_engine_serves_as_generate(model_params, backend, donate):
    """Through ``SlotEngine``: three requests of one, three and six pieces
    resident together, pieces and decode interleaved; each request's ids are
    ``generate()``'s for it alone. With the carry donated the scan holds the
    grouped K and V and carries a chunk's own rows (``chunk_split``)."""
    cfg, params, toks, _, _ = model_params
    cfg = dataclasses.replace(cfg, backend=backend)
    prompts = [np.asarray(toks[0, :30]), np.asarray(toks[1, :90]), np.asarray(toks[0, 20:190])]
    together, engine = serve(cfg, params, prompts, 9, donate)
    xla = dataclasses.replace(cfg, backend="xla")
    for p, ids in zip(prompts, together):
        alone = generate(TransformerLM(xla), params, jnp.asarray(p)[None], 9, GREEDY)
        np.testing.assert_array_equal(ids, np.asarray(alone)[0, -9:])
    held = engine.held_bytes
    kinds = cfg.resolved_layer_types
    assert held["kv_bytes"] == kinds.count("softmax") * 2 * 4 * 2 * 768 * 16 * 4
    assert held["state_bytes"] == kinds.count("ssm") * 4 * (8 * 8 * 16 * 4 + 3 * (64 + 32) * 4)


def test_server_answers_as_generate(model_params):
    """The ``Server`` over the tiny preset: 4 slots, five requests, pieces
    and decode interleaved; every answer is ``generate()``'s, and the
    state-space counters follow the boundaries."""
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
    layers = cfg.resolved_layer_types.count("ssm")
    assert counters["ssm_piece_rows"] == layers * sum(len(p) for p in prompts)
    assert counters["ssm_row_steps"] == layers * 4 * counters["slot_steps_emitting"] > 0


def test_cell_rehearses_on_the_cpu(tmp_path):
    """``granite_4_0_h_micro.serve_batch`` end to end at tiny sizes: the
    served kind for a tied head, the reference named by the configuration's
    file, the check on what was served in the window."""
    import json
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "granite_4_0_h_micro.serve_batch",
         "--seed", str(2 ** 31 + 41), "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    assert 0 < line["metrics"]["kv_live_share.batch"]["value"] < 100


# -- what the other presets trace is the parent's -------------------------------

# sha256 (first 16 hex) of the jaxpr text of four programs of three presets
# at tiny widths, read on the PARENT of PR 41 (461ecb8) and equal on its
# tree: with ``n_kv_heads``, ``attn_scale``, ``embed_init_std`` and the conv's
# bias absent nothing new is traced. A PR that changes one of these programs
# on purpose reads the new value from the assertion and replaces it here.
# PR 45 did, for ``olmo_hybrid_7b``'s prefill, piece and step: the delta-rule
# layer's gate is an op that takes ``o`` head-major, so the ``swapaxes`` that
# ``_rule`` did now comes after the conv tail's equations (prefill and piece:
# the same equations, in another order) and a decode step's one row passes it
# as ``[B, Hv, 1, dv]`` (unit axes; the arithmetic is bit for bit the old
# ``_output``'s: tests/test_gated_norm_kernel.py).
# PR 51 did, for ``hybrid_1b3``'s piece: a window layer's piece now writes its
# last min(length, window) rows into the ring at their slots (one scatter of
# the piece's rows, the rest dropped) where it rebuilt all ``window`` rows by a
# gather and a scatter; the attention's equations and the ring's contents are
# the old ones (tests/test_prefill_inscan.py holds them bitwise).
_OLMO = dict(vocab_size=256, d_model=96, n_heads=3, head_dim=16, gdn_key_heads=3,
             gdn_value_heads=3, gdn_key_dim=8, gdn_value_dim=24, mlp_hidden=128,
             max_seq_len=256, dtype="float32", param_dtype="float32")
_LM = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4, max_seq_len=128, dtype="float32")
_PRESETS = {
    "olmo_hybrid_7b": _OLMO,
    "lm_1b3": _LM,
    "hybrid_1b3": {**_LM, "n_layers": 4, "layer_types": ("swa", "swa", "swa", "linear"), "window": 32},
}
_TRACED = {
    "olmo_hybrid_7b.forward": "1d0bffda123d623c", "olmo_hybrid_7b.prefill": "5e76eafde9c2cca9",
    "olmo_hybrid_7b.piece": "8f805726bc5d85fb", "olmo_hybrid_7b.step": "2c5a2873d6be3918",
    "lm_1b3.forward": "91150cac1cbb2ee7", "lm_1b3.prefill": "55bfda62ea1cc771",
    "lm_1b3.piece": "067511f7d2e33163", "lm_1b3.step": "bf7be0cd0cf2078e",
    "hybrid_1b3.forward": "e12b2be89edde12c", "hybrid_1b3.prefill": "ff81ed1f94e804b7",
    "hybrid_1b3.piece": "d038a27629d8002b", "hybrid_1b3.step": "535629313893979d",
}


@pytest.mark.parametrize("which", sorted(_TRACED))
def test_other_presets_trace_the_parents_programs(which):
    import hashlib

    preset, program = which.split(".")
    cfg = dataclasses.replace(get_config(preset), **_PRESETS[preset])
    model = TransformerLM(cfg)
    toks = jnp.zeros((2, 48), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0), toks))
    states = jax.eval_shape(lambda: init_decode_state(cfg, 2, jnp.float32))
    traced = {
        "forward": lambda: jax.make_jaxpr(lambda p, x: model.apply(p, x))(params, toks),
        "prefill": lambda: jax.make_jaxpr(lambda p, x: model.apply(
            p, x, jnp.int32(40), method=model.prefill_last))(params, toks),
        "piece": lambda: jax.make_jaxpr(lambda p, x, st: model.apply(
            p, x[:, :16], st, jnp.int32(16), jnp.int32(9),
            method=model.prefill_extend_step))(params, toks, states),
        "step": lambda: jax.make_jaxpr(lambda p, x, st: model.apply(
            p, x[:, 0], st, jnp.full((2,), 5, jnp.int32), method=model.decode_step))(params, toks, states),
    }[program]()
    assert hashlib.sha256(str(traced).encode()).hexdigest()[:16] == _TRACED[which]


def test_a_sliding_window_takes_grouped_kv_heads_too():
    """``swa`` under ``n_kv_heads``: the ring holds KV heads, and prefill =
    pieces = the decode walk past the window."""
    cfg = get_config("tiny", vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
                     layer_types=("swa", "linear"), window=16, max_seq_len=64)
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.key(2), (2, 40), 0, 64)
    params = model.init(jax.random.key(0), toks)
    assert params["params"]["block_0"]["attn"]["wk"]["kernel"].shape == (32, 16)
    logits, states = model.apply(params, toks, method=model.prefill)
    assert states[0]["k"].shape == (2, 2, 16, 8)
    np.testing.assert_allclose(model.apply(params, toks), logits, atol=1e-5)
    walked, _ = walk(model, params, cfg, toks)
    np.testing.assert_allclose(walked, logits, atol=2e-4)
    st = init_decode_state(cfg, 2, jnp.float32)
    for off in (0, 16, 32):
        n = min(16, 40 - off)
        x = jnp.zeros((2, 16), toks.dtype).at[:, :n].set(toks[:, off:off + n])
        last, st = model.apply(params, x, st, jnp.int32(off), jnp.int32(n), method=model.prefill_extend_step)
    np.testing.assert_allclose(last, logits[:, -1], atol=2e-4)
