"""The served state-space / grouped full-attention hybrid (ISSUE 41):
state-space layers with a token-dependent scalar decay per head (B and C
shared by the heads of a group, a biased short conv, the gate before one
norm) : un-rotated softmax attention over a grouped KV cache with a config
scale, a scaled embedding, scaled residual branches, scaled logits, a tied
head, against ``benchmark/reference/plain_granite_hybrid.py``; tiny, CPU,
fp32. The contract every served configuration takes is
``tests/served_contract.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from served_contract import (
    Because, ByBackend, Cell, ServedCase, ServedContract, Walk, served_fixture,
)

from orion_tpu.models.configs import get_config
from orion_tpu.models.mixers import MIXERS
from orion_tpu.models.transformer import TransformerLM, init_decode_state
from orion_tpu.ops import dispatch
from orion_tpu.ops import ssm as ssm_ops

# both layer kinds in the pattern, ONE group of eight heads as the model
# publishes (since PR 57 the gated norm is taken over each group, and this
# family's reference norms all channels at once: the two are one at one group;
# two groups with the norm by groups are tests/test_nemotron_h.py's), four
# query heads to two KV heads; T = 600 is over two scan chunks of 256
WHY = "this file's since PR 41: the engine's byte counts below are asserted at it"
T = 600
CASE = ServedCase(
    "granite_4_0_h_micro", seq=T,
    logit_tol=5e-5,  # fp32 against fp32 on logits of ~1: summation order only
    over=dict(
        n_layers=5, layer_types=Because(("ssm", "ssm", "softmax", "ssm", "softmax"), WHY),
        attn_scale=Because(1 / 32, "neither head_dim^-1/2 nor 1/head_dim: a scale read elsewhere shows"),
        ssm_head_dim=Because(8, WHY), ssm_groups=1, max_seq_len=768,
        embed_init_std=None),  # flax's own: at 64 wide the preset's leaves x0 ~ 0
    moved=("scale", "out_norm", "'D'"),  # the skip too
    floor=0.3,
    # logits to 2e-4, states to 1e-4 (fp32; the chunked form sums in another
    # order than the recurrence); pieces of 64, the last one partly padding
    walk=Walk(n=ByBackend(xla=300, pallas_interpret=70), piece=64, cold=70, against="reference",
              tol=2e-4, padded=20, states=dict(atol=1e-4),
              cold_states=ByBackend(pallas_interpret=dict(atol=1e-4)), cache_rows=True),
    server=True,
    cell=Cell("granite_4_0_h_micro.serve_batch", seed=2 ** 31 + 41),
    # read on the parent of PR 59 (44d93ca) at this case's sizes; until then
    # tests/test_trinity_mini.py pinned them at sizes of its own
    pins={"forward": "01623535d2826a4c",
          "piece": "8b97b9bdaebf9838", "step": "c189e771054a617c"},
)
served = served_fixture(CASE)


class TestServed(ServedContract):
    case = CASE

    def published(self, cfg):
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

    def test_decode_step_with_a_row_list_touches_no_other_row(self, served):
        """Under the kernels, given a row list, the state-space step and the
        cache write leave an unlisted row's state bitwise alone, conv tail
        included; a listed row steps as the XLA form does."""
        params, kernels, plain = served.params, served.programs("pallas_interpret"), served.programs()
        toks = jnp.concatenate([served.toks[:, :40], served.toks[:, 100:140]], 0)  # 4 rows
        _, states = plain.prefill(params, toks)
        mask = jnp.asarray([True, False, True, False])
        rows = dispatch.decode_live_rows(mask, backend="pallas_interpret")
        t = jnp.full((4,), 40, jnp.int32)
        lg, new = kernels.step(params, toks[:, 0], states, t, rows)
        want_lg, want = plain.step(params, toks[:, 0], states, t)
        for layer, (n, o, w) in enumerate(zip(new, states, want)):
            for name in o:
                got = np.asarray(n[name])
                np.testing.assert_array_equal(got[1::2], np.asarray(o[name])[1::2], err_msg=f"{layer}.{name}")
                np.testing.assert_allclose(got[0::2], np.asarray(w[name])[0::2], atol=1e-5)
        np.testing.assert_allclose(lg[0::2], want_lg[0::2], atol=2e-4)
        assert all(MIXERS[k].rows_in_place for k in served.cfg.resolved_layer_types)

    def after_engine(self, served, run, backend, donate):
        """With the carry donated the scan holds the grouped K and V and
        carries a chunk's own rows (``chunk_split``)."""
        held, kinds = run.engine.held_bytes, run.cfg.resolved_layer_types
        assert held["kv_bytes"] == kinds.count("softmax") * 2 * 4 * 2 * 768 * 16 * 4
        assert held["state_bytes"] == kinds.count("ssm") * 4 * (8 * 8 * 16 * 4 + 3 * (64 + 32) * 4)

    def after_server(self, served, counters, prompts):
        """The state-space counters follow the boundaries."""
        layers = served.cfg.resolved_layer_types.count("ssm")
        assert counters["ssm_piece_rows"] == layers * sum(len(p) for p in prompts)
        assert counters["ssm_row_steps"] == layers * 4 * counters["slot_steps_emitting"] > 0

    def after_cell(self, result, lines):
        """The served kind for a tied head."""
        assert 0 < result["metrics"]["kv_live_share.batch"]["value"] < 100


def test_other_presets_build_what_they_built():
    """``n_kv_heads``, ``attn_scale`` and the conv's bias absent: the served
    delta-rule hybrid's cache stays a cache a query head, and its norms'
    epsilon the constant it was."""
    cfg = get_config("olmo_hybrid_7b")
    assert cfg.n_kv_heads is None and cfg.attn_scale is None and cfg.norm_eps == 1e-6
    shapes = jax.eval_shape(lambda: init_decode_state(cfg, 1))
    assert shapes[3]["k"].shape == (1, 30, 4096, 128)


@pytest.mark.parametrize("what,over", [
    ("an unscaled residual", {"residual_scale": 1.0}),
    ("an unscaled embedding", {"embed_scale": 1.0}),
    ("unscaled logits", {"logit_scale": 1.0}),
    ("a scale of head_dim^-1/2 in attention", {"attn_scale": 16 ** -0.5}),
    ("another epsilon", {"norm_eps": 1e-2}),
])
def test_the_comparison_sees(served, what, over):
    """The tolerance is tight enough to tell the model from a reference
    that differs in one of the mechanisms."""
    served.differs(served.spec(**over))


@pytest.mark.parametrize("patch", [
    "dt missing from the input term", "no D skip", "no conv bias", "B and C per head",
    "the norm before the gate", "rotary applied", "K and V per query head"])
def test_the_comparison_sees_a_changed_layer(served, monkeypatch, patch):
    """The reference with one line of a layer changed reads differently from
    the model, by far more than the tolerance."""
    ref, params = served.ref, served.params
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
    served.differs(params=params)


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


def test_a_sliding_window_takes_grouped_kv_heads_too():
    """``swa`` under ``n_kv_heads``: the ring holds KV heads, and prefill =
    pieces = the decode walk past the window (each method one program)."""
    cfg = get_config("tiny", vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
                     layer_types=("swa", "linear"), window=16, max_seq_len=64)
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.key(2), (2, 40), 0, 64)
    params = jax.jit(model.init)(jax.random.key(0), toks)
    assert params["params"]["block_0"]["attn"]["wk"]["kernel"].shape == (32, 16)
    logits, states = jax.jit(lambda p, x: model.apply(p, x, method=model.prefill))(params, toks)
    assert states[0]["k"].shape == (2, 2, 16, 8)
    np.testing.assert_allclose(jax.jit(model.apply)(params, toks), logits, atol=1e-5)
    step = jax.jit(lambda p, tok, st, t: model.apply(p, tok, st, t, method=model.decode_step))
    st, walked = init_decode_state(cfg, 2, jnp.float32), []
    for t in range(toks.shape[1]):
        lg, st = step(params, toks[:, t], st, jnp.int32(t))
        walked.append(lg)
    np.testing.assert_allclose(jnp.stack(walked, 1), logits, atol=2e-4)
    piece = jax.jit(lambda p, x, st, off, n: model.apply(
        p, x, st, off, n, method=model.prefill_extend_step))
    st = init_decode_state(cfg, 2, jnp.float32)
    for off in (0, 16, 32):
        n = min(16, 40 - off)
        x = jnp.zeros((2, 16), toks.dtype).at[:, :n].set(toks[:, off:off + n])
        last, st = piece(params, x, st, jnp.int32(off), jnp.int32(n))
    np.testing.assert_allclose(last, logits[:, -1], atol=2e-4)
