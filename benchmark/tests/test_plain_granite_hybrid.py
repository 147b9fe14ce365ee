"""The plain reference of the state-space / grouped-attention hybrid against
forms written another way: the recurrence against the masked ``exp(c_i -
c_s)`` sum, against plain causal linear attention where nothing decays,
one group's B and C for all of its heads, the biased conv by hand, the
grouped attention against attention over repeated KV heads, its pieces
against its whole, and the kind of run for a tied head."""

import numpy as np

import jax
import jax.numpy as jnp

import harness
from reference import plain_granite_hybrid as ref

H, P, N, G, D, KV, DH, V = 4, 3, 5, 2, 6, 2, 4, 10
SPEC = dict(layer_types=("ssm", "softmax"), n_heads=4, n_kv_heads=KV, head_dim=DH,
            attn_scale=1 / 8, ssm_heads=H, ssm_head_dim=P, ssm_state=N, ssm_groups=G,
            embed_scale=12.0, residual_scale=0.22, logit_scale=1 / 8, norm_eps=1e-5)


def rng_arrays(seed, *shapes):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=s), jnp.float32) for s in shapes]


def weights(seed=0):
    rng = np.random.default_rng(seed)
    w = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)  # noqa: E731
    c = H * P + 2 * G * N
    ssm = {"in_proj": {"kernel": w(D, 2 * H * P + 2 * G * N + H)}, "conv": w(4, c), "conv_bias": w(c),
           "A_log": w(H), "dt_bias": w(H), "D": 1 + w(H), "out_norm": 1 + w(H * P),
           "wo": {"kernel": w(H * P, D)}}
    full = {"wq": {"kernel": w(D, 4 * DH)}, "wk": {"kernel": w(D, KV * DH)},
            "wv": {"kernel": w(D, KV * DH)}, "wo": {"kernel": w(4 * DH, D)}}
    mlp = lambda: {n: {"kernel": w(*s)} for n, s in (("gate", (D, 8)), ("up", (D, 8)), ("down", (8, D)))}  # noqa: E731
    blocks = {f"block_{i}": {"attn": attn, "mlp": mlp(), "norm1": {"scale": 1 + w(D)}, "norm2": {"scale": 1 + w(D)}}
              for i, attn in enumerate((ssm, full))}
    return {"params": {"embed": {"embedding": w(V, D)}, **blocks, "final_norm": {"scale": 1 + w(D)}}}


def recurrence_inputs(t=9, seed=1):
    x, dt, a, bm, cm = rng_arrays(seed, (2, t, H, P), (2, t, H), (H,), (2, t, G, N), (2, t, G, N))
    return x, jax.nn.softplus(dt), -jnp.exp(a), bm, cm


def test_recurrence_equals_the_masked_sum():
    """``y_i = sum_{s<=i} exp(c_i - c_s) (C_i . B_s) dt_s x_s`` with ``c`` the
    cumulative sum of ``dt A``: loops, float64."""
    x, dt, a, bm, cm = recurrence_inputs()
    got = ref.ssm_recurrence(x, dt, a, bm, cm)
    x, dt, a, bm, cm = (np.asarray(y, np.float64) for y in (x, dt, a, bm, cm))
    c = np.cumsum(dt * a, axis=1)  # [B, T, H]
    want = np.zeros(x.shape)
    for b in range(x.shape[0]):
        for h in range(H):
            g = h // (H // G)
            for i in range(x.shape[1]):
                for s in range(i + 1):
                    want[b, i, h] += (np.exp(c[b, i, h] - c[b, s, h]) * (cm[b, i, g] @ bm[b, s, g])
                                      * dt[b, s, h] * x[b, s, h])
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_without_decay_it_is_causal_linear_attention():
    """``A_log -> -inf`` (``A = 0``: nothing decays), ``dt = 1``: ``y_i =
    sum_{s<=i} (C_i . B_s) x_s``, linear attention with q = C, k = B, v = x."""
    x, _, _, bm, cm = recurrence_inputs()
    a = -jnp.exp(jnp.full((H,), -jnp.inf))
    got = ref.ssm_recurrence(x, jnp.ones(x.shape[:3]), a, bm, cm)
    rep = lambda y: jnp.repeat(y, H // G, axis=2)  # noqa: E731
    scores = jnp.einsum("bihn,bshn->bhis", rep(cm), rep(bm))
    scores = jnp.where(jnp.tril(jnp.ones((x.shape[1],) * 2, bool)), scores, 0.0)
    np.testing.assert_allclose(got, jnp.einsum("bhis,bshp->bihp", scores, x), atol=1e-4)


def test_one_group_serves_all_of_its_heads():
    """Heads of one group fed the same x, dt and rate give the same y; heads
    of different groups do not."""
    x, dt, a, bm, cm = recurrence_inputs()
    x = jnp.broadcast_to(x[:, :, :1], x.shape)
    dt = jnp.broadcast_to(dt[:, :, :1], dt.shape)
    y = ref.ssm_recurrence(x, dt, jnp.full((H,), -0.7), bm, cm)
    np.testing.assert_allclose(y[:, :, 0], y[:, :, 1], atol=1e-6)  # group 0
    np.testing.assert_allclose(y[:, :, 2], y[:, :, 3], atol=1e-6)  # group 1
    assert float(jnp.abs(y[:, :, 0] - y[:, :, 2]).max()) > 1e-2


def test_three_token_conv_by_hand_bias_included():
    x = jnp.asarray([[[1.0, -2.0], [0.5, 3.0], [2.0, 1.0]]])
    w = jnp.asarray([[9.0, 9.0], [0.25, 0.0], [0.5, -1.0], [2.0, 1.0]])  # row 3: this token
    bias = jnp.asarray([0.25, -0.5])
    pre = np.asarray([
        [2.0 * 1.0 + 0.25, 1.0 * -2.0 - 0.5],
        [0.5 * 1.0 + 2.0 * 0.5 + 0.25, -1.0 * -2.0 + 1.0 * 3.0 - 0.5],
        [0.25 * 1.0 + 0.5 * 0.5 + 2.0 * 2.0 + 0.25, -1.0 * 3.0 + 1.0 * 1.0 - 0.5],
    ])
    np.testing.assert_allclose(ref.short_conv(x, w, bias)[0], pre / (1 + np.exp(-pre)), atol=1e-6)


def test_grouped_attention_is_attention_over_repeated_kv_heads():
    """Query head h reads KV head h // group, scores scaled by the config's
    value, no position term: against a per-head loop."""
    p = weights()["params"]["block_1"]["attn"]
    (u,) = rng_arrays(3, (1, 7, D))
    got = ref.full_attention(SPEC, p, u)
    q = np.asarray(u[0] @ p["wq"]["kernel"], np.float64).reshape(7, 4, DH)
    k = np.asarray(u[0] @ p["wk"]["kernel"], np.float64).reshape(7, KV, DH)
    v = np.asarray(u[0] @ p["wv"]["kernel"], np.float64).reshape(7, KV, DH)
    out = np.zeros((7, 4, DH))
    for h in range(4):
        s = q[:, h] @ k[:, h // 2].T * SPEC["attn_scale"]
        s = np.where(np.tril(np.ones((7, 7), bool)), s, -np.inf)
        pr = np.exp(s - s.max(-1, keepdims=True))
        out[:, h] = (pr / pr.sum(-1, keepdims=True)) @ v[:, h // 2]
    want = out.reshape(7, 4 * DH) @ np.asarray(p["wo"]["kernel"], np.float64)
    np.testing.assert_allclose(got[0], want, atol=1e-4)


def test_pieces_compose_and_the_lowered_reference_is_another_model():
    params = weights()
    toks = jnp.asarray([[1, 4, 7, 2, 9]])
    whole = ref.forward(SPEC, params, toks)
    x = ref.embed(SPEC, params, toks)
    np.testing.assert_allclose(x, 12.0 * params["params"]["embed"]["embedding"][toks], atol=1e-6)
    for i, kind in enumerate(SPEC["layer_types"]):
        x = ref.block(SPEC, kind, params["params"][f"block_{i}"], x)
    np.testing.assert_allclose(ref.logits(SPEC, params, x), whole, atol=1e-6)
    halves = [ref.logits(SPEC, params, x, columns=(s, 5)) for s in (0, 5)]
    np.testing.assert_allclose(jnp.concatenate(halves, -1), whole, atol=1e-6)
    low = ref.forward({**SPEC, "matmul_dtype": "float8_e4m3fn"}, params, toks)
    assert float(jnp.abs(low - whole).max()) > 2e-3  # of logits of ~0.2
    # the scales bite: each one off reads differently
    for key in ("embed_scale", "residual_scale", "logit_scale", "attn_scale"):
        other = ref.forward({**SPEC, key: 1.0}, params, toks)
        assert float(jnp.abs(other - whole).max()) > 1e-3, key


def test_tied_kind_hands_the_check_the_vocabulary_size():
    kind = harness.load_module("kinds", "serve_ref_tied")
    params = weights()
    sized = kind.with_head_size(params)
    head = sized["params"][kind.HEAD]
    assert head.shape == (0, V) and kind.HEAD not in params["params"]
    assert kind.without_head_size(sized)["params"].keys() == params["params"].keys()
    untied = {"params": {**params["params"], kind.HEAD: jnp.zeros((D, V))}}
    assert kind.with_head_size(untied) is untied and kind.without_head_size(untied) is untied
