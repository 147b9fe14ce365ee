"""The plain reference of the indexed-attention mixture of experts: its
attention layer against the equations written out in loops, a selection that
covers everything against dense grouped attention, the expert loop against
every token through every expert, and the interface ``kinds/serve_ref.py``
drives (tiling by ``query_tile``, ``columns=`` blocks of the head,
``lowered``)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from reference import plain_keye_vl2 as ref

H, KV, DH, IH, ID, D, E, W, V = 4, 2, 4, 2, 4, 8, 6, 5, 10
SPEC = dict(layer_types=("indexed", "indexed"), n_heads=H, n_kv_heads=KV, head_dim=DH,
            rope_base=1e7, index_heads=IH, index_dim=ID, index_topk=5, top_k=2, query_tile=7)
T = 23


def weights(seed=0):
    rng = np.random.default_rng(seed)
    w = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)  # noqa: E731
    attn = lambda: {  # noqa: E731
        "wq": {"kernel": w(D, H * DH)}, "wk": {"kernel": w(D, KV * DH)}, "wv": {"kernel": w(D, KV * DH)},
        "wo": {"kernel": w(H * DH, D)}, "q_norm": {"scale": 1 + w(DH)}, "k_norm": {"scale": 1 + w(DH)},
        "wqi": {"kernel": w(D, IH * ID)}, "wki": {"kernel": w(D, ID)}, "ww": {"kernel": w(D, IH)},
        "ki_norm": {"scale": 1 + w(ID), "bias": w(ID)}}
    mlp = lambda: {"router": {"kernel": w(D, E)}, "experts_gate": w(E, D, W),  # noqa: E731
                   "experts_up": w(E, D, W), "experts_down": w(E, W, D)}
    blocks = {f"block_{i}": {"attn": attn(), "mlp": mlp(), "norm1": {"scale": 1 + w(D)},
                             "norm2": {"scale": 1 + w(D)}} for i in range(2)}
    return {"params": {"embed": {"embedding": w(V, D)}, **blocks, "final_norm": {"scale": 1 + w(D)},
                       "lm_head_kernel": w(D, V)}}


def hidden(t, seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(1, t, D)), jnp.float32)


def attention_by_hand(spec, p, x):
    """The docstring's equations in loops, float64."""
    g64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    x = g64(x)[0]
    t = x.shape[0]
    norm = lambda y, w: y / np.sqrt((y * y).mean() + 1e-6) * g64(w)  # noqa: E731

    def rot(y, pos):
        half = y.shape[0] // 2
        ang = pos * spec["rope_base"] ** (-np.arange(half) * 2 / y.shape[0])
        return np.concatenate([y[:half] * np.cos(ang) - y[half:] * np.sin(ang),
                               y[half:] * np.cos(ang) + y[:half] * np.sin(ang)])

    q, k, v = (x @ g64(p[n]["kernel"]) for n in ("wq", "wk", "wv"))
    qi, ki, w = (x @ g64(p[n]["kernel"]) for n in ("wqi", "wki", "ww"))
    w = w * IH ** -0.5 * ID ** -0.5
    keys = []
    for s in range(t):
        y = ki[s]
        y = (y - y.mean()) / np.sqrt(y.var() + 1e-6) * g64(p["ki_norm"]["scale"]) + g64(p["ki_norm"]["bias"])
        keys.append(rot(y, s))
    out = np.zeros((t, H * DH))
    chosen = []
    for i in range(t):
        score = np.zeros(i + 1)
        for j in range(IH):
            qj = rot(qi[i, j * ID:(j + 1) * ID], i)
            score += w[i, j] * np.maximum(np.stack(keys[:i + 1]) @ qj, 0.0)
        order = sorted(range(i + 1), key=lambda s: (-score[s], s))[:spec["index_topk"]]
        chosen.append(sorted(order))
        for h in range(H):
            kv = h // (H // KV)
            qh = rot(norm(q[i, h * DH:(h + 1) * DH], p["q_norm"]["scale"]), i)
            kh = np.stack([rot(norm(k[s, kv * DH:(kv + 1) * DH], p["k_norm"]["scale"]), s) for s in order])
            sc = kh @ qh / np.sqrt(DH)
            pr = np.exp(sc - sc.max())
            out[i, h * DH:(h + 1) * DH] = (pr / pr.sum()) @ v[order, kv * DH:(kv + 1) * DH]
    return out @ g64(p["wo"]["kernel"]), chosen


def test_the_layer_is_the_equations_in_loops():
    p = weights()["params"]["block_0"]["attn"]
    x = hidden(T)
    want, chosen = attention_by_hand(SPEC, p, x)
    np.testing.assert_allclose(ref.indexed_attention(SPEC, p, x)[0], want, atol=2e-5)
    assert all(len(c) == min(5, i + 1) for i, c in enumerate(chosen))
    assert any(i not in c for i, c in enumerate(chosen))  # a token need not select itself


@pytest.mark.parametrize("tile", [1, 5, 23, 128])
def test_tiling_by_query_tile_changes_nothing(tile):
    p = weights()["params"]["block_0"]["attn"]
    x = hidden(T)
    np.testing.assert_allclose(ref.indexed_attention({**SPEC, "query_tile": tile}, p, x),
                               ref.indexed_attention(SPEC, p, x), atol=1e-6)


def test_a_selection_that_covers_everything_is_dense_grouped_attention():
    p = weights()["params"]["block_0"]["attn"]
    x = hidden(T)
    dense, _ = attention_by_hand({**SPEC, "index_topk": T}, p, x)
    np.testing.assert_allclose(ref.indexed_attention({**SPEC, "index_topk": 10 ** 6}, p, x)[0],
                               dense, atol=2e-5)
    assert float(jnp.abs(ref.indexed_attention(SPEC, p, x)[0] - dense).max()) > 1e-3


@pytest.mark.parametrize("case", ["ties", "fewer than k"])
def test_selected_cuts_ties_to_the_lower_position(case):
    scores = jnp.asarray([[3.0, 1.0, 2.0, 2.0, 2.0, 0.5, 2.0, -0.0, 0.0]])
    visible = jnp.ones((1, 9), bool)
    if case == "ties":
        got = ref.selected({"index_topk": 3}, scores, visible)
        np.testing.assert_array_equal(got[0], [1, 0, 1, 1, 0, 0, 0, 0, 0])
    else:
        visible = jnp.arange(9)[None] < 2
        got = ref.selected({"index_topk": 3}, scores, visible)
        np.testing.assert_array_equal(got[0], [1, 1, 0, 0, 0, 0, 0, 0, 0])


def test_the_expert_loop_is_every_token_through_its_chosen_experts():
    p = weights()["params"]["block_0"]["mlp"]
    x = hidden(T)
    g64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    xs = g64(x)[0]
    logits = xs @ g64(p["router"]["kernel"])
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.zeros_like(xs)
    for t in range(T):
        top = sorted(range(E), key=lambda e: (-probs[t, e], e))[:2]
        for e in top:
            g = probs[t, e] / probs[t, top].sum()
            a = xs[t] @ g64(p["experts_gate"][e])
            mid = a / (1 + np.exp(-a)) * (xs[t] @ g64(p["experts_up"][e]))
            want[t] += g * (mid @ g64(p["experts_down"][e]))
    np.testing.assert_allclose(ref.experts(SPEC, p, x)[0], want, atol=2e-5)
    w = ref.routing_weights(SPEC, p, x[0])
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)
    assert ((w > 0).sum(-1) == 2).all()


def test_columns_are_blocks_of_the_head_and_forward_is_the_layers():
    params = weights()
    toks = jnp.asarray(np.random.default_rng(2).integers(0, V, size=(1, T)))
    full = ref.forward(SPEC, params, toks)
    x = ref.embed(SPEC, params, toks)
    for i, kind in enumerate(SPEC["layer_types"]):
        x = ref.block(SPEC, kind, params["params"][f"block_{i}"], x)
    np.testing.assert_allclose(ref.logits(SPEC, params, x), full, atol=1e-6)
    halves = [ref.logits(SPEC, params, x, columns=(s, V // 2)) for s in (0, V // 2)]
    np.testing.assert_allclose(jnp.concatenate(halves, axis=-1), full, atol=1e-6)


def test_lowered_rounds_every_matmul_operand_but_the_routers():
    params = weights()
    toks = jnp.asarray(np.random.default_rng(2).integers(0, V, size=(1, T)))
    full = ref.forward(SPEC, params, toks)
    low = ref.forward({**SPEC, "matmul_dtype": "float8_e4m3fn"}, params, toks)
    assert 1e-2 < float(jnp.abs(low - full).max()) < 10.0
    a = jnp.asarray([[1.0009765625, 1000.0]])
    got = ref.low({"matmul_dtype": "float8_e4m3fn"}, a)[0]
    np.testing.assert_array_equal(got, [[1.0, 448.0]])  # rounded, saturating
    assert ref.low(SPEC, a)[0] is a
    p = params["params"]["block_0"]["mlp"]
    np.testing.assert_array_equal(
        ref.routing_weights({**SPEC, "matmul_dtype": "float8_e4m3fn"}, p, hidden(T)[0]),
        ref.routing_weights(SPEC, p, hidden(T)[0]))
