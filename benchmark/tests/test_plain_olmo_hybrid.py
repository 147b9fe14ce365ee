"""The plain reference of the delta-rule / full-attention hybrid against a
hand-rolled two-token case, its pieces against its whole, and the served
kind's choice of requests to check."""

import numpy as np

import jax
import jax.numpy as jnp

import harness
from reference import plain_olmo_hybrid as ref

H, DK, DV, DH, D = 2, 2, 3, 4, 6
SPEC = dict(layer_types=("gated_delta", "softmax"), n_heads=H, head_dim=DH,
            key_dim=DK, value_dim=DV, beta_scale=2.0)


def weights(seed=0):
    rng = np.random.default_rng(seed)
    w = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)  # noqa: E731
    kd, vd = H * DK, H * DV
    delta = {"in_qkvz": {"kernel": w(D, 2 * kd + 2 * vd)}, "in_ba": {"kernel": w(D, 2 * H)},
             "conv": w(4, 2 * kd + vd), "A_log": w(H), "dt_bias": w(H), "out_norm": 1 + w(DV),
             "wo": {"kernel": w(vd, D)}}
    full = {n: {"kernel": w(D, H * DH)} for n in ("wq", "wk", "wv")}
    full.update(wo={"kernel": w(H * DH, D)}, q_norm={"scale": 1 + w(H * DH)}, k_norm={"scale": 1 + w(H * DH)})
    mlp = lambda: {n: {"kernel": w(*s)} for n, s in (("gate", (D, 8)), ("up", (D, 8)), ("down", (8, D)))}  # noqa: E731
    blocks = {f"block_{i}": {"attn": attn, "mlp": mlp(), "norm1": {"scale": 1 + w(D)}, "norm2": {"scale": 1 + w(D)}}
              for i, attn in enumerate((delta, full))}
    return {"params": {"embed": {"embedding": w(10, D)}, **blocks, "final_norm": {"scale": 1 + w(D)},
                       "lm_head_kernel": w(D, 10)}}


def by_hand_delta(p, x):
    """Two tokens of the linear layer, written out: numpy, loops, no scan."""
    x = np.asarray(x, np.float64)
    g64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    kd, vd = H * DK, H * DV
    proj, ba = x @ g64(p["in_qkvz"]["kernel"]), x @ g64(p["in_ba"]["kernel"])
    pre, z = proj[:, :2 * kd + vd], proj[:, 2 * kd + vd:]
    conv = g64(p["conv"])
    silu = lambda a: a / (1 + np.exp(-a))  # noqa: E731
    post = np.stack([silu(conv[3] * pre[0]), silu(conv[2] * pre[0] + conv[3] * pre[1])])
    out = []
    s = np.zeros((H, DK, DV))
    for t in range(2):
        heads = []
        for h in range(H):
            q = post[t, h * DK:(h + 1) * DK]
            k = post[t, kd + h * DK:kd + (h + 1) * DK]
            v = post[t, 2 * kd + h * DV:2 * kd + (h + 1) * DV]
            q = q / np.sqrt((q * q).sum() + 1e-6) / np.sqrt(DK)
            k = k / np.sqrt((k * k).sum() + 1e-6)
            beta = 2.0 / (1 + np.exp(-ba[t, h]))
            decay = np.exp(-np.exp(g64(p["A_log"])[h]) * np.log1p(np.exp(ba[t, H + h] + g64(p["dt_bias"])[h])))
            s[h] = decay * s[h]
            u = beta * (v - s[h].T @ k)
            s[h] = s[h] + np.outer(k, u)
            o = s[h].T @ q
            o = o / np.sqrt((o * o).mean() + 1e-6) * g64(p["out_norm"])
            heads.append(o * silu(z[t, h * DV:(h + 1) * DV]))
        out.append(np.concatenate(heads) @ g64(p["wo"]["kernel"]))
    return np.stack(out)


def test_linear_layer_matches_two_tokens_by_hand():
    p = weights()["params"]["block_0"]["attn"]
    x = np.random.default_rng(1).normal(size=(2, D))
    got = ref.gated_delta(SPEC, p, jnp.asarray(x, jnp.float32)[None])[0]
    np.testing.assert_allclose(got, by_hand_delta(p, x), atol=2e-5)


def test_full_layer_matches_two_tokens_by_hand():
    p = weights()["params"]["block_1"]["attn"]
    x = np.random.default_rng(2).normal(size=(2, D))
    g64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    rms = lambda a, w: a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-6) * g64(w)  # noqa: E731
    q = rms(x @ g64(p["wq"]["kernel"]), p["q_norm"]["scale"]).reshape(2, H, DH)
    k = rms(x @ g64(p["wk"]["kernel"]), p["k_norm"]["scale"]).reshape(2, H, DH)
    v = (x @ g64(p["wv"]["kernel"])).reshape(2, H, DH)
    rows = []
    for t in range(2):
        heads = []
        for h in range(H):
            s = np.array([q[t, h] @ k[j, h] for j in range(t + 1)]) / np.sqrt(DH)
            w = np.exp(s - s.max())
            heads.append((w / w.sum()) @ v[:t + 1, h])
        rows.append(np.concatenate(heads) @ g64(p["wo"]["kernel"]))
    got = ref.full_attention(SPEC, p, jnp.asarray(x, jnp.float32)[None])[0]
    np.testing.assert_allclose(got, np.stack(rows), atol=2e-5)


def test_pieces_compose_to_forward_and_head_blocks_to_the_head():
    params = weights()
    toks = jnp.asarray([[1, 4, 7, 2, 9]])
    whole = ref.forward(SPEC, params, toks)
    x = ref.embed(SPEC, params, toks)
    for i, kind in enumerate(SPEC["layer_types"]):
        x = ref.block(SPEC, kind, params["params"][f"block_{i}"], x)
    np.testing.assert_allclose(ref.logits(SPEC, params, x), whole, atol=1e-6)
    halves = [ref.logits(SPEC, params, x, columns=(s, 5)) for s in (0, 5)]
    np.testing.assert_allclose(jnp.concatenate(halves, -1), whole, atol=1e-6)
    low = ref.forward({**SPEC, "matmul_dtype": "float8_e4m3fn"}, params, toks)
    assert float(jnp.abs(low - whole).max()) > 1e-2  # the lowered reference is another model


def test_served_kind_checks_the_long_requests():
    kind = harness.load_module("kinds", "serve_ref")
    served = [(np.zeros(p), np.zeros(a)) for p, a in
              [(128, 40), (3072, 50), (256, 900), (2560, 64), (128, 600), (512, 100)] * 3]
    rules = {"check_requests": 8, "check_long_prompts": 2, "check_long_answers": 2}
    chosen = kind.pick(served, rules)
    assert len(chosen) == len(set(chosen)) == 8
    assert sum(len(served[i][0]) > 1024 for i in chosen) >= 2
    assert sum(len(served[i][1]) >= 512 for i in chosen) >= 2
    assert kind.pick(served[:3], rules) == [1, 2, 0]
    assert kind.key_of("%fusion.12 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%a), kind=kLoop") == (
        "fusion.12", "bf16[8,128]")
