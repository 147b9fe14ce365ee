"""The plain reference of the decayed-linear / block-sparse hybrid: its token
recurrence against its masked form, its sparse layer against dense grouped
attention where the selection covers everything, and its selector against
the equations written out in loops on a three-block example."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from reference import plain_minicpm_sala as ref

H, KV, DH, D = 4, 2, 4, 8
SPEC = dict(layer_types=("decay_linear", "block_sparse"), n_heads=H, n_kv_heads=KV, head_dim=DH,
            embed_scale=12.0, residual_scale=1.4 / 32 ** 0.5, logit_scale=1 / 16,
            decay_exponent=8.0, rope_base=10000.0, kernel=4, stride=2, block=8, init_blocks=1,
            window=8, topk=2, dense_len=8, query_tile=5)


def weights(seed=0):
    rng = np.random.default_rng(seed)
    w = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)  # noqa: E731
    norms = lambda: {"q_norm": {"scale": 1 + w(DH)}, "k_norm": {"scale": 1 + w(DH)}}  # noqa: E731
    lin = {n: {"kernel": w(D, H * DH)} for n in ("wq", "wk", "wv", "wg")}
    lin.update(wo={"kernel": w(H * DH, D)}, out_norm={"scale": 1 + w(H * DH)}, **norms())
    sparse = {"wq": {"kernel": w(D, H * DH)}, "wg": {"kernel": w(D, H * DH)},
              "wk": {"kernel": w(D, KV * DH)}, "wv": {"kernel": w(D, KV * DH)},
              "wo": {"kernel": w(H * DH, D)}, **norms()}
    mlp = lambda: {n: {"kernel": w(*s)} for n, s in (("gate", (D, 12)), ("up", (D, 12)), ("down", (12, D)))}  # noqa: E731
    blocks = {f"block_{i}": {"attn": attn, "mlp": mlp(), "norm1": {"scale": 1 + w(D)}, "norm2": {"scale": 1 + w(D)}}
              for i, attn in enumerate((lin, sparse))}
    return {"params": {"embed": {"embedding": w(10, D)}, **blocks, "final_norm": {"scale": 1 + w(D)},
                       "lm_head_kernel": w(D, 10)}}


def hidden(t, seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(1, t, D)), jnp.float32)


def test_recurrence_equals_the_masked_form():
    p = weights()["params"]["block_0"]["attn"]
    x = hidden(29)
    np.testing.assert_allclose(ref.lightning(SPEC, p, x), ref.lightning_masked(SPEC, p, x), atol=2e-5)
    # the decay is there, and every head has its own
    lam = np.exp(-np.asarray(ref.slopes(SPEC)))
    np.testing.assert_allclose(lam, np.exp(-2.0 ** (-8 * np.arange(1, H + 1) / H)), rtol=1e-6)
    flat = ref.lightning_masked({**SPEC, "decay_exponent": 60.0}, p, x)
    assert float(jnp.abs(flat - ref.lightning(SPEC, p, x)).max()) > 1e-2


def test_two_tokens_of_the_linear_layer_by_hand():
    p = weights()["params"]["block_0"]["attn"]
    x = np.asarray(hidden(2), np.float64)[0]
    g64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    q, k, v, gate = (x @ g64(p[n]["kernel"]) for n in ("wq", "wk", "wv", "wg"))
    out = []
    s = np.zeros((H, DH, DH))
    for t in range(2):
        heads = []
        for h in range(H):
            cut = slice(h * DH, (h + 1) * DH)
            qh, kh = q[t, cut], k[t, cut]
            qh = qh / np.sqrt((qh * qh).mean() + 1e-6) * g64(p["q_norm"]["scale"])
            kh = kh / np.sqrt((kh * kh).mean() + 1e-6) * g64(p["k_norm"]["scale"])
            ang = t * 10000.0 ** (-np.arange(DH // 2) * 2 / DH)
            rot = lambda y: np.concatenate([y[:2] * np.cos(ang) - y[2:] * np.sin(ang),  # noqa: E731
                                            y[2:] * np.cos(ang) + y[:2] * np.sin(ang)])
            qh, kh = rot(qh), rot(kh)
            s[h] = np.exp(-2.0 ** (-8 * (h + 1) / H)) * s[h] + np.outer(kh, v[t, cut])
            heads.append(qh @ s[h] / np.sqrt(DH))
        o = np.concatenate(heads)
        o = o / np.sqrt((o * o).mean() + 1e-6) * g64(p["out_norm"]["scale"])
        out.append((o / (1 + np.exp(-gate[t]))) @ g64(p["wo"]["kernel"]))
    np.testing.assert_allclose(ref.lightning(SPEC, p, jnp.asarray(x[None], jnp.float32))[0], np.stack(out), atol=1e-5)


def dense_grouped(p, x):
    """Plain causal attention of H query heads over KV grouped heads, loops."""
    x = np.asarray(x, np.float64)[0]
    g64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    t = x.shape[0]
    q, k, v, gate = (x @ g64(p[n]["kernel"]) for n in ("wq", "wk", "wv", "wg"))
    norm = lambda y, w: y / np.sqrt((y * y).mean() + 1e-6) * g64(w)  # noqa: E731
    out = np.zeros((t, H * DH))
    for h in range(H):
        kv = h // (H // KV)
        kh = np.stack([norm(k[s, kv * DH:(kv + 1) * DH], p["k_norm"]["scale"]) for s in range(t)])
        for i in range(t):
            qh = norm(q[i, h * DH:(h + 1) * DH], p["q_norm"]["scale"])
            sc = kh[:i + 1] @ qh / np.sqrt(DH)
            w = np.exp(sc - sc.max())
            out[i, h * DH:(h + 1) * DH] = (w / w.sum()) @ v[:i + 1, kv * DH:(kv + 1) * DH]
    return (out / (1 + np.exp(-gate))) @ g64(p["wo"]["kernel"])


@pytest.mark.parametrize("over", [{"topk": 4}, {"dense_len": 32}])
def test_a_selection_that_covers_everything_is_dense_grouped_attention(over):
    """``topk x block >= T``, or ``dense_len >= T``: every block is attended."""
    p = weights()["params"]["block_1"]["attn"]
    x = hidden(30)
    got = ref.sparse_attention({**SPEC, **over}, p, x)
    np.testing.assert_allclose(got[0], dense_grouped(p, x), atol=2e-5)
    # and at topk 2 of 4 blocks it is not
    assert float(jnp.abs(ref.sparse_attention(SPEC, p, x) - got).max()) > 1e-3


def selection_by_hand(spec, q, k, i):
    """The blocks token ``i`` selects, per KV head: the docstring's equations
    in loops. q [H, T, dh], k [KV, T, dh] (normed)."""
    kernel, stride, block, topk = spec["kernel"], spec["stride"], spec["block"], spec["topk"]
    t = k.shape[1]
    group = q.shape[0] // k.shape[0]
    cur = i // block
    out = []
    for kv in range(k.shape[0]):
        pooled = [k[kv, j * stride:j * stride + kernel].mean(0) for j in range((t - kernel) // stride + 1)]
        visible = [j for j in range(len(pooled)) if j * stride + kernel <= i + 1]
        shared = {j: 0.0 for j in visible}
        for h in range(kv * group, (kv + 1) * group):
            sc = np.array([q[h, i] @ pooled[j] / np.sqrt(q.shape[-1]) for j in visible])
            w = np.exp(sc - sc.max()) if visible else sc
            for j, pj in zip(visible, w / w.sum() if visible else []):
                shared[j] += pj
        score = {}
        for b in range(cur + 1):
            over = [j for j in visible if j * stride < (b + 1) * block and j * stride + kernel > b * block]
            score[b] = max((shared[j] for j in over), default=-np.inf)
        forced = {b for b in range(cur + 1)
                  if b < spec["init_blocks"] or b >= cur - (spec["window"] // block - 1)}
        rest = sorted((b for b in range(cur + 1) if b not in forced), key=lambda b: (-score[b], b))
        out.append(forced | set(rest[:max(0, topk - len(forced))]))
    return out


@pytest.mark.parametrize("init_blocks,topk", [(0, 2), (1, 2), (0, 1)])
def test_selection_on_a_three_block_example(init_blocks, topk):
    """T = 24 is three blocks of 8; with the current block forced, a token of
    the third block chooses between the first two (or takes the forced
    ones alone), one choice per KV head."""
    spec = {**SPEC, "init_blocks": init_blocks, "topk": topk}
    rng = np.random.default_rng(3)
    q = rng.normal(size=(H, 24, DH))
    k = rng.normal(size=(KV, 24, DH))
    kp = ref.pooled_keys(spec, jnp.asarray(k[None], jnp.float32))
    qg = jnp.asarray(q.reshape(1, KV, H // KV, 24, DH), jnp.float32)
    got = np.asarray(ref.selected_blocks(spec, qg, kp, jnp.arange(24), 3))[0]  # [KV, 24, 3]
    seen = set()
    for i in range(24):
        want = selection_by_hand(spec, q, k, i)
        for kv in range(KV):
            assert set(np.flatnonzero(got[kv, i])) == want[kv], (i, kv)
            if i >= 16:
                seen.add(tuple(sorted(want[kv])))
    if (init_blocks, topk) == (0, 2):
        assert {(0, 2), (1, 2)} <= seen  # both choices occur: the scores decide


def test_forced_blocks_are_always_selected():
    spec = {**SPEC, "window": 16, "topk": 4, "init_blocks": 1}
    rng = np.random.default_rng(5)
    t = 80
    q = jnp.asarray(rng.normal(size=(1, KV, H // KV, t, DH)), jnp.float32)
    kp = ref.pooled_keys(spec, jnp.asarray(rng.normal(size=(1, KV, t, DH)), jnp.float32))
    got = np.asarray(ref.selected_blocks(spec, q, kp, jnp.arange(t), 10))[0]
    for i in range(t):
        cur = i // 8
        for kv in range(KV):
            assert got[kv, i, 0] and got[kv, i, cur] and got[kv, i, max(cur - 1, 0)]
            assert got[kv, i].sum() == min(cur + 1, 4) and not got[kv, i, cur + 1:].any()


def test_whole_forward_runs_and_the_lowered_reading_differs():
    params = weights()
    toks = jnp.asarray(np.random.default_rng(7).integers(0, 10, size=(1, 30)))
    with jax.default_matmul_precision("highest"):
        full = ref.forward(SPEC, params, toks)
        low = ref.forward({**SPEC, "matmul_dtype": "float8_e4m3fn"}, params, toks)
        x = ref.embed(SPEC, params, toks)
        for i, kind in enumerate(SPEC["layer_types"]):
            x = ref.block(SPEC, kind, params["params"][f"block_{i}"], x)
        cols = ref.logits(SPEC, params, x, columns=(4, 3))
    assert full.shape == (1, 30, 10) and bool(jnp.isfinite(full).all())
    np.testing.assert_allclose(cols, full[..., 4:7], atol=1e-6)
    assert 1e-4 < float(jnp.abs(low - full).max()) < 1.0
