"""The plain reference of the latent-attention mixture of experts against
forms written another way: latent attention against loops over heads and
positions in float64, the rotary by hand, the router against a sort, the
16 shares of the routed sum against the uncut layer, and the configuration's
file against the kind of run that reads it."""

import json
import os

import numpy as np

import jax.numpy as jnp

import harness
from bench_paths import BENCH, ROOT
from reference import plain_openpangu_moe as ref

H, DN, DR, DV, R, QR, D, E, HID = 3, 4, 4, 5, 6, 7, 8, 8, 5
SPEC = dict(layer_types=("latent", "latent"), n_heads=H, q_rank=QR, kv_rank=R, nope=DN, rope=DR,
            value=DV, rotary_base=100.0, norm_eps=1e-5, top_k=3, experts_held=E, expert_offset=0,
            router_width=E, route_scale=2.5)


def weights(seed=0):
    rng = np.random.default_rng(seed)
    w = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)  # noqa: E731
    attn = {"wq_a": {"kernel": w(D, QR)}, "q_norm": {"scale": 1 + w(QR)},
            "wq_b": {"kernel": w(QR, H * (DN + DR))}, "wkv_a": {"kernel": w(D, R + DR)},
            "kv_norm": {"scale": 1 + w(R)}, "wkv_b": w(R, H * (DN + DV)),
            "wo": {"kernel": w(H * DV, D)}}
    moe = {"router": {"kernel": w(D, E)}, "experts_gate": w(E, D, HID), "experts_up": w(E, D, HID),
           "experts_down": w(E, HID, D), "shared_gate": {"kernel": w(D, HID)},
           "shared_up": {"kernel": w(D, HID)}, "shared_down": {"kernel": w(HID, D)}}
    dense = {n: {"kernel": w(*s)} for n, s in (("gate", (D, 9)), ("up", (D, 9)), ("down", (9, D)))}
    norms = lambda: {n: {"scale": 1 + w(D)} for n in ("norm1", "post_norm1", "norm2", "post_norm2")}  # noqa: E731
    return attn, moe, dense, norms


def test_latent_attention_against_loops():
    attn, _, _, _ = weights()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 6, D)), jnp.float32)
    got = np.asarray(ref.latent_attention(SPEC, attn, x))[0]
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    xs = f(x)[0]
    rms = lambda v, w: v / np.sqrt((v ** 2).mean(-1, keepdims=True) + 1e-5) * f(w)  # noqa: E731

    def rot(v, p):  # pairs (2j, 2j + 1) by p * base^(-2j / rope)
        out = v.copy()
        for j in range(DR // 2):
            a = p * 100.0 ** (-2 * j / DR)
            out[2 * j] = v[2 * j] * np.cos(a) - v[2 * j + 1] * np.sin(a)
            out[2 * j + 1] = v[2 * j] * np.sin(a) + v[2 * j + 1] * np.cos(a)
        return out

    q = (rms(xs @ f(attn["wq_a"]["kernel"]), attn["q_norm"]["scale"])
         @ f(attn["wq_b"]["kernel"])).reshape(6, H, DN + DR)
    kva = xs @ f(attn["wkv_a"]["kernel"])
    c = rms(kva[:, :R], attn["kv_norm"]["scale"])
    kv = (c @ f(attn["wkv_b"])).reshape(6, H, DN + DV)
    want = np.zeros((6, H, DV))
    for h in range(H):
        for t in range(6):
            s = np.array([q[t, h, :DN] @ kv[u, h, :DN]
                          + rot(q[t, h, DN:], t) @ rot(kva[u, R:], u) for u in range(t + 1)])
            p = np.exp((s - s.max()) / np.sqrt(DN + DR))
            want[t, h] = (p / p.sum()) @ kv[:t + 1, h, DN:]
    want = want.reshape(6, H * DV) @ f(attn["wo"]["kernel"])
    assert np.abs(got - want).max() < 2e-5


def test_router_against_a_sort():
    _, moe, _, _ = weights()
    x = jnp.asarray(np.random.default_rng(2).normal(size=(5, D)), jnp.float32)
    got = np.asarray(ref.routing_weights(SPEC, moe, x))
    s = 1 / (1 + np.exp(-np.asarray(x, np.float64) @ np.asarray(moe["router"]["kernel"], np.float64)))
    for t in range(5):
        top = np.argsort(-s[t])[:3]
        want = np.zeros(E)
        want[top] = 2.5 * s[t, top] / s[t, top].sum()
        assert np.abs(got[t] - want).max() < 1e-6
        assert abs(got[t].sum() - 2.5) < 1e-5


def test_shares_add_up_to_the_uncut_layer():
    """4 shares of 2 experts: their routed parts plus the shared expert
    counted once are the layer with all 8 held."""
    _, moe, _, _ = weights()
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 7, D)), jnp.float32)
    whole = ref.mlp(SPEC, moe, x)
    total = ref.shared_expert(SPEC, moe, x)
    for chip in range(4):
        mine = {**moe, **{n: moe[n][2 * chip:2 * chip + 2]
                          for n in ("experts_gate", "experts_up", "experts_down")}}
        spec = {**SPEC, "experts_held": 2, "expert_offset": 2 * chip}
        total = total + ref.routed_experts(spec, mine, x)
        # a share alone is NOT the layer
        assert float(jnp.abs(ref.mlp(spec, mine, x) - whole).max()) > 1e-3
    assert float(jnp.abs(total - whole).max()) < 1e-5


def test_block_is_a_sandwich_and_the_first_layer_is_dense():
    attn, moe, dense, norms = weights()
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 5, D)), jnp.float32)
    for mlp in (dense, moe):
        blk = {"attn": attn, "mlp": mlp, **norms()}
        h = x + ref.rms(SPEC, ref.latent_attention(SPEC, attn, ref.rms(SPEC, x, blk["norm1"]["scale"])),
                        blk["post_norm1"]["scale"])
        want = h + ref.rms(SPEC, ref.mlp(SPEC, mlp, ref.rms(SPEC, h, blk["norm2"]["scale"])),
                           blk["post_norm2"]["scale"])
        assert float(jnp.abs(ref.block(SPEC, "latent", blk, x) - want).max()) < 1e-6
    # causal: a later token changes nothing before it
    blk = {"attn": attn, "mlp": moe, **norms()}
    y = ref.block(SPEC, "latent", blk, x)
    y2 = ref.block(SPEC, "latent", blk, x.at[:, -1].add(1.0))
    assert float(jnp.abs(y[:, :-1] - y2[:, :-1]).max()) == 0.0


def test_lowered_matmuls_move_the_result_and_leave_the_router():
    attn, moe, _, _ = weights()
    x = jnp.asarray(np.random.default_rng(5).normal(size=(1, 5, D)), jnp.float32)
    low = {**SPEC, "matmul_dtype": "float8_e4m3fn"}
    assert float(jnp.abs(ref.latent_attention(low, attn, x) - ref.latent_attention(SPEC, attn, x)).max()) > 1e-3
    assert float(jnp.abs(ref.routing_weights(low, moe, x) - ref.routing_weights(SPEC, moe, x)).max()) == 0.0


def test_the_configuration_names_this_reference_and_its_sizes():
    from kinds import serve_ref

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"] if w["name"].startswith("openpangu"))
    config = harness.load_json(os.path.join(BENCH, "configs", cell["config"] + ".json"))
    assert config["reference"]["module"] == "plain_openpangu_moe"
    assert config["reduced"] == ["n_layers", "n_experts", "vocab_size"]
    for rehearse, heads, held in ((False, 128, 16), (True, 4, 4)):
        run = harness.Run(root=ROOT, t0=0.0, seed=0, seconds=1.0, trace=False, rehearse=rehearse,
                          cell=cell, workload={}, config=config, device={})
        spec = serve_ref.reference_spec(run)
        assert spec["n_heads"] == heads and spec["experts_held"] == held
        assert spec["route_scale"] == 2.5 and spec["head_block"] == 8
    model = config["model"]
    # every published width, under the published keys and in the model as run
    for pub, key in (("hidden_size", "d_model"), ("q_lora_rank", "latent_q_rank"),
                     ("kv_lora_rank", "latent_kv_rank"), ("qk_nope_head_dim", "latent_nope_dim"),
                     ("qk_rope_head_dim", "latent_rope_dim"), ("v_head_dim", "latent_value_dim"),
                     ("intermediate_size", "mlp_hidden"), ("moe_intermediate_size", "moe_hidden"),
                     ("num_attention_heads", "n_heads"), ("num_experts_per_tok", "moe_top_k"),
                     ("n_routed_experts", "moe_router_width")):
        assert config[pub] == model[key], (pub, key)
