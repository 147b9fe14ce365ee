"""The plain reference of the state-space / attention / latent-mixture hybrid
against forms written another way: the state-space layer against loops over
heads and positions in float64 (the norm by groups among them), attention
against loops, the router against a sort with the bias by hand, the four
shares of the routed sum against the uncut layer, the block form against the
published one-step-a-layer form, the roofline's byte count against the
issue's arithmetic, and the configuration's file against the kind of run that
reads it."""

import json
import os

import numpy as np

import jax.numpy as jnp

import harness
from bench_paths import BENCH, ROOT
from reference import plain_nemotron_h as ref

H, P, N, G, W, D = 4, 3, 5, 2, 4, 8  # state-space heads x width, state, groups, taps; model
QH, KV, DH = 4, 2, 3  # attention
R, E, LAT, HID, SH = 8, 8, 6, 5, 7  # router, experts held, latent, expert and shared widths
SPEC = dict(layer_types=("ssm", "ssm", "softmax"), n_heads=QH, n_kv_heads=KV, head_dim=DH,
            ssm_heads=H, ssm_head_dim=P, ssm_state=N, ssm_groups=G, norm_eps=1e-5, top_k=3,
            experts_held=E, expert_offset=0, router_width=R, route_scale=5.0)
f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731


def weights(seed=0):
    rng = np.random.default_rng(seed)
    w = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)  # noqa: E731
    c = H * P + 2 * G * N
    ssm = {"in_proj": {"kernel": w(D, 2 * H * P + 2 * G * N + H)}, "conv": w(W, c), "conv_bias": w(c),
           "A_log": jnp.log(jnp.asarray(rng.uniform(1, 4, size=(H,)), jnp.float32)),
           "dt_bias": w(H), "D": 1 + w(H), "out_norm": 1 + w(H * P), "wo": {"kernel": w(H * P, D)}}
    attn = {"wq": {"kernel": w(D, QH * DH)}, "wk": {"kernel": w(D, KV * DH)},
            "wv": {"kernel": w(D, KV * DH)}, "wo": {"kernel": w(QH * DH, D)}}
    moe = {"router": {"kernel": w(D, R)}, "router_bias": 0.3 * w(R),
           "latent_down": {"kernel": w(D, LAT)}, "latent_up": {"kernel": w(LAT, D)},
           "experts_up": w(E, LAT, HID), "experts_down": w(E, HID, LAT),
           "shared_up": {"kernel": w(D, SH)}, "shared_down": {"kernel": w(SH, D)}}
    return ssm, attn, moe, (lambda: {"scale": 1 + w(D)})


def test_state_space_layer_against_loops():
    """Token by token and head by head in float64: the conv with its bias
    before the SiLU, the softplus, the recurrence with the GROUP's B and C,
    the skip, the gate, then the norm over each group's channels."""
    p, _, _, _ = weights()
    t = 7
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, t, D)), jnp.float32)
    got = f64(ref.ssm(SPEC, p, x))[0]
    silu = lambda v: v / (1 + np.exp(-v))  # noqa: E731
    d, c = H * P, H * P + 2 * G * N
    proj = f64(x)[0] @ f64(p["in_proj"]["kernel"])
    z, pre, dt = proj[:, :d], proj[:, d:d + c], proj[:, d + c:]
    conv, bias = f64(p["conv"]), f64(p["conv_bias"])
    xbc = np.zeros_like(pre)
    for i in range(t):
        acc = bias.copy()
        for j in range(W):
            if i - (W - 1) + j >= 0:
                acc = acc + conv[j] * pre[i - (W - 1) + j]
        xbc[i] = silu(acc)
    dt = np.log1p(np.exp(dt + f64(p["dt_bias"])))
    a = -np.exp(f64(p["A_log"]))
    y = np.zeros((t, H, P))
    for h in range(H):
        g = h // (H // G)
        s = np.zeros((P, N))
        for i in range(t):
            xi = xbc[i, h * P:(h + 1) * P]
            bi = xbc[i, d + g * N:d + (g + 1) * N]
            ci = xbc[i, d + G * N + g * N:d + G * N + (g + 1) * N]
            s = np.exp(dt[i, h] * a[h]) * s + dt[i, h] * np.outer(xi, bi)
            y[i, h] = s @ ci + f64(p["D"])[h] * xi
    y = y.reshape(t, d) * silu(z)
    per = d // G
    for g in range(G):
        part = y[:, g * per:(g + 1) * per]
        y[:, g * per:(g + 1) * per] = part / np.sqrt((part ** 2).mean(-1, keepdims=True) + 1e-5)
    want = (y * f64(p["out_norm"])) @ f64(p["wo"]["kernel"])
    assert np.abs(got - want).max() < 2e-5
    # ONE norm over all channels is another layer
    whole = {**SPEC, "ssm_groups": 1}
    assert float(jnp.abs(ref.gated_group_norm(SPEC, jnp.asarray(y), jnp.asarray(z), p["out_norm"])
                         - ref.gated_group_norm(whole, jnp.asarray(y), jnp.asarray(z), p["out_norm"])).max()) > 1e-2


def test_attention_against_loops():
    _, p, _, _ = weights()
    t = 6
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, t, D)), jnp.float32)
    got = f64(ref.full_attention(SPEC, p, x))[0]
    blocked = f64(ref.full_attention({**SPEC, "head_block": 1}, p, x))[0]
    xs = f64(x)[0]
    q = (xs @ f64(p["wq"]["kernel"])).reshape(t, QH, DH)
    k = (xs @ f64(p["wk"]["kernel"])).reshape(t, KV, DH)
    v = (xs @ f64(p["wv"]["kernel"])).reshape(t, KV, DH)
    want = np.zeros((t, QH, DH))
    for h in range(QH):
        g = h // (QH // KV)
        for i in range(t):
            s = np.array([q[i, h] @ k[u, g] for u in range(i + 1)]) / np.sqrt(DH)
            pr = np.exp(s - s.max())
            want[i, h] = (pr / pr.sum()) @ v[:i + 1, g]
    want = want.reshape(t, QH * DH) @ f64(p["wo"]["kernel"])
    assert np.abs(got - want).max() < 2e-5 and np.abs(blocked - got).max() < 1e-6


def test_router_against_a_sort_with_the_bias_by_hand():
    """Chosen on the scores PLUS the bias, weighted by the scores WITHOUT it,
    the weights of a token summing to the scaling factor."""
    _, _, moe, _ = weights()
    x = jnp.asarray(np.random.default_rng(3).normal(size=(9, D)), jnp.float32)
    got = f64(ref.routing_weights(SPEC, moe, x))
    s = 1 / (1 + np.exp(-f64(x) @ f64(moe["router"]["kernel"])))
    moved = 0
    for t in range(9):
        top = np.argsort(-(s[t] + f64(moe["router_bias"])))[:3]
        want = np.zeros(R)
        want[top] = 5.0 * s[t, top] / s[t, top].sum()
        assert np.abs(got[t] - want).max() < 1e-5 and abs(got[t].sum() - 5.0) < 1e-5
        moved += set(top) != set(np.argsort(-s[t])[:3])
    assert moved > 0  # the bias changes some token's chosen set: the test would see it missing


def test_shares_add_up_to_the_uncut_layer():
    """4 shares of 2 experts: each computes its routed part IN THE LATENT and
    up-projects it; the four partial sums plus the shared expert counted once
    are the layer with all 8 held; and the layer is the formula by hand."""
    _, _, moe, _ = weights()
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 7, D)), jnp.float32)
    whole = ref.latent_experts(SPEC, moe, x)
    total = ref.shared_expert(SPEC, moe, x)
    for chip in range(4):
        mine = {**moe, **{n: moe[n][2 * chip:2 * chip + 2] for n in ("experts_up", "experts_down")}}
        spec = {**SPEC, "experts_held": 2, "expert_offset": 2 * chip}
        total = total + ref.latent_experts(spec, mine, x, shared=False)
        assert float(jnp.abs(ref.latent_experts(spec, mine, x) - whole).max()) > 1e-3  # a share alone is not the layer
    assert float(jnp.abs(total - whole).max()) < 2e-5
    xs, g = f64(x), f64(ref.routing_weights(SPEC, moe, x))
    lat = xs @ f64(moe["latent_down"]["kernel"])
    r = sum(g[..., e:e + 1] * (np.maximum(lat @ f64(moe["experts_up"][e]), 0) ** 2
                               @ f64(moe["experts_down"][e])) for e in range(E))
    want = r @ f64(moe["latent_up"]["kernel"]) + (
        np.maximum(xs @ f64(moe["shared_up"]["kernel"]), 0) ** 2 @ f64(moe["shared_down"]["kernel"]))
    assert np.abs(f64(whole) - want).max() < 5e-5


def test_blocks_are_the_published_steps():
    """A block with a feed-forward part is two published layers, one without
    is one; the flat list of steps gives the block form's logits; causal."""
    ssm, attn, moe, norm = weights()
    rng = np.random.default_rng(5)
    params = {"params": {
        "embed": {"embedding": jnp.asarray(rng.normal(size=(11, D)), jnp.float32)},
        "block_0": {"norm1": norm(), "attn": ssm, "norm2": norm(), "mlp": moe},
        "block_1": {"norm1": norm(), "attn": ssm},
        "block_2": {"norm1": norm(), "attn": attn, "norm2": norm(), "mlp": moe},
        "final_norm": norm(), "lm_head_kernel": jnp.asarray(rng.normal(size=(D, 11)), jnp.float32)}}
    toks = jnp.asarray(rng.integers(0, 11, size=(1, 6)))
    steps = ref.steps_of(SPEC, params)
    assert "".join(letter for letter, _ in steps) == "MEM*E"
    x = ref.embed(SPEC, params, toks)
    blk = params["params"]["block_0"]
    h = x + ref.ssm(SPEC, ssm, ref.rms(SPEC, x, blk["norm1"]["scale"]))
    want = h + ref.latent_experts(SPEC, moe, ref.rms(SPEC, h, blk["norm2"]["scale"]))
    assert float(jnp.abs(ref.block(SPEC, "ssm", blk, x) - want).max()) < 1e-6
    alone = params["params"]["block_1"]
    assert float(jnp.abs(ref.block(SPEC, "ssm", alone, x) - ref.layer(
        SPEC, "M", {"norm": alone["norm1"]["scale"], "f": ssm}, x)).max()) == 0.0
    got = ref.forward(SPEC, params, toks)
    assert float(jnp.abs(got - ref.forward_steps(SPEC, params, toks)).max()) == 0.0
    later = ref.forward(SPEC, params, toks.at[0, -1].set((toks[0, -1] + 1) % 11))
    assert float(jnp.abs(got[:, :-1] - later[:, :-1]).max()) == 0.0
    cols = ref.logits(SPEC, params, x, columns=(3, 4))
    assert float(jnp.abs(cols - ref.logits(SPEC, params, x)[..., 3:7]).max()) < 1e-6


def test_lowered_matmuls_move_the_result_and_leave_the_router():
    ssm, attn, moe, _ = weights()
    x = jnp.asarray(np.random.default_rng(6).normal(size=(1, 5, D)), jnp.float32)
    low = {**SPEC, "matmul_dtype": "float8_e4m3fn"}
    for f, p in ((ref.ssm, ssm), (ref.full_attention, attn), (ref.latent_experts, moe)):
        assert float(jnp.abs(f(low, p, x) - f(SPEC, p, x)).max()) > 1e-3
    assert float(jnp.abs(ref.routing_weights(low, moe, x) - ref.routing_weights(SPEC, moe, x)).max()) == 0.0


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "plain_nemotron_h.py")) as f:
        text = f.read()
    assert "import orion_tpu" not in text and "from orion_tpu" not in text


def test_the_step_rooflines_bytes_are_the_issues():
    """``readers/gmm_roofline.py`` at the cell's widths: with every held
    expert live, 128 x 2 x 5,505,024 B of matrices a layer and step, plus the
    held pairs' rows in and out of both products; the live experts are the
    program's counter less all the held for every piece's layer, so a router
    that leaves held experts empty at a step lowers the bytes, and pieces
    that had fewer than all only lower them further."""
    from readers import gmm_roofline

    widths = dict(top_k=22, held=128, d=1024, h=2688, matrices=2, itemsize=2)
    kernel = dict(d=1024, h=2688, matrices=2, itemsize=2)
    full = gmm_roofline.step_bytes(128, 10_000 * 5.5, **kernel)
    assert abs(full - (128 * 2 * 5_505_024 + 10_000 * 22 * 0.25 * 2 * 2 * (1024 + 2688))) < 1
    assert gmm_roofline.step_bytes(64, 700, **kernel) < 0.51 * gmm_roofline.step_bytes(128, 700, **kernel)
    # 10 boundaries of 8 steps and 5 layers, 41 pieces: 400 layer-steps at 100 live each, and
    # the pieces taken off at 128 a layer though they had 120
    window = {"chunks": 10, "slot_steps_prefilling": 41,
              "moe_experts_live": 400 * 100 + 41 * 5 * 120, "moe_rows_held": 25, "moe_rows_routed": 100}
    live = gmm_roofline.steps_live_experts(window, held=128, layers=5, steps=8)
    assert live == 100 - 41 * 5 * 8 / 400
    assert gmm_roofline.steps_live_experts({**window, "moe_experts_live": 10 ** 9}, 128, 5, 8) == 128.0
    assert gmm_roofline.steps_live_experts({**window, "moe_experts_live": 41 * 5 * 100}, 128, 5, 8) == 0.0
    assert gmm_roofline.steps_live_experts({"chunks": 10}, 128, 5, 8) is None
    # nothing to read: no capture, no counters, no such kernel
    args = json.load(open(os.path.join(BENCH, "layer_metrics", "gmm_step_roofline.latent.json")))["args"]
    assert args["widths"] == widths and args["layers"] == 5 and args["steps"] == 8
    assert gmm_roofline.read({}, **args) is None
    evidence = {"xplane": {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        ["gmm_live.3 tpu_custom_call -> bf16[4864,2688]", 0.0, 1.0e6],
        ["gmm_live.4 tpu_custom_call -> bf16[4864,1024]", 1.0e6, 1.0e6],
        ["gmm_live.5 tpu_custom_call -> bf16[61440,2688]", 2.0e6, 9.0e6]]}]}]},
        "capture": {"emitting_rows_per_boundary": 127.0}, "counters": window,
        "device_kind": "TPU v5 lite", "rehearse": False}
    got = gmm_roofline.read(evidence, **args)
    # two products of one layer-step in 2 ms: the piece's buffer is left out
    assert abs(got - 100 * gmm_roofline.step_bytes(live, 127 * 5.5, **kernel) / 2e-3 / 819e9) < 1e-6
    assert gmm_roofline.read({**evidence, "counters": {}}, **args) is None
    # a program without the live counter (a parent of the PR that brought it) reports nothing
    assert gmm_roofline.read({**evidence, "counters": {k: v for k, v in window.items()
                                                       if k != "moe_experts_live"}}, **args) is None


def test_the_configuration_names_this_reference_and_its_sizes():
    from kinds import serve_ref

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"] if w["name"].startswith("nemotron"))
    config = harness.load_json(os.path.join(BENCH, "configs", cell["config"] + ".json"))
    assert config["reference"]["module"] == "plain_nemotron_h"
    assert config["reduced"] == ["n_layers", "n_experts", "vocab_size"]
    for rehearse, heads, held, width in ((False, 128, 128, 512), (True, 8, 4, 16)):
        run = harness.Run(root=ROOT, t0=0.0, seed=0, seconds=1.0, trace=False, rehearse=rehearse,
                          cell=cell, workload={}, config=config, device={})
        spec = serve_ref.reference_spec(run)
        assert spec["ssm_heads"] == heads and spec["experts_held"] == held
        assert spec["router_width"] == width and spec["expert_offset"] == 0
        assert spec["route_scale"] == 5.0 and spec["head_block"] == 4
        assert tuple(spec["layer_types"]) == ("ssm", "ssm", "ssm", "ssm", "softmax", "ssm")
    model = config["model"]
    # every published width, under the published keys and in the model as run
    for pub, key in (("hidden_size", "d_model"), ("mamba_num_heads", "ssm_heads"),
                     ("mamba_head_dim", "ssm_head_dim"), ("ssm_state_size", "ssm_state"),
                     ("n_groups", "ssm_groups"), ("conv_kernel", "ssm_conv_width"),
                     ("num_attention_heads", "n_heads"), ("num_key_value_heads", "n_kv_heads"),
                     ("head_dim", "head_dim"), ("moe_intermediate_size", "moe_hidden"),
                     ("moe_latent_size", "moe_latent"),
                     ("moe_shared_expert_intermediate_size", "moe_shared_hidden"),
                     ("num_experts_per_tok", "moe_top_k"), ("n_routed_experts", "moe_router_width"),
                     ("routed_scaling_factor", "moe_route_scale"), ("layer_norm_epsilon", "norm_eps")):
        assert config[pub] == model[key], (pub, key)
    assert config["expand"] * config["hidden_size"] == model["ssm_heads"] * model["ssm_head_dim"]
    assert config["mlp_hidden_act"] == "relu2" == model["mlp"]
