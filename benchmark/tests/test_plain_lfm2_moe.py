"""The plain reference of the gated short-convolution mixture of experts: one
block of each kind against a token-by-token Python loop of the same equations
(the conv an explicit three-term sum over a list, the router a sort, the bias
in the selection only, the chosen's sum + 1e-6), the interface
``kinds/serve_ref.py`` drives, the two-limit kind, the new metrics' readings of synthetic captures,
and the new cell's rehearsal end to end."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench_paths import BENCH, ROOT
from readers import scope_share, xplane
from reference import plain_lfm2_moe as ref

H, KV, DH, D, E, W, DENSE, V = 4, 2, 4, 8, 6, 5, 7, 10
SPEC = dict(layer_types=("gated_conv", "softmax", "gated_conv"), conv_layers="gated_conv",
            n_heads=H, n_kv_heads=KV, head_dim=DH, rope_base=1e6, norm_eps=1e-5, top_k=2,
            route_scale=1.0, gate_eps=1e-6, query_tile=7)
T = 17
CELL = "lfm2_8b_a1b.serve_batch"


def weights(seed=0):
    rng = np.random.default_rng(seed)
    w = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)  # noqa: E731
    attn = {"wq": {"kernel": w(D, H * DH)}, "wk": {"kernel": w(D, KV * DH)},
            "wv": {"kernel": w(D, KV * DH)}, "wo": {"kernel": w(H * DH, D)},
            "q_norm": {"scale": 1 + w(DH)}, "k_norm": {"scale": 1 + w(DH)}}
    conv = lambda: {"in_proj": {"kernel": w(D, 3 * D)}, "conv": w(3, D), "wo": {"kernel": w(D, D)}}  # noqa: E731
    dense = {"gate": {"kernel": w(D, DENSE)}, "up": {"kernel": w(D, DENSE)}, "down": {"kernel": w(DENSE, D)}}
    moe = lambda: {"router": {"kernel": w(D, E)}, "router_bias": 0.3 * w(E),  # noqa: E731
                   "experts_gate": w(E, D, W), "experts_up": w(E, D, W), "experts_down": w(E, W, D)}
    norms = lambda: {n: {"scale": 1 + w(D)} for n in ("norm1", "norm2")}  # noqa: E731
    blocks = {"block_0": {"attn": conv(), "mlp": dense, **norms()},
              "block_1": {"attn": attn, "mlp": moe(), **norms()},
              "block_2": {"attn": conv(), "mlp": moe(), **norms()}}
    return {"params": {"embed": {"embedding": w(V, D)}, **blocks, "final_norm": {"scale": 1 + w(D)}}}


def hidden(t, seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(1, t, D)), jnp.float32)


def block_by_hand(spec, kind, blk, x):
    """The docstring's equations in loops, float64, one token (and one head)
    at a time."""
    g64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    x = g64(x)[0]
    t = x.shape[0]
    norm = lambda y, w: y / np.sqrt((y * y).mean() + spec["norm_eps"]) * g64(w)  # noqa: E731
    sigmoid = lambda y: 1 / (1 + np.exp(-y))  # noqa: E731
    silu = lambda y: y * sigmoid(y)  # noqa: E731
    p = blk["attn"]
    z = [norm(x[i], blk["norm1"]["scale"]) for i in range(t)]
    if kind in spec["conv_layers"].split(","):
        taps, vs, op = g64(p["conv"]), [], []
        for i in range(t):
            proj = z[i] @ g64(p["in_proj"]["kernel"])
            b, c, u = proj[:D], proj[D:2 * D], proj[2 * D:]
            vs.append(b * u)
            before = lambda n: vs[i - n] if i - n >= 0 else np.zeros(D)  # noqa: E731
            s = taps[0] * before(2) + taps[1] * before(1) + taps[2] * vs[i]  # no bias, no activation
            op.append((c * s) @ g64(p["wo"]["kernel"]))
    else:
        def rot(y, pos):
            out = y.copy()
            for j in range(y.shape[0] // 2):  # pair (2j, 2j + 1)
                ang = pos * spec["rope_base"] ** (-2 * j / y.shape[0])
                out[2 * j] = y[2 * j] * np.cos(ang) - y[2 * j + 1] * np.sin(ang)
                out[2 * j + 1] = y[2 * j] * np.sin(ang) + y[2 * j + 1] * np.cos(ang)
            return out

        zs = np.stack(z)
        q, k, v = (zs @ g64(p[n]["kernel"]) for n in ("wq", "wk", "wv"))
        qs = [[rot(norm(q[i, h * DH:(h + 1) * DH], p["q_norm"]["scale"]), i) for h in range(H)]
              for i in range(t)]  # the norm, THEN the rotation
        ks = [[rot(norm(k[i, h * DH:(h + 1) * DH], p["k_norm"]["scale"]), i) for h in range(KV)]
              for i in range(t)]
        op = []
        for i in range(t):
            merged = np.zeros(H * DH)
            for h in range(H):
                kv = h // (H // KV)
                s = np.array([qs[i][h] @ ks[j][kv] for j in range(i + 1)]) / np.sqrt(DH)
                pr = np.exp(s - s.max())
                pr /= pr.sum()
                merged[h * DH:(h + 1) * DH] = sum(w * v[j, kv * DH:(kv + 1) * DH] for j, w in enumerate(pr))
            op.append(merged @ g64(p["wo"]["kernel"]))  # no gate
    hid = x + np.stack(op)
    m = blk["mlp"]
    swiglu = lambda y, a, b, c: (silu(y @ g64(a)) * (y @ g64(b))) @ g64(c)  # noqa: E731
    out = np.zeros((t, D))
    for i in range(t):
        y = norm(hid[i], blk["norm2"]["scale"])
        if "router" not in m:
            f = swiglu(y, m["gate"]["kernel"], m["up"]["kernel"], m["down"]["kernel"])
        else:
            s = sigmoid(y @ g64(m["router"]["kernel"]))
            order = sorted(range(E), key=lambda e: -(s[e] + float(m["router_bias"][e])))  # the bias chooses ...
            chosen = order[:spec["top_k"]]
            total = sum(s[e] for e in chosen) + spec["gate_eps"]  # ... and is in no weight
            f = sum(spec["route_scale"] * s[e] / total * swiglu(
                y, m["experts_gate"][e], m["experts_up"][e], m["experts_down"][e]) for e in chosen)
        out[i] = hid[i] + f
    return out[None]


@pytest.mark.parametrize("index,kind", [(0, "gated_conv"), (1, "softmax"), (2, "gated_conv")])
def test_a_block_of_each_kind_is_the_equations_in_loops(index, kind):
    """A dense conv block, an expert attention block and an expert conv
    block."""
    blk, x = weights()["params"][f"block_{index}"], hidden(T)
    with jax.default_matmul_precision("highest"):
        got = ref.block(SPEC, kind, blk, x)
    np.testing.assert_allclose(got, block_by_hand(SPEC, kind, blk, x), atol=2e-4, rtol=2e-4)


def test_the_conv_is_causal_three_taps_and_bare():
    """An impulse at row 5 of ``v`` comes out at rows 5, 6, 7 weighed by taps
    2, 1, 0, negative values pass (no activation) and nothing comes before."""
    v = jnp.zeros((1, 12, 3)).at[0, 5].set(jnp.array([1.0, -2.0, 0.5]))
    w = jnp.asarray([[0.1, 0.2, 0.3], [1.0, 2.0, 3.0], [10.0, 20.0, 30.0]])
    s = ref.short_conv(v, w)
    np.testing.assert_allclose(s[0, 5], v[0, 5] * w[2])
    np.testing.assert_allclose(s[0, 6], v[0, 5] * w[1])
    np.testing.assert_allclose(s[0, 7], v[0, 5] * w[0])
    assert not bool(s[0, :5].any()) and not bool(s[0, 8:].any()) and float(s[0, 5, 1]) == -40.0


def test_the_bias_is_seen_and_is_in_no_weight():
    p = weights()["params"]["block_1"]["mlp"]
    x = hidden(T)[0]
    w = ref.routing_weights(SPEC, p, x)
    plain = ref.routing_weights(SPEC, {**p, "router_bias": jnp.zeros((E,))}, x)
    assert bool(((w > 0) != (plain > 0)).any())  # it moves the chosen set
    s = jax.nn.sigmoid(x @ p["router"]["kernel"])
    picked = jnp.where(w > 0, s, 0.0).sum(-1)
    np.testing.assert_allclose(w.sum(-1), picked / (picked + 1e-6), rtol=1e-6)  # the sum + 1e-6
    assert bool(((w > 0).sum(-1) == SPEC["top_k"]).all())
    both = (w > 0) & (plain > 0)  # the same expert chosen twice weighs its score, bias or not
    ratio = jnp.where(both, w / jnp.where(both, s, 1.0), 0.0)
    assert bool((jnp.abs(ratio - jnp.max(ratio, -1, keepdims=True)) * both < 1e-5).all())
    # every expert computed for every token and masked == the chosen alone (the loop above)


def test_tiling_head_blocks_and_the_lowered_reading():
    """``query_tile`` moves nothing; the tied head in ``columns`` blocks of the
    embedding's rows is the whole head; ``matmul_dtype`` moves the logits."""
    params = weights()
    toks = jnp.asarray(np.random.default_rng(2).integers(0, V, size=(1, T)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        whole = ref.forward(SPEC, params, toks)
        np.testing.assert_allclose(ref.forward({**SPEC, "query_tile": 128}, params, toks), whole, atol=1e-5)
        x = ref.embed(SPEC, params, toks)
        for i, kind in enumerate(SPEC["layer_types"]):
            x = ref.block(SPEC, kind, params["params"][f"block_{i}"], x)
        blocks = [ref.logits(SPEC, params, x, columns=(start, 5)) for start in (0, 5)]
        np.testing.assert_allclose(jnp.concatenate(blocks, -1), whole, atol=1e-5)
        low = ref.forward({**SPEC, "matmul_dtype": "float8_e4m3fn"}, params, toks)
    assert whole.shape == (1, T, V) and float(jnp.abs(low - whole).max()) > 0.05


# -- the new metrics on synthetic captures ----------------------------------------------

_COPY = "copy.4 -> bf16[128,8,2560,64]"


def _args(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)["args"]


def test_the_cache_copy_share_counts_whole_cache_copies_by_name_and_shape():
    device, t = [], 0.0
    for _ in range(6):  # six caches a scan
        device.append([_COPY, t, 12e3])
        device.append(["copy.9 -> bf16[128,2,2048]", t + 12e3, 4e3])  # a tail: 2,048 wide, not a cache
        device.append(["fusion.7 -> bf16[128,8,2560,64]", t + 16e3, 32e3])  # a cache, not a copy
        t += 48e3
    ev = {"device_kind": "TPU v5 lite", "rehearse": False, "window_s": 50.0,
          "xplane": {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": device}]}]}}
    assert xplane.read(ev, **_args("cache_copy_time_share.batch")) == pytest.approx(25.0)


def test_a_program_without_the_layer_reads_nothing_or_zero():
    """The parent's program under this PR's benchmark files: no such scope,
    so the scope shares are left out and nothing raises; a capture without a
    whole-cache copy reads 0, and no capture reads nothing."""
    bare = {"device_kind": "TPU v5 lite", "rehearse": False, "window_s": 50.0,
            "xplane": {"planes": [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Ops", "events": [["fusion -> f32[8]", 0.0, 1e6]]}]}]}}
    assert xplane.read(bare, **_args("cache_copy_time_share.batch")) == 0.0
    assert xplane.read({"device_kind": "cpu", "rehearse": True},
                       **_args("cache_copy_time_share.batch")) is None
    for metric in ("gated_conv_time_share.batch", "gated_conv_step_time_share.batch"):
        assert scope_share.read({}, **_args(metric)) is None
        assert scope_share.read({"scoped_ops": {"source": "hlo_text", "events": [
            ["jit(x)/while/body/ssm/short_conv/mul", 0.0, 1e6]]}}, **_args(metric)) == 0.0


def test_the_scope_shares_tell_the_steps_from_the_pieces():
    """Name stacks as the boundary programs' HLO text carries them: the
    mixer's scope in a piece, and inside the decode scan's while body."""
    piece = ("jit(_prefill_piece_donated_jit)/TransformerLM.prefill_extend_group/block_3.prefill_extend_group/"
             "block_3._sublayer/attn.prefill_extend/gated_conv/attn._conv/gated_conv_conv/short_conv_fwd/pallas_call")
    step = ("jit(_decode_scan_donated_jit)/while/body/closed_call/TransformerLM.decode_step/block_3.decode_step/"
            "block_3._sublayer/attn.decode_step/gated_conv/attn._output/gated_conv_out/wo/dot_general")
    delta = "jit(_decode_scan_donated_jit)/while/body/closed_call/block_0/attn.decode_step/gated_delta/short_conv/mul"
    other = "jit(_decode_scan_donated_jit)/while/body/closed_call/block_1/mlp/moe_experts/gmm_live/pallas_call"
    ev = {"scoped_ops": {"source": "hlo_text", "events": [
        [piece, 0.0, 3e6], [step, 3e6, 1e6], [delta, 4e6, 2e6], [other, 6e6, 4e6]]}}
    assert scope_share.read(ev, **_args("gated_conv_time_share.batch")) == pytest.approx(40.0)
    assert scope_share.read(ev, **_args("gated_conv_step_time_share.batch")) == pytest.approx(10.0)
    with open(os.path.join(BENCH, "layer_metrics", "short_conv_time_share.train.json")) as f:
        old = json.load(f)["args"]  # the delta-rule conv's metric reads what it read, and not the new scope
    assert scope_share.read(ev, **old) == pytest.approx(20.0)


# -- the kind: two limits -----------------------------------------------------------------


def _fake_run(trace, **reference):
    import harness

    ref = {"served_gap_tolerance": 1.25, "served_choice_share_floor": 0.25, "check_requests": 3,
           "check_long_prompts": 1, "check_long_answers": 1, "lowered": "float8_e4m3fn",
           "constants": {"query_tile": 128}, **reference}
    return harness.Run(root="", t0=0.0, seed=0, seconds=1.0, trace=trace, rehearse=True, cell={},
                       workload={}, config={"reference": ref}, device={})


def _fake_make_check(readings, seen):
    """``serve_ref.make_check``'s shape: the check's dict is looked up by the
    precision its run's reference states and by whether the answers are the
    requests' own."""
    def make_check(run, keep):
        ref = run.config["reference"]
        seen.append((run.trace, ref.get("lowered"), ref["constants"].get("matmul_dtype"),
                     ref["check_requests"]))

        def check(params, cfg, served, length):
            own = all(a == ("answer", p[1]) for p, a in served)
            key = ref["constants"].get("matmul_dtype", "sound") if own else "swapped"
            gap, share = readings[key]
            return {"requests": len(served), "max_gap": gap, "reference_choice_share": share,
                    "ok": gap <= ref["served_gap_tolerance"]}
        return check
    return make_check


SERVED = [(("prompt", i), ("answer", i)) for i in range(3)]
SOUND, LOW, SWAPPED = (0.88, 0.47), (1.20, 0.05), (1.9, 0.0)


@pytest.mark.parametrize("sound,tolerance,ok,by", [
    ((0.88, 0.47), 1.25, True, []),
    ((1.20, 0.47), 1.25, True, []),  # what a limit on the largest gap alone lets through
    ((1.20, 0.47), 1.1, False, ["served_gap_tolerance"]),
    ((0.88, 0.20), 1.25, False, ["served_choice_share_floor"]),
    ((1.30, 0.10), 1.25, False, ["served_gap_tolerance", "served_choice_share_floor"]),
])
def test_the_choice_kind_judges_by_both_limits(sound, tolerance, ok, by):
    import harness

    kind = harness.load_module("kinds", "serve_ref_tied_choice")
    run = _fake_run(False, served_gap_tolerance=tolerance)
    seen = []
    make = _fake_make_check({"sound": sound}, seen)
    from kinds import serve_ref  # ``pick`` as the harness has it

    out = kind.two_limit_check(make, serve_ref.pick, run, {})(None, None, SERVED, 8)
    assert out["ok"] is ok and kind.judged(out, run.config["reference"]) == by
    assert out["served_choice_share_floor"] == 0.25 and "lowered" not in out and "swapped" not in out
    assert seen == [(False, None, None, 3)]  # one reading, serve_ref's own lowered one not taken


def test_the_choice_kind_reads_the_lower_precision_and_foreign_answers_when_traced():
    """A traced run: the float8 reading over ALL the checked requests is under
    the largest gap's limit and refused by the share alone; foreign answers
    are refused by both; neither decides ``ok``."""
    import harness
    from kinds import serve_ref

    kind = harness.load_module("kinds", "serve_ref_tied_choice")
    run, seen, keep = _fake_run(True), [], {}
    make = _fake_make_check({"sound": SOUND, "float8_e4m3fn": LOW, "swapped": SWAPPED}, seen)
    out = kind.two_limit_check(make, serve_ref.pick, run, keep)(None, None, SERVED, 8)
    assert out["ok"] is True
    assert out["lowered"] == {"matmul_dtype": "float8_e4m3fn", "requests": 3, "max_gap": 1.20,
                              "reference_choice_share": 0.05, "refused": True,
                              "refused_by": ["served_choice_share_floor"]}
    assert out["swapped"]["refused_by"] == ["served_gap_tolerance", "served_choice_share_floor"]
    # the run's own check keeps the trace; the readings beside it do not
    assert seen == [(True, None, None, 3), (False, None, "float8_e4m3fn", 3), (False, None, None, 3)]
    assert run.config["reference"]["lowered"] == "float8_e4m3fn"  # the run's own files untouched


# -- the cell, end to end at tiny sizes ------------------------------------------------


def test_the_cell_rehearses_and_prints_every_metric_it_is_listed_under(tmp_path):
    """``--rehearse --trace 1``: the served kind, the reference the
    configuration's file names, the check on what was served in the window;
    every metric whose ``workloads`` lists the cell (or that lists none) is in
    the result or in a ``skipped`` line."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", str(2 ** 31 + 55),
         "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.strip().splitlines() if l.startswith("{")]
    result = lines[-1]
    assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0
    skipped = {l["metric"] for l in lines if "skipped" in l and "metric" in l}
    assert any(e["name"] == "serve_tok_s" and CELL in e["workloads"] for e in manifest["end_to_end"])
    listed = [e["name"] for e in manifest["per_layer"]  # a traced run prints the per-layer metrics
              if "workloads" not in e or CELL in e["workloads"]]
    assert {"gated_conv_time_share.batch", "gated_conv_step_time_share.batch",
            "cache_copy_time_share.batch", "moe_rows_dropped.batch"} <= set(listed)
    for name in listed:
        assert name in result["metrics"] or name in skipped, name
    assert result["metrics"]["moe_rows_dropped.batch"]["value"] == 0
    check = next(l["check"] for l in lines if "check" in l)
    assert check["ok"] and check["requests"] > 0 and check["reference_choice_share"] >= check[
        "served_choice_share_floor"]
    low, swapped = check["lowered"], check["swapped"]
    assert low["matmul_dtype"] == "float8_e4m3fn" and low["requests"] == check["requests"]
    # tiny float32 sizes: which limit refuses is the chip's to say (the configuration's file has
    # the readings); here both readings come out not correct and pick the reference's id less often
    assert low["refused"] and swapped["refused"]
    assert max(low["reference_choice_share"], swapped["reference_choice_share"]) < check["reference_choice_share"]
