"""The reference-by-configuration train kind, its scope reader and the
plain delta-rule / held-experts reference, tiny, CPU, fp32."""

import dataclasses
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import pytest

import harness
from bench_paths import BENCH, ROOT
from reference import plain_gdn_moe as ref


def new_config():
    """The one configuration whose file names a reference module."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = json.load(f)["configs"]
    for entry in entries:
        with open(os.path.join(ROOT, entry["file"])) as f:
            spec = json.load(f)
        if "reference" in spec:
            return entry, spec
    raise AssertionError("no configuration names a reference")


def tiny_run(trace=False):
    entry, spec = new_config()
    return harness.Run(root=ROOT, t0=0.0, seed=0, seconds=1.0, trace=trace, rehearse=True,
                       cell={"config": entry["name"], "chips": 1}, workload={}, config=spec,
                       device={"kind": "cpu"})


@pytest.fixture(scope="module")
def tiny_model():
    from orion_tpu.models.transformer import TransformerLM

    run = tiny_run()
    cfg = harness.model_config(run, max_seq_len=64)
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 48), 0, cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.key(0), toks)
    return run, cfg, model, params, toks


def test_reference_named_by_the_file_matches_the_program(tiny_model):
    train_ref = harness.load_module("kinds", "train_ref")
    run, _, model, params, toks = tiny_model
    spec = train_ref.reference_spec(run)
    assert spec["router_width"] > spec["experts_held"]  # the cut is in the rehearsal too
    want = ref.forward(spec, params, toks)
    got = model.apply(params, toks)
    assert float(jnp.abs(want).max()) > 1.0
    assert float(jnp.abs(got - want).max()) < 1e-3


def test_lower_precision_reference_lands_outside_the_tolerances(tiny_model):
    """What the tolerances have to refuse: the reference with every matmul
    operand rounded to the configuration's ``lowered`` type."""
    train_ref = harness.load_module("kinds", "train_ref")
    run, _, _, params, toks = tiny_model
    spec = train_ref.reference_spec(run)
    tol = run.config["reference"]
    full = ref.forward(spec, params, toks)
    low = ref.forward({**spec, "matmul_dtype": tol["lowered"]}, params, toks)
    assert float(jnp.abs(low - full).max()) > 10 * 1e-3  # ten times the fp32 agreement above
    assert bool(jnp.isfinite(low).all())


def test_share_sum_of_the_reference():
    """Routed parts of all 8 shares (2 of 16 experts each) + the shared
    expert once = the uncut layer: the reference's side of the cut."""
    d, h, r = 32, 16, 16
    ks = jax.random.split(jax.random.key(0), 8)
    p = {"router": {"kernel": jax.random.normal(ks[0], (d, r))},
         "experts_gate": jax.random.normal(ks[1], (r, d, h)) * d ** -0.5,
         "experts_up": jax.random.normal(ks[2], (r, d, h)) * d ** -0.5,
         "experts_down": jax.random.normal(ks[3], (r, h, d)) * h ** -0.5,
         "shared_gate": {"kernel": jax.random.normal(ks[4], (d, h)) * d ** -0.5},
         "shared_up": {"kernel": jax.random.normal(ks[5], (d, h)) * d ** -0.5},
         "shared_down": {"kernel": jax.random.normal(ks[6], (h, d)) * h ** -0.5},
         "shared_scale": {"kernel": jax.random.normal(ks[7], (d, 1))}}
    x = jax.random.normal(jax.random.key(9), (2, 40, d))
    whole = {"top_k": 3, "experts_held": r, "expert_offset": 0, "router_width": r}
    total = ref.shared_expert(whole, p, x)
    for chip in range(8):
        mine = {**p, **{n: p[n][2 * chip: 2 * chip + 2]
                        for n in ("experts_gate", "experts_up", "experts_down")}}
        total = total + ref.routed_experts(
            {**whole, "experts_held": 2, "expert_offset": 2 * chip}, mine, x)
    assert float(jnp.abs(total - ref.moe(whole, p, x)).max()) < 1e-5
    # the weights of a token's top-k sum to 1 over the whole router
    assert float(jnp.abs(ref.routing_weights(whole, p, x).sum(-1) - 1).max()) < 1e-6


def test_active_params_counts_a_held_expert_at_its_token_share(tiny_model):
    train_ref = harness.load_module("kinds", "train_ref")
    _, cfg, _, params, _ = tiny_model
    leaves = {jax.tree_util.keystr(p): x.size
              for p, x in jax.tree_util.tree_leaves_with_path(params)}
    experts = sum(v for k, v in leaves.items() if "experts_" in k)
    embed = sum(v for k, v in leaves.items() if "'embed'" in k)
    want = (sum(leaves.values()) - experts - embed
            + experts * cfg.moe_top_k / cfg.moe_router_width)
    assert train_ref.active_params(cfg, params) == pytest.approx(want)
    tied = dataclasses.replace(cfg, n_experts=0)
    dense = {"params": {"embed": {"embedding": jnp.zeros((7, 3))}, "w": jnp.zeros((3, 3))}}
    assert train_ref.active_params(tied, dense) == 30.0  # a tied table is the head


def test_scope_share_reads_name_stacks_and_guesses_nothing():
    reader = harness.load_module("readers", "scope_share")
    stack = "jit(_train_step)/transpose(jvp(M.features))/checkpoint/block_0/attn/%s/dot_general"
    events = [
        ["jit(_train_step)/while", 0.0, 100.0],                  # encloses the next two
        [stack % "gated_delta", 0.0, 30.0],
        [stack % "gated_delta/short_conv", 40.0, 10.0],
        ["jit(_train_step)/jvp(moe_route)/top_k", 120.0, 20.0],  # a scope under a transform
        ["", 150.0, 40.0],                                       # no name stack: counted busy
    ]
    evidence = {"scoped_ops": {"source": "hlo_text", "events": events}}
    busy = 100.0 + 20.0 + 40.0
    assert reader.read(evidence, "[/(]gated_delta[/)]") == pytest.approx(100 * 40.0 / busy)
    assert reader.read(evidence, "[/(]moe_(route|experts|shared)[/)]") == pytest.approx(100 * 20.0 / busy)
    assert reader.read(evidence, "[/(]gated_softmax[/)]") == 0.0
    assert reader.read({}, "x") is None
    assert reader.read({"scoped_ops": {"source": None, "events": []}}, "x") is None
    unnamed = {"scoped_ops": {"source": "hlo_text", "events": [["", 0.0, 5.0]]}}
    assert reader.read(unnamed, "x") is None


def test_logit_statistics_by_row_and_over_rows():
    """The mean averages over rows, quantiles and the maximum take the worst
    row; one far-off logit moves the maximum and not what the limits bound."""
    train_ref = harness.load_module("kinds", "train_ref")
    want = jnp.zeros((100, 200))
    rows = [
        {k: float(v) for k, v in train_ref.logit_stats(want + d, want).items()}
        for d in (0.01, jnp.full((100, 200), 0.03).at[5, 7].set(9.0))
    ]
    assert rows[0] == pytest.approx({"mean": 0.01, "p999": 0.01, "p9999": 0.01, "max": 0.01})
    assert rows[1]["max"] == 9.0 and rows[1]["p999"] == pytest.approx(0.03)
    got = train_ref.over_rows(rows)
    assert got["mean"] == pytest.approx((rows[0]["mean"] + rows[1]["mean"]) / 2)
    assert got["p999"] == pytest.approx(0.03) and got["max"] == 9.0


def test_instruction_names_map_to_name_stacks_through_the_hlo_text():
    from orion_tpu.utils.profiling import scope

    train_ref = harness.load_module("kinds", "train_ref")

    def fn(x):
        with scope("gated_delta"):
            return jnp.tanh(x @ x).sum()

    text = jax.jit(jax.grad(fn)).lower(jnp.ones((8, 8))).compile().as_text()
    stacks = dict(train_ref.OP_NAME.findall(text))
    assert any("gated_delta" in v for v in stacks.values())
    assert train_ref.instruction("%fusion.12 = bf16[8,128]{1,0} fusion(%p), kind=kLoop") == "fusion.12"
    # no capture in the directory: nothing to read, nothing raised
    out = train_ref.scoped_ops(os.path.join(BENCH, "no-such-dir"), lambda: text)
    assert out == {"source": None, "events": []}


def test_new_cell_rehearses_end_to_end(tmp_path):
    entry, _ = new_config()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = next(w for w in json.load(f)["workloads"] if w["config"] == entry["name"])
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell["name"], "--seed", str(2**31 + 5),
         "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()]
    result = lines[-1]
    assert result["correct"] and result["failed"] == 0
    check = next(l["check"] for l in lines if "check" in l)
    # the batch of the timed shape, every row against the reference's own row
    assert check["rows"] == len(check["logit_diff_by_row"]) > 1
    for stat in ("mean", "p999", "p9999", "max"):
        assert check["logit_diff"][stat] < 1e-3 < check["lowered"]["logit_diff"][stat] * 50
    assert check["logit_diff"]["mean"] <= check["logit_diff"]["p999"] <= check["logit_diff"]["max"]
    counters = next(l["counters"] for l in lines if "counters" in l)
    assert counters["moe_overflow"] == 0 < counters["moe_rows_held"] < counters["moe_rows_routed"]
    shares = [v["value"] for k, v in result["metrics"].items() if "held_row_share" in k]
    assert shares and 0 < shares[0] < 100
