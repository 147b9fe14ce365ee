"""The served latent-attention mixture of experts (ISSUE 43): latent
attention over a latent cache (expand in the parallel forms, absorb at the
decode step), sandwich norms, a leading dense layer, a sigmoid top-k router
over a width of which one chip's share of experts is held, an ungated shared
expert, against ``benchmark/reference/plain_openpangu_moe.py``; tiny, CPU,
fp32."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.generate import SampleConfig, generate
from orion_tpu.models.configs import ModelConfig, get_config
from orion_tpu.models.mixers import MIXERS
from orion_tpu.models.moe import STAT_NAMES, MoEMLP, stats_vector
from orion_tpu.models.transformer import TransformerLM, init_decode_state
from orion_tpu.ops import dispatch
from orion_tpu.serving import DecodeRequest, ServeConfig, Server, SlotEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from reference import plain_openpangu_moe as ref  # noqa: E402

# one dense layer then two expert layers; 4 of 16 experts held, top-4
TINY = dict(vocab_size=256, d_model=64, n_layers=3, layer_types=("latent",) * 3,
            n_heads=4, head_dim=24, latent_q_rank=32, latent_kv_rank=16,
            latent_nope_dim=16, latent_rope_dim=8, latent_value_dim=16,
            mlp_hidden=128, moe_hidden=32, moe_shared_hidden=32, n_experts=4,
            moe_router_width=16, moe_top_k=4, moe_ep_buffer=4.0, max_seq_len=96,
            dtype="float32", param_dtype="float32")
T = 72
LOGIT_TOL = 5e-5  # fp32 against fp32 on logits of ~4: summation order only
GREEDY = SampleConfig(temperature=0.0)


def tiny_cfg(backend="xla", **over):
    return dataclasses.replace(
        get_config("openpangu_ultra_moe_718b"), backend=backend, **{**TINY, **over})


def spec_of(cfg, **over):
    return {
        "layer_types": cfg.resolved_layer_types, "n_heads": cfg.n_heads,
        "q_rank": cfg.latent_q_rank, "kv_rank": cfg.latent_kv_rank,
        "nope": cfg.latent_nope_dim, "rope": cfg.latent_rope_dim,
        "value": cfg.latent_value_dim, "rotary_base": cfg.rotary_base,
        "norm_eps": cfg.norm_eps, "top_k": cfg.moe_top_k, "experts_held": cfg.n_experts,
        "expert_offset": cfg.moe_expert_offset, "router_width": cfg.moe_router_width,
        "route_scale": cfg.moe_route_scale, **over,
    }


@pytest.fixture(scope="module")
def model_params():
    cfg = tiny_cfg()
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, T), 0, cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.key(0), toks[:, :16])
    # norm weights off 1, so that one left out or misplaced shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.3 * jax.random.normal(jax.random.key(len(str(path))), x.shape)
        if "scale" in str(path) else x, params)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(spec_of(cfg), params, toks)
        got = model.apply(params, toks)
    return cfg, params, toks, want, got


def test_preset_is_the_published_shape():
    cfg = get_config("openpangu_ultra_moe_718b")
    shapes = jax.eval_shape(
        TransformerLM(cfg).init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 4_919_139_840
    blk = shapes["params"]["block_1"]
    assert blk["attn"]["wkv_b"].shape == (512, 128 * 256)
    assert blk["attn"]["wq_b"]["kernel"].shape == (1536, 128 * 192)
    assert blk["mlp"]["experts_gate"].shape == (16, 7680, 2048)
    assert blk["mlp"]["router"]["kernel"].shape == (7680, 256)
    assert "shared_scale" not in blk["mlp"]  # the shared expert is ungated
    assert shapes["params"]["block_0"]["mlp"]["gate"]["kernel"].shape == (7680, 18432)
    assert {"norm1", "post_norm1", "norm2", "post_norm2"} <= set(blk)
    state = jax.eval_shape(lambda: init_decode_state(cfg, 2))[0]
    assert {k: v.shape for k, v in state.items()} == {"c": (2, 4608, 512), "kr": (2, 4608, 64)}


@pytest.mark.parametrize("first,period,want", [
    (0, 2, [False, True, False, True, False]),   # as it was
    (1, 1, [False, True, True, True, True]),     # one leading dense layer
    (3, 1, [False, False, False, True, True]),   # the published three
    (1, 2, [False, True, False, True, False]),
    (2, 2, [False, False, False, True, False]),
])
def test_moe_at_knows_leading_dense_layers(first, period, want):
    cfg = ModelConfig(n_layers=5, n_experts=4, moe_period=period, moe_first_dense=first)
    assert [cfg.moe_at(i) for i in range(5)] == want
    assert not any(ModelConfig(n_layers=5).moe_at(i) for i in range(5))


def test_model_matches_the_reference(model_params):
    _, _, _, want, got = model_params
    assert float(jnp.abs(want).max()) > 1.0
    assert float(jnp.abs(got - want).max()) < LOGIT_TOL


@pytest.mark.parametrize("what,over", [
    ("the route scale", {"route_scale": 1.0}),
    ("the rotary base", {"rotary_base": 1e4}),
    ("the top-k", {"top_k": 3}),
    ("which experts are held", {"expert_offset": 4}),
    ("the norm's epsilon", {"norm_eps": 1e-2}),
])
def test_the_comparison_sees(model_params, what, over):
    cfg, params, toks, _, got = model_params
    with jax.default_matmul_precision("highest"):
        other = ref.forward(spec_of(cfg, **over), params, toks)
    assert float(jnp.abs(got - other).max()) > 100 * LOGIT_TOL, what


@pytest.mark.parametrize("name", ["post_norm1", "post_norm2", "norm1", "norm2"])
def test_sandwich_uses_all_four_norms(model_params, name):
    """``x + post(f(pre(x)))``: each of a block's four norm weights moves the
    output, and the program and the reference move together."""
    cfg, params, toks, _, got = model_params
    blk = dict(params["params"]["block_1"])
    blk[name] = {"scale": blk[name]["scale"] * 1.5}
    changed = {"params": {**params["params"], "block_1": blk}}
    with jax.default_matmul_precision("highest"):
        want = ref.forward(spec_of(cfg), changed, toks)
        now = TransformerLM(cfg).apply(changed, toks)
    assert float(jnp.abs(now - got).max()) > 100 * LOGIT_TOL
    assert float(jnp.abs(now - want).max()) < LOGIT_TOL


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_prefill_equals_pieces_equals_the_decode_walk(model_params, backend):
    """Logits of prefill + pieces + decode through the latent cache against
    the reference's one forward: the expanded forms (a piece over the latent
    rows before it, block by block under a Pallas backend) and the absorbed
    step agree."""
    cfg, params, toks, want, _ = model_params
    cfg = dataclasses.replace(cfg, backend=backend)
    model = TransformerLM(cfg)
    n = 40
    logits, _ = model.apply(params, toks[:, :n], method=model.prefill)
    assert float(jnp.abs(logits - want[:, :n]).max()) < LOGIT_TOL
    states = init_decode_state(cfg, 2, jnp.float32)
    for off, cons in ((0, 16), (16, 16), (32, 8)):  # the last piece padded
        piece = jnp.pad(toks[:, off:off + cons], ((0, 0), (0, 16 - cons)))
        last, states = model.apply(
            params, piece, states, jnp.int32(off), jnp.int32(cons),
            method=model.prefill_extend_step)
        assert float(jnp.abs(last - want[:, off + cons - 1]).max()) < LOGIT_TOL
    rows = dispatch.decode_live_rows(jnp.ones((2,), bool), backend=backend)
    for t in range(n, n + 6):
        step, states = model.apply(
            params, toks[:, t], states, jnp.full((2,), t, jnp.int32), rows,
            method=model.decode_step)
        assert float(jnp.abs(step - want[:, t]).max()) < LOGIT_TOL


def test_absorbed_step_equals_the_expanded_forward(model_params):
    """The mixer alone: ``decode_step`` (the up-projections absorbed, over
    the latent) against ``__call__`` (the latent expanded) at every position."""
    cfg, params, toks, _, _ = model_params
    mixer = MIXERS["latent"](cfg, "latent")
    p = {"params": params["params"]["block_1"]["attn"]}
    x = jax.random.normal(jax.random.key(5), (2, 24, cfg.d_model))
    want = mixer.apply(p, x)
    state = MIXERS["latent"].decode_state(cfg, "latent", 2, jnp.float32)
    for t in range(24):
        got, state = mixer.apply(p, x[:, t], state, jnp.int32(t), method=mixer.decode_step)
        assert float(jnp.abs(got - want[:, t]).max()) < 1e-5


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_latent_kernel_against_the_xla_form(dtype):
    """``ops/pallas/cache_attention.py::latent_attention`` in interpret mode:
    listed rows at their lengths (a block boundary, a partial block, an empty
    cache), an unlisted row untouched."""
    b, h, cap, r, dr = 5, 4, 1024 + 512, 32, 8
    ks = jax.random.split(jax.random.key(3), 4)
    qt, qr = jax.random.normal(ks[0], (b, h, r)), jax.random.normal(ks[1], (b, h, dr))
    c = jax.random.normal(ks[2], (b, cap, r)).astype(dtype)
    kr = jax.random.normal(ks[3], (b, cap, dr)).astype(dtype)
    lengths = jnp.asarray([512, 700, 0, 1536, 9], jnp.int32)
    live = jnp.asarray([True, True, True, False, True])
    rows = dispatch.decode_live_rows(live, backend="pallas_interpret")
    got, lse = dispatch.latent_cache_attention(
        qt, qr, c, kr, lengths, rows, scale=0.2, backend="pallas_interpret")
    # the kernel rounds the scaled query and the softmax weights to the
    # cache's dtype: hand the XLA form the same query
    qt_, qr_ = ((y * 0.2).astype(dtype).astype(jnp.float32) / 0.2 for y in (qt, qr))
    want, want_lse = dispatch.latent_cache_attention(
        qt_, qr_, c, kr, lengths, None, scale=0.2, backend="xla")
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    for i in (0, 1, 4):
        assert float(jnp.abs(got[i] - want[i]).max()) < tol
        assert float(jnp.abs(lse[i] - want_lse[i]).max()) < tol
    for i in (2, 3):  # an empty cache, an unlisted row
        assert float(jnp.abs(got[i]).max()) == 0.0 and float(lse[i].max()) < -9e29


def _moe_apply(cfg, p, x, live=None):
    return MoEMLP(cfg).apply({"params": p}, x, live, mutable=["moe_stats"])


def _stats(sown):
    return dict(zip(STAT_NAMES, np.asarray(stats_vector(sown["moe_stats"])).tolist()))


def test_sigmoid_router_against_a_plain_top_k(model_params):
    """The layer with ALL experts held against a plain ``jnp`` form: sigmoid
    scores, ``lax.top_k``, normalised over the chosen, times the scale."""
    cfg, params, _, _, _ = model_params
    whole = dataclasses.replace(cfg, n_experts=16, moe_router_width=16, moe_ep_buffer=1.0)
    model = TransformerLM(whole)
    p = model.init(jax.random.key(3), jnp.zeros((1, 8), jnp.int32))["params"]["block_1"]["mlp"]
    x = jax.random.normal(jax.random.key(2), (2, 40, cfg.d_model))
    got, _ = _moe_apply(whole, p, x)
    scores = jax.nn.sigmoid(x @ p["router"]["kernel"])
    top, ids = jax.lax.top_k(scores, 4)
    gates = 2.5 * top / (top.sum(-1, keepdims=True) + 1e-20)
    ffn = lambda y, i: (jax.nn.silu(y @ p["experts_gate"][i]) * (y @ p["experts_up"][i])) @ p["experts_down"][i]  # noqa: E731
    want = sum(
        jnp.where((ids == i).any(-1, keepdims=True),
                  (gates * (ids == i)).sum(-1, keepdims=True) * ffn(x, i), 0.0)
        for i in range(16))
    want = want + (jax.nn.silu(x @ p["shared_gate"]["kernel"]) * (x @ p["shared_up"]["kernel"])) @ p["shared_down"]["kernel"]
    assert float(jnp.abs(got - want).max()) < 1e-5


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_share_sum_of_all_chips_equals_the_uncut_layer(model_params, backend):
    """16 experts over 4 chips, 4 held each: the routed parts of the 4 shares
    plus the shared expert ONCE are the uncut reference layer; every routed
    row has one owner and nothing drops."""
    cfg, _, _, _, _ = model_params
    whole = dataclasses.replace(cfg, n_experts=16, moe_router_width=16)
    p = TransformerLM(whole).init(
        jax.random.key(3), jnp.zeros((1, 8), jnp.int32))["params"]["block_1"]["mlp"]
    x = jax.random.normal(jax.random.key(2), (2, 40, cfg.d_model))
    live = jnp.ones((2, 40), bool)
    want = ref.mlp(spec_of(whole), p, x)
    shared = ref.shared_expert(spec_of(whole), p, x)
    total, held = jnp.zeros_like(x), 0
    for chip in range(4):
        mine_cfg = dataclasses.replace(cfg, moe_expert_offset=4 * chip, backend=backend)
        mine = {**p, **{n: p[n][4 * chip:4 * chip + 4]
                        for n in ("experts_gate", "experts_up", "experts_down")}}
        got, sown = _moe_apply(mine_cfg, mine, x, live)
        s = _stats(sown)
        assert s["dropless_overflow"] == 0 and s["rows_routed"] == 2 * 40 * 4
        held += s["rows_held"]
        assert float(jnp.abs(got - ref.mlp(spec_of(mine_cfg), mine, x)).max()) < 1e-5
        total = total + (got - shared)
    assert held == 2 * 40 * 4
    assert float(jnp.abs(total + shared - want).max()) < 4e-5


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_an_adversarial_router_drops_nothing_when_served(model_params, backend):
    """Every token to the held experts (their router columns far above the
    rest): the serving path's buffer holds all N x k pairs, and the result is
    still the reference's."""
    cfg, params, _, _, _ = model_params
    cfg = dataclasses.replace(cfg, backend=backend)
    p = dict(params["params"]["block_1"]["mlp"])
    # all-positive features and router columns +1 (held) / -1 (the rest)
    x = jnp.abs(jax.random.normal(jax.random.key(2), (2, 48, cfg.d_model))) + 0.1
    column = jnp.where(jnp.arange(16) < 4, 1.0, -1.0) + 0.01 * jnp.arange(16)
    p["router"] = {"kernel": jnp.ones((cfg.d_model, 1)) * column[None, :] / cfg.d_model}
    got, sown = _moe_apply(cfg, p, x, jnp.ones((2, 48), bool))
    s = _stats(sown)
    assert s["rows_held"] == s["rows_routed"] == 2 * 48 * 4
    assert s["dropless_overflow"] == 0
    assert float(jnp.abs(got - ref.mlp(spec_of(cfg), p, x)).max()) < 1e-5


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_rows_that_do_not_count_route_nowhere(model_params, backend):
    """A row outside ``live`` (a slot that is not emitting, a piece's
    padding) enters no expert's buffer and no counter; the live rows' results
    are what they are without it."""
    cfg, params, _, _, _ = model_params
    cfg = dataclasses.replace(cfg, backend=backend)
    p = params["params"]["block_1"]["mlp"]
    x = jax.random.normal(jax.random.key(2), (6, cfg.d_model))
    live = jnp.asarray([True, False, True, True, False, False])
    got, sown = _moe_apply(cfg, p, x, live)
    alone, sown_alone = _moe_apply(cfg, p, x[live], jnp.ones((3,), bool))
    assert _stats(sown) == _stats(sown_alone)
    assert _stats(sown)["rows_routed"] == 3 * 4
    assert float(jnp.abs(got[live] - alone).max()) < 1e-6
    # a dead row keeps the shared expert's part only (its slot's output is
    # discarded by the decode programs)
    shared = ref.shared_expert(spec_of(cfg), p, x)
    assert float(jnp.abs(got[~live] - shared[~live]).max()) < 1e-6


@pytest.mark.parametrize("backend,donate", [
    ("xla", False), ("pallas_interpret", False), ("pallas_interpret", True)])
def test_engine_serves_as_generate(model_params, backend, donate):
    """The slot engine (pieces, the decode scan, the latent cache carried or
    held once) returns ``generate``'s greedy ids, and its MoE counters add up:
    every prompt token and every emitted step routes top-k pairs a layer."""
    cfg, params, _, _, _ = model_params
    model = TransformerLM(dataclasses.replace(cfg, backend=backend))
    eng = SlotEngine(model, params, slots=4, chunk=4, prefill_buckets=(16, 32, 64),
                     prefill_chunk=16)
    eng.donate_carry = donate
    prompts = [np.asarray(jax.random.randint(jax.random.key(10 + i), (n,), 0, 256))
               for i, n in enumerate((5, 20, 37))]
    for i, pr in enumerate(prompts):
        eng.admit(DecodeRequest(prompt=pr, max_new_tokens=9, sample=GREEDY, seed=i), tag=i)
    done, rows = {}, np.zeros(len(STAT_NAMES), np.int64)
    while eng.busy:
        done.update(dict(eng.step()))
        rows += eng.moe_rows
    plain = TransformerLM(cfg)
    for i, pr in enumerate(prompts):
        want = generate(plain, params, jnp.asarray(pr)[None], 9, sample=GREEDY,
                        rng=jax.random.key(0))
        assert done[i].status == "ok"
        np.testing.assert_array_equal(
            np.asarray(want).reshape(-1)[-9:], np.asarray(done[i].tokens).reshape(-1))
    # 2 expert layers x top-4 x (prompt tokens + 12 decode steps a request:
    # 9 tokens are 3 chunks of 4, the first from the piece's last row)
    assert rows[0] == 2 * 4 * (5 + 20 + 37 + 3 * 12)
    assert 0 < rows[2] <= rows[1] < rows[0] and rows[3] == 0
    # the grouped product's visits: an expert with a row has a tile or more
    assert 0 < rows[5] <= rows[4] <= rows[1]
    assert eng.kv_rows()[1] == 4 * cfg.max_seq_len
    assert eng.held_bytes["kv_bytes"] == 3 * 4 * cfg.max_seq_len * (16 + 8) * 4


def test_server_counts_moe_rows_and_latent_rows(model_params):
    """The ``Server`` over the tiny preset: the MoE row counters ride the
    boundary's probe, the latent layers feed the KV row counters."""
    cfg, params, _, _, _ = model_params
    srv = Server(TransformerLM(cfg), params,
                 ServeConfig(chunk=4, slots=2, max_inflight=8, prefill_chunk=16,
                             prefill_buckets="16,32,64", cost=False))
    handles = [srv.submit(DecodeRequest(
        prompt=np.arange(3 + 9 * i, dtype=np.int32) % 256, max_new_tokens=6,
        sample=GREEDY, seed=i)) for i in range(3)]
    srv.serve(drain_when_idle=True)
    c = srv.metrics.counters_flat()
    srv.close()
    assert all(h.result.status == "ok" for h in handles)
    assert c["moe_rows_routed"] > c["moe_rows_held"] >= c["moe_rows_max_expert"] > 0
    assert c["moe_rows_dropped"] == 0
    assert c["kv_rows_reserved"] > 0 and c["kv_rows_live"] > 0 and c["kv_rows_attended"] > 0


def test_cell_rehearses_on_the_cpu():
    import json
    import subprocess

    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "openpangu_ultra_moe_718b.serve_batch", "--seed", str(2**31 + 11), "--seconds", "2",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")]
    result = lines[-1]
    assert result["correct"] and result["failed"] == 0
    check = next(l["check"] for l in lines if "check" in l)
    # at rehearsal widths (fp32, logits of ~4) the lowered reading sits under
    # the chip's tolerance: printed, and read on the chip
    assert check["ok"] and check["lowered"]["max_gap"] > 100 * check["max_gap"]
    m = result["metrics"]
    assert m["moe_rows_dropped.batch"]["value"] == 0.0
    assert 0 < m["moe_held_row_share.batch"]["value"] < 100
    assert m["kv_live_share.batch"]["value"] > 0


# PR 58: the held layer sows ``tiles_live`` in training too (seven equations a
# layer, the diff of the jaxpr text against the parent's holds nothing else);
# f4d1341c0115e7c2 until then
QWEN_TRAIN_FORWARD = "58b1e83adae5d1f0"


def test_qwen3_next_train_program_is_what_it_was():
    """The edited MoE layer under the delta-rule preset's configuration
    (softmax scores, a gated shared expert, the 1.5x buffer, no ``live``):
    the jaxpr of its tiny train forward, hashed on the PARENT of PR 43
    (f9dd22b), equal on its tree and until PR 58's counter."""
    import hashlib

    cfg = dataclasses.replace(
        get_config("qwen3_next_80b"), vocab_size=256, d_model=128, n_heads=4, n_kv_heads=2,
        head_dim=32, rotary_dims=8, gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16,
        gdn_value_dim=16, mlp_hidden=64, moe_shared_hidden=64, n_experts=8,
        moe_router_width=16, moe_top_k=2, dtype="float32", remat=False, max_seq_len=128)
    model = TransformerLM(cfg)
    toks = jnp.zeros((2, 64), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0), toks))
    traced = jax.make_jaxpr(
        lambda p, x: model.apply(p, x, mutable=["losses", "moe_stats"]))(params, toks)
    assert hashlib.sha256(str(traced).encode()).hexdigest()[:16] == QWEN_TRAIN_FORWARD

