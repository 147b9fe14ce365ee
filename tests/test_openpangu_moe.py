"""The served latent-attention mixture of experts (ISSUE 43): latent
attention over a latent cache (expand in the parallel forms, absorb at the
decode step), sandwich norms, a leading dense layer, a sigmoid top-k router
over a width of which one chip's share of experts is held, an ungated shared
expert, against ``benchmark/reference/plain_openpangu_moe.py``; tiny, CPU,
fp32. The contract every served configuration takes is
``tests/served_contract.py``'s."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from served_contract import (
    GREEDY, Cell, ServedCase, ServedContract, Share, Walk, moe_apply, moe_stats, served_fixture,
)

from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.mixers import MIXERS
from orion_tpu.models.transformer import TransformerLM, init_decode_state
from orion_tpu.ops import dispatch
from orion_tpu.serving import DecodeRequest, ServeConfig, Server

# the rehearse block: one dense layer then two expert layers; 4 of 16 experts
# held, top-4
CASE = ServedCase(
    "openpangu_ultra_moe_718b", seq=72,
    logit_tol=5e-5,  # fp32 against fp32 on logits of ~4: summation order only
    over=dict(max_seq_len=96),
    floor=1.0,
    # the expanded forms (a piece over the latent rows before it, block by
    # block under a Pallas backend) and the absorbed step agree: the steps
    # go on from what the pieces left
    walk=Walk(n=40, piece=16, steps=6, steps_from="pieces", against="reference"),
    share=Share(experts=("experts_gate", "experts_up", "experts_down"), part_tol=1e-5, sum_tol=4e-5),
    engines=(("xla", False), ("pallas_interpret", False), ("pallas_interpret", True)),
    engine=dict(slots=4, chunk=4, prefill_buckets=(16, 32, 64), prefill_chunk=16),
    prompts=((0, 0, 5), (1, 0, 20), (0, 30, 67)),
    cell=Cell("openpangu_ultra_moe_718b.serve_batch", seed=2 ** 31 + 11, seconds=2),
    # read on the parent of PR 59 (44d93ca) at this case's sizes; until then
    # tests/test_trinity_mini.py pinned them at sizes of its own, where PR 56
    # changed the piece and the step (a served held layer sows ``tiles_live``
    # and ``experts_live``: seven equations a layer, nothing else) and PR 58
    # the TRAINING forward (a held layer that is not served sows
    # ``tiles_live`` too; no cell runs it)
    pins={"forward": "6610d864d669152c",
          "piece": "07d9f7881203092b", "step": "339f447fee87c0d0"},
)
served = served_fixture(CASE)


class TestServed(ServedContract):
    case = CASE

    def published(self, cfg):
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

    def share_layer(self, served, spec, p, x):
        return served.ref.mlp(spec, p, x)

    def after_boundary(self, engine):
        return np.asarray(engine.moe_rows)

    def after_engine(self, served, run, backend, donate):
        """The slot engine (pieces, the decode scan, the latent cache carried
        or held once): its MoE counters add up, every prompt token and every
        emitted step routes top-k pairs a layer."""
        rows, cfg = np.sum(run.counted, axis=0), run.cfg
        # 2 expert layers x top-4 x (prompt tokens + 12 decode steps a request:
        # 9 tokens are 3 chunks of 4, the first from the piece's last row)
        assert rows[0] == 2 * 4 * (5 + 20 + 37 + 3 * 12)
        assert 0 < rows[2] <= rows[1] < rows[0] and rows[3] == 0
        # the grouped product's visits: an expert with a row has a tile or more
        assert 0 < rows[5] <= rows[4] <= rows[1]
        assert run.engine.kv_rows()[1] == 4 * cfg.max_seq_len
        assert run.engine.held_bytes["kv_bytes"] == 3 * 4 * cfg.max_seq_len * (16 + 8) * 4

    def after_cell(self, result, lines):
        check = next(line["check"] for line in lines if "check" in line)
        # at rehearsal widths (fp32, logits of ~4) the lowered reading sits under
        # the chip's tolerance: printed, and read on the chip
        assert check["ok"] and check["lowered"]["max_gap"] > 100 * check["max_gap"]
        m = result["metrics"]
        assert m["moe_rows_dropped.batch"]["value"] == 0.0
        assert 0 < m["moe_held_row_share.batch"]["value"] < 100
        assert m["kv_live_share.batch"]["value"] > 0


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


@pytest.mark.parametrize("what,over", [
    ("the route scale", {"route_scale": 1.0}),
    ("the rotary base", {"rotary_base": 1e4}),
    ("the top-k", {"top_k": 3}),
    ("which experts are held", {"expert_offset": 4}),
    ("the norm's epsilon", {"norm_eps": 1e-2}),
])
def test_the_comparison_sees(served, what, over):
    served.differs(served.spec(**over), factor=100)


@pytest.mark.parametrize("name", ["post_norm1", "post_norm2", "norm1", "norm2"])
def test_sandwich_uses_all_four_norms(served, name):
    """``x + post(f(pre(x)))``: each of a block's four norm weights moves the
    output, and the program and the reference move together."""
    cfg, params, toks, got = served.cfg, served.params, served.toks, served.got
    blk = dict(params["params"]["block_1"])
    blk[name] = {"scale": blk[name]["scale"] * 1.5}
    changed = {"params": {**params["params"], "block_1": blk}}
    want = served.reference(params=changed)
    with jax.default_matmul_precision("highest"):
        now = served.forward(changed, toks)
    assert float(jnp.abs(now - got).max()) > 100 * CASE.logit_tol
    assert float(jnp.abs(now - want).max()) < CASE.logit_tol


def test_absorbed_step_equals_the_expanded_forward(served):
    """The mixer alone: ``decode_step`` (the up-projections absorbed, over
    the latent) against ``__call__`` (the latent expanded) at every position."""
    cfg, params = served.cfg, served.params
    mixer = MIXERS["latent"](cfg, "latent")
    p = {"params": params["params"]["block_1"]["attn"]}
    x = jax.random.normal(jax.random.key(5), (2, 24, cfg.d_model))
    want = mixer.apply(p, x)
    state = MIXERS["latent"].decode_state(cfg, "latent", 2, jnp.float32)
    step = jax.jit(lambda x, state, t: mixer.apply(p, x, state, t, method=mixer.decode_step))
    for t in range(24):
        got, state = step(x[:, t], state, jnp.int32(t))
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


def test_sigmoid_router_against_a_plain_top_k(served):
    """The layer with ALL experts held against a plain ``jnp`` form: sigmoid
    scores, ``lax.top_k``, normalised over the chosen, times the scale."""
    cfg, (whole, p) = served.cfg, served.uncut_layer  # the share-sum test's 16 experts
    whole = dataclasses.replace(whole, moe_ep_buffer=1.0)
    x = jax.random.normal(jax.random.key(2), (2, 40, cfg.d_model))
    got, _ = moe_apply(whole, p, x)
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
def test_an_adversarial_router_drops_nothing_when_served(served, backend):
    """Every token to the held experts (their router columns far above the
    rest): the serving path's buffer holds all N x k pairs, and the result is
    still the reference's."""
    params, cfg = served.params, dataclasses.replace(served.cfg, backend=backend)
    p = dict(params["params"]["block_1"]["mlp"])
    # all-positive features and router columns +1 (held) / -1 (the rest)
    x = jnp.abs(jax.random.normal(jax.random.key(2), (2, 48, cfg.d_model))) + 0.1
    column = jnp.where(jnp.arange(16) < 4, 1.0, -1.0) + 0.01 * jnp.arange(16)
    p["router"] = {"kernel": jnp.ones((cfg.d_model, 1)) * column[None, :] / cfg.d_model}
    got, sown = moe_apply(cfg, p, x, jnp.ones((2, 48), bool))
    s = moe_stats(sown)
    assert s["rows_held"] == s["rows_routed"] == 2 * 48 * 4
    assert s["dropless_overflow"] == 0
    assert float(jnp.abs(got - served.ref.mlp(served.spec(cfg), p, x)).max()) < 1e-5


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_rows_that_do_not_count_route_nowhere(served, backend):
    """A row outside ``live`` (a slot that is not emitting, a piece's
    padding) enters no expert's buffer and no counter; the live rows' results
    are what they are without it."""
    params, cfg = served.params, dataclasses.replace(served.cfg, backend=backend)
    p = params["params"]["block_1"]["mlp"]
    x = jax.random.normal(jax.random.key(2), (6, cfg.d_model))
    live = jnp.asarray([True, False, True, True, False, False])
    got, sown = moe_apply(cfg, p, x, live)
    alone, sown_alone = moe_apply(cfg, p, x[live], jnp.ones((3,), bool))
    assert moe_stats(sown) == moe_stats(sown_alone)
    assert moe_stats(sown)["rows_routed"] == 3 * 4
    assert float(jnp.abs(got[live] - alone).max()) < 1e-6
    # a dead row keeps the shared expert's part only (its slot's output is
    # discarded by the decode programs)
    shared = served.ref.shared_expert(served.spec(cfg), p, x)
    assert float(jnp.abs(got[~live] - shared[~live]).max()) < 1e-6


def test_server_counts_moe_rows_and_latent_rows(served):
    """The ``Server`` over the tiny preset: the MoE row counters ride the
    boundary's probe, the latent layers feed the KV row counters."""
    srv = Server(served.model, served.params,
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


