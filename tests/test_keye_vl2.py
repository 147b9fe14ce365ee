"""The served indexed-attention mixture of experts (ISSUE 47): grouped softmax
attention over the ``index_topk`` cache rows a learned per-token indexer picks
(16-head index queries against one index key a token, held in the cache
beside K and V), beside a softmax top-k mixture of experts all held on the
chip, against ``benchmark/reference/plain_keye_vl2.py``; tiny, CPU, fp32. The
contract every served configuration takes is ``tests/served_contract.py``'s."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from served_contract import (
    ROOT, Because, Cell, Scan, ServedCase, ServedContract, Walk, other_presets, served_fixture,
    tiny_cfg,
)

from orion_tpu.models.configs import get_config
from orion_tpu.models.mixers import MIXERS
from orion_tpu.models.mixers.indexed import rows_listed
from orion_tpu.models.moe import MoEMLP, masks_rows
from orion_tpu.models.transformer import TransformerLM, init_decode_state
from orion_tpu.ops import dispatch
from orion_tpu.ops.softmax_attention import cached_attention
from orion_tpu.ops.topk_select import mask_to_list, top_k_mask

# the indexer scaled down: 4 index heads of 8
T, N = 203, 150
CASE = ServedCase(
    "keye_vl_2_0_30b_a3b", seq=T,
    logit_tol=5e-5,  # fp32 against fp32 on logits of ~4: summation order only
    over=dict(index_topk=Because(24, "T = 203 is more than eight times the rows a query keeps"),
              max_seq_len=256),
    constants=dict(query_tile=64),
    moved=("scale", "bias"),  # biases off 0 too, so that a LayerNorm left out shows
    bites=dict(index_topk=10 ** 6),  # the reference that keeps every row
    # the selection past ``index_topk`` rows is the same whatever the chunking;
    # from nothing: under and past index_topk rows
    walk=Walk(n=N, piece=48, steps=T - N, cold=40, states=dict(atol=2e-5, rtol=2e-5)),
    row_list=127,
    scan=Scan(n=N, steps=8, layer=0, held=("k", "v", "ki"), carried=("kn", "vn", "kin", "t0")),
    server=True,
    cell=Cell("keye_vl_2_0_30b_a3b.serve_long", seed=2 ** 31 + 47),
    # read on the parent of PR 59 (44d93ca) at this case's sizes; until then
    # tests/test_trinity_mini.py pinned them at sizes of its own, where PR 56
    # changed the piece and the step: a served held layer sows ``tiles_live``
    # and ``experts_live`` (seven equations a layer, nothing else)
    pins={"forward": "a731e1d746341ad8",
          "piece": "40b4fe50520885e3", "step": "8c89c16d05a18f1b"},
)
served = served_fixture(CASE)


class TestServed(ServedContract):
    case = CASE

    def published(self, cfg):
        assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (2048, 32, 4, 128)
        assert (cfg.index_heads, cfg.index_dim, cfg.index_topk) == (16, 64, 2048)
        assert (cfg.n_experts, cfg.moe_top_k, cfg.moe_hidden, cfg.moe_period) == (128, 8, 768, 1)
        # every expert is here: no share of a wider router, and no width spelled out
        assert not cfg.moe_held and cfg.moe_router_width == 0 and cfg.resolved_router_width == 128
        assert masks_rows(cfg) and cfg.moe_shared_hidden == 0
        assert (cfg.vocab_size, cfg.tie_embeddings, cfg.rotary_base) == (151936, False, 1e7)
        assert cfg.resolved_layer_types == ("indexed",) * 4 and cfg.max_seq_len == 32768 + 512
        shapes = jax.eval_shape(lambda: init_decode_state(cfg, 2))
        assert [sorted(s) for s in shapes] == [["k", "ki", "v"]] * 4
        assert shapes[0]["k"].shape == (2, 33280, 512) and shapes[0]["ki"].shape == (2, 33280, 64)
        assert shapes[0]["ki"].dtype == jnp.bfloat16
        tree = jax.eval_shape(
            lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
        layer = sum(x.size for x in jax.tree.leaves(tree["params"]["block_0"]))
        assert layer == 625_381_760
        assert sum(x.size for x in jax.tree.leaves(tree)) == 3_123_858_944
        blk = tree["params"]["block_3"]
        assert blk["attn"]["wqi"]["kernel"].shape == (2048, 16 * 64)
        assert blk["attn"]["ki_norm"]["bias"].shape == (64,)
        assert blk["mlp"]["experts_down"].shape == (128, 768, 2048)
        # index_topk 0 is no field of any other preset's programs
        assert all(c.index_topk == 0 for c in other_presets(cfg.name))

    def before_boundary(self, engine):
        return engine.kv_rows_listed() + engine.index_piece_pairs()

    def after_engine(self, served, run, backend, donate):
        """The row counters follow the positions. With the carry donated the
        scan reads the three caches and carries a chunk's own rows of each
        (``chunk_split``)."""
        cfg = run.cfg
        listed, scored, visible, selected = (sum(x) for x in zip(*run.seen))
        assert 0 < listed < scored and 0 < selected < visible
        # the prompts' pieces: every (query, key) pair at or before the query, once
        assert visible == sum(n * (n + 1) // 2 for n in (30, 90, 170))
        assert selected == sum(sum(min(cfg.index_topk, i + 1) for i in range(n)) for n in (30, 90, 170))
        # the last boundaries: the long request alone, past index_topk rows
        assert run.seen[-1][0] == cfg.index_topk and run.seen[-1][1] >= 170
        assert run.engine.held_bytes["kv_bytes"] == 2 * 4 * 256 * (2 * 32 + 8) * 4  # k, v and ki
        assert run.engine.kv_rows()[1] == 4 * 256

    def after_server(self, served, counters, prompts):
        """The MoE row counters add up: every routed pair is held, none dropped."""
        assert 0 < counters["kv_rows_listed"] < counters["index_rows_scored"]
        assert 0 < counters["index_pairs_selected"] < counters["index_pairs_visible"]
        assert counters["moe_rows_routed"] == counters["moe_rows_held"] > 0
        assert counters["moe_rows_dropped"] == 0
        # a prompt row routes top_k pairs a layer, once
        prompt_pairs = sum(len(p) for p in prompts) * served.cfg.moe_top_k * served.cfg.n_layers
        assert counters["moe_rows_routed"] >= prompt_pairs

    def after_cell(self, result, lines):
        assert 0 < result["metrics"]["kv_row_read_share.long"]["value"] < 100
        assert result["metrics"]["moe_rows_dropped.batch"]["value"] == 0


@pytest.mark.parametrize("patch", [
    "no ReLU", "an unscaled w", "no LayerNorm on kI", "rotary off the indexer",
    "a selection per KV head", "ties to the higher s", "k - 1", "an un-renormalised router"])
def test_the_comparison_sees(served, monkeypatch, patch):
    """The tolerance is tight enough to tell the model from a reference that
    differs in one of the mechanisms."""
    ref, cfg, spec = served.ref, served.cfg, served.spec()
    if patch == "no ReLU":
        monkeypatch.setattr(ref, "activation", lambda s: s)
    elif patch == "an unscaled w":
        monkeypatch.setattr(ref, "index_weights", lambda spec, p, u: jnp.swapaxes(
            u @ jnp.asarray(p["ww"]["kernel"], jnp.float32), 1, 2))
        # a positive scale moves no selection of ONE row; it does move ... nothing:
        # so the weights are shifted too, which a dropped scale cannot hide
        plain = ref.index_scores
        monkeypatch.setattr(ref, "index_scores", lambda spec, qi, w, ki: plain(spec, qi, w + 1.0, ki))
    elif patch == "no LayerNorm on kI":
        monkeypatch.setattr(ref, "layer_norm", lambda x, p: x)
    elif patch == "rotary off the indexer":
        plain = ref.rope
        monkeypatch.setattr(ref, "rope", lambda x, base: x if x.shape[-1] == cfg.index_dim else plain(x, base))
    elif patch == "a selection per KV head":
        plain = ref.index_scores

        def per_kv(spec, qi, w, ki):  # each KV head ranks by its own half of the index heads
            half = qi.shape[1] // 2
            return jnp.concatenate([plain(spec, qi[:, :half], w[:, :half], ki),
                                    plain(spec, qi[:, half:], w[:, half:], ki)], axis=1)

        monkeypatch.setattr(ref, "index_scores", per_kv)
    elif patch == "ties to the higher s":
        plain = ref.selected

        def higher(spec, scores, visible):
            return plain(spec, scores[..., ::-1], visible[..., ::-1])[..., ::-1]

        monkeypatch.setattr(ref, "selected", higher)
        # ties exist only where scores repeat: a ReLU that is all zero for
        # half the index heads' rows makes many
        monkeypatch.setattr(ref, "activation", lambda s: jnp.floor(jax.nn.relu(s)))
    elif patch == "k - 1":
        spec = served.spec(index_topk=cfg.index_topk - 1)
    else:
        def raw(spec, p, x):
            probs = jax.nn.softmax(x @ jnp.asarray(p["router"]["kernel"], jnp.float32), axis=-1)
            top, ids = jax.lax.top_k(probs, spec["top_k"])
            return jnp.einsum("nk,nke->ne", top, jax.nn.one_hot(ids, probs.shape[-1]))

        monkeypatch.setattr(ref, "routing_weights", raw)
    if patch == "ties to the higher s":
        # the floored activation is a different model: compare the two tie rules on it
        other = served.reference(spec)
        monkeypatch.undo()
        monkeypatch.setattr(ref, "activation", lambda s: jnp.floor(jax.nn.relu(s)))
        assert float(jnp.abs(other - served.reference(spec)).max()) > 20 * CASE.logit_tol, patch
        return
    served.differs(spec)


def _top_k_set(scores, valid, k):
    """``lax.top_k``'s selection as a mask (ties to the lower index; -0.0
    is the score 0.0, which ``lax.top_k``'s total order would put below it)."""
    masked = jnp.where(valid, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    idx = jax.lax.top_k(masked, min(k, scores.shape[-1]))[1]
    hit = jnp.zeros(scores.shape, bool).at[jnp.arange(scores.shape[0])[:, None], idx].set(True)
    return hit & valid


@pytest.mark.parametrize("case", ["random", "repeated at the threshold", "fewer than k",
                                  "signed zeros and negatives"])
def test_search_select_is_top_k(case):
    """The counting search picks ``lax.top_k``'s set, ties included, and
    its list is that set in ascending order."""
    rows, n, k = 6, 300, 40
    key = jax.random.key(3)
    scores = jax.random.normal(key, (rows, n))
    valid = jnp.arange(n)[None, :] <= jnp.array([299, 250, 120, 77, 41, 40])[:, None]
    if case == "repeated at the threshold":
        scores = jnp.round(scores * 2) / 2  # a dozen distinct values: many ties at the k-th
    elif case == "fewer than k":
        valid = jnp.arange(n)[None, :] <= jnp.array([0, 5, 38, 39, 12, 3])[:, None]
    elif case == "signed zeros and negatives":
        scores = jnp.where(scores > 0.3, 0.0, jnp.where(scores > 0, -0.0, scores))
    got = top_k_mask(scores, valid, k)
    want = _top_k_set(scores, valid, k)
    np.testing.assert_array_equal(got, want)
    assert (got.sum(-1) == jnp.minimum(valid.sum(-1), k)).all()
    lists, counts = mask_to_list(got, k)
    for r in range(rows):
        np.testing.assert_array_equal(lists[r, :counts[r]], np.flatnonzero(np.asarray(got[r])))
        assert (lists[r, counts[r]:] == 0).all()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_row_list_attention_against_the_masked_form(dtype):
    """``cache_attention(row_list=...)`` over the listed rows of a ``[B, cap,
    KV Dh]`` cache = attention over the whole reservation under the list's
    mask; a sequence the row list leaves out, or an empty list, reads
    nothing."""
    b, cap, kvh, g, d, width = 3, 96, 2, 2, 16, 20
    keys = jax.random.split(jax.random.key(5), 4)
    q = jax.random.normal(keys[0], (b, kvh * g, d)).astype(dtype)
    kc = jax.random.normal(keys[1], (b, cap, kvh * d)).astype(dtype)
    vc = jax.random.normal(keys[2], (b, cap, kvh * d)).astype(dtype)
    rng = np.random.default_rng(0)
    counts = jnp.array([20, 7, 0])
    lists = jnp.asarray(np.stack([np.sort(rng.permutation(cap)[:width]) for _ in range(b)]), jnp.int32)
    out, lse = dispatch.cache_attention(q, kc, vc, jnp.full((b,), cap), None, backend="xla",
                                        row_list=(lists, counts))
    mask = np.zeros((b, cap), bool)
    for r in range(b):
        mask[r, np.asarray(lists[r, :counts[r]])] = True
    head_major = lambda c: jnp.swapaxes(c.reshape(b, cap, kvh, d), 1, 2).astype(jnp.float32)  # noqa: E731
    want, want_lse = cached_attention(q.astype(jnp.float32), head_major(kc), head_major(vc),
                                      jnp.asarray(mask)[:, None, :], with_lse=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(out[:2], want[:2], atol=tol)
    np.testing.assert_allclose(lse[:2], want_lse[:2], atol=tol)
    assert (out[2] == 0).all() and (lse[2] <= -1e29).all()
    rows = dispatch.decode_live_rows(jnp.array([False, True, True]), backend="pallas_interpret")
    out2, lse2 = dispatch.cache_attention(q, kc, vc, jnp.full((b,), cap), rows,
                                          backend="pallas_interpret", row_list=(lists, counts))
    assert (out2[0] == 0).all() and (lse2[0] <= -1e29).all()
    np.testing.assert_array_equal(out2[1], out[1])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_piece_kernels_against_the_xla_forms(dtype):
    """``ops/pallas/indexed_attention.py`` in interpret mode: the index scores
    are ``mixers/indexed.py::index_scores``', and flash attention under an
    int8 mask over a ``[S, KV Dh]`` cache is the dense softmax under that
    mask; a query row whose mask is empty gives 0."""
    from orion_tpu.models.mixers.indexed import index_scores
    from orion_tpu.ops.pallas import indexed_attention as pia

    b, p, s, kvh, g, d, ih, idim = 2, 64, 256, 2, 2, 16, 4, 8
    keys = jax.random.split(jax.random.key(7), 7)
    qi = jax.random.normal(keys[0], (b, p, ih, idim)).astype(dtype)
    w = jax.random.normal(keys[1], (b, p, ih))
    ki = jax.random.normal(keys[2], (b, s, idim)).astype(dtype)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(pia.index_scores(qi, w, ki, interpret=True),
                               index_scores(qi, w, ki), atol=tol)
    q = jax.random.normal(keys[3], (b, kvh, g, p, d)).astype(dtype)
    kc = jax.random.normal(keys[4], (b, s, kvh * d)).astype(dtype)
    vc = jax.random.normal(keys[5], (b, s, kvh * d)).astype(dtype)
    keep = jax.random.bernoulli(keys[6], 0.2, (b, p, s)).at[:, 3].set(False)
    got = pia.masked_attention(q, kc, vc, keep.astype(jnp.int8), interpret=True)
    k4, v4 = (c.reshape(b, s, kvh, d).astype(jnp.float32) for c in (kc, vc))
    sc = jnp.einsum("bkgqd,bskd->bkgqs", q.astype(jnp.float32), k4) * d ** -0.5
    pr = jax.nn.softmax(jnp.where(keep[:, None, None], sc, -1e30), axis=-1)
    want = jnp.einsum("bkgqs,bskd->bkgqd", jnp.where(keep[:, None, None], pr, 0.0), v4)
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=tol)
    assert bool((got[:, :, :, 3] == 0).all())


def test_grouped_product_blocks_divide_an_expert_768_wide():
    """``gmm_live`` under output blocks of 512: an expert 768 wide takes
    blocks of 384 and its weights are not padded (a padded copy of 128
    experts' weights a call was 19% of a boundary's busy time on the chip);
    widths a block of 512 divides keep it."""
    from orion_tpu.ops.pallas.gmm import gmm_live

    def traced(h):
        x, w = jnp.zeros((256, 64), jnp.bfloat16), jnp.zeros((4, 64, h), jnp.bfloat16)
        return str(jax.make_jaxpr(lambda x, w, g: gmm_live(x, w, g, 128, 512, True))(
            x, w, jnp.array([128, 0, 128, 0], jnp.int32)))

    assert " pad" not in traced(768) and " pad" not in traced(1024)
    assert " pad" in traced(800)  # no block of whole lanes divides it


@pytest.mark.parametrize("width", [0, 8])
@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_rows_outside_live_route_nowhere_with_every_expert_held(backend, width):
    """A dropless layer that holds ALL of its router's experts, the width
    left at 0 or spelled out (8 = the experts): handed ``live`` it takes the
    held-rows path, a row outside ``live`` adds nothing and counts nowhere,
    nothing drops, and the live rows read what the layer gives without a
    mask; without ``live`` it is the ordinary dropless layer and counts
    nothing. The two spellings trace to one program either way."""
    cfg = tiny_cfg(CASE, backend, moe_ep_buffer=1.0, moe_router_width=width)
    assert not cfg.moe_held and masks_rows(cfg)
    x = jax.random.normal(jax.random.key(2), (1, 24, cfg.d_model))
    layer = MoEMLP(cfg)
    params = jax.jit(layer.init)(jax.random.key(0), x)
    live = jnp.arange(24)[None, :] < 17
    y, sown = layer.apply(params, x, live, mutable=["moe_stats"])
    stats = {k: int(v[0]) for k, v in sown["moe_stats"].items()}
    assert stats["rows_routed"] == 17 * cfg.moe_top_k == stats["rows_held"]
    assert stats["dropless_overflow"] == 0
    assert bool((y[0, 17:] == 0).all())
    plain, sown = layer.apply(params, x, mutable=["moe_stats"])
    assert "moe_stats" not in sown
    np.testing.assert_allclose(y[0, :17], plain[0, :17], atol=2e-5)
    other = MoEMLP(dataclasses.replace(cfg, moe_router_width=8 - width))
    for args in ((x,), (x, live)):
        assert str(jax.make_jaxpr(lambda *a: layer.apply(params, *a))(*args)) == str(
            jax.make_jaxpr(lambda *a: other.apply(params, *a))(*args))


def test_a_layer_that_cannot_mask_rows_ignores_live():
    """``masks_rows`` is decided by what the layer IS, not by a field that
    names a path: a capacity layer, an int8 one and one on a mesh of several
    devices have no held-rows form, and compute every row as they did."""
    cfg = tiny_cfg(CASE)
    assert masks_rows(cfg) and not masks_rows(cfg, quant="int8")
    assert not masks_rows(dataclasses.replace(cfg, moe_dropless=False))
    assert not masks_rows(dataclasses.replace(cfg, n_experts=0))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tp",))
    assert not masks_rows(cfg, mesh=mesh)
    capacity = MoEMLP(dataclasses.replace(cfg, moe_dropless=False))
    x = jax.random.normal(jax.random.key(2), (1, 24, cfg.d_model))
    params = jax.jit(capacity.init)(jax.random.key(0), x)
    live = jnp.arange(24)[None, :] < 17
    np.testing.assert_array_equal(capacity.apply(params, x, live), capacity.apply(params, x))


def test_rows_listed_is_the_list_the_step_builds():
    cfg = tiny_cfg(CASE)
    assert [rows_listed(cfg, n) for n in (1, 23, 24, 25, 200)] == [1, 23, 24, 24, 24]
    big = get_config("keye_vl_2_0_30b_a3b")
    assert rows_listed(big, 2048) == 2048 == rows_listed(big, 33280)
    assert MIXERS["indexed"].cache_rows_read(big, "indexed", 20480) == 2048
    assert MIXERS["indexed"].cache_leaves == ("k", "v", "ki")


# -- the cell's rooflines: work and time of the SAME captured boundaries ----------

_WIDTHS = {"layers": 4, "index_heads": 16, "index_dim": 64, "kv_heads": 4, "head_dim": 128,
           "heads": 32, "cache_bytes": 2}
_SCORES = "index_scores tpu_custom_call -> f32[1,1024,4608]"
_ATTEND = "indexed_attention tpu_custom_call -> bf16[1,4,8,1024,128]"
_PIECE = "jit(_prefill_piece)/_prefill_extend_row/indexed_attention/index_attend/call"
_STEP = "jit(_decode_scan)/while/body/indexed_attention/index_select/reduce"


def _captured(pieces, boundaries):
    """Evidence of a capture that holds ``pieces`` piece-layers (1 ms of
    scores, 4 ms of attention each) and ``boundaries`` boundaries of 8
    emitting slots (2 ms of step work each), beside a window's counters."""
    device, scoped, t = [], [], 0.0
    for _ in range(pieces):
        device += [[_SCORES, t, 1e6], [_ATTEND, t + 1e6, 4e6]]
        scoped += [[_PIECE, t, 1e6], [_PIECE, t + 1e6, 4e6]]
        t += 5e6
    host = []
    for _ in range(boundaries):
        host.append(["serve.dispatch", t, 1e5])
        device.append(["fusion -> bf16[32768,512]", t, 2e6])
        scoped.append([_STEP, t, 2e6])
        t += 2e6
    return {
        "device_kind": "TPU v5 lite", "rehearse": False, "window_s": 50.0,
        "scoped_ops": {"source": "hlo_text", "events": scoped},
        "xplane": {"planes": [
            {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": device}]},
            {"name": "/host:CPU", "lines": [{"name": "serve-loop", "events": host}]}]},
        "capture": {"emitting_rows_per_boundary": 8.0, "boundaries": 30},
        "counters": {"slot_steps_emitting": 2000, "slot_steps_prefilling": 800,
                     "index_rows_scored": 2000 * 4 * 20000, "kv_rows_listed": 2000 * 4 * 2048,
                     "index_pairs_visible": 800 * 4 * 1024 * 10000,
                     "index_pairs_selected": 800 * 4 * 1024 * 2048},
    }


def _reader(name):
    import importlib
    return importlib.import_module("readers." + name)


def _metric_args(name):
    import json
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".json")) as f:
        return json.load(f)["args"]


@pytest.mark.parametrize("metric,want", [
    # a piece-layer: 1,024 x 10,000 visible pairs x 2,048 + 1,024 x 2,048 selected x 16,384, in 5 ms
    ("indexed_piece_roofline.long",
     100 * (1024 * 10000 * 2048 + 1024 * 2048 * 16384) / 5e-3 / 197e12),
    # an emitting slot and boundary: 4 steps x 4 layers x (20,000 x 128 B + 2,048 x 2,048 B +
    # 2 x 32 x 128 x 4 B), 8 slots a boundary in 2 ms
    ("indexed_step_roofline.long",
     100 * 8 * 16 * (20000 * 128 + 2048 * 2048 + 32768) / 2e-3 / 819e9),
])
@pytest.mark.parametrize("pieces,boundaries", [(4, 2), (12, 2), (4, 6)])
def test_roofline_counts_the_work_of_the_boundaries_it_times(metric, want, pieces, boundaries):
    """The capture's mix of pieces to steps moves neither share: each counts
    the work of the units the capture holds beside the time they took."""
    read = _reader("indexed_sparse_roofline").read
    got = read(_captured(pieces, boundaries), **_metric_args(metric))
    assert got == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("metric,per_pair,ms", [
    ("indexed_attention_roofline.long", 4 * 32 * 128, 4.0),
    ("index_scores_roofline.long", 2 * 16 * 64, 1.0),
])
def test_kernel_roofline_reads_the_key_length_from_the_capture(metric, per_pair, ms):
    read = _reader("indexed_kernel_roofline").read
    got = read(_captured(3, 1), **_metric_args(metric))
    assert got == pytest.approx(100 * 1024 * 4608 * per_pair / (ms * 1e-3) / 197e12, rel=1e-9)
    assert 0 < got < 100


@pytest.mark.parametrize("reader,metric", [
    ("indexed_sparse_roofline", "indexed_piece_roofline.long"),
    ("indexed_sparse_roofline", "indexed_step_roofline.long"),
    ("indexed_kernel_roofline", "indexed_attention_roofline.long"),
    ("indexed_kernel_roofline", "index_scores_roofline.long"),
])
def test_roofline_of_a_program_without_the_layer_reads_nothing(reader, metric):
    """The parent's program under this PR's benchmark files: no such kernel,
    scope or counter, so the metric is left out and nothing raises."""
    bare = {"device_kind": "TPU v5 lite", "rehearse": False, "window_s": 50.0,
            "counters": {"slot_steps_emitting": 10}, "capture": {"emitting_rows_per_boundary": 2},
            "scoped_ops": {"source": "hlo_text", "events": [["jit(f)/mlp/dot", 0.0, 1e6]]},
            "xplane": {"planes": [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Ops", "events": [["fusion -> f32[8]", 0.0, 1e6]]}]}]}}
    assert _reader(reader).read(bare, **_metric_args(metric)) is None
    assert _reader(reader).read({"device_kind": "cpu", "rehearse": True}, **_metric_args(metric)) is None
