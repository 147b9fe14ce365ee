"""The delta-rule hybrid (preset ``qwen3_next_80b``) at its rehearse sizes,
float32, seeded weights, against ``benchmark/reference/plain_gdn_moe.py``
(which imports nothing of ``orion_tpu``): the chunked gated delta rule
against the token-by-token recurrence, each mixer and the expert layer
against the reference, the share sum of the expert-parallel cut, and the
whole model's logits, loss and gradients. Also: the older presets' parameter
trees and logits are what they were before the new layer types came.

Tolerances: everything here is float32 on the CPU, where the only
difference between the two sides is the order of summation (chunked against
sequential, sorted rows against a masked loop), so 2e-5 absolute on O(1)
values for one op or one layer; gradients are compared relative to each
leaf's largest entry. Through the whole model (four layers, each one's
difference rescaled by the later layers' RMS norms) the readings are 2.7e-4
on the logits and 2.7e-4 of a leaf's largest gradient entry, held to 1e-3:
a dropped term (a gate, a norm weight, the decay, the rotary) moves either
by 1e-2 or more.
"""

import dataclasses
import functools
import itertools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "benchmark") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from reference import plain_gdn_moe as ref  # noqa: E402
from served_contract import ServedCase, trace_pins  # noqa: E402

from orion_tpu.models.configs import get_config  # noqa: E402
from orion_tpu.models.transformer import TransformerLM  # noqa: E402
from orion_tpu.ops.dispatch import gated_delta_rule  # noqa: E402
from orion_tpu.ops.gated_delta import (  # noqa: E402
    causal_short_conv, gated_delta_chunked, gated_delta_recurrent,
)

TOL = 2e-5
WHOLE_TOL = 1e-3

# benchmark/configs/qwen3_next_80b.json's ``rehearse`` sizes
REHEARSE = dict(
    d_model=128, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=32,
    rotary_dims=8, gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16,
    gdn_value_dim=16, mlp_hidden=64, moe_shared_hidden=64, n_experts=8,
    moe_router_width=16, moe_top_k=2, vocab_size=256, dtype="float32",
    max_seq_len=128, remat=False,
)


def tiny(**over):
    return dataclasses.replace(get_config("qwen3_next_80b"), **{**REHEARSE, **over})


def spec_of(cfg, **over):
    spec = {
        "layer_types": cfg.resolved_layer_types, "n_heads": cfg.n_heads,
        "n_kv_heads": cfg.n_kv_heads or cfg.n_heads,
        "head_dim": cfg.resolved_head_dim,
        "rotary_dims": cfg.rotary_dims or cfg.resolved_head_dim,
        "rotary_base": cfg.rotary_base, "key_heads": cfg.gdn_key_heads,
        "value_heads": cfg.gdn_value_heads, "key_dim": cfg.gdn_key_dim,
        "value_dim": cfg.gdn_value_dim, "top_k": cfg.moe_top_k,
        "experts_held": cfg.n_experts, "expert_offset": cfg.moe_expert_offset,
        "router_width": cfg.resolved_router_width,
    }
    spec.update(over)
    return spec


def delta_inputs(t, g_scale, dk=16, dv=24, seed=0, lead=(2, 3)):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], lead + (t, dk)))
    k = unit(jax.random.normal(ks[1], lead + (t, dk)))
    v = jax.random.normal(ks[2], lead + (t, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], lead + (t,)))
    g = -g_scale * jax.nn.softplus(jax.random.normal(ks[4], lead + (t,)))
    return q, k, v, beta, g


# T a multiple of the chunk, not a multiple, shorter than one chunk; a mild
# decay and one that underflows exp() inside a chunk (g down to about -150)
DELTA_CASES = [(128, 0.1), (150, 0.1), (37, 5.0), (150, 40.0), (64, 40.0)]


@pytest.mark.parametrize("t,g_scale", DELTA_CASES)
def test_chunked_delta_rule_equals_the_recurrence(t, g_scale):
    args = delta_inputs(t, g_scale)
    want, s_want = gated_delta_recurrent(*args, return_state=True)
    got, s_got = gated_delta_chunked(*args, chunk=64, return_state=True)
    assert float(jnp.abs(want).max()) > 0.1  # not a comparison of zeros
    assert float(jnp.abs(got - want).max()) < TOL
    assert float(jnp.abs(s_got - s_want).max()) < TOL
    # the dispatcher: "eager" is the recurrence, "xla" the chunked form
    assert jnp.array_equal(gated_delta_rule(*args, backend="eager"), want)
    assert jnp.array_equal(gated_delta_rule(*args, backend="xla"), got)


@pytest.mark.parametrize("t,g_scale", [(150, 0.1), (37, 5.0), (64, 40.0)])
def test_chunked_delta_rule_gradients_equal_the_recurrences(t, g_scale):
    args = delta_inputs(t, g_scale)
    weight = jnp.cos(jnp.arange(args[2].shape[-1]) + jnp.arange(t)[:, None])
    grads = [
        jax.grad(lambda *a: (fn(*a) * weight).sum(), argnums=(0, 1, 2, 3, 4))(*args)
        for fn in (gated_delta_recurrent, gated_delta_chunked)
    ]
    for want, got in zip(*grads):
        assert bool(jnp.isfinite(got).all())
        scale = float(jnp.abs(want).max())
        assert scale > 1e-6
        assert float(jnp.abs(got - want).max()) < 1e-4 * scale


# batch, rows a block: one row at a time (the XLA form at the benchmark
# cell's shape: 8 rows of 32 heads x T 8192 against a bound of one; on the
# chip that cell runs the kernels, tests/test_pallas_gated_delta.py), two at
# a time, and a batch the bound does not divide (3 rows, bound 2: one at a time)
@pytest.mark.parametrize("b,rows", [(2, 1), (4, 2), (3, 2)])
def test_delta_rule_by_rows_equals_the_recurrence_forward_and_grad(b, rows, monkeypatch):
    """The branch the XLA form takes at batch > 1: ``lax.map`` over
    blocks of rows, each under ``jax.checkpoint`` that keeps only the
    triangular inverses, with the inverse's own backward. Reached by
    shrinking the bound on heads x tokens of a block."""
    import orion_tpu.ops.gated_delta as gd

    t, h = 150, 3
    args = delta_inputs(t, 5.0, lead=(b, h))
    monkeypatch.setattr(gd, "_ROWS_HEADS_X_TOKENS", rows * h * t)
    mapped = []
    monkeypatch.setattr(jax.lax, "map", lambda f, xs: mapped.append(1) or _lax_map(f, xs))
    weight = jnp.cos(jnp.arange(args[2].shape[-1]) + jnp.arange(t)[:, None])
    want = gated_delta_recurrent(*args)
    got = jax.jit(lambda *a: gated_delta_rule(*a, backend="xla"))(*args)
    assert mapped, "the batch fitted one block: the plain call was tested, not the branch"
    assert float(jnp.abs(got - want).max()) < TOL
    loss = lambda fn: lambda *a: (fn(*a) * weight).sum()  # noqa: E731
    g_want = jax.grad(loss(gated_delta_recurrent), argnums=(0, 1, 2, 3, 4))(*args)
    g_got = jax.jit(jax.grad(
        loss(lambda *a: gated_delta_rule(*a, backend="xla")), argnums=(0, 1, 2, 3, 4)
    ))(*args)
    for w, g in zip(g_want, g_got):
        assert bool(jnp.isfinite(g).all())
        scale = float(jnp.abs(w).max())
        assert scale > 1e-6
        assert float(jnp.abs(g - w).max()) < 1e-4 * scale


_lax_map = jax.lax.map


def test_chunked_delta_rule_survives_repeated_keys():
    """Identical keys with beta = 1 make the in-chunk system as stiff as it
    gets (a Neumann series of it would overflow): block substitution holds."""
    t, dk, dv = 128, 8, 8
    k = jnp.tile(jnp.eye(dk)[0], (1, t, 1))
    v = jax.random.normal(jax.random.key(0), (1, t, dv))
    q, beta, g = k, jnp.ones((1, t)), jnp.zeros((1, t))
    got = gated_delta_chunked(q, k, v, beta, g)
    assert float(jnp.abs(got - gated_delta_recurrent(q, k, v, beta, g)).max()) < TOL
    assert float(jnp.abs(got - v).max()) < TOL  # the state holds the last value


def test_short_conv_is_causal_and_matches_the_reference():
    x = jax.random.normal(jax.random.key(0), (2, 19, 6))
    w = jax.random.normal(jax.random.key(1), (4, 6))
    got = causal_short_conv(x, w)
    assert float(jnp.abs(got - ref.short_conv(x, w)).max()) < 1e-6
    later = causal_short_conv(x.at[:, 10:].set(0.0), w)
    assert jnp.array_equal(later[:, :10], got[:, :10])  # no look-ahead


@functools.lru_cache(maxsize=None)
def _seeded(cfg):
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 96), 0, cfg.vocab_size)
    return model, jax.jit(model.init)(jax.random.key(0), toks)


def _layer(cfg, index):
    """(model, its seeded params, the params of block ``index``). The
    parameters do not depend on the backend: one seeded tree a width, which
    every backend's case applies."""
    model, params = _seeded(dataclasses.replace(cfg, backend=tiny().backend))
    return model, params, params["params"][f"block_{index}"]


@pytest.mark.parametrize("over", [
    dict(),                                       # 4 q heads on 2 kv heads, rotary 8 of 32
    dict(n_kv_heads=1, rotary_dims=32),           # one kv head, rotary on the whole head
    dict(n_kv_heads=4, rotary_dims=16),           # no grouping
], ids=["gqa2-rot8", "mqa-rot32", "mha-rot16"])
def test_gated_softmax_matches_reference(over):
    from orion_tpu.models.mixers import GatedSoftmaxAttention

    cfg = tiny(**over)
    x = jax.random.normal(jax.random.key(2), (2, 70, cfg.d_model))
    mixer = GatedSoftmaxAttention(cfg)
    attn = dict(jax.jit(mixer.init)(jax.random.key(0), x)["params"])  # the mixer's leaves alone
    # non-trivial q/k norm weights: they are initialised 0
    attn["q_norm"] = {"scale": 0.3 * jax.random.normal(jax.random.key(5), (32,))}
    attn["k_norm"] = {"scale": 0.3 * jax.random.normal(jax.random.key(6), (32,))}
    got = mixer.apply({"params": attn}, x)
    want = ref.gated_softmax(spec_of(cfg, head_block=3), attn, x)
    assert float(jnp.abs(want).max()) > 0.05
    assert float(jnp.abs(got - want).max()) < TOL


@pytest.mark.parametrize("backend,t", [
    ("xla", 70), ("xla", 128), ("eager", 70),
    ("pallas_interpret", 70), ("pallas_interpret", 128),  # the Mosaic kernels' body
])
def test_gated_delta_mixer_matches_reference(backend, t):
    from orion_tpu.models.mixers import GatedDeltaNet

    cfg = tiny(backend=backend)
    _, _, blk = _layer(cfg, 0)
    x = jax.random.normal(jax.random.key(2), (2, t, cfg.d_model))
    got = GatedDeltaNet(cfg).apply({"params": blk["attn"]}, x)
    want = ref.gated_delta(spec_of(cfg), blk["attn"], x)
    assert float(jnp.abs(want).max()) > 0.05
    assert float(jnp.abs(got - want).max()) < TOL


def _moe_apply(cfg, p, x):
    from orion_tpu.models.moe import MoEMLP

    return MoEMLP(cfg).apply({"params": p}, x, mutable=["losses", "moe_stats"])


# xla: sorted rows through ragged_dot; pallas_interpret: the grouped-matmul
# kernel's tile-aligned segments (512 tokens x top-2 = 1024 rows, its gate)
MOE_BACKENDS = [("xla", 96), ("pallas_interpret", 512)]


@pytest.mark.parametrize("width", [0, 16])
@pytest.mark.parametrize("backend,t", MOE_BACKENDS)
def test_moe_layer_matches_reference_with_all_experts_held(backend, t, width):
    """16 experts, all here, the router's width left at 0 or spelled out
    (16 = ``n_experts``): one layer, the ordinary dropless one, which is the
    reference's and counts nothing (served with ``live`` it masks and counts:
    tests/test_keye_vl2.py)."""
    cfg = tiny(n_experts=16, moe_router_width=width, backend=backend)
    assert not cfg.moe_held
    _, params, blk = _layer(cfg, 1)
    x = jax.random.normal(jax.random.key(2), (2, t, cfg.d_model))
    got, sown = _moe_apply(cfg, blk["mlp"], x)
    want = ref.moe(spec_of(dataclasses.replace(cfg, moe_router_width=16)), blk["mlp"], x)
    assert float(jnp.abs(want).max()) > 0.05
    assert float(jnp.abs(got - want).max()) < TOL
    assert "moe_stats" not in sown  # the plain dropless path counts nothing


@pytest.mark.parametrize("backend,t", MOE_BACKENDS)
def test_share_sum_of_all_chips_equals_the_uncut_layer(backend, t):
    """16 experts over 8 chips, 2 held each: the routed parts of all 8
    shares plus the shared expert ONCE are the uncut reference layer."""
    whole = tiny(n_experts=16, moe_router_width=16)
    _, _, blk = _layer(whole, 1)
    p = blk["mlp"]
    x = jax.random.normal(jax.random.key(2), (2, t, whole.d_model))
    want = ref.moe(spec_of(whole), p, x)
    shared = ref.shared_expert(spec_of(whole), p, x)
    total, held_rows = jnp.zeros_like(x), 0
    for chip in range(8):
        # moe_ep_buffer = 8 = router width / experts held: every row fits
        cfg = tiny(n_experts=2, moe_router_width=16, moe_expert_offset=2 * chip,
                   moe_ep_buffer=8.0, backend=backend)
        mine = {**p, **{n: p[n][2 * chip: 2 * chip + 2]
                        for n in ("experts_gate", "experts_up", "experts_down")}}
        got, sown = _moe_apply(cfg, mine, x)
        stats = {k: int(v[0]) for k, v in sown["moe_stats"].items()}
        assert stats["dropless_overflow"] == 0
        assert stats["rows_routed"] == 2 * t * whole.moe_top_k
        assert stats["rows_max_expert"] <= stats["rows_held"]
        held_rows += stats["rows_held"]
        # the share against the reference GIVEN the same share
        want_share = ref.moe(spec_of(cfg), mine, x)
        assert float(jnp.abs(got - want_share).max()) < TOL
        total = total + (got - shared)
    assert held_rows == 2 * t * whole.moe_top_k  # every routed row has one owner
    assert float(jnp.abs(total + shared - want).max()) < 4 * TOL


def test_held_rows_past_the_buffer_are_counted_not_silent():
    cfg = tiny(n_experts=2, moe_router_width=16, moe_ep_buffer=0.25)
    _, _, blk = _layer(tiny(n_experts=16, moe_router_width=16), 1)
    p = {**blk["mlp"], **{n: blk["mlp"][n][:2]
                          for n in ("experts_gate", "experts_up", "experts_down")}}
    x = jax.random.normal(jax.random.key(2), (2, 96, cfg.d_model))
    _, sown = _moe_apply(cfg, p, x)
    stats = {k: int(v[0]) for k, v in sown["moe_stats"].items()}
    budget = 16  # 0.25 x 384 rows x 2/16 = 12, rounded up to a multiple of 8
    assert stats["dropless_overflow"] == stats["rows_held"] - budget > 0


@pytest.fixture(scope="module")
def whole_model():
    cfg = tiny()
    model, params = _seeded(cfg)
    batch = jax.random.randint(jax.random.key(1), (2, 71), 0, cfg.vocab_size)
    # every norm weight off its initial 0 / 1, so a dropped one shows
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.key(9), len(leaves))
    leaves = [
        x + 0.1 * jax.random.normal(k, x.shape) if "norm" in jax.tree_util.keystr(p) else x
        for (p, x), k in zip(leaves, keys)
    ]
    return cfg, model, jax.tree_util.tree_unflatten(tree, leaves), batch


def test_whole_model_logits_and_loss_match_reference(whole_model):
    from orion_tpu.training.trainer import lm_loss

    cfg, model, params, batch = whole_model
    spec = spec_of(cfg)
    want = jax.jit(lambda p, t: ref.forward(spec, p, t))(params, batch[:, :-1])
    got = jax.jit(model.apply)(params, batch[:, :-1])
    assert float(jnp.abs(want).max()) > 1.0
    assert float(jnp.abs(got - want).max()) < WHOLE_TOL
    # the training loss is the reference's plus the (weighted) router losses
    plain = dataclasses.replace(cfg, moe_aux_weight=0.0, moe_zloss_weight=0.0)
    loss, stats = lm_loss(TransformerLM(plain), params, batch, return_stats=True)
    assert abs(float(loss) - float(ref.next_token_loss(spec, params, batch))) < TOL
    assert int(stats["moe_overflow"]) == 0
    assert int(stats["moe_rows_routed"]) == 4 * 2 * 70 * cfg.moe_top_k


def test_whole_model_gradients_match_reference(whole_model):
    from orion_tpu.training.trainer import lm_loss

    cfg, _, params, batch = whole_model
    plain = TransformerLM(dataclasses.replace(cfg, moe_aux_weight=0.0, moe_zloss_weight=0.0))
    got = jax.jit(jax.grad(lambda p: lm_loss(plain, p, batch)))(params)
    want = jax.jit(jax.grad(lambda p: ref.next_token_loss(spec_of(cfg), p, batch)))(params)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    checked = 0
    for path, w in jax.tree_util.tree_leaves_with_path(want):
        scale = float(jnp.abs(w).max())
        assert scale > 0, jax.tree_util.keystr(path)
        err = float(jnp.abs(flat_got[path] - w).max())
        assert err < WHOLE_TOL * scale, (jax.tree_util.keystr(path), err, scale)
        checked += 1
    assert checked == len(flat_got) == len(jax.tree.leaves(params))


@pytest.mark.parametrize("method", ["prefill", "decode"])
def test_serving_entry_points_refuse_the_new_layer_types(method):
    from orion_tpu.models.transformer import init_decode_state

    cfg = tiny()
    with pytest.raises(NotImplementedError, match="training forward only"):
        if method == "decode":
            init_decode_state(cfg, 2)
        else:
            model, params = _seeded(cfg)
            model.apply(params, jnp.zeros((1, 8), jnp.int32), method="prefill")


def test_train_cli_path_trains_the_preset_tiny():
    """``Trainer.train`` (what ``python -m orion_tpu.train --config
    qwen3_next_80b`` runs) on the tiny sizes: finite, falling loss, nothing
    dropped, the routing counters in the step metrics."""
    from orion_tpu.parallel.mesh import MeshConfig
    from orion_tpu.training.data import SyntheticDataset
    from orion_tpu.training.trainer import TrainConfig, Trainer

    cfg = TrainConfig(
        model=tiny(max_seq_len=64), steps=6, batch_size=2, seq_len=64, lr=1e-2,
        optimizer="adafactor", warmup_steps=1, schedule="constant",
        mesh=MeshConfig(dp=1), log_every=10**9,
    )
    trainer = Trainer(cfg)
    seen = []
    batch = jnp.asarray(SyntheticDataset(256, 64).batch(0, 0, 2))  # one batch, overfit
    trainer.train(itertools.repeat(batch), hook=lambda step, m: seen.append(m))
    losses = [float(m["loss"]) for m in seen]
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.5
    last = {k: int(seen[-1][k]) for k in seen[-1] if k.startswith("moe_")}
    assert last["moe_overflow"] == 0
    assert last["moe_rows_routed"] == 4 * 2 * 64 * 2
    assert 0 < last["moe_rows_max_expert"] <= last["moe_rows_held"] < last["moe_rows_routed"]
    # the grouped product's visits: tiles of 128 rows, a partial one an expert and layer
    held_experts = 4 * trainer.cfg.model.n_experts
    assert 0 <= last["moe_tiles_live"] - last["moe_rows_held"] / 128 < held_experts


# -- the training program is what it was ---------------------------------------
# The jaxpr of the tiny train forward (softmax scores, a gated shared expert,
# the 1.5x buffer, no ``live``), read on the parent of PR 59 (44d93ca) at the
# rehearse block's sizes; until then tests/test_openpangu_moe.py pinned it at
# sizes of its own, where PR 58 changed it: the held layer sows ``tiles_live``
# in training too (seven equations a layer, nothing else). The older presets'
# pins are tests/test_models.py's.
PINNED = ServedCase("qwen3_next_80b", over=dict(max_seq_len=128, remat=False), pins={"forward": "0875c37f22a3942a"})


def test_train_program_is_what_it_was():
    assert trace_pins(PINNED, ("forward",)) == PINNED.pins
