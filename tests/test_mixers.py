"""The mixer contract (``orion_tpu/models/mixers/__init__.py::Mixer``): one
registry over every layer type; a servable mixer's declared zero state is
the state its prefill returns; a train-only mixer refuses every serving
entry point by name; ``rows_in_place`` is what the decode programs read;
and the parameter trees — what checkpoints, ``parallel/sharding.py::
spec_for_path`` and ``benchmark/reference/plain_gdn_moe.py`` read — are the
lists written down from the commit before the mixers moved (2a65443).
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from orion_tpu.generate import _freeze_rows
from orion_tpu.models.configs import LAYER_TYPES, get_config, hybrid_pattern
from orion_tpu.models.mixers import MIXERS, Mixer
from orion_tpu.models.transformer import TransformerLM, init_decode_state

SERVED = ("linear", "softmax", "swa", "gated_delta", "decay_linear", "block_sparse", "ssm",
          "latent", "indexed", "gated_conv")
TRAIN_ONLY = ("gated_softmax",)

# benchmark/configs/qwen3_next_80b.json's ``rehearse`` sizes
QWEN_CUT = dict(
    d_model=128, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=32,
    rotary_dims=8, gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16,
    gdn_value_dim=16, mlp_hidden=64, moe_shared_hidden=64, n_experts=8,
    moe_router_width=16, moe_top_k=2, vocab_size=256, dtype="float32",
    max_seq_len=128, remat=False,
)


def one_layer(lt):
    return get_config(
        "tiny", n_layers=1, layer_types=(lt,), window=8, max_seq_len=32,
        gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=16,
        n_kv_heads=2, sparse_kernel=4, sparse_stride=2, sparse_block=8,
        sparse_window=8, sparse_topk=2, sparse_dense_len=16,
        ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2,
        latent_q_rank=16, latent_kv_rank=8, latent_nope_dim=8, latent_rope_dim=4,
        latent_value_dim=8, index_heads=2, index_dim=8, index_topk=8,
    )


def test_registry_has_one_mixer_per_layer_type():
    assert set(MIXERS) == set(LAYER_TYPES)
    assert all(issubclass(m, Mixer) for m in MIXERS.values())
    assert {lt for lt, m in MIXERS.items() if m.rows_in_place} == {
        "linear", "softmax", "swa", "gated_delta", "decay_linear", "block_sparse", "ssm",
        "latent", "indexed", "gated_conv",
    }


@pytest.mark.parametrize("lt", SERVED)
def test_declared_decode_state_is_the_state_prefill_returns(lt):
    cfg = one_layer(lt)
    mixer = MIXERS[lt](cfg, lt)
    x = jax.random.normal(jax.random.key(0), (2, cfg.max_seq_len, cfg.d_model))
    params = mixer.init(jax.random.key(1), x)
    _, state = jax.eval_shape(
        lambda p, y: mixer.apply(p, y, method="prefill"), params, x
    )
    declared = jax.eval_shape(
        lambda: MIXERS[lt].decode_state(cfg, lt, 2, jnp.float32)
    )
    assert jax.tree.structure(declared) == jax.tree.structure(state)
    for want, got in zip(jax.tree.leaves(declared), jax.tree.leaves(state)):
        assert (want.shape, want.dtype) == (got.shape, got.dtype)
    # and the model-level zero state is that declaration, layer by layer
    (zero,) = init_decode_state(cfg, 2)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), zero) == jax.tree.map(
        lambda a: (a.shape, a.dtype), declared
    )
    assert not any(bool(jnp.any(a)) for a in jax.tree.leaves(zero))


@pytest.mark.parametrize("lt", ["softmax", "swa"])
def test_the_gated_form_keeps_the_contract_and_the_class_names_the_ring(lt):
    """``attn_gate`` with per-head norms and rotary by layer kind: one more
    projection ``wg`` in the tree, the declared state still the state prefill
    returns, and ``cache_is_ring`` / ``cache_rows`` say which cache a slot
    holds: a window's ring of ``window`` rows, or ``max_seq_len`` that grow."""
    cfg = dataclasses.replace(
        one_layer(lt), attn_gate=True, qk_norm="head", rotary_layers="swa")
    mixer = MIXERS[lt](cfg, lt)
    x = jax.random.normal(jax.random.key(0), (2, cfg.max_seq_len, cfg.d_model))
    params = jax.eval_shape(lambda: mixer.init(jax.random.key(1), x))
    assert sorted(params["params"]) == ["k_norm", "q_norm", "wg", "wk", "wo", "wq", "wv"]
    assert params["params"]["wg"]["kernel"].shape == (cfg.d_model, cfg.n_heads * cfg.resolved_head_dim)
    _, state = jax.eval_shape(lambda p, y: mixer.apply(p, y, method="prefill"), params, x)
    declared = jax.eval_shape(lambda: MIXERS[lt].decode_state(cfg, lt, 2, jnp.float32))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), declared) == jax.tree.map(
        lambda a: (a.shape, a.dtype), state)
    ring = lt == "swa"
    assert MIXERS[lt].cache_is_ring(cfg, lt) == ring
    assert MIXERS[lt].cache_rows(cfg, lt) == (cfg.window if ring else cfg.max_seq_len)
    assert declared["k"].shape[2] == MIXERS[lt].cache_rows(cfg, lt)
    assert not any(MIXERS[o].cache_is_ring(one_layer(o), o) for o in SERVED if o != "swa")


@pytest.mark.parametrize("lt", TRAIN_ONLY)
def test_train_only_mixer_refuses_every_serving_entry_point(lt):
    cfg = dataclasses.replace(get_config("qwen3_next_80b"), **QWEN_CUT)
    mixer = MIXERS[lt](cfg, lt)
    x = jnp.zeros((1, 8, cfg.d_model))
    params = mixer.init(jax.random.key(0), x)
    t, keep = jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32)
    calls = {
        "prefill": (x,),
        "prefill_extend": (x, {}, jnp.int32(0), jnp.int32(8)),
        "decode_step": (x[:, 0], {}, t),
        "verify_extend": (x, {}, t),
        "advance_verified": ({}, {}, t, keep),
    }
    for method, args in calls.items():
        with pytest.raises(NotImplementedError, match=repr(lt)):
            mixer.apply(params, *args, method=method)
    with pytest.raises(NotImplementedError, match=repr(lt)):
        MIXERS[lt].decode_state(cfg, lt, 1, jnp.float32)
    with pytest.raises(NotImplementedError, match="training forward only"):
        init_decode_state(cfg, 1)


@pytest.mark.parametrize("lt", ["gated_delta", "decay_linear", "block_sparse", "indexed",
                                "gated_conv"])
def test_served_without_a_speculative_pair(lt):
    """Served (prefill, pieces, the decode step), but the speculative
    verify / advance entry points stay the base class's, which raise."""
    cfg = one_layer(lt)
    mixer = MIXERS[lt](cfg, lt)
    x = jnp.zeros((1, 8, cfg.d_model))
    params = mixer.init(jax.random.key(0), x)
    t, keep = jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32)
    for method, args in {"verify_extend": (x, {}, t),
                         "advance_verified": ({}, {}, t, keep)}.items():
        with pytest.raises(NotImplementedError, match=repr(lt)):
            mixer.apply(params, *args, method=method)


def test_freeze_rows_skips_exactly_the_rows_in_place_layers():
    """With a row list the decode programs select back only the layers
    whose step touched every row: a ``rows_in_place`` layer's new state is
    passed through as the object it is."""
    cfg = get_config(
        "tiny", n_layers=3, layer_types=("swa", "linear", "softmax"),
        window=8, max_seq_len=16,
    )
    model = TransformerLM(cfg)
    old = init_decode_state(cfg, 2)
    new = jax.tree.map(lambda a: a + 1, old)
    mask = jnp.array([True, False])
    out = _freeze_rows(model, object(), mask, new, old)
    assert all(out[i] is new[i] for i in range(3))  # every served mixer is in place
    # without a row list every layer is selected
    out = _freeze_rows(model, None, mask, new, old)
    assert not bool(jnp.any(out[1]["s"][1])) and bool(jnp.all(out[1]["s"][0] == 1))
    for i in (0, 2):
        for k in ("k", "v"):
            assert bool(jnp.all(out[i][k][0] == 1)) and not bool(jnp.any(out[i][k][1]))


# -- the checkpoint-compatibility pin ----------------------------------------
# "path shape dtype" of every parameter, in tree order, as the commit before
# models/mixers/ (2a65443) printed them with ``leaves`` below

PINNED_CONFIGS = {
    "tiny": get_config("tiny"),
    "hybrid4": get_config(
        "tiny", n_layers=4, layer_types=hybrid_pattern(4), window=16,
        max_seq_len=64,
    ),
    "qwen3_next_cut": dataclasses.replace(
        get_config("qwen3_next_80b"), **QWEN_CUT
    ),
}

PINNED = {
    "tiny": """
params/block_0/attn/wk/kernel 128x128 float32
params/block_0/attn/wo/kernel 128x128 float32
params/block_0/attn/wq/kernel 128x128 float32
params/block_0/attn/wv/kernel 128x128 float32
params/block_0/mlp/down/kernel 384x128 float32
params/block_0/mlp/gate/kernel 128x384 float32
params/block_0/mlp/up/kernel 128x384 float32
params/block_0/norm1/scale 128 float32
params/block_0/norm2/scale 128 float32
params/block_1/attn/wk/kernel 128x128 float32
params/block_1/attn/wo/kernel 128x128 float32
params/block_1/attn/wq/kernel 128x128 float32
params/block_1/attn/wv/kernel 128x128 float32
params/block_1/mlp/down/kernel 384x128 float32
params/block_1/mlp/gate/kernel 128x384 float32
params/block_1/mlp/up/kernel 128x384 float32
params/block_1/norm1/scale 128 float32
params/block_1/norm2/scale 128 float32
params/embed/embedding 256x128 float32
params/final_norm/scale 128 float32
params/pos_embed/embedding 512x128 float32
""",
    "hybrid4": """
params/block_0/attn/wk/kernel 128x128 float32
params/block_0/attn/wo/kernel 128x128 float32
params/block_0/attn/wq/kernel 128x128 float32
params/block_0/attn/wv/kernel 128x128 float32
params/block_0/mlp/down/kernel 384x128 float32
params/block_0/mlp/gate/kernel 128x384 float32
params/block_0/mlp/up/kernel 128x384 float32
params/block_0/norm1/scale 128 float32
params/block_0/norm2/scale 128 float32
params/block_1/attn/wk/kernel 128x128 float32
params/block_1/attn/wo/kernel 128x128 float32
params/block_1/attn/wq/kernel 128x128 float32
params/block_1/attn/wv/kernel 128x128 float32
params/block_1/mlp/down/kernel 384x128 float32
params/block_1/mlp/gate/kernel 128x384 float32
params/block_1/mlp/up/kernel 128x384 float32
params/block_1/norm1/scale 128 float32
params/block_1/norm2/scale 128 float32
params/block_2/attn/wk/kernel 128x128 float32
params/block_2/attn/wo/kernel 128x128 float32
params/block_2/attn/wq/kernel 128x128 float32
params/block_2/attn/wv/kernel 128x128 float32
params/block_2/mlp/down/kernel 384x128 float32
params/block_2/mlp/gate/kernel 128x384 float32
params/block_2/mlp/up/kernel 128x384 float32
params/block_2/norm1/scale 128 float32
params/block_2/norm2/scale 128 float32
params/block_3/attn/wk/kernel 128x128 float32
params/block_3/attn/wo/kernel 128x128 float32
params/block_3/attn/wq/kernel 128x128 float32
params/block_3/attn/wv/kernel 128x128 float32
params/block_3/mlp/down/kernel 384x128 float32
params/block_3/mlp/gate/kernel 128x384 float32
params/block_3/mlp/up/kernel 128x384 float32
params/block_3/norm1/scale 128 float32
params/block_3/norm2/scale 128 float32
params/embed/embedding 256x128 float32
params/final_norm/scale 128 float32
params/pos_embed/embedding 64x128 float32
""",
    "qwen3_next_cut": """
params/block_0/attn/A_log 4 float32
params/block_0/attn/conv 4x128 float32
params/block_0/attn/dt_bias 4 float32
params/block_0/attn/in_ba/kernel 128x8 float32
params/block_0/attn/in_qkvz/kernel 128x192 float32
params/block_0/attn/out_norm 16 float32
params/block_0/attn/wo/kernel 64x128 float32
params/block_0/mlp/experts_down 8x64x128 float32
params/block_0/mlp/experts_gate 8x128x64 float32
params/block_0/mlp/experts_up 8x128x64 float32
params/block_0/mlp/router/kernel 128x16 float32
params/block_0/mlp/shared_down/kernel 64x128 float32
params/block_0/mlp/shared_gate/kernel 128x64 float32
params/block_0/mlp/shared_scale/kernel 128x1 float32
params/block_0/mlp/shared_up/kernel 128x64 float32
params/block_0/norm1/scale 128 float32
params/block_0/norm2/scale 128 float32
params/block_1/attn/A_log 4 float32
params/block_1/attn/conv 4x128 float32
params/block_1/attn/dt_bias 4 float32
params/block_1/attn/in_ba/kernel 128x8 float32
params/block_1/attn/in_qkvz/kernel 128x192 float32
params/block_1/attn/out_norm 16 float32
params/block_1/attn/wo/kernel 64x128 float32
params/block_1/mlp/experts_down 8x64x128 float32
params/block_1/mlp/experts_gate 8x128x64 float32
params/block_1/mlp/experts_up 8x128x64 float32
params/block_1/mlp/router/kernel 128x16 float32
params/block_1/mlp/shared_down/kernel 64x128 float32
params/block_1/mlp/shared_gate/kernel 128x64 float32
params/block_1/mlp/shared_scale/kernel 128x1 float32
params/block_1/mlp/shared_up/kernel 128x64 float32
params/block_1/norm1/scale 128 float32
params/block_1/norm2/scale 128 float32
params/block_2/attn/A_log 4 float32
params/block_2/attn/conv 4x128 float32
params/block_2/attn/dt_bias 4 float32
params/block_2/attn/in_ba/kernel 128x8 float32
params/block_2/attn/in_qkvz/kernel 128x192 float32
params/block_2/attn/out_norm 16 float32
params/block_2/attn/wo/kernel 64x128 float32
params/block_2/mlp/experts_down 8x64x128 float32
params/block_2/mlp/experts_gate 8x128x64 float32
params/block_2/mlp/experts_up 8x128x64 float32
params/block_2/mlp/router/kernel 128x16 float32
params/block_2/mlp/shared_down/kernel 64x128 float32
params/block_2/mlp/shared_gate/kernel 128x64 float32
params/block_2/mlp/shared_scale/kernel 128x1 float32
params/block_2/mlp/shared_up/kernel 128x64 float32
params/block_2/norm1/scale 128 float32
params/block_2/norm2/scale 128 float32
params/block_3/attn/k_norm/scale 32 float32
params/block_3/attn/q_norm/scale 32 float32
params/block_3/attn/wk/kernel 128x64 float32
params/block_3/attn/wo/kernel 128x128 float32
params/block_3/attn/wq/kernel 128x256 float32
params/block_3/attn/wv/kernel 128x64 float32
params/block_3/mlp/experts_down 8x64x128 float32
params/block_3/mlp/experts_gate 8x128x64 float32
params/block_3/mlp/experts_up 8x128x64 float32
params/block_3/mlp/router/kernel 128x16 float32
params/block_3/mlp/shared_down/kernel 64x128 float32
params/block_3/mlp/shared_gate/kernel 128x64 float32
params/block_3/mlp/shared_scale/kernel 128x1 float32
params/block_3/mlp/shared_up/kernel 128x64 float32
params/block_3/norm1/scale 128 float32
params/block_3/norm2/scale 128 float32
params/embed/embedding 256x128 float32
params/final_norm/scale 128 float32
params/lm_head_kernel 128x256 float32
""",
}


def leaves(cfg):
    shapes = jax.eval_shape(
        TransformerLM(cfg).init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )
    return [
        "/".join(str(getattr(k, "key", k)) for k in path)
        + " " + "x".join(map(str, x.shape)) + " " + str(x.dtype)
        for path, x in jax.tree_util.tree_leaves_with_path(shapes)
    ]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_parameter_tree_is_what_checkpoints_hold(name):
    assert leaves(PINNED_CONFIGS[name]) == PINNED[name].strip().split("\n")
