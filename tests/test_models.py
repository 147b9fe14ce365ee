"""Model tests (SURVEY.md §4): forward shape/finiteness, and the decisive
linear-attention invariant — parallel forward == prefill + recurrent decode
— on a model mixing all three layer types."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from served_contract import ServedCase, tiny_cfg, trace_pins

from orion_tpu.models import (
    LRAClassifier,
    ModelConfig,
    TransformerLM,
    get_config,
    init_decode_state,
)

MIXED = ModelConfig(
    name="mixed_test",
    vocab_size=64,
    d_model=32,
    n_layers=3,
    n_heads=2,
    layer_types=("linear", "softmax", "swa"),
    window=4,
    max_seq_len=32,
    dtype="float32",
    backend="xla",
)


def test_lm_forward_shapes():
    cfg = get_config("tiny", backend="xla")
    model = TransformerLM(cfg)
    toks = jnp.arange(2 * 16).reshape(2, 16) % cfg.vocab_size
    params = model.init(jax.random.PRNGKey(0), toks)
    logits = model.apply(params, toks)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("cfg_over", [{}, {"mlp": "gelu", "norm": "layernorm",
                                           "tie_embeddings": False}])
def test_lm_variants(cfg_over):
    cfg = dataclasses.replace(MIXED, **cfg_over)
    model = TransformerLM(cfg)
    toks = jnp.arange(2 * 12).reshape(2, 12) % cfg.vocab_size
    params = model.init(jax.random.PRNGKey(1), toks)
    logits = model.apply(params, toks)
    assert logits.shape == (2, 12, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("feature_map", ["elu1", "learnable", "favor"])
def test_parallel_vs_prefill_decode_parity(feature_map):
    """logits from one parallel forward == prefill(T0) then T-T0 decode steps."""
    cfg = dataclasses.replace(MIXED, feature_map=feature_map)
    model = TransformerLM(cfg)
    t, t0 = 14, 6
    toks = (jax.random.randint(jax.random.PRNGKey(2), (2, t), 0, cfg.vocab_size))
    params = model.init(jax.random.PRNGKey(3), toks)

    full = model.apply(params, toks)  # [B, T, V]

    pre_logits, states = model.apply(params, toks[:, :t0], method="prefill")
    np.testing.assert_allclose(pre_logits, full[:, :t0], atol=1e-4, rtol=1e-4)

    got = []
    for step in range(t0, t):
        logits, states = model.apply(
            params, toks[:, step], states, jnp.int32(step), method="decode_step"
        )
        got.append(logits)
    got = jnp.stack(got, axis=1)
    np.testing.assert_allclose(got, full[:, t0:], atol=1e-4, rtol=1e-4)


def test_decode_from_zero_state():
    """init_decode_state matches prefill's pytree structure and decoding from
    scratch equals the parallel forward."""
    model = TransformerLM(MIXED)
    t = 8
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, t), 0, MIXED.vocab_size)
    params = model.init(jax.random.PRNGKey(5), toks)
    full = model.apply(params, toks)

    states = init_decode_state(MIXED, batch_size=1, dtype=jnp.float32)
    _, pstates = model.apply(params, toks[:, :1], method="prefill")
    assert jax.tree.structure(states) == jax.tree.structure(pstates)

    got = []
    for step in range(t):
        logits, states = model.apply(
            params, toks[:, step], states, jnp.int32(step), method="decode_step"
        )
        got.append(logits)
    np.testing.assert_allclose(
        jnp.stack(got, axis=1), full, atol=1e-4, rtol=1e-4
    )


def test_classifier_padding_invariance():
    cfg = get_config("lra_listops_linear", max_seq_len=64, backend="xla")
    model = LRAClassifier(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(6), (2, 20), 0, cfg.vocab_size)
    mask = jnp.ones((2, 20), dtype=bool)
    params = model.init(jax.random.PRNGKey(7), toks, mask)
    base = model.apply(params, toks, mask)
    assert base.shape == (2, cfg.n_classes)

    # padding tokens behind the mask must not change logits
    toks_pad = jnp.concatenate([toks, jnp.full((2, 5), 3)], axis=1)
    mask_pad = jnp.concatenate([mask, jnp.zeros((2, 5), dtype=bool)], axis=1)
    padded = model.apply(params, toks_pad, mask_pad)
    np.testing.assert_allclose(padded, base, atol=1e-5, rtol=1e-5)


def test_classifier_softmax_variant():
    cfg = get_config("lra_listops_softmax", max_seq_len=64, backend="xla")
    model = LRAClassifier(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(8), (2, 16), 0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(9), toks)
    out = model.apply(params, toks)
    assert out.shape == (2, cfg.n_classes)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_remat_matches_no_remat():
    cfg = dataclasses.replace(MIXED, remat=False)
    cfg_r = dataclasses.replace(MIXED, remat=True)
    toks = jax.random.randint(jax.random.PRNGKey(10), (1, 10), 0, cfg.vocab_size)
    m, mr = TransformerLM(cfg), TransformerLM(cfg_r)
    params = m.init(jax.random.PRNGKey(11), toks)
    np.testing.assert_allclose(
        m.apply(params, toks), mr.apply(params, toks), atol=1e-6, rtol=1e-6
    )


def test_remat_skip_matches():
    # remat_skip leaves the last K blocks un-rematted: identical math,
    # identical param tree (same block names/shapes), loss AND grads equal
    cfg = dataclasses.replace(MIXED, remat=True, remat_skip=2)
    toks = jax.random.randint(jax.random.PRNGKey(20), (1, 10), 0, cfg.vocab_size)
    m = TransformerLM(dataclasses.replace(MIXED, remat=True))
    ms = TransformerLM(cfg)
    params = m.init(jax.random.PRNGKey(21), toks)
    assert jax.tree.structure(params) == jax.tree.structure(
        ms.init(jax.random.PRNGKey(21), toks)
    )
    np.testing.assert_allclose(
        m.apply(params, toks), ms.apply(params, toks), atol=1e-6, rtol=1e-6
    )

    def loss(mod):
        return lambda p: jnp.sum(mod.apply(p, toks) ** 2)

    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5),
        jax.grad(loss(m))(params), jax.grad(loss(ms))(params),
    )


# -- the two oldest presets are what they were ---------------------------------
# Pinned in the file of the preset they pin (the served configurations' are in
# their own files' ``ServedCase.pins``): a PR that adds a field to
# ``ModelConfig`` and changes what these trace or compute fails HERE. All read
# on the parent of PR 59 (44d93ca) at the sizes of the presets' own
# ``rehearse`` blocks; until then tests/test_granite_hybrid.py pinned the
# programs (at sizes of its own, where PR 51 changed ``hybrid_1b3``'s piece: a
# window layer's piece writes its last min(length, window) rows into the ring
# at their slots, one scatter, where it rebuilt all ``window`` rows) and
# tests/test_qwen3_next.py the tree and the logits.
LM_1B3 = ServedCase("lm_1b3", over=dict(max_seq_len=128, remat=False), pins={
    "forward": "cc4f861c36f01b53", "prefill": "8eb8b78443b25087",
    "piece": "793ceeba9b1a24ea", "step": "6d977d51dc7e0331"})
HYBRID_1B3 = ServedCase("hybrid_1b3", over=dict(max_seq_len=128, remat=False), pins={
    "forward": "962e37b8ef9c4072", "prefill": "1e3f293050ea13a7",
    "piece": "05e89162f9914671", "step": "45b69d7543bf72e4"})
# sha256 of (path, shape, dtype) of every parameter of the full preset, and of
# the float32 logits of the tiny model on seeded weights and tokens
WAS = {
    "lm_1b3": ("93d48b1c0999df4354acee038db1d0c81c82da52d701a68fb879ac4340bdc304",
               "641480d61d6da9f1b26b5f27fe15dab90d9d160e9d2d998714d8cdba622e0b50"),
    "hybrid_1b3": ("93d48b1c0999df4354acee038db1d0c81c82da52d701a68fb879ac4340bdc304",
                   "1c35d13dea3f6a75aed8e152abb8d758151b7e779726826c80c657efb0755945"),
}


@pytest.mark.parametrize("program", sorted(LM_1B3.pins))
@pytest.mark.parametrize("case", [LM_1B3, HYBRID_1B3], ids=lambda c: c.name)
def test_oldest_presets_trace_the_pinned_programs(case, program):
    assert trace_pins(case, (program,))[program] == case.pins[program]


def fingerprints(case):
    shapes = jax.eval_shape(
        TransformerLM(get_config(case.name)).init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    tree = hashlib.sha256(repr([
        (jax.tree_util.keystr(p), x.shape, str(x.dtype))
        for p, x in jax.tree_util.tree_leaves_with_path(shapes)
    ]).encode()).hexdigest()
    model = TransformerLM(tiny_cfg(case))
    toks = jax.random.randint(jax.random.key(1), (2, 64), 0, 256)
    params = jax.jit(model.init)(jax.random.key(0), toks)
    logits = np.asarray(jax.jit(model.apply)(params, toks), np.float32)
    return tree, hashlib.sha256(logits.tobytes()).hexdigest()


@pytest.mark.parametrize("case", [LM_1B3, HYBRID_1B3], ids=lambda c: c.name)
def test_oldest_presets_are_bitwise_what_they_were(case):
    assert fingerprints(case) == WAS[case.name]
