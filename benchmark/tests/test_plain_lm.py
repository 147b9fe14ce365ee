"""The plain reference against the program's own forward, tiny, CPU, fp32."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from reference import plain_lm


@pytest.mark.parametrize("layer_types", [None, ("swa", "linear"), ("softmax", "swa")])
def test_plain_lm_matches_transformer(layer_types):
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM

    cfg = dataclasses.replace(get_config("tiny"), layer_types=layer_types, window=16)
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.key(0), toks)
    spec = {"n_layers": cfg.n_layers, "n_heads": cfg.n_heads,
            "head_dim": cfg.resolved_head_dim,
            "layer_types": cfg.resolved_layer_types, "window": cfg.window}
    want = plain_lm.forward(spec, params, toks)
    got = model.apply(params, toks)
    assert float(jnp.abs(want).max()) > 1.0  # not a comparison of zeros
    assert float(jnp.abs(got - want).max()) < 1e-4


def test_window_and_normaliser_matter():
    """The reference is sensitive to what a shortcut would drop."""
    q, k, v = (jax.random.normal(jax.random.key(i), (1, 2, 48, 8)) for i in range(3))
    full = plain_lm.softmax_attention(q, k, v, None)
    swa = plain_lm.softmax_attention(q, k, v, 16)
    assert float(jnp.abs(full[:, :, :16] - swa[:, :, :16]).max()) < 1e-6
    assert float(jnp.abs(full[:, :, 16:] - swa[:, :, 16:]).max()) > 1e-3
    lin = plain_lm.linear_attention(q, k, v)
    # row t is a convex combination of v[:t+1]: row 0 is v[0]
    assert float(jnp.abs(lin[:, :, 0] - v[:, :, 0]).max()) < 1e-4
