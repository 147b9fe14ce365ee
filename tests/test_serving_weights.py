"""The serving tree (``generate.serving_params``): the matmul weights cast to
the compute dtype ONCE at a ``Server``'s set-up, everything else as handed
in. One model of each served family at its benchmark configuration's
rehearsal sizes, bf16 compute over fp32 parameters (what ``lm_1b3``'s serve
cells hand the ``Server``)."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from served_contract import rehearsal_cfg

from orion_tpu import aot
from orion_tpu import generate as gen
from orion_tpu.generate import SampleConfig
from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.transformer import TransformerLM
from orion_tpu.obs.trace import Tracer
from orion_tpu.serving import DecodeRequest, ServeConfig, Server

GREEDY = SampleConfig(temperature=0.0)

# family -> (the benchmark configuration it is cut from, the leaves that must
# stay fp32 beside every norm scale: each pattern has to name a leaf)
FAMILIES = {
    "linear": ("lm_1b3", [r"\['embed'\]\['embedding'\]", r"\['pos_embed'\]\['embedding'\]"]),
    "swa_hybrid": ("hybrid_1b3", [r"\['embed'\]\['embedding'\]", r"\['pos_embed'\]\['embedding'\]"]),
    "gated_delta": ("olmo_hybrid_7b", [r"\['embed'\]\['embedding'\]", r"\['lm_head_kernel'\]",
                                       r"\['A_log'\]", r"\['dt_bias'\]", r"\['out_norm'\]",
                                       r"\['attn'\]\['conv'\]"]),
    "ssm": ("granite_4_0_h_micro", [r"\['embed'\]\['embedding'\]", r"\['A_log'\]", r"\['dt_bias'\]",
                                    r"\['D'\]", r"\['conv_bias'\]", r"\['out_norm'\]"]),
    "decay_linear_block_sparse": ("minicpm_sala", [r"\['embed'\]\['embedding'\]",
                                                   r"\['lm_head_kernel'\]"]),
    "latent_moe": ("openpangu_ultra_moe_718b", [r"\['embed'\]\['embedding'\]", r"\['router'\]\['kernel'\]",
                                                r"\['wkv_b'\]", r"\['experts_down'\]"]),
}


def family_cfg(config: str, **over) -> ModelConfig:
    """The configuration's file at its rehearsal sizes, as the benchmark
    builds it, with bf16 compute over fp32 parameters."""
    return rehearsal_cfg(config, **{**dict(
        dtype="bfloat16", param_dtype="float32", max_seq_len=256, backend="xla"), **over})


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    config, keep = FAMILIES[request.param]
    cfg = family_cfg(config)
    model = TransformerLM(cfg)
    params = jax.jit(model.init)(jax.random.key(7), jnp.zeros((1, 16), jnp.int32))
    return cfg, model, params, keep


def by_path(tree) -> dict:
    return {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def serve(model, params, prompts):
    """A ``Server`` run over ``prompts``: the tokens of every request, and
    the logits of one more decode step from the carry the run left."""
    tracer = Tracer(path=None, capacity=1 << 14)
    srv = Server(model, params, ServeConfig(chunk=4, slots=4, max_inflight=8, prefill_chunk=16,
                                            prefill_buckets="32,64", cost=False), tracer=tracer)
    handles = [srv.submit(DecodeRequest(prompt=p, max_new_tokens=9, sample=GREEDY, seed=i))
               for i, p in enumerate(prompts)]
    srv.serve(drain_when_idle=True)
    assert all(h.result.status == "ok" for h in handles)
    engine = srv.engine
    engine.flush_admissions()
    token, states, t = engine._carry[:3]
    logits, _ = model.apply(engine.params, token, states, t, method="decode_step")
    held, setup = engine.params, {e["name"]: e["args"] for e in tracer.events()
                                  if e.get("cat") == "setup"}
    gauge = srv.metrics.gauge("params_bytes_held").value()
    srv.close()
    return [np.asarray(h.result.tokens).reshape(-1) for h in handles], np.asarray(logits), held, setup, gauge


def test_server_answers_bitwise_as_from_the_handed_tree(family, monkeypatch):
    """Tokens and last-step logits of a ``Server`` over the serving tree are
    those of a ``Server`` whose cast is disabled, to the bit; the set-up
    record says what was cast."""
    cfg, model, params, _ = family
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32) for n in (40, 7, 64, 21, 33)]
    tokens, logits, held, setup, gauge = serve(model, params, prompts)
    monkeypatch.setattr(gen, "serving_params", lambda model, params: params)
    tokens0, logits0, held0, setup0, gauge0 = serve(model, params, prompts)
    for a, b in zip(tokens, tokens0):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(logits, logits0)
    assert len({tuple(t) for t in tokens}) > 1  # answers differ between requests
    # the engine holds the serving tree; the disabled one the tree as handed in
    assert all(x is y for x, y in zip(jax.tree.leaves(held0), jax.tree.leaves(params)))
    cast = [x for x, y in zip(jax.tree.leaves(params), jax.tree.leaves(held)) if x is not y]
    cast_bytes = sum(x.size * 4 for x in cast)
    assert cast and setup["setup.cast"]["leaves"] == len(cast)
    assert setup["setup.cast"]["seconds"] >= 0
    assert setup["setup.engine"]["params_cast_bytes"] == cast_bytes
    total = sum(x.size * 4 for x in jax.tree.leaves(params))
    assert setup["setup.engine"]["params_bytes"] == total - cast_bytes // 2 == gauge
    assert setup0["setup.cast"]["leaves"] == 0 == setup0["setup.engine"]["params_cast_bytes"]
    assert setup0["setup.engine"]["params_bytes"] == total == gauge0


def test_leaves_left_in_fp32_by_path(family):
    """Cast: the kernel of every projection whose module computes in bf16.
    Left as handed in, the SAME arrays: norm scales, both embedding tables,
    an untied head, the fp32 router, decay and conv parameters, parameters a
    module reads by hand."""
    cfg, model, params, keep = family
    handed, served = by_path(params), by_path(gen.serving_params(model, params))
    assert handed.keys() == served.keys()
    for pattern in keep + [r"norm\w*'\]\['scale'\]"]:
        named = [k for k in served if re.search(pattern, k)]
        assert named, pattern
        for k in named:
            assert served[k] is handed[k] and served[k].dtype == jnp.float32, k
    cast = {k for k in served if served[k] is not handed[k]}
    assert cast and all(k.endswith("['kernel']") and served[k].dtype == jnp.bfloat16 for k in cast)
    for k in cast:
        np.testing.assert_array_equal(np.asarray(served[k]), np.asarray(handed[k].astype(jnp.bfloat16)))
    # every block's projections are among them: mixer and MLP (or shared expert)
    for i in range(cfg.n_layers):
        mine = [k for k in cast if f"['block_{i}']" in k]
        assert any("['attn']" in k for k in mine) and any("['mlp']" in k for k in mine), (i, mine)


def test_a_bf16_tree_comes_back_the_same_object(family, monkeypatch):
    cfg, model, params, _ = family
    low = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    assert gen.serving_params(model, low) is low
    # and a Server over it says so: nothing cast, the engine holds that tree
    tracer = Tracer(path=None, capacity=1 << 10)
    srv = Server(model, low, ServeConfig(chunk=4, slots=4, cost=False), tracer=tracer)
    setup = {e["name"]: e["args"] for e in tracer.events() if e.get("cat") == "setup"}
    assert all(x is y for x, y in zip(jax.tree.leaves(srv.engine.params), jax.tree.leaves(low)))
    srv.close()
    assert setup["setup.cast"]["leaves"] == 0 == setup["setup.engine"]["params_cast_bytes"]
    served = gen.serving_params(model, params)
    assert gen.serving_params(model, served) is served  # nothing left to cast
    wide = TransformerLM(dataclasses.replace(cfg, dtype="float32"))
    assert gen.serving_params(wide, params) is params  # fp32 compute: nothing narrower
    # as the bf16 configurations hold theirs: fp32 vectors (norm scales, decay parameters)
    # beside bf16 matrices. No matmul weight is wide, so no decode step is traced
    mixed = jax.tree.map(lambda x: x.astype(jnp.bfloat16) if x.ndim >= 2 else x, params)
    monkeypatch.setattr(gen, "_compute_dtype_modules", lambda *a: pytest.fail("traced a decode step"))
    assert gen.serving_params(model, mixed) is mixed


def test_abstract_tree_agrees_leaf_for_leaf(family):
    """Shapes in, shapes out, the concrete tree's dtypes: what ``aot.py``
    keys and lowers the serving programs on."""
    cfg, model, params, _ = family
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    abstract, served = by_path(gen.serving_params(model, shapes)), by_path(gen.serving_params(model, params))
    assert abstract.keys() == served.keys()
    for k, x in served.items():
        assert isinstance(abstract[k], jax.ShapeDtypeStruct), k
        assert (abstract[k].shape, abstract[k].dtype) == (x.shape, x.dtype), k
    planned = by_path(aot._decode_abstracts(cfg, 4, "off", 1)[1])
    assert {k: (v.shape, v.dtype) for k, v in planned.items()} == \
        {k: (v.shape, v.dtype) for k, v in served.items()}


def test_no_weight_cast_is_left_in_the_decode_program():
    """The guard against the cast coming back: ``decode_batched`` lowered on
    the serving tree of the rehearsal ``lm_1b3`` converts no function argument
    of rank >= 2 to bf16 but the embedding table (the tied head's one cast a
    call); lowered on the tree as handed in it converts every kernel."""
    cfg = family_cfg("lm_1b3")

    def weight_casts(params):
        model, _, carry, rngs, active, _ = aot._decode_abstracts(cfg, 4, "off", 1)
        text = gen.DECODE_PROGRAMS["decode_batched"].lower(
            model, params, carry, rngs, active, 4, GREEDY).as_text()
        found = []
        for func in text.split("func.func ")[1:]:  # main and what its loop calls
            head, _, body = func.partition("{\n")
            wide = {a: dims for a, dims in re.findall(r"(%arg\d+): tensor<((?:\d+x){2,})f32>", head)}
            found += [wide[a] for a in re.findall(
                r"stablehlo\.convert (%arg\d+) : \(tensor<[\dx]+f32>\) -> tensor<[\dx]+bf16>", body)
                if a in wide]
        return sorted(found)

    shapes = jax.eval_shape(TransformerLM(cfg).init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    table = f"{cfg.vocab_size}x{cfg.d_model}x"
    assert weight_casts(gen.serving_params(TransformerLM(cfg), shapes)) == [table]
    assert len(weight_casts(shapes)) == 1 + 7 * cfg.n_layers
