"""What a rematted block keeps of its forward (``models/transformer.py::
REMAT_KEEPS``): a ``gated_softmax`` block under ``nn.remat`` holds the flash
forward's output and row log-sum-exp by name and runs that kernel ONCE in the
gradient program, where the policy-less remat every block had before ran it
twice; a block with experts whose rows move by list keeps the integer lists
of its counting sort; every other layer type lists no name, and its train
step is the program it was, to the text.

"The parent's remat" below is ``nn.remat(Block, static_argnums=(3,))`` with no
policy, put in ``_rematted``'s place: the same tree's code with the one
mechanism off, so that the two gradient programs differ in nothing else.
"""

import collections
import dataclasses
import io
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.analysis.jaxpr_audit import iter_eqns
from orion_tpu.models import transformer
from orion_tpu.models.configs import get_config
from orion_tpu.models.mixers import kernel_bh
from orion_tpu.obs.trace import Tracer, compile_totals, setup_record
from orion_tpu.ops.softmax_attention import softmax_attention
from orion_tpu.parallel.mesh import MeshConfig, make_mesh
from orion_tpu.training.data import DataLoader, SyntheticDataset
from orion_tpu.training.metrics import MetricsLogger
from orion_tpu.training.trainer import TrainConfig, Trainer

# benchmark/configs/qwen3_next_80b.json's ``rehearse`` widths, every block
# rematted as benchmark/workloads/qwen3_next_80b.train.json pins it
QWEN_TINY = dict(
    d_model=128, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=32,
    rotary_dims=8, gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16,
    gdn_value_dim=16, mlp_hidden=64, moe_shared_hidden=64, n_experts=8,
    moe_router_width=16, moe_top_k=2, vocab_size=256, dtype="float32",
    max_seq_len=128, remat=True, remat_skip=0,
)
# the ``rehearse`` widths of benchmark/configs/{lm_1b3,hybrid_1b3}.json, four
# layers deep so that the hybrid holds a rematted ``swa`` AND ``linear`` block
# under the cells' rehearsal ``remat_skip`` of 1
DENSE_TINY = dict(
    vocab_size=256, d_model=128, n_layers=4, n_heads=4, head_dim=32,
    mlp_hidden=384, dtype="float32", max_seq_len=128, remat_skip=1,
)
HYBRID_TINY = dict(DENSE_TINY, layer_types=("swa", "swa", "swa", "linear"), window=32)


def parents_remat(monkeypatch):
    monkeypatch.setattr(
        transformer, "_rematted",
        lambda layer_type, use_moe: nn.remat(transformer.Block, static_argnums=(3,)),
    )


def kept_since(before):
    now = compile_totals()
    return (int(now["remat_kept_residuals"] - before["remat_kept_residuals"]),
            int(now["remat_kept_bytes"] - before["remat_kept_bytes"]))


def kernel_calls(jaxpr):
    return collections.Counter(
        e.params["name"] for e in iter_eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"
    )


def gradient_program(cfg, tokens):
    """(jaxpr of value_and_grad, its value on seeded weights, what the policy
    counted while it was traced)."""
    model = transformer.TransformerLM(cfg)
    params = model.init(jax.random.key(0), tokens)["params"]

    def loss(p):
        logits, aux = model.apply(
            {"params": p}, tokens[:, :-1], mutable=["losses", "moe_stats"])
        picked = jnp.take_along_axis(
            jax.nn.log_softmax(logits), tokens[:, 1:, None], axis=-1)
        return -picked.mean() + sum(jax.tree.leaves(aux["losses"]))

    jax.clear_caches()  # a kernel entry's jaxpr may be kept from the other side
    before = compile_totals()
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(params)
    kept = kept_since(before)
    return jaxpr, jax.jit(jax.value_and_grad(loss))(params), kept


@pytest.mark.parametrize("backend", ["pallas_interpret", "xla"])
def test_rematted_gated_softmax_block_runs_its_flash_forward_once(backend, monkeypatch):
    cfg = dataclasses.replace(get_config("qwen3_next_80b"), **QWEN_TINY, backend=backend)
    assert cfg.resolved_layer_types.count("gated_softmax") == 1
    tokens = jax.random.randint(jax.random.key(1), (2, 65), 0, cfg.vocab_size)
    jaxpr, (loss, grads), kept = gradient_program(cfg, tokens)
    parents_remat(monkeypatch)
    was_jaxpr, (was_loss, was_grads), was_kept = gradient_program(cfg, tokens)

    flash = backend != "xla"  # the XLA softmax has no forward rule to name in
    assert kernel_calls(was_jaxpr)["flash_attn_fwd"] == (2 if flash else 0)
    assert kernel_calls(jaxpr)["flash_attn_fwd"] == (1 if flash else 0)
    # the backward's own kernels, and every other kernel's calls, are the parent's
    assert kernel_calls(jaxpr) + collections.Counter(flash_attn_fwd=flash) == kernel_calls(was_jaxpr)
    # out [B H, T, Dh] and lse [B H, T] as rows: 4 bytes a query, not a
    # [.., T, 1] column (a 128-lane tile a query on the chip)
    bh, t, dh = 2 * cfg.n_heads, 64, cfg.head_dim
    assert kept == ((2, bh * t * dh * 4 + bh * t * 4) if flash else (0, 0))
    assert was_kept == (0, 0)

    assert np.isfinite(float(loss)) and float(loss) == float(was_loss)
    leaves, was_leaves = jax.tree.leaves(grads), jax.tree.leaves(was_grads)
    assert len(leaves) == len(was_leaves) > 20
    for got, want in zip(leaves, was_leaves):
        assert float(jnp.abs(want).max()) > 0
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_trainers_first_step_says_what_the_policy_kept():
    model = dataclasses.replace(
        get_config("qwen3_next_80b"), **QWEN_TINY, backend="pallas_interpret")
    cfg = TrainConfig(model=model, steps=1, batch_size=2, seq_len=64,
                      optimizer="adafactor", log_every=1, warmup_steps=1,
                      mesh=MeshConfig(dp=1))
    mark = time.monotonic() * 1e6
    trainer = Trainer(cfg, tracer=Tracer(path=None, clock=time.monotonic))
    loader = DataLoader(SyntheticDataset(model.vocab_size, 64), 2, seed=0,
                        sharding=trainer.batch_shd)
    try:
        trainer.train(iter(loader), logger=MetricsLogger(stream=io.StringIO()))
    finally:
        loader.close()
    (first,) = [e for e in setup_record()
                if e["name"] == "setup.first_step" and e["ts"] >= mark]
    assert first["args"]["remat_kept_residuals"] == 2
    assert first["args"]["remat_kept_mb"] == round((8 * 64 * 32 * 4 + 8 * 64 * 4) / 1e6, 3)


def step_text(model, mesh_cfg, batch_size):
    cfg = TrainConfig(model=model, batch_size=batch_size, seq_len=64,
                      optimizer="adafactor", param_storage="bfloat16_sr", mesh=mesh_cfg)
    trainer = Trainer(cfg, materialize=False)
    batch = jax.ShapeDtypeStruct((batch_size, 65), np.int32, sharding=trainer.batch_shd)
    before = compile_totals()
    text = trainer._step_fn.lower(trainer.abstract_state(), batch).as_text()
    return text, kept_since(before)


@pytest.mark.parametrize("preset,over,mesh_cfg", [
    ("lm_1b3", DENSE_TINY, MeshConfig(dp=1)),
    ("hybrid_1b3", HYBRID_TINY, MeshConfig(dp=1)),
    ("lm_1b3", DENSE_TINY, MeshConfig(dp=1, fsdp=4)),
], ids=["lm_1b3", "hybrid_1b3", "lm_1b3-fsdp4"])
def test_dense_train_step_is_the_parents_program(preset, over, mesh_cfg, monkeypatch):
    """``linear`` / ``swa`` blocks list no name: with the names policy and
    with none the lowered step is the same text (the flash forward's names
    are in it, inside the rematted ``swa`` blocks, and kept by nobody)."""
    model = dataclasses.replace(get_config(preset), **over, backend="pallas_interpret")
    assert model.remat and set(model.resolved_layer_types) <= {"linear", "swa"}
    batch_size = 2 * mesh_cfg.resolve(len(jax.devices())).fsdp
    text, kept = step_text(model, mesh_cfg, batch_size)
    parents_remat(monkeypatch)
    was_text, _ = step_text(model, mesh_cfg, batch_size)
    assert kept == (0, 0)
    assert "optimization_barrier" in text  # a rematted block is in it
    assert text == was_text


def test_split_data_axis_keeps_the_flash_residuals_inside_the_shard_map():
    """``gated_softmax`` on a mesh whose data axes split calls the kernel
    through ``shard_map_bh``; the names are then inside the ``shard_map``
    equation, whose partial evaluation hands the block's policy down: the
    residuals are KEPT there too (one forward call in the gradient program,
    the output and rows counted), and the gradients are the unsharded ones."""
    cfg = dataclasses.replace(get_config("qwen3_next_80b"), **QWEN_TINY,
                              backend="pallas_interpret")
    mesh = make_mesh(MeshConfig(dp=2, fsdp=1, tp=2))
    ks = jax.random.split(jax.random.key(2), 4)
    q, k, v, w = (jax.random.normal(x, (4, 4, 64, 32)) for x in ks)
    attend = lambda a, b, c: softmax_attention(  # noqa: E731
        a, b, c, causal=True, backend=cfg.backend)

    def block(on, policy):
        def body(q, k, v):  # what the mixer does around the call, in small
            out = kernel_bh(cfg, on, attend, q * 2.0, k, v)
            return jnp.sum(jnp.tanh(out) * w)
        return jax.grad(jax.checkpoint(body, policy=policy), argnums=(0, 1, 2))

    keeps = transformer._keeps(transformer.REMAT_KEEPS["gated_softmax"])
    jax.clear_caches()
    before = compile_totals()
    jaxpr = jax.make_jaxpr(block(mesh, keeps))(q, k, v)
    # counted as one device's shard: 4 of the 16 (batch, head) rows
    assert kept_since(before) == (2, 4 * 64 * 32 * 4 + 4 * 64 * 4)
    assert any(e.primitive.name == "shard_map" for e in iter_eqns(jaxpr.jaxpr))
    assert kernel_calls(jaxpr)["flash_attn_fwd"] == 1
    assert kernel_calls(jax.make_jaxpr(block(mesh, None))(q, k, v))["flash_attn_fwd"] == 2
    with mesh:
        sharded = jax.jit(block(mesh, keeps))(q, k, v)
    plain = jax.jit(block(None, None))(q, k, v)
    for got, want in zip(sharded, plain):
        assert float(jnp.abs(want).max()) > 1e-3
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_rematted_expert_layer_keeps_its_lists_and_sorts_once():
    """``_held_rows_ffn``'s training form (rows moved by list) names what the
    experts and the backward read of the counting sort; under the policy of a
    block that lists ``moe_lists`` the gradient program sorts ONCE (one
    scatter of ``[M]`` where the policy-less remat has two, and none of the
    lists' selects again) and gives the same bits."""
    from orion_tpu.models.moe import _gmm_matmul, _held_rows_ffn

    assert transformer.REMAT_KEEPS["moe"] == ("moe_lists",)
    n, k, e, el, lo, d, h, budget = 48, 3, 8, 4, 2, 32, 24, 144
    rng = np.random.default_rng(0)
    flat = jnp.asarray(
        np.stack([rng.choice(e, k, replace=False) for _ in range(n)]).reshape(-1), jnp.int32)
    ks = jax.random.split(jax.random.key(3), 6)
    x2, mix = (jax.random.normal(key, (n, d)) for key in ks[:2])
    gates = jax.random.uniform(ks[2], (n * k,), minval=0.1)
    ws = tuple(0.3 * jax.random.normal(key, shape)
               for key, shape in zip(ks[3:], ((el, d, h), (el, d, h), (el, h, d))))
    matmul = _gmm_matmul(8, 128, True)
    assert matmul.by_list

    def layer(x2, gates, ws):
        y, _, _ = _held_rows_ffn(x2, flat, gates, ws, lo, budget, matmul, jnp.float32)
        return jnp.sum(y * mix)

    def grads(policy):
        return jax.grad(jax.checkpoint(layer, policy=policy), argnums=(0, 1, 2))

    def count(jaxpr, primitive):
        return sum(e.primitive.name == primitive for e in iter_eqns(jaxpr.jaxpr))

    keeps = transformer._keeps(transformer.REMAT_KEEPS["moe"])
    before = compile_totals()
    kept = jax.make_jaxpr(grads(keeps))(x2, gates, ws)
    residuals, nbytes = kept_since(before)
    again = jax.make_jaxpr(grads(None))(x2, gates, ws)
    assert (count(kept, "scatter"), count(again, "scatter")) == (1, 2)
    assert count(kept, "cumsum") < count(again, "cumsum")
    # listed, the lists' three, seg, gs, valid, pair: index-sized, a few KB here
    assert residuals == 8 and nbytes < 16 * n * k * 4
    assert kernel_calls(kept) == kernel_calls(again)  # the row movers run as often
    for got, want in zip(jax.tree.leaves(jax.jit(grads(keeps))(x2, gates, ws)),
                         jax.tree.leaves(jax.jit(grads(None))(x2, gates, ws))):
        assert float(jnp.abs(want).max()) > 0
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
