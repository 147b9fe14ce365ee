"""Pipeline-parallel forward for the TransformerLM (SURVEY.md P10).

Adapter from the flax model to the GPipe primitive (pipeline.py): the
per-block param subtrees live stacked on a leading axis (sharded over
pp), the block stack streams through the pp ring, and embedding/head run on
every stage (replicated over pp; still dp/fsdp/tp-sharded by GSPMD — the
pipeline shard_map is partial-manual over pp only).

Heterogeneous depth patterns stack at the GROUP level: the smallest period
g of the layer-type pattern (``stage_group``) makes groups of g consecutive
blocks structurally identical, so both the all-linear 1.3B (g=1) and the
hybrid 7B's swa,swa,swa,linear × 8 (g=4) pipeline — pp must divide
n_layers/g.

Two param layouts are accepted:
- standard flax layout (block_0..block_{L-1}) — restacked on the fly
  (a full param copy; fine for one-off calls, not per step), or
- pipeline layout ({"blocks_stacked": ...} with no block_i entries) — the
  Trainer's pp>1 native state format (training/trainer.py), zero-copy.

``stack_lm_params``/``unstack_lm_params`` convert checkpoints between the
two layouts (e.g. to serve a pp-trained checkpoint with generate.py).

Composes with autodiff: `pp_lm_loss` differentiates end-to-end, the
backward being the reverse pipeline the scan+ppermute transpose yields.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from orion_tpu.models.transformer import Block, TransformerLM
from orion_tpu.parallel.pipeline import (
    pipeline_apply,
    stack_params,
    unstack_params,
)

Array = jax.Array


def stage_group(cfg) -> int:
    """Smallest period g such that the BLOCK-STRUCTURE pattern — layer type
    AND which feed-forward part the block has (routed experts, a dense MLP or
    none: ``cfg.block_form``) — repeats with period g and g divides n_layers.
    Blocks are stacked in GROUPS of g — a group's param structure is then
    identical across depth even for heterogeneous patterns (e.g. the 7B's
    swa,swa,swa,linear × 8 has g=4; an every-other-layer MoE has g=2),
    which is what lets such models pipeline. Homogeneous models get g=1."""
    sig = [
        (lt, *cfg.block_form(i).values())
        for i, lt in enumerate(cfg.resolved_layer_types)
    ]
    n = len(sig)
    for g in range(1, n):
        if n % g == 0 and all(sig[i] == sig[i % g] for i in range(n)):
            return g
    return n  # aperiodic pattern: one group of all layers (pp=1 only)


def stack_lm_blocks(model: TransformerLM, params: Any) -> Any:
    """Pull block_0..block_{L-1} out of a TransformerLM param tree and stack
    them on a leading group axis (shard it over pp). Each stacked element is
    a group of ``stage_group(cfg)`` consecutive blocks ({"sub_0": ...})."""
    p = params["params"]
    g = stage_group(model.cfg)
    groups = [
        {
            f"sub_{j}": p[f"block_{k * g + j}"]
            for j in range(g)
        }
        for k in range(model.cfg.n_layers // g)
    ]
    return stack_params(groups)


def stack_lm_params(model: TransformerLM, params: Any) -> Any:
    """Standard layout -> pipeline layout: {"blocks_stacked": [L/g, ...], rest}."""
    stacked = stack_lm_blocks(model, params)
    p = dict(params["params"])
    for i in range(model.cfg.n_layers):
        p.pop(f"block_{i}")
    p["blocks_stacked"] = stacked
    return {**params, "params": p}


def unstack_lm_params(model: TransformerLM, params: Any) -> Any:
    """Pipeline layout -> standard layout (e.g. to serve a pp-trained
    checkpoint with generate.py / evaluate.py)."""
    p = dict(params["params"])
    stacked = p.pop("blocks_stacked")
    g = stage_group(model.cfg)
    if "sub_0" not in stacked:
        # pre-group layout (plain stacked block trees, g==1 era): wrap so
        # old pp checkpoints keep restoring
        g, stacked = 1, {"sub_0": stacked}
    for k, group in enumerate(unstack_params(stacked, model.cfg.n_layers // g)):
        for j in range(g):
            p[f"block_{k * g + j}"] = group[f"sub_{j}"]
    return {**params, "params": p}


def pp_lm_logits(
    model: TransformerLM,
    params: Any,
    tokens: Array,
    mesh: Mesh,
    *,
    n_micro: int,
    axis: str = "pp",
    dropout_rng: Any = None,
    return_aux: bool = False,
    full_manual: Any = None,
):
    """tokens [B, T] -> logits [B, T, V], blocks executed as a pp pipeline.

    Matches ``model.apply(params, tokens)`` exactly (same submodules, same
    dtypes); only the block loop is restructured. ``dropout_rng`` enables
    dropout (statistically equivalent to the non-pp forward: per-microbatch
    masks — see pipeline_apply). ``return_aux`` returns (logits, aux) where
    aux is the microbatch-averaged sum of the blocks' sown "losses"
    collection (MoE load-balance/z losses, models/moe.py).

    ``full_manual`` (None = auto): run the pipeline shard_map manual over
    EVERY mesh axis — the Mosaic-legal form (pipeline_apply docstring), so
    a ``backend="pallas"`` model keeps its kernels inside the pipeline
    body instead of falling back to the XLA attention forms. Auto turns it
    on exactly when it is both needed and possible: a real-Mosaic backend
    on a tp == ep == 1 mesh.
    """
    cfg = model.cfg
    assert model.mesh is None or model.mesh is mesh, (
        "pp_lm_logits: the model was built with a different mesh than the "
        "pipeline's — _embed's sharding constraints would clash; pass the "
        "same mesh to both (Trainer does) or build the model without one"
    )
    stacked = params["params"].get("blocks_stacked")
    if stacked is None:
        stacked = stack_lm_blocks(model, params)

    t = tokens.shape[-1]
    x = model.apply(
        params, tokens, jnp.arange(t), method=lambda m, tok, pos: m._embed(tok, pos)
    )
    g = stage_group(cfg)
    sp_on = cfg.sequence_parallel and mesh.shape.get("sp", 1) > 1
    if sp_on:
        assert tokens.shape[-1] % mesh.shape["sp"] == 0, (
            tokens.shape, dict(mesh.shape)
        )
    if full_manual is None:
        from orion_tpu.ops.dispatch import resolve

        # auto only when it costs nothing: a real-Mosaic backend and no
        # axis whose sharding the manual body would have to re-implement.
        # fsdp > 1 is deliberately EXCLUDED from auto — full_manual enters
        # stage params via P('pp'), gathering the full stage up front
        # instead of GSPMD's layer-at-a-time gather, so it trades ZeRO
        # memory for kernels; opt in explicitly if that trade is wanted.
        full_manual = (
            resolve(cfg.backend) == "pallas"
            and mesh.shape.get("tp", 1) == 1
            and mesh.shape.get("ep", 1) == 1
            and mesh.shape.get("fsdp", 1) == 1
        )
    blocks = [
        Block(
            cfg, cfg.resolved_layer_types[j], True, None, sp_on,
            sp_local_kernels=bool(full_manual), **cfg.block_form(j),
        )
        for j in range(g)
    ]

    def apply_block(j, group_params, h, key):
        kwargs = {}
        if key is not None:
            kwargs = {
                "deterministic": False,
                "rngs": {"dropout": jax.random.fold_in(key, j)},
            }
        if not return_aux:
            return blocks[j].apply(
                {"params": group_params[f"sub_{j}"]}, h, **kwargs
            ), 0.0
        h, v = blocks[j].apply(
            {"params": group_params[f"sub_{j}"]}, h, mutable="losses", **kwargs
        )
        aux = jnp.zeros((), jnp.float32)
        for leaf in jax.tree.leaves(v.get("losses", {})):
            aux = aux + leaf
        return h, aux

    # pipeline_apply calls layer_fn with (params, h) or (params, h, key)
    # depending on whether rng is passed — one body serves both arities
    def layer_fn(group_params, h, key=None):
        aux = jnp.zeros((), jnp.float32)
        for j in range(g):
            h, a = apply_block(j, group_params, h, key)
            aux = aux + a
        return (h, aux) if return_aux else h

    if cfg.remat:
        # NB remat granularity here is per GROUP of g blocks (the pipeline's
        # unit of work), not per block like the non-pp model — for g>1 the
        # backward recomputes g blocks as one unit, so peak recompute memory
        # is ~g blocks of activations
        layer_fn = jax.checkpoint(layer_fn)

    from jax.sharding import PartitionSpec as P

    if full_manual:
        x_spec = P(("dp", "fsdp"), "sp" if sp_on else None, None)
    else:
        x_spec = P(None, "sp", None) if sp_on else None
    out = pipeline_apply(
        stacked, x, layer_fn, mesh, n_micro=n_micro, axis=axis,
        rng=dropout_rng,
        # pp×sp: sp must be manual in the SAME shard_map (nested manual
        # regions don't lower); blocks then run the sp-local attention
        # bodies on sp-local token shards
        extra_manual_axes=("sp",) if sp_on else (),
        x_spec=x_spec,
        with_aux=return_aux,
        full_manual=full_manual,
    )
    x, aux = out if return_aux else (out, None)
    logits = model.apply(params, x, method=lambda m, h: m._head(h))
    return (logits, aux) if return_aux else logits


def pp_lm_loss(
    model: TransformerLM,
    params: Any,
    batch: Array,
    mesh: Mesh,
    *,
    n_micro: int,
    axis: str = "pp",
    dropout_rng: Any = None,
    full_manual: Any = None,
) -> Array:
    """batch [B, T+1] -> mean next-token cross entropy under the pipeline
    (+ microbatch-averaged MoE aux losses for MoE models)."""
    import optax

    x, y = batch[:, :-1], batch[:, 1:]
    moe = model.cfg.n_experts > 0
    out = pp_lm_logits(
        model, params, x, mesh, n_micro=n_micro, axis=axis,
        dropout_rng=dropout_rng, return_aux=moe, full_manual=full_manual,
    )
    logits, aux = out if moe else (out, None)
    loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
    return loss + aux if moe else loss


__all__ = [
    "pp_lm_logits",
    "pp_lm_loss",
    "stack_lm_blocks",
    "stack_lm_params",
    "unstack_lm_params",
]
