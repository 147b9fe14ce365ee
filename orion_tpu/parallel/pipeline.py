"""Pipeline parallelism: GPipe-style microbatch schedule over a ``pp`` mesh
axis (SURVEY.md round-2 carry-over; BASELINE.json north_star "run end-to-end
on a TPU pod" — the reference scales depth across nodes with NCCL
point-to-point sends; reference checkout never mounted, SURVEY.md §0).

TPU-native formulation: no send/recv rank loops — ONE SPMD program over the
mesh where each pp device holds a *stack* of its stage's blocks (params
stacked on a leading axis, sharded over pp), and activations hop stage→stage
with ``lax.ppermute`` (neighbor ICI hops), exactly like ring attention but
along depth instead of sequence.

Schedule (GPipe, forward):

    step s ∈ [0, n_micro + pp - 1):  stage i works on microbatch (s - i)
    when 0 <= s - i < n_micro, else idles on zeros; after each step the
    activation buffer rotates +1 around the ring.

The whole schedule is a single ``lax.scan`` (compiler-friendly, no Python
step loop), differentiable end-to-end — the backward pass that autodiff
derives through the scan+ppermute IS the reverse pipeline schedule (1B1F
order with stashed activations, which is what remat policies then trade
memory against). Bubble fraction is the usual (pp-1)/(n_micro+pp-1);
choose n_micro >= 4*pp to keep it under ~20%.

Restriction: the pipelined body must be *homogeneous* across stages (same
param pytree structure per layer) so per-stage params stack into one
leading-axis array. The flagship all-linear LM satisfies this; hybrid
swa/linear models do not (their pp support would stack per-type subsets —
future work, noted in SURVEY §7).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Array = jax.Array


def stack_params(per_layer_params: list) -> Any:
    """[p_0, ..., p_{L-1}] (same structure) -> one pytree with leading
    layer axis L on every leaf. Shard that axis over pp."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *per_layer_params)


def unstack_params(stacked: Any, n: int) -> list:
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(n)]


def _stage_apply(
    layer_fn: Callable,
    stage_params: Any,
    x: Array,
    rng: Any = None,
    with_aux: bool = False,
):
    """Run this device's stack of layers_per_stage layers sequentially.
    stage_params leaves: [layers_per_stage, ...]. With ``rng``, layer_fn is
    called as layer_fn(params, h, key) with a key folded per layer slot.
    With ``with_aux``, layer_fn returns (h, aux_scalar) and the summed aux
    is returned alongside the output: (out, aux)."""
    n = jax.tree.leaves(stage_params)[0].shape[0]

    def call(layer_params, h, key):
        if rng is None:
            r = layer_fn(layer_params, h)
        else:
            r = layer_fn(layer_params, h, key)
        return r if with_aux else (r, jnp.zeros((), jnp.float32))

    def body(carry, inp):
        h, aux = carry
        layer_params, slot = inp
        key = None if rng is None else jax.random.fold_in(rng, slot)
        h, a = call(layer_params, h, key)
        return (h, aux + a), None

    # the aux carry must have the same varying-manual-axes type as the aux
    # the body produces (derived from x, which is pp-varying inside the
    # pipeline shard_map); multiplying by a zero slice of x inherits that
    # type in shard_map context and is a no-op outside it
    aux0 = jnp.zeros((), jnp.float32) + 0.0 * x.reshape(-1)[0].astype(
        jnp.float32
    )
    (out, aux), _ = lax.scan(body, (x, aux0), (stage_params, jnp.arange(n)))
    return (out, aux) if with_aux else out


def pipeline_apply(
    stacked_params: Any,
    x: Array,
    layer_fn: Callable,  # (params, h) -> h, or (params, h, key) -> h with rng
    mesh: Mesh,
    *,
    n_micro: int,
    axis: str = "pp",
    rng: Any = None,
    extra_manual_axes: tuple = (),
    x_spec: Any = None,
    with_aux: bool = False,
    full_manual: bool = False,
):
    """Apply L stacked layers to ``x`` [B, ...] as a pp-stage pipeline.

    ``stacked_params``: every leaf [L, ...] with L % pp == 0; leading axis
    sharded over ``axis`` (stage i holds layers [i*L/pp, (i+1)*L/pp)).
    ``x``: microbatch axis comes from splitting B into n_micro groups;
    B % n_micro == 0. Returns the transformed [B, ...], layer order
    preserved (stage order == ring order).

    ``rng``: stochastic-layer support (dropout). layer_fn is then called as
    layer_fn(params, h, key), key = fold(fold(fold(rng, microbatch), stage),
    within-stage slot) — unique per layer×microbatch, so every draw is
    independent. NB *statistically* equivalent to the non-pipelined forward,
    not bit-identical (and not reproducible across different pp values):
    the non-pp model draws one [B, ...] mask per layer, the pipeline draws
    per-microbatch masks; the pp==1 fast path folds per layer slot only
    (whole-batch masks, like non-pp).

    ``extra_manual_axes`` + ``x_spec``: make additional mesh axes manual
    inside the pipeline body (jax's sdy lowering rejects nested manual
    regions, so a layer_fn that needs sp collectives must have sp manual
    HERE and run the sp-local attention bodies directly — the pp×sp
    composition, parallel/pipeline_lm.py). ``x_spec`` places x w.r.t. the
    manual axes (e.g. P(None, 'sp', None) to hand the body sp-local token
    shards).

    ``with_aux``: layer_fn returns (h, aux_scalar) — MoE aux losses
    (models/moe.py). Returns (out, aux) where aux is the per-layer sum,
    averaged over microbatches (each layer's sown value is a mean over
    the tokens it saw, so the microbatch average matches the non-pp
    full-batch scale; for the nonlinear load-balance term this is the
    mean of per-microbatch stats — exactly equal to non-pp at n_micro=1,
    statistically equivalent otherwise) and, when sp is manual, averaged
    over sp shards.

    ``full_manual``: make EVERY mesh axis manual, which is what lets
    Mosaic (Pallas) kernels lower inside the pipeline body — jax rejects
    tpu_custom_call in partial-manual regions. The batch rides the
    (dp, fsdp) axes explicitly (each device pipelines its local batch;
    shard_map's transpose inserts the dp grad psums the auto path got
    from GSPMD), so this mode requires tp == ep == 1: tensor/expert
    sharding inside the body would need hand-written Megatron/MoE
    collectives rather than data placement. The partial-manual default
    remains the general composition.
    """
    pp = mesh.shape[axis]
    if pp == 1 and not extra_manual_axes:
        return _stage_apply(layer_fn, stacked_params, x, rng, with_aux)
    b = x.shape[0]
    n_batch_shards = 1
    if full_manual:
        assert mesh.shape.get("tp", 1) == 1 and mesh.shape.get("ep", 1) == 1, (
            "full_manual pipeline requires tp == ep == 1 "
            f"(got {dict(mesh.shape)}): tensor/expert sharding inside a "
            "fully-manual body needs explicit collectives"
        )
        n_batch_shards = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
        assert b % n_batch_shards == 0, (
            f"full_manual pipeline: batch {b} must divide over the "
            f"{n_batch_shards} dp*fsdp shards"
        )
    assert (b // n_batch_shards) % n_micro == 0, (
        f"n_micro={n_micro} must divide the per-shard batch "
        f"{b // n_batch_shards} (global {b} over {n_batch_shards} batch "
        f"shards{' — full_manual shards the batch explicitly' if full_manual else ''})"
    )
    leaves = jax.tree.leaves(stacked_params)
    n_layers = leaves[0].shape[0]
    assert n_layers % pp == 0, (n_layers, pp)

    def local(params_local, x_all):
        """shard_map body. params_local leaves: [L/pp, ...] (this stage's
        layers). x_all: the batch (replicated over pp; LOCAL over dp/fsdp
        in full_manual mode) — each stage computes every microbatch but
        only its own stage slice, so the activation ring carries one
        microbatch-sized buffer."""
        i = lax.axis_index(axis)
        b_loc = x_all.shape[0]  # == b unless full_manual shards the batch
        micro = x_all.reshape(n_micro, b_loc // n_micro, *x_all.shape[1:])
        # the scan carry is device-varying (each stage holds different
        # activations); mark the replicated initializers/input accordingly
        # so shard_map's varying-mesh-axes check can verify the body
        micro = lax.pcast(micro, (axis,), to="varying")

        n_steps = n_micro + pp - 1
        zeros = jnp.zeros_like(micro[0])
        out0 = jnp.zeros_like(micro)
        aux0 = jnp.zeros((), jnp.float32)
        aux_axes = (axis,) + tuple(extra_manual_axes)
        if full_manual:
            aux_axes = aux_axes + ("dp", "fsdp")
        aux0 = lax.pcast(aux0, aux_axes, to="varying")

        def step(carry, s):
            buf, outs, aux_tot = carry
            # stage 0 injects microbatch s from the source; others take the
            # rotated buffer (their left neighbor's last output)
            m_idx = jnp.clip(s, 0, n_micro - 1)
            inj = lax.dynamic_index_in_dim(micro, m_idx, keepdims=False)
            h_in = jnp.where(i == 0, inj, buf)
            active = (s - i >= 0) & (s - i < n_micro)
            step_rng = None
            if rng is not None:
                # distinct key per (microbatch, stage); _stage_apply folds
                # the within-stage slot on top -> unique per layer×micro
                m = jnp.clip(s - i, 0, n_micro - 1)
                step_rng = jax.random.fold_in(jax.random.fold_in(rng, m), i)
                # manual sharded axes (sp always; dp/fsdp in full_manual):
                # each shard draws only its local slice, so the key must
                # differ per shard or masks repeat along the sharded dim
                # with 1/|axis| the intended entropy
                rng_axes = tuple(extra_manual_axes)
                if full_manual:
                    rng_axes = rng_axes + ("dp", "fsdp")
                for ax in rng_axes:
                    step_rng = jax.random.fold_in(step_rng, lax.axis_index(ax))
            if with_aux:
                h_out, aux_s = _stage_apply(
                    layer_fn, params_local, h_in, step_rng, True
                )
                aux_tot = aux_tot + jnp.where(active, aux_s, 0.0)
            else:
                h_out = _stage_apply(layer_fn, params_local, h_in, step_rng)
            h_out = jnp.where(active, h_out, zeros)
            # last stage banks its finished microbatch (s - (pp-1))
            o_idx = jnp.clip(s - (pp - 1), 0, n_micro - 1)
            bank = (i == pp - 1) & (s - (pp - 1) >= 0)
            prev = lax.dynamic_index_in_dim(outs, o_idx, axis=0, keepdims=False)
            outs = lax.dynamic_update_index_in_dim(
                outs, jnp.where(bank, h_out, prev), o_idx, axis=0
            )
            # rotate stage i -> i+1 (ICI neighbor hop)
            nxt = lax.ppermute(
                h_out, axis, [(j, (j + 1) % pp) for j in range(pp)]
            )
            return (nxt, outs, aux_tot), None

        (_, outs, aux_tot), _ = lax.scan(
            step, (zeros, out0, aux0), jnp.arange(n_steps)
        )
        # every stage ran the scan; only the last stage's banked outputs are
        # real — broadcast them back over pp so out_specs can be replicated
        outs = lax.psum(jnp.where(i == pp - 1, outs, jnp.zeros_like(outs)), axis)
        out = outs.reshape(b_loc, *x_all.shape[1:])
        if not with_aux:
            return out
        # stages hold disjoint layers: sum over pp; each layer sowed once
        # per microbatch: average; sp shards each saw local tokens: average
        aux = lax.psum(aux_tot, axis) / n_micro
        for ax in extra_manual_axes:
            aux = lax.pmean(aux, ax)
        if full_manual:
            # batch shards each averaged their own tokens; the P() out_spec
            # promises a replicated (unvarying) scalar
            for ax in ("dp", "fsdp"):
                aux = lax.pmean(aux, ax)
        return out, aux

    pspec = jax.tree.map(lambda _: P(axis), stacked_params)
    if x_spec is not None:
        xs = x_spec
    elif full_manual:
        xs = P(("dp", "fsdp"))
    else:
        xs = P()
    manual = (
        frozenset(mesh.axis_names)
        if full_manual
        else frozenset({axis}) | frozenset(extra_manual_axes)
    )
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(pspec, xs),
        out_specs=(xs, P()) if with_aux else xs,
        # partial-manual default: pp (and any extra axes the body's
        # collectives need, e.g. sp) are manual; dp/fsdp/tp stay automatic
        # so this composes with GSPMD batch/tensor sharding in the trainer.
        # full_manual: every axis manual (docstring) — the Mosaic-legal form.
        axis_names=manual,
        # vma stays tracked: the transpose of the pp-replicated x input is a
        # psum over pp, whose type rule *requires* tracked vma — so unlike
        # sequence.py this shard_map cannot run check_vma=False, and the
        # sp-local attention inside must avoid pallas interpret mode (which
        # can't trace under the check; transformer.py forces xla there)
    )
    return fn(stacked_params, x)


__all__ = ["pipeline_apply", "stack_params", "unstack_params"]
