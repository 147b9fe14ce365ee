"""Chunked fused linear-cross-entropy: the LM head matmul and the softmax
cross entropy computed together, one sequence chunk at a time, so the full
``[B, T, V]`` fp32 logits tensor never exists in HBM.

Why (measured in this repo — BASELINE.md "Train-step profile"): at the
flagship shapes (batch 16 x T 2048 x V 32k) the unfused head materializes
4.3GB of fp32 logits, reads them back for the log-sum-exp, materializes
their 4.3GB cotangent ``softmax - onehot``, and feeds THAT back through the
head matmul's backward — ~100ms/step of pure HBM traffic on reduce+fusion
passes, plus 4-8GB of peak temp memory that caps the batch size. The fused
form recomputes each logits chunk in the backward (one extra ``x @ W`` pass,
~22ms of MXU time at these shapes) and keeps every [chunk, V] block local:
net faster, and the freed HBM buys no-remat blocks (ModelConfig.remat_skip)
worth far more than the recompute costs.

The reference's training path computes the same loss unfused (reference:
BASELINE.json north_star / configs #3 — its CUDA framework materializes
logits; the checkout was never mounted, SURVEY.md §0). This is the
TPU-native replacement, not a translation: chunking rides ``lax.scan`` with
static shapes so XLA pipelines the chunk matmuls back-to-back on the MXU.

Semantics: ``fused_linear_cross_entropy(x, w, labels)`` equals
``optax.softmax_cross_entropy_with_integer_labels(head(x), labels)``
token-for-token (parity: tests/test_fused_ce.py), where ``head`` is the
bf16-matmul / fp32-accumulation head (models/transformer.py::_head).
Gradients flow to ``x`` and ``w``; ``labels`` (integer) get a float0
cotangent.

Sharding: chunks are cut along T with batch leading, so dp/fsdp batch
sharding passes straight through the scan; tp partitions each chunk matmul
exactly like the unfused head. Sequence-parallel (sp>1) meshes chunk each
shard's LOCAL tokens inside an sp-manual shard_map (``_sp_fused_ce``) —
per-token CE crosses no token boundary, so the body needs no sp
collectives and the logits stay un-materialized at exactly the long-T
operating points sp exists for.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

__all__ = [
    "fused_linear_cross_entropy", "pick_n_chunks", "chunk_plan",
    "fused_ce_ok", "model_token_losses",
]


def fused_ce_ok(model) -> bool:
    """Is the fused head+CE path applicable to this model? Everywhere
    except quantized models (decode-only path, never trained/evaled through
    here). sp meshes ride ``_sp_fused_ce``: head+CE chunked INSIDE an
    sp-manual region over each shard's local tokens (r3 VERDICT #2 — the r3
    gate re-materialized the logits exactly at the long-T operating points
    sp exists for)."""
    return not getattr(model, "quant", "")


def _sp_active(model) -> bool:
    return (
        model.cfg.sequence_parallel
        and model.mesh is not None
        and model.mesh.shape.get("sp", 1) > 1
    )


def model_token_losses(model, params, x: Array, y: Array,
                       mutable: bool = False, **apply_kwargs):
    """Per-token next-token CE [B, T] through the fused head — the ONE
    invocation of this path, shared by the training loss
    (training/trainer.py::lm_loss) and the eval loss
    (evaluate.py::lm_eval_sums) so the two can never drift.
    Returns (losses, variables) — variables is the sowed "losses"
    collection when ``mutable`` (MoE aux), else {}."""
    from orion_tpu.models.transformer import _dtype

    if mutable:
        feats, variables = model.apply(
            params, x, mutable=["losses", "moe_stats"], method="features",
            **apply_kwargs,
        )
    else:
        feats = model.apply(params, x, method="features", **apply_kwargs)
        variables = {}
    w, w_is_vd = model.head_weight(params)
    feats = feats.astype(_dtype(model.cfg.dtype))
    if _sp_active(model):
        losses = _sp_fused_ce(feats, w, y, model.mesh, w_is_vd)
    else:
        losses = _padded_fused_ce(feats, w, y, w_is_vd)
    return losses, variables


def _padded_fused_ce(x: Array, w: Array, labels: Array, w_is_vd: bool) -> Array:
    """fused_linear_cross_entropy behind chunk_plan: pads T when it has no
    divisor under the row cap (pad rows carry label 0; the slice back to
    [B, T] transposes to a zero cotangent on them, so grads are exact — no
    full-logits fallback path remains)."""
    b, t = labels.shape
    n, tp = chunk_plan(b, t)
    if tp != t:
        x = jnp.pad(x, ((0, 0), (0, tp - t), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, tp - t)))
    losses = fused_linear_cross_entropy(x, w, labels, n, w_is_vd)
    return losses[:, :t] if tp != t else losses


def _sp_fused_ce(
    x: Array, w: Array, labels: Array, mesh, w_is_vd: bool
) -> Array:
    """Fused head+CE on an sp mesh: a shard_map manual over ONLY the sp
    axis (dp/fsdp/tp stay automatic, same partial-manual idiom as
    parallel/pipeline.py) whose body chunks each shard's LOCAL tokens.
    Per-token CE needs no cross-token communication, so the body has zero
    sp collectives; the head weight enters unsharded-over-sp (P(None)) and
    its cotangent — varying over sp — is psummed by the shard_map
    transpose. The [B, T, V] logits now never materialize on sp meshes
    either, which is exactly the memory that T=64k sp runs need back
    (r3 VERDICT #2)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    sp = mesh.shape["sp"]
    b, t = labels.shape
    assert t % sp == 0, (t, sp)

    def local(xs, wl, ys):
        # explicitly mark w sp-varying: the cast's transpose is the psum
        # over sp that the (sp-varying) dw cotangent needs on its way back
        # to the unvarying P(None) input — the same idiom pipeline.py uses
        # for its pp-replicated microbatch input
        wl = jax.lax.pcast(wl, ("sp",), to="varying")
        return _padded_fused_ce(xs, wl, ys, w_is_vd)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, "sp", None), P(None, None), P(None, "sp")),
        out_specs=P(None, "sp"),
        axis_names=frozenset({"sp"}),
    )
    return fn(x, w, labels)

# ~rows of each chunk matmul: big enough to fill the MXU (>=8 sublane tiles
# of 8x128 per 128-row pass), small enough that the [rows, V] fp32 logits
# block stays ~256MB at V=32k
_TARGET_ROWS = 2048


def pick_n_chunks(batch: int, seq: int) -> int:
    """Largest divisor of ``seq`` keeping ~_TARGET_ROWS tokens per chunk.
    Returns 1 when ``seq`` has no usable divisor — callers that must never
    materialize the full logits use ``chunk_plan`` (pad-and-chunk)."""
    cap = max(1, (batch * seq) // _TARGET_ROWS)
    best = 1
    for d in range(1, seq + 1):
        if d > cap:
            break
        if seq % d == 0:
            best = d
    return best


def chunk_plan(batch: int, seq: int) -> Tuple[int, int]:
    """(n_chunks, padded_seq) for the fused scan. When ``seq`` has a
    divisor under the row cap, padded_seq == seq and this is pick_n_chunks.
    Otherwise (prime/odd T at large B — r3 VERDICT weak #7: the old
    warn-and-run-unchunked path materialized exactly the [B, T, V] block
    this file exists to avoid) T is padded up to n_chunks equal pieces;
    the caller pads inputs and slices the [B, padded_seq] losses back to
    [B, seq], which keeps gradients exact (zero cotangent on pad rows)."""
    n = pick_n_chunks(batch, seq)
    cap = max(1, (batch * seq) // _TARGET_ROWS)
    # pad whenever the best divisor still leaves chunks far over the row
    # target — not just n == 1: T = 2 x large-prime has divisor 2 under
    # the cap, but half of a 16k-row sequence is still a multi-GB logits
    # block, the exact allocation this path exists to avoid
    if cap >= 2 and n < cap and batch * (seq // n) > 2 * _TARGET_ROWS:
        n = min(cap, seq)
        chunk = -(-seq // n)  # ceil
        return n, n * chunk
    return n, seq


def _logits_chunk(xc: Array, w: Array, w_is_vd: bool) -> Array:
    """[B, C, D] x head weight -> [B, C, V] fp32 (bf16 MXU, fp32 accum —
    same contraction the unfused head runs, transformer.py::_head)."""
    spec = "bcd,vd->bcv" if w_is_vd else "bcd,dv->bcv"
    return jnp.einsum(spec, xc, w, preferred_element_type=jnp.float32)


def _split(a: Array, n_chunks: int) -> Array:
    """[B, T, ...] -> [n_chunks, B, C, ...] (batch stays a leading dim of
    every scan step, preserving dp/fsdp sharding)."""
    b, t = a.shape[0], a.shape[1]
    return a.reshape((b, n_chunks, t // n_chunks) + a.shape[2:]).swapaxes(0, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_linear_cross_entropy(
    x: Array, w: Array, labels: Array, n_chunks: int = 1, w_is_vd: bool = True
) -> Array:
    """Per-token cross entropy [B, T] of the fused head(x) vs labels.

    x: [B, T, D] activations in the compute dtype (the head casts w to
       x.dtype for the matmul, like transformer.py::_head)
    w: [V, D] (w_is_vd=True, tied embedding) or [D, V] (lm_head_kernel)
    labels: [B, T] int32; n_chunks must divide T (pick_n_chunks)
    """
    out, _ = _fwd(x, w, labels, n_chunks, w_is_vd)
    return out


def _fwd(x, w, labels, n_chunks, w_is_vd):
    wc = w.astype(x.dtype)
    xs, ys = _split(x, n_chunks), _split(labels, n_chunks)

    def body(_, xy):
        xc, yc = xy
        logits = _logits_chunk(xc, wc, w_is_vd)
        m = logits.max(-1)
        lse = m + jnp.log(jnp.exp(logits - m[..., None]).sum(-1))
        picked = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        return None, (lse - picked, lse)

    _, (loss, lse) = jax.lax.scan(body, None, (xs, ys))
    b, t = labels.shape
    # residuals: inputs (already live) + the [B, T] fp32 lse — never logits
    return loss.swapaxes(0, 1).reshape(b, t), (x, w, labels, lse)


def _bwd(n_chunks, w_is_vd, res, g) -> Tuple[Array, Array, np.ndarray]:
    x, w, labels, lse = res  # lse [n_chunks, B, C]
    v = w.shape[0] if w_is_vd else w.shape[1]
    cdt = x.dtype
    wc = w.astype(cdt)
    xs, ys, gs = _split(x, n_chunks), _split(labels, n_chunks), _split(g, n_chunks)

    def body(dw, inp):
        xc, yc, lsec, gc = inp
        logits = _logits_chunk(xc, wc, w_is_vd)  # recomputed, fp32
        p = jnp.exp(logits - lsec[..., None])
        dlog = (p - jax.nn.one_hot(yc, v, dtype=p.dtype)) * gc[..., None]
        dl = dlog.astype(cdt)  # bf16 into the MXU, fp32 accumulation out
        dxc = jnp.einsum(
            "bcv,vd->bcd" if w_is_vd else "bcv,dv->bcd", dl, wc,
            preferred_element_type=jnp.float32,
        )
        dwc = (
            jnp.einsum("bcv,bcd->vd", dl, xc,
                       preferred_element_type=jnp.float32)
            if w_is_vd else
            jnp.einsum("bcd,bcv->dv", xc, dl,
                       preferred_element_type=jnp.float32)
        )
        return dw + dwc, dxc.astype(cdt)

    # the dw carry must inherit x's varying-mesh-axes type: inside the
    # sp-manual region (_sp_fused_ce) w enters unvarying while dwc is
    # sp-varying, and a plain-zeros carry trips the scan's carry typing —
    # same workaround as ops/pallas/causal_dot.py::vma_zeros_state (XLA
    # folds the zero-multiply)
    dw0 = jnp.zeros(w.shape, jnp.float32) + 0.0 * x.astype(
        jnp.float32
    ).ravel()[0]
    dw, dxs = jax.lax.scan(body, dw0, (xs, ys, lse, gs))
    b, t = labels.shape
    dx = dxs.swapaxes(0, 1).reshape(x.shape)
    # integer labels: float0 cotangent (the JAX convention for int primals)
    dy = np.zeros(labels.shape, dtype=jax.dtypes.float0)
    return dx, dw.astype(w.dtype), dy


fused_linear_cross_entropy.defvjp(_fwd, _bwd)
