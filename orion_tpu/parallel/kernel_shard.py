"""Manualize per-(batch, head) kernels over a GSPMD mesh's data axes.

Mosaic (Pallas) kernels lower to ``tpu_custom_call``, which XLA's SPMD
partitioner cannot split: under jit-with-shardings, a pallas_call whose
operands are sharded over mesh axes fails to compile with "Mosaic kernels
cannot be automatically partitioned. Please wrap the call in a shard_map"
(surfaced by topology-AOT planning of the dense fsdp path — this module
covers the plain GSPMD meshes; sp-without-pp is covered by the fully-
manual shard_maps in parallel/sequence.py and parallel/ring.py
(SP_PALLAS_AOT.json), and the pp pipeline is partial-manual by design so
its body pins attention to the XLA forms (models/mixers/);
reference checkout never mounted — SURVEY.md §0).

Causal attention is embarrassingly parallel over batch and heads, so the
structural fix is to shard_map the kernel over exactly the axes those dims
are sharded on — batch over (dp, fsdp), heads over tp — and run the
unmodified kernel on each device's local [B/(dp·fsdp), H/tp, T, D] block.
No collectives are introduced (nothing crosses tokens or heads).
``check_vma`` must be True for real Mosaic kernels and False only for
interpret mode — see ``shard_map_bh``. Token-sharded attention lives
elsewhere (parallel/sequence.py for sp linear, parallel/ring.py for sp
softmax/swa).
"""

from __future__ import annotations

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

_BH_AXES = ("dp", "fsdp", "tp")


def bh_spec(rank: int) -> P:
    """[B, H, ...rest] spec: batch over (dp, fsdp), heads over tp."""
    return P(("dp", "fsdp"), "tp", *([None] * (rank - 2)))


def needs_manual(mesh: Mesh | None, resolved_backend: str) -> bool:
    """True when the kernel would hit GSPMD partitioning: a pallas backend
    on a mesh whose data axes actually split anything."""
    if mesh is None or not resolved_backend.startswith("pallas"):
        return False
    s = mesh.shape
    return s.get("dp", 1) * s.get("fsdp", 1) * s.get("tp", 1) > 1


def shard_map_bh(mesh: Mesh, fn, *args, check_vma: bool = True):
    """Run ``fn(*args)`` manualized over (dp, fsdp, tp). Every arg and
    every output leaf must be [B, H, ...]-leading (true of q/k/v, attention
    outputs, and the (S, z) kv-state carries).

    ``check_vma=True`` (real Mosaic kernels) is REQUIRED, not just nice:
    jax's tpu_custom_call lowering rejects a partial-manual region unless
    the vma machinery has registered the manual axes on the mesh — with
    the check off, the same composition raises "Mosaic kernels cannot be
    automatically partitioned" from inside the shard_map. The body is
    collective-free, so tracking costs nothing. Interpret-mode kernels
    (CPU parity tests) are the one caller that must pass False: interpret
    tracing cannot run under the check (same constraint as sequence.py)."""
    outs = jax.eval_shape(fn, *args)
    out_specs = jax.tree.map(lambda s: bh_spec(len(s.shape)), outs)
    in_specs = tuple(bh_spec(a.ndim) for a in args)
    # FULLY manual (all mesh axes), not just the three the specs mention:
    # jax's tpu_custom_call lowering rejects any partial-manual region
    # ("Mosaic kernels cannot be automatically partitioned"), regardless of
    # the leftover axes' sizes. Axes the specs don't name just see the
    # value replicated, which is exactly right for sp/pp/ep here — and is
    # also why this wrapper must NOT be entered from inside the pipeline's
    # partial-manual region (it isn't: pipeline blocks carry mesh=None).
    f = shard_map(
        fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names=frozenset(mesh.axis_names),
        check_vma=check_vma,
    )
    return f(*args)


__all__ = ["bh_spec", "needs_manual", "shard_map_bh"]
