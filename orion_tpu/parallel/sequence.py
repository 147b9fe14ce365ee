"""Sequence/context-parallel causal linear attention (SURVEY.md P5).

Long-context support for linear-attention layers: tokens sharded over the
``sp`` mesh axis. The linear-attention recurrence makes this almost free —
unlike softmax, the cross-shard information is a single [Dk, Dv] kv-cumsum
state per head, not the keys themselves (the reference scales long context
through its CUDA kv-cumsum kernel + NCCL; reference checkout never mounted
— SURVEY.md §0). Per sp shard i:

    1. local chunked causal attention with carried state → out_i needs
       S_prefix_i = Σ_{j<i} S_j   (and z_prefix_i = Σ_{j<i} z_j)
    2. all_gather of the tiny per-shard states (Dk×Dv per head — bytes,
       not activations) over sp; exclusive prefix = masked sum over j < i
    3. re-run local attention seeded with initial_state=S_prefix_i
       (exact: the chunked kernel supports a carried-in state)

Communication: one all_gather of [sp, B, H, Dk, Dv] per layer — O(D²)
bytes over ICI, independent of sequence length. Differentiable end-to-end
(the Pallas kernel's custom VJP handles d/d(initial_state)).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from orion_tpu.ops.dispatch import causal_dot_product

Array = jax.Array


def _local_states(k: Array, v: Array) -> Tuple[Array, Array]:
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    s = jnp.einsum("...td,...te->...de", kf, vf)
    z = jnp.sum(kf, axis=-2)
    return s, z


from orion_tpu.parallel.collectives import exclusive_prefix_sum as _exclusive_prefix


def sp_linear_attention_local(
    q: Array,
    k: Array,
    v: Array,
    axis: str = "sp",
    *,
    backend: str = "auto",
    chunk: Optional[int] = None,
    eps: float = 1e-6,
) -> Array:
    """The shard_map body: q,k,v are the LOCAL [.., T/sp, D] shards (post
    feature map). Normalized causal linear attention, exact across shards.

    Pallas backend — ONE fused kernel pass: the kernel hands back the raw
    fp32 numerator, its normalizer den, and the shard's (S, z); the
    cross-shard prefix then corrects in O(T·D) elementwise/matvec work:
        num_full = num_loc + q @ S_prefix
        out_full = num_full / (den_loc + q·z_prefix + eps)
    (The fp32 numerator comes straight from the kernel — no reconstruction
    from the bf16-rounded output.)
    XLA backend — two passes (local states, then state-seeded attention).
    """
    from orion_tpu.ops.dispatch import resolve

    b = resolve(backend)
    if b in ("pallas", "pallas_interpret"):
        from orion_tpu.ops.pallas.causal_dot import linear_attention_pallas_parts

        num_loc, den_loc, (s_loc, z_loc) = linear_attention_pallas_parts(
            q, k, v, chunk=chunk, interpret=(b == "pallas_interpret"),
        )
        s0 = _exclusive_prefix(s_loc, axis)
        z0 = _exclusive_prefix(z_loc, axis)
        qf = q.astype(jnp.float32)
        num = num_loc + jnp.einsum("...td,...de->...te", qf, s0)
        den = den_loc + jnp.einsum("...td,...d->...t", qf, z0)
        return (num / (den + eps)[..., None]).astype(q.dtype)

    s_loc, z_loc = _local_states(k, v)
    s0 = _exclusive_prefix(s_loc, axis)
    z0 = _exclusive_prefix(z_loc, axis)

    num = causal_dot_product(
        q, k, v, backend=backend, chunk=chunk, initial_state=s0
    )
    kf = k.astype(jnp.float32)
    zcum = jnp.cumsum(kf, axis=-2) + z0[..., None, :]
    den = jnp.einsum("...td,...td->...t", q.astype(jnp.float32), zcum)
    return (num.astype(jnp.float32) / (den[..., None] + eps)).astype(q.dtype)


def sp_linear_attention(
    q: Array,
    k: Array,
    v: Array,
    mesh: Mesh,
    *,
    axis: str = "sp",
    backend: str = "auto",
    chunk: Optional[int] = None,
) -> Array:
    """Global entry: q,k,v [B, H, T, D] with T sharded over ``axis``.
    Batch rides on (dp, fsdp); heads on tp."""
    from orion_tpu.ops.dispatch import resolve

    spec = P(("dp", "fsdp"), "tp", axis, None)
    fn = shard_map(
        partial(
            sp_linear_attention_local, axis=axis, backend=backend, chunk=chunk
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # vma tracking ON except under pallas INTERPRET mode (the CPU test
        # path), which cannot run under the check: its internal
        # dynamic_slice mixes varying operands with unvarying indices and
        # jax itself says "as a temporary workaround pass check_vma=False"
        # (hlo_interpreter.py). Real kernels and the XLA form run fully
        # checked — the kernel out_shapes declare vma
        # (ops/pallas/causal_dot.py::_sds); sp parity tests at 2/4/8 cover
        # the interpret path's values+grads meanwhile.
        check_vma=(resolve(backend) != "pallas_interpret"),
    )
    return fn(q, k, v)


__all__ = ["sp_linear_attention", "sp_linear_attention_local"]
