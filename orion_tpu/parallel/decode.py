"""Tensor-parallel batched decode: placement rules + the mesh report.

The serving path (ISSUE 14) shards the slot-multiplexed decode carry over
a ``tp`` mesh so models too big (or too slow) for one chip serve from N.
Nothing about the decode *programs* changes — the same four jit wrappers
in ``generate.py`` run; what changes is the PLACEMENT of their inputs,
and GSPMD partitions the program from there:

- **weights** follow the training rules (``sharding.spec_for_path``):
  ``wq/wk/wv/gate/up`` heads/hidden on ``tp`` (output-dim: the local
  gemm contracts the full ``d`` — exact), ``wo/down`` contraction-split
  with psum-at-output. GSPMD turns the two split contractions into the
  Megatron contract: exactly TWO all-reduces per block per decode step
  (pinned by golden ``decode_batched_tp{2,4}.json``).
- **decode state** shards on the HEAD dimension (axis 1 of every
  ``(S, z)`` / KV-cache / ring-cache leaf): per-head attention is local,
  so the O(1) state partitions with zero state collectives. A head count
  that doesn't divide ``tp`` clips to replicated — legal but pointless,
  which is exactly what :func:`mesh_report` exists to surface.
- **the per-slot carry vectors** (token / t / emit / done / rng / staged
  prompt) stay REPLICATED: admission (``insert_decode_slot``), ladder
  snapshots, and session suspend/resume remain plain row operations.

Bitwise contract (tests/test_tp_serving.py): the EMITTED TOKENS of a
tp=2/tp=4 engine are pinned bitwise-identical to the unsharded engine's
at the same seeds, greedy and sampled. The float state itself carries
~1-ulp reassociation noise from the two split contractions (a psum sums
per-device partials where the unsharded gemm sums one K loop), so the
cross-footprint contract is deliberately TOKEN-level; the per-footprint
suspend/resume contract stays exact (the carry row round-trips through
the session store bitwise).

Session portability: the session store already persists the LOGICAL
carry row — ``jax.device_get`` on a tp-sharded row assembles the full
host array, so a suspended tp=2 session IS the unsharded pytree on disk.
"Resharding" to tp=4 or unsharded at resume is just the insert path
placing that host row onto the target mesh: a host-side reshape on the
store path, never a device-to-device KV transfer.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional

# Megatron intra-layer partitioning, applied to the recurrent decode step:
# the attention-output projection (wo) and the MLP down projection each
# split their contraction over tp, so GSPMD inserts one all-reduce per
# projection per token — two per block per decode step, O(slots x d)
# activation bytes each, independent of sequence length. Everything else
# (qkv/gate/up output-dim shards, per-head attention, head-dim state) is
# communication-free. The golden snapshots pin the exact counts.
DECODE_ALLREDUCES_PER_BLOCK = 2


def _mesh_axis(mesh, name: str) -> int:
    return int(mesh.shape.get(name, 1))


def decode_param_shardings(abstract_params: Any, mesh) -> Any:
    """NamedSharding tree for serving params — the training rules
    verbatim (``sharding.param_shardings``): decode reuses the exact
    layouts the trainer produced, so a sharded checkpoint needs no
    re-layout to serve."""
    from orion_tpu.parallel.sharding import param_shardings

    return param_shardings(abstract_params, mesh)


def place_decode_params(params: Any, mesh) -> Any:
    """Place a materialized (fp32 or quantized) param tree for tp decode."""
    import jax

    return jax.device_put(
        params, decode_param_shardings(jax.eval_shape(lambda: params), mesh)
    )


def decode_state_shardings(abstract_states: Any, mesh) -> Any:
    """NamedSharding tree for the batched decode state: every leaf with a
    head axis (axis 1) divisible by ``tp`` shards there; anything else —
    including the whole tree on a tp=1 mesh — replicates. The slot
    (batch) axis 0 is never sharded: slots are the serving unit and row
    insert/extract must stay single-row operations."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    tp = _mesh_axis(mesh, "tp")

    def make(leaf) -> NamedSharding:
        if tp > 1 and leaf.ndim >= 2 and leaf.shape[1] % tp == 0:
            return NamedSharding(
                mesh, P(None, "tp", *([None] * (leaf.ndim - 2)))
            )
        return NamedSharding(mesh, P())

    return jax.tree.map(make, abstract_states)


def place_decode_carry(carry: Any, mesh) -> Any:
    """Place the engine carry ``(token, states, t, emit, done)``: state
    head-sharded, the per-slot vectors replicated (fully-replicated
    scalars keep admission, boundary snapshots, and suspend/resume as
    row operations on every footprint)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    token, states, t, emit, done = carry
    rep = NamedSharding(mesh, P())
    states = jax.device_put(
        states, decode_state_shardings(jax.eval_shape(lambda: states), mesh)
    )
    return (
        jax.device_put(token, rep), states, jax.device_put(t, rep),
        jax.device_put(emit, rep), jax.device_put(done, rep),
    )


def mesh_backend(backend: str, tp: int) -> str:
    """The kernel backend a tp footprint serves with. The serving programs
    are mesh-aware by PLACEMENT alone — no shard_map — and a Mosaic kernel
    inside a GSPMD-partitioned program is refused by the TPU compiler
    ("Mosaic kernels cannot be automatically partitioned"; seen compiling
    the ``lm_1b3`` in-scan prefill for a v5e:2x2). So on tp > 1 the
    prefill's attention is pinned to the XLA forms, the rule the pp
    pipeline already follows (models/mixers/), and so is the
    decode step: the slot-multiplexed programs' row-sparse (S, z) kernel
    (ops/pallas/decode_state.py) is not reached under a mesh, every slot's
    state steps through ``recurrent_step`` and the select. Manualizing
    the kernels over tp (parallel/kernel_shard.py) is the faster repair,
    not made yet."""
    from orion_tpu.ops.dispatch import resolve

    if int(tp) > 1 and resolve(backend) == "pallas":
        return "xla"
    return backend


def mesh_model(model: Any, mesh) -> Any:
    """``model`` with :func:`mesh_backend` applied for ``mesh``."""
    import dataclasses

    backend = mesh_backend(model.cfg.backend, _mesh_axis(mesh, "tp"))
    if backend == model.cfg.backend:
        return model
    return model.clone(cfg=dataclasses.replace(model.cfg, backend=backend))


def place_replicated(x: Any, mesh) -> Any:
    """Replicate a host/device value over the mesh (rng table, staged
    prompt buffer, prompt-length vectors)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(x, NamedSharding(mesh, P()))


def serving_mesh(tp: int, devices=None):
    """The 1-axis-that-matters decode mesh: ``tp`` devices from the local
    client (the first ``tp`` by default). Raises a clean error when the
    host exposes fewer devices than the requested footprint — the
    misconfiguration must fail at construction, not as an opaque GSPMD
    error at the first chunk."""
    import jax

    from orion_tpu.parallel.mesh import MeshConfig, make_mesh

    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < tp:
        raise ValueError(
            f"tp={tp} needs {tp} devices but this process has "
            f"{len(devices)}; on CPU hosts provision virtual devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={tp}"
        )
    return make_mesh(MeshConfig(dp=1, tp=tp), devices=devices[:tp])


# -- per-device accounting (goldens, /statusz, aot) ---------------------------


def bytes_per_device(abstract: Any, shardings: Any) -> int:
    """Logical bytes / shard factor, summed over a pytree (the aot.py
    accounting applied to serving params and state)."""
    from orion_tpu.aot import _bytes_per_device

    return _bytes_per_device(abstract, shardings)


def carry_bytes_per_device(cfg, slots: int, mesh) -> Dict[str, int]:
    """The decode scan carry's byte budget per device: the head-sharded
    state divides by tp, the replicated per-slot vectors don't. Pure
    shape arithmetic — nothing compiles, nothing materializes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from orion_tpu.models.transformer import init_decode_state

    states = jax.eval_shape(lambda: init_decode_state(cfg, slots))
    shd = decode_state_shardings(states, mesh)
    state_dev = bytes_per_device(states, shd)
    state_total = sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree.leaves(states)
    )
    # token/t/emit int32 + done bool, replicated on every device
    vectors = slots * (3 * jnp.int32(0).itemsize + 1)
    return {
        "state_bytes": state_total,
        "state_bytes_per_device": state_dev,
        "replicated_vector_bytes": vectors,
        "carry_bytes": state_total + vectors,
        "carry_bytes_per_device": state_dev + vectors,
    }


def _hlo_collectives(hlo_text: str) -> Dict[str, int]:
    ops = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
           "collective-permute")
    return {
        op: len(re.findall(rf"\b{op}(?:-start)?\(", hlo_text)) for op in ops
    }


def mesh_report(
    model,
    params: Any,
    mesh,
    slots: int,
    chunk: int,
    sample,
    compile_probe: bool = True,
) -> Dict[str, Any]:
    """One host dict answering "did the mesh actually engage?" BEFORE the
    first request: axis sizes, per-device param/state bytes (silent
    replication — a head count not dividing tp — shows up as a shard
    factor of 1), the DECLARED per-step collective budget (two
    all-reduces per block, Megatron), and with ``compile_probe`` the
    collectives GSPMD actually inserted into the pure decode program
    (one AOT lower+compile of the same (slots, chunk) shape the engine
    serves — startup cost, never per-chunk). ``budget_ok`` is the
    misconfigured-mesh alarm /statusz surfaces."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from orion_tpu.models.transformer import init_decode_state

    tp = _mesh_axis(mesh, "tp")
    cfg = model.cfg
    abstract_params = jax.eval_shape(lambda: params)
    p_shd = decode_param_shardings(abstract_params, mesh)
    param_total = sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree.leaves(abstract_params)
    )
    report: Dict[str, Any] = {
        "axes": {k: int(v) for k, v in mesh.shape.items()},
        "tp": tp,
        "devices": [str(d) for d in mesh.devices.flat],
        "param_bytes": param_total,
        "param_bytes_per_device": bytes_per_device(abstract_params, p_shd),
        **carry_bytes_per_device(cfg, slots, mesh),
        "allreduces_per_step_budget": (
            DECODE_ALLREDUCES_PER_BLOCK * cfg.n_layers if tp > 1 else 0
        ),
    }
    if compile_probe:
        from orion_tpu.generate import _decode_batched_chunk_jit

        states = jax.eval_shape(lambda: init_decode_state(cfg, slots))
        st_shd = decode_state_shardings(states, mesh)
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(mesh, P())
        sds = lambda shape, dt, shd: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=shd
        )
        vec = lambda dt: sds((slots,), dt, rep)  # noqa: E731
        carry = (
            vec(jnp.int32),
            jax.tree.map(
                lambda l, s: sds(l.shape, l.dtype, s), states, st_shd
            ),
            vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_),
        )
        a_params = jax.tree.map(
            lambda l, s: sds(l.shape, l.dtype, s), abstract_params, p_shd
        )
        try:
            hlo = _decode_batched_chunk_jit.lower(
                model, a_params, carry,
                sds((slots, 2), jnp.uint32, rep), vec(jnp.bool_),
                int(chunk), sample,
            ).compile().as_text()
            observed = _hlo_collectives(hlo)
            report["observed_collectives"] = observed
            # the per-STEP observed count: GSPMD hoists nothing out of the
            # decode scan (each step's psums depend on that step's
            # activations), so the program-level all-reduce count IS the
            # per-step count for the single-scan decode program
            report["budget_ok"] = (
                observed.get("all-reduce", 0)
                == report["allreduces_per_step_budget"]
            )
        except Exception as e:  # introspection must never block serving
            report["observed_error"] = f"{type(e).__name__}: {e}"[:200]
            report["budget_ok"] = None
    return report


__all__ = [
    "DECODE_ALLREDUCES_PER_BLOCK",
    "decode_param_shardings",
    "place_decode_params",
    "decode_state_shardings",
    "place_decode_carry",
    "mesh_backend",
    "mesh_model",
    "place_replicated",
    "serving_mesh",
    "bytes_per_device",
    "carry_bytes_per_device",
    "mesh_report",
]
