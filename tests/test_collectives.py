"""parallel/collectives.py vs single-device numpy oracles (quick tier).

The two composite primitives encode real cross-shard logic — ring rotation
and the exclusive prefix over per-shard partials — so each is checked on
the virtual sp mesh against a pure-numpy reference computed from the same
global array: ``ppermute_shift`` must equal a block-roll of the shard
blocks, ``exclusive_prefix_sum`` must equal the shifted block cumsum. The
Tier C SPMD auditor budgets these collectives structurally
(parallel/budgets.py); these tests pin their VALUES.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from orion_tpu.parallel.collectives import exclusive_prefix_sum, ppermute_shift
from orion_tpu.parallel.mesh import MeshConfig, make_mesh
from jax import shard_map


def _sp_mesh(sp):
    return make_mesh(MeshConfig(dp=1, sp=sp))


def _global(mesh, x):
    return jax.device_put(x, NamedSharding(mesh, P("sp", None)))


@pytest.mark.parametrize("sp,shift", [(2, 1), (4, 1), (4, 2), (4, 3)])
def test_ppermute_shift_matches_block_roll(sp, shift):
    mesh = _sp_mesh(sp)
    x = np.arange(sp * 3 * 5, dtype=np.float32).reshape(sp * 3, 5)

    fn = shard_map(
        lambda xl: ppermute_shift(xl, "sp", shift=shift),
        mesh=mesh, in_specs=P("sp", None), out_specs=P("sp", None),
    )
    got = np.asarray(fn(_global(mesh, x)))

    # device i's block lands on device (i+shift) % sp == roll the block
    # axis forward by `shift`
    blocks = x.reshape(sp, 3, 5)
    want = np.roll(blocks, shift, axis=0).reshape(sp * 3, 5)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("sp", [2, 4, 8])
def test_exclusive_prefix_sum_matches_numpy(sp):
    mesh = _sp_mesh(sp)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((sp * 2, 4)).astype(np.float32)

    fn = shard_map(
        lambda xl: exclusive_prefix_sum(xl, "sp"),
        mesh=mesh, in_specs=P("sp", None), out_specs=P("sp", None),
    )
    got = np.asarray(fn(_global(mesh, x)))

    # shard i receives sum of shard blocks j < i (the kv-state correction)
    blocks = x.reshape(sp, 2, 4)
    prefix = np.cumsum(blocks, axis=0) - blocks  # exclusive
    np.testing.assert_allclose(
        got, prefix.reshape(sp * 2, 4), rtol=1e-6, atol=1e-6
    )


def test_exclusive_prefix_sum_first_shard_is_zero():
    sp = 4
    mesh = _sp_mesh(sp)
    x = np.ones((sp, 3), np.float32)
    fn = shard_map(
        lambda xl: exclusive_prefix_sum(xl, "sp"),
        mesh=mesh, in_specs=P("sp", None), out_specs=P("sp", None),
    )
    got = np.asarray(fn(_global(mesh, x)))
    np.testing.assert_array_equal(got[0], np.zeros(3, np.float32))
    # shard i holds exactly i (sum of i ones-blocks)
    np.testing.assert_array_equal(got[:, 0], np.arange(sp, dtype=np.float32))


def test_exclusive_prefix_sum_keeps_payload_dtype():
    # the gathered mask-sum must not silently upcast the payload: the
    # budget (parallel/budgets.py) declares the f32 payload the callers
    # pass; a bf16 caller gets bf16 back
    sp = 2
    mesh = _sp_mesh(sp)
    x = jnp.ones((sp * 2, 4), jnp.bfloat16)
    fn = shard_map(
        lambda xl: exclusive_prefix_sum(xl, "sp"),
        mesh=mesh, in_specs=P("sp", None), out_specs=P("sp", None),
    )
    assert fn(_global(mesh, x)).dtype == jnp.bfloat16
