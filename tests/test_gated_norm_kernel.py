"""The delta-rule layer's output gate (``ops/pallas/gated_norm.py``) in
interpret mode against the XLA form, ``ops/gated_delta.py::gated_rms_norm``:
the forward and all three gradients (which the benchmark's ``correct``
cannot see) over whole and ragged time tiles, one head group and several,
one lane tile a head and two; where the op declines, and that the XLA form
it falls to is the arithmetic ``GatedDeltaNet._output`` had; the layer with
the kernels against the layer without them.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.ops import dispatch
from orion_tpu.ops.gated_delta import gated_rms_norm as xla_form
from orion_tpu.ops.pallas import gated_norm as pgn

EPS = 1e-6
# two time tiles, and one and a ragged second, at the tile the fixture sets
WHOLE, RAGGED = 128, 100


@pytest.fixture(autouse=True)
def tiles_of_64_rows(monkeypatch):
    """The kernels' own tile is 512 rows; the interpreter walks 64 here,
    the same code (``test_the_kernels_own_tile`` keeps the chip's)."""
    monkeypatch.setattr(pgn, "_TILE_T", 64)


def _inputs(hv, t, dv, dtype, batch=2):
    ks = jax.random.split(jax.random.key(hv + t + dv), 4)
    o = (2.0 * jax.random.normal(ks[0], (batch, hv, t, dv))).astype(dtype)
    z = jax.random.normal(ks[1], (batch, t, hv * dv)).astype(dtype)
    w = 1.0 + 0.3 * jax.random.normal(ks[2], (dv,))
    return o, z, w, jax.random.normal(ks[3], (batch, t, hv * dv))


def _kernel(o, z, w):
    return dispatch.gated_rms_norm(o, z, w, eps=EPS, backend="pallas_interpret")


def _old_output(o, z, w, dtype):
    """``GatedDeltaNet._output`` before the op, up to ``wo``, on the o ``[B,
    T, Hv, Dv]`` that ``_rule`` transposed back for it."""
    from orion_tpu.models.mixers import _rms

    o = _rms(o) * w.astype(jnp.float32)
    o = o * jax.nn.silu(z.reshape(o.shape).astype(jnp.float32))
    return o.reshape(z.shape).astype(dtype)


def _grads(fn, o, z, w, dy):
    return jax.grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * dy), argnums=(0, 1, 2)
    )(o, z, w)


SHAPES = pytest.mark.parametrize(
    "hv,t,dv",
    [(4, WHOLE, 128), (4, RAGGED, 128), (32, WHOLE, 128), (32, RAGGED, 128),
     (4, WHOLE, 256), (4, RAGGED, 256)],
)


def test_the_kernels_choose_their_tiles(monkeypatch):
    assert pgn.time_tile(WHOLE) == 64 and pgn.time_tile(RAGGED) == 64
    assert pgn.time_tile(48) == 48 and pgn.time_tile(40) == 32
    assert pgn.time_tile(15) is None and pgn.time_tile(1) is None
    monkeypatch.undo()  # the chip's: the train point, a serve cell's piece
    assert pgn.time_tile(8192) == 512 and pgn.time_tile(1000) == 512
    assert [pgn._group(h, d) for h, d in ((32, 128), (4, 128), (32, 256), (30, 128), (6, 384), (2, 2048))] == [8, 4, 4, 6, 2, 1]
    o, z = jnp.zeros((1, 4, 64, 128)), jnp.zeros((1, 64, 512))
    assert pgn.supports(o, z)
    assert not pgn.supports(jnp.zeros((1, 4, 64, 192)), jnp.zeros((1, 64, 768)))
    assert not pgn.supports(o[:, :, :1], z[:, :1])
    assert not pgn.supports(o, z[:, :, :256])
    with pytest.raises(ValueError, match="do not take"):
        pgn.gated_rms_norm_pallas(o[:, :, :1], z[:, :1], jnp.ones((128,)), eps=EPS)


@SHAPES
def test_forward_is_the_xla_forms(hv, t, dv):
    """fp32 to 1e-6 of the output's size; bf16 to one unit in the last
    place (the same fp32 arithmetic, rounded once)."""
    o, z, w, _ = _inputs(hv, t, dv, jnp.float32)
    got, want = _kernel(o, z, w), xla_form(o, z, w, EPS)
    assert got.dtype == want.dtype and got.shape == want.shape == z.shape
    assert float(jnp.abs(got - want).max()) <= 1e-6 * float(jnp.abs(want).max())
    ob, zb, _, _ = _inputs(hv, t, dv, jnp.bfloat16)
    got, want = _kernel(ob, zb, w), xla_form(ob, zb, w, EPS)
    assert got.dtype == jnp.bfloat16
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    ulp = np.maximum(2.0 ** (np.floor(np.log2(np.abs(want) + 1e-30)) - 7), 2.0 ** -133)
    assert np.all(np.abs(got - want) <= ulp)


@SHAPES
def test_backward_is_autodiff_of_the_xla_form(hv, t, dv):
    """do, dz, dw in fp32 to 1e-5 of each gradient's size."""
    o, z, w, dy = _inputs(hv, t, dv, jnp.float32)
    got = _grads(_kernel, o, z, w, dy)
    want = _grads(lambda *a: xla_form(*a, EPS), o, z, w, dy)
    for g, r in zip(got, want):
        assert g.shape == r.shape and g.dtype == r.dtype
        assert float(jnp.abs(g - r).max()) <= 1e-5 * float(jnp.abs(r).max())


@pytest.mark.parametrize("t", [WHOLE, RAGGED])
def test_backward_keeps_the_inputs_dtypes(t):
    """bf16 o and z: do and dz come back in bf16, head-major and
    time-major, to a unit in the last place of the gradient's size; dw in
    the scale's own dtype."""
    o, z, w, dy = _inputs(4, t, 128, jnp.bfloat16)
    got = _grads(_kernel, o, z, w, dy)
    want = _grads(lambda *a: xla_form(*a, EPS), o, z, w, dy)
    for g, r, dtype in zip(got, want, (jnp.bfloat16, jnp.bfloat16, jnp.float32)):
        assert g.dtype == r.dtype == dtype and g.shape == r.shape
        g, r = (np.asarray(a, np.float32) for a in (g, r))
        assert np.abs(g - r).max() <= 2.0 ** -7 * np.abs(r).max()


def test_a_ragged_tiles_rows_past_the_end_reach_nothing():
    """``dw`` sums over the sequence's rows alone, whatever lies in the
    part of the last block that is past them."""
    o, z, w, dy = _inputs(4, RAGGED, 128, jnp.float32, batch=1)
    pad = lambda a, axis: jnp.concatenate(  # noqa: E731
        [a, jnp.full_like(jax.lax.slice_in_dim(a, 0, WHOLE - RAGGED, axis=axis), 7.0)], axis
    )
    short = _grads(_kernel, o, z, w, dy)[2]
    long = _grads(_kernel, pad(o, 2), pad(z, 1), w, pad(dy, 1).at[:, RAGGED:].set(0.0))[2]
    np.testing.assert_allclose(np.asarray(short), np.asarray(long), rtol=1e-5)


def test_the_kernels_own_tile(monkeypatch):
    """1,024 rows at the chip's tile (2 x 512), 8 heads of a group and two
    groups: forward and every gradient."""
    monkeypatch.undo()
    o, z, w, dy = _inputs(16, 1024, 128, jnp.float32, batch=1)
    got, want = _kernel(o, z, w), xla_form(o, z, w, EPS)
    assert float(jnp.abs(got - want).max()) <= 1e-6 * float(jnp.abs(want).max())
    for g, r in zip(_grads(_kernel, o, z, w, dy),
                    _grads(lambda *a: xla_form(*a, EPS), o, z, w, dy)):
        assert float(jnp.abs(g - r).max()) <= 1e-5 * float(jnp.abs(r).max())


def test_leading_axes_merge_into_the_batch():
    o, z, w, _ = _inputs(4, WHOLE, 128, jnp.float32, batch=6)
    got = _kernel(o.reshape(2, 3, *o.shape[1:]), z.reshape(2, 3, *z.shape[1:]), w)
    np.testing.assert_array_equal(
        np.asarray(got.reshape(z.shape)), np.asarray(_kernel(o, z, w))
    )


@pytest.mark.parametrize("why,hv,t,dv,backend", [
    ("dv-192", 30, WHOLE, 192, "pallas_interpret"),
    ("one-row", 4, 1, 128, "pallas_interpret"),
    ("xla", 4, WHOLE, 128, "xla"),
    ("eager", 4, WHOLE, 128, "eager"),
    ("auto-on-the-cpu", 4, WHOLE, 128, "auto"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_op_declines_to_the_old_outputs_arithmetic(why, hv, t, dv, backend, dtype):
    """No kernel in the program, and bit for bit what ``_output`` computed
    on the o that ``_rule`` had transposed back."""
    o, z, w, _ = _inputs(hv, t, dv, dtype)
    op = lambda o, z, w: dispatch.gated_rms_norm(o, z, w, eps=EPS, backend=backend)  # noqa: E731
    assert "pallas_call" not in str(jax.make_jaxpr(op)(o, z, w))
    got, want = op(o, z, w), _old_output(jnp.swapaxes(o, 1, 2), z, w, dtype)
    assert got.dtype == want.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    assert "pallas_call" in str(jax.make_jaxpr(_kernel)(*_inputs(4, WHOLE, 128, dtype)[:3]))


def _layer(backend, dv):
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.mixers import GatedDeltaNet

    cfg = dataclasses.replace(
        get_config("qwen3_next_80b"), d_model=64, gdn_key_heads=2,
        gdn_value_heads=4, gdn_key_dim=16, gdn_value_dim=dv, dtype="float32",
        max_seq_len=128, remat=False, backend=backend,
    )
    layer = GatedDeltaNet(cfg)
    x = jax.random.normal(jax.random.key(1), (2, WHOLE, cfg.d_model))
    params = layer.init(jax.random.key(0), x)["params"]
    params["out_norm"] = 1.0 + 0.3 * jax.random.normal(jax.random.key(2), (dv,))
    return layer, params, x


def test_the_layer_with_the_op_is_the_layer_on_the_parents_arithmetic(monkeypatch):
    """``GatedDeltaNet.__call__`` under ``pallas_interpret`` at ``dv`` 128,
    where the gate's kernels engage, against the same layer with the gate
    declined (its XLA form, the parent's arithmetic; the rule's and the
    conv's kernels on both sides): the output and every parameter's
    gradient (through the rule's backward, which amplifies the rounding of
    ``do``) to 1e-4 of its size, the output to the conv test's 1e-6."""
    layer, params, x = _layer("pallas_interpret", 128)
    dy = jax.random.normal(jax.random.key(3), x.shape)

    def run():
        loss = lambda p, x: jnp.sum(layer.apply({"params": p}, x) * dy)  # noqa: E731
        return layer.apply({"params": params}, x), jax.grad(loss, argnums=(0, 1))(params, x)

    assert "gated_norm_fwd" in str(jax.make_jaxpr(lambda x: layer.apply({"params": params}, x))(x))
    got, g_got = run()
    monkeypatch.setattr(pgn, "supports", lambda o, z: False)
    assert "gated_norm_fwd" not in str(jax.make_jaxpr(lambda x: layer.apply({"params": params}, x))(x))
    want, g_want = run()
    assert float(jnp.abs(want).max()) > 0.05
    assert float(jnp.abs(got - want).max()) <= 1e-6 * float(jnp.abs(want).max())
    for g, r in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        assert float(jnp.abs(g - r).max()) <= 1e-4 * float(jnp.abs(r).max())


def test_a_decode_step_takes_the_xla_form_under_a_pallas_backend():
    """One row a sequence: the step's program holds no gate kernel, and its
    output is the parallel forward's last row."""
    layer, params, x = _layer("pallas_interpret", 128)
    x = x[:, :17]
    whole = layer.apply({"params": params}, x)
    _, state = layer.apply({"params": params}, x[:, :16], method="prefill")
    step = lambda x, s: layer.apply(  # noqa: E731
        {"params": params}, x, s, jnp.int32(16), method="decode_step"
    )
    assert "gated_norm" not in str(jax.make_jaxpr(step)(x[:, 16], state))
    got, _ = step(x[:, 16], state)
    assert float(jnp.abs(got - whole[:, 16]).max()) <= 2e-5
