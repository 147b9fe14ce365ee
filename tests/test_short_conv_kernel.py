"""The short causal conv's Mosaic kernels (``ops/pallas/short_conv.py``) in
interpret mode against the XLA form, ``ops/gated_delta.py::
causal_short_conv``: the forward over one tile, two tiles (the halo crosses
a tile edge) and a length the kernels do not take (the dispatch falls to
the XLA form); the backward, which the benchmark's ``correct`` cannot see,
against autodiff of the XLA form; causality across a tile edge.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.ops import dispatch
from orion_tpu.ops.gated_delta import causal_short_conv as xla_form
from orion_tpu.ops.pallas import short_conv as psc

W = 4
# one time tile, two, and no whole tile, at the tile the fixture sets
ONE, TWO, NOT_TAKEN = 512, 1024, 100


@pytest.fixture(autouse=True)
def tiles_of_512_rows(monkeypatch):
    """The kernels' own tile is 2,048 rows, eight strips of 64 a lane
    chunk; the interpreter walks 512 here, the same code over fewer strips
    (``test_two_tiles_of_the_kernels_own`` keeps the chip's)."""
    monkeypatch.setattr(psc, "_TILE_T", 512)


LENGTHS = pytest.mark.parametrize("t", [ONE, TWO, NOT_TAKEN])
WIDTHS = pytest.mark.parametrize("c", [128, 384])
OPTIONS = pytest.mark.parametrize(
    "tail,bias,activation",
    [(False, False, True), (True, False, True), (False, True, True),
     (True, True, True), (True, True, False), (False, False, False)],
)


def _inputs(t, c, tail, bias, dtype, batch=2):
    ks = jax.random.split(jax.random.key(t + c), 5)
    x = jax.random.normal(ks[0], (batch, t, c)).astype(dtype)
    w = (0.5 * jax.random.normal(ks[1], (W, c))).astype(dtype)
    tl = jax.random.normal(ks[2], (batch, W - 1, c)).astype(dtype) if tail else None
    bs = (0.3 * jax.random.normal(ks[3], (c,))).astype(dtype) if bias else None
    return x, w, tl, bs, jax.random.normal(ks[4], (batch, t, c))


def _kernel(x, w, activation, tail, bias):
    return dispatch.causal_short_conv(
        x, w, activation, tail, bias, backend="pallas_interpret"
    )


def _same_outputs_and_gradients(x, w, tl, bs, dy):
    """fp32, tail and bias given: the output to 1e-6, dx, dw, dtail, dbias
    to 1e-5 of their sizes."""
    loss = lambda fn: lambda *a: jnp.sum(fn(a[0], a[1], True, a[2], a[3]) * dy)  # noqa: E731
    got, want = _kernel(x, w, True, tl, bs), xla_form(x, w, True, tl, bs)
    assert float(jnp.abs(got - want).max()) <= 1e-6 * float(jnp.abs(want).max())
    for g, r in zip(jax.grad(loss(_kernel), argnums=(0, 1, 2, 3))(x, w, tl, bs),
                    jax.grad(loss(xla_form), argnums=(0, 1, 2, 3))(x, w, tl, bs)):
        assert float(jnp.abs(g - r).max()) <= 1e-5 * float(jnp.abs(r).max())


def test_the_kernels_choose_their_tiles(monkeypatch):
    assert psc.time_tile(ONE) == 512 and psc.time_tile(TWO) == 512
    assert psc.time_tile(48) == 48 and psc.time_tile(768) == 384
    assert psc.time_tile(NOT_TAKEN) is None and psc.time_tile(16 * 67) is None
    monkeypatch.undo()  # the chip's: the train point, the serve cells' pieces
    assert psc.time_tile(8192) == 2048 and psc.time_tile(1024) == 1024 and psc.time_tile(512) == 512
    assert [psc._channel_tile(c) for c in (8192, 11520, 4352, 128)] == [512, 384, 256, 128]
    assert [psc._strip(r) for r in (2048, 512, 96, 48)] == [64, 64, 32, 16]
    x = jnp.zeros((1, ONE, 128))
    assert psc.supports(x, jnp.zeros((W, 128)))
    assert not psc.supports(jnp.zeros((1, ONE, 96)), jnp.zeros((W, 96)))
    assert not psc.supports(jnp.zeros((1, NOT_TAKEN, 128)), jnp.zeros((W, 128)))
    with pytest.raises(ValueError, match="do not take"):
        psc.causal_short_conv_pallas(jnp.zeros((1, NOT_TAKEN, 128)), jnp.zeros((W, 128)))


@LENGTHS
@WIDTHS
@OPTIONS
def test_forward_is_the_xla_forms(t, c, tail, bias, activation):
    """fp32 to 1e-6 of the output's size; bf16 to one unit in the last
    place (the same fp32 sum in the same order, rounded once)."""
    x, w, tl, bs, _ = _inputs(t, c, tail, bias, jnp.float32)
    got, want = _kernel(x, w, activation, tl, bs), xla_form(x, w, activation, tl, bs)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert float(jnp.abs(got - want).max()) <= 1e-6 * float(jnp.abs(want).max())
    xb, wb, tlb, bsb, _ = _inputs(t, c, tail, bias, jnp.bfloat16)
    got, want = _kernel(xb, wb, activation, tlb, bsb), xla_form(xb, wb, activation, tlb, bsb)
    assert got.dtype == jnp.bfloat16
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    ulp = np.maximum(2.0 ** (np.floor(np.log2(np.abs(want) + 1e-30)) - 7), 2.0 ** -133)
    assert np.all(np.abs(got - want) <= ulp)


@LENGTHS
@WIDTHS
@OPTIONS
def test_backward_is_autodiff_of_the_xla_form(t, c, tail, bias, activation):
    """dx, dw, dtail, dbias in fp32 to 1e-5 of each gradient's size."""
    x, w, tl, bs, dy = _inputs(t, c, tail, bias, jnp.float32)
    args = [a for a in (x, w, tl, bs) if a is not None]

    def grads(fn):
        def loss(*given):
            given = iter(given)
            x, w = next(given), next(given)
            tail_ = next(given) if tail else None
            bias_ = next(given) if bias else None
            return jnp.sum(fn(x, w, activation, tail_, bias_) * dy)

        return jax.grad(loss, argnums=tuple(range(len(args))))(*args)

    for got, want in zip(grads(_kernel), grads(xla_form)):
        assert got.shape == want.shape and got.dtype == want.dtype
        assert float(jnp.abs(got - want).max()) <= 1e-5 * float(jnp.abs(want).max())


def test_backward_keeps_the_inputs_dtypes():
    x, w, tl, bs, dy = _inputs(ONE, 128, True, True, jnp.bfloat16, batch=1)
    got = jax.grad(
        lambda *a: jnp.sum(_kernel(a[0], a[1], True, a[2], a[3]) * dy), argnums=(0, 1, 2, 3)
    )(x, w, tl, bs)
    want = jax.grad(
        lambda *a: jnp.sum(xla_form(a[0], a[1], True, a[2], a[3]) * dy), argnums=(0, 1, 2, 3)
    )(x, w, tl, bs)
    for g, r in zip(got, want):
        assert g.dtype == jnp.bfloat16 and g.shape == r.shape
        g, r = (np.asarray(a, np.float32) for a in (g, r))
        assert np.abs(g - r).max() <= 2.0 ** -7 * np.abs(r).max()


@pytest.mark.parametrize("cut", [ONE - 1, ONE, ONE + 2])
def test_no_output_reads_a_later_row_across_a_tile_edge(cut):
    x, w, _, _, dy = _inputs(TWO, 128, False, False, jnp.float32, batch=1)
    whole = _kernel(x, w, True, None, None)
    zeroed = _kernel(x.at[:, cut:].set(0.0), w, True, None, None)
    np.testing.assert_array_equal(np.asarray(whole[:, :cut]), np.asarray(zeroed[:, :cut]))
    # and no gradient flows back from an earlier output to a later input
    dx = jax.grad(lambda x: jnp.sum(_kernel(x, w, True, None, None)[:, :cut] * dy[:, :cut]))(x)
    assert float(jnp.abs(dx[:, cut:]).max()) == 0.0
    assert float(jnp.abs(dx[:, cut - W:cut]).min()) > 0.0


def test_two_tiles_of_the_kernels_own(monkeypatch):
    """4,096 rows at the chip's tile (2 x 2,048) and 512 channels (two
    256-lane chunks a tile): forward and every gradient."""
    monkeypatch.undo()
    _same_outputs_and_gradients(*_inputs(4096, 512, True, True, jnp.float32, batch=1))


@pytest.mark.parametrize("t", [16, 64, 96])
def test_a_tile_of_one_strip_or_of_short_ones(t):
    """A prompt piece shorter than a strip's 64 rows is one strip (no loop
    over the rest); 96 rows walk as three strips of 32."""
    _same_outputs_and_gradients(*_inputs(t, 128, True, True, jnp.float32))


def test_a_mesh_that_splits_takes_the_xla_form():
    """No shard_map is written for the conv's kernels: the mixers ask for
    the XLA form where a bare Mosaic call would be refused."""
    import dataclasses

    from orion_tpu.models.configs import get_config
    from orion_tpu.models.mixers import whole_array_backend
    from orion_tpu.parallel.mesh import MeshConfig, make_mesh

    cfg = dataclasses.replace(get_config("tiny"), backend="pallas_interpret")
    assert whole_array_backend(cfg, None) == "pallas_interpret"
    one = make_mesh(MeshConfig(dp=1).resolve(1), devices=jax.devices()[:1])
    assert whole_array_backend(cfg, one) == "pallas_interpret"
    two = make_mesh(MeshConfig(dp=2).resolve(2), devices=jax.devices()[:2])
    assert whole_array_backend(cfg, two) == "xla"
    assert whole_array_backend(dataclasses.replace(cfg, backend="xla"), two) == "xla"


def test_leading_axes_merge_into_the_batch():
    x, w, tl, bs, _ = _inputs(ONE, 128, True, True, jnp.float32, batch=6)
    x4, tl4 = x.reshape(2, 3, ONE, 128), tl.reshape(2, 3, W - 1, 128)
    got = _kernel(x4, w, True, tl4, bs)
    np.testing.assert_array_equal(
        np.asarray(got.reshape(x.shape)), np.asarray(_kernel(x, w, True, tl, bs))
    )


def test_every_other_backend_is_the_xla_form_to_the_character():
    x, w, tl, bs, _ = _inputs(ONE, 128, True, True, jnp.float32, batch=1)
    want = str(jax.make_jaxpr(lambda *a: xla_form(a[0], a[1], True, a[2], a[3]))(x, w, tl, bs))
    for backend in ("xla", "eager", "auto"):  # auto: this process sees the CPU
        got = jax.make_jaxpr(
            lambda *a: dispatch.causal_short_conv(a[0], a[1], True, a[2], a[3], backend=backend)
        )(x, w, tl, bs)
        assert str(got) == want
    assert "pallas_call" in str(jax.make_jaxpr(lambda *a: _kernel(a[0], a[1], True, a[2], a[3]))(x, w, tl, bs))
    short = x[:, :NOT_TAKEN]
    assert "pallas_call" not in str(
        jax.make_jaxpr(lambda *a: _kernel(a[0], a[1], True, a[2], a[3]))(short, w, tl, bs)
    )
