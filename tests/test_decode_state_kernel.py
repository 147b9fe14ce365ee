"""The row-sparse decode-state kernel pair (ops/pallas/decode_state.py:
``decode_state_step`` reads ``(S, z)`` and the chunk's own k, v rows,
``decode_state_flush`` writes the state once after the scan) in interpret
mode on the CPU, and the slot-multiplexed decode programs that call it.

Kernel level: rows the list names give ``recurrent_step``'s outputs and,
flushed, its state (fp32 rounding apart: the sums run in another order, the
flush's as one ``K^T V`` a head); every other row's ``(S, z)`` keeps its
bits, and a dead row's output is its ``v`` row. Engine
level: a ``SlotEngine`` under ``backend="pallas_interpret"`` serves the
tokens the XLA engine serves, and under ``backend="xla"`` neither program
holds a ``pallas_call`` (what keeps the CPU goldens under
orion_tpu/analysis/golden/ as they are).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.generate import (
    SampleConfig,
    _decode_batched_chunk_jit,
    _decode_batched_prefill_chunk_jit,
)
from orion_tpu.models.configs import get_config
from orion_tpu.models.transformer import TransformerLM
from orion_tpu.ops.dispatch import decode_state_step as dispatch_step
from orion_tpu.ops.linear_attention import recurrent_step
from orion_tpu.ops.pallas.decode_state import (
    decode_state_flush,
    decode_state_step,
    live_rows,
)
from orion_tpu.serving import DecodeRequest, SlotEngine

H, DK, DV = 2, 16, 24
PATTERNS = {
    "none": lambda b: [],
    "one": lambda b: [b // 2],
    "scattered": lambda b: sorted({0, *range(1, b, 3), b - 1} - {2}),
    "all": lambda b: list(range(b)),
}


def _inputs(b, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    s = jax.random.normal(ks[0], (b, H, DK, DV), jnp.float32)
    z = jax.random.normal(ks[1], (b, H, DK), jnp.float32) ** 2 + 1.0
    q = (jax.nn.elu(jax.random.normal(ks[2], (b, H, DK))) + 1).astype(dtype)
    k = (jax.nn.elu(jax.random.normal(ks[3], (b, H, DK))) + 1).astype(dtype)
    v = jax.random.normal(ks[4], (b, H, DV)).astype(dtype)
    return s, z, q, k, v


def _mask(b, pattern):
    m = np.zeros(b, bool)
    m[PATTERNS[pattern](b)] = True
    return m


def _steps(b, n, dtype=jnp.float32):
    """``n`` steps' q, k, v, each stacked on a leading step axis."""
    qkv = [_inputs(b, dtype, seed=10 + i)[2:] for i in range(n)]
    return tuple(jnp.stack(x) for x in zip(*qkv))


@jax.jit
def _chunk(s, z, xs, mask, t0):
    """The decode programs' shape: the row list built once, a ``lax.scan``
    of read-only steps that carries the chunk's own rows and each row's
    position (a live row's advances), one flush after it."""
    rows = live_rows(mask)
    (b, h, dk, dv), n = s.shape, xs[0].shape[0]
    dtype = xs[0].dtype

    def body(c, qkv):
        kc, vc, t = c
        out, (kc, vc) = decode_state_step(
            *qkv, (s, z), (kc, vc), t - t0, rows, interpret=True
        )
        return (kc, vc, jnp.where(mask, t + 1, t)), out

    rows0 = jnp.zeros((b, n, h, dk), dtype), jnp.zeros((b, n, h, dv), dtype)
    (kc, vc, _), outs = jax.lax.scan(body, (*rows0, t0), xs)
    return outs, decode_state_flush((s, z), (kc, vc), rows, interpret=True)


@jax.jit
def _reference(s, z, xs):
    def body(state, qkv):
        out, state = recurrent_step(*qkv, state)
        return state, out

    state, outs = jax.lax.scan(body, (s, z), xs)
    return outs, state


def _check_chunk(slots, n, live, dtype=jnp.float32, t0=None, out_tol=None):
    s, z = _inputs(slots)[:2]
    xs = _steps(slots, n, dtype)
    t0 = jnp.zeros((slots,), jnp.int32) if t0 is None else t0
    outs, (s1, z1) = _chunk(s, z, xs, jnp.asarray(live), t0)
    ref_outs, (ref_s, ref_z) = _reference(s, z, xs)
    assert s1.dtype == z1.dtype == jnp.float32 and outs.dtype == dtype
    f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
    np.testing.assert_allclose(
        f32(outs)[:, live], f32(ref_outs)[:, live],
        **(out_tol or dict(rtol=1e-5, atol=1e-5)),
    )
    # a dead row's output is its v row, at every step
    np.testing.assert_array_equal(f32(outs)[:, ~live], f32(xs[2])[:, ~live])
    for got, ref, old in ((s1, ref_s, s), (z1, ref_z, z)):
        np.testing.assert_allclose(
            f32(got)[live], f32(ref)[live], rtol=1e-5, atol=1e-5
        )
        np.testing.assert_array_equal(f32(got)[~live], f32(old)[~live])


@pytest.mark.parametrize("pattern", list(PATTERNS))
@pytest.mark.parametrize("slots", [4, 8, 64])
def test_live_rows_step_dead_rows_keep_their_bits(slots, pattern):
    """One step and its flush: ``recurrent_step`` on the listed rows."""
    _check_chunk(slots, 1, _mask(slots, pattern))


@pytest.mark.parametrize("pattern", list(PATTERNS))
def test_three_steps_inside_a_scan_then_one_flush(pattern):
    _check_chunk(8, 3, _mask(8, pattern))


@pytest.mark.parametrize("n_steps", [1, 4, 16])
def test_a_chunk_of_n_steps_is_n_recurrent_steps(n_steps):
    _check_chunk(8, n_steps, _mask(8, "scattered"))


def test_rows_live_from_their_own_positions():
    """``j = t - t0`` a row: rows that enter the chunk at different,
    non-zero positions walk the same chunk."""
    _check_chunk(8, 4, _mask(8, "scattered"), t0=jnp.arange(8, dtype=jnp.int32) * 7 + 3)


def test_the_flush_alone_leaves_unlisted_rows_their_bits():
    slots, n = 8, 4
    s, z = _inputs(slots)[:2]
    _, k, v = _steps(slots, n)
    kc, vc = jnp.moveaxis(k, 0, 1), jnp.moveaxis(v, 0, 1)  # [B, n, H, D]
    live = _mask(slots, "scattered")
    flush = jax.jit(
        lambda *a: decode_state_flush(a[:2], a[2:4], live_rows(a[4]), interpret=True)
    )
    s1, z1 = flush(s, z, kc, vc, jnp.asarray(live))
    np.testing.assert_array_equal(np.asarray(s1)[~live], np.asarray(s)[~live])
    np.testing.assert_array_equal(np.asarray(z1)[~live], np.asarray(z)[~live])
    want_z = np.asarray(z) + np.asarray(kc).sum(axis=1)
    np.testing.assert_allclose(np.asarray(z1)[live], want_z[live], rtol=1e-5, atol=1e-5)
    assert not (np.asarray(s1)[live] == np.asarray(s)[live]).all()
    # no listed row: nothing runs, nothing moves
    s2, z2 = flush(s, z, kc, vc, jnp.zeros(slots, bool))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(s))
    np.testing.assert_array_equal(np.asarray(z2), np.asarray(z))


def test_bf16_qkv_as_the_model_sends_them():
    # one bf16 ulp: the two orders of the sums may round the quotient apart
    _check_chunk(8, 4, _mask(8, "scattered"), dtype=jnp.bfloat16,
                 out_tol=dict(rtol=2 ** -7, atol=1e-5))


def test_state_must_be_fp32_and_qkv_one_dtype():
    s, z, q, k, v = _inputs(4)
    rows = live_rows(jnp.ones(4, bool))
    kc, vc = jnp.zeros((4, 2, H, DK)), jnp.zeros((4, 2, H, DV))
    j = jnp.zeros((4,), jnp.int32)
    with pytest.raises(ValueError, match="float32"):
        decode_state_step(q, k, v, (s.astype(jnp.bfloat16), z), (kc, vc), j,
                          rows, interpret=True)
    with pytest.raises(ValueError, match="share a dtype"):
        decode_state_step(q, k, v.astype(jnp.bfloat16), (s, z), (kc, vc), j,
                          rows, interpret=True)
    with pytest.raises(ValueError, match="do not fit"):
        decode_state_flush((s, z), (kc[:, :, :1], vc), rows, interpret=True)


@pytest.mark.parametrize("backend,rows_given", [
    ("xla", True), ("xla", False), ("pallas_interpret", False),
])
def test_dispatch_steps_every_row_without_a_list_or_a_pallas_backend(
    backend, rows_given
):
    """ops.dispatch: the kernel runs only with a row list AND a Pallas
    backend; every other combination is ``recurrent_step``, bit for bit."""
    s, z, q, k, v = _inputs(4)
    rows = live_rows(jnp.asarray(_mask(4, "one"))) if rows_given else None
    out, (s1, z1) = dispatch_step(q, k, v, (s, z), rows, backend=backend)
    ref_out, (ref_s, ref_z) = recurrent_step(q, k, v, (s, z))
    for got, ref in ((out, ref_out), (s1, ref_s), (z1, ref_z)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_dispatch_refuses_a_row_list_without_the_chunks_rows():
    """Nothing writes ``(S, z)`` at a step under a Pallas backend any
    more: a row list with no ``chunk`` would leave the state behind."""
    s, z, q, k, v = _inputs(4)
    rows = live_rows(jnp.ones(4, bool))
    with pytest.raises(ValueError, match="go together"):
        dispatch_step(q, k, v, (s, z), rows, backend="pallas_interpret")


# -- the slot-multiplexed programs -------------------------------------------

GREEDY = SampleConfig(temperature=0.0)
TINY = get_config("tiny")
CONFIGS = {
    "tiny": TINY,
    # the hybrid's two kinds of state in one carry: the linear layer takes
    # the kernel, the window ring keeps the select
    "tiny-hybrid": dataclasses.replace(
        TINY, layer_types=("linear", "swa"), window=8
    ),
}


def _prompt(i, ln):
    return jax.random.randint(
        jax.random.PRNGKey(4000 + i), (1, ln), 0, TINY.vocab_size
    ).astype(jnp.int32)


def _engine(cfg, params, backend):
    model = TransformerLM(dataclasses.replace(cfg, backend=backend))
    return SlotEngine(model, params, slots=4, chunk=4,
                      prefill_buckets=(8, 16, 32), prefill_chunk=8)


def _params(cfg):
    return TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )


def _serve_mixed(eng):
    """Admissions mid-run, one slot of four always free, and boundaries at
    which a slot waits frozen for its turn at the prefill piece. Returns
    the results, the kinds of boundary that ran, and per boundary the
    linear layers' state before and after with the rows that did not step."""
    # (boundary of admission, prompt length, new tokens)
    plan = [(0, 5, 14), (0, 20, 9), (0, 9, 18), (7, 17, 7), (8, 3, 6)]
    done, kinds, boundaries = {}, set(), []
    b = 0
    while plan or eng.busy:
        while plan and plan[0][0] <= b and eng.active_count < 3:
            _, ln, new = plan.pop(0)
            i = 4 - len(plan)
            eng.admit(DecodeRequest(prompt=_prompt(i, ln), max_new_tokens=new,
                                    sample=GREEDY, seed=900 + i), tag=i)
        eng.flush_admissions()  # `before` reads the staged (zeroed) rows
        kinds.add("unified" if eng.prefilling_count else "pure")
        free = [i for i, s in enumerate(eng._slots) if s is None]
        before = [
            {k: np.asarray(x) for k, x in st.items() if k in ("s", "z")}
            for st in eng._carry[1]
        ]
        done.update(dict(eng.step()))
        frozen = [e["slot"] for e in eng.last_boundary if e.get("frozen")]
        after = [
            {k: np.asarray(x) for k, x in st.items() if k in ("s", "z")}
            for st in eng._carry[1]
        ]
        boundaries.append((before, after, free, frozen))
        b += 1
    return done, kinds, boundaries


@pytest.mark.parametrize("name", list(CONFIGS))
def test_engine_serves_the_xla_engines_tokens(name):
    cfg = CONFIGS[name]
    params = _params(cfg)
    ref, ref_kinds, ref_bounds = _serve_mixed(_engine(cfg, params, "xla"))
    got, kinds, bounds = _serve_mixed(_engine(cfg, params, "pallas_interpret"))
    assert kinds == ref_kinds == {"unified", "pure"}
    assert sorted(got) == sorted(ref) == [0, 1, 2, 3, 4]
    for i in ref:
        assert got[i].status == ref[i].status == "ok"
        np.testing.assert_array_equal(got[i].tokens, ref[i].tokens)
    assert len(bounds) == len(ref_bounds)
    saw_free = saw_frozen = False
    for (before, after, free, frozen), (_, ref_after, _, _) in zip(
        bounds, ref_bounds
    ):
        still = sorted(set(free) | set(frozen))
        stepped = [i for i in range(4) if i not in still]
        saw_free |= bool(free)
        saw_frozen |= bool(frozen)
        for layer_before, layer_after, layer_ref in zip(
            before, after, ref_after
        ):
            for key in layer_after:
                # rows that did not step: never touched by the kernel
                np.testing.assert_array_equal(
                    layer_after[key][still], layer_before[key][still]
                )
                # rows that did: the XLA engine's state, to fp32 rounding
                np.testing.assert_allclose(
                    layer_after[key][stepped], layer_ref[key][stepped],
                    rtol=1e-4, atol=1e-5,
                )
    assert saw_free and saw_frozen


def _program_jaxprs(backend):
    cfg = dataclasses.replace(TINY, backend=backend)
    model = TransformerLM(cfg)
    eng = SlotEngine(model, _params(TINY), slots=4, chunk=4,
                     prefill_buckets=(8,), prefill_chunk=8)
    eng.admit(DecodeRequest(prompt=_prompt(0, 5), max_new_tokens=4,
                            sample=GREEDY, seed=1), tag=0)
    active = jnp.asarray([s is not None for s in eng._slots])
    pure = jax.make_jaxpr(
        lambda p, c, r, a: _decode_batched_chunk_jit(
            model, p, c, r, a, 4, GREEDY)
    )(eng.params, eng._carry, eng._rngs, active)
    unified = jax.make_jaxpr(
        lambda p, c, r, a, pb, pl, pf, pw: _decode_batched_prefill_chunk_jit(
            model, p, c, r, a, pb, pl, pf, pw, 4, 8, GREEDY)
    )(eng.params, eng._carry, eng._rngs, active, eng._pbuf, eng._plen,
      eng._pfold, jnp.zeros_like(eng._plen))
    return str(pure), str(unified)


def test_xla_programs_hold_no_pallas_call():
    for text in _program_jaxprs("xla"):
        assert "pallas_call" not in text


def test_pallas_programs_hold_the_kernel():
    """Both undonated programs hold both kernels: the read-only step in
    the scan, the flush after it."""
    for text in _program_jaxprs("pallas_interpret"):
        assert "decode_state_step" in text and "decode_state_flush" in text
