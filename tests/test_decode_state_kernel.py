"""The row-sparse, in-place decode-state kernel (ops/pallas/decode_state.py)
in interpret mode on the CPU, and the slot-multiplexed decode programs that
call it.

Kernel level: rows the list names step exactly as ``recurrent_step`` does
(fp32 rounding apart: the reduction order of ``q . S`` differs), every
other row's ``(S, z)`` keeps its bits, and a dead row's output is finite
and the same on every call. Engine level: a ``SlotEngine`` under
``backend="pallas_interpret"`` serves the tokens the XLA engine serves,
and under ``backend="xla"`` neither program holds a ``pallas_call`` (what
keeps the CPU goldens under orion_tpu/analysis/golden/ as they are).
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
from orion_tpu.ops.pallas.decode_state import decode_state_step, live_rows
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


@jax.jit
def _step(s, z, q, k, v, mask):
    return decode_state_step(q, k, v, (s, z), live_rows(mask), interpret=True)


@pytest.mark.parametrize("pattern", list(PATTERNS))
@pytest.mark.parametrize("slots", [4, 8, 64])
def test_live_rows_step_dead_rows_keep_their_bits(slots, pattern):
    s, z, q, k, v = _inputs(slots)
    live = _mask(slots, pattern)
    out, (s1, z1) = _step(s, z, q, k, v, jnp.asarray(live))
    ref_out, (ref_s, ref_z) = recurrent_step(q, k, v, (s, z))
    assert s1.dtype == z1.dtype == jnp.float32 and out.dtype == q.dtype
    for got, ref in ((s1, ref_s), (z1, ref_z), (out, ref_out)):
        np.testing.assert_allclose(
            np.asarray(got)[live], np.asarray(ref)[live], rtol=1e-5, atol=1e-5
        )
    np.testing.assert_array_equal(np.asarray(s1)[~live], np.asarray(s)[~live])
    np.testing.assert_array_equal(np.asarray(z1)[~live], np.asarray(z)[~live])
    dead_out = np.asarray(out)[~live]
    assert np.isfinite(dead_out).all()
    again, _ = _step(s, z, q, k, v, jnp.asarray(live))
    np.testing.assert_array_equal(np.asarray(again)[~live], dead_out)


@pytest.mark.parametrize("pattern", list(PATTERNS))
def test_three_steps_inside_a_scan_update_in_place(pattern):
    """The decode programs' shape: the row list built once outside a
    ``lax.scan`` whose carry is the aliased state."""
    slots, steps = 8, 3
    s, z, _, _, _ = _inputs(slots)
    qkv = [_inputs(slots, seed=10 + i)[2:] for i in range(steps)]
    xs = tuple(jnp.stack(x) for x in zip(*qkv))
    live = _mask(slots, pattern)

    @jax.jit
    def run(s, z, xs, mask):
        rows = live_rows(mask)

        def body(state, qkv):
            out, state = decode_state_step(*qkv, state, rows, interpret=True)
            return state, out

        return jax.lax.scan(body, (s, z), xs)

    (s1, z1), outs = run(s, z, xs, jnp.asarray(live))
    state = (s, z)
    for i, (q, k, v) in enumerate(qkv):
        ref_out, state = recurrent_step(q, k, v, state)
        np.testing.assert_allclose(
            np.asarray(outs[i])[live], np.asarray(ref_out)[live],
            rtol=1e-5, atol=1e-5,
        )
        np.testing.assert_array_equal(
            np.asarray(outs[i])[~live], np.asarray(v)[~live]
        )
    np.testing.assert_allclose(
        np.asarray(s1)[live], np.asarray(state[0])[live], rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(z1)[live], np.asarray(state[1])[live], rtol=1e-5, atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(s1)[~live], np.asarray(s)[~live])
    np.testing.assert_array_equal(np.asarray(z1)[~live], np.asarray(z)[~live])


def test_bf16_qkv_as_the_model_sends_them():
    slots = 8
    s, z, q, k, v = _inputs(slots, dtype=jnp.bfloat16)
    live = _mask(slots, "scattered")
    out, (s1, z1) = _step(s, z, q, k, v, jnp.asarray(live))
    ref_out, (ref_s, ref_z) = recurrent_step(q, k, v, (s, z))
    assert out.dtype == jnp.bfloat16 and s1.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(s1)[live], np.asarray(ref_s)[live], rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(z1)[live], np.asarray(ref_z)[live], rtol=1e-5, atol=1e-5
    )
    # one bf16 ulp: the two reduction orders may round the quotient apart
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[live], np.asarray(ref_out, np.float32)[live],
        rtol=2 ** -7, atol=1e-5,
    )
    np.testing.assert_array_equal(np.asarray(s1)[~live], np.asarray(s)[~live])


def test_state_must_be_fp32_and_qkv_one_dtype():
    s, z, q, k, v = _inputs(4)
    rows = live_rows(jnp.ones(4, bool))
    with pytest.raises(ValueError, match="float32"):
        decode_state_step(q, k, v, (s.astype(jnp.bfloat16), z), rows,
                          interpret=True)
    with pytest.raises(ValueError, match="share a dtype"):
        decode_state_step(q, k, v.astype(jnp.bfloat16), (s, z), rows,
                          interpret=True)


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
    for text in _program_jaxprs("pallas_interpret"):
        assert "decode_state_step" in text
