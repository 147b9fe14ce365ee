"""The gated delta rule's Pallas kernels (``ops/pallas/gated_delta.py``) in
interpret mode on the CPU, against the token-by-token recurrence
(``gated_delta_recurrent``), forward and ``jax.grad`` with respect to q, k,
v, beta and g.

Tolerances. fp32 inputs: the two sides differ by the order of summation,
so ``tests/test_qwen3_next.py``'s limits for the XLA chunked form hold
(2e-5 absolute on O(1) outputs, 1e-4 of a gradient's largest entry); the
log-decay's gradient under a decay that underflows inside a chunk is a
sum of cancelling terms and gets 3e-4. bf16 inputs: matmul operands are
rounded to 8 bits where the XLA form rounds them, so the kernel is held
to a few roundings of the output (2^-6 of its largest entry against the
fp32 recurrence, 2^-7 against the XLA form at the same inputs) and a few
percent of each gradient's largest entry.

The kernels that read q, k and v where the short conv left them
(``gated_delta_qkv_pallas``) are held to the same limits against the
specification ``ops/gated_delta.py::gated_delta_qkv`` (slice, l2 norm, scale,
the recurrence), forward and ``jax.grad`` with respect to qkv, beta and g.
"""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import pytest

from orion_tpu.ops import dispatch
from orion_tpu.ops.dispatch import gated_delta_rule
from orion_tpu.ops.gated_delta import (
    gated_delta_chunked, gated_delta_qkv, gated_delta_recurrent,
)
from orion_tpu.ops.pallas import gated_delta as pgd

def inputs(t, g_scale, *, lead=(1, 2), hk=None, dk=16, dv=24, dtype=jnp.float32, seed=0):
    """q, k on ``hk`` key heads (default: one a value head), unit keys."""
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    klead = lead[:-1] + (hk or lead[-1],)
    q = unit(jax.random.normal(ks[0], klead + (t, dk))).astype(dtype)
    k = unit(jax.random.normal(ks[1], klead + (t, dk))).astype(dtype)
    v = jax.random.normal(ks[2], lead + (t, dv)).astype(dtype)
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], lead + (t,)))
    g = -g_scale * jax.nn.softplus(jax.random.normal(ks[4], lead + (t,)))
    return q, k, v, beta, g


def repeated_keys(t=128, d=8):
    """Identical keys at beta 1, no decay: the in-chunk system at its
    stiffest, as ``test_chunked_delta_rule_survives_repeated_keys`` has it.
    (Just under 1 the Neumann products lose digits in BOTH forms alike:
    3.2e-3 at beta 0.999, the XLA form's reading and the kernel's.)"""
    k = jnp.tile(jnp.eye(d)[0], (1, 1, t, 1))
    v = jax.random.normal(jax.random.key(0), (1, 1, t, d))
    return k, k, v, jnp.ones((1, 1, t)), jnp.zeros((1, 1, t))


def recurrence(q, k, v, beta, g):
    group = v.shape[-3] // q.shape[-3]
    q, k = (jnp.repeat(x, group, axis=-3) for x in (q, k))
    return gated_delta_recurrent(q, k, v, beta, g)


def kernel(*args):
    return gated_delta_rule(*args, backend="pallas_interpret")


def weighted(fn, v):
    w = jnp.cos(jnp.arange(v.shape[-1]) + jnp.arange(v.shape[-2])[:, None])
    return lambda *a: (fn(*a).astype(jnp.float32) * w).sum()


# T on and off a multiple of the chunk (128) and of a block of chunks
# (1024), shorter than either; a mild decay, one that underflows exp()
# inside a chunk (g down to about -150); grouped key heads; batch > 1; bf16
CASES = {
    "T64": (lambda: inputs(64, 0.1), {}),
    "T70": (lambda: inputs(70, 0.1), {}),
    "T150": (lambda: inputs(150, 0.1), {}),
    "T37-g5": (lambda: inputs(37, 5.0), {}),
    "T600-one-short-block": (lambda: inputs(600, 1.0), {}),
    "T1100-two-blocks": (lambda: inputs(1100, 1.0, lead=(1, 1)), {}),
    "T150-g40-underflow": (lambda: inputs(150, 40.0), {"dg": 3e-4}),
    "T64-g40-underflow": (lambda: inputs(64, 40.0), {"dg": 3e-4}),
    "repeated-keys": (repeated_keys, {"zero": ("dg",)}),  # o_t = v_t whatever the decay
    "grouped-4-on-2-three-blocks": (lambda: inputs(2100, 1.0, lead=(1, 4), hk=2), {}),
    "grouped-mqa-batch2": (lambda: inputs(70, 0.1, lead=(2, 3), hk=1), {}),
    "batch3": (lambda: inputs(70, 1.0, lead=(3, 2)), {}),
    "bf16-T150": (lambda: inputs(150, 0.5, dtype=jnp.bfloat16), {"bf16": True}),
    "bf16-grouped-T1100": (
        lambda: inputs(1100, 0.1, lead=(2, 4), hk=2, dtype=jnp.bfloat16), {"bf16": True}),
}


def assert_forward_and_grads_agree(want_fn, got_fn, args, names, bf16, opts=None):
    """``got_fn`` (a kernel) against ``want_fn`` (the fp32 recurrence) on
    ``args``: the output, and ``jax.grad`` of a weighted sum of it with
    respect to every argument (``names``), to this file's tolerances."""
    opts = opts or {}
    want, got = want_fn(*args), jax.jit(got_fn)(*args)
    assert got.dtype == want.dtype and got.shape == want.shape
    scale = float(jnp.abs(want.astype(jnp.float32)).max())
    assert scale > 0.1  # not a comparison of zeros
    err = float(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max())
    assert err < (2.0 ** -6 * scale if bf16 else 2e-5), err
    argnums = tuple(range(len(args)))
    g_want = jax.grad(weighted(want_fn, want), argnums=argnums)(*args)
    g_got = jax.jit(jax.grad(weighted(got_fn, want), argnums=argnums))(*args)
    for name, w, g, a in zip(names, g_want, g_got, args):
        assert g.shape == a.shape and g.dtype == a.dtype, name
        w, g = w.astype(jnp.float32), g.astype(jnp.float32)
        assert bool(jnp.isfinite(g).all()), name
        top = float(jnp.abs(w).max())
        if name in opts.get("zero", ()):
            assert top < 1e-6 and float(jnp.abs(g).max()) < 1e-4, name
            continue
        assert top > 1e-6, name
        tol = 0.04 if bf16 else opts.get(name, 1e-4)
        assert float(jnp.abs(g - w).max()) < tol * top, (name, float(jnp.abs(g - w).max()) / top)


@pytest.mark.parametrize("case", CASES)
def test_kernel_equals_the_recurrence_forward_and_grad(case):
    make, opts = CASES[case]
    assert_forward_and_grads_agree(
        recurrence, kernel, make(), ("dq", "dk", "dv", "dbeta", "dg"),
        opts.get("bf16", False), opts,
    )


def test_kernel_and_xla_chunked_form_agree_to_the_outputs_rounding():
    """bf16, the same inputs: the two executions of one algorithm round
    their operands at the same places."""
    q, k, v, beta, g = inputs(1100, 0.3, lead=(2, 4), hk=2, dk=32, dv=32, dtype=jnp.bfloat16)
    rep = lambda x: jnp.repeat(x, 2, axis=1)  # noqa: E731
    xla = gated_delta_chunked(rep(q), rep(k), v, beta, g).astype(jnp.float32)
    got = kernel(q, k, v, beta, g).astype(jnp.float32)
    assert jnp.array_equal(gated_delta_rule(q, k, v, beta, g, backend="xla"), xla.astype(v.dtype))
    assert float(jnp.abs(got - xla).max()) < 2.0 ** -7 * float(jnp.abs(xla).max())
    assert float(jnp.abs(got - xla).mean()) < 2.0 ** -9 * float(jnp.abs(xla).mean())


def test_dispatch_reaches_the_kernel_on_pallas_backends_only(monkeypatch):
    """``xla`` and ``eager`` never touch the kernel module; compiled for a
    TPU, widths off a multiple of 128 fall back to the XLA form."""
    calls = []
    real = pgd.gated_delta_rule_pallas
    monkeypatch.setattr(
        pgd, "gated_delta_rule_pallas",
        lambda *a, **kw: calls.append(kw) or real(*a, **{**kw, "interpret": True}),
    )
    args = inputs(70, 0.1)
    for backend in ("xla", "eager"):
        gated_delta_rule(*args, backend=backend)
    assert not calls
    gated_delta_rule(*args, backend="pallas_interpret")
    assert calls == [{"interpret": True}]
    gated_delta_rule(*args, backend="pallas")  # Dk 16, Dv 24: the XLA form
    assert len(calls) == 1
    assert pgd.supports(128, 128) and pgd.supports(256, 128) and not pgd.supports(128, 64)
    wide = inputs(64, 0.1, lead=(1, 1), dk=128, dv=128)
    gated_delta_rule(*wide, backend="pallas")
    assert calls[1:] == [{"interpret": False}]


def test_mixer_on_a_mesh_runs_the_kernel_on_each_shard():
    """``GatedDeltaNet`` on a dp2 x tp2 mesh: ``kernel_bh`` manualises the
    op over batch and heads (GSPMD cannot partition a Mosaic call), each
    shard's key heads still serving its own value heads. Same values as
    one device's XLA form, forward and the gradient of the input."""
    import dataclasses

    from orion_tpu.models.configs import get_config
    from orion_tpu.models.mixers import GatedDeltaNet
    from orion_tpu.parallel.mesh import MeshConfig, make_mesh

    def cfg(backend):
        return dataclasses.replace(
            get_config("qwen3_next_80b"), d_model=64, gdn_key_heads=2,
            gdn_value_heads=4, gdn_key_dim=16, gdn_value_dim=16,
            dtype="float32", backend=backend,
        )

    x = jax.random.normal(jax.random.key(2), (4, 70, 64))
    plain = GatedDeltaNet(cfg("xla"))
    params = plain.init(jax.random.key(0), x)
    mesh = make_mesh(MeshConfig(dp=2, tp=2).resolve(4), devices=jax.devices()[:4])
    sharded = GatedDeltaNet(cfg("pallas_interpret"), mesh=mesh)
    loss = lambda m: lambda y: (m.apply(params, y) * jnp.cos(jnp.arange(64.0))).sum()  # noqa: E731
    want, got = plain.apply(params, x), jax.jit(sharded.apply)(params, x)
    assert float(jnp.abs(want).max()) > 0.05
    assert float(jnp.abs(got - want).max()) < 2e-5
    g_want, g_got = jax.grad(loss(plain))(x), jax.jit(jax.grad(loss(sharded)))(x)
    assert float(jnp.abs(g_got - g_want).max()) < 1e-4 * float(jnp.abs(g_want).max())


def qkv_inputs(t, *, hk, group, d=16, dtype=jnp.float32, batch=2, seed=0):
    """The short conv's output ``[B, T, C]`` (SiLU of noise, as the conv
    leaves it), beta and g ``[B, Hv, T]``, and the heads' widths."""
    hv = hk * group
    ks = jax.random.split(jax.random.key(seed), 3)
    qkv = jax.nn.silu(jax.random.normal(ks[0], (batch, t, (2 * hk + hv) * d))).astype(dtype)
    beta = jax.nn.sigmoid(jax.random.normal(ks[1], (batch, hv, t)))
    g = -0.5 * jax.nn.softplus(jax.random.normal(ks[2], (batch, hv, t)))
    return (qkv, beta, g), dict(key_heads=hk, key_dim=d, value_dim=d, eps=1e-6)


# batch 2 throughout; T of one chunk (whole and ragged), of one short block
# with a ragged tail, of two blocks; one and two value heads a key head
QKV_CASES = {
    "T100-one-ragged-chunk-group1": dict(t=100, hk=2, group=1),
    "T128-one-chunk-group2": dict(t=128, hk=2, group=2),
    "T128-one-chunk-group2-bf16": dict(t=128, hk=2, group=2, dtype=jnp.bfloat16),
    "T600-one-short-block-group2": dict(t=600, hk=1, group=2),
    "T1100-two-blocks-group1": dict(t=1100, hk=1, group=1),
    "T1100-two-blocks-group2-bf16": dict(t=1100, hk=1, group=2, dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("case", QKV_CASES)
def test_in_place_kernels_equal_the_specification_forward_and_grad(case):
    args, heads = qkv_inputs(**QKV_CASES[case])
    assert_forward_and_grads_agree(
        lambda *a: gated_delta_qkv(*a, **heads),
        lambda *a: dispatch.gated_delta_qkv(*a, backend="pallas_interpret", **heads),
        args, ("dqkv", "dbeta", "dg"), args[0].dtype == jnp.bfloat16,
    )


# sha256[:16] of str(jax.make_jaxpr(...)) on the PARENT of PR 48 (f06ed3f)
PARENT_PROGRAMS = {
    "olmo-pallas-forward": "0d8275577c09e90e", "olmo-pallas-piece": "be1c5c2997640eeb",
    "olmo-pallas-step": "3f5ccda737843369", "olmo-xla-piece": "70328a13b4957845",
    "qwen-xla-forward": "11479214086f6eb4", "qwen-eager-forward": "0a28a85c13b596aa",
}


def test_in_place_form_is_reached_only_where_the_kernels_read_qkv(monkeypatch):
    """Whole lane tiles (compiled), v's columns on whole blocks, a Pallas
    backend, no state and no split mesh: everything else is the program it
    was. At ``olmo_hybrid_7b``'s widths (96 x 192) the forward, a prompt piece
    and a decode step, and under ``xla`` / ``eager`` the training forward,
    trace to the parent's jaxpr."""
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.mixers import GatedDeltaNet
    from orion_tpu.parallel.mesh import MeshConfig, make_mesh

    reads = dispatch.gated_delta_reads_qkv
    assert reads(16, 32, 128, 128, backend="pallas")  # qwen3_next_80b's heads
    assert reads(4, 4, 256, 128, backend="pallas")
    assert not reads(30, 30, 96, 192, backend="pallas")  # olmo_hybrid_7b's
    assert not reads(3, 12, 128, 128, backend="pallas")  # v starts inside a group's block
    assert reads(2, 4, 16, 16, backend="pallas_interpret")
    assert not any(reads(16, 32, 128, 128, backend=b) for b in ("xla", "eager"))

    calls = []
    real = pgd.gated_delta_qkv_pallas
    monkeypatch.setattr(
        pgd, "gated_delta_qkv_pallas", lambda *a, **kw: calls.append(a[0].shape) or real(*a, **kw)
    )
    olmo = lambda backend: dataclasses.replace(  # noqa: E731
        get_config("olmo_hybrid_7b"), d_model=96, gdn_key_heads=3, gdn_value_heads=3,
        gdn_key_dim=96, gdn_value_dim=192, dtype="bfloat16", param_dtype="float32",
        backend=backend)
    qwen = lambda backend: dataclasses.replace(  # noqa: E731
        get_config("qwen3_next_80b"), d_model=64, gdn_key_heads=2, gdn_value_heads=4,
        gdn_key_dim=16, gdn_value_dim=16, dtype="float32", backend=backend)

    def programs(cfg, t, mesh=None):
        m = GatedDeltaNet(cfg, mesh=mesh)
        x = jnp.zeros((2, t, cfg.d_model), jnp.dtype(cfg.dtype))
        params = jax.eval_shape(m.init, jax.random.key(0), x)
        state = jax.eval_shape(lambda: GatedDeltaNet.decode_state(cfg, "gated_delta", 2, x.dtype))
        return {
            "forward": lambda: jax.make_jaxpr(m.apply)(params, x),
            "piece": lambda: jax.make_jaxpr(
                lambda p, y, s, n: m.apply(p, y, s, 0, n, method="prefill_extend")
            )(params, x, state, jnp.int32(100)),
            "step": lambda: jax.make_jaxpr(
                lambda p, y, s: m.apply(p, y, s, jnp.int32(5), method="decode_step")
            )(params, x[:, 0], state),
        }

    traced = {}
    for name, cfg, t in (("olmo-pallas", olmo("pallas"), 160), ("olmo-xla", olmo("xla"), 160),
                         ("qwen-xla", qwen("xla"), 70), ("qwen-eager", qwen("eager"), 70)):
        for kind, trace in programs(cfg, t).items():
            traced[f"{name}-{kind}"] = hashlib.sha256(str(trace()).encode()).hexdigest()[:16]
    assert not calls
    assert {k: traced[k] for k in PARENT_PROGRAMS} == PARENT_PROGRAMS

    interpret = programs(qwen("pallas_interpret"), 70)
    calls.clear()  # init traced the forward
    interpret["piece"](), interpret["step"]()
    assert not calls  # a state in or out: the head-major kernels
    interpret["forward"]()
    assert calls == [(2, 70, 128)]
    mesh = make_mesh(MeshConfig(dp=2, tp=2).resolve(4), devices=jax.devices()[:4])
    programs(qwen("pallas_interpret"), 70, mesh)["forward"]()
    assert len(calls) == 1  # a mesh whose data axes split: kernel_bh's head-major form
