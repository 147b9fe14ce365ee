"""A Mosaic kernel's Python body is traced once a distinct (entry, statics,
argument shapes and dtypes) in a process, not once a call site (ISSUE 50).

Every function of ``orion_tpu/ops/pallas/`` that builds and applies a
``pl.pallas_call`` is entered through ``ops/pallas.kernel_entry``: jax's own
trace cache keeps its jaxpr, inlined at every further site. One case an
entry, in interpret mode on the CPU: its arguments are CAPTURED from a call
of the module's public function (so they are the arguments the program
hands it), then

- two calls inside one ``jax.make_jaxpr`` run the body once
  (``kernel_bodies_traced`` +1, ``kernel_call_sites`` +2, one
  ``compile.kernel`` event with the ``pallas_call``'s name);
- another shape runs it again, and the first is still kept;
- the program is the un-jitted entry's: the same jaxpr, text for text, and
  the same bits out of it; where the public function has a ``custom_vjp``
  its gradients are bit-for-bit those with every entry of the module
  un-jitted.

And one model-level case: a four-layer ``qwen3_next``-shaped step.
"""

import ast
import dataclasses
import functools
import importlib
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.obs.trace import compile_totals, setup_record
from orion_tpu.ops import pallas as kernels

PALLAS = pathlib.Path(kernels.__file__).parent
MODULES = sorted(p.stem for p in PALLAS.glob("*.py") if p.stem != "__init__")


def _module(name):
    return importlib.import_module(f"orion_tpu.ops.pallas.{name}")


def _entries(module):
    return {n: f for n, f in vars(module).items() if hasattr(f, "kernel_name")}


def _draw(i, shape):
    """Numpy's draws: building inputs with jax would compile an op a line."""
    return np.random.default_rng(i).standard_normal(shape).astype(np.float32)


def _normal(i, shape):
    return jnp.asarray(_draw(i, shape))


def _sigmoid(i, shape):
    return jnp.asarray(1.0 / (1.0 + np.exp(-_draw(i, shape))))


def _softplus(i, shape):
    return jnp.asarray(np.log1p(np.exp(_draw(i, shape))))


def _live(b):
    from orion_tpu.ops.pallas.decode_state import live_rows

    return live_rows(jnp.arange(b) % 3 != 1)


# -- drivers: a public function of each module at two sizes ------------------
# size -> (fn, args): ``fn(*args)`` calls the entries named beside the driver
# in ENTRY_DRIVER, and its arguments are what the gradient is taken in; an entry that is itself the public function is
# looked up on its module at call time, so that a test can stand the function as
# written in its place


def _gmm(n):
    from orion_tpu.ops.pallas.gmm import gmm

    x, w = _normal(0, (16 * n, 16)), _normal(1, (2, 16, 16))
    sizes = jnp.asarray([8 * n, 8 * n], jnp.int32)
    return (lambda x, w: gmm(x, w, sizes, 8, 16, True)), (x, w)


def _gmm_live(n):
    from orion_tpu.ops.pallas import gmm as m

    x, w = _normal(0, (16 * n, 16)), _normal(1, (2, 16, 16))
    sizes = jnp.asarray([8 * n, 8], jnp.int32)
    return (lambda x, w: m.gmm_live(x, w, sizes, 8, 16, True)), (x, w)


def _qkv(n, t=32, d=8):
    return tuple(_normal(i, (1, n, t, d)) for i in range(3))


def _causal_dot(n):
    from orion_tpu.ops.pallas.causal_dot import causal_dot_product_pallas

    return (lambda *a: causal_dot_product_pallas(*a, chunk=16, interpret=True)), _qkv(n)


def _fused(n):
    from orion_tpu.ops.pallas.causal_dot import linear_attention_pallas_fused

    q, k, v = _qkv(n)
    args = (jnp.exp(q), jnp.exp(k), v)
    return (lambda *a: linear_attention_pallas_fused(*a, chunk=16, interpret=True)), args


def _decayed(n):
    from orion_tpu.ops.pallas import causal_dot as m

    slopes = jnp.linspace(0.1, 0.3, n)
    return (lambda *a: m.decayed_causal_dot_pallas(*a, slopes, chunk=16, interpret=True)), _qkv(n)


def _flash(n):
    from orion_tpu.ops.pallas.flash_attention import flash_attention

    return (lambda *a: flash_attention(
        *a, causal=True, block_q=16, block_k=16, interpret=True)), _qkv(n)


def _delta_inputs(n, t=128, dk=16, dv=16):
    q, k = (_draw(i, (1, n, t, dk)) for i in range(2))
    unit = lambda x: jnp.asarray(x / np.linalg.norm(x, axis=-1, keepdims=True))  # noqa: E731
    beta, g = _sigmoid(3, (1, n, t)), -0.1 * _softplus(4, (1, n, t))
    return unit(q), unit(k), _normal(2, (1, n, t, dv)), beta, g


def _delta(n):
    from orion_tpu.ops.pallas.gated_delta import gated_delta_rule_pallas

    return (lambda *a: gated_delta_rule_pallas(*a, interpret=True)), _delta_inputs(n)


def _delta_state(n):
    from orion_tpu.ops.pallas.gated_delta import gated_delta_rule_pallas

    return (lambda *a: gated_delta_rule_pallas(
        *a, interpret=True, return_state=True)), _delta_inputs(n)


def _delta_qkv(n):
    from orion_tpu.ops.pallas.gated_delta import gated_delta_qkv_pallas

    hk, hv, dk, dv, t = n, 2 * n, 16, 16, 128
    qkv = _normal(0, (1, t, 2 * hk * dk + hv * dv))
    beta, g = _sigmoid(1, (1, hv, t)), -0.1 * _softplus(2, (1, hv, t))
    return (lambda *a: gated_delta_qkv_pallas(
        *a, key_heads=hk, key_dim=dk, value_dim=dv, eps=1e-6, interpret=True)), (qkv, beta, g)


def _gated_norm(n):
    from orion_tpu.ops.pallas.gated_norm import gated_rms_norm_pallas

    o, z, w = _normal(0, (1, n, 16, 128)), _normal(1, (1, 16, n * 128)), _normal(2, (128,))
    return (lambda *a: gated_rms_norm_pallas(*a, eps=1e-6, interpret=True)), (o, z, w)


def _short_conv(n):
    from orion_tpu.ops.pallas.short_conv import HALO, causal_short_conv_pallas

    x, w = _normal(0, (1, n * HALO, 128)), _normal(1, (4, 128))
    return (lambda *a: causal_short_conv_pallas(*a, interpret=True)), (x, w)


def _state_inputs(n, h=2, dk=16, dv=16):
    b = 2 + n
    q, k, v = _normal(0, (b, h, dk)), _normal(1, (b, h, dk)), _normal(2, (b, h, dv))
    return b, q, k, v, _normal(3, (b, h, dk, dv))


def _state_step(n):
    from orion_tpu.ops.pallas import decode_state as m

    b, q, k, v, s = _state_inputs(n)
    z, kc, vc = _normal(4, (b, 2, 16)), _normal(5, (b, 4, 2, 16)), _normal(6, (b, 4, 2, 16))
    j = jnp.arange(b, dtype=jnp.int32) % 4
    return (lambda q, k, v, s, z, kc, vc: m.decode_state_step(
        q, k, v, (s, z), (kc, vc), j, _live(b), interpret=True)), (q, k, v, s, z, kc, vc)


def _state_flush(n):
    from orion_tpu.ops.pallas import decode_state as m

    b, _, _, _, s = _state_inputs(n)
    z, kc, vc = _normal(4, (b, 2, 16)), _normal(5, (b, 4, 2, 16)), _normal(6, (b, 4, 2, 16))
    return (lambda s, z, kc, vc: m.decode_state_flush(
        (s, z), (kc, vc), _live(b), interpret=True)), (s, z, kc, vc)


def _delta_step(n):
    from orion_tpu.ops.pallas import decode_state as m

    b, q, k, v, s = _state_inputs(n)
    beta, g = _sigmoid(4, (b, 2)), -_softplus(5, (b, 2))
    return (lambda *a: m.gated_delta_step(*a, _live(b), interpret=True)), (q, k, v, beta, g, s)


def _decay_step(n):
    from orion_tpu.ops.pallas import decode_state as m

    b, q, k, v, s = _state_inputs(n)
    slopes = jnp.asarray([0.1, 0.2])
    return (lambda *a: m.decay_state_step(*a, slopes, _live(b), interpret=True)), (q, k, v, s)


def _cache_attention(n):
    from orion_tpu.ops.pallas import cache_attention as m

    b, h, cap, d = 2 + n, 2, 32, 16
    q, k, v = _normal(0, (b, h, d)), _normal(1, (b, h, cap, d)), _normal(2, (b, h, cap, d))
    lengths = jnp.arange(b, dtype=jnp.int32) * 7 + 3
    return (lambda *a: m.cache_attention(*a, lengths, _live(b), interpret=True)), (q, k, v)


def _ring_row_write(n):
    from orion_tpu.ops.pallas import cache_attention as m

    b, h, cap, d = 2 + n, 2, 32, 16
    k, v = _normal(1, (b, h, cap, d)), _normal(2, (b, h, cap, d))
    kn, vn = _normal(3, (b, h, d)), _normal(4, (b, h, d))
    slots = jnp.arange(b, dtype=jnp.int32) * 7 + 3
    return (lambda *a: m.ring_row_write(*a, slots, _live(b), interpret=True)), (k, v, kn, vn)


def _block_attention(n):
    from orion_tpu.ops.pallas import cache_attention as m

    b, kvh, g, cap, d = 2 + n, 2, 2, 64, 16
    q, k, v = _normal(0, (b, kvh, g, d)), _normal(1, (b, kvh, cap, d)), _normal(2, (b, kvh, cap, d))
    lengths = jnp.full((b,), 50, jnp.int32)
    lists = jnp.tile(jnp.asarray([0, 2, 3], jnp.int32), (b, kvh, 1))
    counts = jnp.full((b, kvh), 2, jnp.int32)
    return (lambda *a: m.block_attention(
        *a, lengths, lists, counts, _live(b), block=16, interpret=True)), (q, k, v)


def _latent_attention(n):
    from orion_tpu.ops.pallas import cache_attention as m

    b, h, cap, r, dr = 2 + n, 2, 32, 16, 8
    qt, qr = _normal(0, (b, h, r)), _normal(1, (b, h, dr))
    c, kr = _normal(2, (b, cap, r)), _normal(3, (b, cap, dr))
    lengths = jnp.arange(b, dtype=jnp.int32) * 7 + 3
    return (lambda *a: m.latent_attention(
        *a, lengths, _live(b), scale=0.2, interpret=True)), (qt, qr, c, kr)


def _ssm_step(n):
    from orion_tpu.ops import ssm as ssm_ops
    from orion_tpu.ops.pallas import ssm as m

    b, h, p, g, st = 2 + n, 8, 8, 2, 16
    x, dt = _normal(0, (b, h, p)), _softplus(1, (b, h))
    a, bm, cm = -_softplus(2, (h,)), _normal(3, (b, g, st)), _normal(4, (b, g, st))
    pack = ssm_ops.state_pack(h, p, g)
    s = ssm_ops.pack_state(_normal(5, (b, h, p, st)), pack)
    return (lambda *a: m.ssm_state_step(*a, pack, _live(b), interpret=True)), (x, dt, a, bm, cm, s)


def _index_scores(n):
    from orion_tpu.ops.pallas import indexed_attention as m

    qi, w, ki = _normal(0, (n, 16, 2, 8)), _normal(1, (n, 16, 2)), _normal(2, (n, 128, 8))
    return (lambda *a: m.index_scores(*a, interpret=True)), (qi, w, ki)


def _masked_attention(n):
    from orion_tpu.ops.pallas import indexed_attention as m

    q = _normal(0, (n, 2, 2, 16, 16))
    k, v = _normal(1, (n, 128, 32)), _normal(2, (n, 128, 32))
    keep = jnp.asarray(_draw(3, (n, 16, 128)) > 0.5, jnp.int8)
    return (lambda q, k, v: m.masked_attention(q, k, v, keep, interpret=True)), (q, k, v)


def _piece_attention(n):
    from orion_tpu.ops.pallas import piece_attention as m

    q = _normal(0, (n, 2, 2, 16, 16))
    k, v = _normal(1, (n, 2, 48, 16)), _normal(2, (n, 2, 48, 16))
    return (lambda q, k, v: m.piece_attention(
        q, k, v, 32, 5, 44, window=32, interpret=True)), (q, k, v)


def _moe_rows(n):
    from orion_tpu.ops.pallas import moe_rows as m

    tokens, k, rows = 8 * n, 2, 12 * n
    j = np.arange(rows)
    at_row = np.where(j % 3 == 2, -1, j * 5 % (tokens * k))  # the pair a row holds
    idx = jnp.asarray(np.where(at_row >= 0, at_row // k, -1), jnp.int32)
    held, row = np.zeros(tokens * k, bool), np.zeros(tokens * k, np.int32)
    held[at_row[at_row >= 0]], row[at_row[at_row >= 0]] = True, j[at_row >= 0]
    gates = _sigmoid(1, (rows,))

    def fn(x, gate):
        pairs = jnp.where(held, gate[row], 0.0)  # the gates from the pairs' side
        lists = m.combine_lists(jnp.asarray(held), jnp.asarray(row), pairs, tokens)
        ys = jnp.tanh(m.gather_rows(x, idx, lists, interpret=True))
        return m.combine_rows(ys, gate, idx, lists, interpret=True)

    return fn, (_normal(0, (tokens, 16)), gates)


# (module, entry) -> the driver that reaches it
ENTRY_DRIVER = {
    ("gmm", "_gmm_call"): _gmm,
    ("gmm", "_dw_call"): _gmm,
    ("gmm", "gmm_live"): _gmm_live,
    ("causal_dot", "_cdp_flat"): _causal_dot,
    ("causal_dot", "_cdp_rev_flat"): _causal_dot,
    ("causal_dot", "_cdpn_flat"): _fused,
    ("causal_dot", "_cdp_dq_den_flat"): _fused,
    ("causal_dot", "_cdp_rev_den_flat"): _fused,
    ("causal_dot", "decayed_causal_dot_pallas"): _decayed,
    ("flash_attention", "_flash_fwd_flat"): _flash,
    ("flash_attention", "_flash_bwd_flat"): _flash,
    ("gated_delta", "_forward"): _delta,
    ("gated_delta", "_backward"): _delta,
    ("gated_delta", "_forward_state"): _delta_state,
    ("gated_delta", "_forward_qkv"): _delta_qkv,
    ("gated_delta", "_backward_qkv"): _delta_qkv,
    ("gated_norm", "_forward"): _gated_norm,
    ("gated_norm", "_backward"): _gated_norm,
    ("short_conv", "_forward"): _short_conv,
    ("short_conv", "_backward"): _short_conv,
    ("decode_state", "decode_state_step"): _state_step,
    ("decode_state", "decode_state_flush"): _state_flush,
    ("decode_state", "gated_delta_step"): _delta_step,
    ("decode_state", "decay_state_step"): _decay_step,
    ("cache_attention", "cache_attention"): _cache_attention,
    ("cache_attention", "block_attention"): _block_attention,
    ("cache_attention", "ring_row_write"): _ring_row_write,
    ("cache_attention", "latent_attention"): _latent_attention,
    ("ssm", "ssm_state_step"): _ssm_step,
    ("indexed_attention", "index_scores"): _index_scores,
    ("indexed_attention", "masked_attention"): _masked_attention,
    ("piece_attention", "piece_attention"): _piece_attention,
    ("moe_rows", "moe_rows_gather"): _moe_rows,
    ("moe_rows", "moe_rows_combine"): _moe_rows,
    ("moe_rows", "pack_rows"): _moe_rows,
}


# an entry is named after the ONE ``pallas_call`` it holds, but for this one
CALLS_HELD = {"flash_attn_bwd": ["flash_attn_dq", "flash_attn_dkv"]}

# the drivers whose function has a custom_vjp: their gradient is taken too
DIFFERENTIABLE = {_gmm, _causal_dot, _fused, _flash, _delta, _delta_qkv, _gated_norm, _short_conv,
                  _moe_rows}


def _loss(fn):
    def loss(*args):
        out = jax.tree.leaves(fn(*args))
        return sum(jnp.sum(jnp.cos(jnp.arange(o.size)).reshape(o.shape) * o) for o in out)

    return loss


class _Unjitted:
    """Every entry of ``module`` as written (``__wrapped__``); ``seen`` keeps
    the arguments of each one's first call."""

    def __init__(self, module):
        self.module, self.seen = module, {}
        self.saved = _entries(module)

    def __enter__(self):
        for name, entry in self.saved.items():
            setattr(self.module, name, self._plain(name, entry.__wrapped__))
        return self

    def __exit__(self, *exc):
        for name, entry in self.saved.items():
            setattr(self.module, name, entry)

    def _plain(self, name, build):
        @functools.wraps(build)
        def plain(*args, **kwargs):
            self.seen.setdefault(name, (args, kwargs))
            return build(*args, **kwargs)

        return plain


@functools.lru_cache(maxsize=None)
def _captured(module_name, driver, size):
    """entry -> the ``(args, kwargs)`` the driver's function (and its
    gradient) first hands it, run eagerly on the entries as written."""
    fn, args = driver(size)
    with _Unjitted(_module(module_name)) as plain:
        (jax.grad(_loss(fn), argnums=0) if driver in DIFFERENTIABLE else fn)(*args)
    return plain.seen


@functools.lru_cache(maxsize=None)
def _driven(module_name, driver):
    """What the driver's function gives under ``jax.jit`` with the module's
    entries jitted and as written: (value, gradients). At a third size: an
    entry's own case counts its traces at the first two (nothing else in
    this file calls an entry jitted, and ``conftest.py`` clears jax's caches
    between files)."""
    fn, args = driver(3)
    run = lambda: jax.jit(  # noqa: E731
        jax.value_and_grad(_loss(fn), argnums=tuple(range(len(args))))
        if driver in DIFFERENTIABLE else fn)(*args)
    kept = run()
    with _Unjitted(_module(module_name)):
        plain = run()
    return kept, plain


def _of_arrays(fn, seen):
    """``fn`` on captured ``(args, kwargs)`` as a function of their arrays
    alone, and those arrays."""
    leaves, tree = jax.tree.flatten(seen)
    at = [i for i, leaf in enumerate(leaves) if isinstance(leaf, jax.Array)]

    def run(*arrays):
        full = list(leaves)
        for i, x in zip(at, arrays):
            full[i] = x
        args, kwargs = jax.tree.unflatten(tree, full)
        return fn(*args, **kwargs)

    return run, [leaves[i] for i in at]


def _kernel_events():
    return [e for e in setup_record() if e["name"] == "compile.kernel"]


def _counts():
    totals = compile_totals()
    return np.asarray([totals["kernel_bodies_traced"], totals["kernel_call_sites"]])


def test_every_entry_has_a_case():
    found = {(m, n) for m in MODULES for n in _entries(_module(m))}
    assert found == set(ENTRY_DRIVER)


@pytest.mark.parametrize(
    "module_name,entry_name", sorted(ENTRY_DRIVER), ids=[".".join(k) for k in sorted(ENTRY_DRIVER)])
def test_entry_is_traced_once_and_is_the_unjitted_program(module_name, entry_name):
    driver = ENTRY_DRIVER[(module_name, entry_name)]
    entry = getattr(_module(module_name), entry_name)
    first, second = (_captured(module_name, driver, size)[entry_name] for size in (1, 2))

    # the record is a ring (the newest 8,192 compile events of the process): once a
    # worker has filled it an index into it moves, so mark the newest event itself
    start, newest = _counts(), (_kernel_events() or [None])[-1]
    run, arrays = _of_arrays(entry, first)
    twice = jax.make_jaxpr(lambda *x: (run(*x), run(*x)))(*arrays)
    np.testing.assert_array_equal(_counts() - start, [1, 2])
    other, other_arrays = _of_arrays(entry, second)
    jax.make_jaxpr(other)(*other_arrays)  # another shape: traced again
    np.testing.assert_array_equal(_counts() - start, [2, 3])
    kept = jax.make_jaxpr(run)(*arrays)  # and the first is still kept
    np.testing.assert_array_equal(_counts() - start, [2, 4])
    events = _kernel_events()
    new = events[next((i for i, e in enumerate(events) if e is newest), -1) + 1:]
    assert [(e["args"]["fun_name"], e["args"]["source"]) for e in new] == [
        (entry.kernel_name, "traced")] * 2
    calls = [e for e in twice.jaxpr.eqns if e.primitive.name == "pallas_call"]
    held = CALLS_HELD.get(entry.kernel_name, [entry.kernel_name])
    assert [e.params["name"] for e in calls] == held * 2
    for a, b in zip(calls, calls[len(held):]):
        assert a.params["jaxpr"] is b.params["jaxpr"]  # ONE traced body a call

    # the program is the un-jitted entry's, and so are its bits
    written, _ = _of_arrays(entry.__wrapped__, first)
    assert str(kept) == str(jax.make_jaxpr(written)(*arrays))
    jax.tree.map(np.testing.assert_array_equal, jax.jit(run)(*arrays), jax.jit(written)(*arrays))
    jax.tree.map(np.testing.assert_array_equal, *_driven(module_name, driver))


def _builders(tree):
    """Functions of a module whose own body holds a ``pallas_call``."""
    return {
        f.name: f for f in ast.walk(tree) if isinstance(f, ast.FunctionDef)
        and any(isinstance(n, ast.Attribute) and n.attr == "pallas_call" for n in ast.walk(f))
    }


def _is_entry(fn):
    return any(
        isinstance(d, ast.Call) and getattr(d.func, "id", "") == "kernel_entry"
        for d in fn.decorator_list)


@pytest.mark.parametrize("module_name", MODULES)
def test_no_pallas_call_outside_the_idiom(module_name):
    """A function that builds a ``pl.pallas_call`` is a ``kernel_entry``, or
    is called by entries alone (``gated_delta._forward_call``, handed block
    specs); and the idiom is one: no other ``jax.jit`` in the package."""
    tree = ast.parse((PALLAS / f"{module_name}.py").read_text())
    builders = _builders(tree)
    assert builders
    for name, fn in builders.items():
        if _is_entry(fn):
            continue
        callers = [
            f for f in ast.walk(tree) if isinstance(f, ast.FunctionDef) and f is not fn
            and any(isinstance(n, ast.Name) and n.id == name for n in ast.walk(f))
        ]
        assert callers and all(_is_entry(f) for f in callers), (module_name, name)
    jits = [n for n in ast.walk(tree) if isinstance(n, ast.Attribute) and n.attr == "jit"]
    assert not jits, module_name


def test_a_four_layer_delta_rule_step_traces_each_body_once():
    """``qwen3_next_80b``'s block at tiny widths, four layers (delta rule x3
    : gated softmax x1, experts in every layer), the whole train step: the
    kernels' bodies are traced once each, whatever layer, pass or
    ``jax.checkpoint`` calls them, and a second trace of the step (another
    ``Trainer``) traces none."""
    from orion_tpu.analysis.jaxpr_audit import iter_eqns
    from orion_tpu.models.configs import get_config
    from orion_tpu.parallel.mesh import MeshConfig
    from orion_tpu.training.trainer import TrainConfig, Trainer

    model = dataclasses.replace(
        get_config("qwen3_next_80b"), backend="pallas_interpret", remat_skip=0,
        d_model=128, n_heads=4, n_kv_heads=2, head_dim=32, rotary_dims=8, gdn_key_heads=2,
        gdn_value_heads=4, gdn_key_dim=32, gdn_value_dim=128, mlp_hidden=64,
        moe_shared_hidden=64, n_experts=8, moe_router_width=16, moe_top_k=2, vocab_size=256,
        dtype="float32", max_seq_len=128,
    )
    assert model.n_layers == 4
    cfg = TrainConfig(model=model, batch_size=2, seq_len=128, optimizer="adafactor",
                      mesh=MeshConfig(dp=1))

    def trace():
        trainer = Trainer(cfg, materialize=False)  # its init traces at batch 1: not counted
        batch = jax.ShapeDtypeStruct((2, 129), np.int32, sharding=trainer.batch_shd)
        start = _counts()
        jaxpr = trainer._step_fn.trace(trainer.abstract_state(), batch).jaxpr
        return jaxpr, _counts() - start

    jax.clear_caches()
    jaxpr, (bodies, sites) = trace()
    calls = [e for e in iter_eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    names = {e.params["name"] for e in calls}
    assert {"short_conv_fwd", "gated_delta_bwd", "gated_norm_bwd", "flash_attn_dkv"} <= names
    assert bodies and sites >= 3 * bodies, (bodies, sites)
    assert len(calls) >= 3 * len({id(e.params["jaxpr"]) for e in calls})
    by_shape = {}
    for e in calls:
        key = (e.params["name"], tuple(str(v.aval) for v in e.invars))
        by_shape.setdefault(key, set()).add(id(e.params["jaxpr"]))
    assert all(len(ids) == 1 for ids in by_shape.values()), by_shape
    np.testing.assert_array_equal(trace()[1], [0, sites])  # another Trainer's step: none


@pytest.mark.parametrize("layers", [1, 4])
def test_an_expert_stack_traces_the_row_movers_once_a_shape(layers):
    """Held-expert layers under ``jax.checkpoint`` and ``jax.grad`` (the tiled
    Mosaic product's training form, 2,048 pairs a layer): the forward, the
    recompute and the backward of every layer call ``moe_rows_gather``,
    ``moe_rows_combine`` and ``moe_rows_pack`` (a source laid out a row a
    tile: the tokens' side and the buffer's), and their bodies are traced once
    a distinct (entry, shapes and dtypes), four in all whatever the number of
    layers: the backward meets the forward's shapes. This is what a row mover
    may cost a program's set-up (PERF.md section 6, PR 52)."""
    from orion_tpu.analysis.jaxpr_audit import iter_eqns
    from orion_tpu.models.configs import ModelConfig
    from orion_tpu.models.moe import MoEMLP

    cfg = ModelConfig(
        name="t", d_model=32, n_experts=2, moe_router_width=8, moe_top_k=2, moe_hidden=16,
        moe_dropless=True, moe_ep_buffer=4.0, dtype="float32", backend="pallas_interpret",
    )
    assert cfg.moe_held
    layer = MoEMLP(cfg)
    x = _normal(0, (2, 512, 32))
    params = [layer.init(jax.random.key(i), x[:, :16])["params"] for i in range(layers)]

    @jax.checkpoint
    def block(p, x):
        return x + layer.apply({"params": p}, x, mutable=["losses", "moe_stats"])[0]

    def loss(params, x):
        for p in params:
            x = block(p, x)
        return jnp.sum(x * x)

    jax.clear_caches()
    start = _counts()
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params, x)
    bodies, sites = _counts() - start
    calls = [e for e in iter_eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"
             and e.params["name"].startswith("moe_rows_")]
    assert {e.params["name"] for e in calls} == {
        "moe_rows_gather", "moe_rows_combine", "moe_rows_pack"}
    assert len(calls) >= 8 * layers  # a pack a mover: forward 2, recompute 1 or 2, backward 1 or 2
    by_shape = {}
    for e in calls:
        key = (e.params["name"], tuple(str(v.aval) for v in e.invars))
        by_shape.setdefault(key, set()).add(id(e.params["jaxpr"]))
    assert all(len(ids) == 1 for ids in by_shape.values()), by_shape
    assert len(by_shape) == 4, sorted(by_shape)
    traced = [e["args"]["fun_name"] for e in _kernel_events()]
    rows = [n for n in traced[len(traced) - bodies:] if n.startswith("moe_rows_")]
    assert sorted(rows) == ["moe_rows_combine", "moe_rows_gather"] + ["moe_rows_pack"] * 2
    assert sites >= 4 * bodies or layers == 1, (bodies, sites)
