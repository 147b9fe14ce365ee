"""Tier C (part 1): SPMD collective auditor — trace, never execute.

Extends the Tier B trace-don't-execute approach to the sharded programs:
each target in :data:`SPMD_TARGETS` is traced with ``jax.make_jaxpr`` under
an abstract multi-device mesh (8 virtual CPU devices — the same mesh the
distributed tests run on; nothing executes, no weights materialize), every
communication collective in the jaxpr is extracted with its payload
dtype/bytes and loop scope, and the extraction is checked against the
budget the ``parallel/`` layer declares next to the code
(parallel/budgets.py). Check ids:

- ``spmd-unbudgeted-collective`` — a collective primitive the step's
  budget doesn't mention at all (e.g. a stray psum added to a shard_map
  body, or a manual collective leaking into the GSPMD-only train step).
- ``spmd-collective-count``      — more occurrences of a budgeted
  primitive than declared (a third ppermute per ring step doubles the
  critical-path ICI time without failing any CPU test).
- ``spmd-collective-dtype``     — payload dtype outside the declared set
  (an accidental f32 ring payload doubles ICI bytes silently).
- ``spmd-collective-in-scan``   — a collective the budget marks
  ``hoistable`` found inside a ``scan``/``while`` body, where it runs per
  step instead of once (e.g. the sp state all_gather accidentally pulled
  into a chunk loop).

Like Tier B, trace failures surface as ``audit-error`` findings, never
crashes. The extraction helpers take explicit jaxprs so tests can feed
deliberately-broken toys and doctored budgets.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, List, Optional, Tuple

from orion_tpu.analysis.findings import Finding
from orion_tpu.analysis.jaxpr_audit import AUDIT_ERROR, _where

RULE_UNBUDGETED = "spmd-unbudgeted-collective"
RULE_COUNT = "spmd-collective-count"
RULE_DTYPE = "spmd-collective-dtype"
RULE_IN_SCAN = "spmd-collective-in-scan"

ALL_SPMD_CHECKS = (RULE_UNBUDGETED, RULE_COUNT, RULE_DTYPE, RULE_IN_SCAN)

# the cross-device COMMUNICATION primitives (what budgets ration); unlike
# Tier B's COLLECTIVE_PRIMS this deliberately excludes axis_index — it
# moves no bytes
COMM_PRIMS = frozenset({
    "psum", "psum2", "all_gather", "all_to_all", "ppermute",
    "reduce_scatter", "psum_scatter", "pmax", "pmin", "pmean", "pgather",
    "pbroadcast",
})

_LOOP_PRIMS = frozenset({"scan", "while"})

N_VIRTUAL_DEVICES = 8


def ensure_cpu_devices(n: int = N_VIRTUAL_DEVICES) -> Optional[str]:
    """Make sure jax runs on >= n virtual CPU devices (the abstract mesh
    the audits trace under). Configures jax if its backends are not yet
    initialized (the CLI path — mirrors orion_tpu/aot.py); returns an
    error string (for an audit-error finding) if the process already
    initialized an unsuitable backend."""
    import jax

    try:
        # jax refuses this update once a backend is live: that is the
        # "already initialized" case, judged by the check below
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:
        pass
    else:
        jax.config.update("jax_platforms", "cpu")
    # golden snapshots (analysis/snapshots.py) hash the compiled program;
    # partitionable threefry is what the test mesh uses — pin it so the
    # CLI and pytest produce byte-identical artifacts
    jax.config.update("jax_threefry_partitionable", True)
    if jax.default_backend() != "cpu" or jax.device_count() < n:
        return (
            f"spmd audit needs >= {n} virtual cpu devices but jax is "
            f"already initialized with {jax.device_count()} "
            f"{jax.default_backend()} device(s); run under "
            f"JAX_PLATFORMS=cpu with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n}"
        )
    return None


# -- extraction ---------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CollectiveSite:
    prim: str
    dtypes: Tuple[str, ...]  # distinct dtypes over EVERY operand — a psum
    # of a (bf16, f32) tuple binds one eqn with two invars, and the f32
    # payload must not hide behind the first operand
    payload_bytes: int
    in_loop: bool  # lexically inside a scan/while body
    path: str
    line: int


def iter_eqns_scoped(jaxpr, in_loop: bool = False) -> Iterator[Tuple[Any, bool]]:
    """Every eqn with a flag for "inside a scan/while body", recursing into
    sub-jaxprs carried in eqn params (pjit/scan/cond/shard_map bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        inner_loop = in_loop or eqn.primitive.name in _LOOP_PRIMS
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:  # ClosedJaxpr
                    yield from iter_eqns_scoped(inner, inner_loop)
                elif hasattr(sub, "eqns"):  # raw Jaxpr
                    yield from iter_eqns_scoped(sub, inner_loop)


def _aval_bytes(aval) -> int:
    import numpy as np

    try:
        n = int(np.prod(aval.shape)) if aval.shape else 1
        return n * aval.dtype.itemsize
    except Exception:
        return 0


def extract_collectives(closed_jaxpr, target: str) -> List[CollectiveSite]:
    sites = []
    for eqn, in_loop in iter_eqns_scoped(closed_jaxpr.jaxpr):
        if eqn.primitive.name not in COMM_PRIMS:
            continue
        avals = [getattr(v, "aval", None) for v in eqn.invars]
        avals = [a for a in avals if a is not None]
        dtypes = tuple(sorted({str(a.dtype) for a in avals})) or ("?",)
        path, line = _where(eqn, target)
        sites.append(CollectiveSite(
            prim=eqn.primitive.name,
            dtypes=dtypes,
            payload_bytes=sum(_aval_bytes(a) for a in avals),
            in_loop=in_loop,
            path=path,
            line=line,
        ))
    return sites


# -- budget check -------------------------------------------------------------


def check_budget(
    sites: List[CollectiveSite], budget, target: str
) -> List[Finding]:
    """Check extracted collectives against a parallel/budgets.py
    ``StepBudget``. Pure — tests feed toy sites and doctored budgets."""
    findings: List[Finding] = []
    by_prim: Dict[str, List[CollectiveSite]] = {}
    for s in sites:
        by_prim.setdefault(s.prim, []).append(s)

    for prim, group in sorted(by_prim.items()):
        allow = budget.entry_for(prim)
        first = group[0]
        if allow is None:
            findings.append(Finding(
                RULE_UNBUDGETED, first.path, first.line,
                f"`{prim}` x{len(group)} in the {target} jaxpr but the "
                f"step's budget (parallel/budgets.py::BUDGETS[{target!r}]) "
                "declares no such collective — declare it (count/dtype/"
                "scope, with the cost reviewed) or remove it",
            ))
            continue
        if len(group) > allow.max_count:
            findings.append(Finding(
                RULE_COUNT, first.path, first.line,
                f"`{prim}` x{len(group)} in the {target} jaxpr exceeds the "
                f"budgeted {allow.max_count} — every extra occurrence is "
                "per-call ICI time; raise the budget only with the cost "
                "reviewed",
            ))
        for s in group:
            bad = [d for d in s.dtypes if d not in allow.dtypes]
            if bad:
                findings.append(Finding(
                    RULE_DTYPE, s.path, s.line,
                    f"`{prim}` payload dtype {'/'.join(bad)} "
                    f"({s.payload_bytes} B total) in the {target} jaxpr; "
                    f"budget allows {'/'.join(allow.dtypes)} — a wider "
                    "payload moves more ICI bytes with no parity-test "
                    "signal",
                ))
            if s.in_loop and allow.hoistable:
                findings.append(Finding(
                    RULE_IN_SCAN, s.path, s.line,
                    f"`{prim}` inside a scan/while body of the {target} "
                    "jaxpr but the budget marks it hoistable — inside the "
                    "loop it runs per step instead of once; hoist it out",
                ))
    return findings


# -- repo targets -------------------------------------------------------------


def _attn_inputs(dtype="bfloat16", b=2, h=2, t=64, d=8):
    import jax
    import jax.numpy as jnp

    sds = jax.ShapeDtypeStruct((b, h, t, d), jnp.dtype(dtype))
    return sds, sds, sds


def _sp_mesh(sp=4):
    from orion_tpu.parallel.mesh import MeshConfig, make_mesh

    return make_mesh(MeshConfig(dp=1, sp=sp))


def tiny_dp8_trainer():
    """ONE tiny bf16 dp=8 trainer + abstract batch shared by the budget
    audit (trace_train_step_dp) and the golden snapshot
    (snapshots._snap_train_tiny_dp8) — both must always describe the SAME
    compiled program."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp

    from orion_tpu.models.configs import get_config
    from orion_tpu.parallel.mesh import MeshConfig, make_mesh
    from orion_tpu.training.trainer import TrainConfig, Trainer

    cfg = TrainConfig(
        model=dc.replace(get_config("tiny"), dtype="bfloat16"),
        batch_size=8, seq_len=32, steps=10,
        mesh=MeshConfig(dp=N_VIRTUAL_DEVICES),
    )
    tr = Trainer(cfg, mesh=make_mesh(cfg.mesh), materialize=False)
    batch = jax.ShapeDtypeStruct(
        (cfg.batch_size, cfg.seq_len + 1), jnp.int32, sharding=tr.batch_shd
    )
    return tr, batch


def trace_train_step_dp():
    """The data-parallel train step under an explicit dp=8 mesh — the
    GSPMD path whose jaxpr must stay collective-free (jit inserts all
    communication from the shardings after tracing)."""
    import jax

    tr, batch = tiny_dp8_trainer()
    return jax.make_jaxpr(tr._train_step)(tr._abstract, batch)


def trace_sp_linear_attention():
    import jax

    from orion_tpu.parallel.sequence import sp_linear_attention

    mesh = _sp_mesh()
    q, k, v = _attn_inputs()
    return jax.make_jaxpr(
        lambda q, k, v: sp_linear_attention(q, k, v, mesh, backend="xla")
    )(q, k, v)


def _trace_ring(**kwargs):
    import jax

    from orion_tpu.parallel.ring import ring_attention

    mesh = _sp_mesh()
    q, k, v = _attn_inputs()
    return jax.make_jaxpr(
        lambda q, k, v: ring_attention(q, k, v, mesh, **kwargs)
    )(q, k, v)


def trace_ring_causal():
    return _trace_ring(causal=True)


def trace_ring_window():
    return _trace_ring(causal=True, window=16)


def trace_ring_striped():
    return _trace_ring(causal=True, striped=True)


def trace_swa_halo():
    """The halo form needs the flash kernel; interpret mode keeps the trace
    CPU-legal while the ppermute structure is identical to the real path."""
    import jax

    from orion_tpu.parallel.ring import swa_halo_attention

    mesh = _sp_mesh()
    q, k, v = _attn_inputs()
    return jax.make_jaxpr(
        lambda q, k, v: swa_halo_attention(
            q, k, v, mesh, window=24, backend="pallas_interpret"
        )
    )(q, k, v)


def tp_decode_pieces(tp: int = 2, slots: int = 8):
    """Shared fixtures for the tp decode traces AND the golden snapshots
    (snapshots._snap_decode_batched_tp): tiny model, tp=N mesh over the
    first N virtual devices, tp-sharded abstract params (the training
    rules), head-sharded abstract state, replicated per-slot vectors —
    budget audit and snapshot must always describe the SAME program, the
    one ``SlotEngine(mesh=...)`` serves. Returns
    (model, params, carry, rngs, vec, shardings) where ``shardings`` is
    the (param, state) NamedSharding pair for per-device accounting."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM, init_decode_state
    from orion_tpu.parallel.decode import (
        decode_param_shardings,
        decode_state_shardings,
        serving_mesh,
    )

    cfg = get_config("tiny")
    model = TransformerLM(cfg)
    mesh = serving_mesh(tp)
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh, P())
    sds = lambda l, s: jax.ShapeDtypeStruct(  # noqa: E731
        l.shape, l.dtype, sharding=s
    )
    prompt = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0), prompt)
    p_shd = decode_param_shardings(abstract, mesh)
    params = jax.tree.map(sds, abstract, p_shd)
    states_abs = jax.eval_shape(lambda: init_decode_state(cfg, slots))
    st_shd = decode_state_shardings(states_abs, mesh)
    states = jax.tree.map(sds, states_abs, st_shd)
    vec = lambda dt: jax.ShapeDtypeStruct(  # noqa: E731
        (slots,), dt, sharding=rep
    )
    carry = (
        vec(jnp.int32), states, vec(jnp.int32), vec(jnp.int32),
        vec(jnp.bool_),
    )
    rngs = jax.ShapeDtypeStruct((slots, 2), jnp.uint32, sharding=rep)
    shardings = ((abstract, p_shd), (states_abs, st_shd), mesh)
    return model, params, carry, rngs, vec, shardings


def trace_decode_batched_tp():
    """The tp=2 slot-multiplexed decode chunk: like the GSPMD train step,
    the traced jaxpr must be collective-FREE (jit inserts the two
    per-block all-reduces from the shardings after tracing) — an
    explicit collective inside the decode scan would run per token."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.generate import SampleConfig, _decode_batched_chunk_jit

    model, params, carry, rngs, vec, _ = tp_decode_pieces()
    return jax.make_jaxpr(
        _decode_batched_chunk_jit, static_argnums=(0, 5, 6)
    )(model, params, carry, rngs, vec(jnp.bool_), 8, SampleConfig())


def trace_decode_batched_prefill_tp():
    """The tp=2 unified in-scan prefill + decode program: staging and
    prompt pieces must stay jaxpr-collective-free too."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.generate import (
        SampleConfig,
        _decode_batched_prefill_chunk_jit,
    )

    model, params, carry, rngs, vec, shardings = tp_decode_pieces()
    from jax.sharding import NamedSharding, PartitionSpec as P

    pbuf = jax.ShapeDtypeStruct(
        (8, 16), jnp.int32, sharding=NamedSharding(shardings[2], P())
    )
    return jax.make_jaxpr(
        _decode_batched_prefill_chunk_jit, static_argnums=(0, 9, 10, 11)
    )(
        model, params, carry, rngs, vec(jnp.bool_), pbuf, vec(jnp.int32),
        vec(jnp.int32), vec(jnp.int32), 8, 16, SampleConfig(),
    )


def trace_pipeline_lm_step():
    """The pp=2 trainer step (fwd+bwd): stage-rotation ppermutes inside the
    GPipe scan plus the loop-invariant psums its transposes generate."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp

    from orion_tpu.models.configs import get_config
    from orion_tpu.parallel.mesh import MeshConfig, make_mesh
    from orion_tpu.training.trainer import TrainConfig, Trainer

    cfg = TrainConfig(
        model=dc.replace(get_config("tiny"), dtype="bfloat16"),
        batch_size=4, seq_len=32, steps=10, mesh=MeshConfig(dp=1, pp=2),
    )
    tr = Trainer(cfg, mesh=make_mesh(cfg.mesh), materialize=False)
    batch = jax.ShapeDtypeStruct((cfg.batch_size, cfg.seq_len + 1), jnp.int32)
    return jax.make_jaxpr(tr._train_step)(tr._abstract, batch)


# trace-target name -> zero-arg tracer; keys must match
# parallel/budgets.py::BUDGETS (tested in tests/test_analysis.py)
SPMD_TARGETS = {
    "train_step_dp": trace_train_step_dp,
    "sp_linear_attention": trace_sp_linear_attention,
    "ring_attention_causal": trace_ring_causal,
    "ring_attention_window": trace_ring_window,
    "ring_attention_striped": trace_ring_striped,
    "swa_halo_attention": trace_swa_halo,
    "pipeline_lm_step": trace_pipeline_lm_step,
    "decode_batched_tp": trace_decode_batched_tp,
    "decode_batched_prefill_tp": trace_decode_batched_prefill_tp,
}


def audit_spmd(budgets: Optional[Dict[str, Any]] = None) -> List[Finding]:
    """Trace every SPMD target and check it against its declared budget.
    ``budgets`` overrides parallel/budgets.py::BUDGETS (tests inject
    doctored budgets to prove violations gate)."""
    err = ensure_cpu_devices()
    if err is not None:
        return [Finding(AUDIT_ERROR, "<spmd>", 0, err)]
    if budgets is None:
        from orion_tpu.parallel.budgets import BUDGETS as budgets

    findings: List[Finding] = []
    for name, tracer in SPMD_TARGETS.items():
        budget = budgets.get(name)
        if budget is None:
            findings.append(Finding(
                AUDIT_ERROR, f"<spmd:{name}>", 0,
                f"no budget declared for SPMD target {name!r} in "
                "parallel/budgets.py::BUDGETS",
            ))
            continue
        try:
            sites = extract_collectives(tracer(), name)
        except Exception as e:  # noqa: BLE001 - surfaced as finding, not crash
            findings.append(Finding(
                AUDIT_ERROR, f"<spmd:{name}>", 0,
                f"tracing {name} failed: {type(e).__name__}: {e}",
            ))
            continue
        findings.extend(check_budget(sites, budget, name))
    return findings


__all__ = [
    "audit_spmd", "check_budget", "extract_collectives", "iter_eqns_scoped",
    "ensure_cpu_devices", "CollectiveSite", "SPMD_TARGETS",
    "ALL_SPMD_CHECKS", "RULE_UNBUDGETED", "RULE_COUNT", "RULE_DTYPE",
    "RULE_IN_SCAN", "N_VIRTUAL_DEVICES",
]
