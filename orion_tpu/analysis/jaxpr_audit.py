"""Tier B: jaxpr contract auditor — trace, never execute.

The runtime invariants the paper's headline claims rest on are properties of
the *traced program*, not of any particular run, so they are asserted on
jaxprs obtained with ``jax.make_jaxpr`` over abstract shapes (no params are
materialized, nothing runs on device):

- ``decode-no-collectives`` — the recurrent decode jaxpr contains no
  collective primitives: the O(1)-state decode path must stay
  communication-free (collectives leaking in via sharding rules would
  serialize every generated token on the slowest link).
- ``decode-o1-state``     — the decode scan's carry is byte-identical when
  the prompt length and the number of generated tokens change: per-token
  state is O(1) in sequence length, the paper's headline claim.
- ``bf16-matmul-policy``  — every ``dot_general`` in the bf16 train step
  consumes bf16 inputs, except matmuls whose source scope is declared in
  ``models/configs.py::F32_MATMUL_SCOPES`` (the fp32 kv-state accumulation
  contract). A silent f32 upcast halves MXU throughput and doubles HBM
  traffic without failing any parity test.
- ``no-host-callback``    — no callback/infeed/outfeed primitives inside the
  jitted step bodies: a host round-trip inside the decode scan or the train
  step serializes the device pipeline.

``audit_repo()`` traces the three contract-bearing entrypoints — the jitted
LM train step, the LRA train step, and the recurrent decode step — and
returns findings; the CLI runs it as tier B. The per-contract functions take
explicit jaxprs so tests can feed deliberately-broken toy functions.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Iterator, List, Optional, Sequence, Tuple

from orion_tpu.analysis.findings import Finding, normalize_path

CONTRACT_DECODE_COLLECTIVES = "decode-no-collectives"
CONTRACT_DECODE_STATE = "decode-o1-state"
CONTRACT_BF16_MATMUL = "bf16-matmul-policy"
CONTRACT_HOST_CALLBACK = "no-host-callback"
AUDIT_ERROR = "audit-error"

ALL_CONTRACTS = (
    CONTRACT_DECODE_COLLECTIVES,
    CONTRACT_DECODE_STATE,
    CONTRACT_BF16_MATMUL,
    CONTRACT_HOST_CALLBACK,
)

COLLECTIVE_PRIMS = frozenset({
    "psum", "psum2", "all_gather", "all_to_all", "ppermute", "pmax", "pmin",
    "reduce_scatter", "psum_scatter", "pgather", "pbroadcast", "axis_index",
})

HOST_CALLBACK_PRIMS = frozenset({
    "debug_callback", "debug_print", "pure_callback", "io_callback",
    "callback",
    "outside_call", "infeed", "outfeed",
})


# -- jaxpr walking ------------------------------------------------------------


def iter_eqns(jaxpr) -> Iterator[Any]:
    """Every eqn in ``jaxpr`` and, recursively, in sub-jaxprs carried in eqn
    params (pjit/scan/while/cond/custom_vjp bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:  # ClosedJaxpr
                    yield from iter_eqns(inner)
                elif hasattr(sub, "eqns"):  # raw Jaxpr
                    yield from iter_eqns(sub)


def _user_frames(eqn) -> List[Any]:
    """The eqn's Python frames outside jax itself, innermost first (each
    has ``file_name``, ``function_name`` — a qualname — and ``line_num``)."""
    import jax

    tb = eqn.source_info.traceback
    if tb is None:
        return []
    jax_dir = os.path.dirname(os.path.abspath(jax.__file__)) + os.sep
    return [fr for fr in tb.frames if not fr.file_name.startswith(jax_dir)]


def _repo_root() -> str:
    import orion_tpu

    return os.path.dirname(os.path.dirname(os.path.abspath(orion_tpu.__file__)))


def _where(eqn, target: str) -> Tuple[str, int]:
    for fr in _user_frames(eqn):
        # repo-relative like Tier A findings, so baseline.json entries
        # match on any checkout
        return normalize_path(fr.file_name, _repo_root()), int(fr.line_num)
    return f"<jaxpr:{target}>", 0


def _scope_names(eqn) -> List[str]:
    """'file.py', 'dir/file.py' and 'file.py::function' labels for every
    user frame."""
    out = []
    for fr in _user_frames(eqn):
        base = fr.file_name.rsplit("/", 1)[-1]
        fn = fr.function_name
        # scopes are declared by bare function name; frames carry qualnames
        out.extend((
            base, f"{base}::{fn}", f"{base}::{fn.rsplit('.', 1)[-1]}",
            "/".join(fr.file_name.rsplit("/", 2)[-2:]),
        ))
    return out


def _largest_scan(jaxpr):
    scans = [e for e in iter_eqns(jaxpr) if e.primitive.name == "scan"]
    if not scans:
        return None
    return max(scans, key=lambda e: e.params.get("length") or 0)


def scan_carry_avals(jaxpr) -> Optional[Tuple[Tuple[Any, str], ...]]:
    """(shape, dtype) of each carry of the longest scan, or None if no scan."""
    eqn = _largest_scan(jaxpr)
    if eqn is None:
        return None
    n_const, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
    carries = eqn.invars[n_const:n_const + n_carry]
    return tuple(
        (tuple(v.aval.shape), str(v.aval.dtype)) for v in carries
    )


# -- contracts ----------------------------------------------------------------


def audit_no_collectives(closed_jaxpr, target: str) -> List[Finding]:
    out = []
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name in COLLECTIVE_PRIMS:
            path, line = _where(eqn, target)
            out.append(Finding(
                CONTRACT_DECODE_COLLECTIVES, path, line,
                f"collective `{eqn.primitive.name}` in the {target} jaxpr: "
                "the recurrent decode path must stay communication-free",
            ))
    return out


def audit_no_host_callbacks(closed_jaxpr, target: str) -> List[Finding]:
    out = []
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name in HOST_CALLBACK_PRIMS:
            path, line = _where(eqn, target)
            out.append(Finding(
                CONTRACT_HOST_CALLBACK, path, line,
                f"host callback `{eqn.primitive.name}` in the {target} "
                "jaxpr: host round-trips serialize the device pipeline",
            ))
    return out


def audit_matmul_bf16(
    closed_jaxpr, target: str, allowed_scopes: Sequence[str] = ()
) -> List[Finding]:
    """Flag dot_generals whose inputs are all float32 (a silent upcast in a
    bf16-policy step) unless a source frame matches ``allowed_scopes``."""
    out = []
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        dtypes = {str(v.aval.dtype) for v in eqn.invars}
        if dtypes != {"float32"}:
            continue  # bf16 inputs (f32 accumulation via preferred dtype ok)
        scopes = _scope_names(eqn)
        if any(s in scopes for s in allowed_scopes):
            continue
        path, line = _where(eqn, target)
        fn = scopes[1] if len(scopes) > 1 else "<unknown scope>"
        out.append(Finding(
            CONTRACT_BF16_MATMUL, path, line,
            f"f32xf32 dot_general from {fn} in the bf16 {target} step; "
            "declare the scope in models/configs.py::F32_MATMUL_SCOPES if "
            "the fp32 accumulation is intentional",
        ))
    return out


def audit_scan_state_invariance(
    jaxprs_by_size: Sequence[Tuple[str, Any]], target: str
) -> List[Finding]:
    """``jaxprs_by_size``: (label, closed_jaxpr) traced at different
    sequence/step counts. The decode scan's carry must be identical across
    all of them — O(1) state per token."""
    carries = []
    for label, jx in jaxprs_by_size:
        c = scan_carry_avals(jx.jaxpr)
        if c is None:
            return [Finding(
                CONTRACT_DECODE_STATE, f"<jaxpr:{target}>", 0,
                f"no scan found in the {target} jaxpr traced at {label}: "
                "the decode loop is expected to be ONE lax.scan",
            )]
        carries.append((label, c))
    ref_label, ref = carries[0]
    out = []
    for label, c in carries[1:]:
        if c != ref:
            out.append(Finding(
                CONTRACT_DECODE_STATE, f"<jaxpr:{target}>", 0,
                f"decode scan carry changes with sequence length "
                f"({ref_label}: {ref} != {label}: {c}): the O(1)-state "
                "contract is broken — some per-layer state grows with T",
            ))
    return out


# -- repo targets -------------------------------------------------------------


def trace_decode(prompt_len: int, max_new_tokens: int, cfg_name: str = "tiny"):
    """Abstractly trace the jitted recurrent decode entrypoint."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.generate import SampleConfig, _generate_jit
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM

    model = TransformerLM(get_config(cfg_name))
    key = jax.random.PRNGKey(0)
    prompt = jax.ShapeDtypeStruct((1, prompt_len), jnp.int32)
    params = jax.eval_shape(model.init, key, prompt)
    return jax.make_jaxpr(_generate_jit, static_argnums=(0, 3, 4))(
        model, params, prompt, max_new_tokens, SampleConfig(), key
    )


def trace_train_step(dtype: str = "bfloat16", seq_len: int = 32):
    """Abstractly trace the Trainer's jitted step body on a bf16 tiny
    config (materialize=False: shapes only, no weights allocated)."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.models.configs import get_config
    from orion_tpu.training.trainer import TrainConfig, Trainer

    cfg = TrainConfig(
        model=dataclasses.replace(get_config("tiny"), dtype=dtype),
        batch_size=2, seq_len=seq_len, steps=10,
    )
    tr = Trainer(cfg, materialize=False)
    batch = jax.ShapeDtypeStruct((cfg.batch_size, cfg.seq_len + 1), jnp.int32)
    return jax.make_jaxpr(tr._train_step)(tr._abstract, batch)


def trace_lra_step(cfg_name: str = "lra_listops_linear", seq_len: int = 64):
    """Abstractly trace the LRA classification train step."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.models.classifier import LRAClassifier
    from orion_tpu.models.configs import get_config
    from orion_tpu.train_lra import make_lra_step
    from orion_tpu.training import trainer as tr
    from orion_tpu.utils import rng as rngs

    mcfg = get_config(cfg_name)
    model = LRAClassifier(mcfg)
    shim = tr.TrainConfig(model=mcfg, steps=10)
    tx = tr.make_optimizer(shim)
    sched = tr.make_schedule(shim)
    root = rngs.root_key(0)
    step_fn, _ = make_lra_step(model, tx, sched, root, mcfg.dropout)

    key = jax.random.PRNGKey(0)
    toks = jax.ShapeDtypeStruct((2, seq_len), jnp.int32)
    mask = jax.ShapeDtypeStruct((2, seq_len), jnp.bool_)
    labels = jax.ShapeDtypeStruct((2,), jnp.int32)
    params = jax.eval_shape(model.init, key, toks, mask)
    state = jax.eval_shape(
        lambda p: {
            "params": p, "opt": tx.init(p),
            "step": jnp.zeros((), jnp.int32),
        },
        params,
    )
    return jax.make_jaxpr(step_fn)(state, toks, labels, mask)


def _f32_scopes() -> Tuple[str, ...]:
    from orion_tpu.models.configs import F32_MATMUL_SCOPES

    return F32_MATMUL_SCOPES


def _audit_target(
    name: str, fn: Callable[[], List[Finding]], findings: List[Finding]
) -> None:
    try:
        findings.extend(fn())
    except Exception as e:  # noqa: BLE001 - surfaced as a finding, not a crash
        findings.append(Finding(
            AUDIT_ERROR, f"<jaxpr:{name}>", 0,
            f"tracing {name} failed: {type(e).__name__}: {e}",
        ))


def audit_repo() -> List[Finding]:
    """Trace the three contract-bearing entrypoints and run every contract."""
    findings: List[Finding] = []

    def decode() -> List[Finding]:
        jx_small = trace_decode(8, 8)
        jx_large = trace_decode(16, 16)
        out = audit_no_collectives(jx_small, "decode")
        out += audit_no_host_callbacks(jx_small, "decode")
        out += audit_scan_state_invariance(
            [("t0=8,n=8", jx_small), ("t0=16,n=16", jx_large)], "decode"
        )
        return out

    def train() -> List[Finding]:
        jx = trace_train_step()
        out = audit_matmul_bf16(jx, "train", allowed_scopes=_f32_scopes())
        out += audit_no_host_callbacks(jx, "train")
        return out

    def lra() -> List[Finding]:
        jx = trace_lra_step()
        return audit_no_host_callbacks(jx, "lra")

    _audit_target("decode", decode, findings)
    _audit_target("train", train, findings)
    _audit_target("lra", lra, findings)
    return findings


__all__ = [
    "audit_repo", "audit_no_collectives", "audit_no_host_callbacks",
    "audit_matmul_bf16", "audit_scan_state_invariance", "iter_eqns",
    "scan_carry_avals", "trace_decode", "trace_train_step", "trace_lra_step",
    "ALL_CONTRACTS", "CONTRACT_DECODE_COLLECTIVES", "CONTRACT_DECODE_STATE",
    "CONTRACT_BF16_MATMUL", "CONTRACT_HOST_CALLBACK", "AUDIT_ERROR",
]
