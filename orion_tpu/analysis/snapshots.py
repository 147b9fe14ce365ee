"""Tier C (part 2): golden compile-artifact snapshots.

Budget checks (spmd_audit.py) see the jaxpr; this module looks one layer
down, at what XLA actually compiled. Each target in
:data:`SNAPSHOT_TARGETS` is lowered and compiled on the deterministic
8-virtual-CPU-device mesh and summarized into a small JSON artifact:

- ``op_histogram``     — optimized-HLO opcode counts (fusions included):
  the compiled program's shape, insensitive to register names.
- ``hlo_collectives``  — all-reduce / all-gather / reduce-scatter /
  all-to-all / collective-permute counts in the optimized HLO — the
  communication GSPMD actually inserted from the shardings (the jaxpr of
  the auto-sharded train step shows none of these).
- ``scan_carry_bytes`` — byte size of the largest scan's carry (the
  decode target's O(1)-state budget in bytes).
- ``dtype_counts``     — occurrences of each element-type token in the
  optimized HLO (``s8[...]``, ``f32[...]``, ...): the artifact that pins
  a quantized program's storage story — the int8/int4 decode targets
  must show ``s8`` weight traffic while their scan carry stays the fp32
  target's EXACT byte size (weights quantize, state never does).
- ``flops`` / ``bytes_accessed`` — the compiler's own cost model.
- ``donation``         — declared donated input buffers vs the aliases
  XLA accepted. A donated arg XLA refuses to alias silently doubles that
  buffer's HBM footprint: surfaced as ``donated-arg-unaliased``.

Snapshots are stored under ``orion_tpu/analysis/golden/`` and regenerated
with ``python -m orion_tpu.analysis --update-golden``. The audit recompiles
each target and diffs against the stored file with a human-readable delta,
so any PR that changes the compiled program must either update the golden
file (making the change reviewable) or fail tier-1:

- ``golden-snapshot-missing`` — no stored artifact for a target.
- ``golden-snapshot-drift``   — stored vs fresh mismatch (delta in the
  finding message).

Generation is deterministic on CPU: same jax/jaxlib + same config =>
byte-identical JSON (asserted by tests regenerating in-process).
"""

from __future__ import annotations

import collections
import json
import os
import re
from typing import Any, Callable, Dict, List, Optional, Tuple

from orion_tpu.analysis.findings import Finding
from orion_tpu.analysis.jaxpr_audit import AUDIT_ERROR, scan_carry_avals
from orion_tpu.analysis.spmd_audit import ensure_cpu_devices

RULE_DRIFT = "golden-snapshot-drift"
RULE_MISSING = "golden-snapshot-missing"
RULE_DONATION = "donated-arg-unaliased"

ALL_GOLDEN_CHECKS = (RULE_DRIFT, RULE_MISSING, RULE_DONATION)

GOLDEN_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden")

_MAX_DELTA_LINES = 20


# -- HLO text extraction ------------------------------------------------------

# "%name = shape opcode(...)" — shape is either a bare token or a tuple
_OP_RE = re.compile(
    r"(?m)^\s*(?:ROOT\s+)?%?[\w.\-]+ = (?:\([^)]*\)|\S+) ([a-z][a-z0-9\-]*)\("
)

_HLO_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def op_histogram(hlo_text: str) -> Dict[str, int]:
    return dict(sorted(collections.Counter(_OP_RE.findall(hlo_text)).items()))


# element-type tokens as they appear in HLO shapes ("s8[128,64]{...}")
_DTYPE_RE = re.compile(r"\b(pred|s4|u4|s8|u8|s16|u16|s32|u32|s64|u64|"
                       r"bf16|f16|f32|f64)\[")


def dtype_counts(hlo_text: str) -> Dict[str, int]:
    """Shape-dtype token histogram of the optimized HLO — how often each
    element type appears in an instruction shape. Coarse by design: it
    pins that a quantized program actually streams int8 buffers (s8 > 0)
    and that the fp32 program has none, without depending on how XLA
    fuses the dequant convert into the dot."""
    return dict(sorted(
        collections.Counter(_DTYPE_RE.findall(hlo_text)).items()
    ))


def hlo_collective_counts(hlo_text: str) -> Dict[str, int]:
    return {
        op: len(re.findall(rf"\b{op}(?:-start)?\(", hlo_text))
        for op in _HLO_COLLECTIVES
    }


def alias_count(hlo_text: str) -> int:
    """Input/output aliases XLA ACCEPTED (entry-computation
    ``input_output_alias`` entries)."""
    return hlo_text.count("may-alias") + hlo_text.count("must-alias")


def _carry_bytes(closed_jaxpr) -> Optional[int]:
    import numpy as np

    carries = scan_carry_avals(closed_jaxpr.jaxpr)
    if carries is None:
        return None
    total = 0
    for shape, dtype in carries:
        n = int(np.prod(shape)) if shape else 1
        total += n * np.dtype(dtype).itemsize
    return total


def _cost_ints(compiled) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    try:
        ca = compiled.cost_analysis()
        ca0 = ca[0] if isinstance(ca, (list, tuple)) else ca
        for key, name in (("flops", "flops"), ("bytes accessed", "bytes_accessed")):
            v = ca0.get(key)
            if v is not None:
                out[name] = int(v)
    except Exception as e:  # backend-dependent introspection
        out["cost_analysis_error"] = f"{type(e).__name__}: {e}"[:120]
    return out


# -- targets ------------------------------------------------------------------


def _snap_train_tiny_dp8() -> Tuple[Any, Any, Dict[str, Any]]:
    """The donated, GSPMD-sharded tiny train step on the dp=8 mesh — the
    artifact that proves the sharding rules engage (all-reduces present)
    and donation aliases (state updated in place). Built from the SAME
    trainer the Tier C budget audit traces (spmd_audit.tiny_dp8_trainer)
    so budget and snapshot can never drift onto different programs."""
    import jax

    from orion_tpu.analysis.spmd_audit import tiny_dp8_trainer

    tr, batch = tiny_dp8_trainer()
    jaxpr = jax.make_jaxpr(tr._train_step)(tr._abstract, batch)
    lowered = tr._step_fn.lower(tr.abstract_state(), batch)
    meta = {
        "mesh": {k: int(v) for k, v in tr.mesh.shape.items()},
        "batch_size": tr.cfg.batch_size,
        "seq_len": tr.cfg.seq_len,
        # _step_fn donates the whole TrainState (donate_argnums=(0,))
        "donated_args": len(jax.tree.leaves(tr.abstract_state())),
    }
    return jaxpr, lowered, meta


def _snap_decode_tiny() -> Tuple[Any, Any, Dict[str, Any]]:
    """The jitted recurrent decode step — the O(1)-state artifact (its
    scan carry bytes ARE the per-token state budget)."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.generate import SampleConfig, _generate_jit
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM

    model = TransformerLM(get_config("tiny"))
    key = jax.random.PRNGKey(0)
    prompt = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    params = jax.eval_shape(model.init, key, prompt)
    fn = jax.jit(_generate_jit, static_argnums=(0, 3, 4))
    args = (model, params, prompt, 8, SampleConfig(), key)
    jaxpr = jax.make_jaxpr(_generate_jit, static_argnums=(0, 3, 4))(*args)
    lowered = fn.lower(*args)
    meta = {"prompt_len": 8, "max_new_tokens": 8, "donated_args": 0}
    return jaxpr, lowered, meta


def _snap_decode_batched_tiny() -> Tuple[Any, Any, Dict[str, Any]]:
    """The slot-multiplexed batched decode chunk (continuous batching,
    serving/batching.py SlotEngine) at slots=8, chunk=8 — the artifact
    that pins the engine's compiled shape: scan-carry bytes must scale
    LINEARLY in the slot count (each slot is one row of the O(1) state —
    no paged-KV overhead) and the collective count stays zero (decode
    never communicates). tests/test_batching.py asserts the linearity
    against a slots=1 jaxpr rebuild."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from orion_tpu.generate import SampleConfig, _decode_batched_chunk_jit
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM, init_decode_state

    cfg = get_config("tiny")
    model = TransformerLM(cfg)
    slots, chunk = 8, 8
    key = jax.random.PRNGKey(0)
    prompt = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    params = jax.eval_shape(model.init, key, prompt)
    states = jax.eval_shape(partial(init_decode_state, cfg, slots))
    vec = lambda dt: jax.ShapeDtypeStruct((slots,), dt)  # noqa: E731
    carry = (
        vec(jnp.int32), states, vec(jnp.int32), vec(jnp.int32),
        vec(jnp.bool_),
    )
    rngs = jax.ShapeDtypeStruct((slots, 2), jnp.uint32)
    active = vec(jnp.bool_)
    args = (model, params, carry, rngs, active, chunk, SampleConfig())
    jaxpr = jax.make_jaxpr(
        _decode_batched_chunk_jit, static_argnums=(0, 5, 6)
    )(*args)
    lowered = _decode_batched_chunk_jit.lower(*args)
    meta = {"slots": slots, "chunk": chunk, "donated_args": 0}
    return jaxpr, lowered, meta


def _snap_decode_batched_prefill_tiny() -> Tuple[Any, Any, Dict[str, Any]]:
    """The UNIFIED in-scan prefill + decode chunk (ISSUE 7,
    generate.decode_batched_prefill_chunk) at slots=8, chunk=8,
    prompt_bucket=16 — the program the engine runs while any slot is
    mid-prefill. Pins three things: the scan-carry bytes stay LINEAR in
    the slot count (the staged prompt buffer rides OUTSIDE the scan
    carry — prefill must not fatten the O(1) decode state), collectives
    stay zero, and — because the staging path is a separate jit — the
    pure-decode program (``decode_batched_tiny``) keeps compiling
    byte-identically when no slot is prefilling."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from orion_tpu.generate import (
        SampleConfig,
        _decode_batched_prefill_chunk_jit,
    )
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM, init_decode_state

    cfg = get_config("tiny")
    model = TransformerLM(cfg)
    slots, chunk, bucket, pchunk = 8, 8, 16, 128
    key = jax.random.PRNGKey(0)
    prompt = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    params = jax.eval_shape(model.init, key, prompt)
    states = jax.eval_shape(partial(init_decode_state, cfg, slots))
    vec = lambda dt: jax.ShapeDtypeStruct((slots,), dt)  # noqa: E731
    carry = (
        vec(jnp.int32), states, vec(jnp.int32), vec(jnp.int32),
        vec(jnp.bool_),
    )
    rngs = jax.ShapeDtypeStruct((slots, 2), jnp.uint32)
    active = vec(jnp.bool_)
    pbuf = jax.ShapeDtypeStruct((slots, bucket), jnp.int32)
    args = (
        model, params, carry, rngs, active, pbuf, vec(jnp.int32),
        vec(jnp.int32), vec(jnp.int32), chunk, pchunk, SampleConfig(),
    )
    jaxpr = jax.make_jaxpr(
        _decode_batched_prefill_chunk_jit, static_argnums=(0, 9, 10, 11)
    )(*args)
    lowered = _decode_batched_prefill_chunk_jit.lower(*args)
    meta = {
        "slots": slots, "chunk": chunk, "prompt_bucket": bucket,
        "prefill_chunk": pchunk, "donated_args": 0,
    }
    return jaxpr, lowered, meta


def _snap_decode_batched_quant(mode: str) -> Tuple[Any, Any, Dict[str, Any]]:
    """The slot-multiplexed batched decode chunk compiled over the QUANT
    model (``TransformerLM(cfg, quant=mode)``) at the same slots=8,
    chunk=8 shape as ``decode_batched_tiny`` — the quantized-serving
    artifact (ISSUE 11). Three pins: collectives stay zero, the scan
    carry bytes are EXACTLY the fp32 target's (the carry is tokens +
    decode state + bookkeeping; weights quantize, the carry must not
    grow or shrink with qmode), and ``dtype_counts`` shows the s8 weight
    traffic (int4 packs nibbles into s8 bytes too — halving shows up in
    buffer SIZES, which the op/dtype mix reflects via the unpack ops)."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from orion_tpu.generate import SampleConfig, _decode_batched_chunk_jit
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM, init_decode_state

    cfg = get_config("tiny")
    model = TransformerLM(cfg, quant=mode)
    slots, chunk = 8, 8
    key = jax.random.PRNGKey(0)
    prompt = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    params = jax.eval_shape(model.init, key, prompt)
    states = jax.eval_shape(partial(init_decode_state, cfg, slots))
    vec = lambda dt: jax.ShapeDtypeStruct((slots,), dt)  # noqa: E731
    carry = (
        vec(jnp.int32), states, vec(jnp.int32), vec(jnp.int32),
        vec(jnp.bool_),
    )
    rngs = jax.ShapeDtypeStruct((slots, 2), jnp.uint32)
    active = vec(jnp.bool_)
    args = (model, params, carry, rngs, active, chunk, SampleConfig())
    jaxpr = jax.make_jaxpr(
        _decode_batched_chunk_jit, static_argnums=(0, 5, 6)
    )(*args)
    lowered = _decode_batched_chunk_jit.lower(*args)
    meta = {"slots": slots, "chunk": chunk, "qmode": mode,
            "donated_args": 0}
    return jaxpr, lowered, meta


def _snap_decode_batched_spec_tiny() -> Tuple[Any, Any, Dict[str, Any]]:
    """The self-speculative round (ISSUE 13,
    generate.decode_batched_spec_round) at slots=8, spec depth=4 on the
    tiny config — the artifact that pins the draft-verify program's
    shape: collectives stay ZERO (speculation never communicates), and
    the largest scan carry must NOT exceed the plain batched decode's —
    the draft scan threads the SAME (S, z) rows (shadow copies of the
    carry's own leaves, no growth) and the verify's inner scans carry
    one layer's state at a time. tests/test_analysis.py asserts the
    no-growth bound against ``decode_batched_tiny`` and
    tests/test_spec_decode.py the slot-linearity of the carry."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from orion_tpu.generate import SampleConfig, _decode_batched_spec_round_jit
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM, init_decode_state

    cfg = get_config("tiny")
    model = TransformerLM(cfg)
    slots, depth = 8, 4
    key = jax.random.PRNGKey(0)
    prompt = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    params = jax.eval_shape(model.init, key, prompt)
    states = jax.eval_shape(partial(init_decode_state, cfg, slots))
    vec = lambda dt: jax.ShapeDtypeStruct((slots,), dt)  # noqa: E731
    carry = (
        vec(jnp.int32), states, vec(jnp.int32), vec(jnp.int32),
        vec(jnp.bool_),
    )
    rngs = jax.ShapeDtypeStruct((slots, 2), jnp.uint32)
    active = vec(jnp.bool_)
    spec_on = vec(jnp.bool_)
    args = (
        model, params, carry, rngs, active, spec_on, depth, SampleConfig(),
    )
    jaxpr = jax.make_jaxpr(
        _decode_batched_spec_round_jit, static_argnums=(0, 6, 7)
    )(*args)
    lowered = _decode_batched_spec_round_jit.lower(*args)
    meta = {"slots": slots, "spec_depth": depth, "donated_args": 0}
    return jaxpr, lowered, meta


def _snap_decode_batched_tp(tp: int) -> Tuple[Any, Any, Dict[str, Any]]:
    """The slot-multiplexed batched decode chunk compiled under a tp=N
    mesh (ISSUE 14, SlotEngine(mesh=...)): params sharded by the training
    rules, state head-sharded, per-slot vectors replicated. Four pins:

    - ``hlo_collectives``: exactly the Megatron contract — TWO
      all-reduces per block per decode step (wo + down), nothing else
      (the head-sharded state and the qkv/gate/up output shards
      communicate nothing). A third collective appearing here is a
      leaked per-token cost no CPU parity test would catch.
    - ``scan_carry_bytes_per_device``: the head-sharded state divides by
      tp while only the few per-slot bookkeeping vectors replicate —
      tests/test_analysis.py asserts it against the unsharded
      ``decode_batched_tiny`` carry.
    - the collectives live INSIDE the decode scan's while-loop body
      (they depend on each step's activations — there is nothing to
      hoist), so program-level counts ARE per-step counts.
    - dtype_counts/op_histogram: the partitioned program's shape.

    The trace fixtures are shared with the Tier C budget audit
    (spmd_audit.tp_decode_pieces) so budget and snapshot can never drift
    onto different programs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from orion_tpu.analysis.spmd_audit import tp_decode_pieces
    from orion_tpu.generate import SampleConfig, _decode_batched_chunk_jit
    from orion_tpu.parallel.decode import bytes_per_device

    slots, chunk = 8, 8
    model, params, carry, rngs, vec, shardings = tp_decode_pieces(
        tp=tp, slots=slots
    )
    (p_abs, p_shd), (st_abs, st_shd), _mesh = shardings
    args = (model, params, carry, rngs, vec(jnp.bool_), chunk, SampleConfig())
    jaxpr = jax.make_jaxpr(
        _decode_batched_chunk_jit, static_argnums=(0, 5, 6)
    )(*args)
    lowered = _decode_batched_chunk_jit.lower(*args)
    # per-device carry bytes from the PLACEMENT (shape arithmetic, no
    # compile): sharded state / tp + the replicated per-slot vectors
    state_dev = bytes_per_device(st_abs, st_shd)
    vec_bytes = slots * (3 * np.dtype(np.int32).itemsize + 1)
    meta = {
        "slots": slots, "chunk": chunk, "mesh": {"tp": tp},
        "param_bytes_per_device": bytes_per_device(p_abs, p_shd),
        "scan_carry_bytes_per_device": state_dev + vec_bytes,
        "donated_args": 0,
    }
    return jaxpr, lowered, meta


def _snap_decode_batched_tp2():
    return _snap_decode_batched_tp(2)


def _snap_decode_batched_tp4():
    return _snap_decode_batched_tp(4)


def _snap_decode_batched_int8():
    return _snap_decode_batched_quant("int8")


def _snap_decode_batched_int4():
    return _snap_decode_batched_quant("int4")


# name -> () -> (closed_jaxpr, lowered, meta). Golden files live at
# golden/<name>.json; adding a target here + --update-golden creates one.
SNAPSHOT_TARGETS: Dict[str, Callable[[], Tuple[Any, Any, Dict[str, Any]]]] = {
    "train_tiny_dp8": _snap_train_tiny_dp8,
    "decode_tiny": _snap_decode_tiny,
    "decode_batched_tiny": _snap_decode_batched_tiny,
    "decode_batched_prefill_tiny": _snap_decode_batched_prefill_tiny,
    "decode_batched_spec_tiny": _snap_decode_batched_spec_tiny,
    "decode_batched_int8": _snap_decode_batched_int8,
    "decode_batched_int4": _snap_decode_batched_int4,
    "decode_batched_tp2": _snap_decode_batched_tp2,
    "decode_batched_tp4": _snap_decode_batched_tp4,
}


def build_snapshot(name: str) -> Dict[str, Any]:
    jaxpr, lowered, meta = SNAPSHOT_TARGETS[name]()
    compiled = lowered.compile()
    hlo = compiled.as_text()
    snap: Dict[str, Any] = {
        "target": name,
        **meta,
        "op_histogram": op_histogram(hlo),
        "dtype_counts": dtype_counts(hlo),
        "hlo_collectives": hlo_collective_counts(hlo),
        "scan_carry_bytes": _carry_bytes(jaxpr),
        "donation": {
            "donated_args": meta.get("donated_args", 0),
            "aliased": alias_count(hlo),
        },
    }
    snap.pop("donated_args", None)
    snap.update(_cost_ints(compiled))
    return snap


# -- diff + audit -------------------------------------------------------------


def _flatten(d: Dict[str, Any], prefix: str = "") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k in sorted(d):
        key = f"{prefix}{k}"
        if isinstance(d[k], dict):
            out.update(_flatten(d[k], key + "."))
        else:
            out[key] = d[k]
    return out


def diff_report(golden: Dict[str, Any], fresh: Dict[str, Any]) -> List[str]:
    """Human-readable delta lines, golden -> fresh; empty == identical."""
    g, f = _flatten(golden), _flatten(fresh)
    lines = []
    for k in sorted(set(g) | set(f)):
        if k not in g:
            lines.append(f"+ {k} = {f[k]!r} (not in golden)")
        elif k not in f:
            lines.append(f"- {k} = {g[k]!r} (gone from fresh build)")
        elif g[k] != f[k]:
            lines.append(f"~ {k}: {g[k]!r} -> {f[k]!r}")
    return lines


def donation_findings(snap: Dict[str, Any], path: str) -> List[Finding]:
    """A donated buffer XLA refused to alias is a live memory regression
    regardless of what the golden file says — checked at build time."""
    d = snap.get("donation") or {}
    donated, aliased = d.get("donated_args", 0), d.get("aliased", 0)
    if donated and aliased < donated:
        return [Finding(
            RULE_DONATION, path, 0,
            f"{snap.get('target', path)}: {donated} donated input "
            f"buffer(s) but XLA aliased only {aliased} — each refused "
            "alias keeps both the argument and the output live "
            "(double HBM for that buffer); check dtype/sharding changes "
            "to the donated state",
        )]
    return []


def golden_path(name: str, golden_dir: str = GOLDEN_DIR) -> str:
    return os.path.join(golden_dir, f"{name}.json")


def write_golden(name: str, snap: Dict[str, Any], golden_dir: str = GOLDEN_DIR) -> str:
    os.makedirs(golden_dir, exist_ok=True)
    p = golden_path(name, golden_dir)
    with open(p, "w", encoding="utf-8") as f:
        json.dump(snap, f, indent=2, sort_keys=True)
        f.write("\n")
    return p


def audit_golden(
    update: bool = False,
    golden_dir: str = GOLDEN_DIR,
    fresh: Optional[Dict[str, Dict[str, Any]]] = None,
) -> List[Finding]:
    """Rebuild every snapshot target and diff against the checked-in golden
    files (``update=True`` rewrites them instead). ``fresh`` supplies
    prebuilt snapshots (tests share one expensive build across cases)."""
    err = ensure_cpu_devices()
    if err is not None:
        return [Finding(AUDIT_ERROR, "<golden>", 0, err)]

    findings: List[Finding] = []
    for name in SNAPSHOT_TARGETS:
        rel = f"orion_tpu/analysis/golden/{name}.json"
        try:
            snap = fresh[name] if fresh and name in fresh else build_snapshot(name)
        except Exception as e:  # noqa: BLE001 - surfaced as finding, not crash
            findings.append(Finding(
                AUDIT_ERROR, f"<golden:{name}>", 0,
                f"building snapshot {name} failed: {type(e).__name__}: {e}",
            ))
            continue
        findings.extend(donation_findings(snap, rel))
        if update:
            write_golden(name, snap, golden_dir)
            continue
        gp = golden_path(name, golden_dir)
        if not os.path.exists(gp):
            findings.append(Finding(
                RULE_MISSING, rel, 0,
                f"no golden snapshot for {name}; run "
                "`python -m orion_tpu.analysis --update-golden` and commit "
                "the result",
            ))
            continue
        with open(gp, encoding="utf-8") as f:
            golden = json.load(f)
        delta = diff_report(golden, snap)
        if delta:
            shown = delta[:_MAX_DELTA_LINES]
            if len(delta) > len(shown):
                shown.append(f"... {len(delta) - len(shown)} more line(s)")
            findings.append(Finding(
                RULE_DRIFT, rel, 0,
                f"compiled artifact for {name} drifted from its golden "
                f"snapshot ({len(delta)} delta line(s)):\n    "
                + "\n    ".join(shown)
                + "\n    intentional? rerun with --update-golden and commit "
                "the new snapshot so the change is reviewed",
            ))
    return findings


__all__ = [
    "audit_golden", "build_snapshot", "diff_report", "donation_findings",
    "op_histogram", "dtype_counts", "hlo_collective_counts",
    "alias_count", "write_golden",
    "golden_path", "SNAPSHOT_TARGETS", "GOLDEN_DIR", "ALL_GOLDEN_CHECKS",
    "RULE_DRIFT", "RULE_MISSING", "RULE_DONATION",
]
