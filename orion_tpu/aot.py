"""`python -m orion_tpu.aot` — ahead-of-time lowering + memory planning for
a sharded train step (SURVEY.md M4 buildability / VERDICT r1 item 8).

Answers "does this config build, shard, and fit?" without touching real
weights or real hardware: the full GSPMD train step is lowered and compiled
against *abstract* state (jax.ShapeDtypeStructs carrying NamedShardings),
so a 7B step can be validated on a laptop-sized host with a virtual
8-device mesh (``--force-cpu-devices N``). Reports:

- per-device parameter / optimizer-state bytes (from the sharding rules)
- the compiler's own memory analysis (argument/output/temp/code bytes)
  when the backend exposes it
- the collectives GSPMD inserted (all-gather / reduce-scatter / all-reduce
  counts in the optimized HLO) — evidence the sharding rules actually
  engaged rather than silently replicating

The reference validates its big configs by launching them (BASELINE.json
config #5 "7B hybrid"; reference checkout never mounted — SURVEY.md §0);
XLA's AOT path lets us make the same claim statically.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import re
import sys
from typing import Any, Dict, Optional


def _bytes_per_device(abstract: Any, shardings: Any) -> int:
    """Sum of leaf bytes / shard-factor over the state tree."""
    import jax
    import numpy as np

    total = 0
    for leaf, shd in zip(jax.tree.leaves(abstract), jax.tree.leaves(shardings)):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        factor = 1
        for dim, ax in enumerate(shd.spec):
            if ax is None or dim >= len(leaf.shape):
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            for a in axes:
                factor *= shd.mesh.shape[a]
        total += n * leaf.dtype.itemsize // max(factor, 1)
    return total


def _collective_counts(hlo_text: str) -> Dict[str, int]:
    ops = ("all-gather", "reduce-scatter", "all-reduce", "all-to-all",
           "collective-permute")
    counts: Dict[str, int] = collections.Counter()
    for op in ops:
        counts[op] = len(re.findall(rf"\b{op}(?:-start)?\(", hlo_text))
    # Mosaic kernels land as custom-calls with target "tpu_custom_call":
    # >0 is the proof a backend="pallas" plan actually carries the kernels
    # (vs silently falling back to the XLA forms). Counting bare
    # `custom-call(` would also count AllocateBuffer / async-collective
    # plumbing and overstate kernel presence.
    counts["mosaic_kernels"] = len(
        re.findall(r'custom_call_target="tpu_custom_call"', hlo_text)
    )
    return dict(counts)


def topology_mesh(topology: str, mesh_cfg) -> Any:
    """Mesh over a named TPU topology's ABSTRACT devices (e.g. "v5e:2x4") —
    no hardware attached: jax's topology AOT path hands the real TPU
    compiler (Mosaic included) the target platform, so a plan validated
    here is the exact executable a pod of that shape would run. This is
    strictly stronger evidence than the virtual-CPU mesh: CPU numbers come
    from the CPU backend's memory model and skip Mosaic entirely."""
    from jax.experimental import topologies

    from orion_tpu.parallel.mesh import make_mesh

    topo = topologies.get_topology_desc(platform="tpu", topology_name=topology)
    return make_mesh(mesh_cfg.resolve(len(topo.devices)), devices=topo.devices)


def plan(
    cfg,
    compile_step: bool = True,
    hlo: bool = False,
    mesh: Any = None,
) -> Dict[str, Any]:
    """Lower (and optionally compile) the sharded train step for
    ``cfg: TrainConfig``; return the planning report dict. ``mesh``
    overrides the config-derived device mesh (the --topology path)."""
    import jax
    import numpy as np

    from orion_tpu.training.trainer import Trainer

    trainer = Trainer(cfg, mesh=mesh, materialize=False)
    abstract = trainer.abstract_state()
    batch = jax.ShapeDtypeStruct(
        (cfg.batch_size, cfg.seq_len + 1), np.int32, sharding=trainer.batch_shd
    )

    n_params = sum(
        int(np.prod(l.shape))
        for l in jax.tree.leaves(trainer._abstract.params)
    )
    report: Dict[str, Any] = {
        "config": cfg.model.name,
        "mesh": dict(trainer.mesh.shape),
        "batch_size": cfg.batch_size,
        "seq_len": cfg.seq_len,
        "n_params": n_params,
        "param_bytes_per_device": _bytes_per_device(
            trainer._abstract.params,
            trainer.state_shardings.params,
        ),
        "state_bytes_per_device": _bytes_per_device(
            trainer._abstract, trainer.state_shardings
        ),
    }

    lowered = trainer._step_fn.lower(abstract, batch)
    report["lowered"] = True
    if not compile_step:
        return report

    compiled = lowered.compile()
    report["compiled"] = True
    # these introspection APIs are backend-dependent; record failures rather
    # than silently dropping the sections the tool exists to report
    try:
        hlo_text = compiled.as_text()
        report["collectives"] = _collective_counts(hlo_text)
        if hlo:
            report["hlo_text"] = hlo_text
    except Exception as e:
        report["collectives_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
            ):
                v = getattr(ma, k, None)
                if v is not None:
                    report[k] = int(v)
    except Exception as e:
        report["memory_analysis_error"] = f"{type(e).__name__}: {e}"[:200]
    return report


def _decode_abstracts(model_cfg, slots: int, qmode: str, tp: int):
    """Abstract (model, params, carry, rngs, active, shaped) for lowering
    the serving decode programs — shared by :func:`decode_plan` and
    :func:`decode_cost_entries` so the two can never key off different
    shapes. The params are the SERVING tree's (matmul weights in the
    compute dtype, as ``Server`` casts them once at set-up). With ``tp > 1``
    everything carries the serving mesh's NamedShardings (params by the
    training rules, state head-sharded, per-slot vectors replicated)."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.models.transformer import TransformerLM, init_decode_state

    tp = max(int(tp), 1)
    model = TransformerLM(model_cfg, quant=qmode if qmode != "off" else "")
    mesh = None
    if tp > 1:
        from orion_tpu.parallel.decode import mesh_model, serving_mesh

        mesh = serving_mesh(tp)
        model = mesh_model(model, mesh)

    prompt = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0), prompt)
    # the tree a Server hands its programs: the matmul weights already in
    # the compute dtype (generate.serving_params), so plans, store keys and
    # the cost harvest describe the programs that are run
    from orion_tpu.generate import serving_params

    abstract = serving_params(model, abstract)
    states = jax.eval_shape(lambda: init_decode_state(model_cfg, slots))
    if mesh is not None:
        from orion_tpu.parallel.decode import (
            decode_param_shardings,
            decode_state_shardings,
        )
        from jax.sharding import NamedSharding, PartitionSpec as P

        sds = lambda l, s: jax.ShapeDtypeStruct(  # noqa: E731
            l.shape, l.dtype, sharding=s
        )
        params = jax.tree.map(
            sds, abstract, decode_param_shardings(abstract, mesh)
        )
        states = jax.tree.map(
            sds, states, decode_state_shardings(states, mesh)
        )
        rep = NamedSharding(mesh, P())
        shaped = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dt, sharding=rep
        )
    else:
        params = abstract
        shaped = jax.ShapeDtypeStruct
    vec = lambda dt: shaped((slots,), dt)  # noqa: E731
    carry = (
        vec(jnp.int32), states, vec(jnp.int32), vec(jnp.int32),
        vec(jnp.bool_),
    )
    rngs = shaped((slots, 2), jnp.uint32)
    active = vec(jnp.bool_)
    return model, params, carry, rngs, active, shaped


def _lowered_cost(lowered) -> Dict[str, Any]:
    """Flops/bytes from a Lowered's HLO cost analysis, normalized to one
    flat dict (some jax versions return a per-device list)."""
    ca = lowered.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = ca or {}
    out: Dict[str, Any] = {}
    for src, dst in (("flops", "flops"), ("bytes accessed", "bytes_accessed"),
                     ("transcendentals", "transcendentals")):
        v = ca.get(src)
        if v is not None:
            out[dst] = float(v)
    return out


# identity -> harvested entries; the harvest is pure (abstract shapes in,
# cost numbers out), so one process-wide memo makes repeated Server
# constructions of the same engine shape free after the first
_COST_MEMO: Dict[tuple, list] = {}


def _aligned_pchunk(model_cfg, prefill_chunk: int, tp: int) -> int:
    """The piece width a replica compiles: the engine's in-scan piece
    boundaries align to the linear-attention chunk (SlotEngine rounds the
    knob up; batching.py ``chunk_align``), and an inventory must list that
    width, not the raw knob."""
    from orion_tpu.ops.dispatch import resolve, resolve_chunk
    from orion_tpu.parallel.decode import mesh_backend

    align = resolve_chunk(
        model_cfg.chunk, model_cfg.max_seq_len,
        resolve(mesh_backend(model_cfg.backend, tp)),
    )
    return -(-int(prefill_chunk) // align) * align


def decode_cost_entries(
    model_cfg,
    slots: int = 8,
    chunk: int = 16,
    *,
    bucket: int,
    prefill_chunk: int,
    qmode: str = "off",
    tp: int = 0,
    spec_depth: int = 0,
) -> list:
    """The cost-ledger harvest (ISSUE 15): LOWER (never compile — the
    jit caches stay untouched, which the zero-compile acceptance pins)
    each decode program this engine shape actually runs and extract XLA
    ``cost_analysis()`` flops/bytes. Returns entries
    ``{"kind", "key", "flops", "bytes_accessed", ...}`` keyed by the
    golden-snapshot identity. ``bucket`` is the staged-buffer width the
    unified program is costed at (the engine's largest prefill bucket —
    the worst-case piece); a per-program failure is recorded on its
    entry, never raised: serving must come up even when the harvest
    can't."""
    import time as _time

    tp = max(int(tp), 1)
    memo_key = (repr(model_cfg), slots, chunk, int(bucket),
                int(prefill_chunk), qmode, tp, int(spec_depth))
    got = _COST_MEMO.get(memo_key)
    if got is not None:
        return [dict(e) for e in got]

    import jax.numpy as jnp

    from orion_tpu.generate import (
        SampleConfig,
        _decode_batched_chunk_jit,
        _decode_batched_prefill_chunk_jit,
        _decode_batched_spec_round_jit,
    )
    from orion_tpu.obs.cost import program_key

    model, params, carry, rngs, active, shaped = _decode_abstracts(
        model_cfg, slots, qmode, tp
    )
    vec = lambda dt: shaped((slots,), dt)  # noqa: E731
    sample = SampleConfig()
    base = {"slots": slots, "chunk": chunk, "qmode": qmode, "tp": tp}
    entries = []

    def harvest(kind: str, key: Dict[str, Any], lower) -> None:
        entry: Dict[str, Any] = {
            "kind": kind, "key": program_key(kind, **key),
        }
        t0 = _time.monotonic()
        try:
            entry.update(_lowered_cost(lower()))
            entry["lower_ms"] = round((_time.monotonic() - t0) * 1e3, 3)
        except Exception as e:  # surface on the entry, never crash serving
            entry["error"] = f"{type(e).__name__}: {e}"[:200]
        entries.append(entry)

    harvest("decode_batched", dict(base), lambda: (
        _decode_batched_chunk_jit.lower(
            model, params, carry, rngs, active, int(chunk), sample
        )
    ))
    pchunk = _aligned_pchunk(model_cfg, prefill_chunk, tp)
    pbuf = shaped((slots, int(bucket)), jnp.int32)
    harvest(
        "unified_prefill",
        dict(base, bucket=int(bucket), prefill_chunk=pchunk),
        lambda: _decode_batched_prefill_chunk_jit.lower(
            model, params, carry, rngs, active, pbuf,
            vec(jnp.int32), vec(jnp.int32), vec(jnp.int32), int(chunk),
            min(pchunk, int(bucket)), sample,
        ),
    )
    if int(spec_depth) > 0:
        harvest(
            "spec_round",
            {"slots": slots, "spec_depth": int(spec_depth),
             "qmode": qmode, "tp": tp},
            lambda: _decode_batched_spec_round_jit.lower(
                model, params, carry, rngs, active, vec(jnp.bool_),
                int(spec_depth), sample,
            ),
        )
    _COST_MEMO[memo_key] = [dict(e) for e in entries]
    return entries


def decode_plan(
    model_cfg,
    slots: int = 8,
    chunk: int = 16,
    *,
    prefill_buckets,
    prefill_chunk: int,
    qmode: str = "off",
    tp: int = 0,
    spec_depth: int = 0,
    compile_step: bool = True,
    lower: bool = True,
    store=None,
    sample=None,
) -> Dict[str, Any]:
    """The SERVING-side inventory ``plan`` never had (ISSUE 14): every
    decode/prefill executable a replica of this shape compiles, keyed
    exactly like the jit caches — (slots, chunk, bucket, qmode, tp) —
    lowered (and optionally compiled) against abstract sharded state.
    This is the complete program list ROADMAP item 4's warm-start work
    needs to persist: a respawned replica serving these shapes runs
    precisely these executables, nothing else (the engine's
    one-compile-per-key contract is cache-stat-asserted in tests).

    Per program: the GSPMD collectives (for tp plans: the two
    per-block all-reduces per decode step — evidence the mesh engaged)
    and the compiler's code size, the artifact a warm-start cache would
    key and store. ``lower=False`` skips lowering entirely and returns
    the pure inventory (identity keys only) — the cheap side Tier E's
    plan-drift rule and :func:`verify_decode_plan` diff against the
    declared universe.

    ``store`` (a :class:`~orion_tpu.serving.exec_store.ExecStore`)
    engages the warm-start path both ways: a program whose identity is
    already COMMITTED in the store short-circuits (``warm: True`` on its
    entry, no lowering — repeated ``--verify`` preflights cost one
    listdir per program), and a freshly compiled program is serialized
    and PUBLISHED (``published_gen`` on the entry; per-entry
    ``publish_error`` on failure, never raised — the plan must come out
    even when the store is down).

    ``sample`` is the SampleConfig the programs are specialized on (a
    jit static, part of every executable's content address — the CLIs
    default temperature 0.8, NOT the dataclass default 1.0, so a warm
    meant for CLI-launched replicas must be published under the same
    sampling statics). None = dataclass defaults."""
    tp = max(int(tp), 1)
    base_key = {"slots": slots, "chunk": chunk, "qmode": qmode, "tp": tp}

    # pass 1: the pure inventory — entry identities plus deferred
    # lowering thunks, NO jax work yet (thunks only run in pass 2)
    programs: list = []
    jobs: list = []

    def add(kind: str, key: Dict[str, Any], lower_thunk) -> None:
        entry: Dict[str, Any] = {"kind": kind, **key}
        programs.append(entry)
        jobs.append((entry, lower_thunk))

    add("decode_batched", dict(base_key), lambda env: (
        env["decode_batched"].lower(
            env["model"], env["params"], env["carry"], env["rngs"],
            env["active"], int(chunk), env["sample"],
        )
    ))
    if not prefill_buckets or int(prefill_chunk) <= 0:
        raise ValueError(
            "no engine has this footprint: admission is in-scan, which "
            f"needs prompt buckets (got {tuple(prefill_buckets)}) and "
            f"prefill_chunk > 0 (got {prefill_chunk})"
        )
    pchunk = _aligned_pchunk(model_cfg, prefill_chunk, tp)
    for bucket in prefill_buckets:
        add(
            "unified_prefill",
            dict(base_key, bucket=int(bucket), prefill_chunk=pchunk),
            lambda env, bucket=bucket: (
                env["unified_prefill"].lower(
                    env["model"], env["params"], env["carry"],
                    env["rngs"], env["active"],
                    env["shaped"]((slots, int(bucket)), env["i32"]),
                    env["vec"](env["i32"]), env["vec"](env["i32"]),
                    env["vec"](env["i32"]),
                    int(chunk), pchunk, env["sample"],
                )
            ),
        )
        # the whole-prompt bucketed prefill (the ladder's re-prefill
        # rung, prefix publishes): batch 1
        add(
            "prefill_bucketed",
            {"bucket": int(bucket), "qmode": qmode, "tp": tp},
            lambda env, bucket=bucket: env["prefill_bucketed"].lower(
                env["model"], env["params"],
                env["shaped"]((1, int(bucket)), env["i32"]), env["sample"],
                env["shaped"]((2,), env["u32"]),
                env["shaped"]((), env["i32"]),
                env["shaped"]((1,), env["bool"]),
                env["shaped"]((), env["i32"]),
            ),
        )
    if spec_depth:
        add(
            "spec_round",
            {"slots": slots, "spec_depth": int(spec_depth),
             "qmode": qmode, "tp": tp},
            lambda env: env["spec_round"].lower(
                env["model"], env["params"], env["carry"], env["rngs"],
                env["active"], env["vec"](env["bool"]),
                int(spec_depth), env["sample"],
            ),
        )

    # pass 2: lower (and optionally compile) each planned program
    if lower:
        import jax.numpy as jnp

        from orion_tpu.generate import (
            SampleConfig,
            _decode_batched_chunk_jit,
            _decode_batched_prefill_chunk_jit,
            _decode_batched_spec_round_jit,
            _prefill_carry_bucketed_jit,
        )

        model, params, carry, rngs, active, shaped = _decode_abstracts(
            model_cfg, slots, qmode, tp
        )
        env = {
            "model": model, "params": params, "carry": carry,
            "rngs": rngs, "active": active, "shaped": shaped,
            "vec": lambda dt: shaped((slots,), dt),
            "sample": sample if sample is not None else SampleConfig(),
            "i32": jnp.int32, "u32": jnp.uint32, "bool": jnp.bool_,
            "decode_batched": _decode_batched_chunk_jit,
            "unified_prefill": _decode_batched_prefill_chunk_jit,
            "prefill_bucketed": _prefill_carry_bucketed_jit,
            "spec_round": _decode_batched_spec_round_jit,
        }
        sample_fp = ""
        if store is not None:
            from orion_tpu.serving.exec_store import sample_fingerprint

            sample_fp = sample_fingerprint(env["sample"])
        for entry, thunk in jobs:
            ident = dict(entry)  # pure identity until this pass mutates it
            if store is not None and store.has(ident, sample_fp):
                # content-hash short-circuit: a COMMITTED executable is
                # the proof this program lowers and compiles — repeated
                # preflights (a caller running --verify before real work)
                # cost one listdir per program instead of a lowering
                entry["warm"] = True
                entry["lowered"] = True
                if compile_step:
                    entry["compiled"] = True
                continue
            try:
                lowered = thunk(env)
                entry["lowered"] = True
                try:
                    # the cost-ledger figures (ISSUE 15) ride the
                    # inventory too: the warm-start program list doubles
                    # as the fleet's per-program price sheet
                    entry["cost"] = _lowered_cost(lowered)
                except Exception as e:
                    entry["cost_error"] = f"{type(e).__name__}: {e}"[:120]
                if compile_step:
                    compiled = lowered.compile()
                    entry["compiled"] = True
                    if store is not None:
                        try:
                            entry["published_gen"] = store.publish(
                                ident, compiled, sample_fp
                            )
                        except Exception as e:
                            # the plan must come out even when the store
                            # is down; warm() surfaces these per-entry
                            entry["publish_error"] = (
                                f"{type(e).__name__}: {e}"[:200]
                            )
                    try:
                        entry["collectives"] = _collective_counts(
                            compiled.as_text()
                        )
                    except Exception as e:
                        entry["collectives_error"] = (
                            f"{type(e).__name__}: {e}"[:120]
                        )
                    try:
                        ma = compiled.memory_analysis()
                        if ma is not None:
                            v = getattr(
                                ma, "generated_code_size_in_bytes", None
                            )
                            if v is not None:
                                entry["generated_code_size_in_bytes"] = (
                                    int(v)
                                )
                    except Exception:
                        pass
            except Exception as e:  # surface, never crash the inventory
                entry["error"] = f"{type(e).__name__}: {e}"[:200]
    return {
        "config": model_cfg.name,
        "qmode": qmode,
        "tp": tp,
        "slots": slots,
        "chunk": chunk,
        "prefill_buckets": list(prefill_buckets),
        "prefill_chunk_aligned": pchunk,
        "spec_depth": int(spec_depth),
        "n_programs": len(programs),
        "programs": programs,
    }


def warm(
    model_cfg,
    store,
    slots: int = 8,
    chunk: int = 16,
    *,
    prefill_buckets,
    prefill_chunk: int,
    qmode: str = "off",
    tp: int = 0,
    spec_depth: int = 0,
    sample=None,
) -> Dict[str, Any]:
    """Serialize the whole :func:`decode_plan` universe of one footprint
    into ``store`` (ROADMAP item 1's publish half): compile every
    program a replica of this shape runs and publish each executable
    under its content address. Idempotent and cheap to re-run — a
    program already committed short-circuits on the content hash
    without lowering. Returns the plan report with warm-path summary
    fields (``warmed`` fresh publishes, ``already_warm``
    short-circuits, ``publish_errors``). ``sample`` must be the
    SampleConfig replicas will serve with (see :func:`decode_plan`) —
    a warm under the wrong sampling statics publishes executables no
    lookup ever addresses."""
    report = decode_plan(
        model_cfg, slots=slots, chunk=chunk,
        prefill_buckets=prefill_buckets, prefill_chunk=prefill_chunk,
        qmode=qmode, tp=tp, spec_depth=spec_depth,
        compile_step=True, store=store, sample=sample,
    )
    progs = report.get("programs", ())
    report["warmed"] = sum(
        1 for p in progs if p.get("published_gen") is not None
    )
    report["already_warm"] = sum(1 for p in progs if p.get("warm"))
    report["publish_errors"] = [
        p["publish_error"] for p in progs if p.get("publish_error")
    ]
    return report


def verify_decode_plan(report: Dict[str, Any]) -> list:
    """Diff a :func:`decode_plan` report against the DECLARED universe
    (``analysis/programs.py`` — ``expected_decode_universe`` reproduces
    the plan from each decode row's ``plan`` applicability). Returns
    human-readable mismatch strings, empty when plan == declarations —
    the ``--decode --verify`` gate Tier E's plan-drift rule mirrors."""
    from orion_tpu.analysis import programs as _decls
    from orion_tpu.analysis.program_audit import _ident

    expected = _decls.expected_decode_universe(
        slots=report["slots"], chunk=report["chunk"],
        prefill_buckets=tuple(report.get("prefill_buckets", ())),
        prefill_chunk=report.get("prefill_chunk_aligned", 0),
        qmode=report["qmode"], tp=report["tp"],
        spec_depth=report.get("spec_depth", 0),
    )
    inv = {_ident(p) for p in report.get("programs", ())}
    exp = {_ident(e) for e in expected}
    msgs = [
        f"declared program missing from plan: {dict(k)!r}"
        for k in sorted(exp - inv)
    ] + [
        f"planned program not in declared universe: {dict(k)!r}"
        for k in sorted(inv - exp)
    ]
    msgs += [
        f"planned program fails to lower: {p.get('kind')}: {p['error']}"
        for p in report.get("programs", ()) if p.get("error")
    ]
    return msgs


def main(argv=None) -> int:
    p = argparse.ArgumentParser("orion_tpu.aot")
    p.add_argument("cmd", nargs="?", choices=["warm"], default=None,
                   help="warm: compile the --decode universe and publish "
                        "every executable into --exec-dir (implies "
                        "--decode); default: report only")
    p.add_argument("--config", default="hybrid_7b")
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=None,
                   help="default: model max_seq_len")
    p.add_argument("--dp", type=int, default=1)
    p.add_argument("--fsdp", type=int, default=1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1)
    p.add_argument("--ep", type=int, default=1)
    p.add_argument("--optimizer", default="adamw")
    p.add_argument("--lower-only", action="store_true",
                   help="skip XLA compilation (faster; no memory analysis)")
    p.add_argument("--force-cpu-devices", type=int, default=0,
                   help="plan on N virtual CPU devices instead of real chips")
    p.add_argument("--topology", default="",
                   help="plan against a named TPU topology's real compiler "
                        "without hardware, e.g. v5e:2x4 (overrides "
                        "--force-cpu-devices)")
    p.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                   help="ModelConfig override, e.g. --set backend=pallas")
    # -- serving-side inventory (ISSUE 14): the decode/prefill
    # executables a replica of this shape compiles, per
    # (slots, chunk, bucket, qmode, tp) — the warm-start program list
    p.add_argument("--decode", action="store_true",
                   help="plan the batched decode/prefill executables "
                        "instead of the train step (--slots/--chunk/"
                        "--prefill-chunk/--qmode/--spec-depth; --tp is "
                        "the serving mesh footprint)")
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--chunk", type=int, default=16)
    p.add_argument("--prefill-chunk", type=int, default=64)
    p.add_argument("--prefill-buckets", default="pow2",
                   help="bucket spec as in serving (pow2 | a,b,c)")
    p.add_argument("--qmode", default="off", choices=["off", "int8", "int4"])
    p.add_argument("--spec-depth", type=int, default=0)
    p.add_argument("--verify", action="store_true",
                   help="with --decode: assert the plan inventory exactly "
                        "matches the declared program universe "
                        "(analysis/programs.py) — exit 1 on drift")
    p.add_argument("--exec-dir", default="",
                   help="AOT executable store root (serving/exec_store.py): "
                        "`warm` publishes into it; --decode/--verify "
                        "short-circuit per-program on a committed entry")
    p.add_argument("--params-id", default="",
                   help="weights identity for the executable store "
                        "(default: '<config>:ov=<overrides-hash>:seed=0', "
                        "exactly what the serving/fleet CLIs derive for "
                        "seeded-init params — pin it to the CLI-printed id "
                        "when serving a real checkpoint)")
    p.add_argument("--temperature", type=float, default=0.8,
                   help="sampling statics the executables are specialized "
                        "on (jit statics, part of the content address) — "
                        "defaults MATCH the serving/fleet CLI defaults, "
                        "not the SampleConfig dataclass defaults")
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=1.0)
    p.add_argument("--eos", type=int, default=-1,
                   help="eos token id baked into the sampling statics "
                        "(-1 = none, the CLI default without --tokenizer "
                        "--eos)")
    args = p.parse_args(argv)
    if args.cmd == "warm":
        if not args.exec_dir:
            p.error("warm requires --exec-dir")
        args.decode = True

    if args.topology:
        # the topology client compiles for the named TPU target; the DEFAULT
        # backend is only ever touched for small concrete arrays (rng keys),
        # and on this kind of box the default TPU plugin may be busy or
        # absent — keep those on cpu so planning never waits on a chip
        import jax

        jax.config.update("jax_platforms", "cpu")
    elif args.force_cpu_devices:
        import jax

        from orion_tpu.utils.devices import ensure_virtual_devices

        jax.config.update("jax_platforms", "cpu")
        ensure_virtual_devices(args.force_cpu_devices)

    from orion_tpu.models.configs import get_config
    from orion_tpu.parallel.mesh import MeshConfig
    from orion_tpu.training.trainer import TrainConfig

    model = get_config(args.config)
    if args.set:
        from orion_tpu.utils.config import apply_overrides, parse_set_overrides

        model = apply_overrides(model, parse_set_overrides(args.set))
    if args.decode:
        from orion_tpu.serving.batching import parse_buckets

        store = None
        if args.exec_dir:
            # identity must match what a CLI-launched Server derives
            # EXACTLY (params_id|qmode) or warm entries can never hit
            # at serving time. Both serving CLIs always pass an explicit
            # '<config>:ov=<fp>:seed=<seed>' (or ':ckpt=...:step=...')
            # params_id — the config-hash params_identity fallback in
            # Server only applies to embedded use, so default to the
            # CLI-shaped seeded-init id here
            from orion_tpu.serving.exec_store import ExecStore
            from orion_tpu.serving.prefix_store import overrides_fingerprint
            from orion_tpu.utils.config import parse_set_overrides as _pso

            ov = overrides_fingerprint(_pso(args.set) if args.set else {})
            pid = args.params_id or f"{args.config}:ov={ov}:seed=0"
            store = ExecStore(
                args.exec_dir, identity=f"{pid}|{args.qmode}"
            )
        from orion_tpu.generate import SampleConfig

        footprint = dict(
            slots=args.slots,
            chunk=args.chunk,
            prefill_buckets=parse_buckets(
                args.prefill_buckets, model.max_seq_len
            ),
            prefill_chunk=args.prefill_chunk,
            qmode=args.qmode,
            tp=args.tp,
            spec_depth=args.spec_depth,
            # sampling statics ride the content address; defaults track
            # the serving/fleet CLI defaults (temperature 0.8), NOT the
            # dataclass defaults, so default warm hits default serve
            sample=SampleConfig(
                args.temperature, args.top_k, args.top_p,
                eos_token=args.eos,
            ),
        )
        if args.cmd == "warm":
            report = warm(model, store, **footprint)
            print(json.dumps(report))
            for msg in report["publish_errors"]:
                print(f"aot warm: publish failed: {msg}", file=sys.stderr)
            return 1 if report["publish_errors"] else 0
        report = decode_plan(
            model, compile_step=not args.lower_only, store=store,
            **footprint,
        )
        if args.verify:
            mismatches = verify_decode_plan(report)
            report["verified"] = not mismatches
            print(json.dumps(report))
            for m in mismatches:
                print(f"decode-plan verify: {m}", file=sys.stderr)
            return 1 if mismatches else 0
        print(json.dumps(report))
        return 0
    seq_len = args.seq_len or model.max_seq_len
    if seq_len > model.max_seq_len:
        model = dataclasses.replace(model, max_seq_len=seq_len)
    cfg = TrainConfig(
        model=model,
        batch_size=args.batch_size,
        seq_len=seq_len,
        optimizer=args.optimizer,
        mesh=MeshConfig(dp=args.dp, fsdp=args.fsdp, tp=args.tp, sp=args.sp,
                        pp=args.pp, ep=args.ep),
    )
    mesh = topology_mesh(args.topology, cfg.mesh) if args.topology else None
    report = plan(cfg, compile_step=not args.lower_only, mesh=mesh)
    if args.topology:
        report["topology"] = args.topology
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
