"""Tier E declaration: the compile universe is CLOSED (ISSUE 18).

ROADMAP item 1's executable store only works if a scaled-up replica can
download its programs instead of compiling them — which requires that the
set of jit entrypoints is statically known and every static-argument key
space is finite and enumerable. This module is where that claim is made
*as data*, the way ``serving/locks.py`` declares the lock hierarchy for
Tier D: every ``jax.jit`` / ``shard_map`` site in ``generate.py`` /
``serving/`` / ``parallel/`` has a :class:`ProgramDecl` row, every static
parameter draws from a domain named in :data:`FINITE_DOMAINS`, and
``analysis/program_audit.py`` (Tier E, ``--tier programs``) checks the
code against the table — an undeclared jit, an unbounded static key, or
a drifted ``aot.decode_plan`` inventory is a CI finding.

Sections:

- ``decode`` — the serving universe proper: exactly the programs
  ``generate.DECODE_PROGRAMS`` registers and ``aot.decode_plan``
  inventories. Their per-footprint applicability is declared on the row
  (``plan=``) so :func:`expected_decode_universe` can reproduce the plan
  from declarations alone and the plan-drift rule has an independent
  side to diff against.
- ``solo`` — the batch/CLI decode path (``generate()``); not part of a
  serving replica's universe but still registered so a new jit there is
  a conscious act.
- ``setup`` — one-shot construction-time programs (engine row ops,
  quantization): compiled once per process, no per-request key growth.
- ``training`` — the train-side ``shard_map`` launchers; their key
  spaces follow the training config, not serving traffic
  (``keyspace="open"`` with the rationale on the row).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

GENERATE = "orion_tpu/generate.py"
BATCHING = "orion_tpu/serving/batching.py"


@dataclasses.dataclass(frozen=True)
class ProgramDecl:
    """One declared jit/shard_map program.

    ``qualname`` is the jit-wrapper def name for decorated functions, or
    the ENCLOSING def name for bare ``jax.jit(...)`` / ``shard_map(...)``
    call sites (module-level sites use the assignment target name).
    ``static_args`` are the wrapper's static parameter NAMES in
    static_argnums order — the audit cross-checks them against the AST so
    the declaration cannot silently drift. ``plan`` declares the
    program's per-footprint applicability in ``aot.decode_plan``:
    ``always`` / ``per_bucket`` / ``per_bucket_unified`` (one per bucket,
    keyed by the aligned piece width too) / ``spec`` (only with
    spec_depth > 0) / ``never`` (reachable but deliberately unplanned —
    say why in ``note``) / ``unplanned`` (not a decode-section program).
    ``keyspace="open"`` exempts the row from the unbounded-static-key
    rule; the note must say why an unbounded key space is acceptable.
    ``goldens`` are the compile-artifact snapshots whose donation counts
    pin this program's ``donate_argnums``.
    """

    name: str
    module: str
    qualname: str
    section: str  # "decode" | "solo" | "setup" | "training"
    static_args: Tuple[str, ...] = ()
    donate_argnums: Tuple[int, ...] = ()
    keyspace: str = "closed"  # "closed" | "open"
    plan: str = "unplanned"
    goldens: Tuple[str, ...] = ()
    note: str = ""


# Every static-parameter name that is allowed to be a jit key, mapped to
# the finite domain it draws from. A static parameter whose name is NOT
# here must be proven finite by the interprocedural call-site trace
# (config-attribute reads, literals, declared module constants) or it is
# an unbounded-static-key finding.
FINITE_DOMAINS: Dict[str, str] = {
    "model": "the served TransformerLM — one per deployed ModelConfig",
    "sample_cfg": "SampleConfig — a deployment's sampling presets; the "
                  "batched programs take ONE config for all slots, so the "
                  "key space is the preset count, not the request count",
    "sample": "alias of sample_cfg at the public wrappers",
    "n_steps": "the serve chunk knob (ServeConfig.chunk / --chunk); one "
               "value per engine lifetime",
    "chunk": "the serve chunk knob",
    "slots": "the engine slot count — fixed at construction",
    "pchunk": "the aligned in-scan prefill budget (SlotEngine rounds "
              "prefill_chunk up to chunk_align; one value per engine)",
    "prefill_chunk": "the in-scan prefill budget knob",
    "bucket": "a declared prefill bucket width (parse_buckets)",
    "depth": "the speculative-decode depth knob (--spec-depth)",
    "spec_depth": "the speculative-decode depth knob",
}

# Attribute reads rooted at these names classify as finite in the
# call-site trace: they are config/deployment state, not request state.
FINITE_ATTR_BASES = frozenset({
    "self", "cfg", "config", "args", "model_cfg", "serve_cfg", "CFG",
})


_DECODE_STATICS = ("model", "n_steps", "sample_cfg")

PROGRAMS: Tuple[ProgramDecl, ...] = (
    # -- decode: the serving universe (generate.DECODE_PROGRAMS) ----------
    ProgramDecl(
        "decode_batched", GENERATE, "_decode_batched_chunk_jit", "decode",
        static_args=_DECODE_STATICS, plan="always",
        goldens=("decode_batched_tiny", "decode_batched_int8",
                 "decode_batched_int4", "decode_batched_tp2",
                 "decode_batched_tp4"),
    ),
    ProgramDecl(
        "unified_prefill", GENERATE, "_decode_batched_prefill_chunk_jit",
        "decode",
        static_args=("model", "n_steps", "pchunk", "sample_cfg"),
        plan="per_bucket_unified",
        goldens=("decode_batched_prefill_tiny",),
    ),
    ProgramDecl(
        "prefill_piece_donated", GENERATE, "_prefill_piece_donated_jit",
        "decode", static_args=("model", "pchunk", "sample_cfg"),
        donate_argnums=(2,), plan="never",
        note="one slot's prompt piece on a DONATED carry, for an engine "
             "whose decode state does not fit the device twice "
             "(SlotEngine.donate_carry): unified_prefill's stage 1 for a "
             "slot the host chose. The plan, the goldens and the "
             "warm-start store keep the undonated programs; this one is "
             "compiled where it runs",
    ),
    ProgramDecl(
        "decode_scan_donated", GENERATE, "_decode_scan_donated_jit",
        "decode", static_args=_DECODE_STATICS, donate_argnums=(2,),
        plan="never",
        note="the chunk's decode scan on a donated carry (rows still "
             "mid-prompt frozen): the scan carries each mixer's "
             "chunk_split part and reads the rest; see "
             "prefill_piece_donated",
    ),
    ProgramDecl(
        "spec_round", GENERATE, "_decode_batched_spec_round_jit", "decode",
        static_args=("model", "depth", "sample_cfg"), plan="spec",
        goldens=("decode_batched_spec_tiny",),
    ),
    ProgramDecl(
        "prefill_bucketed", GENERATE, "_prefill_carry_bucketed_jit",
        "decode",
        static_args=("model", "sample_cfg"), plan="per_bucket",
        note="the whole-prompt prefill of the ladder's re-prefill rung and "
             "the prefix store's publish, never of admission; a sequence "
             "past the largest bucket pads to max_seq_len, one more key "
             "and not one per length",
    ),
    # -- solo: the batch/CLI decode path ---------------------------------
    ProgramDecl(
        "generate", GENERATE, "_generate_jit", "solo",
        static_args=("model", "max_new_tokens", "sample_cfg"),
        keyspace="open", goldens=("decode_tiny",),
        note="CLI batch generation: max_new_tokens is the invocation's "
             "token budget — one compile per run is the accepted cost; "
             "serving never calls this (the chunked programs exist "
             "precisely to avoid it)",
    ),
    # -- setup: one-shot construction-time programs ----------------------
    ProgramDecl(
        "quantize_decode_params", GENERATE, "quantize_for_decode", "setup",
        note="bare jax.jit over the whole-tree quantization: runs once "
             "per (model, params) at engine construction",
    ),
    ProgramDecl(
        "cast_serving_params", GENERATE, "_cast_leaves_jit", "setup",
        static_args=("model",),
        note="the serving tree's one cast program (serving_params): every "
             "matmul weight held wider than the compute dtype, cast once "
             "at Server construction; never built for a bf16 tree",
    ),
    ProgramDecl(
        "prefill_extend_row", GENERATE, "_prefill_extend_row", "setup",
        static_args=("model", "pchunk"),
        note="never an executable of its own: called only while "
             "unified_prefill is traced, which holds the piece twice "
             "(inline and in its loop) — the inner jit makes that one "
             "trace and one lowering of the model's forward, and XLA "
             "inlines both calls",
    ),
    ProgramDecl("slot_flags", BATCHING, "_slot_flags", "setup",
                note="per-chunk host readback probe; no static args"),
    ProgramDecl("spec_flags", BATCHING, "_spec_flags", "setup",
                note="speculative boundary readback probe; no static args"),
    ProgramDecl("counted_flags", BATCHING, "_counted_flags", "setup",
                note="the readback probe of a model whose MoE layers are one "
                     "chip's share: finite mask, done flags and the "
                     "boundary's row counters in one transfer; no static "
                     "args, one tuple length per engine"),
    ProgramDecl("insert_carry", BATCHING, "_insert_carry", "setup",
                note="row write of a READY carry (session resume, the "
                     "ladder's re-prefill); traced slot index — one "
                     "compile ever per engine shape"),
    ProgramDecl("stage_rows_carry", BATCHING, "_stage_rows_carry",
                "setup", donate_argnums=(0, 1, 2, 3, 4),
                note="in-scan admission staging, up to STAGE_ROWS prompts "
                     "a dispatch; one compile per staged buffer width, "
                     "none per prompt length or row count. The carry and "
                     "the staging vectors are donated: row writes in "
                     "place, never a copy of the whole decode state"),
    ProgramDecl("stage_prefix_carry", BATCHING, "_stage_prefix_carry",
                "setup", donate_argnums=(0, 1, 2, 3, 4),
                note="prefix-cache-hit admission staging; donated like "
                     "stage_rows_carry"),
    ProgramDecl("restart_prefill_row", BATCHING, "_restart_prefill_row",
                "setup",
                note="chaos-ladder rung 2 row rewind; NOT donated: it "
                     "writes into the boundary's snapshot while the "
                     "engine's carry still aliases it (an engine that "
                     "donates its carry has no snapshot and never "
                     "reaches this rung)"),
    ProgramDecl("extract_carry", BATCHING, "_extract_carry", "setup",
                note="durable-session suspend row read"),
    # -- training: shard_map launchers (train-side key spaces) -----------
    ProgramDecl(
        "kernel_shard", "orion_tpu/parallel/kernel_shard.py",
        "shard_map_bh", "training", keyspace="open",
        note="manual bh shard of a Mosaic kernel call: keyed by the "
             "training mesh/config, not serving traffic",
    ),
    ProgramDecl(
        "sp_attention", "orion_tpu/parallel/sequence.py",
        "sp_linear_attention", "training", keyspace="open",
        note="sequence-parallel linear attention launcher (train mesh)",
    ),
    ProgramDecl(
        "ring_attention", "orion_tpu/parallel/ring.py", "ring_attention",
        "training", keyspace="open",
        note="ring attention launcher (train mesh)",
    ),
    ProgramDecl(
        "swa_halo_attention", "orion_tpu/parallel/ring.py",
        "swa_halo_attention", "training", keyspace="open",
        note="swa halo-exchange attention launcher (train mesh)",
    ),
    ProgramDecl(
        "pipeline_apply", "orion_tpu/parallel/pipeline.py",
        "pipeline_apply", "training", keyspace="open",
        note="pipeline-parallel stage launcher (train mesh)",
    ),
)


# The footprints Tier E and ``aot --decode --verify`` check the plan
# against, and the footprints the engine compile-count acceptance test
# drives traffic through (tests/test_aot.py). Values are chosen unique
# across the test suite so global jit-cache deltas are attributable.
# ``expect_programs`` is the DECLARED per-footprint program count —
# :func:`expected_decode_universe` must produce exactly that many rows.
# ``prefill_chunk`` is a multiple of the tiny configs' linear-attention
# chunk (128), so the knob IS the aligned width the plan lists.
CHECK_FOOTPRINTS: Tuple[Dict[str, Any], ...] = (
    {"slots": 3, "chunk": 6, "prefill_buckets": (12,),
     "prefill_chunk": 128, "qmode": "off", "tp": 1, "spec_depth": 0,
     "expect_programs": 3},
    {"slots": 5, "chunk": 7, "prefill_buckets": (12, 24),
     "prefill_chunk": 128, "qmode": "off", "tp": 1, "spec_depth": 0,
     "expect_programs": 5},
)


def expected_decode_universe(
    slots: int,
    chunk: int,
    prefill_buckets,
    prefill_chunk: int,
    qmode: str = "off",
    tp: int = 1,
    spec_depth: int = 0,
    decls=None,
) -> List[Dict[str, Any]]:
    """The program universe a replica of this footprint compiles, computed
    from the DECLARATIONS (each decode row's ``plan`` applicability) —
    the independent side the plan-drift rule and ``aot --verify`` diff
    ``aot.decode_plan``'s inventory against. ``prefill_chunk`` here is
    the ALIGNED pchunk the engine actually compiles (decode_plan reports
    it as ``prefill_chunk_aligned``)."""
    tp = max(int(tp), 1)
    out: List[Dict[str, Any]] = []
    for d in decls if decls is not None else PROGRAMS:
        if d.section != "decode":
            continue
        if d.plan == "always":
            out.append({"kind": d.name, "slots": slots, "chunk": chunk,
                        "qmode": qmode, "tp": tp})
        elif d.plan == "per_bucket_unified":
            for b in prefill_buckets:
                out.append({"kind": d.name, "slots": slots, "chunk": chunk,
                            "bucket": int(b),
                            "prefill_chunk": int(prefill_chunk),
                            "qmode": qmode, "tp": tp})
        elif d.plan == "per_bucket":
            for b in prefill_buckets:
                out.append({"kind": d.name, "bucket": int(b),
                            "qmode": qmode, "tp": tp})
        elif d.plan == "spec" and int(spec_depth) > 0:
            out.append({"kind": d.name, "slots": slots,
                        "spec_depth": int(spec_depth), "qmode": qmode,
                        "tp": tp})
        # "never"/"unplanned": not part of the planned universe
    return out


__all__ = [
    "ProgramDecl", "PROGRAMS", "FINITE_DOMAINS", "FINITE_ATTR_BASES",
    "CHECK_FOOTPRINTS", "expected_decode_universe", "GENERATE", "BATCHING",
]
