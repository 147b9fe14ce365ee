"""Tier E (ISSUE 18): the compile universe is closed — fixture tests.

Every rule gets a positive (seeded violation) and a negative (clean
idiom) fixture, with the declaration table injected so the fixtures
don't depend on the shipped registry; the repo itself must come out
clean against the REAL table. The seeded-regression acceptance case
patches an unregistered jit wrapper into the real serving/batching.py
source and asserts the audit catches it.
"""

import dataclasses
import json
import os

import pytest

from orion_tpu.analysis import programs as P
from orion_tpu.analysis.program_audit import (
    RULE_DONATION,
    RULE_HAZARD,
    RULE_PLAN,
    RULE_UNBOUNDED,
    RULE_UNREGISTERED,
    ProgramTable,
    audit_programs,
    audit_source,
    donation_drift_findings,
    load_program_table,
    plan_drift_findings,
    registry_drift_findings,
)

pytestmark = pytest.mark.analysis

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rule_ids(findings):
    return {f.rule for f in findings}


def _read(rel):
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        return f.read()


def _table(*decls, **kw):
    return ProgramTable(decls, **kw)


def _decl(name, module, qualname, **kw):
    kw.setdefault("section", "decode")
    return P.ProgramDecl(name, module, qualname, **kw)


# ---------------------------------------------------------------------------
# unregistered-jit
# ---------------------------------------------------------------------------


ROGUE_WRAPPER = """

@jax.jit
def _rogue_probe(carry):
    return carry
"""


def test_seeded_unregistered_jit_in_real_batching_source():
    """The acceptance regression: an undeclared jit wrapper added to the
    REAL serving/batching.py is a finding; the shipped source is clean."""
    src = _read("orion_tpu/serving/batching.py")
    assert audit_source(src, "orion_tpu/serving/batching.py") == []
    patched = src + ROGUE_WRAPPER
    found = [
        f for f in audit_source(patched, "orion_tpu/serving/batching.py")
        if f.rule == RULE_UNREGISTERED
    ]
    assert len(found) == 1
    assert "_rogue_probe" in found[0].message
    assert found[0].line > src.count("\n") - 2  # at the appended def


def test_unregistered_bare_jit_and_shard_map_sites():
    bare = """
import jax

def quantize_all(params):
    return jax.jit(lambda p: p)(params)
"""
    assert RULE_UNREGISTERED in rule_ids(
        audit_source(bare, "orion_tpu/serving/batching.py",
                     table=_table())
    )
    sm = """
from jax import shard_map

def my_launcher(f, mesh, specs):
    return shard_map(f, mesh=mesh, in_specs=specs, out_specs=specs)
"""
    assert RULE_UNREGISTERED in rule_ids(
        audit_source(sm, "orion_tpu/parallel/custom.py", table=_table())
    )
    # the same sites declared (by enclosing-def qualname) are clean
    t = _table(
        _decl("quantize_all", "orion_tpu/serving/batching.py",
              "quantize_all", section="setup"),
        _decl("my_launcher", "orion_tpu/parallel/custom.py",
              "my_launcher", section="training", keyspace="open"),
    )
    assert audit_source(bare, "orion_tpu/serving/batching.py",
                        table=t) == []
    assert audit_source(sm, "orion_tpu/parallel/custom.py", table=t) == []


def test_unregistered_exempts_tests_and_honors_noqa():
    src = """
import jax

@jax.jit
def _rogue(x):  # orion: noqa[unregistered-jit]
    return x
"""
    assert audit_source(src, "orion_tpu/serving/batching.py",
                        table=_table()) == []
    unsuppressed = src.replace("  # orion: noqa[unregistered-jit]", "")
    assert RULE_UNREGISTERED in rule_ids(audit_source(
        unsuppressed, "orion_tpu/serving/batching.py", table=_table()
    ))
    assert audit_source(
        unsuppressed, "tests/test_dummy.py", table=_table()
    ) == []


# ---------------------------------------------------------------------------
# unbounded-static-key
# ---------------------------------------------------------------------------


TWO_HOP = """
import jax
from functools import partial

@partial(jax.jit, static_argnums=(1,))
def _work_jit(x, mode):
    return x

def middle(x, mode):
    return _work_jit(x, mode)

def outer(x, {param}):
    return middle(x, {value})
"""

_WORK_DECL = _decl("work", "orion_tpu/serving/sched.py", "_work_jit",
                   static_args=("mode",))


def test_unbounded_static_key_two_hop_interprocedural():
    """The static arg's value is traced TWO same-module hops to the
    outermost call site: request-derived there is a finding, a
    config-attribute read is not."""
    t = _table(_WORK_DECL)
    bad = TWO_HOP.format(param="request", value="request.n_tokens")
    found = [
        f for f in audit_source(bad, "orion_tpu/serving/sched.py", table=t)
        if f.rule == RULE_UNBOUNDED
    ]
    assert found and "mode" in found[0].message
    clean = TWO_HOP.format(param="cfg", value="cfg.chunk")
    assert audit_source(clean, "orion_tpu/serving/sched.py", table=t) == []


def test_unbounded_static_key_declared_domain_and_open_keyspace():
    t = _table(_WORK_DECL)
    # a declared finite-domain name passes without any trace
    domain = TWO_HOP.format(param="chunk", value="chunk")
    assert audit_source(domain, "orion_tpu/serving/sched.py", table=t) == []
    # keyspace="open" exempts the whole row (the solo-generate idiom)
    t_open = _table(dataclasses.replace(_WORK_DECL, keyspace="open"))
    bad = TWO_HOP.format(param="request", value="request.n_tokens")
    assert audit_source(
        bad, "orion_tpu/serving/sched.py", table=t_open
    ) == []


def test_static_signature_drift_is_a_finding():
    """The declaration's static_args must match the decorator's AST in
    name and order — a silent drift would let the key-space claim rot."""
    src = TWO_HOP.format(param="cfg", value="cfg.chunk")
    drifted = _table(
        dataclasses.replace(_WORK_DECL, static_args=("mode", "extra"))
    )
    found = [
        f for f in audit_source(
            src, "orion_tpu/serving/sched.py", table=drifted
        )
        if f.rule == RULE_UNBOUNDED
    ]
    assert found and "drifted" in found[0].message


# ---------------------------------------------------------------------------
# recompile-hazard
# ---------------------------------------------------------------------------


def test_hazard_closure_captured_array():
    bad = """
import jax
import jax.numpy as jnp

_LUT = jnp.arange(16)

@jax.jit
def _lookup_jit(i):
    return _LUT[i]
"""
    t = _table(_decl("lookup", "orion_tpu/serving/sched.py",
                     "_lookup_jit", section="setup"))
    found = audit_source(bad, "orion_tpu/serving/sched.py", table=t)
    assert RULE_HAZARD in rule_ids(found)
    clean = """
import jax
import jax.numpy as jnp

_LUT = jnp.arange(16)

@jax.jit
def _lookup_jit(lut, i):
    return lut[i]

def use(i):
    return _lookup_jit(_LUT, i)
"""
    assert audit_source(clean, "orion_tpu/serving/sched.py", table=t) == []


SCALE = """
import jax
from functools import partial

@partial(jax.jit, static_argnums=(1,))
def _scale_jit(x, factor):
    return x

def run(x, cfg, chunk):
    return _scale_jit(x, {arg})
"""

_SCALE_T = _table(_decl("scale", "orion_tpu/serving/sched.py",
                        "_scale_jit", static_args=("factor",)))


def test_hazard_float_literal_static_key():
    found = audit_source(SCALE.format(arg="0.5"),
                         "orion_tpu/serving/sched.py", table=_SCALE_T)
    assert RULE_HAZARD in rule_ids(found)
    assert audit_source(SCALE.format(arg="chunk"),
                        "orion_tpu/serving/sched.py",
                        table=_SCALE_T) == []


def test_hazard_dict_iteration_static_key():
    found = audit_source(SCALE.format(arg="tuple(cfg.qmap.keys())"),
                         "orion_tpu/serving/sched.py", table=_SCALE_T)
    assert RULE_HAZARD in rule_ids(found)
    # sorted-into-tuple off a config attribute is the sanctioned shape
    assert audit_source(SCALE.format(arg="tuple(sorted(cfg.qmap))"),
                        "orion_tpu/serving/sched.py",
                        table=_SCALE_T) == []


def test_hazard_partial_rewrap_of_registered_jit():
    src = """
from functools import partial

from orion_tpu.generate import _decode_batched_chunk_jit

def rebind(model):
    return partial(_decode_batched_chunk_jit, model)
"""
    found = audit_source(src, "orion_tpu/serving/sched.py")
    assert rule_ids(found) == {RULE_HAZARD}
    assert "_decode_batched_chunk_jit" in found[0].message
    # a MODULE-level partial is one object with one cache — not a hazard;
    # and re-wrapping an unregistered name is not this rule's business
    module_level = """
from functools import partial

from orion_tpu.generate import _decode_batched_chunk_jit

bound = partial(_decode_batched_chunk_jit, None)
"""
    assert audit_source(module_level, "orion_tpu/serving/sched.py") == []
    other = """
from functools import partial

def rebind(fn, model):
    return partial(some_plain_helper, model)
"""
    assert audit_source(other, "orion_tpu/serving/sched.py") == []


# ---------------------------------------------------------------------------
# plan-drift
# ---------------------------------------------------------------------------


def _fp_args(fp):
    return {k: v for k, v in fp.items() if k != "expect_programs"}


def _faithful_inventory(fp):
    return {
        "prefill_chunk_aligned": fp.get("prefill_chunk", 0),
        "programs": P.expected_decode_universe(**_fp_args(fp)),
    }


def test_plan_drift_clean_against_faithful_inventory():
    assert plan_drift_findings(inventory_fn=_faithful_inventory) == []


def test_plan_drift_catches_stale_decode_plan():
    """A deliberately stale plan — one declared program missing, one
    phantom listed — produces one finding per direction."""
    def stale(fp):
        rep = _faithful_inventory(fp)
        rep["programs"] = rep["programs"][1:] + [
            {"kind": "phantom_warmup", "slots": fp["slots"], "qmode": "off",
             "tp": 1}
        ]
        return rep

    found = plan_drift_findings(
        footprints=P.CHECK_FOOTPRINTS[:1], inventory_fn=stale
    )
    assert rule_ids(found) == {RULE_PLAN}
    msgs = " | ".join(f.message for f in found)
    assert "missing from decode_plan" in msgs
    assert "outside the declared universe" in msgs


def test_plan_drift_checks_declared_program_count():
    doctored = ({**P.CHECK_FOOTPRINTS[0], "expect_programs": 99},)
    found = plan_drift_findings(
        footprints=doctored, inventory_fn=_faithful_inventory
    )
    assert any("99" in f.message for f in found)


def test_plan_drift_surfaces_inventory_crash_as_finding():
    def boom(fp):
        raise RuntimeError("no backend")

    found = plan_drift_findings(
        footprints=P.CHECK_FOOTPRINTS[:1], inventory_fn=boom
    )
    assert rule_ids(found) == {RULE_PLAN}
    assert "decode_plan failed" in found[0].message


def test_registry_drift_both_directions():
    assert registry_drift_findings() == []
    # a DECODE_PROGRAMS entry with no declaration
    missing = _table(*[d for d in P.PROGRAMS if d.name != "spec_round"])
    found = registry_drift_findings(missing)
    assert rule_ids(found) == {RULE_PLAN}
    assert any("spec_round" in f.message for f in found)
    # a declared decode program missing from DECODE_PROGRAMS
    phantom = _table(*P.PROGRAMS,
                     _decl("phantom_kind", P.GENERATE, "_phantom_jit"))
    found = registry_drift_findings(phantom)
    assert any("phantom_kind" in f.message for f in found)
    # the registry must map the declared name to the declared wrapper
    wrong = _table(*[
        dataclasses.replace(d, qualname="_other_jit")
        if d.name == "decode_batched" else d
        for d in P.PROGRAMS
    ])
    found = registry_drift_findings(wrong)
    assert any("_other_jit" in f.message for f in found)


def test_every_decode_programs_entry_is_declared_and_identical():
    """Meta-test: the declared registry and the live DECODE_PROGRAMS dict
    are the SAME objects, name for name — the static audit's universe is
    the one the engine actually dispatches."""
    import orion_tpu.generate as G

    declared = {d.name: d for d in P.PROGRAMS if d.section == "decode"}
    assert set(G.DECODE_PROGRAMS) == set(declared)
    for name, fn in G.DECODE_PROGRAMS.items():
        assert getattr(G, declared[name].qualname) is fn, name


# ---------------------------------------------------------------------------
# donation-drift
# ---------------------------------------------------------------------------


_DB_DECL = next(d for d in P.PROGRAMS if d.name == "decode_batched")


def test_donation_drift_golden_directions(tmp_path):
    decl = dataclasses.replace(_DB_DECL, goldens=("decode_batched_tiny",))
    t = _table(decl)
    golden = tmp_path / "decode_batched_tiny.json"
    golden.write_text(json.dumps(
        {"donation": {"aliased": 0, "donated_args": 0}}
    ))
    assert donation_drift_findings(t, golden_dir=str(tmp_path)) == []
    # golden records donation the declaration doesn't claim -> drift
    golden.write_text(json.dumps(
        {"donation": {"aliased": 2, "donated_args": 2}}
    ))
    found = donation_drift_findings(t, golden_dir=str(tmp_path))
    assert rule_ids(found) == {RULE_DONATION}
    # a missing golden mutes the pin -> itself a finding
    golden.unlink()
    found = donation_drift_findings(t, golden_dir=str(tmp_path))
    assert rule_ids(found) == {RULE_DONATION}
    assert "missing" in found[0].message


def test_donation_drift_ast_vs_declaration():
    # the real wrapper donates nothing; a declaration claiming (1,) drifts
    drifted = _table(dataclasses.replace(
        _DB_DECL, donate_argnums=(1,), goldens=()
    ))
    found = donation_drift_findings(drifted)
    assert rule_ids(found) == {RULE_DONATION}
    assert "_decode_batched_chunk_jit" in found[0].message
    honest = _table(dataclasses.replace(_DB_DECL, goldens=()))
    assert donation_drift_findings(honest) == []


# ---------------------------------------------------------------------------
# the repo itself is the negative case
# ---------------------------------------------------------------------------


def test_repo_program_audit_clean():
    """Tier E over the real tree (lowering skipped — the lowered pass
    rides the CLI budget test in test_analysis.py): zero findings, none
    baselined away."""
    findings = audit_programs(lower=False)
    assert findings == [], "\n".join(f.format() for f in findings)


def test_real_table_covers_every_tier_e_module_site():
    """Every jit/shard_map site the model extracts from the audited
    packages resolves to a ProgramDecl — and the declared static names
    match the decorators (both already implied by the clean audit, but
    pinned here structurally so a scope change can't silently narrow
    the audit)."""
    from orion_tpu.analysis.lint import ModuleContext
    from orion_tpu.analysis.program_audit import (
        TIER_E_PATHS, ProgramModel,
    )

    table = load_program_table()
    sites = 0
    for rel in TIER_E_PATHS:
        full = os.path.join(REPO, rel)
        from orion_tpu.analysis.lint import iter_py_files

        for path in iter_py_files([full]):
            ctx = ModuleContext(_read(os.path.relpath(path, REPO)),
                                path, REPO)
            m = ProgramModel(ctx, table)
            for fn, _ in m.jit_defs:
                assert table.decl_at(ctx.path, fn.name), (ctx.path, fn.name)
                sites += 1
            for call, qual in m.bare_sites:
                assert table.decl_at(ctx.path, qual), (ctx.path, qual)
                sites += 1
    # generate.py's 7 wrappers + batching's 7 helpers + the quantize site
    # + the parallel shard_map launchers: the scope has real teeth
    assert sites >= 18, sites
