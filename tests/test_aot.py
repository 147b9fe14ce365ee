"""AOT planning tests (SURVEY.md M4 buildability / VERDICT r1 item 8): the
7B hybrid config must lower with the sharding rules applied, and a scaled
hybrid must compile end-to-end with GSPMD collectives in the optimized HLO.
"""

import dataclasses

import pytest

from orion_tpu.aot import plan
from orion_tpu.models.configs import get_config, hybrid_pattern, ModelConfig
from orion_tpu.parallel.mesh import MeshConfig
from orion_tpu.training.trainer import TrainConfig


def test_hybrid_7b_lowers_sharded():
    """The flagship stretch config: full train step lowers against abstract
    fsdp4/tp2-sharded state; per-device state fits a 16GB chip."""
    model = get_config("hybrid_7b")
    cfg = TrainConfig(
        model=model,
        batch_size=16,
        seq_len=model.max_seq_len,
        mesh=MeshConfig(dp=1, fsdp=4, tp=2),
    )
    rep = plan(cfg, compile_step=False)
    assert rep["lowered"]
    assert 6.0e9 < rep["n_params"] < 7.5e9, rep["n_params"]
    # adamw fp32: params + 2 moments + grads transient; the sharded resident
    # state must fit a 16GB device
    assert rep["state_bytes_per_device"] < 16e9, rep
    # fsdp/tp actually shard ~everything: per-device param bytes well under
    # half the replicated 26.5GB
    assert rep["param_bytes_per_device"] < 4e9, rep


def test_decode_plan_inventories_serving_programs():
    """ISSUE 14 satellite: ``aot.decode_plan`` lists EVERY executable a
    replica of a given shape compiles — the batched decode per
    (slots, chunk, qmode, tp), the unified prefill and the whole-prompt
    bucketed prefill per bucket, the spec round per depth — the complete
    inventory ROADMAP item 4's warm-start persistence needs. Lower-only
    keeps the test cheap; the compiled/collectives path is covered by
    the tp goldens and the CLI smoke."""
    from orion_tpu.aot import decode_plan

    cfg = get_config("tiny")
    rep = decode_plan(
        cfg, slots=4, chunk=8, prefill_buckets=(16, 32),
        prefill_chunk=16, qmode="int8", spec_depth=2, compile_step=False,
    )
    kinds = [(p["kind"], p.get("bucket")) for p in rep["programs"]]
    assert kinds == [
        ("decode_batched", None),
        ("unified_prefill", 16), ("prefill_bucketed", 16),
        ("unified_prefill", 32), ("prefill_bucketed", 32),
        ("spec_round", None),
    ]
    assert all(p.get("lowered") for p in rep["programs"]), rep["programs"]
    assert rep["qmode"] == "int8" and rep["tp"] == 1
    assert {p["qmode"] for p in rep["programs"]} == {"int8"}
    # tp rides every program key: the warm-start cache must never hand a
    # tp=2 replica an unsharded executable
    assert {p["tp"] for p in rep["programs"]} == {1}
    # the inventory lists the pchunk the ENGINE compiles, not the raw
    # knob: SlotEngine rounds prefill_chunk up to the linear-attention
    # chunk alignment; and a footprint no engine can have (no in-scan
    # prefill, no buckets) is refused — phantom entries would defeat the
    # "runs precisely these executables" warm-start contract
    from orion_tpu.ops.dispatch import resolve, resolve_chunk

    align = resolve_chunk(cfg.chunk, cfg.max_seq_len, resolve(cfg.backend))
    rep2 = decode_plan(
        cfg, slots=4, chunk=8, prefill_buckets=(32,),
        prefill_chunk=align + 1, compile_step=False,
    )
    uni = [p for p in rep2["programs"] if p["kind"] == "unified_prefill"]
    assert [p["prefill_chunk"] for p in uni] == [2 * align], uni
    with pytest.raises(ValueError, match="no engine has this footprint"):
        decode_plan(cfg, slots=4, chunk=8, lower=False, prefill_buckets=(),
                    prefill_chunk=16)


def _topo_mesh_or_skip(mc):
    from orion_tpu.aot import topology_mesh

    try:
        return topology_mesh("v5e:2x4", mc)
    except (RuntimeError, ValueError) as e:
        # skip ONLY for a genuinely absent TPU toolchain — a regression
        # inside topology_mesh/make_mesh must FAIL, not silently skip the
        # sole coverage of the mosaic_kernels>0 guarantee
        msg = str(e).lower()
        if any(w in msg for w in ("topolog", "plugin", "tpu", "pjrt")):
            pytest.skip(f"tpu topology unavailable: {e}")
        raise


@pytest.mark.slow
def test_topology_aot_pallas_dense_gspmd():
    """The REAL TPU compiler (Mosaic) accepts the Pallas kernels on a plain
    GSPMD data/tensor mesh: XLA cannot auto-partition tpu_custom_call, so
    parallel/kernel_shard.py manualizes them over ALL mesh axes (partial-
    manual regions are rejected outright). mosaic_kernels > 0 proves the
    kernels are in the compiled HLO rather than silently falling back."""
    mc = MeshConfig(dp=2, fsdp=2, tp=2)
    mesh = _topo_mesh_or_skip(mc)
    model = ModelConfig(
        name="dense_pallas", vocab_size=512, d_model=256, n_layers=4,
        n_heads=4, layer_types=hybrid_pattern(4, period=2), window=256,
        max_seq_len=1024, dtype="bfloat16", backend="pallas", remat=True,
    )
    cfg = TrainConfig(model=model, batch_size=8, seq_len=1024, mesh=mc)
    rep = plan(cfg, compile_step=True, mesh=mesh)
    assert rep["compiled"]
    cc = rep["collectives"]
    assert cc["mosaic_kernels"] > 0, cc
    assert cc["all-reduce"] > 0, cc  # tp psums / grad reductions


@pytest.mark.slow
def test_topology_aot_pallas_under_sp():
    """Mosaic kernels under sequence parallelism (VERDICT r2 #8):
    sequence.py / ring.py shard_maps are fully manual (axis_names
    defaulted), so the fused-parts linear kernel, the striped ring's
    flash blocks, and the halo swa blocks all compile through the real
    TPU compiler on a token-sharded mesh. (The pp and pp×sp compositions
    are covered by the full-manual pipeline tests below.)"""
    mc = MeshConfig(dp=2, sp=4)
    mesh = _topo_mesh_or_skip(mc)
    # softmax layer: the STRIPED ring with flash-kernel blocks + lse merge;
    # linear layers: the fused-parts sp kernel; the swa layer rides the
    # contiguous (xla-body) windowed ring — keeping its sp lowering covered
    model = ModelConfig(
        name="sp_pallas", vocab_size=512, d_model=256, n_layers=4,
        n_heads=4, layer_types=("softmax", "linear", "swa", "linear"),
        window=256, max_seq_len=1024, dtype="bfloat16", backend="pallas",
        remat=True, sequence_parallel=True, ring_striped=True,
    )
    cfg = TrainConfig(model=model, batch_size=4, seq_len=1024, mesh=mc)
    rep = plan(cfg, compile_step=True, mesh=mesh)
    assert rep["compiled"]
    cc = rep["collectives"]
    assert cc["mosaic_kernels"] > 0, cc
    assert cc["collective-permute"] > 0, cc  # sp state prefix / ring hops
    assert cc["all-to-all"] > 0, cc  # the striped layout exchange


@pytest.mark.slow
def test_topology_aot_pallas_under_pp_full_manual():
    """Mosaic kernels INSIDE the pipeline: the full_manual pipeline makes
    every mesh axis manual (jax rejects tpu_custom_call in partial-manual
    regions), so a backend=pallas model keeps its kernels through a
    dp4×pp2 train step compiled by the real TPU compiler (auto-enabled:
    fsdp>1 is excluded from auto because full_manual gathers the whole
    stage's params up front — pp_full_manual=True opts in explicitly).
    Semantics of the same region are pinned by test_pp_full_manual_parity
    on the virtual mesh."""
    mc = MeshConfig(dp=4, pp=2)
    mesh = _topo_mesh_or_skip(mc)
    model = ModelConfig(
        name="pp_pallas", vocab_size=512, d_model=256, n_layers=4,
        n_heads=4, max_seq_len=1024, dtype="bfloat16", backend="pallas",
        remat=True,
    )
    cfg = TrainConfig(
        model=model, batch_size=8, seq_len=1024, mesh=mc, pp_microbatches=2,
    )
    rep = plan(cfg, compile_step=True, mesh=mesh)
    assert rep["compiled"]
    cc = rep["collectives"]
    assert cc["mosaic_kernels"] > 0, cc  # kernels survived INSIDE pp
    assert cc["collective-permute"] > 0, cc  # the activation ring


@pytest.mark.slow
def test_topology_aot_pallas_under_pp_sp():
    """The pp×sp composition with kernels — sp_local_kernels inside the
    full_manual pipeline: linear layers run the fused-parts sp kernel,
    swa layers the halo flash blocks, all inside the pipeline's manual
    region, compiled by the real TPU compiler."""
    mc = MeshConfig(dp=2, pp=2, sp=2)
    mesh = _topo_mesh_or_skip(mc)
    # all three sp-local kernel forms inside the pipeline: fused-parts
    # linear, halo swa, and the striped ring's flash blocks (softmax +
    # ring_striped); pattern period 4 over 8 layers -> 2 pp stage groups
    model = ModelConfig(
        name="ppsp_pallas", vocab_size=512, d_model=256, n_layers=8,
        n_heads=4, layer_types=("linear", "swa", "softmax", "linear") * 2,
        window=256, max_seq_len=1024, dtype="bfloat16", backend="pallas",
        remat=True, sequence_parallel=True, ring_striped=True,
    )
    cfg = TrainConfig(
        model=model, batch_size=8, seq_len=1024, mesh=mc, pp_microbatches=2,
    )
    rep = plan(cfg, compile_step=True, mesh=mesh)
    assert rep["compiled"]
    cc = rep["collectives"]
    assert cc["mosaic_kernels"] > 0, cc  # kernels inside pp×sp
    assert cc["collective-permute"] > 0, cc  # pp ring + sp hops


def test_scaled_hybrid_compiles_with_collectives():
    """A 1/16-width 7B (same layer pattern, same sharding rules) compiles
    through GSPMD on the virtual mesh and the optimized HLO contains the
    fsdp/tp collectives — proof the rules engaged rather than replicating."""
    model = ModelConfig(
        name="hybrid_scaled",
        vocab_size=512,
        d_model=256,
        n_layers=8,
        n_heads=8,
        layer_types=hybrid_pattern(8, period=4),
        window=64,
        max_seq_len=256,
        dtype="float32",
        backend="xla",
        remat=True,
    )
    cfg = TrainConfig(
        model=model,
        batch_size=4,
        seq_len=128,
        mesh=MeshConfig(dp=1, fsdp=2, tp=2),
    )
    rep = plan(cfg, compile_step=True)
    assert rep["compiled"]
    cc = rep["collectives"]
    assert cc["all-gather"] > 0, cc  # fsdp param gathers
    assert cc["all-reduce"] > 0, cc  # tp psums / grad reductions


@pytest.mark.slow
def test_topology_aot_sp_fused_ce():
    """Fused CE inside the sp-manual region (ops/fused_ce.py::_sp_fused_ce)
    compiles through the real TPU compiler on an sp mesh with Mosaic
    kernels intact, and the Trainer keeps remat_skip under sp (r3 VERDICT
    #2). The committed SP64K_AOT.json is the same path at lm_1b3 scale:
    T=65,536 dp1xsp8, fitting (state 5.66GB + temp 4.39GB < 16GB/device,
    92 Mosaic kernels)."""
    mc = MeshConfig(dp=1, sp=8)
    mesh = _topo_mesh_or_skip(mc)
    model = ModelConfig(
        name="sp_fused_ce", vocab_size=512, d_model=256, n_layers=4,
        n_heads=4, max_seq_len=4096, dtype="bfloat16", backend="pallas",
        remat=True, remat_skip=1, sequence_parallel=True,
    )
    cfg = TrainConfig(
        model=model, batch_size=2, seq_len=4096, mesh=mc,
        optimizer="adafactor",
    )
    from orion_tpu.training.trainer import Trainer

    tr = Trainer(cfg, mesh=mesh, materialize=False)
    assert tr.model.cfg.remat_skip == 1  # the sp zeroing is gone
    rep = plan(cfg, compile_step=True, mesh=mesh)
    assert rep["compiled"]
    cc = rep["collectives"]
    assert cc["mosaic_kernels"] > 0, cc


# ---------------------------------------------------------------------------
# ISSUE 18: decode_plan vs the DECLARED universe (analysis/programs.py)
# ---------------------------------------------------------------------------


def _fp_kwargs(fp):
    return {k: v for k, v in fp.items() if k != "expect_programs"}


def test_decode_plan_pure_inventory_matches_declared_universe():
    """``lower=False`` returns the identity-only inventory — no jax work
    at all — and it equals the universe computed from the declarations,
    for every pinned check footprint."""
    from orion_tpu.aot import decode_plan, verify_decode_plan
    from orion_tpu.analysis import programs as P

    cfg = get_config("tiny")
    for fp in P.CHECK_FOOTPRINTS:
        rep = decode_plan(cfg, compile_step=False, lower=False,
                          **_fp_kwargs(fp))
        assert len(rep["programs"]) == fp["expect_programs"]
        assert not any("lowered" in p for p in rep["programs"])
        assert verify_decode_plan(rep) == []
        expected = P.expected_decode_universe(**_fp_kwargs(fp))
        assert (
            {tuple(sorted(p.items())) for p in rep["programs"]}
            == {tuple(sorted(e.items())) for e in expected}
        ), (rep["programs"], expected)


def test_decode_cli_verify_gate_for_check_footprints(capsys):
    """Acceptance: ``aot --decode --verify`` passes (exit 0, every
    program lowered, verified flag set). The CLI lowers one footprint
    end-to-end; both footprints' universe equality is covered lower-free
    by test_decode_plan_pure_inventory_matches_declared_universe."""
    import json

    from orion_tpu.aot import main as aot_main
    from orion_tpu.analysis import programs as P

    for fp in P.CHECK_FOOTPRINTS[:1]:
        argv = [
            "--config", "tiny", "--decode", "--lower-only", "--verify",
            "--slots", str(fp["slots"]), "--chunk", str(fp["chunk"]),
            "--prefill-buckets",
            ",".join(str(b) for b in fp["prefill_buckets"]),
            "--prefill-chunk", str(fp["prefill_chunk"]),
            "--qmode", fp["qmode"], "--spec-depth", str(fp["spec_depth"]),
        ]
        rc = aot_main(argv)
        out = capsys.readouterr()
        assert rc == 0, out.err
        doc = json.loads(out.out)
        assert doc["verified"] is True
        assert len(doc["programs"]) == fp["expect_programs"]
        assert all(p.get("lowered") for p in doc["programs"]), doc


def test_verify_decode_plan_reports_drift():
    """Doctored reports drift in every direction verify must catch."""
    from orion_tpu.aot import decode_plan, verify_decode_plan
    from orion_tpu.analysis import programs as P

    cfg = get_config("tiny")
    fp = _fp_kwargs(P.CHECK_FOOTPRINTS[1])
    rep = decode_plan(cfg, compile_step=False, lower=False, **fp)

    dropped = dict(rep, programs=rep["programs"][:-1])
    assert any("missing from plan" in m
               for m in verify_decode_plan(dropped))

    phantom = dict(rep, programs=rep["programs"] + [
        {"kind": "phantom_warmup", "slots": fp["slots"], "qmode": "off",
         "tp": 1}
    ])
    assert any("not in declared universe" in m
               for m in verify_decode_plan(phantom))

    broken = dict(rep, programs=[
        dict(rep["programs"][0], error="lowering exploded")
    ])
    assert any("fails to lower" in m for m in verify_decode_plan(broken))


def test_engine_lifetime_compile_count_matches_plan_prediction():
    """Acceptance: a replica's MEASURED lifetime compile count equals the
    plan's prediction — cache-stat deltas on the real jit wrappers while
    a fresh engine serves, for every declared bucket, one request whose
    staged prompt has that width and whose ladder re-prefills it at that
    width (the whole-prompt program runs on the repair path alone), with
    a repeat hit proving bucket reuse does not recompile."""
    from collections import Counter

    import jax
    import jax.numpy as jnp

    from orion_tpu.aot import decode_plan
    from orion_tpu.analysis import programs as P
    from orion_tpu.generate import SampleConfig, DECODE_PROGRAMS
    from orion_tpu.models.transformer import TransformerLM
    from orion_tpu.resilience import inject
    from orion_tpu.serving import DecodeRequest
    from orion_tpu.serving.batching import SlotEngine

    # the smallest model that exercises the real wrappers: cache COUNTS
    # are what's asserted, so one linear layer keeps the compiles this
    # test pays as cheap as they get
    cfg = ModelConfig(
        name="aot_engine_test", vocab_size=32, d_model=16, n_layers=1,
        n_heads=2, layer_types=("linear",), window=4,
        max_seq_len=64, dtype="float32", backend="xla",
    )
    greedy = SampleConfig(temperature=0.0)
    counted = ("decode_batched", "unified_prefill", "prefill_bucketed")

    def sizes():
        return Counter({k: DECODE_PROGRAMS[k]._cache_size() for k in counted})

    for fp in P.CHECK_FOOTPRINTS:
        plan_kinds = Counter(
            p["kind"] for p in decode_plan(
                cfg, compile_step=False, lower=False, **_fp_kwargs(fp)
            )["programs"]
        )
        # the jit static key on the model is STRUCTURAL (config value,
        # not instance identity) — a per-footprint config name keeps the
        # global cache deltas attributable to THIS engine
        model = TransformerLM(dataclasses.replace(
            cfg, name=f"aot_engine_{fp['slots']}x{fp['chunk']}"
        ))
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
        before = sizes()
        eng = SlotEngine(
            model, params, slots=fp["slots"], chunk=fp["chunk"],
            prefill_buckets=fp["prefill_buckets"],
            prefill_chunk=fp["prefill_chunk"],
        )

        def serve(i, ln, plan=None):
            prompt = jax.random.randint(
                jax.random.PRNGKey(7000 + i), (1, ln), 0, cfg.vocab_size
            ).astype(jnp.int32)
            eng.admit(DecodeRequest(prompt=prompt, sample=greedy, seed=i,
                                    max_new_tokens=2 * fp["chunk"]), tag=i)
            done = {}
            with inject.inject(plan or inject.FaultPlan()):
                while eng.busy:
                    done.update(dict(eng.step()))
            return done[i]

        # prompt + its first chunk of tokens fills bucket b less one: the
        # prompt stages at width b, and two poisonings of the second chunk
        # re-prefill prompt + chunk at width b
        for i, b in enumerate(fp["prefill_buckets"]):
            ln = b - fp["chunk"] - 1
            assert ln > 0 and (i == 0 or ln > fp["prefill_buckets"][i - 1])
            r = serve(i, ln, inject.FaultPlan().poison_decode_slot_at(
                0, chunk=1, times=2))
            assert r.status == "ok" and r.reprefills == 1, (fp, b, r)
        assert serve(9, fp["prefill_buckets"][-1] - 1).status == "ok"  # reuse
        assert sizes() - before == plan_kinds, (fp, sizes() - before)
