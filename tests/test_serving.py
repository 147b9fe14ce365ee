"""Serving chaos suite (ISSUE 4): injected decode-state NaNs walked down
the degradation ladder with bitwise-identical recovery, mid-request
SIGTERM draining to exit 0, overload shedding, chunk-granular deadlines,
the health state machine, and the hardened serving-side checkpoint/
tokenizer loaders."""

import os
import shutil
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.generate import SampleConfig, generate, load_params
from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.transformer import TransformerLM
from orion_tpu.parallel.mesh import MeshConfig
from orion_tpu.resilience import inject
from orion_tpu.resilience.retry import RetryPolicy
from orion_tpu.serving import (
    DecodeRequest,
    Health,
    HealthMachine,
    InvalidTransition,
    OverloadError,
    RejectedError,
    ServeConfig,
    Server,
    SlotEngine,
    load_tokenizer,
)
from orion_tpu.training.trainer import TrainConfig

pytestmark = pytest.mark.chaos

CFG = ModelConfig(
    name="serve_test", vocab_size=64, d_model=32, n_layers=3, n_heads=2,
    layer_types=("linear", "softmax", "swa"), window=4, max_seq_len=64,
    dtype="float32", backend="xla",
)
GREEDY = SampleConfig(temperature=0.0)
PROMPT = jnp.ones((1, 5), jnp.int32)
FAST_RETRY = RetryPolicy(attempts=4, base_delay=0.01, max_delay=0.05)


@pytest.fixture(scope="module")
def mp():
    model = TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return model, params


@pytest.fixture(scope="module")
def ref_tokens(mp):
    """The uninjected ground truth — the MONOLITHIC generate() scan, so
    every recovery test below also re-proves chunked == monolithic."""
    model, params = mp
    return np.asarray(
        generate(model, params, PROMPT, 8, GREEDY, rng=jax.random.PRNGKey(0))
    )


def _req(**kw):
    base = dict(prompt=PROMPT, max_new_tokens=8, sample=GREEDY, seed=0)
    base.update(kw)
    return DecodeRequest(**base)


def _solo(mp, chunk, **kw):
    """A one-slot engine: the solo session."""
    model, params = mp
    return SlotEngine(model, params, slots=1, chunk=chunk, **kw)


def _run(eng, request, tick=None, **admit):
    """Serve ``request`` alone: admit, ``step()`` until the engine is not
    busy (``tick()`` first at every boundary), return its result."""
    eng.admit(request, tag="r", **admit)
    done = {}
    while eng.busy:
        if tick is not None:
            tick()
        done.update(dict(eng.step()))
    return done["r"]


# ---------------------------------------------------------------------------
# health state machine
# ---------------------------------------------------------------------------


def test_health_machine_legal_path_and_illegal_edges():
    h = HealthMachine()
    assert h.state is Health.STARTING and h.accepting
    assert h.to(Health.SERVING, "ready")
    assert not h.to(Health.SERVING)  # idempotent, not an error
    assert h.to(Health.DEGRADED, "ladder engaged")
    assert h.accepting, "DEGRADED still serves"
    assert h.to(Health.SERVING, "recovered")
    assert h.to(Health.DRAINING, "sigterm")
    assert not h.accepting
    with pytest.raises(InvalidTransition):
        h.to(Health.SERVING, "no way back from draining")
    assert h.to(Health.DEAD, "drained")
    with pytest.raises(InvalidTransition):
        h.to(Health.SERVING, "dead is dead")
    snap = h.snapshot()
    assert snap["state"] == "dead" and len(snap["transitions"]) == 6
    assert snap["dropped"] == 0


def test_health_history_bounded_on_flapping_replica():
    """A long-lived replica flapping SERVING <-> DEGRADED must not grow
    its /healthz payload (or host memory) without bound: the history
    keeps the last ``history_limit`` transitions and reports how many
    scrolled off."""
    h = HealthMachine(history_limit=8)
    h.to(Health.SERVING, "ready")
    for i in range(50):
        h.to(Health.DEGRADED, f"flap {i}")
        h.to(Health.SERVING, f"recover {i}")
    assert len(h.history) == 8
    snap = h.snapshot()
    assert len(snap["transitions"]) == 8
    assert snap["dropped"] == 102 - 8  # init + ready + 100 flaps
    # the suffix is the NEWEST transitions, reasons intact
    assert snap["transitions"][-1]["reason"] == "recover 49"
    assert snap["state"] == "serving"


# ---------------------------------------------------------------------------
# degradation ladder: every rung deterministically reachable
# ---------------------------------------------------------------------------


def test_injected_nan_rewinds_bitwise(mp, ref_tokens):
    """Acceptance: NaN injected into the decode state at chunk 1 — the
    session rewinds to the chunk-boundary snapshot and the completed
    request's tokens are BITWISE-identical to an uninjected run."""
    sess = _solo(mp, chunk=4)
    plan = inject.FaultPlan().poison_decode_state_at(1)
    with inject.inject(plan):
        r = _run(sess, _req())
    assert plan.delivered == ["decode.state_nan@1"]
    assert r.status == "ok" and (r.rewinds, r.reprefills) == (1, 0)
    assert r.degraded
    np.testing.assert_array_equal(r.tokens, ref_tokens)


def test_persistent_nan_escalates_to_reprefill(mp, ref_tokens):
    """Two deliveries at the same chunk poison the rewind retry too — the
    ladder's second rung rebuilds state by re-prefilling prompt + emitted
    tokens, and (greedy) the output still matches the uninjected run."""
    sess = _solo(mp, chunk=4)
    plan = inject.FaultPlan().poison_decode_state_at(1, times=2)
    with inject.inject(plan):
        r = _run(sess, _req())
    assert r.status == "ok" and (r.rewinds, r.reprefills) == (1, 1)
    np.testing.assert_array_equal(r.tokens, ref_tokens)


def test_unrecoverable_nan_fails_request_never_process(mp, ref_tokens):
    """Unlimited deliveries exhaust the ladder: the REQUEST fails with its
    partial tokens; the session (the process, in effigy) keeps serving."""
    sess = _solo(mp, chunk=4)
    plan = inject.FaultPlan().poison_decode_state_at(1, times=-1)
    with inject.inject(plan):
        r = _run(sess, _req())
    assert r.status == "failed"
    assert r.new_tokens == 4, "the finite chunk before the fault is kept"
    np.testing.assert_array_equal(r.tokens, ref_tokens[:, :4])
    # the next request on the same session is untouched
    r2 = _run(sess, _req())
    assert r2.status == "ok"
    np.testing.assert_array_equal(r2.tokens, ref_tokens)


def test_deadline_enforced_at_chunk_granularity(mp, ref_tokens):
    """A fake clock advancing 1s per chunk boundary against a 2.5s
    deadline: the boundary at t=3.0 refuses to start chunk 2, and the
    request returns its 2 completed chunks with status 'deadline' —
    bounded scans are what make the deadline checkable at all."""
    now = [0.0]
    sess = _solo(mp, chunk=2, clock=lambda: now[0])

    def tick():
        now[0] += 1.0

    r = _run(sess, _req(max_new_tokens=12, deadline_ms=2500.0), tick,
             deadline_at=2.5)
    assert r.status == "deadline"
    assert r.new_tokens == 4 and r.chunks == 2
    np.testing.assert_array_equal(r.tokens, ref_tokens[:, :4])


# ---------------------------------------------------------------------------
# server: SIGTERM drain, shedding, health flow
# ---------------------------------------------------------------------------


def test_deadline_anchored_at_admission_counts_queue_wait(mp):
    """A request whose deadline fully elapsed while QUEUED must come back
    'deadline' with zero tokens (no prefill paid), not decode to a
    too-late 'ok' — the SLO covers queue wait, not just decode time."""
    model, params = mp
    now = [0.0]
    srv = Server(
        model, params, ServeConfig(chunk=4, max_inflight=4),
        clock=lambda: now[0],
    )
    p = srv.submit(_req(deadline_ms=500.0))
    now[0] = 1.0  # the queue ate the whole budget
    srv.serve(drain_when_idle=True)
    assert p.result.status == "deadline" and p.result.new_tokens == 0
    srv.close()


def test_sigterm_mid_request_drains_and_exits_zero(mp, ref_tokens):
    """Acceptance: SIGTERM delivered at a decode chunk boundary of an
    in-flight request — the request completes bitwise-clean, the already-
    admitted request completes too, new submits are rejected, and the
    serve loop exits 0 with health DRAINING -> DEAD."""
    model, params = mp
    srv = Server(model, params, ServeConfig(chunk=4, max_inflight=4))
    p1 = srv.submit(_req())
    p2 = srv.submit(_req())
    plan = inject.FaultPlan().preempt_at_chunk(1)
    with inject.inject(plan):
        rc = srv.serve()
    assert rc == 0
    assert plan.delivered == ["serve.chunk@1"]
    assert srv.health.state is Health.DEAD
    assert p1.result.status == "ok" and p2.result.status == "ok"
    np.testing.assert_array_equal(p1.result.tokens, ref_tokens)
    np.testing.assert_array_equal(p2.result.tokens, ref_tokens)
    with pytest.raises(RejectedError):
        srv.submit(_req())
    assert srv.stats["rejected"] == 1 and srv.stats["ok"] == 2
    edges = [(a, b) for a, b, _, _ in srv.health.history if a is not None]
    assert (Health.SERVING, Health.DRAINING) in edges
    assert (Health.DRAINING, Health.DEAD) in edges


def test_overload_sheds_then_admitted_work_drains(mp, ref_tokens):
    model, params = mp
    srv = Server(model, params, ServeConfig(chunk=4, max_inflight=1))
    p1 = srv.submit(_req())
    with pytest.raises(OverloadError):
        srv.submit(_req())
    assert srv.stats["shed"] == 1
    rc = srv.serve(drain_when_idle=True)
    assert rc == 0
    np.testing.assert_array_equal(p1.result.tokens, ref_tokens)
    # idle drain leaves the server SERVING (CLI waves resubmit); close()
    # finalizes
    assert srv.health.state is Health.SERVING
    srv.close()
    assert srv.health.state is Health.DEAD


def test_ladder_degrades_health_and_clean_request_recovers(mp):
    model, params = mp
    srv = Server(model, params, ServeConfig(chunk=4, max_inflight=4))
    srv.submit(_req())
    plan = inject.FaultPlan().poison_decode_state_at(0)
    with inject.inject(plan):
        srv.serve(drain_when_idle=True)
    assert srv.health.state is Health.DEGRADED
    assert srv.stats["rewinds"] == 1
    srv.submit(_req())
    srv.serve(drain_when_idle=True)
    assert srv.health.state is Health.SERVING, "clean request recovers"
    srv.close()


def test_request_isolation_bad_request_never_kills_server(mp):
    """A request that raises (prompt overflowing max_seq_len) is an error
    RESULT; the admitted requests around it still complete."""
    model, params = mp
    srv = Server(model, params, ServeConfig(chunk=4, max_inflight=4))
    bad = srv.submit(_req(max_new_tokens=CFG.max_seq_len * 2))
    good = srv.submit(_req())
    srv.serve(drain_when_idle=True)
    assert isinstance(bad.error, ValueError) and bad.result is None
    assert good.result is not None and good.result.status == "ok"
    assert srv.stats["failed"] == 1
    srv.close()


def test_watchdog_stall_degrades_health(mp):
    model, params = mp
    srv = Server(model, params, ServeConfig(chunk=4, stall_timeout=60.0))
    srv.health.to(Health.SERVING, "test")
    srv._on_stall("stall detected (attempt 1): no heartbeat")
    assert srv.health.state is Health.DEGRADED and srv.stats["stalls"] == 1


# ---------------------------------------------------------------------------
# hardened loaders: checkpoint params + tokenizer
# ---------------------------------------------------------------------------

TRAIN_TINY = ModelConfig(
    name="serve_ck", vocab_size=32, d_model=16, n_layers=1, n_heads=2,
    max_seq_len=32, dtype="float32", backend="xla",
)


@pytest.fixture(scope="module")
def served_ckpt(tmp_path_factory):
    """One 4-step training run with saves (+ manifests) at steps 2 and 4,
    shared by the loader tests via copytree."""
    from orion_tpu.train import train as train_fn

    d = str(tmp_path_factory.mktemp("serve") / "ck")
    cfg = TrainConfig(
        model=TRAIN_TINY, steps=4, batch_size=2, seq_len=16, lr=1e-3,
        warmup_steps=2, log_every=100, mesh=MeshConfig(dp=1),
        ckpt_dir=d, ckpt_every=2,
    )
    train_fn(cfg, data="synthetic", resume=False)
    return d


def test_load_params_retries_transient_io(served_ckpt):
    plan = inject.FaultPlan().fail_io("serve.ckpt_load", times=2)
    with inject.inject(plan):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            params, step = load_params(served_ckpt, retry=FAST_RETRY)
    assert step == 4
    assert sum("retrying" in str(x.message) for x in w) == 2
    assert plan.delivered == ["serve.ckpt_load@4"] * 2


def test_load_params_falls_back_to_newest_intact_step(served_ckpt, tmp_path):
    d = str(tmp_path / "ck")
    shutil.copytree(served_ckpt, d)
    assert inject.corrupt_step(d, 4)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        params, step = load_params(d, retry=FAST_RETRY)
    assert step == 2, "serving must fall back to the newest INTACT step"
    msgs = " | ".join(str(x.message) for x in w)
    assert "falls back" in msgs
    # an explicitly pinned step never falls back
    with pytest.raises(Exception):
        load_params(d, step=4, retry=FAST_RETRY)


def test_params_manifest_catches_silent_tamper(served_ckpt):
    """The manifest projection (.params subtree, re-rooted for the bare-
    dict serving restore) must catch content corruption orbax itself
    accepts: flip one weight and re-verify."""
    from orion_tpu.training.checkpoint import (
        CheckpointIntegrityError,
        manifest_subtree,
        read_manifest,
        verify_manifest,
    )

    params, step = load_params(served_ckpt)
    sub = manifest_subtree(read_manifest(served_ckpt, step), ".params")
    assert sub is not None and sub["n_leaves"] > 0
    verify_manifest(params, sub)  # intact round-trip
    leaves, treedef = jax.tree.flatten(params)
    leaves[0] = np.asarray(leaves[0]).copy()
    leaves[0].flat[0] += 1.0
    with pytest.raises(CheckpointIntegrityError, match="checksum"):
        verify_manifest(jax.tree.unflatten(treedef, leaves), sub)


def test_tokenizer_load_retries_transient_io():
    plan = inject.FaultPlan().fail_io("serve.tokenizer_io", times=2)
    with inject.inject(plan):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            tok = load_tokenizer(None, retry=FAST_RETRY)
    assert tok.decode(tok.encode("ab")) == "ab"
    assert sum("retrying" in str(x.message) for x in w) == 2


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_serving_cli_smoke(tmp_path, capsys):
    from orion_tpu.serving.__main__ import main

    pf = tmp_path / "prompts.txt"
    pf.write_text("ab\ncd\n")
    rc = main([
        "--config", "tiny", "--prompts-file", str(pf),
        "--max-new-tokens", "4", "--chunk", "2", "--temperature", "0",
        "--max-inflight", "1", "--deadline-ms", "60000",
    ])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 2
    assert out[0].startswith("ab") and out[1].startswith("cd")
