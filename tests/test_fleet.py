"""Fleet suite (ISSUE 8): a replicated front door over O(1) decode state.

The acceptance proofs live here — (1) drain (or SIGKILL) of one replica
mid-conversation: the router re-routes, the session migrates through the
SHARED store, and the conversation's concatenated output is BITWISE-equal
to an uninterrupted single-server run at the same seed, greedy and
sampled; (2) least-loaded dispatch routes around DEGRADED/DRAINING/DEAD
replicas and sheds at the fleet admission bound with the PR 4
OverloadError contract; (3) the supervisor drains-and-respawns a
degraded replica and respawns an exited/killed one, with spawn faults
retried (`fleet.replica_spawn`), dispatch faults failed over
(`fleet.dispatch`), and a broken control channel treated as a dead
replica (`fleet.control_io`). Process-replica tests (a real child OS
process per replica) carry the same proofs end to end and live in the
_SLOW tier; the quick tier drives identical router/supervisor logic over
thread-backed LocalReplicas.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.fleet import (
    AutoscalePolicy,
    LocalReplica,
    ProcessReplica,
    ReplicaHandle,
    ReplicaSpec,
    Router,
    Supervisor,
)
from orion_tpu.generate import SampleConfig, generate
from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.transformer import TransformerLM
from orion_tpu.resilience import inject
from orion_tpu.resilience.retry import RetryPolicy
from orion_tpu.serving import (
    DecodeRequest,
    Health,
    OverloadError,
    RejectedError,
    ServeConfig,
    Server,
)

pytestmark = pytest.mark.chaos

# same shape family as tests/test_sessions.py so the (slots=2, chunk=4)
# decode compiles are shared across the two modules within one run
CFG = ModelConfig(
    name="fleet_test", vocab_size=64, d_model=32, n_layers=3, n_heads=2,
    layer_types=("linear", "softmax", "swa"), window=4, max_seq_len=96,
    dtype="float32", backend="xla",
)
GREEDY = SampleConfig(temperature=0.0)
SAMPLED = SampleConfig(temperature=0.8, top_k=5, top_p=0.9, eos_token=3,
                       pad_token=0)
FAST_RETRY = RetryPolicy(attempts=3, base_delay=0.0, max_delay=0.0)


@pytest.fixture(scope="module")
def mp():
    model = TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return model, params


def _prompt(i, ln=5):
    return jax.random.randint(
        jax.random.PRNGKey(2000 + i), (1, ln), 0, CFG.vocab_size
    ).astype(jnp.int32)


def _ref(mp, prompt, n_new, sample, seed):
    model, params = mp
    return np.asarray(
        generate(model, params, prompt, n_new, sample,
                 rng=jax.random.PRNGKey(seed))
    )


def _serve_cfg(tmp_path, **kw):
    kw.setdefault("chunk", 4)
    kw.setdefault("slots", 2)
    kw.setdefault("max_inflight", 8)
    kw.setdefault("session_dir", str(tmp_path / "sessions"))
    return ServeConfig(**kw)


def _local_fleet(mp, tmp_path, n=2, sup_kw=None, **cfg_kw):
    """Supervisor over n thread-backed replicas sharing one session dir."""
    model, params = mp
    cfg = _serve_cfg(tmp_path, **cfg_kw)

    def factory(name):
        return LocalReplica(model, params, cfg, name=name).start()

    return Supervisor(factory, n, **(sup_kw or {})).start()


def _req(prompt, want, sample, seed, sid=None):
    return DecodeRequest(
        prompt=prompt, max_new_tokens=want, sample=sample, seed=seed,
        session_id=sid,
    )


def _cont(want, sample, sid):
    return _req(np.zeros((1, 0), np.int32), want, sample, 0, sid)


# ---------------------------------------------------------------------------
# router unit tests over scripted fakes: dispatch policy in isolation
# ---------------------------------------------------------------------------


class FakePending:
    def __init__(self):
        self.done = threading.Event()


class FakeReplica(ReplicaHandle):
    """Scripted replica: fixed health/load, records what it was handed."""

    def __init__(self, name, state="serving", inflight=0, alive=True,
                 capacity=None):
        self.name = name
        self._state = state
        self._inflight = inflight
        self._alive = alive
        self.capacity = capacity  # per-replica admission bound
        self.submitted = []

    @property
    def alive(self):
        return self._alive

    @property
    def inflight(self):
        return self._inflight

    def health_state(self):
        return self._state if self._alive else "dead"

    def submit(self, request):
        if self.capacity is not None and self._inflight >= self.capacity:
            raise OverloadError(f"{self.name} full")
        self._inflight += 1
        self.submitted.append(request)
        return FakePending()


def test_least_loaded_dispatch_prefers_idle_replica():
    r0 = FakeReplica("r0", inflight=3)
    r1 = FakeReplica("r1", inflight=1)
    router = Router([r0, r1])
    router.submit(_req(_prompt(0), 4, GREEDY, 0))
    assert [len(r0.submitted), len(r1.submitted)] == [0, 1]
    # ties break to the lowest index — deterministic placement
    r2 = FakeReplica("r2", inflight=0)
    r3 = FakeReplica("r3", inflight=0)
    router2 = Router([r2, r3])
    router2.submit(_req(_prompt(0), 4, GREEDY, 0))
    assert [len(r2.submitted), len(r3.submitted)] == [1, 0]


def test_routes_around_degraded_draining_dead():
    degraded = FakeReplica("limping", state="degraded", inflight=0)
    busy = FakeReplica("busy", state="serving", inflight=6)
    draining = FakeReplica("draining", state="draining", inflight=0)
    dead = FakeReplica("dead", alive=False)
    router = Router([degraded, busy, draining, dead])
    # a healthy replica wins even when the degraded one is idler
    router.submit(_req(_prompt(0), 4, GREEDY, 0))
    assert len(busy.submitted) == 1 and not degraded.submitted
    # ... but DEGRADED still serves when it is the only accepting state
    busy._state = "draining"
    router.submit(_req(_prompt(0), 4, GREEDY, 1))
    assert len(degraded.submitted) == 1
    # DRAINING/DEAD are never candidates
    assert not draining.submitted and not dead.submitted
    degraded._state = "draining"
    with pytest.raises(RejectedError, match="no routable replica"):
        router.submit(_req(_prompt(0), 4, GREEDY, 2))


def test_fleet_admission_bound_sheds_with_overload_error():
    """The PR 4 single-server contract one level up: fleet full => the
    submit itself raises OverloadError (shed, not queued)."""
    r0 = FakeReplica("r0", inflight=2)
    r1 = FakeReplica("r1", inflight=2)
    router = Router([r0, r1], max_inflight=4)
    with pytest.raises(OverloadError, match="fleet admission full"):
        router.submit(_req(_prompt(0), 4, GREEDY, 0))
    assert router.stats["shed"] == 1
    # every replica shedding locally is also a fleet-level shed
    r2 = FakeReplica("r2", inflight=1, capacity=1)
    r3 = FakeReplica("r3", inflight=1, capacity=1)
    router2 = Router([r2, r3])
    with pytest.raises(OverloadError, match="every routable replica shed"):
        router2.submit(_req(_prompt(0), 4, GREEDY, 0))


def test_dispatch_fault_fails_over_to_next_replica():
    """An injected fleet.dispatch fault on the first placement attempt
    moves the request to the next candidate — the request is served, the
    failover is counted, nothing is dropped silently."""
    r0 = FakeReplica("r0")
    r1 = FakeReplica("r1")
    router = Router([r0, r1])
    plan = inject.FaultPlan().fail_io("fleet.dispatch")
    with inject.inject(plan):
        router.submit(_req(_prompt(0), 4, GREEDY, 0))
    assert plan.delivered == ["fleet.dispatch@1"]
    assert [len(r0.submitted), len(r1.submitted)] == [0, 1]
    assert router.stats["failovers"] == 1
    # unlimited dispatch faults: the request fails LOUDLY, not silently
    plan = inject.FaultPlan().fail_io("fleet.dispatch", times=-1)
    with inject.inject(plan):
        with pytest.raises(RejectedError, match="every routable replica"):
            router.submit(_req(_prompt(0), 4, GREEDY, 1))


def test_session_turns_serialized_fleet_wide():
    """One turn at a time per conversation across the WHOLE fleet: with
    shared-store mobility, two concurrent turns could both resume the
    same generation on different replicas and fork the conversation."""
    r0, r1 = FakeReplica("r0"), FakeReplica("r1")
    router = Router([r0, r1])
    p1 = router.submit(_req(_prompt(0), 4, GREEDY, 0, sid="conv"))
    with pytest.raises(ValueError, match="one turn at a time"):
        router.submit(_cont(4, GREEDY, "conv"))
    p1.done.set()  # turn resolved -> the next one may dispatch anywhere
    router.submit(_cont(4, GREEDY, "conv"))
    assert len(r0.submitted) + len(r1.submitted) == 2


def test_replica_spawn_fault_is_retried():
    """A transient spawn failure (fleet.replica_spawn inside the retry
    region) costs a backoff, not fleet capacity."""
    spawned = []

    def factory(name):
        r = FakeReplica(name)
        r.wait_ready = lambda timeout: None
        spawned.append(name)
        return r

    plan = inject.FaultPlan().fail_io("fleet.replica_spawn")
    with inject.inject(plan):
        sup = Supervisor(factory, 2, spawn_retry=FAST_RETRY).start()
    assert plan.delivered == ["fleet.replica_spawn@1"]
    assert len(spawned) == 2 and len(sup.replicas) == 2
    # spawn ordinals keep counting across the retry (names stay unique)
    assert spawned == ["replica-0.g2", "replica-1.g3"]


# ---------------------------------------------------------------------------
# elastic autoscaling (ISSUE 20): hysteresis, cooldown, loss-free scale-in
# ---------------------------------------------------------------------------


class ScriptedReplica(FakeReplica):
    """FakeReplica + the supervisor-facing lifecycle surface (status
    heartbeats, drain/join/kill) so autoscaler control-loop tests drive
    the REAL Supervisor over fully scripted signals. ``actuate`` stays
    False in the slo section so the burn-limit healing path never buys a
    drain-respawn — only the autoscaler reads ``firing_fast`` here."""

    def __init__(self, name, **kw):
        super().__init__(name, **kw)
        self.last_status = None
        self.firing_fast = []
        self.drained = False
        self.killed = False

    def wait_ready(self, timeout=0.0):
        return True

    def status(self, timeout=0.0):
        snap = {
            "state": self._state, "reason": "",
            "slo": {"firing_fast": list(self.firing_fast),
                    "objectives": {}, "actuate": False},
        }
        self.last_status = snap
        return snap

    def drain(self):
        self.drained = True
        self._state = "draining"
        self._alive = False

    def join(self, timeout=0.0):
        return True

    def kill(self):
        self.killed = True
        self._alive = False


def test_fleet_that_cannot_reach_its_size_does_not_start():
    """One process per chip: the second process replica on a one-chip host
    cannot get a device. Its death must end the start with ONE error that
    carries the child's own last words, reap the replicas already up, and
    not be retried (the same wall stands behind every respawn)."""
    from orion_tpu.fleet.replica import ReplicaGone

    made = []

    def factory(name):
        if made:  # every replica after the first dies the same way
            r = ProcessReplica(ReplicaSpec(config="tiny"), name=name)
            r._dispatch({"event": "fatal",
                         "message": "no device for this replica: busy"})
            r._eof = True
            r._ready.set()
            r.kill = lambda: None
            r.join = lambda timeout=None: True
        else:
            r = ScriptedReplica(name)
        made.append(r)
        return r

    with pytest.raises(ReplicaGone, match="no device for this replica"):
        Supervisor(factory, 2, spawn_retry=FAST_RETRY).start()
    assert len(made) == 2  # ReplicaGone is not a transient: no retry
    assert made[0].killed  # the survivor was reaped, not orphaned


@pytest.mark.slow
def test_process_fleet_without_a_device_fails_with_one_error(tmp_path):
    """The real thing, end to end: children that cannot initialize their
    backend (here: a TPU asked for on a box without one) make the fleet
    CLI exit non-zero, in bounded time, with the cause on stderr — no
    hang, no respawn loop."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="tpu", TPU_LOG_DIR="disabled")
    proc = subprocess.run(
        [sys.executable, "-m", "orion_tpu.fleet", "--replicas", "2",
         "--config", "tiny", "--max-new-tokens", "4"],
        input="hello\n", env=env, text=True, capture_output=True,
        timeout=240,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert proc.stderr.count("fleet failed to start") == 1
    assert "no device for this replica" in proc.stderr
    assert "respawning" not in proc.stderr


def _scripted_fleet(n, pol):
    made = []

    def factory(name):
        r = ScriptedReplica(name)
        made.append(r)
        return r

    sup = Supervisor(factory, n, autoscale=pol).start()
    return sup, made


def test_autoscale_queue_pressure_hysteresis_and_cooldown():
    """Queue pressure must persist up_ticks consecutive ticks before a
    spawn; every move opens a cooldown_ticks refractory window in which
    streaks keep accumulating but no move fires; max_replicas caps N."""
    pol = AutoscalePolicy(min_replicas=1, max_replicas=3, queue_high=2.0,
                          queue_low=1.0, up_ticks=2, down_ticks=3,
                          cooldown_ticks=2)
    sup, made = _scripted_fleet(1, pol)
    made[0]._inflight = 5  # 5 >= queue_high * 1 live: pressure
    sup.tick()  # streak 1 of 2: no move yet
    assert len(sup.replicas) == 1
    assert sup.autoscale_state()["queue_pressure"] is True
    assert sup.autoscale_state()["up_streak"] == 1
    sup.tick()  # streak 2: spawn
    assert len(sup.replicas) == 2
    assert any("scale_out (queue)" in e[2] for e in sup.events)
    # pressure persists (5 >= 2.0 * 2): the cooldown must hold the loop
    # still for exactly cooldown_ticks even as the streak accumulates
    sup.tick()  # cooldown 2 -> 1
    sup.tick()  # cooldown 1 -> 0
    assert len(sup.replicas) == 2, "no move inside the refractory window"
    sup.tick()  # cooldown over, streak >= up_ticks: second spawn
    assert len(sup.replicas) == 3
    # at max_replicas: pressure can streak forever, N stays put
    made[1]._inflight = 3  # 8 >= 2.0 * 3: still pressure
    for _ in range(6):
        sup.tick()
    assert sup.autoscale_state()["queue_pressure"] is True
    assert len(sup.replicas) == 3
    assert {r.name for r in sup.replicas} == {
        "replica-0.g1", "replica-1.g2", "replica-2.g3",
    }


def test_autoscale_scale_in_drains_least_loaded_respects_min():
    """Surplus must persist down_ticks before a drain; the victim is the
    least-loaded replica (ties to the HIGHEST slot index), it leaves the
    router BEFORE draining, and min_replicas is a floor."""
    pol = AutoscalePolicy(min_replicas=1, max_replicas=3, queue_high=4.0,
                          queue_low=1.0, up_ticks=2, down_ticks=2,
                          cooldown_ticks=0)
    sup, made = _scripted_fleet(2, pol)
    r0, r1 = made[0], made[1]
    r0._inflight, r1._inflight = 3, 0  # 3 > queue_low * 2: neither signal
    sup.tick()
    sig = sup.autoscale_state()
    assert not sig["pressure"] and not sig["surplus"]
    assert sig["down_streak"] == 0
    r0._inflight = 2  # 2 <= queue_low * 2: surplus
    sup.tick()  # streak 1 of 2
    assert len(sup.replicas) == 2
    sup.tick()  # streak 2: scale in
    assert len(sup.replicas) == 1
    # the idle replica went, the loaded one survived — and the victim
    # was drained (sessions suspend to the shared store), not killed
    assert sup.replicas[0] is r0
    assert r1.drained and not r1.killed
    assert any("scale_in; draining" in e[2] for e in sup.events)
    # min_replicas floors the fleet: surplus streaks forever, N holds
    r0._inflight = 0
    for _ in range(5):
        sup.tick()
    assert len(sup.replicas) == 1 and not r0.drained


def test_autoscale_burn_pressure_spawns_and_vetoes_surplus():
    """Any replica's SLO fast-burn alert is scale-out pressure (more
    capacity is the first response to a latency burn) and vetoes the
    surplus signal even when the queues read idle."""
    pol = AutoscalePolicy(min_replicas=1, max_replicas=2, queue_high=8.0,
                          queue_low=4.0, up_ticks=1, down_ticks=1,
                          cooldown_ticks=0)
    sup, made = _scripted_fleet(1, pol)
    made[0].firing_fast = ["latency_p99"]  # queues idle: burn alone
    sup.tick()
    assert len(sup.replicas) == 2
    assert any("scale_out (burn)" in e[2] for e in sup.events)
    sig = sup.autoscale_state()
    assert sig["burn_pressure"] is True and sig["surplus"] is False
    # burn still firing + queues idle enough for surplus: burn vetoes
    # the drain (down_ticks=1 would otherwise fire instantly)
    for _ in range(3):
        sup.tick()
    assert len(sup.replicas) == 2
    # burn clears, queues idle: surplus finally wins
    made[0].firing_fast = []
    sup.tick()
    assert len(sup.replicas) == 1


# ---------------------------------------------------------------------------
# the small fix: Server.snapshot is one atomic read
# ---------------------------------------------------------------------------


def test_server_snapshot_atomic_and_complete(mp):
    """snapshot() must carry health + prefilling/decoding slot gauges in
    ONE lock acquisition: the health machine shares the server's stats
    lock, so while a reader holds it no health transition can interleave
    (the torn occupancy/health pair a router must never observe)."""
    model, params = mp
    srv = Server(model, params, ServeConfig(chunk=4, slots=2))
    snap = srv.snapshot()
    assert {"state", "stats", "occupancy", "slots", "sessions",
            "queued"} <= set(snap)
    assert {"prefilling", "decoding", "active", "free"} <= set(snap["slots"])
    # the health machine transitions under the server's own stats lock
    entered = threading.Event()
    finished = threading.Event()

    def flip():
        entered.set()
        srv.health.to(Health.SERVING, "probe")
        finished.set()

    with srv._stats_lock:
        t = threading.Thread(target=flip, daemon=True)
        t.start()
        assert entered.wait(timeout=5.0)
        assert not finished.wait(timeout=0.2), (
            "health transition must block while a snapshot reader holds "
            "the shared lock"
        )
    assert finished.wait(timeout=5.0)
    assert srv.health.state is Health.SERVING
    srv.close()


# ---------------------------------------------------------------------------
# integration over LocalReplica fleets: mobility, drain, kill, healing
# ---------------------------------------------------------------------------


def _wait(pending, timeout=120.0):
    assert pending.done.wait(timeout=timeout), "request never resolved"
    return pending


def test_cross_replica_session_resume_bitwise(mp, tmp_path):
    """Session mobility: turn 1 on replica A, A drains, turn 2 lands on
    replica B via the router — B resumes from the SHARED store and the
    concatenation is bitwise an uninterrupted solo run (migration is a
    disk read, not a KV transfer)."""
    prompt = _prompt(0)
    ref = _ref(mp, prompt, 16, GREEDY, seed=123)
    sup = _local_fleet(mp, tmp_path)
    try:
        p1 = _wait(sup.router.submit(_req(prompt, 8, GREEDY, 123, "conv")))
        assert p1.result.status == "ok"
        served_by = [r for r in sup.replicas if r.server.stats["ok"] == 1]
        assert len(served_by) == 1
        served_by[0].drain()
        assert served_by[0].join(timeout=30.0)
        p2 = _wait(sup.router.submit(_cont(8, GREEDY, "conv")))
        assert p2.result.status == "ok"
        other = [r for r in sup.replicas if r is not served_by[0]][0]
        assert other.server.stats["resumed"] == 1, "must resume on B"
        np.testing.assert_array_equal(
            np.concatenate([p1.result.tokens, p2.result.tokens], axis=1), ref
        )
    finally:
        sup.drain_all(timeout=30.0)


def test_stale_resident_cache_revalidated_against_shared_store(mp, tmp_path):
    """Replica A serves turn 1 and keeps the session resident; turn 2 on
    replica B advances the on-disk generation; turn 3 back on A must
    detect its resident copy is STALE (generation check against the
    shared store) and reload generation 2 — or the conversation forks."""
    prompt = _prompt(1)
    ref = _ref(mp, prompt, 24, GREEDY, seed=9)
    sup = _local_fleet(mp, tmp_path)
    a, b = sup.replicas
    try:
        p1 = _wait(a.submit(_req(prompt, 8, GREEDY, 9, "conv")))
        assert "conv" in a.server._sessions, "resident on A after turn 1"
        p2 = _wait(b.submit(_cont(8, GREEDY, "conv")))
        p3 = _wait(a.submit(_cont(8, GREEDY, "conv")))
        total = np.concatenate(
            [p1.result.tokens, p2.result.tokens, p3.result.tokens], axis=1
        )
        np.testing.assert_array_equal(total, ref)
        assert a.server.session_store.newest_generation("conv") == 3
    finally:
        sup.drain_all(timeout=30.0)


@pytest.mark.parametrize("sample", [GREEDY, SAMPLED], ids=["greedy", "sampled"])
def test_drain_midstream_reroutes_continuation_bitwise(mp, tmp_path, sample):
    """THE quick-tier acceptance: a replica is drained MID-conversation
    (its session suspends to the shared store at the next boundary), the
    supervisor respawns it, the router re-routes the continuation, and
    the conversation's concatenated output is bitwise an uninterrupted
    solo run at the same seed."""
    want = 24
    prompt = _prompt(10)
    ref = _ref(mp, prompt, want, sample, seed=500)
    sup = _local_fleet(mp, tmp_path)
    try:
        victim = sup.replicas[0]  # both idle -> router picks index 0
        plan = inject.FaultPlan().add(
            "serve.chunk", step=2, times=1, action=victim.drain
        )
        with inject.inject(plan):
            p1 = _wait(sup.router.submit(_req(prompt, want, sample, 500,
                                              "conv")))
        assert plan.delivered, "drain must hit mid-stream"
        assert p1.result.status == "suspended"
        assert 0 < p1.result.new_tokens < want, "must suspend MID-stream"
        assert victim.join(timeout=30.0)
        sup.tick()  # exited replica is respawned
        assert all(r.alive for r in sup.replicas)
        assert victim not in sup.replicas
        left = want - p1.result.new_tokens
        p2 = _wait(sup.router.submit(_cont(left, sample, "conv")))
        assert p2.result.status == "ok"
        np.testing.assert_array_equal(
            np.concatenate([p1.result.tokens, p2.result.tokens], axis=1), ref
        )
    finally:
        sup.drain_all(timeout=30.0)


def test_killed_replica_mid_turn_last_generation_survives(mp, tmp_path):
    """SIGKILL model: the replica dies abruptly mid-turn (no drain, no
    suspension). The turn in flight fails loudly with partial tokens —
    but the PREVIOUS committed generation on the shared store survives,
    so retrying the turn elsewhere continues the conversation bitwise:
    zero acknowledged turns lost."""
    prompt = _prompt(11)
    ref = _ref(mp, prompt, 16, GREEDY, seed=17)
    sup = _local_fleet(mp, tmp_path)
    try:
        victim = sup.replicas[0]
        p1 = _wait(sup.router.submit(_req(prompt, 8, GREEDY, 17, "conv")))
        assert p1.result.status == "ok"  # gen 1 committed on shared disk
        # turn 1 consumed boundaries 0-1, so step=2 is turn 2's FIRST
        # chunk: the kill flag lands after 4 of its 8 tokens
        plan = inject.FaultPlan().add(
            "serve.chunk", step=2, times=1, action=victim.kill
        )
        with inject.inject(plan):
            p2 = _wait(sup.router.submit(_cont(8, GREEDY, "conv")))
        assert plan.delivered
        assert p2.result is not None and p2.result.status == "failed"
        assert victim.crashed and victim.join(timeout=30.0)
        sup.tick()  # respawn the corpse
        assert all(r.alive for r in sup.replicas)
        # the retry resumes from generation 1 on a surviving replica
        p3 = _wait(sup.router.submit(_cont(8, GREEDY, "conv")))
        assert p3.result.status == "ok"
        np.testing.assert_array_equal(
            np.concatenate([p1.result.tokens, p3.result.tokens], axis=1), ref
        )
    finally:
        sup.drain_all(timeout=30.0)


def test_supervisor_drains_and_respawns_degraded_replica(mp, tmp_path):
    """A replica whose ladder exhausts (poisoned decode state) reports
    DEGRADED; the supervisor SIGTERM-drains it and a fresh replica takes
    its router slot — the fleet heals without operator action."""
    sup = _local_fleet(mp, tmp_path)
    try:
        victim = sup.replicas[0]
        plan = inject.FaultPlan().poison_decode_state_at(chunk=0, times=-1)
        with inject.inject(plan):
            p = _wait(sup.router.submit(_req(_prompt(12), 8, GREEDY, 0)))
        assert p.result is not None and p.result.status == "failed"
        assert victim.health_state() == "degraded"
        sup.tick()
        assert victim not in sup.replicas, "degraded replica replaced"
        assert victim.join(timeout=30.0), "drained, not leaked"
        assert victim.server.health.state is Health.DEAD
        assert all(r.alive for r in sup.replicas)
        assert any("degraded; draining" in e[2] for e in sup.events)
        # and the healed fleet still serves
        p2 = _wait(sup.router.submit(_req(_prompt(13), 4, GREEDY, 1)))
        assert p2.result.status == "ok"
    finally:
        sup.drain_all(timeout=30.0)


class ScriptedStatusReplica(FakeReplica):
    """FakeReplica plus the status/lifecycle surface Supervisor.tick
    drives: a scripted (state, reason) heartbeat and drain/kill
    recorders."""

    def __init__(self, name, state="serving", reason=""):
        super().__init__(name, state=state)
        self.reason = reason
        self.drained = False
        self.killed = False

    def wait_ready(self, timeout):
        pass

    def status(self, timeout=2.0):
        return {"state": self.health_state(), "reason": self.reason}

    def drain(self):
        self.drained = True
        self._alive = False
        self._state = "dead"

    def kill(self):
        self.killed = True
        self._alive = False

    def join(self, timeout=0.0):
        return not self._alive


def test_supervisor_suppresses_respawn_for_store_outage():
    """ISSUE 17 regression: a replica DEGRADED with reason
    ``store-outage:<store>`` must NOT be drained-and-respawned — a fresh
    process meets the same dead store, minus this one's dirty
    write-behind sessions (the only up-to-date turns during the outage).
    The suppression is logged once per outage episode; any OTHER
    degraded reason still takes the drain-and-respawn path."""
    spawned = []

    def factory(name):
        r = ScriptedStatusReplica(name)
        spawned.append(r)
        return r

    sup = Supervisor(factory, 1, spawn_retry=FAST_RETRY,
                     drain_grace=0.1).start()
    r0 = spawned[0]
    r0._state = "degraded"
    r0.reason = "store-outage:session"
    sup.tick()
    sup.tick()  # second heartbeat of the same episode: no new event
    assert sup.replicas[0] is r0, "store-outage replica must keep its slot"
    assert not r0.drained and not r0.killed and len(spawned) == 1
    msgs = [e[2] for e in sup.events]
    assert sum("respawn_suppressed" in m for m in msgs) == 1
    assert any("store-outage:session" in m for m in msgs)
    # recovery closes the episode; a NEW outage is logged again
    r0._state = "serving"
    r0.reason = ""
    sup.tick()
    r0._state = "degraded"
    r0.reason = "store-outage:prefix"
    sup.tick()
    msgs = [e[2] for e in sup.events]
    assert sum("respawn_suppressed" in m for m in msgs) == 2
    assert sup.replicas[0] is r0 and len(spawned) == 1
    # control: degraded for a non-storage reason still drains-and-respawns
    r0.reason = "watchdog: serve loop stalled"
    sup.tick()
    assert r0.drained, "non-storage degradation takes the drain path"
    assert sup.replicas[0] is not r0 and len(spawned) == 2
    assert any("degraded; draining" in e[2] for e in sup.events)


def test_fleet_overload_shed_integration(mp, tmp_path):
    """Fleet-level admission over real replicas: max_inflight=1 with a
    long request in flight sheds the second submit at the door."""
    sup = _local_fleet(mp, tmp_path, sup_kw={"max_inflight": 1})
    try:
        p1 = sup.router.submit(_req(_prompt(14), 16, GREEDY, 0))
        with pytest.raises(OverloadError, match="fleet admission full"):
            sup.router.submit(_req(_prompt(15), 4, GREEDY, 1))
        _wait(p1)
        p2 = _wait(sup.router.submit(_req(_prompt(15), 4, GREEDY, 1)))
        assert p2.result.status == "ok"
    finally:
        sup.drain_all(timeout=30.0)


def test_fleet_cli_local_roundtrip(tmp_path, capsys):
    """CLI wiring: --local --replicas 2 over a prompts file completes
    every prompt and drains the fleet clean."""
    from orion_tpu.fleet.__main__ import main

    pf = tmp_path / "prompts.txt"
    pf.write_text("ab\ncd\n")
    rc = main([
        "--local", "--replicas", "2", "--config", "tiny",
        "--set", "d_model=32", "--set", "n_layers=1", "--set", "n_heads=2",
        "--set", "max_seq_len=64",
        "--prompts-file", str(pf), "--max-new-tokens", "4",
        "--chunk", "2", "--slots", "2", "--prefill-chunk", "8",
        "--temperature", "0",
        "--session-dir", str(tmp_path / "store"),
    ])
    assert rc == 0
    out = capsys.readouterr()
    lines = out.out.strip().splitlines()
    assert len(lines) == 2 and all(ln.startswith(("ab", "cd"))
                                   for ln in lines)
    assert "fleet:" in out.err


# ---------------------------------------------------------------------------
# process replicas: the real child-OS-process fleet (slow tier)
# ---------------------------------------------------------------------------

_PROC_OVERRIDES = {
    "vocab_size": 64, "d_model": 32, "n_layers": 3, "n_heads": 2,
    "layer_types": ["linear", "softmax", "swa"], "window": 4,
    "max_seq_len": 96,
}


def _proc_spec(tmp_path, faults=None, **serve_kw):
    serve = {"chunk": 4, "slots": 2, "max_inflight": 8,
             "session_dir": str(tmp_path / "sessions")}
    serve.update(serve_kw)
    return ReplicaSpec(
        config="tiny", overrides=_PROC_OVERRIDES, serve=serve, faults=faults,
        jax_flags={"jax_threefry_partitionable":
                   jax.config.jax_threefry_partitionable},
    )


def _proc_ref(spec, prompt, n_new, sample, seed):
    """In-parent reference over the SAME model a child builds."""
    from orion_tpu.fleet.replica import build_model

    model, params, _ = build_model(spec)
    return np.asarray(
        generate(model, params, prompt, n_new, sample,
                 rng=jax.random.PRNGKey(seed))
    )


@pytest.mark.parametrize("sample", [GREEDY, SAMPLED], ids=["greedy", "sampled"])
def test_process_fleet_drain_reroute_bitwise(tmp_path, sample):
    """THE acceptance proof on real processes: replica 0 (a child OS
    process) self-delivers SIGTERM mid-conversation (armed via its spec's
    fault plan — chaos is per-child, siblings unaffected), its session
    suspends to the shared store as it drains to exit 0, the supervisor
    respawns it, and the router re-routes the continuation to the other
    child — concatenated output bitwise-equal to an uninterrupted
    single-server run at the same seed."""
    want = 24
    clean = _proc_spec(tmp_path)
    faulted = _proc_spec(
        tmp_path, faults=[{"kind": "preempt_at_chunk", "args": [2]}]
    )
    # same (prompt, seed) as the quick-tier drain test: known EOS-free
    # for 24 sampled tokens, so the SIGTERM at chunk 2 lands MID-stream
    prompt = _prompt(10)
    ref = _proc_ref(clean, prompt, want, sample, seed=500)
    spawned = [0]

    def factory(name):
        spawned[0] += 1
        spec = faulted if spawned[0] == 1 else clean
        return ProcessReplica(spec, name=name).start()

    sup = Supervisor(factory, 2, heartbeat_timeout=10.0).start()
    try:
        p1 = _wait(sup.router.submit(
            _req(np.asarray(prompt), want, sample, 500, "conv")
        ), timeout=300.0)
        assert p1.result.status == "suspended"
        assert 0 < p1.result.new_tokens < want
        victim = sup.replicas[0]
        assert victim.join(timeout=60.0) and victim.exit_rc == 0
        for _ in range(10):  # heal: exited replica respawns
            sup.tick()
            if all(r.alive for r in sup.replicas):
                break
        assert victim not in sup.replicas
        left = want - p1.result.new_tokens
        p2 = _wait(sup.router.submit(_cont(left, sample, "conv")),
                   timeout=300.0)
        assert p2.result.status == "ok"
        assert p2.replica != victim.name, "continuation re-routed"
        np.testing.assert_array_equal(
            np.concatenate([p1.result.tokens, p2.result.tokens], axis=1), ref
        )
    finally:
        sup.drain_all(timeout=60.0)


def test_process_fleet_kill_control_io_and_heartbeat(tmp_path):
    """Process-fleet machinery in one spawn-budget: (1) status() reads
    the atomic health+occupancy snapshot over the wire; (2) an injected
    fleet.control_io fault breaks the first replica's channel mid-submit
    and the router fails over; (3) SIGKILL of a child is noticed by the
    heartbeat (status -> None), the supervisor respawns it, and a
    conversation whose generation was committed before the kill resumes
    bitwise — zero acknowledged turns lost."""
    clean = _proc_spec(tmp_path)
    prompt = _prompt(21)
    ref = _proc_ref(clean, prompt, 16, GREEDY, seed=7)

    def factory(name):
        return ProcessReplica(clean, name=name).start()

    sup = Supervisor(factory, 2, heartbeat_timeout=10.0,
                     miss_limit=1).start()
    try:
        st = sup.replicas[0].status(timeout=30.0)
        assert st is not None and st["state"] == "serving"
        assert {"prefilling", "decoding"} <= set(st["slots"])
        # turn 1: committed generation on the shared store
        p1 = _wait(sup.router.submit(_req(np.asarray(prompt), 8, GREEDY, 7,
                                          "conv")), timeout=300.0)
        assert p1.result.status == "ok"
        served = [r for r in sup.replicas if r.name == p1.replica][0]
        other = [r for r in sup.replicas if r is not served][0]
        # control-channel fault: the serving replica looks dead at the
        # wire; the router fails over to its sibling
        plan = inject.FaultPlan().fail_io("fleet.control_io", times=1)
        with inject.inject(plan):
            # fault delivery order follows dispatch order: the victim is
            # whichever candidate the router tries FIRST (least loaded)
            p = _wait(sup.router.submit(_req(_prompt(22), 4, GREEDY, 1)),
                      timeout=300.0)
        assert plan.delivered and p.result.status == "ok"
        assert sup.router.stats["failovers"] >= 1
        # SIGKILL the replica that served the conversation
        served.kill()
        assert served.join(timeout=30.0)
        assert served.status(timeout=5.0) is None, "no heartbeat from corpse"
        for _ in range(10):
            sup.tick()
            if all(r.alive for r in sup.replicas):
                break
        assert served not in sup.replicas
        # the conversation continues from the committed generation
        p2 = _wait(sup.router.submit(_cont(8, GREEDY, "conv")),
                   timeout=300.0)
        assert p2.result.status == "ok"
        np.testing.assert_array_equal(
            np.concatenate([p1.result.tokens, p2.result.tokens], axis=1), ref
        )
        assert other.alive
    finally:
        sup.drain_all(timeout=60.0)
