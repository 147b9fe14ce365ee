"""Telemetry-spine suite (ISSUE 9 + ISSUE 10): metrics registry, request
traces, flight recorder, SLO engine, live endpoints.

The ISSUE 9 acceptance proofs live here — (1) a chaos run (staggered
admission, mid-stream SIGTERM suspend, ladder rung 2, cross-replica
resume) yields a trace whose spans pair begin/end for every request,
whose chunk events nest inside their request's span, and whose resumed
turn links to the original session id; (2) enabling FULL telemetry
(metrics + trace + flight) adds zero decode/prefill compiles; (3) the
flight recorder dumps at every DEGRADED/ladder-exhaustion/drain trigger
and its ring carries every fired fault-injection site.

The ISSUE 10 proofs too — (4) the interpolated-quantile helper matches
``numpy.percentile`` to within one bucket width (inf overflow bucket and
empty/single-sample edges included); (5) ``/healthz``'s status code
tracks every HealthMachine transition under the PR 4 chaos scenarios;
(6) scraping the live endpoints mid-stream leaves all three decode/
prefill jit caches untouched; (7) THE actuation chaos run: with
``serve.chunk_delay`` injected into replica A of a 2-replica fleet, the
router's dispatch share shifts to B while A is still SERVING, A's
fast-burn alert fires, the supervisor drain-respawns it with zero lost
turns (session suspend/resume bitwise), and the respawned replica's
error budget is whole again; (8) sustained fast burn on a single server
degrades health and sheds admissions at half the bound; (9) a watchdog
stall dumps the flight recorder; (10) ``python -m orion_tpu.obs.slo
check`` gates a dumped snapshot against declared objectives.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.generate import (
    SampleConfig,
    _decode_batched_chunk_jit,
    _decode_batched_prefill_chunk_jit,
    _prefill_carry_bucketed_jit,
    generate,
)
from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.transformer import TransformerLM
from orion_tpu.obs.flight import FlightRecorder
from orion_tpu.obs.metrics import (
    MetricsRegistry,
    aggregate,
    prometheus_from_snapshot,
)
from orion_tpu.obs import slo as obs_slo
from orion_tpu.obs.slo import (
    Objective,
    SLOEngine,
    WindowedHistogram,
    quantile_from_counts,
    registry_readers,
)
from orion_tpu.obs.trace import Tracer, merge_traces, read_jsonl, span_pairs
from orion_tpu.resilience import inject
from orion_tpu.serving import (
    DecodeRequest,
    Health,
    ServeConfig,
    Server,
)
from orion_tpu.serving.health import HTTP_STATUS
from orion_tpu.serving.server import OverloadError

pytestmark = pytest.mark.chaos


def _get(url, timeout=10.0):
    """(status code, body text) — non-2xx replies are data here, not
    exceptions (urllib raises HTTPError for them)."""
    try:
        r = urllib.request.urlopen(url, timeout=timeout)
        return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()

CFG = ModelConfig(
    name="obs_test", vocab_size=64, d_model=32, n_layers=3, n_heads=2,
    layer_types=("linear", "softmax", "swa"), window=4, max_seq_len=96,
    dtype="float32", backend="xla",
)
GREEDY = SampleConfig(temperature=0.0)


@pytest.fixture(scope="module")
def mp():
    model = TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return model, params


def _prompt(i, ln=5):
    return jax.random.randint(
        jax.random.PRNGKey(3000 + i), (1, ln), 0, CFG.vocab_size
    ).astype(jnp.int32)


def _ref(mp, prompt, n_new, sample, seed):
    model, params = mp
    return np.asarray(
        generate(model, params, prompt, n_new, sample,
                 rng=jax.random.PRNGKey(seed))
    )


def _cfg(tmp_path, **kw):
    kw.setdefault("chunk", 4)
    kw.setdefault("slots", 2)
    kw.setdefault("max_inflight", 8)
    return ServeConfig(**kw)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_counters_gauges_histograms_and_prometheus():
    now = [0.0]
    r = MetricsRegistry(clock=lambda: now[0])
    r.counter("ok").inc()
    r.counter("ok").inc(2)
    r.counter("ladder_rungs").inc(labels={"rung": "rewind"})
    r.gauge("depth").set(5)
    r.gauge_fn("live", lambda: 7, labels={"cache": "decode"})
    h = r.histogram("lat_ms", buckets=(1, 10, 100))
    for v in (0.5, 10, 5000):
        h.observe(v)
    assert r.counters_flat()["ok"] == 3
    snap = r.snapshot()
    gauges = {(g["name"], tuple(sorted(g["labels"].items()))): g["value"]
              for g in snap["gauges"]}
    assert gauges[("depth", ())] == 5
    assert gauges[("live", (("cache", "decode"),))] == 7
    (hist,) = snap["histograms"]
    assert hist["count"] == 3 and hist["counts"] == [1, 1, 0, 1]
    assert hist["buckets"][-1] == "+Inf"
    text = r.to_prometheus()
    assert "# TYPE ok counter" in text and "ok 3" in text
    assert 'ladder_rungs{rung="rewind"} 1' in text
    assert 'lat_ms_bucket{le="+Inf"} 3' in text and "lat_ms_count 3" in text
    # snapshot is JSON-clean (the status-op wire format)
    json.dumps(snap)


def test_registry_snapshot_is_one_consistent_read():
    """Callable gauges evaluate INSIDE the same lock acquisition as the
    counter read — a scrape can't see gauge state from after a counter
    bump it didn't see."""
    r = MetricsRegistry()
    c = r.counter("events")

    def gauge_from_counter():
        # runs under the registry lock: reads the same cells the
        # snapshot serializes
        return r._counters["events"].get((), 0)

    r.gauge_fn("events_gauge", gauge_from_counter)
    c.inc(41)
    snap = r.snapshot()
    counter = [x for x in snap["counters"] if x["name"] == "events"][0]
    gauge = [x for x in snap["gauges"] if x["name"] == "events_gauge"][0]
    assert counter["value"] == gauge["value"] == 41


def test_registry_dump_and_aggregate(tmp_path):
    a, b = MetricsRegistry(), MetricsRegistry()
    for r, n in ((a, 2), (b, 3)):
        r.counter("ok").inc(n)
        r.gauge("queue_depth").set(n)
        r.histogram("ms", buckets=(1, 10)).observe(n)
    agg = aggregate([a.snapshot(), b.snapshot()], sources=["r0", "r1"])
    rows = {row["name"]: row for row in agg["counters"]}
    assert rows["ok"]["value"] == 5
    grows = {row["name"]: row for row in agg["gauges"]}
    assert grows["queue_depth"]["value"] == 5  # gauges sum across replicas
    hrow = agg["histograms"][0]
    assert hrow["count"] == 2 and hrow["sum"] == 5
    assert agg["sources"] == ["r0", "r1"]
    text = prometheus_from_snapshot(agg)
    assert "ok 5" in text
    path = str(tmp_path / "m" / "metrics.prom")
    a.dump(path)
    assert os.path.exists(path) and os.path.exists(path + ".json")
    with open(path + ".json") as f:
        assert json.load(f)["counters"][0]["value"] == 2


def test_obs_package_never_imports_jax():
    """The structural half of obs-device-sync: the spine's modules are
    importable (and import-clean) with no jax dependency edge."""
    import sys

    for mod in ("metrics", "trace", "flight"):
        src = open(os.path.join(
            os.path.dirname(sys.modules["orion_tpu.obs"].__file__),
            f"{mod}.py",
        )).read()
        assert "import jax" not in src, mod


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_tracer_span_pairing_flush_and_merge(tmp_path):
    path = str(tmp_path / "t" / "a.jsonl")
    now = [1.0]
    tr = Tracer(path=path, clock=lambda: now[0])
    tr.begin("request", "req-1", session="conv")
    now[0] = 1.01
    tr.complete("decode_chunk", 1.005, 0.004, req="req-1", slot=0, chunk=0)
    tr.instant("ladder", id="req-1", rung="rewind")
    now[0] = 1.02
    tr.end("request", "req-1", status="ok")
    assert tr.flush() == 4
    events = read_jsonl(path)
    assert [e["ph"] for e in events] == ["b", "X", "i", "e"]
    pairs = span_pairs(events)
    assert len(pairs[("request", "req-1", "request")]["b"]) == 1
    assert len(pairs[("request", "req-1", "request")]["e"]) == 1
    x = events[1]
    assert x["dur"] == pytest.approx(4000) and x["args"]["slot"] == 0
    # a second process's file concatenates + merges into Perfetto shape
    path2 = str(tmp_path / "t" / "b.jsonl")
    tr2 = Tracer(path=path2, clock=lambda: 2.0)
    tr2.begin("turn", "conv:1", cat="fleet", session="conv")
    tr2.end("turn", "conv:1", cat="fleet", status="ok")
    tr2.flush()
    out = str(tmp_path / "t" / "merged.json")
    n = merge_traces([path, path2, str(tmp_path / "missing.jsonl")], out)
    assert n == 6
    with open(out) as f:
        doc = json.load(f)
    assert set(doc) >= {"traceEvents", "displayTimeUnit"}
    assert len(doc["traceEvents"]) == 6
    ts = [e["ts"] for e in doc["traceEvents"]]
    assert ts == sorted(ts), "merged events must be time-ordered"


def test_tracer_disabled_is_inert_and_ring_is_bounded():
    tr = Tracer(path=None, enabled=False)
    tr.begin("request", "x")
    assert tr.events() == []
    small = Tracer(path=None, capacity=4)
    for i in range(10):
        small.instant("e", i=i)
    assert len(small.events()) == 4 and small.dropped == 6


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_ring_bounded_dump_and_triggers(tmp_path):
    now = [5.0]
    rec = FlightRecorder(capacity=3, clock=lambda: now[0],
                         dump_dir=str(tmp_path / "fl"))
    for i in range(5):
        rec.record("beat", i=i)
    evs = rec.events()
    assert [e["i"] for e in evs] == [2, 3, 4] and rec.dropped == 2
    p1 = rec.dump("health-degraded")
    now[0] = 6.0
    rec.record("beat", i=99)
    p2 = rec.dump("health-degraded")
    assert p1 != p2, "each trigger writes its OWN file"
    # a SECOND recorder (another replica) dumping the same reason into
    # the same dir must not clobber the first one's files
    other = FlightRecorder(dump_dir=str(tmp_path / "fl"))
    other.record("beat", i=-1)
    p3 = other.dump("health-degraded")
    assert p3 not in (p1, p2)
    assert os.path.exists(p1) and os.path.exists(p2)
    with open(p2) as f:
        doc = json.load(f)
    assert doc["reason"] == "health-degraded" and doc["dropped"] == 3
    assert doc["events"][-1]["i"] == 99
    # no dump_dir -> ring only, dump is a no-op
    assert FlightRecorder().dump("x") is None


def test_flight_subscribes_to_inject_deliveries():
    rec = FlightRecorder()
    rec.attach_inject()
    try:
        plan = inject.FaultPlan().add("serve.chunk", step=3)
        with inject.inject(plan):
            inject.fire("serve.chunk", step=2)  # not armed: no delivery
            inject.fire("serve.chunk", step=3)
    finally:
        rec.detach_inject()
    faults = rec.events("fault")
    assert [(e["site"], e["step"]) for e in faults] == [("serve.chunk", 3)]
    # detached: further deliveries leave no event
    with inject.inject(inject.FaultPlan().add("serve.chunk")):
        inject.fire("serve.chunk", step=0)
    assert len(rec.events("fault")) == 1


# ---------------------------------------------------------------------------
# server migration: stats contract, new gauges, occupancy split
# ---------------------------------------------------------------------------


def test_server_stats_ride_the_registry(mp, tmp_path):
    model, params = mp
    srv = Server(model, params, _cfg(tmp_path))
    for i in range(3):
        srv.submit(DecodeRequest(prompt=_prompt(i), max_new_tokens=8,
                                 sample=GREEDY, seed=i))
    assert srv.serve(drain_when_idle=True) == 0
    # the PR 4-8 dict contract, now registry-backed
    assert srv.stats["ok"] == 3 and srv.stats["admitted"] == 3
    snap = srv.snapshot()
    assert snap["stats"]["ok"] == 3
    # the new gauges we used to fly blind on
    m = snap["metrics"]
    gauges = {(g["name"], tuple(sorted(g["labels"].items()))): g["value"]
              for g in m["gauges"]}
    assert gauges[("queue_depth", ())] == 0
    assert gauges[("slots", (("state", "active"),))] == 0
    assert gauges[("slots", (("state", "free"),))] == 2
    caches = [g for g in m["gauges"] if g["name"] == "compile_cache_entries"]
    assert {g["labels"]["cache"] for g in caches} == {
        # one gauge per entry of generate.DECODE_PROGRAMS (ISSUE 15
        # made that registry the single naming source)
        "decode_batched", "unified_prefill", "prefill_bucketed",
        "spec_round", "prefill_piece_donated", "decode_scan_donated",
    }
    assert any(g["value"] > 0 for g in caches), "the engine compiled SOMETHING"
    hists = {h["name"]: h for h in m["histograms"]}
    assert hists["chunk_ms"]["count"] == srv.stats["chunks"] > 0
    text = srv.metrics.to_prometheus()
    assert "# TYPE ok counter" in text and "chunk_ms_bucket" in text
    srv.close()


def test_occupancy_instantaneous_vs_lifetime(mp, tmp_path):
    model, params = mp
    srv = Server(model, params, _cfg(tmp_path))
    assert srv.occupancy() == 0.0 and srv.occupancy_lifetime() == 0.0
    seen = []
    real_step = srv.engine.step

    def spying_step():
        seen.append(srv.occupancy())  # mid-run: slots ARE live
        return real_step()

    srv.engine.step = spying_step
    srv.submit(DecodeRequest(prompt=_prompt(0), max_new_tokens=8,
                             sample=GREEDY, seed=0))
    assert srv.serve(drain_when_idle=True) == 0
    srv.engine.step = real_step
    assert seen and max(seen) == 0.5, "1 of 2 slots live mid-run"
    assert srv.occupancy() == 0.0, "instantaneous: drained engine is empty"
    assert 0.0 < srv.occupancy_lifetime() <= 1.0
    srv.close()


def test_session_store_latency_histograms(mp, tmp_path):
    model, params = mp
    cfg = _cfg(tmp_path, session_dir=str(tmp_path / "s"))
    srv1 = Server(model, params, cfg)
    srv1.submit(DecodeRequest(prompt=_prompt(0), max_new_tokens=8,
                              sample=GREEDY, seed=0, session_id="conv"))
    assert srv1.serve(drain_when_idle=True) == 0
    assert srv1._h_session_save_ms.cell()["count"] >= 1
    srv1.close()
    srv2 = Server(model, params, cfg)  # fresh replica: resume hits disk
    srv2.submit(DecodeRequest(prompt=np.zeros((1, 0), np.int32),
                              max_new_tokens=4, sample=GREEDY, seed=0,
                              session_id="conv"))
    assert srv2.serve(drain_when_idle=True) == 0
    assert srv2._h_session_load_ms.cell()["count"] >= 1
    srv2.close()


# ---------------------------------------------------------------------------
# THE acceptance chaos run: staggered admission, ladder rung 2, SIGTERM
# suspend, cross-replica resume — complete span pairing, nested chunks,
# session-linked turns, flight dumps at every trigger
# ---------------------------------------------------------------------------


def _request_spans(events):
    return {
        key: v for key, v in span_pairs(events).items()
        if key[2] == "request"
    }


def test_chaos_run_trace_complete_and_flight_dumps(mp, tmp_path):
    model, params = mp
    want = 24
    trace_path = str(tmp_path / "trace.jsonl")
    flight_dir = str(tmp_path / "flight")
    tracer = Tracer(path=trace_path, clock=time.monotonic)
    cfg = _cfg(tmp_path, session_dir=str(tmp_path / "s"),
               flight_dir=flight_dir,
               metrics_path=str(tmp_path / "metrics.prom"),
               metrics_interval_s=0.0)
    sid = "conv"
    refs = {
        "sess": _ref(mp, _prompt(0), want, GREEDY, seed=7),
        "plain": _ref(mp, _prompt(1, ln=4), 16, GREEDY, seed=8),
    }
    # ---- replica 1: two staggered admissions (different lengths →
    # different in-scan staging walks). The SESSIONLESS request (slot 1)
    # is poisoned twice at its chunk 2, so it walks ladder rung 2 and
    # COMPLETES degraded before the drain (SERVING -> DEGRADED fires its
    # dump); SIGTERM at boundary 4 then suspends the session MID-stream
    # while the plain request has already drained to completion.
    srv1 = Server(model, params, cfg, tracer=tracer)
    p_sess = srv1.submit(DecodeRequest(
        prompt=_prompt(0), max_new_tokens=want, sample=GREEDY, seed=7,
        session_id=sid,
    ))
    p_plain = srv1.submit(DecodeRequest(
        prompt=_prompt(1, ln=4), max_new_tokens=16, sample=GREEDY, seed=8,
    ))
    plan = (
        inject.FaultPlan()
        .poison_decode_slot_at(1, 2, times=2)
        .preempt_at_chunk(4)
    )
    with inject.inject(plan):
        rc = srv1.serve()
    assert rc == 0 and srv1.health.state is Health.DEAD
    assert p_sess.result is not None and p_sess.result.status == "suspended"
    assert 0 < p_sess.result.new_tokens < want
    assert p_plain.result is not None and p_plain.result.status == "ok"
    np.testing.assert_array_equal(p_plain.result.tokens, refs["plain"])
    # metrics exposition happened on drain (interval 0 = on-drain only);
    # checked before replica 2 rewrites the scrape with its own registry
    assert os.path.exists(cfg.metrics_path)
    assert "ladder_rungs" in open(cfg.metrics_path).read()
    # ---- replica 2 (fresh server over the same store + tracer file):
    # the resumed turn must link to the original conversation
    import dataclasses

    cfg2 = dataclasses.replace(
        cfg, metrics_path=str(tmp_path / "metrics2.prom")
    )
    srv2 = Server(model, params, cfg2, tracer=tracer)
    p_cont = srv2.submit(DecodeRequest(
        prompt=np.zeros((1, 0), np.int32),
        max_new_tokens=want - p_sess.result.new_tokens,
        sample=GREEDY, seed=0, session_id=sid,
    ))
    assert srv2.serve(drain_when_idle=True) == 0
    assert p_cont.result.status == "ok"
    np.testing.assert_array_equal(
        np.concatenate([p_sess.result.tokens, p_cont.result.tokens], axis=1),
        refs["sess"],
    )
    srv2.close()

    # ---- trace assertions ----
    events = read_jsonl(trace_path)
    req_spans = _request_spans(events)
    assert len(req_spans) == 3, "three requests -> three request spans"
    for key, pair in span_pairs(events).items():
        assert len(pair["b"]) == len(pair["e"]) == 1, (
            f"span {key} must pair begin/end exactly once"
        )
    # chunk events nest inside their request's span
    by_rid = {key[1]: pair for key, pair in req_spans.items()}
    chunk_events = [e for e in events
                    if e["ph"] == "X" and e["cat"] == "chunk"]
    assert chunk_events, "chunk boundaries must leave complete events"
    for ev in chunk_events:
        rid = ev["args"]["req"]
        assert rid in by_rid, f"chunk event {ev} orphaned from any request"
        b = by_rid[rid]["b"][0]
        e = by_rid[rid]["e"][0]
        assert b["ts"] <= ev["ts"] and ev["ts"] + ev["dur"] <= e["ts"], (
            "chunk events must nest inside their request span"
        )
    # both prefill and decode phases appear (in-scan staging is on)
    assert {e["name"] for e in chunk_events} >= {
        "prefill_piece", "decode_chunk",
    }
    # the resumed turn links to the original session id, across servers
    sess_spans = [
        key for key in req_spans if key[1].startswith(f"{sid}:")
    ]
    assert len(sess_spans) == 2, "turn 1 + resumed turn, one conversation"
    for key in sess_spans:
        assert req_spans[key]["b"][0]["args"]["session"] == sid
    # ladder rungs are visible as instants tied to the poisoned request
    ladder = [e for e in events if e["name"] == "ladder"]
    assert ladder and all(e["args"]["rung"] for e in ladder)

    # ---- flight-recorder assertions ----
    # filenames are flight-<recorder token>-<seq>-<reason>.json: the
    # token keeps replicas sharing one dump_dir from clobbering each
    # other's black boxes
    dumps = sorted(os.listdir(flight_dir))
    reasons = {d.split("-", 3)[3].rsplit(".", 1)[0] for d in dumps}
    assert {"health-degraded", "health-draining", "health-dead"} <= reasons, (
        f"every trigger must dump: {dumps}"
    )
    # the drain dump carries every fired fault site (site⇄event parity)
    drain_dump = [d for d in dumps if "health-draining" in d][0]
    with open(os.path.join(flight_dir, drain_dump)) as f:
        doc = json.load(f)
    fault_sites = {e["site"] for e in doc["events"] if e["kind"] == "fault"}
    assert fault_sites >= {"decode.slot_nan.1", "serve.chunk"}, (
        "fired injection sites must appear in the black box"
    )
    kinds = {e["kind"] for e in doc["events"]}
    assert {"admit", "ladder", "health"} <= kinds


def test_ladder_exhaustion_dumps_flight(mp, tmp_path):
    model, params = mp
    cfg = _cfg(tmp_path, flight_dir=str(tmp_path / "fl"))
    srv = Server(model, params, cfg)
    srv.submit(DecodeRequest(prompt=_prompt(0), max_new_tokens=8,
                             sample=GREEDY, seed=0))
    plan = inject.FaultPlan().poison_decode_slot_at(0, 1, times=-1)
    with inject.inject(plan):
        assert srv.serve(drain_when_idle=True) == 0
    assert srv.stats["failed"] == 1
    dumps = os.listdir(str(tmp_path / "fl"))
    assert any("ladder-exhausted" in d for d in dumps), dumps
    exhausted = [e for e in srv.flight.events("ladder")
                 if e["rung"] == "exhausted"]
    assert exhausted, "the exhausted rung must be in the ring"
    srv.close()


def test_full_telemetry_adds_zero_compiles(mp, tmp_path):
    """The acceptance cache-stat: a warmed engine shape re-served with
    metrics + tracing + flight fully on leaves every decode/prefill jit
    cache EXACTLY as the dark run left it — telemetry is host
    bookkeeping, never a new program."""
    model, params = mp

    def run(cfg, tracer=None):
        srv = Server(model, params, cfg, tracer=tracer)
        for i in range(3):
            srv.submit(DecodeRequest(prompt=_prompt(i, ln=3 + i),
                                     max_new_tokens=12, sample=GREEDY,
                                     seed=i))
        assert srv.serve(drain_when_idle=True) == 0
        assert srv.stats["ok"] == 3
        srv.close()
        return srv

    dark = _cfg(tmp_path)
    run(dark)  # warm every compile this shape needs
    sizes = lambda: (  # noqa: E731
        _decode_batched_chunk_jit._cache_size(),
        _decode_batched_prefill_chunk_jit._cache_size(),
        _prefill_carry_bucketed_jit._cache_size(),
    )
    before = sizes()
    lit = _cfg(
        tmp_path,
        metrics_path=str(tmp_path / "m.prom"), metrics_interval_s=0.1,
        trace_path=str(tmp_path / "t.jsonl"),
        flight_dir=str(tmp_path / "fl2"),
    )
    srv = run(lit, tracer=Tracer(path=str(tmp_path / "t.jsonl"),
                                 clock=time.monotonic))
    assert sizes() == before, "telemetry must add ZERO compiles"
    # and the telemetry actually ran — this wasn't a dark pass
    assert read_jsonl(str(tmp_path / "t.jsonl"))
    # chunk_ms cells carry the tp footprint label since ISSUE 14
    assert srv._h_chunk_ms.cell(labels={"tp": "1"})["count"] > 0


# ---------------------------------------------------------------------------
# fleet: aggregated status over the control channel
# ---------------------------------------------------------------------------


def test_fleet_aggregates_child_registries_and_roots_spans(mp, tmp_path):
    from orion_tpu.fleet.replica import LocalReplica
    from orion_tpu.fleet.supervisor import Supervisor

    model, params = mp
    tracer = Tracer(path=None, clock=time.monotonic)

    def factory(name):
        return LocalReplica(model, params, _cfg(tmp_path), name=name).start()

    sup = Supervisor(factory, 2, tracer=tracer).start()
    try:
        pendings = [
            sup.router.submit(DecodeRequest(
                prompt=_prompt(i), max_new_tokens=8, sample=GREEDY, seed=i,
            ))
            for i in range(4)
        ]
        for p in pendings:
            assert p.wait(timeout=60.0) is not None
        agg = sup.aggregate_metrics()
        rows = {row["name"]: row["value"] for row in agg["counters"]
                if not row["labels"]}
        assert rows["ok"] == 4, "fleet view sums child registries"
        assert agg["replicas"] == 2 and len(agg["by_source"]) == 2
        # per-replica breakdown rides along for the drill-down
        per = {
            name: {c["name"]: c["value"] for c in snap["counters"]
                   if not c["labels"]}
            for name, snap in agg["by_source"].items()
        }
        assert sum(d.get("ok", 0) for d in per.values()) == 4
    finally:
        sup.drain_all(timeout=30.0)
    # the router opened (and closed) one root span per dispatched turn
    pairs = {k: v for k, v in span_pairs(tracer.events()).items()
             if k[2] == "turn"}
    assert len(pairs) == 4
    for key, pair in pairs.items():
        assert len(pair["b"]) == len(pair["e"]) == 1, key
        assert pair["e"][0]["args"]["status"] == "ok"


# ---------------------------------------------------------------------------
# ISSUE 10: interpolated quantiles (property test vs numpy.percentile)
# ---------------------------------------------------------------------------


def test_quantile_property_vs_numpy_percentile():
    """The satellite's property test: across random sample sets and
    bucket layouts, the bucket-interpolated estimate is within ONE
    bucket width of the exact ``numpy.percentile`` — the method that
    matches bucket semantics is ``inverted_cdf`` (the value at rank
    ceil(q*n); the default "linear" method interpolates BETWEEN samples,
    which no histogram can resolve). Includes the +Inf overflow bucket
    (clamps to the last finite bound) and the empty/single-sample
    edges."""
    import bisect
    import math

    rng = np.random.default_rng(42)
    layouts = [
        (1, 2, 5, 10, 20, 50, 100, math.inf),
        (0.5, 4, 32, 256, math.inf),
        tuple(range(1, 91, 3)) + (math.inf,),
    ]
    for buckets in layouts:
        finite_top = buckets[-2]
        for trial in range(60):
            n = rng.integers(1, 250)
            samples = rng.uniform(0, finite_top * 1.2, size=n)
            counts = [0] * len(buckets)
            for s in samples:
                i = bisect.bisect_left(buckets, s)
                counts[min(i, len(buckets) - 1)] += 1
            for q in (0.0, 0.5, 0.9, 0.95, 0.99, 1.0):
                est = quantile_from_counts(buckets, counts, q)
                true = float(np.percentile(
                    samples, q * 100, method="inverted_cdf"
                ))
                if true > finite_top:
                    # the true quantile landed in the overflow bucket:
                    # the estimator must CLAMP to the last finite bound,
                    # never invent a larger number
                    assert est == finite_top, (buckets, q, est, true)
                    continue
                i = min(bisect.bisect_left(buckets, true),
                        len(buckets) - 1)
                lo = buckets[i - 1] if i > 0 else 0.0
                hi = buckets[i] if buckets[i] != math.inf else finite_top
                assert abs(est - true) <= (hi - lo) + 1e-9, (
                    buckets, trial, q, est, true
                )
    # edges: empty cell -> None; single sample lands in its own bucket
    assert quantile_from_counts((1, 2, math.inf), [0, 0, 0], 0.99) is None
    one = quantile_from_counts((1, 2, 5, math.inf), [0, 1, 0, 0], 0.5)
    assert 1.0 <= one <= 2.0
    # everything in the overflow bucket: the last finite bound
    assert quantile_from_counts((1, 2, math.inf), [0, 0, 7], 0.5) == 2.0


def test_windowed_histogram_slides_and_forgets():
    """The rolling window sees the last W seconds, not the lifetime: a
    burst of slow observations dominates the windowed p99 while inside
    the window and vanishes once the window slides past it — the exact
    property lifetime histograms lack."""
    now = [0.0]
    reg = MetricsRegistry(clock=lambda: now[0])
    h = reg.histogram("lat", buckets=(1, 10, 100, 1000))
    wh = WindowedHistogram(
        h.buckets, lambda: tuple((h.cell() or {"counts": [0] * len(
            h.buckets)})["counts"]),
        clock=lambda: now[0], slice_s=0.5, keep_s=20.0,
    )
    for _ in range(6):  # 3s of fast traffic
        now[0] += 0.5
        h.observe(2.0)
        wh.tick()
    assert wh.quantile(0.99, window_s=3.0) <= 10.0
    for _ in range(4):  # 2s of slow traffic
        now[0] += 0.5
        h.observe(500.0)
        wh.tick()
    assert wh.quantile(0.99, window_s=2.0) > 100.0
    # window slides past the slow burst: only fresh fast traffic remains
    for _ in range(10):
        now[0] += 0.5
        h.observe(2.0)
        wh.tick()
    assert wh.quantile(0.99, window_s=2.0) <= 10.0
    # the lifetime histogram, by contrast, still remembers the burst
    assert quantile_from_counts(
        h.buckets, h.cell()["counts"], 0.99
    ) > 100.0


def test_slo_engine_multiwindow_burn_and_budget():
    """Deterministic fake-clock walk through the SLOEngine: good
    traffic never alerts, sustained badness fires fast AND slow alerts
    (the fast window detects, the slow window confirms), recovery
    clears them as the windows slide, and the error budget recovers on
    a fresh engine (the supervisor's respawn dividend)."""
    now = [0.0]
    reg = MetricsRegistry(clock=lambda: now[0])
    ok, failed = reg.counter("ok"), reg.counter("failed")
    obj = Objective(
        name="errs", kind="error_rate", target=0.9,
        fast_window_s=1.0, slow_window_s=4.0, fast_burn=5.0, slow_burn=2.0,
    )
    eng = SLOEngine([obj], registry_readers(reg),
                    clock=lambda: now[0], slice_s=0.25)
    for _ in range(8):  # 2s of clean traffic
        now[0] += 0.25
        ok.inc(5)
        st = eng.tick()
    assert st["firing_fast"] == [] and st["firing_slow"] == []
    assert st["objectives"]["errs"]["budget_remaining"] == 1.0
    for _ in range(8):  # 2s of 100% failures
        now[0] += 0.25
        failed.inc(5)
        st = eng.tick()
    assert st["firing_fast"] == ["errs"] and st["firing_slow"] == ["errs"]
    assert st["objectives"]["errs"]["burn_fast"] >= 5.0
    assert st["objectives"]["errs"]["budget_remaining"] < 1.0
    burned = st["objectives"]["errs"]["budget_remaining"]
    for _ in range(24):  # 6s of recovery: both windows slide clean
        now[0] += 0.25
        ok.inc(5)
        st = eng.tick()
    assert st["firing_fast"] == [] and st["firing_slow"] == []
    # lifetime budget stays spent on THIS engine...
    assert st["objectives"]["errs"]["budget_remaining"] <= burned + 0.2
    # ...and is whole again on a fresh one (what a respawn buys)
    reg2 = MetricsRegistry(clock=lambda: now[0])
    eng2 = SLOEngine([obj], registry_readers(reg2), clock=lambda: now[0])
    assert eng2.tick()["objectives"]["errs"]["budget_remaining"] == 1.0


def test_slo_check_cli_gates_a_dumped_snapshot(tmp_path, capsys):
    """The CI gate: ``python -m orion_tpu.obs.slo check`` evaluates a
    dumped registry snapshot against declared objectives and exits
    nonzero on violation (and zero on a clean run / no data)."""
    objectives = [
        {"name": "turn_p99", "kind": "latency", "latency_ms": 100.0,
         "target": 0.9},
        {"name": "errs", "kind": "error_rate", "target": 0.9},
    ]
    obj_path = str(tmp_path / "objectives.json")
    with open(obj_path, "w") as f:
        json.dump(objectives, f)

    def dump_registry(ok_n, failed_n, lat_ms):
        reg = MetricsRegistry()
        reg.counter("ok").inc(ok_n)
        reg.counter("failed").inc(failed_n)
        h = reg.histogram("turn_latency_ms")
        for _ in range(ok_n + failed_n):
            h.observe(lat_ms)
        path = str(tmp_path / "m.prom")
        reg.dump(path)
        return path + ".json"

    snap = dump_registry(99, 0, lat_ms=8.0)
    assert obs_slo.main(["check", "--objectives", obj_path, snap]) == 0
    out = capsys.readouterr().out
    assert "OK" in out and "turn_p99" in out
    # now a violating run: 20% failures and slow turns
    snap = dump_registry(8, 2, lat_ms=5000.0)
    assert obs_slo.main(["check", "--objectives", obj_path, snap,
                         "--format", "json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["ok"] is False
    by_name = {r["name"]: r for r in doc["objectives"]}
    assert by_name["errs"]["status"] == "violated"
    assert by_name["turn_p99"]["status"] == "violated"
    # a run that never exercised the path passes with no_data
    reg = MetricsRegistry()
    reg.dump(str(tmp_path / "empty.prom"))
    assert obs_slo.main(["check", "--objectives", obj_path,
                         str(tmp_path / "empty.prom.json")]) == 0


def test_slo_check_cli_sums_labelled_chunk_cells(tmp_path, capsys):
    """Regression (ISSUE 15 satellite): chunk_ms cells carry a ``tp``
    footprint label since ISSUE 14 — a ``chunk``-source latency
    objective evaluated from a DUMPED snapshot must sum every label
    cell (mirroring ``Histogram.cell_total``), not skip or pick one.
    Pinned both directions: the summed cells pass a threshold the tp=1
    cell alone would pass, and fail one the tp=2 cell pushes over."""
    objectives = [{"name": "chunk_p", "kind": "latency",
                   "latency_ms": 4.0, "source": "chunk", "target": 0.6}]
    obj_path = str(tmp_path / "obj.json")
    with open(obj_path, "w") as f:
        json.dump(objectives, f)

    def dump_registry(slow_tp2):
        reg = MetricsRegistry()
        h = reg.histogram("chunk_ms", buckets=(1, 2, 5, 10))
        for _ in range(8):
            h.observe(1.5, labels={"tp": "1"})  # all under 4 ms
        for _ in range(8 if slow_tp2 else 1):
            h.observe(8.0, labels={"tp": "2"})  # all over 4 ms
        path = str(tmp_path / "chunk.prom")
        reg.dump(path)
        return path + ".json"

    # 8 good + 1 bad across BOTH cells = 89% good: passes 0.6 — and the
    # events count proves the tp cells were summed, not dropped
    snap = dump_registry(slow_tp2=False)
    assert obs_slo.main(["check", "--objectives", obj_path, snap,
                         "--format", "json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    row = doc["objectives"][0]
    assert row["status"] == "ok" and row["events"] == 9
    # 8 good + 8 bad = 50% good: the tp=2 cell must drag it to violated
    snap = dump_registry(slow_tp2=True)
    assert obs_slo.main(["check", "--objectives", obj_path, snap]) == 1
    out = capsys.readouterr().out
    assert "violated" in out and "chunk_p" in out


# ---------------------------------------------------------------------------
# ISSUE 10: live endpoints — /healthz tracks the machine, scrapes are free
# ---------------------------------------------------------------------------


def test_healthz_code_tracks_every_health_transition(mp, tmp_path):
    """The acceptance: under the PR 4 chaos scenarios (ladder rung via
    slot poisoning, SIGTERM mid-stream), the live /healthz endpoint's
    status code tracks every HealthMachine state it passes through —
    STARTING/DRAINING/DEAD say 503 (don't route here), SERVING/DEGRADED
    say 200 — matching the documented health.HTTP_STATUS map exactly."""
    model, params = mp
    cfg = _cfg(tmp_path, metrics_port=0)
    srv = Server(model, params, cfg)
    url = f"http://127.0.0.1:{srv.http_port}"
    code, body = _get(url + "/healthz")
    assert code == 503 and json.loads(body)["state"] == "starting"
    # two staggered requests: the SHORT one walks ladder rung 2 and
    # completes degraded early (SERVING -> DEGRADED while the long one
    # still decodes); SIGTERM later turns the tail into a pollable
    # DRAINING window; serve.chunk_delay stretches every boundary so
    # each state's window is reliably observable
    srv.submit(DecodeRequest(prompt=_prompt(0), max_new_tokens=16,
                             sample=GREEDY, seed=0))
    srv.submit(DecodeRequest(prompt=_prompt(1, ln=4), max_new_tokens=48,
                             sample=GREEDY, seed=1))
    plan = (
        inject.FaultPlan()
        .poison_decode_slot_at(0, 1, times=2)
        .preempt_at_chunk(9)
        .delay_chunk(0.05, times=-1)
    )
    seen = {}
    stop = threading.Event()

    def poll():
        while not stop.is_set():
            code, body = _get(url + "/healthz")
            seen[json.loads(body)["state"]] = code
            time.sleep(0.01)

    poller = threading.Thread(target=poll, daemon=True)
    poller.start()
    try:
        with inject.inject(plan):
            rc = srv.serve()
    finally:
        stop.set()
        poller.join(timeout=5.0)
    assert rc == 0 and srv.health.state is Health.DEAD
    code, body = _get(url + "/healthz")
    payload = json.loads(body)
    assert code == 503 and payload["state"] == "dead"
    seen["dead"] = code
    # every observed state reported its documented code...
    for state, got in seen.items():
        assert got == HTTP_STATUS[Health(state)], (state, got)
    # ...and the chaos walk actually visited the interesting ones
    assert {"serving", "degraded", "draining", "dead"} <= set(seen), seen
    srv.close()
    with pytest.raises(Exception):
        _get(url + "/healthz", timeout=1.0)  # endpoint down after close


def test_healthz_body_carries_store_outage_reason(mp, tmp_path):
    """ISSUE 17: a load balancer polling /healthz during a store outage
    must see WHY the replica is degraded — the body's ``status`` field
    carries the failure-domain reason (``degraded: store-outage:session``)
    while the code stays 200 (degraded still serves), and the status
    returns to plain ``serving`` once the breaker closes."""
    model, params = mp
    cfg = _cfg(tmp_path, metrics_port=0,
               session_dir=str(tmp_path / "sessions"),
               breaker_failures=1, breaker_backoff=0.02,
               breaker_max_backoff=0.05)
    srv = Server(model, params, cfg)
    url = f"http://127.0.0.1:{srv.http_port}"
    try:
        srv.submit(DecodeRequest(prompt=_prompt(0), max_new_tokens=4,
                                 sample=GREEDY, seed=0))
        assert srv.serve(drain_when_idle=True) == 0
        code, body = _get(url + "/healthz")
        assert code == 200 and json.loads(body)["status"] == "serving"
        # the session store dies: one failure trips the breaker, the
        # next health sweep latches DEGRADED with the domain reason
        br = srv.session_store.breaker
        br.record_failure("induced outage")
        assert srv.serve(drain_when_idle=True) == 0
        code, body = _get(url + "/healthz")
        payload = json.loads(body)
        assert code == HTTP_STATUS[Health.DEGRADED] == 200
        assert payload["state"] == "degraded"
        assert payload["status"] == "degraded: store-outage:session"
        # recovery: past the backoff the half-open probe succeeds, the
        # breaker closes, and the next sweep restores plain "serving"
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and br.state != "closed":
            if br.allow():
                br.record_success()
            time.sleep(0.01)
        assert br.state == "closed"
        assert srv.serve(drain_when_idle=True) == 0
        code, body = _get(url + "/healthz")
        assert code == 200 and json.loads(body)["status"] == "serving"
    finally:
        srv.close()


def test_live_scrape_mid_stream_adds_zero_compiles(mp, tmp_path):
    """The zero-cost acceptance: serving with the HTTP endpoint live and
    scraped mid-stream (every ~20 ms, all four routes) leaves all three
    decode/prefill jit caches EXACTLY as the dark run left them — a
    scrape reads host snapshots, never a device value."""
    model, params = mp

    def run(cfg):
        srv = Server(model, params, cfg)
        for i in range(3):
            srv.submit(DecodeRequest(prompt=_prompt(i, ln=3 + i),
                                     max_new_tokens=12, sample=GREEDY,
                                     seed=i))
        assert srv.serve(drain_when_idle=True) == 0
        assert srv.stats["ok"] == 3
        return srv

    run(_cfg(tmp_path)).close()  # warm every compile this shape needs
    sizes = lambda: (  # noqa: E731
        _decode_batched_chunk_jit._cache_size(),
        _decode_batched_prefill_chunk_jit._cache_size(),
        _prefill_carry_bucketed_jit._cache_size(),
    )
    before = sizes()
    srv = Server(model, params, _cfg(tmp_path, metrics_port=0))
    url = f"http://127.0.0.1:{srv.http_port}"
    hits = {"metrics": 0, "slo": 0, "statusz": 0, "healthz": 0}
    stop = threading.Event()

    def scrape():
        while not stop.is_set():
            for route in hits:
                code, _ = _get(f"{url}/{route}")
                if code in (200, 503):
                    hits[route] += 1
            time.sleep(0.02)

    scraper = threading.Thread(target=scrape, daemon=True)
    scraper.start()
    try:
        for i in range(3):
            srv.submit(DecodeRequest(prompt=_prompt(i, ln=3 + i),
                                     max_new_tokens=12, sample=GREEDY,
                                     seed=i))
        assert srv.serve(drain_when_idle=True) == 0
    finally:
        stop.set()
        scraper.join(timeout=5.0)
    assert sizes() == before, "a live scrape must add ZERO compiles"
    assert all(n > 0 for n in hits.values()), hits
    # the endpoint (still live) now exposes the turns it served
    code, body = _get(url + "/metrics")
    assert code == 200 and "turn_latency_ms_bucket" in body
    srv.close()


# ---------------------------------------------------------------------------
# ISSUE 10: actuation — degrade + shed on the server, the fleet loop
# ---------------------------------------------------------------------------

_CHUNK_SLO = (
    {"name": "chunk_lat", "kind": "latency", "source": "chunk",
     "latency_ms": 8.0, "target": 0.9,
     "fast_window_s": 0.25, "slow_window_s": 0.75, "fast_burn": 5.0},
)


def test_slo_fast_burn_degrades_and_sheds_early(mp, tmp_path):
    """Actuation, single-server half: sustained injected chunk latency
    (site serve.chunk_delay) fires the fast-burn alert; after
    slo_degrade_ticks boundaries the server degrades itself with the
    burn as the recorded reason AND halves its effective admission
    bound — a submit that would have queued sheds with the SLO in the
    message."""
    model, params = mp
    cfg = _cfg(tmp_path, slots=2, max_inflight=8, slo=_CHUNK_SLO,
               slo_degrade_ticks=3)
    srv = Server(model, params, cfg)
    # one long request keeps a slot busy for the whole walk
    srv.submit(DecodeRequest(prompt=_prompt(0), max_new_tokens=64,
                             sample=GREEDY, seed=0))
    plan = inject.FaultPlan().delay_chunk(0.04, times=-1)
    overloads = []
    with inject.inject(plan):
        th = threading.Thread(
            target=lambda: srv.serve(drain_when_idle=True), daemon=True
        )
        th.start()
        deadline = time.monotonic() + 30.0
        while not srv._slo_shedding and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv._slo_shedding, "sustained burn must arm early shedding"
        # the queue bound HALVED: 4 queue, the 5th sheds citing the SLO
        for i in range(8):
            try:
                srv.submit(DecodeRequest(
                    prompt=_prompt(10 + i), max_new_tokens=4,
                    sample=GREEDY, seed=100 + i,
                ))
            except OverloadError as e:
                overloads.append(str(e))
        th.join(timeout=60.0)
    assert not th.is_alive()
    assert overloads and "slo fast burn" in overloads[0], overloads
    # health degraded with the burn as the reason, alert counted,
    # black-boxed
    transitions = [
        (a.value if a else None, b.value, r)
        for a, b, r, _ in srv.health.history
    ]
    assert any("slo fast burn" in r for _, _, r in transitions), transitions
    assert srv.metrics.counter("slo_alerts").value(
        labels={"alert": "fast"}
    ) >= 1
    slo_events = srv.flight.events("slo")
    assert any(e.get("alert") == "shedding" for e in slo_events)
    srv.close()


def test_watchdog_stall_dumps_flight(mp, tmp_path):
    """Satellite bugfix regression: a Watchdog stall detection is a
    flight-recorder dump trigger (via the observer tap) — PR 9 dumped on
    health transitions, ladder exhaustion and nan-halt, but a hang
    detection left no black box."""
    model, params = mp
    fl = str(tmp_path / "fl")
    cfg = _cfg(tmp_path, stall_timeout=0.3, flight_dir=fl)
    srv = Server(model, params, cfg)
    real_step = srv.engine.step
    stalled = []

    def wedged_step():
        if not stalled:
            stalled.append(1)
            time.sleep(1.0)  # a wedged scan: no beat for > stall_timeout
        return real_step()

    srv.engine.step = wedged_step
    srv.submit(DecodeRequest(prompt=_prompt(0), max_new_tokens=8,
                             sample=GREEDY, seed=0))
    assert srv.serve(drain_when_idle=True) == 0
    srv.engine.step = real_step
    assert srv.stats["stalls"] >= 1
    dumps = os.listdir(fl)
    assert any("watchdog-stall" in d for d in dumps), dumps
    # the dump carries the stall event itself
    stall_dump = [d for d in dumps if "watchdog-stall" in d][0]
    with open(os.path.join(fl, stall_dump)) as f:
        doc = json.load(f)
    assert any(
        e["kind"] == "watchdog" and e.get("event") == "stall"
        for e in doc["events"]
    )
    srv.close()


class _FakeReplica:
    """Scripted ReplicaHandle stand-in for the router-policy unit test."""

    def __init__(self, name, inflight=0, state="serving", slo=None):
        from orion_tpu.fleet.replica import ReplicaHandle

        self.name = name
        self._inflight = inflight
        self._state = state
        self.last_status = {"state": state, "slo": slo or {}}
        self.slo_penalty = ReplicaHandle.slo_penalty.__get__(self)

    @property
    def alive(self):
        return True

    @property
    def inflight(self):
        return self._inflight

    def health_state(self):
        return self._state

    @property
    def routable(self):
        return self._state in ("starting", "serving", "degraded")


def test_router_tie_break_is_latency_aware_after_health_and_load():
    """Unit pin of the sort key: (health rank, inflight, slo penalty,
    index). Equal rank+load resolves AWAY from the replica whose window
    is slow or burning — but a slow IDLE replica still beats a fast
    BUSY one (inflight dominates), and health rank dominates both."""
    from orion_tpu.fleet.router import Router

    slow = {"firing_fast": ["lat"], "p99_ms": 900.0}
    fast = {"firing_fast": [], "p99_ms": 4.0}
    # equal health+load: the fast replica wins despite the higher index
    r = Router([_FakeReplica("a", slo=slow), _FakeReplica("b", slo=fast)])
    assert [c[-1].name for c in r._candidates()] == ["b", "a"]
    # p99 alone (no alert firing) tie-breaks too
    r = Router([
        _FakeReplica("a", slo={"firing_fast": [], "p99_ms": 50.0}),
        _FakeReplica("b", slo=fast),
    ])
    assert [c[-1].name for c in r._candidates()] == ["b", "a"]
    # inflight dominates the penalty: slow-idle beats fast-busy
    r = Router([
        _FakeReplica("a", inflight=0, slo=slow),
        _FakeReplica("b", inflight=2, slo=fast),
    ])
    assert [c[-1].name for c in r._candidates()] == ["a", "b"]
    # health rank dominates everything: serving-slow beats degraded-fast
    r = Router([
        _FakeReplica("a", state="degraded", slo=fast),
        _FakeReplica("b", slo=slow),
    ])
    assert [c[-1].name for c in r._candidates()] == ["b", "a"]
    # no SLO data sorts neutral: index decides, as before ISSUE 10
    r = Router([_FakeReplica("a"), _FakeReplica("b")])
    assert [c[-1].name for c in r._candidates()] == ["a", "b"]


def test_supervisor_burn_respawn_gated_on_declared_non_availability():
    """Two gates on the supervisor's burn respawn: (1) it acts only
    when the replica's status says its objectives were DECLARED (the
    ``actuate`` bit every Server.snapshot()['slo'] carries) — the
    observe-only defaults report burn without buying a drain; (2) the
    availability objective never actuates even when declared — its bad
    events are the fleet's own sheds, and respawning a saturated
    replica for shedding would churn capacity under the very overload
    that caused the sheds."""
    from orion_tpu.fleet.supervisor import Supervisor

    burning = {
        "firing_fast": ["chunk_lat"], "p99_ms": 900.0,
        "objectives": {"chunk_lat": {"kind": "latency"},
                       "availability": {"kind": "availability"}},
    }

    class _Scripted(_FakeReplica):
        def __init__(self, name):
            super().__init__(name, slo=dict(burning, actuate=False))
            self.drained = 0

        def status(self, timeout=2.0):
            return self.last_status

        def wait_ready(self, timeout):
            pass

        def drain(self):
            self.drained += 1

        def kill(self):
            pass

        def join(self, timeout=10.0):
            return True

    sup = Supervisor(lambda name: _Scripted(name), 1, burn_limit=1).start()
    observed = sup.replicas[0]
    for _ in range(3):
        sup.tick()
    assert observed.drained == 0 and sup.replicas[0] is observed, (
        "observe-only burn must not drain-respawn"
    )
    # declared, but only the AVAILABILITY objective firing: still no act
    observed.last_status["slo"]["actuate"] = True
    observed.last_status["slo"]["firing_fast"] = ["availability"]
    for _ in range(3):
        sup.tick()
    assert observed.drained == 0 and sup.replicas[0] is observed, (
        "a shed-driven availability burn must never churn capacity"
    )
    # a declared latency burn does act
    observed.last_status["slo"]["firing_fast"] = ["chunk_lat"]
    sup.tick()
    assert observed.drained == 1 and sup.replicas[0] is not observed


def test_fleet_actuation_chunk_delay_shifts_burns_respawns_bitwise(
    mp, tmp_path
):
    """THE ISSUE 10 actuation acceptance. serve.chunk_delay is injected
    into replica A of a 2-replica fleet (thread-gated action: only A's
    serve thread sleeps). The proof walks the whole loop:

    1. a long session turn lands on A (index tie-break) and A's chunk
       latency objective starts burning; A is still SERVING;
    2. short turns submitted while A burns all route to B — the
       dispatch share shifts BEFORE A leaves SERVING;
    3. the supervisor sees A's fast-burn alert persist across
       burn_limit heartbeats and drain-respawns it: the in-flight
       session turn SUSPENDS (zero lost turns);
    4. the continuation turn resumes from the shared store and the
       concatenation is BITWISE the uninterrupted solo run;
    5. the respawned replica reports a whole error budget again.
    """
    from orion_tpu.fleet.replica import LocalReplica
    from orion_tpu.fleet.supervisor import Supervisor

    model, params = mp
    want = 64
    sid = "conv-slo"
    ref = _ref(mp, _prompt(0), want, GREEDY, seed=7)
    sdir = str(tmp_path / "sessions")

    def cfg():
        # slo_degrade_ticks huge: the server must NOT degrade itself, so
        # the share shift is observable while A is SERVING and the
        # SUPERVISOR's burn path (not the degraded-state path) is what
        # heals it
        return _cfg(tmp_path, slots=2, max_inflight=8, session_dir=sdir,
                    slo=_CHUNK_SLO, slo_degrade_ticks=10 ** 6)

    def factory(name):
        return LocalReplica(model, params, cfg(), name=name).start()

    sup = Supervisor(factory, 2, burn_limit=2).start()
    rep_a, rep_b = sup.replicas[0], sup.replicas[1]
    a_name = rep_a.name  # gate the delay to THIS incarnation only

    def slow_replica_a():
        # the replica's serve thread is named "<replica name>-serve";
        # only original-A's boundaries stretch — B and the respawned A
        # stay fast
        if threading.current_thread().name.startswith(a_name):
            time.sleep(0.03)

    plan = inject.FaultPlan().add(
        "serve.chunk_delay", times=-1, action=slow_replica_a
    )
    try:
        with inject.inject(plan):
            # 1) the long session turn: all replicas idle and unscored,
            # so the index tie sends it to A — where it slows down
            p_sess = sup.router.submit(DecodeRequest(
                prompt=_prompt(0), max_new_tokens=want, sample=GREEDY,
                seed=7, session_id=sid,
            ))
            deadline = time.monotonic() + 30.0
            status_a = None
            while time.monotonic() < deadline:
                status_a = rep_a.status()
                rep_b.status()  # keep B's snapshot fresh for the router
                if status_a and status_a["slo"].get("firing_fast"):
                    break
                time.sleep(0.03)
            assert status_a and status_a["slo"]["firing_fast"], (
                "A's fast-burn alert must fire while it serves delayed "
                "chunks"
            )
            assert status_a["state"] == "serving", (
                "the shift must be observable BEFORE A leaves SERVING"
            )
            chunk_obj = status_a["slo"]["objectives"]["chunk_lat"]
            assert chunk_obj["budget_remaining"] < 1.0
            # 2) dispatch share: all short turns go to B (A is mid-turn
            # and burning; its penalty + inflight both point away)
            a0 = rep_a.server.stats["admitted"]
            b0 = rep_b.server.stats["admitted"]
            for i in range(4):
                p = sup.router.submit(DecodeRequest(
                    prompt=_prompt(20 + i), max_new_tokens=4,
                    sample=GREEDY, seed=200 + i,
                ))
                assert p.wait(timeout=60.0) is not None
                rep_a.status()
                rep_b.status()
            assert rep_a.server.stats["admitted"] == a0, (
                "no short turn may land on the burning replica"
            )
            assert rep_b.server.stats["admitted"] == b0 + 4
            assert rep_a.server.health.state is Health.SERVING
            # 3) the supervisor: fast burn persists across burn_limit=2
            # heartbeats -> drain (the session suspends) + respawn
            deadline = time.monotonic() + 60.0
            while sup.replicas[0] is rep_a:
                assert time.monotonic() < deadline, sup.events
                sup.tick()
                time.sleep(0.1)
            assert any(
                "slo fast burn persisted" in what
                for _, name, what in sup.events if name == a_name
            ), sup.events
            res1 = p_sess.wait(timeout=60.0)
            assert res1 is not None and res1.status == "suspended"
            assert 0 < res1.new_tokens < want, (
                "the turn must suspend MID-stream for the zero-lost-"
                "turns proof to bite"
            )
            # 5) the respawned replica's error budget is whole again
            new_a = sup.replicas[0]
            assert new_a is not rep_a and new_a.name != a_name
            fresh = new_a.status()
            assert fresh["slo"]["objectives"]["chunk_lat"][
                "budget_remaining"] == 1.0
            assert fresh["slo"]["firing_fast"] == []
            # 4) zero lost turns: the continuation resumes from the
            # shared store (on whichever replica) and the concatenation
            # is bitwise the uninterrupted run
            p_cont = sup.router.submit(DecodeRequest(
                prompt=np.zeros((1, 0), np.int32),
                max_new_tokens=want - res1.new_tokens,
                sample=GREEDY, seed=0, session_id=sid,
            ))
            res2 = p_cont.wait(timeout=120.0)
            assert res2 is not None and res2.status == "ok"
            np.testing.assert_array_equal(
                np.concatenate([res1.tokens, res2.tokens], axis=1), ref,
            )
    finally:
        sup.drain_all(timeout=60.0)


def test_fleet_cli_aggregated_endpoint(mp, tmp_path):
    """The fleet CLI's --metrics-port view: /metrics sums every
    replica's registry over the status op (Supervisor.aggregate_metrics),
    /healthz answers for the FLEET (200 while anything is routable, 503
    once everything drained), /slo carries the per-replica burn state."""
    import types

    from orion_tpu.fleet.__main__ import _start_fleet_http
    from orion_tpu.fleet.replica import LocalReplica
    from orion_tpu.fleet.supervisor import Supervisor

    model, params = mp

    def factory(name):
        return LocalReplica(model, params, _cfg(tmp_path), name=name).start()

    sup = Supervisor(factory, 2).start()
    http = _start_fleet_http(types.SimpleNamespace(metrics_port=0), sup)
    try:
        pendings = [
            sup.router.submit(DecodeRequest(
                prompt=_prompt(i), max_new_tokens=8, sample=GREEDY, seed=i,
            ))
            for i in range(4)
        ]
        for p in pendings:
            assert p.wait(timeout=60.0) is not None
        # /metrics aggregates the heartbeat-refreshed snapshots (no
        # fresh RPC per scrape): one deterministic tick = one heartbeat
        sup.tick()
        url = f"http://127.0.0.1:{http.port}"
        code, body = _get(url + "/metrics")
        assert code == 200 and "ok 4" in body, body[:400]
        code, body = _get(url + "/healthz")
        assert code == 200
        code, body = _get(url + "/slo")
        assert code == 200
        doc = json.loads(body)
        assert set(doc["replicas"]) == {r.name for r in sup.replicas}
        for slo in doc["replicas"].values():
            assert "objectives" in slo
    finally:
        sup.drain_all(timeout=30.0)
    # everything drained: the fleet endpoint itself reports 503
    code, body = _get(f"http://127.0.0.1:{http.port}/healthz")
    assert code == 503
    http.close()
