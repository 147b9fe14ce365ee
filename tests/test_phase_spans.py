"""The inside of a served boundary (ISSUE 28): phase spans, the first-token
stamp, the slot-class counters and the capture's host annotations.

What ``benchmark/readers/tracer_phases.py`` and ``xplane_host.py`` read is
pinned here from the program's side: every boundary the engine stepped has
one ``serve.boundary`` event whose phases lie inside it in the order of
``serving.PHASES`` without overlap; ``first_token`` opens and closes once
per request on every path a request can end by; the three slot classes sum
to ``slot_steps_active`` after every boundary; a disabled tracer records
nothing and builds no annotation; an ``arm_profile`` capture holds host
events under the same names; and the events the older metrics read
(``decode_chunk`` / ``prefill_piece`` / ``queue`` / ``request``,
``chunk_ms``) keep their names and arguments.
"""

import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.generate import SampleConfig
from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.transformer import TransformerLM
from orion_tpu.obs.trace import NULL_SPAN, Tracer, span_pairs
from orion_tpu.resilience import inject
from orion_tpu.serving import (
    PHASES,
    DecodeRequest,
    ServeConfig,
    Server,
)
from orion_tpu.serving.server import OverloadError, RejectedError

CFG = ModelConfig(
    name="phase_test", vocab_size=64, d_model=32, n_layers=2, n_heads=2,
    max_seq_len=96, dtype="float32", backend="xla",
)
GREEDY = SampleConfig(temperature=0.0)
INSIDE = PHASES[1:7]  # the children of serve.boundary, in order


@pytest.fixture(scope="module")
def mp():
    model = TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return model, params


def _prompt(i, ln):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(4000 + i), (1, ln), 0, CFG.vocab_size
    ), np.int32)


def _server(mp, tracer=None, **kw):
    kw.setdefault("chunk", 4)
    kw.setdefault("slots", 2)
    kw.setdefault("max_inflight", 8)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("cost", False)
    if tracer is None:
        tracer = Tracer(path=None, clock=time.monotonic)
    return Server(*mp, ServeConfig(**kw), tracer=tracer)


def _mixed(srv, n=5):
    """More requests than slots, prompts of one to three prefill pieces,
    answers of two to four chunks: boundaries with a prefilling, decoding
    and waiting (frozen) slot all occur."""
    return [
        srv.submit(DecodeRequest(
            prompt=_prompt(i, (5, 20, 9, 17, 12)[i % 5]),
            max_new_tokens=(8, 16, 12)[i % 3], sample=GREEDY, seed=i,
        ))
        for i in range(n)
    ]


def _phase_events(events):
    return [e for e in events if e["ph"] == "X" and e["cat"] == "phase"]


def test_every_boundary_has_one_span_with_ordered_phases_inside(mp):
    srv = _server(mp)
    ps = _mixed(srv)
    assert srv.serve(drain_when_idle=True) == 0
    assert all(p.result.status == "ok" for p in ps)
    phases = _phase_events(srv.trace.events())
    assert {e["name"] for e in phases} <= set(PHASES)
    parents = [e for e in phases if e["name"] == "serve.boundary"]
    chunks = srv.stats["chunks"]
    assert chunks > 4
    assert sorted(e["args"]["boundary"] for e in parents) == list(
        range(1, chunks + 1)
    ), "one serve.boundary per stepped boundary, indexed by chunk_seq"
    for parent in parents:
        seq = parent["args"]["boundary"]
        kids = sorted(
            (e for e in phases
             if e["args"]["boundary"] == seq and e["name"] in INSIDE),
            key=lambda e: e["ts"],
        )
        assert [e["name"] for e in kids] == list(INSIDE), (seq, kids)
        end = parent["ts"]
        for e in kids:
            assert e["ts"] >= end - 1e-3, "phases must not overlap"
            end = e["ts"] + e["dur"]
        assert end <= parent["ts"] + parent["dur"] + 1e-3
        assert sum(e["dur"] for e in kids) <= parent["dur"] + 1e-3
        args = parent["args"]
        assert args["steps"] == 4 and 1 <= args["resident"] <= 2
        assert {"admitted", "finished"} <= set(args)
    assert sum(p["args"]["finished"] for p in parents) == len(ps)
    assert sum(p["args"]["admitted"] for p in parents) == len(ps)
    srv.close()


def test_idle_polls_write_one_span_per_stretch(mp):
    """An idle traced server must not age its requests out of the ring:
    polls that find nothing write nothing, and the stretch that ends with
    a request is ONE serve.idle_wait event."""
    import threading

    srv = _server(mp, poll=0.01)

    class Stop:
        signum = 15
        should_stop = False

    guard = Stop()
    loop = threading.Thread(target=srv.serve, kwargs={"guard": guard})
    loop.start()
    try:
        time.sleep(0.15)  # a dozen empty polls
        assert not _phase_events(srv.trace.events())
        p = srv.submit(DecodeRequest(prompt=_prompt(0, 5), max_new_tokens=4,
                                     sample=GREEDY, seed=0))
        assert p.wait(timeout=120).status == "ok"
    finally:
        guard.should_stop = True
        loop.join(timeout=120)
    assert not loop.is_alive()
    waits = [e for e in _phase_events(srv.trace.events())
             if e["name"] == "serve.idle_wait"]
    assert len(waits) == 1 and waits[0]["dur"] >= 0.1e6
    srv.close()


# -- first_token ---------------------------------------------------------------


def _exit_ok(mp):
    srv = _server(mp)
    p = srv.submit(DecodeRequest(prompt=_prompt(0, 5), max_new_tokens=8,
                                 sample=GREEDY, seed=0))
    srv.serve(drain_when_idle=True)
    assert p.result.status == "ok"
    return srv, [p.rid], p


def _exit_shed(mp):
    srv = _server(mp, max_inflight=1)
    p = srv.submit(DecodeRequest(prompt=_prompt(0, 5), max_new_tokens=4,
                                 sample=GREEDY, seed=0))
    with pytest.raises(OverloadError):
        srv.submit(DecodeRequest(prompt=_prompt(1, 5), max_new_tokens=4,
                                 sample=GREEDY, seed=1))
    srv.serve(drain_when_idle=True)
    shed = [key[1] for key in span_pairs(srv.trace.events())
            if key[2] == "first_token" and key[1] != p.rid]
    assert len(shed) == 1
    return srv, [p.rid] + shed, None


def _exit_deadline_in_queue(mp):
    now = [100.0]
    srv = Server(*mp, ServeConfig(chunk=4, slots=2, max_inflight=4, cost=False),
                 clock=lambda: now[0],
                 tracer=Tracer(path=None, clock=lambda: now[0]))
    p = srv.submit(DecodeRequest(prompt=_prompt(0, 5), max_new_tokens=4,
                                 sample=GREEDY, seed=0, deadline_ms=500.0))
    now[0] += 1.0
    srv.serve(drain_when_idle=True)
    assert p.result.status == "deadline" and p.first_token_at == 0.0
    return srv, [p.rid], None


def _exit_refused(mp):
    srv = _server(mp)
    p = srv.submit(DecodeRequest(  # a batch of two: the engine refuses it
        prompt=np.zeros((2, 5), np.int32), max_new_tokens=4, sample=GREEDY,
        seed=0))
    srv.serve(drain_when_idle=True)
    assert p.error is not None and p.first_token_at == 0.0
    return srv, [p.rid], None


def _exit_ladder_failed(mp):
    srv = _server(mp)
    p = srv.submit(DecodeRequest(prompt=_prompt(0, 20), max_new_tokens=8,
                                 sample=GREEDY, seed=0))
    # poisoned at its first boundary and at every retry: the ladder is
    # exhausted while the slot is still mid-prompt, before any token
    with inject.inject(inject.FaultPlan().poison_decode_slot_at(0, 0, times=-1)):
        srv.serve(drain_when_idle=True)
    assert p.result.status == "failed" and p.first_token_at == 0.0
    return srv, [p.rid], None


def _exit_rejected_at_shutdown(mp):
    srv = _server(mp)
    p = srv.submit(DecodeRequest(prompt=_prompt(0, 5), max_new_tokens=4,
                                 sample=GREEDY, seed=0))
    srv.close()  # never served: rejected with the queue
    assert isinstance(p.error, RejectedError) and p.first_token_at == 0.0
    return srv, [p.rid], None


@pytest.mark.parametrize("scenario", [
    _exit_ok, _exit_shed, _exit_deadline_in_queue, _exit_refused,
    _exit_ladder_failed, _exit_rejected_at_shutdown,
], ids=lambda f: f.__name__[6:])
def test_first_token_pairs_once_on_every_exit_path(mp, scenario):
    srv, rids, ok = scenario(mp)
    pairs = span_pairs(srv.trace.events())
    for rid in rids:
        for name in ("first_token", "queue", "request"):
            got = pairs[("request", rid, name)]
            assert len(got["b"]) == len(got["e"]) == 1, (name, rid, got)
            assert got["b"][0]["ts"] <= got["e"][0]["ts"]
    if ok is not None:
        assert ok.admitted_at <= ok.first_token_at <= ok.done_at
        end = pairs[("request", ok.rid, "first_token")]["e"][0]
        assert end["ts"] == pytest.approx(ok.first_token_at * 1e6)
    srv.close()


def test_first_token_at_is_the_end_of_the_boundary_that_finished_the_prompt(mp):
    srv = _server(mp)
    ps = _mixed(srv)
    srv.serve(drain_when_idle=True)
    events = srv.trace.events()
    for p in ps:
        assert p.admitted_at <= p.first_token_at <= p.done_at
        # slot phases are read BEFORE the step, so the boundary in which a
        # slot consumes its last piece (and emits) is its last
        # prefill_piece event
        pieces = [e for e in events if e["name"] == "prefill_piece"
                  and e["args"]["req"] == p.rid]
        last = max(pieces, key=lambda e: e["ts"])
        assert p.first_token_at * 1e6 == pytest.approx(
            last["ts"] + last["dur"], abs=1.0)
    srv.close()


def test_first_token_of_a_one_piece_prompt_is_not_at_admission(mp):
    """No prefill runs at admission: a prompt shorter than one piece gets
    its first token from its first boundary, after that boundary's
    dispatch."""
    srv = _server(mp)
    p = srv.submit(DecodeRequest(prompt=_prompt(0, 9), max_new_tokens=8,
                                 sample=GREEDY, seed=0))
    srv.serve(drain_when_idle=True)
    assert p.result.status == "ok"
    events = srv.trace.events()
    first_boundary = min(
        e["ts"] for e in events if e["name"] == "serve.dispatch"
    )
    assert len([e for e in events if e["name"] == "prefill_piece"]) == 1
    assert p.admitted_at * 1e6 <= first_boundary <= p.first_token_at * 1e6
    srv.close()


# -- slot classes --------------------------------------------------------------


@pytest.mark.parametrize("slots,chunk,pieces", [
    (2, 4, [1, 1, 1, 0]),  # cap 1: one piece a boundary, as before ISSUE 33
    (4, 2, [2, 2, 0, 0]),  # cap 2: four admitted at once, two boundaries
    (8, 2, [4, 2, 0, 0]),  # cap 4: six admitted at once, the cap binds once
], ids=["cap1", "cap2", "cap4"])
def test_slot_classes_sum_to_active_after_every_boundary(
        mp, slots, chunk, pieces):
    from orion_tpu.generate import prefill_piece_cap

    srv = _server(mp, tracer=Tracer(enabled=False), slots=slots, chunk=chunk)
    cap = prefill_piece_cap(slots, chunk)
    ps = _mixed(srv, n=6)
    seen = [(0, 0, 0)]
    step = srv._step_chunk

    def checked(*a, **kw):
        step(*a, **kw)
        c = srv.metrics.counters_flat()
        seen.append((c["slot_steps_prefilling"], c["slot_steps_decoding"],
                     c["slot_steps_frozen"]))
        assert sum(seen[-1]) == c["slot_steps_active"]
        assert seen[-1][0] - seen[-2][0] <= cap, (
            "a boundary serves at most prefill_piece_cap slots")

    srv._step_chunk = checked
    srv.serve(drain_when_idle=True)
    assert all(p.result.status == "ok" for p in ps)
    prefilling, decoding, frozen = seen[-1]
    # slots served at the first boundaries: every waiting slot up to the cap
    assert [b[0] - a[0] for a, b in zip(seen, seen[1:])][:4] == pieces
    # every prompt went through whole pieces: 5, 20, 9, 17, 12, 5 tokens
    piece = srv.engine.prefill_chunk
    assert prefilling == sum(-(-n // piece) for n in (5, 20, 9, 17, 12, 5))
    assert decoding > 0 and frozen > 0, (
        "more waiting slots than the cap: some slot waited its turn")
    srv.close()


# -- off means off -------------------------------------------------------------


def test_disabled_tracer_records_nothing_and_builds_no_annotation(
        mp, monkeypatch):
    from orion_tpu.utils import profiling

    built = []
    monkeypatch.setattr(profiling, "annotate",
                        lambda name: built.append(name))
    tracer = Tracer(enabled=False)
    srv = _server(mp, tracer=tracer)
    assert srv._phase("serve.tick") is NULL_SPAN
    ps = _mixed(srv)
    srv.serve(drain_when_idle=True)
    assert all(p.result.status == "ok" for p in ps)
    assert all(p.first_token_at > 0 for p in ps), (
        "the stamp is the program's, not the tracer's")
    assert tracer.events() == [] and tracer.annotate is None and not built
    srv.close()


# -- the capture ---------------------------------------------------------------


def test_capture_holds_host_events_named_as_phases(mp, tmp_path):
    from jax.profiler import ProfileData

    srv = _server(mp, profile_dir=str(tmp_path / "prof"))
    ps = _mixed(srv)
    assert srv.arm_profile(3).get("armed") == 3
    srv.serve(drain_when_idle=True)
    assert all(p.result.status == "ok" for p in ps)
    assert srv.trace.annotate is None, "the factory is held for the capture only"
    events = srv.trace.events()
    start, = [e for e in events if e["name"] == "profile_start"]
    stop, = [e for e in events if e["name"] == "profile_stop"]
    assert start["args"]["chunk_seq"] == 1 and stop["args"]["chunk_seq"] == 3
    assert start["args"]["path"] == stop["args"]["path"] == srv._profile_path
    found, = glob.glob(os.path.join(srv._profile_path, "**", "*.xplane.pb"),
                       recursive=True)
    names = {}
    for plane in ProfileData.from_file(found).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in PHASES:
                    names[e.name] = names.get(e.name, 0) + 1
    # the capture starts before boundary 1's dispatch and stops after
    # boundary 3's finish: what both starts and ends inside it is there
    for name in ("serve.dispatch", "serve.probe", "serve.finish"):
        assert names.get(name) == 3, names
    for name in ("serve.complete", "serve.tick", "serve.admit"):
        assert names.get(name) == 2, names
    assert names.get("serve.boundary") == 1, names
    # one span present in both gives the clocks' offset: boundary 2's probe
    ring = [e for e in _phase_events(events)
            if e["name"] == "serve.probe" and e["args"]["boundary"] == 2]
    assert len(ring) == 1
    srv.close()


# -- what the older metrics read ----------------------------------------------


def test_events_the_older_metrics_read_keep_names_and_arguments(mp):
    srv = _server(mp)
    ps = _mixed(srv)
    srv.serve(drain_when_idle=True)
    events = srv.trace.events()
    chunk = [e for e in events if e["ph"] == "X" and e["cat"] == "chunk"]
    assert {e["name"] for e in chunk} == {"decode_chunk", "prefill_piece"}
    assert all(set(e["args"]) == {"req", "slot", "chunk"} for e in chunk)
    # chunk_ms_p50 / prefill_boundary_share: a boundary is one distinct
    # timestamp among those events, its duration engine.step's
    chunks = srv.stats["chunks"]
    assert len({e["ts"] for e in chunk}) == chunks
    dispatch = {e["args"]["boundary"]: e for e in _phase_events(events)
                if e["name"] == "serve.dispatch"}
    for e in chunk:
        assert any(abs(d["ts"] - e["ts"]) < 500 for d in dispatch.values())
    assert srv._h_chunk_ms.cell_total()["count"] == chunks
    # queue_ms_p95: queue opens at submit and closes at admission
    pairs = span_pairs(events)
    for p in ps:
        q = pairs[("request", p.rid, "queue")]
        assert len(q["b"]) == len(q["e"]) == 1
        assert q["b"][0]["ts"] == pytest.approx(p.admitted_at * 1e6, abs=2e3)
    # slot_occupancy: active counts a slot from admission
    flat = srv.metrics.counters_flat()
    assert flat["slot_steps_total"] == 2 * chunks
    assert flat["slot_steps_active"] == sum(
        e["args"]["resident"] for e in _phase_events(events)
        if e["name"] == "serve.boundary")
    srv.close()


def test_obs_lint_rules_pass_on_the_new_call_sites():
    from orion_tpu.analysis.lint import lint_paths
    from orion_tpu.analysis.rules import ALL_RULES

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = [os.path.join(root, "orion_tpu", *p) for p in (
        ("obs", "trace.py"), ("serving", "server.py"),
        ("serving", "batching.py"), ("utils", "profiling.py"),
        ("training", "trainer.py"),
    )]
    rules = [ALL_RULES["obs-device-sync"], ALL_RULES["decode-host-sync"]]
    assert lint_paths(files, rules=rules, root=root) == []


def test_one_place_constructs_profiler_annotations():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    hits = []
    for path in glob.glob(os.path.join(root, "orion_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            if "TraceAnnotation(" in f.read():
                hits.append(os.path.relpath(path, root))
    assert hits == [os.path.join("orion_tpu", "utils", "profiling.py")]
