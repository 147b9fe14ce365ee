"""Set-up on the program's own clock (ISSUE 39): the ``setup`` span tree
from import to the first boundary or step, and a named ``compile`` event
for every program jax builds.

What ``benchmark/readers/setup_record.py`` reads is pinned here from the
program's side: the tree's names and nesting for a ``Server`` and a
``Trainer``; every ``compile.backend`` event names its function, and those
of a first launch lie inside its ``setup.first_launch``; a second ``Server``
in the process builds nothing again and registers no listener; a prompt
length that was not warmed up shows as a compile event inside the boundary
that paid for it; a disabled tracer still hands the boundary the shared
null span while the record keeps the set-up.
"""

import dataclasses
import io
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring  # the public module has no getters

from orion_tpu.generate import SampleConfig
from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.transformer import TransformerLM
from orion_tpu.obs.trace import (
    COMPILE_COUNTERS,
    NULL_SPAN,
    Tracer,
    compile_totals,
    setup_record,
    setup_summary,
)
from orion_tpu.serving import DecodeRequest, ServeConfig, Server
from orion_tpu.training import DataLoader, SyntheticDataset, TrainConfig, Trainer
from orion_tpu.training.metrics import MetricsLogger

CFG = ModelConfig(
    name="setup_span_test", vocab_size=72, d_model=32, n_layers=2, n_heads=2,
    max_seq_len=96, dtype="float32", backend="xla",
)
GREEDY = SampleConfig(temperature=0.0)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BOUNDARY_PROGRAMS = {"decode_batched", "unified_prefill"}


@pytest.fixture(scope="module")
def mp():
    model = TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return model, params


def _prompt(i, ln):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(7000 + i), (1, ln), 0, CFG.vocab_size
    ), np.int32)


def _server(mp, enabled=True, **kw):
    kw.setdefault("chunk", 4)
    kw.setdefault("slots", 2)
    kw.setdefault("max_inflight", 8)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("prefill_buckets", "16,64")
    kw.setdefault("cost", False)
    tracer = Tracer(path=None, clock=time.monotonic, enabled=enabled)
    return Server(*mp, ServeConfig(**kw), tracer=tracer)


def _serve(srv, lengths, seed=0, prompts=None):
    prompts = prompts or [_prompt(seed + i, ln) for i, ln in enumerate(lengths)]
    ps = [srv.submit(DecodeRequest(prompt=p, max_new_tokens=8, sample=GREEDY,
                                   seed=seed + i))
          for i, p in enumerate(prompts)]
    assert srv.serve(drain_when_idle=True) == 0
    assert all(p.result.status == "ok" for p in ps)


def _inside(child, parent, slack_us=1.0):
    return (parent["ts"] - slack_us <= child["ts"]
            and child["ts"] + child.get("dur", 0.0)
            <= parent["ts"] + parent["dur"] + slack_us)


def _mark():
    return time.monotonic() * 1e6


def _since(mark):
    """The record's events that began after ``mark`` (the record is
    bounded: a worker that ran other files first has turned it over)."""
    return [e for e in setup_record() if e["ts"] >= mark]


def _named(events, name):
    return [e for e in events if e["name"] == name]


@pytest.fixture(scope="module")
def first_server(mp):
    """The process's first server of this configuration, served once: the
    record's events since just before it was built, and the server."""
    mark = _mark()
    listeners = len(monitoring.get_event_duration_listeners())
    srv = _server(mp)
    _serve(srv, (5, 12, 9))
    yield srv, _since(mark), listeners
    srv.close()


def test_import_span_is_in_the_record_once_per_entry_package():
    """In an interpreter of its own: this worker imported the packages long
    ago, and its bounded record may have turned over since."""
    probe = (
        "import json, time\n"
        "t0 = time.monotonic()\n"
        "import orion_tpu.serving\n"  # imports orion_tpu.training on its way
        "t1 = time.monotonic()\n"
        "import orion_tpu.training\n"
        "from orion_tpu.obs.trace import setup_record\n"
        "print(json.dumps({'t0': t0, 't1': t1, 'record': setup_record()}))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", probe], env=env, cwd="/", text=True,
        capture_output=True, timeout=300, check=True,
    )
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert {e["cat"] for e in got["record"]} <= {"setup", "compile"}
    # the outermost package of an import writes it, once: from the first
    # line of orion_tpu/__init__.py to the end of its own last line
    (imported,) = _named(got["record"], "setup.import")
    assert imported["cat"] == "setup" and imported["ph"] == "X"
    assert imported["args"] == {"module": "orion_tpu.serving"}
    assert got["t0"] * 1e6 <= imported["ts"]
    assert imported["ts"] + imported["dur"] <= got["t1"] * 1e6
    assert imported["dur"] > 0.5 * (got["t1"] - got["t0"]) * 1e6


def test_server_tree_names_and_nesting(first_server):
    srv, events, _ = first_server
    setup = [e for e in events if e["cat"] == "setup"]
    (server,) = _named(setup, "setup.server")
    assert server["args"] == {"slots": 2, "chunk": 4}
    for child in ("setup.engine", "setup.stores"):
        (e,) = _named(setup, child)
        assert _inside(e, server), child
    engine = _named(setup, "setup.engine")[0]["args"]
    assert engine["donate_carry"] is False  # the CPU reports no limit
    assert engine["carry_bytes"] > 0 and engine["params_bytes"] > 0
    assert not _named(setup, "setup.quantize")  # qmode off
    assert not _named(setup, "setup.cost_harvest")  # cost off
    launches = _named(setup, "setup.first_launch")
    assert {e["args"]["program"] for e in launches} == BOUNDARY_PROGRAMS
    for e in launches:
        assert not _inside(e, server), "launches come after the constructor"
        assert e["args"]["source"] in ("compiled", "cache")
        assert (e["args"]["width"] > 0) == (e["args"]["program"] == "unified_prefill")
    (ready,) = _named(setup, "setup.ready")
    # the first boundary that emitted a token: after the first launch (a
    # program kind launched first at a later boundary follows it)
    assert ready["ph"] == "i" and ready["ts"] >= min(
        e["ts"] + e["dur"] for e in launches)
    # the enabled tracer's ring holds the same set-up events
    ring = {e["name"] for e in srv.trace.events() if e["cat"] == "setup"}
    assert {"setup.server", "setup.engine", "setup.first_launch",
            "setup.ready"} <= ring
    summary = setup_summary(events)
    assert summary["ready"] and "setup.server" in summary["seconds_by_span"]
    assert "setup.engine" not in summary["seconds_by_span"]  # inside another
    # /statusz shows the record: why a replica took long to come up
    shown = srv._statusz()["setup"]
    assert shown["summary"]["ready"]
    assert {"setup.server", "compile.backend"} <= {e["name"] for e in shown["events"]}


def test_quantize_and_cost_harvest_spans_where_they_are_on(mp):
    mark = _mark()
    srv = _server(mp, qmode="int8", cost=True, cost_ledger=True)
    setup = [e for e in _since(mark) if e["cat"] == "setup"]
    (server,) = _named(setup, "setup.server")
    for child in ("setup.quantize", "setup.cost_harvest"):
        (e,) = _named(setup, child)
        assert _inside(e, server), child
    assert _named(setup, "setup.quantize")[0]["args"] == {"qmode": "int8"}
    srv.close()


def test_every_backend_event_is_named_and_lies_inside_its_first_launch(first_server):
    _, events, _ = first_server
    backend = _named(events, "compile.backend")
    assert backend and all(e["cat"] == "compile" for e in backend)
    assert all(e["args"]["fun_name"] for e in backend)
    assert all(e["args"]["source"] in ("compiled", "cache") for e in backend)
    for stage in ("compile.trace", "compile.lower"):
        assert all(e["args"]["fun_name"] for e in _named(events, stage))
    for launch in _named(events, "setup.first_launch"):
        inside = [e for e in backend
                  if e["tid"] == launch["tid"] and _inside(e, launch)]
        assert len(inside) == launch["args"]["programs"] >= 1
        assert sum(e["dur"] for e in inside) <= launch["dur"] + 1.0


def test_second_server_builds_nothing_again_and_adds_no_listener(mp, first_server):
    _, _, listeners_before = first_server
    mark = _mark()
    srv = _server(mp)
    _serve(srv, (5, 12, 9))
    events = _since(mark)
    assert len(_named(events, "setup.server")) == 1
    launches = _named(events, "setup.first_launch")
    assert {e["args"]["program"] for e in launches} == BOUNDARY_PROGRAMS
    for launch in launches:
        assert launch["args"]["source"] == "resident"
        assert launch["args"]["programs"] == 0
        assert not [e for e in _named(events, "compile.backend")
                    if e["tid"] == launch["tid"] and _inside(e, launch)]
    for getter in (monitoring.get_event_duration_listeners,
                   monitoring.get_event_listeners,
                   monitoring.get_scalar_listeners):
        mine = [f for f in getter()
                if getattr(f, "__module__", "") == "orion_tpu.utils.profiling"]
        assert len(mine) == 1
    assert len(monitoring.get_event_duration_listeners()) == listeners_before
    srv.close()


def test_unwarmed_prompt_length_compiles_inside_its_boundary(mp):
    srv = _server(mp)
    _serve(srv, (5, 9))  # the 16-wide staging buffer and the decode program
    # made before the marks: a server hears every compile of its process,
    # this test's own ``randint`` of a new shape among them
    long_prompt = [_prompt(50, 40)]
    before = srv.metrics.counters_flat()
    ring_mark = len(srv.trace.events())
    _serve(srv, (40,), seed=50, prompts=long_prompt)  # the 64-wide bucket
    after = srv.metrics.counters_flat()
    built = sum(after[k] - before[k]
                for k in ("programs_compiled", "programs_cache_loaded"))
    assert built >= 1
    assert after["compile_ms_total"] > before["compile_ms_total"]
    assert after["programs_traced"] > before["programs_traced"]
    ring = srv.trace.events()[ring_mark:]
    compiles = [e for e in ring if e["cat"] == "compile"]
    assert compiles and {e["name"] for e in compiles} <= {
        "compile.trace", "compile.lower", "compile.backend"}
    phases = [e for e in ring if e["cat"] == "phase"
              and e["name"] in ("serve.admit", "serve.dispatch")]
    for e in compiles:
        k = e["args"]["boundary"]
        assert e["args"]["fun_name"]
        assert any(p["args"]["boundary"] == k and _inside(e, p, slack_us=50.0)
                   for p in phases), e
    # the wider staging buffer's first launch is a set-up event of its own
    wide = [e for e in ring if e["name"] == "setup.first_launch"]
    assert [e["args"]["width"] for e in wide] == [64]
    srv.close()


def test_disabled_tracer_takes_the_null_span_and_keeps_the_record(mp):
    mark = _mark()
    srv = _server(mp, enabled=False)
    assert srv._phase("serve.admit") is NULL_SPAN
    _serve(srv, (5, 12))
    assert srv._phase("serve.dispatch") is NULL_SPAN
    assert srv.trace.events() == []
    names = {e["name"] for e in _since(mark)}
    assert {"setup.server", "setup.engine", "setup.stores",
            "setup.first_launch", "setup.ready"} <= names
    srv.close()


def test_trainer_tree_and_counters():
    model = dataclasses.replace(CFG, name="setup_span_train", max_seq_len=40)
    cfg = TrainConfig(model=model, steps=3, batch_size=8, seq_len=32,
                      log_every=1, warmup_steps=1)
    mark = _mark()
    tracer = Tracer(path=None, clock=time.monotonic)
    trainer = Trainer(cfg, tracer=tracer)
    loader = DataLoader(SyntheticDataset(model.vocab_size, 32), 8, seed=0,
                        sharding=trainer.batch_shd)
    logger = MetricsLogger(stream=io.StringIO())
    try:
        trainer.train(iter(loader), logger=logger)
        # shown at log cadence: after the last step's log, what jax built
        shown = logger.registry.counters_flat()
        totals = compile_totals()  # the event counters and the kernel entries' call sites
        assert set(COMPILE_COUNTERS) < set(totals) and {k: shown[k] for k in totals} == totals
        assert shown["programs_compiled"] + shown["programs_cache_loaded"] >= 2
        trainer.evaluate(iter(loader), n_batches=2)
        trainer.train(iter(loader), logger=logger)  # nothing is written twice
    finally:
        loader.close()
    events = _since(mark)
    setup = [e for e in events if e["cat"] == "setup"]
    assert [e["name"] for e in setup] == [
        "setup.init_state", "setup.trainer", "setup.loader",
        "setup.first_step", "setup.ready", "setup.first_eval"]
    by = {e["name"]: e for e in setup}
    assert _inside(by["setup.init_state"], by["setup.trainer"])
    # at the step whose log found the first loss ready on EVERY device of
    # the mesh (the log's own readback waits for one shard of it)
    assert by["setup.ready"]["ph"] == "i"
    assert set(by["setup.ready"]["args"]) == {"step"}
    assert 1 <= by["setup.ready"]["args"]["step"] <= 3
    backend = _named(events, "compile.backend")
    for span, fun in (("setup.init_state", "jit(init_fn)"),
                      ("setup.first_step", "jit(_train_step)"),
                      ("setup.first_eval", "jit(_eval_step)")):
        (e,) = [b for b in backend if b["args"]["fun_name"] == fun]
        assert _inside(e, by[span]), span
    assert {e["name"] for e in tracer.events()} >= {"setup.trainer", "setup.ready"}
    # a Trainer handed no tracer writes the record through the process's own
    mark = _mark()
    Trainer(cfg, materialize=False)
    assert [e["name"] for e in _since(mark) if e["cat"] == "setup"] == [
        "setup.trainer"]
