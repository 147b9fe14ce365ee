"""The training loop on the program's own clock (ISSUE 53): every
iteration of ``Trainer.train`` in the process-wide step record.

What ``benchmark/readers/step_record.py`` reads is pinned here from the
program's side: ``train.step`` parents that tile the loop, each phase a
child inside its parent with the parent's ``step``; the record is written
whatever tracer the ``Trainer`` holds, and an enabled one gets the same
events in its ring; a collection of the interpreter of 1 ms or more is a
``host.gc`` event; the loop leaves ``gc.callbacks`` and the shared tracer's
annotation factory as it found them; and a ``jax.profiler`` capture that
runs meanwhile holds the same names on its host lines. One compiled
``Trainer`` serves the whole module: every run is three more steps of it.
"""

import dataclasses
import gc
import glob
import io
import os
import time

import pytest

from orion_tpu.models.configs import ModelConfig
from orion_tpu.obs import trace
from orion_tpu.obs.trace import PROCESS_TRACER, Tracer, setup_record, step_record
from orion_tpu.training import DataLoader, SyntheticDataset, TrainConfig, Trainer
from orion_tpu.training.metrics import MetricsLogger

MODEL = ModelConfig(
    name="step_span_test", vocab_size=72, d_model=32, n_layers=2, n_heads=2,
    max_seq_len=40, dtype="float32", backend="xla",
)
CFG = TrainConfig(model=MODEL, steps=10**6, batch_size=8, seq_len=32,
                  log_every=2, warmup_steps=1)
CHILDREN = ("train.next_batch", "train.dispatch", "train.log_readback",
            "train.eval", "train.checkpoint", "train.hook")


class StopAfter:
    """The ``preempt`` guard of a run of ``n`` steps."""

    signum = 0

    def __init__(self, n):
        self.left = n

    @property
    def should_stop(self):
        self.left -= 1
        return self.left <= 0


def _mark():
    return time.monotonic() * 1e6


def _since(mark, record=step_record):
    return [e for e in record() if e["ts"] >= mark]


def _named(events, name):
    return [e for e in events if e["name"] == name]


def _inside(child, parent, slack_us=1.0):
    return (parent["ts"] - slack_us <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + slack_us)


@pytest.fixture(scope="module")
def loop():
    """The module's one Trainer and its loader, and what its first three
    steps (no tracer handed in, no hook) left in both records."""
    trainer = Trainer(CFG)
    assert trainer.trace is PROCESS_TRACER
    loader = DataLoader(SyntheticDataset(MODEL.vocab_size, 32), 8, seed=0,
                        sharding=trainer.batch_shd)
    mark = _mark()
    trainer.train(loader, preempt=StopAfter(3))
    first = {"step": _since(mark), "setup": _since(mark, setup_record)}

    def run(n=3, tracer=PROCESS_TRACER, **kw):
        trainer.trace = tracer
        mark = _mark()
        try:
            trainer.train(loader, preempt=StopAfter(n), **kw)
        finally:
            trainer.trace = PROCESS_TRACER
        return _since(mark)

    yield run, first
    loader.close()


def _check_tree(events, hook: bool):
    """Three tiling parents, every child inside the parent of its step."""
    parents = _named(events, "train.step")
    assert len(parents) == 3
    steps = [p["args"]["step"] for p in parents]
    assert steps == list(range(steps[0], steps[0] + 3))
    for a, b in zip(parents, parents[1:]):
        assert b["ts"] == pytest.approx(a["ts"] + a["dur"], abs=1.0), (
            "a parent starts where the last ended")
    for p in parents:
        assert p["cat"] == "step" and p["ph"] == "X"
        assert p["args"] == {"step": p["args"]["step"], "tokens": 8 * 32}
        kids = sorted((e for e in events if e["name"] in CHILDREN
                       and e["args"]["step"] == p["args"]["step"]),
                      key=lambda e: e["ts"])
        want = ["train.next_batch", "train.dispatch"]
        if p["args"]["step"] % CFG.log_every == 0:
            want.append("train.log_readback")
        if hook:
            want.append("train.hook")
        assert [e["name"] for e in kids] == want
        end = p["ts"]
        for e in kids:
            assert e["cat"] == "step" and _inside(e, p)
            assert e["ts"] >= end - 1e-3, "children do not overlap"
            end = e["ts"] + e["dur"]
        # the DataLoader says how many batches it held when asked
        assert set(kids[0]["args"]) == {"step", "ready"}
        assert 0 <= kids[0]["args"]["ready"] <= 2
    assert {e["name"] for e in events} <= {"train.step", "host.gc", *CHILDREN}


def test_first_dispatch_encloses_setup_first_step(loop):
    _, first = loop
    _check_tree(first["step"], hook=False)
    (dispatch,) = [e for e in _named(first["step"], "train.dispatch")
                   if e["args"]["step"] == 1]
    (first_step,) = _named(first["setup"], "setup.first_step")
    assert _inside(first_step, dispatch)
    (loader,) = _named(first["setup"], "setup.loader")
    (next_batch,) = [e for e in _named(first["step"], "train.next_batch")
                     if e["args"]["step"] == 1]
    assert _inside(loader, next_batch)
    # the step's compile events are in the other record, on the same clock
    assert [e for e in _named(first["setup"], "compile.backend")
            if _inside(e, dispatch)]
    assert {e["cat"] for e in setup_record()} <= {"setup", "compile"}


@pytest.mark.parametrize("hook", [False, True], ids=["no_hook", "hook"])
@pytest.mark.parametrize("kind", ["none", "disabled", "enabled"])
def test_record_is_written_whatever_tracer_the_trainer_holds(loop, kind, hook):
    run, _ = loop
    tracer = {"none": PROCESS_TRACER,
              "disabled": Tracer(clock=time.monotonic, enabled=False),
              "enabled": Tracer(clock=time.monotonic)}[kind]
    seen = []
    events = run(tracer=tracer,
                 hook=(lambda step, metrics: seen.append(step)) if hook else None)
    _check_tree(events, hook)
    if hook:
        assert seen == [p["args"]["step"] for p in _named(events, "train.step")]
    ring = tracer.events()
    if kind == "enabled":
        # the same events, on the same clock, in the handed tracer's ring
        assert sorted(ring, key=lambda e: e["ts"] + e["dur"]) == events
    else:
        assert ring == []
    assert tracer.annotate is None


def test_the_exit_that_reads_the_last_metrics_back_is_a_readback_span(loop):
    """A loop that leaves before any log cadence waits for its last step
    on the way out: a wait for the device, under the span of one."""
    run, _ = loop
    events = run(n=1)
    if _named(events, "train.step")[0]["args"]["step"] % CFG.log_every == 0:
        events = run(n=1)  # the log cadence's own readback took that one
    (parent,) = _named(events, "train.step")
    kids = sorted((e for e in events if e["name"] in CHILDREN), key=lambda e: e["ts"])
    assert [e["name"] for e in kids] == [
        "train.next_batch", "train.dispatch", "train.log_readback"]
    assert all(_inside(e, parent) and e["args"]["step"] == parent["args"]["step"]
               for e in kids)


def test_every_period_reaches_the_loggers_histogram(loop):
    run, _ = loop
    logger = MetricsLogger(stream=io.StringIO())
    events = run(n=5, logger=logger)
    periods = [e["dur"] / 1e3 for e in _named(events, "train.step")]
    cell = logger.registry.histogram("step_time_ms").cell_total()
    assert cell["count"] == 5
    assert cell["sum"] == pytest.approx(sum(periods), rel=1e-6)


@pytest.mark.parametrize("heap", ["large", "trivial"])
def test_a_collection_of_a_millisecond_is_a_host_gc_event(loop, heap):
    run, _ = loop
    held = []

    def hook(step, metrics):
        if heap == "large":
            # a young collection finds every one of these still alive
            held.append([[i] for i in range(400_000)])
            gc.collect()
        else:
            gc.collect(0)

    gc.collect()
    was = gc.isenabled()
    gc.disable()  # only the forced collections run
    try:
        events = run(hook=hook)
    finally:
        if was:
            gc.enable()
    collections = _named(events, "host.gc")
    if heap == "trivial":
        assert collections == []
        return
    assert len(collections) == 3
    for e, hooked in zip(collections, _named(events, "train.hook")):
        assert e["cat"] == "step" and e["ph"] == "X" and e["dur"] >= 1e3
        assert e["args"]["generation"] == 2 and e["args"]["collected"] >= 0
        assert _inside(e, hooked)


@pytest.mark.parametrize("ends", ["returns", "raises"])
@pytest.mark.parametrize("found", [None, "a factory"])
def test_loop_leaves_callbacks_and_the_shared_tracer_as_found(loop, ends, found):
    run, _ = loop
    callbacks = list(gc.callbacks)

    def hook(step, metrics):
        assert PROCESS_TRACER.annotate is not found, "held for the loop"
        assert len(gc.callbacks) == len(callbacks) + 1
        if ends == "raises":
            raise KeyError(step)

    PROCESS_TRACER.annotate = found
    mark = _mark()
    try:
        if ends == "raises":
            with pytest.raises(KeyError):
                run(hook=hook)
        else:
            run(hook=hook)
        assert PROCESS_TRACER.annotate is found
    finally:
        PROCESS_TRACER.annotate = None
    assert gc.callbacks == callbacks
    assert getattr(trace._thread, "span", None) is None
    # the iteration that raised is in the record, closed where it raised
    events = _since(mark)
    parents, hooks = _named(events, "train.step"), _named(events, "train.hook")
    assert len(parents) == len(hooks) == (1 if ends == "raises" else 3)
    assert _inside(hooks[-1], parents[-1])


def test_a_capture_holds_the_loops_spans_on_its_host_lines(loop, tmp_path):
    import jax
    from jax.profiler import ProfileData

    run, _ = loop
    jax.profiler.start_trace(str(tmp_path))
    try:
        events = run(hook=lambda step, metrics: None)
    finally:
        jax.profiler.stop_trace()
    (found,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                         recursive=True)
    names = {}
    for plane in ProfileData.from_file(found).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "train.step" or e.name in CHILDREN:
                    names[e.name] = names.get(e.name, 0) + 1
    want = {name: len(_named(events, name)) for name in
            ("train.step", "train.next_batch", "train.dispatch",
             "train.log_readback", "train.hook")}
    assert names == want and want["train.step"] == 3


def test_annotated_steps_is_gone_and_the_loop_calls_no_annotation():
    import inspect

    from orion_tpu.utils import profiling

    assert not hasattr(profiling, "annotated_steps")
    source = inspect.getsource(Trainer.train)
    assert "annotate(" not in source and "annotated_steps" not in source
    # next_batch, dispatch, log_readback (the cadence's and the exit's),
    # eval, hook; train.checkpoint is _save's and train.step _steps'
    assert source.count('self.trace.span("train.') == 6
    # obs/trace.py still never imports jax; the new call sites wait for nothing
    from orion_tpu.analysis.lint import lint_paths
    from orion_tpu.analysis.rules import ALL_RULES

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = [os.path.join(root, "orion_tpu", *p) for p in (
        ("obs", "trace.py"), ("training", "trainer.py"),
        ("training", "metrics.py"), ("training", "data.py"))]
    assert lint_paths(files, rules=[ALL_RULES["obs-device-sync"]], root=root) == []
    # a Trainer handed a tracer keeps it
    tracer = Tracer(enabled=False)
    built = Trainer(dataclasses.replace(CFG, steps=1), materialize=False,
                    tracer=tracer)
    assert built.trace is tracer
