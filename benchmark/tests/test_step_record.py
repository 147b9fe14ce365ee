"""``readers/step_record.py`` against hand-made step records: a window of
ten periods of which one is 2.2 x the others, its excess once inside
``train.hook`` (the device was late) and once outside every child (the
interpreter was held); a record the window's edge cuts, and one whose loop
left inside the window; no record.
"""

import json
import os

import pytest

from bench_paths import BENCH, ROOT
from harness import load_module
from orion_tpu.obs import trace

reader = load_module("readers", "step_record")

CELLS = ["lm_1b3.train", "hybrid_1b3.train", "qwen3_next_80b.train",
         "lm_1b3.train_fsdp4"]
T0 = 5000.0  # the window's first span starts here, seconds on time.monotonic
PERIOD = 1500.0  # ms
NEXT, DISPATCH, SELF = 0.05, 3.0, 0.45  # ms of a normal period


def metric_args():
    """name -> the ``args`` of each metric file that names this reader."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = json.load(f)["per_layer"]
    out = {}
    for entry in entries:
        with open(os.path.join(BENCH, "layer_metrics", entry["name"] + ".json")) as f:
            spec = json.load(f)
        if spec["reader"] == "step_record":
            assert entry["workloads"] == CELLS and entry["layer"] == spec["layer"]
            assert entry["source"] == "program_span"
            assert entry["moves"] == "train_tok_s_chip"
            out[entry["name"]] = spec["args"]
    return out


def event(name, ts_ms, dur_ms, cat="step", **args):
    return {"name": name, "cat": cat, "ph": "X", "ts": ts_ms * 1e3,
            "dur": dur_ms * 1e3, "pid": 1, "tid": 1, "args": args}


def window(n=10, long_at=None, where="hook", excess=1.2 * PERIOD, ready=2):
    """``n`` + 2 tiling periods from one before the window's start, written
    in the record's order (children before their parent), and the evidence
    of a window that opens inside the first and closes inside the last."""
    record, t = [], T0 * 1e3 - 100.0
    for step in range(100, 100 + n + 2):
        extra = excess if step == long_at else 0.0
        gap = extra if where == "self" else 0.0  # before any child starts
        hook = PERIOD - NEXT - DISPATCH - SELF + (extra if where == "hook" else 0.0)
        record += [
            event("train.next_batch", t + gap, NEXT, step=step, ready=ready),
            event("train.dispatch", t + gap + NEXT, DISPATCH, step=step),
            event("train.hook", t + gap + NEXT + DISPATCH + SELF, hook, step=step),
            event("train.step", t, PERIOD + extra, step=step, tokens=16384),
        ]
        t += PERIOD + extra
    evidence = {
        # the first loader span lies just inside the window's first period
        "spans": [("block", T0 + 1.0, 1.4), ("loader", T0 + 1e-4, 1e-5)],
        # the window closes half a period into the last parent
        "window_s": (t - PERIOD / 2) / 1e3 - (T0 + 1e-4),
    }
    return record, evidence


@pytest.fixture
def recorded(monkeypatch):
    def install(steps, setup=()):
        monkeypatch.setattr(trace, "step_record", lambda: list(steps), raising=False)
        monkeypatch.setattr(trace, "setup_record", lambda: list(setup))
    return install


def read_all(evidence):
    return {name: reader.read(evidence, **args) for name, args in metric_args().items()}


def test_nine_metrics_of_an_even_window(recorded, capsys):
    record, evidence = window()
    recorded(record)
    got = read_all(evidence)
    assert len(got) == 9
    # a share names the longest span it summed: nothing else is printed
    (note,) = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    assert note == {"step_record": "train.next_batch", "spans": 11,
                    "max_ms": NEXT, "max_at_step": 100}
    # the parent before the window's start is kept (it holds the first
    # span), the last one, which the window's end cuts, is not: ten and one
    kept = reader.periods(evidence)
    assert [p["args"]["step"] for p, _, _ in kept] == list(range(100, 111))
    assert all(len(kids) == 3 for _, kids, _ in kept)
    assert got["step_ms_p50.train"] == pytest.approx(PERIOD)
    assert got["step_ms_max.train"] == pytest.approx(PERIOD)
    assert got["step_long_periods.train"] == 0
    assert got["step_max_excess_host_ms.train"] == pytest.approx(0.0, abs=1e-6)
    assert got["next_batch_wait_share.train"] == pytest.approx(100 * NEXT / PERIOD)
    assert got["loader_starved_share.train"] == 0.0
    assert got["dispatch_ms_p50.train"] == pytest.approx(DISPATCH)
    assert got["loop_host_share.train"] == pytest.approx(
        100 * (DISPATCH + SELF) / PERIOD)
    assert got["host_gc_ms.train"] == 0.0


@pytest.mark.parametrize("where,host_excess", [("hook", 0.0), ("self", 1.2 * PERIOD)])
def test_one_long_period_names_where_its_excess_lies(recorded, capsys, where, host_excess):
    record, evidence = window(long_at=105, where=where)
    inside = 105 - 100  # periods before it
    at = T0 * 1e3 - 100.0 + inside * PERIOD
    record.append(event("host.gc", at + 10.0, 170.0, generation=2, collected=12))
    record.append(event("host.gc", at - 400.0, 0.0015e3, generation=1, collected=0))
    compiled = [
        event("compile.backend", at + 300.0, 250.0, "compile",
              fun_name="jit(_train_step)", source="compiled"),
        event("compile.backend", at - 3000.0, 250.0, "compile",
              fun_name="jit(init)", source="cache"),
        event("setup.trainer", at + 1.0, 1.0, "setup"),
    ]
    recorded(record, compiled)
    capsys.readouterr()
    got = read_all(evidence)
    assert got["step_long_periods.train"] == 1
    assert got["step_ms_p50.train"] == pytest.approx(PERIOD)
    assert got["step_ms_max.train"] == pytest.approx(2.2 * PERIOD)
    assert got["step_max_excess_host_ms.train"] == pytest.approx(host_excess, abs=1e-6)
    assert got["host_gc_ms.train"] == pytest.approx(171.5)
    # ONE line for the long period, whatever else was read
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    (note,) = [x for x in lines if "long_period" in x]
    assert note["long_period"] == 105
    assert note["excess_ms"] == pytest.approx(1.2 * PERIOD)
    assert note["median_ms"] == pytest.approx(PERIOD)
    assert note["host_ms"] == pytest.approx(DISPATCH + SELF + host_excess)
    assert note["self_ms"] == pytest.approx(SELF + host_excess)
    assert note["ms_by_child"]["train.dispatch"] == pytest.approx(DISPATCH)
    assert note["ms_by_child"]["train.hook"] == pytest.approx(
        PERIOD - NEXT - DISPATCH - SELF + 1.2 * PERIOD - host_excess)
    assert note["inside"] == [["host.gc", 2, 170.0],
                              ["compile.backend", "jit(_train_step)", 250.0]]


def test_a_starved_loader_and_an_iterator_that_cannot_say(recorded):
    record, evidence = window(ready=0)
    recorded(record)
    args = metric_args()["loader_starved_share.train"]
    assert reader.read(evidence, **args) == 100.0
    for e in record:
        e["args"].pop("ready", None)
    assert reader.read(evidence, **args) is None
    assert reader.read(evidence, **metric_args()["next_batch_wait_share.train"]) > 0


def test_a_record_the_windows_edge_cuts(recorded):
    record, evidence = window(n=1)  # three parents: before, whole, cut
    recorded(record)
    kept = reader.periods(evidence)
    assert [p["args"]["step"] for p, _, _ in kept] == [100, 101]
    # the loop left inside the window: its last iteration, which waits for
    # its own step on the way out, is no period either
    record[-1]["dur"] *= 2
    record.insert(-1, event("train.log_readback", record[-1]["ts"] / 1e3 + PERIOD,
                            PERIOD, step=102))
    evidence["window_s"] += 10.0
    assert [p["args"]["step"] for p, _, _ in reader.periods(evidence)] == [100, 101]
    assert reader.read(evidence, "long_periods") == 0
    # a window shorter than the period it opens in holds no whole period
    evidence["window_s"] = 1.0
    assert reader.periods(evidence) is None
    assert all(v is None for v in read_all(evidence).values())
    with pytest.raises(ValueError):
        reader.read(window()[1], "nonsense")


def test_nothing_to_read_is_none(recorded, monkeypatch):
    record, evidence = window()
    recorded([])
    assert all(v is None for v in read_all(evidence).values())
    recorded(record)
    assert all(v is None for v in read_all({"spans": [], "window_s": 50.0}).values())
    assert all(v is None for v in read_all({**evidence, "window_s": None}).values())
    # a program without the record (the parent commit)
    monkeypatch.delattr(trace, "step_record")
    assert all(v is None for v in read_all(evidence).values())
