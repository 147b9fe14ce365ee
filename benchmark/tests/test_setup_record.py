"""``readers/setup_record.py`` against recorded rehearsals: the program's
set-up record of a CPU ``--rehearse`` run of a serving and of a training
cell, each beside the evidence its kind handed over and the readings that
run printed (``tests/data/setup_record_*.json``, written by the run itself).
"""

import json
import os

import pytest

from bench_paths import BENCH, HERE, ROOT
from harness import load_module
from orion_tpu.obs import trace

reader = load_module("readers", "setup_record")


def fixture(kind):
    with open(os.path.join(HERE, "data", f"setup_record_{kind}.json")) as f:
        return json.load(f)


def metric_args():
    """name -> the ``args`` of each metric file that names this reader."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [e["name"] for e in json.load(f)["per_layer"]]
    out = {}
    for name in names:
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        if spec["reader"] == "setup_record":
            out[name] = spec["args"]
    return out


def event(name, ts_s, dur_s, cat="setup", **args):
    return {"name": name, "cat": cat, "ph": "X", "ts": ts_s * 1e6,
            "dur": dur_s * 1e6, "pid": 1, "tid": 1, "args": args}


@pytest.fixture
def recorded(monkeypatch):
    def install(record):
        monkeypatch.setattr(trace, "setup_record", lambda: list(record))
    return install


@pytest.mark.parametrize("kind", ["serve", "train"])
def test_each_reading_of_a_recorded_rehearsal(kind, recorded):
    fx = fixture(kind)
    recorded(fx["record"])
    args = metric_args()
    assert len(args) == 7 and set(args) == set(fx["metrics"])
    for name, want in fx["metrics"].items():
        got = reader.read(fx["evidence"], **args[name])
        assert got == pytest.approx(want, rel=1e-9), name
    # the readings are what the record holds before the window, by hand
    cut = reader.window_start_us(fx["evidence"])
    assert cut == (fx["evidence"]["tracer"][0]["ts"] if kind == "serve"
                   else fx["evidence"]["spans"][0][1] * 1e6)
    before = [e for e in fx["record"] if e["ph"] == "X" and e["ts"] < cut]
    assert 0 < len(before) < len(fx["record"])  # the run went on after the window opened
    build = "setup.server" if kind == "serve" else "setup.trainer"
    (built,) = [e for e in before if e["name"] == build]
    assert reader.read(fx["evidence"], "span_s", names=[build]) == built["dur"] / 1e6
    backend = [e for e in before if e["name"] == "compile.backend"]
    assert backend and all(e["args"]["fun_name"] for e in backend)
    loaded = reader.read(fx["evidence"], "count", names=["compile.backend"], source="cache")
    assert loaded == sum(e["args"]["source"] == "cache" for e in backend) > 0
    assert 0 < fx["metrics"]["setup_unattributed_share"] < 100


def test_the_cut_at_the_first_in_window_event(recorded):
    evidence = {"tracer": [{"ts": 100e6}, {"ts": 90e6}], "values": {"setup_seconds": 50.0}}
    recorded([
        event("setup.import", 45, 5),
        event("compile.backend", 60, 2, "compile", fun_name="jit(a)", source="compiled"),
        event("compile.backend", 88, 4, "compile", fun_name="jit(b)", source="cache"),  # straddles
        event("compile.backend", 95, 1, "compile", fun_name="jit(c)", source="compiled"),  # in the window
        {"name": "setup.ready", "cat": "setup", "ph": "i", "ts": 70e6, "pid": 1, "tid": 1},
    ])
    assert reader.window_start_us(evidence) == 90e6
    assert reader.read(evidence, "span_s", names=["compile.backend"]) == pytest.approx(2 + 2)
    assert reader.read(evidence, "count", names=["compile.backend"], source="compiled") == 1
    assert reader.read(evidence, "span_s", names=["setup.import"]) == pytest.approx(5)
    assert reader.read(evidence, "span_s", names=["setup.trainer"]) == 0.0
    # a training cell's window opens at its first span
    spans = {"spans": [("loader", 61.0, 0.1), ("block", 61.2, 1.0)], "values": {"setup_seconds": 20.0}}
    assert reader.window_start_us(spans) == 61e6
    assert reader.read(spans, "span_s", names=["compile.backend"]) == pytest.approx(1)
    with pytest.raises(ValueError):
        reader.read(evidence, "nonsense")


def test_the_union_behind_the_uncovered_share(recorded, capsys):
    evidence = {"tracer": [{"ts": 100e6}], "values": {"setup_seconds": 40.0}}
    recorded([
        event("setup.engine", 71, 2),  # inside setup.server
        event("setup.server", 70, 10),
        event("compile.trace", 72, 1, "compile", fun_name="f"),  # inside too
        event("compile.backend", 79, 3, "compile", fun_name="jit(f)", source="cache"),  # overlaps its end
        event("setup.first_launch", 85, 5),
        event("setup.first_launch", 95, 10),  # straddles the window's start
    ])
    # covered: [70, 82] + [85, 90] + [95, 100] = 22 of 40 s
    assert reader.read(evidence, "uncovered_share") == pytest.approx(100 * 18 / 40)
    note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert note["covered_s"] == pytest.approx(22)
    assert note["seconds_by_span"] == {"setup.server": 10.0, "setup.first_launch": 10.0}
    assert reader.read({**evidence, "values": {}}, "uncovered_share") is None


def test_nothing_to_read_is_none(recorded, monkeypatch):
    evidence = {"tracer": [{"ts": 100e6}], "values": {"setup_seconds": 40.0}}
    readings = list(metric_args().values())
    recorded([])
    assert all(reader.read(evidence, **a) is None for a in readings)
    recorded([event("setup.import", 200, 5)])  # only after the window opened
    assert all(reader.read(evidence, **a) is None for a in readings)
    recorded([event("setup.import", 45, 5)])
    assert all(reader.read({"values": {"setup_seconds": 40.0}}, **a) is None for a in readings)
    # a program without the record (the parent of the PR that brought it)
    monkeypatch.delattr(trace, "setup_record")
    assert all(reader.read(evidence, **a) is None for a in readings)
    monkeypatch.setitem(__import__("sys").modules, "orion_tpu.obs", None)  # no such module
    assert all(reader.read(evidence, **a) is None for a in readings)
