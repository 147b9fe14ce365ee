"""The two readers of the serve loop's phase spans: a hand-worked example
each, what the program really writes (a recording of its Tracer on the CPU, a
cut of a chip capture), and nothing to read from a program without the spans."""

import json
import os

import pytest

from bench_paths import BENCH, HERE
from readers import tracer_phases, tracer_spans, xplane_host

PARENT = "serve.boundary"


def X(name, ts, dur, boundary, **args):
    return {"name": name, "cat": "phase", "ph": "X", "ts": ts, "dur": dur,
            "args": dict(args, boundary=boundary)}


def test_hand_worked_phases():
    # us. boundary 1: 0-1000 (tick 0-100, probe 200-900, finish 900-950);
    # boundary 2: 1000-2100 (tick 1000-1300, probe 1400-2000); an idle wait;
    # boundary 3: 5000-6000 (probe 5100-5900); boundary 4 is the window's
    # last and is left out of the per-boundary readings
    ev = [X(PARENT, 0, 1000, 1, steps=4), X("serve.tick", 0, 100, 1),
          X("serve.probe", 200, 700, 1), X("serve.finish", 900, 50, 1),
          X(PARENT, 1000, 1100, 2, steps=4), X("serve.tick", 1000, 300, 2),
          X("serve.probe", 1400, 600, 2),
          X("serve.idle_wait", 2100, 2900, 3),
          X(PARENT, 5000, 1000, 3, steps=4), X("serve.probe", 5100, 800, 3),
          X(PARENT, 6000, 1000, 4, steps=4),
          X("serve.tick", 9000, 50, 9),  # no parent: an idle iteration's
          {"name": "decode_chunk", "cat": "chunk", "ph": "X", "ts": 100, "dur": 800,
           "args": {"req": "r", "slot": 0, "chunk": 0}}]
    e = {"tracer": ev}
    read = lambda **kw: tracer_phases.read(e, parent=PARENT, **kw)  # noqa: E731
    assert read(what="phase_ms", name="serve.tick", q=50) == pytest.approx(0.1)  # 0.1, 0.3, 0
    assert read(what="phase_ms", name="serve.tick", q=100) == pytest.approx(0.3)
    assert read(what="phase_ms", name="serve.finish", q=50) == 0.0  # 0.05, 0, 0
    # boundary - probe: 0.3, 0.5, 0.2
    assert read(what="self_ms", minus=["serve.probe"], q=50) == pytest.approx(0.3)
    assert read(what="self_ms", minus=["serve.probe"], q=100) == pytest.approx(0.5)
    # periods: 1000 (1->2) and 1000 (3->4); 2->3 has an idle wait between
    assert read(what="period_ms", q=95) == pytest.approx(1000 / 1e3 / 4)
    # no idle wait after all: 3 starts 50 us after 2 ends, and runs to 4's start
    ev[4]["dur"] = 1050
    ev[8]["ts"], ev[8]["dur"] = 2100, 3900
    assert read(what="period_ms", q=95) == pytest.approx(3900 / 1e3 / 4)
    # a program without the spans
    none = {"tracer": [ev[-1]]}
    for what in ("phase_ms", "self_ms", "period_ms"):
        assert tracer_phases.read(none, what=what, parent=PARENT, name="serve.tick") is None
    assert tracer_phases.read({}, what="phase_ms", parent=PARENT) is None


def test_recorded_tracer_events():
    """What the program's Server really wrote on the CPU (tiny model; times
    are the CPU's and mean nothing): every reading finds its events."""
    with open(os.path.join(HERE, "fixtures", "serve_phase_events.json")) as f:
        rec = json.load(f)
    e = {"tracer": rec["events"], "rids": set(rec["rids"])}
    found = tracer_phases.boundaries(rec["events"], PARENT)
    assert len(found) == rec["chunks"]
    inside = ("serve.tick", "serve.admit", "serve.dispatch", "serve.probe",
              "serve.finish", "serve.complete")
    for parent, kids in found:
        assert set(kids) == set(inside)
        assert sum(kids.values()) <= parent["dur"] / 1e3 + 1e-6
    host = tracer_phases.read(e, what="self_ms", parent=PARENT, minus=["serve.probe"])
    whole = tracer_phases.read(e, what="self_ms", parent=PARENT)
    probe = tracer_phases.read(e, what="phase_ms", parent=PARENT, name="serve.probe")
    assert 0 < host < whole and 0 < probe < whole
    assert tracer_phases.read(e, what="period_ms", parent=PARENT, q=95) > 0
    ttft = tracer_spans.read(e, what="async_ms", name="first_token", q=50)
    queue = tracer_spans.read(e, what="async_ms", name="queue", q=50)
    assert ttft > queue >= 0, "a first token comes after admission"
    # the older readings see the same boundaries as before
    names = ["decode_chunk", "prefill_piece"]
    assert tracer_spans.read(e, what="boundary_ms", names=names) > 0
    share = tracer_spans.read(e, what="boundary_share", names=names, having="prefill_piece")
    assert 0 < share <= 100


def capture(ops, host):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "serve-loop", "events": host}]},
    ]}


def test_hand_worked_idle_attribution(capsys):
    # ns. busy 0-100, 150-400, 1000-1100: gaps 100-150 (50) and 400-1000 (600)
    ops = [["scan", 0, 100], ["scan", 150, 250], ["scan", 1000, 100]]
    host = [[PARENT, 0, 1100], ["serve.probe", 0, 120], ["serve.finish", 120, 30],
            ["serve.probe", 150, 350], ["serve.finish", 500, 300], ["serve.tick", 800, 100],
            ["something else", 0, 2000]]
    phases = [PARENT, "serve.tick", "serve.probe", "serve.finish"]
    e = {"xplane": capture(ops, host)}
    share = xplane_host.read(e, what="idle_attributed_share", phases=phases,
                             exclude=[PARENT, "serve.probe"])
    # finish covers 120-150 (30) + 500-800 (300), tick 800-900 (100) of 650 idle
    assert share == pytest.approx(100 * 430 / 650)
    note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert note["idle_s"] == pytest.approx(650e-9)
    by = note["idle_s_by_phase"]
    assert by["serve.probe"] == pytest.approx(120e-9)  # 100-120 and 400-500
    assert by["serve.finish"] == pytest.approx(330e-9)
    assert by[PARENT] == pytest.approx(650e-9)
    assert note["idle_s_outside_every_phase"] == pytest.approx(0.0)
    # nothing excluded but the parent: probe's part counts too
    assert xplane_host.read(e, what="idle_attributed_share", phases=phases,
                            exclude=[PARENT]) == pytest.approx(100 * 550 / 650)
    # a program without the spans, a run without a capture, a CPU capture
    assert xplane_host.read({"xplane": capture(ops, host[-1:])}, what="idle_attributed_share",
                            phases=phases) is None
    assert xplane_host.read({"xplane": None}, what="idle_attributed_share", phases=phases) is None
    assert xplane_host.read({"xplane": {"planes": []}}, what="idle_attributed_share",
                            phases=phases) is None


def test_metric_files_name_the_programs_phases():
    """The phase names are data in the metric files; they are the program's."""
    from orion_tpu.serving import PHASES

    seen = 0
    folder = os.path.join(BENCH, "layer_metrics")
    for n in sorted(os.listdir(folder)):
        with open(os.path.join(folder, n)) as f:
            spec = json.load(f)
        args = spec.get("args", {})
        if spec["reader"] == "xplane_host":
            assert tuple(args["phases"]) == PHASES
            assert set(args["exclude"]) < set(PHASES)
            seen += 1
        if spec["reader"] == "tracer_phases":
            assert args["parent"] == PHASES[0]
            assert set(args.get("minus", [])) | ({args["name"]} if "name" in args else set()) <= set(PHASES)
            seen += 1
    assert seen >= 15


def test_recorded_chip_capture_cut(capsys):
    """A cut of the chat cell's capture on the chip (three scans, the two
    gaps between them): the first gap holds four admissions, the second
    none. Every idle second lies inside some phase; what is not attributed
    is the end of serve.probe (the flags' transfer and the thread's wake-up)."""
    with open(os.path.join(HERE, "fixtures", "serve_boundary_capture.json")) as f:
        cap = json.load(f)
    with open(os.path.join(BENCH, "layer_metrics", "idle_attributed_share.chat.json")) as f:
        args = json.load(f)["args"]
    share = xplane_host.read({"xplane": cap}, **args)
    note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    by, idle = note["idle_s_by_phase"], note["idle_s"]
    assert idle == pytest.approx(27.351e-3, rel=1e-3)
    assert note["idle_s_outside_every_phase"] < 0.01 * idle
    assert max(by, key=lambda n: by[n] if n != PARENT else 0) == "serve.admit"
    assert 2.0e-3 < by["serve.probe"] / 2 < 2.7e-3, "about 2.5 ms at the end of each probe"
    counted = sum(v for n, v in by.items() if n not in args["exclude"])
    assert share == pytest.approx(100 * counted / idle)  # the phases do not overlap
    assert 75 < share < 85
