"""The trace reducer on a hand-worked example and on a cut of a chip trace."""

import json
import os

import pytest

from bench_paths import HERE
from readers import xplane

DEV = "/device:TPU:0"


def capture(ops, host=()):
    return {"planes": [
        {"name": DEV, "lines": [{"name": "XLA Ops", "events": ops},
                                {"name": "XLA Modules", "events": [["m", 0, 10**6]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": list(host)}]},
    ]}


def test_hand_worked_busy_idle_and_names():
    # ns:   a 0-100 | gap 100-150 | while 150-400 holding b 150-250 and
    #       kernel 250-400 | gap 400-1000 | c 1000-1100 (overlapped by an
    #       async d 1050-1200)
    ops = [["a", 0, 100], ["while", 150, 250], ["b", 150, 100],
           ["k tpu_custom_call -> f32[8]", 250, 150], ["c", 1000, 100], ["d", 1050, 150]]
    host = [["loader", 90, 70], ["block", 380, 700], ["other", 0, 2000]]
    cap = capture(ops, host)
    s = xplane.device_seconds(cap)
    assert s["busy_s"] == pytest.approx((100 + 250 + 200) * 1e-9)
    assert s["window_s"] == pytest.approx(1200e-9)
    idle = xplane.read({"xplane": cap}, what="idle_share")
    assert idle == pytest.approx(100 * (1 - 550 / 1200))
    # the enclosing while is not counted beside its children; c is a parent
    # of nothing but is overlapped by d, which starts inside it
    names = xplane.name_seconds(ops)
    assert "while" not in names and names["b"] == pytest.approx(100e-9)
    share = xplane.read({"xplane": cap}, what="name_share", pattern=" tpu_custom_call -> ")
    assert share == pytest.approx(100 * 150 / 550)
    gaps = xplane.idle_gaps(cap, ["loader", "block"])
    assert gaps[0] == ["block", pytest.approx(600e-9)]
    assert gaps[1] == ["loader", pytest.approx(50e-9)]
    assert xplane.idle_gaps(cap, [])[0][0] == "unattributed"
    assert xplane.read({"xplane": None}, what="idle_share") is None
    assert xplane.read({"xplane": {"planes": []}}, what="idle_share") is None


def test_short_name():
    hlo = ('%attn._kernel_bh.90 = (f32[192,2048,128]{2,1,0:T(8,128)}, f32[192,2048,1]{2,1,0:T(8,128)}) '
           'custom-call(bf16[192,2048,128]{2,1,0:T(8,128)(2,1)} %bitcast.6055), custom_call_target="tpu_custom_call"')
    assert xplane.short_name(hlo) == "attn._kernel_bh tpu_custom_call -> (f32[192,2048,128], f32[192,2048,1])"
    hlo = "%fusion.12 = bf16[12,2048,5504]{2,1,0:T(8,128)(2,1)} fusion(bf16[12,2048,2048]{2,1,0} %custom-call.3), kind=kOutput"
    assert xplane.short_name(hlo) == "fusion -> bf16[12,2048,5504]"
    assert xplane.short_name("loader") == "loader"


def test_recorded_chip_trace_cut():
    with open(os.path.join(HERE, "fixtures", "train_step_boundary.json")) as f:
        cap = json.load(f)
    want = cap["expected"]
    s = xplane.device_seconds(cap)
    assert s["busy_s"] * 1e9 == pytest.approx(want["busy_ns"], rel=1e-5)
    assert s["window_s"] * 1e9 == pytest.approx(want["window_ns"], rel=1e-5)
    gaps = xplane.idle_gaps(cap, ["loader", "step", "block"])
    assert [round(g[1] * 1e9) for g in gaps[:3]] == want["longest_gaps_ns"]
    assert gaps[0][0] == "block"  # the host was waiting for the step before
    b = xplane.breakdown(cap, ["loader", "step", "block"])
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert sum(v for _, v in b["device_ops"]) <= s["busy_s"] * (1 + 1e-9)
