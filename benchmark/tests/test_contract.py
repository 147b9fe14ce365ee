"""BENCHMARK.json against the limits of the benchmark's contract, and the
rule that no cell, configuration or metric is named in code."""

import json
import os
import re

from bench_paths import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|head_dim|d_model|mlp|expand|experts_per)")


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def line(s, n=200):
    return isinstance(s, str) and 1 <= len(s) <= n and "\n" not in s and "\t" not in s


def test_manifest_meets_the_contract():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(m["command"]) <= 32 and all(line(w) for w in m["command"])
    assert 1 <= len(m["paths"]) <= 16
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert 1 <= len(m["configs"]) <= 24 and 1 <= len(m["workloads"]) <= 24
    assert 1 <= len(m["end_to_end"]) <= 16 and 1 <= len(m["per_layer"]) <= 128
    cells = [w["name"] for w in m["workloads"]]
    configs = [c["name"] for c in m["configs"]]
    assert len(set(cells)) == len(cells) and len(set(configs)) == len(configs)
    files = [c["file"] for c in m["configs"]]
    assert len(set(files)) == len(files)
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16
        assert not [k for k in c["reduced"] if WIDTH.search(k)], "a width is never reduced"
        assert c["name"] in {w["config"] for w in m["workloads"]}, "every config has a cell"
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    pairs = set()
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and line(w["why"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(1, len(cells) // 4)
    metrics = m["end_to_end"] + m["per_layer"]
    names = [e["name"] for e in metrics]
    assert len(set(names)) == len(names)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= e["bound"] <= 0.1 and e["source"] in ("host_clock", "device_trace")
    for e in m["per_layer"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert line(e["layer"]) and e["moves"] in e2e
    reports = lambda e, cell: "workloads" not in e or cell in e["workloads"]  # noqa: E731
    for e in metrics:
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher") and e["source"] in SOURCES
        assert all(c in cells for c in e.get("workloads", cells))
    for cell in cells:
        assert reports(e2e["setup_s"], cell)
        assert any(reports(e, cell) for e in m["end_to_end"] if e["name"] != "setup_s")
        mine = [e for e in m["per_layer"] if reports(e, cell)]
        assert mine
        for e in mine:  # what a layer metric moves is reported where it is
            assert reports(e2e[e["moves"]], cell), (e["name"], cell)
    # the longest check fits the driver's day
    runs = 2 + 14 * 24
    assert runs * (m["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_every_entry_has_its_files():
    m = manifest()
    for w in m["workloads"]:
        with open(os.path.join(BENCH, "workloads", w["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.isfile(os.path.join(BENCH, "kinds", spec["kind"] + ".py"))
        gen = spec.get("traffic", {}).get("generator")
        assert gen is None or os.path.isfile(os.path.join(BENCH, "traffic", gen + ".py"))
    for section, folder in (("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")):
        for e in m[section]:
            with open(os.path.join(BENCH, folder, e["name"] + ".json")) as f:
                spec = json.load(f)
            assert os.path.isfile(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
            if section == "per_layer":
                assert spec["layer"] == e["layer"]
    for root, _, names in os.walk(BENCH):
        if "__pycache__" in root:
            continue
        for n in names:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", n), n


def test_no_name_lives_in_code():
    m = manifest()
    names = ({w["name"] for w in m["workloads"]} | {c["name"] for c in m["configs"]}
             | {e["name"] for e in m["end_to_end"] + m["per_layer"]}
             | {w["traffic"] for w in m["workloads"]})
    names.discard("train")  # a kind of run and a traffic name share the word
    code = [os.path.join(BENCH, "run.py"), os.path.join(BENCH, "harness.py")]
    for folder in ("kinds", "readers", "traffic"):
        d = os.path.join(BENCH, folder)
        code += [os.path.join(d, n) for n in os.listdir(d) if n.endswith(".py")]
    for path in code:
        with open(path) as f:
            text = f.read()
        for name in names:
            assert not re.search(r"(?<![A-Za-z0-9_.])" + re.escape(name) + r"(?![A-Za-z0-9_])", text), (name, path)


def test_config_files_state_the_presets_sizes():
    from orion_tpu.models.configs import get_config

    for c in manifest()["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            spec = json.load(f)
        preset = get_config(spec["preset"])
        for key, value in spec["model"].items():
            if key == "rehearse":
                continue
            want = getattr(preset, key)
            if key == "head_dim":
                want = preset.resolved_head_dim
            if key == "mlp_hidden":
                want = preset.resolved_mlp_hidden
            if key == "layer_types":
                value = None if value is None else tuple(value)
            assert value == want, (c["name"], key, value, want)
