"""A cell, a configuration, a traffic mix and a per-layer metric dropped in
as NEW files (plus their BENCHMARK.json entries) are found and run without
editing any file that was there."""

import json
import os
import shutil
import subprocess
import sys

from bench_paths import BENCH, ROOT


def test_new_files_are_found_without_editing_anything(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(BENCH, copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (copy / "benchmark").rglob("*") if p.is_file()}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    base_cell = next(w for w in m["workloads"] if w["traffic"] == "train")
    base_cfg = next(c for c in m["configs"] if c["name"] == base_cell["config"])

    # a configuration: its own file of sizes
    cfg = json.loads((copy / base_cfg["file"]).read_text())
    cfg["name"] = "dropin_cfg"
    cfg["model"]["rehearse"]["n_layers"] = 3
    (copy / "benchmark/configs/dropin_cfg.json").write_text(json.dumps(cfg))
    m["configs"].append({**base_cfg, "name": "dropin_cfg",
                         "file": "benchmark/configs/dropin_cfg.json"})
    # a cell: a data file of job parameters
    job = json.loads((copy / "benchmark/workloads" / (base_cell["name"] + ".json")).read_text())
    job["job"]["rehearse"]["batch_size"] = 3
    (copy / "benchmark/workloads/dropin_cfg.dropin_mix.json").write_text(json.dumps(job))
    m["workloads"].append({**base_cell, "name": "dropin_cfg.dropin_mix",
                           "config": "dropin_cfg", "traffic": "dropin_mix"})
    # a per-layer metric: a small file naming a reader and its arguments
    (copy / "benchmark/layer_metrics/dropin_block_share.json").write_text(json.dumps(
        {"layer": "training loop", "reader": "span_share", "args": {"name": "block"}}))
    moved = next(e for e in m["end_to_end"] if "workloads" in e and base_cell["name"] in e["workloads"])
    moved["workloads"].append("dropin_cfg.dropin_mix")
    m["per_layer"].append({"name": "dropin_block_share", "unit": "%", "better": "lower",
                           "source": "program_span", "layer": "training loop",
                           "moves": moved["name"], "workloads": ["dropin_cfg.dropin_mix"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(m))

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    lines = {}
    for trace in ("0", "1"):
        out = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", "dropin_cfg.dropin_mix",
             "--seed", str(2**31 + 11), "--seconds", "1", "--trace", trace, "--rehearse"],
            cwd=copy, env=env, capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        lines[trace] = json.loads(out.stdout.strip().splitlines()[-1])
    assert lines["0"]["correct"] and set(lines["0"]["metrics"]) == {moved["name"], "setup_s"}
    assert lines["0"]["device"]["platform"] == "cpu"  # a rehearsal says so
    assert 0 < lines["1"]["metrics"]["dropin_block_share"]["value"] <= 100
    after = {p: p.read_bytes() for p in before}
    assert after == before  # nothing that was there changed

    # without --rehearse a CPU is refused and no result is printed
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "dropin_cfg.dropin_mix",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode != 0 and not out.stdout.strip()
