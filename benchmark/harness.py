"""What every kind of run shares: finding files by name, claiming the
device, the compile cache, host spans, and reading metrics through readers."""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
NO_ACCELERATOR_RC = 2
# the manifest's section -> the folder holding one file per metric
METRIC_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json "
                     f"(have: {[e['name'] for e in entries]})")


def load_module(folder: str, name: str):
    """``benchmark/<folder>/<name>.py`` as a module, found by file name."""
    path = os.path.join(HERE, folder, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"no file {path}")
    modname = f"benchmark_{folder}_{name}"
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def note(**fields) -> None:
    """An observation line (never the last line of stdout)."""
    print(json.dumps(fields, default=str), flush=True)


def claim_devices(root: str, chips: int, rehearse: bool) -> dict:
    """Set the compile cache, import jax, and refuse the wrong device.

    The cache directory is fixed (the path is part of the cache's key):
    ``JAX_COMPILATION_CACHE_DIR`` where the environment sets it, otherwise
    ``<checkout>/.jax_cache``. Every program is cached, however quick its
    compile, so a warm start finds the small ones too."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" and not rehearse:
        print(f"no accelerator: jax found {device}; the benchmark never "
              "accepts the CPU without --rehearse", file=sys.stderr)
        sys.exit(NO_ACCELERATOR_RC)
    if device["count"] != chips and not rehearse:
        print(f"the cell needs {chips} chip(s), jax found {device['count']}",
              file=sys.stderr)
        sys.exit(NO_ACCELERATOR_RC)
    return device


@dataclasses.dataclass
class Run:
    root: str
    t0: float  # time.monotonic() at process start
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    cell: dict  # the BENCHMARK.json workload entry
    workload: dict  # benchmark/workloads/<cell>.json
    config: dict  # the configuration's file
    device: dict

    def sized(self, section: dict) -> dict:
        """A section of a data file, with its ``rehearse`` overrides applied
        under --rehearse (tiny sizes for the CPU)."""
        out = {k: v for k, v in section.items() if k != "rehearse"}
        if self.rehearse:
            out.update(section.get("rehearse", {}))
        return out

    def scratch_dir(self, name: str) -> str:
        """An empty directory inside the checkout for what a run leaves
        behind (profiles); listed in .gitignore."""
        d = os.path.join(self.root, ".bench_scratch", name)
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
        return d


def model_config(run: Run, **extra):
    from orion_tpu.models.configs import ModelConfig

    fields = run.sized(run.config["model"])
    if fields.get("layer_types") is not None:
        fields["layer_types"] = tuple(fields["layer_types"])
    fields.update(extra)
    return ModelConfig(name=run.cell["config"], **fields)


def reference_spec(cfg) -> dict:
    return {"n_layers": cfg.n_layers, "n_heads": cfg.n_heads,
            "head_dim": cfg.resolved_head_dim,
            "layer_types": cfg.resolved_layer_types, "window": cfg.window}


class Spans:
    """The benchmark's own host spans: (name, start, duration) on
    time.monotonic(), and the same name as a TraceAnnotation so the device
    trace can attribute idle gaps to what the host was doing."""

    def __init__(self):
        self.items: List[tuple] = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax.profiler

        t = time.monotonic()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.items.append((name, t, time.monotonic() - t))

    def within(self, start: float, end: float) -> List[tuple]:
        return [s for s in self.items if s[1] >= start and s[1] + s[2] <= end]


class CompileCounter:
    """Backend compilations and persistent-cache hits, from jax's own
    monitoring events; ``mark()`` starts the count for the measured window."""

    def __init__(self, t0: float):
        import jax.monitoring as mon

        self.compiles = self.hits = 0
        self.compile_s = 0.0
        self._mark = (0, 0.0)
        self.phases: Dict[str, dict] = {}
        self._phase_t, self._phase_c = t0, (0, 0.0, 0)
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self) -> None:
        self._mark = (self.compiles, self.compile_s)

    def phase(self, name: str) -> None:
        """Close a set-up phase: its wall seconds, and the programs and
        compile-or-load seconds jax spent inside it."""
        now = time.monotonic()
        self.phases[name] = {
            "s": round(now - self._phase_t, 3),
            "programs": self.compiles - self._phase_c[0],
            "compile_s": round(self.compile_s - self._phase_c[1], 3),
            "cache_hits": self.hits - self._phase_c[2],
        }
        self._phase_t, self._phase_c = now, (self.compiles, self.compile_s, self.hits)

    def since_mark(self) -> dict:
        return {"programs": self.compiles - self._mark[0],
                "seconds": self.compile_s - self._mark[1]}


def memory_stats() -> Dict[str, int]:
    """memory_stats() of the fullest chip (by peak_bytes_in_use)."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    return max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))


def footprint_bytes(stats: dict) -> int:
    """Device memory held at the moment of a ``memory_stats()`` sample: live
    arrays plus what the loaded programs reserve beside them (the train step's
    scratch). The two
    PEAK counters cannot be added — they peak at different moments (weights
    being made, before any program is loaded) — so the lasting footprint is
    reported, or the arrays' own peak where that is larger."""
    return int(max(stats.get("peak_bytes_in_use", 0),
                   stats.get("bytes_in_use", 0) + stats.get("bytes_reserved", 0)))


def device_readings(evidence: dict, traced: bool) -> dict:
    """``memory_peak_bytes`` on the fullest chip and, for a traced run,
    ``busy_s`` / ``window_s`` from the device trace."""
    out = {"memory_peak_bytes": footprint_bytes(evidence.get("memory") or {})}
    if traced:
        seconds = load_module("readers", "xplane").device_seconds(
            evidence.get("xplane"))
        if seconds:
            out.update(seconds)
    return out


def percentile(values: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (q in 0..100) of all values; None if empty."""
    if not values:
        return None
    s = sorted(values)
    k = max(0, min(len(s) - 1, int(-(-q * len(s) // 100)) - 1))
    return s[k]


def read_metrics(entries: List[dict], section: str, cell: str,
                 evidence: dict) -> Dict[str, dict]:
    """Each metric of the manifest's ``section`` that this cell reports,
    through the reader its own file names. A reader that finds nothing
    returns None and the metric is left out of the line."""
    out: Dict[str, dict] = {}
    for entry in entries:
        if "workloads" in entry and cell not in entry["workloads"]:
            continue
        spec = load_json(os.path.join(
            HERE, METRIC_DIRS[section], entry["name"] + ".json"))
        reader = load_module("readers", spec["reader"])
        value = reader.read(evidence, **spec.get("args", {}))
        if value is None:
            note(metric=entry["name"], skipped="its reader found nothing")
            continue
        out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out
