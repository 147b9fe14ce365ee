"""Training metrics: JSONL + stdout logging over the telemetry spine.

Since ISSUE 9 the logger is a thin view over the shared
:class:`~orion_tpu.obs.metrics.MetricsRegistry` (the same registry kind
the serving and fleet layers expose): every scalar the trainer hands
over lands as a ``train_<name>`` gauge, steps count into
``train_steps_total``, and every step's period (the loop's own
``train.step`` span, a host float) feeds a ``step_time_ms`` histogram —
so one Prometheus scrape covers a box that both trains and serves. The
legacy behaviour (one JSON line per log point + a human-readable stdout
line with tokens/sec) is unchanged; callers that never pass a registry
get a private one for free.

The registry only ever sees HOST floats: the trainer already
materializes metrics at log cadence precisely so device scalars aren't
read every step, and this module must keep that property (lint rule
``obs-device-sync`` bars jax from the obs layer; this caller-side seam
is covered by the trainer's own log-cadence discipline).
"""

from __future__ import annotations

import json
import sys
import time
from typing import Dict, Optional

from orion_tpu.obs.metrics import MetricsRegistry


class MetricsLogger:
    def __init__(self, path: Optional[str] = None, stream=None,
                 registry: Optional[MetricsRegistry] = None):
        self._f = open(path, "a") if path else None
        self._stream = stream if stream is not None else sys.stdout
        self._last_time: Optional[float] = None
        self._last_step: Optional[int] = None
        self.registry = registry if registry is not None else MetricsRegistry()
        self._c_steps = self.registry.counter("train_steps_total")
        self._h_step_ms = self.registry.histogram("step_time_ms")

    def log(self, step: int, metrics: Dict[str, float], tokens_per_step: int = 0):
        now = time.perf_counter()
        rec = {"step": int(step)}
        rec.update({k: float(v) for k, v in metrics.items()})
        if self._last_time is not None and tokens_per_step and step > self._last_step:
            dt = now - self._last_time
            rec["tokens_per_sec"] = tokens_per_step * (step - self._last_step) / dt
            rec["step_time_ms"] = 1000.0 * dt / (step - self._last_step)
        if self._last_step is not None and step > self._last_step:
            self._c_steps.inc(step - self._last_step)
        self._last_time, self._last_step = now, step
        g = self.registry.gauge("train")
        g.set(step, labels={"metric": "step"})
        for k, v in rec.items():
            if k != "step":
                g.set(v, labels={"metric": k})
        if self._f:
            self._f.write(json.dumps(rec) + "\n")
            self._f.flush()
        parts = [f"step {rec['step']:>7d}"]
        for k in ("loss", "ppl", "grad_norm", "lr", "tokens_per_sec", "step_time_ms"):
            if k in rec:
                v = rec[k]
                parts.append(f"{k} {v:.4g}")
        print("  ".join(parts), file=self._stream, flush=True)

    def observe_step(self, period_ms: float) -> None:
        """One step's period into the ``step_time_ms`` histogram: the log
        line's ``step_time_ms`` is a mean over the log cadence, which hides
        exactly one long step."""
        self._h_step_ms.observe(period_ms)

    def dump(self, path: str) -> None:
        """Prometheus-text + JSON exposition of the training registry
        (``--metrics-path`` on the train CLI; atomic publish)."""
        self.registry.dump(path)

    def close(self):
        if self._f:
            self._f.close()


__all__ = ["MetricsLogger"]
