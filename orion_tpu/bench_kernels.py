"""Kernel-level micro-benchmarks: Pallas kernels vs their XLA twins
(VERDICT r1 item 3 — "prove the Pallas kernels beat XLA somewhere real").

Sweeps causal linear attention (fused Pallas kernel vs XLA chunked scan)
and softmax attention (Pallas flash vs XLA masked-dense) across sequence
lengths at a fixed per-layer operating shape, forward and forward+backward.
Used by ``bench.py --kernels`` on the real chip; results feed the
per-shape "auto" backend heuristic in ops/dispatch.py.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp


def _time_fn(fn: Callable, args, iters: int = 20, warmup: int = 2) -> float:
    """Median-of-3 wall time (ms) of ``iters`` back-to-back dispatches."""
    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / iters * 1000)
    return sorted(times)[1]


def _qkv(b, h, t, d, dtype=jnp.bfloat16, featurized=True):
    ks = jax.random.split(jax.random.key(0), 3)
    if featurized:  # post-feature-map positives, like the model's linear layers
        q = jax.nn.elu(jax.random.normal(ks[0], (b, h, t, d), dtype)) + 1
        k = jax.nn.elu(jax.random.normal(ks[1], (b, h, t, d), dtype)) + 1
    else:
        q = jax.random.normal(ks[0], (b, h, t, d), dtype)
        k = jax.random.normal(ks[1], (b, h, t, d), dtype)
    v = jax.random.normal(ks[2], (b, h, t, d), dtype)
    return q, k, v


def bench_linear_attention(shapes=None, iters: int = 20) -> List[Dict]:
    """Fused normalized linear attention: Pallas kernel vs XLA chunked."""
    from orion_tpu.ops.linear_attention import linear_attention

    if shapes is None:
        # fixed token budget b*t; h/d = lm_1b3 layer geometry
        shapes = [(16, 16, 2048, 128), (4, 16, 8192, 128), (2, 16, 16384, 128),
                  (1, 16, 32768, 128)]
    rows = []
    for b, h, t, d in shapes:
        q, k, v = _qkv(b, h, t, d)
        row = {"op": "linear_attention", "b": b, "h": h, "t": t, "d": d}
        for backend in ("xla", "pallas"):
            fwd = jax.jit(partial(linear_attention, backend=backend))

            def loss(q, k, v, _f=fwd):
                return _f(q, k, v).astype(jnp.float32).sum()

            fb = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            row[f"{backend}_fwd_ms"] = round(_time_fn(fwd, (q, k, v), iters), 3)
            row[f"{backend}_fwdbwd_ms"] = round(_time_fn(fb, (q, k, v), iters), 3)
        row["speedup_fwd"] = round(row["xla_fwd_ms"] / row["pallas_fwd_ms"], 3)
        row["speedup_fwdbwd"] = round(
            row["xla_fwdbwd_ms"] / row["pallas_fwdbwd_ms"], 3
        )
        rows.append(row)
    return rows


def _bench_softmax_family(
    op_name: str, window, shapes, iters: int
) -> List[Dict]:
    """Shared harness for the softmax-attention family: Pallas flash vs
    XLA masked-dense, optionally windowed."""
    from orion_tpu.ops.softmax_attention import softmax_attention

    if shapes is None:
        shapes = [(16, 16, 2048, 128), (4, 16, 8192, 128), (2, 16, 16384, 128)]
    rows = []
    for b, h, t, d in shapes:
        q, k, v = _qkv(b, h, t, d, featurized=False)
        row = {"op": op_name, "b": b, "h": h, "t": t, "d": d}
        if window is not None:
            row["window"] = window
        for backend in ("xla", "pallas"):
            fwd = jax.jit(
                partial(
                    softmax_attention, causal=True, window=window,
                    backend=backend,
                )
            )

            def loss(q, k, v, _f=fwd):
                return _f(q, k, v).astype(jnp.float32).sum()

            fb = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            try:
                row[f"{backend}_fwd_ms"] = round(_time_fn(fwd, (q, k, v), iters), 3)
                row[f"{backend}_fwdbwd_ms"] = round(
                    _time_fn(fb, (q, k, v), iters), 3
                )
            except Exception as e:  # dense T×T OOMs at long T
                row[f"{backend}_fwd_ms"] = None
                row[f"{backend}_fwdbwd_ms"] = None
                row[f"{backend}_error"] = str(e).splitlines()[0][:120]
        if row.get("xla_fwd_ms") and row.get("pallas_fwd_ms"):
            row["speedup_fwd"] = round(row["xla_fwd_ms"] / row["pallas_fwd_ms"], 3)
            row["speedup_fwdbwd"] = round(
                row["xla_fwdbwd_ms"] / row["pallas_fwdbwd_ms"], 3
            )
        rows.append(row)
    return rows


def bench_softmax_attention(shapes=None, iters: int = 20) -> List[Dict]:
    """Causal softmax attention: Pallas flash vs XLA masked-dense."""
    return _bench_softmax_family("softmax_attention", None, shapes, iters)


def bench_swa_attention(shapes=None, window: int = 1024, iters: int = 20) -> List[Dict]:
    """Sliding-window softmax (the 7B hybrid's dominant layer type,
    BASELINE.json config #5): Pallas flash with structural tile skipping
    vs XLA masked-dense. The flash path's cost is O(T·W); the dense path
    is O(T²) regardless of the window."""
    return _bench_softmax_family("swa_attention", window, shapes, iters)


def run_all(iters: int = 20) -> List[Dict]:
    return (
        bench_linear_attention(iters=iters)
        + bench_softmax_attention(iters=iters)
        + bench_swa_attention(iters=iters)
    )


if __name__ == "__main__":
    import json

    for r in run_all():
        print(json.dumps(r), flush=True)
