"""Round-3 perf tool: trace the flagship train step and print a device-op
breakdown grouped by op family (temporary script, like exp_perf.py).

Usage: python exp_profile.py [config] [batch] [seq]
Writes the Perfetto trace under /tmp/orion_trace and prints grouped
device-op times (ms per step) to stdout as JSON lines.
"""
import dataclasses
import glob
import gzip
import json
import os
import shutil
import sys
import time


def build(config, batch_size, seq_len):
    import jax.numpy as jnp

    from orion_tpu.models.configs import get_config
    from orion_tpu.parallel.mesh import MeshConfig
    from orion_tpu.training.data import SyntheticDataset
    from orion_tpu.training.trainer import TrainConfig, Trainer

    model = dataclasses.replace(
        get_config(config), max_seq_len=seq_len, remat=True
    )
    cfg = TrainConfig(
        model=model, steps=10**9, batch_size=batch_size, seq_len=seq_len,
        optimizer="adafactor", mu_dtype=None, lr=1e-4, warmup_steps=10,
        mesh=MeshConfig(dp=1), log_every=10**9,
    )
    trainer = Trainer(cfg)
    batch = jnp.asarray(
        SyntheticDataset(model.vocab_size, seq_len).batch(0, 0, batch_size)
    )
    return trainer, batch


GROUPS = [
    ("attn_kernel", ("tpu_custom_call", "custom-call")),
    ("copy", ("copy",)),
    ("convolution", ("convolution",)),
    ("scatter", ("scatter",)),
    ("gather", ("gather", "dynamic-slice")),
    ("reduce", ("reduce",)),
    ("fusion", ("fusion",)),
]


def classify(name: str) -> str:
    n = name.lower()
    for g, keys in GROUPS:
        if any(k in n for k in keys):
            return g
    return "other"


def parse_trace(logdir: str, n_steps: int):
    # the perfetto trace: one trace.json.gz per run
    paths = glob.glob(
        os.path.join(logdir, "plugins/profile/*/*.trace.json.gz")
    )
    if not paths:
        raise FileNotFoundError(f"no trace under {logdir}")
    with gzip.open(sorted(paths)[-1], "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    # find device-side process ids ("/device:TPU" or "TPU" in process_name)
    dev_pids = set()
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pname = e.get("args", {}).get("name", "")
            if "TPU" in pname and "host" not in pname.lower():
                dev_pids.add(e.get("pid"))
    by_group = {}
    by_name = {}
    for e in events:
        if e.get("ph") != "X" or e.get("pid") not in dev_pids:
            continue
        name = e.get("name", "")
        dur = e.get("dur", 0) / 1000.0  # us -> ms
        g = classify(name)
        by_group[g] = by_group.get(g, 0.0) + dur
        key = name.split(".")[0][:60]
        by_name[key] = by_name.get(key, 0.0) + dur
    total = sum(by_group.values())
    print(json.dumps({
        "per_step_ms": {k: round(v / n_steps, 1)
                        for k, v in sorted(by_group.items(),
                                           key=lambda kv: -kv[1])},
        "total_per_step_ms": round(total / n_steps, 1),
    }), flush=True)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:25]
    for name, ms in top:
        print(json.dumps({"op": name, "ms_per_step": round(ms / n_steps, 2)}),
              flush=True)


def main():
    import jax

    from orion_tpu.utils.cache import enable_compile_cache

    enable_compile_cache()
    config = sys.argv[1] if len(sys.argv) > 1 else "lm_1b3"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    seq = int(sys.argv[3]) if len(sys.argv) > 3 else 2048
    n_steps = 3
    trainer, b = build(config, batch, seq)
    m = trainer.step(b)
    m = trainer.step(b)
    jax.block_until_ready(m)
    logdir = "/tmp/orion_trace"
    shutil.rmtree(logdir, ignore_errors=True)
    t0 = time.perf_counter()
    jax.profiler.start_trace(logdir)
    for _ in range(n_steps):
        m = trainer.step(b)
    jax.block_until_ready(m)
    jax.profiler.stop_trace()
    dt = (time.perf_counter() - t0) / n_steps
    print(json.dumps({"wall_step_ms": round(1000 * dt, 1),
                      "config": config, "batch": batch, "seq": seq}),
          flush=True)
    parse_trace(logdir, n_steps)


if __name__ == "__main__":
    main()
