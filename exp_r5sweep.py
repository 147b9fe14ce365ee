"""Round-5 sweep (VERDICT r4 #1): param_storage="bfloat16_sr" x batch x
remat_skip on the flagship lm_1b3, single 16GB chip.

The r4 negatives proved the 16GB wall for the fp32-param state layout;
bf16 storage + stochastic-rounding updates halves both the persistent
param bytes and the grad buffer (~5.3GB back at 1.3B), which should buy
un-rematted blocks (~11ms each by the r3/r4 accounting). Control row
reproduces the fp32 headline at its shipped operating point. Emits one
JSON line per point on stdout.
"""
import dataclasses as dc
import json
import sys
import time


def run(tag, batch_size, skip, storage, seq_len=2048, iters=10,
        policy="full"):
    import gc

    import jax
    import jax.numpy as jnp

    from orion_tpu.models.configs import get_config
    from orion_tpu.parallel.mesh import MeshConfig
    from orion_tpu.training.data import SyntheticDataset
    from orion_tpu.training.trainer import TrainConfig, Trainer

    model = dc.replace(
        get_config("lm_1b3"), max_seq_len=seq_len, remat=True,
        remat_skip=skip, remat_policy=policy,
    )
    cfg = TrainConfig(model=model, steps=10**9, batch_size=batch_size,
                      seq_len=seq_len, optimizer="adafactor", mu_dtype=None,
                      lr=1e-4, warmup_steps=10, mesh=MeshConfig(dp=1),
                      log_every=10**9, param_storage=storage)
    ok = False
    try:
        trainer = Trainer(cfg)
        batch = jnp.asarray(
            SyntheticDataset(model.vocab_size, seq_len).batch(0, 0, batch_size)
        )
        m = trainer.step(batch)
        m = trainer.step(batch)
        float(m["loss"])
        t0 = time.perf_counter()
        for _ in range(iters):
            m = trainer.step(batch)
        float(m["loss"])
        dt = time.perf_counter() - t0
        toks = batch_size * seq_len * iters / dt
        print(json.dumps({"tag": tag, "storage": storage, "batch": batch_size,
                          "skip": skip, "policy": policy,
                          "tok_s": round(toks, 1),
                          "step_ms": round(1000 * dt / iters, 1),
                          "loss": round(float(m["loss"]), 3),
                          "mfu": round(toks * 6 * 1.284e9 / 197e12, 4)}),
              flush=True)
        ok = True
    except Exception as e:
        msg = str(e).splitlines()[0][:160] if str(e) else repr(e)
        print(json.dumps({"tag": tag, "storage": storage, "batch": batch_size,
                          "skip": skip, "policy": policy, "error": msg}),
              flush=True)
    finally:
        trainer = batch = m = None  # noqa: F841
        gc.collect()
        jax.clear_caches()
    return ok


PHASES = {
    "phase1": lambda: [
        # control: the shipped fp32 operating point (r4 headline repro)
        run("control_fp32", 12, 6, "float32"),
    ] + [
        run(f"sr_b{b}_skip{k}", b, k, "bfloat16_sr")
        for b, skips in ((12, [6, 10, 14, 18, 24]), (16, [8, 12, 16]),
                         (24, [6, 10]))
        for k in skips
    ],
    # phase2: the freed HBM makes remat_policy="dots" affordable (every
    # dots row compile-OOM'd in the r4 fp32-state sweep) + the in-between
    # batch/skip points phase1 skipped over
    "phase2": lambda: [
        run("sr_b12_skip6_dots", 12, 6, "bfloat16_sr", policy="dots"),
        run("sr_b12_skip0_dots", 12, 0, "bfloat16_sr", policy="dots"),
        run("sr_b12_skip8", 12, 8, "bfloat16_sr"),
        run("sr_b16_skip6", 16, 6, "bfloat16_sr"),
        run("sr_b16_skip4", 16, 4, "bfloat16_sr"),
        run("sr_b16_skip0_dots", 16, 0, "bfloat16_sr", policy="dots"),
        run("sr_b14_skip8", 14, 8, "bfloat16_sr"),
    ],
}

if __name__ == "__main__":
    from orion_tpu.utils.cache import enable_compile_cache

    enable_compile_cache()
    for phase in (sys.argv[1:] or ["phase1"]):
        PHASES[phase]()
