"""Round-3 sweep: fused CE x remat_skip on chip-scale configs (temp script,
like exp_perf.py). MFU uses the 1.284B active-param count shared by lm_1b3
and hybrid_1b3 — pass other configs only for tok/s, not MFU."""
import dataclasses as dc
import json
import sys
import time


def run_cfg(tag, config, batch_size, seq_len=2048, iters=8, **model_kw):
    import jax
    import jax.numpy as jnp

    from orion_tpu.models.configs import get_config
    from orion_tpu.parallel.mesh import MeshConfig
    from orion_tpu.training.data import SyntheticDataset
    from orion_tpu.training.trainer import TrainConfig, Trainer

    model_kw.setdefault("remat", True)
    model = dc.replace(get_config(config), max_seq_len=seq_len, **model_kw)
    cfg = TrainConfig(model=model, steps=10**9, batch_size=batch_size,
                      seq_len=seq_len, optimizer="adafactor", mu_dtype=None,
                      lr=1e-4, warmup_steps=10, mesh=MeshConfig(dp=1),
                      log_every=10**9)
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
        print(json.dumps({"tag": tag, "tok_s": round(toks, 1),
                          "step_ms": round(1000 * dt / iters, 1),
                          "mfu": round(toks * 6 * 1.284e9 / 197e12, 4)}),
              flush=True)
    except Exception as e:
        msg = str(e).splitlines()[0][:160] if str(e) else repr(e)
        print(json.dumps({"tag": tag, "error": msg}), flush=True)
    finally:
        import gc

        import jax

        gc.collect()
        jax.clear_caches()


def run(tag, batch_size, seq_len=2048, iters=8, **model_kw):
    run_cfg(tag, "lm_1b3", batch_size, seq_len, iters, **model_kw)


if __name__ == "__main__":
    from orion_tpu.utils.cache import enable_compile_cache

    enable_compile_cache()
    which = sys.argv[1:] or ["0", "2", "4", "6"]
    for k in which:
        run(f"b16_fusedce_skip{k}", 16, remat_skip=int(k))
