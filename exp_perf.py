"""Round-2 perf experiments on the real chip (temporary script)."""
import dataclasses
import json
import sys
import time


def run(tag, batch_size, seq_len=2048, iters=10, **model_kw):
    import jax
    import jax.numpy as jnp

    from orion_tpu.models.configs import get_config
    from orion_tpu.parallel.mesh import MeshConfig
    from orion_tpu.training.data import SyntheticDataset
    from orion_tpu.training.trainer import TrainConfig, Trainer

    opt = model_kw.pop("optimizer", "lion")
    mu_dtype = model_kw.pop("mu_dtype", "bfloat16")
    model_kw.setdefault("remat", True)
    model = dataclasses.replace(
        get_config("lm_1b3"), max_seq_len=seq_len, **model_kw
    )
    cfg = TrainConfig(
        model=model, steps=10**9, batch_size=batch_size, seq_len=seq_len,
        optimizer=opt, mu_dtype=mu_dtype, lr=1e-4, warmup_steps=10,
        mesh=MeshConfig(dp=1), log_every=10**9,
    )
    try:
        trainer = Trainer(cfg)
        batch = jnp.asarray(
            SyntheticDataset(model.vocab_size, seq_len).batch(0, 0, batch_size)
        )
        trainer.step(batch)
        trainer.step(batch)
        jax.block_until_ready(trainer.state.params)
        t0 = time.perf_counter()
        for _ in range(iters):
            trainer.step(batch)
        jax.block_until_ready(trainer.state.params)
        dt = time.perf_counter() - t0
        toks = batch_size * seq_len * iters / dt
        n_params = 1.28e9
        mfu = toks * 6 * n_params / 197e12
        print(json.dumps({"tag": tag, "tok_s": round(toks, 1),
                          "step_ms": round(1000 * dt / iters, 1),
                          "mfu": round(mfu, 4), "batch": batch_size}), flush=True)
        del trainer, batch
    except Exception as e:
        msg = str(e).splitlines()[0][:200] if str(e) else repr(e)
        print(json.dumps({"tag": tag, "error": msg}), flush=True)


if __name__ == "__main__":
    from orion_tpu.utils.cache import enable_compile_cache

    enable_compile_cache()

    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    exps = {
        "base": lambda: run("b8_full_pallas", 8),
        "xla": lambda: run("b8_full_xla", 8, backend="xla"),
        "dots": lambda: run("b8_dots_pallas", 8, remat_policy="dots"),
        "dots_xla": lambda: run("b8_dots_xla", 8, backend="xla", remat_policy="dots"),
        "b16_xla": lambda: run("b16_full_xla", 16, backend="xla"),
        "b16_dots_xla": lambda: run("b16_dots_xla", 16, backend="xla", remat_policy="dots"),
        "b16_adafactor": lambda: run("b16_dots_xla_adafactor", 16, backend="xla",
                                     remat_policy="dots", optimizer="adafactor",
                                     mu_dtype=None),
    }
    for name, fn in exps.items():
        if which == "all" or which == name:
            fn()
