"""Round-5 swa sweep (VERDICT r4 #6 — clip, don't mask): the windowed
flash kernels now run a BANDED grid (k sweep covers only band tiles via a
qi-dependent index map), which also makes small block_k affordable.

Phase "kernel": fwd+bwd time of the windowed kernel at the hybrid
operating shapes (B12·H16, T2048, Dh128, W1024) — banded vs the full
quadratic grid on the SAME build (module switch), across block sizes.
Phase "step": full hybrid_1b3 train step at the shipped operating point
with the best blocks, and the same-run dense lm_1b3 for the ratio the
r3/r4 verdicts track (>= 0.84x target). Appends JSON lines to
R5SWA.jsonl.
"""
import dataclasses as dc
import json
import sys
import time

import jax
import jax.numpy as jnp


def bench_kernel(bq, bk, banded, iters=30):
    import orion_tpu.ops.pallas.flash_attention as fa

    fa._BANDED_ENABLED = banded
    bh, t, dh, w = 12 * 16, 2048, 128, 1024
    key = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(jax.random.fold_in(key, i), (bh, t, dh), jnp.bfloat16)
        for i in range(3)
    )

    @jax.jit
    def f(q, k, v):
        def loss(q, k, v):
            return (fa.flash_attention(
                q, k, v, causal=True, window=w, block_q=bq, block_k=bk
            ).astype(jnp.float32) ** 2).sum()
        l, g = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return l, g

    try:
        l, g = f(q, k, v)
        float(l)
        t0 = time.perf_counter()
        for _ in range(iters):
            l, g = f(q, k, v)
        float(l)
        ms = (time.perf_counter() - t0) / iters * 1000
        print(json.dumps({"phase": "kernel", "bq": bq, "bk": bk,
                          "banded": banded, "fwd_bwd_ms": round(ms, 2)}),
              flush=True)
    except Exception as e:
        print(json.dumps({"phase": "kernel", "bq": bq, "bk": bk,
                          "banded": banded,
                          "error": str(e).splitlines()[0][:160]}), flush=True)
    jax.clear_caches()


def bench_step(tag, config, bq=512, bk=512, iters=10):


    import orion_tpu.ops.pallas.flash_attention as fa

    fa._BANDED_ENABLED = True
    import dataclasses
    import time as _t

    from orion_tpu.models.configs import get_config
    from orion_tpu.parallel.mesh import MeshConfig
    from orion_tpu.training.data import SyntheticDataset
    from orion_tpu.training.trainer import TrainConfig, Trainer

    model = dataclasses.replace(
        get_config(config), max_seq_len=2048, remat=True, remat_skip=6,
        attn_block_q=bq, attn_block_k=bk,
    )
    cfg = TrainConfig(model=model, steps=10**9, batch_size=12, seq_len=2048,
                      optimizer="adafactor", lr=1e-4, warmup_steps=10,
                      mesh=MeshConfig(dp=1), log_every=10**9,
                      param_storage="bfloat16_sr")
    try:
        tr = Trainer(cfg)
        batch = jnp.asarray(SyntheticDataset(32000, 2048).batch(0, 0, 12))
        m = tr.step(batch); m = tr.step(batch); float(m["loss"])
        t0 = _t.perf_counter()
        for _ in range(iters):
            m = tr.step(batch)
        float(m["loss"])
        dt = _t.perf_counter() - t0
        toks = 12 * 2048 * iters / dt
        print(json.dumps({"phase": "step", "tag": tag, "bq": bq, "bk": bk,
                          "tok_s": round(toks, 1),
                          "step_ms": round(1000 * dt / iters, 1)}), flush=True)
        return toks
    except Exception as e:
        print(json.dumps({"phase": "step", "tag": tag, "bq": bq, "bk": bk,
                          "error": str(e).splitlines()[0][:160]}), flush=True)
        return None
    finally:
        tr = batch = m = None  # noqa: F841
        import gc
        gc.collect()
        jax.clear_caches()


if __name__ == "__main__":
    from orion_tpu.utils.cache import enable_compile_cache

    enable_compile_cache()
    phases = sys.argv[1:] or ["kernel", "step"]
    if "kernel" in phases:
        bench_kernel(512, 512, banded=False)  # the r4 masked-grid control
        for bq, bk in [(512, 512), (512, 256), (512, 128), (256, 256),
                       (256, 128), (128, 128)]:
            bench_kernel(bq, bk, banded=True)
    if "step" in phases:
        dense = bench_step("dense_lm1b3", "lm_1b3")
        best = None
        for bq, bk in [(512, 512), (512, 256), (512, 128), (256, 256)]:
            t = bench_step(f"hybrid_b{bq}x{bk}", "hybrid_1b3", bq, bk)
            if t and (best is None or t > best[0]):
                best = (t, bq, bk)
        if dense and best:
            print(json.dumps({"phase": "ratio",
                              "vs_dense_lm1b3": round(best[0] / dense, 4),
                              "best_blocks": best[1:]}), flush=True)
