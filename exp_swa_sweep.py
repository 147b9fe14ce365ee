"""Round-4 perf tool (VERDICT r3 #7): block-size sweep of the windowed
flash kernel at the HYBRID FULL-STEP operating point — W=1024 inside
hybrid_1b3's [B=12, H=16, T=2048, dh=128] swa layers — not the microbench
shapes the r3 tuning used. fwd and fwd+bwd, ms per call.

Usage: python exp_swa_sweep.py [batch] [seq] [window]
"""
import json
import sys
import time


def main():
    b = int(sys.argv[1]) if len(sys.argv) > 1 else 12
    t = int(sys.argv[2]) if len(sys.argv) > 2 else 2048
    w = int(sys.argv[3]) if len(sys.argv) > 3 else 1024
    import jax
    import jax.numpy as jnp

    from orion_tpu.ops.pallas.flash_attention import flash_attention

    h, dh = 16, 128
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, h, t, dh), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), q.shape, jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), q.shape, jnp.bfloat16)

    def run(fn, *args):
        jax.block_until_ready(fn(*args))  # compile + warm
        iters = 10
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1000

    for bq, bk in [(512, 512), (256, 512), (512, 256), (256, 256),
                   (1024, 512), (512, 1024), (256, 1024), (1024, 256),
                   (128, 512), (2048, 512)]:
        if bq > t or bk > t:
            continue
        try:
            fwd = jax.jit(
                lambda q, k, v, bq=bq, bk=bk: flash_attention(
                    q, k, v, causal=True, window=w, block_q=bq, block_k=bk
                )
            )
            g = jax.jit(
                jax.grad(
                    lambda q, k, v, bq=bq, bk=bk: flash_attention(
                        q, k, v, causal=True, window=w, block_q=bq, block_k=bk
                    ).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2),
                )
            )
            row = {
                "bq": bq, "bk": bk, "window": w,
                "fwd_ms": round(run(fwd, q, k, v), 3),
                "fwdbwd_ms": round(run(g, q, k, v), 3),
            }
        except Exception as e:
            row = {"bq": bq, "bk": bk, "error": str(e)[:120]}
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
