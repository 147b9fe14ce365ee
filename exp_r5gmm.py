"""Round-5 gmm backward sweep (VERDICT r4 #4: dropless/capacity was 93.1%
vs a >=95% target; the r4 diagnosis blamed backward scatter/gather
transposes + dw traffic). The dw kernel re-reads x nh times and dy nd
times, so its HBM bill scales with nd*nh — this sweeps the dw output-tile
size at the flagship dropless shapes (m=24576 padded rows, d=2048,
h=5504, E=4) and times the FULL gmm fwd+bwd. Emits JSON lines on
stdout.
"""
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp


def bench(bd, bh, iters=20):
    import orion_tpu.ops.pallas.gmm as G

    G._DW_BLOCK_D, G._DW_BLOCK_H = bd, bh
    m, d, h, e, tm = 24576 + 4 * 128, 2048, 5504, 4, 128
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (m, d), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (e, d, h), jnp.float32)
    counts = jnp.full((e,), m // e, jnp.int32)
    seg, _ = G.pad_group_sizes(counts, tm)

    @jax.jit
    def fwd_bwd(x, w):
        def f(x, w):
            return (G.gmm(x, w, seg, tm, 512, False) ** 2).sum()
        l, (dx, dw) = jax.value_and_grad(f, argnums=(0, 1))(x, w)
        return l, dx, dw

    try:
        l, dx, dw = fwd_bwd(x, w)
        float(l)
        t0 = time.perf_counter()
        for _ in range(iters):
            l, dx, dw = fwd_bwd(x, w)
        float(l)
        dt = (time.perf_counter() - t0) / iters * 1000
        print(json.dumps({"dw_block": [bd, bh], "fwd_bwd_ms": round(dt, 2)}),
              flush=True)
    except Exception as ex:
        print(json.dumps({"dw_block": [bd, bh],
                          "error": str(ex).splitlines()[0][:160]}), flush=True)
    jax.clear_caches()


if __name__ == "__main__":
    from orion_tpu.utils.cache import enable_compile_cache

    enable_compile_cache()
    for bd, bh in [(512, 512), (1024, 512), (1024, 1024), (2048, 1024),
                   (1024, 2048), (2048, 688)]:
        bench(bd, bh)
