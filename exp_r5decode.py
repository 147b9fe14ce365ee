"""Round-5 decode-vs-context probe (R5DECODE.jsonl).

The O(1)-state serving claim, measured directly on the chip: decode-ONLY
per-token latency as the prefill grows 512 -> 16,384. The difference
method — p50 of generate(72) minus p50 of generate(8) over the SAME
prompt, divided by 64 — cancels both the prefill cost and the fixed
dispatch overhead, isolating the steady-state decode-scan step. A
KV-cache transformer slows linearly in context here; the linear state
([H,Dk,Dv]) and the fixed swa ring make the two columns identical by
construction, and this records that the implementation delivers it.

Emits one JSON row per (config, prompt_len); the committed artifact is
R5DECODE.jsonl (2026-08-02). Reuses bench.py's _decode_model (constant
weights — values don't affect decode latency).
"""
import json
import time

import numpy as np


def decode_only(config: str, prompt_len: int, quant: str = "") -> dict:
    import jax.numpy as jnp

    from bench import _decode_model
    from orion_tpu.generate import SampleConfig, generate

    model, params = _decode_model(config, prompt_len, 80, quant)
    sample = SampleConfig(temperature=0.0)
    prompt = jnp.ones((1, prompt_len), jnp.int32)

    def t(n):
        np.asarray(generate(model, params, prompt, n, sample))  # compile
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            np.asarray(generate(model, params, prompt, n, sample))
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[2]

    t8, t72 = t(8), t(72)
    row = {
        "config": config,
        "quant": quant or "fp32",
        "prompt_len": prompt_len,
        "decode_only_ms_per_tok": round((t72 - t8) / 64 * 1000, 3),
        "prefill_plus_8_s": round(t8, 3),
    }
    print(json.dumps(row), flush=True)
    return row


if __name__ == "__main__":
    from orion_tpu.utils.cache import enable_compile_cache

    enable_compile_cache()
    for cfg in ("lm_1b3", "hybrid_1b3"):
        for p in (512, 16384):
            decode_only(cfg, p)
