"""Request sizes: the SAME multiset for every seed, in another order.

A distribution is turned into a fixed population by its quantile grid
(``q_i = (i + 0.5) / n``), so every run of a cell does the same total work
and only the order — which request meets which — changes with ``--seed``.
That keeps seed-to-seed spread down to what the system itself adds.

Spec: ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b,
"multiple_of": k}`` — values are clipped to [a, b] and rounded to a multiple
of k (every distinct prompt length costs the server one small program, so
prompt lengths are kept to a few tens of values and all are warmed up).
``{"dist": "exponential", "mean": m}`` gives arrival gaps, rescaled so their
mean is exactly m.
"""

from __future__ import annotations

import math
import random
import statistics
from typing import Dict, List


def population(spec: dict, n: int) -> List[float]:
    qs = [(i + 0.5) / n for i in range(n)]
    if spec["dist"] == "lognormal":
        normal = statistics.NormalDist()
        k = spec.get("multiple_of", 1)
        out = []
        for q in qs:
            v = spec["median"] * math.exp(spec["sigma"] * normal.inv_cdf(q))
            v = min(max(v, spec["min"]), spec["max"])
            out.append(int(min(max(round(v / k) * k, spec["min"]), spec["max"])))
        return out
    if spec["dist"] == "exponential":
        raw = [-math.log(1.0 - q) for q in qs]
        scale = spec["mean"] * n / sum(raw)
        return [r * scale for r in raw]
    raise ValueError(f"unknown distribution {spec['dist']!r}")


def shuffled(values: List, seed: int, salt: str) -> List:
    out = list(values)
    random.Random(f"{seed}:{salt}").shuffle(out)
    return out


def quantiles(values: List[float]) -> Dict[str, float]:
    s = sorted(values)
    pick = lambda q: s[min(len(s) - 1, int(q * len(s)))]  # noqa: E731
    return {"n": len(s), "min": s[0], "p50": pick(0.5), "p90": pick(0.9),
            "p99": pick(0.99), "max": s[-1], "mean": sum(s) / len(s)}
