"""Open loop: arrivals at a fixed mean rate, whatever the server does.

NOT a Poisson process: the gaps are the quantile grid of an exponential
distribution with mean ``1 / rate_per_s`` -- the same multiset for every
seed, shuffled by ``--seed``. Each gap has the exponential's distribution,
but the gaps are not independent: the number of arrivals in a window is the
same in every run and the longest burst is bounded, so a tail measured under
this traffic is thinner than under independent arrivals. That is the price of
every seed doing the same work (runs of one cell then differ by what the
system adds, not by the draw). A request is DUE at its arrival time; latency is taken from the
due time, not from when the generator got round to sending it, and the
records keep both so the generator's own lateness can be printed.

``drive(submit, requests, params, t_zero, t_end, clock)``: arrivals run from
now to ``t_end``; ``t_zero`` is where the measured window starts."""

from __future__ import annotations

import time

from traffic import lengths


def arrivals_needed(params: dict, span_s: float) -> int:
    return max(1, int(params["rate_per_s"] * span_s))


def drive(submit, requests, params, t_zero, t_end, clock=time.monotonic):
    start = clock()
    n = arrivals_needed(params, t_end - start)
    gaps = lengths.shuffled(
        lengths.population({"dist": "exponential",
                            "mean": 1.0 / params["rate_per_s"]}, n),
        params["seed"], "gaps")
    records, due = [], start
    for gap in gaps:
        due += gap
        if due >= t_end:
            break
        delay = due - clock()
        if delay > 0:
            time.sleep(delay)
        body = next(requests)
        sent = clock()
        records.append({"due": due, "sent": sent, "body": body,
                        "handle": submit(body)})
    return records
