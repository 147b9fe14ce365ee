"""Closed loop: ``clients`` users, each sends its next request the moment
its last one completes (no think time). One thread drives them all.

``drive(submit, requests, params, t_zero, t_end, clock)``: ``requests`` is an
endless iterator of request bodies, ``submit(body)`` returns a handle with a
``done`` Event. Runs from now until ``t_end`` and returns one record per
request sent: ``{"due", "sent", "body", "handle"}`` (a closed-loop request is
due when its client became free, which is when it is sent)."""

from __future__ import annotations

import time

POLL_S = 0.005


def arrivals_needed(params: dict, span_s: float):
    return None  # as many as complete: the iterator is endless


def drive(submit, requests, params, t_zero, t_end, clock=time.monotonic):
    records, open_ = [], []

    def send():
        now = clock()
        body = next(requests)
        rec = {"due": now, "sent": now, "body": body, "handle": submit(body)}
        records.append(rec)
        open_.append(rec)

    for _ in range(params["clients"]):
        send()
    while clock() < t_end:
        still = [rec for rec in open_ if not rec["handle"].done.is_set()]
        freed = len(open_) - len(still)
        open_[:] = still
        for _ in range(freed):
            send()
        time.sleep(POLL_S)
    return records
