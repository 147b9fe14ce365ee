"""Generators reproduce from a seed, keep the same work for every seed, and
latency is taken from the due time."""

import math
import threading

import numpy as np

from traffic import closed_loop, lengths, open_loop

PROMPT = {"dist": "lognormal", "median": 128, "sigma": 0.8, "min": 16, "max": 512, "multiple_of": 16}
OUTPUT = {"dist": "lognormal", "median": 256, "sigma": 0.7, "min": 16, "max": 1024, "multiple_of": 1}


def test_population_has_the_quantiles_it_claims():
    p = lengths.population(PROMPT, 512)
    q = lengths.quantiles(p)
    assert q["min"] >= 16 and q["max"] <= 512 and all(v % 16 == 0 for v in p)
    assert abs(q["p50"] - 128) <= 16
    # lognormal p90 = median * exp(1.2816 sigma)
    assert abs(q["p90"] - 128 * math.exp(1.2816 * 0.8)) <= 16
    assert len(set(p)) <= 32  # few distinct prompt lengths: all are warmed up
    o = lengths.quantiles(lengths.population(OUTPUT, 512))
    assert abs(o["p50"] - 256) <= 4 and o["max"] <= 1024


def test_every_seed_gets_the_same_work_in_another_order():
    p = lengths.population(OUTPUT, 200)
    a, b = lengths.shuffled(p, 1, "output"), lengths.shuffled(p, 2**31 + 7, "output")
    assert a != b and sorted(a) == sorted(b) == sorted(p)
    assert a == lengths.shuffled(p, 1, "output")
    gaps = lengths.population({"dist": "exponential", "mean": 0.2}, 300)
    assert abs(sum(gaps) / len(gaps) - 0.2) < 1e-9 and min(gaps) > 0


class Handle:
    def __init__(self, done_at=None, result=None):
        self.done = threading.Event()
        self.done_at, self.result, self.rid = done_at or 0.0, result, "r"
        if done_at is not None:
            self.done.set()


class Result:
    def __init__(self, n, status="ok"):
        self.status, self.new_tokens = status, n
        self.tokens = np.zeros((1, n), np.int32)


def test_latency_runs_from_the_due_time_and_missing_is_inf():
    import harness

    serve = harness.load_module("kinds", "serve")
    body = {"max_new": 10}
    recs = [
        # due at 1.0, sent late at 1.5, done at 3.0: 200 ms/token, not 150
        {"due": 1.0, "sent": 1.5, "body": body, "handle": Handle(3.0, Result(10))},
        # never finished
        {"due": 2.0, "sent": 2.0, "body": body, "handle": Handle()},
        # finished, but after the drain deadline
        {"due": 2.5, "sent": 2.5, "body": body, "handle": Handle(30.0, Result(10))},
        # wrong number of tokens
        {"due": 3.0, "sent": 3.0, "body": body, "handle": Handle(4.0, Result(9))},
        # due before the window: not judged
        {"due": 0.5, "sent": 0.5, "body": body, "handle": Handle(1.2, Result(10))},
    ]
    j = serve.judge(recs, 1.0, 10.0, 25.0, True, 256)
    assert len(j["mine"]) == 4 and j["failed"] == 3 and j["tokens_ok"] == 10
    assert j["per_token"][0] == 200.0
    assert all(math.isinf(x) for x in j["per_token"][1:])
    assert math.isinf(harness.percentile(j["per_token"], 95))
    # a closed loop judges what ended inside the window
    j = serve.judge(recs, 1.0, 10.0, 10.0, False, 256)
    assert len(j["mine"]) == 3 and j["tokens_ok"] == 20


def test_open_loop_sends_at_the_due_times():
    sent = []
    clock = {"t": 100.0}

    def now():
        return clock["t"]

    real_sleep = open_loop.time.sleep
    open_loop.time.sleep = lambda d: clock.__setitem__("t", clock["t"] + d)
    try:
        recs = open_loop.drive(
            lambda body: sent.append(body) or Handle(), iter(range(10**6)),
            {"rate_per_s": 10.0, "seed": 5}, 101.0, 104.0, clock=now)
    finally:
        open_loop.time.sleep = real_sleep
    assert 30 <= len(recs) <= 40
    assert all(r["sent"] >= r["due"] - 1e-9 for r in recs)
    assert all(b["due"] > a["due"] for a, b in zip(recs, recs[1:]))
    assert recs[-1]["due"] < 104.0


def test_closed_loop_keeps_clients_busy():
    handles = []

    def submit(body):
        h = Handle()
        handles.append(h)
        return h

    import time

    t0 = time.monotonic()

    def finisher():
        while time.monotonic() < t0 + 0.25:
            for h in list(handles):
                h.done_at = time.monotonic()
                h.done.set()
            time.sleep(0.01)

    th = threading.Thread(target=finisher)
    th.start()
    recs = closed_loop.drive(submit, iter(range(10**6)), {"clients": 4}, t0, t0 + 0.3)
    th.join()
    assert len(recs) > 8 and all(r["due"] == r["sent"] for r in recs)
