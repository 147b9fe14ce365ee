"""A serving cell: the program's ``Server`` over its ``SlotEngine``, loaded
by one of the generators under ``benchmark/traffic``.

Each run: weights on the device from ``--seed`` in one jitted ``model.init``
-> start the serve loop -> warm up every prompt length the traffic uses (the
staging buffer at its widest first; a lone request last so the decode-only
program runs too) -> ramp the traffic up for ``ramp_s`` (set-up: a steady
state, not an empty server, is what is measured) -> measure ``--seconds`` ->
let the requests due inside the window drain for at most ``drain_s`` ->
(traced run only) capture a few boundaries -> stop the server -> hold what it
SERVED inside the window to the plain reference.

Evidence handed to the readers (generic names; which metric reads which is
in the metric's own file):

- ``values``: completed_tokens_per_s (output tokens of requests that ended
  ``ok`` inside the window / window), ms_per_token_p50 / _p75 / _p90 / _p95
  ((done - DUE) / output tokens, nearest rank over ALL requests due inside
  the window; a failed, shed or undrained request counts as +inf, and a
  percentile that is +inf is left out), setup_seconds, lateness_ms_p95;
- ``counters``: the server's counters, window end minus window start;
- ``tracer``: the server's Tracer events with a timestamp inside the window,
  ``rids``: the trace ids of the requests due inside the window;
- ``memory``: of samples taken each second of the window, the one holding
  most (live arrays + what loaded programs reserve): one sample depends on
  whether it falls while a step holds the donated slot state;
- ``xplane``: the capture of ``profile_chunks`` boundaries the server's own
  ``arm_profile`` took AFTER the window and the drain, under the same traffic
  replayed (inside the window the profiler's start and stop would be
  measured as a stall of the server).
"""

from __future__ import annotations

import gc
import math
import os
import threading
import time

import harness

# What the server returned is held to the plain reference, teacher-forced on
# the served ids: the reference (fp32, "highest") runs one full forward over
# prompt + answer, and at every position of the answer the served id must be
# the reference's own choice or within SERVED_GAP_TOLERANCE of it:
# ``max(ref logits) - ref logit[served id]``. Greedy serving returns the argmax
# of the system's logits; those differ from the reference's by LOGIT_DELTA at
# most at random weights where logits reach about +-5.2 (bf16 compute, fp32
# accumulation and fp32 (S, z) state: 0.079 to 0.081 on the chip over prefill
# 256 + 32 decode steps, first session of PR 27; 0.065 between the system's
# own two bf16 paths, PR 24), so an honest answer's gap is below twice that.
# Read on the chip: 0.047 to 0.059 over 2,863 to 4,465 positions a run, with
# 97.9 to 99.2% of served ids the reference's own choice (four runs, second
# session of PR 27). The tolerance is 2.5 x LOGIT_DELTA, 3.4 times the largest
# reading. 8-bit weights or activations move logits by several tenths and the
# gap with them; a slot's state carried wrongly, a skipped update, a shifted
# position or another slot's state gives ids whose reference logit is some 4
# below the maximum.
LOGIT_DELTA = 0.08
SERVED_GAP_TOLERANCE = 2.5 * LOGIT_DELTA
CHECK_REQUESTS = 8


class EndOfRun(Exception):
    """Raised once from the serve loop's stop poll when the run is over: the
    loop then leaves through its own failure path (evict every slot, reject
    the queue) instead of decoding the backlog to its end."""


class StopGuard:
    """What ``Server.serve(guard=...)`` polls at every boundary."""

    signum = 0

    def __init__(self):
        self.abort = False
        self._raised = False

    @property
    def should_stop(self) -> bool:
        if self.abort and not self._raised:
            self._raised = True
            raise EndOfRun
        return self.abort


class Shed:
    """The handle of a request the server refused at submission."""

    result = None
    done_at = 0.0
    rid = ""

    def __init__(self):
        self.done = threading.Event()
        self.done.set()


def check_served(params, cfg, served, length: int) -> dict:
    """``served``: (prompt ids, returned ids) of requests the server answered
    ``ok`` inside the window, under the window's load, through whatever
    programs and slot state it used. Each is padded to ``length`` (causal:
    what follows the answer changes nothing before it), so one reference
    program serves them all."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from reference import plain_lm

    spec = harness.reference_spec(cfg)

    @jax.jit
    def gaps(p, toks):
        ref = plain_lm.forward(spec, p, toks)[0, :-1]  # row j predicts token j + 1
        top = ref.max(-1)
        return top - jnp.take_along_axis(ref, toks[0, 1:, None], axis=-1)[:, 0], top

    worst, agree, positions, top_logit = 0.0, 0, 0, 0.0
    with jax.default_matmul_precision("highest"):
        for prompt, answer in served:
            toks = np.zeros((1, length), np.int32)
            n, m = len(prompt), len(answer)
            toks[0, :n], toks[0, n:n + m] = prompt, answer
            gap, top = (np.asarray(x)[n - 1:n + m - 1] for x in gaps(params, toks))
            worst = max(worst, float(gap.max()))
            top_logit = max(top_logit, float(top.max()))
            agree += int((gap == 0).sum())
            positions += m
    ok = bool(served) and math.isfinite(worst) and worst <= SERVED_GAP_TOLERANCE
    return {"requests": len(served), "positions": positions, "max_gap": worst,
            "reference_choice_share": agree / max(positions, 1),
            "max_reference_logit": top_logit,
            "tolerance": SERVED_GAP_TOLERANCE, "ok": ok}


def capture_profile(server, generator, submit, requests, traffic, sv, profile_dir):
    """After the window: the same traffic again for ``profile_lead_s``, then
    the server's own ``arm_profile`` for ``profile_chunks`` boundaries. The
    profiler's start and stop hold the serve loop for seconds, so a capture
    inside the window would be measured as a stall of the server."""
    from readers import xplane as xp

    t = time.monotonic()
    feeder = threading.Thread(
        target=generator.drive, name="profile-traffic",
        args=(submit, requests, traffic, t, t + sv["profile_lead_s"] + sv["profile_hold_s"]))
    feeder.start()
    time.sleep(sv["profile_lead_s"])
    harness.note(profile=server.arm_profile(sv["profile_chunks"]))
    deadline = time.monotonic() + 120
    size = -1
    while time.monotonic() < deadline:  # until the capture is written out
        path = xp.newest(profile_dir)
        now = os.path.getsize(path) if path else -1
        if path and now == size:
            break
        size = now
        time.sleep(0.5)
    feeder.join()
    harness.note(profile_wait_s=time.monotonic() - t - sv["profile_lead_s"])


def request_stream(traffic: dict, seed: int, vocab: int, n):
    """An endless stream of request bodies: prompt and output lengths are
    fixed populations shuffled by the seed (benchmark/traffic/lengths.py)."""
    import numpy as np

    from traffic import lengths

    n = n or traffic["population"]
    plens = lengths.shuffled(lengths.population(traffic["prompt_len"], n), seed, "prompt")
    olens = lengths.shuffled(lengths.population(traffic["output_len"], n), seed, "output")
    rng = np.random.Generator(np.random.Philox(key=[seed, 1]))
    harness.note(prompt_len=lengths.quantiles(plens), output_len=lengths.quantiles(olens))
    i = 0
    while True:
        yield {"prompt": rng.integers(0, vocab, size=plens[i % n], dtype=np.int32),
               "max_new": int(olens[i % n]), "index": i}
        i += 1


def judge(records, t_window, t_end, deadline, by_due, vocab) -> dict:
    """Score the requests of one run. ``by_due``: the requests DUE inside
    the window are judged (an open loop: each must finish by ``deadline`` or
    it has failed); otherwise those that ENDED inside it (a closed loop).
    Latency runs from the due time. A request without an ``ok`` result of
    exactly the asked-for number of in-vocabulary ids is a failure and its
    latency is +inf; only tokens of requests that ended ``ok`` inside the
    window count as completed."""
    import numpy as np

    if by_due:
        mine = [r for r in records if t_window <= r["due"] < t_end]
    else:
        mine = [r for r in records
                if r["handle"].done.is_set() and t_window <= r["handle"].done_at < t_end]
    per_token, tokens_ok, failed, bad_ids = [], 0, 0, 0
    for r in mine:
        h, want = r["handle"], r["body"]["max_new"]
        ok = bool(h.done.is_set() and h.result is not None and h.done_at <= deadline
                  and h.result.status == "ok" and h.result.new_tokens == want)
        if ok:
            ids = np.asarray(h.result.tokens)
            if ids.shape[-1] != want or ids.min() < 0 or ids.max() >= vocab:
                bad_ids += 1
                ok = False
        if ok and t_window <= h.done_at < t_end:
            tokens_ok += want
        failed += not ok
        per_token.append(1000.0 * (h.done_at - r["due"]) / want if ok else math.inf)
    return {"mine": mine, "per_token": per_token, "tokens_ok": tokens_ok,
            "failed": failed, "bad_ids": bad_ids}


def run(run: harness.Run) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from orion_tpu.generate import SampleConfig
    from orion_tpu.models.transformer import TransformerLM
    from orion_tpu.obs.trace import Tracer
    from orion_tpu.serving import DecodeRequest, ServeConfig, Server
    from orion_tpu.serving.server import OverloadError
    from traffic import lengths

    sv = run.sized(run.workload["server"])
    traffic = run.sized(run.workload["traffic"])
    traffic["seed"] = run.seed
    generator = harness.load_module("traffic", traffic["generator"])
    compiles = harness.CompileCounter(run.t0)
    compiles.phase("start")

    cfg = harness.model_config(run, max_seq_len=sv["max_seq_len"], backend=sv["backend"])
    model = TransformerLM(cfg)
    params = jax.jit(model.init)(jax.random.key(run.seed), jnp.zeros((1, 16), jnp.int32))
    jax.block_until_ready(params)
    compiles.phase("init")

    profile_dir = run.scratch_dir("profile") if run.trace else None
    tracer = Tracer(path=None, clock=time.monotonic, capacity=1 << 20)
    server = Server(
        model, params,
        ServeConfig(chunk=sv["chunk"], slots=sv["slots"],
                    max_inflight=sv["max_inflight"],
                    prefill_chunk=sv["prefill_chunk"], qmode=sv["qmode"],
                    prefill_buckets=sv["prefill_buckets"], cost=False,
                    profile_dir=profile_dir),
        tracer=tracer,
    )
    sample = SampleConfig(temperature=0.0)
    guard = StopGuard()
    loop_error = []

    def serve_loop():
        try:
            server.serve(guard=guard)
        except EndOfRun:
            pass
        except BaseException as e:  # reported by the conductor below
            loop_error.append(e)

    def submit(body):
        try:
            return server.submit(DecodeRequest(
                prompt=body["prompt"], max_new_tokens=body["max_new"],
                sample=sample, seed=body["index"]))
        except OverloadError:
            return Shed()

    loop = threading.Thread(target=serve_loop, name="serve-loop")
    loop.start()
    try:
        # -- warm-up: every prompt length of the traffic, widest first -----
        span_s = sv["ramp_s"] + run.seconds
        n_arrivals = generator.arrivals_needed(traffic, span_s)
        n_pop = n_arrivals or traffic["population"]
        rng = np.random.Generator(np.random.Philox(key=[run.seed, 2]))
        warm = []
        for k, plen in enumerate(sorted(set(lengths.population(traffic["prompt_len"], n_pop)),
                                        reverse=True)):
            warm.append(submit({"prompt": rng.integers(0, cfg.vocab_size, size=plen, dtype=np.int32),
                                "max_new": 2 * sv["chunk"] + 1, "index": 10**6 + k}))
            if k == 0:
                warm[0].done.wait(timeout=900)  # the staging buffer is sized once
        for h in warm:
            h.done.wait(timeout=900)
        lone = submit({"prompt": rng.integers(0, cfg.vocab_size, size=16, dtype=np.int32),
                       "max_new": 3 * sv["chunk"], "index": 10**6 - 1})
        lone.done.wait(timeout=900)
        warm.append(lone)
        warm_ok = all(h.result is not None and h.result.status == "ok" for h in warm)
        if loop_error:
            raise loop_error[0]
        compiles.phase("warmup")

        # -- ramp (set-up) + measured window, one generator call -----------
        requests = request_stream(traffic, run.seed, cfg.vocab_size, n_arrivals)
        t_window = time.monotonic() + sv["ramp_s"]
        t_end = t_window + run.seconds
        marks = {}

        def at_window_start():
            time.sleep(max(0.0, t_window - time.monotonic()))
            compiles.mark()
            marks["counters"] = server.metrics.counters_flat()
            marks["setup_seconds"] = time.monotonic() - run.t0
            marks["memory"] = {}
            while time.monotonic() < t_end - 1.0:  # a sample each second
                time.sleep(1.0)
                marks["memory"] = max(marks["memory"], harness.memory_stats(),
                                      key=harness.footprint_bytes)

        marker = threading.Thread(target=at_window_start, name="window-mark")
        marker.start()
        records = generator.drive(submit, requests, traffic, t_window, t_end)
        marker.join()
        counters_end = server.metrics.counters_flat()
        in_window = compiles.since_mark()
        memory = max(marks["memory"], harness.memory_stats(), key=harness.footprint_bytes)

        # -- drain what was due inside the window --------------------------
        by_due = traffic["count_by"] == "due"
        deadline = t_end + sv["drain_s"]
        for r in (records if by_due else []):
            if not t_window <= r["due"] < t_end:
                continue
            r["handle"].done.wait(timeout=max(0.0, deadline - time.monotonic()))
        t_drained = time.monotonic()
        if run.trace:
            capture_profile(server, generator, submit, requests, traffic, sv, profile_dir)
    finally:
        guard.abort = True
        loop.join(timeout=60)
    if loop_error:
        raise loop_error[0]
    server.close()
    del server
    gc.collect()
    jax.clear_caches()  # unload the serving programs and their reserved scratch
    harness.note(memory_before_check=harness.memory_stats())

    t_checked = time.monotonic()
    judged = judge(records, t_window, t_end, deadline, by_due, cfg.vocab_size)
    mine, per_token = judged["mine"], judged["per_token"]
    tokens_ok, failed, bad_ids = judged["tokens_ok"], judged["failed"], judged["bad_ids"]
    in_system = [sum(1 for r in records
                     if r["due"] <= t and not (r["handle"].done.is_set() and r["handle"].done_at <= t))
                 for t in (t_window + f * run.seconds for f in (0.0, 0.25, 0.5, 0.75, 1.0))]
    lateness = [1000.0 * (r["sent"] - r["due"]) for r in records]
    counters = {k: counters_end.get(k, 0) - marks["counters"].get(k, 0)
                for k in counters_end if isinstance(counters_end[k], (int, float))}
    values = {
        "completed_tokens_per_s": tokens_ok / run.seconds,
        "setup_seconds": marks["setup_seconds"],
        "lateness_ms_p95": harness.percentile(lateness, 95),
    }
    for q in (50, 75, 90, 95):  # of ALL judged requests: a failure is +inf
        v = harness.percentile(per_token, q)
        if v is not None and math.isfinite(v):
            values[f"ms_per_token_p{q}"] = v
    # what was served under the window's load, against the plain reference
    ok_records = [r for r, x in zip(mine, per_token) if math.isfinite(x)]
    n_check = min(len(ok_records), CHECK_REQUESTS)
    served = [(r["body"]["prompt"], np.asarray(r["handle"].result.tokens).reshape(-1))
              for r in (ok_records[i * len(ok_records) // n_check] for i in range(n_check))]
    check = check_served(params, cfg, served,
                         traffic["prompt_len"]["max"] + traffic["output_len"]["max"])
    harness.note(check=check, check_s=time.monotonic() - t_checked)
    t0_us, t1_us = t_window * 1e6, t_end * 1e6
    events = [e for e in tracer.events() if t0_us <= e["ts"] < t1_us]
    xplane = None
    if run.trace:
        from readers import xplane as xp

        xplane = xp.load_newest(profile_dir)
    correct = (check["ok"] and warm_ok and bad_ids == 0 and bool(mine)
               and failed <= 0.01 * len(mine) and in_window["programs"] == 0)
    harness.note(
        requests_sent=len(records), judged=len(mine), failed=failed,
        bad_ids=bad_ids, warm_ok=warm_ok, tokens_ok=tokens_ok,
        drain_s=t_drained - t_end, in_system_at_quarters=in_system,
        values=values, counters=counters,
        compiled_in_window=in_window, compiled_total=compiles.compiles,
        cache_hits=compiles.hits, compile_s=compiles.compile_s,
        phases=compiles.phases,
        tracer_events=len(events), tracer_dropped=tracer.dropped,
        memory_stats=memory,
    )
    return {
        "correct": correct, "attempted": len(mine), "failed": failed,
        "values": values,
        "counters": counters, "tracer": events,
        "rids": {r["handle"].rid for r in mine}, "window_s": run.seconds,
        "memory": memory, "xplane": xplane, "annotations": [],
        "device_kind": run.device["kind"], "rehearse": run.rehearse,
    }
