#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that orion-tpu still starts on the chip.

Drives the two main paths once, at the full width of ``lm_1b3`` (d_model
2048, 24 layers, 16 heads, vocab 32000, bf16; random seeded weights), through
the entry points a user types, and checks what comes out:

- **train**: ``python -m orion_tpu.train --config lm_1b3 --batch-size 12
  --seq-len 2048 ...`` for a few steps — finite, falling loss, Pallas kernels
  in the compiled step;
- **serve** (``off`` then ``int8``): prompts of different lengths piped to
  ``python -m orion_tpu.serving --config lm_1b3 --slots 8 ...`` — every
  request ok with the asked-for token count, no shed / failed / ladder
  events, no decode program compiled beyond the declared plan;
- **solo**: the same prompts through ``python -m orion_tpu.generate``; the
  per-prompt agreement with the batched server is printed, not gated (bf16
  batch-8 and batch-1 matmuls may round differently on the MXU);
- **invariant** (gated): logits of prefill + recurrent O(1)-state decode
  against one full parallel forward over the same 512 tokens.

``--chips 4`` runs ONLY the multi-chip phase and what it is compared with:
training at dp=1 / dp=4 / fsdp=4 and serving at tp=0 / tp=4.

The parent never imports jax: each phase is a child process calling the
CLI's own ``main(argv)``, one at a time, so exactly one process holds the
chip. Without ``--rehearse`` a CPU is a failure; with it the same phases run
at ``tiny`` on whatever device there is (the CPU rehearsal of this script).

Every line printed is one JSON object; the LAST one is the verdict:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
The per-phase seconds and bytes on earlier lines are observations for the
next issue, not metrics.
"""

from __future__ import annotations

import argparse
import ast
import io
import json
import math
import os
import random
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPORT_TAG = "CHIP_SMOKE_REPORT "
NO_ACCELERATOR_RC = 2
# the whole script, compilation included: the driver allows the one-chip run
# 1200 s; the four-chip run (the builder's) compiles three sharded steps
BUDGET_S = {1: 1150.0, 4: 2400.0}

NEW_TOKENS = 32
SLOTS, CHUNK, PREFILL_CHUNK = 8, 16, 64
SERVE_FLAGS = ["--slots", str(SLOTS), "--chunk", str(CHUNK),
               "--prefill-chunk", str(PREFILL_CHUNK), "--temperature", "0",
               "--max-new-tokens", str(NEW_TOKENS)]
# lm_1b3.train's operating point (benchmark/workloads/lm_1b3.train.json):
# b12 x T2048, adafactor, bf16 stochastic-rounding storage, 6 un-rematted
# blocks
TRAIN_SETS = ["--set", "optimizer=adafactor", "--set",
              "param_storage=bfloat16_sr", "--set", "model.remat_skip=6",
              # few steps: warm up in two, hold a gentle rate (at the
              # CLI's default 3e-4 the first chip run wandered between
              # 10.84 and 11.38; at 2e-5 eight steps fell 10.91 -> 10.82),
              # print every loss
              "--set", "warmup_steps=2", "--set", "schedule=constant",
              "--set", "log_every=1", "--lr", "2e-5"]
TRAIN_STEPS = 12


def make_prompts(seed: int, rehearse: bool) -> list:
    """Seeded ASCII prompts (byte tokenizer: one token per byte). The first
    is the longest, so the engine's staging buffer is sized once; it is
    longer than one in-scan prefill piece (the knob rounds up to the
    linear-attention chunk: 512 tokens on the chip, 128 on the CPU)."""
    rng = random.Random(seed)
    words = ["state", "linear", "orion", "chunk", "decode", "kernel", "prefix",
             "token", "scan", "slot", "carry", "mesh", "shard", "window"]
    lengths = [150, 9, 70, 40, 33] if rehearse else [600, 9, 70, 200, 33]
    out = []
    for n in lengths:
        text = ""
        while len(text) < n:
            text += rng.choice(words) + " "
        out.append(text[:n - 1] + ".")
    return out


# -- children: one phase each, in a process of its own ----------------------


def _emit_report(obj: dict) -> None:
    sys.stdout.flush()
    print(REPORT_TAG + json.dumps(obj), flush=True)


class _Phase:
    """What every child does around its CLI call: refuse the wrong device,
    split the wall clock into compile and the rest (jax's own monitoring
    events: backend compile, persistent-cache hits), and report."""

    def __init__(self, args):
        import jax
        import jax.monitoring as mon

        devs = jax.devices()
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        error = None
        if self.device["platform"] != "tpu" and not args.rehearse:
            error = NO_ACCELERATOR_RC, (
                f"no accelerator: jax found {self.device['platform']}; this "
                "script never accepts the CPU without --rehearse")
        elif self.device["count"] != args.chips:
            error = 1, (f"needs {args.chips} device(s), jax found "
                        f"{self.device['count']}")
        if error:
            _emit_report({"ok": False, "device": self.device,
                          "error": error[1]})
            sys.exit(error[0])
        self.t0 = time.monotonic()
        self.compile_s = 0.0
        self.n_compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.n_compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def report(self, ok: bool, **extra) -> None:
        """``peak_bytes_in_use`` counts arrays, ``peak_bytes_reserved`` the
        scratch loaded programs reserve beside them (12.9 GB of the train
        step's 15.6), where the backend reports them."""
        import jax

        wall = time.monotonic() - self.t0
        stats = [d.memory_stats() or {} for d in jax.devices()]
        _emit_report({
            "ok": ok, "device": self.device, "wall_s": round(wall, 2),
            "compile_s": round(self.compile_s, 2),
            "run_s": round(wall - self.compile_s, 2),
            "programs_compiled": self.n_compiles,
            "persistent_cache_hits": self.cache_hits,
            "peak_bytes_in_use": stats[0].get("peak_bytes_in_use"),
            "peak_bytes_reserved": stats[0].get("peak_bytes_reserved"),
            "peak_bytes_per_device": [
                st.get("peak_bytes_in_use") for st in stats],
            "bytes_limit": stats[0].get("bytes_limit"),
            "cache_dir": str(jax.config.jax_compilation_cache_dir),
            **extra,
        })


def _bytes_per_device(tree) -> dict:
    """Bytes of ``tree``'s arrays resident on each device (shard sizes)."""
    import jax

    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for sh in getattr(leaf, "addressable_shards", ()):
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return {str(k): v for k, v in sorted(out.items())}


def _dumped_programs(pattern: str) -> list:
    """(name, has Pallas kernel) of every program jax lowered for compile
    in this process whose name matches — read back from the IR dump the
    parent asked for (JAX_DUMP_IR_TO), the program as handed to XLA."""
    root = os.environ.get("JAX_DUMP_IR_TO", "")
    out = []
    for fn in sorted(os.listdir(root)) if os.path.isdir(root) else ():
        m = re.match(r"jax_ir\d+_jit_(.+?)_compile\.mlir$", fn)
        if not m or not re.search(pattern, m.group(1)):
            continue
        with open(os.path.join(root, fn), errors="replace") as f:
            out.append((m.group(1), "tpu_custom_call" in f.read()))
    return out


def _visible_token_ids() -> None:
    """The CLIs print ``tokenizer.decode(ids)``, and the byte tokenizer
    drops every id above 255 — nearly all of a 32000-way model's output.
    Print the ids themselves so the parent can count and compare them
    (steering the program from the test, not through a new option)."""
    from orion_tpu.utils.tokenizer import ByteTokenizer

    ByteTokenizer.decode = lambda self, ids: "".join(
        f" {int(i)}" for i in ids)


def child_train(args, spec) -> int:
    phase = _Phase(args)
    import jax

    import orion_tpu.train as T

    held = []
    run = T.train

    def keep_state(*a, **k):  # main() drops the state; the checks need it
        out = run(*a, **k)
        held.append(out[0])
        return out

    T.train = keep_state
    rc = T.main(spec["argv"])
    jax.block_until_ready(held)
    steps = _dumped_programs(r"_train_step")
    phase.report(
        rc == 0,
        kernels_in_step=bool(steps) and all(k for _, k in steps),
        param_bytes_per_device=_bytes_per_device(held[0].params),
        state_bytes_per_device=_bytes_per_device(held[0]),
    )
    return rc


def child_serve(args, spec) -> int:
    phase = _Phase(args)
    import collections

    import orion_tpu.serving.__main__ as M
    from orion_tpu import aot
    from orion_tpu.serving.batching import parse_buckets

    _visible_token_ids()
    held = []
    make_server = M.Server

    def keep_server(*a, **k):
        held.append(make_server(*a, **k))
        return held[-1]

    M.Server = keep_server
    metrics = os.path.join(os.environ["CHIP_SMOKE_TMP"],
                           f"metrics-{os.getpid()}.prom")
    sys.stdin = io.StringIO("".join(p + "\n" for p in spec["prompts"]))
    rc = M.main(spec["argv"] + ["--metrics-path", metrics])
    server = held[0]
    # the serving programs this run compiled, by the engine's own gauges,
    # against the plan `python -m orion_tpu.aot --decode` lists for it
    with open(metrics + ".json") as f:
        gauges = json.load(f)["gauges"]
    compiled = {g["labels"]["cache"]: int(g["value"]) for g in gauges
                if g["name"] == "compile_cache_entries"}
    cfg = server.engine.model.cfg
    plan = aot.decode_plan(
        cfg, lower=False, slots=SLOTS, chunk=CHUNK,
        prefill_chunk=PREFILL_CHUNK,
        prefill_buckets=parse_buckets("pow2", cfg.max_seq_len),
        qmode=spec["qmode"], tp=spec["tp"],
    )
    planned = collections.Counter(p["kind"] for p in plan["programs"])
    phase.report(
        rc == 0,
        decode_programs_compiled=compiled,
        unplanned_programs={k: v for k, v in compiled.items()
                            if v > planned[k]},
        param_bytes_per_device=_bytes_per_device(server.engine.params),
    )
    return rc


def child_solo(args, spec) -> int:
    phase = _Phase(args)
    import jax

    import orion_tpu.generate as G

    _visible_token_ids()
    rc = 0
    for prompt in spec["prompts"]:
        rc = rc or G.main(spec["argv"] + ["--prompt", prompt])
        # one CLI call per process is what a user runs: drop this call's
        # loaded program before the next, or their reserved scratch (a
        # bf16 copy of the weights each, 2.45 GB) piles up until the chip
        # refuses the fourth
        jax.clear_caches()
    phase.report(rc == 0)
    return rc


def child_invariant(args, spec) -> int:
    """The framework's core claim at full width: a prompt prefilled in the
    chunked parallel form and then decoded token by token from the O(1)
    state gives the logits of ONE parallel forward over the same tokens."""
    phase = _Phase(args)
    import jax
    import jax.numpy as jnp

    from orion_tpu.generate import SampleConfig, generate, prefill_carry
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM
    from orion_tpu.utils.cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_config(spec["config"])
    model = TransformerLM(cfg)
    total, n_prompt, batch = 512, 256, 2
    key = jax.random.PRNGKey(spec["seed"])
    params = model.init(key, jnp.zeros((1, 8), jnp.int32))
    prompt = jax.random.randint(
        jax.random.fold_in(key, 1), (batch, n_prompt), 0, cfg.vocab_size)

    # the prompt's state from the whole-prompt prefill (one bucket, the
    # prompt's own length), and the tokens to walk from generate()'s
    # greedy scan over the same prompt: tokens n_prompt .. total-1
    greedy = SampleConfig(temperature=0.0)
    states = prefill_carry(
        model, params, prompt, greedy, key, buckets=(n_prompt,))[1]
    walked = generate(
        model, params, prompt, total - n_prompt, greedy, rng=key)
    seq = jnp.concatenate([prompt, walked], axis=1)
    # each loaded program reserves its scratch on the device (here a bf16
    # copy of the weights, ~2.5 GB): drop the walk's before the next two
    jax.block_until_ready((seq, states))
    jax.clear_caches()

    # one parallel forward over all 512 tokens: the reference
    full = jax.jit(model.apply)(params, seq)  # [B, T, V] fp32

    # the recurrent logits along the same tokens, from the prefill's state
    @jax.jit
    def recurrent(params, states, toks):
        def step(states, xs):
            tok, t = xs
            logits, states = model.apply(
                params, tok, states, t, method=TransformerLM.decode_step)
            return states, logits

        ts = n_prompt + jnp.arange(toks.shape[1])
        _, logits = jax.lax.scan(step, states, (toks.T, ts))
        return jnp.moveaxis(logits, 0, 1)

    # logits at positions n_prompt..total-1
    rec = recurrent(params, states, walked)
    ref = full[:, n_prompt:]
    diff = jnp.abs(rec - ref)
    max_diff = float(jnp.max(diff))
    scale = float(jnp.max(jnp.abs(ref)))
    # Tolerance: both sides do the same arithmetic in cfg.dtype in a
    # different order. Each of the ~2 roundings per block perturbs the
    # residual stream by eps relative; over n_layers blocks they add like a
    # random walk, sqrt(2 * n_layers) * eps, and reach the logits at their
    # own scale; x2 for the max over B*T*V entries: 0.50 for bf16 lm_1b3
    # (measured on the chip: 0.065). A wrong state moves the logits by
    # their own size, ten times that. The floor covers fp32, where the
    # order of accumulation inside the matmuls is all that differs.
    eps = float(jnp.finfo(jnp.dtype(cfg.dtype)).eps)
    tol = max(2.0 * eps * math.sqrt(2 * cfg.n_layers), 2e-5) * scale
    finite = bool(jnp.isfinite(rec).all() & jnp.isfinite(full).all())
    # token level, printed: the walk's greedy tokens against the argmax of
    # the parallel forward (near-ties may flip under bf16)
    ref_tok = jnp.argmax(full[:, n_prompt - 1:-1], axis=-1)
    agree = int(jnp.sum(ref_tok == walked))
    ok = finite and max_diff <= tol and rec.shape == ref.shape
    phase.report(
        ok, tokens=batch * total, logits_shape=list(rec.shape),
        finite=finite, max_abs_diff=max_diff,
        mean_abs_diff=float(jnp.mean(diff)), max_abs_logit=scale,
        tolerance=tol, greedy_tokens_agree=f"{agree}/{walked.size}",
    )
    return 0 if ok else 1


CHILDREN = {"train": child_train, "serve": child_serve, "solo": child_solo,
            "invariant": child_invariant}


# -- parent: no jax here -----------------------------------------------------


class Smoke:
    """The parent's side of a run: one child per phase, one at a time."""

    def __init__(self, args, tmp: str):
        self.args, self.tmp = args, tmp
        self.config = "tiny" if args.rehearse else "lm_1b3"
        self.seq = 256 if args.rehearse else 2048
        self.prompts = make_prompts(args.seed, args.rehearse)
        self.deadline = time.monotonic() + BUDGET_S[args.chips]
        self.recs: list = []

    def child(self, name: str, kind: str, spec: dict) -> dict:
        """Run one phase in a process of its own; return its parsed
        record. ``_stdout``/``_stderr`` (what the CLI itself printed) are
        there for the phase's judge only when the child reported."""
        rec = {"phase": name, "ok": False}
        self.recs.append(rec)
        left = self.deadline - time.monotonic()
        if left <= 5:
            rec["error"] = "time budget spent"
            return rec
        env = dict(os.environ)
        env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
        env["CHIP_SMOKE_TMP"] = self.tmp
        if kind == "train":  # read back by _dumped_programs
            env["JAX_DUMP_IR_TO"] = os.path.join(self.tmp, f"ir-{name}")
        cmd = [sys.executable, os.path.abspath(__file__), "--child", kind,
               "--spec", json.dumps(spec), "--chips", str(self.args.chips)]
        if self.args.rehearse:
            cmd.append("--rehearse")
        t0 = time.monotonic()
        proc = subprocess.Popen(
            cmd, cwd=HERE, env=env, stdin=subprocess.DEVNULL,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            start_new_session=True,
        )
        try:
            out, err = proc.communicate(timeout=left)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            out, err = proc.communicate()
            rec["error"] = f"killed at the time limit ({left:.0f}s)"
        rec["child_wall_s"] = round(time.monotonic() - t0, 2)
        rec["exit_code"] = proc.returncode
        lines = out.splitlines()
        report = next((json.loads(ln[len(REPORT_TAG):]) for ln in lines
                       if ln.startswith(REPORT_TAG)), None)
        if report is None:
            rec.setdefault("error", "child printed no report")
        else:
            rec.update(report)
            rec["ok"] = bool(report["ok"]) and proc.returncode == 0
            if "error" not in rec:
                rec["_stdout"], rec["_stderr"] = lines, err
        if not rec["ok"]:
            rec["stderr_tail"] = err[-3000:]
        return rec

    def train(self, name: str, mesh: list, steps: int,
              must_fall: bool = True) -> dict:
        argv = ["--config", self.config, "--batch-size", "12", "--seq-len",
                str(self.seq), "--steps", str(steps), "--data", "synthetic",
                "--seed", str(self.args.seed)] + mesh + TRAIN_SETS
        rec = self.child(name, "train", {"argv": argv})
        if "_stdout" not in rec:
            return rec
        losses = [float(m.group(1)) for m in (
            re.match(r"step\s+\d+\s+loss (\S+)", ln) for ln in rec["_stdout"]
        ) if m]
        rec["losses"] = losses
        rec["tokens"] = 12 * self.seq * steps
        check(rec, len(losses) == steps, f"{steps} loss lines printed")
        check(rec, all(math.isfinite(x) for x in losses),
              "every loss finite")
        if must_fall:
            check(rec, len(losses) > 1 and losses[-1] < losses[0],
                  "last loss below the first")
        check(rec, "retrying fully rematted" not in rec["_stderr"],
              "the step fits at the asked-for remat_skip (no OOM retry)")
        if rec["device"]["platform"] == "tpu":
            check(rec, rec.get("kernels_in_step") is True,
                  "tpu_custom_call in the compiled train step")
        return rec

    def printed_tokens(self, rec: dict) -> list:
        """Token ids per prompt from a CLI's stdout lines (prompt + ids)."""
        out = []
        for prompt in self.prompts:
            ids = None
            for ln in rec["_stdout"]:
                if ln.startswith(prompt):
                    tail = ln[len(prompt):]
                    if "[" not in tail:  # a status tag: not an ok answer
                        ids = [int(x) for x in tail.split()]
                    break
            out.append(ids)
        rec["_tokens"] = out
        rec["tokens_out"] = [None if t is None else len(t) for t in out]
        check(rec, rec["tokens_out"] == [NEW_TOKENS] * len(out),
              f"every prompt answered with {NEW_TOKENS} tokens")
        return out

    def serve(self, name: str, qmode: str, tp: int) -> dict:
        argv = (["--config", self.config] + SERVE_FLAGS
                + ["--qmode", qmode, "--tp", str(tp)])
        rec = self.child(name, "serve", {
            "argv": argv, "prompts": self.prompts, "qmode": qmode, "tp": tp})
        if "_stdout" not in rec:
            return rec
        self.printed_tokens(rec)
        m = re.search(r"^stats: (\{.*\})$", rec["_stderr"], re.M)
        stats = rec["stats"] = ast.literal_eval(m.group(1)) if m else {}
        check(rec, stats.get("ok") == len(self.prompts),
              "stats: all requests ok")
        for key in ("shed", "rejected", "failed", "deadline", "rewinds",
                    "reprefills", "stalls"):
            check(rec, stats.get(key) == 0, f"stats: zero {key}")
        check(rec, rec.get("unplanned_programs") == {},
              "no decode program compiled beyond the declared plan")
        m = re.search(r"over (\d+) decode \+ (\d+) prefill", rec["_stderr"])
        if m:
            rec["tokens"] = int(m.group(1)) + int(m.group(2))
        return rec

    def no_chip(self) -> bool:
        return self.recs[-1].get("exit_code") == NO_ACCELERATOR_RC

    # -- the two runs ---------------------------------------------------------

    def one_chip(self) -> None:
        show(self.train("train", [], TRAIN_STEPS))
        if self.no_chip():
            return  # fail at once
        off = self.serve("serve_off", "off", 0)
        show(off)
        show(self.serve("serve_int8", "int8", 0))
        solo = self.child("solo", "solo", {
            "argv": ["--config", self.config, "--temperature", "0",
                     "--max-new-tokens", str(NEW_TOKENS)],
            "prompts": self.prompts})
        if "_stdout" in solo:
            self.printed_tokens(solo)
            solo["tokens"] = sum(n or 0 for n in solo["tokens_out"])
            # printed, not gated: see the module docstring
            solo["batched_vs_solo_common_prefix"] = agreement(off, solo)
        show(solo)
        show(self.child("invariant", "invariant",
                        {"config": self.config, "seed": self.args.seed}))

    def four_chips(self) -> None:
        steps = 3  # need not fall: comparing the layouts is the point
        trains = {}
        for name, mesh in (("dp1", ["--dp", "1"]), ("dp4", []),
                           ("fsdp4", ["--dp", "1", "--fsdp", "4"])):
            trains[name] = self.train(f"train_{name}", mesh, steps,
                                      must_fall=False)
            if self.no_chip():
                show(trains[name])
                return
        base = trains["dp1"].get("losses") or []
        one = sum((trains["dp1"].get("param_bytes_per_device") or {})
                  .values())
        for name in ("dp4", "fsdp4"):
            rec = trains[name]
            got = rec.get("losses") or []
            check(rec, len(got) == len(base) == steps, "losses to compare")
            if len(got) == len(base) == steps:
                # step 1 is computed before any update: the same params
                # and batch, so only the reduction order differs (printed
                # to 4 significant digits). Later steps follow bfloat16_sr
                # updates, whose stochastic rounding draws differ with the
                # layout.
                rec["loss_diff_vs_dp1"] = [round(abs(a - b), 4)
                                           for a, b in zip(got, base)]
                check(rec, abs(got[0] - base[0]) <= 2e-3 * abs(base[0]),
                      "first-step loss equals the one-device run's (0.2%)")
                check(rec, all(abs(a - b) <= 2e-2 * abs(b)
                               for a, b in zip(got[1:], base[1:])),
                      "later losses within 2% of the one-device run's")
            sizes = list((rec.get("param_bytes_per_device") or {}).values())
            check(rec, len(sizes) == 4 and min(sizes) > 0,
                  "all four devices hold params")
            if name == "fsdp4" and sizes and one:
                check(rec, max(sizes) <= 0.3 * one,
                      "fsdp: about a quarter of the params on each device")
            if name == "dp4" and sizes and one:
                check(rec,
                      max(sizes) <= 1.01 * one and min(sizes) >= 0.99 * one,
                      "dp: a full replica on each device, none piled on one")
        for rec in trains.values():
            show(rec)
        tp0 = self.serve("serve_tp0", "off", 0)
        tp4 = self.serve("serve_tp4", "off", 4)
        check(tp4, "budget_ok=True" in tp4.get("_stderr", ""),
              "tp mesh reports budget_ok=True")
        sizes = list((tp4.get("param_bytes_per_device") or {}).values())
        check(tp4, len(sizes) == 4 and min(sizes) > 0,
              "all four devices hold a weight shard")
        # printed, not gated: a psum reorders a bf16 sum
        tp4["tp4_vs_tp0_common_prefix"] = agreement(tp0, tp4)
        show(tp0)
        show(tp4)


def check(rec: dict, cond: bool, what: str) -> None:
    if not cond:
        rec["ok"] = False
        rec.setdefault("failed_checks", []).append(what)


def agreement(a: dict, b: dict) -> list:
    """Length of the common token prefix, per prompt."""
    out = []
    for x, y in zip(a.get("_tokens", []), b.get("_tokens", [])):
        pairs = list(zip(x or [], y or []))
        n = next((i for i, (u, v) in enumerate(pairs) if u != v), len(pairs))
        out.append(f"{n}/{NEW_TOKENS}")
    return out


def show(rec: dict) -> None:
    print(json.dumps({k: v for k, v in rec.items()
                      if not k.startswith("_")}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip phase and what it is "
                         "compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="same phases at `tiny` on whatever device there "
                         "is (without it a CPU is a failure)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--child", choices=sorted(CHILDREN), help=argparse.SUPPRESS)
    ap.add_argument("--spec", default="{}", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        return CHILDREN[args.child](args, json.loads(args.spec))

    tmp = tempfile.mkdtemp(prefix="chip-smoke-")
    smoke = Smoke(args, tmp)
    try:
        smoke.four_chips() if args.chips == 4 else smoke.one_chip()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    recs = smoke.recs
    device = next((r["device"] for r in recs if r.get("device")), None)
    ok = bool(recs) and all(r.get("ok") for r in recs)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
