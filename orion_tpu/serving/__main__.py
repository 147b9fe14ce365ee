"""``python -m orion_tpu.serving`` — resilient batch serving CLI.

Reads prompts (one per line, ``--prompts-file`` or stdin), submits them
through the bounded admission queue, and drains in waves: when the queue
fills, the loop serves until idle and resumes submitting — so a prompt
file larger than ``--max-inflight`` still completes while overload
shedding stays observable (``--no-wave`` sheds instead). SIGTERM at any
point drains gracefully: in-flight requests finish, the rest are
rejected, exit code 0.
"""

from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp

from orion_tpu.generate import (
    SampleConfig,
    adapt_config_to_params,
    load_params,
    unstack_if_pipeline,
)
from orion_tpu.models.configs import get_config
from orion_tpu.models.transformer import TransformerLM
from orion_tpu.obs.trace import PROCESS_TRACER, setup_summary
from orion_tpu.resilience.preempt import PreemptionGuard
from orion_tpu.resilience.retry import RetryPolicy
from orion_tpu.serving.health import Health
from orion_tpu.serving.server import (
    OverloadError,
    RejectedError,
    ServeConfig,
    Server,
    load_tokenizer,
)
from orion_tpu.serving.session import DecodeRequest


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("orion_tpu.serving")
    p.add_argument("--config", default="tiny")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--prompts-file", default="-",
                   help="one prompt per line; '-' = stdin")
    p.add_argument("--max-new-tokens", type=int, default=64)
    p.add_argument("--chunk", type=int, default=16,
                   help="decode chunk length: the deadline / snapshot / "
                        "drain / admission granularity")
    p.add_argument("--slots", type=int, default=8,
                   help="concurrent decode slots sharing one batched scan "
                        "(continuous batching); 1 = one request at a time")
    p.add_argument("--prefill-buckets", default="pow2",
                   help="prompt-length buckets, the widths a staged prompt "
                        "is padded to: 'pow2' (default) or a comma list "
                        "like '32,64,128'")
    p.add_argument("--prefill-chunk", type=int, default=64,
                   help="in-scan chunked prefill: prompt tokens ONE slot "
                        "consumes per chunk boundary INSIDE the batched "
                        "program (up to slots // chunk slots a boundary), "
                        "so a long prompt never stalls co-resident "
                        "decoders (admission is an O(1) slot insert); "
                        "must be > 0")
    p.add_argument("--prompt-overflow", choices=["error", "clamp"],
                   default="error",
                   help="prompts longer than the largest prefill bucket: "
                        "refuse the request cleanly (error, default) or "
                        "serve the newest bucket-sized context (clamp)")
    p.add_argument("--deadline-ms", type=float, default=0.0,
                   help="per-request deadline, enforced at chunk "
                        "boundaries (0 = none)")
    p.add_argument("--max-inflight", type=int, default=8,
                   help="admission bound; a full queue sheds "
                        "(OverloadError) instead of queueing unboundedly")
    p.add_argument("--stall-timeout", type=float, default=0.0,
                   help="watchdog heartbeat budget per decode chunk "
                        "(0 = off); must exceed compile + one chunk")
    p.add_argument("--spec-depth", type=int, default=0,
                   help="self-speculative decode: the model's own "
                        "global-linear layers draft up to this many "
                        "tokens per slot and the full hybrid verifies "
                        "them in ONE batched piece — output stays "
                        "BITWISE identical to plain decode (greedy and "
                        "sampled), only the speed changes; 0 = off "
                        "(dense configs with >= 1 linear layer; "
                        "spec-depth + 1 <= window on swa configs)")
    p.add_argument("--spec-min-accept", type=float, default=0.2,
                   help="adaptive speculation floor: a slot whose "
                        "rolling draft-acceptance EWMA drops below this "
                        "falls back to plain decode for the rest of its "
                        "residency instead of paying a losing draft "
                        "(0 = never fall back)")
    p.add_argument("--tp", type=int, default=0,
                   help="tensor-parallel decode over a tp-device mesh "
                        "(ISSUE 14): weights shard by the training rules "
                        "(two all-reduces per block per step), the O(1) "
                        "state shards on heads, tokens stay bitwise the "
                        "unsharded server's. 0/1 = unsharded. The process "
                        "must expose >= tp devices (on CPU: XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N)")
    p.add_argument("--qmode", choices=["off", "int8", "int4"],
                   default="off",
                   help="weight-streamed quantized serving: the loaded "
                        "params are quantized ONCE at startup (int8 "
                        "quarters each decode step's weight bytes, int4 "
                        "halves them again; per-out-channel scales, "
                        "orion_tpu/quant.py) and every bitwise serving "
                        "contract holds per mode")
    p.add_argument("--prefix-dir", default=None,
                   help="content-addressed prefix cache root: a shared "
                        "prompt prefix (system prompt) is one O(1) "
                        "decode-state snapshot — a hit admits at "
                        "O(suffix) instead of O(prompt); replicas "
                        "sharing the directory share the cache")
    p.add_argument("--prefix-len", type=int, default=0,
                   help="declare the first N tokens of every prompt as a "
                        "shared cacheable prefix: a miss publishes its "
                        "(chunk-aligned) snapshot to --prefix-dir so "
                        "later requests hit (lookups need no "
                        "declaration; 0 = never publish)")
    p.add_argument("--session-dir", default=None,
                   help="durable-session store root: conversations "
                        "suspend to one O(1) state snapshot at turn end "
                        "(and on SIGTERM drain) and resume "
                        "bitwise-identical across restarts")
    p.add_argument("--session-id", default=None,
                   help="tag prompts as turns of this conversation (line "
                        "i gets '<id>-<i>' when several prompts are "
                        "given); with an EMPTY prompt line (or no input "
                        "at all) the turn resumes the saved session O(1) "
                        "and just continues generating")
    p.add_argument("--session-idle-s", type=float, default=300.0,
                   help="resident session-cache idle eviction at chunk "
                        "boundaries (state stays on disk; 0 = off)")
    p.add_argument("--max-dirty-sessions", type=int, default=32,
                   help="write-behind bound during a session-store "
                        "outage: beyond this many DIRTY resident "
                        "sessions (save failed; host copy is the only "
                        "up-to-date one) NEW session admissions shed "
                        "with a retriable overload error while dirty "
                        "sessions keep serving (0 = unbounded)")
    p.add_argument("--breaker-failures", type=int, default=3,
                   help="consecutive failed store operations that OPEN "
                        "a store's circuit breaker: every touch then "
                        "fails in O(1) host work (no syscalls against "
                        "dead storage), health reports DEGRADED "
                        "'store-outage:<store>', and requests keep "
                        "serving (prefix = cold prefill, sessions = "
                        "write-behind)")
    p.add_argument("--breaker-backoff", type=float, default=0.5,
                   help="open-breaker dwell (seconds) before the first "
                        "half-open probe; doubles per re-trip up to "
                        "--breaker-max-backoff, jittered so a fleet's "
                        "probes don't synchronize")
    p.add_argument("--breaker-max-backoff", type=float, default=30.0,
                   help="probe backoff ceiling (seconds)")
    p.add_argument("--grace", type=float, default=30.0,
                   help="SIGTERM drain budget (seconds)")
    p.add_argument("--metrics-path", default=None,
                   help="Prometheus-text metrics exposition file (+ a "
                        ".json sibling), rewritten atomically every "
                        "--metrics-interval-s at chunk boundaries and "
                        "always on drain")
    p.add_argument("--metrics-interval-s", type=float, default=10.0,
                   help="periodic metrics dump cadence (<= 0: on drain "
                        "only)")
    p.add_argument("--metrics-port", type=int, default=-1,
                   help="serve LIVE /metrics (Prometheus text), /healthz "
                        "(status code tracks the health state), /statusz "
                        "(human debug page) and /slo (burn rates + error "
                        "budgets) on this port from a daemon thread "
                        "(0 = ephemeral, reported on stderr; -1 = off). "
                        "Scrapes read host snapshots only — zero device "
                        "syncs, zero compiles.")
    p.add_argument("--slo-latency-ms", type=float, default=0.0,
                   help="declare a per-turn latency SLO: 99%% of turns "
                        "under this many ms (plus error-rate and "
                        "availability objectives at --slo-target). "
                        "Arms ACTUATION: sustained fast burn degrades "
                        "health and sheds admissions earlier. 0 = "
                        "observe-only defaults")
    p.add_argument("--slo-target", type=float, default=0.99,
                   help="good-event fraction each declared objective "
                        "promises (error budget = 1 - target)")
    p.add_argument("--trace-path", default=None,
                   help="request-trace JSONL (Chrome trace events): one "
                        "span per request lifecycle, chunk spans at "
                        "boundary granularity; merge with `python -m "
                        "orion_tpu.obs.trace merge` and load in Perfetto")
    p.add_argument("--flight-dir", default=None,
                   help="flight-recorder dump directory: the black box "
                        "auto-dumps here on DEGRADED/DRAINING/DEAD, "
                        "ladder exhaustion, and SIGTERM drain")
    p.add_argument("--no-cost", action="store_true",
                   help="disable per-request cost attribution + the "
                        "capacity model (on by default: every result "
                        "carries its device_ms/flops share, /costz and "
                        "/statusz report the live tokens/s ceiling and "
                        "headroom — all host arithmetic at chunk "
                        "boundaries, zero device syncs)")
    p.add_argument("--no-cost-ledger", action="store_true",
                   help="skip the construction-time XLA cost_analysis "
                        "harvest (one lower-only pass per program, "
                        "memoized); attribution then weighs by token "
                        "counts and flops fall back to an analytic "
                        "2 x params estimate")
    p.add_argument("--profile-dir", default=None,
                   help="arm-able on-demand jax.profiler capture: GET "
                        "/profilez?chunks=K (or Server.arm_profile) "
                        "records the next K chunk boundaries into one "
                        "TensorBoard-loadable artifact under this "
                        "directory — off by default, flight-recorded "
                        "when triggered")
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=1.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tokenizer", default=None,
                   help="BPE tokenizer JSON; default byte-level")
    p.add_argument("--eos", action="store_true",
                   help="stop sequences at the tokenizer's <eos>")
    p.add_argument("--ckpt-attempts", type=int, default=4)
    p.add_argument("--no-wave", action="store_true",
                   help="don't drain-and-resume on overload: shed excess "
                        "prompts (reported on stderr)")
    p.add_argument(
        "--set", action="append", default=[], metavar="KEY=VALUE",
        help="ModelConfig override (must match the checkpoint)",
    )
    return p


def main(argv=None) -> int:
    from orion_tpu.utils.cache import enable_compile_cache

    enable_compile_cache()
    args = build_argparser().parse_args(argv)
    if args.tp and args.tp > 1:
        # a CPU host needs tp virtual devices; nothing above touched a
        # device, so the flag still takes (real TPU hosts expose chips)
        from orion_tpu.utils.devices import ensure_virtual_devices

        ensure_virtual_devices(args.tp)
    # ONE guard spans the whole lifecycle — startup, submission, every
    # serve wave — so SIGTERM during model load or between waves maps to
    # a graceful drain (exit 0) too, not just mid-decode; Server.serve
    # polls this guard instead of installing its own
    with PreemptionGuard(grace=args.grace) as guard:
        return _run(args, guard)


def _run(args, guard) -> int:
    retry = RetryPolicy(attempts=max(args.ckpt_attempts, 1))

    cfg = get_config(args.config)
    if args.set:
        from orion_tpu.utils.config import apply_overrides, parse_set_overrides

        cfg = apply_overrides(cfg, parse_set_overrides(args.set))
    tok = load_tokenizer(args.tokenizer, retry=retry)
    eos_token = -1
    if args.tokenizer and args.eos:
        eos_token = tok.eos

    # prefix/session addressing must pin the WEIGHTS' provenance, not
    # just the config name: the checkpoint step a default-latest load
    # resolves to and the --set overrides are part of what the weights
    # ARE — two checkpoints (or two override sets) sharing a prefix_dir
    # must never resolve to each other's states. The fingerprint is the
    # SHARED definition (prefix_store.overrides_fingerprint) over the
    # PARSED overrides, so this CLI and a fleet replica built from the
    # same config + --set derive the same identity and share entries.
    from orion_tpu.serving.prefix_store import overrides_fingerprint
    from orion_tpu.utils.config import parse_set_overrides as _parse_ov

    ov = overrides_fingerprint(_parse_ov(args.set) if args.set else {})
    with PROCESS_TRACER.span("setup.weights", "setup",
                             source="checkpoint" if args.ckpt_dir else "init"):
        if args.ckpt_dir:
            params, step = load_params(args.ckpt_dir, retry=retry)
            cfg = adapt_config_to_params(cfg, params)
            print(f"serving step {step} from {args.ckpt_dir}", file=sys.stderr)
            model = TransformerLM(cfg)
            params, _ = unstack_if_pipeline(model, params)
            params_id = (
                f"{args.config}:ov={ov}:ckpt={args.ckpt_dir}:step={step}"
            )
        else:
            model = TransformerLM(cfg)
            params = model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
            )
            print("no --ckpt-dir: random params (smoke test)", file=sys.stderr)
            params_id = f"{args.config}:ov={ov}:seed=0"
    if args.tokenizer:
        # after cfg adaptation: out-of-vocab ids would be silently clamped
        # by the embedding gather — garbage served with status 'ok'
        assert tok.vocab_size <= cfg.vocab_size, (
            f"tokenizer vocab {tok.vocab_size} > model vocab {cfg.vocab_size}"
        )

    if args.prompts_file == "-":
        lines = [ln.rstrip("\n") for ln in sys.stdin]
    else:
        with open(args.prompts_file) as f:
            lines = [ln.rstrip("\n") for ln in f]
    if args.session_id:
        # empty lines are CONTINUATION turns (resume the saved session,
        # no new tokens); without any input, synthesize one continuation
        lines = lines or [""]
    else:
        lines = [ln for ln in lines if ln]
    if args.session_id and not args.session_dir:
        print("--session-id requires --session-dir", file=sys.stderr)
        return 2

    sample = SampleConfig(
        args.temperature, args.top_k, args.top_p, eos_token=eos_token
    )
    slo_cfg = None
    if args.slo_latency_ms > 0:
        # declared objectives arm actuation (sustained fast burn ->
        # DEGRADED + earlier shedding); without the flag the server still
        # evaluates the observe-only defaults
        slo_cfg = (
            {"name": "turn_latency", "kind": "latency",
             "latency_ms": args.slo_latency_ms, "target": args.slo_target},
            {"name": "error_rate", "kind": "error_rate",
             "target": args.slo_target},
            {"name": "availability", "kind": "availability",
             "target": args.slo_target},
        )
    server = Server(
        model, params,
        ServeConfig(
            chunk=args.chunk, slots=args.slots,
            max_inflight=args.max_inflight,
            deadline_ms=args.deadline_ms, stall_timeout=args.stall_timeout,
            grace=args.grace, prefill_buckets=args.prefill_buckets,
            prefill_chunk=args.prefill_chunk,
            prompt_overflow=args.prompt_overflow,
            session_dir=args.session_dir, session_idle_s=args.session_idle_s,
            max_dirty_sessions=args.max_dirty_sessions,
            breaker_failures=args.breaker_failures,
            breaker_backoff=args.breaker_backoff,
            breaker_max_backoff=args.breaker_max_backoff,
            spec_depth=args.spec_depth,
            spec_min_accept=args.spec_min_accept,
            qmode=args.qmode, prefix_dir=args.prefix_dir,
            params_id=params_id,
            metrics_path=args.metrics_path,
            metrics_interval_s=args.metrics_interval_s,
            trace_path=args.trace_path, flight_dir=args.flight_dir,
            metrics_port=args.metrics_port, slo=slo_cfg,
            tp=args.tp,
            cost=not args.no_cost,
            cost_ledger=not (args.no_cost or args.no_cost_ledger),
            profile_dir=args.profile_dir,
        ),
    )
    # the server holds its own serving tree (quantized, or the matmul
    # weights cast to the compute dtype): nothing here reads the loaded
    # tree again, so its arrays are freed
    del params
    if server.mesh_info is not None:
        print(
            f"tp mesh: tp={server.mesh_info['tp']} "
            f"param_bytes/device={server.mesh_info['param_bytes_per_device']} "
            f"carry_bytes/device={server.mesh_info['carry_bytes_per_device']} "
            f"budget_ok={server.mesh_info.get('budget_ok')}",
            file=sys.stderr,
        )
    if server.http_port is not None:
        print(f"live telemetry: http://127.0.0.1:{server.http_port}"
              "/metrics | /healthz | /statusz | /slo | /costz | "
              "/profilez?chunks=K", file=sys.stderr)
    if args.session_dir and server.session_store is not None:
        known = server.session_store.list_sessions()
        if known:
            print(f"session store: {len(known)} suspended session(s) "
                  f"restorable from {args.session_dir}", file=sys.stderr)
    completed = []  # (prompt, Pending) in submission order
    rc = 0
    for i, line in enumerate(lines):
        if guard.should_stop:
            print(f"draining on signal: {len(lines) - i} prompt(s) not "
                  "submitted", file=sys.stderr)
            break
        sid = None
        if args.session_id:
            sid = (args.session_id if len(lines) == 1
                   else f"{args.session_id}-{i}")
        req = DecodeRequest(
            prompt=jnp.asarray([tok.encode(line)], jnp.int32),
            max_new_tokens=args.max_new_tokens,
            sample=sample,
            seed=args.seed + i,
            session_id=sid,
            prefix_len=max(args.prefix_len, 0),
        )
        try:
            completed.append((line, server.submit(req)))
        except OverloadError:
            if args.no_wave:
                print(f"shed (overload): {line!r}", file=sys.stderr)
                continue
            rc = server.serve(drain_when_idle=True, guard=guard)
            if server.health.state is Health.DEAD:
                # drained on a signal mid-wave: the overflow prompt and
                # everything after it were never submitted — say so, an
                # exit-0 run must not silently be incomplete
                print(f"draining on signal: {len(lines) - i} prompt(s) "
                      "not submitted", file=sys.stderr)
                break
            completed.append((line, server.submit(req)))
        except RejectedError:
            print(f"rejected ({server.health.state.value}): {line!r}",
                  file=sys.stderr)
            break
        if server.health.state is Health.DEAD:
            break
    if server.health.state is not Health.DEAD:
        rc = server.serve(drain_when_idle=True, guard=guard)
        server.close()

    for line, pending in completed:
        r = pending.result
        if r is None:
            why = type(pending.error).__name__ if pending.error else "dropped"
            print(f"[{why}] {line}", file=sys.stderr)
            continue
        ids = [int(t) for t in r.tokens[0]]
        if eos_token >= 0 and eos_token in ids:
            ids = ids[: ids.index(eos_token)]
        tag = "" if r.status == "ok" else f" [{r.status}]"
        print(line + tok.decode(ids) + tag)
    print(f"stats: {server.stats}", file=sys.stderr)
    up = setup_summary()
    print("set-up: "
          + ", ".join(f"{k.split('.', 1)[1]} {v:.2f} s"
                      for k, v in up["seconds_by_span"].items())
          + f"; {up['programs_compiled']} program(s) compiled, "
          f"{up['programs_cache_loaded']} loaded from the cache "
          f"({up['compile_or_load_s']:.2f} s; trace + lower "
          f"{up['trace_lower_s']:.2f} s)"
          + ("" if up["ready"] else "; no token was emitted"),
          file=sys.stderr)
    mode = f"in-scan prefill, {server.engine.prefill_chunk} tok/piece"
    print(f"slot occupancy: {server.occupancy_lifetime():.3f} "
          f"({args.slots} slot(s), chunk {args.chunk}, {mode}"
          + (f", qmode {args.qmode}" if args.qmode != "off" else "")
          + (f", spec-depth {args.spec_depth}" if args.spec_depth else "")
          + ")",
          file=sys.stderr)
    if args.spec_depth:
        flat = server.metrics.counters_flat()
        acc = flat.get("spec_accepted_total", 0)
        rej = flat.get("spec_rejected_total", 0)
        rate = acc / (acc + rej) if acc + rej else 0.0
        print(f"speculation: {acc} draft(s) accepted, {rej} rejected "
              f"(rate {rate:.3f}), {flat.get('spec_floor_total', 0)} "
              "slot floor(s)", file=sys.stderr)
    if not args.no_cost:
        flat = server.metrics.counters_flat()
        cap = server.capacity.state() if server.capacity else {}
        line = (f"cost: {flat.get('attributed_ms_total', 0):.1f} ms device "
                f"time attributed over {flat.get('decode_tokens_total', 0)} "
                f"decode + {flat.get('prefill_tokens_total', 0)} prefill "
                "token(s)")
        if not cap.get("no_data"):
            line += (f"; capacity ceiling {cap['ceiling_tokens_per_s']} "
                     f"tok/s, headroom {cap['headroom']:.3f}")
        print(line, file=sys.stderr)
    if args.prefix_dir:
        flat = server.metrics.counters_flat()
        print(f"prefix cache: {flat.get('prefix_hits', 0)} hit(s), "
              f"{flat.get('prefix_misses', 0)} miss(es), "
              f"{flat.get('prefix_publishes', 0)} publish(es)",
              file=sys.stderr)
    if args.metrics_path:
        print(f"metrics: {args.metrics_path} (+ .json)", file=sys.stderr)
    if args.trace_path:
        print(f"trace: {args.trace_path} — merge for Perfetto with "
              f"`python -m orion_tpu.obs.trace merge {args.trace_path} "
              f"-o trace.json`", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
