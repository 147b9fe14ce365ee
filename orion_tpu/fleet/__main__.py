"""``python -m orion_tpu.fleet`` — serve prompts through a replicated
fleet.

Spawns ``--replicas`` child serving processes (identical params: same
seeded init or the same ``--ckpt-dir``), routes prompts through the
least-loaded dispatcher, supervises heartbeats in the background, and
drains the whole fleet on exit (or SIGTERM). With ``--session-dir`` the
replicas share one durable session store, so conversations survive both
replica drains and whole-fleet restarts — and a ``--session-id`` turn may
be served by a different replica each invocation.

``--local`` runs the replicas as in-process threads instead of child
processes: same router/supervisor wiring, no spawn cost — the debugging
and CI transport.
"""

from __future__ import annotations

import argparse
import sys

from orion_tpu.fleet.replica import (
    LocalReplica,
    ProcessReplica,
    ReplicaGone,
    ReplicaSpec,
    build_model,
    serve_config,
)
from orion_tpu.fleet.supervisor import Supervisor
from orion_tpu.serving.server import OverloadError, RejectedError


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("orion_tpu.fleet")
    p.add_argument("--config", default="tiny")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--replicas", type=int, default=2,
                   help="engine replicas behind the router (child serving "
                        "processes; --local makes them threads)")
    p.add_argument("--local", action="store_true",
                   help="thread-backed replicas in this process instead of "
                        "child OS processes (debugging / CI)")
    p.add_argument("--max-inflight", type=int, default=0,
                   help="FLEET-level admission bound across all replicas "
                        "(0 = per-replica bounds only); beyond it submits "
                        "shed with OverloadError, the single-server "
                        "contract one level up")
    p.add_argument("--session-dir", default=None,
                   help="SHARED durable-session store: any replica resumes "
                        "any conversation from disk (migration is a read)")
    p.add_argument("--session-id", default=None,
                   help="tag prompts as conversation turns (line i gets "
                        "'<id>-<i>' when several prompts are given)")
    p.add_argument("--prompts-file", default="-",
                   help="one prompt per line; '-' = stdin")
    p.add_argument("--max-new-tokens", type=int, default=64)
    # pass-through engine knobs (per replica)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--chunk", type=int, default=16)
    p.add_argument("--prefill-chunk", type=int, default=64)
    p.add_argument("--prefill-buckets", default="pow2")
    p.add_argument("--replica-max-inflight", type=int, default=8,
                   help="per-replica admission queue bound")
    p.add_argument("--tp", type=int, default=0,
                   help="device-mesh footprint per replica (ISSUE 14): "
                        "each replica shards its batched decode over a "
                        "tp-device mesh (a CPU child provisions its own "
                        "virtual devices). Tokens are bitwise the "
                        "unsharded fleet's; sessions stay portable "
                        "across footprints. 0/1 = unsharded")
    p.add_argument("--qmode", choices=["off", "int8", "int4"],
                   default="off",
                   help="weight-streamed quantized serving inside EVERY "
                        "replica (each child quantizes the same params "
                        "the same deterministic way, so placement stays "
                        "invisible in the tokens)")
    p.add_argument("--spec-depth", type=int, default=0,
                   help="self-speculative decode inside EVERY replica: "
                        "the global-linear layers draft, one batched "
                        "piece verifies — tokens stay BITWISE identical "
                        "to plain decode, so placement AND speculation "
                        "are both invisible in the output (0 = off)")
    p.add_argument("--spec-min-accept", type=float, default=0.2,
                   help="per-slot adaptive speculation floor inside each "
                        "replica (rolling acceptance below this falls "
                        "back to plain decode; 0 = never)")
    p.add_argument("--prefix-dir", default=None,
                   help="SHARED content-addressed prefix cache: a system "
                        "prompt published by one replica admits O(suffix) "
                        "on every replica")
    p.add_argument("--prefix-len", type=int, default=0,
                   help="declare the first N tokens of every prompt as a "
                        "shared cacheable prefix (miss publishes to "
                        "--prefix-dir; 0 = never publish)")
    p.add_argument("--exec-dir", default=None,
                   help="SHARED content-addressed AOT executable store "
                        "(ISSUE 20): replicas load their decode programs "
                        "pre-compiled from here (publish via 'python -m "
                        "orion_tpu.aot warm' or the first compiling "
                        "replica) — a spawn becomes a download, not a "
                        "compile; any miss falls back to jit")
    p.add_argument("--autoscale", type=int, default=0,
                   help="elastic fleet: let the supervisor move the "
                        "replica count between 1 and this many on "
                        "capacity headroom / queue depth / SLO burn "
                        "(0 = fixed fleet); scale-in drains through the "
                        "shared session store, zero lost turns")
    p.add_argument("--pin-cores", action="store_true",
                   help="pin each replica's XLA compute pool to one core "
                        "(rotating by replica index) — without it one "
                        "replica's pool spans every CPU and N replicas "
                        "fight for the same cores instead of scaling")
    p.add_argument("--deadline-ms", type=float, default=0.0)
    p.add_argument("--metrics-port", type=int, default=-1,
                   help="serve the LIVE fleet-AGGREGATED view on this "
                        "port (0 = ephemeral, reported on stderr; -1 = "
                        "off): /metrics sums every replica's registry "
                        "from the supervisor's heartbeat snapshots "
                        "(staleness <= --heartbeat-s; no per-scrape "
                        "RPCs), /healthz is 200 while any replica is "
                        "routable, /statusz is the router's fleet "
                        "snapshot, /slo the per-replica burn rates and "
                        "budgets")
    p.add_argument("--slo-latency-ms", type=float, default=0.0,
                   help="declare a per-turn latency SLO on every "
                        "replica (--slo-target of turns under this "
                        "many ms): arms the full control loop — fast "
                        "burn degrades + sheds on the replica, the "
                        "router tie-breaks on windowed p99, the "
                        "supervisor drain-respawns a persistent burner")
    p.add_argument("--slo-target", type=float, default=0.99,
                   help="good-event fraction each declared objective "
                        "promises (error budget = 1 - target), as on "
                        "the single-server CLI")
    p.add_argument("--metrics-path", default=None,
                   help="fleet-AGGREGATED Prometheus-text metrics dump "
                        "(+ .json with the per-replica breakdown), "
                        "written on exit; each replica also dumps its "
                        "own registry at <path>.<replica> while serving")
    p.add_argument("--trace-path", default=None,
                   help="request-trace output: the router and every "
                        "replica write Chrome trace-event JSONL "
                        "(<path>.<name>.jsonl), merged on exit into "
                        "<path> — one Perfetto-loadable file where a "
                        "turn that migrated across replicas is one "
                        "connected trace")
    p.add_argument("--flight-dir", default=None,
                   help="flight-recorder dump directory for the parent "
                        "(router/supervisor black box) AND every "
                        "replica; dumps fire on DEGRADED/drain/ladder "
                        "exhaustion/child exit")
    p.add_argument("--no-cost", action="store_true",
                   help="disable per-request cost attribution + the "
                        "capacity model inside every replica (on by "
                        "default; the fleet /metrics.json then carries "
                        "an aggregated capacity/headroom section)")
    p.add_argument("--profile-dir", default=None,
                   help="arm-able jax.profiler capture inside every "
                        "replica (each child writes to "
                        "<dir>/<replica>); trigger via a replica's "
                        "/profilez?chunks=K endpoint — off by default, "
                        "flight-recorded when it fires")
    p.add_argument("--heartbeat-s", type=float, default=1.0,
                   help="supervisor heartbeat interval")
    p.add_argument("--grace", type=float, default=30.0)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=1.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                   help="ModelConfig override (must match the checkpoint)")
    return p


def _spec_from_args(args) -> ReplicaSpec:
    overrides = {}
    if args.set:
        from orion_tpu.utils.config import parse_set_overrides

        overrides = parse_set_overrides(args.set)
    serve = {
        "slots": args.slots,
        "chunk": args.chunk,
        "prefill_chunk": args.prefill_chunk,
        "prefill_buckets": args.prefill_buckets,
        "max_inflight": args.replica_max_inflight,
        "deadline_ms": args.deadline_ms,
        "grace": args.grace,
        "session_dir": args.session_dir,
        "qmode": args.qmode,
        "spec_depth": args.spec_depth,
        "spec_min_accept": args.spec_min_accept,
        "prefix_dir": args.prefix_dir,
        "exec_dir": args.exec_dir,
        # cost attribution + capacity inside every replica; the ledger
        # harvest (a one-time lower at child startup, memoized) gives
        # the fleet real flops figures instead of the analytic fallback
        "cost": not args.no_cost,
        "cost_ledger": not args.no_cost,
        # params_id is NOT set here: every replica derives it from the
        # weights it actually loads (build_model — config + overrides +
        # resolved checkpoint STEP or init seed), so a fleet restarted
        # after training advanced can never hit a previous step's
        # prefix snapshots
    }
    if args.slo_latency_ms > 0:
        # declared objectives (JSON-able Objective kwargs) arm actuation
        # inside every replica; the supervisor and router act on the
        # resulting burn rates over the status op
        serve["slo"] = [
            {"name": "turn_latency", "kind": "latency",
             "latency_ms": args.slo_latency_ms,
             "target": args.slo_target},
            {"name": "error_rate", "kind": "error_rate",
             "target": args.slo_target},
            {"name": "availability", "kind": "availability",
             "target": args.slo_target},
        ]
    return ReplicaSpec(
        config=args.config,
        overrides=overrides or None,
        ckpt_dir=args.ckpt_dir,
        serve=serve,
        tp=max(args.tp, 0),
    )


def _obs_serve_overrides(args, name: str) -> dict:
    """Per-replica telemetry paths (ServeConfig kwargs): each child gets
    its own metrics/trace file keyed by the replica name, all mergeable/
    aggregatable in the parent afterwards."""
    out = {}
    if args.metrics_path:
        out["metrics_path"] = f"{args.metrics_path}.{name}"
    if args.trace_path:
        out["trace_path"] = f"{args.trace_path}.{name}.jsonl"
    if args.flight_dir:
        out["flight_dir"] = args.flight_dir
    if args.profile_dir:
        import os as _os

        out["profile_dir"] = _os.path.join(args.profile_dir, name)
    return out


def main(argv=None) -> int:
    import dataclasses

    args = build_argparser().parse_args(argv)
    if args.session_id and not args.session_dir:
        print("--session-id requires --session-dir", file=sys.stderr)
        return 2
    if args.local and args.tp and args.tp > 1:
        # --local replicas share THIS process's device client: provision
        # the virtual CPU devices here, before anything touches jax
        # (process replicas provision their own in _child_main)
        from orion_tpu.utils.devices import ensure_virtual_devices

        ensure_virtual_devices(args.tp)
    spec = _spec_from_args(args)

    # parent-side telemetry: the router's root spans and the supervisor/
    # control-channel black box (children configure their own from the
    # per-replica ServeConfig overrides below)
    tracer = None
    if args.trace_path:
        import time as _time

        from orion_tpu.obs.trace import Tracer

        # same clock as every replica Server's tracer (Server defaults
        # to time.monotonic): merge_traces sorts by ts, and root spans
        # on a different clock epoch would detach from the chunk spans
        # they contain
        tracer = Tracer(path=f"{args.trace_path}.router.jsonl",
                        clock=_time.monotonic)
    if args.flight_dir:
        from orion_tpu.obs import flight

        flight.configure(dump_dir=args.flight_dir)

    def _spec_for(name: str) -> ReplicaSpec:
        obs = _obs_serve_overrides(args, name)
        if not obs:
            return spec
        return dataclasses.replace(
            spec, serve={**(spec.serve or {}), **obs}
        )

    if args.local:
        model, params, params_id = build_model(spec)

        def factory(name: str):
            return LocalReplica(
                model, params,
                serve_config(_spec_for(name), params_id=params_id),
                name=name,
            ).start()
    else:
        import os

        def factory(name: str):
            s = _spec_for(name)
            if args.pin_cores:
                idx = Supervisor.replica_index(name)
                s = dataclasses.replace(
                    s, compute_cpus=[idx % (os.cpu_count() or 1)]
                )
            return ProcessReplica(s, name=name).start()

    from orion_tpu.generate import SampleConfig
    from orion_tpu.utils.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    sample = SampleConfig(args.temperature, args.top_k, args.top_p)

    if args.prompts_file == "-":
        lines = [ln.rstrip("\n") for ln in sys.stdin]
    else:
        with open(args.prompts_file) as f:
            lines = [ln.rstrip("\n") for ln in f]
    if args.session_id:
        lines = lines or [""]
    else:
        lines = [ln for ln in lines if ln]

    autoscale = None
    if args.autoscale > 0:
        from orion_tpu.fleet.supervisor import AutoscalePolicy

        # queue pressure keyed to the per-replica admission bound: the
        # fleet scales out when the average replica's queue is full —
        # the leading edge of a load step, well before tokens/s moves
        autoscale = AutoscalePolicy(
            min_replicas=1,
            max_replicas=max(args.autoscale, args.replicas),
            queue_high=float(args.replica_max_inflight),
            queue_low=max(args.replica_max_inflight / 4.0, 1.0),
        )
    try:
        sup = Supervisor(
            factory, args.replicas, max_inflight=args.max_inflight,
            tracer=tracer, autoscale=autoscale,
        ).start()
    except ReplicaGone as e:
        # e.g. process replicas outnumber the chips (one process per
        # chip; --local shares one client): one error, no respawn loop
        print(f"fleet failed to start: {e}", file=sys.stderr)
        return 1
    sup.start_monitor(interval=args.heartbeat_s)
    rc = 0
    completed = []
    aggregated = None
    http = None
    try:
        # inside the try: replicas are already spawned, so a bind
        # failure (port in use) must still reach the finally's
        # drain_all — never orphan child decoders over an endpoint
        http = _start_fleet_http(args, sup)
        import numpy as np

        from orion_tpu.serving.session import DecodeRequest

        for i, line in enumerate(lines):
            sid = None
            if args.session_id:
                sid = (args.session_id if len(lines) == 1
                       else f"{args.session_id}-{i}")
            req = DecodeRequest(
                prompt=np.asarray([tok.encode(line)], np.int32).reshape(1, -1),
                max_new_tokens=args.max_new_tokens,
                sample=sample, seed=args.seed + i, session_id=sid,
                prefix_len=max(args.prefix_len, 0),
            )
            while True:
                try:
                    completed.append((line, sup.router.submit(req)))
                    break
                except OverloadError:
                    # wave-drain like the single-server CLI: wait for the
                    # oldest outstanding result, then resubmit
                    for _, p in completed:
                        if not p.done.is_set():
                            p.done.wait(timeout=60.0)
                            break
                except RejectedError as e:
                    print(f"rejected: {e}", file=sys.stderr)
                    rc = 1
                    break
            if rc:
                break
        for line, pending in completed:
            if pending.done.wait(timeout=600.0):
                continue
            print(f"[dropped] {line}", file=sys.stderr)
        for line, pending in completed:
            if pending.error is not None:
                print(f"[{type(pending.error).__name__}] {line}",
                      file=sys.stderr)
                continue
            r = pending.result
            if r is None:
                continue
            ids = [int(t) for t in r.tokens[0]]
            tag = "" if r.status == "ok" else f" [{r.status}]"
            print(line + tok.decode(ids) + tag)
        snap = sup.router.snapshot()
        print(f"fleet: {snap}", file=sys.stderr)
        if args.metrics_path or not args.no_cost:
            # scrape while the children still answer status — after the
            # drain there is nobody to ask
            aggregated = sup.aggregate_metrics()
            cap = aggregated.get("capacity") or {}
            if not cap.get("no_data"):
                print(
                    f"fleet capacity: ceiling "
                    f"{cap['ceiling_tokens_per_s']} tok/s, current "
                    f"{cap['current_tokens_per_s']} tok/s, headroom "
                    f"{cap['headroom']:.3f} over "
                    f"{cap['replicas_reporting']} replica(s)",
                    file=sys.stderr,
                )
    finally:
        sup.drain_all(timeout=args.grace * 2)
        if http is not None:
            http.close()
        _dump_fleet_obs(args, tracer, aggregated)
    return rc


def _fleet_healthz(sup) -> dict:
    """Fleet-level /healthz: 200 while ANY replica is routable (the
    router can place work), 503 otherwise — a balancer in front of
    several fleets needs one bit, the body carries the per-replica
    breakdown."""
    snap = sup.router.snapshot()
    routable = [
        r for r in snap["replicas"]
        if r["alive"] and r["state"] in ("starting", "serving", "degraded")
    ]
    snap["code"] = 200 if routable else 503
    snap["accepting"] = bool(routable)
    return snap


def _fleet_metrics(sup) -> dict:
    """Fleet-level /metrics: aggregate over the supervisor-refreshed
    ``last_status`` snapshots (every heartbeat tick stores one per
    replica) instead of issuing fresh status RPCs per scrape — a
    Prometheus scraper on a sub-second interval must not multiply
    control-channel traffic (or block heartbeat_timeout per wedged
    replica per GET, piling up handler threads mid-incident). Staleness
    is bounded by the heartbeat interval; the end-of-run file dump
    still uses Supervisor.aggregate_metrics for a fresh sweep."""
    from orion_tpu.obs.metrics import aggregate

    snaps, names = [], []
    for replica in list(sup.replicas):
        status = getattr(replica, "last_status", None)
        m = (status or {}).get("metrics")
        if m is not None:
            snaps.append(m)
            names.append(replica.name)
    agg = aggregate(snaps, sources=names)
    agg["replicas"] = len(names)
    # same recomputed fleet headroom as Supervisor.aggregate_metrics
    # (the summed headroom gauge is meaningless; this is the autoscaler
    # number, served live on /metrics.json)
    from orion_tpu.obs.cost import fleet_capacity

    agg["capacity"] = fleet_capacity(agg)
    return agg


def _fleet_slo(sup) -> dict:
    """Fleet-level /slo: every replica's burn rates/budgets from its
    last heartbeat snapshot (the supervisor refreshes them; no extra
    round-trip from the scrape thread)."""
    out = {}
    for replica in list(sup.replicas):
        status = getattr(replica, "last_status", None)
        if status and status.get("slo"):
            out[replica.name] = status["slo"]
    return {"replicas": out}


def _start_fleet_http(args, sup):
    """The aggregated live endpoint (--metrics-port): /metrics sums the
    child registries the supervisor's heartbeats already scraped over
    the existing status op; /healthz, /statusz and /slo serve the fleet
    view."""
    if args.metrics_port is None or args.metrics_port < 0:
        return None
    from orion_tpu.obs.http import ObsHTTPServer

    http = ObsHTTPServer(
        port=args.metrics_port,
        metrics_fn=lambda: _fleet_metrics(sup),
        health_fn=lambda: _fleet_healthz(sup),
        statusz_fn=sup.router.snapshot,
        slo_fn=lambda: _fleet_slo(sup),
    )
    port = http.start()
    print(f"fleet telemetry: http://127.0.0.1:{port}/metrics | /healthz "
          "| /statusz | /slo (aggregated over the status op)",
          file=sys.stderr)
    return http


def _dump_fleet_obs(args, tracer, aggregated) -> None:
    """Post-drain exposition: the fleet-aggregated metrics (Prometheus
    text + JSON with the per-replica breakdown) and the merged
    Perfetto-loadable trace (router root spans + every replica's spans
    in one file)."""
    import glob
    import json as _json
    import os

    if aggregated is not None and args.metrics_path:
        from orion_tpu.obs.metrics import prometheus_from_snapshot

        with open(args.metrics_path + ".tmp", "w") as f:
            f.write(prometheus_from_snapshot(aggregated))
        os.replace(args.metrics_path + ".tmp", args.metrics_path)
        with open(args.metrics_path + ".json.tmp", "w") as f:
            _json.dump(aggregated, f, indent=1, default=repr)
        os.replace(args.metrics_path + ".json.tmp",
                   args.metrics_path + ".json")
        print(f"fleet metrics: {args.metrics_path} (+ .json)",
              file=sys.stderr)
    if tracer is not None and args.trace_path:
        from orion_tpu.obs.trace import merge_traces

        tracer.flush()
        parts = sorted(glob.glob(args.trace_path + ".*.jsonl"))
        n = merge_traces(parts, args.trace_path)
        print(f"fleet trace: {n} events merged into {args.trace_path} "
              f"from {len(parts)} file(s) — load in Perfetto "
              "(ui.perfetto.dev)", file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
