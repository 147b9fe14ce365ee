#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1> [--rehearse]

Everything that belongs to one cell, configuration, metric or kind of run is
found by FILE NAME from the entries of ``BENCHMARK.json``; no such name is
written in this file (see ``benchmark/README.md``). The last line of stdout
is the one JSON result; every other line is an observation.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever device there is (the CPU "
                         "rehearsal); its numbers are never device numbers")
    args = ap.parse_args(argv)

    manifest = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.by_name(manifest["workloads"], args.workload, "workload")
    config_entry = harness.by_name(manifest["configs"], cell["config"], "config")
    seconds = args.seconds if args.seconds is not None else manifest["run_seconds"]

    device = harness.claim_devices(ROOT, cell["chips"], args.rehearse)
    run = harness.Run(
        root=ROOT, t0=T_PROCESS_START, seed=args.seed, seconds=seconds,
        trace=bool(args.trace), rehearse=args.rehearse, cell=cell,
        workload=harness.load_json(
            os.path.join(HERE, "workloads", cell["name"] + ".json")),
        config=harness.load_json(os.path.join(ROOT, config_entry["file"])),
        device=device,
    )
    kind = harness.load_module("kinds", run.workload["kind"])
    evidence = kind.run(run)

    section = "per_layer" if run.trace else "end_to_end"
    metrics = harness.read_metrics(manifest[section], section, cell["name"], evidence)
    device.update(harness.device_readings(evidence, run.trace))
    result = {
        "correct": bool(evidence["correct"]),
        "attempted": int(evidence["attempted"]),
        "failed": int(evidence["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if run.trace:
        breakdown = harness.load_module("readers", "xplane").breakdown(
            evidence.get("xplane"), evidence.get("annotations", []))
        if breakdown:
            result["breakdown"] = breakdown
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the program (prefetch, scrape) must not hold the exit
    os._exit(rc)
