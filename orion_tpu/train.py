"""`python -m orion_tpu.train` — the training entrypoint (SURVEY.md T1).

TPU-native counterpart of the reference's `orion.train` (BASELINE.json;
reference checkout never mounted — SURVEY.md §0). Library use:

    from orion_tpu.train import train
    state, metrics = train(TrainConfig(model=get_config("tiny"), steps=100),
                           data="synthetic")

CLI:

    python -m orion_tpu.train --config tiny --steps 1000 --data synthetic \
        --set lr=1e-3 --set model.n_layers=4 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import sys
import time
from typing import Optional, Tuple

from orion_tpu.models.configs import get_config
from orion_tpu.obs.trace import PROCESS_TRACER, Tracer
from orion_tpu.parallel.mesh import MeshConfig, initialize_distributed
from orion_tpu.resilience.preempt import PreemptionGuard
from orion_tpu.resilience.watchdog import Watchdog
from orion_tpu.training.checkpoint import Checkpointer
from orion_tpu.training.data import DataLoader, make_dataset
from orion_tpu.training.metrics import MetricsLogger
from orion_tpu.training.trainer import TrainConfig, Trainer


def train(
    cfg: TrainConfig,
    data: str = "synthetic",
    eval_data: Optional[str] = None,
    log_path: Optional[str] = None,
    resume: bool = True,
    metrics_path: Optional[str] = None,
    trace_path: Optional[str] = None,
) -> Tuple[object, dict]:
    """Build everything, optionally resume, run to cfg.steps. Returns
    (final TrainState, last metrics dict). ``trace_path``: the set-up and
    ``step`` spans of this run (obs/trace.py) as JSONL, appended at the log
    cadence and at exit; the process-wide record holds them either way."""
    # config errors before the expensive part: Trainer materializes multi-GB
    # state and the loader spawns its prefetch thread
    if eval_data and not cfg.eval_every:
        raise ValueError(
            "eval_data given but eval_every == 0 — the held-out split "
            "would silently never be evaluated; set eval_every > 0 "
            "(CLI: --eval-every N)"
        )
    ckpt = None
    start = 0
    tracer = (
        Tracer(path=trace_path, clock=time.monotonic) if trace_path
        else PROCESS_TRACER
    )
    with tracer.span("setup.weights", "setup") as weights:
        trainer = Trainer(cfg, tracer=tracer)
        if cfg.ckpt_dir:
            ckpt = Checkpointer(
                cfg.ckpt_dir, max_to_keep=cfg.ckpt_keep,
                save_every=cfg.ckpt_every,
            )
            if resume and ckpt.latest_step is not None:
                start = trainer.restore(ckpt)
                print(f"resumed from step {start}", file=sys.stderr)
        weights.note(source="checkpoint" if start else "init")

    dataset = make_dataset(data, cfg.seq_len, cfg.model.vocab_size)
    assert dataset.vocab_size <= cfg.model.vocab_size, (
        f"data vocab {dataset.vocab_size} > model vocab {cfg.model.vocab_size}"
    )
    loader = DataLoader(
        dataset,
        cfg.batch_size,
        seed=cfg.seed,
        start_step=start,
        sharding=trainer.batch_shd,
        stall_timeout=cfg.step_timeout if cfg.step_timeout > 0 else None,
    )
    logger = MetricsLogger(log_path)
    if cfg.ckpt_dir:
        # the run directory doubles as the black box's dump target: a
        # preemption or nan-halt leaves flight-*.json beside the
        # checkpoints it force-saved (obs/flight.py)
        import os as _os

        from orion_tpu.obs import flight as _flight

        _flight.configure(dump_dir=_os.path.join(cfg.ckpt_dir, "flight"))
    eval_factory = None
    if cfg.eval_every:
        # a real held-out split when given (--eval-data val.bin); otherwise
        # a disjoint-seed stream over the training data
        eval_ds = (
            make_dataset(eval_data, cfg.seq_len, cfg.model.vocab_size)
            if eval_data
            else dataset
        )
        assert eval_ds.vocab_size <= cfg.model.vocab_size, (
            f"eval data vocab {eval_ds.vocab_size} > model vocab "
            f"{cfg.model.vocab_size}"
        )

        def eval_factory(step, _ds=eval_ds):
            # batches a pure function of the TRAIN step — a resumed run
            # re-evaluates any step's eval on the exact same batches. A
            # short-lived DataLoader keeps the prefetch overlap AND the
            # multi-host make_array_from_callback path (data.py P7/P11)
            # the sampling math alone would lose.
            base = 10_000_000 + step * cfg.eval_batches
            loader = DataLoader(
                _ds, cfg.batch_size, seed=cfg.seed + 1, start_step=base,
                sharding=trainer.batch_shd,
                # eval reads get the same stall budget as train reads — a
                # dead mount under --eval-data must raise a diagnosable
                # StallError, not hang the (watchdog-disarmed) eval pass
                stall_timeout=cfg.step_timeout if cfg.step_timeout > 0 else None,
            )

            def gen():
                try:
                    it = iter(loader)
                    for j in range(cfg.eval_batches):
                        batch = next(it)
                        if j == cfg.eval_batches - 1:
                            loader.close()  # last batch out; stop the thread
                        yield batch
                finally:
                    loader.close()  # safety if the consumer stops early

            return gen()
    # resilience wiring (resilience/): preempt_grace > 0 installs the
    # SIGTERM/SIGINT graceful-stop guard for the duration of the run;
    # step_timeout > 0 arms the hang watchdog (the loader's stall detector
    # is wired above with the same budget)
    from orion_tpu.obs import flight as _fl

    guard_cm = (
        PreemptionGuard(
            cfg.preempt_grace,
            # signal-context tap: the black box records the signal the
            # instant it lands (lock-free append — the handler runs
            # between two arbitrary bytecodes), not just the boundary
            # where the trainer later acts on it
            on_stop=lambda signum: _fl.recorder().record_signal_safe(
                "preempt_signal", signum=signum
            ),
        )
        if cfg.preempt_grace > 0
        else contextlib.nullcontext()
    )
    watchdog = Watchdog(cfg.step_timeout) if cfg.step_timeout > 0 else None
    try:
        with guard_cm as guard:
            last = trainer.train(
                iter(loader), logger=logger, ckpt=ckpt,
                eval_factory=eval_factory, preempt=guard, watchdog=watchdog,
            )
        if trainer.preempted_at is not None:
            note = (
                "emergency checkpoint saved; rerun with the same "
                "--ckpt-dir to resume"
                if ckpt is not None
                else "NO checkpointer configured — progress since the last "
                     "save is lost (set --ckpt-dir)"
            )
            print(
                f"preempted at step {trainer.preempted_at}: {note}",
                file=sys.stderr,
            )
        elif ckpt is not None:
            ckpt.maybe_save(int(trainer.state.step), trainer.state, force=True)
    finally:
        if watchdog is not None:
            watchdog.close()
        loader.close()
        tracer.close()
        if metrics_path:
            # final scrape on every exit path (same contract as the
            # serving CLI's on-drain dump): Prometheus text + .json
            try:
                logger.dump(metrics_path)
            except OSError as e:
                print(f"metrics dump failed: {e}", file=sys.stderr)
        logger.close()
        if ckpt is not None:
            # close() waits for any in-flight async save, INCLUDING on the
            # exception path — a raise mid-train must not abandon a
            # half-written step (the manifest/fallback machinery handles
            # torn writes, but not leaking the writer)
            ckpt.close()
    return trainer.state, last


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("orion_tpu.train")
    p.add_argument("--config", default="tiny", help="named model config")
    p.add_argument("--data", default="synthetic", help="'synthetic' or token-bin path")
    p.add_argument("--eval-data", default=None,
                   help="held-out token-bin path for eval (default: train data)")
    p.add_argument("--eval-every", type=int, default=0,
                   help="eval cadence in steps (0 = no interleaved eval)")
    p.add_argument("--steps", type=int, default=1000)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=256)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--log-path", default=None)
    p.add_argument("--metrics-path", default=None,
                   help="Prometheus-text metrics exposition file "
                        "(+ .json sibling), written on exit — the same "
                        "registry format the serving/fleet CLIs expose")
    p.add_argument("--trace-path", default=None,
                   help="step-trace JSONL (Chrome trace events): one "
                        "train.step span per iteration of the loop with "
                        "its phases inside (next_batch, dispatch, "
                        "log_readback, eval, checkpoint, hook) and the "
                        "interpreter's collections; merge with `python -m "
                        "orion_tpu.obs.trace merge` and load in Perfetto")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--preempt-grace", type=float, default=10.0,
        help="seconds budgeted for the emergency checkpoint on SIGTERM/"
             "SIGINT (graceful stop at the next step boundary); 0 disables "
             "the signal handlers",
    )
    p.add_argument(
        "--step-timeout", type=float, default=0.0,
        help="hang watchdog: raise StallError if no step completes (or no "
             "data batch arrives) for this many seconds — must exceed jit "
             "compile + one step; 0 disables",
    )
    p.add_argument("--dp", type=int, default=-1)
    p.add_argument("--fsdp", type=int, default=1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline stages (depth-homogeneous models)")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel axis (MoE configs, model.n_experts>0)")
    p.add_argument("--distributed", action="store_true", help="multi-host init")
    p.add_argument(
        "--set", action="append", default=[], metavar="KEY=VALUE",
        help="dotted TrainConfig override, e.g. --set model.n_layers=4",
    )
    p.add_argument("--config-json", default=None, help="JSON override file")
    return p


def main(argv=None) -> int:
    args = build_argparser().parse_args(argv)
    from orion_tpu.utils.cache import enable_compile_cache

    enable_compile_cache()
    if args.distributed:
        initialize_distributed()
    from orion_tpu.utils.config import apply_overrides, load_json_overrides

    cfg = TrainConfig(
        model=get_config(args.config),
        steps=args.steps,
        batch_size=args.batch_size,
        seq_len=args.seq_len,
        lr=args.lr,
        seed=args.seed,
        eval_every=args.eval_every,
        ckpt_dir=args.ckpt_dir,
        preempt_grace=args.preempt_grace,
        step_timeout=args.step_timeout,
        mesh=MeshConfig(dp=args.dp, fsdp=args.fsdp, tp=args.tp, sp=args.sp,
                        pp=args.pp, ep=args.ep),
    )
    if args.config_json:
        cfg = apply_overrides(cfg, load_json_overrides(args.config_json))
    from orion_tpu.utils.config import parse_set_overrides

    overrides = parse_set_overrides(args.set)
    if overrides:
        cfg = apply_overrides(cfg, overrides)
    if cfg.seq_len >= cfg.model.max_seq_len:
        cfg = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, max_seq_len=cfg.seq_len + 1)
        )
    _, last = train(
        cfg, data=args.data, eval_data=args.eval_data,
        log_path=args.log_path, metrics_path=args.metrics_path,
        trace_path=args.trace_path,
    )
    print({k: round(v, 5) for k, v in last.items()})
    if args.trace_path:
        print(f"trace: {args.trace_path} — merge for Perfetto with "
              f"`python -m orion_tpu.obs.trace merge {args.trace_path} "
              f"-o trace.json`", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
