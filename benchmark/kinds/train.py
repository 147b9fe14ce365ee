"""A training cell: the program's ``Trainer.train`` loop fed by its
``DataLoader``.

Each run: build the Trainer, weights from ``--seed`` -> check its forward
against the plain reference -> one call of ``Trainer.train``, the
loop ``python -m orion_tpu.train`` runs: its first steps are the warm-up, the
window opens when the last of them has completed, and a stop guard ends the
loop ``--seconds`` later -> (traced run only) a second call for two profiled
steps. The benchmark hands the loop a ``hook`` (the program's own per-step
callback) that reads back the loss of the step BEFORE the one just enqueued:
proof that it completed, with one step still in flight, and the clock every
rate here is taken from.

Evidence handed to the readers (generic names; which metric reads which is
in the metric's own file):

- ``values``: tokens_per_s_per_chip, setup_seconds, step_ms_mean,
  flops_per_token_6n (6 x active parameters: attention's T-dependent FLOPs
  and the remat recompute are NOT counted);
- ``spans``: host spans inside the window: ``loader`` around each
  ``next()`` the program's loop makes on its data iterator, ``block`` around
  the hook's wait for the step before; ``window_s`` its length;
- ``memory``: ``memory_stats()`` of the fullest chip after the window;
- ``xplane``: device and host events of the two profiled steps.

NOT checked against any reference: the backward pass (the Pallas backward
kernels, remat), the optimizer (fused adafactor) and the stochastic-rounding
apply. They are held only to finite losses. A gradient check against
``jax.grad`` of the reference ran on the chip once (PERF.md s6: worst leaf 2.0
to 2.8% relative error) but its reference program is 227 MB, over the 192 MiB
at which the chip's machine caps the compile cache: 120 s of every run.
"""

from __future__ import annotations

import gc
import math
import time

import harness

# |system eval loss - reference loss| on one sequence at the initial
# parameters: the program's own ``Trainer.evaluate`` (bf16 compute, fp32
# accumulation, the Pallas forward kernels) against the plain reference (fp32,
# "highest" matmul precision). A mean over 2048 tokens whose per-token errors
# have both signs, so a signed error can cancel in it. Read on the chip:
# 1e-4 to 7e-4 in all 29 runs of both configurations (PR 27, both sessions).
# The tolerance is three times the largest reading. A dropped term
# (normaliser, rotary, window) or a wrong weight moves the mean by 1e-2 or
# more. An 8-bit compute type has some 16 times bf16's rounding error, which
# would read 1.6e-3 to 1e-2: most such runs fail this gate, not certainly all.
LOSS_TOLERANCE = 0.002


def active_params(cfg, params) -> float:
    """Parameters that do work per token (bench.py's arithmetic, copied):
    expert stacks count their routed share."""
    import jax

    scale = cfg.moe_top_k / cfg.n_experts if cfg.n_experts > 0 else 1.0
    total = 0.0
    for path, x in jax.tree_util.tree_leaves_with_path(params):
        total += x.size * (scale if "experts_" in jax.tree_util.keystr(path) else 1.0)
    return float(total)


def seeded_params(trainer, seed: int):
    """The weights from ``--seed``, by the Trainer's own initialisers, in one
    jitted call whose program does not depend on the seed.

    ``Trainer.__init__`` bakes the key made from ``TrainConfig.seed`` into its
    init program as a constant, so every new seed would compile that program
    again (63 s at 1.3B, my chip run, PR 27) and never find it in the cache: a
    defect of the program (PERF.md s7), worked round here and nowhere else.
    The Trainer is built with seed 0 -- which also fixes the key its
    stochastic rounding draws from -- and the parameters are made here with
    the key as an ARGUMENT. The optimizer state of a fresh Trainer does not
    depend on the parameters' values."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.training.trainer import storage_cast

    n = trainer.mesh.shape.get("dp", 1) * trainer.mesh.shape.get("fsdp", 1)
    sample = jnp.zeros((n, trainer.cfg.seq_len), jnp.int32)

    def init(key):
        return storage_cast(trainer.model.init(key, sample), trainer.cfg.param_storage)

    return jax.jit(init, out_shardings=trainer.state_shardings.params)(
        jax.random.key(seed))


def check_forward(trainer, dataset, seed: int) -> dict:
    """The program's eval loss against the plain reference's, same weights,
    one sequence, before the train step's program fills the chip."""
    import jax
    import jax.numpy as jnp

    from reference import plain_lm

    n = trainer.mesh.shape.get("dp", 1) * trainer.mesh.shape.get("fsdp", 1)
    # one sequence, repeated to fill the data axes: both sides see the same
    batch = jnp.repeat(jnp.asarray(dataset.batch(seed, 10**6, 1)), n, axis=0)
    system = trainer.evaluate(iter([batch]), n_batches=1)["eval_loss"]
    spec = harness.reference_spec(trainer.model.cfg)
    with jax.default_matmul_precision("highest"):
        ref = float(jax.jit(
            lambda p, b: plain_lm.next_token_loss(spec, p, b)
        )(trainer.state.params, batch[:1]))
    del batch
    gc.collect()
    # loaded programs keep their scratch reserved (PERF.md, PR 24 finding 2):
    # unload the two check programs before the train step claims the chip
    jax.clear_caches()
    return {"system_loss": system, "reference_loss": ref,
            "delta": abs(system - ref), "tolerance": LOSS_TOLERANCE,
            "ok": math.isfinite(system) and abs(system - ref) <= LOSS_TOLERANCE}


class TimedLoader:
    """The data iterator handed to the program's loop: every ``next()`` the
    loop makes is a ``loader`` span."""

    def __init__(self, loader, spans):
        self.loader, self.spans = loader, spans

    def __iter__(self):
        return self

    def __next__(self):
        with self.spans.span("loader"):
            return next(self.loader)


class StepClock:
    """The ``hook`` and the ``preempt`` guard of one ``Trainer.train`` call.

    The loop calls ``hook(step, metrics)`` right after it has enqueued
    ``step``; the hook waits for the step BEFORE (its loss is the proof it
    completed) and stamps it, so one step stays in flight and the device
    never waits for the host to notice that a step ended. The first
    ``warmup`` completions are set-up; the window opens at the last of them.
    The loop polls ``should_stop`` after each hook: true ``seconds`` after the
    window opened, or once ``steps`` steps were enqueued. ``close()`` stamps
    the step still in flight when the loop left."""

    signum = 0

    def __init__(self, spans, warmup: int, seconds=None, steps=None, on_open=None):
        self.spans, self.warmup, self.seconds, self.steps = spans, warmup, seconds, steps
        self.on_open = on_open
        self.done_at, self.losses = [], []
        self.nonfinite = self.enqueued = 0
        self._in_flight = None

    def _finish(self, metrics) -> None:
        with self.spans.span("block"):
            loss = float(metrics["loss"])
        self.done_at.append(time.monotonic())
        self.losses.append(loss)
        self.nonfinite += int(metrics["nonfinite"]) or (not math.isfinite(loss))
        if self.on_open is not None and len(self.done_at) == self.warmup:
            self.on_open()

    def hook(self, step, metrics) -> None:
        self.enqueued += 1
        if self._in_flight is not None:
            self._finish(self._in_flight)
        self._in_flight = metrics

    def close(self) -> None:
        if self._in_flight is not None:
            self._finish(self._in_flight)
            self._in_flight = None

    @property
    def t_window(self):
        return self.done_at[self.warmup - 1] if len(self.done_at) >= self.warmup else None

    @property
    def should_stop(self) -> bool:
        if self.steps is not None:
            return self.enqueued >= self.steps
        t = self.t_window
        return t is not None and time.monotonic() - t >= self.seconds


def run(run: harness.Run) -> dict:
    import jax

    from orion_tpu.parallel.mesh import MeshConfig
    from orion_tpu.training.data import DataLoader, SyntheticDataset
    from orion_tpu.training.trainer import TrainConfig, Trainer

    job = run.sized(run.workload["job"])
    compiles = harness.CompileCounter(run.t0)
    compiles.phase("start")
    spans = harness.Spans()
    seq_len, batch_size = job["seq_len"], job["batch_size"]
    model = harness.model_config(run, max_seq_len=seq_len, remat=True,
                                 remat_skip=job["remat_skip"], backend=job["backend"])
    cfg = TrainConfig(
        model=model, steps=10**9, batch_size=batch_size, seq_len=seq_len,
        optimizer=job["optimizer"], mu_dtype=None, lr=job["lr"],
        warmup_steps=job["warmup_steps"], schedule=job["schedule"],
        mesh=MeshConfig(**job["mesh"]), log_every=10**9,
        param_storage=job["param_storage"], seed=0,
    )
    trainer = Trainer(cfg)
    trainer.state = trainer.state.replace(params=seeded_params(trainer, run.seed))
    jax.block_until_ready(trainer.state)
    compiles.phase("init")
    dataset = SyntheticDataset(model.vocab_size, seq_len)
    check = check_forward(trainer, dataset, run.seed)
    harness.note(check=check)
    compiles.phase("check")

    loader = DataLoader(dataset, batch_size, seed=run.seed,
                        sharding=trainer.batch_shd, prefetch=2)
    batches = TimedLoader(loader, spans)
    chips = run.cell["chips"] if not run.rehearse else 1
    tokens_per_step = batch_size * seq_len
    warmup = job["warmup_steps_run"]

    def window_opens():
        compiles.phase("warmup")
        compiles.mark()

    clock = StepClock(spans, warmup, seconds=run.seconds, on_open=window_opens)
    try:
        trainer.train(batches, hook=clock.hook, preempt=clock)
        clock.close()
        t_window, t_end = clock.t_window, clock.done_at[-1]
        memory = harness.memory_stats()
        in_window = compiles.since_mark()

        xplane = None
        if run.trace:
            from readers import xplane as xp

            traced = StepClock(spans, 0, steps=2)
            logdir = run.scratch_dir("profile")
            jax.profiler.start_trace(logdir)
            try:
                with spans.span("traced"):
                    trainer.train(batches, hook=traced.hook, preempt=traced)
                    traced.close()
            finally:
                jax.profiler.stop_trace()
            clock.nonfinite += traced.nonfinite
            xplane = xp.load_newest(logdir)
    finally:
        loader.close()

    steps = len(clock.done_at) - warmup
    losses = clock.losses[warmup:]
    window_s = t_end - t_window
    setup_seconds = t_window - run.t0
    tok_s = steps * tokens_per_step / window_s
    skip_now = trainer.model.cfg.remat_skip
    correct = (check["ok"] and clock.nonfinite == 0 and skip_now == job["remat_skip"]
               and in_window["programs"] == 0)
    harness.note(
        steps=steps, window_s=window_s, step_ms_mean=1000 * window_s / steps,
        tokens_per_s_per_chip=tok_s / chips, setup_seconds=setup_seconds,
        loss_first=losses[0], loss_last=losses[-1], nonfinite=clock.nonfinite,
        remat_skip=skip_now, compiled_in_window=in_window,
        compiled_total=compiles.compiles, cache_hits=compiles.hits,
        compile_s=compiles.compile_s, phases=compiles.phases,
        memory_stats=memory,
    )
    values = {
        "tokens_per_s_per_chip": tok_s / chips,
        "setup_seconds": setup_seconds,
        "step_ms_mean": 1000 * window_s / steps,
        "flops_per_token_6n": 6.0 * active_params(model, trainer.state.params),
    }
    return {
        "correct": correct, "attempted": steps, "failed": clock.nonfinite,
        "values": values, "spans": spans.within(t_window, t_end),
        "window_s": window_s, "memory": memory, "xplane": xplane,
        "device_kind": run.device["kind"], "rehearse": run.rehearse,
        "annotations": ["loader", "block"],
    }
