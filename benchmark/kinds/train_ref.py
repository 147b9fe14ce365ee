"""A training cell whose plain reference is named by its configuration.

``kinds/train.py`` runs the loop, the window and the traced steps; this kind
runs the same code (a private instance of that module: its ``seeded_params``,
``TimedLoader``, ``StepClock`` and ``run``) and differs in five things:

1. **the reference** is the module the configuration's file names under
   ``"reference"`` (``module``: a file in ``reference/``; ``spec``: reference
   key -> key of the file's ``model`` section, so ``--rehearse`` sizes carry
   over; ``constants``: further reference keys), not ``plain_lm``;
2. **the check** (before the window, the initial weights) runs the program
   on ONE BATCH OF THE TIMED SHAPE — the cell's batch size at its length, so
   every branch the step's forward takes for that shape is what is compared
   (a block of batch rows at a time, the routed rows' buffer at its full
   size) — and the reference on the same tokens one row at a time. It
   compares the program's eval loss (its fused loss over the whole batch)
   with the reference's mean loss, AND the program's logits with the
   reference's at every position of every row: ``logit_stats`` of
   ``|difference|`` over each row's ``[T, V]`` — its mean, two high
   quantiles and its maximum; over rows the mean is averaged and the others
   take the worst row. ``reference.logit_tolerance`` bounds the statistics
   it names; the maximum is printed and bounds nothing where it is not
   named (it sits on the few tokens whose last router choice swaps under
   the compute type, and has a tail). The tolerances and their reasons are
   in the configuration's file (``reference.loss_tolerance``,
   ``reference.logit_tolerance``, ``reference.why``): they were read for
   that model on the chip. A traced run also computes the reference's
   first row once more with every matmul operand rounded to
   ``reference.lowered`` (the nearest precision below the one the
   configuration states) and prints how far THAT lands from the float32
   reference, by the same statistics: the reading a tolerance must refuse
   (one row's quantiles are at most the worst of all rows'). It decides
   nothing;
3. **flops_per_token_6n** counts an expert stack at ``top_k / router width``
   of its size (each held expert sees that share of the tokens) and leaves
   out an input embedding that has a head of its own (a lookup has no FLOPs);
4. **counters**: every ``moe_*`` step metric, summed over the window's steps
   (``evidence["counters"]``), ``expert_load_max_over_mean`` among the
   values, and ``correct`` also asks that no routed row was dropped;
5. **scoped operations** (traced run): the capture is read a second time
   keeping, for each operation of the first chip, the name stack XLA carries
   for it (``jax.named_scope`` names, forward and backward) — from the
   event's own statistics where the capture has them, else from the compiled
   step's HLO text (instruction name -> ``op_name``) — as
   ``evidence["scoped_ops"]`` for ``readers/scope_share.py``. Where neither
   can be had the evidence says so and the scope metrics are left out.

NOT checked against any reference, as in ``kinds/train.py``: the backward
pass, the optimizer and the stochastic-rounding apply (the CPU tests check
the backward against ``jax.grad`` of the reference at small sizes).
"""

from __future__ import annotations

import gc
import importlib.util
import math
import os
import re

import harness


def private_train_kind():
    """``kinds/train.py`` as a module of this kind's own, so that replacing
    its check, its parameter count and its step clock touches no other user
    of ``harness.load_module("kinds", "train")``."""
    path = os.path.join(harness.HERE, "kinds", "train.py")
    spec = importlib.util.spec_from_file_location("benchmark_kinds_train_private", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_spec(run: harness.Run) -> dict:
    ref = run.config["reference"]
    sizes = run.sized(run.config["model"])
    spec = {key: sizes[field] for key, field in ref["spec"].items()}
    spec.update(ref.get("constants", {}))
    return spec


def active_params(cfg, params) -> float:
    """Parameters that do matmul work per token."""
    import jax

    paths = [(jax.tree_util.keystr(p), x) for p, x in jax.tree_util.tree_leaves_with_path(params)]
    own_head = any("lm_head" in path for path, _ in paths)
    routed = cfg.moe_top_k / (cfg.moe_router_width or cfg.n_experts) if cfg.n_experts else 1.0
    total = 0.0
    for path, x in paths:
        if own_head and "'embed'" in path:
            continue
        total += x.size * (routed if "experts_" in path else 1.0)
    return float(total)


QUANTILES = {"p999": 0.999, "p9999": 0.9999}


def logit_stats(got, want):
    """``|got - want|`` over one row's ``[T, V]`` logits: mean, the
    ``QUANTILES`` and the maximum, as a dict of scalars."""
    import jax.numpy as jnp

    diff = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).reshape(-1)
    qs = jnp.quantile(diff, jnp.asarray(list(QUANTILES.values()), jnp.float32))
    return {"mean": diff.mean(), **dict(zip(QUANTILES, qs)), "max": diff.max()}


def over_rows(rows: list) -> dict:
    """Per-row ``logit_stats`` -> the batch's: the mean of the means, the
    worst row's for everything else."""
    return {k: (sum(r[k] for r in rows) / len(rows) if k == "mean"
                else max(r[k] for r in rows)) for k in rows[0]}


def make_check(run: harness.Run, keep: dict):
    """``check_forward(trainer, dataset, seed)`` for this run's reference."""
    ref = run.config["reference"]
    reference = harness.load_module("reference", ref["module"])
    spec = reference_spec(run)

    def check_forward(trainer, dataset, seed: int) -> dict:
        import jax
        import jax.numpy as jnp

        keep["trainer"] = trainer
        # one batch of the step's own shape, rows all different
        batch = jnp.asarray(dataset.batch(seed, 10**6, trainer.cfg.batch_size))
        params = trainer.state.params
        # a loaded program keeps its scratch reserved: each of the two big ones
        # is unloaded before the next claims the chip
        system_loss = trainer.evaluate(iter([batch]), n_batches=1)["eval_loss"]
        jax.clear_caches()
        system_logits = jax.jit(trainer.model.apply)(params, batch[:, :-1])
        jax.clear_caches()
        stats = jax.jit(logit_stats)

        def plain(spec):
            def fn(p, b):
                logits = reference.forward(spec, p, b[:, :-1])
                logp = jax.nn.log_softmax(logits, axis=-1)
                return logits[0], -jnp.take_along_axis(logp, b[:, 1:, None], axis=-1).mean()

            fn = jax.jit(fn)

            def one_row(row):
                with jax.default_matmul_precision("highest"):
                    return fn(params, batch[row:row + 1])

            return one_row

        plain_row = plain(spec)
        rows, ref_losses, biggest = [], [], 0.0
        for row in range(batch.shape[0]):
            ref_logits, ref_loss = plain_row(row)
            rows.append({k: float(v) for k, v in stats(system_logits[row], ref_logits).items()})
            ref_losses.append(float(ref_loss))
            biggest = max(biggest, float(jnp.abs(ref_logits).max()))
            if row == 0 and run.trace and ref.get("lowered"):
                low_logits, low_loss = plain({**spec, "matmul_dtype": ref["lowered"]})(0)
                lowered = {
                    "matmul_dtype": ref["lowered"], "rows": 1,
                    "delta": abs(float(low_loss) - float(ref_loss)),
                    "logit_diff": {k: float(v) for k, v in stats(low_logits, ref_logits).items()},
                }
                del low_logits
            del ref_logits
        ref_loss = sum(ref_losses) / len(ref_losses)
        loss_delta = abs(system_loss - ref_loss)
        diff = over_rows(rows)
        out = {
            "rows": len(rows), "system_loss": system_loss, "reference_loss": ref_loss,
            "delta": loss_delta, "tolerance": ref["loss_tolerance"],
            "logit_diff": diff, "logit_tolerance": ref["logit_tolerance"],
            "logit_diff_by_row": rows, "logit_abs_max": biggest,
            "ok": (math.isfinite(system_loss) and loss_delta <= ref["loss_tolerance"]
                   and all(math.isfinite(diff[k]) and diff[k] <= limit
                           for k, limit in ref["logit_tolerance"].items())),
        }
        if run.trace and ref.get("lowered"):
            out["lowered"] = lowered
        del batch, system_logits
        gc.collect()
        # unload the check programs before the train step claims the chip
        jax.clear_caches()
        return out

    return check_forward


def counting_clock(base):
    """``StepClock`` that also keeps each completed step's ``moe_*`` metrics."""

    class CountingClock(base.StepClock):
        made = []

        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self.counted = []
            CountingClock.made.append(self)

        def _finish(self, metrics) -> None:
            super()._finish(metrics)
            self.counted.append(
                {k: int(v) for k, v in metrics.items() if k.startswith("moe_")})

    return CountingClock


OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]+)"', re.M)


def instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def scoped_ops(logdir: str, hlo_text) -> dict:
    """Operations of the first chip as [name stack, start ns, duration ns].

    ``hlo_text``: a callable giving the compiled step's HLO text, asked only
    when the capture's events carry no name stack themselves."""
    from jax.profiler import ProfileData

    from readers import xplane as xp

    path = xp.newest(logdir)
    if not path:
        return {"source": None, "events": []}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(xp.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != xp.OPS_LINE:
                continue
            raw = [(e.name, float(e.start_ns), float(e.duration_ns),
                    " ".join(str(v) for _, v in e.stats if isinstance(v, str)))
                   for e in line.events]
            carried = sum(1 for r in raw if "op_name=" in r[0] or "jit(" in r[3])
            if carried > len(raw) // 2:
                return {"source": "event", "events": [
                    [name + " " + stats, start, dur] for name, start, dur, stats in raw]}
            try:
                stacks = dict(OP_NAME.findall(hlo_text()))
            except Exception as e:  # no text to be had: say so, guess nothing
                return {"source": None, "events": [], "why": f"{type(e).__name__}: {e}"[:200]}
            return {"source": "hlo_text", "events": [
                [stacks.get(instruction(name), ""), start, dur]
                for name, start, dur, _ in raw]}
    return {"source": None, "events": []}


def run(run: harness.Run) -> dict:
    base = private_train_kind()
    keep: dict = {}
    base.check_forward = make_check(run, keep)
    base.active_params = active_params
    base.StepClock = clock_cls = counting_clock(base)

    evidence = base.run(run)

    window = clock_cls.made[0]
    steps = window.counted[window.warmup:]
    counters = {k: sum(s[k] for s in steps) for k in (steps[0] if steps else {})}
    held = reference_spec(run).get("experts_held")
    if held and counters.get("moe_rows_held"):
        evidence["values"]["expert_load_max_over_mean"] = (
            counters["moe_rows_max_expert"] / (counters["moe_rows_held"] / held))
    evidence["counters"] = counters
    evidence["correct"] = bool(evidence["correct"] and counters.get("moe_overflow", 0) == 0)
    harness.note(counters=counters)

    if run.trace:
        trainer = keep["trainer"]

        def hlo_text():
            import jax

            batch = jax.ShapeDtypeStruct(
                (trainer.cfg.batch_size, trainer.cfg.seq_len + 1), "int32",
                sharding=trainer.batch_shd)
            return trainer._step_fn.lower(trainer.state, batch).compile().as_text()

        scoped = scoped_ops(os.path.join(run.root, ".bench_scratch", "profile"), hlo_text)
        named = sum(1 for e in scoped["events"] if e[0])
        harness.note(scoped_ops={"source": scoped["source"], "events": len(scoped["events"]),
                                 "with_name_stack": named, "why": scoped.get("why")})
        evidence["scoped_ops"] = scoped
    return evidence
