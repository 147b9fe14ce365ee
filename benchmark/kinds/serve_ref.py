"""A serving cell whose plain reference is named by its configuration.

``kinds/serve.py`` runs the server, the traffic, the window and the capture;
this kind runs the same code (a private instance of that module) and differs
in four things, as ``kinds/train_ref.py`` does from ``kinds/train.py``:

1. **the reference** is the module the configuration's file names under
   ``"reference"`` (``module``: a file in ``reference/``; ``spec``: reference
   key -> key of the file's ``model`` section, so ``--rehearse`` sizes carry
   over; ``constants``: further reference keys), not ``plain_lm``;
2. **the check** is ``serve.py``'s, on what the ``Server`` RETURNED inside the
   window under load: the served ids teacher-forced through the reference's
   full forward (prompt + answer, one pass), and at every position of the
   answer the gap ``max(reference logits) - reference logit[served id]``
   under ``reference.served_gap_tolerance``. It runs after the server has
   stopped and its slot state is freed, a layer at a time (each layer's
   weights are cast up to float32 inside its own program, so one layer's
   float32 copy exists at a time) and the head in ``reference.head_blocks``
   blocks of the vocabulary (the whole ``[T, V]`` float32 logits are never
   held). Of the requests that ended ``ok`` inside the window it checks
   ``reference.check_requests``, chosen to include the
   ``reference.check_long_prompts`` longest prompts (more than one
   ``prefill_chunk`` piece each, which the result says) and the
   ``reference.check_long_answers`` longest answers, the rest spread evenly;
3. **the lowered reading** (traced run): the first two checked requests once
   more through the reference with every matmul operand rounded to
   ``reference.lowered`` (the nearest precision below the one the
   configuration states). Printed with whether the tolerance refuses it; it
   decides nothing;
4. **scoped operations and the capture's live rows** (traced run): the
   capture is read a second time keeping, for each device operation, the
   name stack (``jax.named_scope`` names) that the compiled boundary
   programs' HLO text gives its instruction (``evidence["scoped_ops"]``, for
   ``readers/scope_share.py``; the serve capture carries no name stack of
   its own; they are lowered again after the run, from the persistent
   cache), and the server's counters over the profile phase give the mean
   number of slots that emitted at a boundary (``evidence["capture"]``, with
   the model's widths: what a kernel's byte count needs).

The tolerance and its readings are in the configuration's file
(``reference.served_gap_tolerance``, ``reference.why``): read on the chip.
"""

from __future__ import annotations

import importlib.util
import math
import os
import re
import time

import harness


def private_serve_kind():
    """``kinds/serve.py`` as a module of this kind's own, so that replacing
    its check touches no other user of ``harness.load_module``."""
    path = os.path.join(harness.HERE, "kinds", "serve.py")
    spec = importlib.util.spec_from_file_location("benchmark_kinds_serve_private", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_spec(run: harness.Run) -> dict:
    ref = run.config["reference"]
    sizes = run.sized(run.config["model"])
    spec = {key: sizes[field] for key, field in ref["spec"].items()}
    spec.update(ref.get("constants", {}))
    return spec


def pick(served: list, ref: dict) -> list:
    """Indices into ``served`` of the requests to check."""
    n = min(len(served), ref["check_requests"])
    by_prompt = sorted(range(len(served)), key=lambda i: -len(served[i][0]))
    by_answer = sorted(range(len(served)), key=lambda i: -len(served[i][1]))
    chosen = by_prompt[:ref["check_long_prompts"]] + by_answer[:ref["check_long_answers"]]
    chosen += [i * len(served) // max(n, 1) for i in range(n)]
    out = []
    for i in chosen:
        if i not in out and len(out) < n:
            out.append(i)
    return out


def make_check(run: harness.Run, keep: dict):
    """``check_served(params, cfg, served, length)`` for this run's reference."""
    ref = run.config["reference"]
    reference = harness.load_module("reference", ref["module"])
    spec = reference_spec(run)
    piece = run.sized(run.workload["server"])["prefill_chunk"]

    def gaps_of(spec, params, served, length):
        """Per request: the gap at every position of its answer, and the
        reference's largest logit there."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        kinds = spec["layer_types"]
        embed = jax.jit(lambda p, toks: reference.embed(spec, p, toks))
        layers = {kind: jax.jit(lambda blk, x, kind=kind: reference.block(spec, kind, blk, x))
                  for kind in set(kinds)}
        vocab = params["params"]["lm_head_kernel"].shape[1]
        blocks = ref["head_blocks"] if vocab % ref["head_blocks"] == 0 else 1
        width = vocab // blocks

        @jax.jit
        def head(p, x, toks, start):
            block = reference.logits(spec, p, x, columns=(start, width))[0, :-1]
            ids = toks[0, 1:] - start  # row j predicts token j + 1
            mine = jnp.take_along_axis(block, jnp.clip(ids, 0, width - 1)[:, None], axis=-1)[:, 0]
            return block.max(-1), jnp.where((ids >= 0) & (ids < width), mine, -jnp.inf)

        out = []
        with jax.default_matmul_precision("highest"):
            for prompt, answer in served:
                toks = np.zeros((1, length), np.int32)
                n, m = len(prompt), len(answer)
                toks[0, :n], toks[0, n:n + m] = prompt, answer
                toks = jnp.asarray(toks)
                x = embed(params, toks)
                for i, kind in enumerate(kinds):
                    x = layers[kind](params["params"][f"block_{i}"], x)
                top, mine = None, None
                for b in range(blocks):
                    t, g = head(params, x, toks, b * width)
                    top = t if top is None else jnp.maximum(top, t)
                    mine = g if mine is None else jnp.maximum(mine, g)
                rows = slice(n - 1, n + m - 1)
                out.append((np.asarray(top - mine)[rows], float(np.asarray(top)[rows].max())))
        return out

    def check_served(params, cfg, served, length: int) -> dict:
        chosen = [served[i] for i in pick(served, ref)]
        t0 = time.monotonic()
        read = gaps_of(spec, params, chosen, length)
        worst = max((float(g.max()) for g, _ in read), default=math.inf)
        positions = sum(len(g) for g, _ in read)
        agree = sum(int((g == 0).sum()) for g, _ in read)
        tolerance = ref["served_gap_tolerance"]
        out = {
            "requests": len(chosen), "positions": positions, "max_gap": worst,
            "max_gap_by_request": [float(g.max()) for g, _ in read],
            "prompt_lens": [len(p) for p, _ in chosen],
            "answer_lens": [len(a) for _, a in chosen],
            "prompts_of_several_pieces": sum(len(p) > piece for p, _ in chosen),
            "reference_choice_share": agree / max(positions, 1),
            "max_reference_logit": max((top for _, top in read), default=0.0),
            "tolerance": tolerance, "seconds": time.monotonic() - t0,
            "ok": bool(chosen) and math.isfinite(worst) and worst <= tolerance,
        }
        if run.trace and ref.get("lowered"):
            low = gaps_of({**spec, "matmul_dtype": ref["lowered"]}, params, chosen[:2], length)
            low_worst = max(float(g.max()) for g, _ in low)
            out["lowered"] = {"matmul_dtype": ref["lowered"], "requests": len(low),
                              "max_gap": low_worst, "refused": not low_worst <= tolerance}
        keep["model"] = {"heads": cfg.gdn_value_heads, "key_dim": cfg.gdn_key_dim,
                         "value_dim": cfg.gdn_value_dim}
        if run.trace:
            try:
                keep["hlo"] = boundary_programs_text(run, cfg, params)
            except Exception as e:  # no text to be had: say so, guess nothing
                harness.note(hlo_text={"why": f"{type(e).__name__}: {e}"[:300]})
        return out

    return check_served


def counted_capture(base, keep: dict):
    """``capture_profile`` that also keeps the server's counters over the
    profile phase."""
    inner = base.capture_profile

    def capture_profile(server, *args):
        before = server.metrics.counters_flat()
        inner(server, *args)
        after = server.metrics.counters_flat()
        keep["counters"] = {k: after[k] - before.get(k, 0) for k in after
                            if isinstance(after[k], (int, float))}

    return capture_profile


def boundary_programs_text(run: harness.Run, cfg, params) -> str:
    """The compiled text of the boundary programs the server ran, lowered
    again from shapes alone after the server is gone (the persistent cache
    holds them: nothing compiles anew): the engine's own choice between the
    programs that hold the carry once and those that return a new one, at
    its slot count, chunk, aligned piece width and widest staged prompt."""
    import jax
    import jax.numpy as jnp

    from orion_tpu import generate as gen
    from orion_tpu.models.transformer import TransformerLM, init_decode_state
    from orion_tpu.ops.dispatch import resolve, resolve_chunk
    from orion_tpu.serving.batching import fits_once_only, parse_buckets
    from traffic import lengths

    sv = run.sized(run.workload["server"])
    traffic = run.sized(run.workload["traffic"])
    slots, chunk = sv["slots"], sv["chunk"]
    align = resolve_chunk(cfg.chunk, cfg.max_seq_len, resolve(cfg.backend))
    piece = -(-sv["prefill_chunk"] // align) * align
    longest = max(lengths.population(traffic["prompt_len"], traffic["population"]))
    width = gen.bucket_for(longest, parse_buckets(sv["prefill_buckets"], cfg.max_seq_len))
    shape = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    vec = lambda dt: jax.ShapeDtypeStruct((slots,), dt)  # noqa: E731
    model = TransformerLM(cfg)
    states = jax.eval_shape(lambda: init_decode_state(cfg, slots))
    carry = (vec(jnp.int32), states, vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_))
    rngs = jax.ShapeDtypeStruct((slots, 2), jnp.uint32)
    pbuf = jax.ShapeDtypeStruct((slots, width), jnp.int32)
    params = jax.tree.map(shape, params)
    sample = gen.SampleConfig(temperature=0.0)
    ints, flags = vec(jnp.int32), vec(jnp.bool_)
    if fits_once_only(carry, params, jax.devices()[0]):
        lowered = [
            gen._decode_scan_donated_jit.lower(
                model, params, carry, rngs, flags, ints, chunk, sample),
            gen._prefill_piece_donated_jit.lower(
                model, params, carry, rngs, pbuf, ints, ints,
                jax.ShapeDtypeStruct((), jnp.int32), piece, sample),
        ]
    else:
        lowered = [
            gen._decode_batched_prefill_chunk_jit.lower(
                model, params, carry, rngs, flags, pbuf, ints, ints, ints, chunk, piece, sample),
            gen._decode_batched_chunk_jit.lower(model, params, carry, rngs, flags, chunk, sample),
        ]
    return "\n".join(low.compile().as_text() for low in lowered)


OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\S+)\s.*?op_name="([^"]+)"', re.M)
LAYOUT = re.compile(r"\{[^{}]*\}")


def key_of(line: str):
    """``%fusion.12 = bf16[8,128]{1,0} fusion(...)`` -> (``fusion.12``,
    ``bf16[8,128]``): an instruction's name alone repeats across programs."""
    head, _, rest = line.partition(" = ")
    return head.strip().replace("ROOT ", "").lstrip("%"), LAYOUT.sub("", rest.split(" ", 1)[0])


def scoped_ops(logdir: str, hlo_text: str) -> dict:
    """Operations of the first chip as [name stack, start ns, duration ns],
    the stack looked up in ``hlo_text`` by instruction name and result type."""
    from jax.profiler import ProfileData

    from readers import xplane as xp

    path = xp.newest(logdir)
    if not path or not hlo_text:
        return {"source": None, "events": []}
    stacks = {(name, LAYOUT.sub("", kind)): stack
              for name, kind, stack in OP_NAME.findall(hlo_text)}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(xp.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != xp.OPS_LINE:
                continue
            return {"source": "hlo_text", "events": [
                [stacks.get(key_of(e.name), ""), float(e.start_ns), float(e.duration_ns)]
                for e in line.events]}
    return {"source": None, "events": []}


def run(run: harness.Run) -> dict:
    base = private_serve_kind()
    keep: dict = {}
    base.check_served = make_check(run, keep)
    base.CHECK_REQUESTS = 10 ** 9  # every ok request reaches the check, which picks
    base.capture_profile = counted_capture(base, keep)

    evidence = base.run(run)

    if run.trace:
        scoped = scoped_ops(os.path.join(run.root, ".bench_scratch", "profile"),
                            keep.get("hlo", ""))
        named = sum(1 for e in scoped["events"] if e[0])
        harness.note(scoped_ops={"source": scoped["source"], "events": len(scoped["events"]),
                                 "with_name_stack": named})
        evidence["scoped_ops"] = scoped
        counters = keep.get("counters", {})
        if counters.get("chunks"):
            evidence["capture"] = {
                "emitting_rows_per_boundary":
                    counters.get("slot_steps_emitting", 0) / counters["chunks"],
                "boundaries": counters["chunks"], **keep.get("model", {}),
            }
            harness.note(capture=evidence["capture"])
    return evidence
