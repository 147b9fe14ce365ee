"""``kinds/serve_ref_tied.py`` judged by TWO limits, for a model whose LARGEST
gap cannot tell one precision from the next.

``serve_ref.py`` judges ``max_gap`` alone: over every position of the checked
answers, the reference's largest logit less its logit of the served id. Where
the logits are narrow and a deep mixture routes chaotically under rounding,
that largest gap saturates (a wrong id reads about what any id reads), so no
limit on it lies between a sound run and the reference computed one precision
down. What does separate them is how OFTEN the served id is the reference's
own choice: ``reference_choice_share``, which ``serve_ref.py`` already counts
over the same positions. This kind runs that code (a private instance of
``serve_ref_tied.py``, as it does with ``serve_ref.py``) and differs in three
things, all in the check:

1. **correct** also needs ``reference_choice_share >=
   reference.served_choice_share_floor``, over all the checked positions.
   ``served_gap_tolerance`` stays, as the accepted kind judges it; the
   configuration's file says with its readings what each limit refuses;
2. **the lowered reading** (traced run) is over ALL the checked requests, the
   same served ids, and is judged by both limits as the run itself is:
   ``refused`` says whether it came out not correct, ``refused_by`` which
   limit said so. ``serve_ref.py``'s own reading over the first two requests
   is not taken;
3. **the swapped reading** (traced run): each checked prompt followed by the
   NEXT checked request's answer, through the float32 reference: what answers
   that do not follow the model read, under both limits. It decides nothing.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os

import harness

FLOOR = "served_choice_share_floor"


def private_tied_kind():
    path = os.path.join(harness.HERE, "kinds", "serve_ref_tied.py")
    spec = importlib.util.spec_from_file_location("benchmark_kinds_serve_ref_tied_private", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def judged(out: dict, ref: dict) -> list:
    """The limits a reading of the check is over, by name."""
    by = []
    if not out["max_gap"] <= ref["served_gap_tolerance"]:
        by.append("served_gap_tolerance")
    if not out["reference_choice_share"] >= ref[FLOOR]:
        by.append(FLOOR)
    return by


def beside(run: harness.Run, trace: bool = False, **changes) -> harness.Run:
    """``run`` with keys of its configuration's ``reference`` replaced (None
    takes a key away): a reading beside the run's own, untraced."""
    ref = {**run.config["reference"], **changes}
    ref = {k: v for k, v in ref.items() if v is not None}
    return dataclasses.replace(run, trace=trace, config={**run.config, "reference": ref})


def two_limit_check(make_check, pick, run: harness.Run, keep: dict):
    """``serve_ref.py``'s ``make_check`` and ``pick``, judged as the module's
    docstring says."""
    ref = run.config["reference"]
    lowered = ref.get("lowered")
    # the run's own check keeps ``run.trace`` (it lowers the boundary
    # programs' text for the scoped operations) but not serve_ref's reading
    # of two requests
    check = make_check(beside(run, run.trace, lowered=None), keep)

    def brief(out: dict) -> dict:
        by = judged(out, ref)
        return {"requests": out["requests"], "max_gap": out["max_gap"],
                "reference_choice_share": out["reference_choice_share"],
                "refused": bool(by), "refused_by": by}

    def check_served(params, cfg, served, length: int) -> dict:
        out = check(params, cfg, served, length)
        out[FLOOR] = ref[FLOOR]
        out["ok"] = bool(out["ok"]) and not judged(out, ref)
        if not (run.trace and out["requests"]):
            return out
        if lowered:
            constants = {**ref.get("constants", {}), "matmul_dtype": lowered}
            low = make_check(beside(run, lowered=None, constants=constants), {})
            out["lowered"] = {"matmul_dtype": lowered,
                              **brief(low(params, cfg, served, length))}
        chosen = [served[i] for i in pick(served, ref)]
        swapped = [(p, chosen[(i + 1) % len(chosen)][1]) for i, (p, _) in enumerate(chosen)]
        every = make_check(beside(run, lowered=None, check_requests=len(swapped)), {})
        out["swapped"] = brief(every(params, cfg, swapped, length))
        return out

    return check_served


def run(run: harness.Run) -> dict:
    tied = private_tied_kind()
    private_base = tied.private_serve_ref_kind

    def base_with_two_limits():
        base = private_base()
        make_check = base.make_check
        base.make_check = lambda run, keep: two_limit_check(make_check, base.pick, run, keep)
        return base

    tied.private_serve_ref_kind = base_with_two_limits
    return tied.run(run)
