"""Host spans in a ``jax.profiler`` capture (SURVEY.md A1).

The one place the package constructs profiler annotations and named
scopes. A capture is
taken elsewhere — ``Server.arm_profile`` / ``/profilez`` for serving, the
benchmark's train kind for ``Trainer.train`` — and read by
``benchmark/readers/xplane.py``; these names are what such a capture
shows on its host lines, on the same clock as the device's operations,
so an idle gap of the device can be given to what the host was doing.
Outside a capture an annotation costs a check of one flag.
"""

from __future__ import annotations

import functools

import jax


def annotate(name: str):
    """``with annotate("serve.admit"):`` — a named host span."""
    return jax.profiler.TraceAnnotation(name)


def scope(name: str):
    """``with scope("gated_delta"):`` — a ``jax.named_scope``: the name
    goes on the name stack of every XLA operation traced inside, forward
    and backward, where the benchmark's scope reader finds it
    (``benchmark/readers/scope_share.py``). Costs nothing at run time."""
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator: run a method under ``scope(name)`` (a mixer's serving
    methods, so the scope reader finds them as it finds ``__call__``)."""

    def decorate(method):
        @functools.wraps(method)
        def wrapper(*args, **kwargs):
            with scope(name):
                return method(*args, **kwargs)

        return wrapper

    return decorate


def annotated_steps(name: str, steps):
    """``for step in annotated_steps("train", range(a, b)):`` — each
    iteration's body runs inside a ``StepTraceAnnotation`` (the profiler
    groups device work by these); it closes when the loop asks for the next
    step or leaves."""
    for step in steps:
        with jax.profiler.StepTraceAnnotation(name, step_num=step):
            yield step


__all__ = ["annotate", "annotated_steps", "scope", "scoped"]
