"""Host spans in a ``jax.profiler`` capture (SURVEY.md A1), and jax's
compilations as events of the program's own trace.

The one place the package touches ``jax.profiler`` and ``jax.monitoring``:
it constructs profiler annotations and named scopes, and it listens to
jax's compile events. A capture is
taken elsewhere — ``Server.arm_profile`` / ``/profilez`` for serving, the
benchmark's train kind for ``Trainer.train`` — and read by
``benchmark/readers/xplane.py``; these names are what such a capture
shows on its host lines, on the same clock as the device's operations,
so an idle gap of the device can be given to what the host was doing.
Outside a capture an annotation costs a check of one flag.

Two categories of ``obs/trace.py``'s event model start here. ``compile``:
importing this module registers ONE set of ``jax.monitoring`` listeners a
process (:func:`listen_for_compiles`), which turn each stage jax reports
for a program it builds into ``compile.trace`` / ``compile.lower`` /
``compile.backend`` with the function's name (``obs.trace.compile_event``).
A listener runs only when jax compiles: the steady state pays nothing.
``setup``: the spans themselves are written where the work is
(``Server.__init__``, ``Trainer.__init__``, the CLIs); while a capture
runs they are annotations too, through :func:`annotate`: the factory a
``Server`` hands its tracer for the length of ``arm_profile``'s capture
and a ``Trainer`` for the length of ``train()``, whose ``step`` spans are
so the host lines of any capture that runs meanwhile.
"""

from __future__ import annotations

import functools
import threading
import time

import jax
import jax.monitoring

from orion_tpu.obs import trace as _trace

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_STAGES = {
    _TRACE: "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
# per thread: how many traces are open (jax traces the jitted functions a
# program calls inside the program's own trace: only the outermost is an
# event, the others are time inside it), and what jax said of the
# persistent cache since the last backend stage ended (the hit and its
# retrieval time arrive INSIDE that stage, before its own duration does)
_thread = threading.local()
_listening = False


def _on_stage_start(event: str, _value, **_) -> None:
    if event == _TRACE:
        _thread.traces = getattr(_thread, "traces", 0) + 1


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT:
        _thread.hit = True


def _on_duration(event: str, seconds: float, fun_name: str = "", **_) -> None:
    if event == _CACHE_LOAD:
        _thread.load_ms = seconds * 1e3
        return
    name = _STAGES.get(event)
    if name is None:
        return
    if event == _TRACE:
        _thread.traces = open_traces = max(getattr(_thread, "traces", 1) - 1, 0)
        if open_traces:
            return
    args = {"fun_name": str(fun_name)}
    if name == "compile.backend":
        args["source"] = "cache" if getattr(_thread, "hit", False) else "compiled"
        load_ms = getattr(_thread, "load_ms", None)
        if load_ms is not None:
            args["cache_load_ms"] = round(load_ms, 3)
        _thread.hit, _thread.load_ms = False, None
    # jax stamps wall time; the tracer's clock is time.monotonic, so the
    # start is this arrival less the duration
    _trace.compile_event(name, time.monotonic() - seconds, seconds, **args)


def listen_for_compiles() -> None:
    """Register the listeners, once a process however often it is called."""
    global _listening
    if _listening:
        return
    _listening = True
    jax.monitoring.register_scalar_listener(_on_stage_start)
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


listen_for_compiles()


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


__all__ = ["annotate", "listen_for_compiles", "scope", "scoped"]
