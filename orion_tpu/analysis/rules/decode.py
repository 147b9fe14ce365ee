"""Decode-path serving rules.

``decode-host-sync`` — a host synchronization (``.block_until_ready()``,
``.item()``, ``float()``, ``np.asarray``, ``jax.device_get``) inside a
per-chunk decode loop stalls the device pipeline once per chunk: the next
chunk's dispatch waits on the readback, turning the chunked serving walk
into lockstep host-device ping-pong — the latency bug the chunked design
exists to avoid. The serving layer has exactly ONE sanctioned sync per
chunk — the all-finite probe (one [slots]-bool vector of the
slot-multiplexed SlotEngine) — and it lives in a designated probe
function (``SlotEngine._probe_bad``), so the rule exempts any code lexically inside
a function whose name contains ``probe``. Everything else syncs once,
after the loop.

The probe exemption is itself budgeted for the continuous-batching
scheduler loop: the per-chunk host sync must stay at ONE probe no matter
how many slots are resident. Two extra shapes are findings —

- two or more probe-function CALLS inside one decode loop body (each is
  a separate device round-trip per chunk), and
- a probe call inside a loop that is itself nested in another loop (the
  per-slot-probe shape: ``for slot in slots: self._probe(slot)`` inside
  the chunk loop syncs slot-count times per chunk).

Since ISSUE 7 the ADMISSION path is covered too: in-scan chunked prefill
makes ``admit()`` an O(1) slot insert (prompt staged into the carry, no
prefill, no readback), so any host sync inside an admission-path
function of ``serving/batching.py`` — one whose name contains ``admit``,
``insert``, or ``stage`` — is a finding even OUTSIDE a loop: admissions
sit on the scheduler's hot path and a per-admit device round-trip is the
head-of-line stall the unified path exists to kill.

ISSUE 11 adds ``prefix`` to the admission markers: the content-addressed
prefix cache's lookup/stage/publish paths in the engine
(``SlotEngine._prefix_lookup`` / ``_stage_prefix`` /
``publish_pending_prefixes``) are admission code — a hit must cost hash +
disk + ONE fused jitted row write, so any host sync in a *prefix*-named
function of ``serving/batching.py`` is the same finding. The store-side
serialization (publish's device_get) lives in
``serving/prefix_store.py`` by design, off the engine's hot path.

ISSUE 13 covers the SPECULATION path the same way: any host sync inside
a ``draft``/``verify``/``spec``-named function of ``serving/batching.py``
is a finding — the accept/reject decision must come from the existing
single per-chunk probe transfer (the accepted counts ride the same
stacked readback as the finite/done flags), never a second readback per
round; a draft pass or verify piece that syncs the host mid-boundary
re-creates exactly the lockstep ping-pong the batched round exists to
avoid. Probe-named functions remain the designated sync point.

Scope: the decode modules only (``orion_tpu/serving/`` and
``generate.py``); host loops elsewhere (eval CLIs, data prep) may sync
freely. Traced code is already covered by ``tracer-host``; this rule is
about HOST loops driving the device.
"""

from __future__ import annotations

import ast
from typing import Iterator, Optional

from orion_tpu.analysis.findings import Finding
from orion_tpu.analysis.lint import ModuleContext, dotted_name

_SYNC_ATTRS = frozenset({"block_until_ready", "item"})
_SYNC_NAMES = frozenset({"float"})
_SYNC_DOTTED = frozenset({
    "np.asarray", "numpy.asarray", "onp.asarray", "jax.device_get",
})


def _is_decode_module(path: str) -> bool:
    return "serving/" in path or path.endswith("generate.py")


_ADMIT_MARKERS = ("admit", "insert", "stage", "prefix")
_SPEC_MARKERS = ("draft", "verify", "spec")


def _inside_marked(node: ast.AST, markers) -> bool:
    """Lexically inside a function whose name carries one of ``markers``."""
    cur = getattr(node, "_orion_parent", None)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)) and any(
            m in cur.name for m in markers
        ):
            return True
        cur = getattr(cur, "_orion_parent", None)
    return False


def _inside_admission(node: ast.AST) -> bool:
    """Lexically inside an admission-path function of the engine (see
    module docstring: names containing admit/insert/stage/prefix)."""
    return _inside_marked(node, _ADMIT_MARKERS)


def _inside_spec(node: ast.AST) -> bool:
    """Lexically inside a speculation-path function of the engine (see
    module docstring: names containing draft/verify/spec)."""
    return _inside_marked(node, _SPEC_MARKERS)


def _inside_probe(node: ast.AST) -> bool:
    cur = getattr(node, "_orion_parent", None)
    while cur is not None:
        if (
            isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef))
            and "probe" in cur.name
        ):
            return True
        cur = getattr(cur, "_orion_parent", None)
    return False


def _is_probe_call(node: ast.Call) -> bool:
    """A call to a probe-named function/method (the designated sync)."""
    f = node.func
    if isinstance(f, ast.Attribute):
        return "probe" in f.attr
    if isinstance(f, ast.Name):
        return "probe" in f.id
    return False


def _sync_label(node: ast.Call) -> Optional[str]:
    """The one place that decides 'is this call a host sync, and how do
    we print it' — shared by the loop and admission passes so the two
    budgets can never disagree on what counts as a sync."""
    name = dotted_name(node.func)
    if name in _SYNC_NAMES or name in _SYNC_DOTTED:
        return f"{name}()"
    if isinstance(node.func, ast.Attribute) and node.func.attr in _SYNC_ATTRS:
        return f".{node.func.attr}()"
    return None


def _innermost_loop(node: ast.AST) -> Optional[ast.AST]:
    cur = getattr(node, "_orion_parent", None)
    while cur is not None:
        if isinstance(cur, (ast.For, ast.While)):
            return cur
        cur = getattr(cur, "_orion_parent", None)
    return None


class DecodeHostSyncRule:
    id = "decode-host-sync"
    title = "host sync inside a per-chunk decode loop"

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        if ctx.is_test or not _is_decode_module(ctx.path):
            return
        seen = set()
        # loop -> probe calls whose INNERMOST loop it is (a nested loop's
        # probes belong to the inner loop, so a chunk loop isn't blamed
        # for its ladder helper's probes twice)
        probes_per_loop: dict = {}
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            for node in ast.walk(loop):
                if not isinstance(node, ast.Call) or id(node) in seen:
                    continue
                if _is_probe_call(node) and _innermost_loop(node) is loop:
                    if not _inside_probe(node):
                        probes_per_loop.setdefault(id(loop), (loop, []))[1].append(node)
                sync = _sync_label(node)
                if sync is None or _inside_probe(node):
                    continue
                seen.add(id(node))
                yield Finding(
                    self.id, ctx.path, node.lineno,
                    f"{sync} inside a decode loop forces a device round-"
                    "trip every chunk; sync once after the loop, or move "
                    "it into the designated probe (a function named "
                    "*probe*, e.g. SlotEngine._probe_bad)",
                )
        # the admission budget: the engine's admit/insert/stage functions
        # are sync-free — O(1) admission must not pay a device round-trip
        # per request (loop or no loop)
        if ctx.path.endswith("serving/batching.py"):
            for node in ast.walk(ctx.tree):
                if not isinstance(node, ast.Call) or id(node) in seen:
                    continue
                sync = _sync_label(node)
                if sync is None or _inside_probe(node):
                    continue
                if _inside_admission(node):
                    seen.add(id(node))
                    yield Finding(
                        self.id, ctx.path, node.lineno,
                        f"{sync} on the admission path (a function named "
                        "*admit*/*insert*/*stage*/*prefix*): admission is "
                        "an O(1) slot insert — stage the prompt (or the "
                        "cached prefix row) into the carry and let the "
                        "unified scan consume it; a per-admit host sync "
                        "re-creates the head-of-line stall (prefix-store "
                        "serialization belongs in serving/prefix_store.py)",
                    )
                elif _inside_spec(node):
                    seen.add(id(node))
                    yield Finding(
                        self.id, ctx.path, node.lineno,
                        f"{sync} on the speculation path (a function "
                        "named *draft*/*verify*/*spec*): the accept/"
                        "reject decision must ride the existing single "
                        "per-chunk probe transfer (the accepted counts "
                        "stack with the finite/done flags) — a second "
                        "readback per speculative round re-creates the "
                        "lockstep host-device ping-pong the batched "
                        "round exists to avoid",
                    )
        # the probe budget: ONE probe sync per chunk loop, slot count
        # notwithstanding (the continuous-batching scheduler contract)
        for loop, calls in probes_per_loop.values():
            if len(calls) > 1:
                yield Finding(
                    self.id, ctx.path, calls[1].lineno,
                    f"{len(calls)} probe calls in one decode loop body — "
                    "each is a separate device round-trip per chunk; fuse "
                    "them into ONE probe (stack the flags device-side, "
                    "one transfer, e.g. SlotEngine._probe_bad)",
                )
            elif _innermost_loop(loop) is not None:
                yield Finding(
                    self.id, ctx.path, calls[0].lineno,
                    "probe call in a loop nested inside a decode loop — "
                    "this syncs once PER ITERATION (per slot) per chunk; "
                    "probe the whole batch with one vectorized transfer "
                    "outside the inner loop",
                )


RULES = [DecodeHostSyncRule()]
