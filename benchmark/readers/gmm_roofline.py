"""The served grouped product's share of its roofline in the DECODE STEPS:
the bytes the routed experts' products have to move / the device time of the
kernels that run them in the capture, as 100 x that rate / the device's peak
(``benchmark/peaks.json`` by ``device_kind``).

What a step's routed product has to move a layer, whatever kernel does it
(:func:`step_bytes`): the matrices of the held experts that got a row, each
read once, and the held (token, expert) pairs' rows into and out of every
product. Both counts are the PROGRAM's, not a router's that is assumed:

- the experts that got a row (:func:`steps_live_experts`) are the window's
  ``moe_experts_live`` counter, which the served layers sum over every call, a
  decode step's and a prompt piece's alike. The program does not keep the
  steps' apart, so every piece (``slot_steps_prefilling``) is taken off at ALL
  its held experts a layer, the most it can have had: what is left over the
  window's layer-steps is the LEAST the steps can have had. The bytes, and
  the share with them, are a lower bound: a skewed router that leaves held
  experts empty at a step lowers the reading, and nothing assumed raises it;
- the pairs are the step's emitting rows x ``top_k`` x the window's own held
  share (counters ``held`` / ``routed``). The capture does not say how many
  rows a step had, so they are the MEAN number of slots that emitted at a
  boundary over the profile phase
  (``evidence["capture"]["emitting_rows_per_boundary"]``, which the kind of
  run fills from the server's counters). The rows are 0.1% of the bytes.

``pattern`` selects the STEPS' kernel events by the shape of the buffer they
write (a prompt piece's buffer is another size); ``matrices`` products make
one layer's step. The widths, the layers that route and the steps a boundary
come from the metric file's ``args``. The reader notes the live experts it
counted beside the metric. Nothing to read (no such kernel in the capture, no
such counter in the program) gives None.
"""

import json
import re

import harness
from readers import peak_share, xplane


def step_bytes(live: float, pairs: float, d: int, h: int, matrices: int, itemsize: int) -> float:
    """Bytes one layer's routed product moves at a decode step: ``live``
    experts of ``matrices`` ``[d, h]`` matrices each, read once, and
    ``pairs`` (token, expert) rows that go into and come out of every product
    (``d + h`` elements a product either way round)."""
    return itemsize * matrices * (live * d * h + pairs * (d + h))


def steps_live_experts(counters: dict, held: int, layers: int, steps: int):
    """The least the held experts with a row can have been, a layer and
    decode step, by the program's own counters over the window: every call's
    ``moe_experts_live``, less ``held`` for each of the ``layers`` of every
    prompt piece, over ``chunks x steps x layers`` layer-steps. None where the
    program keeps no such counter."""
    if not counters.get("moe_experts_live") or not counters.get("chunks"):
        return None
    pieces = counters.get("slot_steps_prefilling", 0)
    left = counters["moe_experts_live"] - pieces * layers * held
    return min(float(held), max(0.0, left) / (counters["chunks"] * steps * layers))


def read(evidence: dict, pattern: str, peak: str, held_counter: str, routed_counter: str,
         layers: int, steps: int, widths: dict):
    capture, phase = evidence.get("xplane"), evidence.get("capture") or {}
    counters = evidence.get("counters") or {}
    rows = phase.get("emitting_rows_per_boundary")
    if not capture or not rows or not counters.get(held_counter) or not counters.get(routed_counter):
        return None
    widths = dict(widths)
    held, top_k = widths.pop("held"), widths.pop("top_k")
    live = steps_live_experts(counters, held, layers, steps)
    if live is None:
        return None
    lines = xplane.device_lines(capture)
    if not lines:
        return None
    calls = sum(1 for name, _, dur in lines[0] if dur > 0 and re.search(pattern, name))
    seconds = sum(v for k, v in xplane.name_seconds(lines[0]).items() if re.search(pattern, k))
    if not calls or not seconds:
        return None
    with open(peak_share.PEAKS) as f:
        peaks = json.load(f)
    kind = evidence["device_kind"]
    if kind not in peaks and evidence.get("rehearse"):
        return None  # a CPU rehearsal has no peak and reports no share
    if kind not in peaks:
        raise RuntimeError(f"no peaks on record for device_kind {kind!r} (known: {sorted(peaks)})")
    pairs = rows * top_k * counters[held_counter] / counters[routed_counter]
    layer_steps = calls / widths["matrices"]
    moved = layer_steps * step_bytes(live, pairs, **widths)
    harness.note(gmm_step_roofline={
        "live_experts_a_layer_step_at_least": live, "held": held, "pairs_a_layer_step": pairs,
        "layer_steps_in_capture": layer_steps, "seconds": seconds, "bytes": moved,
    })
    return 100.0 * moved / seconds / peaks[kind][peak]
