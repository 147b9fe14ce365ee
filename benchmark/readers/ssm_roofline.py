"""A served state-space kernel's share of its roofline: the bytes it has to
move / its device time in the capture, as 100 x that rate / the device's peak
(``benchmark/peaks.json`` by ``device_kind``).

The one-token step touches only the rows live in a chunk, and the capture
does not say how many those were, so its bytes are those of the MEAN number
of slots that emitted at a boundary over the profile phase
(``evidence["capture"]["emitting_rows_per_boundary"]``, which the kind of run
fills from the server's counters) times the kernel's calls in the capture
(one a layer and step). The prompt pieces' chunked scan is no kernel of its
own in the program (XLA fusions): nothing here reads it. The widths come
from the metric file's ``args`` (the kind of run puts another model's into
the capture). Nothing to read (no such kernel in the capture, no such counter
in the program) gives None.
"""

import json
import re

from readers import peak_share, xplane


def step_bytes(rows: float, heads: int, head_dim: int, state: int) -> float:
    """Bytes one call of the state-space step moves for ``rows`` live rows:
    per row the fp32 state ``heads x head_dim x state`` read and written
    once; the decay and ``dt x`` (``heads x head_dim`` wide) read and the
    output of that width written, and the group's B and C as the kernel takes
    them, repeated for every packed row of ``128 // head_dim`` heads
    (``state`` wide each), all fp32. 3 flops a state element: memory-bound."""
    packed_rows = heads // max(1, 128 // head_dim)
    vectors = 3 * heads * head_dim + 2 * packed_rows * state
    return 4.0 * rows * (2 * heads * head_dim * state + vectors)


WORK = {
    "step": lambda rows, a: step_bytes(rows, a["heads"], a["head_dim"], a["state"]),
}


def read(evidence: dict, pattern: str, peak: str, work: str, widths: dict):
    capture, phase = evidence.get("xplane"), evidence.get("capture") or {}
    rows = phase.get("emitting_rows_per_boundary")
    if not capture or not rows:
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
    return 100.0 * calls * WORK[work](rows, widths) / seconds / peaks[kind][peak]
