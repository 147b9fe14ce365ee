"""A row-sparse decode-state kernel's share of the memory roofline: the bytes
it has to move / its device time in the capture, as 100 x that rate / the
device's peak bandwidth (``benchmark/peaks.json`` by ``device_kind``).

The kernel steps only the rows live in a chunk, and the capture does not say
how many those were, so the bytes are ``step_bytes`` of the MEAN number of
slots that emitted at a boundary over the profile phase
(``evidence["capture"]``, which the kind of run fills from the server's
counters, with the model's widths) times the kernel's calls in the capture.
Nothing to read (no such kernel in the capture, no such counter in the
program) gives None.
"""

import json
import re

from readers import peak_share, xplane


def step_bytes(rows: float, heads: int, key_dim: int, value_dim: int) -> float:
    """Bytes one call of the delta-rule step moves for ``rows`` live rows:
    per row and head the fp32 state ``[key_dim, value_dim]`` read and written
    once; q, k and the decay (key wide), v and the write strength (value
    wide) read, the output (value wide) written, all fp32 as the kernel takes
    them. 2 flops a state element a pass: memory-bound."""
    state = 2 * key_dim * value_dim
    vectors = 3 * key_dim + 3 * value_dim
    return 4.0 * rows * heads * (state + vectors)


def read(evidence: dict, pattern: str, peak: str):
    capture, phase = evidence.get("xplane"), evidence.get("capture") or {}
    rows = phase.get("emitting_rows_per_boundary")
    if not capture or not rows or not phase.get("heads"):
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
    moved = calls * step_bytes(rows, phase["heads"], phase["key_dim"], phase["value_dim"])
    return 100.0 * moved / seconds / peaks[kind][peak]
