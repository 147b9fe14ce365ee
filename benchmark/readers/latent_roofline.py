"""A latent-cache decode attention kernel's share of its roofline: the time
the chip's peaks need for the work the kernel cannot avoid / the kernel's
device time in the capture, x 100.

Per call the kernel attends, for each slot that emits, from one token over
that slot's live latent rows. A latent row costs ``row_bytes`` of HBM traffic
(it is fetched once, for scores and values) and ``row_flops`` on the MXU (all
heads' scores against it and its share of all heads' values), so the least
time a row can take is the LARGER of ``row_bytes / hbm_bytes_per_s`` and
``row_flops / bf16_flops`` (``benchmark/peaks.json`` by ``device_kind``): the
kernel sits where the two meet, and whichever binds is the roofline. Rows are
counted to the row, WITHOUT the kernel's block rounding and without slots
that do not emit, so padding the kernel fetches or multiplies can only lower
the share: it cannot read over 100.

The capture does not say how many rows a call attended over. They are taken
as the mean number of slots that emitted at a boundary over the profile
phase (``evidence["capture"]["emitting_rows_per_boundary"]``, which the kind
of run fills from the server's counters) x the mean live rows of an emitting
slot over the measured window (counters ``attended`` / ``emitting``: the
profile phase replays the window's traffic on the same full server). Nothing
to read (no such kernel in the capture, no such counter in the program: a
parent of the PR that brought them) gives None.
"""

import json
import re

from readers import peak_share, xplane


def read(evidence: dict, pattern: str, row_bytes: float, row_flops: float,
         attended: str, emitting: str):
    capture, phase = evidence.get("xplane"), evidence.get("capture") or {}
    counters = evidence.get("counters") or {}
    slots = phase.get("emitting_rows_per_boundary")
    if not capture or not slots or not counters.get(attended) or not counters.get(emitting):
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
    row_s = max(row_bytes / peaks[kind]["hbm_bytes_per_s"], row_flops / peaks[kind]["bf16_flops"])
    rows_per_call = slots * counters[attended] / counters[emitting]
    return 100.0 * calls * rows_per_call * row_s / seconds
