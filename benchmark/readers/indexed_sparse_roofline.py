"""An indexed-attention layer's share of its roofline, for the decode steps
(bytes) and for the prompt pieces (operations): the work the MATHEMATICS
has to do in the captured boundaries / the device time its operations took
in them, as 100 x that rate / the device's peak (``benchmark/peaks.json`` by
``device_kind``). Whatever implements the layer (XLA gathers and masked dense
products, a kernel), the work counted is the same, so a faster form moves the
share and cannot push it past 100.

Work and time are both the CAPTURE's. The time is the leaf seconds of the
captured operations whose name stack matches ``inside`` (and not
``outside``). The work is the capture's count of the layer's units times the
window's mean work a unit, which the server's counters give
(``evidence["counters"]``, from the host's mirror of positions: the capture
holds no positions, and the profile phase replays the window's traffic on the
same full server):

- a decode step's unit is an emitting slot at a boundary (``chunk`` steps x
  the layers): the capture's boundaries (its host annotations named
  ``boundary``, one a boundary) x the mean emitting slots a boundary over the
  profile phase (``evidence["capture"]["emitting_rows_per_boundary"]``, as
  ``sparse_hybrid_roofline`` takes it), against the window's
  ``slot_steps_emitting``;
- a prompt piece's unit is one layer of one piece: the capture's device
  operations whose name matches ``calls`` (a kernel that runs once a piece
  and layer), against the window's ``slot_steps_prefilling`` x layers.

So a capture that holds more pieces, or more emitting slots, than the
window's mean boundary counts more work beside its longer time. Nothing to
read (no name stacks, no such counter or annotation: a parent of the PR that
brought them) gives None.
"""

import json
import re

from readers import peak_share, xplane


def step_bytes(scored_rows: float, listed_rows: float, emitting_row_layers: float,
               index_dim: int, kv_heads: int, head_dim: int, heads: int,
               cache_bytes: int) -> float:
    """Bytes the index, the selection and the attention of decode steps have
    to move: every live index key once (``index_dim`` wide), every listed
    row's K and V once (``kv_heads x head_dim`` each), and per emitting row
    and layer the fp32 queries read and outputs written."""
    keys = scored_rows * index_dim * cache_bytes
    listed = listed_rows * 2 * kv_heads * head_dim * cache_bytes
    return keys + listed + emitting_row_layers * 2 * heads * head_dim * 4


def piece_flops(visible_pairs: float, selected_pairs: float, index_heads: int,
                index_dim: int, heads: int, head_dim: int) -> float:
    """Operations the index and the attention of prompt pieces have to do: a
    multiply-add a head and index dimension for every (query, visible key)
    pair's score, and q . k plus p . v of every head for every (query,
    SELECTED key) pair."""
    return visible_pairs * 2 * index_heads * index_dim + selected_pairs * 4 * heads * head_dim


def peak_of(evidence: dict, peak: str):
    """The device's ``peak`` (None at a CPU rehearsal, which has none and
    reports no share; a chip that is not in the table is an error)."""
    with open(peak_share.PEAKS) as f:
        peaks = json.load(f)
    kind = evidence["device_kind"]
    if kind not in peaks and evidence.get("rehearse"):
        return None
    if kind not in peaks:
        raise RuntimeError(f"no peaks on record for device_kind {kind!r} (known: {sorted(peaks)})")
    return peaks[kind][peak]


def work_of(work: str, c: dict, chunk: int, w: dict):
    """(the work the window's counters state, the units it was done in)."""
    if work == "step":
        need = ("index_rows_scored", "kv_rows_listed", "slot_steps_emitting")
        if not all(c.get(k) for k in need):
            return None
        return step_bytes(
            chunk * c["index_rows_scored"], chunk * c["kv_rows_listed"],
            chunk * c["slot_steps_emitting"] * w["layers"], w["index_dim"], w["kv_heads"],
            w["head_dim"], w["heads"], w["cache_bytes"]), c["slot_steps_emitting"]
    assert work == "piece", work
    need = ("index_pairs_visible", "index_pairs_selected", "slot_steps_prefilling")
    if not all(c.get(k) for k in need):
        return None
    return piece_flops(c["index_pairs_visible"], c["index_pairs_selected"], w["index_heads"],
                       w["index_dim"], w["heads"], w["head_dim"]), (
        c["slot_steps_prefilling"] * w["layers"])


def units_captured(work: str, evidence: dict, boundary: str, calls: str) -> float:
    capture = evidence.get("xplane")
    if not capture:
        return 0.0
    if work == "step":
        rows = (evidence.get("capture") or {}).get("emitting_rows_per_boundary") or 0.0
        return rows * sum(1 for e in xplane.host_events(capture) if e[0] == boundary)
    lines = xplane.device_lines(capture)
    return float(sum(1 for name, _, dur in lines[0] if dur > 0 and re.search(calls, name))
                 if lines else 0)


def read(evidence: dict, work: str, peak: str, inside: str, widths: dict,
         outside: str = "", chunk: int = 1, boundary: str = "", calls: str = ""):
    scoped = evidence.get("scoped_ops") or {}
    events = scoped.get("events") or []
    if not scoped.get("source") or not any(e[0] for e in events):
        return None
    window = work_of(work, evidence.get("counters") or {}, chunk, widths)
    units = units_captured(work, evidence, boundary, calls)
    seconds = sum(v for k, v in xplane.name_seconds(events).items()
                  if re.search(inside, k) and not (outside and re.search(outside, k)))
    if not window or not units or not seconds:
        return None
    top = peak_of(evidence, peak)
    return top and 100.0 * window[0] * units / window[1] / seconds / top
