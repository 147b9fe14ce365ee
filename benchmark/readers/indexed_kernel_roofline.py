"""A prompt-piece kernel of the indexed layers' share of the MXU peak, by
kernel name: the operations its calls in the capture DO / their device time,
as 100 x that rate / the device's peak (``benchmark/peaks.json`` by
``device_kind``).

Both kernels run once a piece and layer over a static key length ``S`` (the
shortest of a few that holds the piece's end), every (query, key) pair of
``[B, P] x [S]`` multiplied, masked or not: ``index_scores`` 2 x index heads
x index dim a pair, ``indexed_attention`` 4 x heads x head dim a pair. ``S``
is read from the capture itself: ``scores`` matches the ``index_scores``
kernel's operation and takes ``B, P, S`` from its output ``f32[B,P,S]``; a
call of ``kernel`` takes the shape of the latest ``scores`` call before it
(the mixer calls the two in turn on one piece). These are the operations the
kernel performs, not those the mathematics needs (the selected pairs are
6-17% of the visible ones: ``indexed_piece_roofline`` counts those). Nothing
to read (no such kernel in the capture) gives None.
"""

import re

from readers import xplane
from readers.indexed_sparse_roofline import peak_of, piece_flops


def read(evidence: dict, kernel: str, scores: str, work: str, peak: str, widths: dict):
    capture = evidence.get("xplane")
    lines = xplane.device_lines(capture) if capture else []
    if not lines:
        return None
    pairs, flops, seconds = 0, 0.0, 0.0
    for name, _, dur in sorted((e for e in lines[0] if e[2] > 0), key=lambda e: e[1]):
        shape = re.search(scores, name)
        if shape:
            b, p, s = (int(g) for g in shape.groups())
            pairs = b * p * s
        if pairs and re.search(kernel, name):
            visible, selected = (pairs, 0) if work == "scores" else (0, pairs)
            flops += piece_flops(visible, selected, widths["index_heads"], widths["index_dim"],
                                 widths["heads"], widths["head_dim"])
            seconds += dur / 1e9
    if not flops or not seconds:
        return None
    top = peak_of(evidence, peak)
    return top and 100.0 * flops / seconds / top
