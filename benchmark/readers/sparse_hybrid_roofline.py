"""A served decayed-linear / block-sparse kernel's share of its roofline: the
bytes (or operations) it has to move / its device time in the capture, as
100 x that rate / the device's peak (``benchmark/peaks.json`` by
``device_kind``).

The two decode kernels step or read only the rows live in a chunk, and the
capture does not say how many those were, so their bytes are those of the
MEAN number of slots that emitted at a boundary over the profile phase
(``evidence["capture"]["emitting_rows_per_boundary"]``, which the kind of run
fills from the server's counters) times the kernel's calls in the capture.
The prompt pieces' kernel is called once a piece and layer on a batch of one,
so its operations are ``calls x`` one piece's. The widths come from the metric
file's ``args`` (the kind of run puts another model's into the capture).
Nothing to read (no such kernel in the capture, no such counter in the
program) gives None.
"""

import json
import re

from readers import peak_share, xplane


def block_attention_bytes(rows: float, kv_heads: int, group: int, head_dim: int,
                          blocks: int, block: int, cache_bytes: int) -> float:
    """Bytes one call of the block-list decode attention moves for ``rows``
    listed rows: per row and KV head ``blocks`` listed blocks of ``block``
    cache rows of K and of V (``cache_bytes`` an element), the group's fp32
    queries read and its fp32 outputs and log-sum-exps written. 2 flops a
    cache element a query head: memory-bound on the cache."""
    cache = 2 * blocks * block * head_dim * cache_bytes
    vectors = 4 * group * (2 * head_dim + 1)
    return rows * kv_heads * (cache + vectors)


def decay_step_bytes(rows: float, heads: int, head_dim: int, io_bytes: int) -> float:
    """Bytes one call of the decayed state step moves for ``rows`` live rows:
    per row and head the fp32 ``[head_dim, head_dim]`` state read and written
    once; q, k, v read and the output written (``io_bytes`` an element). 3
    flops a state element: memory-bound."""
    return rows * heads * (2 * 4 * head_dim * head_dim + 4 * io_bytes * head_dim)


def decay_piece_flops(heads: int, head_dim: int, piece: int, chunk: int) -> float:
    """Operations of one call of the decayed chunk kernel on one piece: per
    head and chunk of C rows the C x C scores, their product with V, the
    carried state's C x d x d and the state update's, 2 a multiply-add."""
    per_chunk = 2 * chunk * chunk * head_dim * 2 + 2 * chunk * head_dim * head_dim * 2
    return heads * (piece // chunk) * per_chunk


WORK = {
    "block_attention": lambda rows, a: block_attention_bytes(
        rows, a["kv_heads"], a["group"], a["head_dim"], a["blocks"], a["block"], a["cache_bytes"]),
    "decay_step": lambda rows, a: decay_step_bytes(rows, a["heads"], a["head_dim"], a["io_bytes"]),
    "decay_piece": lambda rows, a: decay_piece_flops(a["heads"], a["head_dim"], a["piece"], a["chunk"]),
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
