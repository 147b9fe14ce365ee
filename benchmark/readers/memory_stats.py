"""Device memory of the fullest chip at the end of the window, in GB (1e9
bytes): ``harness.footprint_bytes`` — live arrays (``bytes_in_use``) plus
what the loaded programs reserve beside them (``bytes_reserved``: the train
step's scratch, most of a full chip), or the arrays' own peak where larger.
``peak_bytes_in_use`` alone counts arrays only (PERF.md, PR 24 finding 4),
and the two peak counters cannot be added: they peak at different moments. A
backend that reports no memory statistics makes the reading absent."""

from harness import footprint_bytes


def read(evidence: dict):
    stats = evidence.get("memory") or {}
    return footprint_bytes(stats) / 1e9 if stats.get("bytes_in_use") else None
