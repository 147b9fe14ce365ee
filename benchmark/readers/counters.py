"""100 x one of the program's counters / another, both as the difference
over the measured window (``evidence["counters"]``)."""


def read(evidence: dict, numerator: str, denominator: str):
    c = evidence.get("counters") or {}
    if not c.get(denominator):
        return None
    return 100.0 * c.get(numerator, 0) / c[denominator]
