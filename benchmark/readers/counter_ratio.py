"""``scale`` x one of the program's counters / another, or the counter itself
where no denominator is named, both as the difference over the measured
window (``evidence["counters"]``). ``readers/counters.py`` with a scale other
than 100 and a plain count. A program that does not keep the numerator's
counter (the key is absent, as in a parent of the PR that brought it) gives
None."""


def read(evidence: dict, numerator: str, denominator: str = "", scale: float = 1.0):
    c = evidence.get("counters") or {}
    if numerator not in c:
        return None
    if not denominator:
        return scale * c[numerator]
    if not c.get(denominator):
        return None
    return scale * c[numerator] / c[denominator]
