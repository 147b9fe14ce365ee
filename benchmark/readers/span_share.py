"""100 x the time inside the benchmark's own host spans of one name / the
measured window (spans are (name, start, duration) on time.monotonic())."""


def read(evidence: dict, name: str):
    spans, window = evidence.get("spans"), evidence.get("window_s")
    if spans is None or not window:
        return None
    return 100.0 * sum(d for n, _, d in spans if n == name) / window
