"""100 x (values[rate] x values[per_unit]) / the device's peak, looked up
in ``benchmark/peaks.json`` by ``device_kind``. A device that is not in the
table is an error, not a default."""

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "peaks.json")


def read(evidence: dict, rate: str, per_unit: str, peak: str):
    values = evidence.get("values", {})
    if rate not in values or per_unit not in values:
        return None
    with open(PEAKS) as f:
        peaks = json.load(f)
    kind = evidence["device_kind"]
    if kind not in peaks and evidence.get("rehearse"):
        return None  # a CPU rehearsal has no peak and reports no share
    if kind not in peaks:
        raise RuntimeError(f"no peaks on record for device_kind {kind!r} "
                           f"(known: {sorted(peaks)})")
    return 100.0 * values[rate] * values[per_unit] / peaks[kind][peak]
