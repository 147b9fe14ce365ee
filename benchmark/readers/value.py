"""A number the kind of run computed itself: ``evidence["values"][key]``."""


def read(evidence: dict, key: str):
    return evidence.get("values", {}).get(key)
