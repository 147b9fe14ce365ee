"""Config plumbing (SURVEY.md T8): dataclass configs + JSON + CLI overrides.

`apply_overrides(cfg, {"lr": 1e-3, "model.n_layers": 4})` returns a new
frozen dataclass with dotted-path fields replaced; values are coerced to the
field's existing type. JSON config files are just dicts of the same dotted
(or nested) form."""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Mapping


def _coerce(old: Any, new: Any) -> Any:
    if old is None or new is None:
        return new
    if isinstance(old, bool):
        if isinstance(new, str):
            return new.lower() in ("1", "true", "yes")
        return bool(new)
    if isinstance(old, int) and not isinstance(old, bool):
        return int(new)
    if isinstance(old, float):
        return float(new)
    if isinstance(old, tuple) and isinstance(new, (list, tuple)):
        return tuple(new)
    if isinstance(old, tuple) and isinstance(new, str):
        return tuple(new.split(","))  # --set layer_types=ssm,softmax
    return new


def _flatten(d: Mapping[str, Any], prefix: str = "") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in d.items():
        key = f"{prefix}{k}"
        if isinstance(v, Mapping):
            out.update(_flatten(v, key + "."))
        else:
            out[key] = v
    return out


def apply_overrides(cfg: Any, overrides: Mapping[str, Any]) -> Any:
    """Return cfg with dotted-path overrides applied (recursively)."""
    flat = _flatten(dict(overrides))
    grouped: Dict[str, Dict[str, Any]] = {}
    direct: Dict[str, Any] = {}
    for k, v in flat.items():
        if "." in k:
            head, rest = k.split(".", 1)
            grouped.setdefault(head, {})[rest] = v
        else:
            direct[k] = v

    updates: Dict[str, Any] = {}
    fields = {f.name: f for f in dataclasses.fields(cfg)}
    for k, v in direct.items():
        if k not in fields:
            raise KeyError(f"{type(cfg).__name__} has no field {k!r}")
        updates[k] = _coerce(getattr(cfg, k), v)
    for head, sub in grouped.items():
        if head not in fields:
            raise KeyError(f"{type(cfg).__name__} has no field {head!r}")
        updates[head] = apply_overrides(getattr(cfg, head), sub)
    return dataclasses.replace(cfg, **updates)


def load_json_overrides(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def parse_set_overrides(pairs) -> Dict[str, Any]:
    """['k=v', ...] (the CLIs' repeated --set flag) -> override mapping."""
    overrides: Dict[str, Any] = {}
    for kv in pairs:
        k, sep, v = kv.partition("=")
        if not sep or not k:
            raise ValueError(f"--set expects KEY=VALUE, got {kv!r}")
        overrides[k] = v
    return overrides


def config_to_dict(cfg: Any) -> Dict[str, Any]:
    return dataclasses.asdict(cfg)


__all__ = [
    "apply_overrides",
    "load_json_overrides",
    "parse_set_overrides",
    "config_to_dict",
]
