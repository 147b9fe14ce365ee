"""100 x the device time of operations traced under a ``jax.named_scope``
whose name stack matches ``pattern`` / the device's busy time, over the
profiled steps of the first chip.

``evidence["scoped_ops"]`` (the kind of run makes it, see its docstring) is
``{"source": ..., "events": [[name stack, start ns, duration ns], ...]}``:
one entry per executed XLA operation, named by the name stack XLA carries
for it — forward, remat recompute and backward operations of a scope all
carry the scope's name. A fusion carries its root's. Only leaves count (an
operation that encloses others, a while loop, is skipped), so the shares of
disjoint scopes add up to at most 100. Where the capture has no name stacks
the reading is absent: nothing is guessed from shapes.
"""

import re

from readers import xplane


def read(evidence: dict, pattern: str):
    scoped = evidence.get("scoped_ops") or {}
    events = scoped.get("events") or []
    if not scoped.get("source") or not any(e[0] for e in events):
        return None
    busy = xplane.busy_and_window(events)[0]
    if not busy:
        return None
    hit = sum(v for k, v in xplane.name_seconds(events).items() if re.search(pattern, k))
    return 100.0 * hit * 1e9 / busy
