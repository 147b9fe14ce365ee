"""Serving: continuous batching + the inference-side counterpart of the
training resilience stack (orion_tpu/resilience/, PR 2).

- :mod:`batching` — :class:`SlotEngine`, the ONE engine: slot-multiplexed
  continuous batching — a fixed number of requests share one jitted
  batched decode scan (O(1) recurrent state makes a "slot" just a row of
  the carry; ``slots=1`` is the solo case). ONE admission path: a prompt
  is staged into the carry and consumed in-scan, in pieces. Eviction at
  chunk boundaries, per-chunk state snapshots, a per-slot all-finite
  probe, a rewind -> re-prefill -> fail-request degradation ladder, and
  chunk-granular deadlines. Its parity oracle is ``generate()``'s solo
  scan: equal tokens on every pinned seed (XLA:CPU; on the chip the
  cells' `correct` tolerance, ROADMAP C12).
- :mod:`session` — :class:`DecodeRequest` / :class:`DecodeResult`, the
  records a client submits and gets back.
- :mod:`server`  — :class:`Server`: the scheduler loop over the engine —
  bounded admission with explicit shed-on-overload, per-request
  isolation, watchdog heartbeats, and SIGTERM -> drain (finish in-flight
  slots, reject new, exit 0).
- :mod:`health`  — the validated STARTING -> SERVING <-> DEGRADED ->
  DRAINING -> DEAD process health state machine.
- :mod:`session_store` — durable sessions: a suspended conversation is
  one O(1) decode-state snapshot, persisted atomically with a per-leaf
  crc32 manifest and restored bitwise (``--session-dir``; survives
  SIGTERM drain and server restarts).
- :mod:`prefix_store` — the content-addressed prefix cache: a shared
  prompt prefix (system prompt) is ONE O(1) decode-state snapshot keyed
  by hash(params identity, qmode, token bytes); a hit admits as a row
  copy + in-scan prefill of only the uncached suffix, with the tokens of
  the cold request (``--prefix-dir``;
  shared by every replica of a fleet).

``python -m orion_tpu.serving`` is the CLI (``--slots``, ``--chunk``,
``--deadline-ms``, ``--max-inflight``, ``--prefill-buckets``; see README
"Resilient serving"). The chaos coverage lives in tests/test_serving.py
and tests/test_batching.py under the ``chaos`` marker.
"""

import orion_tpu as _root
from orion_tpu.obs import trace as _trace

_trace.import_begin()  # setup.import ends at this file's last line

from orion_tpu.serving.batching import SlotEngine, parse_buckets
from orion_tpu.serving.health import Health, HealthMachine, InvalidTransition
from orion_tpu.serving.server import (
    PHASES,
    OverloadError,
    Pending,
    RejectedError,
    ServeConfig,
    Server,
    load_tokenizer,
)
from orion_tpu.serving.session import DecodeRequest, DecodeResult
from orion_tpu.serving.prefix_store import PrefixEntry, PrefixStore
from orion_tpu.serving.session_store import (
    SessionIntegrityError,
    SessionState,
    SessionStore,
)

_trace.import_done(__name__, _root.IMPORT_STARTED)

__all__ = [
    "Health", "HealthMachine", "InvalidTransition",
    "Server", "ServeConfig", "Pending", "OverloadError", "RejectedError",
    "load_tokenizer", "SlotEngine", "parse_buckets", "PHASES",
    "DecodeRequest", "DecodeResult",
    "SessionStore", "SessionState", "SessionIntegrityError",
    "PrefixStore", "PrefixEntry",
]
