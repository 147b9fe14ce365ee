"""The request and result records of the serving path.

A :class:`DecodeRequest` is what a client submits and a :class:`DecodeResult`
what it gets back; :class:`~orion_tpu.serving.batching.SlotEngine` is the one
engine that turns the first into the second (a one-slot engine is the solo
case).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np

from orion_tpu.generate import SampleConfig


@dataclasses.dataclass(frozen=True)
class DecodeRequest:
    """One generation request. ``prompt``: token ids, [T] or [B, T].
    ``deadline_ms`` <= 0 means no deadline.

    ``session_id`` makes the request a durable-session turn (server-side
    sessions must be enabled): a fresh id starts a conversation whose
    decode state is suspended at turn end (one O(1) snapshot,
    serving/session_store.py); a known id continues it — with an empty
    prompt the resume is an O(1) row insert (no prefill) and the
    continuation is bitwise what one longer uninterrupted request would
    have produced; with new prompt tokens the turn re-prefills the full
    history (tokens are appended to the context before generation
    continues). A continuation's ``sample`` must match the session's and
    its ``seed`` is ignored in favor of the session's (both anchor the
    resumed rng walk).

    ``prefix_len`` declares the first ``prefix_len`` prompt tokens as a
    SHARED, cacheable prefix (a system prompt): with a prefix store
    configured (serving/prefix_store.py), a miss PUBLISHES the aligned
    prefix's O(1) decode-state snapshot so later requests — on any
    replica sharing the store — admit at O(suffix) instead of O(prompt).
    Lookups are content-addressed and run for every request regardless;
    the declaration only gates publishing (the server cannot guess where
    a shared prefix ends — an undeclared publish would bake one user's
    tokens into the cache key). 0 = no declaration."""

    prompt: Any
    max_new_tokens: int
    sample: SampleConfig = SampleConfig()
    seed: int = 0
    deadline_ms: float = 0.0
    session_id: Optional[str] = None
    prefix_len: int = 0


@dataclasses.dataclass
class DecodeResult:
    tokens: np.ndarray  # [B, new_tokens]
    status: str  # "ok" | "deadline" | "failed" | "suspended"
    new_tokens: int
    chunks: int
    rewinds: int = 0
    reprefills: int = 0
    # -- cost attribution (ISSUE 15; filled by the Server, zeros from a
    # bare engine): this request's share of the measured
    # chunk wall time (shares across co-residents sum to the boundary's
    # chunk_ms — conservation), the ledger-derived flops billed, and the
    # device prefill/decode token counts behind them
    device_ms: float = 0.0
    cost_flops: float = 0.0
    prefill_tokens: int = 0
    decode_tokens: int = 0
    # the suspended SessionState riding out of the engine for the server
    # to persist before the result is released (durable sessions only)
    session: Any = dataclasses.field(default=None, repr=False, compare=False)

    @property
    def degraded(self) -> bool:
        """Did the request need the degradation ladder to complete?"""
        return self.rewinds > 0 or self.reprefills > 0


__all__ = ["DecodeRequest", "DecodeResult"]
