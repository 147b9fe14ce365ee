"""Content-addressed prefix store: shared prompt prefixes as O(1) snapshots.

A paged-KV server needs a radix tree over cache blocks to share a system
prompt between requests; here the paper's O(1) recurrent state makes the
whole problem one row copy. The decode state after prefilling the first
``L`` tokens of a prompt is a small fixed-size ``(S, z)``-plus-caches
pytree — independent of ``L`` — so a *prefix cache entry* is exactly one
such snapshot plus the tokens it was built from, and a cache hit turns
O(prompt) admission into O(suffix): ``insert_decode_slot`` the cached row
at position ``L`` and let the in-scan prefill consume only the uncached
tail (``serving/batching.py::SlotEngine._stage_prefix``).

Addressing is by CONTENT, not coordination: the key is
``sha256(params_id | qmode | prompt[:L] token bytes)``, so every replica
of a fleet sharing one ``prefix_dir`` resolves the same system prompt to
the same entry with no registry and no invalidation protocol — different
checkpoints or quantization modes can never collide because their
activations (and therefore their states) are different functions of the
same tokens. ``params_id`` is the caller's name for the weights (config +
checkpoint step / init seed); serving two different checkpoints into one
store under the same id would silently cross their states, which is why
the Server derives a config-hash default and the CLIs pin the checkpoint
identity.

Alignment: entries are published only at multiples of ``align`` (the
linear-attention chunk), because the in-scan prefill extends a state only
from a chunk boundary: every piece boundary lies on one
(``transformer.prefill_extend`` / ops/linear_attention.py). A published
state is the whole-prompt prefill's (``generate.prefill_carry``), so a hit
continues from a state that agrees with the one the cold request's own
pieces build to fp32 rounding on XLA:CPU, and the hit's TOKENS are the
cold request's on every pinned seed; on the chip, agreement is the cells'
`correct` tolerance (ROADMAP C12). What is bit-for-bit is the round trip:
a loaded entry is the published bytes (per-leaf crc32). A lookup
probes the aligned prefix lengths of the prompt longest-first — each
probe is one sha256 over the candidate's token bytes plus one directory
check, host-only ("hash + disk only"; the ``decode-host-sync`` lint keeps
the engine-side admission path free of device syncs).

Durability model (deliberately the session store's, training/checkpoint.py
lineage): generation-numbered ``gen-%06d.bin`` + ``gen-%06d.json`` under
``directory/<key>/``, payload-then-manifest with the manifest rename as
the COMMIT POINT, per-leaf shape/dtype/crc32 verification on load, retry
with the ``serve.prefix_save`` / ``serve.prefix_load`` fault hooks inside
the retried regions. Two differences, both forced by the fault model the
chaos suite pins (tests/test_quant_serving.py):

- **every load failure degrades to a MISS** — a corrupt or torn entry
  means a cold prefill, never a failed request (the session store's
  all-generations-damaged case raises, because a conversation's state
  cannot be recomputed; a prefix's can, from the prompt itself);
- **racing publishers converge** — the store has no single-writer fence
  (the router serializes sessions, nothing serializes prefixes), so tmp
  files carry a per-process unique suffix: two replicas publishing the
  same prefix each write their own tmp and the last ``os.replace`` wins
  with byte-identical content (the state is a deterministic function of
  (params, qmode, tokens)).

The on-disk layout matches the session store's generation files, so the
chaos damage helpers (``inject.corrupt_session`` / ``truncate_session``)
work on prefix entries unchanged with ``key`` in place of the session id.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
import uuid
import warnings
from typing import Any, Callable, List, Optional, Tuple

import numpy as np

from orion_tpu.resilience.breaker import CircuitBreaker, StoreUnavailableError
from orion_tpu.resilience.inject import fire
from orion_tpu.resilience.retry import RetryPolicy, call_with_retries
from orion_tpu.serving.session_store import (
    _decode_tree,
    _encode_tree,
    _np_dtype,
)
from orion_tpu.training.checkpoint import build_manifest, verify_manifest

PREFIX_FORMAT_VERSION = 1


@dataclasses.dataclass
class PrefixEntry:
    """One cached prefix: the tokens it covers, the decode state after
    prefilling exactly those tokens (batch 1, host arrays), and the
    position ``t == tokens.shape[1]`` the state sits at."""

    key: str
    tokens: np.ndarray  # [1, L] int32
    state: Any  # per-layer decode-state pytree, batch 1
    t: int
    generation: int = 0


def overrides_fingerprint(overrides: Any) -> str:
    """Stable short hash of a ModelConfig-override mapping — the ONE
    definition both params-id derivations use (fleet ``build_model`` on
    the spec's parsed dict, the serving CLI on its parsed ``--set``
    values). Two entry points hashing the same overrides differently
    would give identical weights different prefix identities, silently
    zeroing cross-tool cache hits."""
    doc = json.dumps(dict(overrides or {}), sort_keys=True, default=str)
    return hashlib.sha256(doc.encode()).hexdigest()[:8]


def params_identity(model_cfg: Any, qmode: str, extra: str = "") -> str:
    """Config-hash default ``params_id``: stable across processes for the
    same ModelConfig + qmode. ``extra`` pins the weights' provenance
    (checkpoint step, init seed) — callers serving real checkpoints MUST
    supply it; two different checkpoints of one config otherwise share a
    namespace and a hit would serve the other checkpoint's state."""
    cfg_json = json.dumps(dataclasses.asdict(model_cfg), sort_keys=True,
                          default=str)
    h = hashlib.sha256(
        f"{cfg_json}|{qmode}|{extra}".encode()
    ).hexdigest()[:16]
    return f"cfg-{h}"


class PrefixStore:
    """Content-addressed prefix snapshots under ``directory/<key>/``.

    ``align``: candidate prefix lengths are multiples of this (the
    engine's linear-attention chunk — in-scan piece boundaries land on
    chunk boundaries). ``max_probes`` bounds
    the per-lookup candidate walk (longest candidates first).
    ``observer``: host-only telemetry tap ``(op, ms, nbytes)`` with op in
    {"load", "save"} after each completed store I/O."""

    def __init__(
        self,
        directory: str,
        params_id: str,
        qmode: str = "off",
        align: int = 1,
        keep: int = 2,
        retry: Optional[RetryPolicy] = None,
        should_abort: Optional[Callable[[], bool]] = None,
        observer: Optional[Callable[[str, float, int], None]] = None,
        clock: Callable[[], float] = time.monotonic,
        max_probes: int = 64,
        breaker: Optional[CircuitBreaker] = None,
    ):
        if align < 1:
            raise ValueError(f"align must be >= 1, got {align}")
        if keep < 1:
            raise ValueError(f"keep must be >= 1, got {keep}")
        self.directory = os.path.abspath(directory)
        self.params_id = str(params_id)
        self.qmode = str(qmode or "off")
        self.align = int(align)
        self.keep = int(keep)
        self.max_probes = int(max_probes)
        self._retry = retry if retry is not None else RetryPolicy()
        self._should_abort = should_abort
        self._observer = observer
        self._clock = clock
        self.breaker = breaker
        os.makedirs(self.directory, exist_ok=True)

    def _observe(self, op: str, t0: float, nbytes: int) -> None:
        if self._observer is not None:
            try:
                self._observer(op, (self._clock() - t0) * 1e3, nbytes)
            except Exception:
                pass  # telemetry must never fail the I/O it measures

    # -- breaker gate and raw I/O ---------------------------------------------
    # Same discipline as the session store (lint rule ``raw-store-io``):
    # the ``_io_*`` helpers are the module's only direct filesystem touch
    # points and fail fast while the breaker is open, so an open breaker
    # turns every lookup into an O(1)-host-work MISS (cold prefill) with
    # zero per-request disk probes.

    def _exit(self, ok: bool, reason: str = "") -> None:
        if self.breaker is None:
            return
        if ok:
            self.breaker.record_success()
        else:
            self.breaker.record_failure(reason)

    def _blocked_check(self) -> None:
        if self.breaker is not None and self.breaker.blocked():
            raise StoreUnavailableError("prefix")

    def _io_open(self, path: str, mode: str = "r", **kw):
        self._blocked_check()
        return open(path, mode, **kw)

    def _io_listdir(self, path: str) -> List[str]:
        """Directory scan, or [] when the entry doesn't exist — an
        unpublished prefix is a normal miss, not a store fault."""
        self._blocked_check()
        fire("serve.prefix_scan")
        try:
            return os.listdir(path)
        except (FileNotFoundError, NotADirectoryError):
            return []

    def _io_replace(self, src: str, dst: str) -> None:
        self._blocked_check()
        os.replace(src, dst)

    def _io_makedirs(self, path: str) -> None:
        self._blocked_check()
        os.makedirs(path, exist_ok=True)

    def _io_remove(self, path: str) -> None:
        self._blocked_check()
        os.remove(path)

    def _io_rmdir(self, path: str) -> None:
        self._blocked_check()
        os.rmdir(path)

    # -- keys and paths -------------------------------------------------------

    def key_for(self, tokens: np.ndarray) -> str:
        """Content hash of one aligned prefix: params identity, qmode, and
        the token bytes — nothing else, so every replica resolves the
        same prompt to the same key."""
        toks = np.ascontiguousarray(np.asarray(tokens, np.int32)).tobytes()
        h = hashlib.sha256()
        h.update(b"orion-prefix-v1|")
        h.update(self.params_id.encode())
        h.update(b"|")
        h.update(self.qmode.encode())
        h.update(b"|")
        h.update(toks)
        return h.hexdigest()[:32]

    def _dir(self, key: str) -> str:
        return os.path.join(self.directory, key)

    @staticmethod
    def _bin(d: str, gen: int) -> str:
        return os.path.join(d, f"gen-{gen:06d}.bin")

    @staticmethod
    def _json(d: str, gen: int) -> str:
        return os.path.join(d, f"gen-{gen:06d}.json")

    def generations(self, key: str) -> List[int]:
        """COMMITTED generations of one entry (manifest present), oldest
        first — a ``.bin`` without its ``.json`` is a torn publish and is
        invisible (the session store's commit-point rule). Raises
        StoreUnavailableError without touching disk while the breaker is
        open (callers degrade to a miss / a counted publish drop)."""
        out = []
        for name in self._io_listdir(self._dir(key)):
            if name.startswith("gen-") and name.endswith(".json"):
                try:
                    out.append(int(name[len("gen-"):-len(".json")]))
                except ValueError:
                    continue
        return sorted(out)

    def list_keys(self) -> List[str]:
        return sorted(
            n for n in self._io_listdir(self.directory)
            if self.generations(n)
        )

    # -- candidates -----------------------------------------------------------

    def candidate_lengths(self, prompt_len: int,
                          declared: int = 0) -> List[int]:
        """Aligned prefix lengths to probe, longest first, bounded by
        ``max_probes`` (each probe costs a sha256 over the candidate's
        bytes plus a directory check — admission-path work that must
        stay bounded however long the prompt is). A candidate must leave
        at least ONE uncached suffix token: the in-scan hit path samples
        the request's first token from the suffix piece's last-real-row
        logits, so a whole-prompt entry would have nothing to feed the
        sampler.

        ``declared`` (the request's ``prefix_len``) is probed FIRST when
        it falls outside the longest-first window: a declared system
        prompt must hit however long the user suffix is — without the
        hint, a suffix longer than ``max_probes * align`` tokens would
        walk the whole probe budget above the published length and miss
        a committed entry."""
        top = (prompt_len - 1) // self.align * self.align
        out = []
        if declared > 0:
            hint = self.publish_length(prompt_len, declared)
            if hint > 0:
                out.append(hint)
        length = top
        while length >= self.align and len(out) < self.max_probes:
            if length not in out:
                out.append(length)
            length -= self.align
        return out

    def publish_length(self, prompt_len: int, declared: int) -> int:
        """The aligned length a declared prefix publishes at: the largest
        multiple of ``align`` <= min(declared, prompt_len - 1), or 0 when
        no aligned prefix fits."""
        usable = min(int(declared), prompt_len - 1)
        if usable < self.align:
            return 0
        return usable // self.align * self.align

    # -- lookup ---------------------------------------------------------------

    def lookup(self, prompt: Any, declared: int = 0) -> Optional[PrefixEntry]:
        """Longest cached aligned prefix of ``prompt`` (the request's
        declared ``prefix_len`` probed first — see
        :meth:`candidate_lengths`), or None. Damage of any kind —
        unreadable files, crc mismatch, a hash collision's token
        mismatch — degrades to trying the next generation, then the next
        (shorter) candidate, then a miss: a prefix can always be
        recomputed from the prompt, so the cold path is the fallback and
        the request NEVER fails here.

        Breaker policy: an OPEN breaker is an INSTANT miss — one
        ``allow()`` host check, zero disk probes (no sha256-then-listdir
        walk against dead storage on the admission path). One completed
        walk is one breaker sample: any OSError seen is a failure,
        a clean hit or clean miss a success."""
        toks = np.asarray(prompt, np.int32).reshape(1, -1)
        lengths = self.candidate_lengths(toks.shape[1], declared)
        if not lengths:
            return None
        if self.breaker is not None and not self.breaker.allow():
            return None  # open: cold prefill, fail-fast
        try:
            entry, os_fail, aborted = self._lookup_walk(toks, lengths)
        except BaseException:
            self._exit(False, "lookup: aborted")
            raise
        if aborted:
            # the breaker tripped under us mid-walk (a concurrent
            # operation reported first): miss, no sample of our own
            return None
        if os_fail is not None:
            self._exit(False, f"lookup: {type(os_fail).__name__}")
        else:
            self._exit(True)
        return entry

    def _lookup_walk(
        self, toks: np.ndarray, lengths: List[int]
    ) -> Tuple[Optional[PrefixEntry], Optional[OSError], bool]:
        """The candidate walk of :meth:`lookup`; returns
        ``(entry, first OSError seen, aborted-by-open-breaker)`` and
        never lets a store error escape."""
        os_fail: Optional[OSError] = None
        for length in lengths:
            prefix = toks[:, :length]
            key = self.key_for(prefix)
            try:
                gens = self.generations(key)
            except StoreUnavailableError:
                return None, None, True
            except OSError as e:
                os_fail = e
                continue
            if not gens:
                continue
            t0 = self._clock()
            for gen in reversed(gens):
                try:
                    entry, nbytes = self._load_gen(key, gen)
                except StoreUnavailableError:
                    return None, None, True
                except OSError as e:  # store-shaped: counts as evidence
                    os_fail = e
                    warnings.warn(
                        f"prefix {key} generation {gen} is unreadable "
                        f"({type(e).__name__}: {str(e)[:200]}); trying "
                        "the previous generation",
                        stacklevel=2,
                    )
                    continue
                except Exception as e:  # damaged payloads: many types
                    warnings.warn(
                        f"prefix {key} generation {gen} is corrupt or "
                        f"incomplete ({type(e).__name__}: {str(e)[:200]}); "
                        "trying the previous generation",
                        stacklevel=2,
                    )
                    continue
                if (entry.t != length
                        or entry.tokens.shape != prefix.shape
                        or not np.array_equal(entry.tokens, prefix)):
                    # key collision or cross-config reuse: the stored
                    # tokens are the ground truth, the hash only an index
                    warnings.warn(
                        f"prefix {key} gen {gen} does not match the "
                        "probed tokens; ignoring the entry",
                        stacklevel=2,
                    )
                    continue
                self._observe("load", t0, nbytes)
                return entry, os_fail, False
        return None, os_fail, False

    def _load_gen(self, key: str, gen: int) -> Tuple[PrefixEntry, int]:
        d = self._dir(key)

        def _read():
            fire("serve.prefix_load", step=gen)
            with self._io_open(self._json(d, gen)) as f:
                doc = json.load(f)
            with self._io_open(self._bin(d, gen), "rb") as f:
                blob = f.read()
            return doc, blob

        doc, blob = call_with_retries(
            _read, self._retry,
            describe=f"prefix load ({key} gen {gen})",
            should_abort=self._should_abort,
        )
        if doc.get("params_id") != self.params_id or (
                doc.get("qmode") != self.qmode):
            raise ValueError(
                f"prefix {key} gen {gen} was published for "
                f"({doc.get('params_id')}, {doc.get('qmode')}), not "
                f"({self.params_id}, {self.qmode})"
            )
        manifest = doc["manifest"]
        leaves: List[np.ndarray] = []
        for entry in manifest["leaves"]:
            raw = blob[entry["offset"]:entry["offset"] + entry["nbytes"]]
            if len(raw) != entry["nbytes"]:
                raise ValueError(
                    f"prefix {key} gen {gen}: payload truncated at leaf "
                    f"{entry['path']}"
                )
            leaves.append(
                np.frombuffer(raw, dtype=_np_dtype(entry["dtype"]))
                .reshape(entry["shape"])
            )
        payload = _decode_tree(doc["structure"], leaves)
        verify_manifest(payload, manifest)  # shapes/dtypes/crc32, per leaf
        # telemetry reports the BLOB size (state dominates it), matching
        # what the save side records — both cells of prefix_bytes must
        # measure the same thing
        return PrefixEntry(
            key=key,
            tokens=np.asarray(payload["tokens"], np.int32),
            state=payload["state"],
            t=int(doc["t"]),
            generation=gen,
        ), len(blob)

    # -- publish --------------------------------------------------------------

    def publish(self, tokens: Any, state: Any, *,
                skip_if_present: bool = True) -> Optional[int]:
        """Persist one prefix entry (a NEW generation; commit point = the
        manifest rename). ``state`` may hold device arrays — they are
        pulled to host HERE, which is why the engine's lexically
        sync-free admission path delegates the publish serialization to
        this module. ``skip_if_present`` (default) makes the common
        steady state cheap: an already-committed entry is not rewritten
        (re-publishing the same content is legal and converges — the
        fault-model tests force it with ``skip_if_present=False``).
        Returns the generation number, or None when skipped.

        Raises StoreUnavailableError (no disk syscalls) while the
        breaker is open — the publish queue in serving/batching.py maps
        that to a counted drop. One completed publish is one breaker
        sample."""
        toks = np.asarray(tokens, np.int32).reshape(1, -1)
        if toks.shape[1] % self.align != 0 or toks.shape[1] == 0:
            raise ValueError(
                f"prefix length {toks.shape[1]} is not a positive multiple "
                f"of the alignment {self.align}: in-scan pieces extend "
                "a state only from a chunk boundary"
            )
        if self.breaker is not None and not self.breaker.allow():
            raise StoreUnavailableError("prefix")
        try:
            return self._publish_op(toks, state, skip_if_present)
        except StoreUnavailableError:
            raise
        except OSError as e:
            self._exit(False, f"publish: {type(e).__name__}")
            raise

    def _publish_op(self, toks: np.ndarray, state: Any,
                    skip_if_present: bool) -> Optional[int]:
        key = self.key_for(toks)
        d = self._dir(key)
        gens = self.generations(key)
        if gens and skip_if_present:
            self._exit(True)  # the existence scan answered: store is up
            return None
        gen = (gens[-1] if gens else 0) + 1
        host_state = _host_tree(state)
        payload = {"tokens": toks, "state": host_state}
        leaves: List[np.ndarray] = []
        structure = _encode_tree(payload, leaves)
        manifest = build_manifest(payload, gen)
        if len(manifest["leaves"]) != len(leaves):
            raise AssertionError(
                "serialization order diverged from the manifest flatten "
                f"order ({len(leaves)} vs {manifest['n_leaves']} leaves)"
            )
        offset = 0
        for entry, arr in zip(manifest["leaves"], leaves):
            entry["offset"] = offset
            entry["nbytes"] = arr.nbytes
            offset += arr.nbytes
        blob = b"".join(arr.tobytes() for arr in leaves)
        doc = {
            "format": PREFIX_FORMAT_VERSION,
            "key": key,
            "params_id": self.params_id,
            "qmode": self.qmode,
            "align": self.align,
            "t": int(toks.shape[1]),
            "generation": gen,
            "structure": structure,
            "manifest": manifest,
        }
        # per-process-unique tmp names: unlike sessions (single writer
        # per conversation, router-fenced) prefixes have racing writers
        # by design — two replicas must each complete their own tmp and
        # converge via last-replace-wins on identical bytes
        nonce = f"{os.getpid():x}-{uuid.uuid4().hex[:8]}"

        def _write():
            fire("serve.prefix_save", step=gen)
            self._io_makedirs(d)
            tmp_bin = self._bin(d, gen) + f".tmp-{nonce}"
            with self._io_open(tmp_bin, "wb") as f:
                f.write(blob)
            self._io_replace(tmp_bin, self._bin(d, gen))
            tmp_json = self._json(d, gen) + f".tmp-{nonce}"
            with self._io_open(tmp_json, "w", encoding="utf-8") as f:
                json.dump(doc, f)
            self._io_replace(tmp_json, self._json(d, gen))  # commit point

        t0 = self._clock()
        call_with_retries(
            _write, self._retry,
            describe=f"prefix publish ({key} gen {gen})",
            should_abort=self._should_abort,
        )
        self._exit(True)
        self._observe("save", t0, len(blob))
        self._gc(d, keep_from=gen)
        return gen

    def _gc(self, d: str, keep_from: int) -> None:
        """Drop generations older than the newest ``keep`` plus STALE tmp
        files (advisory, like the session store's). Tmps younger than a
        minute are left alone: a racing replica's in-flight tmp looks
        identical to a stranded one, and unlinking it mid-write would
        fail that publisher's ``os.replace`` — burning its retry budget
        on interference this process caused (the convergence contract
        says racers complete independently)."""
        floor = keep_from - self.keep + 1
        now = time.time()
        try:
            names = self._io_listdir(d)
        except (OSError, StoreUnavailableError):
            return  # advisory: the next publish after recovery re-runs it
        for name in names:
            path = os.path.join(d, name)
            try:
                if ".tmp-" in name:
                    if now - os.path.getmtime(path) > 60.0:
                        self._io_remove(path)
                    continue
                if not name.startswith("gen-"):
                    continue
                gen = int(name.split(".", 1)[0][len("gen-"):])
                if gen < floor:
                    self._io_remove(path)
            except (OSError, ValueError, StoreUnavailableError):
                continue

    def delete(self, key: str) -> None:
        d = self._dir(key)
        try:
            names = self._io_listdir(d)
        except (OSError, StoreUnavailableError):
            return  # best-effort, like _gc
        for name in names:
            try:
                self._io_remove(os.path.join(d, name))
            except (OSError, StoreUnavailableError):
                pass
        try:
            self._io_rmdir(d)
        except (OSError, StoreUnavailableError):
            pass


def _host_tree(tree: Any) -> Any:
    """Device pytree -> host numpy pytree (the store's one sanctioned
    device sync — publish-side only; the hit path copies a host row in)."""
    import jax

    return jax.tree.map(np.asarray, jax.device_get(tree))


__all__ = [
    "PrefixStore", "PrefixEntry", "params_identity",
    "overrides_fingerprint",
]
