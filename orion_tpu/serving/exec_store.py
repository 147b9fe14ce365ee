"""Content-addressed AOT executable store: warm replicas in milliseconds.

The paper's O(1)-state decode makes a replica's working set tiny — the
only expensive thing about spawning one is the jit compile per
``(slots, chunk, bucket, qmode, tp)`` footprint. Tier E proved that
compile universe is CLOSED (``analysis/programs.py``): every program a
replica will ever run is statically enumerable from its footprint. This
module is the payoff: serialize each compiled executable ONCE
(``aot.warm``), and every subsequent replica of the same shape
*downloads* its programs instead of compiling them —
``jax.experimental.serialize_executable`` round-trips an XLA executable
across processes in milliseconds where the compile takes seconds.

Addressing is by CONTENT, not coordination (the prefix store's model,
PR 11): the key hashes everything an executable's validity depends on —

- the **ProgramDecl identity** (``decl_fingerprint``): the declared
  row's (module, qualname, static_args, donate_argnums). A refactor
  that moves or re-keys a program changes its declaration and therefore
  its address; stale executables become unreachable, never wrongly hit.
- the **golden-snapshot identity** (the server's ``params_id|qmode``
  weights identity): executables are specialized on sharding and
  quantization layout, and two checkpoints of one config must not share
  address space.
- the **plan identity** (the footprint's ident dict — exactly the
  fields ``aot.decode_plan`` keys its inventory by), plus the
  **sampling fingerprint**: ``SampleConfig`` is a jit static, so one
  footprint serving two sampling presets is two executables.
- the **runtime fingerprint** (jax + jaxlib versions + backend): a
  serialized executable is an opaque backend artifact; version skew must
  be a clean MISS (cold compile), never a deserialization crash.

Durability is the prefix store's generation scheme verbatim:
``gen-%06d.bin`` (the pickled ``(payload, in_tree, out_tree)`` triple)
+ ``gen-%06d.json`` manifest under ``directory/<key>/``, manifest
rename as the COMMIT POINT, per-process-nonce tmp names so racing
publishers (two ``aot warm`` runs, a warm run racing a replica) each
complete independently and converge on byte-compatible content.

Tiering: an in-process LRU of LOADED executables (a lookup that already
deserialized never pays again), then a node-local disk cache
(``local_dir``, write-through on shared hits), then the shared store.
Every failure at every tier — unreadable file, truncated pickle, sha
mismatch, version skew, open breaker — degrades to a MISS with a
counter: the engine's jit fallback is always correct, so the cold path
is the error handler and a request NEVER fails here (the chaos suite
pins this).

The stats dict is written only by its owner's thread (the engine
scheduler on the serving side, the CLI main thread under ``aot warm``)
and read by metrics gauge closures — single-writer int slots, no lock
by design (see serving/locks.py's lock-free designs note).
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import json
import os
import pickle
import time
import uuid
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

from orion_tpu.resilience.breaker import CircuitBreaker, StoreUnavailableError
from orion_tpu.resilience.inject import fire
from orion_tpu.resilience.retry import RetryPolicy, call_with_retries

EXEC_FORMAT_VERSION = 1


def runtime_fingerprint() -> str:
    """The jax/jaxlib/backend triple a serialized executable is only
    valid under. Part of the content address, so a version bump makes
    every old entry a clean miss (cold compile) instead of a
    deserialization error — the "never an error" half of version skew."""
    import jax
    import jaxlib

    return (
        f"jax-{jax.__version__}|jaxlib-{jaxlib.__version__}"
        f"|{jax.default_backend()}"
    )


def decl_fingerprint(kind: str) -> str:
    """Stable hash of ``kind``'s ProgramDecl row — the Tier E identity
    the store key derives from. Covers exactly the fields that pin the
    executable's call convention: module, qualname, static parameter
    names, donation. An UNDECLARED kind gets a sentinel fingerprint (it
    still stores, but ``analysis/staleness.py`` flags its entries as
    dead — nothing in the declared universe can ever hit them)."""
    from orion_tpu.analysis.programs import PROGRAMS

    for d in PROGRAMS:
        if d.name == kind and d.section == "decode":
            doc = json.dumps(
                [d.name, d.module, d.qualname, list(d.static_args),
                 list(d.donate_argnums)],
            )
            return hashlib.sha256(doc.encode()).hexdigest()[:16]
    return f"undeclared:{kind}"


def sample_fingerprint(sample_cfg: Any) -> str:
    """Stable hash of a SampleConfig — it is a jit static, so it is part
    of the executable's identity exactly like the footprint fields."""
    doc = json.dumps(dataclasses.asdict(sample_cfg), sort_keys=True)
    return hashlib.sha256(doc.encode()).hexdigest()[:16]


class ExecStore:
    """Content-addressed serialized executables under
    ``directory/<key>/`` with an in-process LRU and an optional
    node-local disk tier.

    ``identity`` is the server's weights identity (``params_id|qmode``)
    — the same string that namespaces session/prefix state, because an
    executable is specialized on the same (config, checkpoint, qmode)
    triple. ``max_resident`` bounds the loaded-executable LRU (an
    executable is a few hundred KB of backend code; a replica's whole
    universe is a handful). ``observer``: host-only telemetry tap
    ``(op, ms, nbytes)``, op in {"load", "save"}."""

    def __init__(
        self,
        directory: str,
        identity: str,
        *,
        local_dir: Optional[str] = None,
        keep: int = 2,
        max_resident: int = 32,
        retry: Optional[RetryPolicy] = None,
        should_abort: Optional[Callable[[], bool]] = None,
        observer: Optional[Callable[[str, float, int], None]] = None,
        clock: Callable[[], float] = time.monotonic,
        breaker: Optional[CircuitBreaker] = None,
    ):
        if keep < 1:
            raise ValueError(f"keep must be >= 1, got {keep}")
        self.directory = os.path.abspath(directory)
        self.identity = str(identity)
        self.local_dir = os.path.abspath(local_dir) if local_dir else None
        self.keep = int(keep)
        self.max_resident = int(max_resident)
        self._retry = retry if retry is not None else RetryPolicy()
        self._should_abort = should_abort
        self._observer = observer
        self._clock = clock
        self.breaker = breaker
        # single-writer counters (owner thread only); gauge closures read
        self.stats: Dict[str, int] = {
            "hits": 0, "misses": 0, "publishes": 0,
            "fallback_compiles": 0, "errors": 0,
        }
        # key -> loaded Compiled, true LRU over DESERIALIZED executables
        self._resident: "collections.OrderedDict[str, Any]" = (
            collections.OrderedDict()
        )
        os.makedirs(self.directory, exist_ok=True)
        if self.local_dir:
            os.makedirs(self.local_dir, exist_ok=True)

    def _observe(self, op: str, t0: float, nbytes: int) -> None:
        if self._observer is not None:
            try:
                self._observer(op, (self._clock() - t0) * 1e3, nbytes)
            except Exception:
                pass  # telemetry must never fail the I/O it measures

    # -- breaker gate and raw I/O ---------------------------------------------
    # Same discipline as the prefix/session stores (lint rule
    # ``raw-store-io``): the ``_io_*`` helpers are the module's only
    # direct filesystem touch points and fail fast while the breaker is
    # open, so an open breaker turns every lookup into an O(1)-host-work
    # MISS (cold compile) with zero disk probes.

    def _exit(self, ok: bool, reason: str = "") -> None:
        if self.breaker is None:
            return
        if ok:
            self.breaker.record_success()
        else:
            self.breaker.record_failure(reason)

    def _blocked_check(self) -> None:
        if self.breaker is not None and self.breaker.blocked():
            raise StoreUnavailableError("exec")

    def _io_open(self, path: str, mode: str = "r", **kw):
        self._blocked_check()
        return open(path, mode, **kw)

    def _io_listdir(self, path: str) -> List[str]:
        """Directory scan, or [] when the entry doesn't exist — an
        unpublished executable is a normal miss, not a store fault."""
        self._blocked_check()
        fire("serve.exec_scan")
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

    def key_for(self, ident: Dict[str, Any], sample: str = "") -> str:
        """Content hash of one executable's full identity: weights
        identity, runtime fingerprint, the kind's ProgramDecl
        fingerprint, the plan ident dict, and the sampling fingerprint.
        Every replica of a fleet resolves the same footprint to the same
        key with no registry and no invalidation protocol."""
        doc = json.dumps(dict(ident), sort_keys=True, default=str)
        h = hashlib.sha256()
        h.update(b"orion-exec-v1|")
        h.update(self.identity.encode())
        h.update(b"|")
        h.update(runtime_fingerprint().encode())
        h.update(b"|")
        h.update(decl_fingerprint(str(ident.get("kind", ""))).encode())
        h.update(b"|")
        h.update(doc.encode())
        h.update(b"|")
        h.update(sample.encode())
        return h.hexdigest()[:32]

    @staticmethod
    def _bin(d: str, gen: int) -> str:
        return os.path.join(d, f"gen-{gen:06d}.bin")

    @staticmethod
    def _json(d: str, gen: int) -> str:
        return os.path.join(d, f"gen-{gen:06d}.json")

    def _generations(self, root: str, key: str) -> List[int]:
        """COMMITTED generations of one entry in one tier (manifest
        present) — a ``.bin`` without its ``.json`` is a torn publish
        and is invisible. Raises StoreUnavailableError without touching
        disk while the breaker is open."""
        out = []
        for name in self._io_listdir(os.path.join(root, key)):
            if name.startswith("gen-") and name.endswith(".json"):
                try:
                    out.append(int(name[len("gen-"):-len(".json")]))
                except ValueError:
                    continue
        return sorted(out)

    def generations(self, key: str) -> List[int]:
        return self._generations(self.directory, key)

    def list_keys(self) -> List[str]:
        return sorted(
            n for n in self._io_listdir(self.directory)
            if self.generations(n)
        )

    def has(self, ident: Dict[str, Any], sample: str = "") -> bool:
        """Is a committed entry for this identity in the SHARED store?
        The ``aot --verify`` / ``warm`` short-circuit probe: one listdir,
        no payload read, no deserialization. Degrades to False on any
        store trouble (the caller then lowers/compiles — always
        correct)."""
        try:
            found = bool(self.generations(self.key_for(ident, sample)))
        except StoreUnavailableError:
            return False
        except OSError as e:
            self._exit(False, f"has: {type(e).__name__}")
            return False
        self._exit(True)
        return found

    # -- lookup ---------------------------------------------------------------

    def lookup(self, ident: Dict[str, Any], sample: str = "") -> Optional[Any]:
        """The loaded executable for this identity, or None. Tier order:
        resident LRU (already deserialized), node-local disk, shared
        store (write-through to local on hit). Damage of ANY kind —
        unreadable files, truncated payload, sha mismatch, a pickle that
        won't load, backend refusal — degrades to trying the previous
        generation, then the next tier, then a miss: the jit fallback
        can always recompile, so the cold path is the error handler and
        the engine NEVER sees an exception from here.

        Breaker policy mirrors the prefix store: an OPEN breaker is an
        INSTANT miss — one host check, zero disk probes. One completed
        walk is one breaker sample; local-tier damage is noise, only
        shared-tier OSErrors count as outage evidence."""
        key = self.key_for(ident, sample)
        got = self._resident.get(key)
        if got is not None:
            self._resident.move_to_end(key)
            self.stats["hits"] += 1
            return got
        if self.breaker is not None and not self.breaker.allow():
            self.stats["misses"] += 1
            return None  # open: cold compile, fail-fast
        exe, os_fail, aborted = None, None, False
        try:
            exe, os_fail, aborted = self._lookup_walk(key)
        except BaseException:
            self._exit(False, "lookup: aborted")
            raise
        if not aborted:
            if os_fail is not None:
                self._exit(False, f"lookup: {type(os_fail).__name__}")
            else:
                self._exit(True)
        if exe is None:
            self.stats["misses"] += 1
            return None
        self.stats["hits"] += 1
        self._resident[key] = exe
        self._resident.move_to_end(key)
        while len(self._resident) > self.max_resident:
            self._resident.popitem(last=False)
        return exe

    def _lookup_walk(
        self, key: str
    ) -> Tuple[Optional[Any], Optional[OSError], bool]:
        """The tier walk of :meth:`lookup`; returns ``(executable,
        first shared-tier OSError, aborted-by-open-breaker)`` and never
        lets a store error escape."""
        os_fail: Optional[OSError] = None
        tiers = ([(self.local_dir, False)] if self.local_dir else [])
        tiers.append((self.directory, True))
        for root, shared in tiers:
            try:
                gens = self._generations(root, key)
            except StoreUnavailableError:
                return None, None, True
            except OSError as e:
                if shared:
                    os_fail = e
                continue
            t0 = self._clock()
            for gen in reversed(gens):
                try:
                    blob, doc = self._load_gen(root, key, gen)
                except StoreUnavailableError:
                    return None, os_fail, True
                except OSError as e:  # store-shaped: counts as evidence
                    if shared:
                        os_fail = e
                    warnings.warn(
                        f"exec {key} generation {gen} is unreadable "
                        f"({type(e).__name__}: {str(e)[:200]}); trying "
                        "the previous generation",
                        stacklevel=2,
                    )
                    continue
                except Exception as e:  # damaged payloads: many types
                    self.stats["errors"] += 1
                    warnings.warn(
                        f"exec {key} generation {gen} is corrupt or "
                        f"incomplete ({type(e).__name__}: {str(e)[:200]});"
                        " trying the previous generation",
                        stacklevel=2,
                    )
                    continue
                exe = self._deserialize(key, gen, blob, doc)
                if exe is None:
                    continue
                self._observe("load", t0, len(blob))
                if shared and self.local_dir:
                    self._write_through(key, gen, blob, doc)
                return exe, os_fail, False
        return None, os_fail, False

    def _load_gen(self, root: str, key: str, gen: int) -> Tuple[bytes, dict]:
        """One generation's (blob, manifest) from one tier, verified:
        format version, weights identity, runtime fingerprint, payload
        length and sha256. Raises on any mismatch (the caller degrades)."""
        d = os.path.join(root, key)

        def _read():
            fire("serve.exec_load", step=gen)
            with self._io_open(self._json(d, gen)) as f:
                doc = json.load(f)
            with self._io_open(self._bin(d, gen), "rb") as f:
                blob = f.read()
            return doc, blob

        doc, blob = call_with_retries(
            _read, self._retry,
            describe=f"exec load ({key} gen {gen})",
            should_abort=self._should_abort,
        )
        if doc.get("format") != EXEC_FORMAT_VERSION:
            raise ValueError(
                f"exec {key} gen {gen}: format {doc.get('format')} != "
                f"{EXEC_FORMAT_VERSION}"
            )
        if doc.get("identity") != self.identity:
            raise ValueError(
                f"exec {key} gen {gen} was published for identity "
                f"{doc.get('identity')!r}, not {self.identity!r}"
            )
        if doc.get("runtime") != runtime_fingerprint():
            # defense in depth: the runtime is already in the key, so
            # this only fires on a hash collision or a hand-moved file
            raise ValueError(
                f"exec {key} gen {gen}: runtime skew "
                f"({doc.get('runtime')} vs {runtime_fingerprint()})"
            )
        if len(blob) != int(doc.get("nbytes", -1)):
            raise ValueError(
                f"exec {key} gen {gen}: payload truncated "
                f"({len(blob)} of {doc.get('nbytes')} bytes)"
            )
        if hashlib.sha256(blob).hexdigest() != doc.get("sha256"):
            raise ValueError(f"exec {key} gen {gen}: payload sha mismatch")
        return blob, doc

    def _deserialize(self, key: str, gen: int, blob: bytes,
                     doc: dict) -> Optional[Any]:
        """Pickle triple -> executable loaded onto the devices it was
        compiled for (the manifest's ``devices``, by id: one for an
        unsharded engine, the mesh's for a tp engine — jax's default is
        every visible device, which refuses a one-device program's
        arguments); None (counted, warned) on any failure — the backend
        gets the final say on whether this artifact is loadable, and its
        refusal is a miss, not an error."""
        import jax
        from jax.experimental import serialize_executable as se

        try:
            payload, in_tree, out_tree = pickle.loads(blob)
            devices = None
            if doc.get("devices"):
                by_id = {d.id: d for d in jax.devices()}
                devices = [by_id[i] for i in doc["devices"]]
            return se.deserialize_and_load(
                payload, in_tree, out_tree, execution_devices=devices
            )
        except Exception as e:
            self.stats["errors"] += 1
            warnings.warn(
                f"exec {key} gen {gen} failed to deserialize "
                f"({type(e).__name__}: {str(e)[:200]}); falling back to "
                "jit compile",
                stacklevel=2,
            )
            return None

    def _write_through(self, key: str, gen: int, blob: bytes,
                       doc: dict) -> None:
        """Best-effort copy of a shared-tier hit into the node-local
        tier at the same generation (nonce-replace convergence, racers
        welcome). Failure is silent: the local tier is an optimization,
        never evidence about the shared store's health."""
        try:
            d = os.path.join(self.local_dir, key)
            self._io_makedirs(d)
            nonce = f"{os.getpid():x}-{uuid.uuid4().hex[:8]}"
            tmp_bin = self._bin(d, gen) + f".tmp-{nonce}"
            with self._io_open(tmp_bin, "wb") as f:
                f.write(blob)
            self._io_replace(tmp_bin, self._bin(d, gen))
            tmp_json = self._json(d, gen) + f".tmp-{nonce}"
            with self._io_open(tmp_json, "w", encoding="utf-8") as f:
                json.dump(doc, f)
            self._io_replace(tmp_json, self._json(d, gen))
        except (OSError, StoreUnavailableError):
            pass

    # -- publish --------------------------------------------------------------

    def publish(self, ident: Dict[str, Any], compiled: Any,
                sample: str = "", *,
                skip_if_present: bool = True) -> Optional[int]:
        """Serialize ``compiled`` and persist it as a NEW generation
        (commit point = the manifest rename). ``skip_if_present``
        (default) makes re-warming cheap: an already-committed entry is
        not rewritten. Returns the generation number, or None when
        skipped.

        Raises StoreUnavailableError (no disk syscalls) while the
        breaker is open, and lets serialization errors surface — the
        warm path records them per-entry and moves on; nothing at
        serving time ever publishes."""
        from jax.experimental import serialize_executable as se

        key = self.key_for(ident, sample)
        if self.breaker is not None and not self.breaker.allow():
            raise StoreUnavailableError("exec")
        try:
            gens = self.generations(key)
        except StoreUnavailableError:
            raise
        except OSError as e:
            self._exit(False, f"publish: {type(e).__name__}")
            raise
        if gens and skip_if_present:
            self._exit(True)  # the existence scan answered: store is up
            return None
        gen = (gens[-1] if gens else 0) + 1
        payload, in_tree, out_tree = se.serialize(compiled)
        blob = pickle.dumps((payload, in_tree, out_tree))
        doc = {
            "format": EXEC_FORMAT_VERSION,
            "key": key,
            "identity": self.identity,
            "runtime": runtime_fingerprint(),
            "decl": decl_fingerprint(str(ident.get("kind", ""))),
            "ident": dict(ident),
            "devices": [
                d.id for d in compiled.runtime_executable().local_devices()
            ],
            "sample": sample,
            "generation": gen,
            "nbytes": len(blob),
            "sha256": hashlib.sha256(blob).hexdigest(),
        }
        d = os.path.join(self.directory, key)
        # per-process-unique tmp names: publishers race by design (two
        # warm runs, a warm run racing a replica's preflight) — each
        # completes its own tmp and the last replace wins with
        # equivalent content (same compiler, same inputs)
        nonce = f"{os.getpid():x}-{uuid.uuid4().hex[:8]}"

        def _write():
            fire("serve.exec_save", step=gen)
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
        try:
            call_with_retries(
                _write, self._retry,
                describe=f"exec publish ({key} gen {gen})",
                should_abort=self._should_abort,
            )
        except StoreUnavailableError:
            raise
        except OSError as e:
            self._exit(False, f"publish: {type(e).__name__}")
            raise
        self._exit(True)
        self.stats["publishes"] += 1
        self._observe("save", t0, len(blob))
        self._gc(d, keep_from=gen)
        return gen

    def count_fallback(self) -> None:
        """One jit compile happened that a store hit would have avoided
        — the engine calls this from its compile watch so the warm
        path's '0 fallback compiles' acceptance is a readable counter."""
        self.stats["fallback_compiles"] += 1

    def resident_count(self) -> int:
        return len(self._resident)

    # -- inventory and gc -----------------------------------------------------

    def entries(self) -> List[dict]:
        """Newest committed manifest per key in the SHARED store —
        the staleness pass's inventory (each doc carries the ident dict
        and the decl fingerprint it was published under). Unreadable
        entries are skipped: this is an audit walk, not a serving path."""
        out = []
        for key in self.list_keys():
            try:
                gens = self.generations(key)
                if not gens:
                    continue
                d = os.path.join(self.directory, key)
                with self._io_open(self._json(d, gens[-1])) as f:
                    out.append(json.load(f))
            except (OSError, ValueError, StoreUnavailableError):
                continue
        return out

    def _gc(self, d: str, keep_from: int) -> None:
        """Drop generations older than the newest ``keep`` plus STALE
        tmp files (advisory; racers' young tmps are left alone, exactly
        the prefix store's convergence contract)."""
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
        d = os.path.join(self.directory, key)
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


def main(argv: Optional[List[str]] = None) -> int:
    """``python -m orion_tpu.serving.exec_store {ls,gc} --dir D`` —
    inventory and garbage collection. ``gc`` deletes the DEAD entries
    the staleness audit identifies (kind undeclared, or declaration
    drifted since publication — content addressing means nothing can
    ever hit them again); ``--dry-run`` only reports. Live entries are
    never touched: re-warming is cheap but not free, and gc must be
    safe to cron."""
    import argparse

    p = argparse.ArgumentParser("orion_tpu.serving.exec_store")
    p.add_argument("cmd", choices=["ls", "gc"])
    p.add_argument("--dir", required=True,
                   help="shared exec store directory")
    p.add_argument("--dry-run", action="store_true",
                   help="gc: report dead entries without deleting")
    args = p.parse_args(argv)

    from orion_tpu.analysis.staleness import dead_exec_entries

    # identity is irrelevant for inventory/gc (manifests carry their
    # own); the store object just provides the walk + delete machinery
    store = ExecStore(args.dir, identity="<audit>")
    entries = store.entries()
    dead = dead_exec_entries(entries)
    dead_keys = {d.get("key") for d in dead}
    if args.cmd == "ls":
        for doc in entries:
            ident = doc.get("ident") or {}
            mark = " DEAD" if doc.get("key") in dead_keys else ""
            print(f"{doc.get('key')} kind={ident.get('kind')} "
                  f"gen={doc.get('generation')} "
                  f"nbytes={doc.get('nbytes')}{mark}")
        print(f"{len(entries)} entries, {len(dead)} dead")
        return 0
    for doc in dead:
        key = str(doc.get("key"))
        if args.dry_run:
            print(f"would delete {key} "
                  f"(kind={(doc.get('ident') or {}).get('kind')})")
        else:
            store.delete(key)
            print(f"deleted {key} "
                  f"(kind={(doc.get('ident') or {}).get('kind')})")
    print(f"{len(dead)} dead of {len(entries)} entries"
          + (" (dry run)" if args.dry_run else " removed"))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())


__all__ = [
    "ExecStore", "EXEC_FORMAT_VERSION", "runtime_fingerprint",
    "decl_fingerprint", "sample_fingerprint", "main",
]
