"""Native runtime bindings (SURVEY.md N2): ctypes over liborion_runtime.so,
with the pure-Python implementations as drop-in fallback.

The .so is optional by design — every API here has a Python twin with the
identical determinism contract (same splitmix64 window stream, same
byte-level vocab), so the framework runs anywhere and the native path is a
pure speedup. ``native_available()`` reports which path is live;
``build()`` compiles the .so in-tree with g++.
"""

from __future__ import annotations

import ctypes
import json
import os
import subprocess
import sys
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO_PATH = os.path.join(_DIR, "liborion_runtime.so")

_lib: Optional[ctypes.CDLL] = None
_unloadable = False  # a present .so refused to load: don't retry, don't repeat


def build(quiet: bool = True) -> bool:
    """Compile liborion_runtime.so. Returns success."""
    try:
        subprocess.run(
            ["sh", os.path.join(_DIR, "build.sh")],
            check=True,
            capture_output=quiet,
        )
        return True
    except (subprocess.CalledProcessError, FileNotFoundError):
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _unloadable
    if _lib is not None or _unloadable:
        return _lib
    if not os.path.exists(_SO_PATH) and os.environ.get("ORION_TPU_BUILD_RUNTIME"):
        build()
    if not os.path.exists(_SO_PATH):
        return None
    try:
        lib = ctypes.CDLL(_SO_PATH)
    except OSError as e:
        # present but unloadable (built for another machine): the same
        # Python twins as a missing .so, said once
        print(f"orion_tpu.runtime: {_SO_PATH} cannot load ({e}); using the "
              "Python implementations", file=sys.stderr)
        _unloadable = True
        return None
    lib.orion_loader_open.restype = ctypes.c_void_p
    lib.orion_loader_open.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_int]
    lib.orion_loader_n_tokens.restype = ctypes.c_int64
    lib.orion_loader_n_tokens.argtypes = [ctypes.c_void_p]
    lib.orion_loader_batch.restype = None
    lib.orion_loader_batch.argtypes = [
        ctypes.c_void_p,
        ctypes.c_uint64,
        ctypes.c_uint64,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int,
    ]
    lib.orion_loader_close.restype = None
    lib.orion_loader_close.argtypes = [ctypes.c_void_p]
    try:  # explicit-starts gather (absent in .so builds predating r5)
        lib.orion_loader_gather.restype = None
        lib.orion_loader_gather.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int,
        ]
    except AttributeError:
        pass
    lib.orion_byte_encode.restype = ctypes.c_int64
    lib.orion_byte_encode.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.orion_byte_encode_file.restype = ctypes.c_int64
    lib.orion_byte_encode_file.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    try:  # corpusgen entry points (absent in .so builds predating r5)
        lib.orion_corpusgen_fit.restype = ctypes.c_void_p
        lib.orion_corpusgen_fit.argtypes = [
            ctypes.POINTER(ctypes.c_uint16),
            ctypes.c_int64,
        ]
        lib.orion_corpusgen_sample.restype = None
        lib.orion_corpusgen_sample.argtypes = [
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.c_double,
            ctypes.c_double,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint16),
        ]
        lib.orion_corpusgen_destroy.restype = None
        lib.orion_corpusgen_destroy.argtypes = [ctypes.c_void_p]
    except AttributeError:
        pass
    try:  # BPE entry points (absent in .so builds predating bpe.cc)
        lib.orion_bpe_create.restype = ctypes.c_void_p
        lib.orion_bpe_create.argtypes = [
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int64,
        ]
        lib.orion_bpe_destroy.restype = None
        lib.orion_bpe_destroy.argtypes = [ctypes.c_void_p]
        lib.orion_bpe_encode.restype = ctypes.c_int64
        lib.orion_bpe_encode.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32),
        ]
    except AttributeError:
        pass
    _lib = lib
    return lib


def native_available() -> bool:
    return _load() is not None


class NativeTokenBinDataset:
    """C++ mmap+gather loader; same (seed, step) -> batch contract as the
    Python TokenBinDataset (training/data.py). Raises ImportError when the
    .so is missing — callers use ``make_fastest_dataset`` to auto-fallback."""

    def __init__(self, path: str, seq_len: int, n_threads: int = 4):
        lib = _load()
        if lib is None:
            raise ImportError("liborion_runtime.so not built (run runtime.build())")
        meta_path = path + ".meta.json"
        if os.path.exists(meta_path):
            with open(meta_path) as f:
                meta = json.load(f)
            dtype = np.dtype(meta["dtype"])
            self.vocab_size = int(meta.get("vocab_size", np.iinfo(dtype).max + 1))
        else:
            dtype = np.dtype(np.uint16)
            self.vocab_size = 65536
        self._lib = lib
        self._h = lib.orion_loader_open(
            path.encode(), seq_len, int(dtype.itemsize)
        )
        if not self._h:
            raise OSError(f"orion_loader_open failed for {path}")
        self.seq_len = seq_len
        self.n_threads = n_threads
        self.n_tokens = lib.orion_loader_n_tokens(self._h)
        self.n_windows = self.n_tokens - seq_len - 1

    def batch(self, seed: int, step: int, batch_size: int) -> np.ndarray:
        out = np.empty((batch_size, self.seq_len + 1), dtype=np.int32)
        self._lib.orion_loader_batch(
            self._h,
            ctypes.c_uint64(seed),
            ctypes.c_uint64(step),
            batch_size,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            self.n_threads,
        )
        return out

    def gather(self, starts: np.ndarray) -> np.ndarray:
        """[len(starts), seq_len+1] int32 windows at explicit offsets (the
        sharded-dataset building block; requires an r5+ .so)."""
        if not hasattr(self._lib, "orion_loader_gather"):
            raise ImportError("liborion_runtime.so predates orion_loader_gather")
        starts = np.ascontiguousarray(starts, dtype=np.int64)
        out = np.empty((starts.size, self.seq_len + 1), dtype=np.int32)
        self._lib.orion_loader_gather(
            self._h,
            starts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            starts.size,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            self.n_threads,
        )
        return out

    def close(self):
        if self._h:
            self._lib.orion_loader_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def make_fastest_dataset(path: str, seq_len: int):
    """Native loader if the .so is present, Python mmap fallback otherwise."""
    if native_available():
        return NativeTokenBinDataset(path, seq_len)
    from orion_tpu.training.data import TokenBinDataset

    return TokenBinDataset(path, seq_len)


class NativeBPE:
    """C++ BPE encoder (runtime/bpe.cc); token-for-token identical to the
    Python ``utils/bpe.py`` encode path (contract-tested). Create from the
    tokenizer's merge list; encode() takes/returns what the Python does."""

    def __init__(self, merges):
        lib = _load()
        if lib is None or not hasattr(lib, "orion_bpe_create"):
            raise ImportError("liborion_runtime.so missing BPE entry points")
        flat = np.asarray(merges, dtype=np.int32).reshape(-1)
        self._lib = lib
        self._h = lib.orion_bpe_create(
            flat.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), len(merges)
        )
        if not self._h:
            raise OSError("orion_bpe_create failed")

    def encode(self, text: str):
        data = text.encode("utf-8")
        if not data:
            return []
        out = np.empty(len(data), dtype=np.int32)
        n = self._lib.orion_bpe_encode(
            self._h,
            data,
            len(data),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
        return out[:n].tolist()

    def __del__(self):
        try:
            if self._h:
                self._lib.orion_bpe_destroy(self._h)
                self._h = None
        except Exception:
            pass


class NativeCorpusGen:
    """C++ interpolated-trigram corpus sampler (runtime/corpusgen.cc);
    bit-identical to training/corpusgen.py::MarkovModel (contract-tested)
    at ~10M tokens/s — what makes the 100M+-token synthetic pretraining
    corpus (VERDICT r4 #2) a minutes-scale operation."""

    def __init__(self, corpus: np.ndarray):
        lib = _load()
        if lib is None or not hasattr(lib, "orion_corpusgen_fit"):
            raise ImportError("liborion_runtime.so missing corpusgen entries")
        # keep our own copy: the model holds a pointer into this buffer
        self._corpus = np.ascontiguousarray(corpus, dtype=np.uint16)
        self._lib = lib
        self._h = lib.orion_corpusgen_fit(
            self._corpus.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
            self._corpus.size,
        )
        if not self._h:
            raise OSError("orion_corpusgen_fit failed (need >= 3 tokens)")

    def sample(self, seed: int, n_out: int, p_uni: float = 0.02,
               p_bi: float = 0.15) -> np.ndarray:
        out = np.empty(n_out, dtype=np.uint16)
        self._lib.orion_corpusgen_sample(
            self._h, ctypes.c_uint64(seed), p_uni, p_bi, n_out,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
        )
        return out

    def close(self):
        if self._h:
            self._lib.orion_corpusgen_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def byte_encode_file(in_path: str, out_path: str) -> int:
    """Stream a raw file into a uint16 token-bin (+ sidecar). Native if
    available, Python otherwise. Returns token count."""
    lib = _load()
    if lib is not None:
        n = lib.orion_byte_encode_file(in_path.encode(), out_path.encode())
        if n < 0:
            raise OSError(f"orion_byte_encode_file failed: {in_path}")
    else:
        with open(in_path, "rb") as f:
            data = f.read()
        np.frombuffer(data, dtype=np.uint8).astype(np.uint16).tofile(out_path)
        n = len(data)
    with open(out_path + ".meta.json", "w") as f:
        json.dump({"dtype": "uint16", "count": int(n), "vocab_size": 256}, f)
    return int(n)


__all__ = [
    "build",
    "native_available",
    "NativeTokenBinDataset",
    "NativeBPE",
    "NativeCorpusGen",
    "make_fastest_dataset",
    "byte_encode_file",
]
