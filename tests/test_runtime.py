"""Native runtime tests (SURVEY.md N1-N3): build the .so, then assert the
C++ loader produces bit-identical batches to the Python fallback (the
determinism contract that makes the two paths interchangeable across
checkpoint resume), and the byte tokenizer paths agree."""

import numpy as np
import pytest

from orion_tpu import runtime
from orion_tpu.training.data import TokenBinDataset, window_starts, write_token_bin


@pytest.fixture(scope="module")
def so_built():
    ok = runtime.native_available() or runtime.build()
    if not ok or not runtime.native_available():
        pytest.skip("g++ unavailable; native runtime not built")
    return True


@pytest.fixture()
def token_file(tmp_path):
    path = str(tmp_path / "toks.bin")
    toks = (np.arange(5000, dtype=np.int64) * 7919) % 50000
    write_token_bin(path, toks, vocab_size=50000)
    return path


def test_native_matches_python_loader(so_built, token_file):
    seq = 33
    py = TokenBinDataset(token_file, seq)
    cc = runtime.NativeTokenBinDataset(token_file, seq)
    assert cc.n_windows == py.n_windows
    for seed, step, b in [(0, 0, 4), (1, 0, 8), (0, 123, 3), (42, 7, 16)]:
        np.testing.assert_array_equal(cc.batch(seed, step, b), py.batch(seed, step, b))
    cc.close()


def test_native_loader_uint16(so_built, tmp_path):
    path = str(tmp_path / "small.bin")
    toks = np.arange(300) % 250
    write_token_bin(path, toks, vocab_size=250)  # uint16 file
    py = TokenBinDataset(path, 16)
    cc = runtime.NativeTokenBinDataset(path, 16)
    np.testing.assert_array_equal(cc.batch(5, 5, 6), py.batch(5, 5, 6))
    cc.close()


def test_window_starts_deterministic():
    a = window_starts(3, 9, 32, 1000)
    b = window_starts(3, 9, 32, 1000)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, window_starts(3, 10, 32, 1000))
    assert (a >= 0).all() and (a < 1000).all()


def test_byte_encode_file(so_built, tmp_path):
    src = tmp_path / "text.txt"
    src.write_bytes(b"hello orion tpu" * 100)
    out = str(tmp_path / "text.bin")
    n = runtime.byte_encode_file(str(src), out)
    assert n == 1500
    ds = TokenBinDataset(out, 8)
    assert ds.vocab_size == 256
    b = ds.batch(0, 0, 2)
    assert (b < 256).all()


def test_byte_encode_file_python_fallback(tmp_path, monkeypatch):
    monkeypatch.setattr(runtime, "_load", lambda: None)
    src = tmp_path / "t.txt"
    src.write_bytes(b"abcdef" * 50)
    out = str(tmp_path / "t.bin")
    n = runtime.byte_encode_file(str(src), out)
    assert n == 300
    arr = np.fromfile(out, dtype=np.uint16)
    assert arr[0] == ord("a")


def test_make_fastest_dataset(token_file):
    ds = runtime.make_fastest_dataset(token_file, 16)
    b = ds.batch(0, 0, 2)
    assert b.shape == (2, 17)


# -- corpus generator + sharded datasets (r5, VERDICT r4 #2) ----------------


@pytest.fixture()
def small_corpus():
    # structured stream (not uniform noise) so trigram contexts repeat
    rng = np.random.default_rng(3)
    a = rng.integers(0, 50, 4000)
    b = (a * 7 + np.roll(a, 1) * 3) % 211
    return (a * 211 + b % 37).astype(np.uint16)


def test_corpusgen_native_matches_python(so_built, small_corpus):
    """The C++ sampler and the Python twin share the draw stream
    (splitmix64(seed+k), two draws per token) and the successor order
    (corpus-position) — bit-identical output is the contract that lets
    tests validate what the native path generates at GB scale."""
    from orion_tpu.training.corpusgen import MarkovModel

    g = runtime.NativeCorpusGen(small_corpus)
    fast = g.sample(42, 3000)
    g.close()
    slow = MarkovModel(small_corpus).sample(42, 3000)
    np.testing.assert_array_equal(fast, slow)


def test_corpusgen_deterministic_and_seed_sensitive(so_built, small_corpus):
    g = runtime.NativeCorpusGen(small_corpus)
    x1, x2, y = g.sample(7, 2000), g.sample(7, 2000), g.sample(8, 2000)
    g.close()
    np.testing.assert_array_equal(x1, x2)
    assert (x1 != y).any()
    # the sampled vocabulary is a subset of the source's
    assert set(np.unique(x1)) <= set(np.unique(small_corpus))


def test_corpusgen_matches_source_statistics(so_built, small_corpus):
    """With p_uni=p_bi=0 every step is a trigram draw, so every sampled
    trigram must exist in the source — the 'fitted on the corpus' claim
    as a checkable property."""
    g = runtime.NativeCorpusGen(small_corpus)
    out = g.sample(5, 4000, 0.0, 0.0)
    g.close()
    src = set(
        zip(small_corpus[:-2].tolist(), small_corpus[1:-1].tolist(),
            small_corpus[2:].tolist())
    )
    sampled = set(zip(out[:-2].tolist(), out[1:-1].tolist(), out[2:].tolist()))
    # jumps after unseen contexts can fabricate a few novel trigrams; the
    # overwhelming mass must come from the source table
    assert len(sampled - src) / max(len(sampled), 1) < 0.02


def test_generate_shards_and_sharded_dataset(so_built, tmp_path, small_corpus):
    """End-to-end corpusgen CLI layout -> ShardedTokenBinDataset: shard
    sizes, vocab sidecars, (seed, step) determinism, and the window
    mapping (every row is a contiguous window of exactly one shard)."""
    from orion_tpu.training.corpusgen import generate_shards
    from orion_tpu.training.data import (
        ShardedTokenBinDataset, make_dataset, window_starts as ws,
    )

    src = str(tmp_path / "src.bin")
    write_token_bin(src, small_corpus, vocab_size=32000)
    paths = generate_shards(src, str(tmp_path / "big"), shards=3,
                            tokens_per_shard=2500, seed=1, eval_tokens=800)
    assert len(paths) == 4 and paths[-1].endswith("eval.bin")
    seq = 32
    ds = make_dataset(str(tmp_path / "big"), seq)
    assert isinstance(ds, ShardedTokenBinDataset)
    assert len(ds.shards) == 3  # eval.bin is NOT a train shard
    assert ds.n_windows == 3 * (2500 - seq - 1)
    b1 = ds.batch(7, 3, 8)
    np.testing.assert_array_equal(b1, ds.batch(7, 3, 8))
    assert (b1 != ds.batch(7, 4, 8)).any()
    # every row is a contiguous window of one shard at the mapped offset
    shard_toks = [np.fromfile(p, dtype=np.uint16) for p in paths[:3]]
    starts = ws(7, 3, 8, ds.n_windows)
    cum = np.cumsum([t.size - seq - 1 for t in shard_toks])
    which = np.searchsorted(cum, starts, side="right")
    local = starts - np.concatenate([[0], cum[:-1]])[which]
    for r in range(8):
        np.testing.assert_array_equal(
            b1[r], shard_toks[which[r]][local[r]:local[r] + seq + 1].astype(np.int32)
        )


def test_sharded_dataset_python_fallback_matches_native(so_built, tmp_path):
    from orion_tpu.training.data import ShardedTokenBinDataset

    paths = []
    rng = np.random.default_rng(0)
    for i, n in enumerate([900, 700]):
        p = str(tmp_path / f"shard_{i:03d}.bin")
        write_token_bin(p, rng.integers(0, 32000, n).astype(np.uint16), 32000)
        paths.append(p)
    native = ShardedTokenBinDataset(paths, 16).batch(1, 2, 6)

    import unittest.mock as mock

    with mock.patch("orion_tpu.runtime.native_available", lambda: False):
        py = ShardedTokenBinDataset(paths, 16)
        assert all(isinstance(s, TokenBinDataset) for s in py.shards)
        np.testing.assert_array_equal(py.batch(1, 2, 6), native)


def test_sharded_dataset_rejects_vocab_mismatch(tmp_path):
    from orion_tpu.training.data import ShardedTokenBinDataset

    p1, p2 = str(tmp_path / "shard_000.bin"), str(tmp_path / "shard_001.bin")
    write_token_bin(p1, np.arange(500) % 100, vocab_size=32000)
    write_token_bin(p2, np.arange(500) % 100, vocab_size=256)
    with pytest.raises(AssertionError, match="vocab"):
        ShardedTokenBinDataset([p1, p2], 16)


def test_corpusgen_adjacent_seeds_decorrelated(so_built, small_corpus):
    """r5 review: a raw counter draw stream made seeds i and i+2 emit
    shifted-identical corpora (shards coalescing into verbatim copies).
    The seed now passes through the finalizer first; no small shift may
    align two differently-seeded streams."""
    g = runtime.NativeCorpusGen(small_corpus)
    outs = [g.sample(s, 4000) for s in (1, 2, 3)]
    g.close()
    for i in range(3):
        for j in range(i + 1, 3):
            x, y = outs[i], outs[j]
            for shift in range(-3, 4):
                xs = x[max(0, shift):4000 + min(0, shift)]
                ys = y[max(0, -shift):4000 - max(0, shift)]
                assert (xs == ys).mean() < 0.5, (i, j, shift)


def test_unloadable_so_falls_back_like_a_missing_one(tmp_path, monkeypatch,
                                                     capsys):
    """A .so that is present but cannot load (built for another machine)
    takes the same Python twins as a missing one, saying so once."""
    bad = tmp_path / "liborion_runtime.so"
    bad.write_bytes(b"not an ELF object")
    monkeypatch.setattr(runtime, "_SO_PATH", str(bad))
    monkeypatch.setattr(runtime, "_lib", None)
    monkeypatch.setattr(runtime, "_unloadable", False)
    assert runtime.native_available() is False
    assert runtime.native_available() is False  # no retry, no second line
    err = capsys.readouterr().err
    assert err.count("cannot load") == 1 and "Python implementations" in err
    src = tmp_path / "t.txt"
    src.write_text("hello")
    assert runtime.byte_encode_file(str(src), str(tmp_path / "t.bin")) == 5
