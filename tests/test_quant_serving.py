"""Quantized serving + the content-addressed prefix cache (ISSUE 11).

The two acceptance proofs live here — (1) BITWISE parity per qmode: the
slot-multiplexed Server decoding with int8 / int4-packed weights
(ServeConfig.qmode) produces tokens bitwise-identical to the quantized
solo scan at the same seeds, greedy and sampled, under staggered
admission — quantization changes the numbers, never the determinism; and
(2) a prefix-cache HIT produces output bitwise-identical to the uncached
request (the cached snapshot is the in-scan prefill's state at the
aligned boundary, so resuming from it and cold-prefilling are the same
program), with ZERO new compiles on the hit and one decode compile per
(slots, chunk, bucket, qmode) overall.

Plus the prefix-store fault model the ISSUE pins: a kill mid-publish
leaves the previous generation intact (manifest rename = commit point), a
corrupt entry falls back to a COLD PREFILL — never a failed request — and
two replicas racing to publish the same prefix converge. The fault sites
``serve.prefix_save`` / ``serve.prefix_load`` fire inside the retried
store I/O (this module is their chaos coverage for the registry
meta-test in tests/test_resilience.py).
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.generate import (
    SampleConfig,
    _decode_batched_chunk_jit,
    _decode_batched_prefill_chunk_jit,
    _prefill_carry_bucketed_jit,
    generate,
    quantize_for_decode,
)
from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.transformer import TransformerLM
from orion_tpu.resilience import inject
from orion_tpu.serving import (
    DecodeRequest,
    PrefixStore,
    ServeConfig,
    Server,
    SlotEngine,
    parse_buckets,
)
from orion_tpu.serving.batching import _stage_prefix_carry
from orion_tpu.serving.prefix_store import params_identity

pytestmark = pytest.mark.chaos

# one layer of each type so every decode-state flavour — (S, z), KV
# cache, swa ring — crosses the quantized matmuls and the prefix
# snapshot round trip; chunk=8 keeps the prefix alignment small enough
# for short test prompts
CFG = ModelConfig(
    name="qserve_test", vocab_size=64, d_model=32, n_layers=3, n_heads=2,
    layer_types=("linear", "softmax", "swa"), window=4, max_seq_len=128,
    dtype="float32", backend="xla", chunk=8,
)
GREEDY = SampleConfig(temperature=0.0)
SAMPLED = SampleConfig(temperature=0.8, top_k=5, top_p=0.9, eos_token=3,
                       pad_token=0)


@pytest.fixture(scope="module")
def mp():
    model = TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return model, params


@pytest.fixture(scope="module")
def qmp(mp):
    """Quantized (model, params) per qmode — deterministic, so these are
    exactly what a Server(qmode=...) builds internally at startup."""
    model, params = mp
    return {
        mode: quantize_for_decode(model, params, mode=mode)
        for mode in ("int8", "int4")
    }


def _prompts(n, lens=(3, 5, 6, 4, 7)):
    out = []
    for i in range(n):
        ln = lens[i % len(lens)]
        out.append(
            jax.random.randint(
                jax.random.PRNGKey(1000 + i), (1, ln), 0, CFG.vocab_size
            ).astype(jnp.int32)
        )
    return out


def _shared_prefix_prompt(suffix_seed: int, prefix_len: int = 24,
                          suffix_len: int = 5) -> np.ndarray:
    """System-prompt-shaped prompt: one fixed shared prefix + a
    per-request suffix (host array, like wire-delivered prompts)."""
    prefix = jax.random.randint(
        jax.random.PRNGKey(7), (1, prefix_len), 0, CFG.vocab_size
    )
    suffix = jax.random.randint(
        jax.random.PRNGKey(9000 + suffix_seed), (1, suffix_len), 0,
        CFG.vocab_size,
    )
    return np.concatenate(
        [np.asarray(prefix), np.asarray(suffix)], axis=1
    ).astype(np.int32)


# ---------------------------------------------------------------------------
# acceptance 1: bitwise batched-vs-solo parity PER QMODE
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["int8", "int4"])
@pytest.mark.parametrize("sample", [GREEDY, SAMPLED], ids=["greedy", "sampled"])
def test_qmode_batched_parity_bitwise(mp, qmp, mode, sample):
    """N > slots requests through a quantized Server (admission staggered
    by the queue refilling freed slots at boundaries): every request's
    tokens must be BITWISE what the quantized solo scan emits at the
    same seed. The Server quantizes the fp32 params itself
    (ServeConfig.qmode) — parity against our own quantize_for_decode
    also proves startup quantization is deterministic."""
    model, params = mp
    qmodel, qparams = qmp[mode]
    slots, n = 4, 6
    prompts = _prompts(n)
    refs = [
        np.asarray(generate(qmodel, qparams, p, 8, sample,
                            rng=jax.random.PRNGKey(500 + i)))
        for i, p in enumerate(prompts)
    ]
    srv = Server(model, params, ServeConfig(chunk=4, slots=slots,
                                            max_inflight=n, qmode=mode))
    ps = [
        srv.submit(DecodeRequest(prompt=p, max_new_tokens=8, sample=sample,
                                 seed=500 + i))
        for i, p in enumerate(prompts)
    ]
    assert srv.serve(drain_when_idle=True) == 0
    for i, (p, ref) in enumerate(zip(ps, refs)):
        assert p.result is not None and p.result.status == "ok", (i, p.error)
        assert np.array_equal(p.result.tokens, ref), (mode, i)


def test_qmode_inscan_prefill_parity(mp, qmp):
    """The unified in-scan prefill program under int8: staged admission
    (prefill_chunk > 0) must emit bitwise what the quantized solo scan
    does — the PR 7 contract holds per qmode."""
    model, params = mp
    qmodel, qparams = qmp["int8"]
    prompts = _prompts(3)
    refs = [
        np.asarray(generate(qmodel, qparams, p, 8, GREEDY,
                            rng=jax.random.PRNGKey(500 + i)))
        for i, p in enumerate(prompts)
    ]
    srv = Server(model, params, ServeConfig(
        chunk=4, slots=2, max_inflight=4, qmode="int8", prefill_chunk=8,
    ))
    ps = [
        srv.submit(DecodeRequest(prompt=p, max_new_tokens=8, sample=GREEDY,
                                 seed=500 + i))
        for i, p in enumerate(prompts)
    ]
    assert srv.serve(drain_when_idle=True) == 0
    for i, (p, ref) in enumerate(zip(ps, refs)):
        assert p.result is not None and p.result.status == "ok", (i, p.error)
        assert np.array_equal(p.result.tokens, ref), i


def test_one_decode_compile_per_qmode(mp):
    """The jit cache grows by EXACTLY one decode entry per qmode at a
    fixed (slots, chunk): the quant model is a new static argument (one
    compile), and further traffic under that qmode reuses it — the
    engine-lifetime guarantee, now keyed by (slots, chunk, bucket,
    qmode). A fresh config name keys fresh cache rows, so the count is
    independent of what this module compiled before."""
    import dataclasses

    cfg = dataclasses.replace(CFG, name="qcompile_test")
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    qmodel, qparams = quantize_for_decode(model, params, mode="int8")
    prompt = _prompts(1)[0]

    def run(eng_model, eng_params):
        eng = SlotEngine(eng_model, eng_params, slots=2, chunk=4)
        eng.admit(DecodeRequest(prompt=prompt, max_new_tokens=8,
                                sample=GREEDY, seed=0), tag="t")
        while eng.busy:
            eng.step()

    before = _decode_batched_chunk_jit._cache_size()
    run(model, params)
    assert _decode_batched_chunk_jit._cache_size() - before == 1
    run(qmodel, qparams)
    assert _decode_batched_chunk_jit._cache_size() - before == 2, (
        "a second qmode costs exactly one more decode compile"
    )
    run(qmodel, qparams)  # same qmode again: zero new compiles
    assert _decode_batched_chunk_jit._cache_size() - before == 2
    run(model, params)  # and fp32 again: still cached
    assert _decode_batched_chunk_jit._cache_size() - before == 2


def test_qmode_ladder_rewind_bitwise(mp, qmp):
    """Ladder rung 1 under int8: a transient poisoned chunk rewinds from
    the boundary snapshot and the final tokens are bitwise the unfaulted
    quantized run's — the rewind contract is qmode-invariant because the
    snapshot/replay machinery never touches the weights."""
    qmodel, qparams = qmp["int8"]
    prompt = _prompts(1)[0]
    ref = np.asarray(generate(qmodel, qparams, prompt, 8, GREEDY,
                              rng=jax.random.PRNGKey(11)))
    eng = SlotEngine(qmodel, qparams, slots=2, chunk=4)
    eng.admit(DecodeRequest(prompt=prompt, max_new_tokens=8, sample=GREEDY,
                            seed=11), tag="t")
    done = {}
    plan = inject.FaultPlan().poison_decode_slot_at(0, 1, times=1)
    with inject.inject(plan):
        while eng.busy:
            done.update(dict(eng.step()))
    res = done["t"]
    assert res.status == "ok" and res.rewinds == 1 and res.reprefills == 0
    assert np.array_equal(res.tokens, ref)


def test_qmode_session_suspend_resume_bitwise(mp, qmp, tmp_path):
    """Durable sessions under int8: a turn suspended by one server and
    resumed by a NEW server (restart) concatenates bitwise to one
    uninterrupted quantized run — both servers quantize the same fp32
    params the same deterministic way, so the saved state row re-enters
    a carry whose weights are identical."""
    model, params = mp
    qmodel, qparams = qmp["int8"]
    prompt = _prompts(1)[0]
    ref = np.asarray(generate(qmodel, qparams, prompt, 16, GREEDY,
                              rng=jax.random.PRNGKey(7)))
    sess_dir = str(tmp_path / "sess")
    cfg = ServeConfig(chunk=4, slots=2, max_inflight=4, qmode="int8",
                      session_dir=sess_dir)
    srv = Server(model, params, cfg)
    t1 = srv.submit(DecodeRequest(prompt=prompt, max_new_tokens=8,
                                  sample=GREEDY, seed=7, session_id="conv"))
    assert srv.serve(drain_when_idle=True) == 0
    assert t1.result is not None and t1.result.status == "ok", t1.error
    srv2 = Server(model, params, cfg)  # a fresh process would do the same
    t2 = srv2.submit(DecodeRequest(prompt=np.zeros((1, 0), np.int32),
                                   max_new_tokens=8, sample=GREEDY, seed=7,
                                   session_id="conv"))
    assert srv2.serve(drain_when_idle=True) == 0
    assert t2.result is not None and t2.result.status == "ok", t2.error
    cat = np.concatenate([t1.result.tokens, t2.result.tokens], axis=1)
    assert np.array_equal(cat, ref)


def test_qmode_rejects_unknown_mode(mp):
    model, params = mp
    with pytest.raises(ValueError, match="qmode"):
        Server(model, params, ServeConfig(qmode="fp8"))


# ---------------------------------------------------------------------------
# acceptance 2: prefix-cache hit == uncached, O(suffix), zero new compiles
# ---------------------------------------------------------------------------


def _prefix_server(mp, tmp_path, qmode="off", **kw):
    model, params = mp
    cfg = ServeConfig(
        chunk=4, slots=2, max_inflight=8, prefill_chunk=8,
        prefix_dir=str(tmp_path / "prefix"), qmode=qmode,
        params_id="qserve-test:seed0", **kw,
    )
    return Server(model, params, cfg)


@pytest.mark.parametrize("sample", [GREEDY, SAMPLED], ids=["greedy", "sampled"])
def test_prefix_hit_bitwise_equals_uncached(mp, tmp_path, sample):
    """Request A declares the 24-token shared prefix (miss -> publish);
    request B shares it with a different suffix and HITS. B's tokens
    must be bitwise what the uncached solo scan produces: the cached
    snapshot is the in-scan prefill's state at the aligned boundary, so
    O(suffix) admission and O(prompt) admission are the same program."""
    model, params = mp
    srv = _prefix_server(mp, tmp_path)
    pA, pB = _shared_prefix_prompt(1), _shared_prefix_prompt(2)
    refB = np.asarray(generate(model, params, jnp.asarray(pB), 8, sample,
                               rng=jax.random.PRNGKey(501)))
    a = srv.submit(DecodeRequest(prompt=pA, max_new_tokens=8, sample=sample,
                                 seed=500, prefix_len=24))
    assert srv.serve(drain_when_idle=True) == 0
    assert a.result is not None and a.result.status == "ok", a.error
    flat = srv.metrics.counters_flat()
    assert flat["prefix_misses"] == 1 and flat["prefix_publishes"] == 1
    b = srv.submit(DecodeRequest(prompt=pB, max_new_tokens=8, sample=sample,
                                 seed=501, prefix_len=24))
    assert srv.serve(drain_when_idle=True) == 0
    assert b.result is not None and b.result.status == "ok", b.error
    assert srv.metrics.counters_flat()["prefix_hits"] == 1
    assert np.array_equal(b.result.tokens, refB)


def test_prefix_hit_zero_new_compiles(mp, tmp_path):
    """Steady state: after one warm hit, further hits add ZERO entries to
    every decode/prefill jit cache (including the prefix staging jit) —
    the acceptance criterion 'zero new compiles on a prefix hit'."""
    srv = _prefix_server(mp, tmp_path)
    a = srv.submit(DecodeRequest(prompt=_shared_prefix_prompt(1),
                                 max_new_tokens=8, sample=GREEDY, seed=0,
                                 prefix_len=24))
    assert srv.serve(drain_when_idle=True) == 0 and a.result.status == "ok"
    warm = srv.submit(DecodeRequest(prompt=_shared_prefix_prompt(2),
                                    max_new_tokens=8, sample=GREEDY, seed=1))
    assert srv.serve(drain_when_idle=True) == 0
    assert warm.result.status == "ok"
    assert srv.metrics.counters_flat()["prefix_hits"] == 1
    caches = (
        _decode_batched_chunk_jit, _decode_batched_prefill_chunk_jit,
        _prefill_carry_bucketed_jit, _stage_prefix_carry,
    )
    before = [c._cache_size() for c in caches]
    hit = srv.submit(DecodeRequest(prompt=_shared_prefix_prompt(3),
                                   max_new_tokens=8, sample=GREEDY, seed=2))
    assert srv.serve(drain_when_idle=True) == 0
    assert hit.result.status == "ok"
    assert srv.metrics.counters_flat()["prefix_hits"] == 2
    after = [c._cache_size() for c in caches]
    assert after == before, (
        "a steady-state prefix hit must not compile anything: "
        f"{[c.__name__ if hasattr(c, '__name__') else i for i, c in enumerate(caches)]} {before} -> {after}"
    )


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_prefix_hit_bitwise_per_qmode(mp, qmp, tmp_path, mode):
    """The two tentpoles composed: a prefix hit under quantized serving
    is bitwise the uncached QUANTIZED request (entries are keyed by
    qmode — int8 states and fp32 states of the same tokens are different
    functions and must never cross)."""
    qmodel, qparams = qmp[mode]
    srv = _prefix_server(mp, tmp_path, qmode=mode)
    pA, pB = _shared_prefix_prompt(1), _shared_prefix_prompt(2)
    refB = np.asarray(generate(qmodel, qparams, jnp.asarray(pB), 8, GREEDY,
                               rng=jax.random.PRNGKey(501)))
    a = srv.submit(DecodeRequest(prompt=pA, max_new_tokens=8, sample=GREEDY,
                                 seed=500, prefix_len=24))
    assert srv.serve(drain_when_idle=True) == 0 and a.result.status == "ok"
    b = srv.submit(DecodeRequest(prompt=pB, max_new_tokens=8, sample=GREEDY,
                                 seed=501, prefix_len=24))
    assert srv.serve(drain_when_idle=True) == 0
    assert b.result.status == "ok" and np.array_equal(b.result.tokens, refB)
    assert srv.metrics.counters_flat()["prefix_hits"] == 1


def test_prefix_entries_keyed_by_qmode_and_params(tmp_path):
    """Content addressing: same tokens, different params identity or
    qmode -> different keys (states are different functions); same
    everything -> the same key on every replica."""
    toks = np.arange(16, dtype=np.int32).reshape(1, -1)
    s1 = PrefixStore(str(tmp_path), params_id="a", qmode="off", align=8)
    s2 = PrefixStore(str(tmp_path), params_id="a", qmode="int8", align=8)
    s3 = PrefixStore(str(tmp_path), params_id="b", qmode="off", align=8)
    s4 = PrefixStore(str(tmp_path), params_id="a", qmode="off", align=8)
    keys = {s.key_for(toks) for s in (s1, s2, s3)}
    assert len(keys) == 3
    assert s1.key_for(toks) == s4.key_for(toks)
    assert params_identity(CFG, "int8") != params_identity(CFG, "off")


def test_prefix_candidates_and_publish_length(tmp_path):
    store = PrefixStore(str(tmp_path), params_id="a", align=8)
    # candidates leave >= 1 suffix token and walk longest-first
    assert store.candidate_lengths(25) == [24, 16, 8]
    assert store.candidate_lengths(24) == [16, 8]  # 24 would cover it all
    assert store.candidate_lengths(8) == []
    assert store.publish_length(29, declared=24) == 24
    assert store.publish_length(24, declared=24) == 16  # clamped to len-1
    assert store.publish_length(29, declared=7) == 0
    with pytest.raises(ValueError, match="align"):
        PrefixStore(str(tmp_path), params_id="a", align=0)


def test_prefix_declared_hint_beats_the_probe_budget(tmp_path):
    """A declared system prompt must hit however long the user suffix
    is: the declared length is probed FIRST, so a suffix longer than
    max_probes * align tokens cannot starve a committed entry out of
    the longest-first probe window."""
    store = PrefixStore(str(tmp_path), params_id="a", align=8,
                        max_probes=4)
    # prompt of 1001 tokens, declared 512-token prefix: the longest-first
    # window ([992, 984, 976, ...] at 4 probes) never reaches 512 — the
    # hint must put it at the front
    cands = store.candidate_lengths(1001, declared=512)
    assert cands[0] == 512 and len(cands) <= 4
    # in-window declarations don't duplicate
    assert store.candidate_lengths(25, declared=24) == [24, 16, 8]


def test_session_refuses_cross_qmode_resume(mp, tmp_path):
    """A conversation suspended under int8 must not silently resume
    under fp32 (same shapes, wrong numbers): the session store stamps
    the weights identity (params id + qmode) on every generation and a
    mismatched load is an integrity failure for THAT request — loud,
    never divergent."""
    model, params = mp
    sess_dir = str(tmp_path / "sess")
    prompt = _prompts(1)[0]
    srv = Server(model, params, ServeConfig(
        chunk=4, slots=2, max_inflight=4, qmode="int8",
        session_dir=sess_dir,
    ))
    t1 = srv.submit(DecodeRequest(prompt=prompt, max_new_tokens=8,
                                  sample=GREEDY, seed=7, session_id="conv"))
    assert srv.serve(drain_when_idle=True) == 0
    assert t1.result is not None and t1.result.status == "ok", t1.error
    srv2 = Server(model, params, ServeConfig(
        chunk=4, slots=2, max_inflight=4, qmode="off",
        session_dir=sess_dir,
    ))
    t2 = srv2.submit(DecodeRequest(
        prompt=np.zeros((1, 0), np.int32), max_new_tokens=8,
        sample=GREEDY, seed=7, session_id="conv",
    ))
    assert srv2.serve(drain_when_idle=True) == 0
    assert t2.result is None and t2.error is not None
    assert "identity" in str(t2.error), t2.error
    # the matching server still resumes fine (same config + qmode)
    srv3 = Server(model, params, ServeConfig(
        chunk=4, slots=2, max_inflight=4, qmode="int8",
        session_dir=sess_dir,
    ))
    t3 = srv3.submit(DecodeRequest(
        prompt=np.zeros((1, 0), np.int32), max_new_tokens=8,
        sample=GREEDY, seed=7, session_id="conv",
    ))
    assert srv3.serve(drain_when_idle=True) == 0
    assert t3.result is not None and t3.result.status == "ok", t3.error


def test_prefix_store_rides_the_one_admission_path(mp, tmp_path):
    """The hit path IS staged in-scan consumption, and no other admission
    is left to refuse a prefix store: an engine built with no prefill
    option takes one whose alignment its linear-attention chunk divides
    and refuses one it does not."""
    model, params = mp
    store = PrefixStore(str(tmp_path / "q"), params_id="x", align=8)
    eng = SlotEngine(model, params, slots=2, chunk=4, prefix_store=store)
    assert eng.prefix_store is store and eng.chunk_align == 8
    odd = PrefixStore(str(tmp_path / "r"), params_id="x", align=12)
    with pytest.raises(ValueError, match="not a multiple"):
        SlotEngine(model, params, slots=2, chunk=4, prefix_store=odd)


# ---------------------------------------------------------------------------
# the prefix-store fault model (chaos)
# ---------------------------------------------------------------------------


def _published_store(mp, tmp_path, align=8):
    """A store holding one committed generation of the shared prefix."""
    model, params = mp
    store = PrefixStore(str(tmp_path), params_id="x", align=align)
    toks = _shared_prefix_prompt(1)[:, :24]
    carry = jax.jit(
        lambda p, t: model.apply(p, t, method="prefill_last"),
        static_argnums=(),
    )(params, jnp.asarray(toks))
    store.publish(toks, carry[1])
    return store, toks


def test_kill_mid_publish_leaves_previous_generation_intact(mp, tmp_path):
    """The manifest rename is the commit point: a publish that dies at
    any earlier moment — simulated as (a) an injected I/O failure at the
    ``serve.prefix_save`` site exhausting its retries, and (b) a torn
    ``.bin`` with no manifest — leaves the previous generation the
    newest committed one, byte-for-byte loadable."""
    store, toks = _published_store(mp, tmp_path)
    key = store.key_for(toks)
    assert store.generations(key) == [1]
    ref = store.lookup(np.concatenate(
        [toks, np.zeros((1, 4), np.int32)], axis=1
    ))
    assert ref is not None and ref.generation == 1
    # (a) the write itself fails on every retry: publish raises, gen-2
    # never commits
    plan = inject.FaultPlan().fail_io("serve.prefix_save", times=-1)
    with inject.inject(plan):
        with pytest.raises(OSError):
            store.publish(toks, ref.state, skip_if_present=False)
    assert plan.delivered, "the serve.prefix_save site must have fired"
    assert store.generations(key) == [1]
    # (b) a kill between the payload rename and the manifest rename: the
    # .bin exists, the .json does not — invisible by the commit rule
    import shutil

    d = store._dir(key)
    shutil.copyfile(store._bin(d, 1), store._bin(d, 2))
    assert store.generations(key) == [1]
    again = store.lookup(np.concatenate(
        [toks, np.zeros((1, 4), np.int32)], axis=1
    ))
    assert again is not None and again.generation == 1
    assert jax.tree.all(jax.tree.map(
        lambda a, b: np.array_equal(a, b), ref.state, again.state
    ))


def test_corrupt_prefix_falls_back_to_cold_prefill(mp, tmp_path):
    """Bit-rot in the only committed generation: the lookup warns and
    MISSES (a prefix is recomputable — the cold path is the fallback),
    and the request completes bitwise-correct, never 'failed'."""
    model, params = mp
    srv = _prefix_server(mp, tmp_path)
    pA = _shared_prefix_prompt(1)
    a = srv.submit(DecodeRequest(prompt=pA, max_new_tokens=8, sample=GREEDY,
                                 seed=500, prefix_len=24))
    assert srv.serve(drain_when_idle=True) == 0 and a.result.status == "ok"
    key = srv.prefix_store.key_for(pA[:, :24])
    # the on-disk layout matches the session store's generation files,
    # so the same damage helper applies with the key as the id
    inject.corrupt_session(srv.prefix_store.directory, key)
    pB = _shared_prefix_prompt(2)
    refB = np.asarray(generate(model, params, jnp.asarray(pB), 8, GREEDY,
                               rng=jax.random.PRNGKey(501)))
    with pytest.warns(UserWarning, match="corrupt"):
        b = srv.submit(DecodeRequest(prompt=pB, max_new_tokens=8,
                                     sample=GREEDY, seed=501))
        assert srv.serve(drain_when_idle=True) == 0
    assert b.result is not None and b.result.status == "ok", b.error
    assert np.array_equal(b.result.tokens, refB)
    flat = srv.metrics.counters_flat()
    assert flat["prefix_hits"] == 0 and flat["failed"] == 0


def test_corrupt_latest_falls_back_to_previous_generation(mp, tmp_path):
    """With two committed generations, damage to the newest falls back to
    the older intact one — the session store's restore semantics."""
    store, toks = _published_store(mp, tmp_path)
    key = store.key_for(toks)
    ref = store.lookup(np.concatenate(
        [toks, np.zeros((1, 4), np.int32)], axis=1
    ))
    store.publish(toks, ref.state, skip_if_present=False)
    assert store.generations(key) == [1, 2]
    inject.corrupt_session(store.directory, key, generation=2)
    with pytest.warns(UserWarning, match="corrupt"):
        entry = store.lookup(np.concatenate(
            [toks, np.zeros((1, 4), np.int32)], axis=1
        ))
    assert entry is not None and entry.generation == 1


def test_prefix_io_retried_through_fault_sites(mp, tmp_path):
    """Transient storage blips at both sites are retried (OSError-only,
    jittered backoff): one failed attempt each, then success — and the
    delivered log proves the hooks fired inside the retried regions."""
    store, toks = _published_store(mp, tmp_path)
    probe = np.concatenate([toks, np.zeros((1, 4), np.int32)], axis=1)
    plan = (
        inject.FaultPlan()
        .fail_io("serve.prefix_load", times=1)
        .fail_io("serve.prefix_save", times=1)
    )
    with inject.inject(plan):
        entry = store.lookup(probe)
        assert entry is not None and entry.generation == 1
        gen = store.publish(toks, entry.state, skip_if_present=False)
        assert gen == 2
    assert any("serve.prefix_load" in d for d in plan.delivered)
    assert any("serve.prefix_save" in d for d in plan.delivered)


def test_racing_publishes_converge(mp, tmp_path):
    """No single-writer fence exists for prefixes (unlike sessions): two
    replicas publishing the same content concurrently must both succeed
    and leave ONE intact, loadable entry — unique tmp names + last-
    replace-wins on byte-identical payloads."""
    store, toks = _published_store(mp, tmp_path / "seed")
    entry = store.lookup(np.concatenate(
        [toks, np.zeros((1, 4), np.int32)], axis=1
    ))
    d = str(tmp_path / "race")
    replicas = [
        PrefixStore(d, params_id="x", align=8) for _ in range(2)
    ]
    barrier = threading.Barrier(2)
    errors = []

    def racer(s):
        try:
            barrier.wait(timeout=10)
            s.publish(toks, entry.state, skip_if_present=False)
        except Exception as e:  # pragma: no cover - surfaced via errors
            errors.append(e)

    threads = [threading.Thread(target=racer, args=(s,)) for s in replicas]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors
    got = replicas[0].lookup(np.concatenate(
        [toks, np.zeros((1, 4), np.int32)], axis=1
    ))
    assert got is not None
    assert np.array_equal(got.tokens, toks)
    assert jax.tree.all(jax.tree.map(
        lambda a, b: np.array_equal(a, b), got.state, entry.state
    ))
    # no stranded tmp files pollute the entry directory
    key = replicas[0].key_for(toks)
    leftovers = [n for n in __import__("os").listdir(replicas[0]._dir(key))
                 if ".tmp-" in n]
    assert not leftovers


def test_ladder_restart_on_prefix_hit_slot(mp, tmp_path):
    """Rung 2 on a slot admitted via prefix hit while still consuming its
    suffix: the in-scan prefill RESTARTS from a zero row (position 0 —
    the cached snapshot is not retrusted) and the final tokens are
    bitwise the unfaulted run's, just later."""
    model, params = mp
    store, toks = _published_store(mp, tmp_path)
    eng = SlotEngine(
        model, params, slots=2, chunk=4,
        prefill_buckets=parse_buckets("pow2", CFG.max_seq_len),
        prefill_chunk=8, prefix_store=store,
    )
    # 24 cached + 20 suffix: the hit slot stays mid-prefill for several
    # boundaries, so the poison lands while prompt_remaining > 0
    prompt = _shared_prefix_prompt(4, prefix_len=24, suffix_len=20)
    ref = np.asarray(generate(model, params, jnp.asarray(prompt), 8, GREEDY,
                              rng=jax.random.PRNGKey(42)))
    eng.admit(DecodeRequest(prompt=prompt, max_new_tokens=8, sample=GREEDY,
                            seed=42), tag="t")
    assert eng._slots[0].prompt_remaining == 20  # O(suffix), not O(prompt)
    done = {}
    plan = inject.FaultPlan().poison_decode_slot_at(0, 0, times=2)
    with inject.inject(plan):
        while eng.busy:
            done.update(dict(eng.step()))
    res = done["t"]
    assert res.status == "ok" and res.reprefills == 1
    assert np.array_equal(res.tokens, ref)


def test_prefix_hit_is_o_suffix_admission(mp, tmp_path):
    """The host mirror of the hit: a 24+5 prompt admits with only the
    5-token suffix left to consume (one boundary), where the cold path
    has all 29."""
    model, params = mp
    store, _ = _published_store(mp, tmp_path)
    eng = SlotEngine(
        model, params, slots=2, chunk=4,
        prefill_buckets=parse_buckets("pow2", CFG.max_seq_len),
        prefill_chunk=8, prefix_store=store,
    )
    hit_prompt = _shared_prefix_prompt(5)
    cold_prompt = np.asarray(_prompts(1, lens=(29,))[0])
    eng.admit(DecodeRequest(prompt=hit_prompt, max_new_tokens=4,
                            sample=GREEDY, seed=0), tag="hit")
    eng.admit(DecodeRequest(prompt=cold_prompt, max_new_tokens=4,
                            sample=GREEDY, seed=1), tag="cold")
    assert eng._slots[0].prompt_remaining == 5
    assert eng._slots[1].prompt_remaining == 29
    done = {}
    while eng.busy:
        done.update(dict(eng.step()))
    assert done["hit"].status == "ok" and done["cold"].status == "ok"
