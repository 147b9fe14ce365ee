"""Durable-session suite (ISSUE 6): crash-safe suspend/resume of O(1)
decode state.

The acceptance proofs live here — (1) SIGTERM mid-stream suspends every
resident session and a NEW server process restores them such that the
concatenated outputs are BITWISE-equal to an uninterrupted run at the
same seeds, greedy and sampled; (2) a kill mid-save leaves the previous
intact generation and a corrupted latest session falls back (or fails
only that session) with the process and co-resident slots untouched;
(3) suspend/resume reuses the existing (slots, chunk) decode compile —
no new jit entries. Plus the store's generation/manifest mechanics and
the session-cache edge cases (idle eviction racing re-admission, LRU
cap, resume into a different engine shape).
"""

import os

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
)
from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.transformer import TransformerLM
from orion_tpu.resilience import inject
from orion_tpu.serving import (
    DecodeRequest,
    Health,
    ServeConfig,
    Server,
    SessionIntegrityError,
    SessionState,
    SessionStore,
    SlotEngine,
)

pytestmark = pytest.mark.chaos

# same shape family as tests/test_batching.py: one layer of each type so
# suspension round-trips (S, z), KV-cache, and ring-cache states alike
CFG = ModelConfig(
    name="session_test", vocab_size=64, d_model=32, n_layers=3, n_heads=2,
    layer_types=("linear", "softmax", "swa"), window=4, max_seq_len=96,
    dtype="float32", backend="xla",
)
GREEDY = SampleConfig(temperature=0.0)
SAMPLED = SampleConfig(temperature=0.8, top_k=5, top_p=0.9, eos_token=3,
                       pad_token=0)


@pytest.fixture(scope="module")
def mp():
    model = TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return model, params


def _prompt(i, ln=5):
    return jax.random.randint(
        jax.random.PRNGKey(2000 + i), (1, ln), 0, CFG.vocab_size
    ).astype(jnp.int32)


def _ref(mp, prompt, n_new, sample, seed):
    model, params = mp
    return np.asarray(
        generate(model, params, prompt, n_new, sample,
                 rng=jax.random.PRNGKey(seed))
    )


def _serve_cfg(tmp_path, **kw):
    kw.setdefault("chunk", 4)
    kw.setdefault("slots", 2)
    kw.setdefault("max_inflight", 8)
    kw.setdefault("session_dir", str(tmp_path / "sessions"))
    return ServeConfig(**kw)


def _run_turn(srv, prompt, want, sample, seed, sid):
    p = srv.submit(DecodeRequest(
        prompt=prompt, max_new_tokens=want, sample=sample, seed=seed,
        session_id=sid,
    ))
    assert srv.serve(drain_when_idle=True) == 0
    return p


# ---------------------------------------------------------------------------
# the store itself: generations, manifests, fallback
# ---------------------------------------------------------------------------


def _fake_session(sid="alice", seed=7, served=0, n_emitted=6, dtype=np.float32):
    state = [
        {"s": np.arange(24, dtype=dtype).reshape(1, 2, 3, 4) / 7,
         "z": np.ones((1, 2, 3), dtype)},
        {"k": np.full((1, 2, 4, 3), 0.5, dtype),
         "v": np.zeros((1, 2, 4, 3), dtype)},
    ]
    return SessionState(
        session_id=sid, seed=seed, sample=SAMPLED, served=served,
        token=np.array([9], np.int32), state=state,
        t=np.array(11, np.int32), emit=np.array(n_emitted, np.int32),
        done=np.array([False]),
        prompt=np.arange(5, dtype=np.int32)[None],
        emitted=np.arange(n_emitted, dtype=np.int32)[None],
    )


def _assert_sessions_equal(a: SessionState, b: SessionState):
    la = jax.tree.leaves(a.arrays())
    lb = jax.tree.leaves(b.arrays())
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.asarray(x).dtype == np.asarray(y).dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert (a.seed, a.served, a.sample) == (b.seed, b.served, b.sample)


def test_store_roundtrip_bitwise(tmp_path):
    store = SessionStore(str(tmp_path))
    sess = _fake_session()
    gen = store.save(sess)
    assert gen == 1
    back = store.load("alice")
    assert back.generation == 1
    _assert_sessions_equal(sess, back)
    # unknown session: None, not an error
    assert store.load("nobody") is None
    assert store.list_sessions() == ["alice"]


def test_store_roundtrip_accelerator_dtypes(tmp_path):
    """bfloat16 leaves (the big configs' cache dtype) must round-trip
    bitwise through the byte-blob serialization."""
    store = SessionStore(str(tmp_path))
    sess = _fake_session()
    sess.state[1]["k"] = np.asarray(
        jnp.linspace(-3, 7, 24, dtype=jnp.bfloat16).reshape(1, 2, 4, 3)
    )
    store.save(sess)
    back = store.load("alice")
    _assert_sessions_equal(sess, back)
    assert str(np.asarray(back.state[1]["k"]).dtype) == "bfloat16"


def test_store_retention_keeps_last_n(tmp_path):
    store = SessionStore(str(tmp_path), keep=2)
    sess = _fake_session()
    for served in (1, 2, 3, 4):
        sess.served = served
        store.save(sess)
    assert store.generations("alice") == [3, 4]
    assert store.load("alice").served == 4


def test_corrupt_latest_falls_back_with_warning(tmp_path):
    store = SessionStore(str(tmp_path), keep=2)
    sess = _fake_session(served=0)
    store.save(sess)
    sess.served = 3
    store.save(sess)
    inject.corrupt_session(str(tmp_path), "alice")  # newest gen's payload
    with pytest.warns(UserWarning, match="corrupt or incomplete"):
        back = store.load("alice")
    assert back.generation == 1 and back.served == 0


def test_truncated_latest_falls_back(tmp_path):
    store = SessionStore(str(tmp_path), keep=2)
    sess = _fake_session()
    store.save(sess)
    sess.served = 5
    store.save(sess)
    inject.truncate_session(str(tmp_path), "alice")
    with pytest.warns(UserWarning, match="falling back"):
        back = store.load("alice")
    assert back.generation == 1 and back.served == 0


def test_all_generations_corrupt_raises_integrity_error(tmp_path):
    store = SessionStore(str(tmp_path), keep=1)
    store.save(_fake_session())
    inject.corrupt_session(str(tmp_path), "alice")
    with pytest.warns(UserWarning):
        with pytest.raises(SessionIntegrityError):
            store.load("alice")


def test_kill_mid_save_leaves_previous_generation(tmp_path):
    """A save that dies before its manifest rename is INVISIBLE: the
    previous generation stays the newest committed one. Two flavors: the
    injected I/O fault inside the retried region, and a torn .bin with
    no .json (the exact state a kill between the two renames leaves)."""
    from orion_tpu.resilience.retry import RetryPolicy

    store = SessionStore(str(tmp_path), retry=RetryPolicy(attempts=1))
    sess = _fake_session(served=1)
    store.save(sess)
    sess.served = 2
    plan = inject.FaultPlan().fail_io("serve.session_save")
    with inject.inject(plan):
        with pytest.raises(OSError):
            store.save(sess)
    assert store.generations("alice") == [1]
    assert store.load("alice").served == 1
    # torn write: payload renamed, manifest never was
    with open(os.path.join(str(tmp_path), "alice", "gen-000002.bin"),
              "wb") as f:
        f.write(b"half a session")
    assert store.generations("alice") == [1]
    assert store.load("alice").served == 1


def test_store_rejects_path_traversal_ids(tmp_path):
    store = SessionStore(str(tmp_path))
    for bad in ("../evil", "a/b", ".hidden", ""):
        with pytest.raises(ValueError):
            store.load(bad)


def test_unknown_fault_site_rejected():
    with pytest.raises(ValueError, match="unknown fault-injection site"):
        inject.FaultPlan().fail_io("serve.sesion_save")  # typo'd


# ---------------------------------------------------------------------------
# multi-turn continuation: bitwise vs one uninterrupted run
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sample", [GREEDY, SAMPLED], ids=["greedy", "sampled"])
def test_two_turns_equal_one_uninterrupted_run(mp, tmp_path, sample):
    """Turn 1 asks for 10 tokens (not chunk-aligned: the carry overshoots
    to 12), turn 2 for 6 more — the concatenation must be BITWISE the
    first 16 tokens of one uninterrupted request at the same seed. The
    overshoot rides the session as a host-side buffer, so turn 2 serves
    2 buffered tokens then decodes 4."""
    model, params = mp
    prompt = _prompt(0)
    ref = _ref(mp, prompt, 16, sample, seed=123)
    srv = Server(model, params, _serve_cfg(tmp_path))
    p1 = _run_turn(srv, prompt, 10, sample, 123, "conv")
    assert p1.result.status == "ok" and p1.result.new_tokens == 10
    np.testing.assert_array_equal(p1.result.tokens, ref[:, :10])
    p2 = _run_turn(srv, np.zeros((1, 0), np.int32), 6, sample, 999, "conv")
    assert p2.result.status == "ok" and p2.result.new_tokens == 6
    np.testing.assert_array_equal(
        np.concatenate([p1.result.tokens, p2.result.tokens], axis=1),
        ref[:, :16],
    )
    srv.close()


def test_buffered_continuation_needs_no_device_work(mp, tmp_path):
    """A continuation fully covered by the suspended carry's overshoot is
    served host-side: zero chunks, zero slot occupancy, still bitwise."""
    model, params = mp
    prompt = _prompt(1)
    ref = _ref(mp, prompt, 14, GREEDY, seed=5)
    srv = Server(model, params, _serve_cfg(tmp_path))
    _run_turn(srv, prompt, 10, GREEDY, 5, "c2")  # carry ran 12
    p2 = _run_turn(srv, np.zeros((1, 0), np.int32), 2, GREEDY, 5, "c2")
    assert p2.result.status == "ok" and p2.result.chunks == 0
    np.testing.assert_array_equal(p2.result.tokens, ref[:, 10:12])
    # and the buffer position advanced durably: the NEXT turn continues
    p3 = _run_turn(srv, np.zeros((1, 0), np.int32), 2, GREEDY, 5, "c2")
    np.testing.assert_array_equal(p3.result.tokens, ref[:, 12:14])
    srv.close()


def test_restart_resumes_from_disk_bitwise(mp, tmp_path):
    """Turn 2 on a FRESH Server object (same session_dir) — the restart
    path: nothing resident, the newest intact generation is loaded,
    inserted at the saved position/rng-fold, and the continuation is
    bitwise."""
    model, params = mp
    prompt = _prompt(2)
    ref = _ref(mp, prompt, 16, GREEDY, seed=77)
    srv1 = Server(model, params, _serve_cfg(tmp_path))
    p1 = _run_turn(srv1, prompt, 8, GREEDY, 77, "conv")
    srv1.close()
    srv2 = Server(model, params, _serve_cfg(tmp_path))
    assert srv2.session_store.list_sessions() == ["conv"]
    plan = inject.FaultPlan().add("serve.session_load")
    with inject.inject(plan):
        p2 = _run_turn(srv2, np.zeros((1, 0), np.int32), 8, GREEDY, 0, "conv")
    assert plan.delivered, "restart continuation must read the disk store"
    np.testing.assert_array_equal(
        np.concatenate([p1.result.tokens, p2.result.tokens], axis=1), ref
    )
    srv2.close()


def test_resume_into_different_engine_shape(mp, tmp_path):
    """A session suspended under (slots=2, chunk=4) resumes bitwise under
    (slots=3, chunk=2) — per-slot state is engine-shape-independent, so a
    redeploy with different serving knobs preserves conversations."""
    model, params = mp
    prompt = _prompt(3)
    ref = _ref(mp, prompt, 16, GREEDY, seed=42)
    srv1 = Server(model, params, _serve_cfg(tmp_path, slots=2, chunk=4))
    p1 = _run_turn(srv1, prompt, 8, GREEDY, 42, "conv")
    srv1.close()
    srv2 = Server(model, params, _serve_cfg(tmp_path, slots=3, chunk=2))
    p2 = _run_turn(srv2, np.zeros((1, 0), np.int32), 8, GREEDY, 0, "conv")
    np.testing.assert_array_equal(
        np.concatenate([p1.result.tokens, p2.result.tokens], axis=1), ref
    )
    srv2.close()


def test_new_prompt_tokens_rebase_deterministically(mp, tmp_path):
    """A turn carrying NEW user tokens re-prefills the full history
    (O(history), vs the O(1) empty-prompt resume). There is no
    uninterrupted oracle for injected mid-stream tokens, so the contract
    is determinism + context growth: an identical two-server replay
    produces identical output, and the session's context now contains
    prompt + turn-1 emissions + the new tokens."""
    model, params = mp

    def run(tmp):
        srv = Server(model, params, _serve_cfg(tmp))
        p1 = _run_turn(srv, _prompt(4), 8, GREEDY, 9, "conv")
        p2 = srv.submit(DecodeRequest(
            prompt=_prompt(5, ln=3), max_new_tokens=8, sample=GREEDY,
            seed=9, session_id="conv",
        ))
        assert srv.serve(drain_when_idle=True) == 0
        sess = srv.session_store.load("conv")
        srv.close()
        return p1.result.tokens, p2.result.tokens, sess

    t1a, t2a, sess_a = run(tmp_path / "a")
    t1b, t2b, _ = run(tmp_path / "b")
    np.testing.assert_array_equal(t1a, t1b)
    np.testing.assert_array_equal(t2a, t2b)
    assert t2a.shape == (1, 8)
    # rebased context = 5 prompt + 8 emitted + 3 new tokens
    assert sess_a.prompt.shape == (1, 16)
    assert sess_a.emitted.shape[1] == 8  # this turn's emissions only
    assert int(sess_a.emit) == 16  # rng-fold continued across the rebase


# ---------------------------------------------------------------------------
# acceptance: SIGTERM mid-stream -> restart -> bitwise completion
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sample", [GREEDY, SAMPLED], ids=["greedy", "sampled"])
def test_sigterm_suspends_sessions_restart_completes_bitwise(
    mp, tmp_path, sample
):
    """THE acceptance proof: SIGTERM mid-stream with two resident
    sessions — both are suspended at the next chunk boundary (drain does
    NOT decode their remaining tokens), the server exits 0, and a new
    server process resumes each from disk; concatenated outputs are
    bitwise-equal to uninterrupted runs at the same seeds."""
    model, params = mp
    want = 24
    prompts = [_prompt(10), _prompt(11, ln=4)]
    refs = [_ref(mp, p, want, sample, seed=500 + i)
            for i, p in enumerate(prompts)]
    srv1 = Server(model, params, _serve_cfg(tmp_path))
    ps = [
        srv1.submit(DecodeRequest(
            prompt=p, max_new_tokens=want, sample=sample, seed=500 + i,
            session_id=f"user{i}",
        ))
        for i, p in enumerate(prompts)
    ]
    plan = inject.FaultPlan().preempt_at_chunk(2)
    with inject.inject(plan):
        rc = srv1.serve()
    assert rc == 0 and srv1.health.state is Health.DEAD
    for p in ps:
        assert p.result is not None and p.result.status == "suspended"
        assert 0 < p.result.new_tokens < want, "must suspend MID-stream"
    # ---- "restart": a fresh server over the same session_dir ----
    srv2 = Server(model, params, _serve_cfg(tmp_path))
    assert srv2.session_store.list_sessions() == ["user0", "user1"]
    conts = [
        srv2.submit(DecodeRequest(
            prompt=np.zeros((1, 0), np.int32),
            max_new_tokens=want - ps[i].result.new_tokens,
            sample=sample, seed=0, session_id=f"user{i}",
        ))
        for i in range(2)
    ]
    assert srv2.serve(drain_when_idle=True) == 0
    for i in range(2):
        assert conts[i].result.status == "ok", i
        total = np.concatenate(
            [ps[i].result.tokens, conts[i].result.tokens], axis=1
        )
        np.testing.assert_array_equal(total, refs[i], err_msg=f"session {i}")
    srv2.close()


def test_sessionless_requests_still_drain_to_completion(mp, tmp_path):
    """The PR 4/5 drain contract is untouched for sessionless work: with
    sessions enabled, a SIGTERM drains a sessionless request to its full
    bitwise output while the co-resident session is suspended."""
    model, params = mp
    prompts = [_prompt(20), _prompt(21)]
    ref_plain = _ref(mp, prompts[0], 16, GREEDY, seed=0)
    srv = Server(model, params, _serve_cfg(tmp_path))
    plain = srv.submit(DecodeRequest(
        prompt=prompts[0], max_new_tokens=16, sample=GREEDY, seed=0,
    ))
    tagged = srv.submit(DecodeRequest(
        prompt=prompts[1], max_new_tokens=16, sample=GREEDY, seed=1,
        session_id="sess",
    ))
    plan = inject.FaultPlan().preempt_at_chunk(1)
    with inject.inject(plan):
        assert srv.serve() == 0
    assert plain.result.status == "ok"
    np.testing.assert_array_equal(plain.result.tokens, ref_plain)
    assert tagged.result.status == "suspended"
    assert tagged.result.new_tokens < 16


def test_corrupt_session_fails_only_that_request(mp, tmp_path):
    """Crash proof, server level: every generation of one session is
    corrupted on disk — its continuation becomes an isolated error
    result; a co-resident sessionless request streams through bitwise
    and the process (and health machine) survives."""
    model, params = mp
    prompt = _prompt(30)
    ref = _ref(mp, prompt, 8, GREEDY, seed=3)
    srv1 = Server(model, params, _serve_cfg(tmp_path, session_keep=1))
    _run_turn(srv1, prompt, 8, GREEDY, 3, "victim")
    srv1.close()
    inject.corrupt_session(str(tmp_path / "sessions"), "victim")
    srv2 = Server(model, params, _serve_cfg(tmp_path, session_keep=1))
    bad = srv2.submit(DecodeRequest(
        prompt=np.zeros((1, 0), np.int32), max_new_tokens=8, sample=GREEDY,
        seed=0, session_id="victim",
    ))
    good = srv2.submit(DecodeRequest(
        prompt=prompt, max_new_tokens=8, sample=GREEDY, seed=3,
    ))
    with pytest.warns(UserWarning):
        assert srv2.serve(drain_when_idle=True) == 0
    assert isinstance(bad.error, SessionIntegrityError)
    assert good.result is not None and good.result.status == "ok"
    np.testing.assert_array_equal(good.result.tokens, ref)
    assert srv2.health.state is not Health.DEAD
    srv2.close()


# ---------------------------------------------------------------------------
# session-cache edge cases: idle eviction, LRU cap, busy sessions
# ---------------------------------------------------------------------------


def test_idle_eviction_races_readmission_at_boundary(mp, tmp_path):
    """The resident cache entry idle-evicts at the same serve wave that
    re-admits the session: the continuation must fall through to the
    disk store (write-through means eviction can never lose state) and
    stay bitwise."""
    model, params = mp
    now = [0.0]
    prompt = _prompt(40)
    ref = _ref(mp, prompt, 16, GREEDY, seed=8)
    srv = Server(
        model, params, _serve_cfg(tmp_path, session_idle_s=10.0),
        clock=lambda: now[0],
    )
    p1 = _run_turn(srv, prompt, 8, GREEDY, 8, "idler")
    assert "idler" in srv._sessions
    now[0] += 60.0  # idle way past the timeout...
    p2 = srv.submit(DecodeRequest(  # ...with the continuation ALREADY queued
        prompt=np.zeros((1, 0), np.int32), max_new_tokens=8, sample=GREEDY,
        seed=0, session_id="idler",
    ))
    plan = inject.FaultPlan().add("serve.session_load")
    with inject.inject(plan):
        assert srv.serve(drain_when_idle=True) == 0
    assert plan.delivered, "idle-evicted session must be re-read from disk"
    np.testing.assert_array_equal(
        np.concatenate([p1.result.tokens, p2.result.tokens], axis=1), ref
    )
    srv.close()


def test_lru_cap_bounds_resident_cache(mp, tmp_path):
    """max_resident_sessions=1 with two conversations: the older entry is
    dropped from host memory (never from disk) and both continuations
    stay bitwise."""
    model, params = mp
    prompts = [_prompt(50), _prompt(51)]
    refs = [_ref(mp, p, 16, GREEDY, seed=60 + i)
            for i, p in enumerate(prompts)]
    srv = Server(
        model, params, _serve_cfg(tmp_path, max_resident_sessions=1),
    )
    p1s = [
        _run_turn(srv, prompts[i], 8, GREEDY, 60 + i, f"lru{i}")
        for i in range(2)
    ]
    assert len(srv._sessions) == 1, "LRU cap must bound the resident cache"
    assert len(srv.session_store.list_sessions()) == 2
    for i in range(2):
        p2 = _run_turn(srv, np.zeros((1, 0), np.int32), 8, GREEDY, 0,
                       f"lru{i}")
        np.testing.assert_array_equal(
            np.concatenate([p1s[i].result.tokens, p2.result.tokens], axis=1),
            refs[i],
        )
    srv.close()


def test_concurrent_turns_on_one_session_isolated_error(mp, tmp_path):
    model, params = mp
    srv = Server(model, params, _serve_cfg(tmp_path))
    a = srv.submit(DecodeRequest(
        prompt=_prompt(60), max_new_tokens=16, sample=GREEDY, seed=0,
        session_id="dup",
    ))
    b = srv.submit(DecodeRequest(
        prompt=_prompt(61), max_new_tokens=4, sample=GREEDY, seed=1,
        session_id="dup",
    ))
    assert srv.serve(drain_when_idle=True) == 0
    assert a.result is not None and a.result.status == "ok"
    assert isinstance(b.error, ValueError)  # "session busy", isolated
    srv.close()


def test_session_without_store_is_isolated_error(mp):
    model, params = mp
    srv = Server(model, params, ServeConfig(chunk=4, slots=2))
    p = srv.submit(DecodeRequest(
        prompt=_prompt(62), max_new_tokens=4, sample=GREEDY,
        session_id="nope",
    ))
    assert srv.serve(drain_when_idle=True) == 0
    assert isinstance(p.error, ValueError)
    srv.close()


def test_mismatched_continuation_sample_isolated_error(mp, tmp_path):
    """A continuation under different sampling parameters cannot be
    bitwise — it is refused as that request's error."""
    model, params = mp
    srv = Server(model, params, _serve_cfg(tmp_path))
    _run_turn(srv, _prompt(63), 8, GREEDY, 0, "conv")
    p = srv.submit(DecodeRequest(
        prompt=np.zeros((1, 0), np.int32), max_new_tokens=8, sample=SAMPLED,
        seed=0, session_id="conv",
    ))
    assert srv.serve(drain_when_idle=True) == 0
    assert isinstance(p.error, ValueError)
    srv.close()


# ---------------------------------------------------------------------------
# acceptance: suspend/resume adds no decode compiles
# ---------------------------------------------------------------------------


def test_resume_reuses_existing_decode_compile(mp, tmp_path):
    """Suspend/resume must ride the existing (slots, chunk) jit entry: a
    whole suspend -> restart -> resume cycle adds ZERO batched-decode
    compiles and ZERO prefill compiles, in-scan or whole-prompt (resume
    is a row insert, not a prefill). Uses a (slots, chunk) pair unique to
    this test so the global cache delta is attributable."""
    model, params = mp
    prompt = _prompt(70)
    cfgkw = dict(slots=5, chunk=3)
    srv1 = Server(model, params, _serve_cfg(tmp_path, **cfgkw))
    _run_turn(srv1, prompt, 6, GREEDY, 1, "conv")
    srv1.close()
    decode_before = _decode_batched_chunk_jit._cache_size()
    prefill_before = (
        _decode_batched_prefill_chunk_jit._cache_size(),
        _prefill_carry_bucketed_jit._cache_size(),
    )
    srv2 = Server(model, params, _serve_cfg(tmp_path, **cfgkw))
    p2 = _run_turn(srv2, np.zeros((1, 0), np.int32), 6, GREEDY, 1, "conv")
    assert p2.result.status == "ok"
    assert _decode_batched_chunk_jit._cache_size() == decode_before, (
        "resume must reuse the resident (slots, chunk) decode compile"
    )
    assert (
        _decode_batched_prefill_chunk_jit._cache_size(),
        _prefill_carry_bucketed_jit._cache_size(),
    ) == prefill_before, "an O(1) resume must not prefill"
    srv2.close()


def test_ladder_on_resumed_slot_recovers_bitwise(mp, tmp_path):
    """Poisoning a RESUMED slot's state walks the rewind rung with the
    cross-turn history intact: the continuation still comes out bitwise
    (the re-prefill rung would rebuild from prompt + prior turns + this
    turn's chunks at the session's absolute fold index)."""
    model, params = mp
    prompt = _prompt(80)
    ref = _ref(mp, prompt, 16, GREEDY, seed=13)
    srv = Server(model, params, _serve_cfg(tmp_path, slots=2))
    p1 = _run_turn(srv, prompt, 8, GREEDY, 13, "conv")
    plan = inject.FaultPlan().poison_decode_slot_at(0, chunk=1, times=2)
    p2 = srv.submit(DecodeRequest(
        prompt=np.zeros((1, 0), np.int32), max_new_tokens=8, sample=GREEDY,
        seed=0, session_id="conv",
    ))
    with inject.inject(plan):
        assert srv.serve(drain_when_idle=True) == 0
    assert p2.result.status == "ok"
    assert (p2.result.rewinds, p2.result.reprefills) == (1, 1)
    np.testing.assert_array_equal(
        np.concatenate([p1.result.tokens, p2.result.tokens], axis=1), ref
    )
    srv.close()


def test_failed_turn_releases_session_and_last_generation_survives(
    mp, tmp_path
):
    """A session turn whose slot exhausts the degradation ladder fails
    WITHOUT suspending (a poisoned state must never become the session's
    truth) — and must release the conversation: the next turn resumes
    from the last good on-disk generation bitwise, instead of being
    locked out behind a leaked active-session id."""
    model, params = mp
    prompt = _prompt(95)
    ref = _ref(mp, prompt, 16, GREEDY, seed=31)
    srv = Server(model, params, _serve_cfg(tmp_path))
    p1 = _run_turn(srv, prompt, 8, GREEDY, 31, "conv")  # gen 1 on disk
    plan = inject.FaultPlan().poison_decode_slot_at(0, chunk=0, times=-1)
    p2 = srv.submit(DecodeRequest(
        prompt=np.zeros((1, 0), np.int32), max_new_tokens=8, sample=GREEDY,
        seed=0, session_id="conv",
    ))
    with inject.inject(plan):
        assert srv.serve(drain_when_idle=True) == 0
    assert p2.result is not None and p2.result.status == "failed"
    assert p2.result.session is None
    assert "conv" not in srv._active_sessions, "failed turn must release"
    # turn 3 resumes from generation 1 (turn 2 changed nothing on disk)
    p3 = _run_turn(srv, np.zeros((1, 0), np.int32), 8, GREEDY, 0, "conv")
    assert p3.result.status == "ok"
    np.testing.assert_array_equal(
        np.concatenate([p1.result.tokens, p3.result.tokens], axis=1), ref
    )
    srv.close()


def test_dirty_session_pinned_until_save_lands(mp, tmp_path):
    """If a session's save fails, the resident copy is the ONLY
    up-to-date one: idle eviction must pin it (dropping it would lose a
    turn the client saw), the tick loop retries the save once the store
    recovers, and the continuation stays bitwise throughout."""
    model, params = mp
    now = [0.0]
    prompt = _prompt(96)
    ref = _ref(mp, prompt, 16, GREEDY, seed=17)
    srv = Server(
        model, params, _serve_cfg(tmp_path, session_idle_s=10.0),
        clock=lambda: now[0],
    )
    plan = inject.FaultPlan().fail_io("serve.session_save", times=-1)
    with inject.inject(plan):
        with pytest.warns(UserWarning, match="save failed"):
            p1 = _run_turn(srv, prompt, 8, GREEDY, 17, "frag")
    assert p1.result.status == "ok"
    assert "frag" in srv._dirty_sessions
    assert srv.session_store.generations("frag") == []
    now[0] += 60.0  # way past idle: a CLEAN entry would evict here
    assert srv.serve(drain_when_idle=True) == 0  # tick: store recovered
    assert "frag" not in srv._dirty_sessions, "tick must retry the save"
    assert srv.session_store.generations("frag") == [1]
    # and the conversation is intact — restart-style resume from disk
    srv2 = Server(model, params, _serve_cfg(tmp_path))
    p2 = _run_turn(srv2, np.zeros((1, 0), np.int32), 8, GREEDY, 0, "frag")
    np.testing.assert_array_equal(
        np.concatenate([p1.result.tokens, p2.result.tokens], axis=1), ref
    )
    srv2.close()


def test_serving_cli_session_roundtrip(tmp_path, capsys):
    """CLI wiring: --session-dir/--session-id across two invocations —
    turn 1 creates the session, the restarted process reports it
    restorable and an empty-input continuation resumes it (a second
    generation lands on disk)."""
    from orion_tpu.serving.__main__ import main

    store_dir = str(tmp_path / "store")
    pf = tmp_path / "prompts.txt"
    pf.write_text("ab\n")
    base = [
        "--config", "tiny", "--max-new-tokens", "4", "--chunk", "2",
        "--temperature", "0", "--session-dir", store_dir,
        "--session-id", "conv",
    ]
    assert main(base + ["--prompts-file", str(pf)]) == 0
    out1 = capsys.readouterr()
    assert len(out1.out.strip().splitlines()) == 1
    store = SessionStore(store_dir)
    assert store.generations("conv") == [1]
    assert store.load("conv").served == 4
    # "restart": fresh invocation, no input at all -> one continuation
    empty = tmp_path / "empty.txt"
    empty.write_text("")
    assert main(base + ["--prompts-file", str(empty)]) == 0
    out2 = capsys.readouterr()
    assert "1 suspended session(s) restorable" in out2.err
    assert store.generations("conv")[-1] == 2
    assert store.load("conv").served == 8
    # --session-id without --session-dir is refused up front
    assert main(["--config", "tiny", "--prompts-file", str(pf),
                 "--session-id", "x"]) == 2


def test_engine_level_suspend_resume_roundtrip(mp):
    """SlotEngine unit: suspend mid-stream (no server, no disk), resume
    into another engine, bitwise output — the insert(extract) identity
    plus fold/position bookkeeping in isolation."""
    model, params = mp
    prompt = _prompt(90)
    ref = _ref(mp, prompt, 16, SAMPLED, seed=21)
    eng1 = SlotEngine(model, params, slots=2, chunk=4)
    eng1.admit(
        DecodeRequest(prompt=prompt, max_new_tokens=16, sample=SAMPLED,
                      seed=21, session_id="s"),
        tag="r",
    )
    eng1.step()  # 4 tokens
    [(tag, res)] = eng1.suspend_sessions()
    assert tag == "r" and res.status == "suspended" and res.new_tokens == 4
    sess = res.session
    assert sess is not None and int(sess.emit) == 4
    eng2 = SlotEngine(model, params, slots=4, chunk=4)
    eng2.resume(
        sess,
        DecodeRequest(prompt=np.zeros((1, 0), np.int32), max_new_tokens=12,
                      sample=SAMPLED, seed=0, session_id="s"),
        tag="r2",
    )
    done = {}
    while eng2.busy:
        done.update(dict(eng2.step()))
    np.testing.assert_array_equal(
        np.concatenate([res.tokens, done["r2"].tokens], axis=1), ref
    )
