"""In-scan chunked prefill suite (ISSUE 7): admission without the stall.

Since ISSUE 33 a boundary runs one piece for EACH waiting slot, up to
``prefill_piece_cap`` of them; the last section pins that schedule: K
slots admitted before one boundary, the host's mirror of the device's
order, the bound on a passed-over slot's wait, and the one-waiting-slot
boundary against the one-piece program it replaced.

The two acceptance proofs live here — (1) a request admitted by STAGING
its prompt into the carry and consuming it ``prefill_chunk`` tokens per
boundary inside the batched scan emits the TOKENS of the solo monolithic
scan at the same seed, for slot counts {2, 4, 8}, greedy and sampled,
staggered admission, prompt lengths straddling bucket / linear-chunk /
piece boundaries; and (2) the engine's lifetime decode-compile count
stays one per (slots, chunk, prompt_bucket) and admission itself never
compiles or runs a prefill. What holds between the piecewise and the
monolithic prefill — two XLA programs — is stated where it is tested:
equal tokens on every pinned seed, states equal to fp32 rounding on
XLA:CPU; on the chip, agreement is the cells' `correct` tolerance
(ROADMAP C12). What is bit-for-bit here is what one program gives twice:
a rewind's replay, a suspend/resume row copy, one flush against
single-row stagings. Plus the satellite coverage: ladder rungs fired while a
co-resident slot is mid-prefill, bucket-overflow refusal/clamping before
any jit, mid-prefill deadline/drain behaviour, and a PR 6 session
suspended and resumed across an in-scan-admitted turn.
"""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import orion_tpu.serving.batching as batching
from orion_tpu.generate import (
    SampleConfig,
    _decode_batched_chunk_jit,
    _decode_batched_prefill_body,
    _decode_batched_prefill_chunk_jit,
    _prefill_carry_bucketed_jit,
    _prefill_extend_row,
    _prefill_selection,
    _sample_rows,
    bucket_for,
    generate,
    prefill_overdue_after,
    prefill_piece_cap,
    sample_logits,
)
from orion_tpu.models.configs import ModelConfig
from orion_tpu.ops.dispatch import decode_live_rows
from orion_tpu.models.transformer import TransformerLM, init_decode_state
from orion_tpu.resilience import inject
from orion_tpu.serving import (
    DecodeRequest,
    ServeConfig,
    Server,
    SlotEngine,
)

pytestmark = pytest.mark.chaos

# one layer of each attention type, small linear-attention chunk (4) so a
# modest prefill_chunk already spans several chunks and piece boundaries
# land between/on chunk edges
CFG = ModelConfig(
    name="inscan_test", vocab_size=64, d_model=32, n_layers=3, n_heads=2,
    layer_types=("linear", "softmax", "swa"), window=4, max_seq_len=96,
    dtype="float32", backend="xla", chunk=4,
)
GREEDY = SampleConfig(temperature=0.0)
SAMPLED = SampleConfig(temperature=0.8, top_k=5, top_p=0.9, eos_token=3,
                       pad_token=0)
BUCKETS = (8, 16, 32)


@pytest.fixture(scope="module")
def mp():
    model = TransformerLM(CFG)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return model, params


def _prompt(i, ln):
    return jax.random.randint(
        jax.random.PRNGKey(3000 + i), (1, ln), 0, CFG.vocab_size
    ).astype(jnp.int32)


def _engine(mp, slots=2, chunk=4, **kw):
    model, params = mp
    return SlotEngine(
        model, params, slots=slots, chunk=chunk, prefill_buckets=BUCKETS,
        prefill_chunk=8, **kw,
    )


def _drain(eng):
    done = {}
    while eng.busy:
        done.update(dict(eng.step()))
    return done


# ---------------------------------------------------------------------------
# model layer: piecewise prefill_extend against monolithic prefill
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("plen,pchunk", [
    (5, 8),    # single piece shorter than the piece
    (8, 8),    # exact piece
    (19, 8),   # multi-piece, ragged tail straddling linear chunks (4)
    (13, 4),   # piece == linear-attention chunk
    (31, 12),  # piece = 3 linear chunks, ragged tail
])
def test_prefill_extend_pieces_agree_with_monolithic(mp, plen, pchunk):
    """Piece-by-piece prefill_extend_step and monolithic prefill are two
    XLA programs over the same left fold: (S, z), the KV cache's real rows,
    the ring's readable rows, and the last-real-row logits agree to fp32
    rounding, and the greedy and the sampled token drawn from those logits
    are EQUAL — what the in-scan admission path, the ladder's re-prefill
    rung and the prefix store's publish rest on. Measured over these five
    cases on XLA:CPU (jax 0.9.0): logits differ by at most 1.0728836e-06
    (of a largest logit of 2.6), the ring's rows by at most 8.3446503e-07,
    (S, z) and the KV cache's rows by nothing; the bound below is about
    twice that."""
    model, params = mp
    bucket = -(-plen // 8) * 8
    tokens = _prompt(plen, plen)
    padded = jnp.pad(tokens, ((0, 0), (0, bucket - plen)))
    ref_logits, ref_states = model.apply(
        params, padded, jnp.int32(plen), method="prefill_last"
    )
    states = init_decode_state(CFG, 1)
    logits, off = None, 0
    while off < plen:
        cons = min(pchunk, plen - off)
        idx = jnp.clip(off + jnp.arange(pchunk), 0, padded.shape[1] - 1)
        piece = jnp.take(padded, idx, axis=1)
        logits, states = model.apply(
            params, piece, states, jnp.int32(off), jnp.int32(cons),
            method="prefill_extend_step",
        )
        off += cons
    close = partial(np.testing.assert_allclose, rtol=2e-5, atol=2e-6)
    close(np.asarray(logits), np.asarray(ref_logits))
    key = jax.random.PRNGKey(plen)
    for sample in (GREEDY, SAMPLED):
        np.testing.assert_array_equal(
            np.asarray(sample_logits(logits, key, sample)),
            np.asarray(sample_logits(ref_logits, key, sample)),
        )
    for li, (lt, sr, sg) in enumerate(
        zip(CFG.layer_types, ref_states, states)
    ):
        for key in sr:
            a, b = np.asarray(sr[key]), np.asarray(sg[key])
            if lt == "softmax":
                a, b = a[:, :, :plen], b[:, :, :plen]
            if lt == "swa":
                pos = np.arange(max(0, plen - CFG.window), plen)
                a, b = a[:, :, pos % CFG.window], b[:, :, pos % CFG.window]
            close(b, a, err_msg=f"layer{li}.{key}")


# ---------------------------------------------------------------------------
# acceptance: in-scan admission against the solo scan, engine-level
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("slots", [2, 4, 8])
@pytest.mark.parametrize("sample", [GREEDY, SAMPLED], ids=["greedy", "sampled"])
def test_inscan_tokens_equal_solo_scan_staggered(mp, slots, sample):
    """Staggered admission (one new request per boundary) with prompt
    lengths straddling bucket edges (8/16) and piece/linear-chunk
    boundaries: every request's tokens through the engine are what the
    solo scan emits."""
    model, params = mp
    lengths = [3, 8, 9, 16, 17, 21][: slots + 2]
    prompts = [_prompt(i, ln) for i, ln in enumerate(lengths)]
    refs = [
        np.asarray(generate(model, params, p, 8, sample,
                            rng=jax.random.PRNGKey(500 + i)))
        for i, p in enumerate(prompts)
    ]
    eng = _engine(mp, slots=slots)
    done, pending = {}, list(enumerate(prompts))
    while pending or eng.busy:
        if pending and eng.has_free_slot:
            i, p = pending.pop(0)  # ONE admission per boundary
            eng.admit(DecodeRequest(prompt=p, max_new_tokens=8,
                                    sample=sample, seed=500 + i), tag=i)
        done.update(dict(eng.step()))
    for i, ref in enumerate(refs):
        assert done[i].status == "ok", i
        np.testing.assert_array_equal(
            done[i].tokens, ref, err_msg=f"slots={slots} request {i}"
        )


def test_admission_is_o1_no_prefill_compile_no_prompt_work(mp):
    """Admission must not touch the whole-prompt prefill at all (the
    bucket-overflow satellite's stronger sibling): serving prompts of
    many lengths leaves its compile cache untouched, and the unified
    program compiles once per (slots, chunk, bucket)."""
    model, params = mp
    pb_before = _prefill_carry_bucketed_jit._cache_size()
    un_before = _decode_batched_prefill_chunk_jit._cache_size()
    de_before = _decode_batched_chunk_jit._cache_size()
    eng = _engine(mp, slots=3, chunk=3)
    done = {}
    for i, ln in enumerate([3, 5, 7, 8, 4, 6, 2]):  # all in bucket 8
        eng.admit(DecodeRequest(prompt=_prompt(50 + i, ln),
                                max_new_tokens=6, sample=GREEDY,
                                seed=100 + i), tag=i)
        done.update(dict(eng.step()))
    done.update(_drain(eng))
    assert all(r.status == "ok" for r in done.values())
    assert _prefill_carry_bucketed_jit._cache_size() == pb_before, (
        "admission ran a whole-prompt prefill"
    )
    assert _decode_batched_prefill_chunk_jit._cache_size() - un_before == 1, (
        "the unified program must compile once per (slots, chunk, bucket)"
    )
    assert _decode_batched_chunk_jit._cache_size() - de_before <= 1


def test_unified_compiles_once_per_bucket(mp):
    """Prompt lengths crossing into a bigger bucket add exactly ONE
    unified compile (the staged buffer's width is the compile key);
    lengths within a bucket never add one."""
    model, params = mp
    eng = _engine(mp, slots=2, chunk=5)
    before = _decode_batched_prefill_chunk_jit._cache_size()
    for i, ln in enumerate([3, 7, 8]):  # bucket 8
        eng.admit(DecodeRequest(prompt=_prompt(70 + i, ln),
                                max_new_tokens=5, sample=GREEDY, seed=i),
                  tag=("a", i))
        _drain(eng)
    assert _decode_batched_prefill_chunk_jit._cache_size() - before == 1
    for i, ln in enumerate([9, 13, 16]):  # bucket 16: one more width
        eng.admit(DecodeRequest(prompt=_prompt(80 + i, ln),
                                max_new_tokens=5, sample=GREEDY, seed=i),
                  tag=("b", i))
        _drain(eng)
    assert _decode_batched_prefill_chunk_jit._cache_size() - before == 2


# ---------------------------------------------------------------------------
# satellite: bucket overflow never reaches jit
# ---------------------------------------------------------------------------


def test_prompt_overflow_is_clean_error_before_any_jit(mp):
    """A prompt longer than the largest bucket is refused at admission —
    no prefill compile, no unified compile, no slot claimed."""
    model, params = mp
    long_prompt = _prompt(0, BUCKETS[-1] + 5)
    eng = _engine(mp)
    pb = _prefill_carry_bucketed_jit._cache_size()
    un = _decode_batched_prefill_chunk_jit._cache_size()
    with pytest.raises(ValueError, match="largest prefill bucket"):
        eng.admit(DecodeRequest(prompt=long_prompt, max_new_tokens=4,
                                sample=GREEDY, seed=0))
    assert not eng.busy, "the refused request must not hold a slot"
    assert _prefill_carry_bucketed_jit._cache_size() == pb
    assert _decode_batched_prefill_chunk_jit._cache_size() == un


def test_prompt_overflow_clamp_serves_newest_context(mp):
    """prompt_overflow='clamp': the request is served from the newest
    tokens of the largest bucket that still leaves room for max_new
    under max_seq_len — bitwise what admitting the pre-clamped prompt
    produces. (The cap-aware choice matters: with pow2 buckets the
    largest bucket IS max_seq_len, so a naive clamp to buckets[-1]
    would just trip the capacity check.)"""
    model, params = mp
    long_prompt = _prompt(1, BUCKETS[-1] + 7)
    clamped = long_prompt[:, -BUCKETS[-1]:]  # 32 + 8 new <= cap 96
    ref = np.asarray(generate(model, params, clamped, 8, GREEDY,
                              rng=jax.random.PRNGKey(11)))
    eng = _engine(mp, prompt_overflow="clamp")
    eng.admit(DecodeRequest(prompt=long_prompt, max_new_tokens=8,
                            sample=GREEDY, seed=11), tag="r")
    done = _drain(eng)
    assert done["r"].status == "ok"
    np.testing.assert_array_equal(done["r"].tokens, ref)
    # max_new 70: bucket 32 no longer fits under cap 96 -> clamp picks 16
    eng2 = _engine(mp, prompt_overflow="clamp")
    i = eng2.admit(DecodeRequest(prompt=long_prompt, max_new_tokens=70,
                                 sample=GREEDY, seed=12), tag="r2")
    assert eng2._slots[i].prompt.shape[1] == 16
    # and when NO bucket leaves room, clamp refuses like the error mode
    with pytest.raises(ValueError, match="no bucket leaves room"):
        eng2.admit(DecodeRequest(prompt=long_prompt, max_new_tokens=95,
                                 sample=GREEDY, seed=13))


def test_inscan_requires_buckets_loudly(mp):
    """An engine with no buckets, or with ``prefill_chunk=0``, must refuse
    at construction and say that host-side prefill is gone (a silent
    override would ignore the caller's explicit choice), and so must a
    server and a plan of such an engine; with neither named it stages at
    pow2 buckets."""
    from orion_tpu.aot import decode_plan

    model, params = mp
    gone = pytest.raises(ValueError, match="host-side prefill .* is gone")
    for off in (dict(prefill_buckets=()), dict(prefill_chunk=0)):
        with gone:
            SlotEngine(model, params, slots=2, chunk=4, **off)
    for off in (dict(prefill_buckets="off"), dict(prefill_chunk=0)):
        with gone:
            Server(model, params, ServeConfig(chunk=4, slots=2, **off))
    with pytest.raises(ValueError, match="no engine has this footprint"):
        decode_plan(CFG, slots=2, chunk=4, lower=False,
                    prefill_buckets=BUCKETS, prefill_chunk=0)
    eng = SlotEngine(model, params, slots=2, chunk=4)
    assert eng.buckets == (16, 32, 64, 96) and eng.prefill_chunk == 64


# ---------------------------------------------------------------------------
# chaos: the ladder with a co-resident slot mid-prefill
# ---------------------------------------------------------------------------


def test_rewind_during_neighbour_prefill_bitwise(mp):
    """Rung 1 fired on a DECODING slot while its neighbour is mid-prefill:
    the rewound boundary replays the neighbour's piece identically — both
    requests finish bitwise."""
    model, params = mp
    p0, p1 = _prompt(10, 5), _prompt(11, 30)  # p1: 4 pieces at pchunk=8
    refs = [
        np.asarray(generate(model, params, p, 8, GREEDY,
                            rng=jax.random.PRNGKey(500 + i)))
        for i, p in enumerate((p0, p1))
    ]
    eng = _engine(mp)
    eng.admit(DecodeRequest(prompt=p0, max_new_tokens=8, sample=GREEDY,
                            seed=500), tag=0)
    done = dict(eng.step())  # slot 0 decodes its first chunk
    eng.admit(DecodeRequest(prompt=p1, max_new_tokens=8, sample=GREEDY,
                            seed=501), tag=1)
    # chunk 1 (slot-0-local chunk index 1): slot 1 is mid-prefill
    plan = inject.FaultPlan().poison_decode_slot_at(0, chunk=1)
    with inject.inject(plan):
        done.update(_drain(eng))
    assert plan.delivered == ["decode.slot_nan.0@1"]
    assert done[0].rewinds == 1 and done[0].status == "ok"
    assert done[1].status == "ok" and done[1].rewinds == 0
    for i in range(2):
        np.testing.assert_array_equal(done[i].tokens, refs[i])


def test_reprefill_rung_restarts_midprefill_slot_bitwise(mp):
    """Rungs 1+2 fired on a slot STILL MID-PREFILL: rung 2 cannot rebuild
    from emitted tokens (there are none) — it restarts the in-scan
    prefill from a zero state row. Tokens still come out bitwise; the
    co-resident decoder streams untouched."""
    model, params = mp
    p0, p1 = _prompt(20, 5), _prompt(21, 30)
    refs = [
        np.asarray(generate(model, params, p, 8, GREEDY,
                            rng=jax.random.PRNGKey(600 + i)))
        for i, p in enumerate((p0, p1))
    ]
    eng = _engine(mp)
    eng.admit(DecodeRequest(prompt=p0, max_new_tokens=8, sample=GREEDY,
                            seed=600), tag=0)
    eng.admit(DecodeRequest(prompt=p1, max_new_tokens=8, sample=GREEDY,
                            seed=601), tag=1)
    # slot 1's chunk 1 is mid-prefill (pieces of 8 over a 30-token
    # prompt); two deliveries poison the rewind retry too -> rung 2
    plan = inject.FaultPlan().poison_decode_slot_at(1, chunk=1, times=2)
    with inject.inject(plan):
        done = _drain(eng)
    assert (done[1].rewinds, done[1].reprefills) == (1, 1)
    assert done[0].rewinds == 0
    for i in range(2):
        assert done[i].status == "ok", i
        np.testing.assert_array_equal(done[i].tokens, refs[i],
                                      err_msg=f"request {i}")


def test_deadline_mid_prefill_evicts_with_zero_tokens(mp):
    """A deadline expiring while the slot is still consuming its prompt
    evicts cleanly with zero tokens; the co-resident request streams."""
    model, params = mp
    p0, p1 = _prompt(30, 5), _prompt(31, 30)
    ref0 = np.asarray(generate(model, params, p0, 12, GREEDY,
                               rng=jax.random.PRNGKey(700)))
    now = [0.0]
    eng = _engine(mp, clock=lambda: now[0])
    eng.admit(DecodeRequest(prompt=p0, max_new_tokens=12, sample=GREEDY,
                            seed=700), tag="fast")
    eng.admit(DecodeRequest(prompt=p1, max_new_tokens=12, sample=GREEDY,
                            seed=701), tag="tight", deadline_at=1.5)
    done = {}
    while eng.busy:
        done.update(dict(eng.step()))
        now[0] += 1.0
    assert done["tight"].status == "deadline"
    assert done["tight"].new_tokens == 0, "still mid-prefill at expiry"
    assert done["fast"].status == "ok"
    np.testing.assert_array_equal(done["fast"].tokens, ref0)


# ---------------------------------------------------------------------------
# PR 6 sessions x in-scan admission
# ---------------------------------------------------------------------------


def test_session_suspend_resume_across_inscan_admission(mp, tmp_path):
    """A session whose first turn was admitted VIA IN-SCAN PREFILL
    suspends at turn end and resumes O(1) for turn 2 — the concatenated
    turns are bitwise one longer uninterrupted request (the PR 6 contract
    must survive the new admission path)."""
    model, params = mp
    prompt = _prompt(40, 21)  # 3 pieces at pchunk=8
    ref = np.asarray(generate(model, params, prompt, 16, SAMPLED,
                              rng=jax.random.PRNGKey(900)))
    cfg = ServeConfig(chunk=4, slots=2, max_inflight=4,
                      prefill_buckets="8,16,32", prefill_chunk=8,
                      session_dir=str(tmp_path / "sessions"))
    srv = Server(model, params, cfg)
    p1 = srv.submit(DecodeRequest(prompt=prompt, max_new_tokens=8,
                                  sample=SAMPLED, seed=900, session_id="s"))
    assert srv.serve(drain_when_idle=True) == 0
    assert p1.result.status == "ok"
    np.testing.assert_array_equal(p1.result.tokens, ref[:, :8])
    # turn 2: empty-prompt continuation -> O(1) resume, no prefill
    p2 = srv.submit(DecodeRequest(prompt=np.zeros((1, 0), np.int32),
                                  max_new_tokens=8, sample=SAMPLED,
                                  seed=900, session_id="s"))
    assert srv.serve(drain_when_idle=True) == 0
    assert p2.result.status == "ok"
    np.testing.assert_array_equal(p2.result.tokens, ref[:, 8:16])
    srv.close()


def test_drain_mid_prefill_suspends_without_snapshot(mp, tmp_path):
    """SIGTERM drain while a session turn is STILL MID-PREFILL: the slot
    comes back 'suspended' with zero tokens and NO snapshot persisted —
    the store keeps whatever it held, and a re-submitted turn serves
    bitwise from scratch."""
    model, params = mp
    prompt = _prompt(41, 30)
    ref = np.asarray(generate(model, params, prompt, 8, GREEDY,
                              rng=jax.random.PRNGKey(901)))
    cfg = ServeConfig(chunk=4, slots=2, max_inflight=4,
                      prefill_buckets="8,16,32", prefill_chunk=8,
                      session_dir=str(tmp_path / "sessions"))
    srv = Server(model, params, cfg)
    p1 = srv.submit(DecodeRequest(prompt=prompt, max_new_tokens=8,
                                  sample=GREEDY, seed=901, session_id="d"))
    plan = inject.FaultPlan().preempt_at_chunk(0)  # signal at boundary 0
    with inject.inject(plan):
        assert srv.serve() == 0
    assert p1.result is not None and p1.result.status == "suspended"
    assert p1.result.new_tokens == 0
    assert p1.result.session is None, "a partial prompt is not a turn"
    # a fresh server serves the re-submitted turn bitwise from scratch
    srv2 = Server(model, params, cfg)
    p2 = srv2.submit(DecodeRequest(prompt=prompt, max_new_tokens=8,
                                   sample=GREEDY, seed=901, session_id="d"))
    assert srv2.serve(drain_when_idle=True) == 0
    assert p2.result.status == "ok"
    np.testing.assert_array_equal(p2.result.tokens, ref)
    srv2.close()


def test_occupancy_distinguishes_prefilling_from_decoding(mp):
    model, params = mp
    eng = _engine(mp)
    eng.admit(DecodeRequest(prompt=_prompt(60, 5), max_new_tokens=8,
                            sample=GREEDY, seed=0), tag=0)
    eng.admit(DecodeRequest(prompt=_prompt(61, 30), max_new_tokens=8,
                            sample=GREEDY, seed=1), tag=1)
    occ = eng.occupancy()
    assert occ["active"] == 2
    assert occ["prefilling"] == 2  # nothing consumed before the 1st step
    eng.step()
    occ = eng.occupancy()
    assert occ["prefilling"] == 1 and occ["decoding"] == 1
    _drain(eng)
    occ = eng.occupancy()
    assert occ["prefilling"] == 0 and occ["active"] == 0


# ---------------------------------------------------------------------------
# ISSUE 33: a piece for every waiting slot, up to the cap, at one boundary
# ---------------------------------------------------------------------------

# 8 slots over chunks of 2: four pieces a boundary, overdue after two
WIDE = dict(slots=8, chunk=2)
CAP = prefill_piece_cap(**WIDE)
LENGTHS = [17, 3, 21, 8, 9, 16, 5, 30]  # one to four pieces of 8


def _solo(mp, prompt, n, sample, seed):
    model, params = mp
    return np.asarray(generate(model, params, prompt, n, sample,
                               rng=jax.random.PRNGKey(seed)))


def _pieces_by_boundary():
    """(the list an ``on_event`` tap fills, the tap): the slots that
    consumed a piece, one list per boundary (closed by :func:`_step`)."""
    log = [[]]

    def tap(kind, fields):
        if kind == "prefill_piece":
            log[-1].append(fields["slot"])

    return log, tap


def _step(eng, log):
    out = dict(eng.step())
    log.append([])
    return out


def test_cap_and_overdue_threshold_come_from_slots_and_chunk():
    assert prefill_piece_cap(64, 16) == 4 and prefill_overdue_after(64, 16) == 16
    assert prefill_piece_cap(8, 2) == 4 and prefill_overdue_after(8, 2) == 2
    assert prefill_piece_cap(2, 4) == 1 and prefill_overdue_after(2, 4) == 2
    assert prefill_piece_cap(5, 2) == 2 and prefill_overdue_after(5, 2) == 3


@pytest.mark.parametrize("k", [2, 3, CAP, CAP + 2])
@pytest.mark.parametrize("sample", [GREEDY, SAMPLED], ids=["greedy", "sampled"])
def test_k_slots_admitted_before_one_boundary_bitwise(mp, k, sample):
    """K requests admitted before ONE boundary: it serves min(K, cap) of
    them, and every request's tokens are what the one-piece-a-boundary
    schedule (the next request admitted only once nobody waits) and the
    solo scan emit."""
    prompts = [_prompt(200 + i, ln) for i, ln in enumerate(LENGTHS[:k])]
    refs = [_solo(mp, p, 8, sample, 500 + i) for i, p in enumerate(prompts)]

    def request(i):
        return DecodeRequest(prompt=prompts[i], max_new_tokens=8,
                             sample=sample, seed=500 + i)

    log, tap = _pieces_by_boundary()
    eng = _engine(mp, **WIDE, on_event=tap)
    for i in range(k):
        eng.admit(request(i), tag=i)
    together = _step(eng, log)
    assert len(log[0]) == min(k, CAP)
    while eng.busy:
        together.update(_step(eng, log))
    assert all(len(b) <= CAP for b in log)

    eng = _engine(mp, **WIDE)
    one_by_one, pending = {}, list(range(k))
    while pending or eng.busy:
        if pending and eng.prefilling_count == 0:
            i = pending.pop(0)
            eng.admit(request(i), tag=i)
        assert eng.prefilling_count <= 1
        one_by_one.update(dict(eng.step()))
    for i, ref in enumerate(refs):
        for name, done in (("together", together), ("one", one_by_one)):
            assert done[i].status == "ok", (name, i)
            np.testing.assert_array_equal(
                done[i].tokens, ref, err_msg=f"{name} k={k} request {i}"
            )


def test_selection_order_overdue_then_shortest_then_index():
    """The device's order from crafted rows: overdue slots first, the
    longest passed over first; then the shortest remainder; ties to the
    lowest index; free and decoding rows last and never counted."""
    active = jnp.asarray([1, 1, 1, 1, 1, 1, 0, 1], bool)
    rem = jnp.asarray([9, 4, 4, 30, 0, 12, 3, 25], jnp.int32)
    pwait = jnp.asarray([0, 1, 0, 2, 7, 3, 9, 1], jnp.int32)
    order, n = _prefill_selection(active, rem, pwait, WIDE["chunk"])
    # overdue (pwait >= 2): slot 5 (3) before slot 3 (2); slot 4 decodes
    # and slot 6 is free, whatever their pwait; then rem 4, 4, 9, 25
    assert list(np.asarray(order)[:6]) == [5, 3, 1, 2, 0, 7]
    assert int(n) == CAP
    order, n = _prefill_selection(
        active & (jnp.arange(8) < 2), rem, pwait, WIDE["chunk"])
    assert list(np.asarray(order)[:2]) == [1, 0] and int(n) == 2


def _recording_unified(monkeypatch):
    """Record what the DEVICE's rule selects in every attempt the engine
    launches: the program's own ``_prefill_selection`` on the attempt's
    operands, read back (the program itself returns no schedule)."""
    attempts = []
    real = batching.decode_batched_prefill_chunk

    def spy(model, params, carry, rngs, active, pbuf, plen, pfold, pwait,
            n_steps, pchunk, sample):
        order, n = _prefill_selection(
            active, jnp.maximum(plen - carry[2], 0), pwait, n_steps)
        attempts.append([int(i) for i in np.asarray(order)[:int(n)]])
        return real(model, params, carry, rngs, active, pbuf, plen, pfold,
                    pwait, n_steps, pchunk, sample)

    monkeypatch.setattr(batching, "decode_batched_prefill_chunk", spy)
    return attempts


def _recording_host(eng):
    picks = []
    real = eng._selected_prefill_slots

    def spy(active):
        picks.append(real(active))
        return picks[-1]

    eng._selected_prefill_slots = spy
    return picks


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_host_selection_equals_device_every_boundary(mp, monkeypatch, seed):
    """A seeded mixed run, more requests than slots, bursts of
    admissions: at every boundary the ordered list the host books is the
    list the device's rule walks, and every request comes out solo-equal
    (a host that booked another schedule would release tokens that were
    never computed)."""
    rng = np.random.default_rng(seed)
    n = 12
    lengths = rng.integers(2, 31, n)
    news = rng.choice([2, 4, 6], n)
    prompts = [_prompt(300 + 20 * seed + i, int(ln))
               for i, ln in enumerate(lengths)]
    attempts = _recording_unified(monkeypatch)
    eng = _engine(mp, slots=5, chunk=2)
    picks = _recording_host(eng)
    done, pending, unified = {}, list(range(n)), 0
    while pending or eng.busy:
        for _ in range(int(rng.integers(0, 4))):
            if pending and eng.has_free_slot:
                i = pending.pop(0)
                eng.admit(DecodeRequest(prompt=prompts[i],
                                        max_new_tokens=int(news[i]),
                                        sample=SAMPLED, seed=700 + i), tag=i)
        if not eng.busy:
            continue
        before = len(attempts)
        done.update(dict(eng.step()))
        if len(attempts) > before:
            unified += 1
            assert picks[-1] == attempts[-1]
        else:
            assert picks[-1] == []
    assert unified > 4 and max(len(a) for a in attempts) == 2
    for i in range(n):
        assert done[i].status == "ok"
        np.testing.assert_array_equal(
            done[i].tokens,
            _solo(mp, prompts[i], int(news[i]), SAMPLED, 700 + i),
            err_msg=f"seed {seed} request {i}",
        )


def test_rung3_masks_a_prefilling_slot_and_the_next_moves_up(mp, monkeypatch):
    """Four waiting slots, cap 2: the first attempt serves slots 1 and 2
    (the shortest). Slot 1's ladder is exhausted, rung 3 replays with it
    masked out, and slot 0 moves up: the host books the REPLAY's list."""
    lengths = [20, 5, 9, 30]
    prompts = [_prompt(400 + i, ln) for i, ln in enumerate(lengths)]
    attempts = _recording_unified(monkeypatch)
    eng = _engine(mp, slots=4, chunk=2)
    picks = _recording_host(eng)
    for i, p in enumerate(prompts):
        eng.admit(DecodeRequest(prompt=p, max_new_tokens=6, sample=GREEDY,
                                seed=800 + i), tag=i)
    plan = inject.FaultPlan().poison_decode_slot_at(1, chunk=0, times=3)
    with inject.inject(plan):
        done = dict(eng.step())
    assert attempts == [[1, 2], [1, 2], [1, 2], [2, 0]]
    assert picks == [[2, 0]]
    assert done[1].status == "failed" and done[1].new_tokens == 0
    assert {e["slot"]: e["prefill_tokens"] for e in eng.last_boundary
            if not e.get("failed")} == {0: 8, 2: 8, 3: 0}
    done.update(_drain(eng))
    for i in (0, 2, 3):
        assert done[i].status == "ok", i
        np.testing.assert_array_equal(
            done[i].tokens, _solo(mp, prompts[i], 6, GREEDY, 800 + i))


def test_long_prompt_beside_a_stream_of_short_ones_is_served_in_bound(mp):
    """Shortest-first under a cap would pass a long prompt over for as
    long as shorter ones keep arriving. The bound the program states:
    no slot is passed over more than ``prefill_overdue_after +
    (slots - 1) // cap`` boundaries in a row."""
    slots, chunk = 4, 2
    cap = prefill_piece_cap(slots, chunk)
    bound = prefill_overdue_after(slots, chunk) + (slots - 1) // cap
    long_prompt = _prompt(450, 30)  # four pieces of 8
    log, tap = _pieces_by_boundary()
    eng = _engine(mp, slots=slots, chunk=chunk, on_event=tap)
    eng.admit(DecodeRequest(prompt=long_prompt, max_new_tokens=4,
                            sample=GREEDY, seed=900), tag="long")
    done, shorts, worst, boundaries = {}, 0, 0, 0
    while "long" not in done:
        while eng.has_free_slot:  # a short prompt for every free slot
            eng.admit(DecodeRequest(prompt=_prompt(460 + shorts, 3),
                                    max_new_tokens=2, sample=GREEDY,
                                    seed=shorts), tag=shorts)
            shorts += 1
        assert eng.prefilling_count > cap, "the cap binds every boundary"
        done.update(_step(eng, log))
        boundaries += 1
        worst = max([worst] + [s.passed_over for s in eng._slots
                               if s is not None])
        assert boundaries < 40, "the long prompt starved"
    assert worst <= bound
    assert worst >= prefill_overdue_after(slots, chunk), (
        "the stream never made a slot overdue: the test lost its point")
    assert all(len(b) == cap for b in log[:boundaries])
    assert done["long"].status == "ok"
    np.testing.assert_array_equal(
        done["long"].tokens, _solo(mp, long_prompt, 4, GREEDY, 900))
    assert all(r.status == "ok" for r in done.values())


@partial(jax.jit, static_argnums=(0, 8, 9, 10))
def _one_piece_chunk(model, params, carry, rngs, active, pbuf, plen, pfold,
                     n_steps, pchunk, sample_cfg):
    """The unified program as it was before ISSUE 33: the boundary's ONE
    piece goes to the waiting slot with the shortest remainder (a
    discarded garbage piece when none waits), then the same scan."""
    token, states, t, emit, done = carry
    piece = min(pchunk, pbuf.shape[1])
    rem = jnp.maximum(plen - t, 0)
    prefilling = active & (rem > 0)
    has = prefilling.any()
    sel = jnp.argmin(jnp.where(prefilling, rem, jnp.iinfo(jnp.int32).max))
    cons = jnp.where(has, jnp.minimum(rem[sel], piece), 0)
    logits1, fed = _prefill_extend_row(
        model, params, pbuf, states, sel, t[sel], cons, piece)
    states = jax.tree.map(
        lambda x, n: x.at[sel].set(jnp.where(has, n, x[sel])), states, fed)
    completed = has & (rem[sel] <= piece)
    key = jax.random.fold_in(rngs[sel], pfold[sel])
    first = _sample_rows(logits1[None], key[None], sample_cfg)[0]
    token = token.at[sel].set(jnp.where(completed, first, token[sel]))
    emit = emit.at[sel].set(jnp.where(completed, pfold[sel], emit[sel]))
    t = t.at[sel].set(t[sel] + cons)
    emitting = active & (t >= plen)
    body = partial(
        _decode_batched_prefill_body, model, params, sample_cfg, rngs,
        emitting, decode_live_rows(emitting, backend=model.cfg.backend))
    carry, tokens = jax.lax.scan(
        body, (token, states, t, emit, done), None, length=n_steps)
    return carry, jnp.moveaxis(tokens, 0, 1)


@pytest.mark.parametrize("waiting", [1, 0], ids=["one-waiting", "none"])
@pytest.mark.parametrize("sample", [GREEDY, SAMPLED], ids=["greedy", "sampled"])
def test_one_waiting_slot_leaves_every_carry_leaf_as_the_one_piece_program(
        mp, waiting, sample):
    """With one waiting slot the loop runs once and every leaf of the
    carry, and every emitted token, is what the one-piece program gives;
    with the only waiting slot masked out (a rung-3 replay) it runs no
    piece where the old program ran one and discarded it."""
    model, params = mp
    eng = _engine(mp, slots=4, chunk=2)
    eng.admit(DecodeRequest(prompt=_prompt(480, 5), max_new_tokens=12,
                            sample=sample, seed=1), tag=0)
    eng.step()  # slot 0 decodes from here on
    eng.admit(DecodeRequest(prompt=_prompt(481, 21), max_new_tokens=12,
                            sample=sample, seed=2), tag=1)
    eng.step()  # slot 1: one piece of three consumed, mid-prompt
    assert eng.prefilling_count == 1
    active = jnp.asarray([True, bool(waiting), False, False])
    dyn = (params, eng._carry, eng._rngs, active, eng._pbuf, eng._plen,
           eng._pfold)
    new = _decode_batched_prefill_chunk_jit(
        model, *dyn, jnp.zeros((4,), jnp.int32), 2, 8, sample)
    old = _one_piece_chunk(model, *dyn, 2, 8, sample)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    moved = int(new[0][2][1]) - int(eng._carry[2][1])
    assert moved == (8 if waiting else 0)


# ---------------------------------------------------------------------------
# ISSUE 40: one staging dispatch a boundary — admissions wait as host rows
# and ONE donated K-row program writes them
# ---------------------------------------------------------------------------

K = batching.STAGE_ROWS
# 0, 1, the int32 edge and past it, the uint32 edge and past it, negative,
# and both ends of what ``jax.random.PRNGKey`` takes (a C long)
SEEDS = (0, 1, 2**31 - 1, 2**31, 2**32 - 1, 2**32 + 5, -1, -(2**63), 2**63 - 1)


def _device_state(eng):
    """Every leaf the staging program writes, on the host."""
    return [np.asarray(x) for x in jax.tree.leaves(
        (eng._carry, eng._rngs, eng._plen, eng._pfold, eng._pbuf))]


def _staged_by_hand(state, i, prompt, seed, fold):
    """One admission as the one-row staging of before wrote it, in eager
    jnp: the row padded by ``jnp.pad``, the key from ``PRNGKey``."""
    (token, states, t, emit, done), rngs, plen, pfold, pbuf = state
    width = max(pbuf.shape[1], bucket_for(prompt.shape[1], BUCKETS))
    pbuf = jnp.pad(pbuf, ((0, 0), (0, width - pbuf.shape[1])))
    row = jnp.pad(prompt, ((0, 0), (0, width - prompt.shape[1])))[0]
    states = jax.tree.map(lambda x: x.at[i].set(0), states)
    carry = (token.at[i].set(0), states, t.at[i].set(0), emit.at[i].set(fold),
             done.at[i].set(False))
    return (carry, rngs.at[i].set(jax.random.PRNGKey(seed)),
            plen.at[i].set(prompt.shape[1]), pfold.at[i].set(fold),
            pbuf.at[i].set(row))


def _dirty(eng, n):
    """Serve ``n`` requests to the end, so every row a later admission
    zeroes holds something and the staging buffer exists (8 wide)."""
    for i in range(n):
        eng.admit(DecodeRequest(prompt=_prompt(600 + i, 3 + i % 5),
                                max_new_tokens=5, sample=SAMPLED, seed=i),
                  tag=("warm", i))
    _drain(eng)


@pytest.mark.parametrize("lens,folds,donate", [
    ((5,), (0,), False),
    (tuple(3 + i % 6 for i in range(K)), (0,) * K, False),
    (tuple(2 + i % 7 for i in range(K + 3)), (0,) * (K + 3), False),
    ((5, 12, 30, 7), (0, 0, 0, 0), False),
    ((6, 13, 4), (0, 7, 2), False),
    ((5, 9, 20), (0, 0, 0), True),
], ids=["one-row", "K-rows", "more-than-K", "pbuf-grows-inside", "folds",
        "donate-carry"])
def test_one_flush_writes_what_single_row_stagings_wrote(mp, lens, folds,
                                                         donate):
    """N pending admissions flushed at once leave the carry, the rng keys,
    ``plen``, ``pfold`` and the staging buffer BITWISE what N one-row
    stagings left, in ceil(N / K) dispatches; and the slots then serve the
    solo tokens."""
    model, params = mp
    n = len(lens)
    eng = _engine(mp, slots=max(n, 2), chunk=4)
    eng.donate_carry = donate
    _dirty(eng, n)
    want = (eng._carry, eng._rngs, eng._plen, eng._pfold, eng._pbuf)
    before = eng.staging_dispatches
    prompts = [_prompt(700 + i, ln) for i, ln in enumerate(lens)]
    for i, (prompt, fold) in enumerate(zip(prompts, folds)):
        seed = SEEDS[i % len(SEEDS)]
        slot = eng.admit(DecodeRequest(prompt=prompt, max_new_tokens=6,
                                       sample=SAMPLED, seed=seed), tag=i,
                         sample_index=fold)
        want = _staged_by_hand(want, slot, prompt, seed, fold)
    assert eng.staging_dispatches == before  # nothing staged yet
    want = [np.asarray(x) for x in jax.tree.leaves(want)]
    eng.flush_admissions()
    assert eng.staging_dispatches - before == -(-n // K)
    got = _device_state(eng)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    done = _drain(eng)
    for i, (prompt, fold) in enumerate(zip(prompts, folds)):
        assert done[i].status == "ok"
        if fold == 0:
            ref = generate(model, params, prompt, 6, SAMPLED,
                           rng=jax.random.PRNGKey(SEEDS[i % len(SEEDS)]))
            np.testing.assert_array_equal(done[i].tokens, np.asarray(ref))


@pytest.mark.parametrize("seed", SEEDS + (np.int64(7), np.uint32(2**32 - 1),
                                          True))
def test_host_key_is_prngkey_bitwise(seed):
    key = batching._seed_key(seed)
    ref = np.asarray(jax.random.PRNGKey(seed))
    assert key.dtype == ref.dtype and key.shape == ref.shape
    np.testing.assert_array_equal(key, ref)


@pytest.mark.parametrize("seed", [2**63, -(2**63) - 1, 1.5])
def test_host_key_refuses_what_prngkey_refuses(seed):
    with pytest.raises((OverflowError, TypeError)) as ours:
        batching._seed_key(seed)
    with pytest.raises(ours.type):
        jax.random.PRNGKey(seed)


def _counting_stage(monkeypatch):
    """Count the staging program's calls and the valid rows of each."""
    calls = []
    real = batching._stage_rows_carry

    def counted(carry, rngs, plen, pfold, pbuf, rows):
        calls.append(int((rows[:, 0] >= 0).sum()))
        return real(carry, rngs, plen, pfold, pbuf, rows)

    monkeypatch.setattr(batching, "_stage_rows_carry", counted)
    return calls


def test_a_boundary_makes_one_staging_dispatch(mp, monkeypatch):
    """However many prompts a boundary admits (up to K), ``step`` stages
    them with ONE call; a boundary that admits nothing makes none."""
    calls = _counting_stage(monkeypatch)
    eng = _engine(mp, slots=6, chunk=4)
    for i, ln in enumerate((5, 11, 3)):
        eng.admit(DecodeRequest(prompt=_prompt(800 + i, ln), max_new_tokens=9,
                                sample=GREEDY, seed=i), tag=i)
    assert calls == []
    eng.step()
    assert calls == [3] and eng.staging_dispatches == 1
    eng.step()  # nothing admitted
    assert calls == [3]
    for i, ln in enumerate((7, 2)):
        eng.admit(DecodeRequest(prompt=_prompt(810 + i, ln), max_new_tokens=5,
                                sample=GREEDY, seed=i), tag=10 + i)
    eng.step()
    assert calls == [3, 2] and eng.staging_dispatches == 2
    assert all(r.status == "ok" for r in _drain(eng).values())


def test_server_counts_one_dispatch_a_boundary_and_isolates_refusals(
        mp, monkeypatch):
    """``admit_dispatches`` is the boundaries that admitted; an over-bucket
    prompt and a ``SampleConfig`` mismatch among the admissions are their
    own error results while the others are staged and served."""
    model, params = mp
    calls = _counting_stage(monkeypatch)
    srv = Server(model, params, ServeConfig(
        chunk=4, slots=4, max_inflight=16, prefill_buckets="8,16,32",
        prefill_chunk=8))
    good = [(i, _prompt(820 + i, 3 + 3 * i)) for i in range(7)]
    pend = {}
    for i, prompt in good[:2]:
        pend[i] = srv.submit(DecodeRequest(prompt=prompt, max_new_tokens=6,
                                           sample=GREEDY, seed=i))
    too_long = srv.submit(DecodeRequest(prompt=_prompt(830, 40),
                                        max_new_tokens=6, sample=GREEDY))
    other = srv.submit(DecodeRequest(prompt=_prompt(831, 5), max_new_tokens=6,
                                     sample=SAMPLED))
    for i, prompt in good[2:]:
        pend[i] = srv.submit(DecodeRequest(prompt=prompt, max_new_tokens=6,
                                           sample=GREEDY, seed=i))
    admitting = []
    real = srv._admit_from_queue

    def watched(wd=None):
        resident = srv.engine.active_count
        n = real(wd)
        admitting.append(srv.engine.active_count - resident)
        return n

    monkeypatch.setattr(srv, "_admit_from_queue", watched)
    assert srv.serve(drain_when_idle=True) == 0
    for failed, kind in ((too_long, "bucket"), (other, "SampleConfig")):
        assert failed.result is None and kind in str(failed.error)
    for i, prompt in good:
        ref = generate(model, params, prompt, 6, GREEDY,
                       rng=jax.random.PRNGKey(i))
        assert pend[i].result.status == "ok"
        np.testing.assert_array_equal(pend[i].result.tokens, np.asarray(ref))
    flat = srv.metrics.counters_flat()
    boundaries_that_admitted = sum(n > 0 for n in admitting)
    assert admitting[0] == 4 and boundaries_that_admitted >= 2
    assert flat["admit_dispatches"] == boundaries_that_admitted == len(calls)
    assert sum(calls) == len(good)
    assert flat["admit_dispatches"] < flat["chunks"]
    srv.close()


def _both_orders(mp, act, **kw):
    """Run ``act(engine, admit)`` on two engines: one whose admissions wait
    for the flush, one that stages each admission at once (the order of
    device writes before ISSUE 40). Returns what ``act`` returned and the
    device state of each."""
    out = []
    for at_once in (False, True):
        eng = _engine(mp, slots=4, chunk=4, **kw)

        def admit(*a, eng=eng, at_once=at_once, **k):
            slot = eng.admit(*a, **k)
            if at_once:
                eng.flush_admissions()
            return slot

        out.append((act(eng, admit), _device_state(eng), eng))
    return out


def _same_device_state(runs):
    (_, lazy, _), (_, eager, _) = runs
    assert len(lazy) == len(eager)
    for a, b in zip(lazy, eager):
        np.testing.assert_array_equal(a, b)


def _request(i, ln, new=6, **kw):
    return DecodeRequest(prompt=_prompt(900 + i, ln), max_new_tokens=new,
                         sample=SAMPLED, seed=40 + i, **kw)


def _suspended_session(mp):
    eng = _engine(mp, slots=2, chunk=4)
    eng.admit(_request(0, 9, new=8), tag="s", session_id="conv")
    return _drain(eng)["s"].session


def test_resume_sees_the_rows_still_pending(mp):
    sess = _suspended_session(mp)
    assert sess is not None

    def act(eng, admit):
        admit(_request(1, 12), tag="a")
        admit(_request(2, 5), tag="b")
        eng.resume(sess, DecodeRequest(
            prompt=np.zeros((1, 0), np.int32), max_new_tokens=12,
            sample=SAMPLED, seed=40, session_id="conv"), tag="s")
        assert not eng._staged
        return None

    runs = _both_orders(mp, act)
    _same_device_state(runs)
    (_, _, lazy), (_, _, eager) = runs
    a, b = _drain(lazy), _drain(eager)
    assert sorted(a) == sorted(b) == ["a", "b", "s"]
    for tag in a:
        np.testing.assert_array_equal(a[tag].tokens, b[tag].tokens)


def test_prefix_hit_sees_the_rows_still_pending(mp, tmp_path):
    from orion_tpu.serving.prefix_store import PrefixStore

    store = PrefixStore(str(tmp_path), params_id="inscan-test", align=8)
    shared = np.asarray(_prompt(950, 16))
    first = _engine(mp, slots=2, chunk=4, prefix_store=store)
    miss = np.concatenate([shared, np.asarray(_prompt(952, 5))], axis=1)
    first.admit(DecodeRequest(prompt=miss, max_new_tokens=4, sample=SAMPLED,
                              seed=1, prefix_len=16), tag=0)
    assert first.publish_pending_prefixes() == 1
    hit = np.concatenate([shared, np.asarray(_prompt(951, 7))], axis=1)

    def act(eng, admit):
        admit(_request(3, 6), tag="a")
        before = eng.staging_dispatches
        admit(DecodeRequest(prompt=hit, max_new_tokens=6, sample=SAMPLED,
                            seed=9), tag="hit")
        # the pending row went first, then the hit's own dispatch
        assert not eng._staged and eng.staging_dispatches - before >= 1
        return eng._slots[1].prompt_remaining

    runs = _both_orders(mp, act, prefix_store=store)
    assert runs[0][0] == runs[1][0] == 7  # the uncached suffix only
    _same_device_state(runs)
    model, params = mp
    ref = generate(model, params, jnp.asarray(hit), 6, SAMPLED,
                   rng=jax.random.PRNGKey(9))
    np.testing.assert_array_equal(_drain(runs[0][2])["hit"].tokens,
                                  np.asarray(ref))


@pytest.mark.parametrize("how", ["suspend_sessions", "drain_evict_all"])
def test_suspension_and_forced_eviction_see_the_rows_still_pending(mp, how):
    def act(eng, admit):
        admit(_request(4, 5, new=16), tag="s", session_id="conv")
        eng.step()
        eng.step()  # the session's slot is decoding
        admit(_request(5, 11), tag="a")
        admit(_request(6, 4), tag="b")
        out = dict(getattr(eng, how)())
        assert not eng._staged
        return out

    runs = _both_orders(mp, act)
    _same_device_state(runs)
    lazy, eager = runs[0][0], runs[1][0]
    assert sorted(lazy) == sorted(eager)
    assert set(lazy) == ({"s"} if how == "suspend_sessions" else {"s", "a", "b"})
    for tag in lazy:
        np.testing.assert_array_equal(lazy[tag].tokens, eager[tag].tokens)
    if how == "suspend_sessions":
        a, b = lazy["s"].session, eager["s"].session
        for x, y in zip(jax.tree.leaves((a.token, a.state, a.t, a.emit)),
                        jax.tree.leaves((b.token, b.state, b.t, b.emit))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_a_never_seen_prompt_length_builds_no_program(mp):
    """Padding is numpy's: once a staged width is warm, a prompt of a new
    length inside it traces, lowers and compiles nothing."""
    model, params = mp
    srv = Server(model, params, ServeConfig(
        chunk=4, slots=2, max_inflight=8, prefill_buckets="8,16,32",
        prefill_chunk=8))

    # made first: a server hears every compile of its process, this
    # test's own ``randint`` of a new shape among them
    prompts = [np.asarray(_prompt(970 + i, ln))
               for i, ln in enumerate((9, 10, 11, 13, 16))]

    def serve(i):
        p = srv.submit(DecodeRequest(prompt=prompts[i], max_new_tokens=5,
                                     sample=GREEDY, seed=i))
        assert srv.serve(drain_when_idle=True) == 0
        assert p.result.status == "ok"

    serve(0)  # the 16-wide buffer, both boundary programs
    before = srv.metrics.counters_flat()
    for i in range(1, len(prompts)):
        serve(i)
    after = srv.metrics.counters_flat()
    for key in ("programs_traced", "programs_compiled",
                "programs_cache_loaded"):
        assert after[key] == before[key], key
    assert after["admit_dispatches"] - before["admit_dispatches"] == 4
    srv.close()
