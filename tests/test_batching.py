"""Continuous-batching suite (ISSUE 5): slot-multiplexed batched decode.

The two acceptance proofs live here — (1) N requests multiplexed through
the SlotEngine produce BITWISE-identical tokens to each request served
solo at the same seed, for slot counts {2, 4, 8}, greedy and sampled,
including a late arrival admitted mid-stream at a nonzero position; and
(2) the engine's whole serving lifetime costs ONE decode compile per
(slot count, chunk) with prefill compiles bounded by the bucket count.
Plus the per-slot chaos coverage (poisoning slot k walks the ladder for
THAT request only; SIGTERM mid-batch drains every in-flight slot to
completion) and the model-layer slot ops.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.generate import (
    SampleConfig,
    _decode_batched_chunk_jit,
    _prefill_carry_bucketed_jit,
    bucket_for,
    decode_batched_chunk,
    generate,
    prefill_carry,
)
from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.transformer import (
    TransformerLM,
    decode_state_finite_per_slot,
    extract_decode_slot,
    init_decode_state,
    insert_decode_slot,
)
from orion_tpu.resilience import inject
from orion_tpu.serving import (
    DecodeRequest,
    Health,
    RejectedError,
    ServeConfig,
    Server,
    SlotEngine,
    parse_buckets,
)

pytestmark = pytest.mark.chaos

# same shape family as tests/test_serving.py: one layer of each type so the
# vector-t decode path is exercised for (S, z), KV-cache, and ring-cache
# states alike
CFG = ModelConfig(
    name="batch_test", vocab_size=64, d_model=32, n_layers=3, n_heads=2,
    layer_types=("linear", "softmax", "swa"), window=4, max_seq_len=64,
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


def _prompts(n):
    """n prompts of VARYING lengths (3..7) — slots must sit at different
    positions, exercising the per-sequence t vector."""
    out = []
    for i in range(n):
        ln = 3 + (i % 5)
        out.append(
            jax.random.randint(
                jax.random.PRNGKey(1000 + i), (1, ln), 0, CFG.vocab_size
            ).astype(jnp.int32)
        )
    return out


def _solo_refs(mp, prompts, n_new, sample):
    model, params = mp
    return [
        np.asarray(
            generate(model, params, p, n_new, sample,
                     rng=jax.random.PRNGKey(500 + i))
        )
        for i, p in enumerate(prompts)
    ]


# ---------------------------------------------------------------------------
# acceptance: bitwise batched-vs-solo parity at slots {2, 4, 8}
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("slots", [2, 4, 8])
@pytest.mark.parametrize("sample", [GREEDY, SAMPLED], ids=["greedy", "sampled"])
def test_batched_parity_bitwise(mp, slots, sample):
    """N > slots concurrent requests through the Server: arrival is
    staggered by construction (the queue refills freed slots at chunk
    boundaries, so late requests are admitted mid-stream while earlier
    slots sit at nonzero positions) — every request's tokens must be
    BITWISE what the monolithic solo scan produces at the same seed."""
    model, params = mp
    n = slots + 2
    prompts = _prompts(n)
    refs = _solo_refs(mp, prompts, 8, sample)
    srv = Server(model, params, ServeConfig(chunk=4, slots=slots,
                                            max_inflight=n))
    ps = [
        srv.submit(DecodeRequest(prompt=p, max_new_tokens=8, sample=sample,
                                 seed=500 + i))
        for i, p in enumerate(prompts)
    ]
    assert srv.serve(drain_when_idle=True) == 0
    for i, (p, ref) in enumerate(zip(ps, refs)):
        assert p.result is not None and p.result.status == "ok", i
        np.testing.assert_array_equal(
            p.result.tokens, ref, err_msg=f"slots={slots} request {i}"
        )
    srv.close()


def test_late_admission_joins_midstream_bitwise(mp):
    """Engine-level staggered admission: request A decodes 2 chunks alone,
    THEN B is admitted (A's slot position is nonzero and mid-generation);
    both finish bitwise-identical to their solo runs."""
    model, params = mp
    prompts = _prompts(2)
    ref_a = _solo_refs(mp, prompts[:1], 16, SAMPLED)[0]
    ref_b = np.asarray(
        generate(model, params, prompts[1], 8, SAMPLED,
                 rng=jax.random.PRNGKey(501))
    )
    eng = SlotEngine(model, params, slots=4, chunk=4)
    eng.admit(
        DecodeRequest(prompt=prompts[0], max_new_tokens=16, sample=SAMPLED,
                      seed=500),
        tag="a",
    )
    done = {}
    for _ in range(2):  # A alone for 2 chunks
        done.update(dict(eng.step()))
    assert not done
    eng.admit(
        DecodeRequest(prompt=prompts[1], max_new_tokens=8, sample=SAMPLED,
                      seed=501),
        tag="b",
    )
    while eng.busy:
        done.update(dict(eng.step()))
    np.testing.assert_array_equal(done["a"].tokens, ref_a)
    np.testing.assert_array_equal(done["b"].tokens, ref_b)


def test_eos_evicts_early_and_pads_bitwise(mp):
    """A request whose row hits EOS mid-generation frees its slot at the
    next boundary; the PAD-filled tail must still be bitwise what the
    solo scan emits (it pads inside the scan, the engine pads host-side)."""
    model, params = mp
    prompt = _prompts(1)[0]
    base = np.asarray(
        generate(model, params, prompt, 12, GREEDY,
                 rng=jax.random.PRNGKey(500))
    )
    eos = int(base[0, 2])  # force EOS = the 3rd greedy token
    sample = SampleConfig(temperature=0.0, eos_token=eos, pad_token=0)
    ref = np.asarray(
        generate(model, params, prompt, 12, sample,
                 rng=jax.random.PRNGKey(500))
    )
    eng = SlotEngine(model, params, slots=2, chunk=4)
    eng.admit(
        DecodeRequest(prompt=prompt, max_new_tokens=12, sample=sample,
                      seed=500),
        tag="r",
    )
    steps = 0
    done = {}
    while eng.busy:
        done.update(dict(eng.step()))
        steps += 1
    assert steps < 3, "EOS at token 3 must free the slot before chunk 3"
    np.testing.assert_array_equal(done["r"].tokens, ref)


# ---------------------------------------------------------------------------
# acceptance: one decode compile per (slots, chunk); bounded prefill cache
# ---------------------------------------------------------------------------


def test_one_decode_compile_per_slot_count(mp):
    """Serving any number of requests — staggered arrivals, varying prompt
    lengths, mid-stream admissions — costs ONE batched-scan compile for
    the engine's lifetime at a fixed (slots, chunk): everything per-slot
    rides traced. Uses a (slots, chunk) pair unique to this test so the
    global jit cache delta is attributable."""
    model, params = mp
    before = _decode_batched_chunk_jit._cache_size()
    srv = Server(model, params, ServeConfig(chunk=3, slots=3, max_inflight=9))
    prompts = _prompts(7)
    ps = [
        srv.submit(DecodeRequest(prompt=p, max_new_tokens=7, sample=GREEDY,
                                 seed=i))
        for i, p in enumerate(prompts)
    ]
    srv.serve(drain_when_idle=True)
    assert all(p.result.status == "ok" for p in ps)
    srv.close()
    assert _decode_batched_chunk_jit._cache_size() - before == 1, (
        "the batched decode scan must compile exactly once per "
        "(slots, chunk) — a second entry means something per-slot leaked "
        "into the static signature"
    )


def test_prefill_bucketing_bounds_compile_cache(mp):
    """Bucketed prefill is bounded by the bucket count no matter how many
    lengths traffic brings (a compile a novel prompt length would leak)."""
    model, params = mp
    buckets = (8, 16, 32)
    before = _prefill_carry_bucketed_jit._cache_size()
    for ln in range(3, 20):  # 17 distinct lengths -> 2 buckets (8, 16, 32)
        prompt = jnp.ones((1, ln), jnp.int32)
        prefill_carry(model, params, prompt, GREEDY, jax.random.PRNGKey(0),
                      buckets)
    delta = _prefill_carry_bucketed_jit._cache_size() - before
    assert delta <= len(buckets), (
        f"{delta} prefill compiles for {len(buckets)} buckets"
    )


def test_bucketed_prefill_bitwise_equals_exact(mp):
    """The carry out of a bucket-padded prefill must DECODE like the
    unpadded one's (a bucket of the prompt's own length): same first
    token, same position, same tokens for 16 more steps of the engine's
    decode program (crossing the swa window, so ring-cache reconstruction
    under padding is covered too)."""
    model, params = mp
    one = jnp.ones((1,), bool)
    for ln in (3, 5, 7, 11):
        prompt = jax.random.randint(
            jax.random.PRNGKey(ln), (1, ln), 0, CFG.vocab_size
        ).astype(jnp.int32)
        rng = jax.random.PRNGKey(42)
        exact = prefill_carry(model, params, prompt, SAMPLED, rng, (ln,))
        bucketed = prefill_carry(model, params, prompt, SAMPLED, rng,
                                 (16, 32))
        np.testing.assert_array_equal(
            np.asarray(exact[0]), np.asarray(bucketed[0]),
            err_msg=f"first token, len {ln}",
        )
        assert int(exact[2]) == int(bucketed[2]) == ln
        te, tb = (
            decode_batched_chunk(
                model, params,
                (tok, states, t[None], jnp.zeros((1,), jnp.int32), done),
                rng[None], one, 16, SAMPLED,
            )[1]
            for tok, states, t, done in (exact, bucketed)
        )
        np.testing.assert_array_equal(
            np.asarray(te), np.asarray(tb), err_msg=f"decode, len {ln}"
        )


def test_parse_buckets():
    for off in ("", "off"):  # host-side prefill is gone: no bucket-less engine
        with pytest.raises(ValueError, match="host-side prefill"):
            parse_buckets(off, 512)
    assert parse_buckets("pow2", 512) == (16, 32, 64, 128, 256, 512)
    assert parse_buckets("pow2", 48) == (16, 32, 48)
    assert parse_buckets("32,8,64", 64) == (8, 32, 64)
    with pytest.raises(ValueError):
        parse_buckets("128", 64)
    assert bucket_for(9, (8, 16)) == 16
    assert bucket_for(99, (8, 16)) is None


# ---------------------------------------------------------------------------
# chaos: per-slot ladder + SIGTERM mid-batch
# ---------------------------------------------------------------------------


def test_poison_slot_k_rewinds_bitwise_others_untouched(mp):
    """Acceptance: decode.state_nan poisoning slot 1 only — request 1
    rewinds bitwise while requests 0 and 2 stream through untouched (no
    ladder engagement, bitwise outputs)."""
    model, params = mp
    prompts = _prompts(3)
    refs = _solo_refs(mp, prompts, 8, GREEDY)
    eng = SlotEngine(model, params, slots=4, chunk=4)
    for i, p in enumerate(prompts):
        eng.admit(
            DecodeRequest(prompt=p, max_new_tokens=8, sample=GREEDY,
                          seed=500 + i),
            tag=i,
        )
    plan = inject.FaultPlan().poison_decode_slot_at(1, chunk=1)
    done = {}
    with inject.inject(plan):
        while eng.busy:
            done.update(dict(eng.step()))
    assert plan.delivered == ["decode.slot_nan.1@1"]
    for i in range(3):
        assert done[i].status == "ok"
        np.testing.assert_array_equal(done[i].tokens, refs[i],
                                      err_msg=f"request {i}")
    assert done[1].rewinds == 1 and done[1].reprefills == 0
    assert done[0].rewinds == 0 and done[2].rewinds == 0


def test_poison_slot_escalates_to_reprefill_bitwise(mp):
    """Two deliveries poison the rewind retry too: slot 1 walks to the
    re-prefill rung (prompt + emitted tokens, mid-stream, at its own
    position) and still comes out bitwise; neighbours untouched."""
    model, params = mp
    prompts = _prompts(2)
    refs = _solo_refs(mp, prompts, 8, GREEDY)
    eng = SlotEngine(model, params, slots=2, chunk=4)
    for i, p in enumerate(prompts):
        eng.admit(
            DecodeRequest(prompt=p, max_new_tokens=8, sample=GREEDY,
                          seed=500 + i),
            tag=i,
        )
    plan = inject.FaultPlan().poison_decode_slot_at(1, chunk=1, times=2)
    done = {}
    with inject.inject(plan):
        while eng.busy:
            done.update(dict(eng.step()))
    assert done[1].status == "ok"
    assert (done[1].rewinds, done[1].reprefills) == (1, 1)
    for i in range(2):
        np.testing.assert_array_equal(done[i].tokens, refs[i])
    assert done[0].rewinds == 0


def test_exhausted_ladder_fails_one_slot_others_stream(mp):
    """Unlimited deliveries exhaust slot 0's ladder: THAT request fails
    with its partial tokens; the co-resident request completes bitwise
    and the engine keeps serving new requests afterwards."""
    model, params = mp
    prompts = _prompts(2)
    refs = _solo_refs(mp, prompts, 8, GREEDY)
    eng = SlotEngine(model, params, slots=2, chunk=4)
    for i, p in enumerate(prompts):
        eng.admit(
            DecodeRequest(prompt=p, max_new_tokens=8, sample=GREEDY,
                          seed=500 + i),
            tag=i,
        )
    plan = inject.FaultPlan().poison_decode_slot_at(0, chunk=1, times=-1)
    done = {}
    with inject.inject(plan):
        while eng.busy:
            done.update(dict(eng.step()))
    assert done[0].status == "failed"
    assert done[0].new_tokens == 4, "the finite chunk before the fault is kept"
    np.testing.assert_array_equal(done[0].tokens, refs[0][:, :4])
    assert done[1].status == "ok"
    np.testing.assert_array_equal(done[1].tokens, refs[1])
    # the poisoned slot's row is overwritten by the next admission
    eng.admit(
        DecodeRequest(prompt=prompts[0], max_new_tokens=8, sample=GREEDY,
                      seed=500),
        tag="again",
    )
    while eng.busy:
        done.update(dict(eng.step()))
    assert done["again"].status == "ok"
    np.testing.assert_array_equal(done["again"].tokens, refs[0])


def test_sigterm_mid_batch_drains_all_slots_and_exits_zero(mp):
    """Acceptance: SIGTERM at an engine chunk boundary with a FULL batch —
    every in-flight slot drains to completion (bitwise), the queued
    request is admitted and completes too, new submits are rejected, and
    the loop exits 0 with health DRAINING -> DEAD."""
    model, params = mp
    prompts = _prompts(3)
    refs = _solo_refs(mp, prompts, 8, GREEDY)
    srv = Server(model, params, ServeConfig(chunk=4, slots=2, max_inflight=4))
    ps = [
        srv.submit(DecodeRequest(prompt=p, max_new_tokens=8, sample=GREEDY,
                                 seed=500 + i))
        for i, p in enumerate(prompts)
    ]
    plan = inject.FaultPlan().preempt_at_chunk(1)
    with inject.inject(plan):
        rc = srv.serve()
    assert rc == 0
    assert plan.delivered == ["serve.chunk@1"]
    assert srv.health.state is Health.DEAD
    for i, (p, ref) in enumerate(zip(ps, refs)):
        assert p.result is not None and p.result.status == "ok", i
        np.testing.assert_array_equal(p.result.tokens, ref)
    with pytest.raises(RejectedError):
        srv.submit(DecodeRequest(prompt=prompts[0], max_new_tokens=8,
                                 sample=GREEDY, seed=0))
    edges = [(a, b) for a, b, _, _ in srv.health.history if a is not None]
    assert (Health.SERVING, Health.DRAINING) in edges
    assert (Health.DRAINING, Health.DEAD) in edges


def test_per_slot_deadline_evicts_one_slot_others_stream(mp):
    """A deadline expiring mid-batch evicts THAT slot with its partial
    tokens (bitwise prefix) at the next boundary; the co-resident request
    runs to completion."""
    model, params = mp
    prompts = _prompts(2)
    refs = _solo_refs(mp, prompts, 12, GREEDY)
    now = [0.0]
    eng = SlotEngine(model, params, slots=2, chunk=4, clock=lambda: now[0])
    eng.admit(
        DecodeRequest(prompt=prompts[0], max_new_tokens=12, sample=GREEDY,
                      seed=500),
        tag="slow",
    )
    eng.admit(
        DecodeRequest(prompt=prompts[1], max_new_tokens=12, sample=GREEDY,
                      seed=501),
        tag="tight", deadline_at=2.5,
    )
    done = {}
    while eng.busy:
        done.update(dict(eng.step()))
        now[0] += 1.0
    assert done["tight"].status == "deadline"
    # 2 slots x chunk 4 is one prompt piece a boundary: "tight" waits out
    # the t=0 boundary behind "slow", then emits at t=1 and t=2
    assert done["tight"].new_tokens == 8, "2 chunks before the t=3.0 boundary"
    np.testing.assert_array_equal(done["tight"].tokens, refs[1][:, :8])
    assert done["slow"].status == "ok"
    np.testing.assert_array_equal(done["slow"].tokens, refs[0])


# ---------------------------------------------------------------------------
# request isolation at admission
# ---------------------------------------------------------------------------


def test_mismatched_sample_config_is_isolated_error(mp):
    """A request whose SampleConfig differs from the resident batch's is
    an error RESULT (the scan's sampling params are static per batch);
    the resident request is unaffected."""
    model, params = mp
    prompts = _prompts(2)
    ref = _solo_refs(mp, prompts[:1], 8, GREEDY)[0]
    srv = Server(model, params, ServeConfig(chunk=4, slots=4, max_inflight=4))
    good = srv.submit(DecodeRequest(prompt=prompts[0], max_new_tokens=8,
                                    sample=GREEDY, seed=500))
    bad = srv.submit(DecodeRequest(prompt=prompts[1], max_new_tokens=8,
                                   sample=SAMPLED, seed=501))
    srv.serve(drain_when_idle=True)
    assert isinstance(bad.error, ValueError) and bad.result is None
    assert good.result is not None and good.result.status == "ok"
    np.testing.assert_array_equal(good.result.tokens, ref)
    srv.close()


def test_multirow_prompt_is_isolated_error(mp):
    model, params = mp
    srv = Server(model, params, ServeConfig(chunk=4, slots=2, max_inflight=2))
    bad = srv.submit(DecodeRequest(prompt=jnp.ones((2, 4), jnp.int32),
                                   max_new_tokens=4, sample=GREEDY))
    srv.serve(drain_when_idle=True)
    assert isinstance(bad.error, ValueError)
    srv.close()


# ---------------------------------------------------------------------------
# model-layer slot ops + per-slot probe
# ---------------------------------------------------------------------------


def test_insert_extract_slot_roundtrip(mp):
    model, params = mp
    batched = init_decode_state(CFG, 4)
    prompt = jnp.ones((1, 5), jnp.int32)
    one = prefill_carry(model, params, prompt, GREEDY, jax.random.PRNGKey(0),
                        (8,))
    inserted = insert_decode_slot(batched, one[1], 2)
    back = extract_decode_slot(inserted, 2)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(one[1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the other rows are untouched (still the init zeros)
    for a, z in zip(jax.tree.leaves(extract_decode_slot(inserted, 0)),
                    jax.tree.leaves(extract_decode_slot(batched, 0))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(z))


def test_per_slot_finite_probe_isolates_rows():
    states = init_decode_state(CFG, 4)
    finite = np.asarray(decode_state_finite_per_slot(states))
    assert finite.all()
    poisoned = jax.tree.map(
        lambda x: x.at[2].set(jnp.nan)
        if jnp.issubdtype(x.dtype, jnp.floating) else x,
        states,
    )
    finite = np.asarray(decode_state_finite_per_slot(poisoned))
    np.testing.assert_array_equal(finite, [True, True, False, True])


def test_batched_carry_bytes_scale_linearly_in_slots():
    """Golden-snapshot companion (cheap: jaxpr only, no XLA compile): the
    batched scan's carry is exactly slots x the per-slot O(1) state — no
    paged-KV machinery, no super-linear bookkeeping."""
    from functools import partial

    from orion_tpu.analysis.snapshots import _carry_bytes
    from orion_tpu.generate import SampleConfig as SC
    from orion_tpu.models.configs import get_config

    cfg = get_config("tiny")
    model = TransformerLM(cfg)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(
        model.init, key, jax.ShapeDtypeStruct((1, 8), jnp.int32)
    )

    def carry_bytes(slots):
        states = jax.eval_shape(partial(init_decode_state, cfg, slots))
        vec = lambda dt: jax.ShapeDtypeStruct((slots,), dt)  # noqa: E731
        carry = (vec(jnp.int32), states, vec(jnp.int32), vec(jnp.int32),
                 vec(jnp.bool_))
        jaxpr = jax.make_jaxpr(
            _decode_batched_chunk_jit, static_argnums=(0, 5, 6)
        )(model, params, carry, jax.ShapeDtypeStruct((slots, 2), jnp.uint32),
          vec(jnp.bool_), 8, SC())
        return _carry_bytes(jaxpr)

    one, eight = carry_bytes(1), carry_bytes(8)
    assert eight == 8 * one, (one, eight)


def test_abnormal_loop_exit_completes_resident_pendings(mp, monkeypatch):
    """If the scheduler loop itself dies mid-chunk (device OOM, runtime
    error), Pendings resident in the engine must still complete — as
    'failed' results with their partial tokens — and still-QUEUED
    Pendings must be rejected loudly, not strand callers blocked in
    Pending.wait() forever (the done-exactly-once contract PR 4's
    per-request finally gave)."""
    model, params = mp
    srv = Server(model, params, ServeConfig(chunk=4, slots=1, max_inflight=2))
    prompts = _prompts(2)
    p1 = srv.submit(DecodeRequest(prompt=prompts[0], max_new_tokens=8,
                                  sample=GREEDY, seed=0))
    p2 = srv.submit(DecodeRequest(prompt=prompts[1], max_new_tokens=8,
                                  sample=GREEDY, seed=1))
    calls = {"n": 0}
    real_step = srv.engine.step

    def exploding_step():
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("simulated device failure")
        return real_step()

    monkeypatch.setattr(srv.engine, "step", exploding_step)
    with pytest.raises(RuntimeError, match="simulated device failure"):
        srv.serve(drain_when_idle=True)
    assert p1.done.is_set(), "resident Pending must not hang"
    assert p1.result is not None and p1.result.status == "failed"
    assert p1.result.new_tokens == 4, "the chunk before the crash is kept"
    assert p2.done.is_set(), "queued Pending must not hang either"
    with pytest.raises(RejectedError):
        p2.wait(timeout=0)


def test_server_occupancy_gauges(mp):
    model, params = mp
    srv = Server(model, params, ServeConfig(chunk=4, slots=2, max_inflight=4))
    for i, p in enumerate(_prompts(3)):
        srv.submit(DecodeRequest(prompt=p, max_new_tokens=8, sample=GREEDY,
                                 seed=i))
    srv.serve(drain_when_idle=True)
    assert srv.stats["chunks"] >= 4
    # ISSUE 9 split: occupancy() is INSTANTANEOUS (0.0 on a drained
    # engine); the lifetime packing average moved to occupancy_lifetime()
    assert 0.0 < srv.occupancy_lifetime() <= 1.0
    assert srv.occupancy() == 0.0, "no slot is live after the drain"
    snap = srv.snapshot()
    assert snap["slots"]["slots"] == 2 and snap["slots"]["active"] == 0
    assert snap["stats"]["ok"] == 3
    assert snap["occupancy"] == srv.occupancy_lifetime()
    srv.close()


# ---------------------------------------------------------------------------
# ISSUE 33: the boundary's bookkeeping with several slots served at once
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 3, 4, 6])
def test_boundary_books_every_served_slot_and_only_those(mp, k):
    """K staged admissions before one boundary of an 8-slot, chunk-2
    engine (cap 4): each served slot gets its ``prefill_piece`` event and
    a ``last_boundary`` entry with prompt tokens and this boundary's
    chunk of output; the slots over the cap are the frozen ones, and
    ``passed_over`` counts the boundary they waited."""
    from orion_tpu.generate import prefill_piece_cap

    model, params = mp
    events = []
    eng = SlotEngine(model, params, slots=8, chunk=2,
                     prefill_buckets=(8, 16), prefill_chunk=8,
                     on_event=lambda kind, f: events.append((kind, f)))
    cap = prefill_piece_cap(8, 2)
    prompts = _prompts(k)  # lengths 3..7: one piece each, shortest first
    for i, p in enumerate(prompts):
        eng.admit(DecodeRequest(prompt=p, max_new_tokens=6, sample=GREEDY,
                                seed=i), tag=i)
    assert eng.prefilling_count == k
    assert eng.step() == []
    order = sorted(range(k), key=lambda i: (prompts[i].shape[1], i))
    served, waiting = order[:cap], order[cap:]
    pieces = [f["slot"] for kind, f in events if kind == "prefill_piece"]
    assert sorted(pieces) == sorted(served)
    by_slot = {e["slot"]: e for e in eng.last_boundary}
    assert sorted(by_slot) == list(range(k))
    for i in served:
        assert by_slot[i]["prefill_tokens"] == prompts[i].shape[1]
        assert by_slot[i]["decode_tokens"] == 2 and "frozen" not in by_slot[i]
        assert eng._slots[i].passed_over == 0
        assert eng._slots[i].n_emitted == 2
    for i in waiting:
        assert by_slot[i]["frozen"] and by_slot[i]["prefill_tokens"] == 0
        assert eng._slots[i].passed_over == 1
        assert eng._slots[i].n_emitted == 0
    assert eng.prefilling_count == len(waiting)
    done = {}
    while eng.busy:
        done.update(dict(eng.step()))
    for i, p in enumerate(prompts):
        ref = np.asarray(generate(model, params, p, 6, GREEDY,
                                  rng=jax.random.PRNGKey(i)))
        np.testing.assert_array_equal(done[i].tokens, ref)
