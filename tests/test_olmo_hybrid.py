"""The served delta-rule hybrid (ISSUE 35): gated delta-rule layers (dk != dv,
a head count that is no power of two, beta in (0, 2)) x3 : full attention
(q/k norm over the projection, no rotary) x1, the sublayer's output
normalised, against ``benchmark/reference/plain_olmo_hybrid.py``; tiny, CPU,
fp32."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.generate import SampleConfig
from orion_tpu.models.configs import get_config
from orion_tpu.models.transformer import TransformerLM, init_decode_state
from orion_tpu.ops import dispatch
from orion_tpu.ops.gated_delta import gated_delta_recurrent, gated_delta_step
from orion_tpu.serving import DecodeRequest, SlotEngine

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"))
from reference import plain_olmo_hybrid as ref  # noqa: E402

H, DK, DV = 3, 8, 24
TINY = dict(vocab_size=256, d_model=96, n_heads=H, head_dim=16, gdn_key_heads=H,
            gdn_value_heads=H, gdn_key_dim=DK, gdn_value_dim=DV, mlp_hidden=128,
            max_seq_len=384, dtype="float32", param_dtype="float32")
SPEC = dict(n_heads=H, head_dim=16, key_dim=DK, value_dim=DV, beta_scale=2.0)
# the chunked delta rule's triangular solve against the token-by-token
# reference, fp32: errors of a few 1e-3 on logits of ~4 at beta up to 2
# (the recurrence itself, backend "eager", agrees to 1e-4)
LOGIT_TOL = 1e-2
# the recurrence in pieces and steps against the same recurrence in one pass
# reads ~2e-4; the state rounded to bf16 between calls ~8e-3
WALK_TOL = 2e-3
GREEDY = SampleConfig(temperature=0.0)


def tiny_cfg(backend="xla", **over):
    # cfg.chunk: what the engine aligns piece boundaries to; the delta rule
    # chunks by 64 in XLA and by 128 in its kernels
    chunk = 128 if backend.startswith("pallas") else 64
    return dataclasses.replace(
        get_config("olmo_hybrid_7b"), backend=backend, chunk=chunk, **{**TINY, **over})


@pytest.fixture(scope="module")
def model_params():
    cfg = tiny_cfg()
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 300), 0, cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.key(0), toks)
    with jax.default_matmul_precision("highest"):
        want = ref.forward({**SPEC, "layer_types": cfg.resolved_layer_types}, params, toks)
    return cfg, params, toks, want


def test_preset_is_the_published_shape():
    cfg = get_config("olmo_hybrid_7b")
    assert cfg.resolved_layer_types == ("gated_delta",) * 3 + ("softmax",) + ("gated_delta",) * 3 + ("softmax",)
    assert (cfg.d_model, cfg.n_heads, cfg.resolved_head_dim, cfg.resolved_mlp_hidden) == (3840, 30, 128, 11008)
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim) == (30, 30, 96, 192)
    assert cfg.gdn_allow_neg_eigval and cfg.qk_norm == "projection" and not cfg.rotary
    assert cfg.norm_placement == "post" and cfg.pos_embed == "none" and not cfg.tie_embeddings
    state = jax.eval_shape(lambda: init_decode_state(cfg, 2))
    assert state[0]["s"].shape == (2, 30, 96, 192) and state[0]["s"].dtype == jnp.float32
    assert state[0]["conv"].shape == (2, 3 * 11520)
    assert state[3]["k"].shape == (2, 30, 4096, 128)


@pytest.mark.parametrize("backend", ["xla", "eager", "pallas_interpret"])
def test_forward_matches_the_plain_reference(model_params, backend):
    """(a) logits of the parallel forward, seeded weights."""
    cfg, params, toks, want = model_params
    model = TransformerLM(dataclasses.replace(cfg, backend=backend))
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, toks)
    assert float(jnp.abs(want).max()) > 1.0
    assert float(jnp.abs(got - want).max()) < (1e-4 if backend == "eager" else LOGIT_TOL)


def walk(model, params, toks, pieces, n_decode, state_dtype=None):
    """Prefill ``toks`` in right-padded pieces of the given real lengths,
    then ``n_decode`` teacher-forced decode steps: logits at the end of
    every piece and at every decoded position, [B, n, V]."""
    b = toks.shape[0]
    states = init_decode_state(model.cfg, b)
    lower = (lambda st: st) if state_dtype is None else (lambda st: [
        {k: (v.astype(state_dtype).astype(v.dtype) if k == "s" else v) for k, v in layer.items()}
        for layer in st])
    out, t = [], 0
    width = max(pieces)
    for n in pieces:
        piece = jnp.pad(toks[:, t:t + n], ((0, 0), (0, width - n)))
        lg, states = model.apply(params, piece, states, t, n, method="prefill_extend_step")
        states = lower(states)
        t += n
        out.append((t - 1, lg))
    for _ in range(n_decode):
        lg, states = model.apply(params, toks[:, t], states, jnp.full((b,), t), method="decode_step")
        states = lower(states)
        out.append((t, lg))
        t += 1
    return out


@pytest.mark.parametrize("state_dtype,ok", [(None, True), (jnp.bfloat16, False)])
def test_pieces_then_decode_match_the_reference(model_params, state_dtype, ok):
    """(b) at the model: prefill in pieces (the last one padded), then decode,
    logits against the reference's full forward at every position produced;
    (e) with the delta-rule state rounded to bf16 between calls the same
    walk is out of tolerance."""
    cfg, params, toks, want = model_params
    for backend, tol in (("eager", WALK_TOL), ("xla", LOGIT_TOL)):
        if state_dtype is not None and backend == "xla":
            continue  # the chunked solve's own error is of the bf16 state's size
        model = TransformerLM(dataclasses.replace(cfg, backend=backend))
        with jax.default_matmul_precision("highest"):
            got = walk(model, params, toks, [128, 128, 20], 24, state_dtype)
        worst = max(float(jnp.abs(lg - want[:, pos]).max()) for pos, lg in got)
        assert (worst < tol) == ok, (backend, worst)


def test_pieces_on_chunk_boundaries_equal_the_monolithic_prefill(model_params):
    """The extend contract on the XLA backend: pieces whose boundaries are
    multiples of the delta rule's chunk leave the state the monolithic
    prefill leaves, and the same last-row logits."""
    cfg, params, toks, _ = model_params
    model = TransformerLM(cfg)
    lg0, st0 = model.apply(params, toks[:, :256], method="prefill")
    states = init_decode_state(cfg, toks.shape[0])
    for t in (0, 128):
        lg, states = model.apply(params, toks[:, t:t + 128], states, t, 128, method="prefill_extend_step")
    np.testing.assert_allclose(lg, lg0[:, -1], atol=1e-5)
    for a, b in zip(jax.tree.leaves(states), jax.tree.leaves(st0)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def rule_inputs(b, h, t, dk, dv, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (b, h, t, dk)) * dk ** -0.5
    k = jax.random.normal(ks[1], (b, h, t, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, h, t, dv))
    beta = 1.9 * jax.nn.sigmoid(jax.random.normal(ks[3], (b, h, t)) + 2.0)
    g = -0.1 * jax.nn.softplus(jax.random.normal(ks[4], (b, h, t)))
    return q, k, v, beta, g, jax.random.normal(ks[5], (b, h, dk, dv))


@pytest.mark.parametrize("backend,dk,dv,t", [
    ("xla", DK, DV, 140), ("xla", 96, 192, 200),
    ("pallas_interpret", DK, DV, 140), ("pallas_interpret", 96, 192, 300),
])
def test_chunked_forms_carry_a_state(backend, dk, dv, t):
    """(d) the chunked forms from an initial state against the recurrence,
    beta up to 1.9, widths 96 / 192 through the kernel's zero padding."""
    q, k, v, beta, g, s0 = rule_inputs(1, 3, t, dk, dv)
    assert float(beta.max()) > 1.5
    want, s_want = gated_delta_recurrent(q, k, v, beta, g, initial_state=s0, return_state=True)
    got, s_got = dispatch.gated_delta_rule(
        q, k, v, beta, g, backend=backend, initial_state=s0, return_state=True)
    assert s_got.shape == s0.shape and s_got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=1e-3)
    np.testing.assert_allclose(s_got, s_want, atol=1e-3)


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_step_equals_the_recurrence_and_leaves_unlisted_rows(backend):
    """(c) ``gated_delta_step`` token by token against the recurrence; with a
    row list the unlisted rows' state keeps its bits."""
    b, t = 5, 6
    q, k, v, beta, g, s0 = rule_inputs(b, H, t, DK, DV, seed=3)
    mask = jnp.array([True, False, True, True, False])
    rows = dispatch.decode_live_rows(mask, backend=backend)
    assert (rows is None) == (backend == "xla")
    want, s_want = gated_delta_recurrent(q, k, v, beta, g, initial_state=s0, return_state=True)
    s = s0
    for i in range(t):
        o, s = dispatch.gated_delta_step(
            q[:, :, i], k[:, :, i], v[:, :, i], beta[:, :, i], g[:, :, i], s, rows, backend=backend)
        o_all, _ = gated_delta_step(q[:, :, i], k[:, :, i], v[:, :, i], beta[:, :, i], g[:, :, i], s0)
        live = mask if rows is not None else jnp.ones((b,), bool)
        np.testing.assert_allclose(o[live], want[:, :, i][live], atol=1e-4)
        assert o_all.shape == o.shape
    live = mask if rows is not None else jnp.ones((b,), bool)
    np.testing.assert_allclose(s[live], s_want[live], atol=1e-4)
    if rows is not None:
        assert bool((s[~mask] == s0[~mask]).all())
        assert bool((dispatch.decode_rows_mask(rows, b) == mask).all())


def test_decode_step_with_a_row_list_touches_no_other_row(model_params):
    """(c) at the model: S, the conv tail, K and V of unlisted rows bitwise
    untouched by a slot-multiplexed decode step."""
    cfg, params, toks, _ = model_params
    model = TransformerLM(dataclasses.replace(cfg, backend="pallas_interpret"))
    _, states = model.apply(params, toks[:, :128], method="prefill")
    states = jax.tree.map(lambda x: jnp.concatenate([x, x[:1] + 1], axis=0), states)  # 3 rows
    mask = jnp.array([True, False, True])
    rows = dispatch.decode_live_rows(mask, backend="pallas_interpret")
    tok = jnp.array([5, 6, 7])
    _, new = model.apply(params, tok, states, jnp.array([128, 128, 128]), rows, method="decode_step")
    for old, now in zip(jax.tree.leaves(states), jax.tree.leaves(new)):
        assert bool((now[1] == old[1]).all())
        assert not bool((now[0] == old[0]).all())


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_the_cache_is_split_by_the_program_the_linear_state_by_the_backend(backend):
    """``chunk_split``: a full-attention cache is held only where the
    program's carry is donated (this model's engine on the chip), whatever
    the backend, and a window's ring never; a ``linear`` layer's ``(S, z)``
    is held where the step takes the row-list kernel, in both kinds of
    program; the delta rule's ``S`` is written at every step: never split."""
    from orion_tpu.models.mixers import MIXERS

    cfg = tiny_cfg(backend, n_layers=4, window=8,
                   layer_types=("gated_delta", "softmax", "swa", "linear"))
    states, t = init_decode_state(cfg, 2), jnp.zeros((2,), jnp.int32)
    for donated in (False, True):
        (gh, gc), (sh, sc), (wh, wc), (lh, lc) = (
            MIXERS[lt].chunk_split(cfg, lt, st, 4, t, donated)
            for lt, st in zip(cfg.resolved_layer_types, states)
        )
        assert gh == {} and gc is states[0] and wh == {} and wc is states[2]
        if donated:
            assert set(sh) == {"k", "v"} and set(sc) == {"kn", "vn", "t0"}
        else:
            assert sh == {} and sc is states[1]
        if backend == "xla":
            assert lh == {} and lc is states[3]
        else:
            assert set(lh) == {"s", "z"} and set(lc) == {"kc", "vc", "t0"}


def serve(cfg, params, prompts, max_new, slots=4, chunk=4, prefill_chunk=128, donate=False):
    engine = SlotEngine(TransformerLM(cfg), params, slots=slots, chunk=chunk,
                        prefill_buckets=(128, 256, 384), prefill_chunk=prefill_chunk)
    engine.donate_carry = donate
    for i, p in enumerate(prompts):
        engine.admit(DecodeRequest(prompt=p, max_new_tokens=max_new, sample=GREEDY, seed=i), tag=i)
    done = {}
    engine.kv_seen = []  # (live, reserved, read) before each boundary
    while engine.busy:
        engine.kv_seen.append(engine.kv_rows())
        for tag, res in engine.step():
            assert res.status == "ok", res.status
            done[tag] = np.asarray(res.tokens).reshape(-1)
    return [done[i] for i in range(len(prompts))], engine


@pytest.mark.parametrize("backend,donate", [
    ("xla", False), ("xla", True), ("pallas_interpret", True), ("pallas_interpret", False)])
def test_engine_serves_three_requests_as_alone(model_params, backend, donate):
    """(b) through ``SlotEngine``: three requests of different lengths
    resident (prompts of one, two and three pieces), 2 x chunk + 1 decoded
    tokens each; every request's ids are what it gets served alone (XLA:
    exactly), and teacher-forced through the reference's full forward each
    served id is the reference's choice or within tolerance of it; with the
    carry donated the boundary programs give the same ids. Under the
    kernels (the donated scan's attention over the held cache, and the
    carried cache's per-sequence step with a row list) the ids are the XLA
    engine's, and decode attention streams the emitting slots' live KV
    blocks where XLA's form streams every slot's reservation."""
    from orion_tpu.ops.pallas.cache_attention import kv_block

    cfg, params, toks, _ = model_params
    cfg = dataclasses.replace(cfg, backend=backend, chunk=tiny_cfg(backend).chunk)
    prompts = [np.asarray(toks[0, :100]), np.asarray(toks[1, :250]), np.asarray(toks[0, 30:330])]
    n_new = 9
    together, engine = serve(cfg, params, prompts, n_new, donate=donate)
    reserved = 4 * cfg.max_seq_len
    assert engine.kv_rows()[1] == reserved
    reads = [read for _, _, read in engine.kv_seen]
    if backend == "xla":
        assert set(reads) == {reserved} and engine.kv_rows()[2] == reserved
    else:
        block = kv_block(cfg.max_seq_len)
        assert block < cfg.max_seq_len and all(r % block == 0 for r in reads)
        # the last boundary: the longest request alone, ~340 of 384 rows live
        assert reads[-1] == 3 * block and max(reads) < reserved
        assert engine.kv_rows()[2] == 0  # nothing resident, nothing read
        xla_ids, _ = serve(dataclasses.replace(cfg, backend="xla"), params, prompts, n_new)
        for ids, want in zip(together, xla_ids):
            np.testing.assert_array_equal(ids, want)
    spec = {**SPEC, "layer_types": cfg.resolved_layer_types}
    for p, ids in zip(prompts, together):
        if backend == "xla":
            alone, _ = serve(cfg, params, [p], n_new)
            np.testing.assert_array_equal(ids, alone[0])
        full = jnp.concatenate([jnp.asarray(p), jnp.asarray(ids)])[None]
        with jax.default_matmul_precision("highest"):
            logits = ref.forward(spec, params, full)[0, len(p) - 1:-1]
        gap = logits.max(-1) - jnp.take_along_axis(logits, jnp.asarray(ids)[:, None], axis=-1)[:, 0]
        assert float(gap.max()) < 2 * LOGIT_TOL, gap


def test_cell_rehearses_on_the_cpu(tmp_path):
    """``olmo_hybrid_7b.serve_batch`` end to end at tiny sizes: the served
    kind, the reference named by the configuration's file, the check on what
    was served in the window, the new counters."""
    import json
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "olmo_hybrid_7b.serve_batch",
         "--seed", str(2**31 + 35), "--seconds", "2", "--trace", "0", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    assert line["metrics"]["serve_tok_s"]["value"] > 0
