"""The served delta-rule hybrid (ISSUE 35): gated delta-rule layers (dk != dv,
a head count that is no power of two, beta in (0, 2)) x3 : full attention
(q/k norm over the projection, no rotary) x1, the sublayer's output
normalised, against ``benchmark/reference/plain_olmo_hybrid.py``; tiny, CPU,
fp32. The contract every served configuration takes is
``tests/served_contract.py``'s."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from served_contract import ByBackend, Cell, ServedCase, ServedContract, served_fixture, tiny_cfg

from orion_tpu.models.transformer import init_decode_state
from orion_tpu.ops import dispatch
from orion_tpu.ops.gated_delta import gated_delta_recurrent, gated_delta_step
from orion_tpu.ops.pallas.cache_attention import kv_block

H, DK, DV = 3, 8, 24  # the rehearse block's heads and widths
# the chunked delta rule's triangular solve against the token-by-token
# reference, fp32: errors of a few 1e-3 on logits of ~4 at beta up to 2
# (the recurrence itself, backend "eager", agrees to 1e-4)
LOGIT_TOL = 1e-2
# the recurrence in pieces and steps against the same recurrence in one pass
# reads ~2e-4; the state rounded to bf16 between calls ~8e-3
WALK_TOL = 2e-3
CASE = ServedCase(
    "olmo_hybrid_7b", seq=300, logit_tol=LOGIT_TOL,
    # cfg.chunk: what the engine aligns piece boundaries to; the delta rule
    # chunks by 64 in XLA and by 128 in its kernels
    over=dict(max_seq_len=384, chunk=64), by_backend={"pallas_interpret": {"chunk": 128}},
    moved=(),
    forward=("xla", "eager", "pallas_interpret"), forward_tol=ByBackend(eager=1e-4), floor=1.0,
    row_list=128,
    engines=(("xla", False), ("xla", True), ("pallas_interpret", True), ("pallas_interpret", False)),
    engine=dict(slots=4, chunk=4, prefill_buckets=(128, 256, 384), prefill_chunk=128),
    prompts=((0, 0, 100), (1, 0, 250), (0, 30, 300)),  # of one, two and three pieces
    served_gap=2 * LOGIT_TOL,
    cell=Cell("olmo_hybrid_7b.serve_batch", seed=2**31 + 35, seconds=2, trace=0),
    # read on the parent of PR 59 (44d93ca) at this case's sizes; until then
    # tests/test_granite_hybrid.py pinned them at sizes of its own, where PR 45
    # changed the prefill, the piece and the step: the delta-rule layer's gate
    # is an op that takes ``o`` head-major, so the ``swapaxes`` that ``_rule``
    # did comes after the conv tail's equations and a decode step's one row
    # passes it as ``[B, Hv, 1, dv]`` (tests/test_gated_norm_kernel.py holds
    # the arithmetic bit for bit)
    pins={"forward": "1f9dd0ef019ebb90", "prefill": "49d07adf2d9ce7c5",
          "piece": "2002ef3b93be2f31", "step": "6ad28ec802d2a4a9"},
)
served = served_fixture(CASE)


@functools.lru_cache(maxsize=None)
def served_alone(served):
    """What each request gets served ALONE through the XLA engine (once a file)."""
    return [served.serve("xla", False, prompts=[p]).ids[0] for p in served.prompts]


class TestServed(ServedContract):
    case = CASE

    def published(self, cfg):
        assert cfg.resolved_layer_types == ("gated_delta",) * 3 + ("softmax",) + ("gated_delta",) * 3 + ("softmax",)
        assert (cfg.d_model, cfg.n_heads, cfg.resolved_head_dim, cfg.resolved_mlp_hidden) == (3840, 30, 128, 11008)
        assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim) == (30, 30, 96, 192)
        assert cfg.gdn_allow_neg_eigval and cfg.qk_norm == "projection" and not cfg.rotary
        assert cfg.norm_placement == "post" and cfg.pos_embed == "none" and not cfg.tie_embeddings
        state = jax.eval_shape(lambda: init_decode_state(cfg, 2))
        assert state[0]["s"].shape == (2, 30, 96, 192) and state[0]["s"].dtype == jnp.float32
        assert state[0]["conv"].shape == (2, 3 * 11520)
        assert state[3]["k"].shape == (2, 30, 4096, 128)

    @pytest.mark.parametrize("state_dtype,ok", [(None, True), (jnp.bfloat16, False)])
    def test_prefill_equals_pieces_equals_the_decode_walk(self, served, state_dtype, ok):
        """(b) at the model: prefill in pieces (the last one padded), then
        decode, logits against the reference's full forward at every position
        produced; (e) with the delta-rule state rounded to bf16 between calls
        the same walk is out of tolerance."""
        lower = (lambda st: st) if state_dtype is None else (lambda st: [
            {k: (v.astype(state_dtype).astype(v.dtype) if k == "s" else v) for k, v in layer.items()}
            for layer in st])
        params, toks, b = served.params, served.toks, served.toks.shape[0]
        for backend, tol in (("eager", WALK_TOL), ("xla", LOGIT_TOL)):
            if state_dtype is not None and backend == "xla":
                continue  # the chunked solve's own error is of the bf16 state's size
            prog = served.programs(backend)
            states, worst, t = init_decode_state(prog.cfg, b), 0.0, 0
            with jax.default_matmul_precision("highest"):
                for n in (128, 128, 20):  # right-padded pieces of these real lengths
                    piece = jnp.pad(toks[:, t:t + n], ((0, 0), (0, 128 - n)))
                    lg, states = prog.piece(params, piece, states, t, n)
                    states, t = lower(states), t + n
                    worst = max(worst, float(jnp.abs(lg - served.want[:, t - 1]).max()))
                for _ in range(24):  # teacher-forced decode steps
                    lg, states = prog.step(params, toks[:, t], states, jnp.full((b,), t))
                    states = lower(states)
                    worst = max(worst, float(jnp.abs(lg - served.want[:, t]).max()))
                    t += 1
            assert (worst < tol) == ok, (backend, worst)

    def before_boundary(self, engine):
        return engine.kv_rows()  # (live, reserved, read)

    def after_engine(self, served, run, backend, donate):
        """Under the kernels (the donated scan's attention over the held
        cache, and the carried cache's per-sequence step with a row list)
        decode attention streams the emitting slots' live KV blocks where
        XLA's form streams every slot's reservation."""
        cfg, engine = run.cfg, run.engine
        reserved = 4 * cfg.max_seq_len
        assert engine.kv_rows()[1] == reserved
        reads = [read for _, _, read in run.seen]
        if backend == "xla":
            assert set(reads) == {reserved} and engine.kv_rows()[2] == reserved
            for ids, alone in zip(run.ids, served_alone(served)):  # exactly, as from generate()
                np.testing.assert_array_equal(ids, alone)
        else:
            block = kv_block(cfg.max_seq_len)
            assert block < cfg.max_seq_len and all(r % block == 0 for r in reads)
            # the last boundary: the longest request alone, ~340 of 384 rows live
            assert reads[-1] == 3 * block and max(reads) < reserved
            assert engine.kv_rows()[2] == 0  # nothing resident, nothing read

    def after_cell(self, result, lines):
        assert result["metrics"]["serve_tok_s"]["value"] > 0


def test_pieces_on_chunk_boundaries_equal_the_monolithic_prefill(served):
    """The extend contract on the XLA backend: pieces whose boundaries are
    multiples of the delta rule's chunk leave the state the monolithic
    prefill leaves, and the same last-row logits."""
    prog, params, toks = served.programs(), served.params, served.toks
    lg0, st0 = prog.prefill(params, toks[:, :256])
    states = init_decode_state(prog.cfg, toks.shape[0])
    for t in (0, 128):
        lg, states = prog.piece(params, toks[:, t:t + 128], states, t, 128)
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


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_the_cache_is_split_by_the_program_the_linear_state_by_the_backend(backend):
    """``chunk_split``: a full-attention cache is held only where the
    program's carry is donated (this model's engine on the chip), whatever
    the backend, and a window's ring never; a ``linear`` layer's ``(S, z)``
    is held where the step takes the row-list kernel, in both kinds of
    program; the delta rule's ``S`` is written at every step: never split."""
    from orion_tpu.models.mixers import MIXERS

    cfg = tiny_cfg(CASE, backend, n_layers=4, window=8,
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
