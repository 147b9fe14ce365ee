"""The served gated short-convolution mixture of experts (ISSUE 55): conv
layers whose whole decode state is the last two rows of ``b * u``, one
full-attention layer in four (per-head q / k norms, rotary), a dense layer
then a sigmoid top-k mixture with a per-expert selection bias whose weights
are divided by the chosen's sum ``+ 1e-6``, every expert held, a tied head;
against ``benchmark/reference/plain_lfm2_moe.py``; tiny, CPU, fp32. The
contract every served configuration takes is ``tests/served_contract.py``'s.

No depth-share test is needed (``share=None``): every expert and the whole
vocabulary are held, so there is no share whose parts would have to add up."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from served_contract import (
    ROOT, Because, ServedCase, ServedContract, Walk, other_presets, served_fixture, tiny_cfg,
)

from orion_tpu.models.configs import get_config
from orion_tpu.models.mixers import MIXERS
from orion_tpu.models.moe import MoEMLP, masks_rows
from orion_tpu.models.transformer import TransformerLM, init_decode_state

# the published pattern's head: conv, conv (both dense), then one whole period
# (full_attention, conv, conv, conv) of expert layers
KINDS = ("gated_conv", "gated_conv", "softmax", "gated_conv", "gated_conv", "gated_conv")
T = 29
P = 8  # rows of a prompt piece
CASE = ServedCase(
    "lfm2_8b_a1b", seq=T,
    logit_tol=5e-5,  # fp32 against fp32 on logits of ~4: summation order only
    over=dict(
        n_layers=6, layer_types=KINDS, moe_first_dense=2, max_seq_len=64,
        d_model=Because(64, "as every other file's: the tails' byte counts below are asserted at it"),
        moe_route_bias=Because(0.5, "large enough that the bias moves a choice"),
        embed_init_std=Because(None, "flax's own: logits of ~4, which the tolerance was read at")),
    constants=dict(query_tile=16),
    # a whole-prompt ``prefill`` padded to a bucket, its state taken at the real
    # length, then steps; pieces of 8 and 5 of 8
    walk=Walk(n=13, piece=P, steps=4, steps_from="padded", padded=3, against="reference",
              backends=("xla",)),
    # the chip's programs: the row lists, the conv kernel in the pieces, a
    # piece a program, the carry held once
    engines=(("pallas_interpret", True),),
    engine=dict(slots=4, chunk=4, prefill_buckets=(8, 16, 32), prefill_chunk=8),
    prompts=((0, 0, 5), (1, 0, 8), (0, 3, 29)),
    served_gap=5e-5,  # the reference's own choice to a logit gap of rounding
)
served = served_fixture(CASE)


class TestServed(ServedContract):
    case = CASE

    def published(self, cfg):
        assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (2048, 32, 8, 64)
        assert cfg.resolved_layer_types == ("gated_conv",) + ("softmax", "gated_conv", "gated_conv", "gated_conv") * 3
        assert cfg.qk_norm == "head" and cfg.rotary_base == 1e6
        assert cfg.rotary and cfg.rotary_layers is None and not cfg.attn_gate and cfg.attn_scale is None
        assert (cfg.n_experts, cfg.moe_top_k, cfg.moe_hidden, cfg.mlp_hidden) == (32, 4, 1792, 7168)
        assert (cfg.moe_score, cfg.moe_route_scale, cfg.moe_first_dense) == ("sigmoid", 1.0, 1)
        assert cfg.moe_route_bias > 0 and cfg.moe_gate_eps == 1e-6 and not cfg.moe_shared_hidden
        assert not cfg.moe_held and masks_rows(cfg) and cfg.resolved_router_width == 32
        assert [cfg.moe_at(i) for i in range(13)] == [False] + [True] * 12
        assert (cfg.vocab_size, cfg.tie_embeddings, cfg.max_seq_len) == (65536, True, 2048 + 512)
        assert cfg.norm_eps == 1e-5 and cfg.norm_placement == "pre" and cfg.pos_embed == "none"
        shapes = jax.eval_shape(lambda: init_decode_state(cfg, 2))
        assert [sorted(s) for s in shapes] == [["conv"]] + [["k", "v"], ["conv"], ["conv"], ["conv"]] * 3
        assert shapes[0]["conv"].shape == (2, 2 * 2048)  # two rows a slot: 8 KB in bf16
        assert shapes[1]["k"].shape == (2, 8, 2560, 64)
        tree = jax.eval_shape(
            lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
        count = lambda t: sum(x.size for x in jax.tree.leaves(t))  # noqa: E731
        assert count(tree["params"]["block_0"]["attn"]) == 16_783_360
        assert count(tree["params"]["block_1"]["attn"]) == 10_485_888
        assert count(tree["params"]["block_0"]) == 60_827_648
        assert count(tree["params"]["block_2"]["mlp"]) == 352_387_104
        assert count(tree) == 4_606_249_728
        assert "lm_head_kernel" not in tree["params"]
        blk = tree["params"]["block_2"]
        assert blk["attn"]["in_proj"]["kernel"].shape == (2048, 6144) and blk["attn"]["conv"].shape == (3, 2048)
        assert blk["mlp"]["router_bias"].shape == (32,) and blk["mlp"]["router_bias"].dtype == jnp.float32
        # the new layer type is no part of any other preset's programs; the
        # gates' epsilon is, of the one later mixture that publishes one
        others = other_presets(cfg.name)
        assert all("gated_conv" not in c.resolved_layer_types for c in others)
        assert {c.name for c in others if c.moe_gate_eps} == {"nemotron_3_super_120b"}

    def after_engine(self, served, run, backend, donate):
        """The engine's memory account names the tails."""
        cfg, held = run.cfg, run.engine.held_bytes
        assert held["tail_bytes"] == 4 * 5 * 2 * cfg.d_model * 4  # 5 conv layers, two fp32 rows a slot
        assert held["kv_bytes"] == 4 * 2 * 2 * cfg.max_seq_len * 16 * 4 and held["ring_bytes"] == 0
        assert held["state_bytes"] == held["tail_bytes"]  # nothing else: no state matrix
        assert run.engine.kv_rows()[1] == 4 * cfg.max_seq_len


@pytest.mark.parametrize("patch", [
    "no b gate", "no c gate", "a SiLU on the conv", "the taps reversed", "the split order [b | u | c]",
    "the bias in the weights", "no bias", "no normalisation over the chosen", "no rotary",
    "no q norm", "no k norm", "an untied head"])
def test_the_comparison_sees(served, monkeypatch, patch):
    """The tolerance is tight enough to tell the model from a reference that
    differs in one of the mechanisms."""
    ref, cfg, params = served.ref, served.cfg, served.params
    if patch == "no b gate":
        monkeypatch.setattr(ref, "split_in", lambda proj: (
            jnp.ones_like(proj[..., :cfg.d_model]), *jnp.split(proj, 3, axis=-1)[1:]))
    elif patch == "no c gate":
        def no_c(proj):
            b, c, u = jnp.split(proj, 3, axis=-1)
            return b, jnp.ones_like(c), u
        monkeypatch.setattr(ref, "split_in", no_c)
    elif patch == "a SiLU on the conv":
        monkeypatch.setattr(ref, "conv_activation", jax.nn.silu)
    elif patch == "the taps reversed":
        plain = ref.short_conv
        monkeypatch.setattr(ref, "short_conv", lambda v, w: plain(v, w[::-1]))
    elif patch == "the split order [b | u | c]":  # b * u commutes: c's place is what shows
        monkeypatch.setattr(ref, "split_in", lambda proj: (
            lambda b, c, u: (b, u, c))(*jnp.split(proj, 3, axis=-1)))
    elif patch == "the bias in the weights":
        def biased(spec, p, x):
            scores = jax.nn.sigmoid(x @ jnp.asarray(p["router"]["kernel"], jnp.float32)) + p["router_bias"]
            top, ids = jax.lax.top_k(scores, spec["top_k"])
            top = spec["route_scale"] * top / (top.sum(-1, keepdims=True) + spec["gate_eps"])
            return jnp.einsum("nk,nke->ne", top, jax.nn.one_hot(ids, scores.shape[-1]))
        monkeypatch.setattr(ref, "routing_weights", biased)
    elif patch == "no bias":
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.zeros_like(x) if "router_bias" in str(path) else x, params)
    elif patch == "no normalisation over the chosen":
        def unnormed(spec, p, x):
            scores = jax.nn.sigmoid(x @ jnp.asarray(p["router"]["kernel"], jnp.float32))
            _, ids = jax.lax.top_k(scores + p["router_bias"], spec["top_k"])
            top = spec["route_scale"] * jnp.take_along_axis(scores, ids, axis=-1)
            return jnp.einsum("nk,nke->ne", top, jax.nn.one_hot(ids, scores.shape[-1]))
        monkeypatch.setattr(ref, "routing_weights", unnormed)
    elif patch == "no rotary":
        monkeypatch.setattr(ref, "rope", lambda x, base: x)
    elif patch in ("no q norm", "no k norm"):
        plain, heads = ref.rms, cfg.n_heads if patch == "no q norm" else cfg.n_kv_heads
        monkeypatch.setattr(ref, "rms", lambda spec, x, w: x if x.ndim == 4 and x.shape[1] == heads
                            else plain(spec, x, w))
    elif patch == "an untied head":  # a head that is not the embedding's own table
        plain = ref.logits
        monkeypatch.setattr(ref, "logits", lambda spec, p, x, columns=None: plain(
            spec, {"params": {**p["params"], "embed": {
                "embedding": jnp.roll(p["params"]["embed"]["embedding"], 1, axis=0)}}}, x, columns))
    served.differs(params=params)


# -- the serving path: prefill, pieces and steps against ONE full forward -----------


def _pieces(model, params, piece, toks, n, fault=None):
    """Consume ``toks[:n]`` in pieces of ``P`` rows (the last right-padded)
    from an empty state -> (each piece's last real row's logits, the state).
    ``fault``: what a wrong serving path would do at a piece boundary."""
    states = init_decode_state(model.cfg, 1, jnp.float32)
    out = []
    for at in range(0, n, P):
        real = min(P, n - at)
        rows = jnp.zeros((1, P), jnp.int32).at[0, :real].set(toks[at:at + real])
        if fault == "the tail dropped at a piece boundary":
            states = [{k: jnp.zeros_like(v) if k == "conv" else v for k, v in st.items()}
                      for st in states]
        length = P if fault == "the tail taken at the padded length" else real
        logits, states = piece(params, rows, states, jnp.int32(at), jnp.int32(length))
        out.append((at + real - 1, logits[0]))
    return out, states


def _two_slots(served, fault=None):
    """Slot 0 takes 19 tokens of sequence 0 (pieces of 8, 8 and 3 padded to 8),
    slot 1 takes 17 of sequence 1 (8, 8 and ONE row: shorter than the conv's
    two-row tail, so the new tail is a row of the old and the row); then both
    decode together at their own positions, teacher-forced, slot 1 sitting
    out the third step (its state selected back, as the decode programs do
    for a row that is not emitting). Yields (sequence, position, logits)."""
    prog, params, toks = served.programs(), served.params, served.toks
    model, piece, step = prog.model, prog.piece, prog.step
    starts, read, rows = (19, 17), [], []
    for b, n in enumerate(starts):
        got, states = _pieces(model, params, piece, toks[b], n, fault)
        read += [(b, pos, logits) for pos, logits in got]
        rows.append(states)
    states = jax.tree.map(lambda *leaves: jnp.concatenate(leaves), *rows)
    t = np.array(starts)
    for i in range(8):
        emitting = np.array([True, i != 2])
        tok = jnp.asarray([toks[b, t[b]] if emitting[b] else 7 for b in range(2)])
        logits, new = step(params, tok, states, jnp.asarray(t, jnp.int32))
        if fault != "an unlisted row's tail moved by a step":
            mask = jnp.asarray(emitting)
            new = jax.tree.map(
                lambda n, o: jnp.where(mask.reshape((-1,) + (1,) * (n.ndim - 1)), n, o), new, states)
        states = new
        read += [(b, int(t[b]), logits[b]) for b in range(2) if emitting[b]]
        t = t + emitting
    return read


def test_pieces_then_steps_match_one_full_forward(served):
    """``prefill_extend`` in pieces (one padded, every boundary inside the
    three-tap window, one piece shorter than the tail) then ``decode_step``s
    of two slots at different positions: every logit row read on the way is
    the reference's full forward's at that position."""
    read = _two_slots(served)
    assert len(read) == 6 + 8 + 7 and {b for b, *_ in read} == {0, 1}
    for b, pos, logits in read:
        np.testing.assert_allclose(logits, served.want[b, pos], atol=CASE.logit_tol, err_msg=f"{b} {pos}")


@pytest.mark.parametrize("fault", [
    "the tail dropped at a piece boundary", "the tail taken at the padded length",
    "an unlisted row's tail moved by a step"])
def test_the_served_comparison_sees(served, fault):
    """A serving path that mishandles the two-row tail reads logits that are
    not the reference's."""
    read = _two_slots(served, fault)
    worst = max(float(jnp.abs(logits - served.want[b, pos]).max()) for b, pos, logits in read)
    assert worst > 20 * CASE.logit_tol, (fault, worst)


def test_a_step_leaves_an_unlisted_rows_tail_where_it_is():
    """The mixer's own step under a row list: the tail of a row outside the
    list keeps its bits, a listed row's moves up by the token's ``b * u``, and
    the listed rows' outputs are what a step of every row gives them."""
    cfg = tiny_cfg(CASE)
    mixer = MIXERS["gated_conv"](cfg, "gated_conv")
    assert MIXERS["gated_conv"].rows_in_place and MIXERS["gated_conv"].tail_leaves == ("conv",)
    assert MIXERS["gated_conv"].cache_leaves == () and MIXERS["gated_conv"].cache_rows(cfg, "gated_conv") == 0
    x = jax.random.normal(jax.random.key(0), (3, cfg.d_model))
    params = jax.jit(mixer.init)(jax.random.key(1), x[:, None])
    state = {"conv": jax.random.normal(jax.random.key(2), (3, 2 * cfg.d_model))}
    t = jnp.zeros((3,), jnp.int32)
    rows = (jnp.array([0, 2, 0], jnp.int32), jnp.array([2], jnp.int32))  # rows 0 and 2 listed
    every, moved = mixer.apply(params, x, state, t, method="decode_step")
    listed, kept = mixer.apply(params, x, state, t, rows, method="decode_step")
    np.testing.assert_array_equal(kept["conv"][1], state["conv"][1])
    np.testing.assert_array_equal(kept["conv"][jnp.array([0, 2])], moved["conv"][jnp.array([0, 2])])
    np.testing.assert_array_equal(kept["conv"][0, :cfg.d_model], state["conv"][0, cfg.d_model:])
    assert not bool((moved["conv"][1] == state["conv"][1]).all())
    np.testing.assert_array_equal(listed[jnp.array([0, 2])], every[jnp.array([0, 2])])


def test_the_gates_divide_by_the_sum_plus_1e_6(served):
    """A router whose scores are small (a chosen pair sums to ~0.01) shows the
    published ``+ 1e-6`` at 1e-4 of the layer's output: the layer is the
    reference's with it and is not the reference's without."""
    cfg = tiny_cfg(CASE, moe_route_bias=0.01)
    layer = MoEMLP(cfg)
    x = jax.random.normal(jax.random.key(2), (1, 24, cfg.d_model))
    params = jax.jit(layer.init)(jax.random.key(0), x)
    p = dict(params["params"])
    # every logit near -5.3: scores ~0.005
    p["router"] = {"kernel": 0.02 * p["router"]["kernel"]}
    x = x + 1.0
    p["router"]["kernel"] = p["router"]["kernel"] - 5.3 / cfg.d_model
    got = jax.jit(layer.apply)({"params": p}, x)
    with jax.default_matmul_precision("highest"):
        want = served.ref.mlp(served.spec(cfg), p, x)
        without = served.ref.mlp(served.spec(cfg, gate_eps=0.0), p, x)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 1e-5 * scale
    assert float(jnp.abs(got - without).max()) > 5e-5 * scale
    plain = jax.jit(MoEMLP(dataclasses.replace(cfg, moe_gate_eps=0.0)).apply)({"params": p}, x)
    assert float(jnp.abs(plain - without).max()) < 1e-5 * scale  # 0: the router as it was


def test_the_other_conv_users_name_their_tails_too():
    """``tail_leaves`` is what the engine's account asks, not a layer type's
    name: the delta-rule and state-space layers' conv tails are tails."""
    assert MIXERS["ssm"].tail_leaves == MIXERS["gated_delta"].tail_leaves == ("conv",)
    assert all(MIXERS[lt].tail_leaves == () for lt in MIXERS
               if lt not in ("ssm", "gated_delta", "gated_conv"))


def test_the_donation_rule_counts_what_a_program_relays():
    """A grouped cache of 64-wide heads is held with its rows minor and
    copied, padded to 128 lanes, for the row-list kernel: 128 slots' carry
    fits twice beside the weights by its own bytes (13.26 of 14.8 GB) and
    does not once the copies are counted, so the engine donates; a tree
    without such a leaf weighs what it weighed."""
    from orion_tpu.ops.dispatch import cache_copy_nbytes
    from orion_tpu.serving.batching import fits_once_only, tree_nbytes

    cfg = get_config("lfm2_8b_a1b")
    states = jax.eval_shape(lambda: init_decode_state(cfg, 128))
    kv = 3 * 2 * 128 * 8 * 2560 * 64 * 2
    assert tree_nbytes(states) == kv + 10 * 128 * 2 * 2048 * 2
    assert cache_copy_nbytes(states) == 2 * kv  # the tails [128, 4096] are wide: no copy
    vectors = (jnp.zeros((128,), jnp.int32), jnp.zeros((128,), bool))
    assert cache_copy_nbytes(vectors) == 0
    wide = jax.eval_shape(lambda: init_decode_state(get_config("trinity_mini"), 4))
    assert cache_copy_nbytes(wide) == 0

    class Chip:
        def memory_stats(self):
            return {"bytes_limit": 16.9e9}

    class Host:
        def memory_stats(self):
            return None

    weights = jax.ShapeDtypeStruct((4_606_249_728,), jnp.bfloat16)
    assert 2 * tree_nbytes(states) + tree_nbytes(weights) < 0.875 * 16.9e9
    assert fits_once_only(states, weights, Chip()) and not fits_once_only(states, weights, Host())
    small = jax.eval_shape(lambda: init_decode_state(cfg, 16))
    assert not fits_once_only(small, weights, Chip())


def _served_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    files = {c["name"]: os.path.join(ROOT, c["file"]) for c in manifest["configs"]}
    for cell in manifest["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "workloads", cell["name"] + ".json")) as f:
            server = json.load(f).get("server")
        if server and cell["chips"] == 1:
            yield pytest.param(files[cell["config"]], server, id=cell["name"])


@pytest.mark.parametrize("config_file,server", list(_served_cells()))
def test_counting_the_copies_moves_no_other_cells_decision_to_donate(config_file, server):
    """Every served cell of the benchmark at its own slots x rows, its
    published widths (shapes alone) and a v5e's ``bytes_limit``: the copies
    can only add, and the cells that did not donate hold no narrow cache, so
    each decides as it did; this configuration's cell is the one they move
    (13.26 -> 17.29 of 14.8 GB)."""
    from orion_tpu.models.configs import ModelConfig
    from orion_tpu.ops.dispatch import cache_copy_nbytes
    from orion_tpu.serving.batching import PROGRAM_RESERVE, fits_once_only, tree_nbytes

    with open(config_file) as f:
        config = json.load(f)
    fields = {k: v for k, v in config["model"].items() if k != "rehearse"}
    fields["layer_types"] = fields.get("layer_types") and tuple(fields["layer_types"])
    cfg = ModelConfig(name=config["name"], **{**fields, "max_seq_len": server["max_seq_len"]})
    shapes = jax.eval_shape(
        lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 16), jnp.int32)))
    # as served: the weights in the compute dtype (generate.serving_params)
    weights = jax.ShapeDtypeStruct((sum(l.size for l in jax.tree.leaves(shapes)),), jnp.dtype(cfg.dtype))
    states = jax.eval_shape(lambda: init_decode_state(cfg, server["slots"]))

    class Chip:
        def memory_stats(self):
            return {"bytes_limit": 16909336064}

    without = 2 * tree_nbytes(states) + tree_nbytes(weights) > (1 - PROGRAM_RESERVE) * 16909336064
    moved = fits_once_only(states, weights, Chip()) != without
    assert moved == (config["name"] == "lfm2_8b_a1b"), (tree_nbytes(states), cache_copy_nbytes(states))


def test_a_piece_group_packs_slots_past_126():
    """Four slots a program as ONE int32, a byte each: slot 127 in the fourth
    place sets the word's sign (the first run of this cell on the chip died
    there: 128 slots where the widest cell before had 64 to a group), and
    the program's shift-and-mask reads every place back."""
    from orion_tpu.generate import pack_piece_group

    unpack = jax.jit(lambda sel: jnp.stack([(sel >> (8 * g)) & 0xFF for g in range(4)]))
    for slots in [(123, 127, 27, 32), (0, 1, 2, 127), (5, 6, 7, 254), (254, 254, 254, 254), (9,), ()]:
        word = pack_piece_group(slots)
        assert -(1 << 31) <= word < 1 << 31
        byte = np.asarray(unpack(jnp.int32(word)))
        assert [int(b) - 1 for b in byte if b > 0] == list(slots)
        assert int((byte > 0).sum()) == len(slots)
    assert pack_piece_group((3, 4, 5, 126)) == (4 | 5 << 8 | 6 << 16 | 127 << 24)  # as it was
    with pytest.raises(AssertionError):
        pack_piece_group((255,))
