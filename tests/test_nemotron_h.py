"""The served state-space / attention / latent-mixture hybrid (ISSUE 57):
state-space layers whose gated norm is taken over each group's channels, one
attention layer with no position term, experts that work in a latent (a
sigmoid top-k router with a selection bias over a router wider than the
experts held, two squared-ReLU matrices an expert, an ungated shared expert on
the full width), and one block in six that is a mixer alone; against
``benchmark/reference/plain_nemotron_h.py``; tiny, CPU, fp32. The contract
every served configuration takes is ``tests/served_contract.py``'s."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from served_contract import (
    ServedCase, ServedContract, Share, Walk, config_file, moe_stats, served_fixture, tiny_cfg,
)

from orion_tpu.models.configs import get_config, step_pattern_blocks
from orion_tpu.models.mixers import MIXERS
from orion_tpu.models.moe import STAT_NAMES, MoEMLP, expert_form, masks_rows
from orion_tpu.models.transformer import TransformerLM, init_decode_state

PUBLISHED = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
             "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
# the rehearse block is the preset's six blocks (ssm+E x3, ssm alone,
# attention+E, ssm+E) at toy widths: 4 of a 16-wide router's experts held, 5
# chosen
T = 29
P = 8  # rows of a prompt piece
CASE = ServedCase(
    "nemotron_3_super_120b", seq=T,
    logit_tol=5e-5,  # fp32 against fp32 on logits of ~4: summation order only
    over=dict(max_seq_len=64),
    moved=("scale", "out_norm"),  # a norm taken over other channels shows too
    floor=1.0,
    # a whole-prompt ``prefill`` padded to a bucket, its state taken at the real
    # length, then steps; pieces of 8 and 5 of 8
    walk=Walk(n=13, piece=P, steps=3, steps_from="padded", live=True, padded=3,
              against="reference", backends=("xla",)),
    # every share's routed part is computed in the latent and up-projected
    # there (the up-projection is linear)
    share=Share(experts=("experts_up", "experts_down"), part_tol=2e-5, sum_tol=5e-5),
    # the chip's programs: the row lists, the state-space step's kernel, the
    # grouped product over live tiles, two slots' pieces a program, the carry
    # held once
    engines=(("pallas_interpret", True, {"prefill_group": 2}),),
    engine=dict(slots=4, chunk=4, prefill_buckets=(8, 16, 32), prefill_chunk=8),
    prompts=((0, 0, 5), (1, 0, 8), (0, 3, 29)),
    served_gap=5e-5,  # the reference's own choice to a logit gap of rounding
)
served = served_fixture(CASE)


class TestServed(ServedContract):
    case = CASE

    def published(self, cfg):
        assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (4096, 32, 2, 128)
        assert not cfg.rotary and cfg.qk_norm == "none" and cfg.attn_scale is None and not cfg.attn_gate
        assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups) == (128, 64, 128, 8)
        assert cfg.ssm_heads * cfg.ssm_head_dim == 2 * cfg.d_model and cfg.ssm_conv_width == 4
        assert cfg.resolved_layer_types == ("ssm", "ssm", "ssm", "ssm", "softmax", "ssm")
        assert cfg.mixer_only == "3" and [cfg.has_mlp(i) for i in range(6)] == [
            True, True, True, False, True, True]
        assert [cfg.moe_at(i) for i in range(6)] == [True, True, True, False, True, True]
        assert (cfg.mlp, cfg.moe_hidden, cfg.moe_latent, cfg.moe_shared_hidden) == (
            "relu2", 2688, 1024, 5376)
        assert expert_form(cfg)[0] == ("up", "down") and not cfg.moe_shared_gated
        assert (cfg.n_experts, cfg.resolved_router_width, cfg.moe_expert_offset, cfg.moe_top_k) == (
            128, 512, 0, 22)
        assert (cfg.moe_score, cfg.moe_route_scale, cfg.moe_gate_eps) == ("sigmoid", 5.0, 1e-20)
        assert cfg.moe_route_bias > 0 and cfg.moe_held and masks_rows(cfg)
        assert cfg.moe_ep_buffer == cfg.resolved_router_width / cfg.n_experts  # nothing can drop
        assert (cfg.vocab_size, cfg.tie_embeddings, cfg.max_seq_len) == (32768, False, 4096)
        assert cfg.norm == "rmsnorm" and cfg.norm_eps == 1e-5 and cfg.norm_placement == "pre"
        assert cfg.pos_embed == "none" and cfg.embed_scale == 1.0
        states = jax.eval_shape(lambda: init_decode_state(cfg, 2))
        assert [sorted(s) for s in states] == [["conv", "s"]] * 4 + [["k", "v"]] + [["conv", "s"]]
        # two heads of a group side by side on lanes: 4.19 MB a row a layer
        assert states[0]["s"].shape == (2, 64, 128, 128) and states[0]["s"].dtype == jnp.float32
        assert states[0]["conv"].shape == (2, 3 * 10240)
        assert states[4]["k"].shape == (2, 2, 4096, 128)

    def share_layer(self, served, spec, p, x):
        return served.ref.latent_experts(spec, p, x)

    def after_share(self, served, whole, p, x, want, stats):
        """The counters of the two-matrix path say so too; and the layer every
        expert of which is held, through the training forward's
        sort-and-ragged-dot form, is the same layer."""
        assert p["experts_up"].shape == (16, 32, 48) and p["experts_down"].shape == (16, 48, 32)
        for s in stats:
            assert 0 < s["experts_live"] <= 4 and s["tiles_live"] >= s["experts_live"]
            assert s["rows_max_expert"] * 4 >= s["rows_held"]
        plain = MoEMLP(dataclasses.replace(whole, backend="xla")).apply({"params": p}, x)
        assert float(jnp.abs(plain - want).max()) < 5e-5

    def after_boundary(self, engine):
        return np.asarray(engine.moe_rows)

    def after_engine(self, served, run, backend, donate):
        """The engine's memory account is the states' and the counters are the
        held-rows path's."""
        cfg, prompts = run.cfg, served.prompts
        stats = dict(zip(STAT_NAMES, np.sum(run.counted, axis=0)))
        # five expert layers x five experts a row that counts: every prompt row
        # and every step a slot emitted at (at least the 8 after each first token)
        rows = sum(len(p) for p in prompts) + 3 * 8
        assert stats["rows_routed"] % 25 == 0 and stats["rows_routed"] >= 25 * rows
        assert 0 < stats["rows_held"] < stats["rows_routed"] and stats["dropless_overflow"] == 0
        assert stats["experts_live"] > 0 and stats["tiles_live"] >= stats["experts_live"]
        held = run.engine.held_bytes
        state = 4 * 5 * 8 * 8 * 16 * 4  # five layers' fp32 S a slot
        tails = 4 * 5 * 3 * (64 + 2 * 2 * 16) * 4
        assert held["tail_bytes"] == tails and held["state_bytes"] == state + tails
        assert held["kv_bytes"] == 4 * 2 * 2 * cfg.max_seq_len * 16 * 4 and held["ring_bytes"] == 0
        assert run.engine.kv_rows()[1] == 4 * cfg.max_seq_len


def test_the_published_widths_count_the_issues_parameters():
    """4,648,163,712 parameters at the published widths, by the shapes alone
    (ISSUE 57's arithmetic, which the configuration's ``deployment`` states)."""
    cfg = get_config("nemotron_3_super_120b")
    tree = jax.eval_shape(
        lambda: TransformerLM(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda t: sum(x.size for x in jax.tree.leaves(t))  # noqa: E731
    assert count(tree["block_0"]["attn"]) + 4096 == 109_640_064  # an M layer with its norm
    assert count(tree["block_4"]["attn"]) + 4096 == 35_655_680  # the * layer with its norm
    mlp = tree["block_0"]["mlp"]
    assert mlp["experts_up"].shape == (128, 1024, 2688) and mlp["experts_down"].shape == (128, 2688, 1024)
    assert "experts_gate" not in mlp and "shared_gate" not in mlp
    assert mlp["latent_down"]["kernel"].shape == (4096, 1024)
    assert mlp["latent_up"]["kernel"].shape == (1024, 4096)
    assert mlp["router"]["kernel"].shape == (4096, 512) and mlp["router_bias"].shape == (512,)
    assert mlp["router_bias"].dtype == jnp.float32
    assert count(mlp) + 4096 == 54_530_560 + 128 * 5_505_024  # an E layer with its norm
    assert sorted(tree["block_3"]) == ["attn", "norm1"]  # a mixer alone: no norm2, no mlp
    assert count(tree) == 4_648_163_712
    assert count(tree) == (5 * 109_640_064 + 35_655_680 + 5 * 759_173_632
                           + 2 * 32768 * 4096 + 4096)


def test_the_config_file_recounts_the_deployment():
    """``benchmark/configs/nemotron_3_super_120b.json``: the published keys
    under their own names, the model as run equal to the preset, and the
    numbers its ``deployment`` states recounted from the shapes."""
    spec = config_file("nemotron_3_super_120b")
    assert spec["hybrid_override_pattern"] == PUBLISHED and spec["num_hidden_layers"] == 88
    assert (spec["mamba_num_heads"], spec["mamba_head_dim"], spec["ssm_state_size"],
            spec["n_groups"], spec["conv_kernel"]) == (128, 64, 128, 8, 4)
    assert (spec["n_routed_experts"], spec["num_experts_per_tok"], spec["moe_latent_size"],
            spec["moe_intermediate_size"], spec["moe_shared_expert_intermediate_size"],
            spec["routed_scaling_factor"]) == (512, 22, 1024, 2688, 5376, 5)
    assert spec["reduced"] == ["n_layers", "n_experts", "vocab_size"]
    cfg = get_config("nemotron_3_super_120b")  # the model as run: the contract's preset test
    said = spec["deployment"]
    for number in ("4,648,163,712", "109,640,064", "35,655,680", "759,173,632", "5,505,024",
                   "9.30 GB", "3.26 GB", "25.5 MB"):
        assert number in said, number
    states = jax.eval_shape(lambda: init_decode_state(cfg, 128))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(states))
    assert nbytes == 128 * (5 * 4_194_304 + 5 * 61_440 + 4096 * 1024) == 3_260_547_072
    assert "8 pipeline stages" in said and "4 chips" in said
    assert len(spec["assumed"]) >= 5 and any("rotary" in a for a in spec["assumed"])
    assert any("multi-token" in a or "MTP" in a for a in spec["assumed"])


def test_the_published_pattern_groups_into_48_blocks():
    """The 88 one-step layers as this repo's blocks: 32 x (M, E), 8 x (*, E)
    and the 8 M before each * alone; the first 11 are the preset's six."""
    kinds, alone = step_pattern_blocks(PUBLISHED)
    assert len(PUBLISHED) == 88 and len(kinds) == 48
    assert (kinds.count("ssm"), kinds.count("softmax")) == (40, 8)
    alone = [int(i) for i in alone.split(",")]
    assert len(alone) == 8 and all(kinds[i] == "ssm" and kinds[i + 1] == "softmax" for i in alone)
    again = "".join({"ssm": "M", "softmax": "*"}[k] + ("" if i in alone else "E")
                    for i, k in enumerate(kinds))
    assert again == PUBLISHED
    assert [i for i, c in enumerate(PUBLISHED) if c == "*"] == [7, 16, 25, 36, 47, 58, 69, 78]
    cfg = get_config("nemotron_3_super_120b")
    assert step_pattern_blocks(PUBLISHED[:11]) == (cfg.resolved_layer_types, cfg.mixer_only)
    with pytest.raises(AssertionError):
        step_pattern_blocks("MEE")  # a feed-forward step with no mixer to share a block with


def test_every_builder_of_blocks_takes_the_same_form():
    """``cfg.block_form`` is the one place that says which feed-forward part a
    block has, and the three builders of ``Block`` hand it on whole: the
    classifier and the pipeline's stage build a mixer-alone block without
    ``norm2`` / ``mlp`` too (not a dense MLP in its place), and the pipeline's
    period counts a block's form."""
    from orion_tpu.models.classifier import LRAClassifier
    from orion_tpu.models.configs import ModelConfig
    from orion_tpu.parallel.pipeline_lm import stage_group

    cfg = tiny_cfg(CASE)
    assert [cfg.block_form(i) for i in (2, 3)] == [
        {"use_moe": True, "mixer_only": False}, {"use_moe": False, "mixer_only": True}]
    assert stage_group(cfg) == 6  # one whole period: nothing in it repeats
    same = dict(vocab_size=32, d_model=32, n_layers=4, n_heads=2, max_seq_len=32, dtype="float32")
    assert stage_group(ModelConfig(**same)) == 1
    assert stage_group(ModelConfig(**same, mixer_only="1,3")) == 2
    assert stage_group(ModelConfig(**same, mixer_only="3")) == 4
    lra = ModelConfig(**same, mixer_only="1,3", n_classes=4, mlp="gelu", backend="xla")
    toks = jnp.zeros((2, 16), jnp.int32)
    shapes = jax.eval_shape(
        LRAClassifier(lra).init, jax.random.key(0), toks, jnp.ones((2, 16), bool))["params"]
    assert [sorted(shapes[f"block_{i}"]) for i in (0, 1)] == [
        ["attn", "mlp", "norm1", "norm2"], ["attn", "norm1"]]
    lm = jax.eval_shape(TransformerLM(dataclasses.replace(lra, n_classes=0)).init,
                        jax.random.key(0), toks)["params"]
    assert all(sorted(lm[f"block_{i}"]) == sorted(shapes[f"block_{i}"]) for i in range(4))


def test_the_block_form_is_the_published_step_form(served):
    """``block`` over the served tree (one or two steps a block) gives what the
    flat list of published layers gives, letter for letter."""
    steps = served.ref.steps_of(served.spec(), served.params)
    assert "".join(letter for letter, _ in steps) == PUBLISHED[:11]
    with jax.default_matmul_precision("highest"):
        flat = served.ref.forward_steps(served.spec(), served.params, served.toks)
    np.testing.assert_array_equal(flat, served.want)


# -- the training forward against the reference -----------------------------------------


def test_the_loss_and_its_gradient_are_finite(served):
    """``orion_tpu.train``'s forward and loss at a small size: the routed
    experts in the latent, the selection bias (no gradient reaches it) and the
    block without a feed-forward part all differentiate."""
    model, params, toks = served.model, served.params, served.toks

    def loss(p):
        logits = model.apply(p, toks[:, :-1])
        return -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(logits), toks[:, 1:, None], axis=-1))

    value, grads = jax.jit(jax.value_and_grad(loss))(params)
    assert np.isfinite(float(value)) and abs(float(value) - np.log(256)) < 2.0
    flat = {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_leaves_with_path(grads)}
    assert all(bool(jnp.isfinite(v).all()) for v in flat.values())
    moving = [k for k, v in flat.items() if float(jnp.abs(v).max()) > 0]
    still = sorted(set(flat) - set(moving))
    assert all("router_bias" in k for k in still) and len(still) == 5
    assert any("latent_down" in k for k in moving) and any("experts_up" in k for k in moving)


@pytest.mark.parametrize("patch", [
    "one norm over all groups", "the norm before the gate", "a plain ReLU", "a gated expert",
    "the router reads the latent", "the bias in the weights", "no bias", "no scaling factor",
    "rotary on the attention layer", "the shared expert in the latent",
    "a feed-forward part after every mixer"])
def test_the_comparison_sees(served, monkeypatch, patch):
    """The tolerance is tight enough to tell the model from a reference that
    differs in one of the mechanisms."""
    ref, cfg, params, spec = served.ref, served.cfg, served.params, served.spec()
    f32 = lambda w: jnp.asarray(w, jnp.float32)  # noqa: E731
    if patch == "one norm over all groups":
        monkeypatch.setattr(ref, "gated_group_norm", lambda s, y, z, w: ref.rms(s, y * jax.nn.silu(z), w))
    elif patch == "the norm before the gate":
        plain = ref.gated_group_norm
        monkeypatch.setattr(ref, "gated_group_norm", lambda s, y, z, w: plain(
            s, y, jnp.full_like(z, 1.2784645), w) * jax.nn.silu(z))  # silu(1.278) = 1
    elif patch == "a plain ReLU":
        monkeypatch.setattr(ref, "relu2", lambda x: jnp.maximum(x, 0.0))
    elif patch == "a gated expert":
        monkeypatch.setattr(ref, "relu2", lambda x: jax.nn.silu(x) * x)
    elif patch == "the router reads the latent":
        def from_latent(spec, p, u, shared=True):
            lat = ref.mm(spec, u, f32(p["latent_down"]["kernel"]))
            narrow = {**p, "router": {"kernel": p["router"]["kernel"][:lat.shape[-1]]}}
            y = ref.mm(spec, ref.routed_experts(spec, narrow, lat, lat), f32(p["latent_up"]["kernel"]))
            return y + ref.shared_expert(spec, p, u)
        monkeypatch.setitem(ref.F, "E", from_latent)
    elif patch == "the bias in the weights":
        def biased(spec, p, u):
            scores = jax.nn.sigmoid(u @ f32(p["router"]["kernel"])) + p["router_bias"]
            top, ids = jax.lax.top_k(scores, spec["top_k"])
            top = spec["route_scale"] * top / (top.sum(-1, keepdims=True) + 1e-20)
            return jnp.einsum("...k,...kr->...r", top, jax.nn.one_hot(ids, scores.shape[-1]))
        monkeypatch.setattr(ref, "routing_weights", biased)
    elif patch == "no bias":
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.zeros_like(x) if "router_bias" in str(path) else x, params)
    elif patch == "no scaling factor":
        spec = {**spec, "route_scale": 1.0}
    elif patch == "rotary on the attention layer":
        plain = ref.mm

        def rotated(spec, a, b):  # the projections' outputs turned by position
            y = plain(spec, a, b)
            if a.ndim == 3 and b.shape == (cfg.d_model, cfg.n_heads * cfg.head_dim):
                ang = jnp.arange(a.shape[1])[None, :, None] * 0.3
                y = y * jnp.cos(ang) + jnp.roll(y, 1, axis=-1) * jnp.sin(ang)
            return y
        monkeypatch.setattr(ref, "mm", rotated)
    elif patch == "the shared expert in the latent":
        monkeypatch.setattr(ref, "shared_expert", lambda spec, p, u: 0.0 * u)
    elif patch == "a feed-forward part after every mixer":
        blk = params["params"]
        params = {"params": {**blk, "block_3": {**blk["block_3"], "norm2": blk["block_2"]["norm2"],
                                                "mlp": blk["block_2"]["mlp"]}}}
    served.differs(spec, params)


# -- the gated norm by groups ------------------------------------------------------------


def test_the_gated_norm_is_taken_over_each_group(served, monkeypatch):
    """The mixer's output norm at 2 groups is the reference's by groups and is
    NOT one norm over all channels; at 1 group the two are one and the mixer
    gives what it gave (``granite_4_0_h_micro``'s form)."""
    x = jax.random.normal(jax.random.key(0), (2, 12, 64))
    outs = {}
    for groups in (1, 2):
        cfg = tiny_cfg(CASE, ssm_groups=groups)
        mixer = MIXERS["ssm"](cfg, "ssm")
        params = jax.jit(mixer.init)(jax.random.key(1), x)
        p = dict(params["params"])
        p["out_norm"] = p["out_norm"] + 0.3 * jax.random.normal(jax.random.key(2), p["out_norm"].shape)
        got = mixer.apply({"params": p}, x)
        ref = served.ref
        with jax.default_matmul_precision("highest"):
            by_group = ref.ssm(served.spec(cfg), p, x)
            with monkeypatch.context() as patched:
                patched.setattr(ref, "gated_group_norm",
                                lambda s, y, z, w: ref.rms(s, y * jax.nn.silu(z), w))
                one_norm = ref.ssm(served.spec(cfg), p, x)
        outs[groups] = (got, by_group, one_norm)
        np.testing.assert_allclose(got, by_group, atol=2e-5)
    assert float(jnp.abs(outs[1][1] - outs[1][2]).max()) < 1e-6  # one group: the same norm
    assert float(jnp.abs(outs[2][0] - outs[2][2]).max()) > 1e-2  # two: another layer


# -- the experts in the latent ---------------------------------------------------------


def test_rows_that_do_not_count_route_nowhere(served):
    """A row outside ``live`` adds nothing to any counter and its experts'
    output is the shared expert's alone."""
    cfg = tiny_cfg(CASE)
    layer = MoEMLP(cfg)
    x = jax.random.normal(jax.random.key(2), (6, cfg.d_model))
    params = jax.jit(layer.init)(jax.random.key(0), x)
    live = jnp.array([True, False, True, True, False, True])
    got, sown = layer.apply(params, x, live, mutable=["moe_stats"])
    every, sown_all = layer.apply(params, x, jnp.ones((6,), bool), mutable=["moe_stats"])
    assert moe_stats(sown)["rows_routed"] == 4 * 5 and moe_stats(sown_all)["rows_routed"] == 6 * 5
    with jax.default_matmul_precision("highest"):
        shared = served.ref.shared_expert(served.spec(cfg), params["params"], x)
    np.testing.assert_allclose(got[jnp.array([1, 4])], shared[jnp.array([1, 4])], atol=2e-5)
    np.testing.assert_allclose(got[jnp.array([0, 2, 3, 5])], every[jnp.array([0, 2, 3, 5])], atol=2e-5)


def test_the_latent_projections_write_their_own_scope():
    """``moe_latent`` names the two projections, beside ``moe_route``,
    ``moe_experts`` and ``moe_shared``, in the lowered program's name stacks;
    a layer without a latent has no such scope and no such leaves."""
    cfg = tiny_cfg(CASE)
    x = jnp.zeros((4, cfg.d_model))
    layer = MoEMLP(cfg)
    params = jax.eval_shape(lambda: layer.init(jax.random.key(0), x))
    text = jax.jit(lambda p, x: layer.apply(p, x, jnp.ones((4,), bool), mutable=["moe_stats"])
                   ).lower(params, x).as_text(debug_info=True)
    for scope in ("moe_latent", "moe_route", "moe_experts", "moe_shared"):
        assert scope in text, scope
    flat = MoEMLP(dataclasses.replace(cfg, moe_latent=0))
    leaves = jax.eval_shape(lambda: flat.init(jax.random.key(0), x))["params"]
    assert "latent_down" not in leaves and leaves["experts_up"].shape == (4, 64, 48)
    text = jax.jit(lambda p, x: flat.apply(p, x)).lower({"params": leaves}, x).as_text(debug_info=True)
    assert "moe_latent" not in text


# -- the serving path: pieces, a group of pieces and steps against ONE full forward ------


def _pieces(model, params, piece, toks, n):
    states = init_decode_state(model.cfg, 1, jnp.float32)
    out = []
    for at in range(0, n, P):
        real = min(P, n - at)
        rows = jnp.zeros((1, P), jnp.int32).at[0, :real].set(toks[at:at + real])
        logits, states = piece(params, rows, states, jnp.int32(at), jnp.int32(real))
        out.append((at + real - 1, logits[0]))
    return out, states


def test_pieces_then_steps_match_one_full_forward(served):
    """``prefill_extend`` in pieces (one padded, one shorter than the conv's
    three-row tail) then ``decode_step``s of two slots at different positions,
    one sitting a step out: every logit row read on the way is the
    reference's full forward's at that position. The block that is a mixer
    alone passes through both methods."""
    prog, params, toks, want = served.programs(), served.params, served.toks, served.want
    model, piece = prog.model, prog.piece
    starts, read, rows = (19, 17), [], []
    for b, n in enumerate(starts):
        got, states = _pieces(model, params, piece, toks[b], n)
        read += [(b, pos, logits) for pos, logits in got]
        rows.append(states)
    states = jax.tree.map(lambda *leaves: jnp.concatenate(leaves), *rows)
    t = np.array(starts)
    for i in range(8):
        emitting = np.array([True, i != 2])
        tok = jnp.asarray([toks[b, t[b]] if emitting[b] else 7 for b in range(2)])
        mask = jnp.asarray(emitting)
        logits, new = prog.step(params, tok, states, jnp.asarray(t, jnp.int32), None, mask)
        states = jax.tree.map(
            lambda n, o: jnp.where(mask.reshape((-1,) + (1,) * (n.ndim - 1)), n, o), new, states)
        read += [(b, int(t[b]), logits[b]) for b in range(2) if emitting[b]]
        t = t + emitting
    assert len(read) == 6 + 8 + 7
    for b, pos, logits in read:
        np.testing.assert_allclose(logits, want[b, pos], atol=CASE.logit_tol, err_msg=f"{b} {pos}")


def test_a_group_of_pieces_is_each_piece_alone(served):
    """``prefill_extend_group``: two sequences' pieces in one program (their
    feed-forward rows together, a block without one passing them on) give
    each the logits and the state its piece gives alone."""
    prog, params, toks, want = served.programs(), served.params, served.toks, served.want
    model, piece = prog.model, prog.piece
    firsts, singles = [], []
    for b in range(2):
        _, st = _pieces(model, params, piece, toks[b], P)
        firsts.append(st)
    lengths = jnp.asarray([P, 5], jnp.int32)
    rows = jnp.stack([toks[0, P:2 * P], jnp.zeros((P,), jnp.int32).at[:5].set(toks[1, P:P + 5])])
    for b in range(2):
        singles.append(piece(params, rows[b:b + 1], firsts[b], jnp.int32(P), lengths[b]))
    logits, states = prog.group(params, rows, firsts, jnp.full((2,), P, jnp.int32), lengths)
    for b in range(2):
        np.testing.assert_allclose(logits[b], singles[b][0][0], atol=CASE.logit_tol)
        np.testing.assert_allclose(logits[b], want[b, P + int(lengths[b]) - 1], atol=CASE.logit_tol)
        for got, alone in zip(jax.tree.leaves(states[b]), jax.tree.leaves(singles[b][1])):
            np.testing.assert_allclose(got, alone, atol=2e-5)


def test_the_carry_is_mostly_state_and_is_donated():
    """128 slots x 4,096 at the published widths, by shapes alone: 21 MB of
    recurrent state beside 4 MB of cache a slot (82% of the carry), which does
    not fit twice beside 9.30 GB of weights on a v5e: the engine donates."""
    from orion_tpu.serving.batching import fits_once_only, tree_nbytes

    cfg = get_config("nemotron_3_super_120b")
    states = jax.eval_shape(lambda: init_decode_state(cfg, 128))
    total = tree_nbytes(states)
    cache = tree_nbytes([s for s in states if "k" in s])
    assert total == 3_260_547_072 and cache == 128 * 4096 * 1024
    assert 0.82 < (total - cache) / total < 0.85

    class Chip:
        def memory_stats(self):
            return {"bytes_limit": 16909336064}

    weights = jax.ShapeDtypeStruct((4_648_163_712,), jnp.bfloat16)
    assert 2 * total + tree_nbytes(weights) > 0.875 * 16909336064 > total + tree_nbytes(weights)
    assert fits_once_only(states, weights, Chip())
