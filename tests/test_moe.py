"""MoE + expert parallelism tests (models/moe.py, ep mesh axis).

Reference counterpart: none in BASELINE.json's config list (reference
checkout never mounted — SURVEY.md §0); ep shardings are part of the
driver's multi-chip contract. Test strategy mirrors the repo-wide pattern:
exact small-scale invariants + virtual-mesh parity vs single device.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.moe import MoEMLP, top_k_routing
from orion_tpu.parallel.mesh import MeshConfig


def _probs(n, e, seed=0):
    return jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(seed), (n, e)), axis=-1
    )


class TestRouting:
    def test_no_drops_at_full_capacity(self):
        p = _probs(32, 4)
        disp, comb, assign = top_k_routing(p, 2, capacity=32)
        # every token keeps both slots; combine weights renormalize to 1
        np.testing.assert_allclose(np.asarray(comb.sum((1, 2))), 1.0, atol=1e-5)
        assert int(disp.sum()) == 32 * 2
        np.testing.assert_allclose(np.asarray(assign.sum(-1)), 1.0, atol=1e-6)

    def test_capacity_drops_excess_tokens(self):
        # all tokens prefer expert 0 -> only `cap` survive
        p = jnp.tile(jnp.asarray([[0.9, 0.1]]), (16, 1))
        disp, comb, _ = top_k_routing(p, 1, capacity=4)
        assert int(disp[:, 0].sum()) == 4
        # dropped tokens have zero combine weight (residual passes through)
        assert float(comb.sum((1, 2)).min()) == 0.0

    def test_slots_unique_per_expert(self):
        """No two tokens share an (expert, capacity-slot) cell."""
        disp, _, _ = top_k_routing(_probs(64, 4, seed=3), 2, capacity=40)
        per_cell = np.asarray(disp.sum(0))  # [E, C]
        assert per_cell.max() <= 1

    def test_underflowed_probs_never_redispatch(self):
        """k=2 with softmax mass underflowed to exactly 0 on all non-top
        experts: slot 2 must not re-pick the slot-1 expert (or burn a
        capacity slot on a gate-0 duplicate)."""
        logits = jnp.zeros((4, 4)).at[:, 2].set(200.0)  # softmax -> exact onehot
        p = jax.nn.softmax(logits, axis=-1)
        assert float(p[0].min()) == 0.0
        disp, comb, _ = top_k_routing(p, 2, capacity=8)
        # expert 2 holds each token exactly once (no double-dispatch)
        assert int(disp[:, 2].sum()) == 4
        per_tok = np.asarray(disp.sum((1, 2)))
        assert per_tok.max() == 2  # one real + one (distinct) zero-gate slot
        chosen = np.asarray(disp.any(-1))
        assert not (chosen.sum(-1) == 1).any()  # slot-2 expert != slot-1's

    def test_top1_picks_argmax(self):
        p = _probs(16, 4, seed=5)
        disp, _, _ = top_k_routing(p, 1, capacity=16)
        chosen = np.asarray(disp.any(-1)).argmax(-1)
        np.testing.assert_array_equal(chosen, np.asarray(p.argmax(-1)))


class TestMoEMLP:
    def test_single_expert_equals_dense_ffn(self):
        """E=1, top-1: routing is the identity — the layer must match the
        plain SwiGLU FFN built from expert 0's weights exactly."""
        cfg = ModelConfig(
            name="t", d_model=16, n_experts=1, moe_top_k=1,
            moe_capacity_factor=1.0, dtype="float32",
        )
        m = MoEMLP(cfg)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
        p = m.init(jax.random.PRNGKey(1), x)
        y = m.apply(p, x)
        w = p["params"]
        ref = (
            jax.nn.silu(x @ w["experts_gate"][0]) * (x @ w["experts_up"][0])
        ) @ w["experts_down"][0]
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)

    def test_init_has_no_losses_collection(self):
        cfg = ModelConfig(name="t", d_model=16, n_experts=4, dtype="float32")
        m = MoEMLP(cfg)
        x = jnp.zeros((2, 4, 16))
        p = m.init(jax.random.PRNGKey(0), x)
        assert set(p.keys()) == {"params"}

    def test_aux_loss_sown_once_and_finite(self):
        cfg = ModelConfig(
            name="t", d_model=16, n_experts=4, moe_top_k=2, dtype="float32"
        )
        m = MoEMLP(cfg)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
        p = m.init(jax.random.PRNGKey(1), x)
        _, v = m.apply(p, x, mutable="losses")
        (aux,) = v["losses"]["moe_aux"]
        assert np.isfinite(float(aux)) and float(aux) > 0

    def test_router_gets_gradient(self):
        cfg = ModelConfig(
            name="t", d_model=16, n_experts=4, moe_top_k=2, dtype="float32"
        )
        m = MoEMLP(cfg)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
        p = m.init(jax.random.PRNGKey(1), x)

        def loss(p):
            out, v = m.apply(p, x, mutable="losses")
            return (out**2).mean() + sum(jax.tree.leaves(v["losses"]))

        g = jax.grad(loss)(p)["params"]
        assert float(jnp.abs(g["router"]["kernel"]).max()) > 0
        assert float(jnp.abs(g["experts_gate"]).max()) > 0

    @pytest.mark.parametrize("k", [1, 2])
    def test_causal_under_drops(self, k):
        """Grouped dispatch + token-major positions make causality
        structural for every k: with an aggressive capacity (many drops),
        changing FUTURE tokens must not change any past position's output.
        (k=2 is the case GShard's slot-major ordering would break: a future
        token's slot-0 pick evicting an earlier token's slot-1.)"""
        cfg = ModelConfig(
            name="t", d_model=16, n_experts=2, moe_top_k=k,
            moe_capacity_factor=0.25, moe_group_size=8, dtype="float32",
        )
        m = MoEMLP(cfg)
        p = m.init(jax.random.PRNGKey(1), jnp.zeros((2, 16, 16)))
        for seed in range(8):  # several routing patterns
            x = jax.random.normal(jax.random.PRNGKey(seed), (2, 16, 16))
            y = m.apply(p, x)
            x2 = x.at[:, 12:].set(
                jax.random.normal(jax.random.PRNGKey(100 + seed), (2, 4, 16))
            )
            y2 = m.apply(p, x2)
            np.testing.assert_allclose(
                np.asarray(y[:, :12]), np.asarray(y2[:, :12]), atol=1e-6
            )

    def test_batch_rows_independent_under_drops(self):
        """Groups never span rows: row 0's routing can't evict row 1's
        tokens even when capacity is tight."""
        cfg = ModelConfig(
            name="t", d_model=16, n_experts=2, moe_top_k=1,
            moe_capacity_factor=0.25, moe_group_size=0, dtype="float32",
        )
        m = MoEMLP(cfg)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16))
        p = m.init(jax.random.PRNGKey(1), x)
        y = m.apply(p, x)
        x2 = x.at[0].set(jax.random.normal(jax.random.PRNGKey(7), (16, 16)))
        y2 = m.apply(p, x2)
        np.testing.assert_allclose(np.asarray(y[1]), np.asarray(y2[1]), atol=1e-6)

    def test_group_size_divides(self):
        from orion_tpu.models.moe import _group_size

        assert _group_size(2048, 512) == 512
        assert _group_size(100, 512) == 100
        assert _group_size(96, 50) == 48
        # prime seq len degenerates to singleton groups — capacity can never
        # bind there, so the resolver warns about the regime change
        with pytest.warns(UserWarning, match="degenerated"):
            assert _group_size(7, 4) == 1

    def test_dropless_matches_capacity_when_nothing_drops(self):
        """With capacity at the no-drop bound (cf = E/k), the capacity path
        provably keeps every token — the dropless sort-based path must
        produce the same outputs (same router, same experts, same gates)."""
        for k in (1, 2):
            cfg = ModelConfig(
                name="t", d_model=16, n_experts=4, moe_top_k=k,
                moe_capacity_factor=4.0 / k, moe_group_size=16,
                dtype="float32",
            )
            m_cap = MoEMLP(cfg)
            m_free = MoEMLP(dataclasses.replace(cfg, moe_dropless=True))
            x = jax.random.normal(jax.random.PRNGKey(k), (2, 16, 16))
            p = m_cap.init(jax.random.PRNGKey(1), x)
            # identical param trees: checkpoints move between the two paths
            jax.tree.map(
                lambda a, b: None,
                p, m_free.init(jax.random.PRNGKey(2), x),
            )
            np.testing.assert_allclose(
                np.asarray(m_cap.apply(p, x)),
                np.asarray(m_free.apply(p, x)),
                atol=2e-5, rtol=2e-5,
            )

    def test_dropless_never_drops_under_tight_capacity_cfg(self):
        """moe_capacity_factor is a no-op for dropless: outputs equal the
        no-drop reference even at cf that would make the capacity path drop
        most assignments."""
        base = ModelConfig(
            name="t", d_model=16, n_experts=4, moe_top_k=1,
            moe_group_size=16, dtype="float32", moe_dropless=True,
        )
        tight = dataclasses.replace(base, moe_capacity_factor=0.25)
        loose = dataclasses.replace(base, moe_capacity_factor=4.0)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16))
        p = MoEMLP(base).init(jax.random.PRNGKey(1), x)
        np.testing.assert_allclose(
            np.asarray(MoEMLP(tight).apply(p, x)),
            np.asarray(MoEMLP(loose).apply(p, x)),
            atol=1e-6,
        )
        # while the capacity path at cf=0.25 visibly differs (it drops)
        cap = MoEMLP(dataclasses.replace(tight, moe_dropless=False))
        assert not np.allclose(
            np.asarray(cap.apply(p, x)), np.asarray(MoEMLP(tight).apply(p, x))
        )

    def test_dropless_router_gets_gradient(self):
        cfg = ModelConfig(
            name="t", d_model=16, n_experts=4, moe_top_k=2,
            dtype="float32", moe_dropless=True,
        )
        m = MoEMLP(cfg)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16))
        p = m.init(jax.random.PRNGKey(1), x)

        def loss(p):
            y, aux = m.apply(p, x, mutable="losses")
            return (y**2).mean() + sum(jax.tree.leaves(aux["losses"]))

        g = jax.grad(loss)(p)
        gr = np.asarray(g["params"]["router"]["kernel"])
        assert np.abs(gr).max() > 0

    def test_dropless_quant_rejects_ep_mesh(self):
        # int8 dropless serving stays single-host; the TRAIN path shards
        # over ep (test_dropless_ep_* below)
        from jax.sharding import Mesh

        cfg = ModelConfig(
            name="t", d_model=16, n_experts=4, moe_top_k=1,
            dtype="float32", moe_dropless=True,
        )
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("ep",))
        m = MoEMLP(cfg, mesh=mesh, quant="int8")
        with pytest.raises(AssertionError, match="single-host"):
            m.init(jax.random.PRNGKey(0), jnp.zeros((2, 16, 16)))

    @pytest.mark.parametrize("ep,k", [(2, 1), (2, 2), (4, 2)])
    def test_dropless_ep_matches_single_host(self, ep, k):
        """_dropless_ep (rotated-sort prefix + zero-expert ragged_dot +
        psum) == the single-host dropless path, with buffer >= ep (the
        mathematically-dropless setting)."""
        from orion_tpu.parallel.mesh import MeshConfig, make_mesh

        cfg = ModelConfig(
            name="t", d_model=16, n_experts=4, moe_top_k=k,
            dtype="float32", moe_dropless=True, moe_ep_buffer=float(ep),
        )
        mesh = make_mesh(MeshConfig(dp=1, ep=ep))
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 16))
        m_ref = MoEMLP(cfg)
        p = m_ref.init(jax.random.PRNGKey(1), x)
        m_ep = MoEMLP(cfg, mesh=mesh)
        # identical param trees: checkpoints move across mesh shapes
        jax.tree.map(
            lambda a, b: None, p, m_ep.init(jax.random.PRNGKey(2), x)
        )
        np.testing.assert_allclose(
            np.asarray(m_ep.apply(p, x)),
            np.asarray(m_ref.apply(p, x)),
            atol=2e-5, rtol=2e-5,
        )

    def test_dropless_ep_grads_match_single_host(self):
        from orion_tpu.parallel.mesh import MeshConfig, make_mesh

        cfg = ModelConfig(
            name="t", d_model=16, n_experts=4, moe_top_k=2,
            dtype="float32", moe_dropless=True, moe_ep_buffer=2.0,
        )
        mesh = make_mesh(MeshConfig(dp=1, ep=2))
        x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 16))
        m_ref, m_ep = MoEMLP(cfg), MoEMLP(cfg, mesh=mesh)
        p = m_ref.init(jax.random.PRNGKey(1), x)

        def loss(m):
            def f(p):
                y, aux = m.apply(p, x, mutable=["losses", "moe_stats"])
                return (y**2).mean() + sum(jax.tree.leaves(aux["losses"]))
            return f

        gr = jax.grad(loss(m_ref))(p)
        ge = jax.grad(loss(m_ep))(p)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5
            ),
            gr, ge,
        )

    def test_dropless_ep_overflow_counted_not_silent(self):
        """A starved budget (moe_ep_buffer far below ep) must COUNT its
        drops in the moe_stats collection and still produce finite
        outputs — never silently diverge."""
        from orion_tpu.parallel.mesh import MeshConfig, make_mesh

        cfg = ModelConfig(
            name="t", d_model=16, n_experts=4, moe_top_k=1,
            dtype="float32", moe_dropless=True, moe_ep_buffer=0.05,
        )
        mesh = make_mesh(MeshConfig(dp=1, ep=2))
        m = MoEMLP(cfg, mesh=mesh)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 16))
        p = m.init(jax.random.PRNGKey(1), x)
        y, aux = m.apply(p, x, mutable=["losses", "moe_stats"])
        assert np.isfinite(np.asarray(y)).all()
        (dropped,) = jax.tree.leaves(aux["moe_stats"])
        assert int(dropped) > 0  # the starved budget really dropped rows

    def test_dropless_ep_trainer_step_parity(self):
        """Full train step on a dp2 x ep2 mesh with dropless MoE == the
        single-device dropless step (loss and updated params)."""
        from orion_tpu.parallel.mesh import MeshConfig
        from orion_tpu.training.data import SyntheticDataset
        from orion_tpu.training.trainer import TrainConfig, Trainer

        model = ModelConfig(
            name="t", vocab_size=64, d_model=32, n_layers=2, n_heads=2,
            max_seq_len=64, dtype="float32", n_experts=4, moe_period=2,
            moe_top_k=2, moe_dropless=True, moe_ep_buffer=2.0,
        )
        mk = lambda mesh: TrainConfig(  # noqa: E731
            model=model, steps=1, batch_size=4, seq_len=16, lr=1e-3,
            warmup_steps=1, mesh=mesh, log_every=1,
        )
        batch = jnp.asarray(SyntheticDataset(64, 16).batch(0, 0, 4))
        t_ref = Trainer(mk(MeshConfig(dp=1)))
        t_ep = Trainer(mk(MeshConfig(dp=2, ep=2)))
        m_ref = t_ref.step(batch)
        m_ep = t_ep.step(batch)
        np.testing.assert_allclose(
            float(m_ep["loss"]), float(m_ref["loss"]), atol=2e-5, rtol=2e-5
        )
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5
            ),
            t_ep.state.params, t_ref.state.params,
        )

    def test_dropless_decode_matches_parallel_argmax(self):
        """The asymmetry dropless kills: parallel forward == recurrent
        decode WITHOUT any capacity bump, even at a cf that would make the
        capacity path's prefill drop tokens."""
        from orion_tpu.generate import SampleConfig, generate
        from orion_tpu.models.transformer import TransformerLM

        cfg = ModelConfig(
            name="t", vocab_size=64, d_model=32, n_layers=2, n_heads=2,
            max_seq_len=64, dtype="float32", n_experts=4, moe_period=2,
            moe_top_k=1, moe_capacity_factor=0.25, moe_dropless=True,
        )
        model = TransformerLM(cfg)
        toks = jax.random.randint(jax.random.PRNGKey(0), (2, 12), 0, 64)
        params = model.init(jax.random.PRNGKey(1), toks)
        n_new = 8
        out = np.asarray(
            generate(model, params, toks, n_new, SampleConfig(0.0))
        )
        # reference: token-by-token argmax through the PARALLEL forward
        cur = np.asarray(toks)
        for _ in range(n_new):
            logits = np.asarray(model.apply(params, jnp.asarray(cur)))
            cur = np.concatenate(
                [cur, logits[:, -1].argmax(-1)[:, None].astype(np.int32)], 1
            )
        np.testing.assert_array_equal(out, cur[:, toks.shape[1]:])

    def test_dropless_trainer_step(self):
        from orion_tpu.training.data import SyntheticDataset
        from orion_tpu.training.trainer import TrainConfig, Trainer

        model = ModelConfig(
            name="t", vocab_size=64, d_model=32, n_layers=2, n_heads=2,
            max_seq_len=64, dtype="float32", n_experts=4, moe_period=2,
            moe_top_k=2, moe_dropless=True,
        )
        cfg = TrainConfig(
            model=model, steps=6, batch_size=4, seq_len=16, lr=3e-3,
            warmup_steps=1, mesh=MeshConfig(dp=1), log_every=1,
        )
        tr = Trainer(cfg)
        batch = jnp.asarray(SyntheticDataset(64, 16).batch(0, 0, 4))
        first = float(tr.step(batch)["loss"])
        last = first
        for _ in range(5):
            last = float(tr.step(batch)["loss"])
        assert np.isfinite(first) and np.isfinite(last)
        assert last < first

    def test_ep_mesh_must_divide_experts(self):
        """E % ep != 0 must fail loudly, not silently replicate the
        [G,E,C,D] dispatch tensor on every device."""
        from jax.sharding import Mesh

        cfg = ModelConfig(
            name="t", d_model=16, n_experts=3, moe_top_k=1, dtype="float32",
            moe_group_size=8,
        )
        devs = np.array(jax.devices()[:2]).reshape(2)
        mesh = Mesh(devs, ("ep",))
        m = MoEMLP(cfg, mesh=mesh)
        x = jnp.zeros((2, 16, 16))
        with pytest.raises(AssertionError, match="divide evenly"):
            m.init(jax.random.PRNGKey(0), x)

    def test_decode_rank2_never_drops(self):
        """Decode input [B, D] uses capacity = B: even if every row routes
        to one expert, none is dropped."""
        cfg = ModelConfig(
            name="t", d_model=16, n_experts=8, moe_top_k=1,
            moe_capacity_factor=0.01, dtype="float32",
        )
        m = MoEMLP(cfg)
        x = jnp.tile(jax.random.normal(jax.random.PRNGKey(0), (1, 16)), (4, 1))
        p = m.init(jax.random.PRNGKey(1), x)
        y = m.apply(p, x)
        assert np.isfinite(np.asarray(y)).all()
        # identical rows route identically -> identical outputs (no drops)
        np.testing.assert_allclose(np.asarray(y[0]), np.asarray(y[3]), atol=1e-6)


def _moe_model(**kw):
    base = dict(
        name="moe_test", vocab_size=64, d_model=32, n_layers=4, n_heads=2,
        max_seq_len=64, dtype="float32", backend="xla",
        n_experts=4, moe_period=2, moe_top_k=1, moe_capacity_factor=4.0,
    )
    base.update(kw)
    return ModelConfig(**base)


class TestMoETraining:
    def test_trainer_step_and_loss_includes_aux(self):
        from orion_tpu.training.data import SyntheticDataset
        from orion_tpu.training.trainer import TrainConfig, Trainer, lm_loss

        model = _moe_model()
        cfg = TrainConfig(
            model=model, steps=2, batch_size=8, seq_len=16, lr=1e-3,
            warmup_steps=1, mesh=MeshConfig(dp=1), log_every=100,
        )
        tr = Trainer(cfg)
        batch = jnp.asarray(SyntheticDataset(64, 16).batch(0, 0, 8))
        m1 = tr.step(batch)
        assert np.isfinite(float(m1["loss"]))
        # aux loss really reaches the total: lm_loss > plain CE
        x, y = batch[:, :-1], batch[:, 1:]
        import optax

        logits = tr.model.apply(tr.state.params, x)
        # state advanced one step; re-eval on current params for both sides
        total = lm_loss(tr.model, tr.state.params, batch)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
        assert float(total) > float(ce)

    @pytest.mark.parametrize(
        "mesh_cfg",
        [
            MeshConfig(dp=2, fsdp=1, tp=1, sp=1, ep=4),
            MeshConfig(dp=2, fsdp=1, tp=2, sp=1, ep=2),
        ],
        ids=["dp2ep4", "dp2tp2ep2"],
    )
    def test_trainer_parity_across_ep_meshes(self, mesh_cfg):
        """Train step on an ep-sharded mesh == single device (GSPMD inserts
        the expert all_to_all; the math must not change)."""
        from orion_tpu.training.data import SyntheticDataset
        from orion_tpu.training.trainer import TrainConfig, Trainer

        model = _moe_model()
        mk = lambda m: TrainConfig(  # noqa: E731
            model=model, steps=2, batch_size=8, seq_len=16, lr=1e-3,
            warmup_steps=1, mesh=m, log_every=100,
        )
        batch = jnp.asarray(SyntheticDataset(64, 16).batch(0, 0, 8))
        t_ref = Trainer(mk(MeshConfig(dp=1)))
        t_ep = Trainer(mk(mesh_cfg))
        m_ref = t_ref.step(batch)
        m_ep = t_ep.step(batch)
        np.testing.assert_allclose(
            float(m_ep["loss"]), float(m_ref["loss"]), atol=1e-5, rtol=1e-5
        )
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5
            ),
            t_ep.state.params,
            t_ref.state.params,
        )
        # the expert stack is genuinely sharded over ep
        spec = t_ep.state_shardings.params["params"]["block_1"]["mlp"][
            "experts_gate"
        ].spec
        assert spec[0] == "ep", spec

    def test_moe_composes_with_sequence_parallel(self):
        """MoE layers under sp: activations enter the MLP token-sharded
        over sp and expert weights are ep-sharded; GSPMD must reshard
        through the group reshape without changing the math."""
        from orion_tpu.training.data import SyntheticDataset
        from orion_tpu.training.trainer import TrainConfig, Trainer

        model = _moe_model(
            layer_types=("linear", "softmax", "linear", "swa"), window=8,
            sequence_parallel=True, moe_group_size=8,
        )
        mk = lambda m: TrainConfig(  # noqa: E731
            model=model, steps=2, batch_size=8, seq_len=32, lr=1e-3,
            warmup_steps=1, mesh=m, log_every=100,
        )
        batch = jnp.asarray(SyntheticDataset(64, 32).batch(0, 0, 8))
        m_ref = Trainer(mk(MeshConfig(dp=1))).step(batch)
        m_sp = Trainer(mk(MeshConfig(dp=2, sp=2, ep=2))).step(batch)
        np.testing.assert_allclose(
            float(m_sp["loss"]), float(m_ref["loss"]), atol=1e-5, rtol=1e-5
        )

    def test_moe_composes_with_pp_and_sp(self):
        """The deepest composition: MoE blocks inside the pipeline body on
        sp-local token shards (dp2 x sp2 x pp2). CE and the z-loss are
        linear in per-shard token stats, so with the load-balance term
        zeroed the parity is exact; the full default loss differs only by
        the documented per-shard-vs-global nonlinearity (checked loose)."""
        from orion_tpu.training.data import SyntheticDataset
        from orion_tpu.training.trainer import TrainConfig, Trainer

        for aux_w, tol in ((0.0, 1e-5), (1e-2, 5e-3)):
            model = _moe_model(
                layer_types=None, sequence_parallel=True, moe_group_size=8,
                moe_aux_weight=aux_w,
            )
            mk = lambda m, nm: TrainConfig(  # noqa: E731
                model=model, steps=1, batch_size=8, seq_len=32, lr=1e-3,
                warmup_steps=1, mesh=m, log_every=100, pp_microbatches=nm,
            )
            batch = jnp.asarray(SyntheticDataset(64, 32).batch(0, 0, 8))
            m_ref = Trainer(mk(MeshConfig(dp=1), 0)).step(batch)
            m_x = Trainer(mk(MeshConfig(dp=2, sp=2, pp=2), 1)).step(batch)
            np.testing.assert_allclose(
                float(m_x["loss"]), float(m_ref["loss"]), atol=tol, rtol=tol
            )

    def test_moe_overfits_synthetic(self):
        """The routed model still learns (loss drops >2x in 60 steps on a
        repeated batch) — routing doesn't break optimization."""
        from orion_tpu.training.data import SyntheticDataset
        from orion_tpu.training.trainer import TrainConfig, Trainer

        model = _moe_model(n_layers=2)
        cfg = TrainConfig(
            model=model, steps=60, batch_size=8, seq_len=16, lr=3e-3,
            warmup_steps=5, mesh=MeshConfig(dp=1), log_every=100,
        )
        tr = Trainer(cfg)
        batch = jnp.asarray(SyntheticDataset(64, 16).batch(0, 0, 8))
        first = float(tr.step(batch)["loss"])
        for _ in range(59):
            last = tr.step(batch)
        assert float(last["loss"]) < first / 2, (first, float(last["loss"]))

    def test_pp_moe_parity_single_microbatch(self):
        """MoE under GPipe at n_micro=1: the aux loss sees the full batch
        exactly like the non-pp forward, so the pp train step must equal
        the single-device step to fp tolerance (stage_group=2 stacks
        (dense, moe) block pairs; experts shard P(pp, ep, ...))."""
        from orion_tpu.training.data import SyntheticDataset
        from orion_tpu.training.trainer import TrainConfig, Trainer

        model = _moe_model(layer_types=None)  # homogeneous linear, 4 layers
        mk = lambda m, nm: TrainConfig(  # noqa: E731
            model=model, steps=2, batch_size=8, seq_len=16, lr=1e-3,
            warmup_steps=1, mesh=m, log_every=100, pp_microbatches=nm,
        )
        batch = jnp.asarray(SyntheticDataset(64, 16).batch(0, 0, 8))
        t_ref = Trainer(mk(MeshConfig(dp=1), 0))
        t_pp = Trainer(mk(MeshConfig(dp=1, pp=2), 1))
        m_ref = t_ref.step(batch)
        m_pp = t_pp.step(batch)
        np.testing.assert_allclose(
            float(m_pp["loss"]), float(m_ref["loss"]), atol=2e-5, rtol=2e-5
        )

    def test_pp_moe_microbatched_trains(self):
        """n_micro>1: per-microbatch aux stats are only statistically
        equivalent to full-batch — check the composed step is finite,
        CE-close to the reference, and actually optimizes."""
        from orion_tpu.training.data import SyntheticDataset
        from orion_tpu.training.trainer import TrainConfig, Trainer

        model = _moe_model(layer_types=None)
        cfg = TrainConfig(
            model=model, steps=30, batch_size=8, seq_len=16, lr=3e-3,
            warmup_steps=5, mesh=MeshConfig(dp=2, pp=2, ep=2),
            log_every=100, pp_microbatches=2,
        )
        tr = Trainer(cfg)
        spec = tr.state_shardings.params["params"]["blocks_stacked"]["sub_1"][
            "mlp"
        ]["experts_gate"].spec
        assert spec[:2] == ("pp", "ep"), spec
        batch = jnp.asarray(SyntheticDataset(64, 16).batch(0, 0, 8))
        first = float(tr.step(batch)["loss"])
        for _ in range(29):
            last = tr.step(batch)
        assert np.isfinite(first)
        assert float(last["loss"]) < first / 1.5, (first, float(last["loss"]))


def test_moe_checkpoint_restores_across_ep_meshes(tmp_path):
    """Expert resharding on restore: an MoE checkpoint written on a dp-only
    mesh restores onto an ep-sharded mesh (orbax reshards the stacked
    expert weights onto ep) and continues to the same final params within
    fp tolerance."""
    from orion_tpu.training.checkpoint import Checkpointer
    from orion_tpu.training.data import SyntheticDataset
    from orion_tpu.training.trainer import TrainConfig, Trainer

    model = _moe_model()
    mk = lambda m: TrainConfig(  # noqa: E731
        model=model, steps=4, batch_size=8, seq_len=16, lr=1e-3,
        warmup_steps=1, mesh=m, log_every=100,
        ckpt_dir=str(tmp_path / "ck"), ckpt_every=2,
    )
    ds = SyntheticDataset(model.vocab_size, 16)
    it = lambda start=0: iter(  # noqa: E731
        jnp.asarray(ds.batch(0, s, 8)) for s in range(start + 1, 100)
    )

    tr_a = Trainer(mk(MeshConfig(dp=1)))
    ck_a = Checkpointer(str(tmp_path / "ck"), save_every=2, async_save=False)
    tr_a.train(it(), ckpt=ck_a)  # saves at steps 2 and 4
    final_a = jax.tree.map(np.asarray, tr_a.state.params)
    ck_a.close()

    tr_b = Trainer(mk(MeshConfig(dp=2, ep=2)))
    ck_b = Checkpointer(str(tmp_path / "ck"), save_every=10_000, async_save=False)
    start = tr_b.restore(ck_b, step=2)
    assert start == 2
    spec = tr_b.state_shardings.params["params"]["block_1"]["mlp"][
        "experts_gate"
    ].spec
    assert spec[0] == "ep", spec
    tr_b.train(it(start))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            a, np.asarray(b), atol=2e-5, rtol=2e-5
        ),
        final_a,
        tr_b.state.params,
    )
    ck_b.close()


def test_classifier_honors_moe_config():
    """LRAClassifier builds MoE blocks from the same config fields as
    TransformerLM (and the aux loss is sown for train_lra's loss)."""
    from orion_tpu.models.classifier import LRAClassifier

    cfg = ModelConfig(
        name="lra_moe", vocab_size=32, d_model=32, n_layers=2, n_heads=2,
        max_seq_len=32, dtype="float32", mlp="gelu", norm="layernorm",
        n_classes=4, n_experts=2, moe_period=2, backend="xla",
    )
    m = LRAClassifier(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 32)
    mask = jnp.ones((2, 16), bool)
    p = m.init(jax.random.PRNGKey(1), toks, mask)
    assert "router" in p["params"]["block_1"]["mlp"]
    logits, v = m.apply(p, toks, mask, mutable="losses")
    assert logits.shape == (2, 4)
    assert len(jax.tree.leaves(v.get("losses", {}))) == 1


class TestMoEDecode:
    def test_greedy_decode_matches_parallel_argmax(self):
        """The decisive decode invariant, on a hybrid MoE model: recurrent
        decode through MoE blocks == parallel forward argmax. Capacity
        factor is high so the parallel path drops nothing either."""
        from orion_tpu.generate import SampleConfig, generate

        cfg = _moe_model(
            n_layers=4, layer_types=("linear", "softmax", "linear", "swa"),
            window=8, moe_capacity_factor=8.0,
        )
        from orion_tpu.models.transformer import TransformerLM

        model = TransformerLM(cfg)
        rng = jax.random.PRNGKey(0)
        prompt = jax.random.randint(rng, (2, 12), 0, cfg.vocab_size)
        params = model.init(jax.random.PRNGKey(1), prompt)

        n_new = 6
        out = generate(
            model, params, prompt, max_new_tokens=n_new,
            sample=SampleConfig(temperature=0.0),
        )
        assert out.shape == (2, n_new)
        # teacher-forced parallel re-derivation of each generated token
        seq = prompt
        for i in range(n_new):
            logits = model.apply(params, seq)
            want = jnp.argmax(logits[:, -1], axis=-1)
            np.testing.assert_array_equal(np.asarray(want), np.asarray(out[:, i]))
            seq = jnp.concatenate([seq, want[:, None]], axis=1)

    def test_moe_checkpoint_serves_via_cli(self, tmp_path, capsys):
        """Train-then-serve roundtrip for an MoE model through the CLI:
        checkpoint save, load_params, capacity auto-bump, decode, print."""
        from orion_tpu.generate import main
        from orion_tpu.training.checkpoint import Checkpointer
        from orion_tpu.training.data import SyntheticDataset
        from orion_tpu.training.trainer import TrainConfig, Trainer

        from orion_tpu.models.configs import get_config

        model = get_config(
            "tiny", n_experts=4, moe_period=2, backend="xla",
        )
        cfg = TrainConfig(
            model=model, steps=2, batch_size=2, seq_len=32,
            lr=1e-3, warmup_steps=1, log_every=100,
            ckpt_dir=str(tmp_path / "ck"), ckpt_every=2, mesh=MeshConfig(dp=1),
        )
        trainer = Trainer(cfg)
        ds = SyntheticDataset(model.vocab_size, cfg.seq_len)
        ckpt = Checkpointer(cfg.ckpt_dir, save_every=2, async_save=False)
        for step in (1, 2):
            trainer.step(jnp.asarray(ds.batch(0, step, 2)))
            ckpt.maybe_save(step, trainer.state)
        ckpt.close()

        rc = main([
            "--config", "tiny", "--ckpt-dir", cfg.ckpt_dir,
            "--prompt", "ab", "--max-new-tokens", "4", "--temperature", "0.0",
            "--set", "n_experts=4", "--set", "moe_period=2",
            "--set", "backend=xla",
        ])
        assert rc == 0
        out = capsys.readouterr().out
        assert out.startswith("ab") and len(out.strip()) >= 2

    def test_generate_auto_bumps_capacity_for_serving(self):
        """A model trained with a dropping capacity factor is served in the
        no-drop regime: generate()'s output must match the parallel argmax
        of the capacity-raised model (and params are shared unchanged)."""
        import dataclasses

        from orion_tpu.generate import SampleConfig, generate
        from orion_tpu.models.transformer import TransformerLM

        cfg = _moe_model(n_layers=2, moe_capacity_factor=1.0)
        model = TransformerLM(cfg)
        prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, cfg.vocab_size)
        params = model.init(jax.random.PRNGKey(3), prompt)
        out = generate(
            model, params, prompt, max_new_tokens=4,
            sample=SampleConfig(temperature=0.0),
        )
        nodrop = TransformerLM(
            dataclasses.replace(cfg, moe_capacity_factor=float(cfg.n_experts))
        )
        seq = prompt
        for i in range(4):
            want = jnp.argmax(nodrop.apply(params, seq)[:, -1], axis=-1)
            np.testing.assert_array_equal(np.asarray(want), np.asarray(out[:, i]))
            seq = jnp.concatenate([seq, want[:, None]], axis=1)


def test_bench_active_param_accounting(monkeypatch):
    """The denominator of the ledger's ``mfu_6n``
    (``benchmark/kinds/train.py::active_params``, loaded by file path as
    ``kinds/train_ref.py`` loads it): expert stacks count only their routed
    share (top_k/E); a dense model counts every parameter."""
    import importlib.util
    import os

    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM

    bench_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"
    )
    monkeypatch.syspath_prepend(bench_dir)  # kinds/train.py imports harness
    spec = importlib.util.spec_from_file_location(
        "benchmark_kinds_train_for_test",
        os.path.join(bench_dir, "kinds", "train.py"),
    )
    kind = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kind)

    def count(cfg):
        params = jax.eval_shape(
            TransformerLM(cfg).init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )
        total = sum(x.size for x in jax.tree.leaves(params))
        expert = sum(
            x.size for p, x in jax.tree_util.tree_leaves_with_path(params)
            if "experts_" in jax.tree_util.keystr(p)
        )
        return kind.active_params(cfg, params), total, expert

    cfg = _moe_model(n_layers=2)
    active, total, expert = count(cfg)
    k, e = cfg.moe_top_k, cfg.n_experts
    assert expert > 0 and active == total - expert + expert * k / e
    assert 0 < active < total
    active, total, expert = count(get_config("tiny"))
    assert expert == 0 and active == total


@pytest.mark.parametrize(
    "aux_w,tol", [(0.0, 2e-5), (1e-2, 5e-3)], ids=["exact_no_aux", "stat_default"]
)
def test_moe_grad_accumulation_parity(aux_w, tol):
    """accum_steps=2 vs 1 on an MoE model: exact with the load-balance
    term zeroed (CE + z-loss are linear in per-microbatch token stats);
    only statistically equivalent with it on (same nonlinearity caveat as
    GPipe microbatching)."""
    import dataclasses as dc

    from orion_tpu.training.data import SyntheticDataset
    from orion_tpu.training.trainer import TrainConfig, Trainer

    model = dc.replace(_moe_model(n_layers=2), moe_aux_weight=aux_w)
    mk = lambda acc: TrainConfig(  # noqa: E731
        model=model, steps=1, batch_size=8, seq_len=16, lr=1e-3,
        warmup_steps=1, accum_steps=acc, mesh=MeshConfig(dp=1), log_every=100,
    )
    batch = jnp.asarray(SyntheticDataset(64, 16).batch(0, 0, 8))
    m1 = Trainer(mk(1)).step(batch)
    m2 = Trainer(mk(2)).step(batch)
    np.testing.assert_allclose(
        float(m2["loss"]), float(m1["loss"]), atol=tol, rtol=tol
    )


class TestGmm:
    """Grouped expert matmul kernel (ops/pallas/gmm.py, interpret mode)."""

    def _ref(self, x, w, seg):
        te = np.repeat(np.arange(len(seg)), np.asarray(seg))
        te = np.pad(te, (0, x.shape[0] - len(te)), constant_values=len(seg) - 1)
        return np.stack([
            np.asarray(x[i], np.float32) @ np.asarray(w[te[i]], np.float32)
            for i in range(x.shape[0])
        ])

    def test_gmm_forward_matches_per_row(self):
        from orion_tpu.ops.pallas.gmm import gmm

        tm, e, d, h = 8, 3, 16, 24
        seg = jnp.asarray([16, 0, 24], jnp.int32)  # tile-aligned, one empty
        m = 48
        x = jax.random.normal(jax.random.PRNGKey(0), (m, d))
        w = jax.random.normal(jax.random.PRNGKey(1), (e, d, h)) * 0.1
        got = gmm(x, w, seg, tm, 16, True)[:40]  # the tile past the segments is not written
        np.testing.assert_allclose(
            np.asarray(got), self._ref(x, w, seg)[:40], atol=1e-5, rtol=1e-5
        )

    def test_gmm_grads_match_autodiff_reference(self):
        from orion_tpu.ops.pallas.gmm import gmm, tile_expert_table

        tm, e, d, h = 8, 3, 16, 24
        seg = jnp.asarray([16, 8, 24], jnp.int32)
        m = 48
        x = jax.random.normal(jax.random.PRNGKey(2), (m, d))
        w = jax.random.normal(jax.random.PRNGKey(3), (e, d, h)) * 0.1
        te = tile_expert_table(seg, m // tm, tm)
        row_e = jnp.repeat(te, tm)

        def ref(x, w):
            return (jnp.einsum("md,mdh->mh", x, w[row_e]) ** 2).sum()

        def got(x, w):
            return (gmm(x, w, seg, tm, 16, True) ** 2).sum()

        gr = jax.grad(ref, argnums=(0, 1))(x, w)
        gg = jax.grad(got, argnums=(0, 1))(x, w)
        for a, b in zip(gg, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4
            )

    def test_gmm_zero_count_expert_gets_zero_dw(self):
        from orion_tpu.ops.pallas.gmm import gmm

        tm = 8
        seg = jnp.asarray([16, 0, 32], jnp.int32)
        x = jax.random.normal(jax.random.PRNGKey(4), (48, 16))
        w = jax.random.normal(jax.random.PRNGKey(5), (3, 16, 24)) * 0.1
        dw = jax.grad(lambda w: gmm(x, w, seg, tm, 16, True).sum())(w)
        assert np.abs(np.asarray(dw[1])).max() == 0.0

    @pytest.mark.parametrize("form", ["blocked", "whole"])
    @pytest.mark.parametrize("h", [1024, 1792, 768])
    @pytest.mark.parametrize("split", ["skewed", "even", "gaps"])
    def test_gmm_live_matches_per_row(self, split, h, form):
        """``gmm_live`` against a plain per-row product: experts of 0, 1, 3
        and 10 tiles in one call (``skewed``), four tiles each, or experts
        without a row between and after those with some (``gaps``: the next
        LIVE expert's weights are what the resident form fetches ahead);
        widths that blocks of 512 divide (1,024), that take a smaller block
        (1,792: 256; 768: 384), the output blocked or the whole width one
        block (an expert's weights then stay put over its consecutive tiles). The
        buffer is longer than the live rows and its tail is NaN: the rows past
        ``live`` are never visited, and the caller's to mask. Both forms are
        one product over the whole contraction, so they give the same bits
        (small whole numbers here, which fp32 sums exactly in any order:
        XLA:CPU picks another matmul routine at another block width)."""
        from orion_tpu.ops.pallas.gmm import gmm_live

        tm, d = 8, 32
        tiles = {"skewed": [0, 1, 3, 10], "even": [4, 4, 4, 4], "gaps": [2, 0, 0, 5, 0]}[split]
        seg = jnp.asarray([t * tm for t in tiles], jnp.int32)
        rows = int(seg.sum())
        x = jnp.round(3 * jax.random.normal(jax.random.PRNGKey(6), (rows + 3 * tm, d)))
        x = x.at[rows:].set(jnp.nan)
        w = jnp.round(3 * jax.random.normal(jax.random.PRNGKey(7), (len(tiles), d, h)))
        blocked = gmm_live(x, w, seg, tm, 512, True)[:rows]
        got = blocked if form == "blocked" else gmm_live(x, w, seg, tm, None, True)[:rows]
        assert got.shape == (rows, h) and bool(jnp.isfinite(got).all())
        np.testing.assert_array_equal(np.asarray(got), self._ref(x[:rows], w, seg))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(blocked))

    # tiles an expert of a buffer of 20: two experts without a row and 30% of
    # the tiles past the last segment; or no segment at all
    UNDERFULL = {"skewed": [5, 0, 1, 0, 8], "none": [0, 0, 0, 0, 0]}

    def _underfull(self, split, form, monkeypatch):
        """``gmm``'s forward, ``dx`` and ``dw`` over a buffer the segments do
        not fill, in either form (an expert's whole matrix a block; or, with
        the VMEM budget for that taken away, column blocks and a skipped
        tail): a function of (x, dy) that returns the live rows of ``y`` and
        ``dx`` and ``dw``, what the ragged form gives for them, and the
        number of live rows. The widths differ by form, so that no jitted
        entry of one is found for the other."""
        from orion_tpu.ops.pallas import gmm as G

        tm, h, d = 8, 24, {"whole": 32, "blocked": 48}[form]
        if form == "blocked":
            monkeypatch.setattr(G, "_LIVE_WHOLE_WIDTH_BYTES", 0)
        seg = jnp.asarray([t * tm for t in self.UNDERFULL[split]], jnp.int32)
        rows, m = int(seg.sum()), 20 * tm
        keys = jax.random.split(jax.random.PRNGKey(11), 3)
        x, dy = jax.random.normal(keys[0], (m, d)), jax.random.normal(keys[1], (m, h))
        w = 0.1 * jax.random.normal(keys[2], (len(seg), d, h))
        product = lambda x, w: G.gmm(x, w, seg, tm, 16, True)  # noqa: E731

        def run(x, dy):
            y, vjp = jax.vjp(product, x, w)
            dx, dw = vjp(dy)
            return y[:rows], dx[:rows], dw

        # the form is the one asked for: the tiles lead the forward's grid with
        # the table its one scalar operand, and are dw's whole grid; or they
        # are the last dimension and the live count a second scalar operand
        grids = {e.params["name"]: (len(e.params["grid_mapping"].grid),
                                    e.params["grid_mapping"].num_index_operands)
                 for e in jax.make_jaxpr(run)(x, dy).jaxpr.eqns if e.primitive.name == "pallas_call"}
        assert grids == ({"gmm_fwd": (2, 1), "gmm_dw": (1, 2)} if form == "whole"
                         else {"gmm_fwd": (2, 2), "gmm_dw": (3, 2)})
        if rows:
            y, vjp = jax.vjp(lambda x, w: jax.lax.ragged_dot(x, w, seg), x[:rows], w)
            want = (y, *vjp(dy[:rows]))
        else:
            want = (jnp.zeros((0, h)), jnp.zeros((0, d)), jnp.zeros_like(w))
        return run, (x, dy), want, rows

    @pytest.mark.parametrize("form", ["whole", "blocked"])
    @pytest.mark.parametrize("split", sorted(UNDERFULL))
    def test_gmm_underfull_buffer_matches_the_ragged_form(self, split, form, monkeypatch):
        run, args, want, _ = self._underfull(split, form, monkeypatch)
        for a, b in zip(run(*args), want):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("form", ["whole", "blocked"])
    @pytest.mark.parametrize("split", sorted(UNDERFULL))
    def test_gmm_never_reads_the_rows_past_the_last_segment(self, split, form, monkeypatch):
        """NaN in every row of ``x`` and of ``dy`` past ``sum(group_sizes)``:
        the live rows of ``y`` and ``dx`` and the whole of ``dw`` are bit for
        bit what they were, and finite (a tail multiplied into ``dw``, or an
        expert's block left unwritten and unmasked, would show)."""
        run, (x, dy), _, rows = self._underfull(split, form, monkeypatch)
        clean = run(x, dy)
        poisoned = run(x.at[rows:].set(jnp.nan), dy.at[rows:].set(jnp.nan))
        for a, b in zip(poisoned, clean):
            assert bool(jnp.isfinite(a).all())
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_dropless_gmm_matches_ragged_path(self, monkeypatch):
        """The gmm-backed dropless MoE layer == the ragged_dot path,
        values AND grads (same params, same router). The input is above
        the 1024-row kernel threshold AND the kernel entry is spied on so
        the test fails loudly if the gmm branch is ever not taken."""
        import orion_tpu.ops.pallas.gmm as gmm_mod

        calls = []
        real_gmm = gmm_mod.gmm
        monkeypatch.setattr(
            gmm_mod, "gmm",
            lambda *a, **kw: (calls.append(1), real_gmm(*a, **kw))[1],
        )
        cfg = ModelConfig(
            name="t", d_model=128, n_experts=4, moe_top_k=2,
            dtype="float32", moe_dropless=True, backend="pallas_interpret",
        )
        cfg_x = dataclasses.replace(cfg, backend="xla")
        # 4*256*k=2 -> 2048 routed rows, above the gmm threshold
        x = jax.random.normal(jax.random.PRNGKey(6), (4, 256, 128))
        m_ref = MoEMLP(cfg_x)
        p = m_ref.init(jax.random.PRNGKey(1), x)
        m_gmm = MoEMLP(cfg)
        jax.tree.map(  # identical param trees
            lambda a, b: None, p, m_gmm.init(jax.random.PRNGKey(2), x)
        )

        def loss(m):
            return lambda p: (m.apply(p, x) ** 2).mean()

        np.testing.assert_allclose(
            np.asarray(m_gmm.apply(p, x)), np.asarray(m_ref.apply(p, x)),
            atol=2e-5, rtol=2e-5,
        )
        gr = jax.grad(loss(m_ref))(p)
        gg = jax.grad(loss(m_gmm))(p)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=3e-4, rtol=3e-4
            ),
            gg, gr,
        )
        assert calls, "the gmm branch was never taken — threshold changed?"


# -- the served grouped product: its blocks from shapes, its visits counted -----

# the six calls of the four committed mixture cells (PERF.md section 6, PR 56):
# preset, the call's tokens (a group of four 1,024-row pieces, a lone piece, a
# step's slots) -> (row tile, output block; None = each product's whole width)
_SERVE_CALLS = {
    "trinity_mini-group-of-4-pieces": ("trinity_mini", 4096, (128, None)),
    "trinity_mini-step": ("trinity_mini", 64, (16, 512)),
    "lfm2_8b_a1b-piece-of-512": ("lfm2_8b_a1b", 512, (128, 512)),
    "lfm2_8b_a1b-step": ("lfm2_8b_a1b", 128, (32, 512)),
    "keye_vl_2_0_30b_a3b-piece-of-1024": ("keye_vl_2_0_30b_a3b", 1024, (128, 512)),
    "openpangu_ultra_moe_718b-piece-of-1024": ("openpangu_ultra_moe_718b", 1024, (128, 512)),
}


@pytest.mark.parametrize("call", sorted(_SERVE_CALLS))
def test_served_blocks_follow_from_the_calls_shapes(call):
    """``serve_tiles`` at the committed cells' calls: the whole-width block
    is asked for by ONE program family, ``trinity_mini``'s group of four
    pieces (256 rows an expert on an even router), and by nothing a preset
    says: a later preset, or a change of the rule, that moves another cell's
    block shows here. What the engaged kernel holds in VMEM is under its
    limit; ``openpangu_ultra_moe_718b``'s ``[7680, 2048]`` experts would not
    be, however many rows a call gave them."""
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.moe import serve_tiles
    from orion_tpu.ops.pallas import gmm

    preset, tokens, want = _SERVE_CALLS[call]
    cfg = get_config(preset)
    r, d, h = cfg.resolved_router_width, cfg.d_model, cfg.resolved_moe_hidden
    shapes = (r, cfg.moe_step_tile, d, h, 2)
    assert serve_tiles(tokens * cfg.moe_top_k, *shapes) == want
    if want[1] is None:
        assert (d, h) == (2048, 1024)
        blocks = 2 * 2 * (d * h + want[0] * d + want[0] * h) + 4 * want[0] * max(d, h)
        assert 2 * 2 * d * h == 8 << 20 <= gmm._LIVE_WHOLE_WIDTH_BYTES
        assert blocks < gmm._LIVE_VMEM_BYTES // 4
    if preset == "openpangu_ultra_moe_718b":
        assert serve_tiles(64 * tokens * cfg.moe_top_k, *shapes) == (128, 512)
        assert not gmm.live_whole_width_fits(d, h, 2)


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_served_layer_counts_its_tiles_and_experts(backend):
    """A held layer handed ``live`` sows ``tiles_live`` and ``experts_live``
    (from the per-expert counts it had: ``sum(ceil(counts / tile))`` and
    ``sum(counts > 0)``, the tile the served product's on either backend),
    ``stats_vector`` carries them in ``STAT_NAMES``' order and the server's
    counters name them; a layer that is not served (training) sows neither."""
    from orion_tpu.models.moe import STAT_NAMES, serve_tiles, stats_vector
    from orion_tpu.serving.server import _MOE_KEYS

    cfg = ModelConfig(
        name="t", d_model=32, n_experts=8, moe_top_k=2, moe_hidden=128, mlp="swiglu",
        moe_dropless=True, moe_step_tile=4, dtype="float32", param_dtype="float32",
        backend=backend,
    )
    layer = MoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 96, 32))
    params = jax.jit(layer.init)(jax.random.PRNGKey(9), x)
    live = jnp.arange(96)[None, :] < 90
    y, sown = jax.jit(
        lambda p, x, live: layer.apply(p, x, live, mutable=["moe_stats"])
    )(params, x, live)
    vec = np.asarray(stats_vector(sown["moe_stats"]))
    stats = dict(zip(STAT_NAMES, vec))
    # 192 pairs over 8 experts is past the step tile's 32: tiles of 128 rows,
    # and 24 rows an expert are under one, so the block stays 512
    assert serve_tiles(192, 8, 4, 32, 128, 4) == (128, 512)
    assert stats["rows_routed"] == stats["rows_held"] == 180
    assert 1 <= stats["experts_live"] <= 8 and stats["tiles_live"] == stats["experts_live"]
    assert STAT_NAMES[4:] == ("tiles_live", "experts_live")
    assert _MOE_KEYS[4:] == ("moe_tiles_live", "moe_experts_live") and len(_MOE_KEYS) == len(vec)
    # a step: 8 rows x top-2 = 16 pairs on tiles of 4
    ys, sown = layer.apply(params, x[:, :8], live[:, :8], mutable=["moe_stats"])
    logits = np.asarray(x[0, :8]) @ np.asarray(params["params"]["router"]["kernel"])
    counts = np.bincount(np.argsort(-logits, axis=1)[:, :2].reshape(-1), minlength=8)
    step = dict(zip(STAT_NAMES, np.asarray(stats_vector(sown["moe_stats"]))))
    assert step["tiles_live"] == int(np.ceil(counts / 4).sum())
    assert step["experts_live"] == int((counts > 0).sum())
    _, sown = layer.apply(params, x, mutable=["losses", "moe_stats"])
    assert "moe_stats" not in sown
    # 512 rows: 128 an expert on an even router, so the layer asks for the
    # whole width held resident, and gives what the plain form gives
    big = jax.random.normal(jax.random.PRNGKey(10), (1, 512, 32))
    assert serve_tiles(1024, 8, 4, 32, 128, 4) == (128, None)
    served = lambda cfg: jax.jit(lambda p, x: MoEMLP(cfg).apply(  # noqa: E731
        p, x, jnp.ones(x.shape[:2], bool), mutable=["moe_stats"]))(params, big)
    (got, sown), (want, _) = served(cfg), served(dataclasses.replace(cfg, backend="xla"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    many = dict(zip(STAT_NAMES, np.asarray(stats_vector(sown["moe_stats"]))))
    assert many["tiles_live"] > many["experts_live"] == 8


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("buffer", [1.5, 0.5])
def test_training_held_layer_counts_the_tiles_its_product_visits(backend, buffer):
    """A held layer that is NOT served sows ``tiles_live`` too: the row tiles
    of 128 its grouped product visits (on either backend), over the rows that
    are inside the budget (``buffer`` 0.5 drops some: they have no tile);
    against the buffer's tiles it is the share of the grid that holds a row.
    ``experts_live`` stays the served layers' alone."""
    from orion_tpu.models.moe import STAT_NAMES, stats_vector

    cfg = ModelConfig(
        name="t", d_model=32, n_experts=4, moe_router_width=16, moe_top_k=2, moe_hidden=64,
        moe_dropless=True, moe_ep_buffer=buffer, dtype="float32", param_dtype="float32",
        backend=backend,
    )
    layer = MoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(8), (4, 1024, 32))
    params = jax.jit(layer.init)(jax.random.PRNGKey(9), x)
    _, sown = jax.jit(lambda p, x: layer.apply(p, x, mutable=["losses", "moe_stats"]))(params, x)
    stats = dict(zip(STAT_NAMES, np.asarray(stats_vector(sown["moe_stats"]))))
    logits = np.asarray(x.reshape(-1, 32)) @ np.asarray(params["params"]["router"]["kernel"])
    counts = np.bincount(np.argsort(-logits, axis=1)[:, :2].reshape(-1), minlength=16)[:4]
    budget = int(np.ceil(buffer * 8192 * 4 / 16))
    kept = np.diff(np.minimum(np.cumsum(counts), budget), prepend=0)
    assert stats["rows_held"] == counts.sum()
    assert (stats["dropless_overflow"] > 0) == (buffer == 0.5) == (kept.sum() < counts.sum())
    assert stats["tiles_live"] == int(np.ceil(kept / 128).sum()) > 0
    assert stats["tiles_live"] <= -(-(budget + 4 * 128) // 128)  # the buffer's tiles
    assert stats["experts_live"] == 0


def test_moe_overflow_metric_surfaces_in_trainer():
    """ADVICE r4 (medium): the dropless-ep overflow counter must have a
    consumer. Ample budget -> metric present and 0; starved budget ->
    Trainer build warns (buffer < ep) and the step metric counts drops."""
    from orion_tpu.parallel.mesh import MeshConfig
    from orion_tpu.training.data import SyntheticDataset
    from orion_tpu.training.trainer import TrainConfig, Trainer

    def mk(buffer):
        model = ModelConfig(
            name="t", vocab_size=64, d_model=32, n_layers=2, n_heads=2,
            max_seq_len=64, dtype="float32", n_experts=4, moe_period=2,
            moe_top_k=2, moe_dropless=True, moe_ep_buffer=buffer,
        )
        return TrainConfig(
            model=model, steps=1, batch_size=4, seq_len=16, lr=1e-3,
            warmup_steps=1, mesh=MeshConfig(dp=2, ep=2), log_every=1,
        )

    batch = jnp.asarray(SyntheticDataset(64, 16).batch(0, 0, 4))
    t = Trainer(mk(2.0))  # buffer == ep: mathematically dropless
    m = t.step(batch)
    assert "moe_overflow" in m and int(m["moe_overflow"]) == 0

    with pytest.warns(UserWarning, match="moe_ep_buffer"):
        t2 = Trainer(mk(0.05))
    m2 = t2.step(batch)
    assert int(m2["moe_overflow"]) > 0  # starved budget drops are visible


def test_quantize_for_decode_rejects_dropless_ep_at_setup():
    """ADVICE r4 (low): the quant x dropless x ep>1 combination fails as a
    config-time ValueError with remediation, not an AssertionError deep in
    jit tracing (the in-module assert remains as a backstop)."""
    from orion_tpu.generate import quantize_for_decode
    from orion_tpu.models.transformer import TransformerLM
    from orion_tpu.parallel.mesh import MeshConfig, make_mesh

    cfg = ModelConfig(
        name="t", vocab_size=64, d_model=32, n_layers=2, n_heads=2,
        max_seq_len=64, dtype="float32", n_experts=4, moe_period=2,
        moe_dropless=True,
    )
    mesh = make_mesh(MeshConfig(dp=1, ep=2))
    model = TransformerLM(cfg, mesh=mesh)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="capacity path"):
        quantize_for_decode(model, params, mode="int8")


class TestDroplessEpGmm:
    """VERDICT r4 #3a: the grouped-matmul kernel INSIDE the (fully-manual)
    ep region — the scalable dropless form no longer pays the ragged_dot
    price. Interpret-mode kernels here; the real-Mosaic compile is the
    fsdp x ep topology-AOT artifact + the driver dryrun line."""

    KW = dict(name="t", d_model=32, n_experts=4, dtype="float32",
              moe_dropless=True, moe_ep_buffer=2.0)

    def _models(self, mesh, k=2):
        cfg_i = ModelConfig(backend="pallas_interpret", moe_top_k=k, **self.KW)
        cfg_x = ModelConfig(backend="xla", moe_top_k=k, **self.KW)
        return MoEMLP(cfg_x), MoEMLP(cfg_i, mesh=mesh), MoEMLP(cfg_x, mesh=mesh)

    @pytest.mark.parametrize("k", [1, 2])
    def test_forward_matches_single_host_and_ragged(self, k):
        from orion_tpu.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(dp=2, ep=2))
        # n_loc * k >= 1024 satisfies the gmm gate on the dp2 mesh
        x = jax.random.normal(jax.random.PRNGKey(3), (4, 512 // k, 32))
        m_ref, m_gmm, m_rag = self._models(mesh, k)
        p = m_ref.init(jax.random.PRNGKey(1), jnp.zeros((2, 16, 32)))
        y_ref = jax.jit(m_ref.apply)(p, x)
        y_gmm = jax.jit(m_gmm.apply)(p, x)
        y_rag = jax.jit(m_rag.apply)(p, x)
        np.testing.assert_allclose(
            np.asarray(y_gmm), np.asarray(y_ref), atol=2e-5, rtol=2e-5
        )
        np.testing.assert_allclose(
            np.asarray(y_gmm), np.asarray(y_rag), atol=2e-5, rtol=2e-5
        )

    @pytest.mark.slow
    def test_grads_match_single_host(self):
        from orion_tpu.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(dp=2, ep=2))
        x = jax.random.normal(jax.random.PRNGKey(5), (4, 256, 32))
        m_ref, m_gmm, _ = self._models(mesh)
        p = m_ref.init(jax.random.PRNGKey(1), jnp.zeros((2, 16, 32)))

        def loss(m):
            def f(p):
                y, aux = m.apply(p, x, mutable=["losses", "moe_stats"])
                return (y**2).mean() + sum(jax.tree.leaves(aux["losses"]))
            return f

        gr = jax.jit(jax.grad(loss(m_ref)))(p)
        gg = jax.jit(jax.grad(loss(m_gmm)))(p)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5
            ),
            gr, gg,
        )

    def test_starved_budget_counts_drops(self):
        """The budget semantics carry over: a starved moe_ep_buffer drops
        past the per-shard budget, COUNTED in moe_stats, finite outputs."""
        from orion_tpu.parallel.mesh import MeshConfig, make_mesh

        kw = dict(self.KW, moe_ep_buffer=0.05)
        cfg = ModelConfig(backend="pallas_interpret", moe_top_k=1, **kw)
        mesh = make_mesh(MeshConfig(dp=1, ep=2))
        m = MoEMLP(cfg, mesh=mesh)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 512, 32))
        p = m.init(jax.random.PRNGKey(1), jnp.zeros((2, 16, 32)))
        y, aux = jax.jit(
            lambda p, x: m.apply(p, x, mutable=["losses", "moe_stats"])
        )(p, x)
        assert np.isfinite(np.asarray(y)).all()
        (dropped,) = jax.tree.leaves(aux["moe_stats"])
        assert int(dropped) > 0

    def test_decode_rows_keep_ragged(self):
        """Tiny-m calls (decode) must NOT take the gmm path — the GEMV-
        sized scatter would be all padding; gate falls through to the
        ragged dropless-ep body."""
        from orion_tpu.parallel.mesh import MeshConfig, make_mesh

        cfg = ModelConfig(backend="pallas_interpret", moe_top_k=1, **self.KW)
        mesh = make_mesh(MeshConfig(dp=1, ep=2))
        m = MoEMLP(cfg, mesh=mesh)
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 32))  # decode rank-2
        p = m.init(jax.random.PRNGKey(1), jnp.zeros((2, 16, 32)))
        y = jax.jit(m.apply)(p, x)  # would fail inside gmm if gated wrong
        assert np.isfinite(np.asarray(y)).all()


class TestDroplessDenseMeshGmm:
    """VERDICT r4 #3b: gmm under GSPMD dense meshes (ep == 1, multi-
    device) — the ep-region body degenerates to a per-data-shard counting
    sort + gmm with the budget pinned to m_loc, so the form is EXACT
    dropless with zero overflow by construction. Interpret-mode kernels
    here; the real-Mosaic compile is the dense-mesh topology-AOT artifact
    + the driver dryrun line."""

    KW = dict(name="t", d_model=32, n_experts=4, dtype="float32",
              moe_dropless=True)

    def _models(self, mesh, k=2):
        cfg_i = ModelConfig(backend="pallas_interpret", moe_top_k=k, **self.KW)
        cfg_x = ModelConfig(backend="xla", moe_top_k=k, **self.KW)
        return MoEMLP(cfg_x), MoEMLP(cfg_i, mesh=mesh), MoEMLP(cfg_x, mesh=mesh)

    @pytest.mark.parametrize("mesh_kw", [dict(dp=4), dict(dp=2, fsdp=2)])
    def test_forward_matches_single_host_and_ragged(self, mesh_kw):
        from orion_tpu.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(**mesh_kw))
        # 4 shards x 512 local rows x k=2 = 1024 clears the gmm gate
        x = jax.random.normal(jax.random.PRNGKey(3), (4, 512, 32))
        m_ref, m_gmm, m_rag = self._models(mesh)
        p = m_ref.init(jax.random.PRNGKey(1), jnp.zeros((2, 16, 32)))
        y_ref = jax.jit(m_ref.apply)(p, x)
        y_gmm = jax.jit(m_gmm.apply)(p, x)
        y_rag = jax.jit(m_rag.apply)(p, x)
        np.testing.assert_allclose(
            np.asarray(y_gmm), np.asarray(y_ref), atol=2e-5, rtol=2e-5
        )
        np.testing.assert_allclose(
            np.asarray(y_gmm), np.asarray(y_rag), atol=2e-5, rtol=2e-5
        )

    def test_exact_dropless_zero_overflow(self):
        """ep == 1 pins budget to m_loc: the overflow counter must be
        exactly zero even with a starved moe_ep_buffer (the knob only
        applies to cross-ep budgets)."""
        from orion_tpu.parallel.mesh import MeshConfig, make_mesh

        cfg = ModelConfig(
            backend="pallas_interpret", moe_top_k=2, moe_ep_buffer=0.05,
            **self.KW,
        )
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2))
        m = MoEMLP(cfg, mesh=mesh)
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 512, 32))
        p = m.init(jax.random.PRNGKey(1), jnp.zeros((2, 16, 32)))
        y, aux = jax.jit(
            lambda p, x: m.apply(p, x, mutable=["losses", "moe_stats"])
        )(p, x)
        assert np.isfinite(np.asarray(y)).all()
        (dropped,) = jax.tree.leaves(aux["moe_stats"])
        assert int(dropped) == 0

    @pytest.mark.slow
    def test_grads_match_single_host(self):
        from orion_tpu.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(dp=2, fsdp=2))
        x = jax.random.normal(jax.random.PRNGKey(5), (4, 512, 32))
        m_ref, m_gmm, _ = self._models(mesh)
        p = m_ref.init(jax.random.PRNGKey(1), jnp.zeros((2, 16, 32)))

        def loss(m):
            def f(p):
                y, aux = m.apply(p, x, mutable=["losses", "moe_stats"])
                return (y**2).mean() + sum(jax.tree.leaves(aux["losses"]))
            return f

        gr = jax.jit(jax.grad(loss(m_ref)))(p)
        gg = jax.jit(jax.grad(loss(m_gmm)))(p)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5
            ),
            gr, gg,
        )

    def test_misaligned_rows_keep_ragged(self, monkeypatch):
        """Token counts that don't divide the data shards must fall back
        to the ragged GSPMD body (the manual region's P(rs) in_spec needs
        equal shards) — poisoned entry pins the routing."""
        from orion_tpu.parallel.mesh import MeshConfig, make_mesh

        monkeypatch.setattr(
            MoEMLP, "_dropless_ep_gmm",
            lambda *a, **k: (_ for _ in ()).throw(
                AssertionError("gmm region must not engage on misaligned rows")
            ),
        )
        mesh = make_mesh(MeshConfig(dp=4))
        # 3 x 683 = 2049 tokens: 2049 % 4 == 1 trips ONLY the divisibility
        # guard — the row-count gate would pass ((2049 // 4) * k2 = 1024)
        x = jax.random.normal(jax.random.PRNGKey(3), (3, 683, 32))
        m_ref, m_gmm, _ = self._models(mesh)
        p = m_ref.init(jax.random.PRNGKey(1), jnp.zeros((2, 16, 32)))
        y_ref = jax.jit(m_ref.apply)(p, x)
        y = jax.jit(m_gmm.apply)(p, x)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(y_ref), atol=2e-5, rtol=2e-5
        )

    @pytest.mark.parametrize("mesh_kw", [dict(dp=2, tp=2), dict(dp=2, pp=2)])
    def test_tp_pp_meshes_keep_ragged(self, mesh_kw, monkeypatch):
        """tp/pp > 1 must NOT take the manual gmm region (the region
        would replicate the tp-sharded expert FLOPs / the row work per pp
        shard); the ragged GSPMD body serves them. The manual entry is
        poisoned so ROUTING is what's asserted, not just numerics — on
        these meshes the region's output would be numerically identical,
        so an allclose alone can't pin the gate (r5 review)."""
        from orion_tpu.parallel.mesh import MeshConfig, make_mesh

        monkeypatch.setattr(
            MoEMLP, "_dropless_ep_gmm",
            lambda *a, **k: (_ for _ in ()).throw(
                AssertionError("gmm region must not engage on tp/pp meshes")
            ),
        )
        mesh = make_mesh(MeshConfig(**mesh_kw))
        x = jax.random.normal(jax.random.PRNGKey(3), (4, 512, 32))
        m_ref, m_gmm, _ = self._models(mesh)
        p = m_ref.init(jax.random.PRNGKey(1), jnp.zeros((2, 16, 32)))
        y_ref = jax.jit(m_ref.apply)(p, x)
        y_tp = jax.jit(m_gmm.apply)(p, x)
        np.testing.assert_allclose(
            np.asarray(y_tp), np.asarray(y_ref), atol=2e-5, rtol=2e-5
        )


def _held_case(n=48, k=3, e=8, el=4, lo=2, d=32, h=24, seed=0, skip=None, dtype=jnp.float32):
    """Inputs of ``_held_rows_ffn``: every token's ``k`` distinct experts of
    ``e`` (``skip``: an expert no token picks), of which ``[lo, lo + el)`` are
    held; token 0 picks held experts only and token 1 none."""
    rng = np.random.default_rng(seed)
    pool = np.array([x for x in range(e) if x != skip])
    held = [x for x in range(lo, lo + el) if x != skip]
    away = [x for x in pool if not lo <= x < lo + el]
    ids = np.stack([rng.choice(pool, k, replace=False) for _ in range(n)])
    ids[0], ids[1] = held[:k], away[:k]
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x2 = jax.random.normal(keys[0], (n, d)).astype(dtype)
    gates = jax.random.uniform(keys[1], (n * k,), minval=0.1)
    ws = tuple(
        0.3 * jax.random.normal(key, shape)
        for key, shape in zip(keys[2:], ((el, d, h), (el, d, h), (el, h, d)))
    )
    return x2, jnp.asarray(ids.reshape(-1), jnp.int32), gates, ws, lo


# name -> (the case's keywords, the budget in rows)
HELD_CASES = {
    "several_pairs_a_token_and_none": (dict(), 144),
    "rows_past_the_budget": (dict(seed=1), 24),
    "an_expert_with_no_rows": (dict(seed=2, skip=3), 144),
    "one_pair_a_token": (dict(seed=3, k=1, n=64), 64),
    "wide_rows": (dict(seed=4, d=256, h=16, n=16), 48),
    "bfloat16_rows": (dict(seed=5, d=256, h=16, n=16, dtype=jnp.bfloat16), 48),
    # 1,088 pairs: the lists pass one 1,024-entry tile and are padded to whole ones
    "lists_longer_than_a_tile": (dict(seed=6, n=136, k=8, e=16, el=8, lo=0, d=16, h=8), 1088),
}


class TestHeldRowsByList:
    """``_held_rows_ffn``'s training form with the tiled Mosaic product moves
    its rows by list in the ``moe_rows_*`` kernels (ops/pallas/moe_rows.py,
    interpret mode here) and is held to the plain form: ``ragged_dot``,
    ``jnp.take`` and ``.at[].add``."""

    @staticmethod
    def _forms(dtype=jnp.float32):
        from orion_tpu.models.moe import _gmm_matmul, _held_rows_ffn, _ragged_matmul

        def form(matmul, budget):
            return lambda x2, flat, gates, ws, lo: _held_rows_ffn(
                x2, flat, gates, ws, lo, budget, matmul, dtype
            )

        return form, _gmm_matmul(8, 128, True), _ragged_matmul

    @pytest.mark.parametrize("name", list(HELD_CASES))
    def test_values_and_counts_match_the_plain_form(self, name):
        kw, budget = HELD_CASES[name]
        x2, flat, gates, ws, lo = _held_case(**kw)
        form, tiled, plain = self._forms(kw.get("dtype", jnp.float32))
        y, held, dropped = jax.jit(form(tiled, budget), static_argnums=4)(x2, flat, gates, ws, lo)
        y0, held0, dropped0 = jax.jit(form(plain, budget), static_argnums=4)(x2, flat, gates, ws, lo)
        assert y.dtype == jnp.float32 and y.shape == x2.shape
        tol = 2e-2 if x2.dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(y), np.asarray(y0), atol=tol, rtol=tol)
        np.testing.assert_array_equal(np.asarray(held), np.asarray(held0))
        assert int(dropped) == int(dropped0)
        assert (int(dropped) > 0) == (name == "rows_past_the_budget")
        assert float(jnp.abs(y0[0]).max()) > 0  # token 0: held pairs only
        if kw.get("k", 3) > 1:
            assert float(jnp.abs(y[1]).max()) == 0.0  # token 1: none held here

    @pytest.mark.parametrize("name", list(HELD_CASES))
    def test_gradients_match_the_plain_form(self, name):
        kw, budget = HELD_CASES[name]
        x2, flat, gates, ws, lo = _held_case(**kw)
        form, tiled, plain = self._forms(kw.get("dtype", jnp.float32))
        mix = jax.random.normal(jax.random.PRNGKey(9), x2.shape)

        def grads(matmul):
            loss = lambda x2, gates, ws: jnp.sum(  # noqa: E731
                form(matmul, budget)(x2, flat, gates, ws, lo)[0] * mix
            )
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(x2, gates, ws)

        got, want = grads(tiled), grads(plain)
        tol = 5e-2 if x2.dtype == jnp.bfloat16 else 3e-5
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert a.dtype == b.dtype and a.shape == b.shape
            b = np.asarray(b, np.float32)  # sums of up to 256 products: to their scale
            np.testing.assert_allclose(
                np.asarray(a, np.float32), b, atol=tol * max(1.0, np.abs(b).max()), rtol=tol
            )

    @pytest.mark.parametrize(
        "name", ["several_pairs_a_token_and_none", "rows_past_the_budget", "an_expert_with_no_rows"])
    def test_nothing_reads_the_buffers_unwritten_tail(self, name):
        """The grouped product leaves the rows past the last segment unwritten,
        in the forward and in ``dx``: here every product's tail and every
        cotangent's, into the product and out of it, is NaN, and ``y``, the
        loss and the gradients of ``x2``, the gates and the three stacks are
        finite and the plain form's. Everything between the products works a
        row at a time, and the buffer is read by list."""
        kw, budget = HELD_CASES[name]
        x2, flat, gates, ws, lo = _held_case(**kw)
        form, tiled, plain = self._forms()
        mix = jax.random.normal(jax.random.PRNGKey(9), x2.shape)

        @jax.custom_vjp
        def nan_tail(rows, tail):
            return jnp.where(tail, jnp.nan, rows)

        nan_tail.defvjp(lambda rows, tail: (nan_tail(rows, tail), tail),
                        lambda tail, g: (jnp.where(tail, jnp.nan, g), None))
        tails = []

        def poisoned(lhs, w, seg, gs):
            tail = (jnp.arange(lhs.shape[0]) >= seg.sum())[:, None]
            tails.append(tail)
            return nan_tail(tiled(nan_tail(lhs, tail), w, seg, gs), tail)

        for fact in ("tile", "by_list", "unwritten_tail", "interpret"):
            setattr(poisoned, fact, getattr(tiled, fact))

        def outcome(matmul):
            def loss(x2, gates, ws):
                y = form(matmul, budget)(x2, flat, gates, ws, lo)[0]
                return jnp.sum(y * mix), y

            (value, y), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
                x2, gates, ws)
            return y, value, grads

        got, want = outcome(poisoned), outcome(plain)
        assert len(tails) == 3 and all(bool(t.any()) for t in tails)  # there IS a tail
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert bool(jnp.isfinite(a).all())
            b = np.asarray(b)
            np.testing.assert_allclose(
                np.asarray(a), b, atol=3e-5 * max(1.0, np.abs(b).max()), rtol=3e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_gather_leaves_unlisted_rows_zero_and_combine_skips_them(self, dtype):
        """Tile padding and the spare buffer: ``idx < 0`` rows of the buffer
        are zeros (no mask outside the kernel), and the combine adds nothing
        for them whatever they hold."""
        from orion_tpu.ops.pallas import moe_rows

        n, k, d, r = 16, 2, 256, 24
        x = jax.random.normal(jax.random.PRNGKey(0), (n, d)).astype(dtype)
        at_row = np.asarray([5, 4, -1, -1, 31, 0, 9, -1] + [-1] * 16)  # the pair a row holds
        idx = jnp.asarray(np.where(at_row >= 0, at_row // k, -1), jnp.int32)
        held, row = np.zeros(n * k, bool), np.zeros(n * k, np.int32)
        held[at_row[at_row >= 0]], row[at_row[at_row >= 0]] = True, np.nonzero(at_row >= 0)[0]
        lists = moe_rows.combine_lists(jnp.asarray(held), jnp.asarray(row), jnp.ones((n * k,)), n)
        xs = moe_rows.gather_rows(x, idx, lists, interpret=True)
        want = jnp.where((idx >= 0)[:, None], x[jnp.clip(idx, 0)], 0)
        np.testing.assert_array_equal(np.asarray(xs, np.float32), np.asarray(want, np.float32))
        junk = jnp.where((idx >= 0)[:, None], xs, 7).astype(dtype)  # unlisted rows hold junk
        y = moe_rows.combine_rows(junk, jnp.ones((r,)), idx, lists, interpret=True)
        back = jnp.zeros((n, d), jnp.float32).at[jnp.clip(idx, 0)].add(want.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(y), np.asarray(back), atol=1e-6)

    @pytest.mark.parametrize("form", ["training", "serving", "plain"])
    def test_only_the_training_form_holds_the_row_kernels(self, form):
        """The serving form (``served``: the combine is a gather already) and
        the plain form are
        the parent's programs: no ``moe_rows_*`` call in their jaxprs."""
        from orion_tpu.models.moe import _gmm_matmul, _held_rows_ffn, _ragged_matmul

        matmul = {"training": _gmm_matmul(8, 128, True),
                  "serving": _gmm_matmul(8, 128, True, served=True),
                  "plain": _ragged_matmul}[form]
        x2, flat, gates, ws, lo = _held_case()
        text = str(jax.make_jaxpr(
            lambda x2, gates, ws: _held_rows_ffn(x2, flat, gates, ws, lo, 144, matmul, jnp.float32)
        )(x2, gates, ws))
        for name in ("moe_rows_gather", "moe_rows_combine"):
            assert (name in text) == (form == "training"), (form, name)
        assert ("scatter-add" in text) == (form == "plain")

    def test_ep_shard_gradients_hold_the_kernels_under_shard_map(self):
        """An ep shard of ``_dropless_ep_gmm``: the row kernels inside the
        fully manual region, gradients against the single-host layer."""
        from orion_tpu.parallel.mesh import make_mesh

        kw = dict(name="t", d_model=32, n_experts=4, dtype="float32",
                  moe_dropless=True, moe_ep_buffer=2.0, moe_top_k=2)
        mesh = make_mesh(MeshConfig(dp=2, ep=2))
        ref = MoEMLP(ModelConfig(backend="xla", **kw))
        ep = MoEMLP(ModelConfig(backend="pallas_interpret", **kw), mesh=mesh)
        x = jax.random.normal(jax.random.PRNGKey(5), (4, 256, 32))
        p = ref.init(jax.random.PRNGKey(1), jnp.zeros((2, 16, 32)))
        loss = lambda m: lambda p, x: (  # noqa: E731
            m.apply(p, x, mutable=["losses", "moe_stats"])[0] ** 2).mean()
        text = str(jax.make_jaxpr(jax.grad(loss(ep)))(p, x))
        assert "moe_rows_gather" in text and "moe_rows_combine" in text
        want = jax.jit(jax.grad(loss(ref), argnums=(0, 1)))(p, x)
        got = jax.jit(jax.grad(loss(ep), argnums=(0, 1)))(p, x)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-5)
