"""Training tests (SURVEY.md §4): tiny-LM overfit (loss ↓ 10×), checkpoint
save/resume bitwise parity, NaN-guard skip behavior, deterministic data
stream, config overrides."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.models.configs import ModelConfig
from orion_tpu.training.data import DataLoader, SyntheticDataset, TokenBinDataset, write_token_bin
from orion_tpu.training.trainer import TrainConfig, Trainer

SMALL_MODEL = ModelConfig(
    name="test_small",
    vocab_size=64,
    d_model=32,
    n_layers=2,
    n_heads=2,
    max_seq_len=64,
    dtype="float32",
    backend="xla",
)


def small_cfg(**kw) -> TrainConfig:
    from orion_tpu.parallel.mesh import MeshConfig

    base = dict(
        model=SMALL_MODEL,
        steps=60,
        batch_size=4,
        seq_len=32,
        lr=3e-3,
        warmup_steps=5,
        log_every=1000,
        clip_norm=1.0,
        mesh=MeshConfig(dp=1),  # degenerate single-device mesh (P1)
    )
    base.update(kw)
    return TrainConfig(**base)


class FixedBatch:
    """Same batch every step — the overfit fixture."""

    def __init__(self, vocab, seq_len, batch):
        self.arr = SyntheticDataset(vocab, seq_len).batch(7, 0, batch)

    def batch(self, seed, step, b):
        return self.arr


def _iter(dataset, cfg, start=0):
    step = start
    while True:
        yield jnp.asarray(dataset.batch(cfg.seed, step, cfg.batch_size))
        step += 1


def test_overfit_fixed_batch():
    cfg = small_cfg(steps=80)
    trainer = Trainer(cfg)
    data = FixedBatch(cfg.model.vocab_size, cfg.seq_len, cfg.batch_size)
    it = _iter(data, cfg)
    first = trainer.step(next(it))
    first_loss = float(first["loss"])
    last = trainer.train(it)
    assert last["loss"] < first_loss / 10, (first_loss, last["loss"])


def test_synthetic_converges():
    """Synthetic data has closed-form structure; even 60 steps must cut loss."""
    cfg = small_cfg(steps=60)
    trainer = Trainer(cfg)
    ds = SyntheticDataset(cfg.model.vocab_size, cfg.seq_len)
    it = _iter(ds, cfg)
    first = float(trainer.step(next(it))["loss"])
    last = trainer.train(it)
    assert last["loss"] < first * 0.9


def test_grad_accumulation_matches_big_batch():
    cfg1 = small_cfg(steps=1, batch_size=8, accum_steps=1, clip_norm=0.0)
    cfg2 = small_cfg(steps=1, batch_size=8, accum_steps=4, clip_norm=0.0)
    t1, t2 = Trainer(cfg1), Trainer(cfg2)
    batch = jnp.asarray(
        SyntheticDataset(cfg1.model.vocab_size, cfg1.seq_len).batch(3, 0, 8)
    )
    t1.step(batch)
    t2.step(batch)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5),
        t1.state.params,
        t2.state.params,
    )


def test_fused_clip_matches_optax_chain():
    """Trainer folds clip_by_global_norm into the finite-guard scale (one
    reduction + one elementwise pass). Must be bit-for-bit the semantics of
    the reference optax chain: clip THEN optimizer."""
    import optax

    from orion_tpu.training.trainer import make_optimizer

    cfg = small_cfg(steps=1, clip_norm=0.05)  # tight: clip definitely binds
    trainer = Trainer(cfg)
    p0 = jax.tree.map(np.asarray, trainer.state.params)
    batch = jnp.asarray(
        SyntheticDataset(cfg.model.vocab_size, cfg.seq_len).batch(3, 0, 4)
    )
    metrics = trainer.step(batch)
    assert float(metrics["grad_norm"]) > cfg.clip_norm  # clip was active

    # reference: same grads through the stock chain (clip inside optax)
    from orion_tpu.training.trainer import lm_loss

    ref_tx = make_optimizer(cfg, include_clip=True)
    # checkpoint compat: the fused trainer's opt_state pytree structure is
    # identical to the stock chain's (identity placeholder where clip sat),
    # so pre-fusion orbax checkpoints restore unchanged
    fused_tx = make_optimizer(cfg, include_clip=False)
    params = jax.tree.map(jnp.asarray, p0)
    assert jax.tree.structure(ref_tx.init(params)) == jax.tree.structure(
        fused_tx.init(params)
    )
    opt_state = ref_tx.init(params)
    grads = jax.grad(lambda p: lm_loss(trainer.model, p, batch, None))(params)
    updates, _ = ref_tx.update(grads, opt_state, params)
    ref_params = optax.apply_updates(params, updates)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-6
        ),
        trainer.state.params,
        ref_params,
    )


def test_nan_guard_skips_update():
    cfg = small_cfg(steps=1)
    trainer = Trainer(cfg)
    # poison one param leaf -> non-finite loss -> whole update must be skipped
    params = trainer.state.params
    flat, tree = jax.tree.flatten(params)
    flat[0] = flat[0].at[...].set(jnp.inf)
    trainer.state = trainer.state.replace(params=jax.tree.unflatten(tree, flat))
    before = jax.tree.map(lambda x: np.asarray(x), trainer.state.params)
    batch = jnp.asarray(
        SyntheticDataset(cfg.model.vocab_size, cfg.seq_len).batch(0, 0, 4)
    )
    metrics = trainer.step(batch)
    assert int(metrics["nonfinite"]) == 1
    after = trainer.state.params
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(a, np.asarray(b)), before, after
    )


def test_checkpoint_resume_bitwise(tmp_path):
    from orion_tpu.training.checkpoint import Checkpointer

    cfg = small_cfg(steps=6, ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=3)
    ds = SyntheticDataset(cfg.model.vocab_size, cfg.seq_len)

    trainer = Trainer(cfg)
    ckpt = Checkpointer(cfg.ckpt_dir, save_every=cfg.ckpt_every, async_save=False)
    trainer.train(_iter(ds, cfg), ckpt=ckpt)
    final = jax.tree.map(np.asarray, trainer.state.params)
    ckpt.close()

    trainer2 = Trainer(cfg)
    ckpt2 = Checkpointer(cfg.ckpt_dir, save_every=10_000, async_save=False)
    start = trainer2.restore(ckpt2, step=3)  # resume mid-run, not at latest
    assert start == 3
    trainer2.train(_iter(ds, cfg, start=start))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(a, np.asarray(b)),
        final,
        trainer2.state.params,
    )
    ckpt2.close()


def test_checkpoint_restores_across_meshes(tmp_path):
    """Elastic reconfiguration: a checkpoint written on one mesh restores
    onto a DIFFERENT mesh (orbax reshards to the new trainer's
    NamedShardings) and training continues. Reference = the uninterrupted
    dp=1 run; the restored dp2/fsdp2/tp2 run must land on the same final
    params to fp tolerance (2e-5 — GSPMD changes reduction orders, so
    cross-MESH parity is allclose, unlike same-mesh resume which is
    bitwise in test_checkpoint_resume_bitwise)."""
    from orion_tpu.parallel.mesh import MeshConfig
    from orion_tpu.training.checkpoint import Checkpointer

    batch8 = dict(batch_size=8)  # divisible by the sharded mesh's dp*fsdp
    cfg_a = small_cfg(
        steps=4, ckpt_dir=str(tmp_path / "ck"), ckpt_every=2, **batch8
    )
    ds = SyntheticDataset(cfg_a.model.vocab_size, cfg_a.seq_len)

    # run A: single device, save at step 2, finish at 4
    tr_a = Trainer(cfg_a)
    ck_a = Checkpointer(cfg_a.ckpt_dir, save_every=2, async_save=False)
    tr_a.train(_iter(ds, cfg_a), ckpt=ck_a)
    final_a = jax.tree.map(np.asarray, tr_a.state.params)
    ck_a.close()

    # run B: restore step-2 state onto a dp2/fsdp2/tp2 mesh, train to 4
    cfg_b = small_cfg(
        steps=4, ckpt_dir=cfg_a.ckpt_dir,
        mesh=MeshConfig(dp=2, fsdp=2, tp=2), **batch8
    )
    tr_b = Trainer(cfg_b)
    ck_b = Checkpointer(cfg_b.ckpt_dir, save_every=10_000, async_save=False)
    start = tr_b.restore(ck_b, step=2)
    assert start == 2
    sh = tr_b.state_shardings.params["params"]["block_0"]["attn"]["wq"][
        "kernel"
    ].spec
    assert sh == jax.sharding.PartitionSpec("fsdp", "tp"), sh
    tr_b.train(_iter(ds, cfg_b, start=start))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            a, np.asarray(b), atol=2e-5, rtol=2e-5
        ),
        final_a,
        tr_b.state.params,
    )
    ck_b.close()


def test_token_bin_roundtrip(tmp_path):
    path = str(tmp_path / "toks.bin")
    toks = np.arange(1000) % 100
    write_token_bin(path, toks, vocab_size=100)
    ds = TokenBinDataset(path, seq_len=16)
    assert ds.vocab_size == 100
    b = ds.batch(0, 0, 4)
    assert b.shape == (4, 17)
    assert (b >= 0).all() and (b < 100).all()
    # determinism
    np.testing.assert_array_equal(b, ds.batch(0, 0, 4))
    assert not np.array_equal(b, ds.batch(0, 1, 4))


def test_dataloader_prefetch():
    ds = SyntheticDataset(32, 8)
    loader = DataLoader(ds, batch_size=2, seed=1, start_step=0)
    try:
        b0 = next(iter(loader))
        assert b0.shape == (2, 9)
        np.testing.assert_array_equal(np.asarray(b0), ds.batch(1, 0, 2))
    finally:
        loader.close()


def test_apply_overrides():
    from orion_tpu.utils.config import apply_overrides

    cfg = small_cfg()
    out = apply_overrides(cfg, {"lr": "1e-3", "model.n_layers": "3", "optimizer": "lion"})
    assert out.lr == 1e-3 and out.model.n_layers == 3 and out.optimizer == "lion"
    with pytest.raises(KeyError):
        apply_overrides(cfg, {"nope": 1})


def test_lion_optimizer_runs():
    cfg = small_cfg(steps=2, optimizer="lion", lr=1e-4)
    trainer = Trainer(cfg)
    ds = SyntheticDataset(cfg.model.vocab_size, cfg.seq_len)
    last = trainer.train(_iter(ds, cfg))
    assert np.isfinite(last["loss"])


def test_periodic_eval_during_train():
    cfg = small_cfg(steps=6, eval_every=3, eval_batches=2)
    trainer = Trainer(cfg)
    ds = SyntheticDataset(cfg.model.vocab_size, cfg.seq_len)
    last = trainer.train(_iter(ds, cfg), eval_iter=_iter(ds, cfg, start=500))
    assert "eval_loss" in last and np.isfinite(last["eval_loss"])


def test_evaluate_cli_roundtrip(tmp_path):
    """train -> checkpoint -> evaluate_lm reads it back."""
    from orion_tpu.evaluate import evaluate_lm
    from orion_tpu.models.transformer import TransformerLM
    from orion_tpu.training.checkpoint import Checkpointer

    cfg = small_cfg(steps=3, ckpt_dir=str(tmp_path / "ck"), ckpt_every=3)
    trainer = Trainer(cfg)
    ds = SyntheticDataset(cfg.model.vocab_size, cfg.seq_len)
    ckpt = Checkpointer(cfg.ckpt_dir, save_every=3, async_save=False)
    trainer.train(_iter(ds, cfg), ckpt=ckpt)
    ckpt.close()

    from orion_tpu.generate import load_params

    params, step = load_params(cfg.ckpt_dir)
    assert step == 3
    model = TransformerLM(cfg.model)
    res = evaluate_lm(model, params, ds, batch_size=2, n_batches=2)
    assert np.isfinite(res["eval_loss"]) and res["tokens"] > 0


def test_loader_callback_path_matches_device_put():
    """The multi-host materialization path (make_array_from_callback over
    the addressable shards) must produce the same global array the single-
    host device_put does — verified on the virtual 8-device mesh."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from orion_tpu.parallel.mesh import MeshConfig, make_mesh
    from orion_tpu.training.data import SyntheticDataset

    mesh = make_mesh(MeshConfig(dp=4, fsdp=2))
    shd = NamedSharding(mesh, P(("dp", "fsdp")))
    ds = SyntheticDataset(64, 16)
    host = ds.batch(0, 3, 8)
    a = jax.device_put(host, shd)
    b = jax.make_array_from_callback(host.shape, shd, lambda idx: host[idx])
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert b.sharding == shd


def test_train_cli_smoke_with_pp(tmp_path):
    """The full train.py CLI path (arg parsing, mesh build incl. --pp,
    loader, metrics) runs end-to-end on the virtual mesh."""
    from orion_tpu.train import main

    log = str(tmp_path / "m.jsonl")
    rc = main([
        "--config", "tiny", "--data", "synthetic", "--steps", "3",
        "--batch-size", "4", "--seq-len", "32", "--pp", "2", "--dp", "2",
        "--log-path", log,
    ])
    assert rc == 0
    import json as _json

    lines = [_json.loads(l) for l in open(log)]
    assert lines and all("loss" in l for l in lines)


def test_pp_checkpoint_serves_via_unstack(tmp_path):
    """A pp-trained checkpoint (stacked-block layout) round-trips: saved by
    the pp Trainer, restored, auto-unstacked, and evaluated with the plain
    forward — eval sums match the pp trainer's own eval exactly."""
    from orion_tpu.evaluate import lm_eval_sums
    from orion_tpu.models.transformer import TransformerLM
    from orion_tpu.parallel.mesh import MeshConfig
    from orion_tpu.parallel.pipeline_lm import unstack_lm_params
    from orion_tpu.training.checkpoint import Checkpointer

    cfg = small_cfg(
        steps=3, ckpt_dir=str(tmp_path / "ck"), ckpt_every=3,
        mesh=MeshConfig(dp=1, pp=2),
    )
    trainer = Trainer(cfg)
    ds = SyntheticDataset(cfg.model.vocab_size, cfg.seq_len)
    ckpt = Checkpointer(cfg.ckpt_dir, save_every=3, async_save=False)
    trainer.train(_iter(ds, cfg), ckpt=ckpt)
    ckpt.close()

    from orion_tpu.generate import load_params

    params, step = load_params(cfg.ckpt_dir)
    assert step == 3
    assert "blocks_stacked" in params["params"]
    model = TransformerLM(cfg.model)
    flat = unstack_lm_params(model, params)
    batch = jnp.asarray(ds.batch(0, 0, 4))
    s_flat, c_flat = lm_eval_sums(model, flat, batch)
    s_pp, _ = trainer._eval_fn(trainer.state.params, batch)
    np.testing.assert_allclose(float(s_flat), float(s_pp), rtol=2e-6)
    assert float(c_flat) > 0


def test_trainer_oom_fallback_retries_at_skip0(tmp_path):
    """ADVICE r3 #1: a compile-OOM at the tuned remat_skip retries once
    fully rematted (same math, different memory trade) instead of dying.
    Simulated: the first _step_fn call raises a RESOURCE_EXHAUSTED-shaped
    error before execution (so state buffers stay live, like a compile
    failure)."""
    import warnings

    from orion_tpu.models.configs import ModelConfig
    from orion_tpu.parallel.mesh import MeshConfig
    from orion_tpu.training.data import SyntheticDataset
    from orion_tpu.training.trainer import TrainConfig, Trainer

    model = ModelConfig(
        name="t", vocab_size=64, d_model=32, n_layers=2, n_heads=2,
        max_seq_len=64, dtype="float32", remat=True, remat_skip=1,
    )
    cfg = TrainConfig(
        model=model, steps=2, batch_size=2, seq_len=16, lr=1e-3,
        warmup_steps=1, mesh=MeshConfig(dp=1), log_every=1,
    )
    tr = Trainer(cfg)

    def fake_oom(state, batch):
        # the retry REBUILDS _step_fn, so this fake only ever fires once
        raise RuntimeError("RESOURCE_EXHAUSTED: simulated compile OOM")

    tr._step_fn = fake_oom
    batch = jnp.asarray(SyntheticDataset(64, 16).batch(0, 0, 2))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        m = tr.step(batch)
    assert np.isfinite(float(m["loss"]))
    assert tr.model.cfg.remat_skip == 0  # rebuilt fully rematted
    assert tr._step_fn is not fake_oom  # the rebuild replaced the fake
    assert any("retrying fully rematted" in str(x.message) for x in w)


def _DATA(name):
    import os

    return os.path.join(os.path.dirname(__file__), "..", "data", name)


def test_eval_factory_batches_deterministic_per_step(tmp_path):
    """Eval batches are a pure function of the train step: a killed+
    resumed run re-evaluates any step's eval on the exact same data
    (the round-4 endurance run surfaced the process-relative sampling)."""
    from orion_tpu.train import train as train_fn
    from orion_tpu.training.trainer import TrainConfig
    from orion_tpu.models.configs import ModelConfig

    model = ModelConfig(
        name="t", vocab_size=32000, d_model=32, n_layers=2, n_heads=2,
        max_seq_len=65, dtype="float32",
    )
    from orion_tpu.parallel.mesh import MeshConfig as _MC

    mk = lambda steps, d: TrainConfig(  # noqa: E731
        model=model, steps=steps, batch_size=2, seq_len=64, lr=1e-4,
        warmup_steps=1, log_every=10, eval_every=2, eval_batches=2,
        ckpt_dir=str(tmp_path / d), ckpt_every=2, mesh=_MC(dp=1),
    )
    # run 4 steps straight (evals at 2 and 4)
    _, a = train_fn(mk(4, "a"), data=_DATA("train.bin"),
                    eval_data=_DATA("val.bin"), resume=False)
    # separate dir: run 2 steps, then resume to 4 in a new trainer
    # (fresh-process stand-in; same seed, so trajectories match run a)
    _, _ = train_fn(mk(2, "b"), data=_DATA("train.bin"),
                    eval_data=_DATA("val.bin"), resume=False)
    _, b = train_fn(mk(4, "b"), data=_DATA("train.bin"),
                    eval_data=_DATA("val.bin"), resume=True)
    # same step-4 eval data + bitwise-restored state -> identical eval loss
    np.testing.assert_allclose(a["eval_loss"], b["eval_loss"], rtol=1e-6)


# -- param_storage="bfloat16_sr" (VERDICT r4 #1) ----------------------------


def test_sr_round_bf16_unbiased_exact_and_nonfinite():
    """The three SR contracts: (a) unbiased — the mean of many rounds
    recovers the fp32 value far beyond bf16 precision; (b) exact — a value
    already representable in bf16 round-trips bit-identically (a zero
    update can never perturb params); (c) non-finite passthrough."""
    from orion_tpu.training.trainer import sr_round_bf16

    x = jnp.full((50000,), 1.0 + 2**-12, jnp.float32)  # between bf16 ulps
    y = sr_round_bf16(x, jax.random.PRNGKey(0)).astype(jnp.float32)
    # truncation would be off by 2**-12 ~ 2.4e-4; SR mean lands ~50x closer
    assert abs(float(y.mean()) - float(x[0])) < 2e-5
    # only the two bracketing neighbors ever appear
    assert set(np.unique(np.asarray(y))) <= {1.0, 1.0078125}

    z = jnp.asarray([1.5, -0.25, 0.0, 3.0], jnp.float32)  # bf16-exact
    np.testing.assert_array_equal(
        np.asarray(sr_round_bf16(z, jax.random.PRNGKey(1)).astype(jnp.float32)),
        np.asarray(z),
    )

    nf = jnp.asarray([jnp.inf, -jnp.inf, jnp.nan], jnp.float32)
    out = np.asarray(sr_round_bf16(nf, jax.random.PRNGKey(2)).astype(jnp.float32))
    assert out[0] == np.inf and out[1] == -np.inf and np.isnan(out[2])


def test_bf16_sr_storage_layout_and_convergence():
    """bfloat16_sr stores matrix leaves bf16 (1D leaves stay fp32), the
    optimizer stats stay fp32, and the overfit trajectory tracks the fp32-
    master run closely (the convergence-parity evidence VERDICT r4 #1
    asks for alongside the memory win)."""
    data = FixedBatch(SMALL_MODEL.vocab_size, 32, 4)
    results = {}
    for storage in ("float32", "bfloat16_sr"):
        cfg = small_cfg(steps=80, param_storage=storage)
        trainer = Trainer(cfg)
        if storage == "bfloat16_sr":
            by_ndim = {True: set(), False: set()}
            for l in jax.tree.leaves(trainer.state.params):
                by_ndim[l.ndim >= 2].add(str(l.dtype))
            assert by_ndim[True] == {"bfloat16"}, by_ndim
            assert by_ndim[False] <= {"float32"}, by_ndim
            for l in jax.tree.leaves(trainer.state.opt_state):
                assert l.dtype != jnp.bfloat16, "opt stats must stay fp32"
        it = _iter(data, cfg)
        first = float(trainer.step(next(it))["loss"])
        last = trainer.train(it)
        results[storage] = (first, last["loss"])
    f32_first, f32_last = results["float32"]
    sr_first, sr_last = results["bfloat16_sr"]
    # both overfit the fixed batch; SR lands within 25% of the fp32 loss
    assert sr_last < sr_first / 8, results
    assert abs(sr_last - f32_last) < 0.25 * max(f32_last, 0.05), results


def test_bf16_sr_resume_bitwise(tmp_path):
    """SR keys derive from (state.rng, step, leaf index) only, so a
    killed+resumed bfloat16_sr run replays identical rounding — the A3
    bitwise-resume guarantee survives the new storage mode."""
    from orion_tpu.training.checkpoint import Checkpointer

    cfg = small_cfg(
        steps=6, ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=3,
        param_storage="bfloat16_sr",
    )
    ds = SyntheticDataset(cfg.model.vocab_size, cfg.seq_len)

    trainer = Trainer(cfg)
    ckpt = Checkpointer(cfg.ckpt_dir, save_every=cfg.ckpt_every, async_save=False)
    trainer.train(_iter(ds, cfg), ckpt=ckpt)
    final = jax.tree.map(np.asarray, trainer.state.params)
    ckpt.close()

    trainer2 = Trainer(cfg)
    ckpt2 = Checkpointer(cfg.ckpt_dir, save_every=10_000, async_save=False)
    start = trainer2.restore(ckpt2, step=3)
    assert start == 3
    trainer2.train(_iter(ds, cfg, start=start))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(a, np.asarray(b)),
        final,
        trainer2.state.params,
    )
    ckpt2.close()


def test_bf16_sr_nan_guard_skips_update():
    """The finite guard composes with SR: a poisoned step must leave the
    bf16 params bit-identical (SR of a zero update is exact, and the
    where(finite, ...) select keeps the old leaves)."""
    cfg = small_cfg(steps=1, param_storage="bfloat16_sr")
    trainer = Trainer(cfg)
    params = trainer.state.params
    flat, tree = jax.tree.flatten(params)
    flat[0] = flat[0].at[...].set(jnp.inf)
    trainer.state = trainer.state.replace(params=jax.tree.unflatten(tree, flat))
    before = jax.tree.map(lambda x: np.asarray(x), trainer.state.params)
    batch = jnp.asarray(
        SyntheticDataset(cfg.model.vocab_size, cfg.seq_len).batch(0, 0, 4)
    )
    metrics = trainer.step(batch)
    assert int(metrics["nonfinite"]) == 1
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(a, np.asarray(b)),
        before, trainer.state.params,
    )


def test_unknown_optimizer_and_param_storage_raise():
    with pytest.raises(ValueError, match="unknown optimizer"):
        Trainer(small_cfg(optimizer="no_such_optimizer",
                          param_storage="bfloat16_sr"))
    with pytest.raises(ValueError, match="param_storage"):
        Trainer(small_cfg(param_storage="float16"))


def test_sr_noise_bits_uniform():
    """The counter-hash noise source must make the SR selector's low 16
    bits uniform — mean and per-bit balance within tight Monte-Carlo
    bounds, plus no correlation with the counter parity (the Weyl input
    is sequential)."""
    from orion_tpu.training.trainer import _sr_noise_bits

    r = np.asarray(
        _sr_noise_bits(jax.random.PRNGKey(9), 1 << 20)
    ) & 0xFFFF
    n = r.size
    assert abs(r.mean() - 32767.5) < 4 * (65536 / np.sqrt(12 * n))
    for b in range(16):
        frac = ((r >> b) & 1).mean()
        assert abs(frac - 0.5) < 5 / np.sqrt(n), (b, frac)
    even, odd = r[0::2].mean(), r[1::2].mean()
    assert abs(even - odd) < 8 * (65536 / np.sqrt(12 * n / 2))


def test_train_cli_sharded_corpus_bf16_sr(tmp_path):
    """The ENDURANCE_v2 recipe end-to-end at test scale: corpusgen shards
    -> --data <dir> through the sharded loader -> bfloat16_sr training
    with step-keyed eval on the held-out shard."""
    import numpy as np

    from orion_tpu.train import train as train_fn
    from orion_tpu.training.corpusgen import generate_shards
    from orion_tpu.training.data import write_token_bin

    src = str(tmp_path / "src.bin")
    rng = np.random.default_rng(0)
    a = rng.integers(0, 40, 6000)
    write_token_bin(src, ((a * 37 + np.roll(a, 1)) % 997).astype(np.uint16),
                    vocab_size=1024)
    out = str(tmp_path / "corpus")
    generate_shards(src, out, shards=2, tokens_per_shard=3000, seed=5,
                    eval_tokens=1500)

    from orion_tpu.models.configs import ModelConfig
    from orion_tpu.parallel.mesh import MeshConfig
    from orion_tpu.training.trainer import TrainConfig

    cfg = TrainConfig(
        model=ModelConfig(name="t", vocab_size=1024, d_model=32, n_layers=2,
                          n_heads=2, max_seq_len=33, dtype="float32"),
        steps=4, batch_size=2, seq_len=32, lr=1e-3, warmup_steps=1,
        log_every=2, eval_every=2, eval_batches=2,
        ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=2,
        mesh=MeshConfig(dp=1), param_storage="bfloat16_sr",
    )
    _, last = train_fn(cfg, data=out, eval_data=out + "/eval.bin",
                       resume=False)
    assert np.isfinite(last["loss"]) and np.isfinite(last["eval_loss"])
