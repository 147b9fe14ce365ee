"""Generation tests (SURVEY.md §4 / I1–I5): greedy decode parity against the
parallel forward (teacher-forced argmax), sampling filters, hybrid-model
decode, and the CLI smoke path."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.generate import SampleConfig, generate, sample_logits
from orion_tpu.models import ModelConfig, TransformerLM

CFG = ModelConfig(
    name="gen_test",
    vocab_size=64,
    d_model=32,
    n_layers=3,
    n_heads=2,
    layer_types=("linear", "softmax", "swa"),
    window=4,
    max_seq_len=64,
    dtype="float32",
    backend="xla",
)


def _model_and_params(cfg=CFG, seed=0):
    model = TransformerLM(cfg)
    toks = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(seed), toks)
    return model, params


def test_greedy_decode_matches_parallel_argmax():
    """Greedy generation must equal repeatedly running the full parallel
    forward and taking argmax — recurrent state == parallel attention."""
    model, params = _model_and_params()
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 7), 0, CFG.vocab_size)
    n = 10
    out = generate(model, params, prompt, n, SampleConfig(temperature=0.0))
    assert out.shape == (2, n)

    seq = prompt
    for i in range(n):
        logits = model.apply(params, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        np.testing.assert_array_equal(np.asarray(nxt), np.asarray(out[:, i]))
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)


def test_generate_deterministic_and_batched():
    model, params = _model_and_params()
    prompt = jnp.ones((3, 5), jnp.int32)
    a = generate(model, params, prompt, 6, SampleConfig(0.9, 5, 0.9),
                 rng=jax.random.PRNGKey(7))
    b = generate(model, params, prompt, 6, SampleConfig(0.9, 5, 0.9),
                 rng=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert a.shape == (3, 6)
    assert (np.asarray(a) >= 0).all() and (np.asarray(a) < CFG.vocab_size).all()


def test_top_k_restricts_support():
    logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0, 4.0]] * 4)
    rng = jax.random.PRNGKey(0)
    for i in range(20):
        t = sample_logits(logits, jax.random.fold_in(rng, i),
                          SampleConfig(temperature=1.0, top_k=2))
        assert set(np.asarray(t).tolist()) <= {3, 4}


def test_top_p_restricts_support():
    # probs ~ [0.643, 0.236, 0.087, 0.032, 0.012]; top_p=0.6 keeps only id 4
    logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0, 4.0]] * 4)
    rng = jax.random.PRNGKey(1)
    for i in range(20):
        t = sample_logits(logits, jax.random.fold_in(rng, i),
                          SampleConfig(temperature=1.0, top_p=0.6))
        assert set(np.asarray(t).tolist()) <= {4}


def test_greedy_is_argmax():
    logits = jax.random.normal(jax.random.PRNGKey(2), (3, 17))
    t = sample_logits(logits, jax.random.PRNGKey(3), SampleConfig(temperature=0.0))
    np.testing.assert_array_equal(np.asarray(t), np.argmax(np.asarray(logits), -1))


def test_greedy_ignores_filters():
    """temperature=0 with top_k/top_p set is still exact argmax (the
    filters are no-ops on a greedy request, not a crash or a bias)."""
    logits = jax.random.normal(jax.random.PRNGKey(4), (3, 17))
    t = sample_logits(
        logits, jax.random.PRNGKey(5),
        SampleConfig(temperature=0.0, top_k=3, top_p=0.5),
    )
    np.testing.assert_array_equal(np.asarray(t), np.argmax(np.asarray(logits), -1))


def test_top_k_ge_vocab_is_no_filter():
    """top_k >= V must not index out of range — it means 'no filtering',
    bitwise-identical to top_k off at the same rng."""
    logits = jax.random.normal(jax.random.PRNGKey(6), (4, 7))
    rng = jax.random.PRNGKey(7)
    for k in (7, 8, 100):
        got = sample_logits(logits, rng, SampleConfig(temperature=1.0, top_k=k))
        ref = sample_logits(logits, rng, SampleConfig(temperature=1.0, top_k=0))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_top_p_degenerate_keeps_argmax():
    """A top_p cutoff that would mask every candidate (top_p <= 0, or
    smaller than the argmax's own probability) keeps the argmax instead
    of sampling from an all--inf row."""
    logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0, 4.0]] * 4)
    for p in (0.0, 1e-9, 1e-3):
        for i in range(10):
            t = sample_logits(
                logits, jax.random.fold_in(jax.random.PRNGKey(8), i),
                SampleConfig(temperature=1.0, top_p=p),
            )
            assert set(np.asarray(t).tolist()) == {4}, p


def test_long_decode_past_window():
    """Decode far beyond the swa window and the softmax cache warm region."""
    cfg = dataclasses.replace(CFG, max_seq_len=48)
    model, params = _model_and_params(cfg)
    prompt = jnp.ones((1, 3), jnp.int32)
    n = 40  # >> window=4
    out = generate(model, params, prompt, n, SampleConfig(temperature=0.0))

    seq = prompt
    for i in range(n):
        logits = model.apply(params, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        np.testing.assert_array_equal(np.asarray(nxt), np.asarray(out[:, i]))
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)


def test_cli_smoke(capsys):
    from orion_tpu.generate import main

    rc = main([
        "--config", "tiny", "--prompt", "ab", "--max-new-tokens", "4",
        "--temperature", "0",
    ])
    assert rc == 0
    outp = capsys.readouterr().out
    assert outp.startswith("ab")


def test_byte_tokenizer_roundtrip():
    from orion_tpu.utils.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    s = "hello, κόσμε ✓"
    assert tok.decode(tok.encode(s)) == s


def test_eos_stops_and_pads():
    """Force EOS = the greedy-argmax token at some step; everything after the
    first EOS emission must be pad."""
    model, params = _model_and_params()
    prompt = jnp.ones((2, 4), jnp.int32)
    base = generate(model, params, prompt, 8, SampleConfig(temperature=0.0))
    eos = int(np.asarray(base[0, 2]))  # the token greedily emitted at step 2
    out = generate(
        model, params, prompt, 8,
        SampleConfig(temperature=0.0, eos_token=eos, pad_token=0),
    )
    row = np.asarray(out[0])
    eos_positions = np.where(row == eos)[0]
    assert len(eos_positions) >= 1
    first_eos = eos_positions[0]
    assert (row[first_eos + 1 :] == 0).all()
    # tokens before EOS are unchanged vs the no-EOS run
    np.testing.assert_array_equal(row[: first_eos + 1],
                                  np.asarray(base[0])[: first_eos + 1])


def test_eos_pads_rows_independently():
    """EOS hit mid-batch: each row pads after ITS OWN first EOS while the
    other rows keep decoding unchanged."""
    model, params = _model_and_params()
    prompt = jax.random.randint(jax.random.PRNGKey(5), (3, 4), 0, CFG.vocab_size)
    base = np.asarray(generate(model, params, prompt, 8, SampleConfig(temperature=0.0)))
    eos = int(base[0, 2])  # row 0's greedy token at step 2
    out = np.asarray(generate(
        model, params, prompt, 8,
        SampleConfig(temperature=0.0, eos_token=eos, pad_token=0),
    ))
    for b in range(3):
        hits = np.where(base[b] == eos)[0]
        if len(hits) == 0:
            np.testing.assert_array_equal(out[b], base[b], err_msg=f"row {b}")
            continue
        first = hits[0]
        np.testing.assert_array_equal(out[b, : first + 1], base[b, : first + 1])
        assert (out[b, first + 1 :] == 0).all(), f"row {b} not padded"
    # at least one row must actually differ from another in when it ends,
    # or this test isn't exercising mid-batch divergence
    firsts = [
        np.where(base[b] == eos)[0][0] if (base[b] == eos).any() else 99
        for b in range(3)
    ]
    assert len(set(firsts)) > 1, f"degenerate fixture: {firsts}"


@pytest.mark.parametrize("chunk", [1, 3, 8, 16])
def test_chunked_decode_matches_monolithic_bitwise(chunk):
    """A one-slot engine must reproduce generate() token-for-token at the
    same seed for every chunking — including chunk=1 and a ragged tail —
    with sampling filters AND eos padding active (the serving layer's
    correctness floor)."""
    from orion_tpu.serving import DecodeRequest, SlotEngine

    model, params = _model_and_params()
    prompt = jnp.ones((1, 5), jnp.int32)
    cfg = SampleConfig(0.8, top_k=5, top_p=0.9, eos_token=3, pad_token=0)
    ref = np.asarray(
        generate(model, params, prompt, 8, cfg, rng=jax.random.PRNGKey(9))
    )
    eng = SlotEngine(model, params, slots=1, chunk=chunk)
    eng.admit(DecodeRequest(prompt=prompt, max_new_tokens=8, sample=cfg,
                            seed=9), tag="r")
    done = {}
    while eng.busy:
        done.update(dict(eng.step()))
    assert done["r"].status == "ok"
    np.testing.assert_array_equal(done["r"].tokens, ref)


def test_sharded_generate_parity():
    """Mesh-sharded decode (VERDICT r1 item 7): dp=4 batch sharding and
    dp=2/tp=2 head sharding must reproduce single-device greedy decode
    token-for-token. Params go through the training sharding rules; GSPMD
    propagates the layouts through prefill + the decode scan."""
    from orion_tpu.parallel.mesh import MeshConfig, make_mesh

    model, params = _model_and_params()
    prompt = jax.random.randint(jax.random.PRNGKey(3), (4, 7), 0, CFG.vocab_size)
    ref = generate(model, params, prompt, 9, SampleConfig(temperature=0.0))

    for mc in (MeshConfig(dp=4), MeshConfig(dp=2, fsdp=1, tp=2)):
        mesh = make_mesh(mc)
        out = generate(
            model, params, prompt, 9, SampleConfig(temperature=0.0), mesh=mesh
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref), err_msg=str(mc))


def test_sharded_generate_sampled_parity():
    """Same-rng sampled decode over a mesh matches single-device (threefry
    is partitionable, so the per-step categorical draws are identical)."""
    from orion_tpu.parallel.mesh import MeshConfig, make_mesh

    model, params = _model_and_params()
    prompt = jnp.ones((4, 5), jnp.int32)
    cfg = SampleConfig(temperature=0.8, top_k=8)
    rng = jax.random.PRNGKey(11)
    ref = generate(model, params, prompt, 6, cfg, rng=rng)
    mesh = make_mesh(MeshConfig(dp=4))
    out = generate(model, params, prompt, 6, cfg, rng=rng, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_generate_cli_from_checkpoint(tmp_path, capsys):
    """The CLI path end-to-end from a saved checkpoint: load_params,
    pos-capacity adaptation, decode, byte-tokenizer print."""
    from orion_tpu.models.configs import get_config
    from orion_tpu.training.checkpoint import Checkpointer
    from orion_tpu.training.data import SyntheticDataset
    from orion_tpu.training.trainer import TrainConfig, Trainer
    from orion_tpu.generate import main

    from orion_tpu.parallel.mesh import MeshConfig

    cfg = TrainConfig(
        model=get_config("tiny"), steps=2, batch_size=2, seq_len=32,
        lr=1e-3, warmup_steps=1, log_every=100,
        ckpt_dir=str(tmp_path / "ck"), ckpt_every=2, mesh=MeshConfig(dp=1),
    )
    trainer = Trainer(cfg)
    ds = SyntheticDataset(cfg.model.vocab_size, cfg.seq_len)
    ckpt = Checkpointer(cfg.ckpt_dir, save_every=2, async_save=False)
    for step in (1, 2):
        trainer.step(jnp.asarray(ds.batch(0, step, 2)))
        ckpt.maybe_save(step, trainer.state)
    ckpt.close()

    rc = main([
        "--config", "tiny", "--ckpt-dir", cfg.ckpt_dir,
        "--prompt", "ab", "--max-new-tokens", "4", "--temperature", "0.0",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert out.startswith("ab") and len(out.strip()) >= 2
