"""What decides ``correct`` refuses what it should, and the training clock
keeps one step in flight."""

import dataclasses

import jax
import numpy as np

import harness
from reference import plain_lm


def tiny(layer_types=None):
    from orion_tpu.models.configs import get_config
    from orion_tpu.models.transformer import TransformerLM

    cfg = dataclasses.replace(get_config("tiny"), layer_types=layer_types, window=16)
    model = TransformerLM(cfg)
    toks = jax.random.randint(jax.random.key(1), (1, 24), 0, cfg.vocab_size)
    return cfg, model, jax.jit(model.init)(jax.random.key(0), toks), np.asarray(toks[0])


def test_served_check_takes_the_reference_choice_and_refuses_another_token():
    serve = harness.load_module("kinds", "serve")
    cfg, _, params, prompt = tiny()
    spec = harness.reference_spec(cfg)
    ids = list(prompt)
    for _ in range(12):  # greedy by the reference itself
        logits = plain_lm.forward(spec, params, np.asarray([ids]))
        ids.append(int(logits[0, -1].argmax()))
    answer = np.asarray(ids[len(prompt):])
    good = serve.check_served(params, cfg, [(prompt, answer)], 48)
    assert good["ok"] and good["max_gap"] == 0.0 and good["positions"] == 12
    assert good["reference_choice_share"] == 1.0
    wrong = answer.copy()
    logits = plain_lm.forward(spec, params, np.asarray([ids[:-1]]))
    wrong[-1] = int(logits[0, -1].argmin())  # the last token: nothing after it shifts
    bad = serve.check_served(params, cfg, [(prompt, answer), (prompt, wrong)], 48)
    assert not bad["ok"] and bad["max_gap"] > serve.SERVED_GAP_TOLERANCE
    assert not serve.check_served(params, cfg, [], 48)["ok"]  # nothing served, nothing proven


def test_forward_check_passes_the_program_and_refuses_a_wrong_weight():
    import jax.numpy as jnp

    from orion_tpu.training.trainer import lm_loss

    train = harness.load_module("kinds", "train")
    cfg, model, params, _ = tiny(("swa", "linear"))

    class Dataset:
        def batch(self, seed, index, n):
            return np.asarray(jax.random.randint(jax.random.key(seed), (n, 33), 0, cfg.vocab_size))

    class Trainer:
        class mesh:
            shape = {"dp": 1}

        class state:
            pass

        def __init__(self, served):
            self.model, self.state.params, self.served = model, params, served

        def evaluate(self, batches, n_batches):
            return {"eval_loss": float(lm_loss(model, self.served, jnp.asarray(next(batches))))}

    good = train.check_forward(Trainer(params), Dataset(), 5)
    assert good["ok"] and good["delta"] < 1e-4 and good["reference_loss"] > 1.0
    blk = params["params"]["block_1"]["mlp"]["down"]
    wrong = {"params": {**params["params"], "block_1": {
        **params["params"]["block_1"], "mlp": {
            **params["params"]["block_1"]["mlp"], "down": {"kernel": -blk["kernel"]}}}}}
    bad = train.check_forward(Trainer(wrong), Dataset(), 5)
    assert not bad["ok"] and bad["delta"] > train.LOSS_TOLERANCE


def test_step_clock_waits_for_the_step_before_and_opens_the_window_after_warmup():
    train = harness.load_module("kinds", "train")
    read = []

    class Loss(float):
        def __float__(self):
            read.append(float.__float__(self))
            return float.__float__(self)

    def metrics(k):
        return {"loss": Loss(k), "nonfinite": 0}

    opened = []
    clock = train.StepClock(harness.Spans(), 2, seconds=0.0, on_open=lambda: opened.append(len(read)))
    clock.hook(1, metrics(1))
    assert read == [] and not clock.should_stop  # step 1 is in flight, nothing is waited for
    clock.hook(2, metrics(2))
    assert read == [1.0] and clock.t_window is None and not clock.should_stop
    clock.hook(3, metrics(3))  # the second completion ends the warm-up
    assert read == [1.0, 2.0] and opened == [2] and clock.should_stop
    clock.close()
    assert read == [1.0, 2.0, 3.0] and len(clock.done_at) == 3
    assert clock.done_at[1] == clock.t_window
    counted = train.StepClock(harness.Spans(), 0, steps=2)
    counted.hook(7, metrics(7))
    assert not counted.should_stop
    counted.hook(8, metrics(8))
    assert counted.should_stop
    bad = train.StepClock(harness.Spans(), 0, steps=1)
    bad.hook(1, {"loss": float("nan"), "nonfinite": 0})
    bad.close()
    assert bad.nonfinite == 1
