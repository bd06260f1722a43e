"""The comparison that decides ``correct`` has to fail its controls.

On the chip ``calibrate.py`` reads the served program and the controls at
the cells' own sizes (PERF.md gives the readings). This is the same
comparison at a size a test run can hold, with the ``qwen2`` reference
standing for the program's direct logits: the reference computed with int8
weights, or with an fp8 cache, in the program's place must come out as
not correct under the limits of ``limits.json``; the reference itself
must come out correct.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, weights
from benchmark.architectures import qwen2

CFG = {
    "model_type": "qwen2", "hidden_size": 256, "intermediate_size": 704,
    "num_hidden_layers": 4, "num_attention_heads": 4, "num_key_value_heads": 2,
    "vocab_size": 2048, "rms_norm_eps": 1e-6, "rope_theta": 1_000_000.0,
    "tie_word_embeddings": True,
}
SEEDS = (11, 2_147_483_659, 3_000_000_019)
LIMITS, _ = correct.load_limits("qwen2.5-3b-bf16")  # limits.json: it has no file of its own


def logits(seed, control):
    shapes = qwen2.tree_shapes(CFG)
    shard = jax.tree.map(
        lambda _: jax.sharding.SingleDeviceSharding(jax.devices()[0]), shapes,
        is_leaf=lambda x: isinstance(x, tuple),
    )
    params = weights.make_weights(qwen2, CFG, seed, shard)
    ids = correct.prompt_ids(seed, 0, 96)
    positions = list(range(88, 96))
    return np.asarray(qwen2.forward_logits(params, CFG, ids, positions, control))


def numbers(program, ref):
    return {"rows": [{
        "logit_err": correct.logit_err(program, ref),
        "served_regret": correct.regret(ref, program.argmax(axis=1)),
        "repeat_diff": 0.0,
    }]}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("control", ["int8w", "fp8kv"])
def test_control_is_not_correct(seed, control):
    ref, ctrl = logits(seed, None), logits(seed, control)
    check = numbers(ctrl, ref)
    assert not correct.verdict(check, LIMITS)["correct"], check


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_agrees_with_itself(seed):
    ref = logits(seed, None)
    check = numbers(ref, ref)
    assert correct.verdict(check, LIMITS)["correct"]
    assert check["rows"][0]["logit_err"] == 0.0 and check["rows"][0]["served_regret"] == 0.0


def test_regret_is_the_worst_row_in_units_of_the_spread():
    x = np.array([[0.0, 2.0, -2.0], [1.0, -1.0, 0.0]])
    spread = correct.spread(x)  # row variances 8/3 and 2/3
    assert spread == pytest.approx(np.sqrt((8 / 3 + 2 / 3) / 2))
    assert correct.regret(x, [1, 0]) == 0.0
    assert correct.regret(x, [0, 0]) == pytest.approx(2.0 / spread)
    assert correct.regret(x, [1, 1]) == pytest.approx(2.0 / spread)


def test_weights_follow_the_seed():
    a, b = logits(11, None), logits(12, None)
    assert np.abs(a - b).max() > 0.1
    assert np.array_equal(a, logits(11, None))


def test_a_dropped_bias_or_norm_weight_would_show():
    # make_weights gives biases and norm weights that are not 0 and 1
    shapes = qwen2.tree_shapes(CFG)
    shard = jax.tree.map(
        lambda _: jax.sharding.SingleDeviceSharding(jax.devices()[0]), shapes,
        is_leaf=lambda x: isinstance(x, tuple),
    )
    p = weights.make_weights(qwen2, CFG, 5, shard)
    assert float(jnp.abs(p["layers"]["q_bias"].astype(jnp.float32)).mean()) > 0.05
    assert float(jnp.abs(p["layers"]["ln1"].astype(jnp.float32) - 1).mean()) > 0.05
