"""A second architecture is added with files alone.

``data/toy/`` holds an architecture that the harness does not know
(``architectures/toy_moe.py``: a leading dense group, then layers with a
4-expert top-2 routed MLP, a shared expert and a router bias; its plain
reference in the same file) and its configuration's limits
(``limits/toy-moe.json``). Handed that directory, the functions ``run.py``
calls make its tree from a seed, check its layout, run its reference and
hold its int8 control to its own limits; no file of the benchmark changes.
The ``qwen2`` tree and reference are what they were before architectures
were found by name: digests recorded from the parent commit (PR 30's
tree, this container's CPU backend) still match.
"""

import hashlib
import inspect
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import architectures, correct, weights
from benchmark.system import worker_options

HERE = Path(__file__).resolve().parent
TOY = HERE / "data" / "toy"
TOY_CFG = {
    "name": "toy-moe", "architecture": "toy_moe",
    "hidden_size": 128, "head_dim": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 384, "moe_intermediate_size": 96, "n_routed_experts": 4,
    "num_experts_per_tok": 2, "n_shared_experts": 1, "first_k_dense_replace": 1,
    "num_hidden_layers": 4, "vocab_size": 1024, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "routed_scaling_factor": 2.5, "tie_word_embeddings": False,
}
SEEDS = (11, 2_147_483_659, 3_000_000_019)
QWEN2_CFGS = {
    "rehearsal": json.loads((HERE.parent / "rehearsal.json").read_text())["model"],
    "tied_with_bias": {
        "model_type": "qwen2", "hidden_size": 256, "intermediate_size": 704,
        "num_hidden_layers": 4, "num_attention_heads": 4, "num_key_value_heads": 2,
        "vocab_size": 2048, "rms_norm_eps": 1e-6, "rope_theta": 1_000_000.0,
        "tie_word_embeddings": True,
    },
}
# (tree, reference's logits) at seed 2147483659, from the parent commit.
PARENT_DIGESTS = {
    "rehearsal": (
        "334e0c0ca30e5a5a1fc110155ebadb59c9851f1105593f2192029d8271534978",
        "9792bf27e439a573bdeb943139b53d927213a4387ba78031481a22a1798a97a8",
    ),
    "tied_with_bias": (
        "f10bca745680f59ac14321c6a5f80f7cebd6bc38691904750531d9827d66b05b",
        "8099ca7f37de8c9d2c2c3ba83d5e43d778b76df3cdd2b6813062bc45fe2203a0",
    ),
}


def tree_from_seed(arch, cfg, seed):
    shapes = arch.tree_shapes(cfg)
    shard = jax.tree.map(
        lambda _: jax.sharding.SingleDeviceSharding(jax.devices()[0]), shapes,
        is_leaf=lambda x: isinstance(x, tuple),
    )
    return weights.make_weights(arch, cfg, seed, shard)


def layout(shapes):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16), shapes,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def test_the_toy_is_found_in_the_directory_handed_over_and_nowhere_else():
    arch = architectures.of(TOY_CFG, TOY)
    assert Path(arch.__file__) == TOY / "architectures" / "toy_moe.py"
    assert all(hasattr(arch, m) for m in architectures.MEMBERS)
    with pytest.raises(RuntimeError, match="toy_moe"):
        architectures.of(TOY_CFG)  # the benchmark's own directory does not have it
    assert Path(architectures.of({}).__file__) == HERE.parent / "architectures" / "qwen2.py"


def test_every_leaf_of_the_toy_tree_is_made_and_none_is_trivial():
    arch = architectures.of(TOY_CFG, TOY)
    params = tree_from_seed(arch, TOY_CFG, 5)
    shapes = arch.tree_shapes(TOY_CFG)
    assert set(params) == set(shapes) and {"dense_layers", "moe_layers"} <= set(params)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    assert len(leaves) == 3 + 9 + 14
    for path, leaf in leaves:
        x = np.asarray(leaf.astype(jnp.float32))
        name = path[-1].key
        centre = 1.0 if arch.init_rule(name) == "norm" else 0.0
        assert leaf.dtype == jnp.bfloat16 and np.abs(x - centre).mean() > 0.02, name
        if x.shape[0] > 1 and len(path) > 1:  # the layers of a stack differ
            assert np.abs(x[0] - x[1]).mean() > 0.02, name
    assert np.abs(np.asarray(params["moe_layers"]["router_bias"], np.float32)).mean() > 0.05
    other = tree_from_seed(arch, TOY_CFG, 6)
    assert not np.array_equal(
        np.asarray(params["dense_layers"]["q_proj"], np.float32),
        np.asarray(other["dense_layers"]["q_proj"], np.float32),
    )


def test_layout_check_passes_its_own_shapes_and_refuses_a_missing_leaf():
    arch = architectures.of(TOY_CFG, TOY)
    params = tree_from_seed(arch, TOY_CFG, 5)
    shapes = arch.tree_shapes(TOY_CFG)
    weights.check_same_layout(params, layout(shapes))
    del shapes["moe_layers"]["router_bias"]
    with pytest.raises(RuntimeError, match="layout"):
        weights.check_same_layout(params, layout(shapes))


def test_a_group_whose_leaves_are_not_stacked_alike_is_refused():
    arch = architectures.of(TOY_CFG, TOY)
    shapes = arch.tree_shapes(TOY_CFG)
    shapes["moe_layers"]["router_bias"] = (2, 4)

    class Ragged:
        init_rule = staticmethod(arch.init_rule)
        tree_shapes = staticmethod(lambda cfg: shapes)

    with pytest.raises(ValueError, match="moe_layers"):
        weights.make_weights(Ragged, TOY_CFG, 5, None)


@pytest.mark.parametrize("seed", SEEDS)
def test_toy_reference_agrees_with_itself_and_its_int8_control_is_not_correct(seed):
    limits, source = correct.load_limits(TOY_CFG["name"], TOY)
    assert source == "toy/limits/toy-moe.json" and limits["logit_err"] == 0.012
    arch = architectures.of(TOY_CFG, TOY)
    params = tree_from_seed(arch, TOY_CFG, seed)
    ids = correct.prompt_ids(seed, 0, 96)

    def logits(control=None):
        return np.asarray(arch.forward_logits(params, TOY_CFG, ids, list(range(88, 96)), control))

    ref, again, ctrl = logits(), logits(), logits("int8w")

    def numbers(program):
        return {"rows": [{
            "logit_err": correct.logit_err(program, ref),
            "served_regret": correct.regret(ref, program.argmax(axis=1)),
            "repeat_diff": 0.0,
        }]}

    assert np.array_equal(ref, again)
    assert correct.verdict(numbers(again), limits)["correct"] is True
    v = correct.verdict(numbers(ctrl), limits)
    assert v["correct"] is False and v["compared"]["logit_err"]["value"] > 0.03


def test_a_configuration_without_a_limits_file_falls_back_to_limits_json():
    limits, source = correct.load_limits("a-configuration-with-no-file-of-its-own")
    assert source == "benchmark/limits.json"
    assert limits == json.loads((HERE.parent / "limits.json").read_text())["limits"]
    assert set(limits) == {"logit_err", "repeat_diff", "served_regret"}


def digest(arrays):
    h = hashlib.sha256()
    for name, x in arrays:
        h.update(name.encode())
        h.update(str(x.dtype).encode())
        h.update(str(x.shape).encode())
        h.update(np.asarray(x.astype(jnp.float32)).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(QWEN2_CFGS))
def test_qwen2_tree_and_reference_are_what_the_parent_made(name):
    cfg = QWEN2_CFGS[name]
    seed = 2_147_483_659
    arch = architectures.of(cfg)
    params = tree_from_seed(arch, cfg, seed)
    leaves = sorted(
        (jax.tree_util.keystr(p), x) for p, x in jax.tree_util.tree_leaves_with_path(params)
    )
    ids = correct.prompt_ids(seed, 0, 96)
    logits = arch.forward_logits(params, cfg, ids, list(range(88, 96)), None)
    tree_digest = digest(leaves)
    logits_digest = hashlib.sha256(np.asarray(logits, np.float32).tobytes()).hexdigest()
    assert (tree_digest, logits_digest) == PARENT_DIGESTS[name], jax.__version__


ENGINES = {
    "qwen2.5-3b-bf16": {"dtype": "bfloat16", "max_num_seqs": 128},
    "qwen2.5-7b-bf16-tp4": {"dtype": "bfloat16", "tensor_parallel": 4, "max_model_len": 4096},
}


def fake_build(model, queue, *, tensor_parallel=None, max_num_seqs=None, max_model_len=None,
               dtype="bfloat16", kv_dtype=None, prefill_chunk_size=None):
    """Stands for ``build_tpu_worker``'s signature."""


@pytest.mark.parametrize("config", sorted(ENGINES))
def test_the_worker_gets_every_option_of_engine_and_no_comment(config):
    engine = json.loads((HERE.parent / "configs" / f"{config}.json").read_text())["engine"]
    assert worker_options(engine, inspect.signature(fake_build)) == ENGINES[config]


def test_an_option_the_old_harness_dropped_is_passed_and_an_unknown_key_is_named():
    sig = inspect.signature(fake_build)
    engine = {"kv_dtype": "fp8", "prefill_chunk_size": 512, "prefill_chunk_size_why": "..."}
    assert worker_options(engine, sig) == {"kv_dtype": "fp8", "prefill_chunk_size": 512}
    with pytest.raises(SystemExit, match="kv_dtpe"):
        worker_options({"kv_dtpe": "fp8"}, sig)
    with pytest.raises(SystemExit, match="model"):
        worker_options({"model": "preset://other"}, sig)  # positional: not an option
