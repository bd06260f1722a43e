"""Weights of a run, made by the benchmark on the device from ``--seed``.

One jitted call makes the whole tree in the type it is served in (bf16),
already placed on the shards the program asked for. The stacked layer
weights are made one layer at a time inside that call (``lax.map``), so
the float32 transient is one layer's, not the model's. The tree has the
layout the program's ``Transformer`` reads (``embed``, ``layers/<name>``
stacked on a leading layer axis, ``final_norm``, ``lm_head`` where the
embedding is not tied); shapes are computed here from the configuration
file and checked against the program's own tree before they replace it.

Norm weights are 1 + 0.1 n and biases 0.1 n (n standard normal): with the
program's own ones and zeros a reference could drop the bias or the norm
weight and still agree.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp


def tree_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Leaf shapes of the served tree for an HF-style configuration."""
    H = int(cfg["hidden_size"])
    L = int(cfg["num_hidden_layers"])
    nh = int(cfg["num_attention_heads"])
    nkv = int(cfg["num_key_value_heads"])
    d = int(cfg.get("head_dim") or H // nh)
    I = int(cfg["intermediate_size"])
    V = int(cfg["vocab_size"])
    shapes = {
        "embed": (V, H),
        "final_norm": (H,),
        "layers": {
            "ln1": (L, H),
            "ln2": (L, H),
            "q_proj": (L, H, nh * d),
            "k_proj": (L, H, nkv * d),
            "v_proj": (L, H, nkv * d),
            "o_proj": (L, nh * d, H),
            "gate_proj": (L, H, I),
            "up_proj": (L, H, I),
            "down_proj": (L, I, H),
        },
    }
    if cfg.get("attention_bias", cfg.get("model_type") == "qwen2"):
        shapes["layers"].update(
            q_bias=(L, nh * d), k_bias=(L, nkv * d), v_bias=(L, nkv * d)
        )
    if not cfg.get("tie_word_embeddings", False):
        shapes["lm_head"] = (H, V)
    return shapes


def _leaf(key, name: str, shape, dtype):
    if name in ("ln1", "ln2", "final_norm"):
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if name.endswith("_bias"):
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    fan_in = shape[-1] if name == "embed" else shape[-2]

    def normal(k, shp):
        return (jax.random.normal(k, shp, jnp.float32) / math.sqrt(fan_in)).astype(dtype)

    if name in ("embed", "lm_head"):
        # The vocabulary in eighths, so that the float32 transient is an
        # eighth of a table that is gigabytes wide.
        axis = 0 if name == "embed" else 1
        V = shape[axis]
        n = 8 if V % 8 == 0 else 1
        part = (V // n, shape[1]) if axis == 0 else (shape[0], V // n)
        parts = jax.lax.map(lambda k: normal(k, part), jax.random.split(key, n))
        if axis == 1:
            parts = jnp.moveaxis(parts, 0, 1)
        return parts.reshape(shape)
    return normal(key, shape)


def make_weights(cfg: Dict[str, Any], seed: int, shardings, dtype=jnp.bfloat16):
    """The tree for ``seed``, placed by ``shardings`` (a matching tree)."""
    shapes = tree_shapes(cfg)
    layer_names = sorted(shapes["layers"])
    L = int(cfg["num_hidden_layers"])

    def build(key):
        k_top, k_layers = jax.random.split(key)
        top_names = sorted(n for n in shapes if n != "layers")
        top_keys = jax.random.split(k_top, len(top_names))
        out = {
            n: _leaf(k, n, shapes[n], dtype) for n, k in zip(top_names, top_keys)
        }

        def one_layer(k):
            ks = jax.random.split(k, len(layer_names))
            return {
                n: _leaf(kk, n, shapes["layers"][n][1:], dtype)
                for n, kk in zip(layer_names, ks)
            }

        out["layers"] = jax.lax.map(one_layer, jax.random.split(k_layers, L))
        return out

    # Seeds run to a little over 2**31: fold the halves into the key.
    key = jax.random.fold_in(jax.random.key(int(seed) & 0x7FFFFFFF), int(seed) >> 31)
    return jax.jit(build, out_shardings=shardings)(key)


def check_same_layout(ours, theirs) -> None:
    """Refuse to serve a tree whose layout the program would not read."""
    a = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), ours)
    b = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), theirs)
    if a != b:
        raise RuntimeError(
            "the benchmark's weight tree does not have the layout of the "
            f"program's: ours {a} program's {b}"
        )
